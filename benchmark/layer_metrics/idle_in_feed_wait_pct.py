"""The share of device 0's idle time inside the traced window during
which the step loop waited for the feed: the idle intervals (the traced
window less the union of the device's operations) intersected with the
program's ``feed_wait`` spans, shifted onto the profiler's clock by the
``bench_sync`` annotation, over the idle seconds. What is left is idle
while the loop had a batch in hand. Nothing to read without a device
trace, without ``feed_wait`` spans, or on a device that never idled."""

import trace_reduce


def read(run):
    win = trace_reduce.traced_window(run)
    waits = [s for s in run["spans"] if s["phase"] == "feed_wait"]
    if win is None or not waits:
        return None
    ops = trace_reduce.device_ops(run)
    idle = (win[1] - win[0]) - trace_reduce.busy_seconds(ops, *win)
    if idle <= 0.0:
        return None
    shift = win[0] - run["window"]["traced"][0]
    in_wait = 0.0
    for s in waits:
        t0, t1 = max(s["t0"] + shift, win[0]), min(s["t1"] + shift, win[1])
        if t1 > t0:
            in_wait += (t1 - t0) - trace_reduce.busy_seconds(ops, t0, t1)
    return 100.0 * in_wait / idle
