"""1 minus the union of device 0's operation intervals over the traced
window."""

import trace_reduce


def read(run):
    win = trace_reduce.traced_window(run)
    if win is None:
        return None
    busy = trace_reduce.busy_seconds(trace_reduce.device_ops(run), *win)
    return 100.0 * (1.0 - busy / (win[1] - win[0]))
