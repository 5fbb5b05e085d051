"""Device time from the first to the last operation of one run of the
step program, median over the runs wholly inside the traced window
(device 0)."""

import trace_reduce


def read(run):
    step_s = trace_reduce.median_step_seconds(run)
    return None if step_s is None else 1e3 * step_s
