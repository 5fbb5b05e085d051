"""Time in collective operations during which no other operation runs on
device 0, per run of the step program (median over the runs wholly inside
the traced window). Nothing to read where the step has no collective."""

import trace_reduce


def read(run):
    steps = trace_reduce.whole_steps(run)
    if not steps or not any(trace_reduce.is_collective(o) for s in steps for o in s):
        return None
    return 1e3 * trace_reduce.median(
        [trace_reduce.exposed_seconds(s, trace_reduce.is_collective) for s in steps])
