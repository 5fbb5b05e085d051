"""The least time the chip could take for the configuration's
convolutions in one step (``flops.conv_roofline_seconds``: per
convolution, forward and both gradients, the larger of logical FLOPs over
the peak and least bytes over the memory bandwidth) over the traced
device time of the operations that carry them in one step (XLA's
categories ``convolution`` and ``convolution fusion``, and every custom
call, which is what a Pallas kernel in a convolution's place is: the
least time is of all the model's convolutions, so the time has to be of
all that carry them; median over the step-program runs wholly inside the
traced window, device 0)."""

import flops
import trace_reduce


def read(run):
    if run["rehearsal"] or not run["peak"]:
        return None
    steps = trace_reduce.whole_steps(run)
    if not steps:
        return None
    conv_s = trace_reduce.median(
        [trace_reduce.category_seconds(s, trace_reduce.is_conv) for s in steps])
    if not conv_s:
        return None
    least = flops.conv_roofline_seconds(
        run["config"], run["batch"] // run["chips"], run["peak"])["seconds"]
    return 100.0 * least / conv_s
