"""The least time the chip could take for one step's matrix products
(the configuration's reference walks them, ``matmul_layers``:
projections, the scan's chunk products, causal attention, the shared
expert, the routed experts over the rows the run's ``moe_rows_routed``
counted (the uniform share where it counted none), head; forward and both
gradients;
per product the larger of logical FLOPs over the peak and least bytes over
the memory bandwidth) over the traced device time of the operations that
carry matrix products in one step: XLA's output fusions and convolutions
and every custom call (``trace_reduce.is_conv``: on a TPU a dot is an
output fusion), median over the step-program runs wholly inside the traced
window, device 0. A configuration whose reference has no such walk gives
nothing to read."""

import flops
import trace_reduce


def read(run):
    if run["rehearsal"] or not run["peak"]:
        return None
    ref = flops.load_reference(run["config"])
    if not hasattr(ref, "matmul_roofline_seconds") or "seq_len" not in run:
        return None
    steps = trace_reduce.whole_steps(run)
    if not steps:
        return None
    busy = trace_reduce.median(
        [trace_reduce.category_seconds(s, trace_reduce.is_conv) for s in steps])
    if not busy:
        return None
    least = ref.matmul_roofline_seconds(
        run["config"], run["seq_len"], run["batch"] // run["chips"], run["peak"],
        routed_rows=run.get("routed_rows"))
    return 100.0 * least / busy
