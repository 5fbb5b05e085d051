"""The least time the chip could take for one step's causal attention
products (the configuration's reference walks them, ``matmul_layers``:
every block's ``*/scores`` and ``*/values`` over the causal pairs, forward
and both gradients, logical FLOPs over the peak: attention takes its
compute bound, as in ``matmul_roofline``) over the traced device time a
step of the operations that carry the fused attention kernel: custom calls
whose instruction is named for the program's ``pallas_call``s
(``causal_attention_fwd``, ``causal_attention_bwd``; a recomputed forward
is in the measured time and not in the least time), median over the
step-program runs wholly inside the traced window, device 0. A step with
no such operation (a program whose attention is plain XLA, where nothing
tells its fusions from the other products') gives nothing to read."""

import flops
import trace_reduce

KERNEL_PREFIX = "causal_attention_"


def is_attention_kernel(op) -> bool:
    return op.category == "custom-call" and op.name.startswith(KERNEL_PREFIX)


def read(run):
    if run["rehearsal"] or not run["peak"]:
        return None
    ref = flops.load_reference(run["config"])
    if not hasattr(ref, "matmul_layers") or "seq_len" not in run:
        return None
    steps = trace_reduce.whole_steps(run)
    if not steps:
        return None
    busy = trace_reduce.median(
        [trace_reduce.category_seconds(s, is_attention_kernel) for s in steps])
    if not busy:
        return None
    walk = ref.matmul_layers(run["config"], run["seq_len"],
                             run["batch"] // run["chips"])
    least = sum(m.train_flops for m in walk
                if m.name.endswith(("/scores", "/values")))
    return 100.0 * least / run["peak"]["bf16_flops"] / busy
