"""What the attention path multiplies and its masks throw away: the
(query, key) pairs the path that ran multiplied (the program's step counter
``attention_pairs_computed``: over the layers, the sequences and the query
heads, from the loop bounds of the kernel or the slices of the blocked
path) over the pairs inside the masks (the configuration's reference walks
them, ``matmul_layers``: every layer's ``*/scores`` over the causal pairs,
in a window layer those inside the window only), less 1, over the steps of
the untraced rest of the window (the whole window of an untraced run). The
counter rides back with the step's loss. 0 for a path that multiplies no
masked pair; a kernel that walks whole tiles pays the half-empty tile at
the diagonal and at the window's edge. A program that does not count, or a
configuration whose reference has no such walk, gives nothing to read."""

import flops


def read(run):
    counted = run.get("counters") or {}
    computed, steps = counted.get("attention_pairs_computed"), counted.get("steps")
    ref = flops.load_reference(run["config"])
    if not computed or not steps or not hasattr(ref, "matmul_layers") \
            or "seq_len" not in run:
        return None
    walk = ref.matmul_layers(run["config"], run["seq_len"],
                             run["batch"] // run["chips"])
    inside = sum(m.m * m.count for m in walk if m.name.endswith("/scores"))
    if not inside:
        return None
    return 100.0 * (computed / steps / inside - 1.0)
