"""How far the fullest held expert stands above the mean: the rows of the
fullest held expert (``moe_rows_max_expert``) over the mean rows of a held
expert (``moe_rows_routed`` over the experts held, which the
configuration's reference states: ``held_experts``), less 1, summed over
the sparse layers and over the steps of the untraced rest of the window
(the whole window of an untraced run). What a balanced router leaves for
the longest run of tiles of one expert: 0 where every held expert gets the
same rows. All three are counted inside the compiled step and ride back
with its loss. A program that counts neither, or a configuration whose
reference does not say how many experts are held, gives nothing to read."""

import flops


def read(run):
    counted = run.get("counters") or {}
    routed, fullest = (counted.get("moe_rows_routed"),
                       counted.get("moe_rows_max_expert"))
    held = getattr(flops.load_reference(run["config"]), "held_experts", None)
    if not routed or fullest is None or held is None:
        return None
    return 100.0 * (fullest * held(run["config"]) / routed - 1.0)
