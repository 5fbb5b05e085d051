"""Rows that the grouped expert product multiplied beyond the rows routed
to the held experts, as a share of those: ``moe_rows_computed`` /
``moe_rows_routed`` - 1, summed over the expert blocks and over the steps
of the untraced rest of the window (the whole window of an untraced run).
Both are counted inside the compiled step and ride back with its loss. A
program that counts neither gives nothing to read."""


def read(run):
    counted = run.get("counters") or {}
    routed = counted.get("moe_rows_routed")
    if not routed or "moe_rows_computed" not in counted:
        return None
    return 100.0 * (counted["moe_rows_computed"] / routed - 1.0)
