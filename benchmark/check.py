"""The comparison that decides ``correct`` for a training cell.

What is compared is what the timed object produced in its first steps,
driven through the window's own call and feed, against the plain
reference following the same steps from the same weights and rows:

``loss_gap_step<i>``  |loss - ref| / |ref| of step i;
``grad_norm_gap``     the first gradient as the optimiser gets it (from
                      Adam's first moment after one step), worst leaf;
``change_norm_gap``   the parameters' change over the steps, worst leaf,
                      over the leaves whose reference gradient is at least
                      a thousandth of the median leaf's;
``grad_norm_gap_median`` and ``change_norm_gap_median``: the same
                      gaps, of the median leaf;
``grad_diff_median``  the norm of the difference between the program's
                      first gradient and the reference's, over the
                      reference's norm, of the median leaf: what rounding
                      each element does, which the norms' gap averages out;
``rows_repeated``     rows among the compared batches that are not
                      distinct (exact: 0);
``rows_altered``      rows that the program's loader handed over and that
                      are not, value for value, rows of the traffic mix
                      (exact: 0). The reference is fed the mix's own rows.

A leaf's gap is the gap between the program's norm and the reference's,
against the reference's norm of that leaf or of the median leaf,
whichever is larger. Each number has its own limit, in the cell's file.
"""

from __future__ import annotations

import math
import statistics


def leaf_gaps(prog: dict, ref: dict, leaves=None):
    """[(gap, leaf, program's norm, reference's norm), ...], worst first."""
    median = statistics.median(ref.values())
    out = []
    for k in (leaves if leaves is not None else ref):
        gap = abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
        out.append((gap if math.isfinite(gap) else float("inf"), k,
                    prog[k], ref[k]))
    return sorted(out, reverse=True)


def worst_leaf_gap(prog: dict, ref: dict, leaves=None):
    """(worst leaf's gap, median leaf's gap, "leaf prog/ref (median)" of
    the worst) among ``leaves`` (all by default)."""
    gaps = leaf_gaps(prog, ref, leaves)
    gap, k, p, r = gaps[0]
    median = statistics.median(ref.values())
    return (gap, statistics.median(g[0] for g in gaps),
            f"{k} {p:.6g}/{r:.6g} (median {median:.6g})")


def moving_leaves(ref_grad_norms: dict):
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's. The others move under Adam by
    round-off alone and are left out of the change."""
    median = statistics.median(ref_grad_norms.values())
    return [k for k, v in ref_grad_norms.items() if v >= 1e-3 * median]


def diff_norms(prog_grad: dict, ref_grad: dict) -> dict:
    """Per leaf, the norm of the difference of two gradients."""
    import jax
    import jax.numpy as jnp

    diff = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in b})
    return {k: float(v) for k, v in diff(prog_grad, ref_grad).items()}


def readings(prog: dict, ref: dict) -> dict:
    """Every number compared, and where the worst leaf was."""
    out, where = {}, {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        gap = abs(lp - lr) / max(abs(lr), 1e-30)
        out[f"loss_gap_step{i + 1}"] = gap if math.isfinite(gap) else float("inf")
    (out["grad_norm_gap"], out["grad_norm_gap_median"],
     where["grad_norm_gap"]) = worst_leaf_gap(
        prog["grad_norms"], ref["grad_norms"])
    (out["change_norm_gap"], out["change_norm_gap_median"],
     where["change_norm_gap"]) = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"],
        moving_leaves(ref["grad_norms"]))
    if "grad" in prog and "grad" in ref:
        diff = diff_norms(prog["grad"], ref["grad"])
        shares = sorted(diff[k] / max(ref["grad_norms"][k], 1e-30) for k in diff)
        out["grad_diff_median"] = statistics.median(shares)
        out["grad_diff_worst"] = shares[-1]
    for name in ("rows_repeated", "rows_altered"):
        if name in prog:
            out[name] = float(prog[name])
    return {"numbers": out, "where": where}


def judge(numbers: dict, limits: dict):
    """(correct, [(name, number, limit), ...]). A number with no limit in
    the cell's file is shown and not compared; a limit whose number is
    missing fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        if value is None or not (value <= limit):
            ok = False
        rows.append((name, value, limit))
    for name, value in numbers.items():
        if name not in limits:
            rows.append((name, value, None))
    return ok, rows


def rows_repeated(batches) -> int:
    """How many rows of the compared batches repeat an earlier one."""
    seen, repeated = set(), 0
    for batch in batches:
        for row in batch["image"]:
            key = (float(row[::16, ::16].sum()), row[:4, :64].tobytes())
            if key in seen:
                repeated += 1
            seen.add(key)
    return repeated
