#!/usr/bin/env python3
"""Kernel-policy A/B: ``--kernels xla`` vs ``--kernels pallas``, PER
PHASE — which phase each kernel buys back, measured.

The measurement side of docs/PERFORMANCE.md "Kernels". Each phase is one
engagement site, A/B'd as an (xla, pallas) cell pair on otherwise
identical programs:

* ``train_loss`` — the full unet train step (fwd+bwd+Adam) with the XLA
  loss vs the fused one-pass stats kernel + analytic VJP
  (ops/fused_loss.py);
* ``epilogue``   — the milesial (BatchNorm) train step with the XLA
  BN-normalize+ReLU vs the fused conv-epilogue kernel + hand-written
  VJP (ops/kernels.fused_bn_act);
* ``eval_stats`` — the eval step's loss+Dice via separate XLA
  reductions vs the one-pass stats kernel (ops/pallas_kernels.py);
* ``serve_mask`` — the serve forward returning f32 probabilities + the
  host numpy threshold pass vs the fused sigmoid/threshold mask kernel
  inside the executable (uint8 D2H). The xla cell's ``step_ms``
  INCLUDES its host postprocess — that is the honest end-to-end A/B.

Every cell records compile_s / step_ms / imgs_per_sec, so the summary's
per-phase speedups attribute the win (or loss) to the phase that earned
it. A priors file (tools/probe_kernels.py) marks Mosaic-rejected cells
``skipped: mosaic_rejected`` instead of burning budget on a compile the
chip already refused.

Callable in-process (``kernel_sweep(budget_s=...)``) — registered as the
``kernel_sweep`` bench_multi config (budget-aware, single-device,
collective-free → the static preflight's no-combos fast path), ordered
after the kernel_probe leg.

Usage: python tools/bench_kernels.py [--batch 4] [--hw 640 960]
       [--widths 32 64 128 256] [--steps 5] [--priors kernel_priors.json]
       [--json out.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

#: phase → the probe-registry kernel its pallas cell engages (what a
#: priors rejection skips).
PHASE_KERNELS = {
    "train_loss": "fused_loss",
    "epilogue": "conv_epilogue",
    "eval_stats": "eval_stats",
    "serve_mask": "serve_mask",
}


def _rejected(priors, phase) -> str:
    """The Mosaic reject reason for this phase's kernel, or ''."""
    if not priors:
        return ""
    row = (priors.get("kernels") or {}).get(PHASE_KERNELS[phase])
    if isinstance(row, dict) and not row.get("accepted", True):
        return row.get("reason", "no reason recorded")
    return ""


def kernel_sweep(
    batch: int = 4,
    hw=(64, 96),
    widths=(8, 16),
    steps: int = 3,
    budget_s: float = 0.0,
    priors=None,
    emit=None,
) -> dict:
    """The phase × kernels grid at fixed batch. Returns a summary dict
    (also the bench_multi row) and emits one dict per cell through
    ``emit``. ``budget_s`` > 0 stops opening new cells near the wall
    budget — measured cells keep their rows (the chip-window
    contract)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedpytorch_tpu.models.milesial import (
        MilesialUNet,
        init_milesial,
    )
    from distributedpytorch_tpu.models.unet import UNet, init_unet_params
    from distributedpytorch_tpu.serve.infer import (
        make_forward,
        postprocess_mask,
    )
    from distributedpytorch_tpu.train.steps import (
        create_train_state,
        make_eval_step,
        make_train_step,
    )

    t_start = time.monotonic()
    h, w = hw
    rng = np.random.default_rng(0)
    batch_np = {
        "image": rng.random((batch, h, w, 3), dtype=np.float32),
        "mask": (rng.random((batch, h, w)) > 0.5).astype(np.int32),
    }
    rows, cells = [], []

    def record(row):
        rows.append(row)
        if "skipped" not in row:
            cells.append(row)
        if emit is not None:
            emit(row)

    def over_budget(frac):
        return budget_s and time.monotonic() - t_start > frac * budget_s

    def timed(compiled, first_args, next_args_fn, row):
        """First call (warms allocator) + `steps` timed calls."""
        try:
            out = compiled(*first_args)
            jax.block_until_ready(out)
            args = next_args_fn(out)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = compiled(*args)
                args = next_args_fn(out)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / steps
            row["step_ms"] = round(dt * 1e3, 2)
            row["imgs_per_sec"] = round(batch / dt, 1)
        except Exception as exc:  # noqa: BLE001 — recorded, cell survives
            row["exec_error"] = f"{type(exc).__name__}: {exc}"
        return row

    def cell(phase, kernels, build):
        """One (phase, kernels) cell: build() -> (compiled, first_args,
        next_args_fn, extra_row_fields)."""
        row = {"kind": "kernel_cell", "phase": phase, "kernels": kernels,
               "batch": batch, "hw": list(hw)}
        if over_budget(0.85):
            row["skipped"] = "budget"
            return record(row)
        if kernels == "pallas":
            reason = _rejected(priors, phase)
            if reason:
                row.update(skipped="mosaic_rejected", reason=reason)
                return record(row)
        try:
            t0 = time.monotonic()
            compiled, first_args, next_args_fn, extra = build()
            row["compile_s"] = round(time.monotonic() - t0, 2)
            row.update(extra)
        except Exception as exc:  # noqa: BLE001 — a compile rejection is
            # a result row (the probe registry's contract), not a crash
            row["compile_error"] = f"{type(exc).__name__}: {exc}"
            return record(row)
        record(timed(compiled, first_args, next_args_fn, row))

    # -- phase: train_loss (unet, fused loss stats) -------------------------
    def build_train(use_fused):
        from distributedpytorch_tpu.ops.fused_loss import fused_bce_dice_loss

        model = UNet(dtype=jnp.bfloat16, widths=tuple(widths))
        params = init_unet_params(model, jax.random.key(0), input_hw=(h, w))
        state, tx = create_train_state(params, 1e-4)
        step = jax.jit(make_train_step(
            model, tx, batch_size=batch,
            loss_impl=fused_bce_dice_loss if use_fused else None,
        ))
        placed = {k: jnp.asarray(v) for k, v in batch_np.items()}
        compiled = step.lower(state, placed).compile()
        return compiled, (state, placed), lambda out: (out[0], placed), {}

    cell("train_loss", "xla", lambda: build_train(False))
    cell("train_loss", "pallas", lambda: build_train(True))

    # -- phase: epilogue (milesial DoubleConv BN+ReLU) ----------------------
    def build_epilogue(fused):
        mw = tuple(widths) + (4 * widths[-1],)  # ≥2 widths → ≥1 Down level
        model = MilesialUNet(
            widths=mw, dtype=jnp.bfloat16, s2d_levels=0,
            conv_epilogue=fused,
        )
        params, stats = init_milesial(model, jax.random.key(0),
                                      input_hw=(h, w))
        state, tx = create_train_state(params, 1e-4, model_state=stats)
        step = jax.jit(make_train_step(model, tx, batch_size=batch))
        placed = {k: jnp.asarray(v) for k, v in batch_np.items()}
        compiled = step.lower(state, placed).compile()
        return compiled, (state, placed), lambda out: (out[0], placed), {}

    cell("epilogue", "xla", lambda: build_epilogue(False))
    cell("epilogue", "pallas", lambda: build_epilogue(True))

    # -- phase: eval_stats (one-pass loss+Dice) -----------------------------
    def build_eval(use_pallas):
        model = UNet(dtype=jnp.bfloat16, widths=tuple(widths))
        params = init_unet_params(model, jax.random.key(0), input_hw=(h, w))
        step = jax.jit(make_eval_step(model, use_pallas=use_pallas))
        placed = {k: jnp.asarray(v) for k, v in batch_np.items()}
        compiled = step.lower(params, placed).compile()
        return compiled, (params, placed), lambda out: (params, placed), {}

    cell("eval_stats", "xla", lambda: build_eval(False))
    cell("eval_stats", "pallas", lambda: build_eval(True))

    # -- phase: serve_mask (device threshold vs host postprocess) -----------
    def build_serve(mask_kernel):
        model = UNet(dtype=jnp.float32, widths=tuple(widths))
        params = init_unet_params(model, jax.random.key(0), input_hw=(h, w))
        variables = {"params": params}
        fwd = jax.jit(make_forward(
            model, mask_threshold=0.5 if mask_kernel else None,
        ))
        x = jnp.asarray(batch_np["image"])
        compiled = fwd.lower(variables, x).compile()
        if mask_kernel:
            def run(v, xx):
                return np.asarray(compiled(v, xx))  # uint8 masks D2H
        else:
            def run(v, xx):
                # the honest xla cell: probs D2H + the host threshold
                return postprocess_mask(np.asarray(compiled(v, xx)), 0.5)
        return run, (variables, x), lambda out: (variables, x), {}

    cell("serve_mask", "xla", lambda: build_serve(False))
    cell("serve_mask", "pallas", lambda: build_serve(True))

    # -- summary: per-phase attribution -------------------------------------
    by = {(r["phase"], r["kernels"]): r for r in cells}
    summary = {"kind": "kernel_sweep", "batch": batch, "hw": list(hw),
               "widths": list(widths), "rows": rows}
    for phase in PHASE_KERNELS:
        a, b = by.get((phase, "xla")), by.get((phase, "pallas"))
        if a and b and a.get("step_ms") and b.get("step_ms"):
            summary[f"{phase}_speedup"] = round(
                a["step_ms"] / b["step_ms"], 3)
    return summary


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--hw", type=int, nargs=2, default=(640, 960),
                    help="(H, W) — default the reference geometry")
    ap.add_argument("--widths", type=int, nargs="+",
                    default=(32, 64, 128, 256))
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--priors", default=None,
                    help="Mosaic probe priors file (tools/probe_kernels."
                         "py): rejected kernels' cells are skipped")
    ap.add_argument("--json", default=None,
                    help="also append JSON lines to this file")
    args = ap.parse_args()
    if args.steps < 1:
        ap.error("--steps must be >= 1")

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    priors = None
    if args.priors:
        from distributedpytorch_tpu.ops.kernels import load_priors

        priors = load_priors(args.priors)

    records = []

    def emit(rec):
        records.append(rec)
        line = json.dumps(rec)
        print(line)
        if args.json:
            with open(args.json, "a") as f:
                f.write(line + "\n")

    summary = kernel_sweep(
        batch=args.batch, hw=tuple(args.hw), widths=tuple(args.widths),
        steps=args.steps, priors=priors, emit=emit,
    )
    emit({k: v for k, v in summary.items() if k != "rows"})

    print("\n| phase | kernels | compile s | step ms | imgs/s |")
    print("|---|---|---|---|---|")
    for r in records:
        if r.get("kind") != "kernel_cell" or "step_ms" not in r:
            continue
        print(f"| {r['phase']} | {r['kernels']} | {r.get('compile_s')} "
              f"| {r['step_ms']} | {r['imgs_per_sec']} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
