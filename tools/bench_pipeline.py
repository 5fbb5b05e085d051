#!/usr/bin/env python3
"""Pipeline-schedule efficiency measurement on the 8-device virtual CPU mesh.

VERDICT r04 next-5: the (S−1)/(M+S−1) GPipe bubble was asserted from theory;
this tool produces the empirical side. What a single-host CPU mesh CAN and
CANNOT observe must be stated up front:

  * The 8 "devices" are XLA host-platform partitions of ONE machine (this
    container has 1 core), so per-device work serializes — wall-clock here
    measures TOTAL EXECUTED WORK + SCHEDULE OVERHEAD, not parallel step
    latency, and idle-device bubbles are invisible by construction. Worse,
    heavy per-stage compute starves XLA's CPU collective rendezvous (its
    40 s termination deadline aborts the process — observed on this box at
    batch 8 × 64×96 full-width), so EXECUTION legs run at tiny widths
    where the test suite already executes the same schedule.
  * What IS measured, per (S, M) ∈ {2,4} × {2,4,8}:
      (a) STRUCTURE, from the compiled HLO at representative width —
          collective-permute count vs the schedule's prediction of
          M·(S−1) forward edges (+ their reverse-permute transposes in
          the grad; XLA may fuse/split, so the check is ≥);
      (b) the per-microbatch compute curve w(M) — the plain grad step
          timed at batch B/M — the other half of "when does raising M
          pay" (smaller microbatches run less efficiently);
      (c) EXECUTION time of the full pipelined grad at tiny width; a
          per-S linear fit t(M) ≈ a·M + c exposes the serialized
          signature of the warmup/drain ticks: the S−1 non-full ticks
          contribute M-independent work, so the intercept c must grow
          with S — that intercept IS the bubble as a serialized executor
          sees it.
  * From (b) the tool PREDICTS parallel step time on a real S-device mesh
    as t(S,M) ≈ (M+S−1) · w(M)/(M·S)·M = (M+S−1)·w1(M)/S with
    w1(M)=w(M)/M the per-microbatch time (balanced stages), and reports
    theoretical efficiency M/(M+S−1) next to it. Real multi-chip
    hardware closes the loop (not measured).

A fourth leg (round 6) is the SCHEDULE sweep: M ∈ {2,4,8,16} × schedule
(gpipe vs 1f1b) at FIXED microbatch size (so the batch grows with M —
the lever 1F1B exists to unlock), recording peak memory alongside
imgs/s. Peak memory comes from two sources: XLA's buffer assignment
(`compiled.memory_analysis().temp_size_in_bytes` — available on every
backend, the traced-liveness ground truth) and the runtime's
`device.memory_stats()['peak_bytes_in_use']` (TPU only; None on the CPU
mesh). The expected signature: gpipe temp bytes grow ~linearly in M,
1f1b's stay bounded by the in-flight count (≈S). The sweep is callable
in-process (`schedule_sweep()`) so tools/bench_multi.py can run it as a
300 s chip-window config.

Usage: python tools/bench_pipeline.py [--batch 8] [--hw 64 96]
       [--steps 5] [--json out.jsonl]
Emits one JSON line per measurement and markdown tables (for
docs/DISTRIBUTED.md) on stdout.

Reference anchor: the reference's fixed m=2/s=2 pipeline
(reference model/unet_model.py:24-53) never measures its bubble either —
this grid is strictly more evidence than the reference carries.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_PROVISIONED_ENV = "_DPT_BENCH_PIPE_PROVISIONED"

GRID_S = (2, 4)
GRID_M = (2, 4, 8)
SWEEP_M = (2, 4, 8, 16)
# 1f1b first: the runtime's peak_bytes_in_use is a PROCESS-LIFETIME
# high-water mark with no reset API, so only cells measured before the
# bigger-footprint schedule runs can read their own true peak — gpipe
# after 1f1b still reads correctly (it only raises the mark), the other
# order would stamp gpipe's peak onto every 1f1b cell.
SWEEP_SCHEDULES = ("1f1b", "gpipe")


def schedule_sweep(
    stages: int = 2,
    mb_size: int = 2,
    hw=(32, 48),
    widths=(8, 16),
    steps: int = 3,
    m_grid=SWEEP_M,
    schedules=SWEEP_SCHEDULES,
    budget_s: float = 0.0,
    emit=None,
) -> dict:
    """The M × schedule grid at fixed microbatch size.

    Returns a summary dict (also the bench_multi row) and emits one dict
    per cell through ``emit`` when given. ``budget_s`` > 0 stops opening
    new cells when the wall budget is near (already-measured cells keep
    their rows — the chip-window contract bench_multi expects).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedpytorch_tpu.models.unet import UNet
    from distributedpytorch_tpu.parallel.pipeline import (
        make_pipeline_value_and_grad_fn,
    )
    from jax.sharding import Mesh

    t_start = time.monotonic()
    devices = jax.devices()
    if len(devices) < stages:
        return {
            "kind": "pipeline_schedule_sweep",
            "skipped": f"needs >= {stages} devices, have {len(devices)}",
        }
    mesh = Mesh(np.array(devices[:stages]), ("stage",))
    h, w = hw
    model = UNet(dtype=jnp.float32, s2d_levels=0, widths=tuple(widths))
    params = model.init(jax.random.key(0), jnp.zeros((1, h, w, 3)))["params"]
    rng = np.random.default_rng(0)
    rows, cells = [], []
    for schedule in schedules:
        for M in m_grid:
            if budget_s and time.monotonic() - t_start > 0.7 * budget_s:
                rows.append({"kind": "pipeline_sweep_cell",
                             "schedule": schedule, "M": M,
                             "skipped": "budget"})
                continue
            batch_n = M * mb_size
            batch = {
                "image": jnp.asarray(
                    rng.random((batch_n, h, w, 3), dtype=np.float32)),
                "mask": jnp.asarray(
                    (rng.random((batch_n, h, w, 1)) > 0.5).astype(np.float32)),
            }
            fn = make_pipeline_value_and_grad_fn(
                model, mesh, num_microbatches=M, schedule=schedule
            )
            jit_fn = jax.jit(lambda p, b, _f=fn: _f(p, None, b)[:2])
            t0 = time.monotonic()
            compiled = jit_fn.lower(params, batch).compile()
            compile_s = time.monotonic() - t0
            ma = compiled.memory_analysis()
            row = {
                "kind": "pipeline_sweep_cell",
                "schedule": schedule, "S": stages, "M": M,
                "batch": batch_n, "mb_size": mb_size,
                "compile_s": round(compile_s, 2),
                "temp_bytes": int(ma.temp_size_in_bytes) if ma else None,
                "argument_bytes": int(ma.argument_size_in_bytes) if ma else None,
            }
            try:
                jax.block_until_ready(compiled(params, batch))
                t0 = time.perf_counter()
                for _ in range(steps):
                    out = compiled(params, batch)
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t0) / steps
                row["step_ms"] = round(dt * 1e3, 1)
                row["imgs_per_sec"] = round(batch_n / dt, 1)
            except Exception as exc:  # OOM / rendezvous starvation
                row["exec_error"] = f"{type(exc).__name__}: {exc}"
            stats = devices[0].memory_stats() or {}
            if stats.get("peak_bytes_in_use") is not None:
                # process-lifetime high-water mark (see SWEEP_SCHEDULES
                # note): monotone across cells — a cell's own peak only
                # when no earlier cell exceeded it; temp_bytes above is
                # the per-cell ground truth
                row["device_peak_bytes_cumulative"] = int(
                    stats["peak_bytes_in_use"])
            rows.append(row)
            cells.append(row)
            if emit is not None:
                emit(row)
    by = {(r["schedule"], r["M"]): r for r in cells if "temp_bytes" in r}
    summary = {
        "kind": "pipeline_schedule_sweep", "S": stages,
        "mb_size": mb_size, "hw": list(hw), "rows": rows,
    }
    lo, hi = min(m_grid), max(m_grid)
    for sched in schedules:
        a, b = by.get((sched, lo)), by.get((sched, hi))
        if a and b and a.get("temp_bytes") and b.get("temp_bytes"):
            summary[f"{sched}_temp_growth_m{lo}_to_m{hi}"] = round(
                b["temp_bytes"] / a["temp_bytes"], 2)
    return summary


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--hw", type=int, nargs=2, default=(64, 96),
                    help="representative size for HLO/compute legs")
    ap.add_argument("--tiny-hw", type=int, nargs=2, default=(32, 48),
                    help="execution-leg size (collective-rendezvous-safe)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--json", default=None,
                    help="also append JSON lines to this file")
    args = ap.parse_args()
    if args.steps < 1:
        ap.error("--steps must be >= 1")
    if args.batch % max(GRID_M):
        ap.error(
            f"--batch must be a multiple of {max(GRID_M)} (the largest "
            f"microbatch count in the measured grid {GRID_M})")

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from distributedpytorch_tpu.utils.provision import (
        maybe_reexec_provisioned,
    )

    child_rc = maybe_reexec_provisioned(8, _PROVISIONED_ENV)
    if child_rc is not None:
        return child_rc

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from distributedpytorch_tpu.models.unet import UNet
    from distributedpytorch_tpu.ops.losses import bce_dice_loss
    from distributedpytorch_tpu.parallel.pipeline import make_pipeline_loss_fn

    records = []

    def emit(rec):
        records.append(rec)
        line = json.dumps(rec)
        print(line)
        if args.json:
            with open(args.json, "a") as f:
                f.write(line + "\n")

    B = args.batch
    rng = np.random.default_rng(0)

    def make_batch(h, w):
        return {
            "image": jnp.asarray(rng.random((B, h, w, 3), dtype=np.float32)),
            "mask": jnp.asarray(
                (rng.random((B, h, w, 1)) > 0.5).astype(np.float32)),
        }

    def timed(fn, *fn_args):
        # compile + warm — and BLOCK: dispatch is async even on CPU, so an
        # unblocked warm call would bill its execution tail to the window
        jax.block_until_ready(fn(*fn_args))
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = fn(*fn_args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.steps

    # ---- leg (b): per-microbatch compute curve at representative width ----
    h, w = args.hw
    model = UNet(dtype=jnp.float32, s2d_levels=0)
    params = model.init(jax.random.key(0), jnp.zeros((1, h, w, 3)))["params"]
    batch = make_batch(h, w)

    def plain_loss(params, batch):
        preds = model.apply({"params": params}, batch["image"])
        return bce_dice_loss(preds, batch["mask"])

    plain_grad = jax.jit(jax.grad(plain_loss))
    t_plain = timed(plain_grad, params, batch)
    emit({"kind": "plain_grad", "batch": B, "hw": [h, w],
          "step_ms": round(t_plain * 1e3, 1)})

    w1_of_m = {}  # per-microbatch grad time at microbatch size B/M
    for M in GRID_M:
        mb = {k: v[: B // M] for k, v in batch.items()}
        t = timed(plain_grad, params, mb)
        w1_of_m[M] = t
        emit({"kind": "plain_grad_microbatch", "M": M, "mb_batch": B // M,
              "step_ms": round(t * 1e3, 1),
              "serial_total_ms": round(t * M * 1e3, 1),
              "small_batch_penalty": round(t * M / t_plain, 2)})

    # ---- leg (a): HLO structure + parallel prediction (compile-only) ----
    devices = jax.devices()
    for S in GRID_S:
        mesh = Mesh(np.array(devices[:S]), ("stage",))
        for M in GRID_M:
            loss_fn = make_pipeline_loss_fn(model, mesh, num_microbatches=M)
            grad_fn = jax.jit(jax.grad(loss_fn))
            hlo = grad_fn.lower(params, batch).compile().as_text()
            n_perm = (hlo.count("collective-permute(")
                      + hlo.count("collective-permute-start("))
            ticks = M + S - 1
            emit({
                "kind": "pipeline_hlo", "S": S, "M": M, "ticks": ticks,
                "hlo_collective_permutes": n_perm,
                "expected_min_permutes": M * (S - 1),
                "structure_ok": n_perm >= M * (S - 1),
                "bubble_fraction_theory": round((S - 1) / ticks, 3),
                "efficiency_theory": round(M / ticks, 3),
                "predicted_parallel_step_ms": round(
                    ticks * w1_of_m[M] / S * 1e3, 1),
                "predicted_speedup_vs_1dev": round(
                    t_plain / (ticks * w1_of_m[M] / S), 2),
            })

    # ---- leg (c): execution at tiny width; intercept = serialized bubble --
    th, tw = args.tiny_hw
    tmodel = UNet(dtype=jnp.float32, s2d_levels=0, widths=(8, 16, 32, 64))
    tparams = tmodel.init(
        jax.random.key(0), jnp.zeros((1, th, tw, 3)))["params"]
    tbatch = make_batch(th, tw)
    exec_ms = {}
    for S in GRID_S:
        mesh = Mesh(np.array(devices[:S]), ("stage",))
        for M in GRID_M:
            loss_fn = make_pipeline_loss_fn(tmodel, mesh, num_microbatches=M)
            grad_fn = jax.jit(jax.grad(loss_fn))
            try:
                t = timed(grad_fn, tparams, tbatch)
            except Exception as exc:  # rendezvous starvation etc.
                emit({"kind": "pipeline_exec", "S": S, "M": M,
                      "error": f"{type(exc).__name__}: {exc}"})
                continue
            exec_ms[(S, M)] = t * 1e3
            emit({"kind": "pipeline_exec", "S": S, "M": M,
                  "ticks": M + S - 1, "step_ms": round(t * 1e3, 1)})
        ms = [M for M in GRID_M if (S, M) in exec_ms]
        if len(ms) >= 2:
            ys = np.array([exec_ms[(S, M)] for M in ms])
            a, c = np.polyfit(np.array(ms, dtype=float), ys, 1)
            emit({"kind": "pipeline_exec_fit", "S": S,
                  "per_microbatch_ms": round(float(a), 1),
                  "intercept_ms": round(float(c), 1),
                  "note": "intercept ≈ M-independent warmup/drain work — "
                          "the (S−1)-tick bubble as a serialized host "
                          "executes it; must grow with S"})

    # ---- leg (d): schedule sweep — M × (gpipe|1f1b) at fixed µb size ----
    summary = schedule_sweep(
        stages=2, hw=tuple(args.tiny_hw), steps=args.steps, emit=emit
    )
    emit({k: v for k, v in summary.items() if k != "rows"})

    # ---- markdown tables for docs/DISTRIBUTED.md ----
    print("\n| S | M | ticks | bubble | efficiency | HLO permutes "
          "(≥ M·(S−1)) | predicted parallel step ms | predicted speedup "
          "vs 1 device |")
    print("|---|---|---|---|---|---|---|---|")
    for r in records:
        if r["kind"] != "pipeline_hlo":
            continue
        print(f"| {r['S']} | {r['M']} | {r['ticks']} "
              f"| {r['bubble_fraction_theory']} | {r['efficiency_theory']} "
              f"| {r['hlo_collective_permutes']} "
              f"(≥{r['expected_min_permutes']}"
              f"{' ✓' if r['structure_ok'] else ' ✗'}) "
              f"| {r['predicted_parallel_step_ms']} "
              f"| {r['predicted_speedup_vs_1dev']} |")
    print("\n| S | exec fit: ms/microbatch | intercept ms (serialized "
          "bubble) |")
    print("|---|---|---|")
    for r in records:
        if r["kind"] != "pipeline_exec_fit":
            continue
        print(f"| {r['S']} | {r['per_microbatch_ms']} "
              f"| {r['intercept_ms']} |")
    print("\n| schedule | M | batch | temp bytes (XLA buffer assignment) "
          "| step ms | imgs/s |")
    print("|---|---|---|---|---|---|")
    for r in records:
        if r["kind"] != "pipeline_sweep_cell" or r.get("skipped"):
            continue
        print(f"| {r['schedule']} | {r['M']} | {r['batch']} "
              f"| {r.get('temp_bytes')} | {r.get('step_ms', '—')} "
              f"| {r.get('imgs_per_sec', '—')} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
