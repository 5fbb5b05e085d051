#!/usr/bin/env python3
"""Benchmark harness: UNet training throughput on the available hardware.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "imgs/sec", "vs_baseline": N, ...}

Measured config = the reference's measured config (reference train.py:18-24:
batch 4, 3×640×960, Adam 1e-4, BCE−log-dice), single chip, bf16 compute.

Accounting:
  * FLOPs come from XLA's own cost analysis of the compiled train step,
    with an analytic fallback (~0.257 TFLOP forward/img, ~3× that for the
    full step at 640×960 — per-conv 2·K²·Cin·Cout·H·W summed over the
    UNet; the round-1 "7.3 TFLOP/img" figure was ~10× wrong).
  * `mfu` is measured FLOP/s over the detected chip's bf16 peak.
  * Timing excludes compile: warmup steps run (and are synced) first.
  * A failure exits non-zero with its traceback; no JSON line is printed.

``vs_baseline``: the reference publishes no throughput numbers (SURVEY.md
§6); BASELINE.md's operational target is its 2×GPU DDP config. Until a
measured GPU number exists we normalize against an estimated 2×RTX-3090-class
DDP throughput for this exact model/shape, carried as a BOUNDED RANGE
(VERDICT r04 next-4), derivation:

  * Work: ~0.77 TFLOP logical per image per train step (same analytic conv
    sum as the TPU side, ANALYTIC_STEP_FLOPS_PER_IMG).
  * Peak: RTX 3090 / GA102 = 35.6 TFLOP/s fp32 FFMA; the TF32 tensor-core
    dense rate on GeForce Ampere is the same 35.6 TFLOP/s (NVIDIA
    "GA102 whitepaper", shading/tensor performance tables). The reference
    trains fp32 with no AMP (reference train.py has no autocast), but
    PyTorch runs cuDNN convs in TF32 by default on Ampere
    (torch.backends.cudnn.allow_tf32=True — PyTorch docs, "CUDA semantics:
    TensorFloat-32"), so both paths share the same peak and differ in
    achievable utilization.
  * Utilization bracket for large-image UNet convs: ~20% of peak on the
    fp32 FFMA path (consistent with classic public fp32 ResNet-50 numbers,
    e.g. ~360 imgs/s on V100 ≈ 18% of its 15.7 TFLOP/s peak) up to ~55%
    for well-tiled TF32 tensor-core convs (cuDNN benchmark-mode heuristics,
    reference train_utils sets torch.backends.cudnn.benchmark).
  * Per GPU: 0.20·35.6/0.77 ≈ 9 imgs/s … 0.55·35.6/0.77 ≈ 25 imgs/s;
    ×2 GPUs at 0.90-0.97 DDP scaling → PAIR RANGE ≈ 17-49 imgs/s.
    Central point stays 28 (the round-1..4 estimate, mid-range).

Explicit and revisable, recorded here so the denominator is never
fabricated; carried in-band as ``baseline_source: "estimate"`` with
``baseline_range`` and worst/best-case ``vs_baseline_vs_high`` /
``vs_baseline_vs_low`` alongside the central ``vs_baseline``.

Exit code 0 means a measured number on the device the JSON names
(``platform``, ``device_kind``, ``device_count``). Without a TPU — and
without ``JAX_PLATFORMS=cpu`` naming the CPU — it exits non-zero at once
(utils/backend.require_accelerator); any exception in the run does too.
"""

import json
import os
import time

# Estimated reference DDP (2 GPU) throughput for batch 4 @ 3x640x960 —
# derivation in the module docstring; revise when a measured number lands.
# ``baseline_source: "estimate"`` rides in the JSON so consumers see the
# caveat in-band, not only here (VERDICT r03 weak-9). The range bounds the
# utilization bracket (fp32-FFMA floor … TF32-tensor-core ceiling);
# the central point is the original mid-range estimate (VERDICT r04 next-4).
BASELINE_IMGS_PER_SEC = 28.0
BASELINE_RANGE = (17.0, 49.0)
BASELINE_SOURCE = "estimate"


def _baseline_fields(imgs_per_sec: float) -> dict:
    """The denominator block every bench JSON carries in-band: central
    normalization plus worst/best-case against the bounded range."""
    return {
        "vs_baseline": round(imgs_per_sec / BASELINE_IMGS_PER_SEC, 3),
        "baseline_imgs_per_sec": BASELINE_IMGS_PER_SEC,
        "baseline_range_imgs_per_sec": list(BASELINE_RANGE),
        "vs_baseline_vs_high": round(imgs_per_sec / BASELINE_RANGE[1], 3),
        "vs_baseline_vs_low": round(imgs_per_sec / BASELINE_RANGE[0], 3),
        "baseline_source": BASELINE_SOURCE,
    }

BATCH = int(os.environ.get("BENCH_BATCH", 4))
H = int(os.environ.get("BENCH_H", 640))
W = int(os.environ.get("BENCH_W", 960))
# Validated at module load so a typo'd arch fails loudly instead of
# benching the unet under a mislabeled metric name; ARCH also names the
# error/timeout/preflight metric series so a milesial run's failure is
# never misfiled into the unet series.
ARCH = os.environ.get("BENCH_ARCH", "unet")
if ARCH not in ("unet", "milesial"):
    raise SystemExit(f"BENCH_ARCH={ARCH!r}: expected 'unet' or 'milesial'")
WARMUP_STEPS = 3
MEASURE_STEPS = int(os.environ.get("BENCH_STEPS", 20))
# Steps fused per dispatch (the trainer's --steps-per-dispatch path),
# timed beside the one-dispatch-per-step loop; the faster is the headline.
# Overridable for quick CPU smoke runs (the K-step scan dominates compile).
FUSED_STEPS = int(os.environ.get("BENCH_FUSED_STEPS", 10))

# Analytic per-image LOGICAL (pixel-domain) FLOPs at 640×960: forward = sum
# of 2·K²·Cin·Cout·Hout·Wout over every conv/deconv in the 4-level UNet
# ≈ 0.257 TFLOP; backward ≈ 2× forward. Scales linearly in H·W (every conv's
# spatial extent does), which run() uses for non-default BENCH_H/BENCH_W.
ANALYTIC_FWD_FLOPS_PER_IMG = 0.257e12
ANALYTIC_STEP_FLOPS_PER_IMG = 3.0 * ANALYTIC_FWD_FLOPS_PER_IMG

# bf16 peak FLOP/s of one chip, keyed by the lowercase ``device_kind`` jax
# reports. Source: Google Cloud TPU documentation, system-architecture
# pages ("TPU v5e": 197 TFLOP/s bf16; "TPU v4": 275; "TPU v5p": 459;
# "TPU v6e": 918). A device that is not in the table is an error, not a
# default.
PEAK_BF16_FLOPS = {
    "tpu v6 lite": 918e12,  # v6e
    "tpu v5": 459e12,       # v5p
    "tpu v5 lite": 197e12,  # v5e (the kind BENCH_r05.json records)
    "tpu v4": 275e12,
}


def chip_peak_flops(device) -> float:
    """The MFU denominator for ``device``. 0.0 on an operator-named CPU
    (no meaningful peak: the FLOP-share fields print null); an unknown
    accelerator raises rather than borrow another chip's number."""
    if device.platform == "cpu":
        return 0.0
    kind = device.device_kind.lower()
    try:
        return PEAK_BF16_FLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak on record for device_kind {device.device_kind!r}"
            f"; add it to bench.PEAK_BF16_FLOPS with its source "
            f"(known: {sorted(PEAK_BF16_FLOPS)})"
        ) from None


def xla_step_flops(compiled) -> float:
    """Total FLOPs per executed step per XLA's cost analysis (0 where the
    backend reports none)."""
    return float((compiled.cost_analysis() or {}).get("flops", 0.0))


def run() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedpytorch_tpu.utils.backend import (
        enable_compilation_cache,
        require_accelerator,
    )

    enable_compilation_cache()
    require_accelerator("bench")

    from distributedpytorch_tpu.models.unet import UNet, init_unet_params
    from distributedpytorch_tpu.train.steps import (
        create_train_state,
        make_multi_train_step,
        make_train_step,
    )

    # A/B levers for on-chip experiments (default = shipping config):
    #   BENCH_WGRAD_TAPS=1    9-tap-matmul conv weight gradients
    #   BENCH_S2D_LEVELS=N    force space-to-depth depth (-1 = auto)
    #   BENCH_ARCH=milesial   the 31M-param public-upstream family
    #   BENCH_PALLAS_LOSS=1   fused one-pass Pallas training loss
    arch = ARCH
    wgrad_taps = os.environ.get("BENCH_WGRAD_TAPS") == "1"
    s2d_levels = int(os.environ.get("BENCH_S2D_LEVELS", "-1"))
    if arch == "milesial":
        from distributedpytorch_tpu.models.milesial import (
            MilesialUNet,
            init_milesial,
        )

        model = MilesialUNet(
            dtype=jnp.bfloat16, s2d_levels=s2d_levels, wgrad_taps=wgrad_taps
        )
        params, model_state = init_milesial(
            model, jax.random.key(0), input_hw=(H, W)
        )
    else:
        model = UNet(
            dtype=jnp.bfloat16, s2d_levels=s2d_levels, wgrad_taps=wgrad_taps
        )
        params = init_unet_params(model, jax.random.key(0), input_hw=(H, W))
        model_state = None
    state, tx = create_train_state(params, 1e-4, model_state=model_state)
    loss_impl = None
    if os.environ.get("BENCH_PALLAS_LOSS") == "1":
        from distributedpytorch_tpu.ops.fused_loss import fused_bce_dice_loss

        loss_impl = fused_bce_dice_loss

    # per-phase host-span tracer (utils/trace.py): the same decode/stack/
    # h2d/dispatch/readback phases the trainer's --trace-timeline records,
    # measured inline here so every bench row carries an attribution
    # breakdown next to its imgs/sec (in-memory; summarized at the end)
    from distributedpytorch_tpu.utils.trace import StepTimeline

    timeline = StepTimeline(enabled=True)

    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    host_batch = {
        "image": rng.random((BATCH, H, W, 3), dtype=np.float32),
        "mask": (rng.random((BATCH, H, W)) > 0.5).astype(np.int32),
    }
    batch = {k: jax.device_put(v, dev) for k, v in host_batch.items()}
    # the fused executable scans over K stacked (identical) batches — what
    # the trainer dispatches under --steps-per-dispatch K
    stacked = {
        k: jax.device_put(jnp.broadcast_to(v, (FUSED_STEPS,) + v.shape), dev)
        for k, v in batch.items()
    }
    state = jax.device_put(state, dev)

    # AOT-compile once; the same executables are what we time (no hidden
    # recompiles, and cost_analysis reads the very computation measured).
    step_fn = make_train_step(model, tx, batch_size=BATCH, loss_impl=loss_impl)
    t_compile0 = time.monotonic()
    compiled = (
        jax.jit(step_fn, donate_argnums=(0,)).lower(state, batch).compile()
    )
    if os.environ.get("BENCH_COMPILE_ONLY") == "1":
        # Compile-only probe: prove this config's train-step executable
        # lowers + compiles on the device without spending a
        # measurement window — bench_multi records compiled-or-rejected
        # in its ledger (a compile failure raises out of run()).
        return {
            "compile_only": True,
            "compiled": True,
            "compile_s": round(time.monotonic() - t_compile0, 3),
            "platform": jax.default_backend(),
        }
    multi = (
        jax.jit(make_multi_train_step(step_fn), donate_argnums=(0,))
        .lower(state, stacked)
        .compile()
    )
    # Executed FLOPs (XLA cost analysis of the compiled step). With the
    # default space-to-depth execution mode this EXCEEDS the model's logical
    # FLOPs — the structured dense kernels multiply by zeros the MXU schedule
    # anyway — so MFU is defined on the logical (pixel-domain) count and the
    # executed count is reported separately as hardware utilization. The
    # logical count comes from ONE source in every mode — the analytic conv
    # sum, which scales linearly with H·W — so MFU ratios between execution
    # modes always track measured imgs/sec ratios.
    # The analytic conv sum is the 7.76M-param UNet's; it must never fill
    # a milesial row (≈4× the params — the FLOP fields would be silently
    # ~4× off under a milesial_... metric name). milesial rows without
    # cost_analysis report their FLOP-derived fields as null instead.
    flops_executed = xla_step_flops(compiled)
    flops_source = "xla_cost_analysis"
    if flops_executed <= 0:
        if arch == "unet":
            flops_executed = (
                ANALYTIC_STEP_FLOPS_PER_IMG * BATCH * (H * W) / (640 * 960)
            )
            flops_source = "analytic"
        else:
            flops_executed = None
            flops_source = "unavailable"
    if arch == "unet":
        flops_logical = ANALYTIC_STEP_FLOPS_PER_IMG * BATCH * (H * W) / (640 * 960)
    else:
        flops_logical = None

    # -- unfused: one dispatch per step --------------------------------------
    for _ in range(WARMUP_STEPS):
        state, loss = compiled(state, batch)
    float(loss)  # device→host transfer: a hard sync

    # H2D phase: place the full host batch (what one pipeline payload
    # costs), synced so the span covers the transfer, not just the enqueue
    for _ in range(3):
        with timeline.span("h2d"):
            placed = {k: jax.device_put(v, dev) for k, v in host_batch.items()}
            jax.block_until_ready(placed)
    del placed

    t0 = time.perf_counter()
    for _ in range(MEASURE_STEPS):
        # dispatch spans are the host-side enqueue cost; the final
        # readback span absorbs the queued device time — together they
        # bound where a throughput delta lives (host vs chip vs transfer)
        with timeline.span("dispatch"):
            state, loss = compiled(state, batch)
    with timeline.span("readback"):
        float(loss)  # forces the whole dependency chain of donated states
    dt_unfused = time.perf_counter() - t0
    unfused_per_step = dt_unfused / MEASURE_STEPS

    # -- fused: K steps per dispatch (headline) ------------------------------
    # symmetric methodology on a per-STEP basis: one warmup dispatch already
    # runs FUSED_STEPS (=10) warmup steps vs the unfused path's 3, and the
    # measured window is ≥3 dispatches / ≥30 steps vs the unfused 20 — so
    # min() below compares like with like instead of letting one lucky
    # 2-dispatch window pick the headline
    state, losses = multi(state, stacked)
    float(losses[-1])
    reps = max(3, MEASURE_STEPS // FUSED_STEPS)
    t0 = time.perf_counter()
    for _ in range(reps):
        state, losses = multi(state, stacked)
    float(losses[-1])
    dt_fused = time.perf_counter() - t0
    fused_per_step = dt_fused / (reps * FUSED_STEPS)

    per_step = min(fused_per_step, unfused_per_step)
    imgs_per_sec = BATCH / per_step
    peak = chip_peak_flops(dev)
    # per-phase attribution: the inline spans above, plus (when
    # BENCH_TIMELINE_JSONL names a trainer-written --trace-timeline file)
    # the real end-to-end pipeline's phases including decode. The spans
    # are recorded on the SINGLE-DISPATCH loop; when the fused K-step
    # executable wins the headline, `headline_loop` flags that the phase
    # timings come from a different executable (per-dispatch granularity
    # differs), so a reader never attributes a fused-path delta to them.
    timeline_summary = {
        "source": "bench_inline",
        "loop": "single_dispatch",
        "headline_loop": (
            "fused" if per_step == fused_per_step else "single_dispatch"
        ),
        **timeline.summary(),
    }
    trainer_jsonl = os.environ.get("BENCH_TIMELINE_JSONL")
    timeline_trainer = None
    if trainer_jsonl and os.path.exists(trainer_jsonl):
        from distributedpytorch_tpu.utils.trace import summarize_timeline

        timeline_trainer = {
            "source": trainer_jsonl,
            **summarize_timeline(trainer_jsonl),
        }
    return {
        "metric": f"{arch}_train_imgs_per_sec_b{BATCH}_{H}x{W}_{dev.platform}",
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec",
        **_baseline_fields(imgs_per_sec),
        "step_time_ms": round(1e3 * per_step, 2),
        "steps_per_dispatch": FUSED_STEPS if per_step == fused_per_step else 1,
        "imgs_per_sec_single_dispatch": round(BATCH / unfused_per_step, 2),
        # logical = pixel-domain model FLOPs (the work a user asked for);
        # executed = what the compiled s2d computation runs (incl. its
        # structural zeros). MFU uses logical; hw_utilization uses executed.
        "flops_per_img": (
            round(flops_logical / BATCH / 1e9, 2)  # GFLOP
            if flops_logical is not None else None
        ),
        "flops_per_img_executed": (
            round(flops_executed / BATCH / 1e9, 2)
            if flops_executed is not None else None
        ),
        "flops_source": flops_source,
        "achieved_tflops": (
            round(flops_executed / per_step / 1e12, 2)
            if flops_executed is not None else None
        ),
        "mfu": (
            round(flops_logical / per_step / peak, 4)
            if peak > 0 and flops_logical is not None else None
        ),
        "hw_utilization": (
            round(flops_executed / per_step / peak, 4)
            if peak > 0 and flops_executed is not None else None
        ),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "timeline": timeline_summary,
        "timeline_trainer": timeline_trainer,
    }


def main():
    print(json.dumps(run()))


if __name__ == "__main__":
    main()
