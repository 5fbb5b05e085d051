#!/usr/bin/env python3
"""On-chip A/B: XLA conv backward vs the 9-tap-matmul weight gradient.

Measures, per hot s2d conv shape and for the full train step:
  (a) default backward (XLA conv-backward-filter + conv-backward-input)
  (b) --wgrad-taps backward (ops/conv_backward.py)
and, with --backend pallas, a third leg:
  (c) the taps backward with the single-pass Pallas wgrad kernel
      (ops/wgrad_pallas.py) instead of the 9 einsums.

Timings use the chained-dispatch method from round 3 (lax.scan over the
op inside ONE dispatch, so per-dispatch latency cancels). Run on
the TPU; prints one JSON line per measurement.

Usage: python tools/bench_wgrad.py [--steps 10] [--full-step]
       [--backend einsum|pallas|both]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def chain_time(fn, args, n):
    """Seconds per fn application, measured as one n-deep scan dispatch."""
    import jax

    def body(carry, _):
        return fn(*carry), None

    def chained(args):
        out, _ = jax.lax.scan(body, args, None, length=n)
        return out

    compiled = jax.jit(chained).lower(args).compile()
    out = compiled(args)
    jax.block_until_ready(out)  # warm
    t0 = time.perf_counter()
    out = compiled(args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    # This tool MEASURES the taps path: pin the spatial gate open so an
    # ambient DPT_WGRAD_TAPS_MIN_HW (e.g. exported while iterating on
    # the scoped bench config) can't silently reroute the taps rows to
    # the plain conv under a taps label.
    os.environ["DPT_WGRAD_TAPS_MIN_HW"] = "0"
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--full-step", action="store_true",
                    help="Also A/B the full reference-config train step")
    ap.add_argument("--tiny", action="store_true",
                    help="Tiny shapes (machinery smoke test off-TPU)")
    ap.add_argument("--backend", choices=("einsum", "pallas", "both"),
                    default="einsum",
                    help="tap-contraction backend(s) to measure; the env "
                    "var DPT_WGRAD_BACKEND is set per leg BEFORE tracing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedpytorch_tpu.ops.conv_backward import (
        _PALLAS_MIN_CHANNELS,
        conv3x3_same_taps,
    )
    from distributedpytorch_tpu.ops.s2d import conv_same
    from distributedpytorch_tpu.utils.backend import (
        enable_compilation_cache,
        require_accelerator,
    )

    enable_compilation_cache()
    require_accelerator("bench_wgrad")
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "device_count": len(jax.devices())}))

    # The hot s2d shapes at the reference config (batch 4, 640×960,
    # s2d levels 1-2): (B, H, W, Cin) -> Cout
    shapes = [
        (4, 320, 480, 12, 128),   # enc1 conv1
        (4, 320, 480, 128, 128),  # enc1 conv2 / dec4 block
        (4, 160, 240, 128, 256),  # enc2 conv1
        (4, 160, 240, 256, 256),  # enc2 conv2 / dec3 block
    ]
    if args.tiny:
        shapes = [(2, 16, 24, 8, 16)]
    tap_backends = {
        "einsum": ["einsum"], "pallas": ["pallas"],
        "both": ["einsum", "pallas"],
    }[args.backend]
    legs = [("xla", conv_same, None)] + [
        ("taps" if be == "einsum" else f"taps-{be}", conv3x3_same_taps, be)
        for be in tap_backends
    ]
    for b, h, w, ci, co in shapes:
        x = jnp.asarray(rng.random((b, h, w, ci), np.float32), jnp.bfloat16)
        k = jnp.asarray(rng.random((3, 3, ci, co), np.float32), jnp.bfloat16)
        flops = 2 * 9 * ci * co * b * h * w * 3  # fwd + dx + dw

        for label, conv, backend in legs:
            if backend == "pallas" and min(ci, co) < _PALLAS_MIN_CHANNELS:
                # the dispatch gate would silently fall back to einsum —
                # a mislabeled duplicate row, not a measurement
                print(json.dumps({
                    "shape": f"{ci}->{co}@{h}x{w}b{b}",
                    "backward": label,
                    "skipped": f"channels below the pallas gate "
                               f"({_PALLAS_MIN_CHANNELS})",
                }))
                continue
            if backend is not None:
                # consulted at trace time; each leg compiles fresh
                os.environ["DPT_WGRAD_BACKEND"] = backend

            def fwd_bwd(x, k, _conv=conv):
                y, vjp = jax.vjp(_conv, x, k)
                dx, dk = vjp(y)  # y as cotangent: right shape, no extra input
                return x + dx.astype(x.dtype) * 0 + jnp.mean(dk).astype(x.dtype), k

            secs = chain_time(fwd_bwd, (x, k), args.steps)
            print(json.dumps({
                "shape": f"{ci}->{co}@{h}x{w}b{b}",
                "backward": label,
                "ms": round(secs * 1e3, 3),
                "tflops": round(flops / secs / 1e12, 1),
            }))

    if args.full_step:
        from distributedpytorch_tpu.models.unet import UNet, init_unet_params
        from distributedpytorch_tpu.train.steps import (
            create_train_state,
            make_train_step,
        )

        batch = {
            "image": jnp.asarray(rng.random((4, 640, 960, 3), np.float32)),
            "mask": jnp.asarray(
                (rng.random((4, 640, 960)) > 0.5).astype(np.int32)
            ),
        }
        step_legs = [("xla", False, None)] + [
            ("taps" if be == "einsum" else f"taps-{be}", True, be)
            for be in tap_backends
        ]
        for step_label, taps, backend in step_legs:
            # NOTE: in the full step the pallas backend applies only to
            # the >=128-channel convs (the dispatch gate); skinnier convs
            # in the same step stay on einsum taps.
            if backend is not None:
                os.environ["DPT_WGRAD_BACKEND"] = backend
            model = UNet(dtype=jnp.bfloat16, wgrad_taps=taps)
            params = init_unet_params(model, jax.random.key(0), (640, 960))
            state, tx = create_train_state(params, 1e-4)
            step = make_train_step(model, tx, batch_size=4)
            compiled = jax.jit(step).lower(state, batch).compile()
            state2, loss = compiled(state, batch)
            float(loss)  # warm + sync
            t0 = time.perf_counter()
            reps = 10
            for _ in range(reps):
                state2, loss = compiled(state2, batch)
            float(loss)
            secs = (time.perf_counter() - t0) / reps
            print(json.dumps({
                "full_step": step_label,
                "ms": round(secs * 1e3, 1),
                "imgs_per_sec": round(4 / secs, 1),
                "loss": round(float(loss), 5),
            }))


if __name__ == "__main__":
    main()
