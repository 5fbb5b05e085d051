#!/usr/bin/env python3
"""chip_smoke.py — prove that the main path starts and is right on the TPU.

    python chip_smoke.py              # one chip: train -> checkpoint -> serve
    python chip_smoke.py --chips 4    # only the multi-chip strategies

Default run (one chip), through the entry points a user calls, at the full
width of the course UNet (32-64-128-256, bf16, batch 4 at 960x640):

  train    a Carvana-shaped dataset is generated from ``--seed`` on disk
           (1918x1280 JPEGs + ``_mask.gif``), the native decoder is built
           from its committed source, and ``distributedpytorch_tpu.cli.main``
           trains two short epochs (9 steps each) with the shipping
           defaults: validation passes and checkpoint writes included.
           Checked: TPU backend, s2d depth 2, 7,760,097 parameters, finite
           and decreasing loss, a checkpoint that restores hash-verified.
  serve    ``python -m distributedpytorch_tpu serve -c <that checkpoint>``
           answers real-size ``/predict`` requests; the masks agree with a
           direct jitted forward on the same weights. It is stopped and
           started again on the same AOT store: the second start compiles
           nothing.
  kernels  each Pallas kernel is compiled by Mosaic (``tpu_custom_call`` in
           the compiled text), run once and compared with its XLA reference;
           then the same training run and one served request under
           ``--kernels pallas``.

``--chips 4`` runs only, in one process over four chips: full-width steps of
``-t DP`` (mesh 4x1x1) and of the data x stage hybrid ``2x1x2``, compared
step by step with the same seed and global batch on chip 0 alone; then the
launcher's refusal to start several workers on a TPU host.

**One process owns the chip at a time.** This parent never imports jax. It
runs each phase as a child process, one after another, and waits for each
to exit before it starts the next; the serve processes are children too and
are stopped before anything else starts.

Each phase prints one JSON object (none of them carries an ``ok`` key). The
LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only when every phase passed. Without a TPU the first child
says so and the script exits non-zero at once: there is no switch that lets
it carry on on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
# relative comparison of a kernel with its XLA reference:
# max|got - ref| / max|ref|, held to the rtol tests/test_kernels.py and
# tests/test_pallas.py use for that kernel
KERNEL_RTOL = {
    "eval_stats": 1e-5,
    "fused_loss": 2e-5,
    "fused_loss_grad": 1e-5,
    "fused_bn_act": 1e-5,
    "fused_bn_act_grad": 1e-4,
    # bf16 outputs and gradients of two roundings of the same float32
    # mathematics (the kernel normalises after the values' product, the
    # XLA path before): a few bf16 ulps (2^-8) of the largest entry
    "causal_attention": 2e-2,
}
# served mask vs direct forward, and --kernels pallas vs xla: share of
# pixels that may differ (probabilities within bf16 rounding of 0.5)
MASK_MISMATCH_MAX = 5e-3
# the same seed trained under --kernels pallas and xla: mean loss of the
# first ten steps and final validation loss
PALLAS_LOSS_RTOL = 5e-3
# DP / hybrid per-step loss vs the one-chip run: bf16 compute
MULTICHIP_LOSS_RTOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase's cost depends on. ``FULL`` is what the script
    runs; tests call the phases at toy sizes."""

    widths: tuple = (32, 64, 128, 256)
    image_wh: tuple = (960, 640)      # what the model sees (W, H)
    source_wh: tuple = (1918, 1280)   # Carvana's files (W, H)
    n_images: int = 40                # 10% validation -> 4 val, 36 train
    batch: int = 4
    buckets: tuple = (1, 2, 4, 8)
    n_requests: int = 5
    param_count: int | None = 7_760_097
    bn_widths: tuple = (64, 128, 256, 512, 1024)
    # the token cell's attention block: batch, length, query heads,
    # key-value heads, head size
    attention_bshgd: tuple = (2, 8192, 32, 2, 128)
    multichip_batch: int = 8
    multichip_steps: int = 3


FULL = Sizes()
# two, so that "decreasing" compares like with like: the second pass over
# the same images against the first (per-step losses of different batches
# differ by more than nine steps of learning at lr 1e-4)
EPOCHS = 2


class PhaseFailed(RuntimeError):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# Phases that need jax: each runs in a child process of its own
# ---------------------------------------------------------------------------


def _compile_meter():
    """Sum jax's own backend-compile durations and cache hits in this
    process (jax.monitoring; nothing is timed around a call here)."""
    import jax

    meter = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            meter["compiles"] += 1
            meter["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            meter["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return meter


def _device_fields() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def phase_device(out: str, seed: int, sizes: Sizes) -> dict:
    """What jax finds, and that the program is there to be run. The
    parent decides whether the device is enough."""
    import distributedpytorch_tpu

    return {"package": distributedpytorch_tpu.__version__,
            **_device_fields()}


def _dataset(out: str, seed: int, sizes: Sizes) -> str:
    from distributedpytorch_tpu.data.dataset import (
        write_synthetic_carvana_tree,
    )

    root = os.path.join(out, "data")
    marker = os.path.join(root, f".seed{seed}_n{sizes.n_images}")
    if not os.path.exists(marker):
        write_synthetic_carvana_tree(
            root, n=sizes.n_images, size_wh=sizes.source_wh, seed=seed
        )
        open(marker, "w").close()
    return root


def _model_flags(sizes: Sizes) -> list:
    if sizes.widths == FULL.widths:
        return []  # the shipping default, not named
    return ["--model-widths", *map(str, sizes.widths)]


def phase_train(out: str, seed: int, sizes: Sizes,
                kernels: str = "xla") -> dict:
    """``EPOCHS`` short epochs through the CLI's own ``main`` from disk
    data, then the checks on what it left behind."""
    import numpy as np

    from distributedpytorch_tpu.data import native

    native.build(force=True)  # from the committed source, never a stale .so
    decode = native.decode_path()
    data = _dataset(out, seed, sizes)
    run_dir = os.path.join(out, f"train_{kernels}")
    os.makedirs(run_dir, exist_ok=True)
    os.chdir(run_dir)  # ./logs and ./loss are relative to the cwd
    meter = _compile_meter()
    from distributedpytorch_tpu import cli

    sys.argv = [
        "train.py", "-t", "singleGPU", "-b", str(sizes.batch), "-e", str(EPOCHS),
        "--image-size", *map(str, sizes.image_wh), "--dtype", "bf16",
        "--seed", str(seed), "--data-dir", data,
        "--checkpoint-dir", os.path.join(run_dir, "checkpoints"),
        "--kernels", kernels, *_model_flags(sizes),
    ]
    t0 = time.monotonic()
    cli.main()
    train_s = time.monotonic() - t0

    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu import checkpoint as ckpt
    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.models import create_model
    from distributedpytorch_tpu.models.unet import param_count

    platform = jax.default_backend()
    cfg = TrainConfig(model_widths=tuple(sizes.widths), dtype="bf16")
    model, init_fn = create_model(cfg)
    s2d = model._s2d_levels()
    want_s2d = 2 if platform == "tpu" else 0
    if s2d != want_s2d:
        raise PhaseFailed(f"s2d depth resolved to {s2d}, not {want_s2d}")

    path = ckpt.resolve_checkpoint(
        "singleGPU", os.path.join(run_dir, "checkpoints")
    )
    if not ckpt.verify_checkpoint(path):
        raise PhaseFailed(f"{path} does not verify against its hash")
    w, h = sizes.image_wh
    template = jax.eval_shape(lambda k: init_fn(k, (h, w))[0],
                              jax.random.key(0))
    restored = ckpt.load_checkpoint(path, template, fallback=False)
    n_params = param_count(restored["params"])
    if sizes.param_count is not None and n_params != sizes.param_count:
        raise PhaseFailed(f"{n_params} parameters, not {sizes.param_count}")
    finite = all(
        bool(jnp.all(jnp.isfinite(jnp.asarray(leaf))))
        for leaf in jax.tree.leaves(restored["params"])
    )
    if not finite:
        raise PhaseFailed("restored parameters are not finite")

    # what the run recorded about itself: the mean loss of steps 1-10,
    # the last ten per-step losses, and one validation row per epoch
    records = restored["records"]
    steps = restored["step"]
    per_epoch = (sizes.n_images - sizes.n_images // 10) // sizes.batch
    if steps != EPOCHS * per_epoch:
        raise PhaseFailed(f"{steps} steps, not {EPOCHS} x {per_epoch}")
    first10 = float(records["train_rows"][0][2])
    losses = [float(x) for x in records["window"]]
    last_epoch = float(np.mean(losses[-per_epoch:]))
    val = [float(r[2]) for r in records["val_rows"]]
    dice = [float(r[2]) for r in records["dice_rows"]]
    if len(val) != EPOCHS or not np.all(
            np.isfinite(losses + val + dice + [first10])):
        raise PhaseFailed(f"losses {losses} val {val} dice {dice}")
    # decreasing, on like for like: the last epoch's mean against the
    # first ten steps' (the same images), and the validation loss after
    # each epoch (the same held-out images)
    if not (last_epoch < first10 and val[-1] < val[0]):
        raise PhaseFailed(
            f"loss did not decrease: train {first10} -> {last_epoch}, "
            f"val {val}")
    return {
        **_device_fields(), "kernels": kernels, "decode_path": decode,
        "s2d_levels": s2d, "params": n_params, "steps": steps,
        "loss_mean_steps_1_10": first10, "loss_last_10_steps": losses,
        "loss_mean_last_epoch": last_epoch, "val_loss": val,
        "val_dice": dice,
        "checkpoint": path, "checkpoint_verified": True,
        "train_seconds": round(train_s, 1),
        "compiles": meter["compiles"],
        "compile_seconds": round(meter["compile_s"], 1),
        "compile_cache_hits": meter["cache_hits"],
        "peak_bytes_in_use": _peak_bytes(),
    }


def phase_serve_check(out: str, seed: int, sizes: Sizes) -> dict:
    """The served masks against a direct jitted forward on the same
    weights (runs after the server has been stopped)."""
    import jax
    import numpy as np
    from PIL import Image

    from distributedpytorch_tpu.serve.infer import (
        load_inference_bundle,
        postprocess_mask,
        preprocess_image,
    )

    bundle = load_inference_bundle(
        "singleGPU", os.path.join(out, "train_xla", "checkpoints"),
        image_size=sizes.image_wh, model_widths=sizes.widths,
    )
    fwd = jax.jit(bundle.forward())
    variables = jax.device_put(bundle.variables)
    w, h = sizes.image_wh
    worst = 0.0
    served = json.load(open(os.path.join(out, "serve_1.json")))["masks"]
    for image_path, mask_path in served:
        x = preprocess_image(Image.open(image_path), sizes.image_wh)
        probs = np.asarray(fwd(variables, x[None]))[0]
        want = postprocess_mask(probs, 0.5)
        got = np.asarray(Image.open(mask_path))
        if got.shape != (h, w) or got.dtype != np.uint8:
            raise PhaseFailed(f"{mask_path}: {got.shape} {got.dtype}")
        if not np.isin(got, (0, 255)).all():
            raise PhaseFailed(f"{mask_path}: values other than 0 and 255")
        worst = max(worst, float(np.mean(got != want)))
    if worst > MASK_MISMATCH_MAX:
        raise PhaseFailed(
            f"served masks differ from the direct forward on {worst:.2%} "
            f"of pixels (limit {MASK_MISMATCH_MAX:.2%})"
        )
    return {**_device_fields(), "masks": len(served),
            "mask_shape": [h, w],
            "worst_mismatch_fraction": worst}


def _rel_err(got, ref) -> tuple:
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    err = float(np.max(np.abs(got - ref)))
    return err, err / max(float(np.max(np.abs(ref))), 1e-30)


def phase_kernels(out: str, seed: int, sizes: Sizes) -> dict:
    """Every Pallas kernel of the package: compiled (by Mosaic on a TPU),
    executed once, compared with its XLA reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedpytorch_tpu.ops import attention_pallas, losses
    from distributedpytorch_tpu.ops import sequence as seq
    from distributedpytorch_tpu.ops.fused_loss import fused_bce_dice_loss
    from distributedpytorch_tpu.ops.kernels import (
        fused_bn_act,
        sigmoid_threshold_mask,
    )
    from distributedpytorch_tpu.ops.pallas_kernels import eval_stats_pallas
    from distributedpytorch_tpu.serve.infer import postprocess_mask

    platform = jax.default_backend()
    rng = np.random.default_rng(seed)
    b = sizes.batch
    w, h = sizes.image_wh
    rows = {}

    def compile_kernel(name, fn, args):
        """(compiled, row): Mosaic on a TPU — ``tpu_custom_call`` in the
        compiled text — and the interpreter nowhere but a named CPU."""
        t0 = time.monotonic()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.monotonic() - t0
        mosaic = "tpu_custom_call" in compiled.as_text()
        if mosaic != (platform == "tpu"):
            raise PhaseFailed(
                f"{name}: tpu_custom_call present={mosaic} on {platform}"
            )
        return compiled, {"compiled": "mosaic" if mosaic else "interpret",
                          "compile_seconds": round(compile_s, 2)}

    def run(name, fn, ref_fn, args, checks):
        """checks: [(label, pick(result), rtol)] — pick selects what is
        compared from fn's and ref_fn's results."""
        compiled, row = compile_kernel(name, fn, args)
        got = jax.block_until_ready(compiled(*args))
        ref = jax.block_until_ready(jax.jit(ref_fn)(*args))
        for label, pick, rtol in checks:
            err, rel = _rel_err(pick(got), pick(ref))
            row[f"{label}max_abs_err"] = err
            row[f"{label}max_rel_err"] = rel
            if not rel <= rtol:
                raise PhaseFailed(f"{name} {label}: rel err {rel} > {rtol}")
        rows[name] = row

    # probabilities with saturated pixels (the log clamp) and a {0,1} target
    p = rng.random((b, h, w, 1), dtype=np.float32)
    p = np.where(p < 0.05, 0.0, np.where(p > 0.95, 1.0, p)).astype(np.float32)
    t = (rng.random((b, h, w, 1)) > 0.5).astype(np.float32)
    p, t = jnp.asarray(p), jnp.asarray(t)

    def eval_ref(o, y):
        inter = jnp.sum((o >= 0.5) * (y == 1.0))
        union = jnp.sum(o >= 0.5) + jnp.sum(y == 1.0)
        return jnp.concatenate([
            losses.bce_dice_stats(o, y),
            jnp.stack([inter, union]).astype(jnp.float32),
        ])

    run("eval_stats", eval_stats_pallas, eval_ref, (p, t),
        [("", lambda r: r, KERNEL_RTOL["eval_stats"])])
    soft = jnp.clip(p, 0.02, 0.98)
    run("fused_loss", jax.value_and_grad(fused_bce_dice_loss),
        jax.value_and_grad(losses.bce_dice_loss), (soft, t),
        [("", lambda r: r[0], KERNEL_RTOL["fused_loss"]),
         ("grad_", lambda r: r[1], KERNEL_RTOL["fused_loss_grad"])])

    probs = rng.random((b, h, w), dtype=np.float32)
    probs.flat[:: max(1, probs.size // 17)] = 0.5  # the >= boundary
    mask_exe, row = compile_kernel(
        "serve_mask", lambda v: sigmoid_threshold_mask(v, 0.5),
        (jnp.asarray(probs),),
    )
    got = np.asarray(mask_exe(jnp.asarray(probs)))
    if got.dtype != np.uint8 or not (got == postprocess_mask(probs, 0.5)).all():
        raise PhaseFailed("serve_mask is not bit-identical to postprocess_mask")
    rows["serve_mask"] = {**row, "max_abs_err": 0.0, "bit_identical": True}

    def bn_ref(x, mean, var, scale, bias):
        return jax.nn.relu(
            (x - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias
        )

    for level, c in enumerate(sizes.bn_widths):
        shape = (b, max(1, h >> level), max(1, w >> level), c)
        args = (
            jnp.asarray(rng.standard_normal(shape, dtype=np.float32)),
            jnp.asarray(rng.standard_normal(c), jnp.float32),
            jnp.asarray(rng.random(c) + 0.1, jnp.float32),
            jnp.asarray(rng.standard_normal(c), jnp.float32),
            jnp.asarray(rng.standard_normal(c), jnp.float32),
        )
        run(f"fused_bn_act_c{c}", fused_bn_act, bn_ref, args,
            [("", lambda r: r, KERNEL_RTOL["fused_bn_act"])])
        run(f"fused_bn_act_grad_c{c}",
            jax.grad(lambda *a: jnp.sum(fused_bn_act(*a) ** 2),
                     argnums=(0, 1, 2, 3, 4)),
            jax.grad(lambda *a: jnp.sum(bn_ref(*a) ** 2),
                     argnums=(0, 1, 2, 3, 4)),
            args,
            [(f"d{n}_", (lambda r, i=i: r[i]), KERNEL_RTOL["fused_bn_act_grad"])
             for i, n in enumerate(("x", "mean", "var", "scale", "bias"))])
        del args

    # the fused attention kernel against the blocked XLA path it replaces
    # on a TPU: output and the three gradients, bf16 as the token model runs
    ab, s, hq, hkv, d = sizes.attention_bshgd
    tile = seq.attention_path("tpu", s, d, hq, hkv)
    q = jnp.asarray(rng.standard_normal((ab, s, hq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((ab, s, hkv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((ab, s, hkv, d)), jnp.bfloat16)
    weight = jnp.asarray(rng.standard_normal((ab, s, hq, d)), jnp.float32)

    def attention_grads(fn):
        def loss(q, k, v, weight):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)

    run("causal_attention",
        attention_grads(lambda q, k, v: attention_pallas.causal_attention(
            q, k, v, tile)),
        attention_grads(seq.blocked_attention), (q, k, v, weight),
        [("", lambda r: r[1], KERNEL_RTOL["causal_attention"])]
        + [(f"d{n}_", (lambda r, i=i: r[0][i]), KERNEL_RTOL["causal_attention"])
           for i, n in enumerate("qkv")])
    return {**_device_fields(), "kernels": rows,
            "peak_bytes_in_use": _peak_bytes()}


def _shard_devices(tree) -> int:
    """How many distinct devices hold a shard of any leaf of ``tree``."""
    import jax

    seen = set()
    for leaf in jax.tree.leaves(tree):
        seen.update(s.device for s in leaf.addressable_shards)
    return len(seen)


def phase_multichip(out: str, seed: int, sizes: Sizes) -> dict:
    """DP 4x1x1 and the 2x1x2 hybrid over four chips against chip 0
    alone: same seed, same global batch, per-step losses compared."""
    import jax
    import numpy as np

    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.train import Trainer

    devices = jax.devices()
    if len(devices) < 4:
        raise PhaseFailed(f"--chips 4 needs 4 devices, jax has {len(devices)}")
    os.makedirs(os.path.join(out, "multichip"), exist_ok=True)
    os.chdir(os.path.join(out, "multichip"))
    meter = _compile_meter()
    gb, steps = sizes.multichip_batch, sizes.multichip_steps

    def run(method):
        from distributedpytorch_tpu.parallel import build_strategy

        cfg = TrainConfig(
            train_method=method, batch_size=gb, seed=seed, dtype="bf16",
            image_size=tuple(sizes.image_wh),
            model_widths=tuple(sizes.widths), num_microbatches=2,
            synthetic_samples=gb * steps + gb, val_percent=0.0,
            num_workers=0, prefetch_batches=0,
        )
        trainer = Trainer(cfg, strategy=build_strategy(cfg, devices[:4]))
        before = meter["compile_s"]
        losses, placed = [], None
        for i, batch in enumerate(trainer.train_loader.epoch_batches(0)):
            if i == steps:
                break
            placed = trainer.strategy.place_batch(batch)
            trainer.state, loss = trainer.train_step(trainer.state, placed)
            losses.append(float(loss))
        mesh = trainer.strategy.mesh
        row = {
            "losses": losses,
            "mesh": None if mesh is None else dict(mesh.shape),
            "param_devices": _shard_devices(trainer.state.params),
            "opt_state_devices": _shard_devices(trainer.state.opt_state),
            "batch_devices": _shard_devices(placed),
            "batch_shard_shape": list(
                placed["image"].addressable_shards[0].data.shape),
            "compile_seconds": round(meter["compile_s"] - before, 1),
        }
        stats = [d.memory_stats() for d in devices[:4]]
        if all(s is not None for s in stats):
            row["bytes_in_use_per_device"] = [
                int(s["bytes_in_use"]) for s in stats]
            row["peak_bytes_per_device"] = [
                int(s["peak_bytes_in_use"]) for s in stats]
        del trainer, placed
        return row

    rows = {"singleGPU": run("singleGPU")}
    ref = rows["singleGPU"]["losses"]
    if len(ref) != steps or not np.all(np.isfinite(ref)):
        raise PhaseFailed(f"one-chip reference losses {ref}")
    if rows["singleGPU"]["param_devices"] != 1:
        raise PhaseFailed("the comparison run is not on one chip")
    for method, want_mesh in (("DP", {"data": 4}),
                              ("2x1x2", {"data": 2, "stage": 2})):
        row = rows[method] = run(method)
        if row["mesh"] != want_mesh:
            raise PhaseFailed(f"{method}: mesh {row['mesh']}, not {want_mesh}")
        for what in ("param_devices", "opt_state_devices", "batch_devices"):
            if row[what] != 4:
                raise PhaseFailed(f"{method}: {what} = {row[what]}, not 4")
        if row["batch_shard_shape"][0] != gb // want_mesh["data"]:
            raise PhaseFailed(
                f"{method}: batch shard {row['batch_shard_shape']}")
        held = row.get("bytes_in_use_per_device")
        if held is not None and min(held) <= 0:
            raise PhaseFailed(f"{method}: a device holds nothing: {held}")
        rel = [abs(a - r) / max(abs(r), 1e-6)
               for a, r in zip(row["losses"], ref)]
        row["max_rel_loss_diff"] = max(rel)
        if len(row["losses"]) != steps or not max(rel) <= MULTICHIP_LOSS_RTOL:
            raise PhaseFailed(
                f"{method} losses {row['losses']} vs one chip {ref}")
    return {**_device_fields(), "global_batch": gb,
            "steps": steps, "strategies": rows}


def phase_launcher(out: str, seed: int, sizes: Sizes) -> dict:
    """The elastic launcher on a TPU host: two workers would both claim
    every chip, so it must refuse them — with the documented error —
    unless the operator named the CPU. (No backend is touched here.)"""
    from distributedpytorch_tpu.dist.elastic import (
        MULTI_WORKER_OFF_CPU,
        ElasticSupervisor,
    )

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    run_dir = os.path.join(out, "elastic_run")
    try:
        ElasticSupervisor(["-t", "DDP"], nprocs=2, env=env, run_dir=run_dir)
    except ValueError as exc:
        if str(exc) != MULTI_WORKER_OFF_CPU.format(n=2):
            raise
    else:
        raise PhaseFailed("elastic -n 2 off the CPU was not refused")
    # one worker, and CPU drills, stay possible
    ElasticSupervisor(["-t", "singleGPU"], nprocs=1, env=env, run_dir=run_dir)
    ElasticSupervisor(["-t", "DDP"], nprocs=2, cpu_devices=1, env=env,
                      run_dir=run_dir)
    return {"elastic_nprocs_2_off_cpu": "refused",
            "error": MULTI_WORKER_OFF_CPU.format(n=2)}


PHASES = {
    "device": phase_device,
    "train": phase_train,
    "serve_check": phase_serve_check,
    "kernels": phase_kernels,
    "multichip": phase_multichip,
    "launcher": phase_launcher,
}


def _child() -> None:
    """Entry of a phase's child process: run it, print its JSON line,
    leave the result where the parent reads it."""
    from distributedpytorch_tpu.utils.backend import enable_compilation_cache

    enable_compilation_cache()  # every child, where every entry point puts it
    spec = json.loads(sys.argv[1])
    sizes = Sizes(**{k: tuple(v) if isinstance(v, list) else v
                     for k, v in spec["sizes"].items()})
    result = PHASES[spec["phase"]](
        spec["out"], spec["seed"], sizes, **spec["kwargs"]
    )
    line = {"phase": spec["name"], **result}
    with open(os.path.join(spec["out"], spec["name"] + ".json"), "w") as f:
        json.dump(line, f)
    emit(line)


# ---------------------------------------------------------------------------
# The parent: never imports jax, runs one child at a time
# ---------------------------------------------------------------------------


def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_phase(phase: str, out: str, seed: int, sizes: Sizes,
              name: str | None = None, **kwargs) -> dict:
    name = name or phase
    spec = {"phase": phase, "name": name, "out": out, "seed": seed,
            "sizes": dataclasses.asdict(sizes), "kwargs": kwargs}
    proc = subprocess.run(
        [sys.executable, "-c", "import chip_smoke; chip_smoke._child()",
         json.dumps(spec)],
        cwd=REPO, env=_child_env(),
    )
    if proc.returncode != 0:
        raise PhaseFailed(f"phase {name} exited with code {proc.returncode}")
    with open(os.path.join(out, name + ".json")) as f:
        return json.load(f)


def check_device(found: dict, chips: int) -> None:
    """The one gate: a TPU with at least ``chips`` chips, or nothing."""
    if found.get("platform") != "tpu" or found.get("count", 0) < chips:
        raise PhaseFailed(
            f"needs {chips} TPU chip(s); jax found {found}. "
            "chip_smoke.py does not run on anything else."
        )


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(url: str, data: bytes | None = None, timeout: float = 120.0):
    req = urllib.request.Request(url, data=data)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read()


def run_serve(name: str, out: str, sizes: Sizes, images: list,
              kernels: str = "xla", buckets: tuple | None = None,
              ready_timeout_s: float = 900.0) -> dict:
    """Start the serve CLI as a child, POST ``images`` to ``/predict``,
    read ``/stats``, stop it. Returns the AOT counters and mask paths."""
    port = _free_port()
    log_path = os.path.join(out, f"{name}.log")
    cmd = [
        sys.executable, "-u", "-m", "distributedpytorch_tpu", "serve",
        "-c", "singleGPU",
        "--checkpoint-dir", os.path.join(out, "train_xla", "checkpoints"),
        "--image-size", *map(str, sizes.image_wh),
        "--buckets", *map(str, buckets or sizes.buckets),
        "--kernels", kernels, "--port", str(port),
        "--aot-cache", os.path.join(out, f"aot_store_{kernels}"),
        *_model_flags(sizes),
    ]
    base = f"http://127.0.0.1:{port}"
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=out, env=_child_env(),
                                stdout=log, stderr=subprocess.STDOUT)
    try:
        while True:
            if proc.poll() is not None:
                raise PhaseFailed(
                    f"{name}: serve exited with code {proc.returncode} "
                    f"before it was ready:\n{_tail(log_path)}")
            if time.monotonic() - t0 > ready_timeout_s:
                raise PhaseFailed(f"{name}: not ready in {ready_timeout_s}s")
            try:
                if json.loads(_http(base + "/healthz", timeout=5))["ready"]:
                    break
            except (urllib.error.URLError, OSError, ValueError, KeyError):
                pass
            time.sleep(0.5)
        startup_s = time.monotonic() - t0
        masks = []
        for i, image in enumerate(images):
            with open(image, "rb") as f:
                png = _http(base + "/predict", data=f.read())
            mask_path = os.path.join(out, f"{name}_mask{i}.png")
            with open(mask_path, "wb") as f:
                f.write(png)
            masks.append([image, mask_path])
        stats = json.loads(_http(base + "/stats"))
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    line = {
        "phase": name, "kernels": kernels,
        "startup_seconds": round(startup_s, 1),
        "requests": len(masks), "masks": masks,
        "aot_cache": stats["aot_cache"],
    }
    with open(os.path.join(out, name + ".json"), "w") as f:
        json.dump(line, f)
    emit({k: v for k, v in line.items() if k != "masks"})
    return line


def _tail(path: str, n: int = 3000) -> str:
    with open(path, errors="replace") as f:
        return f.read()[-n:]


def _request_images(out: str, sizes: Sizes) -> list:
    root = os.path.join(out, "data", "train_hq")
    return [os.path.join(root, f)
            for f in sorted(os.listdir(root))[:sizes.n_requests]]


def _mask_mismatch(path_a: str, path_b: str) -> float:
    """Share of differing pixels between two mask PNGs (PIL + numpy: the
    parent stays off jax)."""
    import numpy as np
    from PIL import Image

    a, b = np.asarray(Image.open(path_a)), np.asarray(Image.open(path_b))
    return 1.0 if a.shape != b.shape else float(np.mean(a != b))


def run_one_chip(out: str, seed: int, sizes: Sizes) -> None:
    train = run_phase("train", out, seed, sizes, name="train_xla_result",
                      kernels="xla")
    check_device(train, 1)

    images = _request_images(out, sizes)
    cold = run_serve("serve_1", out, sizes, images)
    warm = run_serve("serve_2", out, sizes, images)
    n_buckets = len(sizes.buckets)
    if cold["aot_cache"]["compiles"] != n_buckets:
        raise PhaseFailed(f"first serve start: {cold['aot_cache']}")
    if (warm["aot_cache"]["compiles"] != 0
            or warm["aot_cache"]["hit"] != n_buckets):
        raise PhaseFailed(
            f"second serve start did not load every bucket from the "
            f"store with zero compiles: {warm['aot_cache']}")
    for (_, a), (_, b) in zip(cold["masks"], warm["masks"]):
        if _mask_mismatch(a, b) != 0.0:
            raise PhaseFailed(f"store-loaded executable answers differ: {b}")
    check_device(run_phase("serve_check", out, seed, sizes), 1)

    check_device(run_phase("kernels", out, seed, sizes), 1)
    pallas = run_phase("train", out, seed, sizes, name="train_pallas_result",
                       kernels="pallas")
    check_device(pallas, 1)
    for key in ("loss_mean_steps_1_10", "val_loss"):
        a, b = pallas[key], train[key]
        a, b = (a[-1], b[-1]) if isinstance(a, list) else (a, b)
        if not abs(a - b) <= PALLAS_LOSS_RTOL * abs(b):
            raise PhaseFailed(f"--kernels pallas {key} {a} vs xla {b}")
    served = run_serve("serve_pallas", out, sizes, images[:1],
                       kernels="pallas", buckets=sizes.buckets[:1])
    diff = _mask_mismatch(served["masks"][0][1], cold["masks"][0][1])
    emit({"phase": "serve_pallas_vs_xla", "mismatch_fraction": diff})
    if diff > MASK_MISMATCH_MAX:
        raise PhaseFailed(f"--kernels pallas mask differs on {diff:.2%}")


def run_four_chips(out: str, seed: int, sizes: Sizes) -> None:
    check_device(run_phase("multichip", out, seed, sizes), 4)
    run_phase("launcher", out, seed, sizes)


def run(chips: int, seed: int, out: str, sizes: Sizes = FULL) -> dict:
    """All phases for ``chips``; returns the device for the last line.
    Raises :class:`PhaseFailed` on the first phase that fails."""
    os.makedirs(out, exist_ok=True)
    device = run_phase("device", out, seed, sizes)
    check_device(device, chips)
    (run_four_chips if chips == 4 else run_one_chip)(out, seed, sizes)
    return {k: device[k] for k in ("platform", "kind", "count")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"),
                    help="output directory (git-ignored by default)")
    args = ap.parse_args(argv)
    try:
        device = run(args.chips, args.seed, os.path.abspath(args.out))
    except PhaseFailed as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
