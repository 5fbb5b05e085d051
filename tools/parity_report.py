#!/usr/bin/env python3
"""Evaluate the framework checkpoint AND the reference's torch checkpoint
with THIS framework's loss/Dice on the SAME validation subset, and emit
the parity table (the "equal validation Dice" comparison the north star
asks for, manufactured on CPU since no GPU exists here).

Inputs are the artifacts of the two training runs on the shared tree:
  * ours:      checkpoints/<tag>/singleGPU.ckpt
               (tools/convergence_run.py --data-dir <tree>)
  * reference: <ref-out>/singleGPU.pth
               (tools/reference_parity_run.py — torch CPU, same split)
The torch weights enter through the tested `.pth` interop
(checkpoint.import_reference_pth, NCHW→NHWC transposes), so both models
are evaluated by literally the same jitted eval step over the same
batches — metric definitions cannot diverge between stacks.

Usage: python tools/parity_report.py [--tree .scratch/parity_tree]
    [--tag parity_r05] [--ref-out .scratch/parity_ref]
    [--image-size 192 128] [--out logs/parity_r05/report.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_PROVISIONED_ENV = "_DPT_PARITY_REPORT_PROVISIONED"


def main() -> int:
    from distributedpytorch_tpu.utils.provision import (
        maybe_reexec_provisioned,
    )

    child_rc = maybe_reexec_provisioned(1, _PROVISIONED_ENV)
    if child_rc is not None:
        return child_rc
    from distributedpytorch_tpu.utils.backend import enable_compilation_cache

    enable_compilation_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--tree",
                    default=os.path.join(REPO, ".scratch", "parity_tree"))
    ap.add_argument("--tag", default="parity_r05")
    ap.add_argument("--ref-out",
                    default=os.path.join(REPO, ".scratch", "parity_ref"))
    ap.add_argument("--image-size", type=int, nargs=2, default=(192, 128),
                    metavar=("W", "H"))
    ap.add_argument("--out",
                    default=os.path.join(REPO, "logs", "parity_r05",
                                         "report.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.checkpoint import (
        import_reference_pth,
        load_checkpoint,
    )
    from distributedpytorch_tpu.data.dataset import build_dataset
    from distributedpytorch_tpu.data.loader import DataLoader, seeded_split
    from distributedpytorch_tpu.evaluate import evaluate
    from distributedpytorch_tpu.models.unet import UNet
    from distributedpytorch_tpu.train.steps import make_eval_step

    w, h = args.image_size
    dataset = build_dataset(
        os.path.join(args.tree, "train_hq"),
        os.path.join(args.tree, "train_masks"),
        (w, h),
    )
    _train_idx, val_idx = seeded_split(len(dataset), 0.10, seed=0)
    val_loader = DataLoader(
        dataset, indices=val_idx, batch_size=4, shuffle=False,
        drop_last=True, num_workers=0,
    )

    model = UNet(dtype=jnp.float32, s2d_levels=0)
    template = model.init(
        jax.random.key(0), jnp.zeros((1, h, w, 3)))["params"]
    eval_step = jax.jit(make_eval_step(model))

    results = {}

    ours_path = os.path.join(REPO, "checkpoints", args.tag,
                             "singleGPU.ckpt")
    ckpt = load_checkpoint(ours_path, template)
    results["framework"] = dict(zip(
        ("val_loss", "val_dice"),
        evaluate(eval_step, ckpt["params"], val_loader),
    ))

    ref_path = os.path.join(args.ref_out, "singleGPU.pth")
    ref_params = import_reference_pth(ref_path, template)
    results["reference_torch"] = dict(zip(
        ("val_loss", "val_dice"),
        evaluate(eval_step, ref_params, val_loader),
    ))

    # Steady-state train throughput from each stack's own (Step, Time)
    # rows — the reference's instrumentation format
    # (reference utils/train_utils.py:75-79), which BASELINE.md names as
    # THE comparison source for imgs/sec. Last half of the rows: skips
    # the compile/warmup-skewed start identically for both stacks.
    import pandas as pd

    def steady_imgs_per_sec(pkl_path, batch_size=4):
        if not os.path.exists(pkl_path):
            return None
        df = pd.read_pickle(pkl_path)
        if len(df) < 4:
            return None
        half = df.iloc[len(df) // 2:]
        dt = float(half["Time"].iloc[-1] - half["Time"].iloc[0])
        dstep = int(half["Step"].iloc[-1] - half["Step"].iloc[0])
        return round(dstep * batch_size / dt, 3) if dt > 0 else None

    results["framework"]["train_imgs_per_sec"] = steady_imgs_per_sec(
        os.path.join(REPO, "loss", args.tag, "singleGPU", "train_loss.pkl"))
    results["reference_torch"]["train_imgs_per_sec"] = steady_imgs_per_sec(
        os.path.join(args.ref_out, "train_loss.pkl"))

    for name in ("framework", "reference_torch"):
        results[name] = {
            k: (round(float(v), 5) if v is not None else None)
            for k, v in results[name].items()
        }
    report = {
        "val_images": int(len(val_idx)),
        "image_size": [w, h],
        "evaluator": "framework eval step (bce_dice_loss + hard Dice), "
                     "identical for both checkpoints",
        **results,
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report))
    print("\n| stack | val loss | val Dice | steady imgs/s (1-core CPU) |")
    print("|---|---:|---:|---:|")
    for name, label in (("framework", "this framework (JAX, CPU)"),
                        ("reference_torch", "reference (torch, CPU)")):
        print(f"| {label} | {results[name]['val_loss']} "
              f"| {results[name]['val_dice']} "
              f"| {results[name]['train_imgs_per_sec']} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
