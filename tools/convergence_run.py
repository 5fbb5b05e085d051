#!/usr/bin/env python3
"""The val-Dice half of the north star: a bounded convergence run with
committed loss/Dice curves.

The north star is "matches or beats the 2×GPU DDP config in imgs/sec AT
EQUAL VALIDATION DICE" — but the reference never computes Dice at all
(reference evaluate.py:18-21 tracks val loss only); this framework defined
the metric (ops/losses.dice_coefficient) and therefore has to produce it.
With zero egress the Carvana download is unreachable, so the run uses the
procedural segmentation dataset (data/dataset.SyntheticSegmentationDataset:
a brightened-ellipse target — genuinely learnable, deterministic, and the
same item contract as the Carvana loader) at the REFERENCE HYPERPARAMETERS
(10 epochs, Adam 1e-4, batch 4, 10% val, seed 42 — reference train.py:18-24)
with resolution reduced to what a 1-core CPU box can traverse in-session;
``--tpu`` is the full-resolution run on the chip.

Usage (the documented, reproducible command):
    python tools/convergence_run.py [--epochs 10] [--samples 160]
        [--image-size 192 128] [--outdir-tag convergence_r05]

On-chip (the full-resolution north-star config — this process then
owns the chip, so nothing else may hold it):
    python tools/convergence_run.py --tpu --image-size 960 640 \
        --steps-per-dispatch 8 --outdir-tag convergence_r05_tpu

Artifacts: loss/<tag>/{train_loss.pkl,val_loss.pkl,val_dice.pkl}
(reference pickle format, utils/metrics.py), checkpoints/<tag>/,
logs/<tag>/run.json with the final metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_PROVISIONED_ENV = "_DPT_CONVERGENCE_PROVISIONED"


def main() -> int:
    # CPU by default: the env that names it must be set BEFORE the
    # training interpreter exists — re-exec via the shared helper.
    from distributedpytorch_tpu.utils.provision import (
        maybe_reexec_provisioned,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--samples", type=int, default=160)
    ap.add_argument("--image-size", type=int, nargs=2, default=(192, 128),
                    metavar=("W", "H"))
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--outdir-tag", default="convergence_r05")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the TPU at the shipping bf16 config "
                    "instead of a provisioned CPU backend")
    ap.add_argument("--steps-per-dispatch", type=int, default=1,
                    help="fuse K train steps per device dispatch (the "
                    "trainer's --steps-per-dispatch)")
    ap.add_argument("--model-arch", default="unet",
                    choices=("unet", "milesial"),
                    help="model family (milesial = the public 31M-param "
                    "upstream architecture, reference modelsummary.txt:150-247)")
    ap.add_argument("--data-dir", default=None,
                    help="train from a Carvana-layout tree on disk instead "
                    "of the in-memory synthetic dataset (used by the "
                    "reference-parity program: both stacks read the same "
                    "files)")
    args = ap.parse_args()

    # --tpu runs on the real chip instead: no CPU provisioning, shipping
    # bf16 compute, K-step fused dispatch. Decided from the PARSED args,
    # not an argv string-match, so argparse prefix forms ("--tp") behave.
    if not args.tpu:
        child_rc = maybe_reexec_provisioned(1, _PROVISIONED_ENV)
        if child_rc is not None:
            return child_rc
    from distributedpytorch_tpu.utils.backend import (
        enable_compilation_cache,
        require_accelerator,
    )

    enable_compilation_cache()
    require_accelerator("convergence_run")

    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.train import Trainer

    # Artifacts anchor to the repo, not the cwd — tools/parity_report.py
    # reads them repo-anchored, and a run launched from elsewhere would
    # otherwise scatter checkpoints/loss/logs under that cwd.
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tag = args.outdir_tag
    config = TrainConfig(
        train_method="singleGPU",
        model_arch=args.model_arch,
        epochs=args.epochs,
        learning_rate=args.lr,
        batch_size=args.batch_size,
        val_percent=10.0,
        seed=42,
        # CPU runs pin float32 (no MXU, and bf16 emulation is slow there);
        # the on-chip run uses the shipping bf16 config — the north-star
        # claim is about THAT config's throughput and val Dice.
        compute_dtype="bfloat16" if args.tpu else "float32",
        steps_per_dispatch=args.steps_per_dispatch,
        image_size=tuple(args.image_size),
        synthetic_samples=0 if args.data_dir else args.samples,
        data_dir=args.data_dir or "./data",
        checkpoint_dir=os.path.join(repo, "checkpoints", tag),
        log_dir=os.path.join(repo, "logs", tag),
        loss_dir=os.path.join(repo, "loss", tag),
        save_best=True,
        metric_every_steps=10,
        # On-chip, host-side synthetic-item generation (~30 ms/img on this
        # 1-core box) would serialize with ~27 ms/img chip time — prefetch
        # threads overlap it with device execution.
        num_workers=2 if args.tpu else 0,
    )
    trainer = Trainer(config)
    result = trainer.train()
    os.makedirs(config.log_dir, exist_ok=True)
    with open(os.path.join(config.log_dir, "run.json"), "w") as f:
        json.dump(
            {
                "config": {
                    "epochs": args.epochs,
                    "model_arch": args.model_arch,
                    "data_dir": args.data_dir,
                    # synthetic samples actually served (0 = disk tree)
                    "samples": config.synthetic_samples,
                    "image_size": list(args.image_size),
                    "batch_size": args.batch_size,
                    "learning_rate": args.lr,
                    "val_percent": 10.0,
                    "seed": 42,
                    "tpu": args.tpu,
                    "compute_dtype": config.compute_dtype,
                    "steps_per_dispatch": args.steps_per_dispatch,
                },
                "result": {k: (float(v) if hasattr(v, "__float__") else v)
                           for k, v in result.items()},
            },
            f, indent=2,
        )
    print("convergence run done:", result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
