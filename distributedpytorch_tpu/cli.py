"""Train UNet on images and target masks — TPU-native CLI.

Installed as the ``dpt-train`` console script (pyproject.toml); ``python
train.py`` at the repo root is the same entry point under the reference's
launch surface.

Flag-for-flag parity with the reference entry point (reference
train.py:15-26): same short/long names, same defaults, same ``-t`` method
names (singleGPU | DP | DDP | MP), plus the new ``DDP_MP`` hybrid and a few
additive flags (--synthetic, --microbatches, --profile-dir, --export-pth).

Launch parity (reference README.md:25-44):
    python3 train.py                      # single device
    python3 train.py -t DP
    torchrun --standalone --nnodes=1 --nproc_per_node=2 train.py -t DDP -b 2
    python3 train.py -t MP
The torchrun path works because dist/runtime.py maps torchrun's env contract
onto `jax.distributed.initialize` (no NCCL — XLA collectives over ICI).
"""

import argparse
import logging
import os
import sys


def get_args():
    parser = argparse.ArgumentParser(
        description="Train UNet on images and target masks"
    )
    # reference flags (train.py:15-26)
    parser.add_argument("--train-method", "-t", type=str, default="singleGPU",
                        help="Training method: singleGPU | DP | DDP | MP | DDP_MP "
                             "| SP | DDP_SP | TP | FSDP, or a mesh spec "
                             "DxMxS[@fsdp|sp] over the ('data','model',"
                             "'stage') mesh — e.g. 4x1x2 (data x pipe), "
                             "2x2x1 (data x tensor), 2x2x1@fsdp, 1x4x1@sp "
                             "(docs/DISTRIBUTED.md 'The mesh engine'; the "
                             "named methods are aliases into mesh configs)")
    parser.add_argument("--validation", "-v", dest="val", type=float, default=10.0,
                        help="Percentage of data used as validation")
    parser.add_argument("--load", "-l", type=str, default=False,
                        help="Load model from a .pth file (alias of -c, which the "
                             "reference parsed but ignored)")
    parser.add_argument("--epochs", "-e", type=int, default=10, help="Number of epochs")
    parser.add_argument("--learning-rate", "--lr", type=float, default=1e-4,
                        help="Learning rate", dest="lr")
    parser.add_argument("--batch-size", "-b", type=int, default=4, help="Batch size")
    parser.add_argument("--checkpoint", "-c", type=str, default=None,
                        help="File name of the checkpoint to load")
    parser.add_argument("--seed", "-s", type=int, default=42,
                        help="Set seed for reproducibility")
    # additive flags
    parser.add_argument("--data-dir", type=str, default="./data",
                        help="Root containing train_hq/ and train_masks/")
    parser.add_argument("--synthetic", type=int, default=0,
                        help="Use N in-memory synthetic samples instead of disk data")
    parser.add_argument("--image-size", type=int, nargs=2, default=(960, 640),
                        metavar=("W", "H"), help="Resize target (W H)")
    parser.add_argument("--microbatches", type=int, default=2,
                        help="Pipeline microbatches (MP/DDP_MP); reference hardcodes 2")
    parser.add_argument("--stages", type=int, default=2,
                        help="Pipeline stages (MP/DDP_MP); 2 = the "
                             "reference's encoder|decoder cut; bubble is "
                             "(S-1)/(M+S-1), so raise --microbatches with S")
    parser.add_argument("--pipeline-cuts", type=int, nargs="+", default=None,
                        help="Explicit stage boundaries as model-segment "
                             "indices (L encoder levels, mid, L decoder "
                             "levels+head); default: faithful 2-stage cut, "
                             "even split otherwise")
    parser.add_argument("--pipeline-schedule", type=str, default="gpipe",
                        choices=["gpipe", "1f1b"],
                        help="MP/DDP_MP schedule: gpipe (fill-drain; "
                             "activation memory grows with --microbatches) "
                             "or 1f1b (PipeDream-flush; in-flight memory "
                             "bounded by --stages, grad-equivalent)")
    parser.add_argument("--num-workers", type=int, default=4,
                        help="Host-side decode threads")
    parser.add_argument("--prefetch-batches", type=int, default=2,
                        help="Batches (or K-stacks) placed on device ahead "
                             "of compute (each pins one payload of HBM; "
                             "0 = synchronous)")
    parser.add_argument("--host-cache-mb", type=int, default=1024,
                        help="Host RAM budget (MiB) for the epoch-persistent "
                             "decoded-sample cache; epochs >= 2 skip decode "
                             "for whatever fits (0 = off)")
    parser.add_argument("--sync-checkpoint", action="store_true",
                        help="Write checkpoints synchronously instead of on "
                             "the background writer thread")
    parser.add_argument("--trace-timeline", type=str, default=None,
                        metavar="PATH",
                        help="Append per-phase step-timeline spans "
                             "(decode/stack/h2d/dispatch/readback) to this "
                             "JSONL file; summarize with "
                             "utils/trace.summarize_timeline, export "
                             "to Perfetto via obs/trace_hub.py (rank R of "
                             "a multi-process run writes PATH.rankR)")
    parser.add_argument("--metrics-port", type=int, default=None,
                        metavar="PORT",
                        help="Serve Prometheus /metrics (+ /healthz) on "
                             "this port for the run (rank R binds PORT+R; "
                             "0 = ephemeral)")
    parser.add_argument("--profile-steps", type=str, default=None,
                        metavar="N:M",
                        help="Capture a jax.profiler device trace from "
                             "global step N until step M into "
                             "--profile-dir (default <log-dir>/profile)")
    parser.add_argument("--steps-per-dispatch", type=int, default=1,
                        help="Optimizer steps fused into one XLA dispatch "
                             "(amortizes runtime dispatch latency)")
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="Accumulate K batches into one optimizer step "
                             "(effective batch K*b, one batch's activation "
                             "memory; exact loss via stats decomposition)")
    parser.add_argument("--remat", action="store_true",
                        help="Rematerialize activations in the backward "
                             "(~half HBM, ~1/3 more FLOPs)")
    parser.add_argument("--kernels", type=str, default="xla",
                        choices=["xla", "pallas"],
                        help="Pallas kernel-engagement policy "
                             "(ops/kernels.py): xla = no fast paths "
                             "(bit-identical reference, default); pallas "
                             "= fused loss stats, one-pass eval stats, "
                             "the DoubleConv BN+ReLU epilogue, and the "
                             "serve mask kernel — each revocable by the "
                             "Mosaic probe priors")
    parser.add_argument("--kernel-priors", type=str, default=None,
                        help="Per-chip Mosaic probe priors file "
                             "(tools/probe_kernels.py): kernels the "
                             "chip's compiler rejected disengage loudly")
    parser.add_argument("--pallas", action="store_true",
                        help="LEGACY alias for the fused loss/eval-stats "
                             "kernels only — prefer --kernels pallas")
    parser.add_argument("--dtype", type=str, default="bf16",
                        choices=["f32", "bf16", "bf16_params"],
                        help="Mixed-precision policy (ops/precision.py): "
                             "f32 = pure-float32 reference; bf16 = bf16 "
                             "conv compute with f32 params/loss (default); "
                             "bf16_params = bf16 on-device params (halved "
                             "param bytes) with f32 master weights in "
                             "optimizer state. Loss, wgrad accumulation, "
                             "and grad psums stay f32 under every policy")
    parser.add_argument("--s2d-levels", type=int, default=-1,
                        help="Shallow UNet levels executed in the "
                             "space-to-depth domain (exact numerics, ~1.9x "
                             "faster on TPU); 0 disables, -1 = auto "
                             "(2 on TPU, 0 elsewhere)")
    parser.add_argument("--model", "--model-arch", dest="model_arch",
                        type=str, default="unet",
                        choices=["unet", "milesial", "twotower", "lfm2",
                                 "smallthinker"],
                        help="Model (models/__init__.py holds the table): "
                             "the reference course UNet (7.76M params), the "
                             "original milesial/Pytorch-UNet (31M params, "
                             "BatchNorm), or 'twotower': one chip's share "
                             "(667M params) of the Mamba-2 + expert + "
                             "attention tower of Nemotron-Labs-TwoTower-"
                             "30B-A3B's config.json, or 'lfm2': one chip's "
                             "share (788M params) of LFM2-24B-A2B's "
                             "config.json (gated short convolutions, "
                             "QK-normed attention, dense and sparse "
                             "SwiGLU feed-forwards), or 'smallthinker': one "
                             "chip's share (657M params) of SmallThinker-"
                             "21BA3B-Instruct's config.json (sliding-window "
                             "attention with rotary and full attention "
                             "without positions, a router that reads the "
                             "layer's input, softmax-gated ReGLU experts); "
                             "all three trained on packed token sequences "
                             "(-t singleGPU only)")
    parser.add_argument("--seq-len", type=int, default=8192,
                        help="Tokens to a packed sequence of a token "
                             "model's batch (-b counts sequences)")
    parser.add_argument("--model-widths", type=int, nargs="+", default=None,
                        help="Encoder channel widths (default 32 64 128 256, "
                             "the reference model; e.g. 64 128 256 512 for a "
                             "4x wider ~31M-param variant)")
    parser.add_argument("--profile-dir", type=str, default=None,
                        help="Capture a jax.profiler trace here")
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="On a crash, resume from the newest epoch "
                             "checkpoint up to N times (single-process "
                             "runs; multi-process restarts belong to the "
                             "launcher)")
    parser.add_argument("--save-best", action="store_true",
                        help="Keep a separate <method>_best.ckpt at the "
                             "highest validation Dice")
    parser.add_argument("--early-stop", type=int, default=0, metavar="N",
                        help="Stop when val loss has not improved for N "
                             "consecutive epochs (0 = off)")
    parser.add_argument("--export-pth", action="store_true",
                        help="Also export final weights as a reference-format .pth")
    # resilience (utils/faults.py, docs/RELIABILITY.md)
    parser.add_argument("--nonfinite-policy", type=str, default="abort",
                        choices=["abort", "rollback", "skip"],
                        help="On a non-finite train loss: abort (raise), "
                             "rollback (reload the newest intact checkpoint"
                             ", bounded by --rollback-retries), or skip "
                             "(discard that step's update; checks the loss "
                             "synchronously per step)")
    parser.add_argument("--rollback-retries", type=int, default=2,
                        help="Rollback budget for --nonfinite-policy "
                             "rollback before aborting")
    parser.add_argument("--data-retries", type=int, default=3,
                        help="Bounded exponential-backoff retries for "
                             "transient decode / placement failures "
                             "(0 = fail fast)")
    parser.add_argument("--step-timeout", type=float, default=0.0,
                        metavar="SECS",
                        help="Dispatch watchdog: a step exceeding this "
                             "dumps the step-timeline spans and "
                             "checkpoints-and-stops (0 = off)")
    parser.add_argument("--keep-checkpoints", type=int, default=2,
                        help="Retain the newest N checkpoint files per "
                             "path; restore hash-verifies and falls back "
                             "to the newest intact one")
    # default=None, not []: argparse appends into the default object
    # itself, so a shared [] would leak armed faults across repeated
    # get_args() calls in one process
    parser.add_argument("--inject-fault", action="append", default=None,
                        metavar="SITE[@RANK]:EPOCH:STEP[:COUNT]",
                        help="Arm a deterministic fault (repeatable; "
                             "sites: decode, placement, nan_loss, "
                             "ckpt_write, sigterm, rank_kill, rank_hang; "
                             "'*' wildcards, '@RANK' pins one process) — "
                             "for recovery drills and tests")
    # elastic runtime (dist/elastic.py appends these to every worker)
    parser.add_argument("--checkpoint-dir", type=str, default="./checkpoints",
                        help="Where epoch checkpoints live (the elastic "
                             "supervisor resumes from here)")
    parser.add_argument("--heartbeat-dir", type=str, default=None,
                        help="Write a per-rank heartbeat file here (armed "
                             "by the elastic supervisor; off when unset)")
    parser.add_argument("--heartbeat-interval", type=float, default=0.5,
                        help="Heartbeat write cadence in seconds")
    return parser.parse_args()


def resolve_checkpoint_arg(args):
    """The -c/-l aliasing: -c wins, then -l (which the reference parses but
    ignores — here it actually loads, reference train.py:19 vs :23)."""
    return args.checkpoint or args.load or None


def parse_profile_steps(text):
    """``--profile-steps N:M`` → (N, M) with 0 <= N < M."""
    if not text:
        return None
    try:
        lo, _, hi = str(text).partition(":")
        lo, hi = int(lo), int(hi)
    except ValueError:
        raise ValueError(
            f"--profile-steps expects N:M (global steps), got {text!r}"
        ) from None
    if lo < 0 or hi <= lo:
        raise ValueError(
            f"--profile-steps needs 0 <= N < M, got {text!r}"
        )
    return (lo, hi)


def _channel_shaped(exc: BaseException) -> bool:
    """Does this exception look like a dead/flapping runtime channel —
    i.e. a PEER failure, not this rank's own bug? One definition with
    the retry classification (utils/faults.is_transient): the OSError family
    plus grpc/socket-marked RuntimeErrors, which is exactly how a gloo
    peer's death presents on every survivor."""
    from distributedpytorch_tpu.utils.faults import is_transient

    return is_transient(exc)


def main():
    args = get_args()
    from distributedpytorch_tpu.utils.backend import (
        enable_compilation_cache,
        require_accelerator,
    )

    enable_compilation_cache()

    # Multi-process init must precede any other jax call (reference
    # train.py:58's init_process_group slot).
    from distributedpytorch_tpu.dist import initialize_from_env, shutdown

    runtime = initialize_from_env()
    require_accelerator("train")

    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.train import Trainer
    from distributedpytorch_tpu.utils.seeding import set_seed

    set_seed(args.seed)

    config = TrainConfig(
        train_method=args.train_method,
        epochs=args.epochs,
        learning_rate=args.lr,
        batch_size=args.batch_size,
        val_percent=args.val,
        seed=args.seed,
        data_dir=args.data_dir,
        image_size=tuple(args.image_size),
        num_microbatches=args.microbatches,
        num_stages=args.stages,
        pipeline_cuts=tuple(args.pipeline_cuts) if args.pipeline_cuts else None,
        pipeline_schedule=args.pipeline_schedule,
        num_workers=args.num_workers,
        prefetch_batches=args.prefetch_batches,
        host_cache_mb=args.host_cache_mb,
        async_checkpoint=not args.sync_checkpoint,
        timeline_path=args.trace_timeline,
        steps_per_dispatch=args.steps_per_dispatch,
        grad_accum=args.grad_accum,
        remat=args.remat,
        use_pallas=args.pallas,
        kernels=args.kernels,
        kernel_priors=args.kernel_priors,
        model_arch=args.model_arch,
        model_widths=tuple(args.model_widths) if args.model_widths else None,
        seq_len=args.seq_len,
        dtype=args.dtype,
        s2d_levels=args.s2d_levels,
        checkpoint_name=resolve_checkpoint_arg(args),
        synthetic_samples=args.synthetic,
        profile_dir=args.profile_dir,
        save_best=args.save_best,
        early_stop_patience=args.early_stop,
        nonfinite_policy=args.nonfinite_policy,
        rollback_retries=args.rollback_retries,
        data_retries=args.data_retries,
        step_timeout_s=args.step_timeout,
        keep_checkpoints=args.keep_checkpoints,
        inject_faults=tuple(args.inject_fault or ()),
        checkpoint_dir=args.checkpoint_dir,
        heartbeat_dir=args.heartbeat_dir,
        heartbeat_interval_s=args.heartbeat_interval,
        metrics_port=args.metrics_port,
        profile_steps=parse_profile_steps(args.profile_steps),
    )

    # logfile parity: ./logs/{method}.log, append, message-only (reference
    # train.py:37-38) — plus stderr mirroring, rank 0 only.
    os.makedirs(config.log_dir, exist_ok=True)
    handlers = [
        logging.FileHandler(
            os.path.join(config.log_dir, f"{config.method_tag}.log"), mode="a"
        )
    ]
    if runtime.is_main:
        handlers.append(logging.StreamHandler(sys.stderr))
    logging.basicConfig(level=logging.INFO, format="%(message)s", handlers=handlers)
    logging.info("UNet for Carvana Image Masking (Segmentation)")

    try:
        try:
            if args.max_restarts > 0:
                from distributedpytorch_tpu.train import fit_with_restarts

                result, trainer = fit_with_restarts(
                    config, max_restarts=args.max_restarts, return_trainer=True
                )
            else:
                trainer = Trainer(config)
                result = trainer.train()
        except Exception as exc:  # noqa: BLE001 — classified, then re-raised
            if runtime.num_processes > 1 and _channel_shaped(exc):
                # A dead/hung gloo peer surfaces on EVERY survivor as a
                # wall of channel-shaped tracebacks that say nothing
                # about which rank actually failed. Print ONE line and
                # exit with the peer-failure code; the elastic
                # supervisor's health classifier owns the real
                # attribution (`rank R: <dead|hung|desynced> at
                # epoch:step`, dist/health.py) and treats this exit as
                # a casualty, not a cause.
                logging.error(
                    "rank %d: aborting on distributed peer failure "
                    "(%s: %.200s) — see the supervisor's per-rank summary",
                    runtime.process_id, type(exc).__name__, exc,
                )
                # os._exit, NOT sys.exit: SystemExit would unwind into
                # the finally's shutdown(), whose coordination barrier
                # blocks on the very peer that just died (the hazard
                # tests/ddp_worker.py documents) — the survivor would
                # hang until the supervisor SIGKILLs it and the
                # PEER_FAILURE_EXIT attribution would be lost.
                logging.shutdown()
                sys.stdout.flush()
                sys.stderr.flush()
                os._exit(13)  # dist/elastic.PEER_FAILURE_EXIT
            raise
        if args.export_pth and runtime.is_main:
            pth = os.path.join(config.checkpoint_dir, f"{config.method_tag}.pth")
            if config.model_arch == "milesial":
                from distributedpytorch_tpu.checkpoint import export_milesial_pth

                export_milesial_pth(
                    trainer.state.params, trainer.state.model_state, pth
                )
            else:
                from distributedpytorch_tpu.checkpoint import export_reference_pth

                export_reference_pth(trainer.state.params, pth)
        logging.info("Done: %s", result)
    finally:
        shutdown()


if __name__ == "__main__":
    main()
