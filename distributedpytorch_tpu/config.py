"""Run configuration.

Replaces the reference's argparse constants + hardcoded paths
(reference train.py:15-31, utils/train_utils.py:19-20, 26) with one dataclass.
Field defaults mirror the reference CLI defaults (reference train.py:18-24).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass
class TrainConfig:
    # -- strategy -----------------------------------------------------------
    # A legacy strategy name — "singleGPU" (kept for CLI parity;
    # single-device), "DP", "DDP", "MP", "DDP_MP", "SP" / "DDP_SP",
    # "TP", "FSDP" — or a mesh spec "DxMxS[@fsdp|sp]" naming an
    # arbitrary point on the N-D ('data','model','stage') mesh
    # (parallel/mesh.py; docs/DISTRIBUTED.md "The mesh engine"):
    # e.g. "4x1x2" (data x pipeline), "2x2x1" (data x tensor),
    # "2x2x1@fsdp" (FSDP x tensor), "1x4x1@sp" (spatial). The legacy
    # names are aliases into the same mesh-rule engine — each resolves
    # to its mesh config at strategy construction and reproduces
    # bit-identically as the equivalent spec.
    train_method: str = "singleGPU"

    # -- optimization (reference train.py:18-24 defaults) -------------------
    epochs: int = 10
    learning_rate: float = 1e-4
    batch_size: int = 4
    val_percent: float = 10.0  # percent, divided by 100 like train_utils.py:35
    seed: int = 42
    weight_decay: float = 1e-8  # Adam L2, reference train_utils.py:45

    # Reference quirk 1 (SURVEY.md §2): `(batch_size * loss).backward()` while
    # recording the unscaled loss. Reproduced by default for curve parity.
    faithful_loss_scaling: bool = True
    # Reference quirk 2: DDP multiplies lr by world_size (train_utils.py:199).
    ddp_lr_world_size_scaling: bool = True

    # -- LR schedule: ReduceLROnPlateau(mode='min', patience=2) -------------
    plateau_patience: int = 2
    plateau_factor: float = 0.1

    # -- data ---------------------------------------------------------------
    data_dir: str = "./data"
    images_subdir: str = "train_hq"
    masks_subdir: str = "train_masks"
    # (W, H) like the reference's `newsize=[960,640]` (train_utils.py:26);
    # preprocess reads it as (newW, newH) (dataloading.py:29).
    image_size: Tuple[int, int] = (960, 640)
    num_workers: int = 0  # host-side prefetch threads (0 = synchronous)
    # Device-placement prefetch depth: host→device transfer of batch i+1..i+k
    # overlaps the device's compute of batch i. Applies to K-stacked
    # dispatch payloads too (the whole stack/place pipeline runs on the
    # worker, see utils/prefetch.pipelined_placement). 0 = place
    # synchronously (the bitwise-identical baseline the equivalence tests
    # compare against).
    prefetch_batches: int = 2
    # Epoch-persistent decoded-sample cache budget (data/dataset.SampleCache,
    # MiB of host RAM): epochs >= 2 serve whatever fit from memory instead of
    # re-running PIL/libjpeg decode on identical files every epoch. Shared by
    # the train and val loaders. 0 disables. No eviction — see SampleCache.
    host_cache_mb: int = 1024

    # -- pipeline (MP) ------------------------------------------------------
    num_microbatches: int = 2  # reference hardcodes 2 (unet_model.py:25)
    # Stages in the GPipe schedule. 2 = the reference's encoder|decoder cut
    # (unet_model.py:16-20); any S up to the model's 2L+1 segments works —
    # the bubble is (S−1)/(M+S−1), so raise num_microbatches with S.
    num_stages: int = 2
    # Where stages begin, as model-segment indices (see UNet.apply_segment:
    # L encoder levels, mid, L decoder levels+head). None = the faithful
    # 2-stage cut for S=2, an even split otherwise.
    pipeline_cuts: Optional[Tuple[int, ...]] = None
    # Pipeline schedule (parallel/pipeline.py):
    #   "gpipe" — fill-drain, differentiated through the shard_map; peak
    #             activation memory grows linearly with num_microbatches
    #             (every microbatch's stage activations stay live until
    #             the backward drains);
    #   "1f1b"  — PipeDream-flush: explicit per-tick vjp backward with at
    #             most ~S in-flight microbatches per stage, so peak
    #             activation memory is bounded by the stage count and M
    #             becomes a free throughput lever (the M=8/16 rows that
    #             OOM or remat under gpipe at batch 4). Grad-equivalent
    #             to gpipe (tests/test_pipeline_1f1b.py).
    # Default gpipe: no on-chip A/B of the two schedules is on record
    # (PERF.md §7 row 4).
    pipeline_schedule: str = "gpipe"

    # -- precision (ops/precision.py, docs/PERFORMANCE.md "Precision") ------
    # The mixed-precision policy, --dtype:
    #   "f32"         pure-float32 reference (what equivalence bands are
    #                 measured against);
    #   "bf16"        bf16 conv/activation compute on the MXU, f32 params
    #                 and loss — the shipping default, now explicit;
    #   "bf16_params" bf16 compute AND bf16 on-device params (halved param
    #                 bytes + FSDP all-gather traffic) with f32 master
    #                 weights living in optimizer state (Micikevicius et
    #                 al.'s recipe). Loss/Dice accumulation, wgrad
    #                 accumulation, and the schedule-closing grad psums
    #                 stay f32 under EVERY policy (the stated contracts,
    #                 precision.LOSS_DTYPE/WGRAD_DTYPE/REDUCE_DTYPE).
    dtype: str = "bf16"
    # Legacy compute-dtype override (pre-policy tests/benches pass
    # compute_dtype="float32" for exact comparisons): None = the policy's
    # own compute dtype; a dtype name overrides conv/activation compute
    # only — param storage and master weights still follow `dtype`.
    compute_dtype: Optional[str] = None

    # -- model --------------------------------------------------------------
    # "unet" = the reference course model (7,760,097 params); "milesial" =
    # the original milesial/Pytorch-UNet it derives from (31,037,698 params
    # at n_classes=2; BatchNorm → stateful training, SyncBN-by-construction
    # under data-parallel meshes; reference model/modelsummary.txt:150-247).
    model_arch: str = "unet"
    # None = the architecture's documented channel plan. Narrower tuples
    # build faster-compiling variants for tests.
    model_widths: Optional[Tuple[int, ...]] = None
    # A token model ("twotower", models/twotower.py; "lfm2", models/lfm2.py;
    # "smallthinker", models/smallthinker.py; models/__init__.py holds the table) is built at its published share;
    # a mapping of its size keys here shrinks it for tests and rehearsals
    # (as model_widths does a UNet).
    model_overrides: Optional[dict] = None
    # Tokens to a packed sequence of a token model's batch (data/tokens.py);
    # -b counts sequences.
    seq_len: int = 8192
    # Shallow levels executed in the space-to-depth domain (ops/s2d.py):
    # exactly equivalent numerics, measured ~1.9× step-time win on TPU v5e at
    # the reference config (the full-res C=32/64 convs starve the 128-lane
    # MXU; their s2d forms don't). -1 = auto: 2 on a TPU backend, 0 elsewhere
    # (the rewrite's 4× nominal MACs only pay off on the MXU).
    # 0 = plain pixel-domain execution. Explicit 3 is supported and proven
    # exact (tests/test_s2d.py level-3 cases, both model families) — a
    # re-measure lever for geometries where level 3 still starves the MXU;
    # auto stays at 2 (level 3 regressed at the reference geometry,
    # docs/PERFORMANCE.md).
    s2d_levels: int = -1

    @property
    def model_levels(self) -> int:
        """Number of 2× downsamplings — what spatial strategies divide H by.

        unet: one pool per width entry. milesial: the first width is the
        stem (inc) — pools = len(widths) − 1."""
        if self.model_arch == "milesial":
            n = len(self.model_widths) if self.model_widths else 5
            return n - 1
        return len(self.model_widths) if self.model_widths else 4

    # -- artifacts (paths mirror the reference layout, §1 layer map) --------
    checkpoint_dir: str = "./checkpoints"
    log_dir: str = "./logs"
    loss_dir: str = "./loss"
    checkpoint_name: Optional[str] = None  # -c flag: load this checkpoint
    # Mid-run checkpointing (crash recovery the reference lacks, SURVEY.md
    # §5 'Failure detection'): save every N epochs; 0 = final save only.
    checkpoint_every_epochs: int = 1
    # Keep a separate <method>_best.ckpt at the highest val Dice seen.
    save_best: bool = False
    # Serialize + write checkpoints on a background thread (the device→host
    # snapshot still happens inline — donated buffers force that): epoch
    # saves stop stalling the step loop. The trainer drains pending writes
    # before train() returns, so a checkpoint is always durable by the time
    # anything could read it. False = fully synchronous saves.
    async_checkpoint: bool = True
    # Stop when val loss has not improved for N consecutive epochs
    # (0 = off). Deterministic across processes: every rank sees the same
    # val loss (sharded eval returns identical values everywhere), so all
    # ranks stop together.
    early_stop_patience: int = 0

    # -- resilience (utils/faults.py, docs/RELIABILITY.md) ------------------
    # Policy when a train-step loss reads back non-finite (detection
    # piggybacks the metrics readback — zero cost on healthy runs):
    #   "abort"    raise NonFiniteLossError (default: fail loudly; under
    #              fit_with_restarts / --max-restarts this already retries
    #              from the last epoch checkpoint);
    #   "rollback" reload the newest intact checkpoint in-place and redo
    #              from its epoch, up to `rollback_retries` times, then
    #              abort;
    #   "skip"     check each step's loss synchronously (one device sync
    #              per step — costs pipeline overlap; state donation is
    #              disabled) and discard the update of any non-finite
    #              step. Incompatible with fused dispatch / grad accum.
    nonfinite_policy: str = "abort"
    rollback_retries: int = 2
    # Bounded exponential-backoff retries for transient host failures in
    # the data decode path and the placement worker (OSError family):
    # attempt i sleeps retry_backoff_s * 2**i. 0 retries = fail fast.
    data_retries: int = 3
    retry_backoff_s: float = 0.05
    # Dispatch watchdog: a step-loop iteration exceeding this many seconds
    # dumps the step-timeline tracer's per-phase spans and requests a
    # checkpoint-and-stop via the collective stop agreement. 0 = off.
    # The FIRST executed epoch is untimed (it compiles every executable
    # shape — a minute or more of compiles — which would false-fire
    # any steady-state-sized timeout); coverage starts at epoch 2.
    step_timeout_s: float = 0.0
    # Checkpoint retention: keep the newest N files per checkpoint path
    # (<tag>.ckpt, <tag>.ckpt.1, ...). Restore verifies each file's
    # content hash and falls back to the newest intact one, so N >= 2
    # makes a torn newest file recoverable. 1 = overwrite in place.
    keep_checkpoints: int = 2
    # Deterministic fault injection (tests / drills): "site[@rank]:
    # epoch:step[:count]" specs, sites in utils/faults.SITES ("@rank"
    # pins a fault to one process of a multi-process job). Empty = inert.
    inject_faults: Tuple[str, ...] = ()
    # Elastic runtime (dist/health.py, dist/elastic.py): when set, the
    # trainer writes a per-rank beat file (rank_R.beat) into this
    # directory from a daemon thread — the supervisor's failure
    # detector. The step loop only assigns attributes per iteration
    # (no host sync, no collective); the thread writes at
    # heartbeat_interval_s cadence. None = no heartbeat (non-elastic
    # runs are untouched). Normally armed by the supervisor, which
    # appends --heartbeat-dir to every worker it launches.
    heartbeat_dir: Optional[str] = None
    heartbeat_interval_s: float = 0.5

    # -- synthetic data (tests / benches without the Carvana download) ------
    synthetic_samples: int = 0  # >0: use an in-memory procedural dataset

    # -- memory -------------------------------------------------------------
    # Rematerialize the forward during backward (jax.checkpoint): ~half the
    # activation HBM for ~1/3 more FLOPs. Off by default (HBM is ample at
    # the reference config); turn on for big batches / high resolutions.
    remat: bool = False

    # -- kernels (ops/kernels.py, docs/PERFORMANCE.md "Kernels") ------------
    # The Pallas kernel-engagement policy, --kernels:
    #   "xla"     no Pallas fast paths — every output bit-identical to
    #             the historical paths (the correctness reference);
    #   "pallas"  the full kernel tier: fused training-loss stats
    #             (ops/fused_loss.py), one-pass eval stats
    #             (ops/pallas_kernels.py), the fused DoubleConv
    #             BN+ReLU epilogue (milesial), and the serve tier's
    #             sigmoid/threshold mask kernel. A kernel Mosaic
    #             refuses fails the run, unless a priors file the
    #             operator hands over (kernel_priors /
    #             DPT_KERNEL_PRIORS) marks it rejected.
    kernels: str = "xla"
    # Per-chip Mosaic probe priors file (tools/probe_kernels.py →
    # ops/kernels.load_priors): kernels the chip's compiler rejected
    # disengage loudly. None = also honors $DPT_KERNEL_PRIORS.
    kernel_priors: Optional[str] = None
    # LEGACY alias (pre-policy flag, kept like compute_dtype → --dtype):
    # True resolves to its historical engagement set — the fused
    # training loss + eval stats kernels only — with a loud log. An
    # explicit kernels="pallas" supersedes it. Prefer --kernels.
    use_pallas: bool = False

    # -- dispatch amortization ----------------------------------------------
    # K optimizer steps per XLA dispatch (lax.scan over K stacked batches).
    # Semantically identical to K single steps on the same data; amortizes
    # per-dispatch runtime latency (its share of the step on the attached
    # chip: not measured). 1 = one dispatch per step (reference-shaped).
    steps_per_dispatch: int = 1

    # -- gradient accumulation ----------------------------------------------
    # ONE optimizer step per K loader batches (effective batch K·b) with
    # one batch's activation memory — EXACT for the non-additive log-dice
    # loss via the two-pass stats/cotangent scheme (train/steps.py
    # make_accum_train_step). Stateless models only; mutually exclusive
    # with steps_per_dispatch > 1. An epoch's trailing batches that don't
    # fill K train as ordinary single steps.
    grad_accum: int = 1

    # -- observability (distributedpytorch_tpu/obs, docs/OBSERVABILITY.md) --
    metric_every_steps: int = 10  # reference records every 10 (train_utils.py:75)
    profile_dir: Optional[str] = None  # jax.profiler trace capture when set
    # Step-timeline tracer (utils/trace.py): per-phase host spans
    # (decode/stack/h2d/dispatch/readback) appended to this JSONL path;
    # summarized by utils/trace.summarize_timeline, exported to Perfetto
    # by obs/trace_hub.py.
    # Multi-process runs: rank 0 writes the path, rank R appends .rankR.
    # None = JSONL off (spans still feed the flight recorder's ring).
    timeline_path: Optional[str] = None
    # Serve GET /metrics (Prometheus text exposition of the process-wide
    # registry) + /healthz on this port for the run's lifetime. Rank R of
    # a multi-process job binds port+R (one scrape target per rank).
    # 0 = ephemeral (tests read trainer.metrics_server.port); None = off.
    metrics_port: Optional[int] = None
    # On-demand device profile over a step range: capture a
    # jax.profiler trace from global step N until M (inclusive:exclusive)
    # into profile_dir (default <log_dir>/profile). None = off.
    profile_steps: Optional[Tuple[int, int]] = None

    @property
    def precision(self):
        """Convenience accessor for the resolved
        :class:`~distributedpytorch_tpu.ops.precision.PrecisionPolicy`.
        The resolver is ``ops.precision.get_policy(config)`` (honoring
        the legacy ``compute_dtype`` override) — layers call it directly
        because it also accepts duck-typed configs; this property wraps
        the same call for TrainConfig holders, so there is exactly one
        resolution path."""
        from distributedpytorch_tpu.ops.precision import get_policy

        return get_policy(self)

    @property
    def kernel_policy(self):
        """Convenience accessor for the resolved
        :class:`~distributedpytorch_tpu.ops.kernels.KernelPolicy` — the
        resolver is ``ops.kernels.get_kernel_policy(config)`` (honoring
        the legacy ``use_pallas`` alias and the Mosaic probe priors);
        this property wraps the same call, so there is exactly one
        resolution path (the precision property's pattern)."""
        from distributedpytorch_tpu.ops.kernels import get_kernel_policy

        return get_kernel_policy(self)

    @property
    def val_fraction(self) -> float:
        return self.val_percent / 100.0

    @property
    def method_tag(self) -> str:
        """Artifact directory tag, e.g. ./loss/<tag>/ and ./logs/<tag>.log."""
        return self.train_method


@dataclasses.dataclass
class ServeConfig:
    """The serving tier's knobs (serve/, docs/SERVING.md) — what
    ``python -m distributedpytorch_tpu serve`` parses into and what
    ``tools/bench_serve.py`` sweeps over.

    Model-identity fields (arch/widths/geometry/s2d) must match the
    trained checkpoint, exactly like predict.py's flags — both surfaces
    resolve them through the same ``serve/infer.load_inference_bundle``.
    """

    # -- model / checkpoint (must match training) ---------------------------
    checkpoint: str = ""
    checkpoint_dir: str = "./checkpoints"
    image_size: Tuple[int, int] = (960, 640)  # (W, H), CLI flag order
    model_arch: str = "unet"
    model_widths: Optional[Tuple[int, ...]] = None
    s2d_levels: int = -1
    threshold: float = 0.5
    # Weights-only quantization for the serving path (--quantize):
    #   None   — serve the checkpoint's own float weights;
    #   "int8" — per-output-channel symmetric int8 weights resident on
    #            device (param bytes quartered vs f32), dequantized
    #            inside the AOT-compiled forward. Accepts either a
    #            regular checkpoint (quantized on load) or a file
    #            written by tools/quantize.py (which also records the
    #            source hash in its manifest). Dice parity vs the float
    #            checkpoint is pinned by tests/test_quantize.py.
    quantize: Optional[str] = None
    # Kernel-engagement policy for the serving path (--kernels,
    # ops/kernels.py): "pallas" traces the fused sigmoid/threshold mask
    # kernel INSIDE every AOT bucket executable — the executable returns
    # the {0,255} uint8 mask itself (1 byte/pixel D2H instead of 4 f32,
    # no host threshold pass), bit-identical to the "xla" path's
    # postprocess at the operating threshold. Honors the Mosaic probe
    # priors exactly like training.
    kernels: str = "xla"
    kernel_priors: Optional[str] = None
    # Content-addressed AOT executable store (--aot-cache,
    # utils/aotstore.py, docs/PERFORMANCE.md "AOT executable store"):
    # startup LOADS each bucket executable from this directory instead
    # of compiling on hit, compiles-and-persists on miss; corrupt or
    # version-skewed entries are refused loudly and recompiled. None =
    # resolve from $DPT_AOT_CACHE (unset = off); "" = force off.
    aot_cache: Optional[str] = None

    # -- batching -----------------------------------------------------------
    # The padded bucket ladder: every dispatch rides one of exactly these
    # batch shapes, each AOT-compiled per replica at startup (first
    # request pays zero compiler time). More buckets = less padding but
    # more startup compiles.
    bucket_sizes: Tuple[int, ...] = (1, 2, 4, 8)
    # Latency SLO for the batching wait: a request is flushed (in the
    # smallest covering bucket) at most this long after admission even
    # if its bucket never fills.
    slo_ms: float = 50.0
    # Work-conserving dispatch: with an idle replica, flush immediately
    # instead of waiting for the SLO — batches form exactly when
    # capacity (not the clock) is the bottleneck. False = pure SLO
    # batching (throughput-biased; useful for bench A/Bs).
    eager_when_idle: bool = True
    # Pending-image admission cap (None = 4x the largest bucket): beyond
    # it submits are rejected ("overloaded"), so queue depth — and with
    # it queueing latency — is bounded by construction under overload.
    queue_cap_images: Optional[int] = None

    # -- execution ----------------------------------------------------------
    # Data-parallel replica groups over the local devices (clamped to
    # the devices present). Serving is collective-free: N replicas serve
    # N concurrent buckets independently.
    replicas: int = 1
    # Buckets stacked + H2D-placed ahead of dispatch on the placement
    # worker (utils/prefetch.pipelined_placement); 0 = synchronous.
    placement_depth: int = 2
    # Dispatched-but-undrained buckets allowed per replica: the device
    # queue keeps one bucket behind the executing one (H2D overlaps
    # compute) but can never absorb unbounded backlog — in-flight slots
    # return at COMPLETION, so total work-in-system stays bounded and
    # overload surfaces as rejections instead of silent latency growth.
    inflight_per_replica: int = 2
    # None = one drain thread per in-flight slot (the drain pool must
    # never be the throughput ceiling).
    completion_workers: Optional[int] = None
    # SampleCache budget (MiB) for path-keyed request decode; 0 = off.
    host_cache_mb: int = 256
    # Clipper-style prediction cache (serve/cache.py): exact-match
    # masks keyed on the decoded-input hash + weights version, bounded
    # LRU over this byte budget. 0 = off.
    predict_cache_mb: int = 0

    # -- self-healing (serve/server.py, docs/SERVING.md "Fleet") ------------
    # In-process dispatch-core relaunch budget: a dead dispatch loop
    # rebuilds (fresh queue + thread against the same AOT engine) up to
    # this many times with exponential backoff; exhausted = the server
    # goes terminal so a process supervisor (elastic --workload serve)
    # relaunches the whole worker.
    restart_limit: int = 3
    restart_backoff_s: float = 0.25
    # Elastic supervision (dist/elastic.py --workload serve): when set,
    # the serve worker writes per-rank beat files — the dispatch loop
    # ticks progress every turn, so a wedged pipeline stops the ticks
    # and the supervisor's progress timeout catches it. Normally armed
    # by the supervisor itself.
    heartbeat_dir: Optional[str] = None
    heartbeat_interval_s: float = 0.5
    # Deterministic chaos (utils/faults.py serve sites:
    # serve_dispatch_death / serve_replica_wedge / serve_decode /
    # swap_crash) — drills the relaunch and rollback paths.
    inject_faults: Tuple[str, ...] = ()

    # -- weight rollout (serve/rollout.py) ----------------------------------
    # Replica groups the candidate canaries on before promotion.
    canary_replicas: int = 1
    # Health-watch window: the canary serves real traffic this long
    # before the gauges + Dice probe judge it.
    rollout_window_s: float = 5.0
    # Pinned-sample probe images (paths, decoded through the engine);
    # empty = gauge-only gating. The canary's masks must score within
    # rollout_dice_margin of the old weights' masks on these samples.
    rollout_probe: Tuple[str, ...] = ()
    rollout_dice_margin: float = 0.02
    # Poll this checkpoint path and roll out (canaried) whenever the
    # file is replaced; None = off. The serve CLI's --watch-checkpoint
    # defaults it to the serving checkpoint's own path.
    watch_checkpoint: Optional[str] = None
    watch_poll_s: float = 2.0

    # -- autoscale (serve/autoscale.py hint + serve/scaler.py actuator) -----
    # Cadence of the replica-count recommendation (gauge + log line)
    # from queue-depth/shed hysteresis. 0 = off.
    autoscale_interval_s: float = 30.0
    # ACT on the hint: grow/shrink the live replica group through
    # Server.resize_replicas (AOT-store-backed, no restart). Requires
    # the hint (autoscale_interval_s > 0); off by default — actuation
    # is opt-in, the hint alone is free.
    autoscale_act: bool = False
    # dpt_serve_plan artifact (analysis/serve_planner.py plan-serve):
    # every scale decision cites the grid point it executes. None =
    # decisions still happen, cited as plan_point=None.
    serve_plan: Optional[str] = None
    # Actuation bounds + anti-flap cooldown (None = the hint's own
    # hysteresis window count).
    min_replicas: int = 1
    max_replicas: Optional[int] = None
    scale_cooldown_windows: Optional[int] = None

    # -- sustained A/B (serve/rollout.py ABTest; POST /admin/ab) ------------
    # Arm "b" traffic fraction when an A/B starts without an explicit
    # split in the request body.
    ab_split: float = 0.5

    # -- request tracing (obs/reqtrace.py, docs/OBSERVABILITY.md) -----------
    # End-to-end "good request" latency bound the SLO burn-rate windows
    # judge against. None = 2x slo_ms (the batching wait plus a
    # comparable service allowance).
    latency_slo_ms: Optional[float] = None
    # Structured-log threshold: any served request slower than this logs
    # ONE JSON line with its id + full span ledger (and lands in the
    # flight ring). <= 0 = 2x the latency SLO.
    slow_request_ms: float = 0.0
    # Per-request span JSONL (the serve analogue of --trace-timeline on
    # training runs): rank 0 writes the path, rank R appends .rankR; the
    # elastic supervisor arms it per attempt and merges the workers into
    # one fleet Perfetto timeline. None = no span export (the ledger
    # ring, /stats attribution, slow-request log, and flight-ring
    # reject/slow events all stay on regardless).
    trace_timeline: Optional[str] = None
    # Arrival-trace recording (serve/sim.py ArrivalRecorder): one
    # bounded JSONL line per ingress (wall-time, decoded rows/shape,
    # covering bucket) — the recorded-trace input `plan-serve` replays
    # against a profiled service-time model. None = off; the line cap
    # bounds the file for long-running servers.
    record_arrivals: Optional[str] = None
    record_arrivals_limit: int = 200_000

    # -- transport ----------------------------------------------------------
    host: str = "127.0.0.1"
    port: int = 8008
