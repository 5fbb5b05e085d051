"""Metric family catalog — one place, created eagerly at import.

Every family the train loop, the serve tier, and the elastic supervisor
record into is defined HERE, against the process-wide registry, so any
``/metrics`` endpoint in any process exposes the full catalog (families
a given process never touches expose at zero / header-only — the
Prometheus-idiomatic shape, and what the acceptance check "exposition
covering train, serve, and supervisor metric families" keys on).

Import as ``from distributedpytorch_tpu.obs import defs as obsm`` —
the ``obsm.`` prefix is what dptlint's ``obs-hot-path`` rule matches
when checking that no metric update happens inside a jit/shard_map-
traced function (docs/ANALYSIS.md).

The full catalog with semantics lives in docs/OBSERVABILITY.md.
"""

from __future__ import annotations

from distributedpytorch_tpu.obs.registry import REGISTRY
from distributedpytorch_tpu.obs.reqtrace import SERVICE_TIME_BOUNDS

# -- train (recorded by train/loop.py + utils/metrics.py at drain
#    boundaries — never on the dispatch hot path) ---------------------------
TRAIN_STEPS = REGISTRY.counter(
    "dpt_train_steps_total", "Optimizer steps completed")
TRAIN_IMAGES = REGISTRY.counter(
    "dpt_train_images_total", "Training images consumed")
TRAIN_LOSS = REGISTRY.gauge(
    "dpt_train_loss", "Last drained mean-of-window train loss")
TRAIN_VAL_LOSS = REGISTRY.gauge(
    "dpt_train_val_loss", "Last epoch validation loss")
TRAIN_VAL_DICE = REGISTRY.gauge(
    "dpt_train_val_dice", "Last epoch validation Dice")
TRAIN_STEP_SECONDS = REGISTRY.histogram(
    "dpt_train_step_seconds",
    "Host-observed step-loop iteration time (dispatch cadence, not "
    "device latency)",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
             5.0, 10.0, 30.0, 60.0, 300.0),
)
TRAIN_IMGS_PER_S = REGISTRY.gauge(
    "dpt_train_imgs_per_s", "Steady-state training throughput")
TRAIN_RETRIES = REGISTRY.counter(
    "dpt_train_retries_total",
    "Bounded-backoff retries of transient host failures", ("site",))
TRAIN_ROLLBACKS = REGISTRY.counter(
    "dpt_train_rollbacks_total",
    "Checkpoint rollbacks consumed by the non-finite-loss policy")
TRAIN_SKIPPED_STEPS = REGISTRY.counter(
    "dpt_train_skipped_steps_total",
    "Updates discarded by the non-finite-loss 'skip' policy")
CACHE_HITS = REGISTRY.counter(
    "dpt_host_cache_hits_total", "Decoded-sample cache hits")
CACHE_MISSES = REGISTRY.counter(
    "dpt_host_cache_misses_total", "Decoded-sample cache misses")
CACHE_HIT_RATIO = REGISTRY.gauge(
    "dpt_host_cache_hit_ratio", "Decoded-sample cache hit rate [0, 1]")

# -- sparse expert layers (ops/moe.py): counted inside the compiled step,
#    read back WITH the step's loss (utils/metrics.StepReadout), per expert
#    block of the model ------------------------------------------------------
MOE_ROWS_ROUTED = REGISTRY.counter(
    "dpt_moe_rows_routed_total",
    "(token, slot) rows routed to the experts held here", ("block",))
MOE_ROWS_COMPUTED = REGISTRY.counter(
    "dpt_moe_rows_computed_total",
    "Rows the grouped expert product multiplied, tile padding included",
    ("block",))
MOE_ROWS_MAX_EXPERT = REGISTRY.gauge(
    "dpt_moe_rows_max_expert",
    "Rows of the fullest held expert in the last step read back",
    ("block",))
# -- the fused attention kernel (ops/attention_pallas.py): which path the
#    model's attention blocks took, decided from platform and shapes
#    (ops/sequence.attention_path) and set once when the Trainer is built --
ATTENTION_KERNEL_BLOCKS = REGISTRY.gauge(
    "dpt_attention_kernel_blocks",
    "Attention blocks of the model whose shapes take the fused kernel "
    "(0: all run as blocked XLA)")
# -- the held experts' weight gradients (ops/moe.py): which path the grouped
#    product over the sorted rows took, decided from platform and shapes
#    (ops/moe.wgrad_path) and set once when the Trainer is built ----------
MOE_WGRAD_KERNEL_LAYERS = REGISTRY.gauge(
    "dpt_moe_wgrad_kernel_layers",
    "Sparse expert layers of the model whose weight gradients take the "
    "grouped_wgrad kernel (0: all run the plain loop over tiles)")
# -- the token model's recomputation (models/twotower.py): what each
#    block's ``jax.checkpoint`` keeps besides the block's input, decided
#    from shapes and the device's memory (``twotower.kept_budget``) and set once
#    when the Trainer is built -------------------------------------------
KEPT_ACTIVATION_BYTES = REGISTRY.gauge(
    "dpt_kept_activation_bytes",
    "Bytes of named activations a step keeps across its blocks' backward "
    "passes (0: each block's input alone is kept and all else recomputed)")
# -- the attention path's own work (ops/sequence.attention_pairs): counted
#    from the shapes, the tile and the window by the rule that sets the
#    kernel's loop bounds (the blocked path counts its query blocks against
#    the keys sliced for them), read back with the step's loss, per layer --
ATTENTION_PAIRS_COMPUTED = REGISTRY.counter(
    "dpt_attention_pairs_computed_total",
    "(query, key) pairs the attention path multiplied, over the sequences "
    "and the query heads, what its masks then threw away included",
    ("block",))
_STEP_COUNTERS = {
    "moe_rows_routed": MOE_ROWS_ROUTED.labels,
    "moe_rows_computed": MOE_ROWS_COMPUTED.labels,
    "moe_rows_max_expert": MOE_ROWS_MAX_EXPERT.labels,
    "attention_pairs_computed": ATTENTION_PAIRS_COMPUTED.labels,
}


def record_step_counters(names, values) -> None:
    """One step's counters, named ``<family>/<block>`` by the model
    (models/twotower.counter_names), into their families: totals are
    added to, a ``max`` is set."""
    for name, value in zip(names, values):
        family, _, block = name.partition("/")
        child = _STEP_COUNTERS[family](block=block)
        if family.endswith("_max_expert"):
            child.set(float(value))
        else:
            child.inc(float(value))


# -- serve (recorded by serve/metrics.py off the dispatch loop) -------------
SERVE_REQUESTS = REGISTRY.counter(
    "dpt_serve_requests_total", "Requests resolved", ("status",))
SERVE_IMAGES = REGISTRY.counter(
    "dpt_serve_images_total", "Images served successfully")
SERVE_REJECTIONS = REGISTRY.counter(
    "dpt_serve_rejections_total", "Requests rejected at admission",
    ("reason",))
SERVE_DISPATCHES = REGISTRY.counter(
    "dpt_serve_dispatches_total", "Bucket executables dispatched",
    ("bucket",))
SERVE_PAD_ROWS = REGISTRY.counter(
    "dpt_serve_pad_rows_total", "Pad rows dispatched")
SERVE_REAL_ROWS = REGISTRY.counter(
    "dpt_serve_real_rows_total", "Real rows dispatched")
SERVE_FLUSHES = REGISTRY.counter(
    "dpt_serve_queue_flushes_total",
    "Batching-queue flush decisions by regime "
    "(full/deadline/eager/shed)", ("kind",))
SERVE_QUEUE_DEPTH = REGISTRY.gauge(
    "dpt_serve_queue_depth_images", "Pending images in the batching queue")
SERVE_LATENCY = REGISTRY.histogram(
    "dpt_serve_latency_seconds", "Request latency, admission to response",
    buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
             10.0, 30.0),
)
SERVE_QUEUE_SECONDS = REGISTRY.histogram(
    "dpt_serve_queue_seconds", "Queueing delay, admission to dispatch",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
             5.0),
)
SERVE_PREDICT_CACHE = REGISTRY.counter(
    "dpt_serve_predict_cache_total",
    "Clipper-style prediction-cache lookups (exact-match on the "
    "decoded-input hash)", ("result",))
SERVE_CORE_RESTARTS = REGISTRY.counter(
    "dpt_serve_core_restarts_total",
    "In-process dispatch-core relaunches after a dispatch-loop death")
SERVE_WEIGHTS_VERSION = REGISTRY.gauge(
    "dpt_serve_weights_version",
    "Weights version promoted to every replica group (0 = the "
    "startup checkpoint)")
SERVE_ROLLOUTS = REGISTRY.counter(
    "dpt_serve_rollouts_total",
    "Weight-rollout attempts by outcome "
    "(promoted/rolled_back/swap_failed/load_failed)", ("outcome",))
SERVE_ROLLOUT_CANARY = REGISTRY.gauge(
    "dpt_serve_rollout_canary",
    "1 while a rollout canary is being health-watched, else 0")
SERVE_REPLICA_HINT = REGISTRY.gauge(
    "dpt_serve_replica_hint",
    "Recommended replica count from queue-depth/shed hysteresis "
    "(the signal serve/scaler.py actuates)")
SERVE_REPLICAS = REGISTRY.gauge(
    "dpt_serve_replicas",
    "Live replica-group size (moved without restart by the "
    "autoscaler — serve/scaler.py)")
SERVE_SCALE_EVENTS = REGISTRY.counter(
    "dpt_serve_scale_events_total",
    "Autoscaler actuations on the live replica group, each citing the "
    "plan-serve grid point it executes", ("direction",))
SERVE_AB_REQUESTS = REGISTRY.counter(
    "dpt_serve_ab_requests_total",
    "Sustained-A/B requests by arm and resolution (server-side view; "
    "the router's ledger discards hedge losers)", ("arm", "status"))
SERVE_AB_ACTIVE = REGISTRY.gauge(
    "dpt_serve_ab_active",
    "1 while a sustained A/B pins two weight versions to disjoint "
    "replica groups, else 0")
AOT_CACHE = REGISTRY.counter(
    "dpt_aot_cache_total",
    "AOT executable store events (utils/aotstore.py): hit = loaded a "
    "serialized executable (zero compiles), miss = no entry "
    "(compile-and-persist), skew = entry present but corrupt or "
    "runtime/identity-skewed (refused loudly, recompiled), evicted = "
    "removed by `aot gc`", ("result",))

# -- request tracing (obs/reqtrace.py; recorded from completion workers
#    and ingress rejection paths — never the dispatch loop) -----------------
# one ladder (reqtrace.SERVICE_TIME_BOUNDS) for both: these histograms
# and the dpt_serve_profile artifact must describe the SAME
# distribution, or planner calibration drifts from what /metrics shows
SERVE_PHASE_SECONDS = REGISTRY.histogram(
    "dpt_serve_phase_seconds",
    "Per-request phase attribution from the span ledger "
    "(decode/queue_wait/placement/dispatch_wait/device_exec/drain)",
    ("phase",),
    buckets=SERVICE_TIME_BOUNDS,
)
SERVE_DEVICE_EXEC = REGISTRY.histogram(
    "dpt_serve_device_exec_seconds",
    "Host-observed device execution time per bucket size (the "
    "per-bucket service-time profile the capacity planner calibrates "
    "against)",
    ("bucket",),
    buckets=SERVICE_TIME_BOUNDS,
)
SERVE_SLOW_REQUESTS = REGISTRY.counter(
    "dpt_serve_slow_requests_total",
    "Requests above the slow-request threshold (each one structured-"
    "logged with its full span ledger and request id)")
SERVE_SLO_BURN_FAST = REGISTRY.gauge(
    "dpt_serve_slo_burn_fast",
    "Error-budget burn rate over the fast window (1.0 = spending "
    "exactly the budget; >1 = on track to exhaust it)")
SERVE_SLO_BURN_SLOW = REGISTRY.gauge(
    "dpt_serve_slo_burn_slow",
    "Error-budget burn rate over the slow window")

# -- router front door (recorded by serve/router.py; jax-free) --------------
ROUTER_REQUESTS = REGISTRY.counter(
    "dpt_router_requests_total",
    "Front-door requests by final client-visible HTTP code (transparent "
    "retries collapse into one row here)", ("code",))
ROUTER_RETRIES = REGISTRY.counter(
    "dpt_router_retries_total",
    "Transparent resubmissions to a sibling worker "
    "(connection = dead worker ejected mid-request, shed = 503 honored)",
    ("reason",))
ROUTER_HEDGES = REGISTRY.counter(
    "dpt_router_hedges_total",
    "Hedged duplicate requests past the p99 deadline, by which copy "
    "answered the client (primary/hedge) — the loser is cancelled and "
    "never counted as a request", ("winner",))
ROUTER_WORKER_EVENTS = REGISTRY.counter(
    "dpt_router_worker_events_total",
    "Worker-pool transitions (eject on connection failure, readmit on "
    "/healthz readiness, stale on a missed stats scrape)", ("event",))
ROUTER_HEALTHY_WORKERS = REGISTRY.gauge(
    "dpt_router_healthy_workers", "Workers currently in the routable pool")
ROUTER_HA_EVENTS = REGISTRY.counter(
    "dpt_router_ha_events_total",
    "Active/standby pair transitions (takeover = standby promoted "
    "itself after the active missed a probe, demote = a router yielded "
    "the active role to a higher-epoch peer, sync = standby imported "
    "the active's /admin/state snapshot)", ("event",))

# -- elastic supervisor (recorded by dist/elastic.py; jax-free) -------------
ELASTIC_RESTARTS = REGISTRY.counter(
    "dpt_elastic_restarts_total", "Supervisor relaunches of the job")
ELASTIC_WORLD_SIZE = REGISTRY.gauge(
    "dpt_elastic_world_size", "Ranks in the current/last attempt")
ELASTIC_RANK_FAILURES = REGISTRY.counter(
    "dpt_elastic_rank_failures_total",
    "Per-rank failure verdicts across attempts", ("failure_class",))
ELASTIC_ATTEMPTS = REGISTRY.counter(
    "dpt_elastic_attempts_total", "Launch attempts by outcome",
    ("outcome",))
FLEET_SCALE_EVENTS = REGISTRY.counter(
    "dpt_fleet_scale_events_total",
    "Supervisor-level fleet actuations: whole serve workers spawned or "
    "retired (dist/elastic.py FleetScaler), each citing the plan-serve "
    "grid point it executes — the process-level sibling of "
    "dpt_serve_scale_events_total", ("direction",))

# -- obs itself -------------------------------------------------------------
FLIGHT_DUMPS = REGISTRY.counter(
    "dpt_flight_dumps_total", "Flight-recorder artifacts written",
    ("reason_class",))
