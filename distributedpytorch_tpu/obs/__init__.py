"""Unified telemetry layer: metrics registry + Prometheus exposition,
Perfetto trace export, and a crash-dumping flight recorder.

Until this subsystem existed, the repo's observability was three
non-composing fragments: ``utils/trace.py`` wrote step spans only under
``--trace-timeline``, ``serve/metrics.py`` was a serve-private
snapshot, and ``dist/health.py`` beat files were supervisor-internal —
so a dead or stalled run left no artifact saying *where* (chip windows
r03–r05, ROADMAP "Recent"). In the spirit of Dapper-style always-on
tracing and MLPerf-logging-style standardized run records, telemetry is
now a first-class subsystem every run carries by default:

* :mod:`~distributedpytorch_tpu.obs.registry` — the process-wide
  metrics registry (counters / gauges / bounded-window histograms,
  labels, lock-cheap updates) with Prometheus text exposition and a
  strict format checker. Train, serve, and supervisor families are
  cataloged in :mod:`~distributedpytorch_tpu.obs.defs` (import it as
  ``obsm``). Served at ``GET /metrics`` on the serve HTTP front and on
  ``--metrics-port`` training runs (:mod:`~distributedpytorch_tpu.obs.http`).
* :mod:`~distributedpytorch_tpu.obs.trace_hub` — rank-tagged step-span
  events exported as Perfetto/Chrome trace JSON, merged across ranks
  by the elastic supervisor; device profiles via the trainer's
  ``--profile-steps N:M``.
* :mod:`~distributedpytorch_tpu.obs.flight` — the always-on bounded
  ring buffer of recent events, dumped to a JSON post-mortem artifact
  on watchdog timeout, dispatch-loop death, non-finite-loss abort,
  SIGTERM, and unhandled exit.

Hot-path contract (enforced by dptlint's ``obs-hot-path`` rule,
docs/ANALYSIS.md): nothing in a record path blocks on a device value or
grows without bound, and no ``obs``/``obsm``/``flight`` call appears
inside a jit/shard_map-traced function. ``DPT_OBS=0`` disables flight
recording (the overhead A/B lever; measured < 1% in
docs/OBSERVABILITY.md). The whole package is stdlib-only and jax-free —
the elastic supervisor imports it before any backend exists.
"""

from distributedpytorch_tpu.obs import defs  # noqa: F401 — eager catalog
from distributedpytorch_tpu.obs import flight  # noqa: F401
from distributedpytorch_tpu.obs.registry import (  # noqa: F401
    CONTENT_TYPE,
    REGISTRY,
    MetricsRegistry,
    get_registry,
    validate_exposition,
)
