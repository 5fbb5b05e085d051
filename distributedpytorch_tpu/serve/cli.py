"""``python -m distributedpytorch_tpu serve``: the production serving
entry point — HTTP over the in-process :class:`Server`.

Stdlib-only transport (``http.server.ThreadingHTTPServer``): each
connection gets a handler thread that decodes, submits, and blocks on
the request's future — the continuous-batching queue coalesces across
handler threads, which is exactly the concurrency shape the batching
layer exists for. Endpoints:

* ``POST /predict`` — body: one image (any PIL-decodable format) →
  ``image/png`` mask ({0, 255}); ``503`` + JSON (with a ``Retry-After``
  header) when shed or mid-relaunch (body carries the rejection
  reason), ``400`` on an undecodable body. Request-scoped tracing
  (obs/reqtrace.py): a W3C ``traceparent`` header's trace-id is
  adopted, else an id is assigned at ingress; EVERY answer echoes it
  as ``X-Request-Id``, and its span ledger is attributable via
  ``/stats`` exemplars, the slow-request log, and the flight ring.
* ``GET /healthz``  — **readiness**: 200 + the compiled bucket/replica
  inventory, ``uptime_s``, ``weights_version``, and the build/config
  fingerprint while serving; **503 + ``ready: false``** while the
  dispatch core is relaunching or a rollout canary is in flight.
* ``GET /livez``    — pure liveness: 200 as long as the process answers.
* ``GET /stats``    — the metrics snapshot (p50/p99, imgs/s, queue
  depth, per-bucket dispatch counts, pad ratio, ``weights_version``,
  ``state``, prediction-cache counters). Schema pinned by
  tests/test_serve.py — dashboards depend on it.
* ``GET /metrics``  — Prometheus text exposition of the process-wide
  telemetry registry (distributedpytorch_tpu/obs, docs/OBSERVABILITY.md).
* ``POST /admin/rollout`` — ``{"checkpoint": <path>}``: hot-swap a new
  checkpoint into the running engine through the canary state machine
  (serve/rollout.py) — 202 accepted, 409 if one is already in flight.
  ``GET`` returns the rollout status.
* ``POST /admin/ab`` — sustained weight A/B (serve/rollout.py:ABTest):
  ``{"action": "start", "checkpoint": ..., "split": 0.5}`` pins the
  candidate to half the replica groups; ``{"action": "verdict"}``
  returns per-arm latency/shed + inter-arm Dice; ``{"action": "stop",
  "winner": "a"|"b"}`` promotes the winner fleet-wide. ``GET`` returns
  the A/B status. Behind a router (serve/router.py) the same route
  fans out to every worker.

Example:
    python -m distributedpytorch_tpu serve -c singleGPU --port 8008 \\
        --buckets 1 2 4 8 --slo-ms 50 --replicas 4
    curl -s --data-binary @car.jpg localhost:8008/predict > mask.png

Supervised fleet launch (dist/elastic.py — a dead worker is a
relaunch, not an outage; worker R binds ``--port base+R``):
    python -m distributedpytorch_tpu elastic --workload serve -n 4 -- \\
        -c singleGPU --port 8008 --replicas 1
"""

from __future__ import annotations

import argparse
import concurrent.futures
import io
import json
import logging
import threading
from typing import Optional

logger = logging.getLogger(__name__)


def get_args(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m distributedpytorch_tpu serve",
        description="Serve mask predictions over HTTP with AOT-compiled "
                    "continuous batching",
    )
    parser.add_argument("--checkpoint", "-c", required=True,
                        help="Checkpoint name (e.g. singleGPU) or path "
                             "(.ckpt/.pth)")
    parser.add_argument("--checkpoint-dir", default="./checkpoints")
    parser.add_argument("--image-size", type=int, nargs=2, default=(960, 640),
                        metavar=("W", "H"))
    parser.add_argument("--model", dest="model_arch", type=str,
                        default="unet", choices=["unet", "milesial"],
                        help="Model family the checkpoint was trained with")
    parser.add_argument("--model-widths", type=int, nargs="+", default=None)
    parser.add_argument("--s2d-levels", type=int, default=-1)
    parser.add_argument("--quantize", type=str, default=None,
                        choices=["int8"],
                        help="Serve weights-only int8 (per-out-channel "
                             "symmetric, ops/quant.py): device-resident "
                             "weight bytes quartered vs f32, dequantized "
                             "inside the AOT-compiled forward. Accepts a "
                             "tools/quantize.py file or quantizes a "
                             "regular checkpoint on load")
    parser.add_argument("--threshold", "-t", type=float, default=0.5)
    parser.add_argument("--kernels", type=str, default="xla",
                        choices=["xla", "pallas"],
                        help="Kernel-engagement policy (ops/kernels.py): "
                             "pallas traces the fused sigmoid/threshold "
                             "mask kernel into every AOT bucket "
                             "executable — uint8 masks come back from "
                             "the device (1 byte/pixel D2H, no host "
                             "threshold pass), bit-identical at the "
                             "operating threshold; honors the Mosaic "
                             "probe priors ($DPT_KERNEL_PRIORS)")
    parser.add_argument("--kernel-priors", type=str, default=None,
                        help="Per-chip Mosaic probe priors file "
                             "(tools/probe_kernels.py): kernels the "
                             "chip's compiler rejected disengage loudly")
    parser.add_argument("--aot-cache", type=str, default=None,
                        help="Content-addressed AOT executable store "
                             "directory (utils/aotstore.py; default "
                             "$DPT_AOT_CACHE, unset = off): startup "
                             "loads serialized bucket executables "
                             "instead of compiling on hit, compiles-"
                             "and-persists on miss; corrupt/skewed "
                             "entries are refused loudly and "
                             "recompiled (docs/PERFORMANCE.md)")
    parser.add_argument("--buckets", type=int, nargs="+", default=(1, 2, 4, 8),
                        help="Padded batch bucket ladder — one AOT compile "
                             "per bucket per replica at startup")
    parser.add_argument("--slo-ms", type=float, default=50.0,
                        help="Batching latency SLO: a request waits at most "
                             "this long for its bucket to fill")
    parser.add_argument("--replicas", type=int, default=1,
                        help="Data-parallel replica groups (clamps to the "
                             "devices present)")
    parser.add_argument("--queue-cap", type=int, default=None,
                        help="Pending-image hard cap (default 4x the "
                             "largest bucket); beyond it requests are shed "
                             "with HTTP 503")
    parser.add_argument("--placement-depth", type=int, default=2,
                        help="Buckets stacked+placed ahead of dispatch "
                             "(0 = synchronous placement)")
    parser.add_argument("--inflight-per-replica", type=int, default=2,
                        help="Dispatched-but-undrained buckets per replica "
                             "(bounds work-in-system under overload)")
    parser.add_argument("--completion-workers", type=int, default=None)
    parser.add_argument("--host-cache-mb", type=int, default=256,
                        help="SampleCache budget for path-keyed request "
                             "decode (0 = off)")
    parser.add_argument("--no-eager", action="store_true",
                        help="Disable work-conserving dispatch: wait for "
                             "full buckets or the SLO even when replicas "
                             "are idle (throughput-biased)")
    parser.add_argument("--predict-cache-mb", type=int, default=0,
                        help="Clipper-style prediction cache budget "
                             "(MiB): exact-match masks keyed on the "
                             "decoded-input hash + weights version; "
                             "0 = off")
    parser.add_argument("--restart-limit", type=int, default=3,
                        help="In-process dispatch-core relaunches before "
                             "the worker goes terminal (a process "
                             "supervisor owns the next level)")
    parser.add_argument("--restart-backoff", type=float, default=0.25,
                        help="Base core-relaunch backoff seconds "
                             "(doubles per consecutive restart)")
    parser.add_argument("--canary-replicas", type=int, default=1,
                        help="Replica groups a rollout canaries on "
                             "before promoting to the rest")
    parser.add_argument("--rollout-window", type=float, default=5.0,
                        help="Canary health-watch window (seconds)")
    parser.add_argument("--rollout-probe", type=str, nargs="+",
                        default=None, metavar="IMAGE",
                        help="Pinned probe images: a rollout candidate's "
                             "masks must score within --rollout-dice-"
                             "margin of the old weights' masks on these")
    parser.add_argument("--rollout-dice-margin", type=float, default=0.02)
    parser.add_argument("--watch-checkpoint", type=str, nargs="?",
                        const="", default=None, metavar="PATH",
                        help="Poll a checkpoint file and roll it out "
                             "(canaried) whenever it is replaced; "
                             "without PATH, watches the serving "
                             "checkpoint's own file")
    parser.add_argument("--watch-poll", type=float, default=2.0,
                        help="Checkpoint-watch poll cadence (seconds)")
    parser.add_argument("--autoscale-interval", type=float, default=30.0,
                        help="Cadence of the replica-count "
                             "recommendation (gauge + log line). 0 = off")
    parser.add_argument("--autoscale-act", action="store_true",
                        help="ACT on the replica hint: grow/shrink the "
                             "live replica group without a restart "
                             "(serve/scaler.py; needs --autoscale-"
                             "interval > 0)")
    parser.add_argument("--serve-plan", type=str, default=None,
                        metavar="PLAN_JSON",
                        help="plan-serve artifact (dpt_serve_plan): "
                             "each scale decision cites the grid point "
                             "it executes")
    parser.add_argument("--min-replicas", type=int, default=1,
                        help="Autoscaler floor")
    parser.add_argument("--max-replicas", type=int, default=None,
                        help="Autoscaler ceiling (default: the devices "
                             "present)")
    parser.add_argument("--ab-split", type=float, default=0.5,
                        help="Default arm-b traffic fraction for "
                             "POST /admin/ab starts")
    parser.add_argument("--latency-slo-ms", type=float, default=None,
                        help="End-to-end good-request latency bound for "
                             "the SLO burn-rate gauges (default 2x "
                             "--slo-ms)")
    parser.add_argument("--slow-request-ms", type=float, default=0.0,
                        help="Structured-log threshold: served requests "
                             "slower than this log one JSON line with "
                             "their id + span ledger (<= 0 = 2x the "
                             "latency SLO)")
    parser.add_argument("--trace-timeline", type=str, default=None,
                        metavar="PATH",
                        help="Append per-request span JSONL here (rank R "
                             "writes PATH.rankR under a supervisor); "
                             "merge to Perfetto via obs/trace_hub.py")
    parser.add_argument("--record-arrivals", type=str, default=None,
                        metavar="PATH",
                        help="Record a bounded JSONL arrival trace here "
                             "(ingress wall-time, decoded rows/shape, "
                             "covering bucket per request; rank R of a "
                             "supervised fleet writes PATH.rankR) — the "
                             "recorded-trace input `plan-serve` replays "
                             "for capacity planning (docs/SERVING.md)")
    parser.add_argument("--record-arrivals-limit", type=int,
                        default=200_000,
                        help="Arrival-trace line cap: past it recording "
                             "stops (the trace keeps the head of the "
                             "traffic; the file stays bounded)")
    parser.add_argument("--heartbeat-dir", type=str, default=None,
                        help="Write per-rank beat files here for the "
                             "elastic supervisor (normally armed by "
                             "elastic --workload serve)")
    parser.add_argument("--heartbeat-interval", type=float, default=0.5)
    parser.add_argument("--inject-fault", action="append", default=[],
                        metavar="SITE[:EPOCH:STEP[:COUNT]]",
                        help="Arm a deterministic chaos fault "
                             "(utils/faults.py serve sites: "
                             "serve_dispatch_death, serve_replica_wedge, "
                             "serve_decode, swap_crash)")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8008)
    return parser.parse_args(argv)


def to_config(args):
    """argparse namespace → :class:`ServeConfig` (single source of knob
    names between the CLI and the bench's programmatic construction)."""
    from distributedpytorch_tpu.config import ServeConfig

    return ServeConfig(
        checkpoint=args.checkpoint,
        checkpoint_dir=args.checkpoint_dir,
        image_size=tuple(args.image_size),
        model_arch=args.model_arch,
        model_widths=tuple(args.model_widths) if args.model_widths else None,
        s2d_levels=args.s2d_levels,
        quantize=args.quantize,
        threshold=args.threshold,
        kernels=args.kernels,
        kernel_priors=args.kernel_priors,
        aot_cache=args.aot_cache,
        bucket_sizes=tuple(args.buckets),
        slo_ms=args.slo_ms,
        eager_when_idle=not args.no_eager,
        queue_cap_images=args.queue_cap,
        replicas=args.replicas,
        placement_depth=args.placement_depth,
        inflight_per_replica=args.inflight_per_replica,
        completion_workers=args.completion_workers,
        host_cache_mb=args.host_cache_mb,
        predict_cache_mb=args.predict_cache_mb,
        restart_limit=args.restart_limit,
        restart_backoff_s=args.restart_backoff,
        canary_replicas=args.canary_replicas,
        rollout_window_s=args.rollout_window,
        rollout_probe=tuple(args.rollout_probe or ()),
        rollout_dice_margin=args.rollout_dice_margin,
        watch_checkpoint=args.watch_checkpoint,
        watch_poll_s=args.watch_poll,
        autoscale_interval_s=args.autoscale_interval,
        autoscale_act=args.autoscale_act,
        serve_plan=args.serve_plan,
        min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        ab_split=args.ab_split,
        latency_slo_ms=args.latency_slo_ms,
        slow_request_ms=args.slow_request_ms,
        trace_timeline=args.trace_timeline,
        record_arrivals=args.record_arrivals,
        record_arrivals_limit=args.record_arrivals_limit,
        heartbeat_dir=args.heartbeat_dir,
        heartbeat_interval_s=args.heartbeat_interval,
        inject_faults=tuple(args.inject_fault),
        host=args.host,
        port=args.port,
    )


def build_server(args):
    """args → started-able :class:`Server` (engine AOT-compiles here),
    with the fleet components attached: rollout manager (+ optional
    checkpoint watcher), autoscale hint, armed chaos faults, and — when
    ``--trace-timeline`` is set — the per-request span JSONL (rank R of
    a supervised fleet appends ``.rankR``, the trace-hub convention)."""
    import os

    from distributedpytorch_tpu.serve.server import Server

    cfg = to_config(args)
    if cfg.inject_faults:
        from distributedpytorch_tpu.utils import faults

        faults.install(cfg.inject_faults)
    timeline = None
    if cfg.trace_timeline:
        from distributedpytorch_tpu.utils.trace import StepTimeline

        rank = int(os.environ.get("RANK", "0"))
        path = (cfg.trace_timeline if rank == 0
                else f"{cfg.trace_timeline}.rank{rank}")
        timeline = StepTimeline(path, rank=rank)
    server = Server.from_config(cfg, timeline=timeline)
    if cfg.record_arrivals:
        from distributedpytorch_tpu.serve.sim import ArrivalRecorder

        # rank-suffixed like --trace-timeline: N supervised workers
        # must not truncate/interleave one shared trace file
        rank = int(os.environ.get("RANK", "0"))
        path = (cfg.record_arrivals if rank == 0
                else f"{cfg.record_arrivals}.rank{rank}")
        server.arrival_recorder = ArrivalRecorder(
            path, limit=cfg.record_arrivals_limit,
        )
    attach_fleet(server, cfg)
    return server


def attach_fleet(server, cfg) -> None:
    """Wire the rollout manager, checkpoint watcher, sustained-A/B
    controller, autoscale hint, and — when opted into — the replica
    scaler onto a built server (split out so tests and the bench can
    attach to servers they construct directly). Components start with
    the server and stop with ``server.stop()``."""
    from distributedpytorch_tpu.serve.rollout import (
        ABTest,
        CheckpointWatcher,
        RolloutManager,
    )

    probe_rows = [
        server.engine.preprocess(path) for path in (cfg.rollout_probe or ())
    ]
    server.rollout = RolloutManager(
        server,
        probe_rows=probe_rows or None,
        window_s=cfg.rollout_window_s,
        dice_margin=cfg.rollout_dice_margin,
        canary_replicas=cfg.canary_replicas,
    )
    # always attached (inert until POST /admin/ab start): sharing the
    # rollout probe rows gives the verdict its inter-arm Dice half
    server.abtest = ABTest(
        server, probe_rows=probe_rows or None,
        split=getattr(cfg, "ab_split", 0.5),
    )
    watch = cfg.watch_checkpoint
    if watch is not None:
        if watch == "":  # --watch-checkpoint without a path: watch the
            # serving checkpoint's own resolved file
            from distributedpytorch_tpu.checkpoint import resolve_checkpoint

            watch = resolve_checkpoint(cfg.checkpoint, cfg.checkpoint_dir)
        server.watcher = CheckpointWatcher(
            server.rollout, watch, poll_s=cfg.watch_poll_s
        ).start()
    if cfg.autoscale_interval_s and cfg.autoscale_interval_s > 0:
        from distributedpytorch_tpu.serve.autoscale import AutoscaleHint

        server.autoscale = AutoscaleHint(
            server, interval_s=cfg.autoscale_interval_s
        ).start()
        if getattr(cfg, "autoscale_act", False):
            from distributedpytorch_tpu.serve.scaler import ReplicaScaler

            server.scaler = ReplicaScaler(
                server, server.autoscale,
                plan=getattr(cfg, "serve_plan", None),
                min_replicas=getattr(cfg, "min_replicas", 1),
                max_replicas=getattr(cfg, "max_replicas", None),
                cooldown_windows=getattr(cfg, "scale_cooldown_windows",
                                         None),
            ).start()


def make_http_server(server, host: str = "127.0.0.1", port: int = 0,
                     request_timeout_s: float = 30.0):
    """Wrap a started :class:`Server` in a ThreadingHTTPServer (port 0 =
    ephemeral; read the bound port off ``.server_address``)."""
    import time

    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from PIL import Image

    from distributedpytorch_tpu.obs.http import (
        build_fingerprint,
        healthz_payload,
        metrics_response,
    )
    from distributedpytorch_tpu.obs.reqtrace import (
        new_request_id,
        request_id_from_headers,
    )
    from distributedpytorch_tpu.serve.server import (
        STATUS_REJECTED,
        STATUS_SHUTDOWN,
    )

    started_t = time.monotonic()
    fingerprint = build_fingerprint(getattr(server, "config", None))

    class Handler(BaseHTTPRequestHandler):
        def _json(self, code: int, obj: dict,
                  retry_after: Optional[int] = None,
                  request_id: Optional[str] = None) -> None:
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if retry_after is not None:
                # every 503 carries the back-off hint: "relaunching" and
                # "overloaded" mean retry HERE after this many seconds
                self.send_header("Retry-After", str(int(retry_after)))
            if request_id:
                self.send_header("X-Request-Id", request_id)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):  # noqa: N802 — http.server's contract
            if self.path == "/healthz":
                # READINESS (the LB signal): 503 + ready:false while the
                # dispatch core is between incarnations or a rollout
                # canary is in flight — /livez stays 200 (don't restart
                # a process that is busy healing itself)
                ready = server.ready
                self._json(
                    200 if ready else 503,
                    healthz_payload(
                        started_t, fingerprint, ready=ready,
                        state=server.state,
                        weights_version=server.engine.weights_version,
                        buckets=list(server.engine.planner.sizes),
                        replicas=server.engine.num_replicas,
                    ),
                    retry_after=None if ready else 1,
                )
            elif self.path == "/livez":
                self._json(200, {"status": "alive"})
            elif self.path == "/stats":
                self._json(200, server.stats())
            elif self.path == "/admin/rollout":
                manager = server.rollout
                if manager is None:
                    self._json(404, {"error": "no rollout manager "
                                              "attached to this server"})
                else:
                    self._json(200, manager.status())
            elif self.path == "/admin/ab":
                abtest = server.abtest
                if abtest is None:
                    self._json(404, {"error": "no A/B controller "
                                              "attached to this server"})
                else:
                    self._json(200, abtest.status())
            elif self.path == "/metrics":
                # burn gauges decay with their windows: re-derive at
                # scrape time so a quiet worker's burn reads 0, not the
                # last error burst's value frozen forever
                server.tracer.refresh_burn_gauges()
                body, ctype = metrics_response()
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self._json(404, {"error": f"no route {self.path}"})

        def _admin_rollout(self, body: bytes) -> None:
            from distributedpytorch_tpu.serve.rollout import (
                RolloutInProgress,
            )

            manager = server.rollout
            if manager is None:
                self._json(404, {"error": "no rollout manager attached "
                                          "to this server"})
                return
            try:
                spec = json.loads(body or b"{}")
                checkpoint = spec["checkpoint"]
            except (ValueError, KeyError, TypeError):
                self._json(400, {
                    "error": 'body must be JSON: {"checkpoint": <path>}',
                })
                return
            try:
                manager.start(checkpoint, label=str(checkpoint))
            except RolloutInProgress as exc:
                self._json(409, {"error": str(exc),
                                 "status": manager.status()})
                return
            self._json(202, {"accepted": True, "status": manager.status()})

        def _admin_ab(self, body: bytes) -> None:
            """Sustained A/B lifecycle (serve/rollout.py:ABTest) —
            ``{"action": "start", "checkpoint": ..., "split": 0.5}`` /
            ``{"action": "verdict"}`` / ``{"action": "stop",
            "winner": "a"|"b"}``."""
            from distributedpytorch_tpu.serve.rollout import (
                RolloutInProgress,
            )

            abtest = server.abtest
            if abtest is None:
                self._json(404, {"error": "no A/B controller attached "
                                          "to this server"})
                return
            try:
                spec = json.loads(body or b"{}")
                action = spec["action"]
            except (ValueError, KeyError, TypeError):
                self._json(400, {
                    "error": 'body must be JSON with an "action" of '
                             'start|verdict|stop',
                })
                return
            try:
                if action == "start":
                    if "split" in spec:
                        abtest.split = min(max(float(spec["split"]), 0.0),
                                           1.0)
                    status = abtest.start(
                        spec["checkpoint"],
                        label=str(spec.get("label", spec["checkpoint"])),
                    )
                    self._json(202, {"accepted": True, "status": status})
                elif action == "verdict":
                    self._json(200, abtest.verdict())
                elif action == "stop":
                    self._json(200, abtest.stop(spec.get("winner")))
                else:
                    self._json(400, {"error": f"unknown action "
                                              f"{action!r}"})
            except RolloutInProgress as exc:
                self._json(409, {"error": str(exc),
                                 "status": abtest.status()})
            except KeyError as exc:
                self._json(400, {"error": f"missing field {exc}"})
            except (ValueError, RuntimeError) as exc:
                self._json(409, {"error": str(exc)[:300]})

        def do_POST(self):  # noqa: N802
            length = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(length)
            if self.path == "/admin/rollout":
                self._admin_rollout(body)
                return
            if self.path == "/admin/ab":
                self._admin_ab(body)
                return
            if self.path != "/predict":
                self._json(404, {"error": f"no route {self.path}"})
                return
            # request-scoped tracing (obs/reqtrace.py): a W3C
            # traceparent's trace-id (or an explicit X-Request-Id) is
            # adopted for cross-service correlation, else one is
            # assigned HERE — every answer, 4xx/5xx included, echoes it
            rid = (request_id_from_headers(self.headers)
                   or new_request_id())
            try:
                img = Image.open(io.BytesIO(body))
                img.load()
            except Exception:  # noqa: BLE001 — undecodable body → 400
                self._json(400, {"error": "body is not a decodable image",
                                 "request_id": rid}, request_id=rid)
                return
            # router-stamped A/B arm (X-AB-Arm): with no header the
            # server derives the SAME arm from the request id, so the
            # stamp is an optimization + an invariant, not a requirement
            arm = self.headers.get("X-AB-Arm", "")
            try:
                response = server.submit(
                    img, request_id=rid, arm=arm
                ).result(timeout=request_timeout_s)
            except concurrent.futures.TimeoutError:
                # a wedged request must get an HTTP answer, not a
                # handler traceback + dropped connection
                self._json(504, {
                    "status": "error",
                    "reason": f"no result within {request_timeout_s:.0f} s",
                    "request_id": rid,
                }, request_id=rid)
                return
            rid = response.request_id or rid
            if not response.ok:
                # rejection/shutdown = "service unavailable, retry"
                # (the reason says whether HERE or elsewhere); anything
                # else is this server's fault
                code = (503 if response.status
                        in (STATUS_REJECTED, STATUS_SHUTDOWN) else 500)
                self._json(code, {
                    "status": response.status, "reason": response.reason,
                    "request_id": rid,
                }, retry_after=(
                    server.retry_after_s(response.reason)
                    if code == 503 else None
                ), request_id=rid)
                return
            buf = io.BytesIO()
            Image.fromarray(response.masks[0]).save(buf, format="PNG")
            data = buf.getvalue()
            self.send_response(200)
            self.send_header("Content-Type", "image/png")
            self.send_header("Content-Length", str(len(data)))
            self.send_header(
                "X-Serve-Latency-Ms", f"{response.latency_ms:.2f}"
            )
            self.send_header("X-Request-Id", rid)
            self.end_headers()
            self.wfile.write(data)

        def log_message(self, fmt, *fmt_args):  # route through logging
            logger.debug("http: " + fmt, *fmt_args)

    return ThreadingHTTPServer((host, port), Handler)


def main(argv=None) -> int:
    import os

    args = get_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    from distributedpytorch_tpu.utils.backend import (
        enable_compilation_cache,
        require_accelerator,
    )

    enable_compilation_cache()
    require_accelerator("serve")
    heartbeat = None
    if args.heartbeat_dir:
        # beat FIRST — the engine's AOT compiles take long enough that a
        # supervisor would otherwise read "no beat within the spawn
        # window" for a perfectly healthy worker
        from distributedpytorch_tpu.dist.health import Heartbeat

        heartbeat = Heartbeat(
            args.heartbeat_dir,
            rank=int(os.environ.get("RANK", "0")),
            interval_s=args.heartbeat_interval,
        ).start()
    server = build_server(args)
    server.heartbeat = heartbeat
    if heartbeat is not None:
        # steady state begins AFTER the engine's AOT compiles (the line
        # above): refresh progress first, THEN arm the progress-timeout
        # verdict — flipping `timed` before/during a long cold compile
        # would read as "hung" and kill-loop a healthy starting worker
        heartbeat.update(0, 0)
        heartbeat.timed = True
    server.start()
    httpd = make_http_server(server, host=args.host, port=args.port)
    host, port = httpd.server_address[:2]
    logger.info(
        "serving on http://%s:%d (buckets %s, slo %.0f ms, %d replica(s)) — "
        "POST /predict, GET /healthz, GET /stats",
        host, port, list(server.engine.planner.sizes), args.slo_ms,
        server.engine.num_replicas,
    )
    threading.Thread(  # Ctrl-C must interrupt serve_forever, not a join
        target=httpd.serve_forever, daemon=True,
    ).start()
    # SIGTERM is the fleet scaler's retire signal (dist/elastic.py
    # retire_fleet_worker: routers drain first, then SIGTERM): exit the
    # wait loop and drain the queue in the finally — a retire must
    # finish the work it already admitted, same as Ctrl-C
    import signal as _signal

    sigterm = threading.Event()
    try:
        _signal.signal(_signal.SIGTERM, lambda *_: sigterm.set())
    except ValueError:
        pass  # not the main thread (embedded in a test harness)
    rc = 0
    try:
        # wake periodically: a server whose in-process restart budget is
        # spent is TERMINAL — exit nonzero so the process supervisor
        # (elastic --workload serve) relaunches the whole worker
        from distributedpytorch_tpu.serve.server import STATE_STOPPED

        while server.state != STATE_STOPPED:
            if sigterm.wait(0.5):
                logger.info("SIGTERM: retiring (draining queue)")
                break
        else:
            logger.error("serve worker terminal (dispatch-core restart "
                         "budget spent) — exiting for relaunch")
            rc = 1
    except KeyboardInterrupt:
        logger.info("shutting down (draining queue)")
    finally:
        httpd.shutdown()
        server.stop(drain=True)
        if heartbeat is not None:
            heartbeat.stop()
    return rc


if __name__ == "__main__":
    import sys

    sys.exit(main())
