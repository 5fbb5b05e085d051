#!/usr/bin/env python3
"""Serving-tier load generator: closed- and open-loop, JSON report.

Two complementary load shapes against the SAME in-process
:class:`~distributedpytorch_tpu.serve.server.Server` the HTTP CLI runs
(so the numbers measure the production path, not a bench-only shortcut):

* **closed loop** — C worker threads, each submit→wait→repeat. Measures
  the latency/throughput curve AT each concurrency level: batches form
  exactly when concurrency exceeds replica capacity, so imgs/s vs C is
  the continuous-batching win made visible. Reported at >= 3 levels.
* **open loop** — arrivals on a fixed-rate clock regardless of
  completions (the real-traffic shape closed loops can't produce,
  coordinated-omission-free). The **overload scenario** drives the
  arrival rate to a multiple of the measured capacity and samples queue
  depth continuously: the report must show depth bounded by the
  admission cap (bucket-shedding + rejection), NOT unbounded latency
  growth — that boundedness is the acceptance criterion of the
  serving tier's degradation story.

No checkpoint needed: ``--fresh-init`` (the default when no checkpoint
is given) serves a seeded randomly-initialized model — garbage masks,
identical machinery — so the bench runs on any CPU, chip-free.

Every leg row additionally records its per-phase attribution medians
(queue_wait/placement/device/drain — obs/reqtrace.py) and the path of
the ``dpt_serve_profile`` v1 artifact written from that leg's
per-bucket service-time profiles, so bench legs double as calibration
runs for the serve capacity planner (``report["profile"]`` names the
in-SLO leg's — the regime a plan should calibrate from).

The closed/open/overload legs go one step further and CLOSE the
plan-serve loop on themselves: each records its own arrival trace
(``dpt_serve_arrivals`` JSONL — the serve front's ``--record-arrivals``
format), then replays that trace against its own profile in the
discrete-event simulator (serve/sim.py) and stamps a ``validation``
block comparing predicted p99 / shed-rate against the measured row,
plus the ``plan_point`` grid key the leg validates. Tier-1 asserts the
tolerance on the CPU-pinned legs — the simulator must reproduce the bench from traces
alone, or capacity plans built on it are fiction.

Usage:
    python tools/bench_serve.py --levels 1 4 16 --duration 5 \\
        --out serve_report.json
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tiny default rig: the serving machinery (queue, placement, AOT
# executables, completion drain) is geometry-independent; a small model
# keeps the bench hostable on the 1-2 core CI/container CPUs.
DEFAULT_WIDTHS = (8, 16)
DEFAULT_SIZE_WH = (96, 64)  # (W, H), CLI order
DEFAULT_BUCKETS = (1, 2, 4, 8)


def build_engine(args):
    """Engine from a checkpoint, or fresh-init (seeded) when none given."""
    from distributedpytorch_tpu.serve.engine import (
        ServeEngine,
        engine_from_checkpoint,
    )

    widths = tuple(args.model_widths) if args.model_widths else None
    common = dict(
        bucket_sizes=tuple(args.buckets),
        replicas=args.replicas,
        host_cache_mb=0,  # bench submits pre-decoded arrays
    )
    if args.checkpoint:
        return engine_from_checkpoint(
            args.checkpoint,
            checkpoint_dir=args.checkpoint_dir,
            image_size=tuple(args.image_size),
            model_arch=args.model_arch,
            model_widths=widths,
            s2d_levels=args.s2d_levels,
            **common,
        )
    import jax

    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.models import create_model

    w, h = int(args.image_size[0]), int(args.image_size[1])
    cfg = TrainConfig(
        model_arch=args.model_arch,
        model_widths=widths,
        compute_dtype="float32",
        s2d_levels=args.s2d_levels,
    )
    model, init_fn = create_model(cfg)
    params, model_state = init_fn(jax.random.key(args.seed), (h, w))
    # fresh-init engines carry the bench identity fingerprint so a
    # $DPT_AOT_CACHE-armed window stops re-paying identical compiles
    # across legs (the engine resolves the store dir from the env)
    return ServeEngine(model, params, model_state, input_hw=(h, w),
                       engine_fingerprint=_engine_fingerprint(args),
                       **common)


def make_images(n: int, hw, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.random((n, hw[0], hw[1], 3), dtype=np.float32)


def _new_server(engine, args, record_leg: Optional[str] = None):
    from distributedpytorch_tpu.serve.server import Server

    server = Server(
        engine,
        slo_ms=args.slo_ms,
        hard_cap_images=args.queue_cap,
        placement_depth=args.placement_depth,
        eager_when_idle=not args.no_eager,
    ).start()
    if record_leg is not None:
        # per-leg arrival trace (the serve front's --record-arrivals
        # format): the validation step replays it through the simulator
        from distributedpytorch_tpu.serve.sim import ArrivalRecorder

        server.arrival_recorder = ArrivalRecorder(
            _arrivals_path(args, record_leg)
        )
    return server


def _engine_fingerprint(args) -> str:
    from distributedpytorch_tpu.obs.reqtrace import engine_fingerprint

    return engine_fingerprint(
        model_arch=args.model_arch,
        image_size=tuple(args.image_size),
        model_widths=tuple(args.model_widths) if args.model_widths else None,
        s2d_levels=args.s2d_levels,
    )


def _leg_calibration(server, args, leg: str) -> dict:
    """The per-leg calibration outputs every leg row records: the
    per-phase attribution medians (queue_wait/placement/device/drain —
    WHERE this leg's latency went) and the ``dpt_serve_profile`` v1
    artifact written from this leg's per-bucket service-time profiles,
    so every bench leg doubles as a calibration run for the serve
    capacity planner (``plan-serve``). The profile carries the bucket
    ladder and engine fingerprint the staleness guard cross-checks."""
    from distributedpytorch_tpu.obs.reqtrace import save_profile

    medians = server.tracer.phase_medians_ms()
    payload = server.tracer.profile_payload(
        phase_medians_ms=medians,
        leg=leg,
        image_size=list(args.image_size),
        bucket_sizes=list(args.buckets),
        replicas=server.engine.num_replicas,
        eager_when_idle=not args.no_eager,
        queue_cap_images=server.queue.hard_cap_images,
        engine_fingerprint=_engine_fingerprint(args),
    )
    path = _artifact_path(args, f"profile_{leg}")
    save_profile(payload, path)
    out = {
        "attribution": {
            "queue_wait_ms": medians.get("queue_wait"),
            "placement_ms": medians.get("placement"),
            "dispatch_wait_ms": medians.get("dispatch_wait"),
            "device_ms": medians.get("device_exec"),
            "drain_ms": medians.get("drain"),
        },
        "profile": path,
    }
    recorder = server.arrival_recorder
    if recorder is not None:
        recorder.close()
        out["arrivals"] = recorder.path
    return out


#: Stated predicted-vs-measured tolerances (the validation contract
#: tier-1 asserts on the CPU-pinned legs): p99 within a 4x factor with
#: a 25 ms floor (CI-container scheduling jitter dominates small
#: absolute values), shed rate within 0.2 absolute (the structural
#: cap-bound number, which the simulator should land close to).
VALIDATION_P99_FACTOR = 4.0
VALIDATION_P99_FLOOR_MS = 25.0
VALIDATION_SHED_ABS = 0.2


def _leg_validation(server, args, row: dict, leg: str) -> None:
    """Close the plan-serve loop on this leg: replay its own recorded
    arrivals against its own profile in the discrete-event simulator
    and stamp predicted-vs-measured p99 / shed-rate (with the stated
    tolerance verdict) plus the ``plan_point`` key the leg validates."""
    from distributedpytorch_tpu.analysis.serve_planner import point_key
    from distributedpytorch_tpu.obs.reqtrace import load_profile
    from distributedpytorch_tpu.serve import sim

    cap = server.queue.hard_cap_images
    row["plan_point"] = point_key(
        f"replay-{leg}", tuple(args.buckets), args.slo_ms,
        server.engine.num_replicas, not args.no_eager, cap,
    )
    profile = load_profile(row.get("profile"))
    arrivals = sim.load_arrival_trace(row.get("arrivals"))
    if profile is None or arrivals is None:
        row["validation"] = {"ok": None,
                             "note": "no profile/arrivals to replay"}
        return
    try:
        model = sim.ServiceModel(profile)
    except ValueError as exc:
        row["validation"] = {"ok": None, "note": str(exc)}
        return
    knobs = sim.SimKnobs(
        bucket_sizes=tuple(args.buckets),
        slo_s=args.slo_ms / 1e3,
        replicas=server.engine.num_replicas,
        eager=not args.no_eager,
        hard_cap_images=cap,
        # the sim's flushed-group buffer mirrors the leg's ACTUAL
        # placement depth (>=1: even synchronous placement holds the
        # one group the dispatch loop has in hand)
        dispatch_buffer=max(1, args.placement_depth),
        seed=args.seed,
    )
    predicted = sim.simulate(model, knobs, arrivals=arrivals).payload()
    snap = server.metrics.snapshot()
    measured_p99 = row.get("p99_ms")
    submitted = snap["requests_ok"] + snap["rejected_total"]
    measured_shed = (
        snap["rejected"].get("overloaded", 0) / submitted if submitted else 0.0
    )
    p99_ok = None
    if measured_p99 is not None and predicted["p99_ms"] is not None:
        floor = VALIDATION_P99_FLOOR_MS
        p99_ok = (
            predicted["p99_ms"]
            <= measured_p99 * VALIDATION_P99_FACTOR + floor
            and measured_p99
            <= predicted["p99_ms"] * VALIDATION_P99_FACTOR + floor
        )
    shed_ok = abs(predicted["shed_rate"] - measured_shed) <= VALIDATION_SHED_ABS
    row["validation"] = {
        "predicted_p99_ms": predicted["p99_ms"],
        "measured_p99_ms": measured_p99,
        "predicted_shed_rate": predicted["shed_rate"],
        "measured_shed_rate": round(measured_shed, 4),
        "predicted_imgs_per_s": predicted["imgs_per_s"],
        "tolerance": {
            "p99_factor": VALIDATION_P99_FACTOR,
            "p99_floor_ms": VALIDATION_P99_FLOOR_MS,
            "shed_abs": VALIDATION_SHED_ABS,
        },
        "ok": bool(p99_ok) and shed_ok if p99_ok is not None else None,
    }


def closed_loop(engine, args, concurrency: int, duration_s: float) -> dict:
    """C workers, submit→wait→repeat for ``duration_s``. A fresh Server
    per level (the compiled engine is reused) keeps each level's metrics
    and queue counters isolated."""
    leg = f"closed_c{concurrency}"
    server = _new_server(engine, args, record_leg=leg)
    images = make_images(max(2 * concurrency, 16), engine.input_hw, args.seed)
    stop_at = time.monotonic() + duration_s
    errors: List[str] = []

    def worker(wid: int) -> None:
        i = wid
        while time.monotonic() < stop_at:
            fut = server.submit(images[i % len(images)], key=f"c{wid}-{i}")
            response = fut.result(timeout=60.0)
            if response.status not in ("ok", "rejected"):
                errors.append(f"{response.status}: {response.reason}")
                return
            i += concurrency

    t0 = time.monotonic()
    threads = [
        threading.Thread(target=worker, args=(w,), daemon=True)
        for w in range(concurrency)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 60.0)
    elapsed = time.monotonic() - t0
    server.stop(drain=True)
    snap = server.metrics.snapshot(elapsed_s=elapsed)
    row = {
        "mode": "closed",
        "concurrency": concurrency,
        "requests": snap["requests_ok"],
        "imgs_per_s": snap["imgs_per_s"],
        "p50_ms": snap["p50_ms"],
        "p99_ms": snap["p99_ms"],
        "pad_ratio": snap["pad_ratio"],
        "bucket_dispatches": snap["bucket_dispatches"],
        "errors": errors[:3],
    }
    row.update(_leg_calibration(server, args, leg))
    _leg_validation(server, args, row, leg)
    return row


def open_loop(engine, args, rate_imgs_per_s: float, duration_s: float,
              label: str = "open") -> dict:
    """Fixed-rate arrivals + a queue-depth sampler. Latency percentiles
    cover ACCEPTED requests; rejections are counted, not averaged in —
    under overload the interesting numbers are (a) bounded depth and
    (b) how much got shed, separately."""
    server = _new_server(engine, args, record_leg=label)
    images = make_images(32, engine.input_hw, args.seed)
    period = 1.0 / max(rate_imgs_per_s, 1e-9)
    futures = []
    depth_samples: List[int] = []
    stop = threading.Event()

    def sampler() -> None:
        while not stop.is_set():
            depth_samples.append(server.queue.depth_images)
            time.sleep(0.002)

    sampler_t = threading.Thread(target=sampler, daemon=True)
    sampler_t.start()
    t0 = time.monotonic()
    n = 0
    while True:
        now = time.monotonic()
        if now - t0 >= duration_s:
            break
        due = t0 + n * period
        if now < due:
            time.sleep(min(due - now, period))
            continue
        futures.append(server.submit(images[n % len(images)], key=f"o{n}"))
        n += 1
    responses = [f.result(timeout=60.0) for f in futures]
    elapsed = time.monotonic() - t0
    stop.set()
    sampler_t.join(timeout=2.0)
    server.stop(drain=True)
    snap = server.metrics.snapshot(elapsed_s=elapsed)
    rejected = sum(1 for r in responses if r.status == "rejected")
    row = {
        "mode": label,
        "offered_imgs_per_s": round(rate_imgs_per_s, 2),
        "submitted": len(responses),
        "ok": sum(1 for r in responses if r.ok),
        "rejected": rejected,
        "imgs_per_s": snap["imgs_per_s"],
        "p50_ms": snap["p50_ms"],
        "p99_ms": snap["p99_ms"],
        "queue_depth_max": max(depth_samples, default=0),
        "queue_depth_cap": server.queue.hard_cap_images,
        "depth_bounded": (
            max(depth_samples, default=0) <= server.queue.hard_cap_images
        ),
        "pad_ratio": snap["pad_ratio"],
    }
    row.update(_leg_calibration(server, args, label))
    _leg_validation(server, args, row, label)
    return row


def _artifact_path(args, name: str) -> str:
    """Per-leg artifact path (flight dumps, dpt_serve_profile files):
    next to the report when ``--out`` is set, else the temp dir."""
    import tempfile

    if args.out:
        return f"{args.out}.{name}.json"
    return os.path.join(tempfile.gettempdir(), f"bench_serve_{name}.json")


def _flight_path(args, leg: str) -> str:
    """Per-leg flight-recorder artifact path (for post-mortems)."""
    return _artifact_path(args, f"flight_{leg}")


def _arrivals_path(args, leg: str) -> str:
    """Per-leg recorded arrival-trace path (dpt_serve_arrivals JSONL)."""
    import tempfile

    if args.out:
        return f"{args.out}.arrivals_{leg}.jsonl"
    return os.path.join(tempfile.gettempdir(),
                        f"bench_serve_arrivals_{leg}.jsonl")


def chaos_leg(engine, args, duration_s: float) -> dict:
    """Self-healing drill: kill the dispatch loop mid-traffic
    (``serve_dispatch_death``) and measure the relaunch — every future
    must resolve (never hang), the core must come back, and a
    post-recovery request must serve. The leg's flight-recorder dump is
    the same post-mortem artifact a production death leaves."""
    from distributedpytorch_tpu.obs import flight
    from distributedpytorch_tpu.utils import faults

    server = _new_server(engine, args)
    images = make_images(16, engine.input_hw, args.seed)
    statuses: dict = {}
    unresolved = 0
    lock = threading.Lock()
    stop_at = time.monotonic() + duration_s

    def worker(wid: int) -> None:
        nonlocal unresolved
        i = wid
        while time.monotonic() < stop_at:
            fut = server.submit(images[i % len(images)], key=f"x{wid}-{i}")
            try:
                response = fut.result(timeout=30.0)
                with lock:
                    statuses[response.status] = (
                        statuses.get(response.status, 0) + 1
                    )
            except Exception:  # noqa: BLE001 — a hung future is THE failure
                with lock:
                    unresolved += 1
            i += 4
            time.sleep(0.002)

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(4)]
    try:
        for t in threads:
            t.start()
        time.sleep(duration_s * 0.3)
        faults.install(("serve_dispatch_death",))  # next dispatch dies
        for t in threads:
            t.join(timeout=duration_s + 60.0)
        # recovery probe: the relaunched core must serve again
        deadline = time.monotonic() + 30.0
        recovered = False
        while time.monotonic() < deadline and not recovered:
            if server.submit(images[0], key="probe").result(30.0).ok:
                recovered = True
            else:
                time.sleep(0.05)
    finally:
        faults.reset()
        artifact = flight.dump("bench_serve_chaos",
                               path=_flight_path(args, "chaos"))
        server.stop(drain=True)
    return {
        "mode": "chaos",
        "fault": "serve_dispatch_death",
        "statuses": statuses,
        "unresolved_futures": unresolved,
        "core_restarts": server.core_restarts,
        "recovered": recovered,
        "flight_recorder": artifact,
    }


def rollout_leg(engine, args, duration_s: float) -> dict:
    """Zero-downtime rollout drill: mid-traffic, canary + promote a
    second set of (seeded fresh-init) weights through the rollout state
    machine; the interesting numbers are the outcome, the promoted
    version, and that no request got a 5xx-shaped answer during the
    swap."""
    import jax

    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.models import create_model
    from distributedpytorch_tpu.obs import flight
    from distributedpytorch_tpu.serve.rollout import RolloutManager

    widths = tuple(args.model_widths) if args.model_widths else None
    cfg = TrainConfig(model_arch=args.model_arch, model_widths=widths,
                      compute_dtype="float32", s2d_levels=args.s2d_levels)
    _model, init_fn = create_model(cfg)
    h, w = engine.input_hw
    new_params, new_state = init_fn(jax.random.key(args.seed + 1), (h, w))

    server = _new_server(engine, args)
    manager = RolloutManager(
        server, window_s=max(0.2, duration_s * 0.2), canary_replicas=1,
    )
    server.rollout = manager
    images = make_images(16, engine.input_hw, args.seed)
    bad = 0
    ok = 0
    stop_at = time.monotonic() + duration_s
    futures = []
    try:
        started = False
        i = 0
        while time.monotonic() < stop_at:
            futures.append(server.submit(images[i % len(images)], key=str(i)))
            i += 1
            if not started and time.monotonic() > stop_at - duration_s * 0.7:
                manager.start((new_params, new_state), label="bench")
                started = True
            time.sleep(0.005)
        outcome = manager.wait(timeout=60.0)
        for fut in futures:
            response = fut.result(timeout=30.0)
            if response.ok:
                ok += 1
            else:
                bad += 1
    finally:
        artifact = flight.dump("bench_serve_rollout",
                               path=_flight_path(args, "rollout"))
        server.stop(drain=True)
    return {
        "mode": "rollout",
        "outcome": outcome,
        "weights_version": engine.weights_version,
        "ok": ok,
        "non_ok": bad,
        "zero_5xx": bad == 0,
        "flight_recorder": artifact,
    }


def router_leg(engine, args, duration_s: float) -> dict:
    """Front-door drill: two HTTP workers (each the SAME Server+handler
    stack the production CLI runs) behind a serve/router.py Router, a
    closed loop of clients talking ONLY to the router's address, and
    two mid-traffic failures — a ``serve_dispatch_death`` chaos kill of
    one worker's dispatch core (503s while it relaunches) and an abrupt
    teardown+rebind of the other worker's HTTP front (connection
    failures → eject, then readmit on recovery). The acceptance number
    is **zero client-visible failures**: every request answers 200,
    failures surface only as the router's transparent retries. The row
    also stamps one explicit scale-up/down cycle through the replica
    scaler when the device pool allows it."""
    import http.client
    import io

    import jax
    from PIL import Image

    from distributedpytorch_tpu.obs import flight
    from distributedpytorch_tpu.serve.autoscale import AutoscaleHint
    from distributedpytorch_tpu.serve.cli import make_http_server
    from distributedpytorch_tpu.serve.router import Router
    from distributedpytorch_tpu.serve.scaler import ReplicaScaler
    from distributedpytorch_tpu.utils import faults

    engine_b = build_engine(args)
    server_a = _new_server(engine, args)
    server_b = _new_server(engine_b, args)
    httpd_a = make_http_server(server_a, port=0)
    httpd_b = make_http_server(server_b, port=0)
    port_a = httpd_a.server_address[1]
    port_b = httpd_b.server_address[1]
    for httpd in (httpd_a, httpd_b):
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
    router = Router(
        [("127.0.0.1", port_a), ("127.0.0.1", port_b)],
        retry_budget=6, backoff_base_s=0.02, backoff_cap_s=0.5,
        hedge=True, probe_interval_s=0.2,
    ).start()

    img8 = (make_images(1, engine.input_hw, args.seed)[0] * 255.0)
    buf = io.BytesIO()
    Image.fromarray(img8.astype(np.uint8)).save(buf, format="PNG")
    body = buf.getvalue()

    from distributedpytorch_tpu.serve.router import make_router_http

    router_httpd = make_router_http(router, port=0)
    router_port = router_httpd.server_address[1]
    threading.Thread(target=router_httpd.serve_forever,
                     daemon=True).start()

    codes: dict = {}
    transport_errors = 0
    lock = threading.Lock()
    stop_at = time.monotonic() + duration_s

    def client(wid: int) -> None:
        nonlocal transport_errors
        while time.monotonic() < stop_at:
            conn = http.client.HTTPConnection(
                "127.0.0.1", router_port, timeout=60.0)
            try:
                conn.request("POST", "/predict", body=body,
                             headers={"Content-Type": "image/png"})
                resp = conn.getresponse()
                resp.read()
                with lock:
                    codes[resp.status] = codes.get(resp.status, 0) + 1
            except Exception:  # noqa: BLE001 — a client-side transport
                # failure IS a client-visible failure
                with lock:
                    transport_errors += 1
            finally:
                try:
                    conn.close()
                except Exception:  # noqa: BLE001
                    pass
            time.sleep(0.002)

    # one explicit, plan-shaped scale cycle when the device pool allows
    hint = AutoscaleHint(server_a, interval_s=1e9)
    scaler = ReplicaScaler(server_a, hint, cooldown_windows=0)
    server_a.scaler = scaler
    base_replicas = engine.num_replicas
    scaled = False

    threads = [threading.Thread(target=client, args=(w,), daemon=True)
               for w in range(3)]
    try:
        for t in threads:
            t.start()
        # failure 1 (~30%): one dispatch core dies; its worker 503s
        # while relaunching and the router retries onto the sibling
        time.sleep(duration_s * 0.3)
        faults.install(("serve_dispatch_death",))
        if len(jax.devices()) > base_replicas:
            scaler.apply(scaler.decide(base_replicas + 1))
            scaled = engine.num_replicas == base_replicas + 1
        # failure 2 (~60%): abrupt HTTP-front teardown (the in-process
        # SIGKILL analogue) → connection failures → eject; rebinding
        # the same port brings it back → /healthz readmit
        time.sleep(duration_s * 0.3)
        httpd_b.shutdown()
        httpd_b.server_close()
        time.sleep(max(0.5, duration_s * 0.1))
        httpd_b = make_http_server(server_b, port=port_b)
        threading.Thread(target=httpd_b.serve_forever,
                         daemon=True).start()
        if scaled:
            scaler.apply(scaler.decide(base_replicas))
        for t in threads:
            t.join(timeout=duration_s + 120.0)
    finally:
        faults.reset()
        artifact = flight.dump("bench_serve_router",
                               path=_flight_path(args, "router"))
        router_httpd.shutdown()
        router.stop()
        for httpd in (httpd_a, httpd_b):
            try:
                httpd.shutdown()
            except Exception:  # noqa: BLE001
                pass
        server_a.stop(drain=True)
        server_b.stop(drain=True)
    stats = router.stats()
    non_200 = sum(n for code, n in codes.items() if code != 200)
    return {
        "mode": "router",
        "requests": sum(codes.values()),
        "codes": {str(code): n for code, n in sorted(codes.items())},
        "transport_errors": transport_errors,
        "zero_client_failures": non_200 == 0 and transport_errors == 0,
        "retries": stats["retries"],
        "hedges_fired": stats["hedges_fired"],
        "hedge_wins": stats["hedge_wins"],
        "scale_ups": scaler.scale_ups,
        "scale_downs": scaler.scale_downs,
        "scale_decisions": scaler.decisions[-4:],
        "router_p99_ms": stats["p99_ms"],
        "core_restarts": server_a.core_restarts + server_b.core_restarts,
        "flight_recorder": artifact,
    }


def hedge_leg(engine, args, duration_s: float) -> dict:
    """Hedging honesty drill on CPU: the same two-worker stack run
    twice against a *synthetically wedged* worker — once with hedging
    off, once with it on — so the hedge's tail-cutting claim is
    measured against the exact pathology it exists for (a dispatch
    loop that stops turning while the HTTP front stays healthy, so
    ejection never triggers). The ``serve_replica_wedge`` fault is
    re-armed on a cadence with a short self-clearing ``DPT_FAULT_HANG_S``
    so the slow tail is a sustained *fraction* of traffic (lands in p99
    at any leg duration), not a single spike. Acceptance: hedged p99 <
    unhedged p99, at least one hedge actually fired, and the router's
    ledger counted every hedged request exactly once (ok+failed ==
    client-side completions — hedge losers never double-count).

    Hedging stays **default-off** in the Router; this leg opts in
    explicitly. The CPU wedge is an honesty floor, not the promotion
    gate — chip-window tail measurement (ROADMAP) remains the gate."""
    import http.client
    import io

    from PIL import Image

    from distributedpytorch_tpu.obs import flight
    from distributedpytorch_tpu.serve.cli import make_http_server
    from distributedpytorch_tpu.serve.router import Router, make_router_http
    from distributedpytorch_tpu.utils import faults

    engine_b = build_engine(args)
    server_a = _new_server(engine, args)
    server_b = _new_server(engine_b, args)
    httpd_a = make_http_server(server_a, port=0)
    httpd_b = make_http_server(server_b, port=0)
    port_a = httpd_a.server_address[1]
    port_b = httpd_b.server_address[1]
    for httpd in (httpd_a, httpd_b):
        threading.Thread(target=httpd.serve_forever, daemon=True).start()

    img8 = (make_images(1, engine.input_hw, args.seed)[0] * 255.0)
    buf = io.BytesIO()
    Image.fromarray(img8.astype(np.uint8)).save(buf, format="PNG")
    body = buf.getvalue()

    hang_s = 0.5
    prev_hang = os.environ.get("DPT_FAULT_HANG_S")
    os.environ["DPT_FAULT_HANG_S"] = str(hang_s)
    phase_s = max(1.0, duration_s * 0.5)

    def phase(hedge: bool) -> dict:
        # hedge_factor=1 pins the adaptive delay near p99 instead of
        # 3x: with the default factor every hedged victim records
        # ~delay into the latency window and the delay ratchets up to
        # the hang itself, hiding the win this drill exists to measure
        router = Router(
            [("127.0.0.1", port_a), ("127.0.0.1", port_b)],
            retry_budget=6, backoff_base_s=0.02, backoff_cap_s=0.5,
            hedge=hedge, hedge_factor=1.0, hedge_floor_ms=40.0,
            probe_interval_s=0.5,
        ).start()
        router_httpd = make_router_http(router, port=0)
        router_port = router_httpd.server_address[1]
        threading.Thread(target=router_httpd.serve_forever,
                         daemon=True).start()
        latencies: list = []
        codes: dict = {}
        transport_errors = 0
        lock = threading.Lock()
        stop_at = time.monotonic() + phase_s
        stop_evt = threading.Event()

        def client() -> None:
            nonlocal transport_errors
            while time.monotonic() < stop_at:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", router_port, timeout=60.0)
                t0 = time.monotonic()
                try:
                    conn.request("POST", "/predict", body=body,
                                 headers={"Content-Type": "image/png"})
                    resp = conn.getresponse()
                    resp.read()
                    with lock:
                        codes[resp.status] = codes.get(resp.status, 0) + 1
                        latencies.append(time.monotonic() - t0)
                except Exception:  # noqa: BLE001 — client-visible
                    with lock:
                        transport_errors += 1
                finally:
                    try:
                        conn.close()
                    except Exception:  # noqa: BLE001
                        pass
                time.sleep(0.002)

        def wedger() -> None:
            # a count-1 wedge stalls exactly ONE dispatch loop for
            # hang_s; re-arming on a cadence keeps a bounded slow
            # fraction of traffic for the whole phase (reset first —
            # install() is idempotent per spec tuple and would keep
            # the spent count otherwise)
            while not stop_evt.wait(hang_s * 1.4):
                faults.reset()
                faults.install(("serve_replica_wedge",))

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(3)]
        wedge_thread = threading.Thread(target=wedger, daemon=True)
        try:
            faults.install(("serve_replica_wedge",))
            for t in threads:
                t.start()
            wedge_thread.start()
            for t in threads:
                t.join(timeout=phase_s + 120.0)
        finally:
            stop_evt.set()
            wedge_thread.join(timeout=5.0)
            faults.reset()
            router_httpd.shutdown()
            router.stop()
        stats = router.stats()
        lat = sorted(latencies)
        p99_ms = (
            lat[max(0, math.ceil(0.99 * len(lat)) - 1)] * 1e3 if lat
            else None
        )
        completions = sum(codes.values())
        return {
            "hedge": hedge,
            "requests": completions,
            "codes": {str(code): n for code, n in sorted(codes.items())},
            "transport_errors": transport_errors,
            "p99_ms": round(p99_ms, 3) if p99_ms is not None else None,
            "hedges_fired": stats["hedges_fired"],
            "hedge_wins": stats["hedge_wins"],
            "ledger_ok": stats["requests_ok"],
            "ledger_failed": stats["requests_failed"],
            # exactly-once: every client completion appears ONCE in the
            # router's ledger, hedge losers never double-count
            "ledger_exact": (
                stats["requests_ok"] + stats["requests_failed"]
                == completions
            ),
        }

    try:
        unhedged = phase(hedge=False)
        hedged = phase(hedge=True)
    finally:
        faults.reset()
        if prev_hang is None:
            os.environ.pop("DPT_FAULT_HANG_S", None)
        else:
            os.environ["DPT_FAULT_HANG_S"] = prev_hang
        artifact = flight.dump("bench_serve_hedge",
                               path=_flight_path(args, "hedge"))
        for httpd in (httpd_a, httpd_b):
            try:
                httpd.shutdown()
            except Exception:  # noqa: BLE001
                pass
        server_a.stop(drain=True)
        server_b.stop(drain=True)
    improved = (
        unhedged["p99_ms"] is not None and hedged["p99_ms"] is not None
        and hedged["p99_ms"] < unhedged["p99_ms"]
    )
    return {
        "mode": "hedge",
        "wedge_hang_s": hang_s,
        "unhedged": unhedged,
        "hedged": hedged,
        "hedged_p99_improved": improved,
        "ledger_exact": hedged["ledger_exact"],
        "hedges_fired": hedged["hedges_fired"],
        "flight_recorder": artifact,
    }


def run_bench(budget_s: float = 600.0, args: Optional[argparse.Namespace] = None,
              levels: Optional[Sequence[int]] = None) -> dict:
    """The whole program: closed-loop sweep over the concurrency levels,
    one in-SLO open-loop run, one overload run, then the fleet drills —
    a chaos leg (dispatch death → relaunch), a rollout leg (mid-traffic
    canaried weight swap), a router leg (two HTTP workers behind the
    front-door router, mid-traffic failures, zero client-visible
    errors), and a hedge leg (wedged worker, hedged vs unhedged p99,
    exactly-once ledger). Returns the report dict."""
    args = args or get_args([])
    levels = [int(c) for c in (levels or args.levels)]
    t_start = time.monotonic()

    engine = build_engine(args)
    engine.warmup()

    # budget split: levels + 2 open-loop scenarios + 4 fleet drills,
    # capped per-leg
    legs = len(levels) + 6
    leg_s = max(1.0, min(args.duration, (budget_s * 0.8) / legs))

    report = {
        "metric": "serve_bench",
        "image_size": list(args.image_size),
        "buckets": list(args.buckets),
        "replicas_requested": args.replicas,
        "replicas": engine.num_replicas,
        "slo_ms": args.slo_ms,
        "eager_when_idle": not args.no_eager,
        "leg_duration_s": round(leg_s, 2),
        "levels": [],
    }
    for concurrency in levels:
        row = closed_loop(engine, args, concurrency, leg_s)
        report["levels"].append(row)
        print(json.dumps(row), flush=True)

    # capacity estimate = best closed-loop throughput; open-loop in-SLO
    # at 60% of it, overload at 3x — overload MUST show bounded depth
    capacity = max(
        (row["imgs_per_s"] or 0.0) for row in report["levels"]
    ) or 10.0
    report["in_slo"] = open_loop(
        engine, args, rate_imgs_per_s=0.6 * capacity, duration_s=leg_s,
        label="open_in_slo",
    )
    # the headline calibration artifact: the in-SLO open-loop leg's
    # per-bucket service-time profile (the realistic-load regime a
    # capacity plan should be calibrated from; every leg's own profile
    # path rides its row)
    report["profile"] = report["in_slo"]["profile"]
    print(json.dumps(report["in_slo"]), flush=True)
    report["overload"] = open_loop(
        engine, args, rate_imgs_per_s=3.0 * capacity, duration_s=leg_s,
        label="open_overload",
    )
    print(json.dumps(report["overload"]), flush=True)
    report["chaos"] = chaos_leg(engine, args, leg_s)
    print(json.dumps(report["chaos"]), flush=True)
    report["rollout"] = rollout_leg(engine, args, leg_s)
    print(json.dumps(report["rollout"]), flush=True)
    report["router"] = router_leg(engine, args, leg_s)
    print(json.dumps(report["router"]), flush=True)
    report["hedge"] = hedge_leg(engine, args, leg_s)
    print(json.dumps(report["hedge"]), flush=True)
    report["elapsed_s"] = round(time.monotonic() - t_start, 2)
    report["value"] = capacity  # headline: peak closed-loop imgs/s
    return report


def get_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--checkpoint", "-c", default=None,
                    help="Checkpoint name/path; default: fresh-init weights "
                         "(identical machinery, garbage masks)")
    ap.add_argument("--checkpoint-dir", default="./checkpoints")
    ap.add_argument("--image-size", type=int, nargs=2,
                    default=DEFAULT_SIZE_WH, metavar=("W", "H"))
    ap.add_argument("--model", dest="model_arch", default="unet",
                    choices=["unet", "milesial"])
    ap.add_argument("--model-widths", type=int, nargs="+",
                    default=list(DEFAULT_WIDTHS))
    ap.add_argument("--s2d-levels", type=int, default=0)
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=list(DEFAULT_BUCKETS))
    ap.add_argument("--slo-ms", type=float, default=25.0)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--queue-cap", type=int, default=None)
    ap.add_argument("--placement-depth", type=int, default=2)
    ap.add_argument("--no-eager", action="store_true")
    ap.add_argument("--levels", type=int, nargs="+", default=[1, 4, 16],
                    help="Closed-loop concurrency levels (>= 3 for the "
                         "acceptance report)")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="Per-leg duration cap (seconds)")
    ap.add_argument("--budget", type=float, default=600.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="Write the report JSON here")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = get_args(argv)
    report = run_bench(budget_s=args.budget, args=args)
    text = json.dumps(report, indent=2)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    # acceptance: >= 3 levels reported, overload depth bounded, the
    # chaos drill relaunched with zero hung futures, the mid-traffic
    # rollout promoted with zero 5xx-shaped answers, the router drill
    # absorbed both failures with zero client-visible failures, and
    # the hedge drill cut the wedged tail with an exactly-once ledger
    ok = (
        len(report["levels"]) >= 3
        and report["overload"]["depth_bounded"]
        and report["chaos"]["recovered"]
        and report["chaos"]["unresolved_futures"] == 0
        and report["rollout"]["outcome"] == "promoted"
        and report["rollout"]["zero_5xx"]
        and report["router"]["zero_client_failures"]
        and report["router"]["requests"] > 0
        and report["hedge"]["hedged_p99_improved"]
        and report["hedge"]["hedges_fired"] >= 1
        and report["hedge"]["ledger_exact"]
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
