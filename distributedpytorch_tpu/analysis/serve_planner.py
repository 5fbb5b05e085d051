"""The serve-tier capacity planner: ``python -m distributedpytorch_tpu
plan-serve``.

PR 10's planner answered "which training config is worth chip time"
from the compiler alone; this module answers the serving twin — "how
many replicas for this traffic at this SLO?" — from two recorded
artifacts alone, with zero devices and zero jax:

* a ``dpt_serve_profile`` v1 (obs/reqtrace.py; every bench_serve leg
  writes one): per-bucket device-exec histograms + pad ratios + phase
  medians — *how long the engine takes*;
* an arrival trace — recorded ``dpt_serve_arrivals`` JSONL (the serve
  front's ``--record-arrivals``), or synthetic open-loop Poisson /
  closed-loop workloads — *when the traffic comes*.

The discrete-event simulator (serve/sim.py) replays each scenario
against a grid of (bucket ladder × SLO × replica count × eager ×
admission cap) using the live queue's OWN policy functions
(serve/policy.py — the shared pure seam, so simulation and production
cannot drift) and emits a versioned ``dpt_serve_plan`` v1 artifact:
predicted p50/p99/shed-rate/queue-depth envelopes per grid point, plus
a replica recommendation per (scenario, SLO).

Calibration discipline (the staleness guard): the profile's recorded
bucket ladder — and, when the engine identity flags are given, its
engine/model fingerprint — are cross-checked against what is being
planned for; a mismatch REFUSES loudly (`ProfileMismatchError`) instead
of calibrating a plan with the wrong engine's numbers. Missing/corrupt
profiles follow the None-with-note idiom and abort with a clear exit.

Determinism: the whole pipeline runs on virtual time with seeded RNG
streams — the same profile + trace + seed produces a bit-identical
plan artifact (no wall-clock field is written), pinned by
tests/test_serve_planner.py.

The runtime shadow: serve/autoscale.py's ``dpt_serve_replica_hint``
watches the same pressure signals (shed deltas, queue depth) live and
must agree with this planner's direction on an obvious overload —
pinned by the autoscale cross-check test.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import zlib
from typing import List, Optional, Sequence

from distributedpytorch_tpu.obs.reqtrace import (
    ProfileMismatchError,
    engine_fingerprint,
    load_profile,
)
from distributedpytorch_tpu.serve import sim

logger = logging.getLogger(__name__)

SERVE_PLAN_KIND = "dpt_serve_plan"
SERVE_PLAN_VERSION = 1

#: Default replica-count search ladder.
DEFAULT_REPLICAS = (1, 2, 4)
#: Default open-loop rate ladder, as multiples of the profile's
#: one-replica best-case capacity (largest bucket, fully packed).
DEFAULT_RATE_FRACTIONS = (0.25, 0.5, 1.0, 2.0, 3.0)


def point_key(scenario_label: str, bucket_sizes: Sequence[int],
              slo_ms: float, replicas: int, eager: bool,
              queue_cap: Optional[int]) -> str:
    """The stable grid-point key — also what bench_serve stamps into a
    leg row's ``plan_point`` provenance, so a leg names the exact point
    it validates."""
    ladder = "x".join(str(int(b)) for b in bucket_sizes)
    return (
        f"{scenario_label}/b{ladder}/slo{slo_ms:g}/r{int(replicas)}/"
        f"{'eager' if eager else 'noeager'}/"
        f"cap{int(queue_cap) if queue_cap is not None else 'auto'}"
    )


def _point_seed(base_seed: int, key: str) -> int:
    """Deterministic per-point RNG seed: stable across runs and
    platforms (crc32, not hash())."""
    return (int(base_seed) ^ zlib.crc32(key.encode())) & 0x7FFFFFFF


def _run_scenario(model: sim.ServiceModel, knobs: sim.SimKnobs,
                  scenario: dict, duration_s: float) -> sim.SimResult:
    if scenario["kind"] == "closed":
        return sim.simulate(model, knobs,
                            closed_concurrency=int(scenario["concurrency"]),
                            duration_s=duration_s)
    return sim.simulate(model, knobs, arrivals=scenario["arrivals"])


def build_serve_plan(
    profile: dict,
    scenarios: Sequence[dict],
    *,
    bucket_ladders: Sequence[Sequence[int]],
    slos_ms: Sequence[float],
    replicas: Sequence[int] = DEFAULT_REPLICAS,
    eager_options: Sequence[bool] = (True,),
    queue_caps: Sequence[Optional[int]] = (None,),
    inflight_per_replica: int = 2,
    duration_s: float = 10.0,
    seed: int = 0,
    latency_slo_ms: Optional[float] = None,
    shed_tolerance: float = 0.01,
    profile_path: Optional[str] = None,
    model: Optional[sim.ServiceModel] = None,
) -> dict:
    """The planner core: simulate every (scenario × grid point), judge
    each against its latency SLO + shed tolerance, and derive the
    replica recommendation per (scenario, SLO). Pure + deterministic;
    the CLI wraps it with artifact IO.

    Each ``scenario`` dict carries ``label``, ``kind``
    (``poisson`` / ``trace`` / ``closed``) and either ``arrivals``
    (``[(t, rows), ...]``) or ``concurrency``. ``latency_slo_ms`` is
    the per-point "good p99" bound; None = 2x that point's batching SLO
    (the ReqTracer convention). ``model`` accepts an already-built
    :class:`~distributedpytorch_tpu.serve.sim.ServiceModel` so notes it
    collected earlier (e.g. scaled buckets behind the CLI's default
    rate ladder) land in the artifact too — ONE model, one note list."""
    if model is None:
        model = sim.ServiceModel(profile)
    points: List[dict] = []
    for scenario in scenarios:
        for ladder in bucket_ladders:
            ladder = tuple(int(b) for b in ladder)
            for slo_ms in slos_ms:
                lat_slo = (
                    float(latency_slo_ms) if latency_slo_ms is not None
                    else 2.0 * float(slo_ms)
                )
                for n_replicas in replicas:
                    for eager in eager_options:
                        for cap in queue_caps:
                            key = point_key(scenario["label"], ladder,
                                            slo_ms, n_replicas, eager, cap)
                            knobs = sim.SimKnobs(
                                bucket_sizes=ladder,
                                slo_s=float(slo_ms) / 1e3,
                                replicas=int(n_replicas),
                                eager=bool(eager),
                                hard_cap_images=cap,
                                inflight_per_replica=inflight_per_replica,
                                seed=_point_seed(seed, key),
                            )
                            result = _run_scenario(model, knobs, scenario,
                                                   duration_s)
                            predicted = result.payload()
                            slo_ok = (
                                predicted["shed_rate"] <= shed_tolerance
                                and predicted["p99_ms"] is not None
                                and predicted["p99_ms"] <= lat_slo
                            )
                            points.append({
                                "key": key,
                                "scenario": scenario["label"],
                                "bucket_sizes": list(ladder),
                                "slo_ms": float(slo_ms),
                                "latency_slo_ms": lat_slo,
                                "replicas": int(n_replicas),
                                "eager": bool(eager),
                                "queue_cap_images": (
                                    int(cap) if cap is not None else None
                                ),
                                "predicted": predicted,
                                "slo_ok": slo_ok,
                            })

    # replica recommendation per (scenario, SLO): the smallest replica
    # count that holds the SLO at the BASE knobs (first ladder / eager
    # option / cap — the what-if axes don't vote)
    base_ladder = list(int(b) for b in bucket_ladders[0])
    base_eager = bool(eager_options[0])
    base_cap = queue_caps[0]
    recommendations: List[dict] = []
    for scenario in scenarios:
        for slo_ms in slos_ms:
            candidates = [
                p for p in points
                if p["scenario"] == scenario["label"]
                and p["slo_ms"] == float(slo_ms)
                and p["bucket_sizes"] == base_ladder
                and p["eager"] == base_eager
                and p["queue_cap_images"] == (
                    int(base_cap) if base_cap is not None else None
                )
            ]
            feasible = sorted(
                (p for p in candidates if p["slo_ok"]),
                key=lambda p: p["replicas"],
            )
            recommendations.append({
                "scenario": scenario["label"],
                "slo_ms": float(slo_ms),
                "replicas": feasible[0]["replicas"] if feasible else None,
                "note": (
                    None if feasible else
                    "no replica count in the grid holds this SLO — "
                    "widen --replicas or relax the SLO"
                ),
                "candidates": [
                    {"replicas": p["replicas"],
                     "p99_ms": p["predicted"]["p99_ms"],
                     "shed_rate": p["predicted"]["shed_rate"],
                     "slo_ok": p["slo_ok"]}
                    for p in sorted(candidates,
                                    key=lambda p: p["replicas"])
                ],
            })

    # NO wall-clock field anywhere: same profile + trace + seed must
    # produce a bit-identical artifact (pinned by test)
    return {
        "kind": SERVE_PLAN_KIND,
        "version": SERVE_PLAN_VERSION,
        "seed": int(seed),
        "duration_s": float(duration_s),
        "shed_tolerance": float(shed_tolerance),
        "profile": {
            "path": profile_path,
            "leg": profile.get("leg"),
            "slo_ms": profile.get("slo_ms"),
            "bucket_sizes": profile.get("bucket_sizes"),
            "engine_fingerprint": profile.get("engine_fingerprint"),
        },
        "grid": {
            "bucket_ladders": [
                [int(b) for b in ladder] for ladder in bucket_ladders
            ],
            "slo_ms": [float(s) for s in slos_ms],
            "replicas": [int(r) for r in replicas],
            "eager": [bool(e) for e in eager_options],
            "queue_caps": [
                int(c) if c is not None else None for c in queue_caps
            ],
            "inflight_per_replica": int(inflight_per_replica),
        },
        "scenarios": [
            {k: v for k, v in s.items() if k != "arrivals"}
            for s in scenarios
        ],
        "service_model_notes": list(model.notes),
        "points": points,
        "recommendations": recommendations,
    }


# -- plan-artifact IO (the planner-file idiom; jax-free) ---------------------
def save_serve_plan(payload: dict, path: str) -> str:
    """Atomic, byte-deterministic write (sorted keys — the bit-identical
    pin diffs file bytes)."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_serve_plan(path: Optional[str]) -> Optional[dict]:
    """The plan, or None (with a logged note) for missing / corrupt /
    version-skewed files — consumers degrade, a torn plan never drives
    a fleet resize."""
    if not path:
        return None
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError) as exc:
        logger.warning("serve plan %r unreadable (%s) — ignored",
                       path, type(exc).__name__)
        return None
    if (
        not isinstance(payload, dict)
        or payload.get("kind") != SERVE_PLAN_KIND
        or payload.get("version") != SERVE_PLAN_VERSION
        or not isinstance(payload.get("points"), list)
    ):
        logger.warning(
            "serve plan %r is not a %s v%d artifact — ignored (stale or "
            "foreign file)", path, SERVE_PLAN_KIND, SERVE_PLAN_VERSION,
        )
        return None
    return payload


# -- CLI ---------------------------------------------------------------------
def get_args(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m distributedpytorch_tpu plan-serve",
        description="Plan serve-tier capacity from recorded artifacts "
                    "alone: replay arrival traces against profiled "
                    "service times in a discrete-event simulation of "
                    "the live queue policy (no devices, no jax)",
    )
    parser.add_argument("--profile", required=True,
                        help="dpt_serve_profile v1 artifact (bench_serve "
                             "writes one per leg) — the calibration input")
    parser.add_argument("--trace", action="append", default=[],
                        metavar="PATH",
                        help="Recorded dpt_serve_arrivals JSONL to replay "
                             "(serve --record-arrivals / bench_serve legs); "
                             "repeatable")
    parser.add_argument("--rates", type=float, nargs="+", default=None,
                        help="Open-loop Poisson arrival rates (rows/s); "
                             "default: fractions of the profile's "
                             "one-replica capacity "
                             f"({'/'.join(str(f) for f in DEFAULT_RATE_FRACTIONS)}x)")
    parser.add_argument("--closed", type=int, nargs="+", default=[],
                        metavar="C",
                        help="Closed-loop concurrency levels to simulate")
    parser.add_argument("--duration", type=float, default=10.0,
                        help="Virtual seconds per simulated scenario")
    parser.add_argument("--slo-ms", type=float, nargs="+", default=None,
                        help="Batching SLO grid (default: the profile's "
                             "own SLO)")
    parser.add_argument("--replicas", type=int, nargs="+",
                        default=list(DEFAULT_REPLICAS),
                        help="Replica-count search ladder")
    parser.add_argument("--buckets", type=int, nargs="+", default=None,
                        help="The serving bucket ladder being planned for "
                             "(default: the profile's recorded ladder). "
                             "Must MATCH the profile — a mismatch refuses "
                             "loudly (the staleness guard)")
    parser.add_argument("--sweep-buckets", type=str, nargs="+", default=[],
                        metavar="L1,L2,...",
                        help="Additional what-if ladders (comma-separated, "
                             "e.g. 1,2,4) — simulated with row-scaled "
                             "service times, noted in the artifact")
    parser.add_argument("--sweep-eager", action="store_true",
                        help="Also simulate --no-eager (pure SLO batching) "
                             "at every point")
    parser.add_argument("--queue-caps", type=int, nargs="+", default=None,
                        help="Admission-cap grid (pending images; default: "
                             "the queue's own 4x-largest-bucket rule)")
    parser.add_argument("--inflight-per-replica", type=int, default=2,
                        help="In-flight buckets per replica (ServeConfig's "
                             "knob): the simulator's service channels per "
                             "replica — must match the deployment being "
                             "planned for")
    parser.add_argument("--latency-slo-ms", type=float, default=None,
                        help="Good-request p99 bound per point (default "
                             "2x that point's batching SLO — the "
                             "ReqTracer convention)")
    parser.add_argument("--shed-tolerance", type=float, default=0.01,
                        help="Max acceptable shed rate for a point to "
                             "count as holding its SLO")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default="serve_plan.json",
                        help="Write the dpt_serve_plan artifact here")
    # engine identity (same flags as the serve CLI): when ANY is given,
    # the profile's engine fingerprint is cross-checked — a profile from
    # a different model/resolution/quantization refuses loudly
    parser.add_argument("--model", dest="model_arch", default=None,
                        choices=["unet", "milesial"])
    parser.add_argument("--model-widths", type=int, nargs="+", default=None)
    parser.add_argument("--image-size", type=int, nargs=2, default=None,
                        metavar=("W", "H"))
    parser.add_argument("--s2d-levels", type=int, default=None)
    parser.add_argument("--quantize", default=None, choices=["int8"])
    parser.add_argument("--kernels", default=None,
                        choices=["xla", "pallas"])
    return parser.parse_args(argv)


def _expected_fingerprint(args) -> Optional[str]:
    """The engine fingerprint to cross-check, or None when no identity
    flag was given (nothing to check against). Unspecified flags fall
    back to the ServeConfig defaults, exactly like the serve CLI."""
    given = (args.model_arch, args.model_widths, args.image_size,
             args.s2d_levels, args.quantize, args.kernels)
    if all(v is None for v in given):
        return None
    return engine_fingerprint(
        model_arch=args.model_arch or "unet",
        image_size=tuple(args.image_size) if args.image_size else (960, 640),
        model_widths=tuple(args.model_widths) if args.model_widths else None,
        s2d_levels=args.s2d_levels if args.s2d_levels is not None else -1,
        quantize=args.quantize,
        kernels=args.kernels or "xla",
    )


def _build_scenarios(args, model: sim.ServiceModel,
                     ladder: Sequence[int]) -> List[dict]:
    scenarios: List[dict] = []
    seen_labels: dict = {}
    for path in args.trace:
        arrivals = sim.load_arrival_trace(path)
        if arrivals is None:
            raise ValueError(
                f"arrival trace {path!r} is missing, unreadable, or not a "
                f"{sim.TRACE_KIND} v{sim.TRACE_VERSION} file — refusing to "
                "plan from it"
            )
        label = f"trace:{os.path.basename(path)}"
        # two traces sharing a basename must not share a label: the
        # recommendation groups points BY label, and a collision would
        # merge two traffic patterns into one candidates list
        n = seen_labels.get(label, 0)
        seen_labels[label] = n + 1
        if n:
            label = f"{label}#{n + 1}"
        scenarios.append({
            "label": label,
            "kind": "trace",
            "path": path,
            "requests": len(arrivals),
            "arrivals": arrivals,
        })
    rates = args.rates
    if rates is None and not args.trace and not args.closed:
        # default rate ladder: fractions of the profile's one-replica
        # best-case capacity (largest bucket, fully packed); the shared
        # model keeps any scaled-bucket note this anchor produces
        capacity = model.capacity_rows_per_s(ladder, 1)
        rates = [round(f * capacity, 1) for f in DEFAULT_RATE_FRACTIONS]
    for rate in rates or []:
        label = f"poisson:{rate:g}rps"
        scenarios.append({
            "label": label,
            "kind": "poisson",
            "rate_rps": float(rate),
            "arrivals": sim.poisson_arrivals(
                float(rate), args.duration,
                seed=_point_seed(args.seed, label),
            ),
        })
    for concurrency in args.closed:
        scenarios.append({
            "label": f"closed:c{int(concurrency)}",
            "kind": "closed",
            "concurrency": int(concurrency),
        })
    if not scenarios:
        raise ValueError("no scenarios: give --trace, --rates, or --closed")
    return scenarios


def main(argv=None) -> int:
    args = get_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    try:
        profile = load_profile(
            args.profile,
            expect_buckets=args.buckets,
            expect_fingerprint=_expected_fingerprint(args),
        )
    except ProfileMismatchError as exc:
        print(f"plan-serve: REFUSING stale/mismatched profile: {exc}",
              file=sys.stderr)
        return 2
    if profile is None:
        print(
            f"plan-serve: no usable profile at {args.profile!r} "
            "(missing/corrupt/version-skewed) — nothing to calibrate "
            "from; run tools/bench_serve.py to produce one",
            file=sys.stderr,
        )
        return 2
    ladder = args.buckets or profile.get("bucket_sizes")
    if not ladder:
        # pre-guard profiles (no recorded ladder): fall back to the
        # bucket keys the histograms themselves cover
        ladder = sorted(int(b) for b in profile.get("buckets", {}))
    ladders: List[Sequence[int]] = [tuple(int(b) for b in ladder)]
    for spec in args.sweep_buckets:
        ladders.append(tuple(int(b) for b in spec.split(",")))
    try:
        model = sim.ServiceModel(profile)
        scenarios = _build_scenarios(args, model, ladders[0])
    except ValueError as exc:
        print(f"plan-serve: {exc}", file=sys.stderr)
        return 2
    slos = args.slo_ms or [float(profile.get("slo_ms") or 50.0)]
    plan = build_serve_plan(
        profile,
        scenarios,
        bucket_ladders=ladders,
        slos_ms=slos,
        replicas=args.replicas,
        eager_options=(True, False) if args.sweep_eager else (True,),
        queue_caps=(
            list(args.queue_caps) if args.queue_caps else [None]
        ),
        inflight_per_replica=args.inflight_per_replica,
        duration_s=args.duration,
        seed=args.seed,
        latency_slo_ms=args.latency_slo_ms,
        shed_tolerance=args.shed_tolerance,
        profile_path=args.profile,
        model=model,
    )
    save_serve_plan(plan, args.out)
    print(f"serve plan: {len(plan['points'])} point(s) over "
          f"{len(scenarios)} scenario(s) -> {args.out}")
    for rec in plan["recommendations"]:
        if rec["replicas"] is not None:
            print(f"  {rec['scenario']} @ slo {rec['slo_ms']:g} ms -> "
                  f"{rec['replicas']} replica(s)")
        else:
            print(f"  {rec['scenario']} @ slo {rec['slo_ms']:g} ms -> "
                  f"NO feasible point ({rec['note']})")
    for note in plan["service_model_notes"]:
        print(f"  note: {note}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
