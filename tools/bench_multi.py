#!/usr/bin/env python3
"""Single-process multi-config on-chip A/B measurement with resume and
poison-marking.

Why one process: a chip belongs to one process at a time, so the whole
A/B program runs in the process that holds it — no probe child, no
lock — cheapest compile classes first and the largest graphs last,
with:

  * a JSONL artifact appended after EVERY config (a mid-program death
    still leaves everything measured so far);
  * an ``attempting`` marker before each config, so a process killed
    mid-compile attributes the kill to the config that caused it;
  * poison-marking — a config that watchdogged or whose attempt killed
    the process is recorded and NEVER retried (re-running the killer
    compile would just wedge the next run);
  * resume — configs with a successful line are skipped, so a
    re-invocation only ever spends chip time on unmeasured configs;
  * ``--plan`` — an auto-planner plan file (``python -m
    distributedpytorch_tpu plan``, docs/PERFORMANCE.md "Planning")
    reorders the legs it models to predicted-winner-first and stamps
    ``plan_rank``/``plan_cost_s`` into their provenance rows, so a
    short run measures the configs the cost model bets on first. Legs the planner cannot model — the Pallas/Mosaic compiles,
    the sweeps' own grids — KEEP their hand-ordered safety position at
    the tail: prediction never moves a wedge-suspect compile earlier.

Exit codes (the program wrapper's loop contract):
  0 = every config terminally resolved (measured, poisoned, or failed
      deterministically) — nothing left to spend chip time on
  1 = innocent configs remain unmeasured (re-invoke to continue)
  3 = a config hit its watchdog (poison-marked; re-invoke to continue)

Measurement methodology is `bench.py`'s own `run()` — same compiled
executables, same chained-dispatch timing, same JSON fields — driven
per-config by setting its module config; numbers land in the same
metric series the driver's BENCH artifact uses.

Reference anchor: the (Step,Time) instrumentation this program must
beat lives at reference utils/train_utils.py:75-79.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributedpytorch_tpu.obs import flight  # noqa: E402 — stdlib-only

# (name, env overrides, per-config watchdog seconds). Order is the
# cheapest-first story (see module docstring): pixel and b8 are the
# default graph's compile class, the milesial pair is plain XLA convs,
# and the largest compiles go last in increasing graph size: the Pallas
# fused loss, then the taps family — scoped-to-level-1 taps, full taps,
# and finally full taps with the Mosaic wgrad kernel on top.
CONFIGS = [
    ("pixel", {"BENCH_S2D_LEVELS": "0"}, 1200.0),
    ("b8", {"BENCH_BATCH": "8"}, 1200.0),
    ("milesial_s2d", {"BENCH_ARCH": "milesial"}, 1500.0),
    ("milesial_pixel",
     {"BENCH_ARCH": "milesial", "BENCH_S2D_LEVELS": "0"}, 1500.0),
    ("pallas_loss", {"BENCH_PALLAS_LOSS": "1"}, 1500.0),
    # 1F1B vs GPipe microbatch sweep (tools/bench_pipeline.schedule_sweep,
    # M ∈ {2,4,8,16} at fixed µb size): per-cell temp-buffer bytes from
    # XLA's buffer assignment + runtime peak_bytes_in_use + imgs/s — the
    # on-chip side of the activation-wall story. Needs ≥2 devices; on a
    # single-chip window the sweep records a skip line and exits clean
    # (no chip time wasted). Cheap, bounded cells → a 300 s budget.
    ("pipeline_sched_sweep", {"BENCH_PIPELINE_SWEEP": "1"}, 300.0),
    # Serving-tier load generator (tools/bench_serve.py): closed-loop
    # concurrency sweep + in-SLO and overload open-loop runs against the
    # AOT-compiled continuous-batching server (docs/SERVING.md). Safe
    # compile class (plain eval forwards, the same executables the
    # analyzer's --hlo tier AOT-compiles); single-process data-parallel
    # replicas, NO collectives — the static preflight correctly has
    # nothing to check for it (see _preflight_combos). Budget covers
    # per-bucket×replica AOT compiles + ~7 bounded measurement legs.
    ("serve_bench", {"BENCH_SERVE": "1"}, 600.0),
    # Precision-policy A/B (tools/bench_dtype.py): f32 vs bf16 vs
    # bf16_params train-step imgs/s + memory_analysis bytes at fixed
    # batch, plus the serve-forward f32-vs-int8 weight-argument bytes —
    # the measurement row behind the --dtype default and the ≥50 imgs/s
    # chase (bf16 conv compute ≈2x on the MXU). Safe compile class (the
    # default train step at the default geometry, three dtype variants);
    # single-device, collective-free → the static preflight has nothing
    # to check (no-combos fast path, like serve_bench). Budget covers 3
    # train-step compiles + 2 forward compiles + bounded timed steps.
    ("dtype_sweep", {"BENCH_DTYPE_SWEEP": "1"}, 900.0),
    # Mesh-geometry A/B (tools/bench_mesh.py): hybrid vs pure mesh
    # shapes (parallel/mesh.py specs — DP / FSDP / MP / TP pure points
    # vs DxMxS hybrids) at a FIXED global batch — imgs/s + per-device
    # memory_analysis bytes per geometry, the measurement row behind
    # the composable-mesh engine and the planner's --meshes axis.
    # Plan-aware: with --plan, cells run planner-ranked-first and rows
    # stamp plan_rank. Compile class: the same GSPMD + shard_map
    # pipeline graphs the strategy tests compile in tier-1; specs the
    # window's device pool cannot satisfy skip clean (a 1-chip window
    # measures 1x1x1 and records explicit skips). Pipeline-bearing
    # specs ride the static preflight (the analyze --mesh surface).
    ("mesh_sweep", {"BENCH_MESH_SWEEP": "1"}, 600.0),
    # Per-kernel compile-only Mosaic probes (ops/kernels.PROBES via
    # tools/probe_kernels.py — the wgrad_pallas_probe pattern, one row
    # per kernel): 60 s to learn accepted-or-rejected for EVERY Pallas
    # kernel before the kernel_sweep (and any future --kernels pallas
    # leg) spends measurement budget on a graph Mosaic refuses. Writes
    # the per-chip priors file ($DPT_KERNEL_PRIORS, default
    # kernel_priors.json) that ops/kernels.get_kernel_policy and
    # `plan --kernel-priors` consume. Zero execution; a wedge poisons
    # only this 60 s probe.
    ("kernel_probe", {"BENCH_KERNEL_PROBE": "1"}, 60.0),
    # Kernel-policy A/B (tools/bench_kernels.py): --kernels xla vs
    # pallas per PHASE (train_loss / epilogue / eval_stats /
    # serve_mask) — which phase each kernel bought back, the
    # measurement row behind the --kernels default and the ≥50 imgs/s
    # chase. Hand-ordered AFTER kernel_probe so Mosaic-rejected cells
    # skip instead of re-compiling a refused graph — and --plan can
    # only move it earlier when the plan carries ranked pallas points,
    # which requires the plan to have been generated against an
    # EXISTING priors file (planner._leg_selector), so the skip data is
    # there either way. Single-device, collective-free → the static
    # preflight's no-combos fast path.
    ("kernel_sweep", {"BENCH_KERNEL_SWEEP": "1"}, 900.0),
    # taps scoped to the top s2d level only (320x480 planes = 153600 px;
    # the next level down is 38400): where the tall-contraction win
    # concentrates, at a severalfold smaller XLA graph than full taps —
    # the fallback if window-1's full-taps compile failure repeats
    ("wgrad_taps_l1",
     {"BENCH_WGRAD_TAPS": "1", "DPT_WGRAD_TAPS_MIN_HW": "100000"}, 1500.0),
    # compile-only probe for the Mosaic wgrad kernel (VERDICT r05
    # next-8): 30 s to learn compiled-or-rejected BEFORE the full taps
    # legs spend a window on a graph whose kernel may not even lower.
    # A rejection lands as a config_error line (terminal); a wedge
    # poisons only this 30 s probe, not a 2700 s measurement budget.
    ("wgrad_pallas_probe",
     {"BENCH_WGRAD_TAPS": "1", "DPT_WGRAD_BACKEND": "pallas",
      "BENCH_COMPILE_ONLY": "1"}, 30.0),
    ("wgrad_taps", {"BENCH_WGRAD_TAPS": "1"}, 2700.0),
    # the taps path with the single-pass Pallas wgrad kernel
    # (ops/wgrad_pallas.py) on channels>=64 taps: Mosaic compile on top
    # of the big taps graph — the most dangerous compile, dead last
    ("wgrad_taps_pallas",
     {"BENCH_WGRAD_TAPS": "1", "DPT_WGRAD_BACKEND": "pallas"}, 2700.0),
]

# Every env key any config may set — popped between configs so a lever
# can never leak from one config into the next.
_CONFIG_ENV_KEYS = sorted({k for _, env, _ in CONFIGS for k in env})

_POISON_PREFIXES = ("watchdog", "wedged_previous_attempt",
                    "static_check_failed")
_INNOCENT_PREFIX = "runtime_error"

# Static-analysis preflight (distributedpytorch_tpu/analysis, docs/
# ANALYSIS.md): a config whose step program fails the jaxpr collective
# checker would burn its whole budget on a deadlocked schedule or a
# silently-degenerated strategy — poison-mark it BEFORE spending chip
# time. The analyzer runs in a provisioned CPU subprocess (utils/
# provision.py): zero chip involvement, works on any window size.
PREFLIGHT_TIMEOUT_S = 300.0

def flight_artifact_path(out_path: str, name: str) -> str:
    """Deterministic flight-recorder artifact path for one config, next
    to the session artifact: the poison line of a leg whose process DIED
    (load_state's wedged_previous_attempt mark, stamped by the NEXT
    invocation) must be able to reference the artifact the dead process
    dumped without re-deriving anything."""
    return os.path.join(
        os.path.dirname(os.path.abspath(out_path)), f"flight_{name}.json"
    )


def append_line(path: str, obj: dict) -> None:
    obj = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()), **obj}
    with open(path, "a") as f:
        f.write(json.dumps(obj) + "\n")
        f.flush()
        os.fsync(f.fileno())


def load_plan_ranks(path: "str | None") -> dict:
    """{leg name: {plan_rank, plan_cost_s, plan_point}} from an
    auto-planner plan file (``python -m distributedpytorch_tpu plan``,
    analysis/planner.py), or {} when no plan was given or the file is
    missing/unreadable/stale (wrong schema version) — a half-written or
    version-skewed plan must degrade to the hand-ordered config
    sequence, never silently reorder a window."""
    if not path:
        return {}
    from distributedpytorch_tpu.analysis.planner import load_plan, rank_legs

    plan = load_plan(path)
    if plan is None:
        print(f"bench_multi: plan {path!r} missing or stale — keeping "
              f"the default config order")
        return {}
    try:
        ranks = rank_legs(plan, CONFIGS)
    except Exception as exc:  # noqa: BLE001 — semantically-corrupt plan
        # a plan that passes the schema check but carries garbage point
        # fields must still degrade, never crash the window driver
        print(f"bench_multi: plan {path!r} unreadable "
              f"({type(exc).__name__}: {exc}) — keeping the default "
              f"config order")
        return {}
    print(f"bench_multi: plan {path!r} ranks {len(ranks)} of "
          f"{len(CONFIGS)} configs: "
          + ", ".join(f"{n}#{d['plan_rank']}"
                      for n, d in sorted(ranks.items(),
                                         key=lambda kv: kv[1]["plan_rank"])))
    return ranks


def order_by_plan(todo, plan_ranks: dict):
    """Planned legs first, best predicted rank first; legs the planner
    does not model (Pallas/Mosaic compiles, the sweeps' own grids) keep
    their hand-ordered SAFETY position after the ranked ones — the
    wedge-suspect compiles stay last no matter what the plan says."""
    if not plan_ranks:
        return todo
    ranked = sorted(
        (t for t in todo if t[0] in plan_ranks),
        key=lambda t: plan_ranks[t[0]]["plan_rank"],
    )
    return ranked + [t for t in todo if t[0] not in plan_ranks]


def _aot_counters() -> dict:
    """Process-wide dpt_aot_cache_total values (the bench runs every
    leg in ONE process, so per-leg deltas are exact)."""
    from distributedpytorch_tpu.obs import defs as obsm

    return {k: int(v) for k, v in obsm.AOT_CACHE.as_dict().items()}


def _aot_delta(before: dict) -> dict:
    """Per-leg AOT store provenance: how many of this leg's executables
    loaded vs compiled (a $DPT_AOT_CACHE-armed window's later legs
    should be all-hit; all zeros = store unarmed)."""
    now = _aot_counters()
    return {k: now.get(k, 0) - before.get(k, 0)
            for k in ("hit", "miss", "skew")}


def _plan_provenance(plan_ranks: dict, name: str) -> dict:
    info = plan_ranks.get(name)
    if not info:
        return {}
    return {"plan_rank": info["plan_rank"],
            "plan_cost_s": info.get("plan_cost_s"),
            "plan_point": info.get("plan_point")}


def supervisor_restarts(path: str = "") -> "int | None":
    """Restart count from the elastic supervisor's report JSON
    (dist/elastic.py writes it; path via $DPT_ELASTIC_REPORT), or None
    when no supervisor is wired in. Recorded in the window's session
    lines so a FLAPPING chip window — the job survived only because the
    supervisor kept relaunching it — is distinguishable from a clean
    one when reading the A/B numbers. Explicit opt-in only: guessing a
    default path would stamp STALE restart counts from some past drill
    onto unrelated sessions, the exact misread this field prevents."""
    path = path or os.environ.get("DPT_ELASTIC_REPORT", "")
    if not path:
        return None
    try:
        with open(path) as f:
            return int(json.load(f).get("restarts", 0))
    except (OSError, ValueError, TypeError, AttributeError):
        return None


def load_state(path: str) -> dict:
    """Parse the artifact into {config_name: status}.

    status: 'ok' (measured), 'poison' (this config wedged a window —
    never retry), 'innocent' (failed because the runtime was already
    dead — retry on a later window), 'permanent' (deterministic error).
    An ``attempting`` marker with no following result line means the
    process died mid-config: that config is poison-marked IN the
    artifact so the attribution is durable, not re-derived.
    """
    state: dict = {}
    attempting = None
    try:
        with open(path) as f:
            lines = [json.loads(line) for line in f if line.strip()]
    except OSError:
        return state
    for d in lines:
        name = d.get("config")
        if name is None:
            continue
        if d.get("event") == "attempting":
            attempting = name
            continue
        attempting = None
        err = d.get("error")
        if err is None:
            state[name] = "ok"
        elif err.startswith(_POISON_PREFIXES):
            state[name] = "poison"
        elif err.startswith(_INNOCENT_PREFIX):
            state[name] = "innocent"
        else:
            state[name] = "permanent"
    if attempting is not None:
        append_line(path, {
            "config": attempting,
            "error": "wedged_previous_attempt: process died mid-config "
                     "(killed or crashed during compile/measure)",
            # the dead process's post-mortem, if its watchdog/excepthook
            # managed to dump one before the end (obs/flight.py)
            "flight_recorder": flight_artifact_path(path, attempting),
        })
        state[attempting] = "poison"
    return state


def _preflight_combos(env: dict):
    """Which strategy × schedule combos a config's step will exercise —
    what the static preflight must clear. Single-device bench configs
    run no collectives (nothing to check statically, and the analyzer's
    lint layer is CI's job, not a chip window's); the pipeline schedule
    sweep traces the MP schedules the analyzer owns. The serve bench
    (BENCH_SERVE) is deliberately in the no-combos class: its replica
    groups are independent single-device executables with no collective
    program, so a static collective check would be vacuous — it must
    skip, not block (tests/test_bench_multi.py pins this)."""
    if env.get("BENCH_PIPELINE_SWEEP") == "1":
        return (("MP", ("gpipe", "1f1b")),)
    if env.get("BENCH_MESH_SWEEP") == "1":
        # every stage-bearing cell the sweep can run (bench_mesh.
        # PREFLIGHT_STAGE_SPECS covers default_specs for any pool up to
        # 8 devices — the 4-stage 2x1x4 program is structurally
        # different from the 2-stage ones and must be vetted too); the
        # analyzer accepts mesh specs directly (contracts derive from
        # the sharding rules — the analyze --mesh surface). The sweep
        # runs the config default schedule (gpipe).
        from tools.bench_mesh import PREFLIGHT_STAGE_SPECS

        return tuple((spec, ("gpipe",)) for spec in PREFLIGHT_STAGE_SPECS)
    return ()


def _run_analyze(strategies, schedules, timeout: float):
    """Invoke the analyzer in a provisioned CPU subprocess (the shared
    runner: analysis/preflight.py); returns (rc, findings_lines). rc 2
    (or a crashed/timed-out analyzer) is an INFRA failure — the caller
    must treat it as clean rather than block a measurement on analyzer
    plumbing. A thin module-level seam so tests can stub it."""
    from distributedpytorch_tpu.analysis.preflight import run_preflight

    return run_preflight(strategies, schedules, timeout)


def _static_preflight(name: str, env: dict, out_path: str) -> bool:
    """True = the config may spend chip budget; False = it failed static
    checks and was poison-marked (``static_check_failed`` provenance, a
    _POISON_PREFIXES member — never retried, like any other config that
    would wedge a window). Analyzer infra failures never block."""
    combos = _preflight_combos(env)
    if not combos:
        return True
    for strategies_schedules in combos:
        strategy, schedules = strategies_schedules
        rc, findings = _run_analyze([strategy], list(schedules),
                                    PREFLIGHT_TIMEOUT_S)
        if rc == 0:
            continue
        if rc == 1 and findings:
            append_line(out_path, {
                "config": name,
                "error": f"static_check_failed: {findings[0]}",
                "findings": findings,
            })
            print(f"bench_multi: static preflight FAILED for {name!r} "
                  f"({len(findings)} finding(s)) — poison-marked, no "
                  f"budget spent: {findings[0]}")
            return False
        print(f"bench_multi: static preflight for {name!r} could not run "
              f"(rc={rc}) — proceeding: "
              f"{findings[0] if findings else 'no detail'}")
    return True


def _arm_config_watchdog(path: str, name: str, secs: float):
    """A wedged runtime hangs inside a native call no exception escapes;
    only a timer thread + hard exit gets an attribution line written."""
    def fire():
        # dump the flight ring FIRST: the poison line ships its own
        # post-mortem (the ring's tail says which phase wedged), so a
        # dead chip-window leg is attributable without a rerun
        artifact = flight.dump(
            f"bench_watchdog: {name}",
            path=flight_artifact_path(path, name),
            extra={"budget_s": secs},
        )
        append_line(path, {
            "config": name,
            "error": f"watchdog: no result after {secs:.0f}s "
                     "(compile wedged or runtime died mid-config)",
            "flight_recorder": artifact,
        })
        sys.stdout.flush()
        os._exit(3)

    t = threading.Timer(secs, fire)
    t.daemon = True
    t.start()
    return t


def _run_one(bench, name: str, env: dict, budget: float) -> dict:
    """Point bench.py's module config at this config and run its
    measurement path (same executables/timing/fields as the driver
    artifact). Pre-existing values of the config env keys are snapshotted
    and restored afterward — an in-process run must not destroy ambient
    state the caller (or an outer harness) set."""
    snapshot = {k: os.environ.get(k) for k in _CONFIG_ENV_KEYS}
    try:
        for k in _CONFIG_ENV_KEYS:
            os.environ.pop(k, None)
        os.environ.update(env)
        if env.get("BENCH_PIPELINE_SWEEP") == "1":
            # schedule-sweep config: runs bench_pipeline's in-process grid
            # instead of bench.run()'s single-device step measurement
            from tools.bench_pipeline import schedule_sweep

            return schedule_sweep(budget_s=budget)
        if env.get("BENCH_SERVE") == "1":
            # serving-tier load generator: in-process closed+open-loop
            # sweep (tools/bench_serve.py), not a train-step measurement
            from tools.bench_serve import run_bench

            return run_bench(budget_s=budget)
        if env.get("BENCH_KERNEL_PROBE") == "1":
            # compile-only Mosaic accept/reject probes for every Pallas
            # kernel → the per-chip priors file (tools/probe_kernels.py)
            from tools.probe_kernels import run_and_save

            priors_path = os.environ.get(
                "DPT_KERNEL_PRIORS", "kernel_priors.json"
            )
            return run_and_save(priors_path)
        if env.get("BENCH_KERNEL_SWEEP") == "1":
            # kernel-policy phase A/B (tools/bench_kernels.py) at the
            # reference geometry — in-process, budget-aware; the probe
            # leg's priors skip Mosaic-rejected cells
            from distributedpytorch_tpu.ops.kernels import load_priors
            from tools.bench_kernels import kernel_sweep

            priors_path = os.environ.get(
                "DPT_KERNEL_PRIORS", "kernel_priors.json"
            )
            return kernel_sweep(
                batch=int(env.get("BENCH_BATCH", 4)),
                hw=(int(env.get("BENCH_H", 640)), int(env.get("BENCH_W", 960))),
                widths=(32, 64, 128, 256),
                steps=5,
                budget_s=budget,
                priors=load_priors(priors_path),
            )
        if env.get("BENCH_MESH_SWEEP") == "1":
            # mesh-geometry grid (tools/bench_mesh.py) at the reference
            # geometry — in-process, budget-aware; planner-ranked cells
            # first when the session carries a plan ($DPT_BENCH_PLAN)
            from tools.bench_mesh import mesh_sweep

            return mesh_sweep(
                batch=int(env.get("BENCH_BATCH", 8)),
                hw=(int(env.get("BENCH_H", 640)), int(env.get("BENCH_W", 960))),
                widths=(32, 64, 128, 256),
                steps=5,
                budget_s=budget,
            )
        if env.get("BENCH_DTYPE_SWEEP") == "1":
            # precision-policy grid (tools/bench_dtype.py) at the
            # reference geometry — in-process, budget-aware
            from tools.bench_dtype import dtype_sweep

            return dtype_sweep(
                batch=int(env.get("BENCH_BATCH", 4)),
                hw=(int(env.get("BENCH_H", 640)), int(env.get("BENCH_W", 960))),
                widths=(32, 64, 128, 256),
                steps=5,
                budget_s=budget,
            )
        # run() reads the lever envs itself but takes batch/arch/geometry
        # from module globals frozen at bench import — re-derive them here.
        bench.BATCH = int(env.get("BENCH_BATCH", 4))
        bench.H = int(env.get("BENCH_H", 640))
        bench.W = int(env.get("BENCH_W", 960))
        bench.ARCH = env.get("BENCH_ARCH", "unet")
        return bench.run()
    finally:
        for k, v in snapshot.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main(argv=None) -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        repo, ".perf_r05", "bench_multi.jsonl"))
    ap.add_argument("--plan", default=None, metavar="PLAN_JSON",
                    help="Auto-planner plan file (python -m "
                         "distributedpytorch_tpu plan): legs the plan "
                         "ranks run first in predicted-winner order and "
                         "their rows carry plan_rank/plan_cost_s; "
                         "missing/stale plans degrade to the default "
                         "order")
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    plan_ranks = load_plan_ranks(args.plan)
    if args.plan:
        # the in-process sweeps that are themselves plan-aware (the
        # mesh sweep's ranked-cells-first ordering) read the session's
        # plan from here
        os.environ["DPT_BENCH_PLAN"] = args.plan
    state = load_state(args.out)
    todo = order_by_plan(
        [(n, e, b) for n, e, b in CONFIGS
         if state.get(n) in (None, "innocent")],
        plan_ranks,
    )
    if not todo:
        print(f"bench_multi: all {len(CONFIGS)} configs terminally "
              f"resolved in {args.out}")
        return 0

    append_line(args.out, {"event": "session_start",
                           "todo": [n for n, _, _ in todo],
                           "plan": (
                               {"path": args.plan,
                                "legs": {n: d["plan_rank"]
                                         for n, d in plan_ranks.items()}}
                               if plan_ranks else
                               {"path": args.plan, "legs": {}}
                               if args.plan else None
                           ),
                           "supervisor_restarts": supervisor_restarts()})

    # this process is the chip's one owner from here on: bench.run()
    # initialises jax in-process, and nothing below starts a child that
    # needs the device
    import bench

    # env hygiene is per-config: _run_one snapshots and restores the
    # ambient values of every key it touches. The flight dump path IS
    # process state — restore it on every exit so an embedding process
    # (tests) keeps its own routing.
    try:
        return _run_configs(args, todo, bench, plan_ranks)
    finally:
        flight.set_dump_path(None)


def _run_configs(args, todo, bench, plan_ranks=None) -> int:
    plan_ranks = plan_ranks or {}
    for name, env, budget in todo:
        # static preflight BEFORE the attempting marker and the watchdog:
        # a poison-marked config consumes none of the session budget
        if not _static_preflight(name, env, args.out):
            continue
        # route this leg's flight-recorder dumps (watchdog, trainer
        # aborts inside the bench, excepthook) to its own artifact
        flight.set_dump_path(flight_artifact_path(args.out, name))
        append_line(args.out, {"event": "attempting", "config": name,
                               "budget_s": budget,
                               **_plan_provenance(plan_ranks, name)})
        dog = _arm_config_watchdog(args.out, name, budget)
        aot_before = _aot_counters()
        try:
            result = _run_one(bench, name, env, budget)
        except Exception as exc:  # noqa: BLE001 — recorded, sequence goes on
            # An exception in a config is that config's error: this
            # process holds the chip, so there is no second opinion to
            # ask a child for. Record it as permanent and keep going —
            # a broken config must not starve the ones ordered after it.
            dog.cancel()
            artifact = flight.dump(
                f"config_error: {name}",
                extra={"error": f"{type(exc).__name__}: {str(exc)[:300]}"},
            )
            append_line(args.out, {
                "config": name,
                "error": f"config_error: {type(exc).__name__}: {exc}",
                "flight_recorder": artifact,
            })
            print(f"bench_multi: config {name!r} failed: {exc}")
            continue
        dog.cancel()
        # every leg's row names its flight-recorder artifact path — the
        # file exists iff something on the leg dumped (watchdog, abort,
        # excepthook); a healthy leg's path simply has nothing at it
        append_line(args.out, {
            "config": name, **result,
            "flight_recorder": flight_artifact_path(args.out, name),
            "aot_cache": _aot_delta(aot_before),
            **_plan_provenance(plan_ranks, name),
        })
        print(json.dumps({"config": name, **result}))
        sys.stdout.flush()

    state = load_state(args.out)
    unresolved = [n for n, _, _ in CONFIGS
                  if state.get(n) in (None, "innocent")]
    rc = 1 if unresolved else 0
    append_line(args.out, {
        "event": "session_end", "rc": rc, "unresolved": unresolved,
        "supervisor_restarts": supervisor_restarts(),
    })
    return rc


if __name__ == "__main__":
    sys.exit(main())
