"""Elastic multi-process supervisor: spawn, watch, relaunch, reshard.

The reference's launcher is `torchrun` (README.md:37) — i.e. TorchElastic:
an agent that supervises worker ranks, detects failures, and restarts
the job, possibly at a different world size. `jax.distributed` has no
such layer; this module provides it, provable end-to-end on the 2–4
process CPU/gloo mesh (tests/test_elastic.py):

  * **spawn** — launch N worker ranks of ``python -m
    distributedpytorch_tpu`` (or any command) with the torchrun-style
    env contract `dist/runtime.py` already maps onto
    `jax.distributed.initialize`, a fresh rendezvous port per attempt,
    per-rank log files, and a per-attempt heartbeat directory
    (``--heartbeat-dir`` is appended to the worker argv);
  * **watch** — poll exit codes + the beat files; `dist/health.classify`
    turns them into per-rank verdicts (dead / hung / desynced) within a
    bounded window (``--heartbeat-timeout`` beat age, opt-in
    ``--progress-timeout`` step-progress age, spawn grace for workers
    that die before their first beat);
  * **teardown** — on any failed rank, SIGTERM the survivors (they are
    blocked inside collectives their dead peer abandoned), wait
    ``--teardown-grace``, SIGKILL stragglers — and print ONE line per
    failed rank (``rank R: dead at epoch:step``) instead of every
    survivor's wall of channel tracebacks;
  * **relaunch** — up to ``--max-restarts`` times with exponential
    backoff, resuming from the newest intact retained checkpoint
    (``-c <method>`` appended to the worker argv once one exists — the
    mesh-resharding restore in checkpoint.py makes that work even when
    the world size changed);
  * **elastic world size** — a rank index that fails
    ``--rank-fail-limit`` consecutive attempts is treated as a lost
    slot: the job relaunches on the remaining M ranks (never below
    ``--min-ranks``), and the checkpoint saved on N processes reshards
    onto the M-process mesh.

  * **static preflight** — before the first spawn, the job's strategy ×
    schedule runs through the static distributed-correctness analyzer
    (``python -m distributedpytorch_tpu analyze`` in a provisioned CPU
    subprocess, docs/ANALYSIS.md): a statically-deadlocked schedule or a
    rank-divergent collective would otherwise spawn N ranks that hang
    until the heartbeat window expires and burn the whole restart budget
    relaunching into the same hang. Findings refuse the launch
    (``STATIC_CHECK_EXIT``); analyzer infra failures never block;
    ``--no-preflight`` overrides. The analyzer also compares the
    ordered-collective fingerprint under every simulated rank of THIS
    job's world size (``--fingerprint-world N``, rule
    ``collective-fingerprint``), so a collective gated on a rank the
    dual-rank re-trace never simulates is caught before the spawn
    instead of desyncing the gloo rendezvous.

Chaos drills: ``--chaos SITE[@RANK]:EPOCH:STEP[:COUNT]`` arms a fault
(utils/faults.py — ``rank_kill`` / ``rank_hang`` live in the step loop)
via ``--inject-fault`` on the FIRST attempt only, so the relaunched
attempt does not immediately re-kill itself at the same coordinates.

**Serve workload** (``--workload serve``): the same supervision adopts
serve processes (serve/cli.py) as its second workload — "a dead
dispatch loop should be a relaunch, not an outage" (ROADMAP), and the
layer above the server's own in-process core relaunches. Differences
from training, all mechanical: worker R gets ``--port base+R`` (one
HTTP front per worker — a shared-nothing fleet behind any TCP load
balancer), there is no checkpoint resume to append (the serve args
already carry ``-c``), and no static preflight to run (serving is
collective-free by construction). The per-attempt ``--trace-timeline``
IS armed (serve/cli.py writes per-request span ledgers under the same
rank-suffix convention), merged into one fleet Perfetto timeline with
"worker R" tracks; with ``--metrics-port`` the supervisor additionally
scrapes every worker's ``/metrics`` and re-exposes the families merged and
worker-labeled on its own port — the fleet pane. The
beats come from the dispatch loop — it ticks progress every turn, so
``--progress-timeout`` catches a wedged pipeline (hung device call,
stalled completions) whose beat *thread* is still alive — and serve
workers run until failure or :meth:`ElasticSupervisor.request_stop`
(SIGINT on the CLI), so "every rank exited 0" is a stop, not a result.

Deliberately jax-free: the supervisor process never initializes a
backend (and so never claims a chip its workers need) — all its knowledge of
the job comes from exit codes, beat files, and the checkpoint chain on
disk.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

from distributedpytorch_tpu.dist import health
from distributedpytorch_tpu.obs import defs as obsm
from distributedpytorch_tpu.obs import flight
from distributedpytorch_tpu.utils.backend import DEFAULT_CACHE_DIR, names_cpu
from distributedpytorch_tpu.serve import control

logger = logging.getLogger(__name__)

#: rc a worker may use for "I am aborting because a PEER failed" (see
#: cli.py's per-rank error summary): the supervisor attributes the
#: failure to the primary rank, not to survivors that died of it.
PEER_FAILURE_EXIT = 13

#: Why the supervisor will not start several workers off the CPU. A TPU
#: chip belongs to one process at a time, and a worker launched with no
#: device assignment of its own claims every local chip: two such
#: workers fight over the same chips and one fails or hangs at backend
#: init. On a TPU host the multi-chip path is ONE process over a mesh.
MULTI_WORKER_OFF_CPU = (
    "elastic: refusing to start {n} worker processes off the CPU — each "
    "would claim every local TPU chip, and a chip belongs to one process "
    "at a time. On a TPU host run ONE process over the chips "
    "(`train.py -t DP`, `-t DDP_MP` or a `DxMxS` mesh spec; `serve "
    "--replicas N`). For CPU drills pass --cpu-devices N or set "
    "JAX_PLATFORMS=cpu."
)

#: Supervisor rc when the static preflight (analysis/, docs/ANALYSIS.md)
#: found the job's step program statically broken: nothing was spawned.
STATIC_CHECK_EXIT = 3


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_arg(args: Sequence[str], names: Sequence[str], default: str,
                abbrev: bool = False) -> str:
    """Pull a flag value out of the worker argv (last occurrence wins,
    like argparse). Supports ``--flag value`` and ``--flag=value``;
    ``abbrev`` additionally accepts argparse-style prefix spellings
    (``--pipeline-sched 1f1b``) — the trainer's parser allows them, so
    a supervisor that only matched the full spelling would silently
    read its default instead of the schedule the workers actually run."""
    value = default
    args = list(args)

    def matches(flag: str, name: str) -> bool:
        if flag == name:
            return True
        return (abbrev and flag.startswith("--") and len(flag) >= 4
                and name.startswith(flag))

    for i, a in enumerate(args):
        flag, eq, rest = a.partition("=")
        for n in names:
            if (len(n) == 2 and not n.startswith("--")
                    and a.startswith(n) and a != n):
                # glued short form: argparse reads -tMP as -t with value
                # "MP" — and -t=X as value "=X", the '=' taken verbatim
                value = a[len(n):]
            elif matches(flag, n):
                if eq:
                    value = rest
                elif i + 1 < len(args):
                    value = args[i + 1]
    return value


def _checkpoint_exists(checkpoint_dir: str, tag: str) -> bool:
    """Is there anything resumable on disk? Mirrors
    `checkpoint.retained_checkpoints` without importing the jax/flax
    stack into the supervisor process."""
    base = os.path.join(checkpoint_dir, f"{tag}.ckpt")
    if os.path.exists(base):
        return True
    return any(os.path.exists(f"{base}.{i}") for i in range(1, 64))


class FleetMetricsScraper:
    """The fleet pane's ingest half (docs/SERVING.md "Fleet pane"): a
    daemon thread scraping each serve worker's ``/metrics`` (port
    base+R) and keeping the latest exposition text per worker. The
    supervisor's own metrics endpoint re-exposes these merged and
    worker-labeled (``registry.merge_expositions``), so one scrape
    target tells the whole shared-nothing fleet's story. A worker that
    fails its scrape (dead, relaunching, mid-bind) drops out of the
    pane until it answers again — stale numbers from a dead worker
    would read as a healthy flatline."""

    def __init__(self, host: str, base_port: int, world_fn,
                 interval_s: float = 2.0, timeout_s: float = 2.0,
                 on_sweep=None):
        self.host = host
        self.base_port = int(base_port)
        self.world_fn = world_fn  # () -> current world size
        self.interval_s = float(interval_s)
        self.timeout_s = float(timeout_s)
        # per-sweep subscriber (the router's placement feed): called
        # with the {rank: exposition_text} of each completed sweep
        self.on_sweep = on_sweep
        self._latest: Dict[str, str] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="dpt-fleet-scrape",
        )

    def start(self) -> "FleetMetricsScraper":
        self._thread.start()
        return self

    def _scrape_worker(self, rank: int) -> Optional[str]:
        import urllib.request

        url = f"http://{self.host}:{self.base_port + rank}/metrics"
        try:
            with urllib.request.urlopen(url, timeout=self.timeout_s) as resp:
                return resp.read().decode()
        except Exception:  # noqa: BLE001 — a dead worker is not news
            return None

    def scrape_once(self) -> Dict[str, str]:
        """One sweep over the current fleet (also the unit under test).
        Workers are scraped CONCURRENTLY: serially, every wedged worker
        would add its full timeout to the sweep and the healthy workers'
        numbers would go tens of seconds stale on a large fleet — the
        exact staleness this pane exists to avoid."""
        import concurrent.futures

        world = max(0, int(self.world_fn()))
        if world == 0:
            return {}
        with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(world, 16),
            thread_name_prefix="dpt-fleet-scrape",
        ) as pool:
            texts = list(pool.map(self._scrape_worker, range(world)))
        return {str(r): t for r, t in enumerate(texts) if t is not None}

    def _loop(self) -> None:
        # sweep IMMEDIATELY: the pane must not serve an empty merged
        # exposition for the first interval after startup
        while True:
            seen = self.scrape_once()
            with self._lock:
                self._latest = seen
            if self.on_sweep is not None:
                try:
                    self.on_sweep(seen)
                except Exception:  # noqa: BLE001 — a subscriber must
                    # not kill the pane
                    logger.exception("fleet scrape: on_sweep failed")
            if self._stop.wait(self.interval_s):
                return

    def latest(self) -> Dict[str, str]:
        with self._lock:
            return dict(self._latest)

    def stop(self) -> None:
        self._stop.set()


class FleetScaler:
    """Supervisor-level capacity actuator: spawn/retire WHOLE serve
    workers (the process-level loop plan-serve actually sizes — the
    in-process :class:`serve.scaler.ReplicaScaler` only resizes replica
    groups *inside* one worker). Both actuators share one control law
    (serve/control.py): every decision cites the ``dpt_serve_plan``
    grid point it executes, exactly like the replica scaler's.

    The recommendation signal is the plan itself — the observed fleet
    arrival rate matched to the nearest simulated poisson scenario at
    or above it, that scenario's recommended replica count read as a
    worker count (one worker hosts one planned replica's capacity at
    fleet granularity). Streak hysteresis (``up_windows`` consecutive
    diverging windows to grow, ``down_windows`` to shrink — shrinking
    is the dangerous direction) plus the shared cooldown keep it from
    flapping; one worker moves per actuation.

    Spawn rides the per-rank relaunch machinery: fresh port base+R, an
    attempt-0 heartbeat slot, and the fleet-shared ``$DPT_AOT_CACHE`` —
    the newcomer cold-starts warm off the executables its siblings
    already compiled (``recompiles: 0``). Retire drains via the
    router(s): eject from every front door, wait out in-flight, THEN
    SIGTERM (serve/cli.py drains on it)."""

    def __init__(self, supervisor: "ElasticSupervisor", plan=None,
                 min_workers: int = 1, max_workers: Optional[int] = None,
                 up_windows: int = 2, down_windows: int = 4,
                 cooldown_windows: Optional[int] = None):
        from distributedpytorch_tpu.serve.control import (  # jax-free
            plan_recommendation,
        )

        self._recommend = plan_recommendation
        if isinstance(plan, str):
            from distributedpytorch_tpu.analysis.serve_planner import (
                load_serve_plan,  # jax-free: profile + sim only
            )

            plan = load_serve_plan(plan)
        self.supervisor = supervisor
        self.plan = plan
        self.min_workers = max(1, int(min_workers))
        self.max_workers = int(
            max_workers if max_workers is not None
            else max(supervisor.nprocs, self.min_workers)
        )
        self.up_windows = max(1, int(up_windows))
        self.down_windows = max(1, int(down_windows))
        self.cooldown_windows = int(
            cooldown_windows if cooldown_windows is not None
            else max(self.up_windows, self.down_windows)
        )
        # start past cooldown: the FIRST sustained divergence may act
        self.windows_since_action = self.cooldown_windows
        self._up_streak = 0
        self._down_streak = 0
        self.decisions: List[dict] = []
        self.spawns = 0
        self.retires = 0
        # arrival-rate observation (thread mode): router request deltas
        self._last_requests: Optional[int] = None
        self._last_t: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def step(self, observed_rate_rps: Optional[float] = None):
        """One control window: age the cooldown, read the plan's
        recommendation for the observed rate, decide through the shared
        law, actuate at most one worker."""
        from distributedpytorch_tpu.serve import control

        self.windows_since_action += 1
        current = len(self.supervisor.active_serve_ranks())
        recommendation = self._recommend(self.plan, observed_rate_rps)
        hold_reason = None
        if recommendation is not None:
            if recommendation > current:
                self._up_streak += 1
                self._down_streak = 0
                if self._up_streak < self.up_windows:
                    hold_reason = (
                        f"up streak {self._up_streak}/{self.up_windows}")
            elif recommendation < current:
                self._down_streak += 1
                self._up_streak = 0
                if self._down_streak < self.down_windows:
                    hold_reason = (f"down streak {self._down_streak}/"
                                   f"{self.down_windows}")
            else:
                self._up_streak = self._down_streak = 0
        decision = control.decide_scale(
            current, recommendation,
            min_units=self.min_workers, max_units=self.max_workers,
            windows_since_action=self.windows_since_action,
            cooldown_windows=self.cooldown_windows,
            hold_reason=hold_reason,
            rate_rps=observed_rate_rps, plan=self.plan,
        )
        return self.apply(decision)

    def apply(self, decision):
        """Actuate a non-hold decision: one worker per window, through
        the supervisor's spawn/retire machinery. Stamps the ledger /
        flight / metric trail either way."""
        import dataclasses as _dc

        from distributedpytorch_tpu.serve import control

        achieved = decision.current
        if decision.direction != control.DIR_HOLD:
            if decision.direction == control.DIR_UP:
                rank = self.supervisor.spawn_fleet_worker()
                if rank is not None:
                    achieved = decision.current + 1
                    self.spawns += 1
            else:
                rank = self.supervisor.retire_fleet_worker()
                if rank is not None:
                    achieved = decision.current - 1
                    self.retires += 1
            if achieved != decision.current:
                self.windows_since_action = 0
                self._up_streak = self._down_streak = 0
                obsm.FLEET_SCALE_EVENTS.labels(
                    direction=decision.direction).inc()
                logger.info(
                    "fleet scaler: %s %d -> %d (%s) plan_point=%s",
                    decision.direction, decision.current, achieved,
                    decision.reason, decision.plan_point,
                )
            entry = {**decision.payload(), "achieved": achieved}
            self.decisions.append(entry)
            del self.decisions[:-50]
            flight.record("fleet_scale", **{
                k: v for k, v in entry.items() if v is not None})
        return _dc.replace(decision, target=achieved)

    # -- background thread (elastic --fleet-interval) ------------------------
    def _observed_rate(self) -> Optional[float]:
        router = self.supervisor.router
        if router is None:
            return None
        now = time.monotonic()
        total = router.requests_ok + router.requests_failed
        rate = None
        if self._last_requests is not None and now > self._last_t:
            rate = (total - self._last_requests) / (now - self._last_t)
        self._last_requests, self._last_t = total, now
        return rate

    def _run(self, interval_s: float) -> None:
        while not self._stop.wait(interval_s):
            try:
                self.step(observed_rate_rps=self._observed_rate())
            except Exception:  # noqa: BLE001 — the control loop must
                # outlive one bad window
                logger.exception("fleet scaler: step failed")

    def start(self, interval_s: float) -> "FleetScaler":
        self._thread = threading.Thread(
            target=self._run, args=(float(interval_s),),
            name="dpt-fleet-scaler", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)

    def status(self) -> dict:
        return {
            "workers": len(self.supervisor.active_serve_ranks()),
            "min_workers": self.min_workers,
            "max_workers": self.max_workers,
            "cooldown_windows": self.cooldown_windows,
            "windows_since_action": self.windows_since_action,
            "spawns": self.spawns,
            "retires": self.retires,
            "plan": bool(self.plan),
            "decisions": self.decisions[-10:],
        }


@dataclasses.dataclass
class AttemptResult:
    """What one launch attempt came to (recorded in the report JSON)."""

    attempt: int
    world: int
    ok: bool
    failures: List[str]  # the one-line per-rank summaries
    exit_codes: Dict[int, Optional[int]]
    duration_s: float


class ElasticSupervisor:
    """Supervise one elastic job (see module docstring).

    ``worker_cmd`` is the base command (default: this package's CLI);
    ``worker_args`` is appended to it. The supervisor appends per-rank
    env (RANK / WORLD_SIZE / MASTER_ADDR / MASTER_PORT), the heartbeat
    flags, ``--chaos`` specs (attempt 0 only), and ``-c <tag>`` once a
    checkpoint exists."""

    def __init__(
        self,
        worker_args: Sequence[str],
        nprocs: int,
        worker_cmd: Optional[Sequence[str]] = None,
        min_ranks: int = 1,
        max_restarts: int = 3,
        heartbeat_timeout_s: float = 10.0,
        heartbeat_interval_s: float = 0.5,
        progress_timeout_s: float = 0.0,
        spawn_timeout_s: float = 300.0,
        poll_interval_s: float = 0.25,
        restart_backoff_s: float = 1.0,
        teardown_grace_s: float = 10.0,
        rank_fail_limit: int = 2,
        run_dir: str = "./elastic_run",
        report_path: Optional[str] = None,
        cpu_devices: int = 0,
        chaos: Sequence[str] = (),
        env: Optional[Dict[str, str]] = None,
        cwd: Optional[str] = None,
        preflight: bool = True,
        preflight_timeout_s: float = 300.0,
        trace: bool = True,
        metrics_port: Optional[int] = None,
        workload: str = "train",
        router_port: Optional[int] = None,
        router_standby_port: Optional[int] = None,
        fleet_plan=None,
        fleet_min_workers: int = 1,
        fleet_max_workers: Optional[int] = None,
        fleet_interval_s: float = 0.0,
    ):
        if nprocs < 1:
            raise ValueError(f"nprocs must be >= 1, got {nprocs}")
        if not 1 <= min_ranks <= nprocs:
            raise ValueError(
                f"min_ranks must be in [1, {nprocs}], got {min_ranks}"
            )
        if workload not in ("train", "serve"):
            raise ValueError(
                f"workload must be 'train' or 'serve', got {workload!r}"
            )
        self.workload = workload
        self.worker_args = list(worker_args)
        default_cmd = [sys.executable, "-u", "-m", "distributedpytorch_tpu"]
        if workload == "serve":
            default_cmd.append("serve")
        self.worker_cmd = list(
            worker_cmd if worker_cmd is not None else default_cmd
        )
        self.nprocs = int(nprocs)
        self.min_ranks = int(min_ranks)
        self.max_restarts = int(max_restarts)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.heartbeat_interval_s = float(heartbeat_interval_s)
        self.progress_timeout_s = float(progress_timeout_s)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.poll_interval_s = float(poll_interval_s)
        self.restart_backoff_s = float(restart_backoff_s)
        self.teardown_grace_s = float(teardown_grace_s)
        self.rank_fail_limit = max(1, int(rank_fail_limit))
        # absolute: the workers receive this path in their argv and may
        # run under a different cwd than the supervisor
        self.run_dir = os.path.abspath(str(run_dir))
        self.report_path = report_path or os.path.join(
            self.run_dir, "report.json"
        )
        self.cpu_devices = int(cpu_devices)
        self.chaos = tuple(chaos)
        self.base_env = dict(env) if env is not None else None
        widest = max(self.nprocs, int(fleet_max_workers or 0))
        if widest > 1 and not self._workers_on_cpu():
            raise ValueError(MULTI_WORKER_OFF_CPU.format(n=widest))
        self.cwd = cwd  # workers' cwd (their relative artifact dirs)
        self.preflight = bool(preflight)
        self.preflight_timeout_s = float(preflight_timeout_s)
        self.preflight_findings: List[str] = []
        # telemetry (docs/OBSERVABILITY.md): per-rank step timelines are
        # armed by default — every elastic run is a diagnostic context,
        # and a dead attempt's merged Perfetto trace is its post-mortem
        self.trace = bool(trace)
        self.metrics_port = metrics_port
        self.merged_timeline: Optional[str] = None
        # fleet pane (serve workload + --metrics-port): the per-worker
        # /metrics scraper feeding the supervisor's merged exposition
        self.fleet_scraper: Optional[FleetMetricsScraper] = None
        # front door (serve workload + --router-port): ONE address
        # proxying /predict across the workers with load-aware
        # placement, transparent retry of 503s/dead workers, and
        # /admin/ab fan-out (serve/router.py — jax-free, runs in this
        # process). None = clients talk to worker ports directly.
        self.router_port = router_port
        self.router = None
        # HA pair (--router-standby-port): a SECOND router instance —
        # both proxy /predict at all times; the standby pulls the
        # active's /admin/state snapshot every probe interval and takes
        # over on the first missed probe (serve/router.py "HA"). The
        # client contract is two addresses, no VIP (docs/SERVING.md).
        self.router_standby_port = router_standby_port
        self.standby_router = None
        # fleet-level elasticity (FleetScaler): spawn/retire whole
        # serve workers off the plan-serve recommendation
        self.fleet_plan = fleet_plan
        self.fleet_min_workers = int(fleet_min_workers)
        self.fleet_max_workers = fleet_max_workers
        self.fleet_interval_s = float(fleet_interval_s)
        self.fleet_scaler: Optional[FleetScaler] = None
        self._retired_ranks: set = set()
        self._grace_until: Dict[int, float] = {}

        # resume coordinates, parsed from the worker argv (the trainer's
        # epoch checkpoints land at <checkpoint_dir>/<train_method>.ckpt).
        # A serve fleet has no resume: workers reload their -c checkpoint
        # themselves, and the tag only labels the report.
        self.method_tag = (
            "serve" if self.workload == "serve" else _worker_arg(
                self.worker_args, ("-t", "--train-method"), "singleGPU",
                abbrev=True,
            )
        )
        # serve worker R binds base+R: one HTTP front per process — a
        # shared-nothing fleet any TCP load balancer can sit in front of
        self.base_port = int(_worker_arg(
            self.worker_args, ("--port",), "8008"
        )) if self.workload == "serve" else None
        # exact-only on purpose: the trainer has a DISTINCT exact flag
        # --checkpoint (load a .pth), which argparse resolves to itself
        # but a prefix match would misread as --checkpoint-dir and break
        # resume (relaunch would probe <cwd>/model.pth for checkpoints)
        ckpt_dir = _worker_arg(
            self.worker_args, ("--checkpoint-dir",), "./checkpoints"
        )
        if not os.path.isabs(ckpt_dir):
            # a relative checkpoint dir is resolved by the WORKERS
            # against their cwd; the resume check here must look in the
            # same place or every relaunch silently restarts from
            # scratch (the supervisor's own cwd may differ)
            ckpt_dir = os.path.join(self.cwd or os.getcwd(), ckpt_dir)
        self.checkpoint_dir = ckpt_dir

        self._shutdown = threading.Event()
        self.restarts = 0
        self.world_history: List[int] = []
        self.attempts: List[AttemptResult] = []
        self._procs: List[subprocess.Popen] = []

    # ------------------------------------------------------------------
    def _workers_on_cpu(self) -> bool:
        """Whether every worker's jax is held to the CPU: the supervisor
        provisions virtual CPU devices (``cpu_devices``), or the
        operator's environment names the CPU itself."""
        if self.cpu_devices > 0:
            return True
        env = os.environ if self.base_env is None else self.base_env
        return names_cpu(env.get("JAX_PLATFORMS", ""))

    def _worker_env(self, rank: int, world: int, port: int,
                    attempt: int = 0) -> Dict[str, str]:
        if self.cpu_devices > 0:
            # CPU-mesh drills/tests: ONE definition of the virtual-device
            # provisioning moves (utils/provision.py — jax-free module)
            from distributedpytorch_tpu.utils.provision import provisioned_env

            env = provisioned_env(self.cpu_devices, base=self.base_env)
        else:
            env = dict(os.environ if self.base_env is None else self.base_env)
        env.update(
            {
                "RANK": str(rank),
                "LOCAL_RANK": str(rank),
                "WORLD_SIZE": str(world),
                "MASTER_ADDR": "127.0.0.1",
                "MASTER_PORT": str(port),
            }
        )
        # a worker stuck joining a rendezvous whose peers died must fail
        # fast (dist/runtime._init_timeout_kwargs) — the supervisor, not
        # jax's 300 s default, owns the retry loop
        env.setdefault(
            "DPT_DIST_INIT_TIMEOUT_S",
            str(int(max(30.0, self.spawn_timeout_s))),
        )
        # worker flight-recorder dumps (obs/flight.py) land with the
        # attempt's other artifacts (rank logs, beats, timelines)
        env.setdefault(
            "DPT_FLIGHT_DIR",
            os.path.join(self.run_dir, f"attempt{attempt}"),
        )
        # shared AOT executable store (utils/aotstore.py) for serve
        # fleets: ONE dir across ranks AND attempts — a relaunch loads
        # the executables attempt 0 compiled instead of re-paying the
        # whole ladder. Safe shared (unlike the per-rank XLA cache
        # below): entries are integrity-footed and atomically renamed,
        # and racing ranks write identical bytes under identical keys.
        # An operator's own $DPT_AOT_CACHE (or base_env) wins.
        if self.workload == "serve":
            env.setdefault(
                "DPT_AOT_CACHE", os.path.join(self.run_dir, "aot_cache")
            )
        # per-rank persistent XLA compilation caches: co-launched ranks
        # compiling identical entries race one directory (jax writes a
        # cache entry in place, not by rename). The operator's
        # $JAX_COMPILATION_CACHE_DIR — or, unset, the in-checkout default
        # every entry point uses — is kept, and each rank gets a FIXED
        # sub-directory of it: the path is part of the cache key, so it
        # is never built from a pid, a time or a temp name. (Serve
        # workers that persist to the AOT store bypass this cache at
        # their one compile site, serve/engine._compile_bucket.)
        base = env.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(base, f"rank{rank}")
        return env

    def _worker_argv(self, attempt: int, rank: int = 0,
                     hb_attempt: Optional[int] = None) -> List[str]:
        # hb_attempt pins the heartbeat/timeline directory independently
        # of the flag-selecting attempt index: a serve worker relaunched
        # IN PLACE (attempt > 0 flags, so chaos specs are not re-armed)
        # must keep beating where its surviving siblings still beat
        hb = attempt if hb_attempt is None else hb_attempt
        argv = self.worker_cmd + self.worker_args
        argv += [
            "--heartbeat-dir", self._hb_dir(hb),
            "--heartbeat-interval", str(self.heartbeat_interval_s),
        ]
        if self.trace:
            # one base path per attempt; rank 0 writes it, rank R writes
            # <path>.rankR (train/loop.py for training; serve/cli.py
            # writes per-request span ledgers under the same convention)
            # — merged after the run by the trace hub into one
            # rank/worker-disambiguated Perfetto timeline
            argv += ["--trace-timeline", self._timeline_base(hb)]
        if attempt == 0:
            for spec in self.chaos:
                argv += ["--inject-fault", spec]
        if self.workload == "serve":
            # appended LAST (last occurrence wins): worker R's HTTP
            # front on base+R regardless of a user-passed --port
            argv += ["--port", str(self.base_port + rank)]
            return argv
        # resume from the newest intact retained checkpoint once one
        # exists. Appended LAST so it wins over any user-passed -c
        # (argparse last-occurrence semantics) — a restart must resume
        # THIS job, not reload the user's warm-start weights again.
        if attempt > 0 and _checkpoint_exists(self.checkpoint_dir, self.method_tag):
            argv += ["-c", self.method_tag]
        return argv

    def _hb_dir(self, attempt: int) -> str:
        # fresh beat dir per attempt: stale beats from a torn-down world
        # must never be classified against the relaunched one
        return os.path.join(self.run_dir, f"attempt{attempt}", "heartbeat")

    def _timeline_base(self, attempt: int) -> str:
        return os.path.join(self.run_dir, f"attempt{attempt}",
                            "timeline.jsonl")

    def _merge_timelines(self) -> Optional[str]:
        """Merge every attempt's per-rank timeline JSONL into ONE
        Perfetto trace for the whole supervised job (rank-disambiguated
        tracks; docs/OBSERVABILITY.md). A serve fleet's per-request
        span ledgers merge the same way — its process tracks read
        "worker R" and the result is the fleet timeline (one pane for N
        shared-nothing workers). Never raises — this runs on the report
        path of jobs that may already be failing."""
        if not self.trace:
            return None
        from distributedpytorch_tpu.obs import trace_hub

        pairs: List = []
        for attempt in range(len(self.world_history)):
            pairs.extend(trace_hub.timeline_rank_paths(
                self._timeline_base(attempt)
            ))
        out = os.path.join(self.run_dir, "timeline_merged.json")
        self.merged_timeline = trace_hub.write_merged_trace(
            pairs, out,
            process_label="worker" if self.workload == "serve" else "rank",
        )
        return self.merged_timeline

    def _log_path(self, attempt: int, rank: int) -> str:
        return os.path.join(
            self.run_dir, f"attempt{attempt}", f"rank{rank}.log"
        )

    # ------------------------------------------------------------------
    def _spawn(self, attempt: int, world: int) -> None:
        port = _free_port()
        os.makedirs(self._hb_dir(attempt), exist_ok=True)
        logger.info(
            "elastic attempt %d: launching %d rank(s): %s",
            attempt, world, shlex.join(self._worker_argv(attempt, 0)),
        )
        self._procs = []
        self._log_files = []
        try:
            for rank in range(world):
                log_f = open(self._log_path(attempt, rank), "ab")
                self._log_files.append(log_f)
                self._procs.append(
                    subprocess.Popen(
                        # per-rank argv: identical for training; serve
                        # workers differ by their --port assignment
                        self._worker_argv(attempt, rank),
                        env=self._worker_env(rank, world, port, attempt),
                        cwd=self.cwd,
                        stdout=log_f,
                        stderr=subprocess.STDOUT,
                    )
                )
        except Exception:
            # a spawn failure on rank k (fd exhaustion, ENOMEM) must not
            # orphan ranks 0..k-1: they hold the rendezvous port and
            # would keep mutating checkpoints with no supervisor
            self._teardown()
            raise

    def _exit_codes(self) -> Dict[int, Optional[int]]:
        return {r: p.poll() for r, p in enumerate(self._procs)}

    def _classify(self, attempt: int, world: int, started_at: float):
        return health.classify(
            world,
            health.read_beats(self._hb_dir(attempt)),
            self._exit_codes(),
            timeout_s=self.heartbeat_timeout_s,
            started_at=started_at,
            spawn_timeout_s=self.spawn_timeout_s,
            progress_timeout_s=self.progress_timeout_s,
        )

    def _teardown(self) -> None:
        """Stop every surviving rank: SIGTERM (the trainer checkpoints
        and exits at the next agreed boundary when it can), grace,
        SIGKILL stragglers (a survivor blocked inside a collective its
        dead peer abandoned cannot run its handler)."""
        for p in self._procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.monotonic() + self.teardown_grace_s
        while time.monotonic() < deadline and any(
            p.poll() is None for p in self._procs
        ):
            time.sleep(0.1)
        for p in self._procs:
            if p.poll() is None:
                p.kill()
        for p in self._procs:
            p.wait()
        for f in getattr(self, "_log_files", []):
            try:
                f.close()
            except OSError:
                pass

    def _relaunch_rank(self, rank: int, attempt: int) -> None:
        """Replace ONE failed serve worker in place — the collective-free
        fleet's siblings keep serving the whole time. Heartbeats and
        timelines stay pinned to the attempt-0 directories (the
        survivors are still writing there); ``attempt`` only selects
        argv flags, so chaos specs are never re-armed on a relaunch."""
        old = self._procs[rank]
        if old.poll() is None:  # hung, not dead: stop it first
            try:
                old.send_signal(signal.SIGTERM)
            except OSError:
                pass
            deadline = time.monotonic() + self.teardown_grace_s
            while time.monotonic() < deadline and old.poll() is None:
                time.sleep(0.05)
            if old.poll() is None:
                old.kill()
        old.wait()
        log_f = open(self._log_path(0, rank), "ab")
        self._log_files.append(log_f)
        try:
            self._procs[rank] = subprocess.Popen(
                self._worker_argv(attempt, rank, hb_attempt=0),
                env=self._worker_env(rank, len(self._procs),
                                     _free_port(), 0),
                cwd=self.cwd,
                stdout=log_f,
                stderr=subprocess.STDOUT,
            )
        except Exception:
            self._teardown()
            raise

    # -- fleet elasticity (serve workload; FleetScaler's actuation) ----------
    def _worker_host(self) -> str:
        return _worker_arg(self.worker_args, ("--host",), "127.0.0.1")

    def _routers(self):
        return [r for r in (self.router, self.standby_router)
                if r is not None]

    def active_serve_ranks(self) -> List[int]:
        """Rank slots currently meant to be serving (spawned and not
        deliberately retired)."""
        return [r for r in range(len(self._procs))
                if r not in self._retired_ranks]

    def spawn_fleet_worker(self) -> Optional[int]:
        """Grow the fleet by ONE worker: reuse the lowest retired rank
        slot (its port base+R and heartbeat slot come back with it) or
        append a fresh rank. Rides the same machinery as a per-rank
        relaunch — attempt-0 beat/timeline dirs, the fleet-shared
        ``$DPT_AOT_CACHE`` (the newcomer loads the executables its
        siblings compiled: ``recompiles: 0``) — then waits for
        ``/healthz`` ready and admits the worker to every router.
        Returns the rank, or None if the spawn failed."""
        # the rank choice is the pure rule the protocol explorer
        # model-checks (serve/control.fleet_spawn_rank): lowest retired
        # slot reused, else a fresh appended rank
        rank = control.fleet_spawn_rank(
            self.active_serve_ranks(), frozenset(self._retired_ranks)
        )
        logger.info("elastic fleet: spawning worker %d (port %d)",
                    rank, self.base_port + rank)
        log_f = open(self._log_path(0, rank), "ab")
        self._log_files.append(log_f)
        world = max(len(self._procs), rank + 1)
        try:
            proc = subprocess.Popen(
                # attempt index 1: chaos specs are armed on attempt 0
                # argv only — a spawned newcomer must not re-fire them
                self._worker_argv(1, rank, hb_attempt=0),
                env=self._worker_env(rank, world, _free_port(), 0),
                cwd=self.cwd,
                stdout=log_f,
                stderr=subprocess.STDOUT,
            )
        except Exception:  # noqa: BLE001 — a failed grow must not kill
            # the fleet that exists
            logger.exception("elastic fleet: spawn of worker %d failed",
                             rank)
            return None
        if rank < len(self._procs):
            self._procs[rank] = proc
        else:
            self._procs.append(proc)
        self._retired_ranks.discard(rank)
        self._grace_until[rank] = time.time() + max(
            self.spawn_timeout_s, self.heartbeat_timeout_s
        )
        host = self._worker_host()
        if self._wait_worker_ready(rank):
            for router in self._routers():
                router.ensure_worker(host, self.base_port + rank)
        else:
            # admit unhealthy: the routers' own probes readmit the
            # moment /healthz answers (slow model load, not a failure)
            for router in self._routers():
                router.ensure_worker(host, self.base_port + rank,
                                     healthy=False)
        obsm.ELASTIC_WORLD_SIZE.set(len(self.active_serve_ranks()))
        return rank

    def _wait_worker_ready(self, rank: int,
                           timeout_s: Optional[float] = None) -> bool:
        import urllib.request

        url = (f"http://{self._worker_host()}:{self.base_port + rank}"
               "/healthz")
        deadline = time.monotonic() + (
            timeout_s if timeout_s is not None else self.spawn_timeout_s
        )
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(url, timeout=2.0) as resp:
                    if resp.status == 200:
                        return True
            except Exception:  # noqa: BLE001 — still booting
                pass
            if self._shutdown.wait(0.1):
                return False
        return False

    def retire_fleet_worker(self) -> Optional[int]:
        """Shrink the fleet by ONE worker: the highest active rank.
        Order matters — eject from every router FIRST (no new
        placements), wait out router-tracked in-flight requests, THEN
        SIGTERM (serve/cli.py drains its own queue on it), grace,
        SIGKILL stragglers. Returns the rank, or None if there is
        nothing retireable."""
        # rank choice + the never-below-one refusal are the pure rule
        # the protocol explorer model-checks (control.fleet_retire_rank);
        # the actuation below follows control.FLEET_RETIRE_ORDER —
        # routers stop placing BEFORE the process dies
        rank = control.fleet_retire_rank(self.active_serve_ranks())
        if rank is None:
            return None
        address = f"{self._worker_host()}:{self.base_port + rank}"
        logger.info("elastic fleet: retiring worker %d (%s)",
                    rank, address)
        for router in self._routers():
            router.retire_worker(
                address, drain_timeout_s=self.teardown_grace_s)
        self._retired_ranks.add(rank)
        proc = self._procs[rank]
        if proc.poll() is None:
            try:
                proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
            deadline = time.monotonic() + self.teardown_grace_s
            while time.monotonic() < deadline and proc.poll() is None:
                time.sleep(0.05)
            if proc.poll() is None:
                proc.kill()
        proc.wait()
        obsm.ELASTIC_WORLD_SIZE.set(len(self.active_serve_ranks()))
        return rank

    def request_stop(self) -> None:
        """Ask a running supervision loop to stop cleanly: tear down the
        workers and return 0 with ``final: stopped``. The serve
        workload's exit path (serve fleets run until told otherwise —
        SIGINT on the CLI, a test's teardown); also honored mid-watch by
        training jobs."""
        self._shutdown.set()

    def _watch(self, attempt: int, world: int) -> Dict[int, health.RankHealth]:
        """Block until the attempt resolves: every rank exits 0 (all-ok
        map) or some rank fails (classified map) — or a clean stop is
        requested (the caller checks ``_shutdown``). Never raises on
        worker behavior — classification is the contract."""
        started_at = time.time()
        while True:
            if self._shutdown.is_set():
                return {r: health.RankHealth(r, "ok") for r in range(world)}
            codes = self._exit_codes()
            if all(rc == 0 for rc in codes.values()):
                # still consult the beats: a desynced world tears itself
                # down CLEANLY (every rank marks its beat, snapshots,
                # and exits 0 via the agreed stop) — all-zero exit codes
                # alone would report that truncated job as success
                verdicts = self._classify(attempt, world, started_at)
                if any(h.failed for h in verdicts.values()):
                    return verdicts
                return {
                    r: health.RankHealth(r, "ok") for r in range(world)
                }
            verdicts = self._classify(attempt, world, started_at)
            # a PEER_FAILURE_EXIT rank is a casualty, not a cause; only
            # treat it as the failure if NO primary failure exists
            primary = {
                r: h for r, h in verdicts.items()
                if h.failed and codes.get(r) != PEER_FAILURE_EXIT
            }
            if primary or any(h.failed for h in verdicts.values()):
                # give one extra beat-interval for a primary failure to
                # surface before blaming a secondary exit
                if not primary:
                    time.sleep(self.heartbeat_interval_s)
                    verdicts = self._classify(attempt, world, started_at)
                return verdicts
            time.sleep(self.poll_interval_s)

    # ------------------------------------------------------------------
    def static_preflight(self) -> List[str]:
        """Run the static distributed-correctness analyzer over this
        job's strategy × schedule BEFORE spawning any rank: a step whose
        collective program is statically broken (deadlocked ppermute
        schedule, rank-divergent collective, dropped gradient reduction)
        would otherwise spawn N ranks that hang until the heartbeat
        window expires, burn the whole restart budget relaunching into
        the same hang, and exit having attributed the failure to
        "hung" ranks instead of the program.

        Returns the findings lines (empty = clean). Scoped to the
        COLLECTIVE layer for this job's strategy × schedule: a source
        lint nit anywhere in the package is CI's gate, not a reason to
        refuse an otherwise-sound launch. Stays jax-free: the analyzer
        runs via the shared runner (analysis/preflight.py — ``python -m
        distributedpytorch_tpu analyze`` in a provisioned CPU
        subprocess), so the supervisor never initializes a backend or
        dials a TPU runtime. Analyzer infrastructure failures (rc !=
        0/1, timeout) return [] — availability first: the supervisor
        must never refuse a launch because the analyzer itself broke.

        Strategies the analyzer doesn't cover (``singleGPU``, the
        multi-process-only ``DDP``) skip the check entirely: nothing to
        verify statically, so don't pay a provisioned analyzer
        subprocess on every launch of a non-collective job."""
        from distributedpytorch_tpu.analysis import ANALYSIS_STRATEGIES
        from distributedpytorch_tpu.analysis.preflight import run_preflight

        if self.workload == "serve":
            # serving is collective-free by construction (independent
            # single-device replica executables): nothing to verify
            # statically, nothing to pay for
            return []
        from distributedpytorch_tpu.parallel.mesh import is_mesh_spec

        if (
            self.method_tag not in ANALYSIS_STRATEGIES
            and not is_mesh_spec(self.method_tag)
        ):
            return []
        schedule = _worker_arg(
            self.worker_args, ("--pipeline-schedule",), "gpipe",
            abbrev=True,
        )
        rc, findings = run_preflight(
            [self.method_tag], [schedule], self.preflight_timeout_s,
            layer="collectives", base_env=self.base_env, cwd=self.cwd,
            # compare each combo's ordered-collective fingerprint under
            # THIS job's world size: a collective gated on a rank >= 2
            # passes the dual-rank re-trace but would desync an N-rank
            # gloo rendezvous — catch it before the first spawn
            fingerprint_world=self.nprocs,
        )
        if rc == 1:
            return findings
        if rc != 0:
            logger.warning(
                "elastic: static preflight could not run (rc=%d) — "
                "proceeding with the launch: %.300s",
                rc, "; ".join(findings),
            )
        return []

    # ------------------------------------------------------------------
    def _write_report(self, final: Optional[str] = None) -> None:
        os.makedirs(
            os.path.dirname(os.path.abspath(self.report_path)), exist_ok=True
        )
        payload = {
            "restarts": self.restarts,
            "world_history": self.world_history,
            "final": final,
            "attempts": [dataclasses.asdict(a) for a in self.attempts],
        }
        if self.preflight_findings:
            payload["preflight_findings"] = list(self.preflight_findings)
        if self.merged_timeline:
            payload["merged_timeline"] = self.merged_timeline
        tmp = f"{self.report_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2)
        os.replace(tmp, self.report_path)

    def run(self) -> int:
        """Supervise to completion. Returns 0 when an attempt finishes
        with every rank at exit 0; 1 when the restart budget is
        exhausted (the report JSON holds the full per-attempt record
        either way); STATIC_CHECK_EXIT (3) when the static preflight
        refused the launch — no rank was spawned and no budget spent."""
        if self.preflight:
            self.preflight_findings = self.static_preflight()
            if self.preflight_findings:
                for line in self.preflight_findings:
                    logger.error("elastic: static preflight: %s", line)
                logger.error(
                    "elastic: refusing to spawn %d rank(s): the step "
                    "fails static distributed-correctness checks (see "
                    "docs/ANALYSIS.md; --no-preflight overrides)",
                    self.nprocs,
                )
                self._write_report(final="static_check_failed")
                return STATIC_CHECK_EXIT
        metrics_server = None
        fleet_scraper = None
        router_httpd = None
        standby_httpd = None
        if self.workload == "serve" and self.router_port is not None:
            # the front door: one address, load-aware placement over
            # worker ports base+R, transparent retry of sheds and
            # SIGKILLed workers (a relaunching worker is a retried
            # sibling, not a client-visible failure). With
            # --router-standby-port, TWO instances run as an
            # active/standby HA pair: both proxy, the standby pulls the
            # active's /admin/state snapshot each probe interval and
            # takes over on the first missed probe — the front door's
            # own death is a client retry to the second address, never
            # an outage.
            from distributedpytorch_tpu.serve.router import (
                Router,
                make_router_http,
            )

            host = self._worker_host()
            workers = [(host, self.base_port + r)
                       for r in range(self.nprocs)]
            peer = ((host, self.router_standby_port)
                    if self.router_standby_port is not None else None)
            self.router = Router(workers, role="active",
                                 peer=peer).start()
            router_httpd = make_router_http(
                self.router, host=host, port=self.router_port,
            )
            threading.Thread(
                target=router_httpd.serve_forever, daemon=True,
                name="dpt-router-http",
            ).start()
            if self.router_standby_port is not None:
                self.standby_router = Router(
                    workers, role="standby",
                    peer=(host, self.router_port),
                ).start()
                standby_httpd = make_router_http(
                    self.standby_router, host=host,
                    port=self.router_standby_port,
                )
                threading.Thread(
                    target=standby_httpd.serve_forever, daemon=True,
                    name="dpt-router-standby-http",
                ).start()
            logger.info(
                "elastic: router front door on http://%s:%d%s over %d "
                "worker(s) — POST /predict, POST /admin/ab, GET /stats",
                host, router_httpd.server_address[1],
                (f" (+ standby on :{self.router_standby_port})"
                 if standby_httpd is not None else ""),
                self.nprocs,
            )
        if self.workload == "serve" and (
                self.fleet_plan is not None
                or self.fleet_max_workers is not None):
            self.fleet_scaler = FleetScaler(
                self, plan=self.fleet_plan,
                min_workers=self.fleet_min_workers,
                max_workers=self.fleet_max_workers,
            )
            if self.fleet_interval_s > 0:
                self.fleet_scaler.start(self.fleet_interval_s)
        if self.metrics_port is not None:
            from distributedpytorch_tpu.obs.http import start_metrics_server

            expose_fn = None
            if self.workload == "serve" and self.base_port is not None:
                # the fleet pane: scrape every worker's /metrics and
                # re-expose the families merged + worker-labeled on the
                # supervisor's own port — one scrape target for N
                # shared-nothing workers (docs/SERVING.md)
                from distributedpytorch_tpu.obs.registry import (
                    REGISTRY,
                    merge_expositions,
                )

                host = _worker_arg(self.worker_args, ("--host",),
                                   "127.0.0.1")
                def _fan_sweep(seen):
                    # BOTH routers place off the same per-worker
                    # numbers: the standby's placement state is
                    # reconstructed from this sweep, not from the
                    # active — part of why failover is stateless
                    for router in self._routers():
                        router.ingest_fleet_metrics(seen)

                fleet_scraper = FleetMetricsScraper(
                    host, self.base_port,
                    # dynamic: the fleet scaler may have grown the
                    # world past nprocs (retired ranks scrape as dead
                    # and drop out of the pane, which is correct)
                    lambda: (len(self._procs) if self._procs
                             else self.nprocs),
                    # the router places off the SAME per-worker numbers
                    # this pane collects: each sweep feeds it queue
                    # depths (and marks non-answering workers stale)
                    on_sweep=(_fan_sweep if self._routers() else None),
                ).start()
                self.fleet_scraper = fleet_scraper

                def expose_fn():
                    return merge_expositions(
                        REGISTRY.expose(), fleet_scraper.latest(),
                    )

            metrics_server = start_metrics_server(
                self.metrics_port, expose_text_fn=expose_fn,
            )
            logger.info("elastic: serving /metrics on port %d%s",
                        metrics_server.port,
                        " (fleet pane: merged worker-labeled families)"
                        if fleet_scraper is not None else "")
        try:
            if self.workload == "serve":
                return self._run_supervised_serve()
            return self._run_supervised()
        except KeyboardInterrupt:
            # the serve workload's normal exit (fleets run until told
            # otherwise); for training it is the operator's call either
            # way — tear down and record a clean stop, not a failure
            logger.info("elastic: interrupted — stopping the fleet")
            self.request_stop()
            self._teardown()
            self._write_report(final="stopped")
            return 0
        finally:
            if self.fleet_scaler is not None:
                self.fleet_scaler.stop()
            if fleet_scraper is not None:
                fleet_scraper.stop()
            if router_httpd is not None:
                router_httpd.shutdown()
            if standby_httpd is not None:
                standby_httpd.shutdown()
            if self.router is not None:
                self.router.stop()
            if self.standby_router is not None:
                self.standby_router.stop()
            if metrics_server is not None:
                metrics_server.close()

    def _run_supervised_serve(self) -> int:
        """Supervision for the collective-free serve fleet: a failed
        worker is relaunched ALONE, in place, while its siblings keep
        serving — behind the router front door the relaunch gap is a
        retried sibling, never a fleet-wide outage. Training keeps the
        whole-world restart (``_run_supervised``): a torn collective
        cannot be healed per rank. The restart budget counts relaunch
        WAVES (one wave may replace several workers), and the attempt
        ledger records one failed entry per wave so reports read the
        same as training's. The world only changes DELIBERATELY here —
        through the fleet scaler's spawn/retire (a retired rank's death
        is the plan, not a failure); unplanned deaths are relaunches."""
        world = self.nprocs
        attempt = 0
        self.world_history.append(world)
        obsm.ELASTIC_WORLD_SIZE.set(world)
        t0 = time.monotonic()
        self._spawn(0, world)
        started_at = time.time()
        # a just-relaunched/spawned worker's stale beat (or missing
        # beat while it re-warms off the AOT store) must not read as a
        # new death; shared with spawn_fleet_worker, hence an attribute
        grace_until = self._grace_until
        while True:
            # the fleet scaler may have grown/shrunk the world
            if len(self._procs) != world:
                world = len(self._procs)
                self.world_history.append(world)
            if self._shutdown.is_set():
                codes = self._exit_codes()
                self._teardown()
                self.attempts.append(AttemptResult(
                    attempt=attempt, world=world, ok=True, failures=[],
                    exit_codes=codes,
                    duration_s=time.monotonic() - t0,
                ))
                self._merge_timelines()
                self._write_report(final="stopped")
                logger.info(
                    "elastic serve fleet stopped on request: %d "
                    "relaunch wave(s), world %d", self.restarts, world,
                )
                return 0
            codes = self._exit_codes()
            verdicts = self._classify(0, world, started_at)
            now = time.time()
            failed: Dict[int, health.RankHealth] = {}
            for r in range(world):
                if r in self._retired_ranks:
                    continue  # dead by design — the scaler retired it
                alive = codes.get(r) is None
                if alive and now < grace_until.get(r, 0.0):
                    continue
                # ANY exit is a failure here: a serve worker runs until
                # the supervisor says stop, even exit 0 means capacity
                # silently left the fleet
                if verdicts[r].failed or not alive:
                    failed[r] = verdicts[r]
            if not failed:
                time.sleep(self.poll_interval_s)
                continue
            lines = health.format_failures(
                {r: verdicts[r] for r in failed}
            )
            for r in sorted(failed):
                if not verdicts[r].failed:
                    lines.append(
                        f"rank {r}: dead (exited {codes.get(r)} — a "
                        "serve worker runs until stopped)"
                    )
            self.attempts.append(AttemptResult(
                attempt=attempt, world=world, ok=False, failures=lines,
                exit_codes=codes, duration_s=time.monotonic() - t0,
            ))
            obsm.ELASTIC_ATTEMPTS.labels(outcome="failed").inc()
            for r, h in failed.items():
                obsm.ELASTIC_RANK_FAILURES.labels(
                    failure_class=h.state
                ).inc()
                flight.record("rank_failure", rank=r, state=h.state,
                              epoch=h.epoch, step=h.step)
            for line in lines:
                logger.error("%s", line)
            if self.restarts >= self.max_restarts:
                self._teardown()
                self._merge_timelines()
                self._write_report(final="failed")
                flight.dump(
                    "elastic_budget_exhausted",
                    path=os.path.join(self.run_dir,
                                      "flight_supervisor.json"),
                    extra={"failures": lines,
                           "world_history": self.world_history},
                )
                logger.error(
                    "elastic serve fleet failed: restart budget (%d) "
                    "exhausted; per-rank logs under %s",
                    self.max_restarts, self.run_dir,
                )
                return 1
            self.restarts += 1
            obsm.ELASTIC_RESTARTS.inc()
            attempt += 1
            t0 = time.monotonic()
            backoff = self.restart_backoff_s * (2.0 ** (self.restarts - 1))
            logger.warning(
                "elastic serve: relaunching worker(s) %s in place "
                "(restart %d/%d; siblings keep serving) in %.1fs",
                sorted(failed), self.restarts, self.max_restarts, backoff,
            )
            if self._shutdown.wait(backoff):
                continue
            for r in sorted(failed):
                self._relaunch_rank(r, attempt)
                grace_until[r] = time.time() + max(
                    self.spawn_timeout_s, self.heartbeat_timeout_s
                )
            self._write_report(final=None)

    def _run_supervised(self) -> int:
        world = self.nprocs
        attempt = 0
        consecutive_fails = {r: 0 for r in range(world)}
        while True:
            self.world_history.append(world)
            obsm.ELASTIC_WORLD_SIZE.set(world)
            t0 = time.monotonic()
            self._spawn(attempt, world)
            verdicts = self._watch(attempt, world)
            if self._shutdown.is_set():
                # snapshot BEFORE teardown (same reason as the failure
                # path below): a healthy worker this stop is about to
                # SIGTERM must not be recorded as if it died on its own
                codes = self._exit_codes()
                self._teardown()
                self.attempts.append(AttemptResult(
                    attempt=attempt, world=world, ok=True, failures=[],
                    exit_codes=codes,
                    duration_s=time.monotonic() - t0,
                ))
                self._merge_timelines()
                self._write_report(final="stopped")
                logger.info(
                    "elastic job stopped on request: %d restart(s), "
                    "world history %s", self.restarts, self.world_history,
                )
                return 0
            failed = {r: h for r, h in verdicts.items() if h.failed}
            # snapshot exit codes BEFORE teardown: a healthy survivor the
            # supervisor is about to SIGTERM must not be recorded as if
            # it died on its own (the report would contradict its own
            # failure lines)
            codes = self._exit_codes()
            self._teardown()
            lines = health.format_failures(verdicts)
            self.attempts.append(
                AttemptResult(
                    attempt=attempt,
                    world=world,
                    ok=not failed,
                    failures=lines,
                    exit_codes=codes,
                    duration_s=time.monotonic() - t0,
                )
            )
            obsm.ELASTIC_ATTEMPTS.labels(
                outcome="ok" if not failed else "failed"
            ).inc()
            for h in failed.values():
                obsm.ELASTIC_RANK_FAILURES.labels(
                    failure_class=h.state
                ).inc()
                flight.record("rank_failure", rank=h.rank, state=h.state,
                              epoch=h.epoch, step=h.step)
            if not failed:
                self._merge_timelines()
                self._write_report(final="ok")
                logger.info(
                    "elastic job complete: %d restart(s), world history %s",
                    self.restarts, self.world_history,
                )
                return 0
            # the per-rank error summary (docs/RELIABILITY.md): one line
            # per failed rank, not a wall of survivor tracebacks
            for line in lines:
                logger.error("%s", line)
            if self.restarts >= self.max_restarts:
                self._merge_timelines()
                self._write_report(final="failed")
                flight.dump(
                    "elastic_budget_exhausted",
                    path=os.path.join(self.run_dir, "flight_supervisor.json"),
                    extra={"failures": lines,
                           "world_history": self.world_history},
                )
                logger.error(
                    "elastic job failed: restart budget (%d) exhausted; "
                    "per-rank logs under %s",
                    self.max_restarts, self.run_dir,
                )
                return 1
            # elastic world size: a rank index that failed
            # rank_fail_limit consecutive attempts is a lost slot.
            # PEER_FAILURE_EXIT ranks are casualties of someone else's
            # failure, not failing slots — counting them would shrink
            # the world by every healthy rank that died OF the one bad
            # slot.
            for r in range(world):
                slot_failed = r in failed and codes.get(r) != PEER_FAILURE_EXIT
                consecutive_fails[r] = (
                    consecutive_fails.get(r, 0) + 1 if slot_failed else 0
                )
            lost = sum(
                1 for r in range(world)
                if consecutive_fails.get(r, 0) >= self.rank_fail_limit
            )
            new_world = max(self.min_ranks, world - lost)
            if new_world != world:
                logger.warning(
                    "elastic: %d slot(s) failed %d consecutive attempt(s) — "
                    "relaunching on %d rank(s) (was %d); the checkpoint "
                    "reshards onto the smaller mesh",
                    lost, self.rank_fail_limit, new_world, world,
                )
                world = new_world
                consecutive_fails = {r: 0 for r in range(world)}
            self.restarts += 1
            obsm.ELASTIC_RESTARTS.inc()
            self._write_report(final=None)
            backoff = self.restart_backoff_s * (2.0 ** (self.restarts - 1))
            logger.warning(
                "elastic: relaunching (restart %d/%d, world %d) in %.1fs",
                self.restarts, self.max_restarts, world, backoff,
            )
            time.sleep(backoff)
            attempt += 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m distributedpytorch_tpu elastic -n N [opts] -- <train
    args...>`` — the torchrun-shaped launch surface (reference
    README.md:37), with supervision."""
    ap = argparse.ArgumentParser(
        prog="python -m distributedpytorch_tpu elastic",
        description="Elastic supervisor: spawn N ranks, detect failures "
        "via heartbeats, relaunch from the newest intact checkpoint "
        "(possibly at a smaller world size).",
    )
    ap.add_argument("-n", "--nprocs", type=int, required=True,
                    help="Worker ranks to launch")
    ap.add_argument("--workload", type=str, default="train",
                    choices=["train", "serve"],
                    help="What the workers are: 'train' (the training "
                         "CLI, checkpoint-resumed relaunches) or "
                         "'serve' (serve/cli.py HTTP workers, one per "
                         "--port base+rank; no resume, no preflight — "
                         "a dead dispatch loop is a relaunch, not an "
                         "outage)")
    ap.add_argument("--min-ranks", type=int, default=1,
                    help="Never relaunch below this world size")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="Relaunch budget (exponential backoff between)")
    ap.add_argument("--heartbeat-timeout", type=float, default=10.0,
                    help="Beat-file age (s) beyond which a live rank is hung")
    ap.add_argument("--heartbeat-interval", type=float, default=0.5,
                    help="Worker beat cadence (s); passed to workers")
    ap.add_argument("--progress-timeout", type=float, default=0.0,
                    help="Step-progress age (s) beyond which a rank is hung "
                         "(0 = off; set above compile/eval duration)")
    ap.add_argument("--spawn-timeout", type=float, default=300.0,
                    help="Grace (s) for a worker to write its first beat")
    ap.add_argument("--restart-backoff", type=float, default=1.0,
                    help="Base relaunch backoff (doubles per restart)")
    ap.add_argument("--teardown-grace", type=float, default=10.0,
                    help="SIGTERM→SIGKILL grace for survivors")
    ap.add_argument("--rank-fail-limit", type=int, default=2,
                    help="Consecutive failures before a slot is dropped")
    ap.add_argument("--run-dir", type=str, default="./elastic_run",
                    help="Heartbeats, per-rank logs, report.json")
    ap.add_argument("--report", type=str, default=None,
                    help="Report JSON path (default <run-dir>/report.json)")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="Give each rank an N-device virtual CPU mesh "
                         "(drills/tests; 0 = inherit the real backend)")
    ap.add_argument("--chaos", action="append", default=[],
                    metavar="SITE[@RANK]:EPOCH:STEP[:COUNT]",
                    help="Arm a fault (--inject-fault) on the FIRST "
                         "attempt only — drills the detect/relaunch path "
                         "without re-killing the relaunched job")
    ap.add_argument("--no-preflight", action="store_true",
                    help="Skip the static distributed-correctness "
                         "preflight (python -m distributedpytorch_tpu "
                         "analyze over this job's strategy/schedule in a "
                         "CPU subprocess) that otherwise runs before any "
                         "rank is spawned")
    ap.add_argument("--preflight-timeout", type=float, default=300.0,
                    help="Preflight subprocess budget (s); an analyzer "
                         "that cannot run never blocks the launch")
    ap.add_argument("--no-trace", action="store_true",
                    help="Do not arm per-rank step timelines "
                         "(--trace-timeline) or merge them into the "
                         "run's Perfetto trace (<run-dir>/"
                         "timeline_merged.json)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="Serve the supervisor's Prometheus /metrics "
                         "(restarts, world size, per-rank failure "
                         "classes) on this port; with --workload serve "
                         "this becomes the FLEET pane — every worker's "
                         "/metrics scraped and re-exposed merged with "
                         "worker=\"R\" labels (one scrape target for "
                         "the whole fleet)")
    ap.add_argument("--router-port", type=int, default=None,
                    help="With --workload serve: front the fleet on ONE "
                         "address — an HTTP router proxying /predict "
                         "across the workers with load-aware placement, "
                         "transparent retry of 503s and dead workers, "
                         "and POST /admin/ab fan-out (serve/router.py)")
    ap.add_argument("--router-standby-port", type=int, default=None,
                    help="With --router-port: run a SECOND router as an "
                         "active/standby HA pair on this port. Both "
                         "proxy /predict; the standby pulls the "
                         "active's /admin/state snapshot every probe "
                         "interval and takes over on the first missed "
                         "probe — clients keep both addresses and fail "
                         "over on connection refusal (no VIP; "
                         "docs/SERVING.md 'Front door HA')")
    ap.add_argument("--fleet-plan", type=str, default=None,
                    help="dpt_serve_plan JSON for the FLEET scaler: the "
                         "supervisor spawns/retires whole serve workers "
                         "to match the plan's replica recommendation "
                         "for the observed arrival rate, every decision "
                         "citing its plan-serve grid point")
    ap.add_argument("--fleet-min-workers", type=int, default=1,
                    help="Fleet scaler floor (never retire below)")
    ap.add_argument("--fleet-max-workers", type=int, default=None,
                    help="Fleet scaler ceiling; setting it (or "
                         "--fleet-plan) enables the fleet scaler")
    ap.add_argument("--fleet-interval", type=float, default=10.0,
                    help="Fleet scaler control-window cadence (s); "
                         "<= 0 leaves the scaler manual (tests/ops "
                         "drive .step() directly)")
    ap.add_argument("worker_args", nargs=argparse.REMAINDER,
                    help="Training CLI args (prefix with --)")
    args = ap.parse_args(argv)

    worker_args = list(args.worker_args)
    if worker_args and worker_args[0] == "--":
        worker_args = worker_args[1:]

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    sup = ElasticSupervisor(
        worker_args,
        nprocs=args.nprocs,
        min_ranks=args.min_ranks,
        max_restarts=args.max_restarts,
        heartbeat_timeout_s=args.heartbeat_timeout,
        heartbeat_interval_s=args.heartbeat_interval,
        progress_timeout_s=args.progress_timeout,
        spawn_timeout_s=args.spawn_timeout,
        restart_backoff_s=args.restart_backoff,
        teardown_grace_s=args.teardown_grace,
        rank_fail_limit=args.rank_fail_limit,
        run_dir=args.run_dir,
        report_path=args.report,
        cpu_devices=args.cpu_devices,
        chaos=args.chaos,
        preflight=not args.no_preflight,
        preflight_timeout_s=args.preflight_timeout,
        trace=not args.no_trace,
        metrics_port=args.metrics_port,
        workload=args.workload,
        router_port=args.router_port,
        router_standby_port=args.router_standby_port,
        fleet_plan=args.fleet_plan,
        fleet_min_workers=args.fleet_min_workers,
        fleet_max_workers=args.fleet_max_workers,
        fleet_interval_s=args.fleet_interval,
    )
    return sup.run()


if __name__ == "__main__":
    sys.exit(main())
