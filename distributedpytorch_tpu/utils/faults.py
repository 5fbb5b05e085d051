"""Deterministic fault injection + failure-policy primitives.

The resilience subsystem (docs/RELIABILITY.md) has to be *provable* on the
CPU mesh — a recovery path that only ever executes when a real pod flakes
is an untested path. This module provides:

  * a **fault-injection harness**: named sites in the data decode path
    (``decode``, data/loader.py), the placement worker (``placement``,
    utils/prefetch.py), the train-step output (``nan_loss``, train/loop.py),
    the checkpoint writer (``ckpt_write``, checkpoint.py), and a simulated
    preemption (``sigterm``, train/loop.py). Specs are
    ``site:epoch:step[:count]`` strings (``*`` wildcards), armed via
    ``Config.inject_faults`` / CLI ``--inject-fault``, and fire
    deterministically at their (epoch, step) coordinates;
  * the transient-error classes the retry machinery keys on
    (:data:`TRANSIENT_ERRORS`, :func:`call_with_retries` — bounded
    exponential backoff shared by the decode and placement retry paths);
  * :class:`StepWatchdog` — the host-side dispatch watchdog the trainer
    arms per step (train/loop.py);
  * :class:`NonFiniteLossError` — raised by the trainer's non-finite-loss
    policies (``abort`` directly; ``rollback`` after the retry budget).

Installation is process-global and **idempotent per spec list**:
``fit_with_restarts`` rebuilds the Trainer after a crash, and a count-1
fault that already fired must NOT re-arm on the rebuilt attempt — that
would turn every injected crash into an unrecoverable crash loop. Tests
that want a fresh arming call :func:`reset` first.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

from distributedpytorch_tpu.obs import flight

logger = logging.getLogger(__name__)

#: The named injection sites (one per recovery path under test).
#: ``rank_kill`` (SIGKILL this process — the chaos input of the elastic
#: supervisor's detect/relaunch path) and ``rank_hang`` (wedge the step
#: loop in a long sleep — what a dead collective looks like from the
#: host) fire in the step loop (train/loop.py) and are usually pinned to
#: one rank with the ``site@RANK`` spec form.
#:
#: The serve tier's chaos sites (docs/SERVING.md "Fleet & rollout")
#: drill the self-healing paths on CPU: ``serve_dispatch_death`` kills
#: the dispatch loop (→ in-process core relaunch, serve/server.py),
#: ``serve_replica_wedge`` wedges a dispatch in a long sleep (what a
#: hung device call looks like from the host — the supervisor's
#: progress-timeout verdict), ``serve_decode`` fails one request's
#: ingress decode, and ``swap_crash`` fails a weight hot-swap mid-
#: device_put (→ canary rollback, serve/rollout.py). Serve sites carry
#: no epoch; their ``step`` coordinate is the dispatch sequence number.
SITES = (
    "decode", "placement", "nan_loss", "ckpt_write", "sigterm",
    "rank_kill", "rank_hang",
    "serve_dispatch_death", "serve_replica_wedge", "serve_decode",
    "swap_crash",
)


class InjectedFault(Exception):
    """Marker base for every injected failure (testable provenance)."""


class InjectedTransientError(InjectedFault, OSError):
    """An injected *transient* failure (decode / placement): an OSError
    subclass, so the retry paths treat it exactly like the real-world
    transient host I/O errors they exist for."""


class NonFiniteLossError(RuntimeError):
    """A train-step loss came back NaN/Inf and the configured policy
    (``abort``, or ``rollback`` with its budget exhausted) gave up."""


#: What the bounded-backoff retry paths consider transient. OSError covers
#: real host I/O flakes (disk reads, sockets, PIL on torn files) and, via
#: ConnectionError/TimeoutError subclassing, runtime-channel blips; the
#: injected transient error subclasses it deliberately.
TRANSIENT_ERRORS: Tuple[type, ...] = (OSError,)

#: Channel-shaped markers in RuntimeError messages: jaxlib surfaces a
#: flapping runtime channel as XlaRuntimeError (a RuntimeError subclass,
#: NOT an OSError), so the placement retry path must recognize these by
#: message. grpc channel statuses + socket-ish strings only — never
#: 'INTERNAL:' (deterministic compile failures must not retry).
_CHANNEL_MARKERS = (
    "UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
    "connection", "Connection", "socket", "stream terminated",
)


def is_transient(exc: BaseException) -> bool:
    """True for the failures the bounded-backoff retry paths retry:
    the OSError family, plus channel-shaped RuntimeErrors (how a
    flapping TPU runtime actually surfaces during placement)."""
    if isinstance(exc, TRANSIENT_ERRORS):
        return True
    return isinstance(exc, RuntimeError) and any(
        m in str(exc) for m in _CHANNEL_MARKERS
    )


@dataclasses.dataclass
class FaultSpec:
    """One armed fault: fire at (epoch, step) — None = wildcard — up to
    ``count`` times (-1 = unlimited). ``rank`` pins the fault to one
    process of a multi-process job (None = every rank): how chaos drills
    kill/hang/poison exactly one peer of a live mesh."""

    site: str
    epoch: Optional[int] = None
    step: Optional[int] = None
    count: int = 1
    rank: Optional[int] = None


def _process_index() -> int:
    """This process's rank, lazily (faults.py stays importable without
    jax, and the backend may initialize after specs are armed)."""
    try:
        import jax

        return jax.process_index()
    except Exception:  # pragma: no cover — jax absent/uninitialized
        return 0


def parse_fault_spec(text: str) -> FaultSpec:
    """Parse ``site[@rank]:epoch:step[:count]``; ``*`` (or omitted)
    wildcards a coordinate; count ``*`` means unlimited; ``@rank`` pins
    the fault to one process (e.g. ``rank_kill@1:1:6``)."""
    parts = str(text).strip().split(":")
    site, rank = parts[0], None
    if "@" in site:
        site, rank_text = site.split("@", 1)
        try:
            rank = int(rank_text)
        except ValueError:
            raise ValueError(
                f"bad fault rank {rank_text!r} in {text!r}: site@RANK"
            ) from None
        if rank < 0:
            raise ValueError(f"fault rank must be >= 0 in {text!r}")
    if site not in SITES:
        raise ValueError(
            f"unknown fault site {site!r}; expected one of {SITES}"
        )

    def coord(i: int) -> Optional[int]:
        if len(parts) <= i or parts[i] in ("", "*"):
            return None
        return int(parts[i])

    if len(parts) > 4:
        raise ValueError(f"bad fault spec {text!r}: site:epoch:step[:count]")
    count = coord(3)
    count = 1 if count is None and (len(parts) <= 3 or parts[3] != "*") else (
        -1 if count is None else count
    )
    if count == 0 or count < -1:
        raise ValueError(f"bad fault count in {text!r} (>=1, or '*')")
    return FaultSpec(
        site=site, epoch=coord(1), step=coord(2), count=count, rank=rank
    )


class FaultInjector:
    """Holds armed :class:`FaultSpec`\\ s; ``fire`` matches + decrements.

    A spec pinned to an epoch/step never matches a call site that cannot
    supply that coordinate (conservative: an unknowable coordinate is not
    a wildcard match) — wildcard the coordinate in the spec instead.
    """

    def __init__(self, specs: Sequence = ()):
        self.raw_specs = tuple(str(s) for s in specs)
        self._specs = [
            s if isinstance(s, FaultSpec) else parse_fault_spec(s)
            for s in specs
        ]
        self._lock = threading.Lock()
        self.fired: Dict[str, int] = {}

    def fire(self, site: str, epoch: Optional[int] = None,
             step: Optional[int] = None) -> bool:
        if not self._specs:  # inert fast path — call sites stay hot-loop safe
            return False
        with self._lock:
            for spec in self._specs:
                if spec.site != site or spec.count == 0:
                    continue
                if spec.rank is not None and spec.rank != _process_index():
                    continue
                if spec.epoch is not None and spec.epoch != epoch:
                    continue
                if spec.step is not None and spec.step != step:
                    continue
                if spec.count > 0:
                    spec.count -= 1
                self.fired[site] = self.fired.get(site, 0) + 1
                logger.warning(
                    "fault injection: firing %r at epoch=%s step=%s",
                    site, epoch, step,
                )
                # the flight recorder's post-mortem tail must show the
                # injected fault next to the phase it killed
                flight.record("fault", site=site, epoch=epoch, step=step)
                return True
        return False


_INERT = FaultInjector(())
_active = _INERT


def install(specs: Sequence) -> FaultInjector:
    """Arm the process-global injector. Idempotent: the same spec tuple
    keeps the CURRENT injector and its decremented counts (see module
    docstring — restart recovery depends on this)."""
    global _active
    raw = tuple(str(s) for s in (specs or ()))
    if raw == _active.raw_specs:
        return _active
    _active = FaultInjector(raw) if raw else _INERT
    return _active


def reset() -> None:
    """Disarm everything (tests)."""
    global _active
    _active = _INERT


def active() -> FaultInjector:
    return _active


def fire(site: str, epoch: Optional[int] = None,
         step: Optional[int] = None) -> bool:
    return _active.fire(site, epoch=epoch, step=step)


def maybe_raise_transient(site: str, epoch: Optional[int] = None,
                          step: Optional[int] = None) -> None:
    if _active.fire(site, epoch=epoch, step=step):
        raise InjectedTransientError(
            f"injected {site} fault (epoch={epoch}, step={step})"
        )


def call_with_retries(
    fn: Callable,
    site: str,
    retries: int,
    backoff_s: float,
    epoch: Optional[int] = None,
    step: Optional[int] = None,
    log: Optional[logging.Logger] = None,
):
    """Run ``fn()`` with up to ``retries`` bounded-exponential-backoff
    retries on :data:`TRANSIENT_ERRORS`, checking the ``site`` injection
    point first each attempt (so an injected transient exercises the SAME
    retry loop a real one would). The final failure re-raises."""
    attempt = 0
    while True:
        try:
            maybe_raise_transient(site, epoch=epoch, step=step)
            return fn()
        except Exception as exc:
            if not is_transient(exc) or attempt >= retries:
                raise
            delay = backoff_s * (2.0 ** attempt)
            from distributedpytorch_tpu.obs import defs as obsm

            obsm.TRAIN_RETRIES.labels(site=site).inc()
            flight.record("retry", site=site, attempt=attempt + 1,
                          error=f"{type(exc).__name__}: {str(exc)[:120]}")
            (log or logger).warning(
                "transient %s failure (attempt %d/%d): %s — retrying in %.2gs",
                site, attempt + 1, retries, exc, delay,
            )
            time.sleep(delay)
            attempt += 1


class StepWatchdog:
    """Host-side dispatch watchdog: flags a step exceeding its timeout.

    The trainer ``pet()``\\ s it once per step-loop iteration and
    ``pause()``\\ s it across the non-step phases (eval, end-of-epoch
    checkpointing) whose legitimate duration is unrelated to step time.
    On expiry, ``on_timeout`` runs ONCE on the watchdog thread (the loop
    thread may be blocked inside a native call — that is the scenario);
    the trainer's callback dumps the step-timeline tracer's spans and
    requests a checkpoint-and-stop through the collective stop agreement
    (train/loop.py). The watchdog disarms after firing — one diagnosis,
    not a spam loop.
    """

    def __init__(self, timeout_s: float, on_timeout: Callable[[], None]):
        self.timeout_s = float(timeout_s)
        self.on_timeout = on_timeout
        self._deadline: Optional[float] = None  # None = paused
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.fired = False
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="dpt-step-watchdog"
        )
        self._thread.start()

    def pet(self) -> None:
        """A step-loop iteration made progress: re-arm the deadline."""
        with self._lock:
            self._deadline = time.monotonic() + self.timeout_s

    def pause(self) -> None:
        with self._lock:
            self._deadline = None

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)

    def _run(self) -> None:
        poll = max(0.01, min(self.timeout_s / 4.0, 0.5))
        while not self._stop.wait(poll):
            with self._lock:
                expired = (
                    not self.fired
                    and self._deadline is not None
                    and time.monotonic() > self._deadline
                )
                if expired:
                    self.fired = True
                    self._deadline = None
            if expired:
                try:
                    self.on_timeout()
                except Exception:  # noqa: BLE001 — diagnostic path only
                    logger.exception("step watchdog callback failed")
