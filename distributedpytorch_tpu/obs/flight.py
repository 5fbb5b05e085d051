"""Flight recorder: an always-on bounded ring buffer of recent events,
dumped to a JSON artifact when the process dies badly.

Chip windows r03–r05 died without a single artifact saying *where*
(ROADMAP "Recent"): a watchdogged step, a wedged compile, a dead
dispatch loop each left only an absence of output. The flight recorder
is the black box for that failure class — cheap enough to leave on for
every run (one ``deque.append`` of a small dict per event; the deque's
``maxlen`` bounds memory by construction), and dumped by the code paths
that already know the run is dying:

* the trainer's dispatch watchdog (``train/loop.py``),
* the non-finite-loss ``abort`` policy,
* the SIGTERM/SIGINT checkpoint-and-stop handler,
* the serve dispatch loop's death path (``serve/server.py``),
* an optional unhandled-exception hook (:func:`install_excepthook`).

What flows in (always-on, no flags): step-timeline spans
(``utils/trace.py`` routes every span here even when JSONL tracing is
off), queue flush/shed decisions and placement/dispatch transitions
(serve tier), fault injections (``utils/faults.py``), and
collective-phase markers (epoch/eval/checkpoint boundaries). The tail
of the ring therefore identifies the phase a dead run was in.

Hot-path contract (enforced by dptlint's ``obs-hot-path`` rule):
``record`` never blocks on a device value and allocates nothing beyond
the ring slot — ``deque.append`` with ``maxlen`` is atomic under the
GIL, so the record path takes **no lock**.

``DPT_OBS=0`` disables recording (the overhead A/B lever used for the
numbers in docs/OBSERVABILITY.md). Dump-path precedence:
:func:`set_dump_path` (explicit caller) >
``$DPT_FLIGHT_PATH`` > ``$DPT_FLIGHT_DIR``/flight_rank<R>.json >
the default installed by the owning subsystem (trainer: under its log
dir) > ``./logs/flight_rank<R>.json``.

Stdlib-only and jax-free, like the rest of ``obs/``.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import sys
import threading
import time
from typing import List, Optional

logger = logging.getLogger(__name__)

#: Ring capacity: enough to hold several steps' worth of spans plus the
#: surrounding phase markers — the post-mortem needs the tail, not the run.
DEFAULT_CAPACITY = 512


def _obs_enabled() -> bool:
    return os.environ.get("DPT_OBS", "1").lower() not in ("0", "off", "false")


class FlightRecorder:
    """See module docstring. One per process (:func:`get`)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self.enabled = _obs_enabled()
        self.rank = 0
        self._explicit_path: Optional[str] = None
        self._default_path: Optional[str] = None
        self._dump_lock = threading.Lock()
        self.last_dump_path: Optional[str] = None
        self._hook_installed = False

    # -- recording (hot-path safe: no locks, bounded allocation) ------------
    def record(self, kind: str, **fields) -> None:
        if not self.enabled:
            return
        fields["t"] = round(time.time(), 6)
        fields["kind"] = kind
        self._events.append(fields)

    def record_span(self, phase: str, t0: float, t1: float, **tags) -> None:
        """A timed phase span (the step-timeline tracer's feed)."""
        if not self.enabled:
            return
        tags["t"] = round(time.time(), 6)
        tags["kind"] = "span"
        tags["phase"] = phase
        tags["dur_ms"] = round((t1 - t0) * 1e3, 3)
        self._events.append(tags)

    # -- configuration -------------------------------------------------------
    def set_dump_path(self, path: Optional[str]) -> None:
        """Explicit dump path — wins over the env vars and defaults."""
        self._explicit_path = path

    def set_default_dump_path(self, path: str) -> None:
        """Subsystem-installed default (trainer/server): used only when
        neither :func:`set_dump_path` nor the env vars name a path."""
        self._default_path = path

    def resolve_dump_path(self) -> str:
        if self._explicit_path:
            return self._explicit_path
        env_path = os.environ.get("DPT_FLIGHT_PATH")
        if env_path:
            return env_path
        env_dir = os.environ.get("DPT_FLIGHT_DIR")
        if env_dir:
            return os.path.join(env_dir, f"flight_rank{self.rank}.json")
        if self._default_path:
            return self._default_path
        return os.path.join("./logs", f"flight_rank{self.rank}.json")

    # -- inspection (tests / exporters) --------------------------------------
    def snapshot(self) -> List[dict]:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()
        self.last_dump_path = None

    def __len__(self) -> int:
        return len(self._events)

    # -- the dump ------------------------------------------------------------
    def dump(self, reason: str, path: Optional[str] = None,
             extra: Optional[dict] = None) -> Optional[str]:
        """Write the ring to a JSON artifact. NEVER raises — every
        caller is already on a dying path where a secondary I/O error
        must not mask the primary failure. Returns the artifact path
        (or None when recording is disabled / the write failed)."""
        if not self.enabled:
            return None
        try:
            out = path or self.resolve_dump_path()
            payload = {
                "reason": reason,
                "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                "pid": os.getpid(),
                "rank": self.rank,
                "events": self.snapshot(),
            }
            if extra:
                payload["extra"] = extra
            # non-blocking: dump() is called from SIGNAL HANDLERS, which
            # Python runs on the main thread — a handler that fires while
            # this same thread is mid-dump would deadlock on a blocking
            # acquire of its own lock. If a dump is already in progress,
            # the post-mortem is being written; skip this one.
            if not self._dump_lock.acquire(blocking=False):
                return None
            try:
                d = os.path.dirname(os.path.abspath(out))
                os.makedirs(d, exist_ok=True)
                tmp = f"{out}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(payload, f)
                os.replace(tmp, out)
                self.last_dump_path = out
            finally:
                self._dump_lock.release()
            logger.error("flight recorder: dumped %d event(s) to %s (%s)",
                         len(payload["events"]), out, reason)
            try:  # lazy: defs pulls in the registry, which dump paths
                # must not depend on to write the artifact itself
                from distributedpytorch_tpu.obs import defs as obsm

                obsm.FLIGHT_DUMPS.labels(
                    reason_class=reason.split(":", 1)[0].strip()
                ).inc()
            except Exception:  # noqa: BLE001 — accounting only
                pass
            return out
        except Exception:  # noqa: BLE001 — see docstring
            logger.exception("flight recorder dump failed")
            return None

    # -- unhandled-exit hook -------------------------------------------------
    def install_excepthook(self) -> None:
        """Dump the ring on an unhandled exception (then defer to the
        previous hook). Idempotent."""
        if self._hook_installed:
            return
        self._hook_installed = True
        prev = sys.excepthook

        def hook(exc_type, exc, tb):
            self.dump(f"unhandled_exception: {exc_type.__name__}: "
                      f"{str(exc)[:200]}")
            prev(exc_type, exc, tb)

        sys.excepthook = hook


_RECORDER = FlightRecorder()


def get() -> FlightRecorder:
    return _RECORDER


def record(kind: str, **fields) -> None:
    _RECORDER.record(kind, **fields)


def record_span(phase: str, t0: float, t1: float, **tags) -> None:
    _RECORDER.record_span(phase, t0, t1, **tags)


def dump(reason: str, path: Optional[str] = None,
         extra: Optional[dict] = None) -> Optional[str]:
    return _RECORDER.dump(reason, path=path, extra=extra)


def set_dump_path(path: Optional[str]) -> None:
    _RECORDER.set_dump_path(path)


def set_default_dump_path(path: str) -> None:
    _RECORDER.set_default_dump_path(path)


def set_rank(rank: int) -> None:
    _RECORDER.rank = int(rank)


def install_excepthook() -> None:
    _RECORDER.install_excepthook()
