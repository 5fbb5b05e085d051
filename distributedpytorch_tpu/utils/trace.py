"""Step-timeline tracer: per-phase host timestamps for the async pipeline.

The fully-overlapped step loop (train/loop.py + utils/prefetch.py) runs
these host-observable phases per batch, in pipeline order (the one list
is ``obs.trace_hub.PHASES``, imported here) —

    decode     host-side sample decode / batch assembly (data/loader.py;
               on the loader's thread, inside the feed's ``fetch``)
    fetch      the prefetch worker pulling its next work item from the
               loader (cache lookup, decode, the batch's np.stack); on a
               serve path this includes waiting for traffic
    slot_wait  the worker waiting for a prefetch slot to free: only the
               time actually waited, so none while the loop keeps up
    stack      np.stack of K per-step batches into one dispatch payload
    h2d        host→device placement (strategy.place_work on the worker):
               the ENQUEUE of the copy, which returns before it has run
    h2d_ready  from the end of ``h2d`` until every placed array is ready
               on its device, recorded by a watcher thread that exists
               only while the timeline is enabled (:class:`ReadyWatcher`)
    feed_wait  the step loop waiting for a placed batch (the consumer's
               side of utils/prefetch.bounded_prefetch)
    dispatch   the host-side step call (async: enqueue, not execution)
    readback   device→host drain of loss scalars (utils/metrics.py)

— and whether they actually overlap is invisible in aggregate throughput
numbers. This tracer records ``(phase, t0, t1)`` wall spans (a shared
``time.perf_counter`` clock across every thread: loader pool, placement
worker, main loop), appends them as JSONL, and summarizes per-phase
totals so a throughput regression is attributable to the phase that
grew. The benchmark's feed readers (benchmark/feed_spans.py) and the
overlap test (tests/test_async_pipeline.py) read the raw spans.

Every span of one batch carries the same ``(epoch, seq)`` tags: ``seq``
counts the feed's work items from 0 in each epoch (a K-stack is one
item), so a batch can be followed from ``fetch`` to ``dispatch`` and an
epoch's first batches told from the rest. The feed closes an epoch with
one ``fetch`` and one ``feed_wait`` tagged ``end=True`` under the ``seq``
after the last batch's. ``fetch`` and ``h2d`` also carry ``bytes``, the
host size of the item's arrays.

One clock with the device trace: where the program itself starts a
``jax.profiler`` trace (``--profile-steps``, ``--profile-dir``) it calls
:meth:`StepTimeline.profile_started`, which writes one
``TraceAnnotation("dpt_sync", pc_ns=<perf_counter_ns>)`` into the profile
and the same reading as a ``clock_sync`` event here. A reader shifts a
span onto the profiler's clock by ``sync.start − pc_ns·1e-9``. Until
:meth:`StepTimeline.profile_stopped`, every ``span()`` also opens a
``TraceAnnotation("dpt_<phase>", **tags)``, so the host spans sit in the
same ``.xplane.pb`` beside the device operations (jax is imported only
then: this module needs no backend).

Disabled (the default: no path) it is a no-op cheap enough to leave the
call sites unconditional.

The telemetry layer (distributedpytorch_tpu/obs) rides these call
sites: every completed span ALSO lands in the flight recorder's bounded
ring (obs/flight.py) whether JSONL tracing is on or not — that is what
makes a crash dump's tail identify the phase a dead run was in — and
events carry a ``rank`` tag plus a wall-clock anchor so the trace hub
(obs/trace_hub.py) can merge per-rank JSONL files into one Perfetto
timeline with cross-rank-comparable timestamps (``t0``/``t1`` stay
``perf_counter`` values, whose origin is per-process).
"""

from __future__ import annotations

import contextlib
import json
import queue
import threading
import time
from typing import Dict, Iterable, List, Optional

from distributedpytorch_tpu.obs import flight
from distributedpytorch_tpu.obs.trace_hub import PHASES  # noqa: F401 — the one list


class StepTimeline:
    """Collects per-phase spans; thread-safe; JSONL-append on flush().

    ``path=None`` disables collection entirely unless ``enabled=True`` is
    forced (in-memory mode — what the benchmark's step loop reads).
    Even disabled, completed spans feed the flight recorder's ring
    (bounded, allocation = the ring slot) unless ``DPT_OBS=0``.
    """

    def __init__(self, path: Optional[str] = None, *,
                 enabled: Optional[bool] = None, rank: int = 0):
        self.path = path
        self.enabled = (path is not None) if enabled is None else enabled
        self.rank = int(rank)
        self._events: List[dict] = []
        self._lock = threading.Lock()
        # per-phase running totals survive flush(): the summary covers the
        # whole run even though events are dumped incrementally
        self._totals: Dict[str, List[float]] = {}  # phase -> [count, total_s]
        # jax.profiler.TraceAnnotation while a profile that the program
        # started is running (profile_started), else None
        self._annotation = None

    def record(self, phase: str, t0: float, t1: float,
               wall: Optional[float] = None, **tags) -> None:
        """``wall`` defaults to now — right for spans recorded at their
        own end (the ``span()`` context manager). Callers that record a
        request's WHOLE ledger at completion (obs/reqtrace.py) pass each
        phase's true end-of-phase wall time instead, so the trace hub's
        ``wall − (t1 − t0)`` anchor lands every phase at its real start
        rather than collapsing them all onto the completion instant."""
        flight.record_span(phase, t0, t1, rank=self.rank, **tags)
        if not self.enabled:
            return
        event = {"phase": phase, "t0": round(t0, 6), "t1": round(t1, 6),
                 "wall": round(wall if wall is not None else time.time(), 6),
                 "rank": self.rank, **tags}
        with self._lock:
            self._events.append(event)
            acc = self._totals.setdefault(phase, [0, 0.0])
            acc[0] += 1
            acc[1] += t1 - t0

    @contextlib.contextmanager
    def span(self, phase: str, **tags):
        """Time the block as one ``phase`` span. Yields ``tags``: what the
        block adds to it before it ends is recorded with the span (the
        ``seq`` of an item known only once it has arrived)."""
        annotation = self._annotation
        if annotation is None and not self.enabled and not flight.get().enabled:
            yield tags
            return
        t0 = time.perf_counter()
        try:
            with (annotation("dpt_" + phase, **tags) if annotation
                  else contextlib.nullcontext()):
                yield tags
        finally:
            self.record(phase, t0, time.perf_counter(), **tags)

    def profile_started(self) -> None:
        """Tie this timeline's clock to a ``jax.profiler`` trace that the
        caller has just started: one ``dpt_sync`` annotation in the
        profile carries the ``perf_counter_ns`` reading that a
        ``clock_sync`` event records here, and until
        :meth:`profile_stopped` every ``span()`` is also an annotation
        ``dpt_<phase>`` in that profile."""
        import jax  # only here: the module stays importable without a backend

        pc_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("dpt_sync", pc_ns=pc_ns):
            pass
        self.record("clock_sync", pc_ns * 1e-9, pc_ns * 1e-9, pc_ns=pc_ns)
        self._annotation = jax.profiler.TraceAnnotation

    def profile_stopped(self) -> None:
        self._annotation = None

    def ready_watcher(self, phase: str, name: str) -> Optional["ReadyWatcher"]:
        """A started :class:`ReadyWatcher` that records ``phase`` spans
        here, or None while the timeline is disabled: then no thread
        exists and nothing waits for a device value."""
        return ReadyWatcher(self, phase, name) if self.enabled else None

    def events(self, phase: Optional[str] = None) -> List[dict]:
        """Unflushed events (optionally one phase), in record order."""
        with self._lock:
            evs = list(self._events)
        return [e for e in evs if phase is None or e["phase"] == phase]

    def flush(self) -> None:
        """Append collected events to ``path`` as JSONL and clear them
        (totals persist). In-memory mode just clears."""
        with self._lock:
            evs, self._events = self._events, []
        if not evs or self.path is None:
            return
        with open(self.path, "a") as f:
            for e in evs:
                f.write(json.dumps(e) + "\n")

    def summary(self) -> Dict[str, Optional[dict]]:
        """Per-phase ``{count, total_ms, mean_ms}`` over the whole run:
        the listed phases first, one never observed reporting None
        (distinguishable from 0 ms), then every other phase seen."""
        with self._lock:
            totals = {k: list(v) for k, v in self._totals.items()}
        return _format_totals(totals)


class ReadyWatcher:
    """A daemon thread that turns "this device result was enqueued" into
    a span that ends when the result is ready: :meth:`watch` stamps the
    hand-over and returns at once, the thread blocks on the result
    (``jax.block_until_ready``) and records the span, and holds the
    result no longer than that. The caller's own thread never waits for
    the device. :meth:`close` ends the thread once it has seen what was
    handed over before."""

    _CLOSE = object()

    def __init__(self, tracer: StepTimeline, phase: str, name: str):
        self._tracer, self._phase = tracer, phase
        self._q: queue.Queue = queue.Queue()
        threading.Thread(target=self._run, daemon=True, name=name).start()

    def watch(self, result, **tags) -> None:
        self._q.put((time.perf_counter(), result, tags))

    def close(self) -> None:
        self._q.put(self._CLOSE)

    def _run(self) -> None:
        import jax

        while True:
            entry = self._q.get()
            if entry is self._CLOSE:
                return
            t0, result, tags = entry
            del entry
            try:
                jax.block_until_ready(result)
            except RuntimeError:  # the consumer meets the same failure on use
                continue
            finally:
                del result
            self._tracer.record(self._phase, t0, time.perf_counter(), **tags)


def _format_totals(totals: Dict[str, List[float]]) -> Dict[str, Optional[dict]]:
    """phase → [count, total_s] accumulators → the summary shape shared by
    StepTimeline.summary and summarize_events (one formatter, so the
    two never drift apart)."""
    out: Dict[str, Optional[dict]] = {}
    for phase in (*PHASES, *(p for p in totals if p not in PHASES)):
        if phase not in totals:
            out[phase] = None
            continue
        count, total = totals[phase]
        out[phase] = {
            "count": int(count),
            "total_ms": round(1e3 * total, 3),
            "mean_ms": round(1e3 * total / count, 3) if count else 0.0,
        }
    return out


#: Shared disabled instance for call sites whose owner passed no tracer.
NULL_TIMELINE = StepTimeline(None)


def load_events(path: str) -> List[dict]:
    """Parse a timeline JSONL file, skipping torn/blank lines (the file is
    appended mid-run; a concurrent reader can catch a partial line)."""
    events = []
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if isinstance(d, dict) and "phase" in d:
                events.append(d)
    return events


def summarize_events(events: Iterable[dict]) -> Dict[str, Optional[dict]]:
    """Same per-phase shape as :meth:`StepTimeline.summary`, from raw
    events (e.g. a trainer-written JSONL read back)."""
    totals: Dict[str, List[float]] = {}
    for e in events:
        try:
            dt = float(e["t1"]) - float(e["t0"])
        except (KeyError, TypeError, ValueError):
            continue
        acc = totals.setdefault(e["phase"], [0, 0.0])
        acc[0] += 1
        acc[1] += dt
    return _format_totals(totals)


def summarize_timeline(path: str) -> Dict[str, Optional[dict]]:
    return summarize_events(load_events(path))
