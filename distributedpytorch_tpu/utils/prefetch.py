"""Bounded prefetch helpers shared by the input pipeline.

Two variants of the same submit-ahead/pop/yield shape, differing in who
runs the work and what happens when the consumer walks away:

* :func:`bounded_prefetch` — a single daemon worker thread. For work that
  may block indefinitely on an external runtime (host→device
  placement): a daemon thread can never block interpreter
  exit, and closing the generator (or breaking out of a ``for``) stops the
  worker within its put-poll interval instead of leaving it wedged on a
  full queue pinning device buffers.
* :func:`bounded_submit` — futures on a caller-owned executor. For
  parallel host-side work (image decode across a pool); abandoning the
  generator cancels everything still queued.

Both yield in submission order and re-raise worker exceptions at the
consumption point.

On top of them sits the step-pipeline placement scheduler
(:func:`stacked_work` + :func:`pipelined_placement`): the trainer's epoch
stream of host batches becomes a stream of *work items* — K-stacks for the
fused-dispatch paths, singles for everything else — whose np.stack and
host→device placement run on the prefetch worker, ``depth`` items ahead of
the consuming step loop. That is what keeps the device dispatch queue
non-empty: batch N+1's H2D transfer rides under batch N's executing scan
instead of serializing behind it.

The serving tier (serve/server.py) runs the SAME scheduler on its
request path: flushed request buckets are the work items, and
``place_fn`` stacks + pads + H2D-places each bucket onto its claimed
replica's device, ``depth`` buckets ahead of the dispatch loop.

Where the feed's time goes is recorded here, into the timeline the
caller passes as ``tracer=`` (utils/trace.py has the phases): ``fetch``,
``slot_wait``, ``stack`` and ``h2d`` on the worker, ``h2d_ready`` on a
watcher thread, ``feed_wait`` on the consumer, every span of one item
under the same ``(epoch, seq)``.
"""

from __future__ import annotations

import collections
import itertools
import logging
import queue as queue_mod
import threading
import time
from typing import Callable, Iterable, Iterator, Optional, Tuple, TypeVar

import numpy as np

from distributedpytorch_tpu.utils import faults
from distributedpytorch_tpu.utils.trace import NULL_TIMELINE

logger = logging.getLogger(__name__)

T = TypeVar("T")
R = TypeVar("R")

_DONE = object()


def _nbytes(item) -> int:
    """Host bytes of the arrays a work item holds (mappings, lists and
    tuples of them); 0 where it holds none, as a serve bucket."""
    if isinstance(item, dict):
        return sum(_nbytes(v) for v in item.values())
    if isinstance(item, (list, tuple)):
        return sum(_nbytes(v) for v in item)
    return int(getattr(item, "nbytes", 0))


def _tokens(item) -> int:
    """Tokens a work item holds: the elements of its ``tokens`` fields."""
    if isinstance(item, dict):
        return sum(int(getattr(v, "size", 0)) if k == "tokens" else _tokens(v)
                   for k, v in item.items())
    if isinstance(item, (list, tuple)):
        return sum(_tokens(v) for v in item)
    return 0


def _bytes_tag(item) -> dict:
    """The ``bytes`` tag of a work item's spans, and ``tokens`` for a
    batch of them: each left out where the item holds none."""
    tags = {"bytes": _nbytes(item), "tokens": _tokens(item)}
    return {k: v for k, v in tags.items() if v}


def _fetched(items: Iterable[T], tracer, tags: dict) -> Iterator[Tuple[int, T, dict]]:
    """``(seq, item, its bytes tag)`` of each item, the pull of each from
    ``items`` under a ``fetch`` span; the pull that finds the end is the
    span tagged ``end=True``."""
    it = iter(items)
    for seq in itertools.count():
        with tracer.span("fetch", seq=seq, **tags) as span:
            try:
                item = next(it)
            except StopIteration:
                span["end"] = True
                return
            size = _bytes_tag(item)
            span.update(size)
        yield seq, item, size


def bounded_prefetch(
    items: Iterable[T], fn: Callable[[T], R], depth: int = 2,
    name: str = "dpt-prefetch", tracer=None, epoch: Optional[int] = None,
) -> Iterator[Tuple[T, R]]:
    """Yield ``(item, fn(item))`` with ``fn`` running up to ``depth`` items
    ahead on a daemon thread.

    The bound counts results the worker holds: a semaphore permit is taken
    BEFORE ``fn`` runs and returned when the consumer pops the result, so
    at most ``depth`` worker-held results (+ the one the consumer is using)
    are alive at once — for device placement, that many batches of device
    memory, including at ``depth=1`` (the round-3 queue-based bound kept
    one extra: a blocked put held a result the accounting missed,
    ADVICE r03).

    Spans into ``tracer`` (the flight ring alone without one), tagged
    ``seq`` (the item's index) and ``epoch`` where given: ``fetch`` around
    each pull from ``items`` and ``slot_wait`` around a permit that had to
    be waited for, both on the worker; ``feed_wait`` around the consumer's
    wait for each result. With the timeline enabled, and only then, a
    second daemon thread watches each result become ready (``h2d_ready``):
    the worker itself never blocks on a copy."""
    tracer = tracer or NULL_TIMELINE
    tags = {} if epoch is None else {"epoch": epoch}
    in_flight = threading.Semaphore(max(1, depth))
    q: queue_mod.Queue = queue_mod.Queue()  # unbounded; the semaphore bounds
    stop = threading.Event()
    # None while the timeline is off: no thread, nothing queued for one
    watcher = tracer.ready_watcher("h2d_ready", name + "-ready")

    def worker():
        try:
            for seq, item, size in _fetched(items, tracer, tags):
                if not in_flight.acquire(blocking=False):
                    with tracer.span("slot_wait", seq=seq, **tags):
                        # poll-acquire so a walked-away consumer (stop set)
                        # never leaves the worker blocked forever on a permit
                        while not in_flight.acquire(timeout=0.1):
                            if stop.is_set():
                                return
                if stop.is_set():
                    return
                result = fn(item)
                if watcher is not None:
                    watcher.watch(result, seq=seq, **tags, **size)
                q.put((item, result))
        except BaseException as exc:  # re-raised at the consumption point
            q.put(exc)
            return
        q.put(_DONE)

    threading.Thread(target=worker, daemon=True, name=name).start()
    try:
        # results arrive in order: the n-th received is the worker's seq n
        for seq in itertools.count():
            with tracer.span("feed_wait", seq=seq, **tags) as span:
                payload = q.get()
                if payload is _DONE:
                    span["end"] = True
            if payload is _DONE:
                return
            if isinstance(payload, BaseException):
                raise payload
            in_flight.release()  # the consumer owns this result now
            yield payload
    finally:
        stop.set()
        if watcher is not None:
            watcher.close()


# ---------------------------------------------------------------------------
# Step-pipeline placement scheduler (train/loop.py's epoch source)
# ---------------------------------------------------------------------------

#: Work-item kinds flowing through the pipeline: a plain per-step batch, or
#: a list of K same-shape batches destined for one fused dispatch
#: (steps_per_dispatch / grad_accum).
SINGLE = "single"
STACK = "stack"


def stacked_work(
    batches: Iterable[dict], stack_size: int, batch_size: int
) -> Iterator[Tuple[str, object]]:
    """Group an epoch's batch stream into pipeline work items.

    Only full, uniformly-shaped batches can stack into the scanned
    executable (their shapes must all match the compiled (K, B, ...)
    payload); a ragged batch flushes the partial group — each buffered
    batch re-emitted as a single, THEN the ragged one — and the epoch's
    trailing partial group drains the same way. This reproduces the
    trainer's historical inline buffering exactly, so the (K>1) loss
    sequence is bit-identical to the old loop's.

    ``stack_size <= 1`` degenerates to all-singles.
    """
    if stack_size <= 1:
        for b in batches:
            yield (SINGLE, b)
        return
    buffer: list = []
    for b in batches:
        if next(iter(b.values())).shape[0] == batch_size:
            buffer.append(b)
            if len(buffer) == stack_size:
                yield (STACK, buffer)
                buffer = []
        else:
            for q in buffer:
                yield (SINGLE, q)
            buffer = []
            yield (SINGLE, b)
    for q in buffer:
        yield (SINGLE, q)


def pipelined_placement(
    work: Iterable[Tuple[str, object]],
    place_fn: Callable[[str, object], object],
    depth: int = 2,
    tracer=None,
    epoch: Optional[int] = None,
    max_retries: int = 0,
    retry_backoff_s: float = 0.05,
    name: str = "dpt-prefetch",
) -> Iterator[Tuple[Tuple[str, object], object]]:
    """Yield ``(work_item, placed)`` with stacking + H2D placement running
    up to ``depth`` items ahead on the prefetch worker.

    ``place_fn(kind, payload)`` is the strategy's placement entry
    (Strategy.place_work): for a STACK item the K host batches are
    np.stack'ed here first — on the worker thread, off the step loop —
    then placed as one (K, B, ...) payload. ``depth <= 0`` places inline
    on the consumer thread (the synchronous baseline; still traced), as a
    generator so ``contextlib.closing`` works identically either way.

    Transient placement failures (OSError family — a flapping runtime
    channel — and the injected ``placement`` fault, coordinates
    ``(epoch, seq)``) retry with bounded exponential backoff before the
    worker surfaces them (utils/faults.py).

    The ``stack``/``h2d`` tracer spans recorded here are what make the
    overlap observable: their wall-clock windows interleave with the
    consumer's ``dispatch`` spans when the pipeline is actually ahead.
    ``seq`` counts the work items here exactly as :func:`bounded_prefetch`
    counts them, so with ``epoch`` it identifies one batch in every span
    from ``fetch`` to the consumer's ``dispatch``.
    """
    tracer = tracer or NULL_TIMELINE
    tags = {} if epoch is None else {"epoch": epoch}
    counter = {"n": 0}

    def place(item):
        kind, payload = item
        seq = counter["n"]
        counter["n"] += 1
        if kind == STACK:
            with tracer.span("stack", seq=seq, **tags):
                payload = {
                    key: np.stack([b[key] for b in payload])
                    for key in payload[0]
                }
        with tracer.span("h2d", seq=seq, kind=kind, **tags,
                         **_bytes_tag(payload)):
            return faults.call_with_retries(
                lambda: place_fn(kind, payload),
                site="placement",
                retries=max_retries,
                backoff_s=retry_backoff_s,
                epoch=epoch,
                step=seq,
                log=logger,
            )

    if depth <= 0:
        return _placed_inline(work, place, tracer, tags)
    return bounded_prefetch(work, place, depth=depth, name=name,
                            tracer=tracer, epoch=epoch)


def _placed_inline(work, place, tracer, tags: dict):
    """``depth <= 0``: fetch and placement on the consumer's own thread,
    under their own spans, so the wait for the feed is a ``feed_wait`` of
    no length."""
    for seq, item, _ in _fetched(work, tracer, tags):
        placed = place(item)
        now = time.perf_counter()
        tracer.record("feed_wait", now, now, seq=seq, **tags)
        yield item, placed


def bounded_submit(
    pool, fn: Callable[[T], R], items: Iterable[T], depth: int = 2
) -> Iterator[R]:
    """Yield ``fn(item)`` results in order, keeping up to ``depth`` futures
    in flight on ``pool``; abandoning the generator cancels queued work."""
    pending: collections.deque = collections.deque()
    it = iter(items)

    def submit_next() -> bool:
        try:
            item = next(it)
        except StopIteration:
            return False
        pending.append(pool.submit(fn, item))
        return True

    try:
        for _ in range(max(1, depth)):
            if not submit_next():
                break
        while pending:
            fut = pending.popleft()
            # refill BEFORE blocking on the result: the pool keeps `depth`
            # items genuinely in flight while the consumer waits
            submit_next()
            yield fut.result()
    finally:
        for fut in pending:
            fut.cancel()
