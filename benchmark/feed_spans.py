"""The feed's spans of a run, for the per-layer readers of the input
layer (``layer_metrics/feed_*.py`` and their neighbours).

The program's ``StepTimeline`` (``utils/trace.py``) records where the
feed's time goes: ``fetch``, ``slot_wait``, ``stack`` and ``h2d`` on the
prefetch worker, ``h2d_ready`` on its watcher, ``feed_wait`` on the step
loop's thread, every span of one batch under the same ``(epoch, seq)``.
The readers take the spans that began in the untraced rest of a
``--trace 1`` run's window (the profiler holds the feed back while it
runs), as ``h2d_ms`` does, over its steps. A program that records no such
span (the parent of the PR that brought them) gives every reader nothing
to read: ``None``, never 0.
"""


def in_rest(run, *phases):
    """(spans of ``phases`` that began in the untraced rest of the
    window, that rest) or ``([], None)`` where the run has no rest or no
    step in it."""
    w = run["window"].get("untraced")
    if not w or not w["steps"]:
        return [], None
    return [s for s in run["spans"]
            if s["phase"] in phases and s["t0"] >= w["t0"]], w


def seconds(spans) -> float:
    return sum(s["t1"] - s["t0"] for s in spans)


def ms_per_step(run, *phases):
    """Milliseconds in ``phases`` per step of the untraced rest; ``None``
    where that rest holds no span of the first of them: the program does
    not record it."""
    spans, w = in_rest(run, *phases)
    if not any(s["phase"] == phases[0] for s in spans):
        return None
    return 1e3 * seconds(spans) / w["steps"]
