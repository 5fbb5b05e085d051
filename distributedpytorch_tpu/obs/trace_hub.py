"""Trace hub: rank-tagged step-timeline spans → Perfetto/Chrome trace
JSON, merged across ranks.

``utils/trace.py`` records host-side phase spans (:data:`PHASES`) as
JSONL; this module turns those into the Chrome
trace-event format (``{"traceEvents": [...]}``) that Perfetto and
``chrome://tracing`` open directly, with one **process track per rank**
and one **thread track per phase** — so a 2-rank elastic run reads as
two aligned lanes of overlapping phase bars instead of two unrelated
JSONL files.

Cross-rank alignment: span ``t0``/``t1`` are ``time.perf_counter``
values (arbitrary per-process origin — the right clock *within* a
process); every span also carries a wall-clock stamp (``wall``, written
at record time), and the exporter anchors each span at
``wall − (t1 − t0)``. Wall clocks on one host are shared, so ranks of a
multi-process CPU/gloo job land on one comparable axis.

The elastic supervisor (``dist/elastic.py``) arms ``--trace-timeline``
per worker (rank 0 writes ``<path>``, rank R writes ``<path>.rankR``)
and calls :func:`write_merged_trace` over the attempt's files when the
job resolves. For device-side profiles, the trainer's
``--profile-steps N:M`` captures a ``jax.profiler`` trace over exactly
that step range (train/loop.py) and ties its clock to these spans'
``perf_counter`` with a ``dpt_sync`` annotation and a ``clock_sync``
event (utils/trace.py) — this module stays host-side and jax-free.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

logger = logging.getLogger(__name__)

_RANK_SUFFIX_RE = re.compile(r"\.rank(\d+)$")

#: The package's one list of step-pipeline phases, in pipeline order
#: (``utils/trace.py`` imports it; what each is: its module docstring).
#: Their positions are the stable thread-track ids; unknown phases get
#: ids after these, in first-seen order.
PHASES = ("decode", "fetch", "slot_wait", "stack", "h2d", "h2d_ready",
          "feed_wait", "dispatch", "readback")


def _load_events(path: str) -> List[dict]:
    # utils.trace.load_events without the import (obs stays standalone;
    # the format — JSONL of {"phase", "t0", "t1", ...} — is the contract)
    events: List[dict] = []
    try:
        with open(path) as f:
            for line in f:
                if not line.strip():
                    continue
                try:
                    d = json.loads(line)
                except ValueError:
                    continue  # torn tail line of a crashed writer
                if isinstance(d, dict) and "phase" in d:
                    events.append(d)
    except OSError:
        return []
    return events


def _phase_tid(phase: str, extra: Dict[str, int]) -> int:
    if phase in PHASES:
        return PHASES.index(phase)
    if phase not in extra:
        extra[phase] = len(PHASES) + len(extra)
    return extra[phase]


def _anchor_us(e: dict) -> Optional[float]:
    """Absolute start time of a span in µs (wall-anchored when the span
    carries a wall stamp; bare perf_counter otherwise)."""
    try:
        t0, t1 = float(e["t0"]), float(e["t1"])
    except (KeyError, TypeError, ValueError):
        return None
    wall = e.get("wall")
    if wall is not None:
        try:
            return (float(wall) - (t1 - t0)) * 1e6
        except (TypeError, ValueError):
            pass
    return t0 * 1e6


def trace_events_from_spans(
    spans: Iterable[dict], default_rank: int = 0,
) -> List[dict]:
    """Chrome 'X' (complete) events from step-timeline span dicts. Each
    span's rank tag (or ``default_rank``) becomes the pid/track."""
    out: List[dict] = []
    extra_tids: Dict[str, int] = {}
    for e in spans:
        ts = _anchor_us(e)
        if ts is None:
            continue
        dur = max(0.0, (float(e["t1"]) - float(e["t0"])) * 1e6)
        rank = int(e.get("rank", default_rank))
        phase = str(e["phase"])
        args = {
            k: v for k, v in e.items()
            if k not in ("phase", "t0", "t1", "wall", "rank")
        }
        out.append({
            "name": phase,
            "cat": "step",
            "ph": "X",
            "ts": round(ts, 3),
            "dur": round(dur, 3),
            "pid": rank,
            "tid": _phase_tid(phase, extra_tids),
            "args": args,
        })
    return out


def _metadata_events(ranks: Sequence[int], phases: Sequence[str],
                     process_label: str = "rank") -> List[dict]:
    meta: List[dict] = []
    extra_tids: Dict[str, int] = {}
    for rank in sorted(set(ranks)):
        meta.append({
            "ph": "M", "name": "process_name", "pid": rank, "tid": 0,
            "args": {"name": f"{process_label} {rank}"},
        })
        for phase in phases:
            meta.append({
                "ph": "M", "name": "thread_name", "pid": rank,
                "tid": _phase_tid(phase, extra_tids),
                "args": {"name": phase},
            })
    return meta


def build_trace(spans_by_rank: Dict[int, List[dict]],
                process_label: str = "rank") -> dict:
    """One Perfetto-loadable trace from per-rank span lists, events
    sorted by timestamp (Perfetto tolerates unsorted input; humans
    diffing the JSON do not). ``process_label`` names the per-process
    tracks — "rank" for training jobs, "worker" for the serve fleet's
    merged pane."""
    events: List[dict] = []
    phases: List[str] = []
    for rank, spans in sorted(spans_by_rank.items()):
        for e in spans:
            p = str(e.get("phase", ""))
            if p and p not in phases:
                phases.append(p)
        events.extend(trace_events_from_spans(spans, default_rank=rank))
    events.sort(key=lambda e: (e["ts"], e["pid"], e["tid"]))
    meta = _metadata_events(list(spans_by_rank), phases,
                            process_label=process_label)
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def timeline_rank_paths(base_path: str) -> List[Tuple[int, str]]:
    """The per-rank timeline files of one run: rank 0 writes
    ``<base>``, rank R writes ``<base>.rankR`` (utils/trace wiring in
    train/loop.py). Only files that exist are returned."""
    out: List[Tuple[int, str]] = []
    if os.path.exists(base_path):
        out.append((0, base_path))
    for path in sorted(glob.glob(f"{base_path}.rank*")):
        m = _RANK_SUFFIX_RE.search(path)
        if m:
            out.append((int(m.group(1)), path))
    return out


def merge_timelines(
    paths: Union[str, Sequence[Union[str, Tuple[int, str]]]],
    process_label: str = "rank",
) -> dict:
    """Merge timeline JSONL files into one trace. ``paths`` may be a
    base path (rank files discovered via :func:`timeline_rank_paths`),
    a list of paths (rank inferred from the ``.rankN`` suffix, the
    events' own rank tags, else 0), or explicit ``(rank, path)``
    pairs."""
    if isinstance(paths, str):
        pairs = timeline_rank_paths(paths)
    else:
        pairs = []
        for item in paths:
            if isinstance(item, tuple):
                pairs.append((int(item[0]), str(item[1])))
            else:
                m = _RANK_SUFFIX_RE.search(str(item))
                pairs.append((int(m.group(1)) if m else 0, str(item)))
    by_rank: Dict[int, List[dict]] = {}
    for rank, path in pairs:
        events = _load_events(path)
        for e in events:
            r = int(e.get("rank", rank))
            by_rank.setdefault(r, []).append(e)
    return build_trace(by_rank, process_label=process_label)


def write_merged_trace(
    paths: Union[str, Sequence[Union[str, Tuple[int, str]]]],
    out_path: str,
    process_label: str = "rank",
) -> Optional[str]:
    """Merge + write; returns ``out_path``, or None when no events were
    found (no empty artifacts). Never raises — callers are teardown
    paths (the elastic supervisor's report step)."""
    try:
        trace = merge_timelines(paths, process_label=process_label)
        if not any(e["ph"] == "X" for e in trace["traceEvents"]):
            return None
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        tmp = f"{out_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(trace, f)
        os.replace(tmp, out_path)
        return out_path
    except Exception:  # noqa: BLE001 — diagnostic artifact only
        logger.exception("merged-trace write failed")
        return None
