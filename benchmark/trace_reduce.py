"""From a profiler trace to plain numbers.

``load(path)`` reads an ``.xplane.pb`` with nothing but JAX and returns
``{"devices": {id: [Op, ...]}, "host": [Span, ...], "sync": ...}``:
device operations (XLA's own instruction names, classed by opcode and
fusion kind; the program names nothing) and the host annotations the harness wrote, all on the
profiler's clock in seconds. Every reduction below is a pure function of
those lists, so it can be checked on a small trace written by hand.
"""

from __future__ import annotations

import glob
import os
import re
from collections import namedtuple

Op = namedtuple("Op", "name category start end program")
Span = namedtuple("Span", "name start end")

#: What carries the model's convolutions: XLA's own, and every custom call
#: (a ``tpu_custom_call`` is a Pallas kernel put in a convolution's place;
#: one that carries no convolution lowers ``conv_roofline``, never raises it).
CONV_CATEGORIES = ("convolution", "convolution fusion", "custom-call")
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "allreduce")


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_HLO = re.compile(r"^%(?P<op>[^ ]+) = (?P<type>.*?) (?P<code>[a-z][a-z\-]*)\(")


def parse_op(text: str):
    """(short name, category) of one event of the 'XLA Ops' line, whose
    name is the instruction's HLO text:
    ``%fusion.19 = bf16[3,3,512,256]{...} fusion(...), kind=kOutput, ...``.

    The category is the opcode, and for a fusion its kind. On a TPU an
    output fusion (``kind=kOutput``) is a convolution or a dot with its
    elementwise neighbours fused in: these models have no dot outside
    their convolutions, so it is called ``convolution fusion`` here, as the
    profiler's own tools call it. The short name keeps the result's type
    without its layout: ``fusion.19 bf16[3,3,512,256]`` is a weight
    gradient by its shape."""
    m = _HLO.match(text)
    if not m:
        return text.lstrip("%")[:80], "unknown"
    code = m.group("code")
    if code == "fusion":
        kind = re.search(r"kind=k(\w+)", text)
        kind = kind.group(1).lower() if kind else "other"
        code = "convolution fusion" if kind == "output" else kind + " fusion"
    shape = re.sub(r"\{[^}]*\}", "", m.group("type"))
    return f"{m.group('op')} {shape[:60]}", code


def load(path: str, op_line: str = "XLA Ops", module_line: str = "XLA Modules"):
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host, sync = {}, [], None
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == module_line:
                    modules = [(e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, e.name)
                               for e in line.events]
                if line.name != op_line:
                    continue
                for e in line.events:
                    name, category = parse_op(e.name)
                    ops.append(Op(name, category, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9, None))
            ops.sort(key=lambda o: o.start)
            devices[int(m.group(1))] = attach_programs(ops, sorted(modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench_"):
                        span = Span(e.name[len("bench_"):], e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9)
                        if span.name == "sync":
                            sync = (span.start, dict(e.stats).get("pc_ns"))
                        else:
                            host.append(span)
    host.sort(key=lambda s: s.start)
    return {"devices": devices, "host": host, "sync": sync}


def attach_programs(ops, modules):
    """Give each operation the index of the program run (one entry of the
    'XLA Modules' line) that it lies in."""
    out, j = [], 0
    for op in ops:
        while j < len(modules) and modules[j][1] <= op.start:
            j += 1
        inside = j < len(modules) and modules[j][0] <= op.start
        out.append(op._replace(program=(j, modules[j][2]) if inside else None))
    return out


# --- pure reductions ------------------------------------------------------

def union_seconds(intervals) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(ops, t0, t1):
    return [o._replace(start=max(o.start, t0), end=min(o.end, t1))
            for o in ops if o.end > t0 and o.start < t1]


def busy_seconds(ops, t0, t1) -> float:
    return union_seconds([(o.start, o.end) for o in clip(ops, t0, t1)])


def is_conv(op) -> bool:
    return op.category in CONV_CATEGORIES


def is_collective(op) -> bool:
    text = (op.category + " " + op.name).lower()
    return any(w in text for w in COLLECTIVE_WORDS)


def category_seconds(ops, pred) -> float:
    return sum(o.end - o.start for o in ops if pred(o))


def async_intervals(ops):
    """The chosen operations as intervals, an asynchronous pair made one:
    ``x-start`` opens what the next ``x-done`` of the same kind closes
    (XLA's ``all-reduce-start`` / ``all-reduce-done``), and the time
    between the two belongs to the collective, which is then in flight."""
    out, open_ = [], {}
    for o in sorted(ops, key=lambda o: o.start):
        kind = o.category
        if kind.endswith("-start"):
            open_.setdefault(kind[: -len("-start")], []).append(o.start)
        elif kind.endswith("-done") and open_.get(kind[: -len("-done")]):
            out.append((open_[kind[: -len("-done")]].pop(0), o.end))
        else:
            out.append((o.start, o.end))
    out.extend((s, s) for starts in open_.values() for s in starts)
    return out


def exposed_seconds(ops, pred) -> float:
    """Seconds of the operations chosen by ``pred`` (an asynchronous pair
    counted from its start to its done) during which no other operation
    runs on the same device."""
    mine = async_intervals([o for o in ops if pred(o)])
    others = [(o.start, o.end) for o in ops if not pred(o)]
    both = union_seconds(mine + others)
    return both - union_seconds(others)


def step_programs(ops, min_ops: int = 50):
    """The runs of the step program: program runs with many operations
    (a host-to-device copy or an eager op has a handful)."""
    runs = {}
    for o in ops:
        if o.program is not None:
            runs.setdefault(o.program, []).append(o)
    return [v for _, v in sorted(runs.items()) if len(v) >= min_ops]


def median(values):
    values = sorted(values)
    n = len(values)
    if not n:
        return None
    return values[n // 2] if n % 2 else 0.5 * (values[n // 2 - 1] + values[n // 2])


def top_ops(ops, n: int = 10, categories: int = 4):
    """[[name, seconds], ...]: the ``categories`` categories with most
    time (``all <category>``), then the single operations with most, each
    summed over its runs."""
    by_cat, by_op = {}, {}
    for o in ops:
        d = o.end - o.start
        by_cat["all " + o.category] = by_cat.get("all " + o.category, 0.0) + d
        key = f"{o.name} [{o.category}]"
        by_op[key] = by_op.get(key, 0.0) + d
    rank = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                            key=lambda kv: -kv[1])
    cats = rank(by_cat)[:categories]
    return cats + rank(by_op)[: n - len(cats)]


def idle_gaps(ops, host_spans, t0, t1, n: int = 10):
    """The longest idle gaps of a device inside [t0, t1], each labelled by
    the host span open for most of it (else ``host_other``)."""
    gaps, cursor = [], t0
    for o in sorted(clip(ops, t0, t1), key=lambda o: o.start):
        if o.start > cursor:
            gaps.append((cursor, o.start))
        cursor = max(cursor, o.end)
    if cursor < t1:
        gaps.append((cursor, t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        cover = {}
        for sp in host_spans:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov > 0:
                cover[sp.name] = cover.get(sp.name, 0.0) + ov
        label = max(cover, key=cover.get) if cover else "host_other"
        if cover and cover[label] < 0.5 * (e - s):
            label = "host_other"
        out.append([label, e - s])
    return out


# --- the traced window of a run --------------------------------------------

def traced_window(run):
    """(t0, t1) of the traced part of the window on the profiler's clock,
    from the ``bench_sync`` annotation that carries the host's
    ``perf_counter`` reading; None where nothing was traced."""
    trace, traced = run.get("trace"), run["window"].get("traced")
    if not trace or not traced or not trace.get("sync") or not trace["devices"]:
        return None
    at, pc_ns = trace["sync"]
    if pc_ns is None:
        return None
    shift = at - float(pc_ns) * 1e-9
    return traced[0] + shift, traced[1] + shift


def device_ops(run, device=None):
    """The operations of one device (the first by default) inside the
    traced window, or None."""
    win = traced_window(run)
    if win is None:
        return None
    devs = run["trace"]["devices"]
    key = sorted(devs)[0] if device is None else device
    return clip(devs[key], *win)


def median_step_seconds(run, device=None):
    """Device time from the first to the last operation of one run of the
    step program, median over the runs wholly inside the traced window;
    None where there is none."""
    return median([max(o.end for o in s) - min(o.start for o in s)
                   for s in whole_steps(run, device)])


def whole_steps(run, device=None):
    """The step-program runs that lie wholly inside the traced window (a
    run that the window cuts is left out, not kept in part)."""
    win = traced_window(run)
    if win is None:
        return []
    devs = run["trace"]["devices"]
    key = sorted(devs)[0] if device is None else device
    return [s for s in step_programs(devs[key])
            if min(o.start for o in s) >= win[0]
            and max(o.end for o in s) <= win[1]]
