"""Look at one profiler trace by hand: which planes are devices, which
lines they carry, how XLA names and classes the operations.

    python3 benchmark/tools/look_at_trace.py <trace dir or .xplane.pb>
    python3 benchmark/tools/look_at_trace.py --gaps <trace dir or .xplane.pb>
"""

from __future__ import annotations

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(path: str):
    import jax

    import trace_reduce

    if os.path.isdir(path):
        path = trace_reduce.newest_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            total = collections.defaultdict(float)
            example = {}
            for e in events:
                total[e.name] += e.duration_ns * 1e-9
                example.setdefault(e.name, e)
            for name, secs in sorted(total.items(), key=lambda kv: -kv[1])[:12]:
                stats = {k: (v if not isinstance(v, str) else v[:60])
                         for k, v in dict(example[name].stats).items()}
                print(f"    {secs:10.6f}s {name[:70]!r} {stats}")


def gaps(path: str, least_s: float = 0.02):
    """Every idle gap of device 0 longer than ``least_s``: when, how long,
    whether it lies inside one run of a program or between two, and the
    harness's host spans that overlap it."""
    import trace_reduce

    if os.path.isdir(path):
        path = trace_reduce.newest_xplane(path)
    trace = trace_reduce.load(path)
    ops = trace["devices"][sorted(trace["devices"])[0]]
    t_first = ops[0].start
    cursor, last = ops[0].end, ops[0]
    for o in ops[1:]:
        if o.start - cursor >= least_s:
            inside = (last.program is not None and last.program == o.program)
            spans = [f"{s.name}[{s.start - t_first:.3f},{s.end - t_first:.3f}]"
                     for s in trace["host"]
                     if s.end > cursor and s.start < o.start]
            print(f"gap {cursor - t_first:9.3f}s +{o.start - cursor:.3f}s "
                  f"{'inside ' + str(last.program) if inside else 'between programs'}"
                  f" after {last.name[:40]!r} before {o.name[:40]!r}")
            print("     host: " + " ".join(spans[:12]))
        if o.end > cursor:
            cursor, last = o.end, o
    runs = trace_reduce.step_programs(ops)
    print("step programs:", len(runs), "ops each:",
          sorted({len(r) for r in runs}))
    print("step spans ms:", [round(1e3 * (max(o.end for o in r) -
                                          min(o.start for o in r)), 1)
                             for r in runs])


if __name__ == "__main__":
    if sys.argv[1] == "--gaps":
        gaps(sys.argv[2])
    else:
        main(sys.argv[1])
