"""Follow the batches of one traced run of a training cell through the
feed, from the program's own spans (``feed_spans.py`` says which).

    python3 benchmark/tools/feed_timeline.py --workload <cell> --seed <n> --seconds <s>

The run is ``run.py``'s ``--trace 1`` run (the first seconds under the
profiler, the rest without it). One JSON line on standard output, the
spans themselves in ``chiprun_out/feed_<cell>_<seed>.json``:

``rest``        steps, images, seconds and images per second of the
                untraced rest, and the loop's wait as the harness timed it
``per_step_ms`` every phase's milliseconds per step of the rest
``accounted``   every second of the rest by what the feed's worker did in
                it: its spans (fetch, slot_wait, stack, h2d), the
                stretches between an epoch's worker and the next one's,
                when none is alive, the tail after the loop has left the
                feed, and what is left between a live worker's spans; all
                but the last as a share of the rest
``epoch``       the last whole epoch of the rest (the first ones refill a
                feed that the profiler's stop drained), one row a batch:
                milliseconds from the epoch's first fetch to the start and
                end of each of the batch's spans
``ready``       of the traced part, where the device's steps are known:
                each h2d_ready span's length and how long after the end of
                the device step before it the copy landed
"""

from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import feed_spans  # noqa: E402
import run as bench  # noqa: E402
import trace_reduce  # noqa: E402

WORKER = ("fetch", "slot_wait", "stack", "h2d")
PHASES = WORKER + ("h2d_ready", "feed_wait", "dispatch")


def accounted(run) -> dict:
    """Every second of the untraced rest, by what the feed's worker did in
    it: its spans; ``no_worker_alive``, from the span in which one worker
    finds its epoch's end (or from the rest's start) to the next one's
    first fetch; ``drain_tail``, from the last span to the rest's end: at
    its deadline the loop leaves the feed, which stops the worker, and
    waits for the steps in flight; ``between_spans``, the remainder, a
    live worker between two of its spans."""
    spans, w = feed_spans.in_rest(run, *WORKER)
    end = w["t0"] + w["seconds"]
    out = dict.fromkeys(
        WORKER + ("no_worker_alive", "drain_tail", "between_spans"), 0.0)
    cursor, alive = w["t0"], False
    for s in sorted(spans, key=lambda s: s["t0"]):
        if s["t0"] >= end:
            break
        out["between_spans" if alive else "no_worker_alive"] += max(
            0.0, s["t0"] - cursor)
        out[s["phase"]] += min(s["t1"], end) - s["t0"]
        cursor, alive = min(s["t1"], end), not s.get("end")
    out["drain_tail" if alive else "no_worker_alive"] += end - cursor
    out["share_of_rest"] = 1.0 - out["between_spans"] / w["seconds"]
    return out


def last_whole_epoch(run) -> list:
    """One row a batch of the last epoch that began and ended in the
    untraced rest: each span's start and end, in milliseconds from the
    epoch's first fetch."""
    spans, _ = feed_spans.in_rest(run, *PHASES)
    ends = {s["epoch"] for s in spans if s["phase"] == "feed_wait" and s.get("end")}
    begun = {s["epoch"] for s in spans if s["phase"] == "fetch" and s.get("seq") == 0}
    if not ends & begun:
        return []
    epoch = max(ends & begun)
    mine = [s for s in spans if s.get("epoch") == epoch]
    # the harness's own dispatch spans carry the step alone: the n-th
    # dispatch after the epoch's first handover is batch n
    t_first = min(s["t0"] for s in mine)
    handed = sorted(s["t1"] for s in mine
                    if s["phase"] == "feed_wait" and not s.get("end"))
    dispatch = sorted(s["t0"] for s in spans
                      if s["phase"] == "dispatch" and s["t0"] >= handed[0])
    rows = []
    for seq in sorted({s["seq"] for s in mine}):
        row = {"seq": seq}
        for s in mine:
            if s["seq"] == seq:
                row[s["phase"]] = [round(1e3 * (s["t0"] - t_first), 1),
                                   round(1e3 * (s["t1"] - t_first), 1)]
        if seq < len(handed) and seq < len(dispatch):
            row["dispatch_at"] = round(1e3 * (dispatch[seq] - t_first), 1)
        rows.append(row)
    return rows


def ready_against_device_steps(run) -> list:
    win = trace_reduce.traced_window(run)
    if win is None:
        return []
    shift = win[0] - run["window"]["traced"][0]
    step_ends = sorted(max(o.end for o in s) for s in trace_reduce.whole_steps(run))
    rows = []
    for s in run["spans"]:
        if s["phase"] != "h2d_ready" or not win[0] <= s["t1"] + shift <= win[1]:
            continue
        before = [e for e in step_ends if e <= s["t1"] + shift]
        rows.append({"epoch": s["epoch"], "seq": s["seq"],
                     "ready_ms": round(1e3 * (s["t1"] - s["t0"]), 1),
                     "after_step_end_ms": round(
                         1e3 * (s["t1"] + shift - before[-1]), 1) if before else None})
    return rows


def main(argv=None) -> int:
    args = bench.parser().parse_args(argv)
    args.trace = 1
    ctx, driver = bench.open_cell(args)
    run = driver.run(ctx)
    try:
        run["trace"] = trace_reduce.load(trace_reduce.newest_xplane(run["trace_dir"]))
    except FileNotFoundError:
        run["trace"] = None
    finally:
        shutil.rmtree(run["trace_dir"], ignore_errors=True)
    w = run["window"]["untraced"]
    out = {
        "cell": ctx.cell["name"], "seed": args.seed,
        "device": ctx.devices[0].device_kind, "correct": run["verdict"]["correct"],
        "rest": {**w, "imgs_per_s": w["images"] / w["seconds"]},
        "per_step_ms": {p: feed_spans.ms_per_step(run, p) for p in PHASES},
        "accounted": accounted(run),
        "epoch": last_whole_epoch(run),
        "ready": ready_against_device_steps(run),
    }
    os.makedirs(os.path.join(bench.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(bench.ROOT, "chiprun_out",
                           f"feed_{ctx.cell['name']}_{args.seed}.json"), "w") as f:
        json.dump({"window": run["window"], "spans": run["spans"]}, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
