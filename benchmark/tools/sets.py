"""Two sets of runs of one cell with the same seeds in both, as the
contract measures a bound: for each end-to-end metric the spread of each
set (the distance between the first and the third quartile, as
``statistics.quantiles(values, n=4)`` gives them, as a share of the
median), the wider of the two, and the second set's median against the
first's. Each run is a process of its own; this one never touches JAX.

    python3 benchmark/tools/sets.py --workload <cell> --seeds 11 12 13 14 15 16 \
        [--seconds N] [--traced-seeds 21 22 23]

One JSON line per run and a last line of spreads on standard output and in
``chiprun_out/sets_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def one_run(command, cell, seed, seconds, trace):
    t0 = time.time()
    proc = subprocess.Popen(
        command + ["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
        cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        proc.stdout, proc.stderr = proc.communicate()
    finally:
        # a run left behind holds the chip: where this process is ended
        # (``main`` turns SIGTERM into SystemExit), so is its run
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    wall = time.time() - t0
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        result = None
    return {"cell": cell, "seed": seed, "trace": trace, "rc": proc.returncode,
            "wall_s": wall, "result": result,
            "stderr_tail": None if result else proc.stderr[-1500:]}


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--traced-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--sets", type=int, default=2)
    args = ap.parse_args()
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(124))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out",
                            f"sets_{args.workload}.jsonl"), "a")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    sets = []
    for s in range(args.sets):
        runs = []
        for seed in args.seeds:
            row = one_run(bench["command"], args.workload, seed, seconds, 0)
            row["set"] = s + 1
            emit(row)
            runs.append(row)
        sets.append(runs)
    for seed in args.traced_seeds:
        emit(one_run(bench["command"], args.workload, seed, seconds, 1))

    summary = {"cell": args.workload, "seconds": seconds, "metrics": {}}
    for m in bench["end_to_end"]:
        per_set = []
        for runs in sets:
            values = [r["result"]["metrics"][m["name"]]["value"] for r in runs
                      if r["result"] and m["name"] in r["result"]["metrics"]]
            if len(values) >= 2:
                per_set.append({"median": statistics.median(values),
                                "spread": spread(values), "values": values})
        if per_set:
            summary["metrics"][m["name"]] = {
                "sets": per_set,
                "widest_spread": max(s["spread"] for s in per_set),
                "second_median_over_first": (
                    per_set[1]["median"] / per_set[0]["median"]
                    if len(per_set) > 1 else None)}
    summary["all_correct"] = all(
        r["result"] and r["result"]["correct"] for runs in sets for r in runs)
    emit(summary)
    log.close()
    return 0 if summary["all_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
