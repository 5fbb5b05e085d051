"""The readings a cell's limits are set from, taken in one process on the
chip at the cell's own size (contract, "How ``correct`` is decided", 3-5).

    python3 benchmark/tools/readings.py --workload <cell> \
        --seeds 101 102 ... --control-seeds 3 --fault-seeds 3

For every seed: one Trainer driven from the seed through the compared
steps by the window's own call and feed (``drivers/train.prepare``), its
state freed, then the plain reference over the same rows: the LOWER
readings. For the first ``--control-seeds`` seeds also the control (the
reference in fp8 put in the program's place) and for the first
``--fault-seeds`` the faults planted in the reference put in its place
(half of the batch left out; on several chips the exchange left out, which
is one chip's share of the rows; a state left unchanged reads 1 by the
measure and needs no run): the UPPER readings. One JSON line per seed on
standard output and in ``chiprun_out/readings_<cell>.jsonl``.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402


def main():
    ap = bench_run.parser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--controls", nargs="+", default=["fp8"],
                    help="precisions of the control")
    ap.add_argument("--leaves", action="store_true",
                    help="keep every leaf's two norms in the line")
    args = ap.parse_args(
        ["--seed", "0", "--seconds", "0"] + sys.argv[1:])
    ctx, driver = bench_run.open_cell(args)
    import check
    from distributedpytorch_tpu.utils.trace import StepTimeline

    out_dir = os.path.join(bench_run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"readings_{ctx.cell['name']}.jsonl"), "a")
    annotate = lambda name, **kw: contextlib.nullcontext()  # noqa: E731
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        session = driver.prepare(ctx, seed, StepTimeline(enabled=False),
                                 annotate, whole_epoch=False)
        prog = session.prog
        driver.release(session)
        t1 = time.perf_counter()
        ref = driver.follow(ctx, session)
        t2 = time.perf_counter()
        r = check.readings(prog, ref)
        row = {"cell": ctx.cell["name"], "seed": seed,
               "program": r["numbers"], "where": r["where"],
               "program_s": t1 - t0, "reference_s": t2 - t1,
               "losses": prog["losses"], "ref_losses": ref["losses"]}
        norms = {"reference": ref, "program": prog}
        if i < args.control_seeds:
            for mode in args.controls:
                norms["control_" + mode] = driver.follow(ctx, session, mode=mode)
        if i < args.fault_seeds:
            norms["fault_half_batch"] = driver.follow(ctx, session, keep=0.5)
            if len(ctx.devices) > 1:
                norms["fault_no_exchange"] = driver.follow(
                    ctx, session, keep=1.0 / len(ctx.devices))
        for name, other in norms.items():
            if name not in ("reference", "program"):
                row[name] = check.readings(other, ref)["numbers"]
        if args.leaves:
            # every leaf's two norms, of each side that was run
            row["leaves"] = {
                name: {what: other[what] for what in ("grad_norms", "change_norms")}
                for name, other in norms.items()}
        row["total_s"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()
        del session, prog, ref
    log.close()


if __name__ == "__main__":
    main()
