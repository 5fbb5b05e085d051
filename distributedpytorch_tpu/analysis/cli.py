"""``python -m distributedpytorch_tpu analyze`` — the dptverify driver.

Runs every static pass, prints one actionable line per finding, and
exits 0 (clean) / 1 (findings) / 2 (analyzer infrastructure failure —
callers must NOT treat this as a finding). ``--json`` writes the
machine-readable report (``-`` = stdout), which the CI job uploads as
an artifact on failure and the elastic launch preflight parses;
``--sarif`` additionally projects the findings into SARIF 2.1.0 for
CI PR-diff annotation (the JSON report stays canonical).

The passes ride the two coarse layers:

* ``--layer collectives`` (jax, trace-only): the train AND eval comms
  contracts per strategy × schedule (dropped eval psum = finding), the
  serve-variant collective-freedom checks (float/int8/pallas forwards
  must trace with zero collectives), and the donation-safety pass
  (every serve variant lowered through ``serve/engine.serve_jit`` must
  be donation-free at the intent and aliasing tiers).
* ``--layer lint`` (pure AST + pure Python, jax-free): the source
  lint — including suppression hygiene (unknown/stale ``dptlint:
  disable`` comments are themselves findings).
* The control-plane protocol explorer (``analysis/protocol.py`` —
  router HA arbitration, rollout canary machine, experiment/capacity
  interleavings, fleet rank selection, model-checked exhaustively in
  milliseconds) is jax-free and runs under EVERY layer selection, so
  both launch preflights and the cold CI lint job get it for free.

Self-provisioning: the collective layer traces pipeline strategies over
an 8-device virtual CPU mesh, and jax backends initialize once per
process — so unless this process was already provisioned (the
``DPT_ANALYZE_PROVISIONED`` sentinel), the CLI exec-replaces itself via
``utils/provision.reexec_provisioned_cmd``: pinned to CPU, zero chip
involvement no matter where it's invoked from (laptop, CI, a
session whose parent holds the chip).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import List, Optional, Sequence

from distributedpytorch_tpu.analysis import (
    ANALYSIS_SCHEDULES,
    ANALYSIS_STRATEGIES,
    MESH_DEVICES,
    PROVISIONED_SENTINEL as _SENTINEL,
)

EXIT_CLEAN = 0
EXIT_FINDINGS = 1
EXIT_INFRA = 2


def _fingerprint_world(text: str) -> int:
    """0 (off) or >= 2 simulated ranks — a world of 1 has nothing to
    compare, and silently skipping the desync gate while reporting clean
    is exactly the false confidence the gate exists to prevent."""
    n = int(text)
    if n != 0 and n < 2:
        raise argparse.ArgumentTypeError(
            f"--fingerprint-world needs 0 (off) or >= 2 simulated ranks "
            f"to compare, got {n}"
        )
    return n


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m distributedpytorch_tpu analyze",
        description="dptlint: static distributed-correctness analysis "
        "(jaxpr collective checker + SPMD source lint). See "
        "docs/ANALYSIS.md for the rule catalog.",
    )
    ap.add_argument("--strategies", nargs="+",
                    default=list(ANALYSIS_STRATEGIES),
                    help="Strategies to trace (default: all analyzed "
                         "strategies)")
    ap.add_argument("--mesh", nargs="+", default=[], metavar="SPEC",
                    help="Mesh-config specs (DxMxS[@fsdp|sp], parallel/"
                         "mesh.py) to analyze IN ADDITION to "
                         "--strategies — the preflight surface for "
                         "``-t 4x1x2``-style mesh launches; specs with "
                         "a stage axis trace both --schedules and their "
                         "comms contract derives from the sharding "
                         "rules")
    ap.add_argument("--schedules", nargs="+",
                    default=list(ANALYSIS_SCHEDULES),
                    choices=["gpipe", "1f1b"],
                    help="Pipeline schedules for MP/DDP_MP (and "
                         "stage-axis mesh spec) combos")
    ap.add_argument("--layer", choices=["all", "collectives", "lint"],
                    default="all", help="Which analysis layer(s) to run")
    ap.add_argument("--hlo", action="store_true",
                    help="Also verify the optimized-HLO comms contract "
                         "(AOT CPU compile per combo; slower, still zero "
                         "execution)")
    ap.add_argument("--fingerprint-world", type=_fingerprint_world,
                    default=0, metavar="N",
                    help="Trace each combo's train step under N "
                         "simulated process identities and compare the "
                         "ordered-collective fingerprints (the "
                         "multi-process launch preflight's gloo-desync "
                         "gate — catches collectives gated on ranks the "
                         "dual-rank re-trace never simulates); "
                         "0 = off, needs N >= 2")
    ap.add_argument("--fingerprint-snapshot", default=None,
                    choices=["write", "check"],
                    help="Persist ('write') or verify ('check') the "
                         "per-combo ordered-collective fingerprints at "
                         "--snapshot-path: write before a jax upgrade, "
                         "check after — drifted combos flag as rule "
                         "fingerprint-snapshot with both toolchain "
                         "versions named (hybrid --mesh specs are "
                         "fingerprinted too); requires the collectives "
                         "layer")
    ap.add_argument("--snapshot-path", default="dpt_fingerprints.json",
                    metavar="PATH",
                    help="Fingerprint snapshot artifact for "
                         "--fingerprint-snapshot (default: "
                         "dpt_fingerprints.json)")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="Check a saved dpt_plan for staleness: re-trace "
                         "every fingerprinted point and flag rows whose "
                         "ordered-collective fingerprint no longer "
                         "matches the current trace (rule stale-plan — "
                         "a drifted plan ranks legs from a program that "
                         "no longer exists); requires the collectives "
                         "layer")
    ap.add_argument("--no-rank-check", action="store_true",
                    help="Skip the simulated-rank re-trace (halves trace "
                         "count; the dual-rank check is what catches "
                         "process_index()-gated collectives at the jaxpr "
                         "level)")
    ap.add_argument("--lint-root", default=None,
                    help="Directory tree for the AST lint (default: the "
                         "installed distributedpytorch_tpu package)")
    ap.add_argument("--json", dest="json_path", default=None,
                    metavar="PATH",
                    help="Write the JSON report here ('-' = stdout; "
                         "findings lines then go to stderr)")
    ap.add_argument("--sarif", dest="sarif_path", default=None,
                    metavar="PATH",
                    help="Also write the findings as SARIF 2.1.0 (for "
                         "CI PR-diff annotation via code-scanning "
                         "upload); the JSON report stays canonical")
    return ap


def run(argv: Optional[Sequence[str]] = None) -> int:
    """The provisioned body: parse, analyze, report."""
    args = build_parser().parse_args(argv)
    if args.fingerprint_world >= 2 and args.layer == "lint":
        # the desync gate lives in the collectives layer; silently
        # skipping a gate the operator explicitly asked for is the
        # false confidence _fingerprint_world exists to prevent
        print("analyze: --fingerprint-world requires the collectives "
              "layer (--layer all|collectives)", file=sys.stderr)
        return EXIT_INFRA
    if args.plan and args.layer == "lint":
        # same contract: the stale-plan re-trace IS a collectives-layer
        # check — skipping it silently would report a drifted plan clean
        print("analyze: --plan requires the collectives layer "
              "(--layer all|collectives)", file=sys.stderr)
        return EXIT_INFRA
    if args.fingerprint_snapshot and args.layer == "lint":
        # same contract again: snapshot write/check trace programs
        print("analyze: --fingerprint-snapshot requires the collectives "
              "layer (--layer all|collectives)", file=sys.stderr)
        return EXIT_INFRA
    t0 = time.monotonic()
    findings: List = []
    combos: List[str] = []
    fingerprints: dict = {}
    serve_variants: List[str] = []
    lint_files = 0
    try:
        if args.layer in ("all", "collectives"):
            from distributedpytorch_tpu.analysis import collectives
            from distributedpytorch_tpu.parallel.mesh import parse_mesh_spec

            for spec in args.mesh:
                try:
                    parse_mesh_spec(spec)  # refuse malformed specs loudly
                except ValueError as exc:
                    # bad invocation, caught BEFORE any combo traces —
                    # a clear message, and no other combo's findings
                    # are ever at stake (unbuildable-but-parseable
                    # specs degrade per combo to a mesh-config finding
                    # inside analyze_combo)
                    print(f"analyze: --mesh {exc}", file=sys.stderr)
                    return EXIT_INFRA
            # order-preserving dedup across (and within) both lists: a
            # repeated method must not trace (and fingerprint) twice —
            # the planner gets this for free from its point de-dup
            strategies = list(
                dict.fromkeys(list(args.strategies) + list(args.mesh))
            )
            cfindings, combos = collectives.analyze(
                strategies=strategies,
                schedules=args.schedules,
                hlo=args.hlo,
                rank_check=not args.no_rank_check,
            )
            findings += cfindings
            if args.fingerprint_world >= 2:
                ffindings, fingerprints = collectives.fingerprint_combos(
                    strategies=strategies,
                    schedules=args.schedules,
                    world=args.fingerprint_world,
                )
                findings += ffindings
            if args.fingerprint_snapshot == "write":
                payload = collectives.write_fingerprint_snapshot(
                    args.snapshot_path,
                    strategies=strategies,
                    schedules=args.schedules,
                )
                print(
                    f"analyze: wrote "
                    f"{len(payload['fingerprints'])} fingerprint(s) "
                    f"(jax {payload['jax']}) to {args.snapshot_path}",
                    file=sys.stderr,
                )
            elif args.fingerprint_snapshot == "check":
                payload = collectives.load_fingerprint_snapshot(
                    args.snapshot_path
                )
                if payload is None:
                    # a missing/corrupt/version-skewed snapshot is a bad
                    # invocation, not a clean check
                    print(f"analyze: --snapshot-path "
                          f"{args.snapshot_path}: not a readable "
                          f"fingerprint snapshot", file=sys.stderr)
                    return EXIT_INFRA
                findings += collectives.check_fingerprint_snapshot(
                    payload
                )
            if args.plan:
                from distributedpytorch_tpu.analysis.planner import (
                    check_plan_staleness,
                    load_plan,
                )

                payload = load_plan(args.plan)
                if payload is None:
                    # a missing/corrupt/version-skewed plan is a bad
                    # invocation, not a clean plan
                    print(f"analyze: --plan {args.plan}: not a readable "
                          f"dpt_plan artifact", file=sys.stderr)
                    return EXIT_INFRA
                findings += check_plan_staleness(payload)
            # serve contracts ride the collectives layer: the traced
            # forwards must be collective-free (and under --hlo the
            # compiled ones too), and every variant must lower
            # donation-free through the engine's one jit wrapper
            sfindings, serve_variants = collectives.analyze_serve(
                hlo=args.hlo
            )
            findings += sfindings
            from distributedpytorch_tpu.analysis import donation

            dfindings, _dtags = donation.analyze_donation()
            findings += dfindings
        if args.layer in ("all", "lint"):
            from distributedpytorch_tpu.analysis import lint

            lfindings, lint_files = lint.lint_package(args.lint_root)
            findings += lfindings
        # the control-plane protocol explorer is jax-free and runs in
        # milliseconds — EVERY layer selection gets it, so the elastic
        # supervisor's collectives-layer preflight and the cold CI lint
        # job both refuse a broken arbitration/rollout/fleet rule
        from distributedpytorch_tpu.analysis import protocol

        findings += protocol.analyze_protocols()
    except Exception as exc:  # noqa: BLE001 — infra failure, distinct rc
        print(f"analyze: infrastructure failure: {type(exc).__name__}: "
              f"{exc}", file=sys.stderr)
        return EXIT_INFRA

    report = {
        "clean": not findings,
        "findings": [dataclasses.asdict(f) for f in findings],
        "combos": combos,
        "fingerprints": fingerprints,
        "serve_variants": serve_variants,
        "protocol": True,
        "lint_files": lint_files,
        "hlo": bool(args.hlo),
        "plan": args.plan,
        "fingerprint_snapshot": args.fingerprint_snapshot,
        "duration_s": round(time.monotonic() - t0, 2),
    }
    out = sys.stderr if args.json_path == "-" else sys.stdout
    for f in findings:
        print(f.line, file=out)
    print(
        f"analyze: {len(findings)} finding(s) over "
        f"{len(combos)} combo(s) + {len(serve_variants)} serve "
        f"variant trace(s) + {lint_files} linted file(s) + the "
        f"protocol explorer in {report['duration_s']}s",
        file=out,
    )
    if args.json_path == "-":
        json.dump(report, sys.stdout, indent=2)
        print()
    elif args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(report, f, indent=2)
    if args.sarif_path:
        from distributedpytorch_tpu.analysis.sarif import write_sarif

        write_sarif(args.sarif_path, findings)
    return EXIT_FINDINGS if findings else EXIT_CLEAN


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[2:] if argv is None else argv)
    if os.environ.get(_SENTINEL) == "1":
        return run(argv)
    from distributedpytorch_tpu.utils.provision import reexec_provisioned_cmd

    # exec-replace, not a child process: the PID CI's `timeout` holds IS
    # the provisioned analyzer, so a timeout kill leaves no orphan still
    # writing the JSON report while the artifact step uploads it
    reexec_provisioned_cmd(
        MESH_DEVICES, _SENTINEL,
        [sys.executable, "-u", "-m", "distributedpytorch_tpu", "analyze",
         *argv],
    )


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
