"""The preflight runner: "invoke the analyzer in a provisioned CPU
subprocess and parse its report".

The launch preflight of dist/elastic.py (rank launches) runs ``python -m
distributedpytorch_tpu analyze`` pinned to a virtual CPU mesh (never
dialing a TPU runtime), scoped to the collective layer for the given
strategy × schedule, and turns the JSON report into printable findings
lines. The module stays jax-free so the elastic supervisor can import
it.

Return contract: ``(rc, findings_lines)`` where rc is the analyzer's
exit code (0 clean / 1 findings / 2 infra) — a crashed or timed-out
subprocess reports rc 2. POLICY IS THE CALLER'S: the launch preflight
treats rc 2 as "proceed" (analyzer plumbing must never block a launch),
but that decision lives at the call site.
"""

from __future__ import annotations

import json
import subprocess
import sys
from typing import List, Mapping, Optional, Sequence, Tuple

from distributedpytorch_tpu.analysis import MESH_DEVICES, PROVISIONED_SENTINEL


def run_preflight(
    strategies: Sequence[str],
    schedules: Sequence[str],
    timeout: float,
    layer: str = "collectives",
    base_env: Optional[Mapping[str, str]] = None,
    cwd: Optional[str] = None,
    fingerprint_world: int = 0,
) -> Tuple[int, List[str]]:
    from distributedpytorch_tpu.utils.provision import provisioned_env

    env = provisioned_env(MESH_DEVICES, base=base_env)
    env[PROVISIONED_SENTINEL] = "1"
    cmd = [
        sys.executable, "-m", "distributedpytorch_tpu", "analyze",
        "--layer", layer, "--json", "-",
    ]
    if fingerprint_world and int(fingerprint_world) >= 2:
        # the multi-process desync gate: compare the ordered-collective
        # fingerprint under every simulated rank of the job's ACTUAL
        # world size (docs/ANALYSIS.md `collective-fingerprint`). The
        # fingerprint comparison covers ranks 0..N-1, so the dual-rank
        # (0 vs 1) re-trace is subsumed — skip it rather than pay two
        # redundant traces per combo inside the preflight's timeout.
        cmd += ["--fingerprint-world", str(int(fingerprint_world)),
                "--no-rank-check"]
    cmd += ["--strategies", *strategies]
    if schedules:
        cmd += ["--schedules", *schedules]
    try:
        proc = subprocess.run(
            cmd, env=env, capture_output=True, text=True,
            timeout=timeout, cwd=cwd,
        )
    except (subprocess.TimeoutExpired, OSError) as exc:
        return 2, [f"analyzer did not run: {type(exc).__name__}: {exc}"]
    findings: List[str] = []
    if proc.returncode == 1:
        try:
            report = json.loads(proc.stdout)
        except ValueError:
            # rc 1 WITHOUT any JSON report is not findings — it's a
            # crashed interpreter (import error, unhandled traceback;
            # Python itself exits 1 for both): an INFRA failure, which
            # must never refuse a launch
            detail = (proc.stderr or proc.stdout).strip()[-300:]
            return 2, [f"analyzer exited 1 without a report: {detail}"]
        try:
            findings = [
                f"[{f['rule']}] {f['where']}: {f['message']}"
                for f in report.get("findings", ())
            ]
        except Exception:  # noqa: BLE001 — version-skewed report shape
            # the analyzer DID run and reported findings; shape
            # surprises (findings as strings, a top-level null) degrade
            # to this line — rc 1 still refuses, just less specifically
            findings = ["analyzer reported findings but the JSON report "
                        "was unreadable"]
        if not findings:
            findings = ["analyzer reported findings but the report was "
                        "empty"]
    return proc.returncode, findings
