"""dptlint: static distributed-correctness analysis.

Every distributed-correctness property in this repo used to be proven
only by *running* the program: a mis-scheduled ``ppermute`` in the 1F1B
tick program deadlocks the CPU collective rendezvous and is caught by a
300 s pytest-timeout, a silently-degenerated strategy is caught by
grepping optimized HLO, and a rank-divergent collective is caught only
when a real 2-process run hangs. Pipeline schedules and SPMD shard_map
programs have exactly the shape static verification handles well — the
collective sequence is fully determined at trace time — so this package
converts minutes of dynamic detection (or a burned chip window) into a
sub-minute abstract-eval pass.

Two layers, one CLI (``python -m distributedpytorch_tpu analyze``):

* ``analysis/collectives.py`` — the jaxpr collective checker: abstractly
  trace each strategy's train/eval step (no device execution), walk the
  closed jaxpr into ``shard_map``/``pjit``/``scan``/``cond`` subjaxprs,
  extract the ordered collective program, and verify axis binding,
  ppermute bijectivity + tick-program deadlock-freedom, SPMD rank
  uniformity, and each strategy's declared comms contract (the table
  ``tests/test_hlo_collectives.py`` cross-checks against optimized HLO).
* ``analysis/lint.py`` — a project-specific AST lint over the package
  source: nondeterminism under trace, donated-buffer use-after-donation,
  host-sync hazards in the step hot path, and collectives gated on
  ``process_index()`` Python conditionals.

Wired as the ``lint-distributed`` CI job ahead of tier-1 and as a launch
preflight in ``dist/elastic.py``. Rule catalog: docs/ANALYSIS.md.

This module stays import-light (no jax): ``Finding`` is shared by the
jax-tracing layer and the pure-AST layer, and jax-free callers (the
elastic supervisor) must be able to name rules without paying for a
backend import.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

#: Virtual CPU devices the collective layer's provisioned subprocess
#: needs (DDP_MP's 4×2 mesh). Single source for ``cli`` (self-provision
#: re-exec) and ``preflight`` (pre-provisioned subprocess) — if one
#: provisioned N and the other M, the sentinel would make ``cli.run``
#: trust the wrong mesh and fail as an rc-2 infra error, which the
#: launch preflight treats as "proceed": the gate would be silently
#: disabled.
MESH_DEVICES = 8

#: Env sentinel marking a process as already provisioned for the
#: analyzer; ``cli.main`` re-execs under ``utils/provision`` unless set.
PROVISIONED_SENTINEL = "DPT_ANALYZE_PROVISIONED"

#: Strategies the jaxpr collective checker covers, and the pipeline
#: schedules that apply to the MP ones. Defined here (not in
#: ``collectives``, which re-exports them as its defaults) so jax-free
#: callers — the elastic supervisor — can gate their
#: preflights on "is this a collective strategy the analyzer owns"
#: without paying for a backend import.
ANALYSIS_STRATEGIES = ("DP", "SP", "TP", "FSDP", "MP", "DDP_MP")
ANALYSIS_SCHEDULES = ("gpipe", "1f1b")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One diagnostic: a rule violation with an actionable one-line
    message and where it was found (a strategy/schedule combo for the
    collective layer, a ``file:line`` for the lint layer)."""

    rule: str
    where: str
    message: str
    layer: str  # "collectives" | "lint"
    count: int = 1  # identical findings collapsed (per-leaf ppermutes)

    @property
    def line(self) -> str:
        mult = f" [x{self.count}]" if self.count > 1 else ""
        return f"dptlint [{self.rule}] {self.where}: {self.message}{mult}"


def dedupe(findings) -> list:
    """Collapse identical (rule, where, message) findings — a tree-typed
    ppermute traces as one eqn per payload leaf per tick and would
    otherwise report the same flipped edge dozens of times."""
    order: list = []
    counts: dict = {}
    for f in findings:
        key = (f.rule, f.where, f.message, f.layer)
        if key in counts:
            counts[key] += 1
        else:
            counts[key] = 1
            order.append(key)
    return [
        Finding(rule=k[0], where=k[1], message=k[2], layer=k[3], count=counts[k])
        for k in order
    ]


class AnalysisEnvironmentError(RuntimeError):
    """The analyzer could not run (wrong device mesh, missing deps) — an
    infrastructure failure, NOT a finding: callers must never poison-mark
    a config or refuse a launch because the analyzer itself broke."""
