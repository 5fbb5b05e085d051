"""dptlint (distributedpytorch_tpu/analysis): mutation tests pinning the
analyzer's teeth, clean-tree passes, and the AST lint rules.

The load-bearing contract (ISSUE 5 acceptance): each seeded mutation —
a flipped 1F1B phase-B ppermute edge, a dropped DDP grad psum, a psum
guarded by a ``process_index()==0`` branch — must be flagged with an
actionable one-line diagnostic, in under 60 s, with ZERO device
execution (the ``no_compile`` fixture makes any XLA compile raise), and
the clean tree must pass every rule for every strategy × schedule combo.
"""

import json
import os
import time

import jax
import pytest

import distributedpytorch_tpu.parallel.pipeline as pipeline
from distributedpytorch_tpu.analysis import Finding, dedupe
from distributedpytorch_tpu.analysis import collectives, lint
from distributedpytorch_tpu.analysis.cli import run as analyze_cli_run

MUTATION_BUDGET_S = 60.0


@pytest.fixture
def no_compile(monkeypatch):
    """Prove zero device execution: the analyzer's trace-only path must
    never reach XLA compilation (compilation is the doorway to running
    collectives); any AOT compile during the test raises."""

    def boom(self, *a, **k):
        raise AssertionError(
            "analyzer compiled an executable during a trace-only check"
        )

    monkeypatch.setattr(jax.stages.Lowered, "compile", boom)


# ---------------------------------------------------------------------------
class TestExtraction:
    def test_1f1b_program_extracted_with_attribution(self):
        colls = collectives.extract_collectives(
            collectives.trace_train("MP", "1f1b")
        )
        pp = [c for c in colls if c.kind == "ppermute"]
        ps = [c for c in colls if c.kind == "psum"]
        assert pp and ps
        # every ppermute sits under the shard_map with 'stage' bound
        assert all("stage" in c.bound_axes for c in pp)
        # the explicit schedule's conds attribute producers AND consumers
        assert all(c.producer_stage is not None for c in pp)
        assert all(c.consumer_stages for c in pp)
        # the schedule-closing grad psum feeds the step outputs
        assert any(c.direct_output for c in ps)

    def test_gspmd_strategy_has_empty_jaxpr_program(self):
        # DP's collectives are GSPMD-inserted at compile time: the traced
        # program contains none — which is exactly why its contract lives
        # in the HLO tier
        assert collectives.extract_collectives(
            collectives.trace_train("DP")) == []


# ---------------------------------------------------------------------------
class TestCleanTree:
    def test_every_strategy_schedule_combo_passes(self, no_compile):
        findings, tags = collectives.analyze()
        assert findings == [], "\n".join(f.line for f in findings)
        assert set(tags) == {
            "DP", "SP", "TP", "FSDP", "MP/gpipe", "MP/1f1b",
            "DDP_MP/gpipe", "DDP_MP/1f1b",
        }

    def test_package_source_is_lint_clean(self):
        findings, n_files = lint.lint_package()
        assert n_files > 30  # the whole package was actually walked
        assert findings == [], "\n".join(f.line for f in findings)


# ---------------------------------------------------------------------------
class TestSeededMutations:
    """The three ISSUE-5 mutations, each: flagged, actionable, <60 s,
    no device execution."""

    def test_flipped_1f1b_phase_b_edge_deadlocks_statically(
        self, monkeypatch, no_compile
    ):
        t0 = time.monotonic()
        orig = pipeline._ppermute_edge

        def flipped(tree, axis_name, edge, reverse=False):
            # the seeded bug: cotangent edge 0 ships forward (0→1)
            # instead of reverse (1→0) — dynamically this hangs the CPU
            # rendezvous until the 300 s pytest-timeout
            if reverse and edge == 0:
                return orig(tree, axis_name, edge, reverse=False)
            return orig(tree, axis_name, edge, reverse=reverse)

        monkeypatch.setattr(pipeline, "_ppermute_edge", flipped)
        findings = collectives.analyze_combo("MP", "1f1b", rank_check=False)
        elapsed = time.monotonic() - t0
        rules = {f.rule for f in findings}
        assert "ppermute-deadlock" in rules, findings
        msgs = " | ".join(f.message for f in findings)
        assert "stage 1" in msgs and "((0, 1),)" in msgs  # actionable
        assert elapsed < MUTATION_BUDGET_S

    def test_dropped_ddp_grad_psum_breaks_contract(
        self, monkeypatch, no_compile
    ):
        t0 = time.monotonic()
        monkeypatch.setattr(
            pipeline, "_reduce_grads",
            # the seeded bug: the stage psum survives but the 'data'
            # axis — the DDP all-reduce — is dropped, so data replicas
            # would silently diverge
            lambda grads, axes: jax.lax.psum(grads, ("stage",)),
        )
        findings = collectives.analyze_combo(
            "DDP_MP", "1f1b", rank_check=False
        )
        elapsed = time.monotonic() - t0
        assert any(
            f.rule == "comms-contract" and "data" in f.message
            for f in findings
        ), findings
        assert elapsed < MUTATION_BUDGET_S

    def test_contract_checked_even_without_explicit_schedule(
        self, monkeypatch, no_compile
    ):
        # analyze_combo("DDP_MP") with no schedule traces the gpipe
        # program — the contract key must follow, or the lookup misses
        # JAXPR_CONTRACTS and the check silently passes (review
        # regression). gpipe's 'data' reduction is autodiff-inserted
        # (no mutable seam), so pin the key resolution directly: plant
        # an unsatisfiable requirement under the resolved gpipe key —
        # only a lookup that followed the traced schedule can find it.
        contracts = dict(collectives.JAXPR_CONTRACTS)
        contracts[("DDP_MP", "gpipe")] = (
            collectives.JaxprComm(
                "reduce_scatter", frozenset({"data"}),
                why="planted: the no-schedule call must resolve gpipe",
            ),
        )
        monkeypatch.setattr(collectives, "JAXPR_CONTRACTS", contracts)
        findings = collectives.analyze_combo("DDP_MP", rank_check=False)
        assert any(
            f.rule == "comms-contract" and "data" in f.message
            for f in findings
        ), findings

    def test_rank_gated_psum_breaks_uniformity(
        self, monkeypatch, no_compile
    ):
        t0 = time.monotonic()
        orig = pipeline._reduce_grads

        def gated(grads, axes):
            # the seeded bug: a collective behind a rank-dependent
            # PYTHON branch — each rank traces a different program
            if jax.process_index() == 0:
                return orig(grads, axes)
            return grads

        monkeypatch.setattr(pipeline, "_reduce_grads", gated)
        findings = collectives.analyze_combo("MP", "1f1b", rank_check=True)
        elapsed = time.monotonic() - t0
        assert any(
            f.rule == "rank-divergent-collective" for f in findings
        ), findings
        assert elapsed < MUTATION_BUDGET_S

    def test_rank_gated_collective_also_caught_by_source_lint(self):
        # the same seeded bug, at the source level (no trace needed)
        src = (
            "import jax\n"
            "def reduce_grads(grads, axes):\n"
            "    if jax.process_index() == 0:\n"
            "        return jax.lax.psum(grads, axes)\n"
            "    return grads\n"
        )
        findings = lint.lint_source(src, "pkg/bad.py")
        assert [f.rule for f in findings] == ["rank-gated-collective"]
        assert "pkg/bad.py:4" in findings[0].where


# ---------------------------------------------------------------------------
class TestCollectiveFingerprint:
    """The ``collective-fingerprint`` rule (ISSUE 10 satellite): a short
    stable hash of each combo's ORDERED collective program, compared
    across every simulated rank of the job's world size in the
    multi-process launch preflight — catching gloo desyncs the dual-rank
    (0 vs 1) re-trace cannot see, before any rank spawns."""

    def test_stable_across_retraces(self, no_compile):
        a = collectives.collective_fingerprint("MP", "1f1b")
        b = collectives.collective_fingerprint("MP", "1f1b")
        assert a == b and len(a) == 16

    def test_schedules_fingerprint_differently(self, no_compile):
        assert (collectives.collective_fingerprint("MP", "gpipe")
                != collectives.collective_fingerprint("MP", "1f1b"))

    def test_clean_tree_matches_across_world(self, no_compile):
        findings, table = collectives.fingerprint_combos(
            ["MP"], ["1f1b"], world=3
        )
        assert findings == []
        fps = table["MP/1f1b"]
        assert len(fps) == 3 and len(set(fps)) == 1

    def test_rank2_gated_collective_needs_world_3(
        self, monkeypatch, no_compile
    ):
        """The gap this rule closes: a collective gated on
        ``process_index() == 2`` traces identically on simulated ranks
        0 and 1 (both skip it), so the dual-rank fingerprint pair
        matches — only fingerprinting the job's ACTUAL world size (3)
        sees rank 2's divergent program."""
        orig = pipeline._reduce_grads

        def gated(grads, axes):
            if jax.process_index() == 2:
                return orig(grads, axes)
            return grads

        monkeypatch.setattr(pipeline, "_reduce_grads", gated)
        f2, table2 = collectives.fingerprint_combos(["MP"], ["1f1b"], 2)
        assert f2 == []  # ranks 0 and 1 agree — the old check's blind spot
        assert len(set(table2["MP/1f1b"])) == 1
        f3, table3 = collectives.fingerprint_combos(["MP"], ["1f1b"], 3)
        assert [f.rule for f in f3] == ["collective-fingerprint"]
        assert "rank(s) [2]" in f3[0].message
        assert "desync" in f3[0].message
        assert len(set(table3["MP/1f1b"])) == 2

    def test_cli_rejects_world_of_one(self):
        # a world of 1 has nothing to compare; silently skipping the
        # gate while reporting clean would be false confidence
        with pytest.raises(SystemExit):
            analyze_cli_run(["--fingerprint-world", "1"])
        with pytest.raises(SystemExit):
            analyze_cli_run(["--fingerprint-world", "-3"])

    def test_cli_rejects_fingerprint_with_lint_only_layer(self):
        # --layer lint never runs the collectives layer, so the
        # requested desync gate would silently not execute — refuse
        # (rc 2, infra) instead of reporting a false clean
        rc = analyze_cli_run(
            ["--layer", "lint", "--fingerprint-world", "2"])
        assert rc == 2

    def test_cli_reports_fingerprints(self, tmp_path):
        report = tmp_path / "report.json"
        rc = analyze_cli_run([
            "--layer", "collectives", "--strategies", "MP",
            "--schedules", "1f1b", "--no-rank-check",
            "--fingerprint-world", "2", "--json", str(report),
        ])
        assert rc == 0
        payload = json.loads(report.read_text())
        fps = payload["fingerprints"]["MP/1f1b"]
        assert len(fps) == 2 and fps[0] == fps[1]


# ---------------------------------------------------------------------------
class TestFingerprintSnapshot:
    """The ``fingerprint-snapshot`` rule (PR 19 satellite): persist each
    combo's ordered-collective fingerprint with the toolchain identity,
    and compare across jax upgrades — drift both sides of an upgrade can
    be internally consistent about, which the per-run contract check can
    therefore never see. Hybrid mesh specs ride the same surface."""

    def test_write_then_check_roundtrip_clean(self, tmp_path, no_compile):
        path = tmp_path / "snap.json"
        payload = collectives.write_fingerprint_snapshot(
            str(path), strategies=["MP", "2x2x2"], schedules=["1f1b"],
        )
        assert set(payload["fingerprints"]) == {"MP/1f1b", "2x2x2/1f1b"}
        assert payload["jax"] == jax.__version__
        loaded = collectives.load_fingerprint_snapshot(str(path))
        assert loaded == payload
        assert collectives.check_fingerprint_snapshot(loaded) == []

    def test_seeded_drift_is_flagged_with_both_versions(
        self, tmp_path, no_compile
    ):
        path = tmp_path / "snap.json"
        payload = collectives.write_fingerprint_snapshot(
            str(path), strategies=["MP"], schedules=["gpipe"],
        )
        payload["fingerprints"]["MP/gpipe"] = "0" * 16
        payload["jax"] = "0.0.1"
        findings = collectives.check_fingerprint_snapshot(payload)
        assert [f.rule for f in findings] == ["fingerprint-snapshot"]
        assert "recorded under jax 0.0.1" in findings[0].message
        assert f"current jax {jax.__version__}" in findings[0].message

    def test_vanished_combo_is_the_loudest_drift(self, no_compile):
        payload = {
            "version": collectives.SNAPSHOT_VERSION,
            "jax": "0.0.1", "jaxlib": "0.0.1",
            "fingerprints": {"1x2x2@sp/gpipe": "f" * 16},
        }
        findings = collectives.check_fingerprint_snapshot(payload)
        assert [f.rule for f in findings] == ["fingerprint-snapshot"]
        assert "no longer traces" in findings[0].message

    def test_unreadable_snapshot_is_none_never_clean(self, tmp_path):
        assert collectives.load_fingerprint_snapshot(
            str(tmp_path / "missing.json")) is None
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert collectives.load_fingerprint_snapshot(str(bad)) is None
        skew = tmp_path / "skew.json"
        skew.write_text(json.dumps({"version": 999, "fingerprints": {}}))
        assert collectives.load_fingerprint_snapshot(str(skew)) is None

    def test_cli_check_flags_drift_and_missing_is_infra(self, tmp_path):
        path = tmp_path / "snap.json"
        rc = analyze_cli_run([
            "--layer", "collectives", "--strategies", "MP",
            "--schedules", "gpipe", "--no-rank-check",
            "--fingerprint-snapshot", "write", "--snapshot-path",
            str(path),
        ])
        assert rc == 0
        payload = json.loads(path.read_text())
        payload["fingerprints"]["MP/gpipe"] = "d" * 16
        path.write_text(json.dumps(payload))
        rc = analyze_cli_run([
            "--layer", "collectives", "--strategies", "MP",
            "--schedules", "gpipe", "--no-rank-check",
            "--fingerprint-snapshot", "check", "--snapshot-path",
            str(path),
        ])
        assert rc == 1
        rc = analyze_cli_run([
            "--layer", "collectives", "--strategies", "MP",
            "--schedules", "gpipe", "--no-rank-check",
            "--fingerprint-snapshot", "check", "--snapshot-path",
            str(tmp_path / "missing.json"),
        ])
        assert rc == 2
        # lint-only layer can't trace: refuse, never a false clean
        rc = analyze_cli_run([
            "--layer", "lint", "--fingerprint-snapshot", "check",
            "--snapshot-path", str(path),
        ])
        assert rc == 2


# ---------------------------------------------------------------------------
class TestContractTables:
    def test_jaxpr_contract_covers_every_analyzed_combo(self):
        for method, schedule in collectives.combos_for():
            key = (
                method,
                schedule if method in collectives.PIPELINE_STRATEGIES
                else None,
            )
            assert key in collectives.JAXPR_CONTRACTS

    def test_pipeline_contracts_require_the_ddp_all_reduce(self):
        reqs = collectives.JAXPR_CONTRACTS[("DDP_MP", "1f1b")]
        assert any(
            r.grad_output and "data" in r.axes and r.kind == "psum"
            for r in reqs
        )

    def test_hlo_table_matches_analyzed_strategies(self):
        # every GSPMD strategy is covered by the HLO tier (TP via the
        # any-of set); the table is what test_hlo_collectives imports
        assert set(collectives.EXPECTED_HLO_COLLECTIVES) >= {
            "DP", "SP", "FSDP", "MP",
        }
        assert collectives.TP_HLO_ANY_OF


# ---------------------------------------------------------------------------
class TestLintRules:
    def test_nondeterminism_inside_jitted_function(self):
        src = (
            "import time, jax\n"
            "def step(x):\n"
            "    return x * time.time()\n"
            "fast = jax.jit(step)\n"
        )
        findings = lint.lint_source(src, "m.py")
        assert [f.rule for f in findings] == ["trace-nondeterminism"]

    def test_nondeterminism_inside_make_builder_closure(self):
        src = (
            "import numpy as np\n"
            "def make_train_step(model):\n"
            "    def step(state, batch):\n"
            "        noise = np.random.rand()\n"
            "        return state, noise\n"
            "    return step\n"
        )
        findings = lint.lint_source(src, "m.py")
        assert [f.rule for f in findings] == ["trace-nondeterminism"]

    def test_scan_data_operands_are_not_marked_traced(self):
        # jax.lax.scan(f, init, xs): init/xs are DATA — a host function
        # that happens to share a data operand's name must not be
        # poisoned as "traced" (review regression)
        src = (
            "import time, jax\n"
            "def f(c, x):\n"
            "    return c, x\n"
            "def run(xs, init):\n"
            "    return jax.lax.scan(f, init, xs)\n"
            "def init(seed):\n"
            "    return time.time() + seed\n"
        )
        assert lint.lint_source(src, "m.py") == []

    def test_cond_branch_callables_are_marked_traced(self):
        src = (
            "import time, jax\n"
            "def hot(x):\n"
            "    return x * time.time()\n"
            "def cold(x):\n"
            "    return x\n"
            "def run(p, x):\n"
            "    return jax.lax.cond(p, hot, cold, x)\n"
        )
        findings = lint.lint_source(src, "m.py")
        assert [f.rule for f in findings] == ["trace-nondeterminism"]

    def test_cond_data_operand_is_not_marked_traced(self):
        # cond(pred, true_fn, false_fn, *operands): the operands are
        # DATA — a host function sharing an operand's name must not be
        # poisoned as "traced" (review regression)
        src = (
            "import time, jax\n"
            "def run(p, x, helper):\n"
            "    return jax.lax.cond(p, lambda v: v, lambda v: v, helper)\n"
            "def helper(x):\n"
            "    return time.time() + x\n"
        )
        assert lint.lint_source(src, "m.py") == []

    def test_switch_branches_list_is_marked_traced(self):
        # switch(index, branches, *operands): the branch callables
        # arrive inside a literal list (review regression — the list
        # was never unpacked, so branch bodies went unchecked)
        src = (
            "import time, jax\n"
            "def hot(x):\n"
            "    return x * time.time()\n"
            "def cold(x):\n"
            "    return x\n"
            "def run(i, x):\n"
            "    return jax.lax.switch(i, [hot, cold], x)\n"
        )
        findings = lint.lint_source(src, "m.py")
        assert [f.rule for f in findings] == ["trace-nondeterminism"]

    def test_associative_scan_fn_is_marked_traced(self):
        # review regression: the entrypoint table had the typo
        # "associated_scan", so this traced fn was never checked
        src = (
            "import time, jax\n"
            "def combine(a, b):\n"
            "    return a + b * time.time()\n"
            "def run(xs):\n"
            "    return jax.lax.associative_scan(combine, xs)\n"
        )
        findings = lint.lint_source(src, "m.py")
        assert [f.rule for f in findings] == ["trace-nondeterminism"]

    def test_host_randomness_outside_trace_is_fine(self):
        src = (
            "import time, numpy as np\n"
            "def shuffle(n, seed):\n"
            "    t0 = time.time()\n"
            "    return np.random.default_rng(seed).permutation(n), t0\n"
        )
        assert lint.lint_source(src, "m.py") == []

    def test_use_after_donation_direct_and_alias(self):
        src = (
            "def run(self, state, batch):\n"
            "    prev = self.state\n"
            "    new_state, loss = self.train_step(self.state, batch)\n"
            "    a = self.state\n"       # direct use-after-donation
            "    b = prev\n"             # alias use-after-donation
            "    return new_state\n"
        )
        findings = lint.lint_source(src, "m.py")
        assert [f.rule for f in findings] == ["use-after-donation"] * 2

    def test_rebinding_assignment_is_not_flagged(self):
        src = (
            "def run(self, batch):\n"
            "    self.state, loss = self.train_step(self.state, batch)\n"
            "    self.record(self.state, loss)\n"
        )
        assert lint.lint_source(src, "m.py") == []

    def test_line_wrapped_rebinding_is_not_flagged(self):
        # the rebind is recognized by the call node living inside the
        # assignment's value, not by line-number equality — a formatter
        # wrapping the statement must not create findings (review
        # regression)
        src = (
            "def run(self, batch):\n"
            "    self.state, loss = (\n"
            "        self.train_step(self.state, batch))\n"
            "    self.record(self.state, loss)\n"
        )
        assert lint.lint_source(src, "m.py") == []

    def test_hot_path_host_sync_flagged_and_drain_sanctioned(self):
        src = (
            "import numpy as np\n"
            "class Trainer:\n"
            "    def train(self):\n"
            "        def run_one(batch, losses):\n"
            "            host = np.asarray(losses)\n"  # hot-path sync
            "            def pull():\n"
            "                return np.asarray(losses)\n"  # sanctioned
            "            return host, pull\n"
            "        return run_one\n"
        )
        findings = lint.lint_source(
            src, "distributedpytorch_tpu/train/loop.py"
        )
        assert [f.rule for f in findings] == ["host-sync-hot-path"]
        assert findings[0].where.endswith(":5")

    def test_item_flagged_package_wide_but_not_in_drain_modules(self):
        src = "def f(loss):\n    return loss.item()\n"
        assert [f.rule for f in lint.lint_source(src, "pkg/train/x.py")] == [
            "host-sync-hot-path"
        ]
        assert lint.lint_source(
            src, "distributedpytorch_tpu/utils/metrics.py") == []

    def test_block_until_ready_flagged_in_both_forms(self):
        # the function form jax.block_until_ready(x) syncs exactly like
        # the method form and must not slip through (review regression)
        for src in (
            "def f(x):\n    return x.block_until_ready()\n",
            "import jax\ndef f(x):\n    return jax.block_until_ready(x)\n",
        ):
            findings = lint.lint_source(src, "pkg/train/x.py")
            assert [f.rule for f in findings] == ["host-sync-hot-path"], src

    def test_inline_suppression(self):
        src = (
            "import time, jax\n"
            "def step(x):\n"
            "    return x * time.time()  "
            "# dptlint: disable=trace-nondeterminism — test seam\n"
            "fast = jax.jit(step)\n"
        )
        assert lint.lint_source(src, "m.py") == []

    def test_suppression_list_with_spaces_covers_every_rule(self):
        # "disable=a, b" (natural comma+space style) must suppress BOTH
        # rules — the regex stopping at whitespace silently dropped the
        # second one (review regression). The listed rule that fires is
        # absorbed; the listed rule that does NOT fire on this line is
        # reported by the hygiene pass as stale — never re-surfaced as
        # the rule itself.
        src = (
            "import time, jax\n"
            "def step(x):\n"
            "    return x * time.time()  "
            "# dptlint: disable=host-sync-hot-path, trace-nondeterminism\n"
            "fast = jax.jit(step)\n"
        )
        findings = lint.lint_source(src, "m.py")
        assert [f.rule for f in findings] == ["stale-suppression"]
        assert "host-sync-hot-path" in findings[0].message

    def test_unknown_rule_suppression_does_not_mask(self):
        # the typo'd rule suppresses nothing — the real finding still
        # fires, and the hygiene pass names the typo itself
        src = (
            "import time, jax\n"
            "def step(x):\n"
            "    return x * time.time()  # dptlint: disable=other-rule\n"
            "fast = jax.jit(step)\n"
        )
        findings = lint.lint_source(src, "m.py")
        assert sorted(f.rule for f in findings) == [
            "trace-nondeterminism", "unknown-suppression",
        ]

    def test_dedupe_collapses_identical_findings(self):
        f = Finding(rule="r", where="w", message="m", layer="lint")
        out = dedupe([f, f, f])
        assert len(out) == 1 and out[0].count == 3
        assert "[x3]" in out[0].line


# ---------------------------------------------------------------------------
class TestObsHotPathRule:
    """The telemetry layer's hot-path contract (ISSUE 7): obs record
    paths never block or grow without bound, and telemetry calls never
    land inside traced functions (docs/ANALYSIS.md row, docs/
    OBSERVABILITY.md contract)."""

    OBS_PATH = "distributedpytorch_tpu/obs/x.py"

    def test_blocking_sync_in_record_path_flagged(self):
        src = (
            "import numpy as np\n"
            "class R:\n"
            "    def record(self, x):\n"
            "        return np.asarray(x)\n"
        )
        findings = lint.lint_source(src, self.OBS_PATH)
        assert "obs-hot-path" in [f.rule for f in findings]

    def test_unbounded_append_in_record_path_flagged(self):
        src = (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._events = []\n"
            "    def record(self, x):\n"
            "        self._events.append(x)\n"
        )
        findings = lint.lint_source(src, self.OBS_PATH)
        assert [f.rule for f in findings] == ["obs-hot-path"]
        assert "deque(maxlen" in findings[0].message

    def test_deque_maxlen_ring_append_is_sanctioned(self):
        src = (
            "import collections\n"
            "class R:\n"
            "    def __init__(self):\n"
            "        self._events = collections.deque(maxlen=8)\n"
            "    def record(self, x):\n"
            "        self._events.append(x)\n"
        )
        assert lint.lint_source(src, self.OBS_PATH) == []

    def test_annotated_deque_assignment_is_recognized(self):
        # flight.py's own idiom: an AnnAssign-constructed ring
        src = (
            "import collections\n"
            "class R:\n"
            "    def __init__(self):\n"
            "        self._events: collections.deque = "
            "collections.deque(maxlen=8)\n"
            "    def record_span(self, x):\n"
            "        self._events.append(x)\n"
        )
        assert lint.lint_source(src, self.OBS_PATH) == []

    def test_append_outside_record_path_not_flagged(self):
        src = (
            "class R:\n"
            "    def expose(self):\n"
            "        lines = []\n"
            "        lines.append('x')\n"
            "        return lines\n"
        )
        assert lint.lint_source(src, self.OBS_PATH) == []

    def test_append_outside_obs_module_not_flagged(self):
        src = (
            "class R:\n"
            "    def record(self, x):\n"
            "        self._events.append(x)\n"
        )
        assert lint.lint_source(src, "pkg/serve/x.py") == []

    def test_obs_call_inside_traced_function_flagged(self):
        src = (
            "import jax\n"
            "from distributedpytorch_tpu.obs import flight\n"
            "from distributedpytorch_tpu.obs import defs as obsm\n"
            "def make_step():\n"
            "    def step(s, b):\n"
            "        flight.record('step', step=1)\n"
            "        obsm.TRAIN_STEPS.inc()\n"
            "        return s\n"
            "    return jax.jit(step)\n"
        )
        findings = [
            f for f in lint.lint_source(src, "pkg/train/x.py")
            if f.rule == "obs-hot-path"
        ]
        assert len(findings) == 2
        assert all("trace time" in f.message for f in findings)

    def test_obs_call_on_host_loop_is_fine(self):
        src = (
            "from distributedpytorch_tpu.obs import flight\n"
            "def train_loop(batches):\n"
            "    for b in batches:\n"
            "        flight.record('step')\n"
        )
        assert lint.lint_source(src, "pkg/train/x.py") == []

    def test_mark_fn_unbounded_append_flagged(self):
        """ISSUE 13: the rule reaches obs/reqtrace.py's request-trace
        lifecycle — ``mark_*`` stamps ride the serve dispatch hot path
        and ``complete`` appends ledgers, so both are record scope."""
        src = (
            "class T:\n"
            "    def __init__(self):\n"
            "        self._spans = []\n"
            "    def mark_flushed(self, t):\n"
            "        self._spans.append(t)\n"
        )
        findings = lint.lint_source(
            src, "distributedpytorch_tpu/obs/reqtrace.py"
        )
        assert [f.rule for f in findings] == ["obs-hot-path"]
        assert "deque(maxlen" in findings[0].message

    def test_complete_fn_blocking_sync_flagged(self):
        src = (
            "import numpy as np\n"
            "class T:\n"
            "    def complete(self, out):\n"
            "        return np.asarray(out)\n"
        )
        findings = lint.lint_source(
            src, "distributedpytorch_tpu/obs/reqtrace.py"
        )
        assert "obs-hot-path" in [f.rule for f in findings]

    def test_shipped_reqtrace_module_is_clean(self):
        """The real obs/reqtrace.py under the extended rule: ledger and
        profile appends are deque(maxlen=...) rings, nothing blocks."""
        import distributedpytorch_tpu.obs.reqtrace as reqtrace_mod

        path = reqtrace_mod.__file__
        findings = lint.lint_file(
            path,
            root=os.path.dirname(os.path.dirname(os.path.dirname(path))),
        )
        assert findings == [], findings

    def test_shipped_obs_package_is_clean(self):
        import distributedpytorch_tpu.obs as obs_pkg

        root = os.path.dirname(obs_pkg.__file__)
        for fname in sorted(os.listdir(root)):
            if not fname.endswith(".py"):
                continue
            findings = lint.lint_file(
                os.path.join(root, fname),
                root=os.path.dirname(os.path.dirname(root)),
            )
            assert findings == [], (fname, findings)


# ---------------------------------------------------------------------------
class TestServeHotPathRule:
    """The serve-tier twin of host-sync-hot-path (ISSUE 6): blocking
    host syncs inside the serve dispatch pipeline (serve/server.py's
    ``_bucket_stream``/``_place``/``_dispatch_loop``) stall every
    in-flight request on every replica; the completion drain (``pull``)
    is the sanctioned exemption, mirroring the train rule's mechanism."""

    SERVE_PATH = "distributedpytorch_tpu/serve/server.py"

    def test_sync_in_dispatch_loop_flagged(self):
        src = (
            "import numpy as np\n"
            "class Server:\n"
            "    def _dispatch_loop(self):\n"
            "        for item in self.stream:\n"
            "            out = self.engine.run(item)\n"
            "            return np.asarray(out)\n"
        )
        findings = lint.lint_source(src, self.SERVE_PATH)
        assert [f.rule for f in findings] == ["serve-hot-path"]
        assert findings[0].where.endswith(":6")

    def test_item_and_block_until_ready_flagged_in_serve_scope(self):
        src = (
            "def _place(self, kind, payload):\n"
            "    x = self.engine.place(payload)\n"
            "    x.block_until_ready()\n"
            "    return x.item()\n"
        )
        rules = [f.rule for f in lint.lint_source(src, self.SERVE_PATH)]
        # both calls also trip the package-wide blocking rule — the
        # serve rule must ADD its scope-specific findings, not replace it
        assert rules.count("serve-hot-path") == 2
        assert rules.count("host-sync-hot-path") == 2

    def test_pull_is_the_sanctioned_drain(self):
        # the real architecture: np.asarray lives in the completion
        # drain — both as a module-level fn and nested inside the loop
        for src in (
            "import numpy as np\n"
            "def pull(server, out):\n"
            "    return np.asarray(out)\n",
            "import numpy as np\n"
            "class Server:\n"
            "    def _dispatch_loop(self):\n"
            "        def pull(out):\n"
            "            return np.asarray(out)\n"
            "        return pull\n",
        ):
            assert [
                f for f in lint.lint_source(src, self.SERVE_PATH)
                if f.rule == "serve-hot-path"
            ] == [], src

    def test_scope_is_serve_server_only(self):
        # same source outside serve/server.py (or outside the scoped
        # functions inside it): the serve rule stays silent
        src = (
            "import numpy as np\n"
            "class Server:\n"
            "    def _dispatch_loop(self):\n"
            "        return np.asarray(self.out)\n"
        )
        assert [
            f for f in lint.lint_source(
                src, "distributedpytorch_tpu/serve/engine.py")
            if f.rule == "serve-hot-path"
        ] == []
        ingress = (
            "import numpy as np\n"
            "class Server:\n"
            "    def submit(self, images):\n"
            "        return np.asarray(images)\n"  # ingress may block
        )
        assert [
            f for f in lint.lint_source(ingress, self.SERVE_PATH)
            if f.rule == "serve-hot-path"
        ] == []

    def test_inline_suppression(self):
        src = (
            "import numpy as np\n"
            "class Server:\n"
            "    def _dispatch_loop(self):\n"
            "        return np.asarray(self.out)  "
            "# dptlint: disable=serve-hot-path — drained at shutdown\n"
        )
        assert [
            f for f in lint.lint_source(src, self.SERVE_PATH)
            if f.rule == "serve-hot-path"
        ] == []

    def test_shipped_server_module_is_clean(self):
        import distributedpytorch_tpu.serve.server as server_mod

        path = server_mod.__file__
        findings = lint.lint_file(
            path, root=os.path.dirname(
                os.path.dirname(os.path.dirname(path)))
        )
        assert findings == [], findings


# ---------------------------------------------------------------------------
class TestCli:
    def test_lint_layer_runs_clean_and_writes_report(self, tmp_path):
        report = tmp_path / "report.json"
        rc = analyze_cli_run(["--layer", "lint", "--json", str(report)])
        assert rc == 0
        payload = json.loads(report.read_text())
        assert payload["clean"] is True
        assert payload["lint_files"] > 30

    def test_findings_exit_code_and_report(self, tmp_path, monkeypatch):
        # a lint root containing one bad file → rc 1 + findings in JSON
        bad = tmp_path / "pkg"
        bad.mkdir()
        (bad / "bad.py").write_text(
            "import jax\n"
            "def f(g, axes):\n"
            "    if jax.process_index() == 0:\n"
            "        return jax.lax.psum(g, axes)\n"
            "    return g\n"
        )
        report = tmp_path / "report.json"
        rc = analyze_cli_run([
            "--layer", "lint", "--lint-root", str(bad),
            "--json", str(report),
        ])
        assert rc == 1
        payload = json.loads(report.read_text())
        assert payload["clean"] is False
        assert payload["findings"][0]["rule"] == "rank-gated-collective"


class TestDtypePolicyRule:
    """The mixed-precision cast-boundary rule (ops/precision.py,
    docs/PERFORMANCE.md "Precision"): bare f32 spellings in traced code
    are upcasts the --dtype policy cannot see. The ROADMAP's
    "dtype-policy rule once bf16 lands" item."""

    def test_bare_f32_literal_in_make_builder_flagged(self):
        src = (
            "import jax.numpy as jnp\n"
            "def make_train_step(model):\n"
            "    def step(state, batch):\n"
            "        g = batch['x'].astype(jnp.float32)\n"
            "        z = jnp.zeros((4,), jnp.float32)\n"
            "        return g, z\n"
            "    return step\n"
        )
        findings = lint.lint_source(src, "train/steps.py")
        assert [f.rule for f in findings] == ["dtype-policy", "dtype-policy"]

    def test_string_f32_spellings_flagged(self):
        src = (
            "def make_step(model):\n"
            "    def step(x):\n"
            "        a = x.astype('float32')\n"
            "        import jax.numpy as jnp\n"
            "        b = jnp.zeros((2,), dtype='float32')\n"
            "        return a, b\n"
            "    return step\n"
        )
        findings = lint.lint_source(src, "train/steps.py")
        assert [f.rule for f in findings] == ["dtype-policy", "dtype-policy"]

    def test_named_contract_constant_is_the_sanctioned_spelling(self):
        src = (
            "from distributedpytorch_tpu.ops.precision import WGRAD_DTYPE\n"
            "import jax.numpy as jnp\n"
            "def make_step(model):\n"
            "    def step(x):\n"
            "        return jnp.zeros((4,), WGRAD_DTYPE)\n"
            "    return step\n"
        )
        assert lint.lint_source(src, "train/steps.py") == []

    def test_host_code_not_flagged(self):
        src = (
            "import jax.numpy as jnp\n"
            "def host_prep(x):\n"
            "    return x.astype(jnp.float32)\n"
        )
        assert lint.lint_source(src, "train/loop.py") == []

    def test_sanctioned_loss_modules_exempt(self):
        src = (
            "import jax.numpy as jnp\n"
            "def make_stats(model):\n"
            "    def stats(x):\n"
            "        return x.astype(jnp.float32).sum()\n"
            "    return stats\n"
        )
        for mod in ("ops/losses.py", "ops/precision.py", "ops/quant.py"):
            assert lint.lint_source(src, mod) == [], mod

    def test_kernel_modules_no_longer_blanket_exempt(self):
        """ISSUE 11: the Pallas kernel modules comply with the named
        constants, so the blanket ops/ exemption is dropped — a bare f32
        regression there is drift again."""
        src = (
            "import jax.numpy as jnp\n"
            "def make_stats(model):\n"
            "    def stats(x):\n"
            "        return x.astype(jnp.float32).sum()\n"
            "    return stats\n"
        )
        for mod in ("ops/pallas_kernels.py", "ops/attention_pallas.py",
                    "ops/fused_loss.py", "ops/kernels.py"):
            findings = lint.lint_source(src, mod)
            assert [f.rule for f in findings] == ["dtype-policy"], mod

    def test_pallas_kernel_body_is_a_traced_scope(self):
        """The rule reaches kernel bodies: a function handed to
        ``pallas_call`` is traced, so its bare f32 accumulator flags."""
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "from jax.experimental import pallas as pl\n"
            "def _kernel(x_ref, o_ref):\n"
            "    o_ref[0, 0] += jnp.sum(x_ref[:].astype(jnp.float32))\n"
            "def run(x):\n"
            "    return pl.pallas_call(\n"
            "        _kernel,\n"
            "        out_shape=jax.ShapeDtypeStruct((1, 1), x.dtype),\n"
            "    )(x)\n"
        )
        findings = lint.lint_source(src, "ops/my_kernel.py")
        assert [f.rule for f in findings] == ["dtype-policy"]

    def test_defvjp_bodies_are_traced_scopes(self):
        """...and so are hand-written custom-VJP forward/backward
        bodies registered through ``defvjp``."""
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "@jax.custom_vjp\n"
            "def op(x):\n"
            "    return x\n"
            "def _fwd(x):\n"
            "    return x, x\n"
            "def _bwd(res, g):\n"
            "    return (g.astype(jnp.float32),)\n"
            "op.defvjp(_fwd, _bwd)\n"
        )
        findings = lint.lint_source(src, "ops/my_kernel.py")
        assert [f.rule for f in findings] == ["dtype-policy"]

    def test_kernel_body_spelling_the_contract_constant_is_clean(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "from jax.experimental import pallas as pl\n"
            "from distributedpytorch_tpu.ops.precision import WGRAD_DTYPE\n"
            "def _kernel(x_ref, o_ref):\n"
            "    o_ref[0, 0] += jnp.sum(x_ref[:].astype(WGRAD_DTYPE))\n"
            "def run(x):\n"
            "    return pl.pallas_call(\n"
            "        _kernel,\n"
            "        out_shape=jax.ShapeDtypeStruct((1, 1), x.dtype),\n"
            "    )(x)\n"
        )
        assert lint.lint_source(src, "ops/my_kernel.py") == []

    def test_shipped_kernel_modules_lint_clean(self):
        """The real kernel modules under the extended rule: their
        accumulators spell LOSS/WGRAD/NORM_DTYPE, so dropping the
        exemption flags nothing."""
        import pathlib

        root = pathlib.Path(lint.__file__).resolve().parents[1]
        for mod in ("ops/pallas_kernels.py", "ops/attention_pallas.py",
                    "ops/fused_loss.py", "ops/kernels.py"):
            path = root / mod
            findings = lint.lint_source(path.read_text(), mod)
            assert findings == [], (mod, findings)

    def test_inline_suppression(self):
        src = (
            "import jax.numpy as jnp\n"
            "def make_step(model):\n"
            "    def step(x):\n"
            "        return x.astype(jnp.float32)  "
            "# dptlint: disable=dtype-policy — measured exact seam\n"
            "    return step\n"
        )
        assert lint.lint_source(src, "train/steps.py") == []


class TestCkptDtypeDriftRule:
    """Restores must route through the precision restore seams
    (ensure_restored_dtypes / convert_checkpoint_state) — a drifted-dtype
    restore otherwise silently retraces the donated-buffer step."""

    def test_naked_restore_flagged(self):
        src = (
            "def restore(path, template):\n"
            "    out = load_checkpoint(path, template)\n"
            "    return out['params']\n"
        )
        findings = lint.lint_source(src, "train/loop.py")
        assert [f.rule for f in findings] == ["ckpt-dtype-drift"]

    def test_naked_load_weights_flagged(self):
        src = (
            "def restore(path, template):\n"
            "    return load_weights(path, template)\n"
        )
        findings = lint.lint_source(src, "serve/infer.py")
        assert [f.rule for f in findings] == ["ckpt-dtype-drift"]

    def test_seam_in_enclosing_function_sanctions(self):
        for seam in ("ensure_restored_dtypes", "convert_checkpoint_state"):
            src = (
                "def restore(path, template, policy):\n"
                "    out = load_checkpoint(path, template)\n"
                f"    return {seam}(out, policy, 'restore')\n"
            )
            assert lint.lint_source(src, "train/loop.py") == [], seam

    def test_checkpoint_module_itself_exempt(self):
        src = (
            "def load_weights(path, template):\n"
            "    return load_checkpoint(path, template, None)['params']\n"
        )
        assert lint.lint_source(src, "checkpoint.py") == []

    def test_shipped_restore_paths_are_clean(self):
        # the trainer's _restore and the serve loader both carry the seam
        import distributedpytorch_tpu.serve.infer as infer_mod
        import distributedpytorch_tpu.train.loop as loop_mod

        for mod in (loop_mod, infer_mod):
            findings = [
                f for f in lint.lint_file(mod.__file__)
                if f.rule == "ckpt-dtype-drift"
            ]
            assert findings == [], (mod.__name__, findings)
