"""The auto-planner (analysis/planner.py + analysis/cost_model.py):
tiny-geometry end-to-end plans on the CPU mesh, the plan-file schema
round-trip, the cost_analysis-absent guard, and
the ISSUE-10 acceptance pins — 1F1B ranked above GPipe at M=8 at the
activation wall, s2d-3 / remat-off feasibility, and the three seeded
statically-broken mutants rejected with ZERO device execution (the
``no_compile`` fixture proves a statically-rejected point never even
reaches the AOT compiler).
"""

import json

import jax
import pytest

import distributedpytorch_tpu.parallel.pipeline as pipeline
from distributedpytorch_tpu.analysis import cost_model as cm
from distributedpytorch_tpu.analysis import planner

# the analysis rig's tiny geometry: image_size is (W, H)
TINY = dict(image_size=(48, 32), widths=(8, 16))


def _grid(**overrides):
    base = dict(
        strategies=("singleGPU", "MP"),
        schedules=("gpipe", "1f1b"),
        microbatches=(2, 8),
        s2d_levels=(0,),
        remats=(False,),
        batches=(8,),
        dtypes=("bf16",),
        hbm_gb=16.0,
        **TINY,
    )
    base.update(overrides)
    return base


@pytest.fixture(scope="module")
def tiny_plan():
    """One end-to-end tiny plan shared by the schema/ranking tests:
    singleGPU + MP × {gpipe, 1f1b} × M ∈ {2, 8} (5 points)."""
    return planner.plan(**_grid())


@pytest.fixture
def no_compile(monkeypatch):
    """Any AOT compile during the test raises — the proof that a
    statically-rejected point spends zero compiler (and zero device)
    time."""

    def boom(self, *a, **k):
        raise AssertionError(
            "planner compiled an executable for a statically-rejected "
            "point"
        )

    monkeypatch.setattr(jax.stages.Lowered, "compile", boom)


# ---------------------------------------------------------------------------
class TestCostModel:
    MM = cm.MESH_MODELS["tpu_v5e"]

    def test_collective_time_factors(self):
        t_psum = cm.collective_time("psum", 1 << 20, 4, self.MM)
        t_ag = cm.collective_time("all_gather", 1 << 20, 4, self.MM)
        t_pp = cm.collective_time("ppermute", 1 << 20, 4, self.MM)
        # all-reduce pays reduce-scatter + all-gather
        assert t_psum > t_ag > 0
        # a point-to-point shift ships the payload across one link once
        assert abs(t_pp - (self.MM.collective_latency_s
                           + (1 << 20) / self.MM.ici_bytes_per_s)) < 1e-12

    def test_degenerate_axis_is_free(self):
        assert cm.collective_time("psum", 1 << 20, 1, self.MM) == 0.0

    def test_fsdp_allgather_bytes_follow_storage_dtype(self):
        # bf16_params halves param storage → halves the all-gather term:
        # why --dtype is a real search dimension
        full = cm.gspmd_comms_program("FSDP", 100, 400, 8)
        half = cm.gspmd_comms_program("FSDP", 50, 400, 8)
        ag_full = sum(b for k, b, _ in full if k == "all_gather")
        ag_half = sum(b for k, b, _ in half if k == "all_gather")
        assert ag_half * 2 == ag_full
        # the gradient reduce-scatter stays f32 under every policy
        assert [b for k, b, _ in full if k == "reduce_scatter"] == [400]

    def test_unmodeled_strategies_return_empty(self):
        assert cm.gspmd_comms_program("SP", 100, 400, 8) == []
        assert cm.gspmd_comms_program("TP", 100, 400, 8) == []

    def test_hbm_pressure_rises_near_budget_and_clamps(self):
        assert cm.hbm_pressure(10, 100) < cm.hbm_pressure(90, 100)
        assert cm.hbm_pressure(99, 100) <= cm.MAX_HBM_PRESSURE
        assert cm.hbm_pressure(10 ** 12, 100) == pytest.approx(
            cm.MAX_HBM_PRESSURE)
        assert cm.hbm_pressure(None, 100) == 1.0
        assert cm.hbm_pressure(10, None) == 1.0

    def test_point_cost_drops_missing_terms(self):
        out = cm.point_cost(self.MM, "bfloat16", None, None, 1e-5)
        assert out["compute_s"] is None and out["hbm_s"] is None
        assert out["cost_s"] == 1e-5

    def test_in_stage_terms_replace_per_conv_gathers(self):
        """stage>1 + channel model axis: ONE gather-at-use param
        all-gather (the stage's own param slice) + the transposed grad
        reduce-scatter — not the flat mesh's per-conv activation
        gathers; and the in-stage ZeRO dance gathers once, not twice."""
        flat = cm.mesh_comms_program(
            data=2, model=2, param_storage_bytes=1000, grad_bytes=2000,
            level_planes=[(64, 8)],
        )
        staged = cm.mesh_comms_program(
            data=2, model=2, param_storage_bytes=1000, grad_bytes=2000,
            level_planes=[(64, 8)], stage=2,
        )
        assert staged == [
            ("psum", 2000, 2),          # schedule-closing grad psum
            ("all_gather", 500, 2),     # per-stage param slice, model
            ("reduce_scatter", 1000, 2),
        ]
        # flat keeps the per-conv channel terms (2*CONVS_PER_LEVEL)
        assert sum(1 for k, _, _ in flat if k == "all_gather") == 8
        zero = cm.mesh_comms_program(
            data=2, model=1, params_rule="fsdp",
            param_storage_bytes=1000, grad_bytes=2000, stage=2,
        )
        assert zero == [("all_gather", 500, 2),
                        ("reduce_scatter", 1000, 2)]
        # stage=1 path is byte-identical to before the parameter existed
        assert cm.mesh_comms_program(
            data=2, model=1, params_rule="fsdp",
            param_storage_bytes=1000, grad_bytes=2000,
        ) == [("all_gather", 1000, 2), ("all_gather", 1000, 2),
              ("reduce_scatter", 2000, 2)]


# ---------------------------------------------------------------------------
class TestModelStagePlannerFlip:
    """PR 19's planner flip: ``2x2x2`` was an honest mesh-config reject
    at PR 15 ('model' and 'stage' not executable together); with
    in-stage sharding it evaluates FEASIBLE — the traced jaxpr program
    carries the gather-at-use collectives, and the predicted breakdown
    names the in-stage terms (``in_stage_comms_s``, advisory — the jaxpr
    comms time already counts the real gathers)."""

    def test_2x2x2_point_now_feasible_with_in_stage_breakdown(self):
        p = planner.plan(**_grid(
            strategies=(), meshes=("2x2x2",), schedules=("gpipe",),
            microbatches=(2,),
        ))
        row = p["points"][0]
        assert row["feasible"] is True, row["reject"]
        predicted = row["predicted"]
        assert predicted["comms_model"] == "jaxpr"
        assert predicted["comms_bytes"] > 0
        assert predicted["in_stage_comms_s"] > 0
        # advisory, never double-counted into the ranked cost
        assert predicted["in_stage_comms_s"] <= predicted["comms_s"]
        assert row["rank"] is not None

    def test_flat_pipeline_point_carries_no_in_stage_term(self, tiny_plan):
        for row in tiny_plan["points"]:
            assert "in_stage_comms_s" not in (row.get("predicted") or {})


# ---------------------------------------------------------------------------
class TestTinyPlanEndToEnd:
    def test_schema_and_rank_assignment(self, tiny_plan):
        assert tiny_plan["kind"] == planner.PLAN_KIND
        assert tiny_plan["version"] == planner.PLAN_VERSION
        rows = tiny_plan["points"]
        assert len(rows) == 5  # singleGPU + MP × 2 schedules × 2 M
        assert all(r["feasible"] for r in rows)
        ranks = sorted(r["rank"] for r in rows)
        assert ranks == list(range(5))
        # ranking list is cost-ascending and names every ranked point
        by_key = {r["key"]: r for r in rows}
        costs = [by_key[k]["predicted"]["cost_s"]
                 for k in tiny_plan["ranking"]]
        assert costs == sorted(costs)

    def test_every_point_carries_the_predicted_terms(self, tiny_plan):
        for r in tiny_plan["points"]:
            p = r["predicted"]
            assert p["cost_s"] > 0
            assert p["temp_bytes"] > 0 and p["live_bytes"] > 0
            assert p["flops"] > 0  # cost_analysis available on CPU
        mp = [r for r in tiny_plan["points"] if r["strategy"] == "MP"]
        # explicit schedules expose their jaxpr comms program with bytes
        assert all(r["predicted"]["comms_model"] == "jaxpr" for r in mp)
        assert all(r["predicted"]["comms_bytes"] > 0 for r in mp)

    def test_gpipe_liveness_exceeds_1f1b_at_m8(self, tiny_plan):
        """The activation-liveness signal itself (PR 4's measured gap),
        read straight from the plan's traced-liveness bytes."""
        by_key = {r["key"]: r for r in tiny_plan["points"]}
        gpipe = by_key["MP/gpipe/m8/s2d0/remat-off/b8/bf16"]["predicted"]
        f1b = by_key["MP/1f1b/m8/s2d0/remat-off/b8/bf16"]["predicted"]
        assert gpipe["temp_bytes"] > 2 * f1b["temp_bytes"]

    def test_1f1b_ranks_above_gpipe_at_m8_at_the_activation_wall(
        self, tiny_plan
    ):
        """ISSUE-10 acceptance: at an HBM budget sized to the activation
        wall (gpipe's M=8 liveness just fits), the liveness term ranks
        1F1B above GPipe — the known chip-window result (gpipe M=8 at
        batch 4 remats/OOMs; 1F1B's in-flight set is stage-bounded),
        reproduced from CPU alone."""
        by_key = {r["key"]: r for r in tiny_plan["points"]}
        gpipe_live = by_key[
            "MP/gpipe/m8/s2d0/remat-off/b8/bf16"]["predicted"]["live_bytes"]
        wall = planner.plan(**_grid(
            strategies=("MP",), microbatches=(8,),
            hbm_gb=gpipe_live * 1.05 / 2**30,
        ))
        ranks = {r["key"]: r["rank"] for r in wall["points"]}
        assert all(r["feasible"] for r in wall["points"])  # both fit...
        assert (ranks["MP/1f1b/m8/s2d0/remat-off/b8/bf16"]
                < ranks["MP/gpipe/m8/s2d0/remat-off/b8/bf16"])

    def test_s2d3_and_remat_off_feasible_at_reference_budget(self):
        """ISSUE-10 acceptance (tiny-geometry analog): s2d level 3 and
        remat-off at batch 4 are marked feasible at the 16 GB reference
        budget."""
        p = planner.plan(**_grid(
            strategies=("singleGPU",), s2d_levels=(3,),
            remats=(False, True), batches=(4,),
        ))
        by_key = {r["key"]: r for r in p["points"]}
        assert by_key["singleGPU/s2d3/remat-off/b4/bf16"]["feasible"]
        assert by_key["singleGPU/s2d3/remat-on/b4/bf16"]["feasible"]

    def test_memory_budget_rejects_with_reason(self):
        p = planner.plan(**_grid(strategies=("singleGPU",),
                                 hbm_gb=1e-6))
        row = p["points"][0]
        assert row["feasible"] is False and row["rank"] is None
        assert row["reject"].startswith("memory:")
        assert "exceeds" in row["reject"]
        assert p["ranking"] == []

    def test_impossible_config_rejected_not_crashed(self):
        # batch 4 with 8 microbatches: the pipeline cannot split it —
        # the strategy's own rejection becomes an infeasible row
        p = planner.plan(**_grid(
            strategies=("MP",), schedules=("gpipe",), microbatches=(8,),
            batches=(4,),
        ))
        row = p["points"][0]
        assert row["feasible"] is False
        assert row["reject"].startswith("config:")

    def test_analyzer_infra_errors_propagate_not_recorded(
        self, monkeypatch
    ):
        # an AnalysisEnvironmentError is a broken environment, not a
        # broken config: it must reach the CLI's EXIT_INFRA handler
        # instead of writing a confident "config:" reject row
        from distributedpytorch_tpu.analysis import AnalysisEnvironmentError

        def broken(*a, **k):
            raise AnalysisEnvironmentError("mesh vanished")

        monkeypatch.setattr(planner, "evaluate_point", broken)
        with pytest.raises(AnalysisEnvironmentError):
            planner.plan(**_grid(strategies=("singleGPU",)))

    def test_budget_exhausted_marks_skipped(self):
        p = planner.plan(**_grid(budget_s=1e-9))
        skipped = [r for r in p["points"] if r.get("skipped") == "budget"]
        assert len(skipped) == len(p["points"])
        assert all(r["rank"] is None for r in skipped)

    def test_cost_analysis_absent_guard(self, monkeypatch):
        """Backends without ``cost_analysis()`` (the satellite's guard):
        the flops term drops, the point still ranks on liveness+comms."""
        monkeypatch.setattr(
            jax.stages.Compiled, "cost_analysis",
            lambda self: (_ for _ in ()).throw(NotImplementedError()),
        )
        p = planner.plan(**_grid(strategies=("singleGPU",)))
        row = p["points"][0]
        assert row["feasible"] is True and row["rank"] == 0
        assert row["predicted"]["flops"] is None
        assert row["predicted"]["compute_s"] is None
        assert row["predicted"]["cost_s"] > 0  # hbm + comms still rank

    def test_fsdp_dtype_halves_gather_traffic(self):
        """dtype as a search dimension: bf16_params halves FSDP's
        analytic all-gather bytes (storage dtype) vs bf16's f32 params."""
        p = planner.plan(**_grid(
            strategies=("FSDP",), dtypes=("bf16", "bf16_params"),
        ))
        by_key = {r["key"]: r["predicted"] for r in p["points"]}
        full = by_key["FSDP/s2d0/remat-off/b8/bf16"]
        half = by_key["FSDP/s2d0/remat-off/b8/bf16_params"]
        assert full["comms_model"] == half["comms_model"] == "analytic"
        assert half["comms_bytes"] < full["comms_bytes"]


# ---------------------------------------------------------------------------
class TestSeededMutantsRejected:
    """The three ISSUE-5 mutations again, now at the planner's front
    door: each must reject every point of its combo with a ``static:``
    reason and ZERO device execution — the compile-forbidding fixture
    proves no rejected point ever reached the AOT tier."""

    MUTANT_GRID = dict(
        s2d_levels=(0,), remats=(False,), batches=(8,), dtypes=("bf16",),
        hbm_gb=16.0, **TINY,
    )

    def _assert_all_static_rejected(self, plan_payload, rule):
        rows = plan_payload["points"]
        assert rows
        for row in rows:
            assert row["feasible"] is False, row
            assert row["reject"].startswith("static:"), row
            assert rule in row["reject"]
        assert plan_payload["ranking"] == []

    def test_flipped_1f1b_edge(self, monkeypatch, no_compile):
        orig = pipeline._ppermute_edge

        def flipped(tree, axis_name, edge, reverse=False):
            if reverse and edge == 0:
                return orig(tree, axis_name, edge, reverse=False)
            return orig(tree, axis_name, edge, reverse=reverse)

        monkeypatch.setattr(pipeline, "_ppermute_edge", flipped)
        p = planner.plan(strategies=("MP",), schedules=("1f1b",),
                         microbatches=(2,), **self.MUTANT_GRID)
        self._assert_all_static_rejected(p, "ppermute-deadlock")

    def test_dropped_ddp_data_psum(self, monkeypatch, no_compile):
        monkeypatch.setattr(
            pipeline, "_reduce_grads",
            lambda grads, axes: jax.lax.psum(grads, ("stage",)),
        )
        p = planner.plan(strategies=("DDP_MP",), schedules=("1f1b",),
                         microbatches=(2,), **self.MUTANT_GRID)
        self._assert_all_static_rejected(p, "comms-contract")

    def test_rank_gated_psum(self, monkeypatch, no_compile):
        orig = pipeline._reduce_grads

        def gated(grads, axes):
            if jax.process_index() == 0:
                return orig(grads, axes)
            return grads

        monkeypatch.setattr(pipeline, "_reduce_grads", gated)
        p = planner.plan(strategies=("MP",), schedules=("1f1b",),
                         microbatches=(2,), **self.MUTANT_GRID)
        self._assert_all_static_rejected(p, "rank-divergent-collective")


# ---------------------------------------------------------------------------
class TestPlanFileIO:
    def test_roundtrip(self, tmp_path, tiny_plan):
        path = str(tmp_path / "plan.json")
        planner.save_plan(tiny_plan, path)
        loaded = planner.load_plan(path)
        assert loaded is not None
        assert loaded["ranking"] == tiny_plan["ranking"]
        assert len(loaded["points"]) == len(tiny_plan["points"])

    def test_missing_file_is_none(self, tmp_path):
        assert planner.load_plan(str(tmp_path / "nope.json")) is None

    def test_garbage_is_none(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        assert planner.load_plan(str(p)) is None
        p.write_text(json.dumps([1, 2, 3]))
        assert planner.load_plan(str(p)) is None

    def test_stale_version_is_none(self, tmp_path):
        p = tmp_path / "stale.json"
        p.write_text(json.dumps({
            "kind": planner.PLAN_KIND, "version": planner.PLAN_VERSION + 99,
            "points": [],
        }))
        assert planner.load_plan(str(p)) is None

    def test_wrong_kind_is_none(self, tmp_path):
        p = tmp_path / "kind.json"
        p.write_text(json.dumps({
            "kind": "something_else", "version": planner.PLAN_VERSION,
            "points": [],
        }))
        assert planner.load_plan(str(p)) is None

    def test_cli_run_writes_loadable_plan(self, tmp_path):
        # run() directly: this process already holds the 8-device mesh
        # (the real CLI re-execs itself into exactly this state)
        out = str(tmp_path / "plan.json")
        rc = planner.run([
            "--out", out, "--strategies", "singleGPU",
            "--s2d-levels", "0", "--remat", "off", "--batches", "8",
            "--dtypes", "bf16", "--image-size", "48", "32",
            "--widths", "8", "16",
        ])
        assert rc == 0
        loaded = planner.load_plan(out)
        assert loaded is not None
        assert len(loaded["points"]) == 1
        assert loaded["points"][0]["feasible"] is True


# ---------------------------------------------------------------------------
class TestStalePlan:
    """The dptlint ``stale-plan`` rule: every evaluated plan row carries
    the ordered-collective fingerprint of the trace its numbers came
    from, and ``check_plan_staleness`` re-traces and compares — a plan
    built from a collective program that no longer exists must flag,
    a fresh plan must not."""

    def test_rows_carry_fingerprints(self, tiny_plan):
        for row in tiny_plan["points"]:
            fp = row["jaxpr_fingerprint"]
            assert isinstance(fp, str) and len(fp) == 16
            int(fp, 16)  # hex digest prefix
        # distinct programs → distinct fingerprints (singleGPU traces
        # zero collectives, MP/gpipe traces the pipeline shifts)
        assert len({r["jaxpr_fingerprint"]
                    for r in tiny_plan["points"]}) > 1

    def test_fresh_plan_is_clean(self, tiny_plan):
        import copy

        # two representative programs (collective-free singleGPU + a
        # pipeline trace) — every row's stamp is covered by
        # test_rows_carry_fingerprints, and each re-trace here costs
        # seconds of tier-1 wall clock
        subset = copy.deepcopy(tiny_plan)
        subset["points"] = [tiny_plan["points"][0],
                            tiny_plan["points"][-1]]
        assert planner.check_plan_staleness(subset) == []

    def test_drifted_fingerprint_is_flagged(self, tiny_plan):
        import copy

        drifted = copy.deepcopy(tiny_plan)
        victim = copy.deepcopy(drifted["points"][1])
        victim["jaxpr_fingerprint"] = "0" * 16
        drifted["points"] = [victim]  # one re-trace, one flag
        findings = planner.check_plan_staleness(drifted)
        assert len(findings) == 1
        f = findings[0]
        assert f.rule == "stale-plan"
        assert f.layer == "collectives"
        assert f.where == victim["key"]
        assert "re-run the planner" in f.message

    def test_fingerprintless_rows_are_skipped(self, tiny_plan):
        import copy

        legacy = copy.deepcopy(tiny_plan)
        for row in legacy["points"]:
            row.pop("jaxpr_fingerprint", None)
        assert planner.check_plan_staleness(legacy) == []

    def test_untraceable_point_is_flagged(self, tiny_plan):
        import copy

        row = copy.deepcopy(tiny_plan["points"][0])
        row["strategy"] = "no_such_strategy_anymore"
        drifted = copy.deepcopy(tiny_plan)
        drifted["points"] = [row]  # don't re-trace the healthy rows
        findings = planner.check_plan_staleness(drifted)
        ours = [f for f in findings if f.where == row["key"]]
        assert len(ours) == 1
        assert ours[0].rule == "stale-plan"
        assert "no longer traces" in ours[0].message

    def test_analyze_cli_refuses_plan_without_collectives_layer(self):
        from distributedpytorch_tpu.analysis import cli

        rc = cli.run(["--layer", "lint", "--plan", "whatever.json"])
        assert rc == cli.EXIT_INFRA
