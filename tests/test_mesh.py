"""The composable N-D mesh engine (parallel/mesh.py + the strategy
refactor onto it).

The load-bearing guarantees, on the 8-device virtual CPU mesh:

* every legacy ``-t`` strategy reproduces **bit-identically** (loss +
  post-step params + BatchNorm stats) as its mesh-config twin — the
  legacy names really are aliases into mesh-shape space;
* NEW hybrid geometries the class-per-strategy design could not express
  (``2x2x1`` = DP x TP, ``2x2x1@fsdp`` = FSDP x TP) build, shard, and
  match the single-device numerics;
* the dptlint comms contracts DERIVE from the sharding rules and equal
  the historical hand-kept tables; mesh specs analyze like strategies;
* the planner enumerates mesh shapes as a first-class axis and ranks at
  least one hybrid above every pure strategy at a pinned
  (batch, HBM-budget) point — with zero device execution;
* the ``mesh_sweep`` bench config and its plan-aware leg mapping.

CI runs this file ahead of tier-1 under pytest-timeout: a mis-ruled
mesh spec feeding the pipeline schedules would DEADLOCK the CPU
collective rendezvous rather than fail.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedpytorch_tpu.config import TrainConfig
from distributedpytorch_tpu.models.unet import UNet
from distributedpytorch_tpu.parallel import build_strategy
from distributedpytorch_tpu.parallel import mesh as mesh_rules
from distributedpytorch_tpu.train.steps import create_train_state

# the strategy-suite rig: tiny shapes, float32 compute for exact twins
H, W, B = 32, 48, 8
WIDTHS = (8, 16)


def _config(method, **kw):
    return TrainConfig(
        train_method=method,
        batch_size=B,
        compute_dtype="float32",
        image_size=(W, H),
        model_widths=WIDTHS,
        ddp_lr_world_size_scaling=False,
        **kw,
    )


def _tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
class TestSpecGrammar:
    def test_parse_and_canonical_round_trip(self):
        for spec, (d, m, s, role, params) in {
            "1x1x1": (1, 1, 1, "channel", "replicate"),
            "8x1x1": (8, 1, 1, "channel", "replicate"),
            "8x1x1@fsdp": (8, 1, 1, "channel", "fsdp"),
            "1x8x1": (1, 8, 1, "channel", "channel"),
            "1x8x1@sp": (1, 8, 1, "spatial", "replicate"),
            "2x4x1@sp": (2, 4, 1, "spatial", "replicate"),
            "2x2x1@fsdp": (2, 2, 1, "channel", "fsdp+channel"),
            "4x1x2": (4, 1, 2, "channel", "replicate"),
        }.items():
            cfg = mesh_rules.parse_mesh_spec(spec)
            assert (cfg.data, cfg.model, cfg.stage) == (d, m, s), spec
            assert cfg.model_role == role, spec
            assert cfg.params == params, spec
            assert cfg.per_process_batch and not cfg.lr_scaling
            # canonical form round-trips to the same config
            assert mesh_rules.parse_mesh_spec(
                mesh_rules.canonical_spec(cfg)
            ) == cfg, spec

    def test_malformed_specs_raise(self):
        for bad in ("2x2", "2x2x2x2", "0x1x1", "2x2x1@zp", "2x2x1@sp+tp",
                    "1x1x1@sp"):
            with pytest.raises(ValueError):
                mesh_rules.parse_mesh_spec(bad)
        assert not mesh_rules.is_mesh_spec("FSDP")
        assert mesh_rules.is_mesh_spec("2x2x1@fsdp")

    def test_pipeline_predicate(self):
        assert mesh_rules.spec_is_pipeline("4x1x2")
        assert not mesh_rules.spec_is_pipeline("4x1x1")
        assert not mesh_rules.spec_is_pipeline("MP")

    def test_legacy_patterns_cover_every_strategy(self):
        from distributedpytorch_tpu.parallel.strategy import STRATEGIES

        assert set(mesh_rules.LEGACY_PATTERNS) == set(STRATEGIES)

    def test_state_leaf_spec_rules(self):
        from jax.sharding import PartitionSpec as P

        kernel = (3, 3, 8, 16)
        tp = mesh_rules.parse_mesh_spec("1x8x1")
        assert mesh_rules.state_leaf_spec(tp, kernel) == P(
            None, None, None, "model")
        fsdp = mesh_rules.parse_mesh_spec("8x1x1@fsdp")
        assert mesh_rules.state_leaf_spec(fsdp, kernel) == P(
            None, None, None, "data")
        both = mesh_rules.parse_mesh_spec("2x2x1@fsdp")
        # channel takes the out axis, fsdp the largest REMAINING axis
        assert mesh_rules.state_leaf_spec(both, kernel) == P(
            None, None, "data", "model")
        assert mesh_rules.state_leaf_spec(both, ()) == P()
        # indivisible leaves replicate (the Cout=1 segmap head)
        assert mesh_rules.state_leaf_spec(tp, (3, 3, 8, 1)) == P(
            None, None, None, None)


# ---------------------------------------------------------------------------
class TestLegacyTwins:
    """Every legacy ``-t`` strategy == its mesh-config twin,
    bit-identically: same mesh, same shardings, same compiled step."""

    @pytest.fixture(scope="class")
    def model(self):
        return UNet(dtype=jnp.float32, widths=WIDTHS)

    @pytest.fixture(scope="class")
    def params(self, model):
        return model.init(jax.random.key(0), jnp.zeros((1, H, W, 3)))["params"]

    @pytest.fixture(scope="class")
    def batch(self):
        rng = np.random.default_rng(0)
        return {
            "image": rng.random((B, H, W, 3), dtype=np.float32),
            "mask": (rng.random((B, H, W)) > 0.5).astype(np.int32),
        }

    def _stepped(self, method, model, params, batch, **kw):
        cfg = _config(method, **kw)
        strategy = build_strategy(cfg)
        p = jax.tree.map(jnp.array, params)
        state, tx = create_train_state(p, cfg.learning_rate, cfg.weight_decay)
        state = strategy.place_state(state)
        step = strategy.build_train_step(model, tx)
        new_state, loss = step(state, strategy.place_batch(batch))
        return strategy, jax.device_get(new_state.params), np.asarray(loss)

    #: legacy name -> its concrete mesh-config twin on the 8-device mesh
    GSPMD_TWINS = [
        ("singleGPU", "1x1x1"),
        ("DP", "8x1x1"),
        ("DDP", "8x1x1"),
        ("TP", "1x8x1"),
        ("FSDP", "8x1x1@fsdp"),
        ("SP", "1x8x1@sp"),
        ("DDP_SP", "2x4x1@sp"),
    ]

    @pytest.mark.parametrize("legacy,spec", GSPMD_TWINS)
    def test_gspmd_strategies_bit_identical(
        self, legacy, spec, model, params, batch
    ):
        ls, lp, ll = self._stepped(legacy, model, params, batch)
        ss, sp_, sl = self._stepped(spec, model, params, batch)
        assert mesh_rules.canonical_spec(ls.mesh_config) == ss.name == spec
        if ls.mesh is not None:
            assert dict(ls.mesh.shape) == dict(ss.mesh.shape)
        np.testing.assert_array_equal(ll, sl)
        _tree_equal(lp, sp_)

    @pytest.mark.parametrize("legacy,spec", [("MP", "1x1x2"),
                                             ("DDP_MP", "4x1x2")])
    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_pipeline_strategies_bit_identical(self, legacy, spec, schedule):
        """Both schedules, on the 1-level pipeline rig (the schedule is
        depth-independent and the differentiated shard_map is the
        expensive compile — tests/test_strategies.py's rationale)."""
        ph, pw = 16, 24
        model = UNet(dtype=jnp.float32, widths=(8,))
        params = model.init(
            jax.random.key(0), jnp.zeros((1, ph, pw, 3))
        )["params"]
        rng = np.random.default_rng(0)
        batch = {
            "image": rng.random((B, ph, pw, 3), dtype=np.float32),
            "mask": (rng.random((B, ph, pw)) > 0.5).astype(np.int32),
        }
        outs = {}
        for method in (legacy, spec):
            cfg = TrainConfig(
                train_method=method, batch_size=B, compute_dtype="float32",
                image_size=(pw, ph), model_widths=(8,),
                pipeline_schedule=schedule,
                ddp_lr_world_size_scaling=False,
            )
            strategy = build_strategy(cfg)
            p = jax.tree.map(jnp.array, params)
            state, tx = create_train_state(
                p, cfg.learning_rate, cfg.weight_decay
            )
            state = strategy.place_state(state)
            step = strategy.build_train_step(model, tx)
            new_state, loss = step(state, strategy.place_batch(batch))
            outs[method] = (np.asarray(loss), jax.device_get(new_state.params))
        np.testing.assert_array_equal(outs[legacy][0], outs[spec][0])
        _tree_equal(outs[legacy][1], outs[spec][1])

    def test_batchnorm_stats_bit_identical(self):
        """The stateful (milesial/BatchNorm) pipeline: loss + grads'
        effect (post-step params) + running stats all bit-identical
        between -t MP and its 1x1x2 twin."""
        from distributedpytorch_tpu.models.milesial import (
            MilesialUNet,
            init_milesial,
        )

        model = MilesialUNet(widths=(4, 8), dtype=jnp.float32)
        params, stats = init_milesial(model, jax.random.key(0), input_hw=(8, 8))
        rng = np.random.default_rng(5)
        batch = {
            "image": rng.random((4, 8, 8, 3), dtype=np.float32),
            "mask": (rng.random((4, 8, 8)) > 0.5).astype(np.int32),
        }
        outs = {}
        for method in ("MP", "1x1x2"):
            cfg = TrainConfig(
                train_method=method, batch_size=4, compute_dtype="float32",
                image_size=(8, 8), model_arch="milesial", model_widths=(4, 8),
                num_microbatches=1,
            )
            strategy = build_strategy(cfg)
            p = jax.tree.map(jnp.array, params)
            state, tx = create_train_state(
                p, cfg.learning_rate, cfg.weight_decay,
                model_state=jax.tree.map(jnp.array, stats),
            )
            state = strategy.place_state(state)
            step = strategy.build_train_step(model, tx)
            new_state, loss = step(state, strategy.place_batch(batch))
            outs[method] = (
                np.asarray(loss),
                jax.device_get(new_state.params),
                jax.device_get(new_state.model_state),
            )
        np.testing.assert_array_equal(outs["MP"][0], outs["1x1x2"][0])
        _tree_equal(outs["MP"][1], outs["1x1x2"][1])
        _tree_equal(outs["MP"][2], outs["1x1x2"][2])

    def test_semantics_flags_match_legacy(self, model, params, batch):
        # DP keeps the torch-DP global-batch convention; specs use the
        # multi-process one (identical on one process); lr quirk stays
        # a DDP-family property
        dp = build_strategy(_config("DP"))
        twin = build_strategy(_config("8x1x1"))
        assert dp.global_batch_size == twin.global_batch_size == B
        assert dp.drop_last_train and twin.drop_last_train
        ddp = build_strategy(
            TrainConfig(train_method="DDP", batch_size=B,
                        compute_dtype="float32", image_size=(W, H),
                        model_widths=WIDTHS)
        )
        assert ddp.lr_for(1e-4) == pytest.approx(8e-4)  # quirk 2 kept
        assert twin.lr_for(1e-4) == pytest.approx(1e-4)  # specs: no quirk


# ---------------------------------------------------------------------------
class TestNewGeometries:
    """Mesh points the class-per-strategy design could not express."""

    @pytest.fixture(scope="class")
    def model(self):
        return UNet(dtype=jnp.float32, widths=WIDTHS)

    @pytest.fixture(scope="class")
    def params(self, model):
        return model.init(jax.random.key(0), jnp.zeros((1, H, W, 3)))["params"]

    @pytest.fixture(scope="class")
    def batch(self):
        rng = np.random.default_rng(0)
        return {
            "image": rng.random((B, H, W, 3), dtype=np.float32),
            "mask": (rng.random((B, H, W)) > 0.5).astype(np.int32),
        }

    def test_data_x_tensor_matches_single_device(self, model, params, batch):
        """4x2x1 (DP x TP): batch over 'data', out-channels over
        'model', one Adam step lands where singleGPU does — the
        headline geometry the refactor unlocks."""
        outs = {}
        for method in ("singleGPU", "4x2x1"):
            cfg = _config(method)
            strategy = build_strategy(cfg)
            p = jax.tree.map(jnp.array, params)
            state, tx = create_train_state(
                p, cfg.learning_rate, cfg.weight_decay
            )
            state = strategy.place_state(state)
            step = strategy.build_train_step(model, tx)
            new_state, loss = step(state, strategy.place_batch(batch))
            outs[method] = (float(loss), jax.device_get(new_state.params))
            # the step takes its own output: GSPMD may pick output
            # shardings that differ from the inputs'
            _, again = step(new_state, strategy.place_batch(batch))
            assert np.isfinite(float(again))
        np.testing.assert_allclose(
            outs["4x2x1"][0], outs["singleGPU"][0], rtol=1e-5, atol=1e-6
        )
        for a, b in zip(jax.tree.leaves(outs["singleGPU"][1]),
                        jax.tree.leaves(outs["4x2x1"][1])):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=3e-4
            )

    def test_fsdp_x_tensor_shards_both_axes(self, params):
        """2x2x1@fsdp: the big kernels shard out-channels over 'model'
        AND their largest remaining axis over 'data' — per-device state
        bytes land near total/4, not near the replicated total."""
        strategy = build_strategy(_config("2x2x1@fsdp"))
        assert dict(strategy.mesh.shape) == {"data": 2, "model": 2}
        state, _ = create_train_state(jax.tree.map(jnp.array, params), 1e-4)
        placed = strategy.place_state(state)
        leaves = [x for x in jax.tree.leaves(placed.params) if x.ndim == 4]
        big = max(leaves, key=lambda x: x.size)
        shard = next(iter(big.addressable_shards))
        assert shard.data.size * 4 == big.size  # split on BOTH axes
        total, per_dev = 0, {}
        for leaf in jax.tree.leaves(placed):
            if not hasattr(leaf, "addressable_shards"):
                continue
            total += leaf.size * leaf.dtype.itemsize
            for sh in leaf.addressable_shards:
                per_dev[sh.device] = (
                    per_dev.get(sh.device, 0)
                    + sh.data.size * sh.data.dtype.itemsize
                )
        assert max(per_dev.values()) <= total / 4 * 1.6

    def test_infeasible_geometries_fail_loudly(self):
        # model x stage with the channel role builds now (PR 19 in-stage
        # sharding, tests/test_hybrid_pipeline.py); spatial-in-stage is
        # the one remaining refusal
        with pytest.raises(ValueError, match="spatial.*not executable"):
            build_strategy(_config("2x2x2@sp"))
        with pytest.raises(ValueError, match="devices"):
            build_strategy(_config("9x1x1"))
        with pytest.raises(ValueError, match="never shrink"):
            build_strategy(_config("3x1x1"))  # batch 8 % 3 != 0
        with pytest.raises(ValueError, match="rows"):
            build_strategy(_config("1x3x1@sp"))  # 8 deep rows % 3 != 0
        with pytest.raises(ValueError, match="Unknown train method"):
            build_strategy(_config("2x2"))  # not a spec, not a name

    def test_pipeline_data_axis_derives_from_mesh(self):
        """The unified data-axis plumbing: the pipeline builders derive
        the hybrid 'data' axis from the mesh itself (the strategy layer
        no longer threads it by hand) — the traced program of the auto
        default equals the explicit data_axis='data' one."""
        from distributedpytorch_tpu.analysis.collectives import (
            extract_collectives,
        )
        from distributedpytorch_tpu.parallel.pipeline import (
            make_pipeline_loss_fn,
        )

        ph, pw = 16, 24
        model = UNet(dtype=jnp.float32, widths=(8,))
        params = model.init(
            jax.random.key(0), jnp.zeros((1, ph, pw, 3))
        )["params"]
        strategy = build_strategy(
            TrainConfig(train_method="4x1x2", batch_size=B,
                        compute_dtype="float32", image_size=(pw, ph),
                        model_widths=(8,))
        )
        prepped = {
            "image": jax.ShapeDtypeStruct((B, ph, pw, 3), jnp.float32),
            "mask": jax.ShapeDtypeStruct((B, ph, pw, 1), jnp.float32),
        }
        programs = {}
        for label, kw in (("auto", {}), ("explicit", {"data_axis": "data"})):
            loss_fn = make_pipeline_loss_fn(
                model, strategy.mesh, num_microbatches=2, **kw
            )
            jaxpr = jax.make_jaxpr(loss_fn)(params, prepped)
            programs[label] = [c.signature for c in extract_collectives(jaxpr)]
        assert programs["auto"] == programs["explicit"]
        assert any(
            "data" in c[1] for c in programs["auto"] if c[0] == "psum"
        )


# ---------------------------------------------------------------------------
class TestDerivedContracts:
    def test_derived_tables_equal_the_historical_literals(self):
        from distributedpytorch_tpu.analysis import collectives as C

        assert C.EXPECTED_HLO_COLLECTIVES == {
            "DP": frozenset({"all-reduce"}),
            "SP": frozenset({"collective-permute"}),
            "FSDP": frozenset({"all-gather"}),
            "MP": frozenset({"collective-permute"}),
            "DDP_MP": frozenset({"collective-permute", "all-reduce"}),
        }
        assert set(C.JAXPR_CONTRACTS) == {
            ("DP", None), ("SP", None), ("TP", None), ("FSDP", None),
            ("MP", "gpipe"), ("MP", "1f1b"),
            ("DDP_MP", "gpipe"), ("DDP_MP", "1f1b"),
        }
        for key in (("DP", None), ("SP", None), ("TP", None), ("FSDP", None)):
            assert C.JAXPR_CONTRACTS[key] == ()
        reqs = C.JAXPR_CONTRACTS[("DDP_MP", "1f1b")]
        assert any(
            r.grad_output and "data" in r.axes and r.kind == "psum"
            for r in reqs
        )

    def test_mesh_spec_contract_derives_on_the_fly(self):
        from distributedpytorch_tpu.analysis import collectives as C

        reqs = C._contract_requirements("4x1x2", "1f1b")
        assert any(
            r.grad_output and r.axes == frozenset({"stage", "data"})
            for r in reqs
        )
        assert C._contract_requirements("2x2x1", None) == ()
        # hlo derivation: a channel hybrid keeps its data-axis exact
        # requirement AND adds the any-of channel tier — a DP x TP
        # point whose data all-reduce regresses must fail even while
        # channel collectives satisfy any-of
        fsdp_tp = mesh_rules.parse_mesh_spec("2x2x1@fsdp")
        assert mesh_rules.derive_hlo_contract(fsdp_tp) == frozenset(
            {"all-gather"})
        assert mesh_rules.channel_comms_required(fsdp_tp)
        dp_tp = mesh_rules.parse_mesh_spec("2x2x1")
        assert mesh_rules.derive_hlo_contract(dp_tp) == frozenset(
            {"all-reduce"})
        sp_hybrid = mesh_rules.parse_mesh_spec("2x4x1@sp")
        assert mesh_rules.derive_hlo_contract(sp_hybrid) == frozenset(
            {"collective-permute", "all-reduce"})
        assert not mesh_rules.channel_comms_required(sp_hybrid)

    def test_channel_hybrid_hlo_contract_holds_on_a_real_compile(self):
        """The derived DP x TP contract against XLA's actual output:
        the compiled 2x2x1 train step must show the data-axis
        all-reduce AND a channel collective (AOT compile, zero
        execution) — and check_hlo_contract agrees."""
        from distributedpytorch_tpu.analysis import collectives as C

        ops = C.hlo_collectives("2x2x1")
        assert "all-reduce" in ops
        assert ops & C.TP_HLO_ANY_OF
        assert C.check_hlo_contract("2x2x1", None) == []

    def test_analyzer_accepts_mesh_specs(self):
        """analyze_combo on a mesh spec: full trace + derived-contract
        check, clean — the surface `analyze --mesh` / the preflights
        use for mesh-config launches. Odd geometries whose data axis
        doesn't divide the rig's default batch (3x1x2 — a default_specs
        cell on 6/7-device pools) round the rig batch up instead of
        refusing on the rig's own choice."""
        from distributedpytorch_tpu.analysis import collectives as C

        assert C.analyze_combo("2x1x2", "gpipe", rank_check=False) == []
        assert C.analyze_combo("3x1x2", "gpipe", rank_check=False) == []

    def test_unbuildable_spec_is_a_finding_not_a_crash(self):
        """A parseable spec the rig cannot BUILD (spatial-in-stage, the
        one refusal left after PR 19's in-stage sharding) refuses with
        an actionable mesh-config finding — the launch preflights turn
        it into a pre-spawn refusal, and an `analyze --mesh` run keeps
        its other combos' results instead of aborting as infra."""
        from distributedpytorch_tpu.analysis import collectives as C

        findings = C.analyze_combo("1x2x2@sp", "gpipe", rank_check=False)
        assert len(findings) == 1
        assert findings[0].rule == "mesh-config"
        assert "not executable" in findings[0].message

    def test_hybrid_mesh_specs_analyze_clean(self):
        """The PR 19 acceptance points pass the static checker with
        non-exempt derived contracts (the in-stage all_gather rows are
        REQUIRED — see _contract_requirements)."""
        from distributedpytorch_tpu.analysis import collectives as C
        from distributedpytorch_tpu.parallel import mesh as M

        # three combos cover every spec and both schedules (the full
        # 3x2 cross product re-traces the same stage graphs; the CI
        # pipeline-schedules step compiles them all anyway)
        for spec, schedule in (
            ("2x2x2", "gpipe"),
            ("1x2x2@fsdp", "1f1b"),
            ("2x2x2@fsdp", "1f1b"),
        ):
            assert C.analyze_combo(spec, schedule, rank_check=False) == []
        cfg = M.parse_mesh_spec("2x2x2")
        rows = M.derive_jaxpr_contract(cfg, "gpipe")
        assert any(
            kind == "all_gather" and set(axes) == {"model"}
            for kind, axes, *_ in rows
        )
        cfg_f = M.parse_mesh_spec("2x2x2@fsdp")
        rows_f = M.derive_jaxpr_contract(cfg_f, "1f1b")
        assert any(
            kind == "all_gather" and set(axes) == {"data"}
            for kind, axes, *_ in rows_f
        )

    def test_analyze_cli_grows_mesh_flag(self):
        from distributedpytorch_tpu.analysis import cli as acli

        args = acli.build_parser().parse_args(
            ["--mesh", "2x1x2", "1x2x1", "--layer", "collectives"]
        )
        assert args.mesh == ["2x1x2", "1x2x1"]

# ---------------------------------------------------------------------------
class TestTopologyManifest:
    def test_topology_records_mesh_spec(self):
        for method, spec in (
            ("DP", "8x1x1"), ("FSDP", "8x1x1@fsdp"), ("DDP_MP", "4x1x2"),
            ("singleGPU", "1x1x1"), ("4x1x2", "4x1x2"),
        ):
            topo = build_strategy(_config(method)).topology()
            assert topo["mesh_spec"] == spec, method
            assert isinstance(topo["mesh"], dict)

    def test_manifest_roundtrip_carries_mesh_spec(self, tmp_path):
        from distributedpytorch_tpu.checkpoint import (
            peek_topology,
            save_checkpoint,
        )

        strategy = build_strategy(_config("2x1x2"))
        path = str(tmp_path / "m.ckpt")
        save_checkpoint(
            path, {"w": np.ones((2, 2), np.float32)},
            topology=strategy.topology(),
        )
        topo = peek_topology(path)
        assert topo["mesh_spec"] == "2x1x2"
        assert topo["mesh"] == {"data": 2, "stage": 2}


# ---------------------------------------------------------------------------
class TestPlannerMeshAxis:
    """Mesh shape as a first-class planner axis, zero device execution
    throughout (make_jaxpr + lower().compile() only)."""

    TINY = dict(image_size=(48, 32), widths=(8, 16))

    def _grid(self, **overrides):
        base = dict(
            strategies=("singleGPU", "MP"),
            meshes=("2x1x2",),
            schedules=("gpipe",),
            microbatches=(2,),
            s2d_levels=(0,),
            remats=(False,),
            batches=(8,),
            dtypes=("bf16",),
            hbm_gb=16.0,
            **self.TINY,
        )
        base.update(overrides)
        return base

    @pytest.fixture(scope="class")
    def mesh_plan(self):
        from distributedpytorch_tpu.analysis import planner

        return planner.plan(**self._grid())

    def test_mesh_points_enumerate_with_schedule_axes(self, mesh_plan):
        keys = [r["key"] for r in mesh_plan["points"]]
        assert "2x1x2/gpipe/m2/s2d0/remat-off/b8/bf16" in keys
        assert mesh_plan["grid"]["meshes"] == ["2x1x2"]
        hybrid = next(
            r for r in mesh_plan["points"] if r["strategy"] == "2x1x2"
        )
        assert hybrid["feasible"]
        # the pipelined hybrid traces a real jaxpr comms program
        assert hybrid["predicted"]["comms_model"] == "jaxpr"
        assert hybrid["predicted"]["comms_bytes"] > 0

    def test_hybrid_ranks_above_every_pure_at_the_wall(self, mesh_plan):
        """THE acceptance pin: at an HBM budget sized just above the
        hybrid's traced liveness, the hybrid mesh shape ranks ABOVE
        every pure strategy — the pures either exceed the budget
        (rejected) or carry a worse liveness-pressured cost."""
        from distributedpytorch_tpu.analysis import planner

        by_strategy = {r["strategy"]: r for r in mesh_plan["points"]}
        hybrid_live = by_strategy["2x1x2"]["predicted"]["live_bytes"]
        pure_lives = [
            r["predicted"]["live_bytes"]
            for r in mesh_plan["points"] if r["strategy"] != "2x1x2"
        ]
        # the premise the budget choice rests on: the hybrid's
        # per-device liveness undercuts every pure point's
        assert hybrid_live < min(pure_lives)
        wall = planner.plan(**self._grid(
            hbm_gb=hybrid_live * 1.05 / 2**30,
        ))
        rows = {r["strategy"]: r for r in wall["points"]}
        hybrid = rows.pop("2x1x2")
        assert hybrid["feasible"] and hybrid["rank"] == 0
        for strategy, row in rows.items():
            assert (
                row["feasible"] is False
                or row["rank"] > hybrid["rank"]
            ), strategy
        assert wall["ranking"][0].startswith("2x1x2/")

    # (the matching positive flip — 2x2x2 now plans FEASIBLE with the
    # in-stage terms in its breakdown — is pinned where the ISSUE asks
    # for it: tests/test_planner.py::TestModelStagePlannerFlip)
    def test_spatial_in_stage_rejects_as_config(self):
        from distributedpytorch_tpu.analysis import planner

        p = planner.plan(**self._grid(
            strategies=(), meshes=("1x2x2@sp",),
        ))
        row = p["points"][0]
        assert row["feasible"] is False
        # the static pass's mesh-config finding (or, were the static
        # pass skipped, the strategy's own construction refusal) — an
        # honest reject either way, never a crash
        assert row["reject"].startswith(("static:", "config:"))
        assert "not executable" in row["reject"]

    def test_gspmd_hybrid_gets_analytic_comms(self):
        from distributedpytorch_tpu.analysis import planner

        p = planner.plan(**self._grid(strategies=(), meshes=("2x2x1",)))
        row = p["points"][0]
        assert row["feasible"]
        predicted = row["predicted"]
        assert predicted["comms_model"] == "analytic"
        # data-axis grad psum AND model-axis channel gathers both count
        assert predicted["comms_bytes"] > 0

    def test_sp_tp_comms_now_modeled(self):
        """The cost-model satellite: pure SP/TP points no longer rank
        with comms_model 'none' — the halo / channel-gather terms are
        analytic."""
        from distributedpytorch_tpu.analysis import cost_model as cm

        halo = cm.mesh_comms_program(
            model=4, model_role="spatial",
            level_planes=((1000, 10), (500, 5)),
        )
        assert halo and all(k == "ppermute" for k, _, _ in halo)
        chan = cm.mesh_comms_program(
            model=4, model_role="channel",
            level_planes=((1000, 10),),
        )
        assert chan and all(k == "all_gather" for k, _, _ in chan)
        # the payload is the FULL gathered plane (the all-gather
        # convention collective_time's ring factor expects) — not the
        # per-device shard, which would discount channel traffic m-fold
        assert all(payload == 1000 for _, payload, _ in chan)
        # the data-axis terms match the legacy strategy-name surface
        assert cm.mesh_comms_program(
            data=8, params_rule="fsdp", param_storage_bytes=100,
            grad_bytes=400,
        ) == cm.gspmd_comms_program("FSDP", 100, 400, 8)
