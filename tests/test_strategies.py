"""Strategy equivalence tests on the 8-device virtual CPU mesh.

The load-bearing guarantee of the one-trainer design: every strategy
computes the SAME loss and the SAME gradients as the single-device step
(up to float tolerance) — DP/DDP via GSPMD sharding, MP/DDP_MP via the
explicit shard_map GPipe schedule (SURVEY.md §7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedpytorch_tpu.config import TrainConfig
from distributedpytorch_tpu.models.unet import UNet
from distributedpytorch_tpu.ops.losses import bce_dice_loss
from distributedpytorch_tpu.parallel import build_strategy
from distributedpytorch_tpu.parallel.pipeline import (
    make_pipeline_forward_fn,
    make_pipeline_loss_fn,
)
from distributedpytorch_tpu.train.steps import create_train_state, make_train_step

# Small shapes; float32 compute for exact comparisons. B=8 covers every
# strategy on the 8-device mesh (hybrid needs data_shards(4) ×
# microbatches(2) = 8). The model under test is a 2-level narrow UNet
# (WIDTHS): these tests exercise the parallelism machinery, where the model
# is a payload — the reference-sized model's own goldens live in
# test_model.py, and compiling 7.76M-param graphs ~20 times here was most
# of the old suite's 13-minute wall time.
H, W, B = 32, 48, 8
WIDTHS = (8, 16)


@pytest.fixture(scope="module")
def model():
    return UNet(dtype=jnp.float32, widths=WIDTHS)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0), jnp.zeros((1, H, W, 3)))["params"]


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    return {
        "image": rng.random((B, H, W, 3), dtype=np.float32),
        "mask": (rng.random((B, H, W)) > 0.5).astype(np.int32),
    }


def _prep(batch):
    return {
        "image": jnp.asarray(batch["image"]),
        "mask": jnp.asarray(batch["mask"])[..., None].astype(jnp.float32),
    }


def _ref_loss_and_grads(model, params, batch):
    def loss_fn(p):
        preds = model.apply({"params": p}, jnp.asarray(batch["image"]))
        target = jnp.asarray(batch["mask"])[..., None].astype(jnp.float32)
        return bce_dice_loss(preds, target)

    return jax.jit(jax.value_and_grad(loss_fn))(params)


def _tree_allclose(a, b, rtol=1e-5, atol=1e-6):
    flat_a, _ = jax.tree.flatten(a)
    flat_b, _ = jax.tree.flatten(b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)


def _config(method, **kw):
    return TrainConfig(
        train_method=method,
        batch_size=B,
        compute_dtype="float32",
        image_size=(W, H),
        model_widths=WIDTHS,
        **kw,
    )


class TestPipelineNumerics:
    """The GPipe schedule's loss/grad equivalence. These use a 1-level UNet
    at 16×24 — the schedule (stage masking, ppermute chains, microbatch
    statistics, its transpose under autodiff) is depth-independent, and the
    differentiated shard_map scan is by far the suite's most expensive
    compile: the 2-level 32×48 variant of the grad test alone cost 108 s of
    single-core XLA time."""

    P_WIDTHS = (8,)
    PH, PW = 16, 24

    @pytest.fixture(scope="class")
    def pmodel(self):
        return UNet(dtype=jnp.float32, widths=self.P_WIDTHS)

    @pytest.fixture(scope="class")
    def pparams(self, pmodel):
        return pmodel.init(jax.random.key(0), jnp.zeros((1, self.PH, self.PW, 3)))[
            "params"
        ]

    @pytest.fixture(scope="class")
    def pbatch(self):
        rng = np.random.default_rng(0)
        return {
            "image": rng.random((B, self.PH, self.PW, 3), dtype=np.float32),
            "mask": (rng.random((B, self.PH, self.PW)) > 0.5).astype(np.int32),
        }

    def _pconfig(self, method, **kw):
        return TrainConfig(
            train_method=method,
            batch_size=B,
            compute_dtype="float32",
            image_size=(self.PW, self.PH),
            model_widths=self.P_WIDTHS,
            **kw,
        )

    def test_pipeline_loss_and_grads_match_plain(self, pmodel, pparams, pbatch):
        """Loss AND grads in one value_and_grad — one XLA compile covers
        both equivalence claims (separate tests each paid the full compile
        of the pipelined backward, the old suite's single slowest item)."""
        strat = build_strategy(self._pconfig("MP"))
        loss_fn = make_pipeline_loss_fn(pmodel, strat.mesh, num_microbatches=2)
        ref_loss, ref_grads = _ref_loss_and_grads(pmodel, pparams, pbatch)
        prepped = _prep(pbatch)
        pipe_loss, pipe_grads = jax.jit(
            jax.value_and_grad(lambda p: loss_fn(p, prepped))
        )(pparams)
        np.testing.assert_allclose(
            float(pipe_loss), float(ref_loss), rtol=1e-5, atol=1e-6
        )
        _tree_allclose(ref_grads, pipe_grads, rtol=2e-4, atol=1e-5)

    def test_pipeline_forward_matches_plain(self, pmodel, pparams, pbatch):
        strat = build_strategy(self._pconfig("MP"))
        fwd = make_pipeline_forward_fn(pmodel, strat.mesh, num_microbatches=2)
        ref = pmodel.apply({"params": pparams}, jnp.asarray(pbatch["image"]))
        out = jax.jit(fwd)(pparams, jnp.asarray(pbatch["image"]))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)

    def test_four_microbatches(self, pmodel, pparams, pbatch):
        strat = build_strategy(self._pconfig("MP", num_microbatches=4))
        loss_fn = make_pipeline_loss_fn(pmodel, strat.mesh, num_microbatches=4)
        ref_loss, _ = _ref_loss_and_grads(pmodel, pparams, pbatch)
        prepped = _prep(pbatch)
        np.testing.assert_allclose(
            float(jax.jit(loss_fn)(pparams, prepped)), float(ref_loss),
            rtol=1e-5, atol=1e-6,
        )

    def test_hybrid_loss_and_grads(self, pmodel, pparams, pbatch):
        strat = build_strategy(self._pconfig("DDP_MP"))
        assert dict(strat.mesh.shape) == {"data": 4, "stage": 2}
        loss_fn = make_pipeline_loss_fn(
            pmodel, strat.mesh, num_microbatches=2, data_axis="data"
        )
        ref_loss, ref_grads = _ref_loss_and_grads(pmodel, pparams, pbatch)
        prepped = _prep(pbatch)
        pipe_loss, pipe_grads = jax.jit(
            jax.value_and_grad(lambda p: loss_fn(p, prepped))
        )(pparams)
        np.testing.assert_allclose(float(pipe_loss), float(ref_loss), rtol=1e-5, atol=1e-6)
        _tree_allclose(ref_grads, pipe_grads, rtol=2e-4, atol=1e-5)

    def test_four_stage_loss_and_grads(self, pbatch):
        """S=4 over a 2-level model (5 segments: enc1, enc2, mid, dec1,
        dec2+head): loss AND grads match the plain step — the generalized
        schedule's warmup/drain masking, per-edge ppermutes, and their
        transposes are all load-bearing here."""
        from distributedpytorch_tpu.parallel.pipeline import default_cuts

        model = UNet(dtype=jnp.float32, widths=(8, 16))
        assert model.num_segments == 5
        params = model.init(
            jax.random.key(0), jnp.zeros((1, self.PH, self.PW, 3))
        )["params"]
        cfg = TrainConfig(
            train_method="MP", batch_size=B, compute_dtype="float32",
            image_size=(self.PW, self.PH), model_widths=(8, 16),
            num_stages=4, num_microbatches=4,
        )
        strat = build_strategy(cfg)
        assert dict(strat.mesh.shape) == {"stage": 4}
        # remainder lands on the LAST stage (stage 0's shallow encoder
        # level is the FLOP-heaviest segment; the slowest stage sets
        # throughput)
        assert default_cuts(5, 4) == (1, 2, 3)
        loss_fn = make_pipeline_loss_fn(
            model, strat.mesh, num_microbatches=4
        )
        ref_loss, ref_grads = _ref_loss_and_grads(model, params, pbatch)
        prepped = _prep(pbatch)
        pipe_loss, pipe_grads = jax.jit(
            jax.value_and_grad(lambda p: loss_fn(p, prepped))
        )(params)
        np.testing.assert_allclose(
            float(pipe_loss), float(ref_loss), rtol=1e-5, atol=1e-6
        )
        _tree_allclose(ref_grads, pipe_grads, rtol=2e-4, atol=1e-5)

    def test_three_stage_forward_and_custom_cuts(self, pmodel, pparams, pbatch):
        """S=3 on the 1-level model (3 segments, one per stage) with
        explicit cuts; the pipelined forward must equal the plain apply."""
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:3]), ("stage",))
        fwd = make_pipeline_forward_fn(
            pmodel, mesh, num_microbatches=2, cuts=(1, 2)
        )
        ref = pmodel.apply({"params": pparams}, jnp.asarray(pbatch["image"]))
        out = jax.jit(fwd)(pparams, jnp.asarray(pbatch["image"]))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6
        )

    def test_bad_cuts_raise(self, pmodel):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("stage",))
        with pytest.raises(ValueError, match="cuts"):
            make_pipeline_loss_fn(pmodel, mesh, cuts=(0,))
        with pytest.raises(ValueError, match="cuts"):
            make_pipeline_loss_fn(pmodel, mesh, cuts=(1, 2))
        with pytest.raises(ValueError, match="num_stages"):
            make_pipeline_loss_fn(
                pmodel, Mesh(np.array(jax.devices()[:4]), ("stage",)), cuts=None
            )

    def test_1f1b_grads_equal_gpipe(self, pmodel, pparams, pbatch):
        """The 1F1B schedule (explicit per-tick vjp backward,
        parallel/pipeline.py) lands on the SAME loss and gradients as the
        gpipe schedule it replaces — the full (S, M) grid and the memory
        bound live in tests/test_pipeline_1f1b.py; this is the
        strategy-suite anchor the ROADMAP names."""
        from distributedpytorch_tpu.parallel.pipeline import (
            make_pipeline_value_and_grad_fn,
        )

        strat = build_strategy(self._pconfig("MP"))
        prepped = _prep(pbatch)
        outs = {}
        for schedule in ("gpipe", "1f1b"):
            fn = make_pipeline_value_and_grad_fn(
                pmodel, strat.mesh, num_microbatches=2, schedule=schedule
            )
            loss, grads, _ = jax.jit(lambda p, b, _f=fn: _f(p, None, b))(
                pparams, prepped
            )
            outs[schedule] = (float(loss), grads)
        np.testing.assert_allclose(
            outs["1f1b"][0], outs["gpipe"][0], rtol=1e-6, atol=1e-7
        )
        _tree_allclose(outs["gpipe"][1], outs["1f1b"][1], rtol=2e-4, atol=1e-5)

    def test_milesial_under_mp_grads_match_plain_step(self, devices):
        """BatchNorm threading through the pipeline (the ROADMAP-listed
        proof): milesial under MP at one microbatch — where pipeline BN
        statistics cover exactly the batch the plain step's do — computes
        the plain single-device stateful step's loss, gradients, and
        updated running stats. M>1 per-microbatch semantics are pinned in
        tests/test_pipeline_1f1b.py::TestBatchNormThreading."""
        from distributedpytorch_tpu.models.milesial import (
            MilesialUNet,
            init_milesial,
        )
        from distributedpytorch_tpu.parallel.pipeline import (
            make_pipeline_value_and_grad_fn,
        )

        model = MilesialUNet(widths=(4, 8), dtype=jnp.float32)
        params, stats = init_milesial(model, jax.random.key(0), input_hw=(8, 8))
        rng = np.random.default_rng(5)
        batch = {
            "image": jnp.asarray(rng.random((4, 8, 8, 3), dtype=np.float32)),
            "mask": jnp.asarray(
                (rng.random((4, 8, 8)) > 0.5).astype(np.float32)
            )[..., None],
        }

        def plain(p):
            preds, upd = model.apply(
                {"params": p, "batch_stats": stats}, batch["image"],
                train=True, mutable=["batch_stats"],
            )
            return bce_dice_loss(preds, batch["mask"]), upd["batch_stats"]

        (ref_loss, ref_stats), ref_grads = jax.jit(
            jax.value_and_grad(plain, has_aux=True)
        )(params)

        cfg = TrainConfig(
            train_method="MP", batch_size=4, compute_dtype="float32",
            image_size=(8, 8), model_arch="milesial", model_widths=(4, 8),
            num_microbatches=1,
        )
        strat = build_strategy(cfg)
        fn = make_pipeline_value_and_grad_fn(
            model, strat.mesh, num_microbatches=1, schedule="gpipe"
        )
        loss, grads, new_stats = jax.jit(fn)(params, stats, batch)
        np.testing.assert_allclose(
            float(loss), float(ref_loss), rtol=1e-5, atol=1e-6
        )
        _tree_allclose(ref_grads, grads, rtol=2e-4, atol=1e-5)
        _tree_allclose(
            jax.device_get(ref_stats), jax.device_get(new_stats),
            rtol=1e-5, atol=1e-6,
        )



class TestStrategySteps:
    """Full train-step equivalence: one Adam step under each strategy lands
    on the same params."""

    def _stepped_params(self, strategy, model, params, batch, cfg):
        # copy: the jitted step donates its state, and place_state may alias
        # the shared fixture arrays when they already sit on the right device
        params = jax.tree.map(jnp.array, params)
        state, tx = create_train_state(params, cfg.learning_rate, cfg.weight_decay)
        state = strategy.place_state(state)
        step = strategy.build_train_step(model, tx)
        placed = strategy.place_batch(batch)
        new_state, loss = step(state, placed)
        return jax.device_get(new_state.params), float(loss)

    @pytest.fixture(scope="class")
    def single_result(self, model, params, batch):
        cfg = _config("singleGPU")
        strat = build_strategy(cfg)
        return self._stepped_params(strat, model, params, batch, cfg)

    @pytest.mark.parametrize(
        "method", ["DP", "DDP", "MP", "DDP_MP", "SP", "DDP_SP", "TP", "FSDP"]
    )
    def test_step_matches_single(self, method, model, params, batch, single_result):
        cfg = _config(method, ddp_lr_world_size_scaling=False)
        strat = build_strategy(cfg)
        got_params, got_loss = self._stepped_params(strat, model, params, batch, cfg)
        ref_params, ref_loss = single_result
        np.testing.assert_allclose(got_loss, ref_loss, rtol=1e-5, atol=1e-6)
        # Post-step params can differ by up to 2·lr where reduction-order
        # noise flips the sign of a near-zero grad (Adam normalizes every
        # grad to ±lr). atol 3e-4 (≈3·lr) still catches wrong-lr / wrong-
        # batch plumbing; exact GRAD equality is covered in
        # TestPipelineNumerics.
        _tree_allclose(ref_params, got_params, rtol=5e-4, atol=3e-4)

    def test_ddp_lr_scaling_quirk(self, batch):
        # reference quirk 2: lr × world_size (train_utils.py:199)
        cfg = _config("DDP", ddp_lr_world_size_scaling=True)
        strat = build_strategy(cfg)
        assert strat.lr_for(1e-4) == pytest.approx(1e-4 * 8)
        cfg2 = _config("DDP", ddp_lr_world_size_scaling=False)
        assert build_strategy(cfg2).lr_for(1e-4) == pytest.approx(1e-4)

    def test_spatial_sharding_shapes(self, batch):
        """SP shards the H axis; DDP_SP shards batch × H on a 2-D mesh.
        2-level model → deep rows = (H=32)/4 = 8 → full 8-way spatial."""
        strat = build_strategy(_config("SP"))
        assert dict(strat.mesh.shape) == {"spatial": 8}
        placed = strat.place_batch(batch)
        shard = next(iter(placed["image"].addressable_shards))
        assert shard.data.shape == (B, H // 8, W, 3)

        strat2 = build_strategy(_config("DDP_SP"))
        assert dict(strat2.mesh.shape) == {"data": 2, "spatial": 4}
        placed2 = strat2.place_batch(batch)
        shard2 = next(iter(placed2["image"].addressable_shards))
        assert shard2.data.shape == (B // 2, H // 4, W, 3)

    def test_spatial_with_reference_depth_model(self, batch):
        """4-level default model at H=32: only 2 deep rows → the SP mesh
        shrinks to 2 and the hybrid becomes data 4 × spatial 2."""
        cfg = TrainConfig(
            train_method="SP", batch_size=B, compute_dtype="float32",
            image_size=(W, H),
        )
        assert dict(build_strategy(cfg).mesh.shape) == {"spatial": 2}
        cfg2 = TrainConfig(
            train_method="DDP_SP", batch_size=B, compute_dtype="float32",
            image_size=(W, H),
        )
        assert dict(build_strategy(cfg2).mesh.shape) == {
            "data": 4, "spatial": 2,
        }

    def test_tp_fsdp_state_actually_sharded(self, model, params, batch):
        """TP shards out-channels over 'model'; FSDP shards each leaf's
        largest axis over 'data' — verify per-device shards are smaller
        than the leaf AND that per-device buffer bytes over the WHOLE
        state (params + Adam) land near total/mesh, not near the
        replicated baseline of total (a silent
        replication regression passes the single-leaf check but not
        this one)."""
        import jax as _jax

        from distributedpytorch_tpu.train.steps import create_train_state

        mesh_size = 8  # the virtual CPU mesh (conftest)
        for method, axis in [("TP", "model"), ("FSDP", "data")]:
            strat = build_strategy(_config(method))
            state, _ = create_train_state(
                _jax.tree.map(jnp.array, params), 1e-4
            )
            placed = strat.place_state(state)
            # the largest kernel must actually be split
            leaves = [
                x for x in _jax.tree.leaves(placed.params) if x.ndim == 4
            ]
            big = max(leaves, key=lambda x: x.size)
            shard = next(iter(big.addressable_shards))
            assert shard.data.size < big.size, (
                f"{method}: params not actually sharded"
            )
            # per-device accounting: sum every leaf's shard bytes per
            # device. Replicated baseline = every device holds `total`;
            # honest sharding ≈ total/mesh (+ the small replicated
            # residue: scalars, the Cout=1 segmap head, tiny biases).
            total = 0
            per_dev = {}
            for leaf in _jax.tree.leaves(placed):
                if not hasattr(leaf, "addressable_shards"):
                    continue
                total += leaf.size * leaf.dtype.itemsize
                for sh in leaf.addressable_shards:
                    per_dev[sh.device] = (
                        per_dev.get(sh.device, 0)
                        + sh.data.size * sh.data.dtype.itemsize
                    )
            assert len(per_dev) == mesh_size
            worst = max(per_dev.values())
            assert worst <= total / mesh_size * 1.5, (
                f"{method}: max per-device bytes {worst} vs total {total} "
                f"— state is (partially) replicated, expected ~1/{mesh_size}"
            )

    def test_tp_warns_when_nothing_shards(self, caplog):
        """Widths that no mesh axis divides → fully replicated state must
        warn loudly, not silently waste every device."""
        import logging

        m = UNet(dtype=jnp.float32, widths=(3, 5))  # nothing divides 8
        p = m.init(jax.random.key(0), jnp.zeros((1, 16, 16, 3)))["params"]
        strat = build_strategy(
            TrainConfig(train_method="TP", batch_size=B,
                        compute_dtype="float32", image_size=(W, H),
                        model_widths=(3, 5))
        )
        state, _ = create_train_state(p, 1e-4)
        with caplog.at_level(logging.WARNING):
            strat.place_state(state)
        assert any("fully replicated" in r.message for r in caplog.records)

    def test_remat_matches_plain(self, model, params, batch, single_result):
        """jax.checkpoint rematerialization must be numerics-neutral: same
        loss, same post-step params as the plain single-device step."""
        cfg = _config("singleGPU", remat=True)
        strat = build_strategy(cfg)
        got_params, got_loss = self._stepped_params(strat, model, params, batch, cfg)
        ref_params, ref_loss = single_result
        np.testing.assert_allclose(got_loss, ref_loss, rtol=1e-6, atol=1e-7)
        _tree_allclose(ref_params, got_params, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("method", ["singleGPU", "DP", "MP"])
    def test_pallas_training_loss_matches(self, method, model, params, batch,
                                          single_result):
        """--pallas routes the TRAINING loss through the fused kernel +
        custom VJP (direct, shard_map-wrapped, and inside the pipeline
        schedule respectively) — one Adam step must land where the XLA
        loss does."""
        cfg = _config(method, use_pallas=True,
                      ddp_lr_world_size_scaling=False)
        strat = build_strategy(cfg)
        got_params, got_loss = self._stepped_params(strat, model, params, batch, cfg)
        ref_params, ref_loss = single_result
        np.testing.assert_allclose(got_loss, ref_loss, rtol=1e-5, atol=1e-6)
        _tree_allclose(ref_params, got_params, rtol=5e-4, atol=3e-4)

    def test_dp_mesh_shrink_warns(self, caplog):
        """An indivisible batch shrinks the data mesh — loudly (the
        silent shrink left devices idle with no trace)."""
        import logging

        cfg = TrainConfig(
            train_method="DP", batch_size=3, compute_dtype="float32",
            image_size=(W, H), model_widths=WIDTHS,
        )
        with caplog.at_level(logging.WARNING):
            strat = build_strategy(cfg)
        assert dict(strat.mesh.shape) == {"data": 3}
        assert any("mesh shrunk" in r.message for r in caplog.records)

    def test_unknown_method_raises(self):
        with pytest.raises(ValueError, match="Unknown train method"):
            build_strategy(_config("FSDP9000"))


class TestGroupedEval:
    """Sharded evaluation: per-group metrics from one
    grouped dispatch must equal per-batch evaluation exactly — that is the
    property that lets multi-process runs split the val set while every
    process still sees identical values."""

    G = 4  # groups per dispatch (the multi-process world size)

    def test_grouped_metrics_exact(self, model, params, batch):
        from distributedpytorch_tpu.ops.losses import (
            bce_dice_loss,
            dice_coefficient,
        )
        from distributedpytorch_tpu.train.steps import make_eval_step

        per_batch = jax.jit(make_eval_step(model))
        grouped = jax.jit(make_eval_step(model, groups=self.G))

        rng = np.random.default_rng(1)
        stacked = {
            "image": rng.random((self.G * B, H, W, 3), dtype=np.float32),
            "mask": (rng.random((self.G * B, H, W)) > 0.5).astype(np.int32),
        }
        got = jax.device_get(grouped(params, stacked))
        assert got["loss"].shape == (self.G,)
        for g in range(self.G):
            one = {
                k: v[g * B : (g + 1) * B] for k, v in stacked.items()
            }
            want = jax.device_get(per_batch(params, one))
            np.testing.assert_array_equal(got["loss"][g], want["loss"])
            np.testing.assert_array_equal(got["dice"][g], want["dice"])

    def test_grouped_metrics_data_sharded(self, model, params, batch):
        """The multi-process compute path: the grouped stack sharded over a
        'data' mesh axis (one group per shard) gives the same values as the
        unsharded dispatch."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from distributedpytorch_tpu.train.steps import make_eval_step

        G = 8
        mesh = Mesh(np.array(jax.devices()), ("data",))
        rng = np.random.default_rng(2)
        stacked = {
            "image": rng.random((G * 4, H, W, 3), dtype=np.float32),
            "mask": (rng.random((G * 4, H, W)) > 0.5).astype(np.int32),
        }
        grouped = jax.jit(make_eval_step(model, groups=G))
        want = jax.device_get(grouped(params, stacked))
        sharding = NamedSharding(mesh, P("data"))
        placed = {k: jax.device_put(v, sharding) for k, v in stacked.items()}
        rep_params = jax.device_put(params, NamedSharding(mesh, P()))
        got = jax.device_get(grouped(rep_params, placed))
        np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-6)
        np.testing.assert_allclose(got["dice"], want["dice"], rtol=1e-6)

    def test_evaluate_sharded_world1_matches_evaluate(self, model, params):
        """world == 1 short-circuits to the plain per-batch loop."""
        from distributedpytorch_tpu.data import (
            DataLoader,
            SyntheticSegmentationDataset,
        )
        from distributedpytorch_tpu.data.loader import ShardSpec
        from distributedpytorch_tpu.evaluate import evaluate, evaluate_sharded
        from distributedpytorch_tpu.train.steps import make_eval_step

        ds = SyntheticSegmentationDataset(length=10, newsize=(W, H), seed=0)
        loader = DataLoader(ds, batch_size=4, drop_last=True)
        step = jax.jit(make_eval_step(model))
        want = evaluate(step, params, loader)
        got = evaluate_sharded(
            step, step, params, loader, None, ShardSpec(0, 1)
        )
        assert got == want


class TestGradAccum:
    """Exact gradient accumulation (make_accum_train_step): one step over
    K stacked b-sized chunks must equal one plain step over the K·b
    concatenated batch — the property naive per-chunk loss-grad summing
    VIOLATES under the non-additive log-dice loss."""

    def test_matches_full_batch_step(self, model, params, batch):
        from distributedpytorch_tpu.train.steps import make_accum_train_step

        K, b = 4, 2
        stacked = {
            k: v.reshape((K, b) + v.shape[1:]) for k, v in batch.items()
        }
        p = jax.tree.map(jnp.array, params)
        state, tx = create_train_state(p, 1e-4)
        # the equivalent single-big-batch run passes -b = K·b, so its
        # faithful grad scale is K·b — what the accum step must match
        plain = jax.jit(make_train_step(model, tx, batch_size=K * b))
        ref_state, ref_loss = plain(state, batch)

        p2 = jax.tree.map(jnp.array, params)
        state2, tx2 = create_train_state(p2, 1e-4)
        accum = jax.jit(
            make_accum_train_step(model, tx2, batch_size=b, chunks=K)
        )
        got_state, got_loss = accum(state2, stacked)
        np.testing.assert_allclose(
            float(got_loss), float(ref_loss), rtol=1e-6, atol=1e-7
        )
        _tree_allclose(ref_state.params, got_state.params, rtol=5e-4, atol=3e-4)

    def test_naive_accumulation_would_differ(self, model, params, batch):
        """Sanity that the exactness above is non-trivial: the mean of
        per-chunk losses differs from the full-batch loss (log-dice is
        not chunk-additive), so summed per-chunk loss grads target a
        different objective."""
        from distributedpytorch_tpu.ops.losses import bce_dice_loss

        imgs = jnp.asarray(batch["image"])
        tgt = jnp.asarray(batch["mask"])[..., None].astype(jnp.float32)
        preds = model.apply({"params": params}, imgs)
        full = bce_dice_loss(preds, tgt)
        halves = (
            bce_dice_loss(preds[:4], tgt[:4]) + bce_dice_loss(preds[4:], tgt[4:])
        ) / 2.0
        assert abs(float(full) - float(halves)) > 1e-6

    def test_accum_composes_with_pallas(self, model, params, batch):
        """--grad-accum + --pallas: per-chunk fused stats (custom_vjp under
        lax.scan) must land where the XLA stats do."""
        from distributedpytorch_tpu.train.steps import make_accum_train_step

        K, b = 4, 2
        stacked = {
            k: v.reshape((K, b) + v.shape[1:]) for k, v in batch.items()
        }
        outs = {}
        for pallas in (False, True):
            p = jax.tree.map(jnp.array, params)
            state, tx = create_train_state(p, 1e-4)
            step = jax.jit(make_accum_train_step(
                model, tx, batch_size=b, chunks=K, use_pallas=pallas
            ))
            s2, loss = step(state, stacked)
            outs[pallas] = (float(loss), jax.device_get(s2.params))
        np.testing.assert_allclose(outs[True][0], outs[False][0], rtol=2e-5)
        _tree_allclose(outs[False][1], outs[True][1], rtol=5e-4, atol=3e-4)

    def test_pipeline_rejects_accum(self):
        cfg = _config("MP", grad_accum=2)
        strat = build_strategy(cfg)
        m = UNet(dtype=jnp.float32, widths=WIDTHS)
        with pytest.raises(ValueError, match="microbatch"):
            strat.build_accum_train_step(m, None)

    def test_stateful_rejects_accum(self):
        from distributedpytorch_tpu.models.milesial import MilesialUNet
        from distributedpytorch_tpu.train.steps import make_accum_train_step

        with pytest.raises(ValueError, match="stateless"):
            make_accum_train_step(
                MilesialUNet(widths=(4, 8)), None, batch_size=2, chunks=2
            )

    def test_trainer_end_to_end(self, tmp_path):
        from distributedpytorch_tpu.train import fit

        cfg = TrainConfig(
            train_method="DP",
            epochs=1,
            batch_size=4,
            grad_accum=2,
            learning_rate=1e-4,
            compute_dtype="float32",
            image_size=(W, H),
            model_widths=WIDTHS,
            synthetic_samples=20,
            val_percent=20.0,
            checkpoint_dir=str(tmp_path / "ckpt"),
            log_dir=str(tmp_path / "logs"),
            loss_dir=str(tmp_path / "loss"),
            metric_every_steps=1,
        )
        result = fit(cfg)
        # 16 train samples / (b=4) = 4 batches → 2 accum steps
        assert result["steps"] == 2
        assert np.isfinite(result["val_loss"])

    def test_accum_excludes_steps_per_dispatch(self, tmp_path):
        from distributedpytorch_tpu.train import Trainer

        cfg = TrainConfig(
            train_method="singleGPU", batch_size=4, grad_accum=2,
            steps_per_dispatch=2, compute_dtype="float32",
            image_size=(W, H), model_widths=WIDTHS, synthetic_samples=12,
            checkpoint_dir=str(tmp_path / "c"), log_dir=str(tmp_path / "l"),
            loss_dir=str(tmp_path / "s"),
        )
        with pytest.raises(ValueError, match="choose one"):
            Trainer(cfg)


@pytest.mark.slow
class TestEightStagePipeline:
    """S=8 over the full 4-level model (9 segments — the deepest cut the
    flagship architecture supports, one stage carrying 2 segments): the
    generalized schedule's masking/ppermute/transpose machinery at its
    maximum depth on the 8-device CPU mesh, grads proven equal to the
    plain step. The first pod-scale pipeline run should not be the first
    time S=8 executes."""

    def test_eight_stage_loss_and_grads(self):
        from distributedpytorch_tpu.parallel.pipeline import default_cuts

        h, w = 32, 48  # 4 pool levels need H,W divisible by 16
        model = UNet(dtype=jnp.float32, widths=(4, 6, 8, 10))
        assert model.num_segments == 9
        params = model.init(
            jax.random.key(0), jnp.zeros((1, h, w, 3))
        )["params"]
        rng = np.random.default_rng(7)
        batch = {
            "image": rng.random((B, h, w, 3), dtype=np.float32),
            "mask": (rng.random((B, h, w)) > 0.5).astype(np.int32),
        }
        cfg = TrainConfig(
            train_method="MP", batch_size=B, compute_dtype="float32",
            image_size=(w, h), model_widths=(4, 6, 8, 10),
            num_stages=8, num_microbatches=4,
        )
        strat = build_strategy(cfg)
        assert dict(strat.mesh.shape) == {"stage": 8}
        assert default_cuts(9, 8) == (1, 2, 3, 4, 5, 6, 7)
        loss_fn = make_pipeline_loss_fn(
            model, strat.mesh, num_microbatches=4
        )
        ref_loss, ref_grads = _ref_loss_and_grads(model, params, batch)
        prepped = _prep(batch)
        pipe_loss, pipe_grads = jax.jit(
            jax.value_and_grad(lambda p: loss_fn(p, prepped))
        )(params)
        np.testing.assert_allclose(
            float(pipe_loss), float(ref_loss), rtol=1e-5, atol=1e-6
        )
        _tree_allclose(ref_grads, pipe_grads, rtol=2e-4, atol=1e-5)
