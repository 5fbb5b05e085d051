"""MilesialUNet (models/milesial.py): the original milesial/Pytorch-UNet
family the reference's model derives from (reference
model/modelsummary.txt:150-247) — parameter golden, stateful (BatchNorm)
training mechanics, SyncBN-by-construction under a sharded batch, and the
checkpoint/restore of running statistics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedpytorch_tpu.config import TrainConfig
from distributedpytorch_tpu.models.milesial import MilesialUNet, init_milesial
from distributedpytorch_tpu.models.unet import param_count
from distributedpytorch_tpu.train.steps import create_train_state, make_train_step

REFERENCE_MILESIAL_PARAMS = 31_037_698  # reference model/modelsummary.txt:239


def test_param_count_matches_reference_doc():
    # the documented configuration: n_classes=2, transposed-conv upsampling.
    # eval_shape: the count is a pure shape function, and a real full-width
    # init costs ~10 s of single-core XLA compile (real builds are covered
    # by the tiny-width trainer tests below)
    m = MilesialUNet(n_classes=2, bilinear=False, dtype=jnp.float32)
    variables = jax.eval_shape(
        lambda rng: m.init(rng, jnp.zeros((1, 32, 48, 3))), jax.random.key(0)
    )
    # param_count works on ShapeDtypeStructs too (it only reads .size)
    assert param_count(variables["params"]) == REFERENCE_MILESIAL_PARAMS
    # running stats are non-trainable: 2 tensors per BatchNorm, 18 BNs
    assert len(jax.tree.leaves(variables["batch_stats"])) == 36


@pytest.fixture(scope="module")
def tiny():
    model = MilesialUNet(widths=(4, 8), dtype=jnp.float32)
    params, batch_stats = init_milesial(model, jax.random.key(0), input_hw=(8, 8))
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.random((4, 8, 8, 3), dtype=np.float32)),
        "mask": jnp.asarray((rng.random((4, 8, 8)) > 0.5).astype(np.int32)),
    }
    return model, params, batch_stats, batch


def test_train_step_updates_batch_stats(tiny):
    model, params, batch_stats, batch = tiny
    state, tx = create_train_state(
        jax.tree.map(jnp.array, params), 1e-3, model_state=batch_stats
    )
    step = make_train_step(model, tx, batch_size=4)
    new_state, loss = jax.jit(step)(state, batch)
    assert np.isfinite(float(loss))
    assert int(new_state.step) == 1
    # the running stats moved (BatchNorm saw the batch)
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(batch_stats), jax.tree.leaves(new_state.model_state))
    )
    assert moved


def test_sync_bn_by_construction(tiny, devices):
    """Under a data-sharded mesh, BatchNorm statistics are computed over
    the GLOBAL batch (XLA inserts the cross-shard mean) — the sharded loss
    equals the single-device loss (asserted for DP, SP, and FSDP), which torch only achieves via the
    separate SyncBatchNorm wrapper."""
    from distributedpytorch_tpu.parallel import build_strategy

    model, params, batch_stats, batch = tiny

    def run(method):
        cfg = TrainConfig(
            train_method=method, batch_size=4, compute_dtype="float32",
            image_size=(8, 8), model_arch="milesial", model_widths=(4, 8),
        )
        strat = build_strategy(cfg)
        # fresh copies: the jitted step donates the whole state, batch_stats
        # included — the second leg must not see deleted buffers
        state, tx = create_train_state(
            jax.tree.map(jnp.array, params),
            1e-3,
            model_state=jax.tree.map(jnp.array, batch_stats),
        )
        state = strat.place_state(state)
        step = strat.build_train_step(model, tx)
        new_state, loss = step(state, strat.place_batch(batch))
        return float(loss), jax.device_get(new_state.model_state)

    loss_single, stats_single = run("singleGPU")
    for method in ("DP", "SP", "FSDP"):
        loss_m, stats_m = run(method)
        np.testing.assert_allclose(loss_m, loss_single, rtol=1e-5, err_msg=method)
        for a, b in zip(jax.tree.leaves(stats_single), jax.tree.leaves(stats_m)):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6, err_msg=method
            )


def test_trainer_end_to_end_and_resume(tmp_path):
    """Full trainer pass with the stateful model: artifacts land, the
    checkpoint carries batch_stats, and a resume restores them."""
    from distributedpytorch_tpu.train import Trainer

    def cfg(**kw):
        base = dict(
            train_method="singleGPU", epochs=2, batch_size=4, val_percent=25.0,
            compute_dtype="float32", image_size=(8, 8),
            model_arch="milesial", model_widths=(4, 8), synthetic_samples=16,
            checkpoint_dir=str(tmp_path / "checkpoints"),
            log_dir=str(tmp_path / "logs"), loss_dir=str(tmp_path / "loss"),
            num_workers=0,
        )
        base.update(kw)
        return TrainConfig(**base)

    t1 = Trainer(cfg())
    result = t1.train()
    assert np.isfinite(result["val_loss"])

    t2 = Trainer(cfg(epochs=4, checkpoint_name="singleGPU"))
    assert t2.start_epoch == 2
    for a, b in zip(
        jax.tree.leaves(jax.device_get(t1.state.model_state)),
        jax.tree.leaves(jax.device_get(t2.state.model_state)),
    ):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a))


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_milesial_trains_under_pipeline(tmp_path, schedule):
    """The BatchNorm-vs-MP guard is gone: the stateful family trains
    end-to-end under the pipeline strategies (both schedules), running
    stats move, and the pipelined eval uses them (grad parity with the
    plain step is pinned in tests/test_pipeline_1f1b.py)."""
    from distributedpytorch_tpu.train import Trainer

    cfg = TrainConfig(
        train_method="MP", epochs=1, batch_size=4, val_percent=25.0,
        compute_dtype="float32", image_size=(8, 8), model_arch="milesial",
        model_widths=(4, 8), synthetic_samples=16,
        pipeline_schedule=schedule,
        checkpoint_dir=str(tmp_path / "c"),
        log_dir=str(tmp_path / "lg"), loss_dir=str(tmp_path / "ls"),
    )
    trainer = Trainer(cfg)
    initial_stats = jax.device_get(trainer.state.model_state)
    result = trainer.train()
    assert np.isfinite(result["val_loss"])
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves(initial_stats),
            jax.tree.leaves(jax.device_get(trainer.state.model_state)),
        )
    )
    assert moved, "pipeline step did not update BatchNorm running stats"


def test_predict_with_milesial_checkpoint(tmp_path):
    """The inference CLI surface handles the stateful family: a milesial
    .ckpt loads with its batch_stats and produces masks."""
    import os

    from distributedpytorch_tpu.data.dataset import write_synthetic_carvana_tree
    from distributedpytorch_tpu.predict import run_prediction
    from distributedpytorch_tpu.train import Trainer

    cfg = TrainConfig(
        train_method="singleGPU", epochs=1, batch_size=4, val_percent=25.0,
        compute_dtype="float32", image_size=(8, 8), model_arch="milesial",
        model_widths=(4, 8), synthetic_samples=16,
        checkpoint_dir=str(tmp_path / "checkpoints"),
        log_dir=str(tmp_path / "logs"), loss_dir=str(tmp_path / "loss"),
        num_workers=0,
    )
    Trainer(cfg).train()

    imgs, _ = write_synthetic_carvana_tree(str(tmp_path / "data"), n=3, size_wh=(8, 8))
    written = run_prediction(
        "singleGPU", imgs, str(tmp_path / "preds"), image_size=(8, 8),
        checkpoint_dir=str(tmp_path / "checkpoints"),
        model_widths=(4, 8), model_arch="milesial",
    )
    assert len(written) == 3
    assert all(os.path.exists(p) for p in written)


class TestMilesialPthInterop:
    """.pth interop with the PUBLIC milesial/Pytorch-UNet layout
    (inc.double_conv.{0,1,3,4}, downN.maxpool_conv.1..., upN.up/conv,
    outc.conv): upstream checkpoints load directly — the migration path
    for that repo's users."""

    def test_export_import_roundtrip(self, tiny, tmp_path):
        torch = pytest.importorskip("torch")  # noqa: F841
        from distributedpytorch_tpu.checkpoint import (
            export_milesial_pth,
            import_milesial_pth,
        )

        model, params, batch_stats, _ = tiny
        path = str(tmp_path / "milesial.pth")
        export_milesial_pth(params, batch_stats, path)
        p2, s2 = import_milesial_pth(path, params, batch_stats)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(batch_stats), jax.tree.leaves(s2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_torch_names_and_shapes(self, tiny):
        """Exported names/shapes are exactly what torch's strict
        load_state_dict expects from the milesial module tree."""
        torch = pytest.importorskip("torch")  # noqa: F841
        from distributedpytorch_tpu.checkpoint import export_milesial_state_dict

        model, params, batch_stats, _ = tiny  # widths (4, 8): 1 down, 1 up
        sd = export_milesial_state_dict(params, batch_stats)
        expected = {
            "inc.double_conv.0.weight": (4, 3, 3, 3),
            "inc.double_conv.1.weight": (4,),
            "inc.double_conv.1.running_mean": (4,),
            "down1.maxpool_conv.1.double_conv.0.weight": (8, 4, 3, 3),
            "up1.up.weight": (8, 4, 2, 2),  # torch ConvTranspose: (I, O, kh, kw)
            "up1.conv.double_conv.0.weight": (4, 8, 3, 3),  # in = skip+up = 8
            "outc.conv.weight": (1, 4, 1, 1),  # in = widths[0]
            "outc.conv.bias": (1,),
            "inc.double_conv.1.num_batches_tracked": (),
        }
        for name, shape in expected.items():
            assert name in sd, name
            assert sd[name].shape == shape, (name, sd[name].shape, shape)

    def test_double_conv_matches_torch_numerics(self, tiny):
        """Eval-mode DoubleConv forward on exported tensors: torch's
        conv2d + batch_norm reproduce our flax block — validates the
        OIHW/HWIO transposes AND the BN scale/bias/mean/var mapping."""
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        from distributedpytorch_tpu.checkpoint import export_milesial_state_dict
        from distributedpytorch_tpu.models.milesial import DoubleConv

        model, params, batch_stats, batch = tiny
        sd = export_milesial_state_dict(params, batch_stats)

        x = np.asarray(batch["image"][:2], np.float32)  # (2, 8, 8, 3)
        ours = DoubleConv(4, dtype=jnp.float32).apply(
            {"params": params["inc"], "batch_stats": batch_stats["inc"]},
            jnp.asarray(x),
            train=False,
        )

        t = torch.from_numpy(x.transpose(0, 3, 1, 2))  # NCHW
        for c_idx, b_idx in ((0, 1), (3, 4)):
            t = F.conv2d(t, torch.from_numpy(sd[f"inc.double_conv.{c_idx}.weight"]),
                         padding=1)
            t = F.batch_norm(
                t,
                torch.from_numpy(sd[f"inc.double_conv.{b_idx}.running_mean"]),
                torch.from_numpy(sd[f"inc.double_conv.{b_idx}.running_var"]),
                torch.from_numpy(sd[f"inc.double_conv.{b_idx}.weight"]),
                torch.from_numpy(sd[f"inc.double_conv.{b_idx}.bias"]),
                training=False, eps=1e-5,
            )
            t = F.relu(t)
        theirs = t.numpy().transpose(0, 2, 3, 1)
        np.testing.assert_allclose(np.asarray(ours), theirs, rtol=1e-4, atol=1e-5)


def test_steps_per_dispatch_with_stateful_model(tmp_path):
    """K=2 fused dispatch vs K=1 for the BatchNorm family: the lax.scan
    carry includes model_state, so running stats must evolve identically."""
    from tests.test_trainer import _compare_k_dispatch

    _compare_k_dispatch(
        tmp_path, "singleGPU", model_arch="milesial", model_widths=(4, 8),
        image_size=(8, 8), epochs=1,
    )


class TestMilesialS2D:
    """Space-to-depth execution for the milesial family (round-4): same
    params, same function — including EXACT BatchNorm statistics reduced
    over the s2d group axis (_S2DBatchNorm)."""

    # 4 widths: _s2d_levels clamps to len(widths)-2, so 3 widths would
    # silently run every "lv=2" test at lv=1, skipping the deep branches
    # (_DownS2D this_s2d, _UpS2D prev_s2d d2s, the last==lv boundary)
    WIDTHS = (4, 8, 16, 32)
    HW = (16, 24)

    def _setup(self, s2d):
        model = MilesialUNet(
            widths=self.WIDTHS, dtype=jnp.float32, s2d_levels=s2d
        )
        params, stats = init_milesial(
            model, jax.random.key(0), input_hw=self.HW
        )
        return model, params, stats

    def test_param_tree_identical(self):
        _, p0, s0 = self._setup(0)
        _, p2, s2 = self._setup(2)
        assert jax.tree_util.tree_structure(p0) == jax.tree_util.tree_structure(p2)
        assert jax.tree_util.tree_structure(s0) == jax.tree_util.tree_structure(s2)
        for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p2)):
            assert a.shape == b.shape

    @pytest.mark.parametrize("s2d", [1, 2])
    def test_eval_forward_matches_pixel(self, s2d):
        m0, params, stats = self._setup(0)
        m2 = MilesialUNet(widths=self.WIDTHS, dtype=jnp.float32, s2d_levels=s2d)
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.random((2, *self.HW, 3), dtype=np.float32))
        v = {"params": params, "batch_stats": stats}
        want = m0.apply(v, x, train=False)
        got = m2.apply(v, x, train=False)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6
        )

    def test_train_forward_and_stats_match_pixel(self):
        """train=True: batch statistics computed over (batch, space, s2d
        group) must equal pixel-domain batch statistics, and so must the
        updated running stats."""
        m0, params, stats = self._setup(0)
        m2 = MilesialUNet(widths=self.WIDTHS, dtype=jnp.float32, s2d_levels=2)
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.random((2, *self.HW, 3), dtype=np.float32))
        v = {"params": params, "batch_stats": stats}
        want, upd0 = m0.apply(v, x, train=True, mutable=["batch_stats"])
        got, upd2 = m2.apply(v, x, train=True, mutable=["batch_stats"])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6
        )
        for a, b in zip(
            jax.tree.leaves(upd0["batch_stats"]),
            jax.tree.leaves(upd2["batch_stats"]),
        ):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=2e-5, atol=2e-6
            )

    def test_jitted_f32_grads_match_pixel_every_leaf(self):
        """The fault ROADMAP M1 named: with ``jnp.max`` as the s2d pool the
        jitted float32 gradients of ``inc/*`` and ``down1/conv/*`` came out
        up to 15% low in norm at these widths and this size (BatchNorm and
        ReLU are recomputed beside the pool in the compiled step, and the
        gradient's equality with the stored maximum missed). The pool now
        picks one winner from the operand it is given: every leaf's norm is
        within 5e-3 of the pixel path's, and the difference within 2e-2 of
        the norm (the readings of PERF.md §6, PRs 24 and 26; the tool that
        took them went with the fault, PR 33)."""
        widths, hw = (8, 16, 32, 64), (32, 48)
        x = jax.random.uniform(jax.random.key(0), (2, *hw, 3))
        t = (jax.random.uniform(jax.random.key(1), (2, *hw, 1)) > 0.5).astype(
            jnp.float32
        )
        params, stats = init_milesial(
            MilesialUNet(widths=widths, dtype=jnp.float32, s2d_levels=0),
            jax.random.key(2), input_hw=hw,
        )

        def grads(levels):
            model = MilesialUNet(
                widths=widths, dtype=jnp.float32, s2d_levels=levels
            )

            def loss(p):
                y, _ = model.apply(
                    {"params": p, "batch_stats": stats}, x, train=True,
                    mutable=["batch_stats"],
                )
                return jnp.mean((y - t) ** 2)

            return jax.jit(jax.grad(loss))(params)

        g0, g2 = grads(0), grads(2)
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g0), jax.tree.leaves(g2)
        ):
            norm = float(jnp.linalg.norm(a))
            ratio = float(jnp.linalg.norm(b)) / norm
            apart = float(jnp.linalg.norm(b - a)) / norm
            name = jax.tree_util.keystr(path)
            assert abs(ratio - 1) <= 5e-3 and apart <= 2e-2, (name, ratio, apart)

    def test_grads_match_pixel(self):
        """float64 (subprocess: x64 is a process-wide jax config): the two
        execution domains are mathematically the SAME function, so
        gradients agree to ~1e-6 relative. (In float32 the BatchNorm
        backward amplifies summation-order noise to some 1e-3 of a leaf's
        norm on the earliest layers, so element-wise f32 equality is not
        the right assertion; the test above holds float32, jitted, by
        norms.)"""
        import os
        import subprocess
        import sys

        script = """
import jax, jax.numpy as jnp, numpy as np
from distributedpytorch_tpu.models.milesial import MilesialUNet, init_milesial
from distributedpytorch_tpu.ops.losses import bce_dice_loss
W, HW = (4, 8, 16, 32), (16, 24)
m0 = MilesialUNet(widths=W, dtype=jnp.float64, s2d_levels=0)
m2 = MilesialUNet(widths=W, dtype=jnp.float64, s2d_levels=2)
params, stats = init_milesial(m0, jax.random.key(0), input_hw=HW)
params = jax.tree.map(lambda a: a.astype(jnp.float64), params)
stats = jax.tree.map(lambda a: a.astype(jnp.float64), stats)
rng = np.random.default_rng(3)
x = jnp.asarray(rng.random((2, *HW, 3)), jnp.float64)
t = jnp.asarray((rng.random((2, *HW, 1)) > 0.5), jnp.float64)
def grads(m):
    def f(p):
        preds, _ = m.apply({"params": p, "batch_stats": stats}, x,
                           train=True, mutable=["batch_stats"])
        return bce_dice_loss(preds, t)
    return jax.grad(f)(params)
g0, g2 = grads(m0), grads(m2)
for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g2)):
    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                               rtol=1e-4, atol=1e-7)
print("GRADS-MATCH")
"""
        env = dict(os.environ)
        env.update({
            "JAX_ENABLE_X64": "1",
            "JAX_PLATFORMS": "cpu",
        })
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-c", script], env=env, cwd=repo,
            capture_output=True, text=True, timeout=600,
        )
        assert out.returncode == 0 and "GRADS-MATCH" in out.stdout, (
            out.stdout + out.stderr
        )

    def test_auto_mode_degrades_gracefully(self):
        """-1 (auto) must never reject a config the pixel path handled:
        bilinear and ragged sizes silently fall back to pixel."""
        m = MilesialUNet(widths=self.WIDTHS, dtype=jnp.float32,
                         bilinear=True, s2d_levels=-1)
        m.init(jax.random.key(0), jnp.zeros((1, *self.HW, 3)))
        m2 = MilesialUNet(widths=self.WIDTHS, dtype=jnp.float32, s2d_levels=-1)
        m2.init(jax.random.key(0), jnp.zeros((1, 18, 26, 3)))

    def test_bilinear_rejects_s2d(self):
        m = MilesialUNet(widths=self.WIDTHS, bilinear=True, s2d_levels=2)
        with pytest.raises(ValueError, match="bilinear"):
            m.init(jax.random.key(0), jnp.zeros((1, *self.HW, 3)))

    def test_ragged_size_rejects_s2d(self):
        m = MilesialUNet(widths=self.WIDTHS, dtype=jnp.float32, s2d_levels=2)
        with pytest.raises(ValueError, match="divisible"):
            m.init(jax.random.key(0), jnp.zeros((1, 18, 24, 3)))
