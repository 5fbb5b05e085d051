"""Space-to-depth execution domain (ops/s2d.py, models/unet.py s2d_levels):
the structured-kernel reformulation of the shallow UNet levels must be
EXACTLY the reference computation — same parameters, same function — not an
approximation. Verified op-by-op against the flax/lax pixel-domain ops and
end-to-end on the full model (forward, gradients, param-tree identity).
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedpytorch_tpu.models.unet import UNet, param_count
from distributedpytorch_tpu.ops import s2d

RNG = np.random.default_rng(7)


def _rand(*shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


def _pixel_conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    return y + b


class TestRearranges:
    def test_s2d_roundtrip(self):
        x = _rand(2, 8, 12, 5)
        assert jnp.array_equal(s2d.depth_to_space(s2d.space_to_depth(x)), x)

    def test_s2d_layout_is_g_major(self):
        x = _rand(1, 4, 4, 3)
        sx = s2d.space_to_depth(x)
        for di in range(2):
            for dj in range(2):
                g = 2 * di + dj
                np.testing.assert_array_equal(
                    np.asarray(sx[0, 1, 1, g * 3 : (g + 1) * 3]),
                    np.asarray(x[0, 2 + di, 2 + dj, :]),
                )

    def test_group_max_is_maxpool(self):
        x = _rand(2, 8, 12, 5)
        pooled = nn.max_pool(x, window_shape=(2, 2), strides=(2, 2))
        np.testing.assert_allclose(
            np.asarray(s2d.group_max(s2d.space_to_depth(x))), np.asarray(pooled)
        )


#: planted ties: which groups of a window hold its maximum, window after window
_TIED_GROUPS = [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3), (0, 1, 2, 3)]


def _pool_input(kind, dtype):
    """A pixel image (2, 8, 12, 5) for the pool's gradient cases."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal((2, 8, 12, 5)).astype(np.float32)
    if kind == "relu_zero_windows":
        x = np.maximum(x, 0.0)
        x[:, 0:4, 0:6, :] = 0.0  # whole windows of zeros
    elif kind == "planted_ties":
        # every window holds its maximum twice or four times, in non-zero
        # values, at every pair of positions in turn
        x = np.abs(x) + 0.5
        blocks = x.reshape(2, 4, 2, 6, 2, 5)  # (b, i, di, j, dj, c), a view
        top = blocks.max(axis=(2, 4)) + 1.0
        for i in range(4):
            for j in range(6):
                for g in _TIED_GROUPS[(i * 6 + j) % len(_TIED_GROUPS)]:
                    blocks[:, i, g // 2, j, g % 2, :] = top[:, i, j, :]
    else:
        assert kind == "random"
    return jnp.asarray(x, dtype)


class TestPoolGradient:
    """``group_max`` routes its gradient as ``nn.max_pool`` on the pixel
    form does (and torch's MaxPool2d): whole, to the window's first
    maximum in row-major window order."""

    @pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
    @pytest.mark.parametrize(
        "kind", ["random", "relu_zero_windows", "planted_ties"]
    )
    @pytest.mark.parametrize(
        "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
    )
    def test_gradient_is_maxpools(self, dtype, kind, jit):
        x = _pool_input(kind, dtype)
        dy = jnp.asarray(
            np.random.default_rng(12).standard_normal((2, 4, 6, 5)), dtype
        )

        def via_s2d(x):
            y = s2d.group_max(s2d.space_to_depth(x))
            return jnp.sum((y * dy).astype(jnp.float32))

        def via_pixels(x):
            y = nn.max_pool(x, window_shape=(2, 2), strides=(2, 2))
            return jnp.sum((y * dy).astype(jnp.float32))

        wrap = jax.jit if jit else (lambda f: f)
        got = np.asarray(wrap(jax.grad(via_s2d))(x).astype(jnp.float32))
        want = np.asarray(wrap(jax.grad(via_pixels))(x).astype(jnp.float32))
        np.testing.assert_array_equal(got, want)
        # one winner a window: the whole of dy arrives, in one place
        assert np.count_nonzero(got) == np.count_nonzero(np.asarray(dy))
        if kind == "planted_ties":
            blocks = got.reshape(2, 4, 2, 6, 2, 5)
            for i in range(4):
                for j in range(6):
                    g = _TIED_GROUPS[(i * 6 + j) % len(_TIED_GROUPS)][0]
                    np.testing.assert_array_equal(
                        blocks[:, i, g // 2, j, g % 2, :],
                        np.asarray(dy.astype(jnp.float32))[:, i, j, :],
                    )

    @pytest.mark.parametrize(
        "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
    )
    def test_forward_is_max_over_group_bit_for_bit(self, dtype):
        """The body this pool replaced, ``jnp.max`` over the split lanes."""
        x = jnp.asarray(
            np.random.default_rng(13).standard_normal((2, 6, 10, 28)), dtype
        )
        want = jnp.max(x.reshape(2, 6, 10, 4, 7), axis=3)
        for fn in (s2d.group_max, jax.jit(s2d.group_max)):
            got = fn(x)
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(
                np.asarray(got.astype(jnp.float32)),
                np.asarray(want.astype(jnp.float32)),
            )

    def test_backward_has_no_divide_reduce_or_lane_split(self):
        """The backward is selects and one concatenate: no equality against
        a broadcast maximum, no count of ties, no divide; and neither pass
        reshapes the channel (lane) dimension."""
        x = jnp.zeros((2, 4, 6, 128), jnp.bfloat16)
        y, vjp = jax.vjp(s2d.group_max, x)
        fwd = jax.make_jaxpr(s2d.group_max)(x)
        bwd = jax.make_jaxpr(vjp)(y)

        def names(jaxpr):
            out = set()
            for eqn in jaxpr.eqns:
                out.add(eqn.primitive.name)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    out |= names(sub)
            return out

        bwd_names = names(bwd.jaxpr)
        assert "div" not in bwd_names
        assert not [n for n in bwd_names if n.startswith("reduce")]
        for n in names(fwd.jaxpr) | bwd_names:
            assert n not in ("reshape", "transpose"), n


class TestKernelBuilders:
    def test_conv3x3(self):
        x, w, b = _rand(2, 10, 14, 5), _rand(3, 3, 5, 7), _rand(7)
        ref = _pixel_conv(x, w, b)
        got = s2d.depth_to_space(
            s2d.conv_same(s2d.space_to_depth(x), s2d.conv3x3_kernel(w))
            + s2d.tile_bias(b)
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    def test_conv3x3_density(self):
        # exactly 1/4 of the dense kernel carries weight (4 of 16 group pairs)
        w = jnp.ones((3, 3, 5, 7))
        dense = s2d.conv3x3_kernel(w)
        assert float(jnp.count_nonzero(dense)) == 4 * 9 * 5 * 7

    def test_conv3x3_segments(self):
        # concat of two s2d tensors == conv of the pixel concat
        a, c = _rand(2, 8, 12, 3), _rand(2, 8, 12, 4)
        w, b = _rand(3, 3, 7, 6), _rand(6)
        ref = _pixel_conv(jnp.concatenate([a, c], axis=-1), w, b)
        sx = jnp.concatenate(
            [s2d.space_to_depth(a), s2d.space_to_depth(c)], axis=-1
        )
        got = s2d.depth_to_space(
            s2d.conv_same(sx, s2d.conv3x3_kernel(w, in_segments=(3, 4)))
            + s2d.tile_bias(b)
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    def test_upconv(self):
        x, u, b = _rand(2, 6, 9, 5), _rand(2, 2, 5, 4), _rand(4)
        m = nn.ConvTranspose(4, (2, 2), strides=(2, 2))
        ref = m.apply({"params": {"kernel": u, "bias": b}}, x)
        got = s2d.depth_to_space(
            s2d.conv_same(x, s2d.upconv_kernel(u)) + s2d.tile_bias(b)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-5
        )

    def test_head1x1(self):
        x, w, b = _rand(2, 8, 12, 6), _rand(1, 1, 6, 2), _rand(2)
        ref = _pixel_conv(x, w, b)
        got = s2d.depth_to_space(
            s2d.conv_same(s2d.space_to_depth(x), s2d.head1x1_kernel(w))
            + s2d.tile_bias(b)
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


class TestModelEquivalence:
    """UNet(s2d_levels=k) is the same function of the same parameters.

    2 levels / 8×12 keeps every structural case (two s2d levels, the s2d→
    pixel boundary in both encoder and decoder, consecutive s2d decoder
    levels with the d2s hand-off — and s2d_levels=1 exercises an s2d level
    feeding a pixel level) at a fraction of the single-core XLA compile
    time of the 4-level 32×48 variant."""

    WIDTHS = (4, 8)

    @pytest.fixture(scope="class")
    def setup(self):
        x = jnp.asarray(RNG.random((2, 8, 12, 3)), jnp.float32)
        base = UNet(dtype=jnp.float32, widths=self.WIDTHS, s2d_levels=0)
        params = base.init(jax.random.key(3), x)["params"]
        return x, base, params

    def _loss_and_grads(self, model, params, x):
        """One compile yields both the forward value and the grads."""

        def loss(p):
            return jnp.sum((model.apply({"params": p}, x) - 0.3) ** 2)

        return jax.jit(jax.value_and_grad(loss))(params)

    @pytest.fixture(scope="class")
    def base_loss_and_grads(self, setup):
        x, base, params = setup
        return self._loss_and_grads(base, params, x)

    def test_param_tree_identical(self, setup):
        x, base, params = setup
        for lv in (1, 2):
            m = UNet(dtype=jnp.float32, widths=self.WIDTHS, s2d_levels=lv)
            p = m.init(jax.random.key(3), x)["params"]
            flat0 = jax.tree_util.tree_leaves_with_path(params)
            flat1 = jax.tree_util.tree_leaves_with_path(p)
            assert [k for k, _ in flat0] == [k for k, _ in flat1]
            for (_, a), (_, b) in zip(flat0, flat1):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_forward_equal_single_level(self, setup, base_loss_and_grads):
        x, base, params = setup
        ref_loss, _ = base_loss_and_grads
        m = UNet(dtype=jnp.float32, widths=self.WIDTHS, s2d_levels=1)
        out_loss = jax.jit(
            lambda p: jnp.sum((m.apply({"params": p}, x) - 0.3) ** 2)
        )(params)
        np.testing.assert_allclose(float(out_loss), float(ref_loss), rtol=1e-6)

    def test_loss_and_grads_equal(self, setup, base_loss_and_grads):
        """The production configuration (two s2d levels): same loss, same
        gradients on the same parameter tree."""
        x, base, params = setup
        ref_loss, g0 = base_loss_and_grads
        m = UNet(dtype=jnp.float32, widths=self.WIDTHS, s2d_levels=2)
        out_loss, g1 = self._loss_and_grads(m, params, x)
        np.testing.assert_allclose(float(out_loss), float(ref_loss), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            scale = float(jnp.abs(a).max()) + 1e-8
            np.testing.assert_allclose(
                np.asarray(b) / scale, np.asarray(a) / scale, atol=5e-5
            )

    def test_level3_cut_loss_and_grads_equal(self):
        """s2d_levels=3 — the ROADMAP hw-util lever past the default 2:
        a THIRD encoder/decoder level in the s2d domain adds the cases
        the 2-level tests never reach (two consecutive s2d encoder levels
        feeding a third, and the decoder's d2s hand-off chain running
        twice before the pixel boundary). Same parameters, same loss,
        same gradients as the pixel path on a 3-level model."""
        widths = (4, 8, 16)
        x = jnp.asarray(RNG.random((2, 16, 24, 3)), jnp.float32)
        base = UNet(dtype=jnp.float32, widths=widths, s2d_levels=0)
        params = base.init(jax.random.key(5), x)["params"]
        ref_loss, g0 = self._loss_and_grads(base, params, x)
        m3 = UNet(dtype=jnp.float32, widths=widths, s2d_levels=3)
        p3 = m3.init(jax.random.key(5), x)["params"]
        flat0 = jax.tree_util.tree_leaves_with_path(params)
        flat3 = jax.tree_util.tree_leaves_with_path(p3)
        assert [k for k, _ in flat0] == [k for k, _ in flat3]
        out_loss, g3 = self._loss_and_grads(m3, params, x)
        np.testing.assert_allclose(float(out_loss), float(ref_loss), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g3)):
            scale = float(jnp.abs(a).max()) + 1e-8
            np.testing.assert_allclose(
                np.asarray(b) / scale, np.asarray(a) / scale, atol=5e-5
            )

    def test_level3_milesial_forward_matches_pixel(self):
        """milesial at s2d_levels=3 (its cap is len(widths)−2, so 5
        widths admit 3): train-mode forward AND updated running stats —
        _S2DBatchNorm statistics at the third level — equal the pixel
        path's."""
        from distributedpytorch_tpu.models.milesial import (
            MilesialUNet,
            init_milesial,
        )

        widths = (2, 4, 8, 16, 32)
        hw = (16, 32)  # divisible by 2**4
        m0 = MilesialUNet(widths=widths, dtype=jnp.float32, s2d_levels=0)
        m3 = MilesialUNet(widths=widths, dtype=jnp.float32, s2d_levels=3)
        params, stats = init_milesial(m0, jax.random.key(0), input_hw=hw)
        x = jnp.asarray(RNG.random((2, *hw, 3)), jnp.float32)
        v = {"params": params, "batch_stats": stats}
        want, upd0 = m0.apply(v, x, train=True, mutable=["batch_stats"])
        got, upd3 = m3.apply(v, x, train=True, mutable=["batch_stats"])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-5, atol=5e-6
        )
        for a, b in zip(
            jax.tree.leaves(upd0["batch_stats"]),
            jax.tree.leaves(upd3["batch_stats"]),
        ):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=5e-5, atol=5e-6
            )

    def test_full_width_param_golden_with_s2d(self):
        # the 7,760,097-param golden (reference modelsummary.txt:63) holds in
        # s2d mode — the transform declares identical parameters
        m = UNet(dtype=jnp.float32, s2d_levels=2)
        p = m.init(jax.random.key(0), jnp.zeros((1, 16, 16, 3)))["params"]
        assert param_count(p) == 7_760_097

    def test_jit_and_bf16_compile(self):
        # bf16 s2d path compiles and produces finite output
        m = UNet(dtype=jnp.bfloat16, widths=(4,), s2d_levels=1)
        x = jnp.asarray(RNG.random((1, 8, 8, 3)), jnp.float32)
        p = m.init(jax.random.key(0), x)["params"]
        y = jax.jit(lambda p, x: m.apply({"params": p}, x))(p, x)
        assert y.shape == (1, 8, 8, 1)
        assert bool(jnp.isfinite(y).all())


class TestS2DUnderParallelism:
    """The s2d execution domain must compose with the parallelism machinery
    the TPU default (s2d_levels=2) will run under. The CPU-mesh suite
    otherwise never exercises it — the auto default resolves to 0 off-TPU."""

    def test_pipeline_loss_matches_plain_with_s2d(self, devices):
        from distributedpytorch_tpu.config import TrainConfig
        from distributedpytorch_tpu.ops.losses import bce_dice_loss
        from distributedpytorch_tpu.parallel import build_strategy
        from distributedpytorch_tpu.parallel.pipeline import make_pipeline_loss_fn

        H, W, B = 16, 24, 8
        model = UNet(dtype=jnp.float32, widths=(8,), s2d_levels=1)
        params = model.init(jax.random.key(0), jnp.zeros((1, H, W, 3)))["params"]
        rng = np.random.default_rng(0)
        image = jnp.asarray(rng.random((B, H, W, 3), dtype=np.float32))
        mask = jnp.asarray(
            (rng.random((B, H, W)) > 0.5).astype(np.float32)
        )[..., None]

        def ref_loss(p):
            return bce_dice_loss(model.apply({"params": p}, image), mask)

        cfg = TrainConfig(
            train_method="MP", batch_size=B, compute_dtype="float32",
            image_size=(W, H), model_widths=(8,),
        )
        strat = build_strategy(cfg)
        loss_fn = make_pipeline_loss_fn(model, strat.mesh, num_microbatches=2)
        batch = {"image": image, "mask": mask}
        np.testing.assert_allclose(
            float(jax.jit(loss_fn)(params, batch)),
            float(jax.jit(ref_loss)(params)),
            rtol=1e-5, atol=1e-6,
        )

    @pytest.mark.parametrize("how", ["DP", "MP-gpipe", "MP-1f1b"])
    def test_pool_vjp_traces_under_parallelism(self, devices, how):
        """``group_max`` carries its own VJP: it has to trace under a DP
        mesh (the strategy's jitted step) and inside the stage functions of
        a 2-stage pipeline under both schedules, and give the plain step's
        gradients there."""
        from distributedpytorch_tpu.config import TrainConfig
        from distributedpytorch_tpu.ops.losses import bce_dice_loss
        from distributedpytorch_tpu.parallel import build_strategy
        from distributedpytorch_tpu.parallel.pipeline import (
            make_pipeline_value_and_grad_fn,
        )
        from distributedpytorch_tpu.train.steps import create_train_state

        H, W, B = 16, 24, 8
        model = UNet(dtype=jnp.float32, widths=(8,), s2d_levels=1)
        params = model.init(jax.random.key(0), jnp.zeros((1, H, W, 3)))["params"]
        rng = np.random.default_rng(0)
        image = rng.random((B, H, W, 3), dtype=np.float32)
        mask = (rng.random((B, H, W)) > 0.5).astype(np.int32)
        target = jnp.asarray(mask)[..., None].astype(jnp.float32)

        def ref_loss(p):
            return bce_dice_loss(
                model.apply({"params": p}, jnp.asarray(image)), target
            )

        ref, ref_grads = jax.jit(jax.value_and_grad(ref_loss))(params)
        method, _, schedule = how.partition("-")
        cfg = TrainConfig(
            train_method=method, batch_size=B, compute_dtype="float32",
            image_size=(W, H), model_widths=(8,), s2d_levels=1,
        )
        strat = build_strategy(cfg)
        if method == "MP":
            fn = make_pipeline_value_and_grad_fn(
                model, strat.mesh, num_microbatches=2, schedule=schedule
            )
            loss, grads, _ = jax.jit(fn)(
                params, None, {"image": jnp.asarray(image), "mask": target}
            )
            for a, b in zip(jax.tree.leaves(ref_grads), jax.tree.leaves(grads)):
                np.testing.assert_allclose(
                    np.asarray(b), np.asarray(a), rtol=2e-4, atol=1e-5
                )
        else:
            assert dict(strat.mesh.shape) == {"data": 8}

            def stepped(strategy):
                # one Adam step of the strategy's own jitted train step
                state, tx = create_train_state(
                    jax.tree.map(jnp.array, params), cfg.learning_rate,
                    cfg.weight_decay,
                )
                new_state, loss = strategy.build_train_step(model, tx)(
                    strategy.place_state(state),
                    strategy.place_batch({"image": image, "mask": mask}),
                )
                return jax.device_get(new_state.params), loss

            got, loss = stepped(strat)
            want, _ = stepped(
                build_strategy(dataclasses.replace(cfg, train_method="singleGPU"))
            )
            # Adam moves a parameter by lr whatever the gradient's size: the
            # tolerance is TestStrategySteps' (3 lr)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
                np.testing.assert_allclose(b, a, rtol=5e-4, atol=3e-4)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5, atol=1e-6)


class TestPropertyEquivalence:
    """Property-based exactness: for ANY channel counts, spatial sizes, and
    segment splits, the s2d kernel builders reproduce the pixel-domain ops.
    The fixed-shape tests above pin known cases; these sweep the space."""

    @staticmethod
    def _settings():
        from hypothesis import HealthCheck, settings

        return settings(
            max_examples=6,  # each example is an XLA compile on 1 CPU core
            deadline=None,  # XLA compile times are not flaky-test evidence
            suppress_health_check=[HealthCheck.too_slow],
        )

    def test_conv3x3_any_shape(self):
        pytest.importorskip("hypothesis")  # optional test extra
        from hypothesis import given, strategies as st

        @self._settings()
        @given(
            h=st.integers(2, 6).map(lambda k: 2 * k),
            w=st.integers(2, 6).map(lambda k: 2 * k),
            cin=st.integers(1, 9),
            cout=st.integers(1, 9),
            seed=st.integers(0, 2**31 - 1),
        )
        def check(h, w, cin, cout, seed):
            rng = np.random.default_rng(seed)
            x = jnp.asarray(rng.standard_normal((1, h, w, cin)), jnp.float32)
            wk = jnp.asarray(rng.standard_normal((3, 3, cin, cout)), jnp.float32)
            ref = _pixel_conv(x, wk, jnp.zeros((cout,)))
            got = s2d.depth_to_space(
                s2d.conv_same(s2d.space_to_depth(x), s2d.conv3x3_kernel(wk))
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4
            )

        check()

    def test_conv3x3_any_segments(self):
        pytest.importorskip("hypothesis")  # optional test extra
        from hypothesis import given, strategies as st

        @self._settings()
        @given(
            segs=st.lists(st.integers(1, 5), min_size=1, max_size=4),
            seed=st.integers(0, 2**31 - 1),
        )
        def check(segs, seed):
            rng = np.random.default_rng(seed)
            cin = sum(segs)
            parts = [
                jnp.asarray(rng.standard_normal((1, 8, 12, c)), jnp.float32)
                for c in segs
            ]
            wk = jnp.asarray(rng.standard_normal((3, 3, cin, 3)), jnp.float32)
            ref = _pixel_conv(
                jnp.concatenate(parts, axis=-1), wk, jnp.zeros((3,))
            )
            sx = jnp.concatenate(
                [s2d.space_to_depth(p) for p in parts], axis=-1
            )
            got = s2d.depth_to_space(
                s2d.conv_same(sx, s2d.conv3x3_kernel(wk, in_segments=segs))
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4
            )

        check()

    def test_upconv_any_shape(self):
        pytest.importorskip("hypothesis")  # optional test extra
        from hypothesis import given, strategies as st

        @self._settings()
        @given(
            h=st.integers(1, 9),
            w=st.integers(1, 9),
            cin=st.integers(1, 8),
            cout=st.integers(1, 8),
            seed=st.integers(0, 2**31 - 1),
        )
        def check(h, w, cin, cout, seed):
            rng = np.random.default_rng(seed)
            x = jnp.asarray(rng.standard_normal((1, h, w, cin)), jnp.float32)
            u = jnp.asarray(rng.standard_normal((2, 2, cin, cout)), jnp.float32)
            m = nn.ConvTranspose(cout, (2, 2), strides=(2, 2))
            ref = m.apply(
                {"params": {"kernel": u, "bias": jnp.zeros((cout,))}}, x
            )
            got = s2d.depth_to_space(s2d.conv_same(x, s2d.upconv_kernel(u)))
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4
            )

        check()
