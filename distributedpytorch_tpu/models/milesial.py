"""The original milesial/Pytorch-UNet architecture, TPU-native.

The reference documents this model as the ancestor of its own UNet
(reference model/modelsummary.txt:150-247: DoubleConv/Down/Up/OutConv
blocks, BatchNorm after every conv, 31,037,698 trainable parameters at
n_classes=2 with transposed-conv upsampling). This is the second model
family the framework ships; parameter-count golden in tests/test_model.py.

Differences from `models/unet.py`'s reference-course model: twice the
widths (64→1024 vs 32→512), BatchNorm (bias-free convs), no explicit mid
block (the deepest Down plays that role), and an optional bilinear
upsampling mode (halves the deepest width, parameter-free Up).

TPU notes:
  * NHWC, bfloat16 convs — but BatchNorm runs in float32 (variance in
    bf16 is numerically unsafe) and casts back.
  * BatchNorm is STATEFUL: `init` returns a `batch_stats` collection
    alongside `params`, and the train step must apply with
    ``mutable=["batch_stats"]`` (train/steps.py `make_train_step` does
    this automatically — `TrainState.model_state` carries the running
    stats). Under a GSPMD data-parallel mesh the batch axis is sharded,
    so the batch statistics XLA computes are GLOBAL-batch statistics:
    data-parallel training gets SyncBN semantics by construction, unlike
    torch where `SyncBatchNorm` is a separate opt-in wrapper.
  * For ``n_classes=1`` (this repo's binary-segmentation task) the output
    is sigmoid probabilities in float32, matching `models/unet.py`'s
    contract; for 2+ classes raw logits are returned (milesial trains
    those with cross-entropy).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributedpytorch_tpu.models.unet import _S2DConv, center_crop
from distributedpytorch_tpu.ops import s2d as s2d_ops

MILESIAL_WIDTHS = (64, 128, 256, 512, 1024)


class _S2DBatchNorm(nn.Module):
    """BatchNorm evaluated on a g-major space-to-depth tensor, EXACTLY
    equal to pixel-domain BatchNorm (up to reduction order): channel c of
    the underlying (B, H, W, C) image lives at s2d channels {g·C+c}, so
    per-logical-channel statistics reduce over (batch, h, w, g) — the
    same value set pixel BN reduces over (batch, H, W). Parameters and
    running statistics are (C,)-shaped with nn.BatchNorm's names, so
    checkpoints and `.pth` interop are identical across execution modes
    (the s2d contract, ops/s2d.py).

    Matches the pixel path's nn.BatchNorm config (milesial: momentum 0.9
    flax-convention, eps 1e-5, float32 statistics).
    """

    features: int  # logical channels C (input carries 4C)
    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        C = self.features
        scale = self.param("scale", nn.initializers.ones_init(), (C,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (C,), jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((C,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((C,), jnp.float32)
        )
        b, h, w, c4 = x.shape
        assert c4 == 4 * C, (c4, C)
        xg = x.astype(jnp.float32).reshape(b, h, w, 4, C)
        if train:
            mean = jnp.mean(xg, axis=(0, 1, 2, 3))
            var = jnp.var(xg, axis=(0, 1, 2, 3))
            if not self.is_initializing():
                ra_mean.value = (
                    self.momentum * ra_mean.value + (1.0 - self.momentum) * mean
                )
                ra_var.value = (
                    self.momentum * ra_var.value + (1.0 - self.momentum) * var
                )
        else:
            mean, var = ra_mean.value, ra_var.value
        y = (xg - mean) * jax.lax.rsqrt(var + self.epsilon) * scale + bias
        return y.reshape(b, h, w, c4)


class DoubleConvS2D(nn.Module):
    """`DoubleConv` in the space-to-depth domain: bias-free structured
    dense convs (kernels assembled from the original (3,3,Cin,Cout)
    params) + exact s2d BatchNorm. Param tree identical to `DoubleConv`
    (conv1/bn1/conv2/bn2, same shapes)."""

    features: int
    in_features: int
    in_segments: Optional[Tuple[int, ...]] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        x = _S2DConv(
            self.features, self.in_features, "conv3x3", dtype=self.dtype,
            in_segments=self.in_segments, use_bias=False, name="conv1",
        )(x)
        x = _S2DBatchNorm(self.features, name="bn1")(x, train)
        x = nn.relu(x).astype(self.dtype)
        x = _S2DConv(
            self.features, self.features, "conv3x3", dtype=self.dtype,
            use_bias=False, name="conv2",
        )(x)
        x = _S2DBatchNorm(self.features, name="bn2")(x, train)
        return nn.relu(x).astype(self.dtype)


class _DownS2D(nn.Module):
    """`Down` where the s2d execution domain touches either side of the
    pool: the 2×2 maxpool of an s2d input is a max over the s2d group
    (ops/s2d.py `group_max`), and the conv runs in whichever domain its
    level belongs to. Param tree identical to `Down`."""

    features: int
    in_features: int
    prev_s2d: bool  # input arrives in s2d form
    this_s2d: bool  # this level's DoubleConv runs in the s2d domain
    dtype: Any = jnp.bfloat16
    epilogue: bool = False  # pixel-domain DoubleConv only (the boundary)

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        x = (
            s2d_ops.group_max(x)
            if self.prev_s2d
            else nn.max_pool(x, window_shape=(2, 2), strides=(2, 2))
        )
        if self.this_s2d:
            x = s2d_ops.space_to_depth(x)
            return DoubleConvS2D(
                self.features, in_features=self.in_features,
                dtype=self.dtype, name="conv",
            )(x, train)
        return DoubleConv(
            self.features, dtype=self.dtype, epilogue=self.epilogue,
            name="conv",
        )(x, train)


class _UpS2D(nn.Module):
    """`Up` (transposed-conv mode) in the s2d domain: the k=2 s=2
    ConvTranspose becomes a 1×1 conv from the pixel-space input
    (ops/s2d.py `upconv_kernel`), the skip arrives already in s2d form,
    and the concat is a kernel-layout concern (`in_segments`). Param tree
    identical to `Up(bilinear=False)`."""

    features: int
    skip_features: int
    prev_s2d: bool  # x arrives in s2d form (previous Up ran s2d)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(
        self, x: jax.Array, skip: jax.Array, train: bool = False
    ) -> jax.Array:
        if self.prev_s2d:
            x = s2d_ops.depth_to_space(x)
        up_feats = x.shape[-1] // 2
        up = _S2DConv(
            up_feats, x.shape[-1], "upconv", dtype=self.dtype, name="up"
        )(x)
        assert skip.shape[:3] == up.shape[:3], (
            "s2d Up expects the identity center-crop (even input sizes): "
            f"skip {skip.shape} vs upconv {up.shape}"
        )
        x = jnp.concatenate([skip, up], axis=-1)
        return DoubleConvS2D(
            self.features,
            in_features=self.skip_features + up_feats,
            in_segments=(self.skip_features, up_feats),
            dtype=self.dtype,
            name="conv",
        )(x, train)


class _FusedEpilogueBatchNorm(nn.Module):
    """``nn.BatchNorm`` + ReLU with the normalize+activation tail in ONE
    fused VMEM pass (ops/kernels.fused_bn_act — the ``--kernels pallas``
    conv-epilogue engagement site). Parameter and ``batch_stats`` trees
    are EXACTLY ``nn.BatchNorm``'s (scale/bias params, mean/var stats —
    same names, shapes, inits), so checkpoints are interchangeable with
    the XLA path. The batch statistics themselves (mean/var reductions +
    running-average updates, mirroring flax's fast-variance formula) stay
    XLA: they are reductions the compiler already fuses, and keeping them
    outside lets autodiff chain d(mean)/d(var) → x through the kernel's
    hand-written VJP. Matches the XLA twin to float-rounding tolerance
    (the folded affine associates differently — tests/test_kernels.py)."""

    features: int
    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        from distributedpytorch_tpu.ops.kernels import fused_bn_act

        C = self.features
        scale = self.param(
            "scale", nn.initializers.ones_init(), (C,), jnp.float32
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (C,), jnp.float32
        )
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((C,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((C,), jnp.float32)
        )
        xf = x.astype(jnp.float32)
        if train:
            # flax _compute_stats fast-variance: E[x²] − E[x]², clipped
            mean = jnp.mean(xf, axis=(0, 1, 2))
            mean2 = jnp.mean(jnp.square(xf), axis=(0, 1, 2))
            var = jnp.maximum(0.0, mean2 - jnp.square(mean))
            if not self.is_initializing():
                ra_mean.value = (
                    self.momentum * ra_mean.value + (1.0 - self.momentum) * mean
                )
                ra_var.value = (
                    self.momentum * ra_var.value + (1.0 - self.momentum) * var
                )
        else:
            mean, var = ra_mean.value, ra_var.value
        return fused_bn_act(xf, mean, var, scale, bias, epsilon=self.epsilon)


class DoubleConv(nn.Module):
    """[Conv3×3(no bias) → BatchNorm → ReLU] × 2
    (reference model/modelsummary.txt:155-160).

    ``epilogue=True`` fuses each BN-normalize + ReLU tail into one VMEM
    pass (``_FusedEpilogueBatchNorm``) while XLA keeps the conv itself —
    the ``--kernels pallas`` conv-epilogue engagement; identical param
    tree either way."""

    features: int
    mid_features: int = 0  # 0 = features (bilinear Up passes in//2)
    dtype: Any = jnp.bfloat16
    epilogue: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        mid = self.mid_features or self.features
        for i, feats in enumerate((mid, self.features)):
            x = nn.Conv(
                feats, (3, 3), padding=1, use_bias=False, dtype=self.dtype,
                name=f"conv{i + 1}",
            )(x)
            if self.epilogue:
                x = _FusedEpilogueBatchNorm(
                    feats, name=f"bn{i + 1}"
                )(x, train).astype(self.dtype)
                continue
            # float32 statistics; torch defaults are eps=1e-5, momentum=0.1
            # (flax momentum = 1 − torch momentum)
            x = nn.BatchNorm(
                use_running_average=not train, momentum=0.9, epsilon=1e-5,
                dtype=jnp.float32, name=f"bn{i + 1}",
            )(x.astype(jnp.float32))
            x = nn.relu(x).astype(self.dtype)
        return x


class Down(nn.Module):
    """MaxPool(2) → DoubleConv (reference modelsummary.txt:161-169)."""

    features: int
    dtype: Any = jnp.bfloat16
    epilogue: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        x = nn.max_pool(x, window_shape=(2, 2), strides=(2, 2))
        return DoubleConv(
            self.features, dtype=self.dtype, epilogue=self.epilogue,
            name="conv",
        )(x, train)


class Up(nn.Module):
    """Upsample → concat skip → DoubleConv (reference modelsummary.txt:193-201).

    ``bilinear=False`` (the documented 31M config): ConvTranspose(k=2,s=2)
    halving the channels. ``bilinear=True``: parameter-free bilinear resize,
    DoubleConv with mid = in//2 (milesial's memory-saving mode).
    """

    features: int
    bilinear: bool = False
    dtype: Any = jnp.bfloat16
    epilogue: bool = False

    @nn.compact
    def __call__(
        self, x: jax.Array, skip: jax.Array, train: bool = False
    ) -> jax.Array:
        if self.bilinear:
            b, h, w, c = x.shape
            x = jax.image.resize(x, (b, 2 * h, 2 * w, c), method="bilinear")
            # milesial: DoubleConv(in_channels, out, mid=in_channels // 2)
            # where in_channels is the CONCATENATED width (skip + upsampled)
            mid = (x.shape[-1] + skip.shape[-1]) // 2
        else:
            x = nn.ConvTranspose(
                x.shape[-1] // 2, (2, 2), strides=(2, 2), dtype=self.dtype,
                name="up",
            )(x)
            mid = 0
        skip = center_crop(skip, (x.shape[1], x.shape[2]))
        x = jnp.concatenate([skip, x], axis=-1)
        return DoubleConv(
            self.features, mid_features=mid, dtype=self.dtype,
            epilogue=self.epilogue, name="conv",
        )(x, train)


class MilesialUNet(nn.Module):
    """inc → Down×4 → Up×4 → OutConv (reference modelsummary.txt:150-247).

    ``s2d_levels`` executes the shallowest levels in the space-to-depth
    domain (ops/s2d.py), exactly like models/unet.py's flagship model —
    level 0 is the full-resolution `inc` stem (64 channels at 640×960:
    the same MXU-starving shape the course model's s2d rewrite attacks),
    level i is `down_i`. BatchNorm statistics stay exact via
    `_S2DBatchNorm` (reduced over the s2d group axis as well as
    batch × space). -1 = auto (2 on TPU, 0 elsewhere); requires
    ``bilinear=False`` (the documented 31M config) and input sizes
    divisible by 2**levels.
    """

    n_classes: int = 1
    bilinear: bool = False
    widths: Sequence[int] = MILESIAL_WIDTHS
    dtype: Any = jnp.bfloat16
    s2d_levels: int = -1
    # Fuse every pixel-domain DoubleConv's BN-normalize + ReLU into one
    # VMEM pass (ops/kernels.fused_bn_act, --kernels pallas). Identical
    # param/batch_stats trees; s2d-domain levels keep _S2DBatchNorm.
    # Engagement is the model factory's call (models/__init__.py via
    # ops/kernels.conv_epilogue_engaged — device-local forwards only).
    conv_epilogue: bool = False

    # train/steps.py and parallel/pipeline.py key off this to thread the
    # batch_stats collection
    is_stateful = True

    def _s2d_levels(self) -> int:
        auto = self.s2d_levels < 0
        lv = (2 if jax.default_backend() == "tpu" else 0) if auto else self.s2d_levels
        lv = max(0, min(lv, len(self.widths) - 2))
        if lv > 0 and self.bilinear:
            if auto:  # auto never breaks a previously-valid config
                return 0
            raise ValueError(
                "s2d execution supports the transposed-conv decoder only "
                "(bilinear=False) — pass s2d_levels=0 with bilinear"
            )
        return lv

    # -- pipeline segments (parallel/pipeline.py) ---------------------------
    # The family's linear block order: inc, L Down levels, L Up levels with
    # the 1×1 outc head folded into the last — 2L+1 segments, the same
    # carry convention as models/unet.py (encoder segments push skips,
    # decoder segments pop; inc's output IS its own skip, milesial-style).
    @property
    def num_segments(self) -> int:
        return 2 * (len(self.widths) - 1) + 1

    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        lv = self._s2d_levels()
        if lv > 0:
            div = 2 ** (len(self.widths) - 1)
            h_, w_ = x.shape[1], x.shape[2]
            if h_ % div or w_ % div:
                if self.s2d_levels < 0:
                    # auto mode degrades to the (center-crop-tolerant)
                    # pixel path rather than rejecting a size the model
                    # handled before s2d existed
                    lv = 0
                else:
                    raise ValueError(
                        f"input {h_}×{w_} is not divisible by {div} "
                        f"(2**levels), which the space-to-depth execution "
                        f"mode requires — resize the input or pass "
                        f"s2d_levels=0 (CLI: --s2d-levels 0)"
                    )
        x, _skips = self._apply_segments(x, (), 0, self.num_segments, train, lv)
        return x

    def apply_segment(
        self, x: jax.Array, skips: Tuple[jax.Array, ...], seg: int,
        train: bool = False,
    ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        """Run segment ``seg`` (static int) of the linear block order —
        the stateful `(params, batch_stats) → (y, batch_stats')` path the
        pipeline schedules thread: apply with ``mutable=['batch_stats']``
        and ``train=True`` to get this segment's BatchNorm updates
        (batch statistics are per-microbatch, GPipe-style; the schedule
        psums the running-stat deltas across the stage axis).

        The s2d execution domain of every segment is a static function of
        the CONFIGURED level count, so stages can start at any segment
        without threading domain state; a ragged input therefore fails
        fast here (the full forward's auto-degrade would silently pick a
        different domain per stage)."""
        lv = self._s2d_levels()
        if seg == 0 and lv > 0:
            div = 2 ** (len(self.widths) - 1)
            h_, w_ = x.shape[1], x.shape[2]
            if h_ % div or w_ % div:
                raise ValueError(
                    f"input {h_}×{w_} is not divisible by {div} "
                    f"(2**levels), which the space-to-depth execution mode "
                    f"requires under the pipeline schedule — resize the "
                    f"input or pass s2d_levels=0 (CLI: --s2d-levels 0)"
                )
        return self._apply_segments(x, tuple(skips), seg, seg + 1, train, lv)

    @nn.compact
    def _apply_segments(
        self,
        x: jax.Array,
        skips: Tuple[jax.Array, ...],
        first: int,
        last: int,
        train: bool,
        lv: int,
    ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        """Segments [first, last) of the linear block order. Module names
        ("inc", "down{i}", "up{i}", "outc") are explicit, so any segment
        subset builds the same parameter tree entries as the full forward
        — what lets `apply_segment` run one segment against the full
        variables dict."""
        w = tuple(self.widths)
        assert len(w) >= 2, "milesial needs at least inc + one Down level"
        factor = 2 if self.bilinear else 1
        L = len(w) - 1  # downs; also the number of Ups
        skips = tuple(skips)
        for seg in range(first, last):
            if seg == 0:  # inc stem; its output is also the first skip
                if lv > 0:
                    xs = s2d_ops.space_to_depth(x)
                    x = DoubleConvS2D(
                        w[0], in_features=x.shape[-1], dtype=self.dtype,
                        name="inc",
                    )(xs, train)
                else:
                    x = DoubleConv(
                        w[0], dtype=self.dtype,
                        epilogue=self.conv_epilogue, name="inc",
                    )(x, train)
                skips = skips + (x,)
            elif seg <= L:  # Down level `seg`
                level = seg
                feats = w[level] // (factor if level == L else 1)
                if level < lv or (level == lv and lv > 0):
                    # s2d level, or the boundary Down whose pool consumes
                    # an s2d input (group_max) but convs in the pixel
                    # domain
                    x = _DownS2D(
                        feats, in_features=w[level - 1],
                        prev_s2d=level - 1 < lv, this_s2d=level < lv,
                        dtype=self.dtype, epilogue=self.conv_epilogue,
                        name=f"down{level}",
                    )(x, train)
                else:
                    x = Down(
                        feats, dtype=self.dtype,
                        epilogue=self.conv_epilogue, name=f"down{level}",
                    )(x, train)
                if level < L:  # the deepest Down is the bottleneck, no skip
                    skips = skips + (x,)
            else:  # Up level; the last one carries outc + activation
                i = seg - L - 1  # 0-based Up index
                feats = w[L - 1 - i]
                out_feats = feats // (factor if i < L - 1 else 1)
                skip = skips[-1]
                skips = skips[:-1]
                if i >= L - lv:
                    # shallowest lv Ups: skip is s2d-form, output stays s2d
                    x = _UpS2D(
                        out_feats,
                        skip_features=feats,
                        prev_s2d=i - 1 >= L - lv,
                        dtype=self.dtype,
                        name=f"up{i + 1}",
                    )(x, skip, train)
                else:
                    x = Up(
                        out_feats,
                        bilinear=self.bilinear,
                        dtype=self.dtype,
                        epilogue=self.conv_epilogue,
                        name=f"up{i + 1}",
                    )(x, skip, train)
                if seg == 2 * L:
                    if lv > 0:
                        x = _S2DConv(
                            self.n_classes, w[0], "head", dtype=self.dtype,
                            name="outc",
                        )(x)
                        x = s2d_ops.depth_to_space(x)
                    else:
                        x = nn.Conv(
                            self.n_classes, (1, 1), dtype=self.dtype,
                            name="outc",
                        )(x)
                    if self.n_classes == 1:
                        x = jax.nn.sigmoid(x.astype(jnp.float32))
                    else:
                        x = x.astype(jnp.float32)
        return x, skips


def init_milesial(
    model: MilesialUNet, rng: jax.Array, input_hw: Tuple[int, int] = (64, 96)
):
    """Initialize; returns ``(params, batch_stats)``."""
    dummy = jnp.zeros((1, input_hw[0], input_hw[1], 3), jnp.float32)
    variables = model.init(rng, dummy, train=False)
    return variables["params"], variables["batch_stats"]
