"""UNet for binary segmentation, TPU-native (flax.linen, NHWC).

Capability parity with the reference model (reference model/unet_parts.py:6-77,
model/unet_model.py:4-62): a 4-down/4-up UNet with channel widths
3→32→64→128→256, a 256→512 mid block, symmetric decoder with skip
concatenation, a 1×1 segmentation head, and a sigmoid output. Parameter-count
golden: 7,760,097 trainable parameters (reference model/modelsummary.txt:63).

TPU-first divergences from the reference (deliberate, not bugs):
  * NHWC layout throughout — XLA:TPU tiles the channel dimension onto the
    (8,128)/(16,128) vector lanes; NCHW would force relayouts around every
    conv. The data pipeline emits NHWC; a checkpoint shim handles NCHW
    interop (see checkpoint.py).
  * Convolutions are `flax.linen.Conv` → `lax.conv_general_dilated`; maxpool
    is `lax.reduce_window`; the 2×2-stride-2 up-convolution is
    `flax.linen.ConvTranspose` → `lax.conv_transpose`. All lower to MXU/VPU
    ops — no Python-level loops.
  * Compute dtype is configurable (default bfloat16 for the MXU); parameters
    are float32.
  * The shallow levels execute in the 2×2 space-to-depth domain by default
    (``s2d_levels=2``, ops/s2d.py): the full-resolution C=32/64 convs run at
    ~2.5% of MXU peak in pixel form but ~19% as structured 4C-channel convs
    at half resolution — an exactly-equivalent rewrite (same parameters,
    same function; tests/test_s2d.py) worth ~1.9× step time at the
    reference config.
  * The center-crop of skip tensors (reference unet_parts.py:58-73 uses
    torchvision CenterCrop) is a static slice; with 'SAME'-padded convs and
    input sizes divisible by 16 it is a no-op, exactly as in the reference.

The 2-stage pipeline split (reference unet_model.py:14-20: encoder+mid on
stage 0, decoder+head on stage 1) is NOT baked into the model here — stage
placement is a *strategy* concern handled in parallel/pipeline.py over the
same flax modules (`UNet.encode_mid` / `UNet.decode_head`).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributedpytorch_tpu.ops import s2d as s2d_ops

# Channel plan of the reference model (unet_parts.py:28-33, 16, 51-54).
ENCODER_WIDTHS = (32, 64, 128, 256)
MID_WIDTH = 512


def center_crop(x: jax.Array, target_hw: Tuple[int, int]) -> jax.Array:
    """Static center crop of an NHWC tensor to (H, W) = target_hw.

    Parity with torchvision CenterCrop as used at reference
    unet_parts.py:58-73. Shapes are static under jit, so this is a slice,
    not a dynamic gather.
    """
    h, w = x.shape[1], x.shape[2]
    th, tw = target_hw
    dh, dw = (h - th) // 2, (w - tw) // 2
    return x[:, dh : dh + th, dw : dw + tw, :]


class _S2DConv(nn.Module):
    """Param-compatible stand-in for ``nn.Conv``/``nn.ConvTranspose``
    evaluated in the space-to-depth domain (ops/s2d.py).

    Declares ``kernel``/``bias`` with the exact names, shapes, and
    initializers flax's own modules use, so checkpoints, the 7,760,097-param
    golden, and `.pth` interop are identical whether or not the s2d
    execution mode is on. The structured dense kernel is assembled from
    those parameters inside the traced computation — autodiff puts the
    gradients back on the original weights.

    Modes: ``conv3x3`` (s2d in → s2d out), ``upconv`` (pixel in → s2d out,
    the k=2 s=2 ConvTranspose), ``head`` (s2d in → s2d out, 1×1 conv).
    """

    features: int
    in_features: int
    mode: str = "conv3x3"
    dtype: Any = jnp.bfloat16
    in_segments: Optional[Tuple[int, ...]] = None
    # False for BatchNorm-following convs (milesial DoubleConv) — the
    # param tree then matches nn.Conv(use_bias=False) exactly.
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kshape = {"conv3x3": (3, 3), "upconv": (2, 2), "head": (1, 1)}[self.mode]
        w = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (*kshape, self.in_features, self.features),
            jnp.float32,
        )
        w = w.astype(self.dtype)
        x = x.astype(self.dtype)
        if self.mode == "conv3x3":
            dense = s2d_ops.conv3x3_kernel(w, self.in_segments)
        elif self.mode == "upconv":
            dense = s2d_ops.upconv_kernel(w)
        else:
            dense = s2d_ops.head1x1_kernel(w, self.in_segments)
        y = s2d_ops.conv_same(x, dense)
        if not self.use_bias:
            return y
        b = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,), jnp.float32
        )
        return y + s2d_ops.tile_bias(b).astype(y.dtype)


class ConvBlock(nn.Module):
    """[Conv3×3(pad=1) → ReLU] × 2 (reference unet_parts.py:6-17).

    ``s2d=True`` evaluates both convs in the space-to-depth domain
    (ops/s2d.py) — exactly equivalent, ~2× faster on the shallow
    full-resolution levels where C ≪ the 128 MXU lanes. ``in_features`` /
    ``in_segments`` describe the logical input channels then (the s2d input
    tensor carries 4× that).
    """

    features: int
    dtype: Any = jnp.bfloat16
    s2d: bool = False
    in_features: Optional[int] = None
    in_segments: Optional[Tuple[int, ...]] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.s2d:
            assert self.in_features is not None
            x = _S2DConv(
                self.features,
                self.in_features,
                "conv3x3",
                dtype=self.dtype,
                in_segments=self.in_segments,
                name="conv1",
            )(x)
            x = nn.relu(x)
            x = _S2DConv(
                self.features, self.features, "conv3x3", dtype=self.dtype,
                name="conv2",
            )(x)
            x = nn.relu(x)
            return x
        conv = functools.partial(
            nn.Conv, kernel_size=(3, 3), padding=1, dtype=self.dtype
        )
        x = conv(self.features, name="conv1")(x)
        x = nn.relu(x)
        x = conv(self.features, name="conv2")(x)
        x = nn.relu(x)
        return x


def _maxpool2x2(x: jax.Array) -> jax.Array:
    """MaxPool2d(kernel=2, stride=2) (reference unet_parts.py:26)."""
    return nn.max_pool(x, window_shape=(2, 2), strides=(2, 2))


class Encoder(nn.Module):
    """4 conv_blocks with 2×2 maxpool between; returns bottleneck + 4 skips
    (reference unet_parts.py:19-41).

    The first ``s2d_levels`` levels run in the space-to-depth domain: their
    skip tensors are emitted in s2d form (the decoder consumes them there
    directly), and the 2×2 maxpool collapses to a max over the s2d group —
    its output is already the next level's pixel-resolution input.

    Levels are individually callable (`level`) so the S-stage pipeline can
    cut the model anywhere in its linear block order (parallel/pipeline.py);
    `__call__` chains them and is unchanged in numerics and param naming.
    """

    widths: Sequence[int] = ENCODER_WIDTHS
    dtype: Any = jnp.bfloat16
    s2d_levels: int = 0
    in_features: int = 3  # input channels (RGB images)

    def setup(self):
        blocks = []
        in_feats = self.in_features
        for i, w in enumerate(self.widths):
            if i < self.s2d_levels:
                blocks.append(ConvBlock(
                    w,
                    dtype=self.dtype,
                    s2d=True,
                    in_features=in_feats,
                    name=f"block{i + 1}",
                ))
            else:
                blocks.append(ConvBlock(
                    w, dtype=self.dtype, name=f"block{i + 1}",
                ))
            in_feats = w
        self.blocks = blocks

    def level(self, x: jax.Array, i: int) -> Tuple[jax.Array, jax.Array]:
        """Encoder level ``i``: conv block + pool → (pooled, skip)."""
        if i < self.s2d_levels:
            xs = s2d_ops.space_to_depth(x)
            xs = self.blocks[i](xs)
            return s2d_ops.group_max(xs), xs  # skip stays in s2d form
        x = self.blocks[i](x)
        return _maxpool2x2(x), x

    def __call__(self, x: jax.Array) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        skips = []
        for i in range(len(self.widths)):
            x, skip = self.level(x, i)
            skips.append(skip)
        return x, tuple(skips)


class Decoder(nn.Module):
    """4 × [ConvTranspose(k=2,s=2) → center-crop skip → concat → conv_block]
    (reference unet_parts.py:43-77)."""

    widths: Sequence[int] = tuple(reversed(ENCODER_WIDTHS))  # 256,128,64,32
    dtype: Any = jnp.bfloat16
    s2d_levels: int = 0
    in_features: Optional[int] = None  # bottleneck channels (default 2·widths[0])

    def setup(self):
        # The shallowest s2d_levels iterations (i ≥ n − s2d_levels) run in
        # the s2d domain: the upconv becomes a 1×1 conv from the pixel-space
        # input, the skip arrives already in s2d form, and the concat needs
        # no data movement (the conv kernel's in_segments absorb the layout).
        n = len(self.widths)
        first_in = self.in_features or 2 * self.widths[0]
        ups, blocks = [], []
        for i, w in enumerate(self.widths):
            logical_in = first_in if i == 0 else self.widths[i - 1]
            if i >= n - self.s2d_levels:
                ups.append(_S2DConv(
                    w, logical_in, "upconv", dtype=self.dtype,
                    name=f"upconv{i + 1}",
                ))
                blocks.append(ConvBlock(
                    w,
                    dtype=self.dtype,
                    s2d=True,
                    in_features=2 * w,
                    in_segments=(w, w),
                    name=f"block{i + 1}",
                ))
            else:
                ups.append(nn.ConvTranspose(
                    w, (2, 2), strides=(2, 2), dtype=self.dtype,
                    name=f"upconv{i + 1}",
                ))
                blocks.append(ConvBlock(
                    w, dtype=self.dtype, name=f"block{i + 1}",
                ))
        self.ups = ups
        self.blocks = blocks

    def level(self, x: jax.Array, skip: jax.Array, i: int) -> jax.Array:
        """Decoder level ``i``: upconv → crop/concat skip → conv block.

        ``x`` arrives in s2d form iff level ``i−1`` ran in the s2d domain —
        a static property of ``i``, so pipeline stages can start at any
        level without threading execution-domain state across stages."""
        n = len(self.widths)
        if i >= n - self.s2d_levels:
            if i - 1 >= n - self.s2d_levels:
                x = s2d_ops.depth_to_space(x)
            up = self.ups[i](x)
            assert skip.shape == up.shape, (
                "s2d decoder expects the identity center-crop (even input "
                f"sizes): skip {skip.shape} vs upconv {up.shape}"
            )
            x = jnp.concatenate([skip, up], axis=-1)
            return self.blocks[i](x)
        x = self.ups[i](x)
        skip = center_crop(skip, (x.shape[1], x.shape[2]))
        x = jnp.concatenate([skip, x], axis=-1)
        return self.blocks[i](x)

    def __call__(self, x: jax.Array, skips: Sequence[jax.Array]) -> jax.Array:
        # skips arrive encoder-ordered (shallow→deep); consume deepest first.
        for i in range(len(self.widths)):
            x = self.level(x, skips[len(skips) - 1 - i], i)
        return x


class UNet(nn.Module):
    """Full UNet: Encoder → mid ConvBlock → Decoder → 1×1 head → sigmoid
    (reference model/unet_model.py:4-11, forward at :55-61).

    Input:  NHWC float, (B, H, W, 3), H and W divisible by 2**len(widths).
    Output: (B, H, W, 1) probabilities in (0, 1).

    `widths` defaults to the reference's channel plan (7,760,097 params);
    narrower/shallower variants (e.g. ``widths=(8, 16)``) compile in a
    fraction of the time — the test suite uses them for the parallelism
    machinery, where the model is a payload, not the thing under test.
    """

    n_classes: int = 1
    dtype: Any = jnp.bfloat16
    widths: Sequence[int] = ENCODER_WIDTHS
    mid_width: int = 0  # 0 = 2 × widths[-1] (the reference's 256→512)
    # Input channels. Static (setup-time) because the s2d execution mode
    # builds its level-1 kernels from it; the data pipeline always emits
    # RGB, so non-3 is for library users feeding other imagery.
    in_channels: int = 3
    # How many shallow levels execute in the space-to-depth domain
    # (ops/s2d.py) — exactly equivalent, measured ~2× faster on TPU for the
    # full-resolution C=32/64 levels. 0 disables; -1 = auto (2 on a TPU
    # backend, 0 elsewhere — the 4× nominal MACs only pay off on the MXU).
    s2d_levels: int = -1

    def _s2d_levels(self) -> int:
        lv = self.s2d_levels
        if lv < 0:
            lv = 2 if jax.default_backend() == "tpu" else 0
        return max(0, min(lv, len(self.widths)))

    def setup(self):
        mid = self.mid_width or 2 * self.widths[-1]
        lv = self._s2d_levels()
        self.encoder = Encoder(
            widths=tuple(self.widths),
            dtype=self.dtype,
            s2d_levels=lv,
            in_features=self.in_channels,
        )
        self.mid = ConvBlock(mid, dtype=self.dtype)
        self.decoder = Decoder(
            widths=tuple(reversed(self.widths)),
            dtype=self.dtype,
            s2d_levels=lv,
            in_features=mid,
        )
        if lv > 0:
            self.segmap = _S2DConv(
                self.n_classes, self.widths[0], "head", dtype=self.dtype
            )
        else:
            self.segmap = nn.Conv(self.n_classes, (1, 1), dtype=self.dtype)

    def __call__(self, x: jax.Array) -> jax.Array:
        x, skips = self.encode_mid(x)
        return self.decode_head(x, skips)

    # -- pipeline stage boundaries (reference unet_model.py:16-20 cut) -----
    def _check_s2d_size(self, x: jax.Array) -> None:
        """The pixel path degrades gracefully on ragged sizes via the
        decoder's center-crop; the s2d path cannot — fail fast with the
        workaround instead of asserting deep in the first step. Called at
        every model entry (full forward, 2-stage cut, segment 0)."""
        if self._s2d_levels() > 0:
            div = 2 ** len(self.widths)
            h, w = x.shape[1], x.shape[2]
            if h % div or w % div:
                raise ValueError(
                    f"input {h}×{w} is not divisible by {div} "
                    f"(2**levels), which the space-to-depth execution mode "
                    f"requires — resize the input or pass s2d_levels=0 "
                    f"(CLI: --s2d-levels 0)"
                )

    def encode_mid(self, x: jax.Array) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        """Stage 0 of the 2-stage pipeline: encoder + mid block."""
        self._check_s2d_size(x)
        x, skips = self.encoder(x)
        x = self.mid(x)
        return x, skips

    def decode_head(self, x: jax.Array, skips: Sequence[jax.Array]) -> jax.Array:
        """Stage 1 of the 2-stage pipeline: decoder + 1×1 head + sigmoid.

        The sigmoid runs in float32: probabilities feed a log-based loss and
        bfloat16 resolution near 0/1 would poison it.
        """
        x = self.decoder(x, skips)
        return self._head(x)

    def _head(self, x: jax.Array) -> jax.Array:
        from distributedpytorch_tpu.ops.precision import LOSS_DTYPE

        x = self.segmap(x)
        if self._s2d_levels() > 0:
            x = s2d_ops.depth_to_space(x)  # (B, H/2, W/2, 4·ncls) → (B, H, W, ncls)
        # sigmoid in the loss dtype: probabilities feed a log-based loss
        # and bf16 resolution near 0/1 would poison it (the policy's
        # LOSS_DTYPE contract — every --dtype keeps this boundary f32)
        return jax.nn.sigmoid(x.astype(LOSS_DTYPE))

    # -- S-stage pipeline segments (parallel/pipeline.py) -------------------
    # The model's linear block order: L encoder levels, the mid block, then
    # L decoder levels with the 1×1 head folded into the last. A pipeline
    # stage is any contiguous run of these 2L+1 segments; the reference's
    # 2-stage cut (unet_model.py:16-20) is the boundary after segment L.
    @property
    def num_segments(self) -> int:
        return 2 * len(self.widths) + 1

    def apply_segment(
        self, x: jax.Array, skips: Tuple[jax.Array, ...], seg: int,
        train: bool = False,
    ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        """Run segment ``seg`` (static int) of the linear block order.

        Carry convention: ``(x, skips)`` where ``skips`` holds the encoder
        outputs produced so far and not yet consumed — segments push during
        encode, pop (deepest-first) during decode, so the inter-stage
        payload at any cut is exactly this carry.

        ``train`` is the uniform segment signature shared with the
        stateful family (models/milesial.py `apply_segment`, where it
        selects batch-vs-running statistics); this model is stateless, so
        it is accepted and ignored.
        """
        L = len(self.widths)
        if seg == 0:
            self._check_s2d_size(x)
        if seg < L:  # encoder level
            x, skip = self.encoder.level(x, seg)
            return x, tuple(skips) + (skip,)
        if seg == L:  # mid block
            return self.mid(x), tuple(skips)
        i = seg - L - 1  # decoder level
        x = self.decoder.level(x, skips[-1], i)
        skips = tuple(skips)[:-1]
        if seg == 2 * L:  # last decoder level carries the head
            x = self._head(x)
        return x, skips


def create_unet(config=None, dtype=None) -> UNet:
    """Build a UNet from a TrainConfig (or dtype override)."""
    if dtype is None:
        from distributedpytorch_tpu.ops.precision import get_policy

        dtype = (
            get_policy(config).compute_dtype
            if config is not None
            else jnp.bfloat16
        )
    widths = ENCODER_WIDTHS
    if config is not None and getattr(config, "model_widths", None):
        widths = tuple(config.model_widths)
    s2d_levels = getattr(config, "s2d_levels", -1) if config is not None else -1
    return UNet(dtype=dtype, widths=widths, s2d_levels=s2d_levels)


def init_unet_params(model: UNet, rng: jax.Array, input_hw=(640, 960)):
    """Initialize parameters with a (1, H, W, 3) dummy batch."""
    dummy = jnp.zeros((1, input_hw[0], input_hw[1], 3), jnp.float32)
    return model.init(rng, dummy)["params"]


def param_count(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
