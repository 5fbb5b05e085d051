"""Space-to-depth (s2d) execution domain for shallow UNet levels.

Why this exists (measured on the attached TPU v5e, batch 4, bf16):
the full-resolution low-channel convolutions that dominate the reference
UNet's shallow levels map terribly onto the 128-lane MXU —

    conv  32→32 @640×960:  5.3 TFLOP/s fwd,  4.2 TFLOP/s bwd   (~2.5% peak)
    conv 128→128 @320×480: 37.9 TFLOP/s fwd, 36.0 TFLOP/s bwd

Rewriting a 3×3 stride-1 SAME conv over (H, W, C) as a 3×3 SAME conv over
the 2×2 space-to-depth image (H/2, W/2, 4C) does 4× the MAC count (the
structured kernel is 3/4 zeros) yet runs ~2× faster wall-clock on those
shapes, forward and backward. The transform is EXACT: the dense kernel is
assembled from the original (3,3,Cin,Cout) parameters inside the traced
computation, so parameter pytrees, checkpoints, and autodiff (gradients
flow through the assembly and land on the original weights) are unchanged.

Layout convention ("g-major"): the s2d image S of a pixel image X is

    S[b, i, j, g*C + c] = X[b, 2i + di, 2j + dj, c],   g = 2*di + dj

with di/dj ∈ {0,1} the intra-block row/col offsets. A concatenation of two
s2d tensors is NOT the s2d of the pixel concatenation — kernel builders
take ``in_segments`` describing the per-tensor channel counts so the skip
concat in the UNet decoder needs no data movement at all.

The pool (``group_max``). The 2×2 window is the s2d group, so the
maxpool is a maximum over the four channel slices ``x[..., g*C:(g+1)*C]``
— taken as elementwise maxima of slices, never as ``reshape(b, h, w, 4,
C)``: on the TPU the channels lie in the 128 lanes, and splitting the lane
dimension is a relayout of the whole activation (two 629 MB copies a step
at level 1 of the course UNet at batch 16, and as many again in the
backward). It carries its own VJP. The forward picks each window's winner
once, from the operand it is given: the FIRST maximum in window order
(g = 2*di + dj), which is what ``torch.nn.MaxPool2d``,
``lax.reduce_window``'s gradient and ``nn.max_pool`` at the pixel levels
pick, stored as a group index 0..3 in the activation's own dtype (so that
XLA writes maximum and index in one pass). The backward hands ``dy`` whole to
that group and zero to the others, as one select at the full 4C lanes
(``dy`` and the index laid four times side by side, compared with each
lane's group), which XLA fuses into the ReLU backward next to it. There is
no comparison with a stored maximum — ``jnp.max``'s gradient made one, and
behind BatchNorm + ReLU in the jitted step it missed in one window of
eight (PERF.md §6, PR 26) — no count of ties and no divide.

Every builder here mirrors one reference op:
  * 3×3 SAME conv           (reference model/unet_parts.py:10-12)
  * 2×2 stride-2 maxpool    (reference model/unet_parts.py:26)
  * 2×2 stride-2 ConvTranspose (reference model/unet_parts.py:51-54)
  * 1×1 segmentation head   (reference model/unet_model.py:10)
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def space_to_depth(x: jax.Array) -> jax.Array:
    """(B, H, W, C) → (B, H/2, W/2, 4C), g-major. H and W must be even."""
    b, h, w, c = x.shape
    assert h % 2 == 0 and w % 2 == 0, f"s2d needs even H, W; got {(h, w)}"
    x = x.reshape(b, h // 2, 2, w // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # (B, H/2, W/2, di, dj, C)
    return x.reshape(b, h // 2, w // 2, 4 * c)


def depth_to_space(x: jax.Array) -> jax.Array:
    """Inverse of :func:`space_to_depth`."""
    b, h, w, c4 = x.shape
    assert c4 % 4 == 0
    c = c4 // 4
    x = x.reshape(b, h, w, 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # (B, H, di, W, dj, C)
    return x.reshape(b, 2 * h, 2 * w, c)


def _conv3x3_kernel_one_segment(w: jax.Array) -> jax.Array:
    """(3,3,Cin,Cout) → (3,3,4Cin,4Cout), single g-major input segment.

    Derivation: output pixel row 2I+do+ky−1 sits in block row I+Bi−1 at
    intra-block offset di where 2·Bi+di = do+ky+1 — so for a fixed output
    group, padding the kernel's ky axis to 6 slots at offset do+1 and
    reshaping 6 → (Bi=3, di=2) places every tap, no scatters. Built from
    pads/reshapes/one stack so the traced graph stays tiny (a 36-scatter
    construction made XLA compiles of the differentiated model ~5× slower).
    """
    cin, cout = w.shape[2], w.shape[3]
    per_group = []
    for do_i in range(2):
        wi = jnp.pad(w, ((do_i + 1, 2 - do_i), (0, 0), (0, 0), (0, 0)))
        for do_j in range(2):
            wij = jnp.pad(wi, ((0, 0), (do_j + 1, 2 - do_j), (0, 0), (0, 0)))
            # (6, 6, Cin, Cout) → (Bi, di, Bj, dj, Cin, Cout)
            wij = wij.reshape(3, 2, 3, 2, cin, cout)
            per_group.append(wij.transpose(0, 2, 1, 3, 4, 5))
    # (g_out, Bi, Bj, di, dj, Cin, Cout) → (Bi, Bj, (di,dj,Cin), (g_out,Cout))
    dense = jnp.stack(per_group, axis=0).transpose(1, 2, 3, 4, 5, 0, 6)
    return dense.reshape(3, 3, 4 * cin, 4 * cout)


def conv3x3_kernel(
    w: jax.Array, in_segments: Optional[Sequence[int]] = None
) -> jax.Array:
    """(3,3,Cin,Cout) → (3,3,4Cin,4Cout) structured dense kernel such that a
    SAME conv of it over the s2d image equals the SAME conv of ``w`` over
    the pixel image (then s2d). 1/4 density — each output group uses 2×2 of
    the 3×3 block taps. ``in_segments`` describes an input that is a channel
    concatenation of independently g-major s2d tensors (the decoder's skip
    concat): each segment's kernel slice transforms independently."""
    kh, kw, cin, cout = w.shape
    assert (kh, kw) == (3, 3), f"conv3x3_kernel got kernel {w.shape}"
    segs = tuple(in_segments) if in_segments is not None else (cin,)
    assert sum(segs) == cin, (segs, cin)
    parts = []
    off = 0
    for seg in segs:
        parts.append(_conv3x3_kernel_one_segment(w[:, :, off : off + seg, :]))
        off += seg
    return jnp.concatenate(parts, axis=2) if len(parts) > 1 else parts[0]


def upconv_kernel(u: jax.Array) -> jax.Array:
    """(2,2,Cin,Cout) ConvTranspose(k=2,s=2) weights → (1,1,Cin,4Cout): the
    stride-2 transpose conv writes each output pixel from exactly one tap,
    so in s2d space it is a 1×1 conv on the PIXEL-space input at half
    resolution. flax/lax orientation (verified): Y[2I+di, 2J+dj] =
    X[I,J] @ U[1−di, 1−dj]."""
    kh, kw, cin, cout = u.shape
    assert (kh, kw) == (2, 2), f"upconv_kernel got kernel {u.shape}"
    flipped = u[::-1, ::-1]  # [di, dj] = U[1−di, 1−dj]
    dense = flipped.transpose(2, 0, 1, 3).reshape(cin, 4 * cout)
    return dense[None, None]


def head1x1_kernel(
    w: jax.Array, in_segments: Optional[Sequence[int]] = None
) -> jax.Array:
    """(1,1,Cin,Cout) → (1,1,4Cin,4Cout) block-diagonal-by-group kernel: a
    1×1 conv acts within each pixel, i.e. within each s2d group —
    kron(I₄, w) in the g-major layout."""
    kh, kw, cin, cout = w.shape
    assert (kh, kw) == (1, 1), f"head1x1_kernel got kernel {w.shape}"
    segs = tuple(in_segments) if in_segments is not None else (cin,)
    assert sum(segs) == cin, (segs, cin)
    eye = jnp.eye(4, dtype=w.dtype)
    parts = []
    off = 0
    for seg in segs:
        parts.append(jnp.kron(eye, w[0, 0, off : off + seg, :]))
        off += seg
    dense = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    return dense[None, None]


def tile_bias(b: jax.Array) -> jax.Array:
    """Per-channel bias → per-s2d-channel bias (g-major ⇒ plain tile)."""
    return jnp.tile(b, 4)


def _max_and_winner(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Maximum over the four lane slices of an s2d tensor and the group it
    came from, 0..3 in ``x``'s own dtype: with the same type and shape as
    the maximum, XLA writes both in one pass over ``x`` (an int8 index costs
    a second pass, 1.1 ms a step more on the v5e; PERF.md §6, PR 26).
    ``>`` is strict, so a tie stays with the first group in window order
    (g = 2*di + dj)."""
    c = x.shape[-1] // 4
    best = x[..., :c]
    winner = jnp.zeros_like(best)
    for g in range(1, 4):
        part = x[..., g * c : (g + 1) * c]
        winner = jnp.where(part > best, jnp.asarray(g, x.dtype), winner)
        best = jnp.maximum(best, part)
    return best, winner


@jax.custom_vjp
def _pool(x: jax.Array) -> jax.Array:
    return _max_and_winner(x)[0]


def _pool_fwd(x):
    return _max_and_winner(x)


def _pool_bwd(winner, dy):
    # at the full 4C lanes, in one select: a lane takes dy where its group won
    group_of_lane = jnp.asarray(np.repeat(np.arange(4), dy.shape[-1]), winner.dtype)

    def four_times(a):  # not jnp.tile: its broadcast + reshape splits the lanes
        return jnp.concatenate([a] * 4, axis=-1)

    zero = jnp.zeros((), dy.dtype)
    return (jnp.where(four_times(winner) == group_of_lane, four_times(dy), zero),)


_pool.defvjp(_pool_fwd, _pool_bwd)


def group_max(x: jax.Array) -> jax.Array:
    """2×2 stride-2 maxpool of the underlying pixel image, evaluated on its
    s2d form: the pool window IS the s2d group. (B,h,w,4C) → (B,h,w,C) at
    what is now the next level's pixel resolution. The gradient goes, whole,
    to the window's first maximum (module docstring, "The pool")."""
    assert x.shape[-1] % 4 == 0
    with jax.named_scope("s2d_pool"):
        return _pool(x)


def conv_same(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """NHWC SAME conv used by the s2d path (stride 1)."""
    return jax.lax.conv_general_dilated(
        x,
        kernel,
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
