"""Causal grouped-query attention as one fused (flash) Pallas TPU kernel,
forward and backward: the float32 scores of a (query tile, key tile) pair
live in VMEM and never reach HBM.

The head size is a multiple of the 128 lanes, or 64: half a row, a block
whose last dimension is the whole head (Mosaic takes it; the products then
fill half of the MXU's depth, which is the head's own size and no fault of
the tiling). With a ``window`` (sliding-window attention: query ``i``
sees key ``j`` iff ``0 <= i - j < window``) the walk starts at the first
key tile the window reaches instead of tile 0 (``key_tiles``): tiles
wholly before the window are skipped like those above the diagonal, and
the tiles the window's edge crosses are masked as the diagonal tile is.
The whole key and value sequence of one key-value head (S x D
in the compute dtype, 2 MB each at 8192 x 128) stays resident in VMEM while
the query heads that share it go by, one query tile to a grid step. A step
walks the key tiles up to its own diagonal with a loop whose trip count is
the query tile's index, so tiles above the diagonal are skipped, not
masked; only the diagonal tile pays for a mask. Forward: running maximum
and sum in float32, the values' product on probabilities rounded to the
compute dtype, one division at the end, one float32 log-sum-exp a query
row. Backward: one kernel of five products a tile pair, in transposed form
(scores as keys x queries, so that the row statistics are lane-dense row
vectors and four of the five products need no transpose); dK and dV are
float32 (S x D) blocks that stay in VMEM over the query heads of the group
and over the query tiles and leave once a key-value head; dQ is summed
over the key tiles in a float32 scratch.

Only q, k, v, the output, the log-sum-exp and the three gradients touch
HBM. ``ops/sequence.attention_path`` decides where this kernel runs;
``ops/sequence.causal_attention`` is the caller.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedpytorch_tpu.ops.precision import LOSS_DTYPE
from distributedpytorch_tpu.utils.backend import pallas_interpret

#: Rows of the log-sum-exp's and delta's blocks: a row vector is stored as
#: the first of one sublane tile.
_SUBLANES = 8
#: Lanes of a row of VMEM.
_LANES = 128
#: What the kernels may use of the chip's 128 MiB of VMEM, and the part of
#: it that one key-value head's resident blocks may take (the rest is the
#: tiles' scores and the query-sized blocks).
_VMEM_LIMIT = 100 * 1024 * 1024
_RESIDENT_LIMIT = 64 * 1024 * 1024
#: Bytes of VMEM one element of a key-value head costs the backward kernel
#: while the head is resident: k, v (counted as float32, the widest compute
#: dtype) and dk, dv in float32, each double-buffered by the pipeline.
_RESIDENT_BYTES_PER_ELEMENT = 2 * (2 * 4 + 2 * 4)
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)

#: ``checkpoint_name``s of what the backward kernel reads besides the
#: output's gradient: q, k and v as the kernels take them (in their
#: layout: a name on the caller's layout would keep a second copy) and
#: the forward kernel's two results. A ``jax.checkpoint`` around the
#: caller whose policy keeps all five finds the forward ``pallas_call``
#: dead in its recomputation, and whatever made q, k and v with it.
RESIDUALS = ("attention_q", "attention_k", "attention_v",
             "attention_out", "attention_lse")

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def residual_bytes(b: int, s: int, hq: int, hkv: int, d: int,
                   itemsize: int) -> int:
    """Bytes of ``RESIDUALS`` for ``b`` sequences of ``s`` positions,
    ``hq`` query and ``hkv`` key-value heads of size ``d``: q, k, v and the
    output in the compute dtype and one float32 log-sum-exp a query row,
    stored ``_SUBLANES`` rows deep."""
    return b * s * ((2 * hq + 2 * hkv) * d * itemsize
                    + hq * _SUBLANES * jnp.dtype(LOSS_DTYPE).itemsize)


def head_size_ok(d: int) -> bool:
    """Head sizes the kernels take: a multiple of the lanes, or half a
    row (64: a block as wide as the head)."""
    return d % _LANES == 0 or d == _LANES // 2


def fits_vmem(s: int, d: int) -> bool:
    """Whether one key-value head's k, v, dk and dv at sequence length
    ``s`` and head size ``d`` can stay resident while the kernels run
    (up to 16,384 positions at head 128, and at head 64: a row of VMEM is
    128 lanes wide, so half a row costs a whole one)."""
    lanes = -(-d // _LANES) * _LANES
    return s * lanes * _RESIDENT_BYTES_PER_ELEMENT <= _RESIDENT_LIMIT


def effective_window(window, s: int):
    """``window`` where it hides a pair of a sequence of ``s`` positions,
    else None: a window that reaches the whole sequence is full causal
    attention, and takes its program."""
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} positions sees no key")
    return None if window is None or window >= s else int(window)


def key_tiles(i, tile: int, window, maximum=max, minimum=min):
    """``(first, whole)`` of query tile ``i``'s walk over the key tiles:
    it multiplies the tiles ``first .. i``; ``first .. whole - 1`` are
    crossed by the window's edge and masked, ``whole .. i - 1`` lie wholly
    inside the masks, tile ``i`` is the diagonal's. Without a window the
    walk starts at tile 0 and only the diagonal tile is masked. The one
    rule for the kernels' loop bounds (``i`` a traced index, ``maximum``
    and ``minimum`` jax's) and for ``pairs_computed`` (plain integers)."""
    if window is None:
        return 0, 0
    first = maximum(i * tile - (window - 1), 0) // tile
    whole = minimum(maximum(i + 1 - window // tile, 0), i)
    return first, whole


def pairs_computed(s: int, tile: int, window=None) -> int:
    """(query, key) pairs of one head of one sequence of ``s`` positions
    that the kernels multiply: whole tiles, the masked part of the
    diagonal and edge tiles included."""
    return sum((i + 1 - key_tiles(i, tile, window)[0]) * tile * tile
               for i in range(s // tile))


def _rows(j, tile):
    return pl.ds(pl.multiple_of(j * tile, tile), tile)


def _below_diagonal(tile, keys_first: bool):
    """(tile, tile) mask of a diagonal tile: key position <= query position."""
    a = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    b = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    return a <= b if keys_first else b <= a


def _inside_window(tile, keys_first: bool, ahead, window: int):
    """(tile, tile) mask of a key tile ``ahead`` positions before the
    query tile (a multiple of ``tile``; traced): query position - key
    position < ``window``."""
    a = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    b = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    return ahead + (b - a if keys_first else a - b) < window


def _mask(i, j, tile, window, keys_first: bool, diagonal: bool, edge: bool):
    """The mask of key tile ``j`` against query tile ``i``, or None for a
    tile wholly inside: the diagonal's, the window's where its edge can
    cross the tile, both for a window shorter than a tile."""
    mask = _below_diagonal(tile, keys_first) if diagonal else None
    if edge:
        inside = _inside_window(tile, keys_first, (i - j) * tile, window)
        mask = inside if mask is None else mask & inside
    return mask


def _walk(i, tile, window, step):
    """Run ``step(j, diagonal, edge)`` over query tile ``i``'s key tiles.
    Without a window: tiles 0 .. i - 1, then the diagonal tile. With one
    the diagonal tile goes first, so that every
    row's running maximum is finite before an edge tile that hides all of
    its keys from some rows; then the whole tiles, then the edge's."""
    if window is None:
        lax.fori_loop(0, i, lambda j, _: step(j, False, False), None)
        step(i, True, False)
        return
    first, whole = key_tiles(i, tile, window, jnp.maximum, jnp.minimum)
    step(i, True, window < tile)
    lax.fori_loop(whole, i, lambda j, _: step(j, False, False), None)
    lax.fori_loop(first, whole, lambda j, _: step(j, False, True), None)


def _forward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                    *, tile, scale, window):
    i = pl.program_id(3)
    q = q_ref[...]
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(j, diagonal, edge):
        rows = _rows(j, tile)
        s = lax.dot_general(q, k_ref[rows, :], _NT,
                            preferred_element_type=LOSS_DTYPE) * scale
        mask = _mask(i, j, tile, window, False, diagonal, edge)
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[rows, :],
            preferred_element_type=LOSS_DTYPE)
        m_ref[...] = m_next

    _walk(i, tile, window, step)
    l = l_ref[...]
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
    lse = m_ref[...] + jnp.log(l)  # (tile, 1) -> a lane-dense row
    lse_ref[...] = jnp.broadcast_to(lse, (tile, _LANES)).T[:_SUBLANES]


def _backward_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, *, tile, scale, window):
    h, i = pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(h == 0, i == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    q, do = q_ref[...], do_ref[...]
    lse, delta = lse_ref[:1, :], delta_ref[:1, :]
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(j, diagonal, edge):
        rows = _rows(j, tile)
        k, v = k_ref[rows, :], v_ref[rows, :]
        # keys x queries: the statistics are row vectors over the lanes
        s = lax.dot_general(k, q, _NT, preferred_element_type=LOSS_DTYPE) * scale
        mask = _mask(i, j, tile, window, True, diagonal, edge)
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        p = jnp.exp(s - lse)
        dv_ref[rows, :] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=LOSS_DTYPE)
        dp = lax.dot_general(v, do, _NT, preferred_element_type=LOSS_DTYPE)
        ds = (p * (dp - delta)).astype(q.dtype)  # without the scores' scale
        dk_ref[rows, :] += jnp.dot(ds, q, preferred_element_type=LOSS_DTYPE)
        dq_acc[...] += lax.dot_general(ds, k, _TN,
                                       preferred_element_type=LOSS_DTYPE)

    _walk(i, tile, window, step)
    dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _specs(tile, s, d):
    """Block specs over the grid (B, Hkv, heads a group, query tiles)."""
    q = pl.BlockSpec((None, None, None, tile, d),
                     lambda b, g, h, i: (b, g, h, i, 0))
    kv = pl.BlockSpec((None, None, s, d), lambda b, g, h, i: (b, g, 0, 0))
    row = pl.BlockSpec((None, None, None, _SUBLANES, tile),
                       lambda b, g, h, i: (b, g, h, 0, i))
    return q, kv, row


def _forward(q, k, v, tile, interpret, window):
    b, hkv, rep, s, d = q.shape
    q_spec, kv_spec, row_spec = _specs(tile, s, d)
    return pl.pallas_call(
        functools.partial(_forward_kernel, tile=tile, scale=1.0 / math.sqrt(d),
                          window=window),
        grid=(b, hkv, rep, s // tile),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, hkv, rep, _SUBLANES, s), LOSS_DTYPE)],
        scratch_shapes=[pltpu.VMEM((tile, 1), LOSS_DTYPE),
                        pltpu.VMEM((tile, 1), LOSS_DTYPE),
                        pltpu.VMEM((tile, d), LOSS_DTYPE)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="causal_attention_fwd",
    )(q, k, v)


def _backward(q, k, v, out, lse, dout, tile, interpret, window):
    b, hkv, rep, s, d = q.shape
    q_spec, kv_spec, row_spec = _specs(tile, s, d)
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(dout.astype(LOSS_DTYPE) * out.astype(LOSS_DTYPE), axis=-1)
    delta = jnp.broadcast_to(delta[:, :, :, None, :], lse.shape)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_backward_kernel, tile=tile, scale=scale,
                          window=window),
        grid=(b, hkv, rep, s // tile),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, LOSS_DTYPE),
                   jax.ShapeDtypeStruct(v.shape, LOSS_DTYPE)],
        scratch_shapes=[pltpu.VMEM((tile, d), LOSS_DTYPE)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="causal_attention_bwd",
    )(q, k, v, dout, lse, delta)
    return dq, (dk * scale).astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, tile, interpret, window):
    return _forward(q, k, v, tile, interpret, window)[0]


def _attention_fwd(q, k, v, tile, interpret, window):
    q, k, v = map(checkpoint_name, (q, k, v), RESIDUALS[:3])
    out, lse = map(checkpoint_name, _forward(q, k, v, tile, interpret, window),
                   RESIDUALS[3:])
    return out, (q, k, v, out, lse)


def _attention_bwd(tile, interpret, window, saved, dout):
    return _backward(*saved, dout, tile, interpret, window)


_attention.defvjp(_attention_fwd, _attention_bwd)


def causal_attention(q, k, v, tile: int, window=None, interpret=None):
    """``q`` (B, S, Hq, D), ``k`` and ``v`` (B, S, Hkv, D) in the compute
    dtype, S a multiple of ``tile``: causal attention (B, S, Hq, D), scores
    scaled by 1 / sqrt(D). With ``window`` query ``i`` sees key ``j`` iff
    ``0 <= i - j < window`` (any positive size; one that reaches the whole
    sequence is no window). ``interpret=None`` follows
    ``utils/backend.pallas_interpret``."""
    if interpret is None:
        interpret = pallas_interpret()
    b, s, hq, d = q.shape
    window = effective_window(window, s)
    hkv = k.shape[2]
    q = q.reshape(b, s, hkv, hq // hkv, d).transpose(0, 2, 3, 1, 4)
    out = _attention(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                     tile, interpret, window)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, d)
