"""Sequence mixers and the token loss: RMSNorm (over the hidden size, or
over each head of q and k: the last axis), rotary positions, causal
grouped-query attention, Mamba-2's state-space dual (SSD) as a chunked
scan, LFM2's gated short convolution, and next-token cross-entropy in
token blocks (the head a matrix of its own, or the embedding's).

Attention runs on one of two paths, chosen by ``attention_path`` from what
the code observes (platform and shapes), never by a user: on a TPU, with a
head size that fills the lanes (or half of them: 64) and a length that a
tile divides, the fused kernel of ``ops/attention_pallas.py`` (forward and
its own backward; scores stay in VMEM); everywhere else (the CPU, toy
sizes, a ragged length) ``blocked_attention``, query blocks in plain XLA,
which is also the kernel's oracle. Everything else here is plain XLA differentiated by
jax: the chunked scan's backward is the chunked scan's transpose, the
blocked attention's scores are recomputed block by block
(``jax.checkpoint``), and so are the logits. Matrix products take
operands in the compute dtype and add up in float32; what rounding would
bend (norm statistics, softmax, the scan's decays and carried state, the
logits) is float32 under every policy, spelled by ``ops/precision``'s
names. Block sizes follow the shapes; there is no flag.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from distributedpytorch_tpu.ops import attention_pallas
from distributedpytorch_tpu.ops.precision import (
    LOSS_DTYPE,
    NORM_DTYPE,
    SCAN_DTYPE,
)

#: Query rows to a block of attention, tokens to a block of the loss: the
#: largest that keeps one block's float32 scores (heads x block x keys) or
#: logits (block x vocabulary) near half a gigabyte at the published sizes.
ATTENTION_BLOCK = 512
LOSS_BLOCK = 2048
#: Query and key rows to a tile of the fused attention kernel, the largest
#: that divides the length (on the chip at 8192: 39.9, 51.4, 97.1 ms a
#: block against plain XLA's 145.5, PERF.md §6).
ATTENTION_TILES = (1024, 512, 256)


def matmul(x, w, spec: str, name: str = ""):
    """``einsum(spec, x, w)`` with float32 accumulation, back in ``x``'s
    dtype: the one spelling of a projection. ``name`` is the result's
    ``checkpoint_name``, given after the cast (a policy on the product
    itself would keep its float32 sum, twice the bytes); an identity
    outside a ``jax.checkpoint`` whose policy knows the name."""
    out = jnp.einsum(spec, x, w.astype(x.dtype),
                     preferred_element_type=NORM_DTYPE).astype(x.dtype)
    return checkpoint_name(out, name) if name else out


def rms_norm(x, scale, eps: float):
    xf = x.astype(NORM_DTYPE)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(NORM_DTYPE)).astype(x.dtype)


def gated_group_rms_norm(y, gate, scale, groups: int, eps: float):
    """Mamba-2's output norm: ``RMSNorm(y * SiLU(gate))`` with the
    statistics taken inside each of ``groups`` slices of the channels."""
    yf = y.astype(NORM_DTYPE) * jax.nn.silu(gate.astype(NORM_DTYPE))
    g = yf.reshape(yf.shape[:-1] + (groups, yf.shape[-1] // groups))
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return (g.reshape(yf.shape) * scale.astype(NORM_DTYPE)).astype(y.dtype)


def rotary(x, theta: float):
    """Rotary positions over the whole head (``x``: (B, S, heads, D)),
    halves rotated against each other as the published model code does."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=NORM_DTYPE) / d))
    angle = jnp.arange(s, dtype=NORM_DTYPE)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    xf = x.astype(NORM_DTYPE)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    return (xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(x.dtype)


def _shifted(x, back: int):
    """``x`` (B, S, D) moved ``back`` positions later along S (earlier for
    a negative ``back``), zeros moved in."""
    if back == 0:
        return x
    s = x.shape[1]
    if back > 0:
        return jnp.pad(x, [(0, 0), (back, 0), (0, 0)])[:, :s]
    return jnp.pad(x, [(0, 0), (0, -back), (0, 0)])[:, -back:]


@jax.custom_vjp
def short_conv(bcx, kernel):
    """LFM2's gated short convolution, the part between its two
    projections: ``bcx`` (B, S, 3 x D) is ``B``, ``C`` and ``x`` side by
    side, ``kernel`` (taps, D) a causal depthwise filter (tap j sees the
    input taps - 1 - j positions back; no bias, no activation). Returns
    ``C * conv(B * x)`` (B, S, D): shifted products, no recurrence. The
    arithmetic is float32 between a read and a write in ``bcx``'s dtype,
    and the backward pass (its own: jax's would keep five float32 passes
    as wide as ``bcx`` for it) reads ``bcx`` and the gradient alone."""
    return _short_conv_fwd(bcx, kernel)[0]


def _short_conv_parts(bcx, kernel):
    # split, then widen: a float32 copy of all of ``bcx`` would be a
    # buffer of its own, twice its size
    b, c, x = (t.astype(SCAN_DTYPE) for t in jnp.split(bcx, 3, axis=-1))
    w = kernel.astype(SCAN_DTYPE)
    taps = w.shape[0]
    bx = b * x
    conv = sum(_shifted(bx, taps - 1 - j) * w[j] for j in range(taps))
    return b, c, x, bx, conv, w


def _short_conv_fwd(bcx, kernel):
    _, c, _, _, conv, _ = _short_conv_parts(bcx, kernel)
    return (c * conv).astype(bcx.dtype), (bcx, kernel)


def _short_conv_bwd(saved, dy):
    bcx, kernel = saved
    # behind a barrier: where a checkpoint keeps ``bcx``, the compiler
    # would else keep the forward pass's float32 parts beside it (the
    # same expressions) from there to here
    b, c, x, bx, conv, w = _short_conv_parts(
        lax.optimization_barrier(bcx), kernel)
    taps = w.shape[0]
    dconv = dy.astype(SCAN_DTYPE) * c
    # conv[t] = sum_j w[j] bx[t - (taps-1-j)], so bx[t] is read by the
    # outputs taps-1-j positions later
    dbx = sum(_shifted(dconv, -(taps - 1 - j)) * w[j] for j in range(taps))
    dkernel = jnp.stack([jnp.sum(_shifted(bx, taps - 1 - j) * dconv, axis=(0, 1))
                         for j in range(taps)])
    dbcx = jnp.concatenate([dbx * x, dy.astype(SCAN_DTYPE) * conv, dbx * b],
                           axis=-1)
    return dbcx.astype(bcx.dtype), dkernel.astype(kernel.dtype)


short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def attention_path(platform: str, s: int, d: int, hq: int, hkv: int) -> int:
    """The fused kernel's tile where attention of these shapes runs on it,
    0 where it runs as ``blocked_attention``: the kernel on a TPU when the
    head size is a multiple of the 128 lanes or half of them (64: a block
    as wide as the head), a tile divides the length, the query heads
    divide evenly over the key-value heads and one key-value head's
    sequence fits the kernel's share of VMEM."""
    if (platform != "tpu" or not attention_pallas.head_size_ok(d) or hq % hkv
            or not attention_pallas.fits_vmem(s, d)):
        return 0
    return next((t for t in ATTENTION_TILES if s % t == 0), 0)


def causal_attention(q, k, v, block: int = ATTENTION_BLOCK, window=None):
    """Causal grouped-query attention with scores scaled by 1 / sqrt(D):
    ``q`` (B, S, Hq, D), ``k`` and ``v`` (B, S, Hkv, D), Hq a multiple of
    Hkv. With ``window`` (sliding-window attention) query ``i`` sees key
    ``j`` iff ``0 <= i - j < window``. The fused kernel where
    ``attention_path`` says so, else query blocks of ``block`` rows in
    plain XLA."""
    tile = attention_path(jax.default_backend(), q.shape[1], q.shape[3],
                          q.shape[2], k.shape[2])
    if tile:
        return attention_pallas.causal_attention(q, k, v, tile, window)
    return blocked_attention(q, k, v, block, window)


def _query_blocks(s: int, block: int, window):
    """``(start, end, first)`` of ``blocked_attention``'s query blocks:
    queries ``start .. end - 1`` against keys ``first .. end - 1``, from
    the first position the block's first query sees."""
    for start in range(0, s, block):
        yield (start, min(s, start + block),
               0 if window is None else max(0, start - window + 1))


def attention_pairs(platform: str, s: int, d: int, hq: int, hkv: int,
                    window=None, block: int = ATTENTION_BLOCK) -> int:
    """(query, key) pairs of positions that ``causal_attention`` multiplies
    for one head of one sequence of these shapes on ``platform``, what its
    masks then throw away included: the kernel's whole tiles
    (``attention_pallas.pairs_computed``: its own loop bounds), or the
    blocked path's query blocks against the keys sliced for them."""
    window = attention_pallas.effective_window(window, s)
    tile = attention_path(platform, s, d, hq, hkv)
    if tile:
        return attention_pallas.pairs_computed(s, tile, window)
    return sum((end - start) * (end - first)
               for start, end, first in _query_blocks(s, block, window))


def blocked_attention(q, k, v, block: int = ATTENTION_BLOCK, window=None):
    """Causal grouped-query attention, one block of queries at a time
    against the keys up to that block's end (with ``window``: from the
    first key that the block's first query sees), so that no (S x S) score
    matrix exists: ``q`` (B, S, Hq, D), ``k`` and ``v`` (B, S, Hkv, D),
    Hq a multiple of Hkv. The query heads that share a key-value head are
    rows of one matrix product (Hq / Hkv x block rows against the keys).
    A block's scores and softmax are float32 and are recomputed in the
    backward pass. Keys and values are held in float32 outside the blocks
    so that their gradient, a sum over the blocks, adds up in float32."""
    b, s, hq, d = q.shape
    window = attention_pallas.effective_window(window, s)
    hkv = k.shape[2]
    rep = hq // hkv
    scale = 1.0 / math.sqrt(d)
    dtype = q.dtype
    # (B, Hkv, rep, S, D) queries; (B, Hkv, S, D) keys and values
    q = q.reshape(b, s, hkv, rep, d).transpose(0, 2, 3, 1, 4)
    k32 = k.astype(NORM_DTYPE).transpose(0, 2, 1, 3)
    v32 = v.astype(NORM_DTYPE).transpose(0, 2, 1, 3)

    @jax.checkpoint
    def one_block(qb, kb, vb, start):
        # ``start``: the block's first query, counted from its first key
        n, end = qb.shape[3], kb.shape[2]
        rows = qb.reshape(b, hkv, rep * n, d)
        scores = jnp.einsum("bgmd,bgkd->bgmk", rows, kb.astype(dtype),
                            preferred_element_type=LOSS_DTYPE) * scale
        qpos = start + jnp.arange(rep * n) % n
        seen = jnp.arange(end)[None, :] <= qpos[:, None]
        if window is not None:
            seen &= qpos[:, None] - jnp.arange(end)[None, :] < window
        scores = jnp.where(seen, scores, -jnp.inf)
        # the row maximum behind a barrier: left to itself the chip's
        # compiler turns "reduce, broadcast, subtract" into a reduce-window
        # as wide as the row, quadratic work (PERF.md §6)
        top = lax.optimization_barrier(
            lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True)))
        weights = jnp.exp(scores - top)
        probs = (weights / jnp.sum(weights, axis=-1, keepdims=True)).astype(dtype)
        out = jnp.einsum("bgmk,bgkd->bgmd", probs, vb.astype(dtype),
                         preferred_element_type=LOSS_DTYPE)
        return out.astype(dtype).reshape(b, hkv, rep, n, d)

    outs = []
    for start, end, first in _query_blocks(s, block, window):
        outs.append(one_block(q[:, :, :, start:end], k32[:, :, first:end],
                              v32[:, :, first:end], start - first))
    out = jnp.concatenate(outs, axis=3)  # (B, Hkv, rep, S, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, d)


def _segsum(x):
    """``out[..., i, j] = sum(x[..., j+1 : i+1])`` for i >= j, else -inf."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def ssd_scan(x, dt, a, b, c, chunk: int):
    """Mamba-2's selective state-space recurrence, chunk by chunk:

        h_t = exp(dt_t * a) h_{t-1} + dt_t * b_t x_t^T,   y_t = c_t h_t

    ``x`` (B, L, H, P), ``dt`` (B, L, H) after its softplus, ``a`` (H,)
    negative, ``b`` and ``c`` (B, L, G, N) with H a multiple of G. Inside
    a chunk the recurrence is a masked (chunk x chunk) product; between
    chunks the state (H, P, N) is carried, in float32. A length that is
    no multiple of ``chunk`` is padded with steps that neither decay nor
    write (dt = 0). Returns y (B, L, H, P) without the ``D x`` skip."""
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups
    pad = -length % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (length + pad) // chunk
    dtype = x.dtype
    dt = dt.astype(SCAN_DTYPE)
    xd = (x.astype(SCAN_DTYPE) * dt[..., None]).astype(dtype)
    xd = xd.reshape(bsz, nc, chunk, groups, per, p)
    b = b.reshape(bsz, nc, chunk, groups, n)
    c = c.reshape(bsz, nc, chunk, groups, n)
    # log-decay of every step, (B, G, per, nc, chunk), and its running sum
    da = (dt * a.astype(SCAN_DTYPE)).reshape(bsz, nc, chunk, groups, per)
    da = da.transpose(0, 3, 4, 1, 2)
    cum = jnp.cumsum(da, axis=-1)

    # inside each chunk: (c_l . b_s) decay(s -> l) for s <= l
    cb = jnp.einsum("bclgn,bcsgn->bcgls", c, b,
                    preferred_element_type=SCAN_DTYPE)
    decay = jnp.exp(_segsum(da)).transpose(0, 3, 1, 2, 4, 5)  # b c g h l s
    y = jnp.einsum("bcghls,bcsghp->bclghp",
                   (cb[:, :, :, None] * decay).astype(dtype), xd,
                   preferred_element_type=SCAN_DTYPE)

    # what each chunk leaves in the state, decayed to the chunk's end
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 3, 4, 1, 2)  # b c l g h
    states = jnp.einsum("bcsgn,bcsghp->bcghpn", b,
                        (xd * to_end[..., None].astype(dtype)),
                        preferred_element_type=SCAN_DTYPE)
    # carry the state over the chunks: entering chunk z is the sum of what
    # chunks before z left, each decayed by the chunks between
    total = jnp.pad(cum[..., -1], [(0, 0)] * 3 + [(1, 0)])  # b g h nc+1
    between = _carry_weights(total, nc)
    entering = jnp.einsum("bghzc,bcghpn->bzghpn", between, states,
                          precision=lax.Precision.HIGHEST)
    from_start = jnp.exp(cum).transpose(0, 3, 4, 1, 2)  # b c l g h
    y = y + jnp.einsum("bclgn,bcghpn->bclghp", c, entering.astype(dtype),
                       preferred_element_type=SCAN_DTYPE) * from_start[..., None]
    y = y.astype(dtype).reshape(bsz, nc * chunk, heads, p)
    return y[:, :length]


def _carry_weights(total, nc: int):
    """(B, G, per, nc, nc): the decay from the end of chunk ``c`` to the
    start of chunk ``z`` for c < z, else 0. ``total`` is each chunk's
    summed log-decay with a leading 0."""
    # seg[i, j] = sum(total[j+1 : i+1]); chunks c+1 .. z-1 lie between the
    # end of c and the start of z: total's entries c+2 .. z (shifted by the
    # leading 0), i.e. seg[z, c+1]
    return jnp.exp(_segsum(total)[..., :nc, 1:])


def next_token_loss(h, head, tokens, block: int = LOSS_BLOCK,
                    tied: bool = False):
    """Mean next-token cross-entropy: position t of every sequence
    predicts token t + 1, the last position predicts nothing. ``h``
    (B, S, D) after the final norm, ``head`` (D, V) as the optimiser holds
    it, or with ``tied`` the embedding's own (V, D) matrix, ``tokens``
    (B, S). The logits of one block of tokens exist at a time, in float32,
    and are recomputed in the backward pass."""
    bsz, s, d = h.shape
    targets = jnp.roll(tokens, -1, axis=1).reshape(-1)
    weight = jnp.broadcast_to(jnp.arange(s) < s - 1, (bsz, s)).reshape(-1)
    weight = weight.astype(LOSS_DTYPE)
    t = bsz * s
    block = min(block, t)
    pad = -t % block
    h = jnp.pad(h.reshape(t, d), [(0, pad), (0, 0)])
    targets, weight = jnp.pad(targets, (0, pad)), jnp.pad(weight, (0, pad))
    spec = "td,vd->tv" if tied else "td,dv->tv"

    @jax.checkpoint
    def one_block(total, xs):
        hb, tb, wb = xs
        logits = jnp.einsum(spec, hb, head.astype(hb.dtype),
                            preferred_element_type=LOSS_DTYPE)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return total + jnp.sum((lse - picked) * wb), None

    nb = (t + pad) // block
    total, _ = lax.scan(one_block, jnp.zeros((), LOSS_DTYPE),
                        (h.reshape(nb, block, d), targets.reshape(nb, block),
                         weight.reshape(nb, block)))
    return total / (bsz * (s - 1))
