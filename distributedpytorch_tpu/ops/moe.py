"""A sparse expert layer that knows its share: the router over ALL the
experts, and the part of the result that the experts HELD HERE give.

``route`` scores every token against every expert (sigmoid scores in
float32, a per-expert selection bias added for the choice alone, the gate
taken from the unbiased scores, normalised over the chosen and scaled; or
``score="softmax"``: the choice by logit + bias, the gates a softmax over
the chosen logits). ``held_experts`` is told which experts this chip holds
(``first_held``, and as many as its weights have) and computes, for the
(token, slot) choices that fall on them, ``gate * W_down relu(W_up x)^2``
(or, for experts with a gate matrix, ``gate * W_down (act(W_gate x) *
W_up x)`` with ``act`` SiLU or ReLU: the same loop, one product more a
tile) added up per token.
What the other experts would add is left out: on one chip there is no
exchange, and no code stands in for the absent chips.

No token is dropped and there is no capacity limit. The chosen rows are
sorted by expert and cut into tiles of ``tile`` rows, each tile of one
expert (an expert's last tile is padded), and a loop whose trip count is
the number of tiles really needed multiplies them: the work follows the
routing, whatever the imbalance. A tile's rows are gathered from the
tokens, its results written side by side into a buffer sized for the
worst routing, and each token then gathers its choices' rows from there:
gathers throughout, never a scatter into the tokens. Such a loop cannot be differentiated by
jax, so the layer carries its own backward pass: the same tiles again, in
chunks of as many tiles as the layer has tokens (``chunk_tiles``). A
chunk's tile loop computes everything but the experts' weight gradients
(the forward again, the gradients of the tile's rows and gates) and
writes their operands side by side in the tiles' order; the weight
gradients of the chunk are then one grouped product over those sorted
rows a matrix, in which an expert's float32 slab is written once for all
its consecutive tiles, not once a tile. ``wgrad_path`` sends that product,
from what the code observes (platform and shapes), to the kernel of
``ops/moe_pallas.py`` (``grouped_wgrad``: the slab stays in VMEM over the
expert's tiles) or to the plain loop of ``_grouped_product`` (the CPU, toy
widths), which adds tile by tile into the slab and is the kernel's oracle.
Three counters say what the routing cost: rows routed to held experts,
rows the loop multiplied (padding included), rows of the fullest expert.

The selection bias is the routers' load balancing, with no loss term:
after every step ``balanced_bias`` raises the bias of each expert that
got fewer choices than the mean of ALL the experts (``expert_load``) and
lowers it for each that got more, by a fixed ``rate``. The router is held
whole on every chip, so a chip sees the load of the absent experts too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from distributedpytorch_tpu.ops import moe_pallas
from distributedpytorch_tpu.ops.precision import ROUTER_DTYPE, WGRAD_DTYPE

#: Counter names, in the order ``held_experts`` returns them.
COUNTERS = ("rows_routed", "rows_computed", "rows_max_expert")


#: How ``route`` turns logits into gates.
SCORES = ("sigmoid", "softmax")
#: What a gated expert's gate product goes through.
GATE_ACTIVATIONS = ("silu", "relu")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _softmax(x, name):
    """Softmax over the last axis whose backward pass reads its own
    result alone, under ``name`` (a ``checkpoint_name``) where one is
    given: a ``jax.checkpoint`` that keeps the name has nothing upstream
    of it to compute again."""
    return _softmax_fwd(x, name)[0]


def _softmax_fwd(x, name):
    y = jax.nn.softmax(x, axis=-1)
    y = checkpoint_name(y, name) if name else y
    return y, y


def _softmax_bwd(name, y, dy):
    return (y * (dy - jnp.sum(y * dy, axis=-1, keepdims=True)),)


_softmax.defvjp(_softmax_fwd, _softmax_bwd)


def route(h, router, bias, top_k: int, norm_topk: bool, scale: float,
          score: str = "sigmoid", names=None):
    """``(expert ids (T, k) int32, gates (T, k) float32)`` of tokens
    ``h`` (T, D). The ids are no function of anything differentiable; the
    gates carry the gradient to ``router`` and ``h``. ``score``:
    ``sigmoid`` (each expert's own score, normalised over the chosen with
    ``norm_topk``) or ``softmax`` (over the chosen logits: what a softmax
    over all the experts renormalised over the chosen gives, so
    ``norm_topk`` has to be set; ``names``, a pair of ``checkpoint_name``s
    for the ids and the gates, given where they are made, so that a
    ``jax.checkpoint`` that keeps both runs no part of the router again)."""
    if score not in SCORES:
        raise ValueError(f"unknown router score {score!r} (known: {SCORES})")
    logits = jnp.einsum("td,de->te", h.astype(ROUTER_DTYPE),
                        router.astype(ROUTER_DTYPE),
                        precision=lax.Precision.HIGHEST)
    if score == "softmax":
        if not norm_topk:
            raise ValueError("a softmax over the chosen logits is normalised "
                             "over the chosen: norm_topk has to be set")
        _, idx = lax.top_k(
            lax.stop_gradient(logits) + bias.astype(ROUTER_DTYPE), top_k)
        idx, gate_name = idx.astype(jnp.int32), ""
        if names is not None:
            idx, gate_name = checkpoint_name(idx, names[0]), names[1]
        gates = _softmax(jnp.take_along_axis(logits, idx, axis=-1), gate_name)
        return idx, gates * scale
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(lax.stop_gradient(scores) + bias.astype(ROUTER_DTYPE),
                       top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gates * scale


def expert_load(idx, experts_total: int):
    """How many of the (token, slot) choices ``idx`` (T, k) fell on each of
    the ``experts_total`` experts, float32. (A comparison and a sum: a
    bincount is a scatter of every choice.)"""
    hit = idx.reshape(-1)[:, None] == jnp.arange(experts_total, dtype=idx.dtype)
    return jnp.sum(hit, axis=0, dtype=jnp.int32).astype(ROUTER_DTYPE)


def balanced_bias(bias, load, rate: float):
    """The selection bias after one step's ``load``:
    ``bias + rate * sign(mean(load) - load)``."""
    return bias + rate * jnp.sign(jnp.mean(load) - load).astype(bias.dtype)


def tile_rows(tokens: int, top_k: int, experts_total: int) -> int:
    """Rows to a tile, from the shapes: half of what one expert expects
    (tokens x k / experts), as a power of two between 8 and 256."""
    expect = max(1, tokens * top_k // experts_total)
    return max(8, min(256, 1 << max(0, (expect // 2).bit_length() - 1)))


def _plan(idx, first_held: int, n_held: int, tile: int):
    """Sort the (token, slot) choices by held expert and cut each expert's
    rows into tiles. ``pos`` (T, k) is each choice's row among the tiles'
    rows (an expert's rows from its first tile on, in the order of the
    tokens), past the end for a choice that no held expert takes."""
    tokens, top_k = idx.shape
    local = idx.reshape(-1) - first_held
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # a comparison and a sum, not a bincount: that is a scatter of every
    # choice, which the chip does one row at a time
    counts = jnp.sum(key[:, None] == jnp.arange(n_held, dtype=key.dtype)[None, :],
                     axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    tiles = (counts + tile - 1) // tile
    tile_ends = jnp.cumsum(tiles)
    tile_starts = tile_ends - tiles
    rank = jnp.argsort(order).astype(jnp.int32)  # each choice's place when sorted
    expert = jnp.minimum(key, n_held - 1)
    pos = jnp.where(key < n_held,
                    tile_starts[expert] * tile + rank - starts[expert],
                    capacity(tokens, top_k, n_held, tile))
    return order, counts, starts, tile_starts, tile_ends, pos.reshape(tokens, top_k)


def capacity(tokens: int, top_k: int, n_held: int, tile: int) -> int:
    """Rows of the tiles' buffer: every choice that can fall on a held
    expert, and a padded last tile for each. Sized for the worst routing,
    so that no row is ever dropped; the loop touches only the tiles in use."""
    return tokens * min(top_k, n_held) + n_held * tile


def chunk_tiles(tokens: int, tile: int) -> int:
    """Tiles to a chunk of the backward pass: as many as the layer has
    tokens. The weight gradients' operands are buffered a chunk at a time
    (the tiles' whole buffer, sized for the worst routing, would be
    ``min(top_k, n_held)`` times as large); an expert that spans two
    chunks has its slab written twice."""
    return max(1, tokens // tile)


def wgrad_path(platform: str, d: int, f: int, tile: int) -> bool:
    """Whether the held experts' weight gradients of hidden size ``d``,
    expert width ``f`` and ``tile`` rows a tile take the kernel
    (``moe_pallas.grouped_wgrad``): on a TPU at shapes the kernel takes.
    Everywhere else (the CPU, toy widths) ``_grouped_product``'s plain
    loop."""
    return platform == "tpu" and moe_pallas.shapes_ok(d, f, tile)


def _tile(j, plan, gates_flat, top_k: int, tile: int, tokens: int):
    """Tile ``j``: its expert, its tokens (past the end for padding rows)
    and its gates (0 for padding rows)."""
    order, counts, starts, tile_starts, tile_ends, _ = plan
    e = jnp.searchsorted(tile_ends, j, side="right").astype(jnp.int32)
    within = (j - tile_starts[e]) * tile + jnp.arange(tile, dtype=jnp.int32)
    valid = within < counts[e]
    slots = order[jnp.where(valid, starts[e] + within, 0)]
    tok = jnp.where(valid, slots // top_k, tokens)
    gate = jnp.where(valid, gates_flat[slots], 0.0)
    return e, tok, gate


def _combine(buffer, pos):
    """``out[t] = sum over t's choices of buffer[pos[t, slot]]`` in
    float32: gathers, which the chip does at memory speed (a scatter-add
    into the tokens it does a row at a time)."""
    out = 0.0
    for s in range(pos.shape[1]):
        out = out + buffer.at[pos[:, s]].get(
            mode="fill", fill_value=0).astype(WGRAD_DTYPE)
    return out


def _act(h):
    r = jnp.maximum(h, 0.0)
    return r * r


def _expert_forward(xt, e, w_up, w_gate, act):
    """What expert ``e`` holds of tile ``xt`` before its down product, in
    float32: ``(activation, (up's result, gate's result or None))``:
    ``relu(W_up x)^2``, or with a gate matrix ``act(W_gate x) * W_up x``,
    ``act`` SiLU or ReLU."""
    h = jnp.dot(xt, w_up[e], preferred_element_type=WGRAD_DTYPE)
    if w_gate is None:
        return _act(h), (h, None)
    a = jnp.dot(xt, w_gate[e], preferred_element_type=WGRAD_DTYPE)
    if act == "relu":
        return jnp.maximum(a, 0.0) * h, (h, a)
    return jax.nn.silu(a) * h, (h, a)


def _expert_backward(da, pre, act):
    """The activation's gradient ``da`` taken back through it: ``(d up's
    result, d gate's result or None)`` in float32."""
    h, a = pre
    if a is None:
        return da * 2.0 * jnp.maximum(h, 0.0), None
    if act == "relu":
        return da * jnp.maximum(a, 0.0), jnp.where(a > 0.0, da * h, 0.0)
    s = jax.nn.sigmoid(a)
    return da * (a * s), da * h * (s * (1.0 + a * (1.0 - s)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _held(x, gates, idx, w_up, w_down, w_gate, first_held, tile, act):
    return _held_fwd(x, gates, idx, w_up, w_down, w_gate, first_held, tile,
                     act)[0]


def _held_fwd(x, gates, idx, w_up, w_down, w_gate, first_held, tile, act):
    tokens, top_k = idx.shape
    n_held = w_up.shape[0]
    plan = _plan(idx, first_held, n_held, tile)
    gates_flat = gates.reshape(-1)

    def body(j, rows):
        e, tok, gate = _tile(j, plan, gates_flat, top_k, tile, tokens)
        xt = x.at[tok].get(mode="fill", fill_value=0)
        a, _ = _expert_forward(xt, e, w_up, w_gate, act)
        y = jnp.dot(a.astype(x.dtype), w_down[e],
                    preferred_element_type=WGRAD_DTYPE)
        return lax.dynamic_update_slice(
            rows, (y * gate[:, None]).astype(x.dtype), (j * tile, 0))

    n_tiles = plan[4][-1]
    rows = lax.fori_loop(
        0, n_tiles, body,
        jnp.zeros((capacity(tokens, top_k, n_held, tile), x.shape[1]), x.dtype))
    counters = jnp.stack([jnp.sum(plan[1]), n_tiles * tile,
                          jnp.max(plan[1])]).astype(WGRAD_DTYPE)
    return ((_combine(rows, plan[5]).astype(x.dtype), counters),
            (x, gates, idx, w_up, w_down, w_gate))


def _grouped_product(lhs, rhs, acc, tile_expert, used, tile: int):
    """``acc[e] += lhs[rows of e].T @ rhs[rows of e]`` over the first
    ``used`` tiles of ``tile`` rows, tile ``t`` of expert
    ``tile_expert[t]``: the plain form of ``moe_pallas.grouped_wgrad``, a
    read and a write of the expert's slab every tile."""
    def body(t, acc):
        rows = lax.dynamic_slice_in_dim(lhs, t * tile, tile)
        other = lax.dynamic_slice_in_dim(rhs, t * tile, tile)
        return acc.at[tile_expert[t]].add(
            jnp.dot(rows.T, other, preferred_element_type=WGRAD_DTYPE))

    return lax.fori_loop(0, used, body, acc)


def _held_bwd(first_held, tile, act, saved, cts):
    x, gates, idx, w_up, w_down, w_gate = saved
    dy = cts[0]
    tokens, top_k = idx.shape
    n_held, d, f = w_up.shape
    plan = _plan(idx, first_held, n_held, tile)
    tile_starts, tile_ends = plan[3], plan[4]
    gates_flat = gates.reshape(-1)
    rows = capacity(tokens, top_k, n_held, tile)
    chunk = chunk_tiles(tokens, tile)
    n_tiles = tile_ends[-1]
    kernel = wgrad_path(jax.default_backend(), d, f, tile)

    # an operand as wide as the experts lies (f, rows) where the kernel
    # wants it so (moe_pallas.rows_last): the order XLA gives its tiles
    turned = kernel and moe_pallas.rows_last(f)

    def tile_body(j, carry):
        dx_rows, dgate_rows, hidden, wide = carry
        e, tok, gate = _tile(j, plan, gates_flat, top_k, tile, tokens)
        xt = x.at[tok].get(mode="fill", fill_value=0)
        dyt = dy.at[tok].get(mode="fill", fill_value=0)
        a, pre = _expert_forward(xt, e, w_up, w_gate, act)
        # d(gate * a W_down) : through a, through W_down, through the gate
        da = jnp.dot(dyt, w_down[e].T, preferred_element_type=WGRAD_DTYPE)
        dg = jnp.sum(a * da, axis=-1)
        dh, dgated = _expert_backward(da * gate[:, None], pre, act)
        dh = dh.astype(x.dtype)
        ag = (a * gate[:, None]).astype(x.dtype)
        dxt = jnp.dot(dh, w_up[e].T, preferred_element_type=WGRAD_DTYPE)
        if w_gate is not None:
            dgated = dgated.astype(x.dtype)
            dxt = dxt + jnp.dot(dgated, w_gate[e].T,
                                preferred_element_type=WGRAD_DTYPE)
        # what the weight gradients multiply, side by side in the tiles'
        # order, for ``wgrads``
        at = (j % chunk) * tile
        hidden = tuple(lax.dynamic_update_slice(buffer, rows_, (at, 0))
                       for buffer, rows_ in zip(hidden, (xt, dyt)))
        wide = tuple(
            lax.dynamic_update_slice(buffer, rows_.T, (0, at)) if turned
            else lax.dynamic_update_slice(buffer, rows_, (at, 0))
            for buffer, rows_ in zip(wide, (ag, dh, dgated)))
        dx_rows = lax.dynamic_update_slice(
            dx_rows, dxt.astype(x.dtype), (j * tile, 0))
        dgate_rows = lax.dynamic_update_slice(dgate_rows, dg, (j * tile,))
        return dx_rows, dgate_rows, hidden, wide

    def wgrads(first, used, hidden, wide, dws):
        """The chunk's part of ``dw_up = x.T dh``, ``dw_down = ag.T dy``
        and ``dw_gate = x.T dgated`` added into ``dws``."""
        (xs, dys), (ags, dhs, *dgateds) = hidden, wide
        tile_expert = jnp.minimum(
            jnp.searchsorted(tile_ends, first + jnp.arange(chunk), side="right"),
            n_held - 1).astype(jnp.int32)
        # (lhs, rhs, whether the hidden dimension is the result's first)
        if turned:  # the turned operand first: every slab (f, d)
            products = [(dhs, xs, False), (ags, dys, False)] + [
                (dgated, xs, False) for dgated in dgateds]
        else:
            products = [(xs, dhs, True), (ags, dys, False)] + [
                (xs, dgated, True) for dgated in dgateds]
        if not kernel:
            return tuple(
                _grouped_product(lhs, rhs, dw, tile_expert, used, tile)
                for (lhs, rhs, _), dw in zip(products, dws))
        # the first tile's expert has tiles in the chunk before this one
        schedule = moe_pallas.tile_schedule(
            tile_expert, used, tile_starts[tile_expert[0]] < first)
        return tuple(
            moe_pallas.grouped_wgrad(lhs, rhs, dw, schedule, tile, hidden_is_k,
                                     turned)
            for (lhs, rhs, hidden_is_k), dw in zip(products, dws))

    def chunk_body(c, carry):
        dx_rows, dgate_rows, hidden, wide, dws = carry
        first = c * chunk
        used = jnp.minimum(n_tiles - first, chunk)
        dx_rows, dgate_rows, hidden, wide = lax.fori_loop(
            first, first + used, tile_body, (dx_rows, dgate_rows, hidden, wide))
        return (dx_rows, dgate_rows, hidden, wide,
                wgrads(first, used, hidden, wide, dws))

    gated = w_gate is not None
    # tiles past a chunk's last in use are never read: zeros once will do
    hidden = (jnp.zeros((chunk * tile, d), x.dtype),) * 2
    wide = (jnp.zeros((f, chunk * tile) if turned else (chunk * tile, f),
                      x.dtype),) * (3 if gated else 2)
    dws = tuple(jnp.zeros(w_down.shape if turned else w.shape, WGRAD_DTYPE)
                for w in (w_up, w_down) + (w_gate,) * gated)
    dx_rows, dgate_rows, _, _, dws = lax.fori_loop(
        0, (n_tiles + chunk - 1) // chunk, chunk_body,
        (jnp.zeros((rows, d), x.dtype), jnp.zeros((rows,), WGRAD_DTYPE),
         hidden, wide, dws))
    dgate = dgate_rows.at[plan[5]].get(mode="fill", fill_value=0)
    dw_up, dw_down, *dw_gate = dws
    if turned:
        # (f, d) slabs of the (d, f) matrices: XLA holds such a matrix d
        # last as well (the dimension the lanes divide), so this moves nothing
        dw_up, dw_gate = dw_up.swapaxes(1, 2), [g.swapaxes(1, 2) for g in dw_gate]
    return (_combine(dx_rows, plan[5]).astype(x.dtype), dgate.astype(gates.dtype),
            None, dw_up.astype(w_up.dtype), dw_down.astype(w_down.dtype),
            dw_gate[0].astype(w_gate.dtype) if gated else None)


_held.defvjp(_held_fwd, _held_bwd)


def held_experts(x, idx, gates, w_up, w_down, experts_total: int,
                 first_held: int, w_gate=None, act: str = "silu"):
    """``(y (T, D), counters (3,))``: what the experts
    ``first_held .. first_held + n_held - 1`` of ``experts_total`` add for
    tokens ``x`` (T, D) routed by ``idx`` and ``gates`` (T, k). ``w_up``
    (n_held, D, F) and ``w_down`` (n_held, F, D) are the held experts'
    weights in the compute dtype; with ``w_gate`` (n_held, D, F) an expert
    is gated, ``W_down (act(W_gate x) * W_up x)`` with ``act`` ``silu`` or
    ``relu``, else ``W_down relu(W_up x)^2``. The counters are
    ``COUNTERS``."""
    if act not in GATE_ACTIVATIONS:
        raise ValueError(f"unknown gate activation {act!r} "
                         f"(known: {GATE_ACTIVATIONS})")
    if not 0 <= first_held <= experts_total - w_up.shape[0]:
        raise ValueError(
            f"experts {first_held}..{first_held + w_up.shape[0] - 1} are not "
            f"among {experts_total}")
    tile = tile_rows(x.shape[0], idx.shape[1], experts_total)
    y, counters = _held(x, gates, idx, w_up, w_down, w_gate, first_held, tile,
                        act)
    return y, lax.stop_gradient(counters)
