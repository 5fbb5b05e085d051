"""A sparse expert layer that knows its share: the router over ALL the
experts, and the part of the result that the experts HELD HERE give.

``route`` scores every token against every expert (sigmoid scores in
float32, a per-expert selection bias added for the choice alone, the gate
taken from the unbiased scores, normalised over the chosen and scaled).
``held_experts`` is told which experts this chip holds
(``first_held``, and as many as its weights have) and computes, for the
(token, slot) choices that fall on them, ``gate * W_down relu(W_up x)^2``
(or, for experts with a gate matrix, ``gate * W_down (silu(W_gate x) *
W_up x)``: the same loop, one product more a tile) added up per token.
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
jax, so the layer carries its own backward pass, the same loop again.
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

from distributedpytorch_tpu.ops.precision import ROUTER_DTYPE, WGRAD_DTYPE

#: Counter names, in the order ``held_experts`` returns them.
COUNTERS = ("rows_routed", "rows_computed", "rows_max_expert")


def route(h, router, bias, top_k: int, norm_topk: bool, scale: float):
    """``(expert ids (T, k) int32, gates (T, k) float32)`` of tokens
    ``h`` (T, D). The ids are no function of anything differentiable; the
    gates carry the gradient to ``router`` and ``h``."""
    logits = jnp.einsum("td,de->te", h.astype(ROUTER_DTYPE),
                        router.astype(ROUTER_DTYPE),
                        precision=lax.Precision.HIGHEST)
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


def _expert_forward(xt, e, w_up, w_gate):
    """What expert ``e`` holds of tile ``xt`` before its down product, in
    float32: ``(activation, (up's result, gate's result or None))``:
    ``relu(W_up x)^2``, or with a gate matrix ``silu(W_gate x) * W_up x``."""
    h = jnp.dot(xt, w_up[e], preferred_element_type=WGRAD_DTYPE)
    if w_gate is None:
        return _act(h), (h, None)
    a = jnp.dot(xt, w_gate[e], preferred_element_type=WGRAD_DTYPE)
    return jax.nn.silu(a) * h, (h, a)


def _expert_backward(da, pre):
    """The activation's gradient ``da`` taken back through it: ``(d up's
    result, d gate's result or None)`` in float32."""
    h, a = pre
    if a is None:
        return da * 2.0 * jnp.maximum(h, 0.0), None
    s = jax.nn.sigmoid(a)
    return da * (a * s), da * h * (s * (1.0 + a * (1.0 - s)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _held(x, gates, idx, w_up, w_down, w_gate, first_held, tile):
    return _held_fwd(x, gates, idx, w_up, w_down, w_gate, first_held, tile)[0]


def _held_fwd(x, gates, idx, w_up, w_down, w_gate, first_held, tile):
    tokens, top_k = idx.shape
    n_held = w_up.shape[0]
    plan = _plan(idx, first_held, n_held, tile)
    gates_flat = gates.reshape(-1)

    def body(j, rows):
        e, tok, gate = _tile(j, plan, gates_flat, top_k, tile, tokens)
        xt = x.at[tok].get(mode="fill", fill_value=0)
        act, _ = _expert_forward(xt, e, w_up, w_gate)
        y = jnp.dot(act.astype(x.dtype), w_down[e],
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


def _held_bwd(first_held, tile, saved, cts):
    x, gates, idx, w_up, w_down, w_gate = saved
    dy = cts[0]
    tokens, top_k = idx.shape
    n_held = w_up.shape[0]
    plan = _plan(idx, first_held, n_held, tile)
    gates_flat = gates.reshape(-1)
    rows = capacity(tokens, top_k, n_held, tile)

    def body(j, carry):
        dx_rows, dgate_rows, dw_up, dw_down, dw_gate = carry
        e, tok, gate = _tile(j, plan, gates_flat, top_k, tile, tokens)
        xt = x.at[tok].get(mode="fill", fill_value=0)
        dyt = dy.at[tok].get(mode="fill", fill_value=0)
        a, pre = _expert_forward(xt, e, w_up, w_gate)
        # d(gate * a W_down) : through a, through W_down, through the gate
        da = jnp.dot(dyt, w_down[e].T, preferred_element_type=WGRAD_DTYPE)
        dg = jnp.sum(a * da, axis=-1)
        dh, dgated = _expert_backward(da * gate[:, None], pre)
        dh = dh.astype(x.dtype)
        ag = (a * gate[:, None]).astype(x.dtype)
        dw_down = dw_down.at[e].add(
            jnp.dot(ag.T, dyt, preferred_element_type=WGRAD_DTYPE))
        dw_up = dw_up.at[e].add(
            jnp.dot(xt.T, dh, preferred_element_type=WGRAD_DTYPE))
        dxt = jnp.dot(dh, w_up[e].T, preferred_element_type=WGRAD_DTYPE)
        if w_gate is not None:
            dgated = dgated.astype(x.dtype)
            dw_gate = dw_gate.at[e].add(
                jnp.dot(xt.T, dgated, preferred_element_type=WGRAD_DTYPE))
            dxt = dxt + jnp.dot(dgated, w_gate[e].T,
                                preferred_element_type=WGRAD_DTYPE)
        dx_rows = lax.dynamic_update_slice(
            dx_rows, dxt.astype(x.dtype), (j * tile, 0))
        dgate_rows = lax.dynamic_update_slice(dgate_rows, dg, (j * tile,))
        return dx_rows, dgate_rows, dw_up, dw_down, dw_gate

    carry = (jnp.zeros((rows, x.shape[1]), x.dtype),
             jnp.zeros((rows,), WGRAD_DTYPE),
             jnp.zeros(w_up.shape, WGRAD_DTYPE),
             jnp.zeros(w_down.shape, WGRAD_DTYPE),
             None if w_gate is None else jnp.zeros(w_gate.shape, WGRAD_DTYPE))
    dx_rows, dgate_rows, dw_up, dw_down, dw_gate = lax.fori_loop(
        0, plan[4][-1], body, carry)
    dgate = dgate_rows.at[plan[5]].get(mode="fill", fill_value=0)
    return (_combine(dx_rows, plan[5]).astype(x.dtype), dgate.astype(gates.dtype),
            None, dw_up.astype(w_up.dtype), dw_down.astype(w_down.dtype),
            None if w_gate is None else dw_gate.astype(w_gate.dtype))


_held.defvjp(_held_fwd, _held_bwd)


def held_experts(x, idx, gates, w_up, w_down, experts_total: int,
                 first_held: int, w_gate=None):
    """``(y (T, D), counters (3,))``: what the experts
    ``first_held .. first_held + n_held - 1`` of ``experts_total`` add for
    tokens ``x`` (T, D) routed by ``idx`` and ``gates`` (T, k). ``w_up``
    (n_held, D, F) and ``w_down`` (n_held, F, D) are the held experts'
    weights in the compute dtype; with ``w_gate`` (n_held, D, F) an expert
    is gated, ``W_down (silu(W_gate x) * W_up x)``, else
    ``W_down relu(W_up x)^2``. The counters are ``COUNTERS``."""
    if not 0 <= first_held <= experts_total - w_up.shape[0]:
        raise ValueError(
            f"experts {first_held}..{first_held + w_up.shape[0] - 1} are not "
            f"among {experts_total}")
    tile = tile_rows(x.shape[0], idx.shape[1], experts_total)
    y, counters = _held(x, gates, idx, w_up, w_down, w_gate, first_held, tile)
    return y, lax.stop_gradient(counters)
