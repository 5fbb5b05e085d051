"""What ``ops/moe.py`` gained for a third expert shape: gates that are a
softmax over the chosen logits (``route(..., score="softmax")``) and
ReLU-gated experts (``held_experts(..., act="relu")``), forward and
backward against dense computations in float32, on the plain path and on
the weight-gradient kernel (interpreted); and that the paths that were
there trace what they traced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedpytorch_tpu.ops import moe, moe_pallas


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def dense_route(h, router, bias, top_k):
    """A softmax over ALL the logits, the top ``top_k`` by logit + bias, the
    chosen probabilities renormalised: the published router's own words."""
    logits = h @ router
    probs = jax.nn.softmax(logits, -1)
    order = jnp.argsort(-(logits + bias), axis=-1, stable=True)[:, :top_k]
    chosen = jnp.take_along_axis(probs, order, -1)
    return order, chosen / jnp.sum(chosen, -1, keepdims=True)


@pytest.mark.parametrize("bias_scale", [0.0, 0.7])
def test_softmax_gates_are_the_renormalised_softmax_over_all(bias_scale):
    t, d, e, k = 50, 16, 12, 4
    keys = jax.random.split(jax.random.key(1), 4)
    h = jax.random.normal(keys[0], (t, d))
    router = jax.random.normal(keys[1], (d, e))
    bias = bias_scale * jax.random.normal(keys[2], (e,))
    weight = jax.random.normal(keys[3], (t, k))
    idx, gates = moe.route(h, router, bias, k, True, 1.0, score="softmax")
    want_idx, want = dense_route(h, router, bias, k)
    assert idx.dtype == jnp.int32 and np.array_equal(idx, want_idx)
    assert float(jnp.max(jnp.abs(gates - want))) < 1e-6
    assert float(jnp.max(jnp.abs(jnp.sum(gates, -1) - 1.0))) < 1e-6
    if bias_scale:  # the bias moves the choice, never a gate
        plain, _ = moe.route(h, router, 0 * bias, k, True, 1.0, score="softmax")
        assert not np.array_equal(idx, plain)

    def mine(h, router, bias, names=None):
        return jnp.sum(moe.route(h, router, bias, k, True, 2.0, score="softmax",
                                 names=names)[1] * weight)

    g = jax.grad(mine, argnums=(0, 1, 2))(h, router, bias)
    g_named = jax.grad(mine, argnums=(0, 1, 2))(h, router, bias, ("a", "b"))
    g_want = jax.grad(lambda h, r, b: 2.0 * jnp.sum(dense_route(h, r, b, k)[1]
                                                    * weight),
                      argnums=(0, 1, 2))(h, router, bias)
    for a, named, b in zip(g, g_named, g_want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5
        assert np.array_equal(a, named)
    assert not np.asarray(g[2]).any()  # no gradient reaches the bias


def test_route_refuses_what_it_does_not_know():
    h, router, bias = jnp.ones((4, 8)), jnp.ones((8, 6)), jnp.zeros((6,))
    with pytest.raises(ValueError, match="unknown router score"):
        moe.route(h, router, bias, 2, True, 1.0, score="tanh")
    with pytest.raises(ValueError, match="norm_topk has to be set"):
        moe.route(h, router, bias, 2, False, 1.0, score="softmax")
    with pytest.raises(ValueError, match="unknown gate activation"):
        moe.held_experts(h, jnp.zeros((4, 2), jnp.int32), jnp.ones((4, 2)),
                         jnp.ones((2, 8, 4)), jnp.ones((2, 4, 8)), 6, 0,
                         w_gate=jnp.ones((2, 8, 4)), act="gelu")


def dense_reglu(x, idx, gates, w_up, w_down, w_gate, first_held):
    """Every held expert over every row, gate 0 where it was not chosen."""
    y = 0.0
    for e in range(w_up.shape[0]):
        gate = jnp.sum(jnp.where(idx == first_held + e, gates, 0.0), -1)
        act = jnp.maximum(x @ w_gate[e], 0.0) * (x @ w_up[e])
        y = y + gate[:, None] * (act @ w_down[e])
    return y


#: Experts in all; 4..6 are held.
EXPERTS = 8


def reglu_case(f):
    """96 tokens, tiles of 16 rows (the kernel's least): a routing that
    leaves held expert 6 empty, gives expert 5 every token (six tiles, over
    a chunk's end) and expert 4 a few; slot 2 falls on experts not held."""
    t, d, first = 96, 128, 4
    keys = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(keys[0], (t, d))
    idx = jnp.stack([jnp.full((t,), 5), jnp.where(jnp.arange(t) < 5, 4, 1),
                     jnp.arange(t) % 3], -1).astype(jnp.int32)
    assert moe.tile_rows(t, 3, EXPERTS) == 16
    gates = jax.random.uniform(keys[1], (t, 3))
    w_up = jax.random.normal(keys[2], (3, d, f)) * d ** -0.5
    w_down = jax.random.normal(keys[3], (3, f, d)) * f ** -0.5
    w_gate = jax.random.normal(keys[4], (3, d, f)) * d ** -0.5
    weight = jax.random.normal(keys[5], (t, d))
    return idx, weight, first, (x, gates, w_up, w_down, w_gate)


@pytest.mark.parametrize("kernel", [False, True])
def test_relu_gated_experts_are_the_dense_computation(monkeypatch, kernel):
    """The result, and the gradient to the tokens, the gates and all three
    matrices; on the plain loop and with the weight gradients on the
    grouped kernel, interpreted (it takes the activation's gradient as it
    is, whatever the activation)."""
    if kernel:
        monkeypatch.setattr(moe.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(moe_pallas, "pallas_interpret", lambda: True)
    idx, weight, first, args = reglu_case(128)
    assert moe.wgrad_path(jax.default_backend(), 128, 128, 16) == kernel

    def mine(x, gates, w_up, w_down, w_gate):
        y, counters = moe.held_experts(x, idx, gates, w_up, w_down, EXPERTS,
                                       first, w_gate=w_gate, act="relu")
        return jnp.sum(y * weight), (y, counters)

    def dense(x, gates, w_up, w_down, w_gate):
        y = dense_reglu(x, idx, gates, w_up, w_down, w_gate, first)
        return jnp.sum(y * weight), y

    argnums = tuple(range(5))
    (_, (y, counters)), g = jax.jit(jax.value_and_grad(
        mine, argnums=argnums, has_aux=True))(*args)
    (_, want), g_want = jax.jit(jax.value_and_grad(
        dense, argnums=argnums, has_aux=True))(*args)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4
    for a, b in zip(g, g_want):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-4 * max(
            1.0, float(jnp.max(jnp.abs(b))))
    # expert 6 is empty: its matrices get no gradient, and no tile
    assert all(float(jnp.max(jnp.abs(a[2]))) == 0.0 for a in g[2:])
    assert [float(c) for c in counters] == [101, 96 + 16, 96]
    # another function than the SiLU gate
    silu = moe.held_experts(*args[:1], idx, *args[1:4], EXPERTS, first,
                            w_gate=args[4])[0]
    assert float(jnp.max(jnp.abs(silu - y))) > 1e-2


def test_the_kernel_takes_the_new_cells_widths():
    """Hidden 2560, experts 768 wide, tiles of 256: whole slabs, no turn."""
    tile = moe.tile_rows(16384, 6, 64)
    assert tile == 256
    assert moe.wgrad_path("tpu", 2560, 768, tile)
    assert not moe.wgrad_path("cpu", 2560, 768, tile)
    assert moe_pallas.hidden_block(2560, 768) == 2560
    assert not moe_pallas.rows_last(768)
    assert moe.capacity(16384, 6, 16, tile) == 16384 * 6 + 16 * 256


@pytest.mark.parametrize("gated", [True, False])
def test_paths_that_were_there_trace_what_they_traced(gated):
    """The defaults are the sigmoid router and the SiLU gate (or relu² with
    no gate matrix): naming them changes no character of the lowered text;
    the new branches are other programs. (Against the parent's own text:
    PERF.md §6, PR 37.)"""
    idx, weight, first, (x, gates, w_up, w_down, w_gate) = reglu_case(32)
    router, bias = jnp.ones((128, EXPERTS)) * 0.01, jnp.zeros((EXPERTS,))

    def layer(**kw):
        def fn(x, router, w_up, w_down, w_gate):
            chosen, g = moe.route(x, router, bias, 3, True, 2.5,
                                  **{k: v for k, v in kw.items() if k == "score"})
            y, c = moe.held_experts(
                x, chosen, g, w_up, w_down, EXPERTS, first,
                w_gate=w_gate if gated else None,
                **{k: v for k, v in kw.items() if k == "act"})
            return jnp.sum(y * weight) + jnp.sum(c)
        return jax.jit(jax.grad(fn, argnums=(0, 1, 2, 3))).lower(
            x, router, w_up, w_down, w_gate).as_text()

    text = layer()
    assert text == layer(score="sigmoid", act="silu")
    assert text != layer(score="softmax")
    # an activation is a gated expert's: relu² has no gate matrix to put it on
    assert (text != layer(act="relu")) == gated
