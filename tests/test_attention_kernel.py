"""The fused attention kernel (ops/attention_pallas.py), interpreted on
the CPU: its output and three gradients against full attention in float32
and against the blocked XLA path, and where ``attention_path`` sends what.
"""

import math

import jax
import jax.numpy as jnp
import pytest

from distributedpytorch_tpu.obs import defs
from distributedpytorch_tpu.ops import attention_pallas
from distributedpytorch_tpu.ops import sequence as seq


def full_attention(q, k, v):
    """Softmax over the whole (S x S) scores, float32, nothing blocked."""
    s, rep = q.shape[1], q.shape[2] // k.shape[2]
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / math.sqrt(q.shape[-1])
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                      precision="highest")


def output_and_gradients(fn, q, k, v, weight):
    """[out, dq, dk, dv] of ``sum(fn(q, k, v) * weight)``, in float32."""
    def loss(q, k, v):
        out = fn(q, k, v).astype(jnp.float32)
        return jnp.sum(out * weight), out

    grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return [out] + [g.astype(jnp.float32) for g in grads]


def gap(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# (S, query heads a key-value head, D, tile): every group holds more than
# one query head, so dK and dV are sums over heads as well as query tiles
SHAPES = [(256, 2, 128, 128), (512, 4, 128, 256), (384, 3, 128, 128),
          (256, 16, 128, 256), (256, 2, 256, 128),
          # head size 64, half the lanes (the lfm2 model's: 4 query heads a
          # key-value head): a block as wide as the head
          (256, 4, 64, 128), (512, 2, 64, 256), (384, 4, 64, 128)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s,rep,d,tile", SHAPES)
def test_kernel_is_full_attention_and_the_blocked_path(s, rep, d, tile, dtype):
    dtype = jnp.dtype(dtype)
    hkv = 2
    keys = jax.random.split(jax.random.key(s + rep + d), 4)
    q = jax.random.normal(keys[0], (1, s, hkv * rep, d)).astype(dtype)
    k = jax.random.normal(keys[1], (1, s, hkv, d)).astype(dtype)
    v = jax.random.normal(keys[2], (1, s, hkv, d)).astype(dtype)
    weight = jax.random.normal(keys[3], q.shape)

    def kernel(q, k, v):
        return attention_pallas.causal_attention(q, k, v, tile, interpret=True)

    def blocked(q, k, v):
        return seq.blocked_attention(q, k, v, block=tile // 2)

    whole, fused, xla = jax.jit(lambda *a: [
        output_and_gradients(fn, *a) for fn in (full_attention, kernel, blocked)
    ])(q, k, v, weight)
    for name, exact, mine, theirs in zip(("out", "dq", "dk", "dv"),
                                         whole, fused, xla):
        if dtype == jnp.float32:
            size = float(jnp.max(jnp.abs(exact)))
            assert gap(mine, exact) < 1e-4 * max(1.0, size), name
            assert gap(mine, theirs) < 1e-4 * max(1.0, size), name
        else:
            # no further from the float32 answer than twice the XLA path
            assert gap(mine, exact) <= 2 * gap(theirs, exact), name


@pytest.mark.parametrize("platform,s,d,hq,hkv,tile", [
    ("tpu", 8192, 128, 32, 2, 1024),                # the published block
    ("tpu", 8192 + 512, 128, 32, 2, 512),           # a smaller tile divides it
    ("tpu", 8192 + 72, 128, 32, 2, 0),              # a ragged length
    ("tpu", 72, 16, 4, 2, 0),                       # the rehearsal's toy head
    ("tpu", 8192, 64, 32, 8, 1024),                 # half the lanes: the kernel
    ("tpu", 16384 + 256, 64, 32, 8, 0),             # ... whose rows cost whole ones
    ("tpu", 8192, 32, 32, 8, 0),                    # a quarter of the lanes
    ("tpu", 8192, 96, 32, 8, 0),                    # no whole or half row
    ("tpu", 65536, 128, 32, 2, 0),                  # a head VMEM cannot hold
    ("cpu", 8192, 128, 32, 2, 0),
])
def test_attention_path_follows_platform_and_shapes(platform, s, d, hq, hkv, tile):
    assert seq.attention_path(platform, s, d, hq, hkv) == tile


@pytest.mark.parametrize("platform,calls", [("cpu", 0), ("tpu", 2)])
def test_causal_attention_takes_the_path_it_is_told(monkeypatch, platform, calls):
    """On the CPU no kernel; where the backend says TPU (here: said to),
    forward and backward are one named ``pallas_call`` each."""
    monkeypatch.setattr(seq.jax, "default_backend", lambda: platform)
    q = jnp.ones((1, 256, 4, 128), jnp.bfloat16)
    kv = jnp.ones((1, 256, 2, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(seq.causal_attention(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, kv, kv))
    assert text.count("pallas_call") == calls
    for name in ("causal_attention_fwd", "causal_attention_bwd"):
        assert (name in text) == bool(calls)


#: An attention block at the kernel's smallest shapes, and a device's
#: memory beside which it fits.
KERNEL_BLOCK = dict(hybrid_override_pattern="*", hidden_size=64, vocab_size=96,
                    num_attention_heads=4, num_key_value_heads=2, head_dim=128)


@pytest.mark.parametrize("memory,forwards,kept", [
    (16 << 30, 1, True),   # the five residuals kept: the forward runs once
    (None, 2, False),      # the block's input alone: it runs again
])
def test_kept_residuals_leave_one_forward_kernel(monkeypatch, memory, forwards,
                                                 kept):
    """The gradient of an attention block whose ``jax.checkpoint`` keeps
    the kernel's residuals holds one forward ``pallas_call`` and one
    backward, and q's product once less; two forwards where it keeps the
    block's input alone. The gauge's value is the residuals' bytes."""
    from distributedpytorch_tpu.models.twotower import TwoTower, twotower_config

    monkeypatch.setattr(seq.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention_pallas, "pallas_interpret", lambda: True)
    model = TwoTower(twotower_config(KERNEL_BLOCK), jnp.float32,
                     memory_bytes=memory)
    params = model.init(jax.random.key(0))
    tokens = jnp.zeros((1, 256), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: model.loss(p, tokens)[0]))(params))
    assert text.count("name=causal_attention_fwd") == forwards
    assert text.count("name=causal_attention_bwd") == 1
    assert text.count("pallas_call") == forwards + 1
    # q's, k's and v's products: forward, and again unless their results
    # are kept (dx of the o product has q's shape too: the same in both)
    assert text.count(":f32[1,256,512] = dot_general[") == forwards + 1
    assert text.count(":f32[1,256,256] = dot_general[") == 2 * forwards
    for name in attention_pallas.RESIDUALS:
        assert (f"name={name}]" in text) is True, name
    # q and the output (1, 256, 4, 128), k and v (1, 256, 2, 128), float32
    # here; the log-sum-exp 8 sublanes deep
    named = 4 * 256 * 128 * (2 * 4 + 2 * 2) + 4 * 4 * 8 * 256
    assert model.named_activation_bytes(1, 256, "tpu") == (named,)
    assert model.named_activation_bytes(1, 256, "cpu") == (0,)
    assert model.kept_activation_bytes(1, 256, "tpu") == (named if kept else 0,)
    assert defs.KEPT_ACTIVATION_BYTES.name == "dpt_kept_activation_bytes"


@pytest.mark.parametrize("d,hq,hkv", [(128, 32, 2), (64, 32, 8)])
def test_residual_bytes_and_vmem_follow_the_head_size(d, hq, hkv):
    """The kept residuals count the head's own width; VMEM counts a row
    of 128 lanes for a head of 64."""
    item = 2
    assert attention_pallas.residual_bytes(2, 8192, hq, hkv, d, item) == (
        2 * 8192 * ((2 * hq + 2 * hkv) * d * item + hq * 8 * 4))
    assert attention_pallas.fits_vmem(16384, d)
    assert not attention_pallas.fits_vmem(16384 + 256, d)


def test_gauge_counts_the_blocks_that_take_the_kernel():
    from distributedpytorch_tpu.models.lfm2 import Lfm2
    from distributedpytorch_tpu.models.twotower import TwoTower, twotower_config

    lfm2 = Lfm2(dtype=jnp.bfloat16)
    assert lfm2.attention_kernel_blocks("tpu", 8192) == 1
    assert lfm2.attention_kernel_blocks("cpu", 8192) == 0

    model = TwoTower(twotower_config(None), dtype=jnp.bfloat16)
    assert model.attention_kernel_blocks("cpu", 8192) == 0
    assert model.attention_kernel_blocks("tpu", 8192) == 1
    assert model.attention_kernel_blocks("tpu", 8192 + 72) == 0
    assert defs.ATTENTION_KERNEL_BLOCKS.name == "dpt_attention_kernel_blocks"


# -- a sliding window: query i sees key j iff 0 <= i - j < window -----------

def windowed_attention(window):
    """Softmax over the whole masked (S x S) scores, float32."""
    def fn(q, k, v):
        s, rep = q.shape[1], q.shape[2] // k.shape[2]
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            precision="highest") / math.sqrt(q.shape[-1])
        ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        scores = jnp.where((ahead >= 0) & (ahead < window), scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                          precision="highest")
    return fn


def _qkvw(s, rep, d, hkv=2, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.key(seed + s + rep + d), 4)
    q = jax.random.normal(keys[0], (1, s, hkv * rep, d)).astype(dtype)
    k = jax.random.normal(keys[1], (1, s, hkv, d)).astype(dtype)
    v = jax.random.normal(keys[2], (1, s, hkv, d)).astype(dtype)
    return q, k, v, jax.random.normal(keys[3], q.shape)


#: Windows against a tile of 128 over 512 positions: below a tile, a tile,
#: above it and no multiple, two tiles, three and a half, one key alone,
#: all but the first key.
WINDOWS = [40, 128, 200, 256, 448, 1, 511]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_kernel_and_blocked_path_are_the_masked_softmax(window, dtype):
    dtype = jnp.dtype(dtype)
    s, tile = 512, 128
    q, k, v, weight = _qkvw(s, 2, 128, dtype=dtype, seed=window)

    def kernel(q, k, v):
        return attention_pallas.causal_attention(q, k, v, tile, window,
                                                 interpret=True)

    def blocked(q, k, v):
        return seq.blocked_attention(q, k, v, block=96, window=window)

    whole, fused, xla = jax.jit(lambda *a: [
        output_and_gradients(fn, *a)
        for fn in (windowed_attention(window), kernel, blocked)
    ])(q, k, v, weight)
    for name, exact, mine, theirs in zip(("out", "dq", "dk", "dv"),
                                         whole, fused, xla):
        assert bool(jnp.all(jnp.isfinite(mine))), name
        size = max(1.0, float(jnp.max(jnp.abs(exact))))
        if dtype == jnp.float32:
            assert gap(mine, exact) < 1e-4 * size, name
            assert gap(theirs, exact) < 1e-4 * size, name
        else:
            assert gap(mine, exact) <= max(2 * gap(theirs, exact), 0.02 * size), name


@pytest.mark.parametrize("window", [512, 513, 10 ** 6])
def test_a_window_that_reaches_the_whole_sequence_is_no_window(window):
    """Bit for bit, on both paths: the same program is traced."""
    q, k, v, weight = _qkvw(512, 2, 128, dtype=jnp.bfloat16)

    def both(window):
        return jax.jit(lambda *a: [output_and_gradients(fn, *a) for fn in (
            lambda q, k, v: attention_pallas.causal_attention(
                q, k, v, 128, window, interpret=True),
            lambda q, k, v: seq.blocked_attention(q, k, v, 96, window))])
    with_window, without = both(window), both(None)
    assert (with_window.lower(q, k, v, weight).as_text()
            == without.lower(q, k, v, weight).as_text())
    for a, b in zip(jax.tree.leaves(with_window(q, k, v, weight)),
                    jax.tree.leaves(without(q, k, v, weight))):
        assert bool(jnp.all(a == b))


def test_a_window_of_no_position_is_refused():
    q, k, v, _ = _qkvw(256, 2, 128)
    with pytest.raises(ValueError, match="sees no key"):
        seq.blocked_attention(q, k, v, window=0)


@pytest.mark.parametrize("s,tile,window,tiles", [
    (16384, 1024, None, 136),   # 16 query tiles to the diagonal
    (16384, 1024, 4096, 70),    # 1 + 2 + 3 + 4, then five a query tile
    (16384, 1024, 4097, 70),    # the tile's first query sees its first key
    (16384, 1024, 4098, 81),    # one key more reaches a sixth tile
    (16384, 1024, 1024, 31),    # the diagonal tile and the one before
    (16384, 1024, 1000, 31),
    (512, 128, 40, 7),
    (512, 128, 1, 4),           # the diagonal tiles alone
])
def test_walk_of_the_key_tiles_starts_where_the_window_does(s, tile, window, tiles):
    assert attention_pallas.pairs_computed(s, tile, window) == tiles * tile * tile
    for i in range(s // tile):
        first, whole = attention_pallas.key_tiles(i, tile, window)
        assert 0 <= first <= whole <= i
        # jax's operators give the kernel the same bounds
        traced = attention_pallas.key_tiles(jnp.int32(i), tile, window,
                                            jnp.maximum, jnp.minimum)
        assert (int(traced[0]), int(traced[1])) == (first, whole)
        if window is None:
            continue
        q0, q1 = i * tile, i * tile + tile - 1
        # the first tile walked holds the first key the tile's first query sees
        assert first == max(0, q0 - window + 1) // tile
        # a whole tile hides nothing: its first key is seen by the last query
        for j in range(whole, i):
            assert q1 - j * tile < window
        for j in range(first, whole):
            assert q1 - j * tile >= window


def test_pairs_counted_are_the_paths_own():
    """The kernel's whole tiles on a TPU at its shapes; elsewhere the
    blocked path's query blocks against the keys sliced for them; never
    fewer than the pairs inside the masks."""
    s, w = 16384, 4096
    inside = w * (w + 1) // 2 + (s - w) * w
    assert inside == 58_722_304 and s * (s + 1) // 2 == 134_225_920
    assert seq.attention_pairs("tpu", s, 128, 28, 4) == 136 * 1024 ** 2
    assert seq.attention_pairs("tpu", s, 128, 28, 4, window=w) == 70 * 1024 ** 2
    assert seq.attention_pairs("tpu", s, 128, 28, 4, window=s) == 136 * 1024 ** 2
    blocked = seq.attention_pairs("cpu", s, 128, 28, 4, window=w)
    # blocks of 512 against at most 4095 + 512 keys
    assert inside < blocked == sum(
        512 * (start + 512 - max(0, start - w + 1)) for start in range(0, s, 512))
    assert seq.attention_pairs("cpu", 72, 16, 4, 2, window=20, block=32) == (
        32 * 32 + 32 * (64 - 13) + 8 * (72 - 45))
