"""Compile the main path for the real chip, without the chip.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached (the `on-chip-measurement` guide, §2).
Every case here lowers at the real size against a described ``v5e:2x2``
with ``interpret=False`` forced FROM THE TEST — interpret-mode tests
cannot see what Mosaic refuses (the serve-mask kernel's i1→uint8 select
passed every one of them and could not start on the chip).

Rules this file keeps: the topology is described inside a module-scoped,
non-autouse fixture of THIS file (never at import, never in a
skipif/parametrize/conftest); JAX's persistent compile cache is off
around the compiles (such an entry is written but cannot be read back
without a chip); everything compiles in the test's own process; nothing
runs, so nothing here is a measurement.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributedpytorch_tpu.ops import kernels, pallas_kernels

B, H, W = 4, 640, 960  # the reference config: batch 4 at 640×960
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def mosaic(one_chip, no_persistent_cache, monkeypatch):
    """Force every ``interpret=None`` kernel of the package to Mosaic —
    from the test, not through an option of the program — and hand back
    ``sds(shape, dtype)`` placing an abstract array on the described
    chip."""
    for mod in (kernels, pallas_kernels):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return sds


def _compile(fn, *args, **jit_kwargs):
    return jax.jit(fn, **jit_kwargs).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# -- kernels, each at the widths the main path runs them -------------------


def test_serve_mask_compiles(mosaic):
    x = mosaic((B, H, W), jnp.float32)
    c = _compile(
        lambda v: kernels.sigmoid_threshold_mask(v, 0.5, interpret=False), x
    )
    assert _has_kernel(c)


def _bn_args(sds, c):
    # milesial level ℓ runs C = 64·2^ℓ at (H, W) / 2^ℓ
    level = {64: 0, 128: 1, 256: 2, 512: 3, 1024: 4}[c]
    x = sds((B, H >> level, W >> level, c), jnp.bfloat16)
    vec = sds((c,), jnp.float32)
    return x, vec, vec, vec, vec


@pytest.mark.parametrize("c", [64, 128, 256, 512, 1024])
def test_fused_bn_act_forward_compiles(mosaic, c):
    compiled = _compile(
        lambda *a: kernels.fused_bn_act(*a, interpret=False),
        *_bn_args(mosaic, c),
    )
    assert _has_kernel(compiled)


@pytest.mark.parametrize("c", [64, 128, 256, 512, 1024])
def test_fused_bn_act_grad_compiles(mosaic, c):
    def loss(*a):
        return jnp.sum(kernels.fused_bn_act(*a, interpret=False))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *_bn_args(mosaic, c)
    )
    assert _has_kernel(compiled)


def test_eval_stats_compiles(mosaic):
    x = mosaic((B, H, W, 1), jnp.float32)
    compiled = _compile(
        lambda p, t: pallas_kernels.eval_stats_pallas(p, t, interpret=False),
        x, x,
    )
    assert _has_kernel(compiled)


def test_fused_loss_value_and_grad_compiles(mosaic):
    from distributedpytorch_tpu.ops.fused_loss import fused_bce_dice_loss

    x = mosaic((B, H, W, 1), jnp.float32)
    compiled = _compile(jax.value_and_grad(fused_bce_dice_loss), x, x)
    assert _has_kernel(compiled)


@pytest.mark.parametrize("s,dtype,d,hkv", [
    (8192, jnp.bfloat16, 128, 2),   # the token cell's block: 2 x 8192, 32 / 2 heads
    (8192 + 512, jnp.bfloat16, 128, 2),  # a length only the 512 tile divides
    (16384, jnp.float32, 128, 2),   # the longest and widest attention_path admits
    (8192, jnp.bfloat16, 64, 8),    # the lfm2 cell's layer: 32 / 8 heads of 64
    (16384, jnp.float32, 64, 8),    # the longest at half the lanes
])
def test_causal_attention_value_and_grads_compile(mosaic, s, dtype, d, hkv):
    """Forward and backward kernels at the tile ``attention_path`` picks,
    with the keys, values and their gradients of one head resident in
    VMEM: Mosaic takes them, and no (S x S) tensor is left in HBM."""
    from distributedpytorch_tpu.ops import attention_pallas
    from distributedpytorch_tpu.ops import sequence as seq

    tile = seq.attention_path("tpu", s, d, 32, hkv)
    assert tile
    q, kv = mosaic((2, s, 32, d), dtype), mosaic((2, s, hkv, d), dtype)
    compiled = _compile(jax.grad(
        lambda q, k, v: jnp.sum(attention_pallas.causal_attention(
            q, k, v, tile, interpret=False).astype(jnp.float32)),
        argnums=(0, 1, 2)), q, kv, kv)
    assert compiled.as_text().count("tpu_custom_call") >= 2
    # q-sized tensors only (a head of 64 is stored 128 lanes wide): the
    # scores of every head pair would be 32 x s * s * 4 bytes a sequence
    assert compiled.memory_analysis().temp_size_in_bytes < (
        6 * q.size * 4 * max(d, 128) // d)


@pytest.mark.parametrize("window", [None, 4096, 1000])
def test_windowed_attention_compiles_at_the_16k_cells_shapes(mosaic, window):
    """One sequence of 16,384 positions, 28 / 4 heads of 128 in bf16 (the
    smallthinker cell's layers): the full layer, the published window of
    four tiles, and a window that no tile divides and that is shorter than
    one (both masks in the diagonal tile). Mosaic takes the walk whose
    first tile follows the query tile."""
    from distributedpytorch_tpu.ops import attention_pallas
    from distributedpytorch_tpu.ops import sequence as seq

    s, hq, hkv, d = 16384, 28, 4, 128
    tile = seq.attention_path("tpu", s, d, hq, hkv)
    assert tile == 1024
    q, kv = mosaic((1, s, hq, d), jnp.bfloat16), mosaic((1, s, hkv, d), jnp.bfloat16)
    compiled = _compile(jax.grad(
        lambda q, k, v: jnp.sum(attention_pallas.causal_attention(
            q, k, v, tile, window, interpret=False).astype(jnp.float32)),
        argnums=(0, 1, 2)), q, kv, kv)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "causal_attention_fwd" in text and "causal_attention_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * q.size * 4


@pytest.mark.parametrize("experts,d,f", [
    (16, 2048, 1536),   # the lfm2 cell's experts: both orders of the result
    (8, 2688, 1856),    # the first token cell's, 14.5 x 128 wide: turned
    (16, 2560, 768),    # the smallthinker cell's: 6 x 128 wide
])
def test_grouped_wgrad_compiles_at_both_cells_widths(mosaic, experts, d, f):
    """The held experts' weight-gradient kernel over one chunk of 64 tiles
    of 256 rows, an expert's whole float32 slab resident in VMEM: Mosaic
    takes every form ``ops/moe.py`` calls, in place (no second slab)."""
    from distributedpytorch_tpu.ops import moe, moe_pallas

    tile, tiles = 256, moe.chunk_tiles(2 * 8192, 256)
    assert moe.wgrad_path("tpu", d, f, tile)
    assert moe_pallas.hidden_block(d, f) == d
    turned = moe_pallas.rows_last(f)
    hidden = mosaic((tiles * tile, d), jnp.bfloat16)
    wide = mosaic((f, tiles * tile) if turned else (tiles * tile, f), jnp.bfloat16)
    scalars = (mosaic((tiles,), jnp.int32), mosaic((), jnp.int32),
               mosaic((), jnp.bool_))
    forms = [(wide, hidden, (experts, f, d), False)]
    if not turned:
        forms.append((hidden, wide, (experts, d, f), True))
    for lhs, rhs, slab, hidden_is_k in forms:
        compiled = _compile(
            lambda l, r, a, e, n, c: moe_pallas.grouped_wgrad(
                l, r, a, moe_pallas.tile_schedule(e, n, c), tile, hidden_is_k,
                turned, interpret=False),
            lhs, rhs, mosaic(slab, jnp.float32), *scalars, donate_argnums=(2,))
        assert compiled.as_text().count("tpu_custom_call") == 1
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= experts * d * f * 4
        assert memory.temp_size_in_bytes < 1 << 20


# -- the b4 640×960 bf16 s2d-2 train step and one serve bucket -------------


def _course_unet(sds):
    """(model, abstract params placed on the described chip)."""
    from distributedpytorch_tpu.models.unet import UNet

    # s2d depth 2 is what `-1` resolves to on a TPU backend; the described
    # chip is not the default backend here, so the test names it
    model = UNet(dtype=jnp.bfloat16, s2d_levels=2)
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, H, W, 3)))["params"],
        jax.random.key(0),
    )
    return model, jax.tree.map(lambda l: sds(l.shape, l.dtype), params)


def _abstract_train_step(sds, loss_impl=None):
    from distributedpytorch_tpu.ops.optim import adam_l2
    from distributedpytorch_tpu.train.steps import TrainState, make_train_step

    model, params = _course_unet(sds)
    tx = adam_l2(1e-4, 1e-8)
    opt_state = jax.eval_shape(tx.init, params)
    state = TrainState(
        params=params,
        opt_state=jax.tree.map(lambda l: sds(l.shape, l.dtype), opt_state),
        step=sds((), jnp.int32),
        model_state=None,
    )
    batch = {
        "image": sds((B, H, W, 3), jnp.float32),
        "mask": sds((B, H, W), jnp.int32),
    }
    step = make_train_step(model, tx, batch_size=B, loss_impl=loss_impl)
    return _compile(step, state, batch, donate_argnums=(0,))


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.temp_size_in_bytes < HBM_BYTES
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB does not fit one v5e"


def test_train_step_xla_policy_compiles_and_fits(mosaic):
    """The shipping default (``--kernels xla``): no Pallas kernel in the
    step, and it fits one chip's 16 GB with room (MODEL.md: ~6 GB)."""
    compiled = _abstract_train_step(mosaic)
    assert not _has_kernel(compiled)
    _fits_one_chip(compiled)


def test_train_step_pallas_policy_compiles_and_fits(mosaic):
    """``--kernels pallas`` on the course UNet: the fused loss kernel is
    in the step program."""
    from distributedpytorch_tpu.ops.fused_loss import fused_bce_dice_loss

    compiled = _abstract_train_step(mosaic, loss_impl=fused_bce_dice_loss)
    assert _has_kernel(compiled)
    _fits_one_chip(compiled)


@pytest.mark.parametrize("policy", ["xla", "pallas"])
def test_serve_bucket_forward_compiles(mosaic, policy):
    """One serve bucket (batch 4 at 640×960), as the engine lowers it:
    probabilities under ``xla``, the on-device ``uint8`` mask under
    ``pallas``."""
    from distributedpytorch_tpu.serve.infer import make_forward

    model, params = _course_unet(mosaic)
    variables = {"params": params}
    x = mosaic((B, H, W, 3), jnp.float32)
    fwd = make_forward(
        model, mask_threshold=0.5 if policy == "pallas" else None
    )
    compiled = _compile(fwd, variables, x)
    assert _has_kernel(compiled) == (policy == "pallas")
    out = jax.eval_shape(fwd, variables, x)
    assert out.shape == (B, H, W)
    assert out.dtype == (jnp.uint8 if policy == "pallas" else jnp.float32)
    _fits_one_chip(compiled)
