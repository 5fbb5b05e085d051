"""The held experts' weight gradients as one grouped product over the
sorted rows (ops/moe.py, ops/moe_pallas.py): the kernel, interpreted on the
CPU, and the plain loop against the dense sum per expert in float32, under
routings that leave an expert empty, end one on a tile, carry one over a
chunk and put every row on one; where ``wgrad_path`` sends what; the gauge.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedpytorch_tpu.obs import defs
from distributedpytorch_tpu.ops import moe, moe_pallas


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def on_the_kernel(monkeypatch):
    """``held_experts`` as on a TPU: ``wgrad_path`` is told so, and the
    kernel runs in the interpreter."""
    monkeypatch.setattr(moe.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe_pallas, "pallas_interpret", lambda: True)


# --- the kernel alone ---------------------------------------------------------

def dense_sum(lhs, rhs, acc, tile_expert, used, tile, continues):
    """What ``grouped_wgrad`` owes: an expert met by the first ``used``
    tiles holds the sum of its tiles' products (on top of ``acc`` only
    where ``continues`` says its run began in an earlier chunk); every
    other expert keeps ``acc``."""
    want, met = np.array(acc, np.float64), set()
    for t in range(used):
        e = int(tile_expert[t])
        if e not in met:
            met.add(e)
            if not (t == 0 and continues):
                want[e] = 0
        rows = slice(t * tile, (t + 1) * tile)
        want[e] += np.asarray(lhs, np.float64)[rows].T @ np.asarray(rhs, np.float64)[rows]
    return want


# (experts, hidden, width, tile, each tile's expert, tiles in use, continues)
SCHEDULES = {
    "an_expert_without_a_tile": (4, 128, 24, 16, [0, 0, 1, 3, 3, 3], 6, False),
    "tiles_past_the_last_in_use": (4, 128, 24, 16, [0, 0, 1, 3, 3, 3], 4, False),
    "the_first_expert_goes_on": (4, 128, 24, 16, [1, 1, 1, 2, 3, 3], 5, True),
    "no_tile_in_use": (4, 128, 24, 16, [1, 1, 1, 2, 3, 3], 0, False),
    "every_tile_of_one_expert": (3, 128, 128, 16, [2, 2, 2, 2], 4, True),
    "lane_wide_experts": (4, 256, 128, 32, [0, 1, 1, 2], 4, False),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", ["hidden_first", "hidden_last", "turned"])
@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_kernel_is_the_dense_sum_per_expert(name, form, dtype):
    """Both orders of the result (``(d, f)`` as ``dw_up``, ``(f, d)`` as
    ``dw_down``) and the turned operand of a width the lanes do not
    divide; an expert no tile meets keeps what the aliased input held."""
    experts, d, f, tile, tile_expert, used, continues = SCHEDULES[name]
    hidden_is_k, turned = form == "hidden_first", form == "turned"
    k, n = (d, f) if hidden_is_k else (f, d)
    rows = tile * len(tile_expert)
    keys = jax.random.split(jax.random.key(len(name)), 3)
    lhs = jax.random.normal(keys[0], (rows, k)).astype(dtype)
    rhs = jax.random.normal(keys[1], (rows, n)).astype(dtype)
    acc = jax.random.normal(keys[2], (experts, k, n))
    schedule = moe_pallas.tile_schedule(
        jnp.asarray(tile_expert, jnp.int32), jnp.int32(used), jnp.bool_(continues))
    got = jax.jit(lambda l, r, a, s: moe_pallas.grouped_wgrad(
        l, r, a, s, tile, hidden_is_k, turned, interpret=True))(
            lhs.T if turned else lhs, rhs, acc, schedule)
    want = dense_sum(lhs.astype(jnp.float32), rhs.astype(jnp.float32), acc,
                     tile_expert, used, tile, continues)
    assert got.dtype == jnp.float32
    assert float(np.max(np.abs(np.asarray(got) - want))) < 1e-3


@pytest.mark.parametrize("hidden_is_k", [True, False])
def test_kernel_cuts_a_hidden_size_that_vmem_cannot_hold_whole(monkeypatch,
                                                               hidden_is_k):
    """With room for one 128-column block of the result, the grid walks
    the hidden dimension's blocks and every one starts its experts anew."""
    monkeypatch.setattr(moe_pallas, "_RESIDENT_LIMIT", 3 * 4 * 128 * 128)
    assert moe_pallas.hidden_block(384, 24) == 128
    experts, d, f, tile, tile_expert = 3, 384, 24, 16, [0, 0, 2, 2, 2]
    k, n = (d, f) if hidden_is_k else (f, d)
    keys = jax.random.split(jax.random.key(1), 3)
    lhs = jax.random.normal(keys[0], (tile * 5, k))
    rhs = jax.random.normal(keys[1], (tile * 5, n))
    acc = jax.random.normal(keys[2], (experts, k, n))
    schedule = moe_pallas.tile_schedule(
        jnp.asarray(tile_expert, jnp.int32), jnp.int32(5), jnp.bool_(True))
    got = moe_pallas.grouped_wgrad(lhs, rhs, acc, schedule, tile, hidden_is_k,
                                   interpret=True)
    want = dense_sum(lhs, rhs, acc, tile_expert, 5, tile, True)
    assert float(np.max(np.abs(np.asarray(got) - want))) < 1e-3


@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_plain_loop_adds_the_same_products(name):
    """``_grouped_product``, the path of the CPU, adds each tile in use
    into its expert's slab."""
    experts, d, f, tile, tile_expert, used, _ = SCHEDULES[name]
    rows = tile * len(tile_expert)
    keys = jax.random.split(jax.random.key(2), 3)
    lhs = jax.random.normal(keys[0], (rows, d))
    rhs = jax.random.normal(keys[1], (rows, f))
    acc = jax.random.normal(keys[2], (experts, d, f))
    got = moe._grouped_product(lhs, rhs, acc, jnp.asarray(tile_expert, jnp.int32),
                               used, tile)
    # adds always: as if every expert's run had begun before
    want = np.array(acc, np.float64)
    for t in range(used):
        part = slice(t * tile, (t + 1) * tile)
        want[tile_expert[t]] += np.asarray(lhs)[part].T @ np.asarray(rhs)[part]
    assert float(np.max(np.abs(np.asarray(got) - want))) < 1e-3


# --- the layer's backward pass on either path -------------------------------------

TOKENS, TOP_K, EXPERTS, FIRST, HELD, D = 64, 2, 4, 1, 3, 128
NOT_HELD = 0


def routing(name):
    """``idx`` (64, 2) over experts 0..3, of which 1..3 are held; tiles
    of 16 rows, chunks of 4 tiles (64 rows)."""
    t = np.arange(TOKENS)
    slots = {
        # expert 2 gets nothing; expert 1 all 64 tokens: four whole tiles,
        # which end on a tile and on the first chunk; expert 3 40 rows
        "empty_and_exact": (np.full(TOKENS, 1), np.where(t < 40, 3, NOT_HELD)),
        # 40, 50 and 20 rows: 3 + 4 + 2 tiles; expert 2's tiles 3..6 lie
        # on both sides of the first chunk's end, expert 3's in the third
        "over_a_chunk": (np.where(t < 40, 1, np.where(t < 60, 3, NOT_HELD)),
                         np.where(t < 50, 2, NOT_HELD)),
        # the worst routing: both choices of every token on one expert,
        # 128 rows, 8 tiles, two chunks of one expert
        "all_on_one": (np.full(TOKENS, 2), np.full(TOKENS, 2)),
        # 32 rows end exactly on expert 3's second tile, 16 on expert 1's
        "exact_tiles": (np.where(t < 32, 3, NOT_HELD), np.where(t >= 48, 1, NOT_HELD)),
    }[name]
    return jnp.asarray(np.stack(slots, -1), jnp.int32)


ROUTINGS = ("empty_and_exact", "over_a_chunk", "all_on_one", "exact_tiles")


def dense_experts(x, idx, gates, w_up, w_down, w_gate):
    """Every held expert over every token, weighted by the gates of the
    choices that fell on it."""
    y = 0.0
    for e in range(w_up.shape[0]):
        gate = jnp.sum(jnp.where(idx == FIRST + e, gates, 0.0), -1)
        act = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e]) if w_gate is not None
               else jnp.square(jnp.maximum(x @ w_up[e], 0)))
        y = y + gate[:, None] * (act @ w_down[e])
    return y


def layer_gradients(name, f, gated):
    """(gradients through ``held_experts``, through the dense
    computation) to the tokens, the gates and every matrix."""
    idx = routing(name)
    keys = jax.random.split(jax.random.key(f + gated), 6)
    x = jax.random.normal(keys[0], (TOKENS, D))
    gates = jax.random.uniform(keys[1], (TOKENS, TOP_K))
    w_up = jax.random.normal(keys[2], (HELD, D, f)) / 8
    w_down = jax.random.normal(keys[3], (HELD, f, D)) / 8
    w_gate = jax.random.normal(keys[4], (HELD, D, f)) / 8 if gated else None
    weight = jax.random.normal(keys[5], (TOKENS, D))
    assert moe.tile_rows(TOKENS, TOP_K, EXPERTS) == 16
    assert moe.chunk_tiles(TOKENS, 16) == 4

    def mine(x, gates, w_up, w_down, w_gate=None):
        y, counters = moe.held_experts(x, idx, gates, w_up, w_down, EXPERTS,
                                       FIRST, w_gate=w_gate)
        return jnp.sum(y * weight), counters

    def dense(x, gates, w_up, w_down, w_gate=None):
        return jnp.sum(dense_experts(x, idx, gates, w_up, w_down, w_gate) * weight)

    args = (x, gates, w_up, w_down) + ((w_gate,) if gated else ())
    argnums = tuple(range(len(args)))
    got, counters = jax.jit(jax.grad(mine, argnums=argnums, has_aux=True))(*args)
    want = jax.jit(jax.grad(dense, argnums=argnums))(*args)
    return got, want, counters


def assert_close(got, want):
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert float(jnp.max(jnp.abs(a - b))) < 2e-4 * max(
            1.0, float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("f", [24, 128])
@pytest.mark.parametrize("name", ROUTINGS)
def test_backward_on_the_kernel_is_the_dense_computation(on_the_kernel, name,
                                                         f, gated):
    """Both expert forms, an expert width the lanes do not divide (24: the
    operands turned, every slab ``(f, d)``) and one they do (128)."""
    assert moe.wgrad_path(jax.default_backend(), D, f, 16)
    got, want, counters = layer_gradients(name, f, gated)
    assert_close(got, want)
    # the same tiles as ever: rows routed, rows multiplied, the fullest
    counts = np.bincount(np.asarray(routing(name)).ravel(), minlength=EXPERTS)[FIRST:]
    assert [float(c) for c in counters] == [
        counts.sum(), (-(-counts // 16) * 16).sum(), counts.max()]


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("name", ROUTINGS)
def test_backward_on_the_plain_path_is_the_dense_computation(name, gated):
    assert not moe.wgrad_path(jax.default_backend(), D, 24, 16)
    got, want, _ = layer_gradients(name, 24, gated)
    assert_close(got, want)


@pytest.mark.parametrize("platform,calls,kernels", [("cpu", 0, 0), ("tpu", 3, 2)])
def test_backward_takes_the_path_it_is_told(monkeypatch, platform, calls, kernels):
    """A gated layer's gradient calls the kernel three times where the
    backend says TPU (here: said to), never on the CPU. The wrapper is
    jitted, so the two calls of one shape (``dw_up``, ``dw_gate``) share
    one traced ``pallas_call``: two named kernels, not three."""
    monkeypatch.setattr(moe.jax, "default_backend", lambda: platform)
    monkeypatch.setattr(moe_pallas, "pallas_interpret", lambda: True)
    idx = routing("over_a_chunk")
    x, gates = jnp.ones((TOKENS, D)), jnp.ones((TOKENS, TOP_K))
    w = jnp.ones((HELD, D, 128))
    text = str(jax.make_jaxpr(jax.grad(
        lambda w: jnp.sum(moe.held_experts(
            x, idx, gates, w, w.swapaxes(1, 2), EXPERTS, FIRST, w_gate=w)[0])))(w))
    assert text.count("name=_grouped_wgrad") == calls
    assert text.count("pallas_call") == kernels
    assert text.count("name=grouped_wgrad") == kernels


# --- where the product runs ----------------------------------------------------------

@pytest.mark.parametrize("platform,d,f,tile,kernel", [
    ("tpu", 2048, 1536, 256, True),    # LFM2-24B-A2B's experts
    ("tpu", 2688, 1856, 256, True),    # the first token model's: 14.5 x 128 wide
    ("cpu", 2048, 1536, 256, False),
    ("cpu", 2688, 1856, 256, False),
    ("gpu", 2048, 1536, 256, False),
    ("tpu", 128, 24, 16, True),        # this file's smallest
    ("tpu", 64, 32, 8, False),         # the tests' toy models: half a row of lanes
    ("tpu", 2048, 1536, 8, False),     # a tile under a whole sublane tile of bf16
    ("tpu", 2048, 1540, 256, False),   # a width the sublanes do not divide
    ("tpu", 2048 + 64, 1536, 256, False),  # a hidden size the lanes do not divide
    ("tpu", 8192, 8192, 256, True),    # cut: 512 columns of the hidden size a block
    ("tpu", 128, 131072, 256, False),  # one 128-column block is already too large
])
def test_wgrad_path_follows_platform_and_shapes(platform, d, f, tile, kernel):
    assert moe.wgrad_path(platform, d, f, tile) is kernel


@pytest.mark.parametrize("hidden,width,block", [
    (2048, 1536, 2048), (2688, 1856, 2688), (8192, 8192, 512), (4096, 14336, 256),
    (2048 + 64, 1536, 0), (128, 131072, 0)])
def test_hidden_block_is_the_largest_that_fits(hidden, width, block):
    assert moe_pallas.hidden_block(hidden, width) == block


@pytest.mark.parametrize("width,turned", [(1536, False), (1856, True),
                                          (128, False), (24, True)])
def test_an_operand_is_turned_where_the_lanes_do_not_divide_its_width(width, turned):
    assert moe_pallas.rows_last(width) is turned


# --- the gauge ------------------------------------------------------------------------

def test_gauge_counts_the_layers_that_take_the_kernel():
    from distributedpytorch_tpu.models.lfm2 import Lfm2
    from distributedpytorch_tpu.models.twotower import TwoTower, twotower_config

    tokens = 2 * 8192
    lfm2 = Lfm2(dtype=jnp.bfloat16)
    assert moe.wgrad_path("tpu", 2048, 1536, moe.tile_rows(tokens, 4, 64))
    assert lfm2.moe_wgrad_kernel_layers("tpu", tokens) == 4
    assert lfm2.moe_wgrad_kernel_layers("cpu", tokens) == 0
    twotower = TwoTower(twotower_config(None), dtype=jnp.bfloat16)
    assert moe.wgrad_path("tpu", 2688, 1856, moe.tile_rows(tokens, 6, 128))
    assert twotower.moe_wgrad_kernel_layers("tpu", tokens) == 4
    assert twotower.moe_wgrad_kernel_layers("cpu", tokens) == 0
    # a step of so few tokens that a tile is under 16 rows: the plain loop
    assert twotower.moe_wgrad_kernel_layers("tpu", 256) == 0
    assert defs.MOE_WGRAD_KERNEL_LAYERS.name == "dpt_moe_wgrad_kernel_layers"


@pytest.mark.parametrize("arch", ["lfm2", "twotower"])
def test_trainer_sets_the_gauge_from_the_model_table(monkeypatch, tmp_path, arch):
    """On the CPU the Trainer reads 0; told that the backend is a TPU, the
    table's entry says what ``wgrad_path`` says of the model's shapes."""
    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.models import create_model, model_entry
    from distributedpytorch_tpu.train.loop import Trainer

    sizes = {"lfm2": dict(hidden_size=128, vocab_size=96, num_attention_heads=4,
                          num_key_value_heads=2, intermediate_size=160,
                          moe_intermediate_size=32, num_experts=4,
                          experts_total=8, num_experts_per_tok=2,
                          layer_types=("conv", "conv"), layer_indices=(0, 1),
                          num_dense_layers=1),
             "twotower": dict(hidden_size=128, vocab_size=96,
                              hybrid_override_pattern="EME", mamba_num_heads=8,
                              mamba_head_dim=8, ssm_state_size=16, n_groups=2,
                              chunk_size=8, moe_intermediate_size=24,
                              moe_shared_expert_intermediate_size=32,
                              n_routed_experts=4, experts_total=8,
                              num_experts_per_tok=2)}[arch]
    layers = {"lfm2": 1, "twotower": 2}[arch]
    cfg = TrainConfig(
        model_arch=arch, model_overrides=sizes, seq_len=64, batch_size=2,
        synthetic_samples=4, epochs=1, val_percent=0.0, dtype="f32",
        checkpoint_dir=str(tmp_path / "ckpt"), log_dir=str(tmp_path / "logs"),
        loss_dir=str(tmp_path / "loss"), async_checkpoint=False)
    trainer = Trainer(cfg)
    assert trainer.moe_wgrad_kernel_layers == 0
    assert defs.MOE_WGRAD_KERNEL_LAYERS.value == 0
    model, _ = create_model(cfg)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tile = moe.tile_rows(2 * 64, 2, 8)
    assert tile == 16 and moe.wgrad_path("tpu", 128, sizes["moe_intermediate_size"], tile)
    assert model_entry(cfg).moe_wgrad_kernel_layers(model, cfg) == layers
