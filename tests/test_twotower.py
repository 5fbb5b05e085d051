"""The token model (models/twotower.py, ops/sequence.py, ops/moe.py,
data/tokens.py) against its plain reference
(benchmark/references/nemotron_twotower_30b_a3b.py) at a small size on the
CPU: seeded random weights, widths shrunk here and nowhere else."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import flops  # noqa: E402
import weights as bench_weights  # noqa: E402
import weights_tokens  # noqa: E402

from distributedpytorch_tpu.config import TrainConfig  # noqa: E402
from distributedpytorch_tpu.models import MODELS, create_model, model_entry  # noqa: E402
from distributedpytorch_tpu.models.twotower import (  # noqa: E402
    NEMOTRON_TWOTOWER_SHARE,
    TwoTower,
    counter_names,
    twotower_config,
)
from distributedpytorch_tpu.ops import moe, sequence as seq  # noqa: E402

TINY = dict(hidden_size=64, vocab_size=96, mamba_num_heads=8, mamba_head_dim=8,
            ssm_state_size=16, n_groups=2, chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, n_routed_experts=4,
            num_experts_per_tok=3, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=48)
CONFIG = flops.load_config("nemotron_twotower_30b_a3b")
REF = flops.load_reference(CONFIG)


def tiny(pattern="ME*E", experts_total=16, first_held=4, **more):
    """(reference's configuration dict, the program's overrides)."""
    # a balancing rate that moves the choice within three steps
    sizes = {**TINY, "hybrid_override_pattern": pattern,
             "router_bias_update_rate": 0.05, **more}
    config = {**CONFIG, **sizes, "deployment": {
        **CONFIG["deployment"], "experts_total": experts_total,
        "first_held": first_held}}
    return config, {**sizes, "experts_total": experts_total,
                    "first_held": first_held}


def worst_leaf(mine, ref):
    """Largest norm of a leaf's difference over the reference's norm."""
    return max(float(jnp.linalg.norm(mine[k] - ref[k]))
               / max(float(jnp.linalg.norm(ref[k])), 1e-6) for k in ref)


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("pattern", ["M", "E", "*", "ME*E"])
def test_program_agrees_with_reference_logits_loss_every_gradient(pattern):
    config, overrides = tiny(pattern)
    model = TwoTower(twotower_config(overrides), jnp.float32)
    shapes = REF.param_shapes(config)
    flat = weights_tokens.make(shapes, 5)
    # a selection bias that changes the choice, not the gate
    flat = {k: (0.3 * jnp.sin(jnp.arange(v.size, dtype=jnp.float32)).reshape(v.shape)
                if k.endswith("router/bias") else v) for k, v in flat.items()}
    params = bench_weights.to_program(
        flat, jax.eval_shape(model.init, jax.random.key(0)))
    tokens = jax.random.randint(jax.random.key(2), (2, 43), 0, 96)
    logits = jax.jit(model.logits)(params, tokens)
    ref_logits = jnp.stack([REF.logits(REF.Ops(), config, flat, t) for t in tokens])
    assert float(jnp.max(jnp.abs(logits - ref_logits))) < 2e-4
    (loss, (_, biases)), grads = jax.jit(jax.value_and_grad(
        lambda p, t: (lambda loss, *rest: (loss, rest))(*model.loss(p, t)),
        has_aux=True))(params, tokens)
    ref_loss, ref_grads, _, loads = REF.make_loss_and_grad(config)(
        flat, np.asarray(tokens))
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    assert worst_leaf(bench_weights.flat_names(grads), ref_grads) < 1e-3
    # the routers' balancing: every bias moved by the rate, as the reference's
    ref_biases = REF.balanced_biases(config, flat, loads)
    assert bench_weights.flat_names(biases).keys() == ref_biases.keys()
    for k, b in bench_weights.flat_names(biases).items():
        assert float(jnp.max(jnp.abs(b - ref_biases[k]))) == 0.0
        moved = np.abs(np.asarray(b - flat[k]))
        assert np.allclose(moved[moved > 0], config["router_bias_update_rate"])
        assert (moved > 0).sum() >= 14  # an expert exactly at the mean stays


def test_published_share_counts_its_parameters():
    shapes = jax.eval_shape(TwoTower().init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 666_963_456
    assert REF.param_count(CONFIG) == 666_963_456
    assert NEMOTRON_TWOTOWER_SHARE.hybrid_override_pattern == "MEMEM*EME"
    assert {k: tuple(v.shape) for k, v in bench_weights.flat_names(shapes).items()} \
        == {k: tuple(v) for k, v in REF.param_shapes(CONFIG).items()}


@pytest.mark.parametrize("length", [256, 200])
def test_chunked_scan_is_the_sequential_recurrence(length):
    """Across chunk boundaries, and at lengths that are no multiple of the
    published chunk of 128."""
    keys = jax.random.split(jax.random.key(length), 5)
    h, p, g, n = 4, 8, 2, 16
    x = jax.random.normal(keys[0], (2, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (2, length, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(keys[2], (h,)))
    b = jax.random.normal(keys[3], (2, length, g, n))
    c = jax.random.normal(keys[4], (2, length, g, n))

    def both(x, dt, a, b, c):
        mine = seq.ssd_scan(x, dt, a, b, c, 128)
        ref = jnp.stack([REF.recurrence(x[i], dt[i], a, b[i], c[i])
                         for i in range(2)])
        return mine, ref

    mine, ref = jax.jit(both)(x, dt, a, b, c)
    assert float(jnp.max(jnp.abs(mine - ref))) < 1e-4 * float(jnp.max(jnp.abs(ref)))
    grads, ref_grads = jax.jit(lambda *args: [
        jax.grad(lambda *a: jnp.sum(jnp.sin(both(*a)[i])), argnums=(0, 1, 2, 3, 4))(
            *args) for i in (0, 1)])(x, dt, a, b, c)
    for mine_g, ref_g in zip(grads, ref_grads):
        assert float(jnp.linalg.norm(mine_g - ref_g)) < 1e-4 * float(
            jnp.linalg.norm(ref_g))


@pytest.mark.parametrize("length,block", [(100, 32), (40, 512)])
def test_blocked_attention_is_full_attention(length, block):
    keys = jax.random.split(jax.random.key(length), 3)
    q = jax.random.normal(keys[0], (2, length, 4, 16))
    k = jax.random.normal(keys[1], (2, length, 2, 16))
    v = jax.random.normal(keys[2], (2, length, 2, 16))

    def full(q, k, v):
        kk, vv = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / 4.0
        s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)

    def blocked(q, k, v):
        return seq.causal_attention(q, k, v, block=block)

    def value_and_grads(q, k, v):
        return [(fn(q, k, v), jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                       argnums=(0, 1, 2))(q, k, v))
                for fn in (blocked, full)]

    (mine, g), (whole, g_full) = jax.jit(value_and_grads)(q, k, v)
    assert float(jnp.max(jnp.abs(mine - whole))) < 1e-5
    for a, b in zip(g, g_full):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_sixteen_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """Each share routes over all 32 experts and computes its own 2; what
    the shares give, with the shared expert counted once, is what the
    reference gives with every expert held."""
    config, _ = tiny("E", experts_total=32, first_held=0, n_routed_experts=32)
    whole = weights_tokens.make(REF.param_shapes(config), 3)
    whole = {k[len("block_00/mixer/"):]: v for k, v in whole.items()
             if k.startswith("block_00/mixer/")}
    x = jax.random.normal(jax.random.key(4), (1, 50, 64))
    uncut, _ = REF.experts(REF.Ops(), config, whole, x[0])
    routed_only, _ = REF.experts(REF.Ops(), config, whole, x[0], only_routed=True)
    total, counted = 0.0, 0.0
    for share in range(16):
        model = TwoTower(twotower_config({
            **TINY, "n_routed_experts": 2, "experts_total": 32,
            "first_held": 2 * share}), jnp.float32)
        held = slice(2 * share, 2 * share + 2)
        p = {"router": {"kernel": whole["router/kernel"], "bias": whole["router/bias"]},
             "shared": {"up": {"kernel": whole["shared/up/kernel"]},
                        "down": {"kernel": whole["shared/down/kernel"]}},
             "experts": {"up": {"kernel": whole["experts/up/kernel"][held]},
                         "down": {"kernel": whole["experts/down/kernel"][held]}}}
        y, counters, _, _ = jax.jit(model._experts)(p, x)
        total = total + y[0]
        counted += float(counters[0])
    shared = uncut - routed_only
    assert float(jnp.max(jnp.abs(total - 15.0 * shared - uncut))) < 1e-4
    assert counted == 50 * 3  # every (token, slot) choice lands on one share


def test_held_experts_drop_no_row_under_any_imbalance():
    """All tokens on one held expert: the loop still multiplies every row,
    and the counters say what it cost."""
    t, d, f = 40, 16, 8
    x = jax.random.normal(jax.random.key(0), (t, d))
    idx = jnp.stack([jnp.full((t,), 5), jnp.arange(t) % 3 + 9], -1).astype(jnp.int32)
    gates = jnp.ones((t, 2)) * 0.5
    w_up = jax.random.normal(jax.random.key(1), (2, d, f))
    w_down = jax.random.normal(jax.random.key(2), (2, f, d))
    y, counters = jax.jit(lambda *a: moe.held_experts(*a, 16, 4))(
        x, idx, gates, w_up, w_down)
    want = 0.5 * jnp.square(jnp.maximum(x @ w_up[1], 0)) @ w_down[1]
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4
    tile = moe.tile_rows(t, 2, 16)
    assert [float(c) for c in counters] == [t, -(-t // tile) * tile, t]
    # with no row routed here the loop multiplies nothing
    none = jnp.full((t, 2), 12, jnp.int32)
    y0, counters0 = jax.jit(lambda *a: moe.held_experts(*a, 16, 4))(
        x, none, gates, w_up, w_down)
    assert float(jnp.max(jnp.abs(y0))) == 0.0
    assert [float(c) for c in counters0] == [0, 0, 0]


def test_packer_keeps_every_token_in_order_with_its_end_marker():
    from distributedpytorch_tpu.data.tokens import pack_documents, synthetic_documents

    rng = np.random.default_rng(0)
    docs = synthetic_documents(rng, 5000, 96, median=40, min_len=3, max_len=400)
    stream = np.concatenate([np.append(d, 95) for d in docs])
    first, rest = pack_documents(docs[:7], 64, 95)
    second, rest = pack_documents(docs[7:], 64, 95, carry=rest)
    packed = np.concatenate([first.ravel(), second.ravel(), rest])
    assert np.array_equal(packed, stream)
    assert first.shape[1] == 64 and len(rest) < 64 and first.dtype == np.int32
    assert max(int(d.max()) for d in docs) < 95 and min(len(d) for d in docs) >= 3


def trainer_config(tmp_path, **more):
    _, overrides = tiny("ME*")
    return TrainConfig(
        model_arch="twotower", model_overrides=overrides, seq_len=40, batch_size=2,
        synthetic_samples=10, epochs=1, val_percent=20.0, learning_rate=3e-4,
        weight_decay=1e-8, faithful_loss_scaling=False, dtype="f32",
        metric_every_steps=1, checkpoint_dir=str(tmp_path / "ckpt"),
        log_dir=str(tmp_path / "logs"), loss_dir=str(tmp_path / "loss"),
        async_checkpoint=False, **more)


def test_trainer_reproduces_the_references_losses_and_resumes(tmp_path):
    """Three steps through Trainer, the loader, the feed and SingleDevice
    on packed tokens give the reference's losses; the checkpoint restores
    and the run goes on; the counters reach the registry with the loss."""
    import reference
    from distributedpytorch_tpu.obs import defs as obsm
    from distributedpytorch_tpu.train.loop import Trainer

    config, _ = tiny("ME*")
    cfg = trainer_config(tmp_path)
    trainer = Trainer(cfg)
    flat0 = {k: jnp.copy(v) for k, v in
             bench_weights.flat_names(trainer.state.params).items()}
    batches = list(trainer.train_loader.epoch_batches(0))[:3]
    routed0 = obsm.MOE_ROWS_ROUTED.labels(block="1").value
    result = trainer.train()
    assert result["steps"] == 4 and np.isnan(result["val_dice"])
    assert np.isfinite(result["val_loss"])
    mine = [row[2] for row in trainer.records.train_rows[:3]]
    assert obsm.MOE_ROWS_ROUTED.labels(block="1").value > routed0

    config = {**config, "optimizer": {**config["optimizer"],
                                      "lr": cfg.learning_rate}}
    loss_and_grad, update = REF.make_loss_and_grad(config), reference.make_update(config)
    cur = flat0
    m = {k: jnp.zeros_like(v) for k, v in cur.items()}
    v = {k: jnp.zeros_like(x) for k, x in cur.items()}
    for i, batch in enumerate(batches):
        loss, g, _, loads = loss_and_grad(cur, batch["tokens"])
        assert abs(float(loss) - mine[i]) < 2e-4 * float(loss), i
        biases = REF.balanced_biases(config, cur, loads)
        cur, m, v, _ = update(cur, m, v, jnp.float32(i + 1), g, jnp.float32(1.0))
        cur = {**cur, **biases}
    # the Trainer's routers were balanced through the same steps
    assert float(jnp.max(jnp.abs(biases["block_01/mixer/router/bias"]))) > 0

    resumed = Trainer(dataclasses.replace(cfg, checkpoint_name="singleGPU", epochs=2))
    assert resumed.start_epoch == 1 and int(resumed.state.step) == 4
    assert worst_leaf(bench_weights.flat_names(resumed.state.params),
                      bench_weights.flat_names(trainer.state.params)) == 0.0
    assert resumed.train()["steps"] == 8


def test_model_table_names_its_models_and_serve_refuses_the_token_model():
    assert set(MODELS) == {"unet", "milesial", "twotower"}
    assert model_entry("twotower").batch.fields == ("tokens",)
    assert not model_entry("twotower").servable and model_entry("unet").servable
    with pytest.raises(ValueError, match="known: .*'twotower'"):
        create_model(TrainConfig(model_arch="resnet"))
    from distributedpytorch_tpu.serve.infer import load_inference_bundle

    with pytest.raises(ValueError, match="token model"):
        load_inference_bundle("x", model_arch="twotower")
    with pytest.raises(ValueError, match="one device"):
        from distributedpytorch_tpu.parallel import build_strategy
        build_strategy(TrainConfig(model_arch="twotower", train_method="DP"))
    names = counter_names(twotower_config({"hybrid_override_pattern": "MEE"}))
    assert names[:3] == ("moe_rows_routed/1", "moe_rows_computed/1",
                         "moe_rows_max_expert/1") and len(names) == 6


def test_fetch_and_h2d_spans_carry_tokens():
    from distributedpytorch_tpu.utils.prefetch import pipelined_placement, stacked_work
    from distributedpytorch_tpu.utils.trace import StepTimeline

    tracer = StepTimeline(enabled=True)
    batches = [{"tokens": np.zeros((2, 40), np.int32)} for _ in range(3)]
    list(pipelined_placement(stacked_work(iter(batches), 1, 2),
                             lambda kind, payload: payload, depth=1,
                             tracer=tracer, epoch=0))
    fetched = [e for e in tracer.events() if e["phase"] == "fetch" and "end" not in e]
    assert [e["tokens"] for e in fetched] == [80, 80, 80]
    assert all(e["bytes"] == 320 for e in fetched)


def test_scopes_name_the_compiled_step():
    _, overrides = tiny("ME*")
    model = TwoTower(twotower_config(overrides), jnp.float32)
    params = model.init(jax.random.key(0))
    text = jax.jit(model.loss).lower(
        params, jnp.zeros((1, 16), jnp.int32)).as_text(debug_info=True)
    for scope in ("mamba2", "ssd_scan", "attention", "moe_router", "moe_experts",
                  "moe_shared", "lm_head"):
        assert scope in text, scope
