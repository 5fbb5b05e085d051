"""The token model (models/twotower.py, ops/sequence.py, ops/moe.py,
data/tokens.py) against its plain reference
(benchmark/references/nemotron_twotower_30b_a3b.py) at a small size on the
CPU: seeded random weights, widths shrunk here and nowhere else."""

import collections
import dataclasses
import math
import os
import re
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
from distributedpytorch_tpu.models import twotower  # noqa: E402
from distributedpytorch_tpu.models.twotower import (  # noqa: E402
    KEPT_ACTIVATIONS,
    NEMOTRON_TWOTOWER_SHARE,
    TwoTower,
    counter_names,
    twotower_config,
)
from distributedpytorch_tpu.ops import attention_pallas, moe, sequence as seq  # noqa: E402

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
    assert set(MODELS) == {"unet", "milesial", "twotower", "lfm2",
                           "smallthinker"}
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


# -- what the blocks' recomputation keeps (twotower.KEPT_ACTIVATIONS) --------

#: A device's memory beside which every toy size fits.
AMPLE = 16 << 30


def loss_grads_and_routing(model, params, tokens):
    """((loss, (counters, biases)), gradients) of one jitted step."""
    return jax.jit(jax.value_and_grad(
        lambda p, t: (lambda loss, *rest: (loss, rest))(*model.loss(p, t)),
        has_aux=True))(params, tokens)


def residuals(model, params, tokens, capsys):
    """How often each shape and dtype is among the residuals that the
    loss's backward pass keeps, as ``print_saved_residuals`` lists them."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        lambda p: model.loss(p, tokens)[0], params)
    return collections.Counter(
        line.split(" ", 1)[0]
        for line in capsys.readouterr().out.strip().splitlines())


def result_dots(jaxpr_text, shape):
    """How many matrix products in the jaxpr give a float32 ``shape``."""
    dims = ",".join(str(n) for n in shape)
    return jaxpr_text.count(f":f32[{dims}] = dot_general[")


@pytest.mark.parametrize("pattern", ["M", "E", "*", "ME*E"])
def test_kept_activations_change_no_number(pattern, monkeypatch):
    """Loss, every gradient leaf, counters and biases with the named
    activations kept are those with each block's input alone kept, and
    those with nothing recomputed at all."""
    _, overrides = tiny(pattern)
    cfg = twotower_config(overrides)
    params = TwoTower(cfg, jnp.float32).init(jax.random.key(1))
    tokens = jax.random.randint(jax.random.key(2), (2, 43), 0, 96)
    kept = loss_grads_and_routing(
        TwoTower(cfg, jnp.float32, memory_bytes=AMPLE), params, tokens)
    bare = loss_grads_and_routing(TwoTower(cfg, jnp.float32), params, tokens)
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **kwargs: fn)
    plain = loss_grads_and_routing(TwoTower(cfg, jnp.float32), params, tokens)
    for other in (bare, plain):
        ((loss, (counters, biases)), grads) = other
        # the same sums in another order of XLA's fusions: rounding alone
        assert abs(float(kept[0][0]) - float(loss)) <= 1e-6 * float(loss)
        assert worst_leaf(bench_weights.flat_names(kept[1]),
                          bench_weights.flat_names(grads)) < 1e-5
        assert np.array_equal(kept[0][1][0], counters)
        assert jax.tree.all(jax.tree.map(np.array_equal, kept[0][1][1], biases))


@pytest.mark.parametrize("pattern", ["M", "E", "*", "ME*E"])
def test_policy_keeps_the_named_results_and_never_the_scans_decays(pattern, capsys):
    _, overrides = tiny(pattern)
    cfg = twotower_config(overrides)
    kept_model = TwoTower(cfg, jnp.float32, memory_bytes=AMPLE)
    bare_model = TwoTower(cfg, jnp.float32)
    params = bare_model.init(jax.random.key(1))
    tokens = jax.random.randint(jax.random.key(2), (2, 43), 0, 96)
    t = tokens.size
    kept = residuals(kept_model, params, tokens, capsys)
    bare = residuals(bare_model, params, tokens, capsys)
    assert not bare - kept
    added = sorted((kept - bare).elements())
    # what the policy adds is the named results and nothing else ...
    shapes = {
        "M": [f"f32[2,43,{cfg.d_inner + cfg.conv_dim + cfg.mamba_num_heads}]"],
        "E": [f"f32[{t},48]"],
        # blocked XLA attention has no names (the kernel's are held by
        # tests/test_attention_kernel.py)
        "*": [],
    }
    assert added == sorted(s for kind in pattern for s in shapes[kind])
    # ... its bytes are the gauge's ...
    sizes = {"f32": 4, "i32": 4}
    assert sum(sizes[a[:3]] * math.prod(int(n) for n in a[4:-1].split(","))
               for a in added) \
        == sum(kept_model.kept_activation_bytes(2, 43, "cpu")) \
        == sum(kept_model.named_activation_bytes(2, 43, "cpu"))
    assert not any(bare_model.kept_activation_bytes(2, 43, "cpu"))
    # ... and no (chunk x chunk) decay matrix of the scan is among them
    assert not [a for a in kept if a.count(",") == 5]
    # every name is in the gradient's jaxpr; a kept product is made once less
    grad = lambda model: str(jax.make_jaxpr(jax.grad(  # noqa: E731
        lambda p: model.loss(p, tokens)[0]))(params))
    kept_text, bare_text = grad(kept_model), grad(bare_model)
    names = {"M": ["mamba_in_proj"], "E": ["shared_up"], "*": []}
    for kind in set(pattern):
        for name in names[kind]:
            assert f"name={name}]" in kept_text, name
    assert set(n for ns in names.values() for n in ns) | set(
        attention_pallas.RESIDUALS) == set(KEPT_ACTIVATIONS)
    results = {"M": (2, 43, cfg.d_inner + cfg.conv_dim + cfg.mamba_num_heads),
               "E": (t, 48)}
    for kind in set(pattern) - {"*"}:
        assert (result_dots(bare_text, results[kind])
                - result_dots(kept_text, results[kind])) == pattern.count(kind), kind


#: One step of the benchmark's cell: 2 x 8192 tokens of the published
#: share, bf16, the attention block on the kernel; bytes a block kind.
CELL_NAMED = {"M": 337_641_472, "E": 121_634_816, "*": 301_989_888}
V5E = 16_909_336_064  # memory_stats()["bytes_limit"] of one v5e chip


@pytest.mark.parametrize("batch,memory,kept", [
    (2, V5E, "MEMEM*EME"),       # the cell: every block's, 0.50 GB to spare
    (3, V5E, "---E-*EME"),       # half as many tokens again: from the last,
    (4, V5E, "------E-E"),       # twice the tokens: what fits beside the rest
    (2, None, "---------"),      # no figure (the CPU): each block's input alone
    (2, 12 << 30, "---------"),  # state and working set alone fill it
])
def test_blocks_keep_their_names_from_the_last_while_the_budget_lasts(
        batch, memory, kept):
    model = TwoTower(dtype=jnp.bfloat16, memory_bytes=memory)
    named = model.named_activation_bytes(batch, 8192, "tpu")
    assert named == tuple(CELL_NAMED[k] * batch // 2 for k in "MEMEM*EME")
    assert model.kept_activation_bytes(batch, 8192, "tpu") == tuple(
        n if k != "-" else 0 for n, k in zip(named, kept))
    budget = twotower.kept_budget(
        666_963_456, 48 * batch * 8192 * 2688, memory)
    assert sum(model.kept_activation_bytes(batch, 8192, "tpu")) <= budget
    assert (budget > 0) == ("E" in kept)


@pytest.mark.parametrize("memory", [None, 1 << 20])
def test_without_the_room_the_step_lowers_as_without_any_name(memory, monkeypatch):
    """A device that reports no memory, or too little, takes block-level
    recomputation: the lowered step is the one of a program in which no
    value has a name (and not with the room), character for character
    once the numbers in private functions' symbols are taken off (jax
    numbers ``@_where_159`` by the equations traced before it, and a name
    is an equation, which lowers to nothing)."""
    _, overrides = tiny("ME*E")
    cfg = twotower_config(overrides)
    params = TwoTower(cfg, jnp.float32).init(jax.random.key(1))
    tokens = jnp.zeros((2, 43), jnp.int32)

    def lowered(memory_bytes):
        model = TwoTower(cfg, jnp.float32, memory_bytes=memory_bytes)
        text = jax.jit(jax.value_and_grad(
            lambda p, t: model.loss(p, t)[0])).lower(params, tokens).as_text()
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

    fallback, engaged = lowered(memory), lowered(AMPLE)
    for module in (seq, attention_pallas):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    assert fallback == lowered(memory)
    assert engaged != fallback
