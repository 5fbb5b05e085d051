"""The SmallThinker token model (models/smallthinker.py: a router that
reads the layer's input before attention, sliding-window and position-free
full attention in one stack, softmax-gated ReGLU experts) against its
plain reference (benchmark/references/smallthinker_21b_a3b.py) at a small
size on the CPU: seeded random weights, widths shrunk here and nowhere
else."""

import collections
import dataclasses
import json
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
from distributedpytorch_tpu.models import MODELS, model_entry, recompute  # noqa: E402
from distributedpytorch_tpu.models import smallthinker as module  # noqa: E402
from distributedpytorch_tpu.models.smallthinker import (  # noqa: E402
    KEPT_ACTIVATIONS,
    SMALLTHINKER_21B_A3B_SHARE,
    SmallThinker,
    SmallThinkerConfig,
    smallthinker_config,
)
from distributedpytorch_tpu.ops import attention_pallas, moe, sequence as seq  # noqa: E402

TINY = dict(hidden_size=64, vocab_size=96, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32,
            moe_num_primary_experts=4, moe_num_active_primary_experts=3,
            sliding_window_size=11)
CONFIG = flops.load_config("smallthinker_21b_a3b")
REF = flops.load_reference(CONFIG)
#: a position-free full layer, then two windowed layers with rotary
PATTERN = ((0, 1, 1), (0, 1, 1), (0, 1, 2))
AMPLE = 16 << 30
V5E = 16_909_336_064  # memory_stats()["bytes_limit"] of one v5e chip


def tiny(windows=PATTERN[0], ropes=PATTERN[1], held=PATTERN[2], experts_total=8,
         first_held=2, **more):
    """(reference's configuration dict, the program's overrides): 4 of 8
    experts held from a non-zero ``first_held``, a window of 11 positions."""
    # a balancing rate that moves the choice within three steps
    sizes = {**TINY, "sliding_window_layout": list(windows),
             "rope_layout": list(ropes), "router_bias_update_rate": 0.05, **more}
    config = {**CONFIG, **sizes, "deployment": {
        **CONFIG["deployment"], "experts_total": experts_total,
        "first_held": first_held, "layers_held": list(held)}}
    return config, REF.program_overrides(config)


def worst_leaf(mine, ref):
    """Largest norm of a leaf's difference over the reference's norm."""
    return max(float(jnp.linalg.norm(mine[k] - ref[k]))
               / max(float(jnp.linalg.norm(ref[k])), 1e-6) for k in ref)


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def seeded(config, model, seed=5):
    """(flat weights for the reference, the program's tree) from the seed,
    with a selection bias that changes the choice, not the gate."""
    flat = weights_tokens.make(REF.param_shapes(config), seed, config)
    flat = {k: (0.3 * jnp.sin(jnp.arange(v.size, dtype=jnp.float32)).reshape(v.shape)
                if k.endswith("router/bias") else v) for k, v in flat.items()}
    return flat, bench_weights.to_program(
        flat, jax.eval_shape(model.init, jax.random.key(0)))


def loss_and_grads(model, params, tokens):
    return jax.jit(jax.value_and_grad(
        lambda p, t: (lambda loss, *rest: (loss, rest))(*model.loss(p, t)),
        has_aux=True))(params, tokens)


@pytest.mark.parametrize("windows,ropes,held", [
    ((0,), (0,), (0,)),          # full attention without positions
    ((1,), (1,), (1,)),          # a window shorter than the sequence, rotary
    ((1,), (0,), (5,)),          # a window without positions
    PATTERN,                     # both in one stack, as published
])
def test_program_agrees_with_reference_logits_loss_every_gradient(windows, ropes,
                                                                   held):
    config, overrides = tiny(windows, ropes, held)
    model = SmallThinker(smallthinker_config(overrides), jnp.float32)
    flat, params = seeded(config, model)
    tokens = jax.random.randint(jax.random.key(2), (2, 43), 0, 96)
    logits = jax.jit(model.logits)(params, tokens)
    ref_logits = jnp.stack([REF.logits(REF.Ops(), config, flat, t) for t in tokens])
    assert float(jnp.max(jnp.abs(logits - ref_logits))) < 2e-4
    (loss, (_, biases)), grads = loss_and_grads(model, params, tokens)
    ref_loss, ref_grads, _, loads = REF.make_loss_and_grad(config)(
        flat, np.asarray(tokens))
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    mine = bench_weights.flat_names(grads)
    assert mine.keys() == ref_grads.keys()
    assert worst_leaf(mine, ref_grads) < 1e-3
    # the routers' balancing: every bias moved by the rate, as the reference's
    ref_biases = REF.balanced_biases(config, flat, loads)
    assert bench_weights.flat_names(biases).keys() == ref_biases.keys()
    assert len(ref_biases) == REF.expert_blocks(config) == len(windows)
    for k, b in bench_weights.flat_names(biases).items():
        assert float(jnp.max(jnp.abs(b - ref_biases[k]))) == 0.0
        moved = np.abs(np.asarray(b - flat[k]))
        assert np.allclose(moved[moved > 0], config["router_bias_update_rate"])
        assert (moved > 0).sum() >= 6  # an expert exactly at the mean stays


def test_references_loop_over_query_blocks_is_the_one_block_softmax(monkeypatch):
    """At the real size the reference walks 32 blocks of 512 queries in a
    ``lax`` loop, a window layer's keys cut from a padded copy; here 43
    positions as two blocks of 16 and a short one of 11 give what one block
    over everything gives (the other tests' path), loss and every leaf."""
    config, _ = tiny()
    flat = weights_tokens.make(REF.param_shapes(config), 7, config)
    tokens = np.asarray(jax.random.randint(jax.random.key(3), (2, 43), 0, 96))
    loss, grads, chosen, _ = REF.make_loss_and_grad(config)(flat, tokens)
    monkeypatch.setattr(REF, "Q_BLOCK", 16)
    for mode, same in (("f32", True), ("no_window", False)):
        cut_loss, cut, cut_chosen, _ = REF.make_loss_and_grad(config, mode)(
            flat, tokens)
        assert (abs(float(cut_loss) - float(loss)) < 1e-6 * float(loss)) == same
        assert (worst_leaf(cut, grads) < 1e-4) == same
        # the first layer's router reads the embedding, whatever attention does
        assert np.array_equal(cut_chosen[0], chosen[0])
        assert all(np.array_equal(a, b) for a, b in zip(cut_chosen, chosen)) == same


def test_the_window_and_the_missing_positions_are_seen_by_the_comparison():
    """The reference with the window ignored (its planted fault), and the
    program with rotary where the layout has none, are other functions."""
    config, overrides = tiny()
    model = SmallThinker(smallthinker_config(overrides), jnp.float32)
    flat, params = seeded(config, model)
    tokens = jax.random.randint(jax.random.key(2), (2, 43), 0, 96)
    _, grads = loss_and_grads(model, params, tokens)
    mine = bench_weights.flat_names(grads)
    _, no_window, _, _ = REF.make_loss_and_grad(config, "no_window")(
        flat, np.asarray(tokens))
    assert worst_leaf(mine, no_window) > 0.05
    with_rope = SmallThinker(smallthinker_config(
        {**overrides, "rope_layout": (1, 1, 1)}), jnp.float32)
    _, other = loss_and_grads(with_rope, params, tokens)
    assert worst_leaf(bench_weights.flat_names(other), mine) > 0.05
    with pytest.raises(ValueError, match="unknown reference mode"):
        REF.make_loss_and_grad(config, "bf16")


def test_published_share_counts_its_parameters_and_the_uncut_model():
    shapes = jax.eval_shape(SmallThinker().init, jax.random.key(0))
    # ISSUE 37's 656,529,920 and the four routers' 64 selection biases
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 656_529_920 + 4 * 64
    assert REF.param_count(CONFIG) == 656_530_176 == CONFIG["parameters"]
    assert {k: tuple(v.shape) for k, v in bench_weights.flat_names(shapes).items()} \
        == {k: tuple(v) for k, v in REF.param_shapes(CONFIG).items()}
    uncut = REF.param_count(REF.published(CONFIG))
    assert uncut - 52 * 64 == 21_506_562_560 == CONFIG["published"]["parameters"]
    pub = CONFIG["published"]
    assert len(pub["sliding_window_layout"]) == 52 == CONFIG["num_hidden_layers"]
    assert pub["sliding_window_layout"] == pub["rope_layout"] == [0, 1, 1, 1] * 13
    # the layers held: one whole period, full then three windowed
    held = CONFIG["deployment"]["layers_held"]
    for key in ("sliding_window_layout", "rope_layout"):
        assert [pub[key][i] for i in held] == CONFIG[key] == list(
            getattr(SMALLTHINKER_21B_A3B_SHARE, key))
    assert SMALLTHINKER_21B_A3B_SHARE.windows == (None, 4096, 4096, 4096)
    assert SmallThinkerConfig(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in REF.program_overrides(CONFIG).items()}) == dataclasses.replace(
            SMALLTHINKER_21B_A3B_SHARE, router_bias_update_rate=0.01)


def test_configuration_keeps_every_published_number_but_the_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(catalog)]
    row = next(r for r in rows if r["source_url"] == CONFIG["source"])
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == set(CONFIG["reduced"])
    assert {k: row["config"][k] for k in CONFIG["reduced"]} == {
        k: CONFIG["published"][k] for k in CONFIG["reduced"]}
    assert set(CONFIG["assumed"]) >= {
        "router_input", "router", "selection_bias", "experts", "attention",
        "optimizer", "precision", "documents", "weights"}
    assert "DEPARTURE" in CONFIG["assumed"]["selection_bias"]


def test_program_overrides_are_the_programs_size_keys_that_the_file_has():
    fields = {f.name for f in dataclasses.fields(SmallThinkerConfig)}
    out = REF.program_overrides(CONFIG)
    assert set(out) <= fields
    assert set(REF.PROGRAM_KEYS) == {k for k in fields if k in CONFIG}
    assert (out["experts_total"], out["first_held"], out["layer_indices"],
            out["rope_theta"], out["sliding_window_size"]) == (
                64, 0, [0, 1, 2, 3], 1_500_000, 4096)
    assert REF.expert_blocks(CONFIG) == 4 and REF.held_experts(CONFIG) == 16


def test_four_shares_of_one_layer_add_up_to_the_uncut_layer():
    """The guide's test of the cut: each share routes over all 8 experts
    and computes its own 2 after the same attention; what the shares add
    to the stream adds up to what the reference's layer adds with every
    expert held. The router is whole on every share: its choices are the
    same on each, and every (token, slot) choice lands on one share."""
    config, _ = tiny((1,), (1,), (1,), experts_total=8, first_held=0,
                     moe_num_primary_experts=8)
    flat = weights_tokens.make(REF.param_shapes(config), 3, config)
    flat["layer_00/router/bias"] = 0.2 * jnp.cos(jnp.arange(8.0))
    whole = REF.sub(flat, "layer_00")
    x = jax.random.normal(jax.random.key(4), (1, 50, 64))
    uncut, chosen = REF.layer(REF.Ops(), config, 11, True, whole, x[0])
    none = {k: (v[:0] if k.startswith("experts/") else v) for k, v in whole.items()}
    after_attention, _ = REF.layer(
        REF.Ops(), {**config, "moe_num_primary_experts": 0}, 11, True, none, x[0])
    total, counted = 0.0, 0.0
    for share in range(4):
        overrides = REF.program_overrides({
            **config, "moe_num_primary_experts": 2,
            "deployment": {**config["deployment"], "first_held": 2 * share}})
        model = SmallThinker(smallthinker_config(overrides), jnp.float32)
        held = {k: (v[2 * share:2 * share + 2] if k.startswith("experts/") else v)
                for k, v in whole.items()}
        template = jax.eval_shape(model.init, jax.random.key(0))["layer_00"]
        p = bench_weights.to_program(
            {f"layer_00/{k}": v for k, v in held.items()}, {"layer_00": template})
        y, (counters, idx, _) = jax.jit(
            lambda p, h: model._layer(11, True, p, h))(p["layer_00"], x)
        assert np.array_equal(np.sort(idx, -1), np.sort(chosen, -1))
        total = total + (y[0] - after_attention)
        counted += float(counters[0])
    assert float(jnp.max(jnp.abs(total - (uncut - after_attention)))) < 1e-4
    assert counted == 50 * 3


def test_the_router_reads_the_layers_input_not_attentions_output():
    """Fails if the router is moved behind attention (or behind a norm):
    the choice and the gates are those of ``ops/moe.route`` on the raw
    input, whatever attention makes of it; a change of the input that
    attention cannot see at a position (a later one) moves no choice there,
    and the experts' input is the stream after attention."""
    config, overrides = tiny((0,), (0,), (0,))
    cfg = smallthinker_config(overrides)
    model = SmallThinker(cfg, jnp.float32)
    flat, params = seeded(config, model)
    p = params["layer_00"]
    h = jax.random.normal(jax.random.key(7), (1, 30, 64))
    _, (_, idx, _) = jax.jit(lambda p, h: model._layer(None, False, p, h))(p, h)
    want, _ = moe.route(h[0], p["router"]["kernel"], p["router"]["bias"], 3, True,
                        1.0, score="softmax")
    assert np.array_equal(idx, want)
    normed = seq.rms_norm(h, p["attn_norm"]["scale"], cfg.rms_norm_eps)
    behind, _ = moe.route(
        (h + model._attention(p["attn"], normed, None, False))[0],
        p["router"]["kernel"], p["router"]["bias"], 3, True, 1.0, score="softmax")
    assert not np.array_equal(idx, behind)
    assert not np.array_equal(idx, moe.route(
        normed[0] * 3.0, p["router"]["kernel"],
        p["router"]["bias"] * 0 + jnp.arange(8.0), 3, True, 1.0,
        score="softmax")[0])
    # scaling the input scales the logits: the gates sharpen, which a router
    # behind the sub-layer's norm could not see
    _, gates = moe.route(h[0], p["router"]["kernel"], p["router"]["bias"], 3,
                         True, 1.0, score="softmax")
    _, sharper = moe.route(4.0 * h[0], p["router"]["kernel"],
                           p["router"]["bias"], 3, True, 1.0, score="softmax")
    assert float(jnp.mean(jnp.max(sharper, -1))) > float(jnp.mean(jnp.max(gates, -1)))
    # the scopes' order in the compiled step: the router ahead of attention
    text = jax.jit(model.loss).lower(
        params, jnp.zeros((1, 16), jnp.int32)).as_text(debug_info=True)
    first = {scope: text.index(f"/{scope}/")
             for scope in ("moe_router", "attention", "moe_experts", "lm_head")}
    assert first["moe_router"] < first["attention"] < first["moe_experts"] \
        < first["lm_head"]
    assert "/layer_00/checkpoint/moe_router/" in text


def trainer_config(tmp_path, **more):
    _, overrides = tiny()
    return TrainConfig(
        model_arch="smallthinker", model_overrides=overrides, seq_len=40,
        batch_size=2, synthetic_samples=10, epochs=1, val_percent=20.0,
        learning_rate=3e-4, weight_decay=1e-8, faithful_loss_scaling=False,
        dtype="f32", metric_every_steps=1, checkpoint_dir=str(tmp_path / "ckpt"),
        log_dir=str(tmp_path / "logs"), loss_dir=str(tmp_path / "loss"),
        async_checkpoint=False, **more)


def test_trainer_reproduces_the_references_losses_and_counts(tmp_path):
    """Three steps through Trainer, the loader, the feed and SingleDevice
    on packed tokens give the reference's losses and balance the routers
    as it does; the counters reach the registry with the loss, a layer at
    a time, the attention pairs among them."""
    import reference
    from distributedpytorch_tpu.obs import defs as obsm
    from distributedpytorch_tpu.train.loop import Trainer

    config, _ = tiny()
    cfg = trainer_config(tmp_path)
    trainer = Trainer(cfg)
    assert trainer.counter_names == tuple(
        f"moe_{name}/{i}" for i in range(3) for name in moe.COUNTERS) + tuple(
            f"attention_pairs_computed/{i}" for i in range(3))
    assert trainer.attention_kernel_blocks == 0 == trainer.kept_activation_bytes
    assert trainer.moe_wgrad_kernel_layers == 0
    flat0 = {k: jnp.copy(v) for k, v in
             bench_weights.flat_names(trainer.state.params).items()}
    batches = list(trainer.train_loader.epoch_batches(0))[:3]
    routed0 = obsm.MOE_ROWS_ROUTED.labels(block="2").value
    pairs0 = obsm.ATTENTION_PAIRS_COMPUTED.labels(block="1").value
    result = trainer.train()
    assert result["steps"] == 4 and np.isnan(result["val_dice"])
    assert np.isfinite(result["val_loss"])
    mine = [row[2] for row in trainer.records.train_rows[:3]]
    assert obsm.MOE_ROWS_ROUTED.labels(block="2").value > routed0
    # the blocked path, one block of 40 queries against 40 keys, 2 sequences
    # of 4 heads, every step that was read back
    gained = obsm.ATTENTION_PAIRS_COMPUTED.labels(block="1").value - pairs0
    assert gained > 0 and gained % (2 * 4 * 40 * 40) == 0

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
    assert float(jnp.max(jnp.abs(biases["layer_01/router/bias"]))) > 0


def test_model_table_refuses_serving_and_meshes_and_bad_layouts():
    assert "smallthinker" in MODELS
    entry = model_entry("smallthinker")
    assert entry.batch.fields == ("tokens",) and entry.adam_b2 == 0.95
    assert not entry.servable and entry.single_device_only
    from distributedpytorch_tpu.serve.infer import load_inference_bundle

    with pytest.raises(ValueError, match="no prefill, decode or cache"):
        load_inference_bundle("x", model_arch="smallthinker")
    with pytest.raises(ValueError, match="expert exchange and sequence split"):
        from distributedpytorch_tpu.parallel import build_strategy
        build_strategy(TrainConfig(model_arch="smallthinker", train_method="DP"))
    with pytest.raises(ValueError, match="differ in length"):
        SmallThinker(smallthinker_config({"rope_layout": (0, 1)}))
    with pytest.raises(ValueError, match="neither 0 nor 1"):
        SmallThinker(smallthinker_config({"rope_layout": (0, 1, 2, 1)}))
    with pytest.raises(ValueError, match="softmax over the chosen"):
        SmallThinker(smallthinker_config(
            {"moe_primary_router_apply_softmax": False}))


def test_counters_say_what_the_attention_path_multiplies():
    """From the shapes, the tile and the window: the kernel's whole tiles
    where the shapes take it, the blocked path's slices elsewhere."""
    model = SmallThinker()
    tiles = 1024 * 1024
    assert model.attention_pairs(1, 16384, "tpu") == (
        28 * 136 * tiles, 28 * 70 * tiles, 28 * 70 * tiles, 28 * 70 * tiles)
    inside = sum(m.m * m.count for m in REF.matmul_layers(CONFIG, 16384, 1)
                 if m.name.endswith("/scores"))
    assert inside == 28 * (134_225_920 + 3 * 58_722_304)
    assert round(100 * (sum(model.attention_pairs(1, 16384, "tpu")) / inside - 1),
                 1) == 16.9
    assert model.attention_pairs(2, 8192, "tpu")[0] == 2 * 28 * 36 * tiles
    assert model.attention_kernel_blocks("tpu", 16384) == 4
    assert model.attention_kernel_blocks("cpu", 16384) == 0
    assert model.attention_kernel_blocks("tpu", 32768) == 0  # over VMEM's share
    assert model.moe_wgrad_kernel_layers("tpu", 16384) == 4
    assert model.moe_wgrad_kernel_layers("cpu", 16384) == 0
    config, overrides = tiny()
    toy = SmallThinker(smallthinker_config(overrides), jnp.float32)
    tokens = jnp.zeros((2, 43), jnp.int32)
    counters = jax.jit(lambda p: toy.hidden(p, tokens)[1])(
        toy.init(jax.random.key(0)))
    assert counters.shape == (len(toy.counter_names),) == (12,)
    assert np.array_equal(counters[-3:], [2 * 4 * 43 * 43] * 3)


# -- what the layers' recomputation keeps (smallthinker.KEPT_ACTIVATIONS) -----

def test_kept_activations_change_no_number(monkeypatch):
    """Loss, every gradient leaf, counters and biases with the named
    activations kept are those with each layer's input alone kept, and
    those with nothing recomputed and no barrier between a layer's
    gradients and its input's."""
    _, overrides = tiny()
    cfg = smallthinker_config(overrides)
    params = SmallThinker(cfg, jnp.float32).init(jax.random.key(1))
    tokens = jax.random.randint(jax.random.key(2), (2, 43), 0, 96)
    kept = loss_and_grads(SmallThinker(cfg, jnp.float32, memory_bytes=AMPLE),
                          params, tokens)
    bare = loss_and_grads(SmallThinker(cfg, jnp.float32), params, tokens)
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **kwargs: fn)
    monkeypatch.setattr(module, "gradients_before_input", lambda p, h: (p, h))
    plain = loss_and_grads(SmallThinker(cfg, jnp.float32), params, tokens)
    for other in (bare, plain):
        ((loss, (counters, biases)), grads) = other
        assert abs(float(kept[0][0]) - float(loss)) <= 1e-6 * float(loss)
        assert worst_leaf(bench_weights.flat_names(kept[1]),
                          bench_weights.flat_names(grads)) < 1e-5
        assert np.array_equal(kept[0][1][0], counters)
        assert jax.tree.all(jax.tree.map(np.array_equal, kept[0][1][1], biases))


def test_policy_keeps_the_routing_and_the_router_is_not_run_again(capsys):
    _, overrides = tiny()
    cfg = smallthinker_config(overrides)
    kept_model = SmallThinker(cfg, jnp.float32, memory_bytes=AMPLE)
    bare_model = SmallThinker(cfg, jnp.float32)
    params = bare_model.init(jax.random.key(1))
    tokens = jax.random.randint(jax.random.key(2), (2, 43), 0, 96)

    def residuals(model):
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(
            lambda p: model.loss(p, tokens)[0], params)
        return collections.Counter(
            line.split(" ", 1)[0]
            for line in capsys.readouterr().out.strip().splitlines())

    kept, bare = residuals(kept_model), residuals(bare_model)
    # every layer's gates and chosen experts (the latter for the gather's
    # backward and for the expert layer's: one array), in place of the
    # selection bias that only a router run again would read (blocked XLA
    # attention has no names: tests/test_attention_kernel.py holds the kernel's)
    assert sorted((kept - bare).elements()) == ["f32[86,3]"] * 3 + ["i32[86,3]"] * 6
    assert sorted((bare - kept).elements()) == ["f32[8]"] * 3
    assert kept_model.named_activation_bytes(2, 43, "cpu") == (2 * 4 * 86 * 3,) * 3
    assert kept_model.kept_activation_bytes(2, 43, "cpu") \
        == kept_model.named_activation_bytes(2, 43, "cpu")
    assert not any(bare_model.kept_activation_bytes(2, 43, "cpu"))
    assert set(KEPT_ACTIVATIONS) == {"moe_chosen", "moe_gates",
                                     *attention_pallas.RESIDUALS}

    # neither the router's product, its choice nor its softmax in the
    # backward pass's recomputation where the routing is kept (the gather's
    # index arithmetic is all that is left of the scope); all three where the
    # layer's input alone is
    def again(model):
        text = jax.jit(jax.grad(lambda p: model.loss(p, tokens)[0])).lower(
            params).compile().as_text()
        return [text.count(f"rematted_computation/moe_router/{op}")
                for op in ("td,de->te", "top_k", "exp")]
    assert again(kept_model) == [0, 0, 0]
    assert all(n >= 3 for n in again(bare_model))


@pytest.mark.parametrize("batch,seq_len,memory,kept", [
    (1, 16384, V5E, "KKKK"),    # the cell: every layer's, 2.4 GB to spare
    (3, 16384, V5E, "---K"),    # three times the tokens: from the last
    (1, 16384, None, "----"),   # no figure (the CPU): each layer's input alone
])
def test_layers_keep_their_names_from_the_last_while_the_budget_lasts(
        batch, seq_len, memory, kept):
    model = SmallThinker(dtype=jnp.bfloat16, memory_bytes=memory)
    named = model.named_activation_bytes(batch, seq_len, "tpu")
    # q, k, v, out in bf16, the log-sum-exp 8 sublanes deep, the routing
    layer = 16384 * ((2 * 28 + 2 * 4) * 128 * 2 + 28 * 8 * 4) + 2 * 4 * 16384 * 6
    assert layer == 283_901_952 and named == (layer * batch,) * 4
    assert model.kept_activation_bytes(batch, seq_len, "tpu") == tuple(
        n if k != "-" else 0 for n, k in zip(named, kept))
    budget = recompute.kept_budget(656_530_176, 32 * batch * seq_len * 2560, memory)
    assert sum(model.kept_activation_bytes(batch, seq_len, "tpu")) <= budget
