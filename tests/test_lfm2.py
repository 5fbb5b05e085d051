"""The LFM2 token model (models/lfm2.py, ops/sequence.short_conv, the gated
``ops/moe.held_experts``) against its plain reference
(benchmark/references/lfm2_24b_a2b.py) at a small size on the CPU: seeded
random weights, widths shrunk here and nowhere else."""

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
import weights_tied  # noqa: E402

from distributedpytorch_tpu.config import TrainConfig  # noqa: E402
from distributedpytorch_tpu.models import MODELS, model_entry  # noqa: E402
from distributedpytorch_tpu.models import recompute  # noqa: E402
from distributedpytorch_tpu.models.lfm2 import (  # noqa: E402
    KEPT_ACTIVATIONS,
    LFM2_24B_A2B_SHARE,
    Lfm2,
    Lfm2Config,
    lfm2_config,
)
from distributedpytorch_tpu.ops import attention_pallas, moe, sequence as seq  # noqa: E402

TINY = dict(hidden_size=64, vocab_size=96, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=160,
            moe_intermediate_size=32, num_experts=4, num_experts_per_tok=3)
CONFIG = flops.load_config("lfm2_24b_a2b")
REF = flops.load_reference(CONFIG)
#: dense + full_attention + conv, as published layers 0, 2, 3
PATTERN = (("conv", "full_attention", "conv"), (0, 2, 3))


def tiny(types=PATTERN[0], held=PATTERN[1], experts_total=8, first_held=2,
         **more):
    """(reference's configuration dict, the program's overrides): 4 of 8
    experts held from a non-zero ``first_held``."""
    # a balancing rate that moves the choice within three steps
    sizes = {**TINY, "layer_types": list(types),
             "router_bias_update_rate": 0.05, **more}
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
    flat = weights_tied.make(REF.param_shapes(config), seed, config)
    flat = {k: (0.3 * jnp.sin(jnp.arange(v.size, dtype=jnp.float32)).reshape(v.shape)
                if k.endswith("router/bias") else v) for k, v in flat.items()}
    return flat, bench_weights.to_program(
        flat, jax.eval_shape(model.init, jax.random.key(0)))


@pytest.mark.parametrize("types,held", [
    (("conv",), (0,)),                      # dense feed-forward alone
    (("full_attention",), (2,)),            # attention + experts
    (("conv",), (3,)),                      # short convolution + experts
    PATTERN,
])
def test_program_agrees_with_reference_logits_loss_every_gradient(types, held):
    config, overrides = tiny(types, held)
    model = Lfm2(lfm2_config(overrides), jnp.float32)
    flat, params = seeded(config, model)
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
    mine = bench_weights.flat_names(grads)
    assert mine.keys() == ref_grads.keys()
    assert worst_leaf(mine, ref_grads) < 1e-3
    # the tied matrix takes the head's gradient and the lookup's
    assert float(jnp.linalg.norm(ref_grads["embed/embedding"])) > 0
    # the routers' balancing: every bias moved by the rate, as the reference's
    ref_biases = REF.balanced_biases(config, flat, loads)
    assert bench_weights.flat_names(biases).keys() == ref_biases.keys()
    assert len(ref_biases) == REF.expert_blocks(config)
    for k, b in bench_weights.flat_names(biases).items():
        assert float(jnp.max(jnp.abs(b - ref_biases[k]))) == 0.0
        moved = np.abs(np.asarray(b - flat[k]))
        assert np.allclose(moved[moved > 0], config["router_bias_update_rate"])
        assert (moved > 0).sum() >= 6  # an expert exactly at the mean stays


def test_published_share_counts_its_parameters_and_the_uncut_model():
    shapes = jax.eval_shape(Lfm2().init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 788_052_352
    assert REF.param_count(CONFIG) == 788_052_352 == CONFIG["parameters"]
    assert {k: tuple(v.shape) for k, v in bench_weights.flat_names(shapes).items()} \
        == {k: tuple(v) for k, v in REF.param_shapes(CONFIG).items()}
    uncut = REF.param_count(REF.published(CONFIG))
    assert uncut == 23_843_661_440 == CONFIG["published"]["parameters"]
    pub = CONFIG["published"]["layer_types"]
    assert len(pub) == 40 == CONFIG["num_hidden_layers"]
    assert pub.count("full_attention") == 10 and pub[2] == "full_attention"
    # the layers held: the leading dense layer once, and one whole period
    held = CONFIG["deployment"]["layers_held"]
    assert [pub[i] for i in held] == CONFIG["layer_types"] == list(
        LFM2_24B_A2B_SHARE.layer_types)
    assert LFM2_24B_A2B_SHARE.dense_layers == (True, False, False, False, False)
    assert LFM2_24B_A2B_SHARE.head_dim == 64
    assert Lfm2Config(**{k: tuple(v) if isinstance(v, list) else v for k, v in
                         REF.program_overrides(CONFIG).items()}) \
        == dataclasses.replace(LFM2_24B_A2B_SHARE, router_bias_update_rate=0.01)


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


def test_program_overrides_are_the_programs_size_keys_that_the_file_has():
    fields = {f.name for f in dataclasses.fields(Lfm2Config)}
    out = REF.program_overrides(CONFIG)
    assert set(out) <= fields
    assert set(REF.PROGRAM_KEYS) == {k for k in fields if k in CONFIG}
    assert (out["experts_total"], out["first_held"], out["layer_indices"],
            out["rope_theta"]) == (64, 0, [0, 2, 3, 4, 5], 1_000_000)
    assert REF.expert_blocks(CONFIG) == 4 and REF.held_experts(CONFIG) == 16


def test_four_shares_with_the_router_counted_once_make_the_uncut_expert_layer():
    """Each share routes over all 8 experts and computes its own 2; what
    the shares give adds up to what the reference gives with every expert
    held. The router is whole on every share: its choices are the same
    on each, and every (token, slot) choice lands on one share."""
    config, _ = tiny(("conv",), (3,), experts_total=8, first_held=0,
                     num_experts=8)
    whole = weights_tied.make(REF.param_shapes(config), 3, config)
    whole = {k[len("layer_00/ffn/"):]: v for k, v in whole.items()
             if k.startswith("layer_00/ffn/")}
    whole["router/bias"] = 0.2 * jnp.cos(jnp.arange(8.0))
    x = jax.random.normal(jax.random.key(4), (1, 50, 64))
    uncut, chosen = REF.experts(REF.Ops(), config, whole, x[0])
    total, counted = 0.0, 0.0
    for share in range(4):
        model = Lfm2(lfm2_config({
            **TINY, "num_experts": 2, "experts_total": 8,
            "first_held": 2 * share}), jnp.float32)
        held = slice(2 * share, 2 * share + 2)
        p = {"router": {"kernel": whole["router/kernel"],
                        "bias": whole["router/bias"]},
             "experts": {name: {"kernel": whole[f"experts/{name}/kernel"][held]}
                         for name in ("gate", "up", "down")}}
        y, counters, idx, _ = jax.jit(model._experts)(p, x)
        assert np.array_equal(np.sort(idx, -1), np.sort(chosen, -1))
        total = total + y[0]
        counted += float(counters[0])
    assert float(jnp.max(jnp.abs(total - uncut))) < 1e-4
    assert counted == 50 * 3


def dense_experts(x, idx, gates, w_up, w_down, w_gate, first_held):
    """Every held expert over every row, gate 0 where it was not chosen:
    no gather, no tiles."""
    y = 0.0
    for e in range(w_up.shape[0]):
        gate = jnp.sum(jnp.where(idx == first_held + e, gates, 0.0), -1)
        act = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e]) if w_gate is not None
               else jnp.square(jnp.maximum(x @ w_up[e], 0)))
        y = y + gate[:, None] * (act @ w_down[e])
    return y


@pytest.mark.parametrize("gated", [True, False])
def test_held_experts_forward_and_backward_are_the_dense_computation(gated):
    """Under a routing that leaves one held expert empty and gives another
    more than two tiles, in float32: the result, and the gradient to the
    tokens, the gates and every matrix."""
    t, d, f, first = 60, 16, 8, 4
    keys = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(keys[0], (t, d))
    # experts 4..6 are held: 5 gets every token (60 rows, tile 8: eight
    # tiles), 6 none, 4 a few; slot 2 falls on experts not held
    idx = jnp.stack([jnp.full((t,), 5), jnp.where(jnp.arange(t) < 5, 4, 9),
                     jnp.arange(t) % 3 + 10], -1).astype(jnp.int32)
    gates = jax.random.uniform(keys[1], (t, 3))
    w_up = jax.random.normal(keys[2], (3, d, f))
    w_down = jax.random.normal(keys[3], (3, f, d))
    w_gate = jax.random.normal(keys[4], (3, d, f)) if gated else None
    weight = jax.random.normal(keys[5], (t, d))
    tile = moe.tile_rows(t, 3, 16)
    assert tile == 8 and -(-t // tile) > 2

    def mine(x, gates, w_up, w_down, w_gate=None):
        y, counters = moe.held_experts(x, idx, gates, w_up, w_down, 16, first,
                                       w_gate=w_gate)
        return jnp.sum(y * weight), (y, counters)

    def dense(x, gates, w_up, w_down, w_gate=None):
        y = dense_experts(x, idx, gates, w_up, w_down, w_gate, first)
        return jnp.sum(y * weight), y

    args = (x, gates, w_up, w_down) + ((w_gate,) if gated else ())
    argnums = tuple(range(len(args)))
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
    assert [float(c) for c in counters] == [65, 64 + 8, 60]


def test_short_convolution_is_a_convolution_per_channel():
    """``C * conv(B * x)`` against ``jnp.convolve`` channel by channel, and
    its own backward pass against jax's of that."""
    length, d, taps = 37, 5, 3
    keys = jax.random.split(jax.random.key(0), 3)
    bcx = jax.random.normal(keys[0], (2, length, 3 * d))
    kernel = jax.random.normal(keys[1], (taps, d))
    weight = jax.random.normal(keys[2], (2, length, d))

    def plain(bcx, kernel):
        b, c, x = jnp.split(bcx, 3, -1)
        bx = b * x
        # tap j sees the input taps - 1 - j back: the reversed filter,
        # cut to the length
        conv = jnp.stack([jnp.stack([
            jnp.convolve(bx[i, :, ch], kernel[::-1, ch])[:length]
            for ch in range(d)], -1) for i in range(2)])
        return c * conv

    assert float(jnp.max(jnp.abs(seq.short_conv(bcx, kernel)
                                 - plain(bcx, kernel)))) < 1e-5
    mine, want = (jax.grad(lambda a, b: jnp.sum(fn(a, b) * weight),
                           argnums=(0, 1))(bcx, kernel)
                  for fn in (seq.short_conv, plain))
    for a, b in zip(mine, want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
    # the first position sees itself alone through the last tap
    b, c, x = jnp.split(bcx, 3, -1)
    first = c[:, 0] * b[:, 0] * x[:, 0] * kernel[-1]
    assert float(jnp.max(jnp.abs(seq.short_conv(bcx, kernel)[:, 0] - first))) < 1e-5
    assert seq.short_conv(bcx.astype(jnp.bfloat16), kernel).dtype == jnp.bfloat16


def trainer_config(tmp_path, **more):
    _, overrides = tiny()
    return TrainConfig(
        model_arch="lfm2", model_overrides=overrides, seq_len=40, batch_size=2,
        synthetic_samples=10, epochs=1, val_percent=20.0, learning_rate=3e-4,
        weight_decay=1e-8, faithful_loss_scaling=False, dtype="f32",
        metric_every_steps=1, checkpoint_dir=str(tmp_path / "ckpt"),
        log_dir=str(tmp_path / "logs"), loss_dir=str(tmp_path / "loss"),
        async_checkpoint=False, **more)


def test_trainer_reproduces_the_references_losses_and_counts(tmp_path):
    """Three steps through Trainer, the loader, the feed and SingleDevice
    on packed tokens give the reference's losses and balance the routers
    as it does; the counters reach the registry with the loss, a layer at
    a time."""
    import reference
    from distributedpytorch_tpu.obs import defs as obsm
    from distributedpytorch_tpu.train.loop import Trainer

    config, _ = tiny()
    cfg = trainer_config(tmp_path)
    trainer = Trainer(cfg)
    assert trainer.counter_names == (
        "moe_rows_routed/1", "moe_rows_computed/1", "moe_rows_max_expert/1",
        "moe_rows_routed/2", "moe_rows_computed/2", "moe_rows_max_expert/2")
    assert trainer.attention_kernel_blocks == 0 == trainer.kept_activation_bytes
    flat0 = {k: jnp.copy(v) for k, v in
             bench_weights.flat_names(trainer.state.params).items()}
    batches = list(trainer.train_loader.epoch_batches(0))[:3]
    routed0 = obsm.MOE_ROWS_ROUTED.labels(block="2").value
    result = trainer.train()
    assert result["steps"] == 4 and np.isnan(result["val_dice"])
    assert np.isfinite(result["val_loss"])
    mine = [row[2] for row in trainer.records.train_rows[:3]]
    assert obsm.MOE_ROWS_ROUTED.labels(block="2").value > routed0

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
    assert float(jnp.max(jnp.abs(biases["layer_01/ffn/router/bias"]))) > 0


def test_model_table_names_its_models_and_refuses_serving_and_meshes():
    assert set(MODELS) == {"unet", "milesial", "twotower", "lfm2",
                           "smallthinker"}
    entry = model_entry("lfm2")
    assert entry.batch.fields == ("tokens",) and entry.adam_b2 == 0.95
    assert not entry.servable and entry.single_device_only
    from distributedpytorch_tpu.serve.infer import load_inference_bundle

    with pytest.raises(ValueError, match="token model"):
        load_inference_bundle("x", model_arch="lfm2")
    with pytest.raises(ValueError, match="one device"):
        from distributedpytorch_tpu.parallel import build_strategy
        build_strategy(TrainConfig(model_arch="lfm2", train_method="DP"))
    names = Lfm2(lfm2_config({"layer_types": ("conv", "conv", "full_attention"),
                              "layer_indices": (1, 2, 3)})).counter_names
    assert names[:3] == ("moe_rows_routed/1", "moe_rows_computed/1",
                         "moe_rows_max_expert/1") and len(names) == 6
    with pytest.raises(ValueError, match="unknown layer types"):
        Lfm2(lfm2_config({"layer_types": ("mamba",), "layer_indices": (0,)}))


def test_scopes_name_the_compiled_step():
    _, overrides = tiny()
    model = Lfm2(lfm2_config(overrides), jnp.float32)
    params = model.init(jax.random.key(0))
    text = jax.jit(model.loss).lower(
        params, jnp.zeros((1, 16), jnp.int32)).as_text(debug_info=True)
    for scope in ("layer_00", "layer_01", "layer_02", "short_conv", "dense_ffn",
                  "attention", "moe_router", "moe_experts", "lm_head"):
        assert f"/{scope}/" in text, scope
    assert "/layer_00/dense_ffn/" in text and "/layer_01/checkpoint/moe_experts/" in text


# -- what the layers' recomputation keeps (lfm2.KEPT_ACTIVATIONS) ------------

#: A device's memory beside which every toy size fits.
AMPLE = 16 << 30
V5E = 16_909_336_064  # memory_stats()["bytes_limit"] of one v5e chip


def loss_and_grads(model, params, tokens):
    return jax.jit(jax.value_and_grad(
        lambda p, t: (lambda loss, *rest: (loss, rest))(*model.loss(p, t)),
        has_aux=True))(params, tokens)


def test_kept_activations_and_the_tied_gradients_change_no_number(monkeypatch):
    """Loss, every gradient leaf, counters and biases with the named
    activations kept are those with each layer's input alone kept, and
    those with nothing recomputed and no barrier between a layer's
    gradients and its input's."""
    _, overrides = tiny()
    cfg = lfm2_config(overrides)
    params = Lfm2(cfg, jnp.float32).init(jax.random.key(1))
    tokens = jax.random.randint(jax.random.key(2), (2, 43), 0, 96)
    kept = loss_and_grads(Lfm2(cfg, jnp.float32, memory_bytes=AMPLE), params, tokens)
    bare = loss_and_grads(Lfm2(cfg, jnp.float32), params, tokens)
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **kwargs: fn)
    from distributedpytorch_tpu.models import lfm2 as module
    monkeypatch.setattr(module, "gradients_before_input", lambda p, h: (p, h))
    plain = loss_and_grads(Lfm2(cfg, jnp.float32), params, tokens)
    for other in (bare, plain):
        ((loss, (counters, biases)), grads) = other
        assert abs(float(kept[0][0]) - float(loss)) <= 1e-6 * float(loss)
        assert worst_leaf(bench_weights.flat_names(kept[1]),
                          bench_weights.flat_names(grads)) < 1e-5
        assert np.array_equal(kept[0][1][0], counters)
        assert jax.tree.all(jax.tree.map(np.array_equal, kept[0][1][1], biases))


def test_policy_keeps_the_convolutions_first_product_and_nothing_else(capsys):
    import collections

    _, overrides = tiny()
    cfg = lfm2_config(overrides)
    kept_model = Lfm2(cfg, jnp.float32, memory_bytes=AMPLE)
    bare_model = Lfm2(cfg, jnp.float32)
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
    assert not bare - kept
    # the two conv layers' in_proj results (blocked XLA attention has no
    # names: tests/test_attention_kernel.py holds the kernel's)
    assert sorted((kept - bare).elements()) == ["f32[2,43,192]"] * 2
    assert kept_model.named_activation_bytes(2, 43, "cpu") == (
        2 * 43 * 192 * 4, 0, 2 * 43 * 192 * 4)
    assert kept_model.kept_activation_bytes(2, 43, "cpu") \
        == kept_model.named_activation_bytes(2, 43, "cpu")
    assert not any(bare_model.kept_activation_bytes(2, 43, "cpu"))
    assert set(KEPT_ACTIVATIONS) == {"conv_in_proj", *attention_pallas.RESIDUALS}


@pytest.mark.parametrize("batch,memory,kept", [
    (2, V5E, "CACCC"),       # the cell: every layer's, 1.0 GB to spare
    (3, V5E, "-ACCC"),       # half as many tokens again: from the last
    (2, None, "-----"),      # no figure (the CPU): each layer's input alone
])
def test_layers_keep_their_names_from_the_last_while_the_budget_lasts(
        batch, memory, kept):
    model = Lfm2(dtype=jnp.bfloat16, memory_bytes=memory)
    named = model.named_activation_bytes(batch, 8192, "tpu")
    cell = {"C": 201_326_592, "A": 184_549_376}
    assert named == tuple(cell[k] * batch // 2 for k in "CACCC")
    assert model.kept_activation_bytes(batch, 8192, "tpu") == tuple(
        n if k != "-" else 0 for n, k in zip(named, kept))
    assert model.attention_kernel_blocks("tpu", 8192) == 1
    assert model.attention_kernel_blocks("cpu", 8192) == 0
    budget = recompute.kept_budget(788_052_352, 32 * batch * 8192 * 2048, memory)
    assert sum(model.kept_activation_bytes(batch, 8192, "tpu")) <= budget


def test_gradients_before_input_is_an_identity_with_a_barrier_behind_it():
    p, h = {"w": jnp.arange(3.0)}, jnp.ones((2, 3))
    out = recompute.gradients_before_input(p, h)
    assert jax.tree.all(jax.tree.map(np.array_equal, out, (p, h)))
    fn = lambda p, h: jnp.sum(  # noqa: E731
        (lambda q, x: x * q["w"])(*recompute.gradients_before_input(p, h)))
    grads = jax.grad(fn, argnums=(0, 1))(p, h)
    assert np.array_equal(grads[0]["w"], [2.0, 2.0, 2.0])
    assert np.array_equal(grads[1], np.tile(np.arange(3.0), (2, 1)))
    text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1)))(p, h))
    assert "optimization_barrier" in text
    assert recompute.keep_from_last((5, 3, 4), 8) == (0, 3, 4)
    assert recompute.keep_from_last((5, 3, 4), 3) == (0, 3, 0)
