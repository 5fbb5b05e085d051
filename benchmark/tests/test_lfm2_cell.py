"""The cell ``lfm2_train_packed8k`` and what it brought, found by name
through the loaders at a toy size on the CPU: the walk of its matrix
products, a ``--rehearse`` run that is sound, the fault that only a
configuration with routers has, the new reader, the weights' recipe of a
tied head. (``test_reference.py`` already runs every cell of
``BENCHMARK.json``, this one among them, through the float32 agreement,
the fp8 control and the faults ``unchanged``, ``half_batch`` and
``wrong_mask``.)"""

import functools
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import flops  # noqa: E402
import run as bench_run  # noqa: E402
from test_reference import rehearse  # noqa: E402

CELL = "lfm2_train_packed8k"
CONFIG = flops.load_config("lfm2_24b_a2b")
REF = flops.load_reference(CONFIG)
PEAK = json.load(open(os.path.join(HERE, "peaks.json")))["TPU v5 lite"]


@functools.lru_cache(maxsize=None)
def sound_line():
    return rehearse(CELL, 2147489010)


def entries(kind):
    return {e["name"]: e for e in bench_run.load_json(
        bench_run.ROOT, "BENCHMARK.json")[kind]}


def test_entries_name_the_cell_alone_and_find_their_readers():
    cell = entries("workloads")[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2_24b_a2b", "packed8k", 1)
    assert entries("configs")["lfm2_24b_a2b"]["reduced"] == CONFIG["reduced"] == [
        "layer_types", "num_experts", "vocab_size"]
    readers = {"matmul_roofline." + CELL: "matmul_roofline.py",
               "attention_roofline." + CELL: "attention_roofline.py",
               "moe_padded_rows_pct." + CELL: "moe_padded_rows_pct.py",
               "moe_fullest_expert_pct": "moe_fullest_expert_pct.py"}
    for name, file in readers.items():
        assert entries("per_layer")[name]["workloads"] == [CELL]
        assert bench_run.load_reader(name).__spec__.origin == os.path.join(
            HERE, "layer_metrics", file)
    # the accepted readers keep the one cell they had
    for name in ("matmul_roofline", "attention_roofline", "moe_padded_rows_pct"):
        assert entries("per_layer")[name]["workloads"] == ["twotower_train_packed8k"]


def test_walk_of_the_matrix_products():
    """Every weight matrix of the share is walked once, with its FLOPs:
    6 x tokens x parameters for a projection; the experts at the uniform
    share; attention at the causal pairs; the tied head once."""
    tokens = 8192
    walk = {m.name: m for m in REF.matmul_layers(CONFIG, tokens, 2)}
    t = 2 * tokens
    assert walk["layer_00/in_proj"].train_flops == 6.0 * t * 2048 * 6144
    assert walk["layer_00/ffn_gate"].train_flops == 6.0 * t * 2048 * 11776
    assert walk["head"].train_flops == 6.0 * t * 2048 * 16384
    up = walk["layer_02/experts_up"]
    assert (up.m, up.count) == (1024, 16)  # 16,384 tokens x 4 / 64
    scores = walk["layer_01/scores"]
    assert scores.train_flops == 6.0 * (tokens * (tokens + 1) // 2) * 64 * 2 * 32
    per_sample = REF.train_flops_per_sample(CONFIG, tokens)
    assert abs(2 * per_sample - sum(m.train_flops for m in walk.values())) \
        < 1e-6 * per_sample
    assert round(2 * per_sample / 1e12, 2) == 23.45
    routed = sum(m.train_flops for n, m in walk.items() if "/experts_" in n)
    assert 0.15 < routed / (2 * per_sample) < 0.17
    # a matrix in no product: the filters of the three-tap convolutions
    matrices = sum(m.k * m.n * m.count for m in walk.values() if m.weight)
    assert matrices == REF.param_count(CONFIG) - sum(
        int(np.prod(s)) for k, s in REF.param_shapes(CONFIG).items()
        if k.endswith(("scale", "bias", "conv/kernel")))
    least = REF.matmul_roofline_seconds(CONFIG, tokens, 2, PEAK)
    assert 2 * per_sample / PEAK["bf16_flops"] <= least < 0.13
    assert REF.matmul_roofline_seconds(
        CONFIG, tokens, 2, PEAK, routed_rows=16 * 1024) == least
    assert REF.train_flops_per_sample(CONFIG, tokens, routed_rows=0) \
        == pytest.approx(per_sample - routed / 2)


def test_rehearsal_is_sound_and_counts_its_experts():
    line = sound_line()
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert {"routing_flips_pct", "biases_differ_pct"} <= set(line["checks"])
    counted = line["info"]["counters"]
    assert sorted(counted) == [
        "moe_rows_computed", "moe_rows_max_expert", "moe_rows_routed", "steps"]
    assert counted["moe_rows_computed"] >= counted["moe_rows_routed"] > 0
    # two sparse layers in the toy
    assert line["info"]["rows_routed_per_block_step"] == pytest.approx(
        counted["moe_rows_routed"] / counted["steps"] / 2)


def test_routed_experts_left_out_come_out_not_correct():
    line = rehearse(CELL, 9, "--fault", "no_routed")
    assert line["correct"] is False
    over = {k for k, v in line["checks"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}
    assert "grad_norm_gap" in over


def test_fullest_expert_reader_reads_the_counters_or_nothing():
    read = bench_run.load_reader("moe_fullest_expert_pct").read
    counted = sound_line()["info"]["counters"]
    toy = {**CONFIG, "num_experts": 4}
    want = 100.0 * (counted["moe_rows_max_expert"] * 4
                    / counted["moe_rows_routed"] - 1.0)
    assert read({"counters": counted, "config": toy}) == pytest.approx(want)
    assert want >= 0
    balanced = {"moe_rows_routed": 16 * 1024.0, "moe_rows_max_expert": 1024.0}
    assert read({"counters": balanced, "config": CONFIG}) == pytest.approx(0.0)
    # a program that counts nothing (the parent), and a configuration whose
    # reference does not say how many experts are held, give nothing
    assert read({"config": CONFIG}) is None
    assert read({"counters": {}, "config": CONFIG}) is None
    assert read({"counters": balanced,
                 "config": flops.load_config("nemotron_twotower_30b_a3b")}) is None
    assert read({"counters": balanced,
                 "config": flops.load_config("course_unet")}) is None


def test_tied_weights_are_the_token_recipe_with_a_head_sized_embedding():
    import weights_tied
    import weights_tokens

    shapes = {k: v for k, v in REF.param_shapes(
        {**CONFIG, "hidden_size": 64, "vocab_size": 128, "intermediate_size": 96,
         "moe_intermediate_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 2}).items()}
    tied = weights_tied.make(shapes, 2147489030, CONFIG)
    plain = weights_tokens.make(shapes, 2147489030, CONFIG)
    assert tied.keys() == plain.keys() == shapes.keys()
    assert "head/kernel" not in tied
    for k in tied:
        scale = 64 ** -0.5 if k == "embed/embedding" else 1.0
        assert np.allclose(np.asarray(tied[k]), np.asarray(plain[k]) * scale), k
    assert abs(float(np.std(np.asarray(tied["embed/embedding"]))) * 8 - 1) < 0.05
    # a sub-layer's last product is divided by sqrt(2 x 40) besides
    down = np.asarray(tied["layer_00/ffn/down/kernel"])
    assert abs(float(np.std(down)) * (96 * 80) ** 0.5 - 1) < 0.05
    assert not np.asarray(tied["layer_01/ffn/router/bias"]).any()
