"""The token configuration's own pieces at a toy size on the CPU: the
walk of its matrix products and parameters, the traffic generator, the
planted fault that only this driver has, and the two readers this
configuration brings. (``test_reference.py`` already runs every cell of
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
import traffic_tokens  # noqa: E402
from test_reference import rehearse  # noqa: E402

CELL = "twotower_train_packed8k"
CONFIG = flops.load_config("nemotron_twotower_30b_a3b")
REF = flops.load_reference(CONFIG)
PEAK = json.load(open(os.path.join(HERE, "peaks.json")))["TPU v5 lite"]
#: What a run of the cell prints, by name, as PR 31's harness printed it:
#: a harness that takes other token models may not change what this one shows.
CHECKS = ["grad_diff_median", "loss_gap_step3", "grad_norm_gap", "change_norm_gap",
          "rows_repeated", "rows_altered", "loss_gap_step1", "loss_gap_step2",
          "grad_norm_gap_median", "change_norm_gap_median", "grad_diff_worst",
          "routing_flips_pct", "biases_differ_pct"]
INFO = ["cache_hits", "compile_s", "compiles_before_window", "counters", "epochs",
        "memory", "reference_s", "routing_flips_pct", "rows_routed_per_block_step",
        "sequences", "steps", "tokens_per_s", "window_s", "worst_leaf"]


@functools.lru_cache(maxsize=None)
def sound_line():
    return rehearse(CELL, 2147489010)


def test_walk_counts_the_cut_and_the_published_tower():
    assert REF.param_count(CONFIG) == 666_963_456 == CONFIG["parameters"]
    uncut = REF.param_count(REF.published(CONFIG))
    assert uncut == 31_577_940_288 == CONFIG["published"]["parameters"]
    assert round(uncut / 1e9, 1) == 31.6
    assert len(CONFIG["published"]["hybrid_override_pattern"]) == 52 \
        == CONFIG["num_hidden_layers"]


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


def test_walk_of_the_matrix_products():
    """Every weight matrix of the share is walked once, with its FLOPs:
    6 x tokens x parameters for a projection; the experts at the uniform
    share; attention at the causal pairs."""
    tokens = 8192
    walk = {m.name: m for m in REF.matmul_layers(CONFIG, tokens, 2)}
    t = 2 * tokens
    assert walk["block_00/in_proj"].train_flops == 6.0 * t * 2688 * 10304
    assert walk["head"].train_flops == 6.0 * t * 2688 * 16384
    up = walk["block_01/experts_up"]
    assert (up.m, up.count) == (768, 8)  # 16,384 tokens x 6 / 128
    scores = walk["block_05/scores"]
    assert scores.train_flops == 6.0 * (tokens * (tokens + 1) // 2) * 128 * 2 * 32
    per_sample = REF.train_flops_per_sample(CONFIG, tokens)
    assert abs(2 * per_sample - sum(m.train_flops for m in walk.values())) \
        < 1e-6 * per_sample
    routed = sum(m.train_flops for n, m in walk.items() if "/experts_" in n)
    assert 0.03 < routed / (2 * per_sample) < 0.05
    least = REF.matmul_roofline_seconds(CONFIG, tokens, 2, PEAK)
    assert 2 * per_sample / PEAK["bf16_flops"] <= least < 0.25
    # the routed experts over the rows a run counted, not the uniform share
    assert REF.matmul_roofline_seconds(
        CONFIG, tokens, 2, PEAK, routed_rows=8 * 768) == least
    none = {m.name: m for m in REF.matmul_layers(CONFIG, tokens, 2, routed_rows=0)}
    assert none["block_01/experts_up"].train_flops == 0.0
    assert REF.train_flops_per_sample(CONFIG, tokens, routed_rows=0) \
        == pytest.approx(per_sample - routed / 2)


def test_packed_documents_from_the_seed():
    mix = {"kind": "packed_documents", "samples": 6, "median": 50, "sigma": 1.25,
           "min_len": 16, "max_len": 400}
    a = traffic_tokens.build(mix, 128, 96, 2147489001)
    b = traffic_tokens.build(mix, 128, 96, 2147489001)
    c = traffic_tokens.build(mix, 128, 96, 2147489002)
    assert np.array_equal(a.sequences, b.sequences)
    assert not np.array_equal(a.sequences, c.sequences)
    assert a.sequences.shape == (6, 128) and a.sequences.dtype == np.int32
    stream = a.sequences.ravel()
    assert stream.max() == 95 and stream.min() >= 0
    ends = np.flatnonzero(stream == 95)
    lengths = np.diff(np.concatenate([[-1], ends])) - 1
    assert lengths.min() >= 16 and lengths.max() <= 400
    # Zipf(1.0): the most frequent id is the first, about twice the second
    counts = np.bincount(stream[stream < 95], minlength=95)
    assert counts.argmax() == 0 and counts[0] > 1.4 * counts[1]
    batches = [{"tokens": a.sequences[[4, 1]]}, {"tokens": a.sequences[[0, 5]].copy()}]
    rows, altered = traffic_tokens.same_rows(b, batches)
    assert altered == 0 and traffic_tokens.rows_repeated(batches) == 0
    batches[1]["tokens"][0, 100] += 1
    rows, altered = traffic_tokens.same_rows(b, batches)
    assert altered == 1 and np.array_equal(rows[1][0], a.sequences[0])
    assert traffic_tokens.rows_repeated(batches + batches[:1]) == 2


def test_routed_experts_left_out_come_out_not_correct():
    line = rehearse(CELL, 9, "--fault", "no_routed")
    assert line["correct"] is False
    over = {k for k, v in line["checks"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}
    assert "grad_norm_gap" in over


def test_rehearsal_prints_the_checks_and_info_it_printed_before():
    line = sound_line()
    assert line["correct"] is True
    assert list(line["checks"]) == CHECKS and list(line)[-1] == "checks"
    assert sorted(line["info"]) == INFO
    assert sorted(line["info"]["counters"]) == [
        "moe_rows_computed", "moe_rows_max_expert", "moe_rows_routed", "steps"]


def test_program_overrides_are_the_programs_size_keys_that_the_file_has():
    """The reference module lists the keys by name (it imports nothing of
    the program); the program's own dataclass says whether one is missing."""
    import dataclasses

    from distributedpytorch_tpu.models.twotower import TwoTowerConfig

    fields = {f.name for f in dataclasses.fields(TwoTowerConfig)}
    out = REF.program_overrides(CONFIG)
    assert set(out) == {k for k in fields if k in CONFIG} | {
        "experts_total", "first_held"}
    assert (out["experts_total"], out["first_held"], out["norm_eps"]) == (
        128, 0, CONFIG["layer_norm_epsilon"])
    assert all(out[k] == CONFIG[k] for k in REF.PROGRAM_KEYS)
    assert REF.expert_blocks(CONFIG) == 4
    # the published share, but for the balancing rate the cell states
    assert TwoTowerConfig(**out) == dataclasses.replace(
        TwoTowerConfig(), router_bias_update_rate=0.01)


def test_rehearsal_reports_counters_and_both_new_readers(monkeypatch):
    line = sound_line()
    counted = line["info"]["counters"]
    assert counted["moe_rows_computed"] >= counted["moe_rows_routed"] > 0
    assert 0 <= line["checks"]["routing_flips_pct"]["value"] < 20
    assert 0 <= line["checks"]["biases_differ_pct"]["value"] < 20
    assert line["info"]["rows_routed_per_block_step"] == pytest.approx(
        counted["moe_rows_routed"] / counted["steps"] / 2)
    import importlib.util

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(HERE, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    run = {"counters": counted}
    want = 100.0 * (counted["moe_rows_computed"] / counted["moe_rows_routed"] - 1)
    assert reader("moe_padded_rows_pct")(run) == pytest.approx(want)
    # a program that counts nothing (the parent) gives nothing to read
    assert reader("moe_padded_rows_pct")({}) is None
    assert reader("matmul_roofline")({"rehearsal": True, "peak": None}) is None
    unet = {"rehearsal": False, "peak": PEAK,
            "config": flops.load_config("course_unet")}
    assert reader("matmul_roofline")(unet) is None
