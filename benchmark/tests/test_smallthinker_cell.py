"""The cell ``smallthinker_train_packed16k`` and what it brought, found by
name through the loaders at a toy size on the CPU: its entries and files,
the walk of its matrix products with the pairs inside the window only, a
``--rehearse`` run that is sound, the planted faults that only this
configuration has (routed experts left out; the window ignored), and the
new reader on a made-up run. (``test_reference.py`` already runs every
cell of ``BENCHMARK.json``, this one among them, through the float32
agreement, the fp8 control and the faults ``unchanged``, ``half_batch``
and ``wrong_mask``.)"""

import functools
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import flops  # noqa: E402
import run as bench_run  # noqa: E402
from test_reference import rehearse, session  # noqa: E402

CELL = "smallthinker_train_packed16k"
CONFIG = flops.load_config("smallthinker_21b_a3b")
REF = flops.load_reference(CONFIG)
PEAK = json.load(open(os.path.join(HERE, "peaks.json")))["TPU v5 lite"]


@functools.lru_cache(maxsize=None)
def sound_line():
    return rehearse(CELL, 2147489010)


def entries(kind):
    return {e["name"]: e for e in bench_run.load_json(
        bench_run.ROOT, "BENCHMARK.json")[kind]}


def over_their_limits(checks):
    return {k for k, v in checks.items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}


def test_entries_name_the_cell_alone_and_find_their_readers():
    cell = entries("workloads")[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker_21b_a3b", "packed16k", 1)
    assert entries("configs")["smallthinker_21b_a3b"]["reduced"] \
        == CONFIG["reduced"] == ["rope_layout", "sliding_window_layout",
                                 "moe_num_primary_experts", "vocab_size"]
    readers = {"matmul_roofline." + CELL: "matmul_roofline.py",
               "attention_roofline." + CELL: "attention_roofline.py",
               "moe_padded_rows_pct." + CELL: "moe_padded_rows_pct.py",
               "moe_fullest_expert_pct." + CELL: "moe_fullest_expert_pct.py",
               "attention_masked_pairs_pct": "attention_masked_pairs_pct.py"}
    for name, file in readers.items():
        entry = entries("per_layer")[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_imgs_per_s"
        assert bench_run.load_reader(name).__spec__.origin == os.path.join(
            HERE, "layer_metrics", file)
    new = entries("per_layer")["attention_masked_pairs_pct"]
    assert (new["layer"], new["source"], new["better"], new["unit"]) == (
        "kernels", "program_counter", "lower", "%")
    # the accepted readers keep the cells they had
    for name in ("matmul_roofline", "attention_roofline", "moe_padded_rows_pct"):
        assert entries("per_layer")[name]["workloads"] == ["twotower_train_packed8k"]
    assert entries("per_layer")["moe_fullest_expert_pct"]["workloads"] == [
        "lfm2_train_packed8k"]


def test_cell_and_traffic_files_say_what_the_issue_asked_for():
    cell = bench_run.load_json(HERE, "workloads", CELL + ".json")
    assert cell["kind"] == "train_tokens" and cell["control"] == "fp8"
    assert cell["train_config"] == {
        "train_method": "singleGPU", "batch_size": 1, "prefetch_batches": 2,
        "num_workers": 0, "host_cache_mb": 64, "steps_per_dispatch": 1}
    assert cell["settle_steps"] == 24
    assert set(cell["limits"]) >= {"grad_diff_median", "grad_norm_gap",
                                   "change_norm_gap", "rows_repeated",
                                   "rows_altered"}
    assert CONFIG["train_config"]["seq_len"] == 16384 == CONFIG["sequence_length"]
    mix = bench_run.load_json(HERE, "traffic", "packed16k.json")
    assert {k: mix[k] for k in ("kind", "samples", "median", "sigma", "min_len",
                                "max_len")} == {
        "kind": "packed_documents", "samples": 48, "median": 512, "sigma": 1.25,
        "min_len": 16, "max_len": 32768}
    # the token count of packed8k: 96 sequences of 8192
    assert mix["samples"] * 16384 == 96 * 8192
    # the toy's window hides pairs: it is shorter than the toy's sequences
    toy = cell["rehearsal"]
    assert toy["config"]["sliding_window_size"] < toy["train_config"]["seq_len"]
    assert 1 in toy["config"]["sliding_window_layout"]
    assert 0 in toy["config"]["rope_layout"]


def test_walk_of_the_matrix_products_counts_the_pairs_inside_the_window():
    """Every weight matrix of the share is walked once, with its FLOPs:
    6 x tokens x parameters for a projection; the experts at the uniform
    share; attention at the pairs inside its masks."""
    tokens = 16384
    walk = {m.name: m for m in REF.matmul_layers(CONFIG, tokens, 1)}
    assert walk["layer_00/q"].train_flops == 6.0 * tokens * 2560 * 28 * 128
    assert walk["layer_02/router"].train_flops == 6.0 * tokens * 2560 * 64
    assert walk["head"].train_flops == 6.0 * tokens * 2560 * 37984
    up = walk["layer_03/experts_up"]
    assert (up.m, up.k, up.n, up.count) == (1536, 2560, 768, 16)
    full, window = walk["layer_00/scores"], walk["layer_01/scores"]
    assert full.m == tokens * (tokens + 1) // 2 == 134_225_920
    assert window.m == 4096 * 4097 // 2 + (tokens - 4096) * 4096 == 58_722_304
    assert (full.count, window.count) == (28, 28)
    assert walk["layer_03/values"].m == window.m
    assert REF.pairs_inside(4096, 4096) == REF.pairs_inside(4096) == 4096 * 4097 // 2
    per_sample = REF.train_flops_per_sample(CONFIG, tokens)
    assert per_sample == pytest.approx(sum(m.train_flops for m in walk.values()))
    assert round(per_sample / 1e12, 2) == 34.70
    attention = sum(m.train_flops for n, m in walk.items()
                    if n.endswith(("/scores", "/values")))
    routed = sum(m.train_flops for n, m in walk.items() if "/experts_" in n)
    assert round(attention / per_sample, 2) == 0.38
    assert round(routed / per_sample, 2) == 0.10
    # every matrix is in one product: all but the norms and the biases,
    # and the embedding, which is a lookup
    matrices = sum(m.k * m.n * m.count for m in walk.values() if m.weight)
    assert matrices == REF.param_count(CONFIG) - 37984 * 2560 - sum(
        v[0] for k, v in REF.param_shapes(CONFIG).items()
        if k.endswith(("scale", "bias")))
    least = REF.matmul_roofline_seconds(CONFIG, tokens, 1, PEAK)
    assert per_sample / PEAK["bf16_flops"] <= least < 0.18
    assert REF.matmul_roofline_seconds(
        CONFIG, tokens, 1, PEAK, routed_rows=16 * 1536) == least
    assert REF.train_flops_per_sample(CONFIG, tokens, routed_rows=0) \
        == pytest.approx(per_sample - routed)


def test_rehearsal_is_sound_and_counts_experts_and_pairs():
    line = sound_line()
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert {"routing_flips_pct", "biases_differ_pct"} <= set(line["checks"])
    assert set(line["metrics"]) == {"train_imgs_per_s", "setup_s"}
    counted = line["info"]["counters"]
    assert sorted(counted) == [
        "attention_pairs_computed", "moe_rows_computed", "moe_rows_max_expert",
        "moe_rows_routed", "steps"]
    assert counted["moe_rows_computed"] >= counted["moe_rows_routed"] > 0
    # three layers in the toy, every one sparse
    assert line["info"]["rows_routed_per_block_step"] == pytest.approx(
        counted["moe_rows_routed"] / counted["steps"] / 3)
    # the blocked path at 72 positions: one block a layer, 4 heads
    assert counted["attention_pairs_computed"] == counted["steps"] * 3 * 4 * 72 * 72


def test_routed_experts_left_out_come_out_not_correct():
    line = rehearse(CELL, 9, "--fault", "no_routed")
    assert line["correct"] is False
    assert "grad_norm_gap" in over_their_limits(line["checks"])


def test_window_ignored_comes_out_not_correct():
    """The reference with every layer causal over the whole sequence, put
    in the program's place (its mode ``no_window``), fails a limit; the
    reference itself in that place fails none."""
    ctx, driver, s, prog = session(CELL, 8)
    ref = driver.follow(ctx, s)
    rows = {k: prog[k] for k in ("rows_repeated", "rows_altered")}
    assert driver.judge(ctx, {**ref, **rows}, ref)["correct"]
    fault = driver.follow(ctx, s, mode="no_window")
    verdict = driver.judge(ctx, {**fault, **rows}, ref)
    assert not verdict["correct"]
    over = {name for name, value, limit in verdict["rows"]
            if limit is not None and not value <= limit}
    assert over & {"grad_norm_gap", "change_norm_gap", "grad_diff_median"}


def test_masked_pairs_reader_reads_the_counter_or_nothing():
    read = bench_run.load_reader("attention_masked_pairs_pct").read
    tiles = 1024 * 1024
    # the kernel at 16,384 positions: 136 tiles in the full layer, 70 in each
    # window layer, 28 heads, 18 steps
    computed = 18 * 28 * (136 + 3 * 70) * tiles
    run = {"counters": {"attention_pairs_computed": float(computed), "steps": 18},
           "config": CONFIG, "seq_len": 16384, "batch": 1, "chips": 1}
    inside = 28 * (134_225_920 + 3 * 58_722_304)
    assert read(run) == pytest.approx(100.0 * (computed / 18 / inside - 1.0))
    assert round(read(run), 1) == 16.9
    # a path that multiplies the pairs inside the masks and no other reads 0
    exact = {**run, "counters": {"attention_pairs_computed": float(18 * inside),
                                 "steps": 18}}
    assert read(exact) == pytest.approx(0.0, abs=1e-9)
    # the rehearsal's own line: the blocked path's one block a layer
    counted = sound_line()["info"]["counters"]
    cell = bench_run.load_json(HERE, "workloads", CELL + ".json")["rehearsal"]
    toy = {**CONFIG, **cell["config"],
           "deployment": {**CONFIG["deployment"], **cell["deployment"]}}
    got = read({"counters": counted, "config": toy, "seq_len": 72, "batch": 1,
                "chips": 1})
    window = 16 * 17 // 2 + (72 - 16) * 16
    assert got == pytest.approx(100.0 * (3 * 72 * 72 / (72 * 73 // 2 + 2 * window)
                                         - 1.0))
    # a program that counts nothing (the parent), no steps, and a
    # configuration whose reference has no walk, give nothing
    assert read({"config": CONFIG, "seq_len": 16384, "batch": 1, "chips": 1}) is None
    assert read({**run, "counters": {}}) is None
    assert read({**run, "counters": {"attention_pairs_computed": 5.0, "steps": 0}}) \
        is None
    assert read({**run, "config": flops.load_config("course_unet")}) is None
