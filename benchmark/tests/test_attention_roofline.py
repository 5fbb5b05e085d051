"""``attention_roofline`` on steps written by hand: the walk's attention
FLOPs over the peak against the time of the custom calls named for the
program's attention kernel, and nothing where no such call ran."""

import functools
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import flops  # noqa: E402
import run as bench_run  # noqa: E402
import trace_reduce as tr  # noqa: E402

CONFIG = flops.load_config("nemotron_twotower_30b_a3b")
PEAK = json.load(open(os.path.join(HERE, "peaks.json")))["TPU v5 lite"]
A, B = (0, "jit_step"), (1, "jit_step")


def reader():
    spec = importlib.util.spec_from_file_location("attention_roofline", os.path.join(
        HERE, "layer_metrics", "attention_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def a_run(ops):
    return {"trace": {"devices": {0: ops}, "host": [], "sync": (0.0, 100.0e9)},
            "window": {"traced": (100.0, 110.0)}, "rehearsal": False,
            "peak": PEAK, "config": CONFIG, "seq_len": 8192, "batch": 2,
            "chips": 1}


def a_step(program, t0, kernel_seconds):
    """Two forwards and a backward of the kernel among other operations
    of one run of the step program, ``kernel_seconds`` of them together."""
    third = kernel_seconds / 4
    return [
        tr.Op("fusion.1 bf16[2,8192,4096]", "convolution fusion", t0, t0 + 0.1, program),
        tr.Op("causal_attention_fwd.1 (bf16[2,2,16,8192,128], f32[2,2,16,8,8192])",
              "custom-call", t0 + 0.1, t0 + 0.1 + third, program),
        tr.Op("moe_tile.7 bf16[256,2688]", "custom-call", t0 + 0.2, t0 + 0.3, program),
        tr.Op("causal_attention_fwd.2 (bf16[2,2,16,8192,128], f32[2,2,16,8,8192])",
              "custom-call", t0 + 0.3, t0 + 0.3 + third, program),
        tr.Op("causal_attention_bwd.3 (bf16[2,2,16,8192,128], f32[2,2,8192,128])",
              "custom-call", t0 + 0.4, t0 + 0.4 + 2 * third, program),
        tr.Op("causal_attention_like.4 f32[8]", "loop fusion", t0 + 0.6, t0 + 0.7, program),
    ]


def test_share_is_the_walks_attention_flops_over_the_kernels_time(monkeypatch):
    monkeypatch.setattr(tr, "step_programs",
                        functools.partial(tr.step_programs, min_ops=1))
    mod = reader()
    # 8192 x 8193 / 2 causal pairs, 2 sequences x 32 heads, head 128, two
    # products, forward + both gradients: 3.30 TFLOP, 16.7 ms at the peak
    pairs = 8192 * 8193 // 2
    least = 3 * 2 * (2.0 * pairs * 128) * 2 * 32 / PEAK["bf16_flops"]
    assert least == pytest.approx(0.01674, rel=1e-3)
    run = a_run(a_step(A, 1.0, 0.040) + a_step(B, 3.0, 0.044))
    assert mod.read(run) == pytest.approx(100 * least / 0.042)
    # only custom calls of that name count
    assert [mod.is_attention_kernel(o) for o in a_step(A, 1.0, 0.04)] == [
        False, True, False, True, True, False]


def test_nothing_to_read_where_no_kernel_ran(monkeypatch):
    monkeypatch.setattr(tr, "step_programs",
                        functools.partial(tr.step_programs, min_ops=1))
    read = reader().read
    # the parent's step: attention is output and loop fusions like the rest
    parent = [o for o in a_step(A, 1.0, 0.04) if "causal_attention" not in o.name]
    assert read(a_run(parent)) is None
    assert read({"rehearsal": True, "peak": None}) is None
    # no traced window, and a configuration whose reference walks no products
    assert read({**a_run(a_step(A, 1.0, 0.04)), "trace": None}) is None
    unet = {**a_run(a_step(A, 1.0, 0.04)),
            "config": flops.load_config("course_unet")}
    del unet["seq_len"]
    assert read(unet) is None


def test_benchmark_json_lists_the_metric_for_the_token_cell_alone():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    entry, = (m for m in bench["per_layer"] if m["name"] == "attention_roofline")
    assert entry == {"name": "attention_roofline", "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "train_imgs_per_s",
                     "workloads": ["twotower_train_packed8k"]}
