"""``BENCHMARK.json`` against the limits of its contract that can be
checked without a run, and against the files it names: every cell,
configuration, traffic mix and per-layer metric is a file of its own.
Every entry is found by its name and through the harness's own loaders
(``run.load_json``, ``flops.load_reference``, ``run.load_reader``), never
by its place in a list: a later PR appends entries
(``test_second_token_config.py`` runs these checks over such a file)."""

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import flops  # noqa: E402
import run as bench_run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "width",
               "head_dim", "expansion", "experts_per")


def bench():
    return bench_run.load_json(ROOT, "BENCHMARK.json")


def line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(b["command"]) <= 32 and all(line(w) for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    cells = 24
    assert (2 + 14 * cells) * (b["run_seconds"] + 60) + cells * 180 + 1200 <= 43200
    assert b["paths"] == ["benchmark"]


def test_configs():
    b = bench()
    used = {w["config"] for w in b["workloads"]}
    assert 1 <= len(b["configs"]) <= 24
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["name"] == c["name"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(w in key for w in WIDTH_WORDS)
        assert callable(flops.load_reference(data).param_shapes)
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])


def test_cells():
    b = bench()
    configs = {c["name"] for c in b["configs"]}
    assert 1 <= len(b["workloads"]) <= 24
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        pairs.add((w["config"], w["traffic"]))
        cell = bench_run.load_json(HERE, "workloads", w["name"] + ".json")
        assert os.path.exists(os.path.join(HERE, "drivers", cell["kind"] + ".py"))
        assert os.path.exists(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        assert all(isinstance(v, (int, float)) for v in cell["limits"].values())
    assert len(pairs) == len(b["workloads"])
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)


def test_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert 1 <= len(b["per_layer"]) <= 128
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES and line(m["layer"])
        assert callable(bench_run.load_reader(m["name"]).read)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(base, f), ROOT))
