"""A second token configuration is files and entries, never an edit.

What a later PR may do to the benchmark is add files and append entries.
Here that is done to a copy of ``BENCHMARK.json`` in memory, with files
that lie under ``tests/data/`` (the loaders are monkeypatched to look
there first; ``run.py`` gets no flag for it): one more configuration
(``tower_no_experts``: the program's token model with Mamba-2 and
attention blocks alone, no router, no ``deployment``), one more cell (ONE
sequence a step, no ``settle_steps``) and one more per-layer entry
(``matmul_roofline.extra``, read by ``layer_metrics/matmul_roofline.py``).
The accepted checks hold over that file, and the cell goes through
``drivers/train_tokens.py`` unedited: correct when sound, not correct
under each fault it can have.
"""

import copy
import inspect
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(HERE, "tests", "data")
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import flops  # noqa: E402
import run as bench_run  # noqa: E402
import test_attention_roofline  # noqa: E402
import test_contract  # noqa: E402
from test_reference import rehearse, session  # noqa: E402

CONFIG, CELL, METRIC = "tower_no_experts", "tower_no_experts_one_seq", \
    "matmul_roofline.extra"
ROUTER_ROWS = {"routing_flips_pct", "biases_differ_pct"}


@pytest.fixture
def appended(monkeypatch):
    """``BENCHMARK.json`` with the three entries appended, and loaders
    that find their files under ``tests/data/``."""
    real_json, real_reference = bench_run.load_json, flops.load_reference
    bench = real_json(bench_run.ROOT, "BENCHMARK.json")
    bench["configs"].append({
        "name": CONFIG, "source": "test-only", "reduced": [],
        "file": f"benchmark/tests/data/configs/{CONFIG}.json",
        "why": "a token model with no expert block, through the token driver"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "packed8k", "chips": 1,
        "why": "one sequence a step, no routers: what the driver may not assume"})
    bench["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "train_imgs_per_s", "workloads": [CELL]})

    def load_json(*parts):
        if parts[-1] == "BENCHMARK.json":
            return copy.deepcopy(bench)
        mine = os.path.join(DATA, *parts[1:])
        return real_json(mine) if os.path.exists(mine) else real_json(*parts)

    def load_reference(config):
        return (bench_run.load_module("tests/data/references", config["reference"])
                or real_reference(config))

    monkeypatch.setattr(bench_run, "load_json", load_json)
    monkeypatch.setattr(flops, "load_reference", load_reference)
    return bench


ACCEPTED = [(mod, name) for mod in (test_contract, test_attention_roofline)
            for name in sorted(vars(mod)) if name.startswith("test_")]


@pytest.mark.parametrize("module,name", ACCEPTED,
                         ids=[f"{m.__name__}.{n}" for m, n in ACCEPTED])
def test_accepted_checks_hold_with_entries_appended(appended, monkeypatch,
                                                    module, name):
    check = getattr(module, name)
    wants = inspect.signature(check).parameters
    check(**({"monkeypatch": monkeypatch} if "monkeypatch" in wants else {}))


def test_a_dotted_entry_is_read_by_its_readers_file(appended):
    assert appended["per_layer"][-1]["name"] == METRIC
    here = os.path.join(HERE, "layer_metrics", "matmul_roofline.py")
    assert bench_run.load_reader(METRIC).__spec__.origin == here
    assert bench_run.load_reader("matmul_roofline").__spec__.origin == here
    assert bench_run.load_reader("no_such_reader.extra") is None
    # nothing to read in a rehearsal: the entry is left out, not failed
    assert bench_run.load_reader(METRIC).read({"rehearsal": True, "peak": None}) \
        is None


def test_sound_run_is_correct_and_shows_no_router(appended):
    line = rehearse(CELL, 2147489020)
    assert line["correct"] is True
    assert not ROUTER_ROWS & set(line["checks"])
    info = line["info"]
    assert info["counters"] == {} and info["rows_routed_per_block_step"] is None
    assert "routing_flips_pct" not in info and info["sequences"] == info["steps"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "wrong_mask"])
def test_each_fault_of_a_one_sequence_cell_comes_out_not_correct(appended, fault):
    line = rehearse(CELL, 9, "--fault", fault)
    assert line["correct"] is False
    over = {k for k, v in line["checks"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}
    assert over and (fault != "wrong_mask" or "rows_altered" in over)


def test_routed_experts_can_be_left_out_only_where_there_are_some(appended):
    driver = bench_run.load_module("drivers", "train_tokens")
    toy = bench_run.load_json(HERE, "configs", CONFIG + ".json")
    assert driver.faults(toy) == ("unchanged", "half_batch", "wrong_mask")
    assert driver.faults(flops.load_config("nemotron_twotower_30b_a3b")) == (
        "unchanged", "half_batch", "no_routed", "wrong_mask")
    with pytest.raises(SystemExit):
        rehearse(CELL, 9, "--fault", "no_routed")


def test_half_of_one_sequence_and_the_control_stand_apart_from_the_reference(
        appended):
    """``follow(keep=0.5)`` on a batch of one sequence follows half the
    row (by rows it would keep the whole and read as sound); the fp8
    control fails as in every cell."""
    ctx, driver, s, prog = session(CELL, 8)
    ref = driver.follow(ctx, s)
    rows = {k: prog[k] for k in ("rows_repeated", "rows_altered")}
    assert driver.judge(ctx, prog, ref)["correct"]
    for other in (driver.follow(ctx, s, keep=0.5),
                  driver.follow(ctx, s, mode=ctx.cell["control"])):
        assert not driver.judge(ctx, {**other, **rows}, ref)["correct"]


def test_the_harness_names_no_model():
    """Drivers, readers, ``run.py`` and the weights' recipes take what is
    one model's from the configuration's reference module and files."""
    files = [os.path.join(HERE, "run.py")]
    for sub in ("drivers", "layer_metrics", "."):
        files += [os.path.join(HERE, sub, f) for f in os.listdir(
            os.path.join(HERE, sub)) if f.endswith(".py") and (
                sub != "." or f.startswith("weights"))]
    for path in files:
        text = open(path).read()
        assert not re.search(r"models\.twotower|models import twotower", text), path
    tokens = open(os.path.join(HERE, "drivers", "train_tokens.py")).read()
    assert '"hybrid_override_pattern"' not in tokens
    assert 'config["deployment"]' not in tokens
