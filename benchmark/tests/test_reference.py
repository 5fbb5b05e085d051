"""The plain reference at a toy size on the CPU: it goes through the
batch in blocks of rows and gives what the whole batch at once gives; it
agrees with the program run in float32; and the comparison that decides
``correct`` fails the control (the reference in fp8 put in the program's
place) and every fault the cell can have.

Slow for a unit test (each case builds a Trainer): run by hand,

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import contextlib
import io
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the four-chip cell rehearses on four virtual devices of the CPU backend
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import run as bench_run  # noqa: E402

WORKLOADS = json.load(
    open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))["workloads"]
CELLS = [w["name"] for w in WORKLOADS]
#: every fault that each cell can have: the exchange exists across chips only
FAULTS = [(w["name"], f) for w in WORKLOADS
          for f in ("unchanged", "half_batch", "wrong_mask")
          + (("no_exchange",) * (w["chips"] > 1))]


def rehearse(cell, seed, *extra):
    """One whole run of the harness in its rehearsal mode (which skips
    only the look for a chip): the result line as a dict."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                             "--seconds", "1", "--trace", "0", "--rehearse",
                             *extra])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def session(cell, seed, dtype=None):
    import check  # noqa: F401
    from distributedpytorch_tpu.utils.trace import StepTimeline

    args = bench_run.parser().parse_args(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--rehearse"])
    ctx, driver = bench_run.open_cell(args)
    if dtype:
        ctx.config["train_config"]["dtype"] = dtype
    s = driver.prepare(ctx, seed, StepTimeline(enabled=False),
                       lambda name, **kw: contextlib.nullcontext(),
                       whole_epoch=False)
    prog = s.prog
    driver.release(s)
    return ctx, driver, s, prog


@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_the_program_in_float32(cell):
    import check

    ctx, driver, s, prog = session(cell, 7, dtype="f32")
    ref = driver.follow(ctx, s)
    numbers = check.readings(prog, ref)["numbers"]
    assert max(numbers[f"loss_gap_step{i}"] for i in (1, 2, 3)) < 1e-4
    assert numbers["grad_norm_gap"] < 5e-3
    assert numbers["change_norm_gap"] < 2e-2
    assert driver.judge(ctx, prog, ref)["correct"]


def test_blocks_of_rows_give_what_the_whole_batch_gives():
    import flops
    import numpy as np
    import reference
    import traffic

    config = {**flops.load_config("course_unet"), "image_size": [96, 64]}
    ref = flops.load_reference(config)
    shapes = ref.param_shapes(config)
    import weights
    params = weights.make(shapes, 11)
    data = traffic.SyntheticBlobs(4, (96, 64), 11)
    image = np.stack([data[i]["image"] for i in range(4)])
    mask = np.stack([data[i]["mask"] for i in range(4)])
    run = reference.make_loss_and_grad(ref, config, "f32")
    whole = run(params, None, image, mask, 4)
    for rows in (1, 2):
        cut = run(params, None, image, mask, rows)
        assert abs(float(cut[0]) - float(whole[0])) < 1e-6
        for k in shapes:
            a, b = np.asarray(cut[2][k]), np.asarray(whole[2][k])
            assert np.abs(a - b).max() <= 1e-5 * max(np.abs(b).max(), 1e-6), k


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_8_bits_comes_out_not_correct(cell):
    """Held by a number that was compared: the control, which is the
    reference in another precision, has no rows of a loader's to count,
    and a number that is missing fails by itself."""
    ctx, driver, s, prog = session(cell, 8)
    ref = driver.follow(ctx, s)
    control = driver.follow(ctx, s, mode=ctx.cell["control"])
    rows = {k: prog[k] for k in ("rows_repeated", "rows_altered")}
    assert rows == {"rows_repeated": 0, "rows_altered": 0}
    verdict = driver.judge(ctx, {**control, **rows}, ref)
    assert not verdict["correct"]
    assert all(value is not None for _, value, _ in verdict["rows"])


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_a_broken_timed_path_comes_out_not_correct(cell, fault):
    line = rehearse(cell, 9, "--fault", fault)
    assert line["correct"] is False
    assert list(line)[-1] == "checks"
    over = {k for k, v in line["checks"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}
    assert over and (fault != "wrong_mask" or "rows_altered" in over)


def test_the_reference_is_fed_the_mixes_own_rows():
    """``traffic.same_rows``: the loader's batches made anew from the mix,
    in the loader's order; a row that differs from the mix's is counted."""
    import numpy as np
    import traffic

    data = traffic.SyntheticBlobs(6, (96, 64), 2147483779)
    for i in range(len(data)):
        assert data[i]["image"].ravel()[:traffic.HEAD].tobytes() == data.head(i)
    order = [[4, 1], [0, 5]]
    batches = [{k: np.stack([data[i][k] for i in idx]) for k in ("image", "mask")}
               for idx in order]
    rows, altered = traffic.same_rows(traffic.SyntheticBlobs(
        6, (96, 64), 2147483779), batches)
    assert altered == 0
    for (image, mask), b in zip(rows, batches):
        assert np.array_equal(image, b["image"]) and np.array_equal(mask, b["mask"])
    batches[0]["mask"][1, 5, 5] ^= 1          # a value of a mask
    batches[1]["image"][0, 0, 0, 0] = 0.5     # a row that begins like no item
    rows, altered = traffic.same_rows(data, batches)
    assert altered == 2
    assert np.array_equal(rows[0][1][1], data[1]["mask"])  # the mix's own mask
