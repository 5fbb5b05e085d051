"""chip_smoke.py rehearsed without the chip (`on-chip-measurement` §2):
its phases end to end at toy widths on the CPU — children, serve
restarts, kernel comparisons and all — and the four-chip phase on
virtual devices. The device check is patched HERE, in the test; the
script itself has no switch that lets it pass without a TPU
(tests/test_backend.py pins that)."""

import json

import pytest

import chip_smoke

TOY = chip_smoke.Sizes(
    widths=(16, 32), image_wh=(96, 64), source_wh=(190, 128), n_images=40,
    batch=4, buckets=(1, 2), n_requests=3, param_count=None,
    bn_widths=(8, 128),
    attention_bshgd=(1, 256, 4, 2, 128),
    multichip_batch=8, multichip_steps=2,
)


@pytest.fixture
def accept_named_cpu(monkeypatch):
    """Let the orchestrator take the operator-named CPU for the chip —
    every other part of the check (keys present, enough devices) stays."""
    real = chip_smoke.check_device

    def check(found, chips):
        assert found["platform"] == "cpu"
        real({**found, "platform": "tpu"}, chips)

    monkeypatch.setattr(chip_smoke, "check_device", check)


def _phase_lines(capfd):
    lines = []
    for line in capfd.readouterr().out.splitlines():
        if line.startswith("{"):
            lines.append(json.loads(line))
    return {l["phase"]: l for l in lines}, [l["phase"] for l in lines]


def test_one_chip_phases_at_toy_widths(tmp_path, accept_named_cpu, capfd):
    device = chip_smoke.run(1, 0, str(tmp_path), TOY)
    assert device == {"platform": "cpu", "kind": "cpu", "count": 8}
    rows, order = _phase_lines(capfd)
    assert order == [
        "device", "train_xla_result", "serve_1", "serve_2", "serve_check",
        "kernels", "train_pallas_result", "serve_pallas",
        "serve_pallas_vs_xla",
    ]
    assert all("ok" not in row for row in rows.values())
    train = rows["train_xla_result"]
    assert train["decode_path"] == "native" and train["steps"] == 18
    assert train["checkpoint_verified"] and train["s2d_levels"] == 0
    # the property the AOT store exists for: a second start compiles nothing
    assert rows["serve_1"]["aot_cache"]["compiles"] == len(TOY.buckets)
    assert rows["serve_2"]["aot_cache"]["compiles"] == 0
    assert rows["serve_2"]["aot_cache"]["hit"] == len(TOY.buckets)
    assert rows["serve_check"]["worst_mismatch_fraction"] == 0.0
    kernels = rows["kernels"]["kernels"]
    assert {"eval_stats", "fused_loss", "serve_mask",
            "fused_bn_act_c8", "fused_bn_act_grad_c128",
            "causal_attention"} <= set(kernels)
    # on the named CPU the kernels are interpreted, and the row says so
    assert {k["compiled"] for k in kernels.values()} == {"interpret"}
    assert rows["serve_pallas_vs_xla"]["mismatch_fraction"] == 0.0


def test_four_chip_phase_on_virtual_devices(
    tmp_path, accept_named_cpu, capfd
):
    device = chip_smoke.run(4, 0, str(tmp_path), TOY)
    assert device["count"] >= 4
    rows, order = _phase_lines(capfd)
    assert order == ["device", "multichip", "launcher"]
    runs = rows["multichip"]["strategies"]
    assert runs["singleGPU"]["param_devices"] == 1
    assert runs["DP"]["mesh"] == {"data": 4}
    assert runs["2x1x2"]["mesh"] == {"data": 2, "stage": 2}
    for method in ("DP", "2x1x2"):
        assert runs[method]["param_devices"] == 4
        assert runs[method]["batch_devices"] == 4
        assert runs[method]["max_rel_loss_diff"] < 1e-3
    assert rows["launcher"]["elastic_nprocs_2_off_cpu"] == "refused"


def test_a_failed_phase_fails_the_script(tmp_path, monkeypatch, capsys):
    """Any phase that fails: non-zero exit, no ``"ok": true`` line."""

    def boom(*args, **kwargs):
        raise chip_smoke.PhaseFailed("phase device exited with code 1")

    monkeypatch.setattr(chip_smoke, "run", boom)
    assert chip_smoke.main(["--out", str(tmp_path)]) == 1
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "FAILED: phase device" in captured.err
