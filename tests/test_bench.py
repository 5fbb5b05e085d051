"""bench.py: one run, one JSON line naming its device — or a non-zero
exit. No probe child, no fallback number, no default peak."""

import json
import os
import subprocess
import sys
import types

import pytest

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_run_compile_only_probe(monkeypatch):
    """BENCH_COMPILE_ONLY=1 compiles the config's train-step executable
    and returns compiled-or-not without a measurement window — the lever
    bench_multi's wgrad_pallas probe pulls."""
    monkeypatch.setenv("BENCH_COMPILE_ONLY", "1")
    monkeypatch.setattr(bench, "BATCH", 1)
    monkeypatch.setattr(bench, "H", 64)
    monkeypatch.setattr(bench, "W", 64)
    result = bench.run()
    assert result == {
        "compile_only": True,
        "compiled": True,
        "compile_s": result["compile_s"],
        "platform": "cpu",
    }
    assert result["compile_s"] >= 0.0


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12),  # what the attached v5e reports
    ("TPU v4", 275e12),
])
def test_peak_table_is_keyed_by_device_kind(kind, peak):
    assert bench.chip_peak_flops(_device("tpu", kind)) == peak


def test_unknown_device_kind_raises():
    """An accelerator the table does not know is an error — never
    another chip's peak (the old code assumed 275 TFLOP/s)."""
    with pytest.raises(ValueError, match="TPU v9 mega"):
        bench.chip_peak_flops(_device("tpu", "TPU v9 mega"))
    # the operator-named CPU has no peak: FLOP-share fields print null
    assert bench.chip_peak_flops(_device("cpu", "cpu")) == 0.0


def test_bench_exits_nonzero_on_error():
    """A failing run ends with a traceback and a non-zero exit code —
    no error JSON with exit 0, no retry in a fresh process."""
    env = dict(os.environ, BENCH_ARCH="unet", BENCH_COMPILE_ONLY="1",
               BENCH_H="33", BENCH_W="33")  # 33 does not divide by 2**4
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "Traceback" in proc.stderr
    for line in proc.stdout.splitlines():
        assert "value" not in line and "error" not in line


def test_bench_prints_device_identity(monkeypatch, capsys):
    monkeypatch.setenv("BENCH_COMPILE_ONLY", "1")
    monkeypatch.setattr(bench, "BATCH", 1)
    monkeypatch.setattr(bench, "H", 64)
    monkeypatch.setattr(bench, "W", 64)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["platform"] == "cpu" and out["compiled"] is True
