"""Tooling: loss-curve rendering from the reference-schema pickles
(tools/plot_losses.py) and the MODEL.md generator's CPU mode."""

import os
import subprocess
import sys

import pandas as pd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_pickles(loss_dir, method):
    mdir = os.path.join(loss_dir, method)
    os.makedirs(mdir)
    pd.DataFrame(
        [[10, 1.0, 2.5], [20, 2.0, 2.1]], columns=["Step", "Time", "Loss"]
    ).to_pickle(os.path.join(mdir, "train_loss.pkl"))
    pd.DataFrame([[20, 2.0, 2.2]], columns=["Step", "Time", "Loss"]).to_pickle(
        os.path.join(mdir, "val_loss.pkl")
    )
    pd.DataFrame([[20, 2.0, 0.4]], columns=["Step", "Time", "Dice"]).to_pickle(
        os.path.join(mdir, "val_dice.pkl")
    )


def test_plot_losses(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from plot_losses import plot_losses
    finally:
        sys.path.pop(0)

    _write_pickles(tmp_path, "singleGPU")
    _write_pickles(tmp_path, "DP")
    out = plot_losses(str(tmp_path), str(tmp_path / "losses.png"))
    assert os.path.getsize(out) > 1000  # a real PNG, not an empty file


def test_model_summary_cpu_mode(tmp_path):
    out = tmp_path / "MODEL.md"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu"})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "model_summary.py"),
         "-o", str(out)],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    text = out.read_text()
    assert "7,760,097" in text  # the golden param count
    assert "29.60 MB" in text  # parity with reference modelsummary.txt:69


def test_plot_img_and_mask(tmp_path):
    """The reference's plot_img_and_mask (reference utils/utils.py:38-51)
    rebuilt headless: renders image + per-class mask panels to a PNG."""
    import numpy as np

    from distributedpytorch_tpu.utils.plotting import plot_img_and_mask

    rng = np.random.default_rng(0)
    img = rng.random((32, 48, 3), dtype=np.float32)
    mask = (rng.random((32, 48)) > 0.5).astype(np.int32)
    out = tmp_path / "panel.png"
    plot_img_and_mask(img, mask, out_path=str(out))
    assert out.stat().st_size > 1000

    # multi-class path: one panel per channel
    mask3 = (rng.random((32, 48, 3)) > 0.5).astype(np.int32)
    out3 = tmp_path / "panel3.png"
    plot_img_and_mask(img, mask3, out_path=str(out3))
    assert out3.stat().st_size > 1000
