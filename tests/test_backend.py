"""The device is never chosen behind the operator's back, the compile
cache can be placed from outside, and the launcher gives no two workers
the same chips (utils/backend.py, dist/elastic.py, chip_smoke.py)."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from distributedpytorch_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile-cache placement ------------------------------------------------


class TestCompileCachePlacement:
    @pytest.fixture
    def config_spy(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: calls.append((k, v))
        )
        return calls

    def test_external_directory_is_used_and_no_other_is_set(
        self, monkeypatch, config_spy
    ):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert backend.enable_compilation_cache() == "/some/dir"
        assert config_spy == []  # jax reads the variable itself

    def test_unset_is_the_fixed_in_checkout_directory(
        self, monkeypatch, config_spy
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        # the retired knob has no say any more
        monkeypatch.setenv("DPT_COMPILATION_CACHE", "/elsewhere")
        got = backend.enable_compilation_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert config_spy == [("jax_compilation_cache_dir", got)]
        ignored = subprocess.run(
            ["git", "check-ignore", "-q", got], cwd=REPO
        ).returncode
        assert ignored == 0, ".jax_cache/ must be git-ignored"

    def test_two_processes_agree_on_the_default(self):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["PYTHONPATH"] = REPO
        script = (
            "import jax\n"
            "from distributedpytorch_tpu.utils.backend import "
            "enable_compilation_cache as e\n"
            "print(e()); print(jax.config.jax_compilation_cache_dir)"
        )
        outs = [
            subprocess.run(
                [sys.executable, "-c", script], env=env, cwd=cwd,
                capture_output=True, text=True, timeout=120, check=True,
            ).stdout.split()
            for cwd in (REPO, os.path.join(REPO, "tests"))
        ]
        assert outs[0] == outs[1] == [os.path.join(REPO, ".jax_cache")] * 2

    #: What earlier PRs retired, as ``git grep`` patterns: a knob, a flag,
    #: a module or a harness that comes back under its old name fails here.
    RETIRED = [
        "DPT_COMPILATION_CACHE",
        "DPT_WGRAD_BACKEND", "DPT_WGRAD_TAPS_MIN_HW", "wgrad_taps",
        "--wgrad-taps", "conv_backward", "wgrad_pallas",
        "DPT_BENCH_PLAN", "BENCH_[A-Z]", "bench\\.py", "bench_multi",
        "rank_legs",
        "VERDICT r",
    ]

    @pytest.mark.parametrize("pattern", RETIRED)
    def test_retired_knob_is_gone_from_the_program(self, pattern):
        paths = ["distributedpytorch_tpu", "train.py", "tools",
                 "chip_smoke.py"]
        if pattern == "VERDICT r":
            # a file PR 21 deleted; this test names what it forbids
            paths += ["tests", ":!tests/test_backend.py"]
        hits = subprocess.run(
            ["git", "grep", "-l", "-e", pattern, "--", *paths],
            cwd=REPO, capture_output=True, text=True,
        ).stdout.split()
        assert hits == []


def _supervisor(tmp_path, env, **kw):
    from distributedpytorch_tpu.dist.elastic import ElasticSupervisor

    kw.setdefault("nprocs", 2)
    return ElasticSupervisor(
        ["-t", "DDP"], env=env, run_dir=str(tmp_path / "run"), **kw
    )


class TestElasticWorkerEnv:
    def test_workers_keep_an_external_cache_directory(self, tmp_path):
        """Never swapped for another: each rank gets a FIXED rank<k>/
        beneath the directory the operator gave, the same on every
        attempt (the path is part of the cache key)."""
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR="/ext/cache")
        sup = _supervisor(tmp_path, env, cpu_devices=1)
        for attempt in (0, 3):
            for rank in (0, 1):
                got = sup._worker_env(rank, 2, 29500, attempt=attempt)
                assert got["JAX_COMPILATION_CACHE_DIR"] == (
                    f"/ext/cache/rank{rank}"
                )

    def test_serve_workers_keep_it_too(self, tmp_path):
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR="/ext/cache")
        sup = _supervisor(tmp_path, env, cpu_devices=1, workload="serve")
        got = sup._worker_env(1, 2, 29500)
        assert got["JAX_COMPILATION_CACHE_DIR"] == "/ext/cache/rank1"
        assert got["DPT_AOT_CACHE"]  # the shared store is still handed out

    def test_unset_falls_to_the_in_checkout_default(self, tmp_path):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        sup = _supervisor(tmp_path, env, cpu_devices=1)
        got = sup._worker_env(0, 2, 29500)
        assert got["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
            backend.DEFAULT_CACHE_DIR, "rank0"
        )


class TestLauncherOffCpu:
    """A TPU chip belongs to one process, and a worker launched with no
    chip of its own claims them all: several workers are started only
    where they are held to the CPU."""

    @pytest.fixture
    def tpu_host_env(self):
        return {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}

    def test_two_workers_off_cpu_are_refused(self, tmp_path, tpu_host_env):
        from distributedpytorch_tpu.dist.elastic import MULTI_WORKER_OFF_CPU

        with pytest.raises(ValueError) as exc:
            _supervisor(tmp_path, tpu_host_env)
        assert str(exc.value) == MULTI_WORKER_OFF_CPU.format(n=2)
        assert "-t DP" in str(exc.value) and "--cpu-devices" in str(exc.value)

    def test_a_fleet_that_may_grow_is_refused_too(
        self, tmp_path, tpu_host_env
    ):
        with pytest.raises(ValueError, match="refusing to start 3 worker"):
            _supervisor(tmp_path, tpu_host_env, nprocs=1, workload="serve",
                        fleet_max_workers=3)

    @pytest.mark.parametrize("how", ["cpu_devices", "operator_env", "one"])
    def test_what_stays_allowed(self, tmp_path, tpu_host_env, how):
        if how == "cpu_devices":
            sup = _supervisor(tmp_path, tpu_host_env, cpu_devices=2)
            assert sup._worker_env(0, 2, 1)["JAX_PLATFORMS"] == "cpu"
        elif how == "operator_env":
            _supervisor(tmp_path, dict(tpu_host_env, JAX_PLATFORMS="cpu"))
        else:  # one supervised TPU process: it may own every chip
            sup = _supervisor(tmp_path, tpu_host_env, nprocs=1)
            assert "JAX_PLATFORMS" not in sup._worker_env(0, 1, 1)

    def test_the_cli_reports_the_refusal(self, tmp_path, tpu_host_env):
        proc = subprocess.run(
            [sys.executable, "-m", "distributedpytorch_tpu", "elastic",
             "-n", "2", "--run-dir", str(tmp_path / "run"), "--",
             "-t", "DDP"],
            env=dict(tpu_host_env, PYTHONPATH=REPO), cwd=str(tmp_path),
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0
        assert "refusing to start 2 worker processes" in proc.stderr


# -- device policy ------------------------------------------------------------


class TestDevicePolicy:
    def test_named_cpu_is_accepted(self):
        # the suite itself runs under JAX_PLATFORMS=cpu (conftest)
        assert backend.operator_named_cpu()
        assert backend.require_accelerator("test") == "cpu"
        assert backend.pallas_interpret() is True

    def test_silent_cpu_fallback_ends_the_entry_point(self, monkeypatch):
        """jax picked the CPU by itself (no TPU found, JAX_PLATFORMS
        unset): every entry point says so and exits non-zero."""
        monkeypatch.setattr(backend, "operator_named_cpu", lambda: False)
        with pytest.raises(SystemExit) as exc:
            backend.require_accelerator("train")
        assert exc.value.code not in (0, None)
        assert "JAX_PLATFORMS=cpu" in str(exc.value.code)

    @pytest.mark.parametrize("entry", ["train", "serve", "convergence_run"])
    def test_every_entry_point_asks(self, monkeypatch, entry, tmp_path):
        asked = []

        def refuse(name):
            asked.append(name)
            raise SystemExit(f"{name}: refused")

        monkeypatch.setattr(backend, "require_accelerator", refuse)
        monkeypatch.chdir(tmp_path)
        with pytest.raises(SystemExit):
            if entry == "train":
                from distributedpytorch_tpu import cli

                monkeypatch.setattr(sys, "argv", ["train.py", "--synthetic", "8"])
                cli.main()
            elif entry == "serve":
                from distributedpytorch_tpu.serve import cli as serve_cli

                serve_cli.main(["-c", "nothing"])
            else:
                from tools import convergence_run

                # this process keeps its compile-cache settings
                monkeypatch.setattr(
                    backend, "enable_compilation_cache", lambda: None)
                monkeypatch.setattr(
                    sys, "argv", ["convergence_run.py", "--tpu"])
                convergence_run.main()
        assert asked == [entry]

    def test_a_requested_kernel_is_never_silently_interpreted(
        self, monkeypatch
    ):
        """On a device that is neither a TPU nor an operator-named CPU a
        kernel with ``interpret=None`` raises — in every kernel module,
        through the one helper."""
        from distributedpytorch_tpu.ops import (
            attention_pallas,
            kernels,
            pallas_kernels,
        )

        monkeypatch.setattr(backend, "operator_named_cpu", lambda: False)
        for mod in (kernels, pallas_kernels, attention_pallas):
            assert mod.pallas_interpret is backend.pallas_interpret
            assert not hasattr(mod, "_auto_interpret")
        x = jnp.full((1, 8, 16, 1), 0.5, jnp.float32)
        with pytest.raises(RuntimeError, match="not be run in the interpreter"):
            pallas_kernels.eval_stats_pallas(x, x)
        with pytest.raises(RuntimeError, match="not be run in the interpreter"):
            kernels.sigmoid_threshold_mask(x[..., 0], 0.5)


class TestChipSmokeNeedsTheChip:
    def _run(self, cwd, script, timeout=120):
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, script], cwd=cwd, capture_output=True,
            text=True, timeout=timeout,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        return proc, time.monotonic() - t0

    def test_cpu_run_exits_nonzero_fast_without_ok(self, tmp_path):
        proc, secs = self._run(
            REPO, os.path.join(REPO, "chip_smoke.py")
        )
        assert proc.returncode != 0
        assert secs < 60
        assert '"ok"' not in proc.stdout
        assert "needs 1 TPU chip" in proc.stderr
        # nothing but the device line was printed, and it names the CPU
        lines = [json.loads(l) for l in proc.stdout.splitlines() if l]
        assert [l["phase"] for l in lines] == ["device"]
        assert lines[0]["platform"] == "cpu"

    def test_script_alone_prints_no_result(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env_path = os.environ.get("PYTHONPATH", "")
        assert REPO not in env_path.split(os.pathsep)
        proc, _ = self._run(str(tmp_path), "chip_smoke.py")
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
        assert "distributedpytorch_tpu" in proc.stderr  # what is missing

    def test_command_line_has_no_way_around_the_device_check(self):
        import chip_smoke

        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--help"],
            capture_output=True, text=True, timeout=60,
        )
        options = {w.rstrip(",") for w in proc.stdout.split()
                   if w.startswith("--")}
        assert options == {"--help", "--chips", "--seed", "--out"}
        with pytest.raises(chip_smoke.PhaseFailed):
            chip_smoke.check_device(
                {"platform": "cpu", "kind": "cpu", "count": 8}, 1
            )
        with pytest.raises(chip_smoke.PhaseFailed):
            chip_smoke.check_device(
                {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 4
            )
        chip_smoke.check_device(
            {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}, 4
        )
