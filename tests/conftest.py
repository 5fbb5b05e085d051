"""Test environment: force an 8-device virtual CPU mesh BEFORE jax inits.

This is the idiomatic JAX "fake backend" for testing pjit/shard_map/pipeline
schedules without TPU hardware (SURVEY.md §4): every distributed test runs
single-process against 8 virtual CPU devices. The tests name the CPU
themselves (``JAX_PLATFORMS=cpu``) — the one way this program runs there
(utils/backend.py).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# 0 = persist EVERY compile, including the sub-second ones. The suite is
# ~900 tiny-model tests whose individual compiles are almost all under
# jax's default 1 s floor, so with the floor in place a warm run still
# re-compiles nearly everything — measured on the 1-core box, dropping
# the floor to 0 cuts a warm tests/test_mesh.py pass from 87 s to 66 s
# (~24%), which is the difference between tier-1 fitting its fixed 870 s
# wall and timing out as the suite grows.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


# Persistent XLA compilation cache: repeat suite runs (and the many
# structurally-identical tiny-model compiles within one run) hit disk
# instead of recompiling. The program's own helper places it — where
# $JAX_COMPILATION_CACHE_DIR says, else the fixed in-checkout directory —
# and the result is exported so the subprocesses tests start agree.
from distributedpytorch_tpu.utils.backend import (  # noqa: E402
    enable_compilation_cache,
)

os.environ["JAX_COMPILATION_CACHE_DIR"] = enable_compilation_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
