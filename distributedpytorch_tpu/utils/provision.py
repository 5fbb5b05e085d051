"""Virtual-device provisioning env for CPU-mesh subprocesses.

jax backends initialize once per process — so a process that wants an
n-device virtual CPU mesh must have the right env BEFORE its interpreter
starts. Every self-provisioning entry point
(`__graft_entry__.dryrun_multichip`, `tools/convergence_run.py`, the
elastic supervisor's CPU drills) needs the same two moves: name the CPU
(JAX_PLATFORMS=cpu — the one way this program runs there,
utils/backend.py) and rewrite --xla_force_host_platform_device_count in
XLA_FLAGS. ONE definition here so a future addition lands everywhere.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Mapping, MutableMapping, NoReturn, Optional, Sequence


def provisioned_env(
    n_devices: int, base: Mapping[str, str] | None = None
) -> MutableMapping[str, str]:
    """A copy of ``base`` (default ``os.environ``) prepared for a subprocess
    that must see ``n_devices`` virtual CPU devices and never claim a
    chip."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={int(n_devices)}"
    ).strip()
    return env


def maybe_reexec_provisioned(n_devices: int, sentinel: str) -> Optional[int]:
    """The self-provisioning entry-point dance, in one place: if
    ``sentinel`` is already set this process IS the provisioned child —
    return None and let the caller proceed. Otherwise re-run
    ``sys.argv`` under ``provisioned_env(n_devices)`` and return the child's exit code for the caller to
    propagate. Used by tools/convergence_run.py; __graft_entry__ keeps
    its own variant (it
    re-execs a ``-c`` command, not a script file)."""
    if os.environ.get(sentinel) == "1":
        return None
    env = provisioned_env(n_devices)
    env[sentinel] = "1"
    return subprocess.run(
        [sys.executable, "-u", os.path.abspath(sys.argv[0])] + sys.argv[1:],
        env=env,
    ).returncode


def reexec_provisioned_cmd(n_devices: int, sentinel: str,
                           cmd: Sequence[str]) -> NoReturn:
    """Replace THIS process with ``cmd`` under ``provisioned_env`` —
    ``os.execvpe``, not a child process. The caller's PID is preserved,
    so whatever supervises it (CI's ``timeout``, a shell) signals the
    provisioned interpreter directly: there is no intermediate parent
    whose death would orphan a still-running child. For entry points
    that re-run a command rather than ``sys.argv`` as a script (the
    ``analyze`` CLI re-runs ``-m distributedpytorch_tpu``)."""
    env = provisioned_env(n_devices)
    env[sentinel] = "1"
    os.execvpe(cmd[0], list(cmd), env)
