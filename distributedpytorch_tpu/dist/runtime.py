"""Process-group runtime: `jax.distributed` with torchrun-compatible env.

The reference joins its process group with
``dist.init_process_group('nccl', init_method='env://')`` under a torchrun
launcher that sets LOCAL_RANK / RANK / WORLD_SIZE / MASTER_ADDR / MASTER_PORT
(reference train.py:29-31, :58-61; README.md:37). The TPU-native equivalent
is `jax.distributed.initialize`, which on real TPU pods autodetects topology;
off-pod (or when launched by torchrun per the driver's north star) we map the
torchrun env onto its coordinator/process arguments.

No NCCL anywhere: after initialization, collectives are XLA's, riding ICI
within a pod slice and DCN across slices (SURVEY.md §5 'Distributed
communication backend').
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional

import jax

logger = logging.getLogger(__name__)

_INITIALIZED = False


@dataclasses.dataclass(frozen=True)
class RuntimeInfo:
    process_id: int
    num_processes: int
    coordinator: Optional[str]

    @property
    def is_main(self) -> bool:
        return self.process_id == 0


def _torchrun_env() -> Optional[RuntimeInfo]:
    """Map torchrun's env contract onto jax.distributed's, if present."""
    if "WORLD_SIZE" not in os.environ or "RANK" not in os.environ:
        return None
    world = int(os.environ["WORLD_SIZE"])
    rank = int(os.environ["RANK"])
    addr = os.environ.get("MASTER_ADDR", "127.0.0.1")
    # jax.distributed's coordinator must not collide with torchrun's c10d
    # rendezvous port, so offset it deterministically.
    port = int(os.environ.get("MASTER_PORT", "29500")) + 1
    return RuntimeInfo(rank, world, f"{addr}:{port}")


def _enable_cpu_collectives() -> None:
    """Give multi-process CPU runs a working collectives backend.

    jaxlib's CPU client defaults to collectives 'none', so ANY
    multiprocess computation — the DDP gradient all-reduce, the sharded
    evaluator's grouped dispatch, `process_allgather` (both the stop
    agreement and the FSDP checkpoint gather) — dies with "Multiprocess
    computations aren't implemented on the CPU backend". Gloo ships in
    jaxlib; it just has to be selected BEFORE the backend initializes.
    Called only on the multi-process paths: single-process runs never
    need it, and on TPU backends the flag is simply unread."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _warm_host_collectives() -> None:
    """Form the all-process host-collective (Gloo, on CPU backends) context
    NOW, while every rank is still in lockstep from `initialize()`'s
    rendezvous.

    Gloo context formation has a hard ~30 s key-value deadline per peer.
    Without this warm-up the first host collective is wherever the trainer
    first calls `multihost_utils.process_allgather` — the per-epoch stop
    check (train/loop.py `_stop_agreed`) — by which point rank skew on a
    contended host (N processes time-slicing few cores, compile times
    diverging) can exceed the deadline and kill the whole job with
    "Gloo context initialization failed: DEADLINE_EXCEEDED" (observed with
    4 localhost processes on a 1-core box). Once the context exists,
    later collectives block on connected sockets with no such deadline.
    On TPU pods this is a single sub-millisecond allgather — harmless."""
    import numpy as np
    from jax.experimental import multihost_utils

    multihost_utils.process_allgather(np.zeros((1,), np.int32))


def _init_timeout_kwargs() -> dict:
    """Bound the rendezvous wait (``DPT_DIST_INIT_TIMEOUT_S``, seconds).

    jax's default initialization timeout is 300 s — fine for a pod
    bring-up, far too patient for the elastic supervisor's relaunch
    loop: a worker stuck joining a rendezvous whose peers already died
    should fail fast so the supervisor can classify it and respawn the
    whole world (dist/elastic.py sets this for its workers' children
    only through the env, so standalone launches keep jax's default)."""
    raw = os.environ.get("DPT_DIST_INIT_TIMEOUT_S")
    if not raw:
        return {}
    try:
        return {"initialization_timeout": int(float(raw))}
    except ValueError:
        logger.warning("ignoring malformed DPT_DIST_INIT_TIMEOUT_S=%r", raw)
        return {}


def initialize_from_env(force: bool = False) -> RuntimeInfo:
    """Initialize multi-process JAX if a launcher env is present.

    Order: explicit JAX_COORDINATOR env → torchrun env → single process.
    Safe to call unconditionally (idempotent; no-op single-process)."""
    global _INITIALIZED
    if _INITIALIZED:
        return RuntimeInfo(jax.process_index(), jax.process_count(), None)

    # Real multi-host TPU pods: argless initialize() autodetects the pod's
    # own coordinator from the TPU runtime/cloud metadata. Opt-in (env
    # flag) because on a single host with no metadata server the
    # detection probes would stall startup.
    if os.environ.get("DPT_JAX_AUTO_INIT") == "1":
        _enable_cpu_collectives()
        jax.distributed.initialize(**_init_timeout_kwargs())
        _INITIALIZED = True
        info = RuntimeInfo(jax.process_index(), jax.process_count(), None)
        if info.num_processes > 1:
            _warm_host_collectives()
        logger.info(
            "jax.distributed auto-initialized: process %d/%d",
            info.process_id,
            info.num_processes,
        )
        return info

    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if coord:
        info = RuntimeInfo(
            int(os.environ.get("JAX_PROCESS_ID", "0")),
            int(os.environ.get("JAX_NUM_PROCESSES", "1")),
            coord,
        )
    else:
        info = _torchrun_env()

    if info is None or info.num_processes <= 1:
        return RuntimeInfo(0, 1, None)

    _enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=info.coordinator,
        num_processes=info.num_processes,
        process_id=info.process_id,
        **_init_timeout_kwargs(),
    )
    _INITIALIZED = True
    _warm_host_collectives()
    logger.info(
        "jax.distributed initialized: process %d/%d via %s",
        info.process_id,
        info.num_processes,
        info.coordinator,
    )
    return info


def shutdown() -> None:
    """`dist.destroy_process_group` parity (reference train.py:61)."""
    global _INITIALIZED
    if _INITIALIZED:
        jax.distributed.shutdown()
        _INITIALIZED = False
