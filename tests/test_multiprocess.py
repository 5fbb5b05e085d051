"""Multi-process DDP integration tests: N OS processes × L virtual CPU
devices each, rendezvous over localhost with torchrun-style env — the real
`jax.distributed` path the single-process mesh tests cannot cover
(SURVEY.md §4: 'multi-process tests via jax.distributed over localhost').

Two topology families:
  * 2 procs × 2 devices — the round-3/4 configuration;
  * 4 procs × 1 device — process-count (4) differs from BOTH mesh axis
    sizes in the DDP_MP hybrid ({data:2, stage:2}), and the sharded
    evaluator's grouped dispatch runs at a world size it had never
    executed at (4 val batches = exactly one 4-rank group) — the
    first-pod-run code paths.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

from distributedpytorch_tpu.utils.provision import provisioned_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "ddp_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_world(tmp_path, world, local_devices, method, mode="train",
                  overrides=None, expect_rc=None):
    """Launch one N-rank world. ``overrides`` → $DPT_WORKER_OVERRIDES
    (TrainConfig replacements, e.g. one-rank fault specs). ``expect_rc``
    maps rank → expected nonzero exit (a rank whose configured policy is
    SUPPOSED to fail); unlisted ranks must exit 0. Returns the per-rank
    reports of ranks that exited 0, plus each rank's captured output."""
    port = _free_port()
    procs = []
    for rank in range(world):
        # CPU backend with `local_devices` virtual devices
        # (ONE definition of those moves: utils/provision.py)
        env = provisioned_env(local_devices)
        env.update(
            {
                # torchrun contract (reference README.md:37)
                "RANK": str(rank),
                "LOCAL_RANK": str(rank),
                "WORLD_SIZE": str(world),
                "MASTER_ADDR": "127.0.0.1",
                "MASTER_PORT": str(port),
                # per-rank but PERSISTENT compilation cache: splitting by
                # rank avoids two ranks racing on identical entries, while
                # keeping warm-cache speed across runs (tmp_path would be
                # cold every invocation): a fixed rank<R>/ beneath the
                # suite's cache directory, as the elastic supervisor does
                "JAX_COMPILATION_CACHE_DIR": os.path.join(
                    os.environ["JAX_COMPILATION_CACHE_DIR"], f"rank{rank}"
                ),
            }
        )
        if overrides:
            env["DPT_WORKER_OVERRIDES"] = json.dumps(overrides)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-u", WORKER, str(tmp_path), method, mode],
                env=env,
                cwd=REPO,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )

    # 1-core boxes serialize all ranks' compiles: world=4 with cold
    # per-rank caches needs well over the old 900 s budget. On timeout,
    # kill the SURVIVING ranks too — otherwise a single wedged rank
    # leaves world−1 live workers holding MASTER_PORT and the CPU while
    # the next parametrized case tries to run.
    outputs = []
    try:
        for p in procs:
            outputs.append(p.communicate(timeout=1800)[0])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    expect_rc = expect_rc or {}
    for rank, (p, out) in enumerate(zip(procs, outputs)):
        want = expect_rc.get(rank, 0)
        if want == 0:
            assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        else:
            assert p.returncode != 0, (
                f"rank {rank} was expected to fail but exited 0:\n{out}"
            )

    prefix = "restore_rank" if mode == "restore" else "rank"
    reports = []
    for rank in range(world):
        if expect_rc.get(rank, 0) != 0:
            continue
        with open(tmp_path / f"{prefix}{rank}.json") as f:
            reports.append(json.load(f))
    return reports, outputs


def _assert_world(tmp_path, reports, method, mesh_data):
    r0 = reports[0]
    # expected global data-axis extent (world × local devices / stage axis)
    assert all(r["mesh_data"] == mesh_data for r in reports)
    assert r0["steps"] > 0
    for r in reports[1:]:
        # replicas identical after gradient all-reduce
        assert r["fingerprint"] == pytest.approx(r0["fingerprint"], rel=1e-6)
        assert r["steps"] == r0["steps"]
        # batch assembly: the same jitted reduction of a placed global
        # batch must agree on every rank — rank-dependent values mean a
        # replicated shard holds different data on different devices
        # (the round-5 co-row corruption signature)
        assert r["batch_sum"] == pytest.approx(r0["batch_sum"], rel=1e-6)
    # sharded eval == replicated eval, on every rank, and identical values
    # across ranks (each rank loads only its own round-robin share; the
    # grouped dispatch's replicated out_shardings hands every rank the
    # full-group metrics). abs=1e-8: the replicated path evaluates each
    # batch process-DUPLICATED (make_array_from_process_local_data concats
    # every rank's identical copy), which loss and dice are invariant to
    # EXCEPT for the eps regularizer — a fully-collapsed model's dice
    # (~1e-10, pure eps floor) legitimately differs by the duplication
    # factor, while any real dice (≥1e-4) still gets the tight rel bound.
    for r in reports:
        assert r["sharded_val"] == pytest.approx(
            r["replicated_val"], rel=1e-5, abs=1e-8)
        assert r["sharded_val"] == pytest.approx(
            r0["sharded_val"], rel=1e-6, abs=1e-9)
    # rank-0-only artifacts (reference train_utils.py:243-248 gating)
    assert os.path.exists(tmp_path / "checkpoints" / f"{method}.ckpt")
    assert os.path.exists(tmp_path / "loss" / method / "train_loss.pkl")


@pytest.mark.slow
@pytest.mark.parametrize(
    "method,mesh_data", [("DDP", 4), ("DDP_MP", 2), ("DDP_SP", 2)]
)
def test_two_process(tmp_path, method, mesh_data):
    """2 procs × 2 devices. DDP: 4-device global data mesh. DDP_MP:
    {data:2, stage:2} — crosses jax.distributed with the explicit pipeline
    schedule. DDP_SP: {data:2, spatial:2} — the
    H-sliced batch placement over jax.distributed."""
    reports, _ = _launch_world(tmp_path, world=2, local_devices=2, method=method)
    _assert_world(tmp_path, reports, method, mesh_data)


@pytest.mark.slow
def test_two_process_fsdp_save_restore(tmp_path):
    """2 procs × 2 devices under FSDP: params/Adam state shard over a
    4-device GLOBAL 'data' mesh, so every sharded leaf is
    non-fully-addressable on each host — the configuration whose
    checkpoint save needs the per-leaf `process_allgather` gather
    (checkpoint._to_host; ROADMAP 'Multi-host-safe sharded checkpoint
    gather'). The worker proves the save restores bit-identically into a
    fresh sharded Trainer on every rank."""
    reports, _ = _launch_world(tmp_path, world=2, local_devices=2, method="FSDP")
    _assert_world(tmp_path, reports, "FSDP", 4)
    for r in reports:
        # the premise: state actually spans processes (else this test
        # degenerates to the single-host path)
        assert r["non_addressable_leaves"] > 0, r
        assert r["restore_ok"] is True, r


@pytest.mark.slow
@pytest.mark.parametrize("save_world,restore_world", [(2, 1), (1, 2)])
def test_fsdp_reshard_restore(tmp_path, save_world, restore_world):
    """Mesh-resharding restore (the elastic tentpole's acceptance
    criterion): a checkpoint saved on an N-process FSDP mesh restores
    onto an M-process mesh — N→M (a shrunk elastic relaunch) AND M→N (a
    recovered slot) — parameter-BIT-identical after gather. Checkpoints
    hold full host arrays (`_to_host` allgathers sharded leaves at save
    time), so restore just re-places them under the current sharding;
    this proves that end to end across actual world sizes."""
    save_reports, _ = _launch_world(
        tmp_path, world=save_world, local_devices=2, method="FSDP"
    )
    trained_hash = save_reports[0]["params_sha256"]
    assert all(r["params_sha256"] == trained_hash for r in save_reports)

    restore_reports, _ = _launch_world(
        tmp_path, world=restore_world, local_devices=2, method="FSDP",
        mode="restore",
    )
    assert len(restore_reports) == restore_world
    for r in restore_reports:
        assert r["start_epoch"] == 1, r  # resumed, not fresh
        assert r["params_sha256"] == trained_hash, (
            f"reshard {save_world}→{restore_world}: restored params "
            f"differ from the saved ones"
        )


@pytest.mark.slow
def test_one_rank_decode_fault_recovers_in_lockstep(tmp_path):
    """PR 2's transient decode injection, fired on ONE rank of a live
    2-process mesh: the bounded-backoff retry recovers locally, the
    survivor never waits on a desynced collective, and both ranks end
    bit-identical (the transparent-recovery contract, now multi-proc)."""
    reports, _ = _launch_world(
        tmp_path, world=2, local_devices=1, method="DDP",
        overrides={"inject_faults": ["decode@1:0:*"]},
    )
    _assert_world(tmp_path, reports, "DDP", 2)
    assert reports[0]["steps"] == reports[1]["steps"]


@pytest.mark.slow
def test_one_rank_nan_skip_is_agreed_collectively(tmp_path):
    """``nan_loss`` injected on rank 1 ONLY, policy ``skip``: without
    the collective finiteness agreement (train/loop._finite_agreed) the
    injected rank discards its update while its peer applies one —
    silently forked replicas. With it, BOTH ranks discard the same step:
    equal step counts, equal skip counts, bit-identical fingerprints."""
    reports, _ = _launch_world(
        tmp_path, world=2, local_devices=1, method="DDP",
        overrides={
            "nonfinite_policy": "skip",
            "inject_faults": ["nan_loss@1:0:3"],
        },
    )
    _assert_world(tmp_path, reports, "DDP", 2)
    assert [r["skipped_steps"] for r in reports] == [1, 1]
    assert reports[0]["steps"] == reports[1]["steps"]
    assert reports[0]["fingerprint"] == reports[1]["fingerprint"]


@pytest.mark.slow
def test_ckpt_write_fault_fails_writer_without_hanging_survivor(tmp_path):
    """``ckpt_write`` on a 2-process mesh fires only on the writing rank
    (rank 0). The torn write surfaces as a hard error out of rank 0's
    final drain — AFTER the run's last collective — so rank 1 completes
    cleanly and neither rank hangs in a collective (the launch's 1800 s
    communicate() timeout is the no-hang oracle)."""
    reports, outputs = _launch_world(
        tmp_path, world=2, local_devices=1, method="DDP", mode="train_only",
        overrides={"inject_faults": ["ckpt_write:1"], "keep_checkpoints": 1},
        expect_rc={0: 1},
    )
    assert "injected ckpt_write fault" in outputs[0]
    # the survivor (rank 1) finished its full run and reported
    assert len(reports) == 1 and reports[0]["rank"] == 1
    assert reports[0]["error"] is None
    assert reports[0]["steps"] > 0


@pytest.mark.slow
@pytest.mark.parametrize(
    "method,mesh_data", [("DDP", 4), ("DDP_MP", 2), ("DDP_SP", 2)]
)
def test_four_process(tmp_path, method, mesh_data):
    """4 procs × 1 device. For the hybrids the
    process count (4) equals NEITHER mesh axis ({data:2, stage:2} /
    {data:2, spatial:2}), so co-row processes must feed identical data
    into replicated/H-sliced shards (the row-based data_shard contract)
    and the collectives cross process boundaries; the sharded
    evaluator's grouped dispatch executes at its row world."""
    reports, _ = _launch_world(tmp_path, world=4, local_devices=1, method=method)
    _assert_world(tmp_path, reports, method, mesh_data)
