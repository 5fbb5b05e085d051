"""Worker for the multi-process integration tests (test_multiprocess.py).

Launched once per rank with torchrun-style env (RANK / WORLD_SIZE /
MASTER_ADDR / MASTER_PORT) — the exact contract `dist/runtime.py` maps onto
`jax.distributed.initialize` (reference launch: README.md:37). Trains a tiny
synthetic run under the method named in argv[2] (DDP, or the DDP_MP
data x stage hybrid) and writes a params fingerprint plus replicated- and
sharded-path val metrics per rank, so the parent can assert replicas stayed
in sync through the gradient all-reduce and the sharded evaluator matches
the replicated one.

Modes (argv[3], default ``train``):
  * ``train`` — the full train + report flow above;
  * ``restore`` — NO training: build a Trainer that resumes from the
    method's checkpoint (written by an earlier launch, possibly at a
    DIFFERENT world size — the mesh-resharding restore path) and report
    the restored params' sha256, so the parent can assert N→M restore is
    parameter-bit-identical after gather;
  * ``train_only`` — train, report, exit; NO post-train collectives
    (eval equivalence, batch sums) and no distributed-shutdown barrier.
    For chaos cases where a PEER is expected to die: the assertion is
    that training's own collectives completed, and a survivor must not
    be made to hang in report-time collectives its dead peer will never
    join.

Config overrides come as a JSON object in $DPT_WORKER_OVERRIDES (e.g.
``{"nonfinite_policy": "skip", "inject_faults": ["nan_loss@1:0:3"]}``) —
how the one-rank fault-injection tests arm a single peer of a live mesh.
"""

import hashlib
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _params_sha256(tree) -> str:
    """Bit-exact digest of a gathered host param tree (leaf order is
    jax.tree's deterministic flattening)."""
    import jax

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        arr = np.ascontiguousarray(np.asarray(leaf))
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def main():
    out_dir = sys.argv[1]
    method = sys.argv[2] if len(sys.argv) > 2 else "DDP"
    mode = sys.argv[3] if len(sys.argv) > 3 else "train"

    from distributedpytorch_tpu.dist import initialize_from_env, shutdown

    runtime = initialize_from_env()

    import jax

    assert jax.process_count() == int(os.environ["WORLD_SIZE"]), (
        jax.process_count(),
        os.environ["WORLD_SIZE"],
    )

    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.train import Trainer

    config = TrainConfig(
        train_method=method,
        epochs=1,
        batch_size=4,  # per-process, like the reference's -b
        learning_rate=1e-4,
        val_percent=25.0,
        seed=42,
        compute_dtype="float32",
        image_size=(48, 32),
        model_widths=(8, 16),  # tiny model: this tests the runtime, not UNet
        # 64 samples → 16 val → 4 val batches: at world=4 that is exactly
        # one sharded-eval group (n_groups = 4//4 = 1), so the grouped
        # dispatch ACTUALLY EXECUTES in the 4-process test (with 32
        # samples it had 2 batches → n_groups 0 and everything fell to
        # the replicated tail, making sharded==replicated trivially true);
        # at world=2 it is 2 groups, strictly more coverage than before.
        synthetic_samples=64,
        checkpoint_dir=os.path.join(out_dir, "checkpoints"),
        log_dir=os.path.join(out_dir, "logs"),
        loss_dir=os.path.join(out_dir, "loss"),
        metric_every_steps=1,
        num_workers=0,
    )
    overrides = json.loads(os.environ.get("DPT_WORKER_OVERRIDES", "{}"))
    if overrides:
        import dataclasses

        for key in ("inject_faults", "model_widths", "image_size"):
            if key in overrides and overrides[key] is not None:
                overrides[key] = tuple(overrides[key])
        config = dataclasses.replace(config, **overrides)

    from distributedpytorch_tpu.checkpoint import _to_host

    rank = runtime.process_id

    if mode == "restore":
        # Mesh-resharding restore: resume the checkpoint some EARLIER
        # world (possibly of different size) saved, and report the
        # restored params bit-exactly. No training — the assertion is
        # about the restore path alone.
        import dataclasses

        trainer = Trainer(dataclasses.replace(config, checkpoint_name=method))
        with open(os.path.join(out_dir, f"restore_rank{rank}.json"), "w") as f:
            json.dump(
                {
                    "rank": rank,
                    "world": jax.process_count(),
                    "start_epoch": trainer.start_epoch,
                    "params_sha256": _params_sha256(_to_host(trainer.state.params)),
                    "mesh_data": trainer.strategy.mesh.shape["data"],
                },
                f,
            )
        shutdown()
        return

    if mode == "train_only":
        import traceback

        trainer = Trainer(config)
        err = None
        result = None
        try:
            result = trainer.train()
        except Exception as exc:  # noqa: BLE001 — reported to the parent
            err = f"{type(exc).__name__}: {exc}"
            traceback.print_exc()
        with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
            json.dump(
                {
                    "rank": rank,
                    "error": err,
                    "steps": result["steps"] if result else None,
                    "skipped_steps": result["skipped_steps"] if result else None,
                },
                f,
            )
        sys.stdout.flush()
        sys.stderr.flush()
        # no shutdown(): its coordination barrier would block on a peer
        # that (by design of these chaos cases) may already be dead
        os._exit(1 if err else 0)

    trainer = Trainer(config)
    result = trainer.train()

    # Eval equivalence: the sharded evaluator — each
    # process computing only its round-robin share through one grouped
    # sharded dispatch — must reproduce the replicated path's value, and
    # both must be identical on every rank (the plateau scheduler's
    # lockstep depends on it).
    from distributedpytorch_tpu.evaluate import evaluate, evaluate_sharded

    rep_loss, rep_dice = evaluate(
        trainer.eval_step,
        trainer._eval_variables(),
        trainer.val_loader,
        trainer.strategy.place_batch,
    )
    if jax.process_count() > 1:
        assert trainer.grouped_eval_step is not None  # multi-process run
        sh_loss, sh_dice = evaluate_sharded(
            trainer.eval_step,
            trainer.grouped_eval_step,
            trainer._eval_variables(),
            trainer.val_loader,
            trainer.strategy.place_batch,
            trainer.strategy.eval_shard(),
        )
    else:
        # a world-1 launch (the reshard tests' save/restore anchors has
        # no one to share eval with — the grouped path never builds
        sh_loss, sh_dice = rep_loss, rep_dice

    # Batch-assembly consistency: the same jitted reduction of a placed
    # train batch must return the SAME value on every rank. Replica
    # corruption (co-row processes feeding different data into a
    # replicated shard — the round-5 {data:2, stage:2} × 4-process bug)
    # manifests as rank-dependent sums of the "same" global array, which
    # the loss-equality asserts alone cannot catch (the corruption is
    # symmetric across replicas).
    import jax.numpy as jnp

    first = next(iter(trainer.train_loader.epoch_batches(0)))
    placed = trainer.strategy.place_batch(first)
    batch_sum = float(jax.jit(
        lambda b: jnp.sum(b["image"]) + jnp.sum(b["mask"])
    )(placed))

    # _to_host, not bare device_get: FSDP shards params across BOTH
    # processes (non-fully-addressable), and the checkpoint module's
    # gather is the one collective-safe way to materialize them — this
    # is also exactly what the save path runs, so the fingerprint
    # doubles as a check of the allgather itself
    params_host = _to_host(trainer.state.params)
    fingerprint = float(
        sum(float(np.abs(np.asarray(p)).sum()) for p in jax.tree.leaves(params_host))
    )
    non_addressable = sum(
        1
        for leaf in jax.tree.leaves(trainer.state.params)
        if hasattr(leaf, "is_fully_addressable") and not leaf.is_fully_addressable
    )

    # FSDP: prove the allgather-based save restores — rebuild a Trainer
    # from the checkpoint rank 0 wrote (every rank reads it; restored
    # host values re-place under the sharded layout) and compare the
    # gathered params bit-for-bit with the in-memory trained state.
    restore_ok = None
    if method == "FSDP":
        import dataclasses

        trainer2 = Trainer(
            dataclasses.replace(config, checkpoint_name=method)
        )
        assert trainer2.start_epoch == config.epochs
        restored_host = _to_host(trainer2.state.params)
        restore_ok = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree.leaves(params_host), jax.tree.leaves(restored_host)
            )
        )

    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump(
            {
                "rank": rank,
                "fingerprint": fingerprint,
                "params_sha256": _params_sha256(params_host),
                "val_loss": result["val_loss"],
                "replicated_val": [rep_loss, rep_dice],
                "sharded_val": [sh_loss, sh_dice],
                "steps": result["steps"],
                "skipped_steps": result["skipped_steps"],
                "mesh_data": trainer.strategy.mesh.shape["data"],
                "batch_sum": batch_sum,
                "non_addressable_leaves": non_addressable,
                "restore_ok": restore_ok,
            },
            f,
        )
    shutdown()


if __name__ == "__main__":
    main()
