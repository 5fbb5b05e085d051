"""The one trainer: epoch driver shared by every strategy.

Replaces the reference's three ~70-line copies (`fit`/`fit_DP`/`fit_DDP`,
reference utils/train_utils.py:22-248) with a single loop; everything that
differed between them lives in the Strategy object (parallel/strategy.py).

Loop semantics parity (reference train_utils.py:49-92):
  * per-step: forward/backward/Adam with the batch_size loss-scaling quirk
    (inside the jitted step), UNSCALED loss recorded;
  * every 10 steps: append (global_step, wall_time, mean of last ≤10 losses);
  * per-epoch: evaluate → val (Step, Time, Loss) row → plateau scheduler;
  * end: checkpoint + pandas pickles + logfile lines.

Deliberate fixes over the reference (each flagged in SURVEY.md §2):
  * periodic mid-run checkpoints with optimizer/scheduler/step state → real
    crash resume (the reference loses everything before the final epoch);
  * scheduler state is part of the checkpoint, and in multi-process runs the
    val loss driving it is computed identically everywhere (quirk 7's
    rank-divergent lr cannot happen: lr lives in replicated optimizer state);
  * per-epoch reshuffle of the sharded train set (missing set_epoch, §3.2).

Host/device split (SURVEY.md §7 hard-part 2): the epoch loop is a fully
overlapped pipeline. Decoded samples persist across epochs in a
memory-budgeted host cache (data/dataset.SampleCache); stacking and
host→device placement run on a prefetch worker `prefetch_batches` payloads
ahead of the step loop (utils/prefetch.pipelined_placement → the
strategy's `place_work`); the jitted step returns the loss as a device
scalar that LossRecords drains asynchronously at row/epoch boundaries;
and checkpoint serialization+writes run on a background writer thread
(checkpoint.save_checkpoint_async), drained before train() returns. Each
phase is observable through the step-timeline tracer (utils/trace.py,
``--trace-timeline``).

Resilience (docs/RELIABILITY.md): non-finite-loss policies riding the
metrics readback (``abort`` / ``rollback``-to-checkpoint / ``skip``),
bounded-backoff retries for transient decode/placement failures, a
dispatch watchdog that dumps the step timeline and checkpoints-and-stops,
and a deterministic fault-injection harness (utils/faults.py) proving
each path. Checkpoint saves build their payload on EVERY rank (the host
snapshot is a collective allgather when state is sharded across
processes) with only the file write rank-0-gated.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import signal
import time
from typing import Optional

import jax
import numpy as np

from distributedpytorch_tpu.checkpoint import (
    load_checkpoint,
    save_checkpoint,
    save_checkpoint_async,
)
from distributedpytorch_tpu.config import TrainConfig
from distributedpytorch_tpu.data import (
    DataLoader,
    SampleCache,
    build_dataset,
    seeded_split,
)
from distributedpytorch_tpu.evaluate import evaluate, evaluate_sharded
from distributedpytorch_tpu.obs import defs as obsm
from distributedpytorch_tpu.obs import flight
from distributedpytorch_tpu.ops.optim import get_learning_rate, set_learning_rate
from distributedpytorch_tpu.ops.schedule import ReduceLROnPlateau
from distributedpytorch_tpu.train.steps import create_train_state
from distributedpytorch_tpu.utils import faults
from distributedpytorch_tpu.utils.faults import NonFiniteLossError, StepWatchdog
from distributedpytorch_tpu.utils.metrics import LossRecords, StepReadout
from distributedpytorch_tpu.utils.prefetch import (
    pipelined_placement,
    stacked_work,
)
from distributedpytorch_tpu.utils.trace import StepTimeline

logger = logging.getLogger(__name__)


class Trainer:
    def __init__(
        self,
        config: TrainConfig,
        dataset=None,
        strategy=None,
        rng: Optional[jax.Array] = None,
    ):
        # local import: parallel/ imports train/steps, so importing it at
        # module scope would be circular
        from distributedpytorch_tpu.parallel import build_strategy

        from distributedpytorch_tpu.models import create_model, model_entry

        self.config = config
        # the model table's entry: loss, batch fields, counters, data set
        self.entry = model_entry(config)
        self.strategy = strategy or build_strategy(config)
        self.dataset = dataset if dataset is not None else self._build_dataset()
        self.rng = rng if rng is not None else jax.random.key(config.seed)
        # arm the fault-injection harness (inert when no specs). install()
        # is idempotent per spec list: fit_with_restarts rebuilds the
        # Trainer after a crash and already-fired counts must survive.
        faults.install(config.inject_faults)
        if config.nonfinite_policy not in ("abort", "rollback", "skip"):
            raise ValueError(
                f"nonfinite_policy must be abort|rollback|skip, got "
                f"{config.nonfinite_policy!r}"
            )
        # rollback budget for the non-finite-loss policy (counts down
        # across the run; NOT reset per epoch — a persistently-NaN run
        # must eventually abort)
        self._rollback_budget = int(config.rollback_retries)
        self._skipped_steps = 0
        # step-timeline tracer (utils/trace.py): JSONL off unless
        # configured (spans still feed the flight recorder's ring). Every
        # rank writes its OWN file — rank 0 the configured path, rank R
        # `<path>.rankR` — so the trace hub (obs/trace_hub.py) can merge
        # them into one rank-disambiguated Perfetto timeline instead of
        # ranks interleaving torn lines into one file.
        rank = jax.process_index()
        timeline_path = config.timeline_path
        if timeline_path and rank != 0:
            timeline_path = f"{timeline_path}.rank{rank}"
        self.tracer = StepTimeline(timeline_path, rank=rank)
        # flight recorder (obs/flight.py): always-on ring; the dump path
        # defaults under this run's log dir unless the caller/env chose one
        flight.set_rank(rank)
        flight.set_default_dump_path(os.path.join(
            config.log_dir, f"flight_{config.method_tag}_rank{rank}.json"
        ))
        # registry counters are process-lifetime; the host-cache gauge
        # needs per-run deltas, so remember where this run started
        self._cache_counted = (0, 0)
        # on-demand device profile over a step range (--profile-steps)
        self._profiling = False
        self.metrics_server = None
        # ONE epoch-persistent decoded-sample cache shared by the train and
        # val loaders (they index the same dataset)
        self.sample_cache = (
            SampleCache(int(config.host_cache_mb) * 2**20)
            if config.host_cache_mb > 0
            else None
        )
        # futures of in-flight async checkpoint writes; drained (and their
        # errors surfaced) when train() ends
        self._ckpt_futures = []
        # per-rank heartbeat (dist/health.py): armed in train() when
        # config.heartbeat_dir is set (the elastic supervisor's failure
        # detector); None otherwise
        self._heartbeat = None

        # model + state
        self.model, init_fn = create_model(config)
        # names of what the model's loss counts: they ride back with the
        # loss in one array (train/steps.pack_readout) and reach the
        # registry when LossRecords reads that loss (StepReadout)
        self.counter_names = tuple(self.entry.counters(self.model))
        # which path attention takes is decided from platform and shapes
        # (ops/sequence.attention_path); said once here, outside any trace
        self.attention_kernel_blocks = self.entry.attention_kernel_blocks(
            self.model, config)
        obsm.ATTENTION_KERNEL_BLOCKS.set(self.attention_kernel_blocks)
        # likewise what the blocks' recomputation keeps, from shapes and
        # the device's memory (models/twotower.kept_budget)
        self.kept_activation_bytes = self.entry.kept_activation_bytes(
            self.model, config)
        obsm.KEPT_ACTIVATION_BYTES.set(self.kept_activation_bytes)
        # and which path the held experts' weight gradients take
        # (ops/moe.wgrad_path)
        self.moe_wgrad_kernel_layers = self.entry.moe_wgrad_kernel_layers(
            self.model, config)
        obsm.MOE_WGRAD_KERNEL_LAYERS.set(self.moe_wgrad_kernel_layers)
        params, model_state = init_fn(
            self.rng, (config.image_size[1], config.image_size[0])
        )
        # BatchNorm state threads through the pipeline schedules
        # (parallel/pipeline.py): stage functions apply their segments
        # with mutable batch_stats per microbatch and the stage-axis psum
        # of the deltas reassembles the replicated running stats — no
        # BatchNorm-vs-MP guard anymore.
        lr0 = self.strategy.lr_for(config.learning_rate)
        # the precision policy (ops/precision.py, --dtype) owns the param
        # cast-in and, under bf16_params, wraps the optimizer with f32
        # master weights living in opt_state
        self.policy = self.strategy.policy
        state, self.tx = create_train_state(
            params, lr0, config.weight_decay, model_state=model_state,
            policy=self.policy, adam_b2=self.entry.adam_b2,
        )
        self.scheduler = ReduceLROnPlateau(
            lr=lr0, patience=config.plateau_patience, factor=config.plateau_factor
        )
        self.start_epoch = 0
        # scalar trainer state that must survive resume (checkpointed as
        # train_meta): --save-best's best metrics, early-stop patience
        self._best_dice = float("-inf")
        self._best_loss = float("inf")
        self._stale_epochs = 0

        if config.checkpoint_name:
            self._restore(config.checkpoint_name, state)
            state = self._restored_state or state

        self.state = self.strategy.place_state(state)

        # data split + loaders (ONE seeded split for every strategy — the
        # deliberate fix of reference quirk 5)
        train_idx, val_idx = seeded_split(
            len(self.dataset), config.val_fraction, seed=0
        )
        if len(val_idx) < config.batch_size and self.strategy.is_main:
            # val loader drops ragged batches (reference train_utils.py:42),
            # so a val split smaller than one batch evaluates NOTHING and
            # val loss/Dice come out NaN — the reference fails the same way,
            # silently; at least say so.
            logger.warning(
                "validation split has %d samples < batch size %d — every "
                "val batch is dropped and val loss/Dice will be NaN; raise "
                "-v/--validation or lower -b",
                len(val_idx), config.batch_size,
            )
        self.train_loader = DataLoader(
            self.dataset,
            indices=train_idx,
            batch_size=config.batch_size,
            shuffle=True,
            drop_last=self.strategy.drop_last_train,
            seed=config.seed,
            shard=self.strategy.data_shard(),
            num_workers=config.num_workers,
            cache=self.sample_cache,
            tracer=self.tracer,
            max_retries=config.data_retries,
            retry_backoff_s=config.retry_backoff_s,
        )
        # Val: drop_last=True (reference train_utils.py:42). The loader is
        # unsharded — batch formation is identical everywhere — but
        # multi-process strategies ASSIGN whole batches round-robin
        # (evaluate_sharded): each process computes 1/world of the val set
        # and every process reads back identical per-batch metrics from
        # the grouped dispatch, so the plateau scheduler stays in lockstep
        # (the reference's rank-divergent lr, quirk 7, cannot happen) with
        # no redundant work.
        self.val_loader = DataLoader(
            self.dataset,
            indices=val_idx,
            batch_size=config.batch_size,
            shuffle=False,
            drop_last=True,
            num_workers=config.num_workers,
            cache=self.sample_cache,
            max_retries=config.data_retries,
            retry_backoff_s=config.retry_backoff_s,
        )

        self.train_step = self.strategy.build_train_step(self.model, self.tx)
        # K>1: fuse K optimizer steps into one dispatch (lax.scan); the
        # single-step path still handles the ragged tail of each epoch.
        self.k_dispatch = max(1, int(config.steps_per_dispatch))
        self.grad_accum = max(1, int(config.grad_accum))
        if config.early_stop_patience < 0:
            raise ValueError(
                f"early_stop_patience must be >= 0 (0 = off), got "
                f"{config.early_stop_patience}"
            )
        if self.grad_accum > 1 and "image" not in self.entry.batch.fields:
            raise ValueError(
                "--grad-accum is exact accumulation of the image loss's "
                "batch statistics (train/steps.make_accum_train_step); "
                f"model_arch {config.model_arch!r} has no such step"
            )
        if self.k_dispatch > 1 and self.grad_accum > 1:
            raise ValueError(
                "--steps-per-dispatch and --grad-accum both stack loader "
                "batches with conflicting step semantics — choose one"
            )
        if config.nonfinite_policy == "skip" and (
            self.k_dispatch > 1 or self.grad_accum > 1
        ):
            raise ValueError(
                "--nonfinite-policy skip discards one STEP's update, which "
                "a fused dispatch / accumulated step cannot isolate — use "
                "rollback or abort with --steps-per-dispatch/--grad-accum"
            )
        self.multi_step = (
            self.strategy.build_multi_train_step(self.model, self.tx)
            if self.k_dispatch > 1
            else None
        )
        self.accum_step = (
            self.strategy.build_accum_train_step(self.model, self.tx)
            if self.grad_accum > 1
            else None
        )
        self.eval_step = self.strategy.build_eval_step(self.model)
        # grouped variant only where there are processes to share with
        self.grouped_eval_step = (
            self.strategy.build_grouped_eval_step(self.model)
            if self.strategy.eval_shard().world > 1
            else None
        )
        self.records = LossRecords(
            config.method_tag,
            config.loss_dir,
            every=config.metric_every_steps,
            tracer=self.tracer,
            nonfinite_hook=self._on_nonfinite_loss,
        )
        if getattr(self, "_restored_records", None):
            # a resumed run appends to the run's metric history instead of
            # overwriting the loss pickles with only its post-resume rows
            self.records.load_state_dict(self._restored_records)

    # ------------------------------------------------------------------
    def _build_dataset(self):
        if self.entry.dataset is not None:
            return self.entry.dataset(self.config)
        if self.config.synthetic_samples > 0:
            from distributedpytorch_tpu.data import SyntheticSegmentationDataset

            return SyntheticSegmentationDataset(
                length=self.config.synthetic_samples,
                newsize=self.config.image_size,
                seed=self.config.seed,
            )
        images = os.path.join(self.config.data_dir, self.config.images_subdir)
        masks = os.path.join(self.config.data_dir, self.config.masks_subdir)
        return build_dataset(images, masks, self.config.image_size)

    def _eval_variables(self):
        """What the eval step consumes: bare params for pure models, the
        full variables dict for stateful ones (running BatchNorm stats)."""
        if self.state.model_state is not None:
            return {
                "params": self.state.params,
                "batch_stats": self.state.model_state,
            }
        return self.state.params

    def _ckpt_path(self, tag: Optional[str] = None) -> str:
        tag = tag or self.config.method_tag
        return os.path.join(self.config.checkpoint_dir, f"{tag}.ckpt")

    def _restore(self, name: str, state):
        """Load a checkpoint by name (reference -c flag, train.py:42-43 —
        with the backslash path bug fixed and full-state resume added).

        Precision-aware (the ckpt-dtype-drift contract, docs/ANALYSIS.md):
        the manifest's ``precision`` entry is peeked BEFORE any target
        structure is built, a checkpoint saved under a different --dtype
        is converted through the policy seams (exact via the f32 master
        weights in either direction), and every restored params tree is
        re-cast loudly when its dtype drifted — never silently retraced.
        """
        from distributedpytorch_tpu.checkpoint import resolve_checkpoint
        from distributedpytorch_tpu.ops.precision import (
            POLICIES,
            convert_checkpoint_state,
            ensure_restored_dtypes,
        )

        path = resolve_checkpoint(name, self.config.checkpoint_dir)
        self._restored_state = None
        self._restored_records = None
        if path.endswith(".pth"):
            # interop: reference-format weights (no optimizer/epoch state)
            from distributedpytorch_tpu.checkpoint import load_weights

            params = load_weights(path, state.params)
            if self.policy.master_weights:
                # weights-only restore under bf16_params: re-seed the
                # optimizer so its f32 master IS the imported weights —
                # the fresh-init master would silently win otherwise
                state = state.replace(opt_state=self.tx.init(params))
            params = ensure_restored_dtypes(
                params, self.policy, f"pth restore {path}"
            )
            self._restored_state = state.replace(params=params)
            logger.info("Loaded reference .pth weights from %s", path)
            return
        from distributedpytorch_tpu.checkpoint import read_payload
        from distributedpytorch_tpu.ops.precision import get_policy

        # ONE file read: the manifest decides the target structures, and
        # the same payload then binds them (a multi-GB checkpoint must
        # not be deserialized twice per resume)
        payload = read_payload(path)
        saved_name = (payload.get("topology") or {}).get("precision")
        if saved_name is None:
            # pre-policy checkpoints carried f32 params + a plain Adam
            # state — structurally the bf16 policy
            saved_policy = POLICIES["bf16"]
        else:
            # unknown names fail LOUDLY (a newer build's policy, a
            # corrupted manifest) — guessing a structure here would die
            # later in an opaque from_state_dict mismatch
            saved_policy = get_policy(saved_name)
        opt_target = state.opt_state
        if saved_policy.master_weights != self.policy.master_weights:
            # the saved opt_state's STRUCTURE differs (the master-weight
            # wrapper nests it) — build the saved-side target to restore
            # into, then convert below
            from distributedpytorch_tpu.ops.optim import adam_l2

            saved_tx = saved_policy.wrap_optimizer(
                adam_l2(self.scheduler.lr, self.config.weight_decay,
                        b2=self.entry.adam_b2)
            )
            # abstract target: from_state_dict needs only the STRUCTURE,
            # so eval_shape builds it without a host copy of the params
            # or throwaway f32 master/m/v allocations (~3x param bytes
            # on the restore path of a large model)
            opt_target = jax.eval_shape(saved_tx.init, state.params)
        restored = load_checkpoint(
            path, state.params, opt_target, state.model_state,
            payload=payload,
        )
        # params in the SAVED dtype, before the policy conversion casts —
        # the exact master seed for weights-only checkpoints below
        raw_params = restored["params"]
        restored["params"], restored["opt_state"] = convert_checkpoint_state(
            saved_policy,
            self.policy,
            restored["params"],
            restored["opt_state"],
            where=f"restore {path}",
        )
        if restored["opt_state"] is None and self.policy.master_weights:
            # weights-only native checkpoint (no opt_state saved) under a
            # master-weight policy: re-seed the optimizer from the SAVED
            # params so the f32 master IS the restored weights — same
            # hazard the .pth branch guards: the fresh-init master would
            # otherwise revert the params at the first update
            logger.warning(
                "restore %s: checkpoint carries no optimizer state — "
                "re-seeding the %r master weights from the restored "
                "params", path, self.policy.name,
            )
            restored["opt_state"] = self.tx.init(raw_params)
        # Mesh-resharding restore (docs/RELIABILITY.md "Elastic runs"):
        # checkpoints hold FULL host arrays (every sharded leaf was
        # allgathered at save time), so restoring under a DIFFERENT
        # topology — N→M processes after an elastic shrink, another
        # strategy's mesh shape — just re-places them under the current
        # sharding (place_state). Say so when it happens: a silent
        # layout change is the kind of thing a post-incident reader
        # needs one grep to find.
        saved_topo = restored.get("topology")
        if saved_topo is not None:
            from distributedpytorch_tpu.checkpoint import save_topology

            current_topo = {**save_topology(), **self.strategy.topology()}
            # a --dtype change is a precision conversion, not a mesh
            # reshard — convert_checkpoint_state logged it above
            current_topo.pop("precision", None)
            if {k: saved_topo.get(k) for k in current_topo} != current_topo:
                logger.warning(
                    "mesh-resharding restore: checkpoint saved under %s, "
                    "restoring onto %s — gathered host arrays re-placed "
                    "under the current mesh",
                    saved_topo, current_topo,
                )
        new_state = state.replace(params=restored["params"], step=restored["step"])
        if restored["opt_state"] is not None:
            new_state = new_state.replace(opt_state=restored["opt_state"])
        if restored["model_state"] is not None:
            new_state = new_state.replace(model_state=restored["model_state"])
        if restored["scheduler"]:
            self.scheduler.load_state_dict(restored["scheduler"])
            new_state = new_state.replace(
                opt_state=set_learning_rate(new_state.opt_state, self.scheduler.lr)
            )
        self.start_epoch = restored["epoch"]
        meta = restored.get("train_meta") or {}
        self._best_dice = float(meta.get("best_dice", float("-inf")))
        self._best_loss = float(meta.get("best_loss", float("inf")))
        self._stale_epochs = int(meta.get("stale_epochs", 0))
        self._restored_state = new_state
        self._restored_records = restored.get("records")
        logger.info("Resumed from %s at epoch %d", path, self.start_epoch)

    def _save_needs_all_ranks(self) -> bool:
        """True iff the checkpoint snapshot is a COLLECTIVE: some state
        leaf is sharded across processes (FSDP/TP pods), so every rank
        must participate in its allgather. Replicated-state strategies
        (DDP) answer False and non-main ranks skip the payload build
        entirely — a full-tree device_get per epoch is pure
        waste. Identical on every rank (the
        sharding layout is), so the skip cannot desync collectives;
        memoized — the layout is fixed for the trainer's lifetime."""
        cached = getattr(self, "_save_collective_memo", None)
        if cached is not None:
            return cached
        if jax.process_count() == 1:
            result = False
        else:
            from distributedpytorch_tpu.checkpoint import (
                needs_collective_gather,
            )

            result = any(
                needs_collective_gather(x)
                for x in jax.tree.leaves(
                    (self.state.params, self.state.opt_state,
                     self.state.model_state)
                )
            )
        self._save_collective_memo = result
        return result

    def _save(self, epoch: int) -> None:
        # dedup on EVERY rank (the decision is epoch-driven, identical
        # everywhere). No blanket is_main gate: when state is sharded
        # across processes the host snapshot inside the save is a
        # COLLECTIVE allgather, so all ranks must reach it in lockstep —
        # but for replicated state non-main ranks have nothing to
        # contribute and skip the (expensive) payload build; the file
        # write itself is always rank-0-gated (_save_tagged).
        if epoch == getattr(self, "_last_saved_epoch", None):
            return
        self._last_saved_epoch = epoch
        if not self.strategy.is_main and not self._save_needs_all_ranks():
            return
        self._save_tagged(self._ckpt_path(), epoch)

    def _save_tagged(self, path: str, epoch: int) -> None:
        """One checkpoint save — async (host snapshot inline, serialize +
        write on the background writer) unless config.async_checkpoint is
        off. Every rank builds the payload (collective when sharded — see
        _save); only the main process writes the file, retaining the
        newest config.keep_checkpoints copies. Async futures are drained
        when train() ends, so the file is durable before anything outside
        the run can read it."""
        if self.config.async_checkpoint:
            # surface a failed EARLIER write now, not at the end of the
            # run (a disk-full at epoch 1 of 100 must not let 99 epochs
            # believe their checkpoints are landing), and bound the queue:
            # with >2 writes still in flight the filesystem is stalled —
            # block on the oldest (the synchronous behavior) rather than
            # accumulate full-model payloads in RAM without limit
            for fut in [f for f in self._ckpt_futures if f.done()]:
                self._ckpt_futures.remove(fut)
                fut.result()  # raises if the write failed
            while len(self._ckpt_futures) > 2:
                self._ckpt_futures.pop(0).result()
        flight.record("phase", name="checkpoint", epoch=epoch)
        save_fn = (
            save_checkpoint_async
            if self.config.async_checkpoint
            else save_checkpoint
        )
        fut = save_fn(
            path,
            self.state.params,
            self.state.opt_state,
            self.scheduler.state_dict(),
            step=int(self.state.step),
            epoch=epoch,
            records_state=self.records.state_dict(),
            model_state=self.state.model_state,
            train_meta=self._train_meta(),
            keep=self.config.keep_checkpoints,
            write=self.strategy.is_main,
            topology=self.strategy.topology(),
        )
        if fut is not None:
            self._ckpt_futures.append(fut)

    def _drain_checkpoint_futures(self, raise_errors: bool) -> None:
        """Block until every queued async checkpoint write is on disk.
        Write errors re-raise when asked (normal exit) and are logged
        otherwise (already unwinding another exception — masking it with
        a secondary I/O error would hide the real failure)."""
        futures, self._ckpt_futures = self._ckpt_futures, []
        first_exc = None
        for fut in futures:
            try:
                fut.result()
            except Exception as exc:  # noqa: BLE001 — surfaced below
                logger.exception("async checkpoint write failed")
                first_exc = first_exc or exc
        if first_exc is not None and raise_errors:
            raise first_exc

    def _train_meta(self) -> dict:
        return {
            "best_dice": self._best_dice,
            "best_loss": self._best_loss,
            "stale_epochs": self._stale_epochs,
        }

    # -- step-level failure policies (docs/RELIABILITY.md) -------------------
    def _finite_agreed(self, loss) -> bool:
        """Policy ``skip``'s per-step finiteness check, made COLLECTIVE
        on multi-process meshes: a non-finite loss can be rank-local (a
        hardware bitflip on one chip, an injected ``nan_loss@R``), and a
        rank that discards its update while its peers apply theirs has
        silently forked the replicas — the exact divergence the policy
        exists to prevent. One tiny allgather per step, only under
        ``skip`` (which already pays a per-step host sync) and only with
        >1 process; ANY rank non-finite → every rank discards."""
        finite = bool(np.isfinite(float(loss)))
        if jax.process_count() == 1:
            return finite
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([0 if finite else 1], np.int32)
        )
        return not bool(np.any(flags))

    def _on_nonfinite_loss(self, step: int, value: float) -> None:
        """LossRecords' readback hook: a train loss drained to host came
        back NaN/Inf. Free on healthy runs — detection rides the drain
        the metrics pipeline already does. ``skip`` handles non-finite
        steps synchronously in the loop (run_one), so reaching this hook
        under it only happens for paths skip cannot guard; log, don't
        kill. ``abort``/``rollback`` raise — the epoch loop catches for
        rollback, everything else propagates."""
        if self.config.nonfinite_policy == "skip":
            logger.warning(
                "non-finite loss %s at step %d reached the metrics drain "
                "under policy 'skip' (unguarded path) — continuing", value, step,
            )
            return
        raise NonFiniteLossError(
            f"non-finite train loss {value} at step {step} "
            f"(policy={self.config.nonfinite_policy})"
        )

    def _try_rollback(self, exc: Exception) -> bool:
        """``rollback`` policy: reload the newest intact checkpoint
        in-place (state, scheduler, metric history, epoch) and let the
        epoch loop redo from there. False = cannot roll back (wrong
        policy, budget exhausted, or nothing to restore) — the caller
        re-raises."""
        cfg = self.config
        if cfg.nonfinite_policy != "rollback":
            return False
        if jax.process_count() > 1:
            # in-place rollback is single-process only, like
            # fit_with_restarts' restarts: ranks would race rank 0's
            # in-flight write/rotate (non-main ranks have no futures to
            # drain) and could restore DIFFERENT epochs — divergent
            # collective programs, deadlocked job. Abort instead; the
            # launcher's restart loop re-rendezvouses all ranks against
            # a settled checkpoint file.
            logger.error(
                "rollback policy is single-process; multi-process runs "
                "abort and rely on the launcher's restart loop"
            )
            return False
        if self._rollback_budget <= 0:
            logger.error(
                "rollback budget exhausted (%d rollbacks used) — aborting",
                cfg.rollback_retries,
            )
            return False
        # the checkpoint we are about to read may still be queued on the
        # background writer — make it durable first
        self._drain_checkpoint_futures(raise_errors=False)
        path = self._ckpt_path()
        from distributedpytorch_tpu.checkpoint import retained_checkpoints

        # any retained candidate will do — load_checkpoint's fallback
        # walks the chain, and a crash between rotate and rename can
        # leave only `path.1` on disk with the live slot empty
        if not retained_checkpoints(path):
            logger.error("rollback requested but no checkpoint at %s", path)
            return False
        self._rollback_budget -= 1
        obsm.TRAIN_ROLLBACKS.inc()
        flight.record("rollback", error=str(exc)[:200],
                      retries_left=self._rollback_budget)
        logger.warning(
            "%s — rolling back to %s (%d retries left)",
            exc, path, self._rollback_budget,
        )
        self._restore(cfg.method_tag, self.state)
        self.state = self.strategy.place_state(self._restored_state)
        if self._restored_records:
            self.records.load_state_dict(self._restored_records)
        else:  # pre-records checkpoint: drop the poisoned history
            self.records = LossRecords(
                cfg.method_tag,
                cfg.loss_dir,
                every=cfg.metric_every_steps,
                tracer=self.tracer,
                nonfinite_hook=self._on_nonfinite_loss,
            )
        self._last_saved_epoch = None
        return True

    def _watchdog_timeout(self) -> None:
        """StepWatchdog expiry (watchdog thread): dump the step-timeline
        tracer's per-phase spans AND the flight recorder's ring for
        diagnosis, then request a checkpoint-and-stop through the same
        collective agreement the signal handler uses. Best-effort by
        nature — a host truly wedged inside a native call cannot
        checkpoint; the dumps are then the run's last diagnostic."""
        summary = {
            k: v for k, v in self.tracer.summary().items() if v is not None
        }
        logger.error(
            "dispatch watchdog: step loop made no progress for %.1fs — "
            "requesting checkpoint-and-stop. Per-phase timeline: %s",
            self.config.step_timeout_s,
            json.dumps(summary) if summary else "(no spans recorded)",
        )
        recent = self.tracer.events()[-24:]
        if recent:
            logger.error("recent timeline spans: %s", json.dumps(recent))
        elif not self.tracer.enabled:
            logger.error(
                "step-timeline tracing is off — run with --trace-timeline "
                "to capture per-phase spans for watchdog diagnosis"
            )
        self.tracer.flush()
        # the post-mortem artifact: the ring's tail identifies the phase
        # the loop wedged in (docs/OBSERVABILITY.md lifecycle)
        flight.dump(
            "watchdog_timeout",
            extra={"step_timeout_s": self.config.step_timeout_s,
                   "timeline_summary": summary},
        )
        self._stop_requested = True

    def _profile_tick(self, global_step: int) -> None:
        """--profile-steps N:M — start the jax.profiler device trace
        entering step N+1, stop once step M has run. Two integer
        compares per iteration when armed; rank 0 only (one profile per
        run, like the whole-run --profile-dir capture)."""
        lo, hi = self.config.profile_steps
        if not self._profiling and lo <= global_step < hi:
            out = self.config.profile_dir or os.path.join(
                self.config.log_dir, "profile"
            )
            logger.info(
                "profiler: capturing device trace for steps [%d, %d) → %s",
                lo, hi, out,
            )
            jax.profiler.start_trace(out)
            # one clock with the step timeline (utils/trace.py): dpt_sync
            self.tracer.profile_started()
            self._profiling = True
            flight.record("profile", action="start", step=global_step)
        elif self._profiling and global_step >= hi:
            self.tracer.profile_stopped()
            jax.profiler.stop_trace()
            self._profiling = False
            flight.record("profile", action="stop", step=global_step)

    def _update_cache_metrics(self) -> None:
        """Epoch-boundary host-cache accounting: registry counters get
        the per-run delta (they are process-lifetime), the gauge gets
        the run's hit rate."""
        if self.sample_cache is None:
            return
        hits, misses = self.sample_cache.hits, self.sample_cache.misses
        h0, m0 = self._cache_counted
        if hits > h0:
            obsm.CACHE_HITS.inc(hits - h0)
        if misses > m0:
            obsm.CACHE_MISSES.inc(misses - m0)
        self._cache_counted = (hits, misses)
        total = hits + misses
        if total:
            obsm.CACHE_HIT_RATIO.set(hits / total)

    # ------------------------------------------------------------------
    def _readout(self, loss):
        """A step's second output as LossRecords takes it: the loss
        itself, or, for a model that counts (``counter_names``), the
        packed ``[loss, *counters]`` behind a ``StepReadout`` whose one
        readback feeds the loss to the records and the counters to the
        registry."""
        if not self.counter_names:
            return loss
        return StepReadout(loss, self.counter_names)

    def _record(self, loss, n_imgs: int, global_step: int, pbar) -> None:
        rows_before = len(self.records.train_rows)
        self.records.record_train(global_step, loss, n_imgs)
        pbar.update(n_imgs)
        if len(self.records.train_rows) > rows_before:
            pbar.set_postfix(loss=f"{self.records.train_rows[-1][2]:.4f}")

    def _install_signal_handler(self):
        """Failure detection the reference lacks (SURVEY.md §5: a mid-run
        crash loses everything): on SIGTERM/SIGINT, finish the in-flight
        step, checkpoint full state, then exit — so preemption (the normal
        way TPU jobs die) costs at most one epoch of progress, resumable
        via ``-c <method>``.

        Signal handlers are main-thread-only; if train() runs on another
        thread the install fails and this feature is simply OFF (signals
        then take their default action — no graceful checkpoint).

        Multi-process runs stop only at epoch boundaries, and only by
        AGREEMENT (`_stop_agreed` allgathers the flag): a rank that broke
        out mid-epoch on a local signal would abandon the collectives its
        peers' jitted steps are waiting on and hang the job.
        """
        self._stop_requested = False
        self._prev_handlers = {}

        def request_stop(signum, frame):
            self._stop_requested = True
            # the preemption post-mortem: what the run was doing when the
            # scheduler pulled the plug (dump is never-raises by contract)
            flight.record("signal", signum=int(signum))
            flight.dump("sigterm" if signum == signal.SIGTERM else
                        f"signal_{int(signum)}")
            logger.info(
                "Signal %d: will checkpoint and stop at the next step", signum
            )

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev_handlers[sig] = signal.signal(sig, request_stop)
            except ValueError:  # not in main thread — feature unavailable
                pass

    def _restore_signal_handler(self):
        for sig, handler in self._prev_handlers.items():
            signal.signal(sig, handler)

    def _stop_agreed(self, global_step: int = -1) -> bool:
        """Collective stop decision: True iff ANY process saw a signal.
        One tiny allgather per epoch — never called per step.

        The same allgather carries each rank's step counter — the
        cross-rank step-agreement check of the elastic health layer
        (dist/health.py): ranks that reach this epoch boundary at
        DIFFERENT global steps are executing divergent programs (a
        skipped update that wasn't agreed, a loader desync), which
        would otherwise surface as replica drift or a wedged collective
        far from the cause. On divergence every rank sees the same
        allgathered evidence, so all mark their beat ``desynced`` (the
        supervisor's classifier keys on it), log ONE line, and stop
        together — an agreed teardown instead of a hang."""
        if jax.process_count() == 1:
            return self._stop_requested
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray(
                [1 if self._stop_requested else 0, int(global_step)],
                np.int32,
            )
        )
        steps = flags[:, 1]
        if global_step >= 0 and len(set(int(s) for s in steps)) > 1:
            logger.error(
                "rank %d: desynced at step agreement — per-rank steps %s",
                jax.process_index(), [int(s) for s in steps],
            )
            if self._heartbeat is not None:
                self._heartbeat.mark("desynced")
            return True
        return bool(np.any(flags[:, 0]))

    def train(self) -> dict:
        """Run the configured epochs; signal handlers are scoped to the run
        (try/finally: an exception mid-epoch must not leave the process
        uninterruptible). Every queued async checkpoint write is drained
        before returning OR raising — a crash-restart rebuilds the next
        Trainer from the checkpoint file, which must be fully on disk by
        then."""
        self._install_signal_handler()
        if self.config.heartbeat_dir:
            from distributedpytorch_tpu.dist.health import Heartbeat

            self._heartbeat = Heartbeat(
                self.config.heartbeat_dir,
                jax.process_index(),
                self.config.heartbeat_interval_s,
            ).start()
            self._heartbeat.update(self.start_epoch, int(self.state.step))
        if self.config.metrics_port is not None:
            from distributedpytorch_tpu.obs.http import (
                build_fingerprint,
                start_metrics_server,
            )

            # rank R binds port+R so every rank of a multi-process job is
            # its own scrape target; port 0 stays 0 (ephemeral — tests)
            port = self.config.metrics_port
            if port:
                port += jax.process_index()
            self.metrics_server = start_metrics_server(
                port, fingerprint=build_fingerprint(self.config)
            )
            logger.info("metrics: serving /metrics on port %d",
                        self.metrics_server.port)
        ok = False
        try:
            result = self._run()
            ok = True
            return result
        finally:
            self._restore_signal_handler()
            if getattr(self, "_watchdog", None) is not None:
                self._watchdog.stop()
            if self._profiling:  # run ended inside the --profile-steps range
                self.tracer.profile_stopped()
                try:
                    jax.profiler.stop_trace()
                finally:
                    self._profiling = False
            if self.metrics_server is not None:
                self.metrics_server.close()
            try:
                # flush BEFORE draining checkpoints: a failed write
                # raises out of the drain, and the final epoch's
                # timeline spans are most valuable exactly when
                # diagnosing that failing run
                self.tracer.flush()
                if self._heartbeat is not None:
                    # keep BEATING through the final drain (a long last
                    # write must not read as a frozen process to the
                    # supervisor's beat-age rule) but leave steady-state
                    # timing: the drain makes no step progress and must
                    # not trip the progress-timeout hang rule either
                    self._heartbeat.timed = False
                # the final drain is a HARD error boundary on a clean
                # run: a failed write of the LAST save has no "next
                # save" to surface it, so it must raise here, out of
                # train() itself
                self._drain_checkpoint_futures(raise_errors=ok)
            finally:
                if self._heartbeat is not None:
                    self._heartbeat.stop()

    def _run(self) -> dict:
        cfg = self.config
        n_train = self.train_loader.num_samples()
        logger.info(
            "Training %s: %d epochs, global batch %d, lr %.2e, %d train "
            "batches/shard, %d attention blocks on the fused kernel, %d bytes "
            "of named activations kept a step, %d expert layers' weight "
            "gradients on the grouped kernel",
            cfg.train_method,
            cfg.epochs,
            self.strategy.global_batch_size,
            get_learning_rate(self.state.opt_state),
            len(self.train_loader),
            self.attention_kernel_blocks,
            self.kept_activation_bytes,
            self.moe_wgrad_kernel_layers,
        )
        # whole-run capture only when no step range was asked for — the
        # two would race one another's start/stop on the same profiler
        whole_run_profile = (
            cfg.profile_dir and cfg.profile_steps is None
            and self.strategy.is_main
        )
        profile_by_steps = cfg.profile_steps is not None and self.strategy.is_main
        if whole_run_profile:
            jax.profiler.start_trace(cfg.profile_dir)
            self.tracer.profile_started()

        from tqdm import tqdm

        global_step = int(self.state.step)
        val_loss = float("nan")
        val_dice = float("nan")
        stopped_early = False
        skip_guard = cfg.nonfinite_policy == "skip"
        # dispatch watchdog (docs/RELIABILITY.md): armed per step-loop
        # iteration, paused across the non-step phases (eval, end-of-epoch
        # checkpointing) whose duration is unrelated to step health;
        # stopped in train()'s finally
        self._watchdog = None
        if cfg.step_timeout_s > 0:
            self._watchdog = StepWatchdog(
                cfg.step_timeout_s, self._watchdog_timeout
            )
            self._watchdog.start()
        watchdog = self._watchdog
        # while, not for: the rollback policy rewinds `epoch` to the
        # restored checkpoint mid-run (NonFiniteLossError handler below).
        # `untimed_epoch` pins the FIRST executed epoch (where every
        # executable shape compiles) for the watchdog exemption — it
        # deliberately does NOT follow a rollback's start_epoch rewind:
        # redone epochs run on warm executables and stay watched.
        epoch = self.start_epoch
        untimed_epoch = self.start_epoch
        while epoch < cfg.epochs:
            try:
                # tqdm parity (reference train_utils.py:57): per-epoch image
                # bar, main process only. Postfix shows the mean-of-last-10
                # row loss — NOT the per-step loss, which would force a
                # device sync per step. exact images this epoch will yield:
                # drop_last trims the ragged tail, otherwise every shard
                # sample appears exactly once
                with tqdm(
                    total=min(n_train, len(self.train_loader) * cfg.batch_size),
                    desc=f"Epoch {epoch + 1}/{cfg.epochs}",
                    unit=self.entry.batch.unit,
                    disable=not self.strategy.is_main,
                    leave=False,
                ) as pbar:
                    def run_one(batch, placed=None):
                        nonlocal global_step
                        n_imgs = self.entry.batch.rows(batch)
                        if placed is None:
                            placed = self.strategy.place_batch(batch)
                        # policy 'skip' holds the pre-step state so a
                        # non-finite step's update can be discarded
                        # (donation is off under it — _state_donation)
                        prev_state = self.state if skip_guard else None
                        with self.tracer.span("dispatch", step=global_step + 1,
                                              epoch=epoch, seq=seq):
                            self.state, loss = self.train_step(self.state, placed)
                        loss = self._readout(loss)
                        if faults.fire("nan_loss", epoch=epoch,
                                       step=global_step + 1):
                            loss = float("nan")  # forced step output
                        if skip_guard and not self._finite_agreed(loss):
                            # the one host sync per step this policy costs
                            self._skipped_steps += 1
                            obsm.TRAIN_SKIPPED_STEPS.inc()
                            logger.warning(
                                "non-finite loss at step %d: update "
                                "discarded (%d skipped so far)",
                                global_step + 1, self._skipped_steps,
                            )
                            self.state = prev_state
                            return
                        global_step += 1
                        # loss stays a device scalar; LossRecords drains it
                        # to host only at the next row/flush boundary
                        self._record(loss, n_imgs, global_step, pbar)

                    def run_stack(buffered, placed):
                        nonlocal global_step
                        with self.tracer.span(
                            "dispatch", step=global_step + 1, epoch=epoch,
                            seq=seq, k=len(buffered)
                        ):
                            self.state, losses = self.multi_step(self.state, placed)
                        # ONE memoized device→host pull for the whole (K,)
                        # loss array, and only when a metrics row actually
                        # needs it — slicing losses[i] here would issue K
                        # extra dispatches and forfeit the amortization
                        # this path exists for.
                        memo = {}

                        def lazy(i):
                            def pull():
                                if "host" not in memo:
                                    memo["host"] = np.asarray(losses)
                                return float(self._readout(memo["host"][i]))

                            # LossRecords' non-blocking drain starts an
                            # async host copy when a row is parked; expose
                            # the (K,) array's hook so the fused-dispatch
                            # path gets the same early D2H streaming as
                            # plain device scalars
                            pull.copy_to_host_async = losses.copy_to_host_async
                            return pull

                        for i, b in enumerate(buffered):
                            global_step += 1
                            self._record(lazy(i), self.entry.batch.rows(b),
                                         global_step, pbar)

                    def run_accum(buffered, placed):
                        # ONE optimizer step over the K stacked batches —
                        # effective batch K·b, exact loss (make_accum_train_step)
                        nonlocal global_step
                        with self.tracer.span(
                            "dispatch", step=global_step + 1, epoch=epoch,
                            seq=seq, k=len(buffered)
                        ):
                            self.state, loss = self.accum_step(self.state, placed)
                        global_step += 1
                        self._record(
                            loss,
                            sum(self.entry.batch.rows(b) for b in buffered),
                            global_step,
                            pbar,
                        )

                    stacking = self.multi_step is not None or self.accum_step is not None
                    stack_size = (
                        self.k_dispatch if self.multi_step is not None else self.grad_accum
                    )
                    run_buffered = (
                        run_stack if self.multi_step is not None else run_accum
                    )
                    single_process = jax.process_count() == 1
                    # The async step pipeline (utils/prefetch.py): the
                    # epoch's batch stream becomes SINGLE/STACK work items
                    # whose np.stack + device placement run on the prefetch
                    # worker, `prefetch_batches` payloads ahead of this
                    # loop — batch N+1's H2D rides under batch N's
                    # executing dispatch. Depth 0 degrades to inline
                    # placement (the synchronous baseline; identical loss
                    # sequence either way).
                    source = pipelined_placement(
                        stacked_work(
                            self.train_loader.epoch_batches(epoch),
                            stack_size if stacking else 1,
                            cfg.batch_size,
                        ),
                        self.strategy.place_work,
                        depth=cfg.prefetch_batches,
                        tracer=self.tracer,
                        epoch=epoch,
                        max_retries=cfg.data_retries,
                        retry_backoff_s=cfg.retry_backoff_s,
                    )
                    # closing(): breaking out mid-epoch (signal stop) must
                    # CLOSE the pipeline generator so its worker stops and
                    # queued device-placed payloads get released — GC-time
                    # cleanup would keep them pinned through the checkpoint
                    # save. Work items past the stop (including a partial
                    # group's drained singles) are simply never stepped:
                    # they were never trained, so skipping them loses
                    # nothing, and a preemption grace window may be ticking.
                    flight.record("phase", name="epoch_start", epoch=epoch,
                                  step=global_step)
                    # host-observed step cadence → the step-time histogram
                    # (a perf_counter read + one bounded observe per
                    # iteration; no device sync)
                    iter_t0 = None
                    with contextlib.closing(source):
                        # seq counts this epoch's work items as the feed
                        # does: with `epoch`, the batch's identifier in
                        # every span (utils/trace.py)
                        for seq, ((kind, payload), placed) in enumerate(source):
                            now_t = time.perf_counter()
                            if iter_t0 is not None:
                                obsm.TRAIN_STEP_SECONDS.observe(
                                    now_t - iter_t0
                                )
                            iter_t0 = now_t
                            if profile_by_steps:
                                self._profile_tick(global_step)
                            if self._heartbeat is not None:
                                # attribute assignments only — the beat
                                # FILE is written by the heartbeat's own
                                # thread (dist/health.py): nothing here
                                # blocks or syncs. `timed` mirrors the
                                # watchdog's first-executed-epoch
                                # exemption: the supervisor's
                                # progress-timeout hang verdict applies
                                # only in steady state.
                                self._heartbeat.timed = epoch != untimed_epoch
                                self._heartbeat.update(epoch, global_step)
                            if watchdog is not None:
                                if epoch == untimed_epoch:
                                    # the first executed epoch compiles
                                    # every executable shape (initial
                                    # step, K-stack, ragged tail) —
                                    # a minute or more of compiles; an
                                    # armed deadline here would fire on
                                    # a healthy compile. Untimed by
                                    # design; steady-state epochs arm.
                                    watchdog.pause()
                                else:
                                    watchdog.pet()
                            # mid-epoch stop is single-process only: in
                            # multi-process runs ranks must agree (epoch
                            # boundary) or collectives desync and hang —
                            # see _install_signal_handler
                            if self._stop_requested and single_process:
                                break
                            if kind == "single":
                                run_one(payload, placed)
                            else:
                                run_buffered(payload, placed)
                            # simulated preemption: deliver a real SIGTERM
                            # through the installed handler so the drill
                            # exercises the production stop path
                            if faults.fire("sigterm", epoch=epoch,
                                           step=global_step):
                                signal.raise_signal(signal.SIGTERM)
                            # elastic chaos sites (docs/RELIABILITY.md
                            # "Elastic runs"): kill or wedge THIS rank
                            # mid-epoch, exactly how a preempted or
                            # stuck peer presents to the supervisor's
                            # health classifier. rank_kill is a real
                            # SIGKILL — no handler, no checkpoint, no
                            # atexit: the survivors' collectives are
                            # genuinely abandoned.
                            if faults.fire("rank_kill", epoch=epoch,
                                           step=global_step):
                                logger.error(
                                    "injected rank_kill: SIGKILL rank %d "
                                    "(pid %d) at %d:%d",
                                    jax.process_index(), os.getpid(),
                                    epoch, global_step,
                                )
                                os.kill(os.getpid(), signal.SIGKILL)
                            if faults.fire("rank_hang", epoch=epoch,
                                           step=global_step):
                                hang_s = float(
                                    os.environ.get("DPT_FAULT_HANG_S", "3600")
                                )
                                logger.error(
                                    "injected rank_hang: rank %d step loop "
                                    "sleeping %.0fs at %d:%d",
                                    jax.process_index(), hang_s,
                                    epoch, global_step,
                                )
                                time.sleep(hang_s)
                if watchdog is not None:
                    watchdog.pause()
                if self._heartbeat is not None:
                    # epoch boundary: beats keep moving through the
                    # (non-step) eval/checkpoint phases
                    self._heartbeat.update(epoch, global_step)

                if self._stop_agreed(global_step):
                    # save a resumable snapshot at the last COMPLETED epoch
                    # — resume redoes the interrupted epoch from its start
                    # (the dedup guard is cleared: mid-epoch params/opt
                    # state are newer than the end-of-previous-epoch save
                    # of same index)
                    self._last_saved_epoch = None
                    self._save(epoch)
                    logger.info(
                        "Stopped by signal at epoch %d step %d; checkpoint saved",
                        epoch + 1,
                        global_step,
                    )
                    break

                flight.record("phase", name="eval", epoch=epoch,
                              step=global_step)
                if self.grouped_eval_step is not None:
                    val_loss, val_dice = evaluate_sharded(
                        self.eval_step,
                        self.grouped_eval_step,
                        self._eval_variables(),
                        self.val_loader,
                        self.strategy.place_batch,
                        self.strategy.eval_shard(),
                        progress=self.strategy.is_main,
                    )
                else:
                    val_loss, val_dice = evaluate(
                        self.eval_step,
                        self._eval_variables(),
                        self.val_loader,
                        self.strategy.place_batch,
                        progress=self.strategy.is_main,
                    )
                self.records.record_val(global_step, val_loss, val_dice)
                new_lr = self.scheduler.step(val_loss)
                # float32 state vs python float: compare with tolerance
                if not np.isclose(new_lr, get_learning_rate(self.state.opt_state), rtol=1e-6):
                    logger.info("Epoch %d: plateau → lr %.3e", epoch + 1, new_lr)
                    self.state = self.state.replace(
                        opt_state=set_learning_rate(self.state.opt_state, new_lr)
                    )
                logger.info(
                    "Epoch %d/%d: val loss %.4f, val dice %.4f (%.1f imgs/s)",
                    epoch + 1,
                    cfg.epochs,
                    val_loss,
                    val_dice,
                    self.records.images_per_second(),
                )
                # append this epoch's timeline spans (no-op when tracing is off)
                self.tracer.flush()
                self._update_cache_metrics()
                # no is_main gate: val_dice is identical on every rank, so
                # all ranks take this branch together — the payload build
                # inside _save_tagged is collective on sharded state, and
                # the file write is rank-0-gated there
                if cfg.save_best and val_dice > self._best_dice:
                    self._best_dice = val_dice
                    if self.strategy.is_main or self._save_needs_all_ranks():
                        self._save_tagged(
                            self._ckpt_path(f"{cfg.method_tag}_best"), epoch + 1
                        )
                    logger.info(
                        "New best val Dice %.4f at epoch %d → %s",
                        val_dice, epoch + 1, self._ckpt_path(f"{cfg.method_tag}_best"),
                    )
                if cfg.checkpoint_every_epochs and (
                    (epoch + 1) % cfg.checkpoint_every_epochs == 0
                ):
                    self._save(epoch + 1)
                if cfg.early_stop_patience:
                    # NaN val loss (empty split) never counts as improvement
                    # — patience running out on no-signal epochs is
                    # deliberate
                    if val_loss < self._best_loss:
                        self._best_loss = val_loss
                        self._stale_epochs = 0
                    else:
                        self._stale_epochs += 1
                        if self._stale_epochs >= cfg.early_stop_patience:
                            logger.info(
                                "Early stop at epoch %d: val loss has not "
                                "improved for %d epochs (best %.4f)",
                                epoch + 1, self._stale_epochs, self._best_loss,
                            )
                            stopped_early = True
                            self._save(epoch + 1)
                            break
            except NonFiniteLossError as exc:
                # the 'rollback' policy: reload the newest intact
                # checkpoint and redo from its epoch (bounded budget —
                # _try_rollback returns False when exhausted and the
                # error propagates like 'abort'). Park the watchdog
                # first: the drain+restore below is not a step, and its
                # duration must not fire a stop that defeats the
                # recovery (it re-arms at the redone epoch's first pet)
                if watchdog is not None:
                    watchdog.pause()
                if not self._try_rollback(exc):
                    # terminal non-finite abort (policy 'abort', or
                    # 'rollback' with its budget spent): ship the
                    # post-mortem before unwinding
                    flight.dump("nonfinite_abort",
                                extra={"error": str(exc)[:200]})
                    raise
                epoch = self.start_epoch  # _restore rewound it
                global_step = int(self.state.step)
                continue
            epoch += 1

        if whole_run_profile:
            self.tracer.profile_stopped()
            jax.profiler.stop_trace()

        if not self._stop_requested and not stopped_early:
            self._save(cfg.epochs)
        if (
            cfg.save_best
            and self.strategy.is_main
            and self._best_dice == float("-inf")
        ):
            logger.warning(
                "--save-best: no epoch produced a finite val Dice "
                "(empty/missing validation split?) — %s was never written",
                self._ckpt_path(f"{cfg.method_tag}_best"),
            )
        if self.strategy.is_main:
            self.records.save()
        return {
            "val_loss": val_loss,
            "val_dice": val_dice,
            "steps": global_step,
            "images_per_second": self.records.images_per_second(),
            "n_train": n_train,
            # resilience accounting (docs/RELIABILITY.md): updates
            # discarded by policy 'skip' and rollbacks consumed
            "skipped_steps": self._skipped_steps,
            "rollbacks": self.config.rollback_retries - self._rollback_budget,
        }


def fit(config: TrainConfig, dataset=None, strategy=None) -> dict:
    """Functional entry: build a Trainer and run it (the reference's
    `fit(model, criterion, ...)` surface, train_utils.py:22)."""
    return Trainer(config, dataset=dataset, strategy=strategy).train()


def fit_with_restarts(
    config: TrainConfig,
    max_restarts: int = 0,
    dataset=None,
    strategy=None,
    return_trainer: bool = False,
):
    """`fit` with crash recovery: on an exception mid-run, rebuild the
    Trainer from the epoch checkpoint THIS run wrote and continue, up to
    ``max_restarts`` times.

    Failure-recovery capability the reference lacks entirely (SURVEY.md §5:
    `torchrun --standalone` with no --max-restarts, checkpoints only at the
    very end — a crash loses everything). Here every epoch checkpoints
    atomically (including the metric history, so the loss curves survive
    the restart), and a restart redoes at most the crashed epoch. A
    checkpoint left behind by some EARLIER invocation is never resumed —
    that would silently turn a crashed fresh run into an instant no-op
    "success". Restarts are single-process only: in a multi-process run,
    ranks cannot re-rendezvous from inside one surviving process — the
    launcher (torchrun --max-restarts, or the pod scheduler) owns that
    loop, and this wrapper simply re-raises for it.

    Returns the result dict, or ``(result, trainer)`` with
    ``return_trainer=True`` (the trainer whose state finished the run —
    e.g. for exporting final weights).
    """
    import dataclasses

    resumable = os.path.join(config.checkpoint_dir, f"{config.method_tag}.ckpt")
    attempt = 0
    saved_this_run = False
    while True:
        trainer = Trainer(config, dataset=dataset, strategy=strategy)
        if attempt > 0 and trainer.start_epoch >= config.epochs:
            # the crash happened AFTER training completed (final checkpoint
            # written, then e.g. records.save() failed); a "restart" would
            # run zero epochs and report NaN metrics as success — surface
            # the real error instead
            raise last_exc
        try:
            result = trainer.train()
            return (result, trainer) if return_trainer else result
        except KeyboardInterrupt:
            raise
        except Exception as exc:
            # clock-free freshness: _last_saved_epoch is set iff THIS
            # attempt actually wrote the checkpoint (mtime-vs-time.time()
            # comparisons break on skewed/coarse filesystem clocks)
            saved_this_run = saved_this_run or (
                getattr(trainer, "_last_saved_epoch", None) is not None
            )
            if (
                attempt >= max_restarts
                or jax.process_count() > 1
                or not saved_this_run
            ):
                raise
            attempt += 1
            last_exc = exc
            logger.exception(
                "Training crashed; restart %d/%d from %s",
                attempt,
                max_restarts,
                resumable,
            )
            # resume from the per-method checkpoint the epoch loop saves
            config = dataclasses.replace(
                config, checkpoint_name=config.method_tag
            )
