"""Strategy objects: one mesh-rule engine, strategies as named points.

ONE trainer (train/loop.py) consumes these; a strategy answers: which
mesh, how batches are placed/sharded, how the train step is jitted,
which process does eval/checkpoint/metrics, how the dataloader is
sharded, and how the lr scales — everything that differed between the
reference's three copy-pasted ``fit*`` loops (SURVEY.md §2).

Since the composable-mesh refactor there is exactly ONE set of step /
eval / placement builders, living on :class:`Strategy` and driven by a
:class:`~distributedpytorch_tpu.parallel.mesh.MeshConfig` (the N-D
``('data', 'model', 'stage')`` mesh + per-tree sharding rules —
parallel/mesh.py). Each legacy ``-t`` name is a thin subclass whose
only job is resolving its named point against the device pool
(`_mesh_layout`); arbitrary points launch as ``-t DxMxS[@rule]`` mesh
specs through :class:`GenericMesh` — including hybrids the old
class-per-strategy design could not express (``2x2x1`` = DP x TP,
``2x2x1@fsdp`` = FSDP x TP).

Method-name parity with the reference CLI (reference train.py:17,
:46-64): ``singleGPU``, ``DP``, ``DDP``, ``MP``, plus the additive
``DDP_MP``/``SP``/``DDP_SP``/``TP``/``FSDP`` and the mesh specs.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributedpytorch_tpu.config import TrainConfig
from distributedpytorch_tpu.data.loader import ShardSpec
from distributedpytorch_tpu.models import model_entry
from distributedpytorch_tpu.ops.precision import get_policy
from distributedpytorch_tpu.parallel import mesh as mesh_rules
from distributedpytorch_tpu.parallel.mesh import MeshConfig
from distributedpytorch_tpu.parallel.pipeline import (
    PIPELINE_SCHEDULES,
    make_pipeline_forward_fn,
    make_pipeline_value_and_grad_fn,
)
from distributedpytorch_tpu.train.steps import (
    TrainState,
    apply_optimizer,
    grouped_eval_metrics,
    make_accum_train_step,
    make_eval_step,
    make_multi_train_step,
    make_train_step,
)


def _prep_mask(mask: jax.Array) -> jax.Array:
    return mask[..., None].astype(jnp.float32)


def _validate_pipeline_schedule(config: TrainConfig) -> None:
    """Fail at strategy CONSTRUCTION (before model build / data setup)
    on an unknown schedule; the pipeline builder itself re-checks for
    direct API users."""
    if config.pipeline_schedule not in PIPELINE_SCHEDULES:
        raise ValueError(
            f"pipeline_schedule must be one of {PIPELINE_SCHEDULES}, "
            f"got {config.pipeline_schedule!r}"
        )


def _state_donation(config: Optional[TrainConfig] = None) -> tuple:
    """``donate_argnums`` for the jitted train steps: donating the state
    halves HBM pressure on accelerators (in-place Adam update). Not on
    the CPU backend: buffers there are host RAM, so donation saves
    nothing, and callers that hold one initial state and feed it to
    several step functions (the strategy-equivalence tests, the static
    analyzer) would find it deleted after the first.

    History: the gate was added for a jax 0.4.37 CPU-client abort
    (native SIGABRT when donated executables of sequentially-built
    trainers ran in one process, ~40-50% of restart-test runs). Re-tested
    in PR 21 on jax 0.9.0 with donation forced on:
    ``test_fit_with_restarts_resumes_after_crash`` 20/20 clean — the
    abort is gone, and the reason above is the one that remains.

    ``nonfinite_policy='skip'`` also disables donation everywhere: the
    trainer holds the PREVIOUS state across each step so a non-finite
    step's update can be discarded — a donated previous state would be
    deleted buffers (train/loop.py)."""
    if config is not None and config.nonfinite_policy == "skip":
        return ()
    return () if jax.default_backend() == "cpu" else (0,)


def _shrunk_data_degree(name: str, batch_size: int, n_devices: int) -> int:
    """Largest data degree <= n_devices dividing the batch, warning
    loudly when devices are left idle (torch DataParallel would scatter
    unevenly instead; GSPMD needs the batch to divide the mesh)."""
    n = n_devices
    while batch_size % n:
        n -= 1
    if n != n_devices:
        import logging

        logging.getLogger(__name__).warning(
            "%s: batch size %d does not divide the %d available devices "
            "— data mesh shrunk to %d device(s); %d idle. torch "
            "DataParallel would scatter unevenly instead; here the "
            "batch must divide the mesh. Use a batch size divisible by "
            "the device count to engage every device.",
            name, batch_size, n_devices, n, n_devices - n,
        )
    return n


class Strategy:
    """Base: the mesh-rule engine. Every step/eval/placement builder
    lives HERE, driven by ``self.mesh_config``; subclasses only resolve
    their named point (`_mesh_layout`). The base itself is the no-mesh
    single-device point."""

    name = "base"

    def __init__(self, config: TrainConfig, devices=None):
        self.config = config
        # the session's precision policy (ops/precision.py, --dtype):
        # resolved ONCE here; the steps this strategy builds, the
        # checkpoint manifest, and the restore path all read this object
        self.policy = get_policy(config)
        # the kernel-engagement policy (ops/kernels.py, --kernels):
        # resolved ONCE with the Mosaic probe priors applied (the legacy
        # use_pallas flag resolves inside, as a loud alias)
        from distributedpytorch_tpu.ops.kernels import get_kernel_policy

        self.kernels = get_kernel_policy(config)
        # the mesh point this strategy IS: axis sizes + sharding rules
        self.mesh_config, devs = self._mesh_layout(config, devices)
        self.mesh: Optional[Mesh] = mesh_rules.build_mesh(
            self.mesh_config, devs
        )
        if self.mesh is not None and model_entry(config).single_device_only:
            raise ValueError(
                f"model_arch {config.model_arch!r} trains on one device "
                f"(-t singleGPU): its expert exchange and sequence split "
                f"across chips do not exist yet (ROADMAP M4, M5); "
                f"{config.train_method!r} builds a mesh")
        self.batch_sharding: Optional[NamedSharding] = (
            None if self.mesh is None
            else NamedSharding(
                self.mesh, mesh_rules.batch_partition_spec(self.mesh_config)
            )
        )

    # -- the named point ----------------------------------------------------
    def _mesh_layout(
        self, config: TrainConfig, devices
    ) -> Tuple[MeshConfig, Sequence]:
        """(MeshConfig, device pool) for this strategy — the ONLY thing
        a legacy strategy class defines. Base: the 1x1x1 point."""
        return MeshConfig(), ()

    @property
    def is_pipeline(self) -> bool:
        return self.mesh_config.is_pipeline

    @property
    def pipeline_data_axis(self) -> Optional[str]:
        return "data" if self.mesh_config.data > 1 else None

    # -- process topology ---------------------------------------------------
    @property
    def is_main(self) -> bool:
        """Rank-0 gating for eval/checkpoint/metrics (reference
        train_utils.py:229-248). Single-process strategies: always True."""
        return jax.process_index() == 0

    def data_shard(self) -> ShardSpec:
        """How the dataloader shards samples across processes
        (DistributedSampler parity, reference train_utils.py:189)."""
        return ShardSpec(0, 1)

    def topology(self) -> Dict[str, Any]:
        """This strategy's mesh/process topology, as recorded in the
        checkpoint manifest (checkpoint.save_topology fills the process/
        device counts): the saving side of the mesh-resharding restore.
        Keys are msgpack-plain (str → str/int)."""
        mesh = (
            {}
            if self.mesh is None
            else {str(k): int(v) for k, v in self.mesh.shape.items()}
        )
        # "precision" is the ckpt-dtype-drift contract's anchor: restore
        # compares it against the session policy and converts/re-casts
        # loudly instead of silently retracing (train/loop._restore).
        # "mesh_spec" is the canonical mesh-point name — an N→M
        # mesh-resharding restore logs the TRUE source geometry, not
        # just the (possibly aliased) legacy strategy name.
        return {
            "strategy": self.name,
            "mesh": mesh,
            "mesh_spec": mesh_rules.canonical_spec(self.mesh_config),
            "precision": self.policy.name,
        }

    # -- batch semantics ----------------------------------------------------
    @property
    def global_batch_size(self) -> int:
        """config.batch_size is the per-process batch (torch DataLoader
        semantics); single-process strategies: global == local."""
        return self.config.batch_size

    @property
    def drop_last_train(self) -> bool:
        return self.mesh_config.drop_last

    def lr_for(self, base_lr: float) -> float:
        return base_lr

    # -- placement ----------------------------------------------------------
    def place_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        if self.mesh is None:
            dev = jax.devices()[0]
            return {k: jax.device_put(v, dev) for k, v in batch.items()}
        return {
            k: jax.device_put(v, self.batch_sharding) for k, v in batch.items()
        }

    def place_state(self, state: TrainState) -> TrainState:
        if self.mesh is None:
            dev = jax.devices()[0]
            return jax.device_put(state, dev)
        if self.mesh_config.params == "replicate":
            return _replicate(self.mesh, state)
        placed = _shard_state_by_rule(
            state, self.mesh, self._leaf_spec, self.name
        )
        if self.is_pipeline and state.model_state is not None:
            # the pipeline schedules read batch_stats whole on every
            # stage (in_specs P()); placing it sharded would force a
            # gather-then-resharding recompile on the second step
            placed = TrainState(
                params=placed.params,
                opt_state=placed.opt_state,
                step=placed.step,
                model_state=_replicate(self.mesh, state.model_state),
            )
        return placed

    def _leaf_spec(self, shape) -> P:
        """The per-tree params/opt-state rule — one definition
        (mesh.state_leaf_spec) shared by placement here and the
        analyzer/planner's AOT sharding pins
        (analysis/collectives.compile_train_step_aot)."""
        return mesh_rules.state_leaf_spec(self.mesh_config, shape)

    def place_work(self, kind: str, payload):
        """The async step pipeline's H2D entry (utils/prefetch.
        pipelined_placement): one call placing either work-item kind, so
        the placement worker needs no strategy knowledge. ``'single'`` is
        a per-step host batch (→ `place_batch`); ``'stack'`` is an
        already-np.stack'ed (K, B, ...) fused-dispatch payload
        (→ `place_stacked_batch`)."""
        if kind == "stack":
            return self.place_stacked_batch(payload)
        return self.place_batch(payload)

    def place_stacked_batch(
        self, stacked: Dict[str, np.ndarray]
    ) -> Dict[str, jax.Array]:
        """Place a (K, B, ...) stack of K per-step batches; the K axis is
        never sharded (it is scanned over), each step's batch keeps this
        strategy's per-batch sharding."""
        if self.mesh is None:
            dev = jax.devices()[0]
            return {k: jax.device_put(v, dev) for k, v in stacked.items()}
        sharding = self._stacked_sharding()
        return {k: jax.device_put(v, sharding) for k, v in stacked.items()}

    def _stacked_sharding(self) -> NamedSharding:
        """`batch_sharding` shifted right by the leading K axis."""
        return NamedSharding(
            self.mesh, P(None, *tuple(self.batch_sharding.spec))
        )

    # -- compiled steps -----------------------------------------------------
    def _train_loss_impl(self) -> Optional[Callable]:
        """The fused Pallas training loss when the kernel policy engages
        it (``--kernels pallas`` or the legacy ``--pallas`` alias; None =
        XLA loss). Single-device runs use the kernel directly; mesh
        strategies wrap it in shard_map — per-shard kernel + a 4-scalar
        stats psum over the batch-sharding axes — so the loss and its
        custom-VJP gradient equal the unsharded computation
        (ops/fused_loss.py)."""
        if not self.kernels.train_loss_fused:
            return None
        from distributedpytorch_tpu.ops.fused_loss import (
            fused_bce_dice_loss,
            make_sharded_fused_loss,
            spec_axes,
        )

        if self.mesh is None:
            return fused_bce_dice_loss
        spec = self.batch_sharding.spec
        return make_sharded_fused_loss(self.mesh, spec, spec_axes(spec))

    def _raw_step(self, model, tx) -> Callable:
        """The unjitted per-batch step this mesh point runs: the
        explicit pipeline schedule when a 'stage' axis exists, the plain
        (GSPMD-sharded) step otherwise — ONE definition for every
        strategy."""
        if self.is_pipeline:
            return self._pipeline_raw_step(model, tx)
        # Quirk-1 scale uses the PER-PROCESS batch_size (the reference's
        # `-b` value): fit_DDP scales by its local -b then
        # mean-allreduces, so the global batch would overscale by world.
        entry = model_entry(self.config)
        return make_train_step(
            model,
            tx,
            batch_size=self.config.batch_size,
            faithful_loss_scaling=(self.config.faithful_loss_scaling
                                   and entry.batch_scaled_backward),
            remat=self.config.remat,
            loss_impl=self._train_loss_impl(),
            policy=self.policy,
            loss_fn=entry.loss,
        )

    def _pipeline_raw_step(self, model, tx) -> Callable:
        """The pipelined step over the 'stage' axis (either schedule);
        the data-axis plumbing — batch sharding, stats/grad psums over
        ('stage'[, 'data']) — derives from the mesh, one definition for
        MP, DDP_MP, and every stage-bearing mesh config."""
        pipeline_vag = make_pipeline_value_and_grad_fn(
            model,
            self.mesh,
            num_microbatches=self.config.num_microbatches,
            remat=self.config.remat,
            cuts=self.config.pipeline_cuts,
            use_pallas=self.kernels.train_loss_fused,
            schedule=self.config.pipeline_schedule,
            mesh_config=self.mesh_config,
        )
        # per-process batch, same rationale as the plain step's scale
        grad_scale = (
            float(self.config.batch_size)
            if self.config.faithful_loss_scaling
            else 1.0
        )

        def step(state: TrainState, batch):
            prepped = {"image": batch["image"], "mask": _prep_mask(batch["mask"])}
            loss, grads, model_state = pipeline_vag(
                state.params, state.model_state, prepped
            )
            # the wgrad contract at the schedule boundary: 1f1b already
            # accumulated in WGRAD_DTYPE; gpipe's autodiff emits grads in
            # the param dtype, so under bf16_params they are stated f32
            # here, before the faithful-quirk scale can round in bf16
            grads = self.policy.cast_grads(grads)
            if grad_scale != 1.0:
                grads = jax.tree.map(lambda g: g * grad_scale, grads)
            params, opt_state = apply_optimizer(
                tx, grads, state.opt_state, state.params)
            return (
                TrainState(
                    params=params,
                    opt_state=opt_state,
                    step=state.step + 1,
                    model_state=model_state,
                ),
                loss,
            )

        return step

    def build_train_step(self, model, tx) -> Callable:
        return jax.jit(self._raw_step(model, tx), donate_argnums=_state_donation(self.config))

    def build_multi_train_step(self, model, tx) -> Callable:
        """K steps per dispatch: `multi(state, stacked) -> (state, losses)`
        with batches stacked on a leading axis (see make_multi_train_step;
        place the stacked batch with `place_stacked_batch`)."""
        multi = make_multi_train_step(self._raw_step(model, tx))
        return jax.jit(multi, donate_argnums=_state_donation(self.config))

    def build_accum_train_step(self, model, tx) -> Callable:
        """ONE optimizer step over config.grad_accum stacked batches with
        one chunk's activation memory — exact for the non-additive
        log-dice loss (see make_accum_train_step). The fused Pallas stats
        run only off-mesh: inside this plain GSPMD jit a sharded chunk
        cannot enter pallas_call (unlike the per-shard shard_map loss)."""
        if self.is_pipeline:
            raise ValueError(
                "pipeline strategies already microbatch inside the "
                "schedule — raise --microbatches instead of --grad-accum"
            )
        step = make_accum_train_step(
            model,
            tx,
            batch_size=self.config.batch_size,
            chunks=self.config.grad_accum,
            faithful_loss_scaling=self.config.faithful_loss_scaling,
            remat=self.config.remat,
            use_pallas=self.kernels.train_loss_fused and self.mesh is None,
        )
        return jax.jit(step, donate_argnums=_state_donation(self.config))

    def _forward_fn(self, model) -> Callable:
        return make_pipeline_forward_fn(
            model,
            self.mesh,
            num_microbatches=self.config.num_microbatches,
            cuts=self.config.pipeline_cuts,
            mesh_config=self.mesh_config,
        )

    def build_eval_step(self, model) -> Callable:
        evaluate = model_entry(self.config).evaluate
        if evaluate is not None:
            # the model table's own validation metrics (a token model has
            # no mask to take a Dice against)
            return jax.jit(functools.partial(evaluate, model))
        if self.is_pipeline:
            # Eval runs the pipelined forward too (the reference
            # evaluates through the pipe model, train.py:62-64 →
            # evaluate.py). For stateful models `variables` is the
            # {'params','batch_stats'} dict the trainer's
            # _eval_variables() builds (running averages only).
            self._pallas_eval()  # warn if --pallas was requested: mesh strategy
            fwd = self._forward_fn(model)
            from distributedpytorch_tpu.ops.losses import (
                bce_dice_loss,
                dice_coefficient,
            )

            def eval_step(variables, batch):
                preds = fwd(variables, batch["image"])
                target = _prep_mask(batch["mask"])
                return {
                    "loss": bce_dice_loss(preds, target),
                    "dice": dice_coefficient(preds, target),
                }

            return jax.jit(eval_step)
        return jax.jit(make_eval_step(model, use_pallas=self._pallas_eval()))

    # -- sharded evaluation -------------------------------------------------
    def eval_shard(self) -> ShardSpec:
        """Round-robin assignment of whole VAL BATCHES to processes
        (rank p evaluates global batches p, p+world, ...). Default: one
        shard — every process evaluates everything (single-process
        strategies have no one to share with)."""
        return ShardSpec(0, 1)

    def build_grouped_eval_step(self, model) -> Callable:
        """Eval step over a (world·b) stack of `world` independent val
        batches, one per process, sharded over the mesh exactly like a
        train batch; returns per-batch vector metrics (see
        train/steps.grouped_eval_metrics). Every process reads back
        identical values, so the plateau scheduler stays in lockstep while
        each process loads and computes only 1/world of the val set.

        Output shardings are pinned REPLICATED: left to itself GSPMD may
        shard the (world,) metric vectors over 'data' (one element per
        shard — exactly the layout), which multi-process hosts cannot
        device_get (elements live on non-addressable devices)."""
        groups = self.eval_shard().world
        if self.is_pipeline and self.mesh_config.per_process_batch:
            fwd = self._forward_fn(model)

            def eval_step(variables, batch):
                preds = fwd(variables, batch["image"])
                return grouped_eval_metrics(
                    preds, _prep_mask(batch["mask"]), groups
                )

            replicated = NamedSharding(self.mesh, P())
            return jax.jit(
                eval_step, out_shardings={"loss": replicated, "dice": replicated}
            )
        step = make_eval_step(model, groups=groups)
        if self.mesh is not None:
            replicated = NamedSharding(self.mesh, P())
            return jax.jit(
                step, out_shardings={"loss": replicated, "dice": replicated}
            )
        return jax.jit(step)

    def _pallas_eval(self) -> bool:
        """The fused EVAL stats kernel applies only where the eval batch
        is unsharded (single device / replicated): pallas_call has no
        GSPMD partitioning rule, so a mesh-sharded (B,H,W,1) input would
        fail to lower or force a de-shard. Sharded strategies keep the
        XLA eval metrics — the TRAINING loss still runs the fused kernel
        via the shard_map wrapper (`_train_loss_impl`), so only the
        per-epoch eval pass differs."""
        if not self.kernels.eval_stats_fused:
            return False
        if self.mesh is not None:
            import logging

            logging.getLogger(__name__).info(
                "--kernels: strategy %s trains through the fused kernel "
                "(shard_map); eval metrics stay on the XLA path (sharded "
                "eval batches cannot enter pallas_call)",
                self.name,
            )
            return False
        return True


class SingleDevice(Strategy):
    """Reference ``-t singleGPU`` (train.py:46-50): whole model + batch on
    one chip — the ``1x1x1`` mesh point."""

    name = "singleGPU"


def _coerce_leaf(x):
    """Python scalars → numpy before placement: a restored checkpoint's
    ``step`` counter is a plain int, which multi-process placement
    rejects outright."""
    return x if isinstance(x, (jax.Array, np.ndarray)) else np.asarray(x)


def _place_global(x, sharding: NamedSharding):
    """Place one leaf under a sharding that may span processes.

    On a multi-process mesh, every locally-materializable value — host
    numpy (the checkpoint-restore path) AND fully-addressable jax arrays
    (fresh single-device init) — goes through
    ``make_array_from_callback``: each process builds its own
    addressable shards from its (identical by construction: same seed,
    same checkpoint file) local copy, with NO cross-process transfer and
    NO collective. ``device_put`` onto a non-addressable sharding
    instead runs a gloo `assert_equal` allgather per leaf — a collective
    per parameter at every trainer construction, observed crashing gloo
    (`op.preamble.length <= op.nbytes`) when those host collectives
    interleave with XLA's own CPU collectives. Single-process keeps
    plain device_put.
    """
    x = _coerce_leaf(x)
    if jax.process_count() > 1:
        if isinstance(x, jax.Array):
            if not x.is_fully_addressable:
                return jax.device_put(x, sharding)  # already global
            x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx, v=x: v[idx]
        )
    return jax.device_put(x, sharding)


def _replicate(mesh: Mesh, tree):
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: _place_global(x, sharding), tree)


class DataParallel(Strategy):
    """Reference ``-t DP`` (torch.nn.DataParallel, train_utils.py:98):
    single process, batch split across local devices — the ``Nx1x1``
    point with replicated params and the torch-DP GLOBAL-batch
    convention. XLA's sharding propagation inserts the gradient
    AllReduce that DataParallel does with scatter/gather — without the
    per-step replica broadcast DataParallel pays."""

    name = "DP"

    def _mesh_layout(self, config, devices):
        devs = list(devices if devices is not None else jax.local_devices())
        n = _shrunk_data_degree(self.name, config.batch_size, len(devs))
        return MeshConfig(data=n, drop_last=True), devs[:n]


class MultiProcessMixin:
    """The torchrun-style multi-process contract, shared by every strategy
    with a 'data' mesh axis spanning processes (DDP, DDP_MP, DDP_SP,
    FSDP, mesh specs):

      * each process loads its own sample shard (`ShardSpec` = the
        DistributedSampler, reference train_utils.py:189, with the
        per-epoch reshuffle fix);
      * config.batch_size is PER-PROCESS (global = b × world), matching
        the torchrun launch convention (reference README.md:37);
      * lr is scaled by the data-parallel degree when the mesh point is
        lr-scaling-eligible (the DDP family) and
        ``ddp_lr_world_size_scaling`` is set (reference quirk 2,
        train_utils.py:199);
      * batches assemble from process-local data into one global array.

    Requires `self.mesh` with a 'data' axis and `self.batch_sharding`.

    Batch-dim sharding is by DATA ROW, not blindly by process. When the
    mesh has axes besides 'data' (stage in DDP_MP, spatial in DDP_SP),
    the devices of one data row can belong to SEVERAL processes — and
    `make_array_from_process_local_data` takes each process's local data
    as its own devices' shard content WITHOUT reconciling replicas, so
    co-row processes feeding different samples silently build a
    corrupted global batch (empirically: the same jitted `sum` of such a
    batch returns DIFFERENT values on different processes — each sees
    its own column's data; found by a 4-process × {data:2, stage:2}
    probe in round 5). Processes sharing a data row must therefore load
    the SAME samples; `_batch_replica_shard()` computes that row-based
    assignment from the global mesh (identical on every process), and
    both the train loader shard and the eval round-robin use it.
    """

    def _batch_replica_shard(self) -> ShardSpec:
        """(rank, world) for batch-dim loading: one shard per data ROW.

        Fast path: when every data row's devices belong to one process
        (1-axis DDP mesh; 2-proc × 2-device hybrids), this is the plain
        process round-robin — maximal parallelism, no redundant loading.
        When rows span processes, co-row processes get the SAME rank
        (they must feed identical data — see class docstring). If the
        topology is irregular (a process spanning rows that are also
        shared, or processes orphaned by a shrunk mesh), fall back to
        world=1 — every branch decides from the GLOBAL process→row map,
        so all processes pick the same regime (divergence here would
        mean different collective programs and a deadlock).

        Memoized: the mesh and process layout are fixed for the
        strategy's lifetime, and this sits on place_batch's per-step
        host path — an O(devices) Python scan per batch key would be
        real overhead on a pod."""
        cached = getattr(self, "_replica_shard_memo", None)
        if cached is not None:
            return cached
        spec = self._compute_batch_replica_shard()
        self._replica_shard_memo = spec
        return spec

    def _compute_batch_replica_shard(self) -> ShardSpec:
        if jax.process_count() == 1:
            return ShardSpec(0, 1)
        if self.mesh is None or "data" not in self.mesh.axis_names:
            return ShardSpec(0, 1)  # no data axis: every process loads all
        axis = self.mesh.axis_names.index("data")
        grid = np.moveaxis(self.mesh.devices, axis, 0)
        grid = grid.reshape(grid.shape[0], -1)
        row_procs = [{d.process_index for d in row} for row in grid]
        proc_rows = {}
        for i, procs in enumerate(row_procs):
            for p in procs:
                proc_rows.setdefault(p, set()).add(i)
        if set(proc_rows) != set(range(jax.process_count())):
            return ShardSpec(0, 1)  # orphaned processes: replicate
        if all(len(s) == 1 for s in row_procs):
            return ShardSpec(jax.process_index(), jax.process_count())
        if any(len(rows) != 1 for rows in proc_rows.values()):
            return ShardSpec(0, 1)
        my_row = next(iter(proc_rows[jax.process_index()]))
        return ShardSpec(my_row, len(row_procs))

    def data_shard(self) -> ShardSpec:
        return self._batch_replica_shard()

    def eval_shard(self) -> ShardSpec:
        """Multi-process strategies split evaluation: each process owns
        every world-th val batch and the grouped eval step psums nothing —
        per-batch metrics come back replicated from one sharded dispatch.
        Same row-based assignment as training (class docstring)."""
        return self._batch_replica_shard()

    @property
    def global_batch_size(self) -> int:
        # b × the number of DISTINCT batch shards (= data rows when rows
        # span processes) — not × process_count: co-row processes feed
        # the same samples, which add capacity only once.
        return self.config.batch_size * self.data_shard().world

    def lr_for(self, base_lr: float) -> float:
        if (
            self.config.ddp_lr_world_size_scaling
            and self.mesh_config.lr_scaling
        ):
            return base_lr * self.mesh.shape["data"]
        return base_lr

    def _global_shape(self, local_shape) -> tuple:
        """Global batch shape: dim 0 scales to the global batch; other
        dims are supplied at FULL extent by every process and
        `make_array_from_process_local_data` slices each device's part
        (how the spatial axis of DDP_SP distributes without the loader
        knowing about H-sharding — verified by the round-5 probe)."""
        return (self.global_batch_size,) + tuple(local_shape[1:])

    def place_batch(self, batch):
        if jax.process_count() == 1:
            return super().place_batch(batch)
        return {
            k: jax.make_array_from_process_local_data(
                self.batch_sharding, v, global_shape=self._global_shape(v.shape)
            )
            for k, v in batch.items()
        }

    def place_stacked_batch(self, stacked):
        if jax.process_count() == 1:
            return super().place_stacked_batch(stacked)
        sharding = self._stacked_sharding()
        return {
            k: jax.make_array_from_process_local_data(
                sharding,
                v,
                global_shape=(v.shape[0],)
                + self._global_shape(v.shape[1:]),
            )
            for k, v in stacked.items()
        }


class DistributedDataParallel(MultiProcessMixin, Strategy):
    """Reference ``-t DDP`` (train_utils.py:170-248): multi-process data
    parallel — the ``Nx1x1`` point over ALL processes' devices with the
    MultiProcessMixin contract (sample sharding, per-process batch, lr
    scaling); eval/checkpoint/metrics on process 0 only.

    Launch: `dist/runtime.py` maps torchrun-style env vars onto
    `jax.distributed.initialize`. Under a single process this degrades to
    DP over all local devices — which is also how it is unit-tested on
    the 8-device virtual CPU mesh.
    """

    name = "DDP"

    def _mesh_layout(self, config, devices):
        devs = list(devices if devices is not None else jax.devices())
        cfg = MeshConfig(
            data=len(devs), per_process_batch=True, lr_scaling=True,
            drop_last=True,
        )
        return cfg, devs


class Pipeline(Strategy):
    """Reference ``-t MP`` (unet_model.py:14-53): the ``1x1xS`` point —
    an S-stage microbatched pipeline, explicit schedule over a
    ('stage',) mesh (see parallel/pipeline.py). ``--pipeline-schedule``
    picks ``gpipe`` (fill-drain) or ``1f1b`` (PipeDream-flush; in-flight
    activations bounded by the stage count). Stateful (BatchNorm) models
    thread their batch_stats through the stages under either schedule."""

    name = "MP"

    def _mesh_layout(self, config, devices):
        _validate_pipeline_schedule(config)
        devs = list(devices if devices is not None else jax.local_devices())
        if len(devs) < config.num_stages:
            raise ValueError(
                f"Requires at least {config.num_stages} devices, got {len(devs)}"
            )
        return MeshConfig(stage=config.num_stages), devs


class HybridDataPipeline(MultiProcessMixin, Strategy):
    """``-t DDP_MP``: data parallel × pipeline — the ``Dx1xS`` point.
    Batch sharded over 'data'; each data replica runs the S-stage
    schedule (either --pipeline-schedule) over its 'stage' group; the
    gradient psum over 'data' is the DDP all-reduce — inserted by
    autodiff under gpipe, issued explicitly by the 1F1B schedule's final
    grad reduction."""

    name = "DDP_MP"

    def _mesh_layout(self, config, devices):
        _validate_pipeline_schedule(config)
        devs = list(devices if devices is not None else jax.devices())
        stages = config.num_stages
        if len(devs) < 2 * stages:
            raise ValueError(
                f"DDP_MP needs at least {2*stages} devices, got {len(devs)}"
            )
        # Each data shard must hold ≥1 full microbatch set: shrink the data
        # degree until batch divides dp × microbatches (mirrors DP's
        # mesh shrink for indivisible batches).
        per_process = config.batch_size
        mb = config.num_microbatches
        if per_process % mb:
            raise ValueError(
                f"batch_size {per_process} must be a multiple of "
                f"num_microbatches {mb}"
            )
        dp = min(len(devs) // stages, per_process // mb)
        while per_process % (dp * mb):
            dp -= 1
        if dp < 2:
            raise ValueError(
                f"DDP_MP degenerates to plain MP: batch_size {per_process} with "
                f"{mb} microbatches leaves no room for a data axis ≥ 2 — "
                f"use -t MP or raise the batch size"
            )
        cfg = MeshConfig(
            data=dp, stage=stages, per_process_batch=True, lr_scaling=True,
            drop_last=True,
        )
        return cfg, devs


class SpatialParallel(Strategy):
    """``-t SP``: spatial (image-plane) sharding — the ``1xMx1@sp``
    point, the conv-net analogue of sequence/context parallelism.

    The image H axis is sharded over the model axis (named 'spatial');
    params stay replicated. Under GSPMD, XLA inserts the halo exchanges
    (collective-permute of boundary rows) that each 3×3 conv window and
    2×2 pool needs at shard edges. Activation memory per chip drops by
    the mesh size, so batch-1 images far beyond one chip's HBM train
    without pipeline bubbles.

    Constraint: H must stay divisible by the mesh size after the pools
    (H/2^L rows at the deepest level), or GSPMD pads ragged shards; the
    constructor shrinks the mesh until it divides evenly.
    """

    name = "SP"

    def _mesh_layout(self, config, devices):
        devs = list(devices if devices is not None else jax.local_devices())
        h = config.image_size[1]  # image_size is (W, H), reference newsize
        deep = 2 ** config.model_levels  # downsampling at the deepest level
        n = len(devs)
        while n > 1 and (h // deep) % n:
            n -= 1
        return MeshConfig(model=n, model_role="spatial"), devs[:n]


class HybridDataSpatial(MultiProcessMixin, Strategy):
    """``-t DDP_SP``: data × spatial — the ``DxMx1@sp`` point: batch
    over 'data', image rows over 'spatial', gradients all-reduced over
    both axes by GSPMD. Scale batch throughput and per-image footprint
    at once (multi-host: 'data' maps across hosts/DCN, 'spatial' stays
    inside the ICI domain where the per-conv halo exchanges are cheap)."""

    name = "DDP_SP"

    def _mesh_layout(self, config, devices):
        devs = list(devices if devices is not None else jax.devices())
        h = config.image_size[1]
        deep = 2 ** config.model_levels
        # Largest spatial degree that (a) divides the deepest level's rows
        # and (b) still leaves a data axis ≥ 2 that divides the batch.
        best = None
        for sp in range(len(devs), 0, -1):
            if (h // deep) % sp:
                continue
            dp = len(devs) // sp
            while dp > 1 and config.batch_size % dp:
                dp -= 1
            if dp >= 2:
                best = (dp, sp)
                break
        if best is None:
            raise ValueError(
                f"DDP_SP degenerates to plain SP: batch_size "
                f"{config.batch_size} leaves no data axis ≥ 2 over "
                f"{len(devs)} devices — use -t SP or raise the batch size"
            )
        dp, sp = best
        cfg = MeshConfig(
            data=dp, model=sp, model_role="spatial",
            per_process_batch=True, lr_scaling=True, drop_last=True,
        )
        return cfg, devs


def _shard_state_by_rule(state, mesh: Mesh, leaf_spec, strategy_name: str) -> Any:
    """Place a TrainState with per-leaf PartitionSpecs chosen by
    `leaf_spec(shape) -> PartitionSpec`. Adam's m/v mirror the param
    shapes, so one shape-driven rule shards params and optimizer state
    consistently; scalars (step/count) replicate.

    Warns loudly when NO leaf shards: the strategy then degenerates to
    fully replicated compute (every device does the whole model) — legal,
    but certainly not what the user asked for.
    """
    sharded = 0

    def place(x):
        nonlocal sharded
        x = _coerce_leaf(x)
        spec = leaf_spec(getattr(x, "shape", ()))
        if any(s is not None for s in spec):
            sharded += 1
        return _place_global(x, NamedSharding(mesh, spec))

    placed = jax.tree.map(place, state)
    if sharded == 0:
        import logging

        logging.getLogger(__name__).warning(
            "%s: no parameter axis divides the %d-device mesh — state is "
            "fully replicated and every device computes the whole model "
            "(no parallel speedup or memory saving). Use a device count "
            "that divides the channel widths.",
            strategy_name,
            mesh.devices.size,
        )
    return placed


class TensorParallel(Strategy):
    """``-t TP``: tensor (model) parallelism — the ``1xMx1`` point with
    the ``channel`` params rule: conv out-channels sharded over
    ('model',).

    TPU-native form: pure sharding annotation. Every conv kernel
    (Kh, Kw, Cin, Cout) and bias is sharded on its out-channel axis; the
    batch is replicated. Under GSPMD each device then computes its channel
    slice of every layer, and XLA inserts the collectives where channels
    must be whole (the next layer contracts over the sharded Cin; skip
    concats; the 1-channel segmap head stays replicated — its Cout=1 does
    not divide). Parameters AND Adam state are sharded, so per-chip
    parameter memory drops by the mesh size — the memory effect of
    Megatron-style TP without hand-written collectives.

    Channel plan divisibility: widths 32..512 divide any power-of-two mesh
    up to 8; kernels whose out-axis does not divide (segmap, tiny test
    widths) replicate, which GSPMD handles per-tensor.
    """

    name = "TP"

    def _mesh_layout(self, config, devices):
        devs = list(devices if devices is not None else jax.local_devices())
        return MeshConfig(model=len(devs), params="channel"), devs


class FullyShardedDataParallel(MultiProcessMixin, Strategy):
    """``-t FSDP``: ZeRO-3-style fully sharded data parallel — the
    ``Nx1x1@fsdp`` point: batch sharded over ('data',) exactly like DP,
    but parameters and Adam state are ALSO sharded over 'data' (each
    leaf along its largest divisible axis). GSPMD inserts the per-layer
    all-gather of params in the forward/backward and the reduce-scatter
    of gradients — the ZeRO dance — from annotations alone.

    Multi-process capable (ZeRO semantics, unlike torch-DP-shaped ``DP``):
    the mesh spans EVERY process's devices and the MultiProcessMixin
    contract applies — per-process batch (global = b × data rows), sample
    sharding, process-local batch assembly. Sharded state on a pod is not
    fully addressable on any one host; checkpointing allgathers each such
    leaf collectively (checkpoint._to_host). The DDP lr × world quirk is
    NOT applied: FSDP is a memory layout, not the reference's DDP recipe.
    """

    name = "FSDP"

    def _mesh_layout(self, config, devices):
        if devices is not None or jax.process_count() == 1:
            # single-process (or explicit devices): exactly DP's mesh,
            # including the shrink-to-largest-divisor warning path
            devs = list(devices if devices is not None else jax.local_devices())
            n = _shrunk_data_degree(self.name, config.batch_size, len(devs))
            cfg = MeshConfig(
                data=n, params="fsdp", per_process_batch=True, drop_last=True,
            )
            return cfg, devs[:n]
        devs = list(jax.devices())
        if (config.batch_size * jax.process_count()) % len(devs) != 0:
            raise ValueError(
                f"FSDP: global batch {config.batch_size} × "
                f"{jax.process_count()} processes must divide the "
                f"{len(devs)}-device mesh"
            )
        cfg = MeshConfig(
            data=len(devs), params="fsdp", per_process_batch=True,
            drop_last=True,
        )
        return cfg, devs


class GenericMesh(MultiProcessMixin, Strategy):
    """``-t DxMxS[@rule[+rule]]``: an arbitrary point in mesh-shape
    space (parallel/mesh.py grammar) — including the hybrids no legacy
    class expresses: ``2x2x1`` (DP x TP), ``2x2x1@fsdp`` (FSDP x TP),
    ``2x4x1@sp`` (DDP_SP's geometry), ``4x1x2`` (DDP_MP's).

    Semantics follow the multi-process (torchrun/FSDP) convention:
    ``batch_size`` is per-process, no DDP lr quirk. Explicit specs fail
    LOUDLY on infeasible divisibility (no silent mesh shrinking — the
    user named an exact geometry). ``stage > 1`` with ``model > 1``
    runs the pipeline schedules with IN-STAGE sharding: the mesh's
    per-tree params rule (channel-TP over 'model', ZeRO over 'data')
    applies inside the stage functions (parallel/pipeline.py, module
    docstring "In-stage sharding"). The one remaining refusal is the
    'spatial' model role inside a stage — its halo exchanges cannot
    ride the tick program's stage-gated conds."""

    name = "mesh"

    def _mesh_layout(self, config, devices):
        cfg = mesh_rules.parse_mesh_spec(config.train_method)
        self.name = mesh_rules.canonical_spec(cfg)
        devs = list(devices if devices is not None else jax.devices())
        if cfg.size > len(devs):
            raise ValueError(
                f"mesh {self.name} needs {cfg.size} devices, "
                f"got {len(devs)}"
            )
        if cfg.stage > 1 and cfg.model > 1 and cfg.model_role == "spatial":
            raise ValueError(
                f"mesh {self.name}: a 'spatial' model role inside a "
                f"pipeline stage is not executable — spatial sharding "
                f"halo-exchanges inside every schedule tick, which the "
                f"stage-gated lax.cond program cannot carry; use the "
                f"channel role on the model axis "
                f"('{cfg.data}x{cfg.model}x{cfg.stage}') or keep spatial "
                f"sharding on a flat mesh "
                f"('{cfg.data}x{cfg.model}x1@sp')"
            )
        # divisibility is judged on the GLOBAL batch: mesh specs use
        # the torchrun convention (batch_size is per-process) while the
        # data axis spans ALL processes — `-t 8x1x1 -b 4` on 2 hosts is
        # global batch 8 over data=8, a launch DDP accepts (FSDP's
        # multi-process check in this file uses the same product)
        global_batch = config.batch_size * jax.process_count()
        if cfg.stage > 1:
            _validate_pipeline_schedule(config)
            mb = config.num_microbatches
            if global_batch % (cfg.data * mb):
                raise ValueError(
                    f"mesh {self.name}: global batch {global_batch} "
                    f"(batch_size {config.batch_size} x "
                    f"{jax.process_count()} processes) must be a "
                    f"multiple of data x microbatches = {cfg.data} x {mb}"
                )
        elif cfg.data > 1 and global_batch % cfg.data:
            raise ValueError(
                f"mesh {self.name}: global batch {global_batch} "
                f"(batch_size {config.batch_size} x "
                f"{jax.process_count()} processes) must divide the data "
                f"axis ({cfg.data}) — explicit mesh specs never shrink "
                f"silently"
            )
        if cfg.model > 1 and cfg.model_role == "spatial":
            h = config.image_size[1]
            deep = 2 ** config.model_levels
            if (h // deep) % cfg.model:
                raise ValueError(
                    f"mesh {self.name}: the deepest level's {h // deep} "
                    f"image rows must divide the spatial axis "
                    f"({cfg.model})"
                )
        return cfg, devs


STRATEGIES = {
    cls.name: cls
    for cls in (
        SingleDevice,
        DataParallel,
        DistributedDataParallel,
        Pipeline,
        HybridDataPipeline,
        SpatialParallel,
        HybridDataSpatial,
        TensorParallel,
        FullyShardedDataParallel,
    )
}


def build_strategy(config: TrainConfig, devices=None) -> Strategy:
    """Resolve ``config.train_method`` — a legacy strategy name (an
    alias into mesh-shape space) or a ``DxMxS[@rule]`` mesh spec — to a
    constructed strategy."""
    cls = STRATEGIES.get(config.train_method)
    if cls is not None:
        return cls(config, devices)
    if mesh_rules.is_mesh_spec(config.train_method):
        return GenericMesh(config, devices)
    raise ValueError(
        f"Unknown train method {config.train_method!r}; "
        f"expected one of {sorted(STRATEGIES)} or a mesh spec "
        f"DxMxS[@fsdp|sp] (docs/DISTRIBUTED.md 'The mesh engine')"
    )
