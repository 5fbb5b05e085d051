"""Microbatched pipeline schedules over a 'stage' mesh axis, S stages.

TPU-native re-design of the reference's hand-written 2-GPU pipeline
(reference model/unet_model.py:14-53). The reference gets overlap for free
from async CUDA launches: while cuda:1 decodes microbatch i, cuda:0 encodes
microbatch i+1, with the bottleneck + all 4 skip tensors copied cuda:0→cuda:1
each microbatch (unet_model.py:36-37,47-48). On TPU the same schedule is
written explicitly: `shard_map` over a ``stage`` mesh axis, a static loop
over schedule ticks, `lax.cond` selecting each device's stage work, and
`jax.lax.ppermute` carrying inter-stage payloads over ICI.

Generalized from the round-3 two-stage schedule to S stages: the model
exposes its linear block order as 2L+1 segments
(models/unet.py `UNet.apply_segment`, models/milesial.py the same), a stage
is any contiguous run of segments, and ``cuts`` picks the boundaries. The
default for S=2 is the faithful reference cut (encoder+mid | decoder+head,
unet_model.py:16-20); for S>2 segments are split evenly.

Skip connections cross stages: encoder segments push skip tensors onto the
carry, decoder segments pop them, so the payload on the edge between stages
s and s+1 is exactly the carry at that cut — bottleneck + not-yet-consumed
skips — and intermediate stages relay the skips their segments don't touch.
Each edge has its own payload shapes; every device materializes every
edge's (zero) buffer, but only the owning stage's is nonzero, and
``lax.cond`` keeps the inactive stage computations unexecuted on TPU.

Two schedules (``TrainConfig.pipeline_schedule``):

``gpipe`` — fill-drain: M microbatches over M+S−1 forward ticks; the whole
schedule is a pure function of the (replicated) params, so `jax.grad`
through the `shard_map` gives the pipelined backward automatically —
`ppermute`'s transpose is the reverse permute, so activation cotangents
flow stage s+1 → s with the same overlap structure. The price is GPipe's
memory profile (Huang et al., 2019): autodiff saves every microbatch's
stage activations across all M+S−1 ticks, so peak activation memory grows
linearly in M — raising M to amortize the (S−1)-tick bubble is exactly
what runs out of HBM first.

``1f1b`` — PipeDream-flush (Narayanan et al., 2021), built in
`make_pipeline_value_and_grad_fn`: an explicit backward schedule whose
steady-state ticks alternate one-forward-one-backward, holding at most
S−s in-flight microbatches at stage s — peak activation memory is bounded
by S, independent of M, which turns M from a memory liability into a free
throughput lever. Two wrinkles specific to this codebase:

  * The loss is NOT microbatch-additive (the log-dice term is a ratio of
    whole-batch sums, reference utils/utils.py:18-23), so the activation
    cotangent entering ANY backward depends on the psummed whole-batch
    stats — no backward may start before every forward has run. The
    schedule therefore runs two phases inside one shard_map: a
    forward-only stats pass (differentiated by nothing, so XLA frees its
    activations tick by tick), then the 1F1B forward/backward pass
    against the now-known global stats cotangent. The extra forward pass
    is the same price `make_accum_train_step` documents for exact
    accumulation under a non-additive loss.
  * `jax.vjp` residuals are function closures, which cannot cross
    `lax.cond`/`ppermute` as carried state — so the residual carried
    from a stage's forward tick to its backward tick is the stage's
    INPUT payload (the cut carry: bottleneck + pending skips), and the
    backward tick runs `jax.vjp` on the stage from that carry
    (per-stage rematerialization). In-flight state per stage is ≈S−s
    cut carries; the full conv activations exist only transiently
    inside the single backward tick's own VJP.

Per-stage weight gradients accumulate across microbatches in float32 and
one explicit `psum` over ('stage'[, 'data']) closes the hybrid: each
stage's params-gradient leaves are nonzero only for its own segments, so
the stage-psum assembles the full gradient and the data-psum is the DDP
all-reduce (the same reduction `jax.grad`'s transpose inserts for the
gpipe schedule).

BatchNorm threads through both schedules (models/milesial.py): stage
functions take ``(params, batch_stats, x, skips) → ((x, skips),
batch_stats')`` and each stage applies its segments with
``mutable=['batch_stats']`` per microbatch, in microbatch order — GPipe's
published BatchNorm treatment (statistics over each microbatch; running
stats updated per microbatch). Only the owning stage's layers move, so the
final running stats are assembled by psumming each leaf's DELTA across the
stage axis (zeros elsewhere — the stage-axis psum of microbatch moments);
on a hybrid mesh the deltas are additionally pmean'ed over 'data' (each
data replica saw its own shard — torch-DDP-default local-BN semantics,
averaged into one replicated running-stats tree).

Parameters are replicated across the stage axis (30 MB of params —
replication is the right trade; what is *pipelined* is the activation
traffic, which at (µB,640,960,32) per skip is the dominant term exactly as
in the reference).

In-stage sharding (hybrid ``DxMxS`` meshes, ``M>1`` and/or ``@fsdp``):
when the builders receive the strategy's ``mesh_config``, the mesh's
per-tree params rule (mesh.state_leaf_spec — channel-TP over 'model',
ZeRO over 'data') applies INSIDE the stage functions. Params enter the
shard_map sharded per-leaf; the body reconstructs each leaf with ONE
tiled `all_gather` per sharded dim at the top of the step — before any
tick's `lax.cond`, so no collective ever sits inside a stage-gated
branch (which would deadlock the rendezvous and trip the analyzer's
branch-divergent rule). Stage compute then runs on full params, the
per-step gather being the ZeRO-3 trade scaled to the pipeline. The
model axis carries NO schedule collective: replicas along it compute
identically, so the stats/grad/BN psums still close over
('stage'[, 'data']) only — extending them over 'model' would
double-reduce. gpipe's backward needs no new code at all: shard_map's
transpose machinery reduces the per-leaf cotangents back to each
input's own shard layout (the all_gather transposes to a
reduce_scatter), verified grad-exact against the plain step; 1f1b's
explicit f32 accumulators stay full-size per device and each leaf is
sliced back to its own shard after the closing psum, making the grads
output sharded exactly like the params input. A 'spatial' model role
inside a stage is refused loudly (halo exchanges would need to run
inside every tick's cond).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distributedpytorch_tpu.ops.losses import bce_dice_stats, loss_from_stats
# The stated f32 contracts (ops/precision.py, docs/PERFORMANCE.md
# "Precision"): loss statistics accumulate in LOSS_DTYPE and per-stage
# weight gradients in WGRAD_DTYPE under EVERY --dtype policy — bf16
# params change what autodiff emits per backward tick, never what this
# schedule accumulates or psums.
from distributedpytorch_tpu.ops.precision import (
    LOSS_DTYPE,
    WGRAD_DTYPE,
    cast_float_leaves,
)

PIPELINE_SCHEDULES = ("gpipe", "1f1b")


def _resolve_data_axis(mesh: Mesh, data_axis):
    """The unified data-axis plumbing: ``"auto"`` (the builders'
    default) derives the hybrid data axis from the mesh itself — a
    'data' axis present means batches shard over it and the stats/grad
    psums close over ('stage', 'data'). Callers no longer thread the
    axis by hand (the strategy layer's mesh config IS the mesh); an
    explicit name or None still overrides for direct API users."""
    if data_axis == "auto":
        return "data" if "data" in mesh.axis_names else None
    return data_axis


def default_cuts(num_segments: int, num_stages: int) -> Tuple[int, ...]:
    """Stage boundaries (the segment index each stage s ≥ 1 starts at).

    S=2 reproduces the reference cut — encoder+mid | decoder+head
    (unet_model.py:16-20) — which for 2L+1 segments is the boundary after
    segment L. Other S split the segment list as evenly as possible, with
    the remainder on the LAST stages: the early segments (shallow encoder
    levels) carry most of the FLOPs, and throughput is set by the slowest
    stage, so extra segments belong with the cheap deep/decoder work."""
    if num_stages == 2:
        return ((num_segments - 1) // 2 + 1,)
    base, rem = divmod(num_segments, num_stages)
    sizes = [
        base + (1 if i >= num_stages - rem else 0) for i in range(num_stages)
    ]
    cuts, acc = [], 0
    for size in sizes[:-1]:
        acc += size
        cuts.append(acc)
    return tuple(cuts)


def _stage_ranges(
    num_segments: int, num_stages: int, cuts: Optional[Sequence[int]]
) -> list:
    if num_stages < 1 or num_stages > num_segments:
        raise ValueError(
            f"num_stages {num_stages} out of range for a "
            f"{num_segments}-segment model"
        )
    cuts = tuple(cuts) if cuts is not None else default_cuts(num_segments, num_stages)
    if len(cuts) != num_stages - 1 or list(cuts) != sorted(set(cuts)) or any(
        not 0 < c < num_segments for c in cuts
    ):
        raise ValueError(
            f"cuts {cuts} must be {num_stages - 1} strictly increasing "
            f"segment indices in (0, {num_segments})"
        )
    bounds = (0,) + cuts + (num_segments,)
    return [range(bounds[s], bounds[s + 1]) for s in range(num_stages)]


def _ppermute_edge(tree, axis_name: str, edge: int, reverse: bool = False):
    """Move edge ``edge``'s payload between stages ``edge`` and ``edge``+1:
    forward activations stage e → e+1, or (``reverse``) activation
    cotangents stage e+1 → e. Every other device receives zeros — which is
    what inactive stages should hold."""
    perm = [(edge + 1, edge)] if reverse else [(edge, edge + 1)]
    return jax.tree.map(
        lambda x: jax.lax.ppermute(x, axis_name, perm=perm), tree
    )


def _zeros_of(template):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), template)


def _is_stateful(model) -> bool:
    """Models carrying non-trainable collections (BatchNorm running stats)
    — one definition with the plain steps (train/steps.py)."""
    from distributedpytorch_tpu.train.steps import is_stateful_model

    return is_stateful_model(model)


def _merge_stats(full: dict, updates) -> dict:
    """Merge a partial ``batch_stats`` update tree (what a mutable apply of
    ONE segment returns — only that segment's BN layers) into the full
    collection, preserving the full tree's structure so the result can
    cross `lax.cond`/carry boundaries against the unmodified tree."""
    out = dict(full)
    for k, v in updates.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge_stats(out[k], v)
        else:
            out[k] = v
    return out


def _build_stage_fns(model, stage_ranges, remat: bool, train: bool = True):
    """One function per stage: chain its segments' carry → carry.

    Stateless models:  ``stage_fn(params, x, skips) -> (x, skips)``.
    Stateful models:   ``stage_fn(params, bn, x, skips) -> ((x, skips), bn')``
    where ``bn`` is the full batch_stats collection and ``bn'`` merges the
    stage's per-segment updates (train mode; eval applies with the running
    averages and returns ``bn`` unchanged).
    """
    stateful = _is_stateful(model)

    if stateful:
        def seg_apply(params, bn, x, skips, seg):
            variables = {"params": params, "batch_stats": bn}
            if train:
                (x, skips), upd = model.apply(
                    variables, x, skips, seg, True,
                    method=type(model).apply_segment,
                    mutable=["batch_stats"],
                )
                return x, skips, _merge_stats(bn, dict(upd["batch_stats"]))
            x, skips = model.apply(
                variables, x, skips, seg, False,
                method=type(model).apply_segment,
            )
            return x, skips, bn
    else:
        def seg_apply(params, x, skips, seg):
            return model.apply(
                {"params": params}, x, skips, seg,
                method=type(model).apply_segment,
            )

    fns = []
    for rng in stage_ranges:
        if stateful:
            def stage_fn(params, bn, x, skips, _rng=rng):
                for seg in _rng:
                    x, skips, bn = seg_apply(params, bn, x, skips, seg)
                return (x, skips), bn
        else:
            def stage_fn(params, x, skips, _rng=rng):
                for seg in _rng:
                    x, skips = seg_apply(params, x, skips, seg)
                return x, skips

        fns.append(jax.checkpoint(stage_fn) if remat else stage_fn)
    return fns


def _edge_templates(stage_fns, params, bn_state, first_input):
    """Per-edge payload templates: chain the stage functions over one
    microbatch's shapes (eval_shape — no FLOPs, no memory). Edge e's
    template is the carry entering stage e+1."""
    S = len(stage_fns)

    def simulate(params):
        x, skips = first_input(0)
        bn = bn_state
        outs = []
        for s in range(S - 1):
            if bn_state is not None:
                (x, skips), bn = stage_fns[s](params, bn, x, skips)
            else:
                x, skips = stage_fns[s](params, x, skips)
            outs.append((x, skips))
        return tuple(outs)

    return jax.eval_shape(simulate, params)


def _run_schedule(stage_fns, M, stage_axis, params, first_input, last_fn,
                  last_zero_fn, bn_state=None):
    """Execute the M+S−1-tick fill-drain forward schedule on this device
    (inside a shard_map body); returns the last stage's M outputs in
    microbatch order, paired with the device's final batch_stats when
    ``bn_state`` is given. ONE definition of the forward schedule — the
    loss, forward, and 1F1B phase-A paths differ only in `last_fn`.

    ``first_input(m) -> (x, skips)`` feeds stage 0 (a microbatch slice);
    ``last_fn(params, bn, payload, m) -> (out, bn')`` is what the final
    stage does with its stage-input payload; ``last_zero_fn()`` is that
    output's zeros (what every non-final-stage device holds in each slot —
    summing or psumming across the stage axis recovers the real values).
    Stateful stages thread the full batch_stats tree tick to tick; each
    device's tree moves only where its own stage's segments have BN layers.
    """
    S = len(stage_fns)
    stateful = bn_state is not None
    stage = jax.lax.axis_index(stage_axis)

    templates = _edge_templates(stage_fns, params, bn_state, first_input)
    zero_payloads = [_zeros_of(t) for t in templates]

    bn = bn_state
    outs = []
    in_flight = list(zero_payloads)  # in_flight[e] feeds stage e+1
    for t in range(M + S - 1):
        outgoing = [None] * (S - 1)
        for s in range(S):
            m = t - s  # microbatch stage s handles this tick (static)
            if not 0 <= m < M:
                continue
            payload_in = first_input(m) if s == 0 else in_flight[s - 1]
            if s < S - 1:
                if stateful:
                    def work(s=s, payload_in=payload_in, bn=bn):
                        return stage_fns[s](params, bn, *payload_in)

                    outgoing[s], bn = jax.lax.cond(
                        stage == s, work,
                        lambda _s=s, bn=bn: (zero_payloads[_s], bn),
                    )
                else:
                    outgoing[s] = jax.lax.cond(
                        stage == s,
                        functools.partial(stage_fns[s], params, *payload_in),
                        lambda _s=s: zero_payloads[_s],
                    )
            else:
                if stateful:
                    out, bn = jax.lax.cond(
                        stage == s,
                        functools.partial(last_fn, params, bn, payload_in, m),
                        lambda bn=bn: (last_zero_fn(), bn),
                    )
                    outs.append(out)
                else:
                    outs.append(jax.lax.cond(
                        stage == s,
                        functools.partial(last_fn, params, None, payload_in, m),
                        last_zero_fn,
                    ))
        in_flight = [
            _ppermute_edge(outgoing[e], stage_axis, e)
            if outgoing[e] is not None
            else zero_payloads[e]
            for e in range(S - 1)
        ]
    return outs, bn


def _combine_bn(model_state, bn_final, stage_axis, data_axis):
    """Assemble the replicated post-step batch_stats from per-device final
    trees: each leaf moved on exactly ONE stage (zeros-delta elsewhere), so
    psumming the deltas over the stage axis broadcasts every stage's
    updates to all devices; a hybrid mesh additionally pmeans over 'data'
    (each replica normalized its own shard — average the running stats)."""
    def combine(init, fin):
        delta = jax.lax.psum(fin - init, stage_axis)
        if data_axis:
            delta = jax.lax.pmean(delta, data_axis)
        return init + delta

    return jax.tree.map(combine, model_state, bn_final)


def _reduce_grads(grads, axes):
    """Close the schedule: each stage holds only its own segments'
    gradient leaves (zeros elsewhere), so the stage psum assembles the
    full gradient and the 'data' psum is the DDP all-reduce. A named
    seam so the static analyzer's mutation tests (tests/test_analysis.py)
    can drop an axis and prove the comms-contract check catches it."""
    return jax.lax.psum(grads, axes)


def _broadcast_preds(preds, stage_axis):
    """Replicate inference output across the stage axis: the last stage
    holds the real predictions, the rest hold zeros, so the psum is a
    broadcast-from-last-stage (the reference's ``.to('cuda:0')`` gather).
    A named seam (same discipline as ``_reduce_grads``) so the static
    analyzer's mutation tests can drop the eval reduction and prove the
    derived EVAL contract catches stage-local metrics shipping as
    global."""
    return jax.lax.psum(preds, stage_axis)


def _in_stage_config(mesh: Mesh, mesh_config):
    """Gate for in-stage sharding: returns the mesh config when its
    params rule actually shards leaves over an axis this mesh carries
    (channel-TP over the model axis, ZeRO over 'data'), else None — and
    the None path is byte-identical to the pre-hybrid flat schedules
    (replicated params, ``P()`` in_specs). Refuses the spatial model
    role: its halo exchanges would have to run inside every tick's
    stage-gated ``lax.cond``, which the schedule's ppermute program does
    not carry."""
    if mesh_config is None:
        return None
    if mesh_config.model > 1 and mesh_config.model_role == "spatial":
        raise ValueError(
            "pipeline: a 'spatial' model role inside a stage is not "
            "executable — spatial sharding halo-exchanges inside every "
            "schedule tick, which the stage-gated lax.cond program "
            "cannot carry; use the channel role on the model axis "
            "(e.g. '2x2x2') or keep spatial sharding on a flat mesh "
            "(e.g. '2x2x1@sp')"
        )
    model_tp = (
        mesh_config.model > 1
        and mesh_config.model_axis_name in mesh.axis_names
    )
    zero = (
        "fsdp" in mesh_config.params
        and mesh_config.data > 1
        and "data" in mesh.axis_names
    )
    return mesh_config if (model_tp or zero) else None


def _param_spec_tree(cfg, params):
    """Per-leaf in-stage PartitionSpecs from the GLOBAL param shapes —
    the same mesh.state_leaf_spec rule the strategy layer places state
    with, evaluated OUTSIDE the shard_map (a local shard's shape could
    flip a divisibility decision)."""
    from distributedpytorch_tpu.parallel.mesh import state_leaf_spec

    return jax.tree.map(lambda x: state_leaf_spec(cfg, x.shape), params)


def _spec_axes(spec):
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        for name in (entry if isinstance(entry, tuple) else (entry,)):
            yield dim, name


def _gather_params(tree, specs):
    """Reconstruct each leaf's full value from its in-stage shards: one
    tiled `all_gather` per sharded dim, ONCE per step at the top of the
    shard_map body. Replicas along the model axis then compute
    identically, so the schedule's ppermutes/psums need no new axes."""
    def gather(x, spec):
        for dim, name in _spec_axes(spec):
            x = jax.lax.all_gather(x, name, axis=dim, tiled=True)
        return x

    return jax.tree.map(gather, tree, specs)


def _slice_to_shard(tree, specs, axis_sizes):
    """The inverse of `_gather_params` for gradient outputs: 1f1b's f32
    accumulators are full-size per device, so after the closing psum
    each leaf is sliced down to this device's own shard per its spec —
    the grads then leave the shard_map sharded exactly like the params
    entered (out_specs = the same spec tree)."""
    def slice_leaf(x, spec):
        for dim, name in _spec_axes(spec):
            n = int(axis_sizes[name])
            if n == 1:
                continue
            shard = x.shape[dim] // n
            idx = jax.lax.axis_index(name)
            x = jax.lax.dynamic_slice_in_dim(x, idx * shard, shard, axis=dim)
        return x

    return jax.tree.map(slice_leaf, tree, specs)


def _shape_key(tree):
    """Cache key for the lazily-built in-stage shard_maps: the spec
    trees depend only on the global leaf shapes (one model = one key in
    practice; direct API users swapping param shapes get a fresh
    build)."""
    return tuple(tuple(x.shape) for x in jax.tree.leaves(tree))


def _stats_fn(use_pallas: bool):
    if use_pallas:
        from distributedpytorch_tpu.ops.fused_loss import bce_dice_stats_fused

        return bce_dice_stats_fused
    return bce_dice_stats


def _check_microbatching(batch_size: int, M: int) -> int:
    if batch_size < M or batch_size % M:
        raise ValueError(
            f"per-shard batch {batch_size} must be a positive "
            f"multiple of num_microbatches={M}"
        )
    return batch_size // M


def make_pipeline_loss_fn(
    model,
    mesh: Mesh,
    num_microbatches: int = 2,
    stage_axis: str = "stage",
    data_axis: str = "auto",
    remat: bool = False,
    cuts: Optional[Sequence[int]] = None,
    use_pallas: bool = False,
    mesh_config=None,
) -> Callable:
    """Build the fill-drain (gpipe) pipeline loss over `mesh`'s ``stage``
    axis (S = the axis size): ``loss_fn(params, batch) -> loss`` for
    stateless models, ``loss_fn(params, model_state, batch) -> (loss,
    model_state')`` for stateful (BatchNorm) ones — differentiate the
    latter with ``has_aux=True``.

    `batch` is ``{'image': (B,H,W,3) f32, 'mask': (B,H,W,1) f32 target}``
    with B divisible by num_microbatches (× data-axis size when hybrid).
    Returns the same scalar loss as the non-pipelined step: the mean over the
    full batch (microbatches are equal-sized, so mean-of-µmeans == mean).

    `use_pallas` computes each microbatch's loss statistics with the fused
    one-pass Pallas kernel + its analytic VJP (ops/fused_loss.py) — legal
    here because inside the shard_map schedule every array is
    device-local, exactly where pallas_call belongs.

    ``mesh_config`` (the strategy's MeshConfig) engages in-stage param
    sharding on hybrid meshes — see the module docstring; None keeps the
    replicated-params flat path bit-identical.
    """
    in_stage = _in_stage_config(mesh, mesh_config)
    data_axis = _resolve_data_axis(mesh, data_axis)
    num_stages = mesh.shape[stage_axis]
    stage_ranges = _stage_ranges(model.num_segments, num_stages, cuts)
    stage_fns = _build_stage_fns(model, stage_ranges, remat)
    stateful = _is_stateful(model)
    M = int(num_microbatches)
    S = num_stages
    stats_fn = _stats_fn(use_pallas)

    batch_spec = P(data_axis) if data_axis else P()
    axes = (stage_axis, data_axis) if data_axis else (stage_axis,)
    batch_in_spec = {"image": batch_spec, "mask": batch_spec}

    def per_device(params, model_state, batch, specs=None):
        if specs is not None:
            params = _gather_params(params, specs)
        images = batch["image"]
        masks = batch["mask"]
        mb = _check_microbatching(images.shape[0], M)

        def microbatch_input(m):
            return jax.lax.dynamic_slice_in_dim(images, m * mb, mb, axis=0), ()

        def last_stage_stats(params, bn, payload, m):
            if stateful:
                (x, _skips), bn = stage_fns[S - 1](params, bn, *payload)
            else:
                x, _skips = stage_fns[S - 1](params, *payload)
            target = jax.lax.dynamic_slice_in_dim(masks, m * mb, mb, axis=0)
            # The log-dice term is a ratio of WHOLE-batch sums (reference
            # utils.py:18-23 computes it on the concatenated pipe output), so
            # microbatches accumulate sufficient statistics, not losses.
            out = stats_fn(x, target)
            return (out, bn) if stateful else out

        per_mb_stats, bn_final = _run_schedule(
            stage_fns, M, stage_axis, params, microbatch_input,
            last_stage_stats, lambda: jnp.zeros((4,), LOSS_DTYPE),
            bn_state=model_state,
        )
        stats_sum = sum(per_mb_stats)
        # Sum stats across the stage axis (only the last stage contributed)
        # and, in the hybrid, across data shards — the result is the EXACT
        # full-global-batch loss, not an average of shard losses.
        stats = jax.lax.psum(stats_sum, axes)
        loss = loss_from_stats(stats)
        if stateful:
            return loss, _combine_bn(model_state, bn_final, stage_axis, data_axis)
        return loss, None

    if in_stage is None:
        if stateful:
            return shard_map(
                per_device,
                mesh=mesh,
                in_specs=(P(), P(), batch_in_spec),
                out_specs=(P(), P()),
                check_vma=False,
            )
        return shard_map(
            lambda params, batch: per_device(params, None, batch)[0],
            mesh=mesh,
            in_specs=(P(), batch_in_spec),
            out_specs=P(),
            check_vma=False,
        )

    # in-stage sharding: the per-leaf spec tree depends on the GLOBAL
    # param shapes, so the shard_map is built lazily at first call (and
    # cached per shape signature — one model, one build)
    cache = {}

    def _built(params):
        key = _shape_key(params)
        fn = cache.get(key)
        if fn is None:
            specs = _param_spec_tree(in_stage, params)
            if stateful:
                fn = shard_map(
                    functools.partial(per_device, specs=specs),
                    mesh=mesh,
                    in_specs=(specs, P(), batch_in_spec),
                    out_specs=(P(), P()),
                    check_vma=False,
                )
            else:
                fn = shard_map(
                    lambda p, b: per_device(p, None, b, specs=specs)[0],
                    mesh=mesh,
                    in_specs=(specs, batch_in_spec),
                    out_specs=P(),
                    check_vma=False,
                )
            cache[key] = fn
        return fn

    if stateful:
        def loss_fn(params, model_state, batch):
            return _built(params)(params, model_state, batch)
    else:
        def loss_fn(params, batch):
            return _built(params)(params, batch)
    return loss_fn


def make_pipeline_value_and_grad_fn(
    model,
    mesh: Mesh,
    num_microbatches: int = 2,
    stage_axis: str = "stage",
    data_axis: str = "auto",
    remat: bool = False,
    cuts: Optional[Sequence[int]] = None,
    use_pallas: bool = False,
    schedule: str = "1f1b",
    mesh_config=None,
) -> Callable:
    """Build ``f(params, model_state, batch) -> (loss, grads, model_state')``
    for either pipeline schedule (``model_state`` is None for stateless
    models and passed through unchanged).

    ``schedule='gpipe'`` differentiates the fill-drain loss with
    `jax.value_and_grad` (activation memory grows with M).
    ``schedule='1f1b'`` runs the explicit PipeDream-flush schedule built
    here: phase A is the forward-only stats pass (fill-drain, nothing
    saved), phase B alternates one-forward-one-backward per stage over
    2(M+S−1) ticks — forward of microbatch m at stage s on tick s+2m,
    backward on tick 2S−1−s+2m, so stage s holds at most ≈S−s in-flight
    input carries and the bubble matches gpipe's. Each backward tick runs
    `jax.vjp` on the stage's segment run from the saved input carry
    against the incoming activation cotangent (the global stats cotangent
    at the last stage); cotangents flow stage s+1 → s over the reverse
    `ppermute`, and per-stage weight gradients accumulate in float32
    before one psum over ('stage'[, 'data']) closes DDP_MP. See the
    module docstring for why the loss's non-additivity forces phase A and
    why the carried residual is the input carry.
    """
    if schedule not in PIPELINE_SCHEDULES:
        raise ValueError(
            f"pipeline schedule must be one of {PIPELINE_SCHEDULES}, "
            f"got {schedule!r}"
        )
    in_stage = _in_stage_config(mesh, mesh_config)
    data_axis = _resolve_data_axis(mesh, data_axis)
    stateful = _is_stateful(model)

    if schedule == "gpipe":
        loss_fn = make_pipeline_loss_fn(
            model, mesh, num_microbatches=num_microbatches,
            stage_axis=stage_axis, data_axis=data_axis, remat=remat,
            cuts=cuts, use_pallas=use_pallas, mesh_config=mesh_config,
        )

        def _wide(params):
            # REDUCE_DTYPE contract: differentiate w.r.t. an f32 view of
            # the params so autodiff's cotangents — and the implicit
            # schedule-closing psum the shard_map transpose inserts over
            # ('stage'[,'data']) — reduce in f32 even when the --dtype
            # policy stores bf16 params (bf16→f32 is exact; the model
            # re-casts to its compute dtype immediately, so the forward
            # is unchanged; a no-op for f32 params).
            return cast_float_leaves(params, WGRAD_DTYPE)

        if stateful:
            def gpipe_vag(params, model_state, batch):
                (loss, new_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(_wide(params), model_state, batch)
                return loss, grads, new_state
        else:
            def gpipe_vag(params, model_state, batch):
                loss, grads = jax.value_and_grad(loss_fn)(_wide(params), batch)
                return loss, grads, model_state
        return gpipe_vag

    num_stages = mesh.shape[stage_axis]
    stage_ranges = _stage_ranges(model.num_segments, num_stages, cuts)
    stage_fns = _build_stage_fns(model, stage_ranges, remat)
    M = int(num_microbatches)
    S = num_stages
    stats_fn = _stats_fn(use_pallas)

    batch_spec = P(data_axis) if data_axis else P()
    axes = (stage_axis, data_axis) if data_axis else (stage_axis,)
    batch_in_spec = {"image": batch_spec, "mask": batch_spec}

    def per_device(params, model_state, batch, specs=None):
        if specs is not None:
            params = _gather_params(params, specs)
        images = batch["image"]
        masks = batch["mask"]
        mb = _check_microbatching(images.shape[0], M)
        stage = jax.lax.axis_index(stage_axis)

        def microbatch_input(m):
            return jax.lax.dynamic_slice_in_dim(images, m * mb, mb, axis=0), ()

        def target(m):
            return jax.lax.dynamic_slice_in_dim(masks, m * mb, mb, axis=0)

        def fwd_stage(s, payload):
            """Stage forward for phase B (BN in train mode, updates
            discarded: phase A already accumulated them, and the
            normalization itself reads only the microbatch moments)."""
            if stateful:
                out, _bn = stage_fns[s](params, model_state, *payload)
                return out
            return stage_fns[s](params, *payload)

        # ---- phase A: forward-only fill-drain — global loss stats (and
        # BatchNorm running-stat updates); NOT differentiated, so XLA
        # frees each tick's activations as soon as the edge payload ships.
        def last_stage_stats(params, bn, payload, m):
            if stateful:
                (x, _skips), bn = stage_fns[S - 1](params, bn, *payload)
                return stats_fn(x, target(m)), bn
            x, _skips = stage_fns[S - 1](params, *payload)
            return stats_fn(x, target(m))

        per_mb_stats, bn_final = _run_schedule(
            stage_fns, M, stage_axis, params, microbatch_input,
            last_stage_stats, lambda: jnp.zeros((4,), LOSS_DTYPE),
            bn_state=model_state if stateful else None,
        )
        stats = jax.lax.psum(sum(per_mb_stats), axes)
        loss = loss_from_stats(stats)
        # the 4-vector every backward needs: ∇loss at the GLOBAL stats
        ct_stats = jax.grad(loss_from_stats)(stats)
        new_model_state = (
            _combine_bn(model_state, bn_final, stage_axis, data_axis)
            if stateful else model_state
        )

        # ---- phase B: 1F1B — forward of (s, m) at tick s+2m, backward at
        # tick 2S−1−s+2m. Forward and backward tick sets of one stage have
        # opposite parities, so each stage does at most one unit per tick;
        # the last stage's "forward" tick only banks the arriving carry
        # (its compute happens inside the backward tick's VJP).
        templates = _edge_templates(
            stage_fns, params, model_state if stateful else None,
            microbatch_input,
        )
        zero_payloads = [_zeros_of(t) for t in templates]
        zero_mb_input = _zeros_of(
            jax.eval_shape(lambda p: microbatch_input(0), params)
        )
        grad_zero = jax.tree.map(
            lambda x: jnp.zeros(x.shape, WGRAD_DTYPE), params
        )
        grads = grad_zero
        saved = {}  # (s, m) -> stage input carry, live ≈S−s ticks
        fwd_edge = list(zero_payloads)  # fwd_edge[e] feeds stage e+1
        bwd_edge = list(zero_payloads)  # bwd_edge[e]: cot of stage e's output
        for t in range(2 * M + 2 * S - 2):
            out_fwd = [None] * (S - 1)
            out_bwd = [None] * (S - 1)
            for s in range(S):
                m_f, r_f = divmod(t - s, 2)
                if r_f == 0 and 0 <= m_f < M:  # forward unit
                    payload_in = (
                        microbatch_input(m_f) if s == 0 else fwd_edge[s - 1]
                    )
                    saved[(s, m_f)] = payload_in
                    if s < S - 1:
                        out_fwd[s] = jax.lax.cond(
                            stage == s,
                            functools.partial(fwd_stage, s, payload_in),
                            lambda _s=s: zero_payloads[_s],
                        )
                m_b, r_b = divmod(t - (2 * S - 1 - s), 2)
                if r_b == 0 and 0 <= m_b < M:  # backward unit
                    payload_in = saved.pop((s, m_b))
                    ct_in = ct_stats if s == S - 1 else bwd_edge[s]

                    # the f32 grad accumulation lives INSIDE the cond:
                    # the inactive branch passes the running tree through
                    # untouched, so only the owning stage's device pays a
                    # full-param-tree add per backward unit (M adds per
                    # device per step, not M·S-with-zeros)
                    def bwd_work(s=s, m=m_b, payload_in=payload_in,
                                 ct_in=ct_in, grads=grads):
                        if s == S - 1:
                            def f(p, payload):
                                if stateful:
                                    (y, _sk), _bn = stage_fns[s](
                                        p, model_state, *payload
                                    )
                                else:
                                    y, _sk = stage_fns[s](p, *payload)
                                return stats_fn(y, target(m))
                        else:
                            def f(p, payload):
                                if stateful:
                                    out, _bn = stage_fns[s](
                                        p, model_state, *payload
                                    )
                                    return out
                                return stage_fns[s](p, *payload)

                        _, vjp = jax.vjp(f, params, payload_in)
                        g_params, g_payload = vjp(ct_in)
                        acc = jax.tree.map(
                            lambda a, g: a + g.astype(WGRAD_DTYPE),
                            grads, g_params,
                        )
                        return acc, g_payload

                    zero_in = zero_mb_input if s == 0 else zero_payloads[s - 1]
                    grads, g_payload = jax.lax.cond(
                        stage == s, bwd_work,
                        lambda g=grads, z=zero_in: (g, z),
                    )
                    if s > 0:
                        out_bwd[s - 1] = g_payload
            fwd_edge = [
                _ppermute_edge(out_fwd[e], stage_axis, e)
                if out_fwd[e] is not None else zero_payloads[e]
                for e in range(S - 1)
            ]
            bwd_edge = [
                _ppermute_edge(out_bwd[e], stage_axis, e, reverse=True)
                if out_bwd[e] is not None else zero_payloads[e]
                for e in range(S - 1)
            ]
        grads = _reduce_grads(grads, axes)
        if specs is not None:
            # the model axis carried no reduction (its replicas'
            # accumulators are identical); slice each full leaf down to
            # this device's own shard so the grads leave the shard_map
            # laid out exactly like the params entered
            grads = _slice_to_shard(grads, specs, dict(mesh.shape))
        return loss, grads, new_model_state

    if in_stage is None:
        if stateful:
            return shard_map(
                per_device,
                mesh=mesh,
                in_specs=(P(), P(), batch_in_spec),
                out_specs=(P(), P(), P()),
                check_vma=False,
            )

        sharded = shard_map(
            lambda params, batch: per_device(params, None, batch)[:2],
            mesh=mesh,
            in_specs=(P(), batch_in_spec),
            out_specs=(P(), P()),
            check_vma=False,
        )

        def stateless_vag(params, model_state, batch):
            loss, grads = sharded(params, batch)
            return loss, grads, model_state

        return stateless_vag

    # in-stage sharding: lazily built per global param shapes (the spec
    # tree is both the params in_spec and the grads out_spec)
    cache = {}

    def _built(params):
        key = _shape_key(params)
        fn = cache.get(key)
        if fn is None:
            specs = _param_spec_tree(in_stage, params)
            if stateful:
                fn = shard_map(
                    functools.partial(per_device, specs=specs),
                    mesh=mesh,
                    in_specs=(specs, P(), batch_in_spec),
                    out_specs=(P(), specs, P()),
                    check_vma=False,
                )
            else:
                fn = shard_map(
                    lambda p, b: per_device(p, None, b, specs=specs)[:2],
                    mesh=mesh,
                    in_specs=(specs, batch_in_spec),
                    out_specs=(P(), specs),
                    check_vma=False,
                )
            cache[key] = fn
        return fn

    if stateful:
        def sharded_vag(params, model_state, batch):
            return _built(params)(params, model_state, batch)
    else:
        def sharded_vag(params, model_state, batch):
            loss, grads = _built(params)(params, batch)
            return loss, grads, model_state

    return sharded_vag


def make_pipeline_forward_fn(
    model,
    mesh: Mesh,
    num_microbatches: int = 2,
    stage_axis: str = "stage",
    data_axis: str = "auto",
    cuts: Optional[Sequence[int]] = None,
    mesh_config=None,
) -> Callable:
    """Pipelined inference: ``forward(variables, images) -> preds``.

    ``variables`` is the bare params tree for stateless models, or the
    full ``{'params', 'batch_stats'}`` dict for stateful ones (running
    averages; nothing mutates). Same fill-drain schedule as the loss path
    (literally — `_run_schedule`); predictions are psummed across the
    stage axis so the output is replicated over 'stage' (the reference's
    ``.to('cuda:0')`` gather, unet_model.py:53).
    """
    in_stage = _in_stage_config(mesh, mesh_config)
    data_axis = _resolve_data_axis(mesh, data_axis)
    num_stages = mesh.shape[stage_axis]
    stage_ranges = _stage_ranges(model.num_segments, num_stages, cuts)
    stateful = _is_stateful(model)
    stage_fns = _build_stage_fns(model, stage_ranges, remat=False, train=False)
    M = int(num_microbatches)
    S = num_stages
    batch_spec = P(data_axis) if data_axis else P()

    def per_device(variables, images, specs=None):
        if stateful:
            params = variables["params"]
            bn = variables["batch_stats"]
        else:
            params, bn = variables, None
        if specs is not None:
            params = _gather_params(params, specs)
        # same guard as the train paths: a ragged batch would silently
        # floor to mb=0 (empty predictions) or drop samples here
        mb = _check_microbatching(images.shape[0], M)

        def microbatch_input(m):
            return jax.lax.dynamic_slice_in_dim(images, m * mb, mb, axis=0), ()

        def last_stage_preds(params, bn_in, payload, m):
            if stateful:
                (x, _skips), bn_in = stage_fns[S - 1](params, bn_in, *payload)
                return x, bn_in
            x, _skips = stage_fns[S - 1](params, *payload)
            return x

        out_shape = (mb,) + images.shape[1:3] + (model.n_classes,)
        preds, _ = _run_schedule(
            stage_fns, M, stage_axis, params, microbatch_input,
            last_stage_preds, lambda: jnp.zeros(out_shape, LOSS_DTYPE),
            bn_state=bn,
        )
        out = jnp.concatenate(preds, axis=0)
        return _broadcast_preds(out, stage_axis)

    if in_stage is None:
        return shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P(), batch_spec),
            out_specs=batch_spec,
            check_vma=False,
        )

    # in-stage sharding: params enter per-leaf sharded (batch_stats, for
    # stateful models, stay replicated — the running averages are read
    # whole by every stage)
    cache = {}

    def forward(variables, images):
        params = variables["params"] if stateful else variables
        key = _shape_key(params)
        fn = cache.get(key)
        if fn is None:
            specs = _param_spec_tree(in_stage, params)
            var_spec = {"params": specs, "batch_stats": P()} if stateful else specs
            fn = shard_map(
                functools.partial(per_device, specs=specs),
                mesh=mesh,
                in_specs=(var_spec, batch_spec),
                out_specs=batch_spec,
                check_vma=False,
            )
            cache[key] = fn
        return fn(variables, images)

    return forward
