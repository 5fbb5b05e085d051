"""The single train/eval step pair every strategy jits.

Semantics parity with the reference hot loop (reference
utils/train_utils.py:59-70):

  * forward → BCE − log-dice on the sigmoid probabilities;
  * the backward runs on ``batch_size × loss`` while the RECORDED loss is the
    unscaled value (train_utils.py:67-69) — reference quirk 1, reproduced
    behind ``TrainConfig.faithful_loss_scaling`` (near-no-op under Adam, see
    SURVEY.md §2);
  * masks arrive as integer (B, H, W); the ``unsqueeze(1)`` channel fix-up
    (train_utils.py:61) becomes a trailing-axis expand — applied in EVERY
    strategy, which deliberately fixes the reference's DP crash (quirk 4);
  * Adam update with the lr read from optimizer state (ops/optim.py), so the
    host-side plateau scheduler never recompiles the step.

TPU notes: precision is governed by the session's PrecisionPolicy
(ops/precision.py, ``--dtype``): under ``f32``/``bf16`` the grad is taken
w.r.t. float32 params directly (XLA inserts the compute-dtype casts once at
trace time); under ``bf16_params`` the on-device params are bf16 and the
policy's master-weight optimizer wrapper runs Adam against an f32 master in
optimizer state, with grads stated f32 at the optimizer boundary
(``policy.cast_grads`` — the wgrad contract). The loss is f32 under every
policy (ops/losses.py pins it). Inputs are NHWC.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax

from distributedpytorch_tpu.ops import precision as precision_ops
from distributedpytorch_tpu.ops.losses import bce_dice_loss, dice_coefficient
from distributedpytorch_tpu.ops.optim import adam_l2
from distributedpytorch_tpu.ops.precision import PrecisionPolicy


@flax.struct.dataclass
class TrainState:
    """Pure-pytree training state (params + Adam state + step counter).

    ``model_state`` carries non-trainable model collections (BatchNorm
    running statistics for stateful models like models/milesial.py); None
    for pure-params models — the default keeps every existing caller and
    checkpoint shape unchanged."""

    params: Any
    opt_state: Any
    step: jax.Array
    model_state: Any = None


def create_train_state(
    params,
    learning_rate: float,
    weight_decay: float = 1e-8,
    model_state=None,
    policy: Optional[PrecisionPolicy] = None,
    adam_b2: float = 0.999,
) -> Tuple[TrainState, optax.GradientTransformation]:
    """Build the TrainState + optimizer under a precision policy.

    ``policy=None`` keeps the historical behavior (params as given, plain
    Adam) — exactly the ``f32``/``bf16`` policies. Under ``bf16_params``
    the params are cast-in to their bf16 on-device storage dtype and the
    optimizer is wrapped with f32 master weights (the master is seeded
    from the params BEFORE the down-cast, so fresh-init and restored f32
    weights lose nothing to the storage dtype)."""
    tx = adam_l2(learning_rate, weight_decay, b2=adam_b2)
    if policy is not None:
        tx = policy.wrap_optimizer(tx)
        # init the (wrapped) optimizer on the FULL-precision params: the
        # master-weight wrapper promotes its copy from what it is given
        opt_state = tx.init(params)
        params = policy.cast_params(params)
    else:
        opt_state = tx.init(params)
    return (
        TrainState(
            params=params,
            opt_state=opt_state,
            step=jnp.zeros((), jnp.int32),
            model_state=model_state,
        ),
        tx,
    )


def _prep_mask(mask: jax.Array) -> jax.Array:
    """(B, H, W) integer mask → (B, H, W, 1) float32 target (the reference's
    `.unsqueeze(1)` + `.to(float32)`, train_utils.py:61 — channel-last here)."""
    return mask[..., None].astype(jnp.float32)


# Names in the compiled step's ``op_name`` metadata (flax already runs
# every module under ``jax.named_scope``, so the encoder and decoder
# levels are named; these two scopes name what no module owns). Metadata
# only: the compiled program and its numbers do not change.
LOSS_SCOPE = "loss"
OPTIMIZER_SCOPE = "optimizer"


def image_loss(model, params, model_state, batch: Dict[str, jax.Array],
               loss_impl: Callable = None):
    """The image models' training loss, as every model-table entry's
    ``loss`` is called (models/__init__.py): ``(loss, model state after
    the forward pass, counters or None)``. BCE − log-dice of the sigmoid
    probabilities against ``batch['mask']``; ``loss_impl(preds, target)``
    swaps the loss computation (the strategy's hook for the fused Pallas
    kernel, ops/fused_loss.py). A stateful model (BatchNorm) is applied
    with ``mutable=['batch_stats']`` and hands back the updated
    statistics: under a sharded batch the statistics XLA computes are
    global-batch statistics — SyncBN semantics for free
    (models/milesial.py notes)."""
    impl = loss_impl or bce_dice_loss
    if is_stateful_model(model):
        preds, updates = model.apply(
            {"params": params, "batch_stats": model_state},
            batch["image"],
            train=True,
            mutable=["batch_stats"],
        )
        model_state = updates["batch_stats"]
    else:
        preds = model.apply({"params": params}, batch["image"])
    with jax.named_scope(LOSS_SCOPE):
        return impl(preds, _prep_mask(batch["mask"])), model_state, None


def pack_readout(loss, counters):
    """One step's loss and the counters its model counts, as ONE float32
    array ``[loss, *counters]``: the counters ride back to the host with
    the loss, through the readback the loss already has
    (utils/metrics.StepReadout unpacks it there)."""
    return jnp.concatenate([
        jnp.reshape(loss, (1,)).astype(precision_ops.LOSS_DTYPE),
        jnp.ravel(counters).astype(precision_ops.LOSS_DTYPE)])


class Counted(NamedTuple):
    """What a model's loss hands back beside the loss and the model state
    (the third output of a model-table entry's ``loss``; None where it has
    neither). ``counters`` ride back to the host with the loss
    (``pack_readout``). ``buffers`` is a part of the parameter tree (the
    same nesting, fewer leaves) that the model sets itself after each
    step: the optimiser's result for those leaves is dropped (the routers'
    selection biases, models/twotower.py)."""

    counters: Any = None
    buffers: Any = None


def set_buffers(params, buffers):
    """``params`` with the leaves of ``buffers`` in place of its own."""
    if not isinstance(buffers, dict):
        return buffers
    return {k: set_buffers(v, buffers[k]) if k in buffers else v
            for k, v in params.items()}


def apply_optimizer(tx, grads, opt_state, params):
    """``tx.update`` + ``apply_updates`` under the optimizer's scope:
    ``(params, opt_state)`` after the update."""
    with jax.named_scope(OPTIMIZER_SCOPE):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state


def is_stateful_model(model) -> bool:
    """Models that carry non-trainable collections (BatchNorm running
    stats) declare ``is_stateful = True`` (models/milesial.py). The one
    definition both the plain steps here and the pipeline schedules
    (parallel/pipeline.py — stateful stage functions) key off."""
    return bool(getattr(model, "is_stateful", False))


_is_stateful = is_stateful_model  # historical internal alias


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    batch_size: int,
    faithful_loss_scaling: bool = True,
    remat: bool = False,
    loss_impl: Callable = None,
    policy: Optional[PrecisionPolicy] = None,
    loss_fn: Callable = image_loss,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, jax.Array]]:
    """Build the (unjitted) train step; the strategy decides how to jit/shard
    it. Returns ``step(state, batch) -> (state, unscaled_loss)``.

    ``loss_fn`` is the model-table entry's loss (models/__init__.py):
    ``(model, params, model_state, batch, loss_impl) -> (loss, model_state,
    Counted or None)``. Where it counts something, the step's second
    output is ``pack_readout(loss, counters)``, not the bare loss; where
    it sets leaves of the parameter tree itself, they replace what the
    optimiser made of them.

    `remat=True` rematerializes the forward during the backward
    (jax.checkpoint): activations are recomputed instead of stored, cutting
    peak HBM roughly in half for ~1/3 more FLOPs — the TPU-native answer to
    the reference's 7.8 GB-at-batch-4 VRAM wall (modelsummary.txt:72).

    `loss_impl` swaps the loss computation (default: the XLA
    `bce_dice_loss`); strategies pass the fused Pallas loss under
    ``--pallas`` (Strategy._train_loss_impl).

    `policy` is the session's precision policy: under a master-weight
    policy the backward's grads come out in the bf16 param dtype and are
    stated f32 HERE — before the faithful-quirk scaling, so the scale
    multiply never rounds in bf16 (the wgrad contract's step-entry end;
    the optimizer-boundary end lives in the master-weight wrapper).
    """

    grad_scale = float(batch_size) if faithful_loss_scaling else 1.0
    def raw_fwd(model, params, model_state, batch):
        loss, model_state, counted = loss_fn(
            model, params, model_state, batch, loss_impl)
        return loss, (model_state, counted or Counted())

    fwd = jax.checkpoint(raw_fwd, static_argnums=(0,)) if remat else raw_fwd

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        # one update body for every model: a pure model carries its (None)
        # model_state through as aux
        (loss, (model_state, counted)), grads = jax.value_and_grad(
            lambda p: fwd(model, p, state.model_state, batch), has_aux=True
        )(state.params)
        if policy is not None:
            grads = policy.cast_grads(grads)
        if grad_scale != 1.0:
            # (batch_size * loss).backward() parity, reference train_utils.py:69
            grads = jax.tree.map(lambda g: g * grad_scale, grads)
        params, opt_state = apply_optimizer(
            tx, grads, state.opt_state, state.params)
        if counted.buffers is not None:
            params = set_buffers(params, counted.buffers)
        return (
            TrainState(
                params=params,
                opt_state=opt_state,
                step=state.step + 1,
                model_state=model_state,
            ),
            loss if counted.counters is None
            else pack_readout(loss, counted.counters),
        )

    return train_step


def make_accum_train_step(
    model,
    tx: optax.GradientTransformation,
    batch_size: int,
    chunks: int,
    faithful_loss_scaling: bool = True,
    remat: bool = False,
    use_pallas: bool = False,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, jax.Array]]:
    """Gradient accumulation: ONE optimizer step over a (K·b) effective
    batch, holding only one b-sized chunk's activations at a time.

    A capability the reference lacks entirely. The subtlety is that this
    framework's loss is NOT chunk-additive: the log-dice term is a ratio
    of whole-batch sums (reference utils/utils.py:18-23), so summing
    per-chunk loss gradients — what naive accumulation does — computes
    the gradient of a DIFFERENT objective (mean of per-chunk losses).
    Exactness comes from the sufficient-statistics decomposition
    (ops/losses.bce_dice_stats):

        pass 1 (scan): accumulate the 4 stats over chunks — forward only;
        combine:       loss = f(Σstats); cotangent c = ∇f(Σstats), a
                       4-vector known only after ALL chunks are seen;
        pass 2 (scan): per-chunk VJP of stats w.r.t. params against c,
                       summed — each chunk's backward runs with the
                       GLOBAL cotangent.

    Cost: one extra forward (~+33% FLOPs over an unachievable one-pass),
    the standard price of exact accumulation under a non-additive loss.
    `batch` is the K-stacked ``{'image': (K,b,H,W,3), 'mask': (K,b,H,W)}``
    (place with `strategy.place_stacked_batch`). Stateful models
    (BatchNorm) are rejected — per-chunk statistics have no single
    faithful semantics; use a data-parallel mesh for large batches there.

    Precision: the stats accumulator is LOSS_DTYPE and the pass-2 grad
    accumulator is WGRAD_DTYPE (ops/precision.py) under EVERY policy —
    under ``bf16_params`` each chunk's VJP emits bf16 leaves and summing
    K of them in bf16 would violate the stated f32 wgrad-accumulation
    contract the pipeline schedules already honor.
    """
    if _is_stateful(model):
        raise ValueError(
            "gradient accumulation supports stateless models only "
            "(BatchNorm statistics are not chunk-decomposable); use a "
            "data-parallel strategy for large effective batches"
        )
    # the faithful quirk scales by the loader's -b value; the equivalent
    # single-big-batch run would pass -b = K·b, so the EFFECTIVE batch is
    # the faithful scale here (matters only through Adam's eps floor and
    # the L2 term — Adam is otherwise scale-invariant)
    grad_scale = float(batch_size * chunks) if faithful_loss_scaling else 1.0
    if use_pallas:
        from distributedpytorch_tpu.ops.fused_loss import bce_dice_stats_fused

        stats_fn = bce_dice_stats_fused
    else:
        from distributedpytorch_tpu.ops.losses import bce_dice_stats

        stats_fn = bce_dice_stats
    from distributedpytorch_tpu.ops.losses import loss_from_stats

    def chunk_stats(params, chunk):
        preds = model.apply({"params": params}, chunk["image"])
        with jax.named_scope(LOSS_SCOPE):
            return stats_fn(preds, _prep_mask(chunk["mask"]))

    fwd = jax.checkpoint(chunk_stats) if remat else chunk_stats

    def accum_step(state: TrainState, stacked: Dict[str, jax.Array]):
        k = stacked["image"].shape[0]
        if k != chunks:
            raise ValueError(
                f"stacked batch carries {k} chunks but this step was built "
                f"for grad_accum={chunks}"
            )
        params = state.params

        def pass1(carry, chunk):
            return carry + fwd(params, chunk), None

        stats, _ = jax.lax.scan(
            pass1, jnp.zeros((4,), precision_ops.LOSS_DTYPE), stacked
        )
        with jax.named_scope(LOSS_SCOPE):
            loss, ct = jax.value_and_grad(loss_from_stats)(stats)

        def pass2(carry, chunk):
            _, vjp = jax.vjp(lambda p: fwd(p, chunk), params)
            (g,) = vjp(ct)
            return (
                jax.tree.map(
                    lambda a, x: a + x.astype(precision_ops.WGRAD_DTYPE),
                    carry, g,
                ),
                None,
            )

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, precision_ops.WGRAD_DTYPE), params
        )
        grads, _ = jax.lax.scan(pass2, zeros, stacked)
        if grad_scale != 1.0:
            grads = jax.tree.map(lambda g: g * grad_scale, grads)
        new_params, opt_state = apply_optimizer(
            tx, grads, state.opt_state, params)
        return (
            TrainState(
                params=new_params,
                opt_state=opt_state,
                step=state.step + 1,
                model_state=state.model_state,
            ),
            loss,
        )

    return accum_step


def make_multi_train_step(
    step: Callable,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, jax.Array]]:
    """Scan `step` over a leading steps axis in ONE XLA executable.

    ``batches`` is the per-step batch stacked to ``{'image': (K,B,H,W,3),
    'mask': (K,B,H,W)}``; returns ``(state, losses (K,))``. Semantically
    identical to K separate `step` calls on the same data, but the runtime
    dispatches once per K steps instead of once per step (what that saves
    on a chip the process holds itself: not measured).
    """

    def multi_step(state: TrainState, batches: Dict[str, jax.Array]):
        return jax.lax.scan(step, state, batches)

    return multi_step


def grouped_eval_metrics(
    preds: jax.Array, target: jax.Array, groups: int
) -> Dict[str, jax.Array]:
    """Per-group {loss (G,), dice (G,)} of a (G·b, ...) prediction stack.

    Group g's metrics are EXACTLY what `bce_dice_loss`/`dice_coefficient`
    return on that b-sized batch alone — same reduction shapes, same
    order — so G reference-semantics val batches evaluate in ONE dispatch.
    Under a batch sharded over a 'data' mesh axis the leading reshape is a
    split along the sharded axis: each shard computes its own group's
    metrics with no cross-device traffic until the tiny (G,) outputs.
    This is how multi-process eval divides the val set: process p feeds
    its own batch as shard p and every process
    reads back the same per-batch values.
    """
    p = preds.reshape((groups, -1) + preds.shape[1:])
    t = target.reshape((groups, -1) + target.shape[1:])
    losses, dices = jax.vmap(
        lambda pp, tt: (bce_dice_loss(pp, tt), dice_coefficient(pp, tt))
    )(p, t)
    return {"loss": losses, "dice": dices}


def make_eval_step(
    model, use_pallas: bool = False, groups: int = 1
) -> Callable[[Any, Dict[str, jax.Array]], Dict[str, jax.Array]]:
    """Eval step: per-batch mean loss (reference evaluate.py:16-19) plus the
    hard-Dice metric the reference never computes (SURVEY.md §2 quirk 6).

    `use_pallas` computes loss AND hard-Dice from the fused one-pass
    Pallas stats kernel (ops/pallas_kernels.py) — same formulas, equal to
    the XLA path within summation-order tolerance (~1e-5 relative).
    Eval-only: the train loss stays XLA so autodiff needs no hand-written
    VJP.

    `groups > 1` evaluates a (G·b)-sized stack of G independent val
    batches at once and returns vector metrics (see
    `grouped_eval_metrics`); the Pallas kernel is scalar-only and is
    ignored in that mode.
    """

    stateful = _is_stateful(model)

    def eval_step(params, batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        if stateful:
            # `params` is the full variables dict ({'params', 'batch_stats'})
            # the trainer's _eval_variables() builds; running averages only
            preds = model.apply(params, batch["image"], train=False)
        else:
            preds = model.apply({"params": params}, batch["image"])
        target = _prep_mask(batch["mask"])
        if groups > 1:
            return grouped_eval_metrics(preds, target, groups)
        if use_pallas:
            from distributedpytorch_tpu.ops.pallas_kernels import (
                eval_metrics_pallas,
            )

            return eval_metrics_pallas(preds, target)
        return {
            "loss": bce_dice_loss(preds, target),
            "dice": dice_coefficient(preds, target),
        }

    return eval_step
