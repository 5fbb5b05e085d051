"""Differentiable fused loss: Pallas one-pass stats forward, analytic VJP.

Round 3 shipped the fused BCE+dice stats kernel (ops/pallas_kernels.py)
eval-only: differentiating a ``pallas_call`` needs a hand-written VJP, and
the training path stayed XLA. This module supplies that VJP at the right
altitude — the SUFFICIENT-STATISTICS level (ops/losses.py `bce_dice_stats`):

    stats = [bce_sum, count, intersection, output_sum + target_sum]

The cotangent of each stat w.r.t. each output element is closed-form:

    ∂bce_sum/∂o_i       = −(t_i·[o_i ≥ m]/o_i − (1−t_i)·[1−o_i ≥ m]/(1−o_i))
    ∂count/∂o_i         = 0
    ∂intersection/∂o_i  = t_i
    ∂(Σo + Σt)/∂o_i     = 1

with m = losses._LOG_SAFE_MIN reproducing the grad-safe clamp (saturated
pixels contribute exactly zero gradient — the round-3 NaN fix's contract,
ops/losses.py `_clamped_log`). Everything downstream of the stats —
`loss_from_stats`, pipeline psums/accumulation, the scalar scheduler math —
is tiny and stays ordinary XLA, so autodiff composes: the pipeline schedule
(parallel/pipeline.py) and the shard_map wrapper below differentiate
through their psums as before while the O(B·H·W) passes run through the
Pallas kernel forward and one fused elementwise backward.

Numerics: the kernel accumulates in a different order than XLA's reduction
tree, so values agree to ~1e-5 relative, not bitwise (same caveat as the
eval kernel); the BACKWARD is elementwise and matches `jax.grad` of the
XLA loss to float tolerance (tests/test_pallas.py).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distributedpytorch_tpu.ops.losses import (
    _LOG_SAFE_MIN,
    loss_from_stats,
)
# The analytic backward spells the LOSS_DTYPE contract (ops/precision.py)
# — the dptlint ``dtype-policy`` rule reaches custom-VJP bodies via
# ``defvjp``, and the named constant is its sanctioned spelling (this
# module is no longer exempt).
from distributedpytorch_tpu.ops.precision import LOSS_DTYPE
from distributedpytorch_tpu.ops.pallas_kernels import bce_dice_stats_pallas


@jax.custom_vjp
def bce_dice_stats_fused(outputs: jax.Array, targets: jax.Array) -> jax.Array:
    """`bce_dice_stats` contract (4 stats) via the Pallas kernel, with an
    analytic VJP so it sits on the TRAINING path."""
    return bce_dice_stats_pallas(outputs, targets)


def _stats_fwd(outputs, targets):
    return bce_dice_stats_pallas(outputs, targets), (outputs, targets)


def _stats_bwd(res, ct):
    outputs, targets = res
    o = outputs.astype(LOSS_DTYPE)
    tb = (targets == 1).astype(LOSS_DTYPE)
    m = _LOG_SAFE_MIN
    # zero (not inf·0=NaN) gradient on saturated pixels — the where-on-
    # both-sides pattern from losses._clamped_log, in derivative form
    inv_o = jnp.where(o >= m, 1.0 / jnp.maximum(o, m), 0.0)
    inv_1mo = jnp.where(1.0 - o >= m, 1.0 / jnp.maximum(1.0 - o, m), 0.0)
    dbce = -(tb * inv_o - (1.0 - tb) * inv_1mo)
    grad = ct[0] * dbce + ct[2] * tb + ct[3]
    return grad.astype(outputs.dtype), jnp.zeros_like(targets)


bce_dice_stats_fused.defvjp(_stats_fwd, _stats_bwd)


def fused_bce_dice_loss(outputs: jax.Array, targets: jax.Array) -> jax.Array:
    """Training-path BCE − log-dice through the fused kernel: unsharded
    (single-device / fully replicated) arrays only — mesh strategies use
    :func:`make_sharded_fused_loss`."""
    return loss_from_stats(bce_dice_stats_fused(outputs, targets))


def make_sharded_fused_loss(mesh: Mesh, spec: P, axes: Sequence[str]):
    """``loss(outputs, targets) -> scalar`` running the fused kernel
    per-shard under ``shard_map`` and psumming the 4 stats over ``axes``
    (the mesh axes `spec` shards the batch/image over).

    This is what lets mesh strategies stop gating Pallas off: pallas_call
    has no GSPMD partitioning rule, but inside shard_map every array is
    process-local and the kernel sees plain (local) shapes. The stats are
    additive over ANY slicing (losses.bce_dice_stats docstring), so the
    psum'd result — and therefore the loss AND its gradient through the
    custom VJP — equals the unsharded computation.
    """
    axes = tuple(axes)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=P(),
        check_vma=False,
    )
    def loss(outputs, targets):
        stats = bce_dice_stats_fused(outputs, targets)
        if axes:
            stats = jax.lax.psum(stats, axes)
        return loss_from_stats(stats)

    return loss


def spec_axes(spec: P) -> Tuple[str, ...]:
    """Mesh axis names a PartitionSpec shards over (entries may be axis
    names or tuples of them)."""
    axes = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.extend(entry)
        else:
            axes.append(entry)
    return tuple(axes)
