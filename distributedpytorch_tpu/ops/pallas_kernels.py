"""Pallas TPU kernels for the framework's hot elementwise+reduction ops.

The compute path of this framework is XLA-compiled convolutions (XLA's conv
lowering owns the MXU; hand-writing convs would fight the compiler, see
SURVEY.md §7 hard-part 4). What Pallas is the right tool for here is the
fused tail op: the BCE + soft-dice sufficient statistics over the full
(B, H, W, 1) probability map — four reductions plus elementwise logs that
XLA schedules as separate fusions. `bce_dice_stats_pallas` computes all
four in ONE pass over the data: each (block, 128-lane) tile is read from
VMEM once, the clamped-log BCE term and the dice partial sums are computed
in registers, and four scalar accumulators in SMEM carry the running sums
across the sequential grid (the standard Pallas reduction pattern:
initialize at program 0, accumulate each step).

Numerics follow ops/losses.py exactly in formula (same clamp at -100, same
`== 1` binarization — reference utils/utils.py:14-25) but NOT bit-for-bit:
multi-block accumulation sums in a different order than XLA's reduction
tree, so results agree to ~1e-5 relative (the equivalence tests' tolerance),
not exactly. The tests run the kernel in interpret mode on CPU and real
mode on TPU.

Used on the no-grad paths (evaluation; anywhere stats are consumed without
autodiff). The training loss keeps the XLA path: differentiating a Pallas
kernel needs a hand-written VJP, and grad-parity risk there buys nothing
while the step is conv-dominated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The stats accumulators spell the LOSS_DTYPE contract (ops/precision.py):
# loss/Dice statistics accumulate f32 under every --dtype policy — the
# dptlint ``dtype-policy`` rule reaches kernel bodies, and these named
# constants are its sanctioned spelling (this module is no longer exempt).
from distributedpytorch_tpu.ops.precision import LOSS_DTYPE
from distributedpytorch_tpu.utils.backend import pallas_interpret

_LOG_CLAMP = -100.0  # torch BCELoss log clamp (ops/losses.py)

LANES = 128  # TPU vector lane width
BLOCK_ROWS = 512  # (512, 128) f32 block = 256 KB per input — fits VMEM


def _stats_kernel(p_ref, t_ref, out_ref):
    """One grid step: partial BCE + soft-dice + hard-dice sums of a
    (BLOCK_ROWS, LANES) tile, accumulated into 6 SMEM scalars laid out as
    out_ref[0, 0:6] (slot 1 is patched with the element count outside)."""
    p = p_ref[:].astype(LOSS_DTYPE)
    t = t_ref[:].astype(LOSS_DTYPE)
    tb = (t == 1.0).astype(LOSS_DTYPE)  # reference utils.py:16 binarize
    pb = (p >= 0.5).astype(LOSS_DTYPE)  # hard-dice threshold (losses.py)
    log_p = jnp.maximum(jnp.log(p), _LOG_CLAMP)
    log_1p = jnp.maximum(jnp.log(1.0 - p), _LOG_CLAMP)
    per_elem = -(tb * log_p + (1.0 - tb) * log_1p)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for j in range(6):
            out_ref[0, j] = 0.0

    out_ref[0, 0] += jnp.sum(per_elem)  # bce numerator
    out_ref[0, 2] += jnp.sum(p * tb)  # soft-dice intersection
    out_ref[0, 3] += jnp.sum(p) + jnp.sum(tb)  # soft-dice union
    out_ref[0, 4] += jnp.sum(pb * tb)  # hard-dice intersection
    out_ref[0, 5] += jnp.sum(pb) + jnp.sum(tb)  # hard-dice union


def _stats_call(p2, t2, n, num_blocks, interpret):
    # no jit here: n/num_blocks/grid must stay static, and callers (the
    # jitted eval step; tests) already run this under their own trace
    if interpret:  # the interpreter has no TPU memory spaces
        in_space = out_space = None
    else:
        in_space, out_space = pltpu.VMEM, pltpu.SMEM

    def spec(block, index_map, space):
        if space is None:
            return pl.BlockSpec(block, index_map)
        return pl.BlockSpec(block, index_map, memory_space=space)

    stats = pl.pallas_call(
        _stats_kernel,
        grid=(num_blocks,),
        in_specs=[
            spec((BLOCK_ROWS, LANES), lambda i: (i, 0), in_space),
            spec((BLOCK_ROWS, LANES), lambda i: (i, 0), in_space),
        ],
        out_specs=spec((1, 6), lambda i: (0, 0), out_space),
        out_shape=jax.ShapeDtypeStruct((1, 6), LOSS_DTYPE),
        interpret=interpret,
    )(p2, t2)
    return jnp.stack(
        [
            stats[0, 0],
            jnp.asarray(n, LOSS_DTYPE),
            stats[0, 2],
            stats[0, 3],
            stats[0, 4],
            stats[0, 5],
        ]
    )


def eval_stats_pallas(
    outputs: jax.Array, targets: jax.Array, interpret=None
) -> jax.Array:
    """Fused one-pass `[bce_sum, count, soft_inter, soft_union, hard_inter,
    hard_union]`: the first four are ops/losses.py `bce_dice_stats`, the
    last two are the hard-Dice metric's sums — everything the eval step
    needs from ONE VMEM read per element.

    Padding invariant: tiles are padded with (p=0, t=0), which contributes
    exactly zero to every accumulator — per_elem = -log(1-0) = 0, p·tb = 0,
    p + tb = 0 — so no masking is needed in the kernel; the true element
    count is patched in outside.

    `interpret=None` follows utils/backend.pallas_interpret: Mosaic on a
    TPU, the interpreter on an operator-named CPU, an error otherwise.
    The inputs must be unsharded (single device or replicated): pallas_call
    has no GSPMD partitioning rule, so callers on sharded meshes must not
    route sharded arrays here (see make_eval_step's gating).
    """
    if interpret is None:
        interpret = pallas_interpret()
    p = outputs.astype(LOSS_DTYPE).reshape(-1)
    t = targets.astype(LOSS_DTYPE).reshape(-1)
    n = p.size
    per_block = BLOCK_ROWS * LANES
    num_blocks = max(1, -(-n // per_block))
    pad = num_blocks * per_block - n
    p = jnp.pad(p, (0, pad)).reshape(num_blocks * BLOCK_ROWS, LANES)
    t = jnp.pad(t, (0, pad)).reshape(num_blocks * BLOCK_ROWS, LANES)
    return _stats_call(p, t, n, num_blocks, interpret)


def bce_dice_stats_pallas(
    outputs: jax.Array, targets: jax.Array, interpret=None
) -> jax.Array:
    """ops/losses.py `bce_dice_stats` contract (4 stats) via the kernel."""
    return eval_stats_pallas(outputs, targets, interpret=interpret)[:4]


def bce_dice_loss_pallas(
    outputs: jax.Array, targets: jax.Array, interpret=None
) -> jax.Array:
    """Scalar BCE − log-dice via the fused kernel (no-grad paths only)."""
    from distributedpytorch_tpu.ops.losses import loss_from_stats

    return loss_from_stats(bce_dice_stats_pallas(outputs, targets, interpret=interpret))


def eval_metrics_pallas(
    outputs: jax.Array, targets: jax.Array, interpret=None, dice_eps: float = 1e-7
) -> dict:
    """{'loss', 'dice'} for the eval step from one fused pass — BCE −
    log-dice (losses.py `bce_dice_loss`) and hard Dice (losses.py
    `dice_coefficient`, threshold 0.5, same eps)."""
    from distributedpytorch_tpu.ops.losses import loss_from_stats

    stats = eval_stats_pallas(outputs, targets, interpret=interpret)
    dice = (2.0 * stats[4] + dice_eps) / (stats[5] + dice_eps)
    return {"loss": loss_from_stats(stats[:4]), "dice": dice}
