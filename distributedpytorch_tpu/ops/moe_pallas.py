"""The held experts' weight gradients as one grouped product over the
sorted rows, a Pallas TPU kernel: ``out[e] += lhs[rows of e].T @
rhs[rows of e]``, where an expert's ``(K, N)`` float32 result stays in
VMEM over that expert's consecutive tiles and goes to HBM once.

``ops/moe.py``'s backward loop writes the products' operands side by side
in the tiles' order (every tile of one expert, padding rows zero on both
sides, so nothing is masked here) and calls this kernel once a weight
matrix and chunk of tiles. The grid is (blocks of K, blocks of N, tiles),
the tiles innermost: the output block of tile ``t``'s expert is the
accumulator, it changes when the expert does, and the pipeline writes it
back then. A run of tiles starts from zeros, or, where its expert was met
by an earlier chunk (at most the first expert of a chunk), from what the
aliased result already holds of it, which comes in as one more block,
read once a call; an expert with no tile keeps what the aliased input
held. Tiles past the number in use are
skipped: their index maps stay on the last tile in use, so nothing moves,
and the time follows the routing as the tile loop's does. Operands in the
compute dtype, sums in float32.

``ops/moe.wgrad_path`` decides where this kernel runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedpytorch_tpu.ops.precision import WGRAD_DTYPE
from distributedpytorch_tpu.utils.backend import pallas_interpret

#: Lanes of a row of VMEM, rows of a float32 sublane tile.
_LANES = 128
_SUBLANES = 8
#: What the kernel may use of the chip's 128 MiB of VMEM, and the part of
#: it that the result's float32 block may take three times over: the
#: accumulator, double-buffered by the pipeline, and what the result held
#: of the first tile's expert (the rest: the operands' tiles and one piece
#: of the product).
_VMEM_LIMIT = 100 * 1024 * 1024
_RESIDENT_LIMIT = 72 * 1024 * 1024
#: Rows of the result that one product of the kernel's body makes: a
#: piece's float32 value is ``_PIECE`` x N, not the whole block (on the
#: chip 128, 256, 512 and the whole block run alike: 0.85 ms for 64 tiles
#: at 2048 x 1536, PERF.md §6, PR 36).
_PIECE = 256

#: ``start`` of a tile: go on with the resident block, begin a run from
#: zeros, begin it from what the aliased result holds.
CONTINUE, FROM_ZERO, FROM_RESULT = 0, 1, 2


def hidden_block(hidden: int, width: int) -> int:
    """Columns of the hidden dimension to a result block: the largest
    multiple of the lanes that divides ``hidden`` and whose float32 block
    against the whole expert ``width``, three times over, fits the
    resident share of VMEM; 0 where none does or ``hidden`` is no
    multiple of the lanes."""
    if hidden % _LANES:
        return 0
    lanes = -(-width // _LANES) * _LANES
    for blocks in range(1, hidden // _LANES + 1):
        block = hidden // blocks
        if hidden % blocks == 0 and block % _LANES == 0 and \
                3 * 4 * block * lanes <= _RESIDENT_LIMIT:
            return block
    return 0


def shapes_ok(hidden: int, width: int, tile: int) -> bool:
    """Shapes the kernel takes: a hidden size that the lanes divide and
    that ``hidden_block`` can cut, the expert width whole (any multiple of
    the sublanes), tiles of whole sublane tiles of the compute dtype."""
    return (hidden_block(hidden, width) > 0 and width % _SUBLANES == 0
            and tile % (2 * _SUBLANES) == 0)


def rows_last(width: int) -> bool:
    """Whether an operand as wide as the experts is handed over turned,
    ``(width, R)``: where the lanes do not divide ``width`` (1856). XLA
    lays such a tile out rows last (the dimension the lanes divide), and
    a buffer of tiles with it; asked for ``(R, width)`` it would turn the
    whole buffer round in front of every call."""
    return width % _LANES != 0


def _kernel(expert_ref, start_ref, used_ref, lhs_ref, rhs_ref, held_ref,
            out_ref, *, turned):
    t = pl.program_id(2)
    start = start_ref[t]

    @pl.when(start == FROM_ZERO)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(start == FROM_RESULT)
    def _():
        out_ref[...] = held_ref[...]

    @pl.when(t < used_ref[0])
    def _():
        # contract over the tile's rows, wherever ``lhs`` has them
        over = (((1 if turned else 0,), (0,)), ((), ()))
        rhs = rhs_ref[...]
        block_k = out_ref.shape[1]
        for first in range(0, block_k, _PIECE):
            rows = slice(first, min(first + _PIECE, block_k))
            lhs = lhs_ref[rows, :] if turned else lhs_ref[:, rows]
            out_ref[0, rows, :] += lax.dot_general(
                lhs, rhs, over, preferred_element_type=WGRAD_DTYPE)


def tile_schedule(tile_expert, n_used, continues):
    """The kernel's three scalar arguments for one chunk: ``tile_expert``
    (tiles,) is each tile's expert, of which the first ``n_used`` count;
    ``continues`` says that the first tile's expert was met by an earlier
    chunk. Returns ``(expert, start, used)``: tiles past the last in use
    repeat its expert and start nothing; with no tile in use the first
    starts from the result, which the write-back then leaves as it was."""
    tiles = tile_expert.shape[0]
    t = jnp.arange(tiles, dtype=jnp.int32)
    last = jnp.maximum(n_used - 1, 0)
    expert = tile_expert[jnp.minimum(t, last)].astype(jnp.int32)
    begins = jnp.concatenate([jnp.ones((1,), bool), expert[1:] != expert[:-1]])
    start = jnp.where(begins & (t < n_used), FROM_ZERO, CONTINUE)
    start = start.at[0].set(jnp.where(continues | (n_used == 0),
                                      FROM_RESULT, FROM_ZERO))
    return expert, start.astype(jnp.int32), jnp.reshape(n_used, (1,)).astype(jnp.int32)


def grouped_wgrad(lhs, rhs, acc, schedule, tile: int, hidden_is_k: bool,
                  turned: bool = False, interpret=None):
    """``acc`` (E, K, N) float32 with ``lhs[rows].T @ rhs[rows]`` added
    to each expert's slab over its tiles of this chunk: ``lhs`` (R, K) and
    ``rhs`` (R, N) in the compute dtype, R a multiple of ``tile``,
    ``schedule`` from ``tile_schedule``. The hidden dimension (K where
    ``hidden_is_k``, else N) is cut by ``hidden_block``; the other, the
    experts' width, is taken whole. ``turned`` (``rows_last``; with the
    experts' width as K alone): ``lhs`` is ``(K, R)``. ``acc`` is given up:
    the result takes its place.
    ``interpret=None`` follows ``utils/backend.pallas_interpret``."""
    if turned and hidden_is_k:
        raise ValueError("only an operand as wide as the experts is turned")
    return _grouped_wgrad(
        lhs, rhs, acc, schedule, tile, hidden_is_k, turned,
        pallas_interpret() if interpret is None else interpret)


# jitted: the layers of a model call it at a few shapes, many times; each
# shape is then traced and lowered once, not once a call
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _grouped_wgrad(lhs, rhs, acc, schedule, tile, hidden_is_k, turned, interpret):
    _, k, n = acc.shape
    block_k = hidden_block(k, n) if hidden_is_k else k
    block_n = n if hidden_is_k else hidden_block(n, k)

    def operand(block, axis, is_turned=False):
        """Tile ``t``'s rows of an operand and block ``axis`` of the grid
        of its columns; past the last tile in use, that tile again."""
        def at(*grid_and_scalars):
            t, used = grid_and_scalars[2], grid_and_scalars[-1]
            place = (jnp.minimum(t, jnp.maximum(used[0] - 1, 0)),
                     grid_and_scalars[axis])
            return place[::-1] if is_turned else place

        return pl.BlockSpec((block, tile) if is_turned else (tile, block), at)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(k // block_k, n // block_n, rhs.shape[0] // tile),
        in_specs=[
            operand(block_k, 0, turned), operand(block_n, 1),
            # what the result holds of the first tile's expert: the same
            # block for every tile, so it is read once, into one buffer
            pl.BlockSpec((1, block_k, block_n),
                         lambda kb, nb, t, expert, start, used: (expert[0], kb, nb),
                         pipeline_mode=pl.Buffered(1)),
        ],
        out_specs=pl.BlockSpec((1, block_k, block_n),
                               lambda kb, nb, t, expert, start, used:
                               (expert[t], kb, nb)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, turned=turned),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        # operand 5 (after the three scalar arguments, lhs and rhs) is acc
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_wgrad",
    )(*schedule, lhs, rhs, acc)
