"""How much a token model's step may keep across its blocks' backward
passes: the arithmetic that ``models/twotower.py`` and ``models/lfm2.py``
share. Every block of such a model is a ``jax.checkpoint`` that keeps its
input; where the device's memory allows, a block keeps besides the
results it names (``jax.ad_checkpoint.checkpoint_name``) that cost a
large matrix product or a kernel to make again. Who keeps is decided from
the shapes and the figure the device reports, never by a flag.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax import lax

#: Bytes a parameter that are the step's arguments (the parameter and
#: Adam's two moments, float32 each) and that are its gradient, a
#: temporary of the step.
ARGUMENT_BYTES_PER_PARAMETER = 12
GRADIENT_BYTES_PER_PARAMETER = 4
#: The part of the memory beside the arguments that the step's
#: temporaries may fill: the runtime wants a tenth beyond them for its
#: region, and the allocator holds batches in flight and read-outs beside
#: the state (0.45 GB in the benchmark's cell).
TEMPORARIES_SHARE = 1 / 1.2


def kept_budget(parameters: int, working_bytes: int, memory_bytes) -> int:
    """Bytes of activations a step may keep on a device of
    ``memory_bytes`` that trains ``parameters`` parameters with Adam and
    needs ``working_bytes`` of temporaries besides their gradient. A
    device that reports no memory (``None``: the CPU) keeps nothing."""
    if not memory_bytes:
        return 0
    temporaries = TEMPORARIES_SHARE * (
        memory_bytes - ARGUMENT_BYTES_PER_PARAMETER * parameters)
    return max(0, int(temporaries - GRADIENT_BYTES_PER_PARAMETER * parameters
                      - working_bytes))


def keep_from_last(named: Sequence[int], budget: int) -> Tuple[int, ...]:
    """What each block keeps of its ``named`` bytes (0: its input alone).
    The blocks are walked from the last to the first and a block keeps
    its names, whole or not at all, while they fit in what is left of
    ``budget``: the backward pass frees the last block's first, so what
    the first blocks keep is what lies beside every other block's
    backward, and they are the first to go without."""
    kept = []
    for n in reversed(tuple(named)):
        kept.append(n if n <= budget else 0)
        budget -= kept[-1]
    return tuple(reversed(kept))


@jax.custom_vjp
def gradients_before_input(params, h):
    """``(params, h)`` as they are. In the backward pass the gradient of
    ``params`` is complete before the gradient of ``h`` goes on to the
    block before: left to itself the chip's compiler fuses a weight's
    gradient into its optimiser update and runs all of those last, so
    every block's activations and cotangents wait for the end of the step
    (8.00 GB of temporaries against 4.96 in the lfm2 cell's step;
    PERF.md §6, PR 35). Wrap a block's parameters and input with it."""
    return params, h


def _tied_fwd(params, h):
    return (params, h), None


def _tied_bwd(_, cotangents):
    return lax.optimization_barrier(cotangents)


gradients_before_input.defvjp(_tied_fwd, _tied_bwd)
