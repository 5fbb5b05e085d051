"""Weights of a token model whose head is its embedding's matrix (tied),
from the seed: ``weights_tokens``'s recipe by the leaf's name (matrices
with variance 1 / fan-in, a sub-layer's last product before the residual
sum divided by sqrt(2 x layers) besides, scales one, biases zero), but the
embedding with variance 1 / hidden size in place of 1: read as the head it
is a matrix with fan-in hidden size like every other, and the logits of a
normalised state have unit scale (a unit-normal embedding would give them
the scale sqrt(hidden size), a loss no freshly initialised network has).
A configuration names this module under ``weights``.
"""

from __future__ import annotations

import weights_tokens


def make(shapes: dict, seed: int, config: dict = None) -> dict:
    flat = weights_tokens.make(shapes, seed, config)
    return {k: v * v.shape[-1] ** -0.5 if k.endswith("/embedding") else v
            for k, v in flat.items()}
