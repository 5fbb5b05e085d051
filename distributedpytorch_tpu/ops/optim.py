"""Adam with torch-parity semantics and a runtime-adjustable learning rate.

The reference optimizes with ``optim.Adam(params, lr, weight_decay=1e-8)``
(reference utils/train_utils.py:45). torch's Adam ``weight_decay`` is L2
regularization folded into the gradient BEFORE the moment updates — not
AdamW's decoupled decay — so the optax chain is::

    add_decayed_weights(wd)  →  scale_by_adam(b1=.9, b2=.999, eps=1e-8)  →  -lr

(`optax.adamw` would decay after the Adam scaling — different trajectory.)

The lr rides in optimizer state via `optax.inject_hyperparams`, so the
plateau scheduler (ops/schedule.py) can change it between epochs WITHOUT
retriggering XLA compilation: the jitted train step reads the lr from state,
and `set_learning_rate` rewrites that one scalar on the host.
"""

from __future__ import annotations

import jax.numpy as jnp
import optax


def adam_l2(learning_rate: float, weight_decay: float = 1e-8,
            b2: float = 0.999) -> optax.GradientTransformation:
    """torch.optim.Adam(lr, weight_decay) parity (defaults b1=0.9, b2=0.999,
    eps=1e-8 match torch's); a model-table entry may state another ``b2``
    (models/__init__.py)."""

    @optax.inject_hyperparams
    def _make(lr):
        return optax.chain(
            optax.add_decayed_weights(weight_decay),
            optax.scale_by_adam(b1=0.9, b2=b2, eps=1e-8),
            optax.scale(-lr),
        )

    return _make(lr=learning_rate)


def _hyperparams(opt_state):
    """The inject_hyperparams dict regardless of precision policy: the
    bf16_params master-weight wrapper (ops/precision.MasterWeightsState)
    nests the real state one level down."""
    from distributedpytorch_tpu.ops.precision import unwrap_opt_state

    return unwrap_opt_state(opt_state).hyperparams


def set_learning_rate(opt_state, lr: float):
    """Rewrite the injected lr scalar in-place on the host (no recompile)."""
    hyperparams = _hyperparams(opt_state)
    hyperparams["lr"] = jnp.asarray(lr, dtype=jnp.asarray(hyperparams["lr"]).dtype)
    return opt_state


def get_learning_rate(opt_state) -> float:
    return float(_hyperparams(opt_state)["lr"])
