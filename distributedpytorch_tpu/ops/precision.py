"""The mixed-precision policy layer: one object owns every cast boundary.

The recipe is Micikevicius et al.'s mixed-precision training (ICLR 2018)
specialized to TPU bf16: low-precision *compute* where the MXU pays
(convolutions, activations), full-precision *state* where rounding
compounds (master weights, loss, reductions). Before this module the
pieces existed as conventions scattered across the codebase — bf16 conv
compute via the model ``dtype``, f32 params by init default, f32 loss by
``astype`` calls in ops/losses.py, f32 wgrad accumulation hand-written
into the 1F1B schedule. A convention cannot be selected, checkpointed,
or linted; a policy object can.

``--dtype`` (``TrainConfig.dtype``) selects one of three policies:

=============  ========  ==========  ==============  =====================
policy         compute   params      master weights  what it is
=============  ========  ==========  ==============  =====================
``f32``        float32   float32     —               the pure-f32 reference
                                                     every equivalence band
                                                     is measured against
``bf16``       bfloat16  float32     —               today's shipping
                                                     default made explicit:
                                                     MXU conv compute in
                                                     bf16, f32 params/loss
``bf16_params`` bfloat16  bfloat16   f32 in opt      halved on-device param
                                                     bytes (and FSDP
                                                     all-gather traffic);
                                                     Adam runs on an f32
                                                     master copy living in
                                                     optimizer state
=============  ========  ==========  ==============  =====================

Invariant under EVERY policy — the three stated f32 contracts, named as
constants so traced code spells the *policy seam*, not a bare dtype
literal (the ``dtype-policy`` dptlint rule flags bare ``jnp.float32`` in
traced functions; these names are the sanctioned spelling):

* ``LOSS_DTYPE``   — loss and Dice/BCE statistics accumulate in f32
  (ops/losses.py casts at entry; a bf16 log-loss near saturation is
  garbage — see losses._clamped_log);
* ``WGRAD_DTYPE``  — weight-gradient accumulation is f32: the 1F1B
  schedule's per-microbatch accumulator (parallel/pipeline.py), the
  grad-accumulation scan (train/steps.make_accum_train_step), and the
  master-weight wrapper's cast at the optimizer boundary;
* ``REDUCE_DTYPE`` — the schedule-closing grad psum and the loss-stats
  psum operate on f32 trees (a contract extended from the PR-4
  pipeline, now stated once here).

Master weights (``bf16_params``): :func:`with_master_weights` wraps the
optax chain so ``opt_state`` carries an f32 master copy; each update
casts incoming grads to ``WGRAD_DTYPE``, runs Adam against the master,
and emits the delta that lands the bf16 on-device params exactly on the
rounded master. The plateau scheduler's lr passthrough keeps working:
:class:`MasterWeightsState` forwards ``.hyperparams`` to the wrapped
inject_hyperparams state.

Checkpoints record the saving policy in the manifest (``topology
["precision"]``); :func:`convert_checkpoint_state` converts between
policies at restore EXACTLY (bf16_params → f32 promotes the f32 master
to the params; f32 → bf16_params seeds the master from the saved f32
params), and :func:`ensure_restored_dtypes` is the loud re-cast seam
every restore path must route through (the ``ckpt-dtype-drift`` dptlint
rule flags restores that bypass it — a silently drifted dtype retraces
the jitted step against donated buffers of the wrong layout).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

logger = logging.getLogger(__name__)

# -- the stated f32 contracts (sanctioned spellings for traced code) --------
LOSS_DTYPE = jnp.float32    # loss + Dice/BCE stats accumulation
WGRAD_DTYPE = jnp.float32   # weight-grad accumulation (pipeline, accum, master)
REDUCE_DTYPE = jnp.float32  # cross-device grad/stats psums
# BatchNorm statistics + normalization math (models/milesial.py: variance
# in bf16 is numerically unsafe, so BN computes f32 and casts back under
# every policy). Named here so the fused conv-epilogue kernel
# (ops/kernels.py) spells the same contract the XLA BN path implements.
NORM_DTYPE = jnp.float32
# The token models' f32 islands inside a bf16 step (models/twotower.py):
# an expert router's scores and gates (a rounding there picks another
# expert), and a chunked scan's decay, cumulative sums and carried state
# (ops/sequence.py). Softmax and logits are LOSS_DTYPE, RMSNorm NORM_DTYPE.
ROUTER_DTYPE = jnp.float32
SCAN_DTYPE = jnp.float32


def _is_float_leaf(x) -> bool:
    dt = getattr(x, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jnp.floating)


def cast_float_leaves(tree, dtype):
    """Cast every floating leaf of ``tree`` to ``dtype``; integer leaves
    (step counters, int8 quantized weights) pass through. THE one
    cast-a-tree definition — every policy boundary in this module (and
    the pipeline's gpipe widening) goes through it, so a change to what
    counts as castable cannot drift between boundaries."""
    return jax.tree.map(
        lambda x: x.astype(dtype) if _is_float_leaf(x) else x, tree
    )


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """One precision policy: which dtype computes, which dtype stores
    params on device, and whether an f32 master copy lives in optimizer
    state. Frozen — strategies, steps, and checkpoints all read the same
    object, so a cast boundary cannot drift between layers."""

    name: str
    compute: str         # conv/activation compute dtype (the model dtype)
    params: str          # on-device param storage dtype
    master_weights: bool  # f32 master copy in optimizer state

    @property
    def compute_dtype(self):
        return jnp.dtype(self.compute)

    @property
    def param_dtype(self):
        return jnp.dtype(self.params)

    # -- cast boundaries ----------------------------------------------------
    def cast_params(self, params):
        """Param cast-in at state construction/restore: float leaves to the
        policy's on-device storage dtype (integer leaves — step counters —
        pass through)."""
        return cast_float_leaves(params, self.param_dtype)

    def cast_grads(self, grads):
        """The optimizer-boundary wgrad contract: under a master-weight
        policy, gradients leave the backward in the param (bf16) dtype and
        must be stated f32 BEFORE any scaling or accumulation touches
        them. No-op when params are already f32."""
        if not self.master_weights:
            return grads
        return cast_float_leaves(grads, WGRAD_DTYPE)

    def wrap_optimizer(self, tx: optax.GradientTransformation):
        """Master-weight policies interpose :func:`with_master_weights`;
        the others return ``tx`` unchanged."""
        if not self.master_weights:
            return tx
        return with_master_weights(tx)


POLICIES = {
    "f32": PrecisionPolicy("f32", "float32", "float32", False),
    "bf16": PrecisionPolicy("bf16", "bfloat16", "float32", False),
    "bf16_params": PrecisionPolicy("bf16_params", "bfloat16", "bfloat16", True),
}


def get_policy(config_or_name=None) -> PrecisionPolicy:
    """Resolve the session's policy.

    Accepts a policy name, ``None`` (→ the ``bf16`` default), or a
    TrainConfig — in which case the legacy ``compute_dtype`` override is
    honored: the test/bench idiom ``TrainConfig(compute_dtype="float32")``
    keeps meaning "f32 conv compute, f32 params" exactly as it did before
    the policy layer existed (param storage and master-weight behavior
    still follow ``dtype``)."""
    if config_or_name is None:
        return POLICIES["bf16"]
    if isinstance(config_or_name, str):
        return _by_name(config_or_name)
    name = getattr(config_or_name, "dtype", None) or "bf16"
    policy = _by_name(name)
    override = getattr(config_or_name, "compute_dtype", None)
    if override is not None and jnp.dtype(override) != policy.compute_dtype:
        policy = dataclasses.replace(
            policy, compute=jnp.dtype(override).name
        )
    return policy


def _by_name(name: str) -> PrecisionPolicy:
    try:
        return POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown precision policy {name!r}; expected one of "
            f"{sorted(POLICIES)}"
        ) from None


# ---------------------------------------------------------------------------
# f32 master weights in optimizer state (the bf16_params policy)
# ---------------------------------------------------------------------------


class MasterWeightsState(NamedTuple):
    """Optimizer state of :func:`with_master_weights`: the f32 master
    params plus the wrapped transformation's own state (over the master).
    A NamedTuple so it is a jax pytree and flax-msgpack-serializable —
    master weights ride in every checkpoint's ``opt_state`` untouched.
    ``hyperparams`` forwards to the wrapped inject_hyperparams state so
    the plateau scheduler's lr rewrite (ops/optim.set_learning_rate)
    works identically under every policy."""

    master: Any
    inner: Any

    @property
    def hyperparams(self):
        return self.inner.hyperparams


def with_master_weights(
    tx: optax.GradientTransformation,
) -> optax.GradientTransformation:
    """Run ``tx`` against an f32 master copy of the params.

    ``init`` promotes the (bf16) params to the f32 master and initializes
    ``tx`` over it — Adam's m/v therefore live in f32, mirroring master
    shapes. ``update`` casts incoming grads to ``WGRAD_DTYPE`` (the
    stated contract), steps the master, and returns the f32 delta whose
    ``optax.apply_updates`` application lands the on-device params
    exactly on the master rounded to their storage dtype (the add
    promotes to f32, so no second rounding accumulates)."""

    def init(params):
        master = cast_float_leaves(params, WGRAD_DTYPE)
        return MasterWeightsState(master=master, inner=tx.init(master))

    def update(updates, state, params=None):
        if params is None:
            raise ValueError(
                "with_master_weights requires params (the on-device "
                "low-precision copy) at every update"
            )
        grads32 = cast_float_leaves(updates, WGRAD_DTYPE)
        inner_updates, inner_state = tx.update(
            grads32, state.inner, state.master
        )
        master = optax.apply_updates(state.master, inner_updates)

        def delta(m, p):
            if not _is_float_leaf(p):
                return jnp.zeros_like(p)
            # target = master rounded to the storage dtype; emit it as an
            # f32 delta so apply_updates' promoted add reconstructs the
            # target without compounding a second rounding
            target = m.astype(p.dtype).astype(WGRAD_DTYPE)
            return target - p.astype(WGRAD_DTYPE)

        return (
            jax.tree.map(delta, master, params),
            MasterWeightsState(master=master, inner=inner_state),
        )

    return optax.GradientTransformation(init, update)


def unwrap_opt_state(opt_state):
    """The inject_hyperparams-bearing inner state regardless of policy —
    ops/optim's lr read/write goes through here."""
    if isinstance(opt_state, MasterWeightsState):
        return opt_state.inner
    return opt_state


# ---------------------------------------------------------------------------
# Restore-side seams (the ckpt-dtype-drift contract)
# ---------------------------------------------------------------------------


def ensure_restored_dtypes(tree, policy: PrecisionPolicy, where: str):
    """Loudly re-cast a restored float tree to the session policy's param
    dtype. The sanctioned restore seam: every ``load_checkpoint`` /
    ``load_weights`` consumer routes its params through here (or through
    :func:`convert_checkpoint_state`), so a checkpoint whose dtype drifted
    from the session policy re-casts with a log line instead of silently
    retracing the donated-buffer step executable against a layout the
    trainer never asked for."""
    dt = policy.param_dtype
    drifted = [
        getattr(x, "dtype", None)
        for x in jax.tree.leaves(tree)
        if _is_float_leaf(x) and x.dtype != dt
    ]
    if not drifted:
        return tree
    logger.warning(
        "%s: restored %d float leaves with dtype(s) %s under policy %r — "
        "re-cast to %s via the precision policy (a checkpoint saved under "
        "a different --dtype)",
        where, len(drifted), sorted({str(d) for d in drifted}), policy.name,
        dt.name,
    )
    return cast_float_leaves(tree, dt)


def convert_checkpoint_state(
    saved: PrecisionPolicy,
    current: PrecisionPolicy,
    params,
    opt_state,
    where: str = "restore",
):
    """Convert a restored (params, opt_state) pair between policies.

    The conversions are EXACT where exactness is possible:

    * master → no-master: the f32 master IS the full-precision truth;
      it becomes the params (cast to the current storage dtype — a no-op
      for f32) and the wrapped inner state becomes the opt_state.
    * no-master → master: the saved f32 params seed the master
      bit-identically; the saved Adam state (already over f32 params of
      the same shapes) becomes the inner state.
    * storage-dtype-only changes re-cast params; Adam state is f32 under
      every policy and passes through.

    Returns ``(params, opt_state)`` under the CURRENT policy. ``opt_state``
    may be None (weights-only restores) and passes through as None.
    """
    if saved.master_weights == current.master_weights:
        out_params = ensure_restored_dtypes(params, current, where)
        return out_params, opt_state
    if opt_state is None:
        return ensure_restored_dtypes(params, current, where), None
    if saved.master_weights and not current.master_weights:
        logger.warning(
            "%s: checkpoint saved under %r, restoring under %r — promoting "
            "the f32 master weights to the params (exact) and unwrapping "
            "the optimizer state",
            where, saved.name, current.name,
        )
        master = opt_state.master
        return current.cast_params(master), opt_state.inner
    logger.warning(
        "%s: checkpoint saved under %r, restoring under %r — seeding the "
        "f32 master from the saved params (exact) and wrapping the "
        "optimizer state",
        where, saved.name, current.name,
    )
    return (
        current.cast_params(params),
        MasterWeightsState(
            master=cast_float_leaves(params, WGRAD_DTYPE), inner=opt_state
        ),
    )


def param_bytes(tree) -> int:
    """Total bytes of a tree's array leaves — the policy table's memory
    claims (bf16 halves, int8 quarters) measured directly."""
    return sum(
        int(x.size) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
        if hasattr(x, "dtype")
    )
