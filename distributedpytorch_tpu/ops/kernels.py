"""The kernel-engagement policy layer: one object owns every Pallas
fast-path decision, the way ``ops/precision.py`` owns every cast
boundary.

Before this module the kernel tier was scattered conventions: an
eval-only stats kernel (ops/pallas_kernels.py) behind ``use_pallas``, a
differentiable fused loss (ops/fused_loss.py) enabled per-strategy, and
nothing the planner could see. A convention cannot be selected, probed,
or searched; a policy object can.

``--kernels`` (``TrainConfig.kernels``) selects one of two policies:

=========  =================================================================
policy     what engages
=========  =================================================================
``xla``    nothing — every output is BIT-IDENTICAL to the historical
           paths (the correctness reference every kernel is pinned
           against)
``pallas`` every engagement site below; a kernel Mosaic refuses fails
           the run, unless the operator hands over a priors file that
           marks it rejected (``apply_priors``)
=========  =================================================================

Engagement sites (the full table lives in docs/PERFORMANCE.md
"Kernels"):

* ``train_loss_fused`` — the training loss statistics through the fused
  one-pass kernel + analytic VJP (ops/fused_loss.py; plain steps, the
  grad-accum scan, and both pipeline schedules);
* ``eval_stats_fused`` — eval loss+Dice from the one-pass stats kernel
  (ops/pallas_kernels.py; unsharded eval batches only, as before);
* ``conv_epilogue``    — the NEW fused DoubleConv epilogue below
  (:func:`fused_bn_act`): BN-normalize + ReLU in one VMEM pass after
  the XLA conv, with a hand-written elementwise VJP so it rides the
  training path (models/milesial.py ``DoubleConv``). XLA keeps the conv
  itself — its conv lowering owns the MXU (pallas_kernels.py design
  note); what Pallas buys is the elementwise tail that XLA schedules as
  separate normalize/activation fusions over HBM;
* ``serve_mask``       — the NEW fused sigmoid/threshold mask kernel
  (:func:`sigmoid_threshold_mask`): probabilities → ``{0,255} uint8``
  masks INSIDE the serve tier's AOT bucket executables
  (serve/infer.make_forward), so the D2H transfer carries 1 byte/pixel
  instead of 4 and the host threshold pass disappears.

**Mosaic probe priors.** Every kernel has a compile-only probe
(``PROBES``): lower + compile at a representative shape, record
accepted-or-rejected with the Mosaic reason, ZERO execution. ``tools/probe_kernels.py`` runs the
registry on the chip and writes a per-chip priors file (exit code
non-zero on any refusal). The file is an EXPLICIT input
(``--kernel-priors`` / ``$DPT_KERNEL_PRIORS``): only then does
``apply_priors`` turn the kernels it marks rejected off in the resolved
policy; with no priors given nothing is probed and a refused kernel
fails the run at compile time. ``analysis/planner.py --kernel-priors``
consumes the same file as a search axis — ``plan`` rejects
Mosaic-rejected kernel points with zero device time and ranks kernel-on
vs kernel-off configs.

The legacy ``TrainConfig.use_pallas`` flag resolves here as a LOUD
backward-compat alias (like ``compute_dtype`` → ``--dtype``): it maps to
exactly its historical engagement set (fused training loss + eval
stats), never the new kernels.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import logging
import os
import time
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedpytorch_tpu.ops.precision import (
    LOSS_DTYPE,
    NORM_DTYPE,
    WGRAD_DTYPE,
)
from distributedpytorch_tpu.utils.backend import pallas_interpret

logger = logging.getLogger(__name__)

LANES = 128  # TPU vector lane width (pallas_kernels.py contract)
#: Rows per grid step of the elementwise kernels: a (512, C) f32 tile is
#: 256 KB at C=128 and 2 MB at the deepest milesial width (C=1024) —
#: comfortably VMEM-resident with in+out+params live.
BLOCK_ROWS = 512


# ---------------------------------------------------------------------------
# The policy object
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """One kernel-engagement policy: which Pallas fast paths trace into
    the step/serve executables. Frozen — strategies, the model factory,
    the serve engine, and the planner all read the same object, so an
    engagement decision cannot drift between layers."""

    name: str
    train_loss_fused: bool   # ops/fused_loss.py on the training path
    eval_stats_fused: bool   # ops/pallas_kernels.py on the eval path
    conv_epilogue: bool      # fused_bn_act in milesial DoubleConv
    serve_mask: bool         # sigmoid_threshold_mask in the AOT serve fwd

    def any_engaged(self) -> bool:
        return any(
            (self.train_loss_fused, self.eval_stats_fused,
             self.conv_epilogue, self.serve_mask)
        )


KERNEL_POLICIES: Dict[str, KernelPolicy] = {
    "xla": KernelPolicy("xla", False, False, False, False),
    "pallas": KernelPolicy("pallas", True, True, True, True),
}

#: Probe-registry kernel name → the policy field(s) it gates: a priors
#: file marking a kernel Mosaic-rejected turns exactly these engagement
#: sites off (``apply_priors``).
KERNEL_GATES: Dict[str, Tuple[str, ...]] = {
    "fused_loss": ("train_loss_fused",),
    "eval_stats": ("eval_stats_fused",),
    "conv_epilogue": ("conv_epilogue",),
    "serve_mask": ("serve_mask",),
}


def get_kernel_policy(
    config_or_name=None, priors: Optional[Mapping] = None
) -> KernelPolicy:
    """Resolve the session's kernel policy.

    Accepts a policy name, ``None`` (→ ``xla``), an already-resolved
    :class:`KernelPolicy` (passes through), or a TrainConfig/ServeConfig
    — in which case the legacy ``use_pallas`` flag is honored as a loud
    backward-compat alias mapping to its HISTORICAL engagement set
    (fused training loss + eval stats, nothing new). An explicit
    ``kernels="pallas"`` supersedes the alias.

    ``priors`` (or the config's ``kernel_priors`` path / the
    ``DPT_KERNEL_PRIORS`` env var) applies the per-chip Mosaic probe
    verdicts: rejected kernels disengage, loudly."""
    if isinstance(config_or_name, KernelPolicy):
        policy = config_or_name
    elif config_or_name is None:
        policy = KERNEL_POLICIES["xla"]
    elif isinstance(config_or_name, str):
        policy = _by_name(config_or_name)
        if priors is None:
            # name-based resolution (the serve engine)
            # still honors the session's probe verdicts
            policy = apply_priors(policy, _env_priors() or {})
    else:
        name = getattr(config_or_name, "kernels", None) or "xla"
        policy = _by_name(name)
        if policy.name == "xla" and getattr(config_or_name, "use_pallas", False):
            logger.warning(
                "use_pallas is a legacy alias — resolving to the fused "
                "loss/eval-stats kernels it always meant; prefer "
                "--kernels pallas (ops/kernels.py), which also engages "
                "the conv-epilogue and serve-mask kernels"
            )
            policy = dataclasses.replace(
                policy, name="pallas_loss", train_loss_fused=True,
                eval_stats_fused=True,
            )
        if priors is None:
            priors = _config_priors(config_or_name)
    if priors is not None:
        policy = apply_priors(policy, priors)
    return policy


def _by_name(name: str) -> KernelPolicy:
    try:
        return KERNEL_POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel policy {name!r}; expected one of "
            f"{sorted(KERNEL_POLICIES)}"
        ) from None


def _env_priors() -> Optional[dict]:
    path = os.environ.get("DPT_KERNEL_PRIORS")
    return load_priors(path) if path else None


def _config_priors(config) -> Optional[dict]:
    path = getattr(config, "kernel_priors", None)
    if not path:
        return _env_priors()
    return load_priors(path)


#: (policy name, kernel, field) verdicts already warned about — see the
#: once-per-verdict note inside :func:`apply_priors`.
_WARNED_REJECTIONS: set = set()


def apply_priors(policy: KernelPolicy, priors: Mapping) -> KernelPolicy:
    """Disengage every kernel the priors file marks Mosaic-rejected.
    A kernel absent from the file stays as the policy says (unprobed ≠
    rejected). Returns the (possibly narrowed) policy."""
    kernels = priors.get("kernels") if isinstance(priors, Mapping) else None
    if not isinstance(kernels, Mapping):
        return policy
    changes: Dict[str, bool] = {}
    for kernel, row in kernels.items():
        if not isinstance(row, Mapping) or row.get("accepted", True):
            continue
        for field in KERNEL_GATES.get(kernel, ()):
            if getattr(policy, field, False):
                changes[field] = False
                # the policy re-resolves per layer (strategy, model
                # factory, serve engine) — warn ONCE per verdict so one
                # rejection doesn't read as several in the log
                mark = (policy.name, kernel, field)
                if mark not in _WARNED_REJECTIONS:
                    _WARNED_REJECTIONS.add(mark)
                    logger.warning(
                        "kernel policy %r: Mosaic rejected %s on this "
                        "chip (%s) — %s disengaged, XLA path "
                        "(bit-identical reference) kept",
                        policy.name, kernel,
                        row.get("reason", "no reason recorded"), field,
                    )
    if not changes:
        return policy
    return dataclasses.replace(policy, **changes)


def conv_epilogue_engaged(config) -> bool:
    """Whether the model factory should build milesial's DoubleConv with
    the fused epilogue: the policy must ask for it AND the strategy's
    forward must be device-local — single device, or the shard_map
    pipeline schedules (stage fns see plain local arrays). GSPMD-sharded
    strategies (DP/DDP/FSDP/TP/SP) keep the XLA BN+ReLU: pallas_call has
    no partition rule for their sharded activations (the same gate
    ``_pallas_eval`` applies to the stats kernel)."""
    policy = get_kernel_policy(config)
    if not policy.conv_epilogue:
        return False
    method = getattr(config, "train_method", "singleGPU")
    if method not in ("singleGPU", "MP", "DDP_MP"):
        logger.info(
            "--kernels: strategy %s runs the model forward under GSPMD "
            "sharding — the conv-epilogue kernel stays off there "
            "(pallas_call has no partition rule); single-device and "
            "shard_map pipeline runs engage it", method,
        )
        return False
    return True


def train_step_kernels(config) -> Tuple[str, ...]:
    """Probe-registry names of the kernels a TRAIN step under ``config``
    would engage with a ``pallas`` policy — what the planner's priors
    gate must clear for a kernel-on point (analysis/planner.py)."""
    names = ["fused_loss"]
    if getattr(config, "model_arch", "unet") == "milesial":
        names.append("conv_epilogue")
    return tuple(names)


# ---------------------------------------------------------------------------
# Kernel 1 (NEW): fused DoubleConv epilogue — BN-normalize + ReLU
# ---------------------------------------------------------------------------
#
# After the XLA conv, milesial's DoubleConv runs BatchNorm-normalize then
# ReLU: two elementwise passes XLA schedules as separate fusions over the
# (B, H, W, C) activation in HBM. Folding the affine —
#
#     y = relu((x − mean)·rsqrt(var + eps)·scale + bias)
#       = relu(x·a + b),   a = rsqrt(var+eps)·scale,  b = bias − mean·a
#
# — makes the whole epilogue one multiply-add + max per element: each
# tile is read from VMEM once and written once. The BATCH STATISTICS
# (mean/var reductions, running-average updates) stay XLA — they are
# tiny reductions the compiler already fuses, and keeping them outside
# means autodiff composes: the kernel's VJP emits cotangents w.r.t.
# (x, mean, var, scale, bias) and XLA chains d(mean)/d(var) back to x
# through its own stats graph.
#
# Backward: dz = g·[z > 0] is elementwise; every parameter cotangent is
# a per-channel reduction of dz — so ONE kernel pass computes dx and
# accumulates s1 = Σ dz, s2 = Σ dz·(x − mean) per channel (the standard
# sequential-grid accumulator, f32 per the WGRAD contract), and the
# closed forms
#
#     dbias = s1          dscale = inv·s2        dmean = −a·s1
#     dvar  = −½·scale·inv³·s2                   dx    = dz·a
#
# finish in a few (C,)-sized XLA ops.


def _bn_act_kernel(x_ref, p_ref, o_ref):
    """One grid step: y = relu(x·a + b) of a (BLOCK_ROWS, C) tile;
    p_ref rows are [a, b] (the folded affine), f32 per NORM_DTYPE."""
    x = x_ref[:].astype(NORM_DTYPE)
    a = p_ref[0, :]
    b = p_ref[1, :]
    o_ref[:] = jnp.maximum(x * a + b, 0.0)


def _bn_act_bwd_kernel(x_ref, g_ref, p_ref, dx_ref, s_ref):
    """One grid step of the epilogue backward: dx tile + the two
    per-channel WGRAD_DTYPE accumulators (s_ref rows: Σdz, Σdz·(x−mean))
    carried VMEM-resident across the sequential grid. p_ref rows are
    [a, b, mean]."""

    @pl.when(pl.program_id(0) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[:].astype(NORM_DTYPE)
    g = g_ref[:].astype(WGRAD_DTYPE)
    a = p_ref[0, :]
    b = p_ref[1, :]
    mean = p_ref[2, :]
    z = x * a + b
    dz = jnp.where(z > 0.0, g, 0.0)
    dx_ref[:] = dz * a
    s_ref[0, :] += jnp.sum(dz, axis=0)
    s_ref[1, :] += jnp.sum(dz * (x - mean), axis=0)


def _rows_of(x: jax.Array) -> Tuple[jax.Array, int]:
    """(B, ..., C) → zero-padded (R, C) with R a BLOCK_ROWS multiple;
    returns (rows, true row count). Zero pad rows are inert in the
    backward (g is padded with zeros too → dz = 0 contributes nothing to
    the channel sums); forward pad rows are sliced off."""
    c = x.shape[-1]
    flat = x.reshape(-1, c)
    n = flat.shape[0]
    pad = (-n) % BLOCK_ROWS
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    return flat, n


def _spec(block, index_map, interpret):
    if interpret:
        return pl.BlockSpec(block, index_map)
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def _sequential_grid_params(interpret):
    if interpret:
        return {}
    # sequential grid: the accumulator output block is carried across
    # steps
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",)
    )}


def fused_bn_act(
    x: jax.Array,
    mean: jax.Array,
    var: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    epsilon: float = 1e-5,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``relu((x − mean)·rsqrt(var + eps)·scale + bias)`` in ONE fused
    VMEM pass, differentiable on the training path via the hand-written
    elementwise VJP above. ``x`` is (..., C); the channel operands are
    (C,). Returns NORM_DTYPE (f32), like the XLA BN it replaces —
    callers cast back to the compute dtype.

    Numerics: the folded affine associates ``x·(inv·scale)`` where the
    XLA path computes ``((x − mean)·inv)·scale`` — equal to float
    rounding (~1e-6 relative), not bitwise; the parity band is pinned in
    tests/test_kernels.py. Inputs must be unsharded/device-local
    (pallas_call has no GSPMD partition rule — see
    ``conv_epilogue_engaged``)."""
    if interpret is None:
        interpret = pallas_interpret()
    return _fused_bn_act_p(
        x, mean, var, scale, bias, float(epsilon), bool(interpret)
    )


def _bn_act_fwd_impl(x, mean, var, scale, bias, epsilon, interpret):
    mean = mean.astype(NORM_DTYPE)
    inv = jax.lax.rsqrt(var.astype(NORM_DTYPE) + epsilon)
    a = inv * scale.astype(NORM_DTYPE)
    b = bias.astype(NORM_DTYPE) - mean * a
    rows, n = _rows_of(x)
    c = rows.shape[-1]
    num_blocks = rows.shape[0] // BLOCK_ROWS
    packed = jnp.stack([a, b])  # (2, C)
    y = pl.pallas_call(
        _bn_act_kernel,
        grid=(num_blocks,),
        in_specs=[
            _spec((BLOCK_ROWS, c), lambda i: (i, 0), interpret),
            _spec((2, c), lambda i: (0, 0), interpret),
        ],
        out_specs=_spec((BLOCK_ROWS, c), lambda i: (i, 0), interpret),
        out_shape=jax.ShapeDtypeStruct(rows.shape, NORM_DTYPE),
        interpret=interpret,
    )(rows, packed)
    return y[:n].reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_bn_act_p(x, mean, var, scale, bias, epsilon, interpret):
    return _bn_act_fwd_impl(x, mean, var, scale, bias, epsilon, interpret)


def _bn_act_fwd(x, mean, var, scale, bias, epsilon, interpret):
    y = _bn_act_fwd_impl(x, mean, var, scale, bias, epsilon, interpret)
    return y, (x, mean, var, scale, bias)


def _bn_act_bwd(epsilon, interpret, res, g):
    x, mean, var, scale, bias = res
    mean32 = mean.astype(NORM_DTYPE)
    inv = jax.lax.rsqrt(var.astype(NORM_DTYPE) + epsilon)
    a = inv * scale.astype(NORM_DTYPE)
    b = bias.astype(NORM_DTYPE) - mean32 * a
    rows, n = _rows_of(x)
    g_rows, _ = _rows_of(g)
    c = rows.shape[-1]
    num_blocks = rows.shape[0] // BLOCK_ROWS
    packed = jnp.stack([a, b, mean32])  # (3, C)
    dx_rows, sums = pl.pallas_call(
        _bn_act_bwd_kernel,
        grid=(num_blocks,),
        in_specs=[
            _spec((BLOCK_ROWS, c), lambda i: (i, 0), interpret),
            _spec((BLOCK_ROWS, c), lambda i: (i, 0), interpret),
            _spec((3, c), lambda i: (0, 0), interpret),
        ],
        out_specs=[
            _spec((BLOCK_ROWS, c), lambda i: (i, 0), interpret),
            _spec((2, c), lambda i: (0, 0), interpret),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(rows.shape, WGRAD_DTYPE),
            jax.ShapeDtypeStruct((2, c), WGRAD_DTYPE),
        ],
        interpret=interpret,
        **_sequential_grid_params(interpret),
    )(rows, g_rows, packed)
    s1, s2 = sums[0], sums[1]
    dx = dx_rows[:n].reshape(x.shape).astype(x.dtype)
    dbias = s1.astype(bias.dtype)
    dscale = (inv * s2).astype(scale.dtype)
    dmean = (-a * s1).astype(mean.dtype)
    dvar = (-0.5 * scale.astype(NORM_DTYPE) * inv**3 * s2).astype(var.dtype)
    return dx, dmean, dvar, dscale, dbias


_fused_bn_act_p.defvjp(_bn_act_fwd, _bn_act_bwd)


# ---------------------------------------------------------------------------
# Kernel 2 (NEW): fused sigmoid/threshold serve mask
# ---------------------------------------------------------------------------


def sigmoid_threshold_mask(
    x: jax.Array,
    threshold: float,
    from_logits: bool = False,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Probabilities (or logits) → the served ``{0, 255} uint8`` mask in
    ONE fused pass, same shape out. The serve tier traces this into its
    AOT bucket executables (serve/infer.make_forward): the executable's
    output — and the D2H transfer behind every completion drain — shrinks
    from 4 f32 bytes/pixel to 1, and the host-side numpy threshold pass
    disappears from the completion workers.

    ``from_logits=True`` fuses the sigmoid in too (for heads that emit
    raw logits); the shipping binary-segmentation heads apply their
    sigmoid inside the model under the LOSS_DTYPE contract, so the serve
    engagement feeds probabilities and the comparison is EXACT — masks
    are bit-identical to ``postprocess_mask`` on the same probabilities
    (tests/test_kernels.py pins this across bucket shapes).

    ``threshold`` is trace-time static (the serve tier compiles one
    executable per bucket at a fixed operating point)."""
    if interpret is None:
        interpret = pallas_interpret()
    thr = float(threshold)

    def kernel(x_ref, o_ref):
        v = x_ref[:].astype(LOSS_DTYPE)
        if from_logits:
            v = jax.nn.sigmoid(v)
        # select in int32, then narrow: Mosaic on v5e refuses the
        # i1 -> uint8 select directly (it cannot move the (512, 128)
        # predicate into the packed 8-bit tiling)
        o_ref[:] = jnp.where(v >= thr, 255, 0).astype(jnp.uint8)

    flat = x.reshape(-1)
    n = flat.shape[0]
    per_block = BLOCK_ROWS * LANES
    num_blocks = max(1, -(-n // per_block))
    pad = num_blocks * per_block - n
    rows = jnp.pad(flat, (0, pad)).reshape(num_blocks * BLOCK_ROWS, LANES)
    mask = pl.pallas_call(
        kernel,
        grid=(num_blocks,),
        in_specs=[_spec((BLOCK_ROWS, LANES), lambda i: (i, 0), interpret)],
        out_specs=_spec((BLOCK_ROWS, LANES), lambda i: (i, 0), interpret),
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.uint8),
        interpret=interpret,
    )(rows)
    return mask.reshape(-1)[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# Mosaic probe registry + per-chip priors file
# ---------------------------------------------------------------------------

PRIORS_KIND = "dpt_kernel_priors"
#: Priors-file schema version: consumers (planner ``--kernel-priors``,
#: ``apply_priors`` via DPT_KERNEL_PRIORS) ignore any other value with a
#: note — a stale priors file must never silently flip engagement.
PRIORS_VERSION = 1


def _probe_eval_stats():
    from distributedpytorch_tpu.ops.pallas_kernels import eval_stats_pallas

    x = jnp.zeros((2, 32, 64, 1), LOSS_DTYPE)
    jax.jit(eval_stats_pallas).lower(x, x).compile()


def _probe_fused_loss():
    from distributedpytorch_tpu.ops.fused_loss import fused_bce_dice_loss

    x = jnp.zeros((2, 32, 64, 1), LOSS_DTYPE)
    jax.jit(jax.value_and_grad(fused_bce_dice_loss)).lower(x, x).compile()


def _probe_conv_epilogue():
    c = 128  # the hot milesial widths are full lane tiles
    x = jnp.zeros((2, 16, 24, c), NORM_DTYPE)
    vec = jnp.zeros((c,), NORM_DTYPE)

    def loss(x, mean, var, scale, bias):
        return jnp.sum(fused_bn_act(x, mean, var, scale, bias))

    jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, vec, vec + 1.0, vec + 1.0, vec
    ).compile()


def _probe_serve_mask():
    x = jnp.zeros((2, 32, 64), LOSS_DTYPE)
    jax.jit(
        lambda v: sigmoid_threshold_mask(v, 0.5)
    ).lower(x).compile()


#: The probe registry: kernel name → a compile-only callable (AOT
#: ``lower().compile()``, ZERO execution). On a TPU
#: utils/backend.pallas_interpret resolves to real Mosaic lowering, so an exception IS the chip's accept/reject
#: verdict; on an operator-named CPU the interpreter path compiles,
#: proving the machinery.
PROBES: Dict[str, Callable[[], None]] = {
    "eval_stats": _probe_eval_stats,
    "fused_loss": _probe_fused_loss,
    "conv_epilogue": _probe_conv_epilogue,
    "serve_mask": _probe_serve_mask,
}


def run_probes(
    names: Optional[Sequence[str]] = None,
    emit: Optional[Callable[[dict], None]] = None,
) -> dict:
    """Run the (selected) probe registry; returns the priors payload
    (what ``save_priors`` writes). A Mosaic rejection is this
    function's RESULT (recorded with its reason): the caller decides
    what it costs — tools/probe_kernels.py exits non-zero on one."""
    selected = list(names) if names else sorted(PROBES)
    unknown = [n for n in selected if n not in PROBES]
    if unknown:
        raise ValueError(
            f"unknown probe kernel(s) {unknown}; registry has "
            f"{sorted(PROBES)}"
        )
    dev = jax.devices()[0]
    kernels: Dict[str, dict] = {}
    for name in selected:
        t0 = time.monotonic()
        row: Dict[str, object] = {"kernel": name}
        try:
            PROBES[name]()
            row.update(accepted=True)
        except Exception as exc:  # noqa: BLE001 — the verdict, not a bug
            reason = f"{type(exc).__name__}: {exc}"
            row.update(accepted=False, reason=reason[:500])
        row["compile_s"] = round(time.monotonic() - t0, 3)
        kernels[name] = {k: v for k, v in row.items() if k != "kernel"}
        if emit is not None:
            emit(row)
    return {
        "kind": PRIORS_KIND,
        "version": PRIORS_VERSION,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "kernels": kernels,
    }


def save_priors(payload: dict, path: str) -> None:
    """Atomic write, mirroring the planner's plan-file IO."""
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)


#: (path → (mtime, payload)) memo: the policy re-resolves per layer in
#: one process (strategy, model factory, serve engine), and each should
#: not re-read + re-parse the same on-disk file. Keyed on mtime so a
#: rewritten file (a fresh probe run) invalidates naturally.
_PRIORS_CACHE: Dict[str, Tuple[float, Optional[dict]]] = {}


def load_priors(path: str) -> Optional[dict]:
    """The priors payload, or None — with a logged note — for a missing,
    unreadable, corrupt, or version-skewed file. Consumers degrade to
    unprobed behavior on None; a half-written or stale priors file must
    never flip kernel engagement or reorder a plan silently."""
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return None
    cached = _PRIORS_CACHE.get(path)
    if cached is not None and cached[0] == mtime:
        return cached[1]
    payload = _read_priors(path)
    _PRIORS_CACHE[path] = (mtime, payload)
    return payload


def _read_priors(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            payload = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, ValueError) as exc:
        logger.warning(
            "kernel priors %s unreadable (%s) — ignored; kernels stay "
            "unprobed", path, exc,
        )
        return None
    if (
        not isinstance(payload, dict)
        or payload.get("kind") != PRIORS_KIND
        or payload.get("version") != PRIORS_VERSION
        or not isinstance(payload.get("kernels"), dict)
    ):
        logger.warning(
            "kernel priors %s stale or malformed (want kind=%r version="
            "%d) — ignored; kernels stay unprobed",
            path, PRIORS_KIND, PRIORS_VERSION,
        )
        return None
    return payload
