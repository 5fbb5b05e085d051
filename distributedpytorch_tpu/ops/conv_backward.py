"""Alternative conv backward: weight-grad as 9 tap matmuls.

Round-3 profiling (docs/PERFORMANCE.md) left the step backward-dominated:
the s2d-domain 3×3 convs run their BACKWARD at ~2.1× the forward's time,
i.e. XLA's conv-backward-filter emitter schedules no better than the
forward even though the weight gradient is just a tall contraction

    dW[ky,kx,ci,co] = Σ_{b,y,x} Xpad[b, y+ky, x+kx, ci] · dY[b, y, x, co]

— for the hot 128→128 @ 320×480 batch-4 shape: M = Cin = 128,
N = Cout = 128, K = B·H·W ≈ 614k per tap. This module re-expresses that
weight gradient as 9 explicit `einsum`s (one per kernel tap, each a plain
MXU matmul over a shifted view of the padded input) behind a
`jax.custom_vjp`, leaving the forward and the input-gradient on XLA's
conv emitter (the input-grad IS a conv — of dY with the rot180,
in/out-swapped kernel — and XLA runs convs at forward speed).

Numerics: the taps accumulate in float32 (`preferred_element_type`) and
cast back to the kernel dtype, the same contract as XLA's bf16 conv
backward; exactness vs `jax.grad` of the plain conv is pinned in
tests/test_s2d.py. Off by default (`TrainConfig.wgrad_taps`) until the
TPU measurement lands — this is a hypothesis with a test harness, not a
claimed win.

Backend: the tap contraction itself has two implementations — the 9
einsums below, and a single-pass Pallas kernel (ops/wgrad_pallas.py)
that loads each row once and accumulates all nine taps from VMEM.
``DPT_WGRAD_BACKEND=pallas`` selects the kernel AT TRACE TIME (set it
before the first jit of the model; already-compiled executables keep
whatever they traced). The Pallas path engages only for channel counts
that fill the 128-wide MXU/lane tiles; skinny convs (the RGB stem) stay
on einsum.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from distributedpytorch_tpu.ops.s2d import conv_same as _conv_same

# Minimum channel count for the Pallas wgrad path: below a full lane tile
# the kernel's (W+2, C) operands waste most of the vector unit and the
# einsum path's XLA fusions win.
_PALLAS_MIN_CHANNELS = 128


def _wgrad_backend() -> str:
    return os.environ.get("DPT_WGRAD_BACKEND", "einsum")


@jax.custom_vjp
def _conv3x3_same_taps_vjp(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """NHWC SAME stride-1 3×3 conv; forward = XLA conv, backward =
    XLA conv for dx + 9 tap matmuls for dW."""
    return _conv_same(x, kernel)


def _taps_min_hw() -> int:
    """Trace-time spatial gate for the taps rewrite.

    ``DPT_WGRAD_TAPS_MIN_HW=N`` scopes the 9-tap weight gradient to
    convs whose H·W plane is at least N pixels (default 0 = every
    conv). Two reasons to scope: (a) the tall-contraction win
    concentrates where K = B·H·W is largest — the shallow levels —
    while small-plane convs gain nothing over XLA's emitter; (b) the
    full-taps graph (9 einsums × every conv) is the largest XLA program
    this framework emits (not measured on the attached chip yet) —
    scoping to the top level(s) shrinks the graph severalfold."""
    raw = os.environ.get("DPT_WGRAD_TAPS_MIN_HW", "0")
    try:
        return int(raw)
    except ValueError:
        # fail LOUD: a typo'd threshold silently falling back to 0 would
        # select the full-taps-everywhere graph — the exact compile hang
        # the scoped config exists to avoid — under the scoped label
        raise ValueError(
            f"DPT_WGRAD_TAPS_MIN_HW={raw!r}: expected an integer pixel "
            "count") from None


def conv3x3_same_taps(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """The public taps conv: every model call site funnels here, so the
    DPT_WGRAD_TAPS_MIN_HW gate applies uniformly. Below the gate the
    conv is the plain XLA one — identical forward AND backward."""
    if x.shape[1] * x.shape[2] >= _taps_min_hw():
        return _conv3x3_same_taps_vjp(x, kernel)
    return _conv_same(x, kernel)


def _fwd(x, kernel):
    return _conv_same(x, kernel), (x, kernel)


def _wgrad_einsum(x, dy):
    """dW (3,3,Cin,Cout) f32 as 9 shifted-view einsums."""
    b, h, w, _ = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    taps = []
    for ky in range(3):
        for kx in range(3):
            win = jax.lax.slice(
                xp, (0, ky, kx, 0), (b, ky + h, kx + w, x.shape[3])
            )
            taps.append(
                jnp.einsum(
                    "bhwi,bhwo->io",
                    win,
                    dy,
                    preferred_element_type=jnp.float32,
                )
            )
    return jnp.stack(taps).reshape(3, 3, x.shape[3], dy.shape[3])


def _bwd(res, dy):
    x, kernel = res
    # dx: SAME conv of dY with the rotated, in/out-swapped kernel —
    # kt[ky,kx,co,ci] = k[2−ky, 2−kx, ci, co] (exact for stride-1 SAME).
    kt = kernel[::-1, ::-1].transpose(0, 1, 3, 2)
    dx = _conv_same(dy, kt)

    cin, cout = x.shape[3], kernel.shape[3]
    if (
        _wgrad_backend() == "pallas"
        and min(cin, cout) >= _PALLAS_MIN_CHANNELS
    ):
        from distributedpytorch_tpu.ops.wgrad_pallas import wgrad_9tap_pallas

        dk = wgrad_9tap_pallas(x, dy)
    else:
        dk = _wgrad_einsum(x, dy)
    return dx.astype(x.dtype), dk.astype(kernel.dtype)


_conv3x3_same_taps_vjp.defvjp(_fwd, _bwd)
