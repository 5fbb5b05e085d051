"""Pallas TPU kernel for the 3×3 conv weight gradient (the 9-tap
tall contraction).

VERDICT r04 weak-3: the conv hot path's one genuinely Pallas-shaped
opportunity is the weight gradient — a tall contraction

    dW[ky,kx,ci,co] = Σ_{b,y,x} Xpad[b, y+ky, x+kx, ci] · dY[b, y, x, co]

with K = B·H·W ≈ 614k for the hot s2d shape (128→128 @ 320×480, batch 4).
The einsum formulation (ops/conv_backward.py) issues 9 independent
matmuls, each streaming a full shifted view of X and all of dY from HBM:
~9× the minimum input traffic for what is, at these C's, a
bandwidth-bound reduction. This kernel makes one pass: each grid step
loads one image row of Xpad (three row-offset views) and of dY (three
column-shift paddings) into VMEM ONCE and accumulates all nine taps from
it — ~3×+3× total traffic instead of 9×+9×.

Why three shifted OPERANDS instead of in-kernel slicing: the kx shift is
along the sublane dimension, and sublane slices at offsets 1 and 2 are
unaligned (f32 tiles are 8×128) — Mosaic may reject or silently relayout
them. Shifting dY *outside* the kernel turns every in-kernel operand into
a full (W+2, C) tile at offset 0, with the identity

    Σ_x Xpad[y+ky, x+kx]·dY[x]  =  Σ_u Xpad[y+ky, u]·dYpad_kx[u],
    dYpad_kx = dY padded with kx zeros left, 2−kx right.

The row (ky) offsets cost nothing: three BlockSpecs on the same Xpad
array whose index_map starts one block (= one row) apart.

Accumulation: the (3,3,Cin,Cout) f32 output block maps to the same block
at every grid step, so it stays VMEM-resident across the sequential grid
("arbitrary" dimension semantics) — the standard Pallas accumulator
pattern; taps accumulate in f32 regardless of input dtype (same contract
as XLA's bf16 conv backward and the einsum path).

Status: exactness-proven vs `jax.grad` of the plain conv in interpret
mode (tests/test_wgrad_pallas.py); real-TPU lowering and the A/B against
the einsum path are part of the chip-gated measurement program
(`tools/bench_wgrad.py --backend pallas`). Selected at trace time via
``DPT_WGRAD_BACKEND=pallas`` (ops/conv_backward.py); einsum remains the
default until the on-chip number exists.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The tap accumulator spells the WGRAD_DTYPE contract (ops/precision.py):
# weight-gradient accumulation is f32 under every --dtype policy — the
# dptlint ``dtype-policy`` rule reaches kernel bodies, and the named
# constant is its sanctioned spelling (this module is no longer exempt).
from distributedpytorch_tpu.ops.precision import WGRAD_DTYPE
from distributedpytorch_tpu.utils.backend import pallas_interpret


def _wgrad_kernel(x0, x1, x2, d0, d1, d2, out_ref):
    """One grid step = one (batch, row): nine (Cin, W+2) × (W+2, Cout)
    tap contractions from VMEM-resident tiles into the f32 accumulator."""

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xrows = (x0, x1, x2)
    dpads = (d0, d1, d2)
    for ky in range(3):
        xrow = xrows[ky][0, 0]  # (W+2, Cin)
        for kx in range(3):
            dpad = dpads[kx][0, 0]  # (W+2, Cout)
            out_ref[ky, kx] += jax.lax.dot_general(
                xrow,
                dpad,
                (((0,), (0,)), ((), ())),
                preferred_element_type=WGRAD_DTYPE,
            )


def wgrad_9tap_pallas(
    x: jax.Array, dy: jax.Array, interpret: Optional[bool] = None
) -> jax.Array:
    """Weight gradient of a SAME stride-1 3×3 NHWC conv: returns
    dW (3, 3, Cin, Cout) in float32 (callers cast to the kernel dtype)."""
    if interpret is None:
        interpret = pallas_interpret()
    b, h, w, cin = x.shape
    cout = dy.shape[-1]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))  # (B, H+2, W+2, Cin)
    # dYpad_kx[u] = dY[u − kx]: kx zeros left, 2−kx right → width W+2
    dps = [
        jnp.pad(dy, ((0, 0), (0, 0), (kx, 2 - kx), (0, 0)))
        for kx in range(3)
    ]

    in_space = None if interpret else pltpu.VMEM

    def spec(block, index_map):
        if in_space is None:
            return pl.BlockSpec(block, index_map)
        return pl.BlockSpec(block, index_map, memory_space=in_space)

    x_specs = [
        spec((1, 1, w + 2, cin), lambda bi, yi, _d=d: (bi, yi + _d, 0, 0))
        for d in range(3)
    ]
    d_specs = [
        spec((1, 1, w + 2, cout), lambda bi, yi: (bi, yi, 0, 0))
        for _ in range(3)
    ]
    out_spec = spec((3, 3, cin, cout), lambda bi, yi: (0, 0, 0, 0))

    kwargs = {}
    if not interpret:
        # sequential grid: the output block accumulates across steps
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        )
    return pl.pallas_call(
        _wgrad_kernel,
        grid=(b, h),
        in_specs=x_specs + d_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((3, 3, cin, cout), WGRAD_DTYPE),
        interpret=interpret,
        **kwargs,
    )(xp, xp, xp, *dps)
