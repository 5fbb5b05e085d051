"""The planner's analytic cost model: mesh-aware tables turning a point's
static artifacts — XLA ``cost_analysis()`` flops, ``memory_analysis()``
traced liveness, and the extracted ordered collective program — into one
comparable predicted step cost. No jax import: the planner feeds this
module plain numbers, and jax-free consumers (anything reading a plan
file) can import it for the mesh tables alone.

The model is deliberately simple — three additive terms:

``compute_s``
    program flops / the mesh's per-device matmul rate at the point's
    compute dtype. Flops come from ``compiled.cost_analysis()`` of the
    AOT-compiled (never executed) step; under SPMD partitioning the
    compiled module is the per-device program, so the rate is per-device
    too. Backends without ``cost_analysis`` degrade to ``None`` and the
    ranking falls back to the other two terms (the planner's guard).

``hbm_s``
    traced-liveness bytes (``temp + argument + output`` from
    ``memory_analysis()``) / HBM bandwidth, scaled by
    :func:`hbm_pressure` as liveness approaches the ``hbm_gb`` budget.
    This is the **activation-liveness term**: it is what ranks 1F1B
    above GPipe at high microbatch counts — GPipe keeps every
    microbatch's activations live through the drain (PR 4's measured
    3.4× temp-bytes gap at M=8), so at the activation wall its HBM term
    explodes (and past the budget the point is rejected outright) while
    1F1B's stage-bounded in-flight set stays cheap.

``comms_s``
    the per-collective latency/bandwidth table over the collective
    program. For the explicit shard_map schedules (MP/DDP_MP) the
    program comes from the jaxpr — every ppermute/psum with its actual
    per-device payload bytes. GSPMD strategies trace EMPTY jaxpr
    programs (XLA inserts their collectives at compile time), so
    ``mesh_comms_program`` supplies the analytic equivalent, composed
    per mesh axis from the sharding rules (parallel/mesh.py): the data
    axis's gradient all-reduce (or FSDP's per-step parameter
    all-gathers in the **storage** dtype — ``--dtype bf16_params``
    halves these bytes, which is exactly why dtype is a real search
    dimension — plus the gradient reduce-scatter), the spatial model
    axis's per-conv boundary-row halo ppermutes, and the channel model
    axis's per-conv activation all-gathers. Hybrid mesh points
    (DP x TP, FSDP x SP) sum their axes' terms, so they rank honestly
    against pure ones. The legacy ``gspmd_comms_program`` remains as
    the data-axis-only strategy-name surface.

Absolute times are rough; the model exists to RANK points, and every
term is monotone in the quantity it abstracts. Numbers live in
``MESH_MODELS`` (documented approximations, not measurements).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Mapping, Optional, Tuple

#: (kind, payload_bytes, axis_size) — one collective in a comms program.
CommOp = Tuple[str, int, int]


@dataclasses.dataclass(frozen=True)
class MeshModel:
    """Per-device rates for one accelerator target. All values are
    order-of-magnitude datasheet numbers: good enough to rank, never to
    be quoted as a measurement."""

    name: str
    #: compute-dtype name -> matmul FLOP/s per device
    flops_per_s: Mapping[str, float]
    hbm_bytes_per_s: float
    hbm_gb: float
    #: per-link interconnect bandwidth, bytes/s
    ici_bytes_per_s: float
    #: fixed per-collective launch/rendezvous latency, seconds
    collective_latency_s: float

    def flops_rate(self, compute_dtype: str) -> float:
        """Rate for ``compute_dtype`` (falls back to the slowest listed
        rate for dtypes the table doesn't name — conservative)."""
        rate = self.flops_per_s.get(str(compute_dtype))
        return float(rate) if rate else float(min(self.flops_per_s.values()))


#: TPU v5e (the chip-window target): ~197 bf16 TFLOP/s MXU (f32 conv
#: runs the multi-pass path, modeled at half), 16 GB HBM at ~819 GB/s,
#: ICI modeled at 45 GB/s per link with ~1 µs collective latency.
MESH_MODELS: Dict[str, MeshModel] = {
    "tpu_v5e": MeshModel(
        name="tpu_v5e",
        flops_per_s={"bfloat16": 1.97e14, "float32": 9.85e13},
        hbm_bytes_per_s=8.19e11,
        hbm_gb=16.0,
        ici_bytes_per_s=4.5e10,
        collective_latency_s=1e-6,
    ),
}

#: Wire-traffic multiplier per collective kind as a function of the
#: ring factor (n-1)/n; psum (all-reduce) pays reduce-scatter +
#: all-gather, ppermute is a point-to-point shift (payload crosses one
#: link once, concurrently on every edge).
_RING_FACTOR = {
    "psum": 2.0,
    "pmin": 2.0,
    "pmax": 2.0,
    "all_gather": 1.0,
    "reduce_scatter": 1.0,
    "all_to_all": 1.0,
}


def collective_time(kind: str, payload_bytes: int, axis_size: int,
                    mesh: MeshModel) -> float:
    """Predicted seconds for one collective over ``axis_size`` devices.
    Degenerate axes (size <= 1) are free: the collective is a no-op."""
    n = int(axis_size)
    if n <= 1 or payload_bytes <= 0:
        return 0.0
    if kind == "ppermute":
        wire = float(payload_bytes)
    else:
        wire = _RING_FACTOR.get(kind, 1.0) * payload_bytes * (n - 1) / n
    return mesh.collective_latency_s + wire / mesh.ici_bytes_per_s


def comms_summary(program: Iterable[CommOp],
                  mesh: MeshModel) -> Tuple[int, float]:
    """(total payload bytes, total predicted seconds) for a comms
    program — the ordered collective sequence of one step."""
    total_bytes = 0
    total_s = 0.0
    for kind, payload, axis_size in program:
        if int(axis_size) > 1:
            total_bytes += int(payload)
        total_s += collective_time(kind, payload, axis_size, mesh)
    return total_bytes, total_s


def gspmd_comms_program(strategy: str, param_storage_bytes: int,
                        grad_bytes: int, axis_size: int) -> List[CommOp]:
    """Analytic per-step comms for strategies whose collectives are
    GSPMD-inserted (empty jaxpr program). ``param_storage_bytes`` is in
    the policy's STORAGE dtype — the bf16_params halving rides through
    here into FSDP's all-gather term. ``grad_bytes`` is f32 (the stated
    REDUCE_DTYPE contract). Strategies not listed (SP/TP) return
    empty — the planner now routes every config through
    :func:`mesh_comms_program`, which models their halo/channel axes
    too; this strategy-name surface survives for direct callers."""
    n = int(axis_size)
    if n <= 1:
        return []
    if strategy in ("DP", "DDP"):
        return [("psum", grad_bytes, n)]
    if strategy == "FSDP":
        # parameters gathered for the forward AND the backward, grads
        # reduce-scattered — the ZeRO-3 dance GSPMD emits
        return [
            ("all_gather", param_storage_bytes, n),
            ("all_gather", param_storage_bytes, n),
            ("reduce_scatter", grad_bytes, n),
        ]
    return []


#: Conv applications per UNet level entering the halo/channel terms: a
#: DoubleConv on the down path and one on the up path = 4 convs of that
#: level's plane scale. Order-of-magnitude accounting, like every
#: number here.
CONVS_PER_LEVEL = 4


def mesh_comms_program(
    *,
    data: int = 1,
    model: int = 1,
    model_role: str = "channel",
    params_rule: str = "replicate",
    param_storage_bytes: int = 0,
    grad_bytes: int = 0,
    level_planes: Iterable[Tuple[int, int]] = (),
    stage: int = 1,
) -> List[CommOp]:
    """Analytic per-step comms for a mesh config whose collectives are
    GSPMD-inserted (empty jaxpr program) — the rule-engine
    generalization of :func:`gspmd_comms_program`, composing per-axis
    terms so hybrid points (DP x TP, FSDP x SP, ...) rank honestly
    against pure ones:

    * **data axis** — the gradient all-reduce (params replicated) or
      the ZeRO-3 dance (``fsdp`` rules: 2 param all-gathers in the
      STORAGE dtype — bf16_params halves them — plus the f32 gradient
      reduce-scatter);
    * **model axis, ``spatial`` role** — the per-conv halo exchanges:
      one boundary-row ppermute each way per conv application
      (``level_planes`` rows of ``(plane_bytes, row_bytes)`` per UNet
      level, CONVS_PER_LEVEL convs each, forward + backward);
    * **model axis, ``channel`` role** — per-conv channel traffic: the
      next layer contracts over sharded in-channels, so each conv's
      input activation plane is (re)gathered over 'model' — one
      all-gather per conv application, forward + backward. The payload
      is the FULL gathered plane (the all-gather convention every
      other term here uses: ``collective_time``'s ring factor applies
      (n-1)/n to the whole buffer, exactly like the FSDP param
      all-gathers above).

    With ``stage > 1`` the in-stage execution model changes the terms
    (pipeline stages shard params via gather-at-use, parallel/pipeline.py):

    * **model axis, ``channel`` role in-stage** — ONE param all-gather
      per step at the top of the shard_map body (not per-conv activation
      gathers: the stage computes on full params), transposing to one
      gradient reduce-scatter on the backward. Payload is the stage's
      own param slice — ``param_storage_bytes / stage`` — gathered
      concurrently across stages;
    * **data axis with ``fsdp`` in-stage** — the same gather-at-use
      dance over the data axis: one STORAGE-dtype param all-gather plus
      the f32 gradient reduce-scatter (not the flat-mesh 2-gather ZeRO
      shape — the pipeline body gathers once, the vjp transposes it);
    * **data axis, replicated params in-stage** — unchanged: the
      schedule-closing gradient psum simply extends over
      ``('stage', 'data')``.

    These were the planner's ``comms_model: none`` gap: SP/TP (and
    every model-axis hybrid) previously ranked with a silent zero-comms
    advantage. The terms are monotone in what they abstract — never a
    measurement."""
    program: List[CommOp] = []
    d, m, s = int(data), int(model), max(1, int(stage))
    stage_params = param_storage_bytes // s
    stage_grads = grad_bytes // s
    if d > 1:
        if "fsdp" in params_rule:
            if s > 1:
                program += [
                    ("all_gather", stage_params, d),
                    ("reduce_scatter", stage_grads, d),
                ]
            else:
                program += [
                    ("all_gather", param_storage_bytes, d),
                    ("all_gather", param_storage_bytes, d),
                    ("reduce_scatter", grad_bytes, d),
                ]
        else:
            program.append(("psum", grad_bytes, d))
    if m > 1:
        if s > 1 and model_role == "channel":
            # in-stage channel-TP: gather-at-use param reconstruction,
            # once per step, transposed to a grad reduce-scatter
            program += [
                ("all_gather", stage_params, m),
                ("reduce_scatter", stage_grads, m),
            ]
        else:
            for plane_bytes, row_bytes in level_planes:
                for _ in range(2 * CONVS_PER_LEVEL):  # forward + backward
                    if model_role == "spatial":
                        # boundary rows cross one link each way per conv
                        program.append(("ppermute", 2 * int(row_bytes), m))
                    else:
                        program.append(("all_gather", int(plane_bytes), m))
    return program


#: HBM round-trips over the (B·H·W) f32 activation/probability plane
#: that each engaged Pallas kernel FUSES AWAY, per step (the ``kernels``
#: search axis, ops/kernels.py). Order-of-magnitude accounting, like
#: every number here — the model ranks kernel-on vs kernel-off, it does
#: not measure:
#:
#: * ``fused_loss``    — XLA schedules the four loss-stat reductions as
#:   separate fusions over the prob map (forward) plus an elementwise
#:   backward read; the one-pass kernel + analytic VJP reads it once
#:   each way: ~4 plane passes saved.
#: * ``conv_epilogue`` — each DoubleConv BN-normalize + ReLU is two
#:   read+write passes over the conv output, twice per block, folded to
#:   one multiply-add pass (+ the backward's fused dz/dx): ~4 passes of
#:   plane-scale activation traffic saved per step.
#: * ``eval_stats`` / ``serve_mask`` — not on the train step; listed for
#:   completeness (serve_mask's win is D2H bytes, not step HBM).
KERNEL_SAVED_PASSES: Dict[str, float] = {
    "fused_loss": 4.0,
    "conv_epilogue": 4.0,
    "eval_stats": 0.0,
    "serve_mask": 0.0,
}


def kernel_savings_s(kernels: Iterable[str], plane_bytes: int,
                     mesh: MeshModel) -> float:
    """Predicted seconds a ``--kernels pallas`` point saves off its XLA
    twin's step: saved HBM passes × the f32 activation-plane bytes /
    HBM bandwidth. Monotone in what it abstracts (fused traffic), never
    quoted as a measurement."""
    passes = sum(KERNEL_SAVED_PASSES.get(k, 0.0) for k in kernels)
    if passes <= 0 or plane_bytes <= 0:
        return 0.0
    return passes * float(plane_bytes) / mesh.hbm_bytes_per_s


#: The memory-pressure factor saturates here: occupancy beyond ~99% of
#: the budget is the infeasibility cliff, not a finer gradation.
MAX_HBM_PRESSURE = 100.0


def hbm_pressure(live_bytes: Optional[int],
                 hbm_budget_bytes: Optional[int]) -> float:
    """Multiplier on the HBM term as traced liveness approaches the
    budget: ``1 / (1 − occupancy)``, clamped. A step whose liveness
    comfortably fits pays bandwidth only; one crowding the budget pays
    steeply — the static shadow of XLA rematerialization and allocator
    thrash near capacity (the measured gpipe M=8/16-at-batch-4 rows
    that rematted or OOM'd while 1F1B's bounded in-flight set ran
    clean). This is what makes the liveness term RANK, not just gate."""
    if not live_bytes or not hbm_budget_bytes or hbm_budget_bytes <= 0:
        return 1.0
    occupancy = min(float(live_bytes) / float(hbm_budget_bytes),
                    1.0 - 1.0 / MAX_HBM_PRESSURE)
    return 1.0 / (1.0 - occupancy)


def point_cost(mesh: MeshModel, compute_dtype: str, flops: Optional[float],
               live_bytes: Optional[int], comms_s: float,
               hbm_budget_bytes: Optional[int] = None,
               ) -> Dict[str, Optional[float]]:
    """Combine the three terms. Missing inputs (no ``cost_analysis`` on
    this backend, no ``memory_analysis``) drop their term rather than
    poisoning the rank — the result is still monotone in what IS known."""
    compute_s = (
        float(flops) / mesh.flops_rate(compute_dtype)
        if flops and flops > 0 else None
    )
    pressure = hbm_pressure(live_bytes, hbm_budget_bytes)
    hbm_s = (
        float(live_bytes) / mesh.hbm_bytes_per_s * pressure
        if live_bytes and live_bytes > 0 else None
    )
    cost_s = comms_s + sum(t for t in (compute_s, hbm_s) if t is not None)
    return {
        "compute_s": compute_s,
        "hbm_s": hbm_s,
        "hbm_pressure": pressure,
        "comms_s": comms_s,
        "cost_s": cost_s,
    }
