"""The composable N-D mesh engine: one rule set, every strategy.

The six hand-written strategy classes (DP/SP/TP/FSDP/MP/DDP_MP plus the
hybrids) all reduce to points in ONE space: an N-D device mesh over the
axes ``('data', 'model', 'stage')`` plus per-tree sharding rules —

``data``
    batch-dimension parallelism. Batches shard their leading axis here;
    gradients reduce over it (the DDP all-reduce — autodiff-inserted for
    GSPMD configs, the explicit schedule-closing psum for pipelined
    ones). The ``fsdp`` params rule additionally shards parameters and
    optimizer state over this axis (ZeRO-3).
``model``
    model-dimension parallelism, in one of two roles: ``channel`` shards
    conv out-channels (Megatron-style TP — parameters and Adam state
    shard on their out-channel axis, XLA inserts the channel
    collectives) and ``spatial`` shards the image H axis (the conv-net
    analogue of sequence parallelism — XLA inserts the per-conv halo
    exchanges). Legacy meshes name this axis by its role (``'model'`` /
    ``'spatial'``) and the engine preserves that naming.
``stage``
    pipeline parallelism: the explicit shard_map schedules of
    parallel/pipeline.py (gpipe / 1f1b) over S stages.

A :class:`MeshConfig` is one point: axis sizes + the params rule + the
batch/LR semantics. Every legacy ``-t`` strategy is a **named alias**
into this space (:data:`LEGACY_PATTERNS`, concrete shapes resolved
against the device pool at build time), and arbitrary points launch as
``-t DxMxS[@rule[+rule]]`` mesh specs — e.g. ``-t 2x2x1`` (DP x TP,
inexpressible under the class-per-strategy design), ``-t 8x1x1@fsdp``
(FSDP), ``-t 2x4x1@sp`` (DDP_SP), ``-t 4x1x2`` (DDP_MP's geometry).

This module is **import-light (no jax at module level)**: the dptlint
contract derivation (analysis/collectives.py), the planner's jax-free
plan-file path, and the elastic supervisor all import it without paying
for a backend. Functions that construct jax objects import lazily.

Execution limits (honest, enforced at strategy construction):
``stage > 1`` composes with ``model > 1`` (channel role) and ``@fsdp``
— the pipeline schedules apply this module's per-tree rules IN-STAGE
(parallel/pipeline.py "In-stage sharding": params enter the shard_map
sharded per-leaf and are reconstructed with tiled all_gathers at the
top of the step). The one remaining refusal is the 'spatial' model
role inside a stage: its conv halo exchanges would have to run inside
every tick's stage-gated cond, which the schedule's ppermute program
cannot carry. The planner records THAT point as an infeasible
``config:`` reject instead of guessing.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: Canonical axis order. The built Mesh drops size-1 axes (a pure-DP
#: mesh is 1-D ``('data',)``, exactly the legacy layout), and the model
#: axis is named by its role.
AXES = ("data", "model", "stage")

#: params-rule vocabulary (how parameters AND Adam state shard):
#:   replicate    — full copy per device (DP/DDP/SP/MP and hybrids);
#:   channel      — out-channel axis over 'model' (TP);
#:   fsdp         — each leaf's largest divisible axis over 'data' (ZeRO-3);
#:   fsdp+channel — both at once (out-channel over 'model', largest
#:                  remaining axis over 'data').
PARAMS_RULES = ("replicate", "channel", "fsdp", "fsdp+channel")

MODEL_ROLES = ("channel", "spatial")

_SPEC_RE = re.compile(r"^(\d+)x(\d+)x(\d+)(?:@([a-z0-9+]+))?$")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """One point in mesh-shape space: axis sizes, sharding rules, and
    the batch/LR semantics the strategy layer reads."""

    data: int = 1
    model: int = 1
    stage: int = 1
    #: what the 'model' axis parallelizes — "channel" (TP) | "spatial" (SP)
    model_role: str = "channel"
    #: how params/opt-state shard — one of PARAMS_RULES
    params: str = "replicate"
    #: torchrun convention (batch_size is PER-PROCESS, global = b x data
    #: rows) vs torch-DP convention (batch_size is the global batch)
    per_process_batch: bool = False
    #: eligible for the reference's lr x world quirk (DDP family only)
    lr_scaling: bool = False
    #: sharded-batch strategies need the batch divisible by 'data'
    drop_last: bool = False

    def __post_init__(self):
        for axis in AXES:
            if int(getattr(self, axis)) < 1:
                raise ValueError(f"mesh axis {axis!r} must be >= 1")
        if self.params not in PARAMS_RULES:
            raise ValueError(
                f"params rule must be one of {PARAMS_RULES}, "
                f"got {self.params!r}"
            )
        if self.model_role not in MODEL_ROLES:
            raise ValueError(
                f"model_role must be one of {MODEL_ROLES}, "
                f"got {self.model_role!r}"
            )

    @property
    def size(self) -> int:
        return int(self.data) * int(self.model) * int(self.stage)

    @property
    def model_axis_name(self) -> str:
        """The model axis carries its ROLE as its mesh name — 'spatial'
        halo exchanges and 'model' channel collectives read differently
        in every trace, and the legacy meshes already named them so."""
        return "spatial" if self.model_role == "spatial" else "model"

    @property
    def is_pipeline(self) -> bool:
        return self.stage > 1


def axis_layout(cfg: MeshConfig) -> Tuple[Tuple[str, int], ...]:
    """((axis name, size), ...) for the axes with size > 1, in canonical
    (data, model, stage) order — the built Mesh's exact layout. Empty
    for the 1x1x1 point (no mesh: single device)."""
    layout: List[Tuple[str, int]] = []
    if cfg.data > 1:
        layout.append(("data", int(cfg.data)))
    if cfg.model > 1:
        layout.append((cfg.model_axis_name, int(cfg.model)))
    if cfg.stage > 1:
        layout.append(("stage", int(cfg.stage)))
    return tuple(layout)


def build_mesh(cfg: MeshConfig, devices: Sequence):
    """The jax Mesh for this config over ``devices`` (first size many),
    or None for the single-device point. Size-1 axes are dropped, so
    every legacy strategy's mesh reproduces its historical layout
    bit-for-bit (same devices, same axis names, same order)."""
    layout = axis_layout(cfg)
    if not layout:
        return None
    import numpy as np
    from jax.sharding import Mesh

    names = tuple(n for n, _ in layout)
    sizes = tuple(s for _, s in layout)
    total = 1
    for s in sizes:
        total *= s
    if len(devices) < total:
        raise ValueError(
            f"mesh {canonical_spec(cfg)} needs {total} devices, "
            f"got {len(devices)}"
        )
    return Mesh(np.array(list(devices[:total])).reshape(sizes), names)


def batch_partition_spec(cfg: MeshConfig):
    """The batch tree's PartitionSpec under this config: leading axis
    over 'data', image H (axis 1) over a spatial model axis, replicated
    otherwise — the one batch rule every strategy used to hand-write."""
    from jax.sharding import PartitionSpec as P

    if cfg.model > 1 and cfg.model_role == "spatial":
        return P("data" if cfg.data > 1 else None, cfg.model_axis_name)
    if cfg.data > 1:
        return P("data")
    return P()


def state_leaf_spec(cfg: MeshConfig, shape):
    """Per-leaf PartitionSpec for params/opt-state under the config's
    params rule. Adam's m/v mirror the param shapes, so one shape-driven
    rule shards both consistently; scalars and indivisible leaves
    replicate (GSPMD handles per-tensor fallback).

    ``channel``: the out-channel (last) axis over 'model' when it
    divides. ``fsdp``: the largest axis that divides 'data'.
    ``fsdp+channel``: channel first, then the largest REMAINING axis
    over 'data' — composable by construction."""
    from jax.sharding import PartitionSpec as P

    ndim = len(shape)
    if ndim == 0:
        return P()
    spec: List[Optional[str]] = [None] * ndim
    rule = cfg.params
    if rule in ("channel", "fsdp+channel") and cfg.model > 1:
        size = int(cfg.model)
        if shape[-1] % size == 0 and shape[-1] >= size:
            spec[-1] = cfg.model_axis_name
    if rule in ("fsdp", "fsdp+channel") and cfg.data > 1:
        size = int(cfg.data)
        axes = sorted(range(ndim), key=lambda i: -shape[i])
        for i in axes:
            if spec[i] is None and shape[i] % size == 0 and shape[i] >= size:
                spec[i] = "data"
                break
    return P(*spec)


# -- mesh-spec grammar -------------------------------------------------------
def is_mesh_spec(name) -> bool:
    """Does this ``-t`` value look like a mesh spec (``DxMxS[@opts]``)?
    Syntactic only — ``parse_mesh_spec`` validates semantics."""
    return isinstance(name, str) and _SPEC_RE.match(name) is not None


def parse_mesh_spec(spec: str) -> MeshConfig:
    """``DxMxS[@opt[+opt]]`` -> MeshConfig. Options: ``tp`` (channel
    model axis, the default), ``sp`` (spatial model axis), ``fsdp``
    (params/opt-state sharded over 'data'). Mesh-spec strategies use the
    multi-process (torchrun/FSDP) batch convention: ``batch_size`` is
    per-process, no DDP lr scaling."""
    m = _SPEC_RE.match(str(spec))
    if m is None:
        raise ValueError(
            f"not a mesh spec: {spec!r} (expected DxMxS[@opt[+opt]], "
            f"e.g. 4x1x2, 2x2x1@fsdp, 1x4x1@sp)"
        )
    data, model, stage = (int(m.group(i)) for i in (1, 2, 3))
    opts = set((m.group(4) or "").split("+")) - {""}
    unknown = opts - {"tp", "sp", "fsdp"}
    if unknown:
        raise ValueError(
            f"mesh spec {spec!r}: unknown option(s) {sorted(unknown)} "
            f"(known: tp, sp, fsdp)"
        )
    if "sp" in opts and "tp" in opts:
        raise ValueError(
            f"mesh spec {spec!r}: the model axis is either spatial (sp) "
            f"or channel (tp), not both"
        )
    if "sp" in opts and model <= 1:
        raise ValueError(
            f"mesh spec {spec!r}: @sp needs a model axis > 1 to shard "
            f"image rows over"
        )
    role = "spatial" if "sp" in opts else "channel"
    if "fsdp" in opts:
        params = "fsdp+channel" if (model > 1 and role == "channel") else "fsdp"
    elif model > 1 and role == "channel":
        params = "channel"
    else:
        params = "replicate"
    return MeshConfig(
        data=data, model=model, stage=stage, model_role=role, params=params,
        per_process_batch=True, lr_scaling=False, drop_last=data > 1,
    )


def canonical_spec(cfg: MeshConfig) -> str:
    """The round-trippable spec string for a config — what checkpoint
    manifests record as ``mesh_spec`` and what docs/tables print."""
    opts = []
    if cfg.model > 1 and cfg.model_role == "spatial":
        opts.append("sp")
    if "fsdp" in cfg.params:
        opts.append("fsdp")
    suffix = ("@" + "+".join(opts)) if opts else ""
    return f"{cfg.data}x{cfg.model}x{cfg.stage}{suffix}"


def spec_is_pipeline(name) -> bool:
    """Does this ``-t`` value name a mesh spec with a stage axis? Cheap
    and non-raising — jax-free callers (the elastic preflight, the
    planner's grid walk) gate schedule enumeration on it."""
    m = _SPEC_RE.match(str(name)) if isinstance(name, str) else None
    return m is not None and int(m.group(3)) > 1


# -- legacy strategies as named points ---------------------------------------
#: Structural pattern of each legacy ``-t`` strategy (axis sizes are
#: placeholders — 2 means "spans devices", resolved concretely at
#: strategy construction; what matters here is WHICH axes exist and
#: which rules apply). Single source for the dptlint contract
#: derivation and the docs' strategy -> mesh-shape table.
LEGACY_PATTERNS: Dict[str, MeshConfig] = {
    "singleGPU": MeshConfig(),
    "DP": MeshConfig(data=2, drop_last=True),
    "DDP": MeshConfig(data=2, per_process_batch=True, lr_scaling=True,
                      drop_last=True),
    "SP": MeshConfig(model=2, model_role="spatial"),
    "DDP_SP": MeshConfig(data=2, model=2, model_role="spatial",
                         per_process_batch=True, lr_scaling=True,
                         drop_last=True),
    "TP": MeshConfig(model=2, params="channel"),
    "FSDP": MeshConfig(data=2, params="fsdp", per_process_batch=True,
                       drop_last=True),
    "MP": MeshConfig(stage=2),
    "DDP_MP": MeshConfig(data=2, stage=2, per_process_batch=True,
                         lr_scaling=True, drop_last=True),
}


# -- contract derivation (the dptlint tables) --------------------------------
def derive_jaxpr_contract(
    cfg: MeshConfig, schedule: Optional[str]
) -> Tuple[Tuple[str, frozenset, bool, str], ...]:
    """The trace-level comms contract a config's train step must
    satisfy, derived from the sharding rules instead of a hand-kept
    table: rows are ``(kind, axes, grad_output, why)`` —
    ``analysis/collectives.JaxprComm``'s field order.

    GSPMD-only configs (no stage axis) have EMPTY jaxpr programs (XLA
    inserts their collectives at compile time; the HLO tier owns them).
    Pipelined configs must show the inter-stage ppermutes and the
    whole-batch stats psum; the 1f1b schedule additionally must show the
    schedule-closing output-feeding gradient psum — whose 'data' axis IS
    the DDP all-reduce on data-hybrid meshes. In-stage-sharded hybrids
    (``model > 1`` channel role, or ``@fsdp`` with ``data > 1``) must
    additionally show the per-step param-reconstruction all_gathers the
    stage bodies run over the sharding axis (parallel/pipeline.py
    ``_gather_params``) — the static checker covers these points
    NON-EXEMPT, same as the flat schedules."""
    if not cfg.is_pipeline:
        return ()
    axes = frozenset({"stage"} | ({"data"} if cfg.data > 1 else set()))
    hybrid = cfg.data > 1
    rows: List[Tuple[str, frozenset, bool, str]] = [
        ("ppermute", frozenset({"stage"}), False,
         "inter-stage activation transfers"
         if schedule == "gpipe" else
         "inter-stage activation/cotangent transfers"),
        ("psum", axes, False,
         "whole-batch loss-stats reduction"
         + (" across stages AND data shards" if hybrid
            and schedule == "gpipe" else "")),
    ]
    if cfg.model > 1 and cfg.model_role == "channel":
        rows.append((
            "all_gather", frozenset({"model"}), False,
            "in-stage channel-TP param reconstruction (gather-at-use, "
            "once per step at the top of the shard_map body)",
        ))
    if "fsdp" in cfg.params and cfg.data > 1:
        rows.append((
            "all_gather", frozenset({"data"}), False,
            "in-stage ZeRO param reconstruction over the data axis "
            "(gather-at-use, once per step)",
        ))
    if schedule == "1f1b":
        rows.append((
            "psum", axes, True,
            "schedule-closing gradient psum — the 'data' axis IS the "
            "DDP all-reduce" if hybrid else
            "schedule-closing gradient assembly across stages",
        ))
    return tuple(rows)


def derive_eval_jaxpr_contract(
    cfg: MeshConfig, schedule: Optional[str]
) -> Tuple[Tuple[str, frozenset, bool, str], ...]:
    """The trace-level comms contract a config's EVAL step must
    satisfy — same row shape as :func:`derive_jaxpr_contract`, derived
    from the same sharding rules. The eval program is the train
    program's forward slice: the inter-stage activation ppermutes and
    the in-stage param-reconstruction all_gathers survive, the
    loss-stats reduction becomes an output-feeding psum over 'stage'
    ONLY (eval stats are reduced across stages but returned per data
    shard — the host averages shards, so no 'data' axis appears even on
    hybrids), and the 1f1b gradient row vanishes with the backward
    pass (eval runs the gpipe-shaped forward under either schedule).
    GSPMD configs stay empty here, same as train."""
    if not cfg.is_pipeline:
        return ()
    rows: List[Tuple[str, frozenset, bool, str]] = [
        ("ppermute", frozenset({"stage"}), False,
         "inter-stage activation transfers (eval forward)"),
        ("psum", frozenset({"stage"}), True,
         "output-feeding eval loss/accuracy-stats reduction across "
         "stages — dropping it ships stage-local metrics as if global"),
    ]
    if cfg.model > 1 and cfg.model_role == "channel":
        rows.append((
            "all_gather", frozenset({"model"}), False,
            "in-stage channel-TP param reconstruction (eval forward "
            "gathers at use, same as train)",
        ))
    if "fsdp" in cfg.params and cfg.data > 1:
        rows.append((
            "all_gather", frozenset({"data"}), False,
            "in-stage ZeRO param reconstruction over the data axis "
            "(eval forward gathers at use, same as train)",
        ))
    return tuple(rows)


def channel_comms_required(cfg: MeshConfig) -> bool:
    """Does this config carry a channel-sharded model axis? Its HLO
    must then show SOME channel collective — XLA picks the mechanism
    per version, so the requirement is the any-of tier
    (analysis/collectives.TP_HLO_ANY_OF), checked IN ADDITION to the
    exact set below: a DP x TP hybrid whose data-axis all-reduce
    regresses away must still fail, any-of satisfied or not."""
    return cfg.model > 1 and cfg.model_role == "channel"


def derive_hlo_contract(cfg: MeshConfig) -> frozenset:
    """Exactly-required optimized-HLO collectives for a config's
    compiled train step, derived from the rules. The channel model
    axis contributes through :func:`channel_comms_required` (the
    any-of tier) instead — its mechanism is XLA's choice — so a pure
    channel-TP config derives an empty exact set here."""
    required = set()
    if cfg.stage > 1:
        required.add("collective-permute")      # ppermute stage transfers
    if cfg.model > 1 and cfg.model_role == "spatial":
        required.add("collective-permute")      # conv halo exchanges
    if cfg.data > 1:
        if "fsdp" in cfg.params:
            required.add("all-gather")          # ZeRO param gathering
        else:
            required.add("all-reduce")          # gradient reduction
    return frozenset(required)
