"""Layer 1: the jaxpr collective checker.

Abstractly traces each strategy's train/eval step on the CPU mesh —
``jax.make_jaxpr`` over ``ShapeDtypeStruct`` inputs, so NO device ever
executes anything — then walks the closed jaxpr (descending into
``shard_map``/``pjit``/``scan``/``cond``/``remat`` subjaxprs) to extract
the ordered collective program: every ``psum`` / ``all_gather`` /
``reduce_scatter`` / ``ppermute`` with its axis names and permutation.
Four checks over that program:

(a) **axis binding** — every collective's axis name is bound by the
    enclosing ``shard_map`` mesh; an unbound axis would fail at run time
    (or worse, under ``check_vma=False``, silently misresolve).

(b) **ppermute bijectivity + tick-program deadlock-freedom** — each
    permutation must be a partial bijection (no duplicated sources or
    destinations), and the composed tick program must be deadlock-free.
    Deadlock-freedom is checked by simulating the send/recv schedule per
    stage: the stage that PRODUCES a payload (the ``stage == s`` branch
    of the ``lax.cond`` feeding the ppermute) must appear among the
    permutation's sources, and every stage that CONSUMES the ppermuted
    value (the ``stage == j`` cond it feeds) must appear among the
    destinations. A flipped edge in the 1F1B phase-B program — perm
    ``((e, e+1),)`` where the cotangent producer is stage ``e+1`` —
    leaves stage ``e+1``'s send unposted and stage ``e`` waiting on a
    payload that never arrives: exactly the cyclic wait that hangs the
    CPU rendezvous for 300 s in CI, failed here statically instead.
    Producer/consumer attribution resolves cond predicates of the form
    ``eq(axis_index('stage'), <literal>)``; ppermutes whose endpoints
    don't resolve (e.g. autodiff-transposed gpipe programs) pass through
    unflagged — the check is sound, not complete.

(c) **SPMD rank uniformity** — (i) no collective may sit inside a
    ``cond`` branch whose predicate depends on ``axis_index`` (devices
    along the axis would execute divergent collective sequences); and
    (ii) the step is re-traced under simulated process identities
    (``jax.process_index`` patched to 0 and then 1) and the two
    extracted collective programs must be identical — a ``psum`` guarded
    by an ``if jax.process_index() == 0:`` Python conditional traces
    into rank 0's program only and is flagged here, instead of hanging a
    real 2-process run.

(d) **comms contract** — each strategy's extracted program must satisfy
    its declared contract below. ``EXPECTED_HLO_COLLECTIVES`` (the table
    ``tests/test_hlo_collectives.py`` used to hardcode, now owned here
    and imported by that test) describes the post-GSPMD optimized-HLO
    collectives; ``JAXPR_CONTRACTS`` describes the trace-level program of
    the explicit shard_map schedules, including the schedule-closing
    gradient psum whose 'data' axis IS the DDP all-reduce for DDP_MP —
    dropping it would silently fork the data replicas.

The GSPMD strategies (DP/SP/TP/FSDP) have EMPTY jaxpr-level programs
(XLA inserts their collectives at compile time); their contract lives in
the HLO tier, verified by ``hlo_collectives`` under ``--hlo`` (an AOT
CPU compile — still zero execution) and independently cross-checked by
tests/test_hlo_collectives.py's regex in tier-1.
"""

from __future__ import annotations

import dataclasses
import unittest.mock
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

from distributedpytorch_tpu.analysis import (
    ANALYSIS_SCHEDULES,
    ANALYSIS_STRATEGIES,
    AnalysisEnvironmentError,
    Finding,
    dedupe,
)
# the mesh rule engine (jax-free module): contracts DERIVE from the
# sharding rules instead of a hand-kept table, and ``DxMxS[@rule]``
# mesh specs analyze exactly like the legacy strategy names
from distributedpytorch_tpu.parallel.mesh import (
    LEGACY_PATTERNS,
    channel_comms_required,
    derive_eval_jaxpr_contract,
    derive_hlo_contract,
    derive_jaxpr_contract,
    is_mesh_spec,
    parse_mesh_spec,
    spec_is_pipeline,
)

# -- the tiny analysis rig ---------------------------------------------------
# Same shapes as tests/test_strategies.py's equivalence rig: the analyzer
# exercises the parallelism machinery, where the model is a payload — the
# collective program of the 2-level narrow UNet is structurally identical
# to the reference-sized model's, and traces in ~2 s per combo.
H, W, B = 32, 48, 8
WIDTHS = (8, 16)

#: ANALYSIS_STRATEGIES / ANALYSIS_SCHEDULES live in the jax-free package
#: ``__init__`` (preflight call sites gate on them) and are re-exported
#: here as the checker's defaults.
PIPELINE_STRATEGIES = ("MP", "DDP_MP")

#: Collective primitives extracted from jaxprs.
COLLECTIVE_PRIMS = frozenset(
    {"psum", "ppermute", "all_gather", "reduce_scatter", "all_to_all",
     "pmin", "pmax"}
)

# -- the declared comms contract (check d) -----------------------------------
#: Optimized-HLO collectives each strategy's compiled train step must
#: contain (verified against XLA's output on the 8-device CPU mesh),
#: DERIVED from each strategy's mesh pattern by the sharding-rule engine
#: (parallel/mesh.derive_hlo_contract) — DP's gradient all-reduce, SP's
#: conv halo collective-permutes, FSDP's ZeRO all-gathers, MP/DDP_MP's
#: ppermute stage transfers. This is the single source
#: tests/test_hlo_collectives.py imports; the test keeps its own
#: independent regex over compiled.as_text().
EXPECTED_HLO_COLLECTIVES: Dict[str, FrozenSet[str]] = {
    method: derive_hlo_contract(LEGACY_PATTERNS[method])
    for method in ("DP", "SP", "FSDP", "MP", "DDP_MP")
}
#: TP's sharded-channel layers must communicate somehow; XLA picks the
#: mechanism per version — any of these proves channels are distributed.
#: (mesh.channel_comms_required marks the configs this tier applies to;
#: for channel HYBRIDS it applies IN ADDITION to the derived exact set.)
TP_HLO_ANY_OF = frozenset({"all-to-all", "all-gather", "collective-permute"})


@dataclasses.dataclass(frozen=True)
class JaxprComm:
    """One trace-level contract requirement: a collective of ``kind``
    whose axes cover ``axes`` must exist; ``grad_output=True`` restricts
    candidates to collectives whose results ARE step outputs (the
    schedule-closing gradient reduction), so a stats psum that happens to
    share the axes cannot mask a dropped grad psum."""

    kind: str
    axes: FrozenSet[str]
    grad_output: bool = False
    why: str = ""


def _derived_contract(pattern, schedule) -> Tuple[JaxprComm, ...]:
    """Wrap the rule engine's derived rows into JaxprComm requirements
    (the row tuples are JaxprComm's field order by construction)."""
    return tuple(
        JaxprComm(kind, axes, grad_output, why)
        for kind, axes, grad_output, why in derive_jaxpr_contract(
            pattern, schedule
        )
    )


def _build_contract_table() -> Dict[Tuple[str, Optional[str]], Tuple[JaxprComm, ...]]:
    table: Dict[Tuple[str, Optional[str]], Tuple[JaxprComm, ...]] = {}
    for method in ANALYSIS_STRATEGIES:
        pattern = LEGACY_PATTERNS[method]
        if pattern.is_pipeline:
            for schedule in ANALYSIS_SCHEDULES:
                table[(method, schedule)] = _derived_contract(
                    pattern, schedule
                )
        else:
            table[(method, None)] = _derived_contract(pattern, None)
    return table


#: Trace-level contract per (strategy, schedule), DERIVED from each
#: strategy's mesh pattern by the sharding-rule engine
#: (parallel/mesh.derive_jaxpr_contract) instead of a hand-kept table:
#: pipelined patterns require the inter-stage ppermutes, the whole-batch
#: stats psum over ('stage'[, 'data']), and (1f1b) the schedule-closing
#: output-feeding gradient psum whose 'data' axis IS the DDP all-reduce
#: for DDP_MP — dropping it would silently fork the data replicas.
#: GSPMD strategies derive EMPTY rows (XLA inserts their collectives at
#: compile time) — their contract lives in EXPECTED_HLO_COLLECTIVES.
#: Mesh-spec methods (``4x1x2``) don't need a row here: check_contract
#: derives theirs on the fly from the parsed spec.
JAXPR_CONTRACTS: Dict[Tuple[str, Optional[str]], Tuple[JaxprComm, ...]] = (
    _build_contract_table()
)


def _derived_eval_contract(pattern, schedule) -> Tuple[JaxprComm, ...]:
    """Eval-step rows from the rule engine, as JaxprComm requirements."""
    return tuple(
        JaxprComm(kind, axes, grad_output, why)
        for kind, axes, grad_output, why in derive_eval_jaxpr_contract(
            pattern, schedule
        )
    )


def _build_eval_contract_table(
) -> Dict[Tuple[str, Optional[str]], Tuple[JaxprComm, ...]]:
    table: Dict[Tuple[str, Optional[str]], Tuple[JaxprComm, ...]] = {}
    for method in ANALYSIS_STRATEGIES:
        pattern = LEGACY_PATTERNS[method]
        if pattern.is_pipeline:
            for schedule in ANALYSIS_SCHEDULES:
                table[(method, schedule)] = _derived_eval_contract(
                    pattern, schedule
                )
        else:
            table[(method, None)] = _derived_eval_contract(pattern, None)
    return table


#: Trace-level contract per (strategy, schedule) for the EVAL step,
#: derived by the same rule engine (parallel/mesh.
#: derive_eval_jaxpr_contract): the forward slice of the train program —
#: inter-stage ppermutes, the in-stage param-reconstruction all_gathers,
#: and the output-feeding eval-stats psum over 'stage' ONLY (stats are
#: returned per data shard; no 'data' axis even on hybrids). Before this
#: table, eval traces got structural checks but NO contract: a dropped
#: eval psum shipped stage-local metrics as if they were global and no
#: static gate noticed.
EVAL_JAXPR_CONTRACTS: Dict[Tuple[str, Optional[str]],
                           Tuple[JaxprComm, ...]] = (
    _build_eval_contract_table()
)


# -- extraction --------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Collective:
    """One extracted collective with everything the checks need."""

    kind: str
    axes: Tuple[object, ...]          # axis names (strs; ints under vmap)
    perm: Optional[Tuple[Tuple[int, int], ...]]
    context: Tuple[str, ...]          # enclosing-eqn path, e.g. (pjit, shard_map)
    bound_axes: FrozenSet[str]        # mesh axes in scope at this point
    producer_stage: Optional[int]     # stage whose cond branch made the input
    consumer_stages: Tuple[int, ...]  # stages whose conds consume the output
    direct_output: bool               # results are body outputs (grad psum)
    axis_guarded: bool                # inside an axis_index-dependent branch
    payload_bytes: int = 0            # summed operand aval bytes (per device)

    @property
    def signature(self) -> Tuple:
        """Order-sensitive identity for rank-invariance comparison."""
        return (self.kind, self.axes, self.perm, self.context)


def _subjaxprs(value) -> List:
    """Jaxpr objects reachable from one eqn param value (ClosedJaxpr,
    Jaxpr, or tuples of either — cond branches, scan bodies, ...)."""
    # ClosedJaxpr proxies .eqns, so unwrap .jaxpr FIRST (the walker needs
    # the raw Jaxpr's outvars for the direct-output attribution)
    if hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        return [value.jaxpr]
    if hasattr(value, "eqns"):
        return [value]
    if isinstance(value, (tuple, list)):
        out = []
        for v in value:
            out.extend(_subjaxprs(v))
        return out
    return []


def _is_literal(v) -> bool:
    return hasattr(v, "val") and not hasattr(v, "count")


def _payload_bytes(eqn) -> int:
    """Summed operand abstract-value bytes of one collective eqn —
    inside a ``shard_map`` body the avals are per-device shard shapes,
    so this is the per-device payload the planner's comms tables want."""
    total = 0
    for var in eqn.invars:
        aval = getattr(var, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        size = 1
        for dim in shape:
            size *= int(dim)
        total += size * dtype.itemsize
    return int(total)


def _body_attribution(jaxpr):
    """Per-body maps for producer/consumer attribution and axis-guard
    detection: which vars come from ``cond(eq(axis_index(ax), s), ...)``
    branches, which conds consume which vars, and which cond predicates
    depend on ``axis_index`` at all."""
    producer_eqn: Dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for ov in eqn.outvars:
            producer_eqn[ov] = i
    axis_vars = {
        e.outvars[0]: e.params["axis_name"]
        for e in jaxpr.eqns
        if e.primitive.name == "axis_index"
    }

    def resolve_stage(var, depth=0):
        """cond index var -> (axis, stage) when the predicate is
        ``eq(axis_index(axis), literal)`` (possibly through dtype
        conversions)."""
        if _is_literal(var) or var not in producer_eqn or depth > 6:
            return None
        eqn = jaxpr.eqns[producer_eqn[var]]
        name = eqn.primitive.name
        if name == "convert_element_type":
            return resolve_stage(eqn.invars[0], depth + 1)
        if name == "eq":
            a, b = eqn.invars
            for x, y in ((a, b), (b, a)):
                if (not _is_literal(x) and x in axis_vars
                        and _is_literal(y)):
                    return (axis_vars[x], int(y.val))
        return None

    def depends_on_axis(var, depth=0):
        """Does this var transitively derive from an axis_index?"""
        if _is_literal(var) or var not in producer_eqn or depth > 8:
            return False
        if var in axis_vars:
            return True
        eqn = jaxpr.eqns[producer_eqn[var]]
        return any(
            depends_on_axis(iv, depth + 1)
            for iv in eqn.invars
            if not _is_literal(iv)
        )

    cond_stage: Dict[int, Tuple[str, int]] = {}
    cond_axis_dep: Dict[int, bool] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name != "cond":
            continue
        idx = eqn.invars[0]
        resolved = resolve_stage(idx)
        if resolved is not None:
            cond_stage[i] = resolved
            cond_axis_dep[i] = True
        else:
            cond_axis_dep[i] = (
                False if _is_literal(idx) else depends_on_axis(idx)
            )

    outvar_stage: Dict = {}
    for i, (_ax, stage) in cond_stage.items():
        for ov in jaxpr.eqns[i].outvars:
            outvar_stage[ov] = stage
    consumers: Dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        if i in cond_stage:
            for iv in eqn.invars:
                if not _is_literal(iv):
                    consumers.setdefault(iv, []).append(cond_stage[i][1])
    return producer_eqn, outvar_stage, consumers, cond_axis_dep


def extract_collectives(closed_jaxpr) -> List[Collective]:
    """Walk a ClosedJaxpr (and every reachable subjaxpr) and return its
    ordered collective program."""
    out: List[Collective] = []

    def walk(jaxpr, context, bound_axes, guarded):
        _prod, outvar_stage, consumers, cond_axis_dep = _body_attribution(
            jaxpr
        )
        body_outs = {
            v for v in jaxpr.outvars if not _is_literal(v)
        }
        # output-feeding closure through pure slicing/layout eqns: the
        # in-stage-sharded 1f1b schedule slices each gradient leaf down
        # to the device's own shard AFTER the schedule-closing psum
        # (parallel/pipeline._slice_to_shard), so the psum's results
        # reach the body outputs through a dynamic_slice — that still
        # counts as output-feeding for the grad_output contract rows.
        # Only the sliced operand (invars[0]) passes through; index
        # operands do not.
        pass_through = {
            "dynamic_slice", "slice", "squeeze", "reshape",
            "transpose", "convert_element_type",
        }
        changed = True
        while changed:
            changed = False
            for eqn in jaxpr.eqns:
                if eqn.primitive.name not in pass_through:
                    continue
                if not any(ov in body_outs for ov in eqn.outvars):
                    continue
                src = eqn.invars[0]
                if not _is_literal(src) and src not in body_outs:
                    body_outs.add(src)
                    changed = True
        for i, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                params = eqn.params
                raw_axes = params.get("axes", params.get("axis_name", ()))
                if not isinstance(raw_axes, (tuple, list)):
                    raw_axes = (raw_axes,)
                perm = params.get("perm")
                if perm is not None:
                    perm = tuple((int(a), int(b)) for a, b in perm)
                out.append(
                    Collective(
                        kind=name,
                        axes=tuple(raw_axes),
                        perm=perm,
                        context=context,
                        bound_axes=bound_axes,
                        producer_stage=outvar_stage.get(eqn.invars[0])
                        if eqn.invars else None,
                        consumer_stages=tuple(
                            consumers.get(eqn.outvars[0], ())
                        ) if eqn.outvars else (),
                        direct_output=any(
                            ov in body_outs for ov in eqn.outvars
                        ),
                        axis_guarded=guarded,
                        payload_bytes=_payload_bytes(eqn),
                    )
                )
                continue
            sub_bound = bound_axes
            if name == "shard_map":
                mesh = eqn.params.get("mesh")
                axis_names = tuple(getattr(mesh, "axis_names", ()) or ())
                sub_bound = bound_axes | frozenset(
                    a for a in axis_names if isinstance(a, str)
                )
            sub_guarded = guarded or (
                name == "cond" and cond_axis_dep.get(i, False)
            )
            for key, value in eqn.params.items():
                for sub in _subjaxprs(value):
                    walk(sub, context + (name,), sub_bound, sub_guarded)

    walk(closed_jaxpr.jaxpr, (), frozenset(), False)
    return out


# -- abstract tracing --------------------------------------------------------
def _require_devices(n: int) -> None:
    import jax

    have = len(jax.devices())
    if have < n:
        raise AnalysisEnvironmentError(
            f"the analyzer needs >= {n} devices (an 8-device virtual CPU "
            f"mesh; the analyze CLI self-provisions one), got {have}"
        )


def _rig_batch(method: str) -> int:
    """The analysis rig's batch for one method: B, rounded UP to the
    nearest multiple a mesh spec's data axis (x microbatches, when
    pipelined) requires — odd geometries like ``3x1x2`` must trace,
    not refuse on the rig's own batch choice."""
    if not is_mesh_spec(method):
        return B
    cfg = parse_mesh_spec(method)
    unit = max(cfg.data, 1) * (2 if cfg.stage > 1 else 1)
    return ((B + unit - 1) // unit) * unit


def _tiny_config(method: str, schedule: Optional[str]):
    from distributedpytorch_tpu.config import TrainConfig

    return TrainConfig(
        train_method=method,
        batch_size=_rig_batch(method),
        compute_dtype="float32",
        image_size=(W, H),
        model_widths=WIDTHS,
        pipeline_schedule=schedule or "gpipe",
    )


def _build(method: str, schedule: Optional[str]):
    """(strategy, model, abstract_state, tx, abstract_batch) for one
    combo — everything ShapeDtypeStructs; nothing placed, nothing run."""
    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.models.unet import UNet
    from distributedpytorch_tpu.ops.optim import adam_l2
    from distributedpytorch_tpu.parallel import build_strategy
    from distributedpytorch_tpu.train.steps import TrainState

    if is_mesh_spec(method):
        _require_devices(parse_mesh_spec(method).size)
    else:
        _require_devices(8 if method in ("DDP_MP", "DDP_SP") else 2)
    cfg = _tiny_config(method, schedule)
    strategy = build_strategy(cfg)
    model = UNet(dtype=jnp.float32, widths=WIDTHS)
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, H, W, 3)))["params"],
        jax.random.key(0),
    )
    tx = adam_l2(cfg.learning_rate, cfg.weight_decay)
    opt_state = jax.eval_shape(tx.init, params)
    state = TrainState(
        params=params,
        opt_state=opt_state,
        step=jax.ShapeDtypeStruct((), jnp.int32),
        model_state=None,
    )
    nb = _rig_batch(method)
    batch = {
        "image": jax.ShapeDtypeStruct((nb, H, W, 3), jnp.float32),
        "mask": jax.ShapeDtypeStruct((nb, H, W), jnp.int32),
    }
    return strategy, model, state, tx, batch


def trace_train(method: str, schedule: Optional[str] = None):
    """The strategy's (unjitted) train step as a ClosedJaxpr — a fresh
    build per call, so repeated traces (the simulated-rank check) never
    reuse a cached jaxpr from a previous identity."""
    import jax

    strategy, model, state, tx, batch = _build(method, schedule)
    step = strategy._raw_step(model, tx)
    return jax.make_jaxpr(step)(state, batch)


def trace_eval(method: str, schedule: Optional[str] = None):
    """The strategy's jitted eval step as a ClosedJaxpr."""
    import jax

    strategy, model, state, _tx, batch = _build(method, schedule)
    eval_step = strategy.build_eval_step(model)
    return jax.make_jaxpr(eval_step)(state.params, batch)


# -- serve forwards ----------------------------------------------------------
#: Every forward the serve engine AOT-compiles per bucket: plain f32,
#: the ``--quantize int8`` weights-quantized path, the ``--kernels
#: pallas`` fused sigmoid-threshold mask head, and their combination.
#: All four must trace COLLECTIVE-FREE: serve replicas are independent
#: (replicated or single-device), so any collective reaching a serve
#: executable would block on peers that are serving other requests —
#: a fleet-wide deadlock the first time that bucket is hit.
SERVE_VARIANTS: Tuple[str, ...] = ("float", "int8", "pallas", "int8+pallas")

#: Batch sizes traced per variant — the smallest and largest default
#: bucket; the collective program must be bucket-size invariant.
SERVE_TRACE_BATCHES: Tuple[int, ...] = (1, 8)


def _abstract_quantized(params):
    """The abstract image of ``ops/quant.quantize_tree`` over a params
    tree of ShapeDtypeStructs. quantize_tree itself is host-side numpy
    (it materializes scales), so it cannot run under tracing — this
    mirrors its structure instead: every >=2-D float leaf becomes a
    ``{q: int8[shape], scale: f32[1,...,1,C]}`` pair (per-out-channel
    scales, keepdims), other float leaves stay f32. Must be kept in
    lockstep with ``quantize_leaf``/``QUANT_KIND``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def walk(node):
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if len(node.shape) >= 2 and np.issubdtype(node.dtype, np.floating):
            scale_shape = (1,) * (len(node.shape) - 1) + (node.shape[-1],)
            return {
                "q": jax.ShapeDtypeStruct(node.shape, jnp.int8),
                "scale": jax.ShapeDtypeStruct(scale_shape, jnp.float32),
            }
        if np.issubdtype(node.dtype, np.floating):
            return jax.ShapeDtypeStruct(node.shape, jnp.float32)
        return node

    return walk(params)


def _serve_rig(variant: str, batch: int):
    """(forward_fn, abstract_variables, abstract_input) for one serve
    variant — the exact function the engine jits per replica
    (serve/infer.make_forward), over ShapeDtypeStructs only."""
    import flax.serialization
    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.models.unet import UNet
    from distributedpytorch_tpu.serve.infer import make_forward

    if variant not in SERVE_VARIANTS:
        raise ValueError(
            f"unknown serve variant {variant!r}; expected one of "
            f"{SERVE_VARIANTS}"
        )
    model = UNet(dtype=jnp.float32, widths=WIDTHS)
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, H, W, 3)))["params"],
        jax.random.key(0),
    )
    quantized = "int8" in variant
    kw = {}
    if quantized:
        kw["quantized"] = True
        params = _abstract_quantized(
            flax.serialization.to_state_dict(params)
        )
    if "pallas" in variant:
        kw["mask_threshold"] = 0.5
    fwd = make_forward(model, **kw)
    x = jax.ShapeDtypeStruct((batch, H, W, 3), jnp.float32)
    return fwd, {"params": params}, x


def trace_serve(variant: str, batch: int = 1):
    """One serve variant's per-bucket forward as a ClosedJaxpr."""
    import jax

    fwd, variables, x = _serve_rig(variant, batch)
    return jax.make_jaxpr(fwd)(variables, x)


def check_serve_collective_free(
    variants: Sequence[str] = SERVE_VARIANTS,
) -> Tuple[List[Finding], List[str]]:
    """Trace every serve variant at the smallest and largest default
    bucket and require a collective-free program. Returns
    ``(findings, tags)`` — one tag per traced (variant, bucket)."""
    findings: List[Finding] = []
    tags: List[str] = []
    for variant in variants:
        for batch in SERVE_TRACE_BATCHES:
            where = f"serve {variant} forward (bucket {batch})"
            tags.append(where)
            colls = extract_collectives(trace_serve(variant, batch))
            if colls:
                kinds = sorted({c.kind for c in colls})
                findings.append(Finding(
                    rule="serve-collective",
                    where=where,
                    message=(
                        f"{len(colls)} collective(s) ({', '.join(kinds)}) "
                        f"leaked into a serve executable — serve replicas "
                        f"are independent, so a collective blocks on peers "
                        f"serving other requests and deadlocks the fleet "
                        f"the first time this bucket is hit"
                    ),
                    layer="collectives",
                ))
    return dedupe(findings), tags


def check_serve_hlo(variant: str, batch: int = 1) -> List[Finding]:
    """The ``--hlo`` tier for serve: AOT-compile one variant's bucket
    forward (GSPMD runs, nothing executes) and require the OPTIMIZED
    HLO to be collective-free too — XLA must not have introduced one
    behind the trace's back."""
    import jax

    fwd, variables, x = _serve_rig(variant, batch)
    compiled = jax.jit(fwd).lower(variables, x).compile()
    text = compiled.as_text()
    ops = {name for name in _HLO_COLLECTIVE_NAMES if name in text}
    if not ops:
        return []
    return [Finding(
        rule="serve-collective-hlo",
        where=f"serve {variant} forward (bucket {batch})",
        message=(
            f"optimized HLO contains {sorted(ops)} — the compiled serve "
            f"executable communicates; replicas must compile to "
            f"collective-free programs"
        ),
        layer="collectives",
    )]


def analyze_serve(variants: Sequence[str] = SERVE_VARIANTS,
                  hlo: bool = False) -> Tuple[List[Finding], List[str]]:
    """Every serve-variant check: trace-level collective-freedom, plus
    the compiled-HLO tier when ``hlo``."""
    findings, tags = check_serve_collective_free(variants)
    if hlo:
        for variant in variants:
            findings += check_serve_hlo(variant)
    return dedupe(findings), tags


# -- checks ------------------------------------------------------------------
def _combo_tag(method: str, schedule: Optional[str], kind: str) -> str:
    sched = f"/{schedule}" if schedule else ""
    return f"{method}{sched} {kind} step"


def check_axis_binding(colls, where: str) -> List[Finding]:
    findings = []
    for c in colls:
        unbound = [
            a for a in c.axes if isinstance(a, str) and a not in c.bound_axes
        ]
        if unbound:
            findings.append(Finding(
                rule="unbound-axis",
                where=where,
                message=(
                    f"{c.kind} names axis {unbound} but the enclosing mesh "
                    f"binds only {sorted(c.bound_axes)} — the collective "
                    f"cannot resolve at run time"
                ),
                layer="collectives",
            ))
    return findings


def check_ppermute_flow(colls, where: str) -> List[Finding]:
    """Bijectivity plus the send/recv simulation (docstring check b)."""
    findings = []
    for c in colls:
        if c.kind != "ppermute" or c.perm is None:
            continue
        srcs = [a for a, _ in c.perm]
        dsts = [b for _, b in c.perm]
        if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
            findings.append(Finding(
                rule="ppermute-bijection",
                where=where,
                message=(
                    f"ppermute perm {c.perm} is not a partial bijection "
                    f"(duplicate source or destination) — results are "
                    f"undefined"
                ),
                layer="collectives",
            ))
            continue
        src_set, dst_set = set(srcs), set(dsts)
        if c.producer_stage is not None and c.producer_stage not in src_set:
            findings.append(Finding(
                rule="ppermute-deadlock",
                where=where,
                message=(
                    f"tick-program deadlock: the payload is produced under "
                    f"the stage=={c.producer_stage} branch but ppermute "
                    f"perm {c.perm} never sends from stage "
                    f"{c.producer_stage} — its send is unposted and the "
                    f"receiving stage waits forever (flipped edge? the "
                    f"dynamic symptom is the 300 s CPU-rendezvous hang)"
                ),
                layer="collectives",
            ))
        for j in c.consumer_stages:
            if j not in dst_set:
                findings.append(Finding(
                    rule="ppermute-deadlock",
                    where=where,
                    message=(
                        f"tick-program deadlock: stage {j} consumes this "
                        f"ppermute's output but perm {c.perm} never "
                        f"delivers to stage {j} — unmatched recv; stage "
                        f"{j} would block on a payload that never arrives"
                    ),
                    layer="collectives",
                ))
    return findings


def check_uniform_branches(colls, where: str) -> List[Finding]:
    findings = []
    for c in colls:
        if c.axis_guarded:
            findings.append(Finding(
                rule="branch-divergent-collective",
                where=where,
                message=(
                    f"{c.kind} over {c.axes} sits inside a cond branch "
                    f"whose predicate depends on axis_index — devices "
                    f"along the axis would execute divergent collective "
                    f"sequences (rendezvous deadlock); hoist the "
                    f"collective out of the branch"
                ),
                layer="collectives",
            ))
    return findings


def _is_pipeline_method(method: str) -> bool:
    """Does this method (legacy name OR mesh spec) run the explicit
    stage schedules — i.e. does the schedule axis apply to it?"""
    return method in PIPELINE_STRATEGIES or spec_is_pipeline(method)


def _contract_requirements(
    method: str, schedule: Optional[str]
) -> Tuple[JaxprComm, ...]:
    """The comms contract for one method: the derived legacy table for
    strategy names, derived on the fly from the parsed spec for mesh
    configs — one rule engine either way."""
    if is_mesh_spec(method):
        cfg = parse_mesh_spec(method)
        return _derived_contract(cfg, schedule if cfg.is_pipeline else None)
    key = (method, schedule if method in PIPELINE_STRATEGIES else None)
    return JAXPR_CONTRACTS.get(key, ())


def _eval_contract_requirements(
    method: str, schedule: Optional[str]
) -> Tuple[JaxprComm, ...]:
    """The EVAL-step comms contract for one method — same resolution
    rule as :func:`_contract_requirements`, eval table/derivation."""
    if is_mesh_spec(method):
        cfg = parse_mesh_spec(method)
        return _derived_eval_contract(
            cfg, schedule if cfg.is_pipeline else None
        )
    key = (method, schedule if method in PIPELINE_STRATEGIES else None)
    return EVAL_JAXPR_CONTRACTS.get(key, ())


def check_contract(method: str, schedule: Optional[str], colls,
                   where: str, requirements=None) -> List[Finding]:
    """Enforce a derived comms contract against an extracted collective
    program. ``requirements`` defaults to the train-step contract;
    ``analyze_combo`` passes the eval table for eval traces."""
    findings = []
    if requirements is None:
        requirements = _contract_requirements(method, schedule)
    for req in requirements:
        candidates = [
            c for c in colls
            if c.kind == req.kind
            and (not req.grad_output or c.direct_output)
            and req.axes <= {a for a in c.axes if isinstance(a, str)}
        ]
        if not candidates:
            what = "output-feeding " if req.grad_output else ""
            findings.append(Finding(
                rule="comms-contract",
                where=where,
                message=(
                    f"declared contract violated: no {what}{req.kind} over "
                    f"axes covering {sorted(req.axes)} in the traced "
                    f"program ({req.why}) — "
                    + (
                        "a missing 'data' reduction silently forks the "
                        "data replicas"
                        if "data" in req.axes else
                        "the strategy degenerated from its declared "
                        "communication pattern"
                    )
                ),
                layer="collectives",
            ))
    return findings


def check_rank_invariance(method: str, schedule: Optional[str],
                          base_signatures) -> List[Finding]:
    """Re-trace the train step with ``jax.process_index`` patched to 1
    and diff the collective program against the rank-0 trace
    (``base_signatures``). Any difference means a Python-level
    rank-dependent branch reached a collective: the program is not
    provably SPMD-uniform."""
    import jax

    with unittest.mock.patch.object(jax, "process_index", lambda: 1):
        other = [c.signature for c in extract_collectives(
            trace_train(method, schedule))]
    if list(base_signatures) == other:
        return []
    n0, n1 = len(base_signatures), len(other)
    diff_at = next(
        (i for i, (a, b) in enumerate(zip(base_signatures, other)) if a != b),
        min(n0, n1),
    )
    return [Finding(
        rule="rank-divergent-collective",
        where=_combo_tag(method, schedule, "train"),
        message=(
            f"collective program differs between simulated ranks (rank 0: "
            f"{n0} collectives, rank 1: {n1}; first divergence at program "
            f"position {diff_at}) — a collective is guarded by a "
            f"process_index()/rank Python conditional, so real ranks would "
            f"trace different programs and deadlock at the first unmatched "
            f"collective; make the collective sequence rank-invariant"
        ),
        layer="collectives",
    )]


# -- collective fingerprints (the multi-process preflight's desync gate) ----
def program_fingerprint(colls: Sequence) -> str:
    """A short stable hash of an ORDERED collective program — kind,
    axes, permutation, enclosing-eqn context, and per-device payload
    bytes of every collective, in program order. The one definition
    shared by the multi-process desync gate (same program on every
    rank) and the planner's per-point provenance stamp (same program
    the plan was built from — the ``stale-plan`` rule's comparator)."""
    import hashlib

    payload = repr([(c.signature, c.payload_bytes) for c in colls])
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def collective_fingerprint(method: str, schedule: Optional[str] = None,
                           process_index: int = 0) -> str:
    """One combo's :func:`program_fingerprint`, traced under the given
    simulated process identity. Two ranks whose fingerprints differ
    would trace different programs in a real launch and desync the
    gloo rendezvous at the first unmatched collective."""
    import jax

    with unittest.mock.patch.object(
        jax, "process_index", lambda: int(process_index)
    ):
        colls = extract_collectives(trace_train(method, schedule))
    return program_fingerprint(colls)


def check_collective_fingerprints(
    method: str, schedule: Optional[str], world: int
) -> Tuple[List[Finding], List[str]]:
    """Fingerprint one combo under ``world`` simulated ranks and flag
    any divergence (rule ``collective-fingerprint``). This generalizes
    the dual-rank re-trace to the job's ACTUAL world size: a collective
    gated on ``process_index() == 2`` traces identically on ranks 0 and
    1 — invisible to ``rank-divergent-collective`` — but desyncs a
    3-process launch; here it is caught before any rank spawns."""
    if _is_pipeline_method(method) and schedule is None:
        schedule = "gpipe"
    fps = [
        collective_fingerprint(method, schedule, r) for r in range(world)
    ]
    if len(set(fps)) <= 1:
        return [], fps
    divergent = sorted({r for r in range(world) if fps[r] != fps[0]})
    return [Finding(
        rule="collective-fingerprint",
        where=_combo_tag(method, schedule, "train"),
        message=(
            f"ordered-collective fingerprint diverges at simulated "
            f"rank(s) {divergent} of world {world} (rank 0: {fps[0]}) — "
            f"a Python-level rank conditional reaches a collective on "
            f"only some ranks, so a real {world}-process launch would "
            f"desync the gloo rendezvous at the first unmatched "
            f"collective; make the program identical on every rank"
        ),
        layer="collectives",
    )], fps


def fingerprint_combos(
    strategies: Sequence[str] = ANALYSIS_STRATEGIES,
    schedules: Sequence[str] = ANALYSIS_SCHEDULES,
    world: int = 2,
) -> Tuple[List[Finding], Dict[str, List[str]]]:
    """(findings, {combo tag: [per-rank fingerprint]}) for every
    requested combo — what ``analyze --fingerprint-world N`` reports and
    the elastic launch preflight compares before an N-process spawn.

    Accepted cost: the rank-0 trace here duplicates the one
    ``analyze_combo`` already ran in the same analyzer invocation (~2 s
    per combo). The preflight scopes to ONE combo, so the overlap stays
    a couple of seconds of its 300 s budget; reusing the program would
    mean threading extraction results through ``analyze``'s public
    return, which isn't worth it at this cost."""
    findings: List[Finding] = []
    table: Dict[str, List[str]] = {}
    for method, schedule in combos_for(strategies, schedules):
        tag = f"{method}/{schedule}" if schedule else method
        combo_findings, fps = check_collective_fingerprints(
            method, schedule, world
        )
        findings += combo_findings
        table[tag] = fps
    return dedupe(findings), table


# -- fingerprint snapshots (the cross-upgrade drift gate) --------------------
#: Artifact schema version; bump on incompatible payload changes.
SNAPSHOT_VERSION = 1


def _parse_combo_tag(tag: str) -> Tuple[str, Optional[str]]:
    """Invert ``fingerprint_combos``' combo tag: ``'MP/gpipe'`` →
    ``('MP', 'gpipe')``, ``'DP'`` → ``('DP', None)``. Methods never
    contain ``/`` (legacy names and ``DxMxS[@rule]`` specs alike)."""
    if "/" in tag:
        method, schedule = tag.rsplit("/", 1)
        return method, schedule
    return tag, None


def snapshot_fingerprints(
    strategies: Sequence[str] = ANALYSIS_STRATEGIES,
    schedules: Sequence[str] = ANALYSIS_SCHEDULES,
) -> dict:
    """The snapshot payload: every combo's rank-0 ordered-collective
    fingerprint plus the toolchain identity it was traced under. Written
    BEFORE a jax upgrade and checked after: a program that silently
    changed shape across the upgrade (a collective reordered, dropped,
    or re-axised by new tracing behavior) is exactly the drift the
    per-run contract check cannot see — both sides of the upgrade can be
    internally consistent yet different. Hybrid mesh specs fingerprint
    through the same surface (pass them in ``strategies``, as the CLI's
    ``--mesh`` merge does)."""
    import jax
    import jaxlib

    fingerprints: Dict[str, str] = {}
    for method, schedule in combos_for(strategies, schedules):
        tag = f"{method}/{schedule}" if schedule else method
        fingerprints[tag] = collective_fingerprint(method, schedule)
    return {
        "version": SNAPSHOT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "fingerprints": fingerprints,
    }


def write_fingerprint_snapshot(
    path: str,
    strategies: Sequence[str] = ANALYSIS_STRATEGIES,
    schedules: Sequence[str] = ANALYSIS_SCHEDULES,
) -> dict:
    """Trace, fingerprint, and persist — returns the written payload."""
    import json

    payload = snapshot_fingerprints(strategies, schedules)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return payload


def load_fingerprint_snapshot(path: str) -> Optional[dict]:
    """The persisted payload, or None when missing/corrupt/version-skewed
    — callers treat None as a bad invocation (rc 2), never as clean."""
    import json

    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict):
        return None
    if payload.get("version") != SNAPSHOT_VERSION:
        return None
    if not isinstance(payload.get("fingerprints"), dict):
        return None
    return payload


def check_fingerprint_snapshot(payload: dict) -> List[Finding]:
    """Re-trace every combo a snapshot records and flag drift (rule
    ``fingerprint-snapshot``): the current toolchain traces a DIFFERENT
    ordered-collective program than the one recorded — after a jax
    upgrade this is the audit trigger, not necessarily a bug, but it
    must never pass silently. Combos that no longer trace at all are
    flagged too (a refusal appearing where a program used to be is the
    loudest possible drift)."""
    import jax

    recorded_jax = payload.get("jax", "unknown")
    current_jax = jax.__version__
    toolchain = (
        f"recorded under jax {recorded_jax}, current jax {current_jax}"
    )
    findings: List[Finding] = []
    for tag in sorted(payload["fingerprints"]):
        recorded = payload["fingerprints"][tag]
        method, schedule = _parse_combo_tag(tag)
        try:
            current = collective_fingerprint(method, schedule)
        except Exception as exc:  # noqa: BLE001 — refusal IS the drift
            findings.append(Finding(
                rule="fingerprint-snapshot",
                where=_combo_tag(method, schedule, "train"),
                message=(
                    f"combo no longer traces ({type(exc).__name__}: "
                    f"{exc}) — {toolchain}; if the combo was removed "
                    f"on purpose, re-write the snapshot"
                ),
                layer="collectives",
            ))
            continue
        if current != recorded:
            findings.append(Finding(
                rule="fingerprint-snapshot",
                where=_combo_tag(method, schedule, "train"),
                message=(
                    f"ordered-collective fingerprint drifted: recorded "
                    f"{recorded} != current {current} ({toolchain}) — "
                    f"the traced program changed shape across the "
                    f"toolchain change; audit the program diff, then "
                    f"re-write the snapshot to accept it"
                ),
                layer="collectives",
            ))
    return dedupe(findings)


# -- HLO tier (opt-in: AOT compile, still zero execution) --------------------
_HLO_COLLECTIVE_NAMES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)


def compile_train_step_aot(strategy, model, tx, state, batch):
    """AOT-compile the strategy's jitted train step over sharding-pinned
    ``ShapeDtypeStruct``s — the GSPMD partitioner runs, nothing executes,
    no device memory is committed. THE pin-and-compile rig, shared by the
    ``--hlo`` contract tier here and the auto-planner's memory/flops
    probe (analysis/planner.py): a change to how a strategy's state or
    batch shardings are pinned must reach both, or plans would silently
    rank a wrongly-pinned program."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = strategy.mesh
    if mesh is not None:
        leaf_spec = getattr(strategy, "_leaf_spec", lambda shape: P())

        def with_sharding(leaf, spec):
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
            )

        state = jax.tree.map(
            lambda x: with_sharding(x, leaf_spec(x.shape)), state
        )
        batch = {
            k: with_sharding(v, strategy.batch_sharding.spec)
            for k, v in batch.items()
        }
    return strategy.build_train_step(model, tx).lower(state, batch).compile()


def hlo_collectives(method: str, schedule: Optional[str] = None) -> set:
    """Collective op names in the optimized HLO of the strategy's
    compiled train step (ahead-of-time via ``compile_train_step_aot``)."""
    strategy, model, state, tx, batch = _build(method, schedule)
    if strategy.mesh is None:
        return set()
    compiled = compile_train_step_aot(strategy, model, tx, state, batch)
    text = compiled.as_text()
    return {name for name in _HLO_COLLECTIVE_NAMES if name in text}


def check_hlo_contract(method: str, schedule: Optional[str]) -> List[Finding]:
    where = _combo_tag(method, schedule, "compiled train")
    ops = hlo_collectives(method, schedule)
    required = EXPECTED_HLO_COLLECTIVES.get(method)
    any_of_tier = method == "TP"
    if required is None and is_mesh_spec(method):
        # mesh specs derive their HLO contract from the parsed rules;
        # a channel model axis adds the any-of tier ON TOP of the exact
        # set — a DP x TP hybrid whose data all-reduce regresses away
        # must fail even while its channel collectives satisfy any-of
        cfg = parse_mesh_spec(method)
        required = derive_hlo_contract(cfg)
        any_of_tier = channel_comms_required(cfg)
    findings: List[Finding] = []
    if any_of_tier and not (ops & TP_HLO_ANY_OF):
        findings.append(Finding(
            rule="comms-contract-hlo",
            where=where,
            message=(
                f"optimized HLO contains none of "
                f"{sorted(TP_HLO_ANY_OF)} — TP's sharded channels are "
                f"not actually communicating (degenerated to "
                f"replication?); found {sorted(ops)}"
            ),
            layer="collectives",
        ))
    if required and not required <= ops:
        findings.append(Finding(
            rule="comms-contract-hlo",
            where=where,
            message=(
                f"optimized HLO is missing {sorted(required - ops)} (found "
                f"{sorted(ops)}) — the strategy silently degenerated: its "
                f"parallelism implies that communication"
            ),
            layer="collectives",
        ))
    return findings


# -- drivers -----------------------------------------------------------------
def combos_for(strategies: Sequence[str] = ANALYSIS_STRATEGIES,
               schedules: Sequence[str] = ANALYSIS_SCHEDULES
               ) -> List[Tuple[str, Optional[str]]]:
    combos: List[Tuple[str, Optional[str]]] = []
    for method in strategies:
        if _is_pipeline_method(method):
            combos.extend((method, s) for s in schedules)
        else:
            combos.append((method, None))
    return combos


def analyze_combo(method: str, schedule: Optional[str] = None,
                  hlo: bool = False, rank_check: bool = True
                  ) -> List[Finding]:
    """Run every layer-1 check for one strategy × schedule combo.
    Trace-only unless ``hlo``; zero device execution either way."""
    if _is_pipeline_method(method) and schedule is None:
        # the trace rig defaults a missing schedule to gpipe; the
        # contract key must name the program actually traced, or the
        # ('MP', None) lookup misses JAXPR_CONTRACTS and the
        # comms-contract check silently becomes vacuous
        schedule = "gpipe"
    findings: List[Finding] = []

    try:
        train_jaxpr = trace_train(method, schedule)
    except ValueError as exc:
        if is_mesh_spec(method):
            # a mesh spec that cannot BUILD (model x stage, divisibility,
            # device count) is a CONFIG refusal, not an analyzer crash:
            # report it as a finding so the launch preflight (elastic)
            # refuses the geometry pre-spawn with the reason,
            # and an `analyze --mesh` run keeps its other combos' results
            return [Finding(
                rule="mesh-config",
                where=_combo_tag(method, schedule, "train"),
                message=(
                    f"mesh config cannot build on the analysis rig: "
                    f"{exc}"
                ),
                layer="collectives",
            )]
        raise
    train_colls = extract_collectives(train_jaxpr)
    where = _combo_tag(method, schedule, "train")
    findings += check_axis_binding(train_colls, where)
    findings += check_ppermute_flow(train_colls, where)
    findings += check_uniform_branches(train_colls, where)
    findings += check_contract(method, schedule, train_colls, where)

    eval_colls = extract_collectives(trace_eval(method, schedule))
    where_e = _combo_tag(method, schedule, "eval")
    findings += check_axis_binding(eval_colls, where_e)
    findings += check_ppermute_flow(eval_colls, where_e)
    findings += check_uniform_branches(eval_colls, where_e)
    findings += check_contract(
        method, schedule, eval_colls, where_e,
        requirements=_eval_contract_requirements(method, schedule),
    )

    if rank_check:
        findings += check_rank_invariance(
            method, schedule, [c.signature for c in train_colls]
        )
    if hlo:
        findings += check_hlo_contract(method, schedule)
    return dedupe(findings)


def analyze(strategies: Sequence[str] = ANALYSIS_STRATEGIES,
            schedules: Sequence[str] = ANALYSIS_SCHEDULES,
            hlo: bool = False, rank_check: bool = True):
    """Analyze every requested combo; returns ``(findings, combo_tags)``."""
    findings: List[Finding] = []
    tags = []
    for method, schedule in combos_for(strategies, schedules):
        tags.append(f"{method}/{schedule}" if schedule else method)
        findings += analyze_combo(
            method, schedule, hlo=hlo, rank_check=rank_check
        )
    return findings, tags
