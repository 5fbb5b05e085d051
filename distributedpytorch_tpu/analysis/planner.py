"""The compiler-driven parallelism auto-planner: ``python -m
distributedpytorch_tpu plan``.

Chip windows r03–r05 spent most of their budget discovering configs that
were statically broken or memory-infeasible — facts that never needed a
device. This module learns them from the compiler alone (Alpa/FlexFlow's
search-with-a-cost-model idea, scoped to this repo's levers): enumerate
(strategy × pipeline-schedule × microbatches × s2d level × remat × batch
× dtype policy), then for each point

1. **static feasibility** — the existing jaxpr collective checker
   (``analysis/collectives.analyze_combo``, including the dual-rank
   re-trace): a point whose schedule deadlocks, drops a contract psum,
   or diverges across ranks is rejected before anything compiles;
2. **memory feasibility** — AOT-compile the strategy's REAL train step
   (``strategy.build_train_step`` over sharding-pinned
   ``ShapeDtypeStruct``s — the GSPMD partitioner runs, nothing
   executes) and reject points whose ``memory_analysis()`` traced
   liveness exceeds the ``--hbm-gb`` budget — the same traced-liveness
   signal PR 4 proved predicts the activation wall;
3. **rank the survivors** — ``analysis/cost_model.point_cost`` over the
   compiled flops (``cost_analysis``; guarded — some backends lack it),
   the liveness bytes, and the comms program (extracted from the jaxpr
   with per-collective payload bytes for the explicit schedules;
   analytic for GSPMD strategies, where ``--dtype bf16_params`` halves
   FSDP's all-gather bytes).

Everything runs on a self-provisioned virtual CPU mesh (same dance as
the ``analyze`` CLI): zero device execution, zero chip involvement, safe
to run while a window is idle or from a laptop.

The output is a versioned JSON plan file: every point with its verdict,
the survivors ranked. ``analyze --plan`` re-traces its fingerprinted
points (``check_plan_staleness``), so a plan that outlived the code it
was built from is flagged before anyone acts on it.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import math
import os
import sys
import time
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from distributedpytorch_tpu.analysis import (
    ANALYSIS_STRATEGIES,
    AnalysisEnvironmentError,
    MESH_DEVICES,
    PROVISIONED_SENTINEL as _SENTINEL,
)
from distributedpytorch_tpu.analysis import cost_model as cm
# import-light at module level (no jax): load_plan stays jax-free
from distributedpytorch_tpu.analysis.collectives import PIPELINE_STRATEGIES
# the mesh rule engine (parallel/mesh.py, jax-free): mesh-shape specs
# (``4x1x2``) enter the search grid exactly like strategy names
from distributedpytorch_tpu.parallel.mesh import spec_is_pipeline

#: Plan-file schema version: ``load_plan`` returns None on any other
#: value — a version-skewed plan is never read as a current one.
PLAN_VERSION = 1
PLAN_KIND = "dpt_plan"

#: The default search grid. Axes that don't apply to a strategy collapse
#: (schedule/microbatches are pipeline-only), so the default enumerates
#: singleGPU·(s2d × remat × batch × dtype) + MP·(everything). Trim with
#: the CLI flags — every point costs one AOT compile (~tens of seconds
#: at the reference geometry on CPU), so ``--budget-s`` matters.
DEFAULT_GRID: Dict[str, tuple] = {
    "strategies": ("singleGPU", "MP"),
    # Mesh-shape axis (parallel/mesh.py specs, e.g. 4x1x2 / 2x2x1 /
    # 1x2x4): OFF by default — the historical grids stay byte-stable —
    # and widened by --meshes; spec points enumerate exactly like
    # strategies (stage-axis specs get the schedule x microbatch axes).
    "meshes": (),
    "schedules": ("gpipe", "1f1b"),
    "microbatches": (2, 8),
    "s2d_levels": (0, 2, 3),
    "remats": (False, True),
    "batches": (4, 8),
    "dtypes": ("bf16", "bf16_params"),
    # The Pallas kernel-engagement axis (ops/kernels.py) is OFF by
    # default: kernel-on points cost no extra compile (they derive from
    # their XLA twin + the analytic fused-traffic saving), but ranking
    # them is only meaningful against a per-chip Mosaic probe priors
    # file — the CLI widens this to ("xla", "pallas") when
    # --kernel-priors (or explicit --kernels) is passed.
    "kernels": ("xla",),
}

EXIT_CLEAN = 0
EXIT_INFRA = 2


@dataclasses.dataclass(frozen=True)
class PlanPoint:
    """One candidate configuration — the search space's coordinates."""

    strategy: str
    schedule: Optional[str]      # None for non-pipeline strategies
    microbatches: Optional[int]  # None for non-pipeline strategies
    s2d_levels: int
    remat: bool
    batch: int
    dtype: str
    # Kernel-engagement policy (ops/kernels.py): "xla" keeps the key
    # format (and every pre-existing plan row) unchanged; "pallas"
    # points derive from their xla twin + the analytic kernel saving.
    kernels: str = "xla"

    @property
    def key(self) -> str:
        sched = f"/{self.schedule}/m{self.microbatches}" if self.schedule else ""
        remat = "on" if self.remat else "off"
        kern = f"/k-{self.kernels}" if self.kernels != "xla" else ""
        return (f"{self.strategy}{sched}/s2d{self.s2d_levels}"
                f"/remat-{remat}/b{self.batch}/{self.dtype}{kern}")

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["key"] = self.key
        return d


def _is_pipeline_point(strategy: str) -> bool:
    return strategy in PIPELINE_STRATEGIES or spec_is_pipeline(strategy)


def enumerate_points(
    strategies: Sequence[str],
    schedules: Sequence[str],
    microbatches: Sequence[int],
    s2d_levels: Sequence[int],
    remats: Sequence[bool],
    batches: Sequence[int],
    dtypes: Sequence[str],
    kernels: Sequence[str] = ("xla",),
) -> List[PlanPoint]:
    """The cartesian grid with non-applicable axes collapsed. dtype is
    a late axis so a budget-truncated run still covers both policies of
    the earliest points (the comparison each pair exists for) before
    opening new strategy corners; kernels is INNERMOST — a kernel-on
    point always directly follows the xla twin it derives from (zero
    extra compile, so the pairing is free even under a budget)."""
    points: List[PlanPoint] = []
    seen = set()
    # xla twins must precede their pallas derivations in the walk
    kerns = sorted({str(k) for k in kernels}, key=lambda k: k != "xla")
    for strategy in strategies:
        pipelined = _is_pipeline_point(strategy)
        scheds: Sequence[Optional[str]] = (
            tuple(schedules) if pipelined else (None,)
        )
        mbs: Sequence[Optional[int]] = (
            tuple(microbatches) if pipelined else (None,)
        )
        for sched, m, b, s2d, remat, dt, kern in itertools.product(
            scheds, mbs, batches, s2d_levels, remats, dtypes, kerns
        ):
            p = PlanPoint(strategy, sched, m, int(s2d), bool(remat),
                          int(b), dt, kern)
            if p not in seen:
                seen.add(p)
                points.append(p)
    return points


# -- evaluation --------------------------------------------------------------
def _point_config(point: PlanPoint, image_size, widths):
    from distributedpytorch_tpu.config import TrainConfig

    return TrainConfig(
        train_method=point.strategy,
        batch_size=point.batch,
        image_size=tuple(image_size),
        model_widths=tuple(widths) if widths else None,
        pipeline_schedule=point.schedule or "gpipe",
        num_microbatches=point.microbatches or 2,
        s2d_levels=point.s2d_levels,
        remat=point.remat,
        dtype=point.dtype,
    )


def _tree_bytes(tree) -> int:
    import jax
    import jax.numpy as jnp

    total = 0
    for leaf in jax.tree.leaves(tree):
        total += math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
    return int(total)


def _tree_count(tree) -> int:
    import jax

    return int(sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree)))


def _activation_levels(image_size, widths, batch: int,
                       itemsize: int) -> tuple:
    """Per-UNet-level ``(plane_bytes, row_bytes)`` of the conv
    activations in the compute dtype — what the analytic halo (spatial)
    and channel-gather (TP) comms terms scale with
    (cost_model.mesh_comms_program). ``widths`` None = the flagship
    architecture's documented channel plan."""
    width, height = image_size  # (W, H), the reference convention
    out = []
    for level, channels in enumerate(widths or (32, 64, 128, 256)):
        h, w = max(height >> level, 1), max(width >> level, 1)
        out.append((
            batch * h * w * int(channels) * itemsize,
            batch * w * int(channels) * itemsize,
        ))
    return tuple(out)


def _flops_of(compiled) -> Optional[float]:
    """``cost_analysis()`` flops, guarded: absent/odd-shaped analyses on
    some backends must degrade the cost model, never crash the plan."""
    try:
        analysis = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — NotImplementedError and friends
        return None
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, Mapping):
        return None
    flops = analysis.get("flops")
    try:
        flops = float(flops)
    except (TypeError, ValueError):
        return None
    return flops if flops > 0 else None


def _trace_point_step(point: PlanPoint, image_size, widths):
    """The point's abstract train step, traced: config → strategy →
    shape-only state/batch → jaxpr collective program. Shared by
    :func:`evaluate_point` (which goes on to AOT-compile) and
    :func:`check_plan_staleness` (which only needs the collective
    program) so the stale-plan re-trace compares like with like.
    Returns ``(cfg, strategy, model, tx, state, batch, colls)``."""
    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.analysis.collectives import (
        extract_collectives,
    )
    from distributedpytorch_tpu.models import create_model
    from distributedpytorch_tpu.ops.optim import adam_l2
    from distributedpytorch_tpu.parallel import build_strategy
    from distributedpytorch_tpu.train.steps import TrainState

    cfg = _point_config(point, image_size, widths)
    strategy = build_strategy(cfg)
    policy = strategy.policy
    model, _init_fn = create_model(cfg)
    width, height = cfg.image_size  # (W, H), the reference convention

    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, height, width, 3))),
        jax.random.key(0),
    )
    params = variables["params"]
    model_state = variables.get("batch_stats")
    # mirror train/steps.create_train_state: optimizer init sees the
    # full-precision params (the master-weight wrapper promotes its copy
    # from what it is given), THEN params cast to storage dtype
    tx = adam_l2(cfg.learning_rate, cfg.weight_decay)
    if policy.master_weights:
        tx = policy.wrap_optimizer(tx)
    opt_state = jax.eval_shape(tx.init, params)
    params = jax.eval_shape(policy.cast_params, params)
    state = TrainState(
        params=params,
        opt_state=opt_state,
        step=jax.ShapeDtypeStruct((), jnp.int32),
        model_state=model_state,
    )
    batch = {
        "image": jax.ShapeDtypeStruct(
            (point.batch, height, width, 3), jnp.float32),
        "mask": jax.ShapeDtypeStruct((point.batch, height, width), jnp.int32),
    }
    colls = extract_collectives(
        jax.make_jaxpr(strategy._raw_step(model, tx))(state, batch)
    )
    return cfg, strategy, model, tx, state, batch, colls


def evaluate_point(point: PlanPoint, image_size, widths,
                   mesh_model: cm.MeshModel, hbm_budget_bytes: int) -> dict:
    """One point's row: abstract state → jaxpr comms program → AOT
    compile → memory/flops → cost. Zero device execution throughout
    (``make_jaxpr`` + ``lower().compile()`` only). Raises on configs the
    strategy itself rejects — the caller records those as infeasible."""
    import jax.numpy as jnp

    from distributedpytorch_tpu.analysis.collectives import (
        compile_train_step_aot,
        program_fingerprint,
    )

    cfg, strategy, model, tx, state, batch, colls = _trace_point_step(
        point, image_size, widths
    )
    policy = strategy.policy
    params = state.params

    # -- comms program: jaxpr-extracted (explicit schedules) or analytic ----
    mesh = strategy.mesh
    program: List[cm.CommOp] = []
    last_sig = None
    for c in colls:
        axis_size = 1
        for axis in c.axes:
            if isinstance(axis, str) and mesh is not None and axis in mesh.shape:
                axis_size *= int(mesh.shape[axis])
        # a tree-typed collective traces one eqn PER LEAF per tick but
        # ships as ONE fused transfer on hardware: merge adjacent eqns
        # with identical signatures into a single op (summed payload),
        # so the per-collective latency term counts ticks, not leaves
        if program and c.signature == last_sig:
            kind, payload, n = program[-1]
            program[-1] = (kind, payload + c.payload_bytes, n)
        else:
            program.append((c.kind, c.payload_bytes, axis_size))
        last_sig = c.signature
    comms_model = "jaxpr" if program else "none"
    if not program and mesh is not None:
        # GSPMD configs trace empty programs: compose the analytic
        # per-axis terms from the strategy's mesh config — the data
        # axis's grad psum / ZeRO dance, and the model axis's halo
        # (spatial) or channel-gather (TP) traffic, previously the
        # ``comms_model: none`` gap that let SP/TP points rank with a
        # silent zero-comms advantage
        mc = strategy.mesh_config
        program = cm.mesh_comms_program(
            data=mc.data,
            model=mc.model,
            model_role=mc.model_role,
            params_rule=mc.params,
            param_storage_bytes=_tree_bytes(params),
            grad_bytes=_tree_count(params) * 4,
            level_planes=_activation_levels(
                cfg.image_size, widths, point.batch,
                jnp.dtype(policy.compute_dtype).itemsize,
            ),
            stage=mc.stage,
        )
        if program:
            comms_model = "analytic"
    comms_bytes, comms_s = cm.comms_summary(program, mesh_model)

    # -- in-stage sharding advisory: hybrid pipeline points carry their
    # gather-at-use collectives inside the traced jaxpr program already
    # (counted in comms_s above); re-derive the analytic in-stage terms
    # separately so the breakdown NAMES them — a 2x2x2 row shows what the
    # model axis costs, not just a merged total. Advisory only: never
    # added to cost_s (that would double-count the jaxpr gathers).
    in_stage_s = None
    mc = getattr(strategy, "mesh_config", None)
    if mc is not None and mc.stage > 1 and (
        (mc.model > 1 and mc.model_role == "channel")
        or ("fsdp" in mc.params and mc.data > 1)
    ):
        in_stage_program = cm.mesh_comms_program(
            data=mc.data,
            model=mc.model,
            model_role=mc.model_role,
            params_rule=mc.params,
            param_storage_bytes=_tree_bytes(params),
            grad_bytes=_tree_count(params) * 4,
            stage=mc.stage,
        )
        _, in_stage_s = cm.comms_summary(in_stage_program, mesh_model)

    # -- AOT compile: traced liveness + flops, nothing executes -------------
    compiled = compile_train_step_aot(strategy, model, tx, state, batch)
    ma = compiled.memory_analysis()
    flops = _flops_of(compiled)

    bytes_row: Dict[str, Optional[int]] = {
        "temp_bytes": int(ma.temp_size_in_bytes) if ma else None,
        "argument_bytes": int(ma.argument_size_in_bytes) if ma else None,
        "output_bytes": int(ma.output_size_in_bytes) if ma else None,
    }
    live_bytes = (
        sum(v for v in bytes_row.values() if v is not None)
        if ma else None
    )

    feasible = True
    reject = None
    if live_bytes is not None and live_bytes > hbm_budget_bytes:
        feasible = False
        reject = (
            f"memory: traced liveness {live_bytes} B exceeds the "
            f"{hbm_budget_bytes} B HBM budget "
            f"(temp={bytes_row['temp_bytes']}, "
            f"args={bytes_row['argument_bytes']}, "
            f"out={bytes_row['output_bytes']})"
        )

    predicted = cm.point_cost(
        mesh_model, policy.compute, flops, live_bytes, comms_s,
        hbm_budget_bytes=hbm_budget_bytes,
    )
    predicted.update(bytes_row)
    predicted["live_bytes"] = live_bytes
    predicted["flops"] = flops
    predicted["comms_bytes"] = comms_bytes
    predicted["comms_model"] = comms_model
    if in_stage_s is not None:
        predicted["in_stage_comms_s"] = in_stage_s
    cost = predicted["cost_s"]
    predicted["imgs_per_s"] = (
        round(strategy.global_batch_size / cost, 2) if cost else None
    )

    row = point.as_dict()
    row.update(feasible=feasible, reject=reject, predicted=predicted)
    # provenance stamp: the ordered-collective fingerprint of the trace
    # this row's numbers were computed from — the stale-plan rule
    # (check_plan_staleness) re-traces and compares against it. Only
    # xla rows trace; kernel-derived rows copy their twin's artifacts
    # and deliberately carry no fingerprint.
    row["jaxpr_fingerprint"] = program_fingerprint(colls)
    return row


def _engaged_train_kernels(point: PlanPoint, widths) -> Tuple[str, ...]:
    """Probe-registry names a TRAIN step at this point would engage
    under a pallas kernel policy (ops/kernels.train_step_kernels over
    the point's config — the one definition of engagement)."""
    from distributedpytorch_tpu.ops.kernels import train_step_kernels

    return train_step_kernels(_point_config(point, (64, 64), widths))


def _kernel_point_row(
    point: PlanPoint,
    twin_row: Optional[dict],
    mesh_model: cm.MeshModel,
    priors: Optional[dict],
    image_size,
    widths,
) -> dict:
    """A ``kernels='pallas'`` point's row, derived with ZERO compile and
    ZERO device time:

    * any engaged kernel the Mosaic probe priors mark rejected → the
      point is infeasible, carrying the probe's reject reason verbatim;
    * otherwise the row copies its xla twin's compiled artifacts (the
      interpret-mode Pallas compile on the planning CPU would distort
      flops/liveness, the twin's are the honest hardware-shaped numbers)
      and subtracts the analytic fused-traffic saving
      (cost_model.kernel_savings_s) from the predicted cost.
    """
    row = point.as_dict()
    engaged = _engaged_train_kernels(point, widths)
    prior_rows = (priors or {}).get("kernels", {})
    for name in engaged:
        verdict = prior_rows.get(name)
        if isinstance(verdict, dict) and not verdict.get("accepted", True):
            reason = verdict.get("reason", "no reason recorded")
            row.update(
                feasible=False,
                reject=f"kernels: Mosaic rejected {name}: {reason}",
                predicted=None,
            )
            return row
    if twin_row is None or twin_row.get("skipped"):
        row.update(feasible=None, reject=None, predicted=None,
                   skipped="budget")
        return row
    if not twin_row.get("feasible"):
        row.update(feasible=False, reject=twin_row.get("reject"),
                   predicted=None)
        return row
    predicted = dict(twin_row.get("predicted") or {})
    width, height = image_size  # (W, H), the reference convention
    plane_bytes = point.batch * height * width * 4
    saving = cm.kernel_savings_s(engaged, plane_bytes, mesh_model)
    cost = predicted.get("cost_s")
    if cost:
        new_cost = max(cost - saving, 0.05 * cost)
        predicted["cost_s"] = new_cost
        predicted["imgs_per_s"] = round(point.batch / new_cost, 2)
    predicted["kernel_saving_s"] = saving
    predicted["kernels_model"] = "analytic"
    predicted["kernels_engaged"] = list(engaged)
    predicted["kernel_priors"] = (
        "accepted" if all(k in prior_rows for k in engaged) else "unprobed"
    )
    row.update(feasible=True, reject=None, predicted=predicted)
    return row


def _static_findings(points: Sequence[PlanPoint]) -> Dict[str, List[str]]:
    """One collective-checker run per distinct (strategy, schedule)
    among the points — the dual-rank re-trace included, so a
    ``process_index()``-gated collective rejects here too. Strategies
    the analyzer doesn't cover (singleGPU) have nothing to check.
    Analyzer crashes on a combo degrade to 'no findings' for that combo
    (the planner is advisory; the memory gate still applies)."""
    from distributedpytorch_tpu.analysis import collectives

    findings: Dict[str, List[str]] = {}
    combos = sorted(
        {(p.strategy, p.schedule) for p in points
         if p.strategy in ANALYSIS_STRATEGIES
         # stage-axis mesh specs run the explicit schedules — the
         # checker derives their contract from the parsed spec; pure
         # GSPMD specs have nothing jaxpr-level to check (HLO tier)
         or spec_is_pipeline(p.strategy)},
        key=lambda c: (c[0], c[1] or ""),
    )
    for method, schedule in combos:
        tag = f"{method}/{schedule}" if schedule else method
        try:
            found = collectives.analyze_combo(
                method, schedule, hlo=False, rank_check=True
            )
        except Exception as exc:  # noqa: BLE001 — infra, not a finding
            findings[tag] = []
            print(f"plan: static check for {tag} could not run "
                  f"({type(exc).__name__}: {exc}) — proceeding",
                  file=sys.stderr)
            continue
        findings[tag] = [f"[{f.rule}] {f.where}: {f.message}" for f in found]
    return findings


def plan(
    strategies: Sequence[str] = DEFAULT_GRID["strategies"],
    meshes: Sequence[str] = DEFAULT_GRID["meshes"],
    schedules: Sequence[str] = DEFAULT_GRID["schedules"],
    microbatches: Sequence[int] = DEFAULT_GRID["microbatches"],
    s2d_levels: Sequence[int] = DEFAULT_GRID["s2d_levels"],
    remats: Sequence[bool] = DEFAULT_GRID["remats"],
    batches: Sequence[int] = DEFAULT_GRID["batches"],
    dtypes: Sequence[str] = DEFAULT_GRID["dtypes"],
    kernels: Sequence[str] = DEFAULT_GRID["kernels"],
    kernel_priors: Optional[dict] = None,
    image_size=(960, 640),
    widths: Optional[Sequence[int]] = None,
    hbm_gb: float = 16.0,
    mesh_model: str = "tpu_v5e",
    budget_s: float = 0.0,
    emit=None,
) -> dict:
    """Search, reject, rank; returns the plan payload (what
    ``save_plan`` writes). ``budget_s`` > 0 stops opening new compiles
    near the wall budget — already-evaluated points keep their rows and
    the rest carry an explicit ``skipped: budget`` marker.

    ``kernels`` is the Pallas engagement axis (ops/kernels.py):
    kernel-on points cost NO compile and NO device time — each derives
    from its xla twin plus the analytic fused-traffic saving, and
    ``kernel_priors`` (a loaded probe-priors payload) rejects any point
    whose engaged kernel Mosaic refused, carrying the probe's reason."""
    t_start = time.monotonic()
    mm = MESH_MODELS_LOOKUP(mesh_model)
    hbm_budget_bytes = int(hbm_gb * 2**30)
    # mesh-shape points are strategies to the rest of the pipeline:
    # build_strategy resolves specs, the collective checker derives
    # their contracts, and evaluate_point's mesh_config drives the
    # analytic comms — appended after the named strategies so legacy
    # grids keep their exact walk order
    strategies = tuple(strategies) + tuple(
        m for m in meshes if m not in strategies
    )
    kernels = tuple(kernels)
    if any(k != "xla" for k in kernels) and "xla" not in kernels:
        # every pallas point derives from its xla twin — force the pair
        kernels = ("xla",) + kernels
    points = enumerate_points(
        strategies, schedules, microbatches, s2d_levels, remats, batches,
        dtypes, kernels,
    )
    static = _static_findings(points)

    rows: List[dict] = []
    twin_rows: Dict[PlanPoint, dict] = {}
    for point in points:
        combo = (f"{point.strategy}/{point.schedule}" if point.schedule
                 else point.strategy)
        lines = static.get(combo, ())
        if lines:
            row = point.as_dict()
            row.update(feasible=False, reject=f"static: {lines[0]}",
                       predicted=None)
        elif point.kernels != "xla":
            # zero-compile derivation (and the Mosaic-priors gate)
            twin = twin_rows.get(dataclasses.replace(point, kernels="xla"))
            row = _kernel_point_row(
                point, twin, mm, kernel_priors, image_size, widths
            )
        elif budget_s and time.monotonic() - t_start > 0.8 * budget_s:
            row = point.as_dict()
            row.update(feasible=None, reject=None, predicted=None,
                       skipped="budget")
        else:
            try:
                row = evaluate_point(
                    point, image_size, widths, mm, hbm_budget_bytes
                )
            except AnalysisEnvironmentError:
                # the analyzer's own infra-failure class: a broken
                # environment must surface as EXIT_INFRA from the CLI,
                # never be recorded as a confident per-point rejection
                raise
            except Exception as exc:  # noqa: BLE001 — strategy/config rejects
                row = point.as_dict()
                row.update(
                    feasible=False,
                    reject=f"config: {type(exc).__name__}: {exc}",
                    predicted=None,
                )
        if point.kernels == "xla":
            twin_rows[point] = row
        rows.append(row)
        if emit is not None:
            emit(row)

    # cost_s must be POSITIVE to rank: a backend yielding neither
    # cost_analysis nor memory_analysis leaves a comms-free point at
    # 0.0 — completely unmeasured, which must not sort ahead of every
    # genuinely evaluated point
    ranked = sorted(
        (r for r in rows
         if r.get("feasible")
         and r.get("predicted")
         and (r["predicted"].get("cost_s") or 0) > 0),
        key=lambda r: (r["predicted"]["cost_s"], r["key"]),
    )
    for rank, row in enumerate(ranked):
        row["rank"] = rank
    for row in rows:
        row.setdefault("rank", None)

    return {
        "kind": PLAN_KIND,
        "version": PLAN_VERSION,
        "mesh_model": mm.name,
        "hbm_gb": float(hbm_gb),
        "image_size": list(image_size),
        "widths": list(widths) if widths else None,
        "grid": {
            "strategies": list(strategies),
            "meshes": list(meshes),
            "schedules": list(schedules),
            "microbatches": list(microbatches),
            "s2d_levels": list(s2d_levels),
            "remats": [bool(r) for r in remats],
            "batches": list(batches),
            "dtypes": list(dtypes),
            "kernels": list(kernels),
        },
        "kernel_priors": (
            {
                "platform": kernel_priors.get("platform"),
                "device_kind": kernel_priors.get("device_kind"),
                "rejected": sorted(
                    name
                    for name, row in (
                        kernel_priors.get("kernels") or {}
                    ).items()
                    if isinstance(row, dict) and not row.get("accepted", True)
                ),
            }
            if kernel_priors
            else None
        ),
        "static_findings": static,
        "points": rows,
        "ranking": [r["key"] for r in ranked],
        "duration_s": round(time.monotonic() - t_start, 2),
    }


def MESH_MODELS_LOOKUP(name: str) -> cm.MeshModel:
    try:
        return cm.MESH_MODELS[name]
    except KeyError:
        raise ValueError(
            f"unknown mesh model {name!r}; expected one of "
            f"{sorted(cm.MESH_MODELS)}"
        ) from None


# -- plan-file IO (jax-free) -------------------------------------------------
def save_plan(payload: dict, path: str) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)


def load_plan(path: str) -> Optional[dict]:
    """The plan file, or None for missing/unreadable/stale: a
    half-written or version-skewed plan is never acted on."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict):
        return None
    if payload.get("kind") != PLAN_KIND or payload.get("version") != PLAN_VERSION:
        return None
    if not isinstance(payload.get("points"), list):
        return None
    return payload


def point_from_row(row: Mapping) -> PlanPoint:
    """The :class:`PlanPoint` coordinates a saved plan row was
    evaluated at (the inverse of ``PlanPoint.as_dict``)."""
    return PlanPoint(
        strategy=row["strategy"],
        schedule=row.get("schedule"),
        microbatches=row.get("microbatches"),
        s2d_levels=int(row.get("s2d_levels") or 0),
        remat=bool(row.get("remat")),
        batch=int(row["batch"]),
        dtype=row["dtype"],
        kernels=row.get("kernels", "xla"),
    )


def check_plan_staleness(payload: Mapping) -> List:
    """The ``stale-plan`` rule (dptlint, collectives layer): re-trace
    every fingerprinted point of a loaded ``dpt_plan`` at the plan's
    own image size/widths and flag rows whose per-point ordered-
    collective fingerprint (``jaxpr_fingerprint``, stamped by
    :func:`evaluate_point`) no longer matches the current trace.

    A drifted fingerprint means the code that traces the train step —
    strategy, model, optimizer wrapping, sharding rules — changed
    since the plan was built: its rankings and comms predictions
    describe a program that no longer exists, and acting on them is
    planning from fiction. Rows without a fingerprint (kernel-derived
    points, plans predating the stamp) are skipped — no trace, nothing
    to compare.
    Infeasible-at-plan-time rows are still checked when they carry a
    fingerprint: their *rejection* was also computed from the trace."""
    from distributedpytorch_tpu.analysis import Finding

    from distributedpytorch_tpu.analysis.collectives import (
        program_fingerprint,
    )

    findings: List[Finding] = []
    image_size = tuple(payload.get("image_size") or (960, 640))
    widths = payload.get("widths")
    for row in payload.get("points") or []:
        if not isinstance(row, Mapping):
            continue
        want = row.get("jaxpr_fingerprint")
        if not want:
            continue
        point = point_from_row(row)
        where = row.get("key") or point.key
        try:
            colls = _trace_point_step(point, image_size, widths)[-1]
        except AnalysisEnvironmentError:
            raise  # broken analyzer environment, not a stale plan
        except Exception as exc:  # noqa: BLE001 — the point no longer
            # builds at all: the strongest possible staleness signal
            findings.append(Finding(
                rule="stale-plan",
                where=where,
                message=(
                    f"plan point no longer traces "
                    f"({type(exc).__name__}: {exc}) — the loaded "
                    f"dpt_plan predates the current code; re-run the "
                    f"planner"
                ),
                layer="collectives",
            ))
            continue
        got = program_fingerprint(colls)
        if got != want:
            findings.append(Finding(
                rule="stale-plan",
                where=where,
                message=(
                    f"collective fingerprint drifted: the plan recorded "
                    f"{want} but the current trace is {got} — this "
                    f"row's cost/comms numbers (and the plan's ranking) "
                    f"were computed from a collective program that no "
                    f"longer exists; re-run the planner before trusting "
                    f"the plan"
                ),
                layer="collectives",
            ))
    return findings


# -- CLI ---------------------------------------------------------------------
def build_parser() -> argparse.ArgumentParser:
    g = DEFAULT_GRID
    ap = argparse.ArgumentParser(
        prog="python -m distributedpytorch_tpu plan",
        description="Compiler-driven parallelism auto-planner: search "
        "strategy × schedule × memory levers with zero device execution, "
        "reject statically-broken / memory-infeasible points, rank the "
        "rest by an analytic cost model, and emit a plan file "
        "(analyze --plan checks it for staleness). See "
        "docs/PERFORMANCE.md 'Planning'.",
    )
    ap.add_argument("--out", default="plan.json",
                    help="Plan file to write (versioned JSON)")
    ap.add_argument("--strategies", nargs="+", default=list(g["strategies"]))
    ap.add_argument("--meshes", nargs="+", default=list(g["meshes"]),
                    metavar="SPEC",
                    help="Mesh-shape points (DxMxS[@fsdp|sp], parallel/"
                         "mesh.py) searched ALONGSIDE --strategies — "
                         "e.g. 4x1x2 2x2x2 1x2x4; stage-axis specs get "
                         "the schedule x microbatch axes, and hybrid "
                         "points rank against pure ones on the same "
                         "memory/comms terms")
    ap.add_argument("--schedules", nargs="+", default=list(g["schedules"]),
                    choices=["gpipe", "1f1b"])
    ap.add_argument("--microbatches", type=int, nargs="+",
                    default=list(g["microbatches"]))
    ap.add_argument("--s2d-levels", type=int, nargs="+",
                    default=list(g["s2d_levels"]),
                    help="Explicit levels only: -1 (auto) would resolve "
                         "against the COMPILING backend, not the chip")
    ap.add_argument("--remat", choices=["off", "on", "both"], default="both")
    ap.add_argument("--batches", type=int, nargs="+",
                    default=list(g["batches"]))
    ap.add_argument("--dtypes", nargs="+", default=list(g["dtypes"]),
                    choices=["f32", "bf16", "bf16_params"])
    ap.add_argument("--kernels", nargs="+", default=None,
                    choices=["xla", "pallas"],
                    help="Pallas kernel-engagement axis (ops/kernels.py). "
                         "Default: xla only; widens to both when "
                         "--kernel-priors is given (kernel-on points cost "
                         "zero extra compile — they derive from their xla "
                         "twin + the analytic fused-traffic saving)")
    ap.add_argument("--kernel-priors", default=None,
                    help="Per-chip Mosaic probe priors file "
                         "(tools/probe_kernels.py): kernel-on points whose "
                         "engaged kernel the chip's compiler rejected are "
                         "rejected here too, with the probe's reason, at "
                         "zero device time; missing/stale/corrupt files "
                         "are ignored with a note (kernels rank unprobed)")
    ap.add_argument("--image-size", type=int, nargs=2, default=(960, 640),
                    metavar=("W", "H"),
                    help="Target geometry (the reference 960 640)")
    ap.add_argument("--widths", type=int, nargs="+", default=None,
                    help="Model channel widths (default: the architecture's "
                         "documented plan)")
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="Per-device HBM budget (default: the mesh "
                         "model's capacity)")
    ap.add_argument("--mesh-model", default="tpu_v5e",
                    choices=sorted(cm.MESH_MODELS))
    ap.add_argument("--budget-s", type=float, default=0.0,
                    help="Stop opening new compiles near this wall "
                         "budget; unevaluated points are marked skipped")
    return ap


def run(argv: Optional[Sequence[str]] = None) -> int:
    """The provisioned body: parse, plan, write, summarize."""
    args = build_parser().parse_args(argv)
    remats = {"off": (False,), "on": (True,), "both": (False, True)}[args.remat]
    try:
        mm = MESH_MODELS_LOOKUP(args.mesh_model)
    except ValueError as exc:
        print(f"plan: {exc}", file=sys.stderr)
        return EXIT_INFRA
    from distributedpytorch_tpu.parallel.mesh import parse_mesh_spec

    for spec in args.meshes:
        try:
            parse_mesh_spec(spec)
        except ValueError as exc:
            print(f"plan: {exc}", file=sys.stderr)
            return EXIT_INFRA
    hbm_gb = args.hbm_gb if args.hbm_gb is not None else mm.hbm_gb

    priors = None
    if args.kernel_priors:
        from distributedpytorch_tpu.ops.kernels import load_priors

        priors = load_priors(args.kernel_priors)
        if priors is None:
            print(f"plan: kernel priors {args.kernel_priors!r} missing, "
                  f"stale, or corrupt — ignored; kernel points rank "
                  f"unprobed", file=sys.stderr)
    if args.kernels is not None:
        kernels = tuple(args.kernels)
    elif priors is not None:
        # a LOADED priors file is the opt-in: search kernel-on vs
        # kernel-off. A --kernel-priors path whose file is missing/stale
        # must NOT widen the axis — an unprobed pallas point would rank
        # beside points the chip's compiler has vetted.
        kernels = ("xla", "pallas")
    else:
        kernels = DEFAULT_GRID["kernels"]

    def emit(row):
        line = {k: row.get(k) for k in ("key", "feasible", "reject")}
        if row.get("skipped"):
            line["skipped"] = row["skipped"]
        predicted = row.get("predicted") or {}
        if predicted.get("cost_s") is not None:
            line["cost_s"] = round(predicted["cost_s"], 6)
        print(json.dumps(line))

    try:
        payload = plan(
            strategies=args.strategies,
            meshes=args.meshes,
            schedules=args.schedules,
            microbatches=args.microbatches,
            s2d_levels=args.s2d_levels,
            remats=remats,
            batches=args.batches,
            dtypes=args.dtypes,
            kernels=kernels,
            kernel_priors=priors,
            image_size=tuple(args.image_size),
            widths=tuple(args.widths) if args.widths else None,
            hbm_gb=hbm_gb,
            mesh_model=args.mesh_model,
            budget_s=args.budget_s,
            emit=emit,
        )
    except Exception as exc:  # noqa: BLE001 — infra failure, distinct rc
        print(f"plan: infrastructure failure: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        return EXIT_INFRA
    save_plan(payload, args.out)

    rows = payload["points"]
    feasible = [r for r in rows if r.get("feasible")]
    rejected = [r for r in rows if r.get("feasible") is False]
    skipped = [r for r in rows if r.get("skipped")]
    print(f"\nplan: {len(rows)} points — {len(feasible)} feasible, "
          f"{len(rejected)} rejected, {len(skipped)} budget-skipped in "
          f"{payload['duration_s']}s → {args.out}")
    by_key = {r["key"]: r for r in rows}
    print("\n| rank | point | predicted cost s | predicted imgs/s |")
    print("|---|---|---|---|")
    for key in payload["ranking"][:10]:
        p = by_key[key]["predicted"]
        print(f"| {by_key[key]['rank']} | {key} | {p['cost_s']:.6g} "
              f"| {p['imgs_per_s']} |")
    for r in rejected[:10]:
        print(f"rejected: {r['key']}: {r['reject']}")
    return EXIT_CLEAN


def main(argv: Optional[Sequence[str]] = None) -> int:
    """Self-provisioning entry (the ``plan`` subcommand): exec-replace
    under an 8-device virtual CPU mesh unless already provisioned —
    pinned to CPU, never claiming a chip, exactly the ``analyze`` CLI's
    dance."""
    argv = list(sys.argv[2:] if argv is None else argv)
    if os.environ.get(_SENTINEL) == "1":
        return run(argv)
    from distributedpytorch_tpu.utils.provision import reexec_provisioned_cmd

    reexec_provisioned_cmd(
        MESH_DEVICES, _SENTINEL,
        [sys.executable, "-u", "-m", "distributedpytorch_tpu", "plan",
         *argv],
    )


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
