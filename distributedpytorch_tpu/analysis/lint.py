"""Layer 2: project-specific AST lint over the package source.

Pure ``ast`` — no jax import, so jax-free processes (the elastic
supervisor) and cold CI jobs can run it in milliseconds. Rules (catalog
with rationale and what each provably excludes: docs/ANALYSIS.md):

* ``trace-nondeterminism`` — ``time.time``/``random.*``/``np.random.*``
  (and friends) inside functions that end up traced by jax. A traced
  call executes ONCE at trace time and freezes its value into the
  compiled program: what looks like per-step randomness is a constant,
  and what looks like a timestamp is the compile time. Traced functions
  are detected as: arguments to jit/shard_map/grad/cond/scan/... calls,
  functions decorated with jit/checkpoint, anything nested in either,
  and anything nested in a ``make_*`` builder (this repo's idiom: every
  ``make_*`` in the package returns a function the strategies jit).

* ``host-sync-hot-path`` — ``.item()``, ``block_until_ready``,
  ``np.asarray``/``jax.device_get`` in the step hot path (the loop
  bodies nested in ``Trainer.train``): each forces a device→host sync
  that stalls the async step pipeline PR 1 built. Sanctioned drain
  points (``LossRecords``' parked-row pulls, nested fns named ``pull``)
  are exempt; ``.item()``/``block_until_ready`` are additionally flagged
  package-wide outside the sanctioned drain modules.

* ``serve-hot-path`` — the same blocking-sync family (``.item()``,
  ``block_until_ready``, ``np.asarray``/``jax.device_get``) inside the
  serving tier's dispatch pipeline (the functions named in
  ``SERVE_HOT_PATH_SCOPES``, serve/server.py): one sync there stalls
  EVERY in-flight request on every replica, not just one step — the
  continuous-batching design routes all device→host reads through the
  completion drain (``pull``), which is the sanctioned exemption,
  mirroring the train-side rule's mechanism.

* ``use-after-donation`` — a value passed in donated position (argument
  0 of a ``*train_step``/``multi_step``/``accum_step`` call) is deleted
  device memory after the call; reading it — or an alias bound from it
  before the call — afterwards is a use-after-free on accelerators.

* ``rank-gated-collective`` — a collective call lexically under an
  ``if``/``while``/ternary whose test calls ``process_index()``: ranks
  would trace different collective programs and deadlock at the first
  unmatched one. (The jaxpr layer proves the same property dynamically
  via dual-rank tracing; this rule points at the exact source line.)

* ``dtype-policy`` — the mixed-precision policy's cast-boundary contract
  (ops/precision.py, docs/PERFORMANCE.md "Precision"): a bare
  ``jnp.float32``/``np.float32`` literal (or ``astype("float32")``)
  inside a traced function is an upcast the ``--dtype`` policies cannot
  see — under bf16 it silently re-widens a hot-path tensor, under
  bf16_params it forks the param dtype mid-trace. The rule reaches
  Pallas KERNEL BODIES (functions handed to ``pallas_call``) and
  custom-VJP forward/backward bodies (``defvjp``): kernel accumulators
  must spell the contract by NAME (``precision.LOSS_DTYPE`` /
  ``WGRAD_DTYPE`` / ``REDUCE_DTYPE`` / ``NORM_DTYPE``) — the kernel
  modules comply and are no longer blanket-exempt; only the loss/quant/
  structured-conv modules whose f32 IS the policy remain sanctioned.

* ``ckpt-dtype-drift`` — donation-aware save/restore dtype drift: a
  ``load_checkpoint``/``load_weights`` call whose enclosing function
  never routes the result through the policy's restore seams
  (``ensure_restored_dtypes`` / ``convert_checkpoint_state``) can hand
  the step params whose dtype differs from the session policy — the
  jitted step would silently RETRACE against the drifted layout (and its
  donated buffers), instead of re-casting loudly or failing.

* ``obs-hot-path`` — the telemetry layer's hot-path contract
  (distributedpytorch_tpu/obs, docs/OBSERVABILITY.md): (a) record paths
  inside ``obs/`` (functions named ``record*``/``inc``/``observe``/
  ``set``/``span``) must not block on a device value (the blocking-sync
  family) and must not grow without bound — a bare ``list.append`` is
  flagged unless the target was constructed as a ``deque(maxlen=...)``
  in the same file (the ring-slot contract); (b) package-wide, any
  telemetry call (``obs.`` / ``obsm.`` / ``flight.`` dotted prefixes)
  inside a jit/shard_map-traced function is flagged — it would execute
  once at trace time and record nothing (or bake a host side effect
  into the compiled program).

* ``serve-donation`` — a ``jit(..., donate_argnums=...)`` (or
  ``donate_argnames``) call inside a serve module. Serve executables
  re-read their weights operand on every request, rollbacks re-read
  pre-swap snapshots, and AOT-store siblings rehydrate shared buffers —
  donation anywhere in the serving tier is a use-after-free waiting for
  a backend that honors it (the CPU donation SIGABRT class). The
  engine's one sanctioned wrapper is ``serve/engine.serve_jit``, which
  never donates; the jaxpr tier (analysis/donation.py) proves the
  lowered executables clean, this rule points at the source line of
  any wrapper that would bypass it.

Suppression: append ``# dptlint: disable=<rule>[,<rule>...]`` (or
``disable=all``) to the offending line, with a justification.
Suppressions are themselves linted: naming a rule this linter does not
define is an ``unknown-suppression`` finding (likely a typo silently
suppressing nothing), and suppressing a rule that no longer fires on
that line is a ``stale-suppression`` finding — dead suppressions hide
future regressions on the lines they squat on.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

from distributedpytorch_tpu.analysis import Finding

#: Call names whose function-valued arguments get traced by jax.
#: ``pallas_call`` makes Pallas KERNEL BODIES traced scopes (a bare f32
#: accumulator inside one is exactly the drift the dtype-policy rule
#: exists for); ``defvjp`` reaches hand-written custom-VJP forward and
#: backward bodies the same way.
TRACE_ENTRYPOINTS = frozenset({
    "jit", "pmap", "vmap", "grad", "value_and_grad", "vjp", "jvp",
    "checkpoint", "remat", "cond", "switch", "scan", "while_loop",
    "shard_map", "eval_shape", "make_jaxpr", "custom_vjp", "custom_jvp",
    "fori_loop", "associative_scan", "named_call", "pallas_call",
    "defvjp",
})

#: Decorators that make the decorated function traced.
TRACED_DECORATORS = frozenset({"jit", "checkpoint", "remat", "custom_vjp",
                               "custom_jvp"})

#: Which positional args of each entrypoint are callables that get
#: traced (default: arg 0). Data operands (scan's init/xs, cond's
#: operands) must NOT be marked — a data variable named like a host
#: function elsewhere in the module would otherwise poison that
#: function as "traced".
CALLABLE_ARG_POSITIONS = {
    "cond": (1, 2),       # cond(pred, true_fn, false_fn, *operands)
    "switch": (1,),       # switch(index, branches, *operands)
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "defvjp": (0, 1),     # f.defvjp(fwd, bwd) — both bodies trace
}
#: Keyword names that carry callables into trace entrypoints.
CALLABLE_KEYWORDS = frozenset({"f", "fun", "fn", "body", "body_fun",
                               "cond_fun", "branches"})

#: Dotted-path prefixes/exacts that are nondeterministic under trace.
NONDET_EXACT = frozenset({
    "time.time", "time.time_ns", "time.monotonic", "time.perf_counter",
    "os.urandom", "uuid.uuid4", "uuid.uuid1",
})
NONDET_PREFIXES = ("random.", "np.random.", "numpy.random.")

#: Collective-issuing call names (terminal attribute) for the rank rule.
COLLECTIVE_CALLS = frozenset({
    "psum", "pmean", "pmin", "pmax", "ppermute", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter", "process_allgather",
    "pbroadcast",
})

#: Hot-path scope: (path suffix, enclosing function name). Everything
#: lexically nested inside these functions is the step hot path.
HOT_PATH_SCOPES: Tuple[Tuple[str, str], ...] = (
    (os.path.join("train", "loop.py"), "train"),
)
#: Nested helpers inside the hot path that ARE the sanctioned drain
#: points (LossRecords' lazy device→host pulls).
SANCTIONED_DRAIN_FNS = frozenset({"pull"})
#: Modules whose whole job is draining device values to the host —
#: .item()/block_until_ready are legitimate there.
SANCTIONED_SYNC_MODULES = (
    "checkpoint.py", "evaluate.py",
    os.path.join("utils", "metrics.py"),
    os.path.join("utils", "trace.py"),
)
HOT_SYNC_CALLS = frozenset({"np.asarray", "np.array", "numpy.asarray",
                            "numpy.array", "jax.device_get", "device_get"})

#: Serve-tier hot path: (path suffix, function name) of the dispatch
#: pipeline in serve/server.py — the flush stream, the placement
#: callback, and the dispatch loop itself. Unlike the train hot path
#: (one step stalled), a host sync here serializes the WHOLE serving
#: pipeline: every queued bucket on every replica waits behind it.
SERVE_HOT_PATH_SCOPES: Tuple[Tuple[str, str], ...] = (
    (os.path.join("serve", "server.py"), "_dispatch_loop"),
    (os.path.join("serve", "server.py"), "_place"),
    (os.path.join("serve", "server.py"), "_bucket_stream"),
)
#: The serve tier's sanctioned drain: completion workers (``pull``) are
#: WHERE device results become host masks — blocking is their job.
SERVE_SANCTIONED_DRAIN_FNS = frozenset({"pull"})

#: Terminal names of calls that donate their first argument's buffers —
#: the jitted step family the strategies build with donate_argnums
#: (train/loop.py binds them as self.train_step/multi_step/accum_step).
#: Deliberately NOT the `build_*`/`make_*` builders: those take (model,
#: tx) and donate nothing.
DONATING_CALLS = frozenset({"train_step", "multi_step", "accum_step"})


#: Bare f32 dtype spellings (rule ``dtype-policy``): inside a traced
#: function these are accidental upcasts the --dtype policy cannot see;
#: the sanctioned spellings are the named contract constants
#: (precision.LOSS_DTYPE / WGRAD_DTYPE / REDUCE_DTYPE).
F32_LITERAL_DOTTED = frozenset({
    "jnp.float32", "jax.numpy.float32", "np.float32", "numpy.float32",
})
#: Modules whose f32 literals ARE the policy: the precision module
#: itself, the loss family (f32 loss/stats is the LOSS_DTYPE contract's
#: implementation), and the structured-conv rewrites. The Pallas kernel
#: modules (ops/{pallas_kernels,fused_loss,kernels}.py)
#: are deliberately NOT here: the rule reaches kernel bodies (via the
#: ``pallas_call``/``defvjp`` entrypoints above) and their accumulators
#: spell the named contract constants (LOSS_DTYPE/WGRAD_DTYPE/
#: NORM_DTYPE) — a bare f32 there is drift, not policy.
DTYPE_POLICY_SANCTIONED_MODULES = (
    os.path.join("ops", "precision.py"),
    os.path.join("ops", "losses.py"),
    os.path.join("ops", "quant.py"),
    os.path.join("ops", "s2d.py"),
)

#: Checkpoint-restore entry points (rule ``ckpt-dtype-drift``) and the
#: precision-policy seams their enclosing function must route through.
CKPT_RESTORE_CALLS = frozenset({"load_checkpoint", "load_weights"})
CKPT_RESTORE_SEAMS = frozenset({
    "ensure_restored_dtypes", "convert_checkpoint_state",
})
#: checkpoint.py defines the loaders (its internal format dispatch calls
#: load_checkpoint without a session policy in scope — the seam is its
#: CALLERS' obligation).
CKPT_RULE_EXEMPT_MODULES = ("checkpoint.py",)

#: The obs record-path scope (rule ``obs-hot-path``): functions with
#: these names (or any ``record*``/``mark*``) inside ``obs/`` modules
#: are the always-on recording paths — one ring slot / one counter bump
#: is the whole allocation budget, and nothing there may touch a device
#: value. ``mark*`` and the completion verbs cover obs/reqtrace.py's
#: request-trace lifecycle: ``mark_*`` stamps ride the serve dispatch
#: hot path, and ``begin``/``complete``/``finish``/``reject`` are the
#: per-request ledger paths whose appends must be bounded rings.
OBS_RECORD_FN_NAMES = frozenset({
    "inc", "observe", "set", "span", "fire",
    "begin", "complete", "finish", "reject",
})
#: Dotted-prefix spellings of telemetry calls (``from ...obs import
#: flight``, ``from ...obs import defs as obsm``, ``obs.flight.record``)
#: that must never appear inside a traced function.
OBS_CALL_PREFIXES = ("obs.", "obsm.", "flight.")


def _is_obs_module(rel_path: str) -> bool:
    sep = rel_path.replace("\\", "/")
    return "/obs/" in sep or sep.startswith("obs/")


def _is_serve_module(rel_path: str) -> bool:
    sep = rel_path.replace("\\", "/")
    return "/serve/" in sep or sep.startswith("serve/")


def _is_obs_record_fn(name: str) -> bool:
    return name.startswith(("record", "mark")) or name in OBS_RECORD_FN_NAMES


def _bounded_append_targets(tree: ast.AST) -> Set[str]:
    """Names/attribute chains assigned from a ``deque(..., maxlen=...)``
    call anywhere in the file — appends to THOSE are bounded by
    construction (the ring-slot idiom obs-hot-path sanctions)."""
    bounded: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            call, targets = node.value, list(node.targets)
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            # `self._events: deque = deque(maxlen=...)` — the ring idiom
            call, targets = node.value, [node.target]
        else:
            continue
        if not isinstance(call, ast.Call) or _terminal(call.func) != "deque":
            continue
        if not any(kw.arg == "maxlen" for kw in call.keywords):
            continue
        for t in targets:
            key = _expr_key(t)
            if key:
                # `self._events` assigned in __init__ is read as
                # `self._events` at the append site too
                bounded.add(key)
    return bounded


def _donating_call(terminal: str) -> bool:
    return terminal in DONATING_CALLS


#: Every rule this linter can emit — the vocabulary a ``dptlint:
#: disable=`` comment may name. A suppression outside this set is a
#: typo that suppresses nothing (rule ``unknown-suppression``).
KNOWN_RULES = frozenset({
    "parse-error", "trace-nondeterminism", "host-sync-hot-path",
    "serve-hot-path", "use-after-donation", "rank-gated-collective",
    "dtype-policy", "ckpt-dtype-drift", "obs-hot-path", "serve-donation",
})

_SUPPRESS_RE = re.compile(
    r"#\s*dptlint:\s*disable=([\w\-]+(?:\s*,\s*[\w\-]+)*)"
)


def _suppressions(source: str) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(line)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def _dotted(node: ast.AST) -> Optional[str]:
    """``np.random.default_rng`` -> "np.random.default_rng"; None when
    the expression is not a plain name/attribute chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _terminal(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _expr_key(node: ast.AST) -> Optional[str]:
    """Stable key for name/attribute chains ("state", "self.state")."""
    return _dotted(node)


@dataclasses.dataclass
class _FnInfo:
    node: ast.AST
    name: str
    parent: Optional[ast.AST]  # enclosing function node (not class)
    traced: bool = False


class _Scopes(ast.NodeVisitor):
    """Function table with parent links plus the traced-function set."""

    def __init__(self):
        self.fns: Dict[ast.AST, _FnInfo] = {}
        self._stack: List[ast.AST] = []
        self.traced_names: Set[str] = set()

    def _enter(self, node, name):
        parent = self._stack[-1] if self._stack else None
        self.fns[node] = _FnInfo(node=node, name=name, parent=parent)
        self._stack.append(node)
        self.generic_visit(node)
        self._stack.pop()

    def visit_FunctionDef(self, node):
        self._enter(node, node.name)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        self._enter(node, "<lambda>")

    def visit_Call(self, node):
        term = _terminal(node.func)
        if term in TRACE_ENTRYPOINTS:
            positions = CALLABLE_ARG_POSITIONS.get(term, (0,))
            candidates = [
                node.args[i] for i in positions if i < len(node.args)
            ] + [
                kw.value for kw in node.keywords
                if kw.arg in CALLABLE_KEYWORDS
            ]
            flat = []
            for arg in candidates:
                # switch's branches (and the `branches=` keyword) arrive
                # as a literal list/tuple of callables — unpack it
                if isinstance(arg, (ast.List, ast.Tuple)):
                    flat.extend(arg.elts)
                else:
                    flat.append(arg)
            for arg in flat:
                if isinstance(arg, ast.Name):
                    self.traced_names.add(arg.id)
                elif isinstance(arg, ast.Lambda):
                    # the Lambda node is visited after this call; mark it
                    # by identity and resolve in _mark_traced
                    self.traced_names.add(id(arg))  # type: ignore[arg-type]
        self.generic_visit(node)


def _mark_traced(scopes: _Scopes) -> None:
    for info in scopes.fns.values():
        node = info.node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if info.name in scopes.traced_names:
                info.traced = True
            for dec in node.decorator_list:
                base = dec.func if isinstance(dec, ast.Call) else dec
                if _terminal(base) in TRACED_DECORATORS:
                    info.traced = True
        if isinstance(node, ast.Lambda) and id(node) in scopes.traced_names:
            info.traced = True
    # propagate: nested in a traced fn, or nested in a make_* builder
    changed = True
    while changed:
        changed = False
        for info in scopes.fns.values():
            if info.traced:
                continue
            parent = info.parent
            while parent is not None:
                pinfo = scopes.fns[parent]
                if pinfo.traced or pinfo.name.startswith("make_"):
                    info.traced = True
                    changed = True
                    break
                parent = pinfo.parent


def _enclosing_chain(scopes: _Scopes, node_to_fn: Dict[int, ast.AST],
                     node: ast.AST) -> List[_FnInfo]:
    """Innermost-first chain of enclosing functions for a node."""
    fn = node_to_fn.get(id(node))
    chain = []
    while fn is not None:
        info = scopes.fns[fn]
        chain.append(info)
        fn = info.parent
    return chain


def lint_source(source: str, rel_path: str) -> List[Finding]:
    """Lint one file's source. ``rel_path`` appears in findings and
    drives the path-scoped rules."""
    try:
        tree = ast.parse(source)
    except SyntaxError as exc:
        return [Finding(
            rule="parse-error", where=f"{rel_path}:{exc.lineno or 0}",
            message=f"file does not parse: {exc.msg}", layer="lint",
        )]
    suppressed = _suppressions(source)
    scopes = _Scopes()
    scopes.visit(tree)
    _mark_traced(scopes)

    # node -> innermost enclosing function node
    node_to_fn: Dict[int, ast.AST] = {}

    def index(node, current):
        for child in ast.iter_child_nodes(node):
            nxt = current
            if child in scopes.fns:
                nxt = child
            node_to_fn[id(child)] = current
            index(child, nxt)

    index(tree, None)  # type: ignore[arg-type]

    findings: List[Finding] = []
    # (line, rule-name) pairs a suppression actually absorbed — the
    # complement at the end is the stale-suppression report
    used_suppressions: Set[Tuple[int, str]] = set()

    def emit(rule: str, node: ast.AST, message: str):
        line = getattr(node, "lineno", 0)
        rules = suppressed.get(line, set())
        if rule in rules or "all" in rules:
            used_suppressions.add((line, rule if rule in rules else "all"))
            return
        findings.append(Finding(
            rule=rule, where=f"{rel_path}:{line}", message=message,
            layer="lint",
        ))

    in_obs_module = _is_obs_module(rel_path)
    in_serve_module = _is_serve_module(rel_path)
    dtype_sanctioned_file = any(
        rel_path.endswith(sfx) for sfx in DTYPE_POLICY_SANCTIONED_MODULES
    )
    ckpt_rule_exempt_file = any(
        rel_path.endswith(sfx) for sfx in CKPT_RULE_EXEMPT_MODULES
    )
    bounded_appends = _bounded_append_targets(tree) if in_obs_module else set()
    in_hot_file = any(rel_path.endswith(sfx) for sfx, _fn in HOT_PATH_SCOPES)
    hot_fn_names = {fn for sfx, fn in HOT_PATH_SCOPES
                    if rel_path.endswith(sfx)}
    serve_fn_names = {fn for sfx, fn in SERVE_HOT_PATH_SCOPES
                      if rel_path.endswith(sfx)}
    sync_sanctioned_file = any(
        rel_path.endswith(sfx) for sfx in SANCTIONED_SYNC_MODULES
    )

    def _scoped_context(chain: List[_FnInfo], scope_names: Set[str],
                        drain_names: FrozenSet[str]) -> bool:
        """Inside one of ``scope_names`` and not inside a sanctioned
        drain — the shared mechanism of both hot-path rules."""
        if not scope_names:
            return False
        names = [info.name for info in chain]
        if any(n in drain_names for n in names):
            return False
        return any(n in scope_names for n in names)

    def hot_context(chain: List[_FnInfo]) -> bool:
        if not in_hot_file:
            return False
        return _scoped_context(chain, hot_fn_names, SANCTIONED_DRAIN_FNS)

    def serve_hot_context(chain: List[_FnInfo]) -> bool:
        return _scoped_context(
            chain, serve_fn_names, SERVE_SANCTIONED_DRAIN_FNS
        )

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _enclosing_chain(scopes, node_to_fn, node)
        dotted = _dotted(node.func)
        term = _terminal(node.func)

        # -- trace-nondeterminism
        traced = any(info.traced for info in chain)
        if traced and dotted is not None:
            if dotted in NONDET_EXACT or any(
                dotted.startswith(p) for p in NONDET_PREFIXES
            ):
                emit(
                    "trace-nondeterminism", node,
                    f"`{dotted}` inside a traced function: it runs ONCE "
                    f"at trace time and bakes a constant into the "
                    f"compiled step — thread host randomness/time in as "
                    f"an argument instead",
                )

        # -- host-sync: package-wide block_until_ready (both the method
        # form `x.block_until_ready()` and the function form
        # `jax.block_until_ready(x)`) and zero-arg `.item()`
        blocks = term == "block_until_ready" or (
            term == "item"
            and isinstance(node.func, ast.Attribute)
            and not node.args
        )
        if blocks and not sync_sanctioned_file:
            emit(
                "host-sync-hot-path", node,
                f"`{dotted or term}` forces a device→host sync; only "
                f"the sanctioned drain modules "
                f"({', '.join(SANCTIONED_SYNC_MODULES)}) may block on "
                f"device values",
            )

        # -- host-sync: hot-path scoped np.asarray/device_get
        if dotted in HOT_SYNC_CALLS and hot_context(chain):
            emit(
                "host-sync-hot-path", node,
                f"`{dotted}` in the step hot path stalls the async "
                f"dispatch pipeline (one device sync per step) — route "
                f"the value through LossRecords' parked-row drain or a "
                f"sanctioned `pull` helper",
            )

        # -- serve-hot-path: any blocking sync in the serve dispatch
        # pipeline (flush stream / placement / dispatch loop) outside
        # the completion drain
        if (blocks or dotted in HOT_SYNC_CALLS) and serve_hot_context(chain):
            emit(
                "serve-hot-path", node,
                f"`{dotted or term}` blocks on a device value inside the "
                f"serve dispatch pipeline — every queued bucket on every "
                f"replica stalls behind it; device→host reads belong in "
                f"the completion drain (`pull`), which resolves request "
                f"futures off the dispatch path",
            )

        # -- obs-hot-path (a): obs record paths must not block or grow
        # unboundedly — the always-on contract is one ring slot / one
        # counter bump per event (docs/OBSERVABILITY.md)
        in_obs_record = in_obs_module and any(
            _is_obs_record_fn(info.name) for info in chain
        )
        if in_obs_record and (blocks or dotted in HOT_SYNC_CALLS):
            emit(
                "obs-hot-path", node,
                f"`{dotted or term}` blocks on a device value inside an "
                f"obs record path — telemetry is always-on and rides hot "
                f"loops; record host-computed values only",
            )
        if (
            in_obs_record
            and term == "append"
            and isinstance(node.func, ast.Attribute)
        ):
            target = _expr_key(node.func.value)
            if target is not None and target not in bounded_appends:
                emit(
                    "obs-hot-path", node,
                    f"`{target}.append` in an obs record path grows "
                    f"without bound — always-on recording must be a "
                    f"ring: construct `{target}` as "
                    f"`deque(maxlen=...)`",
                )

        # -- dtype-policy (b): astype("float32") / dtype="float32" string
        # spellings in traced code — same hazard as the dotted literal
        # form handled in the node walk below
        if traced and not dtype_sanctioned_file:
            string_f32 = (
                term == "astype"
                and len(node.args) == 1
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value == "float32"
            ) or any(
                kw.arg == "dtype"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value == "float32"
                for kw in node.keywords
            )
            if string_f32:
                emit(
                    "dtype-policy", node,
                    "bare \"float32\" dtype inside a traced function is an "
                    "upcast the --dtype policy cannot see — spell the "
                    "contract (precision.LOSS_DTYPE / WGRAD_DTYPE / "
                    "REDUCE_DTYPE) or thread the policy",
                )

        # -- serve-donation: a donating jit wrapper anywhere in the
        # serving tier — serve executables re-read every operand
        # (request path, swap snapshots, store rehydration), so a
        # donated buffer is a use-after-free on any backend that
        # honors it; the one sanctioned wrapper (engine.serve_jit)
        # never donates
        if in_serve_module and term == "jit" and any(
            kw.arg in ("donate_argnums", "donate_argnames")
            for kw in node.keywords
        ):
            emit(
                "serve-donation", node,
                "`jit(..., donate_*)` in a serve module: serve "
                "executables re-read their operands (every request, "
                "rollback snapshots, AOT-store rehydration), so a "
                "donated buffer is freed under a future read — lower "
                "through serve/engine.serve_jit, which never donates",
            )

        # -- obs-hot-path (b): telemetry calls inside traced functions
        # execute ONCE at trace time — the metric/event silently never
        # records (and a constant side effect bakes into the program)
        if traced and dotted is not None and dotted.startswith(
            OBS_CALL_PREFIXES
        ):
            emit(
                "obs-hot-path", node,
                f"`{dotted}` inside a jit/shard_map-traced function runs "
                f"once at trace time and never again — record from the "
                f"host loop (or a drain) instead",
            )

    # -- dtype-policy (a): bare jnp.float32/np.float32 literal loads in
    # traced functions — the accidental-upcast form (an astype arg, a
    # zeros/full dtype operand). The sanctioned spelling is the named
    # precision constant; the sanctioned modules implement the contract.
    if not dtype_sanctioned_file:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Attribute):
                continue
            if not isinstance(getattr(node, "ctx", None), ast.Load):
                continue
            if _dotted(node) not in F32_LITERAL_DOTTED:
                continue
            chain = _enclosing_chain(scopes, node_to_fn, node)
            if any(info.traced for info in chain):
                emit(
                    "dtype-policy", node,
                    f"bare `{_dotted(node)}` inside a traced function is "
                    f"an f32 upcast the --dtype policy cannot see (bf16 "
                    f"silently re-widens, bf16_params forks the param "
                    f"dtype mid-trace) — spell the contract via "
                    f"precision.LOSS_DTYPE / WGRAD_DTYPE / REDUCE_DTYPE "
                    f"or thread the policy",
                )

    # -- ckpt-dtype-drift: checkpoint restores that bypass the precision
    # policy's restore seams. The enclosing function of every
    # load_checkpoint/load_weights call must also call
    # ensure_restored_dtypes or convert_checkpoint_state (anywhere in its
    # subtree — the seam usually guards the result a few lines later);
    # otherwise params of a drifted dtype flow into the jitted step,
    # which silently RETRACES against donated buffers of the old layout.
    if not ckpt_rule_exempt_file:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _terminal(node.func) not in CKPT_RESTORE_CALLS:
                continue
            chain = _enclosing_chain(scopes, node_to_fn, node)
            enclosing = chain[0].node if chain else tree
            has_seam = any(
                isinstance(sub, ast.Call)
                and _terminal(sub.func) in CKPT_RESTORE_SEAMS
                for sub in ast.walk(enclosing)
            )
            if not has_seam:
                emit(
                    "ckpt-dtype-drift", node,
                    f"`{_terminal(node.func)}` restores state without "
                    f"routing it through a precision restore seam "
                    f"({', '.join(sorted(CKPT_RESTORE_SEAMS))}) — a "
                    f"checkpoint saved under a different --dtype would "
                    f"silently retrace the donated-buffer step instead "
                    f"of re-casting loudly or failing",
                )

    # -- use-after-donation (per function body, EXCLUDING nested defs:
    # a load in a different closure has its own lifetime)
    def walk_own_body(fn_node):
        stack = list(ast.iter_child_nodes(fn_node))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                stack.extend(ast.iter_child_nodes(node))

    for fn_node, info in scopes.fns.items():
        if not isinstance(fn_node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        body_calls: List[Tuple[ast.Call, Optional[str]]] = []
        assigns: List[ast.Assign] = []
        for node in walk_own_body(fn_node):
            if isinstance(node, ast.Assign):
                assigns.append(node)
            if isinstance(node, ast.Call):
                term = _terminal(node.func)
                if term and _donating_call(term) and node.args:
                    body_calls.append((node, _expr_key(node.args[0])))
        for call, donated in body_calls:
            if donated is None:
                continue
            call_line = call.lineno
            # aliases bound from the donated expr BEFORE the call
            aliases = {
                t.id
                for a in assigns
                if a.lineno < call_line and _expr_key(a.value) == donated
                for t in a.targets
                if isinstance(t, ast.Name)
            }
            # is the donated expr rebound by the call's own statement?
            # Matched by the CALL NODE living inside the assignment's
            # value expression, not by line number — a line-wrapped
            # `self.state, loss = (\n    self.train_step(...))` must
            # still count as a rebind.
            rebound_at_call = any(
                any(sub is call for sub in ast.walk(a.value)) and any(
                    donated in {
                        _expr_key(el) for el in (
                            t.elts if isinstance(t, ast.Tuple) else [t]
                        )
                    }
                    for t in a.targets
                )
                for a in assigns
            )
            for node in walk_own_body(fn_node):
                line = getattr(node, "lineno", 0)
                if line <= call_line:
                    continue
                if isinstance(node, (ast.Name, ast.Attribute)) and isinstance(
                    getattr(node, "ctx", None), ast.Load
                ):
                    key = _expr_key(node)
                    if key == donated and not rebound_at_call:
                        emit(
                            "use-after-donation", node,
                            f"`{donated}` was passed in donated position "
                            f"to `{_terminal(call.func)}` at line "
                            f"{call_line}; its buffers are deleted on "
                            f"accelerators — rebind the result instead of "
                            f"re-reading the donated value",
                        )
                    elif key in aliases:
                        emit(
                            "use-after-donation", node,
                            f"`{key}` aliases `{donated}`, which was "
                            f"donated to `{_terminal(call.func)}` at line "
                            f"{call_line}; reading the alias afterwards "
                            f"is a use-after-free unless donation is "
                            f"provably disabled on this path",
                        )

    # -- rank-gated-collective
    def test_calls_process_index(test: ast.AST) -> bool:
        return any(
            isinstance(n, ast.Call) and _terminal(n.func) == "process_index"
            for n in ast.walk(test)
        )

    for node in ast.walk(tree):
        branches: List[ast.AST] = []
        if isinstance(node, (ast.If, ast.While)) and test_calls_process_index(
            node.test
        ):
            branches = list(node.body) + list(node.orelse)
        elif isinstance(node, ast.IfExp) and test_calls_process_index(
            node.test
        ):
            branches = [node.body, node.orelse]
        for br in branches:
            for sub in ast.walk(br):
                if isinstance(sub, ast.Call) and _terminal(
                    sub.func
                ) in COLLECTIVE_CALLS:
                    emit(
                        "rank-gated-collective", sub,
                        f"`{_dotted(sub.func) or _terminal(sub.func)}` is "
                        f"guarded by a process_index() conditional — ranks "
                        f"trace different collective programs and deadlock "
                        f"at the first unmatched collective; issue the "
                        f"collective on every rank (gate only the use of "
                        f"its result)",
                    )

    # -- suppression hygiene: every `dptlint: disable=` comment must
    # name a real rule AND still absorb a finding on its line. A typo'd
    # rule suppresses nothing (silently); a suppression whose rule no
    # longer fires is dead weight that would hide the NEXT regression
    # landing on that line.
    for line, rules in sorted(suppressed.items()):
        for rule in sorted(rules):
            if rule != "all" and rule not in KNOWN_RULES:
                findings.append(Finding(
                    rule="unknown-suppression",
                    where=f"{rel_path}:{line}",
                    message=(
                        f"suppression names unknown rule {rule!r} — not "
                        f"one of this linter's rules, so it suppresses "
                        f"nothing (typo?); known: "
                        f"{', '.join(sorted(KNOWN_RULES))}, all"
                    ),
                    layer="lint",
                ))
            elif (line, rule) not in used_suppressions:
                findings.append(Finding(
                    rule="stale-suppression",
                    where=f"{rel_path}:{line}",
                    message=(
                        f"suppression of {rule!r} is stale: the rule no "
                        f"longer fires on this line — remove the comment "
                        f"(a dead suppression hides the next regression "
                        f"that lands here)"
                    ),
                    layer="lint",
                ))

    return findings


def lint_file(path: str, root: Optional[str] = None) -> List[Finding]:
    rel = os.path.relpath(path, root) if root else path
    with open(path, "r", encoding="utf-8") as f:
        return lint_source(f.read(), rel)


SKIP_DIRS = frozenset({"__pycache__", "native"})


def lint_package(root: Optional[str] = None) -> Tuple[List[Finding], int]:
    """Lint every ``.py`` under ``root`` (default: this package).
    Returns ``(findings, files_linted)``."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings: List[Finding] = []
    n = 0
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in sorted(dirnames) if d not in SKIP_DIRS]
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            n += 1
            findings.extend(
                lint_file(os.path.join(dirpath, fname),
                          root=os.path.dirname(root))
            )
    return findings, n
