"""Plain reference of the language model that SmallThinker-21BA3B-Instruct's
``config.json`` defines (``model_name`` ``smallthinker_21b_instruct``; the
family's report: arXiv:2507.20984), for training by next-token
cross-entropy: ``jax.numpy``, float32, every matrix product at ``highest``
precision, a full softmax per block of queries against the keys its mask
leaves, a loop over the experts (both of them ``lax`` loops over one
shape: unrolled, one layer's backward took the chip's compiler three
minutes and 8 GB of temporaries). It imports nothing of the program;
parameters arrive as a flat dict keyed by the program's leaf paths
(``layer_02/attn/q/kernel``), made by the harness from the seed.

With ``x`` the residual stream entering a layer and ``n(.)`` an RMSNorm with
a learnt scale (eps ``rms_norm_eps``), no bias anywhere:

1. ``r = W_r x``: one logit an expert, from the layer's RAW input (before
   any norm); ``S = top6(r + b)``; ``g = softmax(r[S])`` over the six
   chosen logits (``moe_primary_router_apply_softmax`` and
   ``norm_topk_prob`` true: a softmax over all 64 renormalised over the
   chosen is the same function). ``b`` is the balancing's selection bias:
   no gradient reaches it and the gates never see it.
2. ``a = x + W_o Attn(q, k, v)``, ``q, k, v`` from ``n_1(x)``: grouped-query
   attention, scores over sqrt(head size). Where the layer's entry in
   ``sliding_window_layout`` is 0: causal over the whole sequence; where 1:
   query ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window_size``.
   Where its entry in ``rope_layout`` is 1: rotary positions over the whole
   head at ``rope_theta``; where 0: no positional encoding at all.
3. ``x' = a + sum_{e in S} g_e W_down,e (relu(W_gate,e u) * W_up,e u)``,
   ``u = n_2(a)``: the choice made before attention is used after it.
4. Token embedding in; final ``n``, then an untied head.

One SEQUENCE at a time and within it one LAYER at a time ((S, D)
activations; the backward pass goes back through the layers, each
recomputed and differentiated on its own), so that a float32 step fits
beside its own float32 state.

Departures from the published description, each also under ``assumed`` in
the configuration's file:
- the share of one chip of four: the layers ``deployment.layers_held`` (by
  published index; the two layouts hold their entries),
  ``moe_num_primary_experts`` experts from ``deployment.first_held`` of
  ``deployment.experts_total``, and a slice of ``vocab_size`` rows of the
  vocabulary. The router scores all the experts; what the absent ones
  would add is left out;
- the selection bias ``b`` (the published router has none; at zero bias
  this is the published forward pass);
- no attention mask at document boundaries;
- ``mode='fp8'`` (the control) puts every matrix product of the
  projections, the experts, the head and attention into 8-bit floats; the
  router stays float32. ``mode='no_window'`` is a planted fault: the window
  ignored, every layer causal over the whole sequence.
"""

from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
from jax import lax

from reference import HIGHEST, _fp8_product

stateful = False
#: Query rows to a block of attention, tokens to a block of the loss.
Q_BLOCK, TOKEN_BLOCK = 512, 1024
MODES = ("f32", "fp8", "no_window")


# --- shapes -----------------------------------------------------------------

def dims(config) -> dict:
    c = config
    return {
        "d": c["hidden_size"], "v": c["vocab_size"],
        "hq": c["num_attention_heads"], "hkv": c["num_key_value_heads"],
        "hd": c["head_dim"], "f": c["moe_ffn_hidden_size"],
        "experts": c["moe_num_primary_experts"],
        "experts_total": c["deployment"]["experts_total"],
        "first_held": c["deployment"]["first_held"],
        "k": c["moe_num_active_primary_experts"],
    }


def layers(config) -> list:
    """``[(name, the layer's window or None, whether it has rotary
    positions), ...]`` of the layers held here."""
    windows, ropes = config["sliding_window_layout"], config["rope_layout"]
    held = config["deployment"].get("layers_held") or list(range(len(windows)))
    if not len(held) == len(windows) == len(ropes):
        raise ValueError("layers_held and the two layouts differ in length")
    return [(f"layer_{i:02d}", config["sliding_window_size"] if w else None,
             bool(r)) for i, (w, r) in enumerate(zip(windows, ropes))]


def param_shapes(config) -> dict:
    """``{leaf name: shape}`` of every parameter of the share."""
    z = dims(config)
    d, q, kv = z["d"], z["hq"] * z["hd"], z["hkv"] * z["hd"]
    n, f = z["experts"], z["f"]
    out = {"embed/embedding": (z["v"], d)}
    for name, _, _ in layers(config):
        out.update({
            f"{name}/router/kernel": (d, z["experts_total"]),
            f"{name}/router/bias": (z["experts_total"],),
            f"{name}/attn_norm/scale": (d,),
            f"{name}/attn/q/kernel": (d, q), f"{name}/attn/k/kernel": (d, kv),
            f"{name}/attn/v/kernel": (d, kv), f"{name}/attn/o/kernel": (q, d),
            f"{name}/ffn_norm/scale": (d,),
            f"{name}/experts/gate/kernel": (n, d, f),
            f"{name}/experts/up/kernel": (n, d, f),
            f"{name}/experts/down/kernel": (n, f, d)})
    out["final_norm/scale"] = (d,)
    out["head/kernel"] = (d, z["v"])
    return out


def param_count(config) -> int:
    return sum(math.prod(s) for s in param_shapes(config).values())


#: The keys of the configuration that size the program (its ``--model-arch
#: smallthinker``, ``TrainConfig.model_overrides``) under the same names.
PROGRAM_KEYS = (
    "sliding_window_layout", "rope_layout", "sliding_window_size",
    "hidden_size", "vocab_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "rope_theta", "moe_ffn_hidden_size", "moe_num_primary_experts",
    "moe_num_active_primary_experts", "moe_primary_router_apply_softmax",
    "norm_topk_prob", "router_bias_update_rate", "rms_norm_eps",
    "num_hidden_layers")


def program_overrides(config) -> dict:
    """What the driver hands the program to size its model from this
    configuration: ``PROGRAM_KEYS`` as they are and the deployment's three
    under the program's names."""
    out = {k: config[k] for k in PROGRAM_KEYS if k in config}
    dep = config["deployment"]
    out.update(experts_total=dep["experts_total"], first_held=dep["first_held"],
               layer_indices=dep.get("layers_held"))
    return out


def expert_blocks(config) -> int:
    """How many of the layers run here hold routed experts: every one."""
    return len(layers(config))


def held_experts(config) -> int:
    """How many routed experts a layer holds here."""
    return config["moe_num_primary_experts"]


def routed_left_out(config, params):
    """The configuration and the seed's weights of the planted fault
    ``no_routed``: no routed expert is held, the routers still score."""
    return ({**config, "moe_num_primary_experts": 0},
            {k: (v[:0] if "/experts/" in k else v) for k, v in params.items()})


def published(config) -> dict:
    """The configuration uncut: every published layer, every expert, the
    whole vocabulary."""
    pub = config["published"]
    return {**config, "sliding_window_layout": pub["sliding_window_layout"],
            "rope_layout": pub["rope_layout"],
            "moe_num_primary_experts": pub["moe_num_primary_experts"],
            "vocab_size": pub["vocab_size"],
            "deployment": {**config["deployment"], "first_held": 0,
                           "layers_held": None,
                           "experts_total": pub["moe_num_primary_experts"]}}


# --- the walk of the matrix products ------------------------------------------

class Matmul(collections.namedtuple(
        "Matmul", "name m k n count weight", defaults=(1, True))):
    """``count`` products of (m x k) by (k x n) in one step. ``weight``:
    the right operand is a parameter (held in the parameter type, its
    gradient too); else both are activations. (A namedtuple: the harness
    loads this file without registering it as a module, which a
    dataclass needs.)"""

    __slots__ = ()

    @property
    def train_flops(self) -> float:
        """Forward and both gradients."""
        return 3.0 * 2.0 * self.m * self.k * self.n * self.count

    def train_bytes(self, act: int, par: int) -> float:
        """Least bytes: each operand read once and each result written
        once, in the forward product and in each of the two gradients."""
        x, y = self.m * self.k * act, self.m * self.n * act
        w = self.k * self.n * (par if self.weight else act)
        return float(3 * (x + w + y) * self.count)

    def roofline_s(self, act, par, peak_flops, peak_bytes_per_s):
        return max(self.train_flops / peak_flops,
                   self.train_bytes(act, par) / peak_bytes_per_s)


def pairs_inside(tokens: int, window=None) -> int:
    """(query, key) pairs of one head of one sequence inside the masks:
    ``0 <= i - j`` and, with a window, ``i - j < window``."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def matmul_layers(config, tokens: int, sequences: int = 1, routed_rows=None):
    """The model's matrix products for ``sequences`` sequences of
    ``tokens`` tokens: the router, the projections, attention over the
    pairs INSIDE its masks (the causal pairs, in a window layer those
    inside the window only), the routed experts, the head. The embedding
    is a lookup. ``routed_rows`` is how many rows one layer's router sent
    to the experts held here, as counted in a run (spread evenly over
    them: FLOPs do not care which expert); None takes the uniform share,
    tokens x k / experts_total rows to each held expert."""
    z = dims(config)
    t, d = tokens * sequences, z["d"]
    out = []
    for name, window, _ in layers(config):
        pairs = pairs_inside(tokens, window)
        rows = (max(1, t * z["k"] // z["experts_total"]) if routed_rows is None
                else routed_rows / max(z["experts"], 1))
        out += [
            Matmul(f"{name}/router", t, d, z["experts_total"]),
            Matmul(f"{name}/q", t, d, z["hq"] * z["hd"]),
            Matmul(f"{name}/k", t, d, z["hkv"] * z["hd"]),
            Matmul(f"{name}/v", t, d, z["hkv"] * z["hd"]),
            # scores and weighted values: one (1 x hd x 1) product a pair
            Matmul(f"{name}/scores", pairs, z["hd"], 1, sequences * z["hq"],
                   weight=False),
            Matmul(f"{name}/values", pairs, 1, z["hd"], sequences * z["hq"],
                   weight=False),
            Matmul(f"{name}/o", t, z["hq"] * z["hd"], d),
            Matmul(f"{name}/experts_gate", rows, d, z["f"], z["experts"]),
            Matmul(f"{name}/experts_up", rows, d, z["f"], z["experts"]),
            Matmul(f"{name}/experts_down", rows, z["f"], d, z["experts"]),
        ]
    out.append(Matmul("head", t, d, z["v"]))
    return out


def train_flops_per_sample(config, tokens: int, routed_rows=None) -> float:
    """Logical forward + backward FLOPs of one packed sequence
    (``routed_rows``: of that one sequence, as ``matmul_layers`` takes it)."""
    return sum(m.train_flops
               for m in matmul_layers(config, tokens, routed_rows=routed_rows))


_DTYPE_BYTES = {"bf16": 2, "bfloat16": 2, "f32": 4, "float32": 4}


def matmul_roofline_seconds(config, tokens: int, sequences: int, peak: dict,
                            routed_rows=None) -> float:
    """Least seconds for one step's matrix products on one chip: per
    product the larger of FLOPs / peak and least bytes / bandwidth. The
    attention entries' bytes are per (query, key) pair and far above what
    a blocked kernel moves, so attention takes its compute bound."""
    act = _DTYPE_BYTES[config["compute_dtype"]]
    par = _DTYPE_BYTES[config["param_dtype"]]
    total = 0.0
    for m in matmul_layers(config, tokens, sequences, routed_rows):
        if m.name.endswith(("/scores", "/values")):
            total += m.train_flops / peak["bf16_flops"]
        else:
            total += m.roofline_s(act, par, peak["bf16_flops"],
                                  peak["hbm_bytes_per_s"])
    return total


# --- arithmetic -----------------------------------------------------------------

class Ops:
    def __init__(self, mode: str = "f32"):
        if mode not in MODES:
            raise ValueError(f"unknown reference mode {mode!r}")
        self.mode = mode

    def mm(self, spec, x, w):
        fn = lambda a, b: jnp.einsum(spec, a, b, precision=HIGHEST)  # noqa: E731
        return _fp8_product(fn, x, w) if self.mode == "fp8" else fn(x, w)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """(S, heads, D): the two halves of the head rotated against each other
    (``rotate_half``), over the whole head."""
    s, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def attention(ops, config, q, x, window, rope):
    """One sequence ``x`` (S, D) after its norm: a block of ``Q_BLOCK``
    queries at a time against the keys its mask leaves, one loop over
    blocks of one shape (a short last block in a pass of its own): in a
    full layer every key, masked above the diagonal; in a window layer the
    ``window - 1`` keys before the block and the block's own, cut from keys
    with as many rows of padding in front, which the mask hides."""
    z = dims(config)
    s = x.shape[0]
    rep = z["hq"] // z["hkv"]
    if ops.mode == "no_window" or (window is not None and window >= s):
        window = None  # the planted fault; a window that reaches everything
    qh = ops.mm("sd,de->se", x, q["q/kernel"]).reshape(s, z["hq"], z["hd"])
    kh = ops.mm("sd,de->se", x, q["k/kernel"]).reshape(s, z["hkv"], z["hd"])
    vh = ops.mm("sd,de->se", x, q["v/kernel"]).reshape(s, z["hkv"], z["hd"])
    if rope:
        qh, kh = rotary(qh, config["rope_theta"]), rotary(kh, config["rope_theta"])
    qh = qh.reshape(s, z["hkv"], rep, z["hd"])
    before = 0 if window is None else window - 1
    kh, vh = (jnp.pad(t, [(before, 0), (0, 0), (0, 0)]) for t in (kh, vh))

    @jax.checkpoint
    def block(start, qb):
        """Queries ``start .. start + n - 1`` against their keys."""
        n = qb.shape[0]
        if window is None:
            kb, vb, first = kh, vh, 0
        else:  # keys start - (window - 1) .. start + n - 1
            kb, vb = (lax.dynamic_slice_in_dim(t, start, before + n)
                      for t in (kh, vh))
            first = start - before
        scores = ops.mm("qgrd,kgd->grqk", qb, kb) / math.sqrt(z["hd"])
        qpos = start + jnp.arange(n)[:, None]
        kpos = first + jnp.arange(kb.shape[0])[None, :]
        seen = (kpos <= qpos) & (kpos >= 0)
        if window is not None:
            seen = seen & (qpos - kpos < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return ops.mm("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), vb)

    whole = s // Q_BLOCK * Q_BLOCK
    outs = []
    if whole:
        starts = jnp.arange(0, whole, Q_BLOCK)
        blocks = qh[:whole].reshape((-1, Q_BLOCK) + qh.shape[1:])
        outs.append(lax.map(lambda a: block(*a), (starts, blocks)).reshape(
            (whole,) + qh.shape[1:]))
    if whole < s:
        outs.append(block(whole, qh[whole:]))
    y = jnp.concatenate(outs, 0).reshape(s, z["hq"] * z["hd"])
    return ops.mm("se,ed->sd", y, q["o/kernel"])


def reglu(ops, x, gate, up, down):
    return ops.mm("sf,fd->sd", jnp.maximum(ops.mm("sd,df->sf", x, gate), 0.0)
                  * ops.mm("sd,df->sf", x, up), down)


def router(config, q, x):
    """``(chosen experts (S, k), gates (S, k))`` from the layer's raw
    input ``x``: logits in float32, the selection bias added for the
    choice alone, the gates a softmax over the chosen logits."""
    logits = jnp.einsum("sd,de->se", x, q["router/kernel"], precision=HIGHEST)
    _, idx = lax.top_k(lax.stop_gradient(logits) + q["router/bias"],
                       config["moe_num_active_primary_experts"])
    return idx, jax.nn.softmax(jnp.take_along_axis(logits, idx, -1), -1)


def experts(ops, config, q, x, idx, gates):
    """For every held expert in turn (one loop over the experts), its gate
    times its ReGLU over EVERY row (a row that did not choose the expert
    has gate 0): no row is dropped. What the held experts add (S, D)."""
    z = dims(config)

    @jax.checkpoint
    def one(y, expert):
        e, w_gate, w_up, w_down = expert
        gate = jnp.sum(jnp.where(idx == z["first_held"] + e, gates, 0.0), -1)
        return y + gate[:, None] * reglu(ops, x, w_gate, w_up, w_down), None

    y, _ = lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(z["experts"]), q["experts/gate/kernel"],
        q["experts/up/kernel"], q["experts/down/kernel"]))
    return y


def sub(p: dict, prefix: str) -> dict:
    return {k[len(prefix) + 1:]: v for k, v in p.items()
            if k.startswith(prefix + "/")}


def layer(ops, config, window, rope, q, h):
    """One layer of one sequence: ``(h (S, D), the experts its router
    chose (S, k))``. ``q`` holds the layer's own leaves (``router/...``,
    ``attn_norm/scale``, ``attn/...``, ``ffn_norm/scale``,
    ``experts/...``). The router reads ``h`` as it enters."""
    eps = config["rms_norm_eps"]
    idx, gates = router(config, q, h)
    h = h + attention(ops, config, sub(q, "attn"),
                      rms_norm(h, q["attn_norm/scale"], eps), window, rope)
    u = rms_norm(h, q["ffn_norm/scale"], eps)
    return h + experts(ops, config, q, u, idx, gates), idx


def head_loss(ops, config, q, h, tokens):
    """Sum over positions 0 .. S-2 of one sequence of the cross-entropy of
    position t's logits against token t + 1, a block of tokens at a time.
    ``q`` holds ``final_norm/scale`` and ``head/kernel``; ``h`` (S, D) is
    what the last layer gave."""
    h = rms_norm(h, q["final_norm/scale"], config["rms_norm_eps"])[:-1]
    targets = tokens[1:]

    @jax.checkpoint
    def some(hb, tb):
        lg = ops.mm("sd,dv->sv", hb, q["head/kernel"])
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])

    return sum(some(h[i:i + TOKEN_BLOCK], targets[i:i + TOKEN_BLOCK])
               for i in range(0, h.shape[0], TOKEN_BLOCK))


def hidden(ops, config, p, tokens):
    """``((S, D) before the final norm, the experts each layer's router
    chose [(S, k), ...])`` for one sequence ``tokens`` (S,)."""
    h, chosen = p["embed/embedding"][tokens], []
    for name, window, rope in layers(config):
        h, idx = layer(ops, config, window, rope, sub(p, name), h)
        chosen.append(idx)
    return h, chosen


def logits(ops, config, p, tokens):
    h = rms_norm(hidden(ops, config, p, tokens)[0], p["final_norm/scale"],
                 config["rms_norm_eps"])
    return ops.mm("sd,dv->sv", h, p["head/kernel"])


def balanced_biases(config, params, loads) -> dict:
    """``{leaf name: each router's selection bias after a step}`` whose
    routers' choices fell on the experts as ``loads`` says ([(experts_total,)
    counts, ...] per layer): the balancing without a loss term,
    ``bias + rate * sign(mean load - load)``, from the bias the step began
    with. No gradient reaches the bias; this is all that moves it."""
    rate = config["router_bias_update_rate"]
    names = [f"{name}/router/bias" for name, _, _ in layers(config)]
    return {n: params[n] + rate * jnp.sign(jnp.mean(load) - load)
            for n, load in zip(names, loads)}


def make_loss_and_grad(config, mode: str = "f32", tokens: int = None):
    """``(params, tokens (B, S) on the host) -> (mean next-token
    cross-entropy over the batch, its gradient, the routers' choices for
    the first sequence, how many choices of the whole batch fell on each
    expert [(experts_total,), ...] per layer)``. One sequence at a time
    (the loss is a sum over sequences, so the gradients add up), and
    within a sequence one LAYER at a time: the forward pass keeps each
    layer's input, the backward pass goes back through the layers, each
    recomputed and differentiated on its own (the chain rule by hand,
    nothing left out). A program is one layer of one kind (its window and
    whether it has rotary positions), so that it compiles once per kind
    and its float32 temporaries fit beside the float32 parameters and
    gradient of the whole model.

    With ``tokens`` (a sequence's length) every program is compiled here,
    before the caller places a single array, and the function's
    ``temp_bytes`` is the largest of their temporaries (the caller
    reserves that much first: ``drivers/train_tokens.ensure_region``)."""
    ops = Ops(mode)
    held = layers(config)
    kinds = list(dict.fromkeys((window, rope) for _, window, rope in held))
    shapes = param_shapes(config)

    def fwd(kind, q, h):
        return layer(ops, config, *kind, q, h)

    def back(kind, q, h, dh):
        _, vjp = jax.vjp(lambda q, h: fwd(kind, q, h)[0], q, h)
        return vjp(dh)

    top_names = ("final_norm/scale", "head/kernel")
    forward = {k: jax.jit(lambda q, h, k=k: fwd(k, q, h)) for k in kinds}
    backward = {k: jax.jit(lambda q, h, dh, k=k: back(k, q, h, dh))
                for k in kinds}
    head = jax.jit(jax.value_and_grad(
        lambda q, h, t, scale: head_loss(ops, config, q, h, t) * scale,
        argnums=(0, 1)))
    embed_grad = jax.jit(
        lambda t, dh: jnp.zeros(shapes["embed/embedding"], jnp.float32
                                ).at[t].add(dh))
    add = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    compiled = []
    if tokens is not None:
        f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
        h = f32((tokens, config["hidden_size"]))
        for k in kinds:
            n = next(name for name, *kind in held if tuple(kind) == k)
            q = {key[len(n) + 1:]: f32(v) for key, v in shapes.items()
                 if key.startswith(n + "/")}
            forward[k] = forward[k].lower(q, h).compile()
            backward[k] = backward[k].lower(q, h, h).compile()
        head = head.lower({k: f32(shapes[k]) for k in top_names}, h,
                          jax.ShapeDtypeStruct((tokens,), jnp.int32),
                          f32(())).compile()
        compiled = [head, *forward.values(), *backward.values()]

    def one(p, tokens, scale, acc):
        blocks = [sub(p, name) for name, _, _ in held]
        hs, chosen = [p["embed/embedding"][tokens]], []
        for (_, window, rope), q in zip(held, blocks):
            h, idx = forward[(window, rope)](q, hs[-1])
            hs.append(h)
            chosen.append(idx)

        def keep(name, g):  # add to what the sequences before gave
            acc[name] = add(acc[name], g) if name in acc else g

        loss, (g, dh) = head({k: p[k] for k in top_names}, hs.pop(), tokens, scale)
        for k in top_names:
            keep(k, g[k])
        for (name, window, rope), q in reversed(list(zip(held, blocks))):
            dq, dh = backward[(window, rope)](q, hs.pop(), dh)
            for k in list(dq):
                keep(f"{name}/{k}", dq.pop(k))
        keep("embed/embedding", embed_grad(tokens, dh))  # the lookup's
        return loss, chosen

    load = jax.jit(lambda idx: jnp.sum(
        idx.reshape(-1)[:, None] == jnp.arange(
            config["deployment"]["experts_total"])[None, :], axis=0
    ).astype(jnp.float32))

    def loss_and_grad(params, tokens):
        scale = jnp.float32(1.0 / (tokens.shape[0] * (tokens.shape[1] - 1)))
        total, acc, routed, loads = 0.0, {}, None, None
        for row in tokens:
            loss, chosen = one(params, jnp.asarray(row), scale, acc)
            routed = chosen if routed is None else routed
            counts = [load(idx) for idx in chosen]
            loads = counts if loads is None else [
                a + b for a, b in zip(loads, counts)]
            total = total + loss
        return total, acc, routed, loads

    loss_and_grad.temp_bytes = max(
        (int(c.memory_analysis().temp_size_in_bytes) for c in compiled), default=0)
    return loss_and_grad
