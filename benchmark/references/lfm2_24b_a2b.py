"""Plain reference of the language model that LFM2-24B-A2B's ``config.json``
defines (``model_type`` ``lfm2_moe``), for training by next-token
cross-entropy: ``jax.numpy``, float32, every matrix product at ``highest``
precision, the short convolution as three shifted products, a full softmax
per block of queries, a loop over the experts. It imports nothing of the
program; parameters arrive as a flat dict keyed by the program's leaf paths
(``layer_02/op/in_proj/kernel``), made by the harness from the seed.

A layer is two residual sub-layers, ``h = h + Op(RMSNorm(h))`` then
``h = h + FF(RMSNorm(h))``:

- ``Op`` by the layer's entry in ``layer_types``. ``conv``: ``B, C, x =
  split3(W_in u)``, ``W_out (C * conv(B * x))``, ``conv`` a causal depthwise
  filter of ``conv_L_cache`` taps with no bias and no activation.
  ``full_attention``: causal grouped-query attention, RMSNorm over each
  head of q and k, then rotary positions over the whole head, scores over
  sqrt(head size), no biases.
- ``FF``: in a layer whose PUBLISHED index is below ``num_dense_layers`` a
  dense SwiGLU ``W2 (silu(W1 u) * W3 u)``; in the others a router
  ``sigmoid(W_g u)`` over all the experts, the top ``num_experts_per_tok``
  chosen by score + selection bias, the gates from the unbiased scores,
  normalised over the chosen and scaled, each expert a SwiGLU, no shared
  expert.
- Final RMSNorm; the head is the embedding's own matrix (tied).

One SEQUENCE at a time and within it one LAYER at a time ((S, D)
activations; the backward pass goes back through the layers, each
recomputed and differentiated on its own), so that a float32 step fits
beside its own float32 state.

Departures from the published description, each also under ``assumed`` in
the configuration's file:
- the share of one chip of four: the layers ``deployment.layers_held`` (by
  published index; ``layer_types`` has their kinds), ``num_experts``
  experts from ``deployment.first_held`` of ``deployment.experts_total``,
  and a slice of ``vocab_size`` rows of the vocabulary. The router scores
  all the experts; what the absent ones would add is left out;
- no attention mask at document boundaries;
- ``mode='fp8'`` (the control) puts every matrix product of the
  projections, the feed-forwards, the experts, the head and attention into
  8-bit floats; the router and the convolution's three products stay
  float32.
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
Q_BLOCK, TOKEN_BLOCK = 1024, 1024


# --- shapes -----------------------------------------------------------------

def dims(config) -> dict:
    c = config
    return {
        "d": c["hidden_size"], "v": c["vocab_size"],
        "hq": c["num_attention_heads"], "hkv": c["num_key_value_heads"],
        "hd": c["hidden_size"] // c["num_attention_heads"],
        "taps": c["conv_L_cache"], "f_dense": c["intermediate_size"],
        "f": c["moe_intermediate_size"], "experts": c["num_experts"],
        "experts_total": c["deployment"]["experts_total"],
        "first_held": c["deployment"]["first_held"],
        "k": c["num_experts_per_tok"],
    }


def layers(config) -> list:
    """``[(name, Op's kind, whether the feed-forward is dense), ...]`` of
    the layers held here: a layer is dense where its published index is
    below ``num_dense_layers``."""
    kinds = config["layer_types"]
    held = config["deployment"].get("layers_held") or list(range(len(kinds)))
    if len(held) != len(kinds):
        raise ValueError("layers_held and layer_types differ in length")
    return [(f"layer_{i:02d}", kind, index < config["num_dense_layers"])
            for i, (kind, index) in enumerate(zip(kinds, held))]


def param_shapes(config) -> dict:
    """``{leaf name: shape}`` of every parameter of the share."""
    z = dims(config)
    d = z["d"]
    out = {"embed/embedding": (z["v"], d)}
    for name, kind, dense in layers(config):
        out[f"{name}/op_norm/scale"] = (d,)
        out[f"{name}/ffn_norm/scale"] = (d,)
        op, ffn = f"{name}/op", f"{name}/ffn"
        if kind == "conv":
            out.update({f"{op}/in_proj/kernel": (d, 3 * d),
                        f"{op}/conv/kernel": (z["taps"], d),
                        f"{op}/out_proj/kernel": (d, d)})
        elif kind == "full_attention":
            q, kv = z["hq"] * z["hd"], z["hkv"] * z["hd"]
            out.update({f"{op}/q/kernel": (d, q), f"{op}/k/kernel": (d, kv),
                        f"{op}/v/kernel": (d, kv), f"{op}/o/kernel": (q, d),
                        f"{op}/q_norm/scale": (z["hd"],),
                        f"{op}/k_norm/scale": (z["hd"],)})
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        if dense:
            out.update({f"{ffn}/gate/kernel": (d, z["f_dense"]),
                        f"{ffn}/up/kernel": (d, z["f_dense"]),
                        f"{ffn}/down/kernel": (z["f_dense"], d)})
        else:
            n, f = z["experts"], z["f"]
            out.update({f"{ffn}/router/kernel": (d, z["experts_total"]),
                        f"{ffn}/router/bias": (z["experts_total"],),
                        f"{ffn}/experts/gate/kernel": (n, d, f),
                        f"{ffn}/experts/up/kernel": (n, d, f),
                        f"{ffn}/experts/down/kernel": (n, f, d)})
    out["final_norm/scale"] = (d,)
    return out


def param_count(config) -> int:
    return sum(math.prod(s) for s in param_shapes(config).values())


#: The keys of the configuration that size the program (its ``--model-arch
#: lfm2``, ``TrainConfig.model_overrides``) under the same names.
PROGRAM_KEYS = (
    "layer_types", "num_dense_layers", "hidden_size", "vocab_size",
    "conv_L_cache", "num_attention_heads", "num_key_value_heads",
    "intermediate_size", "moe_intermediate_size", "num_experts",
    "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
    "router_bias_update_rate", "norm_eps", "num_hidden_layers")


def program_overrides(config) -> dict:
    """What the driver hands the program to size its model from this
    configuration: ``PROGRAM_KEYS`` as they are, the deployment's three
    under the program's names, and the rotary base out of its group."""
    out = {k: config[k] for k in PROGRAM_KEYS if k in config}
    dep = config["deployment"]
    out.update(experts_total=dep["experts_total"], first_held=dep["first_held"],
               layer_indices=dep.get("layers_held"),
               rope_theta=config["rope_parameters"]["rope_theta"])
    return out


def expert_blocks(config) -> int:
    """How many of the layers run here hold routed experts."""
    return sum(not dense for _, _, dense in layers(config))


def held_experts(config) -> int:
    """How many routed experts a sparse layer holds here."""
    return config["num_experts"]


def routed_left_out(config, params):
    """The configuration and the seed's weights of the planted fault
    ``no_routed``: no routed expert is held, the routers still score."""
    return ({**config, "num_experts": 0},
            {k: (v[:0] if "/experts/" in k else v) for k, v in params.items()})


def published(config) -> dict:
    """The configuration uncut: every published layer, every expert, the
    whole vocabulary."""
    pub = config["published"]
    return {**config, "layer_types": pub["layer_types"],
            "num_experts": pub["num_experts"], "vocab_size": pub["vocab_size"],
            "deployment": {**config["deployment"], "first_held": 0,
                           "layers_held": None,
                           "experts_total": pub["num_experts"]}}


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


def matmul_layers(config, tokens: int, sequences: int = 1, routed_rows=None):
    """The model's matrix products for ``sequences`` sequences of
    ``tokens`` tokens: the projections, causal attention (the pairs at or
    below the diagonal), the dense feed-forward, the router, the routed
    experts, the tied head. The embedding is a lookup and the convolution
    three elementwise products. ``routed_rows`` is how many rows one
    sparse layer's router sent to the experts held here, as counted in a
    run (spread evenly over them: FLOPs do not care which expert); None
    takes the uniform share, tokens x k / experts_total rows to each held
    expert."""
    z = dims(config)
    t, d = tokens * sequences, z["d"]
    out = []
    for name, kind, dense in layers(config):
        if kind == "conv":
            out += [Matmul(f"{name}/in_proj", t, d, 3 * d),
                    Matmul(f"{name}/out_proj", t, d, d)]
        else:
            pairs = tokens * (tokens + 1) // 2  # causal (query, key) pairs
            out += [
                Matmul(f"{name}/q", t, d, z["hq"] * z["hd"]),
                Matmul(f"{name}/k", t, d, z["hkv"] * z["hd"]),
                Matmul(f"{name}/v", t, d, z["hkv"] * z["hd"]),
                # scores and weighted values: one (1 x hd x 1) product a pair
                Matmul(f"{name}/scores", pairs, z["hd"], 1, sequences * z["hq"],
                       weight=False),
                Matmul(f"{name}/values", pairs, 1, z["hd"], sequences * z["hq"],
                       weight=False),
                Matmul(f"{name}/o", t, z["hq"] * z["hd"], d),
            ]
        if dense:
            out += [Matmul(f"{name}/ffn_gate", t, d, z["f_dense"]),
                    Matmul(f"{name}/ffn_up", t, d, z["f_dense"]),
                    Matmul(f"{name}/ffn_down", t, z["f_dense"], d)]
        else:
            rows = (max(1, t * z["k"] // z["experts_total"]) if routed_rows is None
                    else routed_rows / max(z["experts"], 1))
            out += [
                Matmul(f"{name}/router", t, d, z["experts_total"]),
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
        if mode not in ("f32", "fp8"):
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


def causal_depthwise(x, kernel):
    """(S, D) through a causal depthwise filter ``kernel`` (taps, D)
    (torch Conv1d, groups = D, padding taps - 1, cut to S): tap j sees the
    input taps - 1 - j positions back. Shifted products, no recurrence."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, [(taps - 1, 0), (0, 0)])
    return sum(padded[j:j + x.shape[0]] * kernel[j] for j in range(taps))


def short_conv(ops, config, q, x):
    bcx = ops.mm("sd,de->se", x, q["in_proj/kernel"])
    b, c, xs = jnp.split(bcx, 3, axis=-1)
    y = c * causal_depthwise(b * xs, q["conv/kernel"])
    return ops.mm("se,ed->sd", y, q["out_proj/kernel"])


def attention(ops, config, q, x):
    z, eps = dims(config), config["norm_eps"]
    theta = config["rope_parameters"]["rope_theta"]
    s = x.shape[0]
    rep = z["hq"] // z["hkv"]
    qh = ops.mm("sd,de->se", x, q["q/kernel"]).reshape(s, z["hq"], z["hd"])
    kh = ops.mm("sd,de->se", x, q["k/kernel"]).reshape(s, z["hkv"], z["hd"])
    vh = ops.mm("sd,de->se", x, q["v/kernel"]).reshape(s, z["hkv"], z["hd"])
    qh = rotary(rms_norm(qh, q["q_norm/scale"], eps), theta).reshape(
        s, z["hkv"], rep, z["hd"])
    kh = rotary(rms_norm(kh, q["k_norm/scale"], eps), theta)

    @jax.checkpoint
    def block(qb, kb, vb, start):
        scores = ops.mm("qgrd,kgd->grqk", qb, kb) / math.sqrt(z["hd"])
        qpos = start + jnp.arange(qb.shape[0])[:, None]
        scores = jnp.where(jnp.arange(kb.shape[0])[None, :] <= qpos, scores, -jnp.inf)
        return ops.mm("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), vb)

    outs = [block(qh[i:i + Q_BLOCK], kh[:i + Q_BLOCK], vh[:i + Q_BLOCK], i)
            for i in range(0, s, Q_BLOCK)]
    y = jnp.concatenate(outs, 0).reshape(s, z["hq"] * z["hd"])
    return ops.mm("se,ed->sd", y, q["o/kernel"])


def swiglu(ops, x, gate, up, down):
    return ops.mm("sf,fd->sd", jax.nn.silu(ops.mm("sd,df->sf", x, gate))
                  * ops.mm("sd,df->sf", x, up), down)


def router(config, q, x):
    """``(chosen experts (S, k), gates (S, k))``: sigmoid scores in
    float32, the selection bias added for the choice alone
    (``use_expert_bias``), the gate from the unbiased scores, normalised
    over the chosen (``norm_topk_prob``) and scaled."""
    scores = jax.nn.sigmoid(jnp.einsum("sd,de->se", x, q["router/kernel"],
                                       precision=HIGHEST))
    _, idx = lax.top_k(lax.stop_gradient(scores) + q["router/bias"],
                       config["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, idx, -1)
    if config["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return idx, gates * config["routed_scaling_factor"]


def experts(ops, config, q, x):
    """For every held expert in turn, its gate times its SwiGLU over EVERY
    row (a row that did not choose the expert has gate 0): no row is
    dropped. ``(what the held experts add (S, D), the chosen (S, k))``."""
    z = dims(config)
    idx, gates = router(config, q, x)
    y = jnp.zeros_like(x)
    for e in range(z["experts"]):
        gate = jnp.sum(jnp.where(idx == z["first_held"] + e, gates, 0.0), -1)
        y = y + gate[:, None] * swiglu(
            ops, x, q["experts/gate/kernel"][e], q["experts/up/kernel"][e],
            q["experts/down/kernel"][e])
    return y, idx


OPS = {"conv": short_conv, "full_attention": attention}


def sub(p: dict, prefix: str) -> dict:
    return {k[len(prefix) + 1:]: v for k, v in p.items()
            if k.startswith(prefix + "/")}


def layer(ops, config, kind, dense, q, h):
    """One layer of one sequence, both sub-layers: ``(h (S, D), the
    experts its router chose (S, k) or None)``. ``q`` holds the layer's own
    leaves (``op_norm/scale``, ``op/...``, ``ffn_norm/scale``, ``ffn/...``)."""
    eps = config["norm_eps"]
    h = h + OPS[kind](ops, config, sub(q, "op"),
                      rms_norm(h, q["op_norm/scale"], eps))
    x, f = rms_norm(h, q["ffn_norm/scale"], eps), sub(q, "ffn")
    if dense:
        return h + swiglu(ops, x, f["gate/kernel"], f["up/kernel"],
                          f["down/kernel"]), None
    y, idx = experts(ops, config, f, x)
    return h + y, idx


def head_loss(ops, config, q, h, tokens):
    """Sum over positions 0 .. S-2 of one sequence of the cross-entropy of
    position t's logits against token t + 1, a block of tokens at a time.
    ``q`` holds ``final_norm/scale`` and ``embed/embedding``, whose matrix
    is the head; ``h`` (S, D) is what the last layer gave."""
    h = rms_norm(h, q["final_norm/scale"], config["norm_eps"])[:-1]
    targets = tokens[1:]

    @jax.checkpoint
    def some(hb, tb):
        lg = ops.mm("sd,vd->sv", hb, q["embed/embedding"])
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])

    return sum(some(h[i:i + TOKEN_BLOCK], targets[i:i + TOKEN_BLOCK])
               for i in range(0, h.shape[0], TOKEN_BLOCK))


def hidden(ops, config, p, tokens):
    """``((S, D) before the final norm, the experts each sparse layer's
    router chose [(S, k), ...])`` for one sequence ``tokens`` (S,)."""
    h, chosen = p["embed/embedding"][tokens], []
    for name, kind, dense in layers(config):
        h, idx = layer(ops, config, kind, dense, sub(p, name), h)
        if idx is not None:
            chosen.append(idx)
    return h, chosen


def logits(ops, config, p, tokens):
    h = rms_norm(hidden(ops, config, p, tokens)[0], p["final_norm/scale"],
                 config["norm_eps"])
    return ops.mm("sd,vd->sv", h, p["embed/embedding"])


def balanced_biases(config, params, loads) -> dict:
    """``{leaf name: each router's selection bias after a step}`` whose
    routers' choices fell on the experts as ``loads`` says ([(experts_total,)
    counts, ...] per sparse layer): the balancing without a loss term,
    ``bias + rate * sign(mean load - load)``, from the bias the step began
    with. No gradient reaches the bias; this is all that moves it."""
    rate = config["router_bias_update_rate"]
    names = [f"{name}/ffn/router/bias"
             for name, _, dense in layers(config) if not dense]
    return {n: params[n] + rate * jnp.sign(jnp.mean(load) - load)
            for n, load in zip(names, loads)}


def make_loss_and_grad(config, mode: str = "f32", tokens: int = None):
    """``(params, tokens (B, S) on the host) -> (mean next-token
    cross-entropy over the batch, its gradient, the routers' choices for
    the first sequence, how many choices of the whole batch fell on each
    expert [(experts_total,), ...] per sparse layer)``. One sequence at a
    time (the loss is a sum over sequences, so the gradients add up), and
    within a sequence one LAYER at a time: the forward pass keeps each
    layer's input, the backward pass goes back through the layers, each
    recomputed and differentiated on its own (the chain rule by hand,
    nothing left out). A program is one layer of one kind (its ``Op`` and
    whether its feed-forward is dense), so that it compiles once per kind
    and its float32 temporaries fit beside the float32 parameters,
    gradient and Adam moments of the whole model. The embedding's gradient
    is the sum of the head's part and the lookup's.

    With ``tokens`` (a sequence's length) every program is compiled here,
    before the caller places a single array, and the function's
    ``temp_bytes`` is the largest of their temporaries (the caller
    reserves that much first: ``drivers/train_tokens.ensure_region``)."""
    ops = Ops(mode)
    held = layers(config)
    kinds = sorted({(kind, dense) for _, kind, dense in held})
    shapes = param_shapes(config)

    def back(kind, dense, q, h, dh):
        _, vjp = jax.vjp(lambda q, h: layer(ops, config, kind, dense, q, h)[0],
                         q, h)
        return vjp(dh)

    top_names = ("final_norm/scale", "embed/embedding")
    forward = {k: jax.jit(lambda q, h, k=k: layer(ops, config, *k, q, h))
               for k in kinds}
    backward = {k: jax.jit(lambda q, h, dh, k=k: back(*k, q, h, dh))
                for k in kinds}
    head = jax.jit(jax.value_and_grad(
        lambda q, h, t, scale: head_loss(ops, config, q, h, t) * scale,
        argnums=(0, 1)))
    embed_grad = jax.jit(lambda g, t, dh: g.at[t].add(dh), donate_argnums=(0,))
    add = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    compiled = []
    if tokens is not None:
        f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
        h = f32((tokens, config["hidden_size"]))
        for k in kinds:
            n = next(name for name, kind, dense in held if (kind, dense) == k)
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
        for (_, kind, dense), q in zip(held, blocks):
            h, idx = forward[(kind, dense)](q, hs[-1])
            hs.append(h)
            if idx is not None:
                chosen.append(idx)

        def keep(name, g):  # add to what the sequences before gave
            acc[name] = add(acc[name], g) if name in acc else g

        loss, (g, dh) = head({k: p[k] for k in top_names}, hs.pop(), tokens, scale)
        for k in top_names:
            keep(k, g[k])
        for (name, kind, dense), q in reversed(list(zip(held, blocks))):
            dq, dh = backward[(kind, dense)](q, hs.pop(), dh)
            for k in list(dq):
                keep(f"{name}/{k}", dq.pop(k))
        # the lookup's part, onto the head's
        acc["embed/embedding"] = embed_grad(acc["embed/embedding"], tokens, dh)
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
