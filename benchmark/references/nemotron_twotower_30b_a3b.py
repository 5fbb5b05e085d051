"""Plain reference of the tower that Nemotron-Labs-TwoTower-30B-A3B-Base-
BF16's ``config.json`` defines (``model_type`` ``nemotron_h``), for
training by next-token cross-entropy: ``jax.numpy``, float32, every matrix
product at ``highest`` precision, a step-by-step Mamba-2 recurrence, a full
softmax per block of queries, a loop over the experts. It imports nothing
of the program; parameters arrive as a flat dict keyed by the program's
leaf paths (``block_03/mixer/in_proj/kernel``), made by the harness from
the seed.

A block is ``h + Mixer(RMSNorm(h))``, the mixer chosen by a letter of
``hybrid_override_pattern``: ``M`` Mamba-2, ``E`` experts, ``*`` attention.
One SEQUENCE at a time and within it one BLOCK at a time ((S, D)
activations; the backward pass goes back through the blocks, each
recomputed and differentiated on its own), so that a float32 step fits
beside its own float32 state.

Departures from the published description, each also under ``assumed`` in
the configuration's file:
- only the tower that ``config.json`` sizes; the second, denoising tower
  (adaLN, cross-tower conditioning) and block-diffusion decoding are sized
  by no key of it and are left out;
- the share of one chip of sixteen: ``n_routed_experts`` experts from
  ``deployment.first_held`` of ``deployment.experts_total``, and a slice of
  ``vocab_size`` rows of the vocabulary. The router scores all the experts;
  what the absent ones would add is left out;
- no state reset and no attention mask at document boundaries;
- ``mode='fp8'`` (the control) puts every matrix product of the
  projections, the experts, the head and attention into 8-bit floats; the
  recurrence has no matrix product and stays float32.
"""

from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
from jax import lax

from reference import HIGHEST, _fp8_product

stateful = False
#: Query rows to a block of attention, tokens to a block of the loss, steps
#: of the recurrence between two kept states.
Q_BLOCK, TOKEN_BLOCK, SCAN_BLOCK = 1024, 1024, 128


# --- shapes -----------------------------------------------------------------

def dims(config) -> dict:
    c = config
    d_inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    return {
        "d": c["hidden_size"], "v": c["vocab_size"], "d_inner": d_inner,
        "conv_dim": d_inner + 2 * c["n_groups"] * c["ssm_state_size"],
        "h": c["mamba_num_heads"], "p": c["mamba_head_dim"],
        "g": c["n_groups"], "n": c["ssm_state_size"],
        "hq": c["num_attention_heads"], "hkv": c["num_key_value_heads"],
        "hd": c["head_dim"], "experts": c["n_routed_experts"],
        "experts_total": c["deployment"]["experts_total"],
        "first_held": c["deployment"]["first_held"],
        "k": c["num_experts_per_tok"], "f": c["moe_intermediate_size"],
        "fs": c["moe_shared_expert_intermediate_size"],
    }


def param_shapes(config) -> dict:
    """``{leaf name: shape}`` of every parameter of the share."""
    z = dims(config)
    out = {"embed/embedding": (z["v"], z["d"])}
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        b = f"block_{i:02d}"
        out[f"{b}/norm/scale"] = (z["d"],)
        m = f"{b}/mixer"
        if kind == "M":
            out.update({
                f"{m}/in_proj/kernel": (z["d"], z["d_inner"] + z["conv_dim"] + z["h"]),
                f"{m}/conv/kernel": (config["conv_kernel"], z["conv_dim"]),
                f"{m}/conv/bias": (z["conv_dim"],),
                f"{m}/A_log": (z["h"],), f"{m}/D": (z["h"],),
                f"{m}/dt_bias": (z["h"],),
                f"{m}/norm/scale": (z["d_inner"],),
                f"{m}/out_proj/kernel": (z["d_inner"], z["d"]),
            })
        elif kind == "E":
            out.update({
                f"{m}/router/kernel": (z["d"], z["experts_total"]),
                f"{m}/router/bias": (z["experts_total"],),
                f"{m}/shared/up/kernel": (z["d"], z["fs"]),
                f"{m}/shared/down/kernel": (z["fs"], z["d"]),
                f"{m}/experts/up/kernel": (z["experts"], z["d"], z["f"]),
                f"{m}/experts/down/kernel": (z["experts"], z["f"], z["d"]),
            })
        elif kind == "*":
            q, kv = z["hq"] * z["hd"], z["hkv"] * z["hd"]
            out.update({f"{m}/q/kernel": (z["d"], q), f"{m}/k/kernel": (z["d"], kv),
                        f"{m}/v/kernel": (z["d"], kv), f"{m}/o/kernel": (q, z["d"])})
        else:
            raise ValueError(f"unknown block kind {kind!r}")
    out["final_norm/scale"] = (z["d"],)
    out["head/kernel"] = (z["d"], z["v"])
    return out


def param_count(config) -> int:
    return sum(math.prod(s) for s in param_shapes(config).values())


#: The keys of the configuration that size the program (its ``--model-arch
#: twotower``, ``TrainConfig.model_overrides``) under the same names.
PROGRAM_KEYS = (
    "hybrid_override_pattern", "hidden_size", "vocab_size", "mamba_num_heads",
    "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
    "time_step_min", "time_step_max", "time_step_floor", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "n_routed_experts",
    "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "router_bias_update_rate", "num_hidden_layers")


def program_overrides(config) -> dict:
    """What the driver hands the program to size its model from this
    configuration: ``PROGRAM_KEYS`` as they are, the deployment's two, and
    the norm's epsilon under the program's name for it."""
    out = {k: config[k] for k in PROGRAM_KEYS if k in config}
    out.update(experts_total=config["deployment"]["experts_total"],
               first_held=config["deployment"]["first_held"],
               norm_eps=config["layer_norm_epsilon"])
    return out


def expert_blocks(config) -> int:
    """How many of the blocks run here hold routed experts."""
    return config["hybrid_override_pattern"].count("E")


def routed_left_out(config, params):
    """The configuration and the seed's weights of the planted fault
    ``no_routed``: no routed expert is held, the routers still score."""
    return ({**config, "n_routed_experts": 0},
            {k: (v[:0] if "/experts/" in k else v) for k, v in params.items()})


def published(config) -> dict:
    """The configuration uncut: the published pattern, every expert, the
    whole vocabulary."""
    pub = config["published"]
    return {**config, "hybrid_override_pattern": pub["hybrid_override_pattern"],
            "n_routed_experts": pub["n_routed_experts"],
            "vocab_size": pub["vocab_size"],
            "deployment": {**config["deployment"], "first_held": 0,
                           "experts_total": pub["n_routed_experts"]}}


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
    ``tokens`` tokens: projections, the scan's chunk products, causal
    attention (the pairs at or below the diagonal), the shared expert, the
    routed experts, the head. The embedding is a lookup. ``routed_rows``
    is how many rows one expert block's routers sent to the experts held
    here, as counted in a run (spread evenly over them: FLOPs do not care
    which expert); None takes the uniform share, tokens x k / experts_total
    rows to each held expert."""
    z = dims(config)
    t = tokens * sequences
    out = []
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        b = f"block_{i:02d}"
        if kind == "M":
            q = config["chunk_size"]
            chunks = -(-tokens // q) * sequences
            out += [
                Matmul(f"{b}/in_proj", t, z["d"], z["d_inner"] + z["conv_dim"] + z["h"]),
                Matmul(f"{b}/ssd_cb", q, z["n"], q, chunks * z["g"], weight=False),
                Matmul(f"{b}/ssd_diag", q, q, z["p"], chunks * z["h"], weight=False),
                Matmul(f"{b}/ssd_states", z["p"], q, z["n"], chunks * z["h"], weight=False),
                Matmul(f"{b}/ssd_off", q, z["n"], z["p"], chunks * z["h"], weight=False),
                Matmul(f"{b}/out_proj", t, z["d_inner"], z["d"]),
            ]
        elif kind == "E":
            rows = (max(1, t * z["k"] // z["experts_total"]) if routed_rows is None
                    else routed_rows / max(z["experts"], 1))
            out += [
                Matmul(f"{b}/router", t, z["d"], z["experts_total"]),
                Matmul(f"{b}/shared_up", t, z["d"], z["fs"]),
                Matmul(f"{b}/shared_down", t, z["fs"], z["d"]),
                Matmul(f"{b}/experts_up", rows, z["d"], z["f"], z["experts"]),
                Matmul(f"{b}/experts_down", rows, z["f"], z["d"], z["experts"]),
            ]
        else:
            pairs = tokens * (tokens + 1) // 2  # causal (query, key) pairs
            out += [
                Matmul(f"{b}/q", t, z["d"], z["hq"] * z["hd"]),
                Matmul(f"{b}/k", t, z["d"], z["hkv"] * z["hd"]),
                Matmul(f"{b}/v", t, z["d"], z["hkv"] * z["hd"]),
                # scores and weighted values: one (1 x hd x 1) product a pair
                Matmul(f"{b}/scores", pairs, z["hd"], 1, sequences * z["hq"], weight=False),
                Matmul(f"{b}/values", pairs, 1, z["hd"], sequences * z["hq"], weight=False),
                Matmul(f"{b}/o", t, z["hq"] * z["hd"], z["d"]),
            ]
    out.append(Matmul("head", t, z["d"], z["v"]))
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


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def rotary(x, theta):
    """(S, heads, D): the two halves of the head rotated against each other
    (``rotate_half``), over the whole head (``partial_rotary_factor`` 1)."""
    s, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def recurrence(x, dt, a, b, c):
    """Mamba-2's recurrence, one step at a time:
    ``h_t = exp(dt_t a) h_{t-1} + dt_t b_t x_t^T``, ``y_t = c_t h_t``.
    ``x`` (L, H, P), ``dt`` (L, H), ``a`` (H,), ``b`` and ``c`` (L, G, N)
    with the heads of a group sharing its ``b`` and ``c``. The state
    (H, P, N) is kept every ``SCAN_BLOCK`` steps and the steps between are
    recomputed in the backward pass: the same arithmetic, less memory."""
    length, heads, p = x.shape
    per = heads // b.shape[1]
    pad = -length % SCAN_BLOCK
    if pad:  # steps that neither decay nor write
        x, dt, b, c = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                       for t in (x, dt, b, c))

    def step(h, xs):
        xt, dtt, bt, ct = xs
        bt, ct = jnp.repeat(bt, per, axis=0), jnp.repeat(ct, per, axis=0)
        h = (h * jnp.exp(dtt * a)[:, None, None]
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return h, jnp.sum(h * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def block(h, xs):
        return lax.scan(step, h, xs, unroll=8)

    blocks = [t.reshape((-1, SCAN_BLOCK) + t.shape[1:]) for t in (x, dt, b, c)]
    _, y = lax.scan(block, jnp.zeros((heads, p, b.shape[-1]), jnp.float32),
                    tuple(blocks))
    return y.reshape((-1, heads, p))[:length]


def mamba(ops, config, q, x):
    z, eps = dims(config), config["layer_norm_epsilon"]
    zxbcdt = ops.mm("sd,de->se", x, q["in_proj/kernel"])
    gate, xbc, dt = jnp.split(
        zxbcdt, [z["d_inner"], z["d_inner"] + z["conv_dim"]], axis=-1)
    # causal depthwise convolution (torch Conv1d, padding k - 1, cut to S):
    # tap j of the kernel sees the input k - 1 - j steps back
    k = config["conv_kernel"]
    padded = jnp.pad(xbc, [(k - 1, 0), (0, 0)])
    conv = sum(padded[j:j + x.shape[0]] * q["conv/kernel"][j] for j in range(k))
    xbc = jax.nn.silu(conv + q["conv/bias"])
    gn = z["g"] * z["n"]
    xs, b, c = jnp.split(xbc, [z["d_inner"], z["d_inner"] + gn], axis=-1)
    xs = xs.reshape(-1, z["h"], z["p"])
    dt = jax.nn.softplus(dt + q["dt_bias"])  # time_step_limit (0, inf): no clamp
    y = recurrence(xs, dt, -jnp.exp(q["A_log"]),
                   b.reshape(-1, z["g"], z["n"]), c.reshape(-1, z["g"], z["n"]))
    y = (y + xs * q["D"][:, None]).reshape(-1, z["d_inner"])
    # gated RMSNorm, the statistics inside each of n_groups slices
    y = (y * jax.nn.silu(gate)).reshape(-1, z["g"], z["d_inner"] // z["g"])
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    y = y.reshape(-1, z["d_inner"]) * q["norm/scale"]
    return ops.mm("se,ed->sd", y, q["out_proj/kernel"])


def attention(ops, config, q, x):
    z = dims(config)
    s = x.shape[0]
    rep = z["hq"] // z["hkv"]
    qh = rotary(ops.mm("sd,de->se", x, q["q/kernel"]).reshape(s, z["hq"], z["hd"]),
                config["rope_theta"]).reshape(s, z["hkv"], rep, z["hd"])
    kh = rotary(ops.mm("sd,de->se", x, q["k/kernel"]).reshape(s, z["hkv"], z["hd"]),
                config["rope_theta"])
    vh = ops.mm("sd,de->se", x, q["v/kernel"]).reshape(s, z["hkv"], z["hd"])

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


def router(config, q, x):
    """``(chosen experts (S, k), gates (S, k))``: sigmoid scores in
    float32, the selection bias added for the choice alone, the gate from
    the unbiased scores, normalised over the chosen and scaled
    (``n_group`` = ``topk_group`` = 1: no group limit)."""
    scores = jax.nn.sigmoid(jnp.einsum("sd,de->se", x, q["router/kernel"],
                                       precision=HIGHEST))
    _, idx = lax.top_k(lax.stop_gradient(scores) + q["router/bias"],
                       config["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, idx, -1)
    if config["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return idx, gates * config["routed_scaling_factor"]


def experts(ops, config, q, x, only_routed: bool = False):
    """The shared expert plus, for every held expert in turn, its gate
    times ``W_down relu(W_up x)^2`` over EVERY row (a row that did not
    choose the expert has gate 0): no row is dropped."""
    z = dims(config)
    idx, gates = router(config, q, x)
    y = 0.0 if only_routed else ops.mm(
        "sf,fd->sd", relu2(ops.mm("sd,df->sf", x, q["shared/up/kernel"])),
        q["shared/down/kernel"])
    for e in range(z["experts"]):
        gate = jnp.sum(jnp.where(idx == z["first_held"] + e, gates, 0.0), -1)
        h = relu2(ops.mm("sd,df->sf", x, q["experts/up/kernel"][e]))
        y = y + gate[:, None] * ops.mm("sf,fd->sd", h, q["experts/down/kernel"][e])
    return y, idx


MIXERS = {"M": mamba, "*": attention}


def sub(p: dict, prefix: str) -> dict:
    return {k[len(prefix) + 1:]: v for k, v in p.items()
            if k.startswith(prefix + "/")}


def block(ops, config, kind, q, h):
    """One block, ``h + Mixer(RMSNorm(h))``, of one sequence: ``(h (S, D),
    the experts its router chose (S, k) or None)``. ``q`` holds the
    block's own leaves (``norm/scale``, ``mixer/...``)."""
    x = rms_norm(h, q["norm/scale"], config["layer_norm_epsilon"])
    m = sub(q, "mixer")
    if kind == "E":
        y, idx = experts(ops, config, m, x)
        return h + y, idx
    return h + MIXERS[kind](ops, config, m, x), None


def head_loss(ops, config, q, h, tokens):
    """Sum over positions 0 .. S-2 of one sequence of the cross-entropy of
    position t's logits against token t + 1, a block of tokens at a time.
    ``q`` holds ``final_norm/scale`` and ``head/kernel``; ``h`` (S, D) is
    what the last block gave."""
    h = rms_norm(h, q["final_norm/scale"], config["layer_norm_epsilon"])[:-1]
    targets = tokens[1:]

    @jax.checkpoint
    def some(hb, tb):
        lg = ops.mm("sd,dv->sv", hb, q["head/kernel"])
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])

    return sum(some(h[i:i + TOKEN_BLOCK], targets[i:i + TOKEN_BLOCK])
               for i in range(0, h.shape[0], TOKEN_BLOCK))


def hidden(ops, config, p, tokens):
    """``((S, D) before the final norm, the experts each expert block's
    router chose [(S, k), ...])`` for one sequence ``tokens`` (S,)."""
    h, chosen = p["embed/embedding"][tokens], []
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        h, idx = block(ops, config, kind, sub(p, f"block_{i:02d}"), h)
        if idx is not None:
            chosen.append(idx)
    return h, chosen


def logits(ops, config, p, tokens):
    h = rms_norm(hidden(ops, config, p, tokens)[0], p["final_norm/scale"],
                 config["layer_norm_epsilon"])
    return ops.mm("sd,dv->sv", h, p["head/kernel"])


def balanced_biases(config, params, loads) -> dict:
    """``{leaf name: each router's selection bias after a step}`` whose
    routers' choices fell on the experts as ``loads`` says ([(experts_total,)
    counts, ...] per expert block): the balancing without a loss term,
    ``bias + rate * sign(mean load - load)``, from the bias the step began
    with. No gradient reaches the bias; this is all that moves it."""
    rate = config["router_bias_update_rate"]
    names = [f"block_{i:02d}/mixer/router/bias"
             for i, kind in enumerate(config["hybrid_override_pattern"])
             if kind == "E"]
    return {n: params[n] + rate * jnp.sign(jnp.mean(load) - load)
            for n, load in zip(names, loads)}


def make_loss_and_grad(config, mode: str = "f32", tokens: int = None):
    """``(params, tokens (B, S) on the host) -> (mean next-token
    cross-entropy over the batch, its gradient, the routers' choices for
    the first sequence, how many choices of the whole batch fell on each
    expert [(experts_total,), ...] per expert block)``. One sequence at a time (the loss is a sum over
    sequences, so the gradients add up), and within a sequence one BLOCK
    at a time: the forward pass keeps each block's input, the backward
    pass goes back through the blocks, each recomputed and differentiated
    on its own (the chain rule by hand, nothing left out). A program is
    one block of one kind, so that it compiles once per kind and its
    float32 temporaries fit beside the float32 parameters, gradient and
    Adam moments of the whole model.

    With ``tokens`` (a sequence's length) every program is compiled here,
    before the caller places a single array, and the function's
    ``temp_bytes`` is the largest of their temporaries: the chip keeps
    loaded programs' temporaries in one region at the bottom of its
    memory, which can only grow while nothing lies above it, so the caller
    can reserve that much first (``drivers/train_tokens.ensure_region``)."""
    ops = Ops(mode)
    pattern = config["hybrid_override_pattern"]
    shapes = param_shapes(config)

    def back(kind, q, h, dh):
        _, vjp = jax.vjp(lambda q, h: block(ops, config, kind, q, h)[0], q, h)
        return vjp(dh)

    top_names = ("final_norm/scale", "head/kernel")
    forward = {k: jax.jit(lambda q, h, k=k: block(ops, config, k, q, h))
               for k in set(pattern)}
    backward = {k: jax.jit(lambda q, h, dh, k=k: back(k, q, h, dh))
                for k in set(pattern)}
    head = jax.jit(jax.value_and_grad(
        lambda q, h, t, scale: head_loss(ops, config, q, h, t) * scale,
        argnums=(0, 1)))
    embed_grad = jax.jit(lambda table, t, dh: jnp.zeros_like(table).at[t].add(dh))
    add = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    compiled = []
    if tokens is not None:
        f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
        h = f32((tokens, config["hidden_size"]))
        for kind in set(pattern):
            n = f"block_{pattern.index(kind):02d}"
            q = {k[len(n) + 1:]: f32(v) for k, v in shapes.items()
                 if k.startswith(n + "/")}
            forward[kind] = forward[kind].lower(q, h).compile()
            backward[kind] = backward[kind].lower(q, h, h).compile()
        head = head.lower({k: f32(shapes[k]) for k in top_names}, h,
                          jax.ShapeDtypeStruct((tokens,), jnp.int32),
                          f32(())).compile()
        compiled = [head, *forward.values(), *backward.values()]

    def one(p, tokens, scale, acc):
        names = [f"block_{i:02d}" for i in range(len(pattern))]
        blocks = [sub(p, n) for n in names]
        hs, chosen = [p["embed/embedding"][tokens]], []
        for kind, q in zip(pattern, blocks):
            h, idx = forward[kind](q, hs[-1])
            hs.append(h)
            if idx is not None:
                chosen.append(idx)

        def keep(name, g):  # add to what the sequences before gave
            acc[name] = add(acc[name], g) if name in acc else g

        loss, (g, dh) = head({k: p[k] for k in top_names}, hs.pop(), tokens, scale)
        for k in top_names:
            keep(k, g[k])
        for kind, q, n in reversed(list(zip(pattern, blocks, names))):
            dq, dh = backward[kind](q, hs.pop(), dh)
            for k in list(dq):
                keep(f"{n}/{k}", dq.pop(k))
        keep("embed/embedding", embed_grad(p["embed/embedding"], tokens, dh))
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
