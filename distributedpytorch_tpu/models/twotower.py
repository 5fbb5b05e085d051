"""The tower that Nemotron-Labs-TwoTower-30B-A3B-Base-BF16's ``config.json``
defines (``model_type`` ``nemotron_h``): a stack of pre-norm residual
blocks, ``h + Mixer(RMSNorm(h))``, each with ONE mixer chosen by a letter
of ``pattern``: ``M`` Mamba-2, ``E`` sparse experts (sigmoid router, relu^2
experts, one shared expert), ``*`` causal grouped-query attention with
rotary positions. Token embedding in, final norm and an untied head out;
trained by mean next-token cross-entropy.

This is the config's tower only. The published model's second, denoising
tower (adaLN, cross-tower conditioning) and generation by diffusion over
blocks are sized by no key of that config: NOT supported here, and
``serve/`` refuses this model.

The chip's share of a deployment is part of the shape: ``experts_held``
of ``experts_total`` routed experts from ``first_held`` (the router keeps
all its outputs, ``ops/moe.held_experts`` computes the held experts'
part), and ``vocab_size`` rows of the vocabulary (ids, logits and loss
are over that slice).

Every block is a ``jax.checkpoint``: its input is kept and its forward
pass is computed again in the backward pass, so one block's activations
live at a time. Where the device's memory allows (``kept_budget``, from
the shapes and the figure the device reports; never a flag) a block's
checkpoint keeps ``KEPT_ACTIVATIONS`` besides, the results that cost a
large matrix product or a kernel to make again: Mamba-2's ``in_proj``
result, the shared expert's ``up`` result, the attention kernel's q and
k after rotary, its v, its output and log-sum-exp. Everything cheap
(norms, rotary, the router, conv, gates, relu^2) and the scan with its
float32 decay matrices are still computed twice, and a mixer's last
product never was: it feeds the residual sum alone. The blocks are
walked from the last and keep their names while the budget lasts; where
the memory is unknown (the CPU) or too small, a block's input alone is
kept, as before.

Not a flax module: ``init`` returns the nested parameter dict and the
methods take it. Names in the compiled step (``jax.named_scope``):
``mamba2``, ``ssd_scan``, ``attention``, ``moe_router``, ``moe_experts``,
``moe_shared``, ``lm_head``, each under ``block_<i>``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from distributedpytorch_tpu.models.recompute import keep_from_last, kept_budget
from distributedpytorch_tpu.ops import attention_pallas, moe, sequence as seq
from distributedpytorch_tpu.ops.precision import LOSS_DTYPE, SCAN_DTYPE

#: What a block's ``jax.checkpoint`` keeps across its backward pass
#: besides the block's input, by ``checkpoint_name``, where
#: ``kept_budget`` leaves the room: a fixed set, applied to a block whole
#: or not at all.
KEPT_ACTIVATIONS = ("mamba_in_proj", "shared_up", *attention_pallas.RESIDUALS)
#: What the step that keeps each block's input alone holds besides, in
#: bytes a token and unit of ``hidden_size``: every block's input, one
#: block's backward pass with the scan's float32 decays, the logits of a
#: token block (a compile for a described v5e: 2.11 GB of its 4.77 GB of
#: temporaries at 16,384 tokens of width 2688; the rest is the gradient).
WORKING_BYTES_PER_TOKEN_AND_WIDTH = 48


@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    """Sizes under the published config's own key names where it has one."""

    hybrid_override_pattern: str = "MEMEM*EME"
    hidden_size: int = 2688
    vocab_size: int = 16384
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    rope_theta: float = 10000.0
    # experts: the router scores ``experts_total``; this chip holds
    # ``n_routed_experts`` of them from ``first_held``
    experts_total: int = 128
    n_routed_experts: int = 8
    first_held: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # what a router's selection bias moves by after each step, towards the
    # experts that got fewer choices than the mean (ops/moe.balanced_bias)
    router_bias_update_rate: float = 1e-3
    norm_eps: float = 1e-5
    # the PUBLISHED depth: what ``rescale_prenorm_residual`` divides every
    # mixer's output projection by at initialisation (sqrt(2 x layers)),
    # whatever part of the pattern is held here
    num_hidden_layers: int = 52

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def moe_blocks(self) -> Tuple[int, ...]:
        return tuple(i for i, c in enumerate(self.hybrid_override_pattern)
                     if c == "E")


#: The share one chip of sixteen holds (benchmark configuration
#: ``nemotron_twotower_30b_a3b``): the first 9 of the 52 published blocks,
#: experts 0-7 of 128, 16,384 of 131,072 vocabulary rows; every width as
#: published. 666,963,456 parameters.
NEMOTRON_TWOTOWER_SHARE = TwoTowerConfig()


def twotower_config(overrides=None) -> TwoTowerConfig:
    """The published share, with ``overrides`` (a mapping or (key, value)
    pairs: tests and rehearsals shrink sizes through it)."""
    return dataclasses.replace(NEMOTRON_TWOTOWER_SHARE, **dict(overrides or {}))


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


class TwoTower:
    is_stateful = False

    def __init__(self, cfg: TwoTowerConfig = NEMOTRON_TWOTOWER_SHARE,
                 dtype=jnp.bfloat16, memory_bytes=None):
        """``memory_bytes``: what the device that runs the step reports
        as its memory (``utils/backend.device_memory_bytes``), ``None``
        where it reports none."""
        bad = set(cfg.hybrid_override_pattern) - set("ME*")
        if bad:
            raise ValueError(f"unknown block kinds {sorted(bad)} in the pattern")
        self.cfg = cfg
        self.dtype = jnp.dtype(dtype)
        self.memory_bytes = memory_bytes

    # -- parameters ---------------------------------------------------------
    @functools.cached_property
    def parameter_count(self) -> int:
        shapes = jax.eval_shape(self.init, jax.random.key(0))
        return sum(x.size for x in jax.tree.leaves(shapes))

    @property
    def counter_names(self) -> Tuple[str, ...]:
        return counter_names(self.cfg)

    def init(self, rng) -> Dict[str, Any]:
        """Float32 parameters: matrices normal with variance 1 / fan-in
        (a mixer's output projection, the last product before the residual
        sum, divided by sqrt(2 x published layers) besides: the config's
        ``rescale_prenorm_residual``), the embedding unit normal, norm
        scales and Mamba's ``D`` one,
        biases zero, ``A_log`` = log of uniform [1, 16), ``dt_bias`` the
        inverse softplus of a step log-uniform in the config's range."""
        c = self.cfg
        keys = iter(jax.random.split(rng, 16 * (len(c.hybrid_override_pattern) + 2)))

        def dense(shape, fan_in=None):
            return _normal(next(keys), shape, (fan_in or shape[-2]) ** -0.5)

        def out(shape):  # into the residual stream
            return dense(shape) * (2 * c.num_hidden_layers) ** -0.5

        def norm():
            return {"scale": jnp.ones((c.hidden_size,), jnp.float32)}

        params = {"embed": {"embedding": _normal(
            next(keys), (c.vocab_size, c.hidden_size), 1.0)}}
        for i, kind in enumerate(c.hybrid_override_pattern):
            if kind == "M":
                h = c.mamba_num_heads
                dt = jnp.exp(jax.random.uniform(next(keys), (h,)) * (
                    math.log(c.time_step_max) - math.log(c.time_step_min))
                    + math.log(c.time_step_min))
                dt = jnp.maximum(dt, c.time_step_floor)
                mixer = {
                    "in_proj": {"kernel": dense(
                        (c.hidden_size, c.d_inner + c.conv_dim + h))},
                    "conv": {"kernel": dense((c.conv_kernel, c.conv_dim),
                                             c.conv_kernel),
                             "bias": jnp.zeros((c.conv_dim,), jnp.float32)},
                    "A_log": jnp.log(jax.random.uniform(
                        next(keys), (h,), minval=1.0, maxval=16.0)),
                    "D": jnp.ones((h,), jnp.float32),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "norm": {"scale": jnp.ones((c.d_inner,), jnp.float32)},
                    "out_proj": {"kernel": out((c.d_inner, c.hidden_size))},
                }
            elif kind == "E":
                f, fs = c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
                n = c.n_routed_experts
                mixer = {
                    "router": {"kernel": dense((c.hidden_size, c.experts_total)),
                               "bias": jnp.zeros((c.experts_total,), jnp.float32)},
                    "shared": {"up": {"kernel": dense((c.hidden_size, fs))},
                               "down": {"kernel": out((fs, c.hidden_size))}},
                    "experts": {"up": {"kernel": dense((n, c.hidden_size, f))},
                                "down": {"kernel": out((n, f, c.hidden_size))}},
                }
            else:
                q = c.num_attention_heads * c.head_dim
                kv = c.num_key_value_heads * c.head_dim
                mixer = {"q": {"kernel": dense((c.hidden_size, q))},
                         "k": {"kernel": dense((c.hidden_size, kv))},
                         "v": {"kernel": dense((c.hidden_size, kv))},
                         "o": {"kernel": out((q, c.hidden_size))}}
            params[f"block_{i:02d}"] = {"norm": norm(), "mixer": mixer}
        params["final_norm"] = norm()
        params["head"] = {"kernel": dense((c.hidden_size, c.vocab_size))}
        return params

    # -- mixers: (mixer params, normed h (B, S, D)) -> (B, S, D) -------------
    def _mamba(self, p, x):
        c = self.cfg
        with jax.named_scope("mamba2"):
            zxbcdt = seq.matmul(x, p["in_proj"]["kernel"], "bsd,de->bse",
                                name="mamba_in_proj")
            z, xbc, dt = jnp.split(
                zxbcdt, [c.d_inner, c.d_inner + c.conv_dim], axis=-1)
            # causal depthwise convolution: tap j sees the input k-1-j back
            k = c.conv_kernel
            padded = jnp.pad(xbc.astype(SCAN_DTYPE), [(0, 0), (k - 1, 0), (0, 0)])
            w = p["conv"]["kernel"].astype(SCAN_DTYPE)
            conv = sum(padded[:, j:j + x.shape[1]] * w[j] for j in range(k))
            xbc = jax.nn.silu(conv + p["conv"]["bias"]).astype(x.dtype)
            gn = c.n_groups * c.ssm_state_size
            xs, b, cc = jnp.split(xbc, [c.d_inner, c.d_inner + gn], axis=-1)
            lead = x.shape[:2]
            xs = xs.reshape(lead + (c.mamba_num_heads, c.mamba_head_dim))
            b = b.reshape(lead + (c.n_groups, c.ssm_state_size))
            cc = cc.reshape(lead + (c.n_groups, c.ssm_state_size))
            dt = jax.nn.softplus(dt.astype(SCAN_DTYPE) + p["dt_bias"])
            with jax.named_scope("ssd_scan"):
                y = seq.ssd_scan(xs, dt, -jnp.exp(p["A_log"].astype(SCAN_DTYPE)),
                                 b, cc, c.chunk_size)
            y = (y.astype(SCAN_DTYPE)
                 + xs.astype(SCAN_DTYPE) * p["D"][:, None]).astype(x.dtype)
            y = seq.gated_group_rms_norm(
                y.reshape(lead + (c.d_inner,)), z, p["norm"]["scale"],
                c.n_groups, c.norm_eps)
            return seq.matmul(y, p["out_proj"]["kernel"], "bse,ed->bsd")

    def _attention(self, p, x):
        c = self.cfg
        with jax.named_scope("attention"):
            lead = x.shape[:2]
            q = seq.matmul(x, p["q"]["kernel"], "bsd,de->bse").reshape(
                lead + (c.num_attention_heads, c.head_dim))
            k = seq.matmul(x, p["k"]["kernel"], "bsd,de->bse").reshape(
                lead + (c.num_key_value_heads, c.head_dim))
            v = seq.matmul(x, p["v"]["kernel"], "bsd,de->bse").reshape(
                lead + (c.num_key_value_heads, c.head_dim))
            y = seq.causal_attention(seq.rotary(q, c.rope_theta),
                                     seq.rotary(k, c.rope_theta), v)
            return seq.matmul(y.reshape(lead + (-1,)), p["o"]["kernel"],
                              "bse,ed->bsd")

    def attention_kernel_blocks(self, platform: str, seq_len: int) -> int:
        """How many of the model's blocks run attention on the fused
        kernel at this length on ``platform`` (0: blocked XLA)."""
        c = self.cfg
        tile = seq.attention_path(platform, seq_len, c.head_dim,
                                  c.num_attention_heads, c.num_key_value_heads)
        return c.hybrid_override_pattern.count("*") if tile else 0

    def moe_wgrad_kernel_layers(self, platform: str, tokens: int) -> int:
        """How many of the model's expert blocks hand their experts'
        weight gradients to the grouped kernel for a step of ``tokens``
        tokens on ``platform`` (0: the plain loop over tiles)."""
        c = self.cfg
        tile = moe.tile_rows(tokens, c.num_experts_per_tok, c.experts_total)
        kernel = moe.wgrad_path(platform, c.hidden_size,
                                c.moe_intermediate_size, tile)
        return c.hybrid_override_pattern.count("E") if kernel else 0

    def named_activation_bytes(self, batch: int, seq_len: int,
                               platform: str) -> Tuple[int, ...]:
        """Bytes of ``KEPT_ACTIVATIONS`` in each block, for one step of
        ``batch`` sequences of ``seq_len`` tokens on ``platform``, from
        the shapes (tests/test_twotower.py holds them to the traced
        residuals)."""
        c, item, t = self.cfg, self.dtype.itemsize, batch * seq_len
        # blocked XLA recomputes its own blocks and has no names
        attention = attention_pallas.residual_bytes(
            batch, seq_len, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, item) if self.attention_kernel_blocks(
                platform, seq_len) else 0
        block = {
            "M": t * (c.d_inner + c.conv_dim + c.mamba_num_heads) * item,
            "E": t * c.moe_shared_expert_intermediate_size * item,
            "*": attention,
        }
        return tuple(block[kind] for kind in c.hybrid_override_pattern)

    def kept_activation_bytes(self, batch: int, seq_len: int,
                              platform: str) -> Tuple[int, ...]:
        """What each block's ``jax.checkpoint`` keeps of its
        ``named_activation_bytes`` (0: the block's input alone). The
        blocks are walked from the last to the first and a block keeps
        its names while they fit in what is left of ``kept_budget``: the
        backward pass frees the last block's first, so what the first
        blocks keep is what lies beside every other block's backward,
        and they are the first to go without."""
        return keep_from_last(
            self.named_activation_bytes(batch, seq_len, platform),
            kept_budget(self.parameter_count,
                        WORKING_BYTES_PER_TOKEN_AND_WIDTH * batch * seq_len
                        * self.cfg.hidden_size, self.memory_bytes))

    def _experts(self, p, x):
        """``(shared(x) + the held experts' part, counters (3,), the
        chosen experts (T, k), the router's bias after this step)``."""
        c = self.cfg
        lead = x.shape[:2]
        flat = x.reshape(-1, c.hidden_size)
        with jax.named_scope("moe_router"):
            idx, gates = moe.route(
                flat, p["router"]["kernel"], p["router"]["bias"],
                c.num_experts_per_tok, c.norm_topk_prob, c.routed_scaling_factor)
            bias = moe.balanced_bias(
                p["router"]["bias"], moe.expert_load(idx, c.experts_total),
                c.router_bias_update_rate)
        with jax.named_scope("moe_experts"):
            routed, counters = moe.held_experts(
                flat, idx, gates,
                p["experts"]["up"]["kernel"].astype(x.dtype),
                p["experts"]["down"]["kernel"].astype(x.dtype),
                c.experts_total, c.first_held)
        with jax.named_scope("moe_shared"):
            up = seq.matmul(flat, p["shared"]["up"]["kernel"], "td,df->tf",
                            name="shared_up")
            r = jnp.maximum(up, 0)
            shared = seq.matmul(r * r, p["shared"]["down"]["kernel"], "tf,fd->td")
        return ((shared + routed).reshape(lead + (c.hidden_size,)), counters, idx,
                lax.stop_gradient(bias))

    def _block(self, kind, p, h):
        """``(h after the block, (counters, chosen experts, new bias) of
        an expert block or None)``."""
        x = seq.rms_norm(h, p["norm"]["scale"], self.cfg.norm_eps)
        if kind == "M":
            return h + self._mamba(p["mixer"], x), None
        if kind == "*":
            return h + self._attention(p["mixer"], x), None
        y, *routed = self._experts(p["mixer"], x)
        return h + y, tuple(routed)

    # -- the model ----------------------------------------------------------
    def hidden(self, params, tokens, routing: bool = False):
        """``(h (B, S, D) after the final norm, counters (expert blocks,
        3), biases)`` for ``tokens`` (B, S) int32: ``biases`` is the part
        of the parameter tree that the model sets itself, each router's
        selection bias after this step's load. With ``routing`` also each
        expert block's chosen experts, [(B*S, k) int32, ...]."""
        c = self.cfg
        h = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(self.dtype)
        kept = self.kept_activation_bytes(*tokens.shape, jax.default_backend())
        policy = jax.checkpoint_policies.save_only_these_names(*KEPT_ACTIVATIONS)
        counters, chosen, biases = [], [], {}
        for i, kind in enumerate(c.hybrid_override_pattern):
            name = f"block_{i:02d}"
            with jax.named_scope(name):
                block = jax.checkpoint(
                    lambda p, h, kind=kind: self._block(kind, p, h),
                    policy=policy if kept[i] else None)
                h, routed = block(params[name], h)
            if routed is not None:
                counters.append(routed[0])
                chosen.append(routed[1])
                biases[name] = {"mixer": {"router": {"bias": routed[2]}}}
        h = seq.rms_norm(h, params["final_norm"]["scale"], c.norm_eps)
        counters = (jnp.stack(counters) if counters
                    else jnp.zeros((0, len(moe.COUNTERS)), LOSS_DTYPE))
        return (h, counters, biases) + ((chosen,) if routing else ())

    def logits(self, params, tokens):
        """(B, S, V) float32: tests and small sizes only (the loss never
        holds them all)."""
        h = self.hidden(params, tokens)[0]
        return jnp.einsum("bsd,dv->bsv", h, params["head"]["kernel"].astype(h.dtype),
                          preferred_element_type=LOSS_DTYPE)

    def loss(self, params, tokens):
        """``(mean next-token cross-entropy, counters, biases)`` as
        ``hidden`` gives them."""
        h, counters, biases = self.hidden(params, tokens)
        with jax.named_scope("lm_head"):
            return (seq.next_token_loss(h, params["head"]["kernel"], tokens),
                    counters, biases)


#: Counter names of one step, ``<name>/<expert block index>``, in the order
#: ``token_loss`` flattens them.
def counter_names(cfg: TwoTowerConfig) -> Tuple[str, ...]:
    return tuple(f"moe_{name}/{i}" for i in cfg.moe_blocks
                 for name in moe.COUNTERS)

