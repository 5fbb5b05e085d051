"""The language model that LFM2-24B-A2B's ``config.json`` defines
(``model_type`` ``lfm2_moe``): a stack of pre-norm layers with TWO residual
sub-layers each,

    h = h + Op(RMSNorm(h));   h = h + FF(RMSNorm(h))

``Op`` by the layer's entry in ``layer_types``: ``conv`` the gated short
convolution (``B, C, x = split3(W_in u)``, ``W_out (C * conv(B * x))``, a
causal depthwise filter of ``conv_L_cache`` taps, no bias, no activation)
or ``full_attention`` causal grouped-query attention with an RMSNorm over
each head of q and k before the rotary positions. ``FF``: a dense SwiGLU
``W2 (silu(W1 u) * W3 u)`` in the published layers before
``num_dense_layers``, after them sparse experts: a sigmoid router over all
the experts whose selection bias moves the choice alone, the gates
normalised over the chosen, each expert a SwiGLU, no shared expert. Token
embedding in, final norm and the embedding's own matrix as the head out
(tied); trained by mean next-token cross-entropy.

The chip's share of a deployment is part of the shape: ``layer_indices``
are the PUBLISHED indices of the layers held here (``layer_types`` has
their kinds; a layer is dense where its published index is below
``num_dense_layers``), ``num_experts`` of ``experts_total`` routed experts
from ``first_held`` (the router keeps all its outputs,
``ops/moe.held_experts`` computes the held experts' part and nothing
stands in for the others), and ``vocab_size`` rows of the vocabulary.

Every layer is a ``jax.checkpoint`` as in ``models/twotower.py``, and
``models/recompute.py`` holds what both share: a layer keeps its input
and, where the device's memory allows, ``KEPT_ACTIVATIONS``: the short
convolution's ``in_proj`` result and the attention kernel's residuals.
The expert layer keeps nothing by name (its backward reads its own
inputs), and the dense feed-forward, 11776 wide, is a checkpoint of its
own a block of tokens at a time and keeps each block's input alone.

Trained on one device; not served (``serve/`` refuses it), no mesh, no
``--grad-accum``. Names in the compiled step (``jax.named_scope``), each
under ``layer_<i>``: ``short_conv``, ``attention``, ``dense_ffn``,
``moe_router``, ``moe_experts``; and ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from distributedpytorch_tpu.models.recompute import (
    gradients_before_input,
    keep_from_last,
    kept_budget,
)
from distributedpytorch_tpu.ops import attention_pallas, moe, sequence as seq
from distributedpytorch_tpu.ops.precision import LOSS_DTYPE

#: What a layer's ``jax.checkpoint`` keeps besides the layer's input, by
#: ``checkpoint_name``, where the budget leaves the room: a fixed set,
#: applied to a layer whole or not at all.
KEPT_ACTIVATIONS = ("conv_in_proj", *attention_pallas.RESIDUALS)
#: What the step that keeps each layer's input alone holds besides the
#: gradient, in bytes a token and unit of ``hidden_size``: five layers'
#: inputs, one layer's backward pass, the expert layer's tile buffers, one
#: block of the dense feed-forward, the logits of a token block. A compile
#: for a described v5e at 16,384 tokens reads 24 (4.96 GB of temporaries:
#: 3.15 of gradient, 0.99 kept; PERF.md §6, PR 35); a third more for room.
WORKING_BYTES_PER_TOKEN_AND_WIDTH = 32
#: Tokens to a block of the dense feed-forward: its two first results are
#: 11776 wide, and a block of them at a time is what the backward holds.
FFN_BLOCK = 4096


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """Sizes under the published config's own key names where it has one."""

    # the layers held here: their kinds, and their published indices
    # (None: 0, 1, ...). A layer below ``num_dense_layers`` is dense.
    layer_types: Tuple[str, ...] = ("conv", "full_attention", "conv", "conv",
                                    "conv")
    layer_indices: Optional[Tuple[int, ...]] = (0, 2, 3, 4, 5)
    num_dense_layers: int = 2
    hidden_size: int = 2048
    vocab_size: int = 16384
    conv_L_cache: int = 3
    # attention: heads of hidden_size / num_attention_heads
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1e6
    # feed-forwards
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    # experts: the router scores ``experts_total``; this chip holds
    # ``num_experts`` of them from ``first_held``
    experts_total: int = 64
    num_experts: int = 16
    first_held: int = 0
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # what a router's selection bias moves by after each step
    # (ops/moe.balanced_bias)
    router_bias_update_rate: float = 1e-3
    norm_eps: float = 1e-5
    # the PUBLISHED depth: every output projection is divided by
    # sqrt(2 x layers) at initialisation, whatever part is held here
    num_hidden_layers: int = 40

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def published_indices(self) -> Tuple[int, ...]:
        return (tuple(self.layer_indices) if self.layer_indices is not None
                else tuple(range(len(self.layer_types))))

    @property
    def dense_layers(self) -> Tuple[bool, ...]:
        return tuple(i < self.num_dense_layers for i in self.published_indices)

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, dense in enumerate(self.dense_layers) if not dense)


#: The share one chip of four holds (benchmark configuration
#: ``lfm2_24b_a2b``): published layers 0 and 2-5 of 40, experts 0-15 of
#: 64, 16,384 of 65,536 vocabulary rows; every width as published.
#: 788,052,352 parameters.
LFM2_24B_A2B_SHARE = Lfm2Config()


def lfm2_config(overrides=None) -> Lfm2Config:
    """The published share, with ``overrides`` (a mapping or (key, value)
    pairs: tests and rehearsals shrink sizes through it; lists, as JSON
    has them, become tuples)."""
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in dict(overrides or {}).items()}
    return dataclasses.replace(LFM2_24B_A2B_SHARE, **fields)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


class Lfm2:
    is_stateful = False

    def __init__(self, cfg: Lfm2Config = LFM2_24B_A2B_SHARE,
                 dtype=jnp.bfloat16, memory_bytes=None):
        """``memory_bytes``: what the device that runs the step reports
        as its memory (``utils/backend.device_memory_bytes``), ``None``
        where it reports none."""
        bad = set(cfg.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if len(cfg.published_indices) != len(cfg.layer_types):
            raise ValueError("layer_indices and layer_types differ in length")
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
        """``<name>/<layer>`` of every sparse layer's counters, in the
        order ``hidden`` stacks them."""
        return tuple(f"moe_{name}/{i}" for i in self.cfg.moe_layers
                     for name in moe.COUNTERS)

    def init(self, rng) -> Dict[str, Any]:
        """Float32 parameters: matrices normal with variance 1 / fan-in
        (the last product before a residual sum divided by sqrt(2 x
        published layers) besides), the tied embedding with variance
        1 / hidden_size (its logits have unit scale), norm scales one,
        the routers' selection biases zero."""
        c = self.cfg
        keys = iter(jax.random.split(rng, 16 * (len(c.layer_types) + 2)))
        d, hd = c.hidden_size, c.head_dim

        def dense(shape, fan_in=None):
            return _normal(next(keys), shape, (fan_in or shape[-2]) ** -0.5)

        def out(shape):  # into the residual stream
            return dense(shape) * (2 * c.num_hidden_layers) ** -0.5

        def norm(n=d):
            return {"scale": jnp.ones((n,), jnp.float32)}

        params = {"embed": {"embedding": _normal(
            next(keys), (c.vocab_size, d), d ** -0.5)}}
        for i, (kind, is_dense) in enumerate(zip(c.layer_types, c.dense_layers)):
            if kind == "conv":
                op = {"in_proj": {"kernel": dense((d, 3 * d))},
                      "conv": {"kernel": dense((c.conv_L_cache, d),
                                               c.conv_L_cache)},
                      "out_proj": {"kernel": out((d, d))}}
            else:
                q, kv = c.num_attention_heads * hd, c.num_key_value_heads * hd
                op = {"q": {"kernel": dense((d, q))},
                      "k": {"kernel": dense((d, kv))},
                      "v": {"kernel": dense((d, kv))},
                      "q_norm": norm(hd), "k_norm": norm(hd),
                      "o": {"kernel": out((q, d))}}
            if is_dense:
                f = c.intermediate_size
                ffn = {"gate": {"kernel": dense((d, f))},
                       "up": {"kernel": dense((d, f))},
                       "down": {"kernel": out((f, d))}}
            else:
                f, n = c.moe_intermediate_size, c.num_experts
                ffn = {"router": {"kernel": dense((d, c.experts_total)),
                                  "bias": jnp.zeros((c.experts_total,),
                                                    jnp.float32)},
                       "experts": {"gate": {"kernel": dense((n, d, f))},
                                   "up": {"kernel": dense((n, d, f))},
                                   "down": {"kernel": out((n, f, d))}}}
            params[f"layer_{i:02d}"] = {"op_norm": norm(), "op": op,
                                        "ffn_norm": norm(), "ffn": ffn}
        params["final_norm"] = norm()
        return params

    # -- sub-layers: (params, normed h (B, S, D)) -> (B, S, D) ---------------
    def _short_conv(self, p, x):
        with jax.named_scope("short_conv"):
            bcx = seq.matmul(x, p["in_proj"]["kernel"], "bsd,de->bse",
                             name="conv_in_proj")
            y = seq.short_conv(bcx, p["conv"]["kernel"])
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
            # RMSNorm over each head, then the rotary positions
            q = seq.rms_norm(q, p["q_norm"]["scale"], c.norm_eps)
            k = seq.rms_norm(k, p["k_norm"]["scale"], c.norm_eps)
            y = seq.causal_attention(seq.rotary(q, c.rope_theta),
                                     seq.rotary(k, c.rope_theta), v)
            return seq.matmul(y.reshape(lead + (-1,)), p["o"]["kernel"],
                              "bse,ed->bsd")

    def _dense_ffn(self, p, h):
        """The dense layer's whole second sub-layer, ``h + W2 (silu(W1 u)
        * W3 u)`` with ``u = RMSNorm(h)``, a block of ``FFN_BLOCK`` tokens
        at a time, each block a ``jax.checkpoint`` of its own: every part
        of it is per token, and its two first results are 11776 wide, so
        the backward pass holds one block's of them and not the step's."""
        c = self.cfg
        with jax.named_scope("dense_ffn"):
            flat = h.reshape(-1, c.hidden_size)
            t = flat.shape[0]
            blocks = t // FFN_BLOCK if t % FFN_BLOCK == 0 else 1

            @jax.checkpoint
            def one(hb):
                x = seq.rms_norm(hb, p["ffn_norm"]["scale"], c.norm_eps)
                a = seq.matmul(x, p["ffn"]["gate"]["kernel"], "td,df->tf")
                b = seq.matmul(x, p["ffn"]["up"]["kernel"], "td,df->tf")
                act = (jax.nn.silu(a.astype(LOSS_DTYPE))
                       * b.astype(LOSS_DTYPE)).astype(hb.dtype)
                return hb + seq.matmul(act, p["ffn"]["down"]["kernel"],
                                       "tf,fd->td")

            y = lax.map(one, flat.reshape(blocks, t // blocks, -1))
            return y.reshape(h.shape)

    def _experts(self, p, x):
        """``(the held experts' part, counters (3,), the chosen experts
        (T, k), the router's bias after this step)``."""
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
            e = p["experts"]
            routed, counters = moe.held_experts(
                flat, idx, gates, e["up"]["kernel"].astype(x.dtype),
                e["down"]["kernel"].astype(x.dtype), c.experts_total,
                c.first_held, w_gate=e["gate"]["kernel"].astype(x.dtype))
        return (routed.reshape(lead + (c.hidden_size,)), counters, idx,
                lax.stop_gradient(bias))

    def _layer(self, kind, is_dense, p, h):
        """``(h after the layer's first sub-layer and, in a sparse layer,
        its second, (counters, chosen experts, new bias) of a sparse layer
        or None)``: what one ``jax.checkpoint`` holds. A dense layer's
        second sub-layer is ``_dense_ffn``, checkpointed by token blocks."""
        c = self.cfg
        x = seq.rms_norm(h, p["op_norm"]["scale"], c.norm_eps)
        op = self._short_conv if kind == "conv" else self._attention
        h = h + op(p["op"], x)
        if is_dense:
            return h, None
        x = seq.rms_norm(h, p["ffn_norm"]["scale"], c.norm_eps)
        y, *routed = self._experts(p["ffn"], x)
        return h + y, tuple(routed)

    # -- what the layers' recomputation keeps ---------------------------------
    def attention_kernel_blocks(self, platform: str, seq_len: int) -> int:
        """How many of the model's layers run attention on the fused
        kernel at this length on ``platform`` (0: blocked XLA)."""
        c = self.cfg
        tile = seq.attention_path(platform, seq_len, c.head_dim,
                                  c.num_attention_heads, c.num_key_value_heads)
        return c.layer_types.count("full_attention") if tile else 0

    def moe_wgrad_kernel_layers(self, platform: str, tokens: int) -> int:
        """How many of the model's sparse layers hand their experts'
        weight gradients to the grouped kernel for a step of ``tokens``
        tokens on ``platform`` (0: the plain loop over tiles)."""
        c = self.cfg
        tile = moe.tile_rows(tokens, c.num_experts_per_tok, c.experts_total)
        kernel = moe.wgrad_path(platform, c.hidden_size,
                                c.moe_intermediate_size, tile)
        return len(c.moe_layers) if kernel else 0

    def named_activation_bytes(self, batch: int, seq_len: int,
                               platform: str) -> Tuple[int, ...]:
        """Bytes of ``KEPT_ACTIVATIONS`` in each layer, for one step of
        ``batch`` sequences of ``seq_len`` tokens on ``platform``, from
        the shapes (tests/test_lfm2.py holds them to the traced
        residuals)."""
        c, item, t = self.cfg, self.dtype.itemsize, batch * seq_len
        attention = attention_pallas.residual_bytes(
            batch, seq_len, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, item) if self.attention_kernel_blocks(
                platform, seq_len) else 0
        op = {"conv": t * 3 * c.hidden_size * item, "full_attention": attention}
        return tuple(op[kind] for kind in c.layer_types)

    def kept_activation_bytes(self, batch: int, seq_len: int,
                              platform: str) -> Tuple[int, ...]:
        """What each layer's ``jax.checkpoint`` keeps of its
        ``named_activation_bytes`` (0: the layer's input alone), from the
        last layer while ``recompute.kept_budget`` lasts."""
        return keep_from_last(
            self.named_activation_bytes(batch, seq_len, platform),
            kept_budget(self.parameter_count,
                        WORKING_BYTES_PER_TOKEN_AND_WIDTH * batch * seq_len
                        * self.cfg.hidden_size, self.memory_bytes))

    # -- the model ----------------------------------------------------------
    def hidden(self, params, tokens, routing: bool = False):
        """``(h (B, S, D) after the final norm, counters (sparse layers,
        3), biases)`` for ``tokens`` (B, S) int32: ``biases`` is the part
        of the parameter tree that the model sets itself, each router's
        selection bias after this step's load. With ``routing`` also each
        sparse layer's chosen experts, [(B*S, k) int32, ...]."""
        c = self.cfg
        h = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(self.dtype)
        kept = self.kept_activation_bytes(*tokens.shape, jax.default_backend())
        policy = jax.checkpoint_policies.save_only_these_names(*KEPT_ACTIVATIONS)
        counters, chosen, biases = [], [], {}
        for i, (kind, dense) in enumerate(zip(c.layer_types, c.dense_layers)):
            name = f"layer_{i:02d}"
            with jax.named_scope(name):
                layer = jax.checkpoint(
                    lambda p, h, kind=kind, dense=dense: self._layer(
                        kind, dense, p, h),
                    policy=policy if kept[i] else None)
                p, h = gradients_before_input(params[name], h)
                h, routed = layer(p, h)
                if dense:
                    h = self._dense_ffn(p, h)
            if routed is not None:
                counters.append(routed[0])
                chosen.append(routed[1])
                biases[name] = {"ffn": {"router": {"bias": routed[2]}}}
        h = seq.rms_norm(h, params["final_norm"]["scale"], c.norm_eps)
        counters = (jnp.stack(counters) if counters
                    else jnp.zeros((0, len(moe.COUNTERS)), LOSS_DTYPE))
        return (h, counters, biases) + ((chosen,) if routing else ())

    def logits(self, params, tokens):
        """(B, S, V) float32: tests and small sizes only (the loss never
        holds them all)."""
        h = self.hidden(params, tokens)[0]
        return jnp.einsum("bsd,vd->bsv", h,
                          params["embed"]["embedding"].astype(h.dtype),
                          preferred_element_type=LOSS_DTYPE)

    def loss(self, params, tokens):
        """``(mean next-token cross-entropy, counters, biases)`` as
        ``hidden`` gives them; the head is the embedding's matrix."""
        h, counters, biases = self.hidden(params, tokens)
        with jax.named_scope("lm_head"):
            return (seq.next_token_loss(h, params["embed"]["embedding"], tokens,
                                        tied=True), counters, biases)
