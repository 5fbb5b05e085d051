"""The language model that SmallThinker-21BA3B-Instruct's ``config.json``
defines (``model_name`` ``smallthinker_21b_instruct``; the family's report:
arXiv:2507.20984): a stack of pre-norm layers, every one sparse, whose
router reads the layer's INPUT. With ``x`` the residual stream entering a
layer and ``n(.)`` an RMSNorm with a learnt scale (no bias anywhere):

1. ``r = W_r x`` (one logit an expert, float32, from the raw input, before
   any norm); ``S = top6(r + b)``; ``g = softmax(r[S])`` over the chosen
   logits (``moe_primary_router_apply_softmax`` and ``norm_topk_prob``: a
   softmax over all the experts renormalised over the chosen is the same
   function). ``b`` is the balancing's selection bias: no gradient reaches
   it, the gates never see it, and at zero bias the layer is the published
   one.
2. ``a = x + W_o Attn(q, k, v)`` with ``q, k, v`` from ``n_1(x)``: grouped
   query attention, scores over sqrt(head size). Where the layer's entry in
   ``sliding_window_layout`` is 0: causal over the whole sequence; where it
   is 1: query ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window_size``.
   Where its entry in ``rope_layout`` is 1: rotary positions over the whole
   head; where 0: NO positional encoding. (Published: full attention without
   positions in every fourth layer, a window with rotary in the other three.)
3. ``x' = a + sum_{e in S} g_e W_down,e (relu(W_gate,e u) * W_up,e u)`` with
   ``u = n_2(a)``: the router's choice, made before attention, is carried
   past it to the experts.
4. Token embedding in; final ``n``, then an untied head; trained by mean
   next-token cross-entropy.

The chip's share of a deployment is part of the shape: the two layouts
hold the entries of the layers held here and ``layer_indices`` their
PUBLISHED indices, ``moe_num_primary_experts`` of ``experts_total`` routed
experts from ``first_held`` (the router keeps all its outputs,
``ops/moe.held_experts`` computes the held experts' part and nothing
stands in for the others), and ``vocab_size`` rows of the vocabulary.

Every layer is a ``jax.checkpoint`` as in the other token models
(``models/recompute.py``): it keeps its input and, where the device's
memory allows, ``KEPT_ACTIVATIONS``: the attention kernel's residuals and
the routing (the chosen experts and their gates), so that neither the
forward kernel nor the router runs again in the backward pass.

Trained on one device; not served (``serve/`` refuses it), no mesh, no
``--grad-accum``. Names in the compiled step (``jax.named_scope``), each
under ``layer_<i>``: ``moe_router`` (ahead of ``attention``),
``attention``, ``moe_experts``; and ``lm_head``. Counters: the three
``moe_rows_*`` of every layer, and ``attention_pairs_computed``, the
(query, key) pairs the attention path that ran multiplies, over the
sequences and the query heads.
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

#: ``checkpoint_name``s of a layer's routing: the chosen experts and
#: their gates, (tokens, k) each.
ROUTING = ("moe_chosen", "moe_gates")
#: What a layer's ``jax.checkpoint`` keeps besides the layer's input, by
#: ``checkpoint_name``, where the budget leaves the room: a fixed set,
#: applied to a layer whole or not at all.
KEPT_ACTIVATIONS = (*ROUTING, *attention_pallas.RESIDUALS)
#: What the step that keeps each layer's input alone holds besides the
#: gradient, in bytes a token and unit of ``hidden_size``: the layers'
#: inputs, one layer's backward pass, the expert layer's tile buffers, the
#: logits of a token block (the other token models' figure; the compile for
#: a described v5e at 16,384 tokens is in PERF.md §6, PR 37).
WORKING_BYTES_PER_TOKEN_AND_WIDTH = 32
#: Name of the counter beside the expert layers' (``moe.COUNTERS``).
PAIRS_COUNTER = "attention_pairs_computed"


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Sizes under the published config's own key names where it has one."""

    # the layers held here, by their entries in the two published layouts
    # (1: a sliding window; 1: rotary positions), and their published
    # indices (None: 0, 1, ...)
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    layer_indices: Optional[Tuple[int, ...]] = (0, 1, 2, 3)
    sliding_window_size: int = 4096
    hidden_size: int = 2560
    vocab_size: int = 37984
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1.5e6
    # experts: the router scores ``experts_total``; this chip holds
    # ``moe_num_primary_experts`` of them from ``first_held``
    moe_ffn_hidden_size: int = 768
    experts_total: int = 64
    moe_num_primary_experts: int = 16
    first_held: int = 0
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    # what a router's selection bias moves by after each step
    # (ops/moe.balanced_bias)
    router_bias_update_rate: float = 1e-3
    rms_norm_eps: float = 1e-6
    # the PUBLISHED depth: every output projection is divided by
    # sqrt(2 x layers) at initialisation, whatever part is held here
    num_hidden_layers: int = 52

    @property
    def layers(self) -> int:
        return len(self.sliding_window_layout)

    @property
    def published_indices(self) -> Tuple[int, ...]:
        return (tuple(self.layer_indices) if self.layer_indices is not None
                else tuple(range(self.layers)))

    @property
    def windows(self) -> Tuple[Optional[int], ...]:
        """Each held layer's window, None where it sees the whole sequence."""
        return tuple(self.sliding_window_size if w else None
                     for w in self.sliding_window_layout)


#: The share one chip of four holds (benchmark configuration
#: ``smallthinker_21b_a3b``): published layers 0-3 of 52 (one whole period:
#: full attention without positions, then three windowed with rotary),
#: experts 0-15 of 64, 37,984 of 151,936 vocabulary rows; every width as
#: published. 656,529,920 parameters.
SMALLTHINKER_21B_A3B_SHARE = SmallThinkerConfig()


def smallthinker_config(overrides=None) -> SmallThinkerConfig:
    """The published share, with ``overrides`` (a mapping or (key, value)
    pairs: tests and rehearsals shrink sizes through it; lists, as JSON
    has them, become tuples)."""
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in dict(overrides or {}).items()}
    return dataclasses.replace(SMALLTHINKER_21B_A3B_SHARE, **fields)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


class SmallThinker:
    is_stateful = False

    def __init__(self, cfg: SmallThinkerConfig = SMALLTHINKER_21B_A3B_SHARE,
                 dtype=jnp.bfloat16, memory_bytes=None):
        """``memory_bytes``: what the device that runs the step reports
        as its memory (``utils/backend.device_memory_bytes``), ``None``
        where it reports none."""
        if not (len(cfg.rope_layout) == len(cfg.published_indices)
                == cfg.layers):
            raise ValueError("sliding_window_layout, rope_layout and "
                             "layer_indices differ in length")
        bad = (set(cfg.sliding_window_layout) | set(cfg.rope_layout)) - {0, 1}
        if bad:
            raise ValueError(f"layout entries {sorted(bad)} are neither 0 nor 1")
        if not cfg.moe_primary_router_apply_softmax:
            raise ValueError("the router's gates are a softmax over the chosen "
                             "logits: moe_primary_router_apply_softmax is true "
                             "in every published configuration of the family")
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
        """``<name>/<layer>``: every layer's expert counters, then every
        layer's attention pairs, in the order ``hidden`` lays them out."""
        layers = range(self.cfg.layers)
        return (tuple(f"moe_{name}/{i}" for i in layers for name in moe.COUNTERS)
                + tuple(f"{PAIRS_COUNTER}/{i}" for i in layers))

    def init(self, rng) -> Dict[str, Any]:
        """Float32 parameters: matrices normal with variance 1 / fan-in
        (the last product before a residual sum divided by sqrt(2 x
        published layers) besides), the embedding unit normal, norm scales
        one, the routers' selection biases zero."""
        c = self.cfg
        keys = iter(jax.random.split(rng, 16 * (c.layers + 2)))
        d = c.hidden_size

        def dense(shape):
            return _normal(next(keys), shape, shape[-2] ** -0.5)

        def out(shape):  # into the residual stream
            return dense(shape) * (2 * c.num_hidden_layers) ** -0.5

        def norm():
            return {"scale": jnp.ones((d,), jnp.float32)}

        q, kv = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
        f, n = c.moe_ffn_hidden_size, c.moe_num_primary_experts
        params = {"embed": {"embedding": _normal(next(keys), (c.vocab_size, d), 1.0)}}
        for i in range(c.layers):
            params[f"layer_{i:02d}"] = {
                "router": {"kernel": dense((d, c.experts_total)),
                           "bias": jnp.zeros((c.experts_total,), jnp.float32)},
                "attn_norm": norm(),
                "attn": {"q": {"kernel": dense((d, q))},
                         "k": {"kernel": dense((d, kv))},
                         "v": {"kernel": dense((d, kv))},
                         "o": {"kernel": out((q, d))}},
                "ffn_norm": norm(),
                "experts": {"gate": {"kernel": dense((n, d, f))},
                            "up": {"kernel": dense((n, d, f))},
                            "down": {"kernel": out((n, f, d))}}}
        params["final_norm"] = norm()
        params["head"] = {"kernel": dense((d, c.vocab_size))}
        return params

    # -- sub-layers -----------------------------------------------------------
    def _route(self, p, h):
        """``(chosen experts (T, k), gates (T, k), the router's bias after
        this step)`` from the layer's raw input ``h`` (B, S, D)."""
        c = self.cfg
        with jax.named_scope("moe_router"):
            idx, gates = moe.route(
                h.reshape(-1, c.hidden_size), p["kernel"], p["bias"],
                c.moe_num_active_primary_experts, c.norm_topk_prob, 1.0,
                score="softmax", names=ROUTING)
            bias = moe.balanced_bias(
                p["bias"], moe.expert_load(idx, c.experts_total),
                c.router_bias_update_rate)
        return idx, gates, lax.stop_gradient(bias)

    def _attention(self, p, x, window, rope):
        c = self.cfg
        with jax.named_scope("attention"):
            lead = x.shape[:2]
            q = seq.matmul(x, p["q"]["kernel"], "bsd,de->bse").reshape(
                lead + (c.num_attention_heads, c.head_dim))
            k = seq.matmul(x, p["k"]["kernel"], "bsd,de->bse").reshape(
                lead + (c.num_key_value_heads, c.head_dim))
            v = seq.matmul(x, p["v"]["kernel"], "bsd,de->bse").reshape(
                lead + (c.num_key_value_heads, c.head_dim))
            if rope:
                q, k = seq.rotary(q, c.rope_theta), seq.rotary(k, c.rope_theta)
            y = seq.causal_attention(q, k, v, window=window)
            return seq.matmul(y.reshape(lead + (-1,)), p["o"]["kernel"],
                              "bse,ed->bsd")

    def _layer(self, window, rope, p, h):
        """``(h after the layer, (counters (3,), chosen experts (T, k),
        new bias))``: what one ``jax.checkpoint`` holds."""
        c = self.cfg
        idx, gates, bias = self._route(p["router"], h)
        x = seq.rms_norm(h, p["attn_norm"]["scale"], c.rms_norm_eps)
        h = h + self._attention(p["attn"], x, window, rope)
        x = seq.rms_norm(h, p["ffn_norm"]["scale"], c.rms_norm_eps)
        with jax.named_scope("moe_experts"):
            e = p["experts"]
            routed, counters = moe.held_experts(
                x.reshape(-1, c.hidden_size), idx, gates,
                e["up"]["kernel"].astype(x.dtype),
                e["down"]["kernel"].astype(x.dtype), c.experts_total,
                c.first_held, w_gate=e["gate"]["kernel"].astype(x.dtype),
                act="relu")
        return h + routed.reshape(h.shape), (counters, idx, bias)

    # -- what the step says of itself -----------------------------------------
    def attention_kernel_blocks(self, platform: str, seq_len: int) -> int:
        """How many of the model's layers run attention on the fused
        kernel at this length on ``platform`` (0: blocked XLA)."""
        c = self.cfg
        tile = seq.attention_path(platform, seq_len, c.head_dim,
                                  c.num_attention_heads, c.num_key_value_heads)
        return c.layers if tile else 0

    def moe_wgrad_kernel_layers(self, platform: str, tokens: int) -> int:
        """How many of the model's layers hand their experts' weight
        gradients to the grouped kernel for a step of ``tokens`` tokens on
        ``platform`` (0: the plain loop over tiles)."""
        c = self.cfg
        tile = moe.tile_rows(tokens, c.moe_num_active_primary_experts,
                             c.experts_total)
        kernel = moe.wgrad_path(platform, c.hidden_size, c.moe_ffn_hidden_size,
                                tile)
        return c.layers if kernel else 0

    def attention_pairs(self, batch: int, seq_len: int,
                        platform: str) -> Tuple[int, ...]:
        """(query, key) pairs each layer's attention multiplies in a step
        of ``batch`` sequences of ``seq_len`` tokens on ``platform``, over
        the sequences and the query heads (``seq.attention_pairs``: the
        loop bounds of the path that runs)."""
        c = self.cfg
        return tuple(
            batch * c.num_attention_heads * seq.attention_pairs(
                platform, seq_len, c.head_dim, c.num_attention_heads,
                c.num_key_value_heads, window)
            for window in c.windows)

    def named_activation_bytes(self, batch: int, seq_len: int,
                               platform: str) -> Tuple[int, ...]:
        """Bytes of ``KEPT_ACTIVATIONS`` in each layer, for one step of
        ``batch`` sequences of ``seq_len`` tokens on ``platform``, from
        the shapes (tests/test_smallthinker.py holds them to the traced
        residuals): the routing, int32 and float32, and where attention
        takes the kernel its residuals."""
        c, t = self.cfg, batch * seq_len
        attention = attention_pallas.residual_bytes(
            batch, seq_len, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, self.dtype.itemsize) if self.attention_kernel_blocks(
                platform, seq_len) else 0
        routing = 2 * 4 * t * c.moe_num_active_primary_experts
        return (attention + routing,) * c.layers

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
        """``(h (B, S, D) after the final norm, counters, biases)`` for
        ``tokens`` (B, S) int32: ``counters`` one float32 vector in the
        order of ``counter_names``; ``biases`` the part of the parameter
        tree that the model sets itself, each router's selection bias
        after this step's load. With ``routing`` also each layer's chosen
        experts, [(B*S, k) int32, ...]."""
        c = self.cfg
        platform = jax.default_backend()
        h = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(self.dtype)
        kept = self.kept_activation_bytes(*tokens.shape, platform)
        policy = jax.checkpoint_policies.save_only_these_names(*KEPT_ACTIVATIONS)
        counters, chosen, biases = [], [], {}
        for i, (window, rope) in enumerate(zip(c.windows, c.rope_layout)):
            name = f"layer_{i:02d}"
            with jax.named_scope(name):
                layer = jax.checkpoint(
                    functools.partial(self._layer, window, bool(rope)),
                    policy=policy if kept[i] else None)
                h, routed = layer(*gradients_before_input(params[name], h))
            counters.append(routed[0])
            chosen.append(routed[1])
            biases[name] = {"router": {"bias": routed[2]}}
        h = seq.rms_norm(h, params["final_norm"]["scale"], c.rms_norm_eps)
        pairs = jnp.asarray(self.attention_pairs(*tokens.shape, platform),
                            LOSS_DTYPE)
        counters = jnp.concatenate(
            [jnp.stack(counters).reshape(-1).astype(LOSS_DTYPE), pairs])
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
