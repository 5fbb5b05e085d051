"""Weights of a token model from the seed, made by the benchmark in one
jitted call on the device, float32. The program is asked only for the
names and shapes of its leaves; the plain reference gets the same values
under the same flat names. By the leaf's name: ``embedding`` unit normal;
``kernel`` normal with variance 1 / fan-in (the axis before the last; a
depthwise convolution's (taps, channels) kernel: its taps), a mixer's
output projection (``out_proj``, ``o``, ``down``: the last product before
the residual sum) divided by sqrt(2 x layers) besides, the published
depth (``num_hidden_layers``: the config's ``rescale_prenorm_residual``);
``scale`` and ``D`` one; ``bias`` zero; ``A_log`` the log of uniform [1, 16);
``dt_bias`` the inverse softplus of a step log-uniform in [0.001, 0.1]:
what a freshly initialised network of this family holds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def make(shapes: dict, seed: int, config: dict = None) -> dict:
    """``config``: the configuration's file, for the published depth
    (``num_hidden_layers``; 52 without one)."""
    names = sorted(shapes)
    layers = (config or {}).get("num_hidden_layers", 52)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, k = tuple(shapes[name]), jax.random.fold_in(key, i)
            kind = name.rsplit("/", 1)[-1]
            if kind == "embedding":
                out[name] = jax.random.normal(k, shape, jnp.float32)
            elif kind == "kernel":
                fan_in = shape[0] if name.endswith("conv/kernel") else shape[-2]
                out[name] = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
                if name.rsplit("/", 2)[-2] in ("out_proj", "o", "down"):
                    out[name] = out[name] * (2 * layers) ** -0.5
            elif kind in ("scale", "D"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "bias":
                out[name] = jnp.zeros(shape, jnp.float32)
            elif kind == "A_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                             * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                raise ValueError(f"no rule to draw the leaf {name!r}")
        return out

    return jax.jit(build)(jax.random.key(seed))
