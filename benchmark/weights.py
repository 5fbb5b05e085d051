"""Weights from the seed, made by the benchmark in one jitted call on the
device, in the type the program holds them in (float32).

The program is asked only for the NAMES and SHAPES of its parameter
leaves; the values are the benchmark's. A leaf named ``kernel`` is drawn
normal with variance 2 / fan-in (He's rule for ReLU networks, so that the
signal and its gradient reach every layer; fan-in = every axis but the last),
``bias`` is zero and a normalisation ``scale`` is one: what a freshly
initialised network of these families holds. The plain reference gets the
same values under the same flat names.

A k x k stride-k transposed convolution's kernel is stored by the program
(flax ``ConvTranspose``, ``transpose_kernel=False``) with its two spatial
axes reversed against the published definition the reference follows
(output pixel (k*i+di, k*j+dj) takes tap (di, dj)); ``to_program`` turns
the leaves the configuration lists under ``upconv_leaves_flipped``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flat_names(tree) -> dict:
    """``{"a/b/kernel": leaf}`` for a nested dict of leaves."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = leaf
    return out


def make(shapes: dict, seed: int, sharding=None) -> dict:
    """Flat ``{name: float32 array}`` for ``{name: shape}``, from the seed,
    in one jitted call."""
    names = sorted(shapes)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape = tuple(shapes[name])
            kind = name.rsplit("/", 1)[-1]
            if kind == "kernel":
                fan_in = 1
                for d in shape[:-1]:
                    fan_in *= d
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                ) * (2.0 / fan_in) ** 0.5
            elif kind == "scale":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "bias":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError(f"no rule to draw the leaf {name!r}")
        return out

    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(build, **kw)(jax.random.key(seed))


def to_program(flat: dict, template, flipped=()):
    """The program's nested tree, filled from the flat values."""
    flipped = set(flipped)
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, _ in paths:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = flat[name]
        if name in flipped:
            x = x[::-1, ::-1]
        leaves.append(x)
    return jax.tree_util.tree_unflatten(treedef, leaves)
