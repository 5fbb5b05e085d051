"""Witness of the fault that keeps ``milesial_train_synth`` out of
``BENCHMARK.json`` (PERF.md, Open questions, row 0), on the CPU in float32
at a toy size, in about two minutes:

    JAX_PLATFORMS=cpu python3 benchmark/tools/s2d_pool_gradient.py

The program's milesial UNet is built twice on the same weights and rows,
once in the pixel domain (``s2d_levels=0``) and once with its first two
levels in the space-to-depth domain (``s2d_levels=2``, what ``-1``
resolves to on a TPU). The losses agree; under ``jax.jit`` the gradients
of ``inc/*`` and ``down1/conv/*`` do not (norms 5-15% apart, the
difference 20-65% of the norm). ``ops/s2d.group_max`` is ``jnp.max`` over
the s2d group, whose gradient goes to the elements that EQUAL the stored
maximum; in the compiled step that comparison misses in one window of
eight that has a clear winner (BatchNorm and ReLU are recomputed beside
it), and the window's gradient is dropped. Three readings show it: as
the program is, jitted, the leaves are apart; run operation by operation
(no jit, nothing recomputed) every leaf agrees; and jitted with
``group_max`` replaced by a pool that picks its winner from the operand
it is given (``nn.max_pool`` on the ``depth_to_space`` form, as torch
does) every leaf agrees again.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.models import milesial as M
    from distributedpytorch_tpu.ops import s2d as s2d_ops

    x = jax.random.uniform(jax.random.key(0), (2, 64, 96, 3))
    t = (jax.random.uniform(jax.random.key(1), (2, 64, 96, 1)) > 0.5).astype(
        jnp.float32)
    params, stats = M.init_milesial(
        M.MilesialUNet(dtype=jnp.float32, s2d_levels=0), jax.random.key(2),
        (64, 96))

    def grads(levels, jit=True):
        model = M.MilesialUNet(dtype=jnp.float32, s2d_levels=levels)

        def loss(p):
            y, _ = model.apply({"params": p, "batch_stats": stats}, x,
                               train=True, mutable=["batch_stats"])
            return jnp.mean((y - t) ** 2)

        fn = jax.value_and_grad(loss)
        value, g = (jax.jit(fn) if jit else fn)(params)
        return float(value), {
            "/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(g)[0]}

    l0, g0 = grads(0)

    def report(title, jit=True):
        l2, g2 = grads(2, jit)
        apart = 0
        print(f"{title}: loss {l0:.8f} (pixel) {l2:.8f} (s2d)")
        for k in g0:
            n0 = float(jnp.linalg.norm(g0[k]))
            ratio = float(jnp.linalg.norm(g2[k])) / n0
            diff = float(jnp.linalg.norm(g2[k] - g0[k])) / n0
            if abs(ratio - 1) > 5e-3 or diff > 2e-2:
                apart += 1
                print(f"    {k}: norm s2d/pixel {ratio:.3f}, "
                      f"difference {diff:.3f} of the norm")
        print(f"    {apart} of {len(g0)} leaves apart")
        return apart

    as_is = report("the program as it is, jitted")
    op_by_op = report("the program as it is, operation by operation", jit=False)
    s2d_ops.group_max = lambda v: nn.max_pool(
        s2d_ops.depth_to_space(v), (2, 2), (2, 2))
    one_winner = report("group_max as depth_to_space + nn.max_pool, jitted")
    return 0 if as_is and not op_by_op and not one_winner else 1


if __name__ == "__main__":
    sys.exit(main())
