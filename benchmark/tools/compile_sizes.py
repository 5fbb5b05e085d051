"""Rehearsal 3 of the on-chip-measurement guide, by hand and in the sandbox:
compile a configuration's train step (the program's), its plain reference
and the reference's control at the real size for a described ``v5e:2x2``.
A compile, not a run: it says what the chip's compiler accepts and how
many bytes the program holds, and nothing about time or results.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_sizes.py \
        --config course_unet --batch 16 --what step reference control

The step is built as the Trainer builds it (``create_model``, ``adam_l2``,
``build_strategy(cfg, devices).build_train_step``) on the described
devices, with ``s2d_levels`` named as 2: ``-1`` asks the default backend,
which here is the CPU. ``--chips 4`` compiles the data-parallel step over
the described 2x2 and counts its collectives.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


def report(name, compiled, seconds):
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    cost = compiled.cost_analysis() or {}
    print(json.dumps({
        "what": name, "compile_s": round(seconds, 1),
        "temporaries_bytes": int(mem.temp_size_in_bytes),
        "arguments_bytes": int(mem.argument_size_in_bytes),
        "total_bytes": int(total),
        "executed_flops": cost.get("flops"),
    }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--what", nargs="+", default=["step"],
                    choices=("step", "reference", "control"))
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--method", default=None,
                    help="train_method (singleGPU on one chip, DP on four)")
    ap.add_argument("--rows", type=int, default=0,
                    help="rows to a block of the reference (0: the batch)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import flops
    import reference

    jax.config.update("jax_enable_compilation_cache", False)
    config = flops.load_config(args.config)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    w, h = config["image_size"]
    b = args.batch
    image, mask = sds((b, h, w, 3), jnp.float32), sds((b, h, w), jnp.int32)

    if "step" in args.what:
        from distributedpytorch_tpu.config import TrainConfig
        from distributedpytorch_tpu.models import create_model
        from distributedpytorch_tpu.ops.optim import adam_l2
        from distributedpytorch_tpu.parallel import build_strategy
        from distributedpytorch_tpu.train.steps import TrainState

        method = args.method or ("singleGPU" if args.chips == 1 else "DP")
        fields = dict(config["train_config"], batch_size=b, s2d_levels=2,
                      train_method=method)
        fields["image_size"] = tuple(fields["image_size"])
        cfg = TrainConfig(**fields)
        strategy = build_strategy(cfg, list(topo.devices[: args.chips]))
        model, init_fn = create_model(cfg)
        params, model_state = jax.eval_shape(
            lambda k: init_fn(k, (h, w)), jax.random.key(0))
        tx = adam_l2(cfg.learning_rate, cfg.weight_decay)
        rep, rows_sh = chip, chip
        if strategy.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(strategy.mesh, PartitionSpec())
            rows_sh = strategy.batch_sharding
        place = lambda tree: jax.tree.map(  # noqa: E731
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=rep), tree)
        state = TrainState(params=place(params),
                           opt_state=place(jax.eval_shape(tx.init, params)),
                           step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                           model_state=None if model_state is None
                           else place(model_state))
        batch = {"image": jax.ShapeDtypeStruct((b, h, w, 3), jnp.float32,
                                               sharding=rows_sh),
                 "mask": jax.ShapeDtypeStruct((b, h, w), jnp.int32,
                                              sharding=rows_sh)}
        t0 = time.time()
        compiled = strategy.build_train_step(model, tx).lower(state, batch).compile()
        report(f"{args.config} b{b} {method} step on {args.chips} chip(s)",
               compiled, time.time() - t0)
        text = compiled.as_text()
        print(json.dumps({"collectives": {w: text.count(w + "(") + text.count(w + "-start(")
                                          for w in ("all-reduce", "all-gather",
                                                    "reduce-scatter", "all-to-all",
                                                    "collective-permute")},
                          "tpu_custom_call": text.count("tpu_custom_call")}))

    ref = flops.load_reference(config)
    fp = {k: sds(v, jnp.float32) for k, v in ref.param_shapes(config).items()}
    state = {k: sds(v, jnp.float32)
             for k, v in ref.state_shapes(config).items()} or None
    rows = args.rows or b
    x, m = sds((rows, h, w, 3), jnp.float32), sds((rows, h, w), jnp.int32)
    for what, mode in (("reference", "f32"), ("control", "fp8")):
        if what not in args.what:
            continue
        ops = reference.Ops(mode)

        def stats_fn(p, st, x, m):
            out, _ = ref.forward(ops, config, p, st, x)
            return reference.loss_stats(out, m)

        def grad_fn(p, st, c, x, m):
            return jax.grad(lambda p: jnp.dot(c, stats_fn(p, st, x, m)))(p)

        for name, fn, fargs in (
                ("forward", stats_fn, (fp, state, x, m)),
                ("backward", grad_fn, (fp, state, sds((5,), jnp.float32), x, m))):
            t0 = time.time()
            compiled = jax.jit(fn).lower(*fargs).compile()
            report(f"{args.config} {what} ({mode}) {name}, {rows} rows",
                   compiled, time.time() - t0)


if __name__ == "__main__":
    main()
