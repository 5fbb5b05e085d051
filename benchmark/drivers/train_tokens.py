"""Driver of ``kind: train_tokens`` cells: a token model through the same
Trainer, loader, feed, compiled step, step loop and window as
``drivers/train.py`` drives for an image model (its ``StepLoop``,
``measure`` and ``report``), filling the same ``run`` keys, so that every
reader under ``layer_metrics/`` reads such a cell unedited. ``images``
counts samples: one packed sequence.

What differs from the image driver, and why it is a file of its own:
a batch is ``{'tokens': (B, S)}``, not image and mask; the traffic is
packed documents (``traffic_tokens.py``); the step's second output is
``[loss, *counters]`` (the program's ``pack_readout``), from which the
window's counters are read with no readback of their own; the plain
reference follows one sequence at a time. ``correct`` is decided as for
an image cell, by ``check.py``.

The driver names no model. What belongs to one comes from the three
places that a configuration brings (PERF.md §4):

- its reference module ``references/<name>.py``: ``param_shapes``,
  ``make_loss_and_grad``, ``program_overrides(config)`` (the program's
  size keys), ``expert_blocks(config)`` (how many of its blocks hold
  routed experts), ``train_flops_per_sample``, and, where it has routers,
  ``balanced_biases`` and ``routed_left_out``;
- its file ``configs/<name>.json``: ``train_config.model_arch``, an
  optional ``weights`` (the module under ``benchmark/`` whose
  ``make(shapes, seed, config)`` draws the seed's weights; default
  ``weights_tokens``) and an optional ``deployment``;
- the cell's file: ``settle_steps`` (optional), limits, ``rehearsal``.

A model without expert blocks runs with no settling, no routing program
and no router rows among its checks.

``follow(mode=...)``: ``f32`` the reference; ``fp8`` the control (its
matrix products in 8-bit floats); ``no_routed`` a planted fault (the
routed experts left out). ``keep`` and ``skip_update`` as in the image
driver, but that a batch of ONE sequence is cut by positions (half the
row), a larger one by rows. ``--fault`` (rehearsal only): ``unchanged``,
``half_batch``, ``no_routed`` (``faults``: only with expert blocks) break
the step underneath; ``wrong_mask`` (the name the harness's tests give a
loader that alters a row) alters a token of each batch's first sequence.
"""

from __future__ import annotations

import gc
import importlib
import os
import time
import types

import numpy as np

import flops
import reference
import traffic as traffic_params
import traffic_tokens
import weights as weights_mod
from drivers import train as image_driver

COMPARED_STEPS = image_driver.COMPARED_STEPS
NotMeasurable = image_driver.NotMeasurable
trace_summary = image_driver.trace_summary
judge = image_driver.judge


def faults(config) -> tuple:
    """The faults ``--fault`` can plant in a cell of this configuration:
    routed experts can be left out only where there are some."""
    routed = flops.load_reference(config).expert_blocks(config) > 0
    return ("unchanged", "half_batch") + ("no_routed",) * routed + ("wrong_mask",)


def effective_config(ctx) -> dict:
    """The configuration as it is run: in a rehearsal, the cell's toy
    sizes laid over it (widths shrink there and nowhere else)."""
    config = ctx.config
    if ctx.args.rehearse:
        toy = ctx.cell["rehearsal"]
        config = {**config, **toy.get("config", {})}
        if "deployment" in toy:
            config.update(deployment={**config.get("deployment", {}),
                                      **toy["deployment"]})
    return config


def step_loop(trainer, tracer, annotate):
    """``drivers/train.StepLoop`` counting samples on ``tokens`` and
    keeping each step's ``[loss, *counters]`` for the counters."""
    return image_driver.StepLoop(trainer, tracer, annotate, field="tokens",
                                 keep_readouts=True)


def build_train_config(ctx, seed: int):
    from distributedpytorch_tpu.config import TrainConfig

    cell, config = ctx.cell, effective_config(ctx)
    out_dir = os.path.join(ctx.root, ".bench_run", cell["name"])
    fields = dict(config["train_config"])
    fields.update(cell.get("train_config", {}))
    if ctx.args.rehearse:
        fields.update(cell["rehearsal"]["train_config"])
    # the configuration's file sizes the program, whatever its defaults are
    fields["model_overrides"] = flops.load_reference(config).program_overrides(
        config)
    fields.update(
        seed=seed, synthetic_samples=0, val_percent=0.0,
        epochs=10 ** 9, checkpoint_dir=os.path.join(out_dir, "checkpoints"),
        log_dir=os.path.join(out_dir, "logs"),
        loss_dir=os.path.join(out_dir, "loss"),
    )
    return TrainConfig(**fields)


def plant_fault(trainer, fault: str, offered: tuple):
    import jax
    import jax.numpy as jnp

    if fault not in offered:
        raise NotMeasurable(f"unknown fault {fault!r} for this configuration "
                            f"(known: {offered})")
    if fault == "wrong_mask":
        batches = trainer.train_loader.epoch_batches

        def epoch_batches(epoch=0):
            for b in batches(epoch):
                tokens = b["tokens"].copy()
                tokens[0, 5] = (tokens[0, 5] + 1) % 7
                yield {**b, "tokens": tokens}

        trainer.train_loader.epoch_batches = epoch_batches
        return
    if fault == "no_routed":
        # the expert layer gives nothing: traced when the step first runs
        from distributedpytorch_tpu.ops import moe

        real_experts = moe.held_experts

        def nothing(x, *args, **kw):
            return jnp.zeros_like(x), jnp.zeros((len(moe.COUNTERS),), jnp.float32)

        real = trainer.train_step

        def step(state, batch):
            moe.held_experts = nothing
            try:
                return real(state, batch)
            finally:
                moe.held_experts = real_experts
        step.lower = real.lower
        trainer.train_step = step
        return
    real = trainer.train_step
    if fault == "unchanged":
        def step(state, batch):
            _, out = real(jax.tree.map(jnp.copy, state), batch)
            return state, out
    else:  # half_batch: of one sequence, half the row
        def step(state, batch):
            return real(state, {
                k: v[:, : v.shape[1] // 2] if v.shape[0] == 1
                else v[: v.shape[0] // 2] for k, v in batch.items()})
    step.lower = real.lower
    trainer.train_step = step


def settle_routers(trainer, tracer, annotate, steps: int):
    """The routers' balancing run to rest before anything is compared or
    timed: ``steps`` steps of the program's own compiled step over the
    epoch's first batches with the learning rate at 0, so that nothing
    moves but the routers' selection biases, each by the configuration's
    rate a step; then Adam's moments and the step count as they were. It
    stands for what a deployment's routers hold once their balancing has
    converged: from biases of zero the routing is the lottery of the
    seed's weights (PERF.md §6, PR 27)."""
    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.ops.optim import (
        get_learning_rate,
        set_learning_rate,
    )
    from distributedpytorch_tpu.train.steps import TrainState

    if steps < 1:
        return []
    state = trainer.state
    # on the host: the steps donate the state they are given
    lr, step0 = get_learning_rate(state.opt_state), np.asarray(state.step)
    trainer.state = state.replace(
        opt_state=set_learning_rate(state.opt_state, 0.0))
    del state
    loop = step_loop(trainer, tracer, annotate)
    loop.run(max_steps=steps)
    loop.drain()
    state, trainer.state = trainer.state, None
    for leaf in jax.tree.leaves(state.opt_state):
        leaf.delete()
    trainer.state = trainer.strategy.place_state(TrainState(
        params=state.params,
        opt_state=set_learning_rate(trainer.tx.init(state.params), lr),
        step=jnp.asarray(step0)))
    return loop.readouts


def router_biases(flat: dict) -> dict:
    """The leaves the program names ``*/router/bias``: selection biases
    that a model's own balancing moves, not the optimiser (none in a model
    without routers)."""
    return {k: v for k, v in flat.items() if k.endswith("/router/bias")}


def prepare(ctx, seed: int, tracer, annotate, whole_epoch: bool = False):
    """As ``drivers/train.prepare``: the run's one Trainer with the
    benchmark's weights from the seed, its routers settled
    (``settle_routers``, the cell's ``settle_steps``), driven through its
    first steps by the window's own call and feed. A token cell has no
    host cache to fill (64 KB a step), so set-up runs the compared steps
    and no whole epoch."""
    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.parallel import build_strategy
    from distributedpytorch_tpu.train.loop import Trainer

    from distributedpytorch_tpu import models

    arch = ctx.config["train_config"]["model_arch"]
    if arch not in getattr(models, "MODELS", ()):
        raise NotMeasurable(f"the program has no model_arch {arch!r}: "
                            "it cannot run this configuration")
    cell, config, devices = ctx.cell, effective_config(ctx), ctx.devices
    cfg = build_train_config(ctx, seed)
    mix = dict(traffic_params.load(cell["traffic"]))
    if ctx.args.rehearse:
        mix.update(cell["rehearsal"]["traffic"])
    mix = (mix, cfg.seq_len, config["vocab_size"], seed)
    trainer = Trainer(cfg, dataset=traffic_tokens.build(*mix),
                      strategy=build_strategy(cfg, list(devices)))
    if getattr(ctx.args, "fault", None):
        plant_fault(trainer, ctx.args.fault, faults(config))

    # the program's own initial state goes first (8 GB at the published
    # sizes): the seed's weights, their copy in the program's tree and the
    # optimiser's moments would not fit beside it
    template = jax.eval_shape(lambda: trainer.state.params)
    shapes = {k: tuple(v.shape)
              for k, v in weights_mod.flat_names(template).items()}
    ref_module = flops.load_reference(config)
    if shapes != {k: tuple(v) for k, v in ref_module.param_shapes(config).items()}:
        raise NotMeasurable("the program's parameter leaves are not the "
                            "reference's: names or shapes differ")
    state = trainer.state
    trainer.state = None
    step0 = state.step
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        leaf.delete()
    del state
    flat = importlib.import_module(config.get("weights", "weights_tokens")).make(
        shapes, seed, config)
    start = {k: np.asarray(v) for k, v in flat.items()}  # kept on the host
    params = weights_mod.to_program(flat, template)
    del flat
    from distributedpytorch_tpu.train.steps import TrainState
    trainer.state = trainer.strategy.place_state(TrainState(
        params=params, opt_state=trainer.tx.init(params), step=step0))
    del params
    settled = settle_routers(
        trainer, tracer, annotate,
        (cell["rehearsal"] if ctx.args.rehearse else cell).get("settle_steps", 0))
    start.update({k: np.asarray(v) for k, v in router_biases(
        weights_mod.flat_names(trainer.state.params)).items()})

    loop = step_loop(trainer, tracer, annotate)
    prog = {"losses": [], "batches": [], "settled": settled}
    norms = jax.jit(reference.leaf_norms)
    b1 = config["optimizer"]["b1"]
    diff_norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))

    def compared(lp, payload, out):
        if lp.steps > COMPARED_STEPS:
            return
        prog["losses"].append(float(np.asarray(out).ravel()[0]))
        prog["batches"].append(payload)
        if lp.steps == 1:
            mu = weights_mod.flat_names(
                image_driver.find_adam_mu(trainer.state.opt_state))
            prog["grad_norms"] = {k: float(v) / (1.0 - b1)
                                  for k, v in norms(mu).items()}
            prog["grad"] = {k: np.asarray(v) / np.float32(1.0 - b1)
                            for k, v in mu.items()}
        if lp.steps == COMPARED_STEPS:
            now = weights_mod.flat_names(trainer.state.params)
            prog["change_norms"] = {k: float(diff_norm(now[k], start[k]))
                                    for k in now}
            prog["biases"] = {k: np.asarray(v)
                              for k, v in router_biases(now).items()}
            lp.on_step = None

    loop.on_step = compared
    if whole_epoch:
        loop.run(epochs=1)
    else:
        loop.run(max_steps=COMPARED_STEPS)
    loop.drain()
    if len(prog["losses"]) < COMPARED_STEPS:
        raise NotMeasurable(
            f"the first epoch has {len(prog['losses'])} steps, fewer than the "
            f"{COMPARED_STEPS} that are compared")
    prog["rows_repeated"] = traffic_tokens.rows_repeated(prog["batches"])
    return types.SimpleNamespace(trainer=trainer, loop=loop, cfg=cfg, mesh=None,
                                 start=start, stats0=None, prog=prog, mix=mix,
                                 rows=None, config=config)


def seed_weights(session) -> dict:
    """The seed's weights on the device again (the host kept them through
    the window), under the reference's flat names."""
    import jax.numpy as jnp

    return {k: jnp.asarray(v) for k, v in session.start.items()}


def release(session):
    """Free the program's state on the device, and the programs it
    loaded: a loaded step keeps its 4.8 GB of temporaries reserved, beside
    which the reference's float32 state does not fit."""
    import jax

    image_driver.release(session)
    jax.clear_caches()
    gc.collect()


def ensure_region(session, nbytes: int):
    """Have the chip set aside ``nbytes`` for loaded programs' temporaries
    NOW, while nothing of what follows is placed. This runtime keeps them
    in one region at the bottom of the device's memory (``bytes_reserved``
    of ``memory_stats()``) that grows when a program is first run and only
    while no array lies just above it; after a training window the freed
    heap hands out low addresses again, and a program loaded behind the
    reference's 2.7 GB of weights then finds no room for its 2.5 GB (PERF.md
    §6, PR 27). A program with one large temporary and no input, run first
    and kept loaded, grows the region to size."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    have = getattr(session, "region", (0, None))[0]
    if nbytes <= have:
        return
    n = max(1, nbytes // 4)
    grow = jax.jit(lambda x: jnp.sum(lax.optimization_barrier(
        jnp.broadcast_to(x, (n,)))[:: 1 << 20]))
    grow(jnp.float32(1.0)).block_until_ready()
    session.region = (nbytes, grow)  # kept loaded: the region is its programs'


def compared_rows(session) -> list:
    if session.rows is None:
        session.rows, session.prog["rows_altered"] = traffic_tokens.same_rows(
            traffic_tokens.build(*session.mix), session.prog["batches"])
    return session.rows


def follow(ctx, session, mode: str = "f32", keep: float = 1.0,
           skip_update: bool = False) -> dict:
    """The plain reference (or what stands in the program's place) over
    the compared steps, one sequence at a time: its losses, its first
    gradient as the optimiser gets it with the per-leaf norms, and the
    per-leaf norms of the parameters' change."""
    import jax
    import jax.numpy as jnp

    config, start = session.config, session.start
    ref_module = flops.load_reference(config)
    if mode == "no_routed":
        config, start = ref_module.routed_left_out(config, start)
    # a batch of ONE sequence is cut by positions (half of it is half the
    # row), a larger one by rows
    rows = compared_rows(session)
    one, length = rows[0].shape[0] == 1, session.cfg.seq_len
    if one and keep < 1.0:
        length = max(2, round(length * keep))
    # every program of the reference is loaded before its first array is
    # placed (see make_loss_and_grad)
    loss_and_grad = ref_module.make_loss_and_grad(
        config, "f32" if mode == "no_routed" else mode, tokens=length)
    ensure_region(session, int(1.1 * loss_and_grad.temp_bytes))
    update = reference.make_update(config)
    balance = getattr(ref_module, "balanced_biases", None)
    cur = {k: jnp.asarray(v) for k, v in start.items()}
    # Adam's moments wait on the host between updates (5.3 GB that the
    # gradient's pass does not need beside it); a leaf at a time, under one
    # name so that the update compiles once a shape
    moments = {k: [np.zeros(x.shape, np.float32)] * 2 for k, x in start.items()}
    losses, g1, routed = [], {}, None
    for i, tokens in enumerate(rows):
        tokens = (tokens[:, :length] if one
                  else tokens[: max(1, round(len(tokens) * keep))])
        loss, g, chosen, loads = loss_and_grad(cur, tokens)
        routed = chosen if i == 0 else routed
        # the routers' own balancing, from the biases the step began with
        biases = ({} if skip_update or balance is None
                  else balance(config, cur, loads))
        for k in list(cur):
            m, v = ({"x": jnp.asarray(x)} for x in moments[k])
            new, m, v, g_k = update({"x": cur[k]}, m, v, jnp.float32(i + 1),
                                    {"x": g.pop(k)}, jnp.float32(1.0))
            moments[k] = [np.asarray(m["x"]), np.asarray(v["x"])]
            if not skip_update:
                cur[k] = new["x"]
            if i == 0:  # the first gradient as the optimiser gets it
                g1[k] = np.asarray(g_k["x"])
        cur.update(biases)
        losses.append(float(loss))
    diff_norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    change = {k: float(diff_norm(cur[k], start[k])) for k in cur}
    out = {"losses": losses, "grad": g1, "routing": routed,
           "biases": {k: np.asarray(v) for k, v in router_biases(cur).items()},
           "grad_norms": {k: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
                          for k, x in g1.items()},
           "change_norms": change}
    for k, x in session.start.items():
        if out["grad"][k].shape != x.shape:  # a leaf left out: nothing moved
            out["grad"][k] = np.zeros(x.shape, x.dtype)
            out["grad_norms"][k] = out["change_norms"][k] = 0.0
    return out


def program_routing(session) -> list:
    """The experts the PROGRAM's routers choose (bf16 upstream of their
    float32 scores) for the first compared batch's first sequence, from the
    seed's weights: [(S, k) int32, ...] per expert block. The forward pass
    is compiled and loaded before the weights are placed (as the
    reference's programs are)."""
    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.models import create_model

    tokens = compared_rows(session)[0][:1]
    model, _ = create_model(session.cfg)
    template = jax.eval_shape(model.init, jax.random.key(0))
    chosen = jax.jit(lambda p, t: model.hidden(p, t, routing=True)[3]).lower(
        template, jax.ShapeDtypeStruct(tokens.shape, jnp.int32)).compile()
    out = chosen(weights_mod.to_program(seed_weights(session), template),
                 jnp.asarray(tokens))
    return [np.asarray(x) for x in out]


def routing_flips_pct(mine: list, ref: dict) -> float:
    """The share of (token, slot) choices on which the program's router
    chooses another expert than the reference's, over all expert blocks.
    Shown, not compared."""
    differ = total = 0
    for a, b in zip(mine, ref["routing"]):
        b = np.asarray(b)
        differ += sum(len(set(x) - set(y)) for x, y in zip(a.reshape(b.shape), b))
        total += b.size
    return 100.0 * differ / max(total, 1)


def biases_differ_pct(prog: dict, ref: dict) -> float:
    """The share of the routers' selection biases (expert blocks x
    experts) that the program's balancing left elsewhere than the
    reference's after the compared steps: an expert whose load is within
    the routing flips of the mean gets the other sign. Shown, not
    compared."""
    differ = sum(int(np.sum(prog["biases"][k] != v))
                 for k, v in ref["biases"].items())
    return 100.0 * differ / max(sum(v.size for v in ref["biases"].values()), 1)


def counters(readouts, names, since: float = 0.0) -> dict:
    """The counters of the steps dispatched from ``since`` on, summed
    over the steps and over the expert blocks, from the readouts the steps
    returned (read here, after the window: never a readback inside it)."""
    rows = [np.asarray(out).ravel()[1:] for t, out in readouts if t >= since]
    if not rows or not names:
        return {}
    total, out = np.sum(rows, axis=0), {}
    for name, value in zip(names, total):
        family = name.split("/")[0]
        out[family] = out.get(family, 0.0) + float(value)
    out["steps"] = len(rows)
    return out


def routed_by_step(readouts, names) -> list:
    """Per step, the rows its routers sent to the held experts, summed
    over the expert blocks, and the fullest held expert's rows."""
    out = []
    for _, readout in readouts:
        row = dict(zip(names, np.asarray(readout).ravel()[1:]))
        out.append((
            int(sum(v for k, v in row.items() if k.startswith("moe_rows_routed/"))),
            int(max((v for k, v in row.items()
                     if k.startswith("moe_rows_max_expert/")), default=0))))
    return out


def run(ctx) -> dict:
    import jax

    devices = ctx.devices
    meter, tracer, annotate = image_driver.instruments(ctx)
    mem = {"start": image_driver.memory_readings(jax, devices)}
    session = prepare(ctx, ctx.args.seed, tracer, annotate)
    trainer, loop, cfg, prog = (session.trainer, session.loop, session.cfg,
                                session.prog)
    config = session.config
    mem["after_setup"] = image_driver.memory_readings(jax, devices)
    window, setup_s, trace_dir = image_driver.measure(ctx, loop, meter, tracer,
                                                      "sequences")
    mem["after_window"] = image_driver.memory_readings(jax, devices)
    spans = tracer.events()
    memory = image_driver.peak_memory(trainer, prog["batches"][0],
                                      mem["after_window"])
    names = trainer.counter_names
    counted = counters(loop.readouts, names,
                       (window["untraced"] or window)["t0"])
    ref_module = flops.load_reference(config)
    expert_blocks = ref_module.expert_blocks(config)
    # rows one expert block's routers sent to the held experts, a step
    routed_rows = (counted["moe_rows_routed"] / counted["steps"] / expert_blocks
                   if counted.get("steps") and expert_blocks else None)
    if expert_blocks:
        by_step = {"settle": routed_by_step(prog["settled"], names),
                   "run": routed_by_step(loop.readouts, names)}
        ctx.say(f"rows routed to held experts (all expert blocks, fullest "
                f"expert) by step: {by_step}")

    # --- free the program's state, then the reference ---------------------
    batch_size, seq_len = cfg.batch_size, cfg.seq_len
    del trainer, loop
    release(session)
    mem["after_release"] = image_driver.memory_readings(jax, devices)
    ctx.say(f"after release: {devices[0].memory_stats()}")
    t_ref = time.perf_counter()
    ref = follow(ctx, session)
    mine = program_routing(session) if expert_blocks else None
    verdict = judge(ctx, prog, ref)
    routers = {}
    if expert_blocks:  # shown, not compared
        routers["routing_flips_pct"] = routing_flips_pct(mine, ref)
        verdict["rows"] += [
            ("routing_flips_pct", routers["routing_flips_pct"], None),
            ("biases_differ_pct", biases_differ_pct(prog, ref), None)]
    verdict["reference_s"] = time.perf_counter() - t_ref

    out = image_driver.report(
        ctx, window, setup_s, trace_dir, meter, mem, memory, spans, verdict,
        batch_size,
        # logical FLOPs of a sequence, the routed experts' over the rows
        # that were routed to them in this window
        ref_module.train_flops_per_sample(
            config, seq_len,
            routed_rows=None if routed_rows is None else routed_rows / batch_size),
        "sequences")
    out["info"].update(
        tokens_per_s=window["images"] * seq_len / window["seconds"],
        counters=counted, rows_routed_per_block_step=routed_rows, **routers)
    out.update(seq_len=seq_len, counters=counted, routed_rows=routed_rows)
    return out
