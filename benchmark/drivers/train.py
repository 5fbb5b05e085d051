"""Driver of ``kind: train`` cells: one Trainer, its loader, its feed and
its compiled step, driven as ``Trainer._run`` composes them, first through
the steps that are compared and the rest of the first epoch (set-up: the
host cache fills), then for the window.

From the program it takes the system under test: ``TrainConfig``,
``build_strategy``, ``Trainer`` with ``train_loader``, ``strategy``,
``train_step`` and ``state``, the feed (``stacked_work``,
``pipelined_placement``) and the ``StepTimeline`` spans the feed records.
Weights, data, metrics and the comparison are the benchmark's.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import os
import shutil
import time
import types

import numpy as np

import check
import flops
import reference
import traffic as traffic_mod
import weights as weights_mod

#: Steps whose result the loop has not waited for yet: the host runs this
#: far ahead of the device and no farther.
LAG = 2
#: Steps that the reference follows.
COMPARED_STEPS = 3


class NotMeasurable(SystemExit):
    """The run cannot give a measurement: message and exit code."""

    def __init__(self, message: str, code: int = 2):
        super().__init__(code)
        self.message = message


def compile_meter(jax):
    """jax's own count of backend compiles and persistent-cache hits
    (copied from chip_smoke._compile_meter)."""
    meter = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            meter["compiles"] += 1
            meter["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            meter["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return meter


class StepLoop:
    """The step loop of ``Trainer._run``: epoch after epoch of the loader's
    batches through ``stacked_work`` and ``pipelined_placement`` into
    ``trainer.train_step``. One object serves set-up and the window.
    Samples are counted on the batch's ``field``; with ``keep_readouts``
    every step's second output (a device array of a few floats: the loss,
    or ``[loss, *counters]``) is kept with the time of its dispatch, to be
    read once the window has closed."""

    def __init__(self, trainer, tracer, annotate, field: str = "image",
                 keep_readouts: bool = False):
        self.trainer = trainer
        self.tracer = tracer
        self.annotate = annotate
        self.field = field
        self.readouts = [] if keep_readouts else None
        self.epoch = 0
        self.steps = 0
        self.images = 0
        self.wait_s = 0.0
        self.inflight = collections.deque()
        self.on_step = None

    def run(self, deadline=None, epochs=None, max_steps=None):
        """Whole epochs until ``epochs`` are done, or steps until the
        clock passes ``deadline`` or ``max_steps`` more are dispatched
        (the epoch then in hand is dropped)."""
        from distributedpytorch_tpu.utils.prefetch import (
            pipelined_placement,
            stacked_work,
        )

        tr, cfg = self.trainer, self.trainer.config
        done, last = 0, None if max_steps is None else self.steps + max_steps
        while epochs is None or done < epochs:
            source = pipelined_placement(
                stacked_work(tr.train_loader.epoch_batches(self.epoch), 1,
                             cfg.batch_size),
                tr.strategy.place_work,
                depth=cfg.prefetch_batches,
                tracer=self.tracer,
                epoch=self.epoch,
                max_retries=cfg.data_retries,
                retry_backoff_s=cfg.retry_backoff_s,
            )
            with contextlib.closing(source):
                while True:
                    t0 = time.perf_counter()
                    with self.annotate("input_wait"):
                        item = next(source, None)
                    self.wait_s += time.perf_counter() - t0
                    if item is None:
                        break
                    (_, payload), placed = item
                    with self.annotate("dispatch"), self.tracer.span(
                            "dispatch", step=self.steps + 1):
                        tr.state, out = tr.train_step(tr.state, placed)
                    del placed
                    self.steps += 1
                    self.images += int(payload[self.field].shape[0])
                    self.inflight.append(out)
                    if self.readouts is not None:
                        self.readouts.append((time.perf_counter(), out))
                    if len(self.inflight) > LAG:
                        with self.annotate("readback"):
                            self.inflight.popleft().block_until_ready()
                    if self.on_step is not None:
                        self.on_step(self, payload, out)
                    if deadline is not None and time.perf_counter() >= deadline:
                        return
                    if last is not None and self.steps >= last:
                        return
            self.epoch += 1
            done += 1

    def drain(self):
        while self.inflight:
            self.inflight.popleft().block_until_ready()


def tally(loop) -> dict:
    return {"steps": loop.steps, "images": loop.images, "wait_s": loop.wait_s}


def since(mark: dict, loop) -> dict:
    """What the loop has done since ``mark = tally(loop)``."""
    return {k: v - mark[k] for k, v in tally(loop).items()}


def find_adam_mu(opt_state):
    import jax

    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def memory_readings(jax, devices) -> list:
    out = []
    for d in devices:
        s = d.memory_stats() or {}
        out.append({k: int(s[k]) for k in (
            "bytes_in_use", "peak_bytes_in_use", "largest_alloc_size",
            "bytes_limit") if k in s})
    return out


def build_train_config(ctx, seed: int):
    from distributedpytorch_tpu.config import TrainConfig

    cell, config = ctx.cell, ctx.config
    out_dir = os.path.join(ctx.root, ".bench_run", cell["name"])
    fields = dict(config["train_config"])
    fields.update(cell.get("train_config", {}))
    fields.update(ctx.rehearsal_train_config())
    fields.update(
        seed=seed, synthetic_samples=0, val_percent=0.0,
        epochs=10 ** 9, checkpoint_dir=os.path.join(out_dir, "checkpoints"),
        log_dir=os.path.join(out_dir, "logs"),
        loss_dir=os.path.join(out_dir, "loss"),
    )
    for key in ("image_size", "model_widths"):
        if fields.get(key) is not None:
            fields[key] = tuple(fields[key])
    return TrainConfig(**fields)


FAULTS = ("unchanged", "half_batch", "no_exchange", "wrong_mask")


def plant_fault(trainer, fault: str, chips: int):
    """Break the timed path underneath, for the tests that must see
    ``correct`` come out false: ``unchanged`` returns the state it was
    given; ``half_batch`` leaves out half of the rows and takes the mean
    over the rest; ``no_exchange`` is what chip 0 computes where the
    exchange between the chips is left out: its own share of the rows;
    ``wrong_mask`` is a loader that gives each batch's first image the
    second's mask."""
    import jax
    import jax.numpy as jnp

    if fault == "wrong_mask":
        batches = trainer.train_loader.epoch_batches

        def epoch_batches(epoch=0):
            for b in batches(epoch):
                mask = b["mask"].copy()
                mask[0] = mask[1]
                yield {**b, "mask": mask}

        trainer.train_loader.epoch_batches = epoch_batches
        return
    real = trainer.train_step
    if fault == "unchanged":
        def step(state, batch):
            _, loss = real(jax.tree.map(jnp.copy, state), batch)
            return state, loss
    elif fault in ("half_batch", "no_exchange"):
        share = 2 if fault == "half_batch" else chips

        def step(state, batch):
            return real(state, {k: v[: v.shape[0] // share]
                                for k, v in batch.items()})
    else:
        raise NotMeasurable(f"unknown fault {fault!r} (known: {FAULTS})")
    step.lower = real.lower  # what ``peak_memory`` asks of the jitted step
    trainer.train_step = step


def prepare(ctx, seed: int, tracer, annotate, whole_epoch: bool = True):
    """Build the one Trainer of a run, give it the benchmark's weights
    from the seed, and drive it through its first steps with the window's
    own call and feed. ``whole_epoch`` goes on to the end of the first
    epoch, which fills the host cache. Returns the session: that one
    Trainer with its loop, which then serves the window, and what the
    comparison needs."""
    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.parallel import build_strategy
    from distributedpytorch_tpu.train.loop import Trainer

    cell, config, devices = ctx.cell, ctx.config, ctx.devices
    cfg = build_train_config(ctx, seed)
    traffic = dict(traffic_mod.load(cell["traffic"]))
    traffic.update(ctx.rehearsal_traffic())
    mix = (traffic, cfg.image_size, seed)
    dataset = traffic_mod.build(*mix)
    trainer = Trainer(cfg, dataset=dataset,
                      strategy=build_strategy(cfg, list(devices)))
    if getattr(ctx.args, "fault", None):
        plant_fault(trainer, ctx.args.fault, len(devices))

    # weights from the seed, the benchmark's own, into the program's tree
    state = trainer.state
    mesh = trainer.strategy.mesh
    replicated = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        replicated = NamedSharding(mesh, PartitionSpec())
    shapes = {k: v.shape for k, v in weights_mod.flat_names(state.params).items()}
    flat = weights_mod.make(shapes, seed, sharding=replicated)
    flipped = config.get("upconv_leaves_flipped", ())
    params = weights_mod.to_program(jax.tree.map(jnp.copy, flat), state.params,
                                    flipped)
    state = state.replace(params=params, opt_state=trainer.tx.init(params))
    trainer.state = trainer.strategy.place_state(state)
    del state, params
    stats0 = None
    if trainer.state.model_state is not None:
        stats0 = {k: np.asarray(v) for k, v in
                  weights_mod.flat_names(trainer.state.model_state).items()}

    loop = StepLoop(trainer, tracer, annotate)
    prog = {"losses": [], "batches": []}
    norms = jax.jit(reference.leaf_norms)
    b1 = config["optimizer"]["b1"]
    diff_norms = jax.jit(lambda a, b: reference.leaf_norms(
        {k: a[k] - b[k] for k in b}))

    def compared(lp, payload, loss):
        if lp.steps > COMPARED_STEPS:
            return
        prog["losses"].append(float(loss))
        prog["batches"].append(payload)
        if lp.steps == 1:
            mu = weights_mod.flat_names(find_adam_mu(trainer.state.opt_state))
            prog["grad_norms"] = {k: float(v) / (1.0 - b1)
                                  for k, v in norms(mu).items()}
            # the gradient itself, kept on the host through the window and
            # laid out as the reference lays it out
            prog["grad"] = {k: np.asarray(v) / np.float32(1.0 - b1)
                            for k, v in mu.items()}
            for k in flipped:
                prog["grad"][k] = prog["grad"][k][::-1, ::-1]
        if lp.steps == COMPARED_STEPS:
            now = weights_mod.flat_names(trainer.state.params)
            start = weights_mod.flat_names(weights_mod.to_program(
                flat, trainer.state.params, flipped))
            prog["change_norms"] = {k: float(v) for k, v in
                                    diff_norms(now, start).items()}
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
    prog["rows_repeated"] = check.rows_repeated(prog["batches"])
    return types.SimpleNamespace(trainer=trainer, loop=loop, cfg=cfg, mesh=mesh,
                                 flat=flat, stats0=stats0, prog=prog, mix=mix,
                                 rows=None)


def instruments(ctx):
    """What a run of either driver starts with: jax's compile meter, the
    program's timeline and the profiler's annotation (both of the last
    off in an untraced run)."""
    import jax

    from distributedpytorch_tpu.utils.trace import StepTimeline

    if ctx.args.trace:
        annotate = lambda name, **kw: jax.profiler.TraceAnnotation(  # noqa: E731
            "bench_" + name, **kw)
    else:
        annotate = lambda name, **kw: contextlib.nullcontext()  # noqa: E731
    return compile_meter(jax), StepTimeline(enabled=bool(ctx.args.trace)), annotate


def measure(ctx, loop, meter, tracer, samples: str = "images"):
    """The window, for every kind of training cell: ``loop`` driven until
    the clock ends it. A ``--trace 1`` run traces its first seconds, and
    the seconds that writing the trace takes are left out of the window.
    Returns ``(window, setup_s, trace_dir)``; refuses a window in which
    something compiled or no step completed."""
    import jax

    args = ctx.args
    trace_dir = os.path.join(ctx.root, ".bench_run", ctx.cell["name"], "trace")
    traced = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # the harness's own annotations and nothing finer: at the default
        # level the runtime's polling threads write a million events a
        # second, which slows the feed and turns the run input-bound
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(
                "bench_sync", pc_ns=time.perf_counter_ns()):
            pass
    tracer.flush()
    start = tally(loop)
    compiles0 = meter["compiles"]
    t0 = time.perf_counter()
    setup_s = time.monotonic() - ctx.t_start
    paused, rest = 0.0, None
    if args.trace:
        # the traced part of the window: its first seconds
        loop.run(deadline=t0 + min(args.seconds, ctx.trace_seconds))
        loop.drain()
        traced = (t0, time.perf_counter())
        jax.profiler.stop_trace()  # writes the trace: seconds of no work,
        rest = (time.perf_counter(), tally(loop))  # left out of the window
        paused = rest[0] - traced[1]
    loop.run(deadline=t0 + paused + args.seconds)
    loop.drain()
    t1 = time.perf_counter()
    window = {
        "t0": t0, "seconds": t1 - t0 - paused, **since(start, loop),
        "compiles": meter["compiles"] - compiles0,
        "compiles_before": compiles0,
        "epochs": loop.epoch,
        "traced": traced,
        # the rest of the window, after the profiler has stopped: what the
        # per-layer metrics on the host's clock are taken over, since the
        # profiler holds the feed back while it runs (PERF.md)
        "untraced": rest and {"t0": rest[0], "seconds": t1 - rest[0],
                              **since(rest[1], loop)},
    }
    ctx.say(f"window: {window['steps']} steps, {window['images']} {samples} in "
            f"{window['seconds']:.3f} s, {window['compiles']} compiles, waited "
            f"{window['wait_s']:.3f} s for input")
    if window["compiles"]:
        raise NotMeasurable(
            f"{window['compiles']} compilations inside the measured window: "
            "a shape was not warmed in set-up")
    if window["steps"] < 1:
        raise NotMeasurable("no step completed inside the window")
    return window, setup_s, trace_dir if args.trace else None


def report(ctx, window, setup_s, trace_dir, meter, mem, memory, spans, verdict,
           batch, flops_per_sample, samples: str = "images") -> dict:
    """The ``run`` that ``run.py`` and the readers take, from what a
    window and its comparison gave (``info`` counts the window's samples
    under the cell's own word for them)."""
    return {
        "window": window, "peak_bytes": memory["peak_bytes"],
        "end_to_end": {"train_imgs_per_s": window["images"] / window["seconds"],
                       "setup_s": setup_s},
        "attempted": window["steps"], "failed": 0,
        "info": {"steps": window["steps"], samples: window["images"],
                 "window_s": window["seconds"], "epochs": window["epochs"],
                 "compiles_before_window": window["compiles_before"],
                 "compile_s": meter["compile_s"],
                 "cache_hits": meter["cache_hits"],
                 "reference_s": verdict["reference_s"],
                 "memory": memory,
                 "worst_leaf": verdict["where"]},
        "spans": spans, "verdict": verdict, "memory": mem,
        "meter": dict(meter), "batch": batch,
        "chips": len(ctx.devices), "trace_dir": trace_dir,
        "train_flops_per_image": flops_per_sample,
    }


def run(ctx) -> dict:
    import jax

    devices = ctx.devices
    meter, tracer, annotate = instruments(ctx)
    mem = {"start": memory_readings(jax, devices)}
    session = prepare(ctx, ctx.args.seed, tracer, annotate)
    mem["after_setup"] = memory_readings(jax, devices)
    window, setup_s, trace_dir = measure(ctx, session.loop, meter, tracer)
    mem["after_window"] = memory_readings(jax, devices)
    spans = tracer.events()
    memory = peak_memory(session.trainer, session.prog["batches"][0],
                         mem["after_window"])

    # --- free the program's state, then the reference ---------------------
    release(session)
    t_ref = time.perf_counter()
    ref = follow(ctx, session)
    verdict = judge(ctx, session.prog, ref)
    verdict["reference_s"] = time.perf_counter() - t_ref
    return report(ctx, window, setup_s, trace_dir, meter, mem, memory, spans,
                  verdict, session.cfg.batch_size,
                  flops.train_flops_per_image(ctx.effective_config()))


def peak_memory(trainer, batch, readings) -> dict:
    """The peak on the fullest chip: the allocator's own peak
    (``peak_bytes_in_use``: the state, the batches in flight, results)
    plus the step program's temporaries, which that counter does not see
    on this runtime (it read 0.89 GB after a window whose compiled step
    holds 11.5 GB of temporaries; PERF.md, Findings of PR 24). The
    temporaries are read from the very executable the window ran
    (``memory_analysis`` of the jitted step, found again in the cache)."""
    compiled = trainer.train_step.lower(
        trainer.state, trainer.strategy.place_batch(batch)).compile()
    temporaries = int(compiled.memory_analysis().temp_size_in_bytes)
    allocator = max((m.get("peak_bytes_in_use", 0) for m in readings), default=0)
    return {"peak_bytes": allocator + temporaries,
            "allocator_peak_bytes": allocator,
            "step_temporaries_bytes": temporaries}


def release(session):
    """Free the program's state on the device; what the comparison needs
    (weights from the seed, the compared rows, the program's numbers)
    stays."""
    session.trainer.state = None
    session.trainer = session.loop = None
    gc.collect()


def compared_rows(session) -> list:
    """The rows of the compared steps, made anew from the traffic mix in
    the order in which the program's loader stacked them (once a
    session). The reference is fed these and not what the loader handed
    over; ``rows_altered`` counts the loader's rows that differ from
    them."""
    if session.rows is None:
        session.rows, session.prog["rows_altered"] = traffic_mod.same_rows(
            traffic_mod.build(*session.mix), session.prog["batches"])
    return session.rows


def follow(ctx, session, mode: str = "f32", keep: float = 1.0,
           skip_update: bool = False) -> dict:
    """The plain reference (or, in another ``mode`` or with a fault
    planted, what stands in the program's place) over the compared steps:
    its losses, first gradient's norms and change's norms. ``keep`` is the
    share of each batch's rows that is taken, from the front: 0.5 is half
    of the batch left out and the mean taken over the rest; one chip's
    share is what that chip computes where the exchange between chips is
    left out."""
    import jax
    import jax.numpy as jnp

    config, mesh = ctx.effective_config(), session.mesh
    ref_module = flops.load_reference(config)
    put = jnp.asarray
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        rows = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
        put = lambda x: jax.device_put(x, rows)  # noqa: E731
    state = None
    if session.stats0 is not None:
        state = {k: put_rep(jax, v, mesh) for k, v in session.stats0.items()}

    def batches():
        for image, mask in compared_rows(session):
            n = max(1, round(len(image) * keep))
            yield image[:n], mask[:n]

    out = reference.follow(ref_module, config, session.flat, state, batches(),
                           grad_scale=float(session.cfg.batch_size), mode=mode,
                           rows=reference_rows(ctx),
                           skip_update=skip_update, put=put)
    out.pop("state")
    return out


def reference_rows(ctx) -> int:
    """Rows to a block of the reference, per chip times the chips (0: the
    whole batch at once); one row to a block in the rehearsal, so that it
    goes the same way."""
    rows = ctx.cell["reference"]["rows_per_block"]
    return (1 if ctx.args.rehearse else rows) * len(ctx.devices) if rows else 0


def judge(ctx, mine: dict, ref: dict) -> dict:
    r = check.readings(mine, ref)
    ok, rows = check.judge(r["numbers"], ctx.cell["limits"])
    return {"correct": ok, "rows": rows, "where": r["where"],
            "numbers": r["numbers"]}


def put_rep(jax, x, mesh):
    import jax.numpy as jnp

    if mesh is None:
        return jnp.asarray(x)
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(x, NamedSharding(mesh, PartitionSpec()))


def trace_summary(run) -> dict:
    """``busy_s`` (averaged over the chips used), ``window_s`` and the
    breakdown of a traced run."""
    import trace_reduce as tr

    t0, t1 = tr.traced_window(run)
    devs = run["trace"]["devices"]
    busy = {d: tr.busy_seconds(ops, t0, t1) for d, ops in devs.items()}
    first = sorted(devs)[0]
    gaps = tr.idle_gaps(devs[first], run["trace"]["host"], t0, t1)
    breakdown = {"device_ops": tr.top_ops(tr.clip(devs[first], t0, t1)),
                 "idle_gaps": gaps}
    if len(devs) > 1:
        fullest = max(busy, key=busy.get)
        emptiest = min(busy, key=busy.get)
        breakdown["idle_gaps"] = (gaps[:8] + [
            [f"device_{fullest}_idle_share_fullest", 1 - busy[fullest] / (t1 - t0)],
            [f"device_{emptiest}_idle_share_emptiest", 1 - busy[emptiest] / (t1 - t0)],
        ])
    return {"busy_s": sum(busy.values()) / len(busy), "window_s": t1 - t0,
            "breakdown": breakdown}
