"""The fully-overlapped step pipeline (ISSUE 1 tentpole): exact equivalence
with the synchronous loop, epoch-persistent sample cache, placement overlap
via the step-timeline tracer, and non-blocking checkpoints.

Everything runs on the CPU backend: the pipeline only moves WHERE work
happens (worker threads, background writer, deferred drains) — never WHAT
is computed — so the per-step loss sequence must be bit-identical to the
inline baseline, and that is the core assertion here.
"""

import json
import os
import time

import numpy as np
import pandas as pd
import pytest

from distributedpytorch_tpu.config import TrainConfig
from distributedpytorch_tpu.data import SampleCache, SyntheticSegmentationDataset
from distributedpytorch_tpu.data.loader import DataLoader
from distributedpytorch_tpu.train import Trainer
from distributedpytorch_tpu.utils.prefetch import (
    pipelined_placement,
    stacked_work,
)
from distributedpytorch_tpu.utils.trace import (
    StepTimeline,
    load_events,
    summarize_timeline,
)

H, W = 32, 48
WIDTHS = (8, 16)


def _config(tmp_path, **kw):
    defaults = dict(
        train_method="singleGPU",
        epochs=2,
        batch_size=8,
        learning_rate=3e-4,
        val_percent=25.0,
        seed=42,
        compute_dtype="float32",
        image_size=(W, H),
        model_widths=WIDTHS,
        synthetic_samples=32,
        checkpoint_dir=str(tmp_path / "checkpoints"),
        log_dir=str(tmp_path / "logs"),
        loss_dir=str(tmp_path / "loss"),
        metric_every_steps=2,
        num_workers=0,
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


class CountingDataset(SyntheticSegmentationDataset):
    """Synthetic dataset that counts decode (__getitem__) calls."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.decodes = 0

    def __getitem__(self, idx):
        self.decodes += 1
        return super().__getitem__(idx)


# ---------------------------------------------------------------------------
# Equivalence: async pipeline == synchronous baseline, bit for bit
# ---------------------------------------------------------------------------


def _train_and_read(tmp_path, tag, **kw):
    import jax

    cfg = _config(tmp_path / tag, **kw)
    Trainer(cfg).train()
    df = pd.read_pickle(tmp_path / tag / "loss" / "singleGPU" / "train_loss.pkl")
    t = Trainer(_config(tmp_path / tag, checkpoint_name="singleGPU", **kw))
    params = [np.asarray(p) for p in jax.tree.leaves(jax.device_get(t.state.params))]
    return df["Loss"].to_numpy(), params


def test_async_pipeline_matches_synchronous_exactly(tmp_path):
    """prefetch depth 2 + host cache + deferred metric drains vs the fully
    inline depth-0/uncached loop: SAME seed must give the IDENTICAL float
    sequence (not allclose — the pipeline must not change the computation)
    and identical final params."""
    sync_losses, sync_params = _train_and_read(
        tmp_path, "sync", prefetch_batches=0, host_cache_mb=0
    )
    async_losses, async_params = _train_and_read(
        tmp_path, "async", prefetch_batches=2, host_cache_mb=64
    )
    np.testing.assert_array_equal(sync_losses, async_losses)
    for p_sync, p_async in zip(sync_params, async_params):
        np.testing.assert_array_equal(p_sync, p_async)


def test_async_pipeline_matches_synchronous_stacked(tmp_path):
    """Same equivalence with K=2 fused dispatches: the K-stack np.stack +
    placement now run on the worker thread, and must still reproduce the
    inline stacked loop exactly (including the ragged-tail fallback:
    batch 5 over 24 train samples)."""
    kw = dict(
        steps_per_dispatch=2, batch_size=5, epochs=1, model_widths=(8,),
        image_size=(16, 16),
    )
    sync_losses, sync_params = _train_and_read(
        tmp_path, "sync", prefetch_batches=0, host_cache_mb=0, **kw
    )
    async_losses, async_params = _train_and_read(
        tmp_path, "async", prefetch_batches=2, host_cache_mb=64, **kw
    )
    np.testing.assert_array_equal(sync_losses, async_losses)
    for p_sync, p_async in zip(sync_params, async_params):
        np.testing.assert_array_equal(p_sync, p_async)


# ---------------------------------------------------------------------------
# Epoch-persistent sample cache
# ---------------------------------------------------------------------------


class TestSampleCache:
    def test_epoch_two_serves_from_cache(self):
        """Epoch 2 must not decode at all when the budget holds the set."""
        ds = CountingDataset(length=12, newsize=(16, 16), seed=0)
        cache = SampleCache(budget_bytes=64 * 2**20)
        loader = DataLoader(ds, batch_size=4, shuffle=True, cache=cache)
        list(loader.epoch_batches(0))
        assert ds.decodes == 12
        list(loader.epoch_batches(1))  # reshuffled order, same sample set
        assert ds.decodes == 12, "epoch 2 decoded despite a warm cache"
        assert cache.hits == 12 and cache.misses == 12

    def test_budget_is_respected_and_degrades_gracefully(self):
        """A budget smaller than the set caches only what fits — correct
        batches either way, bounded memory, partial decode on epoch 2."""
        ds = CountingDataset(length=8, newsize=(16, 16), seed=0)
        item_bytes = SampleCache._nbytes(ds[0])
        ds.decodes = 0
        cache = SampleCache(budget_bytes=3 * item_bytes)
        loader = DataLoader(ds, batch_size=4, cache=cache)
        b0 = list(loader.epoch_batches(0))
        assert cache.used_bytes <= cache.budget_bytes
        assert len(cache) == 3
        assert ds.decodes == 8
        # cached items must OWN their data: a row view would pin the whole
        # decoded parent batch, blowing the budget by the back door
        for it in cache._items.values():
            assert it["image"].base is None and it["mask"].base is None
        b1 = list(loader.epoch_batches(0))
        assert ds.decodes == 8 + 5  # only the 5 uncached re-decode
        for a, b in zip(b0, b1):
            np.testing.assert_array_equal(a["image"], b["image"])
            np.testing.assert_array_equal(a["mask"], b["mask"])

    def test_trainer_epochs_decode_once(self, tmp_path):
        """End to end: a 2-epoch Trainer run decodes each sample exactly
        once (train + val share the cache; epoch-2 train AND per-epoch
        val re-reads all hit)."""
        ds = CountingDataset(length=32, newsize=(W, H), seed=42)
        cfg = _config(tmp_path, host_cache_mb=256)
        Trainer(cfg, dataset=ds).train()
        assert ds.decodes == 32


# ---------------------------------------------------------------------------
# Overlap, demonstrated through the step-timeline tracer
# ---------------------------------------------------------------------------


def test_placement_overlaps_consumption(tmp_path):
    """Placement of batch N+1 begins BEFORE batch N's results are consumed:
    the scheduler test pins this deterministically — a depth-2 pipeline
    over a deliberately slow consumer must show the h2d span of item 1
    opening inside the consumer's dispatch span of item 0."""
    tracer = StepTimeline(str(tmp_path / "timeline.jsonl"))
    batches = [{"image": np.zeros((4, 8, 8, 3), np.float32)} for _ in range(6)]

    def place(kind, payload):
        time.sleep(0.01)  # a nonzero transfer, so spans have width
        return payload

    pipe = pipelined_placement(
        stacked_work(iter(batches), 1, 4), place, depth=2, tracer=tracer
    )
    for i, ((kind, payload), placed) in enumerate(pipe):
        with tracer.span("dispatch", step=i):
            time.sleep(0.05)  # the "executing scan" the H2D should hide under
    tracer.flush()

    events = load_events(str(tmp_path / "timeline.jsonl"))
    h2d = {e["seq"]: e for e in events if e["phase"] == "h2d"}
    dispatch = {e["step"]: e for e in events if e["phase"] == "dispatch"}
    assert len(h2d) == 6 and len(dispatch) == 6
    overlapped = [
        n for n in range(5) if h2d[n + 1]["t0"] < dispatch[n]["t1"]
    ]
    assert overlapped, (
        "no h2d(N+1) span opened before dispatch(N) closed — placement is "
        "not running ahead of consumption"
    )
    # and in steady state it should overlap nearly every step
    assert len(overlapped) >= 3, overlapped


def test_depth_zero_is_inline(tmp_path):
    """The synchronous baseline: depth 0 must place on the consumer thread,
    strictly between consumptions (no overlap), preserving the closing()
    contract."""
    import contextlib
    import threading

    placed_on = []

    def place(kind, payload):
        placed_on.append(threading.current_thread().name)
        return payload

    batches = [{"image": np.zeros((2, 4, 4, 3), np.float32)} for _ in range(3)]
    pipe = pipelined_placement(stacked_work(iter(batches), 1, 2), place, depth=0)
    with contextlib.closing(pipe):
        out = list(pipe)
    assert len(out) == 3
    assert set(placed_on) == {threading.current_thread().name}


def test_trainer_writes_timeline_jsonl(tmp_path):
    """--trace-timeline end to end: the JSONL lands, carries every pipeline
    phase, and summarize_timeline reads it back."""
    path = tmp_path / "timeline.jsonl"
    cfg = _config(tmp_path, timeline_path=str(path), prefetch_batches=2)
    Trainer(cfg).train()
    assert path.exists()
    phases = {e["phase"] for e in map(json.loads, open(path)) if e}
    assert {"decode", "h2d", "dispatch", "readback"} <= phases
    summary = summarize_timeline(str(path))
    for phase in ("decode", "h2d", "dispatch", "readback"):
        assert summary[phase]["count"] > 0
        assert summary[phase]["total_ms"] >= 0.0
    # 2 epochs x 3 steps: every step dispatched under a span
    assert summary["dispatch"]["count"] == 6


# ---------------------------------------------------------------------------
# One batch followed through the feed: fetch, slot wait, h2d to ready, the
# loop's wait, all under one (epoch, seq)
# ---------------------------------------------------------------------------

FEED_THREADS = ("dpt-prefetch", "dpt-prefetch-ready")


def _feed_threads():
    import threading

    return [t.name for t in threading.enumerate() if t.name in FEED_THREADS]


def _wait_feed_threads_gone(timeout=5.0):
    deadline = time.monotonic() + timeout
    while _feed_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    return _feed_threads()


def _drive_feed(tracer, n_batches=5, epochs=1, produce_s=0.0, consume_s=0.0,
                depth=2):
    """The feed as the trainer composes it, over ``epochs`` epochs of
    ``n_batches``: the loader takes ``produce_s`` a batch, the step
    ``consume_s``; placement is a real ``device_put``."""
    import jax

    def batches():
        for _ in range(n_batches):
            time.sleep(produce_s)
            yield {"image": np.zeros((4, 8, 8, 3), np.float32),
                   "mask": np.zeros((4, 8, 8), np.int32)}

    for epoch in range(epochs):
        pipe = pipelined_placement(
            stacked_work(batches(), 1, 4),
            lambda kind, payload: jax.device_put(payload),
            depth=depth, tracer=tracer, epoch=epoch,
        )
        for seq, ((_kind, _payload), _placed) in enumerate(pipe):
            with tracer.span("dispatch", epoch=epoch, seq=seq):
                time.sleep(consume_s)
    assert _wait_feed_threads_gone() == []


def _by_batch(events):
    """{(epoch, seq): {phase: event}} of the spans that name a batch (the
    epoch's closing ``end`` spans left out)."""
    out = {}
    for e in events:
        if "seq" in e and not e.get("end"):
            out.setdefault((e["epoch"], e["seq"]), {})[e["phase"]] = e
    return out


def _dur(e):
    return e["t1"] - e["t0"]


@pytest.mark.parametrize("depth", [2, 0])
def test_every_span_of_a_batch_carries_its_epoch_and_seq(depth):
    """fetch -> h2d -> feed_wait -> dispatch in that order under one
    (epoch, seq); a second epoch restarts seq at 0 with epoch + 1; an
    epoch closes with one fetch and one feed_wait tagged end."""
    tracer = StepTimeline(enabled=True)
    _drive_feed(tracer, n_batches=4, epochs=2, consume_s=0.005, depth=depth)
    events = tracer.events()
    batches = _by_batch(events)
    assert sorted(batches) == [(e, s) for e in (0, 1) for s in range(4)]
    for key, spans in batches.items():
        assert {"fetch", "h2d", "feed_wait", "dispatch"} <= set(spans), key
        order = [spans[p] for p in ("fetch", "h2d", "feed_wait", "dispatch")]
        assert all(a["t1"] <= b["t1"] for a, b in zip(order, order[1:])), key
        assert spans["fetch"]["t1"] <= spans["h2d"]["t0"]
        assert spans["feed_wait"]["t1"] <= spans["dispatch"]["t0"]
        # two arrays a batch: 4*8*8*3 float32 + 4*8*8 int32
        assert spans["fetch"]["bytes"] == spans["h2d"]["bytes"] == 4096
    ends = [(e["phase"], e["epoch"], e["seq"]) for e in events if e.get("end")]
    # placed inline (depth 0) the loop never waits, for the end either
    assert sorted(ends) == [(phase, epoch, 4) for phase in
                            (("feed_wait", "fetch") if depth else ("fetch",))
                            for epoch in (0, 1)]
    first = min(e["t0"] for e in events if e.get("epoch") == 1)
    assert first >= max(e["t1"] for e in events if e.get("epoch") == 0
                        and e["phase"] != "h2d_ready")


def test_slow_consumer_shows_slot_wait_and_no_feed_wait():
    """The feed runs ahead: the worker waits for a slot, the loop for
    nothing (past the first batch, which nothing can have prefetched)."""
    tracer = StepTimeline(enabled=True)
    _drive_feed(tracer, n_batches=6, consume_s=0.05)
    slot = tracer.events("slot_wait")
    assert slot and sum(map(_dur, slot)) > 0.05
    assert all(e["epoch"] == 0 and 2 <= e["seq"] <= 5 for e in slot)
    waits = [e for e in tracer.events("feed_wait") if e["seq"] >= 1]
    assert len(waits) == 6  # five batches and the end
    assert sum(map(_dur, waits)) < 0.02


def test_slow_producer_shows_feed_wait_and_no_slot_wait():
    tracer = StepTimeline(enabled=True)
    _drive_feed(tracer, n_batches=6, produce_s=0.05)
    assert tracer.events("slot_wait") == []
    waits = [e for e in tracer.events("feed_wait") if not e.get("end")]
    assert len(waits) == 6
    assert all(_dur(e) > 0.03 for e in waits)
    fetch = [e for e in tracer.events("fetch") if not e.get("end")]
    assert all(_dur(e) > 0.04 for e in fetch)


def test_h2d_ready_closes_after_its_h2d_off_the_worker_thread(monkeypatch):
    """h2d_ready runs from the end of the batch's h2d until its arrays are
    ready, on the watcher: the worker never waits for a copy."""
    import threading

    import jax

    blocked_on = []
    real = jax.block_until_ready

    def block(x):
        blocked_on.append(threading.current_thread().name)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", block)
    tracer = StepTimeline(enabled=True)
    _drive_feed(tracer, n_batches=4)
    batches = _by_batch(tracer.events())
    for key, spans in batches.items():
        ready, h2d = spans["h2d_ready"], spans["h2d"]
        assert ready["t0"] >= h2d["t1"] and ready["t1"] >= ready["t0"], key
        assert ready["t0"] - h2d["t1"] < 0.005, key
        assert ready["bytes"] == h2d["bytes"]
    assert blocked_on == ["dpt-prefetch-ready"] * 4


def test_disabled_timeline_stores_nothing_and_starts_no_watcher(monkeypatch):
    """With the timeline disabled: no span stored, no watcher thread, no
    TraceAnnotation opened, and nobody waits for a copy."""
    import jax

    seen = {"threads": set(), "annotations": 0, "blocked": 0}

    class Annotation:
        def __init__(self, *a, **kw):
            seen["annotations"] += 1

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: seen.__setitem__("blocked", seen["blocked"] + 1))
    tracer = StepTimeline(None)
    assert not tracer.enabled

    def batches():
        for _ in range(4):
            seen["threads"].update(_feed_threads())
            yield {"image": np.zeros((2, 4, 4, 3), np.float32)}

    pipe = pipelined_placement(stacked_work(batches(), 1, 2),
                               lambda kind, payload: jax.device_put(payload),
                               depth=2, tracer=tracer, epoch=0)
    assert len(list(pipe)) == 4
    assert tracer.events() == []
    assert seen == {"threads": {"dpt-prefetch"}, "annotations": 0, "blocked": 0}
    assert _wait_feed_threads_gone() == []


def test_closing_the_feed_mid_epoch_stops_worker_and_watcher():
    import contextlib

    tracer = StepTimeline(enabled=True)
    batches = ({"image": np.zeros((2, 4, 4, 3), np.float32)} for _ in range(50))
    pipe = pipelined_placement(stacked_work(batches, 1, 2),
                               lambda kind, payload: payload,
                               depth=2, tracer=tracer, epoch=3)
    with contextlib.closing(pipe):
        next(pipe)
        assert sorted(_feed_threads()) == sorted(FEED_THREADS)
    assert _wait_feed_threads_gone() == []
    assert len(tracer.events("h2d")) < 10


def test_a_program_started_profile_shares_the_timelines_clock(tmp_path):
    """profile_started writes dpt_sync into the profile with the reading
    that the timeline's clock_sync event holds, and until profile_stopped
    every span is also a dpt_<phase> annotation there: shifted by the
    sync, the timeline's span and the profile's annotation coincide."""
    import glob

    import jax

    tracer = StepTimeline(enabled=True)
    with tracer.span("dispatch", step=0):
        pass  # before the profile: no annotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        tracer.profile_started()
        with tracer.span("fetch", epoch=2, seq=5):
            time.sleep(0.01)
        tracer.profile_stopped()
        with tracer.span("h2d", epoch=2, seq=5):
            pass  # after it: none either
    finally:
        jax.profiler.stop_trace()
    (sync,) = tracer.events("clock_sync")
    (fetch,) = tracer.events("fetch")
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dpt_"):
                    found[e.name] = (e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                     dict(e.stats))
    assert set(found) == {"dpt_sync", "dpt_fetch"}
    at, _, stats = found["dpt_sync"]
    assert stats["pc_ns"] == sync["pc_ns"]
    shift = at - sync["pc_ns"] * 1e-9
    start, dur, stats = found["dpt_fetch"]
    assert (stats["epoch"], stats["seq"]) == (2, 5)
    assert start == pytest.approx(fetch["t0"] + shift, abs=2e-3)
    assert dur == pytest.approx(fetch["t1"] - fetch["t0"], abs=2e-3)


def test_summary_reports_every_phase_it_saw():
    """One phase list in the package (obs/trace_hub.py); a phase outside
    it is reported after the listed ones, not dropped."""
    from distributedpytorch_tpu.obs import trace_hub
    from distributedpytorch_tpu.utils import trace

    assert trace.PHASES is trace_hub.PHASES
    assert trace.PHASES == ("decode", "fetch", "slot_wait", "stack", "h2d",
                            "h2d_ready", "feed_wait", "dispatch", "readback")
    tracer = StepTimeline(enabled=True)
    tracer.record("eval", 1.0, 1.5)
    tracer.record("fetch", 2.0, 2.25)
    summary = tracer.summary()
    assert list(summary) == [*trace.PHASES, "eval"]
    assert summary["fetch"]["total_ms"] == 250.0 and summary["h2d"] is None
    assert summary["eval"] == {"count": 1, "total_ms": 500.0, "mean_ms": 500.0}
    assert trace.summarize_events(tracer.events()) == summary


def test_trainer_dispatch_spans_name_their_batch(tmp_path):
    """Trainer._run end to end: each dispatch carries the (epoch, seq) of
    the batch the feed handed over, beside the global step."""
    path = tmp_path / "timeline.jsonl"
    cfg = _config(tmp_path, timeline_path=str(path), prefetch_batches=2)
    Trainer(cfg).train()
    events = load_events(str(path))
    dispatch = [e for e in events if e["phase"] == "dispatch"]
    assert [(e["epoch"], e["seq"], e["step"]) for e in dispatch] == [
        (0, 0, 1), (0, 1, 2), (0, 2, 3), (1, 0, 4), (1, 1, 5), (1, 2, 6)]
    batches = _by_batch(e for e in events if "epoch" in e)
    assert sorted(batches) == [(e, s) for e in (0, 1) for s in range(3)]
    for spans in batches.values():
        assert {"fetch", "h2d", "h2d_ready", "feed_wait", "dispatch"} <= set(spans)


# ---------------------------------------------------------------------------
# Non-blocking checkpoints
# ---------------------------------------------------------------------------


class TestAsyncCheckpoint:
    def test_async_save_roundtrip(self, tmp_path):
        from distributedpytorch_tpu.checkpoint import (
            load_checkpoint,
            save_checkpoint_async,
        )

        params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
        path = str(tmp_path / "a.ckpt")
        fut = save_checkpoint_async(path, params, step=7, epoch=3)
        assert fut.result(timeout=30) == path
        restored = load_checkpoint(path, params)
        np.testing.assert_array_equal(restored["params"]["w"], params["w"])
        assert restored["step"] == 7 and restored["epoch"] == 3

    def test_queued_saves_apply_in_order(self, tmp_path):
        """Two async saves of the SAME path: the file must end at the
        newest snapshot (one writer thread, submission order)."""
        from distributedpytorch_tpu.checkpoint import (
            load_checkpoint,
            save_checkpoint_async,
        )

        path = str(tmp_path / "b.ckpt")
        params = {"w": np.zeros((4,), np.float32)}
        f1 = save_checkpoint_async(path, params, epoch=1)
        f2 = save_checkpoint_async(
            path, {"w": np.ones((4,), np.float32)}, epoch=2
        )
        f1.result(timeout=30)
        f2.result(timeout=30)
        restored = load_checkpoint(path, params)
        assert restored["epoch"] == 2
        np.testing.assert_array_equal(restored["params"]["w"], np.ones((4,)))

    def test_mid_run_save_is_durable_after_fit(self, tmp_path):
        """A save issued mid-epoch (signal stop) must be complete and
        loadable by the time train() returns — the drain in train()'s
        finally is what guarantees a restart never reads a torn file."""
        import signal

        from distributedpytorch_tpu.checkpoint import load_checkpoint

        cfg = _config(tmp_path, epochs=50)
        trainer = Trainer(cfg)
        assert cfg.async_checkpoint  # the default under test
        orig = trainer._record
        fired = {}

        def record_then_signal(*a, **kw):
            orig(*a, **kw)
            if not fired:
                fired["x"] = True
                signal.raise_signal(signal.SIGTERM)

        trainer._record = record_then_signal
        trainer.train()
        assert not trainer._ckpt_futures  # drained, not abandoned
        path = tmp_path / "checkpoints" / "singleGPU.ckpt"
        assert path.exists()
        restored = load_checkpoint(
            str(path), trainer.state.params, trainer.state.opt_state
        )
        assert restored["epoch"] == 0  # interrupted epoch will be redone
        resumed = Trainer(_config(tmp_path, epochs=50, checkpoint_name="singleGPU"))
        assert resumed.start_epoch == 0

    def test_write_failure_surfaces(self, tmp_path, monkeypatch):
        """A failed background write must raise out of train(), not pass
        silently (the save "succeeded" from the step loop's view)."""
        import distributedpytorch_tpu.checkpoint as ckpt_mod
        import distributedpytorch_tpu.train.loop as loop_mod

        def bad_write(path, payload, keep=1):
            raise OSError("disk full")

        monkeypatch.setattr(ckpt_mod, "_write_payload", bad_write)
        # loop.py binds save_checkpoint_async at import; the patched
        # _write_payload is read through the module at call time, so the
        # async path picks it up unmodified
        cfg = _config(tmp_path, epochs=1)
        with pytest.raises(OSError, match="disk full"):
            loop_mod.Trainer(cfg).train()

    def test_last_save_failure_surfaces_at_final_drain(self, tmp_path,
                                                       monkeypatch):
        """A write failure on the FINAL save has no 'next save' to surface
        it — the drain in train()'s finally is the only boundary left and
        must raise it as a hard error (earlier saves all succeed, so this
        pins the final-drain path specifically, not the surface-at-next-
        save path)."""
        import distributedpytorch_tpu.checkpoint as ckpt_mod

        real_write = ckpt_mod._write_payload
        calls = {"n": 0}

        def fail_final_only(path, payload, keep=1):
            calls["n"] += 1
            if payload["epoch"] >= 2:  # only the end-of-run save fails
                raise OSError("disk full on the final save")
            return real_write(path, payload, keep=keep)

        monkeypatch.setattr(ckpt_mod, "_write_payload", fail_final_only)
        cfg = _config(tmp_path, epochs=2, checkpoint_every_epochs=0)
        with pytest.raises(OSError, match="final save"):
            Trainer(cfg).train()
        assert calls["n"] >= 1

    def test_sync_mode_still_works(self, tmp_path):
        from distributedpytorch_tpu.checkpoint import load_checkpoint

        cfg = _config(tmp_path, epochs=1, async_checkpoint=False)
        trainer = Trainer(cfg)
        trainer.train()
        restored = load_checkpoint(
            str(tmp_path / "checkpoints" / "singleGPU.ckpt"),
            trainer.state.params,
        )
        assert restored["epoch"] == 1
