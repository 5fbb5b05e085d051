"""Fault-injection harness + step-level failure policies + resilient
checkpointing (the robustness tentpole, docs/RELIABILITY.md).

Every injection site (decode, placement, nan_loss, ckpt_write, sigterm)
gets a test proving its configured recovery policy actually recovers on
the CPU mesh — no chip required — and the recovery is DETERMINISTIC:
where the policy promises transparency (retries, rollback), the loss
curve must be bit-identical to an uninjected run.
"""

import logging
import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

from distributedpytorch_tpu.checkpoint import (
    CheckpointCorruptError,
    load_checkpoint,
    prune_retained,
    retained_checkpoints,
    save_checkpoint,
    save_checkpoint_async,
    verify_checkpoint,
)
from distributedpytorch_tpu.config import TrainConfig
from distributedpytorch_tpu.train import Trainer, fit_with_restarts
from distributedpytorch_tpu.utils import faults
from distributedpytorch_tpu.utils.faults import (
    FaultSpec,
    InjectedTransientError,
    NonFiniteLossError,
    StepWatchdog,
    parse_fault_spec,
)

H, W = 32, 48
WIDTHS = (8, 16)


@pytest.fixture(autouse=True)
def _fresh_injector():
    """install() is deliberately idempotent per spec list (restart
    recovery) — tests re-using a spec string would otherwise inherit a
    spent injector."""
    faults.reset()
    yield
    faults.reset()


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
        metric_every_steps=1,
        num_workers=0,
        retry_backoff_s=0.01,  # keep injected-retry tests fast
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


def _losses(tmp_path):
    df = pd.read_pickle(tmp_path / "loss" / "singleGPU" / "train_loss.pkl")
    return df["Loss"].to_numpy()


# ---------------------------------------------------------------------------
# Spec parsing + injector semantics
# ---------------------------------------------------------------------------


class TestSpecs:
    def test_parse_full(self):
        assert parse_fault_spec("decode:1:5:3") == FaultSpec(
            "decode", epoch=1, step=5, count=3
        )

    def test_parse_wildcards(self):
        assert parse_fault_spec("nan_loss:*:7") == FaultSpec(
            "nan_loss", epoch=None, step=7, count=1
        )
        assert parse_fault_spec("sigterm") == FaultSpec(
            "sigterm", epoch=None, step=None, count=1
        )
        assert parse_fault_spec("decode:0:1:*").count == -1

    def test_parse_rejects_unknown_site(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            parse_fault_spec("frobnicate:1:1")

    def test_parse_rejects_zero_count(self):
        with pytest.raises(ValueError, match="count"):
            parse_fault_spec("decode:1:1:0")

    def test_fire_matches_and_decrements(self):
        inj = faults.FaultInjector(("decode:1:5:2",))
        assert not inj.fire("decode", epoch=0, step=5)  # wrong epoch
        assert not inj.fire("decode", epoch=1, step=4)  # wrong step
        assert not inj.fire("placement", epoch=1, step=5)  # wrong site
        assert inj.fire("decode", epoch=1, step=5)
        assert inj.fire("decode", epoch=1, step=5)
        assert not inj.fire("decode", epoch=1, step=5)  # count spent
        assert inj.fired == {"decode": 2}

    def test_pinned_coordinate_never_matches_unknown(self):
        """A site that cannot supply its epoch must not trip an
        epoch-pinned spec (conservative, not wildcard)."""
        inj = faults.FaultInjector(("ckpt_write:3",))
        assert not inj.fire("ckpt_write", epoch=None)
        assert inj.fire("ckpt_write", epoch=3)

    def test_install_is_idempotent_per_spec_list(self):
        inj = faults.install(("nan_loss:*:*:1",))
        assert faults.fire("nan_loss", epoch=0, step=1)
        assert not faults.fire("nan_loss", epoch=0, step=2)
        # same specs again (a fit_with_restarts rebuild): counts survive
        assert faults.install(("nan_loss:*:*:1",)) is inj
        assert not faults.fire("nan_loss", epoch=0, step=3)
        # different specs re-arm; empty disarms
        assert faults.install(()) is not inj
        assert not faults.fire("nan_loss", epoch=0, step=1)

    def test_parse_rank_pinned_spec(self):
        assert parse_fault_spec("rank_kill@1:1:6") == FaultSpec(
            "rank_kill", epoch=1, step=6, count=1, rank=1
        )
        assert parse_fault_spec("rank_hang@0:*:2:*") == FaultSpec(
            "rank_hang", epoch=None, step=2, count=-1, rank=0
        )
        # unpinned: every rank
        assert parse_fault_spec("rank_kill:1:6").rank is None

    def test_parse_rejects_bad_rank(self):
        with pytest.raises(ValueError, match="rank"):
            parse_fault_spec("rank_kill@x:1:1")
        with pytest.raises(ValueError, match="rank"):
            parse_fault_spec("rank_kill@-2:1:1")
        with pytest.raises(ValueError, match="unknown fault site"):
            parse_fault_spec("frobnicate@1:1:1")

    def test_rank_pinned_spec_fires_only_on_its_rank(self):
        """Single-process test env: jax.process_index() == 0 — an @0
        spec fires here, an @1 spec never does (how the multi-process
        chaos tests kill exactly one peer of a live mesh)."""
        other = faults.FaultInjector(("nan_loss@1:*:*:*",))
        assert not other.fire("nan_loss", epoch=0, step=1)
        assert other.fired == {}
        mine = faults.FaultInjector(("nan_loss@0:*:*:*",))
        assert mine.fire("nan_loss", epoch=0, step=1)


# ---------------------------------------------------------------------------
# decode / placement: transient faults recover through bounded backoff
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("site", ["decode", "placement"])
def test_transient_fault_recovers_bit_identically(tmp_path, site):
    """An injected transient at either host-pipeline site, with retries
    armed, must be INVISIBLE: same loss curve as the clean run."""
    Trainer(_config(tmp_path / "clean")).train()
    faults.reset()
    cfg = _config(
        tmp_path / "faulty",
        inject_faults=(f"{site}:0:1",),
        data_retries=2,
    )
    Trainer(cfg).train()
    assert faults.active().fired.get(site) == 1, "fault never fired"
    np.testing.assert_array_equal(
        _losses(tmp_path / "clean"), _losses(tmp_path / "faulty")
    )


@pytest.mark.parametrize("site", ["decode", "placement"])
def test_transient_fault_without_retries_surfaces(tmp_path, site):
    cfg = _config(
        tmp_path, inject_faults=(f"{site}:0:1",), data_retries=0, epochs=1
    )
    with pytest.raises(InjectedTransientError):
        Trainer(cfg).train()


def test_channel_shaped_runtime_errors_are_transient():
    """jaxlib surfaces a flapping runtime channel as XlaRuntimeError (a
    RuntimeError), not an OSError — the retry classifier must catch it,
    while deterministic compile failures (INTERNAL:) stay fatal."""
    assert faults.is_transient(RuntimeError("UNAVAILABLE: socket closed"))
    assert faults.is_transient(RuntimeError("DEADLINE_EXCEEDED: rpc"))
    assert faults.is_transient(OSError("disk hiccup"))
    assert not faults.is_transient(RuntimeError("INTERNAL: Mosaic failed"))
    assert not faults.is_transient(ValueError("bad config"))


def test_call_with_retries_covers_channel_runtime_error():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE: peer went away")
        return "ok"

    out = faults.call_with_retries(
        flaky, site="placement", retries=3, backoff_s=0.001
    )
    assert out == "ok" and calls["n"] == 3
    with pytest.raises(ValueError):  # non-transient: no retry
        faults.call_with_retries(
            lambda: (_ for _ in ()).throw(ValueError("x")),
            site="placement", retries=3, backoff_s=0.001,
        )


def test_retry_budget_is_bounded(tmp_path):
    """A PERSISTENT fault (count *) must exhaust the budget and surface,
    not retry forever."""
    cfg = _config(
        tmp_path, inject_faults=("decode:*:*:*",), data_retries=2, epochs=1
    )
    with pytest.raises(InjectedTransientError):
        Trainer(cfg).train()
    # initial attempt + exactly data_retries retries
    assert faults.active().fired["decode"] == 3


# ---------------------------------------------------------------------------
# nan_loss: the three policies
# ---------------------------------------------------------------------------


def test_nan_loss_abort_raises(tmp_path):
    cfg = _config(tmp_path, inject_faults=("nan_loss:0:2",), epochs=1)
    with pytest.raises(NonFiniteLossError, match="non-finite train loss"):
        Trainer(cfg).train()


def test_nan_loss_skip_discards_update_and_continues(tmp_path):
    cfg = _config(
        tmp_path,
        inject_faults=("nan_loss:0:2",),
        nonfinite_policy="skip",
    )
    result = Trainer(cfg).train()
    assert result["skipped_steps"] == 1
    # 3 batches/epoch x 2 epochs, one update discarded
    assert result["steps"] == 2 * 3 - 1
    assert np.isfinite(result["val_loss"])
    assert np.all(np.isfinite(_losses(tmp_path)))


def test_nan_loss_rollback_resumes_bit_identically(tmp_path):
    """Policy 'rollback': reload the last epoch checkpoint, redo the
    poisoned epoch — and because data order and step math are seeded, the
    recovered run's loss curve must equal the clean run's exactly."""
    Trainer(_config(tmp_path / "clean", epochs=3)).train()
    faults.reset()
    cfg = _config(
        tmp_path / "faulty",
        epochs=3,
        inject_faults=("nan_loss:1:5",),  # epoch 2 of 3, after a checkpoint
        nonfinite_policy="rollback",
    )
    result = Trainer(cfg).train()
    assert result["rollbacks"] == 1
    assert result["steps"] == 9
    np.testing.assert_array_equal(
        _losses(tmp_path / "clean"), _losses(tmp_path / "faulty")
    )
    # val curve too: one row per epoch, no NaN epoch left behind
    clean = pd.read_pickle(tmp_path / "clean" / "loss" / "singleGPU" / "val_loss.pkl")
    faulty = pd.read_pickle(tmp_path / "faulty" / "loss" / "singleGPU" / "val_loss.pkl")
    np.testing.assert_array_equal(
        clean["Loss"].to_numpy(), faulty["Loss"].to_numpy()
    )


def test_nan_loss_rollback_budget_exhausts_to_abort(tmp_path):
    """A persistently-NaN run must stop rolling back and abort once the
    budget is spent."""
    cfg = _config(
        tmp_path,
        epochs=3,
        inject_faults=("nan_loss:1:*:*",),  # EVERY step of epoch 1
        nonfinite_policy="rollback",
        rollback_retries=2,
    )
    trainer = Trainer(cfg)
    with pytest.raises(NonFiniteLossError):
        trainer.train()
    assert trainer._rollback_budget == 0


def test_nan_loss_rollback_without_checkpoint_aborts(tmp_path):
    """NaN before ANY checkpoint exists: nothing to roll back to."""
    cfg = _config(
        tmp_path,
        inject_faults=("nan_loss:0:1",),
        nonfinite_policy="rollback",
    )
    with pytest.raises(NonFiniteLossError):
        Trainer(cfg).train()


def test_nan_detected_between_metric_rows(tmp_path):
    """Default metric cadence (every=10) with a 3-step epoch: the NaN
    never lands in a due row, so row-drain detection cannot see it — the
    state_dict flush of the epoch-end checkpoint save must catch it
    instead (a poisoned state must never be checkpointed as healthy)."""
    cfg = _config(
        tmp_path, metric_every_steps=10,
        inject_faults=("nan_loss:0:2",), epochs=1,
    )
    with pytest.raises(NonFiniteLossError):
        Trainer(cfg).train()
    # nothing intact was ever written: the save that would have
    # persisted the poisoned state is the one that detected it
    assert not os.path.exists(tmp_path / "checkpoints" / "singleGPU.ckpt")


# ---------------------------------------------------------------------------
# sigterm: simulated preemption drill
# ---------------------------------------------------------------------------


def test_sigterm_injection_checkpoints_and_stops(tmp_path):
    """The simulated-preemption site delivers a REAL SIGTERM through the
    installed handler: the run stops at the epoch boundary with a
    resumable checkpoint — the production preemption path, as a drill."""
    import signal as signal_mod

    cfg = _config(tmp_path, epochs=50, inject_faults=("sigterm:0:2",))
    result = Trainer(cfg).train()
    assert result["steps"] == 2  # stopped right after the injected step
    assert os.path.exists(tmp_path / "checkpoints" / "singleGPU.ckpt")
    resumed = Trainer(_config(tmp_path, epochs=50, checkpoint_name="singleGPU"))
    assert resumed.start_epoch == 0  # interrupted epoch will be redone
    assert signal_mod.getsignal(signal_mod.SIGTERM) == signal_mod.SIG_DFL


# ---------------------------------------------------------------------------
# ckpt_write: torn write + integrity fallback under fit_with_restarts
# ---------------------------------------------------------------------------


def test_mid_write_crash_falls_back_to_intact_checkpoint(tmp_path):
    """The acceptance drill: an injected mid-write crash leaves a TORN
    <tag>.ckpt; fit_with_restarts must restart, fail the torn file's
    integrity check, fall back to the retained intact <tag>.ckpt.1, and
    finish the configured epochs."""
    cfg = _config(
        tmp_path,
        epochs=3,
        inject_faults=("ckpt_write:2",),  # the end-of-epoch-2 save
        async_checkpoint=False,  # deterministic crash point
        keep_checkpoints=2,
    )
    result = fit_with_restarts(cfg, max_restarts=1)
    assert faults.active().fired.get("ckpt_write") == 1
    assert result["steps"] == 9  # all 3 epochs completed despite the crash
    assert np.isfinite(result["val_loss"])
    # the final save overwrote the torn file; the whole chain is intact now
    for path in retained_checkpoints(
        str(tmp_path / "checkpoints" / "singleGPU.ckpt")
    ):
        assert verify_checkpoint(path), path
    # metric history: restart resumed from epoch 1, so the pickles hold
    # one val row per completed epoch with monotonic time
    val_df = pd.read_pickle(tmp_path / "loss" / "singleGPU" / "val_loss.pkl")
    assert len(val_df) == 3
    assert val_df["Time"].is_monotonic_increasing


def test_torn_write_leaves_corrupt_file_detected(tmp_path):
    """The injected torn write itself: file fails verification, restore
    falls back."""
    cfg = _config(
        tmp_path,
        epochs=2,
        inject_faults=("ckpt_write:2",),
        async_checkpoint=False,
        keep_checkpoints=2,
    )
    with pytest.raises(faults.InjectedFault):
        Trainer(cfg).train()
    ckpt = str(tmp_path / "checkpoints" / "singleGPU.ckpt")
    assert not verify_checkpoint(ckpt)  # torn
    assert verify_checkpoint(f"{ckpt}.1")  # previous epoch intact
    trainer = Trainer(_config(tmp_path, epochs=2, checkpoint_name="singleGPU"))
    assert trainer.start_epoch == 1  # restored from the fallback


# ---------------------------------------------------------------------------
# checkpoint integrity + retention units
# ---------------------------------------------------------------------------


class TestCheckpointIntegrity:
    PARAMS = {"w": np.arange(12, dtype=np.float32).reshape(3, 4)}

    def test_footer_roundtrip_and_tamper_detection(self, tmp_path):
        path = str(tmp_path / "a.ckpt")
        save_checkpoint(path, self.PARAMS, epoch=1)
        assert verify_checkpoint(path)
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0xFF  # flip one payload byte
        with open(path, "wb") as f:
            f.write(blob)
        assert not verify_checkpoint(path)
        with pytest.raises(CheckpointCorruptError):
            load_checkpoint(path, self.PARAMS, fallback=False)

    def test_truncated_file_is_corrupt(self, tmp_path):
        path = str(tmp_path / "t.ckpt")
        save_checkpoint(path, self.PARAMS)
        blob = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(blob[: len(blob) // 3])
        assert not verify_checkpoint(path)

    def test_restore_falls_back_to_newest_intact(self, tmp_path):
        path = str(tmp_path / "c.ckpt")
        save_checkpoint(path, self.PARAMS, epoch=1, keep=3)
        save_checkpoint(path, self.PARAMS, epoch=2, keep=3)
        save_checkpoint(path, self.PARAMS, epoch=3, keep=3)
        assert retained_checkpoints(path) == [path, f"{path}.1", f"{path}.2"]
        with open(path, "wb") as f:
            f.write(b"torn garbage")
        restored = load_checkpoint(path, self.PARAMS)
        assert restored["epoch"] == 2  # newest intact (path.1)

    def test_all_candidates_corrupt_raises(self, tmp_path):
        path = str(tmp_path / "d.ckpt")
        save_checkpoint(path, self.PARAMS, epoch=1, keep=2)
        save_checkpoint(path, self.PARAMS, epoch=2, keep=2)
        for cand in retained_checkpoints(path):
            with open(cand, "wb") as f:
                f.write(b"xx")
        with pytest.raises(CheckpointCorruptError, match="no intact"):
            load_checkpoint(path, self.PARAMS)

    def test_retention_rotates_and_prunes(self, tmp_path):
        path = str(tmp_path / "r.ckpt")
        for epoch in range(1, 5):
            save_checkpoint(path, self.PARAMS, epoch=epoch, keep=2)
        assert load_checkpoint(path, self.PARAMS)["epoch"] == 4
        assert load_checkpoint(f"{path}.1", self.PARAMS)["epoch"] == 3
        assert not os.path.exists(f"{path}.2")  # pruned at keep=2

    def test_trainer_keeps_retention_chain(self, tmp_path):
        cfg = _config(tmp_path, epochs=3)  # keep_checkpoints default 2
        Trainer(cfg).train()
        ckpt = str(tmp_path / "checkpoints" / "singleGPU.ckpt")
        chain = retained_checkpoints(ckpt)
        assert chain == [ckpt, f"{ckpt}.1"]
        assert all(verify_checkpoint(p) for p in chain)

    def test_legacy_footerless_checkpoint_still_loads(self, tmp_path):
        import flax.serialization

        path = str(tmp_path / "legacy.ckpt")
        payload = {
            "version": 1, "params": {"w": self.PARAMS["w"]},
            "opt_state": None, "scheduler": None, "step": 5, "epoch": 2,
            "records": None, "model_state": None, "train_meta": None,
        }
        with open(path, "wb") as f:  # pre-footer format: raw msgpack
            f.write(flax.serialization.msgpack_serialize(payload))
        restored = load_checkpoint(path, self.PARAMS)
        assert restored["epoch"] == 2
        np.testing.assert_array_equal(restored["params"]["w"], self.PARAMS["w"])


class TestRetentionPruneRace:
    """`--keep-checkpoints` prune vs an in-flight async save: the
    retention chain is shared mutable state between the writer thread
    and external pruning, guarded by checkpoint._RETENTION_LOCK."""

    PARAMS = {"w": np.arange(64, dtype=np.float32)}

    def test_prune_blocks_behind_in_flight_rotate(self, tmp_path):
        """Deterministic pin of the lock contract: while a writer holds
        the retention critical section (rotate → rename → prune), an
        external prune must WAIT — it can no longer delete the slot the
        writer is rotating the previous checkpoint into."""
        from distributedpytorch_tpu import checkpoint as ckpt

        path = str(tmp_path / "race.ckpt")
        assert ckpt._RETENTION_LOCK.acquire()
        done = threading.Event()

        def pruner():
            prune_retained(path, 1)
            done.set()

        t = threading.Thread(target=pruner, daemon=True)
        try:
            t.start()
            time.sleep(0.2)
            assert not done.is_set()  # blocked behind the writer
        finally:
            ckpt._RETENTION_LOCK.release()
        t.join(5.0)
        assert done.is_set()

    def test_prune_races_async_saves_without_losing_the_chain(self, tmp_path):
        """Hammer prune_retained(keep=1) against a stream of queued
        async saves (keep=2). Whatever the interleaving, the live slot
        must end intact with the NEWEST payload and load_checkpoint must
        succeed — without the lock, a prune landing between a save's
        rotate and its rename could delete the only intact copy while
        the live slot is mid-replacement."""
        path = str(tmp_path / "race.ckpt")
        save_checkpoint(path, self.PARAMS, epoch=0, keep=2)
        stop = threading.Event()
        errors = []

        def hammer():
            while not stop.is_set():
                try:
                    prune_retained(path, 1)
                except Exception as exc:  # noqa: BLE001 — the assertion
                    errors.append(exc)
                    return

        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        try:
            futures = [
                save_checkpoint_async(path, self.PARAMS, epoch=i, keep=2)
                for i in range(1, 21)
            ]
            for fut in futures:
                fut.result(timeout=60)
        finally:
            stop.set()
            t.join(5.0)
        assert not errors
        assert verify_checkpoint(path)
        assert load_checkpoint(path, self.PARAMS)["epoch"] == 20


# ---------------------------------------------------------------------------
# dispatch watchdog
# ---------------------------------------------------------------------------


class TestWatchdog:
    def test_pet_keeps_it_quiet(self):
        fired = []
        dog = StepWatchdog(0.15, lambda: fired.append(1))
        dog.start()
        try:
            for _ in range(6):
                dog.pet()
                time.sleep(0.05)
            assert not fired
        finally:
            dog.stop()

    def test_paused_never_fires(self):
        fired = []
        dog = StepWatchdog(0.05, lambda: fired.append(1))
        dog.start()
        try:
            time.sleep(0.3)  # never petted → paused → silent
            assert not fired
        finally:
            dog.stop()

    def test_fires_once_after_timeout(self):
        fired = []
        dog = StepWatchdog(0.05, lambda: fired.append(1))
        dog.start()
        try:
            dog.pet()
            time.sleep(0.4)
            assert fired == [1]  # once, then disarmed
        finally:
            dog.stop()

    def test_trainer_watchdog_dumps_spans_and_stops(self, tmp_path, caplog):
        """A slow step past --step-timeout in a STEADY-STATE epoch: the
        watchdog logs the per-phase timeline spans and the run
        checkpoints-and-stops via the existing stop agreement. (The slow
        step is placed in epoch 2 — the first executed epoch is untimed
        by design: it compiles every executable shape.)"""
        cfg = _config(
            tmp_path, epochs=50, step_timeout_s=0.3,
            timeline_path=str(tmp_path / "tl.jsonl"),
        )
        trainer = Trainer(cfg)
        orig_step = trainer.train_step
        calls = {"n": 0}

        def slow_step(state, batch):
            calls["n"] += 1
            if calls["n"] == 4:  # first batch of epoch 2 (3 batches/epoch)
                time.sleep(1.2)
            return orig_step(state, batch)

        trainer.train_step = slow_step
        with caplog.at_level(logging.ERROR):
            result = trainer.train()
        assert trainer._watchdog.fired
        assert result["steps"] < 9  # stopped at epoch 2's boundary
        assert any("dispatch watchdog" in r.message for r in caplog.records)
        assert any("timeline" in r.message for r in caplog.records)
        assert os.path.exists(tmp_path / "checkpoints" / "singleGPU.ckpt")
        resumed = Trainer(
            _config(tmp_path, epochs=50, checkpoint_name="singleGPU")
        )
        assert resumed.start_epoch == 1  # epoch 1 completed and saved

    def test_trainer_watchdog_silent_during_first_epoch(self, tmp_path):
        """A slow step in the FIRST executed epoch (where XLA compiles
        land) must NOT fire the watchdog — a steady-state-sized timeout
        would otherwise kill every cold start."""
        cfg = _config(tmp_path, epochs=2, step_timeout_s=1.5)
        trainer = Trainer(cfg)
        orig_step = trainer.train_step
        calls = {"n": 0}

        def slow_step(state, batch):
            calls["n"] += 1
            if calls["n"] == 1:  # the "compile" of the first step
                time.sleep(3.0)
            return orig_step(state, batch)

        trainer.train_step = slow_step
        result = trainer.train()
        assert not trainer._watchdog.fired
        assert result["steps"] == 2 * 3  # ran to completion

    def test_resumed_run_first_executed_epoch_is_untimed(self, tmp_path):
        """Explicit pin of the exemption's ANCHOR: 'first executed
        epoch' means start_epoch — NOT epoch index 0. A resumed run
        compiles every executable shape all over again in its first
        executed epoch (a fresh process has no warm executables), so a
        refactor that re-times it would kill every elastic relaunch and
        every --max-restarts recovery on a cold cache."""
        Trainer(_config(tmp_path, epochs=1)).train()
        cfg = _config(
            tmp_path, epochs=2, checkpoint_name="singleGPU",
            step_timeout_s=1.5,
        )
        trainer = Trainer(cfg)
        assert trainer.start_epoch == 1  # genuinely resumed
        orig_step = trainer.train_step
        calls = {"n": 0}

        def slow_step(state, batch):
            calls["n"] += 1
            if calls["n"] == 1:  # the resumed epoch's "compile"
                time.sleep(3.0)
            return orig_step(state, batch)

        trainer.train_step = slow_step
        result = trainer.train()
        assert not trainer._watchdog.fired
        assert result["steps"] == 2 * 3  # finished the resumed epoch


# ---------------------------------------------------------------------------
# policy/config validation
# ---------------------------------------------------------------------------


def test_skip_policy_rejects_fused_dispatch(tmp_path):
    with pytest.raises(ValueError, match="skip"):
        Trainer(_config(tmp_path, nonfinite_policy="skip",
                        steps_per_dispatch=2))


def test_unknown_policy_rejected(tmp_path):
    with pytest.raises(ValueError, match="nonfinite_policy"):
        Trainer(_config(tmp_path, nonfinite_policy="shrug"))
