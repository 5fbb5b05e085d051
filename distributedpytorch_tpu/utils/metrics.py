"""Loss/throughput records with reference artifact parity.

The reference's only observability is (a) a message-only logfile and (b)
pandas DataFrames pickled to ``./loss/{method}/{train,val}_loss.pkl`` with
columns ``['Step', 'Time', 'Loss']`` — a train row every 10 steps holding the
mean of the last ≤10 losses, and a val row per epoch (reference
utils/train_utils.py:75-79, 82-84, 89-92). `LossRecords` reproduces that
format exactly (it is the imgs/sec comparison source, SURVEY.md §6) and adds
what the reference lacks: imgs/sec accounting and a val-Dice column written
to a separate file so the pickle schema stays reference-compatible.

Unlike the reference, the output directory is created on demand — the
reference crashes at save time because ``./loss/{method}/`` never exists
(SURVEY.md §2 component 13).

Non-blocking by design (the async step pipeline's readback leg): a
metrics row falling due no longer forces the device→host pull on the
spot. The row's window of device scalars is parked as *pending* — with a
best-effort ``copy_to_host_async`` started immediately, so the bytes
stream back under later dispatches — and materialized at the NEXT row
boundary (by which time its steps are a full window old and the copies
have landed: no stall) or at any flush point (epoch validation,
checkpoint ``state_dict``, ``save``). Values are bit-identical to the
blocking scheme; only when the host blocks changes.
"""

from __future__ import annotations

import os
import time
from typing import List, Optional

import numpy as np

from distributedpytorch_tpu.obs import defs as obsm
from distributedpytorch_tpu.utils.trace import NULL_TIMELINE


def _start_async_copy(x) -> None:
    """Kick off a non-blocking device→host copy where the array supports
    it (jax.Array does; plain floats and lazy callables don't need it)."""
    try:
        x.copy_to_host_async()
    except AttributeError:
        pass


class StepReadout:
    """One step's ``[loss, *counters]`` (train/steps.pack_readout) as the
    loss that ``LossRecords`` takes: ``float()`` is the loss, and the one
    host copy that gives it also hands the counters to the registry
    (obs/defs.record_step_counters). The counters have no readback of
    their own, and none before the loss's lagged one."""

    def __init__(self, packed, names):
        self.packed = packed
        self.names = names
        self._loss = None

    def copy_to_host_async(self) -> None:
        _start_async_copy(self.packed)

    def __float__(self) -> float:
        if self._loss is None:
            host = np.asarray(self.packed, dtype=np.float64)
            self._loss = float(host[0])
            obsm.record_step_counters(self.names, host[1:])
            self.packed = None
        return self._loss


class LossRecords:
    """Accumulates train/val loss rows and writes reference-format pickles."""

    def __init__(
        self,
        method_tag: str,
        loss_dir: str = "./loss",
        every: int = 10,
        tracer=None,
        nonfinite_hook=None,
    ):
        self.method_tag = method_tag
        self.loss_dir = loss_dir
        self.every = every
        self.tracer = tracer or NULL_TIMELINE
        # non-finite loss detection piggybacked on the readback (the drain
        # already materializes every loss to a host float — checking it is
        # free): called as hook(step, value) on the first non-finite value
        # of each drained window. The trainer's failure policies hang off
        # this (train/loop.py); None = no detection (standalone users).
        self.nonfinite_hook = nonfinite_hook
        self.start_time = time.time()
        self.losses: List[float] = []
        self.train_rows: List[list] = []  # [step, time_s, mean-of-last-10 loss]
        # rows due but not yet drained to host: [step, time_s, lo, hi] with
        # (lo, hi) the window's index range in self.losses
        self._pending_rows: List[list] = []
        self.val_rows: List[list] = []  # [step, time_s, val loss]
        self.dice_rows: List[list] = []  # [step, time_s, val dice] (new)
        self.images_seen = 0
        # Steady-state throughput reference point: set when the FIRST train
        # step has been recorded, so XLA compile + warmup of step 1 are
        # excluded from images_per_second.
        self._steady_t0: Optional[float] = None
        self._steady_images0 = 0

    def record_train(self, step: int, loss, batch_images: int = 0) -> None:
        """Call once per optimizer step with the UNSCALED loss
        (reference train_utils.py:67, 75-79).

        `loss` may be a device scalar OR a zero-arg callable returning one
        (the multi-step path defers slicing its (K,) loss array until its
        row drains — slicing eagerly would issue K extra device dispatches
        and undo the dispatch amortization). Nothing blocks here: a due
        row drains the PREVIOUS pending row (its async copies are a full
        window old) and parks its own window for the next boundary."""
        self.losses.append(loss)
        self.images_seen += batch_images
        obsm.TRAIN_STEPS.inc()
        if batch_images:
            obsm.TRAIN_IMAGES.inc(batch_images)
        if self._steady_t0 is None:
            # step 1 just ran (its dispatch included the jit trace+compile):
            # start the steady-state clock here and don't count its images
            self._steady_t0 = time.time()
            self._steady_images0 = self.images_seen
        if step % self.every == 0:
            self.drain()
            lo = max(0, len(self.losses) - self.every)
            hi = len(self.losses)
            for x in self.losses[lo:hi]:
                _start_async_copy(x)
            self._pending_rows.append(
                [step, time.time() - self.start_time, lo, hi]
            )

    def drain(self) -> None:
        """Materialize pending rows: force their loss windows to host (the
        pipeline's ``readback`` phase) and append the finished
        [step, time, mean] rows. The Time column keeps the timestamp of
        when the row fell DUE, not when it drained."""
        if not self._pending_rows:
            return
        pending, self._pending_rows = self._pending_rows, []
        with self.tracer.span("readback", rows=len(pending)):
            for step, ts, lo, hi in pending:
                window = [
                    float(x() if callable(x) else x) for x in self.losses[lo:hi]
                ]
                self.losses[lo:hi] = window
                self.train_rows.append([step, ts, float(np.mean(window))])
                # telemetry rides the drain the pipeline already does —
                # the one place a train-loss value is a host float for free
                obsm.TRAIN_LOSS.set(self.train_rows[-1][2])
                if self.nonfinite_hook is not None:
                    for v in window:
                        if not np.isfinite(v):
                            # the hook may raise (abort/rollback policies);
                            # this row is already appended, so the curve
                            # shows WHERE the run went non-finite
                            self.nonfinite_hook(step, v)
                            break

    def state_dict(self) -> dict:
        """Serializable metric history for checkpointing (msgpack-plain:
        nested lists and numbers only). Pending rows and lazy losses are
        forced — the checkpoint must not hold device references."""
        self.drain()
        window = [float(x() if callable(x) else x) for x in self.losses]
        self.losses[:] = window
        if self.nonfinite_hook is not None:
            # the sub-window since the last due row is only ever forced
            # HERE (drain checks whole rows): without this, a NaN landing
            # between row boundaries would be checkpointed as healthy
            # state and detection would miss it entirely
            for v in window[-self.every:]:
                if not np.isfinite(v):
                    self.nonfinite_hook(len(window), v)
                    break
        return {
            "train_rows": [list(map(float, r)) for r in self.train_rows],
            "val_rows": [list(map(float, r)) for r in self.val_rows],
            "dice_rows": [list(map(float, r)) for r in self.dice_rows],
            # sub-window losses recorded since the last row: without them a
            # resume would under-fill the next mean-of-last-N row and drop
            # those steps from the curve entirely
            "window": window[-self.every :],
            "images_seen": int(self.images_seen),
            "elapsed": float(self.elapsed),
        }

    def load_state_dict(self, state: dict) -> None:
        """Resume metric history: rows append after the restored ones and
        the Time column stays monotonic (start_time is shifted so restored
        elapsed time is accounted for)."""
        self.train_rows = [[int(r[0]), float(r[1]), float(r[2])] for r in state["train_rows"]]
        self.val_rows = [[int(r[0]), float(r[1]), float(r[2])] for r in state["val_rows"]]
        self.dice_rows = [[int(r[0]), float(r[1]), float(r[2])] for r in state["dice_rows"]]
        self.images_seen = int(state["images_seen"])
        self.start_time = time.time() - float(state["elapsed"])
        self.losses = [float(x) for x in state.get("window") or []]
        self._pending_rows = []
        # throughput clock restarts at the resumed run's first step (its
        # compile is excluded just like a fresh run's)
        self._steady_t0 = None
        self._steady_images0 = 0

    def record_val(self, step: int, val_loss: float, val_dice: Optional[float] = None) -> None:
        self.drain()  # epoch boundary: the epoch's train rows land first
        now = time.time() - self.start_time
        self.val_rows.append([step, now, float(val_loss)])
        if val_dice is not None:
            self.dice_rows.append([step, now, float(val_dice)])
        obsm.TRAIN_VAL_LOSS.set(float(val_loss))
        if val_dice is not None:
            obsm.TRAIN_VAL_DICE.set(float(val_dice))
        obsm.TRAIN_IMGS_PER_S.set(self.images_per_second())

    @property
    def elapsed(self) -> float:
        return time.time() - self.start_time

    def images_per_second(self) -> float:
        """Steady-state throughput: images per wall-second measured from the
        end of the first recorded step, so the first step's compile time is
        not in the denominator. 0.0 until two steps have been recorded."""
        if self._steady_t0 is None:
            return 0.0
        dt = time.time() - self._steady_t0
        images = self.images_seen - self._steady_images0
        return images / dt if dt > 0 and images > 0 else 0.0

    def save(self) -> None:
        """Write ``{train,val}_loss.pkl`` (reference schema) + ``val_dice.pkl``."""
        import pandas as pd

        self.drain()

        out = os.path.join(self.loss_dir, self.method_tag)
        os.makedirs(out, exist_ok=True)
        pd.DataFrame(self.train_rows, columns=["Step", "Time", "Loss"]).to_pickle(
            os.path.join(out, "train_loss.pkl")
        )
        pd.DataFrame(self.val_rows, columns=["Step", "Time", "Loss"]).to_pickle(
            os.path.join(out, "val_loss.pkl")
        )
        pd.DataFrame(self.dice_rows, columns=["Step", "Time", "Dice"]).to_pickle(
            os.path.join(out, "val_dice.pkl")
        )
