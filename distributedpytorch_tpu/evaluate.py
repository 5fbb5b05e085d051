"""Validation pass: mean per-batch loss (+ the Dice metric the reference
never computes).

Parity with reference evaluate.py:6-25 — eval-mode forward over the val
loader, mean of per-batch criterion values. The UNet has no dropout/batchnorm
so train/eval mode is a no-op distinction (the reference toggles it anyway);
here the same pure apply serves both.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Tuple

import jax
import numpy as np

from distributedpytorch_tpu.utils.prefetch import bounded_prefetch


def evaluate(
    eval_step: Callable,
    params,
    loader,
    place_batch: Callable = None,
    epoch: int = 0,
    progress: bool = False,
) -> Tuple[float, float]:
    """Returns (mean val loss, mean val dice) over the loader.

    `eval_step(params, batch) -> {'loss', 'dice'}` is the strategy-jitted
    step; `place_batch` moves host batches onto the mesh. `progress` shows
    the reference's per-round tqdm bar (reference evaluate.py:12).
    """
    from tqdm import tqdm

    losses, dices = [], []
    batches = loader.epoch_batches(epoch)
    if progress:
        batches = tqdm(
            batches, total=len(loader), desc="Validation round",
            unit="batch", leave=False,
        )
    # Keep device scalars and pull them in chunks — a float() per batch is a
    # blocking device→host round trip per metric, while NO sync at all lets
    # the host place the
    # entire val set's input buffers on the device before the first eval
    # step retires (gigabytes of live HBM at full resolution). A chunked
    # device_get bounds run-ahead to CHUNK batches per transfer.
    CHUNK = 8
    for batch in batches:
        if place_batch is not None:
            batch = place_batch(batch)
        metrics = eval_step(params, batch)
        losses.append(metrics["loss"])
        dices.append(metrics["dice"])
        if len(losses) % CHUNK == 0:
            losses[-CHUNK:], dices[-CHUNK:] = jax.device_get(
                (losses[-CHUNK:], dices[-CHUNK:])
            )
    if not losses:
        return float("nan"), float("nan")
    losses, dices = jax.device_get((losses, dices))
    return float(np.mean(losses)), float(np.mean(dices))


def evaluate_sharded(
    eval_step: Callable,
    grouped_eval_step: Callable,
    params,
    loader,
    place_batch: Callable,
    shard,
    epoch: int = 0,
    progress: bool = False,
) -> Tuple[float, float]:
    """Multi-process evaluation: each process loads and computes 1/world of
    the val set, every process returns the same (mean loss, mean dice).

    Batch formation is IDENTICAL to the replicated path (consecutive
    b-sized slices of the val order), so per-batch metrics — and the mean
    the plateau scheduler consumes — match `evaluate` exactly. Whole
    batches are assigned round-robin: rank p loads global batches p, p+w,
    ..., contributes each as its shard of one (w·b)-sized grouped dispatch
    (`place_batch` assembles the global array from per-process parts), and
    the grouped step returns all w per-batch metrics to every process.
    The ragged tail (< w batches) falls back to the replicated path, so no
    rank ever skips a collective another rank is waiting in.

    `shard` is the strategy's `eval_shard()`; world == 1 short-circuits to
    plain `evaluate` (same loop, no grouping).
    """
    from tqdm import tqdm

    w, rank = shard.world, shard.rank
    if w == 1:
        return evaluate(
            eval_step, params, loader, place_batch, epoch=epoch, progress=progress
        )

    b = loader.batch_size
    slices = loader.batch_slices(epoch)  # the SAME formation evaluate() uses
    # only uniform b-sized batches can stack into the grouped dispatch; the
    # (at most one) ragged final slice joins the replicated tail
    full = [s for s in slices if len(s) == b]
    n_groups = len(full) // w
    tail = full[n_groups * w :] + slices[len(full) :]

    mine = [full[g * w + rank] for g in range(n_groups)]
    # decode this rank's next batches while the device chews the current
    # group — same overlap epoch_batches gives the replicated path
    gen = bounded_prefetch(mine, loader.load_slice, depth=2)
    iterator = (
        tqdm(gen, total=n_groups, desc="Validation round (sharded)",
             unit="group", leave=False)
        if progress
        else gen
    )
    losses, dices = [], []
    CHUNK = 8
    with contextlib.closing(gen):
        for _idx, local in iterator:
            metrics = grouped_eval_step(params, place_batch(local))
            losses.append(metrics["loss"])  # (w,) device vectors, batch order
            dices.append(metrics["dice"])
            if len(losses) % CHUNK == 0:
                losses[-CHUNK:], dices[-CHUNK:] = jax.device_get(
                    (losses[-CHUNK:], dices[-CHUNK:])
                )
    losses = [x for arr in jax.device_get(losses) for x in np.asarray(arr)]
    dices = [x for arr in jax.device_get(dices) for x in np.asarray(arr)]
    tail_metrics = [
        eval_step(params, place_batch(loader.load_slice(idx))) for idx in tail
    ]
    for m in jax.device_get(tail_metrics):  # ONE host round trip for the tail
        losses.append(float(m["loss"]))
        dices.append(float(m["dice"]))
    if not losses:
        return float("nan"), float("nan")
    return float(np.mean(losses)), float(np.mean(dices))
