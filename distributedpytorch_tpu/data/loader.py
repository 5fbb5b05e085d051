"""Deterministic split + sharded, prefetching batch loader.

Replaces the reference's `random_split` + `DataLoader` + `DistributedSampler`
stack (reference utils/train_utils.py:35-42, :185-191) with host-side numpy
machinery sized for a JAX trainer:

  * `seeded_split` — ONE deterministic split shared by every strategy and
    every process. This deliberately fixes reference quirk 5 (SURVEY.md §2):
    the reference's DDP path splits with a differently-seeded generator than
    its single/DP paths, so val curves were never comparable across methods.
  * `ShardSpec` — DistributedSampler-equivalent per-process sharding: pad the
    sample list to a multiple of world size by wrapping around (exactly what
    torch's DistributedSampler does), then stride by rank.
  * `DataLoader` — per-epoch reshuffle driven by (seed, epoch); the epoch is
    an argument to `epoch_batches`, which structurally fixes the reference's
    missing `sampler.set_epoch` (SURVEY.md §3.2) — you cannot forget to pass
    it. Decodes items with a thread pool (the torch `num_workers=1` process
    boundary, train_utils.py:40, becomes threads: PIL decode releases the
    GIL) and assembles NHWC batches.
"""

from __future__ import annotations

import dataclasses
import logging
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, Optional, Sequence, Tuple

import numpy as np

from distributedpytorch_tpu.utils import faults
from distributedpytorch_tpu.utils.trace import NULL_TIMELINE

logger = logging.getLogger(__name__)

Batch = Dict[str, np.ndarray]


def seeded_split(
    n: int, val_fraction: float, seed: int = 0
) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic (train_indices, val_indices) split.

    `n_val = int(n * val_fraction)` matches the reference's
    ``int(len(dataset) * val_percent/100)`` rounding (train_utils.py:35-36).
    """
    n_val = int(n * val_fraction)
    perm = np.random.default_rng(seed).permutation(n)
    return perm[n_val:], perm[:n_val]


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Which contiguous-strided shard of each (padded) epoch this process owns.

    rank/world mirror `DistributedSampler(dataset, num_replicas, rank)`
    (reference train_utils.py:189): pad by wrap-around so every rank sees the
    same number of samples, then take indices[rank::world].
    """

    rank: int = 0
    world: int = 1

    def __post_init__(self):
        if not 0 <= self.rank < self.world:
            raise ValueError(f"rank {self.rank} out of range for world {self.world}")

    def shard(self, order: np.ndarray) -> np.ndarray:
        if self.world == 1:
            return order
        total = -(-len(order) // self.world) * self.world  # ceil to multiple
        # repeat the whole list as many times as needed (order can be shorter
        # than the padding when world > len(order)), then truncate — torch
        # DistributedSampler semantics: every rank gets exactly total/world
        reps = -(-total // len(order))
        padded = np.concatenate([order] * reps)[:total]
        return padded[self.rank :: self.world]


def _stack_items(items) -> Batch:
    """A batch from its items: every field stacked on a new leading axis."""
    return {k: np.stack([it[k] for it in items]) for k in items[0]}


class DataLoader:
    """Batched, optionally sharded, thread-prefetched iterator over a dataset.

    `dataset` is anything with `__len__` and `__getitem__` returning a
    dict of arrays, the same fields for every item: ``{'image': (H,W,C)
    f32, 'mask': (H,W) i32}`` (data/dataset.py) or ``{'tokens': (S,) i32}``
    (data/tokens.py). A batch stacks each field on a new leading axis.
    """

    def __init__(
        self,
        dataset,
        indices: Optional[Sequence[int]] = None,
        batch_size: int = 4,
        shuffle: bool = False,
        drop_last: bool = False,
        seed: int = 0,
        shard: ShardSpec = ShardSpec(),
        num_workers: int = 0,
        cache=None,
        tracer=None,
        max_retries: int = 0,
        retry_backoff_s: float = 0.05,
    ):
        self.dataset = dataset
        self.indices = (
            np.arange(len(dataset)) if indices is None else np.asarray(indices)
        )
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.seed = seed
        self.shard_spec = shard
        self.num_workers = int(num_workers)
        # transient decode failures (OSError family: disk/network reads,
        # PIL on torn files — and the injected `decode` fault) retry with
        # bounded exponential backoff before surfacing (utils/faults.py)
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        # epoch-persistent decoded-sample cache (data/dataset.SampleCache),
        # shared across loaders of the same dataset (train + val): epochs
        # >= 2 serve whatever fit the budget from host memory, skipping
        # decode entirely
        self.cache = cache
        self.tracer = tracer or NULL_TIMELINE
        self._pool = (
            ThreadPoolExecutor(max_workers=self.num_workers)
            if self.num_workers > 0
            else None
        )

    def __len__(self) -> int:
        """Batches per epoch for this shard."""
        n = self.num_samples()
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def num_samples(self) -> int:
        """Samples per epoch in this process's shard (before drop_last)."""
        return len(self.shard_spec.shard(self.indices))

    def steps_per_epoch(self) -> int:
        return len(self)

    def _epoch_order(self, epoch: int) -> np.ndarray:
        order = self.indices
        if self.shuffle:
            # (seed, epoch)-keyed reshuffle — identical on every process, so
            # shards stay disjoint; varies per epoch, fixing the reference's
            # missing set_epoch (SURVEY.md §3.2).
            rng = np.random.default_rng((self.seed, epoch))
            order = rng.permutation(order)
        return self.shard_spec.shard(order)

    def _load_batch(self, idx_list, epoch: Optional[int] = None,
                    batch_idx: Optional[int] = None) -> Batch:
        """Assemble one batch with bounded-backoff retries on transient
        failures; ``(epoch, batch_idx)`` (when the caller knows them) are
        the `decode` fault-injection site's coordinates."""
        return faults.call_with_retries(
            lambda: self._assemble_batch(idx_list),
            site="decode",
            retries=self.max_retries,
            backoff_s=self.retry_backoff_s,
            epoch=epoch,
            step=batch_idx,
            log=logger,
        )

    def _assemble_batch(self, idx_list) -> Batch:
        """Assemble one batch, serving cached samples from host memory and
        decoding only the misses (traced as the pipeline's ``decode``
        phase — on a warm cache the span collapses to stack-only time)."""
        with self.tracer.span("decode", n=len(idx_list)):
            if self.cache is None:
                return self._decode_batch(idx_list)
            items = {int(i): self.cache.get(int(i)) for i in idx_list}
            missing = [i for i, it in items.items() if it is None]
            if missing:
                fresh = self._decode_batch(missing)
                for row, i in enumerate(missing):
                    item = {k: v[row] for k, v in fresh.items()}
                    self.cache.put(i, item)
                    items[i] = item
                if len(missing) == len(idx_list):
                    # nothing came from cache and indices were unique
                    # (len matches): fresh IS the batch, already in idx
                    # order — the steady state of a full cache must not
                    # pay a redundant split + re-stack per batch
                    return fresh
            return _stack_items([items[int(i)] for i in idx_list])

    def _decode_batch(self, idx_list) -> Batch:
        """Decode one batch from the backing dataset; uses the native C++
        whole-batch path (decode + resize + normalize, threaded in C, see
        data/native.py) when the dataset is filesystem-backed with
        supported formats."""
        ds = self.dataset
        if getattr(ds, "use_native", False) and hasattr(ds, "resolve_paths"):
            from distributedpytorch_tpu.data import native

            if native.get_lib() is not None:
                paths = [ds.resolve_paths(int(i)) for i in idx_list]
                if all(
                    native.supports(p) and native.supports(m) for p, m in paths
                ):
                    imgs, masks = native.load_batch(
                        [p for p, _ in paths],
                        [m for _, m in paths],
                        ds.newsize[0],
                        ds.newsize[1],
                        n_threads=max(self.num_workers, 4),
                    )
                    return {"image": imgs, "mask": masks}
        return _stack_items([ds[int(i)] for i in idx_list])

    def batch_slices(self, epoch: int = 0) -> list:
        """This epoch's batches as index slices, in order — THE definition
        of batch formation, shared by `epoch_batches` and the sharded
        evaluator (evaluate.evaluate_sharded), which assigns whole slices
        to processes; one definition keeps their batch formation
        identical by construction."""
        order = self._epoch_order(epoch)
        cut = (
            len(order) - len(order) % self.batch_size
            if self.drop_last
            else len(order)
        )
        order = order[:cut]
        return [
            order[s : s + self.batch_size]
            for s in range(0, len(order), self.batch_size)
        ]

    def load_slice(self, idx_list) -> Batch:
        """Assemble the batch for one `batch_slices` entry."""
        return self._load_batch(idx_list)

    def epoch_batches(self, epoch: int = 0) -> Iterator[Batch]:
        slices = self.batch_slices(epoch)
        if self._pool is None:
            for i, idx in enumerate(slices):
                yield self._load_batch(idx, epoch=epoch, batch_idx=i)
            return

        # Pipelined prefetch: keep up to 2 whole-batch futures in flight
        # (the native path threads across items inside each batch in C++).
        # bounded_submit cancels queued decodes if the consumer stops early.
        from distributedpytorch_tpu.utils.prefetch import bounded_submit

        def load(pair):
            i, idx = pair
            return self._load_batch(idx, epoch=epoch, batch_idx=i)

        yield from bounded_submit(
            self._pool, load, list(enumerate(slices)), depth=2
        )

    def __iter__(self) -> Iterator[Batch]:
        return self.epoch_batches(0)
