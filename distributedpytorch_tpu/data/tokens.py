"""Token batches: variable-length documents packed into fixed sequences.

The batch spec of a token model (models/__init__.TOKEN_BATCH) is one
field, ``tokens`` (B, S) int32. A sequence is a stretch of the stream
"document, end-of-document id, next document, ..." with no padding and no
regard for where a document ends: position t predicts token t + 1, the
state of a scan is not reset and attention is not masked at a document's
end (Megatron's default for pre-training; masks and resets are ROADMAP
M5's remainder). Items go through the same ``data/loader.DataLoader``,
host cache and feed as images do.
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, Tuple

import numpy as np


def pack_documents(docs: Iterable[np.ndarray], seq_len: int, eod_id: int,
                   carry: np.ndarray = None) -> Tuple[np.ndarray, np.ndarray]:
    """``(sequences (n, seq_len) int32, rest)``: every token of every
    document, in order, each document followed by ``eod_id``, cut into
    sequences of ``seq_len``. ``rest`` is the stream's tail that fills no
    whole sequence: hand it back as ``carry`` with the next documents, and
    nothing is lost or padded."""
    parts = [] if carry is None or not len(carry) else [np.asarray(carry, np.int32)]
    eod = np.asarray([eod_id], np.int32)
    for doc in docs:
        parts.append(np.asarray(doc, np.int32).ravel())
        parts.append(eod)
    stream = np.concatenate(parts) if parts else np.zeros((0,), np.int32)
    n = len(stream) // seq_len
    return stream[: n * seq_len].reshape(n, seq_len), stream[n * seq_len:]


def synthetic_documents(rng: np.random.Generator, tokens: int, vocab_size: int,
                        median: float = 512.0, sigma: float = 1.25,
                        min_len: int = 16, max_len: int = 32768):
    """Documents with log-normal lengths (clipped) and Zipf(1.0) ids over
    ``vocab_size - 1`` ids (the last id is kept for end-of-document),
    until they hold at least ``tokens`` tokens with their end markers."""
    ranks = np.arange(1, vocab_size, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks)
    cdf /= cdf[-1]
    docs, total = [], 0
    while total < tokens:
        n = int(np.clip(round(float(rng.lognormal(np.log(median), sigma))),
                        min_len, max_len))
        docs.append(np.searchsorted(cdf, rng.random(n)).astype(np.int32))
        total += n + 1
    return docs


class PackedTokenDataset:
    """Packed sequences as a data set: item i is ``{'tokens': (S,) int32}``."""

    def __init__(self, sequences: np.ndarray):
        self.sequences = np.ascontiguousarray(sequences, np.int32)

    def __len__(self) -> int:
        return len(self.sequences)

    def __getitem__(self, idx: int) -> dict:
        return {"tokens": self.sequences[idx]}


def build_token_dataset(config, vocab: int) -> PackedTokenDataset:
    """``--synthetic N``: N sequences of ``config.seq_len`` packed from
    generated documents (seeded by ``config.seed``); else every ``*.npy``
    under ``config.data_dir`` is one document of token ids, packed in file
    order. ``vocab`` is the model's vocabulary (the model table's entry
    passes it), its last id the end-of-document marker."""
    seq_len = int(config.seq_len)
    if config.synthetic_samples > 0:
        rng = np.random.default_rng(config.seed)
        docs = synthetic_documents(
            rng, config.synthetic_samples * seq_len, vocab)
        sequences, _ = pack_documents(docs, seq_len, vocab - 1)
        return PackedTokenDataset(sequences[: config.synthetic_samples])
    paths = sorted(glob.glob(os.path.join(config.data_dir, "*.npy")))
    if not paths:
        raise FileNotFoundError(
            f"no *.npy token documents under {config.data_dir!r} "
            "(or pass --synthetic N)")
    docs = [np.load(p) for p in paths]
    worst = max(int(d.max(initial=0)) for d in docs)
    if worst >= vocab - 1:
        raise ValueError(
            f"token id {worst} does not fit the model's {vocab - 1} ids "
            f"(id {vocab - 1} is the end-of-document marker)")
    sequences, _ = pack_documents(docs, seq_len, vocab - 1)
    return PackedTokenDataset(sequences)
