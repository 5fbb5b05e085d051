"""The generator of token traffic: packed documents made from the seed.

``kind: packed_documents`` (a mix is a file of its parameters under
``benchmark/traffic/``): documents with log-normal lengths (``median``,
``sigma``, clipped to ``min_len`` .. ``max_len``), ids Zipf(1.0) over the
vocabulary slice but its last id, which ends every document; all
concatenated and cut into ``samples`` sequences of the configuration's
``sequence_length`` with no padding. The item contract is the program's
token batch spec: ``{'tokens': (S,) int32}``. Written here, not imported
from the program's ``data/tokens.py``, so that a change there cannot move
the yardstick.
"""

from __future__ import annotations

import numpy as np

#: Tokens at the start of a sequence that tell the items of a mix apart.
HEAD = 32


class PackedDocuments:
    def __init__(self, params: dict, seq_len: int, vocab: int, seed: int):
        self.seq_len, self.vocab, self.seed = int(seq_len), int(vocab), int(seed)
        n = int(params["samples"])
        rng = np.random.default_rng(self.seed)
        cdf = np.cumsum(1.0 / np.arange(1, vocab, dtype=np.float64))
        cdf /= cdf[-1]
        need, parts, have, self.documents = n * self.seq_len, [], 0, 0
        while have < need:
            length = int(np.clip(round(float(rng.lognormal(
                np.log(params["median"]), params["sigma"]))),
                params["min_len"], params["max_len"]))
            ids = np.minimum(np.searchsorted(cdf, rng.random(length)), vocab - 2)
            parts += [ids.astype(np.int32), np.asarray([vocab - 1], np.int32)]
            have += length + 1
            self.documents += 1
        self.sequences = np.concatenate(parts)[:need].reshape(n, self.seq_len)

    def __len__(self) -> int:
        return len(self.sequences)

    def __getitem__(self, idx: int) -> dict:
        return {"tokens": self.sequences[idx]}


def build(params: dict, seq_len: int, vocab: int, seed: int) -> PackedDocuments:
    if params["kind"] != "packed_documents":
        raise ValueError(f"unknown traffic kind {params['kind']!r}")
    return PackedDocuments(params, seq_len, vocab, seed)


def same_rows(data: PackedDocuments, batches):
    """``batches`` (what the program's loader stacked) made anew from the
    mix's own sequences: ``([tokens (B, S), ...], altered)``. A row of the
    loader's is found in the mix by how it begins and held against that
    sequence token for token; ``altered`` counts the rows that begin like
    no sequence or differ from theirs (such a row is passed on as the
    loader gave it)."""
    found = {data.sequences[i, :HEAD].tobytes(): i for i in range(len(data))}
    out, altered = [], 0
    for batch in batches:
        rows = []
        for row in np.asarray(batch["tokens"], np.int32):
            idx = found.get(row[:HEAD].tobytes())
            mine = row if idx is None else data.sequences[idx]
            if idx is None or not np.array_equal(mine, row):
                altered += 1
            rows.append(mine)
        out.append(np.stack(rows))
    return out, altered


def rows_repeated(batches) -> int:
    """How many rows of the compared batches repeat an earlier one."""
    seen, repeated = set(), 0
    for batch in batches:
        for row in np.asarray(batch["tokens"]):
            key = row.tobytes()
            repeated += key in seen
            seen.add(key)
    return repeated
