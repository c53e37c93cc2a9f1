"""Packed pre-training batches: seeded documents of log-normal length, joined
by an end-of-document token and cut into rows of ``seq + 1`` tokens.

Parameters (the traffic file's ``params``): ``seq``, ``doc_median``,
``doc_sigma``, ``doc_min``, ``doc_max``. Token ids are uniform over the
vocabulary below the end-of-document id (the last id). Batch ``i`` of seed
``s`` is a pure function of (s, i): the program's feed and the reference
draw the same rows.
"""

import numpy as np


def doc_lengths(rng, n, p):
    lengths = np.exp(rng.normal(np.log(p["doc_median"]), p["doc_sigma"], n))
    return np.clip(lengths, p["doc_min"], p["doc_max"]).astype(np.int64)


def batch(p, seed, index, rows, vocab):
    """(tokens, targets), each (rows, seq) int32, and the document lengths
    packed into them."""
    rng = np.random.default_rng([int(seed), int(index)])
    need = rows * (p["seq"] + 1)
    eod = vocab - 1
    lengths = []
    while sum(lengths) + len(lengths) < need:
        lengths.extend(doc_lengths(rng, 64, p).tolist())
    stream = rng.integers(0, eod, need, dtype=np.int32)
    ends = np.cumsum(np.asarray(lengths) + 1) - 1
    stream[ends[ends < need]] = eod
    data = stream.reshape(rows, p["seq"] + 1)
    return data[:, :-1], data[:, 1:], lengths
