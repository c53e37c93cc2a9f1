"""The generator repeats from the seed and hits its stated distributions."""
import json
import os

import numpy as np

from benchmarks.generators import packed_docs
from benchmarks.reference import gpt_ref

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)["params"]


def test_packed_docs_repeat_and_differ():
    p = mix("packed-code-8k")
    a = packed_docs.batch(p, 2**31 + 5, 3, 2, 49152)
    b = packed_docs.batch(p, 2**31 + 5, 3, 2, 49152)
    c = packed_docs.batch(p, 2**31 + 5, 4, 2, 49152)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (2, 8192) and a[0].dtype == np.int32
    assert np.array_equal(a[0][:, 1:], a[1][:, :-1])   # targets are the next token
    assert len({tuple(r) for r in a[0]}) == 2           # rows all differ


def test_packed_docs_lengths_and_separators():
    p = mix("packed-code-8k")
    lengths = packed_docs.doc_lengths(np.random.default_rng(0), 20000, p)
    assert lengths.min() >= p["doc_min"] and lengths.max() <= p["doc_max"]
    assert abs(np.median(lengths) - p["doc_median"]) < 0.05 * p["doc_median"]
    # the quartiles lie inside the clip, and are 1.349 sigma apart in the log
    q1, q3 = np.percentile(np.log(lengths), [25, 75])
    assert abs((q3 - q1) / 1.349 - p["doc_sigma"]) < 0.05
    tokens, _, docs = packed_docs.batch(p, 1, 0, 4, 49152)
    eod = 49152 - 1
    ends = np.flatnonzero(np.concatenate([tokens[:, :1], tokens], 1)[:, 1:].ravel() == eod)
    assert len(ends) > 0 and tokens.max() <= eod
    # the first separator sits right after the first document
    stream = packed_docs.batch(p, 1, 0, 4, 49152)
    flat = np.concatenate([stream[0], stream[1][:, -1:]], 1).ravel()
    assert flat[docs[0]] == eod


def test_padded_rows_are_never_drawn():
    """gpt2-medium's table has 50,304 rows; ids stay below the published 50,257."""
    with open(os.path.join(HERE, "configs", "gpt2-medium.json")) as f:
        d = gpt_ref.dims(json.load(f))
    tokens, targets, _ = packed_docs.batch(mix("packed-text-1k"), 7, 0, 32, d["vocab_size"])
    assert tokens.shape == (32, 1024)
    assert max(tokens.max(), targets.max()) == d["vocab_size"] - 1 < d["vocab_rows"] - 1
