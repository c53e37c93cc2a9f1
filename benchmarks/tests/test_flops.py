"""``flops.py`` against counts made by hand for both models."""
import json
import os

import pytest

from benchmarks import flops
from benchmarks.reference import gpt_ref

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dims(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return gpt_ref.dims(json.load(f))


def test_starcoderbase_1b_mqa():
    d = dict(dims("starcoderbase-1b-train1"), n_layer=24)    # as published
    assert (d["n_kv_head"], d["head_dim"]) == (1, 128)
    # per layer: q|k|v 2048 x (2048 + 2 x 128), out 2048^2, MLP 2 x 2048 x 8192
    per_layer = 2048 * 2304 + 2048 * 2048 + 2 * 2048 * 8192
    assert flops.matmul_params(d) == 24 * per_layer + 49152 * 2048
    assert flops.total_params(d) == 1_137_207_296         # the published 1.137 B
    # causal attention at 8,192: QK^T and PV, 2 flops each, mean 4096.5 keys, 16 x 128 wide
    attn = 24 * 2 * 2 * 2048 * 4096.5
    assert flops.forward_flops_per_token(d, 8192) == pytest.approx(
        2 * (24 * per_layer + 49152 * 2048) + attn)


def test_train_cut_to_8_layers():
    d = dims("starcoderbase-1b-train1")
    assert d["n_layer"] == 8
    assert flops.train_flops_per_token(d, 8192) == pytest.approx(3.4478e9, rel=1e-4)
    # bench.py's count (12 H^2 a layer whatever the heads and n_inner, and a
    # non-causal 12 L S H) overstates it by a third
    old = 6 * (12 * 8 * 2048 * 2048 + 49152 * 2048) + 12 * 8 * 8192 * 2048
    assert old == pytest.approx(4.63e9, rel=1e-3)
    assert old / flops.train_flops_per_token(d, 8192) == pytest.approx(1.343, abs=0.005)


def test_gpt2_medium_mha():
    d = dims("gpt2-medium")
    assert (d["n_kv_head"], d["head_dim"], d["n_inner"]) == (16, 64, 4096)
    # required work counts the published vocabulary, not the padded table
    assert (d["vocab_size"], d["vocab_rows"]) == (50257, 50304)
    per_layer = 1024 * 3072 + 1024 * 1024 + 2 * 1024 * 4096     # 12 H^2
    assert per_layer == 12 * 1024 * 1024
    assert flops.matmul_params(d) == 24 * per_layer + 50257 * 1024
    assert flops.total_params(d) == 354_823_168           # the published 355 M
    want = 3 * (2 * (24 * per_layer + 50257 * 1024) + 24 * 4 * 1024 * 512.5)
    assert flops.train_flops_per_token(d, 1024) == pytest.approx(want)
    assert flops.train_flops_per_token(d, 1024) == pytest.approx(2.2718e9, rel=1e-4)
