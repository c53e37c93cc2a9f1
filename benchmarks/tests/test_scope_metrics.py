"""The block-level readers (``attn_block_ms`` ... ``unscoped_ms``,
``delta_mixer_ms``) on a hand-made run: seconds by instruction name as the
trace reduction hands them, and the span path of each name handed through
``scope_work``'s one seam, ``run["scope_table"]``, where a run on the chip
finds the step's module among the live executables. Paths are spelt as the
cells' compiled step programs spell them."""
import os

import pytest

from benchmarks import run, scope_work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
FWD = "jit(call)/amp/fwd_bwd/jvp({})/"
REMAT = "jit(call)/amp/fwd_bwd/transpose(jvp(amp/fwd_bwd))/jvp()/checkpoint/"
# name: (ms over the two traced steps, op_name)
OPS = {
    "fusion.1": (8, FWD.format("hybrid/attn_win") + "mix/proj_in/dot_general"),
    "fusion.2": (6, REMAT + "rematted_computation/hybrid/attn_win/mix/proj_in/dot_general"),
    "fusion.3": (4, REMAT + "hybrid/attn/mix/proj_out/transpose"),
    "fusion.4": (2, FWD.format("hybrid/attn_win") + "mix/place/mul"),
    "fusion.5": (1, FWD.format("hybrid/attn") + "reduce_sum"),
    "flash_fwd_bshd_win.0": (10, FWD.format("hybrid/attn_win") + "flash_fwd_bshd_win/pallas_call"),
    "flash_bwd_bshd_fused.1": (20, REMAT + "hybrid/attn/transpose(jvp(flash_bwd_bshd_fused))/pallas_call"),
    "fusion.6": (12, FWD.format("hybrid/attn_mla") + "mla/down/dot_general"),
    "fusion.7": (3, FWD.format("hybrid/attn_mla") + "mla/up/dot_general"),
    "fusion.8": (5, FWD.format("gpt/attn") + "dot_general"),
    "fusion.9": (30, FWD.format("hybrid/gdn") + "mix/proj_in/dot_general"),
    "gdn_fwd.6": (14, FWD.format("hybrid/gdn") + "gdn_fwd/pallas_call"),
    "conv_silu_fwd.18": (7, FWD.format("hybrid/gdn") + "jit(conv_silu_fwd)/pallas_call"),
    "gated_norm_bwd.3": (9, REMAT + "hybrid/gdn/transpose(jvp(gated_norm_bwd))/pallas_call"),
    "fusion.10": (16, FWD.format("hybrid/dense") + "dot_general"),
    "fusion.11": (18, "jit(call)/amp/fwd_bwd/transpose(jvp(gpt/mlp))/dot_general"),
    "fusion.12": (22, FWD.format("hybrid/moe") + "moe/route/sort"),
    "moe_gmm.2": (24, FWD.format("hybrid/moe") + "jvp(moe/experts)/moe_gmm/pallas_call"),
    "moe_rows_gather.1": (26, REMAT + "rematted_computation/hybrid/moe/jit(moe_rows_gather)/pallas_call"),
    "fusion.13": (28, "jit(call)/amp/fwd_bwd/transpose(amp/fwd_bwd)/jvp(hybrid/unembed_xent)/dot_general"),
    "xentropy_stats.1": (2, FWD.format("gpt/unembed_xent") + "xentropy_stats/pallas_call"),
    "fusion.14": (32, "jit(call)/amp/apply_master/sub"),
    "fusion.15": (34, "jit(call)/fused_adam/update/mul"),
    "fusion.16": (36, "jit(call)/amp/unscale_check/reduce_and"),
    "fusion.17": (38, "jit(call)/amp/fwd_bwd/jvp()/convert_element_type"),
    "copy.1": (40, ""),
    "psum.129": (42, "jit(call)/psum"),
    "fusion.99": (44, None),                  # a name the step's module does not hold
}
STEPS = 2
# ms a step: the sums by hand
WANT = {
    "attn_block_ms": (8 + 6 + 4 + 2 + 1 + 10 + 20 + 12 + 3 + 5) / STEPS,
    "attn_outside_kernels_ms": (8 + 6 + 4 + 2 + 1 + 12 + 3 + 5) / STEPS,
    "gdn_block_ms": (30 + 14 + 7 + 9) / STEPS,
    "gdn_outside_kernels_ms": 30 / STEPS,
    "mixer_proj_ms": (8 + 6 + 4 + 12 + 3 + 30) / STEPS,
    "mixer_place_ms": 2 / STEPS,
    "mlp_block_ms": (16 + 18) / STEPS,
    "moe_block_ms": (22 + 24 + 26) / STEPS,
    "moe_route_ms": 22 / STEPS,
    "unembed_xent_ms": (28 + 2) / STEPS,
    "optimizer_ms": (32 + 34 + 36) / STEPS,
    "recompute_ms": (6 + 26) / STEPS,
    "unscoped_ms": (40 + 42 + 44) / STEPS,
    "delta_mixer_ms": (7 + 9) / STEPS,
}


def hand_run(**over):
    trace = {"chips": 1, "ops_s": {name: ms / 1e3 for name, (ms, _) in OPS.items()}}
    table = {name: path for name, (_, path) in OPS.items() if path is not None}
    return dict({"trace": trace, "step_s": [0.5] * STEPS, "scope_table": table}, **over)


def read(name, r):
    return run.load_reader(name).read(r)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_sums_its_spans_on_a_hand_made_run(name):
    assert read(name, hand_run()) == pytest.approx(WANT[name], rel=1e-12)


def test_the_books_close_on_the_hand_made_run():
    r = hand_run()
    rolled = scope_work.rollup(r)
    blocks = sum(read(n, r) for n in ("attn_block_ms", "gdn_block_ms", "mlp_block_ms",
                                      "moe_block_ms", "unembed_xent_ms", "optimizer_ms",
                                      "unscoped_ms"))
    own = sum(rolled["spans"]["amp/fwd_bwd"]["self_ms"].values())
    assert own == pytest.approx(38 / STEPS)
    assert blocks + own == pytest.approx(rolled["busy_ms"])
    assert rolled["busy_ms"] == pytest.approx(sum(ms for ms, _ in OPS.values()) / STEPS)
    assert r["scope_rollup"] is rolled                 # computed once, kept on the run


LACKS = {"trace": dict(trace=None), "chips": dict(trace={"chips": 0, "ops_s": {}}),
         "steps": dict(step_s=[]), "table": dict(scope_table=None)}


# ``delta_mixer_ms`` reads by name: it needs no table
@pytest.mark.parametrize("name,lack", [(n, k) for n in sorted(WANT) for k in LACKS
                                       if (n, k) != ("delta_mixer_ms", "table")])
def test_reader_returns_none_without_a_trace_or_a_table(name, lack, monkeypatch):
    from apex_tpu.prof import scopes
    monkeypatch.setattr(scopes, "live_scope_table", lambda ops_s: None)   # no step alive
    assert read(name, hand_run(**LACKS[lack])) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_returns_none_where_no_operation_matches(name):
    plain = {"fusion.1": (8, "jit(call)/amp/fwd_bwd/jvp()/add")}
    r = {"trace": {"chips": 1, "ops_s": {n: ms / 1e3 for n, (ms, _) in plain.items()}},
         "step_s": [0.5], "scope_table": {n: p for n, (_, p) in plain.items()}}
    assert read(name, r) is None


def test_a_program_without_the_rollup_reads_as_nothing(monkeypatch):
    monkeypatch.setattr(scope_work, "scopes", None)
    assert read("attn_block_ms", hand_run()) is None and read("recompute_ms", hand_run()) is None


GPT_CELLS = ("sc1b-train-8k", "gpt2m-train-1k-dp4")
Q3NEXT, TRINITY, DSV2LITE, NEMOTRON3, OURO, LING3 = (
    "q3next-train-8k", "trinity-train-8k", "dsv2lite-train-8k", "nemotron3-train-8k",
    "ouro-train-8k", "ling3-train-8k")
# the cells KNOWN to hold each block metric's spans, and listed for it: a cell this table
# does not know (a later PR's) may stand in any list and fails nothing
HOLDS = {
    "attn_block_ms": GPT_CELLS + (Q3NEXT, TRINITY, DSV2LITE, NEMOTRON3, OURO, LING3),
    "attn_outside_kernels_ms": GPT_CELLS + (Q3NEXT, TRINITY, DSV2LITE, OURO),
    "gdn_block_ms": (Q3NEXT,), "gdn_outside_kernels_ms": (Q3NEXT,), "delta_mixer_ms": (Q3NEXT,),
    "mixer_proj_ms": (Q3NEXT, TRINITY, DSV2LITE), "mixer_place_ms": (Q3NEXT, TRINITY, DSV2LITE),
    "mlp_block_ms": GPT_CELLS + (TRINITY, DSV2LITE, OURO, LING3),
    "moe_block_ms": (Q3NEXT, TRINITY, DSV2LITE, NEMOTRON3, LING3),
    "moe_route_ms": (Q3NEXT, TRINITY, DSV2LITE, NEMOTRON3, LING3),
    "unembed_xent_ms": GPT_CELLS + (Q3NEXT, TRINITY, DSV2LITE, NEMOTRON3, OURO, LING3),
    "optimizer_ms": GPT_CELLS + (Q3NEXT, TRINITY, DSV2LITE, NEMOTRON3, OURO, LING3),
    "recompute_ms": (Q3NEXT, TRINITY, OURO, LING3),
    "unscoped_ms": GPT_CELLS + (Q3NEXT, TRINITY, DSV2LITE, OURO, LING3),
}
KNOWN_CELLS = set(GPT_CELLS) | {Q3NEXT, TRINITY, DSV2LITE, NEMOTRON3, OURO, LING3}


def check_manifest(m):
    """Every block metric is in the manifest with its reader's constants, and
    lists the cells known to hold its spans: of the known cells exactly those,
    of any other cell whatever its PR entered."""
    assert set(HOLDS) == set(WANT)
    cells = [w["name"] for w in m["workloads"]]
    assert set(GPT_CELLS) <= set(cells)
    listed = {p["name"]: p for p in m["per_layer"] if p["name"] in WANT}
    assert set(listed) == set(WANT)
    for name, p in listed.items():
        reader = run.load_reader(name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (p["layer"], "ms", "train_tokens_per_s")
        assert (p["better"], p["source"]) == ("lower", "device_trace")
        assert set(p["workloads"]) <= set(cells) and len(set(p["workloads"])) == len(p["workloads"])
        assert set(p["workloads"]) & KNOWN_CELLS == set(HOLDS[name]) & set(cells), name
    assert {p["layer"] for p in listed.values()} == {
        "blocks", "experts (dropless routing)", "trainer step", "kernels"}


def test_manifest_lists_each_block_metric_in_the_cells_that_hold_its_spans():
    check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
