"""The ``afmoe`` decoder's cell on the CPU at a toy size: the new adapter
through the harness's own ``execute`` (a sound run is correct, hands back
the load counters and moves the selection bias; the float8 control fails the
comparison), the new readers on a hand-made trace spelt as the chip spells
it (a share over 100 % fails here, the accepted flash shares among them),
the required work by hand, and the manifest's new entries."""
import json
import os
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import afmoe_work, hybrid_work, run, trace_reduce as tr  # noqa: E402
from benchmarks.adapters import afmoe_tree, train_o2_afmoe  # noqa: E402
from benchmarks.reference import afmoe_ref  # noqa: E402
from benchmarks.tests import test_harness, toy  # noqa: E402
from benchmarks.tests.test_trace_reduce import plane  # noqa: E402

HERE = os.path.join(ROOT, "benchmarks")
PEAKS = run.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
CELL = "trinity-train-8k"
NEW_METRICS = ("flash_win_fwd_ms", "flash_win_bwd_ms", "flash_win_fwd_roofline_pct",
               "flash_win_bwd_roofline_pct")
# what the cell reports under names it shares with other cells: their lists hold it
SHARED_METRICS = ("mfu_pct", "moe_gmm_ms", "moe_gmm_roofline_pct", "moe_load_max_over_mean")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the cell's cut at a toy size: published layer 0 (dense) and one period, a
# window shorter than the rows, 16 experts top-4 with a share of 8 held
TOY_AFMOE = {
    "name": "toy-afmoe", "adapter": "train_o2_afmoe",
    "hidden_size": 128, "num_hidden_layers": 5, "num_dense_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "sliding_window": 16, "rope_theta": 10000, "intermediate_size": 256,
    "moe_intermediate_size": 128, "num_experts": 8, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "load_balance_coeff": 0.001, "mup_enabled": True,
    "rms_norm_eps": 1e-5, "vocab_size": 256, "layer_types": PERIOD * 2,
    "layers_kept": [0, 4, 5, 6, 7], "router_num_experts": 16, "experts_held_first": 4,
    "engine": {"rows_per_chip": 2, "lr": 3e-4, "remat": True, "check_steps": 3,
               "trace_steps": 2},
    "limits": {"loss_gap": 0.01, "first_gradient_norm_gap": 0.04,
               "first_gradient_projection_gap": 0.2, "moved_norm_gap": 0.3,
               "held_load_gap": 0.05, "router_bias_gap": 0.1},
}


def manifest():
    m = toy.manifest()
    m["workloads"] = [{"name": "toy-afmoe-cell", "config": "toy-afmoe",
                       "traffic": "toy-docs", "chips": 1}]
    m["per_layer"] += [{"name": n, "unit": "x", "moves": "train_tokens_per_s"}
                       for n in ("moe_load_max_over_mean", "flash_win_fwd_ms")]
    return m


@pytest.fixture
def here(tmp_path):
    (tmp_path / "traffic").mkdir()
    os.symlink(os.path.join(HERE, "layer_metrics"), tmp_path / "layer_metrics")
    mix = toy.TOY_TRAIN_MIX
    (tmp_path / "traffic" / (mix["name"] + ".json")).write_text(json.dumps(mix))
    return str(tmp_path)


def test_traced_rehearsal_is_correct_and_hands_back_the_counters(here):
    m = manifest()
    line = run.execute(m, m["workloads"][0], TOY_AFMOE, toy.args(seed=2**31 + 7, trace=1),
                       jax.devices()[:1], PEAKS, here=here)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    # at most the 8 held experts' whole load on one; how far a toy router
    # drifts at lr 3e-4 depends on how many steps the host fits in the window
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] <= 8.0
    assert 0.0 < line["metrics"]["mfu_pct"]["value"] < 100.0
    assert "flash_win_fwd_ms" not in line["metrics"]          # no device in a CPU trace
    json.dumps(line)


@pytest.mark.parametrize("fault, least", [("frozen", 0.2), ("backwards", 0.4),
                                          ("last_step_only", 0.15)])
def test_a_bias_that_is_not_the_references_is_not_correct(here, monkeypatch, fault, least):
    """The routers' selection bias is the one piece of the step's state that
    no gradient moves, so no gradient's limit sees it: a step that leaves it
    where it was, moves it away from the mean load, or loses all but its last
    move passes every other row and fails ``router_bias_gap``."""
    from apex_tpu.transformer import moe
    sound = moe.router_bias_update
    wrong = {"frozen": lambda bias, counts, rate: bias,
             "backwards": lambda bias, counts, rate: sound(bias, counts, -rate),
             "last_step_only": lambda bias, counts, rate: sound(0 * bias, counts, rate)}
    monkeypatch.setattr(moe, "router_bias_update", wrong[fault])
    rows = []
    monkeypatch.setattr(run, "log", rows.append)
    m = manifest()
    line = run.execute(m, m["workloads"][0], TOY_AFMOE, toy.args(seed=2**31 + 11),
                       jax.devices()[:1], PEAKS, here=here)
    assert line["correct"] is False and line["failed"] == 0
    failed = [r.split()[1] for r in rows if r.startswith("check:") and "NOT CORRECT" in r]
    assert failed == ["router_bias_gap"]
    gap = float(next(r for r in rows if r.startswith("check: router_bias_gap")).split()[3])
    # by the gap's formula such a fault reads a sixth to a whole at any size:
    # seven times the real cell's limit and more
    real = run.load_json(os.path.join(HERE, "configs", "trinity-mini-train1.json"))["limits"]
    assert gap > least >= 7 * real["router_bias_gap"]


def _ctx(seed):
    import importlib
    mix = toy.TOY_TRAIN_MIX
    return {"config": TOY_AFMOE, "mix": mix, "seed": seed, "seconds": 1.0, "chips": 1,
            "log": lambda m: None,
            "generator": importlib.import_module("benchmarks.generators." + mix["generator"])}


def test_first_steps_move_the_bias_and_the_float8_control_fails():
    """What ``readings.py`` drives: the program's first steps leave a bias
    that moved by whole rates; the reference against itself passes every row
    by name; computed in float8 it fails at least one limit."""
    from apex_tpu.parallel import mesh as mesh_lib
    ctx = _ctx(3)
    t = train_o2_afmoe.Trainer(ctx)
    try:
        assert {"Trainer", "first_steps", "reference_readings", "compare", "leaf_gaps",
                "ALL_NUMBERS", "setup", "measure", "finish"} <= set(dir(train_o2_afmoe))
        train_o2_afmoe.first_steps(t, ctx)
        t.stop_feed()
        t.state = None
        ref = train_o2_afmoe.reference_readings(t, ctx)
        low = train_o2_afmoe.reference_readings(t, ctx, precision="float8")
    finally:
        mesh_lib.destroy_model_parallel()
    got = t.readings
    assert got["expert_load"].shape == ref["expert_load"].shape == (3, 4, 8)
    assert got["router_bias"].shape == ref["router_bias"].shape == (4, 16)
    moves = np.asarray(got["router_bias"]) / 0.001
    np.testing.assert_allclose(moves, np.round(moves), atol=1e-3)
    assert 0 < np.abs(moves).max() <= 3 and t.dropped == 0
    assert len(t.bias_spread) == 3 and np.all(np.asarray(t.bias_spread[-1]) > 0)
    assert train_o2_afmoe.bias_gap(got, ref, t.ref_dims, 3) < 0.1
    assert train_o2_afmoe.load_gap(got, ref) < 0.05
    limits = TOY_AFMOE["limits"]
    same = train_o2_afmoe.compare(ref, ref, limits)
    names = [n.split("@")[0].split(".step")[0] for n, _, _ in same]
    assert names == ["loss_gap"] * 3 + ["first_gradient_norm_gap",
                                        "first_gradient_projection_gap", "moved_norm_gap"]
    assert all(v == 0 for _, v, _ in same)
    assert train_o2_afmoe.load_gap(ref, ref) == 0.0
    assert train_o2_afmoe.bias_gap(ref, ref, t.ref_dims, 3) == 0.0
    rows = train_o2_afmoe.compare(low, ref, limits)
    assert any(value > limit for _, value, limit in rows)
    assert train_o2_afmoe.load_gap(low, ref) > 0.0


def test_pinned_routing_takes_the_near_ties_out_of_the_gradient_gap():
    """``readings_afmoe.py`` on the toy: with every token's experts pinned to
    the float32 reference's choice the program's first gradient comes far
    closer to the reference's than on its own routing, the float8 control's
    stays apart, and a bias left at rest reads a quarter or more."""
    from apex_tpu.parallel import mesh as mesh_lib
    from benchmarks.tests import readings_afmoe
    ctx = _ctx(5)
    got = {}
    try:
        readings_afmoe.readings(train_o2_afmoe.Trainer(ctx), ctx, 1, 1, 5,
                                lambda seed, who, number, value: got.update(
                                    {(who, number.split("@")[0]): value}))
    finally:
        mesh_lib.destroy_model_parallel()
    gap = "first_gradient_projection_gap"
    assert got["program_pinned", gap] < 0.5 * got["program", gap]
    assert got["program_pinned", gap + ".experts"] < 0.5 * got["program", gap + ".experts"]
    assert got["control_pinned", gap] > 3 * got["program_pinned", gap]
    assert got["control", "held_load_gap"] > got["program", "held_load_gap"] > 0
    assert got["bias_left_at_rest", "router_bias_gap"] > 0.2 > got["program", "router_bias_gap"]


# --- readers on names as the chip spells them ---------------------------------

TAIL = ', custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
Q = "bf16[2,8192,4096]{2,1,0}"
WIN_FWD = f"%flash_fwd_bshd_win.4 = ({Q}, f32[2,32,8192,8]{{3,2,1,0}}) custom-call({Q} %q)" + TAIL
WIN_DQ = f"%flash_bwd_bshd_win_dq.2 = {Q} custom-call({Q} %q)" + TAIL
WIN_DKV = f"%flash_bwd_bshd_win_dkv.2 = (f32[2,8192,4096]{{2,1,0}}) custom-call({Q} %q)" + TAIL
FULL_FWD = f"%flash_fwd_bshd.1 = ({Q}, f32[2,32,8192,8]{{3,2,1,0}}) custom-call({Q} %q)" + TAIL
FULL_DQ = f"%flash_bwd_bshd_dq.1 = {Q} custom-call({Q} %q)" + TAIL
GMM = "%moe_gmm.5 = bf16[16384,2048]{1,0} custom-call(s32[128]{0} %a, bf16[16384,2048]{1,0} %b)" + TAIL
GMM_DW = "%moe_gmm_dw.7 = bf16[16,2048,2048]{2,1,0} custom-call(s32[128]{0} %a, bf16[16384,2048]{1,0} %b)" + TAIL
FUSION = "%fusion.263 = bf16[16384,2048]{1,0} fusion(bf16[16384,2048]{1,0} %p), kind=kOutput"


def cell_dims():
    config = run.load_json(os.path.join(HERE, "configs", "trinity-mini-train1.json"))
    d = afmoe_ref.dims(config)
    return dict(d, **afmoe_tree.attention_view(d, 8192))


def cell_run(events, steps, loads):
    text = (plane("/device:TPU:0", "XLA Ops", events, 1)
            + plane("/host:CPU", "python", [(0, 10, "bench_step")], 2))
    trace = tr.reduce(ProfileData.from_text_proto(text))
    r = {"trace": trace, "step_s": [0.5] * steps, "steps": 40, "tokens": 40 * 16384,
         "window_s": 20.0, "chips": 1, "seq": 8192, "dims": cell_dims(), "peaks": PEAKS,
         "expert_load": loads}
    return dict(r, train_flops_per_token=afmoe_work.window_flops_per_token(r),   # as the adapter
                expert_matmul_work=hybrid_work.window_expert_matmul_work(
                    r, view=afmoe_work.expert_view))


def read(name, r):
    return run.load_reader(name).read(r)


def even_loads(steps=40):
    return np.full((steps, 4, 16), 1024)      # 16,384 local assignments a layer and step


def test_new_readers_on_names_as_the_chip_spells_them():
    ms = 1_000_000
    events = [(0, 48 * ms, WIN_FWD), (48 * ms, 80 * ms, WIN_DQ), (80 * ms, 120 * ms, WIN_DKV),
              (120 * ms, 144 * ms, FULL_FWD), (144 * ms, 174 * ms, FULL_DQ),
              (174 * ms, 184 * ms, GMM), (184 * ms, 194 * ms, GMM_DW),
              (194 * ms, 300 * ms, FUSION)]
    loads = even_loads()
    loads[:, :, 0] = 1536                     # one expert half as full again
    r = cell_run(events, steps=2, loads=loads)
    assert read("flash_win_fwd_ms", r) == pytest.approx(24.0)
    assert read("flash_win_bwd_ms", r) == pytest.approx(36.0)
    assert read("moe_gmm_ms", r) == pytest.approx(10.0)
    # four banded layers x 16,384 tokens x 4 x 4,096 x 1,792.125 keys = 1.924 TFLOP: 9.768 ms
    # at 197 TFLOP/s (their bytes take 1.5 ms); backward twice that
    assert read("flash_win_fwd_roofline_pct", r) == pytest.approx(100 * 9.768 / 24.0, rel=1e-3)
    assert read("flash_win_bwd_roofline_pct", r) == pytest.approx(100 * 19.536 / 36.0, rel=1e-3)
    n = loads[0].sum()
    ops, nbytes = afmoe_hand_expert_work(n)
    assert read("moe_gmm_roofline_pct", r) == pytest.approx(
        100 * 1e3 * max(ops / 197e12, nbytes / 819e9) / 10.0)
    assert read("moe_load_max_over_mean", r) == pytest.approx(1536 / 1056.0)
    # the whole step's share, by hand: required operations a token at the counted local
    # assignments, times 40 steps of 16,384 tokens in 20 s, over the bf16 peak
    assert read("mfu_pct", r) == pytest.approx(
        100 * afmoe_work.train_flops_per_token(r["dims"], 8192, n / 16384) * 40 * 16384 / 20.0
        / 197e12)
    assert 0 < read("mfu_pct", r) < 100
    # a run whose adapter hands no count, or no work, reads as nothing
    bare = {k: v for k, v in r.items() if k not in ("train_flops_per_token", "expert_matmul_work")}
    assert read("mfu_pct", bare) is None and read("moe_gmm_roofline_pct", bare) is None
    # the accepted flash shares list no cells: they read this cell through the
    # attention view, banded and unbanded kernels together against the
    # required work of 2.75 full causal layers (9.768 + 5.582 = 15.35 ms forward)
    assert read("flash_fwd_ms", r) == pytest.approx(36.0)
    assert read("flash_bwd_ms", r) == pytest.approx(51.0)
    assert read("flash_fwd_roofline_pct", r) == pytest.approx(100 * 15.350 / 36.0, rel=1e-3)
    assert read("flash_bwd_roofline_pct", r) == pytest.approx(100 * 30.700 / 51.0, rel=1e-3)
    for name in NEW_METRICS + ("mfu_pct", "flash_fwd_roofline_pct", "flash_bwd_roofline_pct"):
        if name.endswith("_pct"):
            assert 0 <= read(name, r) <= 100, name   # a share over 100 % is a miscount


def afmoe_hand_expert_work(assignments):
    ops = 3 * 6 * 2048 * 1024 * assignments
    nbytes = 3 * 4 * 16 * 3 * 2048 * 1024 * 2 + 3 * assignments * 2 * 2048 * 2
    return ops, nbytes


def test_a_wrong_layer_count_would_read_over_its_roofline():
    """Why the view hands 2.75 and not 5: the kernels at the band's pace
    against five full causal layers' work read an impossible share."""
    ms = 1_000_000
    r = cell_run([(0, 30 * ms, WIN_FWD), (30 * ms, 40 * ms, FULL_FWD)], steps=2,
                 loads=even_loads())
    assert read("flash_fwd_roofline_pct", r) < 100
    wrong = dict(r, dims=dict(r["dims"], n_layer=5))
    assert read("flash_fwd_roofline_pct", wrong) > 100


def test_new_readers_find_nothing_in_a_program_that_lacks_the_names():
    """The parent's program on this PR's benchmark files: no such kernels, no
    counters, another model's dims — every new reader returns ``None``."""
    from benchmarks.reference import gpt_ref
    sc1b = gpt_ref.dims(run.load_json(os.path.join(HERE, "configs", "starcoderbase-1b-train1.json")))
    r = cell_run([(0, 5, FULL_FWD), (5, 9, FUSION)], steps=1, loads=None)
    r = {k: v for k, v in dict(r, dims=sc1b).items() if k != "expert_load"}
    names = NEW_METRICS + SHARED_METRICS
    assert [read(name, r) for name in names] == [None] * len(names)
    assert [read(name, dict(r, trace=None)) for name in names] == [None] * len(names)


def test_required_work_by_hand():
    d = cell_dims()
    assert afmoe_work.mean_keys(8192) == 4096.5
    assert afmoe_work.mean_keys(8192, 2048) == 1792.125
    assert afmoe_work.mean_keys(1024, 2048) == 512.5            # a window past the row
    assert d["n_layer"] == pytest.approx(1 + 4 * 1792.125 / 4096.5) == pytest.approx(2.75, abs=1e-3)
    ops, nbytes = afmoe_work.attention_work(d, 8192, 16384, "window")
    assert ops == 4 * 16384 * 4 * 4096 * 1792.125
    assert nbytes == 4 * 16384 * (2 * 2 * (4096 + 512) + 4 * 32)
    back_ops, back_bytes = afmoe_work.attention_work(d, 8192, 16384, "window", backward=True)
    assert back_ops == 2 * ops and back_bytes == 4 * 16384 * (2 * 4 * (4096 + 512) + 4 * 32)
    assert afmoe_work.attention_work(d, 8192, 16384, "full")[0] == 16384 * 4 * 4096 * 4096.5
    # every parameter that multiplies a token, at 1.0 local assignments a token and layer
    assert afmoe_work.matmul_params_per_token(d, 4.0) == pytest.approx(
        5 * 27.263e6 + 37.749e6 + 4 * (0.262e6 + 6.291e6) + 4 * 6.291e6 + 25024 * 2048, rel=1e-3)
    # 2 x 276.7 M of weights and 184.6 MFLOP of attention forward, times 3: 2.214 GFLOP a token
    assert afmoe_work.train_flops_per_token(d, 8192, 4.0) == pytest.approx(2.2139e9, rel=1e-4)
    assert afmoe_work.expert_view(d)["num_hidden_layers"] == 4


def check_manifest(m):
    """The cell's entries as members of the manifest's lists (``test_harness.check_cell``),
    and what is this cell's alone."""
    _, config, _, reported = test_harness.check_cell(
        m, CELL, "trinity-mini-train1", NEW_METRICS + SHARED_METRICS)
    assert "gdn_fwd_ms" not in reported
    return config



def test_manifest_holds_the_new_cell_and_its_metrics():
    config = check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    published = {"num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128,
                 "vocab_size": 200192}
    assert config["published"] == published and config["reduced"] == list(published)
    assert (config["num_hidden_layers"], config["num_dense_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 1, 16, 25024)
    assert (config["hidden_size"], config["head_dim"], config["moe_intermediate_size"],
            config["intermediate_size"], config["num_experts_per_tok"], config["sliding_window"],
            config["router_num_experts"]) == (2048, 128, 1024, 6144, 8, 2048, 128)
    assert len(config["layer_types"]) == 32                       # the published list, whole
    d = afmoe_ref.dims(config)
    assert d["layer_types"] == ("window", "window", "window", "window", "full")
    assert d["ffn_types"] == ("dense", "moe", "moe", "moe", "moe")
    assert d["experts_held"] == (0, 16) and d["vocab_rows"] == 25088
    # every number of the catalog row's config that is not reduced, as published
    catalog = {"global_attn_every_n_layers": 4, "hidden_size": 2048, "load_balance_coeff": 0.001,
               "max_position_embeddings": 131072, "n_group": 1, "num_attention_heads": 32,
               "num_key_value_heads": 4, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
               "rope_theta": 10000, "route_scale": 2.826, "topk_group": 1}
    assert {k: config[k] for k in catalog} == catalog
