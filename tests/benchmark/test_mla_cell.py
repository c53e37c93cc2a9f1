"""The ``deepseek_v2`` decoder's cell on the CPU at a toy size: the new adapter
through the harness's own ``execute`` (a sound run is correct and hands back
the load counters; the float8 control fails the comparison), the new readers
on a hand-made trace spelt as the chip spells it (a share over 100 % fails
here, the accepted flash shares among them), the required work by hand, and
the manifest's new entries."""
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

from benchmarks import hybrid_work, mla_work, run, trace_reduce as tr  # noqa: E402
from benchmarks.adapters import mla_tree, train_o2_mla  # noqa: E402
from benchmarks.reference import mla_ref  # noqa: E402
from benchmarks.tests import test_harness, toy  # noqa: E402
from benchmarks.tests.test_trace_reduce import plane  # noqa: E402

HERE = os.path.join(ROOT, "benchmarks")
PEAKS = run.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
CELL = "dsv2lite-train-8k"
# the cell brought no reader of its own: what it reports stands under names it shares
# with other cells, whose lists hold it
SHARED_METRICS = ("mfu_pct", "moe_gmm_ms", "moe_gmm_roofline_pct", "moe_load_max_over_mean")
# the cell's cut at a toy size: the leading dense layer and two expert layers,
# 16 experts top-4 with a share of 4 held, a yarn ramp inside rows of 64
TOY_MLA = {
    "name": "toy-mla", "adapter": "train_o2_mla",
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "kv_lora_rank": 32, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "intermediate_size": 128, "moe_intermediate_size": 64, "n_routed_experts": 4,
    "num_experts_per_tok": 4, "n_shared_experts": 2, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "scoring_func": "softmax", "seq_aux": True,
    "aux_loss_alpha": 0.001, "rms_norm_eps": 1e-6, "vocab_size": 256, "q_lora_rank": None,
    "topk_method": "greedy", "layers_kept": [0, 1, 2], "router_num_experts": 16,
    "experts_held_first": 4,
    "engine": {"rows_per_chip": 2, "lr": 3e-4, "remat": True, "check_steps": 3,
               "trace_steps": 2},
    # the projection separates at this size: sound runs read 0.007-0.013, the
    # float8 control 0.05-0.06
    "limits": {"loss_gap": 0.01, "first_gradient_norm_gap": 0.04,
               "first_gradient_projection_gap": 0.03, "moved_norm_gap": 0.3,
               "held_load_gap": 0.05},
}


def manifest():
    m = toy.manifest()
    m["workloads"] = [{"name": "toy-mla-cell", "config": "toy-mla",
                       "traffic": "toy-docs", "chips": 1}]
    m["per_layer"] += [{"name": n, "unit": "x", "moves": "train_tokens_per_s"}
                       for n in ("moe_load_max_over_mean", "moe_gmm_ms")]
    return m


@pytest.fixture
def here(tmp_path):
    (tmp_path / "traffic").mkdir()
    os.symlink(os.path.join(HERE, "layer_metrics"), tmp_path / "layer_metrics")
    mix = toy.TOY_TRAIN_MIX
    (tmp_path / "traffic" / (mix["name"] + ".json")).write_text(json.dumps(mix))
    return str(tmp_path)


def test_traced_rehearsal_is_correct_and_hands_back_the_counters(here, monkeypatch):
    rows = []
    monkeypatch.setattr(run, "log", rows.append)
    m = manifest()
    line = run.execute(m, m["workloads"][0], TOY_MLA, toy.args(seed=2**31 + 7, trace=1),
                       jax.devices()[:1], PEAKS, here=here)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    # at most the 4 held experts' whole load on one
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] <= 4.0
    assert 0.0 < line["metrics"]["mfu_pct"]["value"] < 100.0
    assert "moe_gmm_ms" not in line["metrics"]            # no device in a CPU trace
    checked = [r.split()[1] for r in rows if r.startswith("check:") and "limit" in r]
    assert {"dropped_assignments", "held_load_gap", "compilations_inside_window",
            "first_gradient_projection_gap"} <= set(checked)
    json.dumps(line)


def _ctx(seed):
    import importlib
    mix = toy.TOY_TRAIN_MIX
    return {"config": TOY_MLA, "mix": mix, "seed": seed, "seconds": 1.0, "chips": 1,
            "log": lambda m: None,
            "generator": importlib.import_module("benchmarks.generators." + mix["generator"])}


def test_first_steps_count_the_loads_and_the_float8_control_fails():
    """What ``readings_mla.py`` drives: the program's first steps hand back
    the held experts' loads; the reference against itself passes every row by
    name; computed in float8 it fails at least one limit."""
    from apex_tpu.parallel import mesh as mesh_lib
    ctx = _ctx(3)
    t = train_o2_mla.Trainer(ctx)
    try:
        assert {"Trainer", "first_steps", "reference_readings", "compare", "leaf_gaps",
                "load_gap", "ALL_NUMBERS", "setup", "measure", "finish"} <= set(dir(train_o2_mla))
        train_o2_mla.first_steps(t, ctx)
        t.stop_feed()
        t.state = None
        ref = train_o2_mla.reference_readings(t, ctx)
        low = train_o2_mla.reference_readings(t, ctx, precision="float8")
    finally:
        mesh_lib.destroy_model_parallel()
    got = t.readings
    assert got["expert_load"].shape == ref["expert_load"].shape == (3, 2, 4)
    assert t.dropped == 0 and got["expert_load"].sum() > 0
    assert train_o2_mla.load_gap(got, ref) < 0.05
    limits = TOY_MLA["limits"]
    assert all(value <= limit for _, value, limit in train_o2_mla.compare(got, ref, limits))
    same = train_o2_mla.compare(ref, ref, limits)
    names = [n.split("@")[0].split(".step")[0] for n, _, _ in same]
    assert names == ["loss_gap"] * 3 + ["first_gradient_norm_gap",
                                        "first_gradient_projection_gap", "moved_norm_gap"]
    assert all(v == 0 for _, v, _ in same) and train_o2_mla.load_gap(ref, ref) == 0.0
    rows = train_o2_mla.compare(low, ref, limits)
    assert any(value > limit for _, value, limit in rows)
    assert train_o2_mla.load_gap(low, ref) > 0.0


def test_a_program_without_the_latent_mixer_refuses_the_cell_at_once(monkeypatch):
    """The parent's program under this PR's benchmark files: its
    configuration knows no latent layer, and the adapter asks it before it
    asks for a mesh or a chip — the parent exits on the cell, it does not hang."""
    from apex_tpu import models
    from apex_tpu.parallel import mesh as mesh_lib

    def parent_config(**kw):
        if "qk_nope_dim" in kw:
            raise TypeError("HybridDecoderConfig.__init__() got an unexpected keyword "
                            "argument 'qk_nope_dim'")
    monkeypatch.setattr(models, "HybridDecoderConfig", parent_config)
    monkeypatch.setattr(mesh_lib, "initialize_model_parallel",
                        lambda **kw: pytest.fail("asked for a mesh first"))
    with pytest.raises(TypeError, match="qk_nope_dim"):
        train_o2_mla.Trainer(_ctx(1))


# --- readers on names as the chip spells them ---------------------------------

TAIL = ', custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
Q = "bf16[2,8192,2048]{2,1,0}"
MLA_FWD = (f"%flash_fwd_bshd_mla.3 = ({Q}, f32[2,16,8192,8]{{3,2,1,0}}) custom-call({Q} %q, "
           "bf16[2,1,8192,64]{3,2,1,0} %k2)" + TAIL)
MLA_BWD = (f"%flash_bwd_bshd_mla_fused.3 = ({Q}, {Q}, {Q}, bf16[2,16,8192,64]{{3,2,1,0}}, "
           f"bf16[2,1,8192,64]{{3,2,1,0}}) custom-call({Q} %q)" + TAIL)
GMM = "%moe_gmm.5 = bf16[16384,2816]{1,0} custom-call(s32[128]{0} %a, bf16[16384,2048]{1,0} %b)" + TAIL
GMM_DW = "%moe_gmm_dw.7 = bf16[8,2048,2816]{2,1,0} custom-call(s32[128]{0} %a, bf16[16384,2048]{1,0} %b)" + TAIL
FUSION = "%fusion.263 = bf16[16384,2048]{1,0} fusion(bf16[16384,2048]{1,0} %p), kind=kOutput"


def cell_dims():
    config = run.load_json(os.path.join(HERE, "configs", "deepseek-v2-lite-train1.json"))
    d = mla_ref.dims(config)
    return dict(d, **mla_tree.attention_view(d))


def cell_run(events, steps, loads):
    text = (plane("/device:TPU:0", "XLA Ops", events, 1)
            + plane("/host:CPU", "python", [(0, 10, "bench_step")], 2))
    trace = tr.reduce(ProfileData.from_text_proto(text))
    r = {"trace": trace, "step_s": [0.6] * steps, "steps": 32, "tokens": 32 * 16384,
         "window_s": 20.0, "chips": 1, "seq": 8192, "dims": cell_dims(), "peaks": PEAKS,
         "expert_load": loads}
    return dict(r, train_flops_per_token=mla_work.window_flops_per_token(r),   # as the adapter
                expert_matmul_work=hybrid_work.window_expert_matmul_work(
                    r, view=mla_work.expert_view))


def read(name, r):
    return run.load_reader(name).read(r)


def even_loads(steps=32):
    return np.full((steps, 5, 8), 1536)       # 12,288 local assignments a layer and step


def hand_expert_work(assignments):
    ops = 3 * 6 * 2048 * 1408 * assignments
    nbytes = 3 * 5 * 8 * 3 * 2048 * 1408 * 2 + 3 * assignments * 2 * 2048 * 2
    return ops, nbytes


def test_new_readers_on_names_as_the_chip_spells_them():
    ms = 1_000_000
    events = [(0, 80 * ms, MLA_FWD), (80 * ms, 240 * ms, MLA_BWD), (240 * ms, 260 * ms, GMM),
              (260 * ms, 280 * ms, GMM_DW), (280 * ms, 400 * ms, FUSION)]
    loads = even_loads()
    loads[:, :, 0] = 2304                     # one expert half as full again
    r = cell_run(events, steps=2, loads=loads)
    assert read("moe_gmm_ms", r) == pytest.approx(20.0)
    n = loads[0].sum()
    ops, nbytes = hand_expert_work(n)
    assert read("moe_gmm_roofline_pct", r) == pytest.approx(
        100 * 1e3 * max(ops / 197e12, nbytes / 819e9) / 20.0)
    assert read("moe_load_max_over_mean", r) == pytest.approx(2304 / 1632.0)
    # 32 steps of 16,384 tokens in 20 s at 2.5345 GFLOP a token (0.7969 local
    # assignments a token and layer) over 197 TFLOP/s
    assert read("mfu_pct", r) == pytest.approx(
        100 * mla_work.train_flops_per_token(r["dims"], 8192, n / 16384) * 32 * 16384 / 20.0
        / 197e12)
    assert 30 < read("mfu_pct", r) < 40
    # the accepted flash times and shares list no cells: they read this cell's
    # two-width kernels through the attention view. Six layers x 16,384 tokens
    # x 16 heads x 640 x 4,096.5 keys = 4.124 TFLOP forward: 20.93 ms at 197
    # TFLOP/s (the bytes take 2.5 ms); backward twice that
    assert read("flash_fwd_ms", r) == pytest.approx(40.0)
    assert read("flash_bwd_ms", r) == pytest.approx(80.0)
    assert read("flash_fwd_roofline_pct", r) == pytest.approx(100 * 20.932 / 40.0, rel=1e-3)
    assert read("flash_bwd_roofline_pct", r) == pytest.approx(100 * 41.864 / 80.0, rel=1e-3)
    for name in SHARED_METRICS + ("flash_fwd_roofline_pct", "flash_bwd_roofline_pct"):
        if name.endswith("_pct"):
            assert 0 <= read(name, r) <= 100, name   # a share over 100 % is a miscount
    # a run whose adapter hands no count, or no work, reads as nothing
    bare = {k: v for k, v in r.items() if k not in ("train_flops_per_token", "expert_matmul_work")}
    assert read("mfu_pct", bare) is None and read("moe_gmm_roofline_pct", bare) is None
    # the banded readers and the other blocks' twins find nothing here
    for name in ("flash_win_fwd_ms", "flash_win_bwd_roofline_pct", "gdn_fwd_ms"):
        assert read(name, r) is None


def test_required_work_is_never_counted_at_the_padded_width():
    """The kernel pads the 64 rotary features to a 128-lane tile in VMEM (768
    operations a score pair where 640 are required): a view that handed the
    padded width would credit the kernels with a fifth more than they owe."""
    d = cell_dims()
    assert d["head_dim"] == 160 and d["n_embd"] == 16 * 160 and d["n_layer"] == 6
    assert mla_work.attention_ops_per_token(d, 8192) == 16 * 640 * 4096.5
    ms = 1_000_000
    r = cell_run([(0, 44 * ms, MLA_FWD)], steps=2, loads=even_loads())
    assert read("flash_fwd_roofline_pct", r) < 100
    padded = dict(r, dims=dict(r["dims"], n_embd=16 * 192, head_dim=192))
    assert read("flash_fwd_roofline_pct", padded) > 100


def test_new_readers_find_nothing_in_a_program_that_lacks_the_names():
    """Another block's run on this PR's benchmark files: no such counters,
    another model's dims — every new reader returns ``None``."""
    from benchmarks.reference import gpt_ref
    sc1b = gpt_ref.dims(run.load_json(os.path.join(HERE, "configs", "starcoderbase-1b-train1.json")))
    r = cell_run([(0, 5, MLA_FWD), (5, 9, FUSION)], steps=1, loads=None)
    r = {k: v for k, v in dict(r, dims=sc1b).items() if k != "expert_load"}
    names = SHARED_METRICS
    assert [read(name, r) for name in names] == [None] * len(names)
    assert [read(name, dict(r, trace=None)) for name in names] == [None] * len(names)


def test_required_work_by_hand():
    d = cell_dims()
    # one mixer: q 6.29 M, kv-down 1.18 M, kv-up 2.10 M, o 4.19 M
    assert mla_work.attention_params(d) == 6291456 + 1179648 + 2097152 + 4194304 == 13762560
    # at the expected 0.75 local assignments a token and expert layer
    assert mla_work.matmul_params_per_token(d, 3.75) == (
        6 * 13762560 + 3 * 2048 * 10944 + 5 * (2048 * 64 + 3 * 2048 * 2816)
        + 3.75 * 3 * 2048 * 1408 + 12800 * 2048) == 295632896
    # 2 x 295.6 M of weights and 251.7 MFLOP of attention forward: 843 MFLOP a token
    forward = 2 * 295632896 + 6 * 16 * 640 * 4096.5
    assert forward == pytest.approx(842.95e6, rel=1e-4)
    assert mla_work.train_flops_per_token(d, 8192, 3.75) == 3 * forward
    assert 6 * 16 * 640 * 4096.5 / forward == pytest.approx(0.299, abs=1e-3)   # the scores' share
    assert (6 * 2 * 13762560 + 6 * 16 * 640 * 4096.5) / forward == pytest.approx(0.49, abs=5e-3)
    view = mla_work.expert_view(d)
    assert (view["num_hidden_layers"], view["moe_intermediate_size"], view["experts_held"]) == (
        5, 1408, (0, 8))
    assert hybrid_work.expert_matmul_work(view, 61440, passes=3) == hand_expert_work(61440)
    # the tree map is a relabelling: nothing is lost or doubled
    w = jax.eval_shape(lambda k: mla_ref.make_weights(d, k), jax.ShapeDtypeStruct((2,), np.uint32))
    p = jax.eval_shape(mla_tree.to_program, w)
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(w) == count(p) == pytest.approx(635.4e6, rel=1e-3)
    assert p["layers"]["mla"]["w_q"].shape == (6, 2048, 3072)
    assert p["layers"]["moe"]["w_gate_up"].shape == (5, 8, 2048, 2816)


def check_manifest(m):
    """The cell's entries as members of the manifest's lists (``test_harness.check_cell``),
    and what is this cell's alone."""
    cell, config, entry, reported = test_harness.check_cell(
        m, CELL, "deepseek-v2-lite-train1", SHARED_METRICS)
    assert "1/8" in cell["why"] and not {"gdn_fwd_ms", "flash_win_fwd_ms"} & reported
    return config, entry



def test_manifest_holds_the_new_cell_and_its_metrics():
    config, entry = check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    published = {"num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 102400}
    assert config["published"] == published and config["reduced"] == list(published)
    assert entry["reduced"] == list(published)
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        6, 8, 12800)
    for key in ("reduced_why", "assumed", "deployment", "limits_why", "aot_memory"):
        assert config[key], key
    assert set(config["limits_why"]) >= set(config["limits"])
    d = mla_ref.dims(config)
    assert d["ffn_types"] == ("dense",) + ("moe",) * 5
    assert d["experts_held"] == (0, 8) and d["vocab_rows"] == 12800
    assert d["router_num_experts"] == 64
    # every number of the catalog row's config that is not reduced, as published
    catalog = {"first_k_dense_replace": 1, "hidden_size": 2048, "intermediate_size": 10944,
               "kv_lora_rank": 512, "max_position_embeddings": 163840,
               "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
               "n_shared_experts": 2, "num_attention_heads": 16, "num_experts_per_tok": 6,
               "num_key_value_heads": 16, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
               "rms_norm_eps": 1e-06, "rope_theta": 10000, "routed_scaling_factor": 1,
               "topk_group": 1, "v_head_dim": 128}
    assert {k: config[k] for k in catalog} == catalog
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    assert config["q_lora_rank"] is None
