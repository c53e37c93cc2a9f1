"""The hybrid decoder's cell on the CPU at a toy size: the new adapter
through the harness's own ``execute`` (a sound run is correct and hands back
the load counters; the float8 control fails the comparison), the new readers
on a hand-made trace spelt as the chip spells it (a share over 100 % fails
here), the required work by hand, and the manifest's new entries."""
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

from benchmarks import hybrid_work, run, trace_reduce as tr  # noqa: E402
from benchmarks.adapters import train_o2_hybrid  # noqa: E402
from benchmarks.reference import hybrid_ref  # noqa: E402
from benchmarks.tests import test_harness, toy  # noqa: E402
from benchmarks.tests.test_trace_reduce import plane  # noqa: E402

HERE = os.path.join(ROOT, "benchmarks")
PEAKS = run.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
CELL = "q3next-train-8k"
NEW_METRICS = ("gdn_fwd_ms", "gdn_bwd_ms", "gdn_fwd_roofline_pct", "gdn_bwd_roofline_pct",
               "moe_gmm_ms", "moe_gmm_roofline_pct", "moe_load_max_over_mean")
# what the cell reports under names it shares with other cells: their lists hold it
SHARED_METRICS = ("mfu_pct",)
# two periods of (linear, full), 16 experts top-4 with a share of 8 held
TOY_HYBRID = {
    "name": "toy-hybrid", "adapter": "train_o2_hybrid",
    "hidden_size": 128, "num_hidden_layers": 4, "full_attention_interval": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "partial_rotary_factor": 0.25, "rope_theta": 1e7, "linear_num_key_heads": 1,
    "linear_num_value_heads": 2, "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "num_experts": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128,
    "rms_norm_eps": 1e-6, "vocab_size": 256, "norm_topk_prob": True,
    "router_num_experts": 16, "experts_held_first": 4,
    "engine": {"rows_per_chip": 2, "lr": 3e-4, "remat": True, "check_steps": 3,
               "trace_steps": 2},
    "limits": {"loss_gap": 0.01, "first_gradient_norm_gap": 0.02,
               "first_gradient_projection_gap": 0.03, "moved_norm_gap": 0.3,
               "held_load_gap": 0.05},
}


def manifest():
    m = toy.manifest()
    m["workloads"] = [{"name": "toy-hybrid-cell", "config": "toy-hybrid",
                       "traffic": "toy-docs", "chips": 1}]
    m["per_layer"] += [{"name": n, "unit": "x", "moves": "train_tokens_per_s"}
                       for n in ("moe_load_max_over_mean", "gdn_fwd_ms")]
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
    line = run.execute(m, m["workloads"][0], TOY_HYBRID, toy.args(seed=2**31 + 5, trace=1),
                       jax.devices()[:1], PEAKS, here=here)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] < 3.0
    assert 0.0 < line["metrics"]["mfu_pct"]["value"] < 100.0
    assert "gdn_fwd_ms" not in line["metrics"]          # no device in a CPU trace
    json.dumps(line)


def _ctx(seed):
    import importlib
    mix = toy.TOY_TRAIN_MIX
    return {"config": TOY_HYBRID, "mix": mix, "seed": seed, "seconds": 1.0, "chips": 1,
            "log": lambda m: None,
            "generator": importlib.import_module("benchmarks.generators." + mix["generator"])}


def test_compare_rows_and_the_float8_control():
    """The reference against itself passes every row by name; computed in
    float8 it fails at least one limit, as ``readings.py`` runs it."""
    from apex_tpu.parallel import mesh as mesh_lib
    ctx = _ctx(3)
    t = train_o2_hybrid.Trainer(ctx)
    try:
        assert {"Trainer", "first_steps", "reference_readings", "compare", "leaf_gaps",
                "ALL_NUMBERS", "setup", "measure", "finish"} <= set(dir(train_o2_hybrid))
        ref = train_o2_hybrid.reference_readings(t, ctx)
        low = train_o2_hybrid.reference_readings(t, ctx, precision="float8")
    finally:
        mesh_lib.destroy_model_parallel()
    limits = TOY_HYBRID["limits"]
    same = train_o2_hybrid.compare(ref, ref, limits)
    names = [n.split("@")[0].split(".step")[0] for n, _, _ in same]
    assert names == ["loss_gap"] * 3 + ["first_gradient_norm_gap",
                                        "first_gradient_projection_gap", "moved_norm_gap"]
    assert all(v == 0 for _, v, _ in same)
    assert ref["expert_load"].shape == (3, 4, 8) and train_o2_hybrid.load_gap(ref, ref) == 0.0
    rows = train_o2_hybrid.compare(low, ref, limits)
    assert any(value > limit for _, value, limit in rows)
    assert train_o2_hybrid.load_gap(low, ref) > 0.0


# --- readers on names as the chip spells them ---------------------------------

TAIL = ', custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
GDN_FWD = ("%gdn_fwd.3 = (bf16[64,8192,128]{2,1,0:T(8,128)(2,1)}, f32[64,16,128,128]{3,2,1,0}) "
           "custom-call(bf16[64,8192,128]{2,1,0} %fusion.11)" + TAIL)
GDN_BWD = ("%gdn_bwd.2 = (bf16[64,8192,128]{2,1,0}, bf16[64,8192,128]{2,1,0}) "
           "custom-call(bf16[64,8192,128]{2,1,0} %fusion.12)" + TAIL)
GMM = "%moe_gmm.5 = bf16[16384,1024]{1,0} custom-call(s32[128]{0} %a, bf16[16384,2048]{1,0} %b)" + TAIL
GMM_DX = "%moe_gmm_dx.1 = bf16[16384,2048]{1,0} custom-call(s32[128]{0} %a, bf16[16384,1024]{1,0} %b)" + TAIL
GMM_DW = "%moe_gmm_dw.7 = bf16[32,2048,1024]{2,1,0} custom-call(s32[128]{0} %a, bf16[16384,2048]{1,0} %b)" + TAIL
FLASH = "%flash_fwd_bshd.1 = bf16[2,8192,4096]{2,1,0} custom-call(bf16[2,8192,4096]{2,1,0} %q)" + TAIL
FUSION = "%fusion.263 = bf16[16384,2048]{1,0} fusion(bf16[16384,2048]{1,0} %p), kind=kOutput"


def cell_dims():
    config = run.load_json(os.path.join(HERE, "configs", "qwen3-next-80b-a3b-train1.json"))
    d = hybrid_ref.dims(config)
    from benchmarks.adapters import hybrid_tree
    return dict(d, **hybrid_tree.attention_view(d))


def cell_run(events, steps, loads):
    text = (plane("/device:TPU:0", "XLA Ops", events, 1)
            + plane("/host:CPU", "python", [(0, 10, "bench_step")], 2))
    trace = tr.reduce(ProfileData.from_text_proto(text))
    r = {"trace": trace, "step_s": [0.6] * steps, "steps": 30, "tokens": 30 * 16384,
         "window_s": 18.0, "chips": 1, "seq": 8192, "dims": cell_dims(), "peaks": PEAKS,
         "expert_load": loads}
    return dict(r, train_flops_per_token=hybrid_work.window_flops_per_token(r),   # as the adapter
                expert_matmul_work=hybrid_work.window_expert_matmul_work(r))


def read(name, r):
    return run.load_reader(name).read(r)


def even_loads(steps=30):
    return np.full((steps, 4, 32), 320)      # 10,240 local assignments a layer and step


def test_new_readers_on_names_as_the_chip_spells_them():
    ms = 1_000_000
    events = [(0, 40 * ms, GDN_FWD), (40 * ms, 140 * ms, GDN_BWD), (140 * ms, 144 * ms, GMM),
              (144 * ms, 147 * ms, GMM_DX), (147 * ms, 152 * ms, GMM_DW),
              (152 * ms, 172 * ms, FLASH), (172 * ms, 300 * ms, FUSION)]
    loads = even_loads()
    loads[:, :, 0] = 480                      # one expert half as full again
    r = cell_run(events, steps=2, loads=loads)
    assert read("gdn_fwd_ms", r) == pytest.approx(20.0)
    assert read("gdn_bwd_ms", r) == pytest.approx(50.0)
    assert read("moe_gmm_ms", r) == pytest.approx(6.0)
    # three layers x 16,384 tokens x 24.8 KB = 1.22 GB: 1.490 ms at 819 GB/s (the operations
    # take 0.785 ms); backward 2.44 GB: 2.981 ms
    assert read("gdn_fwd_roofline_pct", r) == pytest.approx(100 * 1.4903 / 20.0, rel=1e-3)
    assert read("gdn_bwd_roofline_pct", r) == pytest.approx(100 * 2.9806 / 50.0, rel=1e-3)
    n = loads[0].sum()
    ops, nbytes = hybrid_work.expert_matmul_work(r["dims"], n, passes=3)
    assert read("moe_gmm_roofline_pct", r) == pytest.approx(
        100 * 1e3 * max(ops / 197e12, nbytes / 819e9) / 6.0)
    assert read("moe_load_max_over_mean", r) == pytest.approx(480 / 325.0)
    # the whole step's share, by hand: required operations a token at the counted 2.54 local
    # assignments a token, times 30 steps of 16,384 tokens in 18 s, over the bf16 peak
    assert read("mfu_pct", r) == pytest.approx(
        100 * hybrid_work.train_flops_per_token(r["dims"], 8192, n / 16384) * 30 * 16384 / 18.0
        / 197e12)
    assert 0 < read("mfu_pct", r) < 100
    # a run whose adapter hands no count, or no work, reads as nothing
    bare = {k: v for k, v in r.items() if k not in ("train_flops_per_token", "expert_matmul_work")}
    assert read("mfu_pct", bare) is None and read("moe_gmm_roofline_pct", bare) is None
    # the accepted flash shares list no cells: they read this cell through the
    # attention layers' view of its dims (one layer of 16 heads of 256, 2 kv heads)
    assert read("flash_fwd_ms", r) == pytest.approx(10.0)
    assert read("flash_fwd_roofline_pct", r) == pytest.approx(100 * 5.582 / 10.0, rel=1e-3)
    for name in NEW_METRICS + SHARED_METRICS + ("flash_fwd_roofline_pct",):
        if name.endswith("_pct") or name.startswith("mfu"):
            assert 0 <= read(name, r) <= 100, name   # a share over 100 % is a miscount


def test_new_readers_find_nothing_in_a_program_that_lacks_the_names():
    """The parent's program on this PR's benchmark files: no such kernels, no
    counters, another model's dims — every new reader returns ``None``."""
    from benchmarks.reference import gpt_ref
    sc1b = gpt_ref.dims(run.load_json(os.path.join(HERE, "configs", "starcoderbase-1b-train1.json")))
    r = cell_run([(0, 5, FLASH), (5, 9, FUSION)], steps=1, loads=None)
    r = {k: v for k, v in dict(r, dims=sc1b).items() if k != "expert_load"}
    names = NEW_METRICS + SHARED_METRICS
    assert [read(name, r) for name in names] == [None] * len(names)
    assert [read(name, dict(r, trace=None)) for name in names] == [None] * len(names)


def test_required_work_by_hand():
    d = cell_dims()
    # per token and delta-rule layer: 32 value heads x 6 x 128 x 128
    assert hybrid_work.delta_rule_ops_per_token(d) == 3_145_728
    ops, nbytes = hybrid_work.delta_rule_work(d, 16384)
    assert ops == 3 * 16384 * 3_145_728
    assert nbytes == 3 * 16384 * (2 * (2 * 2048 + 2 * 4096) + 4 * 2 * 32)
    back_ops, back_bytes = hybrid_work.delta_rule_work(d, 16384, backward=True)
    assert back_ops == 2 * ops and back_bytes == 3 * 16384 * (2 * 4 * (2048 + 4096) + 4 * 4 * 32)
    # 0.625 local assignments a token and layer: 1.381 GFLOP a token
    assert hybrid_work.train_flops_per_token(d, 8192, 2.5) == pytest.approx(1.3814e9, rel=1e-4)
    # every parameter that multiplies a token, at all 32 experts' worth of assignments
    assert hybrid_work.matmul_params_per_token(d, 0.0) == pytest.approx(
        3 * 33.69e6 + 27.26e6 + 4 * 4.196e6 + 18992 * 2048, rel=2e-3)
    ops, nbytes = hybrid_work.expert_matmul_work(d, 4 * 10240, passes=3)
    assert ops == 3 * 6 * 2048 * 512 * 40960
    assert nbytes == 3 * 4 * 32 * 3 * 2048 * 512 * 2 + 3 * 40960 * 2 * 2048 * 2


def check_manifest(m):
    """The cell's entries as members of the manifest's lists (``test_harness.check_cell``),
    and what is this cell's alone."""
    _, config, _, reported = test_harness.check_cell(
        m, CELL, "qwen3-next-80b-a3b-train1", NEW_METRICS + SHARED_METRICS)
    assert "flash_win_fwd_ms" not in reported
    return config



def test_manifest_holds_the_new_cell_and_its_metrics():
    config = check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    published = {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert config["published"] == published and config["reduced"] == list(published)
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (4, 32, 18992)
    assert (config["hidden_size"], config["head_dim"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["router_num_experts"]) == (2048, 256, 512, 10, 512)
    d = hybrid_ref.dims(config)
    assert d["layer_types"] == ("linear", "linear", "linear", "full")
    assert d["experts_held"] == (0, 32) and d["rotary_dim"] == 64 and d["vocab_rows"] == 19200
