"""The ``bailing_hybrid`` decoder's cell on the CPU at a toy size: the new adapter
through the harness's own ``execute`` (a sound run is correct and hands back the
load counters, the group hits and the smallest log decay; the float8 control
fails the comparison), a program without the layer kind refuses the cell at
once, the new readers on a hand-made trace spelt as the chip spells it (a share
over 100 % fails here: six latent layers where there is one, or a rule counted
twice, are among the ways to get one), the required work by hand, and the
cell's entries of ``BENCHMARK.json`` as MEMBERS of their lists: a later cell
appended after them breaks nothing here."""
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

from benchmarks import bailing_work, hybrid_work, mla_work, run, trace_reduce as tr  # noqa: E402
from benchmarks.adapters import bailing_tree, train_o2_bailing  # noqa: E402
from benchmarks.reference import bailing_ref  # noqa: E402
from benchmarks.tests import test_harness, toy  # noqa: E402
from benchmarks.tests.test_trace_reduce import plane  # noqa: E402

HERE = os.path.join(ROOT, "benchmarks")
PEAKS = run.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
CELL, CONFIG = "ling3-train-8k", "ling-3.0-flash-train1"
NEW_METRICS = ("kda_fwd_ms", "kda_bwd_ms", "kda_fwd_roofline_pct", "kda_bwd_roofline_pct",
               "kda_block_ms", "kda_outside_kernels_ms", "route_group_hit_share")
# what the cell reports under names it shares with other cells: their lists hold it
SHARED_METRICS = ("mfu_pct", "attn_block_ms", "mlp_block_ms", "moe_block_ms", "moe_route_ms",
                  "moe_gmm_ms", "moe_gmm_roofline_pct", "moe_load_max_over_mean",
                  "unembed_xent_ms", "optimizer_ms", "recompute_ms", "unscoped_ms")
# the cell's cut at a toy size: published layers 1 (delta rule, dense) and 5 (latent,
# experts), heads of 16 (the XLA forms), 16 experts in 4 groups of which 2 stay, group 1 held
TOY_BAILING = {
    "name": "toy-bailing", "adapter": "train_o2_bailing",
    "hidden_size": 64, "num_hidden_layers": 2, "first_k_dense_replace": 2, "layer_group_size": 6,
    "num_attention_heads": 4, "head_dim": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "rope_theta": 6e6, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "intermediate_size": 96, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6, "vocab_size": 256,
    "score_function": "sigmoid", "q_lora_rank": None, "rope_scaling": None,
    "kda_safe_gate": True, "num_kv_heads_for_linear_attn": 0, "layers_kept": [1, 5],
    "router_num_experts": 16, "experts_held_first": 4, "router_bias_update_rate": 0.001,
    "engine": {"rows_per_chip": 2, "lr": 3e-4, "remat": True, "check_steps": 3,
               "trace_steps": 2},
    "limits": {"loss_gap": 0.01, "first_gradient_norm_gap": 0.04,
               "first_gradient_projection_gap": 0.1, "moved_norm_gap": 0.3,
               "held_load_gap": 0.05},
}


def manifest():
    m = toy.manifest()
    m["workloads"] = [{"name": "toy-bailing-cell", "config": "toy-bailing",
                       "traffic": "toy-docs", "chips": 1}]
    m["per_layer"] += [{"name": n, "unit": "x", "moves": "train_tokens_per_s"}
                       for n in ("moe_load_max_over_mean", "route_group_hit_share", "kda_fwd_ms",
                                 "moe_gmm_ms")]
    return m


@pytest.fixture
def here(tmp_path):
    (tmp_path / "traffic").mkdir()
    os.symlink(os.path.join(HERE, "layer_metrics"), tmp_path / "layer_metrics")
    mix = toy.TOY_TRAIN_MIX
    (tmp_path / "traffic" / (mix["name"] + ".json")).write_text(json.dumps(mix))
    return str(tmp_path)


def test_traced_rehearsal_is_correct_and_the_float8_control_is_not(here, monkeypatch):
    """One rehearsal through the harness's own ``execute``: a sound run is
    correct and hands back the counters; then, on the trainer and the float32
    readings the check just used, what a readings script drives: the reference
    against itself passes every row by name, computed in float8 it fails a
    limit."""
    rows, kept = [], {}
    monkeypatch.setattr(run, "log", rows.append)
    readings = train_o2_bailing.reference_readings

    def remembered(t, ctx, precision="float32"):
        kept["t"], kept[precision] = t, readings(t, ctx, precision)
        return kept[precision]

    monkeypatch.setattr(train_o2_bailing, "reference_readings", remembered)
    m = manifest()
    line = run.execute(m, m["workloads"][0], TOY_BAILING, toy.args(seed=2**31 + 11, trace=1),
                       jax.devices()[:1], PEAKS, here=here)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] <= 4.0
    assert 0.0 < line["metrics"]["mfu_pct"]["value"] < 100.0
    # the held experts are one group of four, of which a token keeps two
    assert 10.0 < line["metrics"]["route_group_hit_share"]["value"] < 90.0
    assert not {"kda_fwd_ms", "moe_gmm_ms"} & set(line["metrics"])   # no device in a CPU trace
    checked = [r.split()[1] for r in rows if r.startswith("check:") and "limit" in r]
    assert {"dropped_assignments", "held_load_gap", "compilations_inside_window",
            "first_gradient_projection_gap"} <= set(checked)
    assert "router_bias_gap" not in checked and any("router_bias_gap" in r for r in rows)
    assert any("smallest per-step log decay -4." in r and "0 local assignments dropped" in r
               for r in rows)
    json.dumps(line)
    assert {"Trainer", "first_steps", "reference_readings", "compare", "leaf_gaps", "load_gap",
            "bias_gap", "ALL_NUMBERS", "setup", "measure", "finish"} <= set(dir(train_o2_bailing))
    t, ref = kept["t"], kept["float32"]
    got, limits = t.readings, TOY_BAILING["limits"]
    assert got["expert_load"].shape == ref["expert_load"].shape == (3, 1, 4)
    assert got["router_bias"].shape == ref["router_bias"].shape == (1, 16)
    assert t.dropped == 0 and got["expert_load"].sum() > 0
    assert np.abs(got["router_bias"]).max() == pytest.approx(3 * 0.001, rel=1e-3)
    assert len(t.group_hit) == len(t.log_decay_min) >= 5 and -5.0 <= min(t.log_decay_min) < -4.0
    assert train_o2_bailing.bias_gap(got, ref, t.ref_dims, 3) < 0.1
    same = train_o2_bailing.compare(ref, ref, limits)
    names = [n.split("@")[0].split(".step")[0] for n, _, _ in same]
    assert names == ["loss_gap"] * 3 + ["first_gradient_norm_gap",
                                        "first_gradient_projection_gap", "moved_norm_gap"]
    assert all(v == 0 for _, v, _ in same) and train_o2_bailing.load_gap(ref, ref) == 0.0
    low = readings(t, _ctx(2**31 + 11), precision="float8")
    assert any(value > limit for _, value, limit in train_o2_bailing.compare(low, ref, limits))
    assert train_o2_bailing.load_gap(low, ref) > 0.0


def _ctx(seed):
    import importlib
    mix = toy.TOY_TRAIN_MIX
    return {"config": TOY_BAILING, "mix": mix, "seed": seed, "seconds": 1.0, "chips": 1,
            "log": lambda m: None,
            "generator": importlib.import_module("benchmarks.generators." + mix["generator"])}


def test_a_program_without_the_layer_kind_refuses_the_cell_at_once(monkeypatch):
    """The parent's program under this PR's benchmark files: its configuration
    knows no ``kda`` layer, and the adapter asks it before it asks for a mesh
    or a chip — the parent exits on the cell, it does not hang."""
    from apex_tpu import models
    from apex_tpu.parallel import mesh as mesh_lib

    def parent_config(**kw):
        if "kda_heads" in kw:
            raise TypeError("HybridDecoderConfig.__init__() got an unexpected keyword "
                            "argument 'kda_heads'")
    monkeypatch.setattr(models, "HybridDecoderConfig", parent_config)
    monkeypatch.setattr(mesh_lib, "initialize_model_parallel",
                        lambda **kw: pytest.fail("asked for a mesh first"))
    with pytest.raises(TypeError, match="kda_heads"):
        train_o2_bailing.Trainer(_ctx(1))


# --- readers on names as the chip spells them ---------------------------------

TAIL = ', custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
X = "bf16[2,8192,4096]{2,1,0}"
KDA_FWD = (f"%kda_fwd.3 = ({X}, f32[2,32,16,128,128]{{4,3,2,1,0}}) custom-call({X} %q, "
           "f32[2,8192,4096]{2,1,0} %g)" + TAIL)
KDA_BWD = f"%kda_bwd.3 = ({X}, {X}, {X}, f32[2,8192,4096]{{2,1,0}}) custom-call({X} %q)" + TAIL
CONV = "%conv_silu_fwd.9 = bf16[2,8192,4096]{2,1,0} custom-call(bf16[2,8192,12288]{2,1,0} %p)" + TAIL
FLASH = ("%flash_fwd_bshd_mla.2 = (bf16[2,8192,32,128]{3,2,1,0}, f32[2,32,8192,8]{3,2,1,0}) "
         "custom-call(bf16[2,8192,32,128]{3,2,1,0} %q)" + TAIL)
GMM = "%moe_gmm.5 = bf16[16384,1536]{1,0} custom-call(s32[128]{0} %a, bf16[16384,2560]{1,0} %b)" + TAIL
FUSION = "%fusion.263 = bf16[16384,2560]{1,0} fusion(bf16[16384,2560]{1,0} %p), kind=kOutput"
ROUTE, HEAD, ADAM, DENSE, REMAT, LOOSE = (FUSION.replace("263", n) for n in (
    "301", "302", "303", "304", "305", "306"))


def cell_dims():
    config = run.load_json(os.path.join(HERE, "configs", CONFIG + ".json"))
    d = bailing_ref.dims(config)
    return dict(d, **bailing_tree.attention_view(d))


def cell_run(events, steps, loads, table=None):
    text = (plane("/device:TPU:0", "XLA Ops", events, 1)
            + plane("/host:CPU", "python", [(0, 10, "bench_step")], 2))
    trace = tr.reduce(ProfileData.from_text_proto(text))
    r = {"trace": trace, "step_s": [0.5] * steps, "steps": 32, "tokens": 32 * 16384,
         "window_s": 26.0, "chips": 1, "seq": 8192, "dims": cell_dims(), "peaks": PEAKS,
         "expert_load": loads, "router_group_hit": np.full((32, 5), 0.5)}
    if table is not None:
        r["scope_table"] = table
    return dict(r, train_flops_per_token=bailing_work.window_flops_per_token(r),   # as the adapter
                expert_matmul_work=hybrid_work.window_expert_matmul_work(
                    r, view=mla_work.expert_view))


def read(name, r):
    return run.load_reader(name).read(r)


def even_loads(steps=32):
    return np.full((steps, 5, 8), 256)        # 2,048 local assignments a layer and step


def test_new_readers_on_names_as_the_chip_spells_them():
    ms = 1_000_000
    events = [(0, 80 * ms, KDA_FWD), (80 * ms, 330 * ms, KDA_BWD), (330 * ms, 340 * ms, CONV),
              (340 * ms, 350 * ms, GMM), (350 * ms, 390 * ms, FLASH), (390 * ms, 490 * ms, FUSION),
              (490 * ms, 498 * ms, ROUTE), (498 * ms, 504 * ms, HEAD), (504 * ms, 508 * ms, ADAM),
              (508 * ms, 520 * ms, DENSE), (520 * ms, 526 * ms, REMAT), (526 * ms, 528 * ms, LOOSE)]
    under = "jit(run)/amp/fwd_bwd/jvp(hybrid/kda)/"
    moe = "jit(run)/amp/fwd_bwd/jvp(hybrid/moe)/"
    table = {"kda_fwd.3": under + "kda_fwd", "kda_bwd.3": under.replace("jvp(", "transpose(jvp(")
             + ")kda_bwd", "conv_silu_fwd.9": under + "conv_silu_fwd",
             "fusion.263": under + "mix/proj_in/dot_general",
             "moe_gmm.5": moe + "moe/experts/moe_gmm", "fusion.301": moe + "moe/route/reduce",
             "flash_fwd_bshd_mla.2": "jit(run)/amp/fwd_bwd/jvp(hybrid/attn_mla)/flash_fwd_bshd_mla",
             "fusion.302": "jit(run)/amp/fwd_bwd/jvp(hybrid/unembed_xent)/dot_general",
             "fusion.303": "jit(run)/amp/apply_master/add",
             "fusion.304": "jit(run)/amp/fwd_bwd/jvp(hybrid/dense)/dot_general",
             "fusion.305": ("jit(run)/amp/fwd_bwd/transpose(jvp(amp/fwd_bwd))/checkpoint/"
                            "rematted_computation/hybrid/kda/mix/proj_in/dot_general"),
             "fusion.306": "jit(run)/copy"}
    loads = even_loads()
    loads[:, :, 0] = 384                      # one expert half as full again
    r = cell_run(events, steps=2, loads=loads, table=table)
    tokens = 16384
    assert read("kda_fwd_ms", r) == pytest.approx(40.0)
    assert read("kda_bwd_ms", r) == pytest.approx(125.0)
    ops, nbytes = bailing_work.rule_work(r["dims"], tokens)
    assert read("kda_fwd_roofline_pct", r) == pytest.approx(
        100 * 1e3 * max(ops / 197e12, nbytes / 819e9) / 40.0)
    ops_b, bytes_b = bailing_work.rule_work(r["dims"], tokens, backward=True)
    assert read("kda_bwd_roofline_pct", r) == pytest.approx(
        100 * 1e3 * max(ops_b / 197e12, bytes_b / 819e9) / 125.0)
    assert read("moe_gmm_ms", r) == pytest.approx(5.0)
    n = loads[0].sum()
    want = hybrid_work.expert_matmul_work(dict(r["dims"], num_hidden_layers=5), n, passes=3)
    assert want[0] == 3 * 6 * 2560 * 768 * n
    assert read("moe_gmm_roofline_pct", r) == pytest.approx(
        100 * 1e3 * max(want[0] / 197e12, want[1] / 819e9) / 5.0)
    assert read("moe_load_max_over_mean", r) == pytest.approx(384 / 272.0)
    assert read("route_group_hit_share", r) == pytest.approx(50.0)
    assert read("mfu_pct", r) == pytest.approx(
        100 * bailing_work.train_flops_per_token(r["dims"], 8192, n / tokens) * 32 * tokens / 26.0
        / 197e12)
    assert 30 < read("mfu_pct", r) < 40
    # everything traced under hybrid/kda, forward, backward and recomputed: both rule
    # kernels, the convolution, the projection's fusion and its second run
    assert read("kda_block_ms", r) == pytest.approx(40.0 + 125.0 + 5.0 + 50.0 + 3.0)
    assert read("kda_outside_kernels_ms", r) == pytest.approx(53.0)
    assert read("attn_block_ms", r) == pytest.approx(20.0)
    assert read("mlp_block_ms", r) == pytest.approx(6.0)
    assert read("moe_block_ms", r) == pytest.approx(5.0 + 4.0)
    assert read("moe_route_ms", r) == pytest.approx(4.0)
    assert read("unembed_xent_ms", r) == pytest.approx(3.0)
    assert read("optimizer_ms", r) == pytest.approx(2.0)
    assert read("recompute_ms", r) == pytest.approx(3.0)
    assert read("unscoped_ms", r) == pytest.approx(1.0)
    # the accepted flash times and shares list no cells: they read this cell's ONE latent
    # layer through the attention view, at 640 operations a score pair and head
    assert read("flash_fwd_ms", r) == pytest.approx(20.0)
    want = 16384 * 4 * 32 * 160 * 4096.5 / 197e12 * 1e3
    assert read("flash_fwd_roofline_pct", r) == pytest.approx(100 * want / 20.0, rel=1e-3)
    for name in NEW_METRICS + SHARED_METRICS + ("flash_fwd_roofline_pct",):
        if name.endswith("_pct"):
            assert 0 <= read(name, r) <= 100, name   # a share over 100 % is a miscount
    # a run whose adapter hands no count, or no work, reads as nothing
    bare = {k: v for k, v in r.items() if k not in ("train_flops_per_token", "expert_matmul_work")}
    assert read("mfu_pct", bare) is None and read("moe_gmm_roofline_pct", bare) is None
    # the other blocks' readers find nothing here
    for name in ("flash_win_fwd_ms", "gdn_fwd_ms", "ssd_fwd_ms", "flash_bwd_ms"):
        assert read(name, r) is None


def test_a_miscount_reads_over_100_per_cent():
    """At a time 2 % over the least the true shares read just under 100 %: the
    latent layers counted as the cell's six, or the rule's work doubled, read
    over it."""
    d = cell_dims()
    assert d["n_layer"] == 1 and d["n_embd"] == 32 * 160 and d["head_dim"] == 160
    ops, nbytes = bailing_work.rule_work(d, 16384)
    ns = int(2 * 1.02 * 1e9 * max(ops / 197e12, nbytes / 819e9))
    r = cell_run([(0, ns, KDA_FWD)], steps=2, loads=even_loads())
    assert 95 < read("kda_fwd_roofline_pct", r) < 100
    twice = dict(r, dims=dict(d, layer_types=d["layer_types"] + ("kda",) * 5))
    assert read("kda_fwd_roofline_pct", twice) > 100
    flash_ms = 16384 * 4 * 32 * 160 * 4096.5 / 197e12 * 1e3
    r = cell_run([(0, int(2 * 1.02 * flash_ms * 1e6), FLASH)], steps=2, loads=even_loads())
    assert 95 < read("flash_fwd_roofline_pct", r) < 100
    assert read("flash_fwd_roofline_pct", dict(r, dims=dict(d, n_layer=6))) > 500


def test_new_readers_find_nothing_in_a_program_that_lacks_the_names():
    """Another block's run on this PR's benchmark files: no such counters,
    another model's dims, no such kernels — every new reader returns ``None``
    and raises nothing."""
    from benchmarks.reference import gpt_ref
    sc1b = gpt_ref.dims(run.load_json(os.path.join(HERE, "configs", "starcoderbase-1b-train1.json")))
    r = cell_run([(0, 5, FLASH), (5, 9, FUSION)], steps=1, loads=None, table={})
    r = {k: v for k, v in dict(r, dims=sc1b).items()
         if k not in ("expert_load", "router_group_hit", "train_flops_per_token")}
    names = NEW_METRICS + SHARED_METRICS
    assert [read(name, r) for name in names] == [None] * len(names)
    assert [read(name, dict(r, trace=None)) for name in names] == [None] * len(names)


def test_required_work_by_hand():
    d = cell_dims()
    # the rule, a token and layer forward, a head: kk and qk over the causal half of a
    # chunk of 64 at 128 each, the solve against 256 right-hand sides, p v' at 128, and
    # the three state products at 128 x 128
    head = 2 * (2 * 128 * 32.5 + 256 * 32.5 + 128 * 32.5 + 3 * 128 * 128)
    assert bailing_work.rule_ops_per_token(d) == 32 * head == 4476928
    ops, nbytes = bailing_work.rule_work(d, 16384)
    per_token = 2 * 4 * 4096 + 4 * 4096 + 4 * 32 + 4 * 32 * 128 * 128 / 512
    assert ops == 5 * 16384 * 4476928 and nbytes == 5 * 16384 * per_token
    ops_b, bytes_b = bailing_work.rule_work(d, 16384, backward=True)
    assert ops_b == 2 * ops
    assert bytes_b == 5 * 16384 * (2 * 7 * 4096 + 8 * 4096 + 8 * 32 + 4 * 32 * 128 * 128 / 512)
    # bytes bound the rule: 4.4 GB are 5.4 ms, 367 GFLOP 1.9 ms
    assert nbytes / 819e9 > ops / 197e12
    kda = 2560 * 5 * 4096 + 2560 * 32 + 4096 * 2560
    latent = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 4096 * 2560 + 2560 * 32
    layer = 2560 * 512 + 3 * 2560 * 768
    assert (kda, latent, layer) == (62996480, 31965184, 7208960)
    assert bailing_work.kda_params(d) == kda
    # at the expected 0.125 local assignments a token and expert layer
    params = (5 * kda + latent + 3 * 2560 * 6144 + 5 * layer + 0.625 * 3 * 2560 * 768
              + 19648 * 2560)
    assert bailing_work.matmul_params_per_token(d, 0.625) == params
    forward = (2 * params + 5 * (4476928 + 2 * 4 * 3 * 4096)
               + mla_work.attention_ops_per_token(d, 8192))
    assert bailing_work.train_flops_per_token(d, 8192, 0.625) == 3 * forward
    assert forward == pytest.approx(1.07e9, rel=2e-2)
    # five of six mixers are the new rule and its projections: most of the required work
    assert 5 * (2 * kda + 4476928 + 98304) / forward == pytest.approx(0.61, abs=0.02)
    # the tree map is a relabelling: nothing is lost or doubled
    own = bailing_ref.dims(run.load_json(os.path.join(HERE, "configs", CONFIG + ".json")))
    w = jax.eval_shape(lambda k: bailing_ref.make_weights(own, k),
                       jax.ShapeDtypeStruct((2,), np.uint32))
    p = jax.eval_shape(bailing_tree.to_program, w)
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(w) == count(p) == pytest.approx(767e6, rel=3e-3)
    assert p["layers"]["kda"]["w_qkv"].shape == (5, 2560, 12288)
    assert p["layers"]["mla"]["w_q"].shape == (1, 2560, 32 * 192)
    assert p["layers"]["moe"]["w_gate_up"].shape == (5, 8, 2560, 1536)
    assert p["layers"]["norm1"].shape == p["layers"]["norm2"].shape == (6, 2560)


def check_manifest(m):
    """The cell's entries as members of the manifest's lists (``test_harness.check_cell``),
    and what is this cell's alone."""
    cell, config, entry, reported = test_harness.check_cell(
        m, CELL, CONFIG, NEW_METRICS + SHARED_METRICS)
    assert "1/64" in cell["why"] and "group-limited" in cell["why"]
    assert not {"gdn_fwd_ms", "ssd_fwd_ms", "flash_win_fwd_ms", "moe_rows_ms",
                "exit_gate_ms"} & reported
    return config, entry



def test_the_cells_entries_are_members_of_the_manifest_and_keep_to_the_contract():
    check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))


def test_manifest_holds_the_new_cell_and_its_configuration():
    """The configuration behind the cell's entries, on file as the entry says."""
    config, entry = check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    published = {"num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184}
    assert config["published"] == published and config["reduced"] == list(published)
    assert entry["reduced"] == list(published)
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json")
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        6, 8, 19648)
    for key in ("reduced_why", "assumed", "deployment", "limits_why", "aot_memory"):
        assert config[key], key
    assert {"kda_gate", "kda_output_gate", "latent_qk_norm", "balance", "mtp", "clamp",
            "kda_init", "optimizer", "tokens_per_step"} <= set(config["assumed"])
    assert "64-way expert-parallel" in config["deployment"]
    assert set(config["limits_why"]) >= set(config["limits"])
    assert set(config["limits"]) == {"loss_gap", "first_gradient_norm_gap",
                                     "first_gradient_projection_gap", "moved_norm_gap",
                                     "held_load_gap"}
    assert config["engine"]["remat"] is True and config["engine"]["rows_per_chip"] == 2
    d = bailing_ref.dims(config)
    assert d["layer_types"] == ("kda", "kda", "kda", "kda", "latent", "kda")
    assert d["ffn_types"] == ("dense",) + ("moe",) * 5
    assert d["experts_held"] == (0, 8) and d["vocab_rows"] == 19712
    assert d["router_num_experts"] == 512
    # every number of the catalog row's config that is not reduced, as published
    catalog = {"first_k_dense_replace": 2, "group_norm_size": 1, "head_dim": 128,
               "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
               "kv_lora_rank": 512, "layer_group_size": 6, "max_position_embeddings": 262144,
               "max_window_layers": 20, "moe_intermediate_size": 768,
               "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
               "n_group": 8, "num_attention_heads": 32, "num_experts_per_tok": 8,
               "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
               "num_nextn_predict_layers": 1, "num_shared_experts": 1,
               "partial_rotary_factor": 0.5, "qk_head_dim": 192, "qk_nope_head_dim": 128,
               "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 6000000,
               "rotary_dim": 64, "routed_scaling_factor": 2.5, "short_conv_kernel_size": 4,
               "topk_group": 4, "v_head_dim": 128}
    assert {k: config[k] for k in catalog} == catalog
    assert (config["kda_safe_gate"], config["no_kda_lora"], config["use_qk_norm"],
            config["norm_topk_prob"], config["score_function"], config["q_lora_rank"]) == (
                True, True, True, True, "sigmoid", None)
    assert len(config["expert_swiglu_limit_list"]) == 42 and not any(
        config["expert_swiglu_limit_list"][i] for i in config["layers_kept"])
