"""The ``nemotron_h`` decoder's cell on the CPU at a toy size: the new adapter
through the harness's own ``execute`` (a sound run is correct and hands back
the load counters and the bias's spread; the float8 control fails the
comparison), a program without the state-space mixer refuses the cell at
once, the new readers on a hand-made trace spelt as the chip spells it (a
share over 100 % fails here, a padded expert width among the ways to get
one), the required work by hand, and the cell's entries of ``BENCHMARK.json``
(the last of their lists, nothing before them touched)."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import hybrid_work, run, ssm_work, trace_reduce as tr  # noqa: E402
from benchmarks.adapters import ssm_tree, train_o2_ssm  # noqa: E402
from benchmarks.reference import ssm_ref  # noqa: E402
from benchmarks.tests import test_harness, toy  # noqa: E402
from benchmarks.tests.test_trace_reduce import plane  # noqa: E402

HERE = os.path.join(ROOT, "benchmarks")
PEAKS = run.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
CELL = "nemotron3-train-8k"
NEW_METRICS = ("ssd_fwd_ms", "ssd_bwd_ms", "ssd_fwd_roofline_pct", "ssd_bwd_roofline_pct",
               "ssm_block_ms", "ssm_outside_kernels_ms")
# what the cell reports under names it shares with other cells: their lists hold it
SHARED_METRICS = ("mfu_pct", "moe_gmm_ms", "moe_gmm_roofline_pct", "moe_load_max_over_mean",
                  "moe_rows_ms", "moe_block_ms", "moe_route_ms", "attn_block_ms",
                  "unembed_xent_ms", "optimizer_ms")
# the cell's cut at a toy size: one period of the pattern, 16 experts top-4
# with a share of 4 held, chunks of 16 inside rows of 64
TOY_SSM = {
    "name": "toy-ssm", "adapter": "train_o2_ssm",
    "hidden_size": 64, "num_hidden_layers": 7, "hybrid_override_pattern": "MEMEM*EMEMEM*E",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 16, "use_conv_bias": True, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "n_routed_experts": 4, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "norm_topk_prob": True, "n_group": 1, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "vocab_size": 256, "layers_kept": [0, 1, 2, 3, 4, 5, 6],
    "router_num_experts": 16, "experts_held_first": 4, "bias_update_rate": 0.001,
    "engine": {"rows_per_chip": 2, "lr": 3e-4, "remat": True, "bias_balance": [16, 0.02],
               "check_steps": 3, "trace_steps": 2},
    # at this size a sound run reads a projection gap of 0.03-0.05 (four of
    # sixteen experts on 64 features: near-ties everywhere), the control 0.2
    "limits": {"loss_gap": 0.01, "first_gradient_norm_gap": 0.04,
               "first_gradient_projection_gap": 0.1, "moved_norm_gap": 0.3,
               "held_load_gap": 0.05, "router_bias_gap": 0.1},
}


def manifest():
    m = toy.manifest()
    m["workloads"] = [{"name": "toy-ssm-cell", "config": "toy-ssm",
                       "traffic": "toy-docs", "chips": 1}]
    m["per_layer"] += [{"name": n, "unit": "x", "moves": "train_tokens_per_s"}
                       for n in ("moe_load_max_over_mean", "ssd_fwd_ms", "moe_gmm_ms")]
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
    line = run.execute(m, m["workloads"][0], TOY_SSM, toy.args(seed=2**31 + 7, trace=1),
                       jax.devices()[:1], PEAKS, here=here)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    # at most the 4 held experts' whole load on one
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] <= 4.0
    assert 0.0 < line["metrics"]["mfu_pct"]["value"] < 100.0
    assert not {"ssd_fwd_ms", "moe_gmm_ms"} & set(line["metrics"])   # no device in a CPU trace
    checked = [r.split()[1] for r in rows if r.startswith("check:") and "limit" in r]
    assert {"dropped_assignments", "held_load_gap", "router_bias_gap",
            "compilations_inside_window", "first_gradient_projection_gap"} <= set(checked)
    assert any("selection bias spread" in r and "0 local assignments dropped" in r for r in rows)
    json.dumps(line)


def _ctx(seed):
    import importlib
    mix = toy.TOY_TRAIN_MIX
    return {"config": TOY_SSM, "mix": mix, "seed": seed, "seconds": 1.0, "chips": 1,
            "log": lambda m: None,
            "generator": importlib.import_module("benchmarks.generators." + mix["generator"])}


def test_first_steps_count_the_loads_and_the_float8_control_fails():
    """What ``readings_ssm.py`` drives: the program's first steps hand back
    the held experts' loads and the bias they left; the reference against
    itself passes every row by name; computed in float8 it fails a limit."""
    from apex_tpu.parallel import mesh as mesh_lib
    ctx = _ctx(3)
    t = train_o2_ssm.Trainer(ctx)
    try:
        assert {"Trainer", "first_steps", "reference_readings", "compare", "leaf_gaps",
                "load_gap", "bias_gap", "ALL_NUMBERS", "setup", "measure",
                "finish"} <= set(dir(train_o2_ssm))
        train_o2_ssm.first_steps(t, ctx)
        t.stop_feed()
        t.state = None
        ref = train_o2_ssm.reference_readings(t, ctx)
        low = train_o2_ssm.reference_readings(t, ctx, precision="float8")
    finally:
        mesh_lib.destroy_model_parallel()
    got, steps = t.readings, 3
    assert got["expert_load"].shape == ref["expert_load"].shape == (3, 3, 4)
    assert got["router_bias"].shape == ref["router_bias"].shape == (3, 16)
    assert t.dropped == 0 and got["expert_load"].sum() > 0
    assert np.abs(got["router_bias"] - t.start_bias).max() == pytest.approx(3 * 0.001, rel=1e-3)
    assert train_o2_ssm.load_gap(got, ref) < 0.05
    assert train_o2_ssm.bias_gap(got, ref, t.ref_dims, steps) < 0.1
    limits = TOY_SSM["limits"]
    assert all(value <= limit for _, value, limit in train_o2_ssm.compare(got, ref, limits))
    same = train_o2_ssm.compare(ref, ref, limits)
    names = [n.split("@")[0].split(".step")[0] for n, _, _ in same]
    assert names == ["loss_gap"] * 3 + ["first_gradient_norm_gap",
                                        "first_gradient_projection_gap", "moved_norm_gap"]
    assert all(v == 0 for _, v, _ in same) and train_o2_ssm.load_gap(ref, ref) == 0.0
    rows = train_o2_ssm.compare(low, ref, limits)
    assert any(value > limit for _, value, limit in rows)
    assert train_o2_ssm.load_gap(low, ref) > 0.0


def test_the_state_starts_from_a_bias_the_reference_brought_to_rest():
    """``engine.bias_balance``: the starting bias is the seed's (the same
    twice) and the reference's alone — ``ssm_ref.balanced_bias`` on the
    reference's weights, the program's forward pass never asked — moved by
    no more than the rule's rates add up to; on the batch it was settled on
    every expert sits nearer the mean than from zero; and both sides' steps
    start from it."""
    from apex_tpu.parallel import mesh as mesh_lib
    iterations, first = TOY_SSM["engine"]["bias_balance"]
    ctx = _ctx(5)
    t = train_o2_ssm.Trainer(ctx)
    asked = []
    loss_fn = t.model.loss_fn
    t.model.loss_fn = lambda *a, **kw: asked.append(1) or loss_fn(*a, **kw)
    try:
        again = np.asarray(t.init_state(ssm_ref.seed_key(5))[3])
        assert not asked
        t.model.loss_fn = loss_fn
        train_o2_ssm.first_steps(t, ctx)
        t.stop_feed()
        t.state = None
        ref = train_o2_ssm.reference_readings(t, ctx)
    finally:
        mesh_lib.destroy_model_parallel()
    start, rate = t.start_bias, TOY_SSM["bias_update_rate"]
    np.testing.assert_array_equal(start, again)
    d, key = t.ref_dims, ssm_ref.seed_key(5)
    w = ssm_ref.make_weights(d, key)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (t.rows, t.seq), 0,
                                d["vocab_size"] - 1)
    np.testing.assert_array_equal(start, ssm_ref.balanced_bias(w, d, tokens, iterations, first))
    most = lambda b: np.asarray(ssm_ref.hidden(w, b, d, tokens)[1]).max(-1)  # noqa: E731
    assert (most(jnp.asarray(start)) < most(ssm_ref.bias_init(d))).all()
    decay = (rate / first) ** (1 / (iterations - 1))
    assert 0.0 < np.abs(start).max() <= first * sum(decay ** i for i in range(iterations)) + 1e-6
    for got in (t.readings["router_bias"], ref["router_bias"]):     # three steps of ``rate`` on
        assert np.abs(got - start).max() == pytest.approx(3 * rate, rel=1e-3)
    assert train_o2_ssm.bias_gap(t.readings, ref, t.ref_dims, 3) < 0.1


def test_a_program_without_the_state_space_mixer_refuses_the_cell_at_once(monkeypatch):
    """The parent's program under this PR's benchmark files: its
    configuration knows no state-space layer, and the adapter asks it before
    it asks for a mesh or a chip — the parent exits on the cell, it does not
    hang."""
    from apex_tpu import models
    from apex_tpu.parallel import mesh as mesh_lib

    def parent_config(**kw):
        if "ssm_heads" in kw:
            raise TypeError("HybridDecoderConfig.__init__() got an unexpected keyword "
                            "argument 'attn_gate'")
    monkeypatch.setattr(models, "HybridDecoderConfig", parent_config)
    monkeypatch.setattr(mesh_lib, "initialize_model_parallel",
                        lambda **kw: pytest.fail("asked for a mesh first"))
    with pytest.raises(TypeError, match="attn_gate"):
        train_o2_ssm.Trainer(_ctx(1))


# --- readers on names as the chip spells them ---------------------------------

TAIL = ', custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
X = "bf16[2,8192,4096]{2,1,0}"
SSD_FWD = (f"%ssd_fwd.3 = ({X}, f32[2,8,8,4,128,128]{{5,4,3,2,1,0}}) custom-call({X} %x, "
           "bf16[2,8192,1024]{2,1,0} %b)" + TAIL)
SSD_BWD = (f"%ssd_bwd.3 = ({X}, bf16[2,8192,1024]{{2,1,0}}, bf16[2,8192,1024]{{2,1,0}}) "
           f"custom-call({X} %x)" + TAIL)
CONV = "%conv_silu_fwd.9 = bf16[2,8192,4096]{2,1,0} custom-call(bf16[2,8192,10304]{2,1,0} %p)" + TAIL
FLASH = ("%flash_fwd_bshd.2 = (bf16[2,8192,32,128]{3,2,1,0}, f32[2,32,8192,8]{3,2,1,0}) "
         "custom-call(bf16[2,8192,32,128]{3,2,1,0} %q)" + TAIL)
GMM = "%moe_gmm.5 = bf16[16384,1856]{1,0} custom-call(s32[128]{0} %a, bf16[16384,2688]{1,0} %b)" + TAIL
GMM_DW = "%moe_gmm_dw.7 = bf16[8,2688,1856]{2,1,0} custom-call(s32[128]{0} %a, bf16[16384,2688]{1,0} %b)" + TAIL
FUSION = "%fusion.263 = bf16[16384,2688]{1,0} fusion(bf16[16384,2688]{1,0} %p), kind=kOutput"
ROWS = ("%moe_rows_gather.2 = bf16[16384,4096]{1,0} custom-call(s32[1]{0} %n, "
        "u32[2048,8,8,128]{3,2,1,0} %g)" + TAIL)
ROUTE, HEAD, ADAM = (FUSION.replace("263", n) for n in ("301", "302", "303"))


def cell_dims():
    config = run.load_json(os.path.join(HERE, "configs", "nemotron-3-nano-30b-a3b-train1.json"))
    d = ssm_ref.dims(config)
    return dict(d, **ssm_tree.attention_view(d))


def cell_run(events, steps, loads, table=None):
    text = (plane("/device:TPU:0", "XLA Ops", events, 1)
            + plane("/host:CPU", "python", [(0, 10, "bench_step")], 2))
    trace = tr.reduce(ProfileData.from_text_proto(text))
    r = {"trace": trace, "step_s": [0.5] * steps, "steps": 32, "tokens": 32 * 16384,
         "window_s": 16.0, "chips": 1, "seq": 8192, "dims": cell_dims(), "peaks": PEAKS,
         "expert_load": loads}
    if table is not None:
        r["scope_table"] = table
    return as_the_adapter(r)


def as_the_adapter(r):
    return dict(r, train_flops_per_token=ssm_work.window_flops_per_token(r),
                expert_matmul_work=hybrid_work.window_expert_matmul_work(
                    r, work=ssm_work.expert_matmul_work))


def read(name, r):
    return run.load_reader(name).read(r)


def even_loads(steps=32):
    return np.full((steps, 3, 8), 768)        # 6,144 local assignments a layer and step


def hand_expert_work(assignments, F=1856):
    ops = 3 * 4 * 2688 * F * assignments
    nbytes = 3 * 3 * 8 * 2 * 2688 * F * 2 + 3 * assignments * 2 * 2688 * 2
    return ops, nbytes


def test_new_readers_on_names_as_the_chip_spells_them():
    ms = 1_000_000
    events = [(0, 30 * ms, SSD_FWD), (30 * ms, 130 * ms, SSD_BWD), (130 * ms, 140 * ms, CONV),
              (140 * ms, 160 * ms, GMM), (160 * ms, 180 * ms, GMM_DW), (180 * ms, 200 * ms, FLASH),
              (200 * ms, 300 * ms, FUSION), (300 * ms, 312 * ms, ROWS), (312 * ms, 320 * ms, ROUTE),
              (320 * ms, 326 * ms, HEAD), (326 * ms, 330 * ms, ADAM)]
    under = "jit(run)/amp/fwd_bwd/jvp(hybrid/ssm)/"
    moe = "jit(run)/amp/fwd_bwd/jvp(hybrid/moe)/"
    table = {"ssd_fwd.3": under + "ssd_fwd", "ssd_bwd.3": under.replace("jvp(", "transpose(jvp(")
             + ")ssd_bwd", "conv_silu_fwd.9": under + "conv_silu_fwd",
             "fusion.263": under + "mix/proj_in/dot_general",
             "moe_gmm.5": moe + "moe/experts/moe_gmm", "moe_rows_gather.2": moe + "moe_rows_gather",
             "fusion.301": moe + "moe/route/sort",
             "flash_fwd_bshd.2": "jit(run)/amp/fwd_bwd/jvp(hybrid/attn)/flash_fwd_bshd",
             "fusion.302": "jit(run)/amp/fwd_bwd/jvp(hybrid/unembed_xent)/dot_general",
             "fusion.303": "jit(run)/amp/apply_master/add"}
    loads = even_loads()
    loads[:, :, 0] = 1152                     # one expert half as full again
    r = cell_run(events, steps=2, loads=loads, table=table)
    tokens = 16384
    assert read("ssd_fwd_ms", r) == pytest.approx(15.0)
    assert read("ssd_bwd_ms", r) == pytest.approx(50.0)
    ops, nbytes = ssm_work.scan_work(r["dims"], tokens)
    assert read("ssd_fwd_roofline_pct", r) == pytest.approx(
        100 * 1e3 * max(ops / 197e12, nbytes / 819e9) / 15.0)
    ops_b, bytes_b = ssm_work.scan_work(r["dims"], tokens, backward=True)
    assert read("ssd_bwd_roofline_pct", r) == pytest.approx(
        100 * 1e3 * max(ops_b / 197e12, bytes_b / 819e9) / 50.0)
    assert read("moe_gmm_ms", r) == pytest.approx(20.0)
    n = loads[0].sum()
    ops, nbytes = hand_expert_work(n)
    assert read("moe_gmm_roofline_pct", r) == pytest.approx(
        100 * 1e3 * max(ops / 197e12, nbytes / 819e9) / 20.0)
    assert read("moe_load_max_over_mean", r) == pytest.approx(1152 / 816.0)
    assert read("mfu_pct", r) == pytest.approx(
        100 * ssm_work.train_flops_per_token(r["dims"], 8192, n / tokens) * 32 * tokens / 16.0
        / 197e12)
    assert 25 < read("mfu_pct", r) < 35
    # everything traced under hybrid/ssm: both scans, the convolution and the
    # projection's fusion; outside the kernels the fusion alone
    assert read("ssm_block_ms", r) == pytest.approx(15.0 + 50.0 + 5.0 + 50.0)
    assert read("ssm_outside_kernels_ms", r) == pytest.approx(50.0)
    # the shared block readers, on the spans this cell's program holds (``hybrid/attn``
    # alone of the attention spans; nothing under ``gpt/*``): what its twins read
    assert read("moe_rows_ms", r) == pytest.approx(6.0)
    assert read("moe_block_ms", r) == pytest.approx(10.0 + 6.0 + 4.0)   # gmm, rows, route
    assert read("moe_route_ms", r) == pytest.approx(4.0)
    assert read("attn_block_ms", r) == pytest.approx(10.0)
    assert read("unembed_xent_ms", r) == pytest.approx(3.0)
    assert read("optimizer_ms", r) == pytest.approx(2.0)
    # the accepted flash times and shares list no cells: they read this cell's
    # ONE attention layer through the attention view
    assert read("flash_fwd_ms", r) == pytest.approx(10.0)
    want = 16384 * 4 * 32 * 128 * 4096.5 / 197e12 * 1e3
    assert read("flash_fwd_roofline_pct", r) == pytest.approx(100 * want / 10.0, rel=1e-3)
    for name in NEW_METRICS + SHARED_METRICS + ("flash_fwd_roofline_pct",):
        if name.endswith("_pct"):
            assert 0 <= read(name, r) <= 100, name   # a share over 100 % is a miscount
    # a run whose adapter hands no count, or no work, reads as nothing
    bare = {k: v for k, v in r.items() if k not in ("train_flops_per_token", "expert_matmul_work")}
    assert read("mfu_pct", bare) is None and read("moe_gmm_roofline_pct", bare) is None
    # the other blocks' twins and readers find nothing here
    for name in ("flash_win_fwd_ms", "gdn_fwd_ms", "flash_bwd_ms"):
        assert read(name, r) is None


def test_required_work_is_never_counted_at_a_padded_width():
    """The experts are 1,856 wide, 14.5 lane tiles: a count at 1,920 or 2,048
    would credit the kernels with work they do not owe. At a time that puts
    the true share just under 100 %, either padded width reads over it."""
    d = cell_dims()
    assert d["moe_intermediate_size"] == 1856 and d["n_layer"] == 1 and d["n_embd"] == 4096
    loads = even_loads()
    n = loads[0].sum()
    ops, nbytes = hand_expert_work(n)
    least_ms = 1e3 * max(ops / 197e12, nbytes / 819e9)
    ns = int(2 * 1.02 * least_ms * 1e6)                   # two traced steps, 2 % over the least
    r = cell_run([(0, ns, GMM)], steps=2, loads=loads)
    assert 95 < read("moe_gmm_roofline_pct", r) < 100
    for padded in (1920, 2048):
        wide = as_the_adapter(dict(r, dims=dict(r["dims"], moe_intermediate_size=padded)))
        assert read("moe_gmm_roofline_pct", wide) > 100
    assert ssm_work.expert_matmul_work(d, n) == hand_expert_work(n)
    assert ssm_work.expert_matmul_work(d, n)[0] < hybrid_work.expert_matmul_work(
        dict(d, num_hidden_layers=3), n, passes=3)[0]      # two matrices, not a SwiGLU's three


def test_new_readers_find_nothing_in_a_program_that_lacks_the_names():
    """Another block's run on this PR's benchmark files: no such counters,
    another model's dims, no such kernels — every new reader returns ``None``
    and raises nothing."""
    from benchmarks.reference import gpt_ref
    sc1b = gpt_ref.dims(run.load_json(os.path.join(HERE, "configs", "starcoderbase-1b-train1.json")))
    r = cell_run([(0, 5, FLASH), (5, 9, FUSION)], steps=1, loads=None, table={})
    r = {k: v for k, v in dict(r, dims=sc1b).items() if k != "expert_load"}
    names = NEW_METRICS + SHARED_METRICS
    assert [read(name, r) for name in names] == [None] * len(names)
    assert [read(name, dict(r, trace=None)) for name in names] == [None] * len(names)


def test_required_work_by_hand():
    d = cell_dims()
    # the scan, a token and layer forward: C B^T 8 groups x 128 x 64.5, M x 64
    # heads x 64 x 64.5, C S_0 and the state's update 64 x 128 x 64 each, D x
    assert ssm_work.scan_ops_per_token(d) == 2 * (8 * 128 * 64.5 + 64 * 64 * 64.5
                                                  + 2 * 64 * 128 * 64 + 64 * 64) == 2765824
    ops, nbytes = ssm_work.scan_work(d, 16384)
    assert ops == 3 * 16384 * 2765824 and nbytes == 3 * 16384 * (2 * (8192 + 2048) + 256)
    ops_b, bytes_b = ssm_work.scan_work(d, 16384, backward=True)
    assert ops_b == 2 * ops and bytes_b == 3 * 16384 * (2 * (3 * 4096 + 4 * 1024) + 512)
    # bytes bound the scan: 1.02 GB is 1.24 ms, 136 GFLOP 0.69 ms
    assert nbytes / 819e9 > ops / 197e12
    # one mixer 38.7 M, attention 23.4 M, an expert layer outside its routed experts 20.3 M
    mixer = 2688 * (4096 + 6144 + 64) + 4096 * 2688
    attn = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
    layer = 2688 * 128 + 2 * 2688 * 3712
    assert (mixer, attn, layer) == (38707200, 23396352, 20299776)
    # at the expected 0.375 local assignments a token and expert layer
    params = 3 * mixer + attn + 3 * layer + 1.125 * 2 * 2688 * 1856 + 16384 * 2688
    assert ssm_work.matmul_params_per_token(d, 1.125) == params
    forward = 2 * params + 4 * 32 * 128 * 4096.5 + 3 * (2765824 + 2 * 4 * 6144)
    assert ssm_work.train_flops_per_token(d, 8192, 1.125) == 3 * forward
    assert forward == pytest.approx(586.9e6, rel=1e-3)
    # the three state-space mixers are the largest part of the required work
    assert 3 * (2 * mixer + 2765824 + 49152) / forward == pytest.approx(0.41, abs=0.01)
    # the tree map is a relabelling: nothing is lost or doubled
    w = jax.eval_shape(lambda k: ssm_ref.make_weights(d, k), jax.ShapeDtypeStruct((2,), np.uint32))
    p = jax.eval_shape(lambda w: ssm_tree.to_program(w, d), w)
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(w) == count(p) == pytest.approx(528e6, rel=2e-3)
    assert p["layers"]["ssm"]["w_in"].shape == (3, 2688, 10304)
    assert p["layers"]["moe"]["w_up"].shape == (3, 8, 2688, 1856)
    assert p["layers"]["norm1"].shape == (4, 2688) and p["layers"]["norm2"].shape == (3, 2688)


def check_manifest(m):
    """The cell's entries as members of the manifest's lists (``test_harness.check_cell``),
    and what is this cell's alone."""
    cell, config, entry, reported = test_harness.check_cell(
        m, CELL, "nemotron-3-nano-30b-a3b-train1", NEW_METRICS + SHARED_METRICS)
    assert "1/16" in cell["why"] and "41 %" in cell["why"]
    assert not {"gdn_fwd_ms", "flash_win_fwd_ms", "kda_fwd_ms", "mlp_block_ms"} & reported
    return config, entry



def test_the_cell_is_appended_to_the_manifest_and_its_entries_keep_to_the_contract():
    """Appended once, a member ever after: ``check_manifest`` on the file as it is."""
    check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))


def test_manifest_holds_the_new_cell_and_its_metrics():
    """The configuration behind the cell's entries, on file as the entry says."""
    config, entry = check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    published = {"num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072}
    assert config["published"] == published and config["reduced"] == list(published)
    assert entry["reduced"] == list(published)
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        7, 8, 16384)
    for key in ("reduced_why", "assumed", "deployment", "limits_why", "aot_memory"):
        assert config[key], key
    assert {"positions", "bias_update", "bias_start", "optimizer"} <= set(config["assumed"])
    # the state starts from a bias in balance, and the step keeps it there
    assert config["engine"]["bias_balance"] == [48, 0.02] and config["engine"]["lr"] == 1e-6
    assert "16 chips share each layer" in config["deployment"]
    assert set(config["limits_why"]) >= set(config["limits"])
    assert config["aot_memory"]["reference"]["fits"] is True
    d = ssm_ref.dims(config)
    assert d["kinds"] == ("ssm", "moe", "ssm", "moe", "ssm", "attn", "moe")
    assert d["experts_held"] == (0, 8) and d["vocab_rows"] == 16384
    assert d["router_num_experts"] == 128
    # every number of the catalog row's config that is not reduced, as published
    catalog = {"chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
               "hidden_size": 2688, "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
               "mamba_head_dim": 64, "mamba_num_heads": 64, "max_position_embeddings": 262144,
               "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
               "n_group": 1, "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
               "num_attention_heads": 32, "num_experts_per_tok": 6, "num_key_value_heads": 2,
               "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rope_theta": 10000,
               "routed_scaling_factor": 2.5, "ssm_state_size": 128, "time_step_floor": 0.0001,
               "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1}
    assert {k: config[k] for k in catalog} == catalog
    assert config["hybrid_override_pattern"].startswith("MEMEM*EMEMEM*E")
    assert len(config["hybrid_override_pattern"]) == 52
    assert (config["mlp_hidden_act"], config["use_conv_bias"], config["norm_topk_prob"]) == (
        "relu2", True, True)
