"""The looped (``ouro``) decoder's cell on the CPU at a toy size: the new
adapter through the harness's own ``execute`` (a sound run is correct and
hands back the exits' readings; the float8 control fails the comparison), a
program whose stack cannot loop refuses the cell at once, the new readers on
a hand-made trace spelt as the chip spells it (the walks counted twice read a
share over 100 %, which fails here), the required work by hand, and the
cell's entries of ``BENCHMARK.json`` as MEMBERS of their lists: what a later
PR puts after them, or adds to a list that holds this cell, breaks nothing here."""
import importlib
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

from benchmarks import kernel_work, loop_work, run, trace_reduce as tr  # noqa: E402
from benchmarks.adapters import loop_tree, train_o2_loop  # noqa: E402
from benchmarks.reference import loop_ref  # noqa: E402
from benchmarks.tests import test_harness, toy  # noqa: E402
from benchmarks.tests.test_trace_reduce import plane  # noqa: E402

HERE = os.path.join(ROOT, "benchmarks")
PEAKS = run.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
CELL, CONFIG = "ouro-train-8k", "ouro-2.6b-train1"
NEW_METRICS = ("exit_gate_ms", "exit_mass_last")
# what the cell reports under names it shares with other cells: their lists hold it
SHARED_METRICS = ("mfu_pct", "attn_block_ms", "attn_outside_kernels_ms", "mlp_block_ms",
                  "unembed_xent_ms", "optimizer_ms", "recompute_ms", "unscoped_ms")
# the cell's block at a toy size: four heads of 16, a SwiGLU of 2.75 x the
# hidden size, four walks of two layers
TOY_LOOP = {
    "name": "toy-loop", "adapter": "train_o2_loop",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 176,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "vocab_size": 256, "total_ut_steps": 4,
    "entropy_beta": 0.1,
    "engine": {"rows_per_chip": 2, "lr": 3e-4, "remat": True, "check_steps": 3,
               "trace_steps": 2},
    # at this size a sound run reads a projection gap of 0.006, a worst exit
    # loss 0.0005 and share 0.0005 off; the float8 control 0.1, 0.007 and 0.0026
    "limits": {"loss_gap": 0.01, "first_gradient_norm_gap": 0.015,
               "first_gradient_projection_gap": 0.03, "moved_norm_gap": 0.3,
               "exit_losses_gap": 0.002, "exit_mass_gap": 0.0015},
}


def manifest():
    m = toy.manifest()
    m["workloads"] = [{"name": "toy-loop-cell", "config": "toy-loop",
                       "traffic": "toy-docs", "chips": 1}]
    m["per_layer"] += [{"name": n, "unit": "x", "moves": "train_tokens_per_s"}
                       for n in ("exit_mass_last", "exit_gate_ms", "attn_block_ms")]
    return m


@pytest.fixture
def here(tmp_path):
    (tmp_path / "traffic").mkdir()
    os.symlink(os.path.join(HERE, "layer_metrics"), tmp_path / "layer_metrics")
    mix = toy.TOY_TRAIN_MIX
    (tmp_path / "traffic" / (mix["name"] + ".json")).write_text(json.dumps(mix))
    return str(tmp_path)


def test_traced_rehearsal_is_correct_and_hands_back_the_exits(here, monkeypatch):
    rows = []
    monkeypatch.setattr(run, "log", rows.append)
    m = manifest()
    line = run.execute(m, m["workloads"][0], TOY_LOOP, toy.args(seed=2**31 + 7, trace=1),
                       jax.devices()[:1], PEAKS, here=here)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert 0.0 < line["metrics"]["exit_mass_last"]["value"] < 1.0
    assert 0.0 < line["metrics"]["mfu_pct"]["value"] < 100.0
    assert not {"exit_gate_ms", "attn_block_ms"} & set(line["metrics"])   # a CPU trace
    checked = [r.split()[1].split("@")[0] for r in rows if r.startswith("check:") and "limit" in r]
    assert {"exit_losses_gap", "exit_mass_gap", "compilations_inside_window",
            "first_gradient_projection_gap", "moved_norm_gap",
            "train_step_executables_beyond_one"} <= set(checked)
    assert any("exits' share of the tokens" in r for r in rows)
    json.dumps(line)


def _ctx(seed, config=TOY_LOOP):
    mix = toy.TOY_TRAIN_MIX
    return {"config": config, "mix": mix, "seed": seed, "seconds": 1.0, "chips": 1,
            "log": lambda m: None,
            "generator": importlib.import_module("benchmarks.generators." + mix["generator"])}


def test_first_steps_read_the_exits_and_the_float8_control_fails():
    """What ``readings.py`` drives: the program's first steps hand back every
    exit's mean loss and share; the reference against itself passes every row
    by name; computed in float8 it fails a limit."""
    from apex_tpu.parallel import mesh as mesh_lib
    ctx = _ctx(3)
    t = train_o2_loop.Trainer(ctx)
    try:
        assert {"Trainer", "first_steps", "reference_readings", "compare", "leaf_gaps",
                "ALL_NUMBERS", "setup", "measure", "finish"} <= set(dir(train_o2_loop))
        train_o2_loop.first_steps(t, ctx)
        t.stop_feed()
        t.state = None
        ref = train_o2_loop.reference_readings(t, ctx)
        low = train_o2_loop.reference_readings(t, ctx, precision="float8")
    finally:
        mesh_lib.destroy_model_parallel()
    got = t.readings
    for name in train_o2_loop.EXITS:
        assert got[name].shape == ref[name].shape == (3, 4)
    np.testing.assert_allclose(got["exit_mass"].sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(ref["exit_mass"].sum(-1), 1.0, atol=1e-5)
    assert got["exit_entropy"].shape == (3,) and (got["exit_entropy"] > 0).all()
    limits = TOY_LOOP["limits"]
    rows = train_o2_loop.compare(got, ref, limits)
    assert all(value <= limit for _, value, limit in rows), rows
    same = train_o2_loop.compare(ref, ref, limits)
    names = [n.split("@")[0].split(".step")[0] for n, _, _ in same]
    assert names == ["loss_gap"] * 3 + ["first_gradient_norm_gap",
                                        "first_gradient_projection_gap", "moved_norm_gap",
                                        "exit_losses_gap", "exit_mass_gap"]
    assert all(v == 0 for _, v, _ in same)
    assert set(train_o2_loop.ALL_NUMBERS) == set(limits)
    failed = {n.split("@")[0] for n, value, limit in train_o2_loop.compare(low, ref, limits)
              if value > limit}
    assert {"first_gradient_projection_gap", "exit_losses_gap", "exit_mass_gap"} <= failed


def test_a_program_whose_stack_cannot_loop_refuses_the_cell_at_once(monkeypatch):
    """The parent's program under this PR's benchmark files: its
    configuration knows no ``loop_trips``, and the adapter asks it before it
    asks for a mesh or a chip — the parent exits on the cell, it does not
    hang."""
    from apex_tpu import models
    from apex_tpu.parallel import mesh as mesh_lib

    def parent_config(**kw):
        if "loop_trips" in kw:
            raise TypeError("HybridDecoderConfig.__init__() got an unexpected keyword "
                            "argument 'loop_trips'")
    monkeypatch.setattr(models, "HybridDecoderConfig", parent_config)
    monkeypatch.setattr(mesh_lib, "initialize_model_parallel",
                        lambda **kw: pytest.fail("asked for a mesh first"))
    with pytest.raises(TypeError, match="loop_trips"):
        train_o2_loop.Trainer(_ctx(1))


# --- readers on names as the chip spells them ---------------------------------

TAIL = ', custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
Q = "bf16[2,8192,16,128]{3,2,1,0}"
FLASH = f"%flash_fwd_bshd.2 = ({Q}, f32[2,16,8192,8]{{3,2,1,0}}) custom-call({Q} %q)" + TAIL
FLASH_BWD = f"%flash_bwd_bshd_fused.4 = ({Q}, {Q}, {Q}) custom-call({Q} %q)" + TAIL
XENT = "%xentropy_stats.3 = (f32[8192,1]{1,0}) custom-call(bf16[8192,49152]{1,0} %l)" + TAIL
FUSION = "%fusion.263 = bf16[16384,2048]{1,0} fusion(bf16[16384,2048]{1,0} %p), kind=kOutput"
PROJ, REDO, MLP, HEAD, GATE, ADAM, COPY = (FUSION.replace("263", n) for n in (
    "301", "302", "303", "304", "305", "306", "307"))


def cell_dims():
    d = loop_ref.dims(run.load_json(os.path.join(HERE, "configs", CONFIG + ".json")))
    return dict(d, **loop_tree.attention_view(d))


def cell_run(events, steps, mass=None, table=None):
    text = (plane("/device:TPU:0", "XLA Ops", events, 1)
            + plane("/host:CPU", "python", [(0, 10, "bench_step")], 2))
    trace = tr.reduce(ProfileData.from_text_proto(text))
    r = {"trace": trace, "step_s": [2.4] * steps, "steps": 8, "tokens": 8 * 16384,
         "window_s": 19.2, "chips": 1, "seq": 8192, "dims": cell_dims(), "peaks": PEAKS}
    if mass is not None:
        r["exit_mass"] = mass
    if table is not None:
        r["scope_table"] = table
    return dict(r, train_flops_per_token=loop_work.window_flops_per_token(r))   # as the adapter


def read(name, r):
    return run.load_reader(name).read(r)


def test_new_readers_on_names_as_the_chip_spells_them():
    ms = 1_000_000
    events = [(0, 200 * ms, FLASH), (200 * ms, 600 * ms, FLASH_BWD), (600 * ms, 700 * ms, PROJ),
              (700 * ms, 760 * ms, REDO), (760 * ms, 1000 * ms, MLP), (1000 * ms, 1100 * ms, HEAD),
              (1100 * ms, 1120 * ms, XENT), (1120 * ms, 1124 * ms, GATE),
              (1124 * ms, 1150 * ms, ADAM), (1150 * ms, 1160 * ms, COPY)]
    fwd = "jit(run)/amp/fwd_bwd/jvp(hybrid/attn)/"
    bwd = "jit(run)/amp/fwd_bwd/transpose(jvp(hybrid/attn))/"
    table = {"flash_fwd_bshd.2": fwd + "flash_fwd_bshd",
             "flash_bwd_bshd_fused.4": bwd + "flash_bwd_bshd_fused",
             "fusion.301": fwd + "mix/proj_in/dot_general",
             "fusion.302": "jit(run)/amp/fwd_bwd/transpose(jvp(amp/fwd_bwd))/checkpoint/"
                           "rematted_computation/hybrid/attn/mix/proj_in/dot_general",
             "fusion.303": "jit(run)/amp/fwd_bwd/jvp(hybrid/dense)/dot_general",
             "fusion.304": "jit(run)/amp/fwd_bwd/jvp(hybrid/unembed_xent)/dot_general",
             "xentropy_stats.3": "jit(run)/amp/fwd_bwd/jvp(hybrid/unembed_xent)/xentropy_stats",
             "fusion.305": "jit(run)/amp/fwd_bwd/jvp(hybrid/exit)/exp",
             "fusion.306": "jit(run)/amp/apply_master/add",
             "fusion.307": "jit(run)/copy"}
    mass = np.tile([0.4, 0.3, 0.2, 0.1], (8, 1))
    mass[:, -1] += np.linspace(0, 0.07, 8)
    r = cell_run(events, steps=2, mass=mass, table=table)
    assert read("attn_block_ms", r) == pytest.approx(100.0 + 200.0 + 50.0 + 30.0)
    assert read("attn_outside_kernels_ms", r) == pytest.approx(50.0 + 30.0)
    assert read("mlp_block_ms", r) == pytest.approx(120.0)
    assert read("unembed_xent_ms", r) == pytest.approx(50.0 + 10.0)
    assert read("exit_gate_ms", r) == pytest.approx(2.0)
    assert read("optimizer_ms", r) == pytest.approx(13.0)
    assert read("recompute_ms", r) == pytest.approx(30.0)
    assert read("unscoped_ms", r) == pytest.approx(5.0)
    assert read("exit_mass_last", r) == pytest.approx(0.135)
    assert read("mfu_pct", r) == pytest.approx(
        100 * loop_work.train_flops_per_token(r["dims"], 8192) * 8 * 16384 / 19.2 / 197e12)
    assert 50 < read("mfu_pct", r) < 60
    # a run whose adapter hands no count reads as nothing
    assert read("mfu_pct", {k: v for k, v in r.items() if k != "train_flops_per_token"}) is None
    # the accepted flash times and shares list no cells: they read this cell's
    # 8 layers x 4 walks of flash calls through the attention view
    assert read("flash_fwd_ms", r) == pytest.approx(100.0)
    assert read("flash_bwd_ms", r) == pytest.approx(200.0)
    assert read("xentropy_ms", r) == pytest.approx(10.0)
    want = 32 * 16384 * 4 * 16 * 128 * 4096.5 / 197e12 * 1e3
    assert read("flash_fwd_roofline_pct", r) == pytest.approx(100 * want / 100.0, rel=1e-3)
    assert read("flash_bwd_roofline_pct", r) == pytest.approx(100 * 2 * want / 200.0, rel=1e-3)
    for name in ("mfu_pct", "flash_fwd_roofline_pct", "flash_bwd_roofline_pct"):
        assert 0 <= read(name, r) <= 100, name       # a share over 100 % is a miscount
    # the other blocks' readers find nothing here
    for name in ("flash_win_fwd_ms", "gdn_fwd_ms", "ssd_fwd_ms", "moe_gmm_ms",
                 "moe_gmm_roofline_pct", "moe_load_max_over_mean"):
        assert read(name, r) is None


def test_the_walks_are_counted_once_in_the_attention_view_and_nowhere_else():
    """A flash call a layer and walk: ``dims["n_layer"]`` is L x 4 and
    ``kernel_work`` multiplies by nothing else. At a time that puts the true
    share just under 100 %, the walks counted twice read over it (and a view
    that forgot them reads a quarter)."""
    d = cell_dims()
    config = run.load_json(os.path.join(HERE, "configs", CONFIG + ".json"))
    L, T = config["num_hidden_layers"], config["total_ut_steps"]
    assert d["n_layer"] == L * T and (T, d["n_embd"], d["n_head"], d["n_kv_head"]) == (
        4, 2048, 16, 16)
    r = cell_run([(0, 10, FLASH)], steps=2)
    ops, nbytes = kernel_work.flash_work(r)
    assert ops == L * T * 16384 * 4 * 2048 * 4096.5
    least_ms = 1e3 * max(ops / 197e12, nbytes / 819e9)
    r = cell_run([(0, int(2 * 1.02 * least_ms * 1e6), FLASH)], steps=2)
    assert 95 < read("flash_fwd_roofline_pct", r) < 100
    twice = dict(r, dims=dict(d, n_layer=L * T * T))
    assert read("flash_fwd_roofline_pct", twice) > 100
    assert read("flash_fwd_roofline_pct", dict(r, dims=dict(d, n_layer=L))) == pytest.approx(
        read("flash_fwd_roofline_pct", r) / T)
    # the whole step's share likewise: at the bf16 peak's own rate it reads 100
    need = loop_work.train_flops_per_token(d, 8192)
    at_peak = dict(r, tokens=16384, window_s=16384 * need / 197e12 * 1.02,
                   train_flops_per_token=need)
    assert 95 < read("mfu_pct", at_peak) < 100
    assert read("mfu_pct", dict(at_peak, train_flops_per_token=T * need)) > 100


def test_new_readers_find_nothing_in_a_program_that_lacks_the_names():
    """Another block's run on this PR's benchmark files (the parent's too): no
    such counter, no such span, no required work handed over — every new
    reader returns ``None`` and raises nothing."""
    from benchmarks.reference import gpt_ref
    sc1b = gpt_ref.dims(run.load_json(os.path.join(HERE, "configs", "starcoderbase-1b-train1.json")))
    r = cell_run([(0, 5, FLASH), (5, 9, FUSION)], steps=1, table={})
    r = {k: v for k, v in dict(r, dims=sc1b).items() if k != "train_flops_per_token"}
    names = NEW_METRICS + SHARED_METRICS
    assert [read(name, r) for name in names] == [None] * len(names)
    assert [read(name, dict(r, trace=None)) for name in names] == [None] * len(names)


def test_required_work_by_hand():
    d = cell_dims()
    # a layer: q, k, v, o at 2,048 x 2,048 and three SwiGLU matrices at 2,048 x 5,632
    assert loop_work.layer_matmul_params(d) == 4 * 2048 * 2048 + 3 * 2048 * 5632 == 51380224
    L = d["num_hidden_layers"]
    walk = 2 * (L * 51380224 + 49152 * 2048) + L * 4 * 16 * 128 * 4096.5
    assert loop_work.walk_flops_per_token(d, 8192) == walk
    assert loop_work.train_flops_per_token(d, 8192) == 3 * (4 * walk + 3 * 2 * 2048)
    if L == 8:
        assert loop_work.train_flops_per_token(d, 8192) == pytest.approx(15.50e9, rel=1e-3)
        # four walks of the layers' matmuls, of attention and of the head
        assert 3 * 4 * 2 * L * 51380224 == pytest.approx(9.87e9, rel=1e-3)
        assert 3 * 4 * L * 4 * 2048 * 4096.5 == pytest.approx(3.22e9, rel=1e-3)
        assert 3 * 4 * 2 * 49152 * 2048 == pytest.approx(2.42e9, rel=2e-3)
    # a walk is sc1b-train-8k's plain stack but for the block: the same hidden
    # size, heads and vocabulary, the head untied and read four times
    assert (d["hidden_size"], d["head_dim"], d["vocab_size"]) == (2048, 128, 49152)
    # the tree map is a relabelling: nothing is lost or doubled
    w = jax.eval_shape(lambda k: loop_ref.make_weights(d, k), jax.ShapeDtypeStruct((2,), np.uint32))
    p = jax.eval_shape(loop_tree.to_program, w)
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(w) == count(p) == loop_work.total_params(d)
    assert p["layers"]["dense"]["w_gate_up"].shape == (L, 2048, 11264)
    assert p["layers"]["attn"]["w_q"].shape == (L, 2048, 2048)
    assert p["exit_gate"]["weight"].shape == (2048, 1) and p["exit_gate"]["bias"].shape == (1,)
    assert p["layers"]["norm1_post"].shape == p["layers"]["norm2_post"].shape == (L, 2048)


def check_manifest(m):
    """The cell's entries as members of the manifest's lists (``test_harness.check_cell``),
    and what is this cell's alone."""
    cell, config, entry, reported = test_harness.check_cell(
        m, CELL, CONFIG, NEW_METRICS + SHARED_METRICS)
    assert "4 walks" in cell["why"]
    assert next(p for p in m["per_layer"] if p["name"] == "exit_mass_last")["source"] == (
        "program_counter")
    assert not {"gdn_fwd_ms", "moe_gmm_ms", "flash_win_fwd_ms", "ssd_fwd_ms",
                "moe_block_ms"} & reported
    return config, entry



def test_the_cell_comes_after_every_accepted_entry_and_keeps_to_the_contract():
    """Entered once, a member ever after: ``check_manifest`` on the file as it is."""
    check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))


def test_manifest_holds_the_new_cell_and_its_metrics():
    """The configuration behind the cell's entries, on file as the entry says."""
    config, entry = check_manifest(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert 4 <= config["num_hidden_layers"] <= 8
    assert config["layers_kept"] == list(range(config["num_hidden_layers"]))
    for key in ("reduced_why", "assumed", "deployment", "limits_why", "aot_memory", "precision"):
        assert config[key], key
    assert {"entropy_beta", "projection_bias", "rotary", "exit_gate", "weights", "optimizer",
            "tokens_per_step"} <= set(config["assumed"])
    assert config["engine"]["remat"] is True and config["engine"]["rows_per_chip"] == 2
    assert set(config["limits_why"]) >= set(config["limits"])
    assert set(config["limits"]) == set(train_o2_loop.ALL_NUMBERS)
    tries = config["aot_memory"]["tries"]
    chosen = [t for t in tries if t.get("chosen")]
    assert len(chosen) == 1 and chosen[0]["num_hidden_layers"] == config["num_hidden_layers"]
    assert chosen[0]["fits"] and chosen[0]["total_gb"] <= 16.91 - 0.5
    # every number of the catalog row's config that is not reduced, as published
    catalog = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
               "max_position_embeddings": 65536, "max_window_layers": 48,
               "num_attention_heads": 16, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
               "rope_theta": 1000000, "total_ut_steps": 4, "early_exit_threshold": 1,
               "vocab_size": 49152}
    assert {k: config[k] for k in catalog} == catalog
    assert config["layer_types"] == ["full_attention"] * 48
    assert (config["hidden_act"], config["model_type"], config["tie_word_embeddings"],
            config["rope_scaling"], config["sliding_window"], config["use_sliding_window"]) == (
        "silu", "ouro", False, None, None, False)
    d = loop_ref.dims(config)
    assert d["vocab_rows"] == 49152 and d["entropy_beta"] == 0.1


def test_the_reference_imports_nothing_of_the_program():
    for name in ("loop_ref", "afmoe_ref", "gpt_ref"):       # and what it takes from the others
        with open(os.path.join(HERE, "reference", name + ".py")) as f:
            text = f.read()
        assert "apex_tpu" not in text.replace("``apex_tpu", ""), name
