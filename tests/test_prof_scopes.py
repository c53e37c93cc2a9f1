"""``apex_tpu.prof.scopes``: the join of executed operations to the program's
spans. The paths are spelt as jax 0.9 spells them in a compiled module (the
cases below are lines of the five cells' step programs compiled for a v5e);
the compiled program is a small jitted step with nested spans, one
``jax.checkpoint``ed block and a ``shard_map``, on the CPU backend."""
import ast
import glob
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.monitor import spans as monitor_spans
from apex_tpu.prof import scopes
from apex_tpu.prof.__main__ import main as prof_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REMAT = "jit(call)/amp/fwd_bwd/transpose(jvp(amp/fwd_bwd))/jvp()/checkpoint/"
PATH_CASES = [
    ("jit(run)/shard_map/amp/fwd_bwd/jvp(hybrid/attn_win)/mix/proj_in/dot_general",
     ("amp/fwd_bwd", "hybrid/attn_win", "mix/proj_in"), "fwd"),
    ("jit(call)/amp/fwd_bwd/jvp(hybrid/attn)/reduce_sum", ("amp/fwd_bwd", "hybrid/attn"), "fwd"),
    ("jit(call)/amp/fwd_bwd/transpose(jvp(hybrid/attn))/dot_general",
     ("amp/fwd_bwd", "hybrid/attn"), "bwd"),
    (REMAT + "rematted_computation/hybrid/attn_mla/mla/down/dot_general",
     ("amp/fwd_bwd", "hybrid/attn_mla", "mla/down"), "recompute"),
    (REMAT + "rematted_computation/hybrid/moe/jvp(moe/experts)/split",
     ("amp/fwd_bwd", "hybrid/moe", "moe/experts"), "recompute"),
    (REMAT + "hybrid/moe/transpose(jvp(moe/experts))/convert_element_type",
     ("amp/fwd_bwd", "hybrid/moe", "moe/experts"), "bwd"),
    (REMAT + "hybrid/moe/moe/route/jit(take_along_axis)/scatter-add",
     ("amp/fwd_bwd", "hybrid/moe", "moe/route"), "bwd"),
    (REMAT + "hybrid/gdn/mix/place/mul", ("amp/fwd_bwd", "hybrid/gdn", "mix/place"), "bwd"),
    ("jit(call)/amp/fwd_bwd/transpose(amp/fwd_bwd)/jvp(hybrid/unembed_xent)/convert_element_type",
     ("amp/fwd_bwd", "hybrid/unembed_xent"), "bwd"),
    ("jit(call)/amp/fwd_bwd/jvp(gpt/attn)/flash_fwd_packed/pallas_call",
     ("amp/fwd_bwd", "gpt/attn"), "fwd"),
    ("jit(call)/amp/fwd_bwd/jvp(hybrid/dense)/jit(silu)", ("amp/fwd_bwd", "hybrid/dense"), "fwd"),
    ("jit(call)/amp/fwd_bwd/transpose(jvp())/pad", ("amp/fwd_bwd",), "bwd"),
    # a span's name is whole segments: no known span is a prefix of these
    ("jit(f)/amp/fwd_bwd/jvp(hybrid/attn_window)/dot_general", ("amp/fwd_bwd",), "fwd"),
    ("jit(f)/amp/fwd_bwd/jvp(my_hybrid/attn)/dot_general", ("amp/fwd_bwd",), "fwd"),
    # outside amp/fwd_bwd: the update's, whatever it holds
    ("jit(call)/amp/apply_master/jit(_where)/select_n", ("amp/apply_master",), "update"),
    ("jit(call)/fused_adam/update/mul", ("fused_adam/update",), "update"),
    ("jit(call)/amp/unscale_check/reduce_and", ("amp/unscale_check",), "update"),
    ("jit(run)/shard_map/ddp/allreduce/psum", ("ddp/allreduce",), "update"),
    ("jit(run)/shard_map/psum", (), "update"),
    ("", (), "update"),
]


@pytest.mark.parametrize("op_name,spans,phase", PATH_CASES)
def test_parse_path_keeps_nesting_order_and_gives_the_phase(op_name, spans, phase):
    assert scopes.parse_path(op_name) == (spans, phase)


@pytest.mark.parametrize("name,family", [
    ("flash_bwd_bshd_fused.1", "flash_bwd_bshd_fused"), ("fusion.1364", "fusion"),
    ("copy", "copy"), ("select_n.1368.clone.1", "select_n"), ("broadcast.223.clone", "broadcast"),
    ("fusion.437.remat", "fusion.remat"), ("fusion.remat", "fusion.remat"),
    ("wrapped_reduce-window", "wrapped_reduce-window")])
def test_family_drops_the_instruction_number(name, family):
    assert scopes.family(name) == family


# --- a compiled program --------------------------------------------------------

def _block(w, x):
    with monitor_spans.span("hybrid/attn_win"):
        with monitor_spans.span("mix/proj_in"):
            h = jnp.dot(x, w)
        with monitor_spans.span("mix/place"):
            h = jnp.tanh(h)
        return x + h


def _loss(w, x):
    with monitor_spans.span("hybrid/embed"):
        x = x * 2
    x = jax.checkpoint(_block)(w, x)
    with monitor_spans.span("hybrid/attn"):
        x = jnp.dot(x, w)
    with monitor_spans.span("hybrid/unembed_xent"):
        return jnp.sum(jnp.sin(x) ** 2)


def _run(w, x):
    with monitor_spans.span("amp/fwd_bwd"):
        loss, g = jax.value_and_grad(_loss)(w, x)
    g = jax.lax.pmean(g, "dp")
    with monitor_spans.span("amp/apply_master"):
        w = w - 0.1 * g
    return w, loss


@pytest.fixture(scope="module")
def step():
    """(the live jitted step, its compiled text, the names of its entry
    computation: what a device trace of it would hold)."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    fn = jax.jit(jax.shard_map(_run, mesh=mesh, in_specs=(P(), P("dp")),
                               out_specs=(P(), P()), check_vma=False))
    w, x = jnp.ones((64, 64)), jnp.ones((8, 64))
    jax.block_until_ready(fn(w, x))
    text = fn.lower(w, x).compile().as_text()
    entry = text[text.index("ENTRY "):]
    names = [line.split(" = ")[0].split()[-1].lstrip("%")
             for line in entry.splitlines()[1:] if " = " in line]
    executed = [n for n in names if not n.startswith(("param", "constant", "tuple"))]
    yield fn, text, executed


def test_scope_table_finds_every_executed_instruction(step):
    _, text, executed = step
    table = scopes.scope_table(text)
    assert len(executed) >= 12 and set(executed) <= set(table)
    assert len(table) == len(re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = ", text, re.M))
    # every matrix product stands in a span of the program; all four phases
    # are in the module (the recomputed tanh inside a fusion of the backward)
    products = [n for n in executed if n.startswith("dot")]
    assert len(products) == 5
    assert all(len(scopes.parse_path(table[n])[0]) >= 2 for n in products)
    phases = {scopes.parse_path(path)[1] for path in table.values()}
    assert phases == set(scopes.PHASES)
    inner = {scopes.parse_path(table[n])[0][-1] for n in executed if table[n]
             and scopes.parse_path(table[n])[0]}
    assert {"hybrid/attn", "hybrid/attn_win", "mix/proj_in", "mix/place",
            "hybrid/unembed_xent", "amp/apply_master"} <= inner


def test_live_scope_table_picks_the_step_among_the_live_modules(step):
    fn, text, executed = step
    other = jax.jit(lambda a: jnp.tanh(a) @ a)        # a second live module
    jax.block_until_ready(other(jnp.ones((8, 8))))
    ops_s = {name: 1.0 for name in executed}
    table = scopes.live_scope_table(ops_s)
    want = scopes.scope_table(text)
    assert table is not None and {n: table[n] for n in executed} == {n: want[n] for n in executed}
    del fn, other


def test_live_scope_table_is_none_for_foreign_names(step, caplog):
    with caplog.at_level("WARNING", logger=scopes.logger.name):
        assert scopes.live_scope_table({"fusion.999999": 1.0, "flash_fwd_nowhere.7": 2.0}) is None
    assert len(caplog.records) == 1 and "no scope table" in caplog.records[0].getMessage()
    # a step's names among many foreign seconds: under the share asked for
    _, _, executed = step
    ops_s = dict({name: 1.0 for name in executed}, **{"flash_fwd_nowhere.7": 100.0})
    assert scopes.live_scope_table(ops_s) is None
    assert scopes.live_scope_table(dict(ops_s, **{"flash_fwd_nowhere.7": 0.5})) is not None


# seconds in 1024ths: every sum below is exact in binary floating point
HAND_TABLE = {
    "fusion.1": "jit(run)/amp/fwd_bwd/jvp(hybrid/attn)/mix/proj_in/dot_general",
    "fusion.2": "jit(run)/amp/fwd_bwd/jvp(hybrid/attn_win)/mix/proj_in/dot_general",
    "flash_fwd_bshd.3": "jit(run)/amp/fwd_bwd/jvp(hybrid/attn)/flash_fwd_bshd/pallas_call",
    "flash_fwd_bshd.4": REMAT.replace("call", "run") + "rematted_computation/hybrid/attn/"
                        "flash_fwd_bshd/pallas_call",
    "flash_bwd_bshd_fused.1": REMAT.replace("call", "run") + "hybrid/attn/transpose(jvp("
                              "flash_bwd_bshd_fused))/pallas_call",
    "fusion.5": "jit(run)/amp/fwd_bwd/jvp(hybrid/attn)/add",
    "fusion.6": "jit(run)/amp/fwd_bwd/jvp()/convert_element_type",
    "fusion.7": "jit(run)/amp/apply_master/sub",
    "copy.8": "",
    "psum.9": "jit(run)/shard_map/psum",
}
HAND_OPS = {"fusion.1": 8 / 1024, "fusion.2": 16 / 1024, "flash_fwd_bshd.3": 32 / 1024,
            "flash_fwd_bshd.4": 32 / 1024, "flash_bwd_bshd_fused.1": 64 / 1024,
            "fusion.5": 2 / 1024, "fusion.6": 1 / 1024, "fusion.7": 4 / 1024,
            "copy.8": 3 / 1024, "psum.9": 5 / 1024, "fusion.4711": 7 / 1024}   # the last: no entry


def test_rollup_totals_self_times_and_no_scope_add_up_exactly():
    rolled = scopes.rollup(HAND_OPS, HAND_TABLE, steps=2)
    ms = lambda k: 1e3 * k / 1024 / 2  # noqa: E731
    assert rolled["steps"] == 2 and rolled["busy_ms"] == 1e3 * sum(HAND_OPS.values()) / 2
    own = sum(sum(row["self_ms"].values()) for row in rolled["spans"].values())
    bare = rolled["ops"][scopes.NO_SCOPE]
    assert own + sum(bare.values()) == rolled["busy_ms"] == sum(rolled["phases"].values())
    assert bare == {"fusion": ms(7), "psum": ms(5), "copy": ms(3)}
    spans = rolled["spans"]
    assert list(spans) == ["amp/apply_master", "amp/fwd_bwd", "amp/fwd_bwd/hybrid/attn",
                           "amp/fwd_bwd/hybrid/attn/mix/proj_in", "amp/fwd_bwd/hybrid/attn_win",
                           "amp/fwd_bwd/hybrid/attn_win/mix/proj_in"]
    attn = spans["amp/fwd_bwd/hybrid/attn"]
    assert attn["total_ms"] == {"fwd": ms(8 + 32 + 2), "recompute": ms(32), "bwd": ms(64),
                                "update": 0.0}
    assert attn["self_ms"] == {"fwd": ms(32 + 2), "recompute": ms(32), "bwd": ms(64), "update": 0.0}
    step = spans["amp/fwd_bwd"]
    assert sum(step["self_ms"].values()) == ms(1)
    assert sum(step["total_ms"].values()) == ms(8 + 16 + 32 + 32 + 64 + 2 + 1)
    assert sum(spans["amp/fwd_bwd/hybrid/attn_win"]["self_ms"].values()) == 0.0
    assert rolled["phases"] == {"fwd": ms(8 + 16 + 32 + 2 + 1), "recompute": ms(32), "bwd": ms(64),
                                "update": ms(4 + 3 + 5 + 7)}
    assert rolled["ops"]["amp/fwd_bwd/hybrid/attn"] == {
        "flash_bwd_bshd_fused": ms(64), "flash_fwd_bshd": ms(64), "fusion": ms(2)}


@pytest.mark.parametrize("spans,minus,want", [
    (("hybrid/attn",), (), 8 + 32 + 32 + 64 + 2),
    (("hybrid/attn",), ("flash_",), 8 + 2),
    (("hybrid/attn", "hybrid/attn_win"), ("flash_fwd",), 8 + 64 + 2 + 16),
    (("mix/proj_in",), (), 8 + 16),
    (("amp/fwd_bwd",), (), 8 + 16 + 32 + 32 + 64 + 2 + 1),
    ((scopes.NO_SCOPE,), (), 7 + 5 + 3),
    (("hybrid/gdn",), (), None),
    (("hybrid/att",), (), None),
])
def test_span_ms_sums_whole_spans_less_the_named_operations(spans, minus, want):
    got = scopes.span_ms(scopes.rollup(HAND_OPS, HAND_TABLE), spans, minus)
    assert got == (None if want is None else 1e3 * want / 1024)


def test_format_rollup_prints_every_span_nested_and_the_books():
    text = scopes.format_rollup(scopes.rollup(HAND_OPS, HAND_TABLE))
    lines = text.splitlines()
    assert lines[0].split() == ["span", "total", "self", *scopes.PHASES]
    assert any(line.startswith("    mix/proj_in ") for line in lines)       # two spans deep
    assert any(line.startswith("  hybrid/attn_win ") for line in lines)
    assert lines[-1].split()[0] == "busy"
    assert float(lines[-1].split()[1]) == pytest.approx(1e3 * sum(HAND_OPS.values()), abs=1e-3)


# --- the raw .xplane.pb ---------------------------------------------------------

@pytest.fixture(scope="module")
def schema():
    if importlib.util.find_spec("tensorflow") is None:
        pytest.skip("no tensorflow here to take the .xplane.pb schema from")
    return scopes.xplane_schema()


def _space(schema, device="/device:TPU:0"):
    """One chip, an ``XLA Ops`` line of three events over two operations: the
    first's ``tf_op`` held inline and spelt as the chip spells it (``op_name:``),
    the second's interned as a stat metadata's name; a ``Steps`` line and a
    host plane beside them, which are not read."""
    space = schema.XSpace()
    plane = space.planes.add(name=device)
    plane.stat_metadata[1].name = "tf_op"
    plane.stat_metadata[2].name = "jit(run)/amp/fwd_bwd/jvp(gpt/attn)/flash_fwd_packed/pallas_call"
    plane.stat_metadata[3].name = "flops"
    first = plane.event_metadata[1]
    first.name = "%fusion.263 = (bf16[2048]{0}, f32[2,8192]{1,0}) fusion(bf16[16384,2048]{1,0} %p)"
    first.stats.add(metadata_id=3, uint64_value=7)
    first.stats.add(metadata_id=1,
                    str_value="jit(run)/amp/fwd_bwd/transpose(jvp(gpt/mlp))/dot_general:")
    second = plane.event_metadata[2]
    second.name = "%flash_fwd_packed.8 = bf16[2,8192,2048]{2,1,0} custom-call(bf16[8]{0} %b)"
    second.stats.add(metadata_id=1, ref_value=2)
    ops = plane.lines.add(name="XLA Ops")
    for metadata_id, ps in ((1, 3_000_000_000), (2, 2_000_000_000), (1, 1_000_000_000)):
        ops.events.add(metadata_id=metadata_id, duration_ps=ps)
    plane.lines.add(name="Steps").events.add(metadata_id=1, duration_ps=9_000_000_000)
    space.planes.add(name="/host:CPU").lines.add(name="python").events.add(
        metadata_id=1, duration_ps=5)
    return space


def test_xplane_route_reads_both_spellings_of_tf_op(schema, tmp_path, capsys):
    run_dir = tmp_path / "plugins" / "profile" / "2026_01_01"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(_space(schema).SerializeToString())
    ops_s, table, chips = scopes.read_xplane(str(tmp_path))
    assert chips == 1 and ops_s == {"fusion.263": pytest.approx(4e-3),
                                    "flash_fwd_packed.8": pytest.approx(2e-3)}
    assert table == {"fusion.263": "jit(run)/amp/fwd_bwd/transpose(jvp(gpt/mlp))/dot_general",
                     "flash_fwd_packed.8":
                         "jit(run)/amp/fwd_bwd/jvp(gpt/attn)/flash_fwd_packed/pallas_call"}
    rolled = scopes.rollup(ops_s, table, steps=2)
    assert scopes.span_ms(rolled, ("gpt/mlp",)) == pytest.approx(2.0)
    assert scopes.span_ms(rolled, ("gpt/attn",), minus=("flash_",)) is None
    assert rolled["phases"]["bwd"] == pytest.approx(2.0) and rolled["busy_ms"] == pytest.approx(3.0)
    # the operator's command: the logdir, and the file itself
    for target in (str(tmp_path), str(run_dir / "host.xplane.pb")):
        assert prof_main([target, "--steps", "2"]) == 0
        out = capsys.readouterr().out
        assert "1 chip(s)" in out and "gpt/mlp" in out and ":: flash_fwd_packed" in out


def test_prof_cli_exits_2_on_a_trace_without_a_device_plane(schema, tmp_path, capsys):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(_space(schema, device="/host:other").SerializeToString())
    assert prof_main([str(path)]) == 2
    err = capsys.readouterr().err
    assert err.count("\n") == 1 and "/device:TPU" in err


# --- no span falls out of the table ---------------------------------------------

SPAN_SOURCES = sorted(
    glob.glob(os.path.join(ROOT, "apex_tpu", "models", "*.py"))
    + glob.glob(os.path.join(ROOT, "apex_tpu", "amp", "*.py"))
    + [os.path.join(ROOT, "apex_tpu", *p) for p in (
        ("transformer", "moe.py"), ("optimizers", "_fused.py"), ("parallel", "distributed.py"))])
FUSED_OPTIMIZERS = ("fused_adam", "fused_lamb", "fused_sgd", "fused_novograd", "fused_adagrad")


def spans_entered(path):
    """The names a file hands ``span(...)``: a string literal as it stands,
    an f-string as ``*`` + its literal tail; and every string constant of
    the file that is spelt like a span of the table's families (a name held
    in a dict and picked by kind)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    families = "|".join(sorted({s.split("/")[0] for s in scopes.SPANS}))
    spelt = re.compile(rf"^(?:{families})/[a-z_0-9]+$")
    literal, other = set(), 0
    for node in ast.walk(tree):
        if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and spelt.match(node.value)):
            literal.add(node.value)
        is_span = isinstance(node, ast.Call) and (
            getattr(node.func, "attr", None) == "span" or getattr(node.func, "id", None) == "span")
        if not is_span or not node.args:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            literal.add(arg.value)
        elif isinstance(arg, ast.JoinedStr) and isinstance(arg.values[-1], ast.Constant):
            literal.add("*" + arg.values[-1].value)
        else:
            other += 1
    return literal, other


@pytest.mark.parametrize("path", SPAN_SOURCES, ids=lambda p: os.path.relpath(p, ROOT))
def test_every_span_the_program_enters_is_in_the_table(path):
    literal, _ = spans_entered(path)
    for name in literal:
        if name.startswith("*"):        # f"{name}/update": every fused optimizer's
            assert all(opt + name[1:] in scopes.SPANS for opt in FUSED_OPTIMIZERS), name
        else:
            assert name in scopes.SPANS, f"{name} ({os.path.relpath(path, ROOT)}) is not in SPANS"


def test_the_table_holds_no_span_the_program_does_not_enter():
    entered = set().union(*(spans_entered(p)[0] for p in SPAN_SOURCES))
    entered |= {opt + "/update" for opt in FUSED_OPTIMIZERS if "*/update" in entered}
    assert set(scopes.SPANS) == entered - {"*/update"}
    # the one call whose name is no literal picks it from a dict of literals
    assert sum(spans_entered(p)[1] for p in SPAN_SOURCES) == 1


def _hybrid_models():
    from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel

    small = dict(vocab_size=256, hidden_size=128, num_heads=2, num_kv_heads=1, head_dim=64,
                 rotary_dim=16, expert_ffn=128, shared_ffn=128, router_experts=8,
                 experts_held=(0, 4), top_k=2,
                 attention_impl="xla", delta_impl="xla", experts_impl="xla")
    return {
        "qwen3_next": HybridDecoderModel(HybridDecoderConfig(
            layer_types=("linear", "full"), linear_key_heads=1, linear_value_heads=2,
            linear_key_dim=64, linear_value_dim=64, **small)),
        "afmoe": HybridDecoderModel(HybridDecoderConfig(
            layer_types=("window", "full"), ffn_types=("dense", "moe"), window=32,
            dense_ffn=128, **small)),
        "deepseek_v2": HybridDecoderModel(HybridDecoderConfig(
            layer_types=("latent", "latent"), ffn_types=("dense", "moe"), qk_nope_dim=64,
            qk_rope_dim=32, v_head_dim=64, kv_lora_rank=64, dense_ffn=128, **small)),
    }


@pytest.mark.parametrize("block", ["qwen3_next", "afmoe", "deepseek_v2"])
def test_hybrid_steps_carry_the_mixers_inner_spans(block):
    model = _hybrid_models()[block]
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 64), jnp.int32)
    text = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(p, tokens, tokens))).lower(
        params).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    inner = {s for p in paths for s in scopes.parse_path(p)[0]}
    want = {"mix/place", "mix/proj_out"} | (
        {"mla/down", "mla/up"} if block == "deepseek_v2" else {"mix/proj_in"})
    assert want <= inner
    # a mixer's inner spans stand inside its block's span, never beside it
    for p in paths:
        spans = scopes.parse_path(p)[0]
        if spans and spans[-1].startswith(("mix/", "mla/")):
            assert len(spans) >= 2 and spans[-2].startswith("hybrid/"), p
