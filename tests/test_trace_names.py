"""The names a device trace can read: every Pallas kernel carries a
string-literal ``name=`` (so Mosaic custom-calls print as ``flash_fwd…``,
not ``jvp__.N``; a banded call chooses between two literals), and the trainer step's layer boundaries enter
``monitor.span`` scopes that reach the lowered program's metadata whether
or not the monitor is on."""
import ast
import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

PALLAS_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "apex_tpu", "ops", "pallas")
KERNEL_FILES = sorted(os.path.basename(p) for p in glob.glob(os.path.join(PALLAS_DIR, "*.py"))
                      if os.path.basename(p) != "__init__.py")
# readers match by prefix: flash_fwd*, flash_bwd*, xentropy*, gdn_fwd*, gdn_bwd*,
# moe_gmm*; the rest by name (conv_silu_*, gated_norm_*: the stages around the
# rule, which no reader's part may match)
EXPECTED = {
    "attention.py": {
        "flash_fwd", "flash_fwd_packed", "flash_fwd_bshd",
        "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_bshd_dq", "flash_bwd_bshd_dkv",
        "flash_bwd_packed_fused", "flash_bwd_packed_dq", "flash_bwd_packed_dkv",
        "flash_bwd_dbias", "flash_bwd_dtable",
        # the seq-major kernels on a sliding window: still flash_fwd* / flash_bwd*
        "flash_fwd_bshd_win", "flash_bwd_bshd_win_dq", "flash_bwd_bshd_win_dkv"},
    "xentropy.py": {"xentropy_stats"},
    "decode_attention.py": {"decode_attn", "decode_attn_paged"},
    "layer_norm.py": {"ln_fwd", "ln_bwd"},
    "matmul.py": {"matmul_bias_act"},
    "softmax.py": {"softmax_fwd", "softmax_bwd"},
    "sampling.py": {"fused_sample"},
    "verify.py": {"fused_verify", "fused_verify_tree"},
    "gated_delta_rule.py": {"gdn_fwd", "gdn_bwd"},
    "delta_mixer.py": {"conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd", "gated_norm_bwd"},
    "grouped_matmul.py": {"moe_gmm", "moe_gmm_dx", "moe_gmm_dw"},
}
SCOPES = ("amp/fwd_bwd", "amp/unscale_check", "amp/apply_master", "fused_adam/update",
          "gpt/embed", "gpt/attn", "gpt/mlp", "gpt/unembed_xent", "ddp/allreduce")


def literal_names(filename):
    """The ``name=`` of every ``pallas_call(...)`` in the file (both where it
    is ``"a" if ... else "b"``), ``None`` where it is missing or no string
    literal."""
    with open(os.path.join(PALLAS_DIR, filename)) as f:
        tree = ast.parse(f.read())
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and getattr(node.func, "attr", None) == "pallas_call":
            kw = {k.arg: k.value for k in node.keywords}.get("name")
            for one in ([kw.body, kw.orelse] if isinstance(kw, ast.IfExp) else [kw]):
                ok = isinstance(one, ast.Constant) and isinstance(one.value, str)
                out.append(one.value if ok else None)
    return out


@pytest.mark.parametrize("filename", KERNEL_FILES)
def test_every_pallas_call_has_a_literal_name(filename):
    names = literal_names(filename)
    assert names and None not in names
    assert len(set(names)) == len(names)
    assert set(names) == EXPECTED[filename]


def test_kernel_names_are_distinct_across_the_package():
    assert set(KERNEL_FILES) == set(EXPECTED)
    names = [n for f in KERNEL_FILES for n in literal_names(f)]
    assert len(names) == 35 and len(set(names)) == 35


def kernel_names(jaxpr):
    """Names of the ``pallas_call`` equations, through every sub-jaxpr."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += kernel_names(sub)
    return out


@pytest.mark.parametrize("layout,shape,names", [
    ("bhsd", (1, 2, 128, 64), ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    ("bshd", (1, 128, 2, 128), ["flash_fwd_bshd", "flash_bwd_bshd_dq", "flash_bwd_bshd_dkv"]),
])
def test_flash_equations_carry_their_names(layout, shape, names):
    from apex_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas", layout=layout).sum()

    q = jnp.ones(shape, jnp.float32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, q, q)
    assert kernel_names(jaxpr.jaxpr) == names


def test_banded_flash_equations_carry_their_own_names():
    """A windowed call's kernels are told apart by name, and every accepted
    flash reader's part (``flash_fwd`` / ``flash_bwd``) is still in them."""
    from apex_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas", layout="bshd", window=64).sum()

    q = jnp.ones((1, 256, 2, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, q, q)
    names = kernel_names(jaxpr.jaxpr)
    assert names == ["flash_fwd_bshd_win", "flash_bwd_bshd_win_dq", "flash_bwd_bshd_win_dkv"]
    assert "flash_fwd" in names[0] and all("flash_bwd" in n for n in names[1:])


def test_delta_mixer_equations_carry_their_names():
    """One delta-rule mixer, forward and gradient: the convolution a call a
    piece (q, k, v), the rule, the gated norm — and no name of the two stages
    around the rule holds a part a reader matches (``gdn_fwd_ms`` and
    ``gdn_bwd_ms`` keep meaning the rule)."""
    from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel

    model = HybridDecoderModel(HybridDecoderConfig(
        vocab_size=64, hidden_size=128, layer_types=("linear",), linear_key_heads=1,
        linear_value_heads=2, delta_impl="pallas"))
    p = jax.tree.map(lambda a: a[0], model.init(jax.random.PRNGKey(0))["layers"]["gdn"])
    x = jnp.ones((1, 64, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: model._delta_mixer(p, x).sum(), argnums=(0, 1)))(p, x)
    assert kernel_names(jaxpr.jaxpr) == (
        ["conv_silu_fwd"] * 3 + ["gdn_fwd", "gated_norm_fwd", "gated_norm_bwd", "gdn_bwd"]
        + ["conv_silu_bwd"] * 3)
    for name in EXPECTED["delta_mixer.py"]:
        assert not any(part in name for part in ("flash", "xentropy", "gdn", "moe_gmm"))


SPLIT = ["flash_bwd_packed_dq", "flash_bwd_packed_dkv"]


@pytest.mark.parametrize("seq,biased,names", [
    (128, False, ["flash_fwd_packed", "flash_bwd_packed_fused"]),   # one block
    (2048, False, ["flash_fwd_packed", "flash_bwd_packed_fused"]),  # the same kernel at 2 x 2 blocks
    (2048, True, ["flash_fwd_packed", *SPLIT, "flash_bwd_dbias"]),  # a bias keeps the split
])
def test_packed_flash_equations_carry_their_names(seq, biased, names):
    """The fused projection + attention block that ``GPTModel`` takes at
    heads of 128 (the benchmark's ``sc1b-train-8k``)."""
    from apex_tpu.ops.attention import fused_qkv_attention

    h, h_kv, d, H = 2, 1, 128, 256
    bias = jnp.zeros((1, seq, seq)) if biased else None

    def loss(x, w_qkv, b_qkv, w_out):
        return fused_qkv_attention(x, w_qkv, b_qkv, w_out, bias, None, None,
                                   h, h_kv, d, d ** -0.5, True).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(
        jnp.ones((1, seq, H)), jnp.ones(((h + 2 * h_kv) * d, H)),
        jnp.ones(((h + 2 * h_kv) * d,)), jnp.ones((H, h * d)))
    assert kernel_names(jaxpr.jaxpr) == names


@pytest.mark.parametrize("seq,names", [
    (1024, ["flash_bwd_packed_fused"]),   # the flagship recipe: one block
    (8192, ["flash_bwd_packed_fused"]),   # sc1b-train-8k: 2 x 4 MB of fp32 accumulators
    (262144, SPLIT),                      # 2 x 128 MB do not fit a v5e's VMEM
])
def test_packed_backward_is_picked_by_the_vmem_its_accumulators_need(seq, names):
    """No option picks the one-pass backward: the shapes do. Its whole-
    sequence fp32 dk/dv accumulators must fit the VMEM a kernel may ask
    for; a sequence too long for them keeps the dq/dkv split."""
    from apex_tpu.ops.pallas import attention as pk

    h, h_kv, d = 16, 1, 128

    def backward(qkv, o, lse, do):
        return pk.flash_bwd_packed(qkv, h, h_kv, d, o, lse, do, scale=d ** -0.5,
                                   causal=True, interpret=True)

    bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(backward)(
        bf16((1, seq, (h + 2 * h_kv) * d)), bf16((1, seq, h * d)),
        jax.ShapeDtypeStruct((1, h, seq, 8), jnp.float32), bf16((1, seq, h * d)))
    assert kernel_names(jaxpr.jaxpr) == names
    fits = pk._fused_bwd_vmem_bytes(seq, d, 1024, 1024, 2) <= pk._VMEM_CAP
    assert fits == (names != SPLIT)


def test_cross_entropy_equation_carries_its_name():
    from apex_tpu.transformer import tensor_parallel as tp_lib

    def loss(logits, targets):
        return tp_lib.vocab_parallel_cross_entropy(
            logits, targets, axis_name=None, impl="pallas").sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(
        jnp.ones((8, 128), jnp.float32), jnp.zeros((8,), jnp.int32))
    assert kernel_names(jaxpr.jaxpr) == ["xentropy_stats"]


def lowered_toy_step():
    """The README's O2 step at the benchmark's toy size, its gradients
    exchanged through the DDP wrapper: the lowered text with its location
    metadata."""
    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.parallel import distributed, mesh as mesh_lib

    mesh_lib.initialize_model_parallel(devices=jax.devices()[:2])
    model = GPTModel(GPTConfig(vocab_size=256, max_seq_len=128, hidden_size=64,
                               ffn_hidden_size=128, num_layers=2, num_heads=4,
                               num_kv_heads=1, tp_size=1, scan_layers=False))
    opt = fused_adam(3e-4)

    def run(master, opt_state, scaler, tokens, targets):
        loss, (grads, finite, scaler) = amp.scaled_value_and_grad(
            model.loss_fn)(scaler, master.model, tokens, targets)
        grads = distributed.all_reduce_gradients(grads)
        updates, opt_state = opt.update(grads, opt_state, master.master)
        master = amp.apply_updates_with_master(master, updates, grads_finite=finite)
        return master, opt_state, scaler, loss

    step = jax.jit(mesh_lib.shard_map(
        run, in_specs=(P(), P(), P(), P("dp"), P("dp")), out_specs=(P(), P(), P(), P())))
    master = amp.MasterWeights.create(model.init(jax.random.PRNGKey(0)),
                                      amp.get_policy("O2"))
    tokens = jnp.zeros((4, 64), jnp.int32)
    return step.lower(master, opt.init(master.master), amp.init_loss_scaler("dynamic"),
                      tokens, tokens).as_text(debug_info=True)


def scope_paths(text):
    """Every operation's named location in the lowered text (the scope path
    and the primitive, joined by ``/``). The call-stack frames beside them
    (a bare function name at a file and line) are left out: a function JAX
    found in its trace cache keeps the caller's line of its first trace."""
    return sorted(n for n in re.findall(r'loc\("([^"]*)"\(', text) if "/" in n)


def test_lowered_training_step_names_all_nine_scopes():
    from apex_tpu import monitor

    assert not monitor.enabled()
    text = lowered_toy_step()
    for scope in SCOPES:
        # "amp/unscale_check/add"; under a transform "jvp(gpt/attn)/add"
        assert f"{scope}/" in text or f"({scope})" in text, scope
    # backward operations keep the scope, beside their transform's
    assert re.search(r'loc\("[^"]*transpose\(jvp\([^"]*gpt/attn', text)


def test_monitor_on_or_off_the_step_lowers_to_the_same_text():
    import io

    from apex_tpu import monitor

    off = lowered_toy_step()
    sink = io.StringIO()
    monitor.enable(stream=sink)
    try:
        on = lowered_toy_step()
    finally:
        monitor.disable()
    assert scope_paths(on) == scope_paths(off) and len(scope_paths(on)) > 100
    assert '"gpt/attn"' not in sink.getvalue()     # records carry the whole path
    assert "amp/fwd_bwd/gpt/attn" in sink.getvalue()


@pytest.mark.parametrize("layout", ["per_tensor", "chunked"])
@pytest.mark.parametrize("name", ["fused_adam", "fused_lamb", "fused_sgd",
                                  "fused_novograd", "fused_adagrad"])
def test_every_fused_optimizer_names_its_update(name, layout):
    """The scope is entered where every fused optimizer's update is built
    (``optimizers/_fused.py``), under the optimizer's own name."""
    from apex_tpu import optimizers

    opt = getattr(optimizers, name)(1e-3, layout=layout)
    params = {"w": jnp.ones((8, 16)), "b": jnp.ones((16,))}
    text = jax.jit(opt.update).lower(params, opt.init(params), params).as_text(
        debug_info=True)
    assert f"{name}/update/" in text
