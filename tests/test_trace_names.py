"""The names a device trace can read: every Pallas kernel carries a
string-literal ``name=`` (so Mosaic custom-calls print as ``flash_fwd…``,
not ``jvp__.N``; a call that serves several layouts chooses between literals), and the trainer step's layer boundaries enter
``monitor.span`` scopes that reach the lowered program's metadata whether
or not the monitor is on."""
import ast
import functools
import glob
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.prof import scopes

PALLAS_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "apex_tpu", "ops", "pallas")
KERNEL_FILES = sorted(os.path.basename(p) for p in glob.glob(os.path.join(PALLAS_DIR, "*.py"))
                      if os.path.basename(p) != "__init__.py")
# readers match by prefix: flash_fwd*, flash_bwd*, xentropy*, gdn_fwd*, gdn_bwd*,
# ssd_fwd*, ssd_bwd*, kda_fwd*, kda_bwd*, moe_gmm*, moe_rows*; the rest by name (conv_silu_*, gated_norm_*: the
# stages around the rule and the scan, which no reader's part may match)
READER_PARTS = ("flash_fwd", "flash_bwd", "xentropy", "gdn_", "moe_gmm", "moe_rows", "conv_silu",
                "gated_norm", "ssd_", "kda_")
EXPECTED = {
    "attention.py": {
        "flash_fwd", "flash_fwd_packed", "flash_fwd_bshd",
        "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_bshd_dq", "flash_bwd_bshd_dkv",
        "flash_bwd_packed_fused", "flash_bwd_packed_dq", "flash_bwd_packed_dkv",
        "flash_bwd_dbias", "flash_bwd_dtable",
        # the one-pass backward over three arrays: the packed kernel's body
        "flash_bwd_bshd_fused",
        # the seq-major kernels on a sliding window: still flash_fwd* / flash_bwd*
        "flash_fwd_bshd_win", "flash_bwd_bshd_win_fused",
        "flash_bwd_bshd_win_dq", "flash_bwd_bshd_win_dkv",
        # two head widths and a second score term (latent attention): the same
        # two bodies, still flash_fwd* / flash_bwd*
        "flash_fwd_bshd_mla", "flash_bwd_bshd_mla_fused",
        # the packed pair at heads of 64, two to a lane tile: the same two
        # bodies once a head, still flash_fwd* / flash_bwd*
        "flash_fwd_packed_pair", "flash_bwd_packed_pair_fused"},
    "xentropy.py": {"xentropy_stats"},
    "decode_attention.py": {"decode_attn", "decode_attn_paged"},
    "layer_norm.py": {"ln_fwd", "ln_bwd"},
    "matmul.py": {"matmul_bias_act"},
    "softmax.py": {"softmax_fwd", "softmax_bwd"},
    "sampling.py": {"fused_sample"},
    "verify.py": {"fused_verify", "fused_verify_tree"},
    "gated_delta_rule.py": {"gdn_fwd", "gdn_bwd"},
    # the Mamba-2 state-space scan: ssd_*, which no older reader's part matches
    "ssd.py": {"ssd_fwd", "ssd_bwd"},
    # the delta rule with a decay a key channel: kda_*, never gdn_*
    "kda.py": {"kda_fwd", "kda_bwd"},
    "delta_mixer.py": {"conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd", "gated_norm_bwd"},
    "grouped_matmul.py": {"moe_gmm", "moe_gmm_dx", "moe_gmm_dw"},
    # the movements between tokens and expert rows: moe_rows*, never moe_gmm*
    "expert_rows.py": {"moe_rows_gather", "moe_rows_gather_dots", "moe_rows_pack",
                       "moe_rows_combine", "moe_rows_combine_weighted"},
    # the router's top k by rounds: inside ``moe/route``, under no kernel reader's part
    "top_rounds.py": {"moe_top_rounds"},
}
# the nine of the GPT step's: the trainer's own and the model's, out of the
# program's one table of spans
SCOPES = tuple(s for s in scopes.SPANS
               if s.startswith(("amp/", "gpt/", "ddp/", "fused_adam/")))
assert len(SCOPES) == 9


def literal_names(filename):
    """The ``name=`` of every ``pallas_call(...)`` in the file (every branch
    where it is ``"a" if ... else "b"``, nested or not), ``None`` where it is
    missing or no string literal."""
    with open(os.path.join(PALLAS_DIR, filename)) as f:
        tree = ast.parse(f.read())

    def branches(node):
        if isinstance(node, ast.IfExp):
            return branches(node.body) + branches(node.orelse)
        ok = isinstance(node, ast.Constant) and isinstance(node.value, str)
        return [node.value if ok else None]

    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and getattr(node.func, "attr", None) == "pallas_call":
            out += branches({k.arg: k.value for k in node.keywords}.get("name"))
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
    assert len(names) == 51 and len(set(names)) == 51


def all_eqns(jaxpr):
    """Every equation, through every sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from all_eqns(sub)


def pallas_eqns(jaxpr):
    return (eqn for eqn in all_eqns(jaxpr) if eqn.primitive.name == "pallas_call")


def kernel_names(jaxpr):
    return [eqn.params["name"] for eqn in pallas_eqns(jaxpr)]


@pytest.mark.parametrize("layout,shape,names", [
    ("bhsd", (1, 2, 128, 64), ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    ("bshd", (1, 128, 2, 128), ["flash_fwd_bshd", "flash_bwd_bshd_fused"]),
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
    flash reader's part (``flash_fwd`` / ``flash_bwd``; the banded readers'
    ``flash_fwd_bshd_win`` / ``flash_bwd_bshd_win``) is still in them."""
    from apex_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas", layout="bshd", window=64).sum()

    q = jnp.ones((1, 256, 2, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, q, q)
    names = kernel_names(jaxpr.jaxpr)
    assert names == ["flash_fwd_bshd_win", "flash_bwd_bshd_win_fused"]
    assert "flash_fwd" in names[0] and "flash_bwd" in names[1]
    assert "flash_fwd_bshd_win" in names[0] and "flash_bwd_bshd_win" in names[1]


def test_latent_flash_equations_carry_their_own_names():
    """A call with a second score term is told apart by name, and every
    accepted flash reader's part (``flash_fwd`` / ``flash_bwd``) is still in
    it; the banded readers' parts are not."""
    from apex_tpu.ops.attention import flash_attention

    def loss(q, k, v, q2, k2):
        return flash_attention(q, k, v, causal=True, impl="pallas", layout="bshd",
                               second=(q2, k2)).sum()

    q = jnp.ones((1, 256, 2, 128), jnp.float32)
    q2 = jnp.ones((1, 256, 2, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(
        q, q, q, q2, q2[:, :, :1])
    names = kernel_names(jaxpr.jaxpr)
    assert names == ["flash_fwd_bshd_mla", "flash_bwd_bshd_mla_fused"]
    assert "flash_fwd" in names[0] and "flash_bwd" in names[1]
    assert not any("_win" in n or "packed" in n for n in names)
    # the shared key rides at ONE head, head-major, and nothing is 192 or 256 wide
    shapes = [v.aval.shape for eqn in pallas_eqns(jaxpr.jaxpr) for v in eqn.invars]
    assert (1, 1, 256, 64) in shapes and (1, 2, 256, 64) in shapes
    assert all(s[-1] in (64, 128, 256, 8) for s in shapes if len(s) >= 3)


BSHD_SPLIT = ["flash_bwd_bshd_dq", "flash_bwd_bshd_dkv"]


def _bshd_backward_names(sq, sk, h, h_kv, d, dtype=jnp.bfloat16, **kw):
    """Names of the calls ``flash_bwd_bshd`` makes at these shapes (traced
    from shapes alone: nothing runs)."""
    from apex_tpu.ops.pallas import attention as pk

    def backward(q, k, v, o, lse, do):
        return pk.flash_bwd_bshd(q, k, v, o, lse, do, scale=d ** -0.5, interpret=True, **kw)

    arr = functools.partial(jax.ShapeDtypeStruct, dtype=dtype)
    jaxpr = jax.make_jaxpr(backward)(
        arr((1, sq, h, d)), arr((1, sk, h_kv, d)), arr((1, sk, h_kv, d)), arr((1, sq, h, d)),
        jax.ShapeDtypeStruct((1, h, sq, 8), jnp.float32), arr((1, sq, h, d)))
    return kernel_names(jaxpr.jaxpr)


@pytest.mark.parametrize("case,names", [
    ("plain", ["flash_bwd_bshd_fused"]),
    ("noncausal", ["flash_bwd_bshd_fused"]),
    ("window", ["flash_bwd_bshd_win_fused"]),
    ("bias", [*BSHD_SPLIT, "flash_bwd_dbias"]),          # dbias takes D as an operand
    ("bucketed", [*BSHD_SPLIT, "flash_bwd_dtable"]),
    ("longer_keys", BSHD_SPLIT),                          # sq != sk: cross attention, a ring's piece
    ("longer_keys_window", ["flash_bwd_bshd_win_dq", "flash_bwd_bshd_win_dkv"]),
])
def test_bshd_backward_is_picked_by_what_its_operands_show(case, names):
    """The seq-major backward is one pass unless the call carries a bias of
    either kind or two sequence lengths; every name it can take still holds
    the readers' parts."""
    sq, sk = (256, 512) if case.startswith("longer_keys") else (256, 256)
    kw = {"causal": case != "noncausal"}
    if case.endswith("window"):
        kw["window"] = 100
    if case == "bias":
        kw["bias"] = jnp.zeros((1, sq, sk))
    if case == "bucketed":
        kw["rel_bias"] = (jnp.zeros((2, 128)), jnp.zeros((2,), jnp.int32), (32, False, 128))
    got = _bshd_backward_names(sq, sk, 2, 1, 128, jnp.float32, **kw)
    assert got == names
    assert all("flash_bwd" in n for n in got)
    if "window" in kw:
        assert all("flash_bwd_bshd_win" in n for n in got)


@pytest.mark.parametrize("s,h,h_kv,d,window,names", [
    (8192, 32, 4, 128, None, ["flash_bwd_bshd_fused"]),       # trinity-train-8k's full layer: 52 MiB
    (8192, 32, 4, 128, 2048, ["flash_bwd_bshd_win_fused"]),   # its four banded layers
    (8192, 16, 2, 256, None, ["flash_bwd_bshd_fused"]),       # q3next-train-8k: 71 MiB
    (32768, 16, 2, 256, None, BSHD_SPLIT),                    # 2 x 32 MiB of accumulators and as much of outputs
    (262144, 32, 4, 128, 2048, ["flash_bwd_bshd_win_dq", "flash_bwd_bshd_win_dkv"]),
])
def test_bshd_backward_is_picked_by_the_vmem_its_accumulators_need(s, h, h_kv, d, window, names):
    """The packed layout's rule, from the same function: the whole-sequence
    fp32 dk/dv accumulators with the blocks and tile temporaries against the
    VMEM a kernel may ask for."""
    from apex_tpu.ops.pallas import attention as pk

    assert _bshd_backward_names(s, s, h, h_kv, d, causal=True, window=window) == names
    fits = pk._fused_bwd_vmem_bytes(s, d, 1024, 1024, 2) <= pk._VMEM_CAP
    assert fits == ("fused" in names[0])


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


def test_state_space_mixer_equations_carry_their_names():
    """One state-space mixer, forward and gradient: the convolution (with its
    bias) a call a piece (x, B, C), the scan, the gated norm — and the scan's
    names hold no part an older reader matches, nor do the older kernels'
    names hold ``ssd_`` (``ssd_fwd_ms`` / ``ssd_bwd_ms`` mean the scan alone)."""
    from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel

    model = HybridDecoderModel(HybridDecoderConfig(
        vocab_size=64, hidden_size=128, layer_types=("ssm",), ssm_heads=4, ssm_head_dim=64,
        ssm_state=128, ssm_groups=2, delta_impl="pallas"))
    p = jax.tree.map(lambda a: a[0], model.init(jax.random.PRNGKey(0))["layers"]["ssm"])
    x = jnp.ones((1, 128, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: model._ssm_mixer(p, x).sum(), argnums=(0, 1)))(p, x)
    assert kernel_names(jaxpr.jaxpr) == (
        ["conv_silu_fwd"] * 3 + ["ssd_fwd", "gated_norm_fwd", "gated_norm_bwd", "ssd_bwd"]
        + ["conv_silu_bwd"] * 3)
    for name in EXPECTED["ssd.py"]:
        assert not any(part in name for part in ("flash", "xentropy", "gdn", "moe_gmm", "moe_rows",
                                                 "conv_silu", "gated_norm"))
    others = set().union(*(names for f, names in EXPECTED.items() if f != "ssd.py"))
    assert not any("ssd_" in name for name in others)


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


@pytest.mark.parametrize("heads,kv_heads,names", [
    # gpt2-medium's kind: heads of 64, an even number — two to a lane tile
    (4, 4, ["flash_fwd_packed_pair", "flash_bwd_packed_pair_fused"]),
    # what the pair rule leaves on the head-batched route and the flat kernels
    (3, 3, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),      # an odd (local) count
    (4, 2, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),      # grouped kv
])
def test_gpt_at_heads_of_64_takes_the_pair_kernels(heads, kv_heads, names, monkeypatch):
    """``GPTModel._attention`` at heads of 64 chooses by ``packed_kernel_ok``
    from shapes alone: the pair kernels (every one of them holding the
    readers' ``flash_fwd`` / ``flash_bwd``) and never the dq | dkv split,
    or the route it had."""
    monkeypatch.setenv("APEX_TPU_PALLAS", "interpret")
    from apex_tpu.models import GPTConfig, GPTModel

    model = GPTModel(GPTConfig(vocab_size=128, max_seq_len=128, hidden_size=64 * heads,
                               ffn_hidden_size=128, num_layers=1, num_heads=heads,
                               num_kv_heads=kv_heads, tp_size=1, scan_layers=False,
                               attention_impl="flash", remat=False))
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(model.loss_fn))(params, tokens, tokens)
    got = [n for n in kernel_names(jaxpr.jaxpr) if n.startswith("flash")]
    assert got == names
    assert all(any(part in n for part in READER_PARTS[:2]) for n in got)


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


def program_text(closed):
    """A traced call as text: its equations (every kernel body among them)
    and, what ``str`` leaves out, each block's index map."""
    maps = [str(m.index_map_jaxpr) for eqn in pallas_eqns(closed.jaxpr)
            for m in eqn.params["grid_mapping"].block_mappings]
    return "\n".join([str(closed), *maps])


# sha256 of ``program_text`` of the calls below, read on the commit before the
# one-pass kernel's body took a window and a second layout (db094e1, jax 0.9.0).
# A change of the packed kernel's arithmetic moves them, and is then a change of
# ``sc1b-train-8k``'s program: read them again and say so.
PACKED_BEFORE_THE_BAND = {
    "causal": "7feb12415146cf8c",
    "noncausal": "44c355e4db3d695d",
    "kv_lens": "0caaa90fd00dc20a",
    "dropout": "464c0b7a5af07d86",
}


@pytest.mark.parametrize("case", sorted(PACKED_BEFORE_THE_BAND))
def test_packed_backward_without_a_window_is_the_program_it_was(case):
    """The body the packed and bshd layouts now share builds, for the packed
    layout (which has no window), the equations and index maps it built
    before: kernel body, grid, blocks, VMEM limit."""
    from apex_tpu.ops.pallas import attention as pk

    h, h_kv, d, seq = 4, 2, 128, 512
    kw = {"causal": case != "noncausal"}
    if case == "kv_lens":
        kw["kv_lens"] = jnp.array([5, 300], jnp.int32)
    if case == "dropout":
        kw.update(dropout_rate=0.1, dropout_seed=jnp.int32(3))

    def backward(qkv, o, lse, do):
        return pk.flash_bwd_packed(qkv, h, h_kv, d, o, lse, do, scale=d ** -0.5,
                                   interpret=True, bq=128, bk=128, **kw)

    bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    closed = jax.make_jaxpr(backward)(
        bf16((2, seq, (h + 2 * h_kv) * d)), bf16((2, seq, h * d)),
        jax.ShapeDtypeStruct((2, h, seq, 8), jnp.float32), bf16((2, seq, h * d)))
    assert kernel_names(closed.jaxpr) == ["flash_bwd_packed_fused"]
    text = program_text(closed)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PACKED_BEFORE_THE_BAND[case]


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


# --- the dropless expert layer's row movements ---------------------------------

def _expert_layer_operands(tokens=256, hidden=128, top_k=4, width=16, held=8, F=128):
    k = iter(jax.random.split(jax.random.PRNGKey(3), 8))
    n = lambda *s: 0.05 * jax.random.normal(next(k), s)  # noqa: E731
    p = {"router": n(hidden, width), "w_gate_up": n(held, hidden, 2 * F), "w_down": n(held, F, hidden),
         "shared_gate_up": n(hidden, 2 * F), "shared_down": n(F, hidden), "shared_mix": n(hidden)}
    return p, jax.random.normal(next(k), (tokens, hidden)), top_k, (0, held)


def _expert_layer_grads(impl):
    from apex_tpu.transformer import moe
    p, x, top_k, held = _expert_layer_operands()

    def loss(p, x):
        y = moe.dropless_moe_layer(p, x, top_k=top_k, experts_held=held, impl=impl)[0]
        return jnp.sum(y * jnp.cos(jnp.arange(y.size, dtype=y.dtype).reshape(y.shape))), y
    return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True), (p, x)


def test_expert_rows_move_by_kernels_that_no_moe_gmm_reader_matches():
    """With the kernels the layer's program holds the four ``moe_rows*`` calls
    beside the ``moe_gmm*`` ones and no gather whose result is (T, k, H) or a
    block's (R, H); the XLA composition holds both. ``moe_gmm_ms`` reads the
    part ``moe_gmm``, which no movement's name holds."""
    from apex_tpu.transformer import moe
    fn, args = _expert_layer_grads("pallas")
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    names = kernel_names(jaxpr)
    # the first block, then the loop over further blocks; backward the same
    assert names[:6] == ["moe_top_rounds", "moe_rows_gather", "moe_gmm", "moe_gmm", "moe_rows_pack",
                         "moe_rows_combine_weighted"]
    assert set(names) == (EXPECTED["expert_rows.py"] | EXPECTED["grouped_matmul.py"]
                          | EXPECTED["top_rounds.py"])
    for name in EXPECTED["expert_rows.py"]:
        assert "moe_rows" in name and "moe_gmm" not in name
    (_, x, top_k, held), hidden = _expert_layer_operands(), 128
    rows = moe.dropless_block_rows(x.shape[0], top_k, held[1], 16)
    wide = {x.shape[0] * top_k * hidden, rows * hidden}

    def wide_gathers(jaxpr):
        return [e for e in all_eqns(jaxpr) if e.primitive.name in ("gather", "scatter", "scatter-add")
                and e.outvars[0].aval.size in wide]
    assert not wide_gathers(jaxpr)
    fn, args = _expert_layer_grads("xla")
    assert len(wide_gathers(jax.make_jaxpr(fn)(*args).jaxpr)) >= 4     # both movements, both ways


def test_the_xla_composition_is_the_parents_bit_for_bit(monkeypatch):
    """``impl="xla"`` is the reference the kernels are held to, so it stays what
    the parent computed: the layer with the parent's two movements (copied here
    as they stood before the kernels) in place of today's gives the same output
    and the same gradients, bit for bit."""
    from apex_tpu.transformer import moe

    @jax.custom_vjp
    def rows_from_tokens(x, row_token, row_valid, pos, sel):
        return jnp.where(row_valid[:, None], x[row_token], 0).astype(x.dtype)

    def rows_bwd(res, g):
        row_token, row_valid, pos, sel = res
        picked = jnp.where(sel[..., None], g[pos], 0)
        dx = jnp.sum(picked.astype(jnp.float32), axis=1).astype(g.dtype)
        return dx, moe._f0(row_token), moe._f0(row_valid), moe._f0(pos), moe._f0(sel)

    rows_from_tokens.defvjp(lambda x, *r: (rows_from_tokens(x, *r), r), rows_bwd)

    @jax.custom_vjp
    def tokens_from_rows(y, weights, row_token, row_assign, row_valid, pos, sel):
        picked = jnp.where(sel[..., None], y[pos], 0).astype(jnp.float32)
        return jnp.einsum("tkh,tk->th", picked, weights).astype(y.dtype)

    def tokens_bwd(res, dout):
        y, weights, row_token, row_assign, row_valid, pos, sel = res
        d_row = jnp.where(row_valid[:, None], dout[row_token], 0)
        row_weight = weights.reshape(-1)[row_assign]
        dy = (d_row.astype(jnp.float32) * row_weight[:, None]).astype(y.dtype)
        dots = jnp.sum(d_row.astype(jnp.float32) * y.astype(jnp.float32), axis=-1)
        dweights = jnp.where(sel, dots[pos], 0.0).astype(weights.dtype)
        return (dy, dweights, moe._f0(row_token), moe._f0(row_assign), moe._f0(row_valid),
                moe._f0(pos), moe._f0(sel))

    tokens_from_rows.defvjp(lambda *a: (tokens_from_rows(*a), a), tokens_bwd)

    fn, args = _expert_layer_grads("xla")
    (_, y), (gp, gx) = jax.jit(fn)(*args)
    monkeypatch.setattr(moe, "_rows_from_tokens", lambda x, m, impl: rows_from_tokens(
        x, m["row_token"], m["row_valid"], m["pos"], m["sel"]))
    monkeypatch.setattr(moe, "_tokens_from_rows", lambda y, w, m, impl: tokens_from_rows(
        y, w, m["row_token"], m["row_assign"], m["row_valid"], m["pos"], m["sel"]))
    fn, args = _expert_layer_grads("xla")
    (_, y0), (gp0, gx0) = jax.jit(fn)(*args)
    for got, want in zip(jax.tree.leaves((y, gp, gx)), jax.tree.leaves((y0, gp0, gx0))):
        assert got.dtype == want.dtype and bool(jnp.all(got == want))
    assert float(jnp.max(jnp.abs(gp0["w_down"]))) > 0 and float(jnp.max(jnp.abs(gp0["router"]))) > 0


# --- the dropless expert layer's routing: no sort ------------------------------

@pytest.mark.parametrize("recomputed", [False, True])
@pytest.mark.parametrize("router", ["softmax", "sigmoid under a bias"])
def test_expert_layer_lowers_without_a_sort(router, recomputed):
    """The layer's forward and backward hold no ``sort`` and no ``top_k``
    operation (this compiler makes a full stable sort of either), as the
    stack runs it: plain, and under ``jax.checkpoint`` keeping ``EXPERTS_SAVED``."""
    from apex_tpu.models.hybrid_decoder import EXPERTS_SAVED
    from apex_tpu.transformer import moe
    p, x, top_k, held = _expert_layer_operands()
    kw = {} if router == "softmax" else dict(
        score="sigmoid", router_bias=jnp.linspace(-0.1, 0.1, p["router"].shape[-1]))

    def layer(p, x):
        y, aux = moe.dropless_moe_layer(p, x, top_k=top_k, experts_held=held, **kw)
        return jnp.sum(y) + aux["load_balance_loss"]
    if recomputed:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.save_only_these_names(*EXPERTS_SAVED))
    text = jax.jit(jax.value_and_grad(layer, argnums=(0, 1))).lower(p, x).as_text()
    operations = set(re.findall(r"\b(?:stablehlo|chlo|mhlo)\.(\w+)", text))
    assert {"reduce", "scatter", "while"} <= operations          # the text names its operations so
    assert not {o for o in operations if "sort" in o or "top" in o}
    assert "TopK" not in text and "top_k" not in text


def test_the_rounds_kernel_stands_in_the_route_span_under_no_readers_part():
    """``moe_top_rounds`` is traced inside ``moe/route`` (``moe_route_ms``
    counts it) and once for the layers that share its shapes; its name holds
    no reader's part and is held by none."""
    from apex_tpu.transformer import moe
    (name,) = EXPECTED["top_rounds.py"]
    assert not any(part in name or name in part for part in READER_PARTS)
    fn, args = _expert_layer_grads("pallas")
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    calls = [line for line in text.splitlines() if "moe_top_rounds" in line and "moe/route" in line]
    assert calls
    names = kernel_names(jax.make_jaxpr(fn)(*args).jaxpr)
    assert names.count(name) == 1                  # the backward pass runs no round
