"""What ``HybridDecoderConfig.remat`` keeps of a mixer half: the results of
its kernels, by the names their forward rules give them, and the outputs of
its input projections. Per layer kind, on the kernels in interpret mode: the
backward pass holds each forward kernel once a layer, ``jax.ad_checkpoint``
lists the half's arguments and the named values and nothing else, and the
loss and every gradient are those of the model that recomputes nothing, bit
for bit."""
import contextlib
import io
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel, hybrid_decoder  # noqa: E402
from apex_tpu.ops.attention import FLASH_SAVED  # noqa: E402
from apex_tpu.ops.gated_delta_rule import RULE_SAVED  # noqa: E402
from apex_tpu.ops.ssd import SSD_SAVED  # noqa: E402
from comparisons import kernel_calls  # noqa: E402

LAYERS, ROWS, SEQ, HIDDEN = 2, 2, 256, 128
# layer kind -> its forward kernel, the names its half holds, the values
# under them (an attention layer's q, gate, k, v; ``q|k|v|z`` and ``b|a``;
# ``xBC|z|dt``)
KINDS = {"full": ("flash_fwd_bshd", FLASH_SAVED + ("mix_proj",), 2 + 4),
         "window": ("flash_fwd_bshd_win", FLASH_SAVED + ("mix_proj",), 2 + 4),
         "latent": ("flash_fwd_bshd_mla", FLASH_SAVED, 2),
         "linear": ("gdn_fwd", RULE_SAVED + ("mix_proj",), 2 + 2),
         "ssm": ("ssd_fwd", SSD_SAVED + ("mix_proj",), 2 + 1)}


def build(kind, remat):
    """Two layers of ``kind``, a dense and an expert second half."""
    model = HybridDecoderModel(HybridDecoderConfig(
        vocab_size=256, hidden_size=HIDDEN, layer_types=(kind,) * LAYERS, num_heads=2,
        num_kv_heads=1, head_dim=128, rotary_dim=32, window=128 if kind == "window" else None,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, kv_lora_rank=64, linear_key_heads=1,
        linear_value_heads=2, linear_key_dim=128, linear_value_dim=128, ssm_heads=4,
        ssm_head_dim=64, ssm_groups=2, router_experts=8,
        top_k=2, expert_ffn=128, shared_ffn=128, ffn_types=("dense", "moe"), dense_ffn=128,
        remat=remat, attention_impl="pallas", delta_impl="pallas", experts_impl="xla"))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (ROWS, SEQ), 0, 256)
    return model, model.init(jax.random.PRNGKey(0)), tokens


def grad_calls(kind, remat):
    model, params, tokens = build(kind, remat)
    return kernel_calls(jax.make_jaxpr(jax.grad(model.loss_fn))(params, tokens, tokens).jaxpr)


@pytest.mark.parametrize("kind", KINDS)
def test_the_backward_pass_holds_each_forward_kernel_once_a_layer(kind, monkeypatch):
    forward = KINDS[kind][0]
    plain, kept = grad_calls(kind, False), grad_calls(kind, True)
    assert kept[forward] == plain[forward] == LAYERS
    assert {n: c for n, c in kept.items() if "_bwd" in n} == \
        {n: c for n, c in plain.items() if "_bwd" in n}
    # the witness: with no name kept every block runs its kernel again
    monkeypatch.setattr(hybrid_decoder, "MIXER_SAVED", ())
    assert grad_calls(kind, True)[forward] == 2 * LAYERS


@pytest.mark.parametrize("kind", KINDS)
def test_the_mixer_half_saves_its_arguments_and_the_named_values(kind):
    model, params, _ = build(kind, True)
    p = jax.tree.map(lambda a: a[0], params["layers"][hybrid_decoder.GROUP_OF_KIND[kind]])
    x = jnp.ones((ROWS, SEQ, HIDDEN), jnp.float32)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        jax.ad_checkpoint.print_saved_residuals(
            model._mixer_half(kind), p, params["layers"]["norm1"][0], None, x)
    lines = out.getvalue().strip().splitlines()
    made = [line for line in lines if " from the argument " not in line
            and " from a constant" not in line]
    # every weight of the mixer, the norm's weight and the stream
    assert sum(" from the argument " in line for line in lines) == len(p) + 2
    # a named value that the half itself reads on is listed under the
    # ``reduce_precision`` jax puts behind it
    _, names, values = KINDS[kind]
    named = {n for n in hybrid_decoder.MIXER_SAVED if any(f"named '{n}'" in line for line in made)}
    assert named and named <= set(names)
    assert len(made) == values, made
    assert all("named '" in line or "reduce_precision" in line for line in made), made


@pytest.mark.parametrize("kind", KINDS)
def test_loss_and_gradients_are_those_of_the_model_that_recomputes_nothing(kind):
    model, params, tokens = build(kind, False)
    again, _, _ = build(kind, True)
    want, g_want = jax.jit(jax.value_and_grad(model.loss_fn))(params, tokens, tokens)
    got, g_got = jax.jit(jax.value_and_grad(again.loss_fn))(params, tokens, tokens)
    assert float(got) == float(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_got)[0],
                            jax.tree.leaves(g_want)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
