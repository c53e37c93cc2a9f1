"""The one place that knows both parameter layouts of the ``ouro`` decoder:
the reference's plain tree (``reference/loop_ref.py``) and
``apex_tpu.models.HybridDecoderModel``'s, built as a looped stack of
``"full"`` mixers without gate or per-head norms, rotary over the whole
head, sandwich norms and a dense SwiGLU second half. Both store every linear
(in, out); the program fuses the SwiGLU's gate and up matrices into one and
holds the exit gate as a Linear(hidden, 1), so the map is a relabelling with
one concatenation, and a norm taken leaf by leaf of the program's tree is the
same on either side.
"""

import jax.numpy as jnp


def config_kwargs(d, **settings):
    """``HybridDecoderConfig`` keyword arguments from the reference's dims."""
    L = d["num_hidden_layers"]
    return dict(
        vocab_size=d["vocab_rows"], hidden_size=d["hidden_size"],
        layer_types=("full",) * L, ffn_types=("dense",) * L,
        num_heads=d["num_attention_heads"], num_kv_heads=d["num_key_value_heads"],
        head_dim=d["head_dim"], rotary_dim=d["head_dim"], rope_theta=d["rope_theta"],
        attn_gate=False, qk_norm=False, dense_ffn=d["intermediate_size"], aux_coeff=0.0,
        rms_eps=d["rms_norm_eps"], zero_centered_norm=False, sandwich_norms=True,
        loop_trips=d["total_ut_steps"], exit_entropy_coeff=d["entropy_beta"], **settings)


def to_program(w):
    m, g = w["mlp"], w["gate"]
    return {
        "embedding": {"weight": w["embed"]}, "head": {"weight": w["head"]},
        "norm_f": w["norm_f"],
        "exit_gate": {"weight": g["w"][:, None], "bias": g["b"][None]},
        "layers": {
            "norm1": w["norm1"], "norm2": w["norm2"],
            "norm1_post": w["norm1_post"], "norm2_post": w["norm2_post"],
            "attn": dict(w["attn"]),
            "dense": {"w_gate_up": jnp.concatenate([m["w_gate"], m["w_up"]], axis=-1),
                      "w_down": m["w_down"]},
        },
    }


def attention_view(d):
    """The attention layers as ``kernel_work.flash_work`` reads a model (the
    accepted flash roofline shares list no cells, so they are read here too):
    ``n_layer`` counts the flash CALLS a pass, every layer once a walk —
    ``num_hidden_layers x total_ut_steps`` — or the shares would read
    ``total_ut_steps`` times too high. The terms ``flash_work`` subtracts
    again are given as nothing."""
    nh, dh = d["num_attention_heads"], d["head_dim"]
    return {"n_embd": nh * dh, "n_head": nh, "n_kv_head": d["num_key_value_heads"],
            "n_layer": d["num_hidden_layers"] * d["total_ut_steps"], "n_inner": 0}
