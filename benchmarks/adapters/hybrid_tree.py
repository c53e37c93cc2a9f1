"""The one place that knows both parameter layouts of the hybrid decoder: the
reference's plain tree (``reference/hybrid_ref.py``) and
``apex_tpu.models.HybridDecoderModel``'s. Both store every linear (in, out);
the program fuses each expert's gate and up matrices (and the shared
expert's) into one, so the map is a relabelling with two concatenations, and
a norm taken leaf by leaf of the program's tree is the same on either side.
"""

import jax.numpy as jnp


def config_kwargs(d, **settings):
    """``HybridDecoderConfig`` keyword arguments from the reference's dims."""
    return dict(
        vocab_size=d["vocab_rows"], hidden_size=d["hidden_size"],
        layer_types=d["layer_types"], num_heads=d["num_attention_heads"],
        num_kv_heads=d["num_key_value_heads"], head_dim=d["head_dim"],
        rotary_dim=d["rotary_dim"], rope_theta=d["rope_theta"],
        linear_key_heads=d["linear_num_key_heads"],
        linear_value_heads=d["linear_num_value_heads"],
        linear_key_dim=d["linear_key_head_dim"], linear_value_dim=d["linear_value_head_dim"],
        conv_kernel=d["linear_conv_kernel_dim"], router_experts=d["router_num_experts"],
        experts_held=tuple(d["experts_held"]), top_k=d["num_experts_per_tok"],
        expert_ffn=d["moe_intermediate_size"], shared_ffn=d["shared_expert_intermediate_size"],
        normalize_topk=d["norm_topk_prob"], aux_coeff=d["aux_loss_coef"],
        rms_eps=d["rms_norm_eps"], **settings)


def to_program(w):
    m = w["moe"]
    return {
        "embedding": {"weight": w["embed"]}, "head": {"weight": w["head"]},
        "norm_f": w["norm_f"],
        "layers": {
            "norm1": w["norm1"], "norm2": w["norm2"], "gdn": w["gdn"], "attn": w["attn"],
            "moe": {
                "router": m["router"],
                "w_gate_up": jnp.concatenate([m["w_gate"], m["w_up"]], axis=-1),
                "w_down": m["w_down"],
                "shared_gate_up": jnp.concatenate([m["shared_gate"], m["shared_up"]], axis=-1),
                "shared_down": m["shared_down"], "shared_mix": m["shared_mix"],
            },
        },
    }


def attention_view(d):
    """The softmax-attention layers as ``kernel_work.flash_work`` reads a
    model (the accepted flash roofline shares list no cells, so they are read
    here too): the width attention works at (heads x head size) under
    ``n_embd``, the number of layers that ARE attention under ``n_layer``;
    the terms ``flash_work`` subtracts again are given as nothing."""
    nh, dh = d["num_attention_heads"], d["head_dim"]
    return {"n_embd": nh * dh, "n_head": nh, "n_kv_head": d["num_key_value_heads"],
            "n_layer": d["layer_types"].count("full"), "n_inner": 0}
