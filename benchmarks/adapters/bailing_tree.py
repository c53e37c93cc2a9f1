"""The one place that knows both parameter layouts of the ``bailing_hybrid``
decoder: the reference's plain tree (``reference/bailing_ref.py``) and
``apex_tpu.models.HybridDecoderModel``'s. Both store every linear (in, out).
The reference keeps the delta-rule layer's q, k and v projections apart and
the latent layer's query and key/value up-projection head by head (``nope |
rope`` and ``key | value`` inside each head, as published); the program fuses
``q|k|v`` (the convolution reads them in place), stores all heads' ``nope``
columns, then all heads' ``rope`` columns (and all keys, then all values), and
fuses each SwiGLU's gate and up matrices. The map is a relabelling of
columns, and a norm taken leaf by leaf of the program's tree is the same on
either side.
"""

import jax.numpy as jnp

from benchmarks import mla_work
from benchmarks.adapters.mla_tree import _fuse, _heads_apart


def config_kwargs(d, **settings):
    """``HybridDecoderConfig`` keyword arguments from the reference's dims."""
    nh = d["num_attention_heads"]
    return dict(
        vocab_size=d["vocab_rows"], hidden_size=d["hidden_size"],
        layer_types=d["layer_types"], ffn_types=d["ffn_types"],
        kda_heads=nh, kda_head_dim=d["kda_head_dim"], kda_lower_bound=float(d["kda_lower_bound"]),
        conv_kernel=d["short_conv_kernel_size"],
        num_heads=nh, num_kv_heads=nh, qk_nope_dim=d["qk_nope_head_dim"],
        qk_rope_dim=d["qk_rope_head_dim"], v_head_dim=d["v_head_dim"],
        kv_lora_rank=d["kv_lora_rank"], rope_theta=d["rope_theta"], rope_scaling=None,
        latent_qk_norm=True, latent_gate=True,
        router_experts=d["router_num_experts"], experts_held=tuple(d["experts_held"]),
        top_k=d["num_experts_per_tok"], expert_ffn=d["moe_intermediate_size"],
        shared_ffn=d["shared_intermediate_size"], dense_ffn=d["intermediate_size"],
        normalize_topk=d["norm_topk_prob"], router_score="sigmoid",
        route_scale=d["routed_scaling_factor"], router_groups=d["n_group"],
        router_groups_kept=d["topk_group"], shared_gate=False, aux_coeff=0.0,
        rms_eps=d["rms_norm_eps"], zero_centered_norm=False, **settings)


def to_program(w):
    k, a, m, dn = w["kda"], w["attn"], w["moe"], w["dense"]
    nope = a["w_kvb"].shape[-1] - a["w_o"].shape[1] // a["w_kvb"].shape[2]
    return {
        "embedding": {"weight": w["embed"]}, "head": {"weight": w["head"]},
        "norm_f": w["norm_f"],
        "layers": {
            "norm1": w["norm1"], "norm2": w["norm2"],
            "kda": {
                "w_qkv": jnp.concatenate([k["w_q"], k["w_k"], k["w_v"]], axis=-1),
                "w_f": k["w_f"], "w_g": k["w_g"], "w_b": k["w_b"], "conv_w": k["conv_w"],
                "A_log": k["A_log"], "dt_bias": k["dt_bias"], "norm_w": k["norm_w"],
                "w_o": k["w_o"],
            },
            "mla": {
                "w_q": _heads_apart(a["w_q"], nope), "w_kva": a["w_kva"],
                "kv_norm": a["kv_norm"], "w_kvb": _heads_apart(a["w_kvb"], nope),
                "q_norm": a["q_norm"], "k_norm": a["k_norm"], "w_gate": a["w_gate"],
                "w_o": a["w_o"],
            },
            "dense": {"w_gate_up": _fuse(dn["w_gate"], dn["w_up"]), "w_down": dn["w_down"]},
            "moe": {
                "router": m["router"],
                "w_gate_up": _fuse(m["w_gate"], m["w_up"]), "w_down": m["w_down"],
                "shared_gate_up": _fuse(m["shared_gate"], m["shared_up"]),
                "shared_down": m["shared_down"],
            },
        },
    }


def attention_view(d):
    """The latent-attention layers as ``kernel_work.flash_work`` reads a model
    (the accepted flash roofline shares list no cells, so they are read here
    too): ``n_layer`` counts the LATENT layers kept (1 of the cell's 6, the
    others run no flash kernel), at ``mla_tree.attention_view``'s one head
    size ``(192 + 128) / 2 = 160``, at which the operations and the q, k (192)
    and v, o (128) bytes are exact. Here every head has its own rotary key
    (the shared one under the head's q/k norm), so the key bytes are exact
    too. The terms ``flash_work`` subtracts again are given as nothing."""
    nh = d["num_attention_heads"]
    width = (mla_work.score_width(d) + d["v_head_dim"]) // 2
    return {"n_embd": nh * width, "n_head": nh, "n_kv_head": nh, "head_dim": width,
            "n_layer": d["layer_types"].count("latent"), "n_inner": 0}
