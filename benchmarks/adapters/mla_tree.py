"""The one place that knows both parameter layouts of the ``deepseek_v2``
decoder: the reference's plain tree (``reference/mla_ref.py``) and
``apex_tpu.models.HybridDecoderModel``'s. Both store every linear (in, out).
The reference keeps the query and the key/value up-projection head by head
(``nope | rope`` and ``key | value`` inside each head, as published); the
program stores all heads' ``nope`` columns, then all heads' ``rope`` columns
(and all keys, then all values), so that each product lands where the kernel
reads it, and fuses each SwiGLU's gate and up matrices. The map is a
relabelling of columns, and a norm taken leaf by leaf of the program's tree is
the same on either side.
"""

import jax.numpy as jnp

from benchmarks import mla_work


def config_kwargs(d, **settings):
    """``HybridDecoderConfig`` keyword arguments from the reference's dims.
    The model takes the mean of the expert layers' balance terms and the
    published loss their sum: ``aux_coeff`` is alpha times their number."""
    nh = d["num_attention_heads"]
    return dict(
        vocab_size=d["vocab_rows"], hidden_size=d["hidden_size"],
        layer_types=d["layer_types"], ffn_types=d["ffn_types"],
        num_heads=nh, num_kv_heads=nh, qk_nope_dim=d["qk_nope_head_dim"],
        qk_rope_dim=d["qk_rope_head_dim"], v_head_dim=d["v_head_dim"],
        kv_lora_rank=d["kv_lora_rank"], rope_theta=d["rope_theta"],
        rope_scaling=d["rope_scaling"],
        router_experts=d["router_num_experts"], experts_held=tuple(d["experts_held"]),
        top_k=d["num_experts_per_tok"], expert_ffn=d["moe_intermediate_size"],
        shared_ffn=d["shared_intermediate_size"], dense_ffn=d["intermediate_size"],
        normalize_topk=d["norm_topk_prob"], router_score=d["scoring_func"],
        route_scale=d["routed_scaling_factor"], shared_gate=False, seq_aux=d["seq_aux"],
        aux_coeff=d["aux_loss_alpha"] * d["ffn_types"].count("moe"),
        rms_eps=d["rms_norm_eps"], zero_centered_norm=False, **settings)


def _fuse(gate, up):
    return jnp.concatenate([gate, up], axis=-1)


def _heads_apart(w, first):
    """(L, in, heads, a + b) -> (L, in, heads a | heads b): every head's
    first ``first`` columns, then every head's rest."""
    L, fan_in = w.shape[:2]
    return _fuse(w[..., :first].reshape(L, fan_in, -1), w[..., first:].reshape(L, fan_in, -1))


def to_program(w):
    a, m, dn = w["attn"], w["moe"], w["dense"]
    nope = a["w_kvb"].shape[-1] - a["w_o"].shape[1] // a["w_kvb"].shape[2]
    return {
        "embedding": {"weight": w["embed"]}, "head": {"weight": w["head"]},
        "norm_f": w["norm_f"],
        "layers": {
            "norm1": w["norm1"], "norm2": w["norm2"],
            "mla": {
                "w_q": _heads_apart(a["w_q"], nope), "w_kva": a["w_kva"],
                "kv_norm": a["kv_norm"], "w_kvb": _heads_apart(a["w_kvb"], nope),
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
    too; every flash call of this model is the two-width form, named
    ``flash_fwd_bshd_mla`` / ``flash_bwd_bshd_mla_fused``). ``flash_work``
    knows one head size: it is handed ``(192 + 128) / 2 = 160``, at which its
    operations (``4 x 160 = 640`` a score pair and head forward, twice that
    backward) and its q, k (192) and v, o (128) bytes are exact. It counts
    the rotary key once a head where the kernel reads ONE for all 16: 10,240
    against 9,280 elements a token and layer forward, about 10 % too many
    bytes. Operations bound the attention's least time about eight times
    over bytes at 8,192, so the bytes do not decide the share. The terms
    ``flash_work`` subtracts again are given as nothing."""
    nh = d["num_attention_heads"]
    width = (mla_work.score_width(d) + d["v_head_dim"]) // 2
    return {"n_embd": nh * width, "n_head": nh, "n_kv_head": nh, "head_dim": width,
            "n_layer": d["num_hidden_layers"], "n_inner": 0}
