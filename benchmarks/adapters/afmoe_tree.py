"""The one place that knows both parameter layouts of the ``afmoe`` decoder:
the reference's plain tree (``reference/afmoe_ref.py``) and
``apex_tpu.models.HybridDecoderModel``'s. Both store every linear (in, out);
the program fuses each head's query and gate columns into one projection
(``q|gate`` per head) and each SwiGLU's gate and up matrices into one, so the
map is a relabelling with concatenations, and a norm taken leaf by leaf of
the program's tree is the same on either side.
"""

import jax.numpy as jnp

from benchmarks import afmoe_work


def config_kwargs(d, **settings):
    """``HybridDecoderConfig`` keyword arguments from the reference's dims."""
    return dict(
        vocab_size=d["vocab_rows"], hidden_size=d["hidden_size"],
        layer_types=d["layer_types"], ffn_types=d["ffn_types"],
        num_heads=d["num_attention_heads"], num_kv_heads=d["num_key_value_heads"],
        head_dim=d["head_dim"], rope_theta=d["rope_theta"],
        rotary_dim=0, window=d["sliding_window"], window_rotary_dim=d["head_dim"],
        router_experts=d["router_num_experts"], experts_held=tuple(d["experts_held"]),
        top_k=d["num_experts_per_tok"], expert_ffn=d["moe_intermediate_size"],
        shared_ffn=d["shared_intermediate_size"], dense_ffn=d["intermediate_size"],
        normalize_topk=d["route_norm"], router_score=d["score_func"],
        route_scale=d["route_scale"], shared_gate=False, aux_coeff=0.0,
        rms_eps=d["rms_norm_eps"], zero_centered_norm=False, sandwich_norms=True,
        embed_scale=d["embed_scale"], **settings)


def _fuse(gate, up):
    return jnp.concatenate([gate, up], axis=-1)


def to_program(w):
    a, m, dn = w["attn"], w["moe"], w["dense"]
    L, H, _ = a["w_q"].shape
    dh = a["q_norm"].shape[-1]
    per_head = lambda x: x.reshape(L, H, -1, dh)  # noqa: E731
    return {
        "embedding": {"weight": w["embed"]}, "head": {"weight": w["head"]},
        "norm_f": w["norm_f"],
        "layers": {
            "norm1": w["norm1"], "norm2": w["norm2"],
            "norm1_post": w["norm1_post"], "norm2_post": w["norm2_post"],
            "attn": {
                "w_q": _fuse(per_head(a["w_q"]), per_head(a["w_gate"])).reshape(L, H, -1),
                "w_k": a["w_k"], "w_v": a["w_v"], "q_norm": a["q_norm"],
                "k_norm": a["k_norm"], "w_o": a["w_o"],
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


def attention_view(d, seq):
    """The attention layers as ``kernel_work.flash_work`` reads a model (the
    accepted flash roofline shares list no cells, so they are read here too,
    over the banded and the unbanded kernels together: both names hold
    ``flash_fwd`` / ``flash_bwd``). ``flash_work`` counts a layer as full
    causal attention, ``(seq + 1) / 2`` keys a query; a banded layer scores
    fewer, so ``n_layer`` carries the REQUIRED work: the sum over the
    attention layers of the mean keys a query scores over ``(seq + 1) / 2``
    (``afmoe_work.attention_layers_as_causal``: 2.75 of the cell's five
    layers at 8,192). Operations bound the attention's least time by 15
    times over bytes at heads of 128, so the bytes, which this fractional
    layer count understates (every layer moves all of q, k, v, o), do not
    decide the share. The terms ``flash_work`` subtracts again are given as
    nothing."""
    nh, dh = d["num_attention_heads"], d["head_dim"]
    return {"n_embd": nh * dh, "n_head": nh, "n_kv_head": d["num_key_value_heads"],
            "n_layer": afmoe_work.attention_layers_as_causal(d, seq), "n_inner": 0}
