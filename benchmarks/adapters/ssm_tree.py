"""The one place that knows both parameter layouts of the ``nemotron_h``
decoder: the reference's plain tree (``reference/ssm_ref.py``: one entry a
published layer, each layer one half) and
``apex_tpu.models.HybridDecoderModel``'s (blocks of a mixer half and, where
an expert layer follows the mixer, an expert half; ``"none"`` where it does
not). Both store every linear (in, out). The program keeps the state-space
layer's fused projection as ``xBC | z | dt`` where the reference has the
published ``z | xBC | dt`` (the convolved channels first, where the
convolution kernel reads them in place): the map is a relabelling of
columns, and a norm taken leaf by leaf of the program's tree is the same on
either side.
"""

import jax.numpy as jnp

MIXERS = {"ssm": "ssm", "attn": "full"}


def blocks(kinds):
    """The published layers as the program's blocks: ``[(mixer kind, second
    half, the mixer's layer, the expert layer's or None)]``. A mixer opens a
    block; an expert layer right after it is the block's second half."""
    out = []
    for i, kind in enumerate(kinds):
        if kind in MIXERS:
            out.append([MIXERS[kind], "none", i, None])
        elif not out or out[-1][1] != "none":
            raise ValueError(f"layer {i}: an expert layer with no mixer before it "
                             f"has no block here ({kinds!r})")
        else:
            out[-1][1], out[-1][3] = "moe", i
    return [tuple(b) for b in out]


def config_kwargs(d, **settings):
    """``HybridDecoderConfig`` keyword arguments from the reference's dims."""
    laid = blocks(d["kinds"])
    return dict(
        vocab_size=d["vocab_rows"], hidden_size=d["hidden_size"],
        layer_types=tuple(b[0] for b in laid), ffn_types=tuple(b[1] for b in laid),
        num_heads=d["num_attention_heads"], num_kv_heads=d["num_key_value_heads"],
        head_dim=d["head_dim"], rotary_dim=0, attn_gate=False, qk_norm=False,
        ssm_heads=d["mamba_num_heads"], ssm_head_dim=d["mamba_head_dim"],
        ssm_state=d["ssm_state_size"], ssm_groups=d["n_groups"], ssm_chunk=d["chunk_size"],
        conv_kernel=d["conv_kernel"],
        router_experts=d["router_num_experts"], experts_held=tuple(d["experts_held"]),
        top_k=d["num_experts_per_tok"], expert_ffn=d["moe_intermediate_size"],
        shared_ffn=d["shared_intermediate_size"], normalize_topk=d["norm_topk_prob"],
        router_score="sigmoid", route_scale=d["routed_scaling_factor"], shared_gate=False,
        expert_activation="relu2", aux_coeff=0.0, rms_eps=d["layer_norm_epsilon"],
        zero_centered_norm=False, **settings)


def to_program(w, d):
    s, a, m = w["ssm"], w["attn"], w["moe"]
    laid = blocks(d["kinds"])
    inner, conv = d["d_inner"], d["conv_dim"]
    w_in = s["w_in"]
    ssm = {
        "w_in": jnp.concatenate([w_in[..., inner:inner + conv], w_in[..., :inner],
                                 w_in[..., inner + conv:]], axis=-1),
        "conv_w": s["conv_w"], "conv_b": s["conv_b"], "A_log": s["A_log"],
        "dt_bias": s["dt_bias"], "D": s["D"], "norm_w": s["norm_w"], "w_o": s["w_out"],
    }
    return {
        "embedding": {"weight": w["embed"]}, "head": {"weight": w["head"]},
        "norm_f": w["norm_f"],
        "layers": {
            "norm1": w["norm"][jnp.asarray([b[2] for b in laid])],
            "norm2": w["norm"][jnp.asarray([b[3] for b in laid if b[3] is not None])],
            "ssm": ssm,
            "attn": {"w_q": a["w_q"], "w_k": a["w_k"], "w_v": a["w_v"], "w_o": a["w_o"]},
            "moe": {"router": m["router"], "w_up": m["w_up"], "w_down": m["w_down"],
                    "shared_up": m["shared_up"], "shared_down": m["shared_down"]},
        },
    }


def attention_view(d):
    """The attention layers as ``kernel_work.flash_work`` reads a model (the
    accepted flash roofline shares list no cells, so they are read here too):
    ``n_layer`` counts the ATTENTION layers (1 of the cell's 7), grouped
    query heads at their real key/value width. The terms ``flash_work``
    subtracts again are given as nothing."""
    nh, dh = d["num_attention_heads"], d["head_dim"]
    return {"n_embd": nh * dh, "n_head": nh, "n_kv_head": d["num_key_value_heads"],
            "n_layer": d["kinds"].count("attn"), "n_inner": 0}
