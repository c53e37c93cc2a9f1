"""Operations and bytes that the ``deepseek_v2`` decoder's algorithm requires,
from shapes and from the load counters alone (``flops.py``, ``kernel_work.py``,
``hybrid_work.py`` and ``afmoe_work.py`` do the same for the other blocks and
are not touched).

``d`` is the dict ``reference.mla_ref.dims`` returns. One multiply-add is two
operations; nothing recomputed; a query at position ``i`` scores ``i + 1``
keys over 192 features (128 per head and the 64 rotary ones: at the REQUIRED
width, not the 128-lane tile the kernel pads them to in VMEM) and sums 128
value features: 640 operations a score pair and head, forward.
"""
from benchmarks import hybrid_work


def score_width(d):
    return d["qk_nope_head_dim"] + d["qk_rope_head_dim"]


def attention_params(d):
    """One latent-attention mixer's matmul weights: the query projection,
    the down projection to the latent and the shared rotary key, the up
    projection to the per-head keys and values, the output projection."""
    H, nh, rank = d["hidden_size"], d["num_attention_heads"], d["kv_lora_rank"]
    return (H * nh * score_width(d) + H * (rank + d["qk_rope_head_dim"])
            + rank * nh * (d["qk_nope_head_dim"] + d["v_head_dim"])
            + nh * d["v_head_dim"] * H)


def attention_ops_per_token(d, seq):
    """Per layer, forward: QK^T at 192 and PV at 128 over all heads, at the
    causal mean of ``(seq + 1) / 2`` keys a query."""
    return (2 * d["num_attention_heads"] * (score_width(d) + d["v_head_dim"])
            * (seq + 1) / 2)


def expert_view(d):
    """``d`` as ``hybrid_work.expert_matmul_work`` reads a model: the layers
    it counts are the expert layers."""
    return dict(d, num_hidden_layers=d["ffn_types"].count("moe"))


def matmul_params_per_token(d, local_assignments_per_token):
    """Weights every token multiplies, with the routed experts at the
    counted local assignments a token (summed over the expert layers)."""
    H = d["hidden_size"]
    expert_layer = H * d["router_num_experts"] + 3 * H * d["shared_intermediate_size"]
    return (d["num_hidden_layers"] * attention_params(d)
            + d["ffn_types"].count("dense") * 3 * H * d["intermediate_size"]
            + d["ffn_types"].count("moe") * expert_layer
            + local_assignments_per_token * 3 * H * d["moe_intermediate_size"]
            + d["vocab_size"] * H)


def train_flops_per_token(d, seq, local_assignments_per_token):
    """Forward plus backward (twice the forward), nothing recomputed: every
    matmul weight a token meets, attention at its causal half in every
    layer, the head over the vocabulary slice."""
    attn = d["num_hidden_layers"] * attention_ops_per_token(d, seq)
    return 3 * (2 * matmul_params_per_token(d, local_assignments_per_token) + attn)


def window_flops_per_token(run):
    """``train_flops_per_token`` at the window's counted local assignments:
    what the adapter hands the MFU reader under ``run["train_flops_per_token"]``."""
    assignments = hybrid_work.assignments_per_step(run)
    if assignments is None:
        return None
    return train_flops_per_token(run["dims"], run["seq"],
                                 assignments / hybrid_work.step_tokens(run))
