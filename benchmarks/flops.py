"""Operations and bytes that the algorithm requires, from shapes alone.

The counts are the work the forward and backward passes need, not what a
program happens to execute: no recomputation, causal attention counted at
its causal half, multi-query attention at its real key/value width. They are
NOT the PaLM convention (6N + 12 L S H, non-causal) that older numbers in
this repo used; for StarCoderBase-1B at 8,192 that convention overstates the
required work by about a third.

``d`` is the dict ``reference.gpt_ref.dims`` returns.
"""


def matmul_params(d):
    """Weights that multiply every token: per layer the fused q|k|v
    projection, the output projection and the two MLP matrices, plus the
    tied unembedding."""
    H, F, dh = d["n_embd"], d["n_inner"], d["head_dim"]
    per_layer = H * (d["n_head"] + 2 * d["n_kv_head"]) * dh + H * H + 2 * H * F
    return d["n_layer"] * per_layer + d["vocab_size"] * H


def total_params(d):
    H, F = d["n_embd"], d["n_inner"]
    qkv = (d["n_head"] + 2 * d["n_kv_head"]) * d["head_dim"]
    per_layer = 4 * H + H * qkv + qkv + H * H + H + 2 * H * F + F + H
    return (d["n_layer"] * per_layer + d["vocab_size"] * H
            + d["n_positions"] * H + 2 * H)


def forward_flops_per_token(d, seq):
    """One multiply-add is two operations. Causal attention: position i
    scores against i + 1 keys, (seq + 1) / 2 on average, for QK^T and for
    PV, over all query heads."""
    attn = d["n_layer"] * 4 * d["n_embd"] * (seq + 1) / 2
    return 2 * matmul_params(d) + attn


def train_flops_per_token(d, seq):
    """Forward plus backward (twice the forward), nothing recomputed."""
    return 3 * forward_flops_per_token(d, seq)
