"""Operations and bytes that the hybrid decoder's algorithm requires, from
shapes and from the load counters alone (``flops.py`` and ``kernel_work.py``
do the same for the GPT block and are not touched).

``d`` is the dict ``reference.hybrid_ref.dims`` returns. One multiply-add is
two operations; nothing recomputed; the counts are the recurrence's and the
routing's own, not what a chunked or padded program executes.
"""
from benchmarks import kernel_work

GDN_FORWARD, GDN_BACKWARD, EXPERT_MATMUL = "gdn_fwd", "gdn_bwd", "moe_gmm"


def linear_layers(d):
    return d["layer_types"].count("linear")


def full_layers(d):
    return d["layer_types"].count("full")


def delta_rule_ops_per_token(d):
    """Per delta-rule layer, forward: per value head, decay-and-read
    (S^T k), the rank-one update and the output (S^T q): 6 dk dv."""
    return 6 * d["linear_num_value_heads"] * d["linear_key_head_dim"] * d["linear_value_head_dim"]


def delta_rule_work(d, tokens, backward=False):
    """(operations, bytes) of all delta-rule layers for ``tokens`` tokens.
    Bytes, each tensor once: q, k (key heads), v, o (value heads) in bf16 and
    g, beta in float32; backward also do, dq, dk, dv and dg, dbeta."""
    qk = d["linear_num_key_heads"] * d["linear_key_head_dim"]
    vo = d["linear_num_value_heads"] * d["linear_value_head_dim"]
    hv = d["linear_num_value_heads"]
    per_token = 2 * (2 * qk + 2 * vo) + 4 * 2 * hv
    ops = delta_rule_ops_per_token(d)
    if backward:
        per_token = 2 * (4 * qk + 4 * vo) + 4 * 4 * hv
        ops *= 2
    n = linear_layers(d) * tokens
    return n * ops, n * per_token


def expert_matmul_work(d, assignments, passes):
    """(operations, bytes) of the grouped products over the experts held,
    forward and backward together, for ``assignments`` local (token, expert)
    pairs summed over the layers: 6 H F operations an assignment forward and
    twice that backward; bytes: the held experts' weights once a pass
    (``passes``: forward, and the backward's two) and each gathered row in
    and out (bf16)."""
    H, F = d["hidden_size"], d["moe_intermediate_size"]
    layers, held = d["num_hidden_layers"], d["experts_held"][1]
    ops = 3 * 6 * H * F * assignments
    weights = passes * layers * held * 3 * H * F * 2
    rows = 3 * assignments * 2 * H * 2
    return ops, weights + rows


def matmul_params_per_token(d, local_assignments_per_token):
    """Weights every token multiplies, with the routed experts at the
    counted local assignments a token (summed over the layers)."""
    H = d["hidden_size"]
    qk = d["linear_num_key_heads"] * d["linear_key_head_dim"]
    vo = d["linear_num_value_heads"] * d["linear_value_head_dim"]
    nh, nkv, dh = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    gdn = H * (2 * qk + 2 * vo) + H * 2 * d["linear_num_value_heads"] + vo * H
    attn = H * (2 * nh * dh) + 2 * H * nkv * dh + nh * dh * H
    per_layer = H * d["router_num_experts"] + 3 * H * d["shared_expert_intermediate_size"] + H
    return (linear_layers(d) * gdn + full_layers(d) * attn
            + d["num_hidden_layers"] * per_layer
            + local_assignments_per_token * 3 * H * d["moe_intermediate_size"]
            + d["vocab_size"] * H)


def train_flops_per_token(d, seq, local_assignments_per_token):
    """Forward plus backward (twice the forward), nothing recomputed: every
    matmul weight a token meets, causal attention at its half in the
    full-attention layers, the recurrence's own count, the convolution, the
    head over the vocabulary slice."""
    attn = full_layers(d) * 4 * d["num_attention_heads"] * d["head_dim"] * (seq + 1) / 2
    conv_channels = (2 * d["linear_num_key_heads"] * d["linear_key_head_dim"]
                     + d["linear_num_value_heads"] * d["linear_value_head_dim"])
    conv = linear_layers(d) * 2 * d["linear_conv_kernel_dim"] * conv_channels
    rule = linear_layers(d) * delta_rule_ops_per_token(d)
    forward = 2 * matmul_params_per_token(d, local_assignments_per_token) + attn + conv + rule
    return 3 * forward


def roofline_pct(run, part, work):
    """The least time the chip could take for ``work`` = (operations, bytes)
    of one step (the larger of operations over the bf16 peak and bytes over
    the HBM peak) over the time of the kernels whose name holds ``part``."""
    took_ms = kernel_work.kernel_ms(run, part)
    if took_ms is None:
        return None
    ops, nbytes = work
    peaks = run["peaks"]
    least_s = max(ops / peaks["flops_per_s"]["bfloat16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / took_ms


def step_tokens(run):
    return run["tokens"] / run["steps"] / run["chips"]


def assignments_per_step(run):
    """Local assignments a step, summed over the layers, mean over the
    window's steps; ``None`` where the program handed back no counters."""
    loads = run.get("expert_load")
    if loads is None or not len(loads):
        return None
    return float(loads.sum()) / len(loads)


def window_flops_per_token(run):
    """``train_flops_per_token`` at the window's counted local assignments:
    what the adapter hands the MFU reader under ``run["train_flops_per_token"]``."""
    assignments = assignments_per_step(run)
    if assignments is None:
        return None
    return train_flops_per_token(run["dims"], run["seq"], assignments / step_tokens(run))


def window_expert_matmul_work(run, view=None, work=expert_matmul_work):
    """The grouped products' required (operations, bytes) of one step at the
    window's counted local assignments: what the adapter hands the roofline
    reader under ``run["expert_matmul_work"]``. The adapter of another block
    passes its ``expert_view`` of the dims, or its own count as ``work``."""
    assignments = assignments_per_step(run)
    if assignments is None:
        return None
    d = run["dims"] if view is None else view(run["dims"])
    return work(d, assignments, passes=3)
