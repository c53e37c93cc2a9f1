"""Operations and bytes that the ``bailing_hybrid`` decoder's algorithm
requires, from shapes and from the load counters alone (``flops.py``,
``kernel_work.py``, ``hybrid_work.py``, ``afmoe_work.py``, ``mla_work.py`` and
``ssm_work.py`` do the same for the other blocks and are not touched).

``d`` is the dict ``reference.bailing_ref.dims`` returns. One multiply-add is
two operations; nothing recomputed. The delta rule with a decay a key channel
is counted in its chunked form at ``CHUNK`` tokens with the causal half of the
products inside a chunk — the same work whatever implements it: a kernel that
makes its in-chunk scores from float32 factors in several passes, computes a
masked product in full or inverts the triangular system by merged blocks
executes more and is credited with this. The latent layer is ``mla_work``'s.
"""
from benchmarks import hybrid_work, mla_work

KDA_FORWARD, KDA_BACKWARD = "kda_fwd", "kda_bwd"
CHUNK = 64              # tokens a chunk of the rule's chunked form
STATE_BLOCK = 512       # tokens between two entry states that cross HBM


def kda_layers(d):
    return d["layer_types"].count("kda")


def rule_ops_per_token(d):
    """Per delta-rule layer, forward, a token: per head the in-chunk scores
    (``k k^T`` and ``q k^T`` under the per-channel decay, over the causal half
    of a chunk at ``dk`` each), the solve's products (the triangular system
    against ``dk + dv`` right-hand sides, the causal half), ``p v'`` (the
    causal half at ``dv``) and the three state products (``w S``, ``qg S``,
    ``kg^T v'`` at ``dk dv`` each)."""
    n, dk = d["num_attention_heads"], d["kda_head_dim"]
    dv, half = dk, (CHUNK + 1) / 2
    return 2 * n * (2 * dk * half + (dk + dv) * half + dv * half + 3 * dk * dv)


def rule_work(d, tokens, backward=False):
    """(operations, bytes) of all delta-rule layers' rules for ``tokens``
    tokens. Bytes, each tensor once a pass: forward q, k, v in and o out in
    bf16, the log decay g (a key channel) and beta in float32, the entry
    states out (float32, one a head every ``STATE_BLOCK`` tokens); backward
    those five inputs, the entry states and do in, dq, dk, dv, dg and dbeta
    out. Operations backward: twice the forward."""
    n, dk = d["num_attention_heads"], d["kda_head_dim"]
    hd, state = n * dk, 4 * n * dk * dk / STATE_BLOCK
    per_token = 2 * 4 * hd + 4 * hd + 4 * n + state
    ops = rule_ops_per_token(d)
    if backward:
        per_token = 2 * 7 * hd + 4 * 2 * hd + 4 * 2 * n + state
        ops *= 2
    count = kda_layers(d) * tokens
    return count * ops, count * per_token


def kda_params(d):
    """One delta-rule mixer's matmul weights: q, k, v, the decay and the
    output gate at full width, the write strength a head, the output."""
    H, n, dk = d["hidden_size"], d["num_attention_heads"], d["kda_head_dim"]
    return H * 5 * n * dk + H * n + n * dk * H


def matmul_params_per_token(d, local_assignments_per_token):
    """Weights every token multiplies, with the routed experts at the
    counted local assignments a token (summed over the expert layers)."""
    H = d["hidden_size"]
    latent = mla_work.attention_params(d) + H * d["num_attention_heads"]
    expert_layer = H * d["router_num_experts"] + 3 * H * d["shared_intermediate_size"]
    return (kda_layers(d) * kda_params(d) + d["layer_types"].count("latent") * latent
            + d["ffn_types"].count("dense") * 3 * H * d["intermediate_size"]
            + d["ffn_types"].count("moe") * expert_layer
            + local_assignments_per_token * 3 * H * d["moe_intermediate_size"]
            + d["vocab_size"] * H)


def train_flops_per_token(d, seq, local_assignments_per_token):
    """Forward plus backward (twice the forward), nothing recomputed: every
    matmul weight a token meets, the rule's chunked count and the convolution
    in the delta-rule layers, latent attention at its causal half in the
    latent layers, the head over the vocabulary slice."""
    conv = 2 * d["short_conv_kernel_size"] * 3 * d["num_attention_heads"] * d["kda_head_dim"]
    kda = kda_layers(d) * (rule_ops_per_token(d) + conv)
    attn = d["layer_types"].count("latent") * mla_work.attention_ops_per_token(d, seq)
    return 3 * (2 * matmul_params_per_token(d, local_assignments_per_token) + kda + attn)


def window_flops_per_token(run):
    """``train_flops_per_token`` at the window's counted local assignments:
    what the adapter hands the MFU reader under ``run["train_flops_per_token"]``."""
    assignments = hybrid_work.assignments_per_step(run)
    if assignments is None:
        return None
    return train_flops_per_token(run["dims"], run["seq"],
                                 assignments / hybrid_work.step_tokens(run))


def is_bailing(run):
    return "kda" in run.get("dims", {}).get("layer_types", ())


def rule_roofline_pct(run, backward=False):
    """The rule kernels' share of their roofline; ``None`` where the run's
    model has no such layer or no such kernel ran."""
    if not is_bailing(run):
        return None
    work = rule_work(run["dims"], hybrid_work.step_tokens(run), backward)
    return hybrid_work.roofline_pct(run, KDA_BACKWARD if backward else KDA_FORWARD, work)
