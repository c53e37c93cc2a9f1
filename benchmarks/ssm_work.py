"""Operations and bytes that the ``nemotron_h`` decoder's algorithm requires,
from shapes and from the load counters alone (``flops.py``, ``kernel_work.py``,
``hybrid_work.py``, ``afmoe_work.py`` and ``mla_work.py`` do the same for the
other blocks and are not touched).

``d`` is the dict ``reference.ssm_ref.dims`` returns. One multiply-add is two
operations; nothing recomputed. The state-space scan is counted in its
chunked form at the published chunk ``Q`` with the causal half of the
products inside a chunk — the same work whatever implements it: a kernel
that computes a masked product in full, or a head of 64 on a 128-wide unit,
executes more and is credited with this. The experts are counted at their
published width (1,856), never at a padded one.
"""
from benchmarks import hybrid_work

SCAN_FORWARD, SCAN_BACKWARD = "ssd_fwd", "ssd_bwd"


def scan_ops_per_token(d):
    """Per state-space layer, forward, a token: ``C B^T`` a group and ``M x``
    a head over the causal half of a chunk ((Q + 1) / 2 tokens), the entry
    state's part of the output (``C S_0``) and the state's update (``B^T w
    x``) a head at N x P each, and the skip ``D x``."""
    nh, P = d["mamba_num_heads"], d["mamba_head_dim"]
    G, N, Q = d["n_groups"], d["ssm_state_size"], d["chunk_size"]
    half = (Q + 1) / 2
    return 2 * (G * N * half + nh * P * half + 2 * nh * N * P + nh * P)


def scan_work(d, tokens, backward=False):
    """(operations, bytes) of all state-space layers' scans for ``tokens``
    tokens. Bytes, each tensor once a pass: forward ``x``, ``B``, ``C`` in
    and ``y`` out in bf16 and ``dt`` in float32; backward those four inputs
    and ``dy`` in, ``dx``, ``dB``, ``dC`` and ``ddt`` out. Operations
    backward: twice the forward."""
    nh, P = d["mamba_num_heads"], d["mamba_head_dim"]
    bc = d["n_groups"] * d["ssm_state_size"]
    per_token = 2 * (2 * nh * P + 2 * bc) + 4 * nh
    ops = scan_ops_per_token(d)
    if backward:
        per_token = 2 * (3 * nh * P + 4 * bc) + 4 * 2 * nh
        ops *= 2
    n = d["kinds"].count("ssm") * tokens
    return n * ops, n * per_token


def expert_matmul_work(d, assignments, passes=3):
    """(operations, bytes) of the grouped products over the experts held,
    forward and backward together, for ``assignments`` local (token, expert)
    pairs summed over the expert layers: an ungated expert is TWO matrices,
    4 H F operations an assignment forward and twice that backward; bytes:
    the held experts' weights once a pass (``passes``: forward, and the
    backward's two) and each gathered row in and out (bf16)."""
    H, F = d["hidden_size"], d["moe_intermediate_size"]
    layers, held = d["kinds"].count("moe"), d["experts_held"][1]
    ops = 3 * 4 * H * F * assignments
    weights = passes * layers * held * 2 * H * F * 2
    rows = 3 * assignments * 2 * H * 2
    return ops, weights + rows


def matmul_params_per_token(d, local_assignments_per_token):
    """Weights every token multiplies, with the routed experts at the
    counted local assignments a token (summed over the expert layers)."""
    H = d["hidden_size"]
    nh, nkv, dh = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    ssm = H * (d["d_inner"] + d["conv_dim"] + d["mamba_num_heads"]) + d["d_inner"] * H
    attn = H * nh * dh + 2 * H * nkv * dh + nh * dh * H
    expert_layer = H * d["router_num_experts"] + 2 * H * d["shared_intermediate_size"]
    return (d["kinds"].count("ssm") * ssm + d["kinds"].count("attn") * attn
            + d["kinds"].count("moe") * expert_layer
            + local_assignments_per_token * 2 * H * d["moe_intermediate_size"]
            + d["vocab_size"] * H)


def train_flops_per_token(d, seq, local_assignments_per_token):
    """Forward plus backward (twice the forward), nothing recomputed: every
    matmul weight a token meets, the scan's chunked count, the convolution,
    causal attention at its half in the attention layers, the head over the
    vocabulary slice."""
    attn = d["kinds"].count("attn") * 4 * d["num_attention_heads"] * d["head_dim"] * (seq + 1) / 2
    ssm = d["kinds"].count("ssm") * (scan_ops_per_token(d)
                                     + 2 * d["conv_kernel"] * d["conv_dim"])
    return 3 * (2 * matmul_params_per_token(d, local_assignments_per_token) + attn + ssm)


def window_flops_per_token(run):
    """``train_flops_per_token`` at the window's counted local assignments:
    what the adapter hands the MFU reader under ``run["train_flops_per_token"]``."""
    assignments = hybrid_work.assignments_per_step(run)
    if assignments is None:
        return None
    return train_flops_per_token(run["dims"], run["seq"],
                                 assignments / hybrid_work.step_tokens(run))


def scan_roofline_pct(run, backward=False):
    """The scan kernels' share of their roofline; ``None`` where the run's
    model has no state-space layer or no such kernel ran."""
    d = run.get("dims", {})
    if "ssm" not in d.get("kinds", ()):
        return None
    work = scan_work(d, hybrid_work.step_tokens(run), backward)
    return hybrid_work.roofline_pct(run, SCAN_BACKWARD if backward else SCAN_FORWARD, work)
