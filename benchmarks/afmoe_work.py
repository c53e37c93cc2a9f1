"""Operations and bytes that the ``afmoe`` decoder's algorithm requires, from
shapes and from the load counters alone (``flops.py``, ``kernel_work.py`` and
``hybrid_work.py`` do the same for the other blocks and are not touched).

``d`` is the dict ``reference.afmoe_ref.dims`` returns. One multiply-add is
two operations; nothing recomputed; a banded layer's query scores
``min(i + 1, window)`` keys, a full layer's ``i + 1``.
"""
from benchmarks import hybrid_work

BAND_FORWARD, BAND_BACKWARD = "flash_fwd_bshd_win", "flash_bwd_bshd_win"


def mean_keys(seq, window=None):
    """Keys a query scores, mean over the ``seq`` positions of a row."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def layer_keys(d, seq):
    """Mean keys a query scores in each attention layer, in order."""
    return [mean_keys(seq, d["sliding_window"] if kind == "window" else None)
            for kind in d["layer_types"]]


def attention_layers_as_causal(d, seq):
    """The attention layers' required work in units of one full causal
    layer's: what ``kernel_work.flash_work`` takes as ``n_layer``."""
    return sum(layer_keys(d, seq)) / mean_keys(seq)


def attention_work(d, seq, tokens, kind, backward=False):
    """(operations, bytes) of the attention layers of ``kind`` (``"window"``
    or ``"full"``) for ``tokens`` tokens in rows of ``seq``. Operations:
    QK^T and PV over all query heads at the layer's mean keys, twice that
    backward. Bytes, bf16, each tensor once (as ``kernel_work.flash_work``):
    forward q, k, v, o and a float32 log-sum-exp a row and head; backward q,
    k, v, o, do, dq, dk, dv and the log-sum-exp."""
    nh, nkv, dh = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    layers = d["layer_types"].count(kind)
    keys = mean_keys(seq, d["sliding_window"] if kind == "window" else None)
    ops = layers * tokens * 4 * nh * dh * keys
    each = 4 if backward else 2
    per_token = 2 * each * (nh + nkv) * dh + 4 * nh
    return (2 if backward else 1) * ops, layers * tokens * per_token


def expert_view(d):
    """``d`` as ``hybrid_work.expert_matmul_work`` reads a model: the layers
    it counts are the expert layers."""
    return dict(d, num_hidden_layers=d["ffn_types"].count("moe"))


def matmul_params_per_token(d, local_assignments_per_token):
    """Weights every token multiplies, with the routed experts at the
    counted local assignments a token (summed over the expert layers)."""
    H = d["hidden_size"]
    nh, nkv, dh = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    attn = 2 * H * nh * dh + 2 * H * nkv * dh + nh * dh * H      # q, gate | k, v | o
    dense = 3 * H * d["intermediate_size"]
    expert_layer = H * d["router_num_experts"] + 3 * H * d["shared_intermediate_size"]
    return (d["num_hidden_layers"] * attn + d["ffn_types"].count("dense") * dense
            + d["ffn_types"].count("moe") * expert_layer
            + local_assignments_per_token * 3 * H * d["moe_intermediate_size"]
            + d["vocab_size"] * H)


def train_flops_per_token(d, seq, local_assignments_per_token):
    """Forward plus backward (twice the forward), nothing recomputed: every
    matmul weight a token meets, attention at each layer's mean keys, the
    head over the vocabulary slice."""
    attn = 4 * d["num_attention_heads"] * d["head_dim"] * sum(layer_keys(d, seq))
    return 3 * (2 * matmul_params_per_token(d, local_assignments_per_token) + attn)


def window_flops_per_token(run):
    """``train_flops_per_token`` at the window's counted local assignments:
    what the adapter hands the MFU reader under ``run["train_flops_per_token"]``."""
    assignments = hybrid_work.assignments_per_step(run)
    if assignments is None:
        return None
    return train_flops_per_token(run["dims"], run["seq"],
                                 assignments / hybrid_work.step_tokens(run))


def band_roofline_pct(run, backward=False):
    """The banded flash kernels' share of their roofline, against band work;
    ``None`` where the run's model has no window or no such kernel ran."""
    d = run.get("dims", {})
    if "sliding_window" not in d or "window" not in d.get("layer_types", ()):
        return None
    work = attention_work(d, run["seq"], hybrid_work.step_tokens(run), "window", backward)
    return hybrid_work.roofline_pct(run, BAND_BACKWARD if backward else BAND_FORWARD, work)
