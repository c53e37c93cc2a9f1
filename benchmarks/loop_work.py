"""Operations that the ``ouro`` decoder's algorithm requires, from shapes alone
(``flops.py``, ``kernel_work.py`` and the other ``*_work.py`` do the same for
the other blocks and are not touched).

``d`` is the dict ``reference.loop_ref.dims`` returns. One multiply-add is two
operations; nothing recomputed. The stack is walked ``total_ut_steps`` times
on the same weights, so a weight multiplies every token once a WALK: every
matmul weight of the layers, causal attention over its half, and the head (an
exit after every walk) count ``total_ut_steps`` times; the gate before every
walk but the first. The attention kernels' own work is ``kernel_work``'s,
read off ``dims`` whose ``n_layer`` the adapter gives as layers x walks.
"""


def layer_matmul_params(d):
    """Weights of one layer that multiply every token, once a walk: q, k, v
    and the output projection, and the SwiGLU's three matrices."""
    H, I = d["hidden_size"], d["intermediate_size"]
    nh, nkv, dh = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    return H * nh * dh + 2 * H * nkv * dh + nh * dh * H + 3 * H * I


def total_params(d):
    """Every parameter held: the layers with their four norms, the untied
    embedding and head, the final norm, the gate with its bias."""
    H = d["hidden_size"]
    return (d["num_hidden_layers"] * (layer_matmul_params(d) + 4 * H)
            + 2 * d["vocab_size"] * H + H + H + 1)


def walk_flops_per_token(d, seq):
    """One walk forward, with its exit: the layers' matmuls, causal attention
    (position i scores against i + 1 keys, (seq + 1) / 2 on average, for QK^T
    and for PV, over all heads) and the head over the whole vocabulary."""
    L, H = d["num_hidden_layers"], d["hidden_size"]
    attn = L * 4 * d["num_attention_heads"] * d["head_dim"] * (seq + 1) / 2
    return 2 * (L * layer_matmul_params(d) + d["vocab_size"] * H) + attn


def train_flops_per_token(d, seq):
    """Forward plus backward (twice the forward), nothing recomputed: every
    walk with its exit, and the gate after every walk but the last."""
    T = d["total_ut_steps"]
    return 3 * (T * walk_flops_per_token(d, seq) + (T - 1) * 2 * d["hidden_size"])


def window_flops_per_token(run):
    """What the adapter hands the MFU reader under
    ``run["train_flops_per_token"]``."""
    return train_flops_per_token(run["dims"], run["seq"])
