"""The one place that knows both parameter layouts: the reference's plain
tree (``reference/gpt_ref.py``) and ``apex_tpu.models.GPTModel``'s.

The program stores every linear as (out, in) and applies ``x @ W.T``; the
reference stores (in, out) as the published checkpoints' Conv1D does. Both
pack the fused attention projection q | k | v. The map is a relabelling with
transposes, so a norm taken leaf by leaf is the same on either side.
"""

import jax.numpy as jnp


def gpt_config_kwargs(d, **settings):
    """``GPTConfig`` keyword arguments from the reference's dims."""
    return dict(vocab_size=d["vocab_rows"], max_seq_len=d["n_positions"],
                hidden_size=d["n_embd"], ffn_hidden_size=d["n_inner"],
                num_layers=d["n_layer"], num_heads=d["n_head"],
                num_kv_heads=d["n_kv_head"], tp_size=1, **settings)


def to_program(w):
    t = lambda a: jnp.swapaxes(a, -1, -2)  # noqa: E731
    return {
        "embedding": {"weight": w["wte"]},
        "pos_embedding": w["wpe"],
        "layers": {
            "ln1_w": w["ln1_g"], "ln1_b": w["ln1_b"],
            "qkv": {"weight": t(w["w_qkv"]), "bias": w["b_qkv"]},
            "attn_out": {"weight": t(w["w_o"]), "bias": w["b_o"]},
            "ln2_w": w["ln2_g"], "ln2_b": w["ln2_b"],
            "mlp_up": {"weight": t(w["w_fc"]), "bias": w["b_fc"]},
            "mlp_down": {"weight": t(w["w_proj"]), "bias": w["b_proj"]},
        },
        "lnf_w": w["lnf_g"], "lnf_b": w["lnf_b"],
    }


def leaf_norms(tree):
    """Euclidean norm of every tensor, one per layer for the stacked ones:
    a dict of name -> (L,) or () float32 arrays, named by the program's
    tree paths."""
    import jax

    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if "layers" in name else None
        out[name] = jnp.sqrt(jnp.sum(a * a, axis=axes))
    return out


def leaf_projections(tree, key=None):
    """Every tensor's inner product with a fixed random direction (normal,
    from ``key`` and the tensor's place in the tree), one per layer for the
    stacked ones, named as :func:`leaf_norms` names them. A norm hides
    rounding noise (it adds in squares); a projection moves with it in the
    first order, at the same cost of one number a tensor."""
    import jax

    key = jax.random.PRNGKey(20260927) if key is None else key
    out = {}
    for i, (path, a) in enumerate(jax.tree_util.tree_flatten_with_path(tree)[0]):
        name = jax.tree_util.keystr(path)
        a = a.astype(jnp.float32)
        r = jax.random.normal(jax.random.fold_in(key, i), a.shape, jnp.float32)
        axes = tuple(range(1, a.ndim)) if "layers" in name else None
        out[name] = jnp.sum(a * r, axis=axes)
    return out
