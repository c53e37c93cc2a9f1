"""The gated delta rule (linear attention with a decaying, error-correcting
state) and the small ops that stand around it in a decoder layer.

Per value head, with float32 state ``S (d_k, d_v)``, decay ``g_t <= 0`` and
write strength ``beta_t`` in (0, 1):

    S <- exp(g_t) S;   u_t = beta_t (v_t - S^T k_t);   S <- S + k_t u_t^T;   o_t = S^T q_t

:func:`gated_delta_rule` computes it in chunks of ``chunk`` tokens (the WY /
UT form): inside a chunk the ``u`` solve a unit lower-triangular system
``(I + A) U = beta V - (beta e^G K) S_0``, so ``T = (I + A)^-1`` turns each
chunk into operands of a recurrence over *chunks* — matmuls instead of
``chunk`` rank-one updates (float32 decays, cumulative inside the chunk
only, so nothing is ever divided by a decay). The two ``impl``s are the
same mathematics in two orders. The Pallas kernel pair ``gdn_fwd`` /
``gdn_bwd`` (``ops/pallas/gated_delta_rule.py``) takes eight chunks of one key
head, of the value heads it serves and of two batch rows (one of an odd
batch) a grid step: it builds all their operands in VMEM side by side, their
triangular systems inverted as one batch, and walks the chunks unrolled in
the same basic block with the states as values — the rows' chains side by
side; the backward keeps ``T`` and the scores of its preparation and turns a
chunk's cotangents into its inputs' where ``dS`` is carried through it.
``impl="xla"`` prepares all chunks of the sequence at once in XLA and runs a
``lax.scan`` over them: the oracle the kernels are tested against, as every
kernel family here has one, and the path for shapes the kernels refuse.

:func:`kda_rule` is the same recurrence with the decay a vector a key channel
(``g_t`` in R^dk a head: ``S <- Diag(exp(g_t)) S``; Kimi Delta Attention). The
in-chunk matrix is then ``beta_i sum_c k_ic k_jc e^{G_ic - G_jc}``, which no
longer factors into a matmul and a (C, C) mask: the kernel pair ``kda_fwd`` /
``kda_bwd`` (``ops/pallas/kda.py``) makes it a sub-chunk of 16 rows at a time
from float32 factors that stay inside float32's range as long as every
step's log decay is at least ``KDA_LOG_DECAY_MIN`` (-5: what a bounded gate
gives), and everything after it — the inverse, the walk, the saved results,
the shape of a grid step (two batch rows, a few long blocks that make nothing
twice) — is the scalar rule's; its ``impl="xla"`` oracle takes the explicit
differences chunk by chunk in a ``lax.scan``.

Also here: :func:`causal_conv_silu` (the depthwise causal convolution that
precedes the rule) and :func:`gated_rms_norm` (the head-wise RMSNorm times
``silu(gate)`` that follows it). Both take ``impl`` as the rule does and
read a fused projection in place. On a TPU (and in interpret mode where the
tests force it) the kernels of ``ops/pallas/delta_mixer.py`` run —
``conv_silu_fwd`` / ``conv_silu_bwd``, ``gated_norm_fwd`` / ``gated_norm_bwd``:
one pass over the arrays in their own dtype, float32 in VMEM only — wherever
:func:`conv_shapes_ok` / :func:`norm_shapes_ok` hold: channels and heads in
whole lane blocks, rows in whole sublane tiles. Every other shape, and
``impl="xla"``, takes the float32 compositions (:func:`_conv_xla`,
:func:`_norm_xla`), which XLA differentiates piece by piece: the oracle of
the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.amp.lists import apply_op_rules
from apex_tpu.ops import _backend
from apex_tpu.ops.pallas import delta_mixer as _m
from apex_tpu.ops.pallas import gated_delta_rule as _k
from apex_tpu.ops.pallas import kda as _kda

_HI = jax.lax.Precision.HIGHEST


def _conv_xla(x, w, bias=None):
    taps, t = w.shape[0], x.shape[1]
    pad = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(pad[:, j:j + t].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype)


def _norm_xla(x, gate, weight, eps, gate_first=False):
    x32 = x.astype(jnp.float32)
    silu = jax.nn.silu(gate.astype(jnp.float32))
    if gate_first:
        x32 = x32 * silu
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    y = y * weight.astype(jnp.float32)
    return (y if gate_first else y * silu).astype(x.dtype)


# jitted: the layers of a model share one traced and lowered program a kernel
_conv_fwd = jax.jit(_m.conv_silu_fwd, static_argnames=("start", "interpret"))
_conv_bwd = jax.jit(_m.conv_silu_bwd, static_argnames=("start", "interpret"))
_NORM_STATIC = ("start", "dim", "eps", "gate_first", "interpret")
_norm_fwd = jax.jit(_m.gated_norm_fwd, static_argnames=_NORM_STATIC)
_norm_bwd = jax.jit(_m.gated_norm_bwd, static_argnames=_NORM_STATIC)


def _pieces(w, bias, widths):
    """(first channel, float32 taps, float32 bias (1, n) or None) of every piece."""
    starts = [sum(widths[:i]) for i in range(len(widths))]
    cut = lambda a, s, n: a[..., s:s + n].astype(jnp.float32)  # noqa: E731
    return [(s, cut(w, s, n), None if bias is None else cut(bias[None], s, n))
            for s, n in zip(starts, widths)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_pallas(x, w, bias, widths, interpret):
    """The convolution on the kernels, a call a piece: every piece reads its
    channels of ``x`` in place and is an array of its own. ``bias`` (c,) or
    None."""
    return tuple(_conv_fwd(x, wp, bp, start=s, interpret=interpret)
                 for s, wp, bp in _pieces(w, bias, widths))


def _conv_pallas_fwd(x, w, bias, widths, interpret):
    return _conv_pallas(x, w, bias, widths, interpret), (x, w, bias)


def _conv_pallas_bwd(widths, interpret, res, dys):
    x, w, bias = res
    dxs, dws = zip(*(_conv_bwd(x, wp, dy, bp, start=s, interpret=interpret)
                     for (s, wp, bp), dy in zip(_pieces(w, bias, widths), dys)))
    rest = jnp.zeros(x.shape[:2] + (x.shape[2] - sum(widths),), x.dtype)
    # the taps' rows and, under them, the bias's where there is one
    d = jnp.concatenate([jnp.sum(d, axis=(0, 2)) for d in dws], axis=1)
    taps = w.shape[0]
    return (jnp.concatenate(dxs + (rest,), axis=2), d[:taps].astype(w.dtype),
            None if bias is None else d[taps].astype(bias.dtype))


_conv_pallas.defvjp(_conv_pallas_fwd, _conv_pallas_bwd)


def conv_shapes_ok(x, w, widths) -> bool:
    """What the convolution kernels' blocks need: every piece in whole lane
    blocks, rows in whole sublane tiles of the dtype (a time block is a
    divisor of the length, so no tail is left over), the taps before the
    current token inside the halo."""
    return (all(n % _m.LANES == 0 for n in widths) and x.shape[1] % _m.sublanes(x.dtype) == 0
            and w.shape[0] - 1 <= _m.HALO)


def causal_conv_silu(x, w, bias=None, *, widths=None, impl: str = "auto"):
    """Depthwise causal convolution over time, then SiLU. ``x`` (b, t, c');
    ``w`` (taps, c) with the last tap on the current token and ``c <= c'``:
    the first ``c`` channels of ``x`` are convolved (a fused projection is
    read in place); ``bias`` (c,) is added before the SiLU. Float32
    accumulation, output (b, t, c) in ``x``'s dtype —
    or, with ``widths`` (which sum to ``c``), the tuple of its pieces.

    ``impl``: ``auto`` | ``pallas`` | ``xla`` — the ``conv_silu_fwd`` /
    ``conv_silu_bwd`` kernels (``x``, ``dy`` and ``dx`` cross HBM once a pass,
    float32 in VMEM only), or the composition XLA differentiates piece by
    piece."""
    c = w.shape[1]
    parts = tuple(widths) if widths is not None else (c,)
    if _backend.choose_impl(impl, conv_shapes_ok(x, w, parts)) == "pallas":
        ys = _conv_pallas(x, w, bias, parts, _backend.interpret_mode())
    else:
        y = _conv_xla(x[..., :c], w, bias)
        ys = jnp.split(y, [sum(parts[:i]) for i in range(1, len(parts))], axis=-1)
    return tuple(ys) if widths is not None else ys[0]


def _weight_row(w):
    """The norm's weight as the kernels take it: (1, dim) for every head, or
    (1, heads dim) a channel its own."""
    w = w.astype(jnp.float32)
    return w[None] if w.ndim == 1 else w.reshape(1, -1)


def _rows(x, gate):
    """``x`` (..., heads, dim) and ``gate`` (..., c) as rows."""
    return x.reshape(-1, x.shape[-2] * x.shape[-1]), gate.reshape(-1, gate.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm_pallas(x, gate, w, eps, start, gate_first, interpret):
    """``x`` (..., heads, dim); ``gate`` (..., c): its ``heads dim`` channels
    from ``start`` on gate; ``w`` (dim,), or (heads, dim) a channel its own."""
    o, z = _rows(x, gate)
    y = _norm_fwd(o, z, _weight_row(w), start=start, dim=x.shape[-1],
                  eps=eps, gate_first=gate_first, interpret=interpret)
    return y.reshape(x.shape)


def _norm_pallas_fwd(x, gate, w, eps, start, gate_first, interpret):
    return _norm_pallas(x, gate, w, eps, start, gate_first, interpret), (x, gate, w)


def _norm_pallas_bwd(eps, start, gate_first, interpret, res, dy):
    x, gate, w = res
    o, z = _rows(x, gate)
    do, dz, dw = _norm_bwd(o, z, _weight_row(w), dy.reshape(o.shape),
                           start=start, dim=x.shape[-1], eps=eps, gate_first=gate_first,
                           interpret=interpret)
    # the other channels' zeros in the gate's own shape: XLA folds them into
    # whatever reads the cotangent
    after = gate.shape[-1] - start - o.shape[-1]
    dz = jnp.pad(dz.reshape(gate.shape[:-1] + (-1,)),
                 ((0, 0),) * (gate.ndim - 1) + ((start, after),))
    if w.ndim == 1:
        return do.reshape(x.shape), dz, jnp.sum(dw, axis=(0, 1)).astype(w.dtype)
    return do.reshape(x.shape), dz, jnp.sum(dw, axis=1).reshape(w.shape).astype(w.dtype)


_norm_pallas.defvjp(_norm_pallas_fwd, _norm_pallas_bwd)


def norm_shapes_ok(x, gate, start=None) -> bool:
    """What the gated norm's kernels need: a head in whole lane blocks and
    whole heads a channel block (so the gate's first channel is on a block's
    edge), rows in whole sublane tiles of the dtypes."""
    heads, dim = x.shape[-2:]
    rows = x.size // (heads * dim)
    start = gate.shape[-1] - heads * dim if start is None else start
    return (dim % _m.LANES == 0 and _m.NORM_BLOCK[1] % dim == 0 and start % dim == 0
            and rows % max(_m.sublanes(x.dtype), _m.sublanes(gate.dtype)) == 0)


def gated_rms_norm(x, gate, weight, eps=1e-6, *, gate_first: bool = False, gate_start=None,
                   impl: str = "auto"):
    """``rmsnorm(x) * weight * silu(gate)`` over the last axis (one head, or
    one group of a wider layer), statistics in float32 — with ``gate_first``
    ``rmsnorm(x * silu(gate)) * weight``, the gate inside the statistics.
    ``x`` (..., heads, dim); ``weight`` (dim,) for every head, or (heads, dim)
    a channel its own; ``gate`` in ``x``'s shape, or (..., c) with ``c >=
    heads dim``: ``heads dim`` channels of a fused projection, read in place
    from channel ``gate_start`` on (default: the last ones).

    ``impl``: ``auto`` | ``pallas`` | ``xla`` — the ``gated_norm_fwd`` /
    ``gated_norm_bwd`` kernels (one pass each, the statistics recomputed in
    the backward), or the float32 composition."""
    heads, dim = x.shape[-2:]
    if gate.ndim == x.ndim:
        gate = gate.reshape(gate.shape[:-2] + (heads * dim,))
    start = gate.shape[-1] - heads * dim if gate_start is None else gate_start
    if _backend.choose_impl(impl, norm_shapes_ok(x, gate, start)) == "pallas":
        return _norm_pallas(x, gate, weight, float(eps), start, bool(gate_first),
                            _backend.interpret_mode())
    return _norm_xla(x, gate[..., start:start + heads * dim].reshape(x.shape), weight, eps,
                     gate_first)


def l2_normalize(x, eps=_k.EPS):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + eps)


# --- inverse of a unit lower-triangular matrix --------------------------------

def _substitute(a):
    """``(I + a)^-1`` of strictly lower-triangular blocks ``a`` (s, s, N), the
    batch in the minor axis: row after row, ``x_i = e_i - sum_j a_ij x_j`` —
    elementwise float32 work in the recurrence's own order. (A loop, not
    ``s^2 / 2`` unrolled updates: XLA reads ``a`` through the transpose that
    made it for every one of those, four times the time on the chip.)"""
    s = a.shape[0]
    eye = jnp.eye(s, dtype=a.dtype)

    def row(i, x):                         # rows from i on are still zero
        a_i = jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        r = eye[i][:, None] - jnp.sum(a_i[:, None, :] * x, axis=0)
        return jax.lax.dynamic_update_index_in_dim(x, r, i, 0)

    return jax.lax.fori_loop(0, s, row, jnp.zeros_like(a))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(a, precision=_HI):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., C, C), float32,
    by blocked forward substitution: the diagonal blocks of at least 32 rows
    row after row, then pairs of blocks merged, ``[[P, 0], [Q, R]]^-1 =
    [[P^-1, 0], [-R^-1 Q P^-1, R^-1]]``, until one block is left (two
    float32 matmuls a doubling). Every intermediate is a block of the
    inverse itself, so keys that point the same way (``a`` near 1
    everywhere) lose no digits — the finite product ``(I - a)(I + a^2)
    (I + a^4)...`` is the same matrix and cancels powers of ``a`` as large as
    ``binom(C, C/2)`` to get it. ``precision`` is the cotangent's."""
    C = a.shape[-1]
    s = C
    while s % 2 == 0 and s // 2 >= 32:
        s //= 2
    nb, lead = C // s, a.shape[:-2]
    blocks = jnp.diagonal(a.reshape(lead + (nb, s, nb, s)), axis1=-4, axis2=-2)
    x = _substitute(jnp.moveaxis(blocks, (-3, -2), (0, 1)).reshape(s, s, -1))
    x = jnp.moveaxis(x.reshape((s, s) + lead + (nb,)), (0, 1), (-2, -1))   # (..., nb, s, s)
    d = (x[..., :, :, None, :] * jnp.eye(nb, dtype=a.dtype)[:, None, :, None]
         ).reshape(a.shape)                                             # block diagonal
    while s < C:
        row, col = (jnp.arange(C) // s)[:, None], (jnp.arange(C) // s)[None, :]
        q = jnp.where((row % 2 == 1) & (col == row - 1), a, 0.0)
        d = d - jnp.matmul(jnp.matmul(d, q, precision=_HI), d, precision=_HI)
        s *= 2
    return d


def _inv_fwd(a, precision):
    t = _unit_lower_inverse(a, precision)
    return t, t


def _inv_bwd(precision, t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(jnp.matmul(tt, dt, precision=precision), tt, precision=precision),)


_unit_lower_inverse.defvjp(_inv_fwd, _inv_bwd)


# --- chunk operands (XLA) and the recurrence over chunks ----------------------

def _chunk_operands(q, k, v, g, beta, C, dtype):
    """q, k (b, hk, n, C, dk) float32 (normalised, q scaled), v (b, hv, n, C,
    dv), g, beta (b, hv, n, C) float32 -> w, u, qg, kg, p, gam of the module
    docstring, the matmul operands cast to ``dtype``. The chunk-local
    products run at the precision of what they feed: one bf16 pass where the
    recurrence takes bf16 operands (the rounding of ``w`` and ``u`` themselves
    is as large), float32 passes where it takes float32."""
    hi = jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else _HI
    G = jnp.cumsum(g, axis=-1)
    tri = jnp.tril(jnp.ones((C, C), bool))
    diff = jnp.where(tri, G[..., :, None] - G[..., None, :], -jnp.inf)
    decay = jnp.exp(diff)                                  # e^{G_i - G_j}, i >= j
    # the scores are the key heads' own: computed once a key head, then
    # given to the value heads it serves
    serve = lambda x: jnp.repeat(x, v.shape[1] // k.shape[1], axis=1)  # noqa: E731
    kk = serve(jnp.einsum("...ik,...jk->...ij", k, k, precision=hi))
    qk = serve(jnp.einsum("...ik,...jk->...ij", q, k, precision=hi))
    q, k = serve(q), serve(k)
    a = jnp.where(jnp.tril(tri, -1), beta[..., None] * kk * decay, 0.0)
    t = _unit_lower_inverse(a, hi)
    eg = jnp.exp(G)[..., None]
    w = jnp.einsum("...ij,...jk->...ik", t, beta[..., None] * eg * k, precision=hi)
    u = jnp.einsum("...ij,...jk->...ik", t, beta[..., None] * v.astype(jnp.float32),
                   precision=hi)
    p = qk * decay
    kg = k * jnp.exp(G[..., -1:, None] - G[..., None])
    gam = jnp.exp(G[..., -1])
    cast = lambda x: x.astype(dtype)  # noqa: E731
    return cast(w), cast(u), cast(q * eg), cast(kg), cast(p), gam


def _recurrence_xla(w, u, qg, kg, p, gam):
    """(b, h, n, C, .) operands, gam (b, h, n): a scan over the chunks."""
    f32 = jnp.float32
    mm = functools.partial(jnp.einsum, preferred_element_type=f32, precision=_HI)

    def chunk(state, x):
        w, u, qg, kg, p, gam = x
        s = state.astype(w.dtype)
        v_new = u.astype(f32) - mm("bhck,bhkv->bhcv", w, s)
        o = mm("bhck,bhkv->bhcv", qg, s) + mm("bhcj,bhjv->bhcv", p, v_new.astype(p.dtype))
        state = state * gam[..., None, None] + mm("bhck,bhcv->bhkv", kg, v_new.astype(kg.dtype))
        return state, o.astype(u.dtype)

    b, h, n, C, dk = w.shape
    xs = jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0), (w, u, qg, kg, p, gam))
    _, o = jax.lax.scan(chunk, jnp.zeros((b, h, dk, u.shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 2)


# jitted: the layers of a model share one traced and lowered program a kernel
_gdn_fwd = jax.jit(_k.gdn_fwd, static_argnames=("heads", "interpret"))
_gdn_bwd = jax.jit(_k.gdn_bwd, static_argnames=("heads", "interpret"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _rule_pallas(q, k, v, G, beta, gl, heads, interpret):
    """The rule on the kernels: ``q, k`` (b, T, hk dk), ``v`` (b, T, hv dv) as
    the convolution leaves them, ``G`` (the log decay cumulated inside each
    chunk) and ``beta`` (b, hv, n, C) float32, ``gl`` (b, hv, n, dv) the
    chunk's last ``G`` over the lanes. ``heads`` = hk."""
    return _gdn_fwd(q, k, v, G, beta, gl, heads=heads, interpret=interpret)[0]


# ``gdn_fwd``'s results by the names a ``jax.checkpoint`` policy keeps them
# under (``save_only_these_names``): the output and the chunks' entry states,
# which the backward rule reads; with both saved a recomputed block does not
# launch the forward kernel again. Outside ``jax.checkpoint`` a name lowers
# to nothing.
RULE_SAVED = ("gdn_o", "gdn_s0")


def _rule_fwd(q, k, v, G, beta, gl, heads, interpret):
    o, s0 = map(checkpoint_name,
                _gdn_fwd(q, k, v, G, beta, gl, heads=heads, interpret=interpret), RULE_SAVED)
    return o, (q, k, v, G, beta, gl, s0)


def _rule_bwd(heads, interpret, res, do):
    return tuple(_gdn_bwd(*res, do, heads=heads, interpret=interpret))


_rule_pallas.defvjp(_rule_fwd, _rule_bwd)


def _chunked(t, chunk, whole_steps):
    """A sequence of ``t`` tokens in chunks: (their number — whole grid steps
    of the kernels' ``CHUNKS`` where ``whole_steps`` and there are more than
    one step's —, ``padded``: (b, t, ...) with the tail filled with tokens that
    neither decay nor write, ``chunks``: (b, t, heads, ...) -> (b, heads, n,
    C, ...))."""
    n = -(-t // chunk)
    if whole_steps and n > _k.CHUNKS:
        n = -(-n // _k.CHUNKS) * _k.CHUNKS
    pad = n * chunk - t

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) if pad else x

    def chunks(x):
        x = jnp.moveaxis(padded(x), 1, 2)
        return x.reshape(x.shape[:2] + (n, chunk) + x.shape[3:])

    return n, padded, chunks


def shapes_ok(dk: int, dv: int, chunk: int) -> bool:
    """What the kernels' blocks need: features in whole lanes (a head is a
    lane block of the projections' arrays), chunks in whole sublane tiles of
    either operand dtype."""
    return dk % _k.LANES == 0 and dv % _k.LANES == 0 and chunk % 16 == 0


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64, impl: str = "auto"):
    """The rule of the module docstring over whole sequences from a zero state.

    ``q``, ``k`` (b, t, hk, dk) as they leave the convolution — they are
    L2-normalised here, ``q`` scaled by ``dk ** -0.5``; ``v`` (b, t, hv, dv)
    with ``hv`` a multiple of ``hk`` (each key head serves ``hv // hk``
    value heads); ``g`` (log decay) and ``beta`` (b, t, hv), float32 whatever
    the inputs. Returns ``o`` (b, t, hv, dv) in ``v``'s dtype. ``t`` need not
    be a multiple of ``chunk``: the tail is padded with tokens that neither
    decay nor write. HALF-class under O1 (matmul-shaped; decays, norms and
    the state are float32 inside regardless).

    ``impl``: ``auto`` | ``pallas`` | ``xla`` — the ``gdn_fwd`` / ``gdn_bwd``
    kernels, which build every chunk's operands in VMEM, or the same
    mathematics with the operands prepared by XLA and a ``lax.scan`` over
    the chunks.
    """
    q, k, v = apply_op_rules("gated_delta_rule", q, k, v)
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    use_kernel = _backend.choose_impl(impl, shapes_ok(dk, dv, chunk)) == "pallas"
    n, padded, chunks = _chunked(t, chunk, use_kernel)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if use_kernel:
        # only the small float32 g and beta are re-laid (a chunk a row); q, k, v
        # and o keep the layout of the projections, a head a lane block
        G = jnp.cumsum(chunks(g), axis=-1)
        gl = jnp.broadcast_to(G[..., -1:], G.shape[:-1] + (dv,))
        flat = lambda x: padded(x).reshape(b, n * chunk, -1)  # noqa: E731
        o = _rule_pallas(flat(q), flat(k), flat(v), G, chunks(beta), gl,
                         hk, _backend.interpret_mode())
        return o.reshape(b, n * chunk, hv, dv)[:, :t]
    qn = l2_normalize(q) * dk ** -0.5
    kn = l2_normalize(k)
    ops = _chunk_operands(chunks(qn), chunks(kn), chunks(v), chunks(g), chunks(beta), chunk,
                          v.dtype)
    o = _recurrence_xla(*ops).reshape(b, hv, n * chunk, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t]


# --- the rule with a decay a key channel (KDA) --------------------------------

KDA_LOG_DECAY_MIN = _kda.LOG_DECAY_MIN
# ``kda_fwd``'s results by the names a ``jax.checkpoint`` policy keeps them
# under, as ``RULE_SAVED`` are ``gdn_fwd``'s
KDA_SAVED = ("kda_o", "kda_s0")

_kda_fwd = jax.jit(_kda.kda_fwd, static_argnames=("interpret",))
_kda_bwd = jax.jit(_kda.kda_bwd, static_argnames=("interpret",))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda_pallas(q, k, v, g, beta, interpret):
    """The rule on the kernels: ``q, k, v`` (b, T, h d) as the convolution
    leaves them, ``g`` (b, T, h dk) float32 the per-step log decay, ``beta``
    (b, h, n, C) float32."""
    return _kda_fwd(q, k, v, g, beta, interpret=interpret)[0]


def _kda_rule_fwd(q, k, v, g, beta, interpret):
    o, s0 = map(checkpoint_name, _kda_fwd(q, k, v, g, beta, interpret=interpret), KDA_SAVED)
    return o, (q, k, v, g, beta, s0)


def _kda_rule_bwd(interpret, res, do):
    return tuple(_kda_bwd(*res, do, interpret=interpret))


_kda_pallas.defvjp(_kda_rule_fwd, _kda_rule_bwd)


def _kda_chunk(q, k, v, g, beta, dtype):
    """One chunk's operands by the explicit differences: q, k (b, h, C, dk)
    float32 (normalised, q scaled), v (b, h, C, dv), g (b, h, C, dk), beta
    (b, h, C) -> w, u, qg, kg, p in ``dtype`` and gam (b, h, dk)."""
    hi = jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else _HI
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-2)
    tri = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(tri[..., None], G[..., :, None, :] - G[..., None, :, :], -jnp.inf))
    kk = jnp.einsum("...ic,...jc,...ijc->...ij", k, k, decay, precision=_HI)
    qk = jnp.einsum("...ic,...jc,...ijc->...ij", q, k, decay, precision=_HI)
    a = jnp.where(jnp.tril(tri, -1), beta[..., None] * kk, 0.0)
    t = _unit_lower_inverse(a, hi)
    eg = jnp.exp(G)
    w = jnp.einsum("...ij,...jk->...ik", t, beta[..., None] * eg * k, precision=hi)
    u = jnp.einsum("...ij,...jk->...ik", t, beta[..., None] * v.astype(jnp.float32),
                   precision=hi)
    cast = lambda x: x.astype(dtype)  # noqa: E731
    return (cast(w), cast(u), cast(q * eg), cast(k * jnp.exp(G[..., -1:, :] - G)),
            cast(jnp.where(tri, qk, 0.0)), eg[..., -1, :])


def _kda_xla(q, k, v, g, beta, dtype):
    """(b, h, n, C, .) inputs: a scan over the chunks, each preparing its
    operands (recomputed in the backward pass) and moving the state."""
    f32 = jnp.float32
    mm = functools.partial(jnp.einsum, preferred_element_type=f32, precision=_HI)
    prepare = jax.checkpoint(functools.partial(_kda_chunk, dtype=dtype))

    def chunk(state, x):
        w, u, qg, kg, p, gam = prepare(*x)
        s = state.astype(dtype)
        v_new = u.astype(f32) - mm("bhck,bhkv->bhcv", w, s)
        o = mm("bhck,bhkv->bhcv", qg, s) + mm("bhcj,bhjv->bhcv", p, v_new.astype(dtype))
        state = state * gam[..., None] + mm("bhck,bhcv->bhkv", kg, v_new.astype(dtype))
        return state, o.astype(dtype)

    b, h, n, C, dk = q.shape
    xs = jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0), (q, k, v, g, beta))
    _, o = jax.lax.scan(chunk, jnp.zeros((b, h, dk, v.shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 2)


def kda_shapes_ok(dk: int, dv: int, chunk: int) -> bool:
    """What the KDA kernels' blocks need: :func:`shapes_ok`, and chunks in
    whole sub-chunks of the scores."""
    return shapes_ok(dk, dv, chunk) and chunk % _kda.SUB == 0


def kda_rule(q, k, v, g, beta, *, chunk: int = 64, impl: str = "auto"):
    """The delta rule with a decay a key channel over whole sequences from a
    zero state: ``S <- Diag(exp(g_t)) S; u_t = beta_t (v_t - S^T k_t); S <- S
    + k_t u_t^T; o_t = S^T q_t``.

    ``q``, ``k`` (b, t, h, dk) as they leave the convolution — L2-normalised
    here, ``q`` scaled by ``dk ** -0.5``; ``v`` (b, t, h, dv), as many value
    as key heads; ``g`` (b, t, h, dk) the log decay of every key channel and
    ``beta`` (b, t, h), float32 whatever the inputs. Returns ``o`` (b, t, h,
    dv) in ``v``'s dtype. The tail of a ``t`` that is no multiple of
    ``chunk`` is padded with tokens that neither decay nor write. HALF-class
    under O1, as :func:`gated_delta_rule`.

    ``impl``: ``auto`` | ``pallas`` | ``xla`` — the ``kda_fwd`` / ``kda_bwd``
    kernels, which need every ``g >= KDA_LOG_DECAY_MIN`` (their float32
    factors reach ``e^{15 |g|}``: a gate bounded below, ``-5 sigmoid(.)``,
    gives it; nothing checks it), or the explicit differences chunk by chunk
    in a ``lax.scan``, which take any ``g <= 0``."""
    q, k, v = apply_op_rules("kda_rule", q, k, v)
    b, t, h, dk = q.shape
    dv = v.shape[3]
    use_kernel = _backend.choose_impl(impl, kda_shapes_ok(dk, dv, chunk)) == "pallas"
    n, padded, chunks = _chunked(t, chunk, use_kernel)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if use_kernel:
        flat = lambda x: padded(x).reshape(b, n * chunk, -1)  # noqa: E731
        o = _kda_pallas(flat(q), flat(k), flat(v), flat(g), chunks(beta),
                        _backend.interpret_mode())
        return o.reshape(b, n * chunk, h, dv)[:, :t]
    qn = l2_normalize(q) * dk ** -0.5
    o = _kda_xla(chunks(qn), chunks(l2_normalize(k)), chunks(v), chunks(g), chunks(beta), v.dtype)
    return jnp.moveaxis(o.reshape(b, h, n * chunk, dv), 1, 2)[:, :t]
