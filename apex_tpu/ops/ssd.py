"""The state-space scan of Mamba-2 (SSD: a selective state space whose decay
is one scalar a head and token), over whole sequences from a zero state.

Per head (``P`` features) with the ``B``, ``C`` (``N`` wide) of its group and
float32 state ``S (N, P)``:

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T;        y_t = S_t^T C_t + D x_t

:func:`ssd_scan` computes it in chunks of ``chunk`` tokens: inside a chunk the
tokens see each other through the masked decay matrix ``e^{g_i - g_j}`` (``g``
the cumulative log decay inside the chunk, so nothing is ever divided by a
decay), and a recurrence over *chunks* carries the state — matmuls instead
of ``chunk`` rank-one updates. The Pallas pair ``ssd_fwd`` / ``ssd_bwd``
(``ops/pallas/ssd.py``) keeps the state in VMEM and passes over ``x, dt, B,
C`` and ``y`` once; ``impl="xla"`` is the same chunked form as einsums and a
``lax.scan`` over the chunks, which XLA differentiates — the oracle the
kernels are tested against and the path for shapes they refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.amp.lists import apply_op_rules
from apex_tpu.ops import _backend
from apex_tpu.ops.pallas import ssd as _k

_HI = jax.lax.Precision.HIGHEST

# jitted: the layers of a model share one traced and lowered program a kernel
_ssd_fwd = jax.jit(_k.ssd_fwd, static_argnames=("groups", "chunk", "interpret"))
_ssd_bwd = jax.jit(_k.ssd_bwd, static_argnames=("groups", "chunk", "interpret"))

# ``ssd_fwd``'s results by the names a ``jax.checkpoint`` policy keeps them
# under: the output and the entry states of the steps of chunks, which the
# backward rule reads; with both saved a recomputed block does not launch the
# forward kernel again.
SSD_SAVED = ("ssd_o", "ssd_s0")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan_pallas(x, dt, A, B, C, D, groups, chunk, interpret):
    """The scan on the kernels: ``x`` (b, T, H P), ``B``, ``C`` (b, T, G N) as
    the convolution leaves them, ``dt`` (b, T, H), ``A``, ``D`` (H,) float32."""
    return _ssd_fwd(x, dt, A, B, C, D, groups=groups, chunk=chunk, interpret=interpret)[0]


def _scan_fwd(x, dt, A, B, C, D, groups, chunk, interpret):
    y, s0 = map(checkpoint_name, _ssd_fwd(x, dt, A, B, C, D, groups=groups, chunk=chunk,
                                          interpret=interpret), SSD_SAVED)
    return y, (x, dt, A, B, C, D, s0)


def _scan_bwd(groups, chunk, interpret, res, dy):
    return tuple(_ssd_bwd(*res, dy, groups=groups, chunk=chunk, interpret=interpret))


_scan_pallas.defvjp(_scan_fwd, _scan_bwd)


def _scan_xla(x, dt, A, B, C, D, chunk):
    """The chunked form in XLA: ``x`` (b, T, H, P), ``dt`` (b, T, H), ``B``,
    ``C`` (b, T, G, N), ``T`` in whole chunks. Matmul operands in ``x``'s
    dtype, float32 accumulation, decays and state float32. Heads lead every
    product (plain batched matmuls, forward and transposed)."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    n, dtype = T // chunk, x.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32,
                           precision=_HI if dtype == jnp.float32 else None)
    # (b, T, heads, ...) -> (b, n, heads, Q, ...)
    by_chunk = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, n, chunk) + a.shape[2:]), 2, 3)
    x, dt, B, C = map(by_chunk, (x, dt, B, C))
    g = jnp.cumsum(dt * A[:, None], axis=-1)                         # (b, n, H, Q)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.exp(jnp.where(tri, g[..., :, None] - g[..., None, :], -jnp.inf))
    serve = lambda a: jnp.repeat(a, H // G, axis=2)  # noqa: E731    # a head its group's
    cb = serve(mm("bngik,bngjk->bngij", C, B))
    m = (cb * L * dt[..., None, :]).astype(dtype)
    inside = mm("bnhij,bnhjp->bnhip", m, x)
    w = jnp.exp(g[..., -1:] - g) * dt                                # (b, n, H, Q)
    xw = (x.astype(jnp.float32) * w[..., None]).astype(dtype)
    wrote = mm("bnhjk,bnhjp->bnhkp", serve(B), xw)                   # (b, n, H, N, P)
    gam = jnp.exp(g[..., -1])                                        # (b, n, H)

    def step(state, inputs):
        wrote, gam = inputs
        return state * gam[..., None, None] + wrote, state

    _, entry = jax.lax.scan(step, jnp.zeros((b, H, N, P), jnp.float32),
                            (jnp.moveaxis(wrote, 1, 0), jnp.moveaxis(gam, 1, 0)))
    entry = jnp.moveaxis(entry, 0, 1).astype(dtype)                  # (b, n, H, N, P)
    y = inside + jnp.exp(g)[..., None] * mm("bnhik,bnhkp->bnhip", serve(C), entry)
    y = y + D[:, None, None] * x.astype(jnp.float32)
    return jnp.moveaxis(y.astype(dtype), 2, 3).reshape(b, T, H, P)


def shapes_ok(P: int, N: int, heads: int, groups: int, chunk: int) -> bool:
    """What the kernels' blocks need: a head an exact share of a lane tile
    (or a whole one), a group's heads in whole lane tiles, the state's rows
    and the chunk in whole lane tiles (the chunk's matrices are (chunk,
    chunk), ``B`` and ``C`` of a group a lane block)."""
    lanes = _k.LANES
    return (P >= 8 and lanes % P == 0 and heads % groups == 0
            and (heads // groups * P) % lanes == 0 and N % lanes == 0 and chunk % lanes == 0)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128, impl: str = "auto"):
    """The scan of the module docstring. ``x`` (b, t, h, p); ``dt`` (b, t, h)
    positive (after its softplus) and ``A`` (h,) negative, ``D`` (h,), float32
    whatever the inputs; ``B``, ``C`` (b, t, g, n) with ``h`` a multiple of
    ``g`` (each group serves ``h // g`` heads). Returns ``y`` (b, t, h, p) in
    ``x``'s dtype. ``t`` need not be a multiple of ``chunk``: the tail is
    padded with tokens that neither decay nor write. HALF-class under O1
    (matmul-shaped; decays and the state are float32 inside regardless).

    ``impl``: ``auto`` | ``pallas`` | ``xla`` — the ``ssd_fwd`` / ``ssd_bwd``
    kernels, or the same chunked mathematics as einsums and a ``lax.scan``
    over the chunks.
    """
    x, B, C = apply_op_rules("ssd_scan", x, B, C)
    b, t, h, p = x.shape
    g, n_state = B.shape[2:]
    use_kernel = _backend.choose_impl(impl, shapes_ok(p, n_state, h, g, chunk)) == "pallas"
    n = -(-t // chunk)
    if use_kernel and n > _k.CHUNKS:
        n = -(-n // _k.CHUNKS) * _k.CHUNKS         # whole grid steps
    pad = n * chunk - t
    dt, A, D = (a.astype(jnp.float32) for a in (dt, A, D))

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) if pad else a

    x, dt, B, C = map(padded, (x, dt, B, C))
    if use_kernel:
        flat = lambda a: a.reshape(b, n * chunk, -1)  # noqa: E731
        y = _scan_pallas(flat(x), dt, A, flat(B), flat(C), D, g, chunk,
                         _backend.interpret_mode()).reshape(x.shape)
    else:
        y = _scan_xla(x, dt, A, B, C, D, chunk)
    return y[:, :t]
