"""Pallas kernels of the chunked gated delta rule: a chunk's operands are
built where they are used, in VMEM, and only ``q, k, v, g, beta`` and ``o``
cross HBM.

Per chunk of ``C`` tokens and value head, with ``G`` the in-chunk cumulative
log decay, ``qn``/``kn`` the L2-normalised queries and keys of the key head
that serves the value head, and float32 state ``S (dk, dv)``:

    decay_ij = e^{G_i - G_j} (i >= j);   a = tril(beta kk decay, -1);   T = (I + a)^-1
    w = T (beta e^G kn);   u = T (beta v);   qg = qn e^G;   kg = kn e^{G_C - G}
    p = tril(qk decay);    v' = u - w S;    o = qg S + p v';    S <- e^{G_C} S + kg^T v'

``T`` comes from blocked forward substitution (:func:`_inverse`): the diagonal
blocks of ``BASE`` rows column after column on the VPU, then pairs of blocks
merged on the MXU — every intermediate a block of the inverse itself.

A grid step is ``CHUNKS`` chunks of one key head, the value heads it serves
and TWO batch rows (one of an odd batch: ``_plan``). Mosaic schedules a basic
block at a time and packs what stands close together in the text; every loop
trip pays its fill and drain, and a grid step costs more on the chip than its
bundles say. So a grid step is a few long blocks that make nothing twice, the
shape ``ops/pallas/kda.py`` has (PR 45), and the rows' chains stand side by
side in them:

- ``gdn_fwd``: ONE block. :func:`_prepare`, once a row, makes the operands of
  all the row's chunks side by side (nothing there waits for the state) — a
  chunk's normalised q, k and scores once a key head, each value head's
  elementwise operands, then all their triangular systems (sixteen at two
  value heads a key head) inverted as one batch, then ``T``'s products — and
  the walk over the chunks follows in the same block, a chunk of every row
  and head at a time (``for c: for r:``), the states values of the block; it
  writes the states each block started from.
- ``gdn_bwd`` walks the blocks backwards: the same preparation, which also
  KEEPS, in VMEM, ``T`` and the key head's float32 scores ``kk``, ``qk`` (the
  one product the second half would make again; the value heads' elementwise
  operands and the normalisation are made again from what is at hand: kept,
  they cost the preparation's full store slot more than they save); the
  rebuild of the block's states and ``v'`` from the starting state follows
  in the same block. Then the chunks last to first, still in that block: a
  chunk's ``du``, ``dkg`` and the state's part of ``dG_C`` are made where
  ``dS`` is carried through it and turned into the cotangents of ``q, k, v,
  G, beta`` at once, as values (no scratch carries them from one loop to
  another); ``dq``, ``dk`` are summed over the value heads of the key head
  before they are written.

What repeats — a row's preparation, a chunk of the walk, of the rebuild, of the
way back — is written once as the body of :func:`_unrolled`: traced once and
lowered as many times as it runs, with its index a constant of every copy, so
a short row's 2, 3 or 6 chunks take the same form as a grid step's eight.

VMEM a grid step, bf16 inputs at ``dk = dv = 128``, two value heads a key
head, two rows: the pipelined blocks 3.9 MB forward, 6.0 MB backward (two
buffers each); scratch 2.9 / 7.1 MB (a (rows, 64) array takes its tiles' 128
lanes; every chunk's state of both rows is 2.1 MB of it). With what the long
blocks spill the compiler scopes 12.3 MB forward and 21.5 MB backward (the
least ``vmem_limit_bytes`` each compiles at for a v5e; ``kda_*`` 9.2 / 17.0),
past the 16 MiB a kernel may scope unasked: ``VMEM_LIMIT`` asks for 32 MiB of
the chip's 128.

Matmul operands are in ``v``'s dtype (bf16 on the MXU, float32 at ``HIGHEST``
for float32 inputs); decays, ``beta``, the triangular system and its inverse,
the state and every accumulator are float32.

Layout: ``q, k`` (b, T, hk dk) and ``v, o`` (b, T, hv dv) as the projections
leave them, one head a lane block picked by the index map; ``G``, ``beta``
(b, hv, n, C) float32, a chunk a row; ``G_C`` (the chunk's whole log decay)
rides as (b, hv, n, dv), the scalar repeated over the lanes, and its
gradient comes back as lane-partial sums in the same shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
CHUNKS = 8          # chunks a grid step
BASE = 32           # rows of a diagonal block inverted by substitution
VMEM_LIMIT = 32 * 2 ** 20   # of both rules' kernels: the most one scopes is 21.5 MB (above)
EPS = 1e-6          # of the L2 normalisation (ops.gated_delta_rule.l2_normalize takes it from here)

NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_F32 = jnp.float32


def _mm(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=_F32)


def _precision(dtype):
    """float32 operands take float32 passes; bf16 operands one pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _masks(C):
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return row, col


def _column(x_row, eye):
    """(1, C) along the lanes -> (C, 1) along the sublanes."""
    return jnp.sum(jnp.where(eye, x_row, 0.0), axis=1, keepdims=True)


def _row(x_col, eye):
    """(C, 1) -> (1, C)."""
    return jnp.sum(jnp.where(eye, x_col, 0.0), axis=0, keepdims=True)


def _inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular float32 ``a`` (N, C, C), N
    matrices at once (one traced program for all of them). The diagonal
    blocks of ``BASE`` rows by forward substitution, all blocks at once:
    ``(I + a) = L_0 L_1 ...`` with ``L_r = I + a[:, r] e_r^T``, so the inverse
    is ``I`` after the rank-one updates ``x -= a[:, r] x[r, :]`` in order —
    the substitution's own sums, column after column, on the rows below ``r``
    only (from the eight-row register that holds row ``r + 1``). Then pairs of
    blocks merge, ``[[P, 0], [Q, R]]^-1 = [[P^-1, 0], [-R^-1 Q P^-1, R^-1]]``,
    two float32 (``HIGHEST``) matmuls a doubling whatever the operands' dtype."""
    N, C, _ = a.shape
    base = min(BASE, C)
    blocks = C // base

    def within(rows):
        """The column's place in its own diagonal block, per block (blocks,
        rows, C): negative or past ``base`` outside the block. (Built at every
        height used: Mosaic cannot slice an iota.)"""
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, C), 1)
        return jnp.stack([col - blk * base for blk in range(blocks)])

    a = a.reshape(N, blocks, base, C)
    place = within(base)
    ad = jnp.where((place >= 0) & (place < base), a, 0.0)
    x = jnp.broadcast_to(jnp.where(
        place == jax.lax.broadcasted_iota(jnp.int32, (base, C), 0), 1.0, 0.0).astype(_F32), a.shape)
    for r in range(base - 1):
        lo = (r + 1) // 8 * 8
        a_r = jnp.sum(jnp.where(within(base - lo) == r, ad[:, :, lo:], 0.0), axis=3, keepdims=True)
        below = x[:, :, lo:] - a_r * x[:, :, r:r + 1]
        x = jnp.concatenate([x[:, :, :lo], below], axis=2) if lo else below
    a, x = a.reshape(N, C, C), x.reshape(N, C, C)
    row, col = _masks(C)
    apart = row ^ col                        # in [s, 2s) and below the diagonal: the blocks Q
    mm = functools.partial(jnp.einsum, "nij,njk->nik", precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=_F32)
    s = base
    while s < C:
        q = jnp.where((apart >= s) & (apart < 2 * s), a, 0.0)
        x = x - mm(mm(x, q), x)
        s *= 2
    return x


def _normalized(x, scale):
    """L2-normalised rows times ``scale`` (float32) and the factor applied."""
    x32 = x.astype(_F32)
    r = jax.lax.rsqrt(jnp.sum(x32 * x32, axis=1, keepdims=True) + EPS)
    return x32 * (r * scale), r


def _normalized_bwd(y, r, dy, scale):
    """Cotangent of ``x`` for ``y = x r scale``, ``r = rsqrt(|x|^2 + eps)``."""
    return r * (scale * dy - y * (jnp.sum(y * dy, axis=1, keepdims=True) / scale))


def _key_head(q, k, dt):
    """What the value heads of one key head share: normalised q, k (float32)
    and the scores ``kk``, ``qk`` (C, C) of their casts to ``dt``."""
    C, dk = q.shape
    qn, _ = _normalized(q, dk ** -0.5)
    kn, _ = _normalized(k, 1.0)
    kb = kn.astype(dt)
    scores = _mm(jnp.concatenate([kb, qn.astype(dt)], axis=0), kb, NT, _precision(dt))
    return qn, kn, scores[:C], scores[C:]


def _value_head(kn, qn, kk, qk, v, g_row, b_row, gl_row, row, col, dt):
    """The chunk's operands for one value head but ``T``'s products:
    everything elementwise, float32."""
    eye = row == col
    g_col, b_col = _column(g_row, eye), _column(b_row, eye)
    decay = jnp.exp(jnp.where(row >= col, g_col - g_row, -jnp.inf))
    a = jnp.where(row > col, b_col * kk * decay, 0.0)
    p = qk * decay
    eg = jnp.exp(g_col)
    be = b_col * eg
    tail = jnp.exp(jnp.broadcast_to(gl_row, (g_col.shape[0], gl_row.shape[1]))[:, :1] - g_col)
    return dict(b_col=b_col, decay=decay, a=a, p=p, eg=eg, be=be, tail=tail,
                bk=be * kn, bv=b_col * v.astype(_F32), qg=qn * eg, kg=kn * tail)


def _unrolled(n, body, carry, unroll):
    """``carry = body(i, carry)`` for ``i`` in ``range(n)``: traced ONCE and,
    with ``unroll`` (the compiled kernels), lowered ``n`` times into the one
    basic block — unrolled in full a loop is no loop on the chip, the index a
    constant of every copy, and tracing a program pays the body's Python
    once, not once a chunk and a row. Interpreted (the tests, on the CPU) the
    copies buy nothing and XLA's compiler pays for each: there it stays a
    loop."""
    return jax.lax.fori_loop(0, n, body, carry, unroll=unroll)


def _rows(c, C):
    """The rows of chunk ``c``, a Python int or a loop's index."""
    return pl.ds(c * C if isinstance(c, int) else pl.multiple_of(c * C, C), C)


def _prepare(r, chunks, refs, scr, *, C, G, dv, keep):
    """The operands of every value head of the key head in all ``chunks``
    chunks of row ``r`` of the grid step, written to the scratch. Nothing here waits for
    the state, so the chunks are prepared side by side: one basic block,
    their triangular systems inverted as one batch. ``keep``: also what the
    backward reads again — ``T`` and, float32, the key head's scores."""
    q_ref, k_ref, v_ref, g_ref, b_ref, gl_ref = refs
    dt = v_ref.dtype
    pr = _precision(dt)
    row, col = _masks(C)
    heads = []
    for c in range(chunks):
        rows = _rows(c, C)
        one = pl.ds(c, 1)
        qn, kn, kk, qk = _key_head(q_ref[r, rows, :], k_ref[r, rows, :], dt)
        if keep:
            scr["kk"][r, rows, :], scr["qk"][r, rows, :] = kk, qk
        heads += [(h, rows, _value_head(kn, qn, kk, qk, v_ref[r, rows, h * dv:(h + 1) * dv],
                                        g_ref[r, h, one, :], b_ref[r, h, one, :],
                                        gl_ref[r, h, one, :], row, col, dt)) for h in range(G)]
    inverses = _inverse(jnp.stack([x["a"] for _, _, x in heads])).astype(dt)
    for (h, rows, x), t in zip(heads, inverses):
        scr["w"][r, h, rows, :] = _mm(t, x["bk"].astype(dt), NN, pr).astype(dt)
        scr["u"][r, h, rows, :] = _mm(t, x["bv"].astype(dt), NN, pr).astype(dt)
        scr["qg"][r, h, rows, :] = x["qg"].astype(dt)
        scr["kg"][r, h, rows, :] = x["kg"].astype(dt)
        scr["p"][r, h, rows, :] = x["p"].astype(dt)
        if keep:
            scr["t"][r, h, rows, :] = t


def _recur(state, scr, r, h, rows, gam_row, dt):
    """One chunk of the recurrence on prepared operands: (new state, v', o),
    all float32."""
    pr = _precision(dt)
    s = state.astype(dt)
    v_new = scr["u"][r, h, rows, :].astype(_F32) - _mm(scr["w"][r, h, rows, :], s, NN, pr)
    v_lo = v_new.astype(dt)
    o = _mm(scr["qg"][r, h, rows, :], s, NN, pr) + _mm(scr["p"][r, h, rows, :], v_lo, NN, pr)
    state = state * gam_row + _mm(scr["kg"][r, h, rows, :], v_lo, TN, pr)
    return state, v_new, o


_OPERANDS = ("w", "u", "qg", "kg", "p")
_KEPT = ("t", "kk", "qk")   # what the backward keeps of its preparation beside the operands


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, gl_ref, o_ref, s0_ref, s_scr, *operands,
                chunks, C, G, dv, unroll):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    s0_ref[...] = s_scr[...]
    dt = v_ref.dtype
    scr = dict(zip(_OPERANDS, operands))
    refs = (q_ref, k_ref, v_ref, g_ref, b_ref, gl_ref)

    R = q_ref.shape[0]
    _unrolled(R, lambda r, _: _prepare(r, chunks, refs, scr, C=C, G=G, dv=dv, keep=False), None,
              unroll)

    def walk(c, states):                   # one chunk of every row and head, the rows side by side
        rows, states = _rows(c, C), [list(row) for row in states]
        for r in range(R):
            for h in range(G):
                states[r][h], _, o = _recur(states[r][h], scr, r, h, rows,
                                            jnp.exp(gl_ref[r, h, pl.ds(c, 1), :]), dt)
                o_ref[r, rows, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
        return states

    states = _unrolled(chunks, walk, [[s_scr[r, h] for h in range(G)] for r in range(R)], unroll)
    for r in range(R):
        for h in range(G):
            s_scr[r, h] = states[r][h]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, gl_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dgl_ref,
                ds_scr, states_scr, vnew_scr, *operands, chunks, C, G, dv, unroll):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    dt = v_ref.dtype
    pr = _precision(dt)
    dk_ = q_ref.shape[-1]
    scr = dict(zip(_OPERANDS + _KEPT, operands))
    refs = (q_ref, k_ref, v_ref, g_ref, b_ref, gl_ref)
    lo = lambda z: z.astype(dt)  # noqa: E731

    R = q_ref.shape[0]
    _unrolled(R, lambda r, _: _prepare(r, chunks, refs, scr, C=C, G=G, dv=dv, keep=True), None,
              unroll)

    def rebuild(c, states):                # the block's states and v', in order, in the same block
        rows, states = _rows(c, C), [list(row) for row in states]
        for r in range(R):
            for h in range(G):
                states_scr[r, c, h] = states[r][h]
                states[r][h], v_new, _ = _recur(states[r][h], scr, r, h, rows,
                                                jnp.exp(gl_ref[r, h, pl.ds(c, 1), :]), dt)
                vnew_scr[r, h, rows, :] = v_new.astype(dt)
        return states

    _unrolled(chunks, rebuild, [[s0_ref[r, h] for h in range(G)] for r in range(R)], unroll)

    def carry_back(r, c, ds):              # dS through chunk c: (dS before it, [(du, dkg, dG_C's)])
        rows = _rows(c, C)
        one = pl.ds(c, 1)
        before, made = [], []
        for h in range(G):
            ds_lo = lo(ds[h])
            do = do_ref[r, rows, h * dv:(h + 1) * dv]
            gam = jnp.exp(gl_ref[r, h, one, :])
            du = lo(_mm(scr["p"][r, h, rows, :], do, TN, pr)
                    + _mm(scr["kg"][r, h, rows, :], ds_lo, NN, pr))               # = dv'
            dkg = _mm(vnew_scr[r, h, rows, :], ds_lo, NT, pr)
            dgc = jnp.sum(states_scr[r, c, h] * ds[h], axis=0, keepdims=True) * gam
            before.append(ds[h] * gam + _mm(scr["qg"][r, h, rows, :], do, TN, pr)
                          - _mm(scr["w"][r, h, rows, :], du, TN, pr))
            made.append((du, dkg, dgc))
        return before, made

    def inputs(r, c, made):                # the operands' cotangents -> q, k, v, G, beta's
        rows = _rows(c, C)
        one = pl.ds(c, 1)
        row, col = _masks(C)
        eye = row == col
        lanes = lambda z: jnp.sum(z, axis=1, keepdims=True)  # noqa: E731
        kk, qk = scr["kk"][r, rows, :], scr["qk"][r, rows, :]
        qn, rq = _normalized(q_ref[r, rows, :], dk_ ** -0.5)
        kn, rk = _normalized(k_ref[r, rows, :], 1.0)
        qb, kb = lo(qn), lo(kn)
        dqn = jnp.zeros((C, dk_), _F32)
        dkn = jnp.zeros((C, dk_), _F32)
        dkk = jnp.zeros((C, C), _F32)
        dqk = jnp.zeros((C, C), _F32)
        for h in range(G):
            v = v_ref[r, rows, h * dv:(h + 1) * dv]
            x = _value_head(kn, qn, kk, qk, v, g_ref[r, h, one, :], b_ref[r, h, one, :],
                            gl_ref[r, h, one, :], row, col, dt)
            t, v_new = scr["t"][r, h, rows, :], vnew_scr[r, h, rows, :]
            du, dkg, dgc = made[h]
            s = lo(states_scr[r, c, h])
            do = do_ref[r, rows, h * dv:(h + 1) * dv]
            # the recurrence's operands
            dp = jnp.where(row >= col, _mm(do, v_new, NT, pr), 0.0)
            dqg = _mm(do, s, NT, pr)
            dw = lo(-_mm(du, s, NT, pr))
            # w = T bk, u = T bv, T = (I + a)^-1: da = -T^T dT T^T with dT = dw bk^T + du bv^T
            dbk = _mm(t, dw, TN, pr)
            dbv = _mm(t, du, TN, pr)
            da = jnp.where(row > col, -(_mm(lo(dbk), scr["w"][r, h, rows, :], NT, pr)
                                        + _mm(lo(dbv), scr["u"][r, h, rows, :], NT, pr)), 0.0)
            # a = beta kk decay, p = qk decay, decay = e^{G_i - G_j}
            e = da * x["a"] + dp * x["p"]
            dkk = dkk + da * x["b_col"] * x["decay"]
            dqk = dqk + dp * x["decay"]
            tails = lanes(dkg * x["kg"])
            db_col = (lanes(da * kk * x["decay"])
                      + lanes(dbk * (kn * x["eg"]) + dbv * v.astype(_F32)))
            dg_col = lanes(e) + lanes(dbk * x["bk"] + dqg * x["qg"]) - tails
            dg_ref[r, h, one, :] = _row(dg_col, eye) - jnp.sum(e, axis=0, keepdims=True)
            db_ref[r, h, one, :] = _row(db_col, eye)
            # G_C's: the state's decay lane by lane (``carry_back``); the keys' tails add
            # their whole sum, a 1 / dv of it in every lane (the caller sums the lanes)
            dgl_ref[r, h, one, :] = dgc + jnp.sum(jnp.broadcast_to(tails, (C, dv)), axis=0,
                                               keepdims=True) * (1.0 / dv)
            dkn = dkn + dbk * x["be"] + dkg * x["tail"]
            dqn = dqn + dqg * x["eg"]
            dv_ref[r, rows, h * dv:(h + 1) * dv] = (dbv * x["b_col"]).astype(dv_ref.dtype)
        # kk = kn kn^T, qk = qn kn^T: once a key head, summed over its value heads
        dkk_lo, dqk_lo = lo(dkk), lo(dqk)
        dqn = dqn + _mm(dqk_lo, kb, NN, pr)
        dkn = dkn + _mm(dqk_lo, qb, TN, pr) + _mm(dkk_lo, kb, NN, pr) + _mm(dkk_lo, kb, TN, pr)
        dq_ref[r, rows, :] = _normalized_bwd(qn, rq, dqn, dk_ ** -0.5).astype(dq_ref.dtype)
        dk_ref[r, rows, :] = _normalized_bwd(kn, rk, dkn, 1.0).astype(dk_ref.dtype)

    def back(i, ds):                       # dS through a chunk of every row, last to first, and
        c, ds = chunks - 1 - i, list(ds)   # the chunk's cotangents at once: the rows side by side
        for r in range(R):
            ds[r], made = carry_back(r, c, ds[r])
            inputs(r, c, made)
        return ds

    ds = _unrolled(chunks, back, [[ds_scr[r, h] for h in range(G)] for r in range(R)], unroll)
    for r in range(R):
        for h in range(G):
            ds_scr[r, h] = ds[r][h]


def _operand_scratch(R, G, rows, C, dk, dv, dtype, keep):
    """w, u, qg, kg, p of a block's chunks in the operands' dtype and, for the
    backward, T in it and the key head's kk, qk float32."""
    widths = (dk, dv, dk, dk, C) + ((C,) if keep else ())
    kept = [pltpu.VMEM((R, rows, C), _F32)] * 2 if keep else []
    return [pltpu.VMEM((R, G, rows, width), dtype) for width in widths] + kept


def _specs(R, chunks, C, G, dk, dv, order):
    """Block specs over the grid (``R`` batch rows, key head, block of chunks)."""
    qk = pl.BlockSpec((R, chunks * C, dk), lambda b, j, t: (b, order(t), j))
    v = pl.BlockSpec((R, chunks * C, G * dv), lambda b, j, t: (b, order(t), j))
    per_chunk = lambda width: pl.BlockSpec(  # noqa: E731
        (R, G, chunks, width), lambda b, j, t: (b, j, order(t), 0))
    s0 = pl.BlockSpec((R, G, None, dk, dv), lambda b, j, t: (b, j, order(t), 0, 0))
    return qk, v, per_chunk, s0


def _plan(q, v, g, heads):
    """(b, batch rows a grid step — two, or one of an odd batch —, hv, C, dk,
    dv, value heads a key head, chunks a grid step — all of a short row's, the
    caller pads a longer one to whole steps —, steps)."""
    hv, n, C = g.shape[1:]
    b, chunks = q.shape[0], min(n, CHUNKS)
    return (b, 2 - b % 2, hv, C, q.shape[-1] // heads, v.shape[-1] // hv, hv // heads,
            chunks, n // chunks)


def gdn_fwd(q, k, v, g, beta, gl, *, heads, interpret=False):
    """``o`` (b, T, hv dv) and the states every block of chunks started from
    (b, hv, blocks, dk, dv) float32. ``heads`` is the number of key heads."""
    b, R, hv, C, dk, dv, G, chunks, nt = _plan(q, v, g, heads)
    qk, vs, per_chunk, s0 = _specs(R, chunks, C, G, dk, dv, lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=chunks, C=C, G=G, dv=dv, unroll=not interpret),
        name="gdn_fwd",
        grid=(b // R, heads, nt),
        in_specs=[qk, qk, vs, per_chunk(C), per_chunk(C), per_chunk(dv)],
        out_specs=[vs, s0],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, hv, nt, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((R, G, dk, dv), _F32)]
        + _operand_scratch(R, G, chunks * C, C, dk, dv, v.dtype, keep=False),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, g, beta, gl)


def gdn_bwd(q, k, v, g, beta, gl, s0, do, *, heads, interpret=False):
    """Cotangents of (q, k, v, g, beta, gl) in their shapes and dtypes; the
    whole-chunk decay's as lane-partial sums."""
    b, R, hv, C, dk, dv, G, chunks, nt = _plan(q, v, g, heads)
    qk, vs, per_chunk, s0_spec = _specs(R, chunks, C, G, dk, dv, lambda t: nt - 1 - t)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=chunks, C=C, G=G, dv=dv, unroll=not interpret),
        name="gdn_bwd",
        grid=(b // R, heads, nt),
        in_specs=[qk, qk, vs, per_chunk(C), per_chunk(C), per_chunk(dv), s0_spec, vs],
        out_specs=[qk, qk, vs, per_chunk(C), per_chunk(C), per_chunk(dv)],
        out_shape=[like(q), like(k), like(v), like(g), like(beta), like(gl)],
        scratch_shapes=[pltpu.VMEM((R, G, dk, dv), _F32),              # dS
                        pltpu.VMEM((R, chunks, G, dk, dv), _F32),      # every chunk's state
                        pltpu.VMEM((R, G, chunks * C, dv), v.dtype)]   # v'
        + _operand_scratch(R, G, chunks * C, C, dk, dv, v.dtype, keep=True),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, g, beta, gl, s0, do)
