"""Pallas kernels of the chunked delta rule whose decay is a vector a key
channel (KDA): ``ops/pallas/gated_delta_rule.py``'s scheme — a chunk's
operands built in VMEM, the chunks walked with the state there too, only
``q, k, v, g, beta`` and ``o`` across HBM — with the one thing a per-channel
decay changes: the in-chunk scores no longer factor into a matmul and a
(C, C) mask.

Per chunk of ``C`` tokens and head, with ``g`` (C, dk) the per-step log decay
of every key channel, ``G = cumsum(g)`` inside the chunk (here, by a
triangular matmul), ``qn``/``kn`` the L2-normalised queries and keys and
float32 state ``S (dk, dv)``:

    kk_ij = sum_c kn_ic kn_jc e^{G_ic - G_jc},  qk_ij likewise   (i >= j)
    a = tril(beta kk, -1);   T = (I + a)^-1;   p = tril(qk)
    w = T (beta e^G kn);   u = T (beta v);   qg = qn e^G;   kg = kn e^{G_C - G}
    v' = u - w S;   o = qg S + p v';   S <- Diag(e^{G_C}) S + kg^T v'

Only differences ``G_i - G_j``, ``i >= j``, occur, all <= 0. The scores are
made a sub-chunk of ``SUB`` rows at a time (:func:`_scores`): with ``r`` the
first row of the sub-chunk, rows ``i`` of it against every column ``j`` up to
its own last are ONE float32 matmul of ``kn e^{G - G_r}`` with ``kn e^{G_r -
G}``. For ``j`` before the sub-chunk both factors are <= 1; for ``j`` inside
it the second is ``e^{-(G_j - G_r)}``, at most ``e^{(SUB - 1) |g|_max}`` —
``e^75`` at ``LOG_DECAY_MIN`` = -5 a step, inside float32 (``e^88``), which is
the bound the kernels need of ``g`` and why a model that runs them bounds its
gate. Those factors are float32 operands at ``HIGHEST``, never bf16.

The blocked inverse (:func:`gated_delta_rule._inverse`), the operands in the
inputs' dtype, the recurrence and the states a block of chunks started from
are ``gdn_fwd`` / ``gdn_bwd``'s; the state is held transposed, ``S^T (dv,
dk)``, so that its per-channel decay ``e^{G_C}`` is a row over the lanes. The
order of a grid step (``CHUNKS`` chunks of one head and TWO batch rows — one
of an odd batch: ``_plan``) is this file's own, and since PR 48 the scalar
rule's too. Mosaic schedules a basic block at a time and, inside one, packs
what stands close together in the text: two independent streams one after the
other run one after the other, a loop's every trip pays its fill and drain,
and a grid step costs more on the chip than its bundles say (two rows a step
took a fifth off ``kda_fwd`` where the schedule promised 4 %). So a grid step
is a few long blocks, nothing is made twice, and the rows' chains stand side
by side (``for c: for r:``):

- ``kda_fwd``: ONE block. :func:`_prepare`, once a row, makes the operands of all
  the row's chunks side by side — per chunk the cumulated decay, the sub-chunks'
  factors, the scores; then all their triangular systems inverted as one batch
  (the substitution's 31 dependent steps carry eight systems where they carried
  four), then ``T``'s products — and the walk over the chunks follows in the same
  block, a chunk of every row at a time.
- ``kda_bwd``: the same preparation, which also KEEPS, in VMEM, what the second
  half reads again: ``T`` and, float32, each chunk's ``G`` (32 KB) and ``kk``
  (16 KB); the rebuild of the block's states and ``v'`` follows in the same
  block. Then the chunks last to first, still in that block: a chunk's ``du``,
  ``dkg`` and ``dG_C`` are made where ``dS`` is carried through it and turned
  into the cotangents of ``q, k, v, g, beta`` at once, as values (no scratch
  carries them from one loop to another); the sub-chunks' factors come again
  from the kept ``G`` (exponentials, no product) and ``kk`` is read for
  ``dbeta``, so no score and no cumulated decay is made a second time. ``dS``'s
  short chain stands between the chunks' independent products, which is where
  the scheduler covers it.

What repeats is written once as the body of ``gated_delta_rule._unrolled``
(traced once, lowered as many times as it runs), so a short row's 2, 3 or 6
chunks take the same form as a grid step's eight.

VMEM a grid step, bf16 inputs at ``dk = dv = 128``, two rows: the pipelined
blocks 3.4 MB forward, 6.0 MB backward (two buffers each); scratch 1.6 / 4.0 MB
(a (rows, 64) array takes its tiles' 128 lanes: the kept ``G`` and ``kk`` 1.0 MB,
where ``du``, ``dkg``, ``dG_C`` took 0.4 MB a row). The long blocks spill more
than the loops did: the compiler scopes 9.2 MB forward and 17.0 MB backward in
all (the least ``vmem_limit_bytes`` each compiles at for a v5e; 6.2 / 8.7 MB
at one row a step, 4.1 / 6.0 with the loops), past the 16 MiB a kernel may
scope unasked: ``VMEM_LIMIT`` (``gated_delta_rule.py``'s, whose backward scopes
21.5 MB) asks for 32 MiB of the chip's 128.

Layout: ``q, k, v, o`` (b, T, h d) as the projections leave them, ``g`` (b, T,
h dk) float32 in the same layout, one head a lane block picked by the index
map (as many key as value heads); ``beta`` (b, h, n, C) float32, a chunk a
row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas.gated_delta_rule import (_OPERANDS, CHUNKS, NN, NT, TN, VMEM_LIMIT,
                                                  _column, _inverse, _masks, _mm, _normalized,
                                                  _normalized_bwd, _precision, _row, _rows,
                                                  _unrolled)

SUB = 16                  # rows of a sub-chunk of the scores
_KEPT = ("t", "G", "kk")  # what the backward keeps of its preparation beside the operands
LOG_DECAY_MIN = -5.0      # the smallest per-step log decay the kernels take: (SUB - 1) * 5 < 88
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _cumulated(g, dims=NN):
    """``G = cumsum(g)`` over the chunk's rows, float32: a lower-triangular
    matmul of ones (exact factors, float32 passes). ``dims=TN``: its
    transpose, the sums from each row on (the cumsum's cotangent)."""
    row, col = _masks(g.shape[0])
    return _mm(jnp.where(row >= col, 1.0, 0.0).astype(_F32), g, dims, _HI)


def _sub_chunks(kn, qn, G):
    """The factors of every sub-chunk's scores: ``[(ea (SUB, dk), fa (C, dk),
    ra (2 SUB, dk), ca (C, dk))]`` with ``ea = e^{G_i - G_r}`` over the
    sub-chunk's rows, ``fa = e^{G_r - G_j}`` over the columns up to its last
    (0 after), ``ra = [kn ea; qn ea]`` and ``ca = kn fa``."""
    C = kn.shape[0]
    j = jax.lax.broadcasted_iota(jnp.int32, G.shape, 0)
    out = []
    for a in range(C // SUB):
        lo, hi = a * SUB, (a + 1) * SUB
        gr = G[lo:lo + 1]
        ea = jnp.exp(G[lo:hi] - gr)
        fa = jnp.exp(jnp.where(j < hi, gr - G, -jnp.inf))
        out.append((ea, fa, jnp.concatenate([kn[lo:hi] * ea, qn[lo:hi] * ea], axis=0), kn * fa))
    return out


def _scores(subs):
    """``kk, qk`` (C, C): exact at ``i >= j``; above the diagonal finite and
    never read."""
    blocks = [_mm(ra, ca, NT, _HI) for _, _, ra, ca in subs]
    return (jnp.concatenate([s[:SUB] for s in blocks], axis=0),
            jnp.concatenate([s[SUB:] for s in blocks], axis=0))


def _elementwise(q, k, v, G, b_row, row, col):
    """One chunk's operands that no matmul makes, float32, from its cumulated
    log decay ``G``: the sub-chunks' factors among them."""
    C, dk = q.shape
    qn, rq = _normalized(q, dk ** -0.5)
    kn, rk = _normalized(k, 1.0)
    b_col = _column(b_row, row == col)
    eg = jnp.exp(G)
    tail = jnp.exp(G[C - 1:C] - G)
    return dict(qn=qn, kn=kn, rq=rq, rk=rk, subs=_sub_chunks(kn, qn, G), b_col=b_col, eg=eg,
                tail=tail, bk=b_col * eg * kn, bv=b_col * v.astype(_F32), qg=qn * eg,
                kg=kn * tail, gam=eg[C - 1:C])


def _prepare(r, chunks, refs, scr, *, C, keep):
    """The operands of all ``chunks`` chunks of row ``r`` of the grid step,
    written to the scratch: side by side in one basic block, their triangular
    systems inverted as one batch. ``keep``: also what the backward reads again —
    ``T`` and, float32, ``G`` and ``kk``."""
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    dt = v_ref.dtype
    pr = _precision(dt)
    row, col = _masks(C)
    made = []
    for c in range(chunks):
        rows = _rows(c, C)
        G = _cumulated(g_ref[r, rows, :])
        x = _elementwise(q_ref[r, rows, :], k_ref[r, rows, :], v_ref[r, rows, :], G,
                         b_ref[r, pl.ds(c, 1), :], row, col)
        kk, qk = _scores(x["subs"])
        made.append((c, rows, dict(x, G=G, kk=kk, a=jnp.where(row > col, x["b_col"] * kk, 0.0),
                                   p=jnp.where(row >= col, qk, 0.0))))
    inverses = _inverse(jnp.stack([x["a"] for _, _, x in made])).astype(dt)
    for (c, rows, x), t in zip(made, inverses):
        scr["w"][r, rows, :] = _mm(t, x["bk"].astype(dt), NN, pr).astype(dt)
        scr["u"][r, rows, :] = _mm(t, x["bv"].astype(dt), NN, pr).astype(dt)
        scr["qg"][r, rows, :] = x["qg"].astype(dt)
        scr["kg"][r, rows, :] = x["kg"].astype(dt)
        scr["p"][r, rows, :] = x["p"].astype(dt)
        scr["gam"][r, pl.ds(c, 1), :] = x["gam"]
        if keep:
            scr["t"][r, rows, :] = t
            scr["G"][r, rows, :] = x["G"]
            scr["kk"][r, rows, :] = x["kk"]


def _recur(st, scr, r, rows, gam_row, dt):
    """One chunk of row ``r``'s recurrence on prepared operands, the state
    transposed (dv, dk): (new state, v', o), all float32."""
    pr = _precision(dt)
    s = st.astype(dt)
    v_new = scr["u"][r, rows, :].astype(_F32) - _mm(scr["w"][r, rows, :], s, NT, pr)
    v_lo = v_new.astype(dt)
    o = _mm(scr["qg"][r, rows, :], s, NT, pr) + _mm(scr["p"][r, rows, :], v_lo, NN, pr)
    st = st * gam_row + _mm(v_lo, scr["kg"][r, rows, :], TN, pr)
    return st, v_new, o


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s0_ref, s_scr, gam_scr, *operands,
                chunks, C, unroll):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    s0_ref[...] = s_scr[...]
    dt = v_ref.dtype
    R = q_ref.shape[0]
    scr = dict(zip(_OPERANDS, operands), gam=gam_scr)
    refs = (q_ref, k_ref, v_ref, g_ref, b_ref)
    _unrolled(R, lambda r, _: _prepare(r, chunks, refs, scr, C=C, keep=False), None, unroll)

    def walk(c, st):                       # one chunk of every row, the rows side by side
        rows, st = _rows(c, C), list(st)
        for r in range(R):
            st[r], _, o = _recur(st[r], scr, r, rows, gam_scr[r, pl.ds(c, 1), :], dt)
            o_ref[r, rows, :] = o.astype(o_ref.dtype)
        return st

    st = _unrolled(chunks, walk, [s_scr[r] for r in range(R)], unroll)
    for r in range(R):
        s_scr[r] = st[r]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                ds_scr, states_scr, vnew_scr, gam_scr, *operands, chunks, C, unroll):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    dt = v_ref.dtype
    pr = _precision(dt)
    dk_ = q_ref.shape[-1]
    scr = dict(zip(_OPERANDS + _KEPT, operands), gam=gam_scr)
    lo = lambda z: z.astype(dt)  # noqa: E731

    R = q_ref.shape[0]
    refs = (q_ref, k_ref, v_ref, g_ref, b_ref)
    _unrolled(R, lambda r, _: _prepare(r, chunks, refs, scr, C=C, keep=True), None, unroll)

    def rebuild(c, st):                    # the block's states and v', in order, in the same block
        rows, st = _rows(c, C), list(st)
        for r in range(R):
            states_scr[r, c] = st[r]
            st[r], v_new, _ = _recur(st[r], scr, r, rows, gam_scr[r, pl.ds(c, 1), :], dt)
            vnew_scr[r, rows, :] = v_new.astype(dt)
        return st

    _unrolled(chunks, rebuild, [s0_ref[r] for r in range(R)], unroll)

    def carry_back(r, c, ds):              # dS through chunk c: (dS before it, du, dkg, dG_C's)
        rows = _rows(c, C)
        ds_lo = lo(ds)
        do = do_ref[r, rows, :]
        gam = gam_scr[r, pl.ds(c, 1), :]
        du = lo(_mm(scr["p"][r, rows, :], do, TN, pr)
                + _mm(scr["kg"][r, rows, :], ds_lo, NT, pr))                       # = dv'
        dkg = _mm(vnew_scr[r, rows, :], ds_lo, NN, pr)
        dgc = jnp.sum(states_scr[r, c] * ds, axis=0, keepdims=True) * gam
        ds = (ds * gam + _mm(do, scr["qg"][r, rows, :], TN, pr)
              - _mm(du, scr["w"][r, rows, :], TN, pr))
        return ds, du, dkg, dgc

    def inputs(r, c, du, dkg, dgc):        # the operands' cotangents -> q, k, v, g, beta's
        rows = _rows(c, C)
        one = pl.ds(c, 1)
        row, col = _masks(C)
        eye = row == col
        lanes = lambda z: jnp.sum(z, axis=1, keepdims=True)  # noqa: E731
        v = v_ref[r, rows, :]
        x = _elementwise(q_ref[r, rows, :], k_ref[r, rows, :], v, scr["G"][r, rows, :],
                         b_ref[r, one, :], row, col)
        qn, kn = x["qn"], x["kn"]
        t, v_new = scr["t"][r, rows, :], vnew_scr[r, rows, :]
        s = lo(states_scr[r, c])
        do = do_ref[r, rows, :]
        # the recurrence's operands
        dp = jnp.where(row >= col, _mm(do, v_new, NT, pr), 0.0)
        dqg = _mm(do, s, NN, pr)
        dw = lo(-_mm(du, s, NN, pr))
        # w = T bk, u = T bv, T = (I + a)^-1: da = -T^T dT T^T with dT = dw bk^T + du bv^T
        dbk = _mm(t, dw, TN, pr)
        dbv = _mm(t, du, TN, pr)
        da = jnp.where(row > col, -(_mm(lo(dbk), scr["w"][r, rows, :], NT, pr)
                                    + _mm(lo(dbv), scr["u"][r, rows, :], NT, pr)), 0.0)
        # a = beta kk, p = qk; kk, qk a sub-chunk at a time = ra ca^T: the factors'
        # cotangents go to kn, qn and (each factor its own exponent's) to G
        dkk = da * x["b_col"]
        row_k, row_q, row_g = [], [], []
        col_k = jnp.zeros((C, dk_), _F32)
        col_g = jnp.zeros((C, dk_), _F32)
        for a, (ea, fa, ra, ca) in enumerate(x["subs"]):
            ds_a = jnp.concatenate([dkk[a * SUB:(a + 1) * SUB], dp[a * SUB:(a + 1) * SUB]], axis=0)
            dra = _mm(ds_a, ca, NN, _HI)
            dca = _mm(ds_a, ra, TN, _HI)
            row_k.append(dra[:SUB] * ea)
            row_q.append(dra[SUB:] * ea)
            row_g.append(dra[:SUB] * ra[:SUB] + dra[SUB:] * ra[SUB:])
            col_k = col_k + dca * fa
            col_g = col_g + dca * ca
        tails = dkg * x["kg"]
        dG = (jnp.concatenate(row_g, axis=0) - col_g + dbk * x["bk"] + dqg * x["qg"] - tails)
        # G_C's: the state's decay (carry_back) and the keys' tails, on the chunk's last row
        last = jax.lax.broadcasted_iota(jnp.int32, dG.shape, 0) == C - 1
        dG = dG + jnp.where(last, dgc + jnp.sum(tails, axis=0, keepdims=True), 0.0)
        dg_ref[r, rows, :] = _cumulated(dG, TN)
        db_col = (lanes(da * scr["kk"][r, rows, :])
                  + lanes(dbk * (kn * x["eg"]) + dbv * v.astype(_F32)))
        db_ref[r, one, :] = _row(db_col, eye)
        dkn = (jnp.concatenate(row_k, axis=0) + col_k + dbk * (x["b_col"] * x["eg"])
               + dkg * x["tail"])
        dqn = jnp.concatenate(row_q, axis=0) + dqg * x["eg"]
        dv_ref[r, rows, :] = (dbv * x["b_col"]).astype(dv_ref.dtype)
        dq_ref[r, rows, :] = _normalized_bwd(qn, x["rq"], dqn, dk_ ** -0.5).astype(dq_ref.dtype)
        dk_ref[r, rows, :] = _normalized_bwd(kn, x["rk"], dkn, 1.0).astype(dk_ref.dtype)

    def back(i, ds):                       # dS through a chunk of every row, last to first, and
        c, ds = chunks - 1 - i, list(ds)   # the chunk's cotangents at once: the rows side by side
        for r in range(R):
            ds[r], du, dkg, dgc = carry_back(r, c, ds[r])
            inputs(r, c, du, dkg, dgc)
        return ds

    ds = _unrolled(chunks, back, [ds_scr[r] for r in range(R)], unroll)
    for r in range(R):
        ds_scr[r] = ds[r]


def _operand_scratch(R, rows, C, dk, dv, dtype, keep):
    """w, u, qg, kg, p of a block's chunks in the operands' dtype and, for the
    backward, T in it and G, kk float32."""
    widths = (dk, dv, dk, dk, C) + ((C,) if keep else ())
    kept = [pltpu.VMEM((R, rows, dk), _F32), pltpu.VMEM((R, rows, C), _F32)] if keep else []
    return [pltpu.VMEM((R, rows, width), dtype) for width in widths] + kept


def _specs(R, chunks, C, dk, dv, order):
    """Block specs over the grid (``R`` batch rows, head, block of chunks)."""
    qk = pl.BlockSpec((R, chunks * C, dk), lambda b, j, t: (b, order(t), j))
    v = pl.BlockSpec((R, chunks * C, dv), lambda b, j, t: (b, order(t), j))
    beta = pl.BlockSpec((R, None, chunks, C), lambda b, j, t: (b, j, order(t), 0))
    s0 = pl.BlockSpec((R, None, None, dv, dk), lambda b, j, t: (b, j, order(t), 0, 0))
    return qk, v, beta, s0


def _plan(q, v, beta):
    """(b, batch rows a grid step — two, or one of an odd batch —, heads, C,
    dk, dv, chunks a grid step — all of a short row's, the caller pads a longer
    one to whole steps —, steps)."""
    b, h, n, C = beta.shape
    chunks = min(n, CHUNKS)
    return b, 2 - b % 2, h, C, q.shape[-1] // h, v.shape[-1] // h, chunks, n // chunks


def kda_fwd(q, k, v, g, beta, *, interpret=False):
    """``o`` (b, T, h dv) and the transposed states every block of chunks
    started from (b, h, blocks, dv, dk) float32."""
    b, R, h, C, dk, dv, chunks, nt = _plan(q, v, beta)
    qk, vs, bs, s0 = _specs(R, chunks, C, dk, dv, lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=chunks, C=C, unroll=not interpret),
        name="kda_fwd",
        grid=(b // R, h, nt),
        in_specs=[qk, qk, vs, qk, bs],
        out_specs=[vs, s0],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, h, nt, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((R, dv, dk), _F32), pltpu.VMEM((R, chunks, dk), _F32)]
        + _operand_scratch(R, chunks * C, C, dk, dv, v.dtype, keep=False),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, g, beta)


def kda_bwd(q, k, v, g, beta, s0, do, *, interpret=False):
    """Cotangents of (q, k, v, g, beta) in their shapes and dtypes."""
    b, R, h, C, dk, dv, chunks, nt = _plan(q, v, beta)
    qk, vs, bs, s0_spec = _specs(R, chunks, C, dk, dv, lambda t: nt - 1 - t)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=chunks, C=C, unroll=not interpret),
        name="kda_bwd",
        grid=(b // R, h, nt),
        in_specs=[qk, qk, vs, qk, bs, s0_spec, vs],
        out_specs=[qk, qk, vs, qk, bs],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[pltpu.VMEM((R, dv, dk), _F32),                 # dS^T
                        pltpu.VMEM((R, chunks, dv, dk), _F32),         # every chunk's state
                        pltpu.VMEM((R, chunks * C, dv), v.dtype),      # v'
                        pltpu.VMEM((R, chunks, dk), _F32)]             # e^{G_C}
        + _operand_scratch(R, chunks * C, C, dk, dv, v.dtype, keep=True),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, g, beta, s0, do)
