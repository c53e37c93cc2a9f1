"""Compile every Pallas kernel family on the attached TPU and check each
against its XLA composition at the flagship's shapes (hidden 1024, 8 heads
of 128, seq 1024, vocab 32768, 8 serving slots, 128-row cache blocks).

    python tools/tpu_kernel_smoke.py          # exits non-zero on any failure
    python tools/tpu_kernel_smoke.py gdn moe  # only the families so named
    python tools/tpu_kernel_smoke.py "flash bshd" "flash packed pair" --checkout .scratch/parent

``--checkout DIR`` (any number of them): the flash families at a cell's shape
also run their FORWARD from ``DIR``'s ``ops/pallas/attention.py`` on the same
operands, print its device time beside the tree's and hold ``o`` and ``lse`` to
it bit for bit (max |diff| must read 0.0: a forward that only stops masking
what needs no mask and fetching what it skips changes no result). The delta
rules' families (``gdn``, ``kda``) print their kernel pair ALONE at the cell's
shape, 2 rows a chip, by device ms a call, and run ``DIR``'s
``ops/pallas/gated_delta_rule.py`` / ``kda.py`` the same way: ``o``, the states
the blocks started from and every cotangent bit for bit.

A family is ``(kernel_fn, reference_fn, args)``: both run under ``jax.jit``
on the same operands and every output leaf (forward values AND gradients)
must agree — floats to a relative-to-scale tolerance, integers (sampled
tokens, accept lengths) exactly. ``main()`` returns the names of the families
whose outputs disagree; a kernel Mosaic refuses to compile raises straight
through (no handler here catches it), so no failure can leave exit code 0.
``chip_smoke.py`` imports ``main`` and runs it in its own process — the chip
belongs to one process at a time.

``auto=True`` marks the families ``impl="auto"`` can select on a TPU; the
rest are reachable only through an explicit ``impl="pallas"`` (``auto``
resolves them to XLA on measured grounds, PERF.md).
"""

import dataclasses
import functools
import os
import sys
import time
from typing import Callable, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np

from apex_tpu.ops import (BucketedBias, decode_attention, flash_attention,
                          fused_dense, fused_dense_gelu_dense,
                          fused_layer_norm, fused_rms_norm, fused_sample,
                          fused_verify, fused_verify_tree, mlp,
                          scaled_masked_softmax,
                          scaled_upper_triang_masked_softmax)
from apex_tpu.ops import _backend
from apex_tpu.ops.attention import (bshd_output_projection,
                                    bshd_qkv_projection, fused_qkv_attention)
from apex_tpu.transformer.tensor_parallel import vocab_parallel_cross_entropy

# the flagship's widths (bench.py's on_tpu config); batch is cut to 2 —
# kernels tile over batch·heads rows, so 2 covers the row index maps
B, S, H, D, V = 2, 1024, 8, 128, 32768
HID = H * D
SLOTS, BLOCK = 8, 128          # the serving engine's slot array / page
F32_TOL, BF16_TOL = 2e-3, 3e-2


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    build: Callable[[], Tuple[Callable, Callable, tuple]]
    auto: bool = True
    tol: float = BF16_TOL
    timings: Callable[[], list] = None   # lines of times this family reports beside its check


def _key(i):
    return jr.fold_in(jr.PRNGKey(0), i)


def _fwd_and_grads(fn, argnums):
    """(output, grads wrt ``argnums``) under a fixed random cotangent — one
    family then checks the forward and every backward kernel behind it."""
    def run(*args):
        def loss(*a):
            out = fn(*a)
            cot = jr.normal(_key(99), out.shape, jnp.float32)
            return (out.astype(jnp.float32) * cot).sum(), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=argnums, has_aux=True)(*args)
        return out, grads
    return run


# --- flash attention ----------------------------------------------------------

def _qkv_bshd(h_kv=H):
    q = jr.normal(_key(1), (B, S, H, D), jnp.bfloat16)
    k = jr.normal(_key(2), (B, S, h_kv, D), jnp.bfloat16)
    v = jr.normal(_key(3), (B, S, h_kv, D), jnp.bfloat16)
    return q, k, v


def _kv_lens():
    return jnp.array([S, 300], jnp.int32)  # one full row, one padded


def _flash_family(layout, h_kv=H, varlen=False, dropout=0.0):
    def build():
        q, k, v = _qkv_bshd(h_kv)
        if layout == "bhsd":
            q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        kw = {}
        if varlen:  # per batch (bshd) / per (batch, head) (flat)
            kw["kv_lens"] = (_kv_lens() if layout == "bshd"
                             else jnp.repeat(_kv_lens()[:, None], H, 1))
        if dropout:
            kw.update(dropout_rate=dropout, dropout_seed=jnp.int32(7))

        def make(impl):
            return _fwd_and_grads(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=True, layout=layout, impl=impl, **kw),
                (0, 1, 2))
        return make("pallas"), make("xla"), (q, k, v)
    return build


def _flash_bias_family(layout, bucketed):
    """Materialized (h, s, s) bias + dbias, or the in-kernel bucketed bias
    + the dtable kernel — gradients flow to the bias operand too."""
    def build():
        q, k, v = _qkv_bshd()
        if layout == "bhsd":
            q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        if bucketed:
            operand = jr.normal(_key(4), (32, H), jnp.float32) * 0.5
            wrap = lambda t: BucketedBias(t, bidirectional=False,  # noqa: E731
                                          max_distance=128)
        else:
            operand = jr.normal(_key(4), (H, S, S), jnp.float32) * 0.5
            wrap = lambda a: a  # noqa: E731

        def make(impl):
            return _fwd_and_grads(
                lambda q, k, v, t: flash_attention(
                    q, k, v, causal=True, layout=layout, impl=impl,
                    bias=wrap(t)),
                (0, 1, 2, 3))
        return make("pallas"), make("xla"), (q, k, v, operand)
    return build


def _packed_family(varlen=False, dropout=0.0, biased=False):
    """``fused_qkv_attention`` (the flagship train step's attention block:
    packed projection → flash kernels reading head windows → output
    projection) against separate projections around the XLA attention."""
    def build():
        x = jr.normal(_key(5), (B, S, HID), jnp.bfloat16)
        w_qkv = jr.normal(_key(6), (3 * HID, HID), jnp.bfloat16) * 0.02
        b_qkv = jr.normal(_key(7), (3 * HID,), jnp.bfloat16) * 0.02
        w_out = jr.normal(_key(8), (HID, HID), jnp.bfloat16) * 0.02
        scale = D ** -0.5
        seed = jnp.int32(7) if dropout else None
        lens = _kv_lens() if varlen else None
        args = (x, w_qkv, b_qkv, w_out)
        if biased:  # differentiated too: the packed dbias kernel
            args += (jr.normal(_key(4), (H, S, S), jnp.float32) * 0.5,)

        def kernel(x, w_qkv, b_qkv, w_out, bias=None):
            return fused_qkv_attention(x, w_qkv, b_qkv, w_out, bias, seed,
                                       lens, H, H, D, scale, True, dropout)

        def reference(x, w_qkv, b_qkv, w_out, bias=None):
            q, k, v = bshd_qkv_projection(x, w_qkv, b_qkv, H, H, D)
            ctx = flash_attention(q, k, v, causal=True, layout="bshd",
                                  impl="xla", kv_lens=lens, bias=bias,
                                  dropout_rate=dropout, dropout_seed=seed)
            return bshd_output_projection(ctx, w_out, H, D)

        argnums = tuple(range(len(args)))
        return (_fwd_and_grads(kernel, argnums),
                _fwd_and_grads(reference, argnums), args)
    return build


# --- cross entropy ------------------------------------------------------------

def _xent_family():
    logits = jr.normal(_key(9), (B, S, V), jnp.bfloat16)
    target = jr.randint(_key(10), (B, S), 0, V)

    def make(impl):
        return _fwd_and_grads(
            lambda lg: vocab_parallel_cross_entropy(lg, target, 0.0, None,
                                                    impl), (0,))
    return make("pallas"), make("xla"), (logits,)


# --- decode attention ---------------------------------------------------------

def _lengths():
    """One live length per slot, on and off the 128-row block edges."""
    return jnp.array([1, 77, 128, 129, 500, 640, 1000, 1024], jnp.int32)


def _decode_bias():
    return BucketedBias(jr.normal(_key(14), (32, H), jnp.float32) * 0.5,
                        bidirectional=False, max_distance=128)


def _decode_family(h_kv, bias=False):
    def build():
        q = jr.normal(_key(11), (SLOTS, H, D), jnp.bfloat16)
        k = jr.normal(_key(12), (SLOTS, h_kv, S, D), jnp.bfloat16)
        v = jr.normal(_key(13), (SLOTS, h_kv, S, D), jnp.bfloat16)
        rel = _decode_bias() if bias else None

        def make(impl):
            return lambda q, k, v: decode_attention(
                q, k, v, _lengths(), impl=impl, bias=rel)
        return make("pallas"), make("xla"), (q, k, v)
    return build


def _paged_family(h_kv, bias=False, kv_dtype=None):
    """The serving engine's layout: a shared (num_blocks, h_kv, 128, d) pool
    addressed through per-slot block tables (block 0 is the dead block)."""
    def build():
        nb = S // BLOCK
        num_blocks = SLOTS * nb + 1
        q = jr.normal(_key(11), (SLOTS, H, D), jnp.bfloat16)
        k = jr.normal(_key(12), (num_blocks, h_kv, BLOCK, D), jnp.bfloat16)
        v = jr.normal(_key(13), (num_blocks, h_kv, BLOCK, D), jnp.bfloat16)
        tables = (1 + jr.permutation(_key(15), SLOTS * nb)
                  ).reshape(SLOTS, nb).astype(jnp.int32)
        scales = {}
        if kv_dtype is not None:
            from apex_tpu.serving.engine import KV_QUANT_SPECS, _quant_rows
            qmax, qdtype = KV_QUANT_SPECS[kv_dtype]
            k, ks = _quant_rows(k, (1, 3), qmax=qmax, qdtype=qdtype)
            v, vs = _quant_rows(v, (1, 3), qmax=qmax, qdtype=qdtype)
            scales = dict(k_scale=ks, v_scale=vs)
        rel = _decode_bias() if bias else None

        def make(impl):
            return lambda q, k, v: decode_attention(
                q, k, v, _lengths(), impl=impl, bias=rel, block_tables=tables,
                **scales)
        return make("pallas"), make("xla"), (q, k, v)
    return build


# --- sampling and speculative verification ------------------------------------

def _logits(rows):
    return jr.normal(_key(16), rows + (V,), jnp.float32) * 3.0


def _sample_family(**knobs):
    def build():
        def make(impl):
            return lambda lg: fused_sample(lg, _key(17), impl=impl, **knobs)
        return make("pallas"), make("xla"), (_logits((SLOTS,)),)
    return build


def _verify_family(**knobs):
    def build():
        k = 4
        logits = _logits((SLOTS, k + 1))
        # drafts that agree with the target on a per-slot prefix, so accept
        # lengths cover 0..k
        greedy = jnp.argmax(logits[:, :k], -1).astype(jnp.int32)
        keep = jnp.arange(k)[None, :] < (jnp.arange(SLOTS) % (k + 1))[:, None]
        drafted = jnp.where(keep, greedy, (greedy + 1) % V)

        def make(impl):
            return lambda lg: fused_verify(lg, drafted, _key(18), impl=impl,
                                           **knobs)
        return make("pallas"), make("xla"), (logits,)
    return build


def _verify_tree_family(**knobs):
    def build():
        from apex_tpu.spec.tree import draft_tree
        tree = draft_tree(2, 3)                   # branching 2, depth 3
        parents, anc = tree.operands(SLOTS)
        logits = _logits((SLOTS, tree.n1))
        # each node carries its parent's greedy candidate on even slots
        # (the whole first-child path accepts) and a miss on odd ones
        cand = jnp.argmax(logits, -1).astype(jnp.int32)
        par = jnp.asarray(parents)
        hit = jnp.take_along_axis(cand, par, axis=1)
        odd = (jnp.arange(SLOTS) % 2 == 1)[:, None]
        tokens = jnp.where(odd, (hit + 1) % V, hit)

        def make(impl):
            return lambda lg: fused_verify_tree(
                lg, tokens, par, jnp.asarray(anc), _key(19), impl=impl,
                **knobs)
        return make("pallas"), make("xla"), (logits,)
    return build


# --- the hybrid decoder's kernels ------------------------------------------------

def _flash_wide_head_family():
    """Heads of 256 in a group of 8 (the split bshd backward's own VMEM
    limit), the gated-attention layers' shape at the smoke's sequence."""
    def build():
        q = jr.normal(_key(41), (B, S, H, 256), jnp.bfloat16)
        k, v = (jr.normal(_key(i), (B, S, 1, 256), jnp.bfloat16) for i in (42, 43))

        def make(impl):
            return _fwd_and_grads(
                lambda q, k, v: flash_attention(q, k, v, causal=True, layout="bshd",
                                                scale=256 ** -0.5, impl=impl), (0, 1, 2))
        return make("pallas"), make("xla"), (q, k, v)
    return build


def _sliced_attention_reference(fn):
    """``fn``'s XLA form over one batch row and four query heads at a time
    (a score tensor of 1 GB at 8,192 positions, where the whole call's
    would be 17): the output and dq laid back in place, dk and dv summed in
    float32 over the slices that share a kv head. Same cotangent as
    :func:`_fwd_and_grads` draws, sliced."""
    q_heads = 4

    def run(q, k, v):
        b, s, h, d = q.shape
        h_kv, n = k.shape[2], h // q_heads
        cot = jr.normal(_key(99), q.shape, jnp.float32)

        def one(idx):
            row, first = idx // n, (idx % n) * q_heads
            cut = lambda x, head, heads: jax.lax.dynamic_slice(  # noqa: E731
                x, (row, 0, head, 0), (1, s, heads, d))
            kv_head = first // (h // h_kv)
            out, pull = jax.vjp(fn, cut(q, first, q_heads), cut(k, kv_head, 1),
                                cut(v, kv_head, 1))
            dq, dk, dv = pull(cut(cot, first, q_heads).astype(out.dtype))
            return out[0], dq[0], dk[0, :, 0].astype(jnp.float32), dv[0, :, 0].astype(jnp.float32)

        out, dq, dk, dv = jax.lax.map(one, jnp.arange(b * n))
        heads = lambda x: x.reshape(b, n, s, q_heads, d).transpose(  # noqa: E731
            0, 2, 1, 3, 4).reshape(b, s, h, d)
        kv = lambda x, like: x.reshape(b, h_kv, n // h_kv, s, d).sum(2).transpose(  # noqa: E731
            0, 2, 1, 3).astype(like.dtype)
        return heads(out), (heads(dq), kv(dk, k), kv(dv, v))
    return run


# trees whose kernels the flash cell families and the delta rules' compare with (``--checkout``)
CHECKOUTS = []


def _module_at(checkout, file):
    """``ops/pallas/<file>.py`` of another tree as a module of its own (its
    imports resolve to this tree's package, which it shares)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        file + "_at_" + "".join(c if c.isalnum() else "_" for c in checkout),
        os.path.join(checkout, "apex_tpu", "ops", "pallas", file + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _forward_report(forms):
    """A flash cell family's lines: for each of ``forms`` — ``(what, (sq, sk,
    block, window), call, operands)``, ``call(pk, *operands)`` the raw forward
    wrapper of attention module ``pk`` returning ``(o, lse)`` — the grid steps
    a head takes by kind (``forward_tiles``, static by shape), the forward's
    device time, and for every ``--checkout`` that tree's time and the largest
    difference of ``o`` and ``lse`` (it must read 0.0)."""
    def diff(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))

    def report():
        from apex_tpu.ops.pallas import attention as here
        lines = []
        for what, (sq, sk, block, window), call, operands in forms:
            args = operands()

            def run(pk):
                _, by_name, out = _device_ms(functools.partial(call, pk), *args, with_output=True)
                return sum(t for n, t in by_name.items() if "flash_fwd" in n), out

            ms, (o, lse) = run(here)
            running, visible, skipped = here.forward_tiles(sq, sk, block, block, True, window)
            lines.append(f"{what}: {ms:.3f} ms a call on the device; a head's grid steps: "
                         f"{running} running tiles, {visible} of them fully visible (no mask), "
                         f"{skipped} skipped (nothing fetched)")
            for checkout in CHECKOUTS:
                ms_c, (o_c, lse_c) = run(_module_at(checkout, "attention"))
                d_o, d_lse = diff(o, o_c), diff(lse, lse_c)
                lines.append(f"{'FAIL' if d_o or d_lse else '    '} {checkout}: {ms_c:.3f} ms a call; "
                             f"max |diff| of o {d_o}, of lse {d_lse}")
        return lines
    return report


def _bshd_forward_forms(h, h_kv, d, window, packed=False):
    """The forward wrappers a cell family's shape rides: ``flash_fwd_bshd``
    (``_win`` under a window) and, ``packed``, ``flash_fwd_packed`` on a q|k|v
    buffer of the same heads."""
    def bshd(pk, q, k, v):
        return pk.flash_fwd_bshd(q, k, v, scale=d ** -0.5, causal=True, window=window,
                                 full_lse=True, interpret=_backend.interpret_mode())

    def of_buffer(pk, qkv):
        return pk.flash_fwd_packed(qkv, h, h_kv, d, scale=d ** -0.5, causal=True, full_lse=True,
                                   interpret=_backend.interpret_mode())

    qkv = lambda: (jr.normal(_key(54), (2, 8192, (h + 2 * h_kv) * d), jnp.bfloat16),)  # noqa: E731
    name = "flash_fwd_bshd" + ("" if window is None else "_win")
    forms = [(name, (8192, 8192, 1024, window), bshd, lambda: _cell_qkv(h, h_kv, d))]
    if packed:
        forms.append(("flash_fwd_packed", (8192, 8192, 1024, None), of_buffer, qkv))
    return forms


def _cell_qkv(h, h_kv, d):
    q = jr.normal(_key(51), (2, 8192, h, d), jnp.bfloat16)
    k, v = (jr.normal(_key(i), (2, 8192, h_kv, d), jnp.bfloat16) for i in (52, 53))
    return q, k, v


def _flash_cell_family(h, h_kv, d, window):
    """The seq-major kernels at a training cell's attention shape, 2 rows of
    8,192: the forward (``flash_fwd_bshd`` / ``_win``) and the one-pass
    backward (``flash_bwd_bshd_fused`` / ``flash_bwd_bshd_win_fused``: dq, dk
    and dv from one walk of the tiles, on a window 3 of the 8 kv blocks a q
    block) against XLA's masked scores, a slice at a time."""
    def build():
        def attention(impl):
            return lambda q, k, v: flash_attention(
                q, k, v, causal=True, layout="bshd", window=window, impl=impl)
        return (_fwd_and_grads(attention("pallas"), (0, 1, 2)),
                _sliced_attention_reference(attention("xla")), _cell_qkv(h, h_kv, d))
    return build


# ``gpt2m-train-1k-dp4``'s attention a chip: 8 rows of 1,024, 16 heads of 64
PAIR_CELL = dict(b=8, s=1024, h=16, d=64)


def _pair_cell_family():
    """``fused_qkv_attention`` at ``gpt2m-train-1k-dp4``'s shape (hidden
    1,024 = 16 heads of 64, two to a lane tile: ``flash_fwd_packed_pair`` /
    ``flash_bwd_packed_pair_fused``), the value and every gradient against
    separate projections around XLA's attention."""
    def build():
        b, s, h, d = (PAIR_CELL[k] for k in "bshd")
        hid = h * d
        x = jr.normal(_key(5), (b, s, hid), jnp.bfloat16)
        w_qkv = jr.normal(_key(6), (3 * hid, hid), jnp.bfloat16) * 0.02
        b_qkv = jr.normal(_key(7), (3 * hid,), jnp.bfloat16) * 0.02
        w_out = jr.normal(_key(8), (hid, hid), jnp.bfloat16) * 0.02

        def kernel(x, w_qkv, b_qkv, w_out):
            return fused_qkv_attention(x, w_qkv, b_qkv, w_out, None, None, None,
                                       h, h, d, d ** -0.5, True, 0.0)

        def reference(x, w_qkv, b_qkv, w_out):
            q, k, v = bshd_qkv_projection(x, w_qkv, b_qkv, h, h, d)
            ctx = flash_attention(q, k, v, causal=True, layout="bshd", impl="xla")
            return bshd_output_projection(ctx, w_out, h, d)

        return (_fwd_and_grads(kernel, (0, 1, 2, 3)),
                _fwd_and_grads(reference, (0, 1, 2, 3)), (x, w_qkv, b_qkv, w_out))
    return build


def _pair_forward_forms():
    b, s, h, d = (PAIR_CELL[k] for k in "bshd")

    def call(pk, qkv):
        return pk.flash_fwd_packed(qkv, h, h, d, scale=d ** -0.5, causal=True, full_lse=True,
                                   interpret=_backend.interpret_mode())
    return [("flash_fwd_packed_pair", (s, s, 1024, None), call,
             lambda: (jr.normal(_key(4), (b, s, 3 * h * d), jnp.bfloat16),))]


def _device_ms(fn, *args, calls=5, with_output=False):
    """``(total, by name)``: device milliseconds a call of jitted ``fn``, from
    a profiler trace of ``calls`` calls — every operation's, and each
    operation's by its instruction name. Not the host clock, which under
    0.2 ms reads the dispatch floor. ``with_output``: a call's result third."""
    import tempfile
    from apex_tpu.prof.scopes import read_xplane
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as logdir:
        jax.profiler.start_trace(logdir)
        out = [fn(*args) for _ in range(calls)]
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        ops_s, _, _ = read_xplane(logdir)
    by_name = {name: 1e3 * took / calls for name, took in ops_s.items()}
    total = sum(by_name.values())
    return (total, by_name, out[0]) if with_output else (total, by_name)


def _pair_cell_times():
    """The attention of one layer at the cell's shape, forward + backward from
    q, k, v (or the packed buffer) and a cotangent, by device time: XLA's
    composition, the flat kernels, the pair kernels at three blocks."""
    def report():
        from apex_tpu.ops.pallas import attention as pk
        b, s, h, d = (PAIR_CELL[k] for k in "bshd")
        q, k, v = (jr.normal(_key(i), (b, h, s, d), jnp.bfloat16) for i in (1, 2, 3))
        qkv = jr.normal(_key(4), (b, s, 3 * h * d), jnp.bfloat16)
        do = jr.normal(_key(9), (b, s, h * d), jnp.bfloat16)

        def flat(impl):
            return _fwd_and_grads(lambda q, k, v: flash_attention(
                q, k, v, causal=True, impl=impl), (0, 1, 2))

        def pair(block):
            kw = dict(scale=d ** -0.5, causal=True, bq=block, bk=block,
                      interpret=_backend.interpret_mode())

            def run(qkv, do):
                o, lse = pk.flash_fwd_packed(qkv, h, h, d, full_lse=True, **kw)
                return o, pk.flash_bwd_packed(qkv, h, h, d, o, lse, do, **kw)
            return run

        def parts(by_name):
            fwd = sum(t for n, t in by_name.items() if "flash_fwd" in n)
            bwd = sum(t for n, t in by_name.items() if "flash_bwd" in n)
            return f"flash_fwd* {fwd:.3f} + flash_bwd* {bwd:.3f}" if fwd else "no kernel"

        lines = []
        for name, fn, args in (
                ("XLA composition (b, h, s, d)", flat("xla"), (q, k, v)),
                ("flat kernels flash_fwd + flash_bwd_dq/dkv", flat("pallas"), (q, k, v)),
                *((f"pair kernels at blocks of {blk}", pair(blk), (qkv, do))
                  for blk in (1024, 512, 256))):
            total, by_name = _device_ms(fn, *args)
            lines.append(f"{name}: {total:.3f} ms a layer on the device ({parts(by_name)})")
        return lines
    return report


def _latent_cell_family(s=8192):
    """The two-width kernels at ``dsv2lite-train-8k``'s attention shape, 2
    rows of 8,192, 16 heads of 128 (+ 64 rotary features against ONE shared
    key) with values of 128: ``flash_fwd_bshd_mla`` and the one-pass
    ``flash_bwd_bshd_mla_fused`` (dq, dk, dv, dq2 and dk2 — the last summed
    over all 16 heads in VMEM) against XLA's materialised scores, one batch
    row and four heads at a time, dk2 summed in float32 over the slices."""
    heads, q_heads, scale = 16, 4, 192 ** -0.5 * 1.5896

    def build():
        shapes = ((2, s, heads, 128),) * 3 + ((2, s, heads, 64), (2, s, 1, 64))
        args = tuple(jr.normal(_key(61 + i), shape, jnp.bfloat16)
                     for i, shape in enumerate(shapes))

        def attention(impl):
            return lambda q, k, v, q2, k2: flash_attention(
                q, k, v, causal=True, layout="bshd", impl=impl, scale=scale, second=(q2, k2))

        def sliced(q, k, v, q2, k2):
            b, n = q.shape[0], heads // q_heads
            cot = jr.normal(_key(99), v.shape[:2] + (heads, 128), jnp.float32)

            def one(idx):
                row, first = idx // n, (idx % n) * q_heads
                cut = lambda x, hs=q_heads: jax.lax.dynamic_slice(  # noqa: E731
                    x, (row, 0, first if hs > 1 else 0, 0), (1, s, hs, x.shape[3]))
                out, pull = jax.vjp(attention("xla"), cut(q), cut(k), cut(v), cut(q2),
                                    cut(k2, 1))
                dq, dk, dv, dq2, dk2 = pull(cut(cot).astype(out.dtype))
                return out[0], dq[0], dk[0], dv[0], dq2[0], dk2[0].astype(jnp.float32)

            out, dq, dk, dv, dq2, dk2 = jax.lax.map(one, jnp.arange(b * n))
            lay = lambda x: x.reshape(b, n, s, q_heads, x.shape[-1]).transpose(  # noqa: E731
                0, 2, 1, 3, 4).reshape(b, s, heads, x.shape[-1])
            dk2 = dk2.reshape(b, n, s, 1, 64).sum(1).astype(k2.dtype)
            return lay(out), (lay(dq), lay(dk), lay(dv), lay(dq2), dk2)

        return _fwd_and_grads(attention("pallas"), (0, 1, 2, 3, 4)), sliced, args
    return build


def _latent_forward_forms(s=8192, heads=16):
    def call(pk, q, k, v, q2, k2):
        return pk.flash_fwd_bshd(q, k, v, scale=192 ** -0.5 * 1.5896, causal=True, full_lse=True,
                                 second=(q2, k2), interpret=_backend.interpret_mode())
    shapes = ((2, s, heads, 128),) * 3 + ((2, heads, s, 64), (2, 1, s, 64))    # the second term head-major
    return [("flash_fwd_bshd_mla", (s, s, 1024, None), call,
             lambda: tuple(jr.normal(_key(61 + i), shape, jnp.bfloat16)
                           for i, shape in enumerate(shapes)))]


def _delta_rule_family():
    """``gdn_fwd`` / ``gdn_bwd`` (chunk operands and the 64 x 64 inverse built
    in VMEM, then the recurrence over chunks) against the XLA form (operands
    prepared by XLA, a ``lax.scan`` over the chunks), value and all five
    gradients: heads of 128 as the cell's, two key heads serve four value
    heads, 16 chunks a row (two grid steps), decays from 0.2 to 0.999."""
    def build():
        from apex_tpu.ops.gated_delta_rule import gated_delta_rule
        q, k = (jr.normal(_key(i), (B, S, 2, D), jnp.bfloat16) for i in (44, 45))
        v = jr.normal(_key(46), (B, S, 4, D), jnp.bfloat16)
        g = -jnp.exp(jr.uniform(_key(47), (B, S, 4), minval=-7.0, maxval=0.5))
        beta = jax.nn.sigmoid(jr.normal(_key(48), (B, S, 4)))

        def make(impl):
            return _fwd_and_grads(lambda *a: gated_delta_rule(*a, impl=impl), (0, 1, 2, 3, 4))
        return make("pallas"), make("xla"), (q, k, v, g, beta)
    return build


def _kda_operands(t=8192, heads=32):
    """``ling3-train-8k``'s rule: 2 rows of 8,192, 32 heads of 128, a log decay
    a key channel drawn over the whole of (-5, 0) — a fifth of the heads AT
    the bound every step, so that whole sub-chunks reach the kernels' largest
    factors."""
    q, k, v = (jr.normal(_key(i), (B, t, heads, D), jnp.bfloat16) for i in (120, 121, 122))
    g = -5.0 * jax.nn.sigmoid(4.0 * jr.normal(_key(123), (B, t, heads, D)))
    g = g.at[:, :, ::5].set(-5.0)
    beta = jax.nn.sigmoid(jr.normal(_key(124), (B, t, heads)))
    return q, k, v, g, beta


def _kda_family():
    """``kda_fwd`` / ``kda_bwd`` (the in-chunk scores a sub-chunk of 16 rows at
    a time from float32 factors, then ``gdn_*``'s scheme) against the XLA form
    (explicit differences chunk by chunk in a ``lax.scan``) at the cell's
    shape, value and all five gradients."""
    def build():
        from apex_tpu.ops.gated_delta_rule import kda_rule

        def make(impl):
            return _fwd_and_grads(lambda *a: kda_rule(*a, impl=impl), (0, 1, 2, 3, 4))
        return make("pallas"), make("xla"), _kda_operands()
    return build


def _gdn_operands(t=8192, hk=16, hv=32, chunk=64):
    """``q3next-train-8k``'s rule as the kernels take it: 2 rows of 8,192, 16
    key heads serve 32 value heads of 128, decays from 0.2 to 0.999; ``G`` the
    log decay cumulated inside each chunk of 64, ``gl`` its last over the lanes."""
    q, k = (jr.normal(_key(i), (B, t, hk * D), jnp.bfloat16) for i in (130, 131))
    v, do = (jr.normal(_key(i), (B, t, hv * D), jnp.bfloat16) for i in (132, 133))
    g = -jnp.exp(jr.uniform(_key(134), (B, hv, t // chunk, chunk), minval=-7.0, maxval=0.5))
    G = jnp.cumsum(g, axis=-1)
    beta = jax.nn.sigmoid(jr.normal(_key(135), (B, hv, t // chunk, chunk)))
    return (q, k, v, G, beta, jnp.broadcast_to(G[..., -1:], G.shape[:-1] + (D,))), do


def _rule_times(family):
    """The delta rule's kernel pair (``gdn`` or ``kda``) ALONE at its cell's
    shape, 2 rows a chip as the cells run: device ms a call of the forward and
    of the backward — and, from every ``--checkout``, the same pair on the same
    operands beside it with max |diff| of every result (``o``, the states the
    blocks started from, each cotangent), which must read 0.0: a grid step's
    order is not its arithmetic."""
    def report():
        from apex_tpu.ops.pallas import gated_delta_rule, kda
        static = dict(interpret=_backend.interpret_mode())
        if family == "gdn":
            file, here, shape = "gated_delta_rule", gated_delta_rule, "16 key / 32 value heads of 128"
            args, do = _gdn_operands()
            static["heads"] = 16
        else:
            file, here, shape = "kda", kda, "32 heads of 128"
            q, k, v, g, beta = _kda_operands()
            flat = lambda x: x.reshape(x.shape[:2] + (-1,))  # noqa: E731
            args = (flat(q), flat(k), flat(v), flat(g), jnp.moveaxis(beta, 1, 2).reshape(B, 32, -1, 64))
            do = flat(jr.normal(_key(125), q.shape, jnp.bfloat16))

        def both(module):
            fwd, bwd = (functools.partial(getattr(module, f"{family}_{half}"), **static)
                        for half in ("fwd", "bwd"))

            def run(*a):
                o, s0 = fwd(*a[:-1])
                return (o, s0) + tuple(bwd(*a[:-1], s0, a[-1]))
            _, by_name, out = _device_ms(run, *args, do, with_output=True)
            return {n: ms for n, ms in by_name.items() if family + "_" in n}, out

        def line(label, kernels):
            return (f"{label}: " + ", ".join(f"{n} {ms:.3f} ms" for n, ms in sorted(kernels.items()))
                    + f" a call by device time (2 x 8,192 tokens, {shape})")

        kernels, out = both(here)
        lines = [line(f"{family} kernels alone", kernels)]
        for checkout in CHECKOUTS:
            theirs, their_out = both(_module_at(checkout, file))
            gaps = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                    for a, b in zip(out, their_out)]
            same = all(gap == 0.0 for gap in gaps) and len(out) == len(their_out)
            lines.append(("" if same else "FAIL ") + line(f"at {checkout}", theirs)
                         + f"; max |diff| of o, s0 and the cotangents {gaps}")
        return lines
    return report


def _delta_mixer_stages_family():
    """The two stages around the rule at ``q3next-train-8k``'s shape:
    ``conv_silu_fwd`` / ``conv_silu_bwd`` over ``q|k|v`` and ``gated_norm_fwd``
    / ``gated_norm_bwd`` a head of 128, both reading the fused projection
    (2, 8192, 12288) in place, against the XLA compositions — values and the
    gradients of the projection, the taps, ``o`` and the norm's weight."""
    def build():
        from apex_tpu.ops.gated_delta_rule import causal_conv_silu, gated_rms_norm
        t, qk, vv = 8192, 2048, 4096
        qkvz = jr.normal(_key(70), (B, t, 2 * qk + 2 * vv), jnp.bfloat16)
        taps = jr.uniform(_key(71), (4, 2 * qk + vv), minval=-0.5, maxval=0.5).astype(jnp.bfloat16)
        o = jr.normal(_key(72), (B, t, vv // D, D), jnp.bfloat16)
        weight = (1.0 + 0.1 * jr.normal(_key(73), (D,))).astype(jnp.bfloat16)

        def make(impl):
            def stages(qkvz, taps, o, weight):
                qkv = causal_conv_silu(qkvz, taps, widths=(qk, qk, vv), impl=impl)
                y = gated_rms_norm(o, qkvz, weight, impl=impl)
                return jnp.concatenate(qkv + (y.reshape(B, t, vv),), axis=-1)
            return _fwd_and_grads(stages, (0, 1, 2, 3))
        return make("pallas"), make("xla"), (qkvz, taps, o, weight)
    return build


def _timed(fn, *args, calls=5):
    """(milliseconds a call of jitted ``fn`` by the host clock over ``calls``
    calls, its result)."""
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / calls, out


def _ssd_operands(t=8192):
    """``nemotron3-train-8k``'s scan: 2 rows of 8,192, 64 heads of 64 in 8
    groups, a state of 128 rows, ``dt`` and ``A`` as the model starts them."""
    h, p, g, n = 64, 64, 8, 128
    x = jr.normal(_key(80), (B, t, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jr.normal(_key(81), (B, t, h)) + jnp.log(jnp.expm1(
        jnp.exp(jr.uniform(_key(82), (h,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1))))))
    A = -jr.uniform(_key(83), (h,), minval=1.0, maxval=16.0)
    Bm, Cm = (jr.normal(_key(i), (B, t, g, n), jnp.bfloat16) for i in (84, 85))
    D = 1.0 + 0.1 * jr.normal(_key(86), (h,))
    return x, dt, A, Bm, Cm, D


def _ssd_family():
    """``ssd_fwd`` / ``ssd_bwd`` (the chunked Mamba-2 scan, states in VMEM)
    against the XLA form (the same chunked mathematics as einsums and a
    ``lax.scan`` over the chunks) at the cell's shape, value and all six
    gradients (``dA``, ``dD`` and ``ddt`` among them)."""
    def build():
        from apex_tpu.ops.ssd import ssd_scan

        def make(impl):
            return _fwd_and_grads(lambda *a: ssd_scan(*a, impl=impl), (0, 1, 2, 3, 4, 5))
        return make("pallas"), make("xla"), _ssd_operands()
    return build


def _ssd_times():
    def report():
        from apex_tpu.ops.ssd import ssd_scan
        args = _ssd_operands()
        lines = []
        for impl in ("pallas", "xla"):
            fwd = lambda *a: ssd_scan(*a, impl=impl)  # noqa: E731
            both = _fwd_and_grads(fwd, (0, 1, 2, 3, 4, 5))
            lines.append(f"ssd {impl}: forward {_timed(fwd, *args)[0]:.2f} ms, forward + backward "
                         f"{_timed(both, *args)[0]:.2f} ms a layer (2 x 8,192 tokens)")
        return lines
    return report


def _ssm_mixer_stages_family():
    """The two stages around the scan at ``nemotron3-train-8k``'s shape:
    ``conv_silu_*`` WITH a bias over ``x|B|C`` and ``gated_norm_*`` gating
    first over groups of 512 with a weight a channel, both reading the fused
    projection (2, 8192, 10304: ``xBC | z | dt``) in place, against the XLA
    compositions — values and the gradients of the projection, the taps, the
    bias, ``y`` and the norm's weight."""
    def build():
        from apex_tpu.ops.gated_delta_rule import causal_conv_silu, gated_rms_norm
        t, inner, bc = 8192, 4096, 1024
        conv = inner + 2 * bc
        proj = jr.normal(_key(90), (B, t, conv + inner + 64), jnp.bfloat16)
        taps = jr.uniform(_key(91), (4, conv), minval=-0.5, maxval=0.5).astype(jnp.bfloat16)
        bias = (0.02 * jr.normal(_key(92), (conv,))).astype(jnp.bfloat16)
        y = jr.normal(_key(93), (B, t, 8, inner // 8), jnp.bfloat16)
        weight = (1.0 + 0.1 * jr.normal(_key(94), (8, inner // 8))).astype(jnp.bfloat16)

        def make(impl):
            def stages(proj, taps, bias, y, weight):
                xbc = causal_conv_silu(proj, taps, bias, widths=(inner, bc, bc), impl=impl)
                n = gated_rms_norm(y, proj, weight, 1e-5, gate_first=True, gate_start=conv,
                                   impl=impl)
                return jnp.concatenate(xbc + (n.reshape(B, t, inner),), axis=-1)
            return _fwd_and_grads(stages, (0, 1, 2, 3, 4))
        return make("pallas"), make("xla"), (proj, taps, bias, y, weight)
    return build


def _delta_rule_drifted_family():
    """The kernels (``gdn_fwd`` / ``gdn_bwd``: operands, inverse and
    recurrence in VMEM) in
    bfloat16 against the token-by-token recurrence in float32, on keys as
    training leaves them: 0.7 of every key one common direction, strong
    writes, decays of 0.999 and slower — the chunk's triangular system far
    from the identity, where an inverse that cancels powers of it is lost."""
    def build():
        from apex_tpu.ops.gated_delta_rule import gated_delta_rule, l2_normalize
        q = jr.normal(_key(60), (B, S, 2, D), jnp.bfloat16)
        k = (0.3 * jr.normal(_key(61), (B, S, 2, D))
             + 0.7 * jr.normal(_key(62), (B, 1, 2, D))).astype(jnp.bfloat16)
        v = jr.normal(_key(63), (B, S, 4, D), jnp.bfloat16)
        g = -jnp.exp(jr.uniform(_key(64), (B, S, 4), minval=-12.0, maxval=-7.0))
        beta = jax.nn.sigmoid(jr.normal(_key(65), (B, S, 4)) + 3.0)

        def recurrence(q, k, v, g, beta):
            f32 = jnp.float32
            qn = jnp.repeat(l2_normalize(q) * D ** -0.5, 2, axis=2)
            kn = jnp.repeat(l2_normalize(k), 2, axis=2)

            def token(state, x):
                q, k, v, g, beta = x              # (B, 4, D) and (B, 4)
                state = state * jnp.exp(g)[..., None, None]
                u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", state, k, precision="highest"))
                state = state + k[..., :, None] * u[..., None, :]
                return state, jnp.einsum("bhkv,bhk->bhv", state, q, precision="highest")

            xs = jax.tree.map(lambda a: jnp.moveaxis(a.astype(f32), 1, 0), (qn, kn, v, g, beta))
            _, o = jax.lax.scan(token, jnp.zeros((B, 4, D, D), f32), xs)
            return jnp.moveaxis(o, 0, 1)

        args = (0, 1, 2, 3, 4)
        return (_fwd_and_grads(lambda *a: gated_delta_rule(*a, impl="pallas"), args),
                _fwd_and_grads(recurrence, args), (q, k, v, g, beta))
    return build


def _dropless_family():
    """The dropless expert layer on the ``moe_gmm`` kernels against the
    batched-einsum composition: 8 of 16 experts held, top 4, 2,048 tokens."""
    def build():
        from apex_tpu.transformer.moe import dropless_moe_layer
        F, E, held = 512, 16, (4, 8)
        n = lambda i, *shape: (0.05 * jr.normal(_key(i), shape)).astype(jnp.bfloat16)  # noqa: E731
        p = {"router": n(50, HID, E), "w_gate_up": n(51, held[1], HID, 2 * F),
             "w_down": n(52, held[1], F, HID), "shared_gate_up": n(53, HID, 2 * F),
             "shared_down": n(54, F, HID), "shared_mix": n(55, HID)}
        x = jr.normal(_key(56), (B, S, HID), jnp.bfloat16)

        def make(impl):
            return _fwd_and_grads(lambda p, x: dropless_moe_layer(
                p, x, top_k=4, experts_held=held, impl=impl)[0], (0, 1))
        return make("pallas"), make("xla"), (p, x)
    return build


CELL_TOKENS, CELL_HIDDEN = 16384, 2048   # 2 rows of 8,192; the first three expert cells' width
# top-k, experts held, router width, F: trinity-, dsv2lite-, q3next-, nemotron3- and ling3-train-8k
EXPERT_CELLS = {"trinity": (8, 16, 128, 1024), "dsv2lite": (6, 8, 64, 1408),
                "q3next": (10, 32, 512, 512), "nemotron3": (6, 8, 128, 1856),
                "ling3": (8, 8, 512, 768),
                # no cell's: a second width of whole HALF lane tiles (7.5) and a third row
                # of no whole tiles (3,072: 12 lines), which the rules let through
                "halftile": (6, 8, 128, 960)}
# experts that are not SwiGLU at 2,048: (hidden, activation); the rows of 2,688 and 2,560 are
# 10.5 and 10 lines of 128 words, in groups of 11 and 10 (``expert_rows.groups_of``)
EXPERT_FORMS = {"nemotron3": (2688, "relu2"), "ling3": (2560, "silu_gate"),
                "halftile": (3072, "relu2")}
NO_CELL = {"halftile"}


def _expert_form(cell):
    return EXPERT_FORMS.get(cell, (CELL_HIDDEN, "silu_gate"))


def _expert_cell_operands(cell):
    k, held, width, F = EXPERT_CELLS[cell]
    hidden, activation = _expert_form(cell)
    n = lambda i, *shape: (0.05 * jr.normal(_key(i), shape)).astype(jnp.bfloat16)  # noqa: E731
    p = {"router": n(60, hidden, width), "w_down": n(62, held, F, hidden),
         "shared_down": n(64, F, hidden), "shared_mix": n(65, hidden)}
    if activation == "relu2":                   # one up matrix, no gate
        p.update(w_up=n(61, held, hidden, F), shared_up=n(63, hidden, F))
    else:
        p.update(w_gate_up=n(61, held, hidden, 2 * F), shared_gate_up=n(63, hidden, 2 * F))
    return p, jr.normal(_key(66), (CELL_TOKENS, hidden), jnp.bfloat16)


def _dropless_cell_family(cell):
    """The dropless expert layer at a cell's (k, held, width, F), 16,384
    tokens of its hidden size: the row movements (``moe_rows_*`` where the
    width lets them) and the grouped products against the XLA composition,
    forward and every gradient."""
    def build():
        from apex_tpu.transformer.moe import dropless_moe_layer
        k, held = EXPERT_CELLS[cell][:2]
        activation = _expert_form(cell)[1]

        def make(impl):
            return _fwd_and_grads(lambda p, x: dropless_moe_layer(
                p, x, top_k=k, experts_held=(0, held), impl=impl,
                activation=activation)[0], (0, 1))
        return make("pallas"), make("xla"), _expert_cell_operands(cell)
    return build


def _route_and_plan(cell, calls=20):
    """The routing of a cell's router at initialisation over 16,384 tokens of
    its hidden size, and what each half takes alone (host clock, ``calls``
    calls): ``route`` the scores, the top k and the counts
    (``moe.route_topk``), ``plan`` the rows from them (``moe.dropless_plan``).
    Returns ``(the tokens, rows a block, top_p, plan, the two times' text)``."""
    from apex_tpu.ops.pallas import grouped_matmul as gk
    from apex_tpu.transformer import moe
    k, held, width, _ = EXPERT_CELLS[cell]
    p, x = _expert_cell_operands(cell)
    rows = moe.dropless_block_rows(CELL_TOKENS, k, held, width)
    route_ms, (top_e, top_p, _, counts) = _timed(
        lambda x, router: moe.route_topk(x, router, k), x, p["router"], calls=calls)
    plan_ms, plan = _timed(
        lambda top_e, counts: moe.dropless_plan(top_e, counts, (0, held), rows, gk.TM),
        top_e, counts, calls=calls)
    return x, rows, top_p, plan, f"route {route_ms:.3f} ms, plan {plan_ms:.3f} ms"


def _dropless_movement_times(cell, calls=20):
    """Each of the four movements between tokens and rows alone, at a cell's
    shapes and a routing of its router at initialisation: XLA's gathers
    against the ``moe_rows_*`` kernels (host clock, ``calls`` calls), and the
    routing and the plan that feed them, each alone."""
    def report():
        from apex_tpu.transformer import moe
        x, rows, top_p, plan, took = _route_and_plan(cell, calls)
        move = jax.jit(lambda plan: moe._block_move(plan, 0, rows))(plan)
        y = jr.normal(_key(67), (rows, x.shape[-1]), jnp.bfloat16)
        g = jr.normal(_key(68), x.shape, jnp.bfloat16)

        ms = functools.partial(_timed, calls=calls)
        lines = [f"{cell}: block of {rows} rows, {int(plan['n_used'])} tiles in use, "
                 f"{int(plan['row_valid'].sum())} assignments; {took}"]
        movements = {
            "rows <- tokens": lambda impl: (lambda x: moe._rows_from_tokens(x, move, impl), (x,)),
            "its cotangent (tokens <- rows, weight 1)": lambda impl: (
                lambda y: jax.vjp(lambda x: moe._rows_from_tokens(x, move, impl), x)[1](y)[0], (y,)),
            "tokens <- rows, weighted": lambda impl: (
                lambda y, w: moe._tokens_from_rows(y, w, move, impl), (y, top_p)),
            "its cotangents (rows <- tokens scaled, dweights)": lambda impl: (
                lambda y, w, g: jax.vjp(lambda y, w: moe._tokens_from_rows(y, w, move, impl),
                                        y, w)[1](g), (y, top_p, g)),
        }
        for name, make in movements.items():
            took = {}
            for impl in ("xla", "pallas"):
                fn, args = make(impl)
                took[impl] = ms(fn, *args)
            err = max_error(took["pallas"][1], took["xla"][1])
            lines.append(f"{cell}: {name}: xla {took['xla'][0]:.3f} ms, "
                         f"moe_rows {took['pallas'][0]:.3f} ms, max err {err:.2e}")
        return lines
    return report


# --- the explicit-only families (auto resolves them to XLA) --------------------

def _ln_family(rms):
    def build():
        x = jr.normal(_key(20), (512, HID), jnp.bfloat16)
        w = 1.0 + 0.1 * jr.normal(_key(21), (HID,), jnp.bfloat16)
        b = 0.1 * jr.normal(_key(22), (HID,), jnp.bfloat16)

        def make(impl):
            if rms:
                return _fwd_and_grads(
                    lambda x, w: fused_rms_norm(x, w, impl=impl), (0, 1))
            return _fwd_and_grads(
                lambda x, w, b: fused_layer_norm(x, w, b, impl=impl),
                (0, 1, 2))
        return make("pallas"), make("xla"), ((x, w) if rms else (x, w, b))
    return build


def _softmax_family(causal):
    def build():
        s = jr.normal(_key(23), (8, 256, 256), jnp.bfloat16)
        mask = jr.bernoulli(_key(24), 0.2, (8, 256, 256))

        def make(impl):
            if causal:
                return _fwd_and_grads(
                    lambda s: scaled_upper_triang_masked_softmax(
                        s, 0.125, impl=impl), (0,))
            return _fwd_and_grads(
                lambda s: scaled_masked_softmax(s, mask, 0.125, impl=impl),
                (0,))
        return make("pallas"), make("xla"), (s,)
    return build


def _dense_family(kind):
    def build():
        x = jr.normal(_key(25), (1024, HID), jnp.bfloat16)
        w1 = jr.normal(_key(26), (4 * HID, HID), jnp.bfloat16) * 0.02
        b1 = jr.normal(_key(27), (4 * HID,), jnp.bfloat16) * 0.02
        w2 = jr.normal(_key(28), (HID, 4 * HID), jnp.bfloat16) * 0.02
        b2 = jr.normal(_key(29), (HID,), jnp.bfloat16) * 0.02

        def make(impl):
            if kind == "dense":
                return _fwd_and_grads(
                    lambda x, w, b: fused_dense(x, w, b, impl=impl),
                    (0, 1, 2))
            if kind == "dense_gelu_dense":
                return _fwd_and_grads(
                    lambda x: fused_dense_gelu_dense(x, w1, b1, w2, b2,
                                                     impl=impl), (0,))
            return _fwd_and_grads(
                lambda x: mlp(x, [w1], [b1], "relu", impl=impl), (0,))
        args = (x, w1, b1) if kind == "dense" else (x,)
        return make("pallas"), make("xla"), args
    return build


FAMILIES = (
    Family("flash bshd fwd/dq/dkv", _flash_family("bshd")),
    Family("flash flat fwd/dq/dkv", _flash_family("bhsd")),
    Family("flash bshd GQA group 4", _flash_family("bshd", h_kv=2)),
    Family("flash bshd kv_lens", _flash_family("bshd", varlen=True)),
    Family("flash flat kv_lens", _flash_family("bhsd", varlen=True)),
    Family("flash bshd dropout", _flash_family("bshd", dropout=0.1)),
    Family("flash flat dropout", _flash_family("bhsd", dropout=0.1)),
    Family("flash bshd bias + dbias", _flash_bias_family("bshd", False)),
    Family("flash flat bias + dbias", _flash_bias_family("bhsd", False)),
    Family("flash bshd bucketed bias + dtable",
           _flash_bias_family("bshd", True)),
    Family("flash flat bucketed bias + dtable",
           _flash_bias_family("bhsd", True)),
    Family("flash packed fwd/bwd", _packed_family()),
    Family("flash packed kv_lens", _packed_family(varlen=True)),
    Family("flash packed dropout", _packed_family(dropout=0.1)),
    Family("flash packed bias + dbias", _packed_family(biased=True)),
    Family("xentropy stats", _xent_family, tol=F32_TOL),
    Family("flash bshd heads of 256, group 8", _flash_wide_head_family()),
    Family("flash bshd window 2,048 of 8,192, 32 / 4 heads of 128, fwd + one-pass bwd",
           _flash_cell_family(32, 4, 128, 2048),
           timings=_forward_report(_bshd_forward_forms(32, 4, 128, 2048))),
    Family("flash bshd 8,192, 32 / 4 heads of 128, fwd + one-pass bwd",
           _flash_cell_family(32, 4, 128, None),
           timings=_forward_report(_bshd_forward_forms(32, 4, 128, None))),
    Family("flash bshd 8,192, 16 / 1 heads of 128 (sc1b-train-8k's, whose buffer is packed), "
           "fwd + one-pass bwd", _flash_cell_family(16, 1, 128, None),
           timings=_forward_report(_bshd_forward_forms(16, 1, 128, None, packed=True))),
    Family("flash bshd 8,192, 16 / 2 heads of 256, fwd + one-pass bwd",
           _flash_cell_family(16, 2, 256, None),
           timings=_forward_report(_bshd_forward_forms(16, 2, 256, None))),
    Family("flash bshd latent 8,192, 16 heads of 128 + 64 shared / 128, fwd + one-pass bwd",
           _latent_cell_family(), timings=_forward_report(_latent_forward_forms())),
    Family("flash packed pair 8 x 1,024, 16 heads of 64 two to a lane tile, fwd + one-pass bwd",
           _pair_cell_family(),
           timings=lambda: _pair_cell_times()() + _forward_report(_pair_forward_forms())()),
    Family("gated delta rule gdn_fwd/gdn_bwd", _delta_rule_family(), timings=_rule_times("gdn")),
    Family("gated delta rule on drifted keys, against the recurrence",
           _delta_rule_drifted_family()),
    Family("kda rule kda_fwd/kda_bwd at ling3-train-8k's 32 heads of 128, decays down to the bound",
           _kda_family(), timings=_rule_times("kda")),
    Family("delta mixer stages conv_silu/gated_norm fwd/bwd", _delta_mixer_stages_family()),
    Family("ssd scan ssd_fwd/ssd_bwd at nemotron3-train-8k's 64 heads of 64, N 128, 8 groups",
           _ssd_family(), timings=_ssd_times()),
    Family("ssm mixer stages conv_silu + bias / gated_norm gate-first 512 fwd/bwd",
           _ssm_mixer_stages_family()),
    Family("dropless experts moe_gmm/dx/dw", _dropless_family()),
    *(Family(f"dropless experts at {cell}{'' if cell in NO_CELL else '-train-8k'}'s "
             f"top {k} onto {held} of {width}, F {F}: "
             f"moe_rows_gather/combine + moe_gmm", _dropless_cell_family(cell),
             timings=_dropless_movement_times(cell))
      for cell, (k, held, width, F) in EXPERT_CELLS.items()),
    Family("decode contiguous MHA", _decode_family(H)),
    Family("decode contiguous GQA group 4", _decode_family(2)),
    Family("decode contiguous bucketed bias", _decode_family(H, bias=True)),
    Family("decode paged MHA", _paged_family(H)),
    Family("decode paged GQA group 4", _paged_family(2)),
    Family("decode paged bucketed bias", _paged_family(H, bias=True)),
    Family("decode paged int8 pool", _paged_family(H, kv_dtype="int8")),
    Family("decode paged fp8 pool", _paged_family(H, kv_dtype="fp8_e4m3")),
    Family("fused_sample greedy", _sample_family(temperature=0.0)),
    Family("fused_sample top-k", _sample_family(temperature=0.8, top_k=50)),
    Family("fused_sample top-p", _sample_family(temperature=0.8, top_p=0.9)),
    Family("fused_sample top-k + top-p", _sample_family(
        temperature=0.8, top_k=50, top_p=0.9)),
    Family("fused_verify greedy", _verify_family(temperature=0.0)),
    Family("fused_verify sampled", _verify_family(
        temperature=0.8, top_k=50, top_p=0.9)),
    Family("fused_verify_tree greedy", _verify_tree_family(temperature=0.0)),
    Family("fused_verify_tree sampled", _verify_tree_family(
        temperature=0.8, top_k=50, top_p=0.9)),
    Family("layer norm fwd/bwd", _ln_family(False), auto=False),
    Family("rms norm fwd/bwd", _ln_family(True), auto=False),
    Family("causal softmax fwd/bwd", _softmax_family(True), auto=False),
    Family("masked softmax fwd/bwd", _softmax_family(False), auto=False),
    Family("fused_dense fwd/bwd", _dense_family("dense"), auto=False),
    Family("dense_gelu_dense bwd", _dense_family("dense_gelu_dense"),
           auto=False),
    Family("mlp bwd", _dense_family("mlp"), auto=False),
)


def max_error(got, want) -> float:
    """Largest disagreement over all output leaves: floats as max |a-b|
    over max |b| (a scale-relative error — gradients span decades);
    integer leaves count any mismatch as error 1."""
    worst = 0.0
    got_leaves, want_leaves = jax.tree.leaves(got), jax.tree.leaves(want)
    if len(got_leaves) != len(want_leaves):
        raise ValueError(f"output trees differ: {len(got_leaves)} vs "
                         f"{len(want_leaves)} leaves")
    for a, b in zip(got_leaves, want_leaves):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape:
            raise ValueError(f"output shapes differ: {a.shape} vs {b.shape}")
        if np.issubdtype(b.dtype, np.integer):
            err = float(np.any(a != b))
        else:
            a, b = a.astype(np.float32), b.astype(np.float32)
            if not (np.isfinite(a).all() and np.isfinite(b).all()):
                return float("inf")
            err = float(np.abs(a - b).max() / (np.abs(b).max() + 1e-6))
        worst = max(worst, err)
    return worst


def check(family: Family):
    """Compile, then run, one family's kernel and reference on the same
    operands: ``(max error, compile seconds, run seconds, halves)`` —
    ``halves`` the forward's error and the gradients' apart where the family
    returns ``(output, grads)``, else empty."""
    kernel, reference, args = family.build()
    t0 = time.perf_counter()
    compiled = [jax.jit(fn).lower(*args).compile()
                for fn in (kernel, reference)]
    t1 = time.perf_counter()
    got, want = jax.block_until_ready([c(*args) for c in compiled])
    halves = {}
    if isinstance(got, tuple) and len(got) == 2 and isinstance(got[1], tuple):
        halves = {"fwd": max_error(got[0], want[0]), "bwd": max_error(got[1], want[1])}
    err = max(halves.values()) if halves else max_error(got, want)
    return err, t1 - t0, time.perf_counter() - t1, halves


def main(families=FAMILIES) -> list:
    """Run every family; print one line each; return the failing names."""
    if _backend.interpret_mode():
        raise RuntimeError(
            f"kernel smoke needs a TPU: on {jax.default_backend()!r} every "
            f"Pallas kernel would run interpreted, which checks nothing "
            f"Mosaic compiles")
    failed, compile_s, run_s = [], 0.0, 0.0
    for fam in families:
        err, c_s, r_s, halves = check(fam)
        compile_s, run_s = compile_s + c_s, run_s + r_s
        ok = err <= fam.tol
        if not ok:
            failed.append(fam.name)
        apart = "".join(f"{half} {e:.2e}, " for half, e in halves.items())
        print(f"{'PASS' if ok else 'FAIL'} {fam.name}: max err {err:.2e} "
              f"({apart}tol {fam.tol:.0e}, {'auto' if fam.auto else 'explicit'}, "
              f"compile {c_s:.1f} s, run {r_s:.2f} s)", flush=True)
        lines = fam.timings() if fam.timings else ()
        for line in lines:
            print(f"     {line}", flush=True)
        if ok and any(line.startswith("FAIL") for line in lines):
            failed.append(fam.name)
    print(f"{len(families) - len(failed)}/{len(families)} kernel families "
          f"match their XLA composition on {jax.devices()[0].device_kind}")
    print(f"kernels: compile {compile_s:.1f} s, run {run_s:.1f} s "
          f"(operands are built outside both)", flush=True)
    return failed


if __name__ == "__main__":
    # any arguments choose the families whose name holds one of them
    parts = sys.argv[1:]
    while "--checkout" in parts:
        at = parts.index("--checkout")
        CHECKOUTS.append(parts[at + 1])
        del parts[at:at + 2]
    chosen = tuple(f for f in FAMILIES if not parts
                   or any(part in f.name for part in parts))
    sys.exit(1 if main(chosen) else 0)
