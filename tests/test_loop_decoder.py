"""The looped decoder (``HybridDecoderConfig.loop_trips`` > 1: one stack of
blocks walked several times on shared weights, an exit through the one head
and a gate after every walk, a loss over all the exits) against its plain
reference ``benchmarks/reference/loop_ref.py`` on seeded weights at a small
size: the loss, every gradient leaf and the exits' readings with and without
``remat``; the walks against an unshared stack built from copies; the exit
distribution; what the backward pass keeps of a looped stack and of its
exits, whose hand-written rule is held to jax's own; and a stack walked once,
which is the program it was before."""
import contextlib
import dataclasses
import hashlib
import io
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.models import HybridDecoderConfig, HybridDecoderModel, hybrid_decoder  # noqa: E402
from benchmarks.adapters import loop_tree  # noqa: E402
from benchmarks.reference import loop_ref  # noqa: E402
from comparisons import gap, kernel_calls  # noqa: E402

# the cell's block at a small size: two heads of 64 rotated whole, a SwiGLU of
# 2.75 x the hidden size, four walks, the published epsilon and theta
SMALL = {
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 352,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "vocab_size": 512, "total_ut_steps": 4,
    "entropy_beta": 0.1,
}
ROWS, SEQ = 2, 128
TRIPS = SMALL["total_ut_steps"]


def build(small=SMALL, **settings):
    d = loop_ref.dims(small)
    settings.setdefault("attention_impl", "xla")
    model = HybridDecoderModel(HybridDecoderConfig(**loop_tree.config_kwargs(d, **settings)))
    w = loop_ref.make_weights(d, loop_ref.seed_key(3))
    # a gate that is not at rest, so that the exits' shares differ
    w["gate"]["b"] = jnp.asarray(-1.0, jnp.float32)
    w["gate"]["w"] = 5 * w["gate"]["w"]
    return d, model, w


def batch(vocab=SMALL["vocab_size"]):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (ROWS, SEQ), 0, vocab)
    return tokens, jnp.roll(tokens, -1, axis=1)


# the stated tolerances: the loss and the exits' readings against the
# reference's own size, a gradient leaf against its largest entry
LOSS_TOL, GRAD_TOL = 2e-6, 3e-5


def saved_shapes(f, *args):
    """Shapes of what ``jax.grad(f)`` holds between the two passes."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        jax.ad_checkpoint.print_saved_residuals(f, *args)
    return [tuple(int(n) for n in re.match(r"\w+\[([\d,]*)\]", line).group(1).split(",") if n)
            for line in out.getvalue().strip().splitlines()]


def test_the_two_trees_hold_the_same_numbers():
    d, model, w = build()
    p = loop_tree.to_program(w)
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(p) == count(w)                  # a relabelling: nothing lost or doubled
    init = model.init(jax.random.PRNGKey(0))
    assert jax.tree.map(jnp.shape, p) == jax.tree.map(jnp.shape, init)
    assert init["exit_gate"]["weight"].shape == (128, 1)
    assert float(jnp.abs(init["exit_gate"]["bias"]).max()) == 0.0
    # every layer adds to the stream once a walk: the residual projections start smaller
    once = HybridDecoderModel(dataclasses.replace(model.config, loop_trips=1)).init(
        jax.random.PRNGKey(0))
    assert "exit_gate" not in once
    ratio = jnp.std(init["layers"]["attn"]["w_o"]) / jnp.std(once["layers"]["attn"]["w_o"])
    assert abs(float(ratio) - TRIPS ** -0.5) < 1e-3
    assert loop_tree.attention_view(d)["n_layer"] == 2 * TRIPS
    with pytest.raises(ValueError, match="loop_trips"):
        dataclasses.replace(model.config, loop_trips=0)


@pytest.fixture(scope="module")
def reference():
    d, _, w = build()
    tokens, targets = batch()
    with jax.default_matmul_precision("highest"):
        (loss, exits), g = jax.jit(jax.value_and_grad(
            lambda w: loop_ref.loss(w, d, tokens, targets), has_aux=True))(w)
    return float(loss), jax.device_get(exits), loop_tree.to_program(g)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_every_gradient_and_the_exits_match_the_reference(remat, reference):
    want, exits, g_want = reference
    d, model, w = build(remat=remat)
    tokens, targets = batch()
    with jax.default_matmul_precision("highest"):
        (loss, aux), g = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(
            p, tokens, targets, return_aux=True), has_aux=True))(loop_tree.to_program(w))
    assert abs(float(loss) - want) <= LOSS_TOL * abs(want)
    for name in ("exit_losses", "exit_mass", "exit_entropy"):
        np.testing.assert_allclose(aux[name], exits[name], rtol=LOSS_TOL, err_msg=name)
    assert aux["exit_mass"].shape == aux["exit_losses"].shape == (TRIPS,)
    assert abs(float(aux["exit_mass"].sum()) - 1) < 1e-6
    # the gate is off its rest: no exit takes everything, none nothing
    assert 0.02 < float(aux["exit_mass"].min()) and float(aux["exit_mass"].max()) < 0.9
    assert aux["expert_load"].shape == (0, model.config.held[1]) and aux["dropped"] == 0
    leaves = jax.tree_util.tree_flatten_with_path(g)[0]
    assert len(leaves) == len(jax.tree.leaves(g_want)) == 15
    for (path, a), b in zip(leaves, jax.tree.leaves(g_want)):
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)
        assert gap(a, b) <= GRAD_TOL, jax.tree_util.keystr(path)


# what the stated tolerances refuse: each of these is a cheaper objective that a
# program could run in the right one's place
WRONG = {
    "no entropy term": lambda d: dict(d, entropy_beta=0.0),
    "a walk left out": lambda d: dict(d, total_ut_steps=TRIPS - 1),
}


@pytest.mark.parametrize("what", WRONG)
def test_a_cheaper_objective_fails_the_tolerance(what, reference):
    want, exits, _ = reference
    d, _, w = build()
    model = HybridDecoderModel(HybridDecoderConfig(
        **loop_tree.config_kwargs(WRONG[what](d), attention_impl="xla")))
    tokens, targets = batch()
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(model.loss_fn)(loop_tree.to_program(w), tokens, targets)
    assert abs(float(loss) - want) > 10 * LOSS_TOL * abs(want)


def test_a_gate_without_gradient_and_exits_in_bfloat16_fail_the_tolerance(reference):
    want, exits, g_want = reference
    d, model, w = build()
    tokens, targets = batch()
    p = loop_tree.to_program(w)

    def stopped(p):     # the exit distribution as constants: no gradient through p
        states, _ = model.trip_states(p, tokens)
        log_p = jax.lax.stop_gradient(model.exit_log_probs(p["exit_gate"], states))
        losses = jnp.stack([loop_ref.exit_losses(p["head"]["weight"], x.reshape(ROWS * SEQ, -1),
                                                 targets.reshape(-1), "float32")
                            for x in states])
        return jnp.mean(jnp.sum(jnp.exp(log_p).reshape(TRIPS, -1) * losses, axis=0))

    with jax.default_matmul_precision("highest"):
        g = jax.jit(jax.grad(stopped))(p)
        assert float(jnp.abs(g["exit_gate"]["weight"]).max()) == 0.0
        worst = max(gap(a, b) for a, b in zip(jax.tree.leaves(g["layers"]),
                                              jax.tree.leaves(g_want["layers"])))
        assert worst > 10 * GRAD_TOL             # the trunk misses what flows through p
        # the exits' head and loss over bfloat16 logits
        half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
        _, aux = jax.jit(lambda p: model.loss_fn(p, tokens, targets, return_aux=True))(half)
    off = np.abs(np.asarray(aux["exit_losses"], np.float32) / exits["exit_losses"] - 1)
    assert float(off.max()) > 10 * LOSS_TOL


def test_every_tokens_exit_shares_add_up_to_one():
    d, model, w = build()
    tokens, _ = batch()
    p = loop_tree.to_program(w)
    states, _ = model.trip_states(p, tokens)
    log_p = model.exit_log_probs(p["exit_gate"], states)
    assert log_p.shape == (TRIPS, ROWS, SEQ) and log_p.dtype == jnp.float32
    np.testing.assert_allclose(jnp.exp(log_p).sum(0), 1.0, atol=2e-6)
    # against the products of sigmoids, as the published description writes them
    flat = jnp.stack(states).reshape(TRIPS, ROWS * SEQ, -1)
    want = loop_ref.exit_distribution(w["gate"], flat[:-1], "float32")
    np.testing.assert_allclose(jnp.exp(log_p).reshape(TRIPS, -1), want, rtol=1e-5)
    # a gate driven shut or open leaves finite logarithms
    shut = {"weight": 0 * p["exit_gate"]["weight"], "bias": jnp.asarray([-200.0])}
    assert bool(jnp.isfinite(model.exit_log_probs(shut, states)).all())
    np.testing.assert_allclose(jnp.exp(model.exit_log_probs(shut, states))[-1], 1.0)


def test_four_walks_are_an_unshared_stack_of_copies_and_the_gradient_their_sum():
    d, model, w = build()
    tokens, targets = batch()
    p = loop_tree.to_program(w)

    def unshared(copies):           # walk t on its own copy of every layer and of the norm
        x = copies[0]["embedding"]["weight"][tokens]
        states = []
        for copy in copies:
            x, _ = model.walk(copy, x)
            states.append(x)
        return model._exit_loss(copies[0], states, targets)[0]

    with jax.default_matmul_precision("highest"):
        loss, g = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(p, tokens, targets)))(p)
        loss_copies, g_copies = jax.jit(jax.value_and_grad(unshared))([p] * TRIPS)
    assert float(loss) == float(loss_copies)
    stack = lambda tree: {"layers": tree["layers"], "norm_f": tree["norm_f"]}  # noqa: E731
    summed = jax.tree.map(lambda *a: sum(a), *[stack(c) for c in g_copies])
    for a, b, first in zip(jax.tree.leaves(stack(g)), jax.tree.leaves(summed),
                           jax.tree.leaves(stack(g_copies[0]))):
        assert gap(a, b) <= 1e-5
        assert gap(first, a) > 1e-3              # no one walk's share is the whole


KERNEL_SIZE = dict(SMALL, num_attention_heads=1, num_key_value_heads=1, head_dim=128)


def test_a_looped_stack_keeps_its_blocks_inputs_and_flash_results(monkeypatch):
    """Under ``remat`` a looped stack recomputes a block from its input and
    its flash call's results (``LOOP_SAVED``): of a block-pass the backward
    holds the block's input, the attention's output and its log-sum-exp rows,
    so no flash forward runs twice; of a walk its closing norm's input; and
    not the projections a stack walked once keeps (``MIXER_SAVED``), nor the
    second half's input or a SwiGLU value."""
    d, model, w = build(KERNEL_SIZE, remat=True, attention_impl="pallas")
    tokens, targets = batch()
    p = loop_tree.to_program(w)
    passes = d["num_hidden_layers"] * TRIPS
    grad = lambda: kernel_calls(jax.make_jaxpr(jax.grad(model.loss_fn))(  # noqa: E731
        p, tokens, targets).jaxpr)
    calls = grad()
    assert calls["flash_fwd_bshd"] == passes == 8
    assert sum(c for n, c in calls.items() if n.startswith("flash_bwd")) == passes
    saved = saved_shapes(model.loss_fn, p, tokens, targets)
    # a block's input a pass (the embedding's rows and three walks' outputs
    # among them), a closing norm's input a walk, and the walks' outputs as the
    # gate read them (three of the four that ``_exit_loss``'s barrier hands on,
    # the buffers of the next walks' inputs under another name): no projection's
    # output, which has a stream's shape here, is among them
    assert saved.count((ROWS, SEQ, 128)) == passes + TRIPS + TRIPS - 1, saved
    # of the values in the kernel's layout (q, k, v and the context) the context alone
    assert saved.count((ROWS, SEQ, 1, 128)) == passes
    assert saved.count((ROWS, 1, SEQ)) == passes            # its log-sum-exp rows
    assert saved.count((ROWS * SEQ, 128)) == TRIPS          # the exits' gradients to h^t
    wide = KERNEL_SIZE["intermediate_size"]
    assert not [shape for shape in saved
                if shape[:2] == (ROWS, SEQ) and shape[-1] in (wide, 2 * wide)]
    # the witness: a stack that keeps its blocks' inputs alone runs every flash forward twice
    monkeypatch.setattr(hybrid_decoder, "LOOP_SAVED", hybrid_decoder.EXPERTS_SAVED)
    assert grad()["flash_fwd_bshd"] == 2 * passes
    assert (ROWS, SEQ, 1, 128) not in saved_shapes(model.loss_fn, p, tokens, targets)


def most_logits_alive(jaxpr, tokens, vocab):
    """The most values of (at least ``tokens``, ``vocab``) alive at one point
    of a jaxpr read in program order; an equation that holds jaxprs (a
    checkpoint's body) counts their own most at its place."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    last = {}
    for i, eqn in enumerate(jaxpr.eqns):
        last.update({v: i for v in eqn.invars if not isinstance(v, Literal)})
    last.update({v: len(jaxpr.eqns) for v in jaxpr.outvars if not isinstance(v, Literal)})
    alive, most = set(), 0
    for i, eqn in enumerate(jaxpr.eqns):
        inside = max((most_logits_alive(sub, tokens, vocab)
                      for sub in jax.core.jaxprs_in_params(eqn.params)), default=0)
        alive |= {v for v in eqn.outvars if getattr(v.aval, "shape", ())[-1:] == (vocab,)
                  and v.aval.size >= tokens * vocab}
        most = max(most, len(alive) + inside)
        alive = {v for v in alive if last.get(v, -1) > i}
    return most


def onto_vocabulary(jaxpr, vocab):
    """Contractions of a jaxpr, and of the jaxprs it holds, whose result's
    last axis is the vocabulary: the products that make logits."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    return sum((eqn.primitive.name == "dot_general"
                and eqn.outvars[0].aval.shape[-1:] == (vocab,))
               + sum(onto_vocabulary(sub, vocab) for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def plain_exits(head, states, targets, weights):
    """``weighted_exit_losses`` as the plain composition jax differentiates."""
    losses = jnp.stack([jax.nn.logsumexp(logits, axis=-1)
                        - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
                        for logits in (x @ head.T for x in states)])
    return jnp.sum(weights * losses), jax.lax.stop_gradient(losses)


@pytest.mark.parametrize("remat", [False, True])
def test_no_exits_logits_stand_between_the_passes_or_beside_anothers(remat, monkeypatch):
    """An exit makes its gradient where it makes its loss: nothing of (tokens,
    vocabulary) is kept from the forward pass, in the gradient's jaxpr no more
    such values are alive at a point with four exits than with two, and the
    product onto the vocabulary runs once an exit's block. The plain
    composition keeps every exit's logits until the backward pass, and in a
    checkpoint of its own it makes them twice."""
    wide = dict(SMALL, vocab_size=8192)
    tokens, targets = batch(8192)
    monkeypatch.setattr(hybrid_decoder, "EXIT_BLOCK", SEQ)

    def alive(trips):
        d, model, w = build(dict(wide, total_ut_steps=trips), remat=remat)
        p = loop_tree.to_program(w)
        kept = [shape for shape in saved_shapes(model.loss_fn, p, tokens, targets)
                if shape[-1:] == (8192,) and int(np.prod(shape)) >= SEQ * 8192]
        jaxpr = jax.make_jaxpr(jax.grad(model.loss_fn))(p, tokens, targets)
        return kept, most_logits_alive(jaxpr, SEQ, 8192), onto_vocabulary(jaxpr, 8192)

    kept, four, products = alive(4)
    assert not kept
    assert four == alive(2)[1]
    assert products == 4 * ROWS                  # an exit's block of SEQ tokens a row
    unruled = hybrid_decoder.weighted_exit_losses.fun      # the function without its rule
    monkeypatch.setattr(hybrid_decoder, "weighted_exit_losses", plain_exits)
    kept, unkept, _ = alive(4)
    assert kept and unkept > four
    monkeypatch.setattr(hybrid_decoder, "weighted_exit_losses", jax.checkpoint(unruled))
    kept, _, twice = alive(4)
    assert not kept and twice == 2 * products


# the exits' hand-written rule against jax's own of the plain composition:
# (a loss mask?, EXIT_BLOCK, the sum's cotangent)
EXIT_CASES = {
    "one block": (False, ROWS * SEQ, 1.0),
    "blocks, a mask": (True, SEQ // 2, 1.0),
    "a block that does not divide the tokens": (False, SEQ - 32, 1.0),
    "a loss scale, a mask": (True, SEQ, 1024.0),
    "a cotangent below one": (False, SEQ // 2, -0.37),
}


def exit_inputs(masked):
    """A head, four walks' states, targets and the weights ``p^t share`` as
    ``_exit_loss`` makes them, float32."""
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    tokens, hidden, vocab = ROWS * SEQ, 64, 384
    head = 0.3 * jax.random.normal(keys[0], (vocab, hidden))
    states = tuple(jax.random.normal(k, (tokens, hidden)) for k in jax.random.split(keys[1], TRIPS))
    targets = jax.random.randint(keys[2], (tokens,), 0, vocab)
    p = jax.nn.softmax(jax.random.normal(keys[3], (TRIPS, tokens)), axis=0)
    mask = (jax.random.uniform(keys[4], (tokens,)) < 0.7) if masked else jnp.ones((tokens,))
    return head, states, targets, p * mask / jnp.sum(mask)


def exit_gaps(f, case):
    """The largest gaps of ``f``'s values and gradients to the plain
    composition's, each against the plain one's largest entry."""
    masked, block, ct = EXIT_CASES[case]
    args = exit_inputs(masked)
    with jax.default_matmul_precision("highest"):
        (total, losses), pull = jax.vjp(f, *args)
        (want_total, want_losses), want_pull = jax.vjp(plain_exits, *args)
        cts = (jnp.float32(ct), jnp.zeros_like(losses))
        (d_head, d_states, _, d_w), (w_head, w_states, _, w_w) = pull(cts), want_pull(cts)
    assert d_head.dtype == jnp.float32 and d_states[0].shape == args[1][0].shape
    return {"sum": gap(total, want_total), "losses": gap(losses, want_losses),
            "d head": gap(d_head, w_head), "d w": gap(d_w, w_w),
            "d states": max(gap(a, b) for a, b in zip(d_states, w_states))}


@pytest.mark.parametrize("case", EXIT_CASES)
def test_the_exits_rule_is_the_gradient_of_the_plain_composition(case, monkeypatch):
    monkeypatch.setattr(hybrid_decoder, "EXIT_BLOCK", EXIT_CASES[case][1])
    gaps = exit_gaps(hybrid_decoder.weighted_exit_losses, case)
    assert max(gaps.values()) <= GRAD_TOL, gaps
    assert gaps["sum"] <= LOSS_TOL and gaps["losses"] <= LOSS_TOL, gaps

    # a cheaper rule, the gradient through the weights dropped, is refused
    @jax.custom_vjp
    def cheaper(*args):
        return hybrid_decoder.weighted_exit_losses(*args)

    def bwd(res, cts):
        d_head, d_states, _, d_w = hybrid_decoder._exits_bwd(res, cts)
        return d_head, d_states, None, jnp.zeros_like(d_w)

    cheaper.defvjp(hybrid_decoder._exits_fwd, bwd)
    gaps = exit_gaps(cheaper, case)
    assert gaps.pop("d w") > 0.5 and max(gaps.values()) <= GRAD_TOL, gaps


def test_a_masked_looped_loss_is_the_masked_mean_of_the_plain_objective():
    """``loss_fn(loss_mask=)`` of a looped stack against the objective written
    out: the masked mean over tokens of ``sum_t p^t l^t + coeff sum_t p^t log
    p^t``, the loss and every gradient leaf."""
    d, model, w = build()
    tokens, targets = batch()
    p = loop_tree.to_program(w)
    mask = jax.random.uniform(jax.random.PRNGKey(5), (ROWS, SEQ)) < 0.6

    def plain(p):
        states, _ = model.trip_states(p, tokens)
        log_p = model.exit_log_probs(p["exit_gate"], states)
        losses = jnp.stack([loop_ref.exit_losses(p["head"]["weight"], x.reshape(ROWS * SEQ, -1),
                                                 targets.reshape(-1), "float32")
                            for x in states]).reshape(log_p.shape)
        per_token = jnp.sum(jnp.exp(log_p) * (losses + d["entropy_beta"] * log_p), axis=0)
        return jnp.sum(per_token * mask) / jnp.sum(mask)

    with jax.default_matmul_precision("highest"):
        loss, g = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, tokens, targets, loss_mask=mask)))(p)
        want, g_want = jax.jit(jax.value_and_grad(plain))(p)
        unmasked = jax.jit(model.loss_fn)(p, tokens, targets)
    assert abs(float(loss) - float(want)) <= LOSS_TOL * float(want)
    assert abs(float(unmasked) - float(want)) > 10 * LOSS_TOL * float(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0], jax.tree.leaves(g_want)):
        assert gap(a, b) <= GRAD_TOL, jax.tree_util.keystr(path)


def test_an_exit_takes_its_tokens_a_block_at_a_time(monkeypatch):
    """With more tokens than ``EXIT_BLOCK`` an exit's logits are made a block
    of tokens at a time, and the loss and every gradient are those of the
    exit taken whole."""
    d, model, w = build()
    tokens, targets = batch()
    p = loop_tree.to_program(w)
    step = lambda: jax.jit(jax.value_and_grad(lambda p: model.loss_fn(  # noqa: E731
        p, tokens, targets, return_aux=True), has_aux=True))(p)
    with jax.default_matmul_precision("highest"):
        (want, aux_want), g_want = step()
        monkeypatch.setattr(hybrid_decoder, "EXIT_BLOCK", SEQ // 2)
        jaxpr = jax.make_jaxpr(jax.grad(model.loss_fn))(p, tokens, targets)
        assert most_logits_alive(jaxpr, ROWS * SEQ, SMALL["vocab_size"]) == 0
        assert most_logits_alive(jaxpr, SEQ // 2, SMALL["vocab_size"]) > 0
        (loss, aux), g = step()
    assert abs(float(loss) - float(want)) <= LOSS_TOL * float(want)
    np.testing.assert_allclose(aux["exit_losses"], aux_want["exit_losses"], rtol=LOSS_TOL)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_want)):
        assert gap(a, b) <= GRAD_TOL


def test_the_spans_of_a_looped_step():
    d, model, w = build()
    tokens, targets = batch()
    text = jax.jit(jax.grad(model.loss_fn)).lower(
        loop_tree.to_program(w), tokens, targets).as_text(debug_info=True)
    for span in ("hybrid/embed", "hybrid/attn", "hybrid/dense", "hybrid/unembed_xent",
                 "hybrid/exit"):
        assert span in text, span
    assert "hybrid/moe" not in text


# --- a stack walked once is the program it was ----------------------------------

def hybrid_toy(**settings):
    """One hybrid toy configuration: a delta-rule and two gated attention
    layers, two of them over experts (a half used twice shares its trace),
    recomputed (as ``q3next-train-8k`` runs them)."""
    return HybridDecoderModel(HybridDecoderConfig(
        vocab_size=256, hidden_size=128, layer_types=("linear", "full", "full"), num_heads=2,
        num_kv_heads=1, head_dim=64, rotary_dim=32, linear_key_heads=1, linear_value_heads=2,
        linear_key_dim=64, linear_value_dim=64, router_experts=8, top_k=2, expert_ffn=128,
        shared_ffn=128, ffn_types=("moe", "dense", "moe"), dense_ffn=128, remat=True,
        attention_impl="xla", delta_impl="xla", experts_impl="xla", **settings))


def test_one_walk_without_the_entropy_term_is_todays_loss_bit_for_bit():
    model = hybrid_toy()
    assert (model.config.loop_trips, model.config.exit_entropy_coeff) == (1, 0.0)
    assert model.config == hybrid_toy(loop_trips=1, exit_entropy_coeff=0.0).config
    params = model.init(jax.random.PRNGKey(0))
    assert "exit_gate" not in params
    tokens = jax.random.randint(jax.random.PRNGKey(1), (ROWS, SEQ), 0, 256)
    from apex_tpu.transformer import tensor_parallel as tp_lib

    def todays(p):      # the loss as it was written before the stack could loop
        x, aux = model.hidden_states_with_aux(p, tokens)
        losses = tp_lib.vocab_parallel_cross_entropy(model.unembed(p, x), tokens, axis_name=None)
        return tp_lib.masked_mean(losses) + model.config.aux_coeff * aux["load_balance_loss"]

    loss, g = jax.jit(jax.value_and_grad(model.loss_fn))(params, tokens, tokens)
    loss_todays, g_todays = jax.jit(jax.value_and_grad(todays))(params)
    assert float(loss) == float(loss_todays)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_todays)):
        np.testing.assert_array_equal(a, b)
    states, aux = jax.eval_shape(model.trip_states, params, tokens)
    assert len(states) == 1 and set(aux) == {"load_balance_loss", "expert_load",
                                             "router_counts", "dropped"}


# sha256 of the text the toy's gradient lowers to at the parent commit (PR 39,
# a5562be: ``python tests/test_loop_decoder.py`` there prints it), this
# installation's jax: a stack walked once lowers to what it lowered to before
# ``loop_trips`` existed
PARENT_LOWERED = "618fe29dd76ddbb93e8ad7f109ebedb14a30bffad465cc66b2ef1e0bd16ad437"


def lowered_hash():
    model = hybrid_toy()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((ROWS, SEQ), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, a, b: model.loss_fn(p, a, b, return_aux=True), has_aux=True)).lower(
            params, tokens, tokens).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_one_walk_lowers_to_the_parents_text():
    assert lowered_hash() == PARENT_LOWERED


if __name__ == "__main__":
    print(lowered_hash())
