"""apexcheck tests: the jaxpr walker, the JXP contract library, the
entrypoint registry + tier-1 gate, StaticCostReport exactness, and the
predicted-vs-calibrated CostDB diff.

One positive + one negative TRACED fixture per JXP code (the jaxpr
analog of test_lint's per-rule source fixtures), walker descent through
all five higher-order primitives, hand-computed static-cost numbers, the
kind×axis parity acceptance against ``monitor.count_collective``, and
the CLI exit-code / artifact / baseline behavior of
``python -m apex_tpu.lint --jaxpr``.
"""

import json
import os

import jax
import jax.numpy as jnp
import jax.random as jr
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import monitor
from apex_tpu.lint import contracts as jc
from apex_tpu.lint import entrypoints as eps
from apex_tpu.lint import jaxpr_check as jx
from apex_tpu.lint.__main__ import main as lint_main
from apex_tpu.parallel import mesh as mesh_lib

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

K = jr.PRNGKey(3)


def _tp_mesh(n=4):
    return mesh_lib.make_mesh(tensor_model_parallel_size=n)


# --- the walker ---------------------------------------------------------------

class TestWalker:
    def _nested_program(self):
        """One program threading all five higher-order primitives the
        ISSUE names: jit, scan, while, cond, custom_vjp — inside a
        shard_map."""

        @jax.custom_vjp
        def cv(x):
            return x * 2

        cv.defvjp(lambda x: (cv(x), x), lambda r, g: (g * 2,))

        mesh = _tp_mesh()

        def scan_body(c, x):
            return c + jax.lax.psum(x, "tp").sum(), c

        def inner(x):
            c, _ = jax.lax.scan(scan_body, jnp.float32(0), x)
            c = jax.lax.while_loop(lambda v: v < 3, lambda v: v + 1, c)
            c = jax.lax.cond(c > 1, lambda v: v + 1, lambda v: v - 1, c)
            return c + cv(x).sum()

        sm = mesh_lib.shard_map(inner, mesh=mesh,
                                in_specs=(P(None, "tp"),), out_specs=P())
        return jax.jit(sm), (jnp.zeros((5, 8)),)

    def test_descends_all_five_higher_order_primitives(self):
        fn, args = self._nested_program()
        closed = jax.make_jaxpr(fn)(*args)
        sites = list(jx.iter_sites(closed))
        prims = {s.prim for s in sites}
        for prim in ("jit", "scan", "while", "cond",
                     "custom_vjp_call", "shard_map"):
            assert prim in prims, f"walker never saw {prim}"
        # eqns INSIDE each higher-order body were visited: their paths
        # carry the enclosing segment
        paths = {s.path for s in sites}
        for seg in ("scan:5", "while", "cond", "custom_vjp_call",
                    "shard_map"):
            assert any(seg in p for p in paths), (
                f"no site under {seg}: {sorted(paths)}")

    def test_scan_multiplier_and_while_bound(self):
        fn, args = self._nested_program()
        closed = jax.make_jaxpr(fn)(*args)
        psums = [s for s in jx.iter_sites(closed) if s.prim == "psum"]
        assert len(psums) == 1
        assert psums[0].mult == 5           # executes once per scan tick
        assert psums[0].bounded             # a scan is statically bounded
        under_while = [s for s in jx.iter_sites(closed)
                       if "while" in s.path]
        assert under_while and all(not s.bounded for s in under_while)

    def test_scan_lengths_helper(self):
        def f(xs):
            def body(c, x):
                return c + x, c
            c, _ = jax.lax.scan(body, jnp.float32(0), xs[:4])
            c2, _ = jax.lax.scan(body, c, xs)
            return c2

        lengths = jx.scan_lengths(jax.make_jaxpr(f)(jnp.zeros((6,))))
        assert sorted(lengths) == [4, 6]

    def test_as_jaxpr_rejects_non_jaxpr(self):
        with pytest.raises(TypeError, match="not a jaxpr"):
            jx.as_jaxpr(42)


# --- one positive + one negative traced fixture per JXP code ------------------

class TestContractFixtures:
    # JXP101 / JXP102 ---------------------------------------------------------
    def _two_scan_jaxpr(self):
        def f(xs):
            def body(c, x):
                return c + x, c
            c, _ = jax.lax.scan(body, jnp.float32(0), xs[:4])
            c2, _ = jax.lax.scan(body, c, xs)
            return c2

        return jax.make_jaxpr(f)(jnp.zeros((6,)))

    def test_jxp101_scan_count(self):
        closed = self._two_scan_jaxpr()
        assert jc.check_jaxpr(closed, [jc.scan_count(2)]) == []
        bad = jc.check_jaxpr(closed, [jc.scan_count(3)])
        assert [f.code for f in bad] == ["JXP101"]
        assert jc.check_jaxpr(closed, [jc.scan_count(min_count=1,
                                                     max_count=2)]) == []
        assert jc.check_jaxpr(closed, [jc.scan_count(max_count=1)])

    def test_jxp102_scan_length(self):
        closed = self._two_scan_jaxpr()
        assert jc.check_jaxpr(closed, [jc.scan_length(4),
                                       jc.scan_length(6)]) == []
        missing = jc.check_jaxpr(closed, [jc.scan_length(7)])
        assert [f.code for f in missing] == ["JXP102"]
        assert "lengths present: [4, 6]" in missing[0].message
        forbidden = jc.check_jaxpr(closed, [jc.scan_length(4, forbid=True)])
        assert [f.code for f in forbidden] == ["JXP102"]
        assert jc.check_jaxpr(closed,
                              [jc.scan_length(7, forbid=True)]) == []

    # JXP201 ------------------------------------------------------------------
    def test_jxp201_use_after_donate(self):
        donating = jax.jit(lambda x: x * 2, donate_argnums=0)

        def bad(x):
            y = donating(x)
            return y + x          # x's buffer may already be y's

        def good(x):
            y = donating(x)
            return y + 1.0

        x = jnp.zeros((4,))
        findings = jc.check_jaxpr(jax.make_jaxpr(bad)(x),
                                  [jc.donation_honored()])
        assert findings and all(f.code == "JXP201" for f in findings)
        assert jc.check_jaxpr(jax.make_jaxpr(good)(x),
                              [jc.donation_honored()]) == []

    def test_jxp201_donated_value_returned(self):
        donating = jax.jit(lambda x: x * 2, donate_argnums=0)

        def bad(x):
            y = donating(x)
            return y, x           # the dead buffer escapes to the caller

        findings = jc.check_jaxpr(jax.make_jaxpr(bad)(jnp.zeros((4,))),
                                  [jc.donation_honored()])
        assert any("returned" in f.message for f in findings)

    # JXP202 ------------------------------------------------------------------
    def test_jxp202_donated_not_rebound(self):
        bad_fn = jax.jit(lambda x: jnp.sum(x), donate_argnums=0)
        good_fn = jax.jit(lambda x: x * 2, donate_argnums=0)
        x = jnp.zeros((4,))
        findings = jc.check_jaxpr(jax.make_jaxpr(bad_fn)(x),
                                  [jc.donation_rebound()])
        assert [f.code for f in findings] == ["JXP202"]
        assert "no matching-aval output" in findings[0].message
        assert jc.check_jaxpr(jax.make_jaxpr(good_fn)(x),
                              [jc.donation_rebound()]) == []

    # JXP301 ------------------------------------------------------------------
    def test_jxp301_no_aval_matching(self):
        s = 64
        q = jnp.zeros((s, 8))
        contract = jc.no_aval_matching(
            lambda shape: sum(1 for d in shape if d >= s) >= 2,
            "two dims >= seq")

        def bad(q, k):
            scores = q @ k.T          # (s, s): the materialized score
            return jax.nn.softmax(scores, axis=-1).sum()

        def good(q, k):
            return jnp.sum(q * k)     # never forms the (s, s) tensor

        findings = jc.check_jaxpr(jax.make_jaxpr(bad)(q, q), [contract])
        assert findings and all(f.code == "JXP301" for f in findings)
        assert f"[{s}, {s}]" in findings[0].message
        assert jc.check_jaxpr(jax.make_jaxpr(good)(q, q), [contract]) == []

    # JXP401 / JXP402 ---------------------------------------------------------
    def _collective_jaxpr(self, use_gather):
        mesh = _tp_mesh()

        def gathered(x):
            return jax.lax.all_gather(x, "tp").sum()

        def ringed(x):
            perm = [(i, (i + 1) % 4) for i in range(4)]
            return jax.lax.ppermute(x, "tp", perm).sum()

        sm = mesh_lib.shard_map(gathered if use_gather else ringed,
                                mesh=mesh, in_specs=(P("tp"),),
                                out_specs=P())
        return jax.make_jaxpr(sm)(jnp.zeros((8, 4)))

    def test_jxp401_no_full_width_all_gather(self):
        contract = jc.no_full_width_all_gather("tp")
        findings = jc.check_jaxpr(self._collective_jaxpr(True), [contract])
        assert [f.code for f in findings] == ["JXP401"]
        assert jc.check_jaxpr(self._collective_jaxpr(False),
                              [contract]) == []

    def test_jxp401_other_axis_clean(self):
        # a gather on ANOTHER axis does not violate the tp contract
        findings = jc.check_jaxpr(self._collective_jaxpr(True),
                                  [jc.no_full_width_all_gather("dp")])
        assert findings == []

    def test_jxp402_ppermute_present(self):
        contract = jc.ppermute_present("tp")
        assert jc.check_jaxpr(self._collective_jaxpr(False),
                              [contract]) == []
        findings = jc.check_jaxpr(self._collective_jaxpr(True), [contract])
        assert [f.code for f in findings] == ["JXP402"]

    # JXP403 ------------------------------------------------------------------
    def test_jxp403_collective_free_region(self):
        mesh = _tp_mesh()

        def body(c, x):
            return c + jax.lax.psum(x, "tp").sum(), c

        def inner(x):
            c, _ = jax.lax.scan(body, jnp.float32(0), x)
            return c

        sm = mesh_lib.shard_map(inner, mesh=mesh,
                                in_specs=(P(None, "tp"),), out_specs=P())
        closed = jax.make_jaxpr(sm)(jnp.zeros((4, 8)))
        dirty = jc.check_jaxpr(
            closed, [jc.collective_free_region(r"(^|/)scan:4(/|$)",
                                               region="scan body")])
        assert dirty and all(f.code == "JXP403" for f in dirty)
        assert "psum" in dirty[0].message

        def clean_inner(x):
            def body2(c, v):
                return c + v.sum(), c
            c, _ = jax.lax.scan(body2, jnp.float32(0), x)
            return jax.lax.psum(c, "tp")  # collective OUTSIDE the region

        sm2 = mesh_lib.shard_map(clean_inner, mesh=mesh,
                                 in_specs=(P(None, "tp"),), out_specs=P())
        closed2 = jax.make_jaxpr(sm2)(jnp.zeros((4, 8)))
        assert jc.check_jaxpr(
            closed2, [jc.collective_free_region(r"(^|/)scan:4(/|$)",
                                                region="scan body")]) == []

    def test_jxp403_missing_region_is_a_violation(self):
        closed = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros((4,)))
        findings = jc.check_jaxpr(
            closed, [jc.collective_free_region(r"scan:99",
                                               region="nonexistent")])
        assert [f.code for f in findings] == ["JXP403"]
        assert "does not exist" in findings[0].message

    # JXP501 ------------------------------------------------------------------
    def _accum_jaxpr(self, dtype):
        def f(xs):
            def body(c, x):
                return c + x, ()
            c, _ = jax.lax.scan(body, jnp.zeros((4,), dtype), xs)
            return c

        return jax.make_jaxpr(f)(jnp.zeros((6, 4), dtype))

    def test_jxp501_fp32_accumulation(self):
        contract = jc.fp32_accumulation()
        findings = jc.check_jaxpr(self._accum_jaxpr(jnp.bfloat16),
                                  [contract])
        assert [f.code for f in findings] == ["JXP501"]
        assert "bfloat16" in findings[0].message
        assert jc.check_jaxpr(self._accum_jaxpr(jnp.float32),
                              [contract]) == []

    def test_jxp501_threaded_bf16_carry_clean(self):
        # a bf16 carry that is merely threaded (not add-accumulated)
        def f(xs):
            def body(c, x):
                return jnp.minimum(c, x), c
            c, _ = jax.lax.scan(body, jnp.zeros((4,), jnp.bfloat16), xs)
            return c

        closed = jax.make_jaxpr(f)(jnp.zeros((6, 4), jnp.bfloat16))
        assert jc.check_jaxpr(closed, [jc.fp32_accumulation()]) == []

    # assert_contracts --------------------------------------------------------
    def test_assert_contracts_raises_with_rendered_findings(self):
        closed = self._two_scan_jaxpr()
        with pytest.raises(AssertionError, match="JXP102"):
            jc.assert_contracts(closed, [jc.scan_length(99)])
        jc.assert_contracts(closed, [jc.scan_length(4)])  # no raise


# --- StaticCostReport ---------------------------------------------------------

class TestStaticCost:
    def _fixture(self):
        """Two collectives + one GEMM with hand-computable numbers:
        per-shard x is (4, 8) fp32 (128 B), w is (8, 16) fp32;
        dot (4,8)@(8,16) = 2*4*8*16 = 1024 FLOPs; psum moves the
        (4, 16) fp32 product (256 B); ppermute moves x (128 B)."""
        mesh = _tp_mesh()

        def body(x, w):
            h = x @ w                              # 1024 FLOPs
            red = jax.lax.psum(h, "tp")            # 256 B over tp
            perm = [(i, (i + 1) % 4) for i in range(4)]
            nxt = jax.lax.ppermute(x, "tp", perm)  # 128 B over tp
            return red.sum() + nxt.sum()

        sm = mesh_lib.shard_map(body, mesh=mesh,
                                in_specs=(P("tp"), P()), out_specs=P())
        return jax.make_jaxpr(sm)(jnp.zeros((16, 8)), jnp.zeros((8, 16)))

    def test_exact_bytes_and_flops(self):
        cost = jx.static_cost(self._fixture(), entrypoint="fixture")
        assert cost["kind"] == "static_cost"
        assert cost["entrypoint"] == "fixture"
        assert cost["collectives"]["psum[tp]"] == {"calls": 1, "bytes": 256}
        assert cost["collectives"]["ppermute[tp]"] == {"calls": 1,
                                                       "bytes": 128}
        assert cost["gemms"]["flops_1024"] == {"calls": 1, "flops": 1024.0}
        assert cost["total_collective_bytes"] == 384
        assert cost["total_gemm_flops"] == 1024.0
        assert cost["unbounded_sites"] == 0

    def test_scan_multiplies_calls_and_bytes(self):
        mesh = _tp_mesh()

        def inner(xs):
            def body(c, x):
                return c + jax.lax.psum(x, "tp").sum(), ()
            c, _ = jax.lax.scan(body, jnp.float32(0), xs)
            return c

        sm = mesh_lib.shard_map(inner, mesh=mesh,
                                in_specs=(P(None, "tp"),), out_specs=P())
        # per-shard per-tick payload: (2,) fp32 = 8 B; 3 ticks
        cost = jx.static_cost(jax.make_jaxpr(sm)(jnp.zeros((3, 8))))
        assert cost["collectives"]["psum[tp]"] == {"calls": 3, "bytes": 24}

    def test_cond_branches_are_alternatives_not_summed(self):
        """Exactly one cond branch executes per call: the report takes
        the per-key field-wise MAX over branches — a program whose both
        branches hold one 32 B ppermute predicts 32 B, not 64."""
        mesh = _tp_mesh()
        perm = [(i, (i + 1) % 4) for i in range(4)]

        def send_small(x):
            return jax.lax.ppermute(x[:2], "tp", perm).sum()

        def send_big(x):
            return jax.lax.ppermute(x, "tp", perm).sum()

        def inner(pred, x):
            return jax.lax.cond(pred, send_big, send_small, x)

        sm = mesh_lib.shard_map(inner, mesh=mesh,
                                in_specs=(P(), P(None, "tp")),
                                out_specs=P())
        cost = jx.static_cost(
            jax.make_jaxpr(sm)(jnp.bool_(True), jnp.zeros((4, 8))))
        # per-shard payloads: big (4, 2) f32 = 32 B, small (2, 2) = 16 B
        assert cost["collectives"]["ppermute[tp]"] == {"calls": 1,
                                                       "bytes": 32}

    def test_cond_branch_adds_to_same_key_outside_the_cond(self):
        """A key that occurs both OUTSIDE and INSIDE the cond sums the
        unconditional cost with the max-over-branches cost — the branch
        alternative is never absorbed by (nor absorbs) the parent's
        running total."""
        mesh = _tp_mesh()
        perm = [(i, (i + 1) % 4) for i in range(4)]

        def branch(x):
            return jax.lax.ppermute(x, "tp", perm).sum()

        def inner(pred, x):
            unconditional = jax.lax.ppermute(x, "tp", perm).sum()
            return unconditional + jax.lax.cond(pred, branch, branch, x)

        sm = mesh_lib.shard_map(inner, mesh=mesh,
                                in_specs=(P(), P(None, "tp")),
                                out_specs=P())
        cost = jx.static_cost(
            jax.make_jaxpr(sm)(jnp.bool_(True), jnp.zeros((4, 8))))
        # per-shard payload (4, 2) f32 = 32 B: 1 unconditional + 1 branch
        assert cost["collectives"]["ppermute[tp]"] == {"calls": 2,
                                                       "bytes": 64}

    def test_gemm_under_while_is_flagged_unbounded(self):
        """The 'flagged, never silently priced' invariant covers GEMMs
        too: a dot inside a while body lands in unbounded_sites."""
        def f(x, w):
            def body(carry):
                i, acc = carry
                return i + 1, acc + x @ w
            _, acc = jax.lax.while_loop(lambda c: c[0] < 3, body,
                                        (0, jnp.zeros((4, 16))))
            return acc.sum()

        cost = jx.static_cost(
            jax.make_jaxpr(f)(jnp.zeros((4, 8)), jnp.zeros((8, 16))))
        assert cost["gemms"]  # the dot was priced (once)...
        assert cost["unbounded_sites"] >= 1  # ...and flagged

    def test_bucket_parity_with_calibrate(self):
        from apex_tpu.prof.calibrate import size_bucket
        for v in (1, 1.5, 2, 3, 1023, 1024, 1025, 7.3e9):
            assert jx.pow2_floor(v) == size_bucket(v), v

    def test_artifact_schema_valid(self):
        from apex_tpu.monitor import schema
        cost = jx.static_cost(self._fixture(), entrypoint="fixture")
        assert schema.validate(cost) == []

    def test_schema_rejects_junk_and_wrong_kind(self):
        from apex_tpu.monitor import schema
        cost = jx.static_cost(self._fixture(), entrypoint="fixture")
        junk = json.loads(json.dumps(cost))
        junk["collectives"]["psum[tp]"]["vibes"] = 1
        assert schema.validate(junk)
        wrong = json.loads(json.dumps(cost))
        wrong["kind"] = "costdb"
        assert schema.validate(wrong)  # costdb schema rejects this shape
        missing = json.loads(json.dumps(cost))
        del missing["entrypoint"]
        assert any("entrypoint" in e for e in schema.validate(missing))


class TestCountCollectiveParity:
    """The acceptance criterion: the static walker enumerates every
    collective ``count_collective`` sees — the single-axis kind×axis
    key sets are EQUAL, with bytes agreeing EXACTLY on the
    forward-only program (the hooks count ``tree_bytes(payload)`` at
    trace time; the walker reads the same avals off the jaxpr). On the
    fwd+bwd program the walker additionally sees each collective's
    autodiff TRANSPOSE (an all_gather's backward is a reduce_scatter of
    the gathered cotangent), which the hooks deliberately do not
    instrument — there, counted is a byte-wise lower bound of static.
    Composite-axis keys (shard_map's replication psums over the unused
    mesh axes) stay out of the single-axis namespace by construction."""

    @staticmethod
    def _trace_counted(grad):
        from apex_tpu.lint.entrypoints import _collective_matmul_chain

        fn, args = _collective_matmul_chain(overlap=False, grad=grad)
        reg = monitor.enable()
        try:
            closed = jax.make_jaxpr(fn)(*args)  # hooks fire during trace
            counted = {
                name[len("collective/"):-len("_bytes")]: v
                for name, v in reg.counters.items()
                if name.startswith("collective/")
                and name.endswith("_bytes")}
        finally:
            monitor.disable()
        static = {
            key: ent for key, ent in
            jx.static_cost(closed)["collectives"].items()
            if "," not in key}
        return counted, static

    def test_forward_counters_match_static_exactly(self):
        counted, static = self._trace_counted(grad=False)
        assert counted, "the blocking chain counted no collectives"
        assert set(static) == set(counted), (
            f"static {sorted(static)} != counted {sorted(counted)}")
        for key, counted_bytes in counted.items():
            assert static[key]["bytes"] == counted_bytes, (
                f"{key}: static {static[key]['bytes']} != "
                f"counted {counted_bytes}")

    def test_fwd_bwd_static_covers_counters_plus_transposes(self):
        counted, static = self._trace_counted(grad=True)
        assert set(static) == set(counted)
        for key, counted_bytes in counted.items():
            # fwd site counted once; the walker also sees its transpose
            assert static[key]["bytes"] >= counted_bytes, key
            assert static[key]["bytes"] <= 3 * counted_bytes, (
                f"{key}: static {static[key]['bytes']} is not "
                f"fwd+transpose-shaped vs counted {counted_bytes}")

    def test_ring_static_cost_sees_the_hops(self):
        closed = eps.trace("collective_matmul_ring")
        cost = jx.static_cost(closed)
        ring = cost["collectives"]["ppermute[tp]"]
        assert ring["calls"] > 0 and ring["bytes"] > 0
        assert not any(k.startswith("all_gather") for k in
                       cost["collectives"])


# --- entrypoint registry + the tier-1 gate ------------------------------------

class TestEntrypoints:
    def test_flagship_surfaces_registered(self):
        names = eps.names()
        assert "gpt_fwd_bwd" in names
        assert "collective_matmul_ring" in names
        assert "flash_bias_fwd_bwd" in names
        assert {"serve_prefill", "serve_decode"} <= set(names)
        for schedule in ("1f1b", "interleaved", "zb"):
            assert f"pipeline_{schedule}" in names
            assert f"pipeline_{schedule}_overlap" in names

    def test_get_unknown_raises(self):
        with pytest.raises(KeyError, match="registered"):
            eps.get("nope")

    def test_every_entrypoint_declares_contracts(self):
        for name in eps.names():
            contracts = eps.get(name).contracts()
            assert contracts, f"{name} declares no contracts"
            for c in contracts:
                assert c.code.startswith("JXP")


class TestJaxprGate:
    """Tier-1: `python -m apex_tpu.lint --jaxpr` over every registered
    entrypoint is CLEAN (or reason-carrying baselined) — the merge
    acceptance. Run in-process for the same wall-clock reason as the
    AST dogfood gate."""

    def test_all_entrypoints_clean_through_real_cli(self, capsys):
        rc = lint_main(["--jaxpr", "--format", "json"])
        out = capsys.readouterr().out
        assert rc == 0, f"jaxpr contract violations:\n{out}"
        report = json.loads(out)
        from apex_tpu import lint
        assert lint.validate_report(report) == []
        assert report["mode"] == "jaxpr"
        assert report["findings"] == []
        assert report["files_scanned"] == len(eps.names())

    def test_all_entrypoints_within_checked_in_memory_budgets(self,
                                                              capsys):
        """The apexmem tier-1 acceptance: every registered entrypoint's
        donation-aware liveness peak stays under its checked-in budget
        (tools/memory_budgets.json) through the real CLI — a CLEAN
        verdict per entrypoint, exit 0. A new entrypoint without a
        budget entry, or a peak regression past its budget, fails here
        as a JXP601 finding."""
        rc = lint_main(["--jaxpr", "--memory", "--budget-file",
                        os.path.join(REPO, "tools",
                                     "memory_budgets.json"),
                        "--format", "json"])
        out = capsys.readouterr().out
        assert rc == 0, f"memory budget violations:\n{out}"
        report = json.loads(out)
        assert report["findings"] == []
        mems = report["memory"]
        assert len(mems) == len(eps.names())
        for m in mems:
            assert m["verdict"] == "CLEAN", m
            assert m["peak_bytes"] <= m["budget_bytes"]
            assert sum(m["families"].values()) == m["peak_bytes"]

    def test_single_entrypoint_selection(self, capsys):
        rc = lint_main(["--jaxpr", "--entrypoint", "pipeline_zb",
                        "--format", "json"])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert report["entrypoints"] == ["pipeline_zb"]

    def test_violation_exits_1(self, capsys, monkeypatch):
        """A deliberately impossible contract on a real entrypoint must
        surface as findings + exit 1 through the full CLI path."""
        ep = eps.get("pipeline_zb")
        bad = eps.EntryPoint(
            ep.name, ep.description, ep.build,
            lambda: [jc.scan_length(123456)])
        monkeypatch.setitem(eps.REGISTRY, "pipeline_zb", bad)
        rc = lint_main(["--jaxpr", "--entrypoint", "pipeline_zb"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "JXP102" in out and "jaxpr:pipeline_zb" in out

    def test_unknown_entrypoint_exits_2(self, capsys):
        rc = lint_main(["--jaxpr", "--entrypoint", "nope"])
        assert rc == 2
        assert "registered:" in capsys.readouterr().err

    def test_paths_with_jaxpr_exits_2(self, capsys):
        rc = lint_main(["--jaxpr", "apex_tpu/"])
        assert rc == 2

    def test_baseline_suppresses_jaxpr_finding(self, tmp_path, capsys,
                                               monkeypatch):
        ep = eps.get("pipeline_zb")
        bad = eps.EntryPoint(ep.name, ep.description, ep.build,
                             lambda: [jc.scan_length(123456)])
        monkeypatch.setitem(eps.REGISTRY, "pipeline_zb", bad)
        baseline = tmp_path / "baseline.json"
        baseline.write_text(json.dumps({"version": 1, "entries": [
            {"path": "jaxpr:pipeline_zb", "code": "JXP102",
             "reason": "fixture: deliberately impossible geometry"}]}))
        rc = lint_main(["--jaxpr", "--entrypoint", "pipeline_zb",
                        "--baseline", str(baseline), "--format", "json"])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert report["findings"] == []
        assert report["suppressed_baseline"] == 1

    def test_list_entrypoints(self, capsys):
        rc = lint_main(["--list-entrypoints"])
        out = capsys.readouterr().out
        assert rc == 0
        for name in eps.names():
            assert name in out
        assert "JXP" in out  # contracts listed per entrypoint


# --- the static-cost artifact through the CLI + validator ---------------------

class TestStaticCostArtifact:
    def test_cli_writes_valid_jsonl(self, tmp_path, capsys):
        out_path = tmp_path / "static_cost.jsonl"
        rc = lint_main(["--jaxpr", "--entrypoint", "collective_matmul_ring",
                        "--entrypoint", "pipeline_zb",
                        "--static-cost", str(out_path), "--format", "json"])
        capsys.readouterr()
        assert rc == 0
        lines = [json.loads(l) for l in
                 out_path.read_text().splitlines() if l.strip()]
        assert [r["entrypoint"] for r in lines] == [
            "collective_matmul_ring", "pipeline_zb"]
        from apex_tpu.monitor import schema
        for record in lines:
            assert schema.validate(record) == []
        zb = lines[1]
        assert "ppermute[pp]" in zb["collectives"]

    def test_validate_metrics_static_cost_dispatch(self, tmp_path,
                                                   capsys):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "validate_metrics", os.path.join(REPO, "tools",
                                             "validate_metrics.py"))
        vm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(vm)

        cost = jx.static_cost(
            eps.trace("pipeline_zb"), entrypoint="pipeline_zb")
        good = tmp_path / "ok.jsonl"
        good.write_text(json.dumps(cost) + "\n")
        assert vm.main(["--static-cost", str(good)]) == 0
        capsys.readouterr()

        # drift: a record that lost its kind must FAIL as a bad
        # static_cost, not pass as an unrecognized shape
        bad_kind = dict(cost)
        bad_kind.pop("kind")
        nokind = tmp_path / "nokind.json"
        nokind.write_text(json.dumps(bad_kind))
        assert vm.main(["--static-cost", str(nokind)]) == 1
        capsys.readouterr()

        # drift: junk keys inside a collectives row fail
        junk = json.loads(json.dumps(cost))
        junk["collectives"]["ppermute[pp]"]["vibes"] = 1
        junky = tmp_path / "junk.jsonl"
        junky.write_text(json.dumps(junk) + "\n")
        assert vm.main(["--static-cost", str(junky)]) == 1
        capsys.readouterr()

        # drift: a costdb artifact forced as static_cost fails
        db = tmp_path / "costdb.json"
        db.write_text(json.dumps({"schema": 1, "kind": "costdb",
                                  "collectives": {}, "gemms": {}}))
        assert vm.main(["--static-cost", str(db)]) == 1
        capsys.readouterr()

    def test_content_dispatch_without_flag(self, tmp_path, capsys):
        """A .jsonl stream containing static_cost records validates
        through the plain (unforced) path — content dispatch on kind."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "validate_metrics2", os.path.join(REPO, "tools",
                                              "validate_metrics.py"))
        vm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(vm)
        cost = jx.static_cost(
            eps.trace("serve_decode"), entrypoint="serve_decode")
        stream = tmp_path / "stream.jsonl"
        stream.write_text(json.dumps(cost) + "\n")
        assert vm.main([str(stream)]) == 0
        capsys.readouterr()


# --- predicted-vs-calibrated CostDB diff --------------------------------------

def _fake_costdb():
    stat = {"n": 4, "mean": 8e9, "min": 7e9, "max": 9e9,
            "spread_pct": 28.6}
    return {
        "schema": 1, "kind": "costdb", "source": "spans",
        "collectives": {
            "ppermute[pp]": [
                {"bucket_bytes": 128,
                 "bytes": {"n": 4, "mean": 128.0, "min": 128.0,
                           "max": 128.0, "spread_pct": 0.0},
                 "bytes_per_s": stat},
                {"bucket_bytes": 1024,
                 "bytes": {"n": 4, "mean": 1500.0, "min": 1500.0,
                           "max": 1500.0, "spread_pct": 0.0},
                 "bytes_per_s": {**stat, "mean": 16e9}}]},
        "gemms": {"flops_16384": {
            "flops_per_s": {"n": 3, "mean": 1e12, "min": 9e11,
                            "max": 1.1e12, "spread_pct": 22.0},
            "predicted_flops_per_s": None}},
        "predicted_flops_per_s": None,
    }


class TestCostdbDiff:
    def test_diff_covers_and_flags(self):
        from apex_tpu.prof.calibrate import diff_static_cost
        static = {
            "schema": 1, "kind": "static_cost", "entrypoint": "x",
            "collectives": {
                "ppermute[pp]": {"calls": 9, "bytes": 9 * 160},
                "psum[tp]": {"calls": 2, "bytes": 512}},
            "gemms": {"flops_16384": {"calls": 3, "flops": 3 * 20000.0}},
        }
        diff = diff_static_cost(static, _fake_costdb())
        rows = {r["key"]: r for r in diff["rows"]}
        assert diff["uncovered"] == ["psum[tp]"]
        assert diff["covered"] == 2 and diff["total"] == 3
        pp = rows["ppermute[pp]"]
        assert pp["calibrated"] and pp["bucket"] == 128  # nearest to 160 B
        assert pp["predicted_ms"] == pytest.approx(
            1e3 * 9 * 160 / 8e9)
        gemm = rows["flops_16384"]
        assert gemm["calibrated"]
        assert gemm["predicted_ms"] == pytest.approx(1e3 * 60000.0 / 1e12)
        assert not rows["psum[tp]"]["calibrated"]

    def test_nearest_bucket_by_per_call_payload(self):
        from apex_tpu.prof.calibrate import diff_static_cost
        static = {"collectives": {"ppermute[pp]": {"calls": 2,
                                                   "bytes": 2 * 1400}},
                  "gemms": {}}
        diff = diff_static_cost(static, _fake_costdb())
        row = diff["rows"][0]
        assert row["bucket"] == 1024          # 1400 B/call sits nearer 2^10
        assert row["rate"] == 16e9

    def test_cli_costdb_table(self, tmp_path, capsys):
        db_path = tmp_path / "costdb.json"
        db_path.write_text(json.dumps(_fake_costdb()))
        rc = lint_main(["--jaxpr", "--entrypoint", "pipeline_zb",
                        "--costdb", str(db_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "static-cost vs CostDB — pipeline_zb" in out
        assert "ppermute[pp]" in out and "calibrated" in out
        # the pp psum traffic exists in the trace but not in the fake DB
        assert "UNCALIBRATED (absent from CostDB)" in out
        assert "no CostDB row" in out

    def test_cli_costdb_json_carries_diff(self, tmp_path, capsys):
        db_path = tmp_path / "costdb.json"
        db_path.write_text(json.dumps(_fake_costdb()))
        rc = lint_main(["--jaxpr", "--entrypoint", "pipeline_zb",
                        "--costdb", str(db_path), "--format", "json"])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0
        diff = report["costdb_diff"]["pipeline_zb"]
        assert {r["key"] for r in diff["rows"]} >= {"ppermute[pp]"}

    def test_cli_rejects_invalid_costdb(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"kind": "serve"}))
        rc = lint_main(["--jaxpr", "--entrypoint", "pipeline_zb",
                        "--costdb", str(bad)])
        assert rc == 2
        assert "not a valid costdb" in capsys.readouterr().err
