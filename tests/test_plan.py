"""apex_tpu.plan: the ParallelPlan object, CostDB-driven pricing, the
search loop, the `plan` record/CLI surface, and the consolidated
validation satellite (ISSUE 12).

Fixture CostDBs are hand-built (one bucket per key, zero spread) so
every pricing assertion is exact: determinism is bit-identical, and
the recovery tests pin which decomposition a given rate profile must
pick — the gate topology (dp2×tp2×pp2) under fast-tp/slow-hop rates
with tp capped by seq divisibility, and the 8-chip flagship (dp8, the
single-chip hand config replicated) under fast-dp rates.
"""

import dataclasses
import json
import os

import pytest

from apex_tpu.plan import (
    ParallelPlan,
    PlanError,
    Workload,
    enumerate_plans,
    estimate_memory,
    plan_record_fields,
    price_plan,
    search_plans,
)
from apex_tpu.plan import cost as plan_cost


def _stat(mean):
    return {"n": 8, "mean": mean, "min": mean, "max": mean,
            "spread_pct": 0.0}


def make_costdb(rates, gemm_rate=1e11):
    """One-bucket-per-key fixture CostDB (schema-valid)."""
    return {
        "schema": 1, "kind": "costdb",
        "collectives": {
            k: [{"bucket_bytes": 1 << 20, "bytes": _stat(1 << 20),
                 "bytes_per_s": _stat(r)}]
            for k, r in rates.items()},
        "gemms": {"flops_1": {"flops_per_s": _stat(gemm_rate)}},
    }


#: smoke workload for trace-backed pricing: seq=18 caps tp at 2 (18 % 4
#: != 0), the same way the flagship's head count caps tp on real chips
W = Workload(hidden_size=64, ffn_hidden_size=256, num_layers=8,
             vocab_size=512, seq=18, global_batch=16, micro_batch=2,
             dtype_bytes=4)

_TP_FAST = {"all_gather[tp]": 1e11, "psum_scatter[tp]": 1e11,
            "ppermute[tp]": 1e11, "psum[tp]": 1e11}


class TestParallelPlan:
    def test_roundtrip_exact(self):
        p = ParallelPlan(dp=2, tp=2, pp=2, sequence_parallel=True,
                         tp_overlap=True, pp_schedule="zb",
                         overlap_p2p=True, virtual_chunks=2, zero=True)
        assert ParallelPlan.from_json(p.to_json()) == p
        assert ParallelPlan.from_json(json.dumps(p.to_json())) == p
        # field-for-field, not just equality
        assert p.to_json() == ParallelPlan.from_json(
            p.to_json()).to_json()

    def test_from_json_rejects_unknown_fields(self):
        with pytest.raises(PlanError, match="unknown plan field"):
            ParallelPlan.from_json({"dp": 2, "banana": 1})

    @pytest.mark.parametrize("kwargs,needle", [
        (dict(tp_overlap=True), "tp_size >= 2"),
        (dict(pp_schedule="zbb"), "pp_schedule"),
        (dict(dp=3, ep=2), "must divide"),
        (dict(virtual_chunks=2), "pipeline_model_parallel_size >= 2"),
        (dict(sequence_parallel=True), "tp_size >= 2"),
        (dict(tp=2, cp=2, tp_overlap=True), "context"),
        (dict(tp=0), "tp=0"),
    ])
    def test_validation_names_knob(self, kwargs, needle):
        with pytest.raises(PlanError, match=needle):
            ParallelPlan(**kwargs)

    def test_validate_schedule_and_microbatches(self):
        with pytest.raises(PlanError, match="pipeline_model_parallel"):
            ParallelPlan(pp_schedule="zb").validate_schedule()
        with pytest.raises(PlanError, match="cannot fill"):
            ParallelPlan(pp=4).validate_microbatches(2)
        with pytest.raises(PlanError, match="divisible"):
            ParallelPlan(pp=2, virtual_chunks=2).validate_microbatches(3)
        ParallelPlan(pp=2, virtual_chunks=2).validate_microbatches(4)

    def test_world_size_and_describe(self):
        p = ParallelPlan(dp=2, tp=2, pp=2, ep=2, pp_schedule="zb")
        assert p.world_size == 8  # ep rides inside dp
        assert p.describe() == "dp2·tp2·pp2·ep2 zb"

    def test_frozen(self):
        with pytest.raises(dataclasses.FrozenInstanceError):
            ParallelPlan().dp = 2


class TestConsolidatedValidation:
    """The satellite: the same illegal combo is rejected with the same
    message whichever door it walks through."""

    def test_ep_divisibility_same_message_via_mesh(self):
        from apex_tpu.parallel import mesh as mesh_lib

        with pytest.raises(PlanError) as direct:
            ParallelPlan(dp=3, ep=2)
        with pytest.raises(ValueError) as via_spec:
            mesh_lib.MeshSpec(data_parallel_size=3,
                              expert_parallel_size=2)
        assert str(direct.value) == str(via_spec.value)

    def test_gpt_config_routes_through_plan(self):
        from apex_tpu.models import GPTConfig

        with pytest.raises(ValueError) as via_cfg:
            GPTConfig(vocab_size=64, max_seq_len=32, hidden_size=32,
                      num_layers=2, num_heads=4, pp_schedule="zbb")
        with pytest.raises(PlanError) as direct:
            ParallelPlan(pp_schedule="zbb")
        assert str(direct.value) == str(via_cfg.value)

    def test_build_schedule_routes_through_plan(self):
        from apex_tpu.transformer.pipeline_parallel import schedules

        with pytest.raises(ValueError) as via_sched:
            schedules.build_schedule(
                global_batch_size=32, micro_batch_size=2,
                data_parallel_size=1, pipeline_model_parallel_size=1,
                schedule="zb")
        with pytest.raises(PlanError) as direct:
            ParallelPlan(pp_schedule="zb").validate_schedule()
        assert str(direct.value) == str(via_sched.value)

    def test_make_mesh_consumes_plan(self):
        import jax

        from apex_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh(plan=ParallelPlan(dp=2, tp=2, pp=2))
        assert mesh.shape == {"dp": 2, "pp": 2, "cp": 1, "tp": 2}
        # dp is authoritative: the device list is sliced to world_size
        mesh = mesh_lib.make_mesh(plan=ParallelPlan(dp=1, tp=2))
        assert mesh.devices.size == 2
        with pytest.raises(RuntimeError, match="spans"):
            mesh_lib.make_mesh(
                plan=ParallelPlan(dp=2, tp=2, pp=2, cp=2),
                devices=jax.devices()[:8])

    def test_make_mesh_rejects_contradicting_loose_axis(self):
        from apex_tpu.parallel import mesh as mesh_lib

        with pytest.raises(ValueError, match="contradicts plan"):
            mesh_lib.make_mesh(tensor_model_parallel_size=4,
                               plan=ParallelPlan(dp=2, tp=2, pp=2))
        # a loose size AGREEING with the plan is fine
        mesh_lib.make_mesh(tensor_model_parallel_size=2,
                           plan=ParallelPlan(dp=2, tp=2, pp=2))

    def test_shim_normalizes_historically_inert_knobs(self):
        # sequence_parallel at tp=1 was silently inert in GPTConfig;
        # the shim keeps that caller working while direct construction
        # stays strict (asserted above)
        p = ParallelPlan.from_model_kwargs(tp_size=1,
                                           sequence_parallel=True)
        assert p.sequence_parallel is False


class TestPlanConsumption:
    def test_gpt_config_derives_loose_knobs_from_plan(self):
        from apex_tpu.models import GPTConfig

        plan = ParallelPlan(tp=2, sequence_parallel=True,
                            pp_schedule="zb", overlap_p2p=True)
        cfg = GPTConfig(vocab_size=64, max_seq_len=32, hidden_size=32,
                        num_layers=2, num_heads=4, plan=plan)
        assert cfg.tp_size == 2 and cfg.sequence_parallel
        assert cfg.pp_schedule == "zb" and cfg.overlap_p2p
        assert cfg.plan == plan

    def test_gpt_config_shim_constructs_plan(self):
        from apex_tpu.models import GPTConfig

        cfg = GPTConfig(vocab_size=64, max_seq_len=32, hidden_size=32,
                        num_layers=2, num_heads=4, tp_size=2,
                        sequence_parallel=True)
        assert cfg.plan.tp == 2 and cfg.plan.sequence_parallel

    def test_gpt_config_rejects_contradicting_loose_kwarg(self):
        from apex_tpu.models import GPTConfig

        with pytest.raises(ValueError, match="contradicts plan"):
            GPTConfig(vocab_size=64, max_seq_len=32, hidden_size=32,
                      num_layers=2, num_heads=4, tp_size=4,
                      plan=ParallelPlan(tp=2))

    def test_t5_config_rejects_tp_plan(self):
        from apex_tpu.models import T5Config

        with pytest.raises(ValueError, match="GPTConfig"):
            T5Config(plan=ParallelPlan(tp=2))
        # an explicit loose tp_overlap=True never silently merges with
        # a plan that implies False
        with pytest.raises(ValueError, match="contradicts plan"):
            T5Config(plan=ParallelPlan(), tp_overlap=True)

    def test_initialize_model_parallel_rejects_contradicting_v(self):
        from apex_tpu.parallel import mesh as mesh_lib

        try:
            with pytest.raises(ValueError, match="contradicts plan"):
                mesh_lib.initialize_model_parallel(
                    plan=ParallelPlan(pp=2),
                    virtual_pipeline_model_parallel_size=4)
            mesh_lib.initialize_model_parallel(
                plan=ParallelPlan(pp=2, virtual_chunks=2))
            assert (mesh_lib.get_mesh_spec()
                    .virtual_pipeline_model_parallel_size == 2)
        finally:
            mesh_lib.destroy_model_parallel()

    def test_planned_config_grad_parity_vs_hand_config(self):
        """Acceptance: the searched plan's model is the SAME program as
        the hand-configured one — loss and grads bitwise equal at tp=2
        (veScale-style single-semantics guarantee, enforced by the
        existing per-knob parity oracles; this pins the plan door)."""
        import jax
        import jax.numpy as jnp
        import jax.random as jr
        import numpy as np
        from jax.sharding import PartitionSpec as P

        from apex_tpu.models import GPTConfig, GPTModel
        from apex_tpu.models.gpt import shard_params_for_tp
        from apex_tpu.parallel import mesh as mesh_lib

        kw = dict(vocab_size=64, max_seq_len=32, hidden_size=32,
                  num_layers=2, num_heads=4, attention_impl="flash",
                  remat=False)
        plan = ParallelPlan(tp=2, sequence_parallel=True)
        cfg_hand = GPTConfig(**kw, tp_size=2, sequence_parallel=True)
        cfg_plan = GPTConfig(**kw, plan=plan)

        params1 = GPTModel(GPTConfig(**kw, tp_size=1)).init(jr.PRNGKey(0))
        sharded = shard_params_for_tp(params1, 2, GPTConfig(**kw))
        specs = jax.tree.map(lambda _: P("tp"), sharded)
        mesh = mesh_lib.make_mesh(plan=ParallelPlan(tp=2))
        toks = jr.randint(jr.PRNGKey(1), (2, 32), 0, 64)
        tgts = jr.randint(jr.PRNGKey(2), (2, 32), 0, 64)

        def run(cfg):
            model = GPTModel(cfg)

            def f(p, t, g):
                loss, grads = jax.value_and_grad(model.loss_fn)(
                    jax.tree.map(lambda x: x[0], p), t, g)
                return loss, jax.tree.map(lambda x: x[None], grads)

            step = jax.jit(mesh_lib.shard_map(
                f, mesh=mesh, in_specs=(specs, P(), P()),
                out_specs=(P(), specs)))
            return step(sharded, toks, tgts)

        loss_h, g_h = run(cfg_hand)
        loss_p, g_p = run(cfg_plan)
        assert float(loss_h) == float(loss_p)
        for a, b in zip(jax.tree.leaves(g_h), jax.tree.leaves(g_p)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestPricing:
    def _db(self, dp=1e9, pp=1e8, gemm=1e11):
        return make_costdb({"psum[dp]": dp, "ppermute[pp]": pp,
                            **_TP_FAST}, gemm)

    def test_deterministic_bit_identical(self):
        plan = ParallelPlan(dp=2, tp=2, pp=2, sequence_parallel=True,
                            pp_schedule="zb")
        db = self._db()
        a = price_plan(plan, W, db)
        plan_cost._STATIC_CACHE.clear()  # force a fresh trace
        b = price_plan(plan, W, db)
        assert a.predicted_step_ms == b.predicted_step_ms
        assert a.to_json() == b.to_json()

    def test_monotone_in_rates(self):
        """Doubling any rate never makes any plan slower."""
        plans = [ParallelPlan(dp=2, tp=2, pp=2, sequence_parallel=True,
                              pp_schedule="zb"),
                 ParallelPlan(dp=8),
                 ParallelPlan(dp=2, tp=1, pp=4, overlap_p2p=True)]
        base_db = self._db()
        base = [price_plan(p, W, base_db).predicted_step_ms
                for p in plans]
        for key in ("psum[dp]", "ppermute[pp]", "all_gather[tp]"):
            rates = {"psum[dp]": 1e9, "ppermute[pp]": 1e8, **_TP_FAST}
            rates[key] = rates[key] * 2
            faster = make_costdb(rates)
            for p, b in zip(plans, base):
                assert price_plan(p, W, faster).predicted_step_ms <= b
        for p, b in zip(plans, base):
            assert price_plan(p, W, self._db(gemm=2e11)
                              ).predicted_step_ms <= b

    def test_uncalibrated_keys_surface(self):
        plan = ParallelPlan(dp=2, tp=2, pp=2, sequence_parallel=True)
        db = make_costdb({"psum[dp]": 1e9})  # no tp/pp rows
        price = price_plan(plan, W, db, default_bytes_per_s=1e9)
        assert price.confidence == "partial"
        assert "ppermute[pp]" in price.uncalibrated
        assert any(k.startswith("all_gather[tp]")
                   for k in price.uncalibrated)
        full = price_plan(plan, W, self._db())
        assert full.confidence == "calibrated"
        assert full.uncalibrated == ()

    def test_schedule_is_a_priced_choice(self):
        """zb vs 1f1b and overlap vs blocking price differently from
        the same traced program — the cost-model term at work."""
        base = dict(dp=2, tp=1, pp=4)
        db = self._db()
        zb = price_plan(ParallelPlan(**base, pp_schedule="zb"), W, db)
        f1 = price_plan(ParallelPlan(**base, pp_schedule="1f1b"), W, db)
        assert zb.predicted_step_ms != f1.predicted_step_ms
        assert zb.schedule_factor < f1.schedule_factor  # remat=False
        ov = price_plan(ParallelPlan(**base, pp_schedule="zb",
                                     overlap_p2p=True), W, db)
        assert ov.pp_ms == zb.pp_ms  # same traced hop bytes
        # overlap hides the hop bytes but lengthens the drain
        assert ov.schedule_factor > zb.schedule_factor

    def test_ranking_row_reconciles_with_predicted(self):
        """gemm_ms·schedule_factor + collective_ms == predicted_step_ms
        for overlap and blocking plans alike (the record's decomposition
        must sum, or a consumer cannot trust either side)."""
        db = self._db()
        for plan in (ParallelPlan(dp=2, tp=1, pp=4, pp_schedule="zb",
                                  overlap_p2p=True),
                     ParallelPlan(dp=2, tp=2, pp=2,
                                  sequence_parallel=True)):
            row = price_plan(plan, W, db).to_json()
            lhs = (row["gemm_ms"] * row["schedule_factor"]
                   + row["collective_ms"])
            assert abs(lhs - row["predicted_step_ms"]) < 2e-3

    def test_memory_estimate_scales_with_plan(self):
        dense = estimate_memory(ParallelPlan(dp=2, tp=2, pp=2,
                                             sequence_parallel=True), W)
        zero = estimate_memory(
            ParallelPlan(dp=2, tp=2, pp=2, sequence_parallel=True,
                         zero=True), W)
        assert zero.optimizer == dense.optimizer // 2
        assert zero.params == dense.params
        wide = estimate_memory(ParallelPlan(dp=8), W)
        assert wide.params > dense.params  # unsharded model per chip

    def test_nondividing_layers_raise_never_truncate(self):
        """Pricing must reject (not silently shrink) a plan whose
        pp*v does not divide the layer stack — a truncated model's
        price is not comparable with anyone else's."""
        with pytest.raises(PlanError, match="num_layers"):
            price_plan(ParallelPlan(dp=1, tp=1, pp=5), W, self._db())
        with pytest.raises(PlanError, match="num_layers"):
            estimate_memory(ParallelPlan(pp=5), W)

    def test_conservative_defaults_floor_blind_spots(self):
        from apex_tpu.plan import conservative_defaults

        empty = {"schema": 1, "kind": "costdb", "collectives": {},
                 "gemms": {}}
        assert conservative_defaults(empty) == {
            "default_bytes_per_s": 1e10, "default_flops_per_s": 1e14}
        db = make_costdb({"psum[dp]": 5e8, "ppermute[pp]": 2e7},
                         gemm_rate=3e10)
        got = conservative_defaults(db)
        # blind spots price at the SLOWEST measured rate — a plan can
        # never win because its dominant traffic was unmeasured
        assert got == {"default_bytes_per_s": 2e7,
                       "default_flops_per_s": 3e10}

    def test_bucket_rule_shared_with_calibrate(self):
        """One bucket-matching rule: the planner's collective pricing
        and diff_static_cost pick the identical rate for the same
        payload."""
        from apex_tpu.prof.calibrate import nearest_bucket_rate

        rows = [{"bucket_bytes": 1 << b, "bytes": _stat(1 << b),
                 "bytes_per_s": _stat(float(b))} for b in (10, 16, 24)]
        assert nearest_bucket_rate(rows, 3000.0) == 10.0    # near 2^10?
        assert nearest_bucket_rate(rows, 100000.0) == 16.0
        assert nearest_bucket_rate(rows, 1 << 30) == 24.0
        assert nearest_bucket_rate([], 1024.0) is None

    def test_worked_example_matches_docs(self):
        """The docs/api/plan.md worked example is THIS fixture; drift
        between the doc's numbers and the pricer fails here."""
        plan = ParallelPlan(dp=2, tp=1, pp=1)
        db = make_costdb({"psum[dp]": 1e9}, gemm_rate=1e11)
        price = price_plan(plan, W, db)
        static = plan_cost.static_cost_for_plan(plan, W)
        psum_bytes = static["collectives"]["psum[dp]"]["bytes"]
        gemm_flops = static["total_gemm_flops"]
        expect = 1e3 * gemm_flops / 1e11 + 1e3 * psum_bytes / 1e9
        assert price.schedule_factor == 1.0
        assert abs(price.predicted_step_ms - expect) < 1e-9


class TestSearch:
    def test_recovers_flagship_dp8(self):
        """Generous memory + fast dp all-reduce: the 8-chip best is the
        hand config — the single-chip flagship replicated (dp8)."""
        db = make_costdb({"psum[dp]": 1e12, "ppermute[pp]": 1e8,
                          **{k: 1e8 for k in _TP_FAST}})
        res = search_plans(8, W, db, default_bytes_per_s=1e8,
                           default_flops_per_s=1e11)
        best = res.best.plan
        assert (best.dp, best.tp, best.pp) == (8, 1, 1)

    def test_recovers_gate_topology_dp2_tp2_pp2(self):
        """Fast tp ICI, slow pp hops, medium dp, tp capped at 2 by seq
        divisibility: the 8-chip best decomposition is the multichip
        gate's hand config dp2×tp2×pp2."""
        db = make_costdb({"psum[dp]": 5e8, "ppermute[pp]": 5e7,
                          **_TP_FAST}, gemm_rate=2.2e10)
        res = search_plans(8, W, db, default_bytes_per_s=1e8,
                           default_flops_per_s=2.2e10)
        best = res.best.plan
        assert (best.dp, best.tp, best.pp) == (2, 2, 2)
        # tp4 was structurally rejected (seq=18), surfaced with reason
        assert any("tp=4" in d or "tp4" in d for d, _ in res.rejected)

    def test_heterogeneity_repricess_dp_placement(self):
        """AMP's heterogeneity term: slow dp-axis CostDB entries (DCN)
        push the winner away from dp-heavy placement."""
        fast_dp = make_costdb({"psum[dp]": 1e12, "ppermute[pp]": 1e8,
                               **{k: 1e8 for k in _TP_FAST}})
        slow_dp = make_costdb({"psum[dp]": 1e8, "ppermute[pp]": 1e8,
                               **{k: 1e8 for k in _TP_FAST}})
        kw = dict(default_bytes_per_s=1e8, default_flops_per_s=1e11)
        assert search_plans(8, W, fast_dp, **kw).best.plan.dp == 8
        assert search_plans(8, W, slow_dp, **kw).best.plan.dp < 8

    def test_memory_bound_rejects_with_reason(self):
        db = make_costdb({"psum[dp]": 1e12, "ppermute[pp]": 1e8,
                          **{k: 1e8 for k in _TP_FAST}})
        unbounded = search_plans(8, W, db, default_bytes_per_s=1e8,
                                 default_flops_per_s=1e11)
        bound = unbounded.best.price.memory.total - 1
        res = search_plans(8, W, db, memory_bound_bytes=bound,
                           default_bytes_per_s=1e8,
                           default_flops_per_s=1e11)
        assert res.best.plan != unbounded.best.plan
        assert any("exceeds the bound" in r for _, r in res.rejected)

    def test_lattice_rejections_carry_reasons(self):
        plans, rejected = enumerate_plans(8, W)
        assert plans
        # every rejection is (description, reason) — nothing silent
        assert all(d and r for d, r in rejected)

    def test_plan_record_fields_skip_half_is_explicit(self):
        db = make_costdb({"psum[dp]": 1e12}, gemm_rate=1e11)
        res = search_plans(4, W, db, default_bytes_per_s=1e9,
                           default_flops_per_s=1e11)
        fields = plan_record_fields(res, costdb_source="fixture",
                                    skip_reason="off-TPU test")
        assert fields["measured_step_ms"] == ("skipped", "off-TPU test")
        measured = plan_record_fields(res, costdb_source="fixture",
                                      measured_step_ms=2.0)
        assert isinstance(
            measured["predicted_vs_measured_err_pct"], float)


class TestPlannedEntrypoint:
    def test_registered_and_clean_by_default(self):
        from apex_tpu.lint import entrypoints as eps

        assert "planned_gpt_step" in eps.names()
        findings, cost = eps.check("planned_gpt_step")
        assert findings == []
        assert "ppermute[pp]" in cost["collectives"]  # gate default pp2

    def test_env_plan_switches_traced_program(self, monkeypatch):
        from apex_tpu.lint import entrypoints as eps

        plan = ParallelPlan(tp=4, tp_overlap=True,
                            sequence_parallel=True)
        monkeypatch.setenv("APEX_TPU_PLAN", json.dumps(plan.to_json()))
        findings, cost = eps.check("planned_gpt_step")
        assert findings == []
        assert "ppermute[tp]" in cost["collectives"]
        assert not any(k.startswith("all_gather[tp]")
                       for k in cost["collectives"])

    def test_combined_tp_pp_plan_composes_both_contract_families(
            self, monkeypatch):
        """A dp2·tp2·pp2 tp_overlap pick is checked against BOTH the
        schedule witnesses and the ring-overlap invariants in one
        traced program — the gate is never vacuous for either family."""
        from apex_tpu.lint import entrypoints as eps

        plan = ParallelPlan(dp=2, tp=2, pp=2, sequence_parallel=True,
                            tp_overlap=True, pp_schedule="zb")
        monkeypatch.setenv("APEX_TPU_PLAN", json.dumps(plan.to_json()))
        codes = {c.code for c in eps.get("planned_gpt_step").contracts()}
        assert {"JXP401", "JXP402", "JXP403", "JXP201"} <= codes
        findings, cost = eps.check("planned_gpt_step")
        assert findings == []
        keys = set(cost["collectives"])
        assert "ppermute[pp]" in keys and "ppermute[tp]" in keys

    def test_bad_env_plan_fails_loudly(self, monkeypatch):
        from apex_tpu.lint import entrypoints as eps

        monkeypatch.setenv("APEX_TPU_PLAN", '{"tp": 0}')
        with pytest.raises(PlanError):
            eps.check("planned_gpt_step")


class TestPlanRecord:
    def _fields(self):
        db = make_costdb({"psum[dp]": 1e12}, gemm_rate=1e11)
        res = search_plans(4, W, db, default_bytes_per_s=1e9,
                           default_flops_per_s=1e11)
        return plan_record_fields(res, costdb_source="fixture",
                                  measured_step_ms=2.0)

    def test_emit_validates_ok_record(self):
        from apex_tpu import monitor

        record = monitor.MetricsRegistry().emit_plan(
            "OK", **self._fields(), backend="cpu")
        assert monitor.validate(record) == []
        assert record["kind"] == "plan"

    def test_skip_requires_reason(self):
        from apex_tpu import monitor

        with pytest.raises(ValueError, match="reason"):
            monitor.MetricsRegistry().emit_plan("SKIP", **self._fields())

    def test_nan_inside_ok_fails(self):
        from apex_tpu import monitor

        fields = self._fields()
        fields["predicted_step_ms"] = float("nan")
        with pytest.raises(ValueError, match="non-finite"):
            monitor.MetricsRegistry().emit_plan("OK", **fields,
                                                backend="cpu")

    def test_junk_ranking_key_fails_validation(self):
        from apex_tpu import monitor

        record = monitor.MetricsRegistry().emit_plan(
            "OK", **self._fields(), backend="cpu")
        record["ranking"][0]["vibes"] = 11
        assert any("vibes" in e for e in monitor.validate(record))
        del record["ranking"][0]["vibes"]
        record["chosen"]["banana"] = 1
        assert any("banana" in e for e in monitor.validate(record))

    def test_wrong_kind_fails(self):
        from apex_tpu import monitor
        from apex_tpu.monitor import schema

        record = monitor.MetricsRegistry().emit_plan(
            "OK", **self._fields(), backend="cpu")
        record["kind"] = "decode"
        assert schema.validate(record, schema.PLAN_SCHEMA)

    def test_report_renders_plan_line(self):
        from apex_tpu import monitor
        from apex_tpu.monitor import report

        record = monitor.MetricsRegistry().emit_plan(
            "OK", **self._fields(), backend="cpu")
        summary = report.aggregate([record])
        assert summary["plan"]["predicted_vs_measured_err_pct"] == \
            record["predicted_vs_measured_err_pct"]
        text = report.render(summary)
        assert "plan" in text and "chose" in text and "err" in text


class TestPlanCLIs:
    def _record(self, tmp_path, status="OK", err=1.5, hbm_err=None):
        db = make_costdb({"psum[dp]": 1e12}, gemm_rate=1e11)
        res = search_plans(4, W, db, default_bytes_per_s=1e9,
                           default_flops_per_s=1e11)
        from apex_tpu import monitor

        if status == "OK":
            fields = plan_record_fields(res, costdb_source="fixture",
                                        measured_step_ms=2.0)
            fields["predicted_vs_measured_err_pct"] = err
        else:
            fields = plan_record_fields(res, costdb_source="fixture",
                                        skip_reason="off-TPU test")
            fields["reason"] = "off-TPU test"
        if hbm_err is not None:
            # the apexmem fields bench.py --plan adds on a measured run
            fields["predicted_peak_hbm_mb"] = 100.0
            fields["measured_peak_hbm_mb"] = 100.0 * (1 + hbm_err / 100)
            fields["predicted_vs_measured_hbm_err_pct"] = hbm_err
        record = monitor.MetricsRegistry().emit_plan(
            status, **fields, backend="cpu")
        path = tmp_path / f"plan_{status}_{err}_{hbm_err}.json"
        path.write_text(json.dumps(record))
        return str(path)

    def test_validate_metrics_plan_forced_dispatch(self, tmp_path,
                                                   capsys):
        import tools.validate_metrics as vm

        good = self._record(tmp_path)
        assert vm.main(["--plan", good]) == 0
        wrong = tmp_path / "decode.json"
        wrong.write_text(json.dumps({"kind": "decode", "schema": 1,
                                     "status": "SKIP", "reason": "x"}))
        assert vm.main(["--plan", str(wrong)]) == 1
        err = capsys.readouterr().err
        assert "expected a 'plan' artifact" in err

    def test_bench_history_gates_error_drift(self, tmp_path, capsys):
        import tools.bench_history as bh

        history = self._record(tmp_path, err=1.0)
        hist_dir = tmp_path
        os.rename(history, str(hist_dir / "BENCH_r90.json"))
        # fresh error within allowance: OK
        fresh_ok = self._record(tmp_path, err=2.0)
        assert bh.main([fresh_ok, "--root", str(hist_dir),
                        "--history", "BENCH_r9*.json"]) == 0
        assert "OK plan_predicted_vs_measured_err_pct" in \
            capsys.readouterr().out
        # fresh error drifted up beyond tolerance: REGRESSION
        fresh_bad = self._record(tmp_path, err=9.0)
        assert bh.main([fresh_bad, "--root", str(hist_dir),
                        "--history", "BENCH_r9*.json"]) == 1
        assert "REGRESSION" in capsys.readouterr().out
        # SKIP record claims nothing
        skip = self._record(tmp_path, status="SKIP")
        assert bh.main([skip, "--root", str(hist_dir),
                        "--history", "BENCH_r9*.json"]) == 0

    def test_bench_history_gates_hbm_err_drift(self, tmp_path, capsys):
        """The apexmem memory-honesty series rides the same trajectory
        gate as the step-time error — and a history artifact that
        predates it (no hbm field) skips ONLY the new series, never the
        whole gate."""
        import tools.bench_history as bh

        old_history = self._record(tmp_path, err=1.0)  # pre-apexmem
        os.rename(old_history, str(tmp_path / "BENCH_r90.json"))
        fresh = self._record(tmp_path, err=1.5, hbm_err=3.0)
        assert bh.main([fresh, "--root", str(tmp_path),
                        "--history", "BENCH_r9*.json"]) == 0
        out = capsys.readouterr().out
        assert "OK plan_predicted_vs_measured_err_pct" in out
        assert ("SKIP: no history artifact carries metric "
                "'plan_predicted_vs_measured_hbm_err_pct'") in out
        # once the trajectory carries the series, drift gates it
        with_hbm = self._record(tmp_path, err=1.0, hbm_err=1.0)
        os.rename(with_hbm, str(tmp_path / "BENCH_r91.json"))
        ok = self._record(tmp_path, err=1.5, hbm_err=2.0)
        assert bh.main([ok, "--root", str(tmp_path),
                        "--history", "BENCH_r9*.json"]) == 0
        assert "OK plan_predicted_vs_measured_hbm_err_pct" in \
            capsys.readouterr().out
        bad = self._record(tmp_path, err=1.5, hbm_err=9.0)
        assert bh.main([bad, "--root", str(tmp_path),
                        "--history", "BENCH_r9*.json"]) == 1
        assert ("REGRESSION plan_predicted_vs_measured_hbm_err_pct"
                in capsys.readouterr().out)

    def test_lint_strict_gates_uncalibrated(self, tmp_path, capsys):
        from apex_tpu.lint.__main__ import main as lint_main

        empty_db = tmp_path / "empty_costdb.json"
        empty_db.write_text(json.dumps(
            {"schema": 1, "kind": "costdb", "collectives": {},
             "gemms": {}}))
        rc = lint_main(["--jaxpr", "--entrypoint", "planned_gpt_step",
                        "--costdb", str(empty_db), "--strict",
                        "--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["uncalibrated"]["planned_gpt_step"]
        # a fully covered costdb passes --strict
        from apex_tpu.lint import entrypoints as eps
        _, cost = eps.check("planned_gpt_step")
        full = make_costdb(
            {k: 1e9 for k in cost["collectives"]})
        full["gemms"] = {k: {"flops_per_s": _stat(1e11)}
                         for k in cost["gemms"]}
        full_path = tmp_path / "full_costdb.json"
        full_path.write_text(json.dumps(full))
        rc = lint_main(["--jaxpr", "--entrypoint", "planned_gpt_step",
                        "--costdb", str(full_path), "--strict",
                        "--format", "json"])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert report["uncalibrated"] == {}
        # --strict without --costdb is a usage error
        assert lint_main(["--jaxpr", "--strict"]) == 2


class TestLivenessMemorySource:
    """apexmem as the planner's memory model: the donation-aware
    liveness bound of the TRACED per-chip step vs the hand closed form
    — agreement pinned on the flagship plans, the one legitimate
    schedule-knowledge disagreement documented, and the bound as a
    search-pruning predicate."""

    #: the stash-heavy geometry: 32 microbatches at pp=2 make the
    #: schedule-agnostic trace's every-tick stash dominate
    W_STASHY = Workload(vocab_size=4096, global_batch=128, micro_batch=4)

    def test_closed_form_agrees_on_flagship_plans(self):
        """The two models were reconciled term by term (the vocab-head
        logits were the closed form's big gap); on the flagship plans
        they now agree within 10% — a regression in either model breaks
        this pin."""
        from apex_tpu.plan import liveness_memory

        w = Workload()
        for plan in (ParallelPlan(dp=8),
                     ParallelPlan(dp=2, tp=2, pp=2,
                                  sequence_parallel=True,
                                  pp_schedule="zb"),
                     ParallelPlan(dp=1, tp=4, pp=2,
                                  sequence_parallel=True,
                                  pp_schedule="zb")):
            cf = estimate_memory(plan, w).total
            lv = liveness_memory(plan, w).total
            gap = 100.0 * abs(lv - cf) / cf
            assert gap < 10.0, (plan.describe(), gap)
            assert liveness_memory(plan, w).source == "liveness"

    def test_documented_1f1b_disagreement_flags_not_hides(self):
        """The ONE known legitimate disagreement: the traced program is
        schedule-AGNOSTIC (one grad over the full tick scan stashes
        every tick's input — zb-like geometry), while 1f1b's closed
        form knows only a pp-deep window of stashes is ever live. At 32
        microbatches the gap is ~24% (jax 0.9's trace; the flag fires
        above 10%) — and the honesty contract is that
        it SURFACES as an uncalibrated flag + partial confidence, never
        silently."""
        price = price_plan(
            ParallelPlan(dp=1, pp=2, pp_schedule="1f1b"), self.W_STASHY,
            {}, default_bytes_per_s=1e9, default_flops_per_s=1e11,
            memory_source="liveness")
        assert price.memory.source == "liveness"
        assert price.memory_disagreement_pct > 20.0
        flags = [u for u in price.uncalibrated if "memory_model" in u]
        assert flags and "closed_form_vs_liveness" in flags[0]
        assert price.confidence == "partial"
        # the zb schedule matches the trace's geometry: no flag
        zb = price_plan(
            ParallelPlan(dp=1, pp=2, pp_schedule="zb"), self.W_STASHY,
            {}, default_bytes_per_s=1e9, default_flops_per_s=1e11,
            memory_source="liveness")
        assert zb.memory_disagreement_pct < 10.0
        assert not [u for u in zb.uncalibrated if "memory_model" in u]

    def test_liveness_rejects_previously_accepted_candidates(self):
        """The pruning acceptance: with the bound midway between the
        closed form and the liveness peak, closed-form search ACCEPTS
        the 1f1b candidates whose real stash geometry does not fit —
        liveness search rejects them, quoting both numbers."""
        from apex_tpu.plan import liveness_memory

        plan = ParallelPlan(dp=1, pp=2, pp_schedule="1f1b")
        cf = estimate_memory(plan, self.W_STASHY).total
        lv = liveness_memory(plan, self.W_STASHY).total
        assert lv > cf
        bound = (cf + lv) // 2
        kw = dict(memory_bound_bytes=bound, default_bytes_per_s=1e9,
                  default_flops_per_s=1e11)
        accepted_cf = {c.plan.describe() for c in
                       search_plans(2, self.W_STASHY, {}, **kw).ranked}
        res = search_plans(2, self.W_STASHY, {}, **kw,
                           memory_source="liveness")
        accepted_lv = {c.plan.describe() for c in res.ranked}
        newly_rejected = accepted_cf - accepted_lv
        assert plan.describe() in newly_rejected
        reasons = [r for d, r in res.rejected if d in newly_rejected]
        assert reasons
        assert all("liveness per-chip peak" in r
                   and "closed form said" in r for r in reasons)
        # survivors' memory column comes from the liveness analysis
        assert all(c.price.memory.source == "liveness"
                   for c in res.ranked)

    def test_memory_source_validated(self):
        with pytest.raises(PlanError, match="memory_source"):
            price_plan(ParallelPlan(dp=2), W, {},
                       default_bytes_per_s=1e9,
                       default_flops_per_s=1e11, memory_source="vibes")

    def test_record_fields_carry_memory_source(self):
        res = search_plans(2, self.W_STASHY, {},
                           default_bytes_per_s=1e9,
                           default_flops_per_s=1e11,
                           memory_source="liveness")
        fields = plan_record_fields(res, costdb_source="fixture",
                                    skip_reason="off-TPU test")
        assert fields["memory_source"] == "liveness"
        assert any("memory_disagreement_pct" in row
                   for row in fields["ranking"])

    def test_hbm_nan_inside_ok_fails(self):
        from apex_tpu import monitor

        db = make_costdb({"psum[dp]": 1e12}, gemm_rate=1e11)
        res = search_plans(4, W, db, default_bytes_per_s=1e9,
                           default_flops_per_s=1e11)
        fields = plan_record_fields(res, costdb_source="fixture",
                                    measured_step_ms=2.0)
        fields["predicted_vs_measured_hbm_err_pct"] = float("nan")
        with pytest.raises(ValueError, match="non-finite"):
            monitor.MetricsRegistry().emit_plan("OK", **fields,
                                                backend="cpu")

    def test_hbm_reasonless_skip_fails_validation(self):
        from apex_tpu import monitor

        db = make_costdb({"psum[dp]": 1e12}, gemm_rate=1e11)
        res = search_plans(4, W, db, default_bytes_per_s=1e9,
                           default_flops_per_s=1e11)
        fields = plan_record_fields(res, costdb_source="fixture",
                                    measured_step_ms=2.0)
        fields["predicted_vs_measured_hbm_err_pct"] = 1.0
        record = monitor.MetricsRegistry().emit_plan("OK", **fields,
                                                     backend="cpu")
        assert monitor.validate(record) == []
        record["predicted_vs_measured_hbm_err_pct"] = {"skipped": True}
        errors = monitor.validate(record)
        assert any("predicted_vs_measured_hbm_err_pct" in e
                   for e in errors), errors
