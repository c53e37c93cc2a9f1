"""Test harness: a virtual 8-device CPU mesh on a single host.

This is the TPU-framework analog of the reference's ``DistributedTestBase``
(``apex/transformer/testing/distributed_test_base.py:9-60``), which spawns one
NCCL process per local GPU. JAX needs no processes: forcing 8 host-platform
devices gives every test a real 8-way mesh with real collectives.

Must set the env vars before jax initializes its backends, hence the
module-level code in conftest (imported by pytest before test modules).
"""

import os
import resource

# XLA's CPU compiler recurses deeply (LLVM + scan-transpose lowering); the
# default 8 MB thread stack is MARGINAL for the suite's biggest programs —
# the interleaved-pipeline MoE oracle segfaulted mid-suite on it (compile
# threads inherit RLIMIT_STACK as their default pthread stack size). Raise
# the soft limit before jax spawns any threads.
_soft, _hard = resource.getrlimit(resource.RLIMIT_STACK)
_want = 256 * 1024 * 1024
if _soft != resource.RLIM_INFINITY and _soft < _want:
    if _hard == resource.RLIM_INFINITY or _hard >= _want:
        resource.setrlimit(resource.RLIMIT_STACK, (_want, _hard))
    elif _hard > _soft:
        # hard cap finite but below 256 MB: raise to the cap rather than
        # skipping the raise entirely — every byte of compile-thread stack
        # helps, and the cap is the most an unprivileged process can get
        resource.setrlimit(resource.RLIMIT_STACK, (_hard, _hard))

# Force CPU regardless of ambient JAX_PLATFORMS: tests need the 8-device
# virtual mesh. Set APEX_TPU_TEST_ON_TPU=1 to run the suite on real hardware
# instead.
if not os.environ.get("APEX_TPU_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    # APEX_TPU_VIRTUAL_DEVICES widens the harness for ad-hoc runs (e.g.
    # 16 to debug a 4-axis composition in-process). The CHECKED-IN 16-wide
    # gate does not use it: tests/test_full_composition.py spawns
    # subprocesses that set the device-count XLA flag directly (the env
    # must be set before jax initializes — a respawn is the only reliable
    # way mid-suite). Default stays 8: the suite's shapes assume it, and
    # 16 doubles every collective's cost.
    n = os.environ.get("APEX_TPU_VIRTUAL_DEVICES", "8")
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}")

import jax  # noqa: E402
import pytest  # noqa: E402

if not os.environ.get("APEX_TPU_TEST_ON_TPU"):
    # the config update wins over anything that selected a platform
    # between the env var above and this import
    jax.config.update("jax_platforms", "cpu")


if os.environ.get("APEX_TPU_TEST_ON_TPU"):
    # Hardware mode validates the kernels on the real chip; tests that build
    # multi-device meshes (cp/tp/dp > available chips) skip rather than fail
    # — patch mesh construction so the "not divisible" ValueError becomes a
    # skip, mirroring the reference harness shrinking/skipping world sizes
    # (distributed_test_base.py:47-50).
    from apex_tpu.parallel import mesh as _mesh_lib

    def _skip_when_starved(fn):
        def wrapped(*args, **kwargs):
            try:
                return fn(*args, **kwargs)
            except (ValueError, RuntimeError) as e:
                if "divisible" in str(e) or "cannot host" in str(e):
                    pytest.skip(
                        f"needs a bigger mesh than the {jax.device_count()} "
                        f"real device(s): {e}")
                raise
        return wrapped

    _mesh_lib.make_mesh = _skip_when_starved(_mesh_lib.make_mesh)
    _mesh_lib.initialize_model_parallel = _skip_when_starved(
        _mesh_lib.initialize_model_parallel)


# --- tier-1 time budget (off-TPU) --------------------------------------------
#
# Tier-1 is the driver's run: `-m 'not slow'` over six xdist workers,
# `--dist loadfile`, cut by `timeout 1470` (`/root/TESTS_LAST_RUN.json`,
# `commands`; ROADMAP D12 tracks its wall clock and junit sum). A run that is
# cut counts only as far as it got, so the heaviest interpret-mode kernel
# suites and composition oracles (>= ~6 s each when the list was drawn up in
# PR 2, 1400 s combined) are demoted to the `slow` tier HERE, in one list,
# rather than by marks scattered over a dozen files; each entry names the
# sibling that stays in tier-1. They still run in the full suite (`-m ''`)
# and on hardware (`APEX_TPU_TEST_ON_TPU=1` skips this demotion — on a real
# TPU the kernels are fast). An entry is a node id: a test that moves to
# another file takes its entry with it. Time is brought down first by what a
# test compiles (README "Test tiers"), and by this list only after that.
_SLOW_OFF_TPU = {
    "tests/test_examples.py::test_imagenet_example_synthetic",
    "tests/test_entry.py::test_dryrun_multichip_respawn_path",
    "tests/test_examples.py::test_imagenet_example_prefetched_host_data",
    "tests/test_entry.py::test_dryrun_multichip_tp_only[4]",
    "tests/test_entry.py::test_dryrun_multichip_8",
    "tests/test_megatron_surface.py::TestGPTScaling::test_tp4_scaling_runs",
    "tests/test_docs.py::test_training_guide_blocks_execute_in_order",
    "tests/test_contrib.py::TestZeroFlagship::test_zero_adam_under_moe_ep[4]",
    "tests/test_gpt_pipeline.py::TestScheduleFeatureMatrix::test_ep_moe[1]",
    "tests/test_moe.py::TestMoEPipelineEP::test_interleaved_v2_pp2_ep2",
    "tests/test_gpt_pipeline.py::TestScheduleFeatureMatrix::test_zero[2]",
    "tests/test_moe.py::TestMoEPipelineEP::test_five_axis_ep_pp_cp_one_mesh",
    "tests/test_gpt_pipeline.py::TestScheduleFeatureMatrix::test_zero[1]",
    "tests/test_gpt_pipeline.py::TestScheduleFeatureMatrix::test_ep_moe[2]",
    "tests/test_enc_dec_pipeline.py::TestEncDecPipeline::test_loss_and_grads_match_serial",
    "tests/test_entry.py::test_dryrun_multichip_2",
    "tests/test_moe.py::TestMoEPipelineEP::test_pp2_ep2_dp2_matches_serial_shards",
    "tests/test_gpt_pipeline.py::TestScheduleFeatureMatrix::test_cp_ring[2]",
    "tests/test_moe.py::TestGPTMoE::test_gpt_moe_through_pipeline_matches_serial",
    "tests/test_t5.py::TestRelativePositionBias::test_relative_through_pipeline_matches_serial",
    "tests/test_pipeline.py::TestGPTBlockPipeline::test_pp4_interleaved_gpt_blocks_match_serial",
    "tests/test_gpt_pipeline.py::TestScheduleFeatureMatrix::test_cp_ring[1]",
    "tests/test_contrib.py::TestZeroFlagship::test_zero_adam_under_3d_pipeline",
    "tests/test_moe.py::TestExpertParallel::test_ep_matches_single_device",
    "tests/test_moe.py::TestMoEPipelineEP::test_tp2_pp2_ep2_one_mesh",
    "tests/test_gpt_pipeline.py::TestGPTPipelineParity::test_pp2_tp2_dp2_sp_full_3d",
    "tests/test_moe.py::TestDedicatedEpAxis::test_moe_on_ep_axis_matches_single_device",
    "tests/test_gpt_pipeline.py::TestContextParallelFlagship::test_pp2_cp2_dp2_pipeline",
    "tests/test_models.py::TestGPT::test_tp2_grads_match_tp1",
    "tests/test_contrib.py::TestZeroFlagship::test_zero_adam_under_gpt_tp2[4]",
    "tests/test_attention.py::TestGPTFlashDropout::test_flash_dropout_trains_and_is_keyed",
    "tests/test_models.py::TestGPT::test_tp2_matches_tp1[False]",
    "tests/test_t5.py::TestEncDecPipelineModel::test_pipeline_matches_serial[1]",
    "tests/test_t5.py::TestEncoderPadding::test_pipeline_matches_serial_padded",
    "tests/test_t5.py::TestRematPolicies::test_encode_only_matches_blocks_through_pipeline",
    "tests/test_models.py::TestGPT::test_tp2_matches_tp1[True]",
    "tests/test_examples.py::test_simple_distributed_example",
    "tests/test_gpt_pipeline.py::TestContextParallelFlagship::test_pp2_cp2_tp2_one_mesh",
    "tests/test_contrib.py::TestDistributedOptimizers::test_zero_grad_reduce_dtype_opt_out",
    "tests/test_enc_dec_pipeline.py::TestEncDecPipeline::test_uses_installed_mesh_split",
    "tests/test_gpt_pipeline.py::TestContextParallelFlagship::test_cp_with_dropout_trains_keyed[ring]",
    "tests/test_gpt_pipeline.py::TestGPTPipelineParity::test_pp2_matches_single_device[softmax]",
    "tests/test_moe.py::TestGPTMoE::test_gpt_moe_tp2_matches_tp1[False]",
    "tests/test_t5.py::TestEncDecPipelineModel::test_pipeline_matches_serial[2]",
    "tests/test_gpt_pipeline.py::TestGPTPipelinePartition::test_dropout_trains_with_distinct_masks",
    "tests/test_contrib.py::TestDistributedOptimizers::test_zero_lamb_runs_and_differs_from_adam",
    "tests/test_pipeline.py::TestPipelineSPMD::test_interleaved_matches_serial",
    "tests/test_attention.py::TestRingBshd::test_bshd_ring_pallas_bwd_matches_xla_dispatch",
    "tests/test_enc_dec_pipeline.py::TestEncDecPipeline::test_split_rank_changes_execution",
    "tests/test_attention.py::TestRingAttention::test_grouped_kv_grads_match_dense",
    "tests/test_attention.py::TestFlashBias::test_bshd_composed_gqa_varlen_dropout",
    "tests/test_transformer_tp.py::TestTP8Flagship::test_gpt_tp8_loss_and_grads_match_tp1",
    "tests/test_gpt_pipeline.py::TestGPTPipelineParity::test_pp2_interleaved_matches_single_device",
    "tests/test_gpt_pipeline.py::TestGPTPipelineParity::test_pp2_matches_single_device[flash]",
    "tests/test_contrib.py::TestDistributedOptimizers::test_zero_adam_matches_fused_adam",
    "tests/test_pipeline.py::TestPipelineSPMD::test_1f1b_loss_and_grads_match_serial",
    "tests/test_attention.py::TestFlashDropout::test_packed_fused_matches_bshd_same_seed",
    "tests/test_gpt_pipeline.py::TestContextParallelFlagship::test_gpt_cp_matches_full_sequence[ring]",
    "tests/test_gpt_pipeline.py::TestScheduleFeatureMatrix::test_dropout[2]",
    "tests/test_attention.py::TestVarlenFastPath::test_packed_fused_varlen_matches_bshd",
    "tests/test_transformer_tp.py::TestColumnRowParallel::test_headwise_matches_flat_call",
    # r7 (tp-overlap PR): the ring-overlap parity matrix joins tier-1, so
    # the heaviest remaining tests with a cheaper tier-1 sibling move here
    # (same rule as above — they still run under `-m ''` and on hardware):
    # each row names the sibling that keeps the family covered in tier-1.
    # (several of the un-jitted whales were instead made ~3-10x faster by
    # jitting their interpret-mode grads — see test_attention/test_t5.)
    "tests/test_t5.py::TestBucketedRelativeBias::test_bucketed_matches_materialized_flash",  # kernel-level: TestBucketedBias::test_kernel_fwd_bwd_vs_materialized
    "tests/test_models.py::TestResNet::test_train_and_eval_modes",  # examples: test_dcgan_example; resnet fwd: TestResNet shape tests
    "tests/test_moe.py::TestGPTMoE::test_gpt_moe_tp2_matches_tp1[True]",  # sibling [False] demoted in PR 2; dense parity: test_identical_experts_match_dense_gpt
    "tests/test_inference.py::TestDecodeEngine::test_greedy_matches_teacher_forced_full_forward[None]",  # see GQA [2] row below
    "tests/test_t5.py::TestRematPolicies::test_encode_only_matches_blocks",  # pipeline variant demoted in PR 2; policy parity: TestGPTAttentionAndRematVariants
    "tests/test_t5.py::TestRelativePositionBias::test_relative_model_trains_and_bias_matters",  # parity: test_relative_flash_matches_softmax stays
    "tests/test_permutation.py::TestSearch::test_exhaustive_finds_global_optimum",  # TestGreedyVsExhaustive stays tier-1
    "tests/test_pipeline.py::TestInterleavedV3Uneven::test_v3_uneven_grads_match_serial",  # v=2/v=4 interleaved parity stays (TestPipelineSPMD fast rows)
    "tests/test_examples.py::test_dcgan_example_o2",  # test_dcgan_example (O0) stays
    "tests/test_t5.py::TestEncoderPadding::test_padded_matches_unpadded_softmax",  # flash sibling test_flash_matches_softmax_padded_grads stays
    # r7 second pass: the full suite measured 997s on this host against the
    # 870s tier-1 wall, so the heaviest remaining redundantly-covered rows
    # move here too (same contract: `-m ''` and hardware still run them;
    # each row names the sibling that keeps its family covered in tier-1):
    "tests/test_inference.py::TestDecodeEngine::test_greedy_matches_teacher_forced_full_forward[2]",  # test_prefill_cache_matches_training_kv + test_decode_step_compiles_once + TestSampling::test_greedy_is_argmax stay
    "tests/test_attention.py::TestRingAttention::test_grads_match_dense[True]",  # [False] grads + test_matches_dense_full_sequence[True] (causal fwd) stay
    "tests/test_enc_dec_pipeline.py::TestEncDecPipeline::test_forward_matches_serial[1]",  # split [3] stays
    "tests/test_enc_dec_pipeline.py::TestEncDecPipeline::test_forward_matches_serial[2]",  # split [3] stays
    "tests/test_contrib.py::TestMultiheadAttn::test_fmha_varlen_cu_seqlens",  # kernel varlen: TestVarlenAttention::test_pallas_kernel_varlen_fwd_bwd stays
    "tests/test_inference.py::TestDecodeRelativeBias::test_engine_threads_the_hook",  # test_kernel_matches_xla_and_flash_oracle stays
    "tests/test_inference.py::TestDecodeEngine::test_sampled_generation_stays_in_topk_support",  # TestSampling::test_topk_restricts_support stays
    "tests/test_docs.py::test_amp_worked_example_executes",  # test_training_guide_blocks_execute_in_order still executes every guide block
    "tests/test_contrib.py::TestZeroHardening::test_zero_bf16_allgather_converges_close",  # test_zero_bf16_params_fp32_masters + test_zero_e5m2_allgather_converges stay
    "tests/test_attention.py::TestBucketedBias::test_kernel_fwd_bwd_vs_materialized[False-True]",  # [True-False] + remaining combos stay
    "tests/test_models.py::TestResNet::test_param_count_matches_torchvision",  # TestResNet shape tests stay
    "tests/test_contrib.py::TestBottleneckConv::test_spatial_bottleneck_strided_matches_unsharded",  # unstrided test_spatial_bottleneck_matches_unsharded stays
    "tests/test_attention.py::TestGroupedQueryAttention::test_bshd_layout_kernels_match_dense[4-4-128-False]",  # gqa ratios [4-1-128] and [4-2-128] stay
    "tests/test_attention.py::TestGroupedQueryAttention::test_bshd_layout_kernels_match_dense[1-1-64-False]",  # gqa ratios [4-1-128] and [4-2-128] stay
    "tests/test_attention.py::TestFlashBias::test_kernel_fwd_bwd_vs_dense[1-False]",  # [2-False]/[2-True] stay
    "tests/test_t5.py::TestEncoderPadding::test_padded_matches_unpadded_flash",  # test_flash_matches_softmax_padded_grads stays
    "tests/test_attention.py::TestCpDropout::test_ring_dropout_grads_match_autodiff",  # bshd sibling TestRingBshd::test_bshd_ring_dropout_grads_match_autodiff stays
    "tests/test_models.py::TestGPT::test_remat_matches_no_remat",  # TestGPTAttentionAndRematVariants::test_remat_policies_identical_loss_and_grads stays
    "tests/test_attention.py::TestRingBshd::test_bshd_ring_matches_flash[2]",  # [1] stays
    "tests/test_attention.py::TestLseCarrierForms::test_sliced_vs_carrier_identical",  # bshd variant test_bshd_sliced_vs_carrier_identical stays
    "tests/test_attention.py::TestGroupedQueryAttention::test_fused_qkv_attention_matches_composition[4-True]",  # [2-True] stays
    "tests/test_contrib.py::TestTransducer::test_loss_grad_finite",  # test_loss_matches_brute_force (alignment-enumeration oracle) stays
    "tests/test_attention.py::TestVarlenFastPath::test_bshd_kernel_varlen_matches_dense[2]",  # [1] + test_bert_varlen_rides_bshd_kernels stay
    "tests/test_attention.py::TestFlashDropout::test_kernel_matches_dense_same_mask[False]",  # [True] stays
    # r8 (continuous-batching serving PR): the heavy serving sweeps move
    # here (same contract: `-m ''` and hardware still run them; each row
    # names the sibling that keeps its family covered in tier-1):
    "tests/test_serving.py::TestServeBenchLeg::test_bench_serve_emits_valid_skip_record_off_tpu",  # subprocess sweep; record/CLI contract: TestServeRecord; engine churn: test_churn_schedule_recompile_free_and_leak_free stays
    "tests/test_serving.py::TestServingEngine::test_sampled_serving_uses_fused_tail_support",  # fused-tail support: TestFusedSample::test_topk_support stays; engine wiring: greedy parity test stays
    "tests/test_serving.py::TestPagedDecodeAttention::test_paged_with_bucketed_bias",  # unbiased paged parity test_paged_matches_contiguous stays; decode bias: test_inference TestDecodeRelativeBias stays
    # r9 (zero-bubble pipeline PR): the heaviest cells of the zb
    # schedule×feature matrix move here (same contract: `-m ''` and
    # hardware still run them; each row names the sibling that keeps its
    # family covered in tier-1):
    "tests/test_pipeline.py::TestZeroBubble::test_pp2_v1[True]",  # overlap at v=1: test_recompile_free_geometry_reuse[True] + pp2_v3[True] (overlap×interleaved) stay
    "tests/test_pipeline.py::TestZeroBubble::test_pp2_v3[False]",  # blocking interleaved zb: pp2_v3[True] + test_zb_v3_uneven_layer_count stay
    "tests/test_pipeline.py::TestZeroBubble::test_pp4_v1[True]",  # pp4 zb: pp4_v1[False] stays; overlap: pp2_v3[True] stays
    "tests/test_pipeline.py::TestZeroBubble::test_pp4_v3[False]",  # deepest matrix corner: pp4_v1[False] (pp4) + pp2_v3[True] (v=3) stay
    "tests/test_pipeline.py::TestZeroBubble::test_pp4_v3[True]",  # deepest matrix corner: same siblings as above
    "tests/test_pipeline.py::TestZeroBubble::test_zb_bf16_params_accumulate_fp32_main_grad",  # 1f1b bf16 sibling + GPT-level fp32-accum zb parity (test_zb_schedule[1]) stay
    "tests/test_gpt_pipeline.py::TestScheduleFeatureMatrix::test_zb_schedule[2]",  # [1] stays; interleaved zb parity: test_pipeline pp2_v3[True] stays
    "tests/test_monitor.py::TestPipelineBenchLeg::test_bench_pipeline_emits_valid_skip_record_off_tpu",  # record/validator/report contract: test_pipeline_record_emits_validates_and_reports stays
    # r10 (serving-telemetry PR): the heaviest full-engine telemetry
    # sweeps move here (same contract: `-m ''` and hardware still run
    # them; each row names the sibling that keeps its family covered in
    # tier-1):
    "tests/test_serve_telemetry.py::TestServeWindows::test_skip_windows_carry_reason",  # window emission: test_windows_emit_and_validate stays; SKIP-reason contract: test_telemetry_requires_skip_reason + TestReportAndValidator::test_emitter_honesty_on_windows stay
    "tests/test_serve_telemetry.py::TestReportAndValidator::test_aggregate_carries_window_summary_and_anomalies",  # timeline/report path: test_serve_timeline_rows_and_rendering stays; serve-record aggregation: test_serving TestServeRecord stays
    "tests/test_serve_telemetry.py::TestLifecycleStream::test_queue_wait_covers_held_admission",  # lifecycle stream: test_event_sequence_and_payloads stays; blocked-by counters: TestSchedulerTelemetrySeam::test_blocked_by_blocks_vs_slots stays (engine-free)
    # r11 (speculative-decoding PR): the heaviest full-engine spec
    # sweeps move here (same contract: `-m ''` and hardware still run
    # them; each row names the sibling that keeps its family covered
    # in tier-1):
    "tests/test_spec.py::TestServingSpec::test_churn_parity_model_drafter",  # model-drafter parity: TestDecodeEngineSpec::test_greedy_parity_both_drafters stays; serve churn parity: test_churn_parity_ngram stays
    "tests/test_spec.py::TestServingSpec::test_churn_parity_under_pool_pressure",  # preempt-during-spec rewind: TestRewindContract::test_all_rejected_round_restores_pool_state stays; plain churn parity: test_churn_parity_ngram stays
    "tests/test_spec.py::TestServingSpec::test_int8_spec_matches_int8_plain",  # int8 pool: TestQuantizedKV::test_logit_error_bounded_vs_float_oracle + test_quantized_serve_stream_is_reasonable stay; spec churn: test_churn_parity_ngram stays
    "tests/test_spec.py::TestDecodeEngineSpec::test_self_drafter_accepts_everything",  # parity: test_greedy_parity_both_drafters stays; acceptance accounting: TestServingSpec::test_spec_telemetry_events_and_acceptance stays
    "tests/test_spec.py::TestDecodeEngineSpec::test_sampled_spec_generates_within_bounds",  # sampled verify semantics: TestFusedVerify::test_kernel_matches_fallback_sampled + test_sampled_acceptance_is_exact_for_sure_things stay
    "tests/test_spec.py::TestDrafters::test_model_drafter_single_compile_across_streams",  # drafter-step cache pin: test_greedy_parity_both_drafters asserts md.engine.decode_step._cache_size() == 1
    "tests/test_spec.py::TestFusedVerify::test_kernel_handles_long_drafts[32]",  # [8] (the first width the old lane carrier broke at) stays tier-1; 32 is the same column operand
    # r12 (TP serving PR): the heaviest tp shard_map sweeps move here
    # (same contract: `-m ''` and hardware still run them; each row
    # names the sibling that keeps its family covered in tier-1):
    "tests/test_tp_serving.py::TestTPServingParity::test_churn_schedule_bitwise_vs_tp1[4]",  # [2] (same churn schedule, same asserts) stays
    "tests/test_tp_serving.py::TestTPServingParity::test_hot_swap_under_tp",  # tp=1 swap: test_serving TestHotSwap stays; tp re-shard path: churn [2] runs _prepare_params
    "tests/test_tp_serving.py::TestTPServingParity::test_int8_pool_bitwise_vs_tp1_int8",  # int8 pool semantics: test_spec TestQuantizedKV stays; tp parity: churn [2] stays
    "tests/test_tp_serving.py::TestDisaggHandoff::test_roundtrip_token_identical[2]",  # [1] (same digest/parity asserts) stays; tp serving parity: churn [2] stays
    "tests/test_tp_serving.py::TestDecodeEngineTP::test_generate_bitwise_vs_tp1[4]",  # [2] stays
    "tests/test_tp_serving.py::TestDecodeEngineTP::test_speculative_generate_bitwise",  # serving spec under tp: TestTPServingParity::test_spec_rounds_bitwise_vs_plain stays
    # r12 second pass: with the tp shard_map sweeps in, the full suite
    # measured ~1100s on this host against the 870s tier-1 wall, so the
    # heaviest remaining redundantly-covered rows move here too (same
    # contract: `-m ''` and hardware still run them; each row names the
    # sibling that keeps its family covered in tier-1):
    "tests/test_docs.py::test_inference_api_blocks_execute_in_order",  # needle test test_inference_doc_covers_serving_contract stays; every engine claim the blocks make is a tier-1 test in test_serving/test_tp_serving; like the guide blocks, `-m ''` still executes them
    "tests/test_docs.py::test_prof_api_blocks_execute_in_order",  # test_observability_blocks_execute_in_order (capture->report->calibrate superset) stays; `-m ''` still executes the prof blocks
    "tests/test_ckpt.py::TestHotSwapFromCheckpoint::test_restore_params_swaps_token_identically",  # swap contract: test_serving TestHotSwap equal/different-weights rows stay; restore fidelity: TestShardedSameDp::test_fp32_params_ride_the_params_buffer stays
    "tests/test_ckpt.py::TestCkptBenchLeg::test_in_process_smoke",  # record/validator contract: TestCkptRecord::test_emit_and_validate_ok stays; history gating: test_bench_history_gates_save_overhead stays
    "tests/test_ckpt.py::TestShardedSameDp::test_bitwise_resume_bf16_masters",  # fp32-path bitwise restore rows (test_fp32_params_ride_the_params_buffer + TestScalerOverflowRoundtrip) stay; bf16-master semantics: test_contrib TestZeroHardening::test_zero_bf16_params_fp32_masters stays
    "tests/test_ckpt.py::TestElasticResize::test_trajectory_parity_dp8_to_dp4",  # the grow direction test_trajectory_parity_dp4_to_dp8 stays
    "tests/test_pipeline.py::TestZeroBubble::test_pp2_v1[False]",  # blocking v=1 zb: pp4_v1[False] stays; GPT-level zb parity: test_gpt_pipeline test_zb_schedule[1] stays
    "tests/test_pipeline.py::TestZeroBubble::test_per_device_work_counters_show_v2_bubble_shrink",  # counter closed form: test_zb_work_counters_closed_form[True] stays
    "tests/test_pipeline.py::TestBuildSchedule::test_end_to_end_with_calculator",  # schedule choice rows (test_picks_microbatches_and_schedule + test_interleaved_partial) stay; calculator pricing: test_plan TestCalculator rows stay
    "tests/test_monitor.py::TestProfileBenchLeg::test_bench_profile_emits_valid_skip_record_off_tpu",  # record/validator contract: TestProfileRecord::test_emit_roundtrip_and_validation stays
    "tests/test_monitor.py::TestSpans::test_overlap_ring_emits_ring_span",  # ring-collective accounting: TestTPCollectiveCounts::test_overlap_ring_ppermute_counted stays
    "tests/test_plan.py::TestPlanConsumption::test_planned_config_grad_parity_vs_hand_config",  # plan->config routing: test_gpt_config_routes_through_plan + test_make_mesh_consumes_plan stay; the underlying configs' grad parity is test_models territory
    "tests/test_trace.py::TestValidatorTrace::test_trace_family_dispatch",  # subprocess CLI sweep; schema/honesty rows (test_closed_schema_rejects_junk_key + test_nan_in_ok_record_fails_honesty) stay
    "tests/test_collective_matmul.py::TestLayerParityMatrix::test_overlap_matches_blocking[sp-3]",  # [sp-2] + GPT-level [sp] stay
    "tests/test_collective_matmul.py::TestLayerParityMatrix::test_overlap_matches_blocking[sp-4]",  # [sp-2] + GPT-level [sp] stay
    "tests/test_collective_matmul.py::TestLayerParityMatrix::test_overlap_matches_blocking[nosp-3]",  # [nosp-2] + GPT-level [nosp] stay
    "tests/test_collective_matmul.py::TestLayerParityMatrix::test_overlap_matches_blocking[nosp-4]",  # [nosp-2] + GPT-level [nosp] stay
    "tests/test_models.py::TestGPTAttentionAndRematVariants::test_gqa_flash_matches_softmax_impl",  # kernel-level GQA parity (TestGroupedQueryAttention ratios [4-1-128]/[4-2-128]) + test_attention_impls_agree stay
    "tests/test_attention.py::TestBucketedBias::test_ring_bias_and_kv_lens_match_flash",  # kernel vs materialized: test_kernel_fwd_bwd_vs_materialized[True-False] stays; ring parity: TestRingBshd::test_bshd_ring_matches_flash[1] stays
    "tests/test_attention.py::TestBucketedBias::test_bshd_composed_gqa_varlen_dropout",  # kernel vs materialized row stays; varlen+dropout composition: TestVarlenFastPath::test_bshd_varlen_with_dropout stays
    "tests/test_attention.py::TestGroupedQueryAttention::test_fused_qkv_attention_matches_composition[4-False]",  # [2-True] stays
    "tests/test_attention.py::TestGroupedQueryAttention::test_bshd_layout_kernels_match_dense[4-4-128-True]",  # gqa ratios [4-1-128] and [4-2-128] stay
    "tests/test_attention.py::TestGroupedQueryAttention::test_bshd_layout_kernels_match_dense[1-1-64-True]",  # gqa ratios [4-1-128] and [4-2-128] stay
    "tests/test_attention.py::TestFlashBias::test_kernel_fwd_bwd_vs_dense[1-True]",  # [2-False]/[2-True] stay
    "tests/test_attention.py::TestCpDropout::test_ring_dropout_deterministic_and_live",  # keyed ring dropout: TestRingBshd::test_bshd_ring_dropout_grads_match_autodiff stays
    "tests/test_t5.py::TestEncoderDecoderModel::test_trains",  # test_loss_finite_and_deterministic + causality/cross-attn rows stay; enc-dec training parity: TestEncDecPipeline stays under `-m ''`
    "tests/test_t5.py::TestEncoderPadding::test_padding_composes_with_relative_bias",  # test_flash_matches_softmax_padded_grads + test_relative_flash_matches_softmax stay
    "tests/test_moe.py::TestGPTMoE::test_gpt_moe_trains_and_surfaces_drops",  # dense parity: test_identical_experts_match_dense_gpt stays; grads: TestMoEGrads::test_grads_flow_to_experts_and_router stays
    "tests/test_moe.py::TestRouter::test_identical_experts_reduce_to_dense_mlp",  # GPT-level test_identical_experts_match_dense_gpt stays
    "tests/test_gpt_pipeline.py::TestScheduleFeatureMatrix::test_zb_overlap_p2p",  # overlap x interleaved zb: test_pipeline pp2_v3[True] stays; GPT-level zb parity: test_zb_schedule[1] stays
    "tests/test_contrib.py::TestZeroLossScaling::test_overflow_composes_with_zb_pipeline_across_dp_tp_pp",  # scaler semantics: test_fp16_grads_keep_fp32_reduction stays; zb bf16 accum: test_pipeline 1f1b bf16 row stays
    "tests/test_contrib.py::TestZeroHardening::test_zero_adam_50_step_convergence_matches_unsharded",  # test_zero_bf16_params_fp32_masters + test_zero_e5m2_allgather_converges stay
    "tests/test_contrib.py::TestMultiheadAttn::test_additive_attn_mask_fused",  # test_probs_dropout_semantics stays; kernel-level bias path: TestFlashBias [2-True] stays
    "tests/test_serving.py::TestHotSwap::test_unreached_swap_is_dropped_not_leaked",  # equal-weights + different-weights swap rows stay
    "tests/test_serve_telemetry.py::TestServingTier2Telemetry::test_window_and_final_fields_validate_with_tier2_keys",  # window validation: TestServeWindows::test_windows_emit_and_validate stays; tier-2 lifecycle: test_evict_lifecycle_through_real_preemption stays
    "tests/test_spec.py::TestDecodeEngineSpec::test_all_rejected_drafter_still_exact",  # rewind contract: TestRewindContract::test_all_rejected_round_restores_pool_state stays; parity: test_greedy_parity_both_drafters stays
    "tests/test_inference.py::TestDecodeAttentionOp::test_xla_and_kernel_match_oracle[8]",  # [1] stays
    "tests/test_ops.py::TestXentropy::test_loss_and_grad[0.0]",  # smoothing [0.1] stays
    "tests/test_transformer_tp.py::TestVocabParallelCrossEntropy::test_matches_unsharded[0.0]",  # test_grad_matches_unsharded + kernel-path [0.0]/[0.1] rows stay
    "tests/test_aux.py::TestRNN::test_shapes_and_grads[LSTM]",  # [GRU]/[mLSTM] factory rows stay
    "tests/test_megatron_surface.py::TestGPTScaling::test_width_depth_scaling[128-4]",  # [64-2] stays
    "tests/test_permutation.py::TestSearch::test_greedy_on_random_conv_net",  # TestGreedyVsExhaustive stays tier-1
    "tests/test_serving.py::TestServingTier2::test_prefix_hit_parity_and_skipped_chunks",  # prefix-cache rows test_whole_prompt_cached_recomputes_last_block + test_preemption_roundtrip_token_identical stay; hit accounting: test_tp_serving TestDisaggHandoff roundtrip [1] asserts prefix_hit_blocks
    "tests/test_t5.py::TestRelativePositionBias::test_relative_decoder_ignores_future",  # causality: TestEncoderDecoderModel::test_decoder_is_causal stays; relative-bias parity: test_relative_flash_matches_softmax stays
    "tests/test_docs.py::test_ckpt_api_blocks_execute_in_order",  # needle test test_ckpt_doc_covers_the_contract stays; `-m ''` still executes the blocks
    "tests/test_trace.py::TestAttribution::test_emitted_record_validates",  # test_components_sum_to_e2e_on_mixed_run stays; record validation: TestValidatorTrace junk/nan rows stay
    "tests/test_attention.py::TestRingBshd::test_bshd_ring_grads_match_flat_ring",  # test_bshd_ring_matches_flash[1] + the bshd ring dropout grads row stay
    "tests/test_models.py::TestBert::test_flash_impl_matches_softmax_on_suffix_padding",  # kernel-level bert padding path: test_attention test_bert_varlen_rides_bshd_kernels stays
    "tests/test_gpt_pipeline.py::TestGPTPipelinePartition::test_dropout_requires_key",  # keyed-dropout contract: test_dropout_interleaved_schedule stays
    "tests/test_attention.py::TestUlyssesAttention::test_matches_dense_full_sequence[False]",  # ulysses grads row test_grads_match_dense stays
}


def pytest_collection_modifyitems(config, items):
    if os.environ.get("APEX_TPU_TEST_ON_TPU"):
        return
    for item in items:
        if item.nodeid in _SLOW_OFF_TPU:
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def mesh8():
    """A dp=8 mesh, the default decomposition for DP tests."""
    from apex_tpu.parallel import mesh as mesh_lib

    m = mesh_lib.initialize_model_parallel(1, 1)
    yield m
    mesh_lib.destroy_model_parallel()


@pytest.fixture
def mesh_tp4_dp2():
    from apex_tpu.parallel import mesh as mesh_lib

    m = mesh_lib.initialize_model_parallel(tensor_model_parallel_size=4)
    yield m
    mesh_lib.destroy_model_parallel()


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    yield
    from apex_tpu.parallel import mesh as mesh_lib

    mesh_lib.destroy_model_parallel()


def assert_devices(n: int = 8):
    assert jax.device_count() >= n, f"expected >= {n} devices, got {jax.device_count()}"
