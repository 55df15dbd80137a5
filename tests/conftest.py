"""Test harness: run every test on a virtual 8-device CPU mesh.

This is the standard JAX fake-backend trick (SURVEY.md §4): force the host
platform to expose 8 devices so multi-client mesh code runs (and collectives
execute) without TPU hardware. Must be set before jax initializes.
"""

import contextlib
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# libtpu is installed here without a TPU or a metadata service: a
# subprocess test that drops the CPU pin (test_graft_entry strips
# XLA_FLAGS/JAX_PLATFORMS) would spend minutes in libtpu's metadata
# lookups before giving up. Skip the query so backend discovery fails
# fast; subprocesses inherit it.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
# Every entry point keeps a persistent compile cache at one fixed path in
# the checkout (fedtpu.compilation.resolve_cache_dir). Tests must not read
# or write it: an entry left by an earlier run would make a test's outcome
# depend on history. Off for this process and every subprocess it starts;
# the tests of the cache itself turn it back on in their own environment.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8").strip()

# XLA:CPU runs a program's 8 per-device executions on ONE thread pool whose
# size is the machine's core count (or NPROC, XLA's own variable for a CI's
# CPU reservation), and a collective blocks one pool thread per device
# until all 8 have arrived. On an 8-core box that leaves no thread for
# anything else the client schedules there (the next tick's launch, its
# transfers): two participants never get a thread, the rendezvous waits
# ("only 6 of them arrived"), and after 60 s XLA aborts the interpreter,
# "Fatal Python error: Aborted" in whichever test dispatches collective
# programs back to back (test_serving.py, test_robust.py; 8 aborts in 90
# runs of one such test alone beside five others, none in 84 with the pool
# doubled). Twice the devices, unless the environment reserves more;
# subprocesses inherit it.
os.environ["NPROC"] = str(max(int(os.environ.get("NPROC") or 0), 16))

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}")

import pytest  # noqa: E402

# ---------------------------------------------------------------- quick tier
# `pytest -m quick` — the CI-fast tier (VERDICT r1 item 7). Round-3
# re-tune: the r2 selection had crept to 2:42 on this box and was
# re-profiled with --durations and trimmed twice; measured 110 s on the
# quiet 1-core verification box, up to ~2:15 when the box is contended
# (the spread is host load, not the selection — the same set varied
# 110-134 s across one afternoon). At least one test from EVERY
# in-process test module (so a quick run still touches every fedtpu
# subsystem; the two subprocess modules are excluded by name below).
# The full suite (259 tests, ~25 min on this box) remains the merge
# gate; the quick tier is the inner-loop iteration gate. Names, not
# patterns, so a typo'd or gone-stale entry fails loudly via the
# consistency guards at the bottom of pytest_collection_modifyitems
# below.
QUICK_TESTS = {
    # the seam of a model: the imports' direction, by the source (no backend)
    "test_model_seam.py::test_the_registry_is_a_table",
    "test_model_seam.py::"
    "test_every_scope_is_a_name_of_the_one_file_that_writes_it",
    # OLMoE against the plain reference; the shared-global engine's refusals
    "test_olmoe.py::test_top_k_sets_are_the_references_in_float32",
    # the tiled attention core's table against a numpy count (no kernel)
    "test_packed_attention.py::"
    "test_a_step_left_out_holds_the_block_of_the_next_step_that_runs",
    # the hybrid stack: the pattern's letters, the held block's size
    "test_nemotron_h.py::test_the_held_block_is_whole_tiles_at_eight_thirds_of_the_mean",
    # the four-stream stack: the prediction module's targets
    "test_xing4.py::test_the_modules_targets_and_validity_at_document_edges",
    # the delta-rule stack: what its layer lists and its scan refuse
    "test_kimi_linear.py::test_what_the_registry_refuses",
    "test_solar_open2.py::"
    "test_layer_kinds_from_the_published_lists_and_what_the_registry_refuses",
    # the decoder-hybrid-decoder stack: what its held layers must satisfy
    "test_phi4_flash.py::test_what_the_registry_refuses",
    # the recurrence's kernels: the one operand their masks come from
    "test_kda_scan_kernels.py::test_how_far_back_a_positions_run_reaches",
    # the residual modules' kernels: the rule between the two bodies
    "test_hyper_conn_kernels.py::test_the_rule_between_the_bodies",
    # the selective scan's kernels: the rule between the two bodies
    "test_selective_scan_kernels.py::test_the_rule_between_the_bodies",
    "test_stateless_round.py::"
    "test_minibatches_need_the_stateless_engine_and_a_known_client_state",
    # the stage of each operation from a compiled program's text (pure text)
    "test_round_tracing.py::"
    "test_program_scopes_reads_the_stage_of_each_operation",
    # an AOT compile for a described v5e:2x2 (no chip needed, seconds)
    "test_aot_tpu_compile.py::"
    "test_convnet_training_pass_keeps_one_full_resolution_copy",
    # chip_smoke.py off the chip: the seeded CSV (pure numpy/pandas)
    "test_chip_smoke.py::"
    "test_income_csv_has_the_reference_shape_and_is_seeded",
    "test_chip_smoke.py::test_income_csv_goes_through_the_host_pipeline",
    # where the compile cache is placed (no compile happens: spied run)
    "test_compilation.py::"
    "test_run_keeps_the_cache_where_it_was_placed[flag-env]",
    "test_compilation.py::"
    "test_run_keeps_the_cache_where_it_was_placed[no-flag-no-env]",
    "test_compilation.py::test_no_cache_directory_comes_from_tempfile",
    # round-3 modules
    "test_advisor_r3.py::"
    "test_sync_early_stop_exit_gate_catches_poisoned_state",
    "test_dp_accountant.py::test_abadi_et_al_canonical_value",
    "test_dp_accountant.py::test_full_participation_matches_closed_form",
    "test_dp_accountant.py::test_monotonicity",
    "test_dp_accountant.py::test_edge_cases",
    "test_sweep.py::test_plateau_stop_freezes_exactly_at_the_plateau_point",
    "test_stop_lag.py::test_fedtpu_stops_at_the_reference_trained_round_count",
    "test_checkpoint.py::test_latest_step_skips_half_written_rounds",
    "test_checkpoint.py::test_retention_keeps_k_newest_plus_protected",
    "test_combo_matrix.py::"
    "test_combo_round_executes_or_raises_cleanly[plain-none]",
    "test_combo_matrix.py::"
    "test_combo_round_executes_or_raises_cleanly[median-sample]",
    "test_convnet.py::test_convnet_accepts_nhwc_and_flat_inputs",
    "test_local_steps.py::test_local_steps_equals_rounds_for_single_client",
    # aux subsystems (cifar fallback, multihost in-process; the divergence
    # halt is quick-covered by test_pipelined_stop's variant)
    "test_aux_subsystems.py::test_cifar10_synthetic_fallback_shapes",
    "test_aux_subsystems.py::test_synthetic_cifar_deterministic",
    "test_aux_subsystems.py::test_multihost_single_process_paths",
    "test_aux_subsystems.py::test_local_client_slice_multiprocess_simulated",
    "test_aux_subsystems.py::test_looks_multihost_env_detection",
    "test_aux_subsystems.py::test_lazy_top_level_api_resolves",
    "test_chunk_regressions.py::test_no_checkpoint_after_midchunk_early_stop",
    "test_cli.py::test_presets_listing",
    "test_cli.py::test_sweep_bad_table_path_fails_fast",
    "test_cli.py::test_run_new_aggregation_flags_reach_config",
    "test_compilation.py::test_fingerprint_moves_with_the_program",
    "test_compilation.py::test_executor_dedupes_blocks_and_reraises",
    "test_compilation.py::"
    "test_fingerprint_is_stable_across_concrete_and_abstract_args",
    "test_compress.py::test_quantize_roundtrip_error_bound",
    "test_compress.py::test_quantize_zero_delta_is_exact",
    "test_compress.py::test_quantize_preserves_extremes",
    "test_compress.py::test_dequantize_broadcasts_gathered_scales",
    "test_compress.py::test_compress_rejects_delta_path_and_ring",
    "test_compress.py::test_compress_rejects_state_without_shared_start",
    "test_data.py::test_synthetic_dataset_shapes",
    "test_data.py::test_income_csv_pipeline_matches_reference_semantics",
    "test_data.py::test_split_bit_parity_with_sklearn",
    "test_data.py::test_contiguous_shards_partition_with_remainder",
    "test_data.py::test_shared_seed_shuffle_is_a_partition",
    "test_data.py::test_unseeded_bug_parity_shards_overlap",
    "test_data.py::test_dirichlet_shards_partition_and_skew",
    "test_data.py::test_pack_clients_masks_and_counts",
    "test_fedavg.py::test_weighted_average_matches_numpy_oracle",
    "test_fedavg.py::test_uniform_average_matches_plain_mean",
    "test_fedavg.py::test_unequal_shards_weight_by_true_counts",
    "test_fedavg.py::test_optimizer_state_is_not_averaged",
    "test_graft_entry.py::"
    "test_dryrun_after_backend_init_without_flag_raises_cleanly",
    "test_loop.py::test_run_experiment_history_shapes",
    "test_metrics.py::test_metrics_match_sklearn[2-0]",
    "test_metrics.py::test_zero_division_semantics",
    "test_metrics.py::test_mask_excludes_padding",
    "test_metrics.py::test_summed_confusions_equal_concatenated_predictions",
    "test_multiround.py::test_chunked_early_stop_truncates_history",
    "test_native_loader.py::test_income_csv_native_matches_pandas",
    "test_native_loader.py::test_quoting_crlf_and_missing_trailing_newline",
    "test_native_loader.py::test_ragged_row_is_an_error",
    "test_optim.py::test_adam_steplr_matches_torch_trajectory",
    "test_optim.py::test_schedule_staircase_boundaries",
    "test_optim.py::test_onehot_ce_equals_gather_ce",
    "test_parity.py::test_limitation_demonstrated",
    "test_participation.py::test_sampled_average_over_participants_only",
    "test_program_audit.py::test_extract_schedule_counts_psum_bytes",
    "test_program_audit.py::test_branch_divergent_schedule_flags_aud001",
    "test_program_audit.py::test_donation_proof_flags_unaliased_aud002",
    "test_audit_gate.py::test_goldens_are_clean_contracts",
    "test_personalize.py::test_personalize_rejects_zero_steps",
    "test_pipelined_stop.py::test_pipelined_divergence_still_halts",
    "test_privacy_ledger.py::test_checkpoint_meta_roundtrips_exactly",
    "test_privacy_ledger.py::test_zero_order_overlap_projects_finite_not_inf",
    "test_privacy_ledger.py::test_noise_off_resume_never_zeroes"
    "_restored_spend",
    "test_privacy_ledger.py::test_guarantee_void_when_training_unnoised"
    "_after_noised",
    "test_review_fixes.py::test_numeric_labels_reencoded_to_contiguous_indices",
    "test_review_fixes.py::test_empty_shards_excluded_from_client_mean",
    "test_ring.py::test_ring_matches_global_sum[shape0-ring_all_reduce_sum]",
    "test_ring.py::test_ring_matches_global_sum"
    "[shape0-ring_all_reduce_sum_rsag]",
    "test_ring.py::test_pallas_rdma_ring_matches_global_sum[shape0]",
    "test_robust.py::test_median_matches_numpy_oracle",
    "test_robust.py::test_trimmed_mean_matches_numpy_oracle",
    "test_robust.py::test_krum_matches_numpy_oracle",
    "test_robust.py::test_geometric_median_matches_numpy_weiszfeld",
    "test_robust.py::test_robust_rejects_bad_combos",
    "test_robust.py::test_weiszfeld_iteration_budget_converges",
    "test_robust_defense.py::"
    "test_poisoned_user_ids_is_deterministic_and_validated",
    "test_robust_defense.py::test_trace_reader_rejects_future_schema",
    "test_robust_defense.py::"
    "test_defense_sim_compare_reports_first_divergence",
    "test_robust_defense.py::test_cohort_sampler_refuses_quarantined_ids",
    "test_round_smoke.py::test_empty_hidden_sizes_is_logistic_regression",
    "test_server_opt.py::test_update_rules_match_numpy_oracle",
    "test_server_opt.py::test_clip_by_global_norm_is_per_client_joint",
    "test_server_opt.py::test_unknown_server_opt_rejected",
    "test_server_opt.py::test_missing_server_state_is_a_clear_error",
    "test_server_opt.py::test_stale_server_state_is_a_clear_error",
    "test_server_opt.py::test_dp_noise_requires_clip",
    "test_timing.py::test_force_fetch_returns_scalar_from_tree",
    "test_timing.py::test_force_fetch_depends_on_computation",
    "test_timing.py::test_force_fetch_refuses_host_only_trees",
    "test_timing.py::test_flops_floor_passes_above_and_raises_below",
    "test_timing.py::test_timer_laps",
    "test_tp.py::test_mesh_2d_shape",
    "test_tp.py::test_unsupported_combos_raise",
    "test_tp.py::test_per_device_state_bytes_scale_down_with_tp",
    # round-4 modules
    # telemetry subsystem (tracer/report/satellites; backend-free picks)
    "test_telemetry.py::test_event_schema_roundtrip",
    # causal fleet tracing (docs/observability.md): trace_id/flight
    # recorder/merged identity keying are backend-free milliseconds;
    # the sim golden gate stays full-tier (it compiles the engines).
    "test_timeline.py::test_trace_id_deterministic_across_retry",
    "test_timeline.py::test_flight_recorder_ring_bounds",
    "test_timeline.py::test_merged_report_keys_colliding_run_ids",
    "test_timeline.py::test_timeline_merges_and_orders_chains",
    "test_telemetry.py::test_drop_nonwinning_weights_frees_losers",
    "test_telemetry.py::test_no_bare_prints_outside_allowlist",
    "test_scaffold.py::test_server_cv_is_mean_of_client_cv",
    "test_scaffold.py::test_incompatible_combos_raise",
    "test_adaptive_clip.py::test_effective_delta_noise_multiplier_identity",
    "test_adaptive_clip.py::test_one_round_clip_update_matches_oracle",
    "test_async.py::test_guards",
    "test_async.py::test_staleness_bookkeeping_under_sampling",
    # round-5 modules
    # static-analysis subsystem (rule engine is pure AST — both picks are
    # backend-free and fast)
    "test_analysis.py::test_rule_fixtures_catch_seeded_violations",
    "test_analysis.py::test_text_reporter_golden",
    "test_lint_gate.py::test_repo_lint_gate_is_clean",
    # concurrency/determinism auditor (PR 17): the lockdep drills and the
    # fixed-finding regressions are backend-free and run in milliseconds;
    # the subprocess exit-code fold stays full-tier.
    "test_lockdep.py::test_abba_ordering_is_detected_as_a_cycle",
    "test_lockdep.py::test_drills_match_committed_golden_bitwise",
    "test_concurrency_fixes.py::"
    "test_send_msg_bytes_are_canonical_across_insertion_order",
    "test_concurrency_fixes.py::"
    "test_reshard_handler_fires_while_main_thread_polls",
    # test_multihost_e2e spawns 2 OS processes (~70 s for the round-kernel
    # worker since the int8/Byzantine sections joined) and stays full-tier
    # only; fedtpu/parallel/multihost.py is covered above in-process.
    # test_chaos_resume SIGKILLs subprocess CLI runs (~60 s) and stays
    # full-tier only; the resume machinery is covered by test_checkpoint.
    # round-6 modules
    # resilience subsystem (fault plans, rollback, supervisor contract —
    # both picks are backend-free and run in milliseconds)
    "test_resilience.py::test_plan_spec_forms_are_identical",
    "test_resilience.py::test_chunk_limit_isolates_fault_rounds",
    # round-7 modules
    # serving subsystem (admission + trace schema — both backend-free,
    # milliseconds; the engine/socket tests stay full-tier)
    "test_serving.py::"
    "test_admission_check_order_is_rate_backpressure_staleness",
    "test_serving.py::test_trace_roundtrip_and_header",
    # round-8 modules
    # cohort subsystem (sampler + store are backend-free numpy,
    # milliseconds; the parity/resume/RSS tests stay full-tier)
    "test_cohort.py::test_sampler_uniform_full_population_is_identity",
    "test_cohort.py::test_store_roundtrip_memory_and_mmap",
    "test_cohort.py::test_cohort_config_guards",
    # test_chaos_supervised runs supervised subprocess CLI children
    # (kill + restart, ~90 s) and stays full-tier only; the in-process
    # resilience semantics are covered by test_resilience above.
    # round-9 modules
    # elastic reshard (planner + controller are backend-free numpy/
    # filesystem, milliseconds; the integrated shrink/grow loop tests
    # stay full-tier)
    "test_reshard.py::test_row_maps",
    "test_reshard.py::test_spool_roundtrip_and_generation_fence",
    "test_reshard.py::test_signal_agreement_converges",
    # round-10 modules
    # autoscale control plane (policy/bus/simulator are backend-free,
    # seconds; the engine integration, report merge, and chaos drill
    # stay full-tier)
    "test_autoscale.py::test_simulate_decision_sequence_is_bitwise"
    "_deterministic",
    "test_autoscale.py::test_threshold_policy_requires_consecutive"
    "_hot_ticks",
    "test_autoscale.py::test_signal_bus_folds_stats_and_prefers"
    "_exported_burn",
    # round-11 modules
    # gateway fleet (routing/redirect/session-dedup are backend-free or
    # tiny-engine, milliseconds-to-seconds; the socket fleet and chaos
    # rows stay full-tier)
    "test_gateway.py::test_owner_of_and_redirect_msg",
    "test_gateway.py::test_client_partition_matches_gateway_owner",
    "test_gateway.py::test_retried_frame_incorporated_exactly_once",
    # round-12 modules
    # wire faults (plan materialization, the scenario registry pin, and
    # the streaming line cap are backend-free, milliseconds; the proxy
    # end-to-end, the net-sim golden, and the live chaos rows stay
    # full-tier)
    "test_netfaults.py::test_plan_spec_forms_are_identical",
    "test_netfaults.py::test_plan_validation_rejects_bad_entries",
    "test_netfaults.py::test_scenario_registry_is_single_source_of_truth",
    "test_netfaults.py::test_line_cap_streams_bounded_and_connection"
    "_survives",
    # round-13 modules
    # MPMD round pipelining (PR 18): the width-1 two-program DAG parity
    # run is the fastest compile in the module (~seconds); the golden
    # contract check is pure JSON, milliseconds. The chain/SIGTERM/
    # trace-chain parity runs stay full-tier.
    "test_mpmd.py::test_mpmd_width1_matches_monolithic_bitwise",
    "test_mpmd_audit_gate.py::test_mpmd_goldens_are_clean_contracts",
    # round-14 modules
    # compositional chaos fuzzing (PR 19): campaign digests, the oracle
    # library, and the chaos-bar equivalence pins are backend-free,
    # milliseconds; the multi-campaign sweep and ddmin-from-noise runs
    # stay full-tier. The corpus bitwise-replay gate itself runs quick
    # via test_corpus_campaigns... in the tier-1 flow (seconds).
    "test_fuzz.py::test_campaign_digest_roundtrip",
    "test_fuzz.py::test_sampler_is_deterministic_and_covers"
    "_the_fault_space",
    "test_fuzz.py::test_judge_gateway_kill_matches_legacy"
    "_mp_gateway_kill_bar",
    "test_fuzz.py::test_judge_net_row_matches_legacy_mp_torn_frame_bar",
    "test_fuzz.py::test_restart_backoff_is_a_pure_function_of_exit"
    "_and_streak",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "quick: CI-fast tier (<2 min) touching every test module; "
        "run with `pytest -m quick`")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` flow (ROADMAP.md); "
        "full-tier only")


def pytest_collection_modifyitems(config, items):
    matched = set()
    modules_all = set()
    modules_quick = set()
    for item in items:
        rel = item.nodeid.split("tests/")[-1]
        modules_all.add(rel.split("::")[0])
        if rel in QUICK_TESTS:
            item.add_marker(pytest.mark.quick)
            matched.add(rel)
            modules_quick.add(rel.split("::")[0])
    # Consistency guards — scoped to what was actually collected, so
    # single-file and --ignore runs never false-positive:
    quick_modules_expected = {t.split("::")[0] for t in QUICK_TESTS}
    if quick_modules_expected <= modules_all:
        # Every module QUICK_TESTS references was collected, so every entry
        # must have matched a real test — anything left is stale/renamed.
        stale = QUICK_TESTS - matched
        if stale:
            raise pytest.UsageError(
                f"conftest QUICK_TESTS entries match nothing (renamed or "
                f"removed tests?): {sorted(stale)}")
    uncovered = (modules_all - modules_quick
                 - {"test_multihost_e2e.py", "test_chaos_resume.py",
                    "test_chaos_supervised.py", "test_gang_resilience.py"}
                 if quick_modules_expected <= modules_all else set())
    if uncovered:
        raise pytest.UsageError(
            f"test modules with no quick-tier test: {sorted(uncovered)}")


# ------------------------------------------------------- native-cache hygiene
# The suite compiles hundreds of XLA programs; the executables (and their
# buffers) hold memory MAPPINGS for the life of the pytest process, and
# past the kernel's vm.max_map_count (65530 by default) the next native
# mmap fails and aborts the interpreter. Dropping JAX's compilation caches
# releases the executables. The cure follows what it can observe: after
# every test the process counts its own mappings and clears once they pass
# half the limit, however xdist deals tests to workers. Under the driver's
# six workers no worker passed 13,300 mappings in a whole run with no
# clearing at all (PR 27), so there it costs one read of /proc/self/maps
# a test and no recompile; one process running the whole suite holds the
# six workers' sum (42,000 then), which is what the guard is for.
def _map_limit() -> float:
    try:
        with open("/proc/sys/vm/max_map_count", encoding="ascii") as fh:
            return 0.5 * int(fh.read())
    except (OSError, ValueError):
        return 0.5 * 65530


_MAP_LIMIT = _map_limit()


def _mapping_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as fh:
            return sum(1 for _ in fh)
    except OSError:  # no procfs: nothing to observe, nothing to clear
        return 0


@pytest.fixture(autouse=True)
def _clear_jax_caches_near_map_limit():
    yield
    if _mapping_count() > _MAP_LIMIT:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture
def grouped_on_the_cpu(monkeypatch):
    """The whole model through the Pallas body of the expert matmuls
    (``fedtpu.ops.grouped_matmul``; every held-experts layer calls it too):
    the rule between the bodies is steered to it and the
    kernels interpreted (always under jit: the interpreter is not for eager
    use)."""
    from jax.experimental.pallas import tpu as pltpu

    from fedtpu.ops import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "grouped_matmul_applies",
                        lambda xs, w: True)
    with pltpu.force_tpu_interpret_mode():
        yield


@contextlib.contextmanager
def tiled_passes_interpreted(patch):
    """The hybrid stack's state-space mixers through the tiled bodies of
    their two float32 passes (``fedtpu.ops.ssm_passes``) on the CPU: the
    rule between the bodies (``ssm_passes.fused_passes_apply``) is steered
    to them through ``patch`` (a ``MonkeyPatch``) and the kernels
    interpreted (always under jit, as above). The interpreter works through
    ordered callbacks, which ``jax.checkpoint`` cannot hold, so the stack's
    layers are not recomputed here: that moves no value."""
    from jax.experimental.pallas import tpu as pltpu

    from fedtpu.ops import ssm_passes

    patch.setattr(ssm_passes, "fused_passes_apply", lambda cfg, t: True)
    patch.setattr(jax, "checkpoint", lambda fn, **policy: fn)
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture
def tiled_passes_on_the_cpu(monkeypatch):
    """``tiled_passes_interpreted`` for one test."""
    with tiled_passes_interpreted(monkeypatch):
        yield


@pytest.fixture
def hyper_passes_on_the_cpu(monkeypatch):
    """The four-stream stack's residual modules through the tiled bodies of
    their passes over the streams (``fedtpu.ops.hyper_conn``) on the CPU, as
    ``tiled_passes_interpreted`` drives the hybrid stack's: the rule between
    the bodies (``hyper_conn.hyper_passes_apply``) steered to them, the kernels
    interpreted (always under jit), no layer recomputed."""
    from jax.experimental.pallas import tpu as pltpu

    from fedtpu.ops import hyper_conn

    monkeypatch.setattr(hyper_conn, "hyper_passes_apply", lambda x: True)
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **policy: fn)
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture
def fused_scan_on_the_cpu(monkeypatch):
    """The decoder-hybrid-decoder stack's Mamba-1 mixers through the two
    kernels of their selective scan (``fedtpu.ops.selective_scan``) on the
    CPU, as ``tiled_passes_interpreted`` drives the hybrid stack's passes:
    the rule between the bodies (``selective_scan.fused_scan_applies``)
    steered to them at blocks of 128 positions and tiles of 128 channels
    (what a tiny model's rows and inner width are), the kernels interpreted
    (always under jit), no layer recomputed."""
    from jax.experimental.pallas import tpu as pltpu

    from fedtpu.ops import selective_scan

    monkeypatch.setattr(selective_scan, "fused_scan_applies",
                        lambda t, d, n: True)
    monkeypatch.setattr(selective_scan, "scan_tiles", lambda d: (128, 128))
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **policy: fn)
    with pltpu.force_tpu_interpret_mode():
        yield
