"""Test harness: force CPU with 8 virtual devices, so all mesh/collective
code paths run in CI with no TPU (SURVEY.md §4 "Distributed without a
cluster"). The real-chip path is exercised by chip_smoke.py instead.

XLA_FLAGS must be in the environment before the CPU backend is first
initialized; the platform is pinned both ways (env var and jax.config) so
an importer that touched jax first cannot leave the suite on a chip.
"""

import os
import sys

# Repo root on sys.path: `import chip_smoke` (and other root-level entry
# points) must resolve under plain `pytest` too, not only `python -m
# pytest` from the root — same guard the scripts/ entry points carry.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# ---------------------------------------------------------------- quick tier
#
# `pytest -m "not slow"` is tier-1: what the driver runs on every PR, on
# the CPU, with six workers (`-n 6 --dist loadfile`: a file's tests stay
# on one worker) inside a 1,470 s limit. Everything NOT on this allowlist
# is auto-marked `slow` at collection, so a new test defaults into the
# full suite and is promoted here deliberately. What the list is for, in
# order: (1) the measured path — the code the benchmark's cells run
# (learn/learner.py, rollout/anakin.py, models/, ops/, the pixel Pong
# and token envs, parallel/mesh.py, make_agent -> Trainer, the process
# record): its tests belong here whenever they pass under the driver's
# command and cost under ~20 s each; (2) the acceptance contracts of the
# host path, serving, observability and the analyzer, one curated core a
# file. The FULL suite (`python -m pytest tests/ -q`) remains the
# completeness bar; ROADMAP.md lists the `slow` remainder by file.
#
# "all" keeps the whole file (tests marked `slow` in the file itself stay
# slow); a set keeps only those test functions (parametrized variants
# included).
QUICK: dict[str, object] = {
    # Pure numerics / fast units (whole files).
    "test_vtrace.py": "all",
    "test_gae.py": "all",
    "test_scan.py": "all",
    "test_losses.py": "all",
    "test_distributions.py": "all",
    "test_envs.py": "all",
    "test_runtime.py": "all",  # jax-importing subprocesses
    # The measured path (ISSUE 28): the Learner every cell's step is
    # built by, the networks, the pixel Pong env the atari cells render
    # on the device.
    "test_learner.py": "all",
    "test_pong.py": "all",
    # test_remat_is_numerically_invisible stays slow: ~30 s under six
    # workers, and no cell sets `remat`.
    "test_models.py": {
        "test_mlp_flattens_image_observations",
        "test_cnn_torsos_shapes",
        "test_outputs_float32_under_bf16_compute",
    },
    # chip_smoke.py's refusal + the CPU rehearsal of its three legs at
    # tiny sizes through the Pallas interpreter (~70s): what keeps the
    # chip check's control flow and assertions alive between chip runs.
    "test_chip_smoke.py": "all",
    "test_multiprocess.py": "all",  # (slow-marked inside already)
    "test_differential.py": "all",
    "test_metrics.py": "all",
    "test_breakout.py": "all",
    "test_anakin.py": "all",
    "test_cpu_async.py": "all",
    # Curated cores of the heavier files.
    "test_timeshard.py": {
        "test_vtrace_timesharded_matches_single_device",
        "test_gae_timesharded_matches_single_device",
        # the only tests of _algo_loss's time-axis side (~16 s together)
        "test_rollout_learner_timesharded_equals_dp_only",
        "test_rollout_learner_timesharded_multipass_equals_dp_only",
        "test_rollout_learner_timesharded_multipass_minibatched",
    },
    "test_qlearn.py": {"test_huber_td_loss_fixture"},
    "test_sebulba.py": {
        "test_param_store_versioning",
        "test_jax_host_pool_contract",
        "test_rollout_learner_improves_on_fixed_fragment",
        "test_fused_host_updates_match_sequential",
    },
    "test_checkpoint.py": {"test_save_restore_bit_exact_next_step"},
    # make_agent -> Trainer. test_pong_pixels_t2t_preset_trains (47 s)
    # stays slow, and so does test_train_smoke_learns_a_bit: under six
    # workers its ~470 all-reduced updates on the 8-device CPU mesh
    # aborted the worker in 2 runs of 8 (ROADMAP C12).
    "test_api.py": {
        "test_config_override_parsing",
        "test_presets_exist",
        "test_make_agent_unknown_backend",
        "test_make_agent_rejects_bad_enums_eagerly",
        "test_make_agent_train_smoke",
        "test_in_training_eval_cadence",
    },
    "test_race_debug.py": {
        "test_paramstore_detects_removed_lock",  # the §5.2b proof
        "test_fragment_checker_accepts_gapless_and_restarts",
        "test_fragment_checker_detects_violations",
        "test_inference_server_invariant_is_fatal",
    },
    # Fault-injection harness + supervised recovery (utils/faults.py):
    # registry units are sub-second; the recovery-matrix smokes are ~5-8s
    # each (8 envs, 4-step unrolls). The checkpoint-fallback pair stays in
    # the full tier (orbax save/restore round trips, ~30s+).
    "test_faults.py": {
        "test_spec_grammar_round_trip",
        "test_malformed_specs_are_refused",
        "test_fire_sequence_is_deterministic",
        "test_unarmed_sites_are_none_and_counters_empty",
        "test_arm_from_environment",
        "test_corrupt_poisons_payload_deterministically",
        "test_max_fires_caps_and_counts",
        "test_stall_wakes_on_stop_predicate",
        "test_single_crash_in_actor_path_is_recovered",  # 3 sites
        "test_eval_pools_step_unarmed",
        "test_server_crash_is_recovered_and_counted",
        "test_serve_core_crash_is_rebuilt_without_dropping_fleet",  # 2 sites
        "test_watchdog_restarts_stalled_actor",
        "test_restart_storm_aborts_instead_of_churning",
        "test_native_pool_close_is_idempotent",
        "test_native_pool_close_safe_after_failed_init",
        "test_recovery_counters_flow_through_sinks",
        "test_threads_are_named_and_fault_messages_identify_threads",
    },
    # Serving core (asyncrl_tpu/serve/, ISSUE 6): params/router/SLO units
    # are sub-second; the dispatch/routing/storm tests are a few seconds
    # each and the two trainer e2e paths ~15s combined. Tier-1 by the
    # ISSUE 6 acceptance contract (zero-drain swaps proven by test on
    # every PR). Whole file ~30s.
    "test_serve.py": "all",
    # Observability (asyncrl_tpu/obs/, ISSUE 5): ring/export/report/
    # registry units are sub-second; the two pipeline smokes (the
    # fault-injected flight-recorder acceptance run and the disabled-mode
    # window check) are ~10s combined. Whole file ~15s.
    "test_obs.py": "all",
    # External gateway (serve/gateway.py + client.py, ISSUE 15): grammar/
    # breaker/retry units are sub-second (clock-injected, no sleeps);
    # the wire-level tests run against a stub backend on an ephemeral
    # port; the two trainer e2e chaos runs (live swaps over the wire,
    # netfault-crash rebuild without dropping actors) are ~15s combined
    # and ARE the ISSUE 15 acceptance contract. Whole file ~20s.
    "test_gateway.py": "all",
    # Device replay ring + IMPACT learner (learn/replay.py, ISSUE 14):
    # the lease-protocol units (fencing/sampling/ledger/quarantine) are
    # ~1s each against a tiny ring; the trainer e2e pair (off-identity,
    # on-telemetry) and the learner target/anchor probes are ~15s
    # combined. Tier-1 by the ISSUE 14 acceptance contract (replay off
    # pinned to the pre-PR program on every PR). Whole file ~17s.
    "test_replay.py": "all",
    # Training introspection (obs/introspect.py, ISSUE 8): staleness/
    # compile/memory units are sub-second; the live acceptance run
    # (metrics + /healthz flip + forensics) and the introspect-off A/B
    # are ~15s combined. Tier-1 by the ISSUE 8 acceptance contract
    # (detectors proven to flip /healthz on every PR). Whole file ~20s.
    "test_introspect.py": "all",
    # The process record, armed spans in a profiler trace, tracing on the
    # Anakin trainer and the device scopes (ISSUE 24): units are
    # sub-second; three tiny CartPole agents, one CPU profile and one
    # compile of a 2-env pixel step, ~25s combined.
    "test_obs_process_record.py": "all",
    # The max-pool kernels (ops/max_pool.py, ISSUE 25) in the Pallas
    # interpreter against nn.max_pool, ties everywhere (ten shape x dtype
    # cases, 2-20s each), the kernel/fallback choice, and one compile of
    # the learner's geometry for a described v5e (~25s). Whole file ~85s.
    "test_max_pool.py": "all",
    # The Kimi-Linear sequence policy against its plain reference at the
    # tiny preset (ISSUE 26): forms, carry, shares, chunked scan, training.
    "test_kimi_linear.py": "all",
    # The LFM2-MoE sequence policy against its plain reference at the tiny
    # preset (ISSUE 30): forms, carry, positions, the four shares, the
    # grouped expert side against the dense one, Kimi's values as they were.
    "test_lfm2_moe.py": "all",
    # The one-token attention's kernel (ops/gqa.py, ISSUE 31) in the Pallas
    # interpreter against the plain lines: every edge of a chunk, rows
    # beyond len, the VJP, the choice of form and its counter.
    "test_gqa.py": "all",
    # Attention that chooses its rows (ops/dsa.py, ISSUE 32): the exact
    # selection against lax.top_k with ties, both forms against each other,
    # the KL term's gradients; and the Keye sequence policy against its plain
    # reference at the tiny preset: forms, carry, the softmax router, the
    # eight shares, the model's own loss term through the learner.
    "test_dsa.py": "all",
    "test_keye_moe.py": "all",
    # The Moonlight sequence policy (models/mla.py's latent attention with
    # decoupled RoPE in every layer) against its plain reference at the tiny
    # preset: forms, carry, the rope pairing, the eight shares.
    "test_moonlight.py": "all",
    # The one-token latent attention's kernel (ops/latent.py) in the Pallas
    # interpreter against the plain lines, every edge of a chunk, rows beyond
    # len, the VJP, the choice of form and its counter, models/mla.py step on it.
    "test_latent.py": "all",
    # The expert layer's decode side (ops/moe.py): the chosen experts'
    # kernel under the Pallas interpreter against the dense lines, its VJP,
    # the rule at each MoE cell's shape, moe_step_idle_share by hand.
    "test_moe.py": "all",
    # The Granite 4.0-H sequence policy (Mamba-2 mixers on ops/ssd.py, one
    # NoPE attention layer, muP multipliers, a tied head) against its plain
    # reference at the tiny preset: forms, carry, the chunked scan against
    # the recurrence, every leaf's gradient, and the other sequence policies'
    # lowered programs as they were.
    "test_granite_h.py": "all",
    # SPMD contract passes (ISSUE 13): pure-AST; fixture corpus,
    # live-tree deletion proofs (axis rename / check_rep flip /
    # host-guarded all_gather / deleted DMA wait), cache soundness for
    # the SHD/HSY/PAL families, version-bump invalidation, JSON round
    # trip. ~10s, two CLI subprocess runs included. Tier-1 by the
    # ISSUE 13 acceptance contract (deletion proofs pass on every PR).
    "test_spmd_analysis.py": "all",
    # The explicit-DMA scan kernel must stay bit-identical to the
    # automatic kernel (the PAL pass guards its start/wait discipline
    # statically; this guards its numerics). ~8s in the interpreter.
    "test_pallas_scan.py": {"test_dma_kernel_matches_automatic"},
    # Protocol typestate + signal-safety passes (ISSUE 11): pure-AST;
    # fixture corpus, live-tree deletion proofs (release/void/latch),
    # grammar hardness, warm-cache soundness, stats zeros. ~10s, two CLI
    # subprocess runs included. Tier-1 by the ISSUE 11 acceptance
    # contract (deletion proofs pass on every PR).
    "test_protocols.py": "all",
    # Static checker (asyncrl_tpu/analysis/): pure-AST, no training; the
    # whole file (package-gates-clean + fixture corpus + lock/edge
    # deletion detection + cache correctness/speedup + baseline + JSON +
    # annotation-grammar hardness) measures ~25s, CLI subprocess tests
    # included. Tier-1 by the ISSUE 3/4 acceptance contracts: the
    # package must gate clean (modulo the checked-in baseline) on every
    # PR, and the warm cache must stay >= 3x faster than cold.
    "test_analysis.py": "all",
    # Zero-copy staging pipeline (rollout/staging.py): ring/lease units
    # are sub-second; the bit-identity A/B is ~25s (two tiny trainings).
    # The two training smokes (chaos crash recovery, recurrent slabs)
    # stay in the full tier / `-m chaos`.
    "test_staging.py": {
        "test_template_matches_buffer_geometry",
        "test_zero_copy_emit_shares_slab_memory",
        "test_no_reuse_before_transfer_complete",
        "test_retire_reclaims_ready_slabs_without_blocking",
        "test_generation_stamp_fences_restarted_actor",
        "test_reset_invalidates_all_leases",
        "test_auto_num_slabs_covers_pipeline_depth",
        "test_slab_path_bit_identical_to_stack_path",
        # Elastic ring-swap semantics (RingSwapHolder): sub-second units.
        "test_ring_swap_inflight_lease_finishes_on_old_ring",
        "test_ring_swap_zombie_on_drained_ring_raises",
        "test_ring_swap_never_invalidates_a_live_lease",
        "test_ring_swap_wakes_blocked_acquirer_onto_new_ring",
        "test_ring_swap_holder_reset_fences_every_live_ring",
        "test_ring_swap_holder_accumulates_reuse_waits",
    },
    # Elastic runtime (asyncrl_tpu/runtime/elastic.py, ISSUE 9):
    # controller/grammar/registry units are sub-second; the storm-
    # classification unit and serve-registry test are a few seconds; the
    # two scripted-scale e2e runs, the chaos matrix, and the elastic-off
    # bit-identity A/B are ~60s combined. Tier-1 by the ISSUE 9
    # acceptance contract (zero dropped leases + /healthz recovery on
    # every PR). The checkpoint-barrier restore test stays in the full
    # tier (orbax round trips).
    "test_elastic.py": {
        "test_controller_up_needs_hysteresis_then_cools_down",
        "test_controller_respects_bounds",
        "test_controller_down_on_backpressure_delta_not_level",
        "test_controller_down_reason_never_blames_a_disabled_signal",
        "test_controller_admission_signal_has_disable_knob",
        "test_controller_replay_fill_inversion_scales_down_only_when_fed",
        "test_controller_blame_veto_blocks_misattributed_scale_up",
        "test_blame_horizon_covers_the_closed_window_not_the_1s_clamp",
        "test_scripted_requests_bypass_hysteresis_one_per_window",
        "test_scripted_multislot_applies_one_slot_per_window",
        "test_scripted_fire_resets_trends_and_arms_cooldown",
        "test_scripted_noop_does_not_freeze_organic_trends",
        "test_scripted_down_clamps_to_min",
        "test_decision_event_payload_is_structured",
        "test_scale_kind_fires_requests_and_counts",
        "test_scale_after_option_stages_the_script",
        "test_delta_refused_on_non_scale_kinds",
        "test_arm_clears_pending_scale_requests",
        "test_pending_scale_requests_are_bounded",
        "test_scale_spec_requires_elastic_runtime",
        "test_watchdog_retirements_excluded_from_crash_storm",
        "test_serve_core_elastic_client_registry",
        "test_reconfigure_barrier_without_checkpointer_raises",
        "test_scripted_scale_up_grows_fleet_without_storm",
        "test_scripted_scale_down_is_drain_clean",
        "test_organic_stall_signal_scales_up",
        "test_chaos_matrix_interleaved_scale_and_crash",
        "test_elastic_off_is_bit_identical_and_leaks_no_keys",
        "test_elastic_validation_refuses_bad_compositions",
        "test_asyncrl_elastic_env_wins",
    },
    # Durable runs (asyncrl_tpu/runtime/durability.py, ISSUE 10): the
    # policy/coordinator/checksum/gate units are seconds combined (the
    # watchdog tests sleep ~1s total); the scripted-preempt → resume e2e
    # (~26s) and the quarantine→rollback→recovery e2e (~20s) are the
    # acceptance contract and stay on the quick signal. The
    # drain-under-elastic resume and the bounded-attempts abort e2e
    # (~30s each) stay in the full tier.
    "test_durability.py": {
        "test_policy_quarantines_until_threshold_then_rolls_back",
        "test_policy_clean_window_resets_trend_and_records_last_good",
        "test_policy_cooldown_freezes_trend_but_still_quarantines",
        "test_policy_aborts_after_max_attempts",
        "test_policy_ignores_non_trigger_detectors",
        "test_policy_validation",
        "test_drain_deadline_watchdog_hard_kills",
        "test_drain_finish_disarms_the_watchdog",
        "test_drain_request_is_idempotent",
        "test_second_signal_hard_kills_immediately",
        "test_install_off_main_thread_is_a_noop",
        "test_scripted_preempt_requires_an_active_coordinator",
        "test_grace_validation_and_env_precedence",
        "test_corrupt_latest_checksum_falls_back_to_older_step",
        "test_corrupt_latest_data_falls_back_to_older_step",
        "test_pre_manifest_checkpoint_restores_without_checksum",
        "test_delete_step_removes_the_manifest_sidecar",
        "test_retention_gc_orphaned_manifests_are_pruned",
        "test_rollback_with_rotated_out_last_good_keeps_oldest",
        "test_rollback_with_no_retained_steps_is_a_noop",
        "test_slo_gate_close_refuses_new_admissions",
        "test_slo_gate_close_wakes_a_waiting_admitter",
        "test_preempt_spec_refused_when_drain_disabled",
        "test_rollback_requires_checkpoint_dir",
        "test_preempt_drain_then_resume_continues_the_run",
        "test_divergence_quarantines_then_rolls_back_and_recovers",
    },
    # overlap_h2d on/off A/B: identical losses + not-slower (~25s).
    "test_perf_smoke.py": "all",
    "test_ppo_multipass.py": {
        "test_ppo_multipass_minibatch_divisibility_error",
        "test_ppo_multipass_dp_consistency",
    },
    "test_wrappers.py": {
        "test_frame_skip_sums_rewards_and_freezes_at_done",
        "test_frame_skip_wrapper_contract",
        "test_host_pool_refuses_unhonorable_knobs",
        "test_registry_applies_knobs",
    },
    # The carry, `reset_core` and the fragment-initial core the sequence
    # cell's policy lives on, through the Anakin Learner and Trainer; the
    # host-path and PPO-multipass cases stay slow.
    "test_recurrent.py": {
        "test_build_model_dispatch",
        "test_recurrent_apply_and_reset",
        "test_recurrent_learner_update_and_determinism",
        "test_recurrent_fragment_forward_resets_core_mid_fragment",
        "test_recurrent_eval_and_checkpoint",
        "test_recurrent_guards",
    },
    # Observation / return normalisation where it runs inside the Anakin
    # rollout and step and across the mesh.
    "test_normalize.py": {
        "test_sharded_stats_equal_global_batch",
        "test_anakin_normalize_obs_end_to_end",
        "test_disc_return_stream_matches_manual_recurrence",
        "test_anakin_return_normalization_scales_learner_rewards",
        "test_return_normalization_gamma_zero_degrades_gracefully",
    },
    "test_run_to_target.py": {
        # In-process protocol tests (fake trainer, no training): the
        # reached=true confirmation gate must stay on the quick signal.
        "test_unconfirmed_crossing_is_not_banked",
        "test_crossing_banked_only_after_confirmation",
    },
    "test_selfplay.py": {
        "test_observe_opponent_is_the_mirror_view",
        "test_duel_dynamics_are_symmetric",
        "test_duel_single_action_step_keeps_scripted_opponent",
        "test_selfplay_guards",
    },
}


# One assertion of an accepted benchmark test that ISSUE 26 §5 made false:
# `render/section0/max_pool_device_ms` read scopes only the IMPALA-CNN's
# step has, so with the sequence cell they list the cells that have them,
# and the case's last line ("workloads" not in entry) no longer holds.
# That file is a `benchmark` PR's to change, so the three cases are
# expected to fail here, strictly: the PR that repairs the assertion
# makes them pass, which fails until these marks go. What else they held
# is asserted in tests/benchmarks/test_benchmark_seq.py.
SUPERSEDED = {
    f"test_benchmark_program_metrics.py::test_metric_resolves_to_its_reader[{m}]":
    "ISSUE 26 §5: the metric lists the atari cells"
    for m in ("render_device_ms", "section0_device_ms", "max_pool_device_ms")
}
# The same for one case of Keye's cell: it pins the benchmark's list of cells
# to the five there were, and the `moonlight_rl` cell is a sixth. What it held
# for the new cell is asserted in tests/benchmarks/test_benchmark_moonlight.py.
SUPERSEDED["test_benchmark_keye.py::test_make_agent_programs_is_read_in_every_cell"] = (
    "a sixth cell (moonlight_rl); the list at :194 is the benchmark's own "
    "to relax to a prefix")
# And Moonlight's: it asserts that its cell is the benchmark's last, and the
# `granite_h_rl` cell is a seventh. What it held, for both cells, is asserted
# in tests/benchmarks/test_benchmark_granite.py.
SUPERSEDED["test_benchmark_moonlight.py::test_make_agent_programs_is_read_in_the_new_cell"] = (
    "a seventh cell (granite_h_rl); the assertion at :207 that Moonlight's "
    "cell is the last is the benchmark's own to relax")
# Six accepted entries list the `granite_h_rl` cell after their own cells
# (its attention layer, head, episode boundaries and prefetch waits are the
# same program's): four cases pinned each entry's list, or the entries that
# list one cell alone. What they held, with the new lists, is asserted in
# tests/benchmarks/test_benchmark_granite.py.
SUPERSEDED.update({
    "test_benchmark_seq.py::test_every_new_metric_resolves_to_a_reader_in_the_new_cell_only":
    "lm_head_device_ms and episode_resets_per_update list granite_h_rl too",
    "test_benchmark_lfm2.py::test_every_new_metric_resolves_to_a_reader_in_the_new_cell_only":
    "gqa_device_ms and gqa_rows_attended list granite_h_rl too",
    **{f"test_benchmark_update_books.py::test_metric_resolves_to_its_reader_in_its_cells[{m}]":
       f"{m} lists granite_h_rl too"
       for m in ("prefetch_wait_device_ms", "gqa_step_device_ms")},
})


def pytest_collection_modifyitems(config, items):
    slow = pytest.mark.slow
    seen_files: set[str] = set()
    seen_names: set[tuple[str, str]] = set()
    for item in items:
        fname = item.fspath.basename
        seen_files.add(fname)
        superseded = SUPERSEDED.get(item.nodeid.split("/")[-1])
        if superseded:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError, reason=superseded,
            ))
        entry = QUICK.get(fname)
        if entry == "all":
            continue
        name = item.name.split("[")[0]
        if isinstance(entry, set) and name in entry:
            seen_names.add((fname, name))
            continue
        item.add_marker(slow)

    # The quick tier must not thin out silently: a renamed/deleted test
    # that a QUICK entry still points at is a collection-time ERROR, not a
    # quietly-skipped check. (Only enforced on full-tests collections, so
    # running a single file doesn't trip the other entries.)
    if len(seen_files) < len(QUICK):
        return
    stale = [
        (fname, name)
        for fname, entry in QUICK.items()
        if isinstance(entry, set)
        for name in entry
        if (fname, name) not in seen_names
    ]
    missing_files = [f for f in QUICK if f not in seen_files]
    if stale or missing_files:
        raise pytest.UsageError(
            f"tests/conftest.py QUICK allowlist is stale: missing files "
            f"{missing_files}, missing tests {stale} — update the quick "
            "tier so its curated checks don't silently drop out"
        )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs
