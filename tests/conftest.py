"""Test harness: run everything on a virtual 8-device CPU mesh.

TPU-build analog of the reference's @distributed_test fork-N-processes harness
(reference tests/unit/common.py:16-104): instead of spawning N NCCL processes we
give XLA 8 virtual CPU devices, so mesh/sharding/collective logic runs exactly
as it would across chips.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs[:8]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multiprocess: spawns a real 2-process jax.distributed world")
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy / long-running tests (parallelism matrices, "
        "HF interop, e2e convergence). Fast gate: "
        "pytest -m 'not slow and not multiprocess'")


# Tests measured >= ~5 s on the 1-core reference box (pytest --durations,
# round 5): auto-marked `slow` here so the fast gate stays under 5 minutes
# without sprinkling decorators through every file. Explicit
# @pytest.mark.slow in a test file works too — this list is additive.
# Names match the node id up to (not including) any [param] suffix.
_SLOW_TESTS = {
    "test_checkpointing.py": {
        "test_elastic_restage", "test_orbax_backend_roundtrip",
        "test_roundtrip"},
    "test_cpu_adam.py": {
        "test_engine_offload_e2e",
        "test_engine_offload_gas_accumulation_matches"},
    "test_csr.py": {
        "test_csr_dp_armed_only_where_layout_survives",
        "test_csr_dp_collective_bytes_scale_with_tokens_not_vocab",
        "test_csr_dp_matches_dense_trajectory",
        "test_sparse_gradients_offload_matches_dense"},
    "test_engine.py": {
        "test_bf16_training", "test_chunked_lm_cross_entropy_matches_dense",
        "test_empty_grad_params", "test_fp16_dynamic_scale_training",
        "test_fp32_convergence", "test_gpt2_scan_layers_trains",
        "test_gradient_accumulation_equivalence",
        "test_loss_scale_doubles_after_window",
        "test_overflow_skips_step_and_halves_scale", "test_scheduler_wiring",
        "test_static_loss_scale", "test_train_batch_fused_path"},
    "test_flash_attention.py": {
        "test_dropout_causal_blocks_consistent",
        "test_dropout_gradients_multiblock", "test_dropout_mean_preserving",
        "test_flash_bias_constant_no_grad"},
    "test_generation.py": {
        "test_greedy_generation_matches_transformers",
        "test_greedy_matches_full_forward",
        "test_moe_generation_matches_training_forward"},
    "test_moe.py": {
        "test_eval_capacity_factor", "test_gpt2_moe_trains_on_engine",
        "test_moe_elastic_checkpoint_dp8_to_dp4",
        "test_moe_grads_reach_all_params",
        "test_moe_matches_per_token_expert_math",
        "test_moe_sharded_matches_single_device",
        "test_moe_with_tensor_parallel_matches_dp_only",
        "test_moe_with_zero_offload_trains",
        "test_pipeline_moe_depth_invariant", "test_pipeline_moe_router_learns",
        "test_router_z_loss", "test_single_expert_matches_dense_ffn"},
    "test_onebit.py": {
        "test_engine_with_onebit_adam",
        "test_onebit_adam_converges_after_freeze",
        "test_onebit_wire_gpt2_with_sharding_constraints",
        "test_onebit_wire_saves_gradient_bytes",
        "test_onebit_wire_trains_through_freeze"},
    "test_pipe.py": {
        "test_gpt2_pipe_single_stage_int_input",
        "test_pipe_4stage_matches_1stage", "test_pipe_checkpoint_restage",
        "test_pipe_checkpoint_restage_tied", "test_pipe_checkpoint_roundtrip",
        "test_pipe_checkpoint_roundtrip_bf16",
        "test_pipe_tied_matches_sequential",
        "test_pipe_tied_weights_stay_in_sync",
        "test_pipe_tied_with_clipping_matches_sequential",
        "test_pipe_tp_3d_matches_no_tp",
        "test_pipe_tp_params_sharded_over_model",
        "test_pipe_with_data_parallel_matches", "test_pipe_zero1"},
    "test_run.py": {"test_launch_sets_env"},
    "test_transformer_layer.py": {"test_bert_pretraining_e2e"},
    "test_ulysses.py": {
        "test_bert_fused_layer_seq_axis_parity",
        "test_engine_ring_mode_matches_dp_only",
        "test_engine_seq_axis_matches_dp_only",
        "test_pipeline_with_seq_axis_matches_pipe_only"},
    "test_vocab_padding.py": {"test_pad_rows_get_no_gradient"},
    "test_zero.py": {
        "test_zero2_accum_partitioned", "test_zero3_params_sharded_and_parity",
        "test_zero_stages_same_trajectory", "test_zero_state_is_partitioned"},
}


def pytest_collection_modifyitems(config, items):
    matched = set()
    collected_files = set()
    for item in items:
        fname = item.fspath.basename
        collected_files.add(fname)
        base = item.name.split("[", 1)[0]
        if base in _SLOW_TESTS.get(fname, ()):
            item.add_marker(pytest.mark.slow)
            matched.add((fname, base))
    # a renamed/deleted test must not silently rejoin the fast gate: flag
    # stale _SLOW_TESTS entries (only for files actually collected, so
    # running a single other file doesn't spray warnings; node-id selection
    # like file.py::test_x legitimately deselects siblings, so skip then)
    if any("::" in str(a) for a in config.args):
        return
    for fname, names in _SLOW_TESTS.items():
        if fname not in collected_files:
            continue
        for base in names:
            if (fname, base) not in matched:
                import warnings

                warnings.warn(
                    f"tests/conftest.py _SLOW_TESTS entry {fname}::{base} "
                    "matches no collected test — renamed or deleted? The "
                    "test (if renamed) now runs in the fast gate unmarked.")
