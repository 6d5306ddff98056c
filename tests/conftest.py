"""Test config: force an 8-device virtual CPU mesh before any JAX backend init.

The whole suite runs on the CPU platform with eight virtual devices, so the
sharding tests need no chip and a machine that has one keeps it free: the
platform and the device-count flag are pinned here, before the first device
lookup. What runs on the chip is ``chip_smoke.py`` and ``benchmark/run.py``,
never pytest.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, "expected 8 virtual CPU devices for sharding tests"

import pytest  # noqa: E402

# ``ops/attention.py`` binds ``get_registry`` by name when it is imported.
# Imported here, before any test can have patched the accessor: a test that
# patches ``telemetry.get_registry`` and is the first in its worker to import
# the module leaves it counting into that test's private registry for the
# rest of the process (tests/benchmark/test_program_counter.py before a
# cell's traced run, by the order xdist hands the files out).
import fedml_tpu.ops.attention  # noqa: E402,F401

# Measured-duration tiering (round-2 review weak #5): tests whose call time
# exceeded ~5s in the full-suite timing run are auto-marked `slow` so
# `pytest -m "not slow"` is a quick CI tier. Matching is by test-function
# name substring; explicit @pytest.mark.slow decorations still apply.
SLOW_TEST_NAMES = (
    "test_batchnorm_fedopt_splits_server_update",
    "test_batchnorm_resnet_trains_and_averages_stats",
    "test_federated_detection_learns_localization",
    "test_fednas_darts_search_runs",
    "test_fedgkt_learns",
    "test_fedseg_unet_learns",
    "test_fedgan_round_runs",
    "test_bucketed_beats_even_on_skewed_cohort",
    "test_bucketed_matches_even_numerics",
    "test_fednlp_seq2seq_learns",
    "test_fednlp_span_extraction_learns",
    "test_fednlp_seq_tagging_learns",
    "test_fedgraphnn_link_prediction_learns",
    "test_distributed_lm_ulysses_matches_ring_forward",
    "test_distributed_lm_trains",
    "test_ulysses_attention_matches_dense",
    "test_param_specs_megatron_layout",
    "test_pipeline_matches_sequential_forward",
    "test_pipeline_trainer_learns",
    "test_engine_matches_reference_torch_loop",
    "test_fednlp_text_classification_learns",
    "test_example_config_loads_and_resolves",
    "test_hierarchical_fl_learns",
    "test_moe_block_top2_learns_routing",
    "test_moe_learns_routing",
    "test_moe_block_runs_and_shards",
    "test_dp_training_still_learns",
    "test_dp_noise_engages_and_is_seeded",
    "test_packed_checkpoint_resume_matches_uninterrupted",
    "test_packed_with_momentum_and_prox",
    "test_packed_on_mesh_matches_sp",
    "test_packed_matches_even_sp",
    "test_packed_matches_even_multiepoch",
    "test_packed_client_dropout_matches_even",
    "test_fediot_autoencoder_detects_anomalies",
    "test_mesh_matches_sp",
    "test_mesh_params_replicated_and_finite",
    "test_flash_gradients_match_dense",
    "test_flash_gradients_long_context_T1024",
    "test_agent_daemon_end_to_end",
    "test_mobile_artifact_roundtrip",
    "test_checkpoint_resume_matches_uninterrupted",
    "test_grpc_mtls_roundtrip_and_plaintext_refused",
    "test_bilevel_search_moves_alphas_and_learns",
    "test_search_then_retrain_beats_random_genotype",
    "test_hf_bert_checkpoint_logit_equality",
    "test_federated_finetune_from_imported_weights",
    "test_decentralized_dsgd_consensus_and_learning",
    "test_import_shape_check_fails_loudly",
    "test_batchnorm_rejected_for_stats_corrupting_optimizers",
    "test_mobile_lenet_learns",
    "test_fedgraphnn_gcn_learns",
    "test_digits_real_dataset_learns",
    "test_fedopt_adaptive_server_optimizers_learn",
    "test_sync_batchnorm_matches_full_batch_stats",
    "test_efficientnet_family_scales",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(name in item.name for name in SLOW_TEST_NAMES):
            item.add_marker(pytest.mark.slow)
