"""Shared fixtures.

Parity: reference `python/ray/tests/conftest.py` (ray_start_regular:580 boots a
real node per test). JAX tests run on a virtual 8-device CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8), the TPU-world analogue
of the reference's fake multi-node cluster.
"""

import os

# Must be set before jax is imported anywhere in the test process. Tests
# always run on the virtual CPU mesh, even when a real TPU is attached —
# override, don't setdefault (a TPU host may preset JAX_PLATFORMS=tpu).
os.environ["JAX_PLATFORMS"] = "cpu"
# Keep pytest output clean: worker log streaming is exercised by its own
# unit test, not by every fixture cluster.
os.environ.setdefault("RAY_TPU_LOG_TO_DRIVER", "0")
# Share one persistent XLA compilation cache across the whole suite. The
# suite spawns dozens of worker/agent/replica subprocesses that each re-jit
# the same tiny train/rllib/llm graphs; env vars are inherited, so a single
# on-disk cache turns every repeat compile into a ~4x-cheaper cache load.
# Thresholds are zeroed because every entry here is "too small/fast" by the
# defaults. Safe for graphcheck (fingerprints hash the lowered HLO, which is
# computed before the cache is consulted) and for perf gates (they compare
# post-warmup steady state, not first-compile latency).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/ray_tpu_jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# A pytest plugin or an earlier conftest may have imported jax already, in
# which case jax.config read JAX_PLATFORMS from the outer env; update the
# live config too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Same for the cache knobs: update the live config for this process;
# subprocesses import jax with the env vars above already in place.
jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

import pytest  # noqa: E402

# Smoke tier: one fast path per subsystem, selected here rather than by
# editing every module. `pytest -m smoke` must stay green in <3 min on a
# 1-CPU box (the full suite is ~20 min). Parity: the reference's CI tiers
# (ci/ray_ci/core.tests.yml small/medium/large splits).
_SMOKE = {
    "test_core_api.py": {"test_simple_task", "test_put_get",
                         "test_many_async_tasks", "test_error_propagation",
                         "test_large_args_offload_to_shm"},
    "test_object_store.py": {"test_put_get_roundtrip", "test_zero_copy_numpy",
                             "test_concurrent_puts_no_corruption",
                             "test_cross_shard_eviction"},
    "test_cluster.py": {"test_tasks_spread_across_nodes",
                        "test_direct_actor_calls_bypass_head"},
    "test_fault_tolerance.py": {"test_task_retry_on_worker_crash",
                                "test_actor_restart"},
    "test_placement_group.py": {"test_create_ready_remove"},
    "test_collective.py": {"test_allreduce"},
    "test_data.py": {"test_range_take_count", "test_map_and_fusion"},
    "test_train.py": {"test_fit_reports_and_checkpoints",
                      "test_torch_trainer_single_worker"},
    "test_tune.py": {"test_tuner_grid", "test_generate_variants"},
    "test_serve.py": {"test_basic_deploy_and_handle"},
    "test_rllib.py": {"test_gae_matches_reference_impl",
                      "test_actor_critic_module_shapes"},
    "test_llm.py": {"test_engine_matches_naive_greedy"},
    "test_dag.py": {"test_channel_roundtrip_and_versions",
                    "test_compiled_pipeline_two_actors"},
    "test_workflow.py": {"test_run_dag"},
    "test_ops.py": {"test_rmsnorm", "test_flash_attention_multiblock"},
    "test_parallel.py": {"test_ulysses_matches_reference"},
    "test_protocol.py": {"test_agent_frame_round_trip",
                         "test_value_codec_language_neutral"},
    "test_aux.py": {"test_util_queue"},
    "test_launcher.py": {"test_config_parsing_and_validation"},
    "test_head_restart.py": {"test_head_restart_with_sqlite_store"},
    "test_spilling.py": {"test_put_beyond_capacity_spills_and_restores"},
    "test_tooling.py": {"test_state_api"},
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        names = _SMOKE.get(item.fspath.basename)
        if names and item.originalname in names:
            item.add_marker(pytest.mark.smoke)


@pytest.fixture(scope="session")
def tiny_llm_params():
    """ONE set of tiny-transformer params for every LLM-engine test file
    (test_llm / test_spec_decode / test_guided build byte-identical TINY
    configs; re-running init_params per module was pure wall-time). Paired
    with the engine's process-global shared compiled-step cache
    (llm/engine.py _shared_jit), which de-duplicates prefill/decode
    compiles across engine INSTANCES — the two together keep the
    compile-heavy LLM tier inside the tier-1 timeout."""
    from ray_tpu.models import ModelConfig, init_params
    cfg = ModelConfig(vocab=300, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ray_start_regular():
    """A real head runtime with a small worker pool, shared per module."""
    import ray_tpu
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture()
def ray_start_isolated():
    """A fresh runtime per test (for failure-injection tests)."""
    import ray_tpu
    rt = ray_tpu.init(num_cpus=2)
    yield rt
    ray_tpu.shutdown()
