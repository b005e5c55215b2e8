"""Test configuration: force an 8-fake-device CPU platform BEFORE jax import.

This is the idiomatic TPU-stack answer to "test multi-node without a
cluster" (SURVEY §4): XLA exposes N virtual CPU devices so every mesh/
sharding/collective test runs the real SPMD code path. The reference has no
equivalent — its SLURM/MPI/torchrun paths are untested.
"""

import contextlib
import faulthandler
import os
import shutil
import tempfile

# Tests always run on 8 fake CPU devices (mesh coverage), never on a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# One persistent compile cache a RUN: a fresh directory under the system temp
# dir that the controller (or the single process) makes, its xdist workers and
# the OS processes tests spawn inherit through JAX's own variable, and
# pytest_sessionfinish removes. The engines of tests/serving_support.py lower
# to the same programs in every file, so whichever worker asks second reads
# what the first compiled. Never inside the checkout, never a later run's: a
# stale cache is how a test passes on yesterday's program.
# (utils/platform.enable_compile_cache, which runs when a test imports
# cli.main, obeys the variable; without it six workers would fill
# <checkout>/.jax_cache.)
_RUNS_THE_SESSION = "PYTEST_XDIST_WORKER" not in os.environ
if _RUNS_THE_SESSION:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="llmctl-test-compile-cache-")

import jax  # noqa: E402
import pytest  # noqa: E402

_CACHE_LINE = pytest.StashKey[str]()
# keep the engines' sub-second programs too
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@pytest.hookimpl(hookwrapper=True)
def pytest_sessionfinish(session):
    if not _RUNS_THE_SESSION:
        yield
        return
    cache = os.environ["JAX_COMPILATION_CACHE_DIR"]
    # every test has reported by now; the report's last lines say what the
    # run shared
    kept = [e.stat().st_size for e in os.scandir(cache)
            if e.name.endswith("-cache")]
    session.config.stash[_CACHE_LINE] = (
        f"run's compile cache: {len(kept)} programs, {sum(kept)} bytes")
    yield                           # xdist's own hook stops the workers
    shutil.rmtree(cache, ignore_errors=True)


def pytest_terminal_summary(terminalreporter):
    line = terminalreporter.config.stash.get(_CACHE_LINE, None)
    if line:
        terminalreporter.write_line(line)


# -- a limit a case -----------------------------------------------------------
# The driver cuts the whole run at 1,470 s, and a run that is cut writes no
# junit file and names nothing (PR 60's tree). So a case (set-up and tear-down
# included) still running after CASE_LIMIT_S has every thread's stack written
# to the run's stderr, the engine threads' too, and in an xdist worker the
# worker exits: xdist prints "[gwN] node down", fails THAT case by name, starts
# a fresh worker and deals the rest on. In one process the case goes on. One
# timer a process, so pyproject's `faulthandler_timeout` stays unset.
# The dearest tier-1 case under the driver's six workers is a described-TPU
# compile in the run's last phase: 165 s (PR 61's run of PR 60's tree,
# CHANGES.md). Three times that is over a quarter of the driver's clock, the
# most one case may cost the run: 360 s. A case that cannot end inside it gets
# no longer limit: it is too dear for tier 1.
CASE_LIMIT_S = 360

_STDERR = pytest.StashKey[int]()


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    faulthandler.dump_traceback_later(
        CASE_LIMIT_S, exit=not _RUNS_THE_SESSION,
        file=item.config.stash[_STDERR])
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="module")
def short_kda_chunks():
    """A chunk of 8 in place of ``ops/kda.py CHUNK`` = 64 for a module's
    delta-rule (``K``) layers, so that its tiny windows (a prompt of 40
    tokens, a riding piece of 16 rows) run several chunks with the state
    carried between them. A module asks for it with ``pytestmark =
    pytest.mark.usefixtures("short_kda_chunks")``."""
    from distributed_llm_training_and_inference_system_tpu.ops import kda
    plain, kda.CHUNK = kda.CHUNK, 8
    yield
    kda.CHUNK = plain


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 fake devices, got {len(devs)}"
    return devs[:8]


# -- compiling for a DESCRIBED TPU (tests/test_tpu_compile_*.py) ---------------
# libtpu is loaded and the topology described INSIDE the module-scoped
# fixture, when a test of those files first asks for it: never at import
# (every xdist worker imports every test file and this one).
# By name these files sort last: the run ENDS on their compiles, six abreast (a
# third of its CPU time in 45 cases), and that is the cheapest place for them.
# Measured (PR 61, CHANGES.md): spread through the run, one or two compiling
# beside the other files' cases, every case slowed (1,281 s for 1,008); dealt
# to the workers' first chunks, 1,227 s. Leave the order alone.

@contextlib.contextmanager
def _without_the_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    with _without_the_compile_cache():
        yield desc


@pytest.fixture(scope="module", autouse=True)
def _tests_benchmark_compile_uncached(request):
    """The modules under ``tests/benchmark/`` compile as they did before the
    run had a cache. Their runner rehearsals serve from an engine THREAD, and
    the one time the whole suite ran with the cache on there, a worker died
    writing that thread's freshly compiled program to the cache (a
    segmentation fault inside ``executable.serialize()``, PR 57; not seen
    again alone). Those files are a ``benchmark`` PR's, so the switch is
    here, by path."""
    if request.path.parent.name != "benchmark":
        yield
        return
    with _without_the_compile_cache():
        yield


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Make the package's own ``jax.default_backend()`` checks take their
    TPU branch (compiled kernel, not interpret) for this test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

# -- slow-test marking --------------------------------------------------------
# The rule: a case that cannot end inside CASE_LIMIT_S under the driver's six
# workers is not a tier-1 case, and none should take over a third of it. A
# case is listed here when it takes over ~60 s under those workers AND a
# cheaper case that stays in tier 1 (`-m "not slow"`, the driver's gate)
# asserts the same property; name that case beside it. The members from
# before PR 53 were listed by duration alone (rounds 3 and 4, >= ~6-12 s on
# the CI CPU of the time) and stay as they are. One central list by test name
# (not per-file decorators), every name of which matches a collected test
# (`pytest --collect-only -q -m slow`); seven cases carry the marker
# themselves, with their reason beside it (tests/test_models.py, the
# plain-program compiles of tests/test_tpu_compile_*.py).

SLOW_TESTS = {
    "test_admission_counts_pinned_pages_not_as_free",
    "test_resident_stream_advances_during_long_prefill",
    "test_long_context_64k_memory_scales_linearly",
    "test_eviction_under_pressure_still_correct",
    "test_greedy_matches_with_concurrent_requests",
    "test_1f1b_memory_constant_in_microbatches",
    "test_ulysses_matches_ring_and_dense",
    "test_greedy_bit_identical_with_speculation",
    "test_concurrent_shared_prefix_requests",
    "test_pipeline_with_tp",
    "test_multi_step_matches_single_step",
    "test_greedy_matches_dense_forward",
    "test_engine_end_to_end_with_resume",
    "test_two_process_rendezvous_psum_and_checkpoint",
    "test_1f1b_matches_gpipe_trajectory",
    "test_sharded_step_matches_single_device",
    "test_diverging_suffix_still_correct",
    "test_pipeline_matches_single_device",
    "test_greedy_matches_unchunked",
    "test_mixed_greedy_and_sampled_batch",
    "test_chunked_loss_matches_dense",
    "test_long_prompt_multiple_pages",
    "test_cache_off_unchanged",
    "test_moe_ep_sharding",
    "test_moe_with_speculation_and_chunked_prefill",
    "test_tp2_concurrent_requests",
    "test_second_request_hits_and_matches",
    "test_moe_greedy_matches_dense",
    "test_moe_forward_and_grads",
    "test_tp2_with_speculation_and_prefix_cache",
    "test_int8_awq_quantization_roundtrip",
    "test_no_involuntary_remat",
    "test_sampled_requests_match_nonspec_engine",
    "test_sampled_request_prefix_reuse_matches_cold",
    "test_loss_decreases_on_repeated_batch",
    "test_perfect_drafts_fully_accepted",
    "test_chunked_with_prefix_cache_and_speculation",
    "test_flash_gqa_folded_matches_xla",
    "test_tp2_greedy_matches_single_device",
    # round-3 additions (>= ~6 s in the not-slow durations run)
    "test_int4_decode_tracks_fp_logits",
    "test_bf16_nu_loss_trajectory_close_to_fp32",
    "test_decode_consistent_with_quantized_dense",
    "test_fused_adamw_bitwise_matches_optax",
    "test_page_aligned_prompt_recomputes_last_token",
    "test_grad_accum_matches_full_batch",
    "test_seeded_sampling_survives_preemption",
    "test_checkpoint_roundtrip_sharded",
    "test_tp2_sampled_matches_single_device",
    "test_negative_top_k_means_disabled_not_greedy",
    "test_ondemand_coschedules_what_reserve_serializes",
    "test_short_prompts_stay_on_single_dispatch",
    "test_orchestrator_restart_on_failure",
    "test_train_writes_checkpoints_and_manifest",
    "test_top_p_zero_is_greedy",
    "test_per_step_chunk_budget_round_robins",
    "test_kv_cache_decode_matches_full_forward",
    "test_close_to_fp_generation",
    "test_replay_reproduces_loss",
    "test_preempted_greedy_matches_unconstrained",
    "test_long_prompt_burst_does_not_stall_resident_stream",
    "test_all_features_on_quantized_kv",
    "test_batched_scores_match_manual",
    "test_loss_goes_down",
    "test_int4_with_features_stacked",
    "test_preemption_preserves_waiters_and_metadata",
    "test_speculation_and_prefix_cache_on_int8",
    "test_grad_clipping_applied",
    "test_ring_attention_gradients",
    "test_closed_loop_under_pressure_completes",
    # round-3 second wave (>= ~8 s)
    "test_everything_at_once",
    "test_tp2_int4_matches_single_device",
    "test_tp2_int8_matches_single_device_int8",
    "test_tp2_int8_kv_matches_single_device",
    "test_swap_seeded_sampling_deterministic",
    "test_swap_resume_matches_unconstrained_no_reprefill",
    "test_reserve_mode_never_preempts",
    "test_swap_space_budget_falls_back_to_recompute",
    # round-4 re-baseline (>= ~6.5 s in the not-slow durations run)
    "test_latency_adaptive_dispatch_identical_and_engaged",
    "test_sampled_then_greedy_drains_before_spec",
    "test_engine_release_frees_and_next_engine_works",
    "test_int8_artifact_token_identical",
    "test_preemption_pressure_with_pipelining",
    "test_staggered_finishes_mid_chain",
    "test_arrivals_break_chain_and_match",
    "test_seeded_sampling_bitwise_identical",
    "test_greedy_bitwise_identical",
    "test_plain_artifact_matches_params",
    "test_max_tokens_respected",
    "test_poisson_drains_and_reports",
    "test_plan_verify_moment_dtype",
    # spawns a real `llmctl fleet worker` OS process (jax import +
    # engine compile in the child): full-suite merge gate; the fast
    # tier's multi-process coverage is the serve.fleet2+remote dryrun
    "test_spawned_worker_round_trip",
    # fleet-global prefix fetch: the engine-backed spill scenarios
    # build a 2-replica fleet each; greedy/degrade variants stay in the
    # fast tier, the seeded/int8/chaos-retry ones and the 2-process
    # socket acceptance run full-suite only
    "test_fetch_spill_seeded_sampling",
    "test_fetch_spill_int8_kv_pages",
    "test_chunk_chaos_stays_token_identical",
    "test_spawned_worker_prefix_fetch",
    # fleet SSE streaming: each engine-backed scenario builds a
    # 2-replica fleet; the greedy crash / reconnect / loadgen variants
    # stay in the fast tier, the seeded-migration + int8-handoff +
    # plain-salvage ones run full-suite only
    "test_stream_through_drain_migration_seeded",
    "test_stream_through_handoff_int8_kv",
    "test_salvage_without_hint_stays_plain",
}


def pytest_configure(config):
    # the run's own stderr, taken while pytest's capture is suspended (during a
    # case file descriptor 2 is the case's capture file)
    config.stash[_STDERR] = os.dup(2)
    config.addinivalue_line(
        "markers", "slow: listed in conftest.SLOW_TESTS (dear, and covered "
                   "by a cheaper tier-1 case); excluded by -m 'not slow'")
    config.addinivalue_line(
        "markers", "socket: binds real TCP sockets (always ephemeral "
                   "port 0 — never a fixed port, so tier-1 cannot flake "
                   "on collisions); deselect with -m 'not socket' in "
                   "network-restricted sandboxes")
    config.addinivalue_line(
        "markers", "sse: fleet SSE streaming (stream hub, "
                   "migration-transparent delivery, reconnect replay); "
                   "select with -m sse to run the streaming plane alone")


def pytest_collection_modifyitems(config, items):
    for item in items:
        # originalname strips parametrization suffixes ([dp8], ...)
        name = getattr(item, "originalname", None) or item.name
        if name in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
