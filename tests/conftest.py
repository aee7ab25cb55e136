"""Test harness: run everything on a virtual 8-device CPU mesh so multi-chip
sharding logic is exercised without TPU hardware (the driver separately
dry-runs the multichip path)."""

import fnmatch
import gc
import os

# PADDLE_TPU_TEST_ON_TPU=1 keeps the real chip — use it ONLY to run the
# TPU-gated files (`PADDLE_TPU_TEST_ON_TPU=1 pytest tests/*_tpu.py`, as
# its own command on the chip: one process holds it): the rest of the
# suite assumes the 8-device virtual CPU mesh and is skipped on a 1-chip
# backend.
_ON_TPU = os.environ.get("PADDLE_TPU_TEST_ON_TPU", "0") == "1"
if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    # this box exposes ONE core (nproc=1): suite wall time IS XLA-CPU
    # compile throughput. Tests don't need optimized code — level 0
    # cuts the ResNet-class compiles ~40% (48s -> 30s measured); both
    # sides of every parity comparison compile at the same level
    if "xla_backend_optimization_level" not in flags:
        flags += " --xla_backend_optimization_level=0"
    os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

if not _ON_TPU:
    # belt and braces with the env var above: the tests must run on the
    # virtual 8-device CPU mesh even where jax was imported earlier
    jax.config.update("jax_platforms", "cpu")
    # The persistent compile cache (iteration-speed lever on the 1-core
    # box — without it the suite blows the tier-1 time budget) is placed
    # by the package's one rule (paddle_tpu/__init__.py): where
    # JAX_COMPILATION_CACHE_DIR says, else <repo>/.jax_compile_cache.
    # SOUNDNESS: a warm-cache hit of a donate_argnums executable is a
    # use-after-free on the CPU backend, so the executor DROPS donation
    # whenever a cache dir is configured there
    # (core/executor.py::donation_safe).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def _chip_file(path):
    """The chip's own test files are the glob `tests/test_*_tpu.py`: a new
    one is selected under PADDLE_TPU_TEST_ON_TPU by its name alone."""
    return fnmatch.fnmatch(os.path.basename(path), "test_*_tpu.py")


def pytest_collection_modifyitems(config, items):
    if _ON_TPU and len(jax.devices()) < 8:
        skip = pytest.mark.skip(reason="PADDLE_TPU_TEST_ON_TPU: suite "
                                "needs the 8-device virtual CPU mesh")
        for item in items:
            path = str(item.fspath)
            if not _chip_file(path):
                item.add_marker(skip)
    # under pytest-xdist, serialize each subprocess-spawning file into one
    # worker (`--dist loadgroup`): they fork whole jax worlds / embedded
    # interpreters and oversubscribe badly when co-scheduled
    # pserver/dist tests bind ephemeral ports (":0") and are parallel-
    # safe; only the files that spawn whole jax WORLDS or embedded
    # interpreters stay serialized
    heavy = ("test_multihost", "test_capi")
    for item in items:
        path = str(item.fspath)
        for h in heavy:
            if h in path:
                item.add_marker(pytest.mark.xdist_group(h))
                break
        # the TPU-gated files share ONE group: a chip belongs to one
        # process at a time
        if _chip_file(path):
            item.add_marker(pytest.mark.xdist_group("tpu"))
    # schedule the compile-heavy tests FIRST so a late-starting 300s test
    # can't extend the tail (xdist pops in collection order)
    heavy_tests = ("test_resnet50_trains", "test_se_resnext_trains",
                   "test_mp_sp_parity", "test_mp_parity",
                   "test_ring_attention_via_parallel_executor",
                   "test_resnet_space_to_depth_stem", "test_vgg16_trains",
                   "test_async_pserver_deepfm_two_trainers")
    items.sort(key=lambda it: 0 if any(h in it.name for h in heavy_tests)
               else 1)


@pytest.fixture(scope="module", autouse=True)
def _release_the_compiled_programs():
    """After each test file, drop what jax keeps of it: every live XLA:CPU
    executable holds memory maps, jax's caches keep every one alive, and at
    `vm.max_map_count` (65530) a worker's next compile segfaults (PR 69:
    32 k maps after `test_nemotron_h.py`, 54 k after `test_lfm2_moe.py`)."""
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Reset every process-global telemetry store AFTER each test
    (fluid-xray satellite): the metrics registry, tracer ring, steplog,
    recompilation observatory, flight recorder, and ambient trace
    context are shared process state — without this, tests could only
    assert snapshot-and-delta. The `observe` flag is restored too, so a
    test that enables it cannot leak emission into its neighbors.

    fluid-pulse extension: reset_all() also STOPS any pulse HTTP server
    the test started and clears the health engine + memory observatory,
    so no pulse thread (or stale detector state) survives a test — the
    teardown assertion below keeps that contract honest."""
    from paddle_tpu import flags, observe

    prev_observe = flags.get_flag("observe")
    yield
    if flags.get_flag("observe") != prev_observe:
        flags.set_flag("observe", prev_observe)
    observe.reset_all()
    import threading
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("pulse")]
    assert not leaked, f"pulse thread(s) leaked across reset_all: {leaked}"


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test fresh default programs + scope + name counter
    (reference tests use prog_scope decorators)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import ir, executor
    from paddle_tpu import unique_name

    prev_main, prev_startup = ir._main_program, ir._startup_program
    prev_scope = executor._global_scope
    ir._main_program = ir.Program()
    ir._startup_program = ir.Program()
    executor._global_scope = executor.Scope()
    gen = unique_name._generator
    unique_name._generator = unique_name.UniqueNameGenerator()
    np.random.seed(42)
    yield
    ir._main_program, ir._startup_program = prev_main, prev_startup
    executor._global_scope = prev_scope
    unique_name._generator = gen
