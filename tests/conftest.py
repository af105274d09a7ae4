"""Test config: force an 8-device virtual CPU mesh before jax import.

Multi-chip sharding logic (shard_map over a clients mesh axis) is exercised on
virtual CPU devices. The suite runs on the CPU whatever JAX_PLATFORMS the
environment pre-sets, so we override unconditionally; set
FEDML_TPU_TESTS_ON_TPU=1 to run it on an attached chip instead.
"""

import os

if not os.environ.get("FEDML_TPU_TESTS_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags += " --xla_force_host_platform_device_count=8"
    import sys

    _runslow = ("--runslow" in sys.argv
                or os.environ.get("FEDML_TPU_RUN_SLOW"))
    if "xla_backend_optimization_level" not in flags and not _runslow:
        # the fast suite is compile-bound on CPU and its workloads are tiny,
        # so trading codegen quality for compile time roughly halves
        # wall-clock. The --runslow tests are RUNTIME-heavy (real training
        # sweeps), where opt-0 codegen would cost far more than it saves —
        # they keep the default optimization level.
        flags += " --xla_backend_optimization_level=0"
    os.environ["XLA_FLAGS"] = flags

    # a plugin may have imported jax before this file ran; the env var alone
    # is then too late, but the backend is not yet initialized so jax.config
    # can still redirect to the virtual CPU mesh
    import jax

    jax.config.update("jax_platforms", "cpu")

    # persistent XLA compilation cache: the suite is compile-dominated on CPU,
    # so warm re-runs drop to a fraction of the cold time. Same rule as every
    # entry point: JAX_COMPILATION_CACHE_DIR if the caller set it, else the
    # checkout's .jax_cache (gitignored)
    from fedml_tpu.utils.cache import enable_compile_cache

    enable_compile_cache(min_compile_secs=0.5)

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run @pytest.mark.slow tests (DARTS bi-level compiles etc.; "
             "nightly coverage — the default run stays under the CI budget)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow") or os.environ.get("FEDML_TPU_RUN_SLOW"):
        return
    skip = pytest.mark.skip(reason="slow (compile-heavy); run with --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
