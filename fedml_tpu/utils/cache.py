"""Small shared utilities for compile-cache management."""

from __future__ import annotations

import os

# jax.monitoring listener registration is global and permanent — register
# exactly once per process no matter how many runs enable the cache.
_MONITORING_HOOKED = False

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def hook_monitoring() -> None:
    """Forward jax's monitoring into the telemetry ledger, once a process:
    compilation-cache events (hits, misses, writes) as `compile_cache`
    events, and every backend compile — cache-served or not — as a `compile`
    event with its seconds and the round and span it fell in. Both are
    no-ops while no tracer is installed."""
    global _MONITORING_HOOKED
    if _MONITORING_HOOKED:
        return
    import jax

    from fedml_tpu import telemetry

    def _forward(event: str, **kw) -> None:
        if "cache" in event:
            telemetry.emit("compile_cache", name=event)

    def _forward_duration(event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            tracer = telemetry.get_tracer()
            if tracer is not None:
                tracer.compile_event(duration)

    jax.monitoring.register_event_listener(_forward)
    jax.monitoring.register_event_duration_secs_listener(_forward_duration)
    _MONITORING_HOOKED = True


def enable_compile_cache(min_compile_secs: float = 1.0,
                         cache_dir: str | None = None) -> bool:
    """Turn on jax's persistent compilation cache so that heavy compiles
    (the ResNet-56 round, DARTS/GDAS graphs) are paid once and every later
    process — tests, CLIs, the benchmark, chip_smoke — reuses them.

    Where the cache lives is the caller's to say, from outside:
    `JAX_COMPILATION_CACHE_DIR` is read by jax itself, and when it is set
    this function sets NO directory. Otherwise the directory is the fixed
    `<checkout>/.jax_cache` (gitignored) — fixed because the path is part of
    the cache key, so a directory that moves never hits. `cache_dir=` is
    for tests only; no entry point passes it.

    Opt out with FEDML_TPU_NO_COMPILE_CACHE=1 (e.g. when timing cold-start
    compiles). Returns True when the cache was enabled."""
    hook_monitoring()
    if os.environ.get("FEDML_TPU_NO_COMPILE_CACHE"):
        return False
    import jax

    if cache_dir is None and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        cache_dir = os.path.join(repo_root, ".jax_cache")
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return True
