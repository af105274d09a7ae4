"""Small shared utilities for compile-cache management."""

from __future__ import annotations

import os

# jax.monitoring listener registration is global and permanent — register
# exactly once per process no matter how many runs enable the cache.
_MONITORING_HOOKED = False


def _hook_cache_monitoring() -> None:
    """Forward jax's compilation-cache monitoring events (hits, misses,
    writes) into the telemetry ledger as `compile_cache` events. No-op when
    no tracer is installed."""
    global _MONITORING_HOOKED
    if _MONITORING_HOOKED:
        return
    import jax

    def _forward(event: str, **kw) -> None:
        if "cache" not in event:
            return
        from fedml_tpu import telemetry
        telemetry.emit("compile_cache", name=event)

    jax.monitoring.register_event_listener(_forward)
    _MONITORING_HOOKED = True


def enable_compile_cache(min_compile_secs: float = 1.0,
                         cache_dir: str | None = None) -> bool:
    """Turn on jax's persistent compilation cache so that heavy compiles
    (the ResNet-56 round, DARTS/GDAS graphs, the fused local-SGD kernel) are
    paid once and every later process — tests, CLIs, bench, chip_smoke —
    reuses them.

    Where the cache lives is the caller's to say, from outside:
    `JAX_COMPILATION_CACHE_DIR` is read by jax itself, and when it is set
    this function sets NO directory. Otherwise the directory is the fixed
    `<checkout>/.jax_cache` (gitignored) — fixed because the path is part of
    the cache key, so a directory that moves never hits. `cache_dir=` is
    for tests only; no entry point passes it.

    Opt out with FEDML_TPU_NO_COMPILE_CACHE=1 (e.g. when timing cold-start
    compiles). Returns True when the cache was enabled."""
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
    _hook_cache_monitoring()
    return True
