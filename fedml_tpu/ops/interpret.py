"""Where a Pallas kernel runs when the caller does not say: compiled by
Mosaic on a TPU backend, interpreted anywhere else — and never silently."""

from __future__ import annotations

import functools
import logging

import jax

log = logging.getLogger(__name__)


@functools.lru_cache(maxsize=None)
def _warn_interpreted(kernel: str, backend: str) -> None:
    log.warning(
        "pallas kernel %r is being INTERPRETED (default backend %r is not a "
        "TPU): results are correct, timings say nothing about the chip",
        kernel, backend)


def interpret_off_chip(kernel: str) -> bool:
    """The `interpret=` value for `kernel`: False on a TPU backend, always;
    True elsewhere (tier-1 runs on the CPU), with one warning per kernel."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    _warn_interpreted(kernel, backend)
    return True
