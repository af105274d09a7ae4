"""One pool (CIFAR-10) dealt out in equal shares (`homo`)."""

from __future__ import annotations

import numpy as np

from benchmarks.harness import data


def make(spec: dict, seed: int) -> dict:
    c = spec["clients"]
    if spec["train_rows"] % c or spec["test_rows"] % c:
        raise ValueError("pooled data deals equal shares: rows must be a "
                         "multiple of clients")
    return data.federation(
        spec, seed, np.full(c, spec["train_rows"] // c, np.int32),
        np.full(c, spec["test_rows"] // c, np.int32))
