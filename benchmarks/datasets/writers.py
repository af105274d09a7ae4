"""Naturally unbalanced per-writer sets (FederatedEMNIST). The sizes are a
function of the configuration alone, never of `--seed`: a lognormal shape
drawn from `sizes_seed`, scaled so that the federation holds exactly the
published `train_rows` and `test_rows`, clipped to [n_min, n_max], writer 0
pinned at n_max so that the padded width is n_max for every seed."""

from __future__ import annotations

import numpy as np

from benchmarks.harness import data


def _scaled_to(shape: np.ndarray, total: int, lo: int, hi: int) -> np.ndarray:
    """Whole numbers in [lo, hi] in proportion to `shape`, the first pinned at
    hi, that add up to `total` exactly."""
    def at(scale):
        n = np.clip(np.rint(shape * scale), lo, hi).astype(np.int64)
        n[0] = hi
        return n

    if not lo * len(shape) <= total <= hi * len(shape):
        raise ValueError("rows do not fit the clients at these bounds")
    a, b = 0.0, 2.0 * hi / shape.min()
    for _ in range(200):            # bisection on the scale
        mid = (a + b) / 2
        a, b = (mid, b) if at(mid).sum() < total else (a, mid)
    n = at(a)
    for i in range(1, len(n)):      # what rounding left over, one row each
        if n.sum() == total:
            break
        if n[i] < hi:
            n[i] += 1
    if n.sum() != total:
        raise ValueError("cannot reach the published number of rows")
    return n.astype(np.int32)


def sizes(spec: dict) -> tuple[np.ndarray, np.ndarray]:
    """(train rows, test rows) per writer."""
    rng = np.random.RandomState(spec["sizes_seed"])
    shape = rng.lognormal(0.0, spec["lognormal_sigma"], spec["clients"])
    train = _scaled_to(shape, spec["train_rows"], spec["n_min"],
                       spec["n_max"])
    share = spec["test_rows"] / spec["train_rows"]
    test = _scaled_to(train.astype(np.float64), spec["test_rows"], 2,
                      int(np.ceil(spec["n_max"] * share)))
    return train, test


def make(spec: dict, seed: int) -> dict:
    return data.federation(spec, seed, *sizes(spec))
