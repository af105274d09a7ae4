"""Seeded data for the cells, made in bulk on the host (the program stages
cohorts from host arrays). `--seed` moves sample values and labels; it never
moves a shape: the sizes of a federation come from its configuration file
alone, so every seed of a cell is served by one compiled program.

A configuration's `data` group names its `kind`, and
`benchmarks/datasets/<kind>.py` builds it (`make(spec, seed)`): a later PR
brings another kind as a file of its own. The image kinds are "class
prototype * 0.6 + gaussian noise * 0.35" (a copy of the program's surrogates,
fedml_tpu/data/sources.py, which a later PR may change and the yardstick may
not follow), through `federation` below.
"""

from __future__ import annotations

import importlib

import numpy as np


def _samples(rng: np.random.Generator, protos: np.ndarray, n: int):
    y = rng.integers(0, len(protos), size=n, dtype=np.int32)
    x = rng.standard_normal((n,) + protos.shape[1:], dtype=np.float32)
    x *= np.float32(0.35)
    x += protos[y] * np.float32(0.6)
    return x, y


def _pack(x, y, counts, n_max):
    """Rows of a flat (x, y) dealt to clients in order, padded to n_max."""
    px = np.zeros((len(counts), n_max) + x.shape[1:], x.dtype)
    py = np.zeros((len(counts), n_max), y.dtype)
    start = 0
    for i, c in enumerate(counts):
        px[i, :c] = x[start:start + c]
        py[i, :c] = y[start:start + c]
        start += c
    return px, py


def federation(spec: dict, seed: int, n_train: np.ndarray,
               n_test: np.ndarray) -> dict:
    """Prototype-plus-noise images of `spec["image_shape"]` in
    `spec["classes"]` classes, `n_train[i]` / `n_test[i]` rows for client i.
    -> {"train": (x[C, n_max, ...], y[C, n_max], counts[C]), "test": the same
    for the per-client test rows, "train_global"/"test_global": flat (x, y),
    "classes"}."""
    rng = np.random.default_rng(seed)
    shape = tuple(spec["image_shape"])
    protos = rng.standard_normal((spec["classes"],) + shape, dtype=np.float32)
    xtr, ytr = _samples(rng, protos, int(n_train.sum()))
    xte, yte = _samples(rng, protos, int(n_test.sum()))
    return {
        "train": _pack(xtr, ytr, n_train, int(n_train.max())) + (n_train,),
        "test": _pack(xte, yte, n_test, int(n_test.max())) + (n_test,),
        "train_global": (xtr, ytr), "test_global": (xte, yte),
        "classes": spec["classes"],
    }


def make(spec: dict, seed: int) -> dict:
    kind = importlib.import_module("benchmarks.datasets." + spec["kind"])
    return kind.make(spec, seed)
