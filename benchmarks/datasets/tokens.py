"""Token sequences for next-word prediction, equal shares a silo. Ids are
drawn from a Zipf law over 1..vocab-1 (p(i) ~ i^-zipf_a; id 0 is the pad),
`y` is `x` shifted by one with the pad last, so every sequence holds
seq_len - 1 tokens that count and one that the loss has to leave out. The
dataset's `meta` says `task: nwp`: that is what the program picks its
trainer by."""

from __future__ import annotations

import numpy as np


def _split(rng: np.random.Generator, p: np.ndarray, clients: int, n: int,
           seq_len: int) -> tuple:
    x = (rng.choice(len(p), size=(clients, n, seq_len), p=p) + 1).astype(
        np.int32)
    y = np.concatenate([x[..., 1:], np.zeros_like(x[..., :1])], axis=-1)
    return x, y, np.full(clients, n, np.int32)


def make(spec: dict, seed: int) -> dict:
    """-> what `harness/data.py::federation` returns, over int32 tokens
    x[C, n, T] and next tokens y[C, n, T], and `meta`."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, spec["vocab"], dtype=np.float64) ** -spec["zipf_a"]
    p /= p.sum()
    c, t = spec["clients"], spec["seq_len"]
    train = _split(rng, p, c, spec["train_sequences"], t)
    test = _split(rng, p, c, spec["test_sequences"], t)
    return {
        "train": train, "test": test,
        "train_global": tuple(a.reshape(-1, t) for a in train[:2]),
        "test_global": tuple(a.reshape(-1, t) for a in test[:2]),
        "classes": spec["vocab"], "meta": {"task": "nwp"},
    }
