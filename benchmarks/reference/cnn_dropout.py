"""CNN_DropOut (Reddi et al., "Adaptive Federated Optimization", EMNIST CNN):
3x3 VALID conv 32, 3x3 VALID conv 64, 2x2 max-pool, dropout .25, dense 128,
dropout .5, dense classes. 28x28x1 inputs, NHWC. 1,206,590 parameters at 62
classes."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c

HAS_STATE = False


def layers(sizes: dict) -> list[dict]:
    """The layers that multiply, for the FLOP count (harness/flops.py)."""
    n = sizes["classes"]
    return [
        {"kind": "conv", "out_hw": 26, "k": 3, "cin": 1, "cout": 32},
        {"kind": "conv", "out_hw": 24, "k": 3, "cin": 32, "cout": 64},
        {"kind": "dense", "cin": 12 * 12 * 64, "cout": 128},
        {"kind": "dense", "cin": 128, "cout": n},
    ]


def init(key, sizes: dict) -> dict:
    n = sizes["classes"]
    k = jax.random.split(key, 8)
    return {"params": {
        "conv2d_1": {"kernel": c.scaled_normal(k[0], (3, 3, 1, 32), 9),
                     "bias": 0.1 * jax.random.normal(k[1], (32,))},
        "conv2d_2": {"kernel": c.scaled_normal(k[2], (3, 3, 32, 64), 288),
                     "bias": 0.1 * jax.random.normal(k[3], (64,))},
        "linear_1": {"kernel": c.scaled_normal(k[4], (9216, 128), 9216),
                     "bias": 0.1 * jax.random.normal(k[5], (128,))},
        "linear_2": {"kernel": c.scaled_normal(k[6], (128, n), 128),
                     "bias": 0.1 * jax.random.normal(k[7], (n,))},
    }}


def apply(variables, x, train: bool, key, compute: str, mask=None):
    """-> (logits f32, new model state: none, so {}). `mask` (the batch's
    real rows) is unused: no layer here looks across rows."""
    p = variables["params"]
    dt = c.ACT_DTYPE[compute]
    x = x.astype(dt)
    for name in ("conv2d_1", "conv2d_2"):
        x = c.conv(x, p[name]["kernel"], compute) + p[name]["bias"].astype(dt)
        x = jax.nn.relu(x)
    b, h, w, ch = x.shape
    x = x.reshape(b, h // 2, 2, w // 2, 2, ch).max(axis=(2, 4))
    if train:
        x = c.dropout(x, 0.25, c.module_key(key, "Dropout_0", 1))
    x = x.reshape(b, -1)
    x = jax.nn.relu(c.dense(x, p["linear_1"]["kernel"], p["linear_1"]["bias"],
                            compute))
    if train:
        x = c.dropout(x, 0.5, c.module_key(key, "Dropout_1", 1))
    x = c.dense(x, p["linear_2"]["kernel"], p["linear_2"]["bias"], compute)
    return x.astype(jnp.float32), {}
