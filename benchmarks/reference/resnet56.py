"""ResNet-56 for CIFAR as the FedML reference trains it cross-silo
(fedml_api/model/cv/resnet.py: Bottleneck blocks, layers [6, 6, 6], 3x3 stem
conv 16, stage planes 16/32/64 with expansion 4, BatchNorm, global average
pool, fc). 32x32x3 inputs, NHWC. BatchNorm as the source's (torch): batch
statistics in training over the batch's REAL rows only (a client's last
batch is short, as a DataLoader's is; rows that only fill a fixed shape take
no part), biased variance (as E[x^2] - E[x]^2) to normalise with, running
statistics with momentum 0.9 that take the unbiased variance, epsilon 1e-5,
arithmetic in float32 whatever the convolutions compute in."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c

HAS_STATE = True
STAGES = ((16, 6), (32, 6), (64, 6))
EXPANSION = 4


def _blocks():
    """(name, cin, planes, stride, has_downsample) of the 18 blocks."""
    cin, out = 16, []
    for stage, (planes, n) in enumerate(STAGES):
        for b in range(n):
            stride = 2 if (stage > 0 and b == 0) else 1
            down = stride != 1 or cin != planes * EXPANSION
            out.append((f"Bottleneck_{len(out)}", cin, planes, stride, down))
            cin = planes * EXPANSION
    return out


def layers(sizes: dict) -> list[dict]:
    out = [{"kind": "conv", "out_hw": 32, "k": 3, "cin": 3, "cout": 16}]
    hw = 32
    for _, cin, planes, stride, down in _blocks():
        out.append({"kind": "conv", "out_hw": hw, "k": 1, "cin": cin,
                    "cout": planes})
        hw //= stride
        out.append({"kind": "conv", "out_hw": hw, "k": 3, "cin": planes,
                    "cout": planes})
        out.append({"kind": "conv", "out_hw": hw, "k": 1, "cin": planes,
                    "cout": planes * EXPANSION})
        if down:
            out.append({"kind": "conv", "out_hw": hw, "k": 1, "cin": cin,
                        "cout": planes * EXPANSION})
    out.append({"kind": "dense", "cin": 64 * EXPANSION,
                "cout": sizes["classes"]})
    return out


def _bn_init(key, ch):
    k1, k2 = jax.random.split(key)
    return ({"scale": 1.0 + 0.1 * jax.random.normal(k1, (ch,)),
             "bias": 0.1 * jax.random.normal(k2, (ch,))},
            {"mean": jnp.zeros((ch,)), "var": jnp.ones((ch,))})


def init(key, sizes: dict) -> dict:
    keys = iter(jax.random.split(key, 256))
    params, stats = {}, {}

    def norm(into_p, into_s, name, ch):
        p, s = _bn_init(next(keys), ch)
        into_p[name] = {"BatchNorm_0": p}
        into_s[name] = {"BatchNorm_0": s}

    params["conv1"] = {"kernel": c.scaled_normal(next(keys), (3, 3, 3, 16), 27)}
    norm(params, stats, "_Norm_0", 16)
    for name, cin, planes, _, down in _blocks():
        bp, bs = {}, {}
        shapes = [(1, cin, planes), (3, planes, planes),
                  (1, planes, planes * EXPANSION)]
        if down:
            shapes.append((1, cin, planes * EXPANSION))
        for i, (k, ci, co) in enumerate(shapes):
            bp[f"Conv_{i}"] = {"kernel": c.scaled_normal(
                next(keys), (k, k, ci, co), k * k * ci)}
            norm(bp, bs, f"_Norm_{i}", co)
        params[name], stats[name] = bp, bs
    n = sizes["classes"]
    params["fc"] = {
        "kernel": c.scaled_normal(next(keys), (64 * EXPANSION, n),
                                  64 * EXPANSION),
        "bias": 0.1 * jax.random.normal(next(keys), (n,))}
    return {"params": params, "batch_stats": stats}


def _norm(x, p, s, train: bool, mask):
    p, s = p["BatchNorm_0"], s["BatchNorm_0"]
    x = x.astype(jnp.float32)
    if train:
        rows = mask.astype(jnp.float32)
        n = jnp.maximum(rows.sum(), 1.0) * x.shape[1] * x.shape[2]
        w = rows[:, None, None, None] / n
        mean = (x * w).sum(axis=(0, 1, 2))
        var = jnp.maximum((x * x * w).sum(axis=(0, 1, 2)) - mean * mean, 0.0)
        unbiased = var * n / jnp.maximum(n - 1.0, 1.0)
        new = {"BatchNorm_0": {"mean": 0.9 * s["mean"] + 0.1 * mean,
                               "var": 0.9 * s["var"] + 0.1 * unbiased}}
    else:
        mean, var, new = s["mean"], s["var"], {"BatchNorm_0": s}
    y = (x - mean) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    return y, new


def apply(variables, x, train: bool, key, compute: str, mask=None):
    """-> (logits, new batch_stats). `key` is unused: no dropout. `mask`:
    which rows of the batch are real (all, where it is None)."""
    p, s = variables["params"], variables["batch_stats"]
    new = {}
    if mask is None:
        mask = jnp.ones(x.shape[0], bool)

    def norm(x, params, stats, name):
        return _norm(x, params[name], stats[name], train, mask)

    x = c.conv(x, p["conv1"]["kernel"], compute, pad=1)
    x, new["_Norm_0"] = norm(x, p, s, "_Norm_0")
    x = jax.nn.relu(x)
    for name, _, _, stride, down in _blocks():
        bp, bs, bn = p[name], s[name], {}
        out = c.conv(x, bp["Conv_0"]["kernel"], compute)
        out, bn["_Norm_0"] = norm(out, bp, bs, "_Norm_0")
        out = c.conv(jax.nn.relu(out), bp["Conv_1"]["kernel"], compute,
                     stride=stride, pad=1)
        out, bn["_Norm_1"] = norm(out, bp, bs, "_Norm_1")
        out = c.conv(jax.nn.relu(out), bp["Conv_2"]["kernel"], compute)
        out, bn["_Norm_2"] = norm(out, bp, bs, "_Norm_2")
        identity = x
        if down:
            identity = c.conv(x, bp["Conv_3"]["kernel"], compute,
                              stride=stride)
            identity, bn["_Norm_3"] = norm(identity, bp, bs, "_Norm_3")
        x = jax.nn.relu(out + identity)
        new[name] = bn
    x = x.mean(axis=(1, 2))
    logits = c.dense(x, p["fc"]["kernel"], p["fc"]["bias"], compute)
    return logits, {"batch_stats": new}
