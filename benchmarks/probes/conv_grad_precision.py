#!/usr/bin/env python3
"""The witness for PERF.md section 7, row 0: one gradient of CNN_DropOut's
layers (dropout left out) at `flagship.train`'s shapes, 10 clients x 20 rows
of 28x28x1, taken four ways on the chip and held against the same gradient
at `highest` precision:

    vmapped over the clients at JAX's default precision  (what the CLI runs)
    one client at a time at the default precision
    vmapped at `high`;  vmapped at `highest`

    python benchmarks/probes/conv_grad_precision.py [--seed N]

Prints one JSON line: per parameter leaf and way, |g - g_highest| / |g_highest|
over all clients' gradients (the norm of the difference, not the gap of the
norms), and the norm of the mean gradient over the clients. Imports nothing of
the program; not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CLIENTS, ROWS, CLASSES = 10, 20, 62


def loss(params, x, y):
    import jax
    import jax.numpy as jnp

    for name in ("conv2d_1", "conv2d_2"):
        x = jax.lax.conv_general_dilated(
            x, params[name]["kernel"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + params[name]["bias"]
        x = jax.nn.relu(x)
    b, h, w, ch = x.shape
    x = x.reshape(b, h // 2, 2, w // 2, 2, ch).max(axis=(2, 4)).reshape(b, -1)
    x = jax.nn.relu(x @ params["linear_1"]["kernel"]
                    + params["linear_1"]["bias"])
    logits = x @ params["linear_2"]["kernel"] + params["linear_2"]["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.reference import cnn_dropout

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    key = jax.random.PRNGKey(args.seed % 2 ** 32)
    kp, kx, ky, kc = jax.random.split(key, 4)
    params = cnn_dropout.init(kp, {"classes": CLASSES})["params"]
    y = jax.random.randint(ky, (CLIENTS, ROWS), 0, CLASSES)
    protos = jax.random.normal(kc, (CLASSES, 28, 28, 1))
    x = 0.6 * protos[y] + 0.35 * jax.random.normal(
        kx, (CLIENTS, ROWS, 28, 28, 1))

    def vmapped(precision):
        with jax.default_matmul_precision(precision):
            return jax.jit(jax.vmap(jax.grad(loss), (None, 0, 0)))(
                params, x, y)

    def one_by_one(precision):
        with jax.default_matmul_precision(precision):
            g = jax.jit(jax.grad(loss))
            each = [g(params, x[i], y[i]) for i in range(CLIENTS)]
        return jax.tree.map(lambda *a: jnp.stack(a), *each)

    ways = {"vmapped_default": vmapped("default"),
            "one_client_default": one_by_one("default"),
            "vmapped_high": vmapped("high"),
            "vmapped_highest": vmapped("highest"),
            "one_client_highest": one_by_one("highest")}
    truth = ways["vmapped_highest"]

    def norm(a):
        return float(jnp.sqrt(jnp.sum(jnp.square(a))))

    out = {}
    for way, g in ways.items():
        flat, _ = jax.tree_util.tree_flatten_with_path(g)
        ref = jax.tree.leaves(truth)
        out[way] = {
            jax.tree_util.keystr(path): {
                "rel_error": norm(a - r) / norm(r),
                "norm_of_mean": norm(a.mean(axis=0))}
            for (path, a), r in zip(flat, ref)}
    print(json.dumps({"seed": args.seed,
                      "device": jax.devices()[0].device_kind, "ways": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
