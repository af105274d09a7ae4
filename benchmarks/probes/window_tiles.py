#!/usr/bin/env python3
"""The flash kernel's band at one tile against another, on the chip: the
forward and backward of `flash_attention` with a sliding window at the
shapes of `mellum2_lora.train`'s window layers (2 lanes x 8,192 tokens x 32
heads of 128, bfloat16, W 1,024), for each tile asked for, and the causal
kernel at its own tile beside them. Not part of a benchmark run: what chose
the band's own tile in `ops/attention.py::flash_blocks` (PERF.md section 4).

    python benchmarks/probes/window_tiles.py [--tiles 256,512,1024] [--reps 20]

Prints one JSON line a tile: the median milliseconds of a forward and of a
forward + backward over `--reps` calls, each ended by `block_until_ready`,
after two warm-up calls, and the tiles a head computes.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _median_ms(fn, args, reps: int) -> float:
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tiles", default="256,512,1024")
    p.add_argument("--window", type=int, default=1024)
    p.add_argument("--seq_len", type=int, default=8192)
    p.add_argument("--reps", type=int, default=20)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.attention import band_tiles, flash_attention

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    t, w = args.seq_len, args.window
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (2, t, 32, 128), jnp.bfloat16)
               for key in keys)
    scale = 128 ** -0.5
    for tile in [int(s) for s in args.tiles.split(",")] + [None]:
        window = w if tile else None

        def fwd(q, k, v, tile=tile, window=window):
            return flash_attention(q, k, v, True, tile, tile, None, scale,
                                   window)

        def loss(q, k, v, fwd=fwd):
            return jnp.sum(fwd(q, k, v).astype(jnp.float32))

        both = jax.jit(jax.grad(loss, (0, 1, 2)))
        print(json.dumps({
            "tile": tile, "window": window,
            "fwd_ms": _median_ms(jax.jit(fwd), (q, k, v), args.reps),
            "fwd_bwd_ms": _median_ms(both, (q, k, v), args.reps),
            "tiles_a_head": band_tiles(t, tile or 1024, tile or 1024, window),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
