"""Silo-grouped path decomposition ladder (round 5).

The grouped-conv microbench (bench_cross_silo.py) promised 1.55x/1.22x at
the 16/32-channel 3x3 stages; the first silo-grouped bench delivered only
+4% end-to-end. This ladder isolates where the promised win goes:

  vmap_engine     the standard engine (vmap(grad)) — the baseline
  silo_t0         silo update (grad-outside-vmap) with PLAIN nn.Conv:
                  the restructure's own cost, no grouping
  silo_t16/32/64  grouped lowering at increasing channel thresholds
  convonly_*      forward-only conv chain in both lowerings WITH the
                  per-call layout transposes included (the microbench
                  excluded them — measuring the churn hypothesis)

Run on the real TPU: python tools/bench_silo.py
Writes docs/silo_ladder.json, one JSON line per rung.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from fedml_tpu.utils.cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

from fedml_tpu.algorithms.aggregators import make_aggregator  # noqa: E402
from fedml_tpu.algorithms.engine import build_round_fn  # noqa: E402
from fedml_tpu.algorithms.silo_grouped import (  # noqa: E402
    build_silo_round_fn,
    silo_trainer,
)
from fedml_tpu.core.config import FedConfig  # noqa: E402
from fedml_tpu.core.trainer import ClassificationTrainer  # noqa: E402
from fedml_tpu.models.resnet import Bottleneck, ResNetCifar  # noqa: E402
from fedml_tpu.ops.silo_conv import make_silo_conv  # noqa: E402

SILOS, N, BS = 10, 256, 64


def _time(fn, args, reps=3, inner=4):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def run_round_rung(name, threshold):
    cfg = FedConfig(batch_size=BS, epochs=1, lr=0.1, client_optimizer="sgd",
                    dtype="bfloat16", assume_full_clients=True,
                    client_num_per_round=SILOS)
    model = ResNetCifar(block=Bottleneck, layers=(6, 6, 6), output_dim=10)
    trainer = ClassificationTrainer(model)
    agg = make_aggregator("fedavg", cfg)
    if threshold is None:
        fn = build_round_fn(trainer, cfg, agg)
    else:
        tr = silo_trainer(trainer, threshold) if threshold > 0 else trainer
        fn = build_silo_round_fn(tr, cfg, agg)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(SILOS, N, 32, 32, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, size=(SILOS, N)).astype(np.int32))
    counts = jnp.full((SILOS,), N, jnp.int32)
    gv = trainer.init(jax.random.PRNGKey(0), x[0, :1])
    st = agg.init_state(gv)
    key = jax.random.PRNGKey(1)
    dt = _time(lambda *a: fn(*a)[0], (gv, st, x, y, counts, key))
    rec = {"rung": name, "round_time_s": round(dt, 4),
           "samples_per_sec_per_chip": round(SILOS * N / dt, 1)}
    print(json.dumps(rec))
    return rec


def run_convonly_rung(hw, cin, cout, depth=4):
    """A chain of `depth` 3x3 convs with relu between, per lowering, WITH
    layout transposes inside the timed region (unlike the r4 microbench)."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(SILOS, BS, hw, hw, cin), jnp.bfloat16)
    ws = [jnp.asarray(rng.rand(SILOS, 3, 3, cin if d == 0 else cout, cout),
                      jnp.bfloat16) for d in range(depth)]

    def chain_vmap(x, ws):
        def one(x, ws):
            # static depth-`depth` list — deliberate trace-time unroll
            for w in ws:  # graft-lint: disable=traced-loop -- static depth list, intended unroll
                x = jax.nn.relu(jax.lax.conv_general_dilated(
                    x, w, (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC")))
            return x
        return jax.vmap(one)(x, ws)

    conv = make_silo_conv((1, 1), "SAME", threshold=max(cin, cout))

    def chain_grouped(x, ws):
        def one(x, *ws):
            # static depth-`depth` list — deliberate trace-time unroll
            for w in ws:  # graft-lint: disable=traced-loop -- static depth list, intended unroll
                x = jax.nn.relu(conv(x, w))
            return x
        return jax.vmap(one)(x, *ws)

    dt_v = _time(jax.jit(chain_vmap), (x, ws), inner=16)
    dt_g = _time(jax.jit(chain_grouped), (x, ws), inner=16)
    rec = {"rung": f"convonly_{hw}x{hw}x{cin}", "vmap_ms": round(dt_v * 1e3, 3),
           "grouped_ms": round(dt_g * 1e3, 3),
           "grouped_speedup": round(dt_v / dt_g, 2)}
    print(json.dumps(rec))
    return rec


def main():
    print(f"# devices: {jax.devices()}")
    out = []
    out.append(run_round_rung("vmap_engine", None))
    out.append(run_round_rung("silo_t0", 0))
    for t in (16, 32, 64):
        out.append(run_round_rung(f"silo_t{t}", t))
    for hw, cin in [(32, 16), (16, 32), (8, 64)]:
        out.append(run_convonly_rung(hw, cin, cin))
    with open(os.path.join(os.path.dirname(__file__), "..", "docs",
                           "silo_ladder.json"), "w") as f:
        json.dump(out, f, indent=2)


if __name__ == "__main__":
    main()
