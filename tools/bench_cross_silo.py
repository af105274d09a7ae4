"""Cross-silo MFU measurement ladder (VERDICT r3 #2).

The r3 bench measured ResNet-56 cross-silo at 7,513 samples/s/chip
(~2.9 TFLOP/s, ~1.5% of bf16 peak) and PERF.md *argued* the ceiling came
from CIFAR ResNets' 16-64 channel stages underfilling the MXU's 128 lanes —
without measuring. This script runs the ladder that turns the essay into
evidence, timing a full local-training epoch per variant on the real chip:

  baseline      vmap over 10 silos, ResNet-56 (the bench config)
  single_silo   1 silo, bs 64 — is the silo-vmap itself costing anything?
  bigbatch      1 model, bs 640 — all silos' data in one batch (upper bound
                if per-silo weights were free)
  s2d           space-to-depth 2x2 on the input (32x32x3 -> 16x16x12), the
                standard TPU small-image transform, stem adjusted
  width x2/x4   stage widths (32,64,128) / (64,128,256): if TFLOP/s climbs
                steeply with channel width at ~constant time, the lanes were
                idle at width 16-64 and the per-sample model is simply too
                narrow for the MXU — the measured ceiling.
  grouped conv  microbench: vmap-of-conv over 10 silos vs one
                feature_group_count=10 conv at each stage shape — does
                manual grouping beat XLA's vmap lowering?

Run on the real TPU:  python tools/bench_cross_silo.py
Writes docs/cross_silo_ladder.json and prints one JSON line per rung.
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

from fedml_tpu.algorithms.engine import build_local_update  # noqa: E402
from fedml_tpu.core.config import FedConfig  # noqa: E402
from fedml_tpu.core.trainer import ClassificationTrainer  # noqa: E402
from fedml_tpu.models.resnet import ResNetCifar, Bottleneck  # noqa: E402

SILOS, N, BS = 10, 256, 64
# ResNet-56 fwd+bwd ~380 MFLOP/sample at widths (16,32,64) (PERF.md); FLOPs
# scale ~quadratically in width for conv layers
BASE_FLOP_PER_SAMPLE = 380e6


def _time_epoch(fn, args, reps=3, inner=4):
    out = fn(*args)  # compile + warmup
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)  # force completion, no host copy
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def make_variant(name):
    kw = dict(block=Bottleneck, layers=(6, 6, 6), output_dim=10)
    if name == "s2d":
        # s2d quarters spatial extent -> conv FLOPs drop ~4x (same widths)
        return ResNetCifar(s2d=True, **kw), 0.25
    if name == "width_x2":
        return ResNetCifar(widths=(32, 64, 128), **kw), 4.0
    if name == "width_x4":
        return ResNetCifar(widths=(64, 128, 256), **kw), 16.0
    return ResNetCifar(**kw), 1.0


def run_training_rung(name, silos, batch, model, flop_scale, n=N):
    cfg = FedConfig(batch_size=batch, epochs=1, lr=0.1, client_optimizer="sgd",
                    dtype="bfloat16", assume_full_clients=True)
    trainer = ClassificationTrainer(model)
    local = build_local_update(trainer, cfg)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(silos, n, 32, 32, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, size=(silos, n)).astype(np.int32))
    counts = jnp.full((silos,), n, jnp.int32)
    gv = trainer.init(jax.random.PRNGKey(0), x[0, :1])
    keys = jax.random.split(jax.random.PRNGKey(1), silos)

    if silos == 1:
        fn = jax.jit(lambda v, x, y, c, k: local(v, x[0], y[0], c[0], k[0]).variables)
    else:
        fn = jax.jit(lambda v, x, y, c, k: jax.vmap(
            local, in_axes=(None, 0, 0, 0, 0))(v, x, y, c, k).variables)
    dt = _time_epoch(fn, (gv, x, y, counts, keys))
    samples = silos * n
    sps = samples / dt
    tflops = sps * BASE_FLOP_PER_SAMPLE * flop_scale / 1e12
    rec = {"rung": name, "samples_per_sec_per_chip": round(sps, 1),
           "epoch_time_s": round(dt, 4), "achieved_tflops": round(tflops, 2),
           "flop_scale": flop_scale}
    print(json.dumps(rec))
    return rec


def run_grouped_conv_microbench():
    """vmap-of-conv over silos vs one feature_group_count=SILOS conv, at the
    three ResNet-56 stage shapes (bs 64)."""
    recs = []
    for (hw, cin, cout) in [(32, 16, 16), (16, 32, 32), (8, 64, 64)]:
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.rand(SILOS, BS, hw, hw, cin), jnp.bfloat16)
        w = jnp.asarray(rng.rand(SILOS, 3, 3, cin, cout), jnp.bfloat16)

        def conv_one(x, w):
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        vmapped = jax.jit(jax.vmap(conv_one))

        xg = jnp.transpose(x, (1, 2, 3, 0, 4)).reshape(BS, hw, hw, SILOS * cin)
        wg = jnp.transpose(w, (1, 2, 3, 0, 4)).reshape(3, 3, cin, SILOS * cout)

        def grouped(xg, wg):
            return jax.lax.conv_general_dilated(
                xg, wg, (1, 1), "SAME", feature_group_count=SILOS,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        gfn = jax.jit(grouped)
        dt_v = _time_epoch(vmapped, (x, w), inner=16)
        dt_g = _time_epoch(gfn, (xg, wg), inner=16)
        rec = {"rung": f"groupedconv_{hw}x{hw}x{cin}",
               "vmap_ms": round(dt_v * 1e3, 3), "grouped_ms": round(dt_g * 1e3, 3),
               "grouped_speedup": round(dt_v / dt_g, 2)}
        print(json.dumps(rec))
        recs.append(rec)
    return recs


def main():
    print(f"# devices: {jax.devices()}")
    out = []
    model, _ = make_variant("baseline")
    out.append(run_training_rung("baseline_vmap10", SILOS, BS, model, 1.0))
    out.append(run_training_rung("single_silo", 1, BS, model, 1.0))
    out.append(run_training_rung("bigbatch_640", 1, 640, model, 1.0, n=SILOS * N))
    model, fs = make_variant("s2d")
    out.append(run_training_rung("s2d_input", SILOS, BS, model, fs))
    for nm in ("width_x2", "width_x4"):
        model, fs = make_variant(nm)
        out.append(run_training_rung(nm, SILOS, BS, model, fs))
    out.extend(run_grouped_conv_microbench())
    with open(os.path.join(os.path.dirname(__file__), "..", "docs",
                           "cross_silo_ladder.json"), "w") as f:
        json.dump(out, f, indent=2)


if __name__ == "__main__":
    main()
