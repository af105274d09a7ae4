"""Benchmark: FedAvg rounds/sec + samples/sec/chip (+ zoo rungs).

Workloads (BENCH_WORKLOAD env):
  flagship (default) — mirrors the reference's FEMNIST north star
    (BASELINE.md: 3400 clients, 10 clients/round, CNN_DropOut, bs 20, E=1,
    SGD lr 0.1 — reference benchmark/README.md:56-59) with FEMNIST-shaped
    data (~200 samples/client).
  cross_silo — the BASELINE.md cross-silo table: CIFAR-10-shaped data,
    ResNet-56, 10 silos, bs 64 (reference benchmark/README.md:103-112),
    where arithmetic intensity is high enough for MFU to be meaningful.
  fednas | fedgkt | fedseg | turboaggregate — one measured round (or, for
    turboaggregate, the secure-vs-plain aggregation overhead at flagship
    model size) per non-FedAvg family (VERDICT r4 next #4: "measured, not
    argued" for the rest of the zoo).

Timing is variance-aware (VERDICT r4 next #5): BENCH_REPS (default 5)
repeats, value = MEDIAN, and the JSON carries a `spread` {min, max, reps}
field — the regression threshold this implies is recorded in docs/PERF.md.

The reference publishes no throughput numbers (BASELINE.json "published": {}),
so vs_baseline is null unless a reference measurement is provided via
BENCH_REF_SAMPLES_PER_SEC_PER_CHIP.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

import json
import os
import statistics
import time

import numpy as np

WORKLOADS = {
    # name: (model, output_dim, input_shape, samples/client, batch, clients)
    "flagship": ("cnn", 62, (28, 28, 1), 200, 20, 10),
    "cross_silo": ("resnet56", 10, (32, 32, 3), 256, 64, 10),
    # TPU-tuned variant: space-to-depth input (models/resnet.py resnet56_s2d)
    # — 3.7x cross_silo's samples/s/chip (docs/PERF.md ladder); a model
    # variant, so accuracy targets need re-validation before comparisons
    "cross_silo_s2d": ("resnet56_s2d", 10, (32, 32, 3), 256, 64, 10),
    "cross_silo_mobilenet": ("mobilenet", 10, (32, 32, 3), 256, 64, 10),
    # MobileNetV3-small (SE blocks + hardswish) — the registry-wide dtype
    # pipeline reaches it as of this round; rung exists to A/B bf16 there
    "cross_silo_mobilenet_v3": ("mobilenet_v3", 10, (32, 32, 3), 256, 64, 10),
    # BASELINE.md's published cross-silo config is E=20, bs 64, 5000
    # samples/silo (CIFAR/10 silos) — run either cross_silo* workload with
    # BENCH_EPOCHS=20 BENCH_SAMPLES_PER_CLIENT=5000 BENCH_SCAN_ROUNDS=1
    # BENCH_ROUNDS=1 to measure it (docs/PERF.md §cross-silo). E >= 10
    # auto-enables chunked donated-carry dispatch (BENCH_EPOCH_CHUNK below)
    # so the round is short-dispatch-safe and MEASURED, not extrapolated.
}


def _timed_reps(fn, reps):
    """Median + spread of `reps` calls of fn() (fn must block on completion).
    Returns (median_s, [times])."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times), times


def _emit(metric, value, unit, times, scale, **extras):
    """One bench JSON line with the variance-aware spread field (value and
    spread are `scale / time`)."""
    import jax

    print(json.dumps({
        "metric": metric,
        "value": round(value, 3),
        "unit": unit,
        "vs_baseline": None,  # reference publishes nothing for these
        "platform": jax.devices()[0].platform,
        "spread": {"min": round(scale / max(times), 3),
                   "max": round(scale / min(times), 3),
                   "reps": len(times)},
        **extras,
    }))


def _capped(ds, cap, test_cap=256):
    import dataclasses

    from fedml_tpu.data.packing import PackedClients

    return dataclasses.replace(
        ds,
        train=PackedClients(np.asarray(ds.train.x[:, :cap]),
                            np.asarray(ds.train.y[:, :cap]),
                            np.minimum(np.asarray(ds.train.counts), cap)),
        test_global=(ds.test_global[0][:test_cap], ds.test_global[1][:test_cap]),
    )


def run_zoo_workload(workload: str):
    """One measured round per non-FedAvg family (VERDICT r4 next #4); shapes
    chosen to be representative (CIFAR geometry, the reference's default
    models) while bounded enough for a quick bench."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.registry import load_dataset
    from fedml_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    reps = max(1, int(os.environ.get("BENCH_REPS", 5)))

    if workload == "fednas":
        # one federated DARTS search round: 4 silos x 256 CIFAR samples,
        # bi-level (weight+alpha) local search, default 8-channel 4-cell net
        from fedml_tpu.algorithms.fednas import FedNASAPI

        ds = _capped(load_dataset("cifar10", client_num_in_total=4,
                                  partition_method="homo"), 256)
        cfg = FedConfig(batch_size=64, epochs=1, lr=0.025, momentum=0.9,
                        wd=3e-4, client_num_in_total=4, client_num_per_round=4,
                        comm_round=1, dtype="bfloat16")
        api = FedNASAPI(ds, cfg)
        api.train_one_round(0)  # compile
        dt, times = _timed_reps(lambda: api.train_one_round(1), reps)
        samples = 4 * 256
        _emit("fednas_search_samples_per_sec_per_chip", samples / dt,
              "samples/s/chip", times, samples,
              round_time_s=round(dt, 3))
        return

    if workload == "fedgkt":
        # one GKT round (client feature phase + server KD phase), the
        # reference's split ResNet-56 pair, 8 edge clients x 256 samples
        from fedml_tpu.algorithms.fedgkt import FedGKTAPI
        from fedml_tpu.models.resnet_gkt import GKTClientResNet, GKTServerResNet

        ds = _capped(load_dataset("cifar10", client_num_in_total=8,
                                  partition_method="homo"), 256)
        cfg = FedConfig(batch_size=64, epochs=1, lr=0.1,
                        client_num_in_total=8, client_num_per_round=8,
                        comm_round=1)
        # bf16 flows through the model constructors (FedGKTAPI takes
        # modules, not a dtype config) — measured 1.12x over f32 (PERF.md)
        dt = jnp.bfloat16
        api = FedGKTAPI(ds, cfg, GKTClientResNet(output_dim=10, dtype=dt),
                        GKTServerResNet(output_dim=10, dtype=dt),
                        server_epochs=1)
        x = jnp.asarray(ds.train.x)
        y = jnp.asarray(ds.train.y)
        counts = jnp.asarray(ds.train.counts)
        # same mask expression as FedGKTAPI.train; KD targets via the API's
        # own initializer so the bench can't drift from the real loop
        mask = (jnp.arange(ds.train.n_max)[None, :] < counts[:, None]).astype(jnp.float32)
        logits0 = api._init_server_logits()
        key = jax.random.PRNGKey(0)
        jax.block_until_ready(api.train_one_round(0, x, y, counts, mask, logits0, key))

        def one():
            jax.block_until_ready(
                api.train_one_round(1, x, y, counts, mask, logits0, key))

        dt, times = _timed_reps(one, reps)
        samples = 8 * 256
        _emit("fedgkt_round_samples_per_sec_per_chip", samples / dt,
              "samples/s/chip", times, samples, round_time_s=round(dt, 3))
        return

    if workload == "fedseg":
        # one FedSeg round: DeepLabV3+ on pascal-shaped data, 4 clients —
        # the heaviest per-sample model family in the repo. Default rung is
        # 64px / width-32; the COMPUTE-BOUND rung (VERDICT weak #2: the
        # default is dispatch-bound, so dtype deltas drown in the ±10%
        # spread) is BENCH_SEG_IMAGE_SIZE=128 BENCH_SEG_WIDTH=64, where
        # per-sample FLOPs grow ~16x and the conv dtype actually shows.
        from fedml_tpu.algorithms.fedseg import FedSegAPI

        image_size = int(os.environ.get("BENCH_SEG_IMAGE_SIZE", 64))
        width = int(os.environ.get("BENCH_SEG_WIDTH", 32))
        seg_cap = int(os.environ.get("BENCH_SEG_CAP", 0))
        dtype = os.environ.get("BENCH_SEG_DTYPE", "bfloat16")
        ds = load_dataset("pascal_voc", client_num_in_total=4,
                          image_size=image_size)
        if seg_cap:
            ds = _capped(ds, seg_cap)
        cfg = FedConfig(batch_size=8, epochs=1, lr=0.007,
                        client_num_in_total=4, client_num_per_round=4,
                        comm_round=1, frequency_of_the_test=1000,
                        dtype=dtype, extra={"seg_width": width})
        api = FedSegAPI(ds, cfg)
        api.train_one_round(0)  # compile
        import jax as _jax

        def one():
            api.train_one_round(1)
            _jax.block_until_ready(api._inner.global_variables)

        dt, times = _timed_reps(one, reps)
        samples = int(np.asarray(ds.train.counts).sum())
        _emit("fedseg_round_samples_per_sec_per_chip", samples / dt,
              "samples/s/chip", times, samples, round_time_s=round(dt, 3),
              image_shape=list(np.asarray(ds.train.x[:1, 0]).shape[1:]),
              seg_width=width, dtype=dtype)
        return

    if workload == "turboaggregate":
        # the practitioner's first question: what does secure aggregation
        # COST vs a plain weighted mean, at flagship model size
        # (CNN_DropOut, 1,199,882 params) over 10 clients
        from fedml_tpu.algorithms.turboaggregate import SecureAggregator
        from fedml_tpu.core.trainer import ClassificationTrainer
        from fedml_tpu.models.registry import create_model
        from fedml_tpu.utils.pytree import tree_weighted_mean

        trainer = ClassificationTrainer(create_model("cnn", output_dim=62))
        gv = trainer.init(jax.random.PRNGKey(0), jnp.ones((1, 28, 28, 1)))
        rng = np.random.RandomState(0)
        n_clients = 10
        trees = [jax.tree.map(lambda l: np.asarray(l) + rng.normal(
            0, 1e-2, l.shape).astype(np.float32), gv["params"])
            for _ in range(n_clients)]
        weights = rng.randint(50, 200, n_clients).astype(np.float64)
        agg = SecureAggregator(n_clients)
        agg.secure_weighted_sum(trees, weights)  # warmup

        dt_sec, times = _timed_reps(
            lambda: agg.secure_weighted_sum(trees, weights), reps)
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *trees)
        jplain = jax.jit(lambda s, w: tree_weighted_mean(s, w))
        w32 = jnp.asarray(weights, jnp.float32)
        jax.block_until_ready(jplain(stacked, w32))
        dt_plain, _ = _timed_reps(
            lambda: jax.block_until_ready(jplain(stacked, w32)), reps)
        n_params = sum(int(np.asarray(l).size) for l in jax.tree.leaves(gv["params"]))
        print(json.dumps({
            "metric": "turboaggregate_secure_agg_overhead_x",
            "value": round(dt_sec / dt_plain, 1),
            "unit": "x_plain_aggregation",
            "vs_baseline": None,
            "platform": jax.devices()[0].platform,
            "spread": {"min": round(min(times) / dt_plain, 1),
                       "max": round(max(times) / dt_plain, 1),
                       "reps": len(times)},
            "secure_agg_s": round(dt_sec, 4),
            "plain_agg_s": round(dt_plain, 5),
            "n_params": n_params, "n_clients": n_clients,
            "note": "secure path is host-side field arithmetic by design "
                    "(Shamir shares never touch the accelerator)",
        }))
        return

    raise SystemExit(f"unknown zoo workload {workload!r}")


def main():
    import jax
    import jax.numpy as jnp

    from fedml_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()

    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_round_fn
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.core.trainer import ClassificationTrainer
    from fedml_tpu.models.registry import create_model

    workload = os.environ.get("BENCH_WORKLOAD", "flagship")
    if workload in ("fednas", "fedgkt", "fedseg", "turboaggregate"):
        return run_zoo_workload(workload)
    model_name, out_dim, in_shape, d_n, d_bs, d_cpr = WORKLOADS[workload]
    clients_per_round = int(os.environ.get("BENCH_CLIENTS_PER_ROUND", d_cpr))
    n_per_client = int(os.environ.get("BENCH_SAMPLES_PER_CLIENT", d_n))
    epochs = int(os.environ.get("BENCH_EPOCHS", 1))
    batch_size = int(os.environ.get("BENCH_BATCH_SIZE", d_bs))
    timed_rounds = int(os.environ.get("BENCH_ROUNDS", 60))
    # chunked donated-carry dispatch (engine.build_chunked_round_runner):
    # split an E-epoch round into ceil(E/chunk) short device programs so
    # long-E rounds (the reference cross-silo config is E=20) stay short
    # dispatches and BENCH_EPOCHS=20 measures a REAL round
    # instead of extrapolating. Auto-on at chunk=5 for E >= 10; set
    # BENCH_EPOCH_CHUNK=0 to force the monolithic scan, or any K >= 1 to
    # pick the chunk size. Trajectories are bit-identical either way
    # (tests/test_chunked_dispatch.py).
    epoch_chunk = int(os.environ.get("BENCH_EPOCH_CHUNK",
                                     "5" if epochs >= 10 else "0"))
    epoch_chunk = min(epoch_chunk, epochs)

    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")  # MXU-native default
    # the bench's packed rows are full by construction (every count ==
    # samples_per_client, samples % batch == 0), so the engine's
    # assume_full_clients specialization applies — bit-identical trajectories
    # (tests/test_fedavg.py), masks/no-op-selects compiled away. Disable with
    # BENCH_ASSUME_FULL=0 to measure the general ragged-clients path.
    assume_full = (os.environ.get("BENCH_ASSUME_FULL", "1") == "1"
                   and n_per_client % batch_size == 0)
    cfg = FedConfig(
        batch_size=batch_size, epochs=epochs, lr=0.1, client_optimizer="sgd",
        client_num_per_round=clients_per_round, dtype=dtype,
        assume_full_clients=assume_full,
        # one-matvec aggregation probe (docs/PERF.md agg section)
        extra={"flat_agg": os.environ.get("BENCH_FLAT_AGG", "0") == "1"},
    )
    trainer = ClassificationTrainer(create_model(model_name, output_dim=out_dim, dtype=dtype))
    agg = make_aggregator("fedavg", cfg)
    n_chips = jax.device_count()
    # silo-grouped conv lowering (docs/cross_silo_ladder.json: 1.55x @16ch):
    # default-on for the cross-silo ResNet-56 workload, BENCH_SILO_THRESHOLD=0
    # to disable / set a custom channel threshold on other ResNetCifar runs
    silo_thr = int(os.environ.get(
        "BENCH_SILO_THRESHOLD",
        "32" if workload == "cross_silo" and n_chips == 1 else "0"))
    if epoch_chunk > 0 and n_chips == 1 and silo_thr > 0:
        # the silo-grouped update is grad-outside-vmap (custom_vmap does not
        # compose as vmap(grad)), so it keeps the monolithic scan — chunking
        # keeps long-E dispatches short, silo-grouping wins MXU utilization;
        # they are mutually exclusive execution shapes today
        print("# BENCH_EPOCH_CHUNK set: silo-grouped lowering disabled for "
              "this run (chunked dispatch uses the vmap engine)",
              file=__import__("sys").stderr)
        silo_thr = 0
    silo_trainer = None
    if silo_thr > 0 and n_chips == 1 and hasattr(trainer.module, "silo_threshold"):
        from fedml_tpu.algorithms.silo_grouped import silo_trainer as make_silo

        silo_trainer = make_silo(trainer, silo_thr)
    if n_chips > 1:
        # shard the round's clients over every chip (ICI aggregation)
        from fedml_tpu.parallel import build_sharded_round_fn, make_mesh

        clients_per_round = ((clients_per_round + n_chips - 1) // n_chips) * n_chips
        round_fn = build_sharded_round_fn(trainer, cfg, agg, make_mesh())
    elif epoch_chunk > 0:
        from fedml_tpu.algorithms.engine import build_chunked_round_runner

        round_fn = build_chunked_round_runner(trainer, cfg, agg, epoch_chunk)
    elif silo_trainer is not None:
        from fedml_tpu.algorithms.silo_grouped import build_silo_round_fn

        round_fn = build_silo_round_fn(silo_trainer, cfg, agg)
    else:
        round_fn = build_round_fn(trainer, cfg, agg)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(clients_per_round, n_per_client, *in_shape).astype(np.float32))
    y = jnp.asarray(rng.randint(0, out_dim, size=(clients_per_round, n_per_client)).astype(np.int32))
    counts = jnp.asarray(np.full(clients_per_round, n_per_client, np.int32))

    key = jax.random.PRNGKey(0)
    gv = trainer.init(key, x[0, :1])
    state = agg.init_state(gv)

    scan_rounds = int(os.environ.get("BENCH_SCAN_ROUNDS", 20))
    reps = max(1, int(os.environ.get("BENCH_REPS", 5)))  # median-of-N + spread
    fused = os.environ.get("BENCH_FUSED", "0") == "1"
    used_fused = False
    if scan_rounds > 1 and n_chips == 1 and epoch_chunk == 0:
        # dispatch-amortized fast path: R rounds per jit call (in-graph sampling)
        from fedml_tpu.algorithms.engine import build_multi_round_fn

        multi = None
        if (fused and workload == "flagship" and epochs == 1
                and n_per_client % batch_size == 0):
            # fused local-SGD pallas kernel (ops/fused_sgd.py): the whole
            # client epoch in one program, weights resident in VMEM. Measured
            # SLOWER than the engine path at flagship shapes (0.44x — see
            # docs/PERF.md for why), kept opt-in as the measured experiment.
            # Asked for, it runs or the bench fails: no fallback to the engine.
            from fedml_tpu.ops.fused_sgd import (
                FusedEpochSpec, build_fused_multi_round_fn)

            spec = FusedEpochSpec(
                height=in_shape[0], width=in_shape[1], n_classes=out_dim,
                samples=n_per_client, batch=batch_size, lr=cfg.lr,
                grad_clip=cfg.grad_clip,
                compute_dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)
            multi = build_fused_multi_round_fn(spec, agg, scan_rounds)
            gv2, state2, _ = multi(gv, state, x, y, counts, key)
            if not all(bool(jnp.all(jnp.isfinite(l)))
                       for l in jax.tree.leaves(gv2)):
                raise FloatingPointError("fused path produced non-finite params")
            used_fused = True
        if multi is None:
            if silo_trainer is not None:
                from fedml_tpu.algorithms.silo_grouped import build_silo_multi_round_fn

                multi = build_silo_multi_round_fn(silo_trainer, cfg, agg, scan_rounds)
            else:
                multi = build_multi_round_fn(trainer, cfg, agg, scan_rounds)
            gv, state, _ = multi(gv, state, x, y, counts, key)  # warmup/compile
            jax.block_until_ready(gv)
        # (the fused probe above already served as its own warmup)
        calls = max(1, timed_rounds // scan_rounds)
        rep_times = []
        for rep in range(reps):
            t0 = time.perf_counter()
            for r in range(calls):
                gv, state, _ = multi(gv, state, x, y, counts,
                                     jax.random.fold_in(key, rep * calls + r))
            jax.block_until_ready(gv)
            rep_times.append(time.perf_counter() - t0)
        timed_rounds = calls * scan_rounds
    else:
        # warmup (compile)
        gv, state, _ = round_fn(gv, state, x, y, counts, key)
        jax.block_until_ready(gv)
        rep_times = []
        for rep in range(reps):
            t0 = time.perf_counter()
            for r in range(timed_rounds):
                gv, state, _ = round_fn(gv, state, x, y, counts,
                                        jax.random.fold_in(key, rep * timed_rounds + r))
            jax.block_until_ready(gv)
            rep_times.append(time.perf_counter() - t0)

    # variance-aware: median is the headline, min/max bound run-to-run jitter
    dt = statistics.median(rep_times)
    rounds_per_sec = timed_rounds / dt
    samples_per_round = clients_per_round * n_per_client * epochs
    samples_per_sec_per_chip = rounds_per_sec * samples_per_round / n_chips

    ref = os.environ.get("BENCH_REF_SAMPLES_PER_SEC_PER_CHIP")
    vs_baseline = samples_per_sec_per_chip / float(ref) if ref else None

    metric_name = {
        "flagship": "fedavg_femnist_cnn_samples_per_sec_per_chip",
        "cross_silo": "fedavg_cifar_resnet56_samples_per_sec_per_chip",
        "cross_silo_s2d": "fedavg_cifar_resnet56_s2d_samples_per_sec_per_chip",
        "cross_silo_mobilenet": "fedavg_cifar_mobilenet_samples_per_sec_per_chip",
        "cross_silo_mobilenet_v3": "fedavg_cifar_mobilenet_v3_samples_per_sec_per_chip",
    }[workload]
    print(json.dumps({
        "metric": metric_name,
        "value": round(samples_per_sec_per_chip, 2),
        "unit": "samples/s/chip",
        "vs_baseline": vs_baseline,
        "rounds_per_sec": round(rounds_per_sec, 4),
        "round_time_s": round(dt / timed_rounds, 3),
        "epochs": epochs,
        "epoch_chunk": epoch_chunk,
        "clients_per_round": clients_per_round,
        "samples_per_client": n_per_client,
        "batch_size": batch_size,
        "n_chips": n_chips,
        "platform": jax.devices()[0].platform,
        "fused_kernel": used_fused,
        "silo_threshold": silo_thr if silo_trainer is not None else 0,
        "flat_agg": cfg.extra.get("flat_agg", False),
        "spread": {
            # samples/s implied by the slowest/fastest repetition
            "min": round(timed_rounds / max(rep_times) * samples_per_round / n_chips, 2),
            "max": round(timed_rounds / min(rep_times) * samples_per_round / n_chips, 2),
            "reps": len(rep_times),
        },
    }))


if __name__ == "__main__":
    main()
