#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for `--seconds`, compares the timed path's first
rounds with the plain reference, prints one JSON object as the LAST line of
stdout and exits. Exits 2 unless JAX's first device is a TPU and there are
as many chips as the cell asks for: there is no CPU fallback (the tests call
`run_cell`, which takes the platform as found).

Driven by data: the cell's entry in BENCHMARK.json names a configuration
(`benchmarks/configs/<config>.json`, whose `reference` names a module of
`benchmarks/reference/`, whose `compare` one of `benchmarks/compare/` and
whose `data.kind` one of `benchmarks/datasets/`), a traffic mix
(`benchmarks/traffic/<traffic>.json`, which may name a `compare` of its own
and bring the `limits` of what that adds) and, through the metrics that list
it, readers (`benchmarks/end_to_end/<metric>.json`,
`benchmarks/layer_metrics/<metric>.json`). Nothing here names a cell, a
metric, a model, a kind of data, a task, a trainer, a loss or a kind of
layer: the program picks model and trainer from the argv and the dataset's
`meta` (`build_api`), the reference model's module brings its layers'
FLOPs and its loss, and what the device executed is read from the program's
spans. The last line has the contract's keys and `compared`; what else a run
has to say (`run`) is the line before it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmarks")
OUT_DIR = os.path.join(ROOT, ".bench_out")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, manifest: str | None = None) -> dict:
    """Everything the files say about one cell."""
    bench = _json(manifest or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    # a manifest elsewhere (the tests' tiny cells) may bring traffic files
    traffic_dir = os.path.join(os.path.dirname(manifest or ""), "traffic")
    if not os.path.isdir(traffic_dir):
        traffic_dir = os.path.join(HERE, "traffic")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": _json(os.path.join(ROOT, entry["file"])),
        "traffic": _json(os.path.join(traffic_dir,
                                      cell["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def configure_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the key), unless the caller placed it;
    every program is kept, however fast it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(chips: int) -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def build_api(config: dict, traffic: dict, data: dict, seed: int):
    """-> (FedAvgAPI, FedConfig) as `main_fedavg.run` builds them from the
    configuration's and the traffic mix's argv, on the benchmark's own
    dataset (what a `benchmarks/datasets/` kind returns; its `meta`, where
    it brings one, is the dataset's: `{"task": "nwp"}`). Which model an argv
    means, which trainer (and so which loss) a dataset's task takes and what
    wraps it (`--lora_rank`) is decided by the program's preamble
    (`fedml_tpu/experiments/common.py`) and by nothing here: a PR that gives
    the program a new model or trainer changes the program alone."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.data import FederatedDataset, PackedClients
    from fedml_tpu.experiments import common

    argv = (list(config["argv"]) + list(traffic.get("argv", []))
            + ["--comm_round", str(10 ** 6), "--seed", str(seed)])
    args = common.add_args(argparse.ArgumentParser()).parse_args(argv)
    ds = FederatedDataset(
        name=args.dataset, train=PackedClients(*data["train"]),
        test=PackedClients(*data["test"]), train_global=data["train_global"],
        test_global=data["test_global"], class_num=data["classes"],
        meta=dict(data.get("meta", {})))
    cfg, trainer = _through_setup_run(common, args, ds)
    return FedAvgAPI(ds, cfg, trainer), cfg


def _through_setup_run(common, args, ds) -> tuple:
    """(config, trainer) for `ds` through `setup_run(args)`, the program's
    only entry that picks model and trainer. It loads the data itself in the
    same body (an entry that takes a dataset, `build_trainer(args, cfg,
    ds)`, is the program's to cut: PERF.md section 7), so it is called with
    the benchmark's dataset in its loader's place, and has to hand that very
    dataset back: if it does not, the program resolves its loader another
    way now, model and trainer were built for data of its own, and the run
    ends here. It also seeds the global generators, as the CLI does (kept:
    nothing the window runs draws from them), turns INFO logging on, and
    sets the compile cache's threshold to the CLI's 1 s, which would leave
    this run's fast programs out of the cache: those two are put back, so
    that the process is the one the benchmark has measured since PR 26."""
    import logging

    import jax

    root = logging.getLogger()
    level, handlers = root.level, root.handlers[:]
    secs = jax.config.jax_persistent_cache_min_compile_time_secs
    loader = common.load_dataset
    common.load_dataset = lambda *a, **k: ds
    try:
        cfg, loaded, trainer = common.setup_run(args)
    finally:
        common.load_dataset = loader
        root.setLevel(level)
        root.handlers[:] = handlers
        jax.config.update("jax_persistent_cache_min_compile_time_secs", secs)
    if loaded is not ds:
        raise SystemExit(
            "fedml_tpu.experiments.common.setup_run did not take the "
            "benchmark's dataset through common.load_dataset: its model and "
            "trainer are not this cell's")
    return cfg, trainer


def check_hyper(cfg, hyper: dict) -> None:
    """The reference's hyper-parameters are the configuration file's; the
    program's come from its argv. They must be the same run."""
    for key, want in hyper.items():
        got = getattr(cfg, key)
        if got != want:
            raise SystemExit(f"configuration file says {key}={want!r}, the "
                             f"program's config says {got!r}")


def comparison(spec: dict) -> tuple:
    """-> (module of benchmarks/compare/, limits, names not compared). The
    configuration names them; a traffic mix that drives more than the
    configuration's comparison follows (an eval inside the window) names a
    `compare` of its own and brings the `limits` of what that adds."""
    config, traffic = spec["config"], spec["traffic"]
    module = importlib.import_module(
        "benchmarks.compare." + traffic.get("compare", config["compare"]))
    return (module, {**config["limits"], **traffic.get("limits", {})},
            tuple(config.get("not_compared", ())))


def make_inputs(config: dict, seed32: int) -> tuple:
    """-> (reference model module, data, initial weights): all that a run
    makes from the seed, the weights in one jitted call on the device."""
    import jax

    from benchmarks.harness import data as bdata

    model = importlib.import_module(
        "benchmarks.reference." + config["reference"])
    data = bdata.make(config["data"], seed32)
    weights = jax.jit(lambda k: model.init(k, config["sizes"]))(
        jax.random.PRNGKey(seed32))
    return model, data, weights


def window_samples(counts, first: int, last: int, n_round: int, epochs: int,
                   sample_cohort) -> int:
    """Real (unpadded) rows x epochs trained by rounds [first, last)."""
    return epochs * sum(int(counts[sample_cohort(r, len(counts), n_round)]
                            .sum()) for r in range(first, last))


def drive_window(api, tracer) -> tuple:
    """The one `train()` call of a run: warm-up, then the window, ended by
    the tracer. -> (CompileLog, rounds that raised)."""
    from benchmarks.harness.window import CompileLog, WindowOver

    raised = 0
    with CompileLog() as compiles:
        try:
            api.train(tracer=tracer)
        except WindowOver:
            pass
        except Exception as e:  # a round that raised ends the window early
            if tracer.t_open is None:
                raise
            print(f"drive loop raised: {e!r}", file=sys.stderr)
            raised = 1
            tracer.t_close = tracer.now()
            tracer.last = tracer.first + len(tracer.window_spans("round"))
    return compiles, raised


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float | None = None) -> dict:
    """One run of one cell (`spec`: what `load_cell` gives) -> the result
    object of the module docstring, with the run's own record under `run`
    (main prints that on a line of its own). Where the configuration states a
    precision for float32 matrix products (`matmul_precision`), the program
    runs under it: it has no option of its own and takes JAX's default."""
    import jax

    precision = spec["config"].get("matmul_precision")
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        return _run_cell(spec, seed, seconds, trace, t_start)


def _run_cell(spec: dict, seed: int, seconds: float, trace: bool,
              t_start: float | None) -> dict:
    import jax

    from benchmarks.harness import correct, flops, readers
    from benchmarks.harness import trace as btrace
    from benchmarks.harness.window import WindowTracer, peak_bytes
    from benchmarks.reference.fedavg import sample_cohort

    t_start = T_START if t_start is None else t_start
    workload = spec["cell"]["name"]
    config, traffic, chips = (spec["config"], spec["traffic"],
                              spec["cell"]["chips"])
    seed32 = seed % (2 ** 32)       # numpy's and jax's seeds are 32-bit
    compare, limits, not_compared = comparison(spec)
    warm = traffic["warm_rounds"]
    if warm < config["reference_rounds"]:
        raise SystemExit("warm_rounds must cover the reference's rounds")

    # -- set-up: data and weights from the seed, the API as the CLI builds it
    model, data, weights = make_inputs(config, seed32)
    api, cfg = build_api(config, traffic, data, seed32)
    check_hyper(cfg, config["hyper"])
    w0 = api.global_variables = jax.tree.map(
        lambda theirs, ours: ours.astype(theirs.dtype).reshape(theirs.shape),
        api.global_variables, weights)
    capture = compare.Capture(api, config)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT_DIR, "trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    tracer = WindowTracer(warm, seconds, trace_dir,
                          traffic.get("trace_seconds", 3.0))
    tracer.api = api

    # -- warm-up and the window
    compiles, raised = drive_window(api, tracer)
    first, last = tracer.first, tracer.last
    counts = data["train"][2]
    bad_rows = sum(
        1 for rec in api.history if first <= rec["round"] < last and any(
            isinstance(v, float) and not math.isfinite(v)
            for v in rec.values()))
    device = dict(device_info(chips), memory_peak_bytes=peak_bytes())
    tpu = jax.devices()[0].platform == "tpu"
    ctx = {
        "tracer": tracer, "rounds": last - first, "chips": chips,
        # the profiler's own start and stop (device drained, no round
        # running) are taken out of a traced run's window
        "window_s": tracer.t_close - tracer.t_open - tracer.paused_s,
        "samples": window_samples(counts, first, last,
                                  cfg.client_num_per_round, cfg.epochs,
                                  sample_cohort),
        "rows_of_round": lambda r: window_samples(
            counts, r, r + 1, cfg.client_num_per_round, cfg.epochs,
            sample_cohort),
        "setup_s": tracer.t_open - t_start,
        "train_flops_per_sample": flops.train_flops_per_sample(
            model.layers(config["sizes"])),
        # a CPU (the tests) has no peak: its readers then find nothing
        "peaks": (readers.peaks_for(jax.devices()[0].device_kind)
                  if tpu else None),
        "dtype": cfg.dtype, "trace": None,
        "spec": spec, "cfg": cfg, "counts": counts,
    }
    breakdown = None
    if trace:
        ctx["trace"] = btrace.read(trace_dir, chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if ctx["trace"] is not None:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            breakdown = {"device_ops": ctx["trace"]["device_ops"],
                         "idle_gaps": ctx["trace"]["idle_gaps"]}
    metrics = {}
    group = "layer_metrics" if trace else "end_to_end"
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        value = readers.read_metric(group, m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # -- the comparison: after the window, the peak read, the program freed
    window_s, samples = ctx["window_s"], ctx["samples"]
    ms = sorted(s["dur_s"] * 1e3 for s in tracer.window_spans("round"))
    round_ms = {q: readers.percentile(ms, q) for q in (5, 50, 95, 100)}
    prog = capture.followed(w0)
    in_window = compiles.inside(tracer.t_open, tracer.t_close)
    capture.release()
    ctx.clear()
    del api, tracer, capture
    gc.collect()
    t_ref = time.perf_counter()
    ref = compare.reference(model, config, w0, data, seed32)
    nums = compare.numbers(prog, ref)
    nums["compiles_in_window"] = float(in_window)
    ok, compared = correct.verdict(nums, limits, not_compared)
    reference_s = time.perf_counter() - t_ref

    for name, row in compared.items():
        print(f"compared {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    result = {"correct": bool(ok), "attempted": last - first,
              "failed": bad_rows + raised, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["run"] = {
        "workload": workload, "seed": seed,
        "window_s": window_s, "rounds": [first, last],
        "samples": samples, "reference_s": reference_s,
        "compiles": len(compiles.compiles), "numbers": nums,
        "round_ms": round_ms, "leaves": correct.worst_leaves(prog, ref),
    }
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    spec = load_cell(args.workload)
    chips = spec["cell"]["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chip(s); found {len(devices)} x "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    configure_cache()
    from benchmarks.harness import readers

    readers.peaks_for(devices[0].device_kind)  # an unknown device: an error
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    print(json.dumps({"run": result.pop("run")}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
