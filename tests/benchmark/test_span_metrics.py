"""The per-layer metrics that read the program's span ids, parents and work
counts, on the CPU at a tiny size: each reader against a count made by hand
from the run's own spans and data, and the identity that closes the drive
loop. The tiny cells run through a manifest the test writes into its tmp
directory: the tiny manifest's configurations, cells and traffic with the
per-layer metrics of the repo's BENCHMARK.json as it stands, so that no copy
has to be kept equal. The run is what a traced run is, minus the profiler:
the window is shorter than the profiler's start."""

import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import data as bdata  # noqa: E402
from benchmarks.harness import readers  # noqa: E402
from benchmarks.reference.fedavg import sample_cohort  # noqa: E402

CELLS = os.path.join(ROOT, "tests", "benchmark", "cells")
NEW = ("drive.metrics_fetch_ms", "drive.self_ms", "round_program.padding_pct",
       "staging.h2d_mb", "setup.compile_s", "setup.eval_s")
DRIVE = ("drive.stage_wait_ms", "drive.dispatch_ms", "drive.device_wait_ms",
         "drive.metrics_fetch_ms", "drive.self_ms")


def _repo_per_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)["per_layer"]


@pytest.fixture
def manifest(tmp_path):
    """The tiny cells under the repo's per-layer metrics, with the tiny
    traffic beside the manifest, where `run.load_cell` looks for it."""
    with open(os.path.join(CELLS, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] = _repo_per_layer()
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    shutil.copytree(os.path.join(CELLS, "traffic"), tmp_path / "traffic")
    return str(path)


def traced(workload, manifest, monkeypatch, seed=2 ** 31 + 5):
    """One `--trace 1` run of a tiny cell -> (result, its tracer, spec)."""
    spec = run.load_cell(workload, manifest)
    seen = {}
    read_metric = readers.read_metric

    def keep(group, name, ctx):
        seen["tracer"] = ctx["tracer"]
        return read_metric(group, name, ctx)

    monkeypatch.setattr(readers, "read_metric", keep)
    result = run.run_cell(spec, seed, 0.3, True,
                          t_start=time.perf_counter())
    return result, seen["tracer"], spec


def test_each_new_metric_is_declared_as_its_file_says():
    by_name = {m["name"]: m for m in _repo_per_layer()}
    for name in NEW:
        m = by_name[name]
        assert m["source"] == "program_span" and "workloads" not in m
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert {k: spec[k] for k in ("layer", "unit", "better", "moves")} == {
            k: m[k] for k in ("layer", "unit", "better", "moves")}


@pytest.mark.parametrize("workload", [
    "tiny_flagship.train",
    # ResNet-56 on a CPU: 25 minutes for this one run (compiles, three
    # rounds of two silos, the reference); it passed so on 30 Sep 2026
    pytest.param("tiny_cross_silo.train", marks=pytest.mark.slow)])
def test_readers_against_hand_counts_and_the_drive_loop_closes(
        workload, manifest, monkeypatch):
    result, tracer, spec = traced(workload, manifest, monkeypatch)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(got) and all(np.isfinite(got[k]) for k in NEW)
    assert result["correct"] is True, result["compared"]
    first, last = result["run"]["rounds"]
    rounds = last - first

    def window(name):
        return tracer.window_spans(name)

    # -- drive loop: five parts, one round
    mean_round = sum(s["dur_s"] for s in window("round")) / rounds * 1e3
    assert sum(got[k] for k in DRIVE) == pytest.approx(mean_round, rel=0.01)
    assert got["drive.metrics_fetch_ms"] == pytest.approx(
        sum(s["dur_s"] for s in window("metrics_fetch")) / rounds * 1e3)
    by_id = {s["id"]: s for s in tracer.spans}
    assert all(by_id[s["parent"]]["name"] == "round"
               and by_id[s["parent"]]["round"] == s["round"]
               for name in ("stage_wait", "dispatch", "device_wait",
                            "metrics_fetch") for s in window(name))
    # a child the round does not own breaks the identity: it is then counted
    # in its own metric and in the round's self time
    orphan = window("dispatch")[0]
    orphan["parent"] = None
    ctx = {"tracer": tracer, "rounds": rounds}
    assert readers.read_metric("layer_metrics", "drive.self_ms", ctx) > (
        got["drive.self_ms"])
    orphan["parent"] = window("round")[0]["id"]

    # -- padding: the program's count against the federation's own sizes
    config, hyper = spec["config"], spec["config"]["hyper"]
    data = bdata.make(config["data"], (2 ** 31 + 5) % 2 ** 32)
    x, y, counts = data["train"]
    cohort, bs, n_max = hyper["client_num_per_round"], hyper["batch_size"], (
        x.shape[1])
    slots = cohort * -(-n_max // min(bs, n_max)) * min(bs, n_max)
    rows = sum(int(counts[sample_cohort(r, len(counts), cohort)].sum())
               for r in range(first, last))
    assert rows == result["run"]["samples"]   # epochs is 1 in both cells
    assert got["round_program.padding_pct"] == pytest.approx(
        100.0 * (1.0 - rows / (rounds * slots)))

    # -- staging: every cohort is the same bytes
    per_cohort = (x[:cohort].nbytes + y[:cohort].nbytes
                  + counts[:cohort].nbytes)
    h2d = window("h2d")
    assert h2d and all(s["bytes"] == per_cohort for s in h2d)
    assert got["staging.h2d_mb"] == pytest.approx(per_cohort / 1e6)

    # -- set-up: what the warm rounds compiled and evaluated
    compiles = [e for e in tracer.find_events("compile")
                if e["t"] < tracer.t_open]
    assert compiles and all(e["round"] is not None and e["round"] < first
                            for e in compiles)
    assert got["setup.compile_s"] == pytest.approx(
        sum(e["dur_s"] for e in compiles))
    ev, = [s for s in tracer.find_spans("eval") if s["round"] < first]
    assert ev["round"] == 0 and got["setup.eval_s"] == ev["dur_s"]
    sent, = tracer.find_spans("eval_h2d")
    assert sent["parent"] == ev["id"] and sent["dur_s"] <= ev["dur_s"]


def test_readers_find_nothing_in_a_program_without_ids_and_counts(
        manifest, monkeypatch):
    """What the parent commit's tracer records: no id, parent, rows, slots or
    bytes and no compile event. The readers then leave the metric out."""
    result, tracer, _ = traced("tiny_flagship.train", manifest, monkeypatch)
    for s in tracer.spans:
        for key in ("id", "parent", "rows", "slots", "bytes"):
            s.pop(key, None)
    tracer.events[:] = [e for e in tracer.events if e["kind"] != "compile"]
    first, last = result["run"]["rounds"]
    ctx = {"tracer": tracer, "rounds": last - first}
    for name in ("drive.self_ms", "round_program.padding_pct",
                 "staging.h2d_mb", "setup.compile_s"):
        assert readers.read_metric("layer_metrics", name, ctx) is None
    # these two read spans the parent already had
    assert readers.read_metric("layer_metrics", "setup.eval_s", ctx) > 0
    assert readers.read_metric("layer_metrics", "drive.metrics_fetch_ms",
                               ctx) is not None
