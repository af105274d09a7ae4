"""`dsv2lite_lora` as the harness takes it, on the CPU at a tiny size
(tests/benchmark/cells/tiny_dsv2lite_lora.json, under a manifest of its own
beside the accepted tiny manifest, which is not edited): through `run_cell` it
reads `correct` true, and false with top-1 in top-2's place, with the
softmax scale without m^2 and with a changed base; the FLOPs a frozen base needs; the three readers this
configuration brings, on made-up spans, events and device ops."""

import json
import os
import sys
import time
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import flops, readers  # noqa: E402
from benchmarks.probes import dsv2lite_controls as controls  # noqa: E402
from benchmarks.reference import deepseek_v2_lite as ref  # noqa: E402

TINY = os.path.join(ROOT, "tests", "benchmark", "cells",
                    "tiny_dsv2lite_lora.manifest.json")
CELL = "tiny_dsv2lite_lora.train"


def one_run():
    return run.run_cell(run.load_cell(CELL, TINY), 2 ** 31 + 36, 0.3, False,
                        t_start=time.perf_counter())


def test_the_tiny_cell_reads_correct_and_every_number_is_compared():
    spec = run.load_cell(CELL, TINY)
    r = one_run()
    assert r["correct"] is True, r["compared"]
    assert set(r["compared"]) == set(spec["config"]["limits"])
    assert r["compared"]["base_gap"]["value"] == 0.0
    assert r["failed"] == 0 and r["attempted"] >= 1
    first, last = r["run"]["rounds"]
    assert r["run"]["samples"] == 16 * (last - first)   # 2 silos x 8 sequences


@pytest.mark.parametrize("control", ["top5", "no_mscale"])
def test_a_piece_of_the_model_computed_another_way_reads_incorrect(control):
    """`top5` is one expert a token fewer than the configuration says: at
    the tiny size top-1 in top-2's place."""
    with controls.broken(control):
        r = one_run()
    assert r["correct"] is False
    over = {k for k, v in r["compared"].items() if not v["value"] <= v["limit"]}
    assert {"grad_gap", "change_gap"} & over and "base_gap" not in over
    # 2 silos x 8 sequences x 32 tokens x 2 expert layers: one pair a token
    # where the configuration says two; another scale routes the same pairs
    assert r["compared"]["pairs_gap"]["value"] == (
        16 * 32 * 2 if control == "top5" else 0)


def test_a_base_that_moved_reads_incorrect_by_base_gap():
    build = run.build_api

    def moved(*args):
        api, cfg = build(*args)
        inner = api.round_fn

        def round_fn(gv, *rest):
            new, *others = inner(gv, *rest)
            base = jax.tree.map(lambda a: a, new["lora_base"])
            norm = base["final_norm"]
            base["final_norm"] = {"scale": norm["scale"] * 1.5}
            return ({**new, "lora_base": base}, *others)

        api.round_fn = round_fn
        return api, cfg

    run.build_api = moved
    try:
        r = one_run()
    finally:
        run.build_api = build
    over = {k for k, v in r["compared"].items() if not v["value"] <= v["limit"]}
    assert r["correct"] is False and "base_gap" in over
    # 1 -> 1.5 -> 2.25 over the two rounds followed
    assert r["compared"]["base_gap"]["value"] == pytest.approx(1.25)


def test_a_frozen_matrix_counts_two_passes_adapters_and_attention_three():
    with open(os.path.join(ROOT, "benchmarks/configs/dsv2lite_lora.json")) as f:
        config = json.load(f)
    sizes, t, r = config["sizes"], 1024, 16
    layers = ref.layers(sizes)
    train = flops.train_flops_per_sample(layers)
    kernels = [k for i in range(5) for k in ref._kernels(config, i).values()]
    frozen = sum(a * b for a, b in kernels) + 2048 * 102400
    routed = 4 * 6 * 3 * 2048 * 1408
    adapters = sum(r * (a + b) for a, b in kernels)
    attention = 5 * ref.attention_flops(t, 16, 192, 128)
    want = (2 * 2 * (frozen + routed) * t + 3 * 2 * adapters * t
            + 3 * attention)
    assert train == pytest.approx(want, rel=1e-6)   # the thirds' rounding
    assert adapters == 3008512 and train / t == pytest.approx(2.59e9, rel=5e-3)
    assert 3 * attention / train == pytest.approx(0.0304, abs=0.001)
    assert ref.attention_flops(t, 16, 192, 128) == 2 * 16 * 320 * t * (t + 1) // 2


def _ctx(ops, events, slots=(32, 32)):
    tracer = types.SimpleNamespace(
        first=4, last=8, trace_rounds=(5, 7), _events=events,
        find_events=lambda kind: [e for e in events if e["kind"] == kind],
        window_spans=lambda name: [
            {"round": 5 + i, "slots": s} for i, s in enumerate(slots)])
    return {"tracer": tracer, "dtype": "bfloat16",
            "peaks": {"flops_per_s": {"bfloat16": 100e12}},
            "trace": {"ops": ops} if ops is not None else None,
            "spec": run.load_cell("dsv2lite_lora.train")}


def test_readers_find_their_kernels_by_name_and_nothing_without_them():
    ops = [["moe_grouped_matmul.7 bf16[57344,1408]", 1.5, 40],
           ["moe_grouped_matmul.9 bf16[57344,2048]", 0.5, 20],
           ["flash_attention_fwd.3 bf16[2,64,1024,128]", 0.25, 10],
           ["flash_attention_dkv.1 bf16[2,64,1024,192]", 0.5, 5],
           ["flash_attention_dq.2 bf16[2,64,1024,192]", 0.25, 5],
           ["fusion.12 f32[2,16]", 3.0, 9]]
    events = [{"kind": "moe_load", "round": r, "max": m, "mean": 384.0,
               "empty": 0} for r, m in ((3, 9e9), (4, 480.0), (6, 576.0))]
    ctx = _ctx(ops, events)
    assert readers.read_metric("layer_metrics", "moe.load_imbalance",
                               ctx) == pytest.approx((1.25 + 1.5) / 2)
    pairs = 64 * 1024 * 6 * 4
    assert readers.read_metric(
        "layer_metrics", "moe.experts_roofline", ctx) == pytest.approx(
            100 * pairs * 3 * 2 * 2048 * 1408 * 2 / 2.0 / 100e12)
    assert readers.read_metric(
        "layer_metrics", "attention.kernel_roofline", ctx) == pytest.approx(
            100 * 3 * 5 * 64 * ref.attention_flops(1024, 16, 192, 128)
            / 1.0 / 100e12)
    # a program without the kernels or the event (the parent), an untraced
    # run, a CPU: nothing to read, and no metric on the line
    bare = _ctx([["fusion.12 f32[2,16]", 3.0, 9]], [])
    for name in ("moe.load_imbalance", "moe.experts_roofline",
                 "attention.kernel_roofline"):
        assert readers.read_metric("layer_metrics", name, bare) is None
    assert readers.read_metric("layer_metrics", "moe.experts_roofline",
                               _ctx(None, events)) is None
    assert readers.read_metric("layer_metrics", "moe.experts_roofline",
                               dict(ctx, peaks=None)) is None


def test_the_cell_and_its_metrics_resolve_to_files():
    spec = run.load_cell("dsv2lite_lora.train")
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == "train"
    names = {m["name"] for m in spec["per_layer"]}
    assert {"moe.load_imbalance", "moe.experts_roofline",
            "attention.kernel_roofline"} <= names
    assert {m["name"] for m in spec["end_to_end"]} == {
        "setup_s", "train_samples_per_s_chip"}
    with open(os.path.join(ROOT, "fedml_tpu/models/configs/"
                                 "deepseek_v2_lite.json")) as f:
        published = json.load(f)
    config = spec["config"]
    differs = {k for k, v in published.items()
               if k not in ("name", "source_url", "paper") and config[k] != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    assert config["source_url"] == published["source_url"]
    run.check_hyper(types.SimpleNamespace(**config["hyper"]), config["hyper"])
