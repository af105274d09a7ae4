"""`kimi_linear_lora` as the harness takes it, on the CPU at a tiny size
(tests/benchmark/cells/tiny_kimi_linear_lora.json under a manifest of its
own): through `run_cell` it reads `correct` true, and false with KDA's decay
left out, softmax scores in sigmoid's place and top-1 in top-2's place, with
KDA's state kept in bfloat16 (by `kda_core_gap`) and with rotary applied in
MLA (by `mla_gap`); the cell's files resolve; the FLOPs a frozen base and the KDA core need; the five
readers this configuration brings, on made-up spans, events and device ops."""

import importlib
import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import flops, readers  # noqa: E402
from benchmarks.probes import kimi_linear_controls as controls  # noqa: E402
from benchmarks.reference import kimi_linear as ref  # noqa: E402

TINY = os.path.join(ROOT, "tests", "benchmark", "cells",
                    "tiny_kimi_linear_lora.manifest.json")
CELL = "tiny_kimi_linear_lora.train"
NEW = ("kda.kernel_roofline", "kda.round_share_pct",
       "moe.held_experts_roofline", "moe.held_load_imbalance",
       "attention.mla_kernel_roofline")


def one_run():
    return run.run_cell(run.load_cell(CELL, TINY), 2 ** 31 + 38, 0.3, False,
                        t_start=time.perf_counter())


def test_the_tiny_cell_reads_correct_and_every_number_is_compared():
    spec = run.load_cell(CELL, TINY)
    r = one_run()
    assert r["correct"] is True, r["compared"]
    assert set(r["compared"]) == set(spec["config"]["limits"])
    assert r["compared"]["base_gap"]["value"] == 0.0
    assert r["compared"]["pairs_gap"]["value"] == 0.0
    # one layer against the reference array by array: the order of the sums
    assert r["compared"]["kda_core_gap"]["value"] < 1e-5
    assert r["compared"]["mla_gap"]["value"] < 1e-5
    assert r["failed"] == 0 and r["attempted"] >= 1
    first, last = r["run"]["rounds"]
    assert r["run"]["samples"] == 16 * (last - first)   # 2 silos x 8 sequences


@pytest.mark.parametrize("control", ["no_decay", "softmax", "top7"])
def test_a_piece_of_the_model_computed_another_way_reads_incorrect(control):
    """`top7` is one expert a token fewer than the configuration says: at
    the tiny size top-1 in top-2's place, which the pairs count shows."""
    with controls.broken(control):
        r = one_run()
    assert r["correct"] is False
    over = _over(r)
    assert {"grad_gap", "change_gap"} & over and "base_gap" not in over
    assert "kda_core_gap" not in over     # none of the three is the core's
    # 2 silos x 8 sequences x 32 tokens x 2 expert layers, a pair a token
    assert r["compared"]["pairs_gap"]["value"] == (
        16 * 32 * 2 if control == "top7" else 0)


def _over(r) -> set:
    return {k for k, v in r["compared"].items() if not v["value"] <= v["limit"]}


def test_rotary_in_mla_reads_incorrect_by_the_layer_held_array_by_array():
    with controls.broken("rotary"):
        r = one_run()
    assert r["correct"] is False and "mla_gap" in _over(r)
    assert r["compared"]["mla_gap"]["value"] > 0.05
    assert "kda_core_gap" not in _over(r)


def test_kda_state_in_bfloat16_reads_incorrect_by_the_core(monkeypatch):
    """What a later change that keeps the state in bfloat16 would be: the
    model's `kda` rounds its decay and state (here the reference's token
    recurrence in that precision, in the kernel's place)."""
    from fedml_tpu.models import kimi_linear

    monkeypatch.setattr(
        kimi_linear, "kda", lambda q, k, v, g, beta: ref.kda_core(
            q, k, v, g, beta, "bf16").astype(v.dtype))
    r = one_run()
    assert r["correct"] is False and "kda_core_gap" in _over(r)
    assert r["compared"]["kda_core_gap"]["value"] > 5e-3
    assert "mla_gap" not in _over(r)


def test_the_reference_in_bfloat16_reads_incorrect_by_the_core_too():
    """`calibrate.py --control ref:bf16`, the control PERF.md section 2 sets
    `kda_core_gap`'s limit from."""
    from benchmarks import calibrate

    r = calibrate.reference_in_place(run.load_cell(CELL, TINY), 2 ** 31 + 38,
                                     "bf16")
    assert r["correct"] is False
    assert r["compared"]["kda_core_gap"]["value"] > 5e-3


def test_every_control_puts_back_what_it_changed():
    from fedml_tpu.models import deepseek_v2, kimi_linear
    from fedml_tpu.ops import moe

    before = (kimi_linear.log_decay, kimi_linear.short_conv, moe.top_k_route,
              deepseek_v2.biased_route, kimi_linear.KimiLinearConfig.rotary)
    for control in controls.CONTROLS:
        with controls.broken(control):
            pass
    assert before == (kimi_linear.log_decay, kimi_linear.short_conv,
                      moe.top_k_route, deepseek_v2.biased_route,
                      kimi_linear.KimiLinearConfig.rotary)
    cfg = kimi_linear.KimiLinearConfig.from_file(None)
    assert (cfg.scoring_func, cfg.norm_topk_prob) == ("sigmoid", True)
    # the rope values are the control's own: the class has none (NoPE)
    assert not hasattr(cfg, "rope_factor") and cfg.rotary is False


def test_cell_configuration_reference_compare_and_metrics_resolve_to_files():
    spec = run.load_cell("kimi_linear_lora.train")
    config = spec["config"]
    assert spec["cell"]["chips"] == 1 and spec["traffic"]["warm_rounds"] == 4
    for group in ("reference", "compare"):
        importlib.import_module(f"benchmarks.{group}." + config[group])
    importlib.import_module("benchmarks.datasets." + config["data"]["kind"])
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) <= names and "moe.experts_roofline" not in names
    for name in names:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".json")), name
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 27,
                                   "num_experts": 256, "vocab_size": 163840}
    with open(os.path.join(ROOT, "fedml_tpu/models/configs/"
                                 "kimi_linear_48b_a3b.json")) as f:
        published = json.load(f)
    for key, value in published.items():      # every other key as published
        if key not in config["reduced"] and key != "source_url":
            assert config[key] == value, key
    assert config["data"]["vocab"] == config["vocab_size"] == 40960


def test_a_frozen_matrix_counts_two_passes_the_kda_core_three():
    with open(os.path.join(ROOT, "benchmarks/configs/kimi_linear_lora.json")) as f:
        config = json.load(f)
    sizes, t, r = config["sizes"], 4096, 16
    train = flops.train_flops_per_sample(ref.layers(sizes))
    kernels = [k for i in range(5) for k in ref._kernels(config, i).values()]
    frozen = sum(a * b for a, b in kernels) + 2304 * 40960
    routed = 4 * (8 * 64 // 256) * 3 * 2304 * 1024   # the expected held pairs
    adapters = sum(r * (a + b) for a, b in kernels)
    kda = 4 * ref.kda_flops(t, 32, 128, 128)
    mla = ref.attention_flops(t, 32, 192, 128)
    want = (2 * 2 * (frozen + routed) * t + 3 * 2 * adapters * t
            + 3 * (kda + mla))
    assert train == pytest.approx(want, rel=1e-6)   # the thirds' rounding
    assert adapters == 4418560
    assert ref.kda_flops(t, 32, 128, 128) == 7 * 128 * 128 * 32 * t
    assert ref.mixers(sizes) == {"kda": 4, "mla": 1}
    assert ref.routed_pairs(sizes, 16) == 16 * t * 8 * 4


def _ctx(ops, events, slots=(4, 4)):
    tracer = types.SimpleNamespace(
        first=4, last=8, trace_rounds=(5, 7),
        find_events=lambda kind: [e for e in events if e["kind"] == kind],
        window_spans=lambda name: [
            {"round": 5 + i, "slots": s} for i, s in enumerate(slots)])
    return {"tracer": tracer, "dtype": "bfloat16",
            "peaks": {"flops_per_s": {"bfloat16": 100e12}},
            "trace": ({"ops": ops, "modules": [["jit_round_fn", 2, 8.0]]}
                      if ops is not None else None),
            "spec": run.load_cell("kimi_linear_lora.train")}


def test_readers_find_their_kernels_by_name_and_nothing_without_them():
    ops = [["kda_fwd.3 bf16[2,2,4096,4096]", 1.0, 16],
           ["kda_bwd.1 bf16[2,2,4096,4096]", 3.0, 8],
           ["moe_grouped_matmul.7 bf16[139264,1024]", 0.5, 20],
           ["flash_attention_fwd.3 bf16[2,64,4096,128]", 0.25, 4],
           ["flash_attention_dkv.1 bf16[2,64,4096,192]", 0.5, 2],
           ["flash_attention_dq.2 bf16[2,64,4096,192]", 0.25, 2],
           ["fusion.12 f32[2,16]", 3.0, 9]]
    events = [{"kind": "moe_load", "round": r, "max": 900.0, "mean": 512.0,
               "empty": 0, "held": h, "held_max": m, "held_mean": 500.0,
               "held_empty": 0}
              for r, h, m in ((3, 9e9, 9e9), (5, 3e5, 600.0), (6, 5e5, 700.0))]
    ctx = _ctx(ops, events)
    read = lambda name: readers.read_metric("layer_metrics", name, ctx)  # noqa: E731
    assert read("kda.kernel_roofline") == pytest.approx(
        100 * 3 * 4 * 8 * ref.kda_flops(4096, 32, 128, 128) / 4.0 / 100e12)
    assert read("kda.round_share_pct") == pytest.approx(50.0)
    assert read("moe.held_experts_roofline") == pytest.approx(
        100 * 8e5 * 3 * 2 * 2304 * 1024 * 2 / 0.5 / 100e12)
    assert read("moe.held_load_imbalance") == pytest.approx((1.2 + 1.4) / 2)
    assert read("attention.mla_kernel_roofline") == pytest.approx(
        100 * 3 * 1 * 8 * ref.attention_flops(4096, 32, 192, 128) / 1.0 / 100e12)
    # a program without the kernels, or whose events say nothing of a share
    bare = _ctx([["fusion.12 f32[2,16]", 3.0, 9]],
                [{"kind": "moe_load", "round": 5, "max": 9.0, "mean": 3.0,
                  "empty": 0}])
    for name in NEW:
        assert readers.read_metric("layer_metrics", name, bare) is None, name
        assert readers.read_metric("layer_metrics", name,
                                   _ctx(None, [])) is None, name
