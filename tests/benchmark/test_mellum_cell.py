"""`mellum2_lora` as the harness takes it, on the CPU at a tiny size
(tests/benchmark/cells/tiny_mellum2_lora.json under a manifest of its own):
through `run_cell` it reads `correct` true, and false under every control of
the probe (the band left out or halved and the rotary settings swapped or
without the factor, by their attention gaps; top-k less one by `pairs_gap`,
exactly; no renormalisation by the adapters' norms) and with the reference
in bfloat16; the cell's files resolve; the FLOPs the band and the experts
need; the three readers this configuration brings, on made-up ops."""

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
from benchmarks.probes import mellum_controls as controls  # noqa: E402
from benchmarks.reference import mellum as ref  # noqa: E402

TINY = os.path.join(ROOT, "tests", "benchmark", "cells",
                    "tiny_mellum2_lora.manifest.json")
CELL = "tiny_mellum2_lora.train"
NEW = ("swa.window_kernel_roofline", "swa.attention_round_share_pct",
       "swa.experts_roofline")
# the attention gap a control moves, or None
BY_ATTENTION = {"no_window": {"window_attention_gap"},
                "window_half": {"window_attention_gap"},
                "rotary_swapped": {"window_attention_gap",
                                   "full_attention_gap"},
                "no_attention_factor": {"full_attention_gap"},
                "top7": set(), "no_renorm": set()}


def one_run():
    return run.run_cell(run.load_cell(CELL, TINY), 2 ** 31 + 43, 0.3, False,
                        t_start=time.perf_counter())


def _over(r) -> set:
    return {k for k, v in r["compared"].items() if not v["value"] <= v["limit"]}


def test_the_tiny_cell_reads_correct_and_every_number_is_compared():
    spec = run.load_cell(CELL, TINY)
    r = one_run()
    assert r["correct"] is True, r["compared"]
    assert set(r["compared"]) == set(spec["config"]["limits"])
    assert r["compared"]["base_gap"]["value"] == 0.0
    assert r["compared"]["pairs_gap"]["value"] == 0.0
    # each kind of attention module against the reference's: the order of
    # the sums
    assert r["compared"]["window_attention_gap"]["value"] < 1e-5
    assert r["compared"]["full_attention_gap"]["value"] < 1e-5
    assert r["failed"] == 0 and r["attempted"] >= 1
    first, last = r["run"]["rounds"]
    assert r["run"]["samples"] == 8 * (last - first)   # 2 silos x 4 sequences


@pytest.mark.parametrize("control", controls.CONTROLS)
def test_a_piece_of_the_model_computed_another_way_reads_incorrect(control):
    with controls.broken(control):
        r = one_run()
    assert r["correct"] is False
    over = _over(r)
    assert "base_gap" not in over
    gaps = {"window_attention_gap", "full_attention_gap"}
    assert over & gaps == BY_ATTENTION[control]
    assert ("pairs_gap" in over) == (control == "top7")
    if control == "top7":   # one pair fewer a token in each of 2 layers
        assert r["compared"]["pairs_gap"]["value"] == 2 * 4 * 64 * 2
    if not BY_ATTENTION[control]:
        assert {"grad_gap", "change_gap"} & over


def test_the_reference_in_bfloat16_reads_incorrect():
    from benchmarks import calibrate

    r = calibrate.reference_in_place(run.load_cell(CELL, TINY), 2 ** 31 + 43,
                                     "bf16")
    assert r["correct"] is False
    assert {"window_attention_gap", "full_attention_gap"} <= _over(r)


def test_every_control_puts_back_what_it_changed():
    from fedml_tpu.models import mellum

    def now():
        return (mellum.flash_attention, mellum.layer_rotary,
                mellum.MellumConfig.__dict__["from_dict"])

    before = now()
    for control in controls.CONTROLS:
        with controls.broken(control):
            assert now() != before
    assert now() == before


def test_cell_configuration_reference_compare_and_metrics_resolve_to_files():
    spec = run.load_cell("mellum2_lora.train")
    config = spec["config"]
    assert spec["cell"]["chips"] == 1 and spec["traffic"]["warm_rounds"] == 4
    for group in ("reference", "compare"):
        importlib.import_module(f"benchmarks.{group}." + config[group])
    importlib.import_module("benchmarks.datasets." + config["data"]["kind"])
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) <= names and not {n for n in names
                                      if n.split(".")[0] in ("kda", "moe",
                                                             "ssm")}
    for name in names:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".json")), name
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 28}
    with open(os.path.join(ROOT, "fedml_tpu/models/configs/"
                                 "mellum2_12b_a2_5b.json")) as f:
        published = json.load(f)
    for key, value in published.items():      # every other key as published
        if key not in config["reduced"] and key != "source_url":
            assert config[key] == value, key
    assert config["source"].startswith(published["source_url"])
    assert config["data"]["vocab"] == config["vocab_size"] == 98304
    assert config["sizes"]["seq_len"] == config["data"]["seq_len"] == 8192
    for name in NEW:          # the new cell alone lists them
        [entry] = [m for m in spec["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["mellum2_lora.train"]


def test_the_band_and_the_experts_count_what_they_need():
    """Frozen matrices two passes (the experts on 8 pairs a token), the
    adapters and the attention cores three; a window layer's core over the
    band's 7,864,832 pairs a head, the full layer's over the causal ones."""
    with open(os.path.join(ROOT, "benchmarks/configs/mellum2_lora.json")) as f:
        config = json.load(f)
    sizes, t, r = config["sizes"], 8192, 16
    train = flops.train_flops_per_sample(ref.layers(sizes))
    kernels = list(ref._kernels(config).values())
    frozen = 4 * sum(a * b for a, b in kernels) + 2304 * 98304
    adapters = 4 * sum(r * (a + b) for a, b in kernels)
    experts = 4 * ref.expert_flops(t * 8, 2304, 896)
    cores = (3 * ref.attention_flops_band(t, 1024, 32, 128)
             + ref.attention_flops_band(t, None, 32, 128))
    want = (2 * 2 * frozen * t + 2 * experts + 3 * 2 * adapters * t
            + 3 * cores)
    assert train == pytest.approx(want, rel=1e-6)   # the thirds' rounding
    assert adapters == 1331200
    assert ref.band_pairs(t, 1024) == 7864832
    assert ref.band_pairs(t, None) == ref.band_pairs(t, t) == 33558528
    assert ref.attention_flops_band(t, 1024, 32, 128) == (
        7864832 * 32 * 2 * 2 * 128)
    assert ref.mixers(sizes) == {"window": 3, "full": 1}
    assert ref.routed_pairs(sizes, 2) == 2 * t * 8 * 4


def _ctx(ops):
    tracer = types.SimpleNamespace(
        first=4, last=8, trace_rounds=(5, 7),
        find_events=lambda kind: [],
        window_spans=lambda name: [{"round": 5, "slots": 4},
                                   {"round": 6, "slots": 4}],
        api=types.SimpleNamespace(program_scopes=lambda: {}))
    return {"tracer": tracer, "dtype": "bfloat16",
            "peaks": {"flops_per_s": {"bfloat16": 100e12},
                      "hbm_bytes_per_s": 800e9},
            "trace": ({"ops": ops, "modules": [["jit_round_fn", 2, 8.0]]}
                      if ops is not None else None),
            "spec": run.load_cell("mellum2_lora.train")}


def test_readers_find_their_kernels_by_name_and_nothing_without_them():
    ops = [["flash_window_fwd.3 bf16[2,32,8192,128]", 0.25, 12],
           ["flash_window_dq.1 bf16[2,32,8192,128]", 0.5, 12],
           ["flash_window_dkv.2 bf16[2,32,8192,128]", 0.25, 12],
           ["flash_attention_fwd.7 bf16[2,32,8192,128]", 1.0, 4],
           ["moe_grouped_matmul.4 bf16[131072,896]", 2.0, 96],
           ["fusion.13 f32[2,8192,2304]", 3.0, 9]]
    read = lambda name, o=ops: readers.read_metric(  # noqa: E731
        "layer_metrics", name, _ctx(o))
    band = 3 * 3 * 8 * ref.attention_flops_band(8192, 1024, 32, 128)
    assert read("swa.window_kernel_roofline") == pytest.approx(
        100 * band / 1.0 / 100e12)
    assert read("swa.attention_round_share_pct") == pytest.approx(
        100 * 2.0 / 8.0)
    experts = 2 * ref.expert_flops(8 * 8192 * 8 * 4, 2304, 896)
    assert read("swa.experts_roofline") == pytest.approx(
        100 * experts / 2.0 / 100e12)
    bare = [["fusion.13 f32[2,8192,2304]", 3.0, 9]]
    for name in NEW:
        assert read(name, bare) is None, name
        assert read(name, None) is None, name
    # the causal kernel alone (a program without the band): no band roofline
    causal = [ops[3], ops[5]]
    assert read("swa.window_kernel_roofline", causal) is None
    assert read("swa.attention_round_share_pct", causal) == pytest.approx(
        100 * 1.0 / 8.0)
