"""`jamba2_lora` as the harness takes it, on the CPU at a tiny size
(tests/benchmark/cells/tiny_jamba2_lora.json under a manifest of its own):
through `run_cell` it reads `correct` true, and false with the scan's state
reset at every chunk boundary (by `ssm_core_gap`), with rotary applied in the
attention layer (by `attention_gap`), with the state kept in bfloat16 and
with a frozen base that moved; the other controls of the probe each read
false; the cell's files resolve; the FLOPs and bytes the scan needs;
the three readers this configuration brings, on made-up ops."""

import importlib
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
from benchmarks.probes import jamba_controls as controls  # noqa: E402
from benchmarks.reference import jamba as ref  # noqa: E402

TINY = os.path.join(ROOT, "tests", "benchmark", "cells",
                    "tiny_jamba2_lora.manifest.json")
CELL = "tiny_jamba2_lora.train"
NEW = ("ssm.scan_roofline", "ssm.round_share_pct", "ssm.mixer_xla_share_pct")


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
    # the scan and the attention layer against the reference, array by
    # array: the order of the sums
    assert r["compared"]["ssm_core_gap"]["value"] < 1e-5
    assert r["compared"]["attention_gap"]["value"] < 1e-5
    assert r["failed"] == 0 and r["attempted"] >= 1
    first, last = r["run"]["rounds"]
    assert r["run"]["samples"] == 8 * (last - first)   # 2 silos x 4 sequences


@pytest.mark.parametrize("control", controls.CONTROLS)
def test_a_piece_of_the_model_computed_another_way_reads_incorrect(
        monkeypatch, control):
    """The state reset every 16 tokens here (the tiny sequence is one
    chunk of the kernel's); it reads by the scan, rotary by the attention
    layer, the rest by the adapters' norms, never by the base."""
    from fedml_tpu.ops import ssm

    monkeypatch.setattr(ssm, "CHUNK", 16)
    with controls.broken(control):
        r = one_run()
    assert r["correct"] is False
    over = _over(r)
    assert "base_gap" not in over
    assert ("ssm_core_gap" in over) == (control == "state_reset")
    assert ("attention_gap" in over) == (control == "rotary")
    if control == "state_reset":
        assert r["compared"]["ssm_core_gap"]["value"] > 1e-2
    elif control == "rotary":
        assert r["compared"]["attention_gap"]["value"] > 5e-2
    else:
        assert {"grad_gap", "change_gap"} & over


def test_the_reference_in_bfloat16_reads_incorrect_by_the_scan():
    """`calibrate.py --control ref:bf16`: the scan's state rounded."""
    from benchmarks import calibrate

    r = calibrate.reference_in_place(run.load_cell(CELL, TINY), 2 ** 31 + 43,
                                     "bf16")
    assert r["correct"] is False
    assert r["compared"]["ssm_core_gap"]["value"] > 1e-3


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
    assert r["correct"] is False and "base_gap" in _over(r)
    # 1 -> 1.5 -> 2.25 over the two rounds followed
    assert r["compared"]["base_gap"]["value"] == pytest.approx(1.25)


def test_every_control_puts_back_what_it_changed():
    from fedml_tpu.models import jamba

    names = ("selective_scan", "inner_norms", "skip_and_gate", "short_conv",
             "flash_attention")
    before = [getattr(jamba, n) for n in names]
    for control in controls.CONTROLS:
        with controls.broken(control):
            assert [getattr(jamba, n) for n in names] != before
    assert [getattr(jamba, n) for n in names] == before


def test_cell_configuration_reference_compare_and_metrics_resolve_to_files():
    spec = run.load_cell("jamba2_lora.train")
    config = spec["config"]
    assert spec["cell"]["chips"] == 1 and spec["traffic"]["warm_rounds"] == 4
    for group in ("reference", "compare"):
        importlib.import_module(f"benchmarks.{group}." + config[group])
    importlib.import_module("benchmarks.datasets." + config["data"]["kind"])
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) <= names and not {n for n in names
                                      if n.split(".")[0] in ("kda", "moe")}
    for name in names:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".json")), name
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 28}
    with open(os.path.join(ROOT, "fedml_tpu/models/configs/"
                                 "ai21_jamba2_3b.json")) as f:
        published = json.load(f)
    for key, value in published.items():      # every other key as published
        if key not in config["reduced"] and key != "source_url":
            assert config[key] == value, key
    assert config["source"].startswith(published["source_url"])
    assert config["data"]["vocab"] == config["vocab_size"] == 65536
    for name in NEW:          # the new cell alone lists them
        [entry] = [m for m in spec["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["jamba2_lora.train"]


def test_a_frozen_matrix_counts_two_passes_the_scan_three():
    with open(os.path.join(ROOT, "benchmarks/configs/jamba2_lora.json")) as f:
        config = json.load(f)
    sizes, t, r = config["sizes"], 8192, 16
    train = flops.train_flops_per_sample(ref.layers(sizes))
    kernels = [k for i in range(14) for k in ref._kernels(config, i).values()]
    frozen = sum(a * b for a, b in kernels) + 2560 * 65536
    adapters = sum(r * (a + b) for a, b in kernels)
    scan = 13 * ref.ssm_flops(t, 5120, 16)
    attention = ref.attention_flops(t, 20, 128, 128)
    want = 2 * 2 * frozen * t + 3 * 2 * adapters * t + 3 * (scan + attention)
    assert train == pytest.approx(want, rel=1e-6)   # the thirds' rounding
    assert adapters == 13938176
    assert ref.ssm_flops(t, 5120, 16) == 6 * 5120 * 16 * t
    assert ref.mixers(sizes) == {"mamba": 13, "attention": 1}
    # forward: x bf16, delta and y f32, B and C f32; backward twice x's
    # width in and out beside delta, dy and ddelta
    assert ref.scan_bytes(t, 5120, 16, 2) == (
        t * 5120 * (2 + 4 + 4) + 2 * t * 16 * 4
        + t * 5120 * (2 + 4 + 4 + 2 + 4) + 4 * t * 16 * 4)


def _ctx(ops, scopes=None):
    tracer = types.SimpleNamespace(
        first=4, last=8, trace_rounds=(5, 7),
        find_events=lambda kind: [],
        window_spans=lambda name: [{"round": 5, "slots": 4},
                                   {"round": 6, "slots": 4}],
        api=types.SimpleNamespace(program_scopes=lambda: scopes or {}))
    return {"tracer": tracer, "dtype": "bfloat16",
            "peaks": {"flops_per_s": {"bfloat16": 100e12},
                      "hbm_bytes_per_s": 800e9},
            "trace": ({"ops": ops, "modules": [["jit_round_fn", 2, 8.0]]}
                      if ops is not None else None),
            "spec": run.load_cell("jamba2_lora.train")}


def test_readers_find_their_kernels_by_name_and_nothing_without_them():
    ops = [["ssm_scan_fwd.3 f32[2,8192,5120]", 1.0, 26],
           ["ssm_scan_bwd.1 bf16[2,8192,5120]", 3.0, 13],
           ["fusion.12 bf16[2,8192,10240]", 0.5, 9],
           ["fusion.13 f32[2,8192,5120]", 0.25, 9],
           ["flash_attention_fwd.3 bf16[2,20,8192,128]", 0.25, 4]]
    scopes = {"fusion.12": "jit(round_fn)/layers_0/mamba/ssm_conv/add",
              "fusion.13": "jit(round_fn)/layers_7/attn/q_proj/dot_general"}
    ctx = _ctx(ops, scopes)
    read = lambda name: readers.read_metric("layer_metrics", name, ctx)  # noqa: E731
    assert read("ssm.scan_roofline") == pytest.approx(
        100 * 8 * 13 * ref.scan_bytes(8192, 5120, 16, 2) / 4.0 / 800e9)
    assert read("ssm.round_share_pct") == pytest.approx(50.0)
    assert read("ssm.mixer_xla_share_pct") == pytest.approx(100 * 0.5 / 8.0)
    bare = _ctx([["fusion.13 f32[2,8192,5120]", 3.0, 9]], scopes)
    for name in NEW[:2]:
        assert readers.read_metric("layer_metrics", name, bare) is None, name
    for name in NEW:
        assert readers.read_metric("layer_metrics", name,
                                   _ctx(None)) is None, name
