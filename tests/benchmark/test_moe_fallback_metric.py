"""`moe.held_fallback_pct` (PR 39) through the harness's `read_metric`, on
made-up `moe_load` events: the share of a share's routed-expert calls that
took the worst-case path over the window's rounds; nothing where no event
carries `bounded` / `fallback` (a model that holds every expert, a program
from before the fields), nor over an empty window; listed by the cell that
holds a share and by no other."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import readers  # noqa: E402

NAME = "moe.held_fallback_pct"


def _read(events, first=4, last=8):
    tracer = types.SimpleNamespace(
        first=first, last=last,
        find_events=lambda kind: [e for e in events if e["kind"] == kind])
    return readers.read_metric("layer_metrics", NAME, {"tracer": tracer})


def _event(round_idx, bounded=None, fallback=None):
    e = {"kind": "moe_load", "round": round_idx, "max": 9.0, "mean": 3.0,
         "empty": 0, "held": 5.0, "held_max": 3.0, "held_mean": 1.0,
         "held_empty": 0}
    if bounded is not None:
        e.update(bounded=bounded, fallback=fallback)
    return e


@pytest.mark.parametrize("counts,want", [
    ([(32.0, 0.0)] * 4, 0.0),                       # the cell: never
    ([(32.0, 0.0), (30.0, 2.0), (32.0, 0.0), (28.0, 4.0)], 100 * 6 / 128),
    ([(0.0, 32.0)] * 4, 100.0)])
def test_the_share_of_calls_on_the_worst_case_path_over_the_window(counts,
                                                                   want):
    events = [_event(4 + i, b, f) for i, (b, f) in enumerate(counts)]
    # rounds outside the window (warm-up, past its end) do not count
    events += [_event(3, 0.0, 32.0), _event(8, 0.0, 32.0)]
    assert _read(events) == pytest.approx(want)


def test_events_without_the_fields_read_nothing():
    assert _read([_event(r) for r in range(4, 8)]) is None
    # a whole model's event: no `held*` either
    assert _read([{"kind": "moe_load", "round": 5, "max": 9.0, "mean": 3.0,
                   "empty": 0}]) is None
    # only the rounds that say it are counted
    assert _read([_event(4), _event(5, 12.0, 4.0)]) == pytest.approx(25.0)


def test_an_empty_window_reads_nothing():
    assert _read([]) is None
    assert _read([_event(2, 32.0, 0.0)]) is None
    assert _read([_event(5, 32.0, 0.0)], first=6, last=6) is None
    assert _read([_event(5, 0.0, 0.0)]) is None     # no call counted


def test_the_share_cell_lists_the_metric_and_the_whole_model_cell_does_not():
    mine = {m["name"]: m for m in
            run.load_cell("kimi_linear_lora.train")["per_layer"]}
    assert mine[NAME] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_span", "layer": "round program",
        "moves": "train_samples_per_s_chip",
        "workloads": ["kimi_linear_lora.train"]}
    for cell in ("dsv2lite_lora.train", "flagship.train", "cross_silo.train"):
        assert NAME not in {m["name"]
                            for m in run.load_cell(cell)["per_layer"]}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert NAME in [m["name"] for m in json.load(f)["per_layer"]]
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           NAME + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "module" and spec["unit"] == "%"
