"""The three readers of the round program's device time by program phase
(`benchmarks/layer_metrics/program_scopes.py`: `moe.dispatch_share_pct`,
`moe.layout_ms`, `kda.mixer_xla_share_pct`) on made-up device ops, modules
and scope maps: the shares by hand, and nothing (None, never 0) where there
is nothing to read: no map, a map without the scopes, a stale map, a program
that hands out none (the parent's), no trace."""

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
from benchmarks.layer_metrics import program_scopes  # noqa: E402

MAP = {
    "fusion.1": "jit(round_fn)/vmap()/while/body/jit(step_body)/"
                "jvp(DeepseekV2LM.hidden)/layers_1/moe/experts/moe_layout/sort",
    "fusion.2": "transpose(jvp(DeepseekV2LM.hidden))/jvp(DeepseekV2LM.hidden)"
                "/checkpoint/layers_1/moe/experts/moe_combine/reduce_sum",
    "moe_grouped_matmul.3": "jvp(DeepseekV2LM.hidden)/layers_1/moe/experts/"
                            "moe_grouped_matmul",
    "fusion.4": "jvp(KimiLinearLM.hidden)/layers_0/kda/kda_conv/q_proj/"
                "dot_general",
    "kda_fwd.5": "jvp(KimiLinearLM.hidden)/layers_0/kda/kda/kda_fwd",
    "fusion.6": "jvp(KimiLinearLM.hidden)/layers_0/kda/kda_gates/exp",
}
OPS = [["fusion.1 s32[131072] kLoop", 0.12, 24],
       ["fusion.2 bf16[2,1024,2048] kOutput", 0.30, 24],
       ["moe_grouped_matmul.3 bf16[57344,1408]", 0.50, 72],
       ["fusion.4 bf16[2,4096,4096] kOutput", 0.20, 12],
       ["kda_fwd.5 f32[2,32,4096,128]", 0.40, 12],
       ["fusion.6 f32[2,4096,4096] kLoop", 0.05, 12],
       ["fusion.7 f32[10] kLoop", 0.33, 3],          # not under a scope
       ["while.8", 0.10, 3]]                          # no op_name at all
MODULES = [["jit_round_fn", 3, 4.0], ["jit_fold_in", 3, 1e-4]]
SPECS = {
    "moe.dispatch_share_pct": {"scopes": ["experts"],
                               "exclude": "^moe_grouped_matmul"},
    "moe.layout_ms": {"scopes": ["moe_layout"]},
    "kda.mixer_xla_share_pct": {"scopes": ["kda", "kda_conv", "kda_gates"],
                                "exclude": "^kda_(fwd|bwd)"},
}


class _Tracer:
    def __init__(self, api, events=()):
        self.api, self._events = api, list(events)

    def find_events(self, kind):
        return [e for e in self._events if e["kind"] == kind]


def _ctx(names=MAP, stale=False, has_method=True, trace=True):
    def program_scopes():
        return names

    api = types.SimpleNamespace()
    if has_method:
        api.program_scopes = program_scopes
    event = {"kind": "program_scopes", "stale": stale}
    t = {"ops": OPS, "modules": MODULES} if trace else None
    return {"tracer": _Tracer(api, [event] if has_method else []),
            "trace": t}


@pytest.mark.parametrize("metric, expected", [
    # (0.12 + 0.30) s under `experts`, the grouped product left out, of 4.0
    ("moe.dispatch_share_pct", 100.0 * 0.42 / 4.0),
    # 0.12 s over 3 executions of the round program
    ("moe.layout_ms", 0.12 / 3 * 1e3),
    # (0.20 + 0.05) s under the mixer, its Pallas calls left out, of 4.0
    ("kda.mixer_xla_share_pct", 100.0 * 0.25 / 4.0),
])
def test_each_reader_gives_the_share_by_hand(metric, expected):
    assert readers.read_metric("layer_metrics", metric, _ctx()) == \
        pytest.approx(expected)


@pytest.mark.parametrize("case", [
    dict(names={}),                                   # an empty map
    dict(names={"fusion.7": "jit(round_fn)/cohort_stats/add"}),  # no scope
    dict(stale=True),                                 # the event says so
    dict(has_method=False),                           # the parent's program
    dict(trace=False),                                # no device trace
])
@pytest.mark.parametrize("metric", sorted(SPECS))
def test_nothing_to_read_is_none_never_zero(metric, case):
    assert readers.read_metric("layer_metrics", metric, _ctx(**case)) is None


@pytest.mark.parametrize("path, scope, held", [
    ("jit(loss)/vmap(jvp(experts))/moe_layout/jit(argsort)/sort",
     "experts", True),
    ("jit(loss)/vmap(jvp(experts))/moe_layout/jit(argsort)/sort",
     "moe_layout", True),
    ("jit(f)/vmap(transpose(jvp(experts)))/checkpoint/moe_combine/dot",
     "experts", True),
    ("jit(f)/vmap(transpose(jvp(experts)))/checkpoint/moe_combine/dot",
     "moe_combine", True),
    ("jit(f)/vmap(jvp(kda))/kda_gates/exp", "kda", True),
    ("jit(f)/layers_0/kda_gates/exp", "kda", False),
    ("jit(f)/layers_1/moe/experts_up/add", "experts", False),
    ("jit(f)/layers_1/moe_layouts/sort", "moe_layout", False),
])
def test_a_scope_matches_inside_wrappers_and_only_whole(path, scope, held):
    assert program_scopes.holds(path, scope) is held


@pytest.mark.parametrize("metric, unit, cells", [
    ("moe.dispatch_share_pct", "%",
     {"dsv2lite_lora.train", "kimi_linear_lora.train"}),
    ("moe.layout_ms", "ms", {"dsv2lite_lora.train", "kimi_linear_lora.train"}),
    ("kda.mixer_xla_share_pct", "%", {"kimi_linear_lora.train"})])
def test_each_entry_is_listed_by_its_cells_and_by_no_other(metric, unit,
                                                          cells):
    """The entry of BENCHMARK.json as registered, and the cells whose
    per-layer metrics it is: the cells that have the scopes, and no other
    (`kda.mixer_xla_share_pct` not `dsv2lite_lora.train`, none the image
    cells)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    [entry] = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry == {
        "name": metric, "unit": unit, "better": "lower",
        "source": "device_trace", "layer": "round program",
        "moves": "train_samples_per_s_chip", "workloads": sorted(cells)}
    for w in bench["workloads"]:
        names = {m["name"] for m in run.load_cell(w["name"])["per_layer"]}
        assert (metric in names) is (w["name"] in cells), w["name"]


def test_the_files_name_the_readers_and_their_scopes():
    for name, params in SPECS.items():
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "module" and spec["params"] == params
        assert spec["source"] == "device_trace"
