"""The round program's scope map (`FedAvgAPI.program_scopes`,
`telemetry/scopes.py`): on the tiny DeepSeek-V2 and Kimi Linear cells it
names the expert dispatch's phases in the forward and the backward and the
KDA mixer's scopes, on the tiny Jamba cell the Mamba mixer's and on the
tiny Mellum cell its two kinds of attention; a second
call is the same map and compiles nothing;
a compile cache filled without the scopes reads stale and maps nothing;
the HLO parser reads the optimised text's numbered tuple headers; the
operator's join of a profile (`telemetry/report.py::device_by_scope`)."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from fedml_tpu import telemetry
from fedml_tpu.analysis.hlo_engine import parse_hlo_text
from fedml_tpu.telemetry import scopes
from fedml_tpu.telemetry.report import device_by_scope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
METADATA_KEY = "jax_compilation_cache_include_metadata_in_key"


class _Compiles:
    """Backend compiles while open (as harness/window.py::CompileLog)."""

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _tiny_api(cell: str):
    from benchmarks import run
    from benchmarks.harness import data as bdata

    spec = run.load_cell(f"tiny_{cell}.train", os.path.join(
        ROOT, "tests", "benchmark", "cells", f"tiny_{cell}.manifest.json"))
    api, _ = run.build_api(spec["config"], spec["traffic"],
                           bdata.make(spec["config"]["data"], 7), 7)
    return api


@pytest.fixture(scope="module", params=["dsv2lite_lora", "kimi_linear_lora"])
def scoped(request):
    """(cell, api, map, event, compiles of the first and the second call)
    after one round. Keyed with metadata for the test's compiles, so that no
    executable another tree left in the suite's persistent cache (without
    these scopes) is handed back."""
    before = getattr(jax.config, METADATA_KEY)
    jax.config.update(METADATA_KEY, True)
    try:
        api = _tiny_api(request.param)
        tracer = telemetry.Tracer()
        api.train_one_round(0, tracer=tracer)
        with _Compiles() as first:
            names = api.program_scopes(tracer)
        with _Compiles() as second:
            again = api.program_scopes(tracer)
    finally:
        jax.config.update(METADATA_KEY, before)
    assert again is names and second.n == 0
    [event] = tracer.find_events("program_scopes")
    return request.param, api, names, event, first.n


@pytest.mark.parametrize("scope", ["moe_layout", "moe_gather", "moe_combine"])
def test_the_dispatch_phases_are_named_forward_and_backward(scoped, scope):
    """In the compiled text, fused computations included, both directions
    hold each phase; the map (ops that run: a fusion under the op_name XLA
    gave it, so the forward's combine may run inside the block's residual
    add) holds each, always inside the dispatch's outer name."""
    _, api, names, event, _ = scoped
    text = api._round_program.lower(*api._round_avals).compile().as_text()
    paths = [p for p in re.findall(r'op_name="([^"]*)"', text)
             if scopes.holds(p, scope)]
    assert [p for p in paths if "jvp(" in p and "transpose(" not in p]
    assert [p for p in paths if "transpose(" in p]
    mapped = [p for p in names.values() if scopes.holds(p, scope)]
    assert mapped and event["scoped"][scope] == len(mapped)
    assert all(scopes.holds(p, "experts") for p in mapped)


@pytest.mark.parametrize("scope", ["kda", "kda_conv", "kda_gates"])
def test_the_kda_mixer_is_named_in_kimi_linear(scoped, scope):
    cell, _, names, event, _ = scoped
    held = [p for p in names.values() if scopes.holds(p, scope)]
    if cell == "kimi_linear_lora":
        assert held and event["scoped"][scope] == len(held)
    else:
        assert not held and scope not in event["scoped"]


def test_the_mamba_mixer_is_named_in_jamba():
    """The Jamba round program's compiled text names the Mamba mixer's
    three scopes, forward and backward, and the map holds each; the other
    decoders' mixers are not there."""
    before = getattr(jax.config, METADATA_KEY)
    jax.config.update(METADATA_KEY, True)
    try:
        api = _tiny_api("jamba2_lora")
        tracer = telemetry.Tracer()
        api.train_one_round(0, tracer=tracer)
        names = api.program_scopes(tracer)
        text = api._round_program.lower(*api._round_avals).compile().as_text()
    finally:
        jax.config.update(METADATA_KEY, before)
    [event] = tracer.find_events("program_scopes")
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("mamba", "ssm_conv", "ssm_gates"):
        named = [p for p in paths if scopes.holds(p, scope)]
        assert [p for p in named if "transpose(" in p], scope
        assert [p for p in named if "transpose(" not in p], scope
        held = [p for p in names.values() if scopes.holds(p, scope)]
        assert held and event["scoped"][scope] == len(held), scope
    assert not {"kda", "experts"} & set(event["scoped"])


def test_the_two_kinds_of_attention_are_named_in_mellum():
    """The Mellum round program's compiled text names the window layer's
    attention and the full layer's, forward and backward, and the map holds
    each; the other decoders' mixers are not there."""
    before = getattr(jax.config, METADATA_KEY)
    jax.config.update(METADATA_KEY, True)
    try:
        api = _tiny_api("mellum2_lora")
        tracer = telemetry.Tracer()
        api.train_one_round(0, tracer=tracer)
        names = api.program_scopes(tracer)
        text = api._round_program.lower(*api._round_avals).compile().as_text()
    finally:
        jax.config.update(METADATA_KEY, before)
    [event] = tracer.find_events("program_scopes")
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("attn_window", "attn_full"):
        named = [p for p in paths if scopes.holds(p, scope)]
        assert [p for p in named if "transpose(" in p], scope
        assert [p for p in named if "transpose(" not in p], scope
        held = [p for p in names.values() if scopes.holds(p, scope)]
        assert held and event["scoped"][scope] == len(held), scope
    assert not {"kda", "mamba"} & set(event["scoped"])
    assert "experts" in event["scoped"]


def test_the_map_comes_from_memory_and_the_event_says_what_it_holds(scoped):
    _, api, names, event, compiles = scoped
    assert compiles == 0            # the executable that ran: no compile
    assert event["stale"] is False
    assert event["program"] == "round_fn"
    assert 0 < len(names) <= event["named"] <= event["instructions"]
    assert set(names) == {i for i, s in event["op_scopes"].items() if s}
    assert set(event["scoped"]) <= set(scopes.DECLARED_SCOPES)
    # the object the API built, not whatever round_fn holds later
    assert api._round_program is getattr(api.round_fn, "jitted",
                                         api.round_fn)


def test_before_any_round_there_is_no_map_and_no_event():
    tracer = telemetry.Tracer()
    api = _tiny_api("dsv2lite_lora")
    assert api.program_scopes(tracer) == {}
    assert tracer.find_events("program_scopes") == []


@pytest.mark.parametrize("path, scope, held", [
    ("jit(loss)/vmap(jvp(experts))/moe_layout/jit(argsort)/sort",
     "experts", True),
    ("jit(loss)/vmap(jvp(experts))/moe_layout/jit(argsort)/sort",
     "moe_layout", True),
    ("a/vmap(transpose(jvp(experts)))/checkpoint/moe_combine/dot_general",
     "moe_combine", True),
    ("a/vmap(transpose(jvp(experts)))/checkpoint/moe_combine/dot_general",
     "experts", True),
    ("layers_1/kda/kda_conv/q_proj/dot_general", "kda", True),
    ("layers_1/kda_conv/q_proj/dot_general", "kda", False),
    ("layers_1/moe/routed_experts/add", "experts", False),
    ("layers_1/moe/experts_gate/add", "experts", False),
    ("experts", "experts", True),
])
def test_a_scope_is_a_name_of_the_path_inside_wrappers_too(path, scope, held):
    assert scopes.holds(path, scope) is held


def _scoped_and_plain():
    """One function in two trees: with the layout's scope, and without."""
    def f(x):
        return jnp.sort(x) * 2.0
    plain = jax.jit(f)

    def f(x):  # noqa: F811 (the same name: the same cache key)
        with jax.named_scope("moe_layout"):
            y = jnp.sort(x)
        return y * 2.0
    return plain, jax.jit(f)


def test_texts_without_the_scopes_the_lowering_names_read_stale():
    plain, scoped = _scoped_and_plain()
    x = jnp.arange(8.0)
    debug = scoped.lower(x).as_text(debug_info=True)
    names, event = scopes.join(plain.lower(x).compile().as_text(), debug, "f")
    assert event["stale"] is True and names == {} and event["scoped"] == {}
    names, event = scopes.join(scoped.lower(x).compile().as_text(), debug,
                               "f")
    assert event["stale"] is False and event["scoped"] == {"moe_layout": 1}


def test_a_cache_filled_without_the_scopes_reads_stale(tmp_path):
    """The persistent cache keys programs without their locations: an
    executable compiled from the tree without the scope is handed to the
    tree with it. The event says stale and the map is empty."""
    from jax._src import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    x = jnp.arange(8.0)
    compilation_cache.reset_cache()
    jax.config.update(keys[0], str(tmp_path))
    jax.config.update(keys[1], 0)
    jax.config.update(keys[2], 0)
    try:
        plain, scoped = _scoped_and_plain()
        plain.lower(x).compile()
        assert os.listdir(tmp_path)
        names, event = scopes.program_map(scoped, (x,), "f")
    finally:
        compilation_cache.reset_cache()
        for k, v in before.items():
            jax.config.update(k, v)
    assert event["stale"] is True and event["scoped"] == {}
    assert names == {}


def test_the_parser_reads_headers_of_numbered_tuples():
    text = """HloModule m

%body.7 (arg.1: (s32[], f32[2], f32[2], f32[2], f32[2], /*index=5*/f32[2])) -> (s32[], f32[2]) {
  %arg.1 = (s32[], f32[2], f32[2], f32[2], f32[2], /*index=5*/f32[2]) parameter(0)
  %gte.2 = f32[2]{0} get-tuple-element(%arg.1), index=5, metadata={op_name="jit(f)/while/body/moe_combine/add"}
  ROOT %t.3 = (s32[], f32[2]) tuple(%gte.2, %gte.2)
}

ENTRY %main.9 (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0), metadata={op_name="x"}
  %fusion.4 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fused.5, metadata={op_name="jit(f)/experts/moe_layout/sort"}
  %fusion.8 = f32[2]{0} fusion(%fusion.4), kind=kLoop, calls=%fused.5
  ROOT %moe_grouped_matmul.10 = f32[2]{0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={}
}

%fused.5 (q: f32[2]) -> f32[2] {
  %q = f32[2]{0} parameter(0)
  ROOT %neg.6 = f32[2]{0} negate(%q), metadata={op_name="jit(f)/experts/moe_layout/neg"}
}
"""
    module = parse_hlo_text(text)
    assert set(module.computations) == {"body.7", "main.9", "fused.5"}
    # the fused computation's instruction never runs as an op of its own;
    # a fusion XLA made without metadata takes its fused root's; a Pallas
    # call has none and is listed by its kernel's name
    count, named, kernels = scopes.op_names(text)
    assert count == 7 and set(named) == {"gte.2", "p", "fusion.4",
                                         "fusion.8"}
    assert kernels == ["moe_grouped_matmul.10"]
    assert named["fusion.8"] == "jit(f)/experts/moe_layout/neg"
    names, event = scopes.join(text, "", "f")
    assert set(names) == {"gte.2", "fusion.4", "fusion.8"}
    assert event["op_scopes"] == {"gte.2": "moe_combine", "p": "",
                                  "fusion.4": "experts/moe_layout",
                                  "fusion.8": "experts/moe_layout"}
    assert event["kernels"] == ["moe_grouped_matmul.10"]


def test_the_operators_join_of_a_profile_by_scope():
    event = {"op_scopes": {"fusion.1": "experts/moe_layout",
                           "fusion.2": "experts", "copy.3": "",
                           "fusion.4": "kda"},
             "kernels": ["kda_fwd.6", "ssm_scan_bwd.7", "ssm_scan_bwd.8"]}
    ops = [["fusion.1 s32[128] kLoop", 0.2, 8],
           ["fusion.2 bf16[64,2048] kOutput", 0.3, 8],
           ["copy.3 f32[2]", 0.1, 8], ["fusion.4 f32[2]", 0.25, 4],
           ["while.5", 0.05, 4],          # no op_name: neither share
           ["kda_fwd.6 f32[2,4096]", 0.1, 4],   # a kernel: its own share
           ["ssm_scan_bwd.7 f32[2,8192]", 0.02, 4],
           ["ssm_scan_bwd.8 f32[2,8192]", 0.03, 4]]
    out = device_by_scope(event, ops, ["jit_round_fn", 4, 1.0])
    assert out["named_pct"] == pytest.approx(85.0)
    assert out["scoped_pct"] == pytest.approx(75.0)
    assert out["kernel_pct"] == pytest.approx(15.0)
    assert out["by_kernel_s"] == pytest.approx(
        {"kda_fwd": 0.1, "ssm_scan_bwd": 0.05})
    assert list(out["by_kernel_s"]) == ["kda_fwd", "ssm_scan_bwd"]
    assert out["by_scope_s"] == pytest.approx(
        {"experts": 0.5, "kda": 0.25, "moe_layout": 0.2})
    assert list(out["by_scope_s"]) == ["experts", "kda", "moe_layout"]


def test_the_cli_under_a_profile_leaves_the_map_for_the_operators_join(
        tmp_path, monkeypatch, capsys):
    """`--profile_rounds` leaves the `program_scopes` event in TRACE.jsonl;
    `tools/trace_report.py --profile` joins a profile's device ops with it
    (here on made-up ops: a CPU profile holds no device plane), and says
    what is missing where it cannot."""
    import json

    from benchmarks.harness import trace as btrace
    from fedml_tpu.experiments.main_fedavg import main
    from tools import trace_report

    run_dir = tmp_path / "run"
    main(["--dataset", "mnist", "--model", "lr", "--partition_method",
          "homo", "--client_num_in_total", "6", "--client_num_per_round",
          "4", "--comm_round", "3", "--batch_size", "32", "--lr", "0.1",
          "--profile_rounds", "1:2", "--run_dir", str(run_dir)])
    jsonl = str(run_dir / "TRACE.jsonl")
    with open(jsonl) as f:
        [event] = [r for r in map(json.loads, f)
                   if r.get("kind") == "program_scopes"]
    assert event["stale"] is False and event["scoped"] == {}
    named = list(event["op_scopes"])
    assert named and event["named"] == len(named)

    def report(profile):
        trace_report.main([jsonl, "--profile", profile])
        return json.loads(capsys.readouterr().out.splitlines()[-1])["scopes"]

    assert "no device trace" in report(str(run_dir / "trace"))["error"]
    ops = [[named[0] + " f32[6]", 0.75, 3], ["while.9", 0.25, 3]]
    monkeypatch.setattr(btrace, "read", lambda d, chips: {
        "ops": ops, "modules": [["jit_round_fn", 3, 1.0]]})
    got = report("anywhere")
    assert (got["named_pct"], got["scoped_pct"]) == (75.0, 0.0)
