"""graft-matrix: the declarative feature-matrix spec (core/spec.py) and its
analysis engine (analysis/matrix_engine.py).

Covers the spec<->FedConfig.validate round-trip, the illegal-combination
proof (every table entry raises with its exact reason), a cheap abstract
trace of legal points through the real builders, the spec<->budget-file
coverage gate (pass + trip), the axis-drift AST rule on fixtures and on
the repo itself, and the byte-stability of the --update-budgets path.

The full pairwise-cover trace (31 programs, ~15s) runs in ci_smoke.sh's
--matrix step; here only vmap-family points are traced so the module adds
seconds, not minutes, to tier-1."""

import itertools
import json
import os

import pytest

from fedml_tpu.analysis.matrix_engine import (
    check_budget_coverage,
    check_illegal_pairs,
    enumerate_matrix,
    lint_axis_drift,
    lint_axis_drift_source,
    pairwise_cover,
    point_family,
    trace_point,
)
from fedml_tpu.core.spec import (
    ASSEMBLERS,
    AXES,
    AXIS_KWARGS,
    CONSTRAINTS,
    DRIVE_SPECS,
    EXCLUSIONS,
    AssemblerSpec,
    axis_levels,
    drive_program_names,
    first_violation,
    is_legal,
    point_config,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _full(**levels):
    """A complete axis assignment: table defaults overlaid with `levels`."""
    out = {name: axis.default for name, axis in AXES.items()}
    out.update(levels)
    return out


# ---------------------------------------------- spec <-> validate round-trip

def test_every_axis_level_is_reachable_in_some_legal_point():
    legal, total = enumerate_matrix()
    assert total == len(list(itertools.product(
        *(a.levels for a in AXES.values()))))
    assert 0 < len(legal) < total
    seen = {name: set() for name in AXES}
    for point in legal:
        for name, level in point.items():
            seen[name].add(level)
    for name, axis in AXES.items():
        assert seen[name] == set(axis.levels), (
            f"axis {name}: level(s) {set(axis.levels) - seen[name]} appear "
            f"in NO legal point — the exclusion tables made them dead")


def test_legal_points_round_trip_through_fedconfig_validate():
    # spec -> config -> spec: a legal assignment builds a FedConfig,
    # validate() accepts it with the non-config overlay, and axis_levels
    # projects the config back onto the same config-axis levels
    legal, _ = enumerate_matrix()
    overlay_axes = {n for n, a in AXES.items() if a.overrides is None}
    for point in legal[:: max(1, len(legal) // 50)]:  # ~50-point sample
        cfg = point_config(point)
        overlay = {n: point[n] for n in overlay_axes}
        cfg.validate(**overlay)
        projected = axis_levels(cfg)
        for name in AXES:
            if name in overlay_axes:
                continue
            assert projected[name] == point[name], (name, point)


def test_illegal_point_is_rejected_by_fedconfig_validate():
    point = _full(codec="int8", silo="on")
    assert not is_legal(point)
    reason = first_violation(point).reason
    with pytest.raises(ValueError) as e:
        point_config(point).validate(
            **{n: point[n] for n, a in AXES.items() if a.overrides is None})
    assert str(e.value) == reason


def test_the_axes_are_the_twelve_and_the_cli_refuses_a_deleted_one():
    # the `fused` axis went with the kernel it selected (PR 34): the table
    # declares twelve axes, and the flag is argparse's unknown argument
    import argparse

    from fedml_tpu.core.spec import REQUIREMENTS
    from fedml_tpu.experiments.common import add_args

    assert list(AXES) == [
        "backend", "silo", "tensor", "lora", "buffer", "pipeline",
        "superstep", "codec", "aggregator", "chaos", "stats",
        "personalization"]
    assert len(EXCLUSIONS) == 20
    assert [(r.axis, r.level) for r in REQUIREMENTS] == [
        ("personalization", "on")]
    parser = add_args(argparse.ArgumentParser())
    parser.parse_args(["--rounds_per_dispatch", "2"])
    with pytest.raises(SystemExit) as e:
        parser.parse_args(["--fused_kernel", "1"])
    assert e.value.code == 2


# ----------------------------------------------- illegal-combination proof

def test_every_illegal_table_entry_raises_with_its_reason():
    findings, checked = check_illegal_pairs()
    assert not findings, "\n".join(f.message for f in findings)
    # every pairwise exclusion level-pair plus every constraint clause
    # combination must have been probed
    floor = sum(len(e.levels_a) * len(e.levels_b) for e in EXCLUSIONS)
    assert checked >= floor, (checked, floor)
    assert CONSTRAINTS, "spec lost its n-ary constraint table"


def test_shadowed_constraint_raises_the_first_matching_reason():
    # codec x tensor=shard_step x robust violates BOTH the pairwise
    # shard_step exclusion and the ternary robust-codec constraint; table
    # order says the pairwise entry fires — the contract check_illegal_pairs
    # enforces for every combination
    point = _full(codec="int8", tensor="shard_step", aggregator="robust")
    hit = first_violation(point)
    assert hit in EXCLUSIONS, "expected the pairwise exclusion to shadow"
    with pytest.raises(ValueError, match="shard_step"):
        point_config(point).validate(aggregator="robust")


# ---------------------------------------------------- legal-cover tracing

def test_pairwise_cover_hits_every_legal_pair():
    legal, _ = enumerate_matrix()
    cover = pairwise_cover(legal)
    assert 0 < len(cover) < len(legal)

    def pairs(point):
        names = sorted(point)
        return {((a, point[a]), (b, point[b]))
                for a, b in itertools.combinations(names, 2)}

    want = set().union(*(pairs(p) for p in legal))
    have = set().union(*(pairs(p) for p in cover))
    assert want == have, f"{len(want - have)} legal pair(s) uncovered"


def test_trace_smoke_vmap_families():
    # the cheap slice of what ci_smoke's full --matrix run proves: the
    # default point, a codec-wrapped point, and a superstep point all
    # build abstractly through the real assemblers
    trace_point(_full())
    trace_point(_full(codec="topk", chaos="on"))
    trace_point(_full(superstep="on", lora="on"))


def test_trace_point_rejects_illegal_points_at_config_time():
    with pytest.raises(ValueError, match="silo"):
        trace_point(_full(codec="int8", silo="on"))


# ------------------------------------------------- budget coverage gate

def test_budget_coverage_gate_passes_on_the_committed_files():
    findings = check_budget_coverage(ROOT)
    assert not findings, "\n".join(f.message for f in findings)


def test_budget_coverage_trips_on_removed_pin():
    budgets = json.load(open(os.path.join(ROOT, "COMPILE_BUDGET.json")))
    pin = "sharded.round[lr,f32,fedavg,8,topk64]"
    assert pin in budgets["sharded"]["programs"]
    del budgets["sharded"]["programs"][pin]
    findings = check_budget_coverage(ROOT, compile_budgets=budgets,
                                     check_live_comms=False)
    assert any(f.rule == "matrix-coverage" and pin in f.message
               and "not budget-gated" in f.message for f in findings), \
        [f.message for f in findings]


def test_budget_coverage_trips_on_stale_pin_and_count_drift():
    budgets = json.load(open(os.path.join(ROOT, "COMPILE_BUDGET.json")))
    budgets["eager"]["programs"]["engine.round[lr,f32,ghost]"] = 1
    budgets["eager"]["programs"]["engine.eval[lr,f32]"] += 1
    findings = check_budget_coverage(ROOT, compile_budgets=budgets,
                                     check_live_comms=False)
    msgs = [f.message for f in findings]
    assert any("stale budget pin `engine.round[lr,f32,ghost]`" in m
               for m in msgs), msgs
    assert any("engine.eval[lr,f32]" in m and "pins" in m
               for m in msgs), msgs


def test_budget_coverage_trips_on_comms_drift_both_directions():
    comms = {name: {} for name in
             __import__("fedml_tpu.core.spec",
                        fromlist=["COMMS_PROGRAM_NAMES"]).COMMS_PROGRAM_NAMES}
    dropped = sorted(comms)[0]
    del comms[dropped]
    comms["tensor.round[lr,f32,ghost,2x4]"] = {}
    findings = check_budget_coverage(ROOT, comms_budgets=comms,
                                     check_live_comms=False)
    msgs = [f.message for f in findings if f.target == "comms:budget"]
    assert any(dropped in m and "no entry" in m for m in msgs), msgs
    assert any("ghost" in m and "stale pin or undeclared" in m
               for m in msgs), msgs


# ----------------------------------------------------- axis-drift rule

_DRIFT_SPECS = (
    AssemblerSpec("pkg/mod.py", "build_x_round_fn",
                  ("donate_data", "collect_stats")),
)


def test_axis_drift_clean_fixture():
    src = ("def build_x_round_fn(trainer, cfg, *, donate_data=True,\n"
           "                     collect_stats=False):\n"
           "    pass\n")
    assert lint_axis_drift_source(src, "pkg/mod.py",
                                  assemblers=_DRIFT_SPECS) == []


def test_axis_drift_flags_dropped_kwarg():
    src = "def build_x_round_fn(trainer, cfg, *, donate_data=True):\n    pass\n"
    findings = lint_axis_drift_source(src, "pkg/mod.py",
                                      assemblers=_DRIFT_SPECS)
    assert len(findings) == 1 and findings[0].rule == "axis-drift"
    assert "no longer carries feature-axis kwarg `collect_stats`" \
        in findings[0].message


def test_axis_drift_flags_undeclared_kwarg():
    src = ("def build_x_round_fn(trainer, cfg, *, donate_data=True,\n"
           "                     collect_stats=False, codec=None):\n"
           "    pass\n")
    findings = lint_axis_drift_source(src, "pkg/mod.py",
                                      assemblers=_DRIFT_SPECS)
    assert len(findings) == 1
    assert "grew feature-axis kwarg `codec`" in findings[0].message
    assert "codec" in AXIS_KWARGS  # the rule only polices spec'd axis kwargs


def test_axis_drift_ignores_non_axis_kwargs_and_missing_fn():
    src = "def build_x_round_fn(trainer, cfg, *, donate_data=True,\n" \
          "                     collect_stats=False, verbose=False):\n" \
          "    pass\n"
    assert lint_axis_drift_source(src, "pkg/mod.py",
                                  assemblers=_DRIFT_SPECS) == []
    findings = lint_axis_drift_source("x = 1\n", "pkg/mod.py",
                                      assemblers=_DRIFT_SPECS)
    assert len(findings) == 1 and "does not define" in findings[0].message


def test_axis_drift_respects_suppression_with_reason():
    src = ("# graft-lint: disable=axis-drift -- fixture: deliberate drop\n"
           "def build_x_round_fn(trainer, cfg, *, donate_data=True):\n"
           "    pass\n")
    assert lint_axis_drift_source(src, "pkg/mod.py",
                                  assemblers=_DRIFT_SPECS) == []


def test_axis_drift_repo_is_clean():
    # the pin: every ASSEMBLERS signature matches its declaration, so any
    # future kwarg add/drop must come with a table update (or suppression)
    findings = lint_axis_drift(ROOT)
    assert not findings, "\n".join(str(f) for f in findings)


def test_assemblers_table_names_real_modules_and_axis_kwargs():
    for spec in ASSEMBLERS:
        assert os.path.exists(os.path.join(ROOT, spec.module)), spec.module
        assert set(spec.axis_kwargs) <= AXIS_KWARGS, spec


# ------------------------------------------- --update-budgets byte stability

def test_update_budgets_round_trips_byte_stable_from_the_spec():
    # the spec-declared program surface regenerates COMPILE_BUDGET.json
    # byte-for-byte: same entries, same counts, same key order, preserved
    # max_compiles ceilings — proof the committed file IS the spec's view
    from fedml_tpu.analysis.compile_engine import load_budgets, make_budgets

    committed = open(os.path.join(ROOT, "COMPILE_BUDGET.json")).read()
    measured = {d: drive_program_names(d) for d in DRIVE_SPECS}
    regenerated = make_budgets(measured, existing=load_budgets(ROOT))
    assert json.dumps(regenerated, indent=2) + "\n" == committed


def test_spec_families_cover_every_drive_program():
    # every budget-pinned program name parses and maps onto a family the
    # matrix engine knows how to trace
    from fedml_tpu.core.spec import parse_program_name

    eval_prefixes = ("engine.eval", "engine.client_eval",
                     "engine.federation_eval", "engine.chunked")
    for drive in DRIVE_SPECS:
        for name in drive_program_names(drive):
            assert parse_program_name(name), name
            fam = name.rsplit("[", 1)[0]
            assert fam.count(".") == 1 or name.startswith("engine.chunked"), \
                name


def test_point_family_mirrors_fedavg_dispatch_order():
    # superstep wins over buffer wins over the parallel backends wins over
    # silo — the same if/elif ladder FedAvgAPI uses
    assert point_family(_full(superstep="on", buffer="on")) == "superstep"
    assert point_family(_full(buffer="on", backend="shard_map")) == "buffered"
    assert point_family(_full(backend="shard_map")) == "sharded"
    assert point_family(_full(tensor="shards")) == "tensor_round"
    assert point_family(_full(tensor="shard_step")) == "tensor_step"
    assert point_family(_full(silo="on")) == "silo"
    assert point_family(_full()) == "engine"
