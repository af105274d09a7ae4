"""The benchmark's harness on the CPU at a tiny size: the window, the
comparison that decides `correct` with its control and its planted faults,
the FLOP count, the trace reduction, and that BENCHMARK.json resolves to
files. The tiny cells under tests/benchmark/cells/ are added the way a later
PR adds a cell: files and one entry, no edit to the harness."""

import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import calibrate, run  # noqa: E402
from benchmarks.datasets import writers  # noqa: E402
from benchmarks.harness import correct, data, flops, trace  # noqa: E402
from benchmarks.harness import readers  # noqa: E402

TINY = os.path.join(ROOT, "tests", "benchmark", "cells", "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def tiny(control="", seed=2 ** 31 + 77):
    """One run of the tiny flagship cell, past the harness's look for a chip
    (run.main's)."""
    spec = calibrate.with_control(
        run.load_cell("tiny_flagship.train", TINY), control)
    return run.run_cell(spec, seed, 0.3, False, t_start=time.perf_counter())


def test_window_opens_after_warm_up_closes_past_deadline_leaves_no_thread():
    r = tiny()
    spec = run.load_cell("tiny_flagship.train", TINY)
    first, last = r["run"]["rounds"]
    assert first == spec["traffic"]["warm_rounds"] and last > first
    assert r["attempted"] == last - first and r["failed"] == 0
    assert r["run"]["window_s"] >= 0.3
    counts = writers.sizes(spec["config"]["data"])[0]
    from benchmarks.reference.fedavg import sample_cohort
    want = sum(int(counts[sample_cohort(k, 6, 2)].sum())
               for k in range(first, last))
    assert r["run"]["samples"] == want
    got = r["metrics"]["train_samples_per_s_chip"]["value"]
    assert got == pytest.approx(want / r["run"]["window_s"])
    assert set(r["metrics"]) == {"setup_s", "train_samples_per_s_chip",
                                 "round_p95_ms"}
    assert r["correct"] is True, r["compared"]
    assert list(r)[-1] == "compared"
    assert [t.name for t in threading.enumerate()] == ["MainThread"]


@pytest.mark.parametrize("broken", [
    {"fault": "state_unchanged"}, {"fault": "half_batch"},
    {"control": "bfloat16"}, {"control": "ref:bf16"}])
def test_broken_timed_path_or_lower_precision_reads_incorrect(broken):
    """The rest of a run with the timed path broken underneath (a step that
    returns its state unchanged; half of every client's rows left out and
    the mean taken over the rest), and the controls (the program's own
    bfloat16 path where the configuration states float32; the reference in
    bfloat16 put in the program's place)."""
    if "fault" in broken:
        with calibrate.break_round(broken["fault"]):
            r = tiny()
    elif broken["control"].startswith("ref:"):
        r = calibrate.reference_in_place(
            run.load_cell("tiny_flagship.train", TINY), 2 ** 31 + 77,
            broken["control"][4:])
    else:
        r = tiny(broken["control"])
    assert r["correct"] is False
    over = {k for k, v in r["compared"].items() if not v["value"] <= v["limit"]}
    if "fault" in broken:
        assert {"grad_gap", "change_gap"} & over
    else:
        assert over and "total_gap" not in over


def test_verdict_needs_a_limit_for_every_number_and_fails_nan():
    ok, table = correct.verdict({"a": 1.0, "b": float("nan")},
                                {"a": 2.0, "b": 2.0})
    assert not ok and table["a"] == {"value": 1.0, "limit": 2.0}
    with pytest.raises(KeyError):
        correct.verdict({"a": 1.0}, {})
    assert correct.verdict({"a": 1.0, "b": 9.0}, {"a": 2.0}, ("b",)) == (
        True, {"a": {"value": 1.0, "limit": 2.0}})
    ref = {"['params']['w']": 2.0, "['params']['b']": 1e-9,
           "['params']['v']": 1.0}
    same = {"losses": [1.0], "totals": [5.0], "first": ref, "change": ref}
    still = dict(same, first={k: 0.0 for k in ref},
                 change={k: 0.0 for k in ref})
    assert correct.numbers(same, same)["grad_gap"] == 0.0
    n = correct.numbers(still, same)
    assert n["grad_gap"] == 1.0 and n["change_gap"] == 1.0


def test_traffic_may_name_its_comparison_and_bring_limits():
    """A later cell that drives more than the configuration's comparison
    follows comes as a traffic file and a module of benchmarks/compare/."""
    from benchmarks.compare import fedavg_rounds

    spec = run.load_cell("tiny_flagship.train", TINY)
    module, limits, skip = run.comparison(spec)
    assert module is fedavg_rounds and limits == spec["config"]["limits"]
    assert skip == ()
    spec["traffic"] = dict(spec["traffic"], compare="fedavg_rounds",
                           limits={"eval_gap": 1e-3, "loss_gap": 0.5})
    spec["config"]["not_compared"] = ["change_gap"]
    module, limits, skip = run.comparison(spec)
    assert module is fedavg_rounds and skip == ("change_gap",)
    assert limits == dict(spec["config"]["limits"], eval_gap=1e-3,
                          loss_gap=0.5)


def test_flops_against_a_hand_count():
    from benchmarks.reference import cnn_dropout, resnet56

    cnn = (2 * 26 * 26 * 9 * 1 * 32 + 2 * 24 * 24 * 9 * 32 * 64
           + 2 * 9216 * 128 + 2 * 128 * 62)
    assert flops.forward_flops_per_sample(
        cnn_dropout.layers({"classes": 62})) == cnn == 23_998_208
    # ResNet-56, bottleneck [6, 6, 6]: stem, then per stage the first block
    # (with its 1x1 shortcut) and five more, then fc
    def block(hw_in, cin, planes, stride, down):
        hw = hw_in // stride
        f = (hw_in ** 2 * cin * planes + hw ** 2 * 9 * planes * planes
             + hw ** 2 * planes * 4 * planes)
        return 2 * (f + (hw ** 2 * cin * 4 * planes if down else 0))
    want = 2 * 32 * 32 * 27 * 16
    want += block(32, 16, 16, 1, True) + 5 * block(32, 64, 16, 1, False)
    want += block(32, 64, 32, 2, True) + 5 * block(16, 128, 32, 1, False)
    want += block(16, 128, 64, 2, True) + 5 * block(8, 256, 64, 1, False)
    want += 2 * 256 * 10
    got = flops.forward_flops_per_sample(resnet56.layers({"classes": 10}))
    assert got == want
    assert flops.train_flops_per_sample(
        resnet56.layers({"classes": 10})) == 3 * want


def test_trace_reduction_on_a_synthetic_event_list():
    ev = [(0.0, 1.0), (0.5, 1.0), (3.0, 1.0), (3.2, 0.1)]
    assert trace.busy_union(ev) == pytest.approx(2.5)
    gaps = trace.idle_gaps(ev, 0.0, 5.0)
    assert gaps == [(1.5, 1.5), (4.0, 1.0)]
    host = [("host:round", 0.0, 5.0), ("host:stage_wait", 1.4, 1.7)]
    assert trace.name_gaps(gaps, host) == [["host:stage_wait", 1.5],
                                           ["host:round", 1.0]]
    assert trace.top_ops([("a", 1.0), ("b", 3.0), ("a", 1.5)], top=1) == [
        ["b", 3.0]]
    # a loop's event spans its body's: only its own time counts as its
    nested = [("while", 0.0, 10.0), ("a", 1.0, 2.0), ("inner", 5.0, 4.0),
              ("c", 6.0, 1.0), ("after", 11.0, 1.0)]
    assert dict(trace.self_times(nested)) == {
        "while": 4.0, "a": 2.0, "inner": 3.0, "c": 1.0, "after": 1.0}
    assert trace.modules([("jit_round_fn(12)", 0.04), ("jit_f(3)", 0.001),
                          ("jit_round_fn(12)", 0.05)])[0] == [
        "jit_round_fn", 2, pytest.approx(0.09)]
    assert trace.short_name(
        "%fusion.3 = (f32[10,32]{1,0:T(8,128)}, bf16[2]{0}) fusion(f32[1]{0} "
        "%p), kind=kOutput, calls=%f") == "fusion.3 f32[10,32] kOutput"
    top = {"trace": {"modules": [["jit_round_fn", 2, 0.09]]}}
    assert readers.trace_top_module_ms(top, {}) == pytest.approx(45.0)
    ctx = {"trace": {"busy_s": 2.5, "window_s": 5.0}, "peaks": None}
    assert readers.trace_idle_pct(ctx, {}) == pytest.approx(50.0)
    assert readers.trace_idle_pct({"trace": None}, {}) is None
    assert readers.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 95) == pytest.approx(4.8)


@pytest.mark.parametrize("manifest", [os.path.join(ROOT, "BENCHMARK.json"),
                                      TINY])
def test_every_entry_resolves_to_files_and_every_moves_is_reported(manifest):
    with open(manifest) as f:
        bench = json.load(f)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"], manifest)
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        reported = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec["per_layer"]
        for m in spec["per_layer"]:
            assert m["moves"] in reported, (w["name"], m["name"])
            with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                   m["name"] + ".json")) as f:
                lm = json.load(f)
            assert lm["reader"] in readers.GENERAL or lm["reader"] == "module"
            assert (lm["layer"], lm["unit"], lm["moves"]) == (
                m["layer"], m["unit"], m["moves"])
        assert (set(spec["config"]["limits"])
                | set(spec["config"].get("not_compared", ()))) >= {
            "loss_gap", "total_gap", "grad_gap", "change_gap"}
        for group, key in (("reference", spec["config"]["reference"]),
                           ("compare", spec["config"]["compare"]),
                           ("datasets", spec["config"]["data"]["kind"])):
            __import__(f"benchmarks.{group}.{key}")
    for m in bench["end_to_end"]:
        with open(os.path.join(ROOT, "benchmarks", "end_to_end",
                               m["name"] + ".json")) as f:
            assert json.load(f)["reader"] in readers.GENERAL


def test_shapes_do_not_move_with_the_seed():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "flagship.json")) as f:
        spec = json.load(f)["data"]
    n, n_test = writers.sizes(spec)
    assert n.max() == n[0] == 480 and n.min() >= 16 and len(n) == 3400
    assert (n.sum(), n_test.sum()) == (671_585, 77_483)   # TFF's split
    assert (n == writers.sizes(spec)[0]).all()
    small = dict(spec, clients=6, n_max=24, n_min=4, train_rows=80,
                 test_rows=15)
    a, b = data.make(small, 1), data.make(small, 2 ** 31 + 5)
    for split in ("train", "test"):
        assert a[split][0].shape == b[split][0].shape
        assert (a[split][2] == b[split][2]).all()
    assert not np.array_equal(a["train"][0], b["train"][0])
    pooled = {"kind": "pooled", "clients": 4, "classes": 3,
              "image_shape": [4, 4, 3], "train_rows": 40, "test_rows": 8}
    assert (data.make(pooled, 3)["train"][2] == 10).all()


def test_reference_batchnorm_sees_the_real_rows_only():
    """A short last batch is the source's: rows that only fill the fixed
    shape take no part in the batch statistics, and the running variance
    takes the unbiased one (torch)."""
    import jax.numpy as jnp

    from benchmarks.reference import resnet56

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 2, 2, 3)), jnp.float32)
    p = {"BatchNorm_0": {"scale": jnp.ones(3), "bias": jnp.zeros(3)}}
    s = {"BatchNorm_0": {"mean": jnp.zeros(3), "var": jnp.ones(3)}}
    y, new = resnet56._norm(x, p, s, True, jnp.array([1, 1, 0, 0], bool))
    real = np.asarray(x[:2]).reshape(-1, 3)
    np.testing.assert_allclose(new["BatchNorm_0"]["mean"],
                               0.1 * real.mean(0), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(new["BatchNorm_0"]["var"],
                               0.9 + 0.1 * real.var(0, ddof=1), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(y[:2]).reshape(-1, 3),
        (real - real.mean(0)) / np.sqrt(real.var(0) + 1e-5), rtol=1e-4,
        atol=1e-5)
    _, full = resnet56._norm(x, p, s, True, jnp.ones(4, bool))
    np.testing.assert_allclose(full["BatchNorm_0"]["mean"],
                               0.1 * np.asarray(x).reshape(-1, 3).mean(0),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.slow   # two ResNet-56 compiles: 2-6 minutes on the CPU
def test_cross_silo_control_fp8_reads_incorrect_at_a_tiny_size():
    """The control of the bfloat16 cell: the reference put in the program's
    place and computed with float8 operands, held to the cell's own limits.
    The reference alone, at one round of two silos: the program's ResNet-56
    round compiles for minutes on a CPU. The chip's readings at the cell's
    own size are in PERF.md section 2."""
    import jax

    from benchmarks.reference import fedavg, resnet56

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "cross_silo.json")) as f:
        config = json.load(f)
    spec = dict(config["data"], clients=2, train_rows=256, test_rows=8)
    d = data.make(spec, 5)
    w0 = jax.jit(lambda k: resnet56.init(k, config["sizes"]))(
        jax.random.PRNGKey(5))
    hyper = dict(config["hyper"], client_num_per_round=2)

    def follow(compute):
        r = fedavg.run_rounds(resnet56, hyper, w0, *d["train"], 5, 1, compute)
        return {"losses": [r[0]["loss"]], "totals": [r[0]["total"]],
                "first": correct.diff_norms(w0, r[0]["variables"]),
                "change": correct.diff_norms(r[0]["variables"], w0)}

    nums = correct.numbers(follow("fp8"), follow("f32"))
    ok, table = correct.verdict(nums, config["limits"])
    assert not ok, table
