"""The benchmark's harness on the CPU at a tiny size: the window, the
comparison that decides `correct` with its control and its planted faults,
the FLOP count, the trace reduction, and that BENCHMARK.json resolves to
files. The tiny cells under tests/benchmark/cells/ are added the way a later
PR adds a cell: files and one entry, no edit to the harness."""

import contextlib
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
REPO = os.path.join(ROOT, "BENCHMARK.json")
LAYERS = os.path.join(ROOT, "benchmarks", "layer_metrics")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def tiny(control="", seed=2 ** 31 + 77, cell="tiny_flagship.train"):
    """One run of a tiny cell (the flagship one, unless said), past the
    harness's look for a chip (run.main's)."""
    spec = calibrate.with_control(run.load_cell(cell, TINY), control)
    return run.run_cell(spec, seed, 0.3, False, t_start=time.perf_counter())


@contextlib.contextmanager
def pad_tokens_counted():
    """The program's next-word trainer broken underneath: no token is taken
    for the pad, so the pads are trained on and counted in `total`."""
    from fedml_tpu.core.trainer import NWPTrainer

    init = NWPTrainer.__init__
    NWPTrainer.__init__ = lambda self, module, pad_id=0, id=0: init(
        self, module, pad_id=-1, id=id)
    try:
        yield
    finally:
        NWPTrainer.__init__ = init


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
    {"control": "bfloat16"}, {"control": "ref:bf16"},
    {"cell": "tiny_nwp.train", "fault": "pad_tokens_counted"},
    {"cell": "tiny_nwp.train", "control": "bfloat16"}],
    ids=lambda b: "-".join(v.split(".")[0] for v in b.values()))
def test_broken_timed_path_or_lower_precision_reads_incorrect(broken):
    """The rest of a run with the timed path broken underneath (a step that
    returns its state unchanged; half of every client's rows left out and
    the mean taken over the rest; in the language-model cell the pad tokens
    counted), and the controls (the program's own bfloat16 path where the
    configuration states float32; the reference in bfloat16 put in the
    program's place)."""
    cell = broken.get("cell", "tiny_flagship.train")
    if broken.get("fault") == "pad_tokens_counted":
        with pad_tokens_counted():
            r = tiny(cell=cell)
    elif "fault" in broken:
        with calibrate.break_round(broken["fault"]):
            r = tiny(cell=cell)
    elif broken["control"].startswith("ref:"):
        r = calibrate.reference_in_place(
            run.load_cell(cell, TINY), 2 ** 31 + 77, broken["control"][4:])
    else:
        r = tiny(broken["control"], cell=cell)
    assert r["correct"] is False
    over = {k for k, v in r["compared"].items() if not v["value"] <= v["limit"]}
    if broken.get("fault") == "pad_tokens_counted":
        # 2 silos x 10 sequences a round, one pad each
        assert r["compared"]["total_gap"]["value"] == 20.0
    elif "fault" in broken:
        assert {"grad_gap", "change_gap"} & over
    else:
        assert over and "total_gap" not in over


def test_a_language_model_cell_comes_as_files_only():
    """A configuration of another task than the two cells': a dataset kind
    that brings `meta`, a reference module with its own `loss` and its own
    layers' FLOPs, a configuration file and two entries of the tests'
    manifest. The program picks `NWPTrainer` by the dataset's task; `total`
    counts the tokens that are not the pad on both sides."""
    from benchmarks.reference import transformer_nwp

    spec = run.load_cell("tiny_nwp.train", TINY)
    r = tiny(cell="tiny_nwp.train")
    assert r["correct"] is True, r["compared"]
    assert set(r["compared"]) == set(spec["config"]["limits"])
    first, last = r["run"]["rounds"]
    assert r["run"]["samples"] == 20 * (last - first)   # sequences, real ones
    sizes = spec["config"]["sizes"]
    d, t, v = sizes["d_model"], sizes["seq_len"], sizes["vocab"]
    block = t * 2 * (3 * d * d + d * d + 8 * d * d) + 2 * d * t * (t + 1)
    assert flops.forward_flops_per_sample(transformer_nwp.layers(sizes)) == (
        sizes["num_layers"] * block + t * 2 * d * v)


@pytest.mark.parametrize("cell, more, trainer", [
    ("tiny_flagship.train", [], "ClassificationTrainer"),
    ("tiny_nwp.train", [], "NWPTrainer"),
    ("tiny_nwp.train", ["--lora_rank", "4"], "LoRATrainer")])
def test_the_program_picks_model_and_trainer(cell, more, trainer):
    """`build_api` goes through the program's own preamble: the trainer by
    the dataset's task, `--lora_rank` in a configuration's argv wraps it (the
    federated parameters are then the adapters alone), and what the preamble
    changed for the whole process is put back."""
    import logging

    import jax

    from fedml_tpu.experiments import common

    spec = run.load_cell(cell, TINY)
    spec["config"]["argv"] += more
    _, d, _ = run.make_inputs(spec["config"], 7)
    root, loader = logging.getLogger(), common.load_dataset
    before = (root.level, root.handlers[:],
              jax.config.jax_persistent_cache_min_compile_time_secs)
    api, cfg = run.build_api(spec["config"], spec["traffic"], d, 7)
    assert (root.level, root.handlers[:],
            jax.config.jax_persistent_cache_min_compile_time_secs) == before
    assert common.load_dataset is loader
    assert type(api.trainer).__name__ == trainer
    assert api.dataset.meta == d.get("meta", {})
    leaves = [jax.tree_util.keystr(path) for path, _ in
              jax.tree_util.tree_flatten_with_path(
                  api.global_variables["params"])[0]]
    if more:
        assert cfg.lora_rank == 4 and "lora_base" in api.global_variables
        assert leaves and all("lora_A" in k or "lora_B" in k for k in leaves)
    else:
        assert not any("lora" in k for k in leaves)


def test_build_api_ends_the_run_unless_setup_run_hands_its_dataset_back(
        monkeypatch):
    """`build_api` puts the benchmark's dataset in the place of the
    program's loader for the length of `setup_run`. A program that resolves
    its loader another way builds model and trainer for data of its own:
    `setup_run` then returns another dataset, and the run ends there."""
    import copy

    from fedml_tpu.experiments import common

    spec = run.load_cell("tiny_flagship.train", TINY)
    _, d, _ = run.make_inputs(spec["config"], 7)
    setup_run = common.setup_run

    def own_loader(args):
        cfg, ds, trainer = setup_run(args)
        return cfg, copy.copy(ds), trainer

    monkeypatch.setattr(common, "setup_run", own_loader)
    with pytest.raises(SystemExit, match="did not take the benchmark's"):
        run.build_api(spec["config"], spec["traffic"], d, 7)


@pytest.fixture
def process_as_it_was():
    """What `common.setup_run` changes for the whole process (root logging,
    the compile cache's threshold, the global generators), put back after a
    test that calls it bare."""
    import logging
    import random

    import jax

    root = logging.getLogger()
    level, handlers = root.level, root.handlers[:]
    secs = jax.config.jax_persistent_cache_min_compile_time_secs
    states = random.getstate(), np.random.get_state()
    yield
    root.setLevel(level)
    root.handlers[:] = handlers
    jax.config.update("jax_persistent_cache_min_compile_time_secs", secs)
    random.setstate(states[0])
    np.random.set_state(states[1])


@pytest.mark.parametrize("dataset, model, trainer, module, leaves", [
    ("fed_shakespeare", "rnn", "NWPTrainer", "RNN_OriginalFedAvg",
     {"embeddings/embedding": (90, 8), "fc/kernel": (256, 90)}),
    ("mnist", "lr", "ClassificationTrainer", "LogisticRegression",
     {"linear/kernel": (784, 10), "linear/bias": (10,)})])
def test_what_the_programs_preamble_returns(dataset, model, trainer, module,
                                            leaves, process_as_it_was):
    """`common.setup_run` is what `build_api` enters the program through. A
    PR that moves its model-and-trainer half into `build_trainer(args, cfg,
    ds)` (PERF.md section 7) keeps what it returns for a next-word and a
    classifier pair of the CLI's: the trainer, the model with the kwargs the
    dataset gives it, and the parameter tree."""
    import argparse

    import jax

    from fedml_tpu.experiments import common

    args = common.add_args(argparse.ArgumentParser()).parse_args([
        "--dataset", dataset, "--model", model, "--client_num_in_total", "4"])
    cfg, ds, got = common.setup_run(args)
    assert (type(got).__name__, type(got.module).__name__) == (trainer, module)
    assert cfg.lora_rank == 0 and ds.client_num == 4
    if trainer == "NWPTrainer":
        assert got.pad_id == 0 and got.module.per_position is True
    shapes = jax.eval_shape(got.init, jax.random.PRNGKey(0),
                            ds.train.x[0][:2])["params"]
    flat = {"/".join(k.key for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert leaves.items() <= flat.items(), flat


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
    # a matrix applied 16 times a sample, and a kind of the configuration's
    # own that carries its FLOPs (run twice)
    mine = [{"kind": "dense", "cin": 8, "cout": 4, "times": 16},
            {"kind": "attention", "flops": 1000, "times": 2}]
    assert flops.forward_flops_per_sample(mine) == 16 * 64 + 2000
    assert flops.train_flops_per_sample(mine) == 3 * (16 * 64 + 2000)
    with pytest.raises(ValueError, match="attention"):
        flops.forward_flops_per_sample([{"kind": "attention", "times": 2}])


def test_trace_reduction_on_a_synthetic_event_list():
    ev = [(0.0, 1.0), (0.5, 1.0), (3.0, 1.0), (3.2, 0.1)]
    assert trace.busy_union(ev) == pytest.approx(2.5)
    gaps = trace.idle_gaps(ev, 0.0, 5.0)
    assert gaps == [(1.5, 1.5), (4.0, 1.0)]
    host = [("host:round", 0.0, 5.0), ("host:stage_wait", 1.4, 1.7)]
    assert trace.name_gaps(gaps, host) == [["host:stage_wait", 1.5],
                                           ["host:round", 1.0]]
    assert trace.ops_by_name([("a", 1.0), ("b", 3.0), ("a", 1.5)]) == [
        ["b", 3.0, 1], ["a", 2.5, 2]]
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


def test_read_hands_readers_every_op_by_name(tmp_path, monkeypatch):
    """`trace.read` on a profile made by hand: every op of the first chip
    with its self seconds and executions (a kernel's own reader looks its
    kernel up there), the breakdown's ten, and nothing of the second chip."""
    from types import SimpleNamespace as NS

    import jax.profiler

    def ev(name, start_ms, ms):   # a trace's times are whole nanoseconds
        return NS(name=name, start_ns=start_ms * 10 ** 6,
                  duration_ns=ms * 10 ** 6)

    loop = [ev("%while.1 = (f32[2]{0}) while(%p), body=%b", 0, 1000)]
    body = [ev(f"%fusion.{i} = f32[{i + 1}]{{0}} fusion(%p), kind=kLoop",
               5 * i * (i + 1) + i, 10 * (i + 1)) for i in range(12)]
    again = [ev("%fusion.11 = f32[12]{0} fusion(%p), kind=kLoop", 2000, 500)]
    chip0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_round_fn(7)", 0, 1000)]),
        NS(name="XLA Ops", events=loop + body + again)])
    chip1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[ev("%other.1 = f32[1]{0} add()", 0, 9000)])])
    host = NS(name="/host:CPU", lines=[NS(name="t", events=[
        ev("host:round", 0, 3000), ev("not a span", 0, 50000)])])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda path: NS(planes=[host, chip0, chip1]))
    monkeypatch.setattr(trace, "newest", lambda d: "by hand")
    t = trace.read(str(tmp_path), 1)
    ops = {name: (s, n) for name, s, n in t["ops"]}
    assert len(t["ops"]) == 13 and len(t["device_ops"]) == 10
    assert ops["fusion.11 f32[12] kLoop"] == (pytest.approx(0.62), 2)
    assert ops["fusion.0 f32[1] kLoop"] == (pytest.approx(0.01), 1)
    assert ops["while.1 f32[2]"] == (pytest.approx(1.0 - 0.78), 1)
    assert t["device_ops"] == [[n, s] for n, s, _ in t["ops"][:10]]
    assert t["device_ops"][0][0] == "fusion.11 f32[12] kLoop"
    assert t["busy_s"] == pytest.approx(1.5)
    assert t["window_s"] == pytest.approx(3.0)
    assert t["modules"] == [["jit_round_fn", 1, pytest.approx(1.0)]]
    monkeypatch.setattr(trace, "newest", lambda d: None)
    assert trace.read(str(tmp_path), 1) is None


def spans_ctx(slots: dict) -> dict:
    """What a traced run hands `executed_flops_roofline`: a window of rounds
    4..11 traced over 6..8, `slots[r]` on round r's `dispatch` span, and a
    federation whose longest writer has 480 rows, 10 a round, bs 20: at most
    4,800 slots a round; the harness counts 1,000 real rows in each."""
    from types import SimpleNamespace as NS

    from benchmarks.harness.window import WindowTracer

    tracer = WindowTracer(4, 10.0)
    tracer.first, tracer.last, tracer.trace_rounds = 4, 12, (6, 9)
    tracer.spans = [{"name": "dispatch", "round": r, "dur_s": 0.004,
                     "rows": n // 2, "slots": n} for r, n in slots.items()]
    tracer.spans += [{"name": "stage", "round": 7, "dur_s": 0.1,
                      "slots": 10 ** 9},
                     {"name": "dispatch", "round": None, "slots": 10 ** 9}]
    return {"tracer": tracer, "trace": {"busy_s": 0.25, "window_s": 0.3},
            "peaks": {"flops_per_s": {"float32": 1e12}}, "dtype": "float32",
            "train_flops_per_sample": 72_000_000, "chips": 1,
            "cfg": NS(batch_size=20, client_num_per_round=10, epochs=1),
            "counts": np.array([480, 16, 200] * 4),
            "rows_of_round": lambda r: 1000}


def test_executed_flops_from_the_slots_the_program_reports():
    """`kernels.exec_roofline` takes what the device executed from the
    `dispatch` spans of the traced rounds: ragged `slots` (a round stops at
    its cohort's last real batch), epochs already in them; rounds outside
    the trace and spans of other names are left out; without `slots` on any
    span there is nothing to read."""
    ctx = spans_ctx({4: 4800, 5: 3000, 6: 3400, 7: 2200, 8: 4800, 9: 1000,
                     11: 600})
    tracer = ctx["tracer"]
    want = 100.0 * (3400 + 2200 + 4800) * 72e6 / 0.25 / 1e12
    assert readers.executed_flops_roofline(ctx, {}) == pytest.approx(want)
    assert readers.executed_flops_roofline(dict(ctx, peaks=None), {}) is None
    assert readers.executed_flops_roofline(dict(ctx, trace=None), {}) is None
    tracer.trace_rounds = (None, None)
    assert readers.executed_flops_roofline(ctx, {}) is None
    tracer.trace_rounds = (6, 9)
    for s in tracer.spans:
        s.pop("slots")
    assert readers.executed_flops_roofline(ctx, {}) is None


@pytest.mark.parametrize("slots, said", [
    ({6: 1000, 7: 1000, 8: 999}, "2999 executed slots"),
    ({6: 4800, 7: 4800, 8: 4801}, "at most 14400 slots"),
    ({6: 4800, 8: 4801}, "at most 9600 slots")],
    ids=["fewer_than_the_real_rows", "more_than_all_padding",
         "a_round_without_its_span"])
def test_slots_outside_the_harness_own_counts_end_the_run(slots, said):
    """A program that miscounts its slots would move the roofline share with
    no kernel changed: fewer than the real rows the harness counts in the
    traced rounds, or more than every client of every cohort padded to the
    federation's longest, and the run ends. The two counts themselves are
    sound readings."""
    with pytest.raises(SystemExit, match=said):
        readers.executed_flops_roofline(spans_ctx(slots), {})
    for sound in ({6: 1000, 7: 1000, 8: 1000}, {6: 4800, 7: 4800, 8: 4800}):
        assert readers.executed_flops_roofline(spans_ctx(sound), {}) > 0


def resolves_to_files_and_every_moves_is_reported(manifest,
                                                  metric_dirs=(LAYERS,)):
    """Every cell of `manifest` resolves to its files and reports the
    end-to-end metric each of its per-layer metrics moves; a per-layer
    metric's file is looked up in `metric_dirs`, first found."""
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
            found = [p for p in (os.path.join(d, m["name"] + ".json")
                                 for d in metric_dirs) if os.path.exists(p)]
            assert found, (w["name"], m["name"])
            with open(found[0]) as f:
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


@pytest.mark.parametrize("manifest", [REPO, TINY])
def test_every_entry_resolves_to_files_and_every_moves_is_reported(manifest):
    resolves_to_files_and_every_moves_is_reported(manifest)


def test_an_appended_cell_and_metric_come_as_data(tmp_path):
    """What a later PR brings as appended entries and new files, with no
    edit to the harness or to an entry: a per-layer metric on a general
    reader that lists one language-model cell, and a cell cloned from
    another under a new name. The cells that were there keep their
    per-layer metrics, the listed one gains the new metric alone, the new
    cell takes every metric that lists no cell, and the manifest resolves
    as the repo's does."""
    with open(REPO) as f:
        bench = json.load(f)
    before = {w["name"]: run.load_cell(w["name"])["per_layer"]
              for w in bench["workloads"]}
    metric = {"name": "drive.dispatch_p95_ms", "unit": "ms",
              "better": "lower", "source": "program_span",
              "layer": "drive loop", "moves": "train_samples_per_s_chip",
              "workloads": ["dsv2lite_lora.train"]}
    metrics = tmp_path / "layer_metrics"
    metrics.mkdir()
    (metrics / (metric["name"] + ".json")).write_text(json.dumps(
        {**{k: metric[k] for k in ("layer", "unit", "better", "source",
                                   "moves")},
         "reader": "span_percentile_ms",
         "params": {"span": "dispatch", "q": 95}}))
    clone = dict(next(w for w in bench["workloads"]
                      if w["name"] == "kimi_linear_lora.train"),
                 name="kimi_linear_lora.clone")
    bench["per_layer"].append(metric)
    bench["workloads"].append(clone)
    manifest = str(tmp_path / "BENCHMARK.json")
    with open(manifest, "w") as f:
        json.dump(bench, f)

    for cell, was in before.items():
        got = run.load_cell(cell, manifest)["per_layer"]
        assert got == was + ([metric] if cell == "dsv2lite_lora.train"
                             else []), cell
    assert run.load_cell(clone["name"], manifest)["per_layer"] == [
        m for m in bench["per_layer"] if "workloads" not in m]
    resolves_to_files_and_every_moves_is_reported(manifest,
                                                  (str(metrics), LAYERS))


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
