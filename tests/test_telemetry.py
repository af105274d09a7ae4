"""graft-trace (ISSUE 6 tentpole): span/event/gauge tracer, the unified
round-record path, and the perf-regression gate.

The pins that matter:
- spans nest and stay monotonic under an injected fake clock;
- every event kind round-trips through the JSONL sink, and malformed emits
  fail loudly at the call site (a ledger with silent holes is not a ledger);
- eager and pipelined drives emit the SAME ledger event sequence for the
  same seed (order-normalized) — telemetry must not observe the async
  plumbing, only the round semantics;
- a guard rollback leaves both the rollback event and the prefetch
  invalidation gauge behind;
- the perf gate trips with a readable diff and skips honestly on
  incomparable environments;
- a depth-2 chaos drive is >=95% span-covered and its ledger counters are
  bit-equal to the history it committed.
"""

import json
import os
import re

import pytest

from fedml_tpu import telemetry
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.trainer import ClassificationTrainer
from fedml_tpu.data.registry import load_dataset
from fedml_tpu.models.registry import create_model
from fedml_tpu.robustness.chaos import FaultPlan
from fedml_tpu.robustness.guard import GuardVerdict
from fedml_tpu.telemetry.report import (
    coverage,
    fold,
    load_trace,
    newest_bench,
    run_gate,
)
from fedml_tpu.telemetry.tracer import EVENT_SCHEMAS, Tracer


@pytest.fixture(scope="module")
def ds8():
    return load_dataset("mnist", client_num_in_total=8,
                        partition_method="homo", seed=0)


def _cfg(comm_round, **kw):
    kw.setdefault("client_num_per_round", 8)
    return FedConfig(dataset="mnist", model="lr", comm_round=comm_round,
                     batch_size=8, lr=0.05, client_num_in_total=8,
                     seed=0, **kw)


def _api(ds, cfg):
    trainer = ClassificationTrainer(create_model("lr", output_dim=ds.class_num))
    return FedAvgAPI(ds, cfg, trainer)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ------------------------------------------------------------ tracer core

def test_span_nesting_and_monotonicity_with_fake_clock():
    clock = _FakeClock()
    t = Tracer(clock=clock)
    with t.round(0):
        clock.t += 1.0
        with t.span("dispatch", 0) as h:
            clock.t += 2.0
            assert h.elapsed() == pytest.approx(2.0)  # queryable while open
        clock.t += 0.5
    inner, = t.find_spans("dispatch")
    outer, = t.find_spans("round")
    assert inner["dur_s"] == pytest.approx(2.0)
    assert outer["dur_s"] == pytest.approx(3.5)
    # the child lies strictly inside the parent interval
    assert outer["t0"] <= inner["t0"]
    assert inner["t0"] + inner["dur_s"] <= outer["t0"] + outer["dur_s"]
    assert inner["thread"] == outer["thread"] == "main"


def test_span_handle_elapsed_tracks_open_span():
    clock = _FakeClock()
    t = Tracer(clock=clock)
    with t.span("round", 7) as h:
        clock.t += 4.25
        assert h.elapsed() == pytest.approx(4.25)


_SAMPLE_EVENTS = {
    "chaos_inject": dict(round=0, dropped=2, nan=1, corrupt=0),
    "guard_verdict": dict(round=0, ok=True, reason=""),
    "guard_rollback": dict(round=1, retry=1),
    "round_committed": dict(round=0, participated_count=6.0),
    "moe_load": dict(round=0, max=431.0, mean=384.0, empty=0),
    "model_built": dict(model="kimi_linear", layers=5,
                        mixers={"kda": 4, "mla": 1}, experts_held=64,
                        experts_routed=256),
    "superstep_committed": dict(round=4, rounds=4, k=4),
    "checkpoint_save": dict(step=5),
    "compile_cache": dict(name="persistent_cache_hit"),
    "compile": dict(dur_s=0.25, round=0, span=3),
    "program_scopes": dict(program="round_fn", instructions=5448, named=4151,
                           scoped={"experts": 519, "moe_layout": 96},
                           stale=False,
                           op_scopes={"fusion.12": "experts/moe_layout"},
                           kernels=["moe_grouped_matmul.145"]),
    "update_admitted": dict(round=3, birth=1, fill=2),
    "buffer_committed": dict(round=3, size=4, staleness_p50=1.0,
                             staleness_max=2.0),
    "download_retry": dict(attempt=0, status="503", backoff_s=1.5),
    "trace_rotated": dict(rotated_to="TRACE.jsonl.000", segment=0, bytes=1024),
    "client_flagged": dict(client=17, reason="quarantine_recidivist", value=3),
    "job_committed": dict(job="tenant-a", rounds=10, wall_s=1.25),
    "job_evicted": dict(job="tenant-a", round=3, reason="preempted"),
    "job_resumed": dict(job="tenant-a", round=3),
    "job_rejected": dict(job="tenant-z", reason="queue_full",
                         slo="throughput"),
    "deadline_miss": dict(job="tenant-a", deadline_s=2.0, latency_s=3.7),
}


def test_every_event_kind_round_trips_through_jsonl(tmp_path):
    assert set(_SAMPLE_EVENTS) == set(EVENT_SCHEMAS)  # keep the fixture total
    path = str(tmp_path / "TRACE.jsonl")
    t = Tracer(jsonl_path=path)
    for kind, fields in _SAMPLE_EVENTS.items():
        t.event(kind, **fields)
    t.close()
    records = load_trace(path)
    assert records[0]["type"] == "meta" and records[0]["version"] == 1
    events = [r for r in records if r["type"] == "event"]
    assert [e["kind"] for e in events] == list(_SAMPLE_EVENTS)
    for e, (kind, fields) in zip(events, _SAMPLE_EVENTS.items()):
        for k, v in fields.items():
            assert e[k] == v
        assert "t" in e and "thread" in e


def test_event_schema_rejects_unknown_kind_and_missing_fields():
    t = Tracer()
    with pytest.raises(ValueError, match="unknown telemetry event kind"):
        t.event("made_up_kind", round=0)
    with pytest.raises(ValueError, match="missing required field"):
        t.event("chaos_inject", round=0, dropped=1)  # nan, corrupt missing
    # graft-slo kinds are schema'd too: a rejection must name its reason
    # and class, an eviction its resume round
    with pytest.raises(ValueError, match="missing required field"):
        t.event("job_rejected", job="t")  # reason, slo missing
    with pytest.raises(ValueError, match="missing required field"):
        t.event("job_evicted", job="t", reason="preempted")  # round missing
    with pytest.raises(ValueError, match="missing required field"):
        t.event("deadline_miss", job="t", deadline_s=1.0)  # latency_s missing


def test_overload_gauges_round_trip(tmp_path):
    """queue_depth / evicted_jobs gauges (scheduler overload telemetry)
    fold through gauge_summary like any other gauge."""
    t = Tracer(jsonl_path=str(tmp_path / "TRACE.jsonl"))
    t.gauge("queue_depth", depth=3)
    t.gauge("queue_depth", depth=5, rejected=1)
    t.gauge("evicted_jobs", count=1, job="tenant-a")
    t.close()
    gs = t.gauge_summary()
    assert gs["queue_depth"]["count"] == 2
    assert gs["queue_depth"]["last"]["depth"] == 5
    assert gs["queue_depth"]["total"]["depth"] == 8
    assert gs["evicted_jobs"]["last"]["job"] == "tenant-a"
    records = load_trace(str(tmp_path / "TRACE.jsonl"))
    names = [r["name"] for r in records if r["type"] == "gauge"]
    assert names == ["queue_depth", "queue_depth", "evicted_jobs"]


def test_events_are_flushed_to_jsonl_before_close(tmp_path):
    """Satellite 6: ledger lines are durable the moment they occur — a crash
    after emit cannot lose them."""
    path = str(tmp_path / "TRACE.jsonl")
    t = Tracer(jsonl_path=path)
    t.event("chaos_inject", round=3, dropped=1, nan=0, corrupt=0)
    with open(path) as f:          # file read while the tracer is still open
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert lines[-1]["kind"] == "chaos_inject" and lines[-1]["round"] == 3
    t.close()


def test_trace_rotation_archives_segments_and_reopens(tmp_path):
    """--trace_max_mb: the sink rotates at the byte cap; the retired file's
    LAST line is the trace_rotated event naming its archive, and the fresh
    segment re-writes the meta record so every file is self-describing."""
    path = str(tmp_path / "TRACE.jsonl")
    t = Tracer(jsonl_path=path, max_bytes=600, run_meta={"model": "lr"})
    for i in range(30):
        t.event("checkpoint_save", step=i)
    t.close()
    archives = sorted(p.name for p in tmp_path.iterdir()
                      if p.name != "TRACE.jsonl")
    assert archives, "no rotation happened under a 600-byte cap"
    assert archives == [f"TRACE.jsonl.{i:03d}" for i in range(len(archives))]
    steps = []
    for name in archives + ["TRACE.jsonl"]:
        records = load_trace(str(tmp_path / name))
        assert records[0]["type"] == "meta" and records[0]["model"] == "lr"
        # cap + the line that crossed it + the trace_rotated marker
        assert os.path.getsize(tmp_path / name) <= 600 + 300
        if name != "TRACE.jsonl":
            last = records[-1]
            assert last["kind"] == "trace_rotated"
            assert last["rotated_to"].endswith(name)
            steps.extend(r["step"] for r in records
                         if r.get("kind") == "checkpoint_save")
        else:
            steps.extend(r["step"] for r in records
                         if r.get("kind") == "checkpoint_save")
    assert steps == list(range(30))  # chained segments lose nothing
    # the in-memory ledger saw the rotation events too
    assert len(t.find_events("trace_rotated")) == len(archives)


def test_trace_rotation_append_mode_counts_existing_bytes(tmp_path):
    path = str(tmp_path / "TRACE.jsonl")
    with open(path, "w") as f:
        f.write("x" * 500 + "\n")
    t = Tracer(jsonl_path=path, mode="a", max_bytes=600)
    t.event("checkpoint_save", step=0)  # pushes past the cap -> rotates
    t.close()
    assert (tmp_path / "TRACE.jsonl.000").exists()


def test_load_trace_skips_truncated_final_line(tmp_path):
    """A run killed mid-write leaves a partial last line; fold() must keep
    the valid prefix and surface the loss as truncated_lines."""
    path = str(tmp_path / "TRACE.jsonl")
    t = Tracer(jsonl_path=path)
    with t.span("drive"):
        with t.round(0):
            pass
    t.event("checkpoint_save", step=0)
    t.close()
    with open(path, "a") as f:
        f.write('{"type": "event", "kind": "round_com')  # the torn write
    records = load_trace(path)
    report = fold(records)
    assert report["truncated_lines"] == 1
    assert report["events"].get("checkpoint_save") == 1  # prefix survived
    assert report["rounds"] == 1


def test_load_trace_clean_file_reports_zero_truncated(tmp_path):
    path = str(tmp_path / "TRACE.jsonl")
    t = Tracer(jsonl_path=path)
    t.event("checkpoint_save", step=0)
    t.close()
    assert fold(load_trace(path))["truncated_lines"] == 0


def test_emit_seam_routes_to_installed_tracer_and_noops_bare():
    telemetry.emit("chaos_inject", round=0, dropped=0, nan=0, corrupt=0)  # no-op
    t = Tracer()
    telemetry.install(t)
    try:
        telemetry.emit("checkpoint_save", step=9)
        telemetry.gauge("prefetch_occupancy", round=0, inflight=2)
    finally:
        telemetry.uninstall(t)
    assert t.find_events("checkpoint_save")[0]["step"] == 9
    assert t.gauges[0]["name"] == "prefetch_occupancy"
    telemetry.emit("checkpoint_save", step=10)          # uninstalled again
    assert len(t.find_events("checkpoint_save")) == 1


def test_summary_table_has_p50_p95_columns():
    clock = _FakeClock()
    t = Tracer(clock=clock)
    for _ in range(4):
        with t.span("dispatch", 0):
            clock.t += 0.25
    table = t.summary_table()
    head, dispatch_row = table.splitlines()[0], table.splitlines()[1]
    for col in ("phase", "count", "total_s", "p50_ms", "p95_ms"):
        assert col in head
    assert dispatch_row.startswith("dispatch")
    assert "250.000" in dispatch_row  # 0.25 s p50 in ms


# ----------------------------------------------- drive-loop instrumentation

def _ledger(tracer, kinds=("chaos_inject", "round_committed")):
    """Order-normalized ledger: the cross-mode equality contract covers
    round semantics, not wall-clock or which thread emitted."""
    events = [{k: v for k, v in e.items() if k not in ("t", "thread")}
              for e in tracer.events if e["kind"] in kinds]
    return sorted(events, key=lambda e: (e["round"], e["kind"]))


def test_eager_and_pipelined_emit_identical_event_sequences(ds8):
    """Same seed, chaos on, guard off (guard retries re-stage cohorts, which
    is legitimately asymmetric): the ledger must not be able to tell the
    drive loops apart."""
    plan = lambda: FaultPlan(seed=3, drop_rate=0.25, nan_rate=0.25)
    te, tp = Tracer(), Tracer()
    _api(ds8, _cfg(4)).train(chaos=plan(), tracer=te)
    _api(ds8, _cfg(4, pipeline_depth=2)).train(chaos=plan(), tracer=tp)
    assert _ledger(te) == _ledger(tp)
    assert len(_ledger(te)) == 8  # one chaos_inject + one commit per round


class _RejectOnce:
    max_retries = 2

    def __init__(self, bad_round=2):
        self.bad_round = bad_round
        self.fired = False

    def inspect(self, round_idx, loss, global_variables=None):
        if round_idx == self.bad_round and not self.fired:
            self.fired = True
            return GuardVerdict(False, "forced test rejection")
        return GuardVerdict(True, "")


def test_guard_rollback_emits_rollback_event_and_invalidate_gauge(ds8):
    t = Tracer()
    api = _api(ds8, _cfg(4, pipeline_depth=2))
    api.train(guard=_RejectOnce(bad_round=2), tracer=t)

    rollback, = t.find_events("guard_rollback")
    assert rollback["round"] == 2 and rollback["retry"] == 1
    verdicts = t.find_events("guard_verdict")
    assert [v["ok"] for v in verdicts].count(False) == 1
    assert {v["round"] for v in verdicts} == {0, 1, 2, 3}
    # the rollback dropped the in-flight cohorts: the invalidation gauge
    # recorded it (close() adds a final dropped=0 invalidation)
    invals = [g for g in t.gauges if g["name"] == "prefetch_invalidate"]
    assert any(g["dropped"] > 0 for g in invals)
    # and every round still committed exactly once
    assert [e["round"] for e in t.find_events("round_committed")] == [0, 1, 2, 3]


def test_pipelined_occupancy_gauges_present(ds8):
    t = Tracer()
    _api(ds8, _cfg(4, pipeline_depth=2)).train(tracer=t)
    occ = [g for g in t.gauges if g["name"] == "prefetch_occupancy"]
    assert len(occ) == 4                      # one per consumed round
    assert all(set(g) >= {"round", "inflight", "ahead_s", "miss"} for g in occ)
    assert any(g["inflight"] > 0 for g in occ)  # the pipeline actually ran ahead


def test_bank_gauges_surface_in_trace_summary(ds8, tmp_path):
    """graft-pfl: a personalized drive's adapter-bank scatters emit the
    bank_rows_materialized / bank_bytes_physical gauges, and both fold
    into gauge_summary and the --trace_summary table."""
    import jax
    import numpy as np

    from fedml_tpu.models.adapter_bank import open_or_create
    from fedml_tpu.models.lora import maybe_wrap_lora

    cfg = _cfg(3, client_num_per_round=4, lora_rank=4, personalize=True)
    trainer = maybe_wrap_lora(
        ClassificationTrainer(create_model("lr", output_dim=ds8.class_num)),
        cfg)
    api = FedAvgAPI(ds8, cfg, trainer)
    tmpl = jax.tree.map(lambda l: np.zeros(l.shape, l.dtype),
                        jax.device_get(api.global_variables["params"]))
    bank = open_or_create(str(tmp_path / "bank"), ds8.client_num, tmpl)
    t = Tracer()
    try:
        api.train(tracer=t, bank=bank)
    finally:
        bank.close()
    gs = t.gauge_summary()
    assert gs["bank_rows_materialized"]["count"] >= 3  # one per scatter
    assert gs["bank_rows_materialized"]["last"]["total_rows"] > 0
    assert gs["bank_bytes_physical"]["last"]["bytes"] > 0
    table = t.summary_table()
    assert "bank_rows_materialized" in table
    assert "bank_bytes_physical" in table
    # the scatter itself is a traced span on the record-flush path
    assert t.find_spans("bank_write") and t.find_spans("bank_gather")


def test_trace_jsonl_written_next_to_checkpoints(ds8, tmp_path):
    """No tracer passed + ckpt_dir given -> the drive owns a tracer whose
    JSONL sink lands next to the checkpoints."""
    d = str(tmp_path / "ckpt")
    _api(ds8, _cfg(2)).train(ckpt_dir=d)
    records = load_trace(os.path.join(d, "TRACE.jsonl"))
    assert records[0]["type"] == "meta"
    kinds = {r["kind"] for r in records if r["type"] == "event"}
    assert "round_committed" in kinds and "checkpoint_save" in kinds
    assert {r["name"] for r in records if r["type"] == "span"} >= {
        "round", "dispatch", "metrics_fetch", "checkpoint"}


def test_depth2_chaos_coverage_and_ledger_matches_history(ds8):
    """The acceptance pins: spans cover >=95% of round wall-clock on a
    depth-2 chaos run, and the committed ledger's robustness counters are
    bit-equal to the history records."""
    t = Tracer()
    api = _api(ds8, _cfg(4, pipeline_depth=2))
    api.train(chaos=FaultPlan(seed=3, drop_rate=0.25, nan_rate=0.25),
              tracer=t)

    assert coverage(t.spans) >= 0.95
    committed = {e["round"]: e for e in t.find_events("round_committed")}
    assert sorted(committed) == [r["round"] for r in api.history]
    for rec in api.history:
        ev = committed[rec["round"]]
        for key in ("participated_count", "quarantined_count",
                    "chaos_dropped", "chaos_nan", "chaos_corrupt"):
            assert ev[key] == rec[key], (key, ev, rec)


# ------------------------------------------------------- fold + perf gate

def test_fold_produces_bench_style_report(ds8, tmp_path):
    path = str(tmp_path / "TRACE.jsonl")
    t = Tracer(jsonl_path=path, run_meta={"model": "lr", "platform": "cpu"})
    _api(ds8, _cfg(3)).train(tracer=t)
    t.close()
    report = fold(load_trace(path))
    assert report["metric"] == "fedavg_drive_rounds_per_sec"
    assert report["rounds"] == 3 and report["value"] > 0
    assert report["coverage"] >= 0.95
    assert report["model"] == "lr" and report["platform"] == "cpu"
    assert report["phases"]["dispatch"]["count"] == 3
    assert report["events"]["round_committed"] == 3


def test_fold_shows_what_the_dispatches_executed(tmp_path):
    """The `dispatch` row of tools/trace_report.py: lanes, mean trip and
    the padding share, from the spans' own work counts."""
    path = str(tmp_path / "TRACE.jsonl")
    t = Tracer(jsonl_path=path)
    for r, (rows, trip) in enumerate([(90, 6), (150, 10)]):
        with t.round(r):
            with t.span("dispatch", r, rows=rows, slots=4 * trip * 5,
                        lanes=4, trip=trip):
                pass
            with t.span("metrics_fetch", r):
                pass
    t.close()
    row = fold(load_trace(path))["phases"]["dispatch"]
    assert row["count"] == 2
    assert (row["lanes"], row["trip_mean"]) == (4, 8.0)
    assert row["padding_pct"] == 25.0      # 240 rows in 320 slots
    assert "lanes" not in fold(load_trace(path))["phases"]["metrics_fetch"]
    # spans of a program that counts no steps (the superstep's): the share
    with Tracer(jsonl_path=path) as t:
        with t.span("dispatch", 0, rows=30, slots=40, rounds=2):
            pass
    row = fold(load_trace(path))["phases"]["dispatch"]
    assert row["padding_pct"] == 25.0 and "lanes" not in row


def test_perf_gate_trips_with_readable_diff():
    report = {"value": 4.0, "platform": "cpu"}
    bench = {"rounds_per_sec": 40.0, "platform": "cpu"}
    ok, skipped, msg = run_gate(report, "/x/BENCH_r05.json", bench,
                                tolerance=0.5)
    assert not ok and not skipped
    assert "FAIL" in msg and "BENCH_r05.json" in msg
    assert "40.00" in msg and "4.00" in msg          # both sides of the diff
    assert "0.10x" in msg and "floor 0.50x" in msg   # ratio vs tolerance
    assert "host sync" in msg                        # actionable hint


def test_perf_gate_passes_within_tolerance():
    report = {"value": 30.0, "platform": "cpu"}
    bench = {"rounds_per_sec": 40.0, "platform": "cpu"}
    ok, skipped, msg = run_gate(report, "/x/BENCH_r05.json", bench,
                                tolerance=0.5)
    assert ok and not skipped and "PASS" in msg


@pytest.mark.parametrize("key,bval,mval", [
    ("platform", "tpu", "cpu"),
    ("cpu_capped", False, True),
    ("model", "cnn", "lr"),
])
def test_perf_gate_skips_on_environment_mismatch(key, bval, mval):
    report = {"value": 0.001, key: mval}             # would fail if compared
    bench = {"rounds_per_sec": 40.0, key: bval}
    ok, skipped, msg = run_gate(report, "/x/BENCH_r06.json", bench)
    assert ok and skipped and "SKIP" in msg and key in msg


def test_newest_bench_prefers_highest_rnn_suffix(tmp_path):
    for name, rps in (("BENCH_r03.json", 10.0), ("BENCH_r11.json", 20.0)):
        with open(tmp_path / name, "w") as f:
            json.dump({"parsed": {"rounds_per_sec": rps}}, f)
    path, parsed = newest_bench(str(tmp_path))
    assert os.path.basename(path) == "BENCH_r11.json"
    assert parsed["rounds_per_sec"] == 20.0


def test_newest_bench_skips_scale_schema_by_name(tmp_path):
    """BENCH_SCALE_* is an RSS curve, never a throughput baseline — even if
    its schema (maliciously) grows a rounds_per_sec key, the gate must skip
    it by NAME and fall through to the real drive bench."""
    with open(tmp_path / "BENCH_SCALE_r99.json", "w") as f:
        json.dump({"parsed": {"rounds_per_sec": 9999.0}}, f)
    with open(tmp_path / "BENCH_r02.json", "w") as f:
        json.dump({"parsed": {"rounds_per_sec": 12.5}}, f)
    path, parsed = newest_bench(str(tmp_path))
    assert os.path.basename(path) == "BENCH_r02.json"
    assert parsed["rounds_per_sec"] == 12.5


def test_newest_bench_skips_shard_schema_by_name(tmp_path):
    """BENCH_SHARD_* is a bytes table from a forced virtual mesh; with only
    that artifact present the gate has NO baseline rather than a bogus one."""
    with open(tmp_path / "BENCH_SHARD_r99.json", "w") as f:
        json.dump({"parsed": {"rounds_per_sec": 9999.0}}, f)
    assert newest_bench(str(tmp_path)) is None


def test_newest_bench_skips_superstep_schema_by_name(tmp_path):
    """BENCH_SUPERSTEP_* is a K-sweep on a shrunk dispatch-bound workload,
    not a drive-throughput baseline. It is skipped by NAME even when its
    arms carry rounds_per_sec numbers; the gate falls through to the real
    drive bench."""
    with open(tmp_path / "BENCH_SUPERSTEP_r99.json", "w") as f:
        json.dump({"parsed": {"rounds_per_sec": 9999.0,
                              "arms": {"0": {"rounds_per_sec": 9999.0}}}}, f)
    assert newest_bench(str(tmp_path)) is None
    with open(tmp_path / "BENCH_r02.json", "w") as f:
        json.dump({"parsed": {"rounds_per_sec": 12.5}}, f)
    path, parsed = newest_bench(str(tmp_path))
    assert os.path.basename(path) == "BENCH_r02.json"
    assert parsed["rounds_per_sec"] == 12.5


def test_newest_bench_skips_pfl_schema_by_name(tmp_path):
    """BENCH_PFL_* is an RSS-vs-rows + gather/scatter-rows/s artifact at
    tiny round counts — never a drive-throughput baseline. Skipped by
    NAME; the gate falls through to the real drive bench."""
    with open(tmp_path / "BENCH_PFL_r99.json", "w") as f:
        json.dump({"parsed": {"rounds_per_sec": 9999.0}}, f)
    assert newest_bench(str(tmp_path)) is None
    with open(tmp_path / "BENCH_r02.json", "w") as f:
        json.dump({"parsed": {"rounds_per_sec": 12.5}}, f)
    path, parsed = newest_bench(str(tmp_path))
    assert os.path.basename(path) == "BENCH_r02.json"
    assert parsed["rounds_per_sec"] == 12.5


def test_newest_bench_skips_buffered_schema_by_name(tmp_path):
    """BENCH_BUFF_* measures committed-updates/s under a synthetic straggler
    barrier, not drive throughput — skipped by NAME like SCALE and SHARD."""
    with open(tmp_path / "BENCH_BUFF_r99.json", "w") as f:
        json.dump({"parsed": {"rounds_per_sec": 9999.0}}, f)
    with open(tmp_path / "BENCH_r02.json", "w") as f:
        json.dump({"parsed": {"rounds_per_sec": 12.5}}, f)
    path, parsed = newest_bench(str(tmp_path))
    assert os.path.basename(path) == "BENCH_r02.json"
    assert parsed["rounds_per_sec"] == 12.5


# --------------------------------------------------- download-retry ledger

def test_download_retry_emits_schema_checked_events(tmp_path):
    """data/acquire retries leave download_retry ledger lines through the
    telemetry seam: attempt index, HTTP code or failure class, and the
    exact backoff actually slept."""
    import urllib.error

    from fedml_tpu.data.acquire import _download
    from fedml_tpu.robustness.retry import RetryPolicy

    calls = {"n": 0}

    def fetcher(url, dst):
        calls["n"] += 1
        if calls["n"] == 1:
            raise urllib.error.HTTPError(url, 503, "unavailable", None, None)
        if calls["n"] == 2:
            raise ConnectionResetError("peer reset")
        open(dst, "wb").close()

    sleeps = []
    t = Tracer()
    telemetry.install(t)
    try:
        _download("http://example.invalid/a", str(tmp_path / "a"),
                  fetcher=fetcher,
                  policy=RetryPolicy(max_attempts=4, base_delay=1.0,
                                     jitter=False, retryable=(OSError,)),
                  sleep=sleeps.append)
    finally:
        telemetry.uninstall(t)
    events = t.find_events("download_retry")
    assert [e["attempt"] for e in events] == [0, 1]
    assert [e["status"] for e in events] == ["503", "ConnectionResetError"]
    assert [e["backoff_s"] for e in events] == sleeps == [1.0, 2.0]
    assert calls["n"] == 3  # third call succeeded — no further retries


# ------------------------------------------- span identity and work counts

def test_span_ids_are_unique_and_parent_is_innermost_open_span():
    clock = _FakeClock()
    t = Tracer(clock=clock)
    with t.round(5) as r:
        with t.span("stage_wait", 5) as a:
            with t.span("inner", 5) as b:
                assert t.open_span() == (b.id, 5)
        with t.span("dispatch", 5) as c:
            pass
    with t.span("drive") as d:
        assert t.open_span() == (d.id, None)
    assert t.open_span() == (None, None)
    by = {s["name"]: s for s in t.spans}
    assert len({s["id"] for s in t.spans}) == len(t.spans) == 5
    assert by["round"]["parent"] is None and by["drive"]["parent"] is None
    assert by["stage_wait"]["parent"] == by["dispatch"]["parent"] == r.id
    assert by["inner"]["parent"] == a.id and by["dispatch"]["id"] == c.id


def test_self_time_is_duration_minus_union_of_same_thread_children():
    clock = _FakeClock()
    t = Tracer(clock=clock)
    with t.span("round", 0) as r:
        clock.t += 1.0                       # self
        with t.span("a", 0):
            clock.t += 2.0
        with t.span("b", 0):
            clock.t += 3.0
            with t.span("grandchild", 0):    # its parent's, not the round's
                clock.t += 1.0
        clock.t += 0.5                       # self
    span, = t.find_spans("round")
    assert span["dur_s"] == pytest.approx(7.5)
    assert t.self_time(span) == pytest.approx(1.5)
    b, = t.find_spans("b")
    assert t.self_time(b) == pytest.approx(3.0)
    # two children that overlap count once; a child on another thread (the
    # stager working for this round) takes nothing
    for name, dur, thread in (("x", 2.0, "main"), ("stage", 7.5, "stager")):
        t.spans.append({"name": name, "thread": thread, "t0": 0.0,
                        "dur_s": dur, "id": -1, "parent": r.id})
    assert t.self_time(span) == pytest.approx(0.5)


def test_adopted_parent_crosses_threads():
    import threading

    t = Tracer()
    telemetry.install(t)
    try:
        with t.span("round", 3) as r:
            cause = telemetry.open_span_id()

            def work():
                with telemetry.adopt(cause):
                    with t.span("stage", 4):
                        with t.span("bank_gather", 4):
                            pass

            th = threading.Thread(target=work)
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
    finally:
        telemetry.uninstall(t)
    by = {s["name"]: s for s in t.spans}
    assert cause == r.id == by["stage"]["parent"]
    assert by["bank_gather"]["parent"] == by["stage"]["id"]


@pytest.mark.parametrize("depth", [0, 2])
def test_stage_spans_name_the_span_that_scheduled_them(ds8, depth):
    """Eager: `stage` is a child of its own round. Pipelined: a stager-thread
    `stage` names the round span whose prefetch() scheduled it (an earlier
    round), or the `stage_wait` of the get() that found it missing."""
    t = Tracer()
    _api(ds8, _cfg(5, pipeline_depth=depth)).train(tracer=t)
    by_id = {s["id"]: s for s in t.spans}
    assert all("id" in s and "parent" in s for s in t.spans)
    stages = t.find_spans("stage")
    assert sorted(s["round"] for s in stages) == list(range(5))
    for s in stages + t.find_spans("h2d"):
        cause = by_id[s["parent"]]
        if depth == 0:
            assert (s["thread"], cause["name"], cause["round"]) == (
                "main", "round", s["round"])
        else:
            assert s["thread"] == "stager" and cause["thread"] == "main"
            assert cause["name"] in ("round", "stage_wait")
            assert cause["round"] <= s["round"]
    if depth:
        ahead = [s for s in stages if by_id[s["parent"]]["name"] == "round"]
        assert ahead and all(by_id[s["parent"]]["round"] < s["round"]
                             for s in ahead)
        # the drive loop closes: a round is its children plus its self time
        for r in t.find_spans("round"):
            kids = sum(s["dur_s"] for s in t.spans if s["parent"] == r["id"]
                       and s["thread"] == "main")
            assert kids + t.self_time(r) == pytest.approx(r["dur_s"])


@pytest.mark.parametrize("epochs", [1, 2])
def test_work_counts_match_numpy(ds8, epochs):
    from fedml_tpu.algorithms.engine import round_slots
    from fedml_tpu.algorithms.fedavg import client_sampling

    cfg = _cfg(3, client_num_per_round=3, pipeline_depth=2, epochs=epochs)
    t = Tracer()
    _api(ds8, cfg).train(tracer=t)
    n_max = ds8.train.x.shape[1]
    for r in range(3):
        idx = client_sampling(r, 8, 3)
        x, y, counts = ds8.train.select(idx)
        h2d, = t.find_spans("h2d", r)
        dispatch, = t.find_spans("dispatch", r)
        assert h2d["bytes"] == x.nbytes + y.nbytes + counts.nbytes
        assert dispatch["rows"] == int(counts.sum()) * epochs
        # the vmap engine stops at the cohort's last real batch
        assert dispatch["slots"] == round_slots(cfg, 3, n_max, counts)
        assert dispatch["rows"] <= dispatch["slots"] <= round_slots(
            cfg, 3, n_max)


class _BatchSpy:
    """Stands where a trainer stands in the epoch function and notes the
    batch it is handed while the function is traced."""

    def __init__(self):
        self.batch = None

    def loss_fn(self, variables, batch, rng, train):
        import jax.numpy as jnp

        self.batch = batch["x"].shape[0]
        loss = jnp.sum(variables["params"]["w"]) * jnp.sum(batch["mask"])
        return loss, ({}, {"total": jnp.sum(batch["mask"])})


@pytest.mark.parametrize("n_max,batch_size", [(480, 20), (50, 64), (37, 8)])
def test_round_slots_is_what_the_epoch_function_pads_to(n_max, batch_size):
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.algorithms.engine import _build_epoch_fn, round_slots

    import dataclasses

    cfg = dataclasses.replace(_cfg(1, epochs=3), batch_size=batch_size)
    spy = _BatchSpy()
    opt = optax.sgd(0.1)
    params = {"w": jnp.zeros(2)}
    variables = {"params": params}
    carry = (variables, opt.init(params), jnp.zeros((), jnp.int32))
    _, auxs = jax.eval_shape(
        _build_epoch_fn(spy, cfg, opt), params, carry,
        jnp.zeros((n_max, 4)), jnp.zeros((n_max,), jnp.int32),
        jnp.asarray(n_max - 1), jax.random.PRNGKey(0))
    steps, = auxs["total"].shape
    assert steps * spy.batch >= n_max
    assert round_slots(cfg, 7, n_max) == 7 * steps * spy.batch * 3


@pytest.mark.parametrize("drive", [
    {}, {"pipeline_depth": 2}, {"rounds_per_dispatch": 2},
    {"buffer_size": 2}])
def test_every_drive_loop_evaluates_under_one_eval_span(ds8, drive):
    """Eager, pipelined, superstep and buffered drives open a test round's
    `eval` span through FedAvgAPI.evaluate(): one span a test round, a child
    of its round, and the one-off transfer of the resident splits its child."""
    t = Tracer()
    hist = _api(ds8, _cfg(4, frequency_of_the_test=2, **drive)).train(tracer=t)
    by_id = {s["id"]: s for s in t.spans}
    evals = t.find_spans("eval")
    tested = [rec["round"] for rec in hist if "Test/Acc" in rec]
    assert sorted(e["round"] for e in evals) == tested and len(tested) >= 2
    for e in evals:
        assert (by_id[e["parent"]]["name"], e["thread"]) == ("round", "main")
    sent, = t.find_spans("eval_h2d")
    first = min(evals, key=lambda e: e["t0"])
    assert sent["parent"] == first["id"] and sent["dur_s"] <= first["dur_s"]


def test_compile_events_name_the_round_and_span_they_fell_in(ds8):
    t = Tracer()
    with t.span("dispatch", 3) as h:
        t.compile_event(0.5)
    t.compile_event(0.25)
    inside, outside = t.find_events("compile")
    assert (inside["dur_s"], inside["round"], inside["span"]) == (0.5, 3, h.id)
    assert (outside["round"], outside["span"]) == (None, None)

    t = Tracer()
    _api(ds8, _cfg(2, pipeline_depth=2)).train(tracer=t)
    by_id = {s["id"]: s for s in t.spans}
    compiles = t.find_events("compile")
    assert compiles and all(e["dur_s"] >= 0 for e in compiles)
    # a fresh API's round program compiles (or is served) in round 0's
    # dispatch; nothing compiles once the program has settled
    assert any(e["round"] == 0 and by_id[e["span"]]["name"] == "dispatch"
               for e in compiles)
    assert all(e["round"] in (None, 0) for e in compiles)
    for e in compiles:
        if e["span"] is not None and by_id[e["span"]]["round"] is not None:
            assert by_id[e["span"]]["round"] == e["round"]


def test_lowered_programs_hold_the_scope_names(ds8):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import (build_client_eval_fn,
                                             build_round_fn)
    from fedml_tpu.codecs import make_codec

    cfg = _cfg(1, client_num_per_round=3, update_codec="int8")
    trainer = ClassificationTrainer(create_model("lr", output_dim=10))
    round_fn = build_round_fn(trainer, cfg, make_aggregator("fedavg", cfg),
                              collect_stats=True,
                              codec=make_codec("int8", cfg))
    x, y, counts = ds8.train.select([0, 1, 2])
    gv = trainer.init(jax.random.PRNGKey(0), jnp.asarray(x[:1, 0]))
    from fedml_tpu.core.builder import wrap_codec

    state = wrap_codec(make_aggregator("fedavg", cfg),
                       make_codec("int8", cfg), 3).init_state(gv)
    text = round_fn.lower(gv, state, x, y, counts, jax.random.PRNGKey(1),
                          jnp.ones(3, bool)).as_text(debug_info=True)
    for scope in ("cohort_stats", "quarantine", "codec", "aggregate"):
        assert re.search(rf"[/(]{scope}[/)]", text), scope
    # the client update carries no scope of its own (the trace of a deep
    # model paid seconds for one): it is what lies outside the others
    assert not re.search(r"[/(]local_update[/)]", text)
    text = build_client_eval_fn(trainer).lower(
        gv, x, y, counts).as_text(debug_info=True)
    assert "vmap(client_eval)/" in text


def test_profile_window_drains_before_it_starts_and_before_it_stops(
        ds8, monkeypatch):
    """The pipelined host runs rounds ahead of the device: without the
    drains the trace of rounds [1, 3) would hold other rounds' device work."""
    import jax

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda log_dir, **kw: calls.append(
            ("start", kw["profiler_options"].python_tracer_level)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    t = Tracer(profile_rounds="1:3", profile_dir="/unused")
    t.drain_fn = lambda: calls.append(("drain",))
    for r in range(4):
        with t.round(r):
            calls.append(("round", r))
    assert calls == [("round", 0), ("drain",), ("start", 0), ("round", 1),
                     ("round", 2), ("drain",), ("stop",), ("round", 3)]

    # train() lends the tracer its drain for the drive, and takes it back
    class Peek(Tracer):
        def round(self, round_idx):
            self.lent = self.drain_fn is not None
            return super().round(round_idx)

    t = Peek()
    _api(ds8, _cfg(1, pipeline_depth=2)).train(tracer=t)
    assert t.lent and t.drain_fn is None
