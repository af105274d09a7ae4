"""Fold a graft-trace TRACE.jsonl into a BENCH-style report, optionally
running the perf-regression gate against the newest checked-in BENCH_*.json.

Usage:
  python tools/trace_report.py RUN_DIR/TRACE.jsonl            # fold + print
  python tools/trace_report.py TRACE.jsonl --out report.json  # write report
  python tools/trace_report.py TRACE.jsonl --gate             # exit 1 on a
                                                              # regression
  python tools/trace_report.py RUN_DIR/TRACE.jsonl --profile RUN_DIR/trace
      # + the round program's device seconds by declared scope (a run
      # under --profile_rounds A:B leaves both: its TRACE.jsonl holds the
      # `program_scopes` event the join needs)

The gate (ROADMAP open item 5) compares the trace's measured rounds/s
against the newest BENCH_*.json baseline within --tolerance (default 0.5x,
env PERF_GATE_TOLERANCE), honoring platform/cpu_capped/workload mismatches
by skipping rather than lying. --self-test-throttle F scales the measured
value by F before gating — ci_smoke.sh uses it to prove the gate actually
trips (a gate that cannot fail is not a gate).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fedml_tpu.telemetry.report import (  # noqa: E402
    DEFAULT_TOLERANCE,
    device_by_scope,
    fold,
    load_trace,
    newest_bench,
    run_gate,
)


def _scopes(records, profile_dir: str) -> dict:
    """The join of a profile with the trace's `program_scopes` event, or
    what is missing for it."""
    from benchmarks.harness.trace import read

    said = [r for r in records if r.get("kind") == "program_scopes"]
    if not said:
        return {"error": "no program_scopes event in the trace"}
    if said[-1]["stale"]:
        return {"error": "the executable's text lacks this tree's scopes "
                         "(stale: a compile cache another tree filled)"}
    trace = read(profile_dir, 1)
    if trace is None or not trace["modules"]:
        return {"error": f"no device trace under {profile_dir}"}
    return device_by_scope(said[-1], trace["ops"], trace["modules"][0])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("trace", help="path to a TRACE.jsonl manifest")
    parser.add_argument("--out", default=None,
                        help="write the folded BENCH-style JSON here")
    parser.add_argument("--gate", action="store_true",
                        help="compare rounds/s against the newest "
                             "BENCH_*.json; exit 1 on regression")
    parser.add_argument("--bench-root", default=None,
                        help="directory holding BENCH_*.json baselines "
                             "(default: repo root)")
    parser.add_argument("--tolerance", type=float,
                        default=float(os.environ.get("PERF_GATE_TOLERANCE",
                                                     DEFAULT_TOLERANCE)),
                        help="gate floor as a fraction of baseline rounds/s")
    parser.add_argument("--self-test-throttle", type=float, default=None,
                        help="scale measured rounds/s by this factor before "
                             "gating (CI proves the gate trips)")
    parser.add_argument("--profile", default=None,
                        help="a jax.profiler trace directory of the same "
                             "run: print the round program's device "
                             "seconds by declared scope")
    args = parser.parse_args(argv)

    records = load_trace(args.trace)
    report = fold(records)
    if args.profile:
        report["scopes"] = _scopes(records, args.profile)
    if args.self_test_throttle is not None:
        report["value"] = round(report["value"] * args.self_test_throttle, 4)
        report["throttled_for_self_test"] = args.self_test_throttle
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    print(json.dumps(report))

    if not args.gate:
        return 0
    root = args.bench_root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    baseline = newest_bench(root)
    if baseline is None:
        print("perf-regression gate: SKIP — no BENCH_*.json baseline with a "
              "rounds/s number under", root)
        return 0
    bench_path, bench_parsed = baseline
    ok, skipped, message = run_gate(report, bench_path, bench_parsed,
                                    tolerance=args.tolerance)
    print(message)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
