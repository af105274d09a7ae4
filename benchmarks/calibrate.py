#!/usr/bin/env python3
"""Readings for the limits of `correct` (PERF.md section 2): the compared
numbers of one cell over many seeds in ONE process. Not part of a benchmark
run; the controls and the planted faults live here and in the tests, around
run.py and not in it.

    python benchmarks/calibrate.py --workload W --seeds 1,2,3 [--seconds 0.5]
        [--control bfloat16 | precision:high | ref:fp8]
        [--fault half_batch | state_unchanged]

  --control bfloat16       the program's own lower-precision path (`--dtype`)
  --control precision:P    the program's float32 products at JAX precision P
  --control ref:C          no program: the reference computed in C (bf16,
                           fp8) in the program's place, against the reference
  --fault F                the timed path broken underneath (`break_round`)

Prints one JSON line a seed: every number read, compared or not (`correct`
is what the committed limits say of them), the metrics of the short window
and the run's own record.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

FAULTS = ("state_unchanged", "half_batch")


@contextlib.contextmanager
def break_round(fault: str):
    """While open, every API that run.py builds has its compiled round broken
    underneath: `state_unchanged` hands the global model back as it came,
    `half_batch` leaves half of every client's rows out (the mean is then
    taken over the rest)."""
    build = run.build_api

    def broken(*args):
        api, cfg = build(*args)
        inner = api.round_fn

        def round_fn(gv, agg_state, x, y, counts, *rest):
            if fault == "half_batch":
                counts = counts // 2
            out = inner(gv, agg_state, x, y, counts, *rest)
            if fault == "state_unchanged":
                out = (gv,) + tuple(out[1:])
            return out

        api.round_fn = round_fn
        return api, cfg

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    run.build_api = broken
    try:
        yield
    finally:
        run.build_api = build


def with_control(spec: dict, control: str) -> dict:
    """The cell's files as the control runs them."""
    spec = copy.deepcopy(spec)
    if control.startswith("precision:"):
        spec["config"]["matmul_precision"] = control.split(":", 1)[1]
    elif control:
        spec["config"]["argv"] += ["--dtype", control]
    return spec


def reference_in_place(spec: dict, seed: int, compute: str) -> dict:
    """The reference computed in `compute`, put in the program's place and
    held against the reference proper. -> {"correct", "compared"}."""
    from benchmarks.harness import correct

    config = spec["config"]
    compare, limits, not_compared = run.comparison(spec)
    seed32 = seed % 2 ** 32
    model, data, w0 = run.make_inputs(config, seed32)
    nums = compare.numbers(
        compare.reference(model, config, w0, data, seed32, compute),
        compare.reference(model, config, w0, data, seed32))
    nums["compiles_in_window"] = 0.0
    ok, compared = correct.verdict(nums, limits, not_compared)
    return {"correct": ok, "compared": compared, "run": {"numbers": nums}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=0.5)
    p.add_argument("--control", default="")
    p.add_argument("--fault", default="")
    args = p.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    run.configure_cache()
    spec = run.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if args.control.startswith("ref:"):
            r = reference_in_place(spec, seed, args.control[4:])
        else:
            with (break_round(args.fault) if args.fault
                  else contextlib.nullcontext()):
                r = run.run_cell(with_control(spec, args.control), seed,
                                 args.seconds, False, t_start=t0)
        print(json.dumps({
            "seed": seed, "control": args.control, "fault": args.fault,
            "correct": r["correct"],
            "numbers": r["run"]["numbers"],
            "metrics": {k: v["value"]
                        for k, v in r.get("metrics", {}).items()},
            "run": r.get("run"),
            "total_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
