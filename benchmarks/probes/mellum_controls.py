#!/usr/bin/env python3
"""Controls for the limits of `mellum2_lora.train`'s `correct` (PERF.md
section 2): the cell run as `benchmarks/run.py` runs it, with ONE piece of
the program's Mellum decoder computed another way underneath. Each should
read `correct` false at the cell's own sizes; one that reads inside the
sound range is reported as such. Not part of a benchmark run.

    python benchmarks/probes/mellum_controls.py --control no_window \
        --seeds 1,2 [--workload mellum2_lora.train] [--seconds 0.5]

  no_window            the window layers attend causally over every key (the
                       band left out)
  window_half          the band half as wide (512 of 1,024)
  rotary_swapped       YaRN (and its attention factor) on the window layers,
                       the default rotary on the full one
  no_attention_factor  the full layer's YaRN attention factor left out
  top7                 7 experts a token for the published 8
  no_renorm            the top 8 gates as the softmax gives them, not
                       renormalised to sum 1
  ref_bf16, ref_fp8    no program: the reference in bfloat16 (with float8
                       operands) against itself in float32, as
                       `calibrate.py --control ref:bf16` (`ref:fp8`)

Several controls joined by commas run in one process: the float32
reference of a seed is made once and held for all of them, and the round-0
evaluation is skipped (it feeds no compared number): `jamba_controls.py`'s
way. Prints one JSON line a seed and control, as calibrate.py does.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.probes.jamba_controls import (  # noqa: E402
    held_reference_without_eval)

CONTROLS = ("no_window", "window_half", "rotary_swapped",
            "no_attention_factor", "top7", "no_renorm")
REFERENCES = {"ref_bf16": "bf16", "ref_fp8": "fp8"}


def _window(change):
    """`flash_attention` with its band changed by `change` (None stays)."""
    def make(real):
        def attention(*args, window=None, **kwargs):
            return real(*args, window=None if window is None
                        else change(window), **kwargs)
        return attention
    return make


def _configured(**changes):
    """`MellumConfig.from_dict` whose config has `changes` (functions of the
    published config) applied."""
    def make(real):
        def from_dict(cls, d):
            cfg = real.__func__(cls, d)
            return dataclasses.replace(
                cfg, **{k: f(cfg) for k, f in changes.items()})
        return classmethod(from_dict)
    return make


@contextlib.contextmanager
def broken(control: str):
    """While open, the program's Mellum decoder computes `control`'s piece
    the other way."""
    from fedml_tpu.models import mellum as model

    other_kind = {model.WINDOW: model.FULL, model.FULL: model.WINDOW}
    patches = {
        "no_window": (model, "flash_attention", _window(lambda w: None)),
        "window_half": (model, "flash_attention", _window(lambda w: w // 2)),
        "rotary_swapped": (model, "layer_rotary", lambda real: (
            lambda cfg, kind: real(cfg, other_kind[kind]))),
        "no_attention_factor": (model, "layer_rotary", lambda real: (
            lambda cfg, kind: dataclasses.replace(real(cfg, kind),
                                                  attention_factor=1.0))),
        "top7": (model.MellumConfig, "from_dict", _configured(
            num_experts_per_tok=lambda c: c.num_experts_per_tok - 1)),
        "no_renorm": (model.MellumConfig, "from_dict", _configured(
            norm_topk_prob=lambda c: False)),
    }
    if control not in patches:
        raise ValueError(f"unknown control {control!r}; has {CONTROLS}")
    owner, name, other = patches[control]
    kept = owner.__dict__[name]
    setattr(owner, name, other(getattr(owner, name)))
    try:
        yield
    finally:
        setattr(owner, name, kept)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="mellum2_lora.train")
    p.add_argument("--manifest", default=None)
    p.add_argument("--control", required=True,
                   help="one of %s or %s, or several joined by commas"
                   % (CONTROLS, tuple(REFERENCES)))
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=0.5)
    args = p.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    run.configure_cache()
    spec = run.load_cell(args.workload, args.manifest)
    with held_reference_without_eval(spec) as reference:
        for control in args.control.split(","):
            for seed in (int(s) for s in args.seeds.split(",")):
                t0 = time.perf_counter()
                if control in REFERENCES:
                    r = _reference_in(spec, seed, reference,
                                      REFERENCES[control])
                else:
                    with broken(control):
                        r = run.run_cell(spec, seed, args.seconds, False,
                                         t_start=t0)
                print(json.dumps({
                    "seed": seed, "control": control, "correct": r["correct"],
                    "numbers": r["run"]["numbers"],
                    "total_s": time.perf_counter() - t0}), flush=True)
    return 0


def _reference_in(spec: dict, seed: int, reference, compute: str) -> dict:
    """`calibrate.reference_in_place(spec, seed, compute)` on the held
    float32 reference."""
    from benchmarks.harness import correct

    config = spec["config"]
    compare, limits, not_compared = run.comparison(spec)
    seed32 = seed % 2 ** 32
    model, data, w0 = run.make_inputs(config, seed32)
    nums = compare.numbers(reference(model, config, w0, data, seed32, compute),
                           reference(model, config, w0, data, seed32))
    nums["compiles_in_window"] = 0.0
    ok, _ = correct.verdict(nums, limits, not_compared)
    return {"correct": ok, "run": {"numbers": nums}}


if __name__ == "__main__":
    sys.exit(main())
