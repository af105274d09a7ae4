#!/usr/bin/env python3
"""Controls for the limits of `jamba2_lora.train`'s `correct` (PERF.md
section 2): the cell run as `benchmarks/run.py` runs it, with ONE piece of
the program's Jamba decoder computed another way underneath. Each should
read `correct` false at the cell's own sizes; one that reads inside the
sound range is reported as such. Not part of a benchmark run; the reference
in bfloat16 in the program's place (the scan's state rounded too) is
`benchmarks/calibrate.py --control ref:bf16`.

    python benchmarks/probes/jamba_controls.py --control state_reset \
        --seeds 1,2 [--workload jamba2_lora.train] [--seconds 0.5]

  state_reset     the scan's state reset at every chunk boundary of the
                  kernel (`ops/ssm.py` CHUNK): the carry across chunks dropped
  no_inner_norms  the Mamba mixer's dt, B, C norms left out
  no_skip         the skip D x left out
  no_conv_bias    the short convolution's bias left out
  rotary          rotary embedding (theta 10,000) applied to q and k in the
                  attention layer (the model is NoPE)
  ref_bf16        no program: the reference in bfloat16 (the scan's state
                  rounded) against itself, as `calibrate.py --control ref:bf16`

Several controls joined by commas run in one process: the float32
reference of a seed is made once and held for all of them, and the round-0
evaluation is skipped (it feeds no compared number). Prints one JSON line a
seed and control, as calibrate.py does.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

CONTROLS = ("state_reset", "no_inner_norms", "no_skip", "no_conv_bias",
            "rotary")
REFERENCE = "ref_bf16"


def _reset_every_chunk(real):
    """`selective_scan` with its state started afresh every `ssm.CHUNK`
    tokens (read when called): the sequence cut into chunks that become
    sequences of their own."""
    import jax.numpy as jnp

    from fedml_tpu.ops import ssm

    def scan(x, delta, A, B, C):
        b, t, _ = x.shape
        chunk = min(ssm.CHUNK, t)
        pad = -t % chunk
        cut = [jnp.pad(z, ((0, 0), (0, pad), (0, 0))).reshape(
            b * (t + pad) // chunk, chunk, z.shape[-1])
            for z in (x, delta, B, C)]
        y = real(cut[0], cut[1], A, cut[2], cut[3])
        return y.reshape(b, t + pad, -1)[:, :t]

    return scan


def _unnormed(real):
    def inner_norms(p, rank, n, norm):
        real(p, rank, n, norm)       # the norms' scales stay in the variables
        return p[..., :rank], p[..., rank:rank + n], p[..., rank + n:]

    return inner_norms


def _rotated(real):
    import numpy as np

    from fedml_tpu.models.deepseek_v2 import rotary

    def attention(q, k, v, causal, *args, **kwargs):
        t, hd = q.shape[1], q.shape[-1]
        angles = np.outer(np.arange(t, dtype=np.float32),
                          10000.0 ** -(np.arange(0, hd, 2) / hd))
        emb = np.concatenate([angles, angles], axis=-1)
        cos, sin = np.cos(emb), np.sin(emb)
        return real(rotary(q, cos, sin), rotary(k, cos, sin), v, causal,
                    *args, **kwargs)

    return attention


@contextlib.contextmanager
def broken(control: str):
    """While open, the program's Jamba decoder computes `control`'s piece
    the other way."""
    from fedml_tpu.models import jamba as model

    patches = {
        "state_reset": ("selective_scan", _reset_every_chunk),
        "no_inner_norms": ("inner_norms", _unnormed),
        "no_skip": ("skip_and_gate", lambda real: (
            lambda y, x, z, skip: real(y, x, z, skip * 0.0))),
        "no_conv_bias": ("short_conv", lambda real: (
            lambda x, taps, bias: real(x, taps))),
        "rotary": ("flash_attention", _rotated),
    }
    if control not in patches:
        raise ValueError(f"unknown control {control!r}; has {CONTROLS}")
    name, other = patches[control]
    real = getattr(model, name)
    setattr(model, name, other(real))
    try:
        yield
    finally:
        setattr(model, name, real)


@contextlib.contextmanager
def held_reference_without_eval(spec: dict):
    """While open, every run of `spec` skips the round-0 evaluation and
    takes the float32 reference of a seed it has made before."""
    compare = run.comparison(spec)[0]
    made, reference, build = {}, compare.reference, run.build_api

    def once(model, config, w0, data, seed, compute="f32"):
        if compute != "f32":
            return reference(model, config, w0, data, seed, compute)
        if seed not in made:
            made[seed] = reference(model, config, w0, data, seed)
        return made[seed]

    def no_eval(*args):
        api, cfg = build(*args)
        api.evaluate = lambda round_idx, tracer: {}
        return api, cfg

    compare.reference, run.build_api = once, no_eval
    try:
        yield once
    finally:
        compare.reference, run.build_api = reference, build


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="jamba2_lora.train")
    p.add_argument("--manifest", default=None)
    p.add_argument("--control", required=True,
                   help="one of %s or %s, or several joined by commas"
                   % (CONTROLS, REFERENCE))
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
                if control == REFERENCE:
                    r = _reference_in_bf16(spec, seed, reference)
                else:
                    with broken(control):
                        r = run.run_cell(spec, seed, args.seconds, False,
                                         t_start=t0)
                print(json.dumps({
                    "seed": seed, "control": control, "correct": r["correct"],
                    "numbers": r["run"]["numbers"],
                    "total_s": time.perf_counter() - t0}), flush=True)
    return 0


def _reference_in_bf16(spec: dict, seed: int, reference) -> dict:
    """`calibrate.reference_in_place(spec, seed, "bf16")` on the held
    float32 reference."""
    from benchmarks.harness import correct

    config = spec["config"]
    compare, limits, not_compared = run.comparison(spec)
    seed32 = seed % 2 ** 32
    model, data, w0 = run.make_inputs(config, seed32)
    nums = compare.numbers(reference(model, config, w0, data, seed32, "bf16"),
                           reference(model, config, w0, data, seed32))
    nums["compiles_in_window"] = 0.0
    ok, _ = correct.verdict(nums, limits, not_compared)
    return {"correct": ok, "run": {"numbers": nums}}


if __name__ == "__main__":
    sys.exit(main())
