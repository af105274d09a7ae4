#!/usr/bin/env python3
"""Controls for the limits of `kimi_linear_lora.train`'s `correct` (PERF.md
section 2): the cell run as `benchmarks/run.py` runs it, with ONE piece of
the program's model computed another way underneath. Each should read
`correct` false at the cell's own sizes; one that reads inside the sound
range is reported as such. Not part of a benchmark run; the reference in
bfloat16 in the program's place (KDA's decay and state rounded too) is
`benchmarks/calibrate.py --control ref:bf16`.

    python benchmarks/probes/kimi_linear_controls.py --control no_decay \
        --seeds 1,2 [--workload kimi_linear_lora.train] [--seconds 0.5]

  no_decay   KDA's decay left out: alpha = 1 (g = 0)
  beta1      KDA's write strength beta = 1
  no_conv    KDA's short convolutions left out (SiLU of the projection)
  softmax    router scores by softmax in sigmoid's place
  no_bias    the experts selected by score, without the selection bias
  no_renorm  the chosen experts' weights not renormalised
  rotary     rotary embedding applied in MLA (the model is NoPE)
  top7       one expert a token fewer than the configuration's top-k

Prints one JSON line a seed, as calibrate.py does.
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

CONTROLS = ("no_decay", "beta1", "no_conv", "softmax", "no_bias",
            "no_renorm", "rotary", "top7")


@contextlib.contextmanager
def broken(control: str):
    """While open, the program's Kimi Linear decoder computes `control`'s
    piece the other way."""
    import jax.numpy as jnp

    from fedml_tpu.models import deepseek_v2 as shared
    from fedml_tpu.models import kimi_linear as model
    from fedml_tpu.ops import moe

    cfg = model.KimiLinearConfig
    # [(owner, attribute, the other way), ...]
    patches = {
        # g = 0 as a product with the traced g, not a constant: the compiler
        # folds a constant's cumulative sum of 268 MB on the host for half
        # an hour (PERF.md section 6)
        "no_decay": [(model, "log_decay",
                      lambda *a, real=model.log_decay: real(*a) * 0.0)],
        "beta1": [(model, "write_strength",
                   lambda x: jnp.ones(x.shape, jnp.float32))],
        "no_conv": [(model, "short_conv", lambda x, taps: x)],
        "softmax": [(cfg, "scoring_func", "softmax")],
        "no_bias": [(shared, "biased_route",
                     lambda scores, bias, k: moe.top_k_route(scores, k))],
        "no_renorm": [(cfg, "norm_topk_prob", False)],
        # plain rotary, as `rope_scaling` null means it: the configuration
        # class has no rope values of its own (the model is NoPE)
        "rotary": [(cfg, "rotary", True), (cfg, "rope_factor", 1.0),
                   (cfg, "rope_original", 4096), (cfg, "rope_beta_fast", 32.0),
                   (cfg, "rope_beta_slow", 1.0), (cfg, "rope_mscale", 1.0),
                   (cfg, "rope_mscale_all_dim", 1.0)],
        "top7": [(moe, "top_k_route",
                  lambda scores, k, route=moe.top_k_route: route(scores, k - 1))],
    }
    if control not in patches:
        raise ValueError(f"unknown control {control!r}; has {CONTROLS}")
    todo, absent = patches[control], object()
    was = [owner.__dict__.get(name, absent) for owner, name, _ in todo]
    for owner, name, other in todo:
        setattr(owner, name, other)
    try:
        yield
    finally:
        for (owner, name, _), old in zip(todo, was):
            if old is absent:
                delattr(owner, name)
            else:
                setattr(owner, name, old)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="kimi_linear_lora.train")
    p.add_argument("--manifest", default=None)
    p.add_argument("--control", required=True, choices=CONTROLS)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=0.5)
    args = p.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    run.configure_cache()
    spec = run.load_cell(args.workload, args.manifest)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        with broken(args.control):
            r = run.run_cell(spec, seed, args.seconds, False, t_start=t0)
        print(json.dumps({
            "seed": seed, "control": args.control, "correct": r["correct"],
            "numbers": r["run"]["numbers"],
            "total_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
