#!/usr/bin/env python3
"""Controls for the limits of `dsv2lite_lora.train`'s `correct` (PERF.md
section 2): the cell run as `benchmarks/run.py` runs it, with ONE piece of
the program's model computed another way underneath. Each has to read
`correct` false at the cell's own sizes. Not part of a benchmark run; the
fifth control, the reference in float8 in the program's place, is
`benchmarks/calibrate.py --control ref:fp8`.

    python benchmarks/probes/dsv2lite_controls.py --control top5 --seeds 1,2
        [--workload dsv2lite_lora.train] [--seconds 0.5]

  top5          one expert a token fewer than the configuration's top-k
  renorm        the chosen experts' weights renormalised to sum to 1
  no_mscale     the softmax scale without YaRN's m^2
  router_bf16   router logits from bfloat16 operands

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

CONTROLS = ("top5", "renorm", "no_mscale", "router_bf16")


@contextlib.contextmanager
def broken(control: str):
    """While open, the program's DeepSeek-V2 decoder computes `control`'s
    piece the other way."""
    import jax.numpy as jnp

    from fedml_tpu.models import deepseek_v2 as model
    from fedml_tpu.ops import moe

    route, scale, logits = (moe.top_k_route, model.softmax_scale,
                            model.router_logits)
    if control == "top5":
        moe.top_k_route = lambda scores, k: route(scores, k - 1)
    elif control == "renorm":
        def renormalised(scores, k):
            gate, idx = route(scores, k)
            return gate / gate.sum(axis=-1, keepdims=True), idx
        moe.top_k_route = renormalised
    elif control == "no_mscale":
        model.softmax_scale = lambda cfg: (
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    elif control == "router_bf16":
        model.router_logits = lambda x, kernel: jnp.dot(
            x.astype(jnp.bfloat16), kernel.astype(jnp.bfloat16)).astype(
                jnp.float32)
    else:
        raise ValueError(f"unknown control {control!r}; has {CONTROLS}")
    try:
        yield
    finally:
        moe.top_k_route, model.softmax_scale, model.router_logits = (
            route, scale, logits)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="dsv2lite_lora.train")
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
