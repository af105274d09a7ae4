"""The comparison of a cell that trains FedAvg rounds over rank-r adapters
on a FROZEN base (`--lora_rank`): `fedavg_rounds` on the adapters alone, plus
`base_gap`, the largest absolute change of any base leaf on the program's
side (limit 0: the base is frozen by construction, and stays bitwise), and,
where the model routes tokens to experts, `pairs_gap`: the worst round's
difference between the (token, expert) pairs the program says it routed
(its `moe_load` metric, summed) and the pairs a dropless model routes for
the sequences it ran (`routed_pairs` of the reference module: every token
to every one of its k experts in every expert layer; limit 0: a capacity
that drops pairs, or another k, shows here exactly, where the gradient's
norms at top-6 of 64 do not).

The plain reference is `reference/fedavg.py::run_rounds`, unchanged, on
`{"params": adapters}`. The base cannot ride in its `variables` (it keeps a
float32 accumulator of every collection and carries what is not `params` as
state through a `where` a step: a 5.68 GB base would be 11.4 GB of
accumulator and a copy a step), and a jitted function that merely closes
over it would compile it in as a literal. So for the call's duration
`fedavg.make_client_update` hands back the same client update under one
more `jax.jit`, of which the base is an ARGUMENT; the reference model's
`apply` reads it from there.
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp

from benchmarks.compare import fedavg_rounds
from benchmarks.harness import correct
from benchmarks.reference import fedavg

BASE = "lora_base"


def _adapters(variables) -> dict:
    return {"params": variables["params"]}


@jax.jit
def _largest_change(a, b):
    return jnp.max(jnp.stack(jax.tree.leaves(jax.tree.map(
        lambda x, y: jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))), a, b))))


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    out = correct.numbers(prog, ref)
    out["base_gap"] = prog["base_gap"]
    if "pairs" in prog and "pairs" in ref:
        out["pairs_gap"] = max(abs(a - b) for a, b in
                               zip(prog["pairs"], ref["pairs"]))
    return out


class Capture(fedavg_rounds.Capture):
    """`fedavg_rounds.Capture`; what it followed is the adapters' change and
    how far the base moved."""

    def followed(self, w0) -> dict:
        kept = self.variables
        base_gap = max(float(_largest_change(w0[BASE], v[BASE])) for v in kept)
        self.variables = [_adapters(v) for v in kept]
        out = super().followed(_adapters(w0))
        self.variables = kept
        out["base_gap"] = base_gap
        if all("moe_load" in m for m in self.metrics):
            out["pairs"] = [float(m["moe_load"].sum()) for m in
                            jax.device_get(self.metrics)]
        return out


def reference(model, config: dict, w0, data: dict, seed: int,
              compute: str = "f32") -> dict:
    x, y, counts = data["train"]
    held = types.SimpleNamespace(base=None)
    apply = model.make_apply(config["sizes"])
    bound = types.SimpleNamespace(
        loss=model.loss,
        apply=lambda variables, *args: apply(
            {"params": variables["params"], BASE: held.base}, *args))
    make = fedavg.make_client_update

    def make_with_base(*args):
        inner = make(*args)

        @jax.jit
        def client_update(base, *rest):
            held.base = base
            try:
                return inner(*rest)
            finally:
                held.base = None

        return lambda *rest: client_update(w0[BASE], *rest)

    fedavg.make_client_update = make_with_base
    try:
        rounds = fedavg.run_rounds(
            bound, config["hyper"], _adapters(w0), x, y, counts, seed,
            config["reference_rounds"], compute)
    finally:
        fedavg.make_client_update = make
    out = fedavg_rounds._followed(
        [r["loss"] for r in rounds], [r["total"] for r in rounds],
        _adapters(w0), rounds[0]["variables"], rounds[-1]["variables"])
    out["base_gap"] = 0.0
    if hasattr(model, "routed_pairs"):
        # the sequences a round RUNS: every step holds a whole batch
        hp, out["pairs"] = config["hyper"], []
        for r in range(config["reference_rounds"]):
            cohort = fedavg.sample_cohort(r, len(counts),
                                          hp["client_num_per_round"])
            b = min(hp["batch_size"], x.shape[1])
            ran = sum(math.ceil(int(n) / b) * b for n in counts[cohort])
            out["pairs"].append(float(model.routed_pairs(
                config["sizes"], ran * hp["epochs"])))
    return out
