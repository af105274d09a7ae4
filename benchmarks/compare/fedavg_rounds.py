"""The comparison of a cell that trains FedAvg rounds: what the timed path's
first rounds produced, against the plain reference's rounds
(reference/fedavg.py) from the same weights, data and seed. A configuration
names its comparison (`"compare": "fedavg_rounds"`); run.py needs of it:

    Capture(api, config)   attaches to the API before the one train() call;
                           .followed(w0) -> what the timed path produced,
                           .release() gives the API its own entry back
    reference(model, config, w0, data, seed[, compute]) -> the same, followed
                           by the plain reference
    numbers(prog, ref)     -> {name: value}, held to the config's `limits`

A cell of another kind (an eval cell, a language model) brings a module of
its own beside this one.
"""

from __future__ import annotations

import jax

from benchmarks.harness import correct
from benchmarks.reference import fedavg

numbers = correct.numbers


def _followed(losses, totals, w0, after_first, after_last) -> dict:
    return {"losses": losses, "totals": totals,
            "first": correct.diff_norms(w0, after_first),
            "change": correct.diff_norms(after_last, w0)}


class Capture:
    """Wraps the API's compiled round (the SAME object the window drives) to
    keep what its first `reference_rounds` calls returned: the new global
    model and the round's summed metrics, all still on the device."""

    def __init__(self, api, config: dict):
        self.api, self.inner = api, api.round_fn
        self.rounds = config["reference_rounds"]
        self.variables, self.metrics = [], []
        api.round_fn = self

    def __call__(self, *args):
        out = self.inner(*args)
        if len(self.variables) < self.rounds:
            self.variables.append(out[0])
            self.metrics.append(out[2])
        return out

    def followed(self, w0) -> dict:
        sums = jax.device_get(self.metrics)
        return _followed(
            [float(m["loss_sum"]) / max(float(m["total"]), 1.0)
             for m in sums],
            [float(m["total"]) for m in sums], w0, self.variables[0],
            self.variables[-1])

    def release(self) -> None:
        self.api.round_fn = self.inner
        self.api = self.inner = None
        self.variables, self.metrics = [], []


def reference(model, config: dict, w0, data: dict, seed: int,
              compute: str = "f32") -> dict:
    x, y, counts = data["train"]
    rounds = fedavg.run_rounds(model, config["hyper"], w0, x, y, counts, seed,
                               config["reference_rounds"], compute)
    return _followed([r["loss"] for r in rounds], [r["total"] for r in rounds],
                     w0, rounds[0]["variables"], rounds[-1]["variables"])
