"""The four cells' round programs, pinned at tiny sizes by PR 34's method
(PERF.md section 6): the sha256 of `jax.make_jaxpr(api.round_fn)` on round
0's cohort, each cell's own configuration with its data cut. A change of
lowering metadata alone (a `jax.named_scope`, PR 40) leaves every hash as it
is; a change of a cell's arithmetic moves its hash, and the PR that makes it
says so and re-pins it here. `dsv2lite_lora` was re-pinned when its expert
layers moved to the one row-space dispatch (`ops/moe.py`, every expert
held); the other three hashes stayed as they were."""

import hashlib
import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PINS = {
    "flagship":
        "b9d9772c20d78bf89316db4d7c7b5e2daa4a3b6a087b9ab185f4fd22b2ebfe78",
    "cross_silo":
        "5271f44fc834b77f139f8b26b2fa05ff7f2c9a3497616074622b3bdf7da6be92",
    "dsv2lite_lora":
        "fbe95401e33fbd57561b9f93f7c364464343cadbe4b59d66358997a3f019766b",
    "kimi_linear_lora":
        "444d729215f3d8ab0e77b5ec6aa967b1f78148056972e4e6dd14568992baf739",
}


def _cell(name: str):
    from benchmarks import run

    if name in ("flagship", "cross_silo"):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               name + ".json")) as f:
            config = json.load(f)
        with open(os.path.join(ROOT, "benchmarks", "traffic",
                               "train.json")) as f:
            traffic = json.load(f)
        if name == "flagship":
            config["data"].update(clients=40, train_rows=1000, test_rows=200,
                                  n_min=4, n_max=80)
            config["argv"] = ["40" if a == "3400" else a
                              for a in config["argv"]]
        else:
            config["data"].update(train_rows=1280, test_rows=320)
        return config, traffic
    spec = run.load_cell(f"tiny_{name}.train", os.path.join(
        ROOT, "tests", "benchmark", "cells", f"tiny_{name}.manifest.json"))
    return spec["config"], spec["traffic"]


@pytest.mark.parametrize("cell", sorted(PINS))
def test_the_round_program_is_the_pinned_jaxpr(cell):
    from benchmarks import run
    from benchmarks.harness import data as bdata

    config, traffic = _cell(cell)
    api, _ = run.build_api(config, traffic, bdata.make(config["data"], 7), 7)
    st = api.stage_fn(0)
    text = str(jax.make_jaxpr(api.round_fn)(
        api.global_variables, api.agg_state, st.x, st.y, st.counts,
        jax.random.PRNGKey(0)))
    assert hashlib.sha256(text.encode()).hexdigest() == PINS[cell]
