#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that fedml_tpu still starts on the chip.

    python chip_smoke.py              one chip: flagship, cross_silo, kernel, kda,
                                      moe_share
    python chip_smoke.py --multichip  four chips: the two mesh paths, each
                                      against the one-chip vmap engine,
                                      and nothing else

One process, which imports jax once and never sets JAX_PLATFORMS or
XLA_FLAGS. It exits non-zero at once unless jax.devices()[0] is a TPU, and a
phase that raises ends the run non-zero: there is no path from a failure to
`"ok": true`. Every phase prints one JSON line; the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The phases drive the entry points a user calls
(`fedml_tpu.experiments.main_fedavg.main`, `ops.attention.flash_attention`)
at the reference's published shapes with seeded surrogate data and seeded
random weights. Each phase is a function of its sizes so that
tests/test_chip_smoke.py can run it tiny on the CPU. The seconds printed
under "smoke_timing" say that the program started and roughly how long the
smoke takes; they are NOT benchmark numbers and belong in no table.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import statistics
import sys
import time

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
#: run directories (TRACE.jsonl, wandb-summary.json) land under the output
#: directory a chip call brings back, so a failed smoke can be read afterwards
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

#: the reference's north-star shape (benchmark/README.md: FedAvg, FEMNIST,
#: CNN_DropOut, 3400 writers, 10 per round, bs 20, E 1, lr 0.1) — cut nowhere
FLAGSHIP_ARGV = [
    "--dataset", "femnist", "--model", "cnn",
    "--client_num_in_total", "3400", "--client_num_per_round", "10",
    "--batch_size", "20", "--epochs", "1", "--lr", "0.1"]
#: the reference's second published shape and the widest conv model the
#: engine vmaps (bf16 is the dtype the cross_silo benchmark cell runs it in)
CROSS_SILO_ARGV = [
    "--dataset", "cifar10", "--model", "resnet56",
    "--client_num_in_total", "10", "--client_num_per_round", "10",
    "--batch_size", "64", "--epochs", "1", "--partition_method", "homo",
    "--dtype", "bfloat16"]

def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> int | None:
    """peak_bytes_in_use of device 0 so far (None where the backend reports
    no memory stats — the CPU the tests run on)."""
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def compiles_after_round(compiles: list[dict],
                         round_span: dict) -> list[float]:
    """Seconds past the end of `round_span` at which each later `compile`
    event (stamped `t` when the compile ended) fell; empty when the program
    had settled by then."""
    t_settled = round_span["t0"] + round_span["dur_s"]
    return [round(c["t"] - t_settled, 3) for c in compiles
            if c["t"] > t_settled]


def read_trace(run_dir: str) -> tuple[dict[str, list[dict]], list[dict]]:
    """The run's TRACE.jsonl: (spans by name, `compile` events)."""
    from fedml_tpu.telemetry.report import load_trace

    spans: dict[str, list[dict]] = {}
    compiles = []
    for rec in load_trace(os.path.join(run_dir, "TRACE.jsonl")):
        if rec.get("type") == "span":
            spans.setdefault(rec["name"], []).append(rec)
        elif rec.get("kind") == "compile":
            compiles.append(rec)
    return spans, compiles


def run_fedavg(phase: str, argv: list[str], rounds: int, run_dir: str,
               min_test_acc: float | None = None,
               test_every: int = 1) -> dict:
    """One in-process `main_fedavg.main(argv)` run and the checks every
    training phase shares: one record per round, everything finite, test
    loss falling, no compile after round 1 (after round 0 where the run
    has only two rounds)."""
    from fedml_tpu.experiments.main_fedavg import main as fedavg_main

    compile_free_after = min(1, rounds - 2)

    argv = argv + ["--comm_round", str(rounds),
                   "--frequency_of_the_test", str(test_every),
                   "--run_dir", run_dir]
    t_enter = time.perf_counter()
    history = fedavg_main(argv)
    t_exit = time.perf_counter()
    # FedAvgAPI holds itself in a cycle (stage_fn is its own bound method),
    # so its device-resident eval splits — gigabytes at 3400 clients — go
    # only when the collector runs; the next phase needs that memory
    gc.collect()

    if [r["round"] for r in history] != list(range(rounds)):
        raise AssertionError(
            f"{phase}: wanted one record for each of {rounds} rounds, got "
            f"rounds {[r['round'] for r in history]}")
    for rec in history:
        bad = {k: v for k, v in rec.items()
               if isinstance(v, float) and not math.isfinite(v)}
        if bad:
            raise AssertionError(f"{phase}: non-finite in round "
                                 f"{rec['round']}: {bad}")
    tested = [r for r in history if "Test/Loss" in r]
    want_tested = sorted({r for r in range(rounds) if r % test_every == 0}
                         | {rounds - 1})
    if [r["round"] for r in tested] != want_tested or any(
            "Train/Loss" not in r for r in tested):
        raise AssertionError(f"{phase}: evaluated rounds "
                             f"{[r['round'] for r in tested]}, wanted "
                             f"{want_tested} each with Train/Loss")
    first, last = tested[0], tested[-1]
    if not last["Test/Loss"] < first["Test/Loss"]:
        raise AssertionError(
            f"{phase}: Test/Loss did not fall: round {first['round']} "
            f"{first['Test/Loss']} -> round {last['round']} "
            f"{last['Test/Loss']}")
    if min_test_acc is not None and not last["Test/Acc"] > min_test_acc:
        raise AssertionError(f"{phase}: Test/Acc {last['Test/Acc']} is not "
                             f"above {min_test_acc}")

    spans, compiles = read_trace(run_dir)
    round_spans = {s["round"]: s for s in spans["round"]}
    if sorted(round_spans) != list(range(rounds)):
        raise AssertionError(f"{phase}: TRACE.jsonl has round spans "
                             f"{sorted(round_spans)}")
    late = compiles_after_round(compiles, round_spans[compile_free_after])
    if late:
        raise AssertionError(
            f"{phase}: {len(late)} compile(s) after round "
            f"{compile_free_after} ended (seconds after: {late})")

    drive_t0 = spans["drive"][0]["t0"]
    later = [round_spans[r]["dur_s"] for r in range(1, rounds)]
    return {
        "phase": phase, "ok": True, "rounds": rounds,
        "first": {k: first[k] for k in ("Train/Loss", "Test/Loss",
                                        "Test/Acc")},
        "last": {k: last[k] for k in ("Train/Loss", "Test/Loss", "Test/Acc")},
        "compiles": len(compiles),
        "compile_s_total": sum(c["dur_s"] for c in compiles),
        "smoke_timing": {
            "data_build_s": spans["data_load"][0]["dur_s"],
            "setup_s": drive_t0 - t_enter,
            "first_round_s": round_spans[0]["dur_s"],
            "later_rounds_median_s": statistics.median(later),
            "later_rounds_s": later,
            "total_s": t_exit - t_enter,
        },
        "peak_bytes_in_use": peak_bytes(),
    }


def flagship_phase(run_dir: str, rounds: int = 6, test_every: int = 5,
                   min_test_acc: float | None = 2.0 / 62,
                   extra_argv: tuple[str, ...] = ()) -> dict:
    """FedAvg on FEMNIST with CNN_DropOut through the CLI's default drive
    loop. Accuracy is held above twice chance (62 classes): the seeded
    surrogate is separable, so a model that learns clears it in a few
    rounds and one that does not stays at 1/62. `extra_argv` is appended
    (argparse: the last value wins) so that the CPU test can shrink the
    population and point --data_dir at tiny files; the chip run passes
    none."""
    return run_fedavg("flagship", FLAGSHIP_ARGV + list(extra_argv), rounds,
                      run_dir, min_test_acc=min_test_acc,
                      test_every=test_every)


def cross_silo_phase(run_dir: str, rounds: int = 6, test_every: int = 5,
                     extra_argv: tuple[str, ...] = ()) -> dict:
    """FedAvg over ten CIFAR-10 silos with ResNet-56 in bf16. Six rounds,
    not two: the eval runs BatchNorm on running statistics, which need a few
    dozen steps to warm up — on the chip Test/Loss rose for rounds 1-2
    (2.386, 2.449, 2.440) and was below its start only from round 4 on
    (2.352, then 2.332 at round 5), the same in every run of this seed."""
    return run_fedavg("cross_silo", CROSS_SILO_ARGV + list(extra_argv),
                      rounds, run_dir, test_every=test_every)


def kernel_phase(shape: tuple[int, int, int, int] = (2, 2048, 8, 64),
                 interpret: bool = False, seed: int = 0) -> dict:
    """The Pallas flash-attention kernel, forward and jax.grad, against the
    plain-jnp reference. `interpret=False` is passed explicitly, so on the
    chip the phase cannot degrade to the interpreter.

    Tolerances: f32 inputs run true-f32 (HIGHEST) MXU passes in the kernel,
    so the reference is computed at highest matmul precision too (XLA's
    default-precision einsum on the TPU drifts ~1e-2 and would be the
    error measured). What is left is summation order over T keys and the
    two exp implementations: 2e-4 absolute on outputs of magnitude ~1, and
    2e-3 on gradients, whose entries sum T products. A wrong mask, scale or
    block index moves either by 1e-1 or more."""
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.attention import attention_reference, flash_attention

    fwd_tol, grad_tol = 2e-4, 2e-3
    rng = np.random.RandomState(seed)
    q, k, v, cot = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
                    for _ in range(4))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 128, 128, interpret)
                       * cot)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, True) * cot)

    fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 128, 128,
                                                  interpret))
    grad = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
    custom_calls = {"fwd": fwd.lower(q, k, v).as_text().count(
                        "tpu_custom_call"),
                    "grad": grad.lower(q, k, v).as_text().count(
                        "tpu_custom_call")}
    if not interpret and not all(custom_calls.values()):
        raise AssertionError(f"kernel: no tpu_custom_call in the lowered "
                             f"text: {custom_calls}")
    t0 = time.perf_counter()
    out = jax.block_until_ready(fwd(q, k, v))
    grads = jax.block_until_ready(grad(q, k, v))
    first_call_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        ref = attention_reference(q, k, v, True)
        ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    fwd_err = float(jnp.max(jnp.abs(out - ref)))
    grad_err = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(grads, ref_grads))
    if out.shape != shape or not (fwd_err < fwd_tol and grad_err < grad_tol):
        raise AssertionError(
            f"kernel: shape {out.shape}, max|out-ref| {fwd_err} (tol "
            f"{fwd_tol}), max|grad-ref| {grad_err} (tol {grad_tol})")
    return {"phase": "kernel", "ok": True, "shape": list(shape),
            "interpret": interpret, "tpu_custom_calls": custom_calls,
            "fwd_max_abs_err": fwd_err, "grad_max_abs_err": grad_err,
            "smoke_timing": {"compile_and_first_call_s": first_call_s},
            "peak_bytes_in_use": peak_bytes()}


def kda_phase(shape: tuple[int, int, int, int] = (2, 512, 4, 128),
              interpret: bool = False, seed: int = 0) -> dict:
    """The Pallas KDA kernels (`ops/kda.py`), forward and jax.grad, against
    the token-by-token recurrence (`kda_reference`, float32 at `highest`) at
    a small shape: four chunks of 128 a head. Both sides are float32, so what
    is left is the order of the sums: 2e-5 absolute on outputs of magnitude
    ~0.1, and 1e-4 of a gradient's largest entry. A wrong decay, mask or
    chunk state moves either by a tenth or more."""
    import jax.numpy as jnp

    from fedml_tpu.ops.kda import kda, kda_reference

    fwd_tol, grad_tol = 2e-5, 1e-4
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, cot = (jax.nn.silu(jax.random.normal(ks[i], shape))
                    for i in range(4))
    g = -4.0 * jax.nn.softplus(jax.random.normal(ks[4], shape) - 3.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], shape[:-1]))
    args = (q, k, v, g, beta)
    fwd = jax.jit(lambda *a: kda(*a, interpret=interpret))
    grad = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda(*a, interpret=interpret) * cot),
        argnums=(0, 1, 2, 3, 4)))
    custom_calls = {"fwd": fwd.lower(*args).as_text().count("tpu_custom_call"),
                    "grad": grad.lower(*args).as_text().count(
                        "tpu_custom_call")}
    if not interpret and not all(custom_calls.values()):
        raise AssertionError(f"kda: no tpu_custom_call in the lowered text: "
                             f"{custom_calls}")
    t0 = time.perf_counter()
    out = jax.block_until_ready(fwd(*args))
    grads = jax.block_until_ready(grad(*args))
    first_call_s = time.perf_counter() - t0
    ref = kda_reference(*args)
    ref_grads = jax.grad(lambda *a: jnp.sum(kda_reference(*a) * cot),
                         argnums=(0, 1, 2, 3, 4))(*args)
    fwd_err = float(jnp.max(jnp.abs(out - ref)))
    grad_err = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                   for a, b in zip(grads, ref_grads))
    if out.shape != shape or not (fwd_err < fwd_tol and grad_err < grad_tol):
        raise AssertionError(
            f"kda: shape {out.shape}, max|out-ref| {fwd_err} (tol {fwd_tol}), "
            f"max|grad-ref| / max|ref| {grad_err} (tol {grad_tol})")
    return {"phase": "kda", "ok": True, "shape": list(shape),
            "interpret": interpret, "tpu_custom_calls": custom_calls,
            "fwd_max_abs_err": fwd_err, "grad_max_abs_err": grad_err,
            "smoke_timing": {"compile_and_first_call_s": first_call_s},
            "peak_bytes_in_use": peak_bytes()}


def moe_share_phase(tokens: int = 2048, d: int = 256, f: int = 128,
                    held: int = 8, routed: int = 32, k: int = 4,
                    tile: int = 128, seed: int = 0) -> dict:
    """A share's routed-expert dispatch (`ops/moe.py::routed_experts_share`:
    `held` of `routed` experts on this chip), forward and jax.grad, against
    every held expert computed on every token and masked, in float32 at
    `highest` from the same bfloat16 values (the dispatch runs in bfloat16,
    as the cells run it). Twice: the router's own top-k, whose held rows fit the
    bounded buffer, and a routing with every pair on a held expert, which no
    bound under the worst case holds and which has to take the exact
    worst-case path: the path no benchmark cell takes is seen on the
    hardware here. The errors are bfloat16's rounding of g, u, h and the rows
    (under a hundredth of the largest entry); a dropped pair or a row read
    from the wrong tile moves them by a tenth or more."""
    import jax.numpy as jnp

    from fedml_tpu.ops import moe

    tol, first = 3e-2, held        # the share after the first
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x, cot, *w = (
        (jax.random.normal(key, shape) * shape[-2] ** -0.5 * scale).astype(
            jnp.bfloat16)
        for key, shape, scale in zip(ks, (
            (tokens, d), (tokens, d), (held, d, f), (held, d, f),
            (held, f, d)), (tokens ** 0.5, tokens ** 0.5, 1.0, 1.0, 1.0)))
    x32, cot32, w32 = (jax.tree.map(lambda a: a.astype(jnp.float32), t)
                       for t in (x, cot, w))
    scores = jax.nn.sigmoid(jax.random.normal(ks[5], (tokens, routed)))
    routings = {"bounded": moe.top_k_route(scores, k),
                "fallback": moe.top_k_route(
                    scores[:, first:first + held], k)}
    routings["fallback"] = (routings["fallback"][0],
                            routings["fallback"][1] + first)

    def dense(x, gate, idx):
        y = 0
        for e in range(held):
            m = ((idx == e + first) * gate).sum(-1)
            y = y + m[:, None] * ((jax.nn.silu(x @ w32[0][e])
                                   * (x @ w32[1][e])) @ w32[2][e])
        return y

    def step(fn):
        def loss(x, gate, idx):
            y, worst = fn(x, gate, idx)
            return jnp.sum(y.astype(jnp.float32) * cot32), (y, worst)
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))

    share = step(lambda x, gate, idx: moe.routed_experts_share(
        x, idx, gate, *w, first, routed, tile))
    plain = step(lambda x, gate, idx: (dense(x, gate, idx), jnp.zeros(())))
    m_b = moe.share_rows(tokens * k, held, routed, tile)
    out = {"phase": "moe_share", "ok": True, "tokens": tokens,
           "held_of_routed": [held, routed], "rows_bounded": m_b,
           "rows_worst": moe._worst_rows(tokens * k, held, tile), "paths": {}}
    t0 = time.perf_counter()
    for name, (gate, idx) in routings.items():
        (_, (y, worst)), (dx, dgate) = jax.block_until_ready(
            share(x, gate, idx))
        # (the reference alone: a bfloat16 operand is no float32 product's,
        # Mosaic refuses the kernel under `highest`)
        with jax.default_matmul_precision("highest"):
            (_, (ry, _)), (rdx, rdgate) = plain(x32, gate, idx)
        errs = {n: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                         / jnp.max(jnp.abs(b)))
                for n, a, b in (("y", y, ry), ("dx", dx, rdx),
                                ("dgate", dgate, rdgate))}
        took = "fallback" if float(worst[0]) else "bounded"
        out["paths"][name] = {
            "took": took, "max_abs_err": errs,
            "held_rows": int(moe._held_rows(idx, held, tile, first))}
        if took != name or not all(e < tol for e in errs.values()):
            raise AssertionError(
                f"moe_share: a routing meant for the {name} path took the "
                f"{took} one with errors {errs} (tol {tol} of the largest "
                f"entry)")
    out["smoke_timing"] = {
        "compile_and_first_calls_s": time.perf_counter() - t0}
    out["peak_bytes_in_use"] = peak_bytes()
    return out


def max_abs_diff(a, b) -> float:
    """Largest |a - b| over two pytrees of arrays, compared on the host
    (the two sides may live on different devices)."""
    import numpy as np

    return max(float(np.max(np.abs(np.asarray(u, np.float64)
                                   - np.asarray(v, np.float64))))
               for u, v in zip(jax.tree.leaves(jax.device_get(a)),
                               jax.tree.leaves(jax.device_get(b))))


#: sharded == single-chip, as tests/test_parallel.py and
#: tests/test_tensor_shard.py state it: 1e-6 on parameters after one round
#: from the same state and cohort, 1e-3 on the round's summed metrics. The
#: tests' sums are ~1e4; at the real sizes loss_sum reaches 1e5, where one
#: f32 ulp is 1.6e-2, so a sum may also differ by 1e-6 of itself (8 ulps).
PARAM_TOL, METRIC_TOL, METRIC_RTOL = 1e-6, 1e-3, 1e-6


def same_round(name: str, mesh_out, vmap_out, hold: bool = True) -> dict:
    """One mesh round against the one-chip vmap round it must equal:
    `total` (sample counts) exactly, always; parameters and the other
    metric sums to the tolerances above where `hold` is set."""
    (g_mesh, m_mesh), (g_vmap, m_vmap) = mesh_out, vmap_out
    m_mesh, m_vmap = jax.device_get((m_mesh, m_vmap))
    out = {"param_max_abs_diff": max_abs_diff(g_mesh, g_vmap),
           "total": float(m_mesh["total"]),
           "metric_abs_diff": {k: abs(float(m_mesh[k]) - float(m_vmap[k]))
                               for k in m_vmap}}
    ok = float(m_mesh["total"]) == float(m_vmap["total"])
    if hold:
        ok = (ok and out["param_max_abs_diff"] < PARAM_TOL and all(
            d < max(METRIC_TOL, METRIC_RTOL * abs(float(m_vmap[k])))
            for k, d in out["metric_abs_diff"].items()))
    if not ok:
        raise AssertionError(
            f"{name}: mesh round != one-chip vmap round: {out} (vmap "
            f"metrics {m_vmap}; tolerances {PARAM_TOL} on parameters, "
            f"max({METRIC_TOL}, {METRIC_RTOL} relative) on metrics)")
    return out


def mesh_vs_one_chip(name: str, mesh_round, vmap_round) -> dict:
    """Both sides twice: at the default matmul precision, which is what a
    user runs, and at "highest".

    At the default an f32 conv or matmul is ONE bf16 pass on the MXU, and
    XLA keeps excess precision wherever it fuses, so two programs of
    different vmap width (3 clients a device against 12 on one) round
    differently: on four v5e chips one flagship round differed by 7e-4 on
    parameters and 2e-4 of loss_sum, where the CPU's f32 gives 6e-8. So the
    default-precision difference is reported and only `total` is held
    there. At "highest" both sides compute in f32, what remains is
    summation order, and the tests' tolerances are held."""
    out = {}
    for precision, ctx in (
            ("default", contextlib.nullcontext()),
            ("highest", jax.default_matmul_precision("highest"))):
        with ctx:
            out[precision] = same_round(f"{name} at {precision} precision",
                                        mesh_round(), vmap_round(),
                                        hold=precision == "highest")
    return out


def multichip_flagship_phase(run_dir: str, rounds: int = 3,
                             extra_argv: tuple[str, ...] = ()) -> dict:
    """The flagship through the CLI's path (`main_fedavg.run` is `main`
    after argument parsing) with --backend shard_map: the cohort of 10 is
    padded to the mesh's multiple (12 on four devices) and sharded over a
    1-D `clients` mesh. Then, for the cohort of each round that ran, one
    mesh round against the one-chip vmap engine from the same trained
    state, seed and staged (padded) cohort — the comparison
    tests/test_parallel.py makes on virtual devices. The padded cohort is
    what both see: the engine's key table is split(rng, C), so a 10-row
    and a 12-row cohort draw different client keys by design."""
    from fedml_tpu.algorithms.engine import build_round_fn
    from fedml_tpu.experiments import main_fedavg
    from fedml_tpu.experiments.common import add_args

    argv = FLAGSHIP_ARGV + list(extra_argv) + [
        "--backend", "shard_map", "--comm_round", str(rounds),
        "--frequency_of_the_test", str(max(1, rounds - 1)),
        "--run_dir", run_dir]
    api, history = main_fedavg.run(
        add_args(argparse.ArgumentParser()).parse_args(argv))
    if [r["round"] for r in history] != list(range(rounds)) or not all(
            math.isfinite(v) for r in history for v in r.values()
            if isinstance(v, float)):
        raise AssertionError(f"mc_flagship: bad history {history}")

    mesh_devices = set(api.mesh.devices.flat)
    if mesh_devices != set(jax.devices()):
        raise AssertionError(f"mc_flagship: the mesh holds {mesh_devices}, "
                             f"the machine {jax.devices()}")
    vmap_round = build_round_fn(api.trainer, api.cfg, api.aggregator,
                                collect_stats=True)
    first = jax.devices()[0]
    gv, st = api.global_variables, api.agg_state
    per_round = []
    for r in range(rounds):
        staged = api.stage_fn(r)
        cohort = (staged.x, staged.y, staged.counts)
        spans = [len(a.sharding.device_set) for a in cohort]
        if not all(set(a.sharding.device_set) == mesh_devices
                   for a in cohort):
            raise AssertionError(
                f"mc_flagship: round {r}'s staged cohort spans {spans} "
                f"device(s) of {len(mesh_devices)} — staging put it on "
                f"{[a.sharding for a in cohort]}")
        rng = jax.random.fold_in(jax.random.PRNGKey(api.cfg.seed), r)
        on_first = jax.device_put((gv, st) + cohort + (rng,), first)

        def mesh_side():
            g, _, m, _ = api.round_fn(gv, st, *cohort, rng)
            return g, m

        def vmap_side():
            g, _, m, _ = vmap_round(*on_first)
            return g, m

        per_round.append({
            "round": r, "cohort_rows": int(staged.x.shape[0]),
            "cohort_devices": spans[0],
            **mesh_vs_one_chip(f"mc_flagship round {r}", mesh_side,
                               vmap_side)})
    return {"phase": "mc_flagship", "ok": True, "rounds": rounds,
            "mesh": dict(api.mesh.shape),
            "first": {k: history[0][k] for k in ("Test/Loss", "Test/Acc")},
            "last": {k: history[-1][k] for k in ("Test/Loss", "Test/Acc")},
            "sharded_vs_one_chip": per_round,
            "peak_bytes_in_use": peak_bytes()}


def multichip_tensor_phase(tensor_shards: int = 2, clients: int = 8,
                           samples: int = 32, seq: int = 64,
                           batch_size: int = 8, seed: int = 0) -> dict:
    """`tensor.round` for TransformerLM at its full registry width (d_model
    128, 4 heads, 2 layers, vocab 10004) on a (clients, tensor) mesh over
    every device — 2 x 2 on four chips — against the one-chip vmap engine:
    tests/test_tensor_shard.py::test_tensor_round_matches_vmap_engine on
    real chips, with seeded random weights and tokens."""
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_round_fn
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.core.trainer import NWPTrainer
    from fedml_tpu.models.registry import create_model
    from fedml_tpu.parallel import TensorSharding, make_tensor_mesh
    from fedml_tpu.parallel.tensor import build_tensor_round_fn

    vocab = 10004
    cfg = FedConfig(model="transformer_nwp", batch_size=batch_size, epochs=1,
                    lr=0.05, client_num_in_total=clients,
                    client_num_per_round=clients, seed=seed)
    trainer = NWPTrainer(create_model("transformer_nwp", output_dim=vocab))
    agg = make_aggregator("fedavg", cfg)
    rng = jax.random.PRNGKey(seed)
    nprng = np.random.RandomState(seed)
    x = jnp.asarray(nprng.randint(1, vocab, (clients, samples, seq)),
                    jnp.int32)
    y = jnp.asarray(nprng.randint(1, vocab, (clients, samples, seq)),
                    jnp.int32)
    counts = jnp.full((clients,), samples, jnp.int32)
    gv = trainer.init(rng, x[0, :2])
    state = agg.init_state(gv)

    mesh = make_tensor_mesh(tensor_shards)
    sharding = TensorSharding.for_model(mesh, "transformer_nwp")
    tensor_round = build_tensor_round_fn(trainer, cfg, agg, sharding,
                                         donate_state=False)
    vmap_round = build_round_fn(trainer, cfg, agg)
    gv_placed, state_placed = sharding.place(gv), sharding.place(state)
    sharded_leaves = []

    def mesh_side():
        g, _, m = tensor_round(gv_placed, state_placed, x, y, counts, rng)
        sharded_leaves.append(sum("tensor" in str(leaf.sharding.spec)
                                  for leaf in jax.tree.leaves(g)))
        return g, m

    def vmap_side():
        g, _, m = vmap_round(gv, state, x, y, counts, rng)
        return g, m

    compared = mesh_vs_one_chip("mc_tensor", mesh_side, vmap_side)
    if not all(sharded_leaves):
        raise AssertionError("mc_tensor: no output leaf is tensor-sharded")
    return {"phase": "mc_tensor", "ok": True, "mesh": dict(mesh.shape),
            "tensor_sharded_leaves": sharded_leaves[0], **compared,
            "peak_bytes_in_use": peak_bytes()}


def preamble() -> None:
    """Cache placement and the packer in use — printed before any phase, so
    a run says which paths it took."""
    from fedml_tpu.native import native_available
    from fedml_tpu.utils.cache import enable_compile_cache

    enabled = enable_compile_cache()
    emit({"phase": "preamble",
          "compile_cache_enabled": enabled,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir,
          "compile_cache_dir_from_env":
              bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          "packer": "native" if native_available() else "numpy-fallback",
          "jax": jax.__version__})


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--multichip", action="store_true",
        help="run ONLY the four-chip paths and what they are compared with")
    args = parser.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU — jax.devices()[0] is {dev}",
              file=sys.stderr)
        return 2
    if args.multichip and dev["count"] != 4:
        print(f"chip_smoke: --multichip needs 4 chips, found {dev['count']}",
              file=sys.stderr)
        return 2
    preamble()
    if args.multichip:
        emit(multichip_flagship_phase(os.path.join(OUT_DIR, "mc_flagship")))
        emit(multichip_tensor_phase())
    else:
        emit(flagship_phase(os.path.join(OUT_DIR, "flagship")))
        emit(cross_silo_phase(os.path.join(OUT_DIR, "cross_silo")))
        emit(kernel_phase())
        emit(kda_phase())
        emit(moe_share_phase())
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
