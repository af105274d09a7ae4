"""The vmap engine's step loop stops at the cohort's last real batch.

`engine._vmapped_update` hands `local_update` the traced scalar
`live_steps(counts)` and the epoch runs a `while` over steps [0, live) in
place of the static `nb`-step scan. Every step it leaves out is all-padding
for every client (zero masked loss, zero gradients, `has_data` false), so the
round must come out BITWISE as the full-length loop gives it — and stay one
compiled program whatever the cohort's largest client is.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.aggregators import make_aggregator
from fedml_tpu.algorithms.engine import (
    build_local_update,
    build_round_fn,
    build_round_fn_from_update,
    epoch_batches,
    live_steps,
    round_slots,
)
from fedml_tpu.analysis.jaxpr_engine import walk_eqns
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.trainer import ClassificationTrainer
from fedml_tpu.models.linear import DenseMLP

CLIENTS, N, BS, D, CLASSES = 4, 24, 5, 6, 3  # nb = 5, the last batch 4 rows
RAGGED = (11, 3, 7, 9)  # live = 3 of 5


class _NormDropMLP(nn.Module):
    """BatchNorm (state that padded rows would pollute) + dropout (per-step
    keys that must not move)."""

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Dense(8)(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
        x = nn.Dropout(0.25, deterministic=not train)(nn.relu(x))
        return nn.Dense(CLASSES)(x)


def _static_round_fn(trainer, cfg, aggregator):
    """`build_round_fn` forced to `live=None`: the same local update under
    the same vmap, every one of the nb steps executed."""
    local_update = build_local_update(trainer, cfg)

    def batched(global_variables, x, y, counts, crngs):
        return jax.vmap(local_update, in_axes=(None, 0, 0, 0, 0))(
            global_variables, x, y, counts, crngs)

    return build_round_fn_from_update(batched, aggregator)


def _setup(model="mlp", aggregator="fedavg", **cfg_kw):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(CLIENTS, N, D).astype(np.float32))
    y = jnp.asarray(rng.randint(0, CLASSES, (CLIENTS, N)).astype(np.int32))
    cfg = FedConfig(**{**dict(batch_size=BS, epochs=1, lr=0.1,
                              client_num_per_round=CLIENTS,
                              server_optimizer="adam", server_lr=0.05),
                       **cfg_kw})
    module = (_NormDropMLP() if model == "bn"
              else DenseMLP(output_dim=CLASSES, hidden=(8,)))
    trainer = ClassificationTrainer(module)
    gv = trainer.init(jax.random.PRNGKey(1), x[0, :1])
    return cfg, trainer, gv, make_aggregator(aggregator, cfg), x, y


def _setup_case(case):
    """-> (_setup(...) of the case, its counts)."""
    spec = CASES[case]
    return (_setup(spec.get("model", "mlp"), spec.get("aggregator", "fedavg"),
                   **spec.get("cfg", {})), spec.get("counts", RAGGED))


def _run(round_fn, gv, agg, x, y, counts, rounds):
    """`rounds` rounds, the counts rolled one client on each round so that
    one compiled program sees the real rows move."""
    state = agg.init_state(gv)
    counts = np.asarray(counts, np.int32)
    for r in range(rounds):
        gv, state, metrics = round_fn(
            gv, state, x, y, jnp.asarray(np.roll(counts, r)),
            jax.random.fold_in(jax.random.PRNGKey(7), r))
    return gv, state, metrics


def _assert_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for u, v in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def _assert_same_metrics(a, b):
    """Counts are whole numbers and come out bitwise. `loss_sum` is the same
    per-step terms, added one by one in the loop where the scan form leaves
    a stacked [nb] array to an XLA reduce whose order is the backend's: the
    last bit may differ (it does on the CPU, one case in six)."""
    assert a.keys() == b.keys()
    for k in a:
        if k == "loss_sum":
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       rtol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


CASES = {
    # the flagship's stateless path: zero gradients alone make the no-op
    "sgd_clip": dict(),
    "momentum_wd": dict(cfg=dict(momentum=0.9, wd=1e-3), aggregator="fedopt"),
    "adam": dict(cfg=dict(client_optimizer="adam", wd=1e-3),
                 aggregator="fedopt"),
    "fedprox": dict(cfg=dict(fedprox_mu=0.1)),
    "batchnorm": dict(model="bn"),
    "batchnorm_momentum": dict(model="bn", cfg=dict(momentum=0.9)),
    "no_shuffle": dict(cfg=dict(shuffle=False)),
    "epochs2": dict(cfg=dict(epochs=2, momentum=0.9)),
    "full_batch": dict(cfg=dict(batch_size=-1)),
    "zero_count_client": dict(counts=(11, 0, 7, 9)),
    "all_zero": dict(counts=(0, 0, 0, 0)),  # live = 0
    "one_at_n_max": dict(counts=(N, 3, 7, 9)),  # live == nb
}


@pytest.mark.parametrize("rounds", [1, 3])
@pytest.mark.parametrize("case", CASES)
def test_live_round_is_bitwise_the_full_length_round(case, rounds):
    (cfg, trainer, gv, agg, x, y), counts = _setup_case(case)
    live = _run(build_round_fn(trainer, cfg, agg), gv, agg, x, y, counts,
                rounds)
    static = _run(_static_round_fn(trainer, cfg, agg), gv, agg, x, y, counts,
                  rounds)
    # global variables (BatchNorm statistics among them), aggregator state
    _assert_bitwise(live[:2], static[:2])
    _assert_same_metrics(live[2], static[2])
    # (a cohort with no rows at all aggregates to what the aggregator makes
    # of zero weights, the same in both forms: the update-level test below
    # shows every client handing back the global model)
    if case != "all_zero":
        assert all(not np.array_equal(np.asarray(u), np.asarray(v))
                   for u, v in zip(jax.tree.leaves(live[0]["params"]),
                                   jax.tree.leaves(gv["params"])))


@pytest.mark.parametrize("case", ["sgd_clip", "adam", "batchnorm", "epochs2",
                                  "zero_count_client", "all_zero"])
def test_live_update_counts_the_same_steps(case):
    """Per client, before aggregation: trained variables and `num_steps`
    (FedNova's tau) of the live loop against the static scan; a client with
    no rows, and with `live` = 0 every client, hands back the global
    model."""
    (cfg, trainer, gv, _, x, y), counts = _setup_case(case)
    counts = jnp.asarray(counts, jnp.int32)
    crngs = jax.random.split(jax.random.PRNGKey(3), CLIENTS)
    update = build_local_update(trainer, cfg)
    static = jax.jit(jax.vmap(update, in_axes=(None, 0, 0, 0, 0)))(
        gv, x, y, counts, crngs)
    live = jax.jit(jax.vmap(update, in_axes=(None, 0, 0, 0, 0, None)))(
        gv, x, y, counts, crngs, live_steps(counts, N, cfg.batch_size))
    _assert_bitwise(live[:2], static[:2])
    _assert_same_metrics(live.metrics, static.metrics)
    np.testing.assert_array_equal(
        np.asarray(live.num_steps),
        cfg.epochs * np.ceil(np.asarray(counts) / BS).astype(np.int32))
    for c in np.flatnonzero(np.asarray(counts) == 0):
        _assert_bitwise(jax.tree.map(lambda l: l[c], live.variables), gv)


def test_the_model_is_traced_once():
    """The live loop needs the shapes of a step's metrics before it starts;
    it must not pay a second trace of the model for them (ResNet-56's cost
    `cross_silo.train` 22 s of set-up on the chip's host)."""
    cfg, trainer, gv, agg, x, y = _setup()
    traced = []
    loss_fn = trainer.loss_fn
    trainer.loss_fn = lambda *a: (traced.append(1), loss_fn(*a))[1]
    jax.make_jaxpr(build_round_fn(trainer, cfg, agg))(
        gv, agg.init_state(gv), x, y, jnp.asarray(RAGGED, jnp.int32),
        jax.random.PRNGKey(0))
    assert len(traced) == 1


def test_assume_full_clients_keeps_the_scan():
    cfg, trainer, gv, agg, x, y = _setup(assume_full_clients=True,
                                         batch_size=6)
    counts = jnp.full((CLIENTS,), N, jnp.int32)
    jaxpr = jax.make_jaxpr(build_round_fn(trainer, cfg, agg))(
        gv, agg.init_state(gv), x, y, counts, jax.random.PRNGKey(0))
    assert not [e for e in walk_eqns(jaxpr) if e.primitive.name == "while"]


def test_two_cohorts_one_program():
    """`live` is data, not shape: cohorts whose largest clients differ run
    the one compiled round (no compile inside a benchmark window)."""
    cfg, trainer, gv, agg, x, y = _setup()
    round_fn = build_round_fn(trainer, cfg, agg)
    state, rng = agg.init_state(gv), jax.random.PRNGKey(0)
    for counts in (RAGGED, (N, 1, 1, 1), (2, 2, 2, 2), (0, 0, 0, 0)):
        round_fn(gv, state, x, y, jnp.asarray(counts, jnp.int32), rng)
    assert round_fn._cache_size() == 1


def _step_loops(round_fn, args, prim):
    """The round's loops of primitive `prim` whose body takes a gradient
    step, i.e. holds the model's matmuls."""
    jaxpr = jax.make_jaxpr(round_fn)(*args)
    return [e for e in walk_eqns(jaxpr) if e.primitive.name == prim
            and any(q.primitive.name == "dot_general" for q in walk_eqns(
                e.params["body_jaxpr" if prim == "while" else "jaxpr"]))]


@pytest.mark.parametrize("case", ["sgd_clip", "adam", "batchnorm"])
def test_step_loop_is_a_while_with_an_unbatched_condition(case):
    """The trip count reaches the loop unbatched. Had the condition read a
    per-client value, vmap would have turned it into `any(...)` over the
    clients and `select`ed every carry leaf each step; so: the condition is
    scalar compares only, and the body has exactly the `select_n`s of the
    scan form's body."""
    (cfg, trainer, gv, agg, x, y), counts = _setup_case(case)
    args = (gv, agg.init_state(gv), x, y, jnp.asarray(counts, jnp.int32),
            jax.random.PRNGKey(0))
    loop, = _step_loops(build_round_fn(trainer, cfg, agg), args, "while")
    cond = loop.params["cond_jaxpr"].jaxpr
    assert [e.primitive.name for e in walk_eqns(cond)] == ["lt"]
    assert all(v.aval.shape == () for v in cond.eqns[0].invars)

    # the epoch scan holds the step scan: the innermost comes last
    scan = _step_loops(_static_round_fn(trainer, cfg, agg), args, "scan")[-1]

    def selects(jaxpr):
        # arrays only: the dynamic index of a batch brings scalar ones
        return sorted(str(e.outvars[0].aval) for e in walk_eqns(jaxpr)
                      if e.primitive.name == "select_n"
                      and e.outvars[0].aval.shape)

    assert selects(loop.params["body_jaxpr"]) == selects(scan.params["jaxpr"])


@pytest.mark.parametrize("n_max,batch_size,counts,epochs", [
    (480, 20, (480, 16, 200, 333), 1),
    (480, 20, (161, 16, 200, 333), 1),   # ceil(333 / 20) = 17 of 24
    (480, 20, (161, 16, 200, 340), 2),   # a full last batch
    (50, 64, (50, 3), 1),                # one batch of n_max rows
    (37, 8, (0, 0, 0), 3),               # nothing to train
    (37, -1, (5, 9), 1),                 # full batch
])
def test_round_slots_counts_the_live_steps(n_max, batch_size, counts, epochs):
    cfg = FedConfig(batch_size=batch_size, epochs=epochs)
    nb, b = epoch_batches(n_max, batch_size)
    steps = -(-max(counts) // b)
    assert steps <= nb
    slots = round_slots(cfg, len(counts), n_max, np.asarray(counts))
    assert slots == len(counts) * steps * b * epochs
    # the traced trip count is the same integer
    assert int(live_steps(jnp.asarray(counts, jnp.int32), n_max,
                          batch_size)) == steps
    # no counts, full clients: every step, as before
    every = len(counts) * nb * b * epochs
    assert round_slots(cfg, len(counts), n_max) == every
    full = dataclasses.replace(cfg, assume_full_clients=True)
    assert round_slots(full, len(counts), n_max, np.asarray(counts)) == every
    assert round_slots(cfg, len(counts), n_max,
                       np.full(len(counts), n_max)) == every


@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
def test_staged_cohort_slots_follow_the_round_program(backend):
    """A flagship-shaped cohort (10 of many writers, ragged, bs 20) staged
    by FedAvgAPI: `slots` is what ITS round program executes — live steps
    on the vmap engine, all of them on the shard_map mesh, which keeps the
    static loop."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, client_sampling
    from fedml_tpu.data import load_dataset
    from fedml_tpu.models import create_model

    ds = load_dataset("mnist", client_num_in_total=40,
                      partition_method="hetero")
    cfg = FedConfig(client_num_in_total=40, client_num_per_round=10,
                    comm_round=1, batch_size=20, backend=backend)
    api = FedAvgAPI(ds, cfg, ClassificationTrainer(
        create_model("lr", output_dim=ds.class_num)))
    n_max = ds.train.x.shape[1]
    nb, b = epoch_batches(n_max, 20)
    seen = set()
    for r in range(4):
        staged = api.stage_fn(r)
        counts = ds.train.counts[client_sampling(r, 40, 10)]
        live = -(-int(counts.max()) // b)
        clients = staged.x.shape[0]  # the mesh pads 10 up to its 8 devices
        assert staged.rows == int(counts.sum())
        if backend == "vmap":
            # this ragged federation is packed (tests/test_lane_packing.py):
            # fewer lanes than clients, each running `trip` steps
            assert staged.slots == round_slots(cfg, 10, n_max, counts,
                                               api._lanes)
            assert staged.slots == staged.lanes * staged.trip * b
            assert staged.lanes == api._lanes < 10 and staged.trip >= live
            assert staged.slots <= 10 * live * b
        else:
            assert staged.slots == clients * nb * b
        seen.add(live)
    assert backend != "vmap" or min(seen) < nb
