"""The cohort's clients packed onto fewer vmap lanes, longest first.

`engine._packed_update` runs the cohort's C clients on L < C lanes: a
schedule computed in the program from `counts` gives every lane a queue of
clients, one step loop of `trip` steps runs the per-client step under vmap
over the lanes, and a lane that has finished a client writes its row of the
[C, ...] result and starts the next from the global model. Every client must
come out as `_vmapped_update` trains it (same batches, order and keys; only
all-padding steps are left out), L = C must BE that program, and the host's
count of what ran (`round_work`, the `dispatch` span) must follow.
"""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import telemetry
from fedml_tpu.algorithms import fedavg
from fedml_tpu.algorithms.aggregators import make_aggregator
from fedml_tpu.algorithms.engine import (
    _packed_update,
    _vmapped_update,
    build_round_fn,
    build_round_fn_from_update,
    epoch_batches,
    lane_schedule,
    packed_lanes,
    packed_trip,
    round_slots,
    round_work,
)
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.trainer import ClassificationTrainer
from fedml_tpu.data import FederatedDataset, PackedClients
from fedml_tpu.models import create_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLIENTS, N, BS, CLASSES = 7, 23, 5, 3   # nb = 5, the last batch 3 rows
COUNTS = (23, 3, 7, 0, 12, 5, 19)       # steps an epoch: 5 1 2 0 3 1 4


# ------------------------------------------------------------- the schedule

def _random_counts(seed, clients, n_max):
    rng = np.random.RandomState(seed)
    counts = rng.randint(0, n_max + 1, clients)
    counts[rng.rand(clients) < 0.2] = 0
    return counts.astype(np.int32)


@pytest.mark.parametrize("seed,clients,lanes,epochs", [
    (0, 7, 1, 1), (1, 7, 2, 1), (2, 7, 3, 2), (3, 7, 6, 1), (4, 10, 5, 1),
    (5, 16, 4, 3), (6, 3, 2, 1), (7, 12, 5, 2)])
def test_schedule_deals_every_client_to_one_lane_once(seed, clients, lanes,
                                                      epochs):
    n_max, bs = 47, 8
    counts = _random_counts(seed, clients, n_max)
    queue, per_epoch, trip = jax.jit(
        lambda c: lane_schedule(c, n_max, bs, epochs, lanes))(
            jnp.asarray(counts))
    queue, per_epoch = np.asarray(queue), np.asarray(per_epoch)
    steps = np.ceil(counts / bs).astype(np.int32)
    np.testing.assert_array_equal(per_epoch, steps)
    # every client with rows is in exactly one queue, once; none without
    placed = queue[queue < clients]
    assert sorted(placed) == sorted(np.flatnonzero(counts > 0))
    for lane in queue:
        n = int((lane < clients).sum())
        assert (lane[n:] == clients).all()   # a queue is a prefix
    # the loop runs as long as the fullest lane, which the host's twin and
    # the slots counted from it agree on
    loads = [int(steps[lane[lane < clients]].sum()) * epochs
             for lane in queue]
    assert int(trip) == max(loads)
    assert int(trip) == packed_trip(counts, n_max, bs, epochs, lanes)
    cfg = FedConfig(batch_size=bs, epochs=epochs)
    assert round_work(cfg, clients, n_max, counts, lanes) == {
        "lanes": lanes, "trip": int(trip), "slots": lanes * int(trip) * bs}
    assert round_slots(cfg, clients, n_max, counts, lanes) == (
        lanes * int(trip) * bs)
    # longest first, each to the lane of least load: no lane is longer than
    # the mean by more than one client's steps
    assert max(loads) <= sum(loads) / lanes + steps.max() * epochs


def test_round_work_without_lanes_is_the_live_loop():
    cfg = FedConfig(batch_size=BS, epochs=2)
    counts = np.asarray(COUNTS)
    a_lane_a_client = {"lanes": CLIENTS, "trip": 10, "slots": CLIENTS * 50}
    for lanes in (None, CLIENTS, CLIENTS + 3):
        assert round_work(cfg, CLIENTS, N, counts, lanes) == a_lane_a_client
    assert round_work(cfg, CLIENTS, N) == a_lane_a_client
    assert round_work(cfg, 2, N, counts[1:3], 2) == {
        "lanes": 2, "trip": 4, "slots": 40}
    assert round_work(cfg, CLIENTS, N, counts, 3) == {
        "lanes": 3, "trip": 12, "slots": 180}


# ----------------------------------------------------------- the lane rule

def test_lane_rule_reads_the_federation():
    """min(C, ceil(C x mean steps / nb)): the benchmark's flagship writers
    get 5 lanes for 10 clients, like-sized clients and unknown counts a lane
    a client."""
    from benchmarks.datasets import writers
    from benchmarks.reference.fedavg import sample_cohort

    with open(os.path.join(ROOT, "benchmarks/configs/flagship.json")) as f:
        spec = json.load(f)["data"]
    train, _ = writers.sizes(spec)
    assert packed_lanes(train, 10, 480, 20) == 5
    assert packed_lanes(np.full(10, 5000), 10, 5000, 64) == 10
    assert packed_lanes(np.full(40, 17), 8, 30, 4) == 8   # padded wider
    assert packed_lanes(None, 10, 480, 20) == 10
    assert packed_lanes(train, 10, 480, -1) == 10    # full batch: one step
    assert packed_lanes(np.array([480] + [1] * 99), 10, 480, 20) == 1
    assert packed_lanes(np.zeros(5, np.int32), 4, 8, 2) == 4
    # what the issue reckoned from the benchmark's own cohorts (rounds
    # 4-116): 22.50 steps a round on 5 lanes, 12.73 % padding
    rows = slots = 0
    for r in range(4, 117):
        counts = train[sample_cohort(r, 3400, 10)]
        rows += int(counts.sum())
        slots += round_slots(FedConfig(batch_size=20), 10, 480, counts, 5)
    assert round(slots / 113 / 100, 2) == 22.50
    assert round(100 * (1 - rows / slots), 2) == 12.73


# ------------------------------------------------- L = C is today's program

@pytest.mark.parametrize("lanes", [None, CLIENTS, CLIENTS + 1])
def test_a_lane_a_client_is_the_vmapped_program(lanes):
    cfg, trainer, gv, x, y = _setup("lr")
    agg = make_aggregator("fedavg", cfg)
    args = (gv, agg.init_state(gv), x, y, jnp.asarray(COUNTS, jnp.int32),
            jax.random.PRNGKey(0))
    want = jax.make_jaxpr(build_round_fn_from_update(
        _vmapped_update(trainer, cfg), agg))(*args)
    got = jax.make_jaxpr(build_round_fn(trainer, cfg, agg, lanes=lanes))(
        *args)
    assert str(got) == str(want)
    packed = jax.make_jaxpr(build_round_fn(trainer, cfg, agg, lanes=3))(
        *args)
    assert str(packed) != str(want)


# ------------------------------------------- a client trains as it did alone

class _NormDropMLP(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Dense(8)(x.reshape((x.shape[0], -1)))
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
        x = nn.Dropout(0.25, deterministic=not train)(nn.relu(x))
        return nn.Dense(CLASSES)(x)


def _setup(model, **cfg_kw):
    rng = np.random.RandomState(0)
    shape = (28, 28, 1) if model == "cnn" else (6,)
    x = jnp.asarray(rng.rand(CLIENTS, N, *shape).astype(np.float32))
    y = jnp.asarray(rng.randint(0, CLASSES, (CLIENTS, N)).astype(np.int32))
    cfg = FedConfig(**{**dict(batch_size=BS, epochs=1, lr=0.1,
                              client_num_per_round=CLIENTS), **cfg_kw})
    module = (_NormDropMLP() if model == "bn"
              else create_model(model, output_dim=CLASSES))
    trainer = ClassificationTrainer(module)
    return cfg, trainer, trainer.init(jax.random.PRNGKey(1), x[0, :1]), x, y


CASES = {
    "lr_sgd": ("lr", dict()),
    "lr_e2": ("lr", dict(epochs=2)),
    "lr_no_shuffle": ("lr", dict(shuffle=False)),
    "lr_e2_no_shuffle_momentum_wd": ("lr", dict(
        epochs=2, shuffle=False, momentum=0.9, wd=1e-3)),
    "lr_momentum_wd": ("lr", dict(momentum=0.9, wd=1e-3)),
    "lr_adam": ("lr", dict(client_optimizer="adam", wd=1e-3)),
    "lr_adam_e2": ("lr", dict(client_optimizer="adam", epochs=2)),
    "lr_fedprox": ("lr", dict(fedprox_mu=0.1)),
    "lr_fedprox_e2_momentum": ("lr", dict(fedprox_mu=0.1, epochs=2,
                                          momentum=0.9)),
    "lr_no_clip": ("lr", dict(grad_clip=None)),
    "lr_full_batch": ("lr", dict(batch_size=-1, epochs=2)),
    "bn_sgd": ("bn", dict()),
    "bn_e2_momentum": ("bn", dict(epochs=2, momentum=0.9)),
}


def _assert_packed_matches(case_model, cfg_kw, lanes, counts=COUNTS):
    cfg, trainer, gv, x, y = _setup(case_model, **cfg_kw)
    counts = jnp.asarray(counts, jnp.int32)
    crngs = jax.random.split(jax.random.PRNGKey(3), CLIENTS)
    want = jax.jit(_vmapped_update(trainer, cfg))(gv, x, y, counts, crngs)
    got = jax.jit(_packed_update(trainer, cfg, lanes))(
        gv, x, y, counts, crngs)
    assert (jax.tree.structure(got) == jax.tree.structure(want))
    np.testing.assert_array_equal(np.asarray(got.num_steps),
                                  np.asarray(want.num_steps))
    np.testing.assert_array_equal(
        np.asarray(got.num_steps),
        cfg.epochs * np.ceil(np.asarray(counts) / epoch_batches(
            N, cfg.batch_size)[1]).astype(np.int32))
    for k in want.metrics:
        if k == "loss_sum":
            np.testing.assert_allclose(np.asarray(got.metrics[k]),
                                       np.asarray(want.metrics[k]),
                                       rtol=1e-6)
        else:   # `total`, `correct`: whole numbers
            np.testing.assert_array_equal(np.asarray(got.metrics[k]),
                                          np.asarray(want.metrics[k]))
    # parameters and model state (BatchNorm statistics) of every client
    for u, v in zip(jax.tree.leaves(got.variables),
                    jax.tree.leaves(want.variables)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=0,
                                   atol=1e-6)
    # a client of no rows hands back the global model
    for c in np.flatnonzero(np.asarray(counts) == 0):
        for u, v in zip(jax.tree.leaves(got.variables), jax.tree.leaves(gv)):
            np.testing.assert_array_equal(np.asarray(u[c]), np.asarray(v))
    return got, want


@pytest.mark.parametrize("lanes", [1, 2, 3, CLIENTS - 1])
@pytest.mark.parametrize("case", CASES)
def test_packed_clients_train_as_a_lane_a_client(case, lanes):
    model, cfg_kw = CASES[case]
    got, want = _assert_packed_matches(model, cfg_kw, lanes)
    if case == "lr_sgd":
        # the stateless path comes out bitwise on the CPU
        for u, v in zip(jax.tree.leaves(got.variables),
                        jax.tree.leaves(want.variables)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.mark.parametrize("cfg_kw,lanes", [
    (dict(), 3), (dict(epochs=2, shuffle=False), 2)])
def test_packed_cnn_with_dropout(cfg_kw, lanes):
    """The flagship's model: dropout draws from the step's key, which must
    stay the client's own whatever lane and loop step it runs at."""
    _assert_packed_matches("cnn", dict(batch_size=4, **cfg_kw), lanes,
                           counts=(9, 3, 7, 0, 2, 5, 8))


@pytest.mark.parametrize("counts", [
    (0, 0, 0, 0, 0, 0, 0),           # trip = 0: the loop never runs
    (23, 23, 23, 23, 23, 23, 23),    # full clients: C/L rounds of nb steps
    (0, 0, 1, 0, 0, 0, 0)])
def test_packed_edge_cohorts(counts):
    _assert_packed_matches("lr", dict(momentum=0.9), 3, counts=counts)


def test_packed_drops_the_lora_base():
    from fedml_tpu.models.lora import maybe_wrap_lora

    cfg, trainer, _, x, y = _setup("lr", lora_rank=2)
    trainer = maybe_wrap_lora(trainer, cfg)
    gv = trainer.init(jax.random.PRNGKey(1), x[0, :1])
    assert "lora_base" in gv
    counts = jnp.asarray(COUNTS, jnp.int32)
    crngs = jax.random.split(jax.random.PRNGKey(3), CLIENTS)
    want = jax.jit(_vmapped_update(trainer, cfg))(gv, x, y, counts, crngs)
    got = jax.jit(_packed_update(trainer, cfg, 3))(gv, x, y, counts, crngs)
    assert "lora_base" not in got.variables
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for u, v in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=1e-6,
                                   atol=1e-6)


def test_packed_round_traces_the_model_once_and_compiles_once():
    cfg, trainer, gv, x, y = _setup("lr")
    agg = make_aggregator("fedavg", cfg)
    traced = []
    loss_fn = trainer.loss_fn
    trainer.loss_fn = lambda *a: (traced.append(1), loss_fn(*a))[1]
    round_fn = build_round_fn(trainer, cfg, agg, lanes=3)
    state, rng = agg.init_state(gv), jax.random.PRNGKey(0)
    for counts in (COUNTS, COUNTS[::-1], (N,) * CLIENTS, (0,) * CLIENTS):
        round_fn(gv, state, x, y, jnp.asarray(counts, jnp.int32), rng)
    assert len(traced) == 1
    assert round_fn._cache_size() == 1


# --------------------------------------------------------- the whole API

def _ragged_federation(clients=24, n_max=40):
    rng = np.random.RandomState(5)
    counts = np.clip(rng.lognormal(2.2, 0.7, clients), 1, n_max).astype(
        np.int32)
    counts[0] = n_max
    x = rng.rand(clients, n_max, 6).astype(np.float32)
    y = rng.randint(0, CLASSES, (clients, n_max)).astype(np.int32)
    train = PackedClients(x, y, counts)
    return FederatedDataset(
        name="ragged", train=train, test=train,
        train_global=(x[:, 0], y[:, 0]), test_global=(x[:, 0], y[:, 0]),
        class_num=CLASSES)


@pytest.mark.parametrize("pipeline_depth", [0, 2])
def test_api_packs_a_ragged_federation(monkeypatch, pipeline_depth):
    """Five rounds of different cohorts through FedAvgAPI.train(): one
    compile, the global model of the lane-a-client API, and `dispatch` spans
    that say what ran."""
    ds = _ragged_federation()
    cfg = FedConfig(client_num_in_total=24, client_num_per_round=8,
                    comm_round=5, batch_size=4, epochs=2, lr=0.1,
                    momentum=0.9, frequency_of_the_test=100,
                    pipeline_depth=pipeline_depth)

    def run():
        api = fedavg.FedAvgAPI(ds, cfg, ClassificationTrainer(
            create_model("lr", output_dim=CLASSES)))
        tracer = telemetry.Tracer()
        api.train(tracer=tracer)
        return api, [s for s in tracer.spans if s["name"] == "dispatch"]

    api, spans = run()
    want_lanes = packed_lanes(ds.train.counts, 8, 40, 4)
    assert api._lanes == want_lanes < 8
    assert getattr(api.round_fn, "jitted", api.round_fn)._cache_size() == 1
    assert len(spans) == 5
    trips = set()
    for s in spans:
        counts = ds.train.counts[fedavg.client_sampling(s["round"], 24, 8)]
        trip = packed_trip(counts, 40, 4, 2, want_lanes)
        assert (s["lanes"], s["trip"]) == (want_lanes, trip)
        assert s["slots"] == want_lanes * trip * 4
        assert s["rows"] == 2 * int(counts.sum()) <= s["slots"]
        trips.add(trip)
    assert len(trips) > 1   # the cohorts differ, the program does not

    monkeypatch.setattr(fedavg, "packed_lanes",
                        lambda counts, clients, *a: clients)
    unpacked, spans = run()
    assert unpacked._lanes == 8
    assert all(s["lanes"] == 8 and s["slots"] == 8 * s["trip"] * 4
               for s in spans)
    for u, v in zip(jax.tree.leaves(api.global_variables),
                    jax.tree.leaves(unpacked.global_variables)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=0,
                                   atol=1e-6)
    assert [h["Train/Loss"] for h in api.history if "Train/Loss" in h] == \
        pytest.approx([h["Train/Loss"] for h in unpacked.history
                       if "Train/Loss" in h], rel=1e-5)


def test_api_keeps_a_lane_a_client_where_nothing_is_to_pack():
    """Like-sized clients (the cross-silo shape) and the superstep drive."""
    ds = _ragged_federation()
    even = PackedClients(ds.train.x, ds.train.y,
                         np.full(24, 40, np.int32))
    flat = FederatedDataset(name="even", train=even, test=even,
                            train_global=ds.train_global,
                            test_global=ds.test_global, class_num=CLASSES)
    trainer = ClassificationTrainer(create_model("lr", output_dim=CLASSES))
    cfg = FedConfig(client_num_in_total=24, client_num_per_round=8,
                    comm_round=1, batch_size=4)
    assert fedavg.FedAvgAPI(flat, cfg, trainer)._lanes == 8
    fused = FedConfig(client_num_in_total=24, client_num_per_round=8,
                      comm_round=4, batch_size=4, pipeline_depth=0,
                      rounds_per_dispatch=2)
    assert fedavg.FedAvgAPI(ds, fused, trainer)._lanes is None
