"""Silo-grouped conv execution path (VERDICT r4 next #1).

The measured 1.55x grouped-conv lever (docs/cross_silo_ladder.json) ships as
an execution path: GroupableConv lowers vmapped narrow convs to one
feature_group_count=S conv, and the grad-outside-vmap silo engine
(algorithms/silo_grouped.py) trains with it. These tests pin the two claims
that make the path safe to use:
  1. GroupableConv is numerically an nn.Conv drop-in (unbatched AND under
     every vmap pattern the framework uses), with an identical param tree.
  2. Full training trajectories (multi-round, aggregation included) match
     the standard vmap engine to tight tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.aggregators import make_aggregator
from fedml_tpu.algorithms.engine import build_round_fn
from fedml_tpu.algorithms.silo_grouped import build_silo_round_fn
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.trainer import ClassificationTrainer
from fedml_tpu.models.resnet import Bottleneck, ResNetCifar


def _models(threshold=8):
    kw = dict(block=Bottleneck, layers=(1, 1, 1), widths=(4, 8, 16), output_dim=10)
    return ResNetCifar(**kw), ResNetCifar(silo_threshold=threshold, **kw)


def _data(s=3, n=8, hw=8, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.rand(s, n, hw, hw, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, size=(s, n)).astype(np.int32))
    counts = jnp.full((s,), n, jnp.int32)
    return x, y, counts


def test_groupable_conv_is_nn_conv_drop_in():
    """Same param tree structure + same numerics, unbatched and under the
    eval-style vmap (weights unbatched) and the silo-style vmap (weights
    batched — where the grouped lowering actually fires)."""
    plain, silo = _models()
    x, _, _ = _data()
    v_plain = plain.init(jax.random.PRNGKey(0), x[0, :1], train=False)
    v_silo = silo.init(jax.random.PRNGKey(0), x[0, :1], train=False)
    # identical tree: same paths, same shapes, same init values
    assert jax.tree_util.tree_structure(v_plain) == jax.tree_util.tree_structure(v_silo)
    for a, b in zip(jax.tree.leaves(v_plain), jax.tree.leaves(v_silo)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # unbatched forward
    np.testing.assert_allclose(
        np.asarray(silo.apply(v_plain, x[0], train=False)),
        np.asarray(plain.apply(v_plain, x[0], train=False)), rtol=1e-5, atol=1e-6)

    # eval-style vmap: variables broadcast, data batched (fallback rule path)
    f_plain = jax.vmap(lambda xi: plain.apply(v_plain, xi, train=False))
    f_silo = jax.vmap(lambda xi: silo.apply(v_plain, xi, train=False))
    np.testing.assert_allclose(np.asarray(f_silo(x)), np.asarray(f_plain(x)),
                               rtol=1e-5, atol=1e-6)

    # silo-style vmap: per-silo variables AND data batched (grouped lowering)
    stacked = jax.tree.map(lambda l: jnp.stack([l, l * 1.5, l * 0.5]), v_plain)
    g_plain = jax.vmap(lambda v, xi: plain.apply(v, xi, train=False))
    g_silo = jax.vmap(lambda v, xi: silo.apply(v, xi, train=False))
    np.testing.assert_allclose(np.asarray(g_silo(stacked, x)),
                               np.asarray(g_plain(stacked, x)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("full", [True, False], ids=["full", "ragged"])
def test_silo_round_matches_engine_trajectory(full):
    """3 rounds of silo-grouped FedAvg == 3 rounds of the vmap engine
    (weights, BN stats, metrics), tight tolerance. Covers SGD+clip (the
    cross-silo bench config's optimizer chain) and the ragged path's
    per-silo no-op-step machinery."""
    plain, silo = _models()
    x, y, counts = _data()
    if not full:
        counts = jnp.asarray([8, 5, 3], jnp.int32)
    cfg = FedConfig(batch_size=4, epochs=2, lr=0.1, client_optimizer="sgd",
                    client_num_per_round=3, assume_full_clients=full)
    agg = make_aggregator("fedavg", cfg)
    tr_plain, tr_silo = ClassificationTrainer(plain), ClassificationTrainer(silo)
    gv = tr_plain.init(jax.random.PRNGKey(0), x[0, :1])
    st = agg.init_state(gv)

    rf_plain = build_round_fn(tr_plain, cfg, agg)
    rf_silo = build_silo_round_fn(tr_silo, cfg, agg)

    gv_p, st_p = gv, st
    gv_s, st_s = gv, st
    key = jax.random.PRNGKey(7)
    for r in range(3):
        rng = jax.random.fold_in(key, r)
        gv_p, st_p, m_p = rf_plain(gv_p, st_p, x, y, counts, rng)
        gv_s, st_s, m_s = rf_silo(gv_s, st_s, x, y, counts, rng)
        for k in m_p:
            np.testing.assert_allclose(np.asarray(m_s[k]), np.asarray(m_p[k]),
                                       rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(gv_p), jax.tree.leaves(gv_s)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # ~11s; the plain-SGD engine-match twin above pins the
# same silo==engine trajectory in the fast suite
def test_silo_momentum_optimizer_exact_per_silo():
    """vmapped optimizer = exact per-silo semantics for stateful chains
    (momentum + weight decay): trajectories still match the engine."""
    plain, silo = _models()
    x, y, counts = _data()
    cfg = FedConfig(batch_size=4, epochs=1, lr=0.05, client_optimizer="sgd",
                    momentum=0.9, wd=1e-4, client_num_per_round=3,
                    assume_full_clients=True)
    agg = make_aggregator("fedavg", cfg)
    tr_plain, tr_silo = ClassificationTrainer(plain), ClassificationTrainer(silo)
    gv = tr_plain.init(jax.random.PRNGKey(1), x[0, :1])
    st = agg.init_state(gv)
    rng = jax.random.PRNGKey(3)
    gv_p, _, _ = build_round_fn(tr_plain, cfg, agg)(gv, st, x, y, counts, rng)
    gv_s, _, _ = build_silo_round_fn(tr_silo, cfg, agg)(gv, st, x, y, counts, rng)
    for a, b in zip(jax.tree.leaves(gv_p), jax.tree.leaves(gv_s)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # ~10s epochs=2 compile; the LocalResult num_steps
# contract is structural, not codegen-sensitive — nightly coverage suffices
def test_silo_round_with_fednova_aggregator():
    """The silo path's LocalResult contract (stacked variables + per-silo
    num_steps) must satisfy non-FedAvg aggregators too — FedNova consumes
    num_steps for tau normalization. RAGGED counts on purpose: with uniform
    tau FedNova collapses algebraically to FedAvg and a wrong-but-uniform
    num_steps would pass unnoticed; differing per-silo step counts make the
    tau normalization load-bearing."""
    plain, silo = _models()
    x, y, counts = _data()
    counts = jnp.asarray([8, 5, 3], jnp.int32)  # 2 / 2 / 1 real batches
    cfg = FedConfig(batch_size=4, epochs=2, lr=0.1, client_optimizer="sgd",
                    client_num_per_round=3, assume_full_clients=False)
    agg = make_aggregator("fednova", cfg)
    tr_plain, tr_silo = ClassificationTrainer(plain), ClassificationTrainer(silo)
    gv = tr_plain.init(jax.random.PRNGKey(2), x[0, :1])
    st = agg.init_state(gv)
    rng = jax.random.PRNGKey(5)
    gv_p, _, _ = build_round_fn(tr_plain, cfg, agg)(gv, st, x, y, counts, rng)
    gv_s, _, _ = build_silo_round_fn(tr_silo, cfg, agg)(gv, st, x, y, counts, rng)
    for a, b in zip(jax.tree.leaves(gv_p), jax.tree.leaves(gv_s)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5)
