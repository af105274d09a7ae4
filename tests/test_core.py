"""Unit tests for core contracts: partitioners, packing, pytree ops."""

import numpy as np
import jax.numpy as jnp

from fedml_tpu.core.partition import (
    homo_partition,
    non_iid_partition_with_dirichlet_distribution,
    p_hetero_partition,
    record_net_data_stats,
)
from fedml_tpu.data.packing import pack_client_data, pack_eval_batches
from fedml_tpu.utils.pytree import tree_weighted_mean, tree_global_norm, tree_where


def test_homo_partition_covers_all():
    m = homo_partition(103, 7, np.random.RandomState(0))
    all_idx = np.concatenate([m[i] for i in range(7)])
    assert sorted(all_idx.tolist()) == list(range(103))


def test_lda_partition_properties():
    y = np.random.RandomState(0).randint(0, 10, size=2000)
    m = non_iid_partition_with_dirichlet_distribution(y, 8, 10, alpha=0.5, rng=np.random.RandomState(1))
    all_idx = np.concatenate([m[i] for i in range(8)])
    assert sorted(all_idx.tolist()) == list(range(2000))  # exact cover, no dup
    assert min(len(m[i]) for i in range(8)) >= 10  # min-samples guarantee
    # non-IID: class histograms should differ across clients
    stats = record_net_data_stats(y, m)
    h0 = [stats[0].get(c, 0) for c in range(10)]
    h1 = [stats[1].get(c, 0) for c in range(10)]
    assert h0 != h1


def test_p_hetero_partition_covers_all():
    y = np.random.RandomState(0).randint(0, 10, size=1000)
    m = p_hetero_partition(10, y, alpha=0.8, rng=np.random.RandomState(1))
    all_idx = np.concatenate([m[i] for i in range(10)])
    assert sorted(all_idx.tolist()) == list(range(1000))
    # dense class dominates: client of group k holds mostly class k
    stats = record_net_data_stats(y, m)
    for k in range(10):
        hist = stats[k]
        assert max(hist, key=hist.get) == k


def test_pack_client_data_shapes_and_counts():
    x = np.arange(40, dtype=np.float32).reshape(20, 2)
    y = np.arange(20, dtype=np.int32)
    m = {0: np.array([0, 1, 2]), 1: np.array([3, 4, 5, 6, 7, 8, 9])}
    packed = pack_client_data(x, y, m)
    assert packed.x.shape == (2, 7, 2)
    assert packed.counts.tolist() == [3, 7]
    assert packed.total_samples == 10
    np.testing.assert_array_equal(packed.y[0, :3], [0, 1, 2])
    assert packed.y[0, 3:].sum() == 0  # padding


def test_pack_eval_batches_mask():
    x = np.ones((10, 3), np.float32)
    y = np.zeros((10,), np.int32)
    bx, by, bm = pack_eval_batches(x, y, 4)
    assert bx.shape == (3, 4, 3)
    assert bm.sum() == 10


def test_tree_weighted_mean_matches_manual():
    stacked = {"a": jnp.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])}
    w = jnp.array([1.0, 1.0, 2.0])
    out = tree_weighted_mean(stacked, w)
    np.testing.assert_allclose(out["a"], (1 * np.array([1, 2.0]) + 1 * np.array([3, 4.0]) + 2 * np.array([5, 6.0])) / 4)


def test_tree_where_selects():
    a = {"x": jnp.ones(3)}
    b = {"x": jnp.zeros(3)}
    np.testing.assert_array_equal(tree_where(jnp.bool_(True), a, b)["x"], np.ones(3))
    np.testing.assert_array_equal(tree_where(jnp.bool_(False), a, b)["x"], np.zeros(3))


def test_tree_global_norm():
    t = {"a": jnp.array([3.0]), "b": jnp.array([4.0])}
    assert abs(float(tree_global_norm(t)) - 5.0) < 1e-6


def test_native_packing_matches_numpy():
    """The C++ pack_rows kernel produces byte-identical output to the numpy
    fallback (and actually loads in this environment)."""
    from fedml_tpu import native

    assert native.native_available(), "g++ is in the image; native must build"
    rng = np.random.RandomState(0)
    x = rng.normal(size=(50, 3, 4)).astype(np.float32)
    idx_lists = [rng.choice(50, rng.randint(1, 12), replace=False).astype(np.int64)
                 for _ in range(7)]
    n_max = 12
    out = native.pack_rows(x, idx_lists, n_max)
    ref = np.zeros((7, n_max, 3, 4), np.float32)
    for i, idx in enumerate(idx_lists):
        ref[i, : len(idx)] = x[idx]
    np.testing.assert_array_equal(out, ref)


def test_pack_client_data_native_and_fallback_agree():
    from fedml_tpu.data.packing import pack_client_data

    rng = np.random.RandomState(1)
    x = rng.normal(size=(30, 5)).astype(np.float32)
    y = rng.randint(0, 3, size=30).astype(np.int32)
    m = {0: np.arange(10), 1: np.arange(10, 30)}
    packed = pack_client_data(x, y, m)
    assert packed.x.shape == (2, 20, 5)
    np.testing.assert_array_equal(packed.x[0, :10], x[:10])
    assert packed.x[0, 10:].sum() == 0
    np.testing.assert_array_equal(packed.y[1], y[10:30])
