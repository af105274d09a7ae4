"""Routed-expert dispatch (`fedml_tpu/ops/moe.py`) on the CPU, the Pallas
grouped product in interpret mode: dropless, equal to a loop over the experts
with a 0/1 mask times the weight — with ties in the scores, an expert that
gets no token, under the engine's `vmap` over lanes, and in its gradient
with respect to activations and weights of the chosen experts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import moe

N, D, F, E, K, TILE = 40, 16, 24, 8, 2, 8


@pytest.fixture(scope="module")
def case():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (N, D))
    w = (jax.random.normal(ks[1], (E, D, F)) / 4,
         jax.random.normal(ks[2], (E, D, F)) / 4,
         jax.random.normal(ks[3], (E, F, D)) / 5)
    logits = jax.random.normal(ks[4], (N, E))
    logits = logits.at[:, 5].set(-100.0)          # expert 5 gets no token
    logits = logits.at[:8, 2].set(logits[:8, 1])  # ties: the lower index wins
    gate, idx = moe.top_k_route(jax.nn.softmax(logits, -1), K)
    return x, w, gate, idx


def masked_loop(x, w, gate, idx):
    wg, wu, wd = w
    y = 0
    for e in range(E):
        m = ((idx == e) * gate).sum(-1)
        y = y + m[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return y


def test_top_k_is_greedy_by_value_and_ties_go_to_the_lower_index(case):
    _, _, gate, idx = case
    assert idx.dtype == jnp.int32 and gate.shape == (N, K)
    assert not (np.asarray(idx) == 5).any()
    assert (np.asarray(gate[:, 0]) >= np.asarray(gate[:, 1])).all()
    tied = np.asarray(idx[:8])
    both = (tied == 1).any(-1) & (tied == 2).any(-1)
    first = np.where(tied == 1, np.arange(K), K).min(-1)
    second = np.where(tied == 2, np.arange(K), K).min(-1)
    assert (first[both] < second[both]).all()


def test_every_chosen_pair_is_computed(case):
    x, w, gate, idx = case
    with jax.default_matmul_precision("highest"):
        got = moe.routed_experts(x, idx, gate, *w, TILE)
        np.testing.assert_allclose(got, masked_loop(x, w, gate, idx),
                                   atol=2e-6)
    assert np.asarray(moe.expert_load(idx, E)).tolist() == [
        int((np.asarray(idx) == e).sum()) for e in range(E)]
    assert moe.expert_load(idx, E)[5] == 0


def test_gradient_of_activations_and_weights_of_the_pairs(case):
    x, w, gate, idx = case

    def loss(fn):
        return lambda x, gate: (fn(x, gate) ** 2).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda x, g: moe.routed_experts(
            x, idx, g, *w, TILE)), (0, 1))(x, gate)
        want = jax.grad(loss(lambda x, g: masked_loop(x, w, g, idx)),
                        (0, 1))(x, gate)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_lanes_that_share_the_experts_are_dispatched_together(case):
    """Under vmap with unbatched expert matrices the lanes' tokens go
    through ONE grouped product a matrix; every lane reads what it reads
    alone, forward and backward."""
    x, w, gate, idx = case
    xs = jnp.stack([x, x[::-1] * 0.5])
    gs, ids = jnp.stack([gate, gate[::-1]]), jnp.stack([idx, idx[::-1]])

    def step(x, i, g):
        return jax.value_and_grad(lambda x, g: (moe.routed_experts(
            x, i, g, *w, TILE) ** 2).sum(), (0, 1))(x, g)

    with jax.default_matmul_precision("highest"):
        together = jax.jit(jax.vmap(step))(xs, ids, gs)
        alone = [step(xs[l], ids[l], gs[l]) for l in range(2)]
        text = str(jax.make_jaxpr(jax.vmap(
            lambda x, i, g: moe.routed_experts(x, i, g, *w, TILE)))(
                xs, ids, gs))
    for lane in range(2):
        for a, b in zip(jax.tree.leaves(together), jax.tree.leaves(alone[lane])):
            np.testing.assert_allclose(a[lane], b, rtol=1e-5, atol=1e-5)
    # three grouped products for both lanes' 2 x N x K pairs at once
    rows = -(-(2 * N * K + E * TILE) // TILE) * TILE
    assert text.count("moe_grouped_matmul") == 3
    assert f"[{rows},{F}]" in text.replace(" ", "")


def test_lanes_with_experts_of_their_own_run_one_after_another(case):
    x, w, gate, idx = case
    ws = tuple(jnp.stack([a, a * 0.5]) for a in w)
    xs, gs, ids = jnp.stack([x, x]), jnp.stack([gate, gate]), jnp.stack([idx, idx])
    with jax.default_matmul_precision("highest"):
        got = jax.vmap(lambda x, i, g, a, b, c: moe.routed_experts(
            x, i, g, a, b, c, TILE))(xs, ids, gs, *ws)
        want = masked_loop(x, tuple(a[1] for a in ws), gate, idx)
    np.testing.assert_allclose(got[1], want, atol=2e-6)
