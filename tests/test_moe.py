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


# ---- a share of the experts (`routed_experts_share`): the bounded row buffer,
# the exact fallback, the flag that says which a call took

SN, SK, SR, SE, SFIRST = 64, 4, 16, 2, 4     # 2 held (4, 5) of 16 routed
SP = SN * SK
#: SHARE_ROOM 2: fair share 2 x 256 x 2 / 16 = 64 pairs + 2 tiles of filler
M_B, M_WORST = 80, 272


def share_case(held_pairs=None, sizes=None, seed=1):
    """x, w, gate, idx of SN tokens whose top-SK experts are all distinct.
    `sizes`: exactly that many pairs on held expert 4 and on 5, the rest on
    absent ones; `held_pairs` "all": only held experts (k = 2 then)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (SN, D))
    w = (jax.random.normal(ks[1], (SE, D, F)) / 4,
         jax.random.normal(ks[2], (SE, D, F)) / 4,
         jax.random.normal(ks[3], (SE, F, D)) / 5)
    if held_pairs == "all":
        idx = jnp.tile(jnp.array([[4, 5]], jnp.int32), (SN, 1))
        gate = jax.nn.softmax(jax.random.normal(ks[4], (SN, 2)), -1)
        return x, w, gate, idx
    if sizes is None:
        scores = jax.nn.sigmoid(jax.random.normal(ks[4], (SN, SR)))
        gate, idx = moe.top_k_route(scores, SK)
        return x, w, gate, idx
    absent = np.array([0, 1, 2, 3, 6, 7, 8, 9])
    idx = np.stack([absent[(np.arange(SN) + j) % 8] for j in range(SK)], 1)
    idx[:sizes[0], 0] = 4               # one pair a token on each at most
    idx[:sizes[1], 1] = 5
    gate = jax.nn.softmax(jax.random.normal(ks[4], (SN, SK)), -1)
    return x, w, gate, jnp.asarray(idx, jnp.int32)


def held_loop(x, w, gate, idx, first=SFIRST):
    """The dense yardstick: every held expert on every token, masked."""
    wg, wu, wd = w
    y = 0
    for e in range(wg.shape[0]):
        m = ((idx == e + first) * gate).sum(-1)
        y = y + m[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return y


def share_step(x, idx, gate, w, first=SFIRST, routed=SR):
    """-> (y, worst flag [N], dx, dgate) under the cotangent 2 y."""
    def loss(x, g):
        y, worst = moe.routed_experts_share(x, idx, g, *w, first, routed, TILE)
        return (y ** 2).sum(), (y, worst)
    (_, (y, worst)), (dx, dgate) = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(x, gate)
    return y, worst, dx, dgate


def dense_step(x, idx, gate, w, first=SFIRST):
    (_, y), (dx, dgate) = jax.value_and_grad(
        lambda x, g: ((held_loop(x, w, g, idx, first) ** 2).sum(),
                      held_loop(x, w, g, idx, first)), (0, 1),
        has_aux=True)(x, gate)
    return y, dx, dgate


def test_the_share_bound_is_the_fair_share_with_room_and_never_over_worst():
    assert moe.SHARE_ROOM == 2.0
    assert moe.share_rows(SP, SE, SR, TILE) == M_B
    assert moe._worst_rows(SP, SE, TILE) == M_WORST
    # the cell: 64 of 256 held, 131,072 pairs a joint call, tiles of 128
    assert moe.share_rows(131072, 64, 256) == 65536 + 8192
    assert moe._worst_rows(131072, 64, 128) == 139264
    # half or more of the experts held: the bound is the worst case
    assert moe.share_rows(SP, 8, 16, TILE) == moe._worst_rows(SP, 8, TILE)
    # a joint call's buffer fits the lanes' (`_lanes_together`'s split)
    for lanes in (2, 3, 8):
        for p in (8, 40, SP, 1000):
            assert (moe.share_rows(lanes * p, SE, SR, TILE)
                    <= lanes * moe.share_rows(p, SE, SR, TILE))


@pytest.mark.parametrize("room,worst", [(2.0, 0.0), (0.25, 1.0)])
def test_a_share_on_either_path_equals_the_dense_computation(
        monkeypatch, room, worst):
    """The same routing through the bounded buffer (room 2) and, with the
    room cut so that it cannot fit, through the worst-case path."""
    monkeypatch.setattr(moe, "SHARE_ROOM", room)
    x, w, gate, idx = share_case()
    held = int(((idx >= SFIRST) & (idx < SFIRST + SE)).sum())
    assert 0 < held < SP // 2
    with jax.default_matmul_precision("highest"):
        y, flag, dx, dgate = share_step(x, idx, gate, w)
        want = dense_step(x, idx, gate, w)
    assert flag.shape == (SN,) and (np.asarray(flag) == worst).all()
    for a, b in zip((y, dx, dgate), want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # a pair on an absent expert takes no gradient through its weight
    assert (np.asarray(dgate)[(np.asarray(idx) < SFIRST)
                              | (np.asarray(idx) >= SFIRST + SE)] == 0).all()


def test_the_two_paths_of_a_share_agree_to_the_bit(monkeypatch):
    x, w, gate, idx = share_case()
    with jax.default_matmul_precision("highest"):
        bounded = share_step(x, idx, gate, w)
        monkeypatch.setattr(moe, "SHARE_ROOM", 0.25)
        fallback = share_step(x, idx, gate, w)
    assert bounded[1].max() == 0.0 and fallback[1].min() == 1.0
    for a, b in zip(bounded[::2] + (bounded[3],), fallback[::2] + (fallback[3],)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_every_pair_on_held_experts_falls_back_and_equals_the_whole_dispatch():
    x, w, gate, idx = share_case("all")
    with jax.default_matmul_precision("highest"):
        y, flag, dx, dgate = share_step(x, idx, gate, w)

        def whole(x, g):
            y = moe.routed_experts(x, idx - SFIRST, g, *w, TILE)
            return (y ** 2).sum(), y
        (_, want), (wdx, wdgate) = jax.value_and_grad(
            whole, (0, 1), has_aux=True)(x, gate)
    assert (np.asarray(flag) == 1.0).all()
    np.testing.assert_allclose(y, want, atol=2e-6)
    np.testing.assert_allclose(dx, wdx, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dgate, wdgate, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sizes,rows,worst", [
    ((40, 40), 80, 0.0),        # exactly the buffer: 5 + 5 full tiles
    ((40, 33), 80, 0.0),        # the same rows with filler
    ((41, 40), 88, 1.0),        # one row tile over it
    ((64, 64), 128, 1.0)])
def test_a_routing_at_the_bound_fits_and_one_tile_over_it_falls_back(
        sizes, rows, worst):
    x, w, gate, idx = share_case(sizes=sizes)
    assert int(moe._held_rows(idx, SE, TILE, SFIRST)) == rows
    assert (rows <= M_B) == (worst == 0.0)
    with jax.default_matmul_precision("highest"):
        y, flag, dx, dgate = share_step(x, idx, gate, w)
        want = dense_step(x, idx, gate, w)
    assert (np.asarray(flag) == worst).all()
    for a, b in zip((y, dx, dgate), want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("own_matrices", [False, True],
                         ids=["joint", "a_lane_at_a_time"])
@pytest.mark.parametrize("room,worst", [(2.0, 0.0), (0.25, 1.0)])
def test_a_share_under_vmap_on_either_path(monkeypatch, own_matrices, room,
                                           worst):
    """Lanes that share the matrices are ONE call (one count, one path for
    all of them); lanes with matrices of their own decide each for itself."""
    monkeypatch.setattr(moe, "SHARE_ROOM", room)
    x, w, gate, idx = share_case()
    xs = jnp.stack([x, x[::-1] * 0.5])
    gs, ids = jnp.stack([gate, gate[::-1]]), jnp.stack([idx, idx[::-1]])
    ws = tuple(jnp.stack([a, a * 0.5]) for a in w)
    with jax.default_matmul_precision("highest"):
        if own_matrices:
            got = jax.jit(jax.vmap(lambda x, i, g, *w: share_step(x, i, g, w)))(
                xs, ids, gs, *ws)
            alone = [share_step(xs[l], ids[l], gs[l], tuple(a[l] for a in ws))
                     for l in range(2)]
        else:
            got = jax.jit(jax.vmap(lambda x, i, g: share_step(x, i, g, w)))(
                xs, ids, gs)
            alone = [share_step(xs[l], ids[l], gs[l], w) for l in range(2)]
            text = str(jax.make_jaxpr(jax.vmap(
                lambda x, i, g: moe.routed_experts_share(
                    x, i, g, *w, SFIRST, SR, TILE)))(xs, ids, gs))
            # one joint call: three grouped products a branch, over the joint
            # call's own bound (at room 2, 2 x 256 pairs: 128 + 16 rows) and
            # over its worst case
            assert text.count("moe_grouped_matmul") == 6
            rows = moe.share_rows(2 * SP, SE, SR, TILE)
            assert rows == (144 if room == 2.0 else 32)
            assert f"[{rows},{F}]" in text.replace(" ", "")
            assert f"[{2 * SP + SE * TILE},{F}]" in text.replace(" ", "")
    assert got[1].shape == (2, SN) and (np.asarray(got[1]) == worst).all()
    for lane in range(2):
        for a, b in zip(got, alone[lane]):
            np.testing.assert_allclose(a[lane], b, rtol=1e-5, atol=1e-5)


def test_the_flag_is_the_joint_calls_and_counts_one_a_call():
    """Two lanes, one of them over the bound alone: together they fit the
    joint call's buffer (no fallback for either); a lane at a time the full
    one falls back and the other does not."""
    full = share_case(sizes=(64, 64))       # 128 rows alone: over 80
    light = share_case(sizes=(8, 0))        # 8 rows
    xs, gs, ids = (jnp.stack([a, b]) for a, b in zip(
        (full[0], full[2], full[3]), (light[0], light[2], light[3])))
    w = full[1]
    joint = jax.vmap(lambda x, i, g: moe.routed_experts_share(
        x, i, g, *w, SFIRST, SR, TILE)[1])(xs, ids, gs)
    assert np.asarray(joint).tolist() == [[0.0] * SN] * 2   # 136 rows <= 144
    ws = tuple(jnp.stack([a, a]) for a in w)
    each = jax.vmap(lambda x, i, g, *w: moe.routed_experts_share(
        x, i, g, *w, SFIRST, SR, TILE)[1])(xs, ids, gs, *ws)
    assert np.asarray(each)[:, 0].tolist() == [1.0, 0.0]
    # what a module makes of it: one call, one path
    path = jnp.stack([1.0 - each[:, 0], each[:, 0]], -1).sum(0)
    assert path.tolist() == [1.0, 1.0]


def _array_shapes(jaxpr, out):
    """Shapes of every value a jaxpr makes, sub-jaxprs included (a Pallas
    kernel's body works on blocks in VMEM and is left out)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out += [tuple(v.aval.shape) for v in eqn.outvars]
            continue
        out += [tuple(v.aval.shape) for v in eqn.outvars
                if hasattr(v.aval, "shape")]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _array_shapes(sub, out)
    return out


def test_no_array_of_the_bounded_path_has_more_rows_than_the_bound_and_a_width():
    """Token arrays [N, ..] and [P] index or scalar vectors aside, nothing
    the bounded forward or backward makes is larger than [M_b, width]."""
    x, w, gate, idx = share_case()
    dy = jnp.ones_like(x)

    def fwd(x, idx, gate, *w):
        return moe._share_forward(x, idx, gate, *w, TILE, SFIRST, M_B, True)

    def bwd(x, idx, gate, res, dy, *w):
        return moe._share_backward(x, idx, gate, *w, res, dy, TILE, SFIRST,
                                   M_B)

    _, res = fwd(x, idx, gate, *w)
    g, u, src, row_of_pair, grp, nt = res
    assert g.shape == u.shape == (M_B, F) and src.shape == (M_B,)
    assert row_of_pair.shape == (SP,) and grp.shape == (M_B // TILE,)
    shapes = (_array_shapes(jax.make_jaxpr(fwd)(x, idx, gate, *w).jaxpr, [])
              + _array_shapes(jax.make_jaxpr(bwd)(
                  x, idx, gate, res, dy, *w).jaxpr, []))
    assert (M_B, D) in shapes and (M_B, F) in shapes
    assert SN < M_B < SP
    for shape in shapes:
        rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 0
        assert rows <= M_B or shape[-1] <= SK, shape
    # the worst-case path does run over its 272 rows
    big = _array_shapes(jax.make_jaxpr(lambda *a: moe._share_forward(
        *a, TILE, SFIRST, M_WORST, False))(x, idx, gate, *w).jaxpr, [])
    assert (M_WORST, F) in big


def _primitives(jaxpr, out):
    """Names of every primitive a jaxpr runs, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _primitives(sub, out)
    return out


@pytest.mark.parametrize("lanes", [0, 3], ids=["alone", "lanes_together"])
def test_with_every_expert_held_the_dispatch_is_one_path_in_row_space(
        case, lanes):
    """Every expert held is the share with `first` None and `routed` = E:
    no count and no `cond`, and nothing its forward or backward makes is
    pair-sized with a width ([N k, >= D] or [N, k, >= D], nor the joint
    call's [lanes N k, ..] of them): the rows go back to their tokens one
    [N, d] gather a slot."""
    x, w, gate, idx = case

    def step(x, i, g):
        return jax.value_and_grad(lambda x, g: (moe.routed_experts(
            x, i, g, *w, TILE) ** 2).sum(), (0, 1))(x, g)

    if lanes:
        args = [jnp.stack([a] + [a[::-1]] * (lanes - 1))
                for a in (x, idx, gate)]
        jaxpr = jax.make_jaxpr(jax.vmap(step))(*args).jaxpr
    else:
        jaxpr = jax.make_jaxpr(step)(x, idx, gate).jaxpr
    assert "cond" not in _primitives(jaxpr, set())
    n = N * max(lanes, 1)
    pairs = {(n * K,), (n, K), (lanes, N * K), (lanes, N, K)}
    shapes = _array_shapes(jaxpr, [])
    assert (moe._worst_rows(n * K, E, TILE), F) in shapes
    for shape in shapes:
        assert not (shape[:-1] in pairs and shape[-1] >= D), shape
