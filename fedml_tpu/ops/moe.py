"""Routed experts: greedy top-k routing and DROPLESS dispatch.

    y[n] = sum_j gate[n, j] * E_{idx[n, j]}(x[n]),
    E_e(x) = (silu(x Wg[e]) * (x Wu[e])) Wd[e]

Every chosen (token, expert) pair is computed: no capacity, no dropped pair,
and never all experts on every token. The pairs are sorted by expert into
row tiles that hold one expert each (a group's rows start at a multiple of
the tile; the tail tile of a group is filler that nothing reads back), one
grouped product a matrix runs over the tiles, and the rows go back to their
tokens weighted by `gate`.

The grouped product is a Pallas kernel (`grouped_matmul`): one grid step a
row tile, the tile's expert looked up from a scalar-prefetched table, that
expert's WHOLE matrix as the block, so that consecutive tiles of one expert
re-use it and every matrix is read from HBM once; `trans_rhs` contracts
with the matrix's last dimension (the activation gradient) without a
transposed copy of the weights. Off the chip it runs in interpret mode.

**One dispatch (`routed_experts_share`), for a share of the experts or all
of them.** An expert-parallel layer routes over more experts than a chip
holds. `idx` names the router's `routed` experts and the matrices are those
of experts first .. first + E - 1: a pair whose expert is absent leaves the
dispatch before the grouped product (it gets no row, adds nothing to `y` and
takes no gradient; `gate` keeps what the router gave it, renormalised over
all k). A layer whose chip holds every expert (`routed_experts`) is the share
with `first` None and `routed` = E: no pair is absent, the layout keeps no
group for absent ones, and its bound is the worst case (below), so it is one
path with no count and no `cond`. The backward gives the gradient with
respect to `x` and `gate` ONLY: the expert matrices are a frozen base
(`models/lora.py` gives 3-D kernels no adapter; the models that use this op
say `frozen_base_only`, and `experiments/common.py::build_trainer` refuses
such a module without `--lora_rank`).

**A share's buffers.** How many pairs stay is data, and the buffers are
static. Sized for the worst case, every pair on a held
expert (M = N k + E tile rows, as when all are held), the gathers and the
elementwise passes around the grouped product run over three to four times
the rows a quarter share's pairs need (the product itself skips unused
tiles, `n_tiles`; XLA's passes do not). So a share's buffers hold

    M_b = round_up(min(P, c P E / routed), tile) + E tile     (`share_rows`)

rows: c = `SHARE_ROOM` times the FAIR share of a call's P = N k pairs (what a
router that spreads its pairs evenly sends E of `routed` experts) and a tile
of filler an expert. c is 2: over four seeds of the benchmark cell a joint
call's held rows, filler counted, were 0.21-0.34 of P where the fair share
is a quarter (PERF.md section 6, PR 39: Zipf token ids put a tenth of all
tokens on one id, and whether its experts are held is the seed's), so the
buffer, 0.56 P, has 1.66 times the largest call seen; at c = 1.5 it would
have 1.29 times, too little for a path that is meant never to be left. No bound
under the worst case can hold every routing, so nothing is ever dropped on
its account: before anything is gathered the call COUNTS its held rows
(`_held_rows`: `n_tiles` x tile), and one `lax.cond` on that scalar takes
the bounded path where they fit M_b and the worst-case path, over M rows,
where they do not. Both compute the same products in the same order for every
pair, so their results are equal to the bit; the backward counts the same
`idx` again, so both sides of the `custom_vjp` agree. Residuals have ONE
shape, the bounded one (g, u [M_b, f] and the layout's four index vectors,
which the two `cond`s would otherwise each compute): the worst-case forward
hands back zeros of it and its backward lays its rows out and makes g, u
again from x (two more grouped products, on that path only). The call also
says which path it took (a [N] float32 flag, 1.0 the worst case), which
`models/deepseek_v2.py::MoE` reduces and the `moe_load` event carries as
`bounded` / `fallback`. Where c E / routed is 1 or more the bound IS the
worst case and there is one path; with every expert held it always is.
Nothing of the dispatch is pair-sized with a width: the backward works in
row space (dy gathered to the rows and weighted by the row's gate there,
dgate from the rows' g, u and the gate-free dh, no [N, k, d] residual), and
rows go back to tokens one gather of [N, d] a slot (`_to_tokens`).

Under `vmap` (the engine's client axis) with matrices that are the same for
every lane, as a frozen base is, the lanes' tokens are dispatched TOGETHER:
one sort, one grouped product over the cohort's tokens
(`jax.custom_batching.custom_vmap`; a token's result does not depend on
which other tokens share the call). A share's count, bound and `cond` are
then the joint call's, one scalar for all lanes (M_b of a joint call is at
most lanes x M_b of a lane, so a lane's residual rows are a slice of it).

The dispatch names its phases with `jax.named_scope`, which adds no
operation: `moe_layout` (`_layout`, `_held_rows`: the sort, scatters and
count), `moe_gather` (tokens into expert order; in the backward dy's and
the gates' rows) and `moe_combine` (rows back to tokens, the gate weighting
and the k-sum, and the gates' gradient), in the forward, the rematerialised
forward and the backward alike. A device trace's ops are joined to them
through the round program's op metadata (`telemetry/scopes.py`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from fedml_tpu.ops.interpret import interpret_off_chip

#: rows of one grouped-product tile (the MXU's 128 on a v5e)
TILE = 128

#: c of the module docstring: a share's row buffer holds this many times the
#: fair share of a call's pairs (pairs x held / routed) before the call takes
#: the worst-case path
SHARE_ROOM = 2.0


def top_k_route(scores, k: int):
    """Greedy top-k by value over the last axis: -> (gate [.., k] the chosen
    scores as they are, idx [.., k] int32). Ties go to the lower index."""
    gate, idx = jax.lax.top_k(scores, k)
    return gate, idx.astype(jnp.int32)


def expert_load(idx, n_experts: int):
    """Tokens each expert received: [n_experts] float32 counts of `idx`."""
    return jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)


def _worst_rows(p: int, n_experts: int, tile: int) -> int:
    """Rows that hold `p` pairs however they fall on `n_experts` groups."""
    return -(-(p + n_experts * tile) // tile) * tile


def share_rows(p: int, n_held: int, routed: int, tile: int = TILE,
               room: float | None = None) -> int:
    """M_b, the rows of a share's bounded buffer: `room` (`SHARE_ROOM`) times
    the fair share of a call's `p` pairs (never more than all of them), and
    a tile of filler a held expert; the worst case where that is no less."""
    room = SHARE_ROOM if room is None else room
    fair = math.ceil(room * p * n_held / routed)
    return min(-(-min(p, fair) // tile) * tile + n_held * tile,
               _worst_rows(p, n_held, tile))


@jax.named_scope("moe_layout")
def _held_rows(idx, n_experts: int, tile: int, first: int):
    """The rows a call's held pairs take, every group's tile filler counted
    (`_layout`'s `n_tiles` x tile, before anything is sorted): a scalar."""
    flat = idx.reshape(-1, 1) - first
    sizes = (flat == jnp.arange(n_experts, dtype=jnp.int32)).sum(
        axis=0, dtype=jnp.int32)
    return (-(-sizes // tile) * tile).sum()


@jax.named_scope("moe_layout")
def _layout(idx, n_experts: int, tile: int, first, m: int):
    """Where each (token, slot) pair sits among `m` tiled rows.

    idx [N, k] -> (src [m] the pair that feeds each row, P = N * k for a
    filler row; row_of_pair [P]; tile_group [m // tile] the expert of each
    tile; n_tiles [1] the tiles that hold rows). `idx` counts the router's
    experts, `n_experts` are held from `first` and a pair on an absent one
    gets no row (its `row_of_pair` is m, out of range); `first` None: every
    expert is held. `m` holds the call's rows: the worst case
    (`_worst_rows`), or a bound the caller has counted them under
    (`_held_rows`)."""
    n, k = idx.shape
    p = n * k
    flat = idx.reshape(p)
    groups = n_experts
    if first is not None:
        # absent pairs sort behind every held group, as one group of size 0
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < n_experts), flat, n_experts)
        groups += 1
    sizes = jnp.zeros((groups,), jnp.int32).at[flat].add(1)
    if first is not None:
        sizes = sizes.at[-1].set(0)
    padded = -(-sizes // tile) * tile
    ends = jnp.cumsum(padded)
    starts = ends - padded
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sorted_e = flat[order]
    begin = jnp.cumsum(sizes) - sizes            # unpadded start of a group
    rank = jnp.arange(p, dtype=jnp.int32) - begin[sorted_e]
    dest = starts[sorted_e] + rank               # row of the sorted pair
    if first is None:
        src = jnp.full((m,), p, jnp.int32).at[dest].set(order)
    else:
        # (rows past `m` exist only in the branch a `cond` does not take)
        dest = jnp.where(sorted_e == n_experts, m, jnp.minimum(dest, m))
        src = jnp.full((m,), p, jnp.int32).at[dest].set(order, mode="drop")
        ends = ends[:n_experts]
    row_of_pair = jnp.zeros((p,), jnp.int32).at[order].set(dest)
    tile_start = jnp.arange(m // tile, dtype=jnp.int32) * tile
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right").astype(jnp.int32),
        n_experts - 1)
    n_tiles = (ends[-1:] // tile).astype(jnp.int32)
    return src, row_of_pair, tile_group, n_tiles


def _gmm_kernel(group_ref, n_tiles_ref, lhs_ref, rhs_ref, out_ref, *,
                trans_rhs):
    from jax.experimental import pallas as pl

    del group_ref  # read by the index maps

    @pl.when(pl.program_id(0) < n_tiles_ref[0])
    def _tile():
        dims = (((1,), (1,)), ((), ())) if trans_rhs else (((1,), (0,)), ((), ()))
        out_ref[:] = jax.lax.dot_general(
            lhs_ref[:], rhs_ref[:], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def grouped_matmul(lhs, rhs, tile_group, n_tiles, *, tile: int = TILE,
                   trans_rhs: bool = False, interpret=None):
    """out[r] = lhs[r] @ rhs[g(r)] (or @ rhs[g(r)]^T), g constant over each
    tile of `tile` rows (`tile_group`), for the first `n_tiles` tiles; rows
    past them are left as they come. lhs [M, K]; rhs [G, K, N] (trans_rhs:
    [G, N, K]); -> [M, N] in lhs's dtype, accumulated in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = interpret_off_chip("grouped_matmul")
    m, kdim = lhs.shape
    g, r1, r2 = rhs.shape
    ndim = r1 if trans_rhs else r2
    assert (r2 if trans_rhs else r1) == kdim and m % tile == 0
    # one expert's whole matrix, double-buffered, is the footprint
    block_bytes = 2 * (r1 * r2 * rhs.dtype.itemsize
                       + tile * (kdim + ndim) * lhs.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, trans_rhs=trans_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tile,),
            in_specs=[
                pl.BlockSpec((tile, kdim), lambda i, grp, nt: (i, 0)),
                pl.BlockSpec((None, r1, r2), lambda i, grp, nt: (grp[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, ndim), lambda i, grp, nt: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, ndim), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(block_bytes * 1.5) + (8 << 20)),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(tile_group, n_tiles, lhs, rhs)


def _silu(x):
    return x * jax.nn.sigmoid(x)


@jax.named_scope("moe_gather")
def _rows(x, src, k: int):
    """The tiled rows' inputs: x[src // k], zeros for filler rows."""
    return jnp.take(x, src // k, axis=0, mode="fill", fill_value=0)


@jax.named_scope("moe_combine")
def _to_tokens(rows, at, gate, dtype):
    """Rows back to tokens: out[n] = sum_j gate[n, j] * rows[at[n, j]] in
    float32, a pair without a row (`at` out of range) adding nothing. rows
    [m, d]; at [N, k]; gate [N, k] or None (weights of 1) -> [N, d] `dtype`.
    One gather of [N, d] a slot, accumulated: no [N, k, d] array exists
    (step 0 of PR 39: the fastest of four forms, PERF.md section 6)."""
    out = jnp.zeros((at.shape[0], rows.shape[-1]), jnp.float32)
    for j in range(at.shape[1]):
        term = jnp.take(rows, at[:, j], axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)
        if gate is not None:
            term = gate[:, j, None].astype(jnp.float32) * term
        out = out + term
    return out.astype(dtype)


def _share_forward(x, idx, gate, wg, wu, wd, tile, first, m, keep):
    """The forward over `m` tiled rows -> y [N, d], and with `keep`
    the residuals (g, u [m, f], then the layout: src [m], row_of_pair [P],
    tile_group [m // tile], n_tiles [1])."""
    n, k = idx.shape
    layout = _layout(idx, wg.shape[0], tile, first, m)
    src, row_of_pair, grp, nt = layout
    mm = functools.partial(grouped_matmul, tile_group=grp, n_tiles=nt,
                           tile=tile)
    xs = _rows(x, src, k)
    g, u = mm(xs, wg), mm(xs, wu)
    h = (_silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    y = _to_tokens(mm(h, wd), row_of_pair.reshape(n, k), gate, x.dtype)
    return (y, (g, u) + layout) if keep else y


def _share_backward(x, idx, gate, wg, wu, wd, res, dy, tile, first, m):
    """The backward over `m` tiled rows, every array in row space
    -> (dx [N, d], dgate [N, k]). `res`: the forward's residuals, or None on
    the worst-case path, which kept none: it lays the rows out and makes
    g, u from x again."""
    n, k = idx.shape
    if res is None:
        src, row_of_pair, grp, nt = _layout(idx, wg.shape[0], tile, first, m)
    else:
        # (lanes dispatched together hand back lanes x their own sizes of
        # tiled rows, tiles and counts: the tails are filler)
        g, u, src, row_of_pair, grp, nt = res
        g, u, src, grp, nt = g[:m], u[:m], src[:m], grp[:m // tile], nt[:1]
    mm = functools.partial(grouped_matmul, tile_group=grp, n_tiles=nt,
                           tile=tile)
    mm_t = functools.partial(mm, trans_rhs=True)
    if res is None:
        xs = _rows(x, src, k)
        g, u = mm(xs, wg), mm(xs, wu)
    g32, u32 = g.astype(jnp.float32), u.astype(jnp.float32)
    with jax.named_scope("moe_gather"):
        gate_row = jnp.take(gate.reshape(-1).astype(jnp.float32), src,
                            mode="fill", fill_value=0)
    dh = mm_t(_rows(dy, src, k), wd).astype(jnp.float32)  # before the gate
    sig = jax.nn.sigmoid(g32)
    act = g32 * sig
    # filler rows carry what an unvisited tile left there: no pair reads them
    with jax.named_scope("moe_combine"):
        dgate = jnp.take((dh * (act * u32)).sum(axis=-1), row_of_pair,
                         mode="fill", fill_value=0).reshape(n, k)
    dh = dh * gate_row[:, None]
    dg = (dh * u32 * sig * (1.0 + g32 * (1.0 - sig))).astype(dy.dtype)
    du = (dh * act).astype(dy.dtype)
    dxs = mm_t(dg, wg).astype(jnp.float32) + mm_t(du, wu).astype(jnp.float32)
    return (_to_tokens(dxs, row_of_pair.reshape(n, k), None, dy.dtype),
            dgate.astype(gate.dtype))


def _lanes_together(fn, n_lane_args: int):
    """`fn` (its first `n_lane_args` arguments per token, leading axis N;
    the rest the experts' matrices; every output leading with N or with the
    tiled rows M) as a `custom_vmap`: lanes whose matrices are shared run as
    ONE call over all the lanes' tokens. A lane's tiled rows come back as
    its `1 / lanes` share of the joint call's (M of the joint call is under
    lanes x M of one lane: the tail is filler), which the backward's own
    joint call reads back in the same order. Lanes with matrices of their
    own run one after another."""
    wrapped = jax.custom_batching.custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        lane_b, w_b = in_batched[:n_lane_args], in_batched[n_lane_args:]
        if any(w_b):
            full = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                    for a, b in zip(args, in_batched)]
            outs = jax.lax.map(lambda a: fn(*a), tuple(full))
            return outs, jax.tree.map(lambda _: True, outs)
        lane = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args[:n_lane_args], lane_b)]
        shapes = jax.eval_shape(fn, *[a[0] for a in lane],
                                *args[n_lane_args:])
        flat = [a.reshape((axis_size * a.shape[1],) + a.shape[2:])
                for a in lane]
        outs = fn(*flat, *args[n_lane_args:])

        def per_lane(o, s):
            rows = axis_size * s.shape[0]
            if o.shape[0] < rows:
                o = jnp.pad(o, [(0, rows - o.shape[0])] + [(0, 0)] * (o.ndim - 1))
            return o[:rows].reshape((axis_size,) + s.shape)

        outs = jax.tree.map(per_lane, outs, shapes)
        return outs, jax.tree.map(lambda _: True, outs)

    return wrapped


@functools.lru_cache(maxsize=None)
def _share_ops(tile: int, first: int | None, routed: int, room: float):
    """(forward, forward that keeps residuals, backward) of the dispatch,
    each a joint call over the lanes: the bounded path where the call's held
    rows fit `share_rows`, else the worst-case path, by one `lax.cond` a
    call; one path where the bound is the worst case (every expert held).
    Every forward also says which it took: [N] float32, 1.0 the worst case
    (per token, so that a lane's part of a joint call is a slice of it).
    Each is a `jax.jit` of its own: a model's expert layers have one shape,
    and a layer is traced for its forward, its rematerialised forward and
    its backward, so both branches of every `cond` would be traced and
    lowered a dozen times over; jitted, once a shape (XLA inlines the
    calls)."""

    def sizes(idx, wg):
        p, n_held = idx.size, wg.shape[0]
        m = _worst_rows(p, n_held, tile)
        # every expert held: the call's rows are all of its pairs, whatever
        # the room
        return (m if first is None
                else share_rows(p, n_held, routed, tile, room)), m

    def fits(idx, wg, m_b):
        return _held_rows(idx, wg.shape[0], tile, first) <= m_b

    def forward(keep, x, idx, gate, wg, wu, wd):
        m_b, m = sizes(idx, wg)
        run = functools.partial(_share_forward, tile=tile, first=first)
        bounded = functools.partial(run, m=m_b, keep=keep)
        if m_b == m:        # the bound is the worst case: one path
            return (bounded(x, idx, gate, wg, wu, wd),
                    jnp.zeros((idx.shape[0],), jnp.float32))

        def worst(*a):
            y = run(*a, m=m, keep=False)
            if not keep:
                return y
            # residuals have ONE shape, the bounded path's (`_share_forward`):
            # zeros of it here, and the backward makes this path's own
            return y, tuple(
                [jnp.zeros((m_b, wg.shape[-1]), x.dtype) for _ in "gu"]
                + [jnp.zeros((size,), jnp.int32)
                   for size in (m_b, idx.size, m_b // tile, 1)])

        fit = fits(idx, wg, m_b)
        out = jax.lax.cond(fit, bounded, worst, x, idx, gate, wg, wu, wd)
        return out, jnp.broadcast_to(1.0 - fit.astype(jnp.float32),
                                     (idx.shape[0],))

    def backward(x, idx, gate, g, u, src, row_of_pair, grp, nt, dy,
                 wg, wu, wd):
        res = (g, u, src, row_of_pair, grp, nt)
        m_b, m = sizes(idx, wg)
        run = functools.partial(_share_backward, tile=tile, first=first)
        if m_b == m:
            return run(x, idx, gate, wg, wu, wd, res, dy, m=m)

        def bounded(x, idx, gate, res, dy, wg, wu, wd):
            return run(x, idx, gate, wg, wu, wd, res, dy, m=m_b)

        def worst(x, idx, gate, res, dy, wg, wu, wd):
            return run(x, idx, gate, wg, wu, wd, None, dy, m=m)

        # the same count of the same idx as the forward's: both sides agree
        return jax.lax.cond(fits(idx, wg, m_b), bounded, worst,
                            x, idx, gate, res, dy, wg, wu, wd)

    return (_lanes_together(jax.jit(functools.partial(forward, False)), 3),
            _lanes_together(jax.jit(functools.partial(forward, True)), 3),
            _lanes_together(jax.jit(backward), 10))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def routed_experts_share(x, idx, gate, wg, wu, wd, first: int | None,
                         routed: int, tile: int = TILE):
    """x [N, d], idx [N, k] int32, gate [N, k], wg/wu [E, d, f], wd [E, f, d]
    where the matrices are experts first .. first + E - 1 of the router's
    `routed` that `idx` counts (module docstring; `first` None: all of them)
    -> (y [N, d] in x's dtype, worst [N] float32: 1.0 where the call's held
    rows did not fit the bounded buffer and it took the worst-case path)."""
    return _share_ops(tile, first, routed, SHARE_ROOM)[0](
        x, idx, gate, wg, wu, wd)


def _res_fwd(x, idx, gate, wg, wu, wd, first, routed, tile):
    (y, res), worst = _share_ops(tile, first, routed, SHARE_ROOM)[1](
        x, idx, gate, wg, wu, wd)
    return (y, worst), (x, idx, gate, res, wg, wu, wd)


def _res_bwd(first, routed, tile, saved, cot):
    x, idx, gate, res, wg, wu, wd = saved
    dx, dgate = _share_ops(tile, first, routed, SHARE_ROOM)[2](
        x, idx, gate, *res, cot[0], wg, wu, wd)
    return dx, None, dgate, None, None, None


routed_experts_share.defvjp(_res_fwd, _res_bwd)


def routed_experts(x, idx, gate, wg, wu, wd, tile: int = TILE):
    """The dispatch where every expert `idx` counts is held -> y [N, d]: the
    share with `first` None and `routed` = E, one path with no `cond`."""
    return routed_experts_share(x, idx, gate, wg, wu, wd, None, wg.shape[0],
                                tile)[0]
