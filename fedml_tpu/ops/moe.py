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

Differentiation: `routed_experts` is a `jax.custom_vjp` whose backward gives
the gradient with respect to `x` and `gate` ONLY. The expert matrices get
none: they are a frozen base (`models/lora.py` gives 3-D kernels no
adapter), which is the one way the model that uses this op is trained
(it says `frozen_base_only`, and `experiments/common.py::build_trainer`
refuses such a module without `--lora_rank`).

**A share of the experts (`first`).** An expert-parallel layer routes over
more experts than a chip holds. With `first` given, `idx` names the router's
experts and the matrices are those of experts first .. first + E - 1: a pair
whose expert is absent leaves the dispatch before the grouped product (it
gets no row, adds nothing to `y` and takes no gradient; `gate` keeps what
the router gave it, renormalised over all k). How many pairs stay is data;
the buffers are static and sized for the WORST case, every pair on a held
expert (M = N k + E tile rows, as when all are held): no bound under it can
never drop a pair. The grouped product skips the unused tiles (`n_tiles`);
the elementwise work and the gathers around it run over all M rows, which at
a quarter of the experts is about three times what the held pairs need
(PERF.md section 7). With `first` None it is the path it was.

Under `vmap` (the engine's client axis) with matrices that are the same for
every lane, as a frozen base is, the lanes' tokens are dispatched TOGETHER:
one sort, one grouped product over the cohort's tokens
(`jax.custom_batching.custom_vmap`; a token's result does not depend on
which other tokens share the call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fedml_tpu.ops.interpret import interpret_off_chip

#: rows of one grouped-product tile (the MXU's 128 on a v5e)
TILE = 128


def top_k_route(scores, k: int):
    """Greedy top-k by value over the last axis: -> (gate [.., k] the chosen
    scores as they are, idx [.., k] int32). Ties go to the lower index."""
    gate, idx = jax.lax.top_k(scores, k)
    return gate, idx.astype(jnp.int32)


def expert_load(idx, n_experts: int):
    """Tokens each expert received: [n_experts] float32 counts of `idx`."""
    return jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)


def _layout(idx, n_experts: int, tile: int, first=None):
    """Where each (token, slot) pair sits among the tiled rows.

    idx [N, k] -> (src [M] the pair that feeds each row, P = N * k for a
    filler row; row_of_pair [P]; tile_group [M // tile] the expert of each
    tile; n_tiles [1] the tiles that hold rows), M = P + n_experts * tile
    rounded up to the tile. With `first`, `idx` counts the router's experts,
    `n_experts` are held from there, and a pair on an absent one gets no row
    (its `row_of_pair` is M, out of range)."""
    n, k = idx.shape
    p = n * k
    m = -(-(p + n_experts * tile) // tile) * tile
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
        dest = jnp.where(sorted_e == n_experts, m, dest)
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


def _rows(x, src, k: int):
    """The tiled rows' inputs: x[src // k], zeros for filler rows."""
    return jnp.take(x, src // k, axis=0, mode="fill", fill_value=0)


def _pairs(rows, row_of_pair, n: int, k: int, share: bool = False):
    """Tiled rows back to [N, k, width]; of a share, zeros for the pairs
    that got no row."""
    if share:
        return jnp.take(rows, row_of_pair, axis=0, mode="fill",
                        fill_value=0).reshape(n, k, rows.shape[-1])
    return jnp.take(rows, row_of_pair, axis=0).reshape(n, k, rows.shape[-1])


def _forward(x, idx, gate, wg, wu, wd, tile, first=None):
    """-> (y [N, d], residuals (yk [N, k, d], g, u [M, f]))."""
    n, k = idx.shape
    src, row_of_pair, grp, nt = _layout(idx, wg.shape[0], tile, first)
    mm = functools.partial(grouped_matmul, tile_group=grp, n_tiles=nt,
                           tile=tile)
    xs = _rows(x, src, k)
    g, u = mm(xs, wg), mm(xs, wu)
    h = (_silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    yk = _pairs(mm(h, wd), row_of_pair, n, k, first is not None)
    # the k-term sums are elementwise (no float32 matrix product)
    y = (gate.astype(jnp.float32)[:, :, None]
         * yk.astype(jnp.float32)).sum(axis=1).astype(x.dtype)
    return y, (yk, g, u)


def _backward(idx, gate, wg, wu, wd, yk, g, u, dy, tile, first=None):
    """-> (dx [N, d], dgate [N, k])."""
    n, k = idx.shape
    src, row_of_pair, grp, nt = _layout(idx, wg.shape[0], tile, first)
    mm_t = functools.partial(grouped_matmul, tile_group=grp, n_tiles=nt,
                             tile=tile, trans_rhs=True)
    dy32 = dy.astype(jnp.float32)
    dgate = (dy32[:, None, :] * yk.astype(jnp.float32)).sum(axis=-1)
    dyk = (gate.astype(jnp.float32)[:, :, None] * dy32[:, None, :]).astype(
        dy.dtype).reshape(n * k, -1)
    dys = jnp.take(dyk, src, axis=0, mode="fill", fill_value=0)
    dh = mm_t(dys, wd).astype(jnp.float32)
    # (lanes dispatched together hand back lanes x M rows: the tail is filler)
    g32 = g[:src.shape[0]].astype(jnp.float32)
    u32 = u[:src.shape[0]].astype(jnp.float32)
    sig = jax.nn.sigmoid(g32)
    dg = (dh * u32 * sig * (1.0 + g32 * (1.0 - sig))).astype(dy.dtype)
    du = (dh * g32 * sig).astype(dy.dtype)
    # filler rows past a group's real ones carry what an unvisited tile left
    # there: they are never gathered back
    dxs = mm_t(dg, wg).astype(jnp.float32) + mm_t(du, wu).astype(jnp.float32)
    dx = _pairs(dxs, row_of_pair, n, k, first is not None).sum(
        axis=1).astype(dy.dtype)
    return dx, dgate.astype(gate.dtype)


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
def _ops(tile: int, first=None):
    def fwd(x, idx, gate, wg, wu, wd):
        return _forward(x, idx, gate, wg, wu, wd, tile, first)

    def bwd(idx, gate, yk, g, u, dy, wg, wu, wd):
        return _backward(idx, gate, wg, wu, wd, yk, g, u, dy, tile, first)

    return _lanes_together(fwd, 3), _lanes_together(bwd, 6)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def routed_experts(x, idx, gate, wg, wu, wd, tile: int = TILE, first=None):
    """x [N, d], idx [N, k] int32, gate [N, k], wg/wu [E, d, f], wd [E, f, d]
    -> y [N, d] in x's dtype (module docstring). `first`: the matrices are a
    share, experts first .. first + E - 1 of those `idx` counts."""
    return _ops(tile, first)[0](x, idx, gate, wg, wu, wd)[0]


def _re_fwd(x, idx, gate, wg, wu, wd, tile, first):
    y, (yk, g, u) = _ops(tile, first)[0](x, idx, gate, wg, wu, wd)
    return y, (idx, gate, yk, g, u, wg, wu, wd)


def _re_bwd(tile, first, res, dy):
    idx, gate, yk, g, u, wg, wu, wd = res
    dx, dgate = _ops(tile, first)[1](idx, gate, yk, g, u, dy, wg, wu, wd)
    return dx, None, dgate, None, None, None


routed_experts.defvjp(_re_fwd, _re_bwd)
