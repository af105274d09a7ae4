"""Kimi Delta Attention's core (Kimi Linear, arXiv:2510.26692): the gated
delta rule with a decay a CHANNEL, chunked.

A head keeps a state S in R^(K x V), S_0 = 0, and reads it a token:

    q_t = q~_t / |q~_t| * K^-1/2,  k_t = k~_t / |k~_t|   (eps 1e-6 under the root)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

`kda(q, k, v, g, beta)` takes the convolved q~, k~, v, the log-decay g <= 0
and beta in (0, 1) and gives o. `kda_reference` is that recurrence token by
token (a `lax.scan`; what the chip kernel is checked against, nobody ships
it). The kernel works a chunk of C tokens at a time. With G the cumulative
sum of g inside the chunk, u_t = beta_t (v_t - (Diag(exp g_t) S_{t-1})^T k_t)
the rule's "new value" and S the state the chunk starts from:

    Akk[t,s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])   s <  t
    Aqk[t,s] = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])   s <= t
    (I + beta Akk) u = beta (v - (k exp G) S)              a triangular system
    o   = (q exp G) S + Aqk u
    S'  = Diag(exp G_C) S + (k exp(G_C - G))^T u

**The decay a channel is the hazard.** exp(G_t - G_s) must not be formed as
exp(G_t) exp(-G_s) over a chunk: under strong decay the second factor
overflows float32. As the paper's implementation does, the chunk is cut into
sub-blocks of `SUB` rows. A block (i, j < i) is a matrix product of
q exp(G - G_first(i)) and k exp(G_first(i) - G), both factors <= 1. Inside a
diagonal block the exponent is taken PAIR BY PAIR, one diagonal of the block
at a time (rows rolled by d = t - s: exp(G_t - G_{t-d}) <= 1 always), on the
VPU. The triangular system is solved by its inverse: inside a sub-block the
Neumann product (I + N)(I + N^2)(I + N^4).. of the nilpotent N = -beta Akk
(at most SUB - 1 factors of N, so its powers stay small), the sub-blocks then
merged by the block formula inv([[P, 0], [L, Q]]) = [[P', 0], [-Q' L P', Q']],
which is exact. G, S, the products and the solve are float32 (products at
`highest`); q, k, v come and o goes in the caller's dtype.

Two Pallas calls a trace can name: `kda_fwd` (grid batch x head x chunk, the
head's state, held TRANSPOSED [V, K] so that the decay multiplies lanes, in
VMEM scratch across the chunk axis; with a gradient wanted it also writes the
state every chunk starts from, 32 x 128 x 128 float32 a head and sequence of
4,096) and `kda_bwd` (the same grid walked backwards, dS carried in scratch;
it recomputes the chunk's A, its inverse and u from the kept state). The
arrays stay [B, T, H * K]: a block is one head's 128 lanes of a chunk's rows,
so nothing is transposed around the calls but beta. Only the cumulative sum
of g inside a chunk is outside them (`kda` makes it; autodiff turns its
gradient back). Off the chip both run in interpret mode. A sequence that is
no multiple of the chunk is padded with rows of beta = 0, g = 0, which change
no state, and the padding's outputs are dropped; a sequence shorter than the
chunk is one chunk of the next power of two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fedml_tpu.ops.interpret import interpret_off_chip

#: tokens a chunk and rows a sub-block (PERF.md section 6, PR 38, step 0:
#: 128 read 63.2 ms forward + backward at the cell's shapes, 64 67.0, 32 80.8)
CHUNK = 128
SUB = 16
L2_EPS = 1e-6

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_F32 = jnp.float32


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def kda_reference(q, k, v, g, beta):
    """The recurrence of the module docstring, token by token, in float32.
    q, k, g [B, T, H, K]; v [B, T, H, V]; beta [B, T, H] -> o [B, T, H, V]."""
    f = lambda a: jnp.moveaxis(a.astype(_F32), 1, 0)  # noqa: E731
    kdim = q.shape[-1]

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    def step(s, x):
        qt, kt, vt, gt, bt = x                       # [B, H, .]
        qt, kt = unit(qt) * kdim ** -0.5, unit(kt)
        s = s * jnp.exp(gt)[..., None]               # [B, H, K, V]
        ut = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                              precision="highest"))
        s = s + kt[..., None] * ut[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision="highest")

    b, _, h, _ = q.shape
    s0 = jnp.zeros((b, h, kdim, v.shape[-1]), _F32)
    _, o = jax.lax.scan(step, s0, (f(q), f(k), f(v), f(g), f(beta)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


# ------------------------------------------------------------ a chunk's math
# (plain array code on a block's values: the Pallas kernels run it; step 0
# timed the same lines under vmap and scan in plain XLA, PERF.md section 6)

def _log2(n: int) -> int:
    assert n & (n - 1) == 0, f"{n} is no power of two"
    return n.bit_length() - 1


def _grid(c: int):
    shape = (c, c)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _normalise(x, scale: float):
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + L2_EPS)
    return x * (r * scale), r


def _decay_products(q, k, G, roll, sub: int):
    """-> (Aqk [C, C], zero above the diagonal; Akk, zero on and above it)."""
    c = q.shape[0]
    row, col = _grid(c)
    sh = _log2(sub)
    rb, cb = row >> sh, col >> sh
    off_q, off_k = [jnp.zeros((sub, c), _F32)], [jnp.zeros((sub, c), _F32)]
    for i in range(1, c // sub):
        r = slice(i * sub, (i + 1) * sub)
        first = G[i * sub:i * sub + 1]
        e1 = jnp.exp(G[r] - first)
        right = k * jnp.exp(jnp.minimum(first - G, 0.0))
        blk = _dot(jnp.concatenate([q[r] * e1, k[r] * e1], axis=0), right, _NT)
        off_q.append(blk[:sub])
        off_k.append(blk[sub:])
    below = rb > cb
    aqk = jnp.where(below, jnp.concatenate(off_q, axis=0), 0.0)
    akk = jnp.where(below, jnp.concatenate(off_k, axis=0), 0.0)
    same = rb == cb
    for d in range(sub):
        ke = k if d == 0 else roll(k, d) * jnp.exp(
            jnp.minimum(G - roll(G, d), 0.0))
        on = same & (row - col == d)
        aqk = jnp.where(on, jnp.sum(q * ke, axis=1, keepdims=True), aqk)
        if d:
            akk = jnp.where(on, jnp.sum(k * ke, axis=1, keepdims=True), akk)
    return aqk, akk


def _decay_products_bwd(q, k, G, d_aqk, d_akk, roll, sub: int):
    """The cotangents of `_decay_products`' two outputs -> (dq, dk, dG)."""
    c = q.shape[0]
    row, col = _grid(c)
    sh = _log2(sub)
    rb, cb = row >> sh, col >> sh
    below = rb > cb
    mq, mk = jnp.where(below, d_aqk, 0.0), jnp.where(below, d_akk, 0.0)
    zero = jnp.zeros((sub, q.shape[1]), _F32)
    dq_rows, dk_rows, dg_rows = [zero], [zero], [zero]
    dk_cols = dg_cols = jnp.zeros_like(k)
    for i in range(1, c // sub):
        r = slice(i * sub, (i + 1) * sub)
        first = G[i * sub:i * sub + 1]
        e1 = jnp.exp(G[r] - first)
        e2 = jnp.exp(jnp.minimum(first - G, 0.0))
        lq, lk, right = q[r] * e1, k[r] * e1, k * e2
        dblk = jnp.concatenate([mq[r], mk[r]], axis=0)          # [2 sub, C]
        dl = _dot(dblk, right, _NN)                              # [2 sub, K]
        dr = _dot(dblk, jnp.concatenate([lq, lk], axis=0), _TN)  # [C, K]
        dq_rows.append(dl[:sub] * e1)
        dk_rows.append(dl[sub:] * e1)
        dg_rows.append(dl[:sub] * lq + dl[sub:] * lk)
        dk_cols = dk_cols + dr * e2
        dg_cols = dg_cols + dr * right
    dq = jnp.concatenate(dq_rows, axis=0)
    dk = jnp.concatenate(dk_rows, axis=0) + dk_cols
    dg = jnp.concatenate(dg_rows, axis=0) - dg_cols
    same = rb == cb
    for d in range(sub):
        on = same & (row - col == d)
        wq = jnp.sum(jnp.where(on, d_aqk, 0.0), axis=1, keepdims=True)
        if d == 0:
            dq, dk = dq + wq * k, dk + wq * q
            continue
        back = functools.partial(roll, shift=c - d)
        kd = roll(k, d)
        e = jnp.exp(jnp.minimum(G - roll(G, d), 0.0))
        wk = jnp.sum(jnp.where(on, d_akk, 0.0), axis=1, keepdims=True)
        tq, tk = wq * e, wk * e
        dq = dq + tq * kd
        as_s = tq * q + tk * k          # what row t hands the row t - d
        dk = dk + tk * kd + back(as_s)
        x = as_s * kd
        dg = dg + x - back(x)
    return dq, dk, dg


def _solve(a, sub: int):
    """(I + a)^-1 for a [C, C] that is zero on and above the diagonal."""
    c = a.shape[0]
    row, col = _grid(c)
    sh = _log2(sub)
    n = jnp.where((row >> sh) == (col >> sh), -a, 0.0)
    inv = (row == col).astype(_F32) + n
    power, covered = n, 2
    while covered < sub:
        power = _dot(power, power, _NN)
        inv = inv + _dot(inv, power, _NN)
        covered *= 2
    m = sub
    while m < c:
        s1, s2 = _log2(m), _log2(2 * m)
        left = jnp.where(((row >> s2) == (col >> s2))
                         & ((row >> s1) > (col >> s1)), a, 0.0)
        inv = inv - _dot(_dot(inv, left, _NN), inv, _NN)
        m *= 2
    return inv


def _chunk_parts(qr, kr, v, G, beta, st, roll, sub: int):
    """What a chunk's forward and backward both need, from raw q, k [C, K],
    v [C, V], G [C, K], beta [C, 1] and the state transposed st [V, K], all
    float32: the normalised q, k with their inverse norms, the decayed
    products, the inverse, u, and the decays to the chunk's start and end."""
    c, kdim = qr.shape
    q, rq = _normalise(qr, kdim ** -0.5)
    k, rk = _normalise(kr, 1.0)
    aqk, akk = _decay_products(q, k, G, roll, sub)
    inv = _solve(beta * akk, sub)
    e_g = jnp.exp(G)
    w = v - _dot(k * e_g, st, _NT)
    u = _dot(inv, beta * w, _NN)
    last = G[c - 1:c]
    return q, k, rq, rk, aqk, akk, inv, e_g, w, u, jnp.exp(last - G), jnp.exp(last)


def _chunk_fwd(qr, kr, v, G, beta, st, roll, sub: int):
    """One chunk -> (o [C, V], the next st)."""
    q, k, _, _, aqk, _, _, e_g, _, u, e_d, gam = _chunk_parts(
        qr, kr, v, G, beta, st, roll, sub)
    o = _dot(q * e_g, st, _NT) + _dot(aqk, u, _NN)
    return o, gam * st + _dot(u, k * e_d, _TN)


def _chunk_bwd(qr, kr, v, G, beta, st, do, d_st, roll, sub: int):
    """`_chunk_fwd`'s transpose: with the cotangents of o [C, V] and of the
    next state [V, K] -> (dq~, dk~, dv, dG, dbeta [C, 1], d st)."""
    c, kdim = qr.shape
    scale = kdim ** -0.5
    q, k, rq, rk, aqk, akk, inv, e_g, w, u, e_d, gam = _chunk_parts(
        qr, kr, v, G, beta, st, roll, sub)
    kg, qg, kdec = k * e_g, q * e_g, k * e_d

    row, col = _grid(c)
    du = _dot(aqk, do, _TN) + _dot(kdec, d_st, _NT)
    d_aqk = jnp.where(row >= col, _dot(do, u, _NT), 0.0)
    d_rhs = _dot(inv, du, _TN)
    d_a = jnp.where(row > col, -_dot(d_rhs, u, _NT), 0.0)
    d_beta = (jnp.sum(d_rhs * w, axis=1, keepdims=True)
              + jnp.sum(d_a * akk, axis=1, keepdims=True))
    bd = beta * d_rhs                                   # = dv
    d_kg, d_qg = -_dot(bd, st, _NN), _dot(do, st, _NN)
    d_kdec = _dot(u, d_st, _NN)
    d_st0 = _dot(do, qg, _TN) + gam * d_st - _dot(bd, kg, _TN)
    d_gam = jnp.sum(st * d_st, axis=0, keepdims=True)   # [1, K]

    dq, dk, dg = _decay_products_bwd(q, k, G, d_aqk, beta * d_a, roll, sub)
    dq = dq + d_qg * e_g
    dk = dk + d_kg * e_g + d_kdec * e_d
    x = d_kdec * kdec
    dg = dg + d_qg * qg + d_kg * kg - x
    d_last = jnp.sum(x, axis=0, keepdims=True) + d_gam * gam
    rows = jax.lax.broadcasted_iota(jnp.int32, dg.shape, 0)
    dg = dg + jnp.where(rows == c - 1, d_last, 0.0)
    # through the L2 normalisation: x = x~ r s, r = (|x~|^2 + eps)^-1/2
    dqr = rq * (scale * dq - rq * qr * jnp.sum(dq * q, axis=1, keepdims=True))
    dkr = rk * (dk - rk * kr * jnp.sum(dk * k, axis=1, keepdims=True))
    return dqr, dkr, bd, dg, d_beta, d_st0


# ------------------------------------------------------------------ kernels

def _pl_roll(x, shift):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, sub, keep):
    from jax.experimental import pallas as pl

    st_scr = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        st_scr[...] = jnp.zeros_like(st_scr)

    st = st_scr[...]
    if keep:
        rest[0][...] = st
    o, st = _chunk_fwd(q_ref[...].astype(_F32), k_ref[...].astype(_F32),
                       v_ref[...].astype(_F32), g_ref[...], b_ref[...], st,
                       _pl_roll, sub)
    o_ref[...] = o.astype(o_ref.dtype)
    st_scr[...] = st


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, d_scr, *, sub):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        d_scr[...] = jnp.zeros_like(d_scr)

    dq, dk, dv, dg, db, d_st = _chunk_bwd(
        q_ref[...].astype(_F32), k_ref[...].astype(_F32),
        v_ref[...].astype(_F32), g_ref[...], b_ref[...], s_ref[...],
        do_ref[...].astype(_F32), d_scr[...], _pl_roll, sub)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dg_ref[...] = dg
    db_ref[...] = db
    d_scr[...] = d_st


def _specs(chunk: int, kdim: int, vdim: int, n_chunks: int, backwards: bool):
    """BlockSpecs over [B, T, H * width] arrays (one head's lanes of a
    chunk's rows), beta's [B, H, T, 1] and the kept states'
    [B, H, chunks, V, K]; `backwards` walks the chunk axis from its end."""
    from jax.experimental import pallas as pl

    at = (lambda c: n_chunks - 1 - c) if backwards else (lambda c: c)
    wide = lambda w: pl.BlockSpec(  # noqa: E731
        (None, chunk, w), lambda b, h, c: (b, at(c), h))
    beta = pl.BlockSpec((None, None, chunk, 1),
                        lambda b, h, c: (b, h, at(c), 0))
    states = pl.BlockSpec((None, None, None, vdim, kdim),
                          lambda b, h, c: (b, h, at(c), 0, 0))
    return wide(kdim), wide(vdim), beta, states


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _call_fwd(q, k, v, G, beta, chunk, sub, interpret, keep):
    """q, k, G [B, T, H*K], v [B, T, H*V], beta [B, H, T, 1] -> o (and, with
    `keep`, the state every chunk starts from [B, H, T/chunk, V, K])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, _ = q.shape
    h = beta.shape[1]
    kdim, vdim, n = q.shape[2] // h, v.shape[2] // h, t // chunk
    kspec, vspec, bspec, sspec = _specs(chunk, kdim, vdim, n, False)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [vspec]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((b, h, n, vdim, kdim), _F32))
        out_specs.append(sspec)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub, keep=keep),
        grid=(b, h, n),
        in_specs=[kspec, kspec, vspec, kspec, bspec],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((vdim, kdim), _F32)],
        compiler_params=_params(), interpret=interpret, name="kda_fwd",
    )(q, k, v, G, beta)


def _call_bwd(q, k, v, G, beta, states, do, chunk, sub, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, _ = q.shape
    h = beta.shape[1]
    kdim, vdim, n = q.shape[2] // h, v.shape[2] // h, t // chunk
    kspec, vspec, bspec, sspec = _specs(chunk, kdim, vdim, n, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub),
        grid=(b, h, n),
        in_specs=[kspec, kspec, vspec, kspec, bspec, sspec, vspec],
        out_specs=[kspec, kspec, vspec, kspec, bspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(G.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((vdim, kdim), _F32)],
        compiler_params=_params(), interpret=interpret, name="kda_bwd",
    )(q, k, v, G, beta, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _core(q, k, v, G, beta, chunk, sub, interpret):
    return _call_fwd(q, k, v, G, beta, chunk, sub, interpret, False)[0]


def _core_fwd(q, k, v, G, beta, chunk, sub, interpret):
    o, states = _call_fwd(q, k, v, G, beta, chunk, sub, interpret, True)
    return o, (q, k, v, G, beta, states)


def _core_bwd(chunk, sub, interpret, res, do):
    return tuple(_call_bwd(*res, do, chunk, sub, interpret))


_core.defvjp(_core_fwd, _core_bwd)


def _chunked(q, k, v, g, beta, chunk: int):
    """Pads T to the chunk and lays the arrays out as the kernels take them:
    -> (q, k, v [B, T', H*.], G the cumulative g inside a chunk, beta
    [B, H, T', 1])."""
    b, t, h, kdim = q.shape
    pad = -t % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (t + pad) // chunk
    G = jnp.cumsum(g.astype(_F32).reshape(b, n, chunk, h * kdim), axis=2)
    flat = lambda a: a.reshape(b, t + pad, -1)  # noqa: E731
    return (flat(q), flat(k), flat(v), flat(G),
            beta.astype(_F32).transpose(0, 2, 1)[..., None])


def kda(q, k, v, g, beta, *, chunk: int = CHUNK, sub: int = SUB,
        interpret=None):
    """q, k, g [B, T, H, K]; v [B, T, H, V]; beta [B, T, H] -> o [B, T, H, V]
    in v's dtype (module docstring). K and V are the lane width, 128."""
    if interpret is None:
        interpret = interpret_off_chip("kda")
    b, t, h, _ = q.shape
    chunk = min(chunk, max(sub, 1 << (t - 1).bit_length()))
    o = _core(*_chunked(q, k, v, g, beta, chunk), chunk, min(sub, chunk),
              interpret)
    return o[:, :t].reshape(b, t, h, v.shape[-1])
