"""The selective scan of a Mamba-1 layer (arXiv:2312.00752): a diagonal state
a channel, its decay a channel AND a state.

A channel d keeps N states, h_0 = 0, and reads them a token:

    h_t[d, n] = exp(delta_t[d] A[d, n]) h_{t-1}[d, n] + delta_t[d] B_t[n] x_t[d]
    y_t[d]    = sum_n C_t[n] h_t[d, n]

`selective_scan(x, delta, A, B, C)` gives y; the skip `D x` stays with the
caller. No product form exists (the decay differs a state), so the work is
elementwise and sequential in time: the VPU's, not the MXU's. In XLA it would
be a scan of T sequential steps, or an associative scan that keeps every
token's [D, N] state (2.7 GB a sequence of 8,192 at D 5,120, N 16).

Two Pallas calls a trace can name, over the grid batch x channel block x
chunk of tokens:

  `ssm_scan_fwd`  channels on lanes (a block of `BLOCK` a program), the N
                  states on sublanes, time inside the kernel: a [N, block]
                  float32 state in VMEM scratch across the chunk axis
                  ("arbitrary"; batch and channel blocks "parallel"). With a
                  gradient wanted it also writes the state every chunk starts
                  from ([B, chunks, N, D] float32).
  `ssm_scan_bwd`  the chunks in reverse: a chunk's states recomputed from its
                  kept start into VMEM, then the reverse recurrence
                      lam_t = C_t g_t + exp(delta_{t+1} A) lam_{t+1}
                      dx_t  = delta_t sum_n lam_t B_t
                      ddelta_t = x_t sum_n lam_t B_t
                                 + sum_n lam_t A exp(delta_t A) h_{t-1}
                      dA   += lam_t delta_t exp(delta_t A) h_{t-1}
                      dB_t  = sum_d lam_t delta_t x_t,  dC_t = sum_d g_t h_t
                  with lam carried across chunks in scratch and dA
                  accumulated in the output block the chunk axis keeps; dB
                  and dC leave a block's partial sums, added outside.

Time runs in a loop over groups of eight tokens (a sublane tile of x's and
delta's rows), the eight unrolled. Everything in the recurrence is float32
(x may come in bfloat16; y and every cotangent but x's are float32). B and C
are laid out [B, chunks, chunk / 8, N, 8], so that a group's B_t is a column
of a tile found by a leading index. A sequence that is no multiple of the
chunk is padded with delta = 0, x = 0 (a decay of 1 and no input: no state
changes) and the padding's rows dropped. Off the chip both calls run in
interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fedml_tpu.ops.interpret import interpret_off_chip

#: tokens a chunk and channels a program (PERF.md section 6: on a v5e at the
#: jamba2_lora cell's shapes forward + backward of one layer read 17.5 ms
#: at 128 x 1,024, 24.7 at 128 x 512, 43.1 at 128 x 256; 256 x 1,024 runs
#: out of VMEM)
CHUNK = 128
BLOCK = 1024
_F32 = jnp.float32
_ROWS = 8           # tokens a group: a sublane tile of rows


def _block(d: int, block: int) -> int:
    """The channel block: `block` where it divides d, else the largest
    multiple of 128 under it that does, else the whole axis."""
    for b in range(block, 127, -128):
        if d % b == 0:
            return b
    return d


def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _group_fwd(g, h, a, x_ref, d_ref, b_ref, c_ref, rows, keep_state):
    """Eight tokens from state h -> (their y rows [8, block], the state);
    `keep_state(t, h)` is handed every token's state."""
    from jax.experimental import pallas as pl

    span = pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS)
    xs, ds = x_ref[span, :].astype(_F32), d_ref[span, :]
    bt, ct = b_ref[g], c_ref[g]                      # [N, 8]
    ys = jnp.zeros(xs.shape, _F32)
    for j in range(_ROWS):
        dt = ds[j:j + 1]
        h = jnp.exp(dt * a) * h + bt[:, j:j + 1] * (dt * xs[j:j + 1])
        ys = jnp.where(rows == j,
                       jnp.sum(ct[:, j:j + 1] * h, axis=0, keepdims=True), ys)
        keep_state(g * _ROWS + j, h)
    return ys, h


def _fwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, y_ref, *rest, keep):
    from jax.experimental import pallas as pl

    h_scr = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)

    if keep:
        rest[0][...] = h_scr[...]
    a = a_ref[...]
    rows = _iota((_ROWS, x_ref.shape[1]), 0)

    def group(g, h):
        ys, h = _group_fwd(g, h, a, x_ref, d_ref, b_ref, c_ref, rows,
                           lambda t, h: None)
        y_ref[pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS), :] = ys
        return h

    h_scr[...] = jax.lax.fori_loop(0, b_ref.shape[0], group, h_scr[...])


def _bwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, s_ref, g_ref,
                dx_ref, dd_ref, da_ref, db_ref, dc_ref, lam_scr, hs_scr):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        lam_scr[...] = jnp.zeros_like(lam_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    a = a_ref[...]
    groups = b_ref.shape[0]
    rows = _iota((_ROWS, x_ref.shape[1]), 0)
    lanes = _iota(b_ref.shape[1:], 1)

    # the chunk's states again from the one it starts from: hs[t] is the
    # state before token t, hs[t + 1] the one after it
    def keep(t, h):
        hs_scr[t + 1] = h

    hs_scr[0] = s_ref[...]
    jax.lax.fori_loop(0, groups, lambda g, h: _group_fwd(
        g, h, a, x_ref, d_ref, b_ref, c_ref, rows, keep)[1], s_ref[...])

    def group(i, carry):
        carry, da = carry                 # carry: exp(delta_{t+1} A) lam_{t+1}
        g = groups - 1 - i
        span = pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS)
        xs, ds = x_ref[span, :].astype(_F32), d_ref[span, :]
        gs = g_ref[span, :]
        bt, ct = b_ref[g], c_ref[g]
        dxs = jnp.zeros(xs.shape, _F32)
        dds = jnp.zeros(xs.shape, _F32)
        dbt = jnp.zeros(bt.shape, _F32)
        dct = jnp.zeros(ct.shape, _F32)
        for j in reversed(range(_ROWS)):
            t = g * _ROWS + j
            dt, xt, gt = ds[j:j + 1], xs[j:j + 1], gs[j:j + 1]
            e = jnp.exp(dt * a)
            lam = carry + ct[:, j:j + 1] * gt
            dct = jnp.where(lanes == j, jnp.sum(gt * hs_scr[t + 1], axis=1,
                                                keepdims=True), dct)
            dbt = jnp.where(lanes == j, jnp.sum(lam * (dt * xt), axis=1,
                                                keepdims=True), dbt)
            through_b = jnp.sum(lam * bt[:, j:j + 1], axis=0, keepdims=True)
            p = lam * hs_scr[t] * e
            da = da + p * dt
            dxs = jnp.where(rows == j, dt * through_b, dxs)
            dds = jnp.where(rows == j, xt * through_b + jnp.sum(
                p * a, axis=0, keepdims=True), dds)
            carry = e * lam
        dx_ref[span, :] = dxs.astype(dx_ref.dtype)
        dd_ref[span, :] = dds
        db_ref[g] = dbt
        dc_ref[g] = dct
        return carry, da

    carry, da = jax.lax.fori_loop(0, groups, group,
                                  (lam_scr[...], jnp.zeros_like(a)))
    lam_scr[...] = carry
    da_ref[...] += da


def _specs(chunk: int, block: int, n: int, n_chunks: int, backwards: bool):
    """BlockSpecs over the grid (batch, channel block, chunk): the [B, T, D]
    arrays, A^T [N, D], B^T / C^T [B, chunks, chunk / 8, N, 8], the kept
    states [B, chunks, N, D]; `backwards` walks the chunk axis from its
    end."""
    from jax.experimental import pallas as pl

    at = (lambda k: n_chunks - 1 - k) if backwards else (lambda k: k)
    wide = pl.BlockSpec((None, chunk, block), lambda b, c, k: (b, at(k), c))
    a = pl.BlockSpec((n, block), lambda b, c, k: (0, c))
    bc = pl.BlockSpec((None, None, chunk // _ROWS, n, _ROWS),
                      lambda b, c, k: (b, at(k), 0, 0, 0))
    states = pl.BlockSpec((None, None, n, block),
                          lambda b, c, k: (b, at(k), 0, c))
    return wide, a, bc, states


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _call_fwd(x, delta, at, bt, ct, chunk, block, interpret, keep):
    """-> y [B, T, D] float32 (and, with `keep`, the state every chunk
    starts from [B, chunks, N, D])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, d = x.shape
    n, n_chunks = at.shape[0], t // chunk
    wide, aspec, bc, states = _specs(chunk, block, n, n_chunks, False)
    out_shape = [jax.ShapeDtypeStruct((b, t, d), _F32)]
    out_specs = [wide]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((b, n_chunks, n, d), _F32))
        out_specs.append(states)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, keep=keep),
        grid=(b, d // block, n_chunks),
        in_specs=[wide, wide, aspec, bc, bc],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, block), _F32)],
        compiler_params=_params(), interpret=interpret, name="ssm_scan_fwd",
    )(x, delta, at, bt, ct)


def _call_bwd(x, delta, at, bt, ct, states, dy, chunk, block, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, d = x.shape
    n, n_chunks = at.shape[0], t // chunk
    n_blocks = d // block
    wide, aspec, bc, states_spec = _specs(chunk, block, n, n_chunks, True)
    da = pl.BlockSpec((None, n, block), lambda i, c, k: (i, 0, c))
    groups = chunk // _ROWS
    part = pl.BlockSpec((None, None, None, groups, n, _ROWS),
                        lambda i, c, k: (i, c, n_chunks - 1 - k, 0, 0, 0))
    part_shape = jax.ShapeDtypeStruct(
        (b, n_blocks, n_chunks, groups, n, _ROWS), _F32)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(b, n_blocks, n_chunks),
        in_specs=[wide, wide, aspec, bc, bc, states_spec, wide],
        out_specs=[wide, wide, da, part, part],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((b, n, d), _F32),
                   part_shape, part_shape],
        scratch_shapes=[pltpu.VMEM((n, block), _F32),
                        pltpu.VMEM((chunk + 1, n, block), _F32)],
        compiler_params=_params(), interpret=interpret, name="ssm_scan_bwd",
    )(x, delta, at, bt, ct, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _core(x, delta, at, bt, ct, chunk, block, interpret):
    return _call_fwd(x, delta, at, bt, ct, chunk, block, interpret, False)[0]


def _core_fwd(x, delta, at, bt, ct, chunk, block, interpret):
    y, states = _call_fwd(x, delta, at, bt, ct, chunk, block, interpret, True)
    return y, (x, delta, at, bt, ct, states)


def _core_bwd(chunk, block, interpret, res, dy):
    dx, dd, da, db, dc = _call_bwd(*res, dy, chunk, block, interpret)
    return dx, dd, da.sum(0), db.sum(1), dc.sum(1)


_core.defvjp(_core_fwd, _core_bwd)


def _by_chunk(m, chunk: int):
    """[B, T, N] -> [B, T / chunk, chunk / 8, N, 8]: eight tokens' columns a
    tile, so that a group of eight is found by a leading index."""
    b, t, n = m.shape
    return m.astype(_F32).reshape(b, t // chunk, chunk // _ROWS, _ROWS,
                                  n).transpose(0, 1, 2, 4, 3)


def selective_scan(x, delta, A, B, C, *, chunk: int = CHUNK,
                   block: int = BLOCK, interpret=None):
    """x, delta [Bt, T, D]; A [D, N]; B, C [Bt, T, N] -> y [Bt, T, D] float32,
    without the skip (module docstring). delta, A, B and C are taken in
    float32; x in its own dtype, which its cotangent keeps."""
    if interpret is None:
        interpret = interpret_off_chip("ssm_scan")
    b, t, d = x.shape
    chunk = min(chunk, -(-t // _ROWS) * _ROWS)
    pad = -t % chunk
    delta = delta.astype(_F32)
    if pad:
        x, delta, B, C = (jnp.pad(z, ((0, 0), (0, pad), (0, 0)))
                          for z in (x, delta, B, C))
    y = _core(x, delta, A.astype(_F32).T, _by_chunk(B, chunk),
              _by_chunk(C, chunk), chunk, _block(d, block), interpret)
    return y[:, :t]
