"""Attention ops: a pallas TPU flash-attention forward kernel + jnp reference.

The reference framework has no attention models at all (SURVEY §2.9:
longest sequence = 80-char Shakespeare windows), but long-context support is
first-class here: this kernel is the single-chip building block, and
fedml_tpu.parallel.sequence composes it across chips (ring attention over
ICI / Ulysses all-to-all head sharding).

Design (flash-attention-1 style, /opt/skills/guides/pallas_guide.md):
- grid = (batch*heads, q_blocks); each program streams K/V blocks through
  VMEM, keeping running max M, denominator L and numerator accumulator O in
  f32 scratch — the online-softmax recurrence, so the full [T, T] score
  matrix never materializes.
- Q/K/V blocks are MXU-shaped (block 128 on sequence, full head dim lanes).
- training: `flash_attention` is a jax.custom_vjp with a BLOCKED backward
  (FlashAttention-2 style): the forward also emits the per-row logsumexp,
  and two streaming kernels recompute p block-by-block — dQ sweeping K
  blocks, dK/dV sweeping Q blocks — so no [T, T] score matrix ever
  materializes in either direction and the O(T) memory claim holds for
  training too. `parallel/sequence.py` ring attention composes the same
  recurrence across chips.
- off-TPU (tests, CPU CI) the kernel runs in pallas interpret mode, and
  says so once at warning level (ops/interpret.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.ops.interpret import interpret_off_chip


def _resolve_interpret(interpret):
    if interpret is None:
        return interpret_off_chip("flash_attention")
    return interpret


def _out_struct(shape, dtype, *inputs):
    """A pallas_call out_shape entry typed as varying over whatever mesh
    axes its inputs vary over. Under shard_map's check_vma (jax 0.9) a
    pallas_call whose outputs say nothing about that is refused — and the
    transformer runs this kernel inside every mesh round. Outside shard_map
    the set is empty and this is a plain ShapeDtypeStruct."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _block_live(qi, ki, q_block, k_block, causal):
    """Whether a (q-block, k-block) tile has any unmasked entries."""
    return (ki * k_block <= (qi + 1) * q_block - 1) if causal else (ki >= 0)


def _masked_scores(qb, kb, qi, ki, q_block, k_block, scale, causal, precision):
    """Scaled (and causally masked) score tile s = (q*scale) @ k^T — the
    single definition shared by the forward and both backward kernels so
    masking/scaling can never desynchronize between them."""
    s = jax.lax.dot(qb.astype(jnp.float32) * scale,
                    kb.astype(jnp.float32).T, precision=precision)
    if causal:
        q_idx = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = ki * k_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_idx >= k_idx, s, -jnp.inf)
    return s


def attention_reference(q, k, v, causal: bool = False, scale=None):
    """Plain-jnp scaled dot-product attention. q/k: [B, T, H, D], v:
    [B, T, H, Dv]; `scale` as `flash_attention`'s."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s / np.sqrt(d) if scale is None else s * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, o_scr, m_scr,
                      l_scr, *, causal, n_kb, q_block, k_block, scale,
                      precision):
    """Grid (batch*head, q_blocks, k_blocks): TPU iterates the last grid dim
    sequentially, so the f32 scratch accumulators (numerator O, running max
    M, denominator L) persist across the K-block sweep — K/V truly stream
    through VMEM one [block_k, D] tile at a time."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        o_scr[:] = jnp.zeros_like(o_scr)
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)

    # causal: K blocks strictly after this Q block's last row are all masked
    live = _block_live(qi, ki, q_block, k_block, causal)

    @pl.when(live)
    def _block():
        vb = v_ref[:]
        s = _masked_scores(q_ref[:], k_ref[:], qi, ki, q_block, k_block,
                           scale, causal, precision)
        m = m_scr[:]
        m_new = jnp.maximum(m, s.max(axis=-1))
        # exp(-inf - -inf) guard: rows with no valid keys yet keep m=-inf
        alpha = jnp.exp(jnp.where(m == -jnp.inf, 0.0, m - m_new))
        p = jnp.exp(s - m_new[:, None])
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=-1)
        o_scr[:] = o_scr[:] * alpha[:, None] + jax.lax.dot(
            p, vb.astype(jnp.float32), precision=precision)
        m_scr[:] = m_new

    @pl.when(ki == n_kb - 1)
    def _finalize():
        o_ref[:] = (o_scr[:] / jnp.maximum(l_scr[:], 1e-30)[:, None]
                    ).astype(o_ref.dtype)
        # per-row logsumexp of the scaled scores — the blocked backward's
        # residual (p is recomputed as exp(s - lse))
        lse_ref[:] = (m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30)))[:, None]


def _softmax_scale(scale, d: int) -> float:
    """The factor on q k^T: the caller's, or d^-0.5 of the q/k width."""
    return 1.0 / np.sqrt(d) if scale is None else float(scale)


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool, return_lse: bool = False, scale=None):
    from jax.experimental import pallas as pl

    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if tq % block_q or tk % block_k:
        raise ValueError(f"sequence lengths ({tq}, {tk}) must be multiples of "
                         f"the block sizes ({block_q}, {block_k})")
    # [B, T, H, D] -> [B*H, T, D] program-major layout
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, tk, dv)
    # f32 inputs get true-f32 MXU passes (measured: the kernel then matches
    # a HIGHEST-precision dense reference to ~1e-6 while XLA's default-
    # precision einsum drifts ~1e-2); bf16 inputs keep native MXU speed
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    n_kb = tk // block_k
    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, n_kb=n_kb,
        q_block=block_q, k_block=block_k,
        scale=_softmax_scale(scale, d), precision=precision)
    from jax.experimental.pallas import tpu as pltpu

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, tq // block_q, n_kb),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, dv), lambda g, i, j: (g, i, 0)),
            # trailing unit lane dim: Mosaic requires the block's last two
            # dims be (8,128)-divisible or equal to the array's
            pl.BlockSpec((None, block_q, 1), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[
            _out_struct((b * h, tq, dv), q.dtype, qr, kr, vr),
            _out_struct((b * h, tq, 1), jnp.float32, qr, kr, vr),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qr, kr, vr)
    out4 = out.reshape(b, h, tq, dv).transpose(0, 2, 1, 3)
    if return_lse:
        return out4, lse[..., 0]
    return out4


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None,
                    scale: float | None = None):
    """Flash attention, pallas kernels both ways. q/k: [B, T, H, D], v:
    [B, T, H, Dv]; the output has v's width. Dv may differ from D (latent
    attention trains with 192-wide q/k and 128-wide v): v is never padded to
    D. `scale` multiplies q k^T; None is D^-0.5.

    `interpret=None` auto-selects: compiled on TPU, interpret mode elsewhere
    (the CPU CI path). The backward is BLOCKED too (p recomputed per tile
    from the saved logsumexp) — O(T) memory for training as well."""
    return _flash_fwd(q, k, v, causal, block_q, block_k,
                      _resolve_interpret(interpret), scale=scale)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                         dq_ref, dq_scr, *, causal, n_kb, q_block, k_block,
                         scale, precision):
    """Grid (batch*head, q_blocks, k_blocks): sweeps K blocks, accumulating
    this Q block's gradient in f32 scratch. p is recomputed from the saved
    logsumexp, so only [block_q, block_k] tiles ever exist."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = _block_live(qi, ki, q_block, k_block, causal)

    @pl.when(live)
    def _block():
        kb = k_ref[:].astype(jnp.float32)
        vb = v_ref[:].astype(jnp.float32)
        dob = do_ref[:].astype(jnp.float32)
        s = _masked_scores(q_ref[:], k_ref[:], qi, ki, q_block, k_block,
                           scale, causal, precision)
        p = jnp.exp(s - lse_ref[...])                     # [bq, bk] via [bq,1]
        dp = jax.lax.dot(dob, vb.T, precision=precision)  # [bq, bk]
        ds = p * (dp - dl_ref[...])
        dq_scr[:] = dq_scr[:] + jax.lax.dot(ds, kb, precision=precision) * scale

    @pl.when(ki == n_kb - 1)
    def _done():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, causal, n_qb,
                          q_block, k_block, scale, precision):
    """Grid (batch*head, k_blocks, q_blocks): sweeps Q blocks, accumulating
    this K block's dK and dV in f32 scratch."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = _block_live(qi, ki, q_block, k_block, causal)

    @pl.when(live)
    def _block():
        qb = q_ref[:].astype(jnp.float32)
        vb = v_ref[:].astype(jnp.float32)
        dob = do_ref[:].astype(jnp.float32)
        s = _masked_scores(q_ref[:], k_ref[:], qi, ki, q_block, k_block,
                           scale, causal, precision)
        p = jnp.exp(s - lse_ref[...])                     # [bq, bk] via [bq,1]
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())), precision=precision)
        dp = jax.lax.dot(dob, vb.T, precision=precision)
        ds = p * (dp - dl_ref[...])
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())), precision=precision) * scale

    @pl.when(qi == n_qb - 1)
    def _done():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, block_q, block_k, interpret,
               scale=None):
    """Blocked backward: dq/dk/dv without materializing [T, T]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, tk, dv)
    orr = out.transpose(0, 2, 1, 3).reshape(b * h, tq, dv)
    gr = g.transpose(0, 2, 1, 3).reshape(b * h, tq, dv)
    # delta_i = rowsum(dO * O) — the softmax-jacobian diagonal term.
    # lse/delta ride as [B*H, Tq, 1] (unit lane dim for Mosaic block rules)
    delta = jnp.sum(gr.astype(jnp.float32) * orr.astype(jnp.float32),
                    axis=-1, keepdims=True)
    lse3 = lse[..., None]
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    scale = _softmax_scale(scale, d)
    n_qb, n_kb = tq // block_q, tk // block_k
    bwd_in = (qr, kr, vr, gr, lse3, delta)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, n_kb=n_kb,
                          q_block=block_q, k_block=block_k, scale=scale,
                          precision=precision),
        grid=(b * h, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda g_, i, j: (g_, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda g_, i, j: (g_, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda g_, i, j: (g_, j, 0)),
            pl.BlockSpec((None, block_q, dv), lambda g_, i, j: (g_, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda g_, i, j: (g_, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda g_, i, j: (g_, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda g_, i, j: (g_, i, 0)),
        out_shape=_out_struct((b * h, tq, d), q.dtype, *bwd_in),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(*bwd_in)

    dk, dv_ = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, n_qb=n_qb,
                          q_block=block_q, k_block=block_k, scale=scale,
                          precision=precision),
        grid=(b * h, n_kb, n_qb),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda g_, j, i: (g_, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda g_, j, i: (g_, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda g_, j, i: (g_, j, 0)),
            pl.BlockSpec((None, block_q, dv), lambda g_, j, i: (g_, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda g_, j, i: (g_, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda g_, j, i: (g_, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda g_, j, i: (g_, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda g_, j, i: (g_, j, 0)),
        ],
        out_shape=[
            _out_struct((b * h, tk, d), k.dtype, *bwd_in),
            _out_struct((b * h, tk, dv), v.dtype, *bwd_in),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dkv",
    )(*bwd_in)

    def back4(t, tlen):
        return t.reshape(b, h, tlen, t.shape[-1]).transpose(0, 2, 1, 3)

    return back4(dq, tq), back4(dk, tk), back4(dv_, tk)


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret, scale):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k,
                          _resolve_interpret(interpret), return_lse=True,
                          scale=scale)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, scale, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, causal, block_q, block_k,
                      _resolve_interpret(interpret), scale)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
