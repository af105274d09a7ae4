"""Attention ops: a pallas TPU flash-attention kernel (forward and blocked
backward) + jnp reference.

The reference framework has no attention models at all (SURVEY §2.9:
longest sequence = 80-char Shakespeare windows), but long-context support is
first-class here: this kernel is the single-chip building block, and
fedml_tpu.parallel.sequence composes it across chips (ring attention over
ICI / Ulysses all-to-all head sharding).

Design (flash-attention-1 style, /opt/skills/guides/pallas_guide.md):
- grid = (batch*heads, q_blocks, k_blocks); each program streams K/V blocks
  through VMEM, keeping running max M, denominator L and numerator
  accumulator O in f32 scratch — the online-softmax recurrence, so the full
  [T, T] score matrix never materializes.
- operands reach the MXU in the dtype they arrive in (bf16 q/k/v/dO stay
  bf16; f32 inputs multiply at Precision.HIGHEST); every product
  accumulates in f32, and scores, softmax, logsumexp, delta and the scratch
  accumulators are f32. p and ds are rounded to the operand dtype only as
  they enter the next product; `scale` multiplies the f32 score tile.
- tiles are the kernel's choice (`flash_blocks`): the largest block of each
  sequence axis up to 1024 that divides it, shrunk under a VMEM budget,
  separately for the forward, dq and dkv sweeps. On a v5e a grid step costs
  about half a microsecond, more than a 128 x 128 tile's products: at
  T 1024 the three kernels take 2.6 ms a forward + backward on one tile a
  head and 14.2 ms on 128 x 128 (PERF.md section 6, PR 37). A causal tile
  above the diagonal skips its body and its copy: its K/V (in dkv its Q/dO)
  index map repeats a live block's index.
- a sliding window (`window=W`, causal only): query i sees keys j with
  i - W < j <= i. The key sweep of a Q block (in dkv the Q sweep of a K
  block) runs over the band's blocks alone, not over the whole axis: its
  grid axis has as many steps as the widest band (`_band_steps`), a step
  names its K block as the band's first plus the step, held at the band's
  last past its end. Tiles outside the band are neither copied nor
  computed. The calls are named `flash_window_*`, the causal ones
  `flash_attention_*`, so that a trace tells them apart; `window=None` is
  the causal kernel as it was.
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

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b

# Step 0 of PR 37 (PERF.md section 6) read every kernel fastest at the
# largest tile it tried, 1024 each way; nothing larger has been measured.
_MAX_BLOCK = 1024
_LANES = 128
# What one grid step may hold by `_vmem_bytes`'s (generous) count, and the
# limit the compiler is given: a quarter of a v5e core's 128 MiB.
_VMEM_BUDGET = 32 << 20


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


def _candidate_blocks(t: int, align: int) -> list[int]:
    """Blocks of a length-t axis, largest first: the whole axis when it is
    at most `_MAX_BLOCK`, then its divisors that are multiples of `align`
    (the chip's tiling). An axis with neither gets its plain divisors: fine
    in interpret mode, and the chip's compiler says what it makes of them."""
    divisors = [b for b in range(min(t, _MAX_BLOCK), 0, -1) if t % b == 0]
    aligned = [b for b in divisors if b == t or b % align == 0]
    return aligned or divisors


def _vmem_bytes(sweep: str, bq: int, bk: int, d: int, dv: int,
                itemsize: int) -> int:
    """Upper estimate of the VMEM one grid step of `sweep` holds: every
    pipelined block twice, the f32 scratch, and the [bq, bk] f32 score-sized
    temporaries (s, p and their kin). The TPU compiler's own count at
    1024 x 1024 is a third of this (PR 37)."""
    def tile(rows, width, size=itemsize):      # the lane axis pads to 128
        return rows * -(-width // _LANES) * _LANES * size

    q, k, v, do = tile(bq, d), tile(bk, d), tile(bk, dv), tile(bq, dv)
    col = tile(bq, 1, 4)                       # lse, delta, m, l: a row a lane
    if sweep == "fwd":                         # o is dO's size
        piped, scratch, scores = q + k + v + do + col, tile(bq, dv, 4) + 2 * col, 3
    elif sweep == "dq":
        piped, scratch, scores = 2 * q + k + v + do + 2 * col, tile(bq, d, 4), 4
    else:
        piped = q + 2 * k + 2 * v + do + 2 * col
        scratch, scores = tile(bk, d, 4) + tile(bk, dv, 4), 4
    return 2 * piped + scratch + scores * bq * bk * 4


def flash_blocks(sweep: str, tq: int, tk: int, d: int, dv: int, dtype,
                 block_q: int | None = None, block_k: int | None = None,
                 window: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) of one sweep ("fwd", "dq" or "dkv") from what the
    kernel can see. An explicit block is taken as given (cut to the axis, and
    it has to divide it);
    the kernel's own is the largest candidate of the axis that keeps
    `_vmem_bytes` under the budget, the larger block giving way first (Q on
    a tie: the K sweep's length costs the forward most). Under a `window`
    the kernel's own candidates are no wider than the band (and never under
    128): at T 8,192 and W 1,024 on a v5e the forward and backward took
    19.8 ms on 1,024 tiles, 20.7 on 512 and 35.0 on 256 (PERF.md section
    4), fewer grid steps winning over fewer masked pairs."""
    itemsize = jnp.dtype(dtype).itemsize
    qs = ([min(block_q, tq)] if block_q
          else _candidate_blocks(tq, 8 * max(1, 4 // itemsize)))
    ks = [min(block_k, tk)] if block_k else _candidate_blocks(tk, _LANES)
    if window is not None:
        cap = max(_LANES, window)
        qs = qs if block_q else [b for b in qs if b <= cap] or qs[-1:]
        ks = ks if block_k else [b for b in ks if b <= cap] or ks[-1:]
    iq = ik = 0
    while _vmem_bytes(sweep, qs[iq], ks[ik], d, dv, itemsize) > _VMEM_BUDGET:
        q_next, k_next = iq + 1 < len(qs), ik + 1 < len(ks)
        if q_next and (qs[iq] >= ks[ik] or not k_next):
            iq += 1
        elif k_next:
            ik += 1
        else:
            break
    if tq % qs[iq] or tk % ks[ik]:
        raise ValueError(f"sequence lengths ({tq}, {tk}) must be multiples of "
                         f"the block sizes ({qs[iq]}, {ks[ik]})")
    return qs[iq], ks[ik]


def _dot(a, b, dims, precision):
    """An MXU product of operands as they are, accumulated in f32."""
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _precision(dtype):
    """f32 inputs get true-f32 MXU passes (measured: the kernel then matches
    a HIGHEST-precision dense reference to ~1e-6 while XLA's default-
    precision einsum drifts ~1e-2); narrower inputs are the MXU's own."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _block_live(qi, ki, q_block, k_block, causal):
    """Whether a (q-block, k-block) tile has any unmasked entries."""
    return (ki * k_block <= (qi + 1) * q_block - 1) if causal else (ki >= 0)


def _last_live_k(qi, q_block, k_block, n_kb):
    """The last K block a causal Q block attends to. A dead tile's K/V index
    map names this block again, so the pipeline copies nothing for it."""
    return jnp.minimum(((qi + 1) * q_block - 1) // k_block, n_kb - 1)


def _first_live_q(ki, q_block, k_block, n_qb):
    """The first Q block that attends to a causal K block: `_last_live_k`'s
    twin for the dkv sweep, whose dead tiles come first."""
    return jnp.minimum((ki * k_block) // q_block, n_qb - 1)


def _band_k(qi, q_block, k_block, window, xp=jnp):
    """(first, last) K block holding a key of Q block qi's band: the keys
    max(0, i - window + 1) .. i of its rows i, one contiguous stretch, so
    every block between the two holds some row's key."""
    first = xp.maximum(qi * q_block - window + 1, 0) // k_block
    return first, ((qi + 1) * q_block - 1) // k_block


def _band_q(ki, q_block, k_block, window, n_qb, xp=jnp):
    """(first, last) Q block whose band reaches K block ki: rows
    ki * k_block .. (ki + 1) * k_block + window - 2, cut at the axis."""
    last = xp.minimum(((ki + 1) * k_block + window - 2) // q_block, n_qb - 1)
    return (ki * k_block) // q_block, last


def _band_steps(sweep: str, t: int, q_block: int, k_block: int,
                window: int) -> int:
    """Grid steps of a band sweep: the most blocks one Q block's band spans
    (dkv: one K block's), over every block of the axis."""
    if sweep == "dkv":
        first, last = _band_q(np.arange(t // k_block), q_block, k_block,
                              window, t // q_block, np)
    else:
        first, last = _band_k(np.arange(t // q_block), q_block, k_block,
                              window, np)
    return int((last - first).max()) + 1


def band_tiles(t: int, q_block: int, k_block: int, window: int | None) -> int:
    """Tiles a head computes in the forward sweep: the band's live tiles, or
    under `window=None` the causal ones."""
    qi = np.arange(t // q_block)
    if window is None:
        return int((np.minimum(((qi + 1) * q_block - 1) // k_block,
                               t // k_block - 1) + 1).sum())
    first, last = _band_k(qi, q_block, k_block, window, np)
    return int((last - first + 1).sum())


def _masked_scores(qb, kb, qi, ki, q_block, k_block, scale, causal, precision,
                   window=None):
    """Scaled (and causally masked) f32 score tile s = (q @ k^T) * scale —
    the single definition shared by the forward and both backward kernels so
    masking/scaling can never desynchronize between them. The scale is on
    the f32 tile: q k^T keeps exact products of the stored operands. A
    window masks both edges of the band: i - window < j <= i."""
    s = _dot(qb, kb, _NT, precision) * scale
    if causal:
        q_idx = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = ki * k_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = q_idx >= k_idx
        if window is not None:
            seen = seen & (q_idx - k_idx < window)
        s = jnp.where(seen, s, -jnp.inf)
    return s


def attention_reference(q, k, v, causal: bool = False, scale=None,
                        window: int | None = None):
    """Plain-jnp scaled dot-product attention. q/k: [B, T, H, D], v:
    [B, T, H, Dv]; `scale` and `window` as `flash_attention`'s."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s / np.sqrt(d) if scale is None else s * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        if window is not None:
            mask = mask & (jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
                           < window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, o_scr, m_scr,
                      l_scr, *, causal, n_kb, q_block, k_block, scale,
                      precision, window=None):
    """Grid (batch*head, q_blocks, k_blocks): TPU iterates the last grid dim
    sequentially, so the f32 scratch accumulators (numerator O, running max
    M, denominator L; M and L a [block_q, 1] column) persist across the
    K-block sweep — K/V truly stream through VMEM one [block_k, D] tile at a
    time. Under a window the last grid dim counts the band's steps (`n_kb`
    of them) and step ki is K block first + ki."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        o_scr[:] = jnp.zeros_like(o_scr)
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)

    # causal: K blocks strictly after this Q block's last row are all masked
    live, kb = _sweep_step(qi, ki, q_block, k_block, causal, window)

    @pl.when(live)
    def _block():
        vb = v_ref[:]
        s = _masked_scores(q_ref[:], k_ref[:], qi, kb, q_block, k_block,
                           scale, causal, precision, window)
        m = m_scr[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        # exp(-inf - -inf) guard: rows with no valid keys yet keep m=-inf
        alpha = jnp.exp(jnp.where(m == -jnp.inf, 0.0, m - m_new))
        if window is None:
            p = jnp.exp(s - m_new)
        else:   # a band's row may have no key in its first live tile
            p = jnp.exp(s - jnp.where(m_new == -jnp.inf, 0.0, m_new))
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=-1, keepdims=True)
        o_scr[:] = o_scr[:] * alpha + _dot(p.astype(vb.dtype), vb, _NN,
                                           precision)
        m_scr[:] = m_new

    @pl.when(ki == n_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[:] = (o_scr[:] / l).astype(o_ref.dtype)
        # per-row logsumexp of the scaled scores — the blocked backward's
        # residual (p is recomputed as exp(s - lse))
        lse_ref[:] = m_scr[:] + jnp.log(l)


def _sweep_step(qi, ki, q_block, k_block, causal, window):
    """(whether tile (qi, step ki) computes, the K block it names) of a
    sweep over K blocks: step ki is block ki, or under a window the band's
    first block + ki, live up to the band's last."""
    if window is None:
        return _block_live(qi, ki, q_block, k_block, causal), ki
    first, last = _band_k(qi, q_block, k_block, window)
    return first + ki <= last, first + ki


def _softmax_scale(scale, d: int) -> float:
    """The factor on q k^T: the caller's, or d^-0.5 of the q/k width."""
    return 1.0 / np.sqrt(d) if scale is None else float(scale)


def _kv_sweep_maps(causal, block_q, block_k, n_kb, window=None):
    """Index maps of a (batch*head, q_blocks, k_blocks) grid: Q-side blocks
    follow the Q block, K-side blocks the K block, held at the last live one
    under a causal mask; under a window the K block is the band's first +
    the step, held at the band's last."""
    def q_map(g, i, j):
        return g, i, 0

    def k_map(g, i, j):
        if window is not None:
            first, last = _band_k(i, block_q, block_k, window)
            j = jnp.minimum(first + j, last)
        elif causal:
            j = jnp.minimum(j, _last_live_k(i, block_q, block_k, n_kb))
        return g, j, 0

    return q_map, k_map


def _sweep_len(sweep, causal, t, block_q, block_k, window, n_blocks):
    """Grid steps of a sweep: the axis's blocks, or the band's widest."""
    if window is None:
        return n_blocks
    if not causal:
        raise ValueError("a window is a causal band: give causal=True")
    return _band_steps(sweep, t, block_q, block_k, window)


def _call_name(sweep: str, window) -> str:
    return f"flash_{'attention' if window is None else 'window'}_{sweep}"


def _flash_fwd(q, k, v, causal: bool, block_q, block_k, interpret: bool,
               return_lse: bool = False, scale=None, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    if window is not None and tq != tk:
        raise ValueError(f"a window needs as many queries as keys, not "
                         f"({tq}, {tk})")
    block_q, block_k = flash_blocks("fwd", tq, tk, d, dv, q.dtype,
                                    block_q, block_k, window)
    # [B, T, H, D] -> [B*H, T, D] program-major layout
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, tk, dv)
    n_kb = _sweep_len("fwd", causal, tk, block_q, block_k, window,
                      tk // block_k)
    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, n_kb=n_kb,
        q_block=block_q, k_block=block_k,
        scale=_softmax_scale(scale, d), precision=_precision(q.dtype),
        **({} if window is None else {"window": window}))
    q_map, k_map = _kv_sweep_maps(causal, block_q, block_k, n_kb, window)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, tq // block_q, n_kb),
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_map),
            pl.BlockSpec((None, block_k, d), k_map),
            pl.BlockSpec((None, block_k, dv), k_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, dv), q_map),
            # trailing unit lane dim: Mosaic requires the block's last two
            # dims be (8,128)-divisible or equal to the array's
            pl.BlockSpec((None, block_q, 1), q_map),
        ],
        out_shape=[
            _out_struct((b * h, tq, dv), q.dtype, qr, kr, vr),
            _out_struct((b * h, tq, 1), jnp.float32, qr, kr, vr),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BUDGET),
        interpret=interpret,
        name=_call_name("fwd", window),
    )(qr, kr, vr)
    out4 = out.reshape(b, h, tq, dv).transpose(0, 2, 1, 3)
    if return_lse:
        return out4, lse[..., 0]
    return out4


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = False,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None,
                    scale: float | None = None, window: int | None = None):
    """Flash attention, pallas kernels both ways. q/k: [B, T, H, D], v:
    [B, T, H, Dv]; the output has v's width. Dv may differ from D (latent
    attention trains with 192-wide q/k and 128-wide v): v is never padded to
    D. `scale` multiplies q k^T; None is D^-0.5. `window` W (with `causal`,
    as many queries as keys) is a sliding window: query i sees keys
    i - W < j <= i, and only the band's tiles are visited.

    `block_q` / `block_k` None is the kernel's own choice for each of its
    three sweeps (`flash_blocks`); a given block is used in all three.
    `interpret=None` auto-selects: compiled on TPU, interpret mode elsewhere
    (the CPU CI path). The backward is BLOCKED too (p recomputed per tile
    from the saved logsumexp) — O(T) memory for training as well."""
    return _flash_fwd(q, k, v, causal, block_q, block_k,
                      _resolve_interpret(interpret), scale=scale,
                      window=window)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                         dq_ref, dq_scr, *, causal, n_kb, q_block, k_block,
                         scale, precision, window=None):
    """Grid (batch*head, q_blocks, k_blocks): sweeps K blocks, accumulating
    this Q block's gradient in f32 scratch. p is recomputed from the saved
    logsumexp, so only [block_q, block_k] tiles ever exist."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live, kb_i = _sweep_step(qi, ki, q_block, k_block, causal, window)

    @pl.when(live)
    def _block():
        kb = k_ref[:]
        s = _masked_scores(q_ref[:], kb, qi, kb_i, q_block, k_block,
                           scale, causal, precision, window)
        p = jnp.exp(s - lse_ref[:])                       # [bq, bk] via [bq,1]
        dp = _dot(do_ref[:], v_ref[:], _NT, precision)    # [bq, bk]
        ds = p * (dp - dl_ref[:])
        dq_scr[:] = dq_scr[:] + _dot(ds.astype(kb.dtype), kb, _NN, precision)

    @pl.when(ki == n_kb - 1)
    def _done():
        dq_ref[:] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, causal, n_qb,
                          q_block, k_block, scale, precision, window=None,
                          n_q=None):
    """Grid (batch*head, k_blocks, q_blocks): sweeps Q blocks, accumulating
    this K block's dK and dV in f32 scratch. Under a window the last grid
    dim counts the band's steps (`n_qb` of them) over the axis's `n_q`
    blocks, and step qi is Q block first + qi."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if window is None:
        live, qb_i = _block_live(qi, ki, q_block, k_block, causal), qi
    else:
        first, last = _band_q(ki, q_block, k_block, window, n_q)
        live, qb_i = first + qi <= last, first + qi

    @pl.when(live)
    def _block():
        qb = q_ref[:]
        dob = do_ref[:]
        s = _masked_scores(qb, k_ref[:], qb_i, ki, q_block, k_block,
                           scale, causal, precision, window)
        p = jnp.exp(s - lse_ref[:])                       # [bq, bk] via [bq,1]
        dv_scr[:] = dv_scr[:] + _dot(p.astype(dob.dtype), dob, _TN, precision)
        dp = _dot(dob, v_ref[:], _NT, precision)
        ds = p * (dp - dl_ref[:])
        dk_scr[:] = dk_scr[:] + _dot(ds.astype(qb.dtype), qb, _TN, precision)

    @pl.when(qi == n_qb - 1)
    def _done():
        dk_ref[:] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, block_q, block_k, interpret,
               scale=None, window=None):
    """Blocked backward: dq/dk/dv without materializing [T, T]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
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
    precision = _precision(q.dtype)
    scale = _softmax_scale(scale, d)
    bwd_in = (qr, kr, vr, gr, lse3, delta)
    params = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BUDGET)
    band = {} if window is None else {"window": window}

    bq, bk = flash_blocks("dq", tq, tk, d, dv, q.dtype, block_q, block_k,
                          window)
    n_kb = _sweep_len("dq", causal, tk, bq, bk, window, tk // bk)
    q_map, k_map = _kv_sweep_maps(causal, bq, bk, n_kb, window)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, n_kb=n_kb,
                          q_block=bq, k_block=bk, scale=scale,
                          precision=precision, **band),
        grid=(b * h, tq // bq, n_kb),
        in_specs=[
            pl.BlockSpec((None, bq, d), q_map),
            pl.BlockSpec((None, bk, d), k_map),
            pl.BlockSpec((None, bk, dv), k_map),
            pl.BlockSpec((None, bq, dv), q_map),
            pl.BlockSpec((None, bq, 1), q_map),
            pl.BlockSpec((None, bq, 1), q_map),
        ],
        out_specs=pl.BlockSpec((None, bq, d), q_map),
        out_shape=_out_struct((b * h, tq, d), q.dtype, *bwd_in),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=_call_name("dq", window),
    )(*bwd_in)

    bq, bk = flash_blocks("dkv", tq, tk, d, dv, q.dtype, block_q, block_k,
                          window)
    n_q = tq // bq
    n_qb = _sweep_len("dkv", causal, tq, bq, bk, window, n_q)

    def k_map(g_, j, i):
        return g_, j, 0

    def q_map(g_, j, i):
        # a causal K block's dead tiles come first: they name its first
        # live Q block, which the first live step then finds in place; a
        # band's come last and name its last live Q block
        if window is not None:
            first, last = _band_q(j, bq, bk, window, n_q)
            i = jnp.minimum(first + i, last)
        elif causal:
            i = jnp.maximum(i, _first_live_q(j, bq, bk, n_qb))
        return g_, i, 0

    dk, dv_ = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, n_qb=n_qb,
                          q_block=bq, k_block=bk, scale=scale,
                          precision=precision,
                          **({} if window is None else
                             {"window": window, "n_q": n_q})),
        grid=(b * h, tk // bk, n_qb),
        in_specs=[
            pl.BlockSpec((None, bq, d), q_map),
            pl.BlockSpec((None, bk, d), k_map),
            pl.BlockSpec((None, bk, dv), k_map),
            pl.BlockSpec((None, bq, dv), q_map),
            pl.BlockSpec((None, bq, 1), q_map),
            pl.BlockSpec((None, bq, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), k_map),
            pl.BlockSpec((None, bk, dv), k_map),
        ],
        out_shape=[
            _out_struct((b * h, tk, d), k.dtype, *bwd_in),
            _out_struct((b * h, tk, dv), v.dtype, *bwd_in),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=_call_name("dkv", window),
    )(*bwd_in)

    def back4(t, tlen):
        return t.reshape(b, h, tlen, t.shape[-1]).transpose(0, 2, 1, 3)

    return back4(dq, tq), back4(dk, tk), back4(dv_, tk)


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret, scale, window):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k,
                          _resolve_interpret(interpret), return_lse=True,
                          scale=scale, window=window)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, scale, window, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, causal, block_q, block_k,
                      _resolve_interpret(interpret), scale, window)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
