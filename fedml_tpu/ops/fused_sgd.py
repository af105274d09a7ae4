"""Fused local-SGD pallas kernel — one kernel per client per ROUND.

The flagship FedAvg round (CNN_DropOut, 10 clients x bs 20, E=1 — reference
benchmark/README.md:56-59, my_model_trainer_classification.py:17-53) lowers in
XLA to ~56 small ops per SGD step plus hundreds of HBM<->VMEM copies of the
per-client weights and optimizer carries (see docs/PERF.md "fused local-SGD
kernel" + docs/traces/flagship). This kernel runs a client's ENTIRE local
epoch — all minibatch steps: forward, backward, global-norm clip, SGD update —
inside one pallas program, with the weights resident in VMEM across steps (the
output block doubles as the working buffer). HBM traffic for the weights drops
from O(steps) round trips to exactly one load + one store per client per
round, and the per-op dispatch soup collapses into one fused program.

Mosaic-driven design notes (verified by compile probes on the v5e chip):
  - Reshapes that collapse/split ROW (sublane/untiled) dims compile; reshapes
    that merge into or split the LANE dim do not. So there is no [b, Hp*Wp*64]
    flatten anywhere: dense1 is a dim-0-BATCHED dot over the Hp*Wp spatial
    positions ([P,b,64] x [P,64,128] summed over P), with linear_1's kernel
    pre-reshaped to [P, 64, 128] outside the kernel.
  - Strided slices and gathers don't lower, so the 2x2 maxpool extracts its
    four window phases with one-hot SELECTION MATMULS along W (exact — a
    one-hot matmul copies values bit-for-bit through the f32 MXU path) and an
    untiled-dim split along H.
  - conv1's im2col patches are precomputed OUTSIDE the kernel (they depend
    only on the shuffled data, not on weights) in a transposed [9, b*H1*W1]
    layout — the natural [_, 1]-lane layout of single-channel patches would
    waste 128x VMEM. conv2's patches are built in-kernel from lane-aligned
    slice+concat (channel dim 32 stays the lane dim).

Semantics parity with the engine path (algorithms/engine.py):
  - forward = CNN_DropOut (models/cnn.py): 3x3 VALID convs 32/64, 2x2 maxpool,
    dropout .25, dense 128, dropout .5, dense n_classes; bf16 compute with f32
    params (flax Dense/Conv dtype semantics: matmul output cast to compute
    dtype before bias add, logits cast back to f32).
  - loss = mean softmax CE over the batch (all samples valid; the fused path
    requires full batches — bench/flagship has samples % batch == 0).
  - relu backward = (x > 0), exactly jax.nn.relu's custom JVP.
  - maxpool backward routes to the FIRST maximal element in row-major window
    order, exactly lax.reduce_window's SelectAndScatter.
  - grad clip mirrors optax.clip_by_global_norm: g / max(1, ||g||/clip).
  - dropout draws from a counter-based lowbias32 hash PRNG (portable across
    Mosaic and interpret mode) — same Bernoulli semantics as flax Dropout,
    different stream; trajectories therefore match the engine statistically,
    and bit-exactly when both paths disable dropout and shuffling
    (tests/test_fused_sgd.py).

Measured numbers and the decision about the default flagship bench path live
in docs/PERF.md.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class FusedEpochSpec:
    """Static geometry for the fused kernel (flagship: H=W=28, C=62)."""

    def __init__(self, height=28, width=28, n_classes=62, samples=200,
                 batch=20, lr=0.1, grad_clip=1.0, drop1=0.25, drop2=0.5,
                 compute_dtype=jnp.bfloat16, chunk=5):
        if samples % batch != 0:
            raise ValueError("fused path requires samples % batch == 0")
        # sub-batch chunking: the compiled step body scales with the chunk's
        # vector sizes (an inner fori_loop body is compiled ONCE), which is
        # what keeps the Mosaic compiler from being killed for memory
        self.chunk = math.gcd(batch, chunk) if chunk else batch
        self.nchunks = batch // self.chunk
        self.H, self.W, self.C = height, width, n_classes
        self.n, self.b = samples, batch
        self.steps = samples // batch
        self.H1, self.W1 = height - 2, width - 2      # conv1 VALID
        self.H2, self.W2 = self.H1 - 2, self.W1 - 2   # conv2 VALID
        if self.H2 % 2 or self.W2 % 2:
            raise ValueError("pool input must be even")
        self.Hp, self.Wp = self.H2 // 2, self.W2 // 2
        self.P = self.Hp * self.Wp                    # pooled spatial positions
        self.F = self.P * 64                          # flax flatten width
        self.lr, self.clip = lr, grad_clip
        self.drop1, self.drop2 = drop1, drop2
        self.cdtype = compute_dtype
        # conv2 strategy: "accum" = 9 accumulated K=32 matmuls (no [.,288]
        # im2col buffers — the Mosaic compiler is killed for memory by the
        # vreg volume of the im2col form); "im2col" = one K=288 GEMM
        self.conv2_mode = "accum"


def _hash_bits(shape, offset):
    """Counter-based uniform u32 bits: lowbias32 hash of (flat index + offset).

    Portable across Mosaic and pallas interpret mode (pltpu.prng_* has no CPU
    lowering), and deterministic across platforms. Quality is ample for
    dropout masks."""
    flat = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in range(len(shape) - 1, -1, -1):
        flat = flat + jax.lax.broadcasted_iota(jnp.uint32, shape, d) * jnp.uint32(stride)
        stride *= shape[d]
    x = flat + offset.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _first_max_masks(slices, pooled):
    """0/1 routing masks: gradient goes to the first window element attaining
    the max (row-major) — lax.reduce_window max-pool VJP semantics. Compares
    in f32: Mosaic on v5e rejects bf16 cmpf, and f32 comparison of bf16
    values is exact."""
    pooled32 = pooled.astype(jnp.float32)
    masks, taken = [], None
    # static Python list of window slices — deliberate trace-time unroll
    for s in slices:  # graft-lint: disable=traced-loop -- static window-slice list, intended unroll
        eq = s.astype(jnp.float32) == pooled32
        if taken is None:
            masks.append(eq)
            taken = eq
        else:
            masks.append(eq & jnp.logical_not(taken))
            taken = jnp.logical_or(taken, eq)
    return masks


def _epoch_kernel(spec: FusedEpochSpec,
                  seed_ref, p1_ref, y_ref,
                  w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref, w4_ref, b4_ref,
                  ow1, ob1, ow2, ob2, ow3, ob3, ow4, ob4, met_ref):
    """One client's full local epoch. Output refs are the working weight
    buffers: seeded from the (shared) global weights, updated in VMEM every
    step, flushed to HBM once when the program ends."""
    cd = spec.cdtype
    H1, W1, H2, W2 = spec.H1, spec.W1, spec.H2, spec.W2
    Hp, Wp, P, C = spec.Hp, spec.Wp, spec.P, spec.C

    my_seed = seed_ref[pl.program_id(0)]

    # seed working weights from the broadcast global weights
    ow1[0] = w1_ref[...]
    ob1[0, 0] = b1_ref[...]
    ow2[0] = w2_ref[...]
    ob2[0, 0] = b2_ref[...]
    ow3[0] = w3_ref[...]
    ob3[0, 0] = b3_ref[...]
    ow4[0] = w4_ref[...]
    ob4[0, 0] = b4_ref[...]

    inv_keep1 = 1.0 / (1.0 - spec.drop1) if spec.drop1 else 1.0
    inv_keep2 = 1.0 / (1.0 - spec.drop2) if spec.drop2 else 1.0

    # one-hot W-phase selectors: Eev[w, wp] = (w == 2wp), Eod[w, wp] = (w == 2wp+1)
    wr = jax.lax.broadcasted_iota(jnp.int32, (W2, Wp), 0)
    wc = jax.lax.broadcasted_iota(jnp.int32, (W2, Wp), 1)
    Eev = (wr == 2 * wc).astype(cd)
    Eod = (wr == 2 * wc + 1).astype(cd)

    def wsel(t, E):
        """Select W phase by one-hot matmul: [n,Hp,W2,64] -> [n,Hp,Wp,64]."""
        n = t.shape[0]
        f = jnp.swapaxes(t, 2, 3).reshape(n * Hp * 64, W2)
        g = jnp.dot(f, E, preferred_element_type=jnp.float32).astype(cd)
        return jnp.swapaxes(g.reshape(n, Hp, 64, Wp), 2, 3)

    def wexp(t, E):
        """Transpose of wsel (scatter back): [n,Hp,Wp,64] -> [n,Hp,W2,64]."""
        n = t.shape[0]
        f = jnp.swapaxes(t, 2, 3).reshape(n * Hp * 64, Wp)
        g = jax.lax.dot_general(f, E, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32).astype(cd)
        return jnp.swapaxes(g.reshape(n, Hp, 64, W2), 2, 3)

    cb = spec.chunk
    nchunks = spec.nchunks
    full_b = spec.b

    def step(s, carry):
        loss_sum, correct = carry
        w1 = ow1[0].astype(cd)                             # [9, 32]
        w2 = ow2[0].astype(cd)                             # [288, 64]
        w3 = ow3[0].astype(cd)                             # [P, 64, 128]
        w4 = ow4[0].astype(cd)                             # [128, C]

        def chunk_grads(ci, ch_carry):
            (aw1, ab1, aw2, ab2, aw3, ab3, aw4, ab4,
             loss_sum, correct) = ch_carry
            g_idx = s * nchunks + ci                       # global chunk index
            p1 = p1_ref[0, g_idx].astype(cd)               # [9, cb*H1*W1]
            oh = y_ref[0, g_idx]                           # [cb, C] one-hot f32
            b = cb

            # ---- conv1 (patches precomputed; contract the 9-dim) ----------
            z1 = jax.lax.dot_general(p1, w1, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32).astype(cd)
            a1 = jax.nn.relu(z1 + ob1[0, 0].astype(cd))        # [b*H1*W1, 32]
            a14 = a1.reshape(b, H1, W1, 32)

            # ---- conv2 -----------------------------------------------------
            def a1_slice(k):
                di, dj = divmod(k, 3)
                return a14[:, di:di + H2, dj:dj + W2, :].reshape(b * H2 * W2, 32)

            if spec.conv2_mode == "im2col":
                p2 = jnp.concatenate([a1_slice(k) for k in range(9)], axis=1)
                z2 = jnp.dot(p2, w2, preferred_element_type=jnp.float32)
            else:
                # 9 accumulated K=32 matmuls: ~3x worse MXU K-fill than the
                # K=288 im2col GEMM, but avoids the [bH2W2, 288] patch buffers
                # whose vreg volume gets the Mosaic compiler killed for memory
                p2 = None
                z2 = None
                for k in range(9):
                    t = jnp.dot(a1_slice(k), w2[32 * k:32 * (k + 1), :],
                                preferred_element_type=jnp.float32)
                    z2 = t if z2 is None else z2 + t
            a2 = jax.nn.relu(z2.astype(cd) + ob2[0, 0].astype(cd)).reshape(b, H2, W2, 64)

            # ---- 2x2 maxpool: H via untiled split, W via selection matmul -
            a2s = a2.reshape(b, Hp, 2, W2, 64)
            aH0, aH1 = a2s[:, :, 0], a2s[:, :, 1]              # [b,Hp,W2,64]
            s00, s01 = wsel(aH0, Eev), wsel(aH0, Eod)
            s10, s11 = wsel(aH1, Eev), wsel(aH1, Eod)
            pooled = jnp.maximum(jnp.maximum(s00, s01), jnp.maximum(s10, s11))

            # ---- dropout 1 ------------------------------------------------
            if spec.drop1:
                off = (my_seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
                       + g_idx.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
                bits = _hash_bits((b, Hp, Wp, 64), off)
                thresh = np.uint32(int(spec.drop1 * (1 << 32)))
                keep1 = (bits >= thresh).astype(cd) * cd(inv_keep1)
                d = pooled * keep1
            else:
                keep1 = None
                d = pooled

            # ---- dense 1, batched over the P spatial positions ------------
            P3 = jnp.swapaxes(d.reshape(b, P, 64), 0, 1)       # [P, b, 64]
            h3 = jax.lax.dot_general(P3, w3, (((2,), (1,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)
            zh = jnp.sum(h3, axis=0).astype(cd)                # [b, 128]
            h = jax.nn.relu(zh + ob3[0, 0].astype(cd))
            if spec.drop2:
                off2 = (my_seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
                        + g_idx.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F)
                        + jnp.uint32(0x165667B1))
                bits2 = _hash_bits((b, 128), off2)
                thresh2 = np.uint32(int(spec.drop2 * (1 << 32)))
                keep2 = (bits2 >= thresh2).astype(cd) * cd(inv_keep2)
                hd = h * keep2
            else:
                keep2 = None
                hd = h

            # ---- dense 2 + softmax CE (f32, matching the model's f32 cast) -
            zl = jnp.dot(hd, w4, preferred_element_type=jnp.float32).astype(cd)
            logits = (zl + ob4[0, 0].astype(cd)).astype(jnp.float32)  # [b, C]
            lmax = jnp.max(logits, axis=-1, keepdims=True)
            ex = jnp.exp(logits - lmax)
            sumex = jnp.sum(ex, axis=-1, keepdims=True)
            softmax = ex / sumex
            cols = jax.lax.broadcasted_iota(jnp.int32, (b, C), 1)
            ll = jnp.sum(logits * oh, axis=-1, keepdims=True)         # l[y]
            per = jnp.log(sumex) + lmax - ll                          # [b, 1]
            # first-argmax one-hot (ties -> lowest index, = jnp.argmax)
            mi = jnp.min(jnp.where(logits == lmax, cols, C), axis=-1,
                         keepdims=True)
            pm = (cols == mi).astype(jnp.float32)                     # [b, C]

            # ---- backward --------------------------------------------------
            # mean over the FULL batch: chunk grads then sum to the exact
            # batch-mean gradient
            dlogits = ((softmax - oh) * (1.0 / full_b)).astype(cd)    # [b, C]
            gw4 = jax.lax.dot_general(hd, dlogits, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)  # [128, C]
            gb4 = jnp.sum(dlogits.astype(jnp.float32), axis=0)
            dhd = jax.lax.dot_general(dlogits, w4, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32).astype(cd)
            if keep2 is not None:
                dhd = dhd * keep2
            dh = dhd * (h.astype(jnp.float32) > 0).astype(cd)         # relu'
            dh_b = jnp.broadcast_to(dh[None], (P, b, 128))
            gw3 = jax.lax.dot_general(P3, dh_b, (((1,), (1,)), ((0,), (0,))),
                                      preferred_element_type=jnp.float32)  # [P,64,128]
            gb3 = jnp.sum(dh.astype(jnp.float32), axis=0)
            dP3 = jax.lax.dot_general(dh_b, w3, (((2,), (2,)), ((0,), (0,))),
                                      preferred_element_type=jnp.float32).astype(cd)
            dd = jnp.swapaxes(dP3, 0, 1).reshape(b, Hp, Wp, 64)
            if keep1 is not None:
                dd = dd * keep1

            # maxpool backward: first-max routing, W expand, H interleave
            m00, m01, m10, m11 = _first_max_masks([s00, s01, s10, s11], pooled)
            row0 = wexp(dd * m00.astype(cd), Eev) + wexp(dd * m01.astype(cd), Eod)
            row1 = wexp(dd * m10.astype(cd), Eev) + wexp(dd * m11.astype(cd), Eod)
            da2 = jnp.stack([row0, row1], axis=2).reshape(b, H2, W2, 64)

            dz2 = (da2 * (a2.astype(jnp.float32) > 0).astype(cd)).reshape(b * H2 * W2, 64)
            gb2 = jnp.sum(dz2.astype(jnp.float32), axis=0)
            # per-offset wgrad rows + input-grad scatter-back. W offsets use
            # one-hot expansion matmuls (Mosaic cannot pad the sublane dim at an
            # offset); H offsets pad the untiled dim, which lowers fine.
            w2r = jax.lax.broadcasted_iota(jnp.int32, (W2, W1), 0)
            w2c = jax.lax.broadcasted_iota(jnp.int32, (W2, W1), 1)
            gw2_rows = []
            da1 = None
            for k, (di, dj) in enumerate([(i, j) for i in range(3) for j in range(3)]):
                gw2_rows.append(jax.lax.dot_general(
                    a1_slice(k), dz2, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))               # [32, 64]
                chunk = jax.lax.dot_general(
                    dz2, w2[32 * k:32 * (k + 1), :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(cd)
                chunk = chunk.reshape(b, H2, W2, 32)
                Eoff = (w2c == w2r + dj).astype(cd)                    # [W2, W1]
                f = jnp.swapaxes(chunk, 2, 3).reshape(b * H2 * 32, W2)
                g = jnp.dot(f, Eoff, preferred_element_type=jnp.float32).astype(cd)
                wx = jnp.swapaxes(g.reshape(b, H2, 32, W1), 2, 3)      # [b,H2,W1,32]
                padded = jnp.pad(wx, ((0, 0), (di, H1 - H2 - di), (0, 0), (0, 0)))
                da1 = padded if da1 is None else da1 + padded
            gw2 = jnp.concatenate(gw2_rows, axis=0)                    # [288, 64]
            dz1 = (da1 * (a14.astype(jnp.float32) > 0).astype(cd)).reshape(b * H1 * W1, 32)
            gw1 = jnp.dot(p1, dz1, preferred_element_type=jnp.float32)  # [9, 32]
            gb1 = jnp.sum(dz1.astype(jnp.float32), axis=0)
            return (aw1 + gw1, ab1 + gb1, aw2 + gw2, ab2 + gb2,
                    aw3 + gw3, ab3 + gb3, aw4 + gw4, ab4 + gb4,
                    loss_sum + jnp.sum(per), correct + jnp.sum(pm * oh))

        zeros = (jnp.zeros((9, 32), jnp.float32),
                 jnp.zeros((32,), jnp.float32),
                 jnp.zeros((288, 64), jnp.float32),
                 jnp.zeros((64,), jnp.float32),
                 jnp.zeros((P, 64, 128), jnp.float32),
                 jnp.zeros((128,), jnp.float32),
                 jnp.zeros((128, C), jnp.float32),
                 jnp.zeros((C,), jnp.float32))
        out = jax.lax.fori_loop(0, nchunks, chunk_grads,
                                zeros + (loss_sum, correct))
        gw1, gb1, gw2, gb2, gw3, gb3, gw4, gb4 = out[:8]
        loss_sum, correct = out[8], out[9]

        # ---- global-norm clip + SGD -----------------------------------
        grads = [gw1, gb1, gw2, gb2, gw3, gb3, gw4, gb4]
        if spec.clip is not None:
            normsq = functools.reduce(
                jnp.add, [jnp.sum(jnp.square(g)) for g in grads])
            # optax.clip_by_global_norm: g / max(1, ||g||/clip)
            scale = 1.0 / jnp.maximum(1.0, jnp.sqrt(normsq) / spec.clip)
        else:
            scale = jnp.float32(1.0)
        step_size = spec.lr * scale
        ow1[0] = ow1[0] - step_size * gw1
        ob1[0, 0] = ob1[0, 0] - step_size * gb1
        ow2[0] = ow2[0] - step_size * gw2
        ob2[0, 0] = ob2[0, 0] - step_size * gb2
        ow3[0] = ow3[0] - step_size * gw3
        ob3[0, 0] = ob3[0, 0] - step_size * gb3
        ow4[0] = ow4[0] - step_size * gw4
        ob4[0, 0] = ob4[0, 0] - step_size * gb4
        return loss_sum, correct

    loss_sum, correct = jax.lax.fori_loop(
        0, spec.steps, step, (jnp.float32(0.0), jnp.float32(0.0)))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    met = jnp.where(lane == 0, loss_sum,
                    jnp.where(lane == 1, correct,
                              jnp.where(lane == 2, jnp.float32(spec.n), 0.0)))
    met_ref[0, 0] = met[0]


def _conv1_patches(spec: FusedEpochSpec, x):
    """Outside-the-kernel im2col for conv1, in the kernel's transposed
    per-CHUNK layout [clients, steps*nchunks, 9, chunk*H1*W1] (see module
    docstring; the kernel's inner loop walks chunks of the batch)."""
    clients = x.shape[0]
    n_chunks_total = spec.steps * spec.nchunks
    x4 = x.reshape(clients, spec.n, spec.H, spec.W)
    pats = jnp.stack(
        [x4[:, :, di:di + spec.H1, dj:dj + spec.W1]
         for di in range(3) for dj in range(3)], axis=2)
    pats = pats.reshape(clients, n_chunks_total, spec.chunk, 9,
                        spec.H1 * spec.W1)
    pats = jnp.swapaxes(pats, 2, 3)
    return pats.reshape(clients, n_chunks_total, 9,
                        spec.chunk * spec.H1 * spec.W1)


def fused_epoch(spec: FusedEpochSpec, params, x, y, seeds, interpret=False):
    """Run one local epoch for every client in one pallas call.

    params: flax CNN_DropOut params tree (f32); x: [clients, n, H, W, 1];
    y: [clients, n] int32; seeds: [clients] int32 (dropout streams).
    Returns (stacked per-client params tree, metrics dict of [clients]).
    """
    clients = x.shape[0]
    p = params["params"]
    w1 = p["conv2d_1"]["kernel"].reshape(9, 32)
    b1 = p["conv2d_1"]["bias"]
    w2 = p["conv2d_2"]["kernel"].reshape(9 * 32, 64)
    b2 = p["conv2d_2"]["bias"]
    w3 = p["linear_1"]["kernel"].reshape(spec.P, 64, 128)
    b3 = p["linear_1"]["bias"]
    w4 = p["linear_2"]["kernel"]
    b4 = p["linear_2"]["bias"]
    C = w4.shape[1]
    assert C == spec.C and p["linear_1"]["kernel"].shape[0] == spec.F

    p1_all = _conv1_patches(spec, x).astype(spec.cdtype)

    def shared(shape):
        return pl.BlockSpec(shape, lambda c: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    def per_client(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda c, _n=len(shape): (c,) + (0,) * _n,
                            memory_space=pltpu.VMEM)

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                         # seeds
        per_client((spec.steps * spec.nchunks, 9,
                    spec.chunk * spec.H1 * spec.W1)),                  # p1
        per_client((spec.steps * spec.nchunks, spec.chunk, C)),        # y one-hot
        shared((9, 32)), shared((32,)),
        shared((288, 64)), shared((64,)),
        shared((spec.P, 64, 128)), shared((128,)),
        shared((128, C)), shared((C,)),
    ]
    # NB: Mosaic requires each block's last two dims to equal the array's (or
    # be (8,128)-aligned), so rank-2 per-client outputs (biases, metrics, y)
    # carry a singleton middle axis
    out_specs = [
        per_client((9, 32)), per_client((1, 32)),
        per_client((288, 64)), per_client((1, 64)),
        per_client((spec.P, 64, 128)), per_client((1, 128)),
        per_client((128, C)), per_client((1, C)),
        per_client((1, 128)),                                          # metrics
    ]
    out_shape = [
        jax.ShapeDtypeStruct((clients, 9, 32), jnp.float32),
        jax.ShapeDtypeStruct((clients, 1, 32), jnp.float32),
        jax.ShapeDtypeStruct((clients, 288, 64), jnp.float32),
        jax.ShapeDtypeStruct((clients, 1, 64), jnp.float32),
        jax.ShapeDtypeStruct((clients, spec.P, 64, 128), jnp.float32),
        jax.ShapeDtypeStruct((clients, 1, 128), jnp.float32),
        jax.ShapeDtypeStruct((clients, 128, C), jnp.float32),
        jax.ShapeDtypeStruct((clients, 1, C), jnp.float32),
        jax.ShapeDtypeStruct((clients, 1, 128), jnp.float32),
    ]
    flops_step = 2 * spec.b * (spec.H1 * spec.W1 * 9 * 32
                               + spec.H2 * spec.W2 * 288 * 64
                               + spec.F * 128 + 128 * C) * 3
    outs = pl.pallas_call(
        functools.partial(_epoch_kernel, spec),
        grid=(clients,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        # the step working set (patches, activations, f32 grads, the resident
        # weight blocks) needs ~74 MB of VMEM — far above the conservative
        # 16 MB default scoped limit, well inside v5e's 128 MB
        compiler_params=getattr(pltpu, "CompilerParams",
                                getattr(pltpu, "TPUCompilerParams", None))(
            vmem_limit_bytes=100 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=flops_step * spec.steps * clients,
            transcendentals=spec.b * spec.C * spec.steps * clients,
            bytes_accessed=clients * (spec.F * 128 * 8 + p1_all.nbytes // clients),
        ),
    )(seeds.astype(jnp.int32), p1_all,
      jax.nn.one_hot(y.reshape(clients, spec.steps * spec.nchunks,
                               spec.chunk), C, dtype=jnp.float32),
      w1, b1, w2, b2, w3, b3, w4, b4)
    (ow1, ob1, ow2, ob2, ow3, ob3, ow4, ob4, met) = outs
    ob1, ob2, ob3, ob4 = (o[:, 0] for o in (ob1, ob2, ob3, ob4))
    met = met[:, 0]
    kH = p["conv2d_1"]["kernel"].shape  # (3,3,1,32)
    new_params = {
        "conv2d_1": {"kernel": ow1.reshape((clients,) + kH), "bias": ob1},
        "conv2d_2": {"kernel": ow2.reshape((clients, 3, 3, 32, 64)), "bias": ob2},
        "linear_1": {"kernel": ow3.reshape(clients, spec.F, 128), "bias": ob3},
        "linear_2": {"kernel": ow4, "bias": ob4},
    }
    metrics = {"loss_sum": met[:, 0], "correct": met[:, 1], "total": met[:, 2]}
    return {"params": new_params}, metrics


def build_fused_round_fn(spec: FusedEpochSpec, aggregator, shuffle=True,
                         interpret=False, collect_stats=False):
    """Engine-signature round over the fused kernel:
    round_fn(gv, agg_state, x, y, counts, rng) -> (gv, agg_state, metrics).

    Client shuffling happens outside the kernel (one gather per round — the
    out-of-kernel analog of engine.py's per-epoch argsort permutation);
    dropout streams are seeded per (round, client) from the round rng.

    `collect_stats=True` appends the engine's `cohort_stats` health rows as
    a fourth output (same contract as `engine.build_round_fn`), so the
    FedAvg drive's ledger plumbing works unchanged on the fused path. The
    kernel has no participation/quarantine stage — a non-None
    `participation` raises at trace time rather than silently training
    dropped clients.
    """
    from fedml_tpu.algorithms.engine import LocalResult, cohort_stats

    def round_fn(gv, agg_state, x, y, counts, rng, participation=None):
        if participation is not None:
            raise ValueError(
                "the fused kernel round has no participation/quarantine "
                "stage — run without chaos faults or cohort padding, or "
                "drop --fused_kernel")
        clients = x.shape[0]
        prng, srng = jax.random.split(rng)
        if shuffle:
            perms = jax.vmap(lambda k: jax.random.permutation(k, x.shape[1]))(
                jax.random.split(prng, clients))
            x_in = jnp.take_along_axis(
                x, perms[:, :, None, None, None], axis=1)
            y_in = jnp.take_along_axis(y, perms, axis=1)
        else:
            x_in, y_in = x, y
        seeds = jax.random.randint(srng, (clients,), 0, np.int32(2**31 - 1))
        new_vars, metrics = fused_epoch(spec, gv, x_in, y_in, seeds,
                                        interpret=interpret)
        result = LocalResult(
            variables=new_vars,
            num_steps=jnp.full((clients,), spec.steps, jnp.int32),
            metrics=metrics,
        )
        stats = cohort_stats(gv, result) if collect_stats else None
        gv, agg_state = aggregator(gv, result, counts.astype(jnp.float32),
                                   rng, agg_state)
        summed = {k: v.sum() for k, v in metrics.items()}
        if collect_stats:
            return gv, agg_state, summed, stats
        return gv, agg_state, summed

    return jax.jit(round_fn)


def build_fused_multi_round_fn(spec: FusedEpochSpec, aggregator,
                               num_rounds: int, shuffle=True, interpret=False):
    """num_rounds fused rounds under one jitted lax.scan (bench fast path,
    mirrors engine.build_multi_round_fn for full client participation)."""
    round_fn = build_fused_round_fn(spec, aggregator, shuffle=shuffle,
                                    interpret=interpret)
    inner = round_fn.__wrapped__  # un-jitted body for the scan

    def multi(gv, agg_state, x, y, counts, base_rng):
        def body(carry, round_idx):
            gv, st = carry
            rng = jax.random.fold_in(base_rng, round_idx)
            gv, st, metrics = inner(gv, st, x, y, counts, rng)
            return (gv, st), metrics

        (gv, st), metrics = jax.lax.scan(
            body, (gv, agg_state), jnp.arange(num_rounds))
        return gv, st, metrics

    return jax.jit(multi)
