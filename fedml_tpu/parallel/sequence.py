"""Sequence/context parallelism — long-context attention over a device mesh.

The reference has no attention models (SURVEY §2.9 lists SP/CP as absent),
but long-context support is a first-class capability here. Two standard
TPU-native schemes over a `sp` mesh axis:

- `ring_attention`: sequence sharded over devices; K/V blocks rotate around
  the ICI ring via `ppermute` while each device keeps flash-style online
  softmax statistics (running max / denominator / numerator) for its local
  queries. Peak memory per device is O(T/n) — the long-context scheme.
- `ulysses_attention` (DeepSpeed-Ulysses style): two `all_to_all`s reshard
  [B, T/n, H, D] -> [B, T, H/n, D], run full attention locally per head
  shard, and reshard back. Cheaper collectives when H >= n_devices.

Both are bit-close to `fedml_tpu.ops.attention_reference` on a virtual CPU
mesh (tested) and compose with the rest of the framework's shard_map world
(the `sp` axis can live alongside the `clients` axis in one mesh).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P



def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = False):
    """q/k/v: [B, T, H, D] GLOBAL arrays, sequence dim sharded over
    mesh[axis]. Returns attention output with the same sharding."""
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(f"sequence length {q.shape[1]} not divisible by "
                         f"{axis} axis size {n}")
    t_local = q.shape[1] // n
    scale = 1.0 / np.sqrt(q.shape[-1])

    def body(q, k, v):
        # local shards: [B, T/n, H, D]
        d_idx = jax.lax.axis_index(axis)
        qf = q.astype(jnp.float32) * scale
        q_pos = d_idx * t_local + jnp.arange(t_local)

        def block_update(o, m, l, kb, vb, t):
            src = (d_idx - t) % n  # which device's block we hold at step t
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
            if causal:
                k_pos = src * t_local + jnp.arange(t_local)
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m - m_new))
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(axis=-1)
            o = o * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
            return o, m_new, l

        def step(carry, t):
            o, m, l, kb, vb = carry
            o, m, l = block_update(o, m, l, kb, vb, t)
            # rotate K/V blocks one hop around the ring
            perm = [(i, (i + 1) % n) for i in range(n)]
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            return (o, m, l, kb, vb), None

        b, _, h, dd = q.shape
        # pcast to varying: the online-softmax stats become device-varying
        # inside the scan (each device sees different K/V blocks); marking
        # the init values keeps jax's check_vma carry typing satisfied
        var = lambda a: jax.lax.pcast(a, (axis,), to="varying")
        o0 = var(jnp.zeros((b, h, t_local, dd), jnp.float32))
        m0 = var(jnp.full((b, h, t_local), -jnp.inf, jnp.float32))
        l0 = var(jnp.zeros((b, h, t_local), jnp.float32))
        # n-1 compute+rotate hops in the scan, final block computed outside —
        # no wasted last rotation on the ICI ring
        (o, m, l, kb, vb), _ = jax.lax.scan(
            step, (o0, m0, l0, k, v), jnp.arange(n - 1))
        o, m, l = block_update(o, m, l, kb, vb, n - 1)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, T/n, H, D]

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
    )
    return sharded(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      causal: bool = False):
    """All-to-all sequence parallelism: reshard sequence-sharded Q/K/V to
    head-sharded, attend over the FULL sequence per head shard, reshard
    back. Requires H divisible by the axis size."""
    n = mesh.shape[axis]
    h = q.shape[2]
    if h % n:
        raise ValueError(f"head count {h} not divisible by {axis} size {n}")
    if q.shape[1] % n:
        raise ValueError(f"sequence length {q.shape[1]} not divisible by "
                         f"{axis} size {n}")

    from fedml_tpu.ops.attention import attention_reference

    def body(q, k, v):
        # [B, T/n, H, D] -> all_to_all -> [B, T, H/n, D]
        a2a = partial(jax.lax.all_to_all, axis_name=axis,
                      split_axis=2, concat_axis=1, tiled=True)
        qh, kh, vh = a2a(q), a2a(k), a2a(v)
        out = attention_reference(qh, kh, vh, causal=causal)
        # back: [B, T, H/n, D] -> [B, T/n, H, D]
        return jax.lax.all_to_all(out, axis_name=axis,
                                  split_axis=1, concat_axis=2, tiled=True)

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
    )
    return sharded(q, k, v)
