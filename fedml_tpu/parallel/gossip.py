"""Sharded decentralized gossip: node-per-device neighbor exchange.

The dense path (algorithms/decentralized.py) mixes all node models with one
einsum `W @ x` on a single chip — fine until the stacked node models exceed
one chip's HBM. This module is the multi-chip variant (SURVEY §2.9
"decentralized/gossip ... or ppermute"): node i's model lives on device i of
a `nodes` mesh axis and a gossip round moves ONLY actual edges over the ICI
via `lax.ppermute`.

Any mixing matrix decomposes into cyclic shifts:

    W = sum_s  diag(c_s) . P_s        c_s[i] = W[i, (i - s) mod N]

where P_s is the cyclic node shift by s. For a ring + Watts-Strogatz
topology (reference symmetric_topology_manager.py:21-52) only a handful of
shifts carry nonzero weight, so the exchange is a few ppermutes — each a
pure neighbor hop on a ring-wired ICI — instead of an all-to-all.

Equality with the dense einsum path is asserted on the virtual 8-device
mesh by tests/test_parallel.py and in __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P



def shift_decomposition(W: np.ndarray) -> tuple[list[int], np.ndarray]:
    """Nonzero cyclic shifts of W and their per-node coefficients.

    Returns (shifts, coefs[len(shifts), N]) with
    coefs[k, i] = W[i, (i - shifts[k]) % N].
    """
    W = np.asarray(W)
    N = W.shape[0]
    shifts, rows = [], []
    for s in range(N):
        c = np.array([W[i, (i - s) % N] for i in range(N)], W.dtype)
        if np.any(c != 0):
            shifts.append(s)
            rows.append(c)
    return shifts, np.stack(rows) if rows else np.zeros((0, N), W.dtype)


def build_sharded_mix(W: np.ndarray, mesh: Mesh,
                      axis_name: str = "nodes") -> Callable:
    """One-node-per-device gossip mix: stacked [N, ...] pytree -> W @ x,
    computed with one `ppermute` per nonzero cyclic shift of W.

    Requires mesh.shape[axis_name] == N (the node axis is fully sharded —
    that is the point of the multi-chip variant; use the dense einsum path
    below that scale)."""
    W = np.asarray(W, np.float32)
    N = W.shape[0]
    if mesh.shape[axis_name] != N:
        raise ValueError(
            f"sharded gossip wants one node per device: N={N} nodes vs "
            f"mesh axis {axis_name!r}={mesh.shape[axis_name]} devices")
    shifts, coefs = shift_decomposition(W)
    coefs_arr = jnp.asarray(coefs)  # [S, N]

    def mix_leaf(x, c):
        # x: local [1, ...] node block; c: local [S, 1] coefficients
        acc = jnp.zeros_like(x)
        for k, s in enumerate(shifts):
            if s == 0:
                shifted = x
            else:
                # receiver i gets node (i - s) % N: send j -> (j + s) % N
                perm = [(j, (j + s) % N) for j in range(N)]
                shifted = jax.lax.ppermute(x, axis_name, perm)
            acc = acc + c[k].reshape((1,) * x.ndim) * shifted
        return acc

    mix_sharded = jax.shard_map(
        mix_leaf, mesh=mesh,
        in_specs=(P(axis_name), P(None, axis_name)),
        out_specs=P(axis_name),
    )

    def mix(stacked_tree):
        return jax.tree.map(lambda leaf: mix_sharded(leaf, coefs_arr),
                            stacked_tree)

    return jax.jit(mix)
