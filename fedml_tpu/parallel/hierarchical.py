"""Two-level (groups, clients) mesh for hierarchical FL.

SURVEY §2.9 maps the reference's cloud→group→client nesting
(standalone/hierarchical_fl/trainer.py:43-71, group.py:24-46) onto a
two-level device mesh: ICI within a slice hosts a group's clients, the
cross-slice (DCN-reaching) axis is the cloud. Concretely:

  - clients are sharded over BOTH mesh axes: x is [G, C, n_max, ...] with G
    split over the `groups` axis and C over the `clients` axis;
  - each inner group round ends in a weighted `psum` over the `clients`
    axis only — the group-local all-reduce that rides ICI;
  - after `group_comm_round` inner rounds, the cloud average is a weighted
    `psum` over the `groups` axis — the only traffic that crosses slices,
    once per global round instead of once per inner round (the whole point
    of hierarchical FL's communication hierarchy).

Per-group/per-client RNG keys are assigned from the same nested split tables
as the single-chip `build_hierarchical_round_fn`, so the sharded round
reproduces the vmap round to float tolerance (asserted in
tests/test_parallel.py and in the driver dryrun).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu.algorithms.aggregators import (
    client_finite_mask,
    tree_weighted_mean_psum,
    tree_weighted_sum_psum,
)
from fedml_tpu.algorithms.engine import build_local_update
from fedml_tpu.core.builder import shard_key_slice
from fedml_tpu.core.config import FedConfig
from fedml_tpu.utils.pytree import tree_where


def build_sharded_hierarchical_round_fn(
    trainer,
    cfg: FedConfig,
    mesh: Mesh,
    group_comm_round: int,
    group_axis: str = "groups",
    client_axis: str = "clients",
) -> Callable:
    """Jitted two-level round over a (groups, clients) mesh.

    Inputs mirror build_hierarchical_round_fn: x/y/counts are group-major
    [G, C, n_max, ...]; G must divide by mesh.shape[group_axis] and C by
    mesh.shape[client_axis] (pad with zero-count clients / empty groups —
    weight-0 no-ops at both averaging levels).

    Fault tolerance (optional trailing `participation`, [G, C] bool sharded
    like counts) is two-level, matching the communication hierarchy: dropped
    clients are `where`-zeroed zero-weight rows inside every inner round
    (elementwise only — the group weight normalization psum stays hoisted
    outside the inner scan, so no collective enters the loop), while the
    non-finite quarantine runs at GROUP granularity at the cloud step: a
    group whose final variables carry NaN/Inf — one poisoned client inside
    an inner round contaminates its whole group's running mean, there is no
    finer-grained recovery point — is excluded from the cloud average with
    zero weight. All groups quarantined degrades to a no-op (global passes
    through). `participation=None` traces the exact legacy program
    (COMMS_BUDGET.json gates it); metrics of the masked specialization gain
    `participated_count` (participating clients in surviving groups) and
    `quarantined_count` (participating clients in quarantined groups).
    """
    # clients-axis pcast: each client's scan carries become varying over the
    # clients axis; the groups axis is handled at the inner-round scan below
    local_update = build_local_update(trainer, cfg, pvary_axes=(client_axis,))
    g_dev = mesh.shape[group_axis]
    c_dev = mesh.shape[client_axis]

    def shard_body(global_variables, x, y, counts, rng, participation=None):
        masked = participation is not None
        g_loc, c_loc = x.shape[0], x.shape[1]
        g_total, c_total = g_loc * g_dev, c_loc * c_dev
        gidx = jax.lax.axis_index(group_axis)
        cidx = jax.lax.axis_index(client_axis)
        # same group-key table as the vmap engine: split(rng, G)[g]
        grngs = shard_key_slice(rng, g_total, gidx, g_loc)

        def group_train(gv, xg, yg, cg, grng, pg):
            # pg: this group's [c_loc] participation row (unused — and
            # dead-code-eliminated — on the unmasked trace)
            # inner-scan carry: starts as the invariant global broadcast,
            # exits varying over the groups axis (each group trains its own
            # line) — pcast so the carry types match under check_vma
            gv = jax.lax.pcast(gv, (group_axis,), to="varying")
            # the group's total client weight is round-invariant, so its
            # psum is hoisted OUT of the inner-round scan: one scalar
            # all-reduce per global round instead of one per inner round
            # (graft-lint collective-in-loop); the guarded denominator makes
            # an empty padded group zeros (weight-0 at the cloud), not NaN
            cw = cg.astype(jnp.float32)
            if masked:
                # dropped clients: zero weight before the hoisted
                # normalization, so the mask costs no loop-carried collective
                cw = jnp.where(pg, cw, 0.0)
            cw_norm = cw / jnp.maximum(
                jax.lax.psum(jnp.sum(cw), client_axis), 1e-12)

            def inner_round(gv, r_rng):
                # same client-key table: split(r_rng, C)[c]
                crngs = shard_key_slice(r_rng, c_total, cidx, c_loc)
                result = jax.vmap(local_update, in_axes=(None, 0, 0, 0, 0))(
                    gv, xg, yg, cg, crngs
                )
                variables, mets = result.variables, result.metrics
                if masked:
                    # `where`-zero dropped rows (elementwise, no collective):
                    # a zero weight alone cannot save the sum from a NaN row
                    # (NaN * 0 == NaN)
                    def zero_dropped(leaf):
                        keep = pg.reshape((-1,) + (1,) * (leaf.ndim - 1))
                        return jnp.where(keep, leaf, jnp.zeros((), leaf.dtype))

                    variables = jax.tree.map(zero_dropped, variables)
                    mets = {k: jnp.where(pg, v, jnp.zeros((), v.dtype))
                            for k, v in mets.items()}
                # group-local weighted mean == psum over the clients axis
                # (ICI), with the pre-normalized weights from above
                new_gv = tree_weighted_sum_psum(variables, cw_norm, client_axis)
                metrics = {
                    k: jax.lax.psum(v.sum(), client_axis)
                    for k, v in mets.items()
                }
                return new_gv, metrics

            gv, ms = jax.lax.scan(
                inner_round, gv, jax.random.split(grng, group_comm_round)
            )
            return gv, {k: v[-1] for k, v in ms.items()}

        # the trailing operand is the participation block when masked and a
        # dummy (counts — unused, DCE'd) otherwise, keeping one group_train
        part = participation if masked else counts
        group_vars, metrics = jax.vmap(group_train, in_axes=(None, 0, 0, 0, 0, 0))(
            global_variables, x, y, counts, grngs, part
        )
        if not masked:
            # cloud level: weighted mean over groups — the once-per-global-
            # round cross-slice reduction
            gw = jax.lax.psum(counts.sum(axis=1).astype(jnp.float32), client_axis)
            new_global = tree_weighted_mean_psum(group_vars, gw, group_axis)
            out_metrics = {
                k: jax.lax.psum(v.sum(), group_axis) for k, v in metrics.items()
            }
            return new_global, out_metrics
        pb = participation.astype(bool)
        cw_all = jnp.where(pb, counts.astype(jnp.float32), 0.0)
        gw = jax.lax.psum(cw_all.sum(axis=1), client_axis)
        # group-level quarantine: one poisoned client contaminates its whole
        # group's inner-round running mean, so the recovery granularity at
        # the cloud is the group — non-finite groups get zero weight and
        # `where`-zeroed variables
        fin_g = client_finite_mask(group_vars)
        gw_eff = jnp.where(fin_g, gw, 0.0)

        def zero_bad_group(leaf):
            keep = fin_g.reshape((-1,) + (1,) * (leaf.ndim - 1))
            return jnp.where(keep, leaf, jnp.zeros((), leaf.dtype))

        new_global = tree_weighted_mean_psum(
            jax.tree.map(zero_bad_group, group_vars), gw_eff, group_axis)
        any_alive = jax.lax.psum(gw_eff.sum(), group_axis) > 0
        new_global = tree_where(any_alive, new_global, global_variables)
        # participating clients per local group, cloud-summed by survival
        p_g = jax.lax.psum(pb.astype(jnp.float32).sum(axis=1), client_axis)
        out_metrics = {
            k: jax.lax.psum(jnp.where(fin_g, v, jnp.zeros((), v.dtype)).sum(),
                            group_axis)
            for k, v in metrics.items()
        }
        out_metrics["participated_count"] = jax.lax.psum(
            jnp.where(fin_g, p_g, 0.0).sum(), group_axis)
        out_metrics["quarantined_count"] = jax.lax.psum(
            jnp.where(fin_g, 0.0, p_g).sum(), group_axis)
        return new_global, out_metrics

    def round_fn(global_variables, x, y, counts, rng, participation=None):
        data_spec = P(group_axis, client_axis)
        if participation is None:
            sharded = jax.shard_map(
                shard_body,
                mesh=mesh,
                in_specs=(P(), data_spec, data_spec, data_spec, P()),
                out_specs=(P(), P()),
            )
            return sharded(global_variables, x, y, counts, rng)
        sharded = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(), data_spec, data_spec, data_spec, P(), data_spec),
            out_specs=(P(), P()),
        )
        return sharded(global_variables, x, y, counts, rng, participation)

    return jax.jit(round_fn)
