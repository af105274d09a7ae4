"""Tensor-parallel federated rounds: regex partition rules on a 2D
('clients', 'tensor') mesh.

Promotes `analysis/partition.py::match_partition_rules` from the lint-only
coverage contract (PR 3) to a runtime sharding subsystem: per-model-family
rule tables below resolve a variables/opt-state tree into a PartitionSpec
tree over `make_tensor_mesh`'s ('clients', 'tensor') mesh, and
`build_tensor_round_fn` runs the federated round under pjit with the
persistent state tensor-sharded and DONATED (old shards alias the new).
Cohort sharding and the optional trailing participation mask are exactly
the PR 4/5 contract — same key table, same quarantine staging, same
all-dead no-op guard.

What is sharded (v1):

- the persistent state: global variables AND aggregator state (the FedOpt
  server momenta are param-sized x2) live tensor-sharded between rounds —
  per-device resident param bytes shrink by ~|tensor| (tools/
  bench_tensor_shard.py -> BENCH_SHARD_r01.json);
- the aggregation data path: client update stacks are sliced to the
  device's tensor shard BEFORE the client-axis reductions, so the
  weighted-mean partial sums, the psums that carry them, the FedOpt server
  step and the FedNova recombine all move/compute 1/|tensor| of the bytes;
- the client vmap step computes on gathered (full) params: the explicit
  per-leaf `all_gather` at the round's entry and the `dynamic_slice` at
  the aggregation boundary are the two layer-boundary resharding points —
  the shard_map-manual analog of a `with_sharding_constraint` pair in
  GSPMD-automatic pjit. Splitting the client-step matmuls themselves
  (Megatron-style — the qkv/proj column/row rules below already encode
  that layout) reassociates float contractions and is deliberately left
  to a tolerance-gated follow-up: this path keeps bit-identity.

Bit-identity contract: `all_gather`/`dynamic_slice` are pure data
movement and slicing commutes exactly with every elementwise aggregation
rule, so a tensor-sharded round is BIT-IDENTICAL in f32 to the replicated
round on the same mesh (REPLICATED_RULES; pinned by
tests/test_tensor_shard.py, fedavg/fedopt/robust/fednova, masked and
unmasked). The same holds in bf16 on this path — no reduction is
reassociated; only a future compute-split would introduce a documented
tolerance. Versus the single-chip vmap engine the usual client-psum
reassociation applies (<=1e-6, same as parallel/sharded.py).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from fedml_tpu.analysis.partition import _flat_paths, match_partition_rules
from fedml_tpu.core.builder import (all_gather_invariant, build_round_core,
                                    donation_argnums, masked_psum_tail,
                                    shard_key_slice)
from fedml_tpu.core.config import FedConfig

CLIENT_AXIS = "clients"
TENSOR_AXIS = "tensor"

# --------------------------------------------------------------- rule tables
#
# (path regex, spec) per model family; first match wins, scalars
# auto-replicate, an UNMATCHED leaf raises — that is the coverage contract,
# held at 100% over these tables by graft-lint's
# partition-coverage[tensor-rules] rule (analysis/targets.py). Rules are
# matched against opt-state trees too (optax paths embed the param path, so
# `kernel$` covers `0/mu/block0/qkv/kernel`).

# Megatron layout for the transformer blocks: qkv/mlp_up are
# column-parallel (shard out-features = heads / ffn dim), proj/mlp_down are
# row-parallel (shard in-features — the same heads / ffn dim), embeddings
# and lm_head shard d_model. Norms and biases replicate.
TRANSFORMER_PARTITION_RULES: List[Tuple[str, PS]] = [
    # LoRA adapters (models/lora.py) replicate: rank-r factors are tiny and
    # every device needs both to fold base + A @ B. The frozen base keeps
    # matching the kernel rules below through its lora_base/... paths, so a
    # LoRA-wrapped model tensor-shards the big frozen matrices while the
    # federated (trainable) tree stays replicated. First match wins.
    (r"lora_[AB]$", PS()),
    (r"(tok_emb|pos_emb)/embedding$", PS(None, TENSOR_AXIS)),
    (r"qkv/kernel$", PS(None, TENSOR_AXIS)),
    (r"proj/kernel$", PS(TENSOR_AXIS, None)),
    (r"mlp_up/kernel$", PS(None, TENSOR_AXIS)),
    (r"mlp_down/kernel$", PS(TENSOR_AXIS, None)),
    (r"lm_head/kernel$", PS(TENSOR_AXIS, None)),
    (r"(bias|scale)$", PS()),
]

# LSTM gate kernels shard their out-features (the hidden dim), embeddings
# shard the embedding dim, the vocab-sized output projections shard
# out-features. 670-unit stackoverflow kernels are not divisible by small
# tensor axes — resolve_param_specs demotes those leaves to replicated.
RNN_PARTITION_RULES: List[Tuple[str, PS]] = [
    (r"lora_[AB]$", PS()),  # adapters replicate (see transformer table)
    (r"embeddings/embedding$", PS(None, TENSOR_AXIS)),
    (r"OptimizedLSTMCell_\d+/[ih][ifgo]/kernel$", PS(None, TENSOR_AXIS)),
    (r"fc\d?/kernel$", PS(None, TENSOR_AXIS)),
    (r"(bias|scale)$", PS()),
]

# Fallback for the rest of the zoo (lr / mlp / cnn...): shard dense
# in-features (dim 0 — always the large dim for classifier heads), keep
# everything else replicated. Conv kernels ([kh, kw, cin, cout]) hit the
# kernel rule on their tiny kh dim and get demoted to replicated — safe,
# just not sharded.
DEFAULT_TENSOR_RULES: List[Tuple[str, PS]] = [
    (r"lora_[AB]$", PS()),  # adapters replicate (see transformer table)
    (r"embedding$", PS(None, TENSOR_AXIS)),
    (r"kernel$", PS(TENSOR_AXIS, None)),
    (r"(bias|scale)$", PS()),
    (r"(mean|var|count)$", PS()),
]

# every leaf replicated — the baseline arm of the bit-identity tests and
# bench (same program, gathers and slices fold to no-ops)
REPLICATED_RULES: List[Tuple[str, PS]] = [(r".", PS())]

RULE_TABLES = {
    "transformer": TRANSFORMER_PARTITION_RULES,
    "rnn": RNN_PARTITION_RULES,
}

# registry models each family's table must cover at 100% (the lint pin)
FAMILY_MODELS = {
    "transformer": ("transformer_nwp",),
    "rnn": ("rnn", "rnn_stackoverflow"),
}


def rules_for_model(model_name: str) -> List[Tuple[str, PS]]:
    """Family rule table for a registry model name (prefix dispatch);
    unknown families fall back to the generic dense table."""
    if model_name.startswith("transformer"):
        return TRANSFORMER_PARTITION_RULES
    if model_name.startswith("rnn"):
        return RNN_PARTITION_RULES
    return DEFAULT_TENSOR_RULES


# ---------------------------------------------------------- spec resolution

def _tensor_dim(spec) -> Optional[int]:
    """Index of the dim a spec shards over the tensor axis (None if the
    leaf is replicated over it)."""
    if not isinstance(spec, PS):
        return None
    for d, ax in enumerate(spec):
        if ax == TENSOR_AXIS or (isinstance(ax, (tuple, list))
                                 and TENSOR_AXIS in ax):
            return d
    return None


def resolve_param_specs(rules: Sequence[Tuple[str, PS]], tree,
                        tensor_shards: int):
    """match_partition_rules + per-leaf divisibility demotion.

    Returns (spec_tree, demoted) where `demoted` lists the paths whose
    matched rule shards a dim not divisible by `tensor_shards` — those
    leaves fall back to replicated (explicitly, here, instead of deep in a
    device_put error). Raises ValueError on an unmatched leaf, same as the
    lint contract."""
    specs = match_partition_rules(rules, tree)
    flat_leaves = _flat_paths(tree)
    flat_specs = [s for _, s in _flat_paths(specs)]
    demoted: List[str] = []
    resolved = []
    for (path, leaf), spec in zip(flat_leaves, flat_specs):
        d = _tensor_dim(spec)
        if d is not None and (d >= getattr(leaf, "ndim", 0)
                              or leaf.shape[d] % tensor_shards):
            demoted.append(path)
            spec = PS()
        resolved.append(spec)
    spec_tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree), resolved)
    return spec_tree, demoted


@dataclasses.dataclass(frozen=True)
class TensorSharding:
    """The `param_sharding` seam: a ('clients', 'tensor') mesh plus the
    rule table that places every persistent-state leaf on it. Passed to
    `algorithms/engine.py::build_round_fn` to swap the single-chip vmap
    round for the tensor-sharded one."""

    mesh: Mesh
    rules: Tuple[Tuple[str, PS], ...]

    @classmethod
    def for_model(cls, mesh: Mesh, model_name: str) -> "TensorSharding":
        return cls(mesh, tuple(rules_for_model(model_name)))

    @property
    def tensor_shards(self) -> int:
        return self.mesh.shape[TENSOR_AXIS]

    def specs(self, tree):
        return resolve_param_specs(self.rules, tree, self.tensor_shards)[0]

    def shardings(self, tree):
        specs = self.specs(tree)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda s: isinstance(s, PS))

    def place(self, tree):
        """Commit a host/replicated state tree to its tensor-sharded
        layout (one device_put per leaf). The round donates these buffers
        and returns identically-sharded ones."""
        return jax.device_put(tree, self.shardings(tree))

    def per_device_bytes(self, tree) -> Tuple[int, int]:
        """(replicated_bytes, sharded_bytes) a single device holds for
        `tree` — the BENCH_SHARD accounting, computable from specs alone."""
        specs, _ = resolve_param_specs(self.rules, tree, self.tensor_shards)
        flat = _flat_paths(tree)
        flat_specs = [s for _, s in _flat_paths(specs)]
        repl = shard = 0
        for (_, leaf), spec in zip(flat, flat_specs):
            nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            repl += nbytes
            shard += nbytes // (self.tensor_shards
                                if _tensor_dim(spec) is not None else 1)
        return repl, shard


# -------------------------------------------------- shard-local tree movers

def _gather_tree(tree, specs):
    """Reassemble full leaves from tensor shards (tiled all_gather on each
    sharded leaf's dim) — the round-entry layer boundary. The gather is the
    invariant-typed one: the full leaves are identical on every tensor
    device and check_vma must know it, or the client step's scan carries
    (gathered and un-gathered leaves side by side) stop typing."""
    def gather(leaf, spec):
        d = _tensor_dim(spec)
        if d is None:
            return leaf
        return all_gather_invariant(leaf, TENSOR_AXIS, axis=d, tiled=True)

    return jax.tree.map(gather, tree, specs,
                        is_leaf=lambda x: isinstance(x, PS))


def _slice_tree(tree, specs, tensor_shards: int, lead: int = 0):
    """This device's tensor shard of full leaves (`lead` skips stacked
    client axes) — the aggregation-boundary reshard. Pure dynamic_slice:
    together with _gather_tree it is exact data movement, the root of the
    bit-identity contract."""
    tidx = jax.lax.axis_index(TENSOR_AXIS)

    def one(leaf, spec):
        d = _tensor_dim(spec)
        if d is None:
            return leaf
        size = leaf.shape[d + lead] // tensor_shards
        return jax.lax.dynamic_slice_in_dim(leaf, tidx * size, size,
                                            axis=d + lead)

    return jax.tree.map(one, tree, specs,
                        is_leaf=lambda x: isinstance(x, PS))


# ------------------------------------------------- codec transport (uplink
# + downlink). The tensor round is the one program whose collectives ARE
# the federation's wire traffic in both directions: the entry all_gather
# broadcasts the model to the client-hosting devices, the clients-axis
# reductions carry the updates back. A codec therefore compresses BOTH
# legs — measured split on the tformer budget program: 1.85 MB of gather
# (downlink) vs 0.47 MB of psum (uplink), so an uplink-only codec could
# never reach the 4x wire shrink the COMMS budget pins.

def _quantized_gather_tree(tree, specs, tensor_shards: int, levels: int):
    """Codec downlink: each device int8-quantizes its local shard slice
    (per-shard scale, deterministic rounding), the all_gather moves int8
    payloads + a (tensor_shards,) f32 scale vector per leaf, and every
    device dequantizes tile-wise. Replicated leaves move no gather bytes
    and pass through exact."""
    def gather(leaf, spec):
        d = _tensor_dim(spec)
        if d is None:
            return leaf
        amax = jnp.max(jnp.abs(leaf))
        scale = jnp.where(amax > 0, amax / levels, jnp.ones((), leaf.dtype))
        q = jnp.clip(jnp.round(leaf / scale), -levels, levels).astype(jnp.int8)
        qg = all_gather_invariant(q, TENSOR_AXIS, axis=d, tiled=True)
        sg = all_gather_invariant(scale, TENSOR_AXIS)  # (t_sz,) f32
        size = leaf.shape[d]
        shp = qg.shape
        qt = qg.reshape(shp[:d] + (tensor_shards, size) + shp[d + 1:])
        sshape = (1,) * d + (tensor_shards, 1) + (1,) * (len(shp) - d - 1)
        dec = qt.astype(leaf.dtype) * sg.reshape(sshape)
        return dec.reshape(shp)

    return jax.tree.map(gather, tree, specs,
                        is_leaf=lambda x: isinstance(x, PS))


def _shifted_spec(spec, inexact: bool):
    """Residual-leaf spec: leading per-device slot dim over CLIENT_AXIS,
    trailing dims tensor-sharded like the gv leaf (passthrough leaves keep
    only the slot dim)."""
    d = _tensor_dim(spec)
    if d is None or not inexact:
        return PS(CLIENT_AXIS)
    return PS(*((CLIENT_AXIS,) + (None,) * d + (TENSOR_AXIS,)))


def codec_residual_specs(specs_gv, global_variables):
    """PartitionSpecs for the tensor round's uplink residual tree."""
    return jax.tree.map(
        lambda s, l: _shifted_spec(s, jnp.issubdtype(l.dtype, jnp.inexact)),
        specs_gv, global_variables, is_leaf=lambda x: isinstance(x, PS))


def init_codec_agg_state(sharding: "TensorSharding", global_variables,
                         inner_state):
    """Placed {"agg", "codec"} state for a codec-on tensor round: the inner
    aggregator state tensor-sharded as usual, plus the per-device
    error-feedback residual (zeros, one slot per clients-axis device,
    trailing dims sharded like gv). Donated with the rest of the state."""
    from fedml_tpu.models.lora import strip_lora_base

    # the residual mirrors the WIRE tree — adapters-only under LoRA (the
    # frozen base never crosses the uplink, so it carries no error feedback)
    fed_gv = strip_lora_base(global_variables)
    n_cl = sharding.mesh.shape[CLIENT_AXIS]
    resid = jax.tree.map(
        lambda l: jnp.zeros(
            (n_cl,) + (l.shape if jnp.issubdtype(l.dtype, jnp.inexact)
                       else ()), l.dtype),
        fed_gv)
    specs_gv = sharding.specs(fed_gv)
    rspecs = codec_residual_specs(specs_gv, fed_gv)
    shardings = jax.tree.map(
        lambda s: NamedSharding(sharding.mesh, s), rspecs,
        is_leaf=lambda s: isinstance(s, PS))
    return {
        "agg": sharding.place(inner_state),
        "codec": jax.device_put(resid, shardings),
    }


def _add_noise_sharded(aggregator, avg_shard, rng, full_params, specs_params,
                       tensor_shards: int):
    """RobustAggregator._add_noise with the SAME full-shape normal draws as
    the replicated path, sliced to this device's shard — key-per-leaf and
    draw shape unchanged, so sharded noise == replicated noise[shard]."""
    noise_rng = jax.random.fold_in(rng, 7)
    leaves, treedef = jax.tree.flatten(avg_shard["params"])
    full_leaves = jax.tree.leaves(full_params)
    spec_leaves = [s for _, s in _flat_paths(specs_params)]
    keys = jax.random.split(noise_rng, len(leaves))
    tidx = jax.lax.axis_index(TENSOR_AXIS)
    noisy = []
    for leaf, key, full, spec in zip(leaves, keys, full_leaves, spec_leaves):
        noise = aggregator.cfg.stddev * jax.random.normal(
            key, full.shape, leaf.dtype)
        d = _tensor_dim(spec)
        if d is not None:
            size = full.shape[d] // tensor_shards
            noise = jax.lax.dynamic_slice_in_dim(noise, tidx * size, size,
                                                 axis=d)
        noisy.append(leaf + noise)
    out = dict(avg_shard)
    out["params"] = jax.tree.unflatten(treedef, noisy)
    return out


@jax.named_scope("aggregate")
def _aggregate_sharded(aggregator, gv_shard, gv_full, result, result_shard,
                       weights, rng, agg_state, specs_gv, tensor_shards):
    """Dispatch one aggregator over tensor-sharded client stacks.

    fedavg/fedopt/fednova are elementwise over param dims, so their
    existing `sharded` (clients-psum) rules run unchanged on shard-sized
    trees — slicing commutes exactly. RobustAggregator's clip norm is a
    reduction over the WHOLE tree, so the clip runs on the full stacks
    (replicated over tensor — deterministic) and only the clipped result
    is sliced into the mean; the DP noise slices the replicated full-shape
    draw (see _add_noise_sharded)."""
    from fedml_tpu.algorithms.aggregators import (RobustAggregator,
                                                  tree_weighted_mean_psum)

    if isinstance(aggregator, RobustAggregator):
        clipped = aggregator._clipped(gv_full, result)
        clipped_shard = _slice_tree(clipped, specs_gv, tensor_shards, lead=1)
        avg = tree_weighted_mean_psum(clipped_shard, weights, CLIENT_AXIS)
        avg = _add_noise_sharded(aggregator, avg, rng, gv_full["params"],
                                 specs_gv["params"], tensor_shards)
        return avg, agg_state
    return aggregator.sharded(gv_shard, result_shard, weights, rng,
                              agg_state, CLIENT_AXIS)


# ------------------------------------------------------------ round builder

def build_tensor_round_fn(trainer, cfg: FedConfig, aggregator,
                          sharding: TensorSharding,
                          donate_state: bool = True,
                          donate_data: bool = False,
                          collect_stats: bool = False,
                          codec=None) -> Callable:
    """Jitted tensor-sharded round over sharding.mesh — the runtime the
    rule tables exist for.

    Same signature and semantics as engine.build_round_fn /
    parallel.sharded.build_sharded_round_fn:
    (gv, agg_state, x, y, counts, rng[, participation]) ->
    (new_gv, new_agg_state, metrics), where gv/agg_state live
    tensor-sharded (place them once with `sharding.place`; outputs come
    back identically sharded). C must divide by mesh.shape['clients'];
    the participation mask arms PR-4 fault tolerance bit-identically to
    the replicated round (quarantine runs on the FULL stacks — a NaN in
    any tensor shard quarantines the client everywhere).

    `donate_state` (default ON — pjit donation of argnums (0, 1)) aliases
    the old state shards into the new: between-round state costs ONE
    sharded copy of params + opt state. Callers that snapshot live state
    refs (the guard's rollback) must turn it off. `donate_data` matches
    the engine's opt-in cohort-buffer donation for the pipelined loop.
    """
    from fedml_tpu.algorithms.aggregators import quarantine_stage
    from fedml_tpu.algorithms.engine import build_local_update, cohort_stats
    from fedml_tpu.models.lora import attach_lora_base, strip_lora_base

    mesh = sharding.mesh
    n_cl = mesh.shape[CLIENT_AXIS]
    t_sz = mesh.shape[TENSOR_AXIS]
    local_update = build_local_update(trainer, cfg, pvary_axes=(CLIENT_AXIS,))

    if codec is not None:
        from fedml_tpu.algorithms.aggregators import (FedAvgAggregator,
                                                      FedOptAggregator)
        if not isinstance(aggregator, (FedAvgAggregator, FedOptAggregator)):
            raise ValueError(
                "update codecs on the tensor path support fedavg/fedopt "
                "only: robust clips whole-tree norms of raw client deltas "
                "and fednova recombines per-client taus — both would "
                "silently run on already-decoded values. Got %r"
                % type(aggregator).__name__)
        # downlink grid: reuse the int8 codec's level count; top-k has no
        # scalar grid of its own, so its downlink rides the full int8 one
        down_levels = codec.levels if codec.kind == "int8" else 127
        is_fedopt = isinstance(aggregator, FedOptAggregator)

    def specialize(specs_gv, specs_st, masked: bool):
        # federated LoRA: client results are adapters-only (the base leaves
        # local_update inside the vmap), so every aggregation-side tree.map
        # must run over the base-stripped "federated view" of gv/specs —
        # identical to the full trees when the trainer isn't wrapped
        specs_fed = strip_lora_base(specs_gv) if isinstance(specs_gv, dict) \
            else specs_gv

        def shard_body(gv_shard, st_shard, x, y, counts, rng,
                       participation=None):
            c_local = x.shape[0]
            didx = jax.lax.axis_index(CLIENT_AXIS)
            # same key table as the vmap engine / 1-D sharded round:
            # split(rng, C)[d*c_local:(d+1)*c_local]
            crngs = shard_key_slice(rng, c_local * n_cl, didx, c_local)
            gv_full = _gather_tree(gv_shard, specs_gv)
            result = jax.vmap(local_update, in_axes=(None, 0, 0, 0, 0))(
                gv_full, x, y, counts, crngs)
            # ledger stats: per-client rows from the FULL (gathered) result,
            # so they are invariant over the tensor axis by the same
            # argument as result.metrics — check_vma accepts the
            # PS(CLIENT_AXIS) out-spec with zero new collectives
            stats = cohort_stats(gv_full, result) if collect_stats else None
            weights = counts.astype(jnp.float32)
            if participation is not None:
                result, weights, alive, quarantined = quarantine_stage(
                    result, weights, participation)
            result_shard = result._replace(variables=_slice_tree(
                result.variables, specs_fed, t_sz, lead=1))
            new_gshard, new_st = _aggregate_sharded(
                aggregator, strip_lora_base(gv_shard),
                strip_lora_base(gv_full), result, result_shard,
                weights, rng, st_shard, specs_fed, t_sz)
            # the server's frozen base shards re-attach untouched (no-op
            # when the trainer isn't LoRA-wrapped)
            new_gshard = attach_lora_base(new_gshard, gv_shard)
            metrics = {k: jax.lax.psum(v.sum(), CLIENT_AXIS)
                       for k, v in result.metrics.items()}
            if participation is None:
                if collect_stats:
                    return new_gshard, new_st, metrics, stats
                return new_gshard, new_st, metrics
            new_gshard, new_st, metrics = masked_psum_tail(
                new_gshard, new_st, metrics, alive, quarantined,
                gv_shard, st_shard, CLIENT_AXIS)
            if collect_stats:
                return new_gshard, new_st, metrics, stats
            return new_gshard, new_st, metrics

        def shard_body_codec(gv_shard, st_shard, x, y, counts, rng,
                             participation=None):
            """Codec-on twin of shard_body: int8 downlink on the entry
            gather, codec uplink (transport_wsum) on the clients-axis
            reduction of locally-weighted delta partial sums, device-
            resident error-feedback residual in st_shard["codec"]."""
            from fedml_tpu.codecs.transport import transport_wsum

            inner_st = st_shard["agg"]
            resid = st_shard["codec"]
            c_local = x.shape[0]
            didx = jax.lax.axis_index(CLIENT_AXIS)
            crngs = shard_key_slice(rng, c_local * n_cl, didx, c_local)
            gv_full = _quantized_gather_tree(gv_shard, specs_gv, t_sz,
                                             down_levels)
            result = jax.vmap(local_update, in_axes=(None, 0, 0, 0, 0))(
                gv_full, x, y, counts, crngs)
            stats = cohort_stats(gv_full, result) if collect_stats else None
            weights = counts.astype(jnp.float32)
            if participation is not None:
                result, weights, alive, quarantined = quarantine_stage(
                    result, weights, participation)
            vars_shard = _slice_tree(result.variables, specs_fed, t_sz,
                                     lead=1)
            fed_gshard = strip_lora_base(gv_shard)

            # local numerator partials: sum_i w_i * (vars_i - gv) for
            # inexact leaves (deltas are what the codec encodes — small,
            # zero-centered), plain weighted sums for passthrough leaves
            def local_partial(l, g):
                wb = weights.reshape((-1,) + (1,) * (l.ndim - 1))
                if jnp.issubdtype(l.dtype, jnp.inexact):
                    return jnp.sum((l - g[None]) * wb.astype(l.dtype),
                                   axis=0)
                return jnp.sum(l * wb.astype(l.dtype), axis=0)

            wsum = jax.tree.map(local_partial, vars_shard, fed_gshard)
            r0 = jax.tree.map(lambda r: r[0], resid)
            num, r_new = transport_wsum(codec, wsum, r0, CLIENT_AXIS, n_cl)
            den = jax.lax.psum(weights.sum(), CLIENT_AXIS)
            inv = 1.0 / jnp.maximum(den, 1e-12)
            avg = jax.tree.map(
                lambda g, s: (g + s * jnp.asarray(inv, s.dtype)).astype(
                    g.dtype)
                if jnp.issubdtype(g.dtype, jnp.inexact)
                else (s * inv).astype(g.dtype),
                fed_gshard, num)
            if is_fedopt:
                new_gshard, new_inner = aggregator._server_step(
                    fed_gshard, avg, inner_st)
            else:
                new_gshard, new_inner = avg, inner_st
            new_gshard = attach_lora_base(new_gshard, gv_shard)
            new_st = {"agg": new_inner,
                      "codec": jax.tree.map(lambda r: r[None], r_new)}
            metrics = {k: jax.lax.psum(v.sum(), CLIENT_AXIS)
                       for k, v in result.metrics.items()}
            if participation is None:
                if collect_stats:
                    return new_gshard, new_st, metrics, stats
                return new_gshard, new_st, metrics
            new_gshard, new_st, metrics = masked_psum_tail(
                new_gshard, new_st, metrics, alive, quarantined,
                gv_shard, st_shard, CLIENT_AXIS)
            if collect_stats:
                return new_gshard, new_st, metrics, stats
            return new_gshard, new_st, metrics

        body = shard_body if codec is None else shard_body_codec
        data_specs = (PS(CLIENT_AXIS), PS(CLIENT_AXIS), PS(CLIENT_AXIS))
        in_specs = (specs_gv, specs_st) + data_specs + (PS(),)
        if masked:
            in_specs = in_specs + (PS(CLIENT_AXIS),)
        out_specs = (specs_gv, specs_st, PS())
        if collect_stats:
            out_specs = out_specs + (PS(CLIENT_AXIS),)
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs)
        donate = donation_argnums(donate_state, donate_data)
        return jax.jit(fn, donate_argnums=donate) if donate else jax.jit(fn)

    cache: dict = {}

    def _specialized(global_variables, agg_state, masked: bool):
        key = (jax.tree.structure(global_variables),
               tuple(l.shape for l in jax.tree.leaves(global_variables)),
               jax.tree.structure(agg_state),
               tuple(l.shape for l in jax.tree.leaves(agg_state)),
               masked)
        jitted = cache.get(key)
        if jitted is None:
            specs_gv = sharding.specs(global_variables)
            if codec is not None:
                # wrapped {"agg", "codec"} state (init_codec_agg_state):
                # inner state sharded as usual, residual rows on the
                # shifted (CLIENT_AXIS, ..., TENSOR_AXIS) layout
                from fedml_tpu.models.lora import strip_lora_base as _strip
                fed_gv = _strip(global_variables)
                specs_st = {
                    "agg": sharding.specs(agg_state["agg"]),
                    "codec": codec_residual_specs(_strip(specs_gv)
                                                  if isinstance(specs_gv,
                                                                dict)
                                                  else specs_gv, fed_gv),
                }
            else:
                specs_st = sharding.specs(agg_state)
            jitted = specialize(specs_gv, specs_st, masked)
            cache[key] = jitted
        return jitted

    def round_fn(global_variables, agg_state, x, y, counts, rng,
                 participation=None):
        jitted = _specialized(global_variables, agg_state,
                              participation is not None)
        round_fn.jitted = jitted  # graft-lint donation introspection
        args = (global_variables, agg_state, x, y, counts, rng)
        if participation is not None:
            args += (participation,)
        # CPU can't alias some donated shapes — the fallback is a plain
        # copy, so the per-compile warning is noise (engine.py idiom)
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*onat")
            return jitted(*args)

    def lower(*args):
        """jax.jit-compatible lower — the HLO engine (analysis/comms.py)
        lowers round programs from ShapeDtypeStructs without executing."""
        return _specialized(args[0], args[1], len(args) > 6).lower(*args)

    round_fn.lower = lower
    round_fn.sharding = sharding
    round_fn.donate_state = donate_state
    return round_fn


# ----------------------------------------- activation-sharded step (GSPMD)
#
# The shard_map round above gathers FULL params to every device before the
# client vmap step — per-device peak bytes during the step scale with the
# whole model. `--shard_step` swaps that for GSPMD automatic partitioning:
# the round jits with params tensor-sharded per the rule table as
# `in_shardings` and the model zoo's `constrain()` hooks
# (parallel/activations.py) pin attention/MLP/logits intermediates to the
# tensor axis, so the step's matmuls split Megatron-style and the big
# activations never materialize whole on one device. Measured on the forced
# 8-device CPU mesh: 0.24x per-device peak temp bytes for the transformer
# step at 4 shards (COMMS_BUDGET.json `tensor.step` twins pin the <=0.5x
# ratio in CI). The trade, documented in ROADMAP/PERF: GSPMD reassociates
# float contractions, so `shard_step` carries an allclose contract
# (tests/test_lora.py pins the tolerance) instead of the shard_map path's
# f32 bit-identity; at tensor_shards <= 1 the constraint scope is
# structurally off and the program is the plain jitted round.

def build_tensor_step_fn(trainer, cfg: FedConfig, sharding: TensorSharding,
                         activation_rules="auto"):
    """The client step ALONE — vmap(local_update) jitted under GSPMD with
    rule-table `in_shardings` and the activation-constraint scope. This is
    the `tensor.step` program analysis/comms.py lowers for the per-device
    peak-bytes budgets; the full drive uses build_tensor_step_round_fn.

    `activation_rules`: "auto" looks the model family's table up
    (parallel/activations.py); None disables the constraint scope — the
    replicated budget twin the <=0.5x peak ratio is measured against."""
    from fedml_tpu.algorithms.engine import build_local_update
    from fedml_tpu.parallel.activations import (activation_rules_for_model,
                                                activation_sharding)

    mesh = sharding.mesh
    act_rules = (activation_rules_for_model(cfg.model)
                 if activation_rules == "auto" else activation_rules)
    local_update = build_local_update(trainer, cfg)

    def step(global_variables, x, y, counts, rng):
        crngs = jax.random.split(rng, x.shape[0])
        return jax.vmap(local_update, in_axes=(None, 0, 0, 0, 0))(
            global_variables, x, y, counts, crngs)

    data_sh = NamedSharding(mesh, PS(CLIENT_AXIS))
    cache: dict = {}

    def _specialized(gv):
        key = (jax.tree.structure(gv),
               tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(gv)))
        jitted = cache.get(key)
        if jitted is None:
            jitted = jax.jit(step, in_shardings=(
                sharding.shardings(gv), data_sh, data_sh, data_sh, None))
            cache[key] = jitted
        return jitted

    def step_fn(global_variables, x, y, counts, rng):
        # the constraint hooks read the scope at TRACE time; entering it
        # around every call keeps cached traces consistent (the scope is a
        # constant of this builder)
        with activation_sharding(mesh, act_rules):
            return _specialized(global_variables)(
                global_variables, x, y, counts, rng)

    def lower(*args):
        with activation_sharding(mesh, act_rules):
            return _specialized(args[0]).lower(*args)

    step_fn.lower = lower
    step_fn.sharding = sharding
    return step_fn


def build_tensor_step_round_fn(trainer, cfg: FedConfig, aggregator,
                               sharding: TensorSharding,
                               donate_state: bool = True,
                               donate_data: bool = False,
                               collect_stats: bool = False,
                               codec=None) -> Callable:
    """The `--shard_step` round: engine.round_fn semantics (same rng table,
    same quarantine staging, same all-dead no-op guard, same LoRA
    strip/attach) jitted under GSPMD on sharding.mesh — params, opt state
    AND the step's intermediates tensor-sharded; aggregation math is plain
    jnp that GSPMD partitions. State lives sharded between rounds exactly
    like the shard_map tensor round (`sharding.place` once, outputs come
    back identically sharded), so FedAvgAPI's tensor plumbing works
    unchanged."""
    if codec is not None:
        raise ValueError(
            "--shard_step runs under GSPMD automatic partitioning — the "
            "codec transports are manual shard_map collectives and do not "
            "compose with it. Drop --shard_step (the storage-sharded "
            "tensor round supports codecs) or --update_codec.")
    from fedml_tpu.algorithms.engine import _vmapped_update
    from fedml_tpu.parallel.activations import (activation_rules_for_model,
                                                activation_sharding)

    mesh = sharding.mesh
    n_cl = mesh.shape[CLIENT_AXIS]
    t_sz = mesh.shape[TENSOR_AXIS]
    act_rules = activation_rules_for_model(cfg.model)
    # the round body IS the engine's round: the shared core from
    # core/builder.py (same rng table, quarantine staging, all-dead guard,
    # LoRA strip/attach), jitted under GSPMD instead of plain jit — the
    # --equiv engine proves the two programs identical up to sharding
    # annotations (the tensor-shards-1 contract)
    core = build_round_core(_vmapped_update(trainer, cfg), aggregator,
                            collect_stats)

    def round_body(global_variables, agg_state, x, y, counts, rng,
                   participation=None):
        new_global, new_state, metrics, stats = core(
            global_variables, agg_state, x, y, counts, rng, participation)
        if collect_stats:
            return new_global, new_state, metrics, stats
        return new_global, new_state, metrics

    data_sh = NamedSharding(mesh, PS(CLIENT_AXIS))
    repl_sh = NamedSharding(mesh, PS())
    cache: dict = {}

    def _specialized(global_variables, agg_state, masked: bool):
        key = (jax.tree.structure(global_variables),
               tuple(l.shape for l in jax.tree.leaves(global_variables)),
               jax.tree.structure(agg_state),
               tuple(l.shape for l in jax.tree.leaves(agg_state)),
               masked)
        jitted = cache.get(key)
        if jitted is None:
            gv_sh = sharding.shardings(global_variables)
            st_sh = sharding.shardings(agg_state)
            in_sh = (gv_sh, st_sh, data_sh, data_sh, data_sh, None)
            if masked:
                in_sh = in_sh + (data_sh,)
            out_sh = (gv_sh, st_sh, repl_sh)
            if collect_stats:
                out_sh = out_sh + (data_sh,)
            donate = donation_argnums(donate_state, donate_data)
            jitted = jax.jit(round_body, in_shardings=in_sh,
                             out_shardings=out_sh, donate_argnums=donate)
            cache[key] = jitted
        return jitted

    def round_fn(global_variables, agg_state, x, y, counts, rng,
                 participation=None):
        jitted = _specialized(global_variables, agg_state,
                              participation is not None)
        round_fn.jitted = jitted  # graft-lint donation introspection
        args = (global_variables, agg_state, x, y, counts, rng)
        if participation is not None:
            args += (participation,)
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*onat")
            with activation_sharding(mesh, act_rules):
                return jitted(*args)

    def lower(*args):
        with activation_sharding(mesh, act_rules):
            return _specialized(args[0], args[1],
                                len(args) > 6).lower(*args)

    round_fn.lower = lower
    round_fn.sharding = sharding
    round_fn.donate_state = donate_state
    return round_fn
