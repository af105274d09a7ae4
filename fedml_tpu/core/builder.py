"""The ONE composable round-program builder (ROADMAP item 5, second half).

core/spec.py declares the feature matrix; this module CASHES it:
`build_round_program(levels, **extra)` composes model x aggregator x mask x
quarantine x stats x codec x adapter x sharding into the round family's
program(s) from a spec point alone — the same composition the five legacy
assembly sites (engine vmap, buffered admit, parallel/{sharded,tensor,
hierarchical}.py) used to thread by hand. Those sites now delegate their
shared fragments to the helpers below (`build_round_core`,
`masked_psum_tail`, `shard_key_slice`, `donating_jit`, `donation_argnums`,
`wrap_codec`), so each cross-cutting feature has exactly one definition.

analysis/equiv_engine.py (--equiv) certifies the composition: it proves the
builder-emitted jaxpr structurally identical to the hand-assembled legacy
baseline for every matrix cover point and for the standing EQUIV_PAIRS
contracts (codec=none, mask-omitted, tensor_shards=1, rounds_per_dispatch=1,
lora_rank=0). The dispatch below derives the round family from the
EFFECTIVE config — `point_config(levels, **extra)` projected back through
`axis_levels` — which is what makes the structurally-off contracts true by
construction: `rounds_per_dispatch=1` projects superstep=off and never
builds the scan, `lora_rank=0` is `maybe_wrap_lora`'s identity, and codec
level `none` never constructs a CodecAggregator.

Module scope imports only jax + pytree utils: algorithms/* and parallel/*
import THIS module for the shared fragments, so everything heavier loads
lazily inside the functions that need it.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from fedml_tpu.utils.pytree import tree_where

# --------------------------------------------------------- shared fragments

# The gather whose result every device holds alike, TYPED so: under
# shard_map's check_vma, jax 0.9.0's public `jax.lax.all_gather` is
# Varying -> Varying, and only this Varying -> Invariant twin lets gathered
# params feed invariant-typed scan carries and replicated out_specs. Same
# HLO all-gather. jax 0.9.0 does not export it under `jax.lax` yet — this is
# the ONE import of the private name; every user takes it from here.
from jax._src.lax.parallel import all_gather_invariant  # noqa: E402,F401


def donation_argnums(donate_state: bool = False,
                     donate_data: bool = False) -> Tuple[int, ...]:
    """The donate_argnums tuple of a round signature
    (gv, agg_state, x, y, counts, rng, ...): state rides argnums (0, 1),
    the cohort buffers (2, 3, 4). One definition so the tensor round, the
    GSPMD step round and any future assembler donate the same seats."""
    donate: Tuple[int, ...] = ()
    if donate_state:
        donate += (0, 1)
    if donate_data:
        donate += (2, 3, 4)
    return donate


def donating_jit(fn: Callable, donate_argnums: Tuple[int, ...],
                 **jit_kwargs) -> Callable:
    """jax.jit with donation plus the repo's donation idiom: backends that
    can't alias a donated input (CPU for some shapes/dtypes) warn per
    compile — the fallback is a plain copy, so the warning is noise for
    these opt-in paths. The suppressing wrapper exposes the raw jit as
    `.jitted` (graft-lint donation introspection). With an empty
    donate_argnums this is exactly jax.jit(fn, **jit_kwargs)."""
    if not donate_argnums:
        return jax.jit(fn, **jit_kwargs)
    jitted = jax.jit(fn, donate_argnums=donate_argnums, **jit_kwargs)

    def donating_fn(*args, **kwargs):
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*onat")
            return jitted(*args, **kwargs)

    donating_fn.jitted = jitted  # graft-lint donation introspection
    return donating_fn


def wrap_codec(aggregator, codec, slots: int):
    """The ONE CodecAggregator seam: wrap `aggregator` with the compressed
    update transport at `slots` residual rows — a no-op when the codec is
    None (codec-off rounds keep the exact legacy aggregator and state) or
    when the caller already wrapped (FedAvgAPI wraps before init_state and
    passes codec=None down, avoiding double wrapping)."""
    if codec is None:
        return aggregator
    from fedml_tpu.codecs.transport import CodecAggregator

    if isinstance(aggregator, CodecAggregator):
        return aggregator
    return CodecAggregator(codec, aggregator, slots=slots)


def build_round_core(batched_update, aggregator,
                     collect_stats: bool) -> Callable:
    """The ONE synchronous-round body, shared by every single-program round
    assembler: engine.build_round_fn_from_update (one round per dispatch),
    engine.build_superstep_fn_from_update (K rounds per dispatch, scanned)
    and parallel/tensor.py's GSPMD step round. All three trace exactly this
    function, so their bit-identity contracts hold by construction — there
    is no second round definition to drift.

    Returns core(gv, agg_state, x, y, counts, rng, participation) ->
    (new_gv, new_state, metrics, stats-or-None); `participation=None`
    traces the legacy unmasked program, an array arms the quarantine stage
    (see engine.build_round_fn_from_update's docstring for the contract).

    The server-side phases carry `jax.named_scope` names — `cohort_stats`,
    `quarantine` (each set where the phase is defined, so the mesh rounds
    inherit them), `aggregate` here and `codec` inside a wrapped aggregator:
    metadata of the compiled ops, the same program. The client update has
    none and is what lies outside them: a scope around it put 2-4 s on the
    trace of a ResNet-56 round (PERF.md section 6, PR 27).
    """
    # function-level import: aggregators.make_server_optimizer imports
    # engine.torch_adagrad, so the modules must not need each other at
    # import time
    from fedml_tpu.algorithms.aggregators import quarantine_stage
    from fedml_tpu.algorithms.engine import cohort_stats
    from fedml_tpu.models.lora import attach_lora_base, strip_lora_base

    def core(global_variables, agg_state, x, y, counts, rng, participation):
        crngs = jax.random.split(rng, x.shape[0])
        result = batched_update(global_variables, x, y, counts, crngs)
        # ledger stats come from the RAW results (pre-quarantine) so the
        # poisoned rows aggregation zeroes below stay visible per-client
        stats = cohort_stats(global_variables, result) if collect_stats \
            else None
        weights = counts.astype(jnp.float32)
        if participation is None:
            with jax.named_scope("aggregate"):
                new_global, new_state = aggregator(
                    global_variables, result, weights, rng, agg_state)
            # LoRA: aggregation ran adapters-only (results are stripped);
            # the server's frozen base re-attaches untouched (no-op when
            # the trainer isn't wrapped)
            new_global = attach_lora_base(new_global, global_variables)
            # per-client metric sums -> federation totals
            metrics = {k: v.sum(axis=0) for k, v in result.metrics.items()}
            return new_global, new_state, metrics, stats
        result, weights, alive, quarantined = quarantine_stage(
            result, weights, participation)
        with jax.named_scope("aggregate"):
            new_global, new_state = aggregator(
                global_variables, result, weights, rng, agg_state)
        any_alive = jnp.any(alive)
        # the all-dead fallback must match the aggregator output's
        # (adapters-only under LoRA) structure; base re-attaches after
        new_global = tree_where(any_alive, new_global,
                                strip_lora_base(global_variables))
        new_state = tree_where(any_alive, new_state, agg_state)
        new_global = attach_lora_base(new_global, global_variables)
        metrics = {k: v.sum(axis=0) for k, v in result.metrics.items()}
        metrics["participated_count"] = alive.sum().astype(jnp.float32)
        metrics["quarantined_count"] = quarantined.sum().astype(jnp.float32)
        return new_global, new_state, metrics, stats

    return core


def build_personal_round_core(batched_update, aggregator,
                              collect_stats: bool) -> Callable:
    """The personalized-round body (graft-pfl): `build_round_core`'s shape
    plus a trailing [C, ...]-stacked `personal` adapter tree in and the
    updated rows out — UNAGGREGATED. The personal rows never reach the
    aggregator or any collective (COMMS_BUDGET pins the personalized
    twin's collective bytes equal to the shared round's); they ride the
    outputs like ledger stats do and scatter back into the mmap bank on
    the host. `batched_update(gv, x, y, counts, crngs, personal) ->
    (LocalResult, new_personal)` — engine._vmapped_personal_update.

    Returns core(gv, agg_state, x, y, counts, rng, participation,
    personal) -> (new_gv, new_state, metrics, stats-or-None,
    new_personal). Under the chaos mask, a dropped or quarantined
    client's personal row passes through UNCHANGED — its bank row must
    not absorb a poisoned or never-run update."""
    from fedml_tpu.algorithms.aggregators import quarantine_stage
    from fedml_tpu.algorithms.engine import cohort_stats
    from fedml_tpu.models.lora import attach_lora_base, strip_lora_base

    def _keep_dead_rows(new_personal, personal, alive):
        return jax.tree.map(
            lambda n, o: jnp.where(
                alive.reshape(alive.shape + (1,) * (n.ndim - 1)), n, o),
            new_personal, personal)

    def core(global_variables, agg_state, x, y, counts, rng, participation,
             personal):
        crngs = jax.random.split(rng, x.shape[0])
        result, new_personal = batched_update(
            global_variables, x, y, counts, crngs, personal)
        stats = cohort_stats(global_variables, result) if collect_stats \
            else None
        weights = counts.astype(jnp.float32)
        if participation is None:
            with jax.named_scope("aggregate"):
                new_global, new_state = aggregator(
                    global_variables, result, weights, rng, agg_state)
            new_global = attach_lora_base(new_global, global_variables)
            metrics = {k: v.sum(axis=0) for k, v in result.metrics.items()}
            return new_global, new_state, metrics, stats, new_personal
        result, weights, alive, quarantined = quarantine_stage(
            result, weights, participation)
        with jax.named_scope("aggregate"):
            new_global, new_state = aggregator(
                global_variables, result, weights, rng, agg_state)
        any_alive = jnp.any(alive)
        new_global = tree_where(any_alive, new_global,
                                strip_lora_base(global_variables))
        new_state = tree_where(any_alive, new_state, agg_state)
        new_global = attach_lora_base(new_global, global_variables)
        metrics = {k: v.sum(axis=0) for k, v in result.metrics.items()}
        metrics["participated_count"] = alive.sum().astype(jnp.float32)
        metrics["quarantined_count"] = quarantined.sum().astype(jnp.float32)
        new_personal = _keep_dead_rows(new_personal, personal, alive)
        return new_global, new_state, metrics, stats, new_personal

    return core


def masked_psum_tail(new_global, new_state, metrics, alive, quarantined,
                     fallback_global, fallback_state, axis: str):
    """The masked round's shard-local no-op guard + fault metrics, shared
    by every shard_map round body (1-D sharded round, sharded buffer
    commit, tensor round, tensor codec round): psum the alive count over
    `axis`, revert BOTH the globals and the aggregator state to the
    fallbacks when the whole cohort is dead (the revert covers a codec
    residual carry too — a round that commits nothing must not mutate the
    error feedback), and append the participated/quarantined psum counts.
    psum outputs are invariant-typed, so the guard's select is invariant
    too and shard_map's check_vma accepts replicated out_specs unchanged.
    Returns (new_global, new_state, metrics)."""
    alive_total = jax.lax.psum(alive.sum(), axis)
    any_alive = alive_total > 0
    new_global = tree_where(any_alive, new_global, fallback_global)
    new_state = tree_where(any_alive, new_state, fallback_state)
    metrics["participated_count"] = alive_total.astype(jnp.float32)
    metrics["quarantined_count"] = jax.lax.psum(
        quarantined.sum(), axis).astype(jnp.float32)
    return new_global, new_state, metrics


def shard_key_slice(rng, n_total: int, index, n_local: int):
    """This shard's slice of the cohort rng-key table: split(rng, n_total)
    then rows [index*n_local, (index+1)*n_local) — the SAME key table as
    the single-chip vmap engine, so local training is bit-identical per
    client on every sharded geometry (1-D sharded round, hierarchical
    group/client levels, tensor round)."""
    all_keys = jax.random.split(rng, n_total)
    return jax.lax.dynamic_slice_in_dim(all_keys, index * n_local, n_local)


# ------------------------------------------------- the spec-point assembler


@dataclass(frozen=True)
class RoundProgram:
    """One traced round program a spec point builds: its budget-family
    name, the jitted callable, and abstract (ShapeDtypeStruct) args that
    trace it — `jax.eval_shape(fn, *args)` proves it builds,
    `jax.make_jaxpr(fn)(*args)` feeds the equivalence engine."""

    name: str
    fn: Callable
    args: Tuple[Any, ...]


def _trace_model(fam: str) -> Tuple[str, str, Dict[str, Any]]:
    """The representative model/dtype/extra a family traces on (lr/f32
    everywhere except the families whose builders demand otherwise)."""
    model, dtype, extra = "lr", "float32", {}
    if fam == "silo":
        model, dtype = "resnet20", "bfloat16"
    elif fam == "superstep":
        extra["client_num_per_round"] = 2
    return model, dtype, extra


def build_round_program(levels: Mapping[str, str],
                        **extra) -> Tuple[RoundProgram, ...]:
    """Compose the round program(s) of one matrix point from the spec
    alone. `levels` is an axis->level assignment (missing axes default);
    `extra` layers FedConfig overrides ON TOP of the levels' projections —
    the seam the EQUIV_PAIRS structurally-off contracts drive
    (`tensor_shards=1`, `rounds_per_dispatch=1`, `lora_rank=0`).

    The family is dispatched from the EFFECTIVE config: the levels project
    onto a FedConfig, extras apply, and the config projects BACK through
    `axis_levels` — so an extra that turns a feature structurally off
    (rounds_per_dispatch=1) routes to the same family the runtime's
    dispatch (algorithms/fedavg.py) would pick, never the scanned twin.

    Every feature axis is threaded exactly once:
      model      — `_tiny_trainer` on the family's representative
      adapter    — `maybe_wrap_lora` (identity at lora_rank<=0)
      aggregator — `make_aggregator` from the non-config axis level
      codec      — `wrap_codec` for the vmap/shard_map families, a builder
                   kwarg for the tensor round, the admit program's arg for
                   buffered admission (never the cohort step)
      mask       — the chaos level appends the participation arg
      stats      — collect_stats builder kwarg
      pipeline   — donate_data builder kwarg (cohort-buffer donation)
      sharding   — the family's mesh, derived from cfg.tensor_shards

    Returns the point's RoundProgram tuple (three programs for the
    buffered family, one otherwise). analysis/matrix_engine.trace_point
    eval_shapes them; analysis/equiv_engine proves them identical to the
    legacy hand assembly."""
    import numpy as np

    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.analysis.targets import (_abstract_round_args,
                                            _tiny_trainer)
    from fedml_tpu.codecs import make_codec
    from fedml_tpu.core.spec import (AXES, axis_levels, point_config,
                                     point_family, validate_config)
    from fedml_tpu.models.lora import maybe_wrap_lora

    # the requested family picks the representative model; the EFFECTIVE
    # family (extras applied, config projected back) picks the builder
    model, dtype, fam_extra = _trace_model(point_family(levels))
    fam_extra.update(extra)
    cfg = point_config(levels, model=model, dtype=dtype, **fam_extra)
    overlay = {name: levels[name] for name, axis in AXES.items()
               if axis.overrides is None and name in levels}
    eff = axis_levels(cfg)
    eff.update(overlay)
    fam = point_family(eff)
    # the legality round-trip: what the tables call legal must also pass
    # config-time validation with the non-config levels overlaid
    validate_config(cfg, axes=overlay)

    stats = eff.get("stats") == "on"
    donate = eff.get("pipeline") == "on"
    chaos = eff.get("chaos") == "on"

    trainer, shape, in_dtype = _tiny_trainer(model, dtype)
    trainer = maybe_wrap_lora(trainer, cfg)       # identity at lora_rank<=0
    agg = make_aggregator(eff.get("aggregator", "fedavg"), cfg)
    codec = (make_codec(cfg.update_codec, cfg)
             if cfg.update_codec != "none" else None)
    gv, x, y, counts, rng = _abstract_round_args(trainer, shape, in_dtype)
    cohort = x.shape[0]

    if fam == "engine":
        from fedml_tpu.algorithms.engine import build_round_fn

        rule = wrap_codec(agg, codec, slots=cohort)
        agg_state = jax.eval_shape(rule.init_state, gv)
        if eff.get("personalization") == "on":
            # the personalized twin: trailing [C, ...] personal rows in
            # and out of the SAME round shape (codec x personalization
            # is table-illegal)
            from fedml_tpu.algorithms.engine import build_personal_round_fn

            fn = build_personal_round_fn(trainer, cfg, rule,
                                         donate_data=donate,
                                         collect_stats=stats)
            personal = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((cohort,) + l.shape,
                                               l.dtype), gv["params"])
            args = (gv, agg_state, x, y, counts, rng, personal)
            if chaos:
                args = args + (jax.ShapeDtypeStruct((cohort,), jnp.bool_),)
            return (RoundProgram("engine.round", fn, args),)
        fn = build_round_fn(trainer, cfg, rule, donate_data=donate,
                            collect_stats=stats)
        args = (gv, agg_state, x, y, counts, rng)
        if chaos:
            args = args + (jax.ShapeDtypeStruct((cohort,), jnp.bool_),)
        return (RoundProgram("engine.round", fn, args),)

    if fam == "superstep":
        from fedml_tpu.algorithms.engine import build_superstep_fn

        rule = wrap_codec(agg, codec, slots=cohort)
        agg_state = jax.eval_shape(rule.init_state, gv)
        k = cfg.rounds_per_dispatch
        total = int(cfg.client_num_in_total)
        c = min(cfg.client_num_per_round, total, cohort)
        in_graph = bool(cfg.extra.get("in_graph_sampling", False))
        fn = build_superstep_fn(trainer, cfg, rule, k,
                                client_num_in_total=c,
                                collect_stats=stats, chaos_armed=chaos,
                                in_graph_sampling=in_graph)

        def i32(s=()):
            return jax.ShapeDtypeStruct(s, jnp.int32)

        per_round = {"round_idx": i32((k,)),
                     "nan": jax.ShapeDtypeStruct((k, c), jnp.bool_),
                     "corrupt": jax.ShapeDtypeStruct((k, c), jnp.bool_),
                     "participation": jax.ShapeDtypeStruct((k, c),
                                                           jnp.bool_)}
        if in_graph:
            per_round["keys"] = jax.ShapeDtypeStruct((k, 4, 2), jnp.uint32)
        else:
            per_round["idx"] = i32((k, c))
        return (RoundProgram(f"engine.superstep[k{k}]", fn,
                             (gv, agg_state, x, y, counts, rng,
                              per_round)),)

    if fam == "buffered":
        from fedml_tpu.algorithms.aggregators import (build_buffer_admit,
                                                      build_buffer_commit,
                                                      make_staleness_discount)
        from fedml_tpu.algorithms.buffered import build_client_step_fn
        from fedml_tpu.models.lora import strip_lora_base

        agg_state = jax.eval_shape(agg.init_state, gv)
        step = build_client_step_fn(trainer, cfg, donate_data=donate,
                                    collect_stats=stats)
        result = jax.eval_shape(step, gv, x, y, counts, rng)
        if stats:
            result = result[0]
        k = cfg.buffer_size

        def row(l):
            return jax.ShapeDtypeStruct((k,) + l.shape[1:], l.dtype)

        def i32(s=()):
            return jax.ShapeDtypeStruct(s, jnp.int32)

        buf = {"vars": jax.tree.map(row, result.variables),
               "steps": i32((k,)),
               "weights": jax.ShapeDtypeStruct((k,), jnp.float32),
               "metrics": {name: row(v)
                           for name, v in result.metrics.items()},
               "birth": i32((k,)), "fill": i32()}
        admit = build_buffer_admit(codec=codec)
        admit_args = (buf, result.variables, result.num_steps,
                      result.metrics, counts, i32(), i32())
        if codec is not None:
            # the codec delta base mirrors the WIRE tree — adapters-only
            # under LoRA, same strip the drive applies (buffered.py)
            admit_args = admit_args + (strip_lora_base(gv),)
        commit = build_buffer_commit(
            agg, make_staleness_discount(cfg.staleness_alpha))
        return (
            RoundProgram("buffered.client_step", step,
                         (gv, x, y, counts, rng)),
            RoundProgram("buffered.admit", admit, admit_args),
            RoundProgram("buffered.commit", commit,
                         (gv, agg_state, buf, i32(), rng)),
        )

    if fam == "sharded":
        from jax.sharding import Mesh

        from fedml_tpu.parallel.sharded import build_sharded_round_fn

        mesh = Mesh(np.array(jax.devices()[:8]), ("clients",))
        n_dev = mesh.shape["clients"]
        # codec residual slots pad the cohort to a mesh multiple, same as
        # the runtime wrap (algorithms/fedavg.py shard_map branch)
        rule = wrap_codec(agg, codec, slots=-(-cohort // n_dev) * n_dev)
        agg_state = jax.eval_shape(rule.init_state, gv)
        fn = build_sharded_round_fn(trainer, cfg, rule, mesh,
                                    collect_stats=stats)
        return (RoundProgram(
            "sharded.round", fn,
            (gv, agg_state,
             jax.ShapeDtypeStruct((n_dev, 4) + shape[1:], in_dtype),
             jax.ShapeDtypeStruct((n_dev, 4), jnp.int32),
             jax.ShapeDtypeStruct((n_dev,), jnp.int32), rng)),)

    if fam in ("tensor_round", "tensor_step"):
        from jax.sharding import Mesh

        from fedml_tpu.parallel.tensor import (TensorSharding,
                                               build_tensor_round_fn,
                                               build_tensor_step_round_fn,
                                               init_codec_agg_state)

        # the trace geometry keeps the abstract 2-client cohort on the
        # clients axis and cfg.tensor_shards on the tensor axis (the
        # runtime mesh, make_tensor_mesh, absorbs every device instead)
        ts = cfg.tensor_shards
        mesh = Mesh(np.array(jax.devices()[:cohort * ts]).reshape(
            cohort, ts), ("clients", "tensor"))
        sharding = TensorSharding.for_model(mesh, cfg.model)
        build = (build_tensor_step_round_fn if fam == "tensor_step"
                 else build_tensor_round_fn)
        fn = build(trainer, cfg, agg, sharding,
                   donate_state=bool(cfg.extra.get("donate_params", False)),
                   donate_data=donate, collect_stats=stats, codec=codec)
        if codec is not None:
            agg_state = jax.eval_shape(
                lambda g: init_codec_agg_state(sharding, g,
                                               agg.init_state(g)), gv)
        else:
            agg_state = jax.eval_shape(agg.init_state, gv)
        name = "tensor.step" if fam == "tensor_step" else "tensor.round"
        return (RoundProgram(name, fn, (gv, agg_state, x, y, counts, rng)),)

    if fam == "silo":
        from fedml_tpu.algorithms.silo_grouped import (build_silo_round_fn,
                                                       silo_trainer)

        agg_state = jax.eval_shape(agg.init_state, gv)
        st = silo_trainer(trainer, cfg.silo_threshold)
        fn = build_silo_round_fn(st, cfg, agg)
        return (RoundProgram("silo.round", fn,
                             (gv, agg_state, x, y, counts, rng)),)

    raise AssertionError(f"unknown family {fam!r}")  # pragma: no cover
