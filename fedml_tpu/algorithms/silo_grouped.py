"""Silo-grouped federated rounds — grad-outside-vmap local SGD.

The standard engine (algorithms/engine.py) vmaps `local_update` (which
contains `jax.grad`) over the round's clients. For cross-silo CIFAR ResNets
that lowering leaves the MXU half-idle at 16-32 channel stages; the measured
fix (docs/cross_silo_ladder.json: 1.55x @16ch, 1.22x @32ch) is to merge the
silos' convs into one `feature_group_count=n_silos` conv — which the model
does via `ops.silo_conv.GroupableConv` when its batching rule fires under
`jax.vmap`.

`custom_vmap` composes as grad(vmap(f)) but not vmap(grad(f)), so this
module restructures the local update: ONE vmapped forward over the silo
axis computes per-silo losses, their SUM is differentiated once (silos
share no parameters, so d(sum)/d(w_s) == d(loss_s)/d(w_s) — per-silo
gradients are mathematically identical to the engine's), and the optimizer
is vmapped over the silo axis (exact per-silo semantics for any optax
chain, including per-silo clip_by_global_norm).

Per-silo RNG streams replicate `build_local_update` exactly (same
split/fold order), so trajectories match the vmap engine to numerical
tolerance — asserted by tests/test_silo_grouped.py. The returned
`LocalResult` has the engine's stacked-over-clients contract, so every
aggregator works unchanged.

Reference anchor: the cross-silo benchmark rows (reference
benchmark/README.md:103-112); the execution path itself has no reference
counterpart — it is TPU-first scheduling of the same math.

SCOPE — single chip only. The grouped lowering rides `GroupableConv`'s
custom batching rule, which fires under `jax.vmap`; inside `shard_map`
the client axis is a mesh axis, not a vmap axis, so the rule never fires
and there is nothing to group (each device already holds a single silo's
conv — exactly the "single silo (no vmap)" rung the r4 ladder measured
SLOWER than vmap-10, docs/cross_silo_ladder.json). The multi-chip path
(`parallel/sharded.py`) therefore composes `shard_map` with the standard
engine's `build_local_update` instead (silo x shard_map is an EXCLUSIONS
row of core/spec.py). The chunked donated-carry dispatch
(engine.build_chunked_round_runner) is likewise a vmap-engine execution
shape: it has no silo-grouped twin.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.algorithms.engine import (
    LocalResult,
    _merge_variables,
    build_round_fn_from_update,
    make_local_optimizer,
)
from fedml_tpu.core.config import FedConfig


def silo_trainer(trainer, threshold: int):
    """Shallow trainer copy whose module has the silo-grouped conv lowering
    enabled (ResNetCifar family only). Train with the builders below; keep
    the ORIGINAL trainer for eval paths (identical numerics, no custom
    batching rule in eval)."""
    import copy

    if not hasattr(trainer.module, "silo_threshold"):
        raise ValueError(
            f"silo_threshold is only supported for models with a "
            f"silo_threshold attr (ResNetCifar family), got "
            f"{type(trainer.module).__name__}")
    t = copy.copy(trainer)
    t.module = trainer.module.clone(silo_threshold=threshold)
    return t


def _silo_where(cond, new, old):
    """Per-silo select over stacked [S, ...] trees; cond is [S] bool."""
    return jax.tree.map(
        lambda n, o: jnp.where(cond.reshape((cond.shape[0],) + (1,) * (n.ndim - 1)), n, o),
        new, old)


def build_silo_local_update(trainer, cfg: FedConfig) -> Callable:
    """silo_update(global_variables, x, y, counts, crngs) -> LocalResult.

    x: [S, n_max, ...]; crngs: [S, 2] — one fold-in key per silo, the same
    keys engine.build_round_fn hands each vmapped client.
    """
    if cfg.epochs < 1:
        raise ValueError(f"cfg.epochs must be >= 1, got {cfg.epochs}")
    opt = make_local_optimizer(cfg)
    mu = cfg.fedprox_mu
    # same criterion as engine._build_epoch_fn: clip is stateless and maps
    # zero grads to zero, so sgd-without-momentum/wd keeps the no-op property.
    # FedProx disqualifies it — the prox term mu*(p - g) is nonzero on
    # all-padding batches (keep identical to the engine's)
    stateless_opt = (cfg.client_optimizer == "sgd" and not cfg.momentum
                     and not cfg.wd and cfg.fedprox_mu == 0.0)

    def silo_update(global_variables, x, y, counts, crngs) -> LocalResult:
        s, n_max = x.shape[0], x.shape[1]
        b = n_max if cfg.batch_size <= 0 else min(cfg.batch_size, n_max)
        nb = math.ceil(n_max / b)
        n_pad = nb * b
        full = cfg.assume_full_clients
        if full and n_pad != n_max:
            raise ValueError(
                f"assume_full_clients requires n_max ({n_max}) % batch_size "
                f"({b}) == 0 — padded batches would be trained unmasked")

        global_params = global_variables["params"]
        stacked = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (s,) + l.shape), global_variables)
        opt_state = jax.vmap(opt.init)(stacked["params"])

        def mk_epoch_rngs(erng, count):
            # identical stream to engine.local_update's epoch_body
            shuffle_rng, step_rng = jax.random.split(erng)
            if cfg.shuffle and full:
                perm = jnp.argsort(jax.random.uniform(shuffle_rng, (n_max,)))
            elif cfg.shuffle:
                u = jax.random.uniform(shuffle_rng, (n_max,))
                valid = jnp.arange(n_max) < count
                perm = jnp.argsort(jnp.where(valid, u, jnp.inf))
            else:
                perm = jnp.arange(n_max)
            if n_pad > n_max:
                perm = jnp.concatenate([perm, jnp.zeros(n_pad - n_max, perm.dtype)])
            return perm, jax.random.split(step_rng, nb)

        def epoch_body(carry, erngs_e):
            variables, opt_state, steps = carry
            perms, srngs = jax.vmap(mk_epoch_rngs)(erngs_e, counts)  # [S,n_pad],[S,nb,2]
            xe = jax.vmap(lambda xs, p: jnp.take(xs, p, axis=0))(x, perms)
            ye = jax.vmap(lambda ys, p: jnp.take(ys, p, axis=0))(y, perms)
            # [S, nb, b, ...] -> scan-major [nb, S, b, ...]
            xe = jnp.moveaxis(xe.reshape((s, nb, b) + x.shape[2:]), 1, 0)
            ye = jnp.moveaxis(ye.reshape((s, nb, b) + y.shape[2:]), 1, 0)
            if full:
                batch_valid = jnp.ones((nb, s, b), bool)
            else:
                batch_valid = jnp.moveaxis(
                    (jnp.arange(n_pad)[None, :] < counts[:, None]).reshape(s, nb, b), 1, 0)
            srngs = jnp.moveaxis(srngs, 1, 0)  # [nb, S, 2]

            def step_body(carry, scan_in):
                variables, opt_state, steps = carry
                bx, by, bvalid, srng = scan_in  # [S, b, ...] each

                def loss_sum(params):
                    vars_in = _merge_variables(variables, params, {})

                    def one(v, bx_i, by_i, bm_i, r):
                        batch = {"x": bx_i, "y": by_i, "mask": bm_i}
                        return trainer.loss_fn(v, batch, r, True)

                    losses, (new_state, aux) = jax.vmap(one)(
                        vars_in, bx, by, bvalid.astype(jnp.float32), srng)
                    loss = losses.sum()  # silos are parameter-disjoint
                    if mu > 0.0:
                        sq = sum(
                            jnp.sum(jnp.square(p - g[None]))
                            for p, g in zip(jax.tree.leaves(params),
                                            jax.tree.leaves(global_params)))
                        loss = loss + 0.5 * mu * sq
                    return loss, (new_state, aux)

                grads, (new_state, aux) = jax.grad(loss_sum, has_aux=True)(
                    variables["params"])
                updates, new_opt_state = jax.vmap(opt.update)(
                    grads, opt_state, variables["params"])
                new_params = optax.apply_updates(variables["params"], updates)
                if full:
                    variables = _merge_variables(variables, new_params, new_state)
                    opt_state = new_opt_state
                    steps = steps + 1
                    return (variables, opt_state, steps), aux
                has_data = jnp.any(bvalid, axis=1)  # [S]
                if stateless_opt:
                    # masked loss -> exactly-zero grads for all-padding silos;
                    # only mutable model state (BN stats) needs the select
                    variables = _merge_variables(
                        variables, new_params,
                        _silo_where(has_data, new_state,
                                    {k: variables[k] for k in new_state}))
                    opt_state = new_opt_state
                else:
                    new_vars = _merge_variables(variables, new_params, new_state)
                    variables = _silo_where(has_data, new_vars, variables)
                    opt_state = _silo_where(has_data, new_opt_state, opt_state)
                steps = steps + has_data.astype(jnp.int32)
                return (variables, opt_state, steps), aux

            (variables, opt_state, steps), auxs = jax.lax.scan(
                step_body, (variables, opt_state, steps),
                (xe, ye, batch_valid, srngs))
            return (variables, opt_state, steps), auxs

        erngs = jax.vmap(lambda r: jax.random.split(r, cfg.epochs))(crngs)  # [S,E,2]
        erngs = jnp.moveaxis(erngs, 1, 0)  # [E, S, 2]
        (variables, opt_state, steps), auxs = jax.lax.scan(
            epoch_body, (stacked, opt_state, (counts * 0).astype(jnp.int32)), erngs)
        # final-epoch per-silo metric sums: auxs leaves are [E, nb, S]
        metrics = {k: v[-1].sum(axis=0) for k, v in auxs.items()}
        return LocalResult(variables, steps, metrics)

    return silo_update


def build_silo_round_fn(trainer, cfg: FedConfig, aggregator) -> Callable:
    """Jitted synchronous round on the silo-grouped path — the drop-in
    counterpart of engine.build_round_fn (shared round scaffold, so the rng
    stream and metrics contract cannot drift)."""
    return build_round_fn_from_update(
        build_silo_local_update(trainer, cfg), aggregator)
