"""The federated round engine — local SGD + aggregation as one jitted function.

This replaces the reference's message-driven actor loop (SURVEY §3.1/§3.2):
where the reference runs one MPI process per worker and ships pickled
state_dicts, here a round is a pure function

    round_fn(global_variables, agg_state, x, y, counts, rng)
        -> (new_global, agg_state, train_metrics)

with clients vectorized by `vmap` (single chip) — and by `shard_map` over a
device mesh in fedml_tpu.parallel (aggregation then lowers to a weighted
`psum` over ICI).

Local-SGD parity notes (reference my_model_trainer_classification.py:17-53):
torch DataLoader(shuffle=True, drop_last=False) epoch semantics are reproduced
inside jit by sorting a uniform draw restricted to the valid prefix —
`argsort(where(valid, u, +inf))` yields a permutation of the real samples
followed by padding, so batches are full except the last, which is masked.
Steps on all-padding batches are no-ops via `tree_where` so Adam/momentum
state is not polluted (SURVEY §7 hard part (b)) — and the one-chip vmap
engine does not execute the steps past the cohort's last real batch at all:
its step loop's trip count is the traced `live_steps(counts)`, one program
for every cohort. Where the federation's clients differ in size, FedAvgAPI
packs the cohort onto fewer vmap lanes (`packed_lanes`, `_packed_update`):
a lane that has finished a client starts the next, so a small client no
longer sits through the largest one's steps. The mesh, chunked and
single-client callers keep the static `nb`-step scan (`live=None`).
"""

from __future__ import annotations

import math
import warnings
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from fedml_tpu.core.config import FedConfig
from fedml_tpu.utils.pytree import tree_where


class LocalResult(NamedTuple):
    variables: Any  # per-client trained variables (stacked under vmap)
    num_steps: jnp.ndarray  # actual optimizer steps taken (FedNova tau)
    metrics: dict  # summed train metrics of the final epoch


class _TorchAmsgradState(NamedTuple):
    count: jnp.ndarray
    mu: Any
    nu: Any
    nu_max: Any


def scale_by_torch_amsgrad(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
) -> "optax.GradientTransformation":
    """torch.optim.Adam(amsgrad=True) numerics, exactly.

    optax.amsgrad maxes over *bias-corrected* second moments
    (max_t v_t/(1-b2^t)); torch maxes the raw moment and applies the CURRENT
    step's correction after (max_t(v_t)/(1-b2^T)) — the trajectories diverge
    measurably (caught by tests/test_reference_parity.py, ~2e-2 after 10
    steps). Reference client path: my_model_trainer_classification.py:28-29.
    """

    def init_fn(params):
        z = jax.tree.map(jnp.zeros_like, params)
        return _TorchAmsgradState(jnp.zeros([], jnp.int32), z, z, z)

    def update_fn(updates, state, params=None):
        del params
        t = state.count + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, updates)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state.nu, updates)
        nu_max = jax.tree.map(jnp.maximum, state.nu_max, nu)
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        out = jax.tree.map(
            lambda m, v: (m / bc1) / (jnp.sqrt(v / bc2) + eps), mu, nu_max
        )
        return out, _TorchAmsgradState(t, mu, nu, nu_max)

    return optax.GradientTransformation(init_fn, update_fn)


def torch_amsgrad(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    return optax.chain(scale_by_torch_amsgrad(b1, b2, eps), optax.scale(-lr))


def torch_adagrad(lr: float, eps: float = 1e-10):
    """torch.optim.Adagrad numerics, exactly: accumulator starts at 0 and
    eps sits OUTSIDE the sqrt (p -= lr * g / (sqrt(sum) + eps)).

    optax.adagrad differs twice: initial_accumulator_value=0.1 and
    scale_by_rss's eps inside the rsqrt with a zero-sum guard — ~1e-1
    relative divergence on early steps (caught by
    test_reference_parity.py::test_fedopt_server_parity[adagrad])."""

    def init_fn(params):
        return jax.tree.map(jnp.zeros_like, params)

    def update_fn(updates, state, params=None):
        del params
        acc = jax.tree.map(lambda s, g: s + g * g, state, updates)
        out = jax.tree.map(lambda g, s: -lr * g / (jnp.sqrt(s) + eps),
                           updates, acc)
        return out, acc

    return optax.GradientTransformation(init_fn, update_fn)


def make_local_optimizer(cfg: FedConfig) -> optax.GradientTransformation:
    """Client optimizer matching reference trainer construction
    (my_model_trainer_classification.py:25-31: SGD(lr) or Adam(lr, wd,
    amsgrad=True)), with optional grad clipping (:46, clip at 1.0)."""
    chain = []
    if cfg.grad_clip is not None:
        chain.append(optax.clip_by_global_norm(cfg.grad_clip))
    if cfg.client_optimizer == "sgd":
        chain.append(optax.sgd(cfg.lr, momentum=cfg.momentum or None))
        if cfg.wd:
            chain.insert(-1, optax.add_decayed_weights(cfg.wd))
    elif cfg.client_optimizer == "adam":
        # torch Adam(weight_decay=wd, amsgrad=True): L2 added to the gradient
        # *before* adaptive scaling (not adamw-style decoupled decay)
        if cfg.wd:
            chain.append(optax.add_decayed_weights(cfg.wd))
        chain.append(torch_amsgrad(cfg.lr))
    else:
        raise ValueError(f"unknown client_optimizer {cfg.client_optimizer!r}")
    return optax.chain(*chain)


def _merge_variables(variables, new_params, new_state):
    out = dict(variables)
    out["params"] = new_params
    for k, v in new_state.items():
        out[k] = v
    return out


def epoch_batches(n_max: int, batch_size: int) -> tuple[int, int]:
    """(steps, batch) of one local epoch over a client padded to `n_max`
    rows: the ONE place that owns the `nb * b` arithmetic. `_build_epoch_fn`
    shapes its batches with it, `live_steps` bounds its step loop with it and
    `round_slots` counts with it, so a change to the padding changes the
    count too. `steps` is the most a client can need; how many of them a
    cohort executes is `live_steps`."""
    b = n_max if batch_size <= 0 else min(batch_size, n_max)
    return math.ceil(n_max / b), b


def live_steps(counts, n_max: int, batch_size: int):
    """Steps of one local epoch in which ANY client of the cohort has a real
    row: min(nb, ceil(max(counts) / b)). Real rows come first in every
    epoch's order, so each later step is padding for every client and the
    step loop stops there. A traced scalar: the loop's trip count
    (`round_slots` does the same arithmetic on the host's integers)."""
    nb, b = epoch_batches(n_max, batch_size)
    return jnp.minimum(nb, (jnp.max(counts).astype(jnp.int32) + b - 1) // b)


def client_steps(counts, n_max: int, batch_size: int):
    """Steps of one local epoch in which each client has a real row:
    ceil(count / b), elementwise. Host integers in (numpy), host integers
    out; traced in, traced out."""
    nb, b = epoch_batches(n_max, batch_size)
    return ((counts + (b - 1)) // b).clip(0, nb)


def lane_schedule(counts, n_max: int, batch_size: int, epochs: int,
                  lanes: int):
    """The packed round's schedule, traced: the cohort's C clients dealt to
    `lanes` queues longest first (LPT), each to the lane of least load, a
    client's load being the steps it trains, epochs x ceil(count / b).
    -> (queue [lanes, C] int32: a lane's clients in the order it runs them,
    C where the queue has ended; per_epoch [C] int32: a client's steps an
    epoch; trip: the most steps any lane runs, the loop's trip count). A
    client of no rows has no load and is in no queue. `packed_trip` is the
    same arithmetic on the host's integers."""
    c = counts.shape[0]
    per_epoch = client_steps(counts.astype(jnp.int32), n_max, batch_size)
    load = per_epoch * epochs

    def place(state, client):
        lane_load, queue, length = state
        lane = jnp.argmin(lane_load)
        real = load[client] > 0  # the sort puts the clients of no rows last
        queue = queue.at[lane, length[lane]].set(
            jnp.where(real, client, c).astype(jnp.int32))
        return (lane_load.at[lane].add(load[client]), queue,
                length.at[lane].add(real.astype(jnp.int32))), None

    order = jnp.argsort(-load, stable=True)
    (lane_load, queue, _), _ = jax.lax.scan(
        place, (jnp.zeros(lanes, jnp.int32),
                jnp.full((lanes, c), c, jnp.int32),
                jnp.zeros(lanes, jnp.int32)), order)
    return queue, per_epoch, jnp.max(lane_load)


def packed_trip(counts, n_max: int, batch_size: int, epochs: int,
                lanes: int) -> int:
    """`lane_schedule`'s trip count from the host's integers (the lanes'
    loads do not depend on how ties are broken)."""
    lane_load = [0] * lanes
    for steps in sorted(client_steps(np.asarray(counts), n_max,
                                     batch_size).tolist(), reverse=True):
        lane_load[lane_load.index(min(lane_load))] += steps * epochs
    return max(lane_load)


def packed_lanes(counts, clients: int, n_max: int, batch_size: int) -> int:
    """How many vmap lanes a cohort of `clients` drawn from a federation of
    these `counts` is packed onto: the fewest whose balanced depth does not
    exceed the steps the federation's largest client needs anyway,
    min(C, ceil(C x mean_k steps_k / max_k steps_k)) with steps_k =
    ceil(count_k / b). A federation of
    like-sized clients gets C, which is the unpacked program; so does one
    whose counts are not known (None)."""
    if counts is None or not np.any(counts):
        return clients
    steps = client_steps(np.asarray(counts), n_max, batch_size)
    return min(clients, math.ceil(clients * steps.mean() / steps.max()))


def round_work(cfg: FedConfig, clients: int, n_max: int, counts=None,
               lanes: int | None = None) -> dict:
    """What the round program executes for a staged cohort of `clients` x
    `n_max` rows, host integers: the vmap `lanes` it runs, the local steps
    each of them executes (`trip`, all epochs together) and the sample
    `slots` that makes, padding included: lanes x trip x batch. `counts`
    (the cohort's host counts) is given by callers whose program stops at
    the last real batch: after `live_steps(counts)` steps an epoch with a
    lane a client (the vmap engine) or, with `lanes` < clients, after the
    packed schedule's `packed_trip`. Without it, and under
    `assume_full_clients`, the program runs every one of the `nb` steps of
    every epoch."""
    nb, b = epoch_batches(n_max, cfg.batch_size)
    if counts is None or cfg.assume_full_clients:
        lanes, trip = clients, nb * cfg.epochs
    elif lanes is not None and lanes < clients:
        trip = packed_trip(counts, n_max, cfg.batch_size, cfg.epochs, lanes)
    else:
        lanes, trip = clients, cfg.epochs * int(client_steps(
            np.asarray(counts), n_max, cfg.batch_size).max())
    return {"lanes": lanes, "trip": trip, "slots": lanes * trip * b}


def round_slots(cfg: FedConfig, clients: int, n_max: int, counts=None,
                lanes: int | None = None) -> int:
    """`round_work`'s sample slots."""
    return round_work(cfg, clients, n_max, counts, lanes)["slots"]


def _build_epoch_fn(trainer, cfg: FedConfig, opt) -> Callable:
    """Shared one-local-epoch body: epoch_fn(global_params, carry, x, y,
    count, erng, live=None) -> (carry, auxs) with carry = (variables,
    opt_state, steps) and auxs the step metrics stacked on a leading axis,
    whose sum over that axis is the epoch's.

    `live` (a traced scalar, the same for every client under vmap) stops the
    step loop after the cohort's last real batch: a `while` over steps
    [0, live) in place of the static `nb`-step scan. The steps left out are
    all-padding for every client — zero masked loss, zero gradients,
    `has_data` false — so the carry is bitwise the scan's and the metrics
    are the same terms summed (tests/test_live_steps.py). `live=None` IS
    the static scan.

    Both the monolithic E-epoch scan (build_local_update) and the chunked
    donated-carry dispatch (build_chunked_round_runner) scan this same
    function, so the two execution shapes cannot drift apart numerically.
    """
    mu = cfg.fedprox_mu
    # Stateless-optimizer fast path: with plain SGD (no momentum/wd) a zero
    # gradient IS a no-op update — masked losses give exactly-zero grads on
    # all-padding batches (mask is a constant factor of the loss), so the
    # per-leaf tree_where select machinery is dead weight and is left out
    # (not measured on the chip since PR 1: the ledger shows the flagship
    # round device-bound, three convolutions holding 65 % of its busy time);
    # model state (e.g. BatchNorm running stats) is still masked because
    # padded samples DO pollute it.
    # FedProx disqualifies the fast path: the proximal term mu*(p - g) is
    # nonzero even when the data-loss gradient is masked to zero, so an
    # all-padding batch WOULD take a prox-only step toward the global params
    # (keep this criterion identical to algorithms/silo_grouped.py).
    stateless_opt = (cfg.client_optimizer == "sgd" and not cfg.momentum
                     and not cfg.wd and cfg.fedprox_mu == 0.0)
    full = cfg.assume_full_clients

    def epoch_order(count, erng, n_max):
        """(perm [nb * b], step_rng) of one client's local epoch: the rows
        in the order they are trained, real rows first and the tail padded
        with row 0, and the key whose `split(step_rng, nb)[s]` step s draws
        from. The one definition of the batch order and key derivation for
        the per-client scan below and the packed lanes (`_packed_update`)."""
        nb, b = epoch_batches(n_max, cfg.batch_size)
        n_pad = nb * b
        shuffle_rng, step_rng = jax.random.split(erng)
        if cfg.shuffle and full:
            # all rows valid: argsort(u) IS argsort(where(valid,u,inf))
            perm = jnp.argsort(jax.random.uniform(shuffle_rng, (n_max,)))
        elif cfg.shuffle:
            u = jax.random.uniform(shuffle_rng, (n_max,))
            valid = jnp.arange(n_max) < count
            perm = jnp.argsort(jnp.where(valid, u, jnp.inf))
        else:
            # fixed-order epochs: data is packed valid-prefix-first, so
            # identity order == torch DataLoader(shuffle=False)
            perm = jnp.arange(n_max)
        if n_pad > n_max:
            perm = jnp.concatenate([perm, jnp.zeros(n_pad - n_max, perm.dtype)])
        return perm, step_rng

    def step_for(global_params):
        """step_body(carry, (bx, by, bvalid, srng)) -> (carry, aux): one
        local SGD step of one client on one batch of `b` rows; FedProx pulls
        towards `global_params`."""

        def step_body(carry, scan_in):
            variables, opt_state, steps = carry
            bx, by, bvalid, srng = scan_in
            batch = {
                "x": bx,
                "y": by,
                "mask": bvalid.astype(jnp.float32),
            }

            def loss_wrap(params):
                vars_in = _merge_variables(variables, params, {})
                loss, (new_state, aux) = trainer.loss_fn(vars_in, batch, srng, True)
                if mu > 0.0:
                    # FedProx proximal term mu/2 * ||w - w_global||^2
                    # (reference fednova.py:124-126 applies it in-optimizer)
                    sq = sum(
                        jnp.sum(jnp.square(p - g))
                        for p, g in zip(jax.tree.leaves(params), jax.tree.leaves(global_params))
                    )
                    loss = loss + 0.5 * mu * sq
                return loss, (new_state, aux)

            grad_fn = jax.value_and_grad(loss_wrap, has_aux=True)
            (_, (new_state, aux)), grads = grad_fn(variables["params"])
            updates, new_opt_state = opt.update(grads, opt_state, variables["params"])
            new_params = optax.apply_updates(variables["params"], updates)
            if full:
                # every batch has data: the no-op-step machinery vanishes
                variables = _merge_variables(variables, new_params, new_state)
                opt_state = new_opt_state
                steps = steps + 1
                return (variables, opt_state, steps), aux
            has_data = jnp.any(bvalid)
            if stateless_opt:
                # zero grads already make the update a no-op; only guard
                # mutable model state (BN stats) against padded samples
                variables = _merge_variables(
                    variables, new_params,
                    tree_where(has_data, new_state,
                               {k: variables[k] for k in new_state}),
                )
                opt_state = new_opt_state
            else:
                new_vars = _merge_variables(variables, new_params, new_state)
                variables = tree_where(has_data, new_vars, variables)
                opt_state = tree_where(has_data, new_opt_state, opt_state)
            steps = steps + has_data.astype(jnp.int32)
            return (variables, opt_state, steps), aux

        return step_body

    def epoch_fn(global_params, carry, x, y, count, erng, live=None):
        n_max = x.shape[0]
        nb, b = epoch_batches(n_max, cfg.batch_size)
        n_pad = nb * b
        if full and n_pad != n_max:
            raise ValueError(
                f"assume_full_clients requires n_max ({n_max}) % batch_size "
                f"({b}) == 0 — padded batches would be trained unmasked")

        perm, step_rng = epoch_order(count, erng, n_max)
        # ONE epoch-level gather: the loop slices contiguous batches from
        # the pre-permuted copy (the packed lanes, whose clients change
        # inside the loop, gather a step's rows by index instead)
        xe = jnp.take(x, perm, axis=0).reshape((nb, b) + x.shape[1:])
        ye = jnp.take(y, perm, axis=0).reshape((nb, b) + y.shape[1:])
        if full:
            # literal ones: XLA folds the mask multiplies away and the
            # all-padding-batch selects below turn statically true
            batch_valid = jnp.ones((nb, b), bool)
        else:
            batch_valid = (jnp.arange(n_pad) < count).reshape(nb, b)
        step_body = step_for(global_params)
        srngs = jax.random.split(step_rng, nb)
        batches = (xe, ye, batch_valid, srngs)
        if live is None or full:
            # full clients: live == nb, keep the static trip count
            return jax.lax.scan(step_body, carry, batches)

        def batch_at(i):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                batches)

        # jitted so that the model is traced ONCE: the shapes of the step's
        # metrics below and the loop's body share the one cached trace (a
        # second trace of ResNet-56's step cost cross_silo 22 s of set-up)
        step = jax.jit(step_body)

        def live_body(i, state):
            carry, sums = state
            carry, aux = step(carry, batch_at(i))
            return carry, jax.tree.map(jnp.add, sums, aux)

        # the metrics are summed step by step in the loop's state, where the
        # scan stacks them for its caller to sum: one row, already the sum
        sums = jax.tree.map(
            jnp.zeros_like, jax.eval_shape(step, carry, batch_at(0))[1])
        carry, sums = jax.lax.fori_loop(0, live, live_body, (carry, sums))
        return carry, jax.tree.map(lambda a: a[None], sums)

    # the pieces the packed lanes compose into a loop of their own
    epoch_fn.order, epoch_fn.step_for = epoch_order, step_for
    return epoch_fn


def build_local_update(trainer, cfg: FedConfig, pvary_axes: tuple = ()) -> Callable:
    """Returns local_update(global_variables, x, y, count, rng, live=None)
    -> LocalResult.

    x: [n_max, ...], y: [n_max, ...], count: scalar int. Runs cfg.epochs of
    minibatch SGD (lax.scan over epochs and batches). `live` (see
    `_build_epoch_fn`; the vmap engine passes the cohort's `live_steps`
    unbatched) bounds each epoch's step loop; None keeps the static scan.

    ``pvary_axes``: mesh axis names to `jax.lax.pcast(..., to='varying')` the
    incoming global variables over — REQUIRED when this update runs inside
    `shard_map` with replication checking on. The scan carries start as the
    broadcast (invariant-typed) globals and become device-varying through the
    sharded data; without the explicit pcast, jax 0.9 silently MIScompiles
    the vmapped scan instead of raising the carry-typing error it raises for
    the unvmapped one (~2e-2 wrong after 12 LR steps — pinned by
    tests/test_parallel.py::test_scan_carry_pcast_jax_bug).
    """
    if cfg.epochs < 1:
        raise ValueError(f"cfg.epochs must be >= 1, got {cfg.epochs}")
    opt = make_local_optimizer(cfg)
    epoch_fn = _build_epoch_fn(trainer, cfg, opt)

    def local_update(global_variables, x, y, count, rng,
                     live=None) -> LocalResult:
        if pvary_axes:
            global_variables = jax.lax.pcast(
                global_variables, pvary_axes, to="varying")
        global_params = global_variables["params"]
        opt_state = opt.init(global_params)

        def epoch_body(carry, erng):
            return epoch_fn(global_params, carry, x, y, count, erng, live)

        erngs = jax.random.split(rng, cfg.epochs)
        # steps starts as count*0 rather than a literal 0 so that under
        # shard_map the carry is varying-over-the-clients-axis from the
        # start (it becomes varying through batch_valid inside the scan;
        # a non-varying init fails jax's check_vma carry typing)
        (variables, opt_state, steps), auxs = jax.lax.scan(
            epoch_body, (global_variables, opt_state,
                         (count * 0).astype(jnp.int32)), erngs
        )
        # summed train metrics from the final local epoch (shape [E, nb] -> last epoch)
        # (over the steps: a metric that is a vector, the tokens every expert
        # received, stays one)
        metrics = {k: v[-1].sum(axis=0) for k, v in auxs.items()}
        # federated LoRA (models/lora.py): the frozen base never trains, so
        # it leaves the client update HERE — inside the vmapped function —
        # and the cohort-stacked result tree never materializes C copies of
        # it. Aggregation, codecs, buffers and the wire all see
        # adapters-only trees; the round fn re-attaches the server's base.
        variables = {k: v for k, v in variables.items() if k != "lora_base"}
        return LocalResult(variables, steps, metrics)

    return local_update


def _vmapped_update(trainer, cfg: FedConfig) -> Callable:
    """batched_update(gv, x[C,...], y, counts, crngs) -> LocalResult — the
    standard client-axis execution: vmap over local_update. The cohort's
    live steps are computed OUTSIDE the vmap and passed unbatched, so the
    step loop's condition reads no per-client value (a batched condition
    would make vmap `select` the whole carry every step)."""
    local_update = build_local_update(trainer, cfg)

    def batched(global_variables, x, y, counts, crngs):
        live = live_steps(counts, x.shape[1], cfg.batch_size)
        return jax.vmap(local_update, in_axes=(None, 0, 0, 0, 0, None))(
            global_variables, x, y, counts, crngs, live)

    return batched


def _packed_update(trainer, cfg: FedConfig, lanes: int) -> Callable:
    """batched_update(gv, x[C,...], y, counts, crngs) -> LocalResult, the
    cohort's C clients trained on `lanes` < C vmap lanes: `lane_schedule`
    gives every lane a queue of clients, ONE step loop of `trip` steps runs
    `_build_epoch_fn`'s step under vmap over the lanes, and a lane whose
    client has trained its epochs x ceil(count / b) steps writes that
    client's row of the [C, ...] result and starts its next client from the
    global model with a fresh optimizer state. A client trains the batches
    `_vmapped_update` gives it, in that order and with those keys; what is
    left out is the all-padding steps a lane a client sits through while
    the cohort's largest finishes (tests/test_lane_packing.py). A cohort of
    `lanes` clients or fewer, and `assume_full_clients` (every client trains
    every step: nothing to pack), run `_vmapped_update` itself."""
    lane_a_client = _vmapped_update(trainer, cfg)  # checks cfg.epochs
    opt = make_local_optimizer(cfg)
    epoch_fn = _build_epoch_fn(trainer, cfg, opt)
    epochs = cfg.epochs

    def lane_where(pred, a, b):
        return jax.tree.map(
            lambda u, v: jnp.where(
                pred.reshape(pred.shape + (1,) * (v.ndim - 1)), u, v), a, b)

    def stacked(tree, n):
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l, (n,) + l.shape), tree)

    def trained(variables):
        # the frozen LoRA base leaves the result, as in `local_update`
        return {k: v for k, v in variables.items() if k != "lora_base"}

    def batched(global_variables, x, y, counts, crngs):
        c, n_max = x.shape[:2]
        if lanes >= c or cfg.assume_full_clients:
            return lane_a_client(global_variables, x, y, counts, crngs)
        nb, b = epoch_batches(n_max, cfg.batch_size)
        queue, per_epoch, trip = lane_schedule(
            counts, n_max, cfg.batch_size, epochs, lanes)

        # every client's batch order and step keys of every epoch, hoisted:
        # perms [C, E, nb * b] row numbers, keys [C, E, nb, ...]
        def client_orders(count, crng):
            perms, step_rngs = jax.vmap(
                lambda erng: epoch_fn.order(count, erng, n_max))(
                    jax.random.split(crng, epochs))
            return perms, jax.vmap(lambda k: jax.random.split(k, nb))(
                step_rngs)

        perms, keys = jax.vmap(client_orders)(counts, crngs)
        flat_x = x.reshape((c * n_max,) + x.shape[2:])
        flat_y = y.reshape((c * n_max,) + y.shape[2:])

        global_params = global_variables["params"]
        fresh = (global_variables, opt.init(global_params),
                 jnp.zeros((), jnp.int32))
        # jitted for ONE trace of the model, as in the per-client loop
        step = jax.jit(jax.vmap(epoch_fn.step_for(global_params)))
        lane_ids = jnp.arange(lanes)
        in_batch = jnp.arange(b)

        def batch_of(client, active, j):
            """The batch of a lane's client at its j-th step, and where that
            step lies in the client's epochs."""
            spe = per_epoch[client]
            epoch = j // jnp.maximum(spe, 1)
            s = j - epoch * spe
            rows = jax.vmap(
                lambda cl, e, s0: jax.lax.dynamic_slice_in_dim(
                    perms[cl, e], s0, b))(client, epoch, s * b)
            at = client[:, None] * n_max + rows
            bvalid = active[:, None] & (
                s[:, None] * b + in_batch < counts[client][:, None])
            return (jnp.take(flat_x, at, axis=0), jnp.take(flat_y, at, axis=0),
                    bvalid, keys[client, epoch, s]), s

        def body(_, state):
            carry, sums, pos, j, out = state
            client = queue[lane_ids, pos]
            active = client < c
            client = jnp.minimum(client, c - 1)
            batch, s = batch_of(client, active, j)
            # a lane that starts a client takes the global model, a fresh
            # optimizer state and no steps; the metrics are an epoch's
            carry = lane_where(active & (j == 0), fresh, carry)
            sums = jax.tree.map(
                lambda v: jnp.where(
                    (s == 0).reshape(s.shape + (1,) * (v.ndim - 1)), 0, v),
                sums)
            carry, aux = step(carry, batch)
            sums = jax.tree.map(jnp.add, sums, aux)
            j = j + active.astype(jnp.int32)
            done = active & (j == per_epoch[client] * epochs)
            # the client's row of the result; a lane that is not done
            # writes out of bounds, which is dropped
            row = jnp.where(done, client, c)
            variables, _, steps = carry
            out = jax.tree.map(
                lambda o, v: o.at[row].set(v, mode="drop"), out,
                LocalResult(trained(variables), steps, sums))
            return (carry, sums, pos + done.astype(jnp.int32),
                    jnp.where(done, 0, j), out)

        carry = stacked(fresh, lanes)
        zeros = jnp.zeros(lanes, jnp.int32)
        sums = jax.tree.map(jnp.zeros_like, jax.eval_shape(
            lambda: step(carry, batch_of(zeros, zeros < 0, zeros)[0])[1]))
        # a client that is never scheduled (no rows) keeps the global model,
        # no steps and zero metrics: what its lane returns unpacked
        out = LocalResult(
            stacked(trained(global_variables), c), jnp.zeros(c, jnp.int32),
            jax.tree.map(lambda l: jnp.zeros((c,) + l.shape[1:], l.dtype),
                         sums))
        *_, out = jax.lax.fori_loop(0, trip, body,
                                    (carry, sums, zeros, zeros, out))
        return out

    return batched


def build_personal_local_update(trainer, cfg: FedConfig) -> Callable:
    """personal_update(gv, x, y, count, rng, personal) ->
    (LocalResult, new_personal) — the graft-pfl client step.

    The client trains the EFFECTIVE adapters `gv["params"] + personal`
    (elementwise tree add; the zero row — an untouched bank client — is
    the identity, so that client's step is bit-identical to the shared
    round) through the exact same local_update body as the shared round.
    The trained effective adapters flow to the aggregator unchanged (the
    global adapter aggregates as today); the client's NEW personal row is
    the residual `trained - old_global` and returns out-of-band, never
    entering aggregation or the wire."""
    local_update = build_local_update(trainer, cfg)

    def personal_update(global_variables, x, y, count, rng, personal,
                        live=None):
        effective = dict(global_variables)
        effective["params"] = jax.tree.map(
            jnp.add, global_variables["params"], personal)
        result = local_update(effective, x, y, count, rng, live)
        new_personal = jax.tree.map(
            jnp.subtract, result.variables["params"],
            global_variables["params"])
        return result, new_personal

    return personal_update


def _vmapped_personal_update(trainer, cfg: FedConfig) -> Callable:
    """batched(gv, x[C,...], y, counts, crngs, personal[C,...]) ->
    (stacked LocalResult, stacked new_personal)."""
    personal_update = build_personal_local_update(trainer, cfg)

    def batched(global_variables, x, y, counts, crngs, personal):
        live = live_steps(counts, x.shape[1], cfg.batch_size)
        return jax.vmap(personal_update, in_axes=(None, 0, 0, 0, 0, 0, None))(
            global_variables, x, y, counts, crngs, personal, live)

    return batched


@jax.named_scope("cohort_stats")
def cohort_stats(global_variables, result: LocalResult) -> dict:
    """Static-shape per-cohort health stats for the client ledger.

    Four [C]-rows aligned with the cohort axis — per-client update L2-norm
    (over inexact param leaves), finiteness verdict, and the loss_sum/total
    pair the EMA-loss derives from. Everything is computed per client with
    NO cross-client reductions and NO new collectives, so sharded callers
    can return these rows under the plain clients-axis out-spec. Computed
    from the RAW client results (pre-quarantine) on purpose: a poisoned
    update must be visible in the ledger even though aggregation zeroes it.
    """
    from fedml_tpu.algorithms.aggregators import client_finite_mask

    total_sq = None
    for g, p in zip(jax.tree.leaves(global_variables["params"]),
                    jax.tree.leaves(result.variables["params"])):
        if not jnp.issubdtype(p.dtype, jnp.inexact):
            continue
        d = (p - g[None]).astype(jnp.float32)
        sq = jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim)))
        total_sq = sq if total_sq is None else total_sq + sq
    norm = (jnp.sqrt(total_sq) if total_sq is not None
            else jnp.zeros(result.num_steps.shape[0], jnp.float32))
    zeros = jnp.zeros_like(norm)
    return {
        "update_norm": norm,
        "finite": client_finite_mask(result.variables),
        "loss_sum": result.metrics.get("loss_sum", zeros).astype(jnp.float32),
        "total": result.metrics.get("total", zeros).astype(jnp.float32),
    }


# The ONE synchronous-round body moved to core/builder.py (ROADMAP item 5:
# every round assembler composes from the same fragments); the alias keeps
# this module's builders and docstrings reading naturally. Both round
# builders here — and parallel/tensor.py's GSPMD step round — trace exactly
# that function, so the superstep's bit-identity contract with the eager
# loop holds by construction: there is no second round definition to drift.
from fedml_tpu.core.builder import build_round_core as _round_core  # noqa: E402


def build_round_fn_from_update(batched_update, aggregator,
                               donate_data: bool = False,
                               collect_stats: bool = False,
                               base_outside: bool = False) -> Callable:
    """Jitted synchronous round over any batched client update (the vmap
    engine below, or the silo-grouped update in algorithms/silo_grouped.py —
    one definition of the rng stream and metrics contract for both).

    Mirrors the server loop at reference FedAvgServerManager.py:43-88
    (receive all -> aggregate -> broadcast) collapsed into one XLA program.

    The optional trailing `participation` ([C] bool/int, 1 = client reached
    the round) arms fault tolerance: dropped clients and clients whose
    trained variables contain NaN/Inf (quarantine — see
    aggregators.quarantine_stage) are zero-weight `where`-zeroed rows in the
    aggregation, bit-identical to aggregating the surviving cohort alone on
    the same rng table, and the metrics gain `participated_count` /
    `quarantined_count`. When every client is dropped or quarantined the
    round degrades to a no-op: global variables AND aggregator state pass
    through unchanged (no NaN escape). `participation=None` (the default)
    traces the exact legacy program — no masking ops, no extra metric keys,
    no retrace of existing callers; passing an array compiles one additional
    specialization.

    `donate_data=True` donates the (x, y, counts) cohort buffers into the
    round — the pipelined drive loop stages a FRESH device copy per round,
    so XLA may reuse that HBM in place. Donation is strictly opt-in: callers
    that feed the same buffers to more than one round would hit
    deleted-buffer errors. Donation never changes the traced program, only
    buffer aliasing, so donated and undonated rounds are bit-identical.

    `base_outside=True` (a LoRA-wrapped trainer, `build_round_fn`) keeps the
    frozen base out of the program's OUTPUTS: a jitted function that hands
    an input back returns a copy of it, which for a base of several GB is a
    second base on the device every round in flight. The program returns the
    adapters-only model and the wrapper re-attaches the caller's own base
    arrays, by reference. A trainer that is not wrapped gets the jitted
    function itself, as before.
    """
    from fedml_tpu.models.lora import attach_lora_base, strip_lora_base

    core = _round_core(batched_update, aggregator, collect_stats)

    def round_fn(global_variables, agg_state, x, y, counts, rng,
                 participation=None):
        new_global, new_state, metrics, stats = core(
            global_variables, agg_state, x, y, counts, rng, participation)
        if base_outside:
            new_global = strip_lora_base(new_global)
        if collect_stats:
            return new_global, new_state, metrics, stats
        return new_global, new_state, metrics

    from fedml_tpu.core.builder import donating_jit, donation_argnums
    jitted = donating_jit(round_fn, donation_argnums(donate_data=donate_data))
    if not base_outside:
        return jitted

    def with_base(global_variables, *args):
        new_global, *rest = jitted(global_variables, *args)
        return (attach_lora_base(new_global, global_variables), *rest)

    # what a caller may ask of the jitted round
    for attr in ("jitted", "lower", "_cache_size"):
        if hasattr(jitted, attr):
            setattr(with_base, attr, getattr(jitted, attr))
    return with_base


def build_round_fn(trainer, cfg: FedConfig, aggregator,
                   donate_data: bool = False,
                   param_sharding=None,
                   collect_stats: bool = False,
                   codec=None, lanes: int | None = None) -> Callable:
    """Jitted synchronous round: vmap(local_update) + aggregate.

    `lanes` (a static integer, `packed_lanes` of the federation; FedAvgAPI
    derives it) below the cohort's size packs the clients onto that many
    vmap lanes (`_packed_update`). None, or the cohort's size or more, IS
    the lane-a-client program: the same jaxpr. The mesh rounds take no
    lanes.

    `param_sharding` (a parallel.tensor.TensorSharding) switches the round
    onto the 2D ('clients', 'tensor') mesh: params and aggregator state live
    tensor-sharded between rounds, the client vmap step runs on gathered
    params, and aggregation psums move 1/tensor_shards of the bytes. The
    cohort axis and participation-mask semantics are unchanged.

    `collect_stats=True` makes the round return a fourth output — the
    per-cohort `cohort_stats` health rows for the client ledger — from the
    SAME traced program (extra outputs, not extra programs or sync points).
    The default traces the exact legacy 3-tuple program.

    `codec` (a fedml_tpu.codecs codec, or None) arms the compressed update
    transport. On the vmap path the aggregator is wrapped with the
    per-client encode/decode stage and the agg state extends to
    {"agg": inner, "codec": residual_rows} — callers that own agg_state
    init (FedAvgAPI) wrap the aggregator themselves BEFORE init_state and
    pass `codec=None` here to avoid double wrapping. On the tensor path
    the codec swaps the round's collectives for encoded payloads
    (quantized gather downlink, int8-psum / top-k-gather uplink) — the
    codec-on COMMS_BUDGET.json entries pin that program. `codec=None`
    (and an unwrapped aggregator) traces the exact legacy program —
    codec-off rounds stay bit-identical.
    """
    if param_sharding is not None:
        if getattr(cfg, "shard_step", False):
            # activation-sharded client step (GSPMD) — allclose contract,
            # per-device peak-bytes shrink; parallel/tensor.py docs
            from fedml_tpu.parallel.tensor import build_tensor_step_round_fn

            return build_tensor_step_round_fn(
                trainer, cfg, aggregator, param_sharding,
                donate_state=bool(cfg.extra.get("donate_params", False)),
                donate_data=donate_data, collect_stats=collect_stats,
                codec=codec)
        from fedml_tpu.parallel.tensor import build_tensor_round_fn

        return build_tensor_round_fn(
            trainer, cfg, aggregator, param_sharding,
            donate_state=bool(cfg.extra.get("donate_params", False)),
            donate_data=donate_data, collect_stats=collect_stats,
            codec=codec)
    from fedml_tpu.core.builder import wrap_codec
    from fedml_tpu.models.lora import LoRATrainer

    aggregator = wrap_codec(aggregator, codec, slots=cfg.client_num_per_round)
    return build_round_fn_from_update(
        _vmapped_update(trainer, cfg) if lanes is None
        else _packed_update(trainer, cfg, lanes),
        aggregator, donate_data=donate_data, collect_stats=collect_stats,
        base_outside=isinstance(trainer, LoRATrainer))


def build_personal_round_fn(trainer, cfg: FedConfig, aggregator,
                            donate_data: bool = False,
                            collect_stats: bool = False) -> Callable:
    """Jitted personalized round (graft-pfl): vmap(personal client step)
    + aggregate, returning the cohort's updated personal adapter rows as
    a trailing UNAGGREGATED output.

    Signature: ``round_fn(gv, agg_state, x, y, counts, rng, personal,
    participation=None)`` — the legacy round plus one stacked ``personal``
    cohort arg ([C, ...] adapter tree from models/adapter_bank.py's
    gather) and one stacked ``new_personal`` output (the drive loop
    scatters it back through the record log's one deferred device_get).
    The aggregation stage is the legacy one verbatim: it sees the TRAINED
    effective adapters, the personal rows never enter a psum or the wire
    (COMMS_BUDGET pins the personalized twin's collective bytes equal to
    the shared twin). There is no codec kwarg BY DESIGN — codec x
    personalization is table-illegal (core/spec.py): codecs compress the
    wire tree and personal rows never reach it.

    Requires a LoRA-wrapped trainer (lora_rank > 0, table-enforced): the
    personal row is a rank-r adapter tree mirroring gv["params"]. Dropped
    and quarantined clients keep their OLD rows bit-exactly (chaos x
    personalization is legal; see build_personal_round_core).
    """
    from fedml_tpu.core.builder import (build_personal_round_core,
                                        donating_jit, donation_argnums)

    core = build_personal_round_core(
        _vmapped_personal_update(trainer, cfg), aggregator, collect_stats)

    def round_fn(global_variables, agg_state, x, y, counts, rng, personal,
                 participation=None):
        new_global, new_state, metrics, stats, new_personal = core(
            global_variables, agg_state, x, y, counts, rng, participation,
            personal)
        if collect_stats:
            return new_global, new_state, metrics, stats, new_personal
        return new_global, new_state, metrics, new_personal


    # donation covers agg state (0-1) and cohort data (2-4) exactly as the
    # shared round: `personal` is NOT donated — the drive loop's staged row
    # buffer is also the scatter-back source on guard rejection
    return donating_jit(round_fn, donation_argnums(donate_data=donate_data))


def stage_to_device(x, y, counts, participation=None, sharding=None) -> tuple:
    """The stage_fn seam's device-commit step: one non-blocking
    `jax.device_put` per cohort leaf, shared by the eager and pipelined
    FedAvg staging paths (algorithms/fedavg.py `_stage_cohort`). Because
    every data source — in-RAM PackedClients, StreamingPackedClients,
    data.packed_store.MmapPackedStore — reaches the device through this
    one call, swapping the backing store can never change staged bytes,
    and the eager == pipelined bit-identity pin (tests/test_pipeline.py)
    holds for all of them. Returns (x, y, counts, participation-or-None)
    as committed device arrays.

    `sharding` (the mesh rounds' cohort sharding: rows split over the
    `clients` axis) sends each device its own rows straight from the host.
    Without it every leaf lands whole on the first device and the jitted
    mesh round has to reshard it before it can start."""
    dx, dy, dc = (jax.device_put(a, sharding) for a in (x, y, counts))
    dp = (jax.device_put(participation, sharding)
          if participation is not None else None)
    return dx, dy, dc, dp


def build_chunked_round_runner(trainer, cfg: FedConfig, aggregator,
                               epoch_chunk: int) -> Callable:
    """An E-epoch local round as ceil(E/epoch_chunk) host dispatches of
    epoch_chunk-epoch jitted programs, with the per-client
    (variables, opt_state, steps) carry DONATED between dispatches.

    Why: a fused E=20 scan is one long device program, minutes per dispatch
    (the reference cross-silo configs run E=20, benchmark/README.md:103-112,
    and the pre-PR-1 bench could only extrapolate it).
    Chunking keeps each dispatch short; `donate_argnums` makes XLA reuse the
    carry's HBM buffers in place, so the split costs zero device copies —
    only K-1 extra dispatch latencies (~100s of us against multi-second
    chunks).

    Numerics: identical trajectory to build_round_fn — same per-client rng
    stream (crngs = split(rng, C); erngs = split(crng, E), consumed
    chunk-by-chunk), same epoch body (_build_epoch_fn), same aggregation.
    Pinned by tests/test_chunked_dispatch.py::test_chunked_round_matches_monolithic.

    Compiles at most two chunk programs (full-size chunks plus one remainder
    when E % epoch_chunk != 0). Single-host execution shape (vmap over
    clients) — the shard_map path keeps the monolithic scan.
    """
    if epoch_chunk < 1:
        raise ValueError(f"epoch_chunk must be >= 1, got {epoch_chunk}")
    if cfg.epochs < 1:
        raise ValueError(f"cfg.epochs must be >= 1, got {cfg.epochs}")
    opt = make_local_optimizer(cfg)
    epoch_fn = _build_epoch_fn(trainer, cfg, opt)

    def _init(global_variables, counts, rng):
        c = counts.shape[0]
        crngs = jax.random.split(rng, c)
        erngs = jax.vmap(lambda r: jax.random.split(r, cfg.epochs))(crngs)
        stacked = jax.tree.map(
            lambda l: jnp.broadcast_to(l, (c,) + l.shape), global_variables)
        opt_state = jax.vmap(opt.init)(stacked["params"])
        return stacked, opt_state, (counts * 0).astype(jnp.int32), erngs

    def _chunk(stacked, opt_state, steps, global_params, x, y, counts,
               erngs_chunk):
        def one_client(variables, opt_st, st, xc, yc, count, erngs):
            def body(carry, erng):
                return epoch_fn(global_params, carry, xc, yc, count, erng)
            (variables, opt_st, st), auxs = jax.lax.scan(
                body, (variables, opt_st, st), erngs)
            # summed train metrics of this chunk's final epoch; the host
            # keeps only the final chunk's, i.e. the final local epoch's
            return variables, opt_st, st, {k: v[-1].sum()
                                           for k, v in auxs.items()}
        return jax.vmap(one_client)(stacked, opt_state, steps, x, y, counts,
                                    erngs_chunk)

    def _finish(global_variables, agg_state, stacked, steps, metrics,
                counts, rng):
        result = LocalResult(stacked, steps, metrics)
        new_global, agg_state = aggregator(
            global_variables, result, counts.astype(jnp.float32), rng,
            agg_state)
        return new_global, agg_state, {k: v.sum(axis=0) for k, v in metrics.items()}

    init_fn = jax.jit(_init)
    chunk_fn = jax.jit(_chunk, donate_argnums=(0, 1, 2))
    finish_fn = jax.jit(_finish)

    def round_runner(global_variables, agg_state, x, y, counts, rng):
        stacked, opt_state, steps, erngs = init_fn(global_variables, counts,
                                                   rng)
        metrics = None
        for k0 in range(0, cfg.epochs, epoch_chunk):
            stacked, opt_state, steps, metrics = chunk_fn(
                stacked, opt_state, steps, global_variables["params"],
                # graft-lint: disable=retrace-risk -- at most TWO chunk geometries by construction (full chunks + one remainder), both compiled on round one and cached for the drive
                x, y, counts, erngs[:, k0:k0 + epoch_chunk])
        # graft-lint: disable=rng-key-reuse -- mirrors the monolithic round bit-for-bit: clients consume split(rng) streams inside the chunks while the aggregator consumes the raw round key in _finish, exactly as build_round_fn_from_update does in-graph
        return finish_fn(global_variables, agg_state, stacked, steps,
                         metrics, counts, rng)

    # introspection surface for graft-lint's donation rule: the carry
    # donation (donate_argnums=(0, 1, 2)) is the whole point of chunking —
    # the analyzer verifies it still lowers as buffer aliases
    round_runner.init_fn = init_fn
    round_runner.chunk_fn = chunk_fn
    round_runner.chunk_donate_argnums = (0, 1, 2)
    round_runner.finish_fn = finish_fn

    return round_runner


def build_superstep_fn_from_update(batched_update, cfg: FedConfig,
                                   aggregator, num_rounds: int, *,
                                   client_num_in_total: int,
                                   collect_stats: bool = False,
                                   chaos_armed: bool = False,
                                   in_graph_sampling: bool = False) -> Callable:
    """K federated rounds as ONE jitted `lax.scan` over `_round_core` —
    BIT-identical to K eager `build_round_fn_from_update` rounds on the
    `rng = fold_in(base_rng, round_idx)` stream (tests/test_superstep.py).

    Per-round traced inputs arrive as a `per_round` dict of [K]-leading
    arrays (the scan's xs):

    - ``round_idx`` [K] int32 — folded into base_rng per round, the same
      stream the eager drive uses.
    - ``idx`` [K, C] int32 (default sampler, host-precomputed) or
      ``keys`` [K, 4, 2] uint32 (``in_graph_sampling=True``: the Feistel
      key schedule; indices are recomputed in-graph by
      algorithms/sampling.py, bitwise equal to the host sampler).
    - with ``chaos_armed``: ``nan`` / ``corrupt`` / ``participation``
      [K, C] bool masks from the seeded FaultPlan. NaN-fill and the
      x*1e3+7.0 corruption are applied in-graph post-gather, replaying
      chaos.apply_faults' float semantics op-for-op (the masks are
      disjoint by construction, so application order cannot matter);
      int-dtype corruption is data-dependent on the host and is NOT
      expressible here — the drive falls back to eager for it.

    The cohort is gathered from the device-resident whole store
    (data.packed_store.resident_train_arrays) inside the scan, so no host
    work happens between rounds; metrics (and `collect_stats` ledger rows)
    come back with a leading [K] axis, letting RoundRecordLog flush K
    rounds with one deferred device_get.

    Superstep(gv, agg_state, data_x, data_y, data_counts, base_rng,
    per_round) -> (gv, agg_state, metrics[, stats]). The codec residual
    (CodecAggregator state) and fedopt momenta ride the scan carry in
    agg_state; LoRA base re-attachment happens per round inside the core.
    """
    if num_rounds < 1:
        raise ValueError(f"num_rounds must be >= 1, got {num_rounds}")
    core = _round_core(batched_update, aggregator, collect_stats)
    cohort = min(cfg.client_num_per_round, int(client_num_in_total))
    if in_graph_sampling:
        from fedml_tpu.algorithms.sampling import feistel_cohort_in_graph

    def superstep(global_variables, agg_state, data_x, data_y, data_counts,
                  base_rng, per_round):
        def body(carry, pr):
            gv, st = carry
            rng = jax.random.fold_in(base_rng, pr["round_idx"])
            if in_graph_sampling:
                idx = feistel_cohort_in_graph(pr["keys"],
                                              int(client_num_in_total),
                                              cohort)
            else:
                idx = pr["idx"]
            xs = jnp.take(data_x, idx, axis=0)
            ys = jnp.take(data_y, idx, axis=0)
            cs = jnp.take(data_counts, idx, axis=0)
            participation = None
            if chaos_armed:
                mshape = (cohort,) + (1,) * (xs.ndim - 1)
                xs = jnp.where(pr["corrupt"].reshape(mshape),
                               xs * 1e3 + 7.0, xs)
                xs = jnp.where(pr["nan"].reshape(mshape), jnp.nan, xs)
                participation = pr["participation"]
            gv, st, metrics, stats = core(gv, st, xs, ys, cs, rng,
                                          participation)
            return (gv, st), (metrics, stats)

        (gv, st), (metrics, stats) = jax.lax.scan(
            body, (global_variables, agg_state), per_round)
        if collect_stats:
            return gv, st, metrics, stats
        return gv, st, metrics

    return jax.jit(superstep)


def build_superstep_fn(trainer, cfg: FedConfig, aggregator, num_rounds: int,
                       *, client_num_in_total: int,
                       collect_stats: bool = False,
                       chaos_armed: bool = False,
                       in_graph_sampling: bool = False) -> Callable:
    """K vmap-engine rounds as one jitted scan, bit-identical to the eager
    drive (see build_superstep_fn_from_update). The caller passes the SAME
    aggregator instance its eager round_fn closes over (codec-wrapped and
    all), so agg_state trees line up between the fused and eager paths."""
    return build_superstep_fn_from_update(
        _vmapped_update(trainer, cfg), cfg, aggregator, num_rounds,
        client_num_in_total=client_num_in_total, collect_stats=collect_stats,
        chaos_armed=chaos_armed, in_graph_sampling=in_graph_sampling)


def build_eval_fn(trainer) -> Callable:
    """Jitted eval over pre-packed [nb, b, ...] batches; returns metric sums."""

    def eval_fn(variables, bx, by, bmask):
        def body(_, batch):
            bx_i, by_i, bm_i = batch
            m = trainer.eval_fn(variables, {"x": bx_i, "y": by_i, "mask": bm_i})
            return None, m
        _, ms = jax.lax.scan(body, None, (bx, by, bmask))
        return {k: v.sum() for k, v in ms.items()}

    return jax.jit(eval_fn)


def _vmapped_client_eval(trainer) -> Callable:
    """(variables, x[C, n_max, ...], y, counts) -> per-client metric arrays;
    the shared core of both eval builders below (one mask/eval definition so
    the chunked and resident paths cannot drift apart)."""

    @jax.named_scope("client_eval")
    def one(variables, x, y, count):
        mask = (jnp.arange(x.shape[0]) < count).astype(jnp.float32)
        return trainer.eval_fn(variables, {"x": x, "y": y, "mask": mask})

    return jax.vmap(one, in_axes=(None, 0, 0, 0))


def build_client_eval_fn(trainer) -> Callable:
    """Per-client eval: vmap over packed client rows [C, n_max, ...]; returns
    per-client metric sums (reference _local_test_on_all_clients,
    fedavg_api.py:119-183)."""
    return jax.jit(_vmapped_client_eval(trainer))


def build_personal_client_eval_fn(trainer) -> Callable:
    """Per-client PERSONALIZED eval (graft-pfl lift probe): like
    build_client_eval_fn but each client row evaluates under its own
    effective adapters ``variables["params"] + personal[i]``. The drive
    loop runs this next to the global eval on a sampled probe cohort and
    logs the accuracy delta as Personalization/Lift (stored back into the
    bank's lift column). Same mask/eval body as _vmapped_client_eval so
    the two eval definitions cannot drift."""

    @jax.named_scope("client_eval")
    def one(variables, personal, x, y, count):
        effective = dict(variables)
        effective["params"] = jax.tree.map(
            jnp.add, variables["params"], personal)
        mask = (jnp.arange(x.shape[0]) < count).astype(jnp.float32)
        return trainer.eval_fn(effective, {"x": x, "y": y, "mask": mask})

    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0, 0)))


def build_federation_eval_fn(trainer) -> Callable:
    """Whole-federation eval as ONE jitted program scanning client chunks —
    the resident-eval path: with the packed split kept device-resident, a
    full 3400-client eval is a single dispatch that re-sends nothing,
    instead of one host->device transfer and dispatch per chunk.
    xs: [num_chunks, chunk, n_max, ...]; returns summed metric scalars."""
    chunk_fn = _vmapped_client_eval(trainer)

    def eval_fn(variables, xs, ys, counts):
        m = jax.lax.map(lambda inp: chunk_fn(variables, *inp), (xs, ys, counts))
        return jax.tree.map(lambda v: v.sum(), m)

    return jax.jit(eval_fn)
