"""Plain FedAvg (McMahan et al. 2017) as the cells run it: what one round
computes from a global model, a cohort and a seed, client by client.

    round r:  cohort = sample(r);  key_r = fold_in(PRNGKey(seed), r)
              client i of C: key_i = split(key_r, C)[i]
                epoch e of E: key_e = split(key_i, E)[e]
                  shuffle_key, step_key = split(key_e)
                  order: the client's real rows first, by ascending
                         uniform(shuffle_key, n_max); padding rows after
                  batch j: rows order[j*b:(j+1)*b], key split(step_key, nb)[j]
                    g = grad of the model's mean loss over the batch's real
                        rows (`model.loss`; softmax cross-entropy over one
                        label a row where the model module has none)
                    g = g / max(1, |g|/clip);  w -= lr * (g + wd * w)
                    (a batch with no real row changes nothing)
              global = sum_i n_i * client_i / sum_i n_i, every variable
              loss_r = sum of the LAST epoch's losses / what the loss counts
                       (rows; non-pad tokens for a language model)

The key schedule and the batch layout are part of the semantics: they decide
which rows share a batch (and a BatchNorm statistic) and which units dropout
zeroes. A batch is what the source's DataLoader would hand over: its real
rows. The fixed shapes here fill a short batch up with other rows, which
have weight 0 in the loss and take no part in BatchNorm's batch statistics
(the model gets the batch's mask), so they change nothing. (The program
feeds its filler rows through BatchNorm; how far that moves it from this
reference is in PERF.md section 2.)

Nothing here imports the program. Clients run one after another through one
jitted function of one client, so the peak is one client's activations.
"""

from __future__ import annotations

import math
import jax
import jax.numpy as jnp
import numpy as np

from .common import softmax_xent


def sample_cohort(round_idx: int, n_total: int, n_round: int) -> np.ndarray:
    """FedML's sampling: every client when the cohort is the population, else
    RandomState(round).choice without replacement."""
    if n_total == n_round:
        return np.arange(n_total)
    return np.random.RandomState(round_idx).choice(
        n_total, min(n_round, n_total), replace=False)


def classifier_loss(logits, y, mask):
    """The loss of a model module that brings none: softmax cross-entropy
    over one label a row. -> (mean over the real rows, in the logits' type;
    sum of the per-row losses f32; rows counted f32). A module's own
    `loss(outputs, y, mask)` returns the same three, `total` being what the
    program's trainer counts (non-pad tokens for next-word prediction)."""
    per = softmax_xent(logits, y)
    m = mask.astype(per.dtype)
    loss = (per * m).sum() / jnp.maximum(m.sum(), 1.0)
    per32, m32 = per.astype(jnp.float32), mask.astype(jnp.float32)
    return loss, (per32 * m32).sum(), m32.sum()


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


def make_client_update(model, hp: dict, compute: str):
    """client_update(variables, x[n_max,...], y[n_max,...], count, key) ->
    (variables, loss_sum, total)."""
    b, epochs = hp["batch_size"], hp["epochs"]
    lr, wd, clip = hp["lr"], hp["wd"], hp["grad_clip"]
    model_loss = getattr(model, "loss", classifier_loss)

    def loss_fn(params, state, bx, by, mask, key):
        outputs, new_state = model.apply({"params": params, **state}, bx, True,
                                         key, compute, mask)
        loss, loss_sum, total = model_loss(outputs, by, mask)
        return loss, (new_state, loss_sum, total)

    def step(carry, batch):
        params, state = carry
        bx, by, mask, key = batch
        (_, (new_state, loss_sum, total)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state, bx, by, mask, key)
        if clip is not None:
            norm = _global_norm(g)
            g = jax.tree.map(
                lambda a: jnp.where(norm < clip, a, a / norm * clip), g)
        new_params = jax.tree.map(lambda w, a: w - lr * (a + wd * w),
                                  params, g)
        has_data = mask.any()
        keep = lambda new, old: jax.tree.map(  # noqa: E731
            lambda n, o: jnp.where(has_data, n, o), new, old)
        return ((keep(new_params, params), keep(new_state, state)),
                (loss_sum, total))

    def client_update(variables, x, y, count, key):
        n_max = x.shape[0]
        bs = min(b, n_max)
        nb = math.ceil(n_max / bs)
        params = variables["params"]
        state = {k: v for k, v in variables.items() if k != "params"}
        loss_sum = total = None
        for ekey in jax.random.split(key, epochs):
            shuffle_key, step_key = jax.random.split(ekey)
            u = jax.random.uniform(shuffle_key, (n_max,))
            order = jnp.argsort(jnp.where(jnp.arange(n_max) < count, u,
                                          jnp.inf))
            order = jnp.concatenate(
                [order, jnp.zeros(nb * bs - n_max, order.dtype)])
            xe = x[order].reshape((nb, bs) + x.shape[1:])
            ye = y[order].reshape((nb, bs) + y.shape[1:])
            valid = (jnp.arange(nb * bs) < count).reshape(nb, bs)
            (params, state), (loss_sum, total) = jax.lax.scan(
                step, (params, state),
                (xe, ye, valid, jax.random.split(step_key, nb)))
        return {"params": params, **state}, loss_sum.sum(), total.sum()

    return jax.jit(client_update)


@jax.jit
def _weighted_sum(acc, tree, w):
    return jax.tree.map(lambda a, t: a + w * t.astype(jnp.float32), acc, tree)


def run_rounds(model, hp: dict, variables, train_x, train_y, counts,
               seed: int, rounds: int, compute: str = "f32") -> list[dict]:
    """Follow `rounds` rounds from `variables` (round indices 0..rounds-1).
    train_x/train_y/counts are the benchmark's own host arrays
    [clients, n_max, ...]. Returns per round {"loss", "total", "variables"}
    (variables: the global model AFTER that round)."""
    client_update = make_client_update(model, hp, compute)
    n_total = len(counts)
    base = jax.random.PRNGKey(seed)
    out = []
    for r in range(rounds):
        cohort = sample_cohort(r, n_total, hp["client_num_per_round"])
        keys = jax.random.split(jax.random.fold_in(base, r), len(cohort))
        n = counts[cohort].astype(np.float64)
        acc = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                           variables)
        sums = []
        for i, cid in enumerate(cohort):
            new, ls, tot = client_update(
                variables, jnp.asarray(train_x[cid]), jnp.asarray(train_y[cid]),
                jnp.int32(counts[cid]), keys[i])
            acc = _weighted_sum(acc, new, jnp.float32(n[i] / n.sum()))
            sums.append((ls, tot))
        loss_sum, total = (float(sum(col)) for col in zip(*jax.device_get(sums)))
        variables = acc
        out.append({"loss": loss_sum / max(total, 1.0), "total": total,
                    "variables": variables})
    return out
