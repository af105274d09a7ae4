"""ModelTrainer — the framework-agnostic trainer operator, TPU-native form.

Reference contract: fedml_core/trainer/model_trainer.py:4-38 — an ABC with
get/set params, train, test; "does not cache any states". Here the same idea
becomes a bundle of *pure functions* over a flax variables pytree, so the whole
federated round (local SGD included) can live inside one jit:

  - ``init(rng, example_input)``      -> variables pytree
  - ``loss_fn(variables, batch, rng, train)`` -> (loss, (new_model_state, aux))
  - ``eval_fn(variables, batch)``     -> dict of metric *sums* (mergeable)

A ``batch`` is a dict with keys ``x``, ``y`` and a float ``mask`` of per-sample
validity (padding support — SURVEY §7 hard part (a)).

Concrete trainers mirror the reference's three standalone trainers:
  ClassificationTrainer  <- my_model_trainer_classification.py:10-86
  NWPTrainer             <- my_model_trainer_nwp.py:10 (ignore_index=0)
  TagPredictionTrainer   <- my_model_trainer_tag_prediction.py (multi-label)
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import optax


def _module_apply(module, variables, x, rng, train: bool):
    """Apply a flax module, handling dropout rngs and mutable batch stats.

    All fedml_tpu zoo modules take ``train: bool`` as a keyword. Returns
    (output, new_model_state) where new_model_state holds updated non-param
    collections (e.g. BatchNorm running stats) or {} if none.
    """
    mutable = [k for k in variables if k != "params"] if train else []
    rngs = {"dropout": rng} if rng is not None else None
    if mutable:
        out, new_state = module.apply(
            variables, x, train=train, rngs=rngs, mutable=mutable
        )
        return out, dict(new_state)
    out = module.apply(variables, x, train=train, rngs=rngs)
    return out, {}


class ModelTrainer:
    """Base trainer: wraps a flax module + a task loss into pure functions."""

    def __init__(self, module, id: int = 0):
        self.module = module
        self.id = id

    # --- parity shims with reference ModelTrainer ---------------------------
    def set_id(self, trainer_id: int):
        self.id = trainer_id

    def get_model_params(self, variables):
        return variables

    def set_model_params(self, variables, new_params):
        return new_params

    # --- pure functional surface -------------------------------------------
    def init(self, rng, example_input):
        return self.module.init({"params": rng, "dropout": rng}, example_input, train=False)

    def apply(self, variables, x, rng=None, train: bool = False):
        return _module_apply(self.module, variables, x, rng, train)

    def loss_fn(self, variables, batch, rng, train: bool = True):
        raise NotImplementedError

    def eval_fn(self, variables, batch):
        raise NotImplementedError


class ClassificationTrainer(ModelTrainer):
    """Cross-entropy classification (reference my_model_trainer_classification.py).

    Loss is the masked mean of per-sample CE over the batch — identical to
    torch's ``CrossEntropyLoss()`` mean reduction on the valid samples.

    ``augment_fn(rng, x) -> x`` runs inside the jitted train step (the
    TPU-native home of the reference's torchvision train transforms —
    fedml_tpu.data.augment).
    """

    def __init__(self, module, id: int = 0, augment_fn=None):
        super().__init__(module, id)
        self.augment_fn = augment_fn

    def loss_fn(self, variables, batch, rng, train: bool = True):
        x = batch["x"]
        if train and self.augment_fn is not None and rng is not None:
            x = self.augment_fn(jax.random.fold_in(rng, 17), x)
        batch = dict(batch, x=x)
        logits, new_state = self.apply(variables, batch["x"], rng, train)
        per = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
        mask = batch["mask"].astype(per.dtype)
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (per * mask).sum() / denom
        # metric sums accumulate in f32 regardless of compute dtype — bf16
        # sums lose mantissa past a few hundred samples, and the bf16<->f32
        # hops surface as dead-cast chains in the round jaxpr (graft-lint)
        per32 = per.astype(jnp.float32)
        mask32 = batch["mask"].astype(jnp.float32)
        correct = ((jnp.argmax(logits, -1) == batch["y"]) * mask32).sum()
        aux = {"loss_sum": (per32 * mask32).sum(), "correct": correct,
               "total": mask32.sum()}
        return loss, (new_state, aux)

    def eval_fn(self, variables, batch):
        logits, _ = self.apply(variables, batch["x"], None, train=False)
        per = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
        # f32 sums whatever the compute dtype, as in loss_fn: a bf16 sum over
        # a 1000-sample test set moves in steps of 16 (Test/Loss read 2.384
        # then 2.448 on the chip, Test/Acc in steps of 0.008)
        per = per.astype(jnp.float32)
        mask = batch["mask"].astype(jnp.float32)
        correct = ((jnp.argmax(logits, -1) == batch["y"]) * mask).sum()
        return {
            "test_correct": correct,
            "test_loss": (per * mask).sum(),
            "test_total": mask.sum(),
        }


class NWPTrainer(ModelTrainer):
    """Next-word prediction with pad-id masking (reference
    my_model_trainer_nwp.py: CE with ignore_index=0, accuracy over non-pad).

    Batch ``y`` has shape [b, seq]; logits [b, seq, vocab]. Tokens equal to
    ``pad_id`` are ignored in both loss and accuracy, in addition to the
    per-sample padding mask.

    A module that brings ``hidden(tokens, train) -> (states, aux)`` and
    ``head(states) -> logits`` (models/deepseek_v2.py) owns how its loss is
    computed: the head and the cross-entropy run over blocks of
    ``loss_block`` tokens, each block rematerialised in the backward pass,
    so that no [tokens, vocab] float32 array of the whole batch exists (8,192
    tokens over a 102,400-word vocabulary are 3.4 GB); the same sums to
    float32 rounding. What the module's ``aux`` holds (the tokens every
    expert received) rides the step's metrics. Its eval runs ``eval_rows``
    sequences at a time with loss blocks of ``eval_block`` tokens (the
    engine vmaps an eval over up to 64 clients, and every lane holds a block
    of logits of its own). A module without the two (the LSTMs, the toy
    transformer) takes the whole batch's logits, as before."""

    #: tokens a block of the training loss; sequences an eval step; tokens a
    #: block of the eval loss (constants: one value each is in use)
    loss_block, eval_rows, eval_block = 1024, 1, 128

    def __init__(self, module, pad_id: int = 0, id: int = 0):
        super().__init__(module, id)
        self.pad_id = pad_id
        self.blockwise = hasattr(module, "hidden") and hasattr(module, "head")

    def _masked_ce(self, variables, batch, rng, train):
        if self.blockwise:
            return self._blockwise_ce(variables, batch, train)
        logits, new_state = self.apply(variables, batch["x"], rng, train)
        y = batch["y"]
        per = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        tok_mask = (y != self.pad_id).astype(per.dtype)
        samp_mask = batch["mask"].astype(per.dtype)
        mask = tok_mask * samp_mask[:, None]
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (per * mask).sum() / denom
        correct = ((jnp.argmax(logits, -1) == y) * mask).sum()
        return loss, new_state, {"loss_sum": (per * mask).sum(), "correct": correct, "total": mask.sum()}

    def _blockwise_ce(self, variables, batch, train, block=None):
        """`_masked_ce` of a module that owns its loss: float32 sums over
        blocks of `block` (default `loss_block`) tokens."""
        states, extra = self.module.apply(variables, batch["x"], train=train,
                                          method="hidden")
        y = batch["y"]
        mask = ((y != self.pad_id).astype(jnp.float32)
                * batch["mask"].astype(jnp.float32)[:, None])
        n = y.size
        block = min(block or self.loss_block, n)
        pad = -n % block
        flat = [jnp.pad(a.reshape((n,) + a.shape[2:]),
                        [(0, pad)] + [(0, 0)] * (a.ndim - 2))
                for a in (states, y, mask)]
        blocks = [a.reshape((-1, block) + a.shape[1:]) for a in flat]

        @jax.checkpoint
        def one(sums, inp):
            hb, yb, mb = inp
            logits = self.module.apply(variables, hb, method="head")
            per = optax.softmax_cross_entropy_with_integer_labels(logits, yb)
            hit = (jnp.argmax(logits, -1) == yb).astype(jnp.float32)
            return (sums[0] + (per.astype(jnp.float32) * mb).sum(),
                    sums[1] + (hit * mb).sum()), None

        zero = jnp.zeros((), jnp.float32)
        with jax.named_scope("lm_loss"):
            (loss_sum, correct), _ = jax.lax.scan(one, (zero, zero), blocks)
        total = mask.sum()
        aux = {"loss_sum": loss_sum, "correct": correct, "total": total}
        aux.update(extra)
        return loss_sum / jnp.maximum(total, 1.0), {}, aux

    def loss_fn(self, variables, batch, rng, train: bool = True):
        loss, new_state, aux = self._masked_ce(variables, batch, rng, train)
        return loss, (new_state, aux)

    def eval_fn(self, variables, batch):
        if self.blockwise:
            aux = self._eval_sums(variables, batch)
        else:
            _, _, aux = self._masked_ce(variables, batch, None, False)
        # reported-loss contract matches the reference trainer
        # (my_model_trainer_nwp.py:72-80): each batch contributes
        # meanCE-over-non-pad x batch_size, later divided by test_total
        # (non-pad tokens) — reproduced so Test/Loss numbers line up
        n_tok = jnp.maximum(aux["total"], 1.0)
        n_samples = batch["mask"].astype(jnp.float32).sum()
        return {
            "test_correct": aux["correct"],
            "test_loss": aux["loss_sum"] / n_tok * n_samples,
            "test_total": aux["total"],
        }

    def _eval_sums(self, variables, batch):
        """The batch's loss sums, `eval_rows` sequences at a time: an eval
        batch is as many sequences as the engine packs (64, or a client's
        whole split under a vmap over clients), and a forward pass over all
        of them at once is sized for images, not for 1,024-token rows."""
        rows = batch["y"].shape[0]
        step = min(self.eval_rows, rows)
        pad = -rows % step
        parts = {k: jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1)).reshape(
            (-1, step) + v.shape[1:]) for k, v in batch.items()}
        sums = jax.lax.map(
            lambda part: {k: v for k, v in self._blockwise_ce(
                variables, part, False, self.eval_block)[2].items()
                if k in ("loss_sum", "correct", "total")}, parts)
        return {k: v.sum() for k, v in sums.items()}


class TagPredictionTrainer(ModelTrainer):
    """Multi-label tag prediction (reference my_model_trainer_tag_prediction.py):
    BCE-with-logits loss; precision/recall sums at threshold 0.5."""

    def loss_fn(self, variables, batch, rng, train: bool = True):
        logits, new_state = self.apply(variables, batch["x"], rng, train)
        y = batch["y"].astype(logits.dtype)  # [b, num_tags] multi-hot
        per = optax.sigmoid_binary_cross_entropy(logits, y).mean(axis=-1)
        mask = batch["mask"].astype(per.dtype)
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (per * mask).sum() / denom
        aux = {"loss_sum": (per * mask).sum(), "total": mask.sum()}
        return loss, (new_state, aux)

    def eval_fn(self, variables, batch):
        """Reference metric contract (my_model_trainer_tag_prediction.py
        test():75-96): BCE summed over all labels (x batch_size, divided
        back out by the test_total aggregation), exact-match correct, and
        per-sample (macro) precision/recall sums with the 1e-13 guard."""
        logits, _ = self.apply(variables, batch["x"], None, train=False)
        y = batch["y"].astype(jnp.float32)
        probs = jax.nn.sigmoid(logits).astype(jnp.float32)
        predicted = (probs > 0.5).astype(jnp.float32)
        samp = batch["mask"].astype(jnp.float32)
        n_valid = samp.sum()
        # BCELoss(reduction="sum") over valid samples
        eps = 1e-7
        bce = -(y * jnp.log(jnp.maximum(probs, eps))
                + (1 - y) * jnp.log(jnp.maximum(1 - probs, eps)))
        loss_sum = (bce.sum(axis=-1) * samp).sum()
        exact = (jnp.abs(predicted - y).max(axis=-1) < 0.5).astype(jnp.float32)
        tp = ((y * predicted) > 0.1).astype(jnp.float32).sum(axis=-1)
        precision = tp / (predicted.sum(axis=-1) + 1e-13)
        recall = tp / (y.sum(axis=-1) + 1e-13)
        return {
            "test_correct": (exact * samp).sum(),
            "test_loss": loss_sum * n_valid,
            "test_precision": (precision * samp).sum(),
            "test_recall": (recall * samp).sum(),
            "test_total": n_valid,
        }
