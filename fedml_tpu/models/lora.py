"""Federated LoRA (Hu et al. 2021): frozen base params + small trainable
low-rank adapters, so only adapters are federated.

`LoRATrainer` wraps any concrete ModelTrainer. Its variables pytree keeps
the wrapped model's params under a frozen ``"lora_base"`` collection and
puts ONLY the adapters under ``"params"``:

    {"params":    {<path>/kernel: {"lora_A": [d_in, r], "lora_B": [r, d_out]}},
     "lora_base": {<full inner params tree>},
     ...other collections (batch_stats, ...) unchanged}

At apply time the effective kernel is ``base + (A @ B) * (alpha / r)`` —
``B`` initializes to zeros, so the wrapped model starts bit-identical to
the unwrapped one.

Dtypes: the base is held in whatever dtype the wrapped module creates its
parameters in (float32 for most of the zoo; bfloat16 for a module that
creates its weights in its compute dtype, as `models/deepseek_v2.py` does
under ``--dtype bfloat16``, where a float32 base would not fit the chip).
The adapters are float32 WHATEVER the base is: a bfloat16 adapter cannot be
trained by SGD (lr x gradient falls under its rounding). Where the two
differ, the effective kernel is summed in float32 and rounded to the base's
dtype once; where they agree it is the sum above, the same program as
before. 3-D kernels (a routed-expert stack) get no adapter (``ndim == 2``),
so they are frozen outright. `init` runs under one `jax.jit`: the compiler
drops the forward pass a flax `init` makes, which for a base of several GB
is most of its time. The engine's grad core differentiates ``"params"`` only
(`jax.value_and_grad` over ``variables["params"]``), so the base is frozen
*by construction*: no optimizer state, no gradient, no update ever touches
it, and frozen-base bitwise invariance across rounds is a structural
property (tests/test_lora.py), not a masking trick.

Federation-facing consequences, threaded through the drive loops:

  - `engine.build_local_update` strips ``lora_base`` from every client's
    LocalResult, so the cohort-stacked update tree never materializes C
    copies of the base — the wire/aggregation tree is adapters-only (the
    ≥50x `tensor.round` param-byte shrink pinned in COMMS_BUDGET.json).
  - aggregation runs over the stripped tree; the server re-attaches its
    own base afterwards (engine round_fn, tensor shard bodies, buffered
    commit). Aggregators themselves never see the collection.
  - codecs compress adapter deltas only, so LoRA x topk wire bytes stack
    multiplicatively (strictly smaller than either alone).
  - checkpoints store adapters-only (`FedAvgAPI._ckpt_tree`); resume and
    guard rollback re-attach the deterministic base (pure function of
    cfg.seed) from the live API.

Under the 2D ('clients','tensor') mesh the *base* is tensor-sharded via
the existing rule tables (``kernel$``-style regexes match the
``lora_base/...`` paths) while the tiny adapters replicate
(``lora_[AB]$`` -> PS()); the activation-sharded client step then
fine-tunes a model whose full params never materialize on one device.
"""

from __future__ import annotations

import re
from collections.abc import Mapping
from typing import Any, Optional

import jax
import jax.numpy as jnp

# the frozen-base variable collection name; everything that special-cases
# LoRA across the repo keys off this string
LORA_COLLECTION = "lora_base"

# which params get adapters: 2D matmul kernels (Dense / LSTM gate kernels)
# EXCEPT the LM head. Embeddings and norm scales stay base-only per the
# original recipe, and the head is excluded like peft's "all-linear"
# convention excludes the output embedding: a [d_model, vocab] head
# adapter costs r*(d_model+vocab) params — at a realistic NWP vocab that
# single adapter would dwarf every block adapter combined and cap the
# adapter-only wire shrink far below the >=50x the COMMS budgets pin.
DEFAULT_TARGETS = r"(?<!lm_head/)kernel$"


def _as_dict(tree):
    """flax FrozenDict-tolerant shallow copy as a plain dict."""
    if hasattr(tree, "unfreeze"):
        tree = tree.unfreeze()
    return dict(tree)


def strip_lora_base(variables):
    """Drop the frozen-base collection (no-op when absent) — the federated
    view of a LoRA variables tree: what crosses the wire, what aggregators
    average, what checkpoints store."""
    return {k: v for k, v in variables.items() if k != LORA_COLLECTION}


def attach_lora_base(variables, source):
    """Re-attach `source`'s frozen base onto a stripped tree (no-op when
    `source` carries none)."""
    if LORA_COLLECTION not in source:
        return variables
    out = dict(variables)
    out[LORA_COLLECTION] = source[LORA_COLLECTION]
    return out


def _walk_paths(tree, prefix=""):
    """Yield ('a/b/c', leaf) over a nested-Mapping params tree."""
    if isinstance(tree, Mapping):
        for k in tree:
            yield from _walk_paths(tree[k], f"{prefix}{k}/")
    else:
        yield prefix[:-1], tree


def init_lora_adapters(base_params, rank: int, rng,
                       targets: str = DEFAULT_TARGETS):
    """Adapter tree mirroring `base_params`, keeping only matched 2D
    kernels: each becomes {"lora_A": [d_in, r] (scaled normal),
    "lora_B": [r, d_out] (zeros)} so A @ B == 0 at init."""

    def build(tree, key, prefix=""):
        if not isinstance(tree, Mapping):
            path = prefix[:-1]
            leaf = tree
            if (getattr(leaf, "ndim", 0) == 2
                    and jnp.issubdtype(leaf.dtype, jnp.inexact)
                    and re.search(targets, path)):
                # float32 whatever the base's dtype: SGD cannot move a
                # bfloat16 adapter (lr x gradient falls under its rounding)
                d_in, d_out = leaf.shape
                a = (jax.random.normal(key, (d_in, rank), jnp.float32)
                     / jnp.asarray(d_in, jnp.float32) ** 0.5)
                return {"lora_A": a,
                        "lora_B": jnp.zeros((rank, d_out), jnp.float32)}
            return None
        out = {}
        for k in tree:
            sub = build(tree[k], jax.random.fold_in(key, _path_salt(k)),
                        f"{prefix}{k}/")
            if sub is not None and sub != {}:
                out[k] = sub
        return out

    adapters = build(base_params, rng)
    if not adapters:
        raise ValueError(
            f"no base param matched LoRA targets {targets!r} — nothing to "
            f"fine-tune (adapters require >=1 2D kernel leaf)")
    return adapters


def _path_salt(key: str) -> int:
    # deterministic per-branch fold_in salt from the param name (crc32, not
    # hash(): str hashing is per-process randomized and would break
    # same-seed-same-init across processes)
    import zlib

    return zlib.crc32(key.encode()) & 0x7FFFFFFF


def merge_lora_params(base_params, adapters, scale: float):
    """Effective inner params: base + (A @ B) * scale on adapted leaves,
    base passthrough everywhere else. The matmul is rank-r — negligible
    next to the layer's own matmul — and runs inside the jitted step."""

    def walk(base, adapt):
        if not isinstance(base, Mapping):
            delta = adapt["lora_A"] @ adapt["lora_B"]
            if base.dtype == delta.dtype:
                return base + delta * jnp.asarray(scale, base.dtype)
            # a base narrower than its adapters: summed in the adapters'
            # float32 and rounded to the base's dtype once
            return (base.astype(delta.dtype)
                    + delta * jnp.asarray(scale, delta.dtype)).astype(base.dtype)
        out = {}
        for k in base:
            if isinstance(adapt, Mapping) and k in adapt:
                out[k] = walk(base[k], adapt[k])
            else:
                out[k] = base[k]
        return out

    return walk(base_params, adapters)


class LoRATrainer:
    """ModelTrainer adapter: same pure-function surface (init / loss_fn /
    eval_fn / apply), adapters under "params", frozen base under
    "lora_base". Wrap AFTER task-trainer construction:

        trainer = LoRATrainer(NWPTrainer(create_model(...)), rank=8)
    """

    def __init__(self, inner, rank: int, alpha: Optional[float] = None,
                 targets: str = DEFAULT_TARGETS):
        if rank <= 0:
            raise ValueError(f"LoRA rank must be positive, got {rank} "
                             f"(rank 0 means: don't wrap the trainer)")
        self.inner = inner
        self.module = inner.module
        self.rank = int(rank)
        self.scale = float(alpha if alpha is not None else rank) / float(rank)
        self.targets = targets
        self.id = getattr(inner, "id", 0)

    # --- parity shims (reference ModelTrainer surface) ---------------------
    def set_id(self, trainer_id: int):
        self.id = trainer_id
        self.inner.set_id(trainer_id)

    def get_model_params(self, variables):
        return variables

    def set_model_params(self, variables, new_params):
        return new_params

    # --- pure functional surface -------------------------------------------
    def init(self, rng, example_input):
        # jitted: the compiler drops the forward pass a flax `init` runs, and
        # the base comes out in the module's parameter dtype in one program
        base = _as_dict(jax.jit(self.inner.init)(rng, example_input))
        base_params = base.pop("params")
        adapters = init_lora_adapters(
            base_params, self.rank, jax.random.fold_in(rng, 0x10A),
            self.targets)
        out = dict(base)
        out["params"] = adapters
        out[LORA_COLLECTION] = base_params
        return out

    def merged_variables(self, variables):
        """The wrapped model's view: adapters folded into the base, the
        lora collections gone (the inner module must never see them —
        `_module_apply` would mark any non-"params" collection mutable)."""
        inner_vars = {k: v for k, v in variables.items()
                      if k not in ("params", LORA_COLLECTION)}
        inner_vars["params"] = merge_lora_params(
            variables[LORA_COLLECTION], variables["params"], self.scale)
        return inner_vars

    def apply(self, variables, x, rng=None, train: bool = False):
        return self.inner.apply(self.merged_variables(variables), x, rng,
                                train)

    def loss_fn(self, variables, batch, rng, train: bool = True):
        return self.inner.loss_fn(self.merged_variables(variables), batch,
                                  rng, train)

    def eval_fn(self, variables, batch):
        return self.inner.eval_fn(self.merged_variables(variables), batch)


def maybe_wrap_lora(trainer, cfg) -> Any:
    """The one seam every entry point shares: wrap when cfg.lora_rank > 0,
    structurally off otherwise (the returned trainer IS the input, so
    --lora_rank 0 traces the exact legacy programs)."""
    rank = int(getattr(cfg, "lora_rank", 0) or 0)
    if rank <= 0 or isinstance(trainer, LoRATrainer):
        return trainer
    alpha = cfg.extra.get("lora_alpha") if hasattr(cfg, "extra") else None
    return LoRATrainer(trainer, rank=rank, alpha=alpha)
