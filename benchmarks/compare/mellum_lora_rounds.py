"""The comparison of `mellum2_lora`: `lora_rounds` (with `pairs_gap`: every
token to its k experts in every layer), and two numbers that hold ONE
ATTENTION MODULE of the program against the reference array by array, for
what the adapters' norms cannot read (PERF.md section 2: a rotary fault once
read inside the sound range of the norm numbers, and a window or a rotary
setting that is wrong would hide the same way):

  window_attention_gap  the first window layer's attention module
  full_attention_gap    the first full layer's

each the program's `Attention` module (`fedml_tpu.models.mellum`, looked up
when called) under the model's configuration and dtype, on w0's kernels
merged with their adapters as the program merges them, against the
reference's (`make_attention`), on one input of a step's lanes x `seq_len`
x hidden: the band or the causal mask, the kind's rotary and its attention
factor, the grouped heads. |a - b| / |b| over the whole output (2-norms,
float32), both sides from the same seeded input (the run's seed), made
after the window, outside every timed span.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.compare import kimi_lora_rounds, lora_rounds

BASE = lora_rounds.BASE
KINDS = {"window_attention": "sliding_attention",
         "full_attention": "full_attention"}


def first_layer(config: dict, kind: str) -> str:
    """The name of the first layer of `kind` (an entry of `layer_types`)."""
    return f"layers_{config['layer_types'].index(kind)}"


def probe_input(config: dict, seed: int):
    """The attention modules' input, stored in the activations' dtype so
    that either side's cast of it is exact."""
    sizes, hyper = config["sizes"], config["hyper"]
    lanes = hyper["client_num_per_round"] * hyper["batch_size"]
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (lanes, sizes["seq_len"], config["hidden_size"]),
        jnp.float32).astype(jnp.dtype(sizes["base_dtype"]))


def program_layers(api, config: dict, w0) -> dict:
    from fedml_tpu.models import lora, mellum

    x, lm = probe_input(config, api.cfg.seed), api.trainer.module
    out = {}
    for name, kind in KINDS.items():
        layer = first_layer(config, kind)
        merged = lora.merge_lora_params(w0[BASE][layer]["attn"],
                                        w0["params"][layer]["attn"],
                                        api.trainer.scale)
        module = mellum.Attention(lm.cfg, kind, lm.dtype)
        # jitted here, not at import: a control changes what these trace
        out[name] = jax.jit(
            lambda p, x, module=module: module.apply({"params": p}, x))(
            merged, x)
    return out


def reference_layers(model, config: dict, w0, seed: int, compute: str) -> dict:
    x, attention = probe_input(config, seed), model.make_attention(
        config["sizes"])
    out = {}
    for name, kind in KINDS.items():
        layer = first_layer(config, kind)
        out[name] = jax.jit(
            lambda w, a, x, kind=kind: attention(w, a, x, kind, compute))(
            w0[BASE][layer], w0["params"][layer], x)
    return out


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    out = lora_rounds.numbers(prog, ref)
    for name in KINDS:
        out[name + "_gap"] = float(kimi_lora_rounds._gap(
            prog["layers"][name], ref["layers"][name]))
    return out


class Capture(lora_rounds.Capture):
    """`lora_rounds.Capture`; what it followed also holds the two attention
    modules' outputs, made when asked for (after the window)."""

    def __init__(self, api, config: dict):
        super().__init__(api, config)
        self.config = config

    def followed(self, w0) -> dict:
        out = super().followed(w0)
        out["layers"] = program_layers(self.api, self.config, w0)
        return out


def reference(model, config: dict, w0, data: dict, seed: int,
              compute: str = "f32") -> dict:
    out = lora_rounds.reference(model, config, w0, data, seed, compute)
    out["layers"] = reference_layers(model, config, w0, seed, compute)
    return out
