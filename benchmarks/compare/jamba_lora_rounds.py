"""The comparison of `jamba2_lora`: `lora_rounds`, and two numbers that hold
ONE PIECE of the program against the reference array by array, for what the
adapters' norms cannot read (PERF.md section 2: the reference with the
scan's state in bfloat16, and rotary applied in the attention layer, both
read inside the sound range of `grad_gap` and `change_gap`):

  ssm_core_gap  the scan alone, what the program's Mamba mixer calls for the
                recurrence (`fedml_tpu.models.jamba.selective_scan`, looked
                up when called) against the reference's token recurrence
                (`ssm_core`), on the same inputs at the cell's own shapes: a
                step's lanes x `seq_len` x D_in channels x N states, x in
                the activations' dtype, delta, A, B and C float32, delta and
                A made as the model makes them from the first Mamba layer's
                own `dt_bias` and `A_log`. Compared: y and, under one
                cotangent, the gradients of x, delta, B and C, each as the
                program hands it on (the reference's rounded to that dtype);
                the worst of the five. The configuration states float32 for
                the state: the kernel differs from the token recurrence by
                rounding alone, a state kept in bfloat16 or a carry dropped
                at a chunk's boundary by orders of magnitude more.
  attention_gap the attention layer's mixer alone: the program's `Attention`
                module under the model's configuration and dtype, on w0's
                kernels merged with their adapters as the program merges
                them, against the reference's (`make_attention`), on one input
                of a step's lanes x `seq_len` x hidden. A rotation of q and
                k moves no norm of a seeded layer; it moves its output.

Both are |a - b| / |b| over the whole array (2-norms, float32), both sides
from the same seeded inputs (`probe_inputs`: the run's seed, w0), made after
the window, outside every timed span.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.compare import kimi_lora_rounds, lora_rounds

BASE = lora_rounds.BASE


def probe_inputs(config: dict, w0, seed: int) -> dict:
    """What both sides take: the scan's x, delta, A, B, C and the cotangent
    of y; the attention layer's input."""
    sizes, hyper = config["sizes"], config["hyper"]
    lanes = hyper["client_num_per_round"] * hyper["batch_size"]
    t, dt = sizes["seq_len"], jnp.dtype(sizes["base_dtype"])
    mamba = w0[BASE][kimi_lora_rounds._first_layer(w0, "mamba")]["mamba"]
    inner, n = mamba["A_log"].shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    hidden = w0[BASE]["final_norm"]["scale"].shape[0]
    normal = lambda i, shape: jax.random.normal(  # noqa: E731
        keys[i], shape, jnp.float32)
    delta = jax.nn.softplus(normal(1, (lanes, t, inner))
                            + mamba["dt_bias"].astype(jnp.float32))
    return {
        "scan": (normal(0, (lanes, t, inner)).astype(dt), delta,
                 -jnp.exp(mamba["A_log"].astype(jnp.float32)),
                 normal(2, (lanes, t, n)), normal(3, (lanes, t, n))),
        "dy": normal(4, (lanes, t, inner)),
        # stored in the activations' dtype, so that either side's cast of
        # it is exact
        "x": normal(5, (lanes, t, hidden)).astype(dt)}


def _scan_and_gradients(scan, inputs: dict) -> list:
    """[y, dx, ddelta, dB, dC] of `scan` under the cotangent `dy`."""
    y, vjp = jax.vjp(scan, *inputs["scan"])
    dx, dd, _, db, dc = vjp(inputs["dy"].astype(y.dtype))
    return [y, dx, dd, db, dc]


def program_layers(api, config: dict, w0) -> dict:
    """The program's side: the scan its Mamba module calls, and its
    attention module as the model builds it."""
    from fedml_tpu.models import jamba, lora

    inputs = probe_inputs(config, w0, api.cfg.seed)
    lm, layer = api.trainer.module, kimi_lora_rounds._first_layer(w0, "attn")
    merged = lora.merge_lora_params(w0[BASE][layer]["attn"],
                                    w0["params"][layer]["attn"],
                                    api.trainer.scale)
    attention = jamba.Attention(lm.cfg, lm.dtype)
    # jitted here, not at import: a control changes what these trace
    return {
        "ssm_core": jax.jit(lambda i: _scan_and_gradients(
            lambda *a: jamba.selective_scan(*a), i))(inputs),
        "attention": jax.jit(lambda p, x: attention.apply({"params": p}, x))(
            merged, inputs["x"])}


def reference_layers(model, config: dict, w0, seed: int, compute: str) -> dict:
    inputs = probe_inputs(config, w0, seed)
    layer = kimi_lora_rounds._first_layer(w0, "attn")
    attention = model.make_attention(config["sizes"])
    return {
        "ssm_core": jax.jit(lambda i: _scan_and_gradients(
            lambda *a: model.ssm_core(*a, compute), i))(inputs),
        "attention": jax.jit(lambda w, a, x: attention(w, a, x, compute))(
            w0[BASE][layer], w0["params"][layer], inputs["x"])}


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    out = lora_rounds.numbers(prog, ref)
    p, r = prog["layers"], ref["layers"]
    out["ssm_core_gap"] = max(float(kimi_lora_rounds._gap(a, b))
                              for a, b in zip(p["ssm_core"], r["ssm_core"]))
    out["attention_gap"] = float(kimi_lora_rounds._gap(p["attention"],
                                                       r["attention"]))
    return out


class Capture(lora_rounds.Capture):
    """`lora_rounds.Capture`; what it followed also holds the two pieces'
    outputs, made when asked for (after the window)."""

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
