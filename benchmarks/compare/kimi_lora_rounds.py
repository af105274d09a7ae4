"""The comparison of `kimi_linear_lora`: `lora_rounds`, and two numbers that
hold ONE LAYER of the program against the reference array by array, for what
the adapters' norms cannot read (PERF.md section 2: the reference with KDA's
state in bfloat16, and rotary applied in MLA, both read inside the sound
range of `grad_gap`, `change_gap` and `loss_gap`):

  kda_core_gap  the KDA core alone, what the program's model calls for steps
                2 to 4 (`fedml_tpu.models.kimi_linear.kda`, looked up when
                called) against the reference's token recurrence
                (`kda_core`), on the same inputs at the cell's own shapes: a
                lane's batch x `seq_len` x heads x head width, q~, k~, v in
                the activations' dtype, g and beta float32, g made as the
                model makes it from the first KDA layer's own `A_log` and
                `dt_bias`. Compared: o and, under one cotangent, the
                gradients of all five, each as the program hands it on (the
                reference's rounded to that dtype); the worst of the six.
                The configuration states float32 for g, its sums, the solve
                and the state: a chunked float32 core differs from the token
                recurrence by the order of its sums, a state or a decay kept
                in bfloat16 by orders of magnitude more.
  mla_gap       the first MLA layer's mixer alone: the program's `MLA` module
                under the model's configuration and dtype, on w0's kernels
                merged with their adapters as the program merges them,
                against the reference's (`make_mixer`), on one input of a
                lane's batch x `seq_len` x hidden. A rotation of q and k
                moves no norm of a seeded layer; it moves its output.

Both are |a - b| / |b| over the whole array (2-norms, float32), both sides
from the same seeded inputs (`probe_inputs`: the run's seed, w0). They are
made after the window, outside every timed span.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.compare import lora_rounds

BASE = lora_rounds.BASE


def _first_layer(w0, mixer: str) -> str:
    """The name of the first layer whose base holds `mixer`."""
    i = 0
    while mixer not in w0[BASE][f"layers_{i}"]:
        i += 1
    return f"layers_{i}"


def probe_inputs(config: dict, w0, seed: int) -> dict:
    """What both sides take: the KDA core's q~, k~, v, g, beta and the
    cotangent of o; the MLA layer's input."""
    sizes, batch = config["sizes"], config["hyper"]["batch_size"]
    t, dt = sizes["seq_len"], jnp.dtype(sizes["base_dtype"])
    kda = w0[BASE][_first_layer(w0, "kda")]["kda"]
    h = kda["A_log"].shape[0]
    width = kda["dt_bias"].shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda i, shape: jax.random.normal(  # noqa: E731
        keys[i], shape, jnp.float32)
    heads = (batch, t, h, width // h)
    soft = jax.nn.softplus(normal(3, (batch, t, width))
                           + kda["dt_bias"].astype(jnp.float32))
    g = -jnp.exp(kda["A_log"].astype(jnp.float32))[:, None] * soft.reshape(heads)
    hidden = w0[BASE]["final_norm"]["scale"].shape[0]
    return {
        "kda": (normal(0, heads).astype(dt), normal(1, heads).astype(dt),
                normal(2, heads).astype(dt), g,
                jax.nn.sigmoid(normal(4, heads[:3]))),
        # both stored in the activations' dtype, so that either side's cast
        # of them is exact
        "do": normal(5, heads).astype(dt),
        "x": normal(6, (batch, t, hidden)).astype(dt)}


def _core_and_gradients(core, inputs: dict) -> list:
    """[o, dq, dk, dv, dg, dbeta] of `core` under the cotangent `do`."""
    o, vjp = jax.vjp(core, *inputs["kda"])
    return [o, *vjp(inputs["do"].astype(o.dtype))]


def program_layers(api, config: dict, w0) -> dict:
    """The program's side: the core its KDA module calls, and its MLA module
    as the model builds it."""
    from fedml_tpu.models import deepseek_v2, kimi_linear, lora

    inputs = probe_inputs(config, w0, api.cfg.seed)
    lm, layer = api.trainer.module, _first_layer(w0, "attn")
    merged = lora.merge_lora_params(w0[BASE][layer]["attn"],
                                    w0["params"][layer]["attn"],
                                    api.trainer.scale)
    mla = deepseek_v2.MLA(lm.cfg, lm.dtype)
    # jitted here, not at import: a control changes what these trace
    return {
        "kda_core": jax.jit(lambda i: _core_and_gradients(
            lambda *a: kimi_linear.kda(*a), i))(inputs),
        "mla": jax.jit(lambda p, x: mla.apply({"params": p}, x))(
            merged, inputs["x"])}


def reference_layers(model, config: dict, w0, seed: int, compute: str) -> dict:
    inputs = probe_inputs(config, w0, seed)
    layer = _first_layer(w0, "attn")
    mixer = model.make_mixer(config["sizes"])
    return {
        "kda_core": jax.jit(lambda i: _core_and_gradients(
            lambda *a: model.kda_core(*a, compute), i))(inputs),
        "mla": jax.jit(lambda w, a, x: mixer(w, a, x, compute))(
            w0[BASE][layer], w0["params"][layer], inputs["x"])}


@jax.jit
def _gap(a, b):
    """|a - b| / |b| of `b` as `a` is stored."""
    a32, b32 = a.astype(jnp.float32), b.astype(a.dtype).astype(jnp.float32)
    return jnp.linalg.norm((a32 - b32).ravel()) / jnp.linalg.norm(b32.ravel())


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    out = lora_rounds.numbers(prog, ref)
    p, r = prog["layers"], ref["layers"]
    out["kda_core_gap"] = max(float(_gap(a, b))
                              for a, b in zip(p["kda_core"], r["kda_core"]))
    out["mla_gap"] = float(_gap(p["mla"], r["mla"]))
    return out


class Capture(lora_rounds.Capture):
    """`lora_rounds.Capture`; what it followed also holds the two layers'
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
