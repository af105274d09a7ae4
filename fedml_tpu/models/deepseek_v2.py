"""DeepSeek-V2 decoder (arXiv:2405.04434; `modeling_deepseek.py` beside the
published `config.json`), training path: latent attention (MLA) with
decoupled rotary keys, a dense SwiGLU first layer, then routed experts with
shared experts, RMSNorm, an untied head.

Every size comes from a configuration of the published keys (`DeepseekV2Config
.from_dict`; `configs/deepseek_v2_lite.json` is DeepSeek-V2-Lite's as
published, with its `source_url`): nothing is a Python default.

    h   = x + MLA(RMSNorm(x));  out = h + FFN(RMSNorm(h))
    MLA: q = x W_q -> heads x (nope | rope)
         [c_kv | k_rope] = x W_kv_a;  [k_nope | v] = RMSNorm(c_kv) W_kv_b
         rotary (YaRN inverse frequencies) on q_rope and on the ONE k_rope
         all heads share;  softmax(q k^T * (nope + rope)^-0.5 * m^2) causal,
         m = 0.1 * mscale_all_dim * ln(factor) + 1;  o = (p v) W_o
    FFN: layers < first_k_dense_replace: W_down(silu(x W_gate) * x W_up)
         after them: s = softmax(float32(x) W_r); top-k by value, weights the
         k scores as they are (x routed_scaling_factor; renormalised only if
         norm_topk_prob);  y = sum_k s_k E_k(x) + S(x), dropless
         (`ops/moe.py`), S one SwiGLU of n_shared_experts x the expert width

In training keys and values are expanded for every head and no cache is
kept; the attention core is the Pallas flash kernel (`ops/attention.py`)
with q/k of nope + rope and v of its own width.

Departures from the source, each for a reason:
  - `seq_aux` (the load-balance loss) is left out: the router's base and the
    routed experts are frozen here and fine-tuning recipes switch it off.
  - `q_lora_rank` other than null, group-limited routing (`n_group` > 1) and
    a sigmoid scorer are not built: the Lite model uses none of them, and a
    configuration that asks for one is refused, not approximated.
  - weights are created in the compute dtype (`--dtype bfloat16` gives a
    bfloat16 base): a float32 copy of a 2.8 B-parameter base would not fit
    beside its activations. Norm scales follow.
  - the routed experts are differentiated with respect to activations only
    (`ops/moe.py`): the model trains as a frozen base under `--lora_rank`.
  - each layer is rematerialised in the backward pass (`nn.remat`).

`RMSNorm`, `SwiGLU`, `MLA`, `MoE` and `_Router` are shared with
`models/kimi_linear.py`. What differs between the two decoders they read as
VALUES of the configuration object, under these names: `rotary` (false: the
rope part of q and k is used as it comes, NoPE), `scoring_func` ("softmax" |
"sigmoid"), `selection_bias` (the k experts are the largest of score + a
stored bias, which selects and does not weigh), `norm_topk_prob`,
`routed_scaling_factor`, and `experts_held` (None: all; else (first, count) of
the router's `n_routed_experts` outputs: this chip's share of an
expert-parallel layer, the pairs on absent experts contribute nothing, and
`MoE` hands back which path the share's dispatch took beside the load).

The module has three entry points: `hidden` (tokens -> final-norm states and
the tokens every expert received), `head` (states -> float32 logits) and
`__call__` (both, the whole batch's logits). A trainer that finds `hidden`
and `head` computes its loss over blocks of tokens (`core/trainer.py`).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.ops import moe
from fedml_tpu.ops.attention import flash_attention

PUBLISHED = os.path.join(os.path.dirname(__file__), "configs",
                         "deepseek_v2_lite.json")

_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
         "num_hidden_layers", "num_attention_heads", "kv_lora_rank",
         "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
         "first_k_dense_replace", "moe_layer_freq", "norm_topk_prob",
         "routed_scaling_factor", "rms_norm_eps", "rope_theta", "vocab_size",
         "max_position_embeddings")


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    q_lora_rank: Any
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int
    moe_layer_freq: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    vocab_size: int
    max_position_embeddings: int
    # rope_scaling, flattened so that the dataclass hashes
    rope_factor: float
    rope_original: int
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float

    @classmethod
    def from_dict(cls, d: dict) -> "DeepseekV2Config":
        """From the published keys; every one has to be there."""
        missing = [k for k in _KEYS + ("rope_scaling",) if k not in d]
        if missing:
            raise KeyError(f"model configuration lacks {missing}")
        unbuilt = {
            "q_lora_rank": d["q_lora_rank"] is not None,
            "n_group": d.get("n_group", 1) != 1,
            "scoring_func": d.get("scoring_func", "softmax") != "softmax",
            "topk_method": d.get("topk_method", "greedy") != "greedy",
            "attention_bias": bool(d.get("attention_bias", False)),
            "tie_word_embeddings": bool(d.get("tie_word_embeddings", False)),
            "hidden_act": d.get("hidden_act", "silu") != "silu",
            "rope_scaling.type": d["rope_scaling"].get("type") != "yarn",
        }
        if any(unbuilt.values()):
            raise NotImplementedError(
                "this DeepSeek-V2 decoder does not build "
                f"{[k for k, v in unbuilt.items() if v]} as configured")
        rs = d["rope_scaling"]
        return cls(**{k: d[k] for k in _KEYS},
                   rope_factor=rs["factor"],
                   rope_original=rs["original_max_position_embeddings"],
                   rope_beta_fast=rs["beta_fast"],
                   rope_beta_slow=rs["beta_slow"], rope_mscale=rs["mscale"],
                   rope_mscale_all_dim=rs["mscale_all_dim"])

    @classmethod
    def from_file(cls, path: str | None) -> "DeepseekV2Config":
        with open(path or PUBLISHED) as f:
            return cls.from_dict(json.load(f))

    def is_moe_layer(self, i: int) -> bool:
        return (i >= self.first_k_dense_replace
                and i % self.moe_layer_freq == 0)

    # what `MLA` and `MoE` read besides the published keys (module docstring)
    rotary = True
    scoring_func = "softmax"
    selection_bias = False
    experts_held = None


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg) -> float:
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
         if cfg.rotary else 1.0)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def yarn_inv_freq(cfg) -> np.ndarray:
    """The rotary inverse frequencies [rope / 2]: extrapolated (as trained)
    above the `beta_fast` correction, interpolated by `factor` below the
    `beta_slow` one, a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** exponent
    inter = 1.0 / (cfg.rope_factor * base ** exponent)

    def correction_dim(rotations):
        return (dim * math.log(cfg.rope_original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rotary(x, cos, sin):
    """The source's `apply_rotary_pos_emb`: the pairs (x0, x1), (x2, x3), ..
    are first laid out as [x0, x2, .. | x1, x3, ..], then rotated by halves.
    x [B, T, H, rope]; cos, sin [T, rope]."""
    b, t, h, d = x.shape
    x = x.reshape(b, t, h, d // 2, 2).swapaxes(-1, -2).reshape(b, t, h, d)
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return (x.astype(jnp.float32) * c + rot.astype(jnp.float32) * s).astype(
        x.dtype)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.dtype)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps)).astype(self.dtype) * scale


def _dense(features: int, dtype, name: str | None = None):
    return nn.Dense(features, use_bias=False, dtype=dtype, param_dtype=dtype,
                    kernel_init=nn.initializers.variance_scaling(
                        1.0, "fan_in", "normal"), name=name)


class SwiGLU(nn.Module):
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.width, self.dtype, "gate_proj")(x)
        up = _dense(self.width, self.dtype, "up_proj")(x)
        return _dense(x.shape[-1], self.dtype, "down_proj")(nn.silu(gate) * up)


class MLA(nn.Module):
    cfg: Any
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, t, _ = x.shape
        h, nope, rope, vd = (c.num_attention_heads, c.qk_nope_head_dim,
                             c.qk_rope_head_dim, c.v_head_dim)
        q = _dense(h * (nope + rope), self.dtype, "q_proj")(x)
        q = q.reshape(b, t, h, nope + rope)
        kv_a = _dense(c.kv_lora_rank + rope, self.dtype, "kv_a_proj")(x)
        c_kv, k_rope = kv_a[..., :c.kv_lora_rank], kv_a[..., c.kv_lora_rank:]
        kv = _dense(h * (nope + vd), self.dtype, "kv_b_proj")(
            RMSNorm(c.rms_norm_eps, self.dtype, name="kv_norm")(c_kv))
        kv = kv.reshape(b, t, h, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        if c.rotary:
            freqs = np.outer(np.arange(t, dtype=np.float32), yarn_inv_freq(c))
            emb = np.concatenate([freqs, freqs], axis=-1)
            # the cos/sin factor mscale / mscale_all_dim of the source
            ratio = (_yarn_mscale(c.rope_factor, c.rope_mscale)
                     / _yarn_mscale(c.rope_factor, c.rope_mscale_all_dim))
            cos, sin = np.cos(emb) * ratio, np.sin(emb) * ratio
            q_rope = rotary(q[..., nope:], cos, sin)
            k_rope = rotary(k_rope[:, :, None, :], cos, sin)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        else:
            k_rope = k_rope[:, :, None, :]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, t, h, rope))], axis=-1)
        o = flash_attention(q, k, v, True, scale=softmax_scale(c))
        return _dense(c.hidden_size, self.dtype, "o_proj")(
            o.reshape(b, t, h * vd))


class MoE(nn.Module):
    cfg: Any
    dtype: Any

    @nn.compact
    def __call__(self, x):
        """-> (y, tokens each of the router's experts received
        [n_routed_experts] f32, path). `path` is None where every expert is
        held. A share (`cfg.experts_held`) hands `ops/moe.py` the router's
        width beside its first expert: the dispatch sizes its row buffers by
        the share and says whether this call fit them, which leaves here as
        [2] f32, (1, 0) for the bounded path and (0, 1) for the worst-case
        one (under the engine's `vmap` a lane's value is its joint call's)."""
        c = self.cfg
        b, t, d = x.shape
        e, f = c.n_routed_experts, c.moe_intermediate_size
        flat = x.reshape(b * t, d)
        # router logits in float32, as the source computes them
        logits = _Router(e, self.dtype, name="router")(flat)
        if c.scoring_func == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        if c.selection_bias:
            bias = self.param("selection_bias", nn.initializers.zeros, (e,),
                              self.dtype)
            gate, idx = biased_route(scores, bias.astype(jnp.float32),
                                     c.num_experts_per_tok)
        else:
            gate, idx = moe.top_k_route(scores, c.num_experts_per_tok)
        if c.norm_topk_prob:
            gate = gate / (gate.sum(axis=-1, keepdims=True) + 1e-20)
        gate = gate * c.routed_scaling_factor
        held = c.experts_held
        n_held = e if held is None else held[1]
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_gate = self.param("experts_gate", init, (n_held, d, f), self.dtype)
        w_up = self.param("experts_up", init, (n_held, d, f), self.dtype)
        w_down = self.param("experts_down", init, (n_held, f, d), self.dtype)
        path = None
        with jax.named_scope("experts"):
            if held is None:
                y = moe.routed_experts(flat, idx, gate, w_gate, w_up, w_down,
                                       moe.TILE)
            else:
                y, worst = moe.routed_experts_share(
                    flat, idx, gate, w_gate, w_up, w_down, held[0], e)
                path = jnp.stack([1.0 - worst[0], worst[0]])
        shared = SwiGLU(c.n_shared_experts * f, self.dtype, name="shared")(flat)
        return (y + shared).reshape(b, t, d), moe.expert_load(idx, e), path


def biased_route(scores, bias, k: int):
    """The k experts with the largest score + bias, weighed by their SCORES:
    the bias selects and does not weigh. -> (gate [.., k], idx [.., k])."""
    _, idx = moe.top_k_route(scores + bias, k)
    return jnp.take_along_axis(scores, idx, axis=-1), idx


def router_logits(x, kernel):
    """x W_r with float32 operands: `F.linear(x.float(), W.float())`."""
    return jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


class _Router(nn.Module):
    experts: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            (x.shape[-1], self.experts), self.dtype)
        return router_logits(x, kernel)


class Block(nn.Module):
    cfg: DeepseekV2Config
    is_moe: bool
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)  # noqa: E731
        h = x + MLA(c, self.dtype, name="attn")(norm("input_norm")(x))
        z = norm("post_norm")(h)
        if self.is_moe:
            y, load, _ = MoE(c, self.dtype, name="moe")(z)
        else:
            y = SwiGLU(c.intermediate_size, self.dtype, name="mlp")(z)
            load = jnp.zeros((c.n_routed_experts,), jnp.float32)
        return h + y, load


class DeepseekV2LM(nn.Module):
    cfg: DeepseekV2Config
    dtype: Any = jnp.float32
    #: `experiments/common.py::build_trainer` refuses full-parameter training
    #: of a module that says so: `ops/moe.py` gives the routed experts no
    #: weight gradient, and 28 bytes a trained parameter fit no chip
    frozen_base_only = True

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                              param_dtype=self.dtype)
        block = nn.remat(Block)
        self.layers = [block(c, c.is_moe_layer(i), self.dtype)
                       for i in range(c.num_hidden_layers)]
        self.final_norm = RMSNorm(c.rms_norm_eps, self.dtype)
        self.lm_head = _dense(c.vocab_size, self.dtype)

    def hidden(self, tokens, train: bool = False):
        """tokens [B, T] -> (final-norm states [B, T, hidden], {"moe_load":
        [expert layers, n_routed_experts] tokens each expert received})."""
        if tokens.shape[1] > self.cfg.max_position_embeddings:
            raise ValueError(f"sequence length {tokens.shape[1]} exceeds "
                             f"max_position_embeddings")
        x = self.embed(tokens)
        loads = []
        for i, layer in enumerate(self.layers):
            x, load = layer(x)
            if self.cfg.is_moe_layer(i):
                loads.append(load)
        aux = {"moe_load": jnp.stack(loads)} if loads else {}
        return self.final_norm(x), aux

    def head(self, h):
        """states [.., hidden] -> float32 logits [.., vocab]."""
        return self.lm_head(h).astype(jnp.float32)

    def describe(self) -> dict:
        """The `model_built` event's fields (`telemetry/tracer.py`)."""
        c = self.cfg
        return {"layers": c.num_hidden_layers,
                "mixers": {"mla": c.num_hidden_layers},
                "experts_held": c.n_routed_experts,
                "experts_routed": c.n_routed_experts}

    def __call__(self, tokens, train: bool = False):
        return self.head(self.hidden(tokens, train)[0])
