"""Jamba decoder (arXiv:2403.19887; the `jamba` model type of the published
`config.json` of AI21-Jamba2-3B), training path: a mixer a layer, a Mamba-1
selective state space (arXiv:2312.00752) or multi-query attention with NoPE,
chosen by `layer % attn_layer_period == attn_layer_offset`; a dense SwiGLU in
every layer (`num_experts` 1); RMSNorm; the head tied to the embedding.

    h = x + Mixer_l(RMSNorm(x));  out = h + SwiGLU(RMSNorm(h));  final RMSNorm
    logits = out E^T                        E the embedding, tied
    Attention: H query heads, `num_key_value_heads` key-value heads of
      hidden / H, no bias, no positional encoding; the key-value heads are
      repeated to the query heads and the causal softmax at head_dim^-1/2 is
      `ops/attention.py`'s flash kernel
    Mamba (D_in = mamba_expand x hidden, N = mamba_d_state, R = mamba_dt_rank):
      [x, z]    = u W_in                               no bias
      x         = silu(conv4(x) + b_conv)              depthwise causal,
                  `mamba_d_conv` taps a channel (`kimi_linear.short_conv`)
      [dt, B, C] = x W_x                               no bias
      dt, B, C  = RMSNorm_R(dt), RMSNorm_N(B), RMSNorm_N(C)   (Jamba's own)
      delta     = softplus(dt W_dt + b_dt)             float32
      A         = -exp(A_log)                          [D_in, N] float32
      y         = selective_scan(x, delta, A, B, C) + D x     `ops/ssm.py`
      out       = (y * silu(z)) W_out                  no bias

Every size comes from the published keys (`JambaConfig.from_dict`;
`configs/ai21_jamba2_3b.json` is the file as published, with its
`source_url`). Refused, not approximated: `num_experts` > 1, a
`sliding_window`, `mamba_proj_bias` or no `mamba_conv_bias`, another
activation than silu, an untied head. As the other decoders
(`models/deepseek_v2.py`): weights are created in the compute dtype, each
layer is rematerialised, and the module has the entry points `hidden`,
`head` and `__call__`.
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

from fedml_tpu.models.deepseek_v2 import RMSNorm, SwiGLU, _dense
from fedml_tpu.models.kimi_linear import short_conv
from fedml_tpu.ops.attention import flash_attention
from fedml_tpu.ops.ssm import selective_scan

PUBLISHED = os.path.join(os.path.dirname(__file__), "configs",
                         "ai21_jamba2_3b.json")

_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "attn_layer_offset",
         "attn_layer_period", "mamba_d_state", "mamba_d_conv", "mamba_expand",
         "mamba_dt_rank", "rms_norm_eps", "vocab_size",
         "max_position_embeddings")


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    attn_layer_offset: int
    attn_layer_period: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_expand: int
    mamba_dt_rank: int
    rms_norm_eps: float
    vocab_size: int
    max_position_embeddings: int

    @classmethod
    def from_dict(cls, d: dict) -> "JambaConfig":
        """From the published keys; every one has to be there."""
        missing = [k for k in _KEYS if k not in d]
        if missing:
            raise KeyError(f"model configuration lacks {missing}")
        unbuilt = {
            "num_experts": d.get("num_experts", 1) > 1,
            "sliding_window": d.get("sliding_window") is not None,
            "mamba_proj_bias": bool(d.get("mamba_proj_bias", False)),
            "mamba_conv_bias": not d.get("mamba_conv_bias", True),
            "hidden_act": d.get("hidden_act", "silu") != "silu",
            "tie_word_embeddings": not d.get("tie_word_embeddings", True),
        }
        if any(unbuilt.values()):
            raise NotImplementedError(
                "this Jamba decoder does not build "
                f"{[k for k, v in unbuilt.items() if v]} as configured")
        cfg = cls(**{k: d[k] for k in _KEYS})
        if cfg.hidden_size % cfg.num_attention_heads or (
                cfg.num_attention_heads % cfg.num_key_value_heads):
            raise ValueError("the heads must divide the hidden size and the "
                             "key-value heads the query heads")
        return cfg

    @classmethod
    def from_file(cls, path: str | None) -> "JambaConfig":
        with open(path or PUBLISHED) as f:
            return cls.from_dict(json.load(f))

    def is_attention_layer(self, i: int) -> bool:
        """Layer i counted from 0, as the source counts it."""
        return i % self.attn_layer_period == self.attn_layer_offset

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size


def _a_log_init(key, shape, dtype):
    # S4D-real: A = -(1..N) a channel
    del key
    a = jnp.arange(1, shape[1] + 1, dtype=jnp.float32)
    return jnp.broadcast_to(jnp.log(a), shape).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    # inverse softplus of a log-uniform step in [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3),
                                    math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def inner_norms(p, rank: int, n: int, norm):
    """[dt, B, C] = p split, each through an RMSNorm of its own (`norm(name)`
    makes one): Jamba's `dt_layernorm`, `b_layernorm`, `c_layernorm`."""
    return (norm("dt_norm")(p[..., :rank]),
            norm("b_norm")(p[..., rank:rank + n]),
            norm("c_norm")(p[..., rank + n:]))


def skip_and_gate(y, x, z, skip):
    """(y + D x) silu(z) in float32."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return (f32(y) + f32(skip) * f32(x)) * nn.silu(f32(z))


class Mamba(nn.Module):
    cfg: JambaConfig
    dtype: Any

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        inner, n, r = c.mamba_inner, c.mamba_d_state, c.mamba_dt_rank
        xz = _dense(2 * inner, self.dtype, "in_proj")(u)
        x, z = xz[..., :inner], xz[..., inner:]
        with jax.named_scope("ssm_conv"):
            taps = self.param("conv_taps", nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (c.mamba_d_conv, inner), self.dtype)
            bias = self.param("conv_bias", nn.initializers.zeros, (inner,),
                              self.dtype)
            x = nn.silu(short_conv(x, taps, bias))
        with jax.named_scope("ssm_gates"):
            p = _dense(r + 2 * n, self.dtype, "x_proj")(x)
            dt, b, cc = inner_norms(p, r, n, lambda name: RMSNorm(
                c.rms_norm_eps, self.dtype, name=name))
            dt_bias = self.param("dt_bias", _dt_bias_init, (inner,), self.dtype)
            delta = jax.nn.softplus(
                _dense(inner, self.dtype, "dt_proj")(dt).astype(jnp.float32)
                + dt_bias.astype(jnp.float32))
        a_log = self.param("A_log", _a_log_init, (inner, n), self.dtype)
        skip = self.param("D", nn.initializers.ones, (inner,), self.dtype)
        y = selective_scan(x, delta, -jnp.exp(a_log.astype(jnp.float32)),
                           b, cc)
        y = skip_and_gate(y, x, z, skip)
        return _dense(c.hidden_size, self.dtype, "out_proj")(y.astype(self.dtype))


class Attention(nn.Module):
    cfg: JambaConfig
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, t, _ = x.shape
        h, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = _dense(h * hd, self.dtype, "q_proj")(x).reshape(b, t, h, hd)
        k = _dense(kv * hd, self.dtype, "k_proj")(x).reshape(b, t, kv, hd)
        v = _dense(kv * hd, self.dtype, "v_proj")(x).reshape(b, t, kv, hd)
        # the key-value heads to every query head (the kernel takes no groups)
        k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
        o = flash_attention(q, k, v, True)
        return _dense(c.hidden_size, self.dtype, "o_proj")(o.reshape(b, t, h * hd))


class Block(nn.Module):
    cfg: JambaConfig
    is_attention: bool
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)  # noqa: E731
        mixer = (Attention(c, self.dtype, name="attn") if self.is_attention
                 else Mamba(c, self.dtype, name="mamba"))
        h = x + mixer(norm("input_norm")(x))
        return h + SwiGLU(c.intermediate_size, self.dtype, name="mlp")(
            norm("post_norm")(h))


class JambaLM(nn.Module):
    cfg: JambaConfig
    dtype: Any = jnp.float32

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                              param_dtype=self.dtype)
        block = nn.remat(Block)
        self.layers = [block(c, c.is_attention_layer(i), self.dtype)
                       for i in range(c.num_hidden_layers)]
        self.final_norm = RMSNorm(c.rms_norm_eps, self.dtype)

    def hidden(self, tokens, train: bool = False):
        """tokens [B, T] -> (final-norm states [B, T, hidden], {})."""
        if tokens.shape[1] > self.cfg.max_position_embeddings:
            raise ValueError(f"sequence length {tokens.shape[1]} exceeds "
                             f"max_position_embeddings")
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x)
        return self.final_norm(x), {}

    def head(self, h):
        """states [.., hidden] -> float32 logits [.., vocab], by the
        embedding (tied)."""
        return self.embed.attend(h).astype(jnp.float32)

    def __call__(self, tokens, train: bool = False):
        return self.head(self.hidden(tokens, train)[0])

    def describe(self) -> dict:
        """The `model_built` event's fields (`telemetry/tracer.py`)."""
        c = self.cfg
        n_attn = sum(c.is_attention_layer(i)
                     for i in range(c.num_hidden_layers))
        return {"layers": c.num_hidden_layers,
                "mixers": {"mamba": c.num_hidden_layers - n_attn,
                           "attention": n_attn}}
