"""Kimi Linear decoder (arXiv:2510.26692; `modeling_kimi.py` beside the
published `config.json` of Kimi-Linear-48B-A3B), training path: a mixer a
layer, Kimi Delta Attention (KDA, a chunked gated delta rule with a decay a
channel) or latent attention WITHOUT rotary (NoPE MLA), by the published
layer lists (`linear_attn_config.kda_layers` / `full_attn_layers`, counted
from 1: three KDA to one MLA); a dense SwiGLU first layer, then experts
routed by sigmoid scores with a selection bias, plus a shared expert;
RMSNorm, an untied head.

    h = x + Mixer_l(RMSNorm(x));  out = h + FFN_l(RMSNorm(h));  final RMSNorm
    KDA (H heads of d_k = d_v = `linear_attn_config.head_dim`, no bias):
      q~, k~, v = silu(conv4(x W_q)), silu(conv4(x W_k)), silu(conv4(x W_v))
            conv4 a depthwise causal convolution over time of
            `short_conv_kernel_size` taps, a channel a filter
      g    = -exp(A_log[h]) * softplus((x W_fa) W_fb + dt_bias)   float32
      beta = sigmoid(x W_b)                                        float32
      o    = kda(q~, k~, v, g, beta)          `ops/kda.py`: L2-normalised
            q (x d_k^-1/2) and k, S_t = (I - beta k k^T) Diag(exp g) S_{t-1}
            + beta k v^T, o_t = S_t^T q_t
      y    = (RMSNorm_{d_v}(o) * sigmoid((x W_ga) W_gb)) W_o   one norm weight
            [d_v] shared by the heads
    MLA: `models/deepseek_v2.py::MLA` with `rotary` false (`mla_use_nope`)
    FFN: `deepseek_v2.SwiGLU` / `deepseek_v2.MoE` reading `scoring_func`
         "sigmoid", `selection_bias`, `norm_topk_prob` (`moe_renormalize`),
         `routed_scaling_factor` and `experts_held` from this configuration

Every size comes from the published keys (`KimiLinearConfig.from_dict`;
`configs/kimi_linear_48b_a3b.json` is the file as published, with its
`source_url`). Not in the published configuration, so assumed and said so in
a benchmark configuration's `assumed`: the two gates' rank is the linear
`head_dim`, no projection has a bias, the L2 norm's eps is 1e-6.

**A share of an expert-parallel layer.** `expert_share: {"of": n, "index":
i}` beside the published keys says that `num_experts` counts the experts
this chip HOLDS, the i-th of n equal shares: the router keeps `num_experts` x
n outputs, top-k and the renormalisation run over all of them, and the pairs
on absent experts contribute nothing (`ops/moe.py`, whose row buffers are
sized by the share: `num_experts` of `n_routed_experts`). Their exchange with the
other chips is not built (ROADMAP B5): on one chip the layer's output lacks
what the absent experts would add, and goes on to the next layer so.

Refused, not approximated: `q_lora_rank`, `num_expert_group` > 1,
`num_nextn_predict_layers` > 0, `rope_scaling`, tied embeddings,
`mla_use_nope` false, another activation than silu, a router activation
other than sigmoid or softmax. As `models/deepseek_v2.py`: weights are
created in the compute dtype, the routed experts take no weight gradient
(`frozen_base_only`), each layer is rematerialised, and the module has the
entry points `hidden`, `head` and `__call__`.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.deepseek_v2 import MLA, MoE, RMSNorm, SwiGLU, _dense
from fedml_tpu.ops.kda import kda

PUBLISHED = os.path.join(os.path.dirname(__file__), "configs",
                         "kimi_linear_48b_a3b.json")

_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
         "num_hidden_layers", "num_attention_heads", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts",
         "num_shared_experts", "num_experts_per_token",
         "first_k_dense_replace", "moe_layer_freq", "moe_renormalize",
         "moe_router_activation_func", "routed_scaling_factor",
         "rms_norm_eps", "rope_theta", "vocab_size", "model_max_length")


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    num_experts: int
    num_shared_experts: int
    num_experts_per_token: int
    first_k_dense_replace: int
    moe_layer_freq: int
    moe_renormalize: bool
    moe_router_activation_func: str
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    vocab_size: int
    model_max_length: int
    # linear_attn_config, flattened so that the dataclass hashes
    kda_layers: tuple
    full_attn_layers: tuple
    kda_heads: int
    kda_head_dim: int
    short_conv_kernel_size: int
    # expert_share (module docstring): of how many, which
    share_of: int = 1
    share_index: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "KimiLinearConfig":
        """From the published keys; every one has to be there."""
        missing = [k for k in _KEYS + ("linear_attn_config",) if k not in d]
        if missing:
            raise KeyError(f"model configuration lacks {missing}")
        unbuilt = {
            "q_lora_rank": d.get("q_lora_rank") is not None,
            "num_expert_group": d.get("num_expert_group", 1) != 1,
            "num_nextn_predict_layers":
                d.get("num_nextn_predict_layers", 0) > 0,
            "rope_scaling": d.get("rope_scaling") is not None,
            "tie_word_embeddings": bool(d.get("tie_word_embeddings", False)),
            "mla_use_nope": not d.get("mla_use_nope", False),
            "hidden_act": d.get("hidden_act", "silu") != "silu",
            "moe_router_activation_func":
                d["moe_router_activation_func"] not in ("sigmoid", "softmax"),
        }
        if any(unbuilt.values()):
            raise NotImplementedError(
                "this Kimi Linear decoder does not build "
                f"{[k for k, v in unbuilt.items() if v]} as configured")
        la = d["linear_attn_config"]
        share = d.get("expert_share", {"of": 1, "index": 0})
        cfg = cls(**{k: d[k] for k in _KEYS},
                  kda_layers=tuple(la["kda_layers"]),
                  full_attn_layers=tuple(la["full_attn_layers"]),
                  kda_heads=la["num_heads"], kda_head_dim=la["head_dim"],
                  short_conv_kernel_size=la["short_conv_kernel_size"],
                  share_of=share["of"], share_index=share["index"])
        for layer in range(1, cfg.num_hidden_layers + 1):
            if (layer in cfg.kda_layers) == (layer in cfg.full_attn_layers):
                raise ValueError(f"layer {layer} must be in exactly one of "
                                 f"kda_layers and full_attn_layers")
        if not 0 <= cfg.share_index < cfg.share_of:
            raise ValueError(f"expert_share {share} names no share")
        return cfg

    @classmethod
    def from_file(cls, path: str | None) -> "KimiLinearConfig":
        with open(path or PUBLISHED) as f:
            return cls.from_dict(json.load(f))

    def is_moe_layer(self, i: int) -> bool:
        return (i >= self.first_k_dense_replace
                and i % self.moe_layer_freq == 0)

    def is_kda_layer(self, i: int) -> bool:
        """Layer i counted from 0; the published lists count from 1."""
        return i + 1 in self.kda_layers

    # ---- what `deepseek_v2.MLA` and `deepseek_v2.MoE` read (its docstring)
    rotary = False
    selection_bias = True

    @property
    def scoring_func(self) -> str:
        return self.moe_router_activation_func

    @property
    def norm_topk_prob(self) -> bool:
        return self.moe_renormalize

    @property
    def n_routed_experts(self) -> int:
        """The router's width: every share's experts."""
        return self.num_experts * self.share_of

    @property
    def experts_held(self):
        if self.share_of == 1:
            return None
        return (self.share_index * self.num_experts, self.num_experts)

    @property
    def n_shared_experts(self) -> int:
        return self.num_shared_experts

    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token


def short_conv(x, taps):
    """Depthwise causal convolution over time, a channel a filter, no bias:
    y_t = sum_j taps[j] x_{t - (n - 1) + j}. x [B, T, C]; taps [n, C];
    multiply-adds in float32, -> x's dtype."""
    n, t = taps.shape[0], x.shape[1]
    x32, w = x.astype(jnp.float32), taps.astype(jnp.float32)
    padded = jnp.pad(x32, ((0, 0), (n - 1, 0), (0, 0)))
    y = sum(w[j] * padded[:, j:j + t] for j in range(n))
    return y.astype(x.dtype)


def log_decay(a_log, f, dt_bias):
    """g = -exp(A_log[h]) softplus(f + dt_bias) in float32. a_log [H];
    f [B, T, H * d]; dt_bias [H * d] -> [B, T, H, d], <= 0."""
    b, t, _ = f.shape
    h = a_log.shape[0]
    soft = jax.nn.softplus(f.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return (-jnp.exp(a_log.astype(jnp.float32))[:, None]
            * soft.reshape(b, t, h, -1))


def write_strength(x):
    """beta = sigmoid(.) in float32."""
    return jax.nn.sigmoid(x.astype(jnp.float32))


def _a_log_init(key, shape, dtype):
    # log U(1, 16), as the source initialises it
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    # inverse softplus of a log-uniform step in [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class KDA(nn.Module):
    cfg: KimiLinearConfig
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, t, _ = x.shape
        h, dk = c.kda_heads, c.kda_head_dim
        width, rank = h * dk, c.kda_head_dim
        taps_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1)

        def branch(name):
            y = _dense(width, self.dtype, name + "_proj")(x)
            taps = self.param(name + "_conv", taps_init,
                              (c.short_conv_kernel_size, width), self.dtype)
            return nn.silu(short_conv(y, taps)).reshape(b, t, h, dk)

        with jax.named_scope("kda_conv"):
            q, k, v = branch("q"), branch("k"), branch("v")
        with jax.named_scope("kda_gates"):
            f = _dense(width, self.dtype, "f_b_proj")(
                _dense(rank, self.dtype, "f_a_proj")(x))
            a_log = self.param("A_log", _a_log_init, (h,), self.dtype)
            dt_bias = self.param("dt_bias", _dt_bias_init, (width,),
                                 self.dtype)
            g = log_decay(a_log, f, dt_bias)
            beta = write_strength(_dense(h, self.dtype, "b_proj")(x))
            gate = _dense(width, self.dtype, "g_b_proj")(
                _dense(rank, self.dtype, "g_a_proj")(x))
        with jax.named_scope("kda"):
            o = kda(q, k, v, g, beta)
        o = RMSNorm(c.rms_norm_eps, self.dtype, name="o_norm")(o)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            self.dtype).reshape(b, t, h, dk)
        return _dense(c.hidden_size, self.dtype, "o_proj")(
            o.reshape(b, t, width))


class Block(nn.Module):
    cfg: KimiLinearConfig
    is_kda: bool
    is_moe: bool
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)  # noqa: E731
        z = norm("input_norm")(x)
        if self.is_kda:
            h = x + KDA(c, self.dtype, name="kda")(z)
        else:
            h = x + MLA(c, self.dtype, name="attn")(z)
        z = norm("post_norm")(h)
        path = None
        if self.is_moe:
            y, load, path = MoE(c, self.dtype, name="moe")(z)
        else:
            y = SwiGLU(c.intermediate_size, self.dtype, name="mlp")(z)
            load = jnp.zeros((c.n_routed_experts,), jnp.float32)
        if path is None:        # no experts here, or every one of them held
            path = jnp.zeros((2,), jnp.float32)
        return h + y, load, path


class KimiLinearLM(nn.Module):
    cfg: KimiLinearConfig
    dtype: Any = jnp.float32
    #: as `DeepseekV2LM`: refused without `--lora_rank`
    frozen_base_only = True

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                              param_dtype=self.dtype)
        block = nn.remat(Block)
        self.layers = [block(c, c.is_kda_layer(i), c.is_moe_layer(i),
                             self.dtype)
                       for i in range(c.num_hidden_layers)]
        self.final_norm = RMSNorm(c.rms_norm_eps, self.dtype)
        self.lm_head = _dense(c.vocab_size, self.dtype)

    def hidden(self, tokens, train: bool = False):
        """tokens [B, T] -> (final-norm states [B, T, hidden], {"moe_load":
        [expert layers, n_routed_experts] tokens each of the router's experts
        received, held here or not; and where this chip holds a share of
        them "moe_path": [2] the expert layers whose dispatch fit the share's
        row buffer and those that took the worst-case path, `ops/moe.py`})."""
        c = self.cfg
        if tokens.shape[1] > c.model_max_length:
            raise ValueError(f"sequence length {tokens.shape[1]} exceeds "
                             f"model_max_length")
        x = self.embed(tokens)
        loads, paths = [], jnp.zeros((2,), jnp.float32)
        for i, layer in enumerate(self.layers):
            x, load, path = layer(x)
            if c.is_moe_layer(i):
                loads.append(load)
                paths = paths + path
        aux = {"moe_load": jnp.stack(loads)} if loads else {}
        if loads and c.experts_held is not None:
            aux["moe_path"] = paths
        return self.final_norm(x), aux

    def head(self, h):
        """states [.., hidden] -> float32 logits [.., vocab]."""
        return self.lm_head(h).astype(jnp.float32)

    def __call__(self, tokens, train: bool = False):
        return self.head(self.hidden(tokens, train)[0])

    def describe(self) -> dict:
        """The `model_built` event's fields (`telemetry/tracer.py`), and
        `experts_first`: the first of the router's outputs this chip holds
        (the `moe_load` event's `held*` count `experts_held` from there)."""
        c = self.cfg
        n_kda = sum(c.is_kda_layer(i) for i in range(c.num_hidden_layers))
        return {"layers": c.num_hidden_layers,
                "mixers": {"kda": n_kda, "mla": c.num_hidden_layers - n_kda},
                "experts_held": c.num_experts,
                "experts_routed": c.n_routed_experts,
                "experts_first": c.share_index * c.num_experts}
