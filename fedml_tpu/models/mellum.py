"""Mellum 2 decoder (the `mellum` model type of the published `config.json`
of JetBrains' Mellum2-12B-A2.5B), training path: grouped-query attention
with a sliding window on some layers and full causal attention on others,
chosen per layer by the published `layer_types`, each kind with a rotary
setting of its own; routed experts in every layer; RMSNorm; an untied head.

    h = x + Attn_l(RMSNorm(x));  out = h + MoE(RMSNorm(h));  final RMSNorm
    Attn_l: q = x W_q as H heads, k = x W_k, v = x W_v as `num_key_value_heads`
      heads, all `head_dim` wide, no bias; rotary on q and k over the whole
      head in the rotate-half layout, by the kind's `rope_parameters`:
      `sliding_attention` "default" (theta), `full_attention` "yarn" (the
      YaRN blend of `deepseek_v2.yarn_blend`, its `attention_factor` on cos
      and sin, so on the scores squared); query head h reads key-value head
      h // (H / kv); softmax(q k^T head_dim^-1/2 + mask_l) v, then W_o.
      mask_l: causal on a full layer; on a window layer query i sees keys
      i - sliding_window < j <= i (`ops/attention.py`'s band)
    MoE: p = softmax(float32(x) W_r) over `num_experts`, the top
      `num_experts_per_tok` renormalised to sum to 1 (`norm_topk_prob`),
      y = sum_k p_k E_k(x), E_k a SwiGLU of `moe_intermediate_size`; no
      shared expert (`deepseek_v2.MoE` with none), dropless (`ops/moe.py`)

Every size comes from the published keys (`MellumConfig.from_dict`;
`configs/mellum2_12b_a2_5b.json` is the file as published, with its
`source_url`). A model of fewer layers than `layer_types` lists is the first
layers of the published pattern (a first pipeline stage). Refused, not
approximated: a `rope_type` other than default or yarn, a partial rotary, a
layer kind other than the two, an `mlp_layer_types` entry other than sparse,
attention bias, a tied head, shared experts, another activation than silu,
`use_sliding_window` false. Not built: the multi-token-prediction head the
model card names (no key of `config` describes it) and the load-balance
loss (the router is frozen). As the other decoders: weights are created in
the compute dtype, each layer is rematerialised, the key-value heads are
repeated to every query head before the kernel, and the module has the
entry points `hidden`, `head` and `__call__`.

The attention factor multiplies the kernel's softmax scale (cos and sin
times a, the scores times a^2): the same product as the source's, with
rotary's outputs rounded before the factor and not after it.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.models.deepseek_v2 import (MoE, RMSNorm, _dense, rotate_half,
                                          yarn_blend)
from fedml_tpu.ops.attention import band_tiles, flash_attention, flash_blocks

PUBLISHED = os.path.join(os.path.dirname(__file__), "configs",
                         "mellum2_12b_a2_5b.json")

_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "head_dim", "num_experts",
         "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
         "rms_norm_eps", "vocab_size", "max_position_embeddings",
         "sliding_window")
WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One kind's `rope_parameters`: "default" (theta alone) or "yarn"."""
    rope_type: str
    theta: float
    factor: float = 1.0
    original: int = 0
    beta_fast: float = 0.0
    beta_slow: float = 0.0
    attention_factor: float = 1.0

    @classmethod
    def from_dict(cls, d: dict) -> "Rotary":
        if d["rope_type"] == "default":
            return cls("default", d["rope_theta"])
        return cls("yarn", d["rope_theta"], d["factor"],
                   d["original_max_position_embeddings"], d["beta_fast"],
                   d["beta_slow"], d["attention_factor"])

    def inv_freq(self, dim: int) -> np.ndarray:
        if self.rope_type == "default":
            return (1.0 / self.theta ** (np.arange(0, dim, 2, dtype=np.float64)
                                         / dim)).astype(np.float32)
        return yarn_blend(dim, self.theta, self.factor, self.original,
                          self.beta_fast, self.beta_slow)

    def cos_sin(self, t: int, dim: int) -> tuple:
        """cos, sin [t, dim] of positions 0..t-1, the halves' angles
        repeated (float32)."""
        freqs = np.outer(np.arange(t, dtype=np.float32), self.inv_freq(dim))
        emb = np.concatenate([freqs, freqs], axis=-1)
        return np.cos(emb), np.sin(emb)


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool
    rms_norm_eps: float
    vocab_size: int
    max_position_embeddings: int
    sliding_window: int
    layer_types: tuple
    rotary: tuple   # (Rotary of the window layers, of the full layers)

    @classmethod
    def from_dict(cls, d: dict) -> "MellumConfig":
        """From the published keys; every one has to be there."""
        missing = [k for k in _KEYS + ("layer_types", "rope_parameters")
                   if k not in d]
        if missing:
            raise KeyError(f"model configuration lacks {missing}")
        rope = d["rope_parameters"]
        unbuilt = {
            "rope_type": any(rope.get(kind, {}).get("rope_type")
                             not in ("default", "yarn")
                             for kind in (WINDOW, FULL)),
            "partial_rotary_factor": d.get("partial_rotary_factor", 1.0) != 1,
            "layer_types": bool(set(d["layer_types"]) - {WINDOW, FULL}),
            "mlp_layer_types": any(m != "sparse" for m in
                                   d.get("mlp_layer_types", ["sparse"])),
            "attention_bias": bool(d.get("attention_bias", False)),
            "tie_word_embeddings": bool(d.get("tie_word_embeddings", False)),
            "shared_experts": bool(
                d.get("num_shared_experts")
                or d.get("shared_expert_intermediate_size")),
            "hidden_act": d.get("hidden_act", "silu") != "silu",
            "use_sliding_window": not d.get("use_sliding_window", True),
        }
        if any(unbuilt.values()):
            raise NotImplementedError(
                "this Mellum decoder does not build "
                f"{[k for k, v in unbuilt.items() if v]} as configured")
        n = d["num_hidden_layers"]
        if len(d["layer_types"]) < n:
            raise ValueError(f"layer_types names {len(d['layer_types'])} "
                             f"layers, the model has {n}")
        cfg = cls(**{k: d[k] for k in _KEYS},
                  layer_types=tuple(d["layer_types"][:n]),
                  rotary=(Rotary.from_dict(rope[WINDOW]),
                          Rotary.from_dict(rope[FULL])))
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("the key-value heads must divide the query heads")
        return cfg

    @classmethod
    def from_file(cls, path: str | None) -> "MellumConfig":
        with open(path or PUBLISHED) as f:
            return cls.from_dict(json.load(f))

    # what `deepseek_v2.MoE` reads (its module docstring)
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    n_shared_experts = 0
    routed_scaling_factor = 1.0
    scoring_func = "softmax"
    selection_bias = False
    experts_held = None


def layer_window(cfg: MellumConfig, kind: str):
    """The band a layer of `kind` attends over: None is full causal."""
    return cfg.sliding_window if kind == WINDOW else None


def layer_rotary(cfg: MellumConfig, kind: str) -> Rotary:
    return cfg.rotary[kind == FULL]


class Attention(nn.Module):
    cfg: MellumConfig
    kind: str
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, t, _ = x.shape
        h, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = _dense(h * hd, self.dtype, "q_proj")(x).reshape(b, t, h, hd)
        k = _dense(kv * hd, self.dtype, "k_proj")(x).reshape(b, t, kv, hd)
        v = _dense(kv * hd, self.dtype, "v_proj")(x).reshape(b, t, kv, hd)
        rope = layer_rotary(c, self.kind)
        cos, sin = rope.cos_sin(t, hd)
        q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
        # the key-value heads to every query head (the kernel takes no groups)
        k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
        o = flash_attention(q, k, v, True,
                            scale=rope.attention_factor ** 2 * hd ** -0.5,
                            window=layer_window(c, self.kind))
        return _dense(c.hidden_size, self.dtype, "o_proj")(
            o.reshape(b, t, h * hd))


class Block(nn.Module):
    cfg: MellumConfig
    kind: str
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)  # noqa: E731
        with jax.named_scope("attn_window" if self.kind == WINDOW
                             else "attn_full"):
            h = x + Attention(c, self.kind, self.dtype, name="attn")(
                norm("input_norm")(x))
        y, load, _ = MoE(c, self.dtype, name="moe")(norm("post_norm")(h))
        return h + y, load


class MellumLM(nn.Module):
    cfg: MellumConfig
    dtype: Any = jnp.float32
    #: as `DeepseekV2LM`'s: the routed experts get no weight gradient
    frozen_base_only = True

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                              param_dtype=self.dtype)
        block = nn.remat(Block)
        self.layers = [block(c, kind, self.dtype) for kind in c.layer_types]
        self.final_norm = RMSNorm(c.rms_norm_eps, self.dtype)
        self.lm_head = _dense(c.vocab_size, self.dtype)

    def hidden(self, tokens, train: bool = False):
        """tokens [B, T] -> (final-norm states [B, T, hidden], {"moe_load":
        [layers, num_experts] tokens each expert received})."""
        if tokens.shape[1] > self.cfg.max_position_embeddings:
            raise ValueError(f"sequence length {tokens.shape[1]} exceeds "
                             f"max_position_embeddings")
        x = self.embed(tokens)
        loads = []
        for layer in self.layers:
            x, load = layer(x)
            loads.append(load)
        return self.final_norm(x), {"moe_load": jnp.stack(loads)}

    def head(self, h):
        """states [.., hidden] -> float32 logits [.., vocab]."""
        return self.lm_head(h).astype(jnp.float32)

    def __call__(self, tokens, train: bool = False):
        return self.head(self.hidden(tokens, train)[0])

    def describe(self) -> dict:
        """The `model_built` event's fields (`telemetry/tracer.py`)."""
        c = self.cfg
        n_window = c.layer_types.count(WINDOW)
        return {"layers": c.num_hidden_layers,
                "mixers": {"window": n_window,
                           "full": c.num_hidden_layers - n_window},
                "window": c.sliding_window,
                "experts_held": c.num_experts,
                "experts_routed": c.num_experts}

    def attention_tiles(self, seq_len: int) -> dict:
        """A head's forward tiles at `seq_len` on the tile the kernel
        chooses for the band: the band's live ones against causal's at the
        same tile (`ops/attention.py::band_tiles`)."""
        c = self.cfg
        bq, bk = flash_blocks("fwd", seq_len, seq_len, c.head_dim, c.head_dim,
                              self.dtype, window=c.sliding_window)
        return {"tile": [bq, bk],
                "window": band_tiles(seq_len, bq, bk, c.sliding_window),
                "causal": band_tiles(seq_len, bq, bk, None)}
