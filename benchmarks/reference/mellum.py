"""Mellum 2 (`model_type` mellum, JetBrains' Mellum2-12B-A2.5B) as a FROZEN
base under rank-r adapters, plain `jax.numpy` in float32 at `highest`: the
decoder as its published `config.json` describes it, training path, with
LoRA written out as x W + s (x A) B on every 2-D kernel but the head.
Nothing here imports the program (the loss, the norm and the head's
`Outputs` are `reference/deepseek_v2_lite.py`'s).

    h = x + Attn_l(RMSNorm(x));  out = h + MoE(RMSNorm(h));  final RMSNorm
    logits = out W_head (untied)
    Attn_l, t_l = layer_types[l]:
      q = lin(x) as H heads, k, v = lin(x) as kv heads, all head_dim wide;
      rotary on q and k over the whole head, rotate-half layout (the
      source's `apply_rotary_pos_emb`): x cos + [-x2 | x1] sin, cos and sin
      of positions x inverse frequencies repeated over the two halves;
        sliding_attention: theta^(-2i/d) ("default")
        full_attention:    YaRN: 1/(f theta^(2i/d)) below the ramp,
                           1/theta^(2i/d) above it, the ramp linear between
                           floor(corr(beta_fast)) and ceil(corr(beta_slow)),
                           corr(r) = d ln(orig / (2 pi r)) / (2 ln theta);
                           cos and sin times `attention_factor`
      query head h reads key-value head h // (H / kv);
      softmax(q k^T d^-1/2 + mask) v, mask causal on a full layer and the
      band i - sliding_window < j <= i on a window layer, as a DENSE mask
    MoE: p = softmax(lin_f32(x)) over the experts, the top k (ties to the
      lower index) renormalised to sum 1 where `norm_topk_prob`;
      y = sum over the chosen p_e (silu(x G_e) * x U_e) D_e
    loss: softmax cross-entropy of the next token, pad id 0 left out

Departures, none of which changes a value: the experts are computed by a
LOOP over all of them, each on every token and weighed by the token's
renormalised gate or 0 (a gather of each token's 8 experts' weights would
move 4 MB a token and matrix, 0.8 TB a layer and sequence: the loop's 8
times the needed products cost less); the scores are made a head and a
block of `QUERY_BLOCK` queries at a time, each over every key under the
dense mask; the base stays in its stored dtype and is cast a layer (an
expert) at a time; each layer, head and expert is a `jax.checkpoint`; the
loss runs over blocks of tokens. A model of fewer layers than
`layer_types` lists takes its first entries. `compute` other than "f32"
(the controls of PERF.md section 2) rounds as `common.py` says.

`make_attention(sizes)` is one layer's attention module (adapters and all)
on a given input, which `compare/mellum_lora_rounds.py` holds the
program's to, array against array.

**FLOPs (`layers`)**: frozen matrices (the experts' on the k pairs a token
makes, the head) at two thirds of their forward operations, as
`deepseek_v2_lite.py` and for its reason; adapters and the attention core
in full, the core over the pairs its mask leaves (`attention_flops_band`).
`expert_flops` is what the readers count the grouped products by.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp

from . import common as c
from .deepseek_v2_lite import (LOSS_BLOCK, PAD_ID, Outputs,  # noqa: F401
                               _rms_norm, loss, published)

QUERY_BLOCK = 1024
WINDOW, FULL = "sliding_attention", "full_attention"


def kinds(cfg: dict) -> list[str]:
    """The attention kind of each layer the configuration holds."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def window_of(cfg: dict, kind: str):
    return cfg["sliding_window"] if kind == WINDOW else None


def _kernels(cfg: dict) -> dict:
    """{path: (cin, cout)} of a layer's 2-D kernels (the adapted ones)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {("attn", "q_proj"): (d, h * hd), ("attn", "k_proj"): (d, kv * hd),
            ("attn", "v_proj"): (d, kv * hd), ("attn", "o_proj"): (h * hd, d),
            ("moe", "router"): (d, cfg["num_experts"])}


# ------------------------------------------------------------------ rotary

def inv_freq(rope: dict, dim: int):
    """Inverse frequencies [dim / 2] of one kind's `rope_parameters`."""
    theta = rope["rope_theta"]
    pos = [theta ** (2 * i / dim) for i in range(dim // 2)]
    if rope["rope_type"] == "default":
        return jnp.asarray([1.0 / p for p in pos], jnp.float32)
    factor, orig = rope["factor"], rope["original_max_position_embeddings"]

    def corr(rotations):
        return (dim * math.log(orig / (2 * math.pi * rotations))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, p in enumerate(pos):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(ramp / (factor * p) + (1.0 - ramp) / p)
    return jnp.asarray(out, jnp.float32)


def attention_factor(rope: dict) -> float:
    return 1.0 if rope["rope_type"] == "default" else rope["attention_factor"]


def _rotate(x, cos, sin):
    """x [.., T, H, d]: x cos + rotate_half(x) sin, cos/sin [T, d]."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[:, None, :] + rot * sin[:, None, :]).astype(x.dtype)


# ------------------------------------------------------------------ FLOPs

def band_pairs(t: int, window) -> int:
    """(query, key) pairs a sequence's mask leaves: causal, or the band."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def attention_flops_band(t: int, window, heads: int, d: int) -> int:
    """Forward operations a sequence of one layer's attention core: the
    score and the context product over `d`-wide heads (2 per multiply-add)
    over the pairs the mask leaves. The softmax is not counted."""
    return 2 * heads * (d + d) * band_pairs(t, window)


def expert_flops(pairs: int, hidden: int, width: int) -> int:
    """Forward operations of the three matrices of `pairs` (token, expert)
    pairs."""
    return pairs * 3 * 2 * hidden * width


def routed_pairs(sizes: dict, sequences: int) -> int:
    """(token, expert) pairs a dropless model routes for `sequences`
    sequences: every token to `num_experts_per_tok` experts in every
    layer."""
    cfg = published(sizes)
    return (sequences * sizes["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["num_hidden_layers"])


def mixers(sizes: dict) -> dict:
    """{"window": layers, "full": layers} as the configuration runs."""
    ks = kinds(published(sizes))
    return {"window": ks.count(WINDOW), "full": ks.count(FULL)}


def layers(sizes: dict) -> list[dict]:
    """The layers that multiply (harness/flops.py): a sample is a sequence,
    a matrix is applied `seq_len` times, an expert's on the k pairs a token
    makes."""
    cfg, t, r = published(sizes), sizes["seq_len"], sizes["lora_rank"]
    d, f, k = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts_per_tok"])

    def frozen(cin, cout, times=t):
        return {"kind": "frozen_dense", "flops": 4 * cin * cout // 3,
                "times": times}

    out = []
    for kind in kinds(cfg):
        for cin, cout in _kernels(cfg).values():
            out += [frozen(cin, cout),
                    {"kind": "dense", "cin": cin, "cout": r, "times": t},
                    {"kind": "dense", "cin": r, "cout": cout, "times": t}]
        out.append({"kind": "attention", "flops": attention_flops_band(
            t, window_of(cfg, kind), cfg["num_attention_heads"],
            cfg["head_dim"])})
        out += [frozen(d, f, t * k), frozen(d, f, t * k), frozen(f, d, t * k)]
    return out + [frozen(d, cfg["vocab_size"])]


# ---------------------------------------------------------------- weights

def init(key, sizes: dict) -> dict:
    """{"params": the adapters, "lora_base": the base in `base_dtype`}, laid
    out as the program lays its variables out; `lora_B` small and not zero
    (as `deepseek_v2_lite.py`)."""
    cfg = published(sizes)
    dt = jnp.dtype(sizes["base_dtype"])
    r, d, v = sizes["lora_rank"], cfg["hidden_size"], cfg["vocab_size"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    keys = iter(jax.random.split(key, 4 + 24 * cfg["num_hidden_layers"]))

    def weight(shape, fan_in):
        return c.scaled_normal(next(keys), shape, fan_in).astype(dt)

    def put(tree, path, leaf):
        for name in path[:-1]:
            tree = tree.setdefault(name, {})
        tree[path[-1]] = leaf

    ones = lambda n: jnp.ones((n,), dt)  # noqa: E731
    base = {"embed": {"embedding": weight((v, d), 1.0) * jnp.asarray(0.02, dt)},
            "final_norm": {"scale": ones(d)},
            "lm_head": {"kernel": weight((d, v), d)}}
    adapters = {}
    for i in range(cfg["num_hidden_layers"]):
        layer, adapt = {}, {}
        for path, (cin, cout) in _kernels(cfg).items():
            put(layer, path + ("kernel",), weight((cin, cout), cin))
            put(adapt, path + ("kernel",), {
                "lora_A": c.scaled_normal(next(keys), (cin, r), cin),
                "lora_B": sizes["lora_b_std"] * jax.random.normal(
                    next(keys), (r, cout), jnp.float32)})
        layer["input_norm"] = {"scale": ones(d)}
        layer["post_norm"] = {"scale": ones(d)}
        layer["moe"]["experts_gate"] = weight((e, d, f), d)
        layer["moe"]["experts_up"] = weight((e, d, f), d)
        layer["moe"]["experts_down"] = weight((e, f, d), f)
        base[f"layers_{i}"], adapters[f"layers_{i}"] = layer, adapt
    return {"params": adapters, "lora_base": base}


# ---------------------------------------------------------------- forward

def _pieces(cfg, scale, compute, b, t):
    """The layers' functions for inputs of [b, t, hidden]: `attention`
    (x, w, a, kind) -> the attention module's output, `experts`,
    `block` (x, w, a, kind) -> the layer's."""
    dt = c.ACT_DTYPE[compute]
    eps = cfg["rms_norm_eps"]
    h_n, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    qb = min(QUERY_BLOCK, t)
    pos = jnp.arange(t, dtype=jnp.float32)

    def lin(x, w, a):
        """x W + s (x A) B, the adapters' pair written out."""
        y = c.matmul(x, w["kernel"].astype(jnp.float32), compute)
        low = c.matmul(c.matmul(x, a["kernel"]["lora_A"], compute),
                       a["kernel"]["lora_B"], compute)
        return y + (scale * low).astype(y.dtype)

    def cos_sin(kind):
        rope = cfg["rope_parameters"][kind]
        angles = pos[:, None] * inv_freq(rope, hd)[None]
        emb = jnp.concatenate([angles, angles], axis=-1)
        m = attention_factor(rope)
        return jnp.cos(emb) * m, jnp.sin(emb) * m

    def attention(x, w, a, kind):
        q = lin(x, w["q_proj"], a["q_proj"]).reshape(b, t, h_n, hd)
        k = lin(x, w["k_proj"], a["k_proj"]).reshape(b, t, kv, hd)
        v = lin(x, w["v_proj"], a["v_proj"]).reshape(b, t, kv, hd)
        cos, sin = cos_sin(kind)
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        group = jnp.arange(h_n) // (h_n // kv)
        window = window_of(cfg, kind)

        @jax.checkpoint
        def head(qkv):
            qh, kh, vh = qkv                        # [b, t, hd]

            def rows(i):
                qi = jax.lax.dynamic_slice_in_dim(qh, i * qb, qb, axis=1)
                s = jnp.einsum("bqd,bkd->bqk", c.operand(qi, compute),
                               c.operand(kh, compute),
                               precision=c.precision(compute),
                               preferred_element_type=jnp.float32) * hd ** -0.5
                row = i * qb + jnp.arange(qb)[:, None]
                col = jnp.arange(t)[None, :]
                seen = col <= row
                if window is not None:
                    seen = seen & (row - col < window)
                p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
                return jnp.einsum("bqk,bkd->bqd", c.operand(p, compute),
                                  c.operand(vh, compute),
                                  precision=c.precision(compute),
                                  preferred_element_type=dt)

            o = jax.lax.map(rows, jnp.arange(t // qb))   # [t/qb, b, qb, hd]
            return jnp.moveaxis(o, 0, 1).reshape(b, t, hd)

        o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0),
                               jnp.moveaxis(k, 2, 0)[group],
                               jnp.moveaxis(v, 2, 0)[group]))
        return lin(jnp.moveaxis(o, 0, 2).reshape(b, t, h_n * hd),
                   w["o_proj"], a["o_proj"])

    def experts(x, w, a):
        """x [N, hidden] -> sum over the chosen experts, by a loop over all."""
        k = cfg["num_experts_per_tok"]
        s = jax.nn.softmax(lin(x.astype(jnp.float32), w["router"],
                               a["router"]).astype(jnp.float32), axis=-1)
        e = s.shape[-1]
        # the rank of every expert's score among a token's: chosen if under k
        before = (s[:, :, None] > s[:, None, :]) | (
            (s[:, :, None] == s[:, None, :])
            & (jnp.arange(e)[:, None] < jnp.arange(e)[None, :]))
        weight = jnp.where(before.sum(axis=1) < k, s, 0.0)
        if cfg["norm_topk_prob"]:
            weight = weight / weight.sum(axis=-1, keepdims=True)

        @jax.checkpoint
        def one(wg, wu, wd, m_e):
            f32 = lambda z: z.astype(jnp.float32)  # noqa: E731
            gate = c.matmul(x, f32(wg), compute)
            hid = jax.nn.silu(gate) * c.matmul(x, f32(wu), compute)
            return (c.matmul(hid, f32(wd), compute).astype(jnp.float32)
                    * m_e[:, None])

        def step(y, per):
            return y + one(*per), None

        y, _ = jax.lax.scan(
            step, jnp.zeros(x.shape, jnp.float32),
            (w["experts_gate"], w["experts_up"], w["experts_down"], weight.T))
        return y.astype(x.dtype)

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def block(x, w, a, kind):
        hid = x + attention(_rms_norm(x, w["input_norm"]["scale"], eps),
                            w["attn"], a["attn"], kind)
        z = _rms_norm(hid, w["post_norm"]["scale"], eps)
        return hid + experts(z.reshape(b * t, -1), w["moe"],
                             a["moe"]).reshape(z.shape)

    return types.SimpleNamespace(attention=attention, experts=experts,
                                 block=block)


def _forward(cfg, scale, compute, base, adapters, tokens):
    """tokens [B, T] -> final-norm states [B, T, hidden]."""
    block = _pieces(cfg, scale, compute, *tokens.shape).block
    x = base["embed"]["embedding"][tokens].astype(c.ACT_DTYPE[compute])
    for i, kind in enumerate(kinds(cfg)):
        x = block(x, base[f"layers_{i}"], adapters[f"layers_{i}"], kind)
    return _rms_norm(x, base["final_norm"]["scale"], cfg["rms_norm_eps"])


def make_attention(sizes: dict):
    """-> attention(w, a, x, kind, compute="f32"): ONE layer's attention
    module alone; `w` the layer's base, `a` its adapters, x [B, T, hidden]
    what the layer's input norm would hand it, `kind` the layer's entry of
    `layer_types`."""
    cfg = published(sizes)
    scale = sizes["lora_alpha"] / sizes["lora_rank"]

    def attention(w, a, x, kind, compute: str = "f32"):
        x = x.astype(c.ACT_DTYPE[compute])
        return _pieces(cfg, scale, compute, *x.shape[:2]).attention(
            x, w["attn"], a["attn"], kind)

    return attention


def make_apply(sizes: dict):
    """-> apply(variables, x, train, key, compute, mask=None) for a model of
    these sizes: tokens x[B, T] -> (Outputs, {}). No dropout, no layer that
    looks across rows: `key` and `mask` are unused."""
    cfg = published(sizes)
    scale = sizes["lora_alpha"] / sizes["lora_rank"]

    def apply(variables, x, train, key, compute, mask=None):
        base = variables["lora_base"]
        states = _forward(cfg, scale, compute, base, variables["params"], x)
        return Outputs(states, base["lm_head"]["kernel"], compute), {}

    return apply


def apply(*args, **kwargs):
    raise TypeError(
        "this model's forward pass needs its configuration (the layer "
        "pattern, the two rotary settings): bind it with make_apply(sizes), "
        "as benchmarks/compare/lora_rounds.py does")
