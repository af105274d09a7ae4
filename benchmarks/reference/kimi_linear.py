"""Kimi Linear (arXiv:2510.26692) as a FROZEN base under rank-r adapters,
plain `jax.numpy` in float32 at `highest`: the decoder of the published
`modeling_kimi.py`, training path, with LoRA written out as x W + s (x A) B
on every 2-D projection but the head. Nothing here imports the program (the
loss, the norm and the attention count are `reference/deepseek_v2_lite.py`'s).

    h = x + Mixer_l(RMSNorm(x));  out = h + FFN_l(RMSNorm(h));  final RMSNorm
    layers count from 1 in `linear_attn_config`: `kda_layers` take KDA,
    `full_attn_layers` MLA; the first `first_k_dense_replace` FFNs a SwiGLU

    KDA (H heads of d_k = d_v = `linear_attn_config.head_dim`, no bias):
      1. q~, k~, v~ = lin(x), each through a depthwise causal convolution of
         `short_conv_kernel_size` taps (FOUR SHIFTED MULTIPLY-ADDS), then SiLU
      2. a head at a time: q = q~ / |q~| * d_k^-1/2, k = k~ / |k~| (eps 1e-6
         under the root), v = v~
      3. g = -exp(A_log[h]) softplus(lin(lin(x)) + dt_bias), alpha = exp(g);
         beta = sigmoid(lin(x))
      4. TOKEN BY TOKEN, S_0 = 0 in R^(d_k x d_v) a head:
         S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
         o_t = S_t^T q_t
      5. y = lin(RMSNorm_{d_v}(o) * sigmoid(lin(lin(x)))), one norm weight
         [d_v] shared by the heads
    MLA, NoPE: q = lin(x) as heads x (nope + rope); [c | k_pe] = lin(x);
      [k_nope | v] = lin(RMSNorm(c)); k = [k_nope ; k_pe to every head]; no
      rotation; the FULL causal score matrix softmax(q k^T (nope + rope)^-1/2)
    experts: s = sigmoid(lin_f32(x)) over `num_experts` x `expert_share.of`
      outputs; the k largest of s + b chosen (ties to the lower index); weights
      w = routed_scaling_factor * s / (sum of the chosen s + 1e-20) (the bias
      selects and does not weigh); the HELD experts (`expert_share.index`-th
      share) by a loop, each applied to every token and multiplied by the
      token's 0/1 choice times w; what the absent experts would add is left
      out; plus one shared SwiGLU
    loss: softmax cross-entropy of the next token, pad id 0 left out

Departures, none of which changes a value: step 4 is a `lax.scan` over tokens
inside a checkpointed scan over stretches of 64 tokens, and MLA's score matrix
is made a head at a time (`lax.map`), so that the backward pass fits beside a
4.6 GB base; the base stays in its stored dtype and is cast a layer (an
expert) at a time; each layer and expert is a `jax.checkpoint`; the loss runs
over blocks of tokens. `compute` other than "f32" (the controls of PERF.md
section 2) also rounds KDA's decay, state and gates to that precision.

Two pieces can be asked for alone (`compare/kimi_lora_rounds.py` holds the
program's to them, array against array): `kda_core`, steps 2 and 4 from the
convolved q~, k~, v, g and beta to o, and `make_mixer(sizes)`, one layer's
mixer (KDA or MLA, adapters and all) on a given input.

**FLOPs (`layers`)**: frozen matrices at two thirds of their forward
operations, as `deepseek_v2_lite.py` and for its reason; adapters and the
attention core in full; the KDA core by `kda_flops`, what the RECURRENCE needs
(a chunked program multiplies more: that is not counted); a routed expert's
matrices `times` = tokens x k x held / routed, the EXPECTATION under a
balanced router (what a traced run routed to the held experts is in its
`moe_load` events, and `moe.held_experts_roofline` reads that).
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp

from . import common as c
from .deepseek_v2_lite import (LOSS_BLOCK, PAD_ID, Outputs,  # noqa: F401
                               _rms_norm, attention_flops, loss, published)

STRETCH = 64
L2_EPS = 1e-6


def _is_moe(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"] and i % cfg["moe_layer_freq"] == 0


def _is_kda(cfg: dict, i: int) -> bool:
    """Layer i counted from 0; the published lists count from 1."""
    la = cfg["linear_attn_config"]
    kda, full = i + 1 in la["kda_layers"], i + 1 in la["full_attn_layers"]
    assert kda != full, f"layer {i + 1} must be in exactly one list"
    return kda


def _share(cfg: dict) -> tuple:
    """-> (router outputs, first held expert, experts held)."""
    share = cfg.get("expert_share", {"of": 1, "index": 0})
    held = cfg["num_experts"]
    return held * share["of"], held * share["index"], held


def _kernels(cfg: dict, i: int) -> dict:
    """{path: (cin, cout)} of layer i's 2-D projections (the adapted ones)."""
    d = cfg["hidden_size"]
    if _is_kda(cfg, i):
        la = cfg["linear_attn_config"]
        h, rank = la["num_heads"], la["head_dim"]
        w = h * la["head_dim"]
        out = {("kda", n + "_proj"): (d, w) for n in ("q", "k", "v")}
        out.update({("kda", "f_a_proj"): (d, rank), ("kda", "f_b_proj"): (rank, w),
                    ("kda", "g_a_proj"): (d, rank), ("kda", "g_b_proj"): (rank, w),
                    ("kda", "b_proj"): (d, h), ("kda", "o_proj"): (w, d)})
    else:
        h = cfg["num_attention_heads"]
        nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
        out = {("attn", "q_proj"): (d, h * (nope + rope)),
               ("attn", "kv_a_proj"): (d, cfg["kv_lora_rank"] + rope),
               ("attn", "kv_b_proj"): (cfg["kv_lora_rank"], h * (nope + vd)),
               ("attn", "o_proj"): (h * vd, d)}
    if _is_moe(cfg, i):
        f = cfg["num_shared_experts"] * cfg["moe_intermediate_size"]
        out[("moe", "router")] = (d, _share(cfg)[0])
        ffn = ("moe", "shared")
    else:
        f, ffn = cfg["intermediate_size"], ("mlp",)
    out[ffn + ("gate_proj",)] = (d, f)
    out[ffn + ("up_proj",)] = (d, f)
    out[ffn + ("down_proj",)] = (f, d)
    return out


# ------------------------------------------------------------------ FLOPs

def kda_flops(seq_len: int, heads: int, dk: int, dv: int) -> int:
    """Forward operations a sequence of one layer's KDA core as the
    recurrence needs them, a token and head: the decay of S (dk dv), S^T k,
    the rank-one update and S^T q (2 dk dv each). Norms, gates and the
    convolution are not counted."""
    return seq_len * heads * 7 * dk * dv


def mla_flops(sizes: dict) -> int:
    """`attention_flops` of one MLA layer at these sizes."""
    cfg = published(sizes)
    return attention_flops(
        sizes["seq_len"], cfg["num_attention_heads"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def mixers(sizes: dict) -> dict:
    """{"kda": layers, "mla": layers} of the configuration as it is run."""
    cfg = published(sizes)
    n = sum(_is_kda(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return {"kda": n, "mla": cfg["num_hidden_layers"] - n}


def layers(sizes: dict) -> list[dict]:
    """The layers that multiply (harness/flops.py): a sample is a sequence, a
    matrix is applied `seq_len` times (module docstring)."""
    cfg, t, r = published(sizes), sizes["seq_len"], sizes["lora_rank"]
    la = cfg["linear_attn_config"]
    routed, _, held = _share(cfg)

    def frozen(cin, cout, times=t):
        return {"kind": "frozen_dense", "flops": 4 * cin * cout // 3,
                "times": times}

    out = []
    d = cfg["hidden_size"]
    for i in range(cfg["num_hidden_layers"]):
        for cin, cout in _kernels(cfg, i).values():
            out += [frozen(cin, cout),
                    {"kind": "dense", "cin": cin, "cout": r, "times": t},
                    {"kind": "dense", "cin": r, "cout": cout, "times": t}]
        if _is_kda(cfg, i):
            out.append({"kind": "kda", "flops": kda_flops(
                t, la["num_heads"], la["head_dim"], la["head_dim"])})
        else:
            out.append({"kind": "attention", "flops": mla_flops(sizes)})
        if _is_moe(cfg, i):
            pairs = t * cfg["num_experts_per_token"] * held // routed
            f = cfg["moe_intermediate_size"]
            out += [frozen(d, f, pairs), frozen(d, f, pairs),
                    frozen(f, d, pairs)]
    return out + [frozen(d, cfg["vocab_size"])]


def routed_pairs(sizes: dict, sequences: int) -> int:
    """(token, expert) pairs the router makes for `sequences` sequences, over
    ALL its outputs, held or not: every token of every sequence goes to
    `num_experts_per_token` experts in every expert layer."""
    cfg = published(sizes)
    expert_layers = sum(_is_moe(cfg, i)
                        for i in range(cfg["num_hidden_layers"]))
    return (sequences * sizes["seq_len"] * cfg["num_experts_per_token"]
            * expert_layers)


# ---------------------------------------------------------------- weights

def init(key, sizes: dict) -> dict:
    """{"params": the adapters, "lora_base": the base in `base_dtype`}, laid
    out as the program lays its variables out. Seeded as the configuration's
    `assumed` says: `A_log` log U(1, 16), `dt_bias` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1], the selection bias U(-0.05, 0.05) (not
    zero, so that selecting by s + b and by s differ), `lora_B` small and not
    zero (as `deepseek_v2_lite.py`)."""
    cfg = published(sizes)
    la = cfg["linear_attn_config"]
    dt = jnp.dtype(sizes["base_dtype"])
    r, d, v = sizes["lora_rank"], cfg["hidden_size"], cfg["vocab_size"]
    routed, _, held = _share(cfg)
    f = cfg["moe_intermediate_size"]
    keys = iter(jax.random.split(key, 4 + 48 * cfg["num_hidden_layers"]))

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
        for path, (cin, cout) in _kernels(cfg, i).items():
            put(layer, path + ("kernel",), weight((cin, cout), cin))
            put(adapt, path + ("kernel",), {
                "lora_A": c.scaled_normal(next(keys), (cin, r), cin),
                "lora_B": sizes["lora_b_std"] * jax.random.normal(
                    next(keys), (r, cout), jnp.float32)})
        layer["input_norm"] = {"scale": ones(d)}
        layer["post_norm"] = {"scale": ones(d)}
        if _is_kda(cfg, i):
            h, taps = la["num_heads"], la["short_conv_kernel_size"]
            w = h * la["head_dim"]
            for n in ("q", "k", "v"):
                layer["kda"][n + "_conv"] = weight((taps, w), taps)
            layer["kda"]["A_log"] = jnp.log(jax.random.uniform(
                next(keys), (h,), jnp.float32, 1.0, 16.0)).astype(dt)
            step = jnp.exp(jax.random.uniform(
                next(keys), (w,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            layer["kda"]["dt_bias"] = (step + jnp.log(-jnp.expm1(-step))
                                       ).astype(dt)
            layer["kda"]["o_norm"] = {"scale": ones(la["head_dim"])}
        else:
            layer["attn"]["kv_norm"] = {"scale": ones(cfg["kv_lora_rank"])}
        if _is_moe(cfg, i):
            layer["moe"]["selection_bias"] = jax.random.uniform(
                next(keys), (routed,), jnp.float32, -0.05, 0.05).astype(dt)
            layer["moe"]["experts_gate"] = weight((held, d, f), d)
            layer["moe"]["experts_up"] = weight((held, d, f), d)
            layer["moe"]["experts_down"] = weight((held, f, d), f)
        base[f"layers_{i}"], adapters[f"layers_{i}"] = layer, adapt
    return {"params": adapters, "lora_base": base}


# ---------------------------------------------------------------- forward

def short_conv(x, taps):
    """y_t = sum_j taps[j] x_{t - (n - 1) + j}: shifted multiply-adds, a
    channel a filter, zeros before the sequence. x [B, T, C]; taps [n, C]."""
    n, t = taps.shape[0], x.shape[1]
    y = jnp.zeros(x.shape, jnp.float32)
    for j in range(n):
        back = n - 1 - j
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        y = y + taps[j].astype(jnp.float32) * shifted.astype(jnp.float32)
    return y.astype(x.dtype)


def delta_rule(q, k, v, g, beta):
    """Step 4, token by token. q, k, g [B, T, H, dk] (q, k normalised);
    v [B, T, H, dv]; beta [B, T, H] -> o [B, T, H, dv]. The state has g's
    dtype. T is padded to the stretch with rows of beta = 0, g = 0."""
    b, t, h, dk = q.shape
    pad = -t % STRETCH
    q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                        for a in (q, k, v, g, beta))
    high = jax.lax.Precision.HIGHEST

    def token(s, x):
        qt, kt, vt, gt, bt = x                      # [B, H, .]
        s = jnp.exp(gt)[..., None] * s              # Diag(alpha) S
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=high))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=high)

    @jax.checkpoint
    def stretch(s, xs):
        return jax.lax.scan(token, s, xs)

    n = (t + pad) // STRETCH
    xs = [jnp.moveaxis(a.astype(g.dtype), 1, 0).reshape(
        (n, STRETCH) + a.shape[:1] + a.shape[2:]) for a in (q, k, v, g, beta)]
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), g.dtype)
    _, o = jax.lax.scan(stretch, s0, tuple(xs))
    o = jnp.moveaxis(o.reshape((t + pad,) + o.shape[2:]), 0, 1)
    return o[:, :t]


def kda_core(q, k, v, g, beta, compute: str = "f32"):
    """Steps 2 and 4: the convolved q~, k~ [B, T, H, dk] normalised a head in
    float32, then the recurrence token by token -> o [B, T, H, dv]. The
    control precisions round the decay and the state too (o comes in the
    state's dtype)."""
    def unit(z):
        z = z.astype(jnp.float32)
        return z * jax.lax.rsqrt(
            jnp.sum(z * z, axis=-1, keepdims=True) + L2_EPS)

    return delta_rule(unit(q) * q.shape[-1] ** -0.5, unit(k), v,
                      g.astype(c.ACT_DTYPE[compute]), beta)


def _pieces(cfg, scale, compute, b, t):
    """The layers' functions for inputs of [b, t, hidden]: `kda`, `attention`
    (x, w, a) -> the mixer's output, `block` (x, w, a) -> the layer's."""
    dt = c.ACT_DTYPE[compute]
    eps = cfg["rms_norm_eps"]
    la = cfg["linear_attn_config"]
    h_n, nope, rope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                           cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    routed, first, held = _share(cfg)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def lin(x, w, a):
        """x W + s (x A) B, the adapters' pair written out."""
        y = c.matmul(x, w["kernel"].astype(jnp.float32), compute)
        low = c.matmul(c.matmul(x, a["kernel"]["lora_A"], compute),
                       a["kernel"]["lora_B"], compute)
        return y + (scale * low).astype(y.dtype)

    def swiglu(x, w, a):
        gate = lin(x, w["gate_proj"], a["gate_proj"])
        return lin(jax.nn.silu(gate) * lin(x, w["up_proj"], a["up_proj"]),
                   w["down_proj"], a["down_proj"])

    def kda(x, w, a):
        hk, dk = la["num_heads"], la["head_dim"]

        def branch(n):
            y = short_conv(lin(x, w[n + "_proj"], a[n + "_proj"]),
                           w[n + "_conv"])
            return jax.nn.silu(y).reshape(b, t, hk, dk)

        q, k, v = branch("q"), branch("k"), branch("v")
        f = lin(lin(x, w["f_a_proj"], a["f_a_proj"]), w["f_b_proj"],
                a["f_b_proj"])
        g = (-jnp.exp(w["A_log"].astype(jnp.float32))[:, None]
             * jax.nn.softplus(f.astype(jnp.float32)
                               + w["dt_bias"].astype(jnp.float32)
                               ).reshape(b, t, hk, dk))
        beta = jax.nn.sigmoid(
            lin(x, w["b_proj"], a["b_proj"]).astype(jnp.float32))
        o = kda_core(q, k, v, g, beta, compute).astype(dt)
        gate = lin(lin(x, w["g_a_proj"], a["g_a_proj"]), w["g_b_proj"],
                   a["g_b_proj"])
        o = _rms_norm(o, w["o_norm"]["scale"], eps) * jax.nn.sigmoid(
            gate.astype(jnp.float32)).astype(dt).reshape(b, t, hk, dk)
        return lin(o.reshape(b, t, hk * dk), w["o_proj"], a["o_proj"])

    def attention(x, w, a):
        q = lin(x, w["q_proj"], a["q_proj"]).reshape(b, t, h_n, nope + rope)
        kv_a = lin(x, w["kv_a_proj"], a["kv_a_proj"])
        c_kv, k_pe = (kv_a[..., :cfg["kv_lora_rank"]],
                      kv_a[..., cfg["kv_lora_rank"]:])
        kv = lin(_rms_norm(c_kv, w["kv_norm"]["scale"], eps), w["kv_b_proj"],
                 a["kv_b_proj"]).reshape(b, t, h_n, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, :, None, :], (b, t, h_n, rope))], axis=-1)
        v = kv[..., nope:]

        @jax.checkpoint
        def head(qkv):
            qh, kh, vh = qkv                        # [B, T, .]
            s = jnp.einsum("bqd,bkd->bqk", c.operand(qh, compute),
                           c.operand(kh, compute),
                           precision=c.precision(compute),
                           preferred_element_type=jnp.float32
                           ) * (nope + rope) ** -0.5
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", c.operand(p, compute),
                              c.operand(vh, compute),
                              precision=c.precision(compute),
                              preferred_element_type=dt)

        o = jax.lax.map(head, tuple(jnp.moveaxis(z, 2, 0) for z in (q, k, v)))
        return lin(jnp.moveaxis(o, 0, 2).reshape(b, t, h_n * vd),
                   w["o_proj"], a["o_proj"])

    def experts(x, w, a):
        """x [N, hidden] -> sum over the chosen HELD experts, by a loop."""
        k = cfg["num_experts_per_token"]
        logits = lin(x.astype(jnp.float32), w["router"], a["router"])
        if cfg["moe_router_activation_func"] == "sigmoid":
            s = jax.nn.sigmoid(logits.astype(jnp.float32))
        else:
            s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        sel = s + w["selection_bias"].astype(jnp.float32)
        # the rank of every expert's biased score among a token's
        e = s.shape[-1]
        before = (sel[:, :, None] > sel[:, None, :]) | (
            (sel[:, :, None] == sel[:, None, :])
            & (jnp.arange(e)[:, None] < jnp.arange(e)[None, :]))
        chosen = before.sum(axis=1) < k
        weight = jnp.where(chosen, s, 0.0)
        if cfg["moe_renormalize"]:
            weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
        weight = weight * cfg["routed_scaling_factor"]

        @jax.checkpoint
        def one(wg, wu, wd, m_e):
            f32 = lambda z: z.astype(jnp.float32)  # noqa: E731
            gate = c.matmul(x, f32(wg), compute)
            hid = jax.nn.silu(gate) * c.matmul(x, f32(wu), compute)
            return c.matmul(hid, f32(wd), compute).astype(jnp.float32) * m_e[:, None]

        def step(y, per):
            return y + one(*per), None

        y, _ = jax.lax.scan(
            step, jnp.zeros(x.shape, jnp.float32),
            (w["experts_gate"], w["experts_up"], w["experts_down"],
             weight[:, first:first + held].T))
        return y.astype(x.dtype) + swiglu(x, w["shared"], a["shared"])

    @jax.checkpoint
    def block(x, w, a):
        z = _rms_norm(x, w["input_norm"]["scale"], eps)
        if "kda" in w:
            hid = x + kda(z, w["kda"], a["kda"])
        else:
            hid = x + attention(z, w["attn"], a["attn"])
        z = _rms_norm(hid, w["post_norm"]["scale"], eps)
        if "moe" in w:
            y = experts(z.reshape(b * t, -1), w["moe"], a["moe"]).reshape(z.shape)
        else:
            y = swiglu(z, w["mlp"], a["mlp"])
        return hid + y

    return types.SimpleNamespace(kda=kda, attention=attention, block=block)


def _forward(cfg, scale, compute, base, adapters, tokens):
    """tokens [B, T] -> final-norm states [B, T, hidden]."""
    block = _pieces(cfg, scale, compute, *tokens.shape).block
    x = base["embed"]["embedding"][tokens].astype(c.ACT_DTYPE[compute])
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, base[f"layers_{i}"], adapters[f"layers_{i}"])
    return _rms_norm(x, base["final_norm"]["scale"], cfg["rms_norm_eps"])


def make_mixer(sizes: dict):
    """-> mixer(w, a, x, compute="f32"): ONE layer's mixer alone, KDA where
    the layer's base `w` holds "kda", else MLA; `a` the layer's adapters,
    x [B, T, hidden] what the layer's input norm would hand it."""
    cfg = published(sizes)
    scale = sizes["lora_alpha"] / sizes["lora_rank"]

    def mixer(w, a, x, compute: str = "f32"):
        p = _pieces(cfg, scale, compute, *x.shape[:2])
        x = x.astype(c.ACT_DTYPE[compute])
        if "kda" in w:
            return p.kda(x, w["kda"], a["kda"])
        return p.attention(x, w["attn"], a["attn"])

    return mixer


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
        "this model's forward pass needs its configuration (the layer lists, "
        "the share of experts): bind it with make_apply(sizes), as "
        "benchmarks/compare/lora_rounds.py does")
