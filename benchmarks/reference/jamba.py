"""Jamba (arXiv:2403.19887; the Mamba-1 layer, arXiv:2312.00752) as a FROZEN
base under rank-r adapters, plain `jax.numpy` in float32 at `highest`: the
decoder of the published `modeling_jamba.py`, training path, with LoRA
written out as x W + s (x A) B on every 2-D projection. Nothing here imports
the program (the loss, the norm and the attention count are
`reference/deepseek_v2_lite.py`'s, the short convolution
`reference/kimi_linear.py`'s).

    h = x + Mixer_l(RMSNorm(x));  out = h + SwiGLU(RMSNorm(h));  final RMSNorm
    logits = out E^T, E the embedding (tied)
    Mixer_l: attention where l % attn_layer_period == attn_layer_offset (l
      from 0), else Mamba
    attention: q = lin(x) as H heads, k, v = lin(x) as `num_key_value_heads`
      heads of hidden / H, a query head reading key-value head
      h // (H / kv); no positional encoding; the FULL causal score matrix
      softmax(q k^T head_dim^-1/2)
    Mamba (D_in = mamba_expand x hidden, N = mamba_d_state, R = mamba_dt_rank):
      1. [x, z] = lin(u); x = silu(conv(x) + b_conv), the convolution depthwise
         and causal over `mamba_d_conv` taps (FOUR SHIFTED MULTIPLY-ADDS)
      2. [dt, B, C] = lin(x), each through an RMSNorm of its own
      3. delta = softplus(lin(dt) + b_dt); A = -exp(A_log) [D_in, N]
      4. TOKEN BY TOKEN, h_0 = 0 in R^(D_in x N):
         h_t = exp(delta_t A) h_{t-1} + delta_t x_t B_t;  y_t = h_t C_t
      5. out = lin((y + D x) * silu(z))
    loss: softmax cross-entropy of the next token, pad id 0 left out

Departures, none of which changes a value: step 4 is a `lax.scan` over tokens
inside a checkpointed scan over stretches of 64 tokens (a scan differentiated
whole would keep every token's state, 2.7 GB a layer and sequence of 8,192),
and the score matrix is made a head at a time (`lax.map`), so that the
backward pass fits; the base stays in its stored dtype and is cast a layer
at a time; each layer is a `jax.checkpoint`; the loss runs over blocks of
tokens. `compute` other than "f32" (the controls of PERF.md section 2) also
keeps the state of step 4 in that precision.

Two pieces can be asked for alone (`compare/jamba_lora_rounds.py` holds the
program's to them, array against array): `ssm_core`, step 4 from x, delta,
A, B and C to y, and `make_attention(sizes)`, the attention layer's mixer
(adapters and all) on a given input.

**FLOPs (`layers`)**: frozen matrices at two thirds of their forward
operations, as `deepseek_v2_lite.py` and for its reason (the tied head
included: a product of the final states with the embedding); adapters and
the attention core in full; the scan by `ssm_flops`, what the RECURRENCE
needs. `scan_bytes` is what the scan's two kernels need to move, for the
scan's roofline share (`layer_metrics/ssm_scan_roofline.py`).
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp

from . import common as c
from .deepseek_v2_lite import (LOSS_BLOCK, PAD_ID, Outputs,  # noqa: F401
                               _rms_norm, attention_flops, loss, published)
from .kimi_linear import short_conv

STRETCH = 64


def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def _widths(cfg: dict) -> tuple:
    """(D_in, N, R, head_dim)."""
    return (cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"],
            cfg["mamba_dt_rank"],
            cfg["hidden_size"] // cfg["num_attention_heads"])


def _kernels(cfg: dict, i: int) -> dict:
    """{path: (cin, cout)} of layer i's 2-D projections (the adapted ones)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    inner, n, r, hd = _widths(cfg)
    if is_attention(cfg, i):
        h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        out = {("attn", "q_proj"): (d, h * hd), ("attn", "k_proj"): (d, kv * hd),
               ("attn", "v_proj"): (d, kv * hd), ("attn", "o_proj"): (h * hd, d)}
    else:
        out = {("mamba", "in_proj"): (d, 2 * inner),
               ("mamba", "x_proj"): (inner, r + 2 * n),
               ("mamba", "dt_proj"): (r, inner),
               ("mamba", "out_proj"): (inner, d)}
    out.update({("mlp", "gate_proj"): (d, f), ("mlp", "up_proj"): (d, f),
                ("mlp", "down_proj"): (f, d)})
    return out


# ------------------------------------------------------------------ FLOPs

def ssm_flops(seq_len: int, inner: int, n: int) -> int:
    """Forward operations a sequence of one layer's scan as the recurrence
    needs them, a token, channel and state: delta A, the decay's product
    with h, B times delta x and its sum with it, C times h and the sum over
    the states (the exponential is not counted)."""
    return seq_len * inner * n * 6


def scan_bytes(seq_len: int, inner: int, n: int, x_bytes: int) -> int:
    """HBM bytes a sequence of one layer's scan needs to move, forward and
    backward, each pass's inputs read once and outputs written once at the
    configuration's dtypes: forward x, delta, B, C in, y out (float32);
    backward those and dy in, dx, ddelta, dB, dC out (dx in x's dtype). A
    and dA ([D_in, N]) and the forward the backward recomputes are not
    counted."""
    wide, narrow = seq_len * inner, seq_len * n * 4
    forward = wide * (x_bytes + 4 + 4) + 2 * narrow
    backward = wide * (x_bytes + 4 + 4 + x_bytes + 4) + 4 * narrow
    return forward + backward


def mixers(sizes: dict) -> dict:
    """{"mamba": layers, "attention": layers} as the configuration runs."""
    cfg = published(sizes)
    n = sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return {"mamba": cfg["num_hidden_layers"] - n, "attention": n}


def attention_layer_flops(sizes: dict) -> int:
    cfg = published(sizes)
    hd = _widths(cfg)[3]
    return attention_flops(sizes["seq_len"], cfg["num_attention_heads"], hd,
                           hd)


def layers(sizes: dict) -> list[dict]:
    """The layers that multiply (harness/flops.py): a sample is a sequence, a
    matrix is applied `seq_len` times (module docstring)."""
    cfg, t, r = published(sizes), sizes["seq_len"], sizes["lora_rank"]
    inner, n, _, _ = _widths(cfg)

    def frozen(cin, cout):
        return {"kind": "frozen_dense", "flops": 4 * cin * cout // 3,
                "times": t}

    out = []
    for i in range(cfg["num_hidden_layers"]):
        for cin, cout in _kernels(cfg, i).values():
            out += [frozen(cin, cout),
                    {"kind": "dense", "cin": cin, "cout": r, "times": t},
                    {"kind": "dense", "cin": r, "cout": cout, "times": t}]
        if is_attention(cfg, i):
            out.append({"kind": "attention",
                        "flops": attention_layer_flops(sizes)})
        else:
            out.append({"kind": "ssm", "flops": ssm_flops(t, inner, n)})
    return out + [frozen(cfg["hidden_size"], cfg["vocab_size"])]


# ---------------------------------------------------------------- weights

def init(key, sizes: dict) -> dict:
    """{"params": the adapters, "lora_base": the base in `base_dtype`}, laid
    out as the program lays its variables out. Seeded as the configuration's
    `assumed` says: `A_log` = log(1..N) a channel, `D` = 1, `b_dt` the
    inverse softplus of a log-uniform step in [1e-3, 1e-1], `b_conv`
    U(-1/2, 1/2) (a 4-tap filter's fan-in, as PyTorch initialises a
    convolution's bias), `lora_B` small and not zero (as
    `deepseek_v2_lite.py`)."""
    cfg = published(sizes)
    dt = jnp.dtype(sizes["base_dtype"])
    r, d, v = sizes["lora_rank"], cfg["hidden_size"], cfg["vocab_size"]
    inner, n, _, _ = _widths(cfg)
    taps = cfg["mamba_d_conv"]
    keys = iter(jax.random.split(key, 4 + 32 * cfg["num_hidden_layers"]))

    def weight(shape, fan_in):
        return c.scaled_normal(next(keys), shape, fan_in).astype(dt)

    def put(tree, path, leaf):
        for name in path[:-1]:
            tree = tree.setdefault(name, {})
        tree[path[-1]] = leaf

    ones = lambda m: jnp.ones((m,), dt)  # noqa: E731
    base = {"embed": {"embedding": weight((v, d), 1.0) * jnp.asarray(0.02, dt)},
            "final_norm": {"scale": ones(d)}}
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
        if not is_attention(cfg, i):
            m = layer["mamba"]
            m["conv_taps"] = weight((taps, inner), taps)
            m["conv_bias"] = jax.random.uniform(
                next(keys), (inner,), jnp.float32, -0.5, 0.5).astype(dt)
            step = jnp.exp(jax.random.uniform(
                next(keys), (inner,), jnp.float32, math.log(1e-3),
                math.log(1e-1)))
            m["dt_bias"] = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
            m["A_log"] = jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                (inner, n)).astype(dt)
            m["D"] = ones(inner)
            for name, width in (("dt_norm", _widths(cfg)[2]), ("b_norm", n),
                                ("c_norm", n)):
                m[name] = {"scale": ones(width)}
        base[f"layers_{i}"], adapters[f"layers_{i}"] = layer, adapt
    return {"params": adapters, "lora_base": base}


# ---------------------------------------------------------------- forward

def ssm_core(x, delta, A, B, C, compute: str = "f32"):
    """Step 4, token by token. x, delta [b, T, D_in]; A [D_in, N]; B, C
    [b, T, N] -> y [b, T, D_in] in the state's dtype: float32, or the
    control's precision (module docstring). T is padded to the stretch with
    rows of delta = 0, x = 0, which change no state."""
    dt = c.ACT_DTYPE[compute]
    b, t, inner = x.shape
    pad = -t % STRETCH
    x, delta, B, C = (jnp.pad(z.astype(dt), ((0, 0), (0, pad), (0, 0)))
                      for z in (x, delta, B, C))
    A = A.astype(dt)
    high = jax.lax.Precision.HIGHEST

    def token(h, inp):
        xt, dlt, bt, ct = inp                       # [b, .]
        h = (jnp.exp(dlt[..., None] * A) * h
             + (dlt * xt)[..., None] * bt[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, ct, precision=high)

    @jax.checkpoint
    def stretch(h, xs):
        return jax.lax.scan(token, h, xs, unroll=16)

    steps = (t + pad) // STRETCH
    xs = tuple(jnp.moveaxis(z, 1, 0).reshape((steps, STRETCH, b, -1))
               for z in (x, delta, B, C))
    h0 = jnp.zeros((b, inner, A.shape[1]), dt)
    _, y = jax.lax.scan(stretch, h0, xs)
    return jnp.moveaxis(y.reshape(t + pad, b, inner), 0, 1)[:, :t]


def _pieces(cfg, scale, compute, b, t):
    """The layers' functions for inputs of [b, t, hidden]: `mamba`,
    `attention` (x, w, a) -> the mixer's output, `block` -> the layer's."""
    dt = c.ACT_DTYPE[compute]
    eps = cfg["rms_norm_eps"]
    inner, n, r, hd = _widths(cfg)
    h_n, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
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

    def mamba(u, w, a):
        xz = lin(u, w["in_proj"], a["in_proj"])
        x, z = xz[..., :inner], xz[..., inner:]
        x = short_conv(x, w["conv_taps"]).astype(jnp.float32) + w[
            "conv_bias"].astype(jnp.float32)
        x = jax.nn.silu(x).astype(dt)
        p = lin(x, w["x_proj"], a["x_proj"])
        dtr = _rms_norm(p[..., :r], w["dt_norm"]["scale"], eps)
        bb = _rms_norm(p[..., r:r + n], w["b_norm"]["scale"], eps)
        cc = _rms_norm(p[..., r + n:], w["c_norm"]["scale"], eps)
        delta = jax.nn.softplus(
            lin(dtr, w["dt_proj"], a["dt_proj"]).astype(jnp.float32)
            + w["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(w["A_log"].astype(jnp.float32))
        y = ssm_core(x, delta, A, bb, cc, compute).astype(jnp.float32)
        y = (y + w["D"].astype(jnp.float32) * x.astype(jnp.float32)) * (
            jax.nn.silu(z.astype(jnp.float32)))
        return lin(y.astype(dt), w["out_proj"], a["out_proj"])

    def attention(x, w, a):
        q = lin(x, w["q_proj"], a["q_proj"]).reshape(b, t, h_n, hd)
        k = lin(x, w["k_proj"], a["k_proj"]).reshape(b, t, kv, hd)
        v = lin(x, w["v_proj"], a["v_proj"]).reshape(b, t, kv, hd)
        group = jnp.arange(h_n) // (h_n // kv)

        @jax.checkpoint
        def head(qkv):
            qh, kh, vh = qkv                        # [b, t, hd]
            s = jnp.einsum("bqd,bkd->bqk", c.operand(qh, compute),
                           c.operand(kh, compute),
                           precision=c.precision(compute),
                           preferred_element_type=jnp.float32) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", c.operand(p, compute),
                              c.operand(vh, compute),
                              precision=c.precision(compute),
                              preferred_element_type=dt)

        o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0),
                               jnp.moveaxis(k, 2, 0)[group],
                               jnp.moveaxis(v, 2, 0)[group]))
        return lin(jnp.moveaxis(o, 0, 2).reshape(b, t, h_n * hd),
                   w["o_proj"], a["o_proj"])

    @jax.checkpoint
    def block(x, w, a):
        z = _rms_norm(x, w["input_norm"]["scale"], eps)
        if "mamba" in w:
            hid = x + mamba(z, w["mamba"], a["mamba"])
        else:
            hid = x + attention(z, w["attn"], a["attn"])
        return hid + swiglu(_rms_norm(hid, w["post_norm"]["scale"], eps),
                            w["mlp"], a["mlp"])

    return types.SimpleNamespace(mamba=mamba, attention=attention, block=block)


def _forward(cfg, scale, compute, base, adapters, tokens):
    """tokens [B, T] -> final-norm states [B, T, hidden]."""
    block = _pieces(cfg, scale, compute, *tokens.shape).block
    x = base["embed"]["embedding"][tokens].astype(c.ACT_DTYPE[compute])
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, base[f"layers_{i}"], adapters[f"layers_{i}"])
    return _rms_norm(x, base["final_norm"]["scale"], cfg["rms_norm_eps"])


def make_attention(sizes: dict):
    """-> attention(w, a, x, compute="f32"): ONE attention layer's mixer
    alone; `w` the layer's base, `a` its adapters, x [B, T, hidden] what the
    layer's input norm would hand it."""
    cfg = published(sizes)
    scale = sizes["lora_alpha"] / sizes["lora_rank"]

    def attention(w, a, x, compute: str = "f32"):
        x = x.astype(c.ACT_DTYPE[compute])
        return _pieces(cfg, scale, compute, *x.shape[:2]).attention(
            x, w["attn"], a["attn"])

    return attention


def make_apply(sizes: dict):
    """-> apply(variables, x, train, key, compute, mask=None) for a model of
    these sizes: tokens x[B, T] -> (Outputs, {}), the head the embedding's
    transpose. No dropout, no layer that looks across rows: `key` and `mask`
    are unused."""
    cfg = published(sizes)
    scale = sizes["lora_alpha"] / sizes["lora_rank"]

    def apply(variables, x, train, key, compute, mask=None):
        base = variables["lora_base"]
        states = _forward(cfg, scale, compute, base, variables["params"], x)
        return Outputs(states, base["embed"]["embedding"].T, compute), {}

    return apply


def apply(*args, **kwargs):
    raise TypeError(
        "this model's forward pass needs its configuration (the layer "
        "pattern, the widths): bind it with make_apply(sizes), as "
        "benchmarks/compare/lora_rounds.py does")
