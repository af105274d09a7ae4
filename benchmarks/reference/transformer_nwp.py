"""A pre-norm decoder for next-word prediction, plain `jax.numpy`: token and
learned position embeddings, per block LayerNorm -> fused q/k/v projection
(no bias) -> causal softmax attention over heads -> output projection (no
bias), LayerNorm -> GELU (tanh form) MLP of four times the width, a final
LayerNorm and an untied head (no bias). Its loss is softmax cross-entropy
over the next token, the pad id 0 left out. The sizes are the
configuration's (`vocab`, `d_model`, `num_layers`, `max_len`, `seq_len`);
the number of heads shows in no weight's shape, so it is fixed here."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c

PAD_ID = 0
HEADS = 4
MLP_RATIO = 4
LN_EPS = 1e-6


def attention_flops(seq_len: int, d_model: int) -> int:
    """Forward operations a sequence of one layer's causal attention core:
    the score and the context product (2 per multiply-add each), over the
    seq_len x (seq_len + 1) / 2 pairs of positions the mask leaves; the
    heads' sizes add up to d_model. The softmax is not counted."""
    return 2 * 2 * d_model * seq_len * (seq_len + 1) // 2


def layers(sizes: dict) -> list[dict]:
    """The layers that multiply, for the FLOP count (harness/flops.py): a
    sample is one sequence, so a matrix is applied `seq_len` times."""
    d, t = sizes["d_model"], sizes["seq_len"]
    block = [
        {"kind": "dense", "cin": d, "cout": 3 * d, "times": t},
        {"kind": "attention", "flops": attention_flops(t, d)},
        {"kind": "dense", "cin": d, "cout": d, "times": t},
        {"kind": "dense", "cin": d, "cout": MLP_RATIO * d, "times": t},
        {"kind": "dense", "cin": MLP_RATIO * d, "cout": d, "times": t},
    ]
    return block * sizes["num_layers"] + [
        {"kind": "dense", "cin": d, "cout": sizes["vocab"], "times": t}]


def init(key, sizes: dict) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    keys = iter(jax.random.split(key, 3 + 6 * sizes["num_layers"]))

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    params = {
        "tok_emb": {"embedding": 0.02 * jax.random.normal(next(keys), (v, d))},
        "pos_emb": {"embedding": 0.02 * jax.random.normal(
            next(keys), (sizes["max_len"], d))},
        "ln_f": norm(),
        "lm_head": {"kernel": c.scaled_normal(next(keys), (d, v), d)},
    }
    for i in range(sizes["num_layers"]):
        params[f"block{i}"] = {
            "ln1": norm(), "ln2": norm(),
            "qkv": {"kernel": c.scaled_normal(next(keys), (d, 3 * d), d)},
            "proj": {"kernel": c.scaled_normal(next(keys), (d, d), d)},
            "mlp_up": {
                "kernel": c.scaled_normal(next(keys), (d, MLP_RATIO * d), d),
                "bias": 0.02 * jax.random.normal(next(keys), (MLP_RATIO * d,))},
            "mlp_down": {
                "kernel": c.scaled_normal(next(keys), (MLP_RATIO * d, d),
                                          MLP_RATIO * d),
                "bias": 0.02 * jax.random.normal(next(keys), (d,))},
        }
    return {"params": params}


def _layer_norm(x, p):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = jnp.square(x32 - mean).mean(axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, compute: str):
    """Causal softmax attention, q/k/v [B, T, H, hd]; scores and softmax in
    float32."""
    t, hd = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", c.operand(q, compute),
                   c.operand(k, compute), precision=c.precision(compute),
                   preferred_element_type=jnp.float32) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", c.operand(p, compute),
                      c.operand(v, compute), precision=c.precision(compute),
                      preferred_element_type=c.ACT_DTYPE[compute])


def apply(variables, x, train: bool, key, compute: str, mask=None):
    """tokens x[B, T] -> (logits f32 [B, T, vocab], {}). No dropout, no layer
    that looks across rows: `key` and `mask` are unused."""
    p = variables["params"]
    dt = c.ACT_DTYPE[compute]
    b, t = x.shape
    h = (p["tok_emb"]["embedding"][x]
         + p["pos_emb"]["embedding"][jnp.arange(t)][None]).astype(dt)
    for i in range(sum(name.startswith("block") for name in p)):
        blk = p[f"block{i}"]
        qkv = c.matmul(_layer_norm(h, blk["ln1"]), blk["qkv"]["kernel"],
                       compute)
        q, k, v = jnp.split(qkv.reshape(b, t, 3 * HEADS, -1), 3, axis=2)
        ctx = _attention(q, k, v, compute).reshape(b, t, -1)
        h = h + c.matmul(ctx, blk["proj"]["kernel"], compute)
        up = _gelu(c.dense(_layer_norm(h, blk["ln2"]), blk["mlp_up"]["kernel"],
                           blk["mlp_up"]["bias"], compute))
        h = h + c.dense(up, blk["mlp_down"]["kernel"], blk["mlp_down"]["bias"],
                        compute)
    logits = c.matmul(_layer_norm(h, p["ln_f"]), p["lm_head"]["kernel"],
                      compute)
    return logits.astype(jnp.float32), {}


def loss(logits, y, mask):
    """-> (mean over the tokens that count, sum of their losses f32, their
    number f32): the next tokens of the batch's real rows that are not the
    pad."""
    per = c.softmax_xent(logits, y)
    counts = ((y != PAD_ID) & mask[:, None]).astype(jnp.float32)
    loss_sum, total = (per * counts).sum(), counts.sum()
    return loss_sum / jnp.maximum(total, 1.0), loss_sum, total
