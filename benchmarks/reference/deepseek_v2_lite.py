"""DeepSeek-V2 (arXiv:2405.04434) as a FROZEN base under rank-r adapters,
plain `jax.numpy`: the decoder of the published `modeling_deepseek.py`,
training path, with LoRA written out as x W + s (x A) B on every 2-D kernel
but the head. Nothing here imports the program.

    h   = x + MLA(RMSNorm(x));  out = h + FFN(RMSNorm(h));  final RMSNorm
    MLA: q = lin(x) -> heads x (nope | rope)
         [c_kv | k_rope] = lin(x);  [k_nope | v] = lin(RMSNorm(c_kv))
         rotary on q_rope and on the one k_rope all heads share, over YaRN's
         inverse frequencies; the FULL causal score matrix
         softmax(q k^T * (nope + rope)^-0.5 * m^2), m = 0.1 * mscale_all_dim
         * ln(factor) + 1;  o = lin(p v)
    FFN: the first `first_k_dense_replace` layers a SwiGLU; after them
         s = softmax(lin_f32(x)) over the experts, the k largest chosen (ties
         to the lower index), weights the chosen s as they are; the experts
         by a LOOP over all of them, each applied to every token and
         multiplied by the token's 0/1 choice times s; plus one shared SwiGLU
    loss: softmax cross-entropy of the next token, pad id 0 left out

`seq_aux` (the load-balance loss) is left out, as in the program: the routed
experts are frozen. So that it fits beside a 5.68 GB base: the base stays in
its stored dtype (bfloat16) and is cast a layer (an expert) at a time, each
layer and each expert of the loop is a `jax.checkpoint`, and the loss runs
over blocks of tokens. None of that changes a value.

The sizes are the configuration file's published keys (`sizes["config"]`
names the file, relative to the repository's root); `sizes` adds what no
published key says: `seq_len`, `lora_rank`, `lora_alpha`, `base_dtype`,
`lora_b_std`. `init` draws `lora_B` small and NOT zero: the harness hands the
same weights to both sides, and with B = 0 round 0's gradient of every A is
nought, so that `grad_gap` would compare nothing on half the leaves.

**FLOPs of a frozen base (`layers`).** `harness/flops.py` counts training
as 3 x forward for every layer; a frozen matrix needs 2 x (forward and the
activation gradient, never the weight gradient). Until a `benchmark` PR
teaches `flops.py` a frozen layer, every frozen matrix here carries its own
`flops` of two thirds of its forward operations, so that 3 x gives 2 x; the
adapters and the attention core count in full.
"""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp

from . import common as c

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PAD_ID = 0
LOSS_BLOCK = 1024


def published(sizes: dict) -> dict:
    with open(os.path.join(ROOT, sizes["config"])) as f:
        return json.load(f)


def _is_moe(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"] and i % cfg["moe_layer_freq"] == 0


def _kernels(cfg: dict, i: int) -> dict:
    """{path: (cin, cout)} of layer i's 2-D kernels."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    out = {
        ("attn", "q_proj"): (d, h * (nope + rope)),
        ("attn", "kv_a_proj"): (d, cfg["kv_lora_rank"] + rope),
        ("attn", "kv_b_proj"): (cfg["kv_lora_rank"], h * (nope + vd)),
        ("attn", "o_proj"): (h * vd, d),
    }
    if _is_moe(cfg, i):
        f = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
        out[("moe", "router")] = (d, cfg["n_routed_experts"])
        ffn = ("moe", "shared")
    else:
        f, ffn = cfg["intermediate_size"], ("mlp",)
    out[ffn + ("gate_proj",)] = (d, f)
    out[ffn + ("up_proj",)] = (d, f)
    out[ffn + ("down_proj",)] = (f, d)
    return out


# ------------------------------------------------------------------ FLOPs

def attention_flops(seq_len: int, heads: int, qk: int, v: int) -> int:
    """Forward operations a sequence of one layer's causal attention core:
    the score product over `qk`-wide and the context product over `v`-wide
    heads (2 per multiply-add), over the seq_len x (seq_len + 1) / 2 pairs
    of positions the mask leaves. The softmax is not counted."""
    return 2 * heads * (qk + v) * seq_len * (seq_len + 1) // 2


def layers(sizes: dict) -> list[dict]:
    """The layers that multiply (harness/flops.py): a sample is a sequence,
    a matrix is applied `seq_len` times, a routed expert's on the
    `num_experts_per_tok` pairs a token makes. Frozen matrices carry two
    thirds of their forward operations (module docstring)."""
    cfg, t, r = published(sizes), sizes["seq_len"], sizes["lora_rank"]

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
        out.append({"kind": "attention", "flops": attention_flops(
            t, cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])})
        if _is_moe(cfg, i):
            pairs, f = t * cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]
            out += [frozen(d, f, pairs), frozen(d, f, pairs),
                    frozen(f, d, pairs)]
    return out + [frozen(d, cfg["vocab_size"])]


def routed_pairs(sizes: dict, sequences: int) -> int:
    """(token, expert) pairs a dropless model routes for `sequences`
    sequences: every token of every sequence, pads and filler rows too,
    goes to `num_experts_per_tok` experts in every expert layer."""
    cfg = published(sizes)
    expert_layers = sum(_is_moe(cfg, i)
                        for i in range(cfg["num_hidden_layers"]))
    return (sequences * sizes["seq_len"] * cfg["num_experts_per_tok"]
            * expert_layers)


# ---------------------------------------------------------------- weights

def init(key, sizes: dict) -> dict:
    """{"params": the adapters, "lora_base": the base in `base_dtype`}, laid
    out as the program lays its variables out."""
    cfg = published(sizes)
    dt = jnp.dtype(sizes["base_dtype"])
    r, d, v = sizes["lora_rank"], cfg["hidden_size"], cfg["vocab_size"]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    keys = iter(jax.random.split(key, 4 + 40 * cfg["num_hidden_layers"]))

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
        layer["attn"]["kv_norm"] = {"scale": ones(cfg["kv_lora_rank"])}
        if _is_moe(cfg, i):
            layer["moe"]["experts_gate"] = weight((e, d, f), d)
            layer["moe"]["experts_up"] = weight((e, d, f), d)
            layer["moe"]["experts_down"] = weight((e, f, d), f)
        base[f"layers_{i}"], adapters[f"layers_{i}"] = layer, adapt
    return {"params": adapters, "lora_base": base}


# ---------------------------------------------------------------- forward

def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.square(x32).mean(axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def yarn_inv_freq(cfg: dict):
    """Rotary inverse frequencies [rope / 2]: the trained ones where a
    dimension turns more than `beta_fast` times over the original context,
    divided by `factor` where it turns less than `beta_slow` times, a linear
    ramp between the two correction dimensions."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    pos = [base ** (2 * j / dim) for j in range(dim // 2)]

    def correction(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for j, p in enumerate(pos):
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append((1.0 - ramp) / p + ramp / (rs["factor"] * p))
    return jnp.asarray(out, jnp.float32)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rotary(x, angles, ratio):
    """x [.., T, H, rope] holding the pairs (x0, x1), (x2, x3), ..: pair j
    turns by angles[t, j]; the result holds the pairs' first members, then
    their second members (the source's layout)."""
    a, b = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    cos = (jnp.cos(angles) * ratio)[:, None, :]
    sin = (jnp.sin(angles) * ratio)[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _forward(cfg, scale, compute, base, adapters, tokens):
    """tokens [B, T] -> final-norm states [B, T, hidden]."""
    dt = c.ACT_DTYPE[compute]
    eps = cfg["rms_norm_eps"]
    h_n, nope, rope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                           cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rs = cfg["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    sm_scale = (nope + rope) ** -0.5 * m * m
    ratio = _mscale(rs["factor"], rs["mscale"]) / m
    b, t = tokens.shape
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
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

    def attention(x, w, a):
        q = lin(x, w["q_proj"], a["q_proj"]).reshape(b, t, h_n, nope + rope)
        kv_a = lin(x, w["kv_a_proj"], a["kv_a_proj"])
        c_kv, k_rope = (kv_a[..., :cfg["kv_lora_rank"]],
                        kv_a[..., cfg["kv_lora_rank"]:])
        kv = lin(_rms_norm(c_kv, w["kv_norm"]["scale"], eps), w["kv_b_proj"],
                 a["kv_b_proj"]).reshape(b, t, h_n, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q = jnp.concatenate(
            [q[..., :nope], _rotary(q[..., nope:], angles, ratio)], axis=-1)
        k_rope = _rotary(k_rope[:, :, None, :], angles, ratio)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, t, h_n, rope))], axis=-1)
        s = jnp.einsum("bqhd,bkhd->bhqk", c.operand(q, compute),
                       c.operand(k, compute), precision=c.precision(compute),
                       preferred_element_type=jnp.float32) * sm_scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", c.operand(p, compute),
                       c.operand(v, compute), precision=c.precision(compute),
                       preferred_element_type=dt)
        return lin(o.reshape(b, t, h_n * vd), w["o_proj"], a["o_proj"])

    def experts(x, w, a):
        """x [N, hidden] -> sum over the chosen experts, by a loop over all."""
        k = cfg["num_experts_per_tok"]
        logits = lin(x.astype(jnp.float32), w["router"], a["router"])
        s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        # the rank of every expert's score among a token's: chosen if under k
        e = s.shape[-1]
        before = (s[:, :, None] > s[:, None, :]) | (
            (s[:, :, None] == s[:, None, :])
            & (jnp.arange(e)[:, None] < jnp.arange(e)[None, :]))
        chosen = before.sum(axis=1) < k
        weight = jnp.where(chosen, s, 0.0)
        if cfg["norm_topk_prob"]:
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
            (w["experts_gate"], w["experts_up"], w["experts_down"], weight.T))
        return y.astype(x.dtype) + swiglu(x, w["shared"], a["shared"])

    @jax.checkpoint
    def block(x, w, a):
        hid = x + attention(_rms_norm(x, w["input_norm"]["scale"], eps),
                            w["attn"], a["attn"])
        z = _rms_norm(hid, w["post_norm"]["scale"], eps)
        if "moe" in w:
            y = experts(z.reshape(b * t, -1), w["moe"], a["moe"]).reshape(z.shape)
        else:
            y = swiglu(z, w["mlp"], a["mlp"])
        return hid + y

    x = base["embed"]["embedding"][tokens].astype(dt)
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, base[f"layers_{i}"], adapters[f"layers_{i}"])
    return _rms_norm(x, base["final_norm"]["scale"], eps)


@jax.tree_util.register_pytree_node_class
class Outputs:
    """What `apply` hands `loss`: the final states and the head's kernel (the
    logits are made a block of tokens at a time), and how to multiply."""

    def __init__(self, states, head, compute):
        self.states, self.head, self.compute = states, head, compute

    def tree_flatten(self):
        return (self.states, self.head), self.compute

    @classmethod
    def tree_unflatten(cls, compute, children):
        return cls(*children, compute)


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
        "this model's forward pass needs its configuration (rotary scaling, "
        "experts a token): bind it with make_apply(sizes), as "
        "benchmarks/compare/lora_rounds.py does")


def loss(outputs: Outputs, y, mask):
    """-> (mean over the tokens that count, sum of their losses f32, their
    number f32): the next tokens of the batch's real rows that are not the
    pad. The head and the cross-entropy run over blocks of tokens."""
    states, compute = outputs.states, outputs.compute
    n = y.size
    counts = ((y != PAD_ID) & mask[:, None]).astype(jnp.float32).reshape(n)
    block = min(LOSS_BLOCK, n)
    pad = -n % block
    parts = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (-1, block) + a.shape[1:])
        for a in (states.reshape(n, -1), y.reshape(n), counts)]

    @jax.checkpoint
    def one(part):
        hb, yb, cb = part
        logits = c.matmul(hb, outputs.head.astype(jnp.float32), compute)
        return (c.softmax_xent(logits.astype(jnp.float32), yb) * cb).sum()

    loss_sum, total = jax.lax.map(one, parts).sum(), counts.sum()
    return loss_sum / jnp.maximum(total, 1.0), loss_sum, total
