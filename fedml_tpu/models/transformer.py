"""Transformer LM for federated next-word prediction — the long-context
model family the LSTM zoo (reference rnn.py) caps at 20-80 token windows.

Uses the pallas flash-attention kernel (fedml_tpu/ops/attention.py) as the
hot op: O(T) memory in BOTH directions — the forward streams K/V blocks
through the online-softmax recurrence and the blocked backward recomputes
p tile-by-tile from the saved logsumexp (validated on-chip: a causal
T=8192 bf16 train step runs where a dense score matrix would need
~270 MB per (batch, head)). Across chips the same blocks compose with
`fedml_tpu.parallel.sequence.ring_attention` (sequence sharded over a
mesh axis). Pre-norm blocks, learned positional embeddings, per-position logits
(NWPTrainer-compatible, like RNN_StackOverFlow)."""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.ops.attention import flash_attention
from fedml_tpu.parallel.activations import constrain


class _Block(nn.Module):
    d_model: int
    heads: int
    mlp_ratio: int = 4
    # compute dtype for qkv/proj/mlp matmuls AND the flash kernel (which
    # follows q/k/v dtype); params stay f32, LayerNorm math promotes to f32
    dtype: object = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        b, t, dm = x.shape
        hd = dm // self.heads
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        qkv = nn.Dense(3 * dm, use_bias=False, dtype=self.dtype, name="qkv")(h)
        # activation-sharding hooks (identity outside a scope): the qkv /
        # attention-context / MLP-hidden intermediates are where Megatron
        # column/row splits keep the channel dim on the mesh's tensor axis
        qkv = constrain(qkv, "attn_qkv")
        q, k, v = jnp.split(qkv.reshape(b, t, 3 * self.heads, hd),
                            3, axis=2)  # each [B, T, H, hd]
        attn = flash_attention(q, k, v, True)
        attn = constrain(attn.reshape(b, t, dm), "attn_ctx")
        x = x + nn.Dense(dm, use_bias=False, dtype=self.dtype, name="proj")(attn)
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        h = nn.gelu(nn.Dense(self.mlp_ratio * dm, dtype=self.dtype,
                             name="mlp_up")(h))
        h = constrain(h, "mlp_hidden")
        return x + nn.Dense(dm, dtype=self.dtype, name="mlp_down")(h)


class TransformerLM(nn.Module):
    vocab_size: int = 10004
    d_model: int = 128
    heads: int = 4
    num_layers: int = 2
    max_len: int = 512
    dtype: object = None

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        b, t = tokens.shape
        if t > self.max_len:
            # fail loudly: the gather would silently clamp every position
            # past max_len onto the last positional embedding row
            raise ValueError(f"sequence length {t} exceeds max_len "
                             f"{self.max_len}; raise max_len")
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name="tok_emb")(tokens)
        pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                       name="pos_emb")(jnp.arange(t)[None, :])
        x = x + pos
        for i in range(self.num_layers):
            x = _Block(self.d_model, self.heads, dtype=self.dtype,
                       name=f"block{i}")(x, train)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                          name="lm_head")(x)
        # the (b, t, vocab) logits are the step's biggest activation; vocab
        # stays sharded into the loss (GSPMD reduces the CE over shards)
        return constrain(logits, "logits")
