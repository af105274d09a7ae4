"""Pieces every plain reference model shares: how a layer computes in a given
precision, and the one key derivation of the program's model library (flax)
that is part of what a round computes (which units dropout zeroes).

`compute` names the arithmetic of the matrix products:
  "f32"  float32 operands at `highest` MXU precision (the reference proper)
  "bf16" operands and activations rounded to bfloat16, f32 accumulation
  "fp8"  operands rounded to float8_e4m3fn, then multiplied as bfloat16
The two lower ones exist for the controls of `correct` (PERF.md section 2).
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

ACT_DTYPE = {"f32": jnp.float32, "bf16": jnp.bfloat16, "fp8": jnp.bfloat16}


def operand(a, compute: str):
    """An operand of a matrix product, rounded as `compute` says."""
    if compute == "fp8":
        return a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    return a.astype(ACT_DTYPE[compute])


def precision(compute: str):
    return jax.lax.Precision.HIGHEST if compute == "f32" else None


def conv(x, kernel, compute: str, stride: int = 1, pad: int = 0):
    """NHWC convolution with an HWIO kernel, no bias."""
    return jax.lax.conv_general_dilated(
        operand(x, compute), operand(kernel, compute), (stride, stride),
        ((pad, pad), (pad, pad)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision(compute),
        preferred_element_type=ACT_DTYPE[compute])


def matmul(x, kernel, compute: str):
    return jnp.dot(operand(x, compute), operand(kernel, compute),
                   precision=precision(compute),
                   preferred_element_type=ACT_DTYPE[compute])


def dense(x, kernel, bias, compute: str):
    y = matmul(x, kernel, compute)
    return y + bias.astype(y.dtype)


def module_key(key, *path_and_count):
    """The key flax hands a submodule's `make_rng`: the apply-time key with
    the SHA-1 of the module path and call count folded in
    (flax.core.scope._fold_in_static, flax 0.12, `flax_fix_rng_separator`
    off). Written out here because WHICH units a dropout layer zeroes
    decides the loss to the first digit, so the reference has to draw the
    masks the program draws."""
    m = hashlib.sha1()
    for part in path_and_count:
        if isinstance(part, str):
            m.update(part.encode("utf-8"))
        else:
            m.update(part.to_bytes((part.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def dropout(x, rate: float, key):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, p=keep, shape=x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def softmax_xent(logits, labels):
    """Softmax cross-entropy of integer labels over the last axis."""
    logits = logits - jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.log(jnp.exp(logits).sum(axis=-1)) - picked


def scaled_normal(key, shape, fan_in: int):
    return jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
