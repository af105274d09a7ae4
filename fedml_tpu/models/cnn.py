"""FedAvg-paper CNNs (reference fedml_api/model/cv/cnn.py), flax/NHWC.

  CNN_OriginalFedAvg  <- cnn.py:8  (McMahan et al. 2016; 1,663,370 params with
                         only_digits=True — verified by tests)
  CNN_DropOut         <- cnn.py:77 (Reddi et al. "Adaptive Federated
                         Optimization" EMNIST CNN; 1,199,882 params digits)
  CNNCifar            <- cnn.py:243 (small CIFAR CNN)

Inputs are NHWC [b, 28, 28, 1] / [b, 32, 32, 3] — the TPU-native layout
(channels-last feeds the MXU without transposes).

Which XLA operation CNN_DropOut's convolutions become is decided by the
``train`` argument it already receives (`ops/matmul_conv.py`). A training step
runs under the client vmap with per-client weights, where an `nn.Conv` is a
grouped convolution that holds most of the flagship's device time at a tenth
of the MXU; there ``conv2d_1`` / ``conv2d_2`` are written as matrix products,
which the vmap turns into batched `dot_general`s with the client as the batch
dimension. Eval (``train=False``) runs shared weights over hundreds of rows a
client: XLA's ordinary convolution is good there and materialised patches
would not fit, so it stays `lax.conv_general_dilated`, the jaxpr of `nn.Conv`.
Same parameters, same precision (the context's) either way. The other models
here are in no benchmark cell and keep `nn.Conv`.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.ops.matmul_conv import MatmulConv


class CNN_OriginalFedAvg(nn.Module):
    """2x(5x5 conv SAME + 2x2 maxpool) -> 512 dense -> out.

    ``dtype`` sets the activation/compute dtype (bfloat16 feeds the MXU at
    full rate; parameters stay float32)."""

    output_dim: int = 10
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        x = nn.relu(nn.Conv(32, (5, 5), padding="SAME", dtype=self.dtype, name="conv2d_1")(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.relu(nn.Conv(64, (5, 5), padding="SAME", dtype=self.dtype, name="conv2d_2")(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(512, dtype=self.dtype, name="linear_1")(x))
        return nn.Dense(self.output_dim, dtype=self.dtype, name="linear_2")(x).astype(jnp.float32)


class CNN_DropOut(nn.Module):
    """3x3 VALID convs 32/64 -> maxpool -> drop .25 -> 128 dense -> drop .5 -> out.

    The flagship cross-device model (FEMNIST 84.9% target, BASELINE.md).
    ``dtype`` = activation/compute dtype (bfloat16 for the MXU fast path;
    params stay float32, logits are cast back to float32)."""

    output_dim: int = 10
    dtype: Any = jnp.float32
    # reference rates
    drop1: float = 0.25
    drop2: float = 0.5

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        x = nn.relu(MatmulConv(32, (3, 3), padding="VALID", dtype=self.dtype, name="conv2d_1")(x, as_matmul=train))
        x = nn.relu(MatmulConv(64, (3, 3), padding="VALID", dtype=self.dtype, name="conv2d_2")(x, as_matmul=train))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Dropout(self.drop1, deterministic=not train)(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128, dtype=self.dtype, name="linear_1")(x))
        x = nn.Dropout(self.drop2, deterministic=not train)(x)
        return nn.Dense(self.output_dim, dtype=self.dtype, name="linear_2")(x).astype(jnp.float32)


class HAR_CNN(nn.Module):
    """UCI-HAR 1-D CNN (reference fedml_api/model/linear/har_cnn.py:49-84):
    two 1-D convs 32ch k3 (VALID), dropout .5, maxpool/2, fc 100 -> classes.

    Input [b, seq, channels] (reference is [b, chan, seq] — NHWC analog here).
    The reference applies a final Softmax before CrossEntropyLoss (a known
    quirk); we emit raw logits, the correct formulation."""

    output_dim: int = 6
    dtype: Any = None  # compute dtype (bf16 = MXU-native); params stay f32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.relu(nn.Conv(32, (3,), padding="VALID", dtype=self.dtype, name="conv1")(x))
        x = nn.relu(nn.Conv(32, (3,), padding="VALID", dtype=self.dtype, name="conv2")(x))
        x = nn.Dropout(0.5, deterministic=not train)(x)
        x = nn.max_pool(x, (2,), strides=(2,))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(100, dtype=self.dtype, name="lin3")(x))
        x = nn.Dropout(0.5, deterministic=not train)(x)
        return nn.Dense(self.output_dim, dtype=self.dtype, name="lin4")(x)


class CNNCifar(nn.Module):
    """Small CIFAR CNN (reference cnn.py:243): conv6/16 5x5 + pools, fc 120/84."""

    output_dim: int = 10
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.max_pool(nn.relu(nn.Conv(6, (5, 5), padding="VALID", dtype=self.dtype, name="conv1")(x)), (2, 2), strides=(2, 2))
        x = nn.max_pool(nn.relu(nn.Conv(16, (5, 5), padding="VALID", dtype=self.dtype, name="conv2")(x)), (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(120, dtype=self.dtype, name="fc1")(x))
        x = nn.relu(nn.Dense(84, dtype=self.dtype, name="fc2")(x))
        return nn.Dense(self.output_dim, dtype=self.dtype, name="fc3")(x)
