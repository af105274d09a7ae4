"""`MatmulConv`: an `nn.Conv` whose training-step call is patches x matrix.

Under the client `vmap` every `nn.Conv` with per-client weights becomes a
convolution with `feature_group_count` = lanes forward and a batch-grouped
convolution for the weight gradient. On the chip those hold 76 % of
`flagship.train`'s device time at 8-14 % of the MXU (PERF.md section 6, PR 35).
A `dot_general` under the same `vmap` becomes a batched matrix product with the
client as its batch dimension, so `MatmulConv(...)(x, as_matmul=True)` writes
the convolution as matrix products:

  * the input is viewed [H, W, B, C] (rows of the batch next to the channels,
    the two window dimensions outermost, where a shifted slice is an offset);
  * the `kw` column taps are concatenated on the channel axis (K = kw * cin)
    and each of the `kh` row taps is one `dot_general` against
    `kernel[i].reshape(K, cout)`, accumulated. All `kh * kw` taps on the
    channel axis, one product a convolution, moves three times the bytes
    through 32-lane pieces and lost on the chip;
  * a one-channel float32 input (a first layer over greyscale images) has
    nothing to contract: its `kh * kw` taps are broadcast multiply-adds, exact
    in float32. A narrower dtype keeps the products, whose sums the MXU
    carries in float32 where elementwise sums would round to the dtype.

No `lax.conv_general_dilated_patches` (it is a convolution itself) and no
precision of its own: like `nn.Conv` the products follow the context
(`jax.default_matmul_precision`) and the module's `dtype`.

`as_matmul=False` (the default) IS `nn.Conv.__call__`: the eval programs run
shared weights over hundreds of rows a client, where XLA's ordinary convolution
is good and materialised patches would not fit. Parameters (`kernel`
[kh, kw, cin, features], `bias`), their initialisers and the dtype promotion
are `nn.Conv`'s, so variables trees, checkpoints and the LoRA wrap are
interchangeable between the two calls and with `nn.Conv` itself.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype


def _same_padding(k: int) -> tuple[int, int]:
    """lax's SAME at stride 1: k - 1 in all, the odd one after."""
    return (k - 1) // 2, k - 1 - (k - 1) // 2


def _ones(v) -> bool:
    """A stride or dilation as `nn.Conv` takes it (None, an int, a sequence)
    that says 1 everywhere."""
    return v is None or v == 1 or (
        not isinstance(v, int) and all(i == 1 for i in v))


def conv_as_matmul(x, kernel, bias=None, padding: str = "VALID"):
    """Stride-1 2-D convolution of `x` [B, H, W, cin] with `kernel`
    [kh, kw, cin, cout] as matrix products -> [B, oh, ow, cout]; the forms
    and what each read on the chip are in PERF.md section 6, PR 35, step 0."""
    kh, kw, cin, cout = kernel.shape
    if padding == "SAME":
        x = jnp.pad(x, ((0, 0), _same_padding(kh), _same_padding(kw), (0, 0)))
    elif padding != "VALID":
        raise ValueError(f"conv_as_matmul takes padding VALID or SAME: {padding!r}")
    xt = x.transpose(1, 2, 0, 3)
    oh, ow = xt.shape[0] - kh + 1, xt.shape[1] - kw + 1
    if cin == 1 and x.dtype == jnp.float32:
        y = sum(xt[i:i + oh, j:j + ow] * kernel[i, j, 0]
                for i in range(kh) for j in range(kw))
    else:
        cols = jnp.concatenate([xt[:, j:j + ow] for j in range(kw)], axis=-1)
        y = sum(jnp.einsum("hwbk,kf->hwbf", cols[i:i + oh],
                           kernel[i].reshape(kw * cin, cout))
                for i in range(kh))
    if bias is not None:
        y = y + bias
    return y.transpose(2, 0, 1, 3)


class MatmulConv(nn.Conv):
    """`nn.Conv` (2-D, stride 1, VALID or SAME) with a second lowering:
    `as_matmul=True` computes the same convolution as matrix products."""

    @nn.compact
    def __call__(self, x, as_matmul: bool = False):
        if not as_matmul:
            return super().__call__(x)
        plain = (x.ndim == 4 and len(self.kernel_size) == 2
                 and all(_ones(v) for v in (self.strides, self.input_dilation,
                                            self.kernel_dilation))
                 and self.feature_group_count == 1 and self.mask is None)
        if not plain:
            raise ValueError(
                "MatmulConv(as_matmul=True) takes [B, H, W, C] at stride 1, "
                "no dilation, groups or mask")
        kernel = self.param(
            "kernel", self.kernel_init,
            tuple(self.kernel_size) + (x.shape[-1], self.features),
            self.param_dtype)
        bias = (self.param("bias", self.bias_init, (self.features,),
                           self.param_dtype) if self.use_bias else None)
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        return conv_as_matmul(x, kernel, bias, self.padding)
