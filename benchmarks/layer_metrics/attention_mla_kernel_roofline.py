"""`attention.mla_kernel_roofline`: `attention.kernel_roofline`'s helper over
the configuration's own count of its latent-attention layers (a model whose
mixer differs a layer: not every layer is attention)."""

import importlib

from benchmarks.layer_metrics.attention_kernel_roofline import roofline


def needed_flops(config: dict, sequences: int) -> int:
    ref = importlib.import_module("benchmarks.reference." + config["reference"])
    sizes = config["sizes"]
    return 3 * ref.mla_flops(sizes) * ref.mixers(sizes)["mla"] * sequences


def read(ctx, params):
    return roofline(ctx, params["ops"], needed_flops)
