"""`swa.experts_roofline`: the routed experts' grouped products in the device
trace, by the name pattern in the metric's file, against the operations the
traced rounds need of them at this configuration's widths (the
configuration's reference module counts them)."""

import importlib

from benchmarks.layer_metrics.attention_kernel_roofline import roofline


def needed_flops(config: dict, sequences: int) -> int:
    """Forward + activation gradient (x 2) of the three matrices of every
    (token, expert) pair the traced sequences route."""
    ref = importlib.import_module("benchmarks.reference." + config["reference"])
    cfg = ref.published(config["sizes"])
    return 2 * ref.expert_flops(ref.routed_pairs(config["sizes"], sequences),
                                cfg["hidden_size"],
                                cfg["moe_intermediate_size"])


def read(ctx, params):
    return roofline(ctx, params["ops"], needed_flops)
