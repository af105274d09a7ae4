"""`swa.window_kernel_roofline`: the flash kernel's band calls in the device
trace, by the name pattern in the metric's file, against the operations the
traced rounds need of the window layers' attention cores (the
configuration's reference module counts the band's pairs). Nothing where
the program has no such call."""

import importlib

from benchmarks.layer_metrics.attention_kernel_roofline import roofline


def needed_flops(config: dict, sequences: int) -> int:
    """Forward and backward (x 3) of every window layer's score and context
    products over the band's pairs."""
    ref = importlib.import_module("benchmarks.reference." + config["reference"])
    cfg, t = ref.published(config["sizes"]), config["sizes"]["seq_len"]
    once = ref.attention_flops_band(t, cfg["sliding_window"],
                                    cfg["num_attention_heads"], cfg["head_dim"])
    return 3 * once * ref.mixers(config["sizes"])["window"] * sequences


def read(ctx, params):
    return roofline(ctx, params["ops"], needed_flops)
