"""`kda.kernel_roofline`: the KDA core's Pallas calls in the device trace, by
the name pattern in the metric's file, against the operations the traced
rounds need of the recurrence (the configuration's reference module counts
them). Nothing where the program has no such call."""

import importlib

from benchmarks.layer_metrics.attention_kernel_roofline import roofline


def needed_flops(config: dict, sequences: int) -> int:
    ref = importlib.import_module("benchmarks.reference." + config["reference"])
    la = ref.published(config["sizes"])["linear_attn_config"]
    once = ref.kda_flops(config["sizes"]["seq_len"], la["num_heads"],
                         la["head_dim"], la["head_dim"])
    return 3 * once * ref.mixers(config["sizes"])["kda"] * sequences


def read(ctx, params):
    return roofline(ctx, params["ops"], needed_flops)
