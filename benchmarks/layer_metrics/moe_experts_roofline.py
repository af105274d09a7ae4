"""`moe.experts_roofline`: the routed experts' grouped products in the device
trace, by the name pattern in the metric's file, against the operations the
traced rounds need of them."""

from benchmarks.layer_metrics.attention_kernel_roofline import roofline
from benchmarks.reference import deepseek_v2_lite as ref


def needed_flops(config: dict, sequences: int) -> int:
    """Forward + activation gradient of the three matrices of every chosen
    (token, expert) pair: 2 per multiply-add."""
    cfg = ref.published(config["sizes"])
    expert_layers = sum(ref._is_moe(cfg, i)
                        for i in range(cfg["num_hidden_layers"]))
    pairs = (sequences * config["sizes"]["seq_len"]
             * cfg["num_experts_per_tok"] * expert_layers)
    return pairs * 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2


def read(ctx, params):
    return roofline(ctx, params["ops"], needed_flops)
