"""`moe.held_experts_roofline`: the grouped products in the device trace
against the operations the pairs on HELD experts need, the pairs read from
the traced rounds' `moe_load` events (`held`). Nothing where no event says
`held` (a model that holds every expert), no trace, no kernel or no peak."""

import re

from benchmarks.layer_metrics.attention_kernel_roofline import kernel_seconds


def read(ctx, params):
    tracer = ctx["tracer"]
    lo, hi = tracer.trace_rounds
    seconds = kernel_seconds(ctx, re.compile(params["ops"]))
    if not seconds or lo is None or hi is None or ctx["peaks"] is None:
        return None
    pairs = [e["held"] for e in tracer.find_events("moe_load")
             if "held" in e and lo <= e["round"] < hi]
    if not pairs:
        return None
    cfg = ctx["spec"]["config"]
    # forward + activation gradient of the three matrices: 2 a multiply-add
    need = (sum(pairs) * 3 * 2 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * 2)
    peak = ctx["peaks"]["flops_per_s"][ctx["dtype"]]
    return 100.0 * need / seconds / peak
