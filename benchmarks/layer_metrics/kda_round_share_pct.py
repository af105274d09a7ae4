"""`kda.round_share_pct`: the KDA calls' self seconds over the device seconds
of the program that took most device time in the traced stretch."""

import re

from benchmarks.layer_metrics.attention_kernel_roofline import kernel_seconds


def read(ctx, params):
    trace = ctx["trace"]
    seconds = kernel_seconds(ctx, re.compile(params["ops"]))
    if not seconds or not trace["modules"] or not trace["modules"][0][2]:
        return None
    return 100.0 * seconds / trace["modules"][0][2]
