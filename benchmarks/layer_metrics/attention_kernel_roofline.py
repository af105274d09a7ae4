"""`attention.kernel_roofline`: the flash kernel's calls in the device trace,
by the name pattern in the metric's file, against the operations the traced
rounds need of the attention core (the configuration's reference module
counts them). Also what a kernel's roofline reader shares: the seconds of
the ops a pattern names, and the sequences the traced rounds executed."""

import importlib
import re


def kernel_seconds(ctx, pattern) -> float:
    """Self seconds of the first chip's device ops whose name matches."""
    trace = ctx["trace"]
    if not trace:
        return 0.0
    return sum(s for name, s, _ in trace["ops"] if pattern.search(name))


def traced_sequences(ctx) -> int:
    """Sample slots the program says it executed in the traced rounds (the
    `dispatch` spans' `slots`: padding included, as the kernels ran it)."""
    lo, hi = ctx["tracer"].trace_rounds
    if lo is None or hi is None:
        return 0
    return sum(s.get("slots", 0) for s in ctx["tracer"].window_spans("dispatch")
               if lo <= s["round"] < hi)


def needed_flops(config: dict, sequences: int) -> int:
    ref = importlib.import_module("benchmarks.reference." + config["reference"])
    cfg, t = ref.published(config["sizes"]), config["sizes"]["seq_len"]
    once = ref.attention_flops(
        t, cfg["num_attention_heads"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return 3 * once * cfg["num_hidden_layers"] * sequences


def roofline(ctx, pattern: str, needed) -> float | None:
    """100 x `needed(config, traced sequences)` operations over the matching
    kernels' seconds and the peak; None where there is no trace, no such
    kernel, no traced round or no peak (a CPU)."""
    seconds = kernel_seconds(ctx, re.compile(pattern))
    sequences = traced_sequences(ctx)
    if not seconds or not sequences or ctx["peaks"] is None:
        return None
    peak = ctx["peaks"]["flops_per_s"][ctx["dtype"]]
    return 100.0 * needed(ctx["spec"]["config"], sequences) / seconds / peak


def read(ctx, params):
    return roofline(ctx, params["ops"], needed_flops)
