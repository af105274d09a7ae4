"""Reading a metric out of a finished run. Every metric has a file of its
own, `benchmarks/end_to_end/<name>.json` or
`benchmarks/layer_metrics/<name>.json`, which names one of the general
readers below with its parameters — or, where none fits, `"reader":
"module"` and a module `<name with . as _>.py` beside it with a
`read(ctx, params)` of its own. A reader that finds nothing to read returns
None and the metric is left out of the line; it never returns 0 for a share
of a peak.

`ctx` is what run.py knows after the window: the tracer with its spans, the
window's seconds, rounds and real samples, the model's FLOPs, the peaks, (in
a traced run) the reduced device trace (`ctx["trace"]`, whose `ops` holds
every device op by name: what a kernel's own reader looks its kernel up
in), and for a reader of its own the raw
material: the cell's files (`spec`), the program's config (`cfg`), the
federation's row counts (`counts`) and the real rows x epochs the harness
counts in one round (`rows_of_round(r)`).
"""

from __future__ import annotations

import importlib
import json
import math
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "harness", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"benchmarks/harness/peaks.json")
    return table[device_kind]


def _peak_flops(ctx) -> float:
    return ctx["peaks"]["flops_per_s"][ctx["dtype"]]


# ------------------------------------------------------------- end to end

def percentile(values: list[float], q: float) -> float:
    """Linear-interpolated percentile of a non-empty list, q in [0, 100]."""
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def ctx_value(ctx, params):
    """A quantity run.py has already taken (`setup_s`)."""
    return ctx.get(params["key"])


def samples_per_s_chip(ctx, params):
    return ctx["samples"] / ctx["window_s"] / ctx["chips"]


def span_percentile_ms(ctx, params):
    """A percentile of one span's milliseconds over ALL its occurrences in
    the window's rounds."""
    ms = [s["dur_s"] * 1e3
          for s in ctx["tracer"].window_spans(params["span"])]
    return percentile(ms, params["q"]) if ms else None


# -------------------------------------------------------------- per layer


def span_mean_ms(ctx, params):
    """Mean milliseconds of the named spans: per round of the window
    (`per: round`, the spans of one round summed) or per span."""
    total, n = 0.0, 0
    for name in params["spans"]:
        found = ctx["tracer"].window_spans(name)
        total += sum(s["dur_s"] for s in found)
        n = max(n, len(found))
    if not n:
        return None
    if params.get("per") == "round":
        n = ctx["rounds"]
    return total / n * 1e3


def model_flops_utilization(ctx, params):
    """FLOPs the model NEEDS for the real rows trained in the window (forward
    and backward, padding not counted) over window seconds, chips and peak."""
    if ctx["peaks"] is None:
        return None
    need = ctx["samples"] * ctx["train_flops_per_sample"]
    return 100.0 * need / ctx["window_s"] / ctx["chips"] / _peak_flops(ctx)


def trace_top_module_ms(ctx, params):
    """Mean device milliseconds of one execution of the compiled program
    that took most device time in the traced stretch: in a training cell,
    the round program."""
    t = ctx["trace"]
    if not t or not t["modules"]:
        return None
    _, count, seconds = t["modules"][0]
    return seconds / count * 1e3


def executed_flops_roofline(ctx, params):
    """FLOPs the device EXECUTES in the traced rounds over peak and the
    seconds the device was busy: the sample slots the program says it ran
    there (`slots` of the `dispatch` spans: every padded slot of every
    executed step, epochs included) times the model's FLOPs a sample. The
    harness keeps no slot formula of its own, and finds nothing to read
    where no such span carries `slots`. Compute side of the roofline only:
    bytes are not counted.

    What the program reports is held between two counts of the harness's
    own, so that a miscount cannot move the share with no kernel changed:
    no fewer slots than the real rows the harness counts in those rounds
    (`rows_of_round`), no more than every client of every such cohort padded
    to the federation's longest. Outside them the run ends: the spans are
    wrong, and so is every metric read from them."""
    t = ctx["trace"]
    lo, hi = ctx["tracer"].trace_rounds
    if (not t or lo is None or hi is None or t["busy_s"] <= 0
            or ctx["peaks"] is None):
        return None
    spans = [s for s in ctx["tracer"].window_spans("dispatch")
             if "slots" in s and lo <= s["round"] < hi]
    if not spans:
        return None
    slots = sum(s["slots"] for s in spans)
    cfg, counts, chips = ctx["cfg"], ctx["counts"], ctx["chips"]
    rows = sum(ctx["rows_of_round"](s["round"]) for s in spans)
    n_max = int(max(counts))
    b = min(cfg.batch_size, n_max) if cfg.batch_size > 0 else n_max
    # a mesh round pads its cohort to a multiple of the chips (10 silos: 12)
    cohort = -(-min(cfg.client_num_per_round, len(counts)) // chips) * chips
    most = len(spans) * cohort * math.ceil(n_max / b) * b * cfg.epochs
    if not rows <= slots <= most:
        raise SystemExit(
            f"the dispatch spans of rounds [{lo}, {hi}) report {slots} "
            f"executed slots; the harness counts {rows} real rows there and "
            f"at most {most} slots with every client padded to {n_max} rows")
    executed = slots * ctx["train_flops_per_sample"]
    return 100.0 * executed / t["busy_s"] / _peak_flops(ctx)


def trace_idle_pct(ctx, params):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


GENERAL = {f.__name__: f for f in (
    ctx_value, samples_per_s_chip, span_percentile_ms, span_mean_ms,
    model_flops_utilization, trace_top_module_ms, executed_flops_roofline,
    trace_idle_pct)}


def read_metric(group: str, name: str, ctx: dict):
    """`group`: the metric's directory, "end_to_end" or "layer_metrics"."""
    with open(os.path.join(HERE, group, name + ".json")) as f:
        spec = json.load(f)
    reader = spec["reader"]
    if reader == "module":
        mod = importlib.import_module(
            f"benchmarks.{group}." + name.replace(".", "_"))
        return mod.read(ctx, spec.get("params", {}))
    return GENERAL[reader](ctx, spec.get("params", {}))
