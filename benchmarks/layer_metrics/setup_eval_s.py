"""`setup.eval_s`: the `eval` spans of the rounds before the window."""


def read(ctx, params):
    tracer = ctx["tracer"]
    durs = [s["dur_s"] for s in tracer.spans if s["name"] == "eval"
            and s["round"] is not None and s["round"] < tracer.first]
    return sum(durs) if durs else None
