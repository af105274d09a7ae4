"""`setup.compile_s`: the `compile` events the program's tracer holds from
before the window opened."""


def read(ctx, params):
    tracer = ctx["tracer"]
    durs = [e["dur_s"] for e in tracer.events
            if e["kind"] == "compile" and e["t"] < tracer.t_open]
    return sum(durs) if durs else None
