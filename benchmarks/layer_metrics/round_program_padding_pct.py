"""`round_program.padding_pct`: the program's own count of the rows it
trains and the slots it executes for them, from the `dispatch` spans."""


def read(ctx, params):
    spans = [s for s in ctx["tracer"].window_spans("dispatch")
             if "rows" in s and "slots" in s]
    slots = sum(s["slots"] for s in spans)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(s["rows"] for s in spans) / slots)
