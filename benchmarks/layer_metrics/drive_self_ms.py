"""`drive.self_ms`: what the drive loop does in a round outside every span
it opens. Reads the span ids and parents the program's tracer records; a
program whose tracer has none gives nothing to read."""


def read(ctx, params):
    tracer = ctx["tracer"]
    rounds = [s for s in tracer.window_spans("round")
              if s["thread"] == "main"]
    if not rounds or "id" not in rounds[0] or not ctx["rounds"]:
        return None
    return sum(tracer.self_time(s) for s in rounds) / ctx["rounds"] * 1e3
