"""`moe.load_imbalance`: max over mean of the tokens the routed experts
received in a round, from the program's `moe_load` events."""


def read(ctx, params):
    tracer = ctx["tracer"]
    ratios = [e["max"] / e["mean"] for e in tracer.find_events("moe_load")
              if tracer.first <= e["round"] < tracer.last and e["mean"] > 0]
    return sum(ratios) / len(ratios) if ratios else None
