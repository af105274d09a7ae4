"""`moe.held_load_imbalance`: max over mean of the tokens the HELD experts
received in a round, from the program's `moe_load` events; nothing where no
event says `held_max` (a model that holds every expert)."""


def read(ctx, params):
    tracer = ctx["tracer"]
    ratios = [e["held_max"] / e["held_mean"]
              for e in tracer.find_events("moe_load")
              if "held_max" in e and tracer.first <= e["round"] < tracer.last
              and e["held_mean"] > 0]
    return sum(ratios) / len(ratios) if ratios else None
