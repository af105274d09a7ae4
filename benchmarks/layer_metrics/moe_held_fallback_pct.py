"""`moe.held_fallback_pct`: of the routed-expert calls of a model that holds
a share of its experts, the share that took the worst-case path because the
call's held rows did not fit the bounded buffer (`ops/moe.py`), from the
`bounded` and `fallback` counts of the program's `moe_load` events over the
window's rounds; nothing where no event carries them."""


def read(ctx, params):
    tracer = ctx["tracer"]
    events = [e for e in tracer.find_events("moe_load")
              if "fallback" in e and "bounded" in e
              and tracer.first <= e["round"] < tracer.last]
    calls = sum(e["bounded"] + e["fallback"] for e in events)
    if not calls:
        return None
    return 100.0 * sum(e["fallback"] for e in events) / calls
