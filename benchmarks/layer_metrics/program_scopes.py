"""The round program's device time by program phase: what the per-layer
metrics `moe.dispatch_share_pct`, `moe.layout_ms` and
`kda.mixer_xla_share_pct` share.

The join: the program hands out `{HLO instruction: op_name}` of its round
program's compiled text (`api.program_scopes()`, `fedml_tpu/telemetry/
scopes.py`); a device op of `ctx["trace"]["ops"]` is that instruction by the
first token of its short name. Summed: the self seconds of the ops whose
op_name holds any scope of `params["scopes"]` (a NAME of the path, inside a
transformation's wrappers too: `experts` in `vmap(jvp(experts))`) and whose
trace name does not match `params["exclude"]` (a Pallas kernel under the
scope, which a roofline of its own reads; on JAX 0.9 a kernel's call has no
op_name at all and is in no map, so this only guards a JAX that gives one).

Attribution: a fusion counts under the op_name XLA gave the fusion
instruction, so a fusion that mixes scopes counts whole under one of them.
Op names are those of the first chip's trace, every program's: an op of
another program in the traced stretch that shares an instruction name with
a scoped one would count too (the round program takes all but microseconds
of the stretch in the cells that list these metrics).

Nothing to read (None, never 0): no trace, a program that hands out no map
(`program_scopes` is PR 40's), a map that holds none of the scopes, or one
the program says is stale (its `program_scopes` event: an executable a
compile cache handed over without this tree's scopes)."""

import re


def holds(path: str, scope: str) -> bool:
    """Whether `scope` is a name of the op_name `path`."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/)])",
                     path) is not None


def scoped_seconds(ctx, params):
    """-> (self seconds of the scoped ops, [program, executions, device
    seconds] of the program with most device time), or None."""
    trace, tracer = ctx["trace"], ctx["tracer"]
    program_scopes = getattr(getattr(tracer, "api", None), "program_scopes",
                             None)
    if not trace or not trace["modules"] or program_scopes is None:
        return None
    names = program_scopes()
    said = tracer.find_events("program_scopes")
    if said and said[-1]["stale"]:
        return None
    wanted = {inst for inst, path in names.items()
              if any(holds(path, s) for s in params["scopes"])}
    if not wanted:
        return None
    skip = re.compile(params["exclude"]) if params.get("exclude") else None
    seconds = sum(s for name, s, _ in trace["ops"]
                  if name.split(" ")[0] in wanted
                  and not (skip and skip.search(name)))
    return seconds, trace["modules"][0]


def share_pct(ctx, params):
    """100 x the scoped seconds over the round program's device seconds."""
    found = scoped_seconds(ctx, params)
    if found is None or not found[1][2]:
        return None
    return 100.0 * found[0] / found[1][2]


def ms_a_round(ctx, params):
    """The scoped milliseconds a round: over the round program's
    executions in the traced stretch."""
    found = scoped_seconds(ctx, params)
    if found is None or not found[1][1]:
        return None
    return found[0] / found[1][1] * 1e3
