"""Which part of the round program an XLA instruction belongs to: the join
from a device trace's ops to the program's `jax.named_scope` names.

A device trace names an op by its HLO instruction (`fusion.4023`) and
carries no scope; the compiled program's text does, in each instruction's
`metadata={op_name="jit(round_fn)/.../experts/moe_layout/sort"}`. The
round program hands out that map (`FedAvgAPI.program_scopes`), parsed here
from `Compiled.as_text()` with the repo's one HLO parser.

A transformation wraps the names it was traced under, so a scope is found
as a NAME anywhere in the path, inside wrappers too: `experts` in
`vmap(jvp(experts))/moe_layout/...` and in
`transpose(jvp(DeepseekV2LM.hidden))/.../layers_1/moe/experts/...`.

Attribution: a fusion counts under the `op_name` XLA gave the fusion
instruction (where it gave none, as to a fusion it cloned, under its fused
root's), so a fusion that mixes scopes counts whole under one of them.
Instructions inside a fused computation or a reducer (`to_apply`) never run
as ops of their own and are left out.

A persistent compile cache keys programs without their locations: one that
another tree filled can hand back an executable whose metadata lacks scopes
this tree's lowering names. `stale` says so, and the map is then empty:
nothing is attributed. (On the chip the two language-model cells' programs
never cross trees: a Pallas kernel's serialized body keeps its source
locations, so each tree's key differs. PERF.md section 6, PR 40.)
"""

from __future__ import annotations

import re

#: the scopes the round program declares and a reader may ask for:
#: `ops/moe.py`'s dispatch (`experts` is `models/deepseek_v2.py::MoE`'s
#: outer name for it; `moe_layout` the sort and scatters, `moe_gather` the
#: rows into expert order, `moe_combine` the rows back to tokens), the KDA
#: mixer (`models/kimi_linear.py`: the flax module `kda` and the scopes
#: `kda`, `kda_conv`, `kda_gates`), the blockwise loss (`core/trainer.py`),
#: and the flax modules `attn` and `router`
DECLARED_SCOPES = ("experts", "moe_layout", "moe_gather", "moe_combine",
                   "kda", "kda_conv", "kda_gates", "lm_loss", "attn",
                   "router")

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOC_NAME = re.compile(r'loc\("([^"]*)"')


def holds(path: str, scope: str) -> bool:
    """Whether `scope` is a name of `path`: a whole component, or the name
    inside a transformation's wrapper (`vmap(jvp(experts))`)."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/)])",
                     path) is not None


def scopes_in(path: str, scopes=DECLARED_SCOPES) -> tuple:
    """The declared scopes `path` holds."""
    return tuple(s for s in scopes if holds(path, s))


def op_names(compiled_text: str) -> tuple:
    """(instructions, {instruction: op_name}, kernels) of an optimised HLO
    module's text: the instructions that can run as ops (none inside a
    fused computation or a reducer, whose op_names XLA leaves without the
    path they were traced under), the op_name of each that has one, and the
    Pallas kernels' calls, which JAX lowers with no metadata at all (a
    `tpu_custom_call` is named by its kernel: `moe_grouped_matmul.145`). A
    fusion XLA made without metadata (a clone) takes its fused root's
    op_name, or else the last one inside it."""
    from fedml_tpu.analysis.hlo_engine import attr_value, parse_hlo_text

    def op_name(inst) -> str:
        found = _OP_NAME.search(attr_value(inst.attrs, "metadata") or "")
        return found.group(1) if found else ""

    module = parse_hlo_text(compiled_text)
    inner = set()
    for _, inst in module.all_instructions():
        for key in ("calls", "to_apply"):
            callee = attr_value(inst.attrs, key)
            if callee and (key == "to_apply" or inst.opcode == "fusion"):
                inner.add(callee.lstrip("%"))
    count, named, kernels = 0, {}, []
    for comp, inst in module.all_instructions():
        if comp.name in inner:
            continue
        count += 1
        path = op_name(inst)
        if not path and "tpu_custom_call" in (
                attr_value(inst.attrs, "custom_call_target") or ""):
            kernels.append(inst.name)
        body = module.computations.get(
            (attr_value(inst.attrs, "calls") or "").lstrip("%"))
        if not path and inst.opcode == "fusion" and body is not None:
            inside = [body.instructions[body.root]] + body.order[::-1]
            path = next(filter(None, map(op_name, inside)), "")
        if path:
            named[inst.name] = path
    return count, named, kernels


def lowered_scopes(debug_text: str, scopes=DECLARED_SCOPES) -> set:
    """The declared scopes a lowered module's locations name
    (`Lowered.as_text(debug_info=True)`)."""
    return {s for path in set(_LOC_NAME.findall(debug_text))
            for s in scopes_in(path, scopes)}


def join(compiled_text: str, debug_text: str, program: str) -> tuple:
    """-> ({instruction: op_name} of the instructions under a declared scope,
    the `program_scopes` event's fields). `op_scopes` in the event maps every
    named instruction to the declared scopes it holds, "/"-joined ("" for
    none), and `kernels` lists the Pallas calls, which have no op_name:
    what `tools/trace_report.py` joins a profile with."""
    count, named, kernels = op_names(compiled_text)
    held = {inst: scopes_in(path) for inst, path in named.items()}
    scoped = {inst: named[inst] for inst, s in held.items() if s}
    # inside fused computations too: a scope whose ops all went into
    # fusions XLA named by another op is in the text all the same
    found = {s for path in set(_OP_NAME.findall(compiled_text))
             for s in scopes_in(path)}
    counts = {s: sum(s in ss for ss in held.values())
              for s in DECLARED_SCOPES}
    event = {
        "program": program, "instructions": count, "named": len(named),
        "scoped": {s: n for s, n in counts.items() if n},
        "stale": bool(lowered_scopes(debug_text) - found),
        "op_scopes": {inst: "/".join(s) for inst, s in held.items()},
        "kernels": kernels,
    }
    return scoped, event


def program_map(jitted, avals, program: str) -> tuple:
    """`join` of the executable `jitted` runs on `avals` (`lower` and
    `compile` are served from memory after a call): -> ({instruction:
    op_name}, the event's fields); {} where the text is stale."""
    lowered = jitted.lower(*avals)
    scoped, event = join(lowered.compile().as_text(),
                         lowered.as_text(debug_info=True), program)
    return ({} if event["stale"] else scoped), event
