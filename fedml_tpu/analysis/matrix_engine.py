"""graft-lint's fifth engine (--matrix): the feature-matrix prover.

Driven entirely by the declarative RoundProgramSpec in core/spec.py, this
engine answers three questions the other four engines cannot:

1. **Does every legal feature combination build?** The full legal matrix
   (the product of all axis levels minus the EXCLUSIONS/CONSTRAINTS
   tables) is enumerated, pruned to a greedy pairwise covering array —
   every legal PAIR of axis levels appears in at least one cover point —
   and each cover point is abstractly traced (jax.eval_shape, zero FLOPs)
   through the real round builders. A legal point that fails to build is
   a finding: either the table is wrong (the combination is not actually
   supported — add an exclusion with an honest reason) or a builder
   regressed.

2. **Does config-time validation reject every illegal combination?** For
   every EXCLUSIONS pair and CONSTRAINTS clause-set, a representative
   config is built and `validate_config` must raise ValueError with the
   table's exact reason string — proving the runtime's scattered gates
   really were centralized, not dropped.

3. **Is the budget surface exactly the reachable surface?** The spec's
   DRIVE_SPECS program points are cross-checked against
   COMPILE_BUDGET.json (reachable-but-ungated programs, stale pins,
   signature-count drift) and COMMS_PROGRAM_NAMES against both
   COMMS_BUDGET.json and the live analysis/comms.py PROGRAMS table.
   Deliberate scope decisions (spec.SCOPE_NOTES) are echoed into
   MATRIX.json instead of flagged.

Plus one AST rule, **axis-drift**: a feature-axis kwarg
(spec.AXIS_KWARGS) that a round assembler's signature carries without a
declaration in spec.ASSEMBLERS — or declares without carrying. The
ASSEMBLERS table is the cross-sibling contract; its ``note`` fields
record deliberate absences (silo's missing collect_stats is a decision,
not drift).

CLI: ``python -m fedml_tpu.analysis --matrix [--fast] [--update-budgets]
[--json MATRIX.json]``. ``--fast`` traces one cover point per round
family instead of the full pairwise cover; ``--update-budgets`` rewrites
COMPILE_BUDGET.json from the spec-derived enumeration (static counts
only — max_compiles ceilings survive untouched).
"""

from __future__ import annotations

import ast
import itertools
import json
import os
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from fedml_tpu.analysis.core import Finding, Report, is_suppressed

# ---------------------------------------------------------------------------
# 1. the legal matrix and its pairwise cover
# ---------------------------------------------------------------------------


def enumerate_matrix() -> Tuple[List[Dict[str, str]], int]:
    """(legal assignments, full product size) over every spec axis."""
    from fedml_tpu.core.spec import AXES, is_legal

    names = list(AXES)
    legal: List[Dict[str, str]] = []
    total = 0
    for combo in itertools.product(*(AXES[n].levels for n in names)):
        total += 1
        levels = dict(zip(names, combo))
        if is_legal(levels):
            legal.append(levels)
    return legal, total


def _point_pairs(levels: Mapping[str, str]) -> frozenset:
    items = sorted(levels.items())
    return frozenset((a, b) for i, a in enumerate(items)
                     for b in items[i + 1:])


def pairwise_cover(legal: Sequence[Mapping[str, str]]
                   ) -> List[Dict[str, str]]:
    """Greedy pairwise covering array: a pruned-but-complete subset of
    `legal` in which every legal pair of axis levels (every 2-way feature
    interaction the tables permit) appears in at least one point. 2-way
    coverage is the classic combinatorial-testing sweet spot — the matrix
    has 18k points but only a few hundred distinct pairs."""
    pair_sets = [_point_pairs(p) for p in legal]
    uncovered = set().union(*pair_sets) if pair_sets else set()
    cover: List[Dict[str, str]] = []
    while uncovered:
        best = max(range(len(legal)), key=lambda i: len(pair_sets[i]
                                                        & uncovered))
        gained = pair_sets[best] & uncovered
        if not gained:      # unreachable pairs would loop forever
            break
        cover.append(dict(legal[best]))
        uncovered -= gained
    return cover


# ---------------------------------------------------------------------------
# 2. tracing the cover through the real builders
# ---------------------------------------------------------------------------

# The family-dispatch tables moved to core/spec.py with the rest of the
# declarative surface (core/builder.py composes from them too); re-exported
# here for the existing import surface (tests/test_matrix.py pins the
# dispatch order through these names).
from fedml_tpu.core.spec import (_FAMILY_TRACE_AXES,  # noqa: F401
                                 point_family, trace_key)


def _non_config_overlay(levels: Mapping[str, str]) -> Dict[str, str]:
    from fedml_tpu.core.spec import AXES

    return {name: levels[name] for name, axis in AXES.items()
            if axis.overrides is None and name in levels}


def trace_point(levels: Mapping[str, str]) -> None:
    """Abstractly trace (jax.eval_shape) the round program(s) one legal
    matrix point builds — composed by core/builder.py from the spec point,
    through the same builders the runtime uses, on the lr/f32 example
    (resnet20/bf16 for silo). Raises on any structural
    incompatibility the tables failed to declare. The hand-assembled twin
    this delegation replaced lives on in analysis/equiv_engine.py as
    `legacy_round_programs`, the certification baseline --equiv proves the
    builder against."""
    import jax

    from fedml_tpu.core.builder import build_round_program

    for prog in build_round_program(levels):
        jax.eval_shape(prog.fn, *prog.args)


def trace_legal_cover(cover: Sequence[Mapping[str, str]],
                      fast: bool = False
                      ) -> Tuple[List[Finding], List[Tuple]]:
    """Trace every distinct trace-key of the cover; with `fast`, one per
    round family. Returns (findings, traced keys)."""
    keyed: Dict[Tuple, Mapping[str, str]] = {}
    for levels in cover:
        keyed.setdefault(trace_key(levels), levels)
    if fast:
        per_family: Dict[str, Tuple] = {}
        for key in sorted(keyed):
            per_family.setdefault(key[0], key)
        keyed = {k: keyed[k] for k in per_family.values()}
    findings: List[Finding] = []
    traced: List[Tuple] = []
    for key in sorted(keyed):
        levels = keyed[key]
        try:
            trace_point(levels)
            traced.append(key)
        except Exception as e:                       # noqa: BLE001
            desc = ",".join(f"{a}={v}" for a, v in
                            sorted(levels.items()) if v not in
                            ("off", "none"))
            findings.append(Finding(
                rule="matrix-coverage", target=f"matrix:{key[0]}",
                message=(f"legal matrix point ({desc or 'all-defaults'}) "
                         f"failed to build: {type(e).__name__}: "
                         f"{str(e)[:200]} — either the builder regressed "
                         f"or core/spec.py needs an exclusion with an "
                         f"honest reason")))
    return findings, traced


# ---------------------------------------------------------------------------
# 3. the illegal half: every exclusion must raise at config time
# ---------------------------------------------------------------------------


def check_illegal_pairs() -> Tuple[List[Finding], int]:
    """For every EXCLUSIONS level-pair and CONSTRAINTS clause-set, build a
    representative config and prove `validate_config` raises ValueError
    with the FIRST matching table entry's exact reason (table order is
    the firing order — a constraint combo shadowed by a pairwise
    exclusion must raise the exclusion's reason). Returns
    (findings, combinations checked)."""
    from fedml_tpu.core.spec import (CONSTRAINTS, EXCLUSIONS,
                                     first_violation, point_config,
                                     validate_config)

    findings: List[Finding] = []
    checked = 0

    def expect(levels: Dict[str, str], label: str) -> None:
        nonlocal checked
        checked += 1
        hit = first_violation(levels)
        if hit is None:
            findings.append(Finding(
                rule="matrix-coverage", target=f"illegal:{label}",
                message=("table entry names a combination first_violation "
                         "does not flag — the tables disagree with "
                         "themselves")))
            return
        try:
            cfg = point_config(levels)
            validate_config(cfg, axes=_non_config_overlay(levels))
        except ValueError as e:
            if str(e) == hit.reason:
                return
            findings.append(Finding(
                rule="matrix-coverage", target=f"illegal:{label}",
                message=(f"illegal combination raised the WRONG reason: "
                         f"got {str(e)[:120]!r}, table says "
                         f"{hit.reason[:120]!r}")))
            return
        findings.append(Finding(
            rule="matrix-coverage", target=f"illegal:{label}",
            message=("illegal combination passed config-time validation "
                     "— the runtime gate this table entry mirrors is no "
                     "longer reachable from validate_config")))

    for exc in EXCLUSIONS:
        for la in exc.levels_a:
            for lb in exc.levels_b:
                expect({exc.axis_a: la, exc.axis_b: lb},
                       f"{exc.axis_a}={la}&{exc.axis_b}={lb}")
    for con in CONSTRAINTS:
        for combo in itertools.product(*(lvls for _, lvls in con.clauses)):
            levels = {axis: lvl for (axis, _), lvl in
                      zip(con.clauses, combo)}
            label = "&".join(f"{a}={v}" for a, v in sorted(levels.items()))
            expect(levels, label)
    return findings, checked


# ---------------------------------------------------------------------------
# 4. budget coverage: spec-reachable vs COMPILE/COMMS pins
# ---------------------------------------------------------------------------


def check_budget_coverage(repo_root: str,
                          compile_budgets: Optional[Dict] = None,
                          comms_budgets: Optional[Dict] = None,
                          check_live_comms: bool = True) -> List[Finding]:
    """Two-way spec <-> budget-file diff. Budgets may be injected (the
    ci_smoke trip self-test removes an entry in-memory to prove the gate
    fires); None loads the committed files."""
    from fedml_tpu.analysis.compile_engine import BUDGET_FILE as COMPILE_FILE
    from fedml_tpu.analysis.compile_engine import load_budgets
    from fedml_tpu.core.spec import (COMMS_PROGRAM_NAMES, DRIVE_SPECS,
                                     drive_program_names)

    findings: List[Finding] = []
    hint = ("re-run `python -m fedml_tpu.analysis --matrix "
            "--update-budgets` (or add a spec.SCOPE_NOTES entry naming "
            "the deliberate gap)")

    budgets = (compile_budgets if compile_budgets is not None
               else load_budgets(repo_root))
    for drive in sorted(DRIVE_SPECS):
        declared = drive_program_names(drive)
        entry = budgets.get(drive)
        if entry is None:
            findings.append(Finding(
                rule="matrix-coverage", target=f"compile:{drive}",
                message=(f"drive config `{drive}` declares "
                         f"{len(declared)} reachable program(s) but has "
                         f"no {COMPILE_FILE} entry — {hint}")))
            continue
        pinned = entry.get("programs", {})
        for name in sorted(set(declared) - set(pinned)):
            findings.append(Finding(
                rule="matrix-coverage", target=f"compile:{drive}",
                message=(f"program `{name}` is reachable per the spec "
                         f"but not budget-gated — {hint}")))
        for name in sorted(set(pinned) - set(declared)):
            findings.append(Finding(
                rule="matrix-coverage", target=f"compile:{drive}",
                message=(f"stale budget pin `{name}` — no DRIVE_SPECS "
                         f"point reaches it; {hint}")))
        for name in sorted(set(pinned) & set(declared)):
            if pinned[name] != declared[name]:
                findings.append(Finding(
                    rule="matrix-coverage", target=f"compile:{drive}",
                    message=(f"program `{name}`: spec declares "
                             f"{declared[name]} signature(s), "
                             f"{COMPILE_FILE} pins {pinned[name]} — "
                             f"{hint}")))

    if comms_budgets is None:
        path = os.path.join(repo_root, "COMMS_BUDGET.json")
        comms_budgets = {}
        if os.path.exists(path):
            with open(path) as f:
                comms_budgets = json.load(f)
    declared_comms = set(COMMS_PROGRAM_NAMES)
    for name in sorted(declared_comms - set(comms_budgets)):
        findings.append(Finding(
            rule="matrix-coverage", target="comms:budget",
            message=(f"spec declares HLO program `{name}` but "
                     f"COMMS_BUDGET.json carries no entry — run "
                     f"`python -m fedml_tpu.analysis --comms "
                     f"--update-budgets`")))
    for name in sorted(set(comms_budgets) - declared_comms):
        findings.append(Finding(
            rule="matrix-coverage", target="comms:budget",
            message=(f"COMMS_BUDGET.json entry `{name}` is not declared "
                     f"in spec.COMMS_PROGRAM_NAMES — stale pin or "
                     f"undeclared program")))

    if check_live_comms:
        from fedml_tpu.analysis import comms as comms_mod

        live = set(comms_mod.PROGRAMS)
        for name in sorted(declared_comms - live):
            findings.append(Finding(
                rule="matrix-coverage", target="comms:programs",
                message=(f"spec.COMMS_PROGRAM_NAMES declares `{name}` "
                         f"but analysis/comms.py PROGRAMS no longer "
                         f"builds it")))
        for name in sorted(live - declared_comms):
            findings.append(Finding(
                rule="matrix-coverage", target="comms:programs",
                message=(f"analysis/comms.py builds `{name}` but "
                         f"spec.COMMS_PROGRAM_NAMES does not declare it "
                         f"— add it so the matrix can gate its budget")))
    return findings


# ---------------------------------------------------------------------------
# 5. the axis-drift AST rule
# ---------------------------------------------------------------------------


def _signature_kwargs(fn: ast.FunctionDef) -> set:
    args = fn.args
    names = [a.arg for a in args.args] + [a.arg for a in args.kwonlyargs]
    return set(names)


def lint_axis_drift_source(source: str, path: str,
                           assemblers: Optional[Sequence] = None
                           ) -> List[Finding]:
    """axis-drift over one module's source: each ASSEMBLERS entry for
    `path` must find its function, and the signature's slice of
    AXIS_KWARGS must equal the declared tuple — a kwarg carried by one
    sibling but missing here (or carried here without a declaration) is
    drift. `assemblers` injects a spec table for fixture tests."""
    from fedml_tpu.core.spec import ASSEMBLERS, AXIS_KWARGS

    table = ASSEMBLERS if assemblers is None else tuple(assemblers)
    specs = [s for s in table if s.module == path]
    if not specs:
        return []
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(rule="axis-drift", target=f"{path}:{e.lineno}",
                        message=f"could not parse: {e.msg}",
                        severity="warning")]
    lines = source.splitlines()
    fns = {node.name: node for node in ast.walk(tree)
           if isinstance(node, ast.FunctionDef)}
    findings: List[Finding] = []
    for spec in specs:
        fn = fns.get(spec.func)
        if fn is None:
            findings.append(Finding(
                rule="axis-drift", target=f"{path}:{spec.func}",
                message=(f"spec.ASSEMBLERS declares round assembler "
                         f"`{spec.func}` but the module does not define "
                         f"it — update the table")))
            continue
        if is_suppressed(lines, fn.lineno, "axis-drift"):
            continue
        present = _signature_kwargs(fn) & AXIS_KWARGS
        declared = set(spec.axis_kwargs)
        for kw in sorted(declared - present):
            findings.append(Finding(
                rule="axis-drift", target=f"{path}:{fn.lineno}",
                message=(f"`{spec.func}` no longer carries feature-axis "
                         f"kwarg `{kw}` its siblings thread through "
                         f"(declared in spec.ASSEMBLERS) — restore it or "
                         f"re-declare with a note")))
        for kw in sorted(present - declared):
            findings.append(Finding(
                rule="axis-drift", target=f"{path}:{fn.lineno}",
                message=(f"`{spec.func}` grew feature-axis kwarg `{kw}` "
                         f"without a spec.ASSEMBLERS declaration — "
                         f"declare it so sibling assemblers are checked "
                         f"for the same axis")))
    return findings


def lint_axis_drift(repo_root: str) -> List[Finding]:
    """Run axis-drift over every module the ASSEMBLERS table names."""
    from fedml_tpu.core.spec import ASSEMBLERS

    findings: List[Finding] = []
    for module in sorted({s.module for s in ASSEMBLERS}):
        full = os.path.join(repo_root, module)
        if not os.path.exists(full):
            findings.append(Finding(
                rule="axis-drift", target=module,
                message="spec.ASSEMBLERS names a module that does not "
                        "exist — update the table"))
            continue
        with open(full) as f:
            findings.extend(lint_axis_drift_source(f.read(), module))
    return findings


# ---------------------------------------------------------------------------
# 6. the engine entry point
# ---------------------------------------------------------------------------


def _key_label(key: Tuple) -> str:
    """Human-readable trace-key: family plus its non-default levels."""
    on = ",".join(f"{a}={v}" for a, v in key[1:]
                  if v not in ("off", "none", "fedavg"))
    return f"{key[0]}:{on}" if on else key[0]


def format_matrix_table(matrix: Dict) -> str:
    lines = [
        f"{'feature matrix':<22} {matrix['legal_points']} legal of "
        f"{matrix['total_points']} "
        f"({matrix['illegal_pairs_checked']} illegal combination(s) "
        f"proven to raise)",
        f"{'pairwise cover':<22} {matrix['cover_points']} point(s), "
        f"{matrix['traced_programs']} distinct program(s) traced",
        f"{'compile surface':<22} "
        f"{sum(len(v) for v in matrix['drives'].values())} pinned "
        f"program name(s) across {len(matrix['drives'])} drive(s)",
        f"{'comms surface':<22} {matrix['comms_programs']} declared HLO "
        f"program(s)",
        f"{'scope notes':<22} {len(matrix['scope_notes'])} deliberate "
        f"gap(s) documented",
    ]
    return "\n".join(lines)


def run_matrix(repo_root: str, fast: bool = False,
               update_budgets: bool = False) -> Tuple[Report, Dict]:
    """The --matrix engine: enumerate, prove illegal, trace legal,
    cross-check budgets, lint axis drift. Returns (Report, MATRIX.json
    content)."""
    from fedml_tpu.core.spec import (COMMS_PROGRAM_NAMES, DRIVE_SPECS,
                                     SCOPE_NOTES, drive_program_names)

    report = Report()

    legal, total = enumerate_matrix()
    report.mark("matrix:enumerate")

    illegal_findings, n_illegal = check_illegal_pairs()
    report.extend(illegal_findings)
    report.mark("matrix:illegal")

    cover = pairwise_cover(legal)
    trace_findings, traced = trace_legal_cover(cover, fast=fast)
    report.extend(trace_findings)
    report.mark("matrix:trace")

    if update_budgets:
        from fedml_tpu.analysis.compile_engine import (BUDGET_FILE,
                                                       load_budgets,
                                                       make_budgets)
        from fedml_tpu.analysis.targets import enumerate_drive_programs

        # belt and braces: refresh the pins from the TRACED enumeration
        # (targets.py walks the same spec points through the builders), so
        # a spec typo cannot silently pin an untraceable program
        measured = {d: enumerate_drive_programs(d) for d in DRIVE_SPECS}
        budgets = make_budgets(measured, existing=load_budgets(repo_root))
        with open(os.path.join(repo_root, BUDGET_FILE), "w") as f:
            json.dump(budgets, f, indent=2)
            f.write("\n")

    report.extend(check_budget_coverage(repo_root))
    report.mark("matrix:budgets")

    report.extend(lint_axis_drift(repo_root))
    report.mark("ast:axis-drift")

    matrix = {
        "total_points": total,
        "legal_points": len(legal),
        "illegal_pairs_checked": n_illegal,
        "cover_points": len(cover),
        "traced_programs": len(traced),
        "traced": [_key_label(key) for key in traced],
        "drives": {d: sorted(drive_program_names(d))
                   for d in sorted(DRIVE_SPECS)},
        "comms_programs": len(COMMS_PROGRAM_NAMES),
        "scope_notes": dict(SCOPE_NOTES),
        "lint": report.to_dict(),
    }
    return report, matrix
