"""Source-level lint rules over fedml_tpu/ and tools/.

Traced-root detection: a function is "traced" when it is jit-decorated
(`@jax.jit`, `@partial(jax.jit, ...)`, `@nn.jit`) or its NAME is passed to
a tracing combinator (`jax.jit(f)`, `jax.vmap`, `jax.grad`,
`jax.value_and_grad`, `jax.lax.scan/map/fori_loop/while_loop/cond`,
`jax.checkpoint`, `shard_map`). Tracedness propagates through the
intra-module call graph: a helper called (by name) from a traced function
is traced too. Nested `def`s inherit their enclosing function's
tracedness.

Rules (all suppressible with `# graft-lint: disable=<rule> -- <reason>` on
the line or the line above; the reason is mandatory — a bare disable is
itself the `bare-suppression` finding):

- `host-transfer`: `.block_until_ready()`, `jax.device_get`, `.item()`,
  `np.asarray`/`np.array`/`onp.asarray`, and `float()`/`int()` applied to
  a parameter of the traced function — each forces a host sync (or a
  ConcretizationError) inside code that is supposed to stay on device.
- `traced-loop`: `for _ in <param>` inside a traced function — unrolls at
  trace time into O(n) HLO and retraces when n changes; use lax.scan.
- `sync-idiom`: `float(np.asarray(x))` ANYWHERE (traced or not) — a
  double host transfer; `jax.block_until_ready(x)` (no copy) or a single
  `jax.device_get` is always what's meant.
- `bare-suppression`: a `# graft-lint: disable=<rule>` comment without a
  `-- <reason>` tail — every suppression must say WHY the rule is wrong
  here, or the next reader deletes the comment and reintroduces the bug.
- `blocking-fetch-in-drive-loop` (algorithms/ drivers only): per-item
  `float()`/`int()`/`np.asarray()`/`.item()` host syncs inside `for`/
  comprehension iteration, or `float(jnp...)` anywhere inside a loop — the
  UNTRACED drive-loop half of the host-sync story (the jaxpr host-sync rule
  only sees traced code). Each such call is one blocking device round trip
  per item; the blessed idiom is ONE
  `jax.device_get` of the whole tree with host-side iteration —
  `{k: float(v) for k, v in jax.device_get(m).items()}` is clean because
  the iterable resolves everything in a single transfer.
- `naked-timer-in-drive-loop` (algorithms/ drivers only): raw
  `time.time()`/`time.perf_counter()` reads inside a drive loop — async
  dispatch makes them measure the enqueue, not the device. Blessed: the
  telemetry Span API and `jax.block_until_ready`-bracketed timers.
- `unschema-event`: a `tracer.event(...)` / `telemetry.emit(...)` call whose
  literal kind string is not registered in EVENT_SCHEMAS — the emit raises
  ValueError the FIRST time it fires at runtime, which for error-path events
  (reconnects, rollbacks) is exactly when you can least afford a crash.
  Non-literal kinds (the seam's own `tracer.event(kind, ...)` forward) are
  skipped: the rule is a static spelling check, not a dataflow analysis.
- `unregistered-codec` (algorithms/, parallel/, serving/ only): a direct
  `Int8Codec(...)` / `TopKCodec(...)` constructor call outside
  `fedml_tpu/codecs/` — codecs must come from `fedml_tpu.codecs.make_codec`
  so the CLI/config name, the COMMS/COMPILE budget program names, and the
  codec-off bit-identity contract stay in sync; a hand-built codec with
  ad-hoc parameters would run under a budget pin measured for different
  wire bytes.
- `personal-state-in-federated-tree`: a personal-adapter collection (any
  argument whose name mentions "personal") handed to a federated-tree
  surface — the aggregator/collective tail (`psum`, `pmean`, `all_reduce`,
  `aggregate`, `masked_psum_tail`), the update-codec encode path (`encode`,
  `wrap_codec`), or checkpointing (`save_checkpoint`). Personal rows are
  client-private BY CONTRACT (graft-pfl): the aggregator sees only trained
  effective params, the wire carries zero extra bytes, and persistence is
  the mmap adapter bank — a personal tree reaching any of those surfaces
  either leaks private state into the global model/checkpoint or breaks
  the pinned COMMS twin equality. Blessed path: `models/adapter_bank.py`
  (the bank IS the sanctioned persistence for personal rows).
- `full-store-materialize`: `np.asarray(store.x)` / `np.stack(...)` /
  `store.x[:]` whole-store reads over a packed/streaming client store —
  the data plane's O(cohort) contract (data/packed_store.py) dies the
  moment someone materializes `.x` wholesale. Blessed, call-graph-aware:
  code inside a function named `materialize` or `__array__` (and the
  closure of local helpers those call) is the one sanctioned whole-store
  path. Bounded reads (`store.x[idx]`, `.x[:1, 0]`) are clean.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set

from fedml_tpu.analysis.core import Finding, is_suppressed, iter_suppressions

_TRACING_CALLS = {
    "jit", "vmap", "pmap", "grad", "value_and_grad", "checkpoint", "remat",
    "scan", "map", "fori_loop", "while_loop", "cond", "switch", "shard_map",
    "custom_vmap", "associated_scan", "associative_scan",
}
_NP_ALIASES = {"np", "onp", "numpy"}
_HOST_ATTR_CALLS = {"block_until_ready", "item"}  # x.block_until_ready(), x.item()


def _dotted(node) -> str:
    """'jax.lax.scan' for an Attribute/Name chain, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_tracing_call(call: ast.Call) -> bool:
    name = _dotted(call.func)
    return bool(name) and name.split(".")[-1] in _TRACING_CALLS


def _is_jit_decorator(dec) -> bool:
    if isinstance(dec, ast.Call):
        name = _dotted(dec.func)
        tail = name.split(".")[-1] if name else ""
        if tail in {"jit", "pmap", "checkpoint", "remat"}:
            return True
        if tail == "partial" and dec.args:
            inner = _dotted(dec.args[0])
            return bool(inner) and inner.split(".")[-1] in _TRACING_CALLS
        return False
    name = _dotted(dec)
    return bool(name) and name.split(".")[-1] in {"jit", "pmap", "checkpoint",
                                                  "remat"}


class _FnInfo:
    def __init__(self, node: ast.FunctionDef, parent: Optional["_FnInfo"]):
        self.node = node
        self.parent = parent
        self.traced = any(_is_jit_decorator(d) for d in node.decorator_list)
        self.calls: Set[str] = set()  # local function names this fn calls
        self.params: Set[str] = {
            a.arg for a in (node.args.args + node.args.posonlyargs
                            + node.args.kwonlyargs)}


class _Collector(ast.NodeVisitor):
    """Pass 1: find every function, its decorators, its local calls, and
    which names get handed to tracing combinators anywhere in the module."""

    def __init__(self):
        self.fns: Dict[str, _FnInfo] = {}   # qualified-by-nesting name
        self.by_name: Dict[str, List[_FnInfo]] = {}
        self.traced_names: Set[str] = set()
        self._stack: List[_FnInfo] = []

    def visit_FunctionDef(self, node: ast.FunctionDef):
        info = _FnInfo(node, self._stack[-1] if self._stack else None)
        self.fns[node.name + f"@{node.lineno}"] = info
        self.by_name.setdefault(node.name, []).append(info)
        self._stack.append(info)
        self.generic_visit(node)
        self._stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node: ast.Call):
        if self._stack:
            callee = _dotted(node.func)
            if callee and "." not in callee:
                self._stack[-1].calls.add(callee)
        if _is_tracing_call(node):
            # every plain-name argument to jit/vmap/scan/... is traced
            for a in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(a, ast.Name):
                    self.traced_names.add(a.id)
                elif isinstance(a, ast.Call):  # jit(partial(f, ...)) etc.
                    inner = _dotted(a.func)
                    if inner and inner.split(".")[-1] == "partial" and a.args:
                        if isinstance(a.args[0], ast.Name):
                            self.traced_names.add(a.args[0].id)
        self.generic_visit(node)


def _propagate(col: _Collector) -> None:
    for name in col.traced_names:
        for info in col.by_name.get(name, []):
            info.traced = True
    # nested defs inherit; call-graph closure over local names
    changed = True
    while changed:
        changed = False
        for info in col.fns.values():
            if not info.traced and info.parent is not None and info.parent.traced:
                info.traced = changed = True
            if info.traced:
                for callee in info.calls:
                    for ci in col.by_name.get(callee, []):
                        if not ci.traced:
                            ci.traced = changed = True


def _is_np_asarray(call: ast.Call) -> bool:
    name = _dotted(call.func)
    if not name or "." not in name:
        return False
    head, tail = name.split(".", 1)
    return head in _NP_ALIASES and tail in {"asarray", "array"}


class _RuleRunner(ast.NodeVisitor):
    """Pass 2: emit findings inside one traced function body (not into
    nested defs — they're visited as their own _FnInfo)."""

    def __init__(self, info: _FnInfo, path: str, lines: List[str],
                 findings: List[Finding]):
        self.info = info
        self.path = path
        self.lines = lines
        self.findings = findings

    def _emit(self, rule: str, node, msg: str):
        if not is_suppressed(self.lines, node.lineno, rule):
            self.findings.append(
                Finding(rule, f"{self.path}:{node.lineno}", msg))

    def visit_FunctionDef(self, node):
        if node is not self.info.node:
            return  # nested def handled by its own runner
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        tail = name.split(".")[-1] if name else ""
        if (isinstance(node.func, ast.Attribute) and tail in _HOST_ATTR_CALLS
                and not name.startswith("jax.")):
            self._emit("host-transfer", node,
                       f".{tail}() in traced code forces a host sync")
        elif name == "jax.device_get":
            self._emit("host-transfer", node,
                       "jax.device_get in traced code forces a host sync")
        elif _is_np_asarray(node):
            self._emit("host-transfer", node,
                       f"{name}() in traced code pulls the array to host "
                       f"(and breaks the trace)")
        elif isinstance(node.func, ast.Name) and node.func.id in {"float", "int"}:
            if node.args and self._mentions_param(node.args[0]):
                self._emit("host-transfer", node,
                           f"{node.func.id}() on a traced argument "
                           f"concretizes it — keep it a 0-d array")
        self.generic_visit(node)

    def _mentions_param(self, expr) -> bool:
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Name) and sub.id in self.info.params:
                return True
        return False

    def visit_For(self, node: ast.For):
        it = node.iter
        if isinstance(it, ast.Name) and it.id in self.info.params:
            self._emit("traced-loop", node,
                       f"Python for-loop over traced argument {it.id!r} "
                       f"unrolls at trace time — use jax.lax.scan")
        self.generic_visit(node)


class _SyncIdiom(ast.NodeVisitor):
    """float(np.asarray(x)) anywhere in the module — traced or not."""

    def __init__(self, path: str, lines: List[str], findings: List[Finding]):
        self.path = path
        self.lines = lines
        self.findings = findings

    def visit_Call(self, node: ast.Call):
        if (isinstance(node.func, ast.Name)
                and node.func.id in {"float", "int"} and node.args):
            inner = node.args[0]
            # unwrap trailing .ravel()[0] / indexing around the asarray
            while True:
                if isinstance(inner, ast.Subscript):
                    inner = inner.value
                elif (isinstance(inner, ast.Call)
                      and isinstance(inner.func, ast.Attribute)
                      and not _is_np_asarray(inner)):
                    inner = inner.func.value
                else:
                    break
            if isinstance(inner, ast.Call) and _is_np_asarray(inner):
                if not is_suppressed(self.lines, node.lineno, "sync-idiom"):
                    self.findings.append(Finding(
                        "sync-idiom", f"{self.path}:{node.lineno}",
                        "float(np.asarray(...)) double-transfers; use "
                        "jax.block_until_ready (no copy) or one device_get"))
        self.generic_visit(node)


class _DriveLoopFetch(ast.NodeVisitor):
    """blocking-fetch-in-drive-loop: per-item host syncs in the untraced
    drive loops of algorithms/ drivers.

    Two triggers, one rule:
    - a `float()`/`int()`/`np.asarray()`/`np.array()`/`.item()` whose
      argument mentions the target variable of an enclosing `for` statement
      or comprehension generator — the per-item fetch shape
      (`{k: float(v) for k, v in metrics.items()}` syncs once per key);
    - any `float(jnp...)`/`int(jnp...)`/`np.asarray(jnp...)` inside a loop
      (for/while/comprehension) — a device value resolved per iteration
      regardless of what drives the loop.

    A loop/generator whose iterable expression contains a `device_get` call
    blesses its targets: the transfer already happened in one batch, so
    host-side `float()` over the fetched tree is free. Shape/size
    arithmetic (`int(np.prod(l.shape))` and friends) never touches device
    data and is skipped.
    """

    def __init__(self, path: str, lines: List[str], findings: List[Finding]):
        self.path = path
        self.lines = lines
        self.findings = findings
        self._frames: List[tuple] = []  # (target_names, blessed)
        self._loops = 0

    @staticmethod
    def _names(node) -> Set[str]:
        return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}

    @staticmethod
    def _blessed(iter_node) -> bool:
        for sub in ast.walk(iter_node):
            if isinstance(sub, ast.Call):
                name = _dotted(sub.func)
                if name and name.split(".")[-1] == "device_get":
                    return True
        return False

    @staticmethod
    def _shape_math(expr) -> bool:
        # int(np.prod(l.shape[1:])) etc. — static metadata, no device data
        return any(isinstance(sub, ast.Attribute)
                   and sub.attr in {"shape", "ndim", "size", "nbytes"}
                   for sub in ast.walk(expr))

    @staticmethod
    def _has_jnp_call(expr) -> bool:
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call):
                name = _dotted(sub.func)
                if name.startswith("jnp.") or name.startswith("jax.numpy."):
                    return True
        return False

    def _emit(self, node, what: str):
        if not is_suppressed(self.lines, node.lineno,
                             "blocking-fetch-in-drive-loop"):
            self.findings.append(Finding(
                "blocking-fetch-in-drive-loop", f"{self.path}:{node.lineno}",
                f"{what} inside a drive loop is one blocking device->host "
                "round trip per item; fetch once with jax.device_get(tree) "
                "and iterate the host copy"))

    # ---- loop frames ------------------------------------------------------
    def visit_For(self, node: ast.For):
        self.visit(node.iter)  # the iterable belongs to the OUTER scope
        self._frames.append((self._names(node.target),
                             self._blessed(node.iter)))
        self._loops += 1
        for stmt in node.body + node.orelse:
            self.visit(stmt)
        self._loops -= 1
        self._frames.pop()

    visit_AsyncFor = visit_For

    def visit_While(self, node: ast.While):
        self.visit(node.test)
        self._loops += 1
        for stmt in node.body + node.orelse:
            self.visit(stmt)
        self._loops -= 1

    def _visit_comprehension(self, node, bodies):
        for gen in node.generators:
            self.visit(gen.iter)
        for gen in node.generators:
            self._frames.append((self._names(gen.target),
                                 self._blessed(gen.iter)))
        self._loops += 1
        for body in bodies:
            self.visit(body)
        for gen in node.generators:
            for cond in gen.ifs:
                self.visit(cond)
        self._loops -= 1
        for _ in node.generators:
            self._frames.pop()

    def visit_ListComp(self, node):
        self._visit_comprehension(node, [node.elt])

    visit_SetComp = visit_ListComp
    visit_GeneratorExp = visit_ListComp

    def visit_DictComp(self, node):
        self._visit_comprehension(node, [node.key, node.value])

    # ---- the fetch calls --------------------------------------------------
    def visit_Call(self, node: ast.Call):
        arg = None
        what = None
        if (isinstance(node.func, ast.Name)
                and node.func.id in {"float", "int"} and node.args):
            arg, what = node.args[0], f"{node.func.id}()"
        elif _is_np_asarray(node) and node.args:
            arg, what = node.args[0], f"{_dotted(node.func)}()"
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr == "item" and not node.args):
            arg, what = node.func.value, ".item()"
        if arg is not None and not self._shape_math(arg):
            mentioned = self._names(arg)
            per_item = any(targets & mentioned
                           for targets, blessed in self._frames
                           if not blessed)
            in_any_blessed = any(targets & mentioned
                                 for targets, blessed in self._frames
                                 if blessed)
            if per_item and not in_any_blessed:
                self._emit(node, f"per-item {what}")
            elif self._loops and self._has_jnp_call(arg):
                self._emit(node, f"{what} on a jnp expression")
        self.generic_visit(node)


class _NakedTimer(ast.NodeVisitor):
    """naked-timer-in-drive-loop: raw wall-clock reads inside algorithms/
    drive loops.

    `time.time()` / `time.perf_counter()` / `time.monotonic()` /
    `time.process_time()` bracketing a jitted call measures DISPATCH
    latency, not compute — jax returns futures, so the timer closes before
    the device finishes. That is exactly how the r01–r05 throughput
    trajectory went flat without anyone noticing (PERF.md): the numbers
    timed the dispatch, and a regression in the round program hid behind
    async dispatch. Two blessed idioms:

    - the telemetry Span API (`tracer.span(...)` context managers,
      `tracer.now()` — spans are what the perf gate audits); a loop whose
      body opens a `.span(...)` / `.round(...)` context is considered
      instrumented and its remaining timer reads are measurement plumbing;
    - a loop body that calls `jax.block_until_ready(...)` — the timer pair
      then measures completed device work (tools/bench_* style).
    """

    _TIMER_TAILS = {"time", "perf_counter", "monotonic", "process_time"}
    _BLESSING_ATTRS = {"block_until_ready", "span", "round"}

    def __init__(self, path: str, lines: List[str], findings: List[Finding]):
        self.path = path
        self.lines = lines
        self.findings = findings
        self._blessed_loops = 0
        self._loops = 0

    @classmethod
    def _loop_blessed(cls, node) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                name = _dotted(sub.func)
                if name and name.split(".")[-1] in cls._BLESSING_ATTRS:
                    return True
        return False

    def _visit_loop(self, node, parts):
        blessed = self._loop_blessed(node)
        self._loops += 1
        self._blessed_loops += blessed
        for stmt in parts:
            self.visit(stmt)
        self._blessed_loops -= blessed
        self._loops -= 1

    def visit_For(self, node: ast.For):
        self.visit(node.iter)
        self._visit_loop(node, node.body + node.orelse)

    visit_AsyncFor = visit_For

    def visit_While(self, node: ast.While):
        self.visit(node.test)
        self._visit_loop(node, node.body + node.orelse)

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        if (name.startswith("time.")
                and name.split(".")[-1] in self._TIMER_TAILS
                and self._loops and not self._blessed_loops
                and not is_suppressed(self.lines, node.lineno,
                                      "naked-timer-in-drive-loop")):
            self.findings.append(Finding(
                "naked-timer-in-drive-loop", f"{self.path}:{node.lineno}",
                f"{name}() in a drive loop times async dispatch, not "
                "compute — record a telemetry span (tracer.span/round) or "
                "bracket the timed region with jax.block_until_ready"))
        self.generic_visit(node)


def _first_index(sub: ast.Subscript):
    """The leading index expression of `a[i, j, ...]` (or `a[i]`)."""
    sl = sub.slice
    if isinstance(sl, ast.Tuple):
        return sl.elts[0] if sl.elts else None
    return sl


def _is_full_slice(node) -> bool:
    """True for a bare `:` — the whole-first-axis read."""
    return (isinstance(node, ast.Slice)
            and node.lower is None and node.upper is None)


def _blessed_store_ranges(col: _Collector) -> List[tuple]:
    """(lineno, end_lineno) spans of the blessed whole-store readers:
    functions named `materialize` or `__array__` plus the call-graph
    closure of the local helpers they invoke (same propagation idea as
    tracedness — a helper that materialize() delegates to is blessed
    too)."""
    frontier = [info for name in ("materialize", "__array__")
                for info in col.by_name.get(name, [])]
    blessed = set()
    while frontier:
        info = frontier.pop()
        if id(info) in {id(b) for b in blessed}:
            continue
        blessed.add(info)
        for callee in info.calls:
            frontier.extend(col.by_name.get(callee, []))
    return [(i.node.lineno, i.node.end_lineno or i.node.lineno)
            for i in blessed]


class _FullStoreMaterialize(ast.NodeVisitor):
    """full-store-materialize: whole-store reads outside materialize().

    Two triggers, one rule:
    - a gather call (`np`/`onp`/`numpy`/`jnp` × `asarray`/`array`/`stack`)
      whose argument contains a `.x` attribute that is bare or first-indexed
      with a full `:` slice — `np.asarray(store.x)` copies EVERY client row
      through the facade;
    - any `<expr>.x[...]` subscript whose leading index is a full `:` —
      `.x[:]` and `.x[:, :cap]` read the whole first axis no matter how the
      rest is bounded.

    Bounded first indices (`store.x[idx]`, `.x[k]`, `.x[:64]`) are the
    select()-shaped access pattern and stay clean. Findings inside the
    blessed ranges (functions named `materialize`/`__array__` and their
    local-callee closure) are skipped — that is the ONE place a full read
    is the point, and it enforces its own byte budget.
    """

    _GATHER_HEADS = _NP_ALIASES | {"jnp"}
    _GATHER_TAILS = {"asarray", "array", "stack"}

    def __init__(self, path: str, lines: List[str], findings: List[Finding],
                 blessed_ranges: List[tuple]):
        self.path = path
        self.lines = lines
        self.findings = findings
        self.blessed_ranges = blessed_ranges
        self._flagged_lines: Set[int] = set()  # call-level finding emitted

    def _blessed(self, lineno: int) -> bool:
        return any(lo <= lineno <= hi for lo, hi in self.blessed_ranges)

    def _emit(self, node, msg: str):
        if self._blessed(node.lineno):
            return
        if node.lineno in self._flagged_lines:
            return
        if not is_suppressed(self.lines, node.lineno,
                             "full-store-materialize"):
            self._flagged_lines.add(node.lineno)
            self.findings.append(Finding(
                "full-store-materialize", f"{self.path}:{node.lineno}", msg))

    def _is_gather(self, call: ast.Call) -> bool:
        name = _dotted(call.func)
        if not name or "." not in name:
            return False
        head, tail = name.split(".", 1)
        return head in self._GATHER_HEADS and tail in self._GATHER_TAILS

    @classmethod
    def _whole_x_reads(cls, expr) -> List[ast.Attribute]:
        """`.x` attributes in `expr` read without a bounding first index:
        bare (`p.x`) or full-sliced (`p.x[:, ...]`)."""
        bounded = set()
        for sub in ast.walk(expr):
            if (isinstance(sub, ast.Subscript)
                    and isinstance(sub.value, ast.Attribute)
                    and sub.value.attr == "x"
                    and not _is_full_slice(_first_index(sub))):
                bounded.add(id(sub.value))
        return [a for a in ast.walk(expr)
                if isinstance(a, ast.Attribute) and a.attr == "x"
                and id(a) not in bounded]

    def visit_Call(self, node: ast.Call):
        if self._is_gather(node):
            exprs = list(node.args) + [k.value for k in node.keywords]
            if any(self._whole_x_reads(e) for e in exprs):
                self._emit(node,
                           f"{_dotted(node.func)}() over a store's .x "
                           "materializes every client row — select() the "
                           "cohort, or route through the blessed "
                           "materialize() helper")
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript):
        if (isinstance(node.value, ast.Attribute) and node.value.attr == "x"
                and _is_full_slice(_first_index(node))):
            self._emit(node,
                       ".x[:] reads the whole first axis of a store — "
                       "index with the sampled cohort (store.x[idx]) or "
                       "use materialize()")
        self.generic_visit(node)


class _UnschemaEvent(ast.NodeVisitor):
    """unschema-event: literal event kinds must exist in EVENT_SCHEMAS.

    Matches the two emit surfaces — the seam (`telemetry.emit(...)` or a
    bare `emit(...)` from `from fedml_tpu.telemetry import emit`) and tracer
    methods (`<anything>.event(...)`, e.g. `tracer.event`,
    `self.tracer.event`). The kind is the first positional string literal,
    or the `kind=` keyword; calls passing a variable are skipped (the
    tracer's own runtime check owns those)."""

    def __init__(self, path: str, lines: List[str], findings: List[Finding]):
        self.path = path
        self.lines = lines
        self.findings = findings
        # late import keeps analysis importable even if telemetry grows
        # heavier deps; tracer.py is stdlib-only today
        from fedml_tpu.telemetry.tracer import EVENT_SCHEMAS
        self.schemas = EVENT_SCHEMAS

    @staticmethod
    def _is_emit_call(name: str) -> bool:
        if name == "emit":
            return True
        parts = name.split(".")
        if parts[-1] == "emit" and parts[-2:-1] == ["telemetry"]:
            return True
        # tracer.event / self.tracer.event — but not a bare event() name
        return parts[-1] == "event" and len(parts) > 1

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        if name and self._is_emit_call(name):
            kind = None
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                kind = node.args[0].value
            else:
                for kw in node.keywords:
                    if kw.arg == "kind" and isinstance(kw.value, ast.Constant) \
                            and isinstance(kw.value.value, str):
                        kind = kw.value.value
            if kind is not None and kind not in self.schemas \
                    and not is_suppressed(self.lines, node.lineno,
                                          "unschema-event"):
                self.findings.append(Finding(
                    "unschema-event", f"{self.path}:{node.lineno}",
                    f"event kind {kind!r} is not in EVENT_SCHEMAS — this "
                    f"call raises ValueError the first time it fires; "
                    f"register the kind (with its required fields) in "
                    f"telemetry/tracer.py"))
        self.generic_visit(node)


class _UnregisteredCodec(ast.NodeVisitor):
    """unregistered-codec: update codecs are built ONLY by make_codec.

    Scope: the codec-armed data-plane packages (algorithms/, parallel/,
    serving/). A direct `Int8Codec(...)` / `TopKCodec(...)` call there
    bypasses the registry — its bits/k come from call-site literals instead
    of FedConfig, so the `--update_codec` CLI, the budget program names
    (`...,int8]` / `...,topk64]`), and the codec-off bit-identity tests all
    describe a codec the round isn't actually running. Dotted spellings
    (`int8.Int8Codec`, `codecs.topk.TopKCodec`) match too; the
    `CodecAggregator` wrapper is exempt — round builders construct it
    around a make_codec-produced codec by design."""

    _CODEC_CTORS = {"Int8Codec", "TopKCodec"}

    def __init__(self, path: str, lines: List[str], findings: List[Finding]):
        self.path = path
        self.lines = lines
        self.findings = findings

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        if name and name.split(".")[-1] in self._CODEC_CTORS \
                and not is_suppressed(self.lines, node.lineno,
                                      "unregistered-codec"):
            self.findings.append(Finding(
                "unregistered-codec", f"{self.path}:{node.lineno}",
                f"`{name}(...)` constructs an update codec directly — build "
                f"it with `fedml_tpu.codecs.make_codec(cfg.update_codec, "
                f"cfg)` so the codec's parameters come from FedConfig and "
                f"match the COMMS/COMPILE budget program twins"))
        self.generic_visit(node)


class _PersonalStateInFederatedTree(ast.NodeVisitor):
    """personal-state-in-federated-tree: personal rows never federate.

    The graft-pfl privacy/bit-identity contract has three walls: personal
    adapter rows are never summed into the global tree (the aggregator
    input is the TRAINED effective params, the delta returns unaggregated),
    never encoded onto the wire (the COMMS twin gate pins pfl collective
    bytes == non-pfl), and never ride the global checkpoint (the mmap bank
    owns persistence, byte-stably). This rule is the static tripwire for
    all three: a call whose dotted tail is one of the federated-tree
    surfaces with an argument that names personal state is a contract
    breach no matter what the runtime gates happen to measure that day.
    Matching is by identifier substring ("personal" in a Name or attribute
    chain inside the argument), so `new_personal`, `staged.personal`,
    `personal_rows` all trip; calls inside models/adapter_bank.py are
    blessed (lint_source path-scopes the visitor away from it)."""

    _SURFACE_TAILS = {"psum", "pmean", "all_reduce", "aggregate",
                      "masked_psum_tail", "encode", "wrap_codec",
                      "save_checkpoint"}

    def __init__(self, path: str, lines: List[str], findings: List[Finding]):
        self.path = path
        self.lines = lines
        self.findings = findings

    @staticmethod
    def _personal_names(expr) -> List[str]:
        names = []
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Name) and "personal" in sub.id:
                names.append(sub.id)
            elif isinstance(sub, ast.Attribute) and "personal" in sub.attr:
                names.append(sub.attr)
        return names

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        tail = name.split(".")[-1] if name else ""
        if tail in self._SURFACE_TAILS:
            exprs = list(node.args) + [k.value for k in node.keywords
                                       if k.value is not None]
            hits = [n for e in exprs for n in self._personal_names(e)]
            if hits and not is_suppressed(self.lines, node.lineno,
                                          "personal-state-in-federated-tree"):
                self.findings.append(Finding(
                    "personal-state-in-federated-tree",
                    f"{self.path}:{node.lineno}",
                    f"personal adapter state ({hits[0]!r}) reaches the "
                    f"federated-tree surface `{name}(...)` — personal rows "
                    f"are client-private: they never aggregate, never hit "
                    f"the update codec, and persist only through "
                    f"models/adapter_bank.py"))
        self.generic_visit(node)


def lint_source(source: str, path: str) -> List[Finding]:
    """Run all AST rules on one module's source text."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("host-transfer", f"{path}:{e.lineno or 0}",
                        f"unparseable module: {e.msg}", severity="warning")]
    lines = source.splitlines()
    col = _Collector()
    col.visit(tree)
    _propagate(col)
    findings: List[Finding] = []
    for info in col.fns.values():
        if info.traced:
            _RuleRunner(info, path, lines, findings).visit(info.node)
    _SyncIdiom(path, lines, findings).visit(tree)
    _UnschemaEvent(path, lines, findings).visit(tree)
    # the bank is the ONE sanctioned persistence path for personal rows —
    # everywhere else, personal state reaching a federated surface is a
    # privacy/bit-identity breach (see _PersonalStateInFederatedTree)
    norm = path.replace(os.sep, "/")
    if not norm.endswith("models/adapter_bank.py"):
        _PersonalStateInFederatedTree(path, lines, findings).visit(tree)
    _FullStoreMaterialize(path, lines, findings,
                          _blessed_store_ranges(col)).visit(tree)
    # drive-loop fetch hygiene is an algorithms/-driver contract: that is
    # where the untraced round loops live (lint_tree hands us repo-relative
    # paths, so the scope survives any checkout location)
    parts = path.replace(os.sep, "/").split("/")
    if "algorithms" in parts:
        _DriveLoopFetch(path, lines, findings).visit(tree)
        _NakedTimer(path, lines, findings).visit(tree)
    # codec registry discipline is a data-plane contract: these are the
    # packages whose rounds the codec budget twins pin (codecs/ itself is
    # out of scope — it's where the constructors legitimately live)
    if {"algorithms", "parallel", "serving"} & set(parts):
        _UnregisteredCodec(path, lines, findings).visit(tree)
    # compile-layer rules (engine #4) ride the same sweep so LINT.json and
    # the repo-clean pins cover them; late import avoids a module cycle
    from fedml_tpu.analysis.compile_engine import lint_compile_tree
    findings.extend(lint_compile_tree(tree, path, lines))
    for lineno, rules, reason in iter_suppressions(source):
        if reason is None and not is_suppressed(lines, lineno,
                                                "bare-suppression"):
            findings.append(Finding(
                "bare-suppression", f"{path}:{lineno}",
                f"suppression of {', '.join(sorted(rules))} has no reason — "
                "write `# graft-lint: disable=<rule> -- <why it is safe "
                "here>`"))
    return findings


def lint_file(path: str, rel: Optional[str] = None) -> List[Finding]:
    with open(path) as f:
        src = f.read()
    return lint_source(src, rel or path)


def lint_tree(root: str, subdirs: Optional[List[str]] = None) -> List[Finding]:
    """Lint every .py under `root` (optionally restricted to `subdirs`),
    reporting repo-relative paths."""
    findings: List[Finding] = []
    tops = subdirs or [""]
    for top in tops:
        base = os.path.join(root, top)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames
                           if d not in {"__pycache__", ".git", ".pytest_cache"}]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    full = os.path.join(dirpath, fn)
                    findings += lint_file(full, os.path.relpath(full, root))
    return findings
