"""HLO-layer lint engine: walk lowered programs, not source or jaxprs.

The jaxpr engine sees what the USER wrote; this engine sees what XLA will
actually RUN. The round programs in fedml_tpu.parallel are lowered on a
forced multi-device host mesh (``--xla_force_host_platform_device_count=8``)
and the **pre-optimization** StableHLO/HLO is parsed into a tiny module
graph. Pre-opt HLO is the inventory substrate on purpose: user-written
collectives appear verbatim (op kind, channel_id, replica_groups,
source_target_pairs) regardless of backend — the CPU backend's optimized
HLO decomposes e.g. `all-to-all` into concat/slice and would hide the
traffic we are budgeting. The **optimized** HLO and
``compiled.memory_analysis()`` / ``cost_analysis()`` are consulted only for
what genuinely requires compilation: partitioner-inserted resharding
all-gathers the user never wrote, peak memory, and FLOPs.

Rules (HLO-layer rows of core.RULES):

- `collective-in-loop`: a collective inside a `while` body (lax.scan /
  fori_loop lower to `while`) whose operands are all loop-invariant — the
  same reduction re-runs every iteration; hoist it out of the scan. The
  invariance analysis is dataflow over the body: constants/iota and
  pass-through carry elements (root tuple element k == gte(param, k)) seed
  the invariant set, which propagates through pure ops and into `call`
  bodies with per-call-site parameter environments.
- `accidental-replication`: an all-gather whose output is at least the
  full parameter tree — every device rematerializes the global model the
  psum-aggregation design exists to avoid; plus any all-gather that only
  appears AFTER optimization (the partitioner resharding arrays the user
  thought were already placed).
- `ppermute-coverage`: `collective-permute` source/target pairs that are
  not a permutation covering the full axis group — uncovered targets
  silently receive ZEROS (XLA's documented behavior), the classic
  truncated-ring bug.
- `unweighted-psum-mean`: `psum(x) / axis_size` (or `* (1/axis_size)`) —
  a uniform mean where this repo's client aggregation is sample-count
  weighted (aggregators.tree_weighted_mean_psum); uniform means silently
  bias toward small clients.
- `axis-name-mismatch`: lowering raised jax's "unbound axis name" (or,
  under check_vma on jax 0.9, the bare assertion of `core.pvary`) — a
  collective names a mesh axis the enclosing shard_map does not bind
  (caught at lower time in analyze_program, reported as a finding instead
  of a stack trace).

`comms.py` names the lowered surface and the budget gate; this module is
the parser + rules + per-program `analyze_program` entry point.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from fedml_tpu.analysis.core import Finding

# ---------------------------------------------------------------------------
# HLO text parsing. The official python bindings expose no instruction-level
# walk of an HloModule, but the text format is stable and line-oriented:
#
#   HloModule jit_round_fn, entry_computation_layout={...}
#
#   region_0.34 {
#     arg_tuple.35 = (s32[], f32[8]) parameter(0)
#     get-tuple-element.36 = s32[] get-tuple-element(arg_tuple.35), index=0
#     all-reduce.40 = f32[8] all-reduce(x.39), replica_groups={{0,1,...,7}},
#         to_apply=region_2.20
#     ROOT tuple.47 = (s32[], f32[8]) tuple(add.46, all-reduce.40)
#   }
#
#   ENTRY main.60 {
#     ...
#   }
#
# Instructions are topologically sorted (operands defined before use), which
# the dataflow rules below rely on.
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(
    r"\b(pred|bf16|f16|f32|f64|f8e4m3fn|f8e5m2|s4|s8|s16|s32|s64"
    r"|u4|u8|u16|u32|u64|c64|c128)\[([\d,]*)\]")

# `all-reduce-start`/`-done` async pairs only appear post-optimization;
# matching the base opcode by prefix keeps both spellings in the inventory.
COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                  "collective-permute", "reduce-scatter",
                  "collective-broadcast")


def shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string; tuple shapes sum their leaves
    (layout suffixes like {1,0} are ignored by construction)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group(1)]
    return total


@dataclass
class HloInstruction:
    name: str
    opcode: str
    shape: str
    operands: List[str]        # operand instruction names (sigils stripped)
    operands_raw: List[str]    # verbatim operand tokens (constants keep value)
    attrs: str                 # everything after the operand list
    is_root: bool = False

    @property
    def bytes(self) -> int:
        return shape_bytes(self.shape)


@dataclass
class HloComputation:
    name: str
    order: List[HloInstruction] = field(default_factory=list)
    instructions: Dict[str, HloInstruction] = field(default_factory=dict)
    root: Optional[str] = None

    def add(self, inst: HloInstruction) -> None:
        self.order.append(inst)
        self.instructions[inst.name] = inst
        # explicit ROOT wins; otherwise the last instruction is the root
        if inst.is_root:
            self.root = inst.name
            self._explicit_root = True
        elif not getattr(self, "_explicit_root", False):
            self.root = inst.name

    @property
    def param(self) -> Optional[HloInstruction]:
        """The computation's (first) parameter instruction."""
        for inst in self.order:
            if inst.opcode == "parameter":
                return inst
        return None


@dataclass
class HloModule:
    name: str
    computations: Dict[str, HloComputation] = field(default_factory=dict)
    entry: Optional[str] = None

    def all_instructions(self):
        for comp in self.computations.values():
            for inst in comp.order:
                yield comp, inst


_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)[^=]*\{\s*$")
_INST_RE = re.compile(r"^\s+(ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+)$")
_OPCODE_RE = re.compile(r"([\w\-]+)\(")
#: optimised text numbers long tuples (`/*index=5*/`), headers included
_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _balanced(s: str, open_ch: str, close_ch: str, start: int = 0) -> int:
    """Index of the close matching the open at `start` (s[start]==open_ch)."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == open_ch:
            depth += 1
        elif s[i] == close_ch:
            depth -= 1
            if depth == 0:
                return i
    return len(s) - 1


def _split_top(s: str) -> List[str]:
    """Split on commas at bracket depth 0."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
            continue
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        out.append(tail)
    return out


def _parse_rhs(rhs: str) -> Tuple[str, str, List[str], List[str], str]:
    """'(s32[], f32[8]) tuple(a, b), attr=v' -> (shape, opcode, operand
    names, raw operand tokens, attrs)."""
    rhs = rhs.strip()
    if rhs.startswith("("):
        end = _balanced(rhs, "(", ")")
        shape, rest = rhs[:end + 1], rhs[end + 1:].lstrip()
    else:
        shape, _, rest = rhs.partition(" ")
        rest = rest.lstrip()
    m = _OPCODE_RE.match(rest)
    if not m:
        return shape, rest.strip() or "unknown", [], [], ""
    opcode = m.group(1)
    op_start = m.end() - 1
    op_end = _balanced(rest, "(", ")", op_start)
    raw = _split_top(rest[op_start + 1:op_end])
    # operand tokens may carry shape prefixes ('f32[2] %add.3'); the name is
    # the last whitespace token with the % sigil stripped
    names = [t.split()[-1].lstrip("%") for t in raw if t]
    attrs = rest[op_end + 1:].lstrip(", ")
    return shape, opcode, names, raw, attrs


def parse_hlo_text(text: str) -> HloModule:
    """Parse an HloModule dump (pre- or post-optimization) into a walkable
    module graph. Unrecognized lines are skipped, not fatal — the parser
    needs only shapes, opcodes, operands, and attrs."""
    module = HloModule(name="")
    comp: Optional[HloComputation] = None
    for line in text.splitlines():
        if line.startswith("HloModule"):
            parts = line.split(None, 2)
            module.name = parts[1].rstrip(",") if len(parts) > 1 else ""
            continue
        stripped = line.strip()
        if comp is None:
            m = _COMP_HEADER_RE.match(_COMMENT_RE.sub("", line))
            if m:
                comp = HloComputation(name=m.group(2))
                if m.group(1):
                    module.entry = comp.name
                module.computations[comp.name] = comp
            continue
        if stripped.startswith("}"):
            comp = None
            continue
        m = _INST_RE.match(line)
        if not m:
            continue
        shape, opcode, names, raw, attrs = _parse_rhs(m.group(3))
        comp.add(HloInstruction(
            name=m.group(2), opcode=opcode, shape=shape, operands=names,
            operands_raw=raw, attrs=attrs, is_root=bool(m.group(1))))
    if module.entry is None and module.computations:
        module.entry = next(reversed(module.computations))
    return module


def attr_value(attrs: str, key: str) -> Optional[str]:
    """Raw value of `key=` in an instruction's attr tail; brace values are
    returned with balanced nesting ('replica_groups={{0,1},{2,3}}')."""
    idx = attrs.find(key + "=")
    if idx < 0:
        return None
    v = attrs[idx + len(key) + 1:]
    if v.startswith("{"):
        return v[:_balanced(v, "{", "}") + 1]
    m = re.match(r"[^,\s]+", v)
    return m.group(0) if m else None


def replica_groups(inst: HloInstruction) -> List[List[int]]:
    """Parsed replica_groups; [] means 'one group of all devices'."""
    v = attr_value(inst.attrs, "replica_groups")
    if not v:
        return []
    return [[int(x) for x in inner.split(",") if x]
            for inner in re.findall(r"\{([\d,]*)\}", v) if inner]


def source_target_pairs(inst: HloInstruction) -> List[Tuple[int, int]]:
    v = attr_value(inst.attrs, "source_target_pairs") or ""
    return [(int(a), int(b)) for a, b in re.findall(r"\{(\d+),(\d+)\}", v)]


def is_collective(inst: HloInstruction) -> bool:
    op = inst.opcode
    return any(op == c or op == c + "-start" for c in COLLECTIVE_OPS)


def collective_inventory(module: HloModule) -> List[Dict]:
    """Every collective in the module: op kind, defining computation, output
    bytes, and the axis grouping (replica groups or permute pairs)."""
    out = []
    for comp, inst in module.all_instructions():
        if not is_collective(inst):
            continue
        op = inst.opcode.replace("-start", "")
        entry = {
            "op": op,
            "name": inst.name,
            "computation": comp.name,
            "bytes": inst.bytes,
        }
        ch = attr_value(inst.attrs, "channel_id")
        if ch:
            entry["channel_id"] = int(ch)
        if op == "collective-permute":
            entry["source_target_pairs"] = source_target_pairs(inst)
        else:
            entry["replica_groups"] = replica_groups(inst)
        out.append(entry)
    return out


# ---------------------------------------------------------------------------
# Rule: collective-in-loop
# ---------------------------------------------------------------------------

# ops whose output changes even with identical operands (or whose semantics
# the analysis does not model) — never invariant
_NONINVARIANT_OPS = {
    "rng", "rng-bit-generator", "rng-get-and-update-state",
    "infeed", "outfeed", "custom-call", "partition-id", "replica-id",
    "while", "conditional", "after-all", "send", "recv",
}


def _flat_inv(value) -> bool:
    if isinstance(value, list):
        return all(_flat_inv(v) for v in value)
    return bool(value)


def _walk_invariance(module: HloModule, comp_name: str, param_inv: list,
                     target: str, findings: List[Finding],
                     reported: set, memo: dict):
    """Propagate loop-invariance through one computation; `param_inv` is a
    per-parameter list of invariance values (each value True/False or a
    nested per-element list when that parameter is a tuple, as in a while
    body's carry). Returns the invariance of the root. Collectives reached
    with an all-invariant operand set are the finding."""
    key = (comp_name, repr(param_inv))
    if key in memo:
        return memo[key]
    memo[key] = False  # cycle guard; real value set below
    comp = module.computations.get(comp_name)
    if comp is None:
        return False
    inv: Dict[str, object] = {}
    for inst in comp.order:
        if inst.opcode == "parameter":
            # `parameter(N)` declares its index — call targets print their
            # parameters in arbitrary textual order, so never rely on order
            # of appearance
            try:
                idx = int(inst.operands_raw[0]) if inst.operands_raw else 0
            except ValueError:
                idx = 0
            inv[inst.name] = (param_inv[idx] if idx < len(param_inv)
                              else False)
        elif inst.opcode in ("constant", "iota"):
            inv[inst.name] = True
        elif inst.opcode in _NONINVARIANT_OPS:
            inv[inst.name] = False
        elif inst.opcode == "get-tuple-element":
            src = inv.get(inst.operands[0], False) if inst.operands else False
            idx = attr_value(inst.attrs, "index")
            if isinstance(src, list) and idx is not None:
                i = int(idx)
                inv[inst.name] = src[i] if i < len(src) else False
            else:
                inv[inst.name] = _flat_inv(src)
        elif inst.opcode == "tuple":
            inv[inst.name] = [inv.get(o, False) for o in inst.operands]
        elif inst.opcode == "call":
            callee = attr_value(inst.attrs, "to_apply")
            op_inv = [inv.get(o, False) for o in inst.operands]
            inv[inst.name] = _walk_invariance(
                module, callee, op_inv, target, findings, reported, memo
            ) if callee else False
        elif is_collective(inst):
            all_inv = all(_flat_inv(inv.get(o, False))
                          for o in inst.operands)
            if all_inv and (comp_name, inst.name) not in reported:
                reported.add((comp_name, inst.name))
                findings.append(Finding(
                    "collective-in-loop", target,
                    f"{inst.opcode} {inst.name} ({inst.bytes}B) in loop "
                    f"body {comp_name} has only loop-invariant operands — "
                    f"the same reduction re-runs every iteration; hoist it "
                    f"out of the scan"))
            inv[inst.name] = all_inv
        else:
            inv[inst.name] = all(_flat_inv(inv.get(o, False))
                                 for o in inst.operands)
    root_inv = inv.get(comp.root, False) if comp.root else False
    memo[key] = root_inv
    return root_inv


def _pass_through_elements(module: HloModule, body: HloComputation
                           ) -> List[bool]:
    """Carry tuple elements the while body returns untouched: root tuple
    operand k is get-tuple-element(param, index=k). lax.scan lowers its
    consts exactly this way, so scan consts seed the invariant set."""
    root = body.instructions.get(body.root) if body.root else None
    param = body.param
    if root is None or param is None or root.opcode != "tuple":
        return []
    out = []
    for k, opnd in enumerate(root.operands):
        src = body.instructions.get(opnd)
        out.append(bool(
            src is not None
            and src.opcode == "get-tuple-element"
            and src.operands and src.operands[0] == param.name
            and attr_value(src.attrs, "index") == str(k)))
    return out


def check_collective_in_loop(module: HloModule, target: str
                             ) -> List[Finding]:
    findings: List[Finding] = []
    reported: set = set()
    for comp, inst in module.all_instructions():
        if inst.opcode != "while":
            continue
        for role in ("body", "condition"):
            cname = attr_value(inst.attrs, role)
            body = module.computations.get(cname) if cname else None
            if body is None:
                continue
            elem_inv = _pass_through_elements(module, body)
            # one parameter (the carry tuple) whose invariance is per-element
            _walk_invariance(module, cname, [elem_inv], target, findings,
                             reported, {})
    return findings


# ---------------------------------------------------------------------------
# Rule: accidental-replication
# ---------------------------------------------------------------------------

_OPT_ALL_GATHER_RE = re.compile(r"=\s+\S+\s+all-gather(?:-start)?\(")


def check_accidental_replication(module: HloModule, target: str,
                                 params_bytes: Optional[int] = None,
                                 optimized_text: Optional[str] = None,
                                 expect_resharding: bool = False
                                 ) -> List[Finding]:
    findings: List[Finding] = []
    if expect_resharding:
        # GSPMD programs (automatic partitioning, e.g. tensor.step): the
        # partitioner inserting resharding collectives IS the mechanism,
        # not an accident — the traced program pins the user-written
        # collectives at zero and the peak-bytes budget bounds what the
        # resharding may cost per device. The pre-opt full-tree gather
        # check below still applies.
        optimized_text = None
    pre_gathers = [inst for _, inst in module.all_instructions()
                   if inst.opcode in ("all-gather", "all-gather-start")]
    if params_bytes:
        for inst in pre_gathers:
            if inst.bytes >= params_bytes:
                findings.append(Finding(
                    "accidental-replication", target,
                    f"all-gather {inst.name} materializes {inst.bytes}B on "
                    f"every device — at least the full {params_bytes}B "
                    f"param tree; aggregate with weighted psums "
                    f"(aggregators.tree_weighted_mean_psum) instead of "
                    f"gathering client stacks"))
    if optimized_text is not None:
        surplus = (len(_OPT_ALL_GATHER_RE.findall(optimized_text))
                   - len(pre_gathers))
        if surplus > 0:
            findings.append(Finding(
                "accidental-replication", target,
                f"optimized HLO contains {surplus} all-gather(s) absent "
                f"from the traced program — the partitioner is resharding "
                f"arrays behind your back; check in_specs/out_specs against "
                f"where the data actually lives"))
    return findings


# ---------------------------------------------------------------------------
# Rule: ppermute-coverage
# ---------------------------------------------------------------------------

def check_ppermute_coverage(module: HloModule, target: str,
                            num_devices: int) -> List[Finding]:
    findings: List[Finding] = []
    full = set(range(num_devices))
    for comp, inst in module.all_instructions():
        if inst.opcode not in ("collective-permute",
                               "collective-permute-start"):
            continue
        pairs = source_target_pairs(inst)
        srcs = [s for s, _ in pairs]
        tgts = [t for _, t in pairs]
        problems = []
        if len(set(srcs)) != len(srcs) or len(set(tgts)) != len(tgts):
            problems.append("duplicate source or target device")
        missing_t = sorted(full - set(tgts))
        missing_s = sorted(full - set(srcs))
        if missing_t:
            problems.append(f"devices {missing_t} are never targets and "
                            f"receive ZEROS")
        if missing_s:
            problems.append(f"devices {missing_s} never send")
        if problems:
            findings.append(Finding(
                "ppermute-coverage", target,
                f"collective-permute {inst.name} pairs {pairs} are not a "
                f"permutation of the full {num_devices}-device group: "
                + "; ".join(problems)))
    return findings


# ---------------------------------------------------------------------------
# Rule: unweighted-psum-mean
# ---------------------------------------------------------------------------

_PASS_THROUGH_OPS = {"broadcast", "convert", "copy", "reshape", "transpose",
                     "bitcast", "bitcast-convert"}


def _resolve(comp: HloComputation, name: str) -> Optional[HloInstruction]:
    """Chase through shape/dtype-only ops to the defining instruction."""
    seen = set()
    while name in comp.instructions and name not in seen:
        seen.add(name)
        inst = comp.instructions[name]
        if inst.opcode in _PASS_THROUGH_OPS and inst.operands:
            name = inst.operands[0]
            continue
        return inst
    return None


def _scalar_constant(inst: Optional[HloInstruction]) -> Optional[float]:
    if inst is None or inst.opcode != "constant" or not inst.operands_raw:
        return None
    try:
        return float(inst.operands_raw[0])
    except ValueError:
        return None


def _group_size(inst: HloInstruction, num_devices: int) -> int:
    groups = replica_groups(inst)
    return len(groups[0]) if groups else num_devices


def check_unweighted_psum_mean(module: HloModule, target: str,
                               num_devices: int) -> List[Finding]:
    findings: List[Finding] = []
    for comp, inst in module.all_instructions():
        if inst.opcode not in ("divide", "multiply") or len(inst.operands) != 2:
            continue
        a = _resolve(comp, inst.operands[0])
        b = _resolve(comp, inst.operands[1])
        pairs = [(a, b)] if inst.opcode == "divide" else [(a, b), (b, a)]
        for ar, const in pairs:
            if ar is None or ar.opcode not in ("all-reduce",
                                               "all-reduce-start"):
                continue
            c = _scalar_constant(const)
            if c is None or c == 0:
                continue
            n = _group_size(ar, num_devices)
            if n < 2:
                continue
            is_mean = (abs(c - n) < 1e-6 if inst.opcode == "divide"
                       else abs(c * n - 1.0) < 1e-6)
            if is_mean:
                findings.append(Finding(
                    "unweighted-psum-mean", target,
                    f"{inst.opcode} {inst.name} scales {ar.opcode} "
                    f"{ar.name} by the axis size {n} — an unweighted mean; "
                    f"this repo's aggregation is sample-count weighted "
                    f"(tree_weighted_mean_psum); suppress only if a true "
                    f"uniform mean is intended"))
                break
    return findings


# ---------------------------------------------------------------------------
# Per-program entry point
# ---------------------------------------------------------------------------

@dataclass
class ProgramComms:
    """One lowered program's communication + memory footprint."""
    target: str
    collective_count: int
    collective_bytes: int
    per_op: Dict[str, int]
    per_op_bytes: Dict[str, int]
    collectives: List[Dict]
    temp_bytes: Optional[int] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    peak_bytes: Optional[int] = None
    flops: Optional[float] = None

    def to_dict(self) -> Dict:
        return {
            "target": self.target,
            "collective_count": self.collective_count,
            "collective_bytes": self.collective_bytes,
            "per_op": self.per_op,
            "per_op_bytes": self.per_op_bytes,
            "collectives": self.collectives,
            "temp_bytes": self.temp_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "peak_bytes": self.peak_bytes,
            "flops": self.flops,
        }


def summarize_inventory(inventory: List[Dict]
                        ) -> Tuple[int, int, Dict[str, int], Dict[str, int]]:
    per_op: Dict[str, int] = {}
    per_op_bytes: Dict[str, int] = {}
    for c in inventory:
        per_op[c["op"]] = per_op.get(c["op"], 0) + 1
        per_op_bytes[c["op"]] = per_op_bytes.get(c["op"], 0) + c["bytes"]
    return (len(inventory), sum(c["bytes"] for c in inventory),
            per_op, per_op_bytes)


def _unbound_axes(jitted, args) -> Tuple[str, ...]:
    """The axis names a collective of `jitted` uses and its mesh does not
    bind, () when its lowering fails for another reason. Under shard_map's
    check_vma jax 0.9 reports such a name as a bare AssertionError from
    `core.pvary`, ahead of the NameError that says "unbound axis name", and
    the default traceback filtering drops that frame: lower once more with
    the filtering off and read the names where the assertion compared them
    (the collective's `axes` against the mesh's)."""
    import traceback

    import jax

    was = jax.config.jax_traceback_filtering
    jax.config.update("jax_traceback_filtering", "off")
    try:
        jitted.lower(*args)
    except AssertionError as e:
        frames = [f for f, _ in traceback.walk_tb(e.__traceback__)
                  if f.f_code.co_name == "pvary"]
        if frames:
            seen = frames[-1].f_locals
            bound = getattr(seen.get("cur_mesh"), "axis_names", ())
            return tuple(a for a in seen.get("axes", ()) if a not in bound)
    finally:
        jax.config.update("jax_traceback_filtering", was)
    return ()


def analyze_program(fn, args, target: str, *, num_devices: int,
                    params_bytes: Optional[int] = None,
                    compile: bool = True,
                    expect_resharding: bool = False
                    ) -> Tuple[Optional[ProgramComms], List[Finding]]:
    """Lower one program, inventory its collectives, run every HLO rule.

    Returns (ProgramComms or None, findings). An unbound axis name at
    lower time becomes the axis-name-mismatch finding (with no comms — the
    program never lowered); any other lowering error propagates.

    `expect_resharding` marks a GSPMD program (automatic partitioning):
    partitioner-inserted post-opt collectives are expected there and the
    optimized-vs-traced all-gather surplus check is skipped — the traced
    inventory and the peak-bytes budget remain the gates.
    """
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    try:
        lowered = jitted.lower(*args)
        pre_text = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    except Exception as e:  # jax raises NameError, wrapped variously
        why = str(e)
        if isinstance(e, AssertionError) and not why:
            why = ", ".join(f"unbound axis name: {a}"
                            for a in _unbound_axes(jitted, args))
        if "unbound axis name" not in why:
            raise
        return None, [Finding(
            "axis-name-mismatch", target,
            f"lowering failed: {why} — a collective names a mesh axis "
            f"the program's shard_map does not bind")]

    module = parse_hlo_text(pre_text)
    inventory = collective_inventory(module)
    findings: List[Finding] = []
    findings += check_collective_in_loop(module, target)
    findings += check_ppermute_coverage(module, target, num_devices)
    findings += check_unweighted_psum_mean(module, target, num_devices)

    opt_text = None
    temp = arg_b = out_b = peak = flops = None
    if compile:
        compiled = lowered.compile()
        try:
            opt_text = compiled.as_text()
        except Exception:
            opt_text = None
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
        if mem is not None:
            temp = int(getattr(mem, "temp_size_in_bytes", 0))
            arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
            out_b = int(getattr(mem, "output_size_in_bytes", 0))
            peak = temp + arg_b + out_b
        try:
            cost = compiled.cost_analysis()
        except Exception:
            cost = None
        if cost:
            entries = cost if isinstance(cost, (list, tuple)) else [cost]
            f = sum(float(c.get("flops", 0.0)) for c in entries
                    if isinstance(c, dict))
            flops = f if f > 0 else None
    findings += check_accidental_replication(
        module, target, params_bytes=params_bytes, optimized_text=opt_text,
        expect_resharding=expect_resharding)

    count, total_bytes, per_op, per_op_bytes = summarize_inventory(inventory)
    comms = ProgramComms(
        target=target, collective_count=count,
        collective_bytes=total_bytes, per_op=per_op,
        per_op_bytes=per_op_bytes, collectives=inventory,
        temp_bytes=temp, argument_bytes=arg_b, output_bytes=out_b,
        peak_bytes=peak, flops=flops)
    return comms, findings
