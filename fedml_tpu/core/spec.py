"""graft-matrix: the declarative round-program spec (ROADMAP item 5).

One table for the whole feature matrix. Every cross-cutting feature axis
(drive backend, silo grouping, tensor sharding, LoRA, buffered aggregation,
the round pipeline, the multi-round superstep, the update codec, the
aggregator rule, chaos masking, ledger stats, personalization) is declared
ONCE here — its legal levels, how a level projects onto `FedConfig`, and a
single centralized compatibility relation (`EXCLUSIONS` + `REQUIREMENTS`).
`FedConfig.validate()` and the formerly-scattered per-module `ValueError`s
in algorithms/fedavg.py and algorithms/engine.py are lookups into these
tables, so exclusion logic exists in exactly one place and the analysis
layer can *enumerate* what the runtime *enforces*.

The second half of the table is the program surface: `DRIVE_SPECS` declares,
per registered drive config, the budget-pinned programs that drive's loop
can reach — base points plus codec twins EXPANDED from the codec axis
(``codec_twins``), not hand-listed per drive. `analysis/targets.py` derives
`enumerate_drive_programs` from these points (byte-identical names to the
hand enumeration it replaced), and `analysis/matrix_engine.py` (--matrix)
cross-checks COMPILE_BUDGET.json / COMMS_BUDGET.json coverage against them:
a reachable point nobody pinned is a finding, as is a stale pin no legal
config can reach. Expanding the sharded drive's codec twins from the axis
(all armed levels, not a hand slice) is exactly what surfaced
``sharded.round[lr,f32,fedavg,8,topk64]`` — reachable since graft-codec
(the shard_map branch wraps ANY codec), pinned only now.

This module imports neither jax nor FedConfig at module scope — validation
must stay import-cheap from core/config.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, Tuple

# --------------------------------------------------------------------- axes


@dataclass(frozen=True)
class Axis:
    """One feature axis: its legal levels and how a level projects onto
    FedConfig fields. `overrides` is None for axes that are NOT config
    fields (aggregator name, chaos arming, stats collection — those ride
    constructor args / builder kwargs, see ASSEMBLERS below)."""

    name: str
    levels: Tuple[str, ...]
    default: str
    overrides: Optional[Mapping[str, Mapping[str, Any]]]
    doc: str


AXES: Dict[str, Axis] = {a.name: a for a in (
    Axis("backend", ("vmap", "shard_map"), "vmap",
         {"vmap": {"backend": "vmap"},
          "shard_map": {"backend": "shard_map"}},
         "single-chip vmap engine vs the 1-D 'clients' shard_map mesh"),
    Axis("silo", ("off", "on"), "off",
         {"off": {"silo_threshold": 0}, "on": {"silo_threshold": 32}},
         "silo-grouped conv execution (ResNetCifar models, one chip)"),
    Axis("tensor", ("off", "shards", "shard_step"), "off",
         {"off": {"tensor_shards": 0},
          "shards": {"tensor_shards": 4},
          "shard_step": {"tensor_shards": 4, "shard_step": True}},
         "2-D ('clients','tensor') mesh: storage-sharded round, or the "
         "GSPMD activation-sharded client step on top of it"),
    Axis("lora", ("off", "on"), "off",
         {"off": {"lora_rank": 0}, "on": {"lora_rank": 8}},
         "federate rank-r adapters only (models/lora.py seam)"),
    Axis("buffer", ("off", "on"), "off",
         {"off": {"buffer_size": 0}, "on": {"buffer_size": 5}},
         "staleness-aware buffered aggregation (FedBuff admit/commit)"),
    Axis("pipeline", ("off", "on"), "off",
         {"off": {"pipeline_depth": 0}, "on": {"pipeline_depth": 2}},
         "async round pipeline: staged cohorts donated into the round"),
    Axis("superstep", ("off", "on"), "off",
         {"off": {"rounds_per_dispatch": 1},
          "on": {"rounds_per_dispatch": 4}},
         "K federated rounds fused into one scanned device program"),
    Axis("codec", ("none", "int8", "topk"), "none",
         {"none": {"update_codec": "none"},
          "int8": {"update_codec": "int8"},
          "topk": {"update_codec": "topk"}},
         "compressed update transport (graft-codec)"),
    Axis("aggregator", ("fedavg", "fedopt", "robust", "fednova"), "fedavg",
         None, "server aggregation rule (FedAvgAPI aggregator_name arg)"),
    Axis("chaos", ("off", "on"), "off",
         None, "in-round participation mask + quarantine (FaultPlan arm)"),
    Axis("stats", ("off", "on"), "off",
         None, "per-cohort ledger stats rows (collect_stats builder kwarg)"),
    Axis("personalization", ("off", "on"), "off",
         {"off": {"personalize": False}, "on": {"personalize": True}},
         "per-client personal adapter rows from the mmap bank "
         "(models/adapter_bank.py): trained alongside the global "
         "adapters, returned UNAGGREGATED — never on the wire"),
)}


def _tensor_level(cfg) -> str:
    if cfg.tensor_shards > 0:
        return "shard_step" if getattr(cfg, "shard_step", False) else "shards"
    return "off"


# FedConfig -> axis level, per config-backed axis (non-config axes always
# project to their default: the config cannot see them).
_PROJECTIONS: Dict[str, Callable] = {
    "backend": lambda cfg: cfg.backend,
    "silo": lambda cfg: "on" if cfg.silo_threshold > 0 else "off",
    "tensor": _tensor_level,
    "lora": lambda cfg: "on" if getattr(cfg, "lora_rank", 0) > 0 else "off",
    "buffer": lambda cfg: "on" if cfg.buffer_size > 0 else "off",
    "pipeline": lambda cfg: "on" if cfg.pipeline_depth > 0 else "off",
    "superstep": lambda cfg: "on" if cfg.rounds_per_dispatch > 1 else "off",
    "codec": lambda cfg: cfg.update_codec,
    "personalization": lambda cfg: ("on" if getattr(cfg, "personalize",
                                                    False) else "off"),
}


def axis_levels(cfg) -> Dict[str, str]:
    """Project a FedConfig onto the axis table (non-config axes default)."""
    return {name: (_PROJECTIONS[name](cfg) if name in _PROJECTIONS
                   else axis.default)
            for name, axis in AXES.items()}


def point_config(levels: Mapping[str, str], **extra):
    """A representative FedConfig at a matrix point (config axes only)."""
    from fedml_tpu.core.config import FedConfig  # late: config imports us

    overrides: Dict[str, Any] = dict(model="lr", batch_size=2, epochs=1,
                                     dtype="float32")
    for axis in AXES.values():
        if axis.overrides is None:
            continue
        overrides.update(axis.overrides[levels.get(axis.name, axis.default)])
    overrides.update(extra)
    return FedConfig(**overrides)


# --------------------------------------------- the compatibility relation


@dataclass(frozen=True)
class Exclusion:
    """Levels of `axis_a` that cannot combine with levels of `axis_b`.
    `reason` is the exact ValueError text `validate_config` raises — the
    strings the test suite (and users' tracebacks) match on, preserved
    verbatim from the per-module checks this table replaced."""

    axis_a: str
    levels_a: Tuple[str, ...]
    axis_b: str
    levels_b: Tuple[str, ...]
    reason: str


_CODEC_ON = ("int8", "topk")
_TENSOR_ON = ("shards", "shard_step")

_BUFFER_REASON = (
    "buffer_size (staleness-aware buffered aggregation) drives "
    "the single-controller vmap engine; the sharded admit/commit "
    "twin (parallel.sharded.build_sharded_buffer_fns) is a "
    "program-level building block — combine buffer_size with "
    "neither backend='shard_map', tensor_shards, nor "
    "silo_threshold")
_SUPERSTEP_REASON = (
    "rounds_per_dispatch (the multi-round superstep) fuses K "
    "rounds into ONE program on the single-chip vmap engine — "
    "there is no per-round host gap left for the pipeline or "
    "buffer to exploit, and the sharded/silo lowerings "
    "have no superstep twin; combine it with none of "
    "pipeline_depth / buffer_size / backend='shard_map' / "
    "tensor_shards / silo_threshold")
_TENSOR_REASON = (
    "tensor_shards already places rounds on its own 2D "
    "('clients', 'tensor') mesh — combine it with neither "
    "silo_threshold nor backend='shard_map'")
_PFL_REASON = (
    "personalize (per-client adapter rows, models/adapter_bank.py) "
    "drives the single-chip vmap engine's eager or pipelined loop — "
    "the superstep/buffered/shard_map/tensor/silo lowerings "
    "have no personal-row seam; drop personalize or the conflicting "
    "setting")

# Order matters: for a config violating several pairs, the FIRST matching
# exclusion's reason is raised — the order below mirrors the firing order
# of the scattered checks this table replaced (fedavg.py), so existing
# tracebacks and test matches are unchanged.
EXCLUSIONS: Tuple[Exclusion, ...] = (
    Exclusion("codec", _CODEC_ON, "silo", ("on",),
              "update_codec has no seam in the silo-grouped lowering "
              "(silos merge clients before any update crosses a wire) — "
              "drop one of update_codec / silo_threshold"),
    Exclusion("buffer", ("on",), "backend", ("shard_map",), _BUFFER_REASON),
    Exclusion("buffer", ("on",), "tensor", _TENSOR_ON, _BUFFER_REASON),
    Exclusion("buffer", ("on",), "silo", ("on",), _BUFFER_REASON),
    Exclusion("superstep", ("on",), "pipeline", ("on",), _SUPERSTEP_REASON),
    Exclusion("superstep", ("on",), "buffer", ("on",), _SUPERSTEP_REASON),
    Exclusion("superstep", ("on",), "backend", ("shard_map",),
              _SUPERSTEP_REASON),
    Exclusion("superstep", ("on",), "tensor", _TENSOR_ON, _SUPERSTEP_REASON),
    Exclusion("superstep", ("on",), "silo", ("on",), _SUPERSTEP_REASON),
    Exclusion("silo", ("on",), "backend", ("shard_map",),
              "silo_threshold (the single-chip silo-grouped conv path) "
              "and backend='shard_map' are mutually exclusive — the "
              "grouped lowering merges silos on ONE chip; drop one of the "
              "two settings"),
    Exclusion("tensor", _TENSOR_ON, "silo", ("on",), _TENSOR_REASON),
    Exclusion("tensor", _TENSOR_ON, "backend", ("shard_map",),
              _TENSOR_REASON),
    # A runtime gate lifted into the table (the matrix's trace probes found
    # it firing deep inside a builder — now it is also a config-time
    # answer). Reason verbatim from the runtime raise.
    Exclusion("codec", _CODEC_ON, "tensor", ("shard_step",),
              "--shard_step runs under GSPMD automatic partitioning — the "
              "codec transports are manual shard_map collectives and do "
              "not compose with it. Drop --shard_step (the storage-sharded "
              "tensor round supports codecs) or --update_codec."),
    # graft-pfl: the personalized round is a vmap-engine program (eager or
    # pipelined drive) — the other families have no personal-row seam, and
    # the bank scatter rides the per-round RoundRecordLog flush that the
    # superstep/buffered loops restructure.
    Exclusion("personalization", ("on",), "superstep", ("on",),
              _PFL_REASON),
    Exclusion("personalization", ("on",), "buffer", ("on",), _PFL_REASON),
    Exclusion("personalization", ("on",), "backend", ("shard_map",),
              _PFL_REASON),
    Exclusion("personalization", ("on",), "tensor", _TENSOR_ON,
              _PFL_REASON),
    Exclusion("personalization", ("on",), "silo", ("on",), _PFL_REASON),
    Exclusion("personalization", ("on",), "codec", _CODEC_ON,
              "update codecs compress the WIRE tree, and personal rows "
              "never reach the wire — a codec on the personalized round "
              "would stage deltas for a tree the client step does not "
              "ship; drop one of update_codec / personalize"),
    Exclusion("personalization", ("on",), "lora", ("off",),
              "personalize trains a PERSONAL rank-r adapter per client on "
              "top of the shared adapters — it requires lora_rank > 0 "
              "(models/adapter_bank.py rows are LoRA adapter trees)"),
)


@dataclass(frozen=True)
class Constraint:
    """An n-ary exclusion: illegal when EVERY clause ``(axis, levels)``
    holds simultaneously. The pairwise EXCLUSIONS stay pairwise (that is
    what users trip and tests match); this table exists for the few
    genuinely three-way interactions the trace probes surfaced."""

    clauses: Tuple[Tuple[str, Tuple[str, ...]], ...]
    reason: str


CONSTRAINTS: Tuple[Constraint, ...] = (
    # parallel/tensor.py's codec gate: the storage-sharded round decodes
    # updates before aggregation, and robust/fednova must see RAW deltas
    Constraint(
        (("tensor", _TENSOR_ON), ("codec", _CODEC_ON),
         ("aggregator", ("robust", "fednova"))),
        "update codecs on the tensor path support fedavg/fedopt only: "
        "robust clips whole-tree norms of raw client deltas and fednova "
        "recombines per-client taus — both would silently run on "
        "already-decoded values"),
    # CodecAggregator._stage (codecs/transport.py) maps deltas over the
    # FULL federated tree, but the LoRA client step ships adapters only —
    # the engine/shard_map codec wrap dies on the asymmetric trees at
    # trace time (Dict key mismatch). Two paths ARE adapter-aware: the
    # tensor-sharded round (parallel/tensor.py, its lora8,topk64 twin is
    # COMMS-pinned) and the buffered admit, whose memoryless delta runs
    # against the stripped dispatch base (algorithms/buffered.py passes
    # strip_lora_base(globals); tests/test_lora.py pins LoRA x topk on
    # the buffered drive end-to-end).
    Constraint(
        (("codec", _CODEC_ON), ("lora", ("on",)), ("tensor", ("off",)),
         ("buffer", ("off",))),
        "update codecs reach LoRA runs only through the tensor-sharded "
        "round or buffered admission (the adapter-aware transports in "
        "parallel/tensor.py and the buffered admit) — the vmap/shard_map "
        "CodecAggregator stages deltas for the full federated tree while "
        "the LoRA client step ships adapters only; drop one of "
        "update_codec / lora_rank, or add --tensor_shards / --buffer_size"),
)


@dataclass(frozen=True)
class Requirement:
    """A value constraint that applies when `axis` sits at `level`.
    `check` takes the FedConfig and returns True when satisfied."""

    axis: str
    level: str
    check: Callable
    reason: str


REQUIREMENTS: Tuple[Requirement, ...] = (
    Requirement("personalization", "on", lambda cfg: cfg.lora_rank > 0,
                "personalize requires lora_rank > 0 — the personal row "
                "is a rank-r adapter tree (models/adapter_bank.py)"),
)


def _level(levels: Mapping[str, str], axis: str) -> str:
    return levels.get(axis, AXES[axis].default)


def first_violation(levels: Mapping[str, str]):
    """The first EXCLUSIONS (then CONSTRAINTS) entry an axis-level
    assignment violates — both carry ``.reason``; None when legal."""
    for exc in EXCLUSIONS:
        if (_level(levels, exc.axis_a) in exc.levels_a
                and _level(levels, exc.axis_b) in exc.levels_b):
            return exc
    for con in CONSTRAINTS:
        if all(_level(levels, axis) in lvls for axis, lvls in con.clauses):
            return con
    return None


def is_legal(levels: Mapping[str, str]) -> bool:
    return first_violation(levels) is None


def validate_config(cfg, axes: Optional[Mapping[str, str]] = None) -> None:
    """Raise ValueError (with the table's reason) for the first exclusion
    or requirement `cfg` violates. `axes` overlays non-config axis levels
    (aggregator/chaos/stats) when the caller knows them. This is the ONE
    compatibility check — FedConfig.validate() and FedAvgAPI.__init__
    delegate here."""
    levels = axis_levels(cfg)
    if axes:
        levels.update(axes)
    exc = first_violation(levels)
    if exc is not None:
        raise ValueError(exc.reason)
    for req in REQUIREMENTS:
        if levels.get(req.axis) == req.level and not req.check(cfg):
            raise ValueError(req.reason)


# ------------------------------------------------------- family dispatch


# Which axes actually REACH each round family's builder — the rest ride
# host-side (pipeline staging, the chaos arrival plan) or are excluded by
# the tables, so they cannot alter the traced program. Consumed by the
# matrix engine's cover dedup and by core/builder.py's composition.
_FAMILY_TRACE_AXES: Dict[str, Tuple[str, ...]] = {
    "engine": ("aggregator", "codec", "lora", "chaos", "stats", "pipeline",
               "personalization"),
    "superstep": ("aggregator", "codec", "lora", "chaos", "stats"),
    "buffered": ("aggregator", "codec", "lora", "stats", "pipeline"),
    "sharded": ("aggregator", "codec", "lora", "stats"),
    "tensor_round": ("aggregator", "codec", "lora", "stats", "pipeline"),
    "tensor_step": ("aggregator", "lora", "stats", "pipeline"),
    "silo": ("aggregator", "lora"),
}


def point_family(levels: Mapping[str, str]) -> str:
    """The round family FedAvgAPI's dispatch picks for this assignment
    (mirrors the branch order in algorithms/fedavg.py — pinned by
    tests/test_matrix.py::test_point_family_mirrors_fedavg_dispatch_order)."""
    if levels.get("superstep") == "on":
        return "superstep"
    if levels.get("buffer") == "on":
        return "buffered"
    if levels.get("backend") == "shard_map":
        return "sharded"
    if levels.get("tensor") == "shards":
        return "tensor_round"
    if levels.get("tensor") == "shard_step":
        return "tensor_step"
    if levels.get("silo") == "on":
        return "silo"
    return "engine"


def trace_key(levels: Mapping[str, str]) -> Tuple:
    """Dedup key for traced programs: family plus the levels of the axes
    that reach its builder."""
    fam = point_family(levels)
    return (fam,) + tuple(
        (a, levels.get(a, "off")) for a in _FAMILY_TRACE_AXES[fam])


# ------------------------------------------------------- program surface


@dataclass(frozen=True)
class ProgramPoint:
    """One budget-pinned program: a name (family prefix + bracketed parts,
    e.g. ``sharded.round[lr,f32,fedavg,8,int8]``), the axis levels it
    exercises, its distinct-jit-signature count, and tracer options
    (codec/k/lora/mesh/...) consumed by analysis/targets.py."""

    family: str
    parts: Tuple[str, ...]
    axes: Tuple[Tuple[str, str], ...] = ()
    signatures: int = 1
    opts: Tuple[Tuple[str, Any], ...] = ()

    @property
    def name(self) -> str:
        return f"{self.family}[{','.join(self.parts)}]"

    def opt(self, key: str, default=None):
        return dict(self.opts).get(key, default)

    def level(self, axis: str) -> str:
        return dict(self.axes).get(axis, AXES[axis].default)


def codec_tag(level: str, k: int) -> str:
    """The budget-name tag of a codec axis level at a drive's COMMS-twin k
    (``int8`` carries no k; ``topk`` pins it: ``topk64``)."""
    return "int8" if level == "int8" else f"topk{k}"


@dataclass(frozen=True)
class CodecTwin:
    """Codec-on twins of `base`, EXPANDED from the codec axis: one twin
    per armed level, named by appending ``codec_tag(level, k)``. Arming
    `levels` is a statement about the runtime ("this drive's loop wraps
    any of these codecs"), so a missing budget pin becomes a matrix
    finding instead of a silent gap."""

    base: ProgramPoint
    levels: Tuple[str, ...]
    k: int

    def expand(self) -> Tuple[ProgramPoint, ...]:
        return tuple(
            ProgramPoint(
                self.base.family,
                self.base.parts + (codec_tag(level, self.k),),
                self.base.axes + (("codec", level),),
                self.base.signatures,
                self.base.opts + (("codec", level), ("codec_k", self.k)))
            for level in self.levels)


@dataclass(frozen=True)
class DriveSpec:
    """One registered drive config's reachable program surface."""

    drive: str
    points: Tuple[ProgramPoint, ...]
    codec_twins: Tuple[CodecTwin, ...] = ()
    evals: bool = True


# the three eval programs every FedAvgAPI drive shares (targets.py traces
# them; federation_eval has two signatures — Train/Test splits pack to
# different n_max)
EVAL_POINTS: Tuple[ProgramPoint, ...] = (
    ProgramPoint("engine.eval", ("lr", "f32")),
    ProgramPoint("engine.client_eval", ("lr", "f32")),
    ProgramPoint("engine.federation_eval", ("lr", "f32"), signatures=2),
)

_ENGINE_BASE = ProgramPoint("engine.round", ("lr", "f32", "fedavg"))
_ADMIT_BASE = ProgramPoint("buffered.admit", ("lr", "f32"),
                           axes=(("buffer", "on"),))
_BUFFERED_BASE = (
    ProgramPoint("buffered.client_step", ("lr", "f32"),
                 axes=(("buffer", "on"),)),
    _ADMIT_BASE,
    ProgramPoint("buffered.commit", ("lr", "f32", "fedavg"),
                 axes=(("buffer", "on"),)),
)
_TENSOR_BASE = ProgramPoint("tensor.round", ("lr", "f32", "fedavg", "2x4"),
                            axes=(("tensor", "shards"),),
                            opts=(("mesh", (2, 4)),))
_SHARDED_BASE = ProgramPoint("sharded.round", ("lr", "f32", "fedavg", "8"),
                             axes=(("backend", "shard_map"),),
                             opts=(("mesh", (8,)),))

DRIVE_SPECS: Dict[str, DriveSpec] = {s.drive: s for s in (
    DriveSpec("eager", ( _ENGINE_BASE,)),
    DriveSpec("pipelined", (
        ProgramPoint("engine.round", ("lr", "f32", "fedavg", "masked"),
                     axes=(("pipeline", "on"), ("chaos", "on")),
                     opts=(("masked", True),)),)),
    DriveSpec("finetune", (
        ProgramPoint("engine.round", ("lr", "f32", "fedavg", "lora8"),
                     axes=(("lora", "on"),), opts=(("lora_rank", 8),)),
        ProgramPoint("engine.round", ("lr", "f32", "fedavg", "lora8",
                                      "pfl"),
                     axes=(("lora", "on"), ("personalization", "on")),
                     opts=(("lora_rank", 8), ("pfl", True))),
        ProgramPoint("engine.superstep", ("lr", "f32", "fedavg", "k4"),
                     axes=(("superstep", "on"), ("chaos", "on"),
                           ("stats", "on")),
                     opts=(("rounds", 4),)),)),
    DriveSpec("buffered", _BUFFERED_BASE,
              codec_twins=(CodecTwin(_ADMIT_BASE, ("int8", "topk"), 16),)),
    DriveSpec("serving", (_ENGINE_BASE,) + _BUFFERED_BASE,
              codec_twins=(
                  # sync-tenant topk is structurally reachable too
                  # (JobDescriptor.codec rides update_codec into the vmap
                  # wrap) but deliberately outside the pinned static
                  # surface — see SCOPE_NOTES
                  CodecTwin(_ENGINE_BASE, ("int8",), 16),
                  CodecTwin(_ADMIT_BASE, ("int8", "topk"), 16))),
    DriveSpec("tensor", (
        _TENSOR_BASE,
        ProgramPoint("tensor.step", ("lr", "f32", "fedavg", "2x4"),
                     axes=(("tensor", "shard_step"),),
                     opts=(("mesh", (2, 4)),))),
              codec_twins=(CodecTwin(_TENSOR_BASE, ("int8", "topk"), 64),)),
    DriveSpec("sharded", (_SHARDED_BASE,),
              # ALL armed codec levels: the shard_map branch wraps any
              # codec (fedavg.py CodecAggregator), so the topk twin is as
              # reachable as the int8 one — the hand enumeration's [:1]
              # slice had silently left it ungated
              codec_twins=(CodecTwin(_SHARDED_BASE, ("int8", "topk"), 64),)),
    DriveSpec("hierarchical", (
        ProgramPoint("hier.round", ("lr", "f32", "2x4"),
                     axes=(("backend", "shard_map"),),
                     opts=(("mesh", (2, 4)),)),), evals=False),
    DriveSpec("silo", (
        ProgramPoint("silo.round", ("resnet20", "bf16", "fedavg"),
                     axes=(("silo", "on"),),
                     opts=(("model", "resnet20"), ("dtype", "bfloat16"))),)),
)}

# Deliberate static-surface scope decisions — the matrix engine echoes
# these in MATRIX.json instead of flagging them ungated. Each one names a
# reachable-but-unpinned program family and the reason it stays unpinned;
# deleting a note without pinning the program turns it into a finding.
SCOPE_NOTES: Tuple[Tuple[str, str], ...] = (
    ("eager:codec",
     "an eager --update_codec run wraps the vmap round "
     "(engine.round[lr,f32,fedavg,int8/topk*]) but the eager drive's "
     "max_compiles ceiling pins the codec-OFF loop; the codec-on sync "
     "program is budget-pinned under the serving drive instead"),
    ("serving:sync-topk",
     "a sync tenant with update_codec='topk' reaches "
     "engine.round[lr,f32,fedavg,topk16]; the pinned serving surface "
     "carries the int8 sync tenant as the codec-on representative — arm "
     "the topk level in DRIVE_SPECS['serving'] when a topk sync tenant "
     "lands"),
)


def drive_points(drive: str) -> Tuple[ProgramPoint, ...]:
    """Every budget-pinned ProgramPoint of one drive config (base points,
    expanded codec twins, shared evals)."""
    spec = DRIVE_SPECS[drive]
    points = list(spec.points)
    for twin in spec.codec_twins:
        points.extend(twin.expand())
    if spec.evals:
        points.extend(EVAL_POINTS)
    return tuple(points)


def drive_program_names(drive: str) -> Dict[str, int]:
    return {p.name: p.signatures for p in drive_points(drive)}


def all_reachable_programs() -> Dict[str, List[str]]:
    """program name -> drives that reach it, over every DRIVE_SPECS entry."""
    out: Dict[str, List[str]] = {}
    for drive in DRIVE_SPECS:
        for p in drive_points(drive):
            out.setdefault(p.name, []).append(drive)
    return out


def parse_program_name(name: str) -> Optional[Tuple[str, Tuple[str, ...]]]:
    """``family[p1,p2,...]`` -> (family, parts); None when malformed."""
    if not name.endswith("]") or "[" not in name:
        return None
    family, _, rest = name.partition("[")
    parts = tuple(rest[:-1].split(","))
    return (family, parts) if family and all(parts) else None


# The HLO-layer (COMMS_BUDGET.json) surface: analysis/comms.py PROGRAMS
# keys, declared here so the matrix engine can cross-check both directions
# (a comms PROGRAMS entry the spec does not declare, or a declared name
# comms.py no longer builds, is drift — matrix_engine asserts set
# equality against the live module).
COMMS_PROGRAM_NAMES: Tuple[str, ...] = (
    "sharded.round[lr,f32,fedavg]",
    "sharded.round[lr,f32,fedopt]",
    "sharded.round[lr,f32,robust]",
    "sharded.round[lr,f32,fednova]",
    "hier.round[lr,f32,2x4]",
    "tensor.round[tformer,f32,fedavg,2x4]",
    "tensor.round[tformer,f32,fedopt,2x4]",
    "tensor.round[lr,f32,robust,2x4]",
    "tensor.round[lr,f32,fednova,2x4]",
    "tensor.round[tformer,f32,fedavg,2x4,int8]",
    "tensor.round[tformer,f32,fedavg,2x4,topk64]",
    "tensor.round[tformer,f32,fedavg,2x4,lora8]",
    "tensor.round[tformer,f32,fedavg,2x4,lora8,topk64]",
    "tensor.step[tformer,f32,2x4]",
    "tensor.step[tformer,f32,2x4,replicated]",
    "buffered.admit[lr,f32]",
    "buffered.admit[lr,f32,int8]",
    "buffered.admit[lr,f32,topk16]",
    "buffered.commit[lr,f32,fedavg]",
    "buffered.commit[lr,f32,fedopt]",
    "gossip.mix[ring8]",
    "sequence.ring[b1,t64,h8,d16]",
    "sequence.ulysses[b1,t64,h8,d16]",
    "engine.round[lr,f32,fedavg]",
    "engine.chunked.chunk_fn[lr]",
    "engine.round[lr,f32,fedavg,lora8]",
    "engine.round[lr,f32,fedavg,lora8,pfl]",
)


# --------------------------------------------------- assembler kwarg table


# the feature-axis kwargs that are threaded through round assemblers by
# hand (the axis-drift rule's universe) — everything else in a signature
# is plumbing (trainer/cfg/aggregator/mesh), not a feature axis
AXIS_KWARGS: frozenset = frozenset({
    "donate_data", "donate_state", "param_sharding", "collect_stats",
    "codec", "chaos_armed", "in_graph_sampling",
})


@dataclass(frozen=True)
class AssemblerSpec:
    """One round assembler and the feature-axis kwargs its signature MUST
    carry per this spec. `note` documents deliberate absences (silo's
    missing collect_stats is a decision, not drift) — the axis-drift rule
    flags only divergence between a signature and this table."""

    module: str       # repo-relative path
    func: str
    axis_kwargs: Tuple[str, ...]
    note: str = ""


ASSEMBLERS: Tuple[AssemblerSpec, ...] = (
    AssemblerSpec("fedml_tpu/algorithms/engine.py", "build_round_fn",
                  ("donate_data", "param_sharding", "collect_stats",
                   "codec")),
    AssemblerSpec("fedml_tpu/algorithms/engine.py",
                  "build_round_fn_from_update",
                  ("donate_data", "collect_stats")),
    AssemblerSpec("fedml_tpu/algorithms/engine.py",
                  "build_personal_round_fn",
                  ("donate_data", "collect_stats"),
                  note="no codec kwarg by design: codec x personalization "
                       "is table-illegal (personal rows never hit the "
                       "wire)"),
    AssemblerSpec("fedml_tpu/algorithms/engine.py", "build_superstep_fn",
                  ("collect_stats", "chaos_armed", "in_graph_sampling")),
    AssemblerSpec("fedml_tpu/algorithms/buffered.py", "build_client_step_fn",
                  ("donate_data", "collect_stats"),
                  note="codec lives at admit (build_buffer_admit), not in "
                       "the cohort step"),
    AssemblerSpec("fedml_tpu/parallel/sharded.py", "build_sharded_round_fn",
                  ("collect_stats",),
                  note="codec rides the CodecAggregator wrap (FedAvgAPI), "
                       "not a builder kwarg; cohorts are mesh-resident so "
                       "there is no donate seam"),
    AssemblerSpec("fedml_tpu/parallel/tensor.py", "build_tensor_round_fn",
                  ("donate_state", "donate_data", "collect_stats", "codec")),
    AssemblerSpec("fedml_tpu/parallel/tensor.py",
                  "build_tensor_step_round_fn",
                  ("donate_state", "donate_data", "collect_stats", "codec")),
    AssemblerSpec("fedml_tpu/parallel/hierarchical.py",
                  "build_sharded_hierarchical_round_fn", (),
                  note="two-level group round: no stats (outputs are "
                       "group-major, not cohort-aligned) and no codec seam"),
    AssemblerSpec("fedml_tpu/algorithms/silo_grouped.py",
                  "build_silo_round_fn", (),
                  note="silo outputs don't align with the cohort axis — "
                       "no ledger stats by design (fedavg.py sets "
                       "_round_has_stats=False); no codec seam"),
)


# -------------------------------------------- structural-identity contracts


@dataclass(frozen=True)
class EquivSide:
    """One side of an equivalence contract: which assembly path emits the
    program (`builder` = core/builder.py's spec-point composition,
    `legacy` = the hand assembly preserved in analysis/equiv_engine.py as
    the certification baseline), at which axis levels, with which extra
    FedConfig overrides layered on top of the levels' projections."""

    kind: str                                       # "builder" | "legacy"
    levels: Tuple[Tuple[str, str], ...] = ()
    extra: Tuple[Tuple[str, Any], ...] = ()


@dataclass(frozen=True)
class EquivPair:
    """A standing structural-identity contract: the two sides must trace to
    the SAME canonical jaxpr (analysis/equiv_engine.py proves it, program
    by program). These are the repo's `structurally off == exact legacy
    program` claims, previously asserted only by running twin programs."""

    name: str
    lhs: EquivSide
    rhs: EquivSide
    doc: str


EQUIV_PAIRS: Tuple[EquivPair, ...] = (
    EquivPair(
        "codec-none.engine",
        EquivSide("builder", (("codec", "none"),)),
        EquivSide("legacy"),
        "the builder's one codec seam at level `none` emits the "
        "hand-assembled vmap round — codec-off rounds carry zero codec "
        "residue in the traced program"),
    EquivPair(
        "codec-none.sharded",
        EquivSide("builder", (("backend", "shard_map"), ("codec", "none"))),
        EquivSide("legacy", (("backend", "shard_map"),)),
        "codec-off shard_map round: the unwrapped aggregator keeps the "
        "exact legacy P() state spec and psum program"),
    EquivPair(
        "codec-none.tensor",
        EquivSide("builder", (("tensor", "shards"), ("codec", "none"))),
        EquivSide("legacy", (("tensor", "shards"),)),
        "codec-off tensor-sharded round: no quantized-gather/int8-psum "
        "collectives appear when the codec level is none"),
    EquivPair(
        "codec-none.buffered",
        EquivSide("builder", (("buffer", "on"), ("codec", "none"))),
        EquivSide("legacy", (("buffer", "on"),)),
        "codec-off buffered admission: the admit program takes no trailing "
        "delta base and moves full-width f32 rows"),
    EquivPair(
        "mask-omitted.engine",
        EquivSide("builder", (("pipeline", "on"),)),
        EquivSide("legacy"),
        "participation=None traces the exact legacy unmasked program — no "
        "masking ops, no extra metric keys — and cohort donation "
        "(pipeline staging) changes buffer aliasing only, never the "
        "computation (donated_invars are normalized away)"),
    EquivPair(
        "tensor-shards-1",
        EquivSide("builder", (("tensor", "shard_step"),),
                  (("tensor_shards", 1),)),
        EquivSide("legacy"),
        "at tensor_shards=1 the GSPMD activation-sharded step is "
        "structurally the plain vmap engine round — sharding constraints "
        "over a size-1 axis are placement no-ops (normalized away)"),
    EquivPair(
        "superstep-k1",
        EquivSide("builder", (("superstep", "on"),),
                  (("rounds_per_dispatch", 1),)),
        EquivSide("legacy"),
        "rounds_per_dispatch=1 NEVER builds the superstep scan — the "
        "builder emits the plain eager round program (the structurally-"
        "off path in algorithms/fedavg.py's dispatch)"),
    EquivPair(
        "lora-rank-0",
        EquivSide("builder", (("lora", "on"),), (("lora_rank", 0),)),
        EquivSide("legacy"),
        "lora_rank=0 is the identity wrap: maybe_wrap_lora returns the "
        "trainer unchanged and the round federates the full tree"),
    EquivPair(
        "personalization-off",
        EquivSide("builder", (("lora", "on"), ("personalization", "on")),
                  (("personalize", False),)),
        EquivSide("legacy", (("lora", "on"),)),
        "personalize=False NEVER builds the personalized round — the "
        "effective config projects the axis back off and the builder "
        "emits the exact legacy LoRA program (bank off == axis absent, "
        "zero personal-row residue in the traced jaxpr)"),
)
