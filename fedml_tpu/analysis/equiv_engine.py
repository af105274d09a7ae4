"""graft-lint's sixth engine (--equiv): the jaxpr equivalence prover.

ROADMAP item 5's certification half: core/builder.py claims that ONE
spec-point-driven composition (`build_round_program`) emits exactly the
programs the five hand-assembly sites used to thread by hand. This engine
PROVES it — structurally, program by program — instead of asserting it with
runtime twins:

1. **The standing contracts** (spec.EQUIV_PAIRS): every `structurally off
   == exact legacy program` claim the repo makes — codec level `none`
   leaves zero codec residue, `participation=None` traces the unmasked
   program, `tensor_shards=1` is the plain vmap round, `rounds_per_dispatch
   =1` never builds the superstep scan, `lora_rank=0` is the identity wrap
   — is proven by tracing both sides to jaxprs and diffing their canonical
   forms.

2. **Builder vs legacy over the matrix cover**: for every distinct
   trace-key of the pairwise cover, `build_round_program(point)` is traced
   against `legacy_round_programs(point)` — the hand assembly preserved
   here verbatim from the pre-builder matrix engine — and the jaxprs must
   be identical. Only after this proof were the five legacy assembly
   bodies deleted.

The canonicalizer makes `identical` mean *same computation*, not *same
trace accidents*: variables are alpha-renamed to definition-order numbers,
dead bindings are eliminated, params are key-sorted with volatile jit
plumbing (donated_invars, shardings, layouts, names) dropped, and
`sharding_constraint` equations — placement hints, never values — are
erased with their uses rewired. When two programs are NOT identical, the
differ reports the first divergence readably: equation index, primitive
pair, and each operand's provenance (which invar / which producing
equation).

CLI: ``python -m fedml_tpu.analysis --equiv [--fast] [--target SUBSTR]
[--json EQUIV.json]``. ``--fast`` proves one cover point per round family
(the EQUIV_PAIRS contracts always run in full); ``--target`` filters both
parts by substring.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

from fedml_tpu.analysis.core import Finding, Report

try:                                     # jax >= 0.4.33 public extension API
    from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var  # noqa: F401
except ImportError:                      # pragma: no cover - older jax
    from jax.core import ClosedJaxpr, Jaxpr, Literal, Var  # noqa: F401

# ---------------------------------------------------------------------------
# 1. the canonicalizer: jaxpr -> trace-accident-free structure
# ---------------------------------------------------------------------------

# jit/pjit plumbing that changes with donation, placement or naming but
# never with the computed values. `donated_invars` is what makes the
# mask-omitted/pipeline contract provable; the sharding/layout params are
# what makes tensor_shards=1 provable (a size-1 mesh axis shards nothing).
_VOLATILE_PARAMS = {
    "donated_invars", "name", "keep_unused", "inline", "in_shardings",
    "out_shardings", "in_layouts", "out_layouts", "resource_env",
    "compiler_options_kvs",
}

# placement hints, never values: outvar == invar as far as the computation
# is concerned, so the eqn is erased and its uses rewired
_ERASED_PRIMITIVES = {"sharding_constraint"}

_ADDR_RE = re.compile(r" at 0x[0-9a-f]+")


def _canon_value(v) -> Any:
    """Canonical, hashable, address-free form of a param / literal value."""
    import numpy as np

    if isinstance(v, (ClosedJaxpr, Jaxpr)):
        return ("jaxpr", _canon_jaxpr_obj(v))
    if isinstance(v, np.ndarray):
        if v.dtype == object:          # e.g. pallas indexer trees: the
            return ("repr", _ADDR_RE.sub("", repr(v.tolist())))  # bytes
        return ("ndarray", str(v.dtype), v.shape, v.tobytes())   # are ptrs
    if isinstance(v, np.generic):
        return ("scalar", str(v.dtype), v.tobytes())
    if isinstance(v, (tuple, list)):
        return tuple(_canon_value(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((str(k), _canon_value(x)) for k, x in v.items()))
    if callable(v):                    # jit-captured callables: identity-free
        return ("callable", getattr(v, "__name__", type(v).__name__))
    tn = type(v).__name__
    if tn == "Mesh":
        return ("mesh", tuple(v.axis_names),
                tuple(v.shape[a] for a in v.axis_names))
    if tn in ("PartitionSpec", "NamedSharding", "GSPMDSharding"):
        return (tn, _ADDR_RE.sub("", str(v)))
    if isinstance(v, float) and v != v:
        # jax 0.9 hands a literal's value over as a python float: a NaN
        # (chaos' fill) has to equal itself for two programs to match
        return ("nan",)
    if isinstance(v, (bool, int, float, complex, str, bytes, type(None))):
        return v
    try:                              # jnp scalars and other array-likes
        arr = np.asarray(v)
        if arr.dtype != object:
            return ("ndarray", str(arr.dtype), arr.shape, arr.tobytes())
    except Exception:                                    # noqa: BLE001
        pass
    return ("repr", _ADDR_RE.sub("", repr(v)))


def _canon_jaxpr_obj(j) -> Tuple[Dict[str, Any], ...]:
    """Recursive seam for jaxpr-valued params (pjit/scan/shard_map bodies):
    (canonical dict,) so nested bodies get the full pipeline too."""
    if isinstance(j, ClosedJaxpr):
        return (canonicalize(j),)
    return (canonicalize(ClosedJaxpr(j, ())),)


def canonicalize(closed: ClosedJaxpr) -> Dict[str, Any]:
    """Alpha-rename + DCE + param normalization: two traces of the same
    computation canonicalize to the same (==-comparable) dict regardless
    of trace order accidents, donation/sharding plumbing, dead bindings
    or `sharding_constraint` placement hints.

    Returned keys: ``invars``/``consts`` (aval strings), ``eqns`` (tuples
    of (primitive, operands, out-avals, params)), ``outvars`` (operand
    forms), and ``provenance`` (operand number -> readable origin; derived,
    excluded from equality — see `equal`)."""
    jaxpr = closed.jaxpr

    # -- pass 1: erase placement-hint eqns, resolving chains a->b->c
    subst: Dict[int, Any] = {}

    def resolve(atom):
        while isinstance(atom, Var) and id(atom) in subst:
            atom = subst[id(atom)]
        return atom

    kept_pre = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _ERASED_PRIMITIVES and len(eqn.invars) == 1 \
                and len(eqn.outvars) == 1:
            subst[id(eqn.outvars[0])] = resolve(eqn.invars[0])
            continue
        kept_pre.append(eqn)

    outvars = [resolve(v) for v in jaxpr.outvars]

    # -- pass 2: DCE backwards from the (resolved) outvars; effectful eqns
    # (io/debug callbacks and friends) are live by definition
    live = {id(v) for v in outvars if isinstance(v, Var)}
    keep = [False] * len(kept_pre)
    for i in range(len(kept_pre) - 1, -1, -1):
        eqn = kept_pre[i]
        if eqn.effects or any(id(o) in live for o in eqn.outvars):
            keep[i] = True
            for a in eqn.invars:
                a = resolve(a)
                if isinstance(a, Var):
                    live.add(id(a))
    eqns = [e for e, k in zip(kept_pre, keep) if k]

    # -- pass 3: de-Bruijn-style renumbering in definition order, with a
    # readable provenance entry per number (the differ's operand labels)
    number: Dict[int, int] = {}
    provenance: Dict[int, str] = {}

    def define(var, origin: str) -> int:
        n = len(number)
        number[id(var)] = n
        provenance[n] = origin
        return n

    consts = []
    for k, (cv, cval) in enumerate(zip(jaxpr.constvars, closed.consts)):
        define(cv, f"const[{k}]")
        consts.append((str(cv.aval), _canon_value(cval)))
    for k, iv in enumerate(jaxpr.invars):
        define(iv, f"invar[{k}]")
    invars = [str(v.aval) for v in jaxpr.invars]

    def atom(a) -> Tuple:
        a = resolve(a)
        if isinstance(a, Literal):
            return ("lit", str(a.aval), _canon_value(a.val))
        if id(a) not in number:      # unreached defs (dropvars etc.)
            define(a, "?")
        return ("v", number[id(a)])

    canon_eqns = []
    for j, eqn in enumerate(eqns):
        operands = tuple(atom(a) for a in eqn.invars)
        outs = []
        for o in eqn.outvars:
            define(o, f"eqn[{j}]:{eqn.primitive.name}")
            outs.append(str(o.aval))
        params = tuple(sorted(
            (k, _canon_value(v)) for k, v in eqn.params.items()
            if k not in _VOLATILE_PARAMS))
        canon_eqns.append((eqn.primitive.name, operands, tuple(outs), params))

    return {
        "invars": invars,
        "consts": consts,
        "eqns": canon_eqns,
        "outvars": tuple(atom(v) for v in outvars),
        "provenance": provenance,
    }


def equal(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    """Structural identity of two canonical forms (provenance is derived
    labeling, not structure)."""
    keys = ("invars", "consts", "eqns", "outvars")
    return all(a[k] == b[k] for k in keys)


# ---------------------------------------------------------------------------
# 2. the differ: first divergence, readably
# ---------------------------------------------------------------------------


def _operand_str(op: Tuple, prov: Mapping[int, str]) -> str:
    if op[0] == "lit":
        return f"lit({op[2]!r}:{op[1]})"
    return f"v{op[1]}<{prov.get(op[1], '?')}>"


def _eqn_str(eqn: Tuple, prov: Mapping[int, str]) -> str:
    name, operands, outs, params = eqn
    ops = ", ".join(_operand_str(o, prov) for o in operands)
    ps = "" if not params else " {" + ", ".join(
        f"{k}={'<jaxpr>' if isinstance(v, tuple) and v and v[0] == 'jaxpr' else v!r}"
        for k, v in params) + "}"
    return f"{name}({ops}) -> {list(outs)}{ps}"


def first_divergence(a: Dict[str, Any], b: Dict[str, Any]) -> Optional[str]:
    """None when canonically identical; else a readable one-divergence
    report: where (signature / eqn index / outvars), the primitive pair,
    and each side's operand provenance."""
    if a["invars"] != b["invars"]:
        for k, (ia, ib) in enumerate(zip(a["invars"], b["invars"])):
            if ia != ib:
                return (f"signature: invar[{k}] aval {ia} != {ib}")
        return (f"signature: {len(a['invars'])} invars != "
                f"{len(b['invars'])}")
    if a["consts"] != b["consts"]:
        return "consts differ"
    ea, eb = a["eqns"], b["eqns"]
    for j, (qa, qb) in enumerate(zip(ea, eb)):
        if qa != qb:
            lines = [f"eqn[{j}]:",
                     f"  lhs: {_eqn_str(qa, a['provenance'])}",
                     f"  rhs: {_eqn_str(qb, b['provenance'])}"]
            if qa[0] != qb[0]:
                lines.insert(1, f"  primitive {qa[0]} != {qb[0]}")
            elif qa[1] != qb[1]:
                lines.insert(1, "  operands differ")
            elif qa[3] != qb[3]:
                ka = dict(qa[3]).keys() | dict(qb[3]).keys()
                bad = [k for k in sorted(ka)
                       if dict(qa[3]).get(k) != dict(qb[3]).get(k)]
                # a differing jaxpr-valued param recurses for the REAL spot
                for k in bad:
                    va, vb = dict(qa[3]).get(k), dict(qb[3]).get(k)
                    if (isinstance(va, tuple) and va and va[0] == "jaxpr"
                            and isinstance(vb, tuple) and vb
                            and vb[0] == "jaxpr"):
                        inner = first_divergence(va[1][0], vb[1][0])
                        if inner:
                            return (f"eqn[{j}] {qa[0]} param {k!r} body: "
                                    + inner)
                lines.insert(1, f"  params differ: {bad}")
            return "\n".join(lines)
    if len(ea) != len(eb):
        j = min(len(ea), len(eb))
        longer, side = (ea, "lhs") if len(ea) > len(eb) else (eb, "rhs")
        prov = (a if side == "lhs" else b)["provenance"]
        return (f"eqn[{j}]: {side} has {abs(len(ea) - len(eb))} extra "
                f"eqn(s), first: {_eqn_str(longer[j], prov)}")
    if a["outvars"] != b["outvars"]:
        return (f"outvars: {[_operand_str(o, a['provenance']) for o in a['outvars']]}"
                f" != {[_operand_str(o, b['provenance']) for o in b['outvars']]}")
    return None


# ---------------------------------------------------------------------------
# 3. the legacy baseline: the hand assembly, preserved verbatim
# ---------------------------------------------------------------------------


def legacy_round_programs(levels: Mapping[str, str], **extra):
    """The pre-builder hand assembly of a matrix point's round program(s) —
    the body analysis/matrix_engine.trace_point carried before it delegated
    to core/builder.build_round_program, preserved HERE as the
    certification baseline (same per-family feature threading, same trace
    geometry). `extra` layers FedConfig overrides like the builder's seam,
    so the EQUIV_PAIRS legacy sides can pin e.g. tensor_shards.

    Returns the point's RoundProgram tuple in the builder's program order
    (buffered: client_step, admit, commit)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.analysis.targets import (_abstract_round_args,
                                            _tiny_trainer)
    from fedml_tpu.codecs import make_codec
    from fedml_tpu.core.builder import RoundProgram
    from fedml_tpu.core.spec import point_config, point_family

    fam = point_family(levels)
    stats = levels.get("stats") == "on"
    donate = levels.get("pipeline") == "on"
    chaos = levels.get("chaos") == "on"
    model, dtype, fam_extra = "lr", "float32", {}
    if fam == "silo":
        model, dtype = "resnet20", "bfloat16"
    elif fam == "superstep":
        fam_extra["client_num_per_round"] = 2
    fam_extra.update(extra)
    cfg = point_config(levels, model=model, dtype=dtype, **fam_extra)

    trainer, shape, in_dtype = _tiny_trainer(model, dtype)
    if levels.get("lora") == "on" and cfg.lora_rank > 0:
        from fedml_tpu.models.lora import LoRATrainer

        trainer = LoRATrainer(trainer, rank=cfg.lora_rank)
    agg = make_aggregator(levels.get("aggregator", "fedavg"), cfg)
    codec = (make_codec(cfg.update_codec, cfg)
             if levels.get("codec", "none") != "none" else None)
    gv, x, y, counts, rng = _abstract_round_args(trainer, shape, in_dtype)
    agg_state = jax.eval_shape(agg.init_state, gv)
    mask = jax.ShapeDtypeStruct((2,), jnp.bool_)

    if fam == "engine":
        from fedml_tpu.algorithms.engine import build_round_fn

        rule = agg
        if codec is not None:
            from fedml_tpu.codecs.transport import CodecAggregator

            rule = CodecAggregator(codec, agg, slots=2)
            agg_state = jax.eval_shape(rule.init_state, gv)
        if levels.get("personalization") == "on":
            # the personalized hand assembly: thread the trailing
            # [C, ...] personal adapter rows exactly as the runtime
            # drive does (codec x personalization is table-illegal, so
            # `rule` is always the bare aggregator here)
            from fedml_tpu.algorithms.engine import build_personal_round_fn

            fn = build_personal_round_fn(trainer, cfg, rule,
                                         donate_data=donate,
                                         collect_stats=stats)
            personal = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((2,) + l.shape, l.dtype),
                gv["params"])
            args = (gv, agg_state, x, y, counts, rng, personal)
            if chaos:
                args = args + (mask,)
            return (RoundProgram("engine.round", fn, args),)
        fn = build_round_fn(trainer, cfg, rule, donate_data=donate,
                            collect_stats=stats)
        args = (gv, agg_state, x, y, counts, rng)
        if chaos:
            args = args + (mask,)
        return (RoundProgram("engine.round", fn, args),)

    if fam == "superstep":
        from fedml_tpu.algorithms.engine import build_superstep_fn

        rule = agg
        if codec is not None:
            from fedml_tpu.codecs.transport import CodecAggregator

            rule = CodecAggregator(codec, agg, slots=2)
            agg_state = jax.eval_shape(rule.init_state, gv)
        k = cfg.rounds_per_dispatch
        fn = build_superstep_fn(trainer, cfg, rule, k,
                                client_num_in_total=2, collect_stats=stats,
                                chaos_armed=chaos)

        def i32(s=()):
            return jax.ShapeDtypeStruct(s, jnp.int32)

        per_round = {"round_idx": i32((k,)), "idx": i32((k, 2)),
                     "nan": jax.ShapeDtypeStruct((k, 2), jnp.bool_),
                     "corrupt": jax.ShapeDtypeStruct((k, 2), jnp.bool_),
                     "participation": jax.ShapeDtypeStruct((k, 2),
                                                           jnp.bool_)}
        return (RoundProgram(f"engine.superstep[k{k}]", fn,
                             (gv, agg_state, x, y, counts, rng,
                              per_round)),)

    if fam == "buffered":
        # hand assembly matching analysis/targets._trace_buffered_programs'
        # shapes, with the stats/donation axes threaded (the runtime drive
        # threads them; the admit program is the CODEC admit when the point
        # arms a codec — algorithms/buffered.py admits through the codec
        # seam INSTEAD of the plain path, never both)
        from fedml_tpu.algorithms.aggregators import (build_buffer_admit,
                                                      build_buffer_commit,
                                                      make_staleness_discount)
        from fedml_tpu.algorithms.buffered import build_client_step_fn
        from fedml_tpu.models.lora import strip_lora_base

        step = build_client_step_fn(trainer, cfg, donate_data=donate,
                                    collect_stats=stats)
        result = jax.eval_shape(step, gv, x, y, counts, rng)
        if stats:
            result = result[0]
        k = cfg.buffer_size

        def row(l):
            return jax.ShapeDtypeStruct((k,) + l.shape[1:], l.dtype)

        def i32(s=()):
            return jax.ShapeDtypeStruct(s, jnp.int32)

        buf = {"vars": jax.tree.map(row, result.variables),
               "steps": i32((k,)),
               "weights": jax.ShapeDtypeStruct((k,), jnp.float32),
               "metrics": {name: row(v)
                           for name, v in result.metrics.items()},
               "birth": i32((k,)), "fill": i32()}
        admit = build_buffer_admit(codec=codec)
        admit_args = (buf, result.variables, result.num_steps,
                      result.metrics, counts, i32(), i32())
        if codec is not None:
            admit_args = admit_args + (strip_lora_base(gv),)
        commit = build_buffer_commit(agg, make_staleness_discount(0.5))
        return (
            RoundProgram("buffered.client_step", step,
                         (gv, x, y, counts, rng)),
            RoundProgram("buffered.admit", admit, admit_args),
            RoundProgram("buffered.commit", commit,
                         (gv, agg_state, buf, i32(), rng)),
        )

    if fam == "sharded":
        from jax.sharding import Mesh

        from fedml_tpu.parallel.sharded import build_sharded_round_fn

        rule = agg
        if codec is not None:
            from fedml_tpu.codecs.transport import CodecAggregator

            rule = CodecAggregator(codec, agg, slots=8)
            agg_state = jax.eval_shape(rule.init_state, gv)
        mesh = Mesh(np.array(jax.devices()[:8]), ("clients",))
        fn = build_sharded_round_fn(trainer, cfg, rule, mesh,
                                    collect_stats=stats)
        return (RoundProgram(
            "sharded.round", fn,
            (gv, agg_state,
             jax.ShapeDtypeStruct((8, 4) + shape[1:], in_dtype),
             jax.ShapeDtypeStruct((8, 4), jnp.int32),
             jax.ShapeDtypeStruct((8,), jnp.int32), rng)),)

    if fam in ("tensor_round", "tensor_step"):
        from jax.sharding import Mesh

        from fedml_tpu.parallel.tensor import (TensorSharding,
                                               build_tensor_round_fn,
                                               build_tensor_step_round_fn)

        ts = cfg.tensor_shards
        mesh = Mesh(np.array(jax.devices()[:2 * ts]).reshape(2, ts),
                    ("clients", "tensor"))
        sharding = TensorSharding.for_model(mesh, "lr")
        build = (build_tensor_step_round_fn if fam == "tensor_step"
                 else build_tensor_round_fn)
        fn = build(trainer, cfg, agg, sharding, donate_state=False,
                   donate_data=donate, collect_stats=stats, codec=codec)
        if codec is not None:
            from fedml_tpu.models.lora import strip_lora_base

            def init_st(g):
                # the residual mirrors the WIRE tree — adapters-only
                # under LoRA (same contract as analysis/comms.py)
                fed = strip_lora_base(g)
                resid = jax.tree.map(
                    lambda l: jnp.zeros(
                        (2,) + (l.shape
                                if jnp.issubdtype(l.dtype, jnp.inexact)
                                else ()), l.dtype), fed)
                return {"agg": agg.init_state(g), "codec": resid}

            agg_state = jax.eval_shape(init_st, gv)
        name = "tensor.step" if fam == "tensor_step" else "tensor.round"
        return (RoundProgram(name, fn, (gv, agg_state, x, y, counts, rng)),)

    if fam == "silo":
        from fedml_tpu.algorithms.silo_grouped import (build_silo_round_fn,
                                                       silo_trainer)

        st = silo_trainer(trainer, cfg.silo_threshold)
        fn = build_silo_round_fn(st, cfg, agg)
        return (RoundProgram("silo.round", fn,
                             (gv, agg_state, x, y, counts, rng)),)

    raise AssertionError(f"unknown family {fam!r}")  # pragma: no cover


# ---------------------------------------------------------------------------
# 4. the runner: EQUIV_PAIRS contracts + builder-vs-legacy over the cover
# ---------------------------------------------------------------------------


def _trace_canon(prog) -> Dict[str, Any]:
    import jax

    return canonicalize(jax.make_jaxpr(prog.fn)(*prog.args))


def _prove(name: str, lhs_progs, rhs_progs, rule: str,
           report: Report) -> Dict[str, Any]:
    """Prove two RoundProgram tuples pairwise canonically identical;
    append findings to `report`. Returns the JSON row."""
    report.mark(name)
    if len(lhs_progs) != len(rhs_progs):
        report.extend([Finding(
            rule, name,
            f"program count differs: lhs {len(lhs_progs)} "
            f"({[p.name for p in lhs_progs]}) != rhs {len(rhs_progs)} "
            f"({[p.name for p in rhs_progs]})")])
        return {"name": name, "programs": 0, "ok": False}
    ok = True
    for lp, rp in zip(lhs_progs, rhs_progs):
        ca, cb = _trace_canon(lp), _trace_canon(rp)
        if equal(ca, cb):
            continue
        ok = False
        div = first_divergence(ca, cb) or "canonical forms differ"
        report.extend([Finding(
            rule, f"{name}:{lp.name}",
            f"builder program {lp.name!r} is not the legacy program "
            f"{rp.name!r}: first divergence at {div}")])
    return {"name": name, "programs": len(lhs_progs), "ok": ok}


def _side_programs(side):
    from fedml_tpu.core.builder import build_round_program

    levels, extra = dict(side.levels), dict(side.extra)
    if side.kind == "builder":
        return build_round_program(levels, **extra)
    return legacy_round_programs(levels, **extra)


def run_equiv(repo_root: str, fast: bool = False,
              targets: Optional[Sequence[str]] = None
              ) -> Tuple[Report, Dict[str, Any]]:
    """Run both proof parts. Returns (report, EQUIV.json payload)."""
    from fedml_tpu.core import spec

    report = Report()
    wanted = list(targets or [])

    def selected(name: str) -> bool:
        return not wanted or any(w in name for w in wanted)

    # -- part A: the standing structurally-off contracts
    pairs: List[Dict[str, Any]] = []
    for pair in spec.EQUIV_PAIRS:
        if not selected(pair.name):
            continue
        row = _prove(pair.name, _side_programs(pair.lhs),
                     _side_programs(pair.rhs), "equiv-contract", report)
        row["doc"] = pair.doc
        pairs.append(row)

    # -- part B: builder vs the preserved hand assembly, over the cover
    from fedml_tpu.analysis.matrix_engine import (enumerate_matrix,
                                                  pairwise_cover, trace_key)
    from fedml_tpu.core.builder import build_round_program

    legal, _total = enumerate_matrix()
    keyed: Dict[Tuple, Mapping[str, str]] = {}
    for levels in pairwise_cover(legal):
        keyed.setdefault(trace_key(levels), levels)
    if fast:
        per_family: Dict[str, Tuple] = {}
        for key in sorted(keyed):
            per_family.setdefault(key[0], key)
        keyed = {k: keyed[k] for k in per_family.values()}

    cover: List[Dict[str, Any]] = []
    for key in sorted(keyed):
        levels = keyed[key]
        name = _key_name(key)
        if not selected(name):
            continue
        try:
            row = _prove(name, build_round_program(levels),
                         legacy_round_programs(levels),
                         "equiv-divergence", report)
        except Exception as e:                           # noqa: BLE001
            report.mark(name)
            report.extend([Finding(
                "equiv-divergence", name,
                f"side failed to build/trace: {type(e).__name__}: {e}")])
            row = {"name": name, "programs": 0, "ok": False}
        row["family"] = key[0]
        cover.append(row)

    payload = {
        "pairs": pairs,
        "cover": cover,
        "fast": fast,
        "lint": report.to_dict(),
    }
    return report, payload


def _key_name(key: Tuple) -> str:
    fam = key[0]
    on = [f"{a}={lv}" for a, lv in key[1:] if lv not in ("off", "none")]
    return fam + ("[" + ",".join(on) + "]" if on else "")


def format_equiv_table(payload: Mapping[str, Any]) -> str:
    rows = [("contract", "programs", "status")]
    for p in payload["pairs"]:
        rows.append((p["name"], str(p["programs"]),
                     "proven" if p["ok"] else "DIVERGED"))
    rows.append(("-- cover --", "", ""))
    for c in payload["cover"]:
        rows.append((c["name"], str(c["programs"]),
                     "proven" if c["ok"] else "DIVERGED"))
    w0 = max(len(r[0]) for r in rows)
    w1 = max(len(r[1]) for r in rows)
    lines = [f"{r[0]:<{w0}}  {r[1]:>{w1}}  {r[2]}" for r in rows]
    lines.insert(1, "-" * (w0 + w1 + 12))
    n_ok = sum(1 for r in payload["pairs"] + payload["cover"] if r["ok"])
    n = len(payload["pairs"]) + len(payload["cover"])
    lines.append(f"graft-equiv: {n_ok}/{n} proofs hold "
                 f"({len(payload['pairs'])} contracts, "
                 f"{len(payload['cover'])} cover points"
                 + (", fast" if payload.get("fast") else "") + ")")
    return "\n".join(lines)
