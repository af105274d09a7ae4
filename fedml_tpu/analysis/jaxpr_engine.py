"""jaxpr-level lint rules.

All rules operate on a traced `Jaxpr`/`ClosedJaxpr` (or, for donation and
retrace, on the jitted callable itself) and return `core.Finding` lists —
nothing here raises on a violation; callers (CLI, tests) decide severity.

The recursive walker descends into scan/while/cond/pjit/custom_vmap
sub-jaxprs but NOT into pallas kernels: flash attention accumulates in f32
*inside* the kernel by design (bf16 in/out, f32 accumulate is the
numerically-correct flash formulation). The dtype knob governs what the
kernel is *fed*, which the surrounding dots cover.
"""

from __future__ import annotations

import re
import warnings
from collections import Counter
from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from fedml_tpu.analysis.core import Finding

MATMUL_PRIMS = ("dot_general", "conv_general_dilated")
# Host-callback primitives: any of these inside a round body forces a
# device->host round-trip per invocation — the dispatch-bound failure mode
# the chunked runner exists to avoid.
CALLBACK_PRIMS = ("pure_callback", "debug_callback", "io_callback")

_ALIASING_RE = re.compile(r"tf\.aliasing_output")


def _subjaxprs(eqn) -> Iterable[jex_core.Jaxpr]:
    for v in eqn.params.values():
        for sub in jax.tree.leaves(v, is_leaf=lambda l: isinstance(
                l, (jex_core.Jaxpr, jex_core.ClosedJaxpr))):
            if isinstance(sub, jex_core.ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, jex_core.Jaxpr):
                yield sub


def _as_jaxpr(jaxpr) -> jex_core.Jaxpr:
    return jaxpr.jaxpr if isinstance(jaxpr, jex_core.ClosedJaxpr) else jaxpr


def walk_eqns(jaxpr):
    """All eqns, recursing into scan/cond/pjit/... sub-jaxprs — but NOT into
    pallas kernels (see module docstring)."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        if "pallas" in eqn.primitive.name:
            continue
        for sub in _subjaxprs(eqn):
            yield from walk_eqns(sub)


def _walk_levels(jaxpr):
    """Each (sub-)jaxpr as its own level — dead-cast needs per-level
    producer/use maps, since vars don't cross jaxpr boundaries."""
    jaxpr = _as_jaxpr(jaxpr)
    yield jaxpr
    for eqn in jaxpr.eqns:
        if "pallas" in eqn.primitive.name:
            continue
        for sub in _subjaxprs(eqn):
            yield from _walk_levels(sub)


def check_dtype_policy(jaxpr, target: str,
                       policy=jnp.bfloat16) -> List[Finding]:
    """No floating matmul/conv may produce a dtype other than `policy`.
    Integer dots (e.g. turboaggregate's field arithmetic) pass, and so does
    a product that states `precision=HIGHEST` itself: the rule is for the
    SILENTLY float32 matmul, and a float32 product written out with its
    precision is a decision (an expert router's logits, which the source
    computes in float32 because the top-k flips on rounding)."""
    out: List[Finding] = []
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name not in MATMUL_PRIMS:
            continue
        stated = eqn.params.get("precision")
        if stated is not None and all(
                p == jax.lax.Precision.HIGHEST for p in (
                    stated if isinstance(stated, tuple) else (stated,))):
            continue
        dt = eqn.outvars[0].aval.dtype
        if jnp.issubdtype(dt, jnp.floating) and dt != policy:
            out.append(Finding(
                "dtype-policy", target,
                f"{eqn.primitive.name} lowers to {dt} under "
                f"policy={jnp.dtype(policy).name} (MXU half-rate)"))
    return out


def check_host_sync(jaxpr, target: str) -> List[Finding]:
    out: List[Finding] = []
    for eqn in walk_eqns(jaxpr):
        for prim in CALLBACK_PRIMS:
            if prim in eqn.primitive.name:
                out.append(Finding(
                    "host-sync", target,
                    f"{eqn.primitive.name} inside the traced body forces a "
                    f"device->host round-trip per step"))
    return out


def check_dead_cast(jaxpr, target: str) -> List[Finding]:
    """A->B->A convert_element_type round-trips where the intermediate is
    used exactly once. These burn VPU cycles and memory bandwidth for a
    no-op (modulo bf16 rounding, which makes them a *numerics* hazard too:
    the value silently lost mantissa bits on the way through)."""
    out: List[Finding] = []
    for level in _walk_levels(jaxpr):
        producer = {}
        uses: Counter = Counter()
        for eqn in level.eqns:
            for ov in eqn.outvars:
                producer[ov] = eqn
            for iv in eqn.invars:
                if isinstance(iv, jex_core.Var):
                    uses[iv] += 1
        for ov in level.outvars:
            if isinstance(ov, jex_core.Var):
                uses[ov] += 1
        for eqn in level.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            iv = eqn.invars[0]
            if not isinstance(iv, jex_core.Var):
                continue
            prev = producer.get(iv)
            if prev is None or prev.primitive.name != "convert_element_type":
                continue
            a = prev.invars[0].aval.dtype
            b = prev.outvars[0].aval.dtype
            c = eqn.outvars[0].aval.dtype
            if a == c and a != b and uses[iv] == 1:
                out.append(Finding(
                    "dead-cast", target,
                    f"{a}->{b}->{a} convert round-trip (intermediate used "
                    f"once) — drop both casts or keep the narrow dtype"))
    return out


def check_unconstrained_intermediate(jaxpr, target: str,
                                     tensor_axis_size: int) -> List[Finding]:
    """A tensor-sharded client step (GSPMD, mesh tensor axis > 1) whose
    matmul/einsum intermediates carry NO sharding constraint. Without the
    `constrain` hooks the partitioner is free to (and in practice does)
    re-gather every activation replicated between layers — the program
    still runs, still converges, and silently loses the entire per-device
    peak-memory win the tensor axis exists for. One finding per program:
    the fix is model-level (thread `parallel.activations.constrain` through
    the intermediates), not per-dot."""
    if tensor_axis_size <= 1:
        # a 1-shard tensor axis is trivially replicated; constraints are
        # structurally off there by design (bit-identity at shards=1)
        return []
    n_dots = 0
    n_constraints = 0
    for eqn in walk_eqns(jaxpr):
        name = eqn.primitive.name
        if name in MATMUL_PRIMS:
            n_dots += 1
        elif name == "sharding_constraint":
            n_constraints += 1
    if n_dots and not n_constraints:
        return [Finding(
            "unconstrained-intermediate", target,
            f"{n_dots} matmul intermediate(s), 0 sharding constraints on a "
            f"{tensor_axis_size}-way tensor axis — GSPMD re-gathers the "
            f"activations replicated between layers; mark the model's "
            f"attention/MLP/logits intermediates with "
            f"parallel.activations.constrain (or build the step with its "
            f"activation rule table)")]
    return []


def lint_jaxpr(jaxpr, target: str, policy=None,
               rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run the pure-jaxpr rules on one traced program. `policy=None` skips
    dtype-policy (f32-policy programs legitimately lower f32 dots)."""
    out: List[Finding] = []
    if policy is not None and (rules is None or "dtype-policy" in rules):
        out += check_dtype_policy(jaxpr, target, policy)
    if rules is None or "host-sync" in rules:
        out += check_host_sync(jaxpr, target)
    if rules is None or "dead-cast" in rules:
        out += check_dead_cast(jaxpr, target)
    return out


def check_donation(jitted, args, target: str,
                   argnums: Optional[Sequence[int]] = None,
                   expected_leaves: Optional[int] = None) -> List[Finding]:
    """Verify declared `donate_argnums` actually lower as donated buffers.

    Mechanism: a successfully-donated leaf shows up in the lowered MLIR as a
    `tf.aliasing_output = N` arg attribute; a declared-but-unusable donation
    (dtype/shape mismatch with every output) emits ZERO aliasing attrs plus
    a "Some donated buffers were not usable" UserWarning. Both signals are
    checked — the aliasing count is the ground truth, the warning gives the
    compiler's own reason when available. Pass the same `argnums` the jit
    declares (to size the expectation), or an explicit `expected_leaves`.
    """
    if expected_leaves is None:
        if argnums:
            expected_leaves = sum(
                len(jax.tree.leaves(args[i])) for i in argnums if i < len(args))
        else:
            expected_leaves = 1  # caller said "this should donate something"
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        txt = jitted.lower(*args).as_text()
    found = len(_ALIASING_RE.findall(txt))
    out: List[Finding] = []
    if found < expected_leaves:
        why = "; ".join(
            str(w.message) for w in caught
            if "donated" in str(w.message).lower()) or "no compiler diagnostic"
        out.append(Finding(
            "donation", target,
            f"declared donations lower as {found}/{expected_leaves} aliased "
            f"buffer(s) — the carry is being copied, not reused ({why})"))
    return out


def check_retrace(jitted, make_args, target: str, rounds: int = 3,
                  expected_signatures: int = 1) -> List[Finding]:
    """Drive `jitted` for `rounds` calls (args from `make_args(i)`) and
    assert one compile per shape signature. A cache that grows past
    `expected_signatures` means something non-hashable-stable (weak types,
    python scalars, shifting shapes) retraces every round — the
    compile-once contract every bench and the chunked runner assume."""
    for i in range(rounds):
        a = make_args(i)
        jax.block_until_ready(jitted(*a))
    size = jitted._cache_size()
    if size > expected_signatures:
        return [Finding(
            "retrace", target,
            f"{size} compiles across {rounds} same-signature rounds "
            f"(expected {expected_signatures}) — per-round retracing")]
    return []
