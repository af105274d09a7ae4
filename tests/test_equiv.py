"""graft-equiv (analysis/equiv_engine.py): the canonicalizer's PASS/FAIL
fixtures, the EQUIV_PAIRS contract plumbing, and bitwise spot-checks that
core/builder.build_round_program and the preserved legacy hand assembly
don't just trace to the same canonical jaxpr but COMPUTE the same values
on the four drive-loop families."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.analysis.equiv_engine import (canonicalize, equal,
                                             first_divergence,
                                             legacy_round_programs)
from fedml_tpu.core.builder import build_round_program


def _canon(fn, *args):
    return canonicalize(jax.make_jaxpr(fn)(*args))


def _sds(shape=(), dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------------ canonicalizer


def test_swapped_primitive_fails_with_primitive_pair():
    ca = _canon(lambda a, b: a + b, _sds((3,)), _sds((3,)))
    cb = _canon(lambda a, b: a - b, _sds((3,)), _sds((3,)))
    assert not equal(ca, cb)
    div = first_divergence(ca, cb)
    assert div and "add" in div and "sub" in div and "eqn[" in div


def test_perturbed_literal_fails():
    ca = _canon(lambda x: x + 1.0, _sds((3,)))
    cb = _canon(lambda x: x + 1.5, _sds((3,)))
    assert not equal(ca, cb)
    div = first_divergence(ca, cb)
    assert div and "eqn[" in div
    # a NaN literal (chaos' fill) is the same literal in both programs
    nan_a = _canon(lambda x: jnp.where(x > 0, x, jnp.nan), _sds((3,)))
    nan_b = _canon(lambda x: jnp.where(x > 0, x, jnp.nan), _sds((3,)))
    assert equal(nan_a, nan_b) and first_divergence(nan_a, nan_b) is None


def test_reordered_tree_keys_pass():
    # dict pytrees flatten key-sorted; insertion order is a trace accident
    def f(tree):
        return tree["a"] * tree["b"]

    ca = _canon(f, {"a": _sds((2,)), "b": _sds((2,))})
    cb = _canon(f, {"b": _sds((2,)), "a": _sds((2,))})
    assert equal(ca, cb)
    assert first_divergence(ca, cb) is None


def test_extra_dead_eqn_passes():
    def live(x):
        return x * 2.0

    def with_dead(x):
        _ = jnp.sin(x)          # traced, unused — DCE'd by canonicalization
        return x * 2.0

    ca, cb = _canon(live, _sds((4,))), _canon(with_dead, _sds((4,)))
    assert equal(ca, cb)


def test_sharding_constraint_is_erased():
    # placement hints are not computation: constraining over a mesh must
    # canonicalize away (what makes the tensor-shards-1 contract provable)
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("clients",))

    def plain(x):
        return x + 1.0

    def hinted(x):
        x = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, P()))
        return x + 1.0

    assert equal(_canon(plain, _sds((4,))), _canon(hinted, _sds((4,))))


def test_different_aggregator_fails_with_eqn_diff():
    # a REAL divergence (fedavg vs robust trimmed aggregation) must be
    # caught and reported at equation level, operands labeled by origin
    a = build_round_program({})[0]
    b = build_round_program({"aggregator": "robust"})[0]
    ca = _canon(a.fn, *a.args)
    cb = _canon(b.fn, *b.args)
    assert not equal(ca, cb)
    div = first_divergence(ca, cb)
    assert div is not None
    assert "eqn[" in div or "signature" in div


# ------------------------------------- builder vs legacy: bitwise spot-check


def _concretize(aval):
    """Deterministic concrete value for one abstract leaf: positive ints
    (counts/fills stay nonzero), small varied floats, all-True bools (every
    client participates — the masked and unmasked programs agree there)."""
    if not isinstance(aval, jax.ShapeDtypeStruct):
        return aval                       # already concrete (the rng key)
    n = max(1, int(np.prod(aval.shape)))
    flat = np.arange(n, dtype=np.float64)
    if jnp.issubdtype(aval.dtype, jnp.bool_):
        return jnp.ones(aval.shape, dtype=bool)
    if jnp.issubdtype(aval.dtype, jnp.integer):
        return jnp.asarray((flat % 3 + 1).reshape(aval.shape),
                           dtype=aval.dtype)
    return jnp.asarray(((flat % 7 + 1) / 7.0).reshape(aval.shape),
                       dtype=aval.dtype)


def _bitwise_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        eq = (np.array_equal(x, y, equal_nan=True)
              if x.dtype.kind == "f" else np.array_equal(x, y))
        if not eq:
            return False
    return True


@pytest.mark.parametrize("levels", [
    {},                             # engine vmap round
    {"backend": "shard_map"},       # 1-D sharded round
    {"tensor": "shards"},           # tensor-sharded round
    {"buffer": "on"},               # buffered client_step / admit / commit
], ids=["engine", "sharded", "tensor", "buffered"])
def test_builder_and_legacy_compute_bitwise_identical(levels):
    built = build_round_program(levels)
    legacy = legacy_round_programs(levels)
    assert len(built) == len(legacy)
    for bp, lp in zip(built, legacy):
        b_args = jax.tree.map(_concretize, bp.args)
        l_args = jax.tree.map(_concretize, lp.args)
        out_b = bp.fn(*b_args)
        out_l = lp.fn(*l_args)
        assert _bitwise_equal(out_b, out_l), (
            f"{bp.name} vs {lp.name}: outputs diverge bitwise")


# --------------------------------------------------- contract-trip plumbing


def test_mutated_equiv_pair_trips_with_readable_diff(monkeypatch):
    # the CI self-test's seam: perturb ONE contract (lora rank 0 -> 2) and
    # the engine must FAIL that contract with an eqn-level divergence while
    # the others keep proving
    import fedml_tpu.core.spec as spec
    from fedml_tpu.analysis.equiv_engine import run_equiv

    mutated = tuple(
        spec.EquivPair(p.name, spec.EquivSide(p.lhs.kind, p.lhs.levels,
                                              (("lora_rank", 2),)),
                       p.rhs, p.doc)
        if p.name == "lora-rank-0" else p
        for p in spec.EQUIV_PAIRS)
    monkeypatch.setattr(spec, "EQUIV_PAIRS", mutated)
    report, payload = run_equiv(".", fast=True, targets=["lora-rank-0"])
    assert not report.ok
    [row] = [r for r in payload["pairs"] if r["name"] == "lora-rank-0"]
    assert row["ok"] is False
    msg = report.findings[0].message
    assert "divergence" in msg and ("eqn[" in msg or "signature" in msg)


def test_equiv_pairs_all_prove(monkeypatch):
    # the unmutated contracts hold (the full sweep runs in ci_smoke; this
    # is the fast in-suite gate)
    from fedml_tpu.analysis.equiv_engine import run_equiv

    report, payload = run_equiv(".", fast=True)
    assert report.ok, report.summary()
    assert all(r["ok"] for r in payload["pairs"] + payload["cover"])
