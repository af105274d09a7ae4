"""The comparison that decides `correct`: what the timed path produced in
its first rounds against the plain reference's rounds from the same weights,
data and seed.

Numbers compared (each with a limit of its own in the configuration file,
set from readings on the chip — PERF.md section 2):

  loss_gap     worst round: |loss - reference| / |reference|; a round's loss
               is the sum of the last local epoch's per-row losses over rows
  total_gap    worst round: |rows trained - reference's|; exact, limit 0
  grad_gap     the first round's pseudo-gradient w0 - w1 (what the server
               step gets), by the worst parameter leaf: the gap between the
               program's norm and the reference's over the reference's norm
               of that leaf or of the median leaf, whichever is larger
  change_gap   the same measure of w_k - w0 after the k rounds followed.
               Leaves whose reference pseudo-gradient is under a thousandth
               of the median leaf's are left out (they move by rounding)
  state_gap    the same measure of the change of every variable that is not
               a parameter (BatchNorm running statistics), where there is one

A step that returns its state unchanged reads 1 on grad_gap and change_gap.
"""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


def diff_norms(a, b) -> dict[str, float]:
    """{leaf path: |a - b|} of two variable trees, on the host."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.device_get(_diff_norms(a, b)))
    return {jax.tree_util.keystr(path): float(v) for path, v in flat}


def leaf_gaps(prog: dict[str, float], ref: dict[str, float],
              skip: frozenset = frozenset()) -> dict[str, float]:
    """{leaf: |prog - ref| / max(ref, median ref)} over the leaves kept."""
    keys = [k for k in ref if k not in skip]
    if not keys:
        return {}
    median = statistics.median(ref[k] for k in keys)
    out = {}
    for k in keys:
        denom = max(ref[k], median)
        gap = abs(prog[k] - ref[k]) / denom if denom > 0 else (
            0.0 if prog[k] == 0 else math.inf)
        out[k] = gap if math.isfinite(gap) else math.inf
    return out


def worst_leaf_gap(prog: dict[str, float], ref: dict[str, float],
                   skip: frozenset = frozenset()) -> float:
    """max over leaves of |prog - ref| / max(ref, median ref)."""
    return max(leaf_gaps(prog, ref, skip).values(), default=0.0)


def worst_leaves(prog: dict, ref: dict, top: int = 4) -> dict:
    """For the record of a run: the `top` leaves of grad_gap and change_gap
    with the program's and the reference's norms, and the median leaf's gap
    (what to look at when a gap reads high — PERF.md section 2)."""
    out = {}
    for which in ("first", "change"):
        gaps = leaf_gaps(prog[which], ref[which])
        rows = sorted(gaps, key=gaps.get, reverse=True)[:top]
        out[which] = {
            "median_leaf_gap": statistics.median(gaps.values()),
            "worst": [[k, gaps[k], prog[which][k], ref[which][k]]
                      for k in rows]}
    return out


def _split(norms: dict[str, float]):
    params = {k: v for k, v in norms.items() if k.startswith("['params']")}
    state = {k: v for k, v in norms.items() if k not in params}
    return params, state


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    """`prog` and `ref`: {"losses": [..], "totals": [..], "first": norms of
    w0 - w1, "change": norms of w_k - w0}, norms as diff_norms gives them."""
    def rel(a, b):
        if not (math.isfinite(a) and math.isfinite(b)):
            return math.inf
        return abs(a - b) / abs(b) if b else (0.0 if a == b else math.inf)

    out = {
        "loss_gap": max(rel(a, b) for a, b in
                        zip(prog["losses"], ref["losses"])),
        "total_gap": max(abs(a - b) for a, b in
                         zip(prog["totals"], ref["totals"])),
    }
    p_first, _ = _split(prog["first"])
    r_first, _ = _split(ref["first"])
    out["grad_gap"] = worst_leaf_gap(p_first, r_first)
    median = statistics.median(r_first.values())
    still = frozenset(k for k, v in r_first.items() if v < 1e-3 * median)
    p_change, p_state = _split(prog["change"])
    r_change, r_state = _split(ref["change"])
    out["change_gap"] = worst_leaf_gap(p_change, r_change, still)
    if r_state:
        out["state_gap"] = worst_leaf_gap(p_state, r_state)
    return out


def verdict(nums: dict[str, float], limits: dict[str, float],
            not_compared: tuple = ()) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}). Every number needs a limit,
    or to be named in the configuration's `not_compared` (a number that has
    no upper reading in that cell — PERF.md section 2 — is read and left
    out); a number that is not finite fails."""
    table, ok = {}, True
    for name, value in nums.items():
        if name in not_compared:
            continue
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        table[name] = {"value": value, "limit": limits[name]}
        ok = ok and math.isfinite(value) and value <= limits[name]
    return ok, table
