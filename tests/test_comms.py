"""HLO-layer lint: parser, every new rule on its deliberately-bad fixture,
the repo's parallel programs clean, and the COMMS_BUDGET.json gate.

The jax fixtures lower tiny shard_map programs on the 8-virtual-device
mesh from conftest.py with ``compile=False`` — pre-optimization collective
counts/bytes are independent of backend optimization flags, so these
assertions hold under the fast suite's ``--xla_backend_optimization_level=0``
as well as the CI smoke environment. Peak-memory (compile-dependent)
checks live only in the slow full run and the CLI."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu.analysis.hlo_engine import (
    analyze_program,
    check_collective_in_loop,
    collective_inventory,
    parse_hlo_text,
    shape_bytes,
)

N = 8


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("i",))


def _sharded1d(body, n_in=1):
    mesh = _mesh()
    specs = tuple(P("i") for _ in range(n_in))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=specs,
                                 out_specs=P("i")))


_S = jax.ShapeDtypeStruct((N, 16), jnp.float32)


# --------------------------------------------------------------------- parser

def test_shape_bytes():
    assert shape_bytes("f32[8,16]") == 8 * 16 * 4
    assert shape_bytes("bf16[4]{0}") == 8
    assert shape_bytes("pred[]") == 1
    # tuple shapes sum their leaves
    assert shape_bytes("(s32[], f32[2,2], u8[3])") == 4 + 16 + 3


_SYNTH = """\
HloModule synth, entry_computation_layout={(f32[8])->f32[]}

adder {
  a = f32[] parameter(0)
  b = f32[] parameter(1)
  ROOT r = f32[] add(a, b)
}

body {
  p = (s32[], f32[], f32[8]) parameter(0)
  i = s32[] get-tuple-element(p), index=0
  one = s32[] constant(1)
  inext = s32[] add(i, one)
  acc = f32[] get-tuple-element(p), index=1
  w = f32[8] get-tuple-element(p), index=2
  zero = f32[] constant(0)
  s = f32[] reduce(w, zero), dimensions={0}, to_apply=adder
  ar = f32[] all-reduce(s), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=adder
  accn = f32[] add(acc, ar)
  ROOT t = (s32[], f32[], f32[8]) tuple(inext, accn, w)
}

cond {
  p2 = (s32[], f32[], f32[8]) parameter(0)
  i2 = s32[] get-tuple-element(p2), index=0
  n = s32[] constant(4)
  ROOT lt = pred[] compare(i2, n), direction=LT
}

ENTRY main {
  arg = f32[8] parameter(0)
  c0 = s32[] constant(0)
  f0 = f32[] constant(0)
  init = (s32[], f32[], f32[8]) tuple(c0, f0, arg)
  loop = (s32[], f32[], f32[8]) while(init), condition=cond, body=body
  ROOT out = f32[] get-tuple-element(loop), index=1
}
"""


def test_parse_hlo_module_structure():
    m = parse_hlo_text(_SYNTH)
    assert set(m.computations) == {"adder", "body", "cond", "main"}
    assert m.entry == "main"
    body = m.computations["body"]
    assert body.root == "t"
    ar = body.instructions["ar"]
    assert ar.opcode == "all-reduce" and ar.operands == ["s"]
    assert ar.bytes == 4
    # tuple shape + operand list with nested brackets both survive
    t = body.instructions["t"]
    assert t.opcode == "tuple" and t.operands == ["inext", "accn", "w"]
    assert t.is_root


def test_collective_inventory_synthetic():
    inv = collective_inventory(parse_hlo_text(_SYNTH))
    assert len(inv) == 1
    (c,) = inv
    assert c["op"] == "all-reduce" and c["computation"] == "body"
    assert c["bytes"] == 4 and c["channel_id"] == 1
    assert c["replica_groups"] == [[0, 1, 2, 3], [4, 5, 6, 7]]


def test_collective_in_loop_fires_on_synthetic_while():
    # `w` is a pass-through carry element, so `ar` recomputes the same
    # reduction every iteration — the finding, found without any jax
    findings = check_collective_in_loop(parse_hlo_text(_SYNTH), "synth")
    assert [f.rule for f in findings] == ["collective-in-loop"]
    assert "ar" in findings[0].message and "body" in findings[0].message


def test_collective_in_loop_clean_when_carry_varies():
    # same module but the loop rotates `w` through the collective's result:
    # not pass-through, so nothing is invariant
    varied = _SYNTH.replace(
        "ROOT t = (s32[], f32[], f32[8]) tuple(inext, accn, w)",
        "wb = f32[8] broadcast(ar), dimensions={}\n"
        "  ROOT t = (s32[], f32[], f32[8]) tuple(inext, accn, wb)")
    assert not check_collective_in_loop(parse_hlo_text(varied), "synth")


# -------------------------------------------------- rules on lowered fixtures

def test_collective_in_loop_fires_on_shard_map_scan():
    def body(x, w):
        def step(c, _):
            tot = jax.lax.psum(jnp.sum(w), "i")  # loop-invariant psum
            return c + jnp.sum(x) / tot, None
        c, _ = jax.lax.scan(step, jnp.sum(x) * 0.0, None, length=4)
        return x * 0 + c

    fn = _sharded1d(body, n_in=2)
    _, findings = analyze_program(fn, (_S, _S), "fix", num_devices=N,
                                  compile=False)
    assert [f.rule for f in findings] == ["collective-in-loop"]


def test_collective_in_loop_clean_when_hoisted():
    def body(x, w):
        tot = jax.lax.psum(jnp.sum(w), "i")  # hoisted: once per call

        def step(c, _):
            return c + jnp.sum(x) / tot, None
        c, _ = jax.lax.scan(step, jnp.sum(x) * 0.0, None, length=4)
        return x * 0 + c

    fn = _sharded1d(body, n_in=2)
    _, findings = analyze_program(fn, (_S, _S), "fix", num_devices=N,
                                  compile=False)
    assert not findings


def test_accidental_replication_fires_on_param_gather():
    def body(x):
        full = jax.lax.all_gather(x, "i")  # rematerializes the full array
        return x + jnp.sum(full, axis=0)

    fn = _sharded1d(body)
    _, findings = analyze_program(
        fn, (_S,), "fix", num_devices=N,
        params_bytes=N * 16 * 4, compile=False)
    assert [f.rule for f in findings] == ["accidental-replication"]
    assert "all-gather" in findings[0].message


def test_ppermute_coverage_fires_on_truncated_ring():
    def body(x):
        perm = [(i, i + 1) for i in range(N - 1)]  # missing the wraparound
        return jax.lax.ppermute(x, "i", perm)

    fn = _sharded1d(body)
    _, findings = analyze_program(fn, (_S,), "fix", num_devices=N,
                                  compile=False)
    assert [f.rule for f in findings] == ["ppermute-coverage"]
    assert "ZEROS" in findings[0].message


def test_ppermute_coverage_clean_on_full_ring():
    def body(x):
        perm = [(i, (i + 1) % N) for i in range(N)]
        return jax.lax.ppermute(x, "i", perm)

    fn = _sharded1d(body)
    _, findings = analyze_program(fn, (_S,), "fix", num_devices=N,
                                  compile=False)
    assert not findings


def test_unweighted_psum_mean_fires():
    def body(x):
        return x * 0 + jax.lax.psum(jnp.sum(x), "i") / N

    fn = _sharded1d(body)
    _, findings = analyze_program(fn, (_S,), "fix", num_devices=N,
                                  compile=False)
    assert [f.rule for f in findings] == ["unweighted-psum-mean"]


def test_unweighted_psum_mean_clean_on_weighted_mean():
    # weighted mean: the denominator is itself a psum, not the axis size
    def body(x, w):
        num = jax.lax.psum(jnp.sum(x * w), "i")
        den = jax.lax.psum(jnp.sum(w), "i")
        return x * 0 + num / den

    fn = _sharded1d(body, n_in=2)
    _, findings = analyze_program(fn, (_S, _S), "fix", num_devices=N,
                                  compile=False)
    assert not findings


def test_axis_name_mismatch_reported_as_finding():
    def body(x):
        return x * 0 + jax.lax.psum(jnp.sum(x), "dz")  # unbound axis

    fn = _sharded1d(body)
    comms, findings = analyze_program(fn, (_S,), "fix", num_devices=N,
                                      compile=False)
    assert comms is None
    assert [f.rule for f in findings] == ["axis-name-mismatch"]
    assert "dz" in findings[0].message

    # any other failure of the lowering, a bare assertion too, propagates,
    # and the look for the name leaves jax's traceback filtering as it was
    def broken(x):
        assert x.ndim == 7
        return x

    was = jax.config.jax_traceback_filtering
    with pytest.raises(AssertionError):
        analyze_program(_sharded1d(broken), (_S,), "fix", num_devices=N,
                        compile=False)
    assert jax.config.jax_traceback_filtering == was


# ------------------------------------------------------- real round programs

def test_gossip_inventory_counts_and_bytes():
    from fedml_tpu.analysis.comms import PROGRAMS

    builder, ndev = PROGRAMS["gossip.mix[ring8]"]
    fn, args, _ = builder()
    comms, findings = analyze_program(fn, args, "gossip", num_devices=ndev,
                                      compile=False)
    assert not findings
    # ring W has 3 nonzero shifts (0, +1, -1); the identity shift moves no
    # bytes, so each of the 2 pytree leaves pays exactly 2 ppermutes
    assert comms.per_op == {"collective-permute": 4}
    # per-device shard bytes: (1,16,4) f32 = 256 and (1,4) f32 = 16
    assert comms.collective_bytes == 2 * (256 + 16)


def test_psum_aggregation_halves_all_gather_bytes():
    # the claim in fedml_tpu/parallel/sharded.py: psum-aggregation moves at
    # most HALF the collective bytes of all-gathering the client stacks
    from fedml_tpu.analysis.comms import PROGRAMS

    builder, ndev = PROGRAMS["sharded.round[lr,f32,fedavg]"]
    fn, args, params_bytes = builder()
    comms, findings = analyze_program(
        fn, args, "sharded", num_devices=ndev,
        params_bytes=params_bytes, compile=False)
    assert not findings
    assert comms.per_op.get("all-reduce", 0) > 0
    # an all_gather of per-device partial trees lands ndev * params_bytes
    # on every device; the psum path must stay under half of that
    gather_bytes = ndev * params_bytes
    assert comms.collective_bytes <= gather_bytes / 2, (
        f"psum path moves {comms.collective_bytes}B vs all_gather "
        f"{gather_bytes}B — the sharded.py comment is now a lie")


@pytest.mark.slow  # ~22s full-surface lowering; ci_smoke's --comms step
# lowers the same programs AND gates the budgets on every push
def test_all_parallel_programs_lower_clean():
    # every shard_map round lowers on the virtual mesh with zero HLO-rule
    # findings (budget gate excluded — that needs compiled memory numbers)
    from fedml_tpu.analysis.comms import EXTRA_PROGRAMS, PROGRAMS

    for name, (builder, ndev) in PROGRAMS.items():
        if name in EXTRA_PROGRAMS:
            continue
        # builders optionally append federated-tree bytes (the param_bytes
        # pin) — same [:3] slice run_comms takes
        fn, args, params_bytes = builder()[:3]
        comms, findings = analyze_program(
            fn, args, name, num_devices=ndev,
            params_bytes=params_bytes, compile=False,
            expect_resharding=name.startswith("tensor.step"))
        assert comms is not None and not findings, (
            name + ":\n" + "\n".join(str(f) for f in findings))
        if name.startswith("tensor.step"):
            # the client-step programs are pure compute by contract — all
            # cross-client traffic lives in the round program around them
            assert comms.collective_count == 0, (
                f"{name}: the step program grew collectives "
                f"({comms.per_op}) — cross-client traffic belongs to the "
                f"round program")
        else:
            assert comms.collective_count > 0, (
                f"{name}: a parallel round with no collectives means the "
                f"program is not actually sharded")


# ---------------------------------------------------------------- budget gate

def test_budget_gate_trips_on_tightened_entry():
    from fedml_tpu.analysis.comms import PROGRAMS, check_budgets

    builder, ndev = PROGRAMS["gossip.mix[ring8]"]
    fn, args, _ = builder()
    comms, _ = analyze_program(fn, args, "gossip.mix[ring8]",
                               num_devices=ndev, compile=False)
    programs = {"gossip.mix[ring8]": comms}

    # exact budget: clean
    ok_budget = {"gossip.mix[ring8]": {
        "collective_count": comms.collective_count,
        "collective_bytes": comms.collective_bytes}}
    assert not check_budgets(programs, ok_budget)

    # tighten collective_count by one: the gate trips with a readable diff
    tight = {"gossip.mix[ring8]": {
        "collective_count": comms.collective_count - 1,
        "collective_bytes": comms.collective_bytes}}
    findings = check_budgets(programs, tight)
    assert [f.rule for f in findings] == ["comms-budget"]
    msg = findings[0].message
    assert "collective_count" in msg
    assert str(comms.collective_count) in msg            # measured
    assert str(comms.collective_count - 1) in msg        # ceiling
    assert "+1" in msg                                   # overshoot


def test_budget_missing_entry_is_a_finding():
    from fedml_tpu.analysis.comms import check_budgets
    from fedml_tpu.analysis.hlo_engine import ProgramComms

    pc = ProgramComms(target="new.round", collective_count=1,
                      collective_bytes=4, per_op={"all-reduce": 1},
                      per_op_bytes={"all-reduce": 4}, collectives=[])
    findings = check_budgets({"new.round": pc}, {})
    assert [f.rule for f in findings] == ["comms-budget"]
    assert "--update-budgets" in findings[0].message


def test_budget_file_covers_every_program():
    import os

    from fedml_tpu.analysis.comms import PROGRAMS, load_budgets

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    budgets = load_budgets(root)
    missing = sorted(set(PROGRAMS) - set(budgets))
    assert not missing, (
        f"programs without a COMMS_BUDGET.json entry: {missing} — run "
        f"`python -m fedml_tpu.analysis --comms --update-budgets`")
    for name, entry in budgets.items():
        assert {"collective_count", "collective_bytes"} <= set(entry), name


@pytest.mark.slow
def test_comms_full_repo_clean(tmp_path):
    # the whole CLI path: lower + compile all 10 programs, memory analysis,
    # budget gate against the checked-in COMMS_BUDGET.json (valid under
    # --runslow where conftest leaves XLA optimization at its default, the
    # same environment the budgets were measured in)
    import os

    from fedml_tpu.analysis.comms import run_comms

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report, comms = run_comms(root)
    assert report.ok, "\n" + report.summary()
    assert len(comms["programs"]) == 10
    for pc in comms["programs"].values():
        assert pc["peak_bytes"] is not None
