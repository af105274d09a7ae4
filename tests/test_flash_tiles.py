"""The flash kernel's operands and tiles (PR 37): bfloat16 q/k/v reach the
MXU as bfloat16 and accumulate in float32, and the kernel picks its own
tiles, a pure function of what it can see. Interpret mode, CPU; the float32
cases live in tests/test_sequence.py and tests/test_attention_widths.py."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import attention
from fedml_tpu.ops.attention import (attention_reference, flash_attention,
                                     flash_blocks)

B, T, H, DQK, DV = 2, 64, 2, 192, 128   # latent attention's widths
SCALE = 0.1147
BF = jnp.bfloat16
# A value rounded to bfloat16 (8 significant bits) moves by at most 2^-9 of
# its binade, so by at most 2^-8 of the largest magnitude around per two
# roundings. The forward rounds p and the output: 2 x 2^-8 of max |ref|
# leaves the measured 0.41-0.80 x 2^-8 (four seeds) 2.5 x of room. A
# gradient rounds o (inside delta), p or ds, and the result, and sums them
# through one more product: 4 x 2^-8, over the measured 0.65-1.41.
FWD_TOL, GRAD_TOL = 2 * 2.0 ** -8, 4 * 2.0 ** -8


def f32(x):
    return x.astype(jnp.float32)


@pytest.fixture(scope="module")
def bf16_case():
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, T, H, DQK)).astype(BF)
    k = jax.random.normal(ks[1], (B, T, H, DQK)).astype(BF)
    v = jax.random.normal(ks[2], (B, T, H, DV)).astype(BF)
    # a cotangent bfloat16 holds exactly: both sides see the same dO
    cot = f32(jax.random.normal(ks[3], (B, T, H, DV)).astype(BF))
    return q, k, v, cot


def _loss(fn, cot):
    return lambda q, k, v: jnp.sum(f32(fn(q, k, v)) * cot)


def _close(got, want, tol, what):
    err = float(jnp.abs(f32(got) - want).max() / jnp.abs(want).max())
    assert err < tol, f"{what}: {err / 2.0 ** -8:.2f} x 2^-8 of max |ref|"


@pytest.mark.parametrize("blocks", [(16, 16), (None, None)],
                         ids=["16x16", "own"])
@pytest.mark.parametrize("causal", [True, False])
def test_bfloat16_kernel_matches_float32_reference_of_the_same_values(
        bf16_case, causal, blocks):
    q, k, v, cot = bf16_case

    def flash(q, k, v):
        return flash_attention(q, k, v, causal, *blocks, True, SCALE)

    out = flash(q, k, v)
    got = jax.grad(_loss(flash, cot), (0, 1, 2))(q, k, v)
    assert out.dtype == BF and [g.dtype for g in got] == [BF] * 3
    with jax.default_matmul_precision("highest"):
        ref = lambda q, k, v: attention_reference(q, k, v, causal, SCALE)
        want_out = ref(f32(q), f32(k), f32(v))
        want = jax.grad(_loss(ref, cot), (0, 1, 2))(f32(q), f32(k), f32(v))
    _close(out, want_out, FWD_TOL, "out")
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        _close(a, b, GRAD_TOL, name)


def _kernels(jaxpr, found):
    """{kernel name: its jaxpr} of every pallas_call under `jaxpr`."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn.params["jaxpr"]
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernels(sub, found)
    return found


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("dtype,operand", [(BF, BF), (jnp.float32, jnp.float32)],
                         ids=["bf16", "f32"])
def test_kernels_multiply_their_inputs_dtype_and_accumulate_in_float32(dtype,
                                                                       operand):
    q = jnp.zeros((1, 32, 2, DQK), dtype)
    v = jnp.zeros((1, 32, 2, DV), dtype)

    def loss(q, k, v):
        return jnp.sum(f32(flash_attention(q, k, v, True, 16, 16, True, SCALE)))

    kernels = _kernels(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, v).jaxpr,
                       {})
    assert sorted(kernels) == ["flash_attention_dkv", "flash_attention_dq",
                               "flash_attention_fwd"]
    dots = {"flash_attention_fwd": 2, "flash_attention_dq": 3,
            "flash_attention_dkv": 4}
    for name, kernel in kernels.items():
        eqns = list(_eqns(kernel))
        widened = [e for e in eqns if e.primitive.name == "convert_element_type"
                   and e.params["new_dtype"] == jnp.float32
                   and e.invars[0].aval.dtype != jnp.float32]
        assert not widened, f"{name} casts a tile up to float32: {widened}"
        products = [e for e in eqns if e.primitive.name == "dot_general"]
        assert len(products) == dots[name]
        for e in products:
            assert e.params["preferred_element_type"] == jnp.float32
            assert e.outvars[0].aval.dtype == jnp.float32
            assert [x.aval.dtype for x in e.invars] == [operand, operand]
            high = e.params["precision"] is not None and all(
                p == jax.lax.Precision.HIGHEST for p in e.params["precision"])
            assert high == (dtype == jnp.float32)


# ---- the tile choice, a pure function ----

SWEEPS = ("fwd", "dq", "dkv")


@pytest.mark.parametrize("sweep", SWEEPS)
@pytest.mark.parametrize("tq,tk,d,dv,dtype", [
    (1024, 1024, 192, 128, BF),        # dsv2lite_lora.train
    (32768, 32768, 128, 128, BF),
    (2048, 2048, 64, 64, jnp.float32),  # chip_smoke's shape
    (1536, 4096, 128, 128, BF),
    (8192, 8192, 512, 512, jnp.float32),
])
def test_blocks_divide_align_and_fit_the_budget(sweep, tq, tk, d, dv, dtype):
    bq, bk = flash_blocks(sweep, tq, tk, d, dv, dtype)
    itemsize = jnp.dtype(dtype).itemsize
    assert tq % bq == 0 and tk % bk == 0
    # never under the chip's tile for the dtype: block_q is a sublane axis
    # (8 rows of float32, 16 of bfloat16), block_k the score tile's lane axis
    assert bq % (8 * 4 // itemsize) == 0 and bk % 128 == 0
    assert max(bq, bk) <= attention._MAX_BLOCK
    assert attention._vmem_bytes(sweep, bq, bk, d, dv, itemsize) \
        <= attention._VMEM_BUDGET


@pytest.mark.parametrize("sweep", SWEEPS)
def test_blocks_at_the_cells_shape_and_at_a_long_sequence(sweep):
    # step 0 (PERF.md section 6, PR 37): one tile a head wins at T 1024
    assert flash_blocks(sweep, 1024, 1024, 192, 128, BF) == (1024, 1024)
    assert flash_blocks(sweep, 32768, 32768, 128, 128, BF) == (1024, 1024)


@pytest.mark.parametrize("sweep", SWEEPS)
def test_a_short_sequence_is_one_block_and_an_explicit_block_wins(sweep):
    assert flash_blocks(sweep, 48, 80, 24, 16, jnp.float32) == (48, 80)
    assert flash_blocks(sweep, 1000, 1000, 64, 64, BF) == (1000, 1000)
    assert flash_blocks(sweep, 1024, 1024, 192, 128, BF, 16, 32) == (16, 32)
    assert flash_blocks(sweep, 1024, 1024, 192, 128, BF, None, 128) == (1024, 128)
    assert flash_blocks(sweep, 64, 64, 24, 16, BF, 128, 128) == (64, 64)
    # 1536 = 12 x 128: the largest aligned divisor under the cap, not 1024
    assert flash_blocks(sweep, 1536, 1536, 128, 128, BF) == (768, 768)


def test_wide_heads_shrink_the_larger_block_first_to_fit_the_budget():
    wide = [flash_blocks(s, 8192, 8192, 2048, 2048, jnp.float32) for s in SWEEPS]
    for sweep, (bq, bk) in zip(SWEEPS, wide):
        assert (bq, bk) != (1024, 1024) and bq <= bk
        assert attention._vmem_bytes(sweep, bq, bk, 2048, 2048, 4) \
            <= attention._VMEM_BUDGET
    # the sweeps hold different blocks, so their choices may differ
    assert attention._vmem_bytes("dkv", 1024, 1024, 192, 128, 2) \
        > attention._vmem_bytes("fwd", 1024, 1024, 192, 128, 2)


@pytest.mark.parametrize("bq,bk", [(16, 16), (16, 64), (64, 16), (32, 128),
                                   (128, 32)])
def test_a_dead_causal_tile_repeats_a_live_blocks_index(bq, bk):
    """The index a K/V (dkv: Q/dO) map names is the tile's own exactly where
    the tile is live, and a live block's where it is dead."""
    tq, tk = 256, 128
    n_qb, n_kb = tq // bq, tk // bk
    for qi, ki in itertools.product(range(n_qb), range(n_kb)):
        live = bool(attention._block_live(qi, ki, bq, bk, True))
        k_named = int(jnp.minimum(ki, attention._last_live_k(qi, bq, bk, n_kb)))
        q_named = int(jnp.maximum(qi, attention._first_live_q(ki, bq, bk, n_qb)))
        assert (k_named == ki) == live
        assert bool(attention._block_live(qi, k_named, bq, bk, True))
        if live:
            assert q_named == qi
        else:
            assert q_named > qi


@pytest.mark.parametrize("blocks", [(16, 64), (64, 16), (32, 16)])
def test_causal_q_and_k_tiles_of_different_sizes(blocks):
    """Outputs and all three gradients equal the reference's and a run with
    equal tiles, whichever way the tiles are oblong."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (B, 64, H, 24))
    k = jax.random.normal(ks[1], (B, 64, H, 24))
    v = jax.random.normal(ks[2], (B, 64, H, 16))
    cot = jax.random.normal(ks[3], (B, 64, H, 16))

    def run(fn):
        return (fn(q, k, v),) + jax.grad(_loss(fn, cot), (0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = run(lambda q, k, v: flash_attention(q, k, v, True, *blocks, True,
                                                  SCALE))
        equal = run(lambda q, k, v: flash_attention(q, k, v, True, 16, 16, True,
                                                    SCALE))
        want = run(lambda q, k, v: attention_reference(q, k, v, True, SCALE))
    for a, b, c in zip(got, equal, want):
        np.testing.assert_allclose(a, b, atol=2e-5)
        np.testing.assert_allclose(a, c, atol=5e-5)


def test_an_explicit_block_that_does_not_divide_the_sequence_is_refused():
    with pytest.raises(ValueError, match="multiples of the block sizes"):
        flash_blocks("fwd", 100, 100, 64, 64, BF, 16, 16)
    q = jnp.zeros((1, 100, 1, 16))
    with pytest.raises(ValueError, match="multiples of the block sizes"):
        flash_attention(q, q, q, True, 16, 16, True)
