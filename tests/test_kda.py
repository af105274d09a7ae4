"""`ops/kda.py`: the chunked KDA kernels, in interpret mode, against the
benchmark reference's token-by-token recurrence (`benchmarks/reference/
kimi_linear.py::delta_rule`, which imports nothing of the program): outputs
and the gradients of q, k, v, g, beta; two chunk sizes; a sequence of several
chunks and one that is no multiple of the chunk (it is PADDED with rows of
beta = 0, g = 0, not refused); strong decay; rows that change no state; the
pieces of a chunk (the decayed products, the triangular inverse) against
plain formulas."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import kimi_linear as ref  # noqa: E402
from fedml_tpu.ops import kda as K  # noqa: E402


def inputs(seed, b, t, h, d, strong=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.nn.silu(jax.random.normal(ks[i], (b, t, h, d)))
               for i in range(3))
    if strong:      # A_log at log 16 with a large dt_bias: alpha ~ e^-34
        a, bias = 16.0, 2.0
    else:
        a = jnp.exp(jax.random.uniform(ks[3], (h, 1), minval=0.0,
                                       maxval=np.log(16.0)))
        bias = -3.0
    g = -a * jax.nn.softplus(jax.random.normal(ks[4], (b, t, h, d)) + bias)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, t, h)))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    """The reference's step 4 after its step 2 (the L2 norms)."""
    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + ref.L2_EPS)
    return ref.delta_rule(unit(q) * q.shape[-1] ** -0.5, unit(k), v, g, beta)


def worst(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


@pytest.mark.parametrize("t,chunk,d,strong", [
    (128, 64, 32, False),     # two chunks
    (128, 32, 32, False),     # four chunks of another size
    (100, 32, 32, False),     # no multiple of the chunk: padded
    (96, 64, 128, False),     # the chip's lane width; padded too
    (128, 64, 32, True),      # strong decay
])
def test_kernel_matches_the_token_recurrence(t, chunk, d, strong):
    args = inputs(t + chunk, 2, t, 2, d, strong)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    fn = lambda *a: K.kda(*a, chunk=chunk)  # noqa: E731
    got, want = fn(*args), recurrence(*args)
    assert got.shape == want.shape and bool(jnp.isfinite(got).all())
    assert worst(got, want) < 2e-5
    grads = [jax.grad(lambda *a, f=f: (f(*a) * w).sum(), argnums=(0, 1, 2, 3, 4)
                      )(*args) for f in (fn, recurrence)]
    for name, a, b in zip("q k v g beta".split(), *grads):
        assert bool(jnp.isfinite(a).all()), name
        # under strong decay g's gradient is ~1e-9 of the others': rounding
        assert worst(a, b) < (1e-3 if strong and name == "g" else 5e-5), name


def test_the_ops_own_recurrence_is_the_references():
    """`kda_reference` (what `chip_smoke.py` holds the kernels to on the
    chip) against the benchmark reference's, which shares no code with it."""
    args = inputs(3, 1, 128, 2, 32)
    assert worst(K.kda_reference(*args), recurrence(*args)) < 1e-6


def test_rows_of_beta_zero_and_g_zero_change_no_state():
    """Such rows inserted in the middle leave every other row's output as
    it was: that is what pads a sequence to the chunk."""
    q, k, v, g, beta = inputs(5, 1, 64, 1, 32)
    cut = 24

    def stuffed(a, fill):
        filler = jnp.full((1, 16) + a.shape[2:], fill, a.dtype)
        return jnp.concatenate([a[:, :cut], filler, a[:, cut:]], axis=1)

    plain = K.kda(q, k, v, g, beta, chunk=32)
    long = K.kda(stuffed(q, 0.3), stuffed(k, -0.7), stuffed(v, 2.0),
                 stuffed(g, 0.0), stuffed(beta, 0.0), chunk=32)
    kept = jnp.concatenate([long[:, :cut], long[:, cut + 16:]], axis=1)
    assert worst(kept, plain) < 1e-6


@pytest.mark.parametrize("c,sub", [(32, 16), (64, 16), (64, 8)])
def test_the_inverse_of_a_unit_lower_triangle(c, sub):
    a = np.tril(np.random.default_rng(c + sub).normal(size=(c, c)), -1) * 0.3
    got = K._solve(jnp.asarray(a, jnp.float32), sub)
    want = np.linalg.inv(np.eye(c) + a)
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()


def test_decayed_products_against_the_pairwise_formula_under_strong_decay():
    """exp(G_t - G_s) pair by pair in float64, against the sub-block form:
    no factor of it may overflow (G reaches -2,000 in a chunk here)."""
    q, k, _, g, _ = inputs(7, 1, 64, 1, 32, strong=True)
    q, k = np.asarray(q[0, :, 0], np.float64), np.asarray(k[0, :, 0], np.float64)
    G = np.cumsum(np.asarray(g[0, :, 0], np.float64), axis=0)
    assert G.min() < -1500
    decay = np.exp(np.minimum(G[:, None, :] - G[None, :, :], 0.0))
    low = np.tril(np.ones((64, 64)))
    want_qk = np.einsum("tc,sc,tsc->ts", q, k, decay) * low
    want_kk = np.einsum("tc,sc,tsc->ts", k, k, decay) * np.tril(low, -1)
    roll = lambda x, shift: jnp.roll(x, shift, axis=0)  # noqa: E731
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    aqk, akk = K._decay_products(f32(q), f32(k), f32(G), roll, 16)
    assert bool(jnp.isfinite(aqk).all() and jnp.isfinite(akk).all())
    assert np.abs(np.asarray(aqk) - want_qk).max() < 1e-5
    assert np.abs(np.asarray(akk) - want_kk).max() < 1e-5


def test_under_vmap_the_lanes_are_independent():
    a, b = inputs(11, 1, 64, 1, 32), inputs(12, 1, 64, 1, 32)
    both = jax.vmap(lambda *x: K.kda(*x, chunk=32))(
        *[jnp.stack(p) for p in zip(a, b)])
    assert worst(both[0], K.kda(*a, chunk=32)) < 1e-6
    assert worst(both[1], K.kda(*b, chunk=32)) < 1e-6
