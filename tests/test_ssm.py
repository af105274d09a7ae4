"""`ops/ssm.py`: the selective-scan kernels, in interpret mode, against the
benchmark reference's token-by-token recurrence (`benchmarks/reference/
jamba.py::ssm_core`, which imports nothing of the program): y and the
gradients of x, delta, A, B and C; one chunk and several; a sequence that is
no multiple of the chunk (PADDED with delta = 0, x = 0, not refused);
channel blocks; x in bfloat16; rows that change no state; lanes under vmap."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import jamba as ref  # noqa: E402
from fedml_tpu.ops import ssm  # noqa: E402


def inputs(seed, b, t, d, n=16, dtype=jnp.float32):
    """x, delta, A, B, C as a Mamba layer makes them: delta a softplus
    around the initial steps, A = -(1..N) a channel, scaled."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, t, d)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, t, d)) - 2.0)
    scale = jax.random.uniform(ks[2], (d, 1), minval=0.5, maxval=1.5)
    A = -jnp.arange(1, n + 1, dtype=jnp.float32)[None] * scale
    return (x, delta, A, jax.random.normal(ks[3], (b, t, n)),
            jax.random.normal(ks[4], (b, t, n)))


def worst(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("t,chunk,d,block,dtype", [
    (32, 32, 32, 256, jnp.float32),     # one chunk
    (64, 16, 32, 256, jnp.float32),     # four chunks
    (40, 16, 32, 256, jnp.float32),     # no multiple of the chunk: padded
    (48, 16, 256, 128, jnp.float32),    # two channel blocks of the lanes
    (48, 16, 32, 256, jnp.bfloat16),    # x in bfloat16
])
def test_kernel_matches_the_token_recurrence(t, chunk, d, block, dtype):
    args = inputs(t + chunk + d, 2, t, d, dtype=dtype)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, t, d))
    fn = lambda *a: ssm.selective_scan(*a, chunk=chunk, block=block)  # noqa: E731
    got, want = fn(*args), ref.ssm_core(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert worst(got, want) < 1e-6
    grads = [jax.grad(lambda *a, f=f: (f(*a) * w).sum(),
                      argnums=(0, 1, 2, 3, 4))(*args) for f in (fn, ref.ssm_core)]
    for name, a, b in zip("x delta A B C".split(), *grads):
        assert bool(jnp.isfinite(a).all()), name
        assert a.dtype == (dtype if name == "x" else jnp.float32), name
        # x's cotangent comes in x's dtype: bfloat16 rounds it
        assert worst(a, b) < (1e-2 if a.dtype == jnp.bfloat16 else 1e-6), name


def test_rows_of_delta_zero_and_x_zero_change_no_state():
    """Such rows inserted in the middle leave every other row's output as
    it was: that is what pads a sequence to the chunk."""
    x, delta, A, B, C = inputs(5, 1, 48, 32)
    cut = 20

    def stuffed(a, fill):
        filler = jnp.full((1, 8) + a.shape[2:], fill, a.dtype)
        return jnp.concatenate([a[:, :cut], filler, a[:, cut:]], axis=1)

    plain = ssm.selective_scan(x, delta, A, B, C, chunk=16)
    long = ssm.selective_scan(stuffed(x, 0.0), stuffed(delta, 0.0), A,
                              stuffed(B, 3.0), stuffed(C, -2.0), chunk=16)
    kept = jnp.concatenate([long[:, :cut], long[:, cut + 8:]], axis=1)
    assert worst(kept, plain) < 1e-6


def test_the_state_is_carried_across_chunks():
    """The same sequence, whole and cut at a chunk's boundary: only the
    whole one's second half sees the first half's state."""
    x, delta, A, B, C = inputs(6, 1, 32, 32)
    whole = ssm.selective_scan(x, delta, A, B, C, chunk=16)
    second = ssm.selective_scan(*(a[:, 16:] for a in (x, delta)), A,
                                *(a[:, 16:] for a in (B, C)), chunk=16)
    assert worst(whole[:, 16:], ref.ssm_core(x, delta, A, B, C)[:, 16:]) < 1e-6
    assert worst(whole[:, 16:], second) > 1e-2


def test_under_vmap_the_lanes_are_independent():
    a, b = inputs(11, 1, 32, 32), inputs(12, 1, 32, 32)
    both = jax.vmap(lambda x, d, bb, cc: ssm.selective_scan(
        x, d, a[2], bb, cc, chunk=16))(
        *[jnp.stack([p, q]) for p, q in zip(
            (a[0], a[1], a[3], a[4]), (b[0], b[1], b[3], b[4]))])
    assert worst(both[0], ssm.selective_scan(*a, chunk=16)) < 1e-6
    assert worst(both[1], ssm.selective_scan(*b[:2], a[2], *b[3:],
                                             chunk=16)) < 1e-6
