"""The flash kernel with v of its own width and an explicit softmax scale
(latent attention trains with 192-wide q/k and 128-wide v and a scale that is
not D^-0.5), against plain `jnp`, interpret mode, forward and both backward
kernels. The kernel's own tests (tests/test_sequence.py) hold q/k/v of one
width and the default scale."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.attention import attention_reference, flash_attention

B, T, H, DQK, DV = 2, 64, 2, 24, 16
SCALE = DQK ** -0.5 * 1.2608 ** 2


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    return (jax.random.normal(ks[0], (B, T, H, DQK)),
            jax.random.normal(ks[1], (B, T, H, DQK)),
            jax.random.normal(ks[2], (B, T, H, DV)))


def dense(q, k, v, causal, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_has_vs_width_and_the_given_scale(qkv, causal):
    with jax.default_matmul_precision("highest"):
        out = flash_attention(*qkv, causal, 16, 16, True, SCALE)
        assert out.shape == (B, T, H, DV)
        np.testing.assert_allclose(out, dense(*qkv, causal, SCALE), atol=2e-5)
        np.testing.assert_allclose(
            attention_reference(*qkv, causal, SCALE),
            dense(*qkv, causal, SCALE), atol=2e-5)
        # the scale is used, not D^-0.5
        assert float(jnp.abs(out - flash_attention(
            *qkv, causal, 16, 16, True)).max()) > 1e-3


def test_backward_kernels_give_dq_dk_of_one_width_and_dv_of_another(qkv):
    cot = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, DV))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * cot)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, True, 16, 32, True, SCALE)), (0, 1, 2))(*qkv)
        want = jax.grad(loss(lambda q, k, v: dense(q, k, v, True, SCALE)),
                        (0, 1, 2))(*qkv)
    assert [g.shape[-1] for g in got] == [DQK, DQK, DV]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_one_width_and_no_scale_is_the_kernel_as_it_was(qkv):
    q, k, _ = qkv
    with jax.default_matmul_precision("highest"):
        same = flash_attention(q, k, k, True, 16, 16, True)
        np.testing.assert_allclose(
            same, flash_attention(q, k, k, True, 16, 16, True, DQK ** -0.5),
            atol=1e-6)
        np.testing.assert_allclose(same, attention_reference(q, k, k, True),
                                   atol=2e-5)
