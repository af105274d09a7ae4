"""The flash kernel's sliding window (`ops/attention.py`, `window=W`): query
i sees keys i - W < j <= i. In interpret mode, against `attention_reference`
under the band mask: the output and dq, dk, dv at several sequence lengths,
windows and tiles (a window no multiple of the tile, a window as wide as
the sequence, which is causal, the one-token edge j = i - W left out); the
tiles a band sweep visits are exactly its live ones; `window=None` lowers to
the causal kernel's program as it was before the band existed."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import attention
from fedml_tpu.ops.attention import (attention_reference, band_tiles,
                                     flash_attention, flash_blocks)

SCALE = 0.3


def _qkv(t, seed=0, heads=3, d=16, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (2, t, heads, d), jnp.float32).astype(dtype)
            for k in keys]


def _both_ways(fn, q, k, v, do):
    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(fn, q, k, v)
        return [o, *vjp(do)]


@pytest.mark.parametrize("t,window,bq,bk", [
    (64, 24, 16, 16),      # a window no multiple of the tile
    (64, 16, 16, 32),      # a K tile wider than the window
    (64, 40, 32, 16),      # a Q tile wider than the K tile
    (96, 33, 32, 32),
    (64, 64, 16, 16),      # the window as wide as the sequence: causal
    (64, 1, 8, 16),        # a query sees itself alone
])
def test_the_band_matches_the_reference_both_ways(t, window, bq, bk):
    q, k, v, do = _qkv(t)
    got = _both_ways(lambda *a: flash_attention(*a, True, bq, bk, True, SCALE,
                                                window), q, k, v, do)
    want = _both_ways(lambda *a: attention_reference(*a, True, SCALE, window),
                      q, k, v, do)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert float(jnp.abs(a - b).max()) < 1e-5, name


def test_the_edge_key_is_left_out():
    """j = i - W is outside the band: a value placed there alone moves no
    output of query i; one at j = i - W + 1 does."""
    t, window = 32, 8
    q, k, _, _ = _qkv(t, heads=1)
    i = 20
    for j, seen in ((i - window, False), (i - window + 1, True)):
        v = jnp.zeros((2, t, 1, 16)).at[:, j].set(1.0)
        o = flash_attention(q, k, v, True, 8, 8, True, SCALE, window)
        assert bool(jnp.abs(o[:, i]).max() > 0) == seen, j


def test_a_window_as_wide_as_the_sequence_is_the_causal_kernel():
    q, k, v, do = _qkv(64, seed=3)
    band = _both_ways(lambda *a: flash_attention(*a, True, 16, 16, True,
                                                 SCALE, 64), q, k, v, do)
    causal = _both_ways(lambda *a: flash_attention(*a, True, 16, 16, True,
                                                   SCALE), q, k, v, do)
    for a, b in zip(band, causal):
        assert float(jnp.abs(a - b).max()) < 1e-6


@pytest.mark.parametrize("t,window,bq,bk", [
    (8192, 1024, 1024, 1024), (8192, 1024, 512, 512), (8192, 1024, 256, 256),
    (64, 24, 16, 16), (96, 33, 32, 16), (64, 64, 16, 16)])
def test_the_sweep_visits_the_band_live_tiles_and_no_other(t, window, bq, bk):
    """A tile is live iff it holds a pair i - W < j <= i; the forward and dq
    sweeps compute the live ones of each Q block (the dkv sweep those of
    each K block), in no more steps than `_band_steps` gives them."""
    n_qb, n_kb = t // bq, t // bk
    pairs = np.zeros((n_qb, n_kb), bool)
    i = np.arange(t)[:, None]
    j = np.arange(t)[None, :]
    band = (j <= i) & (i - j < window)
    for qi in range(n_qb):
        for ki in range(n_kb):
            pairs[qi, ki] = band[qi * bq:(qi + 1) * bq,
                                 ki * bk:(ki + 1) * bk].any()
    assert band_tiles(t, bq, bk, window) == pairs.sum()
    steps = attention._band_steps("fwd", t, bq, bk, window)
    assert steps == pairs.sum(axis=1).max()
    for qi in range(n_qb):
        first, last = attention._band_k(qi, bq, bk, window, np)
        live = [first + s for s in range(steps) if first + s <= last]
        assert live == list(np.flatnonzero(pairs[qi]))
    steps = attention._band_steps("dkv", t, bq, bk, window)
    assert steps == pairs.sum(axis=0).max()
    for ki in range(n_kb):
        first, last = attention._band_q(ki, bq, bk, window, n_qb, np)
        live = [first + s for s in range(steps) if first + s <= last]
        assert live == list(np.flatnonzero(pairs[:, ki]))
    # the causal count at the same tiles: every tile on or below the diagonal
    assert band_tiles(t, bq, bk, None) == (
        (j <= i).reshape(n_qb, bq, n_kb, bk).any(axis=(1, 3)).sum())


def test_the_band_pairs_of_the_cell():
    """T 8,192 and W 1,024: 7,864,832 pairs a head against causal's
    33,558,528; the tiles computed at 1,024, 512 and 256 (PERF.md)."""
    t, window = 8192, 1024
    i = np.arange(t)
    assert int(np.minimum(i + 1, window).sum()) == 7864832
    assert t * (t + 1) // 2 == 33558528
    assert [band_tiles(t, b, b, window) for b in (1024, 512, 256)] == [
        15, 45, 150]


def test_the_kernel_chooses_its_band_tile_and_takes_a_given_one():
    bf = jnp.bfloat16
    for sweep in ("fwd", "dq", "dkv"):
        # no wider than the band: at W 1,024 the causal kernel's own tile
        assert flash_blocks(sweep, 8192, 8192, 128, 128, bf,
                            window=1024) == (1024, 1024)
        assert flash_blocks(sweep, 8192, 8192, 128, 128, bf,
                            window=512) == (512, 512)
        assert flash_blocks(sweep, 8192, 8192, 128, 128, bf,
                            window=300) == (256, 256)
        assert flash_blocks(sweep, 8192, 8192, 128, 128, bf, 256, 256,
                            window=1024) == (256, 256)
        # never under 128: a short axis keeps its whole length
        assert flash_blocks(sweep, 64, 64, 16, 16, jnp.float32,
                            window=8) == (64, 64)


def test_a_window_needs_a_causal_square_sweep():
    q, k, v, _ = _qkv(32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, 16, 16, True, SCALE, 8)
    with pytest.raises(ValueError, match="as many queries as keys"):
        flash_attention(q, k[:, :16], v[:, :16], True, 16, 16, True, SCALE, 8)


def test_the_band_calls_carry_their_own_names():
    q, k, v, _ = _qkv(32)

    def program(window):
        def loss(q, k, v):
            return flash_attention(q, k, v, True, 16, 16, True, SCALE,
                                   window).sum()
        return str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v))

    band, causal = program(8), program(None)
    for sweep in ("fwd", "dq", "dkv"):
        assert f"name=flash_window_{sweep}" in band
        assert f"flash_window_{sweep}" not in causal
        assert f"name=flash_attention_{sweep}" in causal
        assert f"flash_attention_{sweep}" not in band


# sha256 of the lowered program of the causal kernel's forward and backward
# (interpret mode, so the kernels' bodies are in it), made on the tree
# before the band: `window=None` is that kernel, operation for operation
CAUSAL_PROGRAMS = {
    (16, 16): "15f709023b99913b95a5e84291e5c07d7ee60a785114e128c9838ddfd39f6a46",
    (32, 16): "71f2963462dc23f704c1c7f82e4fa14d876afe0d314ce78bcffc814b5b8e1a21",
}


@pytest.mark.parametrize("blocks", sorted(CAUSAL_PROGRAMS))
def test_no_window_is_the_causal_kernel_as_it_was(blocks):
    q = jax.ShapeDtypeStruct((2, 64, 3, 16), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, *blocks, True, 0.3).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, q, q).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == CAUSAL_PROGRAMS[blocks]
