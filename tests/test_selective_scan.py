"""kernels/selective_scan.py: the one Pallas kernel of a state-space
layer's prefill chunk, in interpret mode against a ``lax.scan`` over time
(its own ``selective_scan_reference``, which tests/test_sambay.py holds to
the plain reference through the whole model). float32 on both sides: what
differs is the order of the 16 adds of a read-out, 1e-5 of values of order
one. That the chip's compiler takes it at the published width is
tests/test_tpu_compile.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.kernels.selective_scan import (selective_scan,
                                                  selective_scan_reference)


def _inputs(b, s, d, n, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, s, d), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)) - 2.0)
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    a_t = -jnp.exp(0.5 * jax.random.normal(ks[4], (n, d)))
    d_skip = jax.random.normal(ks[5], (d,))
    h0 = jax.random.normal(ks[6], (b, n, d))
    return x, dt, bm, cm, a_t, d_skip, h0


@pytest.mark.parametrize("b,s,d,n,block_t", [
    (2, 32, 1024, 16, 16),      # a slab of 8 x 128 channels, two blocks
    (1, 16, 2048, 16, 8),       # two slabs
    (3, 24, 128, 8, 8),         # a width that is one slab whole
])
def test_kernel_matches_the_scan_over_time(b, s, d, n, block_t):
    args = _inputs(b, s, d, n)
    lens = jnp.full((b,), s, jnp.int32)
    y, h = selective_scan(*args, lens, block_t=block_t, interpret=True)
    yr, hr = selective_scan_reference(*args, lens)
    assert float(jnp.abs(y - yr).max()) < 1e-4
    assert float(jnp.abs(h - hr).max()) < 1e-4


def test_chunked_equals_whole():
    """Chunk c + 1 entered with chunk c's leaving state is the whole."""
    args = _inputs(2, 48, 1024, 16, seed=1)
    x, dt, bm, cm, a_t, d_skip, h0 = args
    whole = jnp.full((2,), 48, jnp.int32)
    y, h = selective_scan(*args, whole, block_t=16, interpret=True)
    ys, state = [], h0
    for lo in (0, 16, 32):
        cut = slice(lo, lo + 16)
        yc, state = selective_scan(
            x[:, cut], dt[:, cut], bm[:, cut], cm[:, cut], a_t, d_skip,
            state, jnp.full((2,), 16, jnp.int32), block_t=8, interpret=True)
        ys.append(yc)
    assert float(jnp.abs(jnp.concatenate(ys, 1) - y).max()) < 1e-4
    assert float(jnp.abs(state - h).max()) < 1e-4


def test_padded_positions_advance_nothing():
    """Rows of lengths 32 (whole), 11 (ends inside a block), 16 (ends on
    a block's edge: the blocks behind are skipped and write zeros) and 0
    (the state leaves as it entered)."""
    args = _inputs(4, 32, 1024, 16, seed=2)
    x, dt, bm, cm, a_t, d_skip, h0 = args
    lens = jnp.asarray([32, 11, 16, 0], jnp.int32)
    y, h = selective_scan(*args, lens, block_t=8, interpret=True)
    assert float(jnp.abs(h[3] - h0[3]).max()) == 0.0
    assert float(jnp.abs(y[2, 16:]).max()) == 0.0
    for row, n in enumerate([32, 11, 16]):
        yr, hr = selective_scan_reference(
            x[row:row + 1, :n], dt[row:row + 1, :n], bm[row:row + 1, :n],
            cm[row:row + 1, :n], a_t, d_skip, h0[row:row + 1],
            jnp.asarray([n], jnp.int32))
        assert float(jnp.abs(y[row, :n] - yr[0]).max()) < 1e-4
        assert float(jnp.abs(h[row] - hr[0]).max()) < 1e-4
    # The reference honours the same lengths.
    yr, hr = selective_scan_reference(*args, lens)
    assert float(jnp.abs(hr - h).max()) < 1e-4


def test_the_leaving_state_is_held_below_one_bfloat16_rounding():
    """The state is float32 in and out, and the tolerance it is held to
    here is far below what ONE rounding of it to bfloat16 would move it
    by: a state kept cheaper fails this file, whatever the benchmark's
    ``correct`` can see of it (PERF.md section 7)."""
    args = _inputs(1, 64, 128, 8, seed=3)
    lens = jnp.full((1,), 64, jnp.int32)
    y, h = selective_scan(*args, lens, block_t=16, interpret=True)
    yr, hr = selective_scan_reference(*args, lens)
    assert h.dtype == hr.dtype == jnp.float32
    tol = 1e-4
    assert float(jnp.abs(h - hr).max()) < tol
    rounded = hr.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.abs(rounded - hr).max()) > 10 * tol


def test_bfloat16_activations_keep_a_float32_state():
    args = _inputs(1, 16, 1024, 16, seed=4, dtype=jnp.bfloat16)
    lens = jnp.full((1,), 16, jnp.int32)
    y, h = selective_scan(*args, lens, block_t=8, interpret=True)
    yr, hr = selective_scan_reference(*args, lens)
    assert y.dtype == jnp.bfloat16 and h.dtype == jnp.float32
    assert float(jnp.abs(h - hr).max()) < 1e-4
    assert float(np.abs(np.asarray(y, np.float32)
                        - np.asarray(yr, np.float32)).max()) < 0.1
