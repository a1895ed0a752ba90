"""Selective scan (Mamba-1) of one layer over a prefill chunk, as ONE
Pallas kernel named ``selective_scan``.

    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t
    y_t = h_t . C_t + D * x_t

x, dt, y are ``[B, S, d_inner]``, B_t / C_t ``[B, S, N]``, A ``[N,
d_inner]`` (``-exp(A_log)``, state-major), h ``[B, N, d_inner]`` float32.
The state is kept STATE-MAJOR (``[N, d_inner]``, not the ``[d_inner, N]``
the equations are usually written with): d_inner lies on the chip's 128
lanes, so a float32 state of 16 x 5120 is 320 KiB and not the 2.5 MiB a
16-wide minor dim would pad to.

Layout: ``d_inner`` is viewed as ``[d_inner / 128, 128]`` (a free
reshape) and tiled over the grid in slabs of ``ROWS`` x 128 channels; a
slab of one time step is one float32 vreg. Grid ``(B, d_inner tiles,
time blocks)`` with time innermost: the slab's state, ``N`` vregs, stays
in VMEM scratch across time blocks (entering state in at the first
block, leaving state out at the last). Inside a time block a
``fori_loop`` walks the steps ``GROUP`` at a turn (unrolled) with the
``N`` state vregs as its carry; step t's ``B_t[n]`` / ``C_t[n]`` are
needed as values broadcast over a slab, and come from a per-block scratch
``[T, N * 128]`` that one small MXU product (``[T, N] @ [N, N * 128]``
against blocks of ones) fills: row t, lanes ``n * 128 ..`` hold
``B_t[n]`` 128 times. A turn reads its ``GROUP`` rows as aligned ``[8,
128]`` tiles (the chip's compiler takes a dynamic sublane index only at a
multiple of 8) and a ``[1, 128]`` row of one broadcasts over the slab's
sublanes for free.

``lens`` ``[B]`` (scalar-prefetched): positions ``t >= lens[b]`` are
padding and advance nothing (``dt`` reads 0 there: ``exp(0) = 1`` keeps
h, and nothing is added); a time block that lies wholly behind
``lens[b]`` is skipped and writes zeros.

Accumulation and state are float32 whatever the dtype of x / y.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROWS = 8            # sublanes of a slab: ROWS x 128 channels a grid tile
GROUP = 8           # time steps unrolled a loop turn


def _scan_kernel(lens_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                 h0_ref, y_ref, ht_ref, h_scr, b_scr, c_scr, *,
                 block_t: int, n_state: int):
    bi, ti = pl.program_id(0), pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        h_scr[...] = h0_ref[0]

    n_valid = lens_ref[bi] - ti * block_t     # valid steps of this block

    @pl.when(n_valid <= 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n_valid > 0)
    def _():
        # ones[n, m * 128 + l] = (n == m): B_blk @ ones puts B_t[n] on
        # lanes n * 128 .. of row t.
        col = jax.lax.broadcasted_iota(jnp.int32, (n_state, n_state * LANES),
                                       1) // LANES
        row = jax.lax.broadcasted_iota(jnp.int32, (n_state, n_state * LANES),
                                       0)
        ones = (col == row).astype(jnp.float32)
        b_scr[...] = jnp.dot(b_ref[0], ones,
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
        c_scr[...] = jnp.dot(c_ref[0], ones,
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
        d_skip = d_ref[...]

        def group(g, hs):
            # GROUP steps a turn: B / C rows are read as aligned
            # [GROUP, 128] tiles (a dynamic sublane index has to be a
            # multiple of 8) and a step takes its row statically.
            base = pl.multiple_of(g * GROUP, GROUP)
            rows_ = pl.ds(base, GROUP)
            bt = [b_scr[rows_, pl.ds(n * LANES, LANES)]
                  for n in range(n_state)]
            ct = [c_scr[rows_, pl.ds(n * LANES, LANES)]
                  for n in range(n_state)]
            for j in range(GROUP):
                t = base + j
                live = t < n_valid
                x_t = x_ref[0, t].astype(jnp.float32)      # [rows, 128]
                dt_t = jnp.where(live, dt_ref[0, t], 0.0)
                dx = dt_t * x_t
                y = d_skip * x_t
                new = []
                for n in range(n_state):
                    h = (jnp.exp(dt_t * a_ref[n]) * hs[n]
                         + dx * bt[n][j:j + 1, :])
                    y = y + h * ct[n][j:j + 1, :]
                    new.append(h)
                y_ref[0, t] = y.astype(y_ref.dtype)
                hs = tuple(new)
            return hs

        hs = jax.lax.fori_loop(0, block_t // GROUP, group,
                               tuple(h_scr[n] for n in range(n_state)))
        for n in range(n_state):
            h_scr[n] = hs[n]

    @pl.when(ti == pl.num_programs(2) - 1)
    def _():
        ht_ref[0] = h_scr[...]


@partial(jax.jit, static_argnames=("block_t", "interpret"))
def selective_scan(x: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
                   a_t: jax.Array, d_skip: jax.Array, h0: jax.Array,
                   lens: jax.Array, *, block_t: int = 128,
                   interpret: bool = False):
    """x [B, S, d] (any float dtype), dt [B, S, d] float32 (after
    softplus), b / c [B, S, N] float32, a_t [N, d] float32, d_skip [d]
    float32, h0 [B, N, d] float32, lens [B] int32 ->
    (y [B, S, d] in x.dtype, h_T [B, N, d] float32)."""
    bsz, s, d = x.shape
    n = b.shape[-1]
    assert d % LANES == 0, f"d_inner {d} is no multiple of {LANES}"
    block_t = min(block_t, s)
    assert s % block_t == 0 and block_t % GROUP == 0, (s, block_t)
    r = d // LANES
    # A slab is ROWS x 128 channels (one float32 vreg a step); a width
    # that is no multiple of it (test sizes) is one slab whole.
    rows = ROWS if r % ROWS == 0 else r
    x4 = x.reshape(bsz, s, r, LANES)
    dt4 = dt.astype(jnp.float32).reshape(bsz, s, r, LANES)
    a3 = a_t.astype(jnp.float32).reshape(n, r, LANES)
    d2 = d_skip.astype(jnp.float32).reshape(r, LANES)
    h4 = h0.astype(jnp.float32).reshape(bsz, n, r, LANES)

    seq = lambda bi, di, ti, lens: (bi, ti, di, 0)        # noqa: E731
    bc = lambda bi, di, ti, lens: (bi, ti, 0)             # noqa: E731
    state = lambda bi, di, ti, lens: (bi, 0, di, 0)       # noqa: E731
    y4, ht4 = pl.pallas_call(
        partial(_scan_kernel, block_t=block_t, n_state=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, r // rows, s // block_t),
            in_specs=[
                pl.BlockSpec((1, block_t, rows, LANES), seq),      # x
                pl.BlockSpec((1, block_t, rows, LANES), seq),      # dt
                pl.BlockSpec((1, block_t, n), bc),                 # b
                pl.BlockSpec((1, block_t, n), bc),                 # c
                pl.BlockSpec((n, rows, LANES),
                             lambda bi, di, ti, lens: (0, di, 0)),  # a
                pl.BlockSpec((rows, LANES),
                             lambda bi, di, ti, lens: (di, 0)),     # d
                pl.BlockSpec((1, n, rows, LANES), state),          # h0
            ],
            out_specs=[
                pl.BlockSpec((1, block_t, rows, LANES), seq),      # y
                pl.BlockSpec((1, n, rows, LANES), state),          # h_T
            ],
            scratch_shapes=[
                pltpu.VMEM((n, rows, LANES), jnp.float32),
                pltpu.VMEM((block_t, n * LANES), jnp.float32),
                pltpu.VMEM((block_t, n * LANES), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct(x4.shape, x.dtype),
                   jax.ShapeDtypeStruct(h4.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(lens.astype(jnp.int32), x4, dt4, b.astype(jnp.float32),
      c.astype(jnp.float32), a3, d2, h4)
    return y4.reshape(bsz, s, d), ht4.reshape(bsz, n, d)


def selective_scan_reference(x, dt, b, c, a_t, d_skip, h0, lens):
    """The same function as a ``lax.scan`` over time in plain jax.numpy
    (tests, and the engine's path off the kernel)."""
    s = x.shape[1]
    live = jnp.arange(s)[None, :] < lens[:, None]                 # [B, S]
    dt = jnp.where(live[..., None], dt.astype(jnp.float32), 0.0)
    xf = x.astype(jnp.float32)

    def step(h, t):
        x_t, dt_t, b_t, c_t = t                    # [B, d] x2, [B, N] x2
        da = jnp.exp(dt_t[:, None, :] * a_t[None])            # [B, N, d]
        h = da * h + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        y = jnp.sum(h * c_t[:, :, None], axis=1)
        return h, y + d_skip * x_t

    tm = lambda a: jnp.moveaxis(a, 1, 0)                  # noqa: E731
    h, y = jax.lax.scan(step, h0.astype(jnp.float32),
                        (tm(xf), tm(dt), tm(b.astype(jnp.float32)),
                         tm(c.astype(jnp.float32))))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype), h
