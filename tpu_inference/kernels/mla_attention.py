"""Pallas latent (MLA) attention over the paged latent pool, absorbed form.

DeepSeek-V3 / Kimi-K2 attention caches ONE entry per token per layer:
``[c_kv (R) | k_rope (Dr)]`` (512 | 64), shared by every query head.
With the key / value up-projections folded into the query and the output
(models/deepseek_v3.py), head h scores a cached token t as

    s[h, t] = scale * (q_lat[h] . c_kv[t] + q_rope[h] . k_rope[t])

and its result is the weighted sum of the LATENTS, ``sum_t p[h, t]
c_kv[t]`` (R wide), which the model takes through W_UV afterwards. So
one page of latents serves all heads with one MXU contraction: 64 heads
read each 576-wide entry once, where a GQA kernel reads a K and a V row
per 4 heads (kernels/paged_attention.py).

One kernel body, two entry points:

- ``mla_decode_attention``: one query token per sequence.
- ``mla_prefill_attention``: a chunk of S tokens per sequence against
  its own tokens AND whatever latent prefix the pages hold already
  (chunk 2.. of a long prompt, a prefix-cache hit). The engine writes the
  chunk's latents into the pool first, so the pages are the one source.

Mechanics, as in the GQA kernels: the operand is the STACKED pool
``[L, P, page, W]`` with the layer index scalar-prefetched, so the
layer is part of the DMA address and no ``pool[layer]`` slice (= copy)
is made in front of the call. ``W`` is R + Dr rounded up to the chip's
128 lanes (576 -> 640, zeros behind the rope part): handed a 576-wide
pool, the v5e compiler keeps it with the PAGE dim minor-most to save the
padding and then copies the whole pool (2.9 GB at 20k pages) into the
row-major layout a kernel operand needs, in front of every call. The
query is zero-padded to W here, so scores are one K = W contraction.

How the pages arrive: the pool is passed ONCE and stays in HBM
(``pl.ANY``); the grid is 1-D, one step a (lane, query block), so decode
is one step a lane of the rung. A step folds a BLOCK of pages at a time
(``_block_pages``: by bytes, from the page's shape and ``mp``) in a loop
of as many trips as it HAS visible blocks, from page 0 to the page of
min(kv_len, last query + 1) - 1. Each trip's pages are copied into one
half of a VMEM double buffer (``make_async_copy``, a DMA a page) while
the other half is folded; the next step's first block is in flight
during this step's last, and which half comes next is carried in SMEM:
``paged_attention._walk_blocks``, shared with both GQA kernels. The page
DMAs are this module's own (``_page_copies``: issued 16 at a time in
straight-line code, because a 20 KB page is too small to hide a loop
trip of the scalar core behind). A latent page is always whole 128-lane
tiles, so there is no pipeline-fed form beside this one.
A lane with ``kv_len`` 0 (an idle lane of the rung), or a query block of
padding rows, runs no trip and comes back 0; no page the block table
does not name for a visible position leaves HBM. Before PR 31 the pool
was passed 16 times, each with its own index map, on a grid of (B, query
blocks, 42 blocks of 16 pages): 1344 steps a decode call at 32 lanes,
~1.5 us each on the scalar core whether the step fetched or not.
Rows are token-major ``[bq * H]``, so no transpose surrounds the call.
Scores and the running sums are float32; the operands of both matmuls
are the pool's dtype (bf16 on the chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_inference.kernels import mxu_precision
from tpu_inference.kernels.paged_attention import NEG_INF, _walk_blocks


# A block of pages: at most this many bytes of latents (one of the two
# VMEM buffers) and this many bytes of float32 scores a trip.
BLOCK_BYTES = 1280 * 1024
SCORE_BYTES = 4 << 20
# Page DMAs issued (and waited for) in one straight-line run.
DMA_RUN = 16


def _block_pages(page_size: int, page_bytes: int, rows: int,
                 n_page_axis: int) -> int:
    """Pages a block holds, from what the call can see: a page's tokens
    and bytes (``page * W * itemsize``), the query rows a step scores
    them against (``bq * H``) and how many pages a lane can need at all
    (``mp``). By bytes, where the GQA rule (_pages_per_step) stops at 256
    tokens: a latent page is a third of a K + V page, and a trip's fixed
    cost weighs the more the fewer bytes it moves. A prefill step's 2048
    rows make the float32 score tile the larger buffer, and past 4 MiB of
    it a call gets slower again. On the v5e at the Kimi cell's shapes
    (PR 31), 32 / 64 / 128 pages a block: 694 / 597 / 563 us a decode
    call of 32 lanes, 3347 / 3700 / 4231 us a 512-token prefill call."""
    return max(1, min(BLOCK_BYTES // page_bytes,
                      SCORE_BYTES // (4 * rows * page_size), n_page_axis))


def _page_copies(layer, bt_ref, pool_hbm, buf, sem):
    """paged_attention._page_copies for the one latent pool:
    ``copies(lane, at, count, slot, wait=False)`` starts, or waits for,
    the copies of block-table positions ``at .. at + count`` of row
    ``lane`` into half ``slot`` of ``buf`` [2, pages, page, W], one DMA a
    page. The DMAs go out ``DMA_RUN`` at a time in straight-line code and
    the rest one a loop trip: a 20 KB page moves in 25 ns at the HBM
    peak, and a loop trip a page (the GQA helper's way, where a page is
    2 x 32 KB) costs the scalar core 43 ns, in series with the block
    body (v5e, PR 31: 874 against 595 us a decode call at 64 pages a
    block). All under two loops: unrolled whole, 64 pages x 3 sites trace
    in 0.6 s a graph where this takes 0.14 s."""

    def copies(lane, at, count, slot, wait=False):
        def dma(n):
            c = pltpu.make_async_copy(
                pool_hbm.at[layer, bt_ref[lane, at + n]], buf.at[slot, n],
                sem.at[slot])
            c.wait() if wait else c.start()

        def run(i, carry):
            for n in range(DMA_RUN):
                dma(i * DMA_RUN + n)
            return carry

        def page(n, carry):
            dma(n)
            return carry

        runs = jax.lax.div(count, DMA_RUN)
        jax.lax.fori_loop(0, runs, run, 0)
        jax.lax.fori_loop(runs * DMA_RUN, count, page, 0)

    return copies


def _mla_kernel(layer_ref, bt_ref, kv_len_ref, q_off_ref, q_ref, pool_hbm,
                out_ref, buf, sem, slot_ref, m_ref, l_ref, acc_ref, *,
                pages_per_step: int, page_size: int, max_pages: int,
                block_q: int, n_qb: int, n_heads: int, rank: int,
                scale: float):
    """Grid (B * S / bq,): one query block of one lane a step, its visible
    blocks in a loop of as many trips as it has blocks, the pages copied
    from the pool in HBM by hand (paged_attention._walk_blocks)."""
    nps = pages_per_step
    g = pl.program_id(0)

    def query_block(step):
        """(lane, its kv_len, first query position) of grid step ``step``."""
        lane = jax.lax.div(step, n_qb)
        return (lane, kv_len_ref[lane],
                q_off_ref[lane] + jax.lax.rem(step, n_qb) * block_q)

    def span(step):
        """(lane, 0, pages) of the block-table positions grid step
        ``step`` reads: up to the page of the last position that is both
        written (< kv_len) and not after its last query. None for a lane
        without a token, or a query block of padding rows."""
        lane, kv_len, q_lo = query_block(step)
        end = jax.lax.min(kv_len, q_lo + block_q)
        pages = jax.lax.min(jax.lax.div(end + page_size - 1, page_size),
                            max_pages)
        return lane, 0, jax.lax.select(q_lo < kv_len, pages, 0)

    @pl.when(g == 0)
    def _first_step():
        # What a partial block leaves of a buffer is multiplied by
        # weights of 0: it has to be finite, which fresh VMEM need not be.
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    _, kv_len, q_lo = query_block(g)

    def fold(carry, slot, first_page):
        kv = buf[slot].reshape(nps * page_size, -1)        # [T, W]
        c = kv[:, :rank]                                   # [T, R]
        prec = mxu_precision(kv.dtype)
        s = jax.lax.dot_general(
            q_ref[0, 0], kv, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale    # [bq*H, T]
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                0) // n_heads
        k_pos = (first_page * page_size
                 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        valid = (k_pos <= q_pos) & (k_pos < kv_len)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:]                                  # [bq*H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # A fully masked row keeps m at NEG_INF: exp(0) = 1 there.
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(c.dtype), c, precision=prec,
            preferred_element_type=jnp.float32)
        return carry

    _walk_blocks(span, _page_copies(layer_ref[0], bt_ref, pool_hbm, buf, sem),
                 slot_ref, nps, fold, 0)
    # Rows that read nothing (an idle lane, padding) give 0, not NaN.
    out_ref[0, 0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-20)
                     ).astype(out_ref.dtype)


def _mla_call(name: str, q, pool, layer, block_tables, kv_len, q_offset,
              *, rank: int, scale: float, block_q: int, interpret: bool):
    """q [B, S, H, R + Dr] -> [B, S, H, R]."""
    b, s, h, qd = q.shape
    _, _, page_size, width = pool.shape
    assert rank < qd <= width, (q.shape, pool.shape, rank)
    mp = block_tables.shape[1]
    bq = next(x for x in range(min(block_q, s), 0, -1) if s % x == 0)
    n_qb, rows = s // bq, bq * h
    nps = _block_pages(page_size, page_size * width * pool.dtype.itemsize,
                       rows, mp)
    qp = jnp.pad(q.astype(pool.dtype), ((0, 0),) * 3 + ((0, width - qd),)
                 ).reshape(b, n_qb, rows, width)

    def q_spec(d):
        return pl.BlockSpec((1, 1, rows, d),
                            lambda g, *_: (g // n_qb, g % n_qb, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,       # layer, block_tables, kv_len, q_offset
        grid=(b * n_qb,),
        in_specs=[q_spec(width), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec(rank),
        scratch_shapes=[
            pltpu.VMEM((2, nps, page_size, width), pool.dtype),  # pages
            pltpu.SemaphoreType.DMA((2,)),       # a buffer half each
            pltpu.SMEM((1,), jnp.int32),         # buffer to use next
            pltpu.VMEM((rows, 1), jnp.float32),     # max
            pltpu.VMEM((rows, 1), jnp.float32),     # sum
            pltpu.VMEM((rows, rank), jnp.float32)])  # out
    out = pl.pallas_call(
        functools.partial(_mla_kernel, pages_per_step=nps,
                          page_size=page_size, max_pages=mp, block_q=bq,
                          n_qb=n_qb, n_heads=h, rank=rank, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_qb, rows, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), block_tables, kv_len,
      q_offset, qp, pool)
    return out.reshape(b, s, h, rank)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def mla_decode_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                         block_tables: jax.Array, kv_len: jax.Array, *,
                         rank: int, scale: float,
                         interpret: bool = False) -> jax.Array:
    """Decode: q [B, H, R + Dr] (the absorbed query of the one new token,
    whose entry is in the pool already: latent part | rope part) over
    ``pool`` [L, P, page, W] at layer ``layer`` -> [B, H, R] weighted
    latents. block_tables [B, MP]; kv_len [B] counts the new token, and is
    0 for a lane that holds no sequence: it reads nothing and comes back
    0."""
    out = _mla_call("mla_decode_attention", q[:, None], pool, layer,
                    block_tables, kv_len, kv_len - 1, rank=rank, scale=scale,
                    block_q=1, interpret=interpret)
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("rank", "scale", "block_q",
                                             "interpret"))
def mla_prefill_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                          block_tables: jax.Array, kv_len: jax.Array,
                          q_offset: jax.Array, *, rank: int, scale: float,
                          block_q: int = 32,
                          interpret: bool = False) -> jax.Array:
    """Prefill: q [B, S, H, R + Dr]; the chunk sits at positions
    q_offset .. q_offset + S of sequences of kv_len tokens (cached
    prefix + chunk) -> [B, S, H, R]. Rows at positions past kv_len are
    padding: their result is not defined."""
    return _mla_call("mla_prefill_attention", q, pool, layer, block_tables,
                     kv_len, q_offset, rank=rank, scale=scale,
                     block_q=block_q, interpret=interpret)


def mla_attention_dense(q: jax.Array, pool: jax.Array, layer: jax.Array,
                        block_tables: jax.Array, kv_len: jax.Array,
                        q_offset: jax.Array, *, rank: int,
                        scale: float) -> jax.Array:
    """The same function in plain XLA over a gathered context (the
    ``dense`` backend off the chip, and what the kernels are tested
    against): float32 throughout. Shapes as mla_prefill_attention."""
    b, mp = block_tables.shape
    qd = q.shape[-1]
    ctx = pool[layer][block_tables].reshape(b, mp * pool.shape[2], -1)
    ctx = ctx[..., :qd].astype(jnp.float32)
    s = jnp.einsum("bshd,btd->bhst", q.astype(jnp.float32), ctx) * scale
    q_pos = q_offset[:, None] + jnp.arange(q.shape[1])[None, :]
    k_pos = jnp.arange(ctx.shape[1])
    mask = ((k_pos[None, None, :] <= q_pos[:, :, None])
            & (k_pos[None, None, :] < kv_len[:, None, None]))
    s = jnp.where(mask[:, None], s, NEG_INF)
    out = jnp.einsum("bhst,btr->bshr", jax.nn.softmax(s, axis=-1),
                     ctx[..., :rank])
    return out.astype(q.dtype)
