"""Pallas paged prefill attention (flash-style online softmax over KV pages).

Prefill previously gathered every page of a sequence into a contiguous
[B, MP*page, H, D] buffer and materialized dense [B, H, S, S_kv] scores
(models/common.py dense path) — O(S^2) HBM traffic and VMEM pressure that
walls at long context. This kernel streams the KV pages a query block can
see HBM->VMEM and folds them into running (m, l, acc) online-softmax
state: memory is O(S·block), the gather never materializes, and both the
prompt's own KV and any cached prefix are read from the same paged pool
(the engine writes the current chunk's KV before attending, so pool pages
are the single source of truth).

It is the decode kernel's twin (kernels/paged_attention.py, whose pieces
it shares) with a block of QUERIES where that one has a token:
- The K / V operands are the STACKED pool [L, P, page, Hkv, D], viewed
  [L, P, page * Hkv, D] (the same bytes), with the layer, the block
  table, the kv lengths and the query offsets scalar-prefetched: layer
  and physical page are part of each page DMA's address, and XLA never
  slices (= copies) one layer's pool out of the stack.
- One grid step is one query block (``block_q`` tokens of one sequence,
  every KV head: q viewed [Hkv, bq * R, D], a row's token is row // R).
  It folds a BLOCK of ``pages_per_step`` pages (about 256 tokens,
  ``_pages_per_step``) at a time, in a loop of as many trips as the query
  block HAS visible blocks: from the first page its window (or position
  0) reaches to the page of min(kv_len, q_hi + 1) - 1. The pages are
  copied from the pool in HBM by hand into a VMEM double buffer
  (``_walk_blocks``: the next block, or the next query block's first,
  flies while this one is folded); a query block of padding rows, or a
  whole all-padding sequence (kv_len 0), runs no trip and comes back 0.
  Before PR 29 it was one 16-token page a grid step on a grid of (B,
  S / bq, window pages): 2120 steps a 1024-token Mistral chunk, each
  widening the whole query block to float32 again and feeding the MXU 16
  keys.
- The pools Mosaic cannot copy from by hand (a page that is not whole
  128-lane tiles: head_dim 96, int4, int8 under 8 KV heads — the decode
  kernel's rule, read off the operand shapes) get the same block through
  the pipeline: grid (B * S / bq, blocks), ``pages_per_step`` operands a
  pool. One block body (``_fold_block``) serves both. No cell serves such
  a pool.
- Each KV head's keys meet only its own query rows: a query block is
  hundreds of rows A KV HEAD, so the decode kernel's all-heads-against-
  all-rows product would be Hkv times the work. A block [T * Hkv, D] (row
  t * Hkv + h is token t's head h) is re-laid-out ONCE, for all its query
  rows, into [Hkv, T, D] (``_by_head``: head h is every Hkv-th row, a
  strided read of a float32 staging buffer, the one place a block is
  float32: Mosaic strides 32-bit rows only), and a loop over the KV heads
  contracts [bq * R, D] x [T, D] and [2 * bq * R, T] x [T, D] on the MXU
  in the pool's dtype with float32 accumulation. q is cast to the pool's
  dtype before the call (exact for the bf16 activations serving hands
  over), not once a page. Measured on the v5e (PERF.md, PR 29): the
  strided read costs 1-5% of a call; ``reshape(T, Hkv, D)[:, h]`` 10%
  (Mistral) to 40% (Qwen2), a ``transpose(1, 0, 2)`` 3 to 11%.
- Softmax state (max, sum, acc) is float32 in VMEM scratch. The weights
  go to the MXU as two pool-dtype halves (p = hi + lo, stacked on the row
  dim: V is loaded once), as in the decode kernel: one rounding of p to
  bf16 is 5e-3 to 7e-3 of the output's spread off.
- Causality, cache validity and the sliding window fuse into one mask
  (q_pos - window < k_pos <= min(q_pos, kv_len - 1)); the running max
  starts at NEG_INF / 2, above the mask's NEG_INF, so a masked score's
  weight is exp(-5e29) = 0 without a second select and a row that has
  seen nothing keeps sum 0.
- Quantized pools: a block's codes are scaled to values row by row (the
  per-(token, head) scales arrive lane-major, [1, page * Hkv] a page, and
  are turned into a column by a masked lane-sum) before the re-layout.

Reference has no analogue (client-only, SURVEY.md §0); this is the
prefill half of the vLLM-style PagedAttention pair, re-designed for
Mosaic/TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_inference.kernels import mxu_precision
from tpu_inference.kernels.paged_attention import (
    NEG_INF, _codes, _page_copies, _pages_per_step, _walk_blocks)


def _span(q_off, kv_len, qb, *, block_q: int, page_size: int,
          max_pages: int, sliding_window: int):
    """(first page, pages) of the block-table positions query block
    ``qb`` of a sequence can see: from the page its first query's window
    reaches (or page 0) to the page of the last position that is both
    written (< kv_len) and not after its last query. None when its
    first query is past the context's end: all its rows are padding (the
    rest of a bucket, a row of the batch without a sequence)."""
    # lax, not jnp, on these scalars: every jnp call traces a jit of its
    # own, and this runs at four sites a graph (a boot traces and lowers
    # every warm-up graph before it can ask the compile cache).
    q_lo = q_off + qb * block_q
    first = (jax.lax.div(jax.lax.max(q_lo - sliding_window + 1, 0),
                         page_size) if sliding_window else 0)
    end = jax.lax.min(kv_len, q_lo + block_q)
    last = jax.lax.min(jax.lax.div(end - 1, page_size), max_pages - 1)
    return first, jax.lax.select(q_lo < kv_len, last - first + 1, 0)


def _scaled(codes, scale_rows):
    """A block's values: ``codes`` [pages * rows, D] float32 times the
    per-row scales, which arrive lane-major ([1, rows] a page). A masked
    lane-sum puts each page's scales in a column."""
    rows = scale_rows[0].shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1))
    col = jnp.concatenate(
        [jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)
         for r in scale_rows], axis=0)                   # [pages * rows, 1]
    return codes * col


def _by_head(block, wide_ref, out_ref):
    """[T * Hkv, D] float32 (row t * Hkv + h is token t's head h) ->
    out_ref [Hkv, T, D]: head h is every Hkv-th row from h, a strided
    read, which Mosaic does on 32-bit rows only: so through ``wide_ref``
    ([T * Hkv, D] float32), once a block for all its query rows."""
    n_kv, t, _ = out_ref.shape
    wide_ref[...] = block
    for h in range(n_kv):
        out_ref[h] = wide_ref[pl.ds(h, t, stride=n_kv), :].astype(
            out_ref.dtype)


def _fold_block(q_ref, blocks, scales, state, wide_ref, by_head, *, start,
                q_lo, kv_len, n_rep: int, scale: float, packed: bool,
                sliding_window: int):
    """Fold one block of pages into the online-softmax state of a query
    block. ``q_ref`` [1, 1, Hkv, bq * R, D]; ``blocks`` = (K, V) codes
    [pages, page * Hkv, D_pool]; ``scales`` = per pool a list of [1, page
    * Hkv] float32 rows, or (); ``state`` = (m, l, acc) VMEM refs [Hkv,
    bq * R, 1 | D] float32; ``by_head`` = two VMEM refs [Hkv, T, D] in
    q's dtype. ``start`` is the block's first position."""
    m_ref, l_ref, acc_ref = state
    for i, (codes, out) in enumerate(zip(blocks, by_head)):
        codes = _codes(codes, packed, jnp.float32)
        _by_head(_scaled(codes, scales[i]) if scales else codes, wide_ref,
                 out)
    kh_ref, vh_ref = by_head
    cdt = q_ref.dtype
    _, _, n_kv, rows, _ = q_ref.shape
    t = kh_ref.shape[1]
    prec = mxu_precision(cdt)

    # The bounds of a query row: its own position (and the context's end)
    # above, its window's edge below.
    q_pos = q_lo + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), n_rep)
    hi_pos = jnp.minimum(q_pos, kv_len - 1)
    k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)

    def head(h, carry):
        s = jax.lax.dot_general(
            q_ref[0, 0, h], kh_ref[h], (((1,), (1,)), ((), ())),
            precision=prec,
            preferred_element_type=jnp.float32) * scale    # [bq * R, T]
        valid = k_pos <= hi_pos
        if sliding_window:
            valid = valid & (k_pos > q_pos - sliding_window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # m >= NEG_INF / 2: a masked score's weight is exp(-5e29) = 0.
        p = jnp.exp(s - m_new)
        m_ref[h] = m_new
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = vh_ref[h]
        if cdt == jnp.float32:
            o = jnp.dot(p, v, precision=prec,
                        preferred_element_type=jnp.float32)
        else:
            # p = hi + lo, as in the decode kernel's _attend.
            hi = p.astype(cdt)
            lo = (p - hi.astype(jnp.float32)).astype(cdt)
            o = jnp.dot(jnp.concatenate([hi, lo], axis=0), v, precision=prec,
                        preferred_element_type=jnp.float32)
            o = o[:rows] + o[rows:]
        acc_ref[h] = acc_ref[h] * alpha + o
        return carry

    jax.lax.fori_loop(0, n_kv, head, 0)


def _init(state):
    m_ref, l_ref, acc_ref = state
    m_ref[...] = jnp.full_like(m_ref, NEG_INF / 2)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _flush(out_ref, state):
    _, l_ref, acc_ref = state
    # Rows that saw nothing (padding) give 0, not NaN.
    out_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                     ).astype(out_ref.dtype)


def _dma_kernel(layer_ref, bt_ref, kv_len_ref, q_off_ref, q_ref, *rest,
                pages_per_step: int, n_qb: int, quantized: bool,
                span: dict, **fold):
    """Grid (B * S / bq,): a query block's visible blocks in a loop, the
    pages copied from the pool in HBM by hand."""
    nps = pages_per_step
    if quantized:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, out_ref, k_buf, v_buf, ks_buf,
         vs_buf, sem, slot_ref, *scratch) = rest
        scale_bufs = ((ks_hbm, ks_buf), (vs_hbm, vs_buf))
    else:
        k_hbm, v_hbm, out_ref, k_buf, v_buf, sem, slot_ref, *scratch = rest
        scale_bufs = ()
    state, wide_ref, by_head = scratch[:3], scratch[3], scratch[4:]
    g = pl.program_id(0)

    def step_span(step):
        lane = jax.lax.div(step, n_qb)
        return (lane,) + _span(q_off_ref[lane], kv_len_ref[lane],
                               jax.lax.rem(step, n_qb), **span)

    @pl.when(g == 0)
    def _first_step():
        # What a partial block leaves of a buffer is masked or multiplied
        # by weights of 0: it has to be finite, which fresh VMEM need not
        # be.
        for buf in (k_buf, v_buf) + tuple(buf for _, buf in scale_bufs):
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

    _init(state)
    b = jax.lax.div(g, n_qb)
    q_lo = q_off_ref[b] + jax.lax.rem(g, n_qb) * span["block_q"]

    def fold_block(carry, slot, first_page):
        _fold_block(
            q_ref, (k_buf[slot], v_buf[slot]),
            [[buf[slot, n] for n in range(nps)] for _, buf in scale_bufs],
            state, wide_ref, by_head,
            start=first_page * span["page_size"], q_lo=q_lo,
            kv_len=kv_len_ref[b], **fold)
        return carry

    _walk_blocks(step_span,
                 _page_copies(layer_ref[0], bt_ref,
                              ((k_hbm, k_buf), (v_hbm, v_buf)), scale_bufs,
                              sem),
                 slot_ref, nps, fold_block, 0)
    _flush(out_ref, state)


def _pipelined_kernel(layer_ref, bt_ref, kv_len_ref, q_off_ref, q_ref, *rest,
                      pages_per_step: int, n_qb: int, quantized: bool,
                      span: dict, **fold):
    """Grid (B * S / bq, blocks): the block's pages arrive as
    ``pages_per_step`` operands a pool, each fetched by the pipeline under
    its own index map."""
    del layer_ref, bt_ref
    nps = pages_per_step
    n_in = (4 if quantized else 2) * nps
    k_refs, v_refs, ks_refs, vs_refs = (
        rest[i * nps:min((i + 1) * nps, n_in)] for i in range(4))
    out_ref, *scratch = rest[n_in:]
    state, wide_ref, by_head = scratch[:3], scratch[3], scratch[4:]
    g, j = pl.program_id(0), pl.program_id(1)
    b, qb = jax.lax.div(g, n_qb), jax.lax.rem(g, n_qb)

    @pl.when(j == 0)
    def _first_block():
        _init(state)

    first, n_pages = _span(q_off_ref[b], kv_len_ref[b], qb, **span)

    @pl.when(j * nps < n_pages)
    def _accumulate():
        _fold_block(
            q_ref,
            tuple(jnp.stack([r[0] for r in refs])
                  for refs in (k_refs, v_refs)),
            [[r[0] for r in refs] for refs in (ks_refs, vs_refs)
             ] if quantized else (),
            state, wide_ref, by_head,
            start=(first + j * nps) * span["page_size"],
            q_lo=q_off_ref[b] + qb * span["block_q"], kv_len=kv_len_ref[b],
            **fold)

    @pl.when(j == pl.num_programs(1) - 1)
    def _last_block():
        _flush(out_ref, state)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret",
                                             "sliding_window"))
def paged_prefill_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, layer: jax.Array,
                            block_tables: jax.Array, kv_len: jax.Array,
                            q_offset: jax.Array,
                            k_scale: jax.Array | None = None,
                            v_scale: jax.Array | None = None,
                            block_q: int = 128,
                            interpret: bool = False,
                            sliding_window: int = 0) -> jax.Array:
    """Prefill attention over the paged KV pool.

    q:            [B, S, Hq, D]  (the current chunk's queries)
    k/v_pages:    [L, P, page_size, Hkv, D]  (the stacked pool of all
                  layers, read in place; the chunk's own KV must already
                  be written)
    layer:        int32 scalar: which layer's pages to read; may be
                  traced (the model's scan index)
    block_tables: [B, MP] int32 physical page ids (0 = trash page)
    kv_len:       [B] total valid tokens (cached prefix + this chunk); 0
                  for a row of the batch that holds no sequence: it reads
                  nothing and comes back 0
    q_offset:     [B] absolute position of q[:, 0] (= prefix length)
    k/v_scale:    [P, page_size, Hkv] f32, layer ``layer``'s scales, when
                  the pool is quantized — int8 codes or uint8
                  nibble-packed int4 (trailing dim D/2); dequant happens
                  in VMEM per block. One layer's, sliced by the caller:
                  see kernels/paged_attention.py for why not stacked.
    block_q:      query tokens a grid step (the largest divisor of S not
                  over it).
    interpret:    Pallas interpret mode (tests on the CPU pass True); the
                  default compiles through Mosaic and needs a TPU.
    The MXU works in the pool's dtype (q's for integer codes): q is cast
    to it, which is exact for the bf16 activations serving hands over.
    Returns [B, S, Hq, D] in q.dtype.
    """
    quantized = k_scale is not None
    # uint8 pool = nibble-packed int4 codes (engine/kv_cache.py); the
    # pool's trailing dim is D/2 bytes and the kernel unpacks in VMEM.
    packed = k_pages.dtype == jnp.uint8
    b, s, hq, d = q.shape
    n_layers, n_pool, page_size, hkv, d_pool = k_pages.shape
    n_rep = hq // hkv
    mp = block_tables.shape[1]
    rows = page_size * hkv
    # Largest divisor of s not exceeding block_q (buckets are usually
    # powers of two, but any length must work — e.g. a 192 bucket).
    bq = next(n for n in range(min(block_q, s), 0, -1) if s % n == 0)
    n_qb = s // bq
    # A query block's window reaches back window-1 positions from its
    # first query and forward to its last: bq + window - 1 positions ->
    # at most that many pages + 1 for misalignment.
    n_page_axis = (min(mp, -(-(bq + sliding_window - 1) // page_size) + 1)
                   if sliding_window else mp)
    nps = _pages_per_step(page_size, rows * d_pool * k_pages.dtype.itemsize,
                          n_page_axis)
    cdt = (k_pages.dtype if jnp.issubdtype(k_pages.dtype, jnp.floating)
           else q.dtype)

    # [B, S, Hq, D] -> [B, QB, Hkv, bq*R, D]: GQA groups contiguous so a
    # row's kv head is its leading index and its token is row // n_rep.
    q_g = (q.astype(cdt).reshape(b, n_qb, bq, hkv, n_rep, d)
           .transpose(0, 1, 3, 2, 4, 5)
           .reshape(b, n_qb, hkv, bq * n_rep, d))
    # [page, Hkv] -> one row dim: the same bytes in HBM (no copy).
    pools = [x.reshape(n_layers, n_pool, rows, d_pool)
             for x in (k_pages, v_pages)]
    scales = ([x.reshape(n_pool, 1, rows) for x in (k_scale, v_scale)]
              if quantized else [])
    span = dict(block_q=bq, page_size=page_size, max_pages=mp,
                sliding_window=sliding_window)
    static = dict(pages_per_step=nps, n_qb=n_qb, quantized=quantized,
                  span=span, n_rep=n_rep, scale=1.0 / (d ** 0.5),
                  packed=packed, sliding_window=sliding_window)
    scratch = [
        pltpu.VMEM((hkv, bq * n_rep, 1), jnp.float32),     # running max
        pltpu.VMEM((hkv, bq * n_rep, 1), jnp.float32),     # running sum
        pltpu.VMEM((hkv, bq * n_rep, d), jnp.float32),     # running out
        pltpu.VMEM((nps * rows, d), jnp.float32),          # a block, wide
        pltpu.VMEM((hkv, nps * page_size, d), cdt),        # K by head
        pltpu.VMEM((hkv, nps * page_size, d), cdt),        # V by head
    ]
    # The decode kernel's rule (kernels/paged_attention.py): pages are
    # copied by hand where a page's codes and scales are whole 128-lane
    # tiles; the others arrive through the pipeline.
    by_hand = d_pool % 128 == 0 and (not quantized or rows % 128 == 0)

    if by_hand:
        grid = (b * n_qb,)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [hbm] * len(pools + scales)
        operands = pools + scales
        scratch = ([pltpu.VMEM((2, nps, rows, d_pool), x.dtype)
                    for x in pools]
                   + [pltpu.VMEM((2, nps, 1, rows), jnp.float32)
                      for _ in scales]
                   + [pltpu.SemaphoreType.DMA((2, 4)),  # [buffer, operand]
                      pltpu.SMEM((1,), jnp.int32)]      # buffer to use next
                   + scratch)
        kernel = functools.partial(_dma_kernel, **static)
        semantics = ("arbitrary",)
    else:
        def page(n, g, j, qo, kl, bt):
            # Past the query block's last page, stay on it: the pipeline
            # fetches nothing new and the block is skipped.
            i = g // n_qb
            first, n_pages = _span(qo[i], kl[i], g % n_qb, **span)
            at = jnp.minimum(first + j * nps + n,
                             first + jnp.maximum(n_pages, 1) - 1)
            return bt[i, jnp.minimum(at, mp - 1)]

        grid = (b * n_qb, -(-n_page_axis // nps))
        in_specs = 2 * [pl.BlockSpec(
            (None, 1, rows, d_pool),
            lambda g, j, ly, bt, kl, qo, n=n: (
                ly[0], page(n, g, j, qo, kl, bt), 0, 0))
            for n in range(nps)]
        in_specs += len(scales) * [pl.BlockSpec(
            (1, 1, rows),
            lambda g, j, ly, bt, kl, qo, n=n: (
                page(n, g, j, qo, kl, bt), 0, 0))
            for n in range(nps)]
        operands = [x for x in pools + scales for _ in range(nps)]
        kernel = functools.partial(_pipelined_kernel, **static)
        semantics = ("parallel", "arbitrary")

    q_spec = pl.BlockSpec((1, 1, hkv, bq * n_rep, d),
                          lambda g, *_: (g // n_qb, g % n_qb, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,    # layer, block_tables, kv_len, q_offset
            grid=grid, in_specs=[q_spec] + in_specs, out_specs=q_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, n_qb, hkv, bq * n_rep, d),
                                       q.dtype),
        # The query block, its f32 accumulators and one KV head's score
        # tile live in VMEM at once, over the 16 MiB a kernel gets by
        # default at the widest shapes served. The chip has 128 MiB; say
        # what the kernel may take.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret, name="paged_prefill_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), block_tables, kv_len,
      q_offset, q_g, *operands)
    return (out.reshape(b, n_qb, hkv, bq, n_rep, d)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(b, s, hq, d))
