"""Pallas paged-attention decode kernel (flash-style online softmax).

One query token per sequence attends over its KV pages scattered through
the HBM pool (engine/kv_cache.py). The dense fallback path first gathers
a sequence's pages into a contiguous buffer ([B, max_pages*page, H, D])
every layer, every step — a full extra HBM round trip of the KV working
set. This kernel instead streams each page HBM->VMEM exactly once and
folds it into running (max, sum, acc) online-softmax state, the standard
TPU pattern for decode attention (vLLM's PagedAttention re-designed for
Mosaic; reference has no analogue — SURVEY.md §2b).

Mechanics:
- The kernel's K / V operands are the engine's STACKED pool
  ``[L, P, page, Hkv, D]`` (all layers; bf16, int8 or packed-int4
  codes), never one layer's slice of it: a ``pallas_call`` operand is a
  buffer of its own, so ``pool[layer]`` under the model's ``lax.scan``
  over layers made XLA copy a whole layer's pool (~100 MB, twice a layer)
  in front of every call. The layer, the block table and the kv lengths
  are scalar-prefetched, so layer and physical page are part of each
  page DMA's address.
- A BLOCK of ``pages_per_step`` pages at a time (about 256 tokens, at most
  ~1 MiB of K + V: ``_pages_per_step``, from the operand shapes), counted
  from the window's first page, wherever in the block table that is.
  Before PR 27 it was one 16-token page a grid step on a grid of
  ``(B, window pages)``: 2056 steps a call at rung 8, ~97% of them empty
  in a chat cell, each with a transpose and an f32 copy of K and V.
- How a block's pages arrive (``_dma_kernel``): the pool stays in HBM
  (``pl.ANY``), the grid is ``(B,)``, one step a lane of the rung, and
  the lane's blocks are a loop of as many trips as it HAS blocks, from
  the window's first page to its last token's page. Each trip copies its
  pages into one half of a VMEM double buffer (``make_async_copy``, a DMA
  a page a pool) while the other half is computed; the next lane's first
  block is started during this lane's last, and which half comes next is
  carried from lane to lane in SMEM. A lane with ``kv_len`` 0 (an idle
  lane of the rung) runs no trip, a block's pages past the lane's last
  are not copied, and no page the block table does not name for a visible
  position ever leaves HBM. Measured on the v5e against the same block
  fed by the pipeline (16 page operands a pool, each with its own index
  map: ``_pipelined_kernel`` below): 27 against 392 us a call for three
  short lanes of eight, 163 against 460 us for six full-window ones
  (PERF.md, PR 27): a grid step evaluates every operand's index map
  whether it fetches or not, ~2.9 us a step with 32 of them.
- The pipeline-fed form (``_pipelined_kernel``, grid ``(B, blocks)``) is
  kept for the pools Mosaic cannot copy from by hand: it slices a ref only
  along whole 128-lane tiles, so a page whose minor dim is no multiple of
  128 (head_dim 96; int4's D / 2 bytes; the ``[1, page * Hkv]`` scales of
  an int8 page under 8 KV heads) cannot be addressed in ``pl.ANY``. One
  block body (``_attend``) serves both; the choice is read off the
  operand shapes. No cell serves such a pool.
- No transpose, no float32 copy of K or V. The pool is viewed
  ``[L, P, page * Hkv, D]`` (the same bytes: merging the two dims above
  the minor one keeps the chip's tiling, so XLA makes no copy), and a
  block is a ``[T * Hkv, D]`` matrix whose row ``t * Hkv + h`` is token
  t's head h. ALL query heads are scored against all of its rows in one
  MXU contraction in the pool's dtype with float32 accumulation, and a
  mask keeps, for query head r, the columns of its own KV head
  (``col % Hkv == r // n_rep``) beside the length / window mask. The MXU
  has to take every K and V tile once whichever heads ask, so the
  cross-head products cost it nothing; the softmax runs on Hkv times the
  needed columns, which is cheap beside a per-head relayout.
- Softmax state (max, sum, acc) is float32. The weights go to the MXU as
  TWO pool-dtype halves (``p = hi + lo``, both bf16, stacked on the row
  dim so V is loaded once): V is exact in its dtype, so the sum carries
  ~16 bits of p. One rounding of p to bf16 is 5e-3 to 7e-3 of the
  output's spread off (the rounding alone, 100 to 4096 keys), over
  ``chip_smoke.py``'s 1e-3; the two halves read 1.2e-05 on the chip.
- Quantized pools: the codes go to the MXU as they are (int8 and int4
  codes are exact in bf16) and the per-(token, head) scales multiply the
  score COLUMNS (K) and the weights (V) instead of the codes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_inference.kernels import mxu_precision

NEG_INF = -1e30

# A block of pages: about this many tokens, at most this many bytes of
# K + V codes (one of the two VMEM buffers).
BLOCK_TOKENS = 256
BLOCK_BYTES = 1 << 20


def _pages_per_step(page_size: int, page_bytes: int, n_page_axis: int) -> int:
    """Pages a block holds, from what the call can see: the page's tokens
    and bytes (``page * Hkv * D_pool * itemsize``) and how many pages a
    lane can need at all (``mp``, or the window's span)."""
    return max(1, min(BLOCK_TOKENS // page_size,
                      BLOCK_BYTES // (2 * page_bytes), n_page_axis))


def _int_div(x, n: int):
    """x // n and x % n of non-negative int32 vectors; shifts where n is
    a power of two (every head count served)."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1), x & (n - 1)
    return jax.lax.div(x, n), jax.lax.rem(x, n)


def _unpack_int4(packed):
    """uint8 nibble-packed [..., D//2] -> f32 [..., D]. ONE copy of the
    packing contract (engine/kv_cache.py unpack_int4_kv: integer
    compare/select sign extension, Mosaic-friendly); float32 is what both
    paged kernels widen the codes to first."""
    from tpu_inference.engine.kv_cache import unpack_int4_kv

    return unpack_int4_kv(packed).astype(jnp.float32)


def _codes(block, packed: bool, dtype):
    """A block's codes ``[nps, rows, D_pool]`` as the ``[nps * rows, D]``
    matrix the MXU takes, in ``dtype``."""
    if packed:
        # int4: one uint8 read of half a page's bytes, unpacked in VMEM.
        block = _unpack_int4(block)
    if block.dtype != dtype:
        # Integer codes reach bf16 through float32 (exact either way).
        block = block.astype(jnp.float32).astype(dtype)
    return block.reshape(-1, block.shape[-1])


def _attend(carry, q, k, v, k_scale, v_scale, *, start, kv_len, n_kv: int,
            n_rep: int, scale: float, sliding_window: int):
    """Fold one block into the online-softmax state ``carry`` = (max
    [Hq, 1], sum [Hq, 1], acc [Hq, D]), all float32.

    q [Hq, D]; k, v [T * Hkv, D] in q's dtype, row ``t * Hkv + h`` token
    ``start + t``'s KV head h; k_scale / v_scale [1, T * Hkv] float32 or
    None. Every query head meets every row on the MXU; the mask keeps
    query head r the columns of KV head r // n_rep at visible positions.
    """
    m_prev, l_prev, acc = carry
    hq = q.shape[0]
    prec = mxu_precision(q.dtype)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32) * scale        # [Hq, T * Hkv]
    if k_scale is not None:
        s = s * k_scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    tok, head = _int_div(col, n_kv)
    pos = start + tok
    valid = ((row >= head * n_rep) & (row < (head + 1) * n_rep)
             & (pos < kv_len))
    if sliding_window:
        # The window's edge can fall inside its first page.
        valid = valid & (pos >= kv_len - sliding_window)
    s = jnp.where(valid, s, NEG_INF)

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # where(): a row without a column here keeps m at NEG_INF, and
    # exp(0) = 1 would count its masked columns.
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    if v_scale is not None:
        p = jnp.where(valid, p * v_scale, 0.0)
    if v.dtype == jnp.float32:
        o = jnp.dot(p, v, precision=prec, preferred_element_type=jnp.float32)
    else:
        # p = hi + lo, both in V's dtype and stacked on the row dim (V
        # goes through the MXU once): ~16 bits of p against V's exact
        # values, where hi alone is ~6e-3 of the output's spread off.
        hi = p.astype(v.dtype)
        lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
        o = jnp.dot(jnp.concatenate([hi, lo], axis=0), v, precision=prec,
                    preferred_element_type=jnp.float32)
        o = o[:hq] + o[hq:]
    return m_new, l_new, acc * alpha + o


def _first_page(kv_len, page_size: int, sliding_window: int):
    """Block-table position of the first page a lane reads: 0, or the
    page the window starts in (wherever in the table that is: blocks are
    counted from it, so a window needs no alignment)."""
    if not sliding_window:
        return 0
    return jnp.maximum(kv_len - sliding_window, 0) // page_size


def _lanes(refs):
    """Per-page [1, rows] scale tiles side by side: [1, T * Hkv]."""
    return jnp.concatenate(list(refs), axis=1)


def _page_copies(layer, bt_ref, codes, scales, sem):
    """``copies(lane, at, count, slot, wait=False)``: start, or wait for,
    the copies of block-table positions ``at .. at + count`` of row
    ``lane`` into half ``slot`` of the VMEM double buffers: one DMA a
    page a pool. ``codes`` / ``scales`` pair each pool in HBM with its
    buffer ``[2, pages, ...]``. Start and wait walk the same descriptors
    over the same pages; issued from a loop, not unrolled (PR 27: 16
    pages x 3 sites unrolled cost 0.3 s of tracing a warm-up graph)."""

    def copies(lane, at, count, slot, wait=False):
        def page_dmas(n, carry):
            page = bt_ref[lane, at + n]
            dmas = [pltpu.make_async_copy(
                hbm.at[layer, page], buf.at[slot, n], sem.at[slot, i])
                for i, (hbm, buf) in enumerate(codes)]
            dmas += [pltpu.make_async_copy(
                hbm.at[page], buf.at[slot, n], sem.at[slot, 2 + i])
                for i, (hbm, buf) in enumerate(scales)]
            for c in dmas:
                c.wait() if wait else c.start()
            return carry

        jax.lax.fori_loop(0, count, page_dmas, 0)

    return copies


def _walk_blocks(span, copies, slot_ref, pages_per_step: int, fold, init):
    """Fold the blocks of this step of a 1-D grid: a loop of as many
    trips as the step HAS blocks, each block's pages copied into one half
    of the double buffer while the other half is folded.

    ``span(step)`` -> (block-table row, first position in it, pages) of
    what grid step ``step`` reads, from scalars alone; ``copies`` as
    ``_page_copies`` makes it; ``fold(carry, slot, first page of the
    block)`` -> carry. A step's first block is started by the step before
    it, during its last block or, if that step has none, in its place;
    step 0 starts its own. Which half comes next is carried from step to
    step in ``slot_ref`` (SMEM), which the caller zeroes at step 0. The
    scalar arithmetic is lax's, not jnp's: every jnp call traces a jit of
    its own, and a boot traces and lowers each warm-up graph before it
    can ask the compile cache."""
    nps = pages_per_step
    g = pl.program_id(0)
    more_steps = g + 1 < pl.num_programs(0)
    _, first, n_pages = span(g)
    n_blocks = jax.lax.div(n_pages + nps - 1, nps)
    slot0 = slot_ref[0]

    def block_dmas(step, j, slot, wait=False):
        lane, first, n_pages = span(step)
        # None for a position past the step's last page.
        copies(lane, first + j * nps,
               jax.lax.clamp(0, n_pages - j * nps, nps), slot, wait)

    @pl.when(((g == 0) & (n_blocks > 0)) | ((n_blocks == 0) & more_steps))
    def _first_block():
        block_dmas(jax.lax.select(n_blocks == 0, g + 1, g), 0, slot0)

    def block(j, carry):
        slot = jax.lax.rem(slot0 + j, 2)
        last_block = j + 1 == n_blocks

        # The next block, or the next step's first, flies while this one
        # is computed.
        @pl.when(~last_block | more_steps)
        def _next_block():
            block_dmas(jax.lax.select(last_block, g + 1, g),
                       jax.lax.select(last_block, 0, j + 1), 1 - slot)

        block_dmas(g, j, slot, wait=True)
        return fold(carry, slot, first + j * nps)

    carry = jax.lax.fori_loop(0, n_blocks, block, init)
    slot_ref[0] = jax.lax.rem(slot0 + n_blocks, 2)
    return carry


def _dma_kernel(layer_ref, bt_ref, kv_len_ref, q_ref, *rest,
                pages_per_step: int, page_size: int, max_pages: int,
                quantized: bool, packed: bool, sliding_window: int,
                **attend):
    """Grid (B,): the lane's blocks in a loop of as many trips as it has
    blocks, the pages copied from the pool in HBM by hand."""
    nps = pages_per_step
    if quantized:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, out_ref,
         k_buf, v_buf, ks_buf, vs_buf, sem, slot_ref) = rest
        scales = ((ks_hbm, ks_buf), (vs_hbm, vs_buf))
    else:
        k_hbm, v_hbm, out_ref, k_buf, v_buf, sem, slot_ref = rest
        scales = ()
    b = pl.program_id(0)

    def span(lane):
        """(lane, first page, pages) of the block-table positions
        ``lane`` reads: from the window's first page to its last token's
        page."""
        kv_len = kv_len_ref[lane]
        first = _first_page(kv_len, page_size, sliding_window)
        last = jnp.minimum((kv_len - 1) // page_size, max_pages - 1)
        return lane, first, jnp.where(kv_len > 0, last - first + 1, 0)

    @pl.when(b == 0)
    def _first_lane():
        # What a partial block leaves of a buffer is multiplied by
        # weights of 0: it has to be finite, which fresh VMEM need not be.
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0

    kv_len = kv_len_ref[b]
    q = q_ref[0]                                           # [Hq, D]

    def fold(carry, slot, first_page):
        return _attend(
            carry, q, _codes(k_buf[slot], packed, q.dtype),
            _codes(v_buf[slot], packed, q.dtype),
            _lanes(ks_buf[slot, n] for n in range(nps)) if quantized else None,
            _lanes(vs_buf[slot, n] for n in range(nps)) if quantized else None,
            start=first_page * page_size, kv_len=kv_len,
            sliding_window=sliding_window, **attend)

    _, l, acc = _walk_blocks(
        span, _page_copies(layer_ref[0], bt_ref, ((k_hbm, k_buf),
                                                  (v_hbm, v_buf)),
                           scales, sem),
        slot_ref, nps, fold,
        (jnp.full((q.shape[0], 1), NEG_INF, jnp.float32),
         jnp.zeros((q.shape[0], 1), jnp.float32),
         jnp.zeros(q.shape, jnp.float32)))
    # A lane that read nothing (kv_len 0) gives 0, not NaN.
    out_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)


def _pipelined_kernel(layer_ref, bt_ref, kv_len_ref, q_ref, *rest,
                      pages_per_step: int, page_size: int, quantized: bool,
                      packed: bool, sliding_window: int, **attend):
    """Grid (B, blocks): the block's pages arrive as ``pages_per_step``
    operands a pool, each fetched by the pipeline under its own index map
    (what the latent kernel did too, before PR 31)."""
    del layer_ref, bt_ref
    nps = pages_per_step
    n_in = (4 if quantized else 2) * nps
    k_refs, v_refs, ks_refs, vs_refs = (
        rest[i * nps:min((i + 1) * nps, n_in)] for i in range(4))
    out_ref, m_ref, l_ref, acc_ref = rest[n_in:]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = kv_len_ref[b]
    start = (_first_page(kv_len, page_size, sliding_window)
             + j * nps) * page_size

    @pl.when(start < kv_len)
    def _accumulate():
        q = q_ref[0]

        def codes(refs):
            return _codes(jnp.stack([r[0] for r in refs]), packed, q.dtype)

        m_ref[:], l_ref[:], acc_ref[:] = _attend(
            (m_ref[:], l_ref[:], acc_ref[:]), q, codes(k_refs), codes(v_refs),
            _lanes(r[0] for r in ks_refs) if quantized else None,
            _lanes(r[0] for r in vs_refs) if quantized else None,
            start=start, kv_len=kv_len, sliding_window=sliding_window,
            **attend)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        out_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-20)
                      ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "sliding_window"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    layer: jax.Array, block_tables: jax.Array,
                    kv_len: jax.Array,
                    k_scale: jax.Array | None = None,
                    v_scale: jax.Array | None = None,
                    interpret: bool = False,
                    sliding_window: int = 0) -> jax.Array:
    """Decode attention over the paged KV pool.

    q:            [B, Hq, D]   (one query token per sequence)
    k/v_pages:    [L, P, page_size, Hkv, D]  (the stacked pool of all
                  layers, read in place: only the pages the block table
                  names for visible positions, in layer ``layer``, leave
                  HBM)
    layer:        int32 scalar: which layer's pages to read; may be
                  traced (the model's scan index)
    block_tables: [B, MP] int32 physical page ids (0 = trash page)
    kv_len:       [B] int32 valid tokens per sequence (incl. current);
                  0 for a lane that holds no sequence: it reads nothing
                  and its rows come back 0
    k/v_scale:    [P, page_size, Hkv] f32, layer ``layer``'s scales —
                  present when the pool holds int8 codes
                  (engine/kv_cache.py quantize_kv) or uint8 nibble-packed
                  int4 codes (quantize_kv_int4; pool trailing dim D/2);
                  they scale score columns and weights in VMEM after each
                  page's DMA. ONE layer's, sliced by the caller, and not
                  the stacked [L, P, page, Hkv]: the chip keeps that f32
                  array with the page dim minor-most (its last dim, Hkv,
                  is far under a 128-lane tile), a kernel operand has to
                  be row-major, and so XLA re-lays-out whatever it is
                  handed — one layer's scales (1/L of the scale pool, 1%
                  of the layer's codes) or, stacked, all L layers' in
                  front of every call (v5e compile: +0.4 GB of temps at
                  1024 pages).
    sliding_window > 0 (SWA, Mistral): only the pages overlapping the
    last ``sliding_window`` positions are streamed — a lane's blocks
    start at the window's first page, wherever in the block table that
    is, so decode cost is O(window), not O(context).
    The MXU works in the pool's dtype (q's for integer codes): q is cast
    to it, which is exact for the bf16 activations serving hands over.
    interpret: run in Pallas interpret mode (tests on the CPU pass True).
    The default compiles through Mosaic and so needs a TPU — the backend
    is never consulted to pick a slower mode quietly.
    Returns [B, Hq, D] in q.dtype.
    """
    quantized = k_scale is not None
    # uint8 pool = nibble-packed int4 codes (engine/kv_cache.py); the
    # pool's trailing dim is D/2 bytes and the kernel unpacks in VMEM.
    packed = k_pages.dtype == jnp.uint8
    b, hq, d = q.shape
    n_layers, n_pool, page_size, hkv, d_pool = k_pages.shape
    mp = block_tables.shape[1]
    rows = page_size * hkv
    # A window of W positions spans at most ceil(W/page)+1 pages when
    # unaligned to page boundaries.
    n_page_axis = (min(mp, -(-sliding_window // page_size) + 1)
                   if sliding_window else mp)
    nps = _pages_per_step(page_size, rows * d_pool * k_pages.dtype.itemsize,
                          n_page_axis)
    cdt = (k_pages.dtype if jnp.issubdtype(k_pages.dtype, jnp.floating)
           else q.dtype)
    # [page, Hkv] -> one row dim: the same bytes in HBM (no copy).
    pools = [x.reshape(n_layers, n_pool, rows, d_pool)
             for x in (k_pages, v_pages)]
    scales = ([x.reshape(n_pool, 1, rows) for x in (k_scale, v_scale)]
              if quantized else [])
    static = dict(pages_per_step=nps, page_size=page_size, n_kv=hkv,
                  n_rep=hq // hkv, scale=1.0 / (d ** 0.5),
                  quantized=quantized, packed=packed,
                  sliding_window=sliding_window)
    # Mosaic slices a ref it copies from only along whole 128-lane tiles,
    # so the kernel can copy pages by hand only where a page's codes (and
    # its scales, [1, page * Hkv]) have a minor dim that is a multiple of
    # 128: D = 128 as bf16, or as int8 with 8 KV heads. The others (D =
    # 96; int4's D / 2 bytes; int8 under 8 KV heads) get their pages from
    # the pipeline.
    by_hand = d_pool % 128 == 0 and (not quantized or rows % 128 == 0)

    if by_hand:
        grid = (b,)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [hbm] * len(pools + scales)
        operands = pools + scales
        scratch = [pltpu.VMEM((2, nps, rows, d_pool), x.dtype) for x in pools]
        scratch += [pltpu.VMEM((2, nps, 1, rows), jnp.float32)
                    for _ in scales]
        scratch += [pltpu.SemaphoreType.DMA((2, 4)),   # [buffer, operand]
                    pltpu.SMEM((1,), jnp.int32)]       # buffer to use next
        kernel = functools.partial(_dma_kernel, max_pages=mp, **static)
        semantics = ("arbitrary",)
    else:
        def page(n, i, j, bt, kl):
            # Past the lane's last page, stay on it: the pipeline fetches
            # nothing new and the block is skipped.
            last = jnp.maximum(kl[i] - 1, 0) // page_size
            at = _first_page(kl[i], page_size, sliding_window) + j * nps + n
            return bt[i, jnp.minimum(jnp.minimum(at, last), mp - 1)]

        grid = (b, -(-n_page_axis // nps))
        in_specs = 2 * [pl.BlockSpec(
            (None, 1, rows, d_pool),
            lambda i, j, ly, bt, kl, n=n: (ly[0], page(n, i, j, bt, kl), 0, 0))
            for n in range(nps)]
        in_specs += len(scales) * [pl.BlockSpec(
            (1, 1, rows),
            lambda i, j, ly, bt, kl, n=n: (page(n, i, j, bt, kl), 0, 0))
            for n in range(nps)]
        operands = [x for x in pools + scales for _ in range(nps)]
        scratch = [pltpu.VMEM((hq, 1), jnp.float32),       # running max
                   pltpu.VMEM((hq, 1), jnp.float32),       # running sum
                   pltpu.VMEM((hq, d), jnp.float32)]       # running out
        kernel = functools.partial(_pipelined_kernel, **static)
        semantics = ("parallel", "arbitrary")

    lane_spec = pl.BlockSpec((1, hq, d), lambda i, *_: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # layer, block_tables, kv_len
            grid=grid, in_specs=[lane_spec] + in_specs, out_specs=lane_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret, name="paged_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), block_tables, kv_len,
      q.astype(cdt), *operands)
