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
  codes), never one layer's slice of it:
  a ``pallas_call`` operand is a buffer of its own, so ``pool[layer]``
  under the model's ``lax.scan`` over layers made XLA copy a whole
  layer's pool (~100 MB, twice a layer) in front of every call. Which
  layer is read is part of the DMA address instead.
- ``PrefetchScalarGridSpec`` with the layer index, the block table + kv
  lengths as scalar prefetch: the KV BlockSpec's index_map returns
  ``(layer[0], block_tables[b, p], 0, 0, 0)`` to pick which physical
  page of which layer the pipeline DMAs next — neither the layer slice
  nor the gather materializes.
- Grid (B, MP), page index innermost; VMEM scratch (m, l, acc) carries
  the online-softmax state across a sequence's pages and is flushed to
  the output on the last page.
- GQA folded in-kernel: q viewed [Hkv, n_rep, D], each KV head's page
  serves its n_rep query heads via one MXU contraction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _unpack_int4(packed):
    """uint8 nibble-packed [..., D//2] -> f32 [..., D]. ONE copy of the
    packing contract (engine/kv_cache.py unpack_int4_kv: integer
    compare/select sign extension, Mosaic-friendly); the f32 cast is
    this kernel's consumption dtype."""
    from tpu_inference.engine.kv_cache import unpack_int4_kv

    return unpack_int4_kv(packed).astype(jnp.float32)


def _decode_kernel(layer_ref, block_tables_ref, kv_len_ref, q_ref, k_ref,
                   v_ref, *rest, page_size: int, scale: float,
                   quantized: bool,
                   packed: bool = False, sliding_window: int = 0):
    if quantized:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = rest
    else:
        out_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    num_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = kv_len_ref[b]
    if sliding_window:
        # Grid position p is RELATIVE to the window's first page (the
        # BlockSpec index maps apply the same offset), so decode reads
        # O(window) pages however long the context is — the property
        # SWA models (Mistral) are built around.
        win_start = jnp.maximum(kv_len - sliding_window, 0)
        page_start = (win_start // page_size + p) * page_size
    else:
        page_start = p * page_size

    @pl.when(page_start < kv_len)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)                  # [Hkv, R, D]
        # Mosaic requires dot_general batch dims at matching positions, so
        # bring the kv-head dim to the front before the batched contractions.
        if packed:
            # int4: one uint8 read of half a page's bytes, unpacked in VMEM.
            k = _unpack_int4(k_ref[0]).transpose(1, 0, 2)    # [Hkv, pg, D]
            v = _unpack_int4(v_ref[0]).transpose(1, 0, 2)
        else:
            k = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # [Hkv,pg,D]
            v = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
        if quantized:
            # int8 codes * per-(token, head) scale — dequant in VMEM, so
            # HBM sees one int8 read of the page.
            k = k * ks_ref[0].astype(jnp.float32).transpose(1, 0)[:, :, None]
            v = v * vs_ref[0].astype(jnp.float32).transpose(1, 0)[:, :, None]

        # scores[h, r, t] = <q[h, r], k[h, t]> * scale
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # [Hkv, R, pg]
        pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=2)
        valid = pos < kv_len
        if sliding_window:
            # Window edge can fall inside this page.
            valid = jnp.logical_and(valid, pos >= kv_len - sliding_window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:]                                  # [Hkv, R]
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=2)                         # [Hkv, R]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new[:, :, None])                # [Hkv, R, pg]
        # o[h, r, d] = sum_t pr[h, r, t] * v[h, t, d]
        o = jax.lax.dot_general(
            pr, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [Hkv, R, D]
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(pr, axis=2)
        acc_ref[:] = acc_ref[:] * alpha[:, :, None] + o

    @pl.when(p == num_pages - 1)
    def _flush():
        denom = jnp.maximum(l_ref[:], 1e-20)[:, :, None]
        out_ref[0] = (acc_ref[:] / denom).astype(out_ref.dtype)


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
                  names, in layer ``layer``, leave HBM)
    layer:        int32 scalar: which layer's pages to read; may be
                  traced (the model's scan index)
    block_tables: [B, MP] int32 physical page ids (0 = trash page)
    kv_len:       [B] int32 valid tokens per sequence (incl. current)
    k/v_scale:    [P, page_size, Hkv] f32, layer ``layer``'s scales —
                  present when the pool holds int8 codes
                  (engine/kv_cache.py quantize_kv) or uint8 nibble-packed
                  int4 codes (quantize_kv_int4; pool trailing dim D/2);
                  dequant happens in VMEM after each page's DMA. ONE
                  layer's, sliced by the caller, and not the stacked
                  [L, P, page, Hkv]: the chip keeps that f32 array with
                  the page dim minor-most (its last dim, Hkv, is far
                  under a 128-lane tile), a kernel operand has to be
                  row-major, and so XLA re-lays-out whatever it is
                  handed — one layer's scales (1/L of the scale pool, 1%
                  of the layer's codes) or, stacked, all L layers' in
                  front of every call (v5e compile: +0.4 GB of temps at
                  1024 pages).
    sliding_window > 0 (SWA, Mistral): only the pages overlapping the
    last ``sliding_window`` positions are streamed — the grid's page
    axis shrinks to the window's page span and the index maps offset
    into the block table from the window's first page, so decode cost
    is O(window), not O(context).
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
    _, _, page_size, hkv, d_pool = k_pages.shape
    n_rep = hq // hkv
    mp = block_tables.shape[1]
    scale = 1.0 / (d ** 0.5)

    q_g = q.reshape(b, hkv, n_rep, d)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    if sliding_window:
        # A window of W positions spans at most ceil(W/page)+1 pages
        # when unaligned to page boundaries.
        n_page_axis = min(mp, -(-sliding_window // page_size) + 1)

        def page_idx(i, p, bt, kl):
            start = jnp.maximum(kl[i] - sliding_window, 0) // page_size
            # Clamp: relative pages past the sequence's last page are
            # compute-masked in the kernel; the DMA just needs a legal id.
            return bt[i, jnp.minimum(start + p, mp - 1)]
    else:
        n_page_axis = mp

        def page_idx(i, p, bt, kl):
            return bt[i, p]

    # Leading layer dim squeezed (None): the kernel body sees one page,
    # [1, page, Hkv, D], exactly as it did with a per-layer pool.
    page_spec = pl.BlockSpec(
        (None, 1, page_size, hkv, d_pool),
        lambda i, p, ly, bt, kl: (ly[0], page_idx(i, p, bt, kl),
                                  0, 0, 0))
    q_spec = pl.BlockSpec((1, hkv, n_rep, d),
                          lambda i, p, ly, bt, kl: (i, 0, 0, 0))
    in_specs = [q_spec, page_spec, page_spec]
    operands = [q_g, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, page_size, hkv),
            lambda i, p, ly, bt, kl: (page_idx(i, p, bt, kl), 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # layer, block_tables, kv_len
        grid=(b, n_page_axis),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, n_rep), jnp.float32),       # running max
            pltpu.VMEM((hkv, n_rep), jnp.float32),       # running sum
            pltpu.VMEM((hkv, n_rep, d), jnp.float32),    # running out
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, scale=scale,
                          quantized=quantized, packed=packed,
                          sliding_window=sliding_window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, n_rep, d), q.dtype),
        interpret=interpret,
    )(layer, block_tables, kv_len, *operands)
    return out.reshape(b, hq, d)
