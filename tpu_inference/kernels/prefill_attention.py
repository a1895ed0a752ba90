"""Pallas paged prefill attention (flash-style online softmax over KV pages).

Prefill previously gathered every page of a sequence into a contiguous
[B, MP*page, H, D] buffer and materialized dense [B, H, S, S_kv] scores
(models/common.py dense path) — O(S^2) HBM traffic and VMEM pressure that
walls at long context. This kernel streams each KV page HBM->VMEM once per
query block and folds it into running (m, l, acc) online-softmax state:
memory is O(S·page), the gather never materializes, and both the prompt's
own KV and any cached prefix are read from the same paged pool (the engine
writes the current chunk's KV before attending, so pool pages are the
single source of truth).

Like the decode kernel, this one takes the STACKED pool
[L, P, page, Hkv, D] plus the layer index as a scalar-prefetch operand:
the K / V index maps return (layer[0], page, 0, 0, 0), so the layer is
chosen in the DMA address and XLA never slices (= copies) one layer's
pool out of the stack in front of the call.

Layout mirrors the decode kernel (kernels/paged_attention.py): grid
(B, S/bq, MP) with the page index innermost; each instance carries a
whole query block for every kv head — q viewed [Hkv, bq*R, D] so each
page contributes one head-batched [bq*R, pg] MXU contraction per head.
Causality and cache validity fuse into one mask (k_pos <= q_pos and
k_pos < kv_len, plus k_pos > q_pos - sliding_window for SWA models);
pages entirely in the causal future or past kv_len are skipped via
@pl.when. With a sliding window the page axis is RELATIVE per query
block (scalar-prefetch index maps offset from the block's window
start), so each block touches O(block_q + window) pages, not O(S).

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

NEG_INF = -1e30


# Shared with the decode kernel — one f32-consuming unpack wrapper over
# the single packing contract in engine/kv_cache.py.
from tpu_inference.kernels.paged_attention import _unpack_int4  # noqa: E402


def _prefill_kernel(layer_ref, block_tables_ref, kv_len_ref, q_offset_ref,
                    q_ref, k_ref, v_ref, *rest, page_size: int,
                    block_q: int, n_rep: int,
                    scale: float, quantized: bool, packed: bool = False,
                    sliding_window: int = 0):
    if quantized:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = rest
    else:
        out_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    qb = pl.program_id(1)
    p = pl.program_id(2)
    num_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = kv_len_ref[b]
    q_off = q_offset_ref[b]
    q_lo = q_off + qb * block_q
    if sliding_window:
        # Page index is RELATIVE to the first page this query block's
        # window can reach (BlockSpec index maps apply the same offset):
        # pages touched per block are O(block_q + window), not O(S).
        win_first = jnp.maximum(q_lo - sliding_window + 1, 0)
        page_start = (win_first // page_size + p) * page_size
    else:
        page_start = p * page_size
    # Highest query position in this block; later pages are all-masked.
    q_hi = q_lo + block_q - 1

    @pl.when((page_start < kv_len) & (page_start <= q_hi))
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)               # [Hkv, bq*R, D]
        # Mosaic wants batched dot dims in matching positions: kv-head
        # leading on both sides.
        if packed:
            k = _unpack_int4(k_ref[0]).transpose(1, 0, 2)    # [Hkv, pg, D]
            v = _unpack_int4(v_ref[0]).transpose(1, 0, 2)
        else:
            k = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # [Hkv,pg,D]
            v = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
        if quantized:
            k = k * ks_ref[0].astype(jnp.float32).transpose(1, 0)[:, :, None]
            v = v * vs_ref[0].astype(jnp.float32).transpose(1, 0)[:, :, None]

        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [Hkv, bq*R, pg]
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // n_rep
        q_pos = q_lo + row
        k_pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = (k_pos <= q_pos) & (k_pos < kv_len)
        if sliding_window:
            valid &= k_pos > q_pos - sliding_window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:]                                 # [Hkv, bq*R, 1]
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)
        # Fully-masked rows: exp(NEG_INF - NEG_INF) = 1; zero them.
        pr = jnp.where(s > NEG_INF / 2, pr, 0.0)
        o = jax.lax.dot_general(
            pr, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [Hkv, bq*R, D]
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(pr, axis=2, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + o

    @pl.when(p == num_pages - 1)
    def _flush():
        denom = jnp.maximum(l_ref[:], 1e-20)
        out_ref[0, 0] = (acc_ref[:] / denom).astype(out_ref.dtype)


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
    kv_len:       [B] total valid tokens (cached prefix + this chunk)
    q_offset:     [B] absolute position of q[:, 0] (= prefix length)
    k/v_scale:    [P, page_size, Hkv] f32, layer ``layer``'s scales, when
                  the pool is quantized — int8 codes or uint8
                  nibble-packed int4 (trailing dim D/2); dequant happens
                  in VMEM per page. One layer's, sliced by the caller:
                  see kernels/paged_attention.py for why not stacked.
    interpret:    Pallas interpret mode (tests on the CPU pass True); the
                  default compiles through Mosaic and needs a TPU.
    Returns [B, S, Hq, D] in q.dtype.
    """
    quantized = k_scale is not None
    # uint8 pool = nibble-packed int4 codes (engine/kv_cache.py); the
    # pool's trailing dim is D/2 bytes and the kernel unpacks in VMEM.
    packed = k_pages.dtype == jnp.uint8
    b, s, hq, d = q.shape
    _, _, page_size, hkv, d_pool = k_pages.shape
    n_rep = hq // hkv
    mp = block_tables.shape[1]
    scale = 1.0 / (d ** 0.5)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # Largest divisor of s not exceeding block_q (buckets are usually
    # powers of two, but any length must work — e.g. a 192 bucket).
    bq = next(b for b in range(min(block_q, s), 0, -1) if s % b == 0)
    n_qb = s // bq

    # [B, S, Hq, D] -> [B, QB, Hkv, bq*R, D]: GQA groups contiguous so a
    # row's kv head is row // n_rep within its block.
    q_g = (q.reshape(b, n_qb, bq, hkv, n_rep, d)
           .transpose(0, 1, 3, 2, 4, 5)
           .reshape(b, n_qb, hkv, bq * n_rep, d))

    if sliding_window:
        # A query block's window reaches back window-1 positions from
        # its first query and forward to its last: bq + window - 1
        # positions -> at most that many pages + 1 for misalignment.
        n_page_axis = min(mp, -(-(bq + sliding_window - 1) // page_size) + 1)

        def page_idx(i, qb, p, bt, kl, qo):
            first = jnp.maximum(qo[i] + qb * bq - sliding_window + 1, 0)
            # Clamp: relative pages past the block table are compute-
            # masked in the kernel; the DMA just needs a legal id.
            return bt[i, jnp.minimum(first // page_size + p, mp - 1)]
    else:
        n_page_axis = mp

        def page_idx(i, qb, p, bt, kl, qo):
            return bt[i, p]

    # Leading layer dim squeezed (None): the kernel body sees one page,
    # [1, page, Hkv, D], exactly as it did with a per-layer pool.
    page_spec = pl.BlockSpec(
        (None, 1, page_size, hkv, d_pool),
        lambda i, qb, p, ly, bt, kl, qo: (
            ly[0], page_idx(i, qb, p, bt, kl, qo), 0, 0, 0))
    q_spec = pl.BlockSpec((1, 1, hkv, bq * n_rep, d),
                          lambda i, qb, p, ly, bt, kl, qo: (i, qb, 0, 0, 0))
    in_specs = [q_spec, page_spec, page_spec]
    operands = [q_g, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, page_size, hkv),
            lambda i, qb, p, ly, bt, kl, qo: (
                page_idx(i, qb, p, bt, kl, qo), 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,    # layer, block_tables, kv_len, q_offset
        grid=(b, n_qb, n_page_axis),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, bq * n_rep, 1), jnp.float32),   # running max
            pltpu.VMEM((hkv, bq * n_rep, 1), jnp.float32),   # running sum
            pltpu.VMEM((hkv, bq * n_rep, d), jnp.float32),   # running out
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, page_size=page_size, block_q=bq,
                          n_rep=n_rep, scale=scale, quantized=quantized,
                          packed=packed, sliding_window=sliding_window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_qb, hkv, bq * n_rep, d),
                                       q.dtype),
        # The query block, its f32 accumulators and the score tile of
        # all hq*bq = 4096 rows live in VMEM at once: ~17 MiB at 32
        # heads x 128, just over the 16 MiB a kernel gets by default
        # (the v5e compiler refused the 4x512 prefill graph for 1.4
        # MiB). The chip has 128 MiB; say what the kernel may take.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
    )(layer, block_tables, kv_len, q_offset, *operands)
    return (out.reshape(b, n_qb, hkv, bq, n_rep, d)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(b, s, hq, d))
