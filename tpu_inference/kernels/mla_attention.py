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
A grid step covers ``pages_per_step`` pages: the pool is passed that many
times, each with its own BlockSpec whose index map names one page of the
block table, so the pipeline fetches them all while the previous block
computes; 16 pages x 16 tokens give the MXU a 256-token tile instead of
a 16-token one. Pages past the
sequence's (or the query block's causal) end repeat the last needed
page's index, which the pipeline does not fetch again, and are skipped.
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

NEG_INF = -1e30


def mxu_precision(dtype):
    """bf16 operands go to the MXU as they are whatever
    ``jax_default_matmul_precision`` says (Mosaic refuses a bf16 dot asked
    for at float32 precision); float32 operands (interpret-mode tests)
    keep the ambient setting."""
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _mla_kernel(layer_ref, bt_ref, kv_len_ref, q_off_ref, q_ref, *rest,
                pages_per_step: int, page_size: int, block_q: int,
                n_heads: int, rank: int, scale: float):
    del layer_ref, bt_ref
    page_refs = rest[:pages_per_step]
    out_ref, m_ref, l_ref, acc_ref = rest[pages_per_step:]
    b, qb, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = kv_len_ref[b]
    q_lo = q_off_ref[b] + qb * block_q
    start = j * pages_per_step * page_size

    @pl.when((start < kv_len) & (start <= q_lo + block_q - 1))
    def _accumulate():
        kv = jnp.concatenate([r[0] for r in page_refs], axis=0)  # [T, W]
        c = kv[:, :rank]                                   # [T, R]
        prec = mxu_precision(kv.dtype)
        s = jax.lax.dot_general(
            q_ref[0, 0], kv, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale    # [bq*H, T]
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                0) // n_heads
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
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

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        out_ref[0, 0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-20)
                         ).astype(out_ref.dtype)


def _mla_call(name: str, q, pool, layer, block_tables, kv_len, q_offset,
              *, rank: int, scale: float, block_q: int, pages_per_step: int,
              interpret: bool):
    """q [B, S, H, R + Dr] -> [B, S, H, R]."""
    b, s, h, qd = q.shape
    _, _, page_size, width = pool.shape
    assert rank < qd <= width, (q.shape, pool.shape, rank)
    mp = block_tables.shape[1]
    nps = min(pages_per_step, mp)
    bq = next(x for x in range(min(block_q, s), 0, -1) if s % x == 0)
    n_qb, rows = s // bq, bq * h
    n_kb = -(-mp // nps)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    qp = jnp.pad(q.astype(pool.dtype), ((0, 0),) * 3 + ((0, width - qd),)
                 ).reshape(b, n_qb, rows, width)

    def page_map(n):
        def index(i, qb, j, ly, bt, kl, qo):
            # The last page this query block can see: past it, repeat
            # that page (same block index: no new DMA; compute skipped).
            seen = jnp.minimum(kl[i], qo[i] + (qb + 1) * bq)
            last = jnp.maximum(seen - 1, 0) // page_size
            return ly[0], bt[i, jnp.minimum(j * nps + n, last)], 0, 0
        return index

    def q_spec(d):
        return pl.BlockSpec((1, 1, rows, d),
                            lambda i, qb, j, ly, bt, kl, qo: (i, qb, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,       # layer, block_tables, kv_len, q_offset
        grid=(b, n_qb, n_kb),
        in_specs=[q_spec(width)] + [
            pl.BlockSpec((None, 1, page_size, width), page_map(n))
            for n in range(nps)],
        out_specs=q_spec(rank),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),     # max
                        pltpu.VMEM((rows, 1), jnp.float32),     # sum
                        pltpu.VMEM((rows, rank), jnp.float32)])  # out
    out = pl.pallas_call(
        functools.partial(_mla_kernel, pages_per_step=nps,
                          page_size=page_size, block_q=bq, n_heads=h,
                          rank=rank, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_qb, rows, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name=name,
    )(layer, block_tables, kv_len, q_offset, qp, *([pool] * nps))
    return out.reshape(b, s, h, rank)


@functools.partial(jax.jit, static_argnames=("rank", "scale",
                                             "pages_per_step", "interpret"))
def mla_decode_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                         block_tables: jax.Array, kv_len: jax.Array, *,
                         rank: int, scale: float, pages_per_step: int = 16,
                         interpret: bool = False) -> jax.Array:
    """Decode: q [B, H, R + Dr] (the absorbed query of the one new token,
    whose entry is in the pool already: latent part | rope part) over
    ``pool`` [L, P, page, W] at layer ``layer`` -> [B, H, R] weighted
    latents. block_tables [B, MP]; kv_len [B] counts the new token."""
    out = _mla_call("mla_decode_attention", q[:, None], pool, layer,
                    block_tables, kv_len, kv_len - 1, rank=rank, scale=scale,
                    block_q=1, pages_per_step=pages_per_step,
                    interpret=interpret)
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("rank", "scale", "block_q",
                                             "pages_per_step", "interpret"))
def mla_prefill_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                          block_tables: jax.Array, kv_len: jax.Array,
                          q_offset: jax.Array, *, rank: int, scale: float,
                          block_q: int = 32, pages_per_step: int = 16,
                          interpret: bool = False) -> jax.Array:
    """Prefill: q [B, S, H, R + Dr]; the chunk sits at positions
    q_offset .. q_offset + S of sequences of kv_len tokens (cached
    prefix + chunk) -> [B, S, H, R]."""
    return _mla_call("mla_prefill_attention", q, pool, layer, block_tables,
                     kv_len, q_offset, rank=rank, scale=scale,
                     block_q=block_q, pages_per_step=pages_per_step,
                     interpret=interpret)


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
