"""Ring attention: causal attention with the sequence sharded over a mesh
axis (sequence/context parallelism for long-context prefill).

Each device holds one contiguous sequence shard of Q, K, V. K/V shards
rotate around the ring (``lax.ppermute`` — XLA lowers it to ICI
neighbor transfers), while every device folds each visiting K/V chunk
into flash-style online-softmax state for its local Q. After
``axis_size`` steps every Q row has attended to every K/V row at or
before it; peak memory per chip stays O(S/n), enabling contexts n× the
single-chip limit.

This is the TPU-native replacement for the reference's (absent)
long-context support: SURVEY.md §5 notes the reference clamps prompts to
1024 tokens client-side (traffic_generator/main.py:92-93,163-165) and
delegates all attention to its external server. Design follows the
ring-attention / blockwise-parallel-transformer pattern (PAPERS.md) with
XLA collectives instead of hand-rolled RDMA.

Communication note: ppermute sends ride ICI when the ``sp`` axis maps to
physically adjacent chips (parallel/mesh.py lays tp innermost, then sp);
compute per step is O((S/n)^2) while each transfer is O(S/n), so XLA can
overlap the next chunk's transfer with the current chunk's attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


NEG_INF = -1e30


def _block_attend(q, k, v, q_pos, k_pos, scale, n_rep, sliding_window=0):
    """One (local Q) x (visiting KV chunk) block: masked scores + partial
    softmax stats. q: [B,Sq,Hq,D] f32; k/v: [B,Sk,Hkv,D] raw dtype (GQA
    expansion + f32 upcast happen here, per block, so the ring rotates the
    small raw shards). ``sliding_window`` > 0 additionally masks keys more
    than window-1 positions behind the query (matches
    models.common.dense_causal_attention). Returns (m [B,H,Sq],
    l [B,H,Sq], o [B,Sq,H,D])."""
    if n_rep != 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = k_pos[None, None, None, :] <= q_pos[None, None, :, None]
    if sliding_window:
        mask &= (k_pos[None, None, None, :]
                 > q_pos[None, None, :, None] - sliding_window)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                              # [B, H, Sq]
    # exp(NEG_INF - NEG_INF) = 1 on fully-masked rows; zero them via mask.
    pr = jnp.exp(s - m[..., None]) * mask
    l = jnp.sum(pr, axis=-1)                             # [B, H, Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v)
    return m, l, o


def ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str = "sp",
                         sliding_window: int = 0) -> jax.Array:
    """Per-shard body; call under shard_map with the sequence dim sharded
    over ``axis_name``. q: [B, S_loc, Hq, D]; k/v: [B, S_loc, Hkv, D]
    (GQA expanded internally). ``sliding_window`` > 0 applies the SWA
    mask (each query sees itself + the window-1 tokens before it); fully
    behind-window chunks skip their einsums just like fully-future ones.
    Returns [B, S_loc, Hq, D] in q.dtype."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, hq, d = q.shape
    hkv = k.shape[2]
    n_rep = hq // hkv
    scale = 1.0 / (d ** 0.5)

    qf = q.astype(jnp.float32)
    local_pos = jnp.arange(s_loc, dtype=jnp.int32)
    q_pos = idx * s_loc + local_pos

    m = jnp.full((b, hq, s_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((b, hq, s_loc), jnp.float32)
    acc = jnp.zeros((b, s_loc, hq, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    k_cur, v_cur = k, v          # raw dtype, Hkv heads: minimal ring bytes
    for step in range(n):
        src = (idx - step) % n          # chunk id this device now holds
        k_pos = src * s_loc + local_pos

        def attend(ops):
            kc, vc = ops
            return _block_attend(qf, kc, vc, q_pos, k_pos, scale, n_rep,
                                 sliding_window)

        def skip(ops):
            # Mark the constants as device-varying so both cond branches
            # agree under shard_map's varying-axis typing.
            vals = (jnp.full((b, hq, s_loc), NEG_INF, jnp.float32),
                    jnp.zeros((b, hq, s_loc), jnp.float32),
                    jnp.zeros((b, s_loc, hq, d), jnp.float32))
            return jax.lax.pcast(vals, (axis_name,), to="varying")

        # Chunks entirely in the causal future contribute nothing; skip
        # their einsums (the ring still rotates them — wall-clock per step
        # is set by the busiest device, but ~half the fleet-wide FLOPs and
        # energy go away). A zigzag shard layout would balance the load
        # too; that changes the caller-visible sharding, so not done here.
        # Under SWA, chunks entirely behind every local query's window are
        # equally dead: max k_pos <= min(q_pos) - window.
        skippable = src * s_loc > q_pos[-1]
        if sliding_window:
            skippable |= (src * s_loc + s_loc - 1
                          <= q_pos[0] - sliding_window)
        m_blk, l_blk, o_blk = jax.lax.cond(skippable, skip, attend,
                                           (k_cur, v_cur))
        m_new = jnp.maximum(m, m_blk)
        a_prev = jnp.exp(m - m_new)
        a_blk = jnp.exp(m_blk - m_new)
        l = l * a_prev + l_blk * a_blk
        acc = (acc * a_prev.transpose(0, 2, 1)[..., None]
               + o_blk * a_blk.transpose(0, 2, 1)[..., None])
        m = m_new
        if step != n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    denom = jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-20)
    return (acc / denom).astype(q.dtype)


def seq_sharded_call(body, q, k, v, mesh: Mesh, axis_name: str,
                     sliding_window: int = 0):
    """Shared wrapper for sequence-parallel attention kernels: reshard
    q/k/v so the sequence dim shards over ``axis_name`` (batch/head dims
    replicated), run the per-shard ``body`` under shard_map, return with
    the same sequence sharding. Used by ring and ulysses."""
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(functools.partial(body, axis_name=axis_name,
                                         sliding_window=sliding_window),
                       mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    sh = NamedSharding(mesh, spec)
    return fn(jax.device_put(q, sh), jax.device_put(k, sh),
              jax.device_put(v, sh))


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis_name", "sliding_window"))
def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Mesh, axis_name: str = "sp",
                   sliding_window: int = 0) -> jax.Array:
    """Full-sequence causal attention, sequence-sharded over ``axis_name``.

    q: [B, S, Hq, D]; k/v: [B, S, Hkv, D] with S divisible by the axis
    size. ``sliding_window`` > 0 applies the SWA mask (Mistral-style).
    """
    return seq_sharded_call(ring_attention_local, q, k, v, mesh, axis_name,
                            sliding_window)
