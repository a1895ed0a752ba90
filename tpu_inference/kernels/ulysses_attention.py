"""Ulysses-style sequence parallelism: all-to-all head-scatter attention.

The second of the two canonical sequence/context-parallel schemes (the
other is ring attention, kernels/ring_attention.py — the reference has
neither; it clamps prompts to 1024 tokens client-side, SURVEY.md §5).
Instead of rotating K/V shards around a ring, two ``all_to_all``
collectives re-shard the activations between layouts:

    [B, S/n, H,   D]   (sequence-sharded — the layer's layout)
        -- all_to_all(split=heads, concat=seq) -->
    [B, S,   H/n, D]   (head-sharded: every device sees the FULL
                        sequence for its head group)
        -- plain causal attention, no cross-device bookkeeping --
        -- all_to_all(split=seq, concat=heads) -->
    [B, S/n, H,   D]

Trade-offs vs the ring (both kept; EngineConfig.sp_attn picks):

- **Latency/hops**: Ulysses is 2 collective phases regardless of axis
  size; the ring is n-1 sequential ppermute steps. On short-to-medium
  prompts the ring's per-step latency dominates and Ulysses wins.
- **Load balance**: causal masking makes ring step cost skewed (early
  ranks finish their useful work sooner); Ulysses gives every device
  the same full-sequence attention for H/n heads.
- **Bytes on the wire**: Ulysses moves q+k+v+out once each
  (~4·S/n·H·D per device); the ring moves only k+v, (n-1) times
  (~2·(n-1)·S/n·Hkv·D). With strong GQA (Hkv << Hq) the ring can move
  fewer bytes for large n.
- **Memory**: Ulysses materializes full-sequence scores per local head
  group (O(S²·H/n)); the ring stays O((S/n)²) — for extreme contexts
  prefer the ring.
- **Divisibility**: Ulysses needs both Hq and Hkv divisible by the sp
  axis size (after tp head sharding); the ring only needs S divisible.

Design follows the DeepSpeed-Ulysses pattern (PAPERS.md) with XLA
``all_to_all`` (lowered to ICI all-to-all on TPU) instead of NCCL.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh


def ulysses_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                            axis_name: str = "sp",
                            sliding_window: int = 0) -> jax.Array:
    """Per-shard body; call under shard_map with the sequence dim sharded
    over ``axis_name``. q: [B, S_loc, Hq, D]; k/v: [B, S_loc, Hkv, D].
    Requires the local Hq and Hkv to be divisible by the axis size.
    ``sliding_window`` > 0 applies the SWA mask (the head-sharded
    attention sees the full sequence, so the window term needs no
    cross-device bookkeeping at all). Returns [B, S_loc, Hq, D] in
    q.dtype.

    The head-sharded attention IS the repo's correctness-reference
    attention (models.common.dense_causal_attention — GQA expansion,
    f32 softmax, causal mask, output back in q.dtype), so the math can
    never drift from the oracle; activations cross the wire in their
    raw dtype (the upcast happens inside the attention, after the
    collective)."""
    from tpu_inference.models.common import dense_causal_attention

    n = jax.lax.axis_size(axis_name)
    hq, hkv = q.shape[2], k.shape[2]
    if n == 1:
        return dense_causal_attention(q, k, v, sliding_window=sliding_window)
    assert hq % n == 0 and hkv % n == 0, (
        f"ulysses needs head counts divisible by the sp axis: "
        f"Hq={hq}, Hkv={hkv}, sp={n}")
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            tiled=True)
    # seq-sharded -> head-sharded: full sequence, H/n local heads.
    qg = a2a(q, split_axis=2, concat_axis=1)
    kg = a2a(k, split_axis=2, concat_axis=1)
    vg = a2a(v, split_axis=2, concat_axis=1)
    out = dense_causal_attention(qg, kg, vg,       # returns q.dtype
                                 sliding_window=sliding_window)
    # head-sharded -> seq-sharded (raw dtype on the wire).
    return a2a(out, split_axis=1, concat_axis=2)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis_name", "sliding_window"))
def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      mesh: Mesh, axis_name: str = "sp",
                      sliding_window: int = 0) -> jax.Array:
    """Full-sequence causal attention, sequence-sharded over
    ``axis_name`` (same call surface as kernels.ring_attention)."""
    from tpu_inference.kernels.ring_attention import seq_sharded_call

    return seq_sharded_call(ulysses_attention_local, q, k, v, mesh,
                            axis_name, sliding_window)
