"""Shared transformer building blocks (TPU-idiomatic JAX).

Conventions:
- Activations flow in ``cfg.dtype`` (bfloat16 in production) so matmuls hit
  the MXU at full rate; normalization statistics and attention softmax
  accumulate in float32.
- All functions are pure and shape-static, safe under ``jax.jit``.
- Attention is *injected*: model forward passes take an ``AttentionFn``
  ``attn(layer_idx, q, k, v, kv) -> (out, kv)`` with q [B,S,Hq,D] and
  k/v [B,S,Hkv,D]; the engine's paged-cache attention, the dense causal
  test path, and the Pallas kernels all fit this signature. Latent
  attention (models/deepseek_v3.py) uses the same call with a second
  shape: q [B,S,H,R+Dr] is the ABSORBED query (latent part | rope part),
  k [B,S,R+Dr] the one cache entry per token, v None; the result is the
  per-head weighted sum of latents [B,S,H,R].
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from tpu_inference.config import YarnScaling
from tpu_inference.models.quant import qdot

# attn(layer_idx, q, k, v, kv_state) -> (attn_out, kv_state)
AttentionFn = Callable[[int, jax.Array, jax.Array, jax.Array, Any],
                       Tuple[jax.Array, Any]]

# Gated-FFN activations; a KeyError here fails loudly on an unknown or
# unmapped hidden_act instead of silently running the wrong function.
_GATE_ACTS = {
    "silu": jax.nn.silu,
    "gelu_tanh": partial(jax.nn.gelu, approximate=True),
}


def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             offset: float = 0.0) -> jax.Array:
    """RMSNorm with float32 statistics, output in x.dtype.

    ``offset`` supports Gemma's stored-as-delta weights (y = normed *
    (1 + w)); adding in float32 avoids the precision loss of
    pre-materializing 1 + w in bf16.
    """
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (weight.astype(jnp.float32) + offset)).astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    """LayerNorm (GPT-2 family) with float32 statistics."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mean) * jax.lax.rsqrt(var + eps)
    out = normed * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_frequencies(head_dim: int, theta: float,
                     scaling=None) -> jax.Array:
    """Inverse frequencies for rotary embeddings, [head_dim // 2] f32.

    ``scaling`` (config.RopeScaling) applies the Llama-3.1 "llama3"
    per-channel rescale, matching HF's _compute_llama3_parameters:
    channels with wavelength above original_max_len/low_freq_factor run
    ``factor``× slower, those below original_max_len/high_freq_factor are
    untouched, and the band between interpolates by how far the original
    context fits into the wavelength.
    """
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta ** exponent)
    if isinstance(scaling, YarnScaling):
        return _yarn_frequencies(inv_freq, head_dim, theta, scaling)
    if scaling is not None:
        wavelen = 2.0 * jnp.pi / inv_freq
        smooth = ((scaling.original_max_len / wavelen
                   - scaling.low_freq_factor)
                  / (scaling.high_freq_factor - scaling.low_freq_factor))
        interp = ((1.0 - smooth) * inv_freq / scaling.factor
                  + smooth * inv_freq)
        inv_freq = jnp.where(
            wavelen > scaling.original_max_len / scaling.low_freq_factor,
            inv_freq / scaling.factor,
            jnp.where(
                wavelen < scaling.original_max_len / scaling.high_freq_factor,
                inv_freq, interp))
    return inv_freq


def _yarn_frequencies(inv_freq: jax.Array, dim: int, theta: float,
                      sc: YarnScaling) -> jax.Array:
    """YaRN (DeepSeek-V3 ``_compute_yarn_parameters``): channel i keeps
    its frequency below the ramp, runs ``factor`` x slower above it.
    The ramp spans the channels that turn ``beta_fast`` .. ``beta_slow``
    times over the original context (floor / ceil, clamped to the
    table); an empty ramp is widened by 0.001 as published."""
    def turns_to_dim(turns):
        return (dim * math.log(sc.original_max_len / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_to_dim(sc.beta_fast)), 0)
    high = min(math.ceil(turns_to_dim(sc.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv_freq / sc.factor * ramp + inv_freq * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term, 0.1 * m * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None, rotary_dim: int = 0) -> jax.Array:
    """Rotary position embedding.

    x: [B, S, H, D]; positions: [B, S] int32. Uses the half-split pairing
    (first half with second half), matching HF Llama's rotate_half.
    ``scaling`` forwards to rope_frequencies (Llama-3.1 rescale).
    ``rotary_dim`` < D turns the first ``rotary_dim`` dims of each head
    only (paired within themselves); the rest pass through.
    """
    if rotary_dim and rotary_dim < x.shape[-1]:
        turned = apply_rope(x[..., :rotary_dim], positions, theta, scaling)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    half = x.shape[-1] // 2
    inv_freq = rope_frequencies(x.shape[-1], theta, scaling)  # [half]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]                      # [B,S,1,half]
    sin = jnp.sin(angles)[:, :, None, :]
    if isinstance(scaling, YarnScaling):
        m = scaling.attention_factor or (
            yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """Expand KV heads for GQA: [B, S, Hkv, D] -> [B, S, Hkv*n_rep, D]."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def dense_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           q_offset: jax.Array | int = 0,
                           kv_len: jax.Array | None = None,
                           sliding_window: int = 0) -> jax.Array:
    """Dense causal attention; the correctness reference for all kernels.

    q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D] (GQA expanded internally).
    ``q_offset`` (scalar or [B]) is the absolute position of q's first token
    within the KV sequence (for chunked prefill / decode against a cache).
    ``kv_len`` (scalar or [B]) masks out cache slots beyond the valid length.
    ``sliding_window`` > 0 additionally masks keys more than window-1
    positions behind the query (Mistral-style SWA: each token attends to
    itself and the window-1 tokens before it). Softmax in float32.
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    # [B, H, Sq, Skv]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    offs = jnp.broadcast_to(jnp.asarray(q_offset), (b,))        # [B]
    q_pos = offs[:, None] + jnp.arange(sq)[None, :]             # [B, Sq]
    k_pos = jnp.arange(skv)                                     # [Skv]
    mask = k_pos[None, None, :] <= q_pos[:, :, None]            # [B, Sq, Skv]
    if sliding_window:
        mask = jnp.logical_and(
            mask, k_pos[None, None, :] > q_pos[:, :, None] - sliding_window)
    if kv_len is not None:
        lens = jnp.broadcast_to(jnp.asarray(kv_len), (b,))
        mask = jnp.logical_and(mask, k_pos[None, None, :] < lens[:, None, None])
    scores = jnp.where(mask[:, None, :, :], scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def make_dense_attn(sliding_window: int = 0) -> AttentionFn:
    """AttentionFn for cache-free full-sequence forward (tests, parity).
    ``sliding_window`` mirrors ModelConfig.sliding_window for SWA models
    (Mistral)."""

    def attn(layer_idx: int, q, k, v, kv):
        del layer_idx
        return dense_causal_attention(q, k, v,
                                      sliding_window=sliding_window), kv

    return attn


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, act: str = "silu") -> jax.Array:
    """Gated FFN: down( act(x @ gate) * (x @ up) ).

    ``act``: "silu" (SwiGLU — Llama/Qwen/Mistral) or "gelu_tanh" (GeGLU
    with the tanh approximation — Gemma). Weights may be int8/int4
    ``QuantizedArray``s (models/quant.py) — ``qdot`` handles both
    representations.
    """
    fn = _GATE_ACTS[act]
    gate = fn(qdot(x, w_gate))
    up = qdot(x, w_up)
    return qdot((gate * up).astype(x.dtype), w_down).astype(x.dtype)


def linear(x: jax.Array, w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    out = qdot(x, w)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)
