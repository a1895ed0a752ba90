"""Llama-family decoder (RMSNorm + RoPE + GQA + SwiGLU) as pure JAX.

One module serves the whole dialect family via ModelConfig knobs:
vanilla Llama, Mistral (sliding_window — masked in the attention
backend), Qwen2 (qkv_bias), and Gemma (norm_offset, gelu_tanh gate,
embed_scale, decoupled head_dim). Parity for each dialect is pinned
against its HF implementation in tests/test_model_parity.py.

TPU-first design notes:
- Per-layer weights are **stacked along a leading layer axis** and the block
  stack runs under ``jax.lax.scan`` — one traced layer body regardless of
  depth, so Llama-70B (80 layers) compiles as fast as the tiny test model.
- Activations are cfg.dtype (bf16 in production) feeding the MXU; norms and
  softmax accumulate f32 (see models/common.py).
- Attention is injected (AttentionFn), so the same forward serves full-context
  parity tests, paged-KV decode, and Pallas kernels.

Functional parity target: the reference repo has no model code (SURVEY.md §0);
this implements the server-side model the reference delegates to an external
Ollama endpoint (reference: traffic_generator/main.py:306). Correctness is
pinned against HuggingFace ``LlamaForCausalLM`` in tests/test_llama_parity.py.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from tpu_inference.config import ModelConfig
from tpu_inference.models.common import (
    AttentionFn,
    apply_rope,
    rms_norm,
    swiglu,
)
from tpu_inference.models.quant import qdot


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random init (normal, 0.02 std) with stacked layer weights."""
    cfg.validate()
    d, f = cfg.d_model, cfg.d_ff
    hd = cfg.head_dim
    keys = jax.random.split(key, 8)

    def norm(k, shape):
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(cfg.dtype)

    L = cfg.n_layers
    params = {
        "embed": norm(keys[0], (cfg.vocab_size, d)),
        "blocks": {
            "attn_norm": jnp.ones((L, d), cfg.dtype),
            "wq": norm(keys[1], (L, d, cfg.n_heads * hd)),
            "wk": norm(keys[2], (L, d, cfg.n_kv_heads * hd)),
            "wv": norm(keys[3], (L, d, cfg.n_kv_heads * hd)),
            "wo": norm(keys[4], (L, cfg.n_heads * hd, d)),
            "ffn_norm": jnp.ones((L, d), cfg.dtype),
            "w_gate": norm(keys[5], (L, d, f)),
            "w_up": norm(keys[6], (L, d, f)),
            "w_down": norm(keys[7], (L, f, d)),
        },
        "final_norm": jnp.ones((d,), cfg.dtype),
    }
    if cfg.qkv_bias:
        params["blocks"]["bq"] = jnp.zeros((L, cfg.n_heads * hd), cfg.dtype)
        params["blocks"]["bk"] = jnp.zeros((L, cfg.n_kv_heads * hd), cfg.dtype)
        params["blocks"]["bv"] = jnp.zeros((L, cfg.n_kv_heads * hd), cfg.dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(jax.random.split(keys[0])[0],
                                 (d, cfg.vocab_size))
    return params


def decoder_block(cfg: ModelConfig, layer_idx: jax.Array, lp: dict,
                  x: jax.Array, positions: jax.Array, kv: Any,
                  attn: AttentionFn):
    """One transformer block. x: [B, S, D]. Public: parallel/pipeline.py
    runs per-stage layer slabs through it, models/ouro.py runs it once a
    (pass, layer) with ``layer_idx`` the KV slot. With
    ``cfg.sandwich_norm`` each branch's output passes a norm of its own
    (``attn_out_norm`` / ``ffn_out_norm``) before the residual add."""
    b, s, d = x.shape
    hd = cfg.head_dim

    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps, cfg.norm_offset)
    q, k, v = qdot(h, lp["wq"]), qdot(h, lp["wk"]), qdot(h, lp["wv"])
    if cfg.qkv_bias:
        q = q + lp["bq"].astype(jnp.float32)
        k = k + lp["bk"].astype(jnp.float32)
        v = v + lp["bv"].astype(jnp.float32)
    q, k, v = (t.astype(x.dtype) for t in (q, k, v))
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)

    attn_out, kv = attn(layer_idx, q, k, v, kv)
    attn_out = attn_out.reshape(b, s, cfg.n_heads * hd)
    a = qdot(attn_out, lp["wo"]).astype(x.dtype)
    if cfg.sandwich_norm:
        a = rms_norm(a, lp["attn_out_norm"], cfg.norm_eps, cfg.norm_offset)
    x = x + a

    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps, cfg.norm_offset)
    m = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"],
               act=cfg.hidden_act)
    if cfg.sandwich_norm:
        m = rms_norm(m, lp["ffn_out_norm"], cfg.norm_eps, cfg.norm_offset)
    return x + m, kv


def embed_tokens(params: dict, cfg: ModelConfig,
                 tokens: jax.Array) -> jax.Array:
    """Token ids -> input embeddings (shared with parallel/pipeline.py)."""
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.embed_scale:
        # Gemma: HF casts the sqrt(d) normalizer to the activation dtype
        # before multiplying; match that rounding for parity.
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype=cfg.dtype)
    return x


def forward_hidden(params: dict, cfg: ModelConfig, tokens: jax.Array,
                   positions: jax.Array, kv: Any,
                   attn: AttentionFn) -> Tuple[jax.Array, Any]:
    """Token ids -> final hidden states. tokens, positions: [B, S]."""
    x = embed_tokens(params, cfg, tokens)

    def body(carry, scanned):
        x, kv = carry
        layer_idx, lp = scanned
        x, kv = decoder_block(cfg, layer_idx, lp, x, positions, kv, attn)
        return (x, kv), None

    layer_ids = jnp.arange(cfg.n_layers)
    (x, kv), _ = jax.lax.scan(body, (x, kv), (layer_ids, params["blocks"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_offset)
    return x, kv


def unembed(params: dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """Hidden states -> f32 logits."""
    if cfg.tie_embeddings:
        return jnp.dot(hidden, params["embed"].T,
                       preferred_element_type=jnp.float32)
    return qdot(hidden, params["lm_head"])


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv: Any,
            attn: AttentionFn) -> Tuple[jax.Array, Any]:
    """Convenience: full-sequence logits (tests / tiny models)."""
    hidden, kv = forward_hidden(params, cfg, tokens, positions, kv, attn)
    return unembed(params, cfg, hidden), kv
