"""Ouro (ByteDance), a looped language model, as pure JAX.

The Llama block of ``models/llama.py`` with two changes, and a loop
around the stack:

- **Passes.** The ``n_layers`` layers run ``loop_steps`` times a token
  with the SAME weights ("ut steps"); the final norm closes every pass
  and its output is the next pass's input; the head reads the last
  pass. An outer ``lax.scan`` over passes around the scan over the
  stacked layers: one traced layer body whatever the depth or the pass
  count, the weights closed over and never copied per pass.
- **A KV slot per (pass, layer).** The keys and values of pass t differ
  from pass t - 1's (their input does), so the injected attention is
  called with slot ``t * n_layers + l`` and the pool's leading dim is
  ``cfg.n_kv_slots``. (The paper's cheaper "reuse the last pass's KV"
  is an approximation and not what this module computes.)
- **Sandwich norms.** Each branch's output passes an RMSNorm of its own
  before the residual add (``cfg.sandwich_norm``, in
  ``llama.decoder_block``): four norms a layer.
- **Exit gate.** ``lambda_t = sigmoid(w . h_t + b)`` on each pass's
  normed output; ``exit_probabilities`` turns the gates into the
  published exit distribution and ``exit_pass`` applies the threshold.
  At the published threshold 1.0 every token leaves at the last pass,
  so the serving graphs run every pass and never call the gate.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_inference.config import ModelConfig
from tpu_inference.models import llama
from tpu_inference.models.common import AttentionFn, rms_norm

unembed = llama.unembed


def param_shapes(cfg: ModelConfig) -> dict:
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {
        "embed": (cfg.vocab_size, d),
        "blocks": {
            "attn_norm": (L, d), "wq": (L, d, hq), "wk": (L, d, hkv),
            "wv": (L, d, hkv), "wo": (L, hq, d), "attn_out_norm": (L, d),
            "ffn_norm": (L, d), "w_gate": (L, d, f), "w_up": (L, d, f),
            "w_down": (L, f, d), "ffn_out_norm": (L, d),
        },
        "final_norm": (d,),
        "exit_gate_w": (d,), "exit_gate_b": (),
        "lm_head": (d, cfg.vocab_size),
    }


def param_count(cfg: ModelConfig, active: bool = False) -> int:
    """Parameters off the leaf shapes: stored ONCE (resident bytes), or
    with ``active`` what a token position multiplies through and a
    decode step reads, the looped layers ``loop_steps`` times."""
    shapes = param_shapes(cfg)
    layers = sum(math.prod(s) for s in shapes["blocks"].values())
    rest = sum(math.prod(s) for s in shapes.values() if isinstance(s, tuple))
    return rest + layers * (cfg.loop_steps if active else 1)


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random init (normal, 0.02 std; norm gains 1) with stacked layers."""
    cfg.validate()
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))

    def draw(k, path, shape):
        if "norm" in path[-1].key:
            return jnp.ones(shape, cfg.dtype)
        return (0.02 * jax.random.normal(k, shape, jnp.float32)
                ).astype(cfg.dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [draw(k, path, shape)
                  for k, (path, shape) in zip(keys, leaves)])


def forward_passes(params: dict, cfg: ModelConfig, tokens: jax.Array,
                   positions: jax.Array, kv: Any, attn: AttentionFn,
                   collect: bool = False
                   ) -> Tuple[jax.Array, Any, Optional[jax.Array]]:
    """Token ids -> (the last pass's normed hidden states, kv, every
    pass's [loop_steps, B, S, D] when ``collect``)."""
    x = llama.embed_tokens(params, cfg, tokens)
    layer_ids = jnp.arange(cfg.n_layers)

    def one_pass(carry, t):
        def layer(carry, scanned):
            x, kv = carry
            l, lp = scanned
            return llama.decoder_block(cfg, t * cfg.n_layers + l, lp, x,
                                       positions, kv, attn), None

        with jax.named_scope("ut_pass"):
            carry, _ = jax.lax.scan(layer, carry,
                                    (layer_ids, params["blocks"]))
            x, kv = carry
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return (x, kv), (x if collect else None)

    (x, kv), per_pass = jax.lax.scan(one_pass, (x, kv),
                                     jnp.arange(cfg.loop_steps))
    return x, kv, per_pass


def forward_hidden(params: dict, cfg: ModelConfig, tokens: jax.Array,
                   positions: jax.Array, kv: Any,
                   attn: AttentionFn) -> Tuple[jax.Array, Any]:
    """Token ids -> final hidden states. tokens, positions: [B, S]."""
    x, kv, _ = forward_passes(params, cfg, tokens, positions, kv, attn)
    return x, kv


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv: Any,
            attn: AttentionFn) -> Tuple[jax.Array, Any]:
    """Convenience: full-sequence logits (tests / tiny models)."""
    hidden, kv = forward_hidden(params, cfg, tokens, positions, kv, attn)
    return unembed(params, cfg, hidden), kv


def exit_probabilities(params: dict, cfg: ModelConfig,
                       per_pass: jax.Array) -> jax.Array:
    """Each pass's normed hidden states [T, ..., D] -> the probability
    [T, ...] (f32) that a token leaves at that pass: p_t = lambda_t *
    prod_{s<t} (1 - lambda_s) for t < T - 1, the last pass takes the
    remainder."""
    with jax.named_scope("exit_gate"):
        lam = jax.nn.sigmoid(
            jnp.einsum("t...d,d->t...", per_pass.astype(jnp.float32),
                       params["exit_gate_w"].astype(jnp.float32),
                       precision="highest")
            + params["exit_gate_b"].astype(jnp.float32))
        stay = jnp.cumprod(1.0 - lam, axis=0)
        before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
        return jnp.concatenate([(lam * before)[:-1], before[-1:]])


def exit_pass(probs: jax.Array, threshold: float) -> jax.Array:
    """The first pass whose cumulative exit probability reaches
    ``threshold``; the last pass where none does, and for every token at
    a threshold of 1.0 (no early exit: a sum that rounds up to 1.0 a
    pass early does not count)."""
    if threshold >= 1.0:
        return jnp.full(probs.shape[1:], probs.shape[0] - 1, jnp.int32)
    reached = jnp.cumsum(probs, axis=0) >= threshold
    reached = reached.at[-1].set(True)
    return jnp.argmax(reached, axis=0)
