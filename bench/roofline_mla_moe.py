"""Operations and bytes of the latent-attention and grouped-expert kernels
and of a decode step of a DeepSeek-V3 / Kimi-K2 configuration, from the
configuration FILE's published sizes and token counts the CLIENT observed.
Nothing here asks the program what it did: the expert layer's bytes use
the distinct held experts EXPECTED at the observed token count when each
token chooses a given held expert with probability ``hit_probability``:
uniform routing's k / all, unless the configuration file states, under
``assumed.served_routing``, how many distinct held experts a decode step
of its SERVED weights reaches at a stated batch (a chip measurement,
written into the file as an input); nothing is read from the program
while a roofline is computed.

Counted as a perfect implementation of the absorbed form would pay them:
a latent entry is kv_lora_rank + qk_rope_head_dim values (576, not the 640
the pool stores), scores contract over those, the weighted sum over
kv_lora_rank; weights are bfloat16 (2 bytes).
"""

from __future__ import annotations

BYTES = 2      # bfloat16 weights and cache


def latent_dim(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def held_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"]


def all_experts(cfg: dict) -> int:
    return cfg["published"]["n_routed_experts"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def mla_attn_bytes(ctx_tokens: float, cfg: dict) -> float:
    """HBM bytes ONE layer's attention must read for queries whose
    visible contexts sum to ``ctx_tokens``: each latent entry once (every
    head shares it)."""
    return ctx_tokens * latent_dim(cfg) * BYTES


def mla_attn_flops(pairs: float, cfg: dict) -> float:
    """ONE layer's absorbed attention for that many (query, key) pairs:
    per head a score over the latent entry and a sum over the latent
    rank, 2 flops a multiply-add."""
    return (2.0 * cfg["num_attention_heads"]
            * (latent_dim(cfg) + cfg["kv_lora_rank"]) * pairs)


def prefill_pairs(new_tokens: int, cached_tokens: int) -> float:
    """Causal (query, key) pairs of ``new_tokens`` queries behind
    ``cached_tokens`` in the cache."""
    return new_tokens * cached_tokens + new_tokens * (new_tokens + 1) / 2.0


def expert_params(cfg: dict) -> int:
    """Parameters of one routed expert (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def hit_probability(cfg: dict) -> float:
    """The chance that a token chooses a given held expert. Uniform
    routing: k / all. Where the configuration states what its served
    weights do (``distinct_held_experts`` of a decode step at
    ``decode_batch`` sequences), the probability that gives that count
    when tokens choose independently."""
    served = cfg.get("assumed", {}).get("served_routing")
    if served is None:
        return cfg["num_experts_per_tok"] / all_experts(cfg)
    reached = served["distinct_held_experts"] / held_experts(cfg)
    return 1.0 - (1.0 - reached) ** (1.0 / served["decode_batch"])


def local_pairs_per_token(cfg: dict) -> float:
    """(token, expert) pairs a token sends to the experts held here."""
    return held_experts(cfg) * hit_probability(cfg)


def expected_distinct_experts(tokens: float, cfg: dict) -> float:
    """Held experts that get at least one of ``tokens`` tokens."""
    return held_experts(cfg) * (1.0 - (1.0 - hit_probability(cfg)) ** tokens)


def expected_local_pairs(tokens: float, cfg: dict) -> float:
    return tokens * local_pairs_per_token(cfg)


def moe_layer_bytes(tokens: float, cfg: dict) -> float:
    """Weights ONE expert layer's routed part must read for a call of
    ``tokens`` tokens: every distinct expert with a token, once (gate and
    up in the first kernel, down in the second)."""
    return expected_distinct_experts(tokens, cfg) * expert_params(cfg) * BYTES


def moe_layer_flops(tokens: float, cfg: dict) -> float:
    return 2.0 * expected_local_pairs(tokens, cfg) * expert_params(cfg)


def attn_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d * cfg["q_lora_rank"]
            + cfg["q_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                        + cfg["qk_rope_head_dim"])
            + d * latent_dim(cfg)
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def non_expert_weight_bytes(cfg: dict) -> float:
    """Bytes of the weights every decode step reads whatever the routing:
    attention of every layer, the dense layers' SwiGLU, router and shared
    expert of the expert layers, the output head."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    n = (cfg["num_hidden_layers"] * attn_params(cfg)
         + dense * 3 * d * cfg["intermediate_size"]
         + expert_layers(cfg) * (d * all_experts(cfg)
                                 + cfg["n_shared_experts"]
                                 * expert_params(cfg))
         + d * cfg["vocab_size"])
    return float(n * BYTES)


def decode_step_bytes(batch: float, ctx_tokens: float, cfg: dict) -> float:
    """HBM bytes of one decode step of ``batch`` sequences whose visible
    contexts sum to ``ctx_tokens``."""
    return (non_expert_weight_bytes(cfg)
            + expert_layers(cfg) * moe_layer_bytes(batch, cfg)
            + cfg["num_hidden_layers"] * mla_attn_bytes(ctx_tokens, cfg))
