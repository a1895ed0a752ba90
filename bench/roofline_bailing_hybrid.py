"""Operations and bytes of the delta-rule kernels, the latent attention,
the grouped experts and a decode step of a ``bailing_hybrid``
configuration (Ling-3.0-flash), from the configuration FILE's published
sizes and counts the CLIENT (or, for the experts reached, the program's
counters) observed. The counts are of the WORK, as the equations state
it, so they read the same whatever implements it:

* the one-token update of a delta-rule layer must read a lane's matrix
  state (heads x d x d float32) once and write it once, beside the
  token's q, k, v, g (heads x d each), beta (heads) and its output;
* a chunk of the delta rule in the WY / UT form over blocks of BLOCK = 64
  tokens: per block and head the products ``A`` and ``B`` (C x C over d),
  ``(beta K Gam) S``, ``(Q Gam) S`` and ``K^T U`` (C x d x d), ``T rhs``
  and ``B U`` (C x C x d) and one C x C x C product for the triangular
  inverse; its bytes are the token's q, k, v, g and o (float32) and beta,
  and a lane's state in and out once a call;
* latent attention, the experts and the other weights as
  ``roofline_mla_moe.py`` counts them, under this configuration's keys.
"""

from __future__ import annotations

import roofline_mla_moe as M

BYTES = 2      # bfloat16 weights and cache
F32 = 4
BLOCK = 64     # tokens a block of the chunked form


def kinds(cfg: dict) -> tuple:
    held = cfg["deployment"]["published_layers"][:cfg["num_hidden_layers"]]
    return tuple("full" if (p + 1) % cfg["layer_group_size"] == 0 else "kda"
                 for p in held)


def layers_of(cfg: dict, kind: str) -> int:
    return kinds(cfg).count(kind)


def kda_heads(cfg: dict) -> int:
    return cfg["num_kv_heads_for_linear_attn"] or cfg["num_attention_heads"]


def kda_width(cfg: dict) -> int:
    return kda_heads(cfg) * cfg["head_dim"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


# ------------------------------------------------------------ the delta rule
def state_bytes_per_seq_layer(cfg: dict) -> int:
    """One layer's matrix state of one sequence."""
    return kda_heads(cfg) * cfg["head_dim"] ** 2 * F32


def conv_tail_bytes_per_seq_layer(cfg: dict) -> int:
    return (cfg["short_conv_kernel_size"] - 1) * 3 * kda_width(cfg) * BYTES


def token_operand_bytes(cfg: dict) -> int:
    """q, k, v, g in and o out, float32, and beta: one token, one layer."""
    return (5 * kda_width(cfg) + kda_heads(cfg)) * F32


def step_bytes(lanes: float, cfg: dict) -> float:
    """ONE layer's one-token update of ``lanes`` lanes."""
    return lanes * (2 * state_bytes_per_seq_layer(cfg)
                    + token_operand_bytes(cfg))


def step_flops(lanes: float, cfg: dict) -> float:
    """Decay, k^T S, the rank-one update and S^T q: 7 a state element."""
    return lanes * kda_heads(cfg) * cfg["head_dim"] ** 2 * 7.0


def chunk_flops(tokens: float, cfg: dict) -> float:
    """ONE layer's chunked delta rule over ``tokens`` tokens."""
    c, d = BLOCK, cfg["head_dim"]
    per_token = 8 * c * d + 6 * d * d + 2 * c * c
    return tokens * kda_heads(cfg) * float(per_token)


def chunk_bytes(tokens: float, lanes: float, cfg: dict) -> float:
    return (tokens * token_operand_bytes(cfg)
            + lanes * 2 * state_bytes_per_seq_layer(cfg))


def decode_state_bytes(lanes: float, cfg: dict) -> float:
    """Every delta-rule layer's state in and out, and the convolution's
    tail, once a lane a step."""
    return lanes * layers_of(cfg, "kda") * 2 * (
        state_bytes_per_seq_layer(cfg) + conv_tail_bytes_per_seq_layer(cfg))


# ------------------------------- latent attention, one expert: as Kimi's
# (the same keys of the published config: roofline_mla_moe.py counts a
# latent entry as kv_lora_rank + qk_rope_head_dim values read once for all
# heads, absorbed scores and sums, and an expert as gate, up and down).
latent_dim = M.latent_dim
mla_attn_bytes = M.mla_attn_bytes
mla_attn_flops = M.mla_attn_flops
expert_params = M.expert_params


def uniform_local_pairs_per_token(cfg: dict) -> float:
    """Pairs a token sends here under uniform routing: the held group is
    among the topk_group chosen with probability topk_group / n_group and
    then takes its even share of the k."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["published"]["num_experts"]


def uniform_group_reach_share(cfg: dict) -> float:
    return 100.0 * cfg["topk_group"] / cfg["n_group"]


def moe_read_bytes(distinct: float, cfg: dict) -> float:
    """Weights ONE expert layer's routed part must read in a call that
    reaches ``distinct`` held experts: each once."""
    return distinct * expert_params(cfg) * BYTES


def moe_flops(pairs: float, cfg: dict) -> float:
    return 2.0 * pairs * expert_params(cfg)


# ---------------------------------------------------------------- the step
def mixer_params(cfg: dict, kind: str) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if kind == "kda":
        w, kh = kda_width(cfg), kda_heads(cfg)
        return (d * 3 * w + d * w + w * d + 2 * d * kh
                + 3 * w * cfg["short_conv_kernel_size"] + kh + w
                + cfg["head_dim"])
    return (d * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
            + d * latent_dim(cfg)
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + d * h + h * cfg["v_head_dim"] * d)


def non_expert_weight_bytes(cfg: dict) -> float:
    """What every decode step reads whatever the routing: every layer's
    mixer, the dense layer's SwiGLU, router and shared expert of the
    expert layers, the output head."""
    d = cfg["hidden_size"]
    n = (sum(layers_of(cfg, k) * mixer_params(cfg, k)
             for k in ("kda", "full"))
         + cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
         + expert_layers(cfg) * (d * cfg["published"]["num_experts"]
                                 + cfg["num_shared_experts"]
                                 * 3 * d
                                 * cfg["moe_shared_expert_intermediate_size"])
         + d * cfg["vocab_size"])
    return float(n * BYTES)


def decode_step_bytes(lanes: float, distinct: float, ctx_tokens: float,
                      cfg: dict) -> float:
    """HBM bytes of one decode step of ``lanes`` sequences whose visible
    contexts sum to ``ctx_tokens`` and whose expert layers each reach
    ``distinct`` held experts: weights used, the states in and out, the
    visible latents."""
    return (non_expert_weight_bytes(cfg)
            + expert_layers(cfg) * moe_read_bytes(distinct, cfg)
            + decode_state_bytes(lanes, cfg)
            + layers_of(cfg, "full") * mla_attn_bytes(ctx_tokens, cfg))
