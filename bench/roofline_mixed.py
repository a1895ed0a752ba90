"""Operations and bytes of the attention and grouped-expert kernels and of
a decode step of a configuration whose layers differ in KIND (Laguna:
``layer_types`` full / sliding with ``num_attention_heads_per_layer``
query heads, a dense layer then routed experts beside a shared one, this
chip's share of the experts), from the configuration FILE's published
sizes and token counts the CLIENT observed. Nothing here asks the program
what it did: kinds, head counts, window and expert counts are the file's;
the expert layer's bytes use the distinct held experts EXPECTED at the
observed token count (``hit_probability``: uniform routing's k / all,
unless ``assumed.served_routing`` states what the served weights reach, a
chip measurement written into the file as an input).

Counted as a perfect implementation would pay them: a visible token's K
and V once a layer (a sliding layer sees min(context, sliding_window)),
every matrix a step reads once, bfloat16 (2 bytes).
"""

from __future__ import annotations

BYTES = 2      # bfloat16 weights and cache
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def kinds(cfg: dict) -> list:
    """The held layers' kinds ("full" / "window"), in order."""
    return [KINDS[k]
            for k in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def layers_of(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in kinds(cfg))


def heads_of(cfg: dict, kind: str) -> int:
    """Query heads of a layer of ``kind`` (one count a kind)."""
    found = {h for k, h in zip(kinds(cfg),
                               cfg["num_attention_heads_per_layer"])
             if k == kind}
    assert len(found) == 1, f"{kind}: head counts {sorted(found)}"
    return found.pop()


def head_dim(cfg: dict) -> int:
    return cfg["head_dim"]


def window(cfg: dict, kind: str) -> int:
    return int(cfg["sliding_window"]) if kind == "window" else 0


def kv_bytes_per_token_layer(cfg: dict) -> int:
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BYTES


def kv_bytes_per_token(cfg: dict, kind: str) -> int:
    """K and V of one token over a kind's layers: what its pool holds."""
    return layers_of(cfg, kind) * kv_bytes_per_token_layer(cfg)


def visible(ctx_len: int, cfg: dict, kind: str) -> int:
    """Tokens of a context that one decode query of ``kind`` attends to."""
    w = window(cfg, kind)
    return min(ctx_len, w) if w else ctx_len


def decode_attn_bytes(ctx_tokens: float, cfg: dict) -> float:
    """HBM bytes ONE layer's decode attention must read for queries whose
    visible contexts sum to ``ctx_tokens``."""
    return ctx_tokens * kv_bytes_per_token_layer(cfg)


def attn_flops(pairs: float, cfg: dict, kind: str) -> float:
    """ONE layer's QK^T and PV for that many (query, key) pairs."""
    return 4.0 * heads_of(cfg, kind) * head_dim(cfg) * pairs


def prefill_pairs(new_tokens: int, cached_tokens: int, cfg: dict,
                  kind: str) -> int:
    """(query, key) pairs of ``new_tokens`` queries behind
    ``cached_tokens`` in the cache, by the kind's window."""
    w = window(cfg, kind)
    if not w:
        return (new_tokens * cached_tokens
                + new_tokens * (new_tokens + 1) // 2)
    return sum(min(cached_tokens + i + 1, w) for i in range(new_tokens))


def ledger_prefill_pairs(rec: dict, cfg: dict, kind: str) -> float:
    """Pairs of one prefill record of the engine's step ledger
    (``kv_read_tokens`` = chunk * offset + chunk * (chunk + 1) / 2, no
    window), counted again by the kind's window for a one-prompt chunk;
    a batched record's prompts start at offset 0 each and are counted at
    their mean length."""
    c, slots = rec["chunk_tokens"], max(1, rec["slots"])
    if not c:
        return 0.0
    if not window(cfg, kind):
        return float(rec["kv_read_tokens"])
    if slots == 1:
        offset = (rec["kv_read_tokens"] - c * (c + 1) // 2) // c
        return float(prefill_pairs(c, offset, cfg, kind))
    return float(slots * prefill_pairs(c // slots, 0, cfg, kind))


# ------------------------------------------------------------------ experts
def held_experts(cfg: dict) -> int:
    return cfg["num_experts"]


def all_experts(cfg: dict) -> int:
    return cfg["published"]["num_experts"]


def expert_layers(cfg: dict) -> int:
    return sum(t == "sparse"
               for t in cfg["mlp_layer_types"][:cfg["num_hidden_layers"]])


def expert_params(cfg: dict) -> int:
    """Parameters of one routed expert (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def hit_probability(cfg: dict) -> float:
    """The chance that a token chooses a given held expert (see
    roofline_mla_moe.hit_probability)."""
    served = cfg.get("assumed", {}).get("served_routing")
    if served is None:
        return cfg["num_experts_per_tok"] / all_experts(cfg)
    reached = served["distinct_held_experts"] / held_experts(cfg)
    return 1.0 - (1.0 - reached) ** (1.0 / served["decode_batch"])


def local_pairs_per_token(cfg: dict) -> float:
    return held_experts(cfg) * hit_probability(cfg)


def expected_distinct_experts(tokens: float, cfg: dict) -> float:
    """Held experts that get at least one of ``tokens`` tokens."""
    return held_experts(cfg) * (1.0 - (1.0 - hit_probability(cfg)) ** tokens)


def moe_layer_bytes(tokens: float, cfg: dict) -> float:
    """Weights ONE expert layer's routed part must read for a call of
    ``tokens`` tokens: every distinct expert with a token, once."""
    return expected_distinct_experts(tokens, cfg) * expert_params(cfg) * BYTES


def moe_layer_flops(tokens: float, cfg: dict) -> float:
    return 2.0 * tokens * local_pairs_per_token(cfg) * expert_params(cfg)


# ---------------------------------------------------------------- the step
def attn_params(cfg: dict, kind: str) -> int:
    """One layer's attention matrices: Wq, Wk, Wv, Wo and the head gate."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, hkv = heads_of(cfg, kind), cfg["num_key_value_heads"]
    gate = d * h if cfg.get("gating") == "per-head" else 0
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + gate


def non_expert_weight_bytes(cfg: dict) -> float:
    """Bytes of the weights every decode step reads whatever the routing:
    attention of every layer by its kind, the dense layers' SwiGLU, router
    and shared expert of the expert layers, the output head."""
    d = cfg["hidden_size"]
    dense = cfg["num_hidden_layers"] - expert_layers(cfg)
    n = (sum(layers_of(cfg, k) * attn_params(cfg, k)
             for k in ("full", "window"))
         + dense * 3 * d * cfg["intermediate_size"]
         + expert_layers(cfg) * (d * all_experts(cfg) + 3 * d
                                 * cfg["shared_expert_intermediate_size"])
         + d * cfg["vocab_size"])
    return float(n * BYTES)


def decode_step_bytes(batch: float, vis: dict, cfg: dict) -> float:
    """HBM bytes of one decode step of ``batch`` sequences whose visible
    contexts sum to ``vis[kind]`` tokens for a layer of each kind."""
    return (non_expert_weight_bytes(cfg)
            + expert_layers(cfg) * moe_layer_bytes(batch, cfg)
            + sum(layers_of(cfg, k) * decode_attn_bytes(vis[k], cfg)
                  for k in ("full", "window")))
