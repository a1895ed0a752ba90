"""Operations and bytes of the kernels and of a decode step, from shapes.

The benchmark's own arithmetic: inputs are a configuration file's
published sizes and token counts the CLIENT observed; nothing here asks
the program what it thinks it did. A roofline share is the least time the
chip could take (the larger of flops / peak flops and bytes / peak bytes
per second) over the time the trace shows; readers say which bound held.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def window(cfg: dict) -> int:
    if not cfg.get("use_sliding_window", True):
        return 0
    return int(cfg.get("sliding_window") or 0)


def kv_bytes_per_token_layer(cfg: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one layer (bf16 cache: 2 bytes)."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * kv_bytes


def visible(ctx_len: int, cfg: dict) -> int:
    """Tokens of a context that one decode query attends to."""
    w = window(cfg)
    return min(ctx_len, w) if w else ctx_len


def decode_attn_bytes(ctx_tokens: float, cfg: dict) -> float:
    """HBM bytes one layer's decode attention must read for queries whose
    visible contexts sum to ``ctx_tokens`` (K and V once; q and out are
    a few KB and left out)."""
    return ctx_tokens * kv_bytes_per_token_layer(cfg)


def decode_attn_flops(ctx_tokens: float, cfg: dict) -> float:
    """QK^T and PV: 2 * 2 * heads * head_dim per visible token."""
    return 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * ctx_tokens


def prefill_attn_keys(new_tokens: int, cached_tokens: int, cfg: dict) -> int:
    """Query-key pairs of ``new_tokens`` queries that follow
    ``cached_tokens`` already in the cache (causal, windowed): query i
    sees min(cached + i + 1, window) keys."""
    w = window(cfg)
    keys = 0
    for i in range(new_tokens):
        seen = cached_tokens + i + 1
        keys += min(seen, w) if w else seen
    return keys


def prefill_attn_flops(keys: float, cfg: dict) -> float:
    """One layer's attention flops for that many query-key pairs: QK^T
    and PV, 2 * 2 * heads * head_dim a pair."""
    return 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * keys


def ledger_prefill_keys(rec: dict, cfg: dict) -> float:
    """Query-key pairs of one prefill record of the engine's step ledger.
    The engine counts ``kv_read_tokens`` = chunk * offset + chunk *
    (chunk + 1) / 2 with no window; for a one-prompt chunk the offset is
    recovered from that and the pairs counted again here with the
    window. A batched record (several short prompts, none past the
    window) is taken as counted."""
    c = rec["chunk_tokens"]
    if not window(cfg) or rec["slots"] != 1 or not c:
        return float(rec["kv_read_tokens"])
    offset = (rec["kv_read_tokens"] - c * (c + 1) // 2) // c
    return float(prefill_attn_keys(c, offset, cfg))


def matmul_params_per_layer(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * head_dim(cfg)
    hkv = cfg["num_key_value_heads"] * head_dim(cfg)
    return d * (hq + 2 * hkv) + hq * d + 3 * d * f


def weight_bytes_per_step(cfg: dict, quant: str, chips: int = 1) -> float:
    """Bytes of weights one chip reads in one decode step: every layer's
    matrices and the output head (the embedding is a gather of a few
    rows). int8: one byte a weight plus a float32 scale per output
    channel."""
    n = (cfg["num_hidden_layers"] * matmul_params_per_layer(cfg)
         + cfg["hidden_size"] * cfg["vocab_size"])
    if quant == "int8":
        d, f = cfg["hidden_size"], cfg["intermediate_size"]
        hq = cfg["num_attention_heads"] * head_dim(cfg)
        hkv = cfg["num_key_value_heads"] * head_dim(cfg)
        scales = (cfg["num_hidden_layers"] * (hq + 2 * hkv + d + 2 * f + d)
                  + cfg["vocab_size"])
        return (n + 4 * scales) / chips
    raise ValueError(f"no weight bytes for quant {quant!r}")
