"""Operations and bytes of a decode step and of the attention kernels of a
LOOPED configuration (Ouro): ``num_hidden_layers`` layers run
``total_ut_steps`` times a token with the same weights, and each (pass,
layer) keeps keys and values of its own. From the configuration FILE's
published sizes and token counts the CLIENT observed; nothing here asks
the program what it did, and the pass and layer counts are the file's.

Counted as a perfect implementation would pay them: every layer
application reads that layer's matrices once (nothing of a 51M-parameter
layer survives in on-chip memory until the next pass, 47 layers later),
the head is read once a step, a visible token's K and V are read once in
every (pass, layer) slot; weights and cache are bfloat16 (2 bytes).
"""

from __future__ import annotations

import roofline as R

BYTES = 2      # bfloat16 weights and cache


def passes(cfg: dict) -> int:
    return int(cfg["total_ut_steps"])


def layer_applications(cfg: dict) -> int:
    """Layer applications a token: passes x layers, which is also the
    number of KV slots and of decode-kernel calls a step."""
    return passes(cfg) * cfg["num_hidden_layers"]


def layer_weight_bytes(cfg: dict) -> int:
    """One layer's matrices (the four norm gains, 16 KB, are left out)."""
    return R.matmul_params_per_layer(cfg) * BYTES


def head_weight_bytes(cfg: dict) -> int:
    """The output head; the embedding is a gather of a few rows."""
    return cfg["hidden_size"] * cfg["vocab_size"] * BYTES


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one token over all (pass, layer) slots."""
    return layer_applications(cfg) * R.kv_bytes_per_token_layer(cfg, BYTES)


def decode_step_bytes(ctx_tokens: float, cfg: dict) -> float:
    """HBM bytes one decode step must read when the visible contexts of
    its sequences sum to ``ctx_tokens``."""
    return (layer_applications(cfg) * layer_weight_bytes(cfg)
            + head_weight_bytes(cfg) + ctx_tokens * kv_bytes_per_token(cfg))


def layer_pass_floor_us(cfg: dict, hbm_bytes_per_s: float) -> float:
    """The least one layer application can take: its matrices at the HBM
    peak (125 us for Ouro-2.6B on a v5e)."""
    return 1e6 * layer_weight_bytes(cfg) / hbm_bytes_per_s


def prefill_attn_flops(keys: float, cfg: dict) -> float:
    """Attention flops of that many (query, key) pairs over every (pass,
    layer): each pass attends again, over its own keys."""
    return layer_applications(cfg) * R.prefill_attn_flops(keys, cfg)
