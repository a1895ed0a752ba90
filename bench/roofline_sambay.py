"""Operations and bytes of the kernels and of a decode step of a SambaY
configuration (Phi-4-mini-flash-reasoning: state-space layers, window
attention, ONE full-attention layer whose K / V the cross layers read,
gated memory units), from the configuration FILE's published sizes (and
its ``assumed`` scan sizes) and token counts the CLIENT observed. Nothing
here asks the program what it did.

Counted as a perfect implementation would pay them, bfloat16 (2 bytes)
unless said:

* a visible token's K and V once a READING layer: the one full layer and
  each cross layer read the same slot, so its bytes count once a reader
  (n_cross + 1 times a step), and a window layer sees min(context,
  sliding_window);
* the selective scan of one layer over a chunk of T tokens reads x (2 B)
  and dt (float32, 4 B) and writes y (2 B) a channel and token, reads B
  and C (float32, 2 x 4 x d_state a token), and reads and writes the
  float32 state once a chunk and lane: what the program's kernel is
  handed (the z gate is applied outside it, so z is not counted);
* a decode step reads and writes every state-space layer's float32 state
  and conv tail once a lane;
* every matrix a step reads once.
"""

from __future__ import annotations

# A kind's window and its (query, key) pairs are counted as for any stack
# of window and full layers (they read only ``sliding_window``).
from roofline_mixed import (ledger_prefill_pairs, prefill_pairs,  # noqa: F401
                            visible)

BYTES = 2      # bfloat16 weights, cache and activations
F32 = 4


def kinds(cfg: dict) -> list:
    """The layers' kinds, derived from the depth as the source does."""
    n = cfg["num_hidden_layers"]
    mid = n // 2
    return [("ssm" if l % 2 == 0 else "window") if l <= mid
            else "full" if l == mid + 1
            else ("gmu" if l % 2 == 0 else "cross") for l in range(n)]


def layers_of(cfg: dict, kind: str) -> int:
    return kinds(cfg).count(kind)


def full_readers(cfg: dict) -> int:
    """Layers that read the full slot a token: itself and the cross ones."""
    return layers_of(cfg, "full") + layers_of(cfg, "cross")


def head_dim(cfg: dict) -> int:
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def pool_heads(cfg: dict) -> tuple:
    """(pair heads, width) of a pool entry as the program stores it."""
    return cfg["num_key_value_heads"] // 2, 2 * head_dim(cfg)


def scan_sizes(cfg: dict) -> tuple:
    """(d_inner, d_state, d_conv, dt_rank)."""
    a, d = cfg["assumed"]["mamba"], cfg["hidden_size"]
    return a["expand"] * d, a["d_state"], a["d_conv"], -(-d // 16)


def kv_bytes_per_token_layer(cfg: dict) -> int:
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BYTES


def decode_attn_bytes(ctx_tokens: float, cfg: dict) -> float:
    """HBM bytes ONE layer's decode attention must read for queries whose
    visible contexts sum to ``ctx_tokens``."""
    return ctx_tokens * kv_bytes_per_token_layer(cfg)


def attn_flops(pairs: float, cfg: dict) -> float:
    """ONE layer's differential attention for that many (query, key)
    pairs, as the equations pay it: two softmaxes of H / 2 heads, QK^T d
    wide and PV 2 d wide (the padded form the kernels run is 4 / 3 of
    this; the share is of what the model needs)."""
    return 6.0 * cfg["num_attention_heads"] * head_dim(cfg) * pairs


# ------------------------------------------------------------------ the scan
def state_bytes_per_seq_layer(cfg: dict) -> int:
    """One layer's state of one sequence: float32 h and the conv tail."""
    di, ns, kc, _ = scan_sizes(cfg)
    return di * (ns * F32 + (kc - 1) * BYTES)


def scan_bytes(tokens: float, lanes: float, cfg: dict) -> float:
    """HBM bytes ONE layer's selective-scan kernel must move for a call of
    ``tokens`` valid tokens over ``lanes`` lanes."""
    di, ns, _, _ = scan_sizes(cfg)
    return (tokens * (di * (BYTES + F32 + BYTES) + 2 * ns * F32)
            + lanes * 2 * di * ns * F32)


def scan_flops(tokens: float, cfg: dict) -> float:
    """exp, two multiplies and an add to advance, a multiply-add to read
    out, a state element and token."""
    di, ns, _, _ = scan_sizes(cfg)
    return tokens * di * ns * 7.0


def decode_state_bytes(lanes: float, cfg: dict) -> float:
    """Every state-space layer's state in and out, once a lane a step."""
    return lanes * layers_of(cfg, "ssm") * 2 * state_bytes_per_seq_layer(cfg)


# ---------------------------------------------------------------- the step
def weight_params(cfg: dict) -> int:
    """Every parameter a decode step multiplies through (all of them; the
    tied embedding once, as the head)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    di, ns, kc, r = scan_sizes(cfg)
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    per = {"ssm": d * 2 * di + di * (r + 2 * ns) + r * di + di * d
           + di * (kc + 2 + ns + 1),
           "window": d * (q + 2 * kv) + q * d, "full": d * (q + 2 * kv) + q * d,
           "gmu": 2 * d * di, "cross": 2 * d * q}
    return (sum(layers_of(cfg, k) * p for k, p in per.items())
            + cfg["num_hidden_layers"] * 3 * d * f + d * cfg["vocab_size"])


def decode_step_bytes(batch: float, vis: dict, cfg: dict) -> float:
    """HBM bytes of one decode step of ``batch`` sequences whose visible
    contexts sum to ``vis[kind]`` tokens for a layer of each kind."""
    return (weight_params(cfg) * BYTES
            + full_readers(cfg) * decode_attn_bytes(vis["full"], cfg)
            + layers_of(cfg, "window") * decode_attn_bytes(vis["window"], cfg)
            + decode_state_bytes(batch, cfg))
