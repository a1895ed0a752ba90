"""Operations and bytes of a hyper-connection (models/hyper_connections.py:
n residual streams a token, mixed twice a layer) and of a decode step of
a configuration that holds EVERY routed expert of its layers beside
latent attention, from the configuration FILE's published sizes. The
latent attention's and the non-expert weights' counts are
``roofline_mla_moe``'s own (the block is the DeepSeek-V3 one); what is
new here: the hyper-connections, and an expert layer whose read bytes
follow the distinct experts the program COUNTED (as
``roofline_smallthinker`` does: every expert is held, so the count is
what the kernels were given, and no routing need be assumed).

Counted as a perfect implementation would pay them: a hyper-connection
reads the n streams and the sublayer's output once and writes the n
streams once, bfloat16 (the coefficient head, the stream norm and both
mixes in one pass over the streams). These are counts of what a
hyper-connection touches, not of HBM traffic: on the v5e the compiler
keeps a chunk's streams in VMEM between the ops (readers/xing_mhc.py),
so no roofline share is made of them.
"""

from __future__ import annotations

import roofline_mla_moe as M

BYTES = M.BYTES


def streams(cfg: dict) -> int:
    return cfg["hc_mult"]


def n_coeff(cfg: dict) -> int:
    """Coefficients a token a sublayer: H_pre, H_post (n each), H_res."""
    n = streams(cfg)
    return n * (n + 2)


def wide(cfg: dict) -> int:
    """vec(X): the n streams of a token side by side."""
    return streams(cfg) * cfg["hidden_size"]


def sublayers(cfg: dict) -> int:
    """Hyper-connections a token passes: attention and FFN of every layer."""
    return 2 * cfg["num_hidden_layers"]


def mhc_stream_bytes(tokens: float, cfg: dict) -> float:
    """HBM bytes ONE hyper-connection must move for ``tokens`` tokens:
    read n D (the streams) + D (the sublayer's output), write n D."""
    return tokens * (2 * wide(cfg) + cfg["hidden_size"]) * BYTES


def mhc_weight_bytes(cfg: dict) -> float:
    """ONE hyper-connection's own leaves: phi bfloat16, b and alpha
    float32."""
    return wide(cfg) * n_coeff(cfg) * BYTES + (n_coeff(cfg) + 3) * 4


def mhc_flops(tokens: float, cfg: dict) -> float:
    """ONE hyper-connection's arithmetic for ``tokens`` tokens: the
    stream norm (2 n D), the head's matmul (2 n D x n (n + 2)), the
    pre-mix (2 n D), the residual mix (2 n^2 D) and the post-mix (2 n D),
    and the projection: ``hc_sinkhorn_iters`` x (2 n^2 divides + 2 n (n -
    1) adds + 2 n eps adds)."""
    n, d = streams(cfg), cfg["hidden_size"]
    per_token = (2 * n * d + 2 * n * d * n_coeff(cfg) + 2 * n * d
                 + 2 * n * n * d + 2 * n * d
                 + cfg["hc_sinkhorn_iters"] * (2 * n * n + 2 * n * (n - 1)
                                               + 2 * n))
    return float(tokens * per_token)


def top_k(cfg: dict) -> int:
    return cfg["num_experts_per_tok"]


def moe_read_bytes(distinct: float, cfg: dict) -> float:
    """Weights ONE expert layer's routed part must read in a call that
    reaches ``distinct`` experts: each once (gate and up in the first
    kernel, down in the second)."""
    return distinct * M.expert_params(cfg) * BYTES


def moe_flops(tokens: float, cfg: dict) -> float:
    """ONE expert layer's routed part for ``tokens`` tokens: every pair
    is local (all experts held), 2 flops a multiply-add."""
    return 2.0 * tokens * top_k(cfg) * M.expert_params(cfg)


def non_expert_weight_bytes(cfg: dict) -> float:
    """What every decode step reads whatever the routing:
    ``roofline_mla_moe``'s count (attention, the dense layer's SwiGLU,
    router and shared expert, the head) plus the hyper-connections' own
    leaves."""
    return (M.non_expert_weight_bytes(cfg)
            + sublayers(cfg) * mhc_weight_bytes(cfg))


def decode_step_bytes(distinct: float, ctx_tokens: float,
                      cfg: dict) -> float:
    """HBM bytes of one decode step of sequences whose visible contexts
    sum to ``ctx_tokens`` and whose expert layers each reach ``distinct``
    experts: weights once, the reached experts, the latents. The streams
    of a decode step (64 lanes x 28 KB) are not counted: the v5e's
    compiler keeps them in VMEM between a hyper-connection's ops."""
    return (non_expert_weight_bytes(cfg)
            + M.expert_layers(cfg) * moe_read_bytes(distinct, cfg)
            + cfg["num_hidden_layers"] * M.mla_attn_bytes(ctx_tokens, cfg))
