"""Operations and bytes of the attention and grouped-expert kernels and of
a decode step of the SmallThinker configuration (one pipeline stage:
rope-less full layers among window ones, EVERY expert of every layer
held, top-6 of 64 ReLU-gated experts, no shared expert, no dense layer),
from the configuration FILE's published sizes. The counts that do not
depend on the key names are ``roofline_mixed``'s: ``as_mixed`` says the
file's sizes under the names that module reads (``layer_types``,
``num_attention_heads_per_layer``, ``sliding_window``, ``num_experts``
...), and everything here that has a twin there calls it.

What is counted differently, because every expert is here:

* a token's k pairs are ALL local (``local_pairs_per_token`` = top-k,
  whatever the routing), so an expert call's FLOPs follow from its
  token count alone: no expectation over the routing is taken;
* the experts a decode layer step READS are taken from the program's
  counter (``distinct_experts`` a decode layer step), not from an
  expected count: ``moe_read_bytes``. ``expected_distinct_experts`` is
  the uniform figure to hold that counter against (63.9 of 64 at 64
  lanes).

Counted as a perfect implementation would pay them: a visible token's K
and V once a layer (a window layer sees min(context, window)), every
matrix a step reads once, bfloat16 (2 bytes).
"""

from __future__ import annotations

import roofline_mixed as R

BYTES = R.BYTES


def as_mixed(cfg: dict) -> dict:
    """The configuration under the key names ``roofline_mixed`` (and
    ``readers/mixed``) read."""
    n = len(cfg["sliding_window_layout"])
    experts = cfg["moe_num_primary_experts"]
    return {
        "num_hidden_layers": cfg["num_hidden_layers"],
        "hidden_size": cfg["hidden_size"],
        "head_dim": cfg["head_dim"],
        "num_key_value_heads": cfg["num_key_value_heads"],
        "vocab_size": cfg["vocab_size"],
        "layer_types": ["sliding_attention" if w else "full_attention"
                        for w in cfg["sliding_window_layout"]],
        "num_attention_heads_per_layer": [cfg["num_attention_heads"]] * n,
        "sliding_window": cfg["sliding_window_size"],
        "mlp_layer_types": ["sparse"] * n,
        "intermediate_size": 0, "shared_expert_intermediate_size": 0,
        "moe_intermediate_size": cfg["moe_ffn_hidden_size"],
        "num_experts": experts, "published": {"num_experts": experts},
        "num_experts_per_tok": cfg["moe_num_active_primary_experts"],
        "assumed": {k: v for k, v in cfg.get("assumed", {}).items()
                    if k == "served_routing"},
    }


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def experts(cfg: dict) -> int:
    return cfg["moe_num_primary_experts"]


def top_k(cfg: dict) -> int:
    return cfg["moe_num_active_primary_experts"]


def expert_params(cfg: dict) -> int:
    """Parameters of one routed expert (gate, up, down)."""
    return R.expert_params(as_mixed(cfg))


def layer_params(cfg: dict) -> int:
    """One layer: attention, router, two norms, every expert."""
    m = as_mixed(cfg)
    return (R.attn_params(m, "full") + cfg["hidden_size"] * experts(cfg)
            + 2 * cfg["hidden_size"] + experts(cfg) * expert_params(cfg))


def stage_params(cfg: dict) -> int:
    """This stage: its layers, the embedding, the head, the final norm."""
    d = cfg["hidden_size"]
    return layers(cfg) * layer_params(cfg) + 2 * cfg["vocab_size"] * d + d


def kv_bytes_per_token(cfg: dict, kind=None) -> int:
    """K and V of one token over a kind's layers (None: both kinds', what
    a token under the window holds)."""
    m = as_mixed(cfg)
    if kind is None:
        return sum(R.kv_bytes_per_token(m, k) for k in ("full", "window"))
    return R.kv_bytes_per_token(m, kind)


def expected_distinct_experts(tokens: float, cfg: dict) -> float:
    """Experts of a layer that get at least one of ``tokens`` tokens
    under uniform independent routing."""
    e = experts(cfg)
    return e * (1.0 - (1.0 - top_k(cfg) / e) ** tokens)


def moe_read_bytes(distinct: float, cfg: dict) -> float:
    """Weights ONE expert layer's call must read: every distinct expert
    with a row, once."""
    return distinct * expert_params(cfg) * BYTES


def moe_flops(tokens: float, cfg: dict) -> float:
    """ONE expert layer's multiply-adds for ``tokens`` tokens: top-k real
    pairs a token, all here (padded rows are not work)."""
    return 2.0 * tokens * top_k(cfg) * expert_params(cfg)


def non_expert_weight_bytes(cfg: dict) -> float:
    """Weights every decode step reads whatever the routing: attention
    and router of every layer, the head (the embedding is a gather of a
    row a lane)."""
    return R.non_expert_weight_bytes(as_mixed(cfg))


def decode_step_bytes(distinct: float, vis: dict, cfg: dict) -> float:
    """HBM bytes of one decode step: the non-expert weights once, the
    distinct experts a layer read (the program's count) in every layer,
    the visible K / V of each kind's layers."""
    m = as_mixed(cfg)
    return (non_expert_weight_bytes(cfg)
            + layers(cfg) * moe_read_bytes(distinct, cfg)
            + sum(R.layers_of(m, k) * R.decode_attn_bytes(vis[k], m)
                  for k in ("full", "window")))
