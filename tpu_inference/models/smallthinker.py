"""SmallThinker (PowerInfer) decoder: rope-less full-attention layers
among window ones, and EVERY layer routed from its pre-attention norm to
ReLU-gated experts, as pure JAX.

The stack of kinds is ``models/laguna.py``'s (parameters stacked per
kind, a KV pool a kind, one traced body a kind, a scan over whole
periods) and the expert layer ``models/deepseek_v3.py``'s ``route`` /
``moe_ffn`` over the grouped kernels: this module calls both and copies
neither. What differs, each a ``ModelConfig`` field those read:

- **No position signal on a full layer** (``nope_kinds = ("full",)``):
  layer 0 of each four attends over the whole context with q and k as
  projected; the three window layers behind it see ``sliding_window``
  keys with plain rope on every dim.
- **The router reads the layer's normed INPUT** (``router_input =
  "attn_norm"``): ``h = RMSNorm(x)`` feeds q / k / v AND the router, so a
  token's experts are known before its attention runs; the experts
  themselves read the post-attention norm. The logits are computed
  there, under ``moe_early_router``.
- **Gates are a softmax over the chosen logits** (``moe_scoring =
  "softmax"`` with ``norm_topk_prob``: softmax over all, renormalised
  over the k chosen, is the same function), no selection bias (the tree
  has no ``router_bias``), no scaling factor.
- **ReLU-gated experts** (``moe_act = "relu"``): ``relu(h Wg) * (h Wu)``.
- **No shared expert and no dense layer**: the tree has neither
  ``ws_*`` nor ``ffn_dense``; every expert of a layer is held here
  (``ep_size`` 1), so a token's k pairs are all local.
- The expert layers also count the rows the grouped kernels ran beside
  the real pairs (``moe_row_stats``).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax

from tpu_inference.config import ModelConfig
from tpu_inference.models import laguna
from tpu_inference.models.common import AttentionFn
from tpu_inference.models.deepseek_v3 import n_moe_stats

unembed = laguna.unembed
make_dense_attn = laguna.make_dense_attn
forward_hidden = laguna.forward_hidden
n_aux_stats = n_moe_stats
combines_by_gather = laguna.combines_by_gather


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree's leaf shapes (bench/references/smallthinker.py builds
    the same tree from the configuration file)."""
    d, n = cfg.d_model, cfg.n_layers
    e, f = cfg.n_local_experts, cfg.moe_d_ff
    return {
        "embed": (cfg.vocab_size, d),
        "attn_full": laguna._attn_shapes(cfg, "full"),
        "attn_window": laguna._attn_shapes(cfg, "window"),
        "ffn_moe": {"ffn_norm": (n, d), "w_router": (n, d, cfg.n_experts),
                    "we_gate": (n, e, d, f), "we_up": (n, e, d, f),
                    "we_down": (n, e, f, d)},
        "final_norm": (d,), "lm_head": (d, cfg.vocab_size),
    }


def param_count(cfg: ModelConfig, active: bool = False) -> int:
    return laguna.param_count(cfg, active, param_shapes(cfg))


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    assert cfg.first_k_dense == 0 and cfg.n_shared_experts == 0
    return laguna.init_params(cfg, key, param_shapes(cfg))


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv: Any,
            attn: AttentionFn) -> Tuple[jax.Array, Any]:
    hidden, kv = forward_hidden(params, cfg, tokens, positions, kv, attn)
    return unembed(params, cfg, hidden), kv
