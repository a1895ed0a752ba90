"""NamedSharding specs for model params and the paged KV pool.

Megatron-style tensor parallelism expressed declaratively: annotate the
weights, let GSPMD place the collectives.

- QKV projections shard the *head* (output) dim; the attention output
  projection shards its *input* dim — one all-reduce per attention block.
- SwiGLU gate/up shard the hidden (f) dim; down shards its input — one
  all-reduce per FFN.
- Mixtral experts shard the *expert* dim over the same ``tp`` axis
  (expert parallelism): the dispatch/combine einsums in
  models/mixtral.py:moe_ffn become all-to-alls over ICI.
- Embedding and lm_head shard the vocab dim (vocab-parallel logits).
- KV pages shard the kv-head dim, which keeps the paged pool's per-chip
  slice aligned with the head-sharded K/V projections — no resharding
  between projection, cache write, and attention.

The reference has no analogue of any of this (SURVEY.md §2b: parallelism was
a property of its external server); the sharding design follows the
jax-ml scaling-book recipe: pick a mesh, annotate, let XLA insert
collectives.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_inference.config import ModelConfig
from tpu_inference.models.quant import (QuantizedArray, Transposed,
                                        transposed_spec)


def _llama_specs(cfg: ModelConfig) -> dict:
    specs = {
        "embed": P("tp", None),
        "blocks": {
            "attn_norm": P(),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "ffn_norm": P(),
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        },
        "final_norm": P(),
    }
    if cfg.qkv_bias:
        # Qwen2 q/k/v biases follow their projection's head (output) dim.
        specs["blocks"]["bq"] = P(None, "tp")
        specs["blocks"]["bk"] = P(None, "tp")
        specs["blocks"]["bv"] = P(None, "tp")
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def _mixtral_specs(cfg: ModelConfig) -> dict:
    return {
        "embed": P("tp", None),
        "blocks": {
            "attn_norm": P(),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "ffn_norm": P(),
            "w_router": P(),
            # Expert parallelism: experts distributed over the tp axis.
            "w_gate": P(None, "tp", None, None),
            "w_up": P(None, "tp", None, None),
            "w_down": P(None, "tp", None, None),
        },
        "final_norm": P(),
        "lm_head": P(None, "tp"),
    }


def _gpt2_specs(cfg: ModelConfig) -> dict:
    # w_qkv packs [q|k|v] along the output dim (3*d_model wide). A contiguous
    # tp shard of the packed dim crosses the q/k/v boundaries unless tp is a
    # multiple of 3, so GSPMD reshards around the split in gpt2._block —
    # correct but costs extra collectives. gpt2 is the CPU-stub/parity model
    # (BASELINE.json config 0), never the TP-serving flagship, so the simple
    # packed sharding is kept.
    return {
        "embed": P("tp", None),
        "pos_embed": P(),
        "blocks": {
            "ln1_w": P(), "ln1_b": P(),
            "w_qkv": P(None, None, "tp"),
            "b_qkv": P(None, "tp"),
            "w_proj": P(None, "tp", None),
            "b_proj": P(),
            "ln2_w": P(), "ln2_b": P(),
            "w_fc": P(None, None, "tp"),
            "b_fc": P(None, "tp"),
            "w_out": P(None, "tp", None),
            "b_out": P(),
        },
        "ln_f_w": P(), "ln_f_b": P(),
    }


def validate_tp(cfg: ModelConfig, tp: int) -> None:
    """Fail fast (with a named dimension) when tp can't evenly shard the
    model, instead of an opaque GSPMD error deep inside engine init."""
    checks = [
        ("n_heads", cfg.n_heads),
        ("n_kv_heads", cfg.n_kv_heads),
        ("d_ff", cfg.d_ff),
        ("vocab_size", cfg.vocab_size),
    ]
    if cfg.n_experts:
        checks.append(("n_experts", cfg.n_experts))
    for name, dim in checks:
        if dim % tp != 0:
            raise ValueError(
                f"tp={tp} does not divide {name}={dim} for model "
                f"{cfg.name!r}; choose tp from the divisors of {name}")


def param_specs(cfg: ModelConfig) -> dict:
    """PartitionSpec pytree with the same structure as the family's params."""
    fam = {"llama": _llama_specs, "mixtral": _mixtral_specs,
           "gpt2": _gpt2_specs}[cfg.family]
    return fam(cfg)


def _scale_spec(spec: P, leaf) -> P:
    """Spec for a QuantizedArray's scale: same as the weight's. The
    contraction dim is size 1 in an int8 scale (unshard it — replicated)
    but holds G groups in an int4 scale, where it must follow the
    weight's contraction-dim sharding so each chip keeps the scales for
    its own weight shard."""
    ndim = leaf.q.ndim
    entries = list(spec) + [None] * (ndim - len(spec))
    if leaf.scale.shape[-2] == 1:
        entries[ndim - 2] = None
    return P(*entries)


def param_shardings(cfg: ModelConfig, mesh: Mesh,
                    params: Optional[dict] = None) -> Any:
    """NamedSharding pytree for the family's params.

    Without ``params`` the tree mirrors ``param_specs`` (plain-array
    leaves). With ``params`` (possibly holding int8 ``QuantizedArray``
    leaves, models/quant.py) the result mirrors the actual params tree:
    the quantized payload takes the weight's spec, the scale the same
    spec with its reduced contraction dim unsharded. A leaf stored
    transposed (the engine's own orientation, ``quant.store_transposed``)
    takes the spec with its last two entries swapped: each dim keeps
    its axis.
    """
    validate_tp(cfg, mesh.shape.get("tp", 1))
    specs = param_specs(cfg)
    if params is None:
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def mk(spec: P, leaf: Any):
        if isinstance(leaf, Transposed):
            return Transposed(NamedSharding(
                mesh, transposed_spec(spec, leaf.ndim)))
        if isinstance(leaf, QuantizedArray):
            sspec = _scale_spec(spec, leaf)
            ngrp = leaf.scale.shape[-2]
            axis = sspec[leaf.q.ndim - 2] if len(sspec) >= leaf.q.ndim - 1 \
                else None
            if ngrp > 1 and axis is not None:
                n = int(mesh.shape.get(axis, 1))
                if ngrp % n:
                    # Fail here with a named leaf, not deep inside GSPMD
                    # placement (same job validate_tp does for head/ff
                    # divisibility — the grouped constraint depends on
                    # the quantized leaf, so it's checked at shard time).
                    raise ValueError(
                        f"int4 grouped scales: {ngrp} groups on a "
                        f"contraction dim sharded over {axis}={n} don't "
                        f"divide evenly; use a tp that divides the group "
                        f"count (dim/{2 * leaf.q.shape[-2] // ngrp}, "
                        "codes nibble-packed) or --quant int8")
            if leaf.transposed:
                spec = transposed_spec(spec, leaf.q.ndim)
            return QuantizedArray(
                q=NamedSharding(mesh, spec),
                scale=NamedSharding(mesh, sspec),
                transposed=leaf.transposed)
        return NamedSharding(mesh, spec)

    return jax.tree.map(mk, specs, params,
                        is_leaf=lambda x: isinstance(x, P))


def shard_params(params: dict, cfg: ModelConfig, mesh: Mesh) -> dict:
    """Place a params pytree onto the mesh per `param_specs`."""
    return jax.tree.map(jax.device_put, params,
                        param_shardings(cfg, mesh, params))


def kv_spec() -> P:
    """KV pool [L, pages, page_size, Hkv, head_dim]: shard kv heads on tp."""
    return P(None, None, None, "tp", None)


def kv_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, kv_spec())


def kv_scale_sharding(mesh: Mesh) -> NamedSharding:
    """Scale pool [L, pages, page_size, Hkv] (int8 KV): heads on tp,
    aligned with the code pool so in-kernel dequant stays chip-local."""
    return NamedSharding(mesh, P(None, None, None, "tp"))
