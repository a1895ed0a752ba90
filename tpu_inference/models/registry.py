"""Model family registry: maps ModelConfig.family -> module of pure fns."""

from __future__ import annotations

import types
from typing import Tuple

import jax

from tpu_inference.config import ModelConfig


def get_model_fns(cfg: ModelConfig) -> types.ModuleType:
    from tpu_inference.models import (bailing_hybrid, deepseek_v3, gpt2,
                                      laguna, llama, mixtral, ouro, sambay,
                                      smallthinker)

    return {"llama": llama, "mixtral": mixtral, "gpt2": gpt2,
            "deepseek_v3": deepseek_v3, "ouro": ouro,
            "laguna": laguna, "sambay": sambay,
            "smallthinker": smallthinker,
            "bailing_hybrid": bailing_hybrid}[cfg.family]


def family_fn(cfg: ModelConfig, name: str):
    """A function the family's module defines where it differs from the
    dense default (``param_count``, ``attn_pair_dim``, ``n_aux_stats``),
    or None: shared layers ask here and never name a family."""
    return getattr(get_model_fns(cfg), name, None)


def build_model(cfg: ModelConfig, seed: int = 0,
                shardings=None) -> Tuple[dict, types.ModuleType]:
    """Random-init params + family module.

    With ``shardings`` (a NamedSharding tree, parallel/shardings.py
    param_shardings) every leaf is generated straight into its sharded
    layout: each chip draws and keeps only its own shard, f32
    intermediates included — a model that one chip cannot hold (Mistral-7B
    bf16 over tp=4: the unsharded init needs 7 GB for one f32 leaf) never
    exists in one place. Same values either way: the threefry PRNG is
    partitionable, so a leaf's bits do not depend on how it is sharded."""
    mod = get_model_fns(cfg)
    key = jax.random.PRNGKey(seed)
    if shardings is None:
        return mod.init_params(cfg, key), mod
    init = jax.jit(lambda k: mod.init_params(cfg, k), out_shardings=shardings)
    return init(key), mod
