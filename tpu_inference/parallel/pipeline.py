"""Inference pipeline parallelism: layer stages over a ``pp`` mesh axis.

The last of the survey's named parallelism strategies (SURVEY.md §2b:
DP/TP/PP/SP/EP): the stacked-layer parameter pytree shards along its
LAYER axis, each stage owns ``n_layers / pp`` consecutive blocks, and
activations flow stage-to-stage with ``jax.lax.ppermute`` in a
GPipe-style micro-batch schedule — the TPU-idiomatic shape of pipeline
parallelism (collective-permute over ICI intra-slice, DCN inter-slice;
XLA overlaps the permute with the next micro-batch's compute). PP is
the inter-slice scaling tier in the scaling-book recipe: TP saturates
ICI inside a slice, PP spans slices where all-reduce would be
DCN-bound, because its only cross-stage traffic is one activation
tensor per micro-batch.

Scope: full-sequence forward (prefill-shaped). This demonstrates the
sharding + schedule against the unsharded oracle; the serving engine's
production scaling axes remain (dp, tp, sp) — for paged decode the
natural composition shards the KV pool's layer dim with the stages
(each stage already holds only its layers' pages), which this module's
layer-slab layout is designed to line up with.

SPMD notes: every stage executes every step's full program (embedding,
its local blocks, final norm + unembed) with non-owned results masked
to zero and combined by one ``psum`` at the end — the standard
"compute-and-mask" pipelining formulation that keeps the program
identical across devices (no data-dependent control flow for XLA).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpu_inference.config import ModelConfig
from tpu_inference.models import llama
from tpu_inference.models.common import make_dense_attn, rms_norm


def stage_specs(params: dict) -> dict:
    """Partition specs: blocks shard their leading (layer) axis over
    ``pp``; embeddings / norms / head replicate."""
    return {
        name: (jax.tree.map(lambda _: P("pp"), sub)
               if name == "blocks" else jax.tree.map(lambda _: P(), sub))
        for name, sub in params.items()
    }


def pp_forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
               positions: jax.Array, mesh: Mesh,
               n_micro: int | None = None) -> jax.Array:
    """Pipeline-parallel full-sequence logits, == llama.forward output.

    tokens/positions: [B, S]; B must divide into ``n_micro``
    micro-batches (default: the pp degree, the smallest count that
    fills the pipe). Total steps = n_micro + pp - 1.
    """
    pp = mesh.shape["pp"]
    if cfg.loop_steps > 1:
        raise ValueError(
            f"pp_forward runs each stage's layer slab once: a looped stack "
            f"({cfg.name}, loop_steps={cfg.loop_steps}) is not supported")
    if cfg.family != "llama":
        raise ValueError(
            f"pp_forward supports the llama family (got {cfg.family!r}); "
            "MoE layer stacks ([L, E, ...] experts) need EP-aware stages")
    if cfg.n_layers % pp:
        raise ValueError(f"n_layers {cfg.n_layers} % pp {pp} != 0")
    b = tokens.shape[0]
    if n_micro is None:
        n_micro = pp
    if n_micro < 1 or b % n_micro:
        raise ValueError(f"batch {b} % n_micro {n_micro} != 0")
    l_local = cfg.n_layers // pp
    mb = b // n_micro
    attn = make_dense_attn(cfg.sliding_window)

    def stage_fn(params, tokens, positions):
        s = jax.lax.axis_index("pp")
        blocks = params["blocks"]          # local slab [l_local, ...]
        t_micro = tokens.reshape(n_micro, mb, -1)
        p_micro = positions.reshape(n_micro, mb, -1)

        def run_local(x, pos):
            ids = s * l_local + jnp.arange(l_local)

            def body(carry, scanned):
                layer_idx, lp = scanned
                x, _ = llama.decoder_block(cfg, layer_idx, lp, carry,
                                           pos, None, attn)
                return x, None

            x, _ = jax.lax.scan(body, x, (ids, blocks))
            return x

        seq = tokens.shape[-1]
        carry = jnp.zeros((mb, seq, cfg.d_model), cfg.dtype)
        out = jnp.zeros((n_micro, mb, seq, cfg.d_model), cfg.dtype)
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        for t in range(n_micro + pp - 1):
            recv = jax.lax.ppermute(carry, "pp", perm)
            # Stage 0 injects micro-batch t (static index; clamped after
            # the last injection — those steps' stage-0 output is dead).
            inject = llama.embed_tokens(params, cfg,
                                        t_micro[min(t, n_micro - 1)])
            x_in = jnp.where(s == 0, inject, recv)
            # Stage s works on micro-batch t - s (traced index, clipped;
            # out-of-range steps compute masked garbage — SPMD bubbles).
            mb_idx = jnp.clip(t - s, 0, n_micro - 1)
            pos = jax.lax.dynamic_index_in_dim(p_micro, mb_idx, 0,
                                               keepdims=False)
            carry = run_local(x_in, pos)
            # The LAST stage finished micro-batch t - (pp - 1).
            done = t - (pp - 1)
            if done >= 0:
                h = rms_norm(carry, params["final_norm"],
                             cfg.norm_eps, cfg.norm_offset)
                out = out.at[done].set(jnp.where(s == pp - 1, h, 0.0))
        # Only the last stage wrote non-zero hidden states; the combine
        # moves d_model-sized data (NOT logits — unembed happens once,
        # replicated, outside the pipe, so cross-stage traffic stays
        # activation-sized as the module docstring promises).
        return jax.lax.psum(out, "pp").reshape(b, seq, cfg.d_model)

    fn = jax.shard_map(stage_fn, mesh=mesh,
                       in_specs=(stage_specs(params), P(), P()),
                       out_specs=P(), check_vma=False)
    hidden = fn(params, tokens, positions)
    return llama.unembed(params, cfg, hidden)
