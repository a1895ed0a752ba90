"""DeepSeek-V3 / Kimi-K2 decoder: latent (MLA) attention, a leading dense
layer, then sigmoid-routed experts beside a shared expert, as pure JAX.

What differs from ``models/llama.py``:

- **Latent attention, absorbed.** A token's cache entry is ``[RMSNorm(c_kv)
  | RoPE(k_r)]`` (kv_lora_rank + qk_rope_head_dim values), one for all
  heads. The per-head key / value up-projection ``W_kvb`` never touches
  the cache: its key half is folded into the query (``q~ = q_nope
  W_UK^T``) and its value half applied to the attention's result, the
  weighted sum of latents (``o = (sum p c_kv) W_UV``). So the injected
  attention gets the second shape of ``models/common.py``'s contract:
  ``attn(layer, q [B,S,H,R+Dr], entry [B,S,R+Dr], None, kv) -> ([B,S,H,R],
  kv)``. Prefill uses the same absorbed form over the pages (its own
  chunk and any cached prefix alike), which costs 3.4x the expanded
  form's attention FLOPs on the chunk's own tokens and needs no
  re-expansion of a cached prefix.
- **Two stacks, two scans**: ``params["dense"]`` (first_k_dense layers
  with a SwiGLU of d_ff) and ``params["moe"]`` (router, shared expert,
  held routed experts) are different pytrees.
- **This chip's share of the experts.** The router scores all
  ``n_experts``; the top-k is taken over all of them (over the experts
  of the ``topk_group`` best of ``n_group`` groups where the router is
  limited to groups: a chip of such a deployment holds whole groups)
  and the gates are normalised over the k chosen; only pairs whose expert is held here
  (``ep_rank``'s ``n_experts / ep_size``) are computed, none of them
  dropped (kernels/moe_experts.py); the shared expert runs in full. The
  routed stacks ``we_*`` stay OUT of the scan's xs: the grouped kernels
  address (layer, expert) in the stacked array themselves.
- Expert-routing counts ride ``kv.aux`` (an int32 vector in the KV
  state) out of the graph: ``MOE_STATS`` names its slots.
- **Several residual streams** where ``cfg.hc_mult`` > 1
  (models/hyper_connections.py): the scans carry ``[B, S, n D]``, every
  sublayer reads a per-token mix of the n streams and writes back through
  a doubly stochastic matrix. The streams live inside this forward call:
  a token's latent entry is made from ``RMSNorm(H_pre X)`` as it is from
  ``RMSNorm(x)`` with one stream, and no cache entry knows of them.

Rope is the half-split pairing of ``apply_rope`` on the rope dims only,
YaRN frequencies; the softmax scale carries YaRN's ``mscale_all_dim``
term squared.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from tpu_inference.config import ModelConfig, YarnScaling
from tpu_inference.kernels import moe_experts
from tpu_inference.models import hyper_connections as mhc
from tpu_inference.models.common import (
    AttentionFn,
    apply_rope,
    rms_norm,
    swiglu,
    yarn_mscale,
)
from tpu_inference.models.quant import qdot, split_heads

# kv.aux slots, then one per held expert (its routed pairs).
MOE_STATS = ("tokens", "local_pairs", "computed_pairs", "busiest_pairs",
             "distinct_experts", "decode_layers")
# Behind the per-expert slots, for a preset that asks
# (``cfg.moe_row_stats``): the rows the grouped kernels ran, whole
# tiles, beside the real pairs among them (``computed_pairs``): what
# kernels/moe_experts.tile_rows costs.
ROW_STATS = ("tile_rows",)
# Behind those, where the router is limited to groups (``cfg.n_group`` >
# 1): the routed token positions one of whose chosen groups has experts
# held here. Over ``tokens`` it is the share of tokens the expert
# exchange would send this chip at all.
GROUP_STATS = ("group_reach_tokens",)


def n_moe_stats(cfg: ModelConfig) -> int:
    return (len(MOE_STATS) + cfg.n_local_experts
            + (len(ROW_STATS) if cfg.moe_row_stats else 0)
            + (len(GROUP_STATS) if cfg.n_group > 1 else 0))


# What the shared layers ask a family module for, where it differs from
# the dense default (models/registry.py family_fn): the length of the
# int32 counter vector beside the KV pool (the routing counts, then
# ``mhc.MHC_STATS`` where the model carries several streams), the slots
# of it that fold by max and not by sum, the parameter count, and the
# width an attention pair costs 4 x n_heads x of in FLOPs.
def n_aux_stats(cfg: ModelConfig) -> int:
    return n_moe_stats(cfg) + (len(mhc.MHC_STATS) if cfg.hc_mult > 1 else 0)


def aux_max_slots(cfg: ModelConfig) -> tuple:
    return (n_aux_stats(cfg) - 1,) if cfg.hc_mult > 1 else ()


def param_count(cfg: ModelConfig, active: bool = False) -> int:
    """Parameters, counted off the leaf shapes. With ``active``, those a
    token position multiplies through: a routed expert counts as the
    share of it one token uses (k of all the layer's experts are chosen,
    so k / n_experts of each HELD one on average)."""
    shapes = param_shapes(cfg)
    share = cfg.n_experts_per_tok / cfg.n_experts if active else 1.0
    total = 0.0
    for group in (shapes, shapes["dense"], shapes["moe"]):
        for name, shape in group.items():
            if isinstance(shape, tuple):
                total += math.prod(shape) * (
                    share if name.startswith("we_") else 1.0)
    return int(total)


def attn_pair_dim(cfg: ModelConfig) -> int:
    """Absorbed attention scores the latent entry and sums the latent
    rank per head: (entry + rank) / 2 stands where head_dim does."""
    return (cfg.latent_dim + cfg.kv_lora_rank) // 2


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if isinstance(cfg.rope_scaling, YarnScaling):
        scale *= yarn_mscale(cfg.rope_scaling.factor,
                             cfg.rope_scaling.mscale_all_dim) ** 2
    return scale


def _attn_shapes(cfg: ModelConfig, n: int) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    hq = h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    # No query compression (``q_lora_rank`` 0): one projection ``wq``.
    query = ({"wq_a": (n, d, cfg.q_lora_rank), "q_norm": (n, cfg.q_lora_rank),
              "wq_b": (n, cfg.q_lora_rank, hq)} if cfg.q_lora_rank
             else {"wq": (n, d, hq)})
    if cfg.attn_gate == "per_head":
        query["w_head_gate"] = (n, d, h)
    return {
        "attn_norm": (n, d), **query,
        "wkv_a": (n, d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_norm": (n, cfg.kv_lora_rank),
        "wkv_b": (n, cfg.kv_lora_rank,
                  h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (n, h * cfg.v_head_dim, d), "ffn_norm": (n, d),
    }


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree's leaf shapes (bench/references/deepseek_v3.py builds the
    same tree from the configuration file)."""
    d, nd = cfg.d_model, cfg.first_k_dense
    ne, e, f = cfg.n_layers - nd, cfg.n_local_experts, cfg.moe_d_ff
    fs = f * cfg.n_shared_experts
    dense = dict(_attn_shapes(cfg, nd), **mhc.shapes(cfg, nd),
                 w_gate=(nd, d, cfg.d_ff),
                 w_up=(nd, d, cfg.d_ff), w_down=(nd, cfg.d_ff, d))
    moe = dict(_attn_shapes(cfg, ne), **mhc.shapes(cfg, ne),
               w_router=(ne, d, cfg.n_experts),
               router_bias=(ne, cfg.n_experts),
               ws_gate=(ne, d, fs), ws_up=(ne, d, fs), ws_down=(ne, fs, d),
               we_gate=(ne, e, d, f), we_up=(ne, e, d, f),
               we_down=(ne, e, f, d))
    return {"embed": (cfg.vocab_size, d), "dense": dense, "moe": moe,
            "final_norm": (d,), "lm_head": (d, cfg.vocab_size)}


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random init (normal, 0.02 std; norm scales 1; the selection bias
    float32, 0.01 std: the top scores of a few hundred sigmoids lie
    ~0.003 apart, so a larger bias would pick the experts by itself; a
    hyper-connection's float32 leaves as ``mhc.init_float32`` says). One
    jitted draw a leaf: the float32 normals of a 2 GB expert stack never
    exist beside it."""
    cfg.validate()
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    @partial(jax.jit, static_argnames=("shape", "dtype", "std"))
    def draw(k, shape, dtype, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if "norm" in name:
            out.append(jnp.ones(shape, cfg.dtype))
        elif name in mhc.FLOAT32_LEAVES:
            out.append(mhc.init_float32(name, jax.random.fold_in(key, i),
                                        shape))
        else:
            bias = name == "router_bias"
            out.append(draw(jax.random.fold_in(key, i), shape,
                            jnp.float32 if bias else cfg.dtype,
                            0.01 if bias else 0.02))
    return jax.tree_util.tree_unflatten(treedef, out)


def latent_attention(cfg: ModelConfig, layer_idx, lp: dict, h: jax.Array,
                     positions: jax.Array, kv: Any, attn: AttentionFn):
    """h [B, S, D] (normed) -> (attention output [B, S, D] f32, kv)."""
    b, s, _ = h.shape
    nh, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla_q_proj"):
        if cfg.q_lora_rank:
            cq = rms_norm(qdot(h, lp["wq_a"]).astype(h.dtype), lp["q_norm"],
                          cfg.norm_eps)
            q = qdot(cq, lp["wq_b"])
        else:
            q = qdot(h, lp["wq"])
        q = q.astype(h.dtype).reshape(b, s, nh, dn + dr)
        q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta,
                            cfg.rope_scaling)
    with jax.named_scope("mla_kv_proj"):
        ckv = qdot(h, lp["wkv_a"]).astype(h.dtype)               # [B,S,R+Dr]
        c = rms_norm(ckv[..., :r], lp["kv_norm"], cfg.norm_eps)
        k_rope = apply_rope(ckv[..., None, r:], positions, cfg.rope_theta,
                            cfg.rope_scaling)[:, :, 0]
        entry = jnp.concatenate([c, k_rope], axis=-1)
    w_kvb = split_heads(lp["wkv_b"], nh)              # bf16 [r, nh, dn+dv]
    with jax.named_scope("mla_absorb_q"):
        q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :dn], w_kvb[..., :dn],
                           preferred_element_type=jnp.float32
                           ).astype(h.dtype)
    o_lat, kv = attn(layer_idx, jnp.concatenate([q_lat, q_rope], axis=-1),
                     entry, None, kv)                             # [B,S,H,R]
    with jax.named_scope("mla_out_proj"):
        o = jnp.einsum("bshr,rhv->bshv", o_lat, w_kvb[..., dn:],
                       preferred_element_type=jnp.float32).astype(h.dtype)
        if cfg.attn_gate == "per_head":
            # Head h's output times sigmoid(x_normed . w_h) (gated
            # attention, head-wise: models/laguna.py has the same gate).
            gate = jax.nn.sigmoid(jnp.einsum(
                "bsd,dh->bsh", h, lp["w_head_gate"],
                preferred_element_type=jnp.float32))
            o = (o.astype(jnp.float32) * gate[..., None]).astype(h.dtype)
        return qdot(o.reshape(b, s, nh * dv), lp["wo"]), kv


def group_limit(cfg: ModelConfig, ranked: jax.Array):
    """ranked [T, n_experts] (the scores the top-k ranks by, selection
    bias in) -> (ranked with the experts of every group that does not
    stay at -inf, kept [T, n_group] bool). The experts lie in
    ``cfg.n_group`` groups of consecutive experts; a group's score is the
    sum of its two largest ranked scores and the best ``cfg.topk_group``
    groups stay."""
    t = ranked.shape[0]
    grouped = ranked.reshape(t, cfg.n_group, -1)
    best2, _ = jax.lax.top_k(grouped, min(2, grouped.shape[-1]))
    _, stay = jax.lax.top_k(best2.sum(-1), cfg.topk_group)      # [T, tg]
    kept = jnp.any(stay[:, :, None] == jnp.arange(cfg.n_group)[None, None],
                   axis=1)                                       # [T, G]
    return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, -1), kept


def router_scores(cfg: ModelConfig, lp: dict, x2: jax.Array):
    """x2 [T, D] -> (scores [T, n_experts] f32, the same with the
    selection bias in: what the top-k ranks by). The router runs in
    float32 at the highest matmul precision: a pair flips expert on a
    last-bit difference, and 2.75M weights are cheap."""
    logits = jnp.dot(x2.astype(jnp.float32),
                     lp["w_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if cfg.moe_scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    # (A router with no selection bias has no such leaf: the top-k is
    # taken by the scores themselves.)
    bias = lp.get("router_bias")
    return scores, scores if bias is None else scores + bias[None, :]


def route(cfg: ModelConfig, lp: dict, x2: jax.Array):
    """x2 [T, D] -> (top_idx [T, k] over all n_experts, gates [T, k] f32).
    Where the router is limited to groups (``cfg.n_group`` > 1:
    ``group_limit``) the top-k is taken among the experts of the groups
    that stay; with one group every expert is ranked at once. The gates
    are the chosen experts' scores WITHOUT the selection bias either
    way, normalised over the chosen."""
    scores, ranked = router_scores(cfg, lp, x2)
    if cfg.n_group > 1:
        with jax.named_scope("moe_group_select"):
            ranked, _ = group_limit(cfg, ranked)
    _, top_idx = jax.lax.top_k(ranked, cfg.n_experts_per_tok)
    gates = jnp.take_along_axis(scores, top_idx, axis=1)
    if cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-20)
    return top_idx, gates * cfg.routed_scaling_factor


def expected_local_pairs(cfg: ModelConfig, rows: int) -> float:
    """Pairs of ``rows`` tokens that land on this chip's experts if the
    router spreads them evenly: what sizes the expert layer's layout."""
    return rows * cfg.n_experts_per_tok * cfg.n_local_experts / cfg.n_experts


def combines_by_gather(cfg: ModelConfig, rows: int) -> bool:
    """Whether the expert layer of a step program that runs ``rows``
    token rows sums each round's rows by gather (kernels/moe_experts.py,
    point 2: ``rows x k`` is no more than ``SCATTERED_ROW_COST`` x a
    round's rows; the rounds' loop stays wherever several are laid out):
    the predicate ``moe_ffn``'s layout is built by, from the same ints."""
    return moe_experts.combines_by_gather(
        rows, cfg.n_experts_per_tok, cfg.n_local_experts,
        expected_local_pairs(cfg, rows))


def moe_ffn(cfg: ModelConfig, lp: dict, experts: tuple, moe_layer,
            h: jax.Array, attn: AttentionFn, routing=None):
    """h [B, S, D] -> (shared expert + this chip's routed part, stats).
    ``routing``: ``route``'s result where the architecture routes from
    another input than the experts' (``cfg.router_input``; the caller
    computed it there), else h is routed here. A layer with no shared
    expert (``lp`` has no ``ws_*``) is its routed part alone."""
    b, s, d = h.shape
    x2 = h.reshape(b * s, d)
    n_held = cfg.n_local_experts
    # Rows that hold a token (the engine's attention knows: a padded
    # bucket's tail and a lane that is not decoding route nowhere, so
    # they cost no expert read and count in no statistic).
    valid = getattr(attn, "valid", None)
    n_tokens = b * s if valid is None else jnp.sum(valid)
    with jax.named_scope("moe_router"):
        top_idx, gates = route(cfg, lp, x2) if routing is None else routing
        first = cfg.ep_rank * n_held
        held = (top_idx >= first) & (top_idx < first + n_held)
        if valid is not None:
            held &= valid.reshape(b * s, 1)
        top_local = jnp.where(held, top_idx - first, n_held)
        groups = moe_experts.group_pairs(top_local, gates, n_held,
                                         expected_local_pairs(cfg, b * s))
    routed, computed = moe_experts.grouped_experts(
        x2, groups, *experts, moe_layer,
        pallas=getattr(attn, "pallas", False),
        interpret=getattr(attn, "interpret", False), act=cfg.moe_act)
    shared = None
    if "ws_gate" in lp:
        with jax.named_scope("moe_shared_expert"):
            shared = swiglu(x2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    decode = jnp.int32(s == 1)
    stats = [
        jnp.stack([jnp.int32(n_tokens), jnp.sum(groups.counts), computed,
                   jnp.max(groups.counts),
                   decode * jnp.sum(groups.counts > 0), decode]),
        groups.counts]
    if cfg.moe_row_stats:
        stats.append((groups.n_tiles * groups.tm).reshape(1))
    if cfg.n_group > 1:
        # Tokens one of whose chosen groups has experts held here (the
        # router's scores a second time in the trace, once in the
        # program: the compiler merges the two).
        size = cfg.n_experts // cfg.n_group
        lo, hi = first // size, (first + n_held - 1) // size + 1
        with jax.named_scope("moe_group_select"):
            _, kept = group_limit(cfg, router_scores(cfg, lp, x2)[1])
        reach = jnp.any(kept[:, lo:hi], axis=1)
        if valid is not None:
            reach &= valid.reshape(b * s)
        stats.append(jnp.sum(reach).reshape(1))
    stats = jnp.concatenate(stats).astype(jnp.int32)
    if shared is not None:
        routed = routed + shared.astype(jnp.float32)
    return routed.astype(h.dtype).reshape(b, s, d), stats


def _block(cfg: ModelConfig, layer_idx, lp: dict, x: jax.Array,
           positions: jax.Array, kv: Any, attn: AttentionFn, ffn):
    """One layer on the residual x -> (x, kv, the FFN's stats, the
    hyper-connections' largest row / column sum error or None)."""
    if cfg.hc_mult > 1:
        return _block_streams(cfg, layer_idx, lp, x, positions, kv, attn,
                              ffn)
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    a, kv = latent_attention(cfg, layer_idx, lp, h, positions, kv, attn)
    x = x + a.astype(x.dtype)
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    y, stats = ffn(lp, h)
    return x + y, kv, stats, None


def _block_streams(cfg: ModelConfig, layer_idx, lp: dict, x: jax.Array,
                   positions: jax.Array, kv: Any, attn: AttentionFn, ffn):
    """``_block`` on n residual streams x [B, S, n D]: each sublayer
    reads ``H_pre X`` and writes ``H_res X + H_post^T y``."""
    valid = getattr(attn, "valid", None)
    coef, err_a = mhc.coefficients(cfg, lp, "attn", x, valid=valid)
    h = rms_norm(mhc.pre_mix(cfg, coef, x), lp["attn_norm"], cfg.norm_eps)
    a, kv = latent_attention(cfg, layer_idx, lp, h, positions, kv, attn)
    x = mhc.post_mix(cfg, coef, x, a)
    coef, err_f = mhc.coefficients(cfg, lp, "ffn", x, valid=valid)
    h = rms_norm(mhc.pre_mix(cfg, coef, x), lp["ffn_norm"], cfg.norm_eps)
    y, stats = ffn(lp, h)
    return (mhc.post_mix(cfg, coef, x, y), kv, stats,
            jnp.maximum(err_a, err_f))


def forward_hidden(params: dict, cfg: ModelConfig, tokens: jax.Array,
                   positions: jax.Array, kv: Any,
                   attn: AttentionFn) -> Tuple[jax.Array, Any]:
    """Token ids -> final hidden states. tokens, positions: [B, S]."""
    x = params["embed"][tokens].astype(cfg.dtype)
    nd = cfg.first_k_dense
    streams = cfg.hc_mult > 1
    if streams:
        x = mhc.fan_out(cfg, x)

    def dense_body(carry, scanned):
        x, kv = carry
        layer_idx, lp = scanned
        x, kv, _, err = _block(
            cfg, layer_idx, lp, x, positions, kv, attn,
            lambda lp, h: (swiglu(h, lp["w_gate"], lp["w_up"],
                                  lp["w_down"]), None))
        return (x, kv), err

    (x, kv), err_d = jax.lax.scan(dense_body, (x, kv),
                                  (jnp.arange(nd), params["dense"]))

    moe = dict(params["moe"])
    experts = tuple(moe.pop(k) for k in ("we_gate", "we_up", "we_down"))

    def moe_body(carry, scanned):
        x, kv = carry
        i, lp = scanned
        x, kv, stats, err = _block(
            cfg, nd + i, lp, x, positions, kv, attn,
            lambda lp, h: moe_ffn(cfg, lp, experts, i, h, attn))
        return (x, kv), (stats, err)

    (x, kv), (stats, err_m) = jax.lax.scan(
        moe_body, (x, kv), (jnp.arange(cfg.n_layers - nd), moe))
    aux = getattr(kv, "aux", None)
    if aux is not None and not streams:
        kv = kv._replace(aux=aux + stats.sum(0))
    elif aux is not None:
        # mhc.MHC_STATS behind the routing counts: the mixes add up, the
        # sum error is the largest seen since the counts last left.
        valid = getattr(attn, "valid", None)
        mixes = len(mhc.SUBLAYERS) * cfg.n_layers * (
            tokens.size if valid is None else jnp.sum(valid))
        ppm = jnp.round(1e6 * jnp.max(jnp.concatenate([err_d, err_m])))
        kv = kv._replace(aux=jnp.concatenate([
            aux[:-1] + jnp.append(stats.sum(0), mixes).astype(jnp.int32),
            jnp.maximum(aux[-1:], ppm.astype(jnp.int32)[None])]))
    if streams:
        x = mhc.read_out(cfg, x)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), kv


def unembed(params: dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """Hidden states -> f32 logits over this chip's vocabulary slice."""
    return qdot(hidden, params["lm_head"])


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv: Any,
            attn: AttentionFn) -> Tuple[jax.Array, Any]:
    hidden, kv = forward_hidden(params, cfg, tokens, positions, kv, attn)
    return unembed(params, cfg, hidden), kv


def make_dense_attn(cfg: ModelConfig) -> AttentionFn:
    """Cache-free causal attention in the latent contract (tests)."""
    scale, r = softmax_scale(cfg), cfg.kv_lora_rank

    def attn(layer_idx, q, entry, v, kv):
        del layer_idx, v
        s = jnp.einsum("bshd,btd->bhst", q.astype(jnp.float32),
                       entry.astype(jnp.float32)) * scale
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None, None], s, -1e30)
        out = jnp.einsum("bhst,btr->bshr", jax.nn.softmax(s, axis=-1),
                         entry[..., :r].astype(jnp.float32))
        return out.astype(q.dtype), kv

    return attn
