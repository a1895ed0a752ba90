"""Ling-3.0-flash (inclusionAI, ``bailing_hybrid``): delta-rule
linear-attention layers beside latent attention, over routed experts
limited to groups, as pure JAX.

Every layer l is ``x = x + Mix_l(RMSNorm(x)); x = x + FFN_l(RMSNorm(x))``.
The kind of ``Mix_l`` is ``cfg.layer_types[l]``:

- ``kda`` (Kimi delta attention, arXiv:2510.26692). ``[q~ | k~ | v~] = h
  W_qkv``, each behind a causal depthwise convolution of ``kda_d_conv``
  taps and a SiLU; per head ``q = l2norm(q) d^-0.5``, ``k = l2norm(k)``;
  a decay a CHANNEL ``g = bound * sigmoid(exp(A_log_h) (h W_f +
  dt_bias))`` in (bound, 0) and ``beta = sigmoid(h W_beta)`` a head; the
  state ``S [d, d]`` a head, float32:

      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  and ``y = concat_h(RMSNorm_d(o_h) sigmoid((h W_gate)_h)) W_o``. No rope
  and no page: positions enter through the state alone. The state (the
  convolution's last ``kda_d_conv - 1`` inputs and ``S``) belongs to a
  SEQUENCE and a token ADVANCES it; the model reads and writes it
  through ``attn.state`` (``tail`` / ``put_tail`` / ``conv_step`` /
  ``delta`` / ``lens``: Phi-4's contract, models/sambay.py) and never
  names a pool or a slot: positions behind ``attn.state.lens`` advance
  nothing.
- ``full``: latent attention, ``models/deepseek_v3.latent_attention``
  with ONE query projection (``q_lora_rank`` 0) and a sigmoid gate a
  head, through ``attn`` itself (the latent contract); the layer's slot
  in the latent pool is its place among the full layers.

``FFN_l`` is a SwiGLU of ``d_ff`` for the first ``first_k_dense`` layers
and ``deepseek_v3.moe_ffn`` behind them: sigmoid scores, the top-k taken
within the ``topk_group`` best of ``n_group`` groups, this chip's share of
the experts (one group of an EP8 deployment) beside the shared expert.

Layers are scanned a RUN of like layers at a time (``laguna.layer_runs``):
the stacks are indexed inside the body, by the layer's place among its
kind and among the dense / expert layers.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from tpu_inference.config import ModelConfig
from tpu_inference.kernels import delta_rule
from tpu_inference.models import deepseek_v3
from tpu_inference.models.common import AttentionFn, rms_norm, swiglu
from tpu_inference.models.deepseek_v3 import (  # noqa: F401 — family fns
    attn_pair_dim, combines_by_gather, moe_ffn, n_moe_stats, softmax_scale)
from tpu_inference.models.laguna import layer_runs
from tpu_inference.models.quant import qdot

EXPERT_STACKS = ("we_gate", "we_up", "we_down")
L2_EPS = 1e-6           # inside the square root of q's and k's l2 norm

# What the shared layers ask a family module for (models/registry.py).
n_aux_stats = n_moe_stats


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree's leaf shapes (bench/references/bailing_hybrid.py builds
    the same tree from the configuration file)."""
    d, nd = cfg.d_model, cfg.first_k_dense
    ne, e, f = cfg.n_layers - nd, cfg.n_local_experts, cfg.moe_d_ff
    fs = f * cfg.n_shared_experts
    nk, w = len(cfg.kind_layers("kda")), cfg.kda_width
    h, hd = cfg.kda_n_heads, cfg.kda_head_dim
    full = deepseek_v3._attn_shapes(cfg, len(cfg.kind_layers("full")))
    del full["ffn_norm"]
    return {
        "embed": (cfg.vocab_size, d),
        "kda": {"attn_norm": (nk, d), "w_qkv": (nk, d, 3 * w),
                "conv_w": (nk, cfg.kda_d_conv, 3 * w), "w_f": (nk, d, w),
                "a_log": (nk, h), "dt_bias": (nk, w), "w_beta": (nk, d, h),
                "w_head_gate": (nk, d, h), "o_norm": (nk, hd),
                "w_o": (nk, w, d)},
        "full": full,
        "dense": {"ffn_norm": (nd, d), "w_gate": (nd, d, cfg.d_ff),
                  "w_up": (nd, d, cfg.d_ff), "w_down": (nd, cfg.d_ff, d)},
        "moe": {"ffn_norm": (ne, d), "w_router": (ne, d, cfg.n_experts),
                "router_bias": (ne, cfg.n_experts),
                "ws_gate": (ne, d, fs), "ws_up": (ne, d, fs),
                "ws_down": (ne, fs, d), "we_gate": (ne, e, d, f),
                "we_up": (ne, e, d, f), "we_down": (ne, e, f, d)},
        "final_norm": (d,), "lm_head": (d, cfg.vocab_size),
    }


def _leaves(cfg: ModelConfig):
    return jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))


def param_count(cfg: ModelConfig, active: bool = False) -> int:
    """Parameters, counted off the leaf shapes. With ``active``, those a
    token position multiplies through: a routed expert counts as the
    share of it one token uses (deepseek_v3.param_count)."""
    share = cfg.n_experts_per_tok / cfg.n_experts if active else 1.0
    return int(sum(math.prod(shape)
                   * (share if path[-1].key.startswith("we_") else 1.0)
                   for path, shape in _leaves(cfg)[0]))


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random init, one jitted draw a leaf: matrices normal with std
    0.02, norm gains 1, the selection bias float32 with std 0.01
    (deepseek_v3.init_params says why); the delta rule's own as the
    published KDA layer draws them: ``A_log = ln U(1, 16)`` and a
    ``dt_bias`` whose softplus is log-uniform in [1e-3, 1e-1], both
    float32; the convolution's taps std ``taps ** -0.5``."""
    cfg.validate()
    leaves, treedef = _leaves(cfg)

    @partial(jax.jit, static_argnames=("shape", "dtype", "std"))
    def draw(k, shape, dtype, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    out = []
    for i, (path, shape) in enumerate(leaves):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        if "norm" in name:
            out.append(jnp.ones(shape, cfg.dtype))
        elif name == "a_log":
            out.append(jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                  1.0, 16.0)))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                         * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
        elif name == "router_bias":
            out.append(draw(k, shape, jnp.float32, 0.01))
        elif name == "conv_w":
            out.append(draw(k, shape, cfg.dtype, shape[1] ** -0.5))
        else:
            out.append(draw(k, shape, cfg.dtype, 0.02))
    return jax.tree_util.tree_unflatten(treedef, out)


def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def conv_taps(st, slot, qkv: jax.Array, conv_w: jax.Array, kv: Any):
    """The causal depthwise convolution and its SiLU over qkv [B, S, C]
    behind each lane's tail -> (x [B, S, C] float32, kv with the tails
    moved on), through ``st.tail`` / ``st.put_tail``."""
    b, s, c = qkv.shape
    kc, f32 = conv_w.shape[0], jnp.float32
    seq = jnp.concatenate([st.tail(slot, kv).astype(qkv.dtype), qkv], axis=1)
    conv = jnp.zeros((b, s, c), f32)
    for j in range(kc):
        conv = conv + conv_w[j].astype(f32) * seq[:, j:j + s].astype(f32)
    # The tail a lane leaves: the last kc - 1 inputs among its VALID
    # ones (rows lens .. of [tail | qkv]); none valid: the one it had.
    at = st.lens[:, None] + jnp.arange(kc - 1)[None, :]
    return jax.nn.silu(conv), st.put_tail(
        slot, jnp.take_along_axis(seq, at[..., None], axis=1), kv)


def kda_mix(cfg: ModelConfig, slot, lp: dict, h: jax.Array, kv: Any,
            attn: AttentionFn):
    """A KDA mixer over h [B, S, D] (normed) -> (output [B, S, D], kv).
    Each lane's state comes from and goes back through ``attn.state``."""
    b, s, _ = h.shape
    nh, hd, w = cfg.kda_n_heads, cfg.kda_head_dim, cfg.kda_width
    st = attn.state
    f32 = jnp.float32
    delta_rule.check_bound(cfg.kda_gate_lower_bound)
    with jax.named_scope("kda_conv"):
        qkv = qdot(h, lp["w_qkv"]).astype(h.dtype)             # [B, S, 3W]
        # One token a lane: the state's own step (the engine's advances
        # the tail where it lies); a chunk: the taps over the tails.
        if s == 1:
            x, kv = st.conv_step(slot, qkv, lp["conv_w"], kv)
        else:
            x, kv = conv_taps(st, slot, qkv, lp["conv_w"], kv)
        # A head a row, the form the step leaves x in: q, k and v are
        # then whole rows of it and nothing is laid out again.
        x = x.reshape(b, s, 3 * nh, hd)
        q = _l2norm(x[:, :, :nh]) * hd ** -0.5
        k = _l2norm(x[:, :, nh:2 * nh])
        v = x[:, :, 2 * nh:]
    with jax.named_scope("kda_gate"):
        heads = lambda a: a.reshape(b, s, nh, hd)              # noqa: E731
        rate = jnp.exp(lp["a_log"].astype(f32))[:, None]       # [H, 1]
        g = cfg.kda_gate_lower_bound * jax.nn.sigmoid(
            rate * heads(qdot(h, lp["w_f"]) + lp["dt_bias"].astype(f32)))
        beta = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", h, lp["w_beta"], preferred_element_type=f32))
    with jax.named_scope("kda_chunk" if s > 1 else "kda_step"):
        o, kv = st.delta(slot, q, k, v, g, beta, kv)           # [B,S,H,d]
    with jax.named_scope("kda_out"):
        gate = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", h, lp["w_head_gate"], preferred_element_type=f32))
        o = rms_norm(o, lp["o_norm"], cfg.norm_eps).astype(f32) \
            * gate[..., None]
        return qdot(o.astype(h.dtype).reshape(b, s, w),
                    lp["w_o"]).astype(h.dtype), kv


def forward_hidden(params: dict, cfg: ModelConfig, tokens: jax.Array,
                   positions: jax.Array, kv: Any,
                   attn: AttentionFn) -> Tuple[jax.Array, Any]:
    """Token ids -> final hidden states. tokens, positions: [B, S]."""
    x = params["embed"][tokens].astype(cfg.dtype)
    nd = cfg.first_k_dense
    moe = dict(params["moe"])
    experts = tuple(moe.pop(k) for k in EXPERT_STACKS)
    slot_of = {kind: {l: i for i, l in enumerate(cfg.kind_layers(kind))}
               for kind in ("kda", "full")}
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)   # noqa: E731

    def layer(carry, l, kind, routed, slot):
        """Layer ``l`` (a traced index inside a scan) of ``kind``, at
        place ``slot`` among its kind."""
        x, kv = carry
        mp = at(params[kind], slot)
        h = rms_norm(x, mp["attn_norm"], cfg.norm_eps)
        if kind == "kda":
            a, kv = kda_mix(cfg, slot, mp, h, kv, attn)
        else:
            a, kv = deepseek_v3.latent_attention(cfg, slot, mp, h, positions,
                                                 kv, attn)
        x = x + a.astype(x.dtype)
        if not routed:
            fp = at(params["dense"], l)
            h = rms_norm(x, fp["ffn_norm"], cfg.norm_eps)
            return (x + swiglu(h, fp["w_gate"], fp["w_up"], fp["w_down"]),
                    kv), None
        fp = at(moe, l - nd)
        h = rms_norm(x, fp["ffn_norm"], cfg.norm_eps)
        y, stats = moe_ffn(cfg, fp, experts, l - nd, h, attn)
        return (x + y, kv), stats

    carry, total = (x, kv), jnp.zeros((n_moe_stats(cfg),), jnp.int32)
    for kind, routed, first, count in layer_runs(cfg, 0, cfg.n_layers):
        def body(carry, i, kind=kind, routed=routed, first=first):
            return layer(carry, first + i, kind, routed,
                         slot_of[kind][first] + i)

        if count == 1:
            carry, stats = body(carry, 0)
        else:
            carry, stats = jax.lax.scan(body, carry, jnp.arange(count))
            stats = None if stats is None else stats.sum(0)
        if stats is not None:
            total = total + stats
    x, kv = carry
    aux = getattr(kv, "aux", None)
    if aux is not None:
        kv = kv._replace(aux=aux + total)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), kv


def unembed(params: dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """Hidden states -> f32 logits over this chip's vocabulary slice."""
    return qdot(hidden, params["lm_head"])


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv: Any,
            attn: AttentionFn) -> Tuple[jax.Array, Any]:
    hidden, kv = forward_hidden(params, cfg, tokens, positions, kv, attn)
    return unembed(params, cfg, hidden), kv


class DenseState:
    """``attn.state`` without a cache: every row starts from zeros and
    all S positions are valid; nothing is kept."""

    def __init__(self, cfg: ModelConfig, batch: int, seq_len: int):
        self.cfg, self.batch = cfg, batch
        self.lens = jnp.full((batch,), seq_len, jnp.int32)

    def tail(self, slot, kv):
        c = self.cfg
        return jnp.zeros((self.batch, c.kda_d_conv - 1, 3 * c.kda_width),
                         c.dtype)

    def put_tail(self, slot, tail, kv):
        return kv

    def conv_step(self, slot, qkv, conv_w, kv):
        return conv_taps(self, slot, qkv, conv_w, kv)

    def delta(self, slot, q, k, v, g, beta, kv):
        c = self.cfg
        zeros = jnp.zeros((self.batch, c.kda_n_heads, c.kda_head_dim,
                           c.kda_head_dim), jnp.float32)
        return delta_rule.kda_recurrence(q, k, v, g, beta, zeros,
                                         self.lens)[0], kv


def make_dense_attn(cfg: ModelConfig, batch: int = 1,
                    seq_len: int = 0) -> AttentionFn:
    """Cache-free latent attention and delta-rule state for a forward
    over whole sequences [batch, seq_len] (tests)."""
    inner = deepseek_v3.make_dense_attn(cfg)

    def attn(slot, q, entry, v, kv):
        return inner(slot, q, entry, v, kv)

    attn.state = DenseState(cfg, batch, seq_len)
    attn.valid = jnp.ones((batch, seq_len), bool)
    return attn
