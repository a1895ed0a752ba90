"""Laguna (poolside) decoder: full and window attention layers with
different head counts in one stack, a per-head output gate, a leading
dense layer, then routed experts beside a shared one, as pure JAX.

What differs from the other families:

- **Layers differ in kind** (``cfg.layer_types``): a "full" layer has
  ``cfg.n_heads`` query heads, attends over the whole context, and
  turns the first ``partial_rotary_factor`` of each head's dims with
  YaRN frequencies; a "window" layer has ``cfg.window_n_heads``, sees
  the last ``cfg.sliding_window`` keys, and turns every dim with plain
  rope of ``cfg.window_rope_theta``. Wq / Wo / the gate differ in SHAPE
  by kind, so the stack is no single stacked pytree: attention
  parameters are stacked per kind (``params["attn_full"]``,
  ``params["attn_window"]``), feed-forward parameters per form
  (``params["ffn_dense"]``, ``params["ffn_moe"]``), and a layer takes
  its place in each. A layer's place among its kind is also its KV
  slot: each kind has a pool of its own (engine/kv_cache.py), and the
  injected attention is one function a kind (``attn.kinds[kind]``)
  that knows its window statically.
- **One traced body a (kind, form)**: the first period of the kinds'
  pattern (it holds the dense layers) runs as runs of like layers; the
  whole periods after it are one ``lax.scan`` over periods whose body
  holds the period's runs; a partial last period (a depth cut) runs as
  runs again. Twelve layers of (full, window x 3) trace four layer
  bodies, forty-eight would too.
- **Per-head output gate**: head h's attention output is multiplied by
  ``sigmoid(x_normed . w_h)`` before the output projection.
- **Experts** are ``models/deepseek_v3.py``'s ``route`` / ``moe_ffn``
  (this chip's share of an expert-parallel deployment, dropless grouped
  kernels, counters through ``kv.aux``); the routed stacks stay out of
  every scan's xs.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from tpu_inference.config import LAYER_KINDS, ModelConfig
from tpu_inference.models.common import (
    AttentionFn,
    apply_rope,
    dense_causal_attention,
    rms_norm,
    swiglu,
)
from tpu_inference.models.deepseek_v3 import (combines_by_gather, moe_ffn,
                                              n_moe_stats, route)
from tpu_inference.models.quant import qdot

EXPERT_STACKS = ("we_gate", "we_up", "we_down")

# What the shared layers ask a family module for (models/registry.py
# family_fn): the counter vector's length, and which warmed step programs
# sum their expert layers' rows by gather (``combines_by_gather``, imported).
n_aux_stats = n_moe_stats


def _attn_shapes(cfg: ModelConfig, kind: str) -> dict:
    n, d, hd = len(cfg.kind_layers(kind)), cfg.d_model, cfg.head_dim
    h, hkv = cfg.kind_heads(kind), cfg.n_kv_heads
    shapes = {"attn_norm": (n, d), "wq": (n, d, h * hd),
              "wk": (n, d, hkv * hd), "wv": (n, d, hkv * hd),
              "wo": (n, h * hd, d)}
    if cfg.attn_gate == "per_head":
        shapes["w_head_gate"] = (n, d, h)
    return shapes


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree's leaf shapes (bench/references/laguna.py builds the same
    tree from the configuration file)."""
    d, nd = cfg.d_model, cfg.first_k_dense
    ne, e, f = cfg.n_layers - nd, cfg.n_local_experts, cfg.moe_d_ff
    fs = f * cfg.n_shared_experts
    return {
        "embed": (cfg.vocab_size, d),
        "attn_full": _attn_shapes(cfg, "full"),
        "attn_window": _attn_shapes(cfg, "window"),
        "ffn_dense": {"ffn_norm": (nd, d), "w_gate": (nd, d, cfg.d_ff),
                      "w_up": (nd, d, cfg.d_ff), "w_down": (nd, cfg.d_ff, d)},
        "ffn_moe": {"ffn_norm": (ne, d), "w_router": (ne, d, cfg.n_experts),
                    "router_bias": (ne, cfg.n_experts),
                    "ws_gate": (ne, d, fs), "ws_up": (ne, d, fs),
                    "ws_down": (ne, fs, d), "we_gate": (ne, e, d, f),
                    "we_up": (ne, e, d, f), "we_down": (ne, e, f, d)},
        "final_norm": (d,), "lm_head": (d, cfg.vocab_size),
    }


def param_count(cfg: ModelConfig, active: bool = False,
                shapes=None) -> int:
    """Parameters, counted off the leaf shapes (``shapes``: another
    family's tree). With ``active``, those a token position multiplies
    through: a routed expert counts as the share of it one token uses
    (k of all the layer's experts are chosen, so k / n_experts of each
    HELD one on average)."""
    share = cfg.n_experts_per_tok / cfg.n_experts if active else 1.0
    leaves = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg) if shapes is None else shapes,
        is_leaf=lambda x: isinstance(x, tuple))[0]
    return int(sum(math.prod(shape)
                   * (share if path[-1].key in EXPERT_STACKS else 1.0)
                   for path, shape in leaves))


def init_params(cfg: ModelConfig, key: jax.Array, shapes=None) -> dict:
    """Random init (normal, 0.02 std; norm scales 1; the selection bias
    float32, 0.01 std), one jitted draw a leaf. ``shapes``: another
    family's tree of leaf shapes (models/smallthinker.py)."""
    cfg.validate()
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg) if shapes is None else shapes,
        is_leaf=lambda x: isinstance(x, tuple))

    @partial(jax.jit, static_argnames=("shape", "dtype", "std"))
    def draw(k, shape, dtype, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if "norm" in name:
            out.append(jnp.ones(shape, cfg.dtype))
        else:
            bias = name == "router_bias"
            out.append(draw(jax.random.fold_in(key, i), shape,
                            jnp.float32 if bias else cfg.dtype,
                            0.01 if bias else 0.02))
    return jax.tree_util.tree_unflatten(treedef, out)


def attention(cfg: ModelConfig, kind: str, slot, ap: dict, h: jax.Array,
              positions: jax.Array, kv: Any, attn: AttentionFn):
    """h [B, S, D] (normed) -> (gated attention output projected back to
    [B, S, D], kv). ``slot`` is the layer's place among its kind."""
    b, s, _ = h.shape
    nh, hkv, hd = cfg.kind_heads(kind), cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn_" + kind):
        q = qdot(h, ap["wq"]).astype(h.dtype).reshape(b, s, nh, hd)
        k = qdot(h, ap["wk"]).astype(h.dtype).reshape(b, s, hkv, hd)
        v = qdot(h, ap["wv"]).astype(h.dtype).reshape(b, s, hkv, hd)
        if kind in cfg.nope_kinds:
            def rope(x):        # no position signal on this kind
                return x
        elif kind == "window":
            rope = partial(apply_rope, positions=positions,
                           theta=cfg.window_rope_theta)
        else:
            rope = partial(apply_rope, positions=positions,
                           theta=cfg.rope_theta, scaling=cfg.rope_scaling,
                           rotary_dim=int(hd * cfg.partial_rotary_factor))
        o, kv = attn.kinds[kind](slot, rope(q), rope(k), v, kv)
    if cfg.attn_gate == "per_head":
        with jax.named_scope("attn_head_gate"):
            gate = jax.nn.sigmoid(jnp.einsum(
                "bsd,dh->bsh", h, ap["w_head_gate"],
                preferred_element_type=jnp.float32))
            o = (o.astype(jnp.float32) * gate[..., None]).astype(h.dtype)
    return qdot(o.reshape(b, s, nh * hd), ap["wo"]).astype(h.dtype), kv


def layer_runs(cfg: ModelConfig, lo: int, hi: int) -> list:
    """Layers [lo, hi) as runs of like layers: (kind, routed?, first
    layer, count), a run ending where the kind or the form changes."""
    runs = []
    for l in range(lo, hi):
        key = (cfg.layer_types[l], l >= cfg.first_k_dense)
        if runs and tuple(runs[-1][:2]) == key:
            runs[-1][3] += 1
        else:
            runs.append([*key, l, 1])
    return [tuple(r) for r in runs]


def kinds_period(cfg: ModelConfig) -> int:
    """The shortest period of the kinds' pattern that holds every dense
    layer in its first turn."""
    kinds = cfg.layer_types[:cfg.n_layers]
    for p in range(max(1, cfg.first_k_dense), len(kinds)):
        if all(kinds[i] == kinds[i % p] for i in range(len(kinds))):
            return p
    return len(kinds)


def forward_hidden(params: dict, cfg: ModelConfig, tokens: jax.Array,
                   positions: jax.Array, kv: Any,
                   attn: AttentionFn) -> Tuple[jax.Array, Any]:
    """Token ids -> final hidden states. tokens, positions: [B, S]."""
    x = params["embed"][tokens].astype(cfg.dtype)
    nd = cfg.first_k_dense
    moe = dict(params["ffn_moe"])
    experts = tuple(moe.pop(k) for k in EXPERT_STACKS)
    period = kinds_period(cfg)
    whole = cfg.n_layers // period
    slot_of = {kind: {l: i for i, l in enumerate(cfg.kind_layers(kind))}
               for kind in LAYER_KINDS}
    per_turn = {kind: sum(k == kind for k in cfg.layer_types[:period])
                for kind in slot_of}

    def layer(carry, l, kind, routed, slot):
        """Layer ``l`` (a traced index inside a scan) of ``kind``, at
        place ``slot`` among its kind."""
        x, kv = carry
        ap = jax.tree.map(lambda a: a[slot], params["attn_" + kind])
        h = rms_norm(x, ap["attn_norm"], cfg.norm_eps)
        routing = None
        if routed and cfg.router_input == "attn_norm":
            # The architecture routes from the layer's normed INPUT: the
            # experts a token will use are known before its attention
            # runs.
            with jax.named_scope("moe_early_router"):
                routing = route(
                    cfg, {k: moe[k][l - nd]
                          for k in ("w_router", "router_bias") if k in moe},
                    h.reshape(-1, h.shape[-1]))
        a, kv = attention(cfg, kind, slot, ap, h, positions, kv, attn)
        x = x + a
        if not routed:
            fp = jax.tree.map(lambda a: a[l], params["ffn_dense"])
            h = rms_norm(x, fp["ffn_norm"], cfg.norm_eps)
            return (x + swiglu(h, fp["w_gate"], fp["w_up"], fp["w_down"]),
                    kv), None
        fp = jax.tree.map(lambda a: a[l - nd], moe)
        h = rms_norm(x, fp["ffn_norm"], cfg.norm_eps)
        if routing is None:
            y, stats = moe_ffn(cfg, fp, experts, l - nd, h, attn)
        else:
            y, stats = moe_ffn(cfg, fp, experts, l - nd, h, attn,
                               routing=routing)
        return (x + y, kv), stats

    def run(state, seg, turn=0):
        """One run of like layers, moved ``turn`` periods on."""
        carry, total = state
        kind, routed, first, count = seg

        def body(carry, i):
            return layer(carry, first + turn * period + i, kind, routed,
                         slot_of[kind][first] + turn * per_turn[kind] + i)

        if count == 1:
            carry, stats = body(carry, 0)
        else:
            carry, stats = jax.lax.scan(body, carry, jnp.arange(count))
            stats = None if stats is None else stats.sum(0)
        return carry, total if stats is None else total + stats

    state = (x, kv), jnp.zeros((n_moe_stats(cfg),), jnp.int32)
    for seg in layer_runs(cfg, 0, period):
        state = run(state, seg)
    if whole > 1:
        segs = layer_runs(cfg, period, 2 * period)

        def one_turn(state, turn):
            for seg in segs:
                state = run(state, seg, turn)
            return state, None

        state, _ = jax.lax.scan(one_turn, state, jnp.arange(whole - 1))
    for seg in layer_runs(cfg, whole * period, cfg.n_layers):
        state = run(state, seg)
    (x, kv), total = state
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


def make_dense_attn(cfg: ModelConfig) -> AttentionFn:
    """Cache-free causal attention, one function a kind (tests)."""
    def of(window):
        def attn(slot, q, k, v, kv):
            del slot
            return dense_causal_attention(q, k, v,
                                          sliding_window=window), kv
        return attn

    def attn(*_):
        raise TypeError("a stack of mixed kinds calls attn.kinds[kind]")

    attn.kinds = {"full": of(0), "window": of(cfg.sliding_window)}
    return attn
