"""Plain float32 reference of the DeepSeek-V3 / Kimi-K2 decoder as ONE
chip's share of an expert-parallel deployment. Straightforward
``jax.numpy``: the whole stream at once, no cache, no kernel, no batching,
EXPANDED (not absorbed) attention, the routed experts as a plain loop over
the experts held here, every matmul in float32 at "highest" precision.

The equations (``h = RMSNorm(x)``, eps ``rms_norm_eps``, everywhere):

* Latent attention. ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` -> H heads
  of (nope | rope). ``[c_kv | k_r] = h W_kva``; ``c_kv = RMSNorm(c_kv)``;
  ``k_r = RoPE(k_r)`` is ONE head shared by all query heads; ``q_r =
  RoPE(q_r)``. ``[k_nope_i | v_i] = c_kv W_kvb`` per head i.
  ``score_i = s (q_nope_i . k_nope_i + q_r_i . k_r)``, causal softmax,
  ``o_i = sum p v_i``, ``out = concat_i(o_i) W_o``.
  ``s = (nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``.
* RoPE on the rope dims only, YaRN frequencies: channel j of D/2 keeps
  ``theta^(-2j/D)`` below a ramp and runs ``factor`` x slower above it;
  the ramp spans floor(f(beta_fast)) .. ceil(f(beta_slow)), ``f(t) = D
  ln(original / (2 pi t)) / (2 ln theta)``, widened by 0.001 when empty;
  cos / sin scaled by ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
  mscale_all_dim)`` (1 here).
* Layers 0 .. first_k_dense - 1: SwiGLU of ``intermediate_size``.
* Expert layers: ``sc = sigmoid(h W_r)`` (float32, all published
  experts); the top k of ``sc + b`` (``b`` the selection bias; one group,
  so no group stage); ``g_e = routed_scaling_factor sc_e / sum_selected
  sc``; ``y = sum_{e in top-k} g_e E_e(h) + S(h)``, ``E_e(h) = (silu(h
  W_g,e) * (h W_u,e)) W_d,e``, ``S`` the shared expert.

Departures, each stated in the configuration file too:

* **This chip's share.** The sum over experts runs over ``top-k ∩ held``
  (the rank's ``n_routed_experts`` of the published count); ``g_e`` is
  still normalised over all k chosen; ``S(h)`` in full. What the absent
  experts would add is left out, and that partial result goes on to the
  next layer. The vocabulary is rows / columns 0 .. vocab_size - 1.
* **Rope pairing** is half-split ``(x_j, x_{j + D/2})``. The published
  code pairs ``(x_2j, x_2j+1)``: the same function under a fixed
  permutation of the rope columns of W_qb and W_kva, which random weights
  do not tell apart.
* Weights are made here from the seed: normal, std 0.02, every matrix
  alike; norm scales 1 + 0.1 n; the selection bias float32. For the
  experts NOT held here the bias is 0.01 n (the top scores of 384
  sigmoids lie ~0.003 apart, so this already moves the busiest expert to
  ~2x the mean load). For the experts held here it DECIDES: per layer,
  HELD_CHOSEN of them (drawn from the seed) get +2 and are among every
  token's k, the others get -2 and never are (HELD_MARGIN says why). The
  other k - HELD_CHOSEN places, the sum the gates are normalised over and
  every gate stay the token's own. Nothing the program made enters.

Sizes come from the configuration FILE alone. The weight tree has the
layout the program's engine accepts through ``InferenceEngine(params=)``
(stacked layers per kind, [in, out] matrices): that layout is the
interface between the two. ``reference_weights`` hands the bfloat16 tree
back as it is and ``logits`` widens ONE layer at a time: a float32 copy
of a 3-layer cut at the published widths is 8.6 GB beside the 4.3 GB
parity.py keeps, and the values are the same.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# What the configuration's control ("int8 weight-only") covers: every
# projection but the absorbed W_kvb, the router and the embedding.
QUANTISED = ("wq_a", "wq_b", "wkv_a", "wo", "w_gate", "w_up", "w_down",
             "ws_gate", "ws_up", "ws_down", "we_gate", "we_up", "we_down",
             "lm_head")
BLOCK = 512          # queries a block of attention, tokens a block of FFN
# The 8th and 9th of 384 sigmoid scores lie ~0.003 apart, and bfloat16
# activations move a score by about a tenth of that: whatever the draw,
# 1-3% of the CHOSEN (token, expert) pairs lie within that of the
# threshold (the normal's hazard rate at it), so one (token, layer) pair
# in fifty sends a held expert's place to another expert in bfloat16 and
# not in float32. A whole expert's output then differs: rms 0.11 of the
# logits' spread at that position on 3 seeds of 10 (v5e, PR 26), above the
# int8 control's 0.047, and as much as a grouped-expert path that lost an
# expert. parity.py judges the WORST position, so it cannot tell the two
# apart. So the held experts' membership is taken out of the margin: the
# selection bias puts HELD_CHOSEN of them (per layer, from the seed) 2
# above every score and the rest 2 below (scores lie in 0..1). Every
# position then carries HELD_CHOSEN routed experts at full weight (std 0.02
# like every matrix), with gates that are the token's own; a routed part
# that is zero, addressed to another expert or layer, or normalised
# wrongly moves every compared position. Token-dependent membership at
# published widths is chip_smoke.py's routed-expert check.
HELD_CHOSEN = 3
HELD_MARGIN = 2.0


def sizes(model: dict, layers: int) -> dict:
    """Published config.json keys (+ the file's ``published`` and
    ``deployment``) -> the sizes this file uses. ``layers`` counts the
    leading dense layer(s)."""
    rs = model["rope_scaling"]
    dep = model["deployment"]
    held = model["n_routed_experts"]
    return {
        "vocab": model["vocab_size"], "d": model["hidden_size"],
        "layers": layers, "dense_layers": model["first_k_dense_replace"],
        "heads": model["num_attention_heads"],
        "q_rank": model["q_lora_rank"], "kv_rank": model["kv_lora_rank"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "v": model["v_head_dim"], "ff": model["intermediate_size"],
        "moe_ff": model["moe_intermediate_size"],
        "shared": model["n_shared_experts"],
        "experts": model["published"]["n_routed_experts"], "held": held,
        "first_held": dep["rank"] * held,
        "top_k": model["num_experts_per_tok"],
        "route_scale": float(model["routed_scaling_factor"]),
        "norm_topk": bool(model["norm_topk_prob"]),
        "theta": float(model["rope_theta"]),
        "yarn_factor": float(rs["factor"]),
        "yarn_original": int(rs["original_max_position_embeddings"]),
        "beta_fast": float(rs["beta_fast"]),
        "beta_slow": float(rs["beta_slow"]),
        "mscale": float(rs["mscale"]),
        "mscale_all_dim": float(rs["mscale_all_dim"]),
        "eps": float(model["rms_norm_eps"]),
    }


def key_of(seed: int):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _shapes(sz: dict) -> dict:
    d, h = sz["d"], sz["heads"]
    nd = min(sz["dense_layers"], sz["layers"])
    ne = sz["layers"] - nd

    def attn(n):
        return {"attn_norm": (n, d), "wq_a": (n, d, sz["q_rank"]),
                "q_norm": (n, sz["q_rank"]),
                "wq_b": (n, sz["q_rank"], h * (sz["nope"] + sz["rope"])),
                "wkv_a": (n, d, sz["kv_rank"] + sz["rope"]),
                "kv_norm": (n, sz["kv_rank"]),
                "wkv_b": (n, sz["kv_rank"], h * (sz["nope"] + sz["v"])),
                "wo": (n, h * sz["v"], d), "ffn_norm": (n, d)}

    f, fs, e = sz["moe_ff"], sz["moe_ff"] * sz["shared"], sz["held"]
    return {
        "embed": (sz["vocab"], d),
        "dense": dict(attn(nd), w_gate=(nd, d, sz["ff"]),
                      w_up=(nd, d, sz["ff"]), w_down=(nd, sz["ff"], d)),
        "moe": dict(attn(ne), w_router=(ne, d, sz["experts"]),
                    router_bias=(ne, sz["experts"]),
                    ws_gate=(ne, d, fs), ws_up=(ne, d, fs),
                    ws_down=(ne, fs, d), we_gate=(ne, e, d, f),
                    we_up=(ne, e, d, f), we_down=(ne, e, f, d)),
        "final_norm": (d,), "lm_head": (d, sz["vocab"]),
    }


def _selection_bias(key, sz: dict, layers: int):
    """[layers, experts] float32: 0.01 n, and +-HELD_MARGIN on the held."""
    k_n, k_held = jax.random.split(key)
    held, first = sz["held"], sz["first_held"]
    chosen = min(HELD_CHOSEN, sz["top_k"] // 2, held)
    order = jax.vmap(lambda k: jax.random.permutation(k, held))(
        jax.random.split(k_held, layers))
    bias = 0.01 * jax.random.normal(k_n, (layers, sz["experts"]), jnp.float32)
    return bias.at[:, first:first + held].set(
        jnp.where(order < chosen, HELD_MARGIN, -HELD_MARGIN))


def make_weights(sz: dict, seed: int) -> dict:
    """bfloat16 weights from the seed, on the device, one jitted call a
    leaf (the whole tree in one program would hold every float32 draw at
    once)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(sz), is_leaf=lambda x: isinstance(x, tuple))
    key = key_of(seed)

    @functools.partial(jax.jit, static_argnames=("shape", "norm"))
    def draw(k, shape, norm):
        n = jax.random.normal(k, shape, jnp.float32)
        return ((1.0 + 0.1 * n) if norm else 0.02 * n).astype(jnp.bfloat16)

    out = []
    for i, (path, shape) in enumerate(leaves):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        out.append(_selection_bias(k, sz, shape[0]) if name == "router_bias"
                   else draw(k, shape, "norm" in name))
    return jax.tree_util.tree_unflatten(treedef, out)


def int8_per_channel(w):
    """Symmetric int8 with one scale per output channel (scale = max|w| /
    127 over the contraction axis), then back to the input's dtype."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2, keepdims=True),
                        1e-8) / 127.0
    return (jnp.clip(jnp.round(wf / scale), -127, 127) * scale)


def reference_weights(weights: dict, quant: str) -> dict:
    """The weights as the configuration serves them: the bfloat16 tree
    itself for ``quant`` "none" (``logits`` widens a layer at a time; the
    values are those of a float32 copy), float32 int8-rounded copies of
    the QUANTISED leaves for "int8"."""
    if quant in (None, "none"):
        return weights

    def leaf(path, w):
        if quant == "int8" and path[-1].key in QUANTISED:
            return jax.jit(int8_per_channel)(w)
        return w

    return jax.tree_util.tree_map_with_path(leaf, weights)


# ------------------------------------------------------------------ forward
def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def _yarn_inv_freq(sz: dict) -> np.ndarray:
    dim, theta = sz["rope"], sz["theta"]
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_to_dim(turns):
        return (dim * math.log(sz["yarn_original"] / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_to_dim(sz["beta_fast"])), 0)
    high = min(math.ceil(turns_to_dim(sz["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inv / sz["yarn_factor"] * ramp + inv * (1.0 - ramp)
            ).astype(np.float32)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, pos, sz):
    """x [S, H, D], pos [S]: rotate pairs (j, j + D/2)."""
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(_yarn_inv_freq(sz))
    m = (_mscale(sz["yarn_factor"], sz["mscale"])
         / _mscale(sz["yarn_factor"], sz["mscale_all_dim"]))
    cos, sin = (m * jnp.cos(ang))[:, None, :], (m * jnp.sin(ang))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q_nope, q_rope, k_nope, k_rope, v, scale):
    """q_* [S, H, .], k_nope / v [S, H, .], k_rope [S, 1, Dr] -> [S, H, Dv],
    causal, a block of queries at a time."""
    s = q_nope.shape[0]
    kpos = jnp.arange(s)

    def one(args):
        qn, qr, start = args
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
              + jnp.einsum("qhd,kd->hqk", qr, k_rope[:, 0])) * scale
        mask = kpos[None, :] <= (start + jnp.arange(BLOCK))[:, None]
        sc = jnp.where(mask[None], sc, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    nb = s // BLOCK
    out = jax.lax.map(one, (q_nope.reshape(nb, BLOCK, *q_nope.shape[1:]),
                            q_rope.reshape(nb, BLOCK, *q_rope.shape[1:]),
                            jnp.arange(nb) * BLOCK))
    return out.reshape(s, *out.shape[2:])


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _layer(x, lp, *, sz, moe: bool):
    """One decoder layer on the whole stream x [S, D]; ``lp`` one layer's
    weights (any dtype; widened here)."""
    lp = {k: w if k.startswith("we_") else w.astype(jnp.float32)
          for k, w in lp.items()}
    s = x.shape[0]
    pos = jnp.arange(s)
    nh, dn, dr, dv = sz["heads"], sz["nope"], sz["rope"], sz["v"]
    r = sz["kv_rank"]
    scale = ((dn + dr) ** -0.5
             * _mscale(sz["yarn_factor"], sz["mscale_all_dim"]) ** 2)

    h = _rms(x, lp["attn_norm"], sz["eps"])
    q = (_rms(h @ lp["wq_a"], lp["q_norm"], sz["eps"]) @ lp["wq_b"]
         ).reshape(s, nh, dn + dr)
    ckv = h @ lp["wkv_a"]
    c = _rms(ckv[:, :r], lp["kv_norm"], sz["eps"])
    k_rope = _rope(ckv[:, None, r:], pos, sz)                  # [S, 1, Dr]
    kvb = (c @ lp["wkv_b"]).reshape(s, nh, dn + dv)
    o = _attention(q[..., :dn], _rope(q[..., dn:], pos, sz), kvb[..., :dn],
                   k_rope, kvb[..., dn:], scale)
    x = x + o.reshape(s, nh * dv) @ lp["wo"]

    h = _rms(x, lp["ffn_norm"], sz["eps"])
    if not moe:
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    sc = jax.nn.sigmoid(h @ lp["w_router"])                    # [S, E]
    _, top = jax.lax.top_k(sc + lp["router_bias"][None, :], sz["top_k"])
    g = jnp.take_along_axis(sc, top, axis=1)
    if sz["norm_topk"]:
        g = g / jnp.sum(g, axis=1, keepdims=True)
    g = g * sz["route_scale"]
    y = _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])

    def expert(y, scanned):
        e, wg, wu, wd = (a.astype(jnp.float32) if a.ndim else a
                         for a in scanned)
        # This token's gate for held expert e: 0 unless it chose it.
        ge = jnp.sum(jnp.where(top == sz["first_held"] + e, g, 0.0), axis=1)
        return y + ge[:, None] * _swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(expert, y, (jnp.arange(sz["held"]), lp["we_gate"],
                                    lp["we_up"], lp["we_down"]))
    return x + y


def _head(x, at, norm, head, *, eps):
    return _rms(x[at], norm, eps) @ head.astype(jnp.float32)


_JITTED: dict = {}


def _fns(sz: dict):
    key = tuple(sorted(sz.items()))
    if key not in _JITTED:
        _JITTED[key] = (
            jax.jit(functools.partial(_layer, sz=dict(sz), moe=False)),
            jax.jit(functools.partial(_layer, sz=dict(sz), moe=True)),
            jax.jit(functools.partial(_head, eps=sz["eps"])))
    return _JITTED[key]


def logits(w: dict, sz: dict, tokens, at) -> np.ndarray:
    """Reference logits of one token stream at positions ``at`` (each
    predicts the token after it). The stream is right-padded to a
    multiple of BLOCK (causal, so harmless): streams of similar length
    share one compiled program. Layers run one after another, each
    widening only its own weights."""
    dense, moe, head = _fns(sz)
    toks = np.zeros((-(-len(tokens) // BLOCK) * BLOCK,), np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for kind, fn in (("dense", dense), ("moe", moe)):
            stack = w[kind]
            for i in range(stack["attn_norm"].shape[0]):
                x = fn(x, jax.tree.map(lambda a: a[i], stack))
        out = head(x, jnp.asarray(at, jnp.int32), w["final_norm"],
                   w["lm_head"])
    return np.asarray(out, np.float32)
