"""Plain float32 reference of the Laguna decoder (poolside) as ONE chip's
share of an expert-parallel deployment. Straightforward ``jax.numpy``: the
whole stream at once, no cache, no kernel, no batching, a dense mask a
layer by its kind, the routed experts as a plain loop over the experts
held here, every matmul in float32 at "highest" precision.

The equations (x is ``hidden_size`` wide, ``h = RMSNorm(x)``, eps
``rms_norm_eps``). Layer l has kind ``layer_types[l]``, H_l =
``num_attention_heads_per_layer[l]`` query heads, ``num_key_value_heads``
KV heads (head h reads KV head h // (H_l / Hkv)), d = ``head_dim``:

* ``q = h Wq`` [H_l x d], ``k = h Wk``, ``v = h Wv`` [Hkv x d], no bias.
* Rope, half-split pairing ``(x_j, x_{j + R/2})`` over the first R dims.
  A ``full_attention`` layer: R = d x ``partial_rotary_factor`` (dims R..d
  pass through), YaRN frequencies over R (channel j of R/2 keeps
  ``theta^(-2j/R)`` below a ramp and runs ``factor`` x slower above it;
  the ramp spans floor(f(beta_fast)) .. ceil(f(beta_slow)), ``f(t) = R
  ln(original / (2 pi t)) / (2 ln theta)``), cos and sin times
  ``attention_factor``. A ``sliding_attention`` layer: R = d, its own
  theta, no scaling.
* ``s = q k^T / sqrt(d)``, causal; on a sliding layer key j is visible to
  query i only if ``i - sliding_window < j <= i``. ``o_h = softmax(s) v``.
* Gate: ``g = sigmoid(h Wg)`` [H_l]; ``o_h <- g_h o_h``.
  ``x <- x + concat_h(o_h) Wo``.
* ``h2 = RMSNorm(x)``. A dense layer (``mlp_layer_types``): ``x <- x +
  SwiGLU(h2)`` of ``intermediate_size``. A sparse layer: ``sc =
  sigmoid(h2 Wr)`` (float32, all published experts; softmax where
  ``assumed.moe_scoring`` says so); the top k of ``sc + b`` (``b`` the
  selection bias); ``g_e = moe_routed_scaling_factor sc_e / sum_selected
  sc``; ``x <- x + sum_{e in top-k} g_e E_e(h2) + S(h2)``, ``E_e(h) =
  (silu(h W_g,e) * (h W_u,e)) W_d,e``, ``S`` the shared expert, ungated.
* Final RMSNorm, then the head.

Departures, each stated in the configuration file too:

* **This chip's share.** The sum over experts runs over ``top-k ∩ held``
  (the rank's ``num_experts`` of the published count); ``g_e`` is still
  normalised over all k chosen; ``S(h)`` in full. What the absent experts
  would add is left out, and that partial result goes on to the next
  layer. The vocabulary is rows / columns 0 .. vocab_size - 1.
* What the config does not say (``assumed``): the gate is a sigmoid of
  the NORMED input; router scores are sigmoid with a float32 selection
  bias; no QK-norm; no gate on the shared expert; rope pairing half-split
  (the published pairing, if interleaved, is the same function under a
  fixed permutation of Wq / Wk columns, which random weights do not tell
  apart).
* Weights are made here from the seed: normal, std 0.02, every matrix
  alike; norm scales 1 + 0.1 n; the selection bias float32, 0.01 n for
  the experts NOT held here; for those held here it DECIDES: per layer
  HELD_CHOSEN of them (from the seed) get +HELD_MARGIN and are among
  every token's k, the others -HELD_MARGIN and never are
  (bench/references/deepseek_v3.py HELD_MARGIN says why: a place that
  flips between bfloat16 and float32 moves a whole expert's output, and
  the comparison judges the worst position). Gates, the other places and
  the sum they are normalised over stay each token's own.

Sizes come from the configuration FILE alone. The weight tree has the
layout the program's engine accepts through ``InferenceEngine(params=)``
(attention stacked per kind, feed-forward per form, [in, out] matrices):
that layout is the interface between the two. ``reference_weights`` hands
the bfloat16 tree back as it is and ``logits`` widens ONE layer at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUANTISED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
             "ws_gate", "ws_up", "ws_down", "we_gate", "we_up", "we_down",
             "lm_head")
BLOCK = 256          # queries a block of attention; streams pad to it
HELD_CHOSEN = 3
HELD_MARGIN = 2.0
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def sizes(model: dict, layers: int) -> dict:
    """Published config.json keys (+ the file's ``published``,
    ``deployment`` and ``assumed``) -> the sizes this file uses (hashable
    values only). ``layers`` counts the leading dense layer."""
    rope = model["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    held = model["num_experts"]
    return {
        "vocab": model["vocab_size"], "d": model["hidden_size"],
        "layers": layers,
        "kinds": tuple(KINDS[k] for k in model["layer_types"][:layers]),
        "heads": tuple(model["num_attention_heads_per_layer"][:layers]),
        "routed": tuple(t == "sparse"
                        for t in model["mlp_layer_types"][:layers]),
        "kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"], "window": model["sliding_window"],
        "ff": model["intermediate_size"],
        "moe_ff": model["moe_intermediate_size"],
        "shared_ff": model["shared_expert_intermediate_size"],
        "experts": model["published"]["num_experts"], "held": held,
        "first_held": model["deployment"]["rank"] * held,
        "top_k": model["num_experts_per_tok"],
        "route_scale": float(model["moe_routed_scaling_factor"]),
        "norm_topk": bool(model["norm_topk_prob"]),
        "scoring": model["assumed"].get("moe_scoring", "sigmoid"),
        "gate": model["gating"] == "per-head",
        "theta": float(full["rope_theta"]),
        "rotary": float(full["partial_rotary_factor"]),
        "yarn_factor": float(full["factor"]),
        "yarn_original": int(full["original_max_position_embeddings"]),
        "beta_fast": float(full["beta_fast"]),
        "beta_slow": float(full["beta_slow"]),
        "attention_factor": float(full["attention_factor"]),
        "window_theta": float(slide["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
    }


def key_of(seed: int):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _layers_of(sz: dict, kind: str) -> list:
    return [l for l, k in enumerate(sz["kinds"]) if k == kind]


def _shapes(sz: dict) -> dict:
    d, hd, hkv = sz["d"], sz["head_dim"], sz["kv_heads"]

    def attn(kind):
        at = _layers_of(sz, kind)
        n, h = len(at), (sz["heads"][at[0]] if at else 0)
        assert all(sz["heads"][l] == h for l in at), "heads differ in a kind"
        shapes = {"attn_norm": (n, d), "wq": (n, d, h * hd),
                  "wk": (n, d, hkv * hd), "wv": (n, d, hkv * hd),
                  "wo": (n, h * hd, d)}
        if sz["gate"]:
            shapes["w_head_gate"] = (n, d, h)
        return shapes

    ne = sum(sz["routed"])
    nd = sz["layers"] - ne
    assert not any(sz["routed"][:nd]), "dense layers lead"
    f, fs, e = sz["moe_ff"], sz["shared_ff"], sz["held"]
    return {
        "embed": (sz["vocab"], d),
        "attn_full": attn("full"), "attn_window": attn("window"),
        "ffn_dense": {"ffn_norm": (nd, d), "w_gate": (nd, d, sz["ff"]),
                      "w_up": (nd, d, sz["ff"]), "w_down": (nd, sz["ff"], d)},
        "ffn_moe": {"ffn_norm": (ne, d), "w_router": (ne, d, sz["experts"]),
                    "router_bias": (ne, sz["experts"]),
                    "ws_gate": (ne, d, fs), "ws_up": (ne, d, fs),
                    "ws_down": (ne, fs, d), "we_gate": (ne, e, d, f),
                    "we_up": (ne, e, d, f), "we_down": (ne, e, f, d)},
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
    127 over the contraction axis), then back to float32."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2, keepdims=True),
                        1e-8) / 127.0
    return (jnp.clip(jnp.round(wf / scale), -127, 127) * scale)


def reference_weights(weights: dict, quant: str) -> dict:
    """The weights as the configuration serves them: the bfloat16 tree
    itself for ``quant`` "none" (``logits`` widens a layer at a time),
    float32 int8-rounded copies of the QUANTISED leaves for "int8"."""
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


def _inv_freq(sz: dict, kind: str) -> np.ndarray:
    """Rope frequencies of a kind, [R / 2] float32."""
    if kind == "window":
        dim = sz["head_dim"]
        return (1.0 / sz["window_theta"]
                ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
                ).astype(np.float32)
    dim, theta = int(sz["head_dim"] * sz["rotary"]), sz["theta"]
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


def _rope(x, pos, sz, kind):
    """x [S, H, D], pos [S]: rotate pairs (j, j + R/2) of the first R
    dims; the rest pass through."""
    inv = jnp.asarray(_inv_freq(sz, kind))
    half = inv.shape[0]
    ang = pos[:, None].astype(jnp.float32) * inv
    m = sz["attention_factor"] if kind == "full" else 1.0
    cos, sin = (m * jnp.cos(ang))[:, None, :], (m * jnp.sin(ang))[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _attention(q, k, v, window: int):
    """q [S, H, D], k / v [S, Hkv, D] -> [S, H, D]; causal, and on a
    window layer only the last ``window`` keys; a block of queries at a
    time."""
    s, h, d = q.shape
    n_rep = h // k.shape[1]
    k, v = jnp.repeat(k, n_rep, axis=1), jnp.repeat(v, n_rep, axis=1)
    kpos = jnp.arange(s)

    def one(args):
        qb, start = args
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        qpos = (start + jnp.arange(BLOCK))[:, None]
        mask = kpos[None, :] <= qpos
        if window:
            mask &= kpos[None, :] > qpos - window
        sc = jnp.where(mask[None], sc, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    nb = s // BLOCK
    out = jax.lax.map(one, (q.reshape(nb, BLOCK, h, d),
                            jnp.arange(nb) * BLOCK))
    return out.reshape(s, h, d)


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _layer(x, ap, fp, *, sz, kind: str, heads: int, routed: bool):
    """One decoder layer on the whole stream x [S, D]; ``ap`` / ``fp`` one
    layer's attention / feed-forward weights (any dtype; widened here)."""
    ap = {k: w.astype(jnp.float32) for k, w in ap.items()}
    fp = {k: w if k.startswith("we_") else w.astype(jnp.float32)
          for k, w in fp.items()}
    s = x.shape[0]
    pos = jnp.arange(s)
    hkv, hd = sz["kv_heads"], sz["head_dim"]

    h = _rms(x, ap["attn_norm"], sz["eps"])
    q = _rope((h @ ap["wq"]).reshape(s, heads, hd), pos, sz, kind)
    k = _rope((h @ ap["wk"]).reshape(s, hkv, hd), pos, sz, kind)
    v = (h @ ap["wv"]).reshape(s, hkv, hd)
    o = _attention(q, k, v, sz["window"] if kind == "window" else 0)
    if sz["gate"]:
        o = o * jax.nn.sigmoid(h @ ap["w_head_gate"])[:, :, None]
    x = x + o.reshape(s, heads * hd) @ ap["wo"]

    h = _rms(x, fp["ffn_norm"], sz["eps"])
    if not routed:
        return x + _swiglu(h, fp["w_gate"], fp["w_up"], fp["w_down"])
    logit = h @ fp["w_router"]                                 # [S, E]
    sc = (jax.nn.sigmoid(logit) if sz["scoring"] == "sigmoid"
          else jax.nn.softmax(logit, -1))
    _, top = jax.lax.top_k(sc + fp["router_bias"][None, :], sz["top_k"])
    g = jnp.take_along_axis(sc, top, axis=1)
    if sz["norm_topk"]:
        g = g / jnp.sum(g, axis=1, keepdims=True)
    g = g * sz["route_scale"]
    y = _swiglu(h, fp["ws_gate"], fp["ws_up"], fp["ws_down"])

    def expert(y, scanned):
        e, wg, wu, wd = (a.astype(jnp.float32) if a.ndim else a
                         for a in scanned)
        # This token's gate for held expert e: 0 unless it chose it.
        ge = jnp.sum(jnp.where(top == sz["first_held"] + e, g, 0.0), axis=1)
        return y + ge[:, None] * _swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(expert, y, (jnp.arange(sz["held"]), fp["we_gate"],
                                    fp["we_up"], fp["we_down"]))
    return x + y


def _head(x, at, norm, head, *, eps):
    return _rms(x[at], norm, eps) @ head.astype(jnp.float32)


_JITTED: dict = {}


def _fns(sz: dict):
    key = tuple(sorted(sz.items()))
    if key not in _JITTED:
        forms = {(kind, heads, routed)
                 for kind, heads, routed in zip(sz["kinds"], sz["heads"],
                                                sz["routed"])}
        _JITTED[key] = (
            {form: jax.jit(functools.partial(
                _layer, sz=dict(sz), kind=form[0], heads=form[1],
                routed=form[2])) for form in forms},
            jax.jit(functools.partial(_head, eps=sz["eps"])))
    return _JITTED[key]


def logits(w: dict, sz: dict, tokens, at) -> np.ndarray:
    """Reference logits of one token stream at positions ``at`` (each
    predicts the token after it). The stream is right-padded to a
    multiple of BLOCK (causal, so harmless): streams of similar length
    share one compiled program. Layers run one after another, each
    widening only its own weights."""
    layer_fns, head = _fns(sz)
    toks = np.zeros((-(-len(tokens) // BLOCK) * BLOCK,), np.int32)
    toks[:len(tokens)] = tokens
    place = {"full": 0, "window": 0, "dense": 0, "moe": 0}
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for kind, heads, routed in zip(sz["kinds"], sz["heads"],
                                       sz["routed"]):
            form = "moe" if routed else "dense"
            ap = jax.tree.map(lambda a: a[place[kind]], w["attn_" + kind])
            fp = jax.tree.map(lambda a: a[place[form]], w["ffn_" + form])
            x = layer_fns[(kind, heads, routed)](x, ap, fp)
            place[kind] += 1
            place[form] += 1
        out = head(x, jnp.asarray(at, jnp.int32), w["final_norm"],
                   w["lm_head"])
    return np.asarray(out, np.float32)
