"""Plain float32 reference of the SmallThinker decoder (PowerInfer) as ONE
stage of a pipeline that holds whole layers: every expert of every layer
it has. Straightforward ``jax.numpy``: the whole stream at once, no
cache, no kernel, no batching, a dense mask a layer by its kind, the
routed experts as a plain loop over all ``moe_num_primary_experts``,
every matmul in float32 at "highest" precision.

The equations (x is ``hidden_size`` wide, eps ``rms_norm_eps``). Layer l
is a window layer where ``sliding_window_layout[l]`` is 1 and takes rope
where ``rope_layout[l]`` is 1 (the two lists are the same list as
published: a full layer has no rope); H = ``num_attention_heads``, Hkv =
``num_key_value_heads`` (head h reads KV head h // (H / Hkv)), d =
``head_dim``:

* ``h = RMSNorm(x; g1)``. **The router reads h**, the PRE-attention
  norm: ``z = h Wr`` (float32, all experts); S = the top
  ``moe_num_active_primary_experts`` of z; ``gate = softmax(z[S])``
  (``moe_primary_router_apply_softmax`` with ``norm_topk_prob``: a
  softmax over all experts renormalised over S is the same function).
* ``q = h Wq`` [H x d], ``k = h Wk``, ``v = h Wv`` [Hkv x d], no bias.
* Rope where the layer takes it: half-split pairing ``(x_j, x_{j + d/2})``
  over all d dims, theta ``rope_theta``, no scaling. A layer that takes
  none attends with q and k as projected (NoPE).
* ``s = q k^T / sqrt(d)``, causal; on a window layer key j is visible to
  query i only if ``i - sliding_window_size < j <= i``. ``o =
  softmax(s) v``; ``x <- x + concat_h(o_h) Wo``.
* ``h2 = RMSNorm(x; g2)``; ``x <- x + sum_{e in S} gate_e E_e(h2)``,
  ``E_e(h) = (relu(h W_g,e) * (h W_u,e)) W_d,e`` of width
  ``moe_ffn_hidden_size``. No shared expert, no dense layer.
* Final RMSNorm, then the untied head.

Departures from the published description, each stated in the
configuration file too:

* **Depth.** The layers held are the first ``num_hidden_layers`` of the
  published 52 (whole periods of one full and three window layers);
  embedding and head both here. No width, expert count or vocabulary is
  cut, so nothing a layer computes is left out.
* What the config does not say (``assumed``): the router's input is the
  NORMED layer input (the description says "router placed before
  attention"; that it reads the norm's output and not x is assumed); no
  bias anywhere; no QK-norm; rope pairing half-split (an interleaved
  pairing is the same function under a fixed permutation of Wq / Wk
  columns, which random weights do not tell apart); only the keys the
  config has: no secondary experts.
* Weights are made here from the seed: normal, std 0.02 every matrix
  alike (the router's is ``assumed.weights.router_std``); norm scales
  1 + 0.1 n. **Which six experts a token uses is PINNED by its id**
  (``assumed.weights.pinned``; the served weights are the program's own
  draw and pin nothing). The architecture has no selection bias to pin
  with, as bench/references/deepseek_v3.py HELD_MARGIN does, so the
  parity weights make one: the first G = ``groups`` hidden dims are a
  one-hot of the token's GROUP (``embed[t, :G] = constant x
  onehot(group(t))``, and nothing writes to them: columns 0..G-1 of
  every Wo and W_down are zero), and the router's row g is ``+margin``
  for group g's OWN six experts of the layer and 0 for the rest. Group g
  takes g + 1 ids of every G (G + 1) / 2 (uneven on purpose: 1.5% to 17%
  of drawn ids at G = 11), and the groups' sixes are cut from one
  permutation of the experts a layer (slots 6g..6g+5, mod 64: G x 6 >=
  64, so every expert is some group's, two of them two groups'). A
  token's six then lead its logits by margin x h[group(t)] >= ~10 and
  its own part (spread ~1) decides only their GATES; the tokens of one
  chunk or one decode step use DIFFERENT sixes, so all 64 experts get
  rows, ~15 to ~190 of a 1024-token chunk (both sides of a 128-row
  tile) and 0 to 3 of a decode step, and a wrong top-k, a wrong expert
  index, a mis-sorted group or a fault in the tiles' padding at mixed
  row counts shows. Why a margin at all: with a natural draw the sixth and seventh
  of 64 logits lie within bfloat16's reach of each other in 1-3% of
  (token, layer) pairs whatever the router's scale, a swapped expert
  then reads rms 0.03-0.09 at that position, and parity.py judges the
  worst of 27 positions, so nearly every seed held one (PERF.md section
  2 has the three scales tried). With every expert held here, ANY
  change of membership shows; pinned, none happens between bfloat16 and
  float32.

Sizes come from the configuration FILE alone. The weight tree has the
layout the program's engine accepts through ``InferenceEngine(params=)``
(attention stacked per kind, the expert layers in ``ffn_moe``, [in, out]
matrices): that layout is the interface between the two.
``reference_weights`` hands the bfloat16 tree back as it is and
``logits`` widens ONE layer at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUANTISED = ("wq", "wk", "wv", "wo", "we_gate", "we_up", "we_down",
             "lm_head")
BLOCK = 256          # queries a block of attention; streams pad to it


def sizes(model: dict, layers: int) -> dict:
    """Published config.json keys (+ the file's ``assumed``) -> the
    sizes this file uses (hashable values only)."""
    window, rope = (tuple(model[k][:layers])
                    for k in ("sliding_window_layout", "rope_layout"))
    assert model.get("rope_scaling") is None
    assert model["moe_primary_router_apply_softmax"] and \
        model["norm_topk_prob"]
    weights = model.get("assumed", {}).get("weights", {})
    return {
        "vocab": model["vocab_size"], "d": model["hidden_size"],
        "layers": layers,
        "kinds": tuple("window" if w else "full" for w in window),
        "rope": tuple(bool(r) for r in rope),
        "heads": model["num_attention_heads"],
        "kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"],
        "window": model["sliding_window_size"],
        "moe_ff": model["moe_ffn_hidden_size"],
        "experts": model["moe_num_primary_experts"],
        "top_k": model["moe_num_active_primary_experts"],
        "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "router_std": float(weights.get("router_std", 0.02)),
        # (one-hot's height, margin, groups) of the pinned membership,
        # or ().
        "pinned": tuple(float(weights["pinned"][k])
                        for k in ("constant", "margin", "groups"))
        if weights.get("pinned") else (),
    }


def key_of(seed: int):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _shapes(sz: dict) -> dict:
    d, hd, h, hkv = sz["d"], sz["head_dim"], sz["heads"], sz["kv_heads"]
    n, e, f = sz["layers"], sz["experts"], sz["moe_ff"]

    def attn(kind):
        at = [l for l, k in enumerate(sz["kinds"]) if k == kind]
        # One rope rule a kind: the program ropes by kind, not by layer.
        assert len({sz["rope"][l] for l in at}) <= 1, "rope differs in a kind"
        m = len(at)
        return {"attn_norm": (m, d), "wq": (m, d, h * hd),
                "wk": (m, d, hkv * hd), "wv": (m, d, hkv * hd),
                "wo": (m, h * hd, d)}

    return {
        "embed": (sz["vocab"], d),
        "attn_full": attn("full"), "attn_window": attn("window"),
        "ffn_moe": {"ffn_norm": (n, d), "w_router": (n, d, e),
                    "we_gate": (n, e, d, f), "we_up": (n, e, d, f),
                    "we_down": (n, e, f, d)},
        "final_norm": (d,), "lm_head": (d, sz["vocab"]),
    }


def make_weights(sz: dict, seed: int) -> dict:
    """bfloat16 weights from the seed, on the device, one jitted call a
    leaf (the whole tree in one program would hold every float32 draw at
    once)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(sz), is_leaf=lambda x: isinstance(x, tuple))
    key = key_of(seed)

    @functools.partial(jax.jit, static_argnames=("shape", "norm", "std"))
    def draw(k, shape, norm, std):
        n = jax.random.normal(k, shape, jnp.float32)
        return ((1.0 + 0.1 * n) if norm else std * n).astype(jnp.bfloat16)

    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        out.append(draw(jax.random.fold_in(key, i), shape, "norm" in name,
                        sz["router_std"] if name == "w_router" else 0.02))
    tree = jax.tree_util.tree_unflatten(treedef, out)
    return _pin(tree, sz, jax.random.fold_in(key, len(leaves)))


def _pin(w: dict, sz: dict, key) -> dict:
    """The pinned membership (the module docstring): the first
    ``groups`` hidden dims a one-hot of the token's id that nothing
    writes to, the router's row g the margin for group g's ``top_k``
    experts of the layer and 0 for the others."""
    if not sz["pinned"]:
        return w
    constant, margin, groups = sz["pinned"]
    g, k, e = int(groups), sz["top_k"], sz["experts"]
    assert g * k >= e, f"{g} groups of {k} leave experts of {e} unrouted"
    order = jax.vmap(lambda key: jax.random.permutation(key, e))(
        jax.random.split(key, sz["layers"]))                   # [L, E]
    # Group g's experts: k slots in a row of the layer's permutation
    # (k <= e, so no group names an expert twice).
    own = order[:, (np.arange(g)[:, None] * k + np.arange(k)) % e]
    rows = (margin * jax.nn.one_hot(own, e).sum(-2)).astype(jnp.bfloat16)
    # Uneven on purpose: group j takes j + 1 ids of every g (g + 1) / 2.
    group_of = np.repeat(np.arange(g), np.arange(g) + 1)
    onehot = constant * (
        group_of[np.arange(sz["vocab"]) % len(group_of)][:, None]
        == np.arange(g)).astype(np.float32)

    def set_at(index, value):
        return jax.jit(lambda a: a.at[index].set(
            jnp.asarray(value, a.dtype)), donate_argnums=0)

    first = (Ellipsis, slice(0, g))
    w = dict(w, embed=set_at(first, onehot)(w["embed"]))
    for kind in ("attn_full", "attn_window"):
        w[kind] = dict(w[kind], wo=set_at(first, 0.0)(w[kind]["wo"]))
    moe = w["ffn_moe"]
    w["ffn_moe"] = dict(
        moe, we_down=set_at(first, 0.0)(moe["we_down"]),
        w_router=set_at((slice(None), slice(0, g)), rows)(moe["w_router"]))
    return w


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


def _rope(x, pos, sz):
    """x [S, H, D], pos [S]: rotate pairs (j, j + D/2) of all D dims."""
    dim = sz["head_dim"]
    inv = jnp.asarray((1.0 / sz["theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


def _layer(x, ap, fp, *, sz, kind: str, rope: bool):
    """One decoder layer on the whole stream x [S, D]; ``ap`` / ``fp`` one
    layer's attention / expert-layer weights (any dtype; widened here,
    the experts one at a time)."""
    ap = {k: w.astype(jnp.float32) for k, w in ap.items()}
    s = x.shape[0]
    pos = jnp.arange(s)
    h_, hkv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]

    h = _rms(x, ap["attn_norm"], sz["eps"])
    # The router reads the PRE-attention norm.
    z = h @ fp["w_router"].astype(jnp.float32)                 # [S, E]
    zs, top = jax.lax.top_k(z, sz["top_k"])
    gate = jax.nn.softmax(zs, axis=-1)                         # [S, k]

    q = (h @ ap["wq"]).reshape(s, h_, hd)
    k = (h @ ap["wk"]).reshape(s, hkv, hd)
    v = (h @ ap["wv"]).reshape(s, hkv, hd)
    if rope:
        q, k = _rope(q, pos, sz), _rope(k, pos, sz)
    o = _attention(q, k, v, sz["window"] if kind == "window" else 0)
    x = x + o.reshape(s, h_ * hd) @ ap["wo"]

    h2 = _rms(x, fp["ffn_norm"], sz["eps"])

    def expert(y, scanned):
        e, wg, wu, wd = (a.astype(jnp.float32) if a.ndim else a
                         for a in scanned)
        # This token's gate for expert e: 0 unless it chose it.
        ge = jnp.sum(jnp.where(top == e, gate, 0.0), axis=1)
        return y + ge[:, None] * ((jax.nn.relu(h2 @ wg) * (h2 @ wu)) @ wd), \
            None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (jnp.arange(sz["experts"]), fp["we_gate"],
                         fp["we_up"], fp["we_down"]))
    return x + y


def _head(x, at, norm, head, *, eps):
    return _rms(x[at], norm, eps) @ head.astype(jnp.float32)


_JITTED: dict = {}


def _fns(sz: dict):
    key = tuple(sorted(sz.items()))
    if key not in _JITTED:
        forms = set(zip(sz["kinds"], sz["rope"]))
        _JITTED[key] = (
            {form: jax.jit(functools.partial(
                _layer, sz=dict(sz), kind=form[0], rope=form[1]))
             for form in forms},
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
    place = {"full": 0, "window": 0}
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for l, (kind, rope) in enumerate(zip(sz["kinds"], sz["rope"])):
            ap = jax.tree.map(lambda a: a[place[kind]], w["attn_" + kind])
            fp = jax.tree.map(lambda a: a[l], w["ffn_moe"])
            x = layer_fns[(kind, rope)](x, ap, fp)
            place[kind] += 1
        out = head(x, jnp.asarray(at, jnp.int32), w["final_norm"],
                   w["lm_head"])
    return np.asarray(out, np.float32)
