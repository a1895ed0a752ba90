"""Plain float32 reference of the Xing4.0 decoder as ONE stage of a
pipeline that holds whole layers: FOUR residual streams a token, mixed
by manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on the
hyper-connections of arXiv:2409.19606) around the DeepSeek-V3 block
(latent attention; a leading dense SwiGLU layer, then sigmoid-routed
experts, ALL of a layer's held here, beside a shared one).
Straightforward ``jax.numpy``: the whole stream at once, no cache, no
kernel, no batching, EXPANDED (not absorbed) attention, the routed
experts as a plain loop over all ``n_routed_experts``, every matmul in
float32 at "highest" precision. It imports nothing of the program; the
DeepSeek-V3 block's own pieces (RMSNorm, YaRN rope, the blocked causal
attention, SwiGLU) are those of the sibling reference
``deepseek_v3.py``, whose docstring has their equations.

State ``X in R^{n x D}`` a token, n = ``hc_mult``. ``X_0`` is the
embedding in each of the n streams. For each sublayer F of a layer
(attention, then the FFN; each with its own ``phi``, ``b``, ``alpha``):

    x~      = RMSNorm(vec(X))                   no gain, eps hc_eps
    H~_pre  = a_pre  (x~ phi_pre)  + b_pre      [n]
    H~_post = a_post (x~ phi_post) + b_post     [n]
    H~_res  = a_res  mat(x~ phi_res) + b_res    [n, n]
    H_pre   = sigmoid(H~_pre)      H_post = 2 sigmoid(H~_post)
    H_res   = exp(clip(H~_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max)),
              then hc_sinkhorn_iters times: every column over (its sum +
              hc_eps), then every row over (its sum + hc_eps)
    h       = H_pre X                           [D]
    X'      = H_res X + H_post^T F(RMSNorm(h; g))

F is latent attention, the dense SwiGLU (layers below
``first_k_dense_replace``) or ``sum_{e in top-k} g_e E_e(.) + S(.)``
with ``sc = sigmoid(. W_r)``, the top k of ``sc + b``, ``g_e =
routed_scaling_factor sc_e / sum_selected sc``; the router reads the
same ``RMSNorm(h; g_ffn)`` the experts read. The final RMSNorm reads
the SUM of the streams; then the untied head.

Departures from the published description, each stated in the
configuration file too (``assumed``):

* **Depth.** The layers held are 1 dense layer and the expert layers
  behind it; the multi-token-prediction block is not run
  (``num_nextn_predict_layers`` 0). No width, expert count or
  vocabulary row is cut, so nothing a layer computes is left out.
* What the config's keys do not fix: the fan-out (a copy a stream) and
  the read-out (the sum), which are the hyper-connections paper's; the
  column-before-row order; that ``hc_eps`` enters the stream norm and
  every divisor; that the clamp is on ``H~_res`` before the exponential;
  that the stream norm has no gain; the router's input.
* **Rope pairing** is half-split, as in ``deepseek_v3.py``.
* Weights are made here from the seed: normal, std 0.02 every matrix
  alike (the router's is ``assumed.weights.router_std``); norm scales
  1 + 0.1 n; the selection bias float32 0.01 n. The hyper-connections'
  are drawn so that the coefficients DIFFER BY TOKEN
  (``assumed.weights.hc``): ``alpha`` 1, ``phi`` std ``spread / sqrt(n
  D)`` (so ``x~ phi`` spreads by ``spread``), ``b`` std ``b_std`` with
  ``b_res_diag`` added on the diagonal of ``b_res``. At the published
  initialisation (alpha ~ 0.01) every token would get the same matrices
  and a fault in the coefficient head could not be seen.
* **Which four experts a token uses is PINNED by its id**
  (``assumed.weights.pinned``), as ``smallthinker.py _pin`` does and for
  its reason (the 4th and 5th of 64 scores lie within bfloat16's reach
  of each other in a few (token, layer) pairs of a hundred, and every
  expert is held, so any swap shows): the first G = ``groups`` hidden
  dims of the embedding are ``constant x onehot(group(t))``, nothing
  writes to them (columns 0..G-1 of every Wo, W_down, shared and routed
  down-projection are zero), and the router's row g is ``+margin`` for
  group g's own four experts of the layer. A doubly stochastic ``H_res``
  KEEPS a feature that all streams share and no sublayer writes
  (``H_res (c 1) = c 1``), so the pin works only while the projection's
  rows sum to 1: it is a check of the projection in itself. The pinned
  logits saturate the sigmoid (score 1.0), so the four gates are all
  ``routed_scaling_factor / 4``: the gates' own arithmetic is judged at
  natural routing, on the CPU (tests/test_xing_mhc.py).

Sizes come from the configuration FILE alone. The weight tree has the
layout the program's engine accepts through ``InferenceEngine(params=)``.
``reference_weights`` hands the bfloat16 tree back as it is and
``logits`` widens ONE layer (and one expert of it) at a time: at parity
depth 1 + 2 the bfloat16 arrays are 5.1 GB and a float32 copy would be
10.2 GB beside them.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("bench_ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ds = _sibling("deepseek_v3")
key_of, int8_per_channel = ds.key_of, ds.int8_per_channel
QUANTISED = ds.QUANTISED       # the hyper-connections' leaves are not
BLOCK = ds.BLOCK
SUBLAYERS = ("attn", "ffn")


def sizes(model: dict, layers: int) -> dict:
    """Published config.json keys (+ the file's ``published``,
    ``deployment`` and ``assumed.weights``) -> the sizes this file uses
    (hashable values only)."""
    sz = ds.sizes(model, layers)
    assert sz["held"] == sz["experts"] and sz["first_held"] == 0
    w = model.get("assumed", {}).get("weights", {})
    hc, pin = w.get("hc", {}), w.get("pinned")
    sz.update(
        n=int(model["hc_mult"]), hc_iters=int(model["hc_sinkhorn_iters"]),
        hc_eps=float(model["hc_eps"]),
        clamp=(float(model["mhc_h_res_clamp_min"]),
               float(model["mhc_h_res_clamp_max"])),
        router_std=float(w.get("router_std", 0.02)),
        # (spread of x~ phi, alpha, std of b, diagonal of b_res)
        hc_draw=tuple(float(hc.get(k, v)) for k, v in
                      (("spread", 2.0), ("alpha", 1.0), ("b_std", 1.5),
                       ("b_res_diag", 0.0))),
        pinned=tuple(float(pin[k]) for k in ("constant", "margin", "groups"))
        if pin else ())
    return sz


def _shapes(sz: dict) -> dict:
    tree = ds._shapes(sz)
    n, wide = sz["n"], sz["n"] * sz["d"]
    for stack in ("dense", "moe"):
        layers = tree[stack]["attn_norm"][0]
        for s in SUBLAYERS:
            tree[stack].update({f"hc_{s}_phi": (layers, wide, n * (n + 2)),
                                f"hc_{s}_b": (layers, n * (n + 2)),
                                f"hc_{s}_alpha": (layers, 3)})
    return tree


def make_weights(sz: dict, seed: int) -> dict:
    """bfloat16 weights from the seed (the selection bias and the
    hyper-connections' ``b`` / ``alpha`` float32), on the device, one
    jitted call a leaf."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(sz), is_leaf=lambda x: isinstance(x, tuple))
    key = key_of(seed)
    n = sz["n"]
    spread, alpha, b_std, diag = sz["hc_draw"]

    @functools.partial(jax.jit, static_argnames=("shape", "mean", "std",
                                                 "dtype"))
    def draw(k, shape, mean, std, dtype=jnp.bfloat16):
        return (mean + std * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    out = []
    for i, (path, shape) in enumerate(leaves):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        if name.endswith("_alpha"):
            out.append(jnp.full(shape, alpha, jnp.float32))
        elif name.endswith("_b") and name.startswith("hc_"):
            eye = jnp.concatenate([jnp.zeros(2 * n),
                                   diag * jnp.eye(n).reshape(-1)])
            out.append(draw(k, shape, 0.0, b_std, jnp.float32) + eye)
        elif name.endswith("_phi"):
            out.append(draw(k, shape, 0.0, spread / np.sqrt(shape[1])))
        elif name == "router_bias":
            out.append(draw(k, shape, 0.0, 0.01, jnp.float32))
        elif "norm" in name:
            out.append(draw(k, shape, 1.0, 0.1))
        else:
            out.append(draw(k, shape, 0.0, sz["router_std"]
                            if name == "w_router" else 0.02))
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
    layers = w["moe"]["w_router"].shape[0]
    order = jax.vmap(lambda key: jax.random.permutation(key, e))(
        jax.random.split(key, layers))                         # [L, E]
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
    for stack, downs in (("dense", ("wo", "w_down")),
                         ("moe", ("wo", "ws_down", "we_down"))):
        w[stack] = dict(w[stack], **{
            name: set_at(first, 0.0)(w[stack][name]) for name in downs})
    w["moe"]["w_router"] = set_at((slice(None), slice(0, g)), rows)(
        w["moe"]["w_router"])
    return w


def reference_weights(weights: dict, quant: str) -> dict:
    """The weights as the configuration serves them: the bfloat16 tree
    itself for ``quant`` "none", float32 int8-rounded copies of the
    QUANTISED leaves (the projections and the experts; never the router
    or a hyper-connection's leaves) for "int8"."""
    if quant in (None, "none"):
        return weights

    def leaf(path, w):
        if quant == "int8" and path[-1].key in QUANTISED:
            return jax.jit(int8_per_channel)(w)
        return w

    return jax.tree_util.tree_map_with_path(leaf, weights)


# ------------------------------------------------------------------ forward
def hyper_connection(x, phi, b, alpha, sz):
    """x [S, n, D] -> (H_pre [S, n], H_post [S, n], H_res [S, n, n])."""
    s, n, _ = x.shape
    v = x.reshape(s, -1)
    xt = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + sz["hc_eps"])
    z = xt @ phi
    pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(alpha[2] * z[:, 2 * n:] + b[2 * n:],
                           *sz["clamp"])).reshape(s, n, n)
    for _ in range(sz["hc_iters"]):
        res = res / (jnp.sum(res, axis=1, keepdims=True) + sz["hc_eps"])
        res = res / (jnp.sum(res, axis=2, keepdims=True) + sz["hc_eps"])
    return pre, post, res


def _attn(h, lp, sz):
    """Latent attention, expanded, on the whole stream h [S, D]."""
    s = h.shape[0]
    pos = jnp.arange(s)
    nh, dn, dr, dv = sz["heads"], sz["nope"], sz["rope"], sz["v"]
    r = sz["kv_rank"]
    scale = ((dn + dr) ** -0.5
             * ds._mscale(sz["yarn_factor"], sz["mscale_all_dim"]) ** 2)
    q = (ds._rms(h @ lp["wq_a"], lp["q_norm"], sz["eps"]) @ lp["wq_b"]
         ).reshape(s, nh, dn + dr)
    ckv = h @ lp["wkv_a"]
    c = ds._rms(ckv[:, :r], lp["kv_norm"], sz["eps"])
    k_rope = ds._rope(ckv[:, None, r:], pos, sz)
    kvb = (c @ lp["wkv_b"]).reshape(s, nh, dn + dv)
    o = ds._attention(q[..., :dn], ds._rope(q[..., dn:], pos, sz),
                      kvb[..., :dn], k_rope, kvb[..., dn:], scale)
    return o.reshape(s, nh * dv) @ lp["wo"]


def _experts(h, lp, sz):
    """The shared expert plus a plain loop over all routed experts."""
    sc = jax.nn.sigmoid(h @ lp["w_router"])
    _, top = jax.lax.top_k(sc + lp["router_bias"][None, :], sz["top_k"])
    g = jnp.take_along_axis(sc, top, axis=1)
    if sz["norm_topk"]:
        g = g / jnp.sum(g, axis=1, keepdims=True)
    g = g * sz["route_scale"]
    y = ds._swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])

    def expert(y, scanned):
        e, wg, wu, wd = (a.astype(jnp.float32) if a.ndim else a
                         for a in scanned)
        ge = jnp.sum(jnp.where(top == e, g, 0.0), axis=1)
        return y + ge[:, None] * ds._swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(expert, y, (jnp.arange(sz["experts"]),
                                    lp["we_gate"], lp["we_up"],
                                    lp["we_down"]))
    return y


def _layer(x, lp, *, sz, moe: bool):
    """One decoder layer on the whole stream's n residual streams x
    [S, n, D]; ``lp`` one layer's weights (widened here, the routed
    experts one at a time)."""
    lp = {k: w if k.startswith("we_") else w.astype(jnp.float32)
          for k, w in lp.items()}
    ffn = (_experts if moe else
           lambda h, lp, sz: ds._swiglu(h, lp["w_gate"], lp["w_up"],
                                        lp["w_down"]))
    for name, f in zip(SUBLAYERS, (_attn, ffn)):
        pre, post, res = hyper_connection(
            x, *(lp[f"hc_{name}_{k}"] for k in ("phi", "b", "alpha")), sz)
        h = jnp.einsum("sn,snd->sd", pre, x)
        y = f(ds._rms(h, lp[f"{name}_norm"], sz["eps"]), lp, sz)
        x = (jnp.einsum("sij,sjd->sid", res, x)
             + post[:, :, None] * y[:, None, :])
    return x


_JITTED: dict = {}


def _fns(sz: dict):
    key = tuple(sorted(sz.items()))
    if key not in _JITTED:
        _JITTED[key] = (
            jax.jit(functools.partial(_layer, sz=dict(sz), moe=False)),
            jax.jit(functools.partial(_layer, sz=dict(sz), moe=True)),
            jax.jit(functools.partial(ds._head, eps=sz["eps"])))
    return _JITTED[key]


def logits(w: dict, sz: dict, tokens, at) -> np.ndarray:
    """Reference logits of one token stream at positions ``at``. The
    stream is right-padded to a multiple of BLOCK (causal, so harmless).
    Layers run one after another, each widening only its own weights."""
    dense, moe, head = _fns(sz)
    toks = np.zeros((-(-len(tokens) // BLOCK) * BLOCK,), np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(toks)].astype(jnp.float32)
        x = jnp.tile(x[:, None, :], (1, sz["n"], 1))      # a copy a stream
        for kind, fn in (("dense", dense), ("moe", moe)):
            stack = w[kind]
            for i in range(stack["attn_norm"].shape[0]):
                x = fn(x, jax.tree.map(lambda a: a[i], stack))
        out = head(x.sum(1), jnp.asarray(at, jnp.int32), w["final_norm"],
                   w["lm_head"])
    return np.asarray(out, np.float32)
