"""Plain float32 reference of the SambaY decoder (Phi-4-mini-flash-
reasoning, arXiv:2507.06607). Straightforward ``jax.numpy``: the whole
stream at once, no cache, no kernel, no batching, the recurrence as a
``lax.scan`` over tokens, a dense mask a layer by its kind, every matmul
in float32 at "highest" precision, a layer at a time so that published
widths fit.

The equations (x is ``hidden_size`` = D wide; ``LN`` = LayerNorm with
gain and bias, eps ``layer_norm_eps``). Layer l of n:
``x <- x + Mix_l(LN(x)); x <- x + MLP(LN(x))``, ``MLP(h) = (silu(g) * u)
W2`` with ``[g, u] = h W1``. The kind of ``Mix_l`` follows from l and n:

* even l <= n/2: **ssm**, a Mamba-1 selective scan. ``[x, z] = h W_in``;
  ``xc_t = silu(b_c + sum_{j<4} w_c[j] * x_{t-3+j})`` (zeros before the
  stream); ``[r, B_t, C_t] = xc_t W_x``; ``dt_t = softplus(r W_dt +
  b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t
  * xc_t) (x) B_t``; ``y_t = h_t . C_t + D * xc_t``; ``Mix = (y_t *
  silu(z_t)) W_out``. Layer n/2 also hands ``m_t = y_t`` (BEFORE the z
  gate) to the gmu layers of the same token.
* odd l < n/2: **window**, differential attention over the last
  ``sliding_window`` keys; l = n/2 + 1: **full**, over the whole stream.
* even l >= n/2 + 2: **gmu**, ``(silu(h W_in) * m) W_out``.
* odd l >= n/2 + 3: **cross**, differential attention whose queries are
  the layer's own and whose keys and values are the FULL layer's.

Differential attention: ``[q, k, v] = h W_qkv + b``; heads split in
halves, ``q1, q2`` = query heads 0 .. H/2-1, H/2 .. H-1, ``k1, k2`` and
``v1, v2`` = KV heads 0 .. Hkv/2-1, Hkv/2 .. Hkv-1, KV head j of a half
serving that half's query heads j * rep .. (rep = H / Hkv). ``P1 =
softmax(q1 k1^T / sqrt(d))``, ``P2`` likewise (causal; window: the last
``sliding_window`` keys); ``A1 = P1 [v1 | v2]``, ``A2 = P2 [v1 | v2]``;
``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0_l``, ``lam0_l = 0.8 - 0.6
exp(-0.3 l)``; ``Mix = (RMSNorm_2d(A1 - lam A2) * (1 - lam0_l)) W_o +
b_o``. No rotary or learned positions anywhere.

Sizes come from the configuration FILE alone (its ``assumed`` names what
the published config does not: the four scan sizes, the halves' split,
the biases, W1's chunk order, lam0). The weight tree has the layout the
program's engine accepts through ``InferenceEngine(params=)`` (parameters
stacked per kind, [in, out] matrices, the scan's state-major ``A_log``
``[N, d_inner]`` and tap-major conv ``[4, d_inner]``): that layout is the
interface between the two. Weights are made here from the seed: normal,
std 0.02 (max(0.02, D ** -0.5): the published width's 0.0198 rounds up
to it), every matrix alike; gains 1 + 0.1 n and biases 0.02 n; the
lambda vectors std 0.1; the conv taps std 0.5; ``A_log = ln(1 .. N) + 0.1
n`` and ``D = 1 + 0.1 n`` (float32); a dt bias whose softplus is
log-uniform in [1e-3, 1e-1].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256          # queries a block of attention; streams pad to it


def kinds_of(layers: int) -> tuple:
    mid = layers // 2
    return tuple(("ssm" if l % 2 == 0 else "window") if l <= mid
                 else "full" if l == mid + 1
                 else ("gmu" if l % 2 == 0 else "cross")
                 for l in range(layers))


def sizes(model: dict, layers: int) -> dict:
    """Published config.json keys (+ the file's ``assumed``) -> the sizes
    this file uses (hashable values only). ``layers`` is the depth that
    runs: the kinds follow from it."""
    a = model["assumed"]["mamba"]
    d = model["hidden_size"]
    assert layers % 4 == 0 and layers >= 8, layers
    return {
        "vocab": model["vocab_size"], "d": d, "layers": layers,
        "heads": model["num_attention_heads"],
        "kv_heads": model["num_key_value_heads"],
        "head_dim": (model.get("head_dim")
                     or d // model["num_attention_heads"]),
        "window": model["sliding_window"], "ff": model["intermediate_size"],
        "d_inner": a["expand"] * d, "d_state": a["d_state"],
        "d_conv": a["d_conv"], "dt_rank": -(-d // 16),
        "eps": float(model["layer_norm_eps"]),
    }


def key_of(seed: int):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _shapes(sz: dict) -> dict:
    d, f, n, hd = sz["d"], sz["ff"], sz["layers"], sz["head_dim"]
    di, ns, kc, r = sz["d_inner"], sz["d_state"], sz["d_conv"], sz["dt_rank"]
    kinds = kinds_of(n)
    q, kv = sz["heads"] * hd, sz["kv_heads"] * hd

    def attn(kind):
        c = kinds.count(kind)
        proj = ({"w_q": (c, d, q), "b_q": (c, q)} if kind == "cross" else
                {"w_qkv": (c, d, q + 2 * kv), "b_qkv": (c, q + 2 * kv)})
        return {"norm_w": (c, d), "norm_b": (c, d), **proj,
                "w_o": (c, q, d), "b_o": (c, d), "lq1": (c, hd),
                "lk1": (c, hd), "lq2": (c, hd), "lk2": (c, hd),
                "subln_w": (c, 2 * hd)}

    s, g = kinds.count("ssm"), kinds.count("gmu")
    return {
        "embed": (sz["vocab"], d),
        "mlp": {"norm_w": (n, d), "norm_b": (n, d), "w1": (n, d, 2 * f),
                "w2": (n, f, d)},
        "ssm": {"norm_w": (s, d), "norm_b": (s, d), "w_in": (s, d, 2 * di),
                "conv_w": (s, kc, di), "conv_b": (s, di),
                "w_x": (s, di, r + 2 * ns), "w_dt": (s, r, di),
                "b_dt": (s, di), "a_log": (s, ns, di), "d_skip": (s, di),
                "w_out": (s, di, d)},
        "window": attn("window"), "full": attn("full"),
        "gmu": {"norm_w": (g, d), "norm_b": (g, d), "w_in": (g, d, di),
                "w_out": (g, di, d)},
        "cross": attn("cross"),
        "final_norm_w": (d,), "final_norm_b": (d,),
    }


def make_weights(sz: dict, seed: int) -> dict:
    """Weights from the seed, on the device, one jitted call a leaf."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(sz), is_leaf=lambda x: isinstance(x, tuple))
    key = key_of(seed)
    # 0.02 at the published width; a rehearsal's toy width gets matrices
    # large enough for a layer to matter to the logits.
    matrix_std = max(0.02, sz["d"] ** -0.5)

    @functools.partial(jax.jit, static_argnames=("shape", "form"))
    def draw(k, shape, form):
        n = jax.random.normal(k, shape, jnp.float32)
        if form == "gain":
            return (1.0 + 0.1 * n).astype(jnp.bfloat16)
        if form == "a_log":
            return jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)
                           )[None, :, None] + 0.1 * n
        if form == "d_skip":
            return 1.0 + 0.1 * n
        if form == "b_dt":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                         * math.log(100.0) + math.log(1e-3))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.bfloat16)
        std = {"lam": 0.1, "conv": 0.5}.get(form, matrix_std)
        return (std * n).astype(jnp.bfloat16)

    def form_of(name):
        if name.endswith("norm_w") or name == "subln_w":
            return "gain"
        if name in ("a_log", "d_skip", "b_dt"):
            return name
        if name.startswith(("lq", "lk")):
            return "lam"
        return "conv" if name == "conv_w" else "matrix"

    out = [draw(jax.random.fold_in(key, i), shape, form_of(path[-1].key))
           for i, (path, shape) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def reference_weights(weights: dict, quant: str) -> dict:
    """The weights as the configuration serves them: the tree itself
    (``logits`` widens a layer at a time). The configuration serves
    bfloat16 only."""
    assert quant in (None, "none"), quant
    return weights


# ------------------------------------------------------------------ forward
def _ln(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _mlp(h, w1, w2):
    g, u = jnp.split(h @ w1, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w2


def _ssm(h, p, *, sz):
    """h [S, D] -> (Mix [S, D], y [S, d_inner] before the z gate)."""
    di, ns, kc, r = sz["d_inner"], sz["d_state"], sz["d_conv"], sz["dt_rank"]
    s = h.shape[0]
    xz = h @ p["w_in"]
    x, z = xz[:, :di], xz[:, di:]
    padded = jnp.concatenate([jnp.zeros((kc - 1, di), x.dtype), x])
    conv = p["conv_b"] + sum(p["conv_w"][j] * padded[j:j + s]
                             for j in range(kc))
    xc = jax.nn.silu(conv)
    proj = xc @ p["w_x"]
    dt = jax.nn.softplus(proj[:, :r] @ p["w_dt"] + p["b_dt"])     # [S, di]
    bm, cm = proj[:, r:r + ns], proj[:, r + ns:]                  # [S, N]
    a = -jnp.exp(p["a_log"])                                      # [N, di]

    def step(hs, t):
        x_t, dt_t, b_t, c_t = t
        hs = (jnp.exp(dt_t[None, :] * a) * hs
              + (dt_t * x_t)[None, :] * b_t[:, None])
        return hs, jnp.sum(hs * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((ns, di), jnp.float32),
                        (xc, dt, bm, cm))
    y = y + p["d_skip"] * xc
    return (y * jax.nn.silu(z)) @ p["w_out"], y


def _probs_times(q, k, v, window: int):
    """softmax(q k^T / sqrt(d)) v, causal (and windowed): q [S, H, d], k
    [S, Hk, d], v [S, Hk, dv] with head h reading KV head h // (H / Hk);
    a block of queries at a time."""
    s, h, d = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
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
    return out.reshape(s, h, v.shape[-1])


def _diff_attn(h, p, kv, l, *, sz, window: int):
    """h [S, D] -> (Mix [S, D], (k, v) [S, Hkv, d]). ``kv`` given: the
    layer is a cross layer and projects queries only."""
    s = h.shape[0]
    nh, hkv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    if kv is None:
        qkv = h @ p["w_qkv"] + p["b_qkv"]
        q = qkv[:, :nh * hd].reshape(s, nh, hd)
        k = qkv[:, nh * hd:(nh + hkv) * hd].reshape(s, hkv, hd)
        v = qkv[:, (nh + hkv) * hd:].reshape(s, hkv, hd)
    else:
        q = (h @ p["w_q"] + p["b_q"]).reshape(s, nh, hd)
        k, v = kv
    q1, q2 = q[:, :nh // 2], q[:, nh // 2:]
    k1, k2 = k[:, :hkv // 2], k[:, hkv // 2:]
    vv = jnp.concatenate([v[:, :hkv // 2], v[:, hkv // 2:]], axis=-1)
    a1 = _probs_times(q1, k1, vv, window)                 # [S, H/2, 2d]
    a2 = _probs_times(q2, k2, vv, window)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * l)
    lam = (jnp.exp(jnp.sum(p["lq1"] * p["lk1"]))
           - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0)
    mixed = a1 - lam * a2
    mixed = (mixed * jax.lax.rsqrt(jnp.mean(mixed * mixed, -1, keepdims=True)
                                   + sz["eps"]) * p["subln_w"]) * (1 - lam0)
    return mixed.reshape(s, nh * hd) @ p["w_o"] + p["b_o"], (k, v)


def _layer(x, m, kv, p, mp, *, sz, kind: str, l: int):
    """One layer on x [S, D]: (x, m, kv); m and kv pass through where the
    layer makes none."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa
    p, mp = f32(p), f32(mp)
    h = _ln(x, p["norm_w"], p["norm_b"], sz["eps"])
    if kind == "ssm":
        mix, y = _ssm(h, p, sz=sz)
        if l == sz["layers"] // 2:
            m = y
    elif kind == "gmu":
        mix = (jax.nn.silu(h @ p["w_in"]) * m) @ p["w_out"]
    elif kind == "cross":
        mix, _ = _diff_attn(h, p, kv, l, sz=sz, window=0)
    else:
        mix, made = _diff_attn(h, p, None, l, sz=sz,
                               window=sz["window"] if kind == "window" else 0)
        if kind == "full":
            kv = made
    x = x + mix
    h = _ln(x, mp["norm_w"], mp["norm_b"], sz["eps"])
    return x + _mlp(h, mp["w1"], mp["w2"]), m, kv


def _head(x, at, w, b, embed, *, eps):
    return _ln(x[at], w.astype(jnp.float32), b.astype(jnp.float32),
               eps) @ embed.astype(jnp.float32).T


_JITTED: dict = {}


def _fns(sz: dict):
    key = tuple(sorted(sz.items()))
    if key not in _JITTED:
        _JITTED[key] = (
            {(kind, l): jax.jit(functools.partial(
                _layer, sz=dict(sz), kind=kind, l=l))
             for l, kind in enumerate(kinds_of(sz["layers"]))},
            jax.jit(functools.partial(_head, eps=sz["eps"])))
    return _JITTED[key]


def logits(w: dict, sz: dict, tokens, at) -> np.ndarray:
    """Reference logits of one token stream at positions ``at`` (each
    predicts the token after it). The stream is right-padded to a
    multiple of BLOCK (causal, so harmless). Layers run one after
    another, each widening only its own weights."""
    layer_fns, head = _fns(sz)
    toks = np.zeros((-(-len(tokens) // BLOCK) * BLOCK,), np.int32)
    toks[:len(tokens)] = tokens
    place = {k: 0 for k in ("ssm", "window", "full", "gmu", "cross")}
    take = lambda tree, i: jax.tree.map(lambda a: a[i], tree)   # noqa: E731
    di, hd = sz["d_inner"], sz["head_dim"]
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(toks)].astype(jnp.float32)
        m = jnp.zeros((len(toks), di), jnp.float32)
        kv = (jnp.zeros((len(toks), sz["kv_heads"], hd), jnp.float32),) * 2
        for l, kind in enumerate(kinds_of(sz["layers"])):
            x, m, kv = layer_fns[(kind, l)](
                x, m, kv, take(w[kind], place[kind]), take(w["mlp"], l))
            place[kind] += 1
        out = head(x, jnp.asarray(at, jnp.int32), w["final_norm_w"],
                   w["final_norm_b"], w["embed"])
    return np.asarray(out, np.float32)
