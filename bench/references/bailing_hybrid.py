"""Plain float32 reference of the Ling-3.0-flash decoder (``bailing_hybrid``)
as ONE chip's share of an expert-parallel deployment. Straightforward
``jax.numpy``: the whole stream at once, no cache, no kernel, no batching;
the delta rule as the token-by-token recurrence it is written as (no
chunked form exists here), EXPANDED (not absorbed) latent attention, the
routed experts as a plain loop over the experts held here, every product
in float32 at "highest" precision. Nothing of the program is imported.

The equations (``h = RMSNorm(x)``, eps ``rms_norm_eps``, everywhere; every
layer is ``x += Mix(RMSNorm(x)); x += FFN(RMSNorm(x))``):

* Which layer is which. Published layer ``p`` is a latent-attention layer
  where ``(p + 1) % layer_group_size == 0`` and a KDA layer otherwise;
  the chip holds the published layers ``deployment.published_layers``
  (0 and 2..13: the two leading dense layers count once) and a depth cut
  keeps a prefix of them.
* KDA layer (Kimi delta attention, arXiv:2510.26692; H heads of d = 128
  for q, k and v alike). ``[q~ | k~ | v~] = h W_qkv``; each channel goes
  through a causal depthwise convolution of ``short_conv_kernel_size``
  taps (no bias; ``y_t = sum_j w_j x_{t - (taps - 1) + j}``, zeros in
  front of the stream) and a SiLU. Per head ``q = q / sqrt(|q|^2 + 1e-6)
  d^-0.5``, ``k = k / sqrt(|k|^2 + 1e-6)``. The decay a channel:
  ``g = kda_lower_bound sigmoid(exp(A_log_head) (h W_f + dt_bias))``, so
  g lies in (-5, 0); ``beta = sigmoid(h W_beta)`` a head. The state
  ``S [d, d]`` a head starts at zero and

      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  ``y = concat_heads(RMSNorm_d(o_head; gamma) sigmoid((h W_gate)_head))
  W_o``. No positions.
* Latent-attention layer. ``q = h W_q`` -> H heads of (nope | rope), no
  query compression. ``[c_kv | k_r] = h W_kva``; ``c_kv = RMSNorm(c_kv)``;
  ``k_r = RoPE(k_r)`` is ONE head shared by all query heads; ``q_r =
  RoPE(q_r)``. ``[k_nope_i | v_i] = c_kv W_kvb`` per head i. ``score_i =
  (nope + rope)^-0.5 (q_nope_i . k_nope_i + q_r_i . k_r)``, causal
  softmax, ``o_i = sum p v_i`` times ``sigmoid((h W_gate)_i)``, ``out =
  concat_i(o_i) W_o``. RoPE: plain frequencies ``theta^(-2j/D)`` on the
  rope dims only, half-split pairing ``(x_j, x_{j + D/2})`` (the published
  interleaved pairing is the same function under a fixed permutation of
  columns, which random weights do not tell apart).
* Layers 0 .. first_k_dense_replace - 1: SwiGLU of ``intermediate_size``.
* Expert layers: ``s = sigmoid(h W_r)`` over all published experts;
  ``s' = s + b`` (b the selection bias); the experts lie in ``n_group``
  groups of consecutive experts, a group's score is the sum of its two
  largest ``s'``, the best ``topk_group`` groups stay; the top k of ``s'``
  among the experts of those groups; ``g_e = routed_scaling_factor s_e /
  sum_selected s`` (without the bias); ``y = sum_{e in top-k} g_e E_e(h)
  + S(h)``, ``E_e(h) = (silu(h W_g,e) * (h W_u,e)) W_d,e``, ``S`` the
  shared expert.

Departures, each stated in the configuration file too:

* **This chip's share.** The sum over experts runs over ``top-k ∩ held``
  (the rank's ``num_experts`` of the published count: one routing group);
  ``g_e`` is still normalised over all k chosen; ``S(h)`` in full. What
  the absent experts would add is left out, and that partial result goes
  on to the next layer. The vocabulary is rows / columns 0 ..
  vocab_size - 1.
* Weights are made here from the seed: matrices normal with std 0.02,
  norm gains 1 + 0.1 n, convolution taps std 0.5, ``A_log = 0.1 n`` and
  ``dt_bias`` uniform in (-9, 0): the channels' time scales run from one
  token to over a thousand, so the fast ones stand at the gate's bound
  and the slow ones carry the state across the engine's chunks.
* **The selection bias decides membership** (bench/references/
  deepseek_v3.py HELD_MARGIN says why a bfloat16 activation must not:
  one flipped place in fifty is a whole expert's output). For the
  experts NOT held here the bias is 0.01 n. For the held group it is +2
  on HELD_CHOSEN experts a layer (drawn from the seed) and -2 on the
  rest, so the held group's score is ~5 where another's is ~1.5: it
  stays, and its +2 experts are among every token's k with gates that
  are the token's own. In every third expert layer (OUT_EVERY) one
  expert in each of ``topk_group`` OTHER groups gets +6 besides: those
  groups' scores are >= 6 where the held group's is < 6, so the group
  limit shuts the held group OUT and this chip's routed part is zero
  there, though its +2 experts would be among the top k of all experts:
  a router that ignores the groups computes three experts too many.

Sizes come from the configuration FILE alone. The weight tree has the
layout the program's engine accepts through ``InferenceEngine(params=)``
(stacked layers per kind, [in, out] matrices): that layout is the
interface between the two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512          # queries a block of attention; streams pad to it
HELD_CHOSEN = 3
HELD_MARGIN = 2.0
OUT_MARGIN = 6.0
OUT_EVERY = 3        # expert layer i shuts the held group out if i % 3 == 1
L2_EPS = 1e-6


def layer_kinds(model: dict, layers: int) -> tuple:
    """"kda" / "full" for each of the first ``layers`` layers held."""
    held = model["deployment"]["published_layers"][:layers]
    group = model["layer_group_size"]
    return tuple("full" if (p + 1) % group == 0 else "kda" for p in held)


def sizes(model: dict, layers: int) -> dict:
    """Published config.json keys (+ the file's ``published`` and
    ``deployment``) -> the sizes this file uses. ``layers`` counts the
    leading dense layer(s)."""
    dep = model["deployment"]
    held = model["num_experts"]
    return {
        "vocab": model["vocab_size"], "d": model["hidden_size"],
        "layers": layers, "kinds": layer_kinds(model, layers),
        "dense_layers": model["first_k_dense_replace"],
        "heads": model["num_attention_heads"],
        "kv_rank": model["kv_lora_rank"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "v": model["v_head_dim"], "ff": model["intermediate_size"],
        "moe_ff": model["moe_intermediate_size"],
        "shared": model["num_shared_experts"],
        "experts": model["published"]["num_experts"], "held": held,
        "first_held": dep["rank"] * held,
        "top_k": model["num_experts_per_tok"],
        "n_group": model["n_group"], "topk_group": model["topk_group"],
        "route_scale": float(model["routed_scaling_factor"]),
        "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "kda_heads": (model["num_kv_heads_for_linear_attn"]
                      or model["num_attention_heads"]),
        "kda_d": model["head_dim"],
        "taps": model["short_conv_kernel_size"],
        "bound": float(model["kda_lower_bound"]),
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
    nk = sz["kinds"].count("kda")
    nf = sz["kinds"].count("full")
    kh, kd = sz["kda_heads"], sz["kda_d"]
    w = kh * kd
    f, fs, e = sz["moe_ff"], sz["moe_ff"] * sz["shared"], sz["held"]
    return {
        "embed": (sz["vocab"], d),
        "kda": {"attn_norm": (nk, d), "w_qkv": (nk, d, 3 * w),
                "conv_w": (nk, sz["taps"], 3 * w), "w_f": (nk, d, w),
                "a_log": (nk, kh), "dt_bias": (nk, w),
                "w_beta": (nk, d, kh), "w_head_gate": (nk, d, kh),
                "o_norm": (nk, kd), "w_o": (nk, w, d)},
        "full": {"attn_norm": (nf, d),
                 "wq": (nf, d, h * (sz["nope"] + sz["rope"])),
                 "w_head_gate": (nf, d, h),
                 "wkv_a": (nf, d, sz["kv_rank"] + sz["rope"]),
                 "kv_norm": (nf, sz["kv_rank"]),
                 "wkv_b": (nf, sz["kv_rank"], h * (sz["nope"] + sz["v"])),
                 "wo": (nf, h * sz["v"], d)},
        "dense": {"ffn_norm": (nd, d), "w_gate": (nd, d, sz["ff"]),
                  "w_up": (nd, d, sz["ff"]), "w_down": (nd, sz["ff"], d)},
        "moe": {"ffn_norm": (ne, d), "w_router": (ne, d, sz["experts"]),
                "router_bias": (ne, sz["experts"]),
                "ws_gate": (ne, d, fs), "ws_up": (ne, d, fs),
                "ws_down": (ne, fs, d), "we_gate": (ne, e, d, f),
                "we_up": (ne, e, d, f), "we_down": (ne, e, f, d)},
        "final_norm": (d,), "lm_head": (d, sz["vocab"]),
    }


def _selection_bias(key, sz: dict, layers: int):
    """[layers, experts] float32: 0.01 n; +-HELD_MARGIN on the held
    group; in every OUT_EVERY-th layer OUT_MARGIN on one expert of each
    of ``topk_group`` other groups."""
    k_n, k_held, k_out = jax.random.split(key, 3)
    held, first, n = sz["held"], sz["first_held"], sz["experts"]
    size = n // sz["n_group"]
    chosen = min(HELD_CHOSEN, sz["top_k"] // 2, held)
    order = jax.vmap(lambda k: jax.random.permutation(k, held))(
        jax.random.split(k_held, layers))
    bias = 0.01 * jax.random.normal(k_n, (layers, n), jnp.float32)
    bias = bias.at[:, first:first + held].set(
        jnp.where(order < chosen, HELD_MARGIN, -HELD_MARGIN))
    mine = set(range(first // size, (first + held - 1) // size + 1))
    others = np.asarray([g for g in range(sz["n_group"]) if g not in mine])
    if len(others) < sz["topk_group"]:
        return bias
    for i in range(1, layers, OUT_EVERY):
        k = jax.random.fold_in(k_out, i)
        groups = others[np.asarray(jax.random.permutation(
            k, len(others)))[:sz["topk_group"]]]
        within = np.asarray(jax.random.randint(
            jax.random.fold_in(k, 1), (sz["topk_group"],), 0, size))
        bias = bias.at[i, groups * size + within].set(OUT_MARGIN)
    return bias


def make_weights(sz: dict, seed: int) -> dict:
    """bfloat16 weights from the seed (the selection bias, A_log and
    dt_bias float32), on the device, one jitted call a leaf."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(sz), is_leaf=lambda x: isinstance(x, tuple))
    key = key_of(seed)

    @functools.partial(jax.jit, static_argnames=("shape", "what"))
    def draw(k, shape, what):
        n = jax.random.normal(k, shape, jnp.float32)
        if what == "norm":
            return (1.0 + 0.1 * n).astype(jnp.bfloat16)
        if what == "a_log":
            return 0.1 * n
        if what == "dt_bias":
            return jax.random.uniform(k, shape, jnp.float32, -9.0, 0.0)
        return ((0.5 if what == "conv_w" else 0.02) * n).astype(jnp.bfloat16)

    out = []
    for i, (path, shape) in enumerate(leaves):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        if name == "router_bias":
            out.append(_selection_bias(k, sz, shape[0]))
        else:
            out.append(draw(k, shape, "norm" if "norm" in name else name))
    return jax.tree_util.tree_unflatten(treedef, out)


def reference_weights(weights: dict, quant: str) -> dict:
    """The weights as the configuration serves them: the bfloat16 tree
    itself (``logits`` widens a layer at a time)."""
    if quant not in (None, "none"):
        raise ValueError(f"this configuration is served unquantised, not "
                         f"{quant!r}")
    return weights


# ------------------------------------------------------------------ forward
def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def _rope(x, pos, theta):
    """x [S, H, D], pos [S]: rotate pairs (j, j + D/2)."""
    dim = x.shape[-1]
    half = dim // 2
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
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


def _latent_mix(h, lp, sz):
    s = h.shape[0]
    pos = jnp.arange(s)
    nh, dn, dr, dv, r = (sz["heads"], sz["nope"], sz["rope"], sz["v"],
                         sz["kv_rank"])
    q = (h @ lp["wq"]).reshape(s, nh, dn + dr)
    ckv = h @ lp["wkv_a"]
    c = _rms(ckv[:, :r], lp["kv_norm"], sz["eps"])
    k_rope = _rope(ckv[:, None, r:], pos, sz["theta"])         # [S, 1, Dr]
    kvb = (c @ lp["wkv_b"]).reshape(s, nh, dn + dv)
    o = _attention(q[..., :dn], _rope(q[..., dn:], pos, sz["theta"]),
                   kvb[..., :dn], k_rope, kvb[..., dn:], (dn + dr) ** -0.5)
    o = o * jax.nn.sigmoid(h @ lp["w_head_gate"])[:, :, None]
    return o.reshape(s, nh * dv) @ lp["wo"]


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _kda_mix(h, lp, sz):
    s = h.shape[0]
    nh, d, taps = sz["kda_heads"], sz["kda_d"], sz["taps"]
    w = nh * d
    qkv = h @ lp["w_qkv"]                                      # [S, 3W]
    padded = jnp.concatenate([jnp.zeros((taps - 1, 3 * w)), qkv], 0)
    conv = sum(lp["conv_w"][j] * padded[j:j + s] for j in range(taps))
    x = jax.nn.silu(conv)
    heads = lambda a: a.reshape(s, nh, d)                      # noqa: E731
    q = _l2(heads(x[:, :w])) * d ** -0.5
    k = _l2(heads(x[:, w:2 * w]))
    v = heads(x[:, 2 * w:])
    g = sz["bound"] * jax.nn.sigmoid(
        jnp.exp(lp["a_log"])[:, None] * heads(h @ lp["w_f"] + lp["dt_bias"]))
    beta = jax.nn.sigmoid(h @ lp["w_beta"])                    # [S, H]

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t               # [H, d] x4, [H]
        state = jnp.exp(g_t)[:, :, None] * state  # Diag(a) S
        # (I - beta k k^T) S' + beta k v^T = S' + beta k (v - S'^T k)^T
        rest = v_t - jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + (b_t[:, None] * k_t)[:, :, None] * rest[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, o = jax.lax.scan(token, jnp.zeros((nh, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, lp["o_norm"], sz["eps"]) \
        * jax.nn.sigmoid(h @ lp["w_head_gate"])[:, :, None]
    return o.reshape(s, w) @ lp["w_o"]


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def route(h, lp, sz):
    """h [S, D] -> (chosen experts [S, k], their gates [S, k])."""
    s = h.shape[0]
    sc = jax.nn.sigmoid(h @ lp["w_router"])                    # [S, E]
    ranked = sc + lp["router_bias"][None, :]
    groups = ranked.reshape(s, sz["n_group"], -1)
    score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)      # [S, G]
    _, stay = jax.lax.top_k(score, sz["topk_group"])
    kept = jnp.zeros((s, sz["n_group"]), bool).at[
        jnp.arange(s)[:, None], stay].set(True)
    ranked = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(s, -1)
    _, top = jax.lax.top_k(ranked, sz["top_k"])
    g = jnp.take_along_axis(sc, top, axis=1)
    return top, sz["route_scale"] * g / jnp.sum(g, axis=1, keepdims=True)


def _moe(h, lp, sz):
    top, g = route(h, lp, sz)
    y = _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])

    def expert(y, scanned):
        e, wg, wu, wd = (a.astype(jnp.float32) if a.ndim else a
                         for a in scanned)
        # This token's gate for held expert e: 0 unless it chose it.
        ge = jnp.sum(jnp.where(top == sz["first_held"] + e, g, 0.0), axis=1)
        return y + ge[:, None] * _swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(expert, y, (jnp.arange(sz["held"]), lp["we_gate"],
                                    lp["we_up"], lp["we_down"]))
    return y


def _layer(x, mp, fp, *, sz, kind: str, moe: bool):
    """One decoder layer on the whole stream x [S, D]; ``mp`` / ``fp`` one
    layer's mixer and FFN weights (any dtype; widened here)."""
    mp = {k: w.astype(jnp.float32) for k, w in mp.items()}
    fp = {k: w if k.startswith("we_") else w.astype(jnp.float32)
          for k, w in fp.items()}
    h = _rms(x, mp["attn_norm"], sz["eps"])
    x = x + (_kda_mix if kind == "kda" else _latent_mix)(h, mp, sz)
    h = _rms(x, fp["ffn_norm"], sz["eps"])
    if not moe:
        return x + _swiglu(h, fp["w_gate"], fp["w_up"], fp["w_down"])
    return x + _moe(h, fp, sz)


def _head(x, at, norm, head, *, eps):
    return _rms(x[at], norm, eps) @ head.astype(jnp.float32)


_JITTED: dict = {}


def _fns(sz: dict):
    key = tuple(sorted((k, v) for k, v in sz.items()))
    if key not in _JITTED:
        _JITTED[key] = (
            {(kind, moe): jax.jit(functools.partial(
                _layer, sz=dict(sz), kind=kind, moe=moe))
             for kind in ("kda", "full") for moe in (False, True)},
            jax.jit(functools.partial(_head, eps=sz["eps"])))
    return _JITTED[key]


def logits(w: dict, sz: dict, tokens, at) -> np.ndarray:
    """Reference logits of one token stream at positions ``at`` (each
    predicts the token after it). The stream is right-padded to a
    multiple of BLOCK (causal, so harmless). Layers run one after
    another, each widening only its own weights."""
    layer, head = _fns(sz)
    toks = np.zeros((-(-len(tokens) // BLOCK) * BLOCK,), np.int32)
    toks[:len(tokens)] = tokens
    nd = min(sz["dense_layers"], sz["layers"])
    seen = {"kda": 0, "full": 0}
    one = lambda tree, i: jax.tree.map(lambda a: a[i], tree)   # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for l, kind in enumerate(sz["kinds"]):
            moe = l >= nd
            fp = one(w["moe"], l - nd) if moe else one(w["dense"], l)
            x = layer[kind, moe](x, one(w[kind], seen[kind]), fp)
            seen[kind] += 1
        out = head(x, jnp.asarray(at, jnp.int32), w["final_norm"],
                   w["lm_head"])
    return np.asarray(out, np.float32)
