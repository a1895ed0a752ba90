"""Plain float32 reference of Ouro (ByteDance), a looped language model:
48 Llama-shaped layers (RMSNorm, rotary positions in the half-split
pairing of HF's ``rotate_half``, causal multi-head attention, SwiGLU) run
``total_ut_steps`` times a token with the SAME weights. Straightforward
``jax.numpy``; no cache, no kernel, no batching, every matmul at "highest"
precision. The equations (L layers, T passes):

    h = Embed[tokens]
    for t in 0..T-1:
        for l in 0..L-1:
            q, k, v = Wq_l x, Wk_l x, Wv_l x     x = RMS(h; g1_l); RoPE on q, k
            a = Wo_l softmax(q K^T / sqrt(D)) V  causal, over THIS pass's keys
            h = h + RMS(a; g2_l)
            m = Wdown_l(silu(Wgate_l y) * Wup_l y)      y = RMS(h; g3_l)
            h = h + RMS(m; g4_l)
        h = RMS(h; g_final)                      after EVERY pass, and fed on
        lambda_t = sigmoid(w_gate . h + b_gate)
    logits = W_head h                            h after the last pass

Having no cache, the reference cannot share keys and values between
passes: pass t's attention sees pass t's own keys of positions <= i, which
is the published "one cache entry per (pass, layer)".

Departures from the published description, each forced by having no
checkpoint and no network: the weights are random from the seed; what the
public ``config.json`` does not spell out is taken as the configuration
file's ``assumed`` lists it (four norms a layer, the final norm inside the
pass, the gate a Linear(d, 1) with bias, no projection bias, no QK-norm).

It takes its inputs from the seed and the configuration FILE alone.
The weight tree has the layout the program's engine accepts through
``InferenceEngine(params=...)`` (stacked layers, [in, out] matrices).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The matrices "int8 weight-only" (the parity control) covers.
QUANTISED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head")


def sizes(model: dict, layers: int) -> dict:
    """Published config.json keys -> the sizes this file uses. ``layers``
    is the depth of ONE pass; the pass count is the file's own."""
    heads = model["num_attention_heads"]
    return {
        "vocab": model["vocab_size"], "d": model["hidden_size"],
        "layers": layers, "passes": int(model["total_ut_steps"]),
        "heads": heads, "kv_heads": model["num_key_value_heads"],
        "head_dim": model.get("head_dim") or model["hidden_size"] // heads,
        "ff": model["intermediate_size"],
        "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "exit_threshold": float(model["early_exit_threshold"]),
    }


def key_of(seed: int):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(sz: dict, seed: int) -> dict:
    """bfloat16 weights from the seed, on the device, in ONE jitted call.
    Every norm gain varies around 1 (1 + 0.1 n) and the gate is not zero,
    so a skipped or swapped norm shows in the logits and a dropped gate
    term in the exit probabilities."""
    d, f, L = sz["d"], sz["ff"], sz["layers"]
    hq, hkv = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]

    def build(key):
        k = iter(jax.random.split(key, 20))

        def mat(*shape, std=0.02):
            return (std * jax.random.normal(next(k), shape, jnp.float32)
                    ).astype(jnp.bfloat16)

        def gain(*shape):
            return (1.0 + 0.1 * jax.random.normal(next(k), shape,
                                                  jnp.float32)
                    ).astype(jnp.bfloat16)

        blocks = {
            "attn_norm": gain(L, d), "wq": mat(L, d, hq),
            "wk": mat(L, d, hkv), "wv": mat(L, d, hkv), "wo": mat(L, hq, d),
            "attn_out_norm": gain(L, d), "ffn_norm": gain(L, d),
            "w_gate": mat(L, d, f), "w_up": mat(L, d, f),
            "w_down": mat(L, f, d), "ffn_out_norm": gain(L, d),
        }
        return {"embed": mat(sz["vocab"], d), "blocks": blocks,
                "final_norm": gain(d), "exit_gate_w": mat(d),
                "exit_gate_b": mat(std=0.5), "lm_head": mat(d, sz["vocab"])}

    return jax.jit(build)(key_of(seed))


def int8_per_channel(w):
    """Symmetric int8 with one scale per output channel, back in float32."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-8) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def reference_weights(weights: dict, quant: str) -> dict:
    """float32 copies of the weights as the configuration serves them."""
    def leaf(path, w):
        if quant == "int8" and path[-1].key in QUANTISED:
            return int8_per_channel(w)
        return w.astype(jnp.float32)

    return jax.tree_util.tree_map_with_path(leaf, weights)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, H, D], pos [S]: rotate pairs (i, i + D/2)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, block: int = 256):
    """q [S, Hq, D], k/v [S, Hkv, D] -> [S, Hq, D]; query i sees keys
    j <= i. A block of queries at a time, so that it fits."""
    s, hq, d = q.shape
    rep = hq // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    pad = (-s) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    kpos = jnp.arange(s)

    def one(args):
        qb, start = args
        qpos = start + jnp.arange(block)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(d))
        sc = jnp.where((kpos[None, :] <= qpos[:, None])[None], sc, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    nb = (s + pad) // block
    out = jax.lax.map(one, (qp.reshape(nb, block, hq, d),
                            jnp.arange(nb) * block))
    return out.reshape(nb * block, hq, d)[:s]


def _passes(w, tokens, *, sz):
    """tokens [S] -> each pass's normed hidden states [T, S, d]."""
    s = tokens.shape[0]
    pos = jnp.arange(s)
    hd, eps = sz["head_dim"], sz["eps"]

    def layer(h, lp):
        x = _rms(h, lp["attn_norm"], eps)
        q = _rope((x @ lp["wq"]).reshape(s, sz["heads"], hd), pos,
                  sz["theta"])
        k = _rope((x @ lp["wk"]).reshape(s, sz["kv_heads"], hd), pos,
                  sz["theta"])
        a = _attention(q, k, (x @ lp["wv"]).reshape(s, sz["kv_heads"], hd))
        h = h + _rms(a.reshape(s, -1) @ lp["wo"], lp["attn_out_norm"], eps)
        y = _rms(h, lp["ffn_norm"], eps)
        m = (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) @ lp["w_down"]
        return h + _rms(m, lp["ffn_out_norm"], eps), None

    h = w["embed"][tokens]
    out = []
    for _ in range(sz["passes"]):
        h, _ = jax.lax.scan(layer, h, w["blocks"])
        h = _rms(h, w["final_norm"], eps)
        out.append(h)
    return jnp.stack(out)


def _forward(w, tokens, at, *, sz):
    """tokens [S] -> logits [len(at), V] at the positions ``at``."""
    return _passes(w, tokens, sz=sz)[-1][at] @ w["lm_head"]


def _exit(w, tokens, *, sz):
    """tokens [S] -> exit probabilities [T, S]: p_t = lambda_t *
    prod_{s<t} (1 - lambda_s) for t < T - 1, the last pass the rest."""
    h = _passes(w, tokens, sz=sz)
    lam = jax.nn.sigmoid(h @ w["exit_gate_w"] + w["exit_gate_b"])
    probs, stay = [], jnp.ones_like(lam[0])
    for t in range(sz["passes"] - 1):
        probs.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(probs + [stay])


_JITTED: dict = {}


def _jitted(fn, sz):
    key = (fn.__name__,) + tuple(sorted(sz.items()))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(functools.partial(fn, sz=dict(sz)))
    return _JITTED[key]


def _padded(tokens):
    """Right-padded to a multiple of 256 (causal, so harmless): streams
    of similar length share one compiled program."""
    toks = np.zeros((-(-len(tokens) // 256) * 256,), np.int32)
    toks[:len(tokens)] = tokens
    return jnp.asarray(toks)


def logits(w32: dict, sz: dict, tokens, at) -> np.ndarray:
    """Reference logits of one token stream at positions ``at`` (each
    predicts the token after it). float32, matmuls at 'highest'."""
    with jax.default_matmul_precision("highest"):
        out = _jitted(_forward, sz)(w32, _padded(tokens),
                                    jnp.asarray(at, jnp.int32))
    return np.asarray(out, np.float32)


def exit_probabilities(w32: dict, sz: dict, tokens) -> np.ndarray:
    """[T, len(tokens)]: the probability that each token leaves at each
    pass, under the published rule."""
    with jax.default_matmul_precision("highest"):
        out = _jitted(_exit, sz)(w32, _padded(tokens))
    return np.asarray(out, np.float32)[:, :len(tokens)]


def exit_pass(probs: np.ndarray, threshold: float) -> np.ndarray:
    """The first pass at which the cumulative exit probability reaches
    ``threshold``; the last where none does, and always at 1.0 (the
    published configuration: no early exit)."""
    last = probs.shape[0] - 1
    if threshold >= 1.0:
        return np.full(probs.shape[1:], last)
    reached = np.cumsum(probs, axis=0) >= threshold
    reached[last] = True
    return np.argmax(reached, axis=0)
