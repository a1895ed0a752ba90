"""Plain float32 reference of the Llama-family decoder (Llama, Mistral,
Qwen2): RMSNorm, rotary positions (half-split pairing, as HF's
``rotate_half``), grouped-query causal attention with an optional
sliding window, SwiGLU. Straightforward ``jax.numpy``; no cache, no
kernel, no batching tricks, every matmul at "highest" precision.

It takes its inputs from the seed and the configuration FILE alone: the
sizes are the published ``config.json`` keys, the weights are made here,
and the weight-only int8 quantisation the configuration states is done
here too, from the definition (symmetric, one scale per output channel:
scale = max|w| / 127 over the contraction axis, codes = round(w / scale)).
Nothing the program made — no weights, scales or tables — enters.

The weight tree has the layout the program's engine accepts through
``InferenceEngine(params=...)``: that layout is the interface between the
two (stacked layers, [in, out] matrices).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The matrices the configuration's "int8 weight-only" covers: every
# projection and the output head. Embedding table, norms and biases
# stay in bfloat16.
QUANTISED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head")


def sizes(model: dict, layers: int) -> dict:
    """Published config.json keys -> the sizes this file uses."""
    heads = model["num_attention_heads"]
    return {
        "vocab": model["vocab_size"], "d": model["hidden_size"],
        "layers": layers, "heads": heads,
        "kv_heads": model["num_key_value_heads"],
        "head_dim": model.get("head_dim") or model["hidden_size"] // heads,
        "ff": model["intermediate_size"],
        "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "window": (int(model.get("sliding_window") or 0)
                   if model.get("use_sliding_window", True) else 0),
        "qkv_bias": bool(model.get("attention_bias", False)),
    }


def key_of(seed: int):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(sz: dict, seed: int) -> dict:
    """bfloat16 weights from the seed, on the device, in ONE jitted call.
    Norm scales vary around 1 and (Qwen2) biases are non-zero, so a
    dropped scale or bias shows in the logits."""
    d, f, L = sz["d"], sz["ff"], sz["layers"]
    hq, hkv = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]

    def build(key):
        k = iter(jax.random.split(key, 16))

        def mat(*shape):
            return (0.02 * jax.random.normal(next(k), shape, jnp.float32)
                    ).astype(jnp.bfloat16)

        def scale(*shape):
            return (1.0 + 0.1 * jax.random.normal(next(k), shape,
                                                  jnp.float32)
                    ).astype(jnp.bfloat16)

        blocks = {
            "attn_norm": scale(L, d), "wq": mat(L, d, hq),
            "wk": mat(L, d, hkv), "wv": mat(L, d, hkv), "wo": mat(L, hq, d),
            "ffn_norm": scale(L, d), "w_gate": mat(L, d, f),
            "w_up": mat(L, d, f), "w_down": mat(L, f, d),
        }
        if sz["qkv_bias"]:
            blocks.update(bq=mat(L, hq), bk=mat(L, hkv), bv=mat(L, hkv))
        return {"embed": mat(sz["vocab"], d), "blocks": blocks,
                "final_norm": scale(d), "lm_head": mat(d, sz["vocab"])}

    return jax.jit(build)(key_of(seed))


def int8_per_channel(w):
    """The stated quantisation, then back to float32: what an exact int8
    weight-only model multiplies by."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-8) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def reference_weights(weights: dict, quant: str) -> dict:
    """float32 copies of the weights as the configuration serves them."""
    def leaf(path, w):
        name = path[-1].key
        if quant == "int8" and name in QUANTISED:
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


def _attention(q, k, v, window: int, block: int = 512):
    """q [S, Hq, D], k/v [S, Hkv, D] -> [S, Hq, D]. Query i sees keys
    j <= i, and with a window only j > i - window. Computed a block of
    queries at a time so a 4k context fits beside the engine."""
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
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(mask[None], sc, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    nb = (s + pad) // block
    out = jax.lax.map(one, (qp.reshape(nb, block, hq, d),
                            jnp.arange(nb) * block))
    return out.reshape(nb * block, hq, d)[:s]


def _forward(w, tokens, at, *, sz):
    """tokens [S] -> logits [len(at), V] at the positions ``at``."""
    s = tokens.shape[0]
    pos = jnp.arange(s)
    hd = sz["head_dim"]
    x = w["embed"][tokens]

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"], sz["eps"])
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        if sz["qkv_bias"]:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = _rope(q.reshape(s, sz["heads"], hd), pos, sz["theta"])
        k = _rope(k.reshape(s, sz["kv_heads"], hd), pos, sz["theta"])
        a = _attention(q, k, v.reshape(s, sz["kv_heads"], hd), sz["window"])
        x = x + a.reshape(s, -1) @ lp["wo"]
        h = _rms(x, lp["ffn_norm"], sz["eps"])
        x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
                 ) @ lp["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    x = _rms(x[at], w["final_norm"], sz["eps"])
    return x @ w["lm_head"]


_JITTED: dict = {}


def logits(w32: dict, sz: dict, tokens, at) -> np.ndarray:
    """Reference logits of one token stream at positions ``at`` (each
    predicts the token after it). float32, matmuls at 'highest'. The
    stream is right-padded to a multiple of 512 (causal, so harmless):
    streams of similar length share one compiled program."""
    key = tuple(sorted(sz.items()))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(functools.partial(_forward, sz=dict(sz)))
    toks = np.zeros((-(-len(tokens) // 512) * 512,), np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        out = _JITTED[key](w32, jnp.asarray(toks),
                           jnp.asarray(at, jnp.int32))
    return np.asarray(out, np.float32)
