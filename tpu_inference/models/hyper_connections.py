"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on the
hyper-connections of arXiv:2409.19606), as pure JAX: a token carries
``n = cfg.hc_mult`` residual streams, and each sublayer F (attention,
then the FFN; each with its own ``phi``, ``b``, ``alpha``) reads a mix of
them and writes back through a doubly stochastic matrix:

    x~      = RMSNorm(vec(X))                   no gain, eps hc_eps, float32
    H~_pre  = a_pre  (x~ phi_pre)  + b_pre      [n]
    H~_post = a_post (x~ phi_post) + b_post     [n]
    H~_res  = a_res  mat(x~ phi_res) + b_res    [n, n]
    H_pre   = sigmoid(H~_pre)     H_post = 2 sigmoid(H~_post)
    H_res   = Sinkhorn(exp(clip(H~_res, -clamp, clamp)))
    h       = H_pre X                           the sublayer's input [D]
    X'      = H_res X + H_post^T F(RMSNorm(h; g))

The streams are carried FLAT, ``[B, S, n * D]`` (stream j is lanes
``j D .. (j + 1) D``: ``vec(X)`` as stored, and a stream is a lane-aligned
slice where ``[.., n, D]`` would put four rows into a sixteen-row tile).
A sublayer's ``n (n + 2)`` coefficients a token are one vector, ``[pre
(n) | post (n) | res (n x n, row-major)]``: ``phi`` is ``[n D, n (n +
2)]``, ``b`` ``[n (n + 2)]`` and ``alpha`` ``[3]`` (b and alpha float32;
none of them is quantised or stored transposed: models/quant.py names
leaves, and names none of these). The head and the projection run with
the TOKENS in the minor dim (``[coefficient, T]``): twenty iterations
over sixteen numbers a token are then a few hundred vector operations a
chunk, which the compiler fuses; the result is transposed once for the
mixes, which want a column a coefficient. Coefficients are float32, mixes
accumulate in float32 and store the streams' dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpu_inference.config import ModelConfig

SUBLAYERS = ("attn", "ffn")
# kv.aux slots behind the routing counts (models/deepseek_v3.py): sublayer
# applications x valid tokens, summed; and the largest |sum - 1| over the
# rows AND columns of any H_res seen, in parts per million (rows are
# normalised last, so theirs is rounding: the columns' is what fewer
# iterations move). The second folds by MAX (``aux_max_slots``).
MHC_STATS = ("mixes", "row_sum_err_ppm")
# The float32 leaves (``phi`` is drawn like every matrix).
FLOAT32_LEAVES = tuple(f"hc_{s}_{leaf}" for s in SUBLAYERS
                       for leaf in ("b", "alpha"))


def init_float32(name: str, key: jax.Array, shape: tuple):
    """A random init's draw of a float32 leaf of this module, or None for
    any other name: ``alpha`` 0.01, the published initialisation's order
    (a layer's matrices hardly differ by token); ``b`` normal, std 1, so
    that every layer mixes its streams by matrices of its own."""
    if name not in FLOAT32_LEAVES:
        return None
    if name.endswith("_alpha"):
        return jnp.full(shape, 0.01, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32)


def n_coeff(cfg: ModelConfig) -> int:
    return cfg.hc_mult * (cfg.hc_mult + 2)


def shapes(cfg: ModelConfig, n_layers: int) -> dict:
    """The leaves a stack of ``n_layers`` layers carries, three a
    sublayer; none where the residual is plain."""
    if cfg.hc_mult == 1:
        return {}
    c, wide = n_coeff(cfg), cfg.hc_mult * cfg.d_model
    return {f"hc_{s}_{leaf}": shape for s in SUBLAYERS
            for leaf, shape in (("phi", (n_layers, wide, c)),
                                ("b", (n_layers, c)),
                                ("alpha", (n_layers, 3)))}


def fan_out(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """The embedding in each of the n streams: [B, S, D] -> [B, S, n D]."""
    return jnp.tile(x, (1, 1, cfg.hc_mult))


def _streams(cfg: ModelConfig, x: jax.Array) -> list:
    d = cfg.d_model
    return [x[..., j * d:(j + 1) * d].astype(jnp.float32)
            for j in range(cfg.hc_mult)]


def read_out(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """The SUM of the streams, which the final norm reads."""
    return sum(_streams(cfg, x)).astype(x.dtype)


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """m [n, n, T] positive (row, column, token) -> ``iters`` times:
    every column over its sum, then every row over its sum, ``eps`` in
    each divisor. Unrolled: no loop reaches the compiler."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def _sum_error(m: jax.Array) -> jax.Array:
    """The largest |sum - 1| over the rows and columns of m [n, n, T], a
    token: [T]."""
    return jnp.maximum(
        jnp.max(jnp.abs(jnp.sum(m, axis=0) - 1.0), axis=0),
        jnp.max(jnp.abs(jnp.sum(m, axis=1) - 1.0), axis=0))


def stream_scale(x2: jax.Array, eps: float) -> jax.Array:
    """x2 [T, n D] -> the stream norm's scale a token, float32 [T]. The
    norm has no gain, so it is this one number a token, and it goes on
    the n (n + 2) products of the head and not on its n D inputs."""
    xf = x2.astype(jnp.float32)
    return jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + eps)


def coefficients(cfg: ModelConfig, lp: dict, sublayer: str, x: jax.Array,
                 valid=None):
    """x [B, S, n D] -> (coef [B, S, n (n + 2)] float32: H_pre | H_post |
    H_res row-major; the largest |sum - 1| over H_res's rows and columns
    among the ``valid`` [B, S] tokens, float32 scalar)."""
    n = cfg.hc_mult
    b, s, wide = x.shape
    phi, bias, alpha = (lp[f"hc_{sublayer}_{k}"]
                        for k in ("phi", "b", "alpha"))
    with jax.named_scope("mhc_coeff"):
        x2 = x.reshape(b * s, wide)
        inv = stream_scale(x2, cfg.hc_eps)
        # Stored values are exact in float32 whatever their dtype:
        # bfloat16 streams times a bfloat16 phi is ONE pass with exact
        # products.
        z = jnp.einsum("tk,kc->ct", x2, phi,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
        scale = alpha.astype(jnp.float32)[
            np.repeat(np.arange(3), [n, n, n * n])]
        z = (z * inv[None, :] * scale[:, None]
             + bias.astype(jnp.float32)[:, None])
        pre = jax.nn.sigmoid(z[:n])
        post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    with jax.named_scope("mhc_sinkhorn"):
        res = jnp.exp(jnp.clip(z[2 * n:], -cfg.hc_res_clamp,
                               cfg.hc_res_clamp)).reshape(n, n, b * s)
        res = sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)
        off = _sum_error(res)                           # [T]
        if valid is not None:
            off = jnp.where(valid.reshape(b * s), off, 0.0)
        coef = jnp.concatenate([pre, post, res.reshape(n * n, b * s)])
        return coef.T.reshape(b, s, n * (n + 2)), jnp.max(off)


def pre_mix(cfg: ModelConfig, coef: jax.Array, x: jax.Array) -> jax.Array:
    """h = H_pre X: [B, S, n D] -> [B, S, D], the sublayer's input."""
    with jax.named_scope("mhc_pre_mix"):
        xs = _streams(cfg, x)
        return sum(coef[..., j:j + 1] * xs[j]
                   for j in range(cfg.hc_mult)).astype(x.dtype)


def post_mix(cfg: ModelConfig, coef: jax.Array, x: jax.Array,
             y: jax.Array) -> jax.Array:
    """X' = H_res X + H_post^T y: stream i is row i of H_res over the
    streams plus its own share of the sublayer's output y [B, S, D]."""
    n = cfg.hc_mult
    with jax.named_scope("mhc_post_mix"):
        xs, yf = _streams(cfg, x), y.astype(jnp.float32)
        out = [sum(coef[..., 2 * n + n * i + j:2 * n + n * i + j + 1] * xs[j]
                   for j in range(n)) + coef[..., n + i:n + i + 1] * yf
               for i in range(n)]
        return jnp.concatenate(out, axis=-1).astype(x.dtype)
