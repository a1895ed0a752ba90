"""SambaY (Phi-4-mini-flash-reasoning, arXiv:2507.06607): a decoder whose
first half alternates state-space scans with window attention, one
full-attention layer whose keys and values are the model's only
full-context cache, and a second half of gated memory units and
cross-attention layers that read that one cache, as pure JAX.

Every layer l is ``x = x + Mix_l(LN(x)); x = x + MLP(LN(x))`` (LayerNorm
with bias, a gated SiLU MLP with one fused up-projection). The kind of
``Mix_l`` is derived from l and the depth (config.sambay_layer_kinds):

- ``ssm``: a Mamba-1 selective scan. Its state (the conv's last
  ``d_conv - 1`` inputs and ``h``) belongs to a SEQUENCE and a token
  ADVANCES it; the model reads and writes it through ``attn.state`` and
  never names a pool or a slot. The middle layer's scan output (before
  the ``z`` gate) is handed down, for the same token, as ``m``.
- ``window`` / ``full``: differential attention (below) over the last
  ``sliding_window`` keys / the whole context: ``attn.kinds[kind]``.
- ``gmu``: ``(silu(h W_in) * m) W_out``. No state.
- ``cross``: differential attention with a query projection only, over
  the ``full`` layer's K / V: ``attn.kinds["cross"]``. No state.

No rotary or learned positions: order comes from the scans.

**Differential attention on an ordinary GQA attention function.** Heads
split in halves (q1, q2 / k1, k2 / v1, v2); ``A1 = softmax(q1 k1^T /
sqrt(d)) [v1 | v2]``, ``A2`` likewise from q2, k2; the layer's output is
``RMSNorm_2d(A1 - lam A2) (1 - lam0)``. A PAIR head j is stored as
``K'_j = [k1_j | k2_j]``, ``V'_j = [v1_j | v2_j]`` (2d wide) and queried
with ``q1' = sqrt(2) [q1 | 0]`` and ``q2' = sqrt(2) [0 | q2]``: an
attention function that scales by ``1 / sqrt(2d)`` then returns A1 for
q1' and A2 for q2', reading each K and V byte once. Query heads are
ordered so that pair head j serves ``{q1_2j, q1_2j+1, q2_2j, q2_2j+1}``
(group size 2 * n_rep).

**One traced body a pair.** Layers below the middle are (ssm, window)
pairs, those behind the full layer (gmu, cross) pairs: each run of pairs
is one ``lax.scan`` whose body indexes the stacked parameters.

**The cross-decoder runs where it is needed.** Layers behind the full
one write no state, so a prefill that samples from one position runs
them for that position alone: ``forward_hidden(..., cross_at=[B])``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_inference.config import ModelConfig
from tpu_inference.models.common import (AttentionFn, dense_causal_attention,
                                         layer_norm, linear, rms_norm)
from tpu_inference.models.quant import qdot

# Counters the model adds to inside any graph (``kv.aux``): prompt
# positions a prefill program ran its first half for, and positions it
# ran the cross-decoder for.
AUX_STATS = ("prefill_positions", "prefill_cross_positions")


def n_aux_stats(cfg: ModelConfig) -> int:
    return len(AUX_STATS)


def attn_pair_dim(cfg: ModelConfig) -> int:
    """What a (query head, key) pair costs the attention function, as a
    head width: 2 d, the pair head's (the zero half of a padded query
    doubles QK^T; 3/4 of it is the differential form's own)."""
    return 2 * cfg.head_dim


def lam0(l):
    """Differential attention's depth-dependent constant."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, jnp.float32))


def _attn_shapes(cfg: ModelConfig, n: int, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    proj = ({"w_q": (n, d, q), "b_q": (n, q)} if cross else
            {"w_qkv": (n, d, q + 2 * kv), "b_qkv": (n, q + 2 * kv)})
    return {"norm_w": (n, d), "norm_b": (n, d), **proj,
            "w_o": (n, q, d), "b_o": (n, d),
            "lq1": (n, hd), "lk1": (n, hd), "lq2": (n, hd), "lk2": (n, hd),
            "subln_w": (n, 2 * hd)}


def param_shapes(cfg: ModelConfig) -> dict:
    """The tree's leaf shapes (bench/references/sambay.py builds the same
    tree from the configuration file)."""
    d, f, n = cfg.d_model, cfg.d_ff, cfg.n_layers
    di, ns, kc, r = cfg.d_inner, cfg.ssm_d_state, cfg.ssm_d_conv, cfg.dt_rank
    count = {k: len(cfg.kind_layers(k)) for k in set(cfg.layer_types)}
    s, g = count["ssm"], count["gmu"]
    return {
        "embed": (cfg.vocab_size, d),
        "mlp": {"norm_w": (n, d), "norm_b": (n, d), "w1": (n, d, 2 * f),
                "w2": (n, f, d)},
        "ssm": {"norm_w": (s, d), "norm_b": (s, d), "w_in": (s, d, 2 * di),
                "conv_w": (s, kc, di), "conv_b": (s, di),
                "w_x": (s, di, r + 2 * ns), "w_dt": (s, r, di),
                "b_dt": (s, di), "a_log": (s, ns, di), "d_skip": (s, di),
                "w_out": (s, di, d)},
        "window": _attn_shapes(cfg, count["window"]),
        "full": _attn_shapes(cfg, count["full"]),
        "gmu": {"norm_w": (g, d), "norm_b": (g, d), "w_in": (g, d, di),
                "w_out": (g, di, d)},
        "cross": _attn_shapes(cfg, count["cross"], cross=True),
        "final_norm_w": (d,), "final_norm_b": (d,),
    }


def _leaves(cfg: ModelConfig):
    return jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))


def param_count(cfg: ModelConfig, active: bool = False) -> int:
    """Parameters, counted off the leaf shapes (every one is active)."""
    del active
    return int(sum(math.prod(shape) for _, shape in _leaves(cfg)[0]))


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random init, one jitted draw a leaf: matrices normal with std
    d_model ** -0.5 (0.02 at the published width), the lambda vectors
    std 0.1; norm gains 1 and biases 0; the scan's own as Mamba-1 sets
    them: ``A = -(1 .. N)``, ``D = 1``, and a dt bias whose softplus is
    log-uniform in [1e-3, 1e-1]. ``a_log`` and ``d_skip`` stay float32."""
    cfg.validate()
    leaves, treedef = _leaves(cfg)
    std = cfg.d_model ** -0.5

    @partial(jax.jit, static_argnames=("shape", "dtype", "std"))
    def draw(k, shape, dtype, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    out = []
    for i, (path, shape) in enumerate(leaves):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        if name.endswith("norm_w") or name == "subln_w":
            out.append(jnp.ones(shape, cfg.dtype))
        elif name in ("norm_b", "final_norm_b", "conv_b", "b_o", "b_q",
                      "b_qkv"):
            out.append(jnp.zeros(shape, cfg.dtype))
        elif name == "a_log":
            out.append(jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32))[None, :, None], shape))
        elif name == "d_skip":
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "b_dt":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                         * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
            out.append((dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.dtype))
        elif name.startswith(("lq", "lk")):
            out.append(draw(k, shape, cfg.dtype, 0.1))
        elif name == "conv_w":
            out.append(draw(k, shape, cfg.dtype, shape[1] ** -0.5))
        else:
            out.append(draw(k, shape, cfg.dtype, std))
    return jax.tree_util.tree_unflatten(treedef, out)


def mlp(lp: dict, h: jax.Array) -> jax.Array:
    """``(silu(g) * u) W2`` with ``[g, u] = h W1``."""
    gu = qdot(h, lp["w1"])
    g, u = jnp.split(gu, 2, axis=-1)
    return qdot((jax.nn.silu(g) * u).astype(h.dtype), lp["w2"]).astype(h.dtype)


def ssm_mix(cfg: ModelConfig, slot, lp: dict, h: jax.Array, kv: Any,
            attn: AttentionFn):
    """A Mamba-1 mixer over h [B, S, D] (normed): (output [B, S, D], the
    scan's output before the z gate [B, S, d_inner], kv). The state of
    each lane comes from and goes back through ``attn.state``; positions
    behind ``attn.state.lens`` advance nothing."""
    b, s, _ = h.shape
    di, ns, kc, r = cfg.d_inner, cfg.ssm_d_state, cfg.ssm_d_conv, cfg.dt_rank
    st = attn.state
    with jax.named_scope("ssm_scan" if s > 1 else "ssm_step"):
        xz = qdot(h, lp["w_in"]).astype(h.dtype)
        x, z = xz[..., :di], xz[..., di:]
        tail, h0 = st.read(slot, kv)       # [B, kc - 1, di], [B, N, di] f32
        seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        conv = lp["conv_b"].astype(jnp.float32)
        for j in range(kc):
            conv = conv + (lp["conv_w"][j].astype(jnp.float32)
                           * seq[:, j:j + s].astype(jnp.float32))
        xc = jax.nn.silu(conv).astype(h.dtype)
        # The tail a lane leaves: the last kc - 1 inputs among its VALID
        # ones (rows lens .. of [tail | x]); none valid: the tail it had.
        at = st.lens[:, None] + jnp.arange(kc - 1)[None, :]
        tail = jnp.take_along_axis(seq, at[..., None], axis=1)
        proj = qdot(xc, lp["w_x"])                        # f32 [B, S, r+2N]
        dt = jax.nn.softplus(
            qdot(proj[..., :r].astype(h.dtype), lp["w_dt"])
            + lp["b_dt"].astype(jnp.float32))
        bm, cm = proj[..., r:r + ns], proj[..., r + ns:]
        a_t = -jnp.exp(lp["a_log"].astype(jnp.float32))   # [N, di]
        d_skip = lp["d_skip"].astype(jnp.float32)
        if s == 1:
            # One step, plain XLA: the state's bytes in and out.
            live = (st.lens > 0)[:, None]
            dt1 = jnp.where(live, dt[:, 0], 0.0)
            x1 = xc[:, 0].astype(jnp.float32)
            ht = (jnp.exp(dt1[:, None, :] * a_t[None]) * h0
                  + (dt1 * x1)[:, None, :] * bm[:, 0, :, None])
            y = (jnp.sum(ht * cm[:, 0, :, None], axis=1)
                 + d_skip * x1)[:, None].astype(h.dtype)
        else:
            y, ht = st.scan(xc, dt, bm, cm, a_t, d_skip, h0)
        kv = st.write(slot, tail, ht, kv)
        out = qdot((y.astype(jnp.float32)
                    * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype),
                   lp["w_out"]).astype(h.dtype)
    return out, y, kv


def pair_heads(cfg: ModelConfig, k: jax.Array) -> jax.Array:
    """K or V [B, S, Hkv * d] -> pair heads [B, S, Hkv / 2, 2 d]: head j
    of the first half beside head j of the second."""
    b, s, _ = k.shape
    half, hd = cfg.n_kv_heads // 2, cfg.head_dim
    return k.reshape(b, s, 2, half, hd).transpose(0, 1, 3, 2, 4).reshape(
        b, s, half, 2 * hd)


def diff_queries(cfg: ModelConfig, q: jax.Array, dtype) -> jax.Array:
    """q float32 [B, S, H * d] -> the padded queries [B, S, H, 2 d]:
    pair head j's group is (q1_2j', q1_2j+1', q2_2j', q2_2j+1') with
    q1' = sqrt(2) [q1 | 0], q2' = sqrt(2) [0 | q2]."""
    b, s, _ = q.shape
    half, rep, hd = cfg.n_kv_heads // 2, cfg.n_rep, cfg.head_dim
    q = (q * math.sqrt(2.0)).astype(dtype).reshape(b, s, 2, half, rep, hd)
    zero = jnp.zeros_like(q[:, :, 0])
    q1 = jnp.concatenate([q[:, :, 0], zero], axis=-1)
    q2 = jnp.concatenate([zero, q[:, :, 1]], axis=-1)
    return jnp.stack([q1, q2], axis=3).reshape(b, s, cfg.n_heads, 2 * hd)


def diff_attention(cfg: ModelConfig, kind: str, slot, l, ap: dict,
                   h: jax.Array, kv: Any, attn: AttentionFn):
    """Differential attention of layer ``l`` (its depth sets lam0) over h
    [B, S, D] (normed), at place ``slot`` among its kind."""
    b, s, _ = h.shape
    hd, half, rep = cfg.head_dim, cfg.n_kv_heads // 2, cfg.n_rep
    nq = cfg.n_heads * hd
    with jax.named_scope("attn_" + kind):
        if kind == "cross":
            q = qdot(h, ap["w_q"]) + ap["b_q"].astype(jnp.float32)
            k = v = None
        else:
            qkv = qdot(h, ap["w_qkv"]) + ap["b_qkv"].astype(jnp.float32)
            nkv = cfg.n_kv_heads * hd
            q = qkv[..., :nq]
            k = pair_heads(cfg, qkv[..., nq:nq + nkv].astype(h.dtype))
            v = pair_heads(cfg, qkv[..., nq + nkv:].astype(h.dtype))
        out, kv = attn.kinds[kind](slot, diff_queries(cfg, q, h.dtype), k, v,
                                   kv)
        out = out.astype(jnp.float32).reshape(b, s, half, 2, rep, 2 * hd)
        f32 = lambda a: a.astype(jnp.float32)                # noqa: E731
        lam_0 = lam0(l)
        lam = (jnp.exp(jnp.sum(f32(ap["lq1"]) * f32(ap["lk1"])))
               - jnp.exp(jnp.sum(f32(ap["lq2"]) * f32(ap["lk2"]))) + lam_0)
        mixed = rms_norm(out[:, :, :, 0] - lam * out[:, :, :, 1],
                         ap["subln_w"], cfg.norm_eps) * (1.0 - lam_0)
        return linear(mixed.astype(h.dtype).reshape(b, s, nq), ap["w_o"],
                      ap["b_o"]), kv


def forward_hidden(params: dict, cfg: ModelConfig, tokens: jax.Array,
                   positions: jax.Array, kv: Any, attn: AttentionFn,
                   cross_at: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Any]:
    """Token ids -> final hidden states. tokens [B, S]; positions are
    not read (the model has none). ``cross_at`` [B]: the one index along
    S each row's cross-decoder runs for (the result is then [B, 1, D]);
    None: every position."""
    del positions
    n, mid = cfg.n_layers, cfg.n_layers // 2
    x = params["embed"][tokens].astype(cfg.dtype)
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)   # noqa: E731

    def block(x, l, mix):
        """Layer l around ``mix(h) -> (out, extra)``."""
        mp = at(params["mlp"], l)
        out, extra = mix(x, l)
        x = x + out
        h = layer_norm(x, mp["norm_w"], mp["norm_b"], cfg.norm_eps)
        return x + mlp(mp, h), extra

    def normed(x, lp):
        return layer_norm(x, lp["norm_w"], lp["norm_b"], cfg.norm_eps)

    def ssm_layer(x, kv, slot):
        lp = at(params["ssm"], slot)

        def mix(x, l):
            out, y, kv2 = ssm_mix(cfg, slot, lp, normed(x, lp), kv, attn)
            return out, (y, kv2)
        x, (y, kv) = block(x, 2 * slot, mix)
        return x, y, kv

    def attn_layer(x, kv, kind, slot, l):
        lp = at(params[kind], slot)

        def mix(x, l):
            return diff_attention(cfg, kind, slot, l, lp, normed(x, lp), kv,
                                  attn)
        return block(x, l, mix)

    def self_pair(carry, i):
        x, kv = carry
        x, _, kv = ssm_layer(x, kv, i)
        x, kv = attn_layer(x, kv, "window", i, 2 * i + 1)
        return (x, kv), None

    (x, kv), _ = jax.lax.scan(self_pair, (x, kv), jnp.arange(mid // 2))
    x, m, kv = ssm_layer(x, kv, mid // 2)
    x, kv = attn_layer(x, kv, "full", 0, mid + 1)

    aux = getattr(kv, "aux", None)
    if aux is not None and tokens.shape[1] > 1:
        # A prefill program: what its two halves ran for.
        live = jnp.sum(attn.valid, axis=1)
        ran = (live > 0).astype(jnp.int32) if cross_at is not None else live
        kv = kv._replace(aux=aux + jnp.stack(
            [jnp.sum(live), jnp.sum(ran)]).astype(aux.dtype))
    if cross_at is not None:
        pick = cross_at[:, None, None].astype(jnp.int32)
        x = jnp.take_along_axis(x, pick, axis=1)
        m = jnp.take_along_axis(m, pick, axis=1)

    def cross_pair(carry, j):
        x, kv = carry
        gp = at(params["gmu"], j)

        def gmu(x, l):
            with jax.named_scope("gmu"):
                g = jax.nn.silu(qdot(normed(x, gp), gp["w_in"]))
                return qdot((g * m.astype(jnp.float32)).astype(x.dtype),
                            gp["w_out"]).astype(x.dtype), None
        x, _ = block(x, mid + 2 + 2 * j, gmu)
        x, kv = attn_layer(x, kv, "cross", j, mid + 3 + 2 * j)
        return (x, kv), None

    (x, kv), _ = jax.lax.scan(cross_pair, (x, kv),
                              jnp.arange((n - mid - 2) // 2))
    return layer_norm(x, params["final_norm_w"], params["final_norm_b"],
                      cfg.norm_eps), kv


def unembed(params: dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """Hidden states -> f32 logits (the embedding, tied)."""
    return jnp.dot(hidden, params["embed"].T,
                   preferred_element_type=jnp.float32)


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv: Any,
            attn: AttentionFn) -> Tuple[jax.Array, Any]:
    hidden, kv = forward_hidden(params, cfg, tokens, positions, kv, attn)
    return unembed(params, cfg, hidden), kv


class DenseState:
    """``attn.state`` without a cache: every row starts from zeros and
    all S positions are valid; nothing is kept."""

    def __init__(self, cfg: ModelConfig, batch: int, seq_len: int):
        from tpu_inference.kernels.selective_scan import (
            selective_scan_reference)

        self.cfg, self.batch = cfg, batch
        self.lens = jnp.full((batch,), seq_len, jnp.int32)
        self.scan = lambda *a: selective_scan_reference(*a, self.lens)

    def read(self, slot, kv):
        c = self.cfg
        return (jnp.zeros((self.batch, c.ssm_d_conv - 1, c.d_inner), c.dtype),
                jnp.zeros((self.batch, c.ssm_d_state, c.d_inner),
                          jnp.float32))

    def write(self, slot, tail, h, kv):
        return kv


def make_dense_attn(cfg: ModelConfig, batch: int = 1,
                    seq_len: int = 0) -> AttentionFn:
    """Cache-free attention and state for a forward over whole sequences
    [batch, seq_len] (tests): the cross kind reads the keys and values
    the full layer was just given."""
    held = {}

    def of(window):
        def attn(slot, q, k, v, kv):
            if window == 0:
                held["k"], held["v"] = k, v
            return dense_causal_attention(q, k, v,
                                          sliding_window=window), kv
        return attn

    def cross(slot, q, k, v, kv):
        return dense_causal_attention(q, held["k"], held["v"]), kv

    def attn(*_):
        raise TypeError("a stack of mixed kinds calls attn.kinds[kind]")

    attn.kinds = {"full": of(0), "window": of(cfg.sliding_window),
                  "cross": cross}
    attn.state = DenseState(cfg, batch, seq_len)
    attn.valid = jnp.ones((batch, seq_len), bool)
    return attn
