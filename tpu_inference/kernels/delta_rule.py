"""Gated delta rule with a decay a channel (Kimi delta attention,
arXiv:2510.26692): the state of one head of one sequence is a float32
matrix ``S [d_k, d_v]`` and a token advances it,

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                    a_t = exp(g_t), g_t in (bound, 0)

Three functions over the STATE POOL ``[layers, slots, H, d_k, d_v]``
(engine/kv_cache.py: a slot a sequence, slot 0 the trash slot), which
read a lane's state where it lies and write it back in place (the pool
is aliased to the result: a gather in front and a scatter behind would
each copy every lane's state once more):

- ``kda_chunk_prefill``: ONE Pallas kernel, named ``kda_chunk_prefill``,
  walks a prefill chunk in BLOCKS of ``BLOCK`` = 64 tokens in the WY / UT
  form. With ``G`` the running sum of ``g`` inside a block (``Gam =
  exp(G)``), ``A_ij = sum_c beta_i k_ic k_jc Gam_ic / Gam_jc`` (j < i)
  and ``B_ij`` the same with ``q_i`` for ``beta_i k_i`` (j <= i):

      U = (I + A)^-1 (beta V - (beta K * Gam) S_0)
      O = (Q * Gam) S_0 + B U
      S_C = Diag(Gam_C) S_0 + (K * Gam_C / Gam)^T U

  all matrix products. ``(I + A) U = R`` is solved in the sub-blocks of
  ``SUB`` = 16 tokens: the diagonal 16 x 16 blocks are inverted by
  squarings, ``(I - D)(I + D^2)(I + D^4)(I + D^8)``, and the blocks
  below them taken in by forward substitution (``_solve``). Squarings
  over the WHOLE block, ``(I - A)(I + A^2) ... (I + A^32)``, were the
  first form and are wrong in float32 where a block's keys point one
  way (a deep layer's do): the powers' entries reach C(63, k) a^k and
  cancel; it read 1e13 times the output's spread on such keys on the
  CPU and NOT correct on one seed in four on the chip (PERF.md section
  6, PR 51). The state is carried from block to block in VMEM and
  enters and leaves through the slot, so it crosses the engine's chunk
  boundaries too.
  The decays are a channel and down to ``exp(bound)`` a token, so the
  factored form ``(k_i Gam_i) (k_j / Gam_j)`` overflows float32 over 64
  tokens (e^320). Column block ``b`` of ``A`` (the ``SUB`` = 16 tokens of
  sub-block b) is therefore referenced to the END of sub-block b:
  ``(beta k_i e^{G_i - E_b}) . (k_j e^{E_b - G_j})``; the right factor is
  <= 1, the left one <= 1 for every row behind the sub-block and at most
  ``e^{16 |bound|}`` (e^80 < float32's e^88, which is what the lower
  bound is for) inside it; rows in FRONT of it are masked and their
  exponent is clamped. ``lens`` [B]: positions at or past it advance
  nothing (their decay reads 1 and their beta 0), a block wholly behind
  it is skipped.
- ``kda_step``: the one-token update, one Pallas kernel named
  ``kda_step``: each state is read once and written once, on the VPU
  (``S`` is the "weights" of every product here and belongs to one lane
  and head, so the MXU would load 64 KB to multiply one row).
- ``kda_recurrence``: the same function as a ``lax.scan`` over time on
  gathered states (tests, and the engine's path off the kernels).

Float32 throughout, matrix products at the highest precision.

In front of the delta rule a KDA layer passes ``[q | k | v]`` through a
causal depthwise convolution whose last ``taps - 1`` inputs a sequence
keeps in the TAIL POOL ``[layers, slots, taps - 1, 3 H, d]`` (model
dtype; a head a row, so a lane's tail is whole tiles in one piece).
``kda_tail_step`` is that convolution for ONE token a lane, one Pallas
kernel named ``kda_tail_step``: every lane's tail is copied from its
slot into VMEM (all copies started before the first is waited for),
the taps are summed in float32, and the tail, moved on by the token, is
copied back to the slot it is written to. As gathers and scatters the
same job was seven XLA ops a layer that touched the tail row by row,
192 us against 614 us for ``kda_step``'s fifty-seven times the bytes
(PERF.md section 6, PR 52).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 64            # tokens a block of the chunk kernel
SUB = 16              # tokens a sub-block: SUB * |bound| has to stay < 88
MAX_EXPONENT = 80.0   # what a masked row's exponent is clamped to
TIME_BLOCK = 256      # tokens a grid step brings in
STEP_HEADS = 16       # heads a grid step of the one-token update
TAIL_LANES = 8        # lanes a grid step of the one-token convolution
HIGHEST = jax.lax.Precision.HIGHEST


def check_bound(lower_bound: float) -> None:
    """The gate's lower bound a token has to keep a sub-block's decay
    inside float32: ``SUB * |bound| <= MAX_EXPONENT``."""
    if not -MAX_EXPONENT / SUB <= lower_bound < 0:
        raise ValueError(
            f"a decay down to exp({lower_bound}) a token overflows the "
            f"chunk kernel's sub-blocks of {SUB} tokens (at most "
            f"exp({MAX_EXPONENT}))")


def _mm(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _nn(a, b):            # [m, k] @ [k, n]
    return _mm(a, b, ((1,), (0,)))


def _nt(a, b):            # [m, k] @ [n, k]^T
    return _mm(a, b, ((1,), (1,)))


def _tn(a, b):            # [k, m]^T @ [k, n]
    return _mm(a, b, ((0,), (0,)))


def _solve(a, rhs, size: int):
    """``(I + a)^-1 rhs`` for a strictly lower ``a [c, c]``. The diagonal
    blocks of ``size`` rows are inverted all at once by squarings
    (``(I - D)(I + D^2)(I + D^4) ...``, ``D^size = 0``; block-diagonal
    stays block-diagonal), then the blocks below them are taken in by
    forward substitution a block row at a time, which as a fixed point
    ``U <- T (rhs - L U)`` is exact after ``c / size - 1`` turns: no
    power of ``a`` past ``size - 1`` is ever formed."""
    c = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    near = row // size == col // size
    diag = jnp.where(near, a, 0.0)
    inv = (row == col).astype(jnp.float32) - diag
    power, reach = _nn(diag, diag), 2
    while reach < size:
        inv = inv + _nn(inv, power)
        reach *= 2
        if reach < size:
            power = _nn(power, power)
    u = _nn(inv, rhs)
    if size < c:
        below = jnp.where(near, 0.0, a)
        for _ in range(c // size - 1):
            u = _nn(inv, rhs - _nn(below, u))
    return u


def _block(q, k, kb, vb, g, s0, block: int, sub: int):
    """One block of ``block`` tokens of one head. q, k [C, dk], kb = beta
    k, vb = beta v [C, dv], g [C, dk] (0 and kb = vb = 0 at a position
    that advances nothing), s0 [dk, dv] -> (o [C, dv], s_C)."""
    c, dk = k.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    cum = _nn((row >= col).astype(jnp.float32), g)     # G, inclusive
    # E: each row's own sub-block's end.
    ends = [cum[(b + 1) * sub - 1:(b + 1) * sub] for b in range(c // sub)]
    own = jnp.concatenate([jnp.broadcast_to(e, (sub, dk)) for e in ends], 0)
    k_out = k * jnp.exp(own - cum)                     # k_j e^{E_b - G_j}
    a = jnp.zeros((c, c), jnp.float32)
    bm = jnp.zeros((c, c), jnp.float32)
    for b, e in enumerate(ends):
        left = jnp.exp(jnp.minimum(cum - e, MAX_EXPONENT))
        mine = col // sub == b
        a = jnp.where(mine, _nt(kb * left, k_out), a)
        bm = jnp.where(mine, _nt(q * left, k_out), bm)
    a = jnp.where(row > col, a, 0.0)
    bm = jnp.where(row >= col, bm, 0.0)
    gam = jnp.exp(cum)
    u = _solve(a, vb - _nn(kb * gam, s0), sub)
    o = _nn(q * gam, s0) + _nn(bm, u)
    last = cum[c - 1:c]
    # The block's whole decay a channel, as rows of s0 need it: g^T
    # against ones puts G_C[k] on every lane of row k.
    total = jnp.exp(_tn(g, jnp.ones((c, s0.shape[1]), jnp.float32)))
    return o, total * s0 + _tn(k * jnp.exp(last - cum), u)


def _chunk_kernel(lens_ref, slots_r, slots_w, fresh_ref, layer_ref,
                  q_ref, k_ref, kb_ref, vb_ref, g_ref, h_in, o_ref, h_out,
                  s_scr, *, time_block: int, block: int, sub: int):
    del slots_r, slots_w, layer_ref
    bi, ti = pl.program_id(0), pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        s_scr[...] = jnp.where(fresh_ref[bi] != 0, 0.0, h_in[0, 0, 0])

    n_valid = lens_ref[bi] - ti * time_block

    @pl.when(n_valid <= 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_valid > 0)
    def _():
        def one(c, carry):
            rows = pl.ds(pl.multiple_of(c * block, block), block)
            at = c * block + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block, 1), 0)
            live = at < n_valid
            f32 = lambda ref: ref[0, rows, :].astype(jnp.float32)  # noqa
            o, s = _block(
                f32(q_ref), f32(k_ref), jnp.where(live, f32(kb_ref), 0.0),
                jnp.where(live, f32(vb_ref), 0.0),
                jnp.where(live, f32(g_ref), 0.0), s_scr[...], block, sub)
            o_ref[0, rows, :] = o.astype(o_ref.dtype)
            s_scr[...] = s
            return carry

        jax.lax.fori_loop(0, time_block // block, one, 0)

    @pl.when(ti == pl.num_programs(2) - 1)
    def _():
        h_out[0, 0, 0] = s_scr[...]


@partial(jax.jit, static_argnames=("n_heads", "interpret"))
def kda_chunk_prefill(pool: jax.Array, layer: jax.Array, slots_r: jax.Array,
                      slots_w: jax.Array, fresh: jax.Array, q: jax.Array,
                      k: jax.Array, v: jax.Array, g: jax.Array,
                      beta: jax.Array, lens: jax.Array, *, n_heads: int,
                      interpret: bool = False):
    """A chunk of S tokens of B lanes through one layer's delta rule.

    pool:    [L, N, H, dk, dv] float32, the state slots
    layer:   int32 scalar, may be traced (the model's scan index)
    slots_r: [B] the slot lane b's state is read from; ``fresh`` [B]
             bool: read zeros instead (a sequence's first chunk)
    slots_w: [B] the slot it is written to (0, the trash slot, for a
             lane with no valid position)
    q, k:    [B, S, H * dk] (q scaled, both normalised by the caller)
    v:       [B, S, H * dv];  g [B, S, H * dk] log-decays;  beta [B, S, H]
    lens:    [B] valid positions of each lane
    -> (o [B, S, H * dv] float32, the pool with the states advanced).
    """
    bsz, s, _ = q.shape
    h = n_heads
    dk, dv = pool.shape[-2:]
    assert dk == dv, "one head width for q, k and v"
    block = min(BLOCK, s)
    sub = min(SUB, block)
    time_block = min(TIME_BLOCK, s)
    assert s % time_block == 0 and time_block % block == 0 \
        and block % sub == 0, (s, time_block, block, sub)
    f32 = jnp.float32
    rep = jnp.repeat(beta.astype(f32), dk, axis=-1)            # [B, S, H dk]
    kb, vb = k.astype(f32) * rep, v.astype(f32) * rep

    seq = pl.BlockSpec((1, time_block, dk),
                       lambda bi, hi, ti, *_: (bi, ti, hi))
    state_in = pl.BlockSpec(
        (1, 1, 1, dk, dv),
        lambda bi, hi, ti, lens, sr, sw, fr, lay: (lay[0], sr[bi], hi, 0, 0))
    state_out = pl.BlockSpec(
        (1, 1, 1, dk, dv),
        lambda bi, hi, ti, lens, sr, sw, fr, lay: (lay[0], sw[bi], hi, 0, 0))
    i32 = lambda a: jnp.asarray(a, jnp.int32)                  # noqa: E731
    o, pool = pl.pallas_call(
        partial(_chunk_kernel, time_block=time_block, block=block, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bsz, h, s // time_block),
            in_specs=[seq, seq, seq, seq, seq, state_in],
            out_specs=[seq, state_out],
            scratch_shapes=[pltpu.VMEM((dk, dv), f32)]),
        out_shape=[jax.ShapeDtypeStruct((bsz, s, h * dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="kda_chunk_prefill",
    )(i32(lens), i32(slots_r), i32(slots_w), i32(fresh),
      i32(layer).reshape(1), q.astype(f32), k.astype(f32), kb, vb,
      g.astype(f32), pool)
    return o, pool


def _step_kernel(slots_r, slots_w, layer_ref, a_ref, k_ref, q_ref, vb_ref,
                 beta_ref, h_in, o_ref, h_out, *, heads: int):
    del slots_r, slots_w, layer_ref
    for j in range(heads):
        col = lambda ref: ref[0, 0, :, j:j + 1]                # noqa: E731
        s = h_in[0, 0, j] * col(a_ref)                         # Diag(a) S
        k = col(k_ref)
        ks = jnp.sum(s * k, axis=0, keepdims=True)             # [1, dv]
        s = s + k * (vb_ref[0, j:j + 1] - beta_ref[0, j:j + 1] * ks)
        h_out[0, 0, j] = s
        o_ref[0, j:j + 1] = jnp.sum(s * col(q_ref), axis=0, keepdims=True)


@partial(jax.jit, static_argnames=("n_heads", "interpret"))
def kda_step(pool: jax.Array, layer: jax.Array, slots_r: jax.Array,
             slots_w: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
             g: jax.Array, beta: jax.Array, *, n_heads: int,
             interpret: bool = False):
    """One token of B lanes through one layer's delta rule, each state
    read once and written once, in place. As ``kda_chunk_prefill`` with
    S = 1 and no ``lens`` / ``fresh``: q, k, g [B, H * dk], v [B, H * dv],
    beta [B, H]; a lane that advances nothing is given slot 0 to write.
    -> (o [B, H * dv] float32, the pool)."""
    bsz = q.shape[0]
    h = n_heads
    dk, dv = pool.shape[-2:]
    hb = STEP_HEADS if h % STEP_HEADS == 0 else h
    f32 = jnp.float32

    def cols(x):
        """[B, H * dk] -> [B, H / hb, dk, hb]: head j of a block is
        column j, its channels along the sublanes as the state's rows."""
        return x.astype(f32).reshape(bsz, h // hb, hb, dk).transpose(
            0, 1, 3, 2)

    bt = beta.astype(f32)[..., None]                           # [B, H, 1]
    vb = v.astype(f32).reshape(bsz, h, dv) * bt
    col = pl.BlockSpec((1, 1, dk, hb), lambda bi, hi, *_: (bi, hi, 0, 0))
    rows = pl.BlockSpec((1, hb, dv), lambda bi, hi, *_: (bi, hi, 0))
    state_in = pl.BlockSpec(
        (1, 1, hb, dk, dv),
        lambda bi, hi, sr, sw, lay: (lay[0], sr[bi], hi, 0, 0))
    state_out = pl.BlockSpec(
        (1, 1, hb, dk, dv),
        lambda bi, hi, sr, sw, lay: (lay[0], sw[bi], hi, 0, 0))
    i32 = lambda a: jnp.asarray(a, jnp.int32)                  # noqa: E731
    o, pool = pl.pallas_call(
        partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz, h // hb),
            in_specs=[col, col, col, rows, rows, state_in],
            out_specs=[rows, state_out]),
        out_shape=[jax.ShapeDtypeStruct((bsz, h, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="kda_step",
    )(i32(slots_r), i32(slots_w), i32(layer).reshape(1),
      cols(jnp.exp(g.astype(f32))), cols(k), cols(q), vb,
      jnp.broadcast_to(bt, (bsz, h, dv)), pool)
    return o.reshape(bsz, h * dv), pool


def _tail_kernel(layer_ref, slots_r, slots_w, lens_ref, fresh_ref, qkv_ref,
                 w_ref, pool_in, x_ref, pool, buf, sem_in, sem_out, *,
                 group: int, taps: int):
    del pool_in                           # aliased: ``pool`` is it
    i = pl.program_id(0)
    layer, lanes = layer_ref[0], buf.shape[0]

    def fetch(b):
        return pltpu.make_async_copy(pool.at[layer, slots_r[b]], buf.at[b],
                                     sem_in.at[b // group])

    def leave(b):
        return pltpu.make_async_copy(buf.at[b], pool.at[layer, slots_w[b]],
                                     sem_out.at[0])

    def each(n, fn):
        def body(b, carry):
            fn(b)
            return carry
        jax.lax.fori_loop(0, n, body, 0)

    @pl.when(i == 0)
    def _():
        each(lanes, lambda b: fetch(b).start())

    each(group, lambda g: fetch(i * group + g).wait())
    for g in range(group):
        b = i * group + g
        keep = fresh_ref[b] == 0
        seq = [jnp.where(keep, buf[b, j], jnp.zeros_like(buf[b, j]))
               for j in range(taps - 1)] + [qkv_ref[g]]
        conv = jnp.zeros(seq[0].shape, jnp.float32)
        for j in range(taps):
            conv = conv + (w_ref[j].astype(jnp.float32)
                           * seq[j].astype(jnp.float32))
        x_ref[g] = jax.nn.silu(conv)
        # The tail the lane leaves: [tail[1:], qkv] behind a valid token,
        # the tail it read (zeros if fresh) behind none.
        moved = lens_ref[b] > 0
        for j in range(taps - 1):
            buf[b, j] = jnp.where(moved, seq[j + 1], seq[j])
        leave(b).start()

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        each(lanes, lambda b: leave(b).wait())


@partial(jax.jit, static_argnames=("interpret",))
def kda_tail_step(pool: jax.Array, layer: jax.Array, slots_r: jax.Array,
                  slots_w: jax.Array, lens: jax.Array, fresh: jax.Array,
                  qkv: jax.Array, conv_w: jax.Array, *,
                  interpret: bool = False):
    """One token of B lanes through one layer's convolution, each lane's
    tail read once and written once, in place.

    pool:    [L, N, taps - 1, R, d] the tail slots, ``R d`` channels
    layer:   int32 scalar, may be traced (the model's scan index)
    slots_r: [B] the slot lane b's tail is read from; ``fresh`` [B]
             bool: read zeros instead
    slots_w: [B] the slot it is written to (0, the trash slot, for a
             lane with ``lens`` 0; several lanes may write it at once)
    lens:    [B] 1 where the lane's token is valid, 0 where the tail
             stays as it was read
    qkv:     [B, R * d] the token's input;  conv_w [taps, R * d]
    -> (x [B, R * d] float32 = silu(sum_j conv_w[j] * [tail | qkv][j]),
    taps summed in float32 from the oldest on, and the pool)."""
    km1, rows, d = pool.shape[2:]
    bsz, taps = qkv.shape[0], km1 + 1
    group = TAIL_LANES if bsz % TAIL_LANES == 0 else bsz
    lane = pl.BlockSpec((group, rows, d), lambda i, *_: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    held = bsz * km1 * rows * d * pool.dtype.itemsize          # 7.1 MB at 96
    i32 = lambda a: jnp.asarray(a, jnp.int32)                  # noqa: E731
    # The token's row arrives a lane a row and is re-laid a head a row,
    # one XLA copy of B rows. Behind a barrier: without it the chip's
    # compiler moves the reshape into the projection in front and re-lays
    # that layer stack's whole weight instead, once a dispatch (692 MB
    # for Ling-3.0-flash's w_qkv: benchmarks/aot_rehearsal.py refuses it).
    qkv = jax.lax.optimization_barrier(qkv.astype(pool.dtype))
    x, pool = pl.pallas_call(
        partial(_tail_kernel, group=group, taps=taps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bsz // group,),
            in_specs=[lane, pl.BlockSpec((taps, rows, d),
                                         lambda i, *_: (0, 0, 0)), hbm],
            out_specs=[lane, hbm],
            scratch_shapes=[pltpu.VMEM((bsz, km1, rows, d), pool.dtype),
                            pltpu.SemaphoreType.DMA((bsz // group,)),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=[jax.ShapeDtypeStruct((bsz, rows, d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=held + (8 << 20)),
        interpret=interpret, name="kda_tail_step",
    )(i32(layer).reshape(1), i32(slots_r), i32(slots_w), i32(lens),
      i32(fresh), qkv.reshape(bsz, rows, d),
      conv_w.reshape(taps, rows, d), pool)
    return x.reshape(bsz, rows * d), pool


def kda_recurrence(q, k, v, g, beta, s0, lens):
    """The recurrence itself, a token at a time: q, k, g [B, S, H, dk], v
    [B, S, H, dv], beta [B, S, H], s0 [B, H, dk, dv] float32, lens [B] ->
    (o [B, S, H, dv] float32, s_T). Positions at or past ``lens``
    advance nothing."""
    f32 = jnp.float32
    live = jnp.arange(q.shape[1])[None, :] < lens[:, None]        # [B, S]

    def step(s, t):
        q_t, k_t, v_t, g_t, b_t, on = t
        sd = s * jnp.exp(g_t)[..., None]                          # Diag(a) S
        r = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, sd, precision=HIGHEST)
        new = sd + (b_t[..., None] * k_t)[..., None] * r[:, :, None, :]
        s = jnp.where(on[:, None, None, None], new, s)
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HIGHEST)

    tm = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)          # noqa: E731
    s, o = jax.lax.scan(step, s0.astype(f32),
                        (tm(q), tm(k), tm(v), tm(g), tm(beta),
                         jnp.moveaxis(live, 1, 0)))
    return jnp.moveaxis(o, 0, 1), s
