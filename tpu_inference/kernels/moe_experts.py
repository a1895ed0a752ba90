"""Dropless grouped expert FFN: the routed pairs that land on experts
held here, sorted by expert and run as grouped matmuls.

``models/mixtral.py``'s capacity model builds ``[T, E, C]`` one-hots and
loses the pairs over C. Here nothing is dropped and no shape depends on
the routing:

1. ``group_pairs`` lays the (token, expert) pairs whose expert is held
   here out in rows, expert by expert, each expert's run padded up to a
   whole tile of ``tm`` rows, so a tile belongs to ONE expert. Only index
   vectors are sized for the worst case (every pair local).
2. ``grouped_experts`` walks that layout in rounds of a fixed number of
   tiles: one round covers twice the expected load, so it is almost
   always the only one that has rows; where several are laid out a
   ``lax.while_loop`` whose trip count is the routing's runs them (a
   routing skewed onto one expert just takes more). A round gathers its
   rows' activations and runs the two kernels below. How its gated rows
   come back to their tokens is decided by the layout's shape alone
   (``combines_by_gather``, Python ints at trace time):
   - GATHER where ``T x k <= SCATTERED_ROW_COST x`` a round's rows: each
     token reads its k rows (``PairGroups.pair_row``) FROM THAT ROUND's
     result, a pair whose row lies in another round (or nowhere) at gate
     0, and sums them in float32 (``gathered_rows``): the padding is
     never touched, no index collides. One round always qualifies (``T x
     k <= cap``; SmallThinker's stage holds all 64: no loop), and so do
     the decode rungs of a chip that holds few (Kimi 12 of 384: 3 rounds
     of 208 rows for 256 pairs; Laguna 32 of 256: 2 of 592 for 320);
   - SCATTER-ADD of the round's gated rows everywhere else (their prefill
     programs: 896 rows a round for 8192 pairs, 4608 for 10,240).
3. The kernels (``moe_grouped_experts_gate_up``, ``moe_grouped_experts_
   down``) take the STACKED expert weights ``[Le, E, K, N]`` with the
   layer and each tile's expert scalar-prefetched: the weight block's DMA
   address names (layer, expert), so no ``w[layer]`` slice is copied in
   front of the call, and an expert with no tile is never read. Weights
   stream in contiguous ``[tk, N]`` row blocks into a float32
   accumulator; tiles past the last one in use repeat the previous block
   index (no DMA) and write zeros. int8 weights (the parity control) are
   widened in VMEM and scaled per output channel at the end.

On one chip of an expert-parallel deployment this is the layer without
its exchange: what absent experts would add is not computed.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_inference.kernels import mxu_precision
from tpu_inference.models.quant import QuantizedArray


# The gate's activation, act(x Wg) * (x Wu): SwiGLU or ReGLU.
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _split(w: Any):
    """(codes or weights, per-output-channel scale or None)."""
    if isinstance(w, QuantizedArray):
        assert w.scale.shape[-2] == 1, "grouped experts take int8, not int4"
        return w.q, w.scale
    return w, None


# ------------------------------------------------------------------ kernels
def _tile_maps(n_k: int):
    """Index maps of a (tile, k-block) grid. A tile past the last one in
    use points at the last used tile's last k-block: the block the step
    before fetched, so nothing moves for it."""
    def tile(i, k, ly, te, na):
        live = i < na[0]
        return jnp.where(live, i, jnp.maximum(na[0] - 1, 0)), live

    def x_map(i, k, ly, te, na):
        t, live = tile(i, k, ly, te, na)
        return t, jnp.where(live, k, n_k - 1)

    def w_map(i, k, ly, te, na):
        t, live = tile(i, k, ly, te, na)
        return ly[0], te[t], jnp.where(live, k, n_k - 1), 0

    def s_map(i, k, ly, te, na):
        t, _ = tile(i, k, ly, te, na)
        return ly[0], te[t], 0, 0

    def o_map(i, k, ly, te, na):
        return i, 0

    return x_map, w_map, s_map, o_map


def _gate_up_kernel(ly_ref, te_ref, na_ref, x_ref, wg_ref, wu_ref, *rest,
                    quantized: bool, act: str):
    if quantized:
        sg_ref, su_ref, out_ref, ag_ref, au_ref = rest
    else:
        out_ref, ag_ref, au_ref = rest
    i, k = pl.program_id(0), pl.program_id(1)
    live = i < na_ref[0]

    @pl.when(live & (k == 0))
    def _init():
        ag_ref[:] = jnp.zeros_like(ag_ref)
        au_ref[:] = jnp.zeros_like(au_ref)

    @pl.when(live)
    def _accumulate():
        x = x_ref[:]
        prec = mxu_precision(x.dtype)
        ag_ref[:] += jnp.dot(x, wg_ref[:].astype(x.dtype), precision=prec,
                             preferred_element_type=jnp.float32)
        au_ref[:] += jnp.dot(x, wu_ref[:].astype(x.dtype), precision=prec,
                             preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _flush():
        g, u = ag_ref[:], au_ref[:]
        if quantized:
            g, u = g * sg_ref[:], u * su_ref[:]
        h = ACTS[act](g) * u
        out_ref[:] = jnp.where(live, h, 0.0).astype(out_ref.dtype)


def _down_kernel(ly_ref, te_ref, na_ref, x_ref, w_ref, *rest,
                 quantized: bool):
    if quantized:
        s_ref, out_ref, acc_ref = rest
    else:
        out_ref, acc_ref = rest
    i, k = pl.program_id(0), pl.program_id(1)
    live = i < na_ref[0]

    @pl.when(live & (k == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        x = x_ref[:]
        acc_ref[:] += jnp.dot(x, w_ref[:].astype(x.dtype),
                              precision=mxu_precision(x.dtype),
                              preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _flush():
        y = acc_ref[:]
        if quantized:
            y = y * s_ref[:]
        out_ref[:] = jnp.where(live, y, 0.0).astype(out_ref.dtype)


def _block_k(k_dim: int, n_dim: int, weights: int) -> int:
    """Rows of a weight block: the largest divisor of K that is a
    multiple of 128 (or K itself) and keeps the double-buffered bf16
    blocks of ``weights`` matrices near 16 MiB."""
    budget = (16 << 20) // (4 * weights * n_dim)
    for tk in range(min(k_dim, max(128, budget // 128 * 128)), 127, -128):
        if k_dim % tk == 0:
            return tk
    return k_dim


def _grouped_call(kernel, name, x, weights, layer, tile_expert, n_tiles, *,
                  tm: int, out_dtype, n_acc: int, interpret: bool):
    m, k_dim = x.shape
    codes, scales = zip(*(_split(w) for w in weights))
    quantized = scales[0] is not None
    n_dim = codes[0].shape[-1]
    tk = _block_k(k_dim, n_dim, len(codes))
    n_k = k_dim // tk
    x_map, w_map, s_map, o_map = _tile_maps(n_k)
    in_specs = [pl.BlockSpec((tm, tk), x_map)] + [
        pl.BlockSpec((None, None, tk, n_dim), w_map) for _ in codes]
    operands = [x, *codes]
    if quantized:
        in_specs += [pl.BlockSpec((None, None, 1, n_dim), s_map)
                     for _ in scales]
        operands += list(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # layer, tile_expert, n_tiles
        grid=(m // tm, n_k), in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, n_dim), o_map),
        scratch_shapes=[pltpu.VMEM((tm, n_dim), jnp.float32)] * n_acc)
    return pl.pallas_call(
        functools.partial(kernel, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_dim), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret, name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_expert,
      jnp.asarray(n_tiles, jnp.int32).reshape(1), *operands)


def moe_grouped_experts_gate_up(x, w_gate, w_up, layer, tile_expert,
                                n_tiles, *, tm: int, act: str = "silu",
                                interpret: bool = False):
    """x [M, D] (rows in tiles of ``tm``, tile t all for expert
    tile_expert[t]); w_gate / w_up [Le, E, D, F] (or int8
    QuantizedArray) -> act(x Wg) * (x Wu) [M, F] in x.dtype (``act``:
    "silu" | "relu", static); rows of tiles >= n_tiles are zero."""
    return _grouped_call(functools.partial(_gate_up_kernel, act=act),
                         "moe_grouped_experts_gate_up", x, (w_gate, w_up),
                         layer, tile_expert, n_tiles, tm=tm,
                         out_dtype=x.dtype, n_acc=2, interpret=interpret)


def moe_grouped_experts_down(h, w_down, layer, tile_expert, n_tiles, *,
                             tm: int, interpret: bool = False):
    """h [M, F]; w_down [Le, E, F, D] -> h Wd [M, D] float32."""
    return _grouped_call(_down_kernel, "moe_grouped_experts_down", h,
                         (w_down,), layer, tile_expert, n_tiles, tm=tm,
                         out_dtype=jnp.float32, n_acc=1, interpret=interpret)


def _tile_weights(w, layer, tile_expert):
    """[tiles, K, N] float32 weights of each tile's expert (plain XLA)."""
    codes, scale = _split(w)
    wt = codes[layer][tile_expert].astype(jnp.float32)
    return wt if scale is None else wt * scale[layer][tile_expert]


def grouped_ffn_xla(x, w_gate, w_up, w_down, layer, tile_expert, n_tiles,
                    *, tm: int, act: str = "silu"):
    """The two kernels' function in plain XLA (the ``dense`` backend off
    the chip; gathers every tile's expert weights, so for tests only)."""
    xt = x.reshape(-1, tm, x.shape[-1]).astype(jnp.float32)
    g = jnp.einsum("tmk,tkn->tmn", xt, _tile_weights(w_gate, layer,
                                                     tile_expert))
    u = jnp.einsum("tmk,tkn->tmn", xt, _tile_weights(w_up, layer,
                                                     tile_expert))
    h = (ACTS[act](g) * u).astype(x.dtype).astype(jnp.float32)
    y = jnp.einsum("tmf,tfd->tmd", h, _tile_weights(w_down, layer,
                                                    tile_expert))
    live = (jnp.arange(xt.shape[0]) < n_tiles)[:, None, None]
    return jnp.where(live, y, 0.0).reshape(x.shape[0], -1)


# ----------------------------------------------------------------- grouping
class PairGroups(NamedTuple):
    """The local pairs laid out in rows, in rounds of ``round_rows``."""
    row_token: jax.Array      # [rounds * R] token of each row; T = padding
    row_gate: jax.Array       # [rounds * R] float32 gate; 0 on padding
    tile_expert: jax.Array    # [rounds * R / tm] held expert of each tile
    n_tiles: jax.Array        # [] tiles in use
    counts: jax.Array         # [E_held] pairs per held expert
    tm: int
    round_rows: int
    # Where the rows come back by gather (``combines_by_gather``), else None:
    pair_row: Optional[jax.Array] = None    # [T, k] row of each pair; cap: none
    pair_gate: Optional[jax.Array] = None   # [T, k] float32 gate; 0 where none


def tile_rows(n_tokens: int, expected_pairs_per_expert: float) -> int:
    """Rows of a tile: 1.5 x the expected pairs of an expert, as a power
    of two in 16 .. 128."""
    want = max(16.0, 1.5 * expected_pairs_per_expert)
    return int(min(128, 1 << (int(want) - 1).bit_length()))


def round_layout(t: int, k: int, n_held: int, expected_pairs: float):
    """(rows of a tile, tiles of a round, rounds laid out) for T tokens
    of k pairs each, from Python ints alone."""
    tm = tile_rows(t, expected_pairs / n_held)
    # One round: every held expert's partial tile + twice the expected
    # rows; never more than the worst case needs.
    worst_tiles = n_held + (t * k) // tm
    round_tiles = min(worst_tiles, n_held + -(-int(2 * expected_pairs) // tm))
    return tm, round_tiles, -(-worst_tiles // round_tiles)


# What adding one row of a round by scatter costs on the v5e, in rows
# read by gather. ``chip_smoke._combine_costs`` (my chip run, PR 45: one
# round's combine alone, us, scatter over the round's rows / gather over
# T x k pairs, three readings within 5%), Laguna's widths (D 3072, k 10):
# rung 8 78 / 12 (544 rows / 80 pairs: 0.98), rung 32 80 / 21 (592 /
# 320: 2.05-2.10), 128 rows 85 / 43 (832 / 1280: 3.04), a 1024-token
# chunk 594 / 864 (4608 / 10,240: 1.53-1.56; the gather LOSES, and by
# 0.63 ms a layer inside the layer), 4 chunks 2219 / 3374 (1.88); Kimi's
# (D 7168, k 8): rung 32 43 / 22 (208 / 256: 2.3-2.4), 64 rows 88 / 40
# (5.0-5.2), a chunk 890 / 761 (896 / 8192: 10.7). A scattered row is
# 0.10-0.16 us at D 3072 and 0.2-1.0 at 7168, a gathered one 0.03-0.15:
# no one ratio holds everywhere. 2.0 is the rungs' reading and lies
# under 2.22, Laguna's ``T x k`` over a round's rows from 256 rows on,
# where the scatter wins; it leaves Kimi's prefill programs (2.3-9.1),
# where the gather would win by 15% of a 0.9 ms op, on the scatter.
SCATTERED_ROW_COST = 2.0


def combines_by_gather(t: int, k: int, n_held: int,
                       expected_pairs: float) -> bool:
    """Whether a round of ``grouped_experts`` sums each token's k rows by
    a gather (``T x k`` rows read) or scatter-adds the round's rows to
    their tokens (``round_rows`` walked, each at ``SCATTERED_ROW_COST``
    gathered rows), from Python ints alone. One round always gathers
    (``T x k <= cap``). What ``group_pairs`` decides
    ``PairGroups.pair_row`` by, and what the engine counts its warmed
    step programs by."""
    tm, round_tiles, _ = round_layout(t, k, n_held, expected_pairs)
    return t * k <= SCATTERED_ROW_COST * round_tiles * tm


def group_pairs(top_local: jax.Array, gates: jax.Array, n_held: int,
                expected_pairs: float) -> PairGroups:
    """top_local [T, k]: each pair's held-expert index, or ``n_held`` for
    an expert that is not here; gates [T, k] float32."""
    t, k = top_local.shape
    tm, round_tiles, rounds = round_layout(t, k, n_held, expected_pairs)
    cap = rounds * round_tiles * tm

    expert = top_local.reshape(-1)                             # [P]
    local = expert < n_held
    held = jnp.minimum(expert, n_held - 1)       # any legal index if not
    onehot = (expert[:, None] == jnp.arange(n_held)[None, :]).astype(
        jnp.int32)                                             # [P, E]
    counts = onehot.sum(0)                                     # [E]
    tiles_e = -(-counts // tm)
    tile_end = jnp.cumsum(tiles_e)                             # [E]
    row_start = (tile_end - tiles_e) * tm
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1, held[:, None],
                               axis=1)[:, 0]
    dest = jnp.where(local, row_start[held] + rank, cap)       # cap: left out
    token = jnp.arange(t * k, dtype=jnp.int32) // k
    row_token = jnp.full((cap,), t, jnp.int32).at[dest].set(
        token, mode="drop")
    row_gate = jnp.zeros((cap,), jnp.float32).at[dest].set(
        gates.reshape(-1), mode="drop")
    tile_expert = jnp.sum(jnp.arange(cap // tm)[:, None]
                          >= tile_end[None, :], axis=1).astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, n_held - 1)
    groups = PairGroups(row_token, row_gate, tile_expert,
                        tile_end[-1].astype(jnp.int32), counts, tm,
                        round_tiles * tm)
    if combines_by_gather(t, k, n_held, expected_pairs):
        groups = groups._replace(
            pair_row=dest.reshape(t, k),
            pair_gate=jnp.where(local, gates.reshape(-1), 0.0).reshape(t, k))
    return groups


def gathered_rows(yr: jax.Array, groups: PairGroups, r=None) -> jax.Array:
    """What round ``r``'s rows ``yr`` [round_rows, D] add to the tokens,
    [T, D] float32: each token's k rows of that round, gated, summed over
    k (``r`` None: the layout's only round, every pair's row is its
    own)."""
    # A pair with no row (not local, no token) reads any row at gate 0.
    # The k rows of a token are k-major, [k, T, D]: whole tiles of
    # (tokens, D), where [T, k, D] would pad k up to a tile's 8 rows.
    # (Index, rows, gate, in that order: a one-round program is then op
    # for op what it was, and its compiled form is found in the cache.)
    at = groups.pair_row.T
    if r is not None:
        at = at - r * groups.round_rows
    rows = jnp.take(yr, at, axis=0, mode="clip")
    gate = groups.pair_gate.T
    if r is not None:       # a pair whose row lies in another round: 0
        gate = jnp.where((at >= 0) & (at < groups.round_rows), gate, 0.0)
    return jnp.sum(rows * gate[:, :, None], axis=0)


def grouped_experts(x: jax.Array, groups: PairGroups, w_gate, w_up, w_down,
                    layer, *, pallas: bool, interpret: bool = False,
                    act: str = "silu"):
    """x [T, D] -> (sum over local pairs of gate * E_e(x) [T, D] float32,
    pairs computed). One round runs once; where several are laid out,
    ceil(tiles in use / tiles a round) of them run under a loop. A
    round's rows come back to their tokens by gather
    (``groups.pair_row``) or by scatter-add (module docstring, 2).
    ``act`` is the gate's activation (ACTS), static."""
    t, d = x.shape
    tm, rr = groups.tm, groups.round_rows
    rt = rr // tm
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)

    def run(tok, te, n_act):
        """One round: its rows' activations through their tiles' experts
        -> [rows, D] float32, ungated; rows of tiles >= n_act are zero."""
        xr = x_pad[tok]
        with jax.named_scope("moe_grouped_experts"):
            if pallas:
                h = moe_grouped_experts_gate_up(xr, w_gate, w_up, layer, te,
                                                n_act, tm=tm, act=act,
                                                interpret=interpret)
                return moe_grouped_experts_down(h, w_down, layer, te, n_act,
                                                tm=tm, interpret=interpret)
            return grouped_ffn_xla(xr, w_gate, w_up, w_down, layer, te,
                                   n_act, tm=tm, act=act)

    gathers = groups.pair_row is not None
    if gathers and groups.row_token.shape[0] == rr:
        tok = groups.row_token
        yr = run(tok, groups.tile_expert, groups.n_tiles)
        return gathered_rows(yr, groups), jnp.sum(tok < t).astype(jnp.int32)
    n_rounds = -(-groups.n_tiles // rt)

    def body(carry):
        r, y, done = carry
        tok = jax.lax.dynamic_slice(groups.row_token, (r * rr,), (rr,))
        gate = (None if gathers else
                jax.lax.dynamic_slice(groups.row_gate, (r * rr,), (rr,)))
        te = jax.lax.dynamic_slice(groups.tile_expert, (r * rt,), (rt,))
        n_act = jnp.clip(groups.n_tiles - r * rt, 0, rt)
        yr = run(tok, te, n_act)
        if gathers:
            y = y + gathered_rows(yr, groups, r)
        else:
            y = y.at[tok].add(yr * gate[:, None], mode="drop")
        return r + 1, y, done + jnp.sum(tok < t).astype(jnp.int32)

    _, y, done = jax.lax.while_loop(
        lambda c: c[0] < n_rounds, body,
        (jnp.int32(0), jnp.zeros((t, d), jnp.float32), jnp.int32(0)))
    return y, done
