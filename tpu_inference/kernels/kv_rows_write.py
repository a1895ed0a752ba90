"""A decode step's K / V write into pools allocated with merged rows, as
ONE Pallas kernel a layer named ``kv_rows_write``.

``kv_cache.write_kv_rows`` is a ``lax.scatter`` of one ``[H, D]`` window
a lane into ``[L, P * R, D]`` (``R = page * H`` rows a page), and the
chip runs a scatter a window at a time: at Phi-4-mini-flash's 10 pair
heads a window starts ``10 t`` rows into 16-row bf16 tiles, a
read-modify-write of part tiles behind a bounds check, six small ops a
window: 64 lanes x (K, V) x 9 writing layers = 1152 windows a step in
series, 500-560 us a layer on the v5e against 10-11 us here (4.3 ms of
cell 7's 27.6 ms decode step: PERF.md section 6, PR 49). Here every
lane's copy is in flight at once.

Mosaic copies whole tiles only (a slice of 10 rows is refused), so a
lane's rows travel inside a SPAN: whole tiles of its page that hold ``H``
rows from any offset a token can have. Per call:

1. every lane's K and V span HBM -> VMEM (``make_async_copy``, all
   started before any is waited for);
2. the lane's new rows, padded to a span and rolled to their offset in
   it (``pltpu.roll`` on 32-bit sublanes: a pair of bf16 rows a word),
   replace the span's rows under an iota mask;
3. every span VMEM -> HBM, again all in flight together.

The span never leaves its page (``min(r // tile * tile, R - span)``): two
lanes never hold one page, so no span carries rows that another lane
writes, and a span that reached into the next page would put stale rows
over that page's owner's new ones. Lanes without a token map to the
trash page (row 0) as in the scatter; several may land there at once,
and nothing reads it.

The pools are the engine's STACKED ones in ``pl.ANY``, each aliased to
its result, the layer a scalar-prefetched operand: under the model's
``lax.scan`` over layers with donated pools nothing copies them
(tests/test_tpu_compile.py reads the chip compiler's HLO).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def tile_rows(dtype) -> int:
    """Rows of a tile: 8 sublanes of 32 bits (8 float32 rows, 16 bf16)."""
    return 32 // jnp.dtype(dtype).itemsize


def span_rows(h: int, rows: int, dtype) -> int:
    """Rows of a lane's span: the whole tiles that hold ``h`` rows from
    any offset ``h * t``. Refuses, with the numbers, what the kernel
    cannot address: a token's rows that split a packed sublane, a page
    that ends inside a tile or is shorter than a span."""
    tile = tile_rows(dtype)
    pack = tile // 8
    if h % pack:
        raise ValueError(
            f"kv_rows_write: {h} rows a token in {jnp.dtype(dtype).name} "
            f"start inside a packed sublane ({pack} rows each); the rows "
            f"a token have to be a multiple of {pack}")
    span = -(-(tile - math.gcd(h, tile) + h) // tile) * tile
    if rows % tile or rows < span:
        raise ValueError(
            f"kv_rows_write: a page of {rows} rows has to be whole "
            f"{tile}-row tiles and hold a span of {span} rows ({h} rows a "
            f"token from any offset)")
    return span


def _write_kernel(layer_ref, starts_ref, k_new, v_new, k_hbm, v_hbm,
                  k_out, v_out, k_buf, v_buf, sem, *, h: int, rows: int,
                  span: int, tile: int):
    del k_hbm, v_hbm                      # aliased: k_out / v_out are they
    layer = layer_ref[0]
    lanes = k_buf.shape[0]
    pools = ((k_out, k_buf, k_new), (v_out, v_buf, v_new))

    def place(w):
        """(first row of lane w's span in the layer's pages laid end to
        end, offset of its token's rows inside the span)."""
        start = starts_ref[w]
        r = jax.lax.rem(start, rows)
        at = jax.lax.min(jax.lax.div(r, tile) * tile, rows - span)
        return pl.multiple_of(start - r + at, tile), r - at

    def copies(w, back: bool, wait: bool):
        first, _ = place(w)
        for i, (hbm, buf, _) in enumerate(pools):
            there = hbm.at[layer, pl.ds(first, span)]
            src, dst = (buf.at[w], there) if back else (there, buf.at[w])
            c = pltpu.make_async_copy(src, dst, sem.at[i])
            c.wait() if wait else c.start()

    def each(fn):
        def body(w, carry):
            fn(w)
            return carry
        jax.lax.fori_loop(0, lanes, body, 0)

    def blend(w):
        # On 32-bit sublanes (Mosaic rotates nothing narrower): a packed
        # dtype's rows 2i, 2i + 1 are word-row i, and a token's rows start
        # on a whole one (span_rows holds h to it).
        pack = tile // 8
        _, off = place(w)
        off = jax.lax.div(off, pack)
        words = (span // pack,) + k_buf.shape[2:]
        row = jax.lax.broadcasted_iota(jnp.int32, words, 0)
        mine = (row >= off) & (row < off + h // pack)
        for _, buf, new in pools:
            old, rows_new = (pltpu.bitcast(x[w], jnp.uint32)
                             for x in (buf, new))
            buf[w] = pltpu.bitcast(
                jnp.where(mine, pltpu.roll(rows_new, off, 0), old),
                buf.dtype)

    each(lambda w: copies(w, back=False, wait=False))
    each(lambda w: copies(w, back=False, wait=True))
    each(blend)
    each(lambda w: copies(w, back=True, wait=False))
    each(lambda w: copies(w, back=True, wait=True))


@partial(jax.jit, static_argnames=("interpret",))
def kv_rows_write(k_pool: jax.Array, v_pool: jax.Array, layer: jax.Array,
                  k_new: jax.Array, v_new: jax.Array, starts: jax.Array,
                  interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """``kv_cache.write_kv_rows`` of one token a lane, for K and V at once.

    k/v_pool: [L, P, R, D]  merged-row pools (``R = page * H``)
    layer:    int32 scalar, may be traced (the model's scan index)
    k/v_new:  [W, H, D]     lane w's rows
    starts:   [W] int32     first row of lane w's token among the layer's
              pages laid end to end (``kv_cache.slot_mapping(..) * H``);
              0, the trash page, for a lane without a token. Two lanes
              with a token never share a page.
    Returns the pools with rows ``starts[w] .. + H`` of layer ``layer``
    replaced, bit for bit what the scatter gives.
    """
    n_layers, n_pages, rows, d = k_pool.shape
    lanes, h, _ = k_new.shape
    dtype = k_pool.dtype
    span, tile = span_rows(h, rows, dtype), tile_rows(dtype)

    def padded(new):
        return jnp.pad(new.astype(dtype), ((0, 0), (0, span - h), (0, 0)))

    flat = (n_layers, n_pages * rows, d)
    whole = pl.BlockSpec((lanes, span, d), lambda i, *_: (0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    k_out, v_out = pl.pallas_call(
        partial(_write_kernel, h=h, rows=rows, span=span, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                  # layer, starts
            grid=(1,), in_specs=[whole, whole, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.VMEM((lanes, span, d), dtype),
                            pltpu.VMEM((lanes, span, d), dtype),
                            pltpu.SemaphoreType.DMA((2,))]),   # K, V
        out_shape=[jax.ShapeDtypeStruct(flat, dtype)] * 2,
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="kv_rows_write",
    )(jnp.asarray(layer, jnp.int32).reshape(1), starts.astype(jnp.int32),
      padded(k_new), padded(v_new), k_pool.reshape(flat),
      v_pool.reshape(flat))
    return k_out.reshape(k_pool.shape), v_out.reshape(v_pool.shape)
