"""kernels/kv_rows_write.py against ``kv_cache.write_kv_rows``, bit for bit.

The kernel is a decode step's K / V write into merged-row pools
(``[L, P, page * H, D]``) through the Pallas backend; the ``lax.scatter``
it replaces there stays as every other write's path and is its reference
here, on the same pools, in interpret mode on the CPU. What Mosaic
accepts is tests/test_tpu_compile.py's, what the chip computes
chip_smoke.py's (``kv_rows_write_err``).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.engine import kv_cache as kvc
from tpu_inference.kernels.kv_rows_write import (kv_rows_write, span_rows,
                                                 tile_rows)

D = 128
LAYERS = 3
TRASH = 0


def _case(h, page, lanes, dtype, seed):
    """Pools of ``lanes + 3`` pages, a token a lane on a page of its own:
    lane 0's at the last position of its page (the span is clamped to
    end with the page), lane 1's at position 0, and of eight lanes or
    more, three without a token (the trash page, together)."""
    rng = np.random.default_rng(seed)
    n_pages = lanes + 3
    rows = page * h

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    pools = rand(LAYERS, n_pages, rows, D), rand(LAYERS, n_pages, rows, D)
    new = rand(lanes, h, D), rand(lanes, h, D)
    pages = rng.permutation(np.arange(1, n_pages))[:lanes]
    tok = rng.integers(0, page, lanes)
    tok[:2] = (page - 1, 0)[:lanes]
    starts = (pages * page + tok) * h
    if lanes >= 8:
        starts[[2, 5, 7]] = TRASH
    return pools, new, jnp.asarray(starts, jnp.int32)


def _bits(x):
    return np.asarray(x.view(jnp.uint16 if x.dtype == jnp.bfloat16
                             else jnp.uint32))


@pytest.mark.parametrize("lanes", [1, 8, 64])
@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("h", [10, 8, 4])
def test_the_kernel_writes_the_scatters_pool_bit_for_bit(h, page, lanes):
    pools, new, starts = _case(h, page, lanes, jnp.bfloat16,
                               seed=h * 1000 + page + lanes)
    for layer in (0, LAYERS - 1):
        want = [kvc.write_kv_rows(pool, jnp.int32(layer), rows, starts)
                for pool, rows in zip(pools, new)]
        got = kv_rows_write(*pools, jnp.int32(layer), *new, starts,
                            interpret=True)
        trashed = np.flatnonzero(np.asarray(starts) == TRASH)
        for g, w, before, rows in zip(got, want, pools, new):
            g, w, before = _bits(g), _bits(w), _bits(before)
            # Every page a lane holds, and every page nobody wrote.
            assert (g[:, 1:] == w[:, 1:]).all()
            # The trash page holds one of its lanes' rows (which is
            # nobody's business: nothing reads it) and is otherwise as it
            # was, in this layer and in the others.
            others = [i for i in range(LAYERS) if i != layer]
            assert (g[others, TRASH] == before[others, TRASH]).all()
            assert (g[layer, TRASH, h:] == before[layer, TRASH, h:]).all()
            assert any((g[layer, TRASH, :h] == x).all() for x in (
                [_bits(rows[i]) for i in trashed]
                or [before[layer, TRASH, :h]]))


def test_a_float32_pool_of_small_pages_is_written_too():
    """The tiny presets' pools: float32 (8-row tiles), 4-token pages of 2
    heads, so a span is the whole page."""
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.standard_normal((2, 9, 8, 16)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((4, 2, 16)), jnp.float32)
    starts = jnp.asarray([3 * 8 + 6, 5 * 8, TRASH, 8 * 8 + 2], jnp.int32)
    want = kvc.write_kv_rows(pool, jnp.int32(1), new, starts)
    got_k, got_v = kv_rows_write(pool, pool, jnp.int32(1), new, new, starts,
                                 interpret=True)
    assert (_bits(got_k) == _bits(want)).all()
    assert (_bits(got_v) == _bits(want)).all()


@pytest.mark.parametrize("h,rows,dtype,spans", [
    (10, 160, jnp.bfloat16, 32), (8, 128, jnp.bfloat16, 16),
    (4, 64, jnp.bfloat16, 16), (2, 8, jnp.float32, 8),
    (10, 160, jnp.float32, 16)])
def test_a_span_is_the_whole_tiles_a_tokens_rows_can_lie_in(h, rows, dtype,
                                                            spans):
    span = span_rows(h, rows, dtype)
    assert span == spans
    tile = tile_rows(dtype)
    for r in range(0, rows, h):
        at = min(r // tile * tile, rows - span)
        assert at % tile == 0 and at <= r and r + h <= at + span


@pytest.mark.parametrize("h,rows,dtype,numbers", [
    (5, 80, jnp.bfloat16, ("5 rows", "bfloat16", "multiple of 2")),
    (10, 16, jnp.bfloat16, ("16 rows", "span of 32")),
    (10, 40, jnp.bfloat16, ("40 rows", "16-row tiles"))])
def test_what_the_kernel_cannot_address_is_refused_with_its_numbers(
        h, rows, dtype, numbers):
    pool = jnp.zeros((1, 2, rows, D), dtype)
    new = jnp.zeros((1, h, D), dtype)
    with pytest.raises(ValueError) as e:
        kv_rows_write(pool, pool, jnp.int32(0), new, new,
                      jnp.zeros((1,), jnp.int32), interpret=True)
    for number in numbers:
        assert number in str(e.value)


@pytest.mark.parametrize("preset,backend,path", [
    ("tiny-sambay", "pallas", "kernel"), ("tiny-sambay", "dense", "scatter"),
    ("tiny-llama", "pallas", "scatter")])
def test_healthz_says_how_a_decode_step_writes(preset, backend, path):
    """``device_info()`` is /healthz ``replicas[].device`` and the worker
    hello: the path is fixed when the programs are built, from the pool's
    layout and the backend."""
    from tpu_inference.config import PRESETS, EngineConfig
    from tpu_inference.engine.engine import InferenceEngine

    eng = InferenceEngine(
        PRESETS[preset](),
        EngineConfig(page_size=16, num_pages=16, max_pages_per_seq=4,
                     max_batch_size=2, prefill_buckets=(16,)),
        attn_backend=backend, pallas_interpret=backend == "pallas")
    assert eng.device_info()["kv_decode_write"] == path


@pytest.mark.parametrize("backend,path", [("pallas", "kernel"),
                                          ("dense", "scatter")])
def test_the_autosize_line_says_it_too(capsys, backend, path):
    from tpu_inference.config import PRESETS, EngineConfig
    from tpu_inference.engine import autosize

    autosize.resolve_sizing(
        PRESETS["phi4-mini-flash"](),
        EngineConfig(attn_backend=backend, page_size=16,
                     max_pages_per_seq=640),
        {"max_batch_size": "auto", "num_pages": "auto",
         "decode_ladder": "off", "target_ctx": 2176, "batch_cap": 64},
        hbm_bytes=16.91e9)
    assert f"kv_decode_write={path} " in capsys.readouterr().err


@pytest.mark.parametrize("preset,backend,path", [
    ("tiny-ling", "pallas", "kernel"), ("tiny-ling", "dense", "xla"),
    ("tiny-sambay", "pallas", None)])
def test_healthz_says_how_a_decode_step_passes_the_convolution(
        preset, backend, path):
    """``device.kda_tail_step``, beside ``kv_decode_write``: a delta-rule
    layer's one-token convolution is the kernel under the Pallas backend
    (kernels/delta_rule.kda_tail_step) and the XLA form off it; a model
    with no such layer has no such field."""
    from tpu_inference.config import PRESETS, EngineConfig
    from tpu_inference.engine.engine import InferenceEngine

    eng = InferenceEngine(
        PRESETS[preset](),
        EngineConfig(page_size=16, num_pages=16, max_pages_per_seq=4,
                     max_batch_size=2, prefill_buckets=(16,),
                     enable_prefix_cache=False),
        attn_backend=backend, pallas_interpret=backend == "pallas")
    assert eng.device_info().get("kda_tail_step") == path


@pytest.mark.parametrize("preset,backend,said", [
    ("ling3-flash-ep8", "pallas", "kda_tail_step=kernel "),
    ("ling3-flash-ep8", "dense", "kda_tail_step=xla "),
    ("phi4-mini-flash", "pallas", "")])
def test_the_autosize_line_says_the_convolutions_path_too(capsys, preset,
                                                          backend, said):
    from tpu_inference.config import PRESETS, EngineConfig
    from tpu_inference.engine import autosize

    autosize.resolve_sizing(
        PRESETS[preset](),
        EngineConfig(attn_backend=backend, page_size=16,
                     max_pages_per_seq=640),
        {"max_batch_size": "auto", "num_pages": "auto",
         "decode_ladder": "off", "target_ctx": 2176, "batch_cap": 64},
        hbm_bytes=16.91e9)
    line = capsys.readouterr().err
    assert said in line and ("kda_tail_step" in line) == bool(said)
