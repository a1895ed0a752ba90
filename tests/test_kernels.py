"""Pallas kernel correctness vs the dense jnp reference paths.

Kernels run in interpreter mode on CPU (tests/conftest.py forces the cpu
backend); the same code compiles via Mosaic on a real TPU. The dense
gather-based attention in models/common.py + engine/kv_cache.py is the
correctness oracle (SURVEY.md §7 layer 5: "kernel validated against it").
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_inference import config as cfgs
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine.engine import InferenceEngine
from tpu_inference.kernels.paged_attention import paged_attention
from tpu_inference.models import build_model, common


# The kernels take the engine's STACKED pool [L, P, page, Hkv, D] and a
# layer index (and, quantized, that layer's scales). Every pool here has
# LAYERS layers with different contents (and different scales: layer l's
# values are (l + 1) times larger), and tests run at the first, a middle
# and the last layer: a wrong layer offset, or codes of one layer under
# the scales of another, cannot pass.
LAYERS = 3
LAYER_IDS = [0, 1, LAYERS - 1]


def _stacked_pool(rng, num_pages, page_size, hkv, d, dtype=jnp.float32):
    """One stacked [LAYERS, P, page, Hkv, D] pool, each layer different."""
    x = rng.standard_normal((LAYERS, num_pages, page_size, hkv, d))
    x *= np.arange(1, LAYERS + 1).reshape(LAYERS, 1, 1, 1, 1)
    return jnp.asarray(x, dtype)


def _quantize_pools(k_pool, v_pool, kv_quant) -> kvc.KVPages:
    """The stacked pools as the engine would hold them under ``kv_quant``
    (kv_cache.alloc_kv_pages layouts): codes + per-(token, head) scales."""
    if kv_quant == "none":
        return kvc.KVPages(k=k_pool, v=v_pool)
    quant = kvc.quantize_kv_int4 if kv_quant == "int4" else kvc.quantize_kv
    (k, ks), (v, vs) = quant(k_pool), quant(v_pool)
    return kvc.KVPages(k=k, v=v, k_scale=ks, v_scale=vs)


def _random_paged_setup(rng, *, b=3, hq=8, hkv=2, d=64, page_size=8,
                        num_pages=32, max_pages=4, dtype=jnp.float32):
    """Build a stacked pool + block tables with random per-seq lengths."""
    k_pool = _stacked_pool(rng, num_pages, page_size, hkv, d, dtype)
    v_pool = _stacked_pool(rng, num_pages, page_size, hkv, d, dtype)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    # Distinct physical pages per sequence (page 0 reserved as trash).
    perm = rng.permutation(np.arange(1, num_pages))[:b * max_pages]
    bt = perm.reshape(b, max_pages).astype(np.int32)
    kv_len = rng.integers(1, page_size * max_pages + 1, size=b).astype(np.int32)
    return q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(kv_len)


def _dense_reference(q, k_pool, v_pool, layer, bt, kv_len):
    kv = kvc.KVPages(k=k_pool, v=v_pool)
    k_all, v_all = kvc.gather_kv(kv, layer, bt)
    out = common.dense_causal_attention(
        q[:, None], k_all, v_all, q_offset=kv_len - 1, kv_len=kv_len)
    return out[:, 0]


@pytest.mark.parametrize("layer", LAYER_IDS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_dense(dtype, layer):
    rng = np.random.default_rng(0)
    q, k_pool, v_pool, bt, kv_len = _random_paged_setup(rng, dtype=dtype)
    got = paged_attention(q, k_pool, v_pool, layer, bt, kv_len,
                          interpret=True)
    want = _dense_reference(q, k_pool, v_pool, layer, bt, kv_len)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_paged_attention_single_token_context():
    """kv_len=1: only the current token is attendable (softmax of one)."""
    rng = np.random.default_rng(1)
    q, k_pool, v_pool, bt, _ = _random_paged_setup(rng, b=2)
    kv_len = jnp.asarray([1, 1], jnp.int32)
    got = paged_attention(q, k_pool, v_pool, 1, bt, kv_len, interpret=True)
    want = _dense_reference(q, k_pool, v_pool, 1, bt, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_attention_mha():
    """n_rep == 1 (no GQA grouping)."""
    rng = np.random.default_rng(2)
    q, k_pool, v_pool, bt, kv_len = _random_paged_setup(rng, hq=4, hkv=4)
    got = paged_attention(q, k_pool, v_pool, LAYERS - 1, bt, kv_len,
                          interpret=True)
    want = _dense_reference(q, k_pool, v_pool, LAYERS - 1, bt, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", LAYER_IDS)
@pytest.mark.parametrize("window", [0, 11])
@pytest.mark.parametrize("kv_quant", ["none", "int8", "int4"])
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_kernels_read_the_named_layer(kernel, kv_quant, window, layer):
    """Both kernels, on the stacked pool in each KV layout, windowed and
    not, attend over layer ``layer``'s pages and scales and no other's:
    they equal the dense gather of THAT layer (kv_cache.gather_kv, which
    dequantizes) and differ from every other layer's."""
    from tpu_inference.kernels.prefill_attention import paged_prefill_attention

    rng = np.random.default_rng(21)
    b, s, hq, hkv, d, pg, npg, mp = 2, 16, 4, 2, 32, 8, 24, 5
    kv = _quantize_pools(_stacked_pool(rng, npg, pg, hkv, d),
                         _stacked_pool(rng, npg, pg, hkv, d), kv_quant)
    bt = jnp.asarray(rng.permutation(np.arange(1, npg))[:b * mp]
                     .reshape(b, mp).astype(np.int32))
    # The codes are read from the stack in place; the scales (1% of the
    # bytes) are the caller's slice of this layer, as in make_paged_attn.
    scales = ((kv.k_scale[layer], kv.v_scale[layer]) if kv.quantized
              else (None, None))
    if kernel == "decode":
        q = jnp.asarray(rng.standard_normal((b, 1, hq, d)), jnp.float32)
        kv_len = jnp.asarray([13, 37], jnp.int32)
        q_off = kv_len - 1
        got = paged_attention(q[:, 0], kv.k, kv.v, layer, bt, kv_len,
                              *scales, interpret=True,
                              sliding_window=window)[:, None]
    else:
        q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
        q_off = jnp.asarray([0, 19], jnp.int32)      # fresh + cached prefix
        kv_len = q_off + s
        got = paged_prefill_attention(q, kv.k, kv.v, layer, bt, kv_len,
                                      q_off, *scales, block_q=8,
                                      interpret=True, sliding_window=window)

    def dense(at_layer):
        k_all, v_all = kvc.gather_kv(kv, at_layer, bt)
        return np.asarray(common.dense_causal_attention(
            q, k_all, v_all, q_offset=q_off, kv_len=kv_len,
            sliding_window=window))

    # Values grow with the layer number (_stacked_pool), and so does the
    # f32 rounding of either order of summation.
    tol = 2e-5 * (layer + 1)
    np.testing.assert_allclose(np.asarray(got), dense(layer),
                               rtol=tol, atol=tol)
    for other in set(range(LAYERS)) - {layer}:
        assert not np.allclose(np.asarray(got), dense(other), atol=1e-2)


# ---------------------------------------------------------------------------
# The decode kernel walks a lane's pages a BLOCK at a time (PR 27): 16
# pages of 16 tokens here, so 256 tokens a block and, with 40 pages a
# sequence, a last block that is half there. What a block makes new:
# contexts that end on a block's last token, one token into the next, and
# inside a later block's first page; a window whose first page sits in the
# middle of what would be a block of the table; lanes of the rung that hold
# no sequence (kv_len 0: nothing read, rows 0) or one token on the trash
# page, between live ones.
# ---------------------------------------------------------------------------

BLOCK_PAGE, BLOCK_MP = 16, 40
BLOCK_CASES = {
    "block_edges": (0, [256, 257, 521, 640, 15]),
    "idle_lanes": (0, [300, 0, 1, 256, 0, 521]),
    "window_mid_block": (300, [600, 299, 0, 640, 316, 1]),
    "window_one_block": (100, [400, 1, 128, 639]),
}


def _block_setup(rng, kv_lens, kv_quant, d, strangers_nan=False):
    """Stacked pools (each layer different) in ``kv_quant``'s layout and
    block tables that name exactly the pages a lane's tokens fill, the
    rest 0 (the trash page), as the engine's allocator leaves them.
    ``strangers_nan``: every page no row names, bar page 0, is NaN."""
    # 8 KV heads at head_dim 128: an int8 page's scales fill 128 lanes,
    # as the kernel's own page copies need them to.
    hq, hkv = (16, 8) if d == 128 else (8, 2)
    need = [-(-n // BLOCK_PAGE) for n in kv_lens]
    n_pages = 1 + sum(need) + 7
    dtype = jnp.bfloat16 if kv_quant == "bf16" else jnp.float32
    k_pool = _stacked_pool(rng, n_pages, BLOCK_PAGE, hkv, d, dtype)
    v_pool = _stacked_pool(rng, n_pages, BLOCK_PAGE, hkv, d, dtype)
    ids = iter(rng.permutation(np.arange(1, n_pages)))
    bt = np.zeros((len(kv_lens), BLOCK_MP), np.int32)
    for i, n in enumerate(need):
        bt[i, :n] = [next(ids) for _ in range(n)]
    if strangers_nan:
        strange = np.setdiff1d(np.arange(1, n_pages), bt.ravel())
        assert strange.size >= 7
        k_pool = k_pool.at[:, strange].set(jnp.nan)
        v_pool = v_pool.at[:, strange].set(jnp.nan)
    kv = _quantize_pools(k_pool, v_pool,
                         "none" if kv_quant == "bf16" else kv_quant)
    q = jnp.asarray(rng.standard_normal((len(kv_lens), hq, d)), dtype)
    return q, kv, jnp.asarray(bt), jnp.asarray(kv_lens, jnp.int32)


def _check_blocks(q, kv, bt, kv_len, window, layer, tol):
    scales = ((kv.k_scale[layer], kv.v_scale[layer]) if kv.quantized
              else (None, None))
    got = np.asarray(paged_attention(
        q, kv.k, kv.v, layer, bt, kv_len, *scales, interpret=True,
        sliding_window=window), np.float32)
    k_all, v_all = kvc.gather_kv(kv, layer, bt)
    live = np.asarray(kv_len) > 0
    seen = jnp.maximum(kv_len, 1)
    want = np.asarray(common.dense_causal_attention(
        q[:, None], k_all, v_all, q_offset=seen - 1, kv_len=seen,
        sliding_window=window)[:, 0], np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    # A lane without a sequence read nothing: its (discarded) rows are 0.
    assert not got[~live].any()


# The kernel copies pages by hand where the pool's minor dim is whole
# 128-lane tiles (head_dim 128 as float, or as int8 with 8 KV heads) and
# lets the pipeline fetch them where it is not (head_dim 32 here; int4's
# D / 2 bytes).
@pytest.mark.parametrize("kv_quant,d", [
    ("none", 128), ("bf16", 128), ("int8", 128), ("int4", 128),
    ("none", 32), ("int8", 32)])
@pytest.mark.parametrize("case", sorted(BLOCK_CASES))
def test_paged_attention_walks_blocks_of_pages(case, kv_quant, d):
    window, kv_lens = BLOCK_CASES[case]
    rng = np.random.default_rng(27)
    q, kv, bt, kv_len = _block_setup(rng, kv_lens, kv_quant, d)
    _check_blocks(q, kv, bt, kv_len, window, layer=1,
                  tol=4e-2 if kv_quant == "bf16" else 1e-4)


@pytest.mark.parametrize("kv_quant,d", [("none", 128), ("bf16", 128),
                                        ("none", 32)])
@pytest.mark.parametrize("case", sorted(BLOCK_CASES))
def test_paged_attention_fetches_no_page_a_lane_does_not_own(case, kv_quant,
                                                             d):
    """Every pool page that no block-table row names (trash page 0
    excepted) is NaN: a page fetched and multiplied, even by a weight of
    0, shows as NaN in the result."""
    window, kv_lens = BLOCK_CASES[case]
    rng = np.random.default_rng(28)
    q, kv, bt, kv_len = _block_setup(rng, kv_lens, kv_quant, d,
                                     strangers_nan=True)
    _check_blocks(q, kv, bt, kv_len, window, layer=LAYERS - 1,
                  tol=4e-2 if kv_quant == "bf16" else 1e-4)


@pytest.mark.parametrize("head_dim", [32, 128])
def test_engine_pallas_backend_matches_dense(head_dim):
    """Full engine generation with the Pallas decode kernel == dense path:
    at tiny-llama's own head size (pages through the pipeline) and at 128
    (pages copied by hand, as every served model's are); a rung of 4 with
    3 prompts, so one lane is idle (kv_len 0) throughout."""
    import dataclasses

    model_cfg = dataclasses.replace(cfgs.tiny_llama(vocab_size=256),
                                    head_dim_override=head_dim)
    ecfg = cfgs.EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=16,
                             max_batch_size=4, prefill_buckets=(16, 32),
                             decode_steps_per_call=4)
    params, _ = build_model(model_cfg, seed=0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (5, 12, 27)]

    dense = InferenceEngine(model_cfg, ecfg, params=params,
                            attn_backend="dense")
    pallas = InferenceEngine(model_cfg, ecfg, params=params,
                             attn_backend="pallas", pallas_interpret=True)
    got_d = dense.generate(prompts, max_new_tokens=10)
    got_p = pallas.generate(prompts, max_new_tokens=10)
    assert got_d == got_p


def test_engine_pallas_backend_mixtral_sharded_matches_dense():
    """MoE (expert-parallel) engine under a tp mesh with the Pallas
    decode+prefill kernels == dense single-device."""
    from tpu_inference.parallel.mesh import build_mesh

    model_cfg = cfgs.tiny_mixtral(vocab_size=256)
    ecfg = cfgs.EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=16,
                             max_batch_size=4, prefill_buckets=(16, 32),
                             decode_steps_per_call=4)
    params, _ = build_model(model_cfg, seed=0)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (5, 18)]

    dense = InferenceEngine(model_cfg, ecfg, params=params,
                            attn_backend="dense")
    got_d = dense.generate(prompts, max_new_tokens=8)
    mesh = build_mesh(cfgs.ParallelConfig(tp=2))
    pallas = InferenceEngine(model_cfg, ecfg, params=params,
                             attn_backend="pallas", mesh=mesh,
                             pallas_interpret=True)
    got_p = pallas.generate(prompts, max_new_tokens=8)
    assert got_d == got_p


def test_engine_pallas_backend_sharded_matches_dense():
    """Pallas decode under a dp×tp mesh (shard_map over tp) == dense.

    The kernel is head-local: q shards on query heads, the pool on kv
    heads; no collective inside attention (engine.make_paged_attn)."""
    from tpu_inference.parallel.mesh import build_mesh

    model_cfg = cfgs.tiny_llama(vocab_size=256)
    ecfg = cfgs.EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=16,
                             max_batch_size=4, prefill_buckets=(16, 32),
                             decode_steps_per_call=4)
    params, _ = build_model(model_cfg, seed=0)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (5, 12, 27)]

    dense = InferenceEngine(model_cfg, ecfg, params=params,
                            attn_backend="dense")
    got_d = dense.generate(prompts, max_new_tokens=10)
    mesh = build_mesh(cfgs.ParallelConfig(dp=2, tp=2))
    pallas = InferenceEngine(model_cfg, ecfg, params=params,
                             attn_backend="pallas", mesh=mesh,
                             pallas_interpret=True)
    got_p = pallas.generate(prompts, max_new_tokens=10)
    assert got_d == got_p


# Shapes of test_paged_prefill_attention_matches_dense. Defaults: 2
# sequences of a 32-row chunk, 8 q / 2 kv heads x 64, 8-token pages (a
# page that is not whole 128-lane tiles: the pipeline-fed side), block_q
# 128. ``q_off`` / ``prompt``: per sequence, the cached prefix and the
# chunk's real rows (kv_len = their sum; 0 + 0 is a row of the batch that
# holds no sequence). head_dim 128 pools are copied by hand, a block of
# 256 tokens at a time (PR 29), so contexts there run to several blocks.
PREFILL_SHAPES = {
    "offsets-bq16": dict(block_q=16, q_off=(5, 0), prompt=(20, 32)),
    "offsets-bq8": dict(block_q=8, q_off=(0, 13), prompt=(20, 32)),
    # Contexts that end mid-block and mid-page (321 = a block + 4 pages +
    # 1 token), on a block's last token (512), and one token on (513).
    "mid-block-mid-page": dict(d=128, pg=16, block_q=16, mp=40,
                               q_off=(300, 480, 481), prompt=(21, 32, 32)),
    # A batch with an all-padding row between two live ones, and a live
    # one whose last query block is all padding: 0, not NaN.
    "padding-row": dict(d=128, pg=16, block_q=8, mp=24,
                        q_off=(270, 0, 0), prompt=(32, 0, 5)),
    # Qwen2's heads (4 kv x 7 q: a row's token is row // 7) behind a
    # cached prefix.
    "qwen2-heads": dict(hq=28, hkv=4, d=128, pg=16, mp=24,
                        q_off=(300, 40), prompt=(32, 17)),
    # A verification call: a handful of query rows.
    "verify-4-rows": dict(s=4, d=128, pg=16, mp=24, q_off=(290, 37),
                          prompt=(4, 4)),
    "head-dim-96": dict(hq=4, hkv=4, d=96, pg=16, mp=24, q_off=(300, 0),
                        prompt=(32, 20)),
    # 16-token pages x 8 kv heads: the scales are whole tiles, by hand.
    "int8-by-hand": dict(hq=16, hkv=8, d=128, pg=16, mp=24, q_off=(300, 0),
                         prompt=(32, 20), kv_quant="int8"),
    "int8-pipelined": dict(d=128, pg=16, mp=24, q_off=(300, 0),
                           prompt=(32, 20), kv_quant="int8"),
    "int4": dict(d=128, pg=16, mp=24, q_off=(300, 0), prompt=(32, 20),
                 kv_quant="int4"),
}


@pytest.mark.parametrize("shape,layer", [
    (name, layer) for name in PREFILL_SHAPES
    for layer in (LAYER_IDS if name.startswith("offsets") else [1])])
def test_paged_prefill_attention_matches_dense(shape, layer):
    """Flash prefill over pool pages == dense gather+causal attention,
    including cached-prefix offsets and partially-filled last pages."""
    from tpu_inference.kernels.prefill_attention import paged_prefill_attention

    c = dict(s=32, hq=8, hkv=2, d=64, pg=8, mp=8, block_q=128,
             kv_quant="none")
    c.update(PREFILL_SHAPES[shape])
    rng = np.random.default_rng(7)
    b, s, mp = len(c["q_off"]), c["s"], c["mp"]
    npg = 1 + b * mp
    kv = _quantize_pools(
        _stacked_pool(rng, npg, c["pg"], c["hkv"], c["d"]),
        _stacked_pool(rng, npg, c["pg"], c["hkv"], c["d"]), c["kv_quant"])
    q = jnp.asarray(rng.standard_normal((b, s, c["hq"], c["d"])), jnp.float32)
    perm = rng.permutation(np.arange(1, npg))[:b * mp]
    bt = jnp.asarray(perm.reshape(b, mp).astype(np.int32))
    q_off = jnp.asarray(c["q_off"], jnp.int32)
    prompt = np.asarray(c["prompt"])
    kv_len = q_off + jnp.asarray(prompt, jnp.int32)
    scales = ((kv.k_scale[layer], kv.v_scale[layer]) if kv.quantized
              else (None, None))

    got = np.asarray(paged_prefill_attention(
        q, kv.k, kv.v, layer, bt, kv_len, q_off, *scales,
        block_q=c["block_q"], interpret=True))
    k_all, v_all = kvc.gather_kv(kv, layer, bt)
    want = common.dense_causal_attention(q, k_all, v_all, q_offset=q_off,
                                         kv_len=kv_len)
    assert np.isfinite(got).all()
    tol = 2e-5 * (layer + 1)       # layer l's values are l + 1 times larger
    for i in range(b):
        n = int(prompt[i])                    # padded query rows unused
        np.testing.assert_allclose(got[i, :n], np.asarray(want)[i, :n],
                                   rtol=tol, atol=tol)
        if n == 0:
            assert not got[i].any()           # a row without a sequence


def test_paged_prefill_non_power_of_two_bucket():
    """Lengths with no 128 divisor pick a smaller valid query block."""
    from tpu_inference.kernels.prefill_attention import paged_prefill_attention

    rng = np.random.default_rng(8)
    b, s, h, d, pg, npg, mp = 1, 24, 4, 32, 8, 16, 4
    k_pool = _stacked_pool(rng, npg, pg, h, d)
    v_pool = _stacked_pool(rng, npg, pg, h, d)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    bt = jnp.asarray(np.arange(1, 1 + mp)[None].astype(np.int32))
    kv_len = jnp.asarray([s], jnp.int32)
    got = paged_prefill_attention(q, k_pool, v_pool, 1, bt, kv_len,
                                  jnp.zeros((b,), jnp.int32), block_q=16,
                                  interpret=True)
    kv = kvc.KVPages(k=k_pool, v=v_pool)
    k_all, v_all = kvc.gather_kv(kv, 1, bt)
    want = common.dense_causal_attention(q, k_all, v_all, q_offset=0,
                                         kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sp,hq,hkv", [(4, 4, 4), (8, 8, 2)])
def test_ring_attention_matches_dense(sp, hq, hkv):
    """Sequence-parallel ring attention == dense causal attention."""
    from jax.sharding import Mesh
    from tpu_inference.kernels.ring_attention import ring_attention

    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    rng = np.random.default_rng(4)
    b, s, d = 2, 8 * sp, 16
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)

    got = ring_attention(q, k, v, mesh=mesh)
    want = common.dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_bf16():
    from jax.sharding import Mesh
    from tpu_inference.kernels.ring_attention import ring_attention

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    rng = np.random.default_rng(5)
    b, s, h, d = 1, 32, 4, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    got = ring_attention(q, k, v, mesh=mesh)
    want = common.dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("sp,hq,hkv", [(4, 4, 4), (4, 8, 4), (2, 8, 2)])
def test_ulysses_attention_matches_dense(sp, hq, hkv):
    """All-to-all (Ulysses) sequence parallelism == dense causal
    attention, including GQA head ratios."""
    from jax.sharding import Mesh
    from tpu_inference.kernels.ulysses_attention import ulysses_attention

    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    rng = np.random.default_rng(6)
    b, s, d = 2, 8 * sp, 16
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)

    got = ulysses_attention(q, k, v, mesh=mesh)
    want = common.dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_matches_ring():
    """The two sequence-parallel schemes agree with each other (and the
    dense oracle) on the same sharded inputs."""
    from jax.sharding import Mesh
    from tpu_inference.kernels.ring_attention import ring_attention
    from tpu_inference.kernels.ulysses_attention import ulysses_attention

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    rng = np.random.default_rng(7)
    b, s, h, d = 1, 32, 4, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ulysses_attention(q, k, v, mesh=mesh)),
        np.asarray(ring_attention(q, k, v, mesh=mesh)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [5, 8, 20])
def test_sp_attention_sliding_window_matches_dense(window):
    """Windowed ring AND Ulysses SP attention == the window-masked dense
    oracle (VERDICT r4 item 5: SWA composes with sequence parallelism).
    Windows chosen to exercise all mask regimes on 8-token shards:
    window < shard (behind-window chunk-skip fires), window == shard,
    and window spanning multiple shards."""
    from jax.sharding import Mesh
    from tpu_inference.kernels.ring_attention import ring_attention
    from tpu_inference.kernels.ulysses_attention import ulysses_attention

    rng = np.random.default_rng(9)
    b, s, hq, hkv, d = 2, 32, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)

    want = common.dense_causal_attention(q, k, v, sliding_window=window)
    # Ring at sp=4 (8-token shards: window 5 puts whole chunks behind the
    # window, firing the chunk-skip); Ulysses at sp=2 (GQA head counts
    # must divide the axis).
    for name, fn, sp in (("ring", ring_attention, 4),
                         ("ulysses", ulysses_attention, 2)):
        mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
        got = fn(q, k, v, mesh=mesh, sliding_window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
    # And the window actually binds (differs from full attention).
    full = common.dense_causal_attention(q, k, v)
    assert not np.allclose(np.asarray(want), np.asarray(full))


def test_ulysses_attention_bf16():
    """bf16 activations stay bf16 across the all-to-alls (raw-dtype
    wire bytes) and still match the dense oracle within bf16 tolerance."""
    from jax.sharding import Mesh
    from tpu_inference.kernels.ulysses_attention import ulysses_attention

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    rng = np.random.default_rng(8)
    b, s, h, d = 1, 32, 4, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    got = ulysses_attention(q, k, v, mesh=mesh)
    assert got.dtype == jnp.bfloat16
    want = common.dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# The delta rule (kernels/delta_rule.py): the chunk kernel and the
# one-token update, in interpret mode, against the recurrence a token at
# a time. Float32 at the highest precision on both sides.
# ---------------------------------------------------------------------------

def _delta_inputs(lanes, rows, heads, d, seed, bound=False, aligned=0.0):
    """``aligned``: a common direction added to every key and query
    before they are normalised, a slow decay and a beta near 1 (what a
    deep layer of a randomly initialised stack gives: k_i . k_j -> 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    shape = (lanes, rows, heads, d)
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], shape))
    if bound:
        g = jnp.full(shape, -5.0)
    if aligned:
        g = g * 0.002
    return (unit(jax.random.normal(ks[0], shape) + aligned) * d ** -0.5,
            unit(jax.random.normal(ks[1], shape) + aligned),
            jax.random.normal(ks[2], shape), g,
            jax.nn.sigmoid(jax.random.normal(ks[4], (lanes, rows, heads))
                           + (3.0 if aligned else 0.0)),
            jax.random.normal(ks[5], (lanes, heads, d, d)))


def _delta_pool(s0, layers=2, layer=1):
    """A state pool of ``layers`` layers whose layer ``layer`` holds s0
    in slots 1.. (slot 0 the trash slot) and sevens elsewhere."""
    lanes = s0.shape[0]
    pool = jnp.full((layers, lanes + 1) + s0.shape[1:], 7.0)
    return pool.at[layer, 1:].set(s0)


@pytest.mark.parametrize("lanes,rows,heads,d,lens,bound", [
    (2, 128, 2, 64, (128, 91), False),      # a row that ends inside a block
    (1, 64, 1, 128, (64,), True),           # every channel AT the bound
    (3, 256, 2, 32, (256, 70, 0), False),   # two time blocks, an idle row
    (2, 16, 1, 64, (16, 5), False),         # a bucket shorter than a block
])
def test_kda_chunk_kernel_equals_the_recurrence(lanes, rows, heads, d, lens,
                                                bound):
    from tpu_inference.kernels import delta_rule as dr

    q, k, v, g, beta, s0 = _delta_inputs(lanes, rows, heads, d, rows, bound)
    lens = jnp.asarray(lens, jnp.int32)
    o_ref, s_ref = dr.kda_recurrence(q, k, v, g, beta, s0, lens)
    pool = _delta_pool(s0)
    at = jnp.arange(1, lanes + 1)
    flat = lambda a: a.reshape(lanes, rows, -1)                # noqa: E731
    o, out = dr.kda_chunk_prefill(
        pool, 1, at, jnp.where(lens > 0, at, 0), jnp.zeros((lanes,), bool),
        flat(q), flat(k), flat(v), flat(g), beta, lens, n_heads=heads,
        interpret=True)
    live = (jnp.arange(rows)[None] < lens[:, None])[..., None, None]
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(out).all())
    err = jnp.where(live, o.reshape(o_ref.shape) - o_ref, 0.0)
    assert float(jnp.abs(err).max()) < 2e-5 * float(jnp.std(o_ref) + 1)
    assert float(jnp.abs(out[1, 1:] - s_ref).max()) < 2e-5
    # A lane with no valid position leaves its state as it was (it wrote
    # the trash slot); the other layer's states are untouched.
    idle = np.asarray(lens) == 0
    assert bool((out[1, 1:][idle] == s0[idle]).all())
    assert bool((out[0] == pool[0]).all())


def test_kda_chunk_kernel_carries_the_state_across_chunks():
    """Three engine chunks of one stream, the second from the slot the
    first wrote and the last ragged, equal ONE recurrence over the whole;
    a fresh lane reads zeros whatever its slot holds."""
    from tpu_inference.kernels import delta_rule as dr

    heads, d, chunk = 2, 64, 64
    q, k, v, g, beta, s0 = _delta_inputs(1, 3 * chunk, heads, d, 7)
    total = 2 * chunk + 23
    o_ref, s_ref = dr.kda_recurrence(q, k, v, g, beta, jnp.zeros_like(s0),
                                     jnp.asarray([total]))
    pool = _delta_pool(s0)                   # the slot holds somebody's
    outs = []
    for c in range(3):
        part = lambda a: a[:, c * chunk:(c + 1) * chunk].reshape(  # noqa
            1, chunk, -1)
        o, pool = dr.kda_chunk_prefill(
            pool, 1, jnp.asarray([1]), jnp.asarray([1]),
            jnp.asarray([c == 0]), part(q), part(k), part(v), part(g),
            beta[:, c * chunk:(c + 1) * chunk],
            jnp.asarray([min(chunk, total - c * chunk)]), n_heads=heads,
            interpret=True)
        outs.append(o.reshape(1, chunk, heads, d))
    o = jnp.concatenate(outs, axis=1)[:, :total]
    assert float(jnp.abs(o - o_ref[:, :total]).max()) < 2e-5
    assert float(jnp.abs(pool[1, 1] - s_ref[0]).max()) < 2e-5


@pytest.mark.parametrize("aligned,tol", [(2.0, 5e-4), (10.0, 5e-3)])
def test_kda_chunk_kernel_with_keys_that_point_one_way(aligned, tol):
    """Keys of a block nearly parallel (k_i . k_j 0.8 / 0.99), a decay
    near 1 and a beta near 1: ``A`` is then close to a full lower
    triangle of ones, whose powers reach C(63, k) and cancel in float32
    (inverting the whole block by squarings read 1e13 times the output's
    spread here; the deep layers of a randomly initialised stack are
    like this, and the first chip run of the cell read not correct on one
    seed in four for it). The blocks of 16 with forward substitution
    below them stay at rounding."""
    from tpu_inference.kernels import delta_rule as dr

    q, k, v, g, beta, s0 = _delta_inputs(1, 128, 1, 128, 3, aligned=aligned)
    assert float(jnp.mean(jnp.einsum("bshd,bthd->bst", k, k))) > 0.75
    lens = jnp.asarray([128], jnp.int32)
    o_ref, s_ref = dr.kda_recurrence(q, k, v, g, beta, s0, lens)
    flat = lambda a: a.reshape(1, 128, -1)                     # noqa: E731
    o, out = dr.kda_chunk_prefill(
        _delta_pool(s0), 1, jnp.asarray([1]), jnp.asarray([1]),
        jnp.zeros((1,), bool), flat(q), flat(k), flat(v), flat(g), beta,
        lens, n_heads=1, interpret=True)
    assert float(jnp.abs(o.reshape(o_ref.shape) - o_ref).max()) \
        < tol * float(jnp.std(o_ref))
    assert float(jnp.abs(out[1, 1] - s_ref[0]).max()) \
        < tol * float(jnp.std(s_ref))


@pytest.mark.parametrize("lanes,heads,d", [(3, 2, 64), (2, 16, 128)])
def test_kda_step_kernel_equals_one_step(lanes, heads, d):
    from tpu_inference.kernels import delta_rule as dr

    q, k, v, g, beta, s0 = _delta_inputs(lanes, 1, heads, d, 11)
    o_ref, s_ref = dr.kda_recurrence(q, k, v, g, beta, s0,
                                     jnp.ones((lanes,), jnp.int32))
    pool = _delta_pool(s0)
    at = jnp.arange(1, lanes + 1)
    write = at.at[-1].set(0)                 # the last lane is masked
    one = lambda a: a[:, 0].reshape(lanes, -1)                 # noqa: E731
    o, out = dr.kda_step(pool, 1, at, write, one(q), one(k), one(v), one(g),
                         beta[:, 0], n_heads=heads, interpret=True)
    assert float(jnp.abs(o.reshape(lanes, heads, d) - o_ref[:, 0]).max()) \
        < 1e-5
    assert float(jnp.abs(out[1, 1:-1] - s_ref[:-1]).max()) < 1e-5
    assert bool((out[1, -1] == s0[-1]).all())   # ... and kept its state
    assert bool((out[0] == pool[0]).all())


def _tail_reference(pool, layer, slots, slots_w, lens, fresh, qkv, conv_w):
    """The XLA form ``kda_tail_step`` replaces, as the engine ran it
    until PR 52: ``PagedState.tail`` (a gather, zeros for a fresh lane),
    the taps summed in float32 from the oldest on, ``take_along_axis`` at
    ``lens`` for the tail a lane leaves, ``PagedState.put_tail`` (a
    scatter to ``slots_w``)."""
    b, taps, f32 = qkv.shape[0], conv_w.shape[0], jnp.float32
    tail = jnp.where(~fresh[:, None, None],
                     pool[layer, slots].reshape(b, taps - 1, -1), 0)
    seq = jnp.concatenate([tail, qkv[:, None]], axis=1)
    conv = jnp.zeros((b, 1, qkv.shape[1]), f32)
    for j in range(taps):
        conv = conv + conv_w[j].astype(f32) * seq[:, j:j + 1].astype(f32)
    at = lens[:, None] + jnp.arange(taps - 1)[None, :]
    new = jnp.take_along_axis(seq, at[..., None], axis=1)
    return jax.nn.silu(conv)[:, 0], pool.at[layer, slots_w].set(
        new.reshape((b,) + pool.shape[2:])), tail


# name: (lanes, taps, lens of each lane (cycled), fresh lanes, a lane
# staged with lane 0's slot (stale: its lens is 0), the layer traced)
TAIL_CASES = {
    "every_lane_valid": (3, 4, (1,), (), None, False),
    "idle_lanes_among_them": (16, 4, (1, 0, 1, 1, 0), (), None, False),
    "a_fresh_lane": (4, 4, (1, 1, 0, 1), (1, 2), None, False),
    "two_lanes_staged_with_one_slot": (4, 4, (1, 1, 1, 0), (), 3, False),
    "two_taps": (8, 2, (1, 0, 1), (0,), None, False),
    "two_grid_steps_of_four_taps": (16, 4, (1,), (5,), None, False),
    "a_traced_layer_in_a_scan": (3, 4, (1, 0, 1), (2,), None, True),
}


@pytest.mark.parametrize("case", sorted(TAIL_CASES))
def test_kda_tail_step_equals_the_gather_taps_and_scatter(case):
    """The one-token convolution in interpret mode against the XLA form:
    the tails bit-equal in every slot a valid lane owns, untouched in the
    slot of a lane with no valid token (which writes the tail it read to
    slot 0) and in the other layer; x within 1e-6 of its spread."""
    from tpu_inference.kernels import delta_rule as dr

    lanes, taps, lens, fresh_at, stale, traced = TAIL_CASES[case]
    rows, d, layers = 6, 128, 2
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    bf = jnp.bfloat16
    pool = jax.random.normal(
        ks[0], (layers, lanes + 1, taps - 1, rows, d)).astype(bf)
    qkv = jax.random.normal(ks[1], (lanes, rows * d)).astype(bf)
    conv_w = (taps ** -0.5 * jax.random.normal(
        ks[2], (layers, taps, rows * d))).astype(bf)
    lens = jnp.asarray([lens[i % len(lens)] for i in range(lanes)],
                       jnp.int32)
    fresh = jnp.zeros((lanes,), bool).at[jnp.asarray(fresh_at, int)].set(True)
    slots = jnp.arange(1, lanes + 1)
    if stale is not None:
        slots = slots.at[stale].set(slots[0])
    slots_w = jnp.where(lens > 0, slots, 0)
    args = (slots, slots_w, lens, fresh, qkv)

    if traced:
        def body(p, layer):
            x, p = dr.kda_tail_step(p, layer, *args, conv_w[layer],
                                    interpret=True)
            return p, x

        out, xs = jax.jit(lambda p: jax.lax.scan(
            body, p, jnp.arange(layers)))(pool)
        want = pool
        for layer in range(layers):
            x_ref, want, _ = _tail_reference(want, layer, *args,
                                             conv_w[layer])
            assert float(jnp.abs(xs[layer] - x_ref).max()) \
                <= 1e-6 * float(jnp.std(x_ref))
        assert bool((out[:, 1:] == want[:, 1:]).all())
        return
    x, out = dr.kda_tail_step(pool, 1, *args, conv_w[1], interpret=True)
    x_ref, want, read = _tail_reference(pool, 1, *args, conv_w[1])
    assert float(jnp.abs(x - x_ref).max()) <= 1e-6 * float(jnp.std(x_ref))
    assert bool((out[1, 1:] == want[1, 1:]).all())
    assert bool((out[0] == pool[0]).all())
    idle = [i for i in range(lanes) if int(lens[i]) == 0]
    for i in idle:
        if stale is None:
            assert bool((out[1, slots[i]] == pool[1, slots[i]]).all())
    if idle:                       # slot 0 holds what one of them read
        assert any(bool((out[1, 0].reshape(taps - 1, -1) == read[i]).all())
                   for i in idle)
    moved = [i for i in range(lanes) if int(lens[i]) == 1]
    for i in moved:                # [tail[1:], qkv]
        mine = out[1, slots[i]].reshape(taps - 1, -1)
        assert bool((mine[-1] == qkv[i]).all())
        assert bool((mine[:-1] == read[i][1:]).all())


def test_kda_gate_bound_that_would_overflow_is_refused():
    from tpu_inference.kernels import delta_rule as dr

    dr.check_bound(-5.0)
    with pytest.raises(ValueError, match="overflows"):
        dr.check_bound(-6.0)
