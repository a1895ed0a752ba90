"""int8 KV-cache quantization (engine/kv_cache.py quantize_kv + kernels).

The pool stores int8 codes with per-(token, kv-head) scales; dequant is
in-kernel for the Pallas decode/prefill kernels and at-gather for the
dense path. The reference has no KV cache at all (client-only, SURVEY.md
§0); this is the memory-bandwidth tier of the server its external
endpoint provided. Tests pin: quantization error bounds, write/gather
roundtrip through the paged pool, cross-backend token equality (dense
gather vs Pallas in-kernel dequant read the same codes, so greedy tokens
must match exactly), TP-sharded equality, and spec-decode compatibility.
"""

import dataclasses

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from tpu_inference.config import (
    EngineConfig,
    ParallelConfig,
    tiny_llama,
    tiny_mixtral,
)
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine.engine import InferenceEngine

BASE = dict(page_size=16, num_pages=64, max_batch_size=2,
            prefill_buckets=(64,),
            max_new_tokens=16)
PROMPTS = [list(range(1, 20)), list(range(5, 40))]


def test_quantize_kv_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16)) * 2.0
    q, scale = kvc.quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.shape == (2, 5, 3)
    err = jnp.abs(q.astype(jnp.float32) * scale[..., None] - x)
    assert bool((err <= scale[..., None] / 2 + 1e-6).all())


def test_write_gather_roundtrip_quantized():
    cfg = tiny_llama()
    ecfg = EngineConfig(**BASE, kv_quant="int8")
    kv = kvc.alloc_kv_pages(cfg, ecfg)
    assert kv.quantized and kv.k.dtype == jnp.int8
    k_new = jax.random.normal(jax.random.PRNGKey(1),
                              (1, 4, cfg.n_kv_heads, cfg.head_dim))
    v_new = jax.random.normal(jax.random.PRNGKey(2), k_new.shape)
    bt = jnp.zeros((1, ecfg.max_pages_per_seq), jnp.int32).at[0, 0].set(3)
    positions = jnp.arange(4)[None]
    valid = jnp.ones((1, 4), bool)
    slots = kvc.slot_mapping(bt, positions, valid, ecfg.page_size)
    kv = kvc.write_kv(kv, 0, k_new, v_new, slots)
    k_got, v_got = kvc.gather_kv(kv, 0, bt)
    # Dequantized readback within the per-row quantization envelope.
    _, ks = kvc.quantize_kv(k_new)
    np.testing.assert_allclose(np.asarray(k_got[0, :4]),
                               np.asarray(k_new[0], np.float32),
                               atol=float(ks.max()) / 2 + 1e-6)
    _, vs = kvc.quantize_kv(v_new)
    np.testing.assert_allclose(np.asarray(v_got[0, :4]),
                               np.asarray(v_new[0], np.float32),
                               atol=float(vs.max()) / 2 + 1e-6)


def test_unquantized_pool_unchanged():
    cfg = tiny_llama()
    kv = kvc.alloc_kv_pages(cfg, EngineConfig(**BASE))
    assert not kv.quantized and kv.k_scale is None


def test_dense_and_pallas_token_equal_kv_int8():
    """Both backends read the SAME int8 codes; greedy tokens must agree
    exactly (in-kernel dequant == gather dequant)."""
    cfg = tiny_llama()
    dense = InferenceEngine(cfg, EngineConfig(**BASE, kv_quant="int8"),
                            seed=0).generate(PROMPTS, max_new_tokens=10)
    pallas = InferenceEngine(
        cfg, EngineConfig(**BASE, kv_quant="int8", attn_backend="pallas"),
        seed=0, pallas_interpret=True).generate(PROMPTS, max_new_tokens=10)
    assert dense == pallas


def test_kv_int8_close_to_full_precision():
    cfg = tiny_llama()
    fp = InferenceEngine(cfg, EngineConfig(**BASE),
                         seed=0).generate(PROMPTS, max_new_tokens=10)
    kv8 = InferenceEngine(cfg, EngineConfig(**BASE, kv_quant="int8"),
                          seed=0).generate(PROMPTS, max_new_tokens=10)
    # Greedy drift is bounded: the first tokens (short context) agree.
    assert fp[0][:4] == kv8[0][:4]


def test_tp_sharded_kv_int8_matches_unsharded():
    from tpu_inference.parallel.mesh import build_mesh
    cfg = tiny_llama()
    ecfg = EngineConfig(**BASE, kv_quant="int8", attn_backend="pallas")
    base = InferenceEngine(cfg, ecfg, seed=0, pallas_interpret=True
                           ).generate(PROMPTS, max_new_tokens=10)
    mesh = build_mesh(ParallelConfig(tp=2))
    tp_eng = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh,
                             pallas_interpret=True)
    assert tp_eng.kv.k_scale.sharding.spec == \
        jax.sharding.PartitionSpec(None, None, None, "tp")
    assert base == tp_eng.generate(PROMPTS, max_new_tokens=10)


def test_mixtral_kv_int8():
    cfg = tiny_mixtral()
    out = InferenceEngine(cfg, EngineConfig(**BASE, kv_quant="int8"),
                          seed=0).generate([PROMPTS[0]], max_new_tokens=8)
    assert len(out[0]) == 8


def test_verify_round_over_kv_int8():
    """The verify round writes and reads the int8 pool like the decode
    programs do: greedy tokens equal the plain int8 engine's."""
    cfg = tiny_llama()
    echo = [[3, 4, 5, 6] * 5]
    want = InferenceEngine(cfg, EngineConfig(**BASE, kv_quant="int8"),
                           seed=0).generate(echo, max_new_tokens=16)
    eng = InferenceEngine(
        cfg, EngineConfig(**BASE, kv_quant="int8", num_speculative_tokens=3),
        seed=0)
    assert eng.kv.quantized
    assert eng.generate(echo, max_new_tokens=16) == want
    assert eng.spec_rounds_total > 0


@pytest.mark.slow   # int8 x kv-int8 x pallas combination sweep
def test_both_quant_tiers_together():
    """Weights int8 + KV int8 — the full memory-bandwidth configuration."""
    cfg = tiny_llama()
    ecfg = EngineConfig(**BASE, quant="int8", kv_quant="int8",
                        attn_backend="pallas")
    out = InferenceEngine(cfg, ecfg, seed=0, pallas_interpret=True
                          ).generate(PROMPTS, max_new_tokens=8)
    assert all(len(t) == 8 for t in out)
    assert all(0 <= tok < cfg.vocab_size for t in out for tok in t)


def test_quantize_kv_int4_roundtrip_and_bounds():
    """Nibble pack/unpack is lossless on the codes; dequant error stays
    inside the per-row quantization envelope (scale/2 per element)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 3, 16)) * 2.0
    packed, scale = kvc.quantize_kv_int4(x)
    assert packed.dtype == jnp.uint8 and packed.shape == (2, 5, 3, 8)
    codes = kvc.unpack_int4_kv(packed)
    assert codes.shape == x.shape
    assert int(jnp.max(jnp.abs(codes))) <= 7
    err = jnp.abs(codes.astype(jnp.float32) * scale[..., None] - x)
    assert bool((err <= scale[..., None] / 2 + 1e-6).all())


def test_kv_int4_pool_alloc():
    cfg = tiny_llama()
    kv = kvc.alloc_kv_pages(cfg, EngineConfig(**BASE, kv_quant="int4"))
    assert kv.quantized and kv.packed_int4
    assert kv.k.dtype == jnp.uint8
    assert kv.k.shape[-1] == cfg.head_dim // 2
    assert kv.k_scale.shape[-1] == cfg.n_kv_heads
    odd = dataclasses.replace(cfg, d_model=120, n_heads=4, n_kv_heads=2,
                              head_dim_override=15)
    with pytest.raises(ValueError, match="even head_dim"):
        kvc.alloc_kv_pages(odd, EngineConfig(**BASE, kv_quant="int4"))


def test_dense_and_pallas_token_equal_kv_int4():
    """Both backends read the SAME packed nibbles; greedy tokens must
    agree exactly (in-kernel unpack+dequant == gather unpack+dequant)."""
    cfg = tiny_llama()
    dense = InferenceEngine(cfg, EngineConfig(**BASE, kv_quant="int4"),
                            seed=0).generate(PROMPTS, max_new_tokens=10)
    pallas = InferenceEngine(
        cfg, EngineConfig(**BASE, kv_quant="int4", attn_backend="pallas"),
        seed=0, pallas_interpret=True).generate(PROMPTS, max_new_tokens=10)
    assert dense == pallas


def test_kv_int4_dequant_error_bounded_at_pool_scale():
    """Full write->gather through the paged pool at realistic shapes:
    int4 dequant error stays in its expected band (~10% relative for
    7-level symmetric on standard-normal data) and strictly below a
    hard ceiling. Token-level closeness vs full precision is NOT
    asserted: on a random-init tiny model greedy argmax margins are
    smaller than honest int4 noise (int8 is the accuracy-safe tier;
    the cross-backend exact-equality test pins implementation
    correctness instead)."""
    cfg = tiny_llama()
    ecfg = EngineConfig(**BASE, kv_quant="int4")
    kv = kvc.alloc_kv_pages(cfg, ecfg)
    k_new = jax.random.normal(jax.random.PRNGKey(1),
                              (1, 16, cfg.n_kv_heads, cfg.head_dim))
    v_new = jax.random.normal(jax.random.PRNGKey(2), k_new.shape)
    bt = jnp.zeros((1, ecfg.max_pages_per_seq), jnp.int32).at[0, 0].set(3)
    slots = kvc.slot_mapping(bt, jnp.arange(16)[None],
                             jnp.ones((1, 16), bool), ecfg.page_size)
    kv = kvc.write_kv(kv, 0, k_new, v_new, slots)
    k_got, v_got = kvc.gather_kv(kv, 0, bt)
    for got, ref in ((k_got, k_new), (v_got, v_new)):
        rel = float(jnp.linalg.norm(got[0, :16] - ref[0])
                    / jnp.linalg.norm(ref[0]))
        assert rel < 0.15, rel


def test_tp_sharded_kv_int4_matches_unsharded():
    """The packed pool (trailing dim D/2) shards on the kv-head dim like
    every other pool; TP generation is token-equal to unsharded."""
    from tpu_inference.parallel.mesh import build_mesh
    cfg = tiny_llama()
    ecfg = EngineConfig(**BASE, kv_quant="int4", attn_backend="pallas")
    base = InferenceEngine(cfg, ecfg, seed=0, pallas_interpret=True
                           ).generate(PROMPTS, max_new_tokens=10)
    mesh = build_mesh(ParallelConfig(tp=2))
    tp_eng = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh,
                             pallas_interpret=True)
    assert tp_eng.kv.k.dtype == jnp.uint8
    assert base == tp_eng.generate(PROMPTS, max_new_tokens=10)


def test_unknown_kv_quant_mode_rejected():
    import pytest
    cfg = tiny_llama()
    with pytest.raises(ValueError, match="unknown kv_quant"):
        InferenceEngine(cfg, EngineConfig(**BASE, kv_quant="fp8"), seed=0)


def test_prefix_cache_reuses_quantized_pages():
    """Cached pages hold int8 codes + scales; a second request sharing
    the prefix must reuse them and produce the same tokens as a cold
    run (cache hits are output-invisible, quantized or not)."""
    cfg = tiny_llama()
    ecfg = EngineConfig(**BASE, kv_quant="int8")
    eng = InferenceEngine(cfg, ecfg, seed=0)
    cold = eng.generate([PROMPTS[1]], max_new_tokens=8)
    hits_before = eng.prefix_cache.hits_hbm.value
    warm = eng.generate([PROMPTS[1]], max_new_tokens=8)
    assert eng.prefix_cache.hits_hbm.value > hits_before
    assert cold == warm


@pytest.mark.slow   # sp x kv-int8 combination; each covered separately
def test_sp_ring_prefill_with_kv_int8():
    """sp>1 ring-attention prefill writes the chunk's KV into the
    quantized pool; decode then reads int8 codes — token-equal to the
    unsharded int8-KV engine."""
    from tpu_inference.parallel.mesh import build_mesh
    cfg = tiny_llama()
    ecfg = EngineConfig(**BASE, kv_quant="int8")
    prompt = [list(range(1, 33))]                 # 32 % sp == 0
    base = InferenceEngine(cfg, ecfg, seed=0).generate(prompt,
                                                       max_new_tokens=8)
    mesh = build_mesh(ParallelConfig(tp=2, sp=2))
    eng = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh)
    assert eng.sp == 2
    assert base == eng.generate(prompt, max_new_tokens=8)
