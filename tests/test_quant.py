"""Weight-only int8 quantization (models/quant.py).

The reference has no quantization tier (no model code at all, SURVEY.md
§0); its external Ollama endpoint served quantized GGUF models — this is
the TPU-native equivalent (int8 weights + per-channel scales, XLA fusing
the dequant into the matmul). Tests pin: quantization error bounds, the
qdot/qeinsum contraction helpers, end-to-end engine serving parity, and
TP-sharded quantized params matching the unsharded quantized tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.config import (
    EngineConfig,
    ParallelConfig,
    tiny_gpt2,
    tiny_llama,
    tiny_mixtral,
)
from tpu_inference.engine.engine import InferenceEngine
from tpu_inference.models.quant import (
    QUANT_KEYS,
    QuantizedArray,
    dequantize,
    qdot,
    qeinsum,
    quantize_array,
    quantize_params,
)


def test_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.05
    qa = quantize_array(w)
    assert qa.q.dtype == jnp.int8
    assert qa.scale.shape == (1, 32)
    # Symmetric rounding: |w - dq(q(w))| <= scale/2 per output channel.
    err = jnp.abs(dequantize(qa) - w)
    assert bool((err <= qa.scale / 2 + 1e-7).all())


def test_qdot_matches_dequantized_product():
    # The contraction invariant: qdot(x, qa) == x @ dequantize(qa) — the
    # scale factors out of the contraction exactly (it scales the output
    # channel, which is never summed over).
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(32, 16)) * 0.05, jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    qa = quantize_array(w)
    np.testing.assert_allclose(np.asarray(qdot(x, qa)),
                               np.asarray(x @ dequantize(qa)),
                               rtol=1e-5, atol=1e-6)
    # Plain-array passthrough.
    np.testing.assert_allclose(qdot(x, w), x @ w, rtol=1e-6)


def test_qeinsum_expert_contractions():
    rng = np.random.default_rng(1)
    e, c, d, f = 2, 3, 8, 16
    a = jnp.asarray(rng.normal(size=(e, c, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, d, f)) * 0.02, jnp.float32)
    qa = quantize_array(w)
    got = qeinsum("ecd,edf->ecf", a, qa)
    want = jnp.einsum("ecd,edf->ecf", a, dequantize(qa))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def test_quantize_params_selects_matmul_weights_only():
    from tpu_inference.models.registry import build_model
    cfg = tiny_llama()
    params, _ = build_model(cfg, seed=0)
    qp = quantize_params(params)
    assert isinstance(qp["blocks"]["wq"], QuantizedArray)
    assert isinstance(qp["blocks"]["w_down"], QuantizedArray)
    # Norms, embeddings stay full precision.
    assert not isinstance(qp["blocks"]["attn_norm"], QuantizedArray)
    assert not isinstance(qp["embed"], QuantizedArray)
    # Stacked-layer leaves keep the leading L axis on q and scale.
    assert qp["blocks"]["wq"].q.shape[0] == cfg.n_layers
    assert qp["blocks"]["wq"].scale.shape == (cfg.n_layers, 1,
                                              qp["blocks"]["wq"].q.shape[-1])


def test_quantized_forward_close_to_full_precision():
    from tpu_inference.models.common import make_dense_attn
    from tpu_inference.models.registry import build_model, get_model_fns
    cfg = tiny_llama()
    params, _ = build_model(cfg, seed=0)
    mod = get_model_fns(cfg)
    toks = jnp.arange(1, 17, dtype=jnp.int32)[None]
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    full, _ = mod.forward(params, cfg, toks, pos, None, make_dense_attn())
    quant, _ = mod.forward(quantize_params(params), cfg, toks, pos, None,
                           make_dense_attn())
    # Per-channel int8 keeps logits within a tight relative envelope.
    denom = jnp.abs(full).max()
    assert float(jnp.abs(quant - full).max() / denom) < 0.05


@pytest.mark.parametrize("cfg_fn", [tiny_llama, tiny_mixtral, tiny_gpt2])
def test_engine_serves_int8(cfg_fn):
    cfg = cfg_fn()
    ecfg = EngineConfig(num_pages=64, max_batch_size=2,
                        prefill_buckets=(64,), max_new_tokens=16,
                        quant="int8")
    engine = InferenceEngine(cfg, ecfg, seed=0)
    out = engine.generate([list(range(1, 20)), list(range(5, 40))],
                          max_new_tokens=8)
    assert all(len(t) == 8 for t in out)
    assert all(0 <= tok < cfg.vocab_size for t in out for tok in t)


def test_tp_sharded_int8_matches_unsharded():
    from tpu_inference.parallel.mesh import build_mesh
    cfg = tiny_llama()
    ecfg = EngineConfig(num_pages=64, max_batch_size=2,
                        prefill_buckets=(64,), max_new_tokens=16,
                        quant="int8")
    prompts = [list(range(1, 20)), list(range(5, 40))]
    base = InferenceEngine(cfg, ecfg, seed=0).generate(prompts,
                                                       max_new_tokens=10)
    mesh = build_mesh(ParallelConfig(tp=2))
    tp = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh).generate(
        prompts, max_new_tokens=10)
    assert base == tp


@pytest.mark.slow   # EP x int8 combination sweep; EP and int8 each covered separately
def test_tp_sharded_int8_mixtral_ep():
    from tpu_inference.parallel.mesh import build_mesh
    cfg = tiny_mixtral()
    ecfg = EngineConfig(num_pages=64, max_batch_size=2,
                        prefill_buckets=(64,), max_new_tokens=16,
                        quant="int8")
    prompts = [list(range(1, 16))]
    base = InferenceEngine(cfg, ecfg, seed=0).generate(prompts,
                                                       max_new_tokens=8)
    mesh = build_mesh(ParallelConfig(tp=2))
    tp = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh).generate(
        prompts, max_new_tokens=8)
    assert base == tp


def test_scale_sharding_unshards_reduced_dim():
    """wo shards its input (contraction) dim on tp; the scale's size-1
    contraction dim must come out unsharded or device_put would fail."""
    from jax.sharding import PartitionSpec as P

    from tpu_inference.models.registry import build_model
    from tpu_inference.parallel import shardings as shd
    from tpu_inference.parallel.mesh import build_mesh
    cfg = tiny_llama()
    params, _ = build_model(cfg, seed=0)
    qp = quantize_params(params)
    mesh = build_mesh(ParallelConfig(tp=2))
    sh = shd.param_shardings(cfg, mesh, qp)
    wo = sh["blocks"]["wo"]
    assert wo.q.spec == P(None, "tp", None)
    assert wo.scale.spec == P(None, None, None)
    placed = shd.shard_params(qp, cfg, mesh)
    assert placed["blocks"]["wo"].q.sharding.spec == P(None, "tp", None)


def test_check_numerics_passes_on_quantized_params():
    cfg = tiny_llama()
    ecfg = EngineConfig(num_pages=64, max_batch_size=2,
                        prefill_buckets=(64,), quant="int8")
    InferenceEngine(cfg, ecfg, seed=0).check_numerics()


def test_unknown_quant_mode_rejected():
    with pytest.raises(ValueError, match="unknown quant mode"):
        quantize_params({}, "fp4")


def test_quant_keys_cover_all_families():
    # Every family's big matmul weights are in QUANT_KEYS (drift guard).
    from tpu_inference.models.registry import build_model
    for cfg_fn in (tiny_llama, tiny_mixtral, tiny_gpt2):
        cfg = cfg_fn()
        params, _ = build_model(cfg, seed=0)
        qp = quantize_params(params)
        n_quant = sum(isinstance(x, QuantizedArray)
                      for x in jax.tree.leaves(
                          qp, is_leaf=lambda x: isinstance(x, QuantizedArray))
                      if isinstance(x, QuantizedArray))
        assert n_quant >= 4, f"{cfg.name}: only {n_quant} quantized leaves"


def test_init_quantized_params_structure_and_determinism():
    """Leaf-by-leaf quantized init (the 8B-on-16GB path) produces the
    same tree structure as init-then-quantize — QuantizedArray at every
    QUANT_KEYS leaf, same shapes/dtypes — and is deterministic per
    seed."""
    import jax

    from tpu_inference.models.quant import (QuantizedArray,
                                            init_quantized_params,
                                            quantize_params)
    from tpu_inference.models.registry import build_model

    cfg = tiny_llama()
    a = init_quantized_params(cfg, seed=0)
    b = init_quantized_params(cfg, seed=0)
    ref = quantize_params(build_model(cfg, seed=0)[0])

    ra = jax.tree_util.tree_structure(a)
    assert ra == jax.tree_util.tree_structure(ref)
    for la, lb, lr in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                          jax.tree.leaves(ref)):
        assert la.shape == lr.shape and la.dtype == lr.dtype
        assert (la == lb).all()      # deterministic per seed
    # The quantized leaves really are quantized (int8 codes).
    flat = jax.tree_util.tree_flatten_with_path(a)[0]
    n_q = sum(1 for p, _ in flat if any(
        getattr(k, "name", "") == "q" for k in p))
    assert n_q >= 8  # wq wk wv wo gate up down lm_head


def test_engine_random_init_quant_decodes():
    """An engine that initializes its own int8 params (params=None)
    serves tokens — the BENCH_MODEL=8b lane's construction path."""
    cfg = tiny_llama()
    ecfg = EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=8,
                        max_batch_size=2, prefill_buckets=(16,),
                        quant="int8")
    eng = InferenceEngine(cfg, ecfg, seed=0)
    out = eng.generate([[1, 2, 3, 4]], max_new_tokens=6, temperature=0.0)
    assert len(out[0]) == 6


# ---------------------------------------------------------------------
# int4 (group-quantized) tier — quarter weight traffic vs bf16; the
# reference's Ollama endpoint served a 4-bit Mistral by default, so this
# is the tier its numbers actually came from.
# ---------------------------------------------------------------------

def test_int4_pack_unpack_roundtrip():
    """Nibble packing is lossless over the full code range, including
    sign extension of negative nibbles from both byte halves."""
    from tpu_inference.models.quant import pack_int4, unpack_int4

    codes = jnp.tile(jnp.arange(-7, 8, dtype=jnp.int8), 30)[:448]
    codes = codes.reshape(56, 8)              # even contraction dim
    packed = pack_int4(codes)
    assert packed.dtype == jnp.int8 and packed.shape == (28, 8)
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(codes))


def test_int4_roundtrip_grouped():
    from tpu_inference.models.quant import GROUP_SIZE

    w = jax.random.normal(jax.random.PRNGKey(2),
                          (2 * GROUP_SIZE, 32)) * 0.05
    qa = quantize_array(w, "int4")
    # Codes are nibble-packed two-per-byte: a byte array is the portable
    # representation, so no sub-byte dtype persists across jit boundaries.
    assert qa.q.dtype == jnp.int8
    assert qa.q.shape == (GROUP_SIZE, 32)     # half the contraction dim
    assert qa.scale.shape == (2, 32)          # one scale per (group, col)
    # Per-group symmetric rounding error bound.
    err = jnp.abs(dequantize(qa) - w).reshape(2, GROUP_SIZE, 32)
    bound = qa.scale[:, None, :] / 2 + 1e-7
    assert bool((err <= bound).all())
    # Indivisible contraction dims degrade to one whole-column group.
    qa1 = quantize_array(jax.random.normal(jax.random.PRNGKey(3),
                                           (96, 8)), "int4")
    assert qa1.scale.shape == (1, 8)


def test_int4_qdot_and_qeinsum_match_dequantized():
    # Grouped contraction invariant: folding per-group partials with
    # their scales == contracting against the dequantized weight.
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=(256, 16)) * 0.05, jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    qa = quantize_array(w, "int4")
    assert qa.scale.shape[-2] == 2            # really grouped
    np.testing.assert_allclose(np.asarray(qdot(x, qa)),
                               np.asarray(x @ dequantize(qa)),
                               rtol=1e-4, atol=1e-5)
    we = jnp.asarray(rng.normal(size=(2, 256, 8)) * 0.02, jnp.float32)
    a = jnp.asarray(rng.normal(size=(2, 3, 256)), jnp.float32)
    qe = quantize_array(we, "int4")
    assert qe.scale.shape == (2, 2, 8)
    got = qeinsum("ecd,edf->ecf", a, qe)
    want = jnp.einsum("ecd,edf->ecf", a, dequantize(qe))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_int4_grouped_bf16_activations():
    """bf16 activations through the grouped paths (the real-checkpoint
    serving dtype). XLA:CPU can't execute batched bf16 dots, so the
    grouped contraction upcasts off-TPU (_contract_dtype) — this is the
    regression test for the int4 CPU-smoke failure."""
    rng = np.random.default_rng(6)
    w = jnp.asarray(rng.normal(size=(256, 16)) * 0.05, jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(4, 256)), jnp.bfloat16)
    qa = quantize_array(w, "int4")
    assert qa.scale.shape[-2] == 2
    got = jax.jit(qdot)(x, qa)               # must compile AND execute
    want = x.astype(jnp.float32) @ dequantize(qa)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2, atol=5e-2)
    we = jnp.asarray(rng.normal(size=(2, 256, 8)) * 0.02, jnp.bfloat16)
    a = jnp.asarray(rng.normal(size=(2, 3, 256)), jnp.bfloat16)
    qe = quantize_array(we, "int4")
    got = jax.jit(lambda a_, w_: qeinsum("ecd,edf->ecf", a_, w_))(a, qe)
    want = jnp.einsum("ecd,edf->ecf", a.astype(jnp.float32),
                      dequantize(qe))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("cfg_fn", [tiny_llama, tiny_mixtral])
def test_engine_serves_int4(cfg_fn):
    """End-to-end serving with int4 weights (w_down's 256-dim contraction
    exercises the truly-grouped path inside the engine graphs)."""
    cfg = cfg_fn()
    ecfg = EngineConfig(num_pages=64, max_batch_size=2,
                        prefill_buckets=(64,), max_new_tokens=16,
                        quant="int4")
    engine = InferenceEngine(cfg, ecfg, seed=0)
    out = engine.generate([list(range(1, 20)), list(range(5, 40))],
                          max_new_tokens=8)
    assert all(len(t) == 8 for t in out)
    assert all(0 <= tok < cfg.vocab_size for t in out for tok in t)


def test_tp_sharded_int4_matches_unsharded():
    """TP token equality for int4 — w_down shards its 256-dim contraction
    over tp, so the grouped scale must shard its group axis alongside
    (shardings._scale_spec)."""
    from tpu_inference.parallel.mesh import build_mesh
    cfg = tiny_llama()
    ecfg = EngineConfig(num_pages=64, max_batch_size=2,
                        prefill_buckets=(64,), max_new_tokens=16,
                        quant="int4")
    prompts = [list(range(1, 20)), list(range(5, 40))]
    base = InferenceEngine(cfg, ecfg, seed=0).generate(prompts,
                                                       max_new_tokens=10)
    mesh = build_mesh(ParallelConfig(tp=2))
    tp = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh).generate(
        prompts, max_new_tokens=10)
    assert base == tp


def test_int4_scale_sharding_follows_contraction_dim():
    """Grouped scales keep the weight's contraction-dim sharding (each
    chip holds the scales for its own weight shard); int8's size-1 scale
    dim stays replicated."""
    from jax.sharding import PartitionSpec as P

    from tpu_inference.models.registry import build_model
    from tpu_inference.parallel import shardings as shd
    from tpu_inference.parallel.mesh import build_mesh
    cfg = tiny_llama()
    params, _ = build_model(cfg, seed=0)
    qp = quantize_params(params, "int4")
    mesh = build_mesh(ParallelConfig(tp=2))
    sh = shd.param_shardings(cfg, mesh, qp)
    # w_down [L, d_ff=256, d_model] shards the contraction dim -> its
    # G=2 scale groups shard with it.
    wd = sh["blocks"]["w_down"]
    assert qp["blocks"]["w_down"].scale.shape[-2] == 2
    assert wd.q.spec == wd.scale.spec
    placed = shd.shard_params(qp, cfg, mesh)
    assert placed["blocks"]["w_down"].scale.sharding.spec == wd.scale.spec
