"""Sliding-window attention (Mistral-style SWA).

The reference's endpoint served `mistral` — whose signature architecture
feature is a sliding attention window (each token attends to itself and
the window-1 tokens before it). Tests pin: the mask semantics against a
naive numpy oracle, engine serving equality with a windowed full-forward
oracle (prefill + paged decode both windowed), the HF config mapping,
and the window-aware Pallas kernels (decode + prefill) against the
dense reference on both KV tiers."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_inference import config as cfgs
from tpu_inference.engine.engine import InferenceEngine
from tpu_inference.models import build_model, common


def _naive_swa(q, k, v, window):
    """O(S^2) numpy oracle: causal + window mask, per head."""
    b, s, h, d = q.shape
    out = np.zeros_like(q)
    for bi in range(b):
        for hi in range(h):
            for i in range(s):
                lo = max(0, i - window + 1) if window else 0
                ks = k[bi, lo:i + 1, hi]
                sc = (q[bi, i, hi] @ ks.T) / np.sqrt(d)
                p = np.exp(sc - sc.max())
                p /= p.sum()
                out[bi, i, hi] = p @ v[bi, lo:i + 1, hi]
    return out


def test_window_mask_matches_naive_oracle():
    rng = np.random.default_rng(0)
    b, s, h, d = 1, 12, 2, 8
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, h, d)).astype(np.float32)
    v = rng.standard_normal((b, s, h, d)).astype(np.float32)
    for window in (0, 1, 4, 12, 100):
        got = common.dense_causal_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            sliding_window=window)
        want = _naive_swa(q, k, v, window)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"window={window}")


def _swa_cfg(window):
    base = cfgs.tiny_llama(vocab_size=256)
    import dataclasses

    return dataclasses.replace(base, name="tiny-swa",
                               sliding_window=window)


# Shared geometry for the window-8 serving tests; the module-scoped
# dense engine below serves every test that only needs plain windowed
# generate() (tokens are geometry-invariant given the same params).
SWA_KW = dict(page_size=8, num_pages=96, max_pages_per_seq=8,
              max_batch_size=2, prefill_buckets=(16, 32))


@pytest.fixture(scope="module")
def swa8():
    cfg = _swa_cfg(8)
    params, mod = build_model(cfg, seed=0)
    return cfg, params, mod


@pytest.fixture(scope="module")
def swa8_dense_engine(swa8):
    # attn_backend pinned: "auto" would resolve to pallas on a real TPU
    # backend and make the dense-vs-pallas parity test vacuous.
    cfg, params, _ = swa8
    return InferenceEngine(cfg, cfgs.EngineConfig(**SWA_KW,
                                                  attn_backend="dense"),
                           params=params)


def test_engine_matches_windowed_oracle(swa8, swa8_dense_engine):
    """Greedy serving (bucketed prefill + paged decode) == repeated
    windowed full forwards: the window must hold across the
    prefill/decode boundary and as decode slides past it."""
    cfg, params, mod = swa8
    engine = swa8_dense_engine
    rng = np.random.default_rng(3)
    # Prompts shorter and longer than the window; enough new tokens that
    # decode positions slide well past it.
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (5, 20)]
    got = engine.generate(prompts, max_new_tokens=12)

    # reference_greedy honors cfg.sliding_window (shared-compile oracle).
    from tests.test_engine import reference_greedy
    for prompt, gen in zip(prompts, got):
        want = reference_greedy(params, mod, cfg, prompt, 12)
        assert gen == want, f"prompt len {len(prompt)}"


def test_windowed_differs_from_full_attention():
    """Sanity that the window actually changes behavior: same weights,
    window on vs off, long-enough prompt -> different logits."""
    cfg_full = cfgs.tiny_llama(vocab_size=256)
    params, mod = build_model(cfg_full, seed=0)
    toks = jnp.asarray(np.arange(1, 25)[None] % 256)
    pos = jnp.broadcast_to(jnp.arange(24), (1, 24))
    full, _ = mod.forward(params, cfg_full, toks, pos, None,
                          common.make_dense_attn())
    swa, _ = mod.forward(params, cfg_full, toks, pos, None,
                         common.make_dense_attn(sliding_window=4))
    assert not np.allclose(np.asarray(full[0, -1]), np.asarray(swa[0, -1]))


def test_config_from_hf_reads_mistral_sliding_window(tmp_path):
    from tpu_inference.models.weights import config_from_hf

    hf = {"model_type": "mistral", "vocab_size": 32000,
          "hidden_size": 128, "num_hidden_layers": 2,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "intermediate_size": 256, "max_position_embeddings": 4096,
          "sliding_window": 1024}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = config_from_hf(str(tmp_path))
    assert cfg.family == "llama" and cfg.sliding_window == 1024

    hf["sliding_window"] = None          # v0.2+ spelling for "no window"
    (tmp_path / "config.json").write_text(json.dumps(hf))
    assert config_from_hf(str(tmp_path)).sliding_window == 0


# The kernels take the STACKED pool [L, P, page, Hkv, D] plus a layer
# index: pools here hold KERNEL_LAYERS layers, each with its own
# contents and magnitude (so its own scales), and the kernel tests run at
# the first, a middle and the last layer.
KERNEL_LAYERS = 3


def _stacked_pools(rng, n_pages, page, hkv, d, kv_quant):
    """(k_in, v_in, ks, vs) stacked [L, ...] under ``kv_quant`` (the
    kernels take the codes stacked and one layer's scales), and the
    float32 pools [L, P, page, Hkv, D] the dense reference should see
    (dequantized when quantized)."""
    from tpu_inference.engine import kv_cache as kvc

    shape = (KERNEL_LAYERS, n_pages, page, hkv, d)
    grow = np.arange(1, KERNEL_LAYERS + 1, dtype=np.float32).reshape(
        -1, 1, 1, 1, 1)
    k_pool = rng.standard_normal(shape).astype(np.float32) * grow
    v_pool = rng.standard_normal(shape).astype(np.float32) * grow
    if kv_quant == "none":
        return jnp.asarray(k_pool), jnp.asarray(v_pool), None, None, \
            k_pool, v_pool
    if kv_quant == "int4":
        quant, codes = kvc.quantize_kv_int4, kvc.unpack_int4_kv
    else:
        quant, codes = kvc.quantize_kv, lambda x: x
    (kq, ks), (vq, vs) = quant(jnp.asarray(k_pool)), quant(jnp.asarray(v_pool))
    return (kq, vq, ks, vs,
            np.asarray(codes(kq), np.float32) * np.asarray(ks)[..., None],
            np.asarray(codes(vq), np.float32) * np.asarray(vs)[..., None])


# (page, pages a sequence, head_dim, window, kv_lens). "pages": a window
# of a page and a half, contexts <W, >W, >>W. "blocks" (PR 27: the kernel
# walks 16 pages a block, counted from the window's first page): a window
# of 19 pages = a block and a bit, whose first page sits at table
# positions 18, 0, 21 and 1 (none a multiple of 16); contexts that end a
# block's last token past the window's first page, one token on, and
# before the window binds; head_dim 128, so the pages are copied by hand.
WINDOW_GEOMETRY = {
    "pages": (8, 6, 16, 11, [5, 17, 41]),
    "blocks": (16, 40, 128, 300, [600, 299, 640, 316, 544, 545]),
}


@pytest.mark.parametrize("layer", [0, 1, KERNEL_LAYERS - 1])
@pytest.mark.parametrize("kv_quant", ["none", "int8", "int4"])
@pytest.mark.parametrize("geometry", sorted(WINDOW_GEOMETRY))
def test_windowed_paged_decode_kernel_matches_dense(geometry, kv_quant, layer):
    """The Pallas decode kernel's O(window) page walk (blocks of pages
    counted from the window's first page) == the window-masked dense
    reference, for ragged kv_lens crossing page and block boundaries,
    GQA, and the int8 / int4 pools, at each layer of the stacked pool."""
    from tpu_inference.kernels.paged_attention import paged_attention

    rng = np.random.default_rng(11)
    page, mp, d, window, kv_lens = WINDOW_GEOMETRY[geometry]
    # 8 KV heads at head_dim 128: an int8 page's scales fill 128 lanes,
    # which the kernel's own page copies need.
    hq, hkv = (16, 8) if d == 128 else (4, 2)
    kv_lens = np.array(kv_lens, np.int32)
    b = len(kv_lens)
    n_pages = 2 + b * mp
    # Dense reference sees the dequantized pool of this layer.
    k_in, v_in, ks, vs, k_pool, v_pool = _stacked_pools(
        rng, n_pages, page, hkv, d, kv_quant)
    k_pool, v_pool = k_pool[layer], v_pool[layer]
    if ks is not None:
        ks, vs = ks[layer], vs[layer]
    bt = rng.permutation(np.arange(1, 1 + b * mp)).reshape(b, mp).astype(
        np.int32)
    q = rng.standard_normal((b, hq, d)).astype(np.float32)

    got = paged_attention(jnp.asarray(q), k_in, v_in, layer,
                          jnp.asarray(bt), jnp.asarray(kv_lens), ks, vs,
                          sliding_window=window, interpret=True)

    # Dense reference: gather each sequence's pages, window-masked
    # attention with the query at position kv_len-1.
    tol = 2e-5 * (layer + 1)       # layer l's values are l + 1 times larger
    for i in range(b):
        n = int(kv_lens[i])
        flat = np.concatenate([k_pool[bt[i, j]] for j in range(mp)])[:n]
        flatv = np.concatenate([v_pool[bt[i, j]] for j in range(mp)])[:n]
        want = common.dense_causal_attention(
            jnp.asarray(q[i][None, None]),                 # [1, 1, Hq, D]
            jnp.asarray(flat[None]), jnp.asarray(flatv[None]),
            q_offset=n - 1, kv_len=n, sliding_window=window)
        np.testing.assert_allclose(np.asarray(got[i]),
                                   np.asarray(want[0, 0]),
                                   rtol=tol, atol=tol,
                                   err_msg=f"seq {i} kv_len {n}")


def test_swa_pallas_engine_matches_dense_engine(swa8, swa8_dense_engine):
    """Serving on the full windowed Pallas path (flash prefill + paged
    decode) produces exactly the dense backend's tokens."""
    cfg, params, _ = swa8
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (6, 21)]

    want = swa8_dense_engine.generate(prompts, max_new_tokens=14)
    pallas = InferenceEngine(cfg, cfgs.EngineConfig(**SWA_KW,
                                                    attn_backend="pallas"),
                             params=params, pallas_interpret=True)
    got = pallas.generate(prompts, max_new_tokens=14)
    assert got == want


@pytest.mark.parametrize("sp_attn", ["ring", "ulysses"])
def test_swa_sp_engine_matches_unsharded(sp_attn, swa8, swa8_dense_engine):
    """SWA composes with sequence parallelism (VERDICT r4 item 5): a
    sliding-window model served on an sp=2 mesh — prompts long enough to
    span both sequence shards, window smaller than the prompt so the
    mask binds — produces exactly the unsharded engine's tokens, for
    both SP prefill algorithms."""
    from tpu_inference.config import ParallelConfig
    from tpu_inference.parallel.mesh import build_mesh

    cfg, params, _ = swa8
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (21, 13)]

    want = swa8_dense_engine.generate(prompts, max_new_tokens=10)

    # Ulysses needs n_kv_heads (2) divisible by tp*sp, so it runs tp=1;
    # the ring composes with tp=2 head sharding.
    tp = 2 if sp_attn == "ring" else 1
    mesh = build_mesh(ParallelConfig(tp=tp, sp=2))
    eng = InferenceEngine(cfg, cfgs.EngineConfig(**SWA_KW, sp_attn=sp_attn),
                          params=params, mesh=mesh)
    assert eng.sp == 2 and eng.swa_evict
    got = eng.generate(prompts, max_new_tokens=10)
    assert got == want


# (page, pages a sequence, head_dim, window, chunk rows, block_q, q_off).
# "pages": the window is a page and a quarter, a fresh and a continued
# chunk. "blocks" (PR 29: a query block folds 256-token blocks counted
# from the first page its window reaches): head_dim 128, so the pages are
# copied by hand; a window of 300 whose first position falls inside a
# page (201 = 12 * 16 + 9) and whose per-row edges all fall inside the
# first block, beside a sequence the window does not bind yet. "doc": the
# doc cell's geometry: a query block with no visible block before its own
# (offset 0) beside one at offset 3072 under Mistral's 4096 window.
PREFILL_WINDOW_GEOMETRY = {
    "pages": (8, 8, 16, 10, 24, 8, (0, 16)),
    "blocks": (16, 40, 128, 300, 64, 32, (500, 100)),
    "doc": (16, 208, 128, 4096, 256, 128, (0, 3072)),
}


@pytest.mark.parametrize("geometry,kv_quant,layer", [
    ("pages", quant, layer) for quant in ("none", "int8", "int4")
    for layer in (0, 1, KERNEL_LAYERS - 1)] + [
    ("blocks", "none", 1), ("blocks", "int8", 1), ("doc", "none", 1)])
def test_windowed_paged_prefill_kernel_matches_dense(geometry, kv_quant,
                                                     layer):
    """The windowed Pallas prefill (a query block's blocks start at the
    first page its window reaches) == the window-masked dense reference,
    including a chunked-prefill q_offset > 0 and the int8 / int4 pools,
    at each layer of the stacked pool."""
    from tpu_inference.kernels.prefill_attention import (
        paged_prefill_attention)

    rng = np.random.default_rng(13)
    page, mp, d, window, s, block_q, q_off = PREFILL_WINDOW_GEOMETRY[geometry]
    hq, hkv = 4, 2
    q_off = np.array(q_off, np.int32)        # fresh + continued chunk
    b = len(q_off)
    kv_lens = q_off + s
    n_pages = 8 + b * mp
    k_in, v_in, ks, vs, k_pool, v_pool = _stacked_pools(
        rng, n_pages, page, hkv, d, kv_quant)
    k_pool, v_pool = k_pool[layer], v_pool[layer]
    if ks is not None:
        ks, vs = ks[layer], vs[layer]
    bt = rng.permutation(np.arange(1, 1 + b * mp)).reshape(b, mp).astype(
        np.int32)
    q = rng.standard_normal((b, s, hq, d)).astype(np.float32)

    got = paged_prefill_attention(
        jnp.asarray(q), k_in, v_in, layer, jnp.asarray(bt),
        jnp.asarray(kv_lens), jnp.asarray(q_off), ks, vs, block_q=block_q,
        sliding_window=window, interpret=True)

    tol = 2e-5 * (layer + 1)
    for i in range(b):
        n = int(kv_lens[i])
        flat = np.concatenate([k_pool[bt[i, j]] for j in range(mp)])[:n]
        flatv = np.concatenate([v_pool[bt[i, j]] for j in range(mp)])[:n]
        want = common.dense_causal_attention(
            jnp.asarray(q[i][None]), jnp.asarray(flat[None]),
            jnp.asarray(flatv[None]), q_offset=int(q_off[i]), kv_len=n,
            sliding_window=window)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[0]),
                                   rtol=tol, atol=tol,
                                   err_msg=f"seq {i} q_off {q_off[i]}")


def test_swa_disables_prefix_cache():
    """SWA + prefix caching don't compose (evicted holes in cached
    prefixes); the engine makes the vLLM-style exclusion and turns on
    behind-window eviction instead."""
    eng = InferenceEngine(_swa_cfg(8), cfgs.EngineConfig(
        page_size=8, num_pages=32, max_pages_per_seq=4, max_batch_size=2,
        prefill_buckets=(16,), enable_prefix_cache=True), seed=0)
    assert eng.prefix_cache is None
    assert eng.swa_evict


def test_swa_exclusions_gated_on_window_binding():
    """When max_context <= window the mask can never bind (behavior is
    identical to full attention), so the SWA exclusions don't apply: the
    prefix cache stays on and eviction stays off (ADVICE r4)."""
    # window 64 vs max_context 4 pages x 8 = 32: never binds.
    eng = InferenceEngine(_swa_cfg(64), cfgs.EngineConfig(
        page_size=8, num_pages=32, max_pages_per_seq=4, max_batch_size=2,
        prefill_buckets=(16,), enable_prefix_cache=True), seed=0)
    assert eng.prefix_cache is not None
    assert not eng.swa_evict


def test_swa_eviction_bounds_live_pages_and_preserves_tokens():
    """A sequence decoding far past its window holds O(window) live KV
    pages (behind-window pages return to the pool mid-flight), and the
    tokens still match the windowed full-forward oracle."""
    from tpu_inference.engine.engine import Sequence

    window, page = 8, 8
    cfg = _swa_cfg(window)
    ecfg = cfgs.EngineConfig(page_size=page, num_pages=64,
                             max_pages_per_seq=16, max_batch_size=2,
                             prefill_buckets=(16, 32))
    params, mod = build_model(cfg, seed=0)
    engine = InferenceEngine(cfg, ecfg, params=params)
    assert engine.swa_evict

    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 256, size=20).tolist()
    seq = Sequence(request_id=0, prompt_tokens=prompt, max_new_tokens=40)
    free_at_prefill = engine.allocator.num_free
    engine.prefill(seq)
    max_live = 0
    while engine.active_sequences():
        engine.decode_step()
        live = sum(1 for p in seq.pages if p)
        max_live = max(max_live, live)
    # Window spans at most ceil(W/page)+1 pages; +1 more for the page
    # being written at the head.
    assert max_live <= -(-window // page) + 2, max_live
    # Behind-window pages really went back to the pool mid-flight: at
    # the end the sequence holds far fewer than its ctx would need.
    assert sum(1 for p in seq.pages if p) < (seq.ctx_len // page)

    got = list(seq.generated)
    engine.release(seq)
    assert engine.allocator.num_free == free_at_prefill

    # Token equality with the windowed no-cache oracle (shared-compile).
    from tests.test_engine import reference_greedy
    assert got == reference_greedy(params, mod, cfg, prompt, 40)


def test_mistral_preset_registered():
    """'mistral' is what the reference's endpoint served; the preset
    carries its sliding window into the windowed serving path."""
    cfg = cfgs.PRESETS["mistral-7b"]()
    assert cfg.sliding_window == 4096 and cfg.family == "llama"
    from tpu_inference.engine.autosize import auto_size

    # And it sizes onto one 16 GB chip with int8 (the reference's
    # Ollama served it quantized too).
    sz = auto_size(cfg, hbm_bytes=16e9, quant="int8", kv_quant="int8")
    assert sz.max_batch_size >= 8


def test_speculation_serves_swa_target_with_eviction_on(
        swa8, swa8_dense_engine, monkeypatch):
    """Speculation over an SWA target keeps behind-window eviction ON
    (the verify queries sit at positions >= ctx, so no windowed reader
    reaches a released page): pages go back to the pool mid-flight and
    the tokens equal the plain SWA engine's. The proposer is handed the
    plain continuation, so whole proposals are accepted and the context
    advances several positions a round."""
    from tpu_inference.engine import engine as engine_mod

    cfg, params, _ = swa8
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (6, 18)]
    want = swa8_dense_engine.generate(prompts, max_new_tokens=40)

    def oracle(hist, gamma, max_n, min_n=1):
        for p, w in zip(prompts, want):
            if list(hist[:len(p)]) == p:
                done = len(hist) - len(p)
                return np.asarray(w[done:done + gamma], np.int32)

    monkeypatch.setattr(engine_mod, "ngram_propose", oracle)
    spec = InferenceEngine(
        cfg, cfgs.EngineConfig(**SWA_KW, num_speculative_tokens=3),
        params=params)
    assert spec.swa_evict
    assert spec.generate(prompts, max_new_tokens=40) == want
    assert spec.spec_accepted > 40
    assert spec.window_pages_released > 0


def test_swa_admission_reserves_window_not_generation():
    """Admission must charge an SWA-evict sequence its true peak (full
    prompt at prefill, O(window) during decode) — not prompt+max_new.
    A long-generation Mistral-style request fits a small pool."""
    from tpu_inference.engine.engine import Sequence

    cfg = _swa_cfg(8)
    ecfg = cfgs.EngineConfig(page_size=8, num_pages=16, max_pages_per_seq=8,
                             max_batch_size=1, prefill_buckets=(16,),
                             max_new_tokens=512)
    eng = InferenceEngine(cfg, ecfg, seed=0)
    seq = Sequence(request_id=0, prompt_tokens=list(range(1, 11)),
                   max_new_tokens=500)     # 510 tokens = 64 pages naively
    assert eng._pages_reserved(seq) <= 5   # window span + margins
    assert eng.can_ever_admit(seq)
    # And it actually serves to completion inside the 15-page pool.
    eng.prefill(seq)
    while eng.active_sequences():
        eng.decode_steps()
    assert seq.finish_reason in ("stop", "length"), seq.finish_reason
    assert len(seq.generated) > 50         # decoded far past the pool's
    eng.release(seq)                       # naive capacity
