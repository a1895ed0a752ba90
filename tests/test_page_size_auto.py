"""The tokens of a KV page, where nobody gave a number.

``EngineConfig.page_size = None`` ("--page-size auto") is resolved once,
by ``autosize.resolve_page_size``, from two things the program observes:
the bytes a 16-token page of one pool holds on one chip, and whether the
Pallas kernels read the pool. Held here: the rule as a table over the
served presets, that capacity keeps its meaning (page counts written in
16-token units come out as the same tokens), that the resolved config is
a fixed point and survives the JSON transport, and that a 64-token page
holds the same rows a 16-token page held: three tiny engines emit the
same tokens and take the same prefix from the cache at both sizes,
through the Pallas kernels in interpret mode.
"""

import json
import types

import numpy as np
import pytest

from tpu_inference.config import (KV_PAGE_UNIT, PRESETS, EngineConfig,
                                  FrameworkConfig, framework_config_from_dict,
                                  framework_config_to_dict)
from tpu_inference.engine import autosize
from tpu_inference.engine.engine import InferenceEngine
from tpu_inference.engine.scheduler import Sequence

# preset: tokens a page (tp 1, tp 2) where the Pallas kernels read the
# pool. 16-token page of one pool: Qwen2 / SmallThinker 4 heads = 16 KB,
# Kimi / Xing latents = 20 KB (never sharded), Mistral / Laguna 8 heads
# = 32 KB (16 KB a chip under tp 2), Phi-4-mini-flash 10 pair heads =
# 40 KB (20 KB), Ouro 16 heads = 64 KB (32 KB).
SERVED = {
    "mistral-7b": (16, 64),
    "qwen2-7b": (64, 64),
    "kimi-k2-ep32": (64, 64),
    "ouro-2.6b": (16, 16),
    "laguna-s-ep8": (16, 64),
    "phi4-mini-flash": (16, 64),
    "smallthinker-21b-pp4": (64, 64),
    "xing4-29b-pp6": (64, 64),
}


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("preset", sorted(SERVED))
def test_the_rule_over_the_served_presets(preset, tp):
    mcfg = PRESETS[preset]()
    want = SERVED[preset][tp - 1]
    got = autosize.resolve_page_size(
        mcfg, EngineConfig(attn_backend="pallas"), tp=tp)
    assert got.page_size == want
    assert (autosize.page_bytes(mcfg, KV_PAGE_UNIT, tp=tp)
            < autosize.SMALL_PAGE_BYTES) == (want == 64)
    # The dense backend reads the pool by a gather: a page is no
    # descriptor there, whatever its bytes.
    dense = autosize.resolve_page_size(
        mcfg, EngineConfig(attn_backend="dense"), tp=tp)
    assert dense.page_size == KV_PAGE_UNIT
    # 'auto' off a TPU is the dense backend (these tests run on the CPU).
    assert autosize.resolve_page_size(
        mcfg, EngineConfig(), tp=tp).page_size == KV_PAGE_UNIT


@pytest.mark.parametrize("page", [4, 8, 16, 32, 128])
def test_a_page_size_that_is_given_is_taken_as_given(page):
    ecfg = EngineConfig(page_size=page, attn_backend="pallas",
                        max_pages_per_seq=100, num_pages=333,
                        num_window_pages=77)
    assert autosize.resolve_page_size(PRESETS["qwen2-7b"](), ecfg) is ecfg


@pytest.mark.parametrize("attn_backend,platform,want", [
    ("pallas", "cpu", True), ("pallas", None, True),
    ("dense", "tpu", False), ("dense", None, False),
    ("auto", "tpu", True), ("auto", "auto", True), ("auto", "cpu", False),
    ("auto", None, False)])            # None: jax's backend, the CPU here
def test_which_backend_reads_the_pool(attn_backend, platform, want):
    assert autosize.pallas_reads_pool(attn_backend, platform) is want


@pytest.mark.parametrize("mp16,pool16,win16", [
    (512, 6902, 6901),      # cell 8's server, as sized at 16-token pages
    (704, 18646, 0),        # Xing's
    (672, 28860, 0),        # Kimi's
    (192, 1024, 0),         # Qwen2's parity engine
    (400, 800, 0),          # SmallThinker's parity engine
    (52, 337, 0),           # a cap that is no whole 64-token page
    (1, 5, 3)])
def test_capacity_keeps_its_meaning(mp16, pool16, win16):
    ecfg = EngineConfig(attn_backend="pallas", max_pages_per_seq=mp16,
                        num_pages=pool16, num_window_pages=win16)
    got = autosize.resolve_page_size(PRESETS["smallthinker-21b-pp4"](), ecfg)
    assert got.page_size == 64
    # The context cap: never under what was asked, within a page of it.
    assert 0 <= got.max_context - ecfg.max_context < 64
    assert ecfg.max_context == mp16 * KV_PAGE_UNIT
    assert got.max_pages_per_seq == -(-mp16 // 4)
    # A pool's tokens: never above the 16-token pool's, within a page.
    for a, b in ((got.num_pages, pool16), (got.num_window_pages, win16)):
        assert 0 <= b * KV_PAGE_UNIT - a * 64 < 64
    assert got.max_pages_per_seq == {512: 128, 704: 176, 672: 168,
                                     192: 48, 400: 100, 52: 13, 1: 1}[mp16]


def test_a_numeric_num_pages_of_the_request_counts_16_token_pages():
    mcfg = PRESETS["qwen2-7b"]()
    args = types.SimpleNamespace(max_batch_size=16, num_pages=1024,
                                 page_size="auto", decode_ladder="auto",
                                 target_ctx=0, batch_cap=32)
    req = autosize.sizing_request(args)
    assert req["pages_of"] == KV_PAGE_UNIT
    ecfg = autosize.resolve_sizing(
        mcfg, EngineConfig(attn_backend="pallas", max_pages_per_seq=192), req)
    assert (ecfg.page_size, ecfg.num_pages, ecfg.max_pages_per_seq) == (
        64, 256, 48)
    # A router that settled the page hands its workers the number and
    # the same request: the count is not restated twice.
    again = autosize.resolve_sizing(mcfg, ecfg, req)
    assert (again.page_size, again.num_pages, again.max_pages_per_seq) == (
        64, 256, 48)
    # An integer --page-size: every count is in pages of that size.
    args.page_size = 64
    given = autosize.resolve_sizing(
        mcfg, EngineConfig(attn_backend="pallas", page_size=64,
                           max_pages_per_seq=48),
        autosize.sizing_request(args))
    assert (given.num_pages, given.max_pages_per_seq) == (1024, 48)
    # The dense backend: 16-token pages, the counts as written.
    args.page_size = "auto"
    dense = autosize.resolve_sizing(
        mcfg, EngineConfig(attn_backend="dense", max_pages_per_seq=192),
        autosize.sizing_request(args))
    assert (dense.page_size, dense.num_pages, dense.max_pages_per_seq) == (
        16, 1024, 192)


@pytest.mark.parametrize("preset,flags", [
    ("smallthinker-21b-pp4", dict(mp=512, target_ctx=1024, batch_cap=64)),
    ("xing4-29b-pp6", dict(mp=704, target_ctx=2048, batch_cap=64)),
    ("kimi-k2-ep32", dict(mp=672, target_ctx=0, batch_cap=32)),
    ("qwen2-7b", dict(mp=192, target_ctx=0, batch_cap=32, quant="int8"))])
def test_auto_sizes_hold_the_lanes_and_the_pools_tokens(preset, flags):
    """The four configurations the rule moves, sized for the v5e's
    16.91e9 bytes as their cells' flags ask: the same lanes, each pool's
    tokens within a page a lane, at the 64-token page as at 16."""
    mcfg = PRESETS[preset]()
    req = dict(max_batch_size="auto", num_pages="auto", decode_ladder="auto",
               target_ctx=flags["target_ctx"], batch_cap=flags["batch_cap"])
    sized = {}
    for page in (16, None):
        sized[page] = autosize.resolve_sizing(
            mcfg, EngineConfig(attn_backend="pallas", page_size=page,
                               quant=flags.get("quant", "none"),
                               max_pages_per_seq=flags["mp"]),
            req, hbm_bytes=16.91e9)
    narrow, wide = sized[16], sized[None]
    assert wide.page_size == 64 and narrow.page_size == 16
    assert wide.max_batch_size == narrow.max_batch_size
    assert wide.decode_ladder == narrow.decode_ladder
    assert wide.max_context == narrow.max_context
    lanes = wide.max_batch_size
    for a, b in ((wide.num_pages, narrow.num_pages),
                 (wide.num_window_pages, narrow.num_window_pages)):
        assert abs(a * 64 - b * 16) <= 64 * lanes


def test_the_resolver_is_a_fixed_point_and_survives_the_transport():
    mcfg = PRESETS["tiny-kimi"]()
    ecfg = EngineConfig(attn_backend="pallas", max_pages_per_seq=40,
                        num_pages=401, prefill_buckets=(64, 128))
    once = autosize.resolve_page_size(mcfg, ecfg)
    assert once.page_size == 64 and once.max_pages_per_seq == 10
    assert autosize.resolve_page_size(mcfg, once) is once
    for cfg in (ecfg, once):         # unresolved (None) and resolved
        wire = json.loads(json.dumps(framework_config_to_dict(
            FrameworkConfig(model=mcfg, engine=cfg))))
        back = framework_config_from_dict(wire).engine
        assert back == cfg
        assert autosize.resolve_page_size(mcfg, back) == once


def _metrics(eng) -> str:
    from tpu_inference.telemetry import render_prometheus
    return render_prometheus([({}, eng.telemetry.registry)])


def test_an_engine_built_directly_settles_its_page_and_says_so():
    mcfg = PRESETS["tiny-llama"]()
    ecfg = EngineConfig(max_pages_per_seq=32, num_pages=130,
                        max_batch_size=2, prefill_buckets=(64,))
    wide = InferenceEngine(mcfg, ecfg, attn_backend="pallas",
                           pallas_interpret=True)
    assert wide.engine_cfg.page_size == 64
    assert (wide.engine_cfg.max_pages_per_seq, wide.engine_cfg.num_pages) == (
        8, 32)
    assert wide.kv.k.shape[1:3] == (32, 64)
    assert wide.device_info()["page_size"] == 64
    assert "tpu_inf_kv_page_tokens 64" in _metrics(wide)
    dense = InferenceEngine(mcfg, ecfg)
    assert dense.engine_cfg.page_size == 16
    assert dense.kv.k.shape[1:3] == (130, 16)
    assert "tpu_inf_kv_page_tokens 16" in _metrics(dense)


# ---------------------------------------------------------------------------
# A 64-token page holds the same rows a 16-token page held.
# ---------------------------------------------------------------------------

def _run(preset, page, prefix_cache):
    mcfg = PRESETS[preset]()
    ecfg = EngineConfig(page_size=page, num_pages=4096 // page,
                        max_pages_per_seq=512 // page, max_batch_size=4,
                        prefill_buckets=(64, 128), decode_steps_per_call=4,
                        enable_prefix_cache=prefix_cache)
    eng = InferenceEngine(mcfg, ecfg, seed=0, attn_backend="pallas",
                          pallas_interpret=True)
    assert eng.attn_backend == "pallas"
    rng = np.random.default_rng(11)
    vocab = min(mcfg.vocab_size, 256)
    shared = [int(t) for t in rng.integers(1, vocab, 128)]
    # 133: two chunks; 198: two chunks and past the tiny presets' window
    # of 8 from the first decode step on; 64-aligned shared prefix.
    prompts = [shared + [int(t) for t in rng.integers(1, vocab, n)]
               for n in (5, 70)]
    seqs = [Sequence(request_id=i, prompt_tokens=p, max_new_tokens=32)
            for i, p in enumerate(prompts)]
    for s in seqs:
        eng.prefill(s)
    while any(len(s.generated) < 13 for s in seqs):
        eng.decode_steps()            # both lanes, fused K = 4
    out = [list(s.generated[:13]) for s in seqs]
    cached = None
    if prefix_cache:
        for s in seqs:
            eng.release(s)
        hit = Sequence(request_id=9, max_new_tokens=8, prompt_tokens=shared + [
            int(t) for t in rng.integers(1, vocab, 21)])
        eng.prefill(hit)
        eng.decode_steps()
        cached = hit.cached_tokens
        out.append(list(hit.generated[:4]))
    return out, cached


@pytest.mark.parametrize("preset,prefix_cache", [
    ("tiny-llama", True),             # GQA, the prefix cache on
    ("tiny-smallthinker", False),     # full + window-8 kinds, a pool a kind
    ("tiny-kimi", True)])             # a latent pool, the prefix cache on
def test_a_wide_page_holds_the_same_rows(preset, prefix_cache):
    narrow, cached16 = _run(preset, 16, prefix_cache)
    wide, cached64 = _run(preset, 64, prefix_cache)
    assert wide == narrow
    assert cached64 == cached16
    if prefix_cache:
        assert cached64 == 128        # every shared page came back
