"""The Laguna family on the CPU at tiny widths with the real structure
(tiny-laguna: two periods of full / window x 3 with 6 / 9 query heads on
3 KV heads, window 8, layer 0 dense, then 16 experts top-3 of which a
rank holds 4, a shared expert, the per-head gate, YaRN on half of a full
layer's head dims), seeded random weights:

(a) the engine (chunked prefill, batched fused-K decode, contexts many
    windows long, window-kind pages released inside the compared run)
    against the in-repo plain reference, logits; sigmoid and softmax
    routing; the dense path and both Pallas kernels (interpret mode);
(b) the shares add up: the routed parts of all expert-parallel ranks plus
    the shared expert once equal the uncut reference's layer output;
(c) the allocator a kind: window pages released and reused while full
    pages stay, both released on finish and on preemption, exhaustion of
    either kind makes admission wait, nothing leaks over 200 random
    admit / step / finish rounds;
(d) 'auto' sizing splits the budget, and a one-kind model's numbers are
    what they were;
(e) the presets' fields equal the keys of the configuration files, the
    value checks of ``validate()``, and what is refused at construction.
"""

import dataclasses
import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.config import PRESETS, EngineConfig
from tpu_inference.engine import autosize
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.engine.scheduler import EngineScheduler
from tpu_inference.models import deepseek_v3 as dsv3
from tpu_inference.models import laguna

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    """A file of bench/ as a module, without putting bench/ on sys.path
    (its ``tests`` directory would shadow this one's ``tests.conftest``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.splitext(os.path.basename(path))[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(REPO, "bench", "references", "laguna.py"))


def config_file(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


TINY_FILE = "bench/tests/rehearsal/configs/tiny-laguna.json"
REAL_FILE = "bench/configs/laguna-s-ep8-bf16.json"


def tiny(scoring="sigmoid", seed=5):
    cfg = config_file(TINY_FILE)
    cfg["assumed"]["moe_scoring"] = scoring
    sz = REF.sizes(cfg, 8)
    weights = jax.tree.map(lambda a: a.astype(jnp.float32),
                           REF.make_weights(sz, seed))
    mcfg = dataclasses.replace(PRESETS["tiny-laguna"](), moe_scoring=scoring)
    return mcfg, sz, weights


def engine(mcfg, weights=None, **kw):
    ecfg = EngineConfig(**{**dict(page_size=4, num_pages=160,
                                  max_pages_per_seq=40, max_batch_size=4,
                                  prefill_buckets=(8, 16),
                                  decode_steps_per_call=4), **kw})
    return InferenceEngine(mcfg, ecfg, params=weights,
                           pallas_interpret=kw.get("attn_backend")
                           == "pallas")


def probe_logits(eng, seq, p):
    """Logits at position p off the pools the serving graphs wrote (as
    bench/parity.py's probe: one row holding a table a kind)."""
    stream = seq.prompt_tokens + seq.generated
    pos = jnp.asarray([p], jnp.int32)
    table = jnp.asarray(eng._block_table_array(seq.pages))[None]
    attn = eng._paged_attn(eng.model_cfg, table, pos[:, None],
                           jnp.ones((1, 1), bool), q_offset=pos,
                           kv_len=pos + 1)
    hidden, eng.kv = eng.mod.forward_hidden(
        eng.params, eng.model_cfg, jnp.asarray([[stream[p]]], jnp.int32),
        pos[:, None], eng.kv, attn)
    return np.asarray(eng.mod.unembed(eng.params, eng.model_cfg,
                                      hidden[:, 0])[0])


def live(pages):
    return sum(1 for p in pages if p)


# ------------------------------------------------------------------ (a)
@pytest.mark.parametrize("scoring,backend", [
    ("sigmoid", "dense"), ("softmax", "dense"), ("sigmoid", "pallas")])
def test_engine_matches_the_reference(scoring, backend):
    mcfg, sz, weights = tiny(scoring)
    eng = engine(mcfg, weights, attn_backend=backend)
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(0, 512, n)]
               for n in (11, 70, 121)]      # 1, 5 and 8 chunks of 16
    seqs = [Sequence(request_id=i, prompt_tokens=p, max_new_tokens=12)
            for i, p in enumerate(prompts)]
    span = eng.window_span
    got = {}
    for i, s in enumerate(seqs):
        eng.prefill(s)
        # A 121-token prompt never held 121 tokens of window-kind pages.
        assert live(s.pages.window) <= span
        assert live(s.pages) == -(-len(s.prompt_tokens) // 4)
        # Read NOW: the window frees the pages a later token no longer
        # needs (as bench/parity.py's run_streams).
        n = len(s.prompt_tokens)
        got[i] = {n - 1: probe_logits(eng, s, n - 1)}
    released_in_prefill = eng.window_pages_released
    assert released_in_prefill >= (121 - 8 - 16) // 4
    while any(len(s.generated) < 9 for s in seqs):
        eng.decode_steps()                  # all lanes, fused K
    assert eng.window_pages_released > released_in_prefill

    for i, s in enumerate(seqs):
        n = len(s.prompt_tokens)
        stream = (s.prompt_tokens + s.generated)[:n + 8]
        got[i][n + 7] = probe_logits(eng, s, n + 7)
        at = [n - 1, n + 7]
        for p, r in zip(at, REF.logits(weights, sz, stream, at)):
            err = (got[i][p] - r) / np.std(r)
            assert np.sqrt(np.mean(err ** 2)) < 2e-4, (backend, n, p)
        full = REF.logits(weights, sz, stream, list(range(n - 1, n + 8)))
        assert [int(np.argmax(r)) for r in full] == s.generated[:9]
        # The full kind holds every page, the window kind its span.
        assert live(s.pages) == len(s.pages) == -(-s.ctx_len // 4)
        assert live(s.pages.window) <= span
        eng.release(s)
    assert eng.allocator.num_free == eng.engine_cfg.num_pages - 1
    assert eng.win_allocator.num_free == eng.win_allocator.num_pages - 1
    st = dict(zip(dsv3.MOE_STATS, eng.aux_stats))
    assert st["tokens"] > 0 and st["local_pairs"] == st["computed_pairs"] > 0


def test_forward_is_the_reference_on_a_whole_stream():
    """No cache: the dense attention a kind against the reference, at a
    depth cut that ends inside a period (the parity depth, 5)."""
    mcfg, _, _ = tiny()
    cfg = config_file(TINY_FILE)
    for depth in (5, 8):
        sz = REF.sizes(cfg, depth)
        weights = jax.tree.map(lambda a: a.astype(jnp.float32),
                               REF.make_weights(sz, 7))
        m = dataclasses.replace(mcfg, n_layers=depth)
        toks = np.random.default_rng(1).integers(0, 512, 70)
        got, _ = laguna.forward(weights, m, jnp.asarray(toks)[None],
                                jnp.arange(70)[None], None,
                                laguna.make_dense_attn(m))
        want = REF.logits(weights, sz, list(toks), [10, 69])
        np.testing.assert_allclose(np.asarray(got[0])[[10, 69]], want,
                                   atol=5e-5)


def test_the_stack_traces_one_body_a_kind_and_form():
    """Layers grouped so that each traced body sees one parameter shape:
    the first period as runs, the whole periods after it as one scan."""
    mcfg = PRESETS["laguna-s-ep8"]()
    assert laguna.kinds_period(mcfg) == 4
    assert laguna.layer_runs(mcfg, 0, 4) == [("full", False, 0, 1),
                                             ("window", True, 1, 3)]
    assert laguna.layer_runs(mcfg, 4, 8) == [("full", True, 4, 1),
                                             ("window", True, 5, 3)]
    shapes = laguna.param_shapes(mcfg)
    assert shapes["attn_full"]["wq"] == (3, 3072, 48 * 128)
    assert shapes["attn_window"]["wq"] == (9, 3072, 72 * 128)
    assert shapes["attn_window"]["w_head_gate"] == (9, 3072, 72)
    assert shapes["ffn_moe"]["we_gate"] == (11, 32, 3072, 1024)
    # ISSUE 32's count: 4.33B parameters, 8.65 GB in bfloat16.
    assert abs(laguna.param_count(mcfg) / 1e9 - 4.3255) < 1e-3


# ------------------------------------------------------------------ (b)
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_shares_add_up(scoring):
    """One expert layer: sum over ranks of (routed part of the rank) +
    shared expert once == the uncut layer (every expert on one rank) ==
    the reference's layer."""
    mcfg, sz, _ = tiny(scoring)
    full_sz = dict(sz, held=sz["experts"], first_held=0)
    full = REF.make_weights(full_sz, 11)
    lp = jax.tree.map(lambda a: a[0].astype(jnp.float32), full["ffn_moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 37, mcfg.d_model))
    attn = laguna.make_dense_attn(mcfg)

    def routed_plus_shared(cfg, lp_rank):
        experts = tuple(lp_rank[k][None] for k in laguna.EXPERT_STACKS)
        out, stats = dsv3.moe_ffn(cfg, lp_rank, experts, 0, h, attn)
        return out[0], stats

    uncut, _ = routed_plus_shared(
        dataclasses.replace(mcfg, ep_size=1, ep_rank=0), lp)
    shared = dsv3.swiglu(h[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    held = mcfg.n_local_experts
    parts, pairs = [], 0
    for rank in range(mcfg.ep_size):
        lp_rank = dict(lp, **{k: lp[k][rank * held:(rank + 1) * held]
                              for k in laguna.EXPERT_STACKS})
        out, stats = routed_plus_shared(
            dataclasses.replace(mcfg, ep_rank=rank), lp_rank)
        parts.append(out - shared)
        pairs += int(stats[1])
    assert pairs == 37 * mcfg.n_experts_per_tok     # every pair somewhere
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-5)
    logit = h[0] @ lp["w_router"]
    sc = (jax.nn.sigmoid(logit) if scoring == "sigmoid"
          else jax.nn.softmax(logit, -1))
    _, top = jax.lax.top_k(sc + lp["router_bias"][None], sz["top_k"])
    g = jnp.take_along_axis(sc, top, 1)
    g = g / g.sum(1, keepdims=True) * sz["route_scale"]
    want = REF._swiglu(h[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    for e in range(sz["experts"]):
        ge = jnp.where(top == e, g, 0.0).sum(1)
        want = want + ge[:, None] * REF._swiglu(
            h[0], lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e])
    np.testing.assert_allclose(uncut, want, atol=1e-5)


def test_the_references_bias_decides_the_held_experts():
    mcfg, sz, weights = tiny()
    chosen = min(REF.HELD_CHOSEN, sz["top_k"] // 2, sz["held"])
    bias = np.asarray(weights["ffn_moe"]["router_bias"])
    held = slice(sz["first_held"], sz["first_held"] + sz["held"])
    assert ((bias[:, held] == REF.HELD_MARGIN).sum(1) == chosen).all()
    assert ((bias[:, held] == -REF.HELD_MARGIN).sum(1)
            == sz["held"] - chosen).all()
    lp = jax.tree.map(lambda a: a[0], weights["ffn_moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (200, mcfg.d_model)) * 3
    top = np.asarray(dsv3.route(mcfg, lp, x)[0])
    is_held = (top >= held.start) & (top < held.stop)
    assert (is_held.sum(1) == chosen).all()
    assert len(np.unique(top[~is_held])) > sz["top_k"] - chosen


# ------------------------------------------------------------------ (c)
@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_merged_row_pools_decode_what_five_dim_pools_decode(backend):
    """``ModelConfig.pool_rows_merged`` is a layout of a stack of kinds'
    pools, not one family's: this stack on pools ``[slots, P, page *
    heads, width]`` (written a page at a time in a prefill, a token at a
    time in a decode step: by the scatter under the dense backend, by
    kernels/kv_rows_write.py under the Pallas one, here in interpret
    mode) samples the tokens it samples on five-dim ones, over chunked
    prompts that release window pages."""
    mcfg, _, weights = tiny()
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(0, 512, n)] for n in (11, 70)]
    got = {}
    for merged in (False, True):
        # (The kernel copies whole 8-row float32 tiles: 8-token pages of
        # this stack's 3 KV heads are three of them, 4-token pages 1.5.)
        eng = engine(dataclasses.replace(mcfg, pool_rows_merged=merged),
                     weights, attn_backend=backend,
                     page_size=8 if backend == "pallas" else 4)
        assert eng.kv.wk.ndim == (4 if merged else 5)
        assert eng.device_info()["kv_decode_write"] == (
            "kernel" if merged and backend == "pallas" else "scatter")
        seqs = [Sequence(request_id=i, prompt_tokens=p, max_new_tokens=10)
                for i, p in enumerate(prompts)]
        for s in seqs:
            eng.prefill(s)
        while not all(s.done for s in seqs):
            eng.decode_steps()
        got[merged] = [s.generated for s in seqs]
    assert got[True] == got[False]


def test_window_pages_are_released_and_reused_while_full_pages_stay():
    mcfg, _, _ = tiny()
    eng = engine(mcfg, num_window_pages=3 * 8 + 1)      # three lanes' spans
    assert eng.window_span == 8         # (8 + 16) / 4 + 2
    rng = np.random.default_rng(0)
    a = Sequence(request_id=0, max_new_tokens=60, prompt_tokens=[
        int(t) for t in rng.integers(0, 512, 90)])
    eng.prefill(a)
    seen = {p for p in a.pages.window if p}
    peak = 0
    while not a.done:
        eng.decode_steps()
        peak = max(peak, live(a.pages.window))
        seen |= {p for p in a.pages.window if p}
    # 150 tokens went through a window pool of 24 pages: pages came back.
    assert peak <= 8 and eng.window_pages_released >= 150 // 4 - 8
    assert live(a.pages) == len(a.pages) == -(-a.ctx_len // 4)
    assert a.pages.window[:a.evicted_pages] == [0] * a.evicted_pages
    first_full = a.pages[0]
    b = Sequence(request_id=1, max_new_tokens=4, prompt_tokens=[
        int(t) for t in rng.integers(0, 512, 30)])
    eng.prefill(b)
    assert {p for p in b.pages.window if p} & seen     # a released page
    assert first_full not in b.pages                   # a's are still a's
    eng.release(a)
    eng.release(b)
    assert eng.allocator.num_free == 159
    assert eng.win_allocator.num_free == 24


def test_preemption_releases_both_kinds_and_resumes():
    mcfg, sz, weights = tiny()
    eng = engine(mcfg, weights, admission="optimistic")
    prompt = [int(t) for t in np.random.default_rng(4).integers(0, 512, 45)]
    s = Sequence(request_id=0, prompt_tokens=prompt, max_new_tokens=24)
    eng.prefill(s)
    for _ in range(2):
        eng.decode_steps()
    before = list(s.generated)
    eng.preempt(s)
    assert s.pages == [] and s.evicted_pages == 0
    assert eng.allocator.num_free == 159
    assert eng.win_allocator.num_free == eng.win_allocator.num_pages - 1
    (again,) = eng.take_preempted()
    eng.prefill(again)                      # recompute-resume
    while not again.done:
        eng.decode_steps()
    assert again.generated[:len(before)] == before
    n = len(prompt)
    ref = REF.logits(weights, sz, prompt + again.generated[:-1],
                     list(range(n - 1, n + 23)))
    assert [int(np.argmax(r)) for r in ref] == again.generated
    eng.release(again)


@pytest.mark.parametrize("short", ["full", "window"])
def test_exhaustion_of_either_kind_makes_admission_wait(short):
    """A pool that holds ONE sequence's charge of a kind: the second
    request waits in the queue (no error, no crash) until the first has
    finished, and both complete."""
    mcfg, _, _ = tiny()
    sizes = (dict(num_pages=30, num_window_pages=4 * 8 + 1)
             if short == "full" else
             dict(num_pages=160, num_window_pages=8 + 3))
    eng = engine(mcfg, **sizes)
    rng = np.random.default_rng(1)
    seqs = [Sequence(request_id=i, max_new_tokens=20, prompt_tokens=[
        int(t) for t in rng.integers(0, 512, 70)]) for i in range(2)]
    need = eng.admission_need(seqs[0])
    assert list(need) == [-(-90 // 4), 8, 0]       # (no state slots)
    assert eng.can_ever_admit(seqs[0])
    overlapped, finished = [], threading.Event()

    def on_token(seq, tok):
        overlapped.append(sum(s is not None for s in eng.slots) > 1)

    def on_finish(seq):
        if all(s.done for s in seqs):
            finished.set()

    sched = EngineScheduler(eng).start()
    for s in seqs:
        sched.submit(s, on_token, on_finish)
    assert finished.wait(120)
    sched.stop()
    assert all(s.finish_reason == "length" for s in seqs), [
        s.finish_reason for s in seqs]
    overlapped = any(overlapped)
    assert not overlapped, "the second was admitted beside the first"
    assert eng.allocator.num_free == eng.engine_cfg.num_pages - 1
    assert eng.win_allocator.num_free == eng.win_allocator.num_pages - 1


def test_no_page_leaks_over_random_rounds():
    """200 random admit / step / finish / preempt rounds: every page of
    either kind is free at the end, and no sequence ever held more
    window-kind pages than its span."""
    mcfg, _, _ = tiny()
    eng = engine(mcfg, admission="optimistic")
    rng = np.random.default_rng(12)
    running, rid = [], 0
    for _ in range(200):
        op = rng.integers(0, 4)
        if op == 0 and eng.free_slots():
            s = Sequence(request_id=rid, max_new_tokens=int(
                rng.integers(1, 30)), prompt_tokens=[
                    int(t) for t in rng.integers(0, 512,
                                                 int(rng.integers(2, 100)))])
            rid += 1
            if eng.can_admit(s):
                eng.prefill(s)
                running.append(s)
        elif op == 1 and running:
            eng.decode_steps()
        elif op == 2 and running:
            s = running.pop(int(rng.integers(0, len(running))))
            eng.release(s)                  # finished or cancelled
        elif op == 3 and running:
            s = running.pop(int(rng.integers(0, len(running))))
            if not s.done:
                eng.preempt(s)
                eng.take_preempted()
            else:
                eng.release(s)
        for s in list(running):
            assert live(getattr(s.pages, "window", [])) <= eng.window_span
            if s.done:
                running.remove(s)
                eng.release(s)
    for s in running:
        eng.release(s)
    assert eng.allocator.num_free == eng.engine_cfg.num_pages - 1
    assert eng.win_allocator.num_free == eng.win_allocator.num_pages - 1


def test_cost_model_and_gauges_by_kind():
    """StepCostModel counts a window layer's pairs at min(context,
    window); /metrics carries a pool's pages a kind, without labels."""
    from tpu_inference.telemetry import StepCostModel, render_prometheus

    mcfg, _, _ = tiny()
    eng = engine(mcfg)
    m = StepCostModel.from_engine(eng)
    per_layer = 2 * 3 * 32 * 2          # K and V at the 2 bytes autosize counts
    assert (m.n_layers, m.window_layers, m.window) == (2, 6, 8)
    assert (m.n_heads, m.window_heads, m.head_dim) == (6, 9, 32)
    assert (m.kv_token_bytes, m.kv_window_token_bytes) == (2 * per_layer,
                                                           6 * per_layer)
    # One decode record: 4 lanes x 1 step over contexts that sum to 400.
    rec = (0, 0, 0, 0, 4, 0, 1, 0, 0, 0, 400, 0)
    pairs_w = min(400, 8 * 4)
    assert m.flops(rec) == 2.0 * m.n_params * 4 + 4.0 * 32 * (
        2 * 6 * 400 + 6 * 9 * pairs_w)
    assert m.hbm_bytes(rec) == m.weight_bytes + 2 * per_layer * 404 \
        + 6 * per_layer * (pairs_w + 4)
    s = Sequence(request_id=0, max_new_tokens=30, prompt_tokens=list(
        range(3, 73)))
    eng.prefill(s)
    text = render_prometheus([({}, eng.telemetry.registry)])
    vals = {l.split()[0]: float(l.split()[1]) for l in text.splitlines()
            if l.startswith("tpu_inf_kv_") and "{" not in l}
    assert vals["tpu_inf_kv_full_pages_total"] == 159
    assert vals["tpu_inf_kv_full_pages_in_use"] == 18 == len(s.pages)
    assert vals["tpu_inf_kv_window_pages_total"] == 4 * 8
    assert vals["tpu_inf_kv_window_pages_in_use"] == live(s.pages.window)
    # 70 tokens in chunks of 16 behind a window of 8: never over the span.
    assert vals["tpu_inf_kv_full_pages_peak"] == 18
    assert live(s.pages.window) <= vals["tpu_inf_kv_window_pages_peak"] <= 8
    assert vals["tpu_inf_kv_window_pages_released_total"] == \
        eng.window_pages_released > 0
    assert vals["tpu_inf_kv_bytes_per_token"] == 8 * per_layer
    eng.release(s)


# ------------------------------------------------------------------ (d)
REQ = dict(max_batch_size="auto", num_pages="auto", decode_ladder="off",
           target_ctx=0, batch_cap=32)


def test_auto_sizing_splits_the_budget_by_kind():
    mcfg = PRESETS["laguna-s-ep8"]()
    base = EngineConfig(page_size=16, max_pages_per_seq=832)
    e = autosize.resolve_sizing(mcfg, base, dict(REQ, target_ctx=4608),
                                hbm_bytes=16e9)
    span = kvc.window_span_pages(mcfg, base)
    assert span == (512 + 1024) // 16 + 2
    assert e.max_batch_size == 32
    assert kvc.num_window_pages(mcfg, e) == 32 * span + 1
    full_tok = autosize.kv_bytes_per_token(mcfg, kind="full")
    win_tok = autosize.kv_bytes_per_token(mcfg, kind="window")
    assert (full_tok, win_tok) == (3 * 4096, 9 * 4096)
    assert autosize.kv_bytes_per_token(mcfg) == 12 * 4096
    budget = 0.85 * 16e9 - autosize.weight_bytes(mcfg) - (512 << 20)
    used = 16 * (e.num_pages * full_tok + (32 * span + 1) * win_tok)
    assert 0 <= budget - used < 16 * full_tok          # the rest, to a page
    assert (e.num_pages - 1) * 16 // 4608 >= 32
    # The default target (half the cap) fits one lane fewer.
    d = autosize.resolve_sizing(mcfg, base, REQ, hbm_bytes=16e9)
    assert d.max_batch_size == 31
    assert kvc.num_window_pages(mcfg, d) == 31 * span + 1


@pytest.mark.parametrize("model,quant,mp,want", [
    ("mistral-7b", "int8", 320, (18, 2986)),
    ("qwen2-7b", "int8", 192, (32, 5521)),
    ("kimi-k2-ep32", "none", 672, (32, 28860)),
    ("ouro-2.6b", "none", 52, (12, 337)),
])
def test_a_one_kind_model_gets_the_numbers_it_got(model, quant, mp, want):
    """The four configurations that exist: batch and pool as the parent
    commit (PR 31) sized them at the chip's own 16.91e9 bytes, with the
    cells' flags."""
    e = autosize.resolve_sizing(
        PRESETS[model](), EngineConfig(quant=quant, max_pages_per_seq=mp),
        REQ, hbm_bytes=16.91e9)
    assert e.num_window_pages == 0
    assert (e.max_batch_size, e.num_pages) == want


# ------------------------------------------------------------------ (e)
def test_preset_equals_the_configuration_file():
    cfg, m = config_file(REAL_FILE), PRESETS["laguna-s-ep8"]()
    full = cfg["rope_parameters"]["full_attention"]
    slide = cfg["rope_parameters"]["sliding_attention"]
    n = cfg["num_hidden_layers"]
    pairs = [
        (m.n_layers, n), (m.d_model, cfg["hidden_size"]),
        (m.n_heads, cfg["num_attention_heads"]),
        (m.n_kv_heads, cfg["num_key_value_heads"]),
        (m.head_dim, cfg["head_dim"]), (m.d_ff, cfg["intermediate_size"]),
        (m.vocab_size, cfg["vocab_size"]),
        (m.sliding_window, cfg["sliding_window"]),
        (m.moe_d_ff, cfg["moe_intermediate_size"]),
        (m.n_shared_experts * m.moe_d_ff,
         cfg["shared_expert_intermediate_size"]),
        (m.n_experts, cfg["published"]["num_experts"]),
        (m.n_local_experts, cfg["num_experts"]),
        (m.ep_size, cfg["deployment"]["expert_parallel"]),
        (m.ep_rank, cfg["deployment"]["rank"]),
        (m.n_experts_per_tok, cfg["num_experts_per_tok"]),
        (m.routed_scaling_factor, cfg["moe_routed_scaling_factor"]),
        (m.norm_topk_prob, cfg["norm_topk_prob"]),
        (m.moe_scoring, cfg["assumed"]["moe_scoring"]),
        (m.first_k_dense, len(cfg["mlp_only_layers"])),
        (m.norm_eps, cfg["rms_norm_eps"]),
        (m.rope_theta, full["rope_theta"]),
        (m.partial_rotary_factor, full["partial_rotary_factor"]),
        (m.rope_scaling.factor, full["factor"]),
        (m.rope_scaling.original_max_len,
         full["original_max_position_embeddings"]),
        (m.rope_scaling.beta_fast, full["beta_fast"]),
        (m.rope_scaling.beta_slow, full["beta_slow"]),
        (m.rope_scaling.attention_factor, full["attention_factor"]),
        (m.window_rope_theta, slide["rope_theta"]),
        (m.max_seq_len, cfg["max_position_embeddings"]),
        ([REF.KINDS[k] for k in cfg["layer_types"][:n]],
         list(m.layer_types[:n])),
        ([m.kind_heads(k) for k in m.layer_types[:n]],
         cfg["num_attention_heads_per_layer"][:n]),
        (m.attn_gate == "per_head", cfg["gating"] == "per-head"),
    ]
    assert [p for p in pairs if p[0] != p[1]] == []
    # Every number of the catalog row but the three that are reduced.
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                "vocab_size": 100352}
    assert len(cfg["layer_types"]) == 48
    # The tiny preset and its file, through the reference's reading.
    t, tm = config_file(TINY_FILE), PRESETS["tiny-laguna"]()
    sz = REF.sizes(t, tm.n_layers)
    assert sz["heads"] == tuple(tm.kind_heads(k) for k in tm.layer_types)
    assert (sz["held"], sz["experts"], sz["window"]) == (
        tm.n_local_experts, tm.n_experts, tm.sliding_window)


def test_validate_checks_values():
    m = PRESETS["tiny-laguna"]()
    m.validate()
    for bad in (dict(n_layers=9),                   # kinds name 8 layers
                dict(window_n_heads=8),             # 8 % 3 KV heads
                dict(ep_size=3),                    # 16 experts over 3
                dict(layer_types=("full", "strided") * 4),
                dict(partial_rotary_factor=0.3),    # 9.6 dims
                dict(sliding_window=0)):
        with pytest.raises(AssertionError):
            dataclasses.replace(m, **bad).validate()
    # ... on any family's name: a one-kind model with a share of experts.
    with pytest.raises(AssertionError):
        dataclasses.replace(PRESETS["tiny-kimi"](), ep_size=3).validate()


@pytest.mark.parametrize("kw,what", [
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(host_cache_pages=8), "host KV tier"),
    (dict(num_speculative_tokens=2), "speculative"),
    (dict(role="prefill"), "role"),
    (dict(quant="int4"), "int4"),
])
def test_what_is_not_supported_is_refused(kw, what):
    with pytest.raises(ValueError, match=what):
        engine(PRESETS["tiny-laguna"](), **kw)


def test_the_prefix_cache_stays_off_with_its_reason(capsys):
    eng = engine(PRESETS["tiny-laguna"](), enable_prefix_cache=True)
    assert eng.prefix_cache is None and eng.swa_evict
    assert "window kind's last sliding_window tokens" in capsys.readouterr().out
