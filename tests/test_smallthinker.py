"""The SmallThinker family on the CPU at tiny widths with the real
structure (tiny-smallthinker: two periods of one rope-less full layer and
three window-8 layers, 8 query / 2 KV heads of 16, every layer routed
from its PRE-attention norm to top-3 of 8 ReLU-gated experts, all held,
no shared expert, no dense layer), seeded random weights:

(a) the engine (chunked prefill, batched fused-K decode, contexts many
    windows long, window-kind pages released inside the compared run)
    against the in-repo plain reference, logits; the dense path and the
    Pallas kernels (interpret mode);
(b) what the architecture adds, each against a hand computation in
    numpy: the router reads the pre-attention norm, a full layer takes no
    rope, the experts gate with relu, the gates are a softmax over the
    chosen logits: the right form agrees and the neighbouring wrong one
    does not;
(c) the grouped kernels with ``relu`` against a plain loop at 64 held
    experts with idle rows;
(d) 'auto' sizes: the window pool on live tokens where ``target_ctx``
    lies under the window, page for page what they were for the Laguna
    and Phi-4 presets;
(e) the lowered step programs of the accepted presets' tiny twins are
    byte for byte the parent commit's (the new ``ModelConfig`` fields'
    defaults change nothing);
(f) the preset against the configuration file, the counts of ISSUE 43 by
    hand, the counters and gauges this family fills, what is refused.
"""

import dataclasses
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference import telemetry
from tpu_inference.config import PRESETS, EngineConfig
from tpu_inference.engine import autosize
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.kernels import moe_experts
from tpu_inference.models import deepseek_v3 as dsv3
from tpu_inference.models import smallthinker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    """A file of bench/ as a module, without putting bench/ on sys.path
    (its ``tests`` directory would shadow this one's ``tests.conftest``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.splitext(os.path.basename(path))[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(REPO, "bench", "references", "smallthinker.py"))
TINY_FILE = "bench/tests/rehearsal/configs/tiny-smallthinker.json"
REAL_FILE = "bench/configs/smallthinker-21b-pp4-bf16.json"


def config_file(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def tiny(seed=5):
    sz = REF.sizes(config_file(TINY_FILE), 8)
    weights = jax.tree.map(lambda a: a.astype(jnp.float32),
                           REF.make_weights(sz, seed))
    return PRESETS["tiny-smallthinker"](), sz, weights


def engine(mcfg, weights=None, **kw):
    ecfg = EngineConfig(**{**dict(page_size=4, num_pages=160,
                                  max_pages_per_seq=40, max_batch_size=4,
                                  prefill_buckets=(8, 16),
                                  decode_steps_per_call=4,
                                  keep_logits=True), **kw})
    return InferenceEngine(mcfg, ecfg, params=weights,
                           pallas_interpret=kw.get("attn_backend")
                           == "pallas")


def live(pages):
    return sum(1 for p in pages if p)


# ------------------------------------------------------------------ (a)
@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_engine_matches_the_reference(backend):
    mcfg, sz, weights = tiny()
    eng = engine(mcfg, weights, attn_backend=backend)
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(0, 512, n)]
               for n in (11, 70, 121)]      # 1, 5 and 8 chunks of 16
    seqs = [Sequence(request_id=i, prompt_tokens=p, max_new_tokens=12)
            for i, p in enumerate(prompts)]
    for s in seqs:
        eng.prefill(s)
        # A 121-token prompt never held 121 tokens of window-kind pages.
        assert live(s.pages.window) <= eng.window_span
        assert live(s.pages) == -(-len(s.prompt_tokens) // 4)
    released_in_prefill = eng.window_pages_released
    assert released_in_prefill >= (121 - 8 - 16) // 4
    while any(len(s.generated) < 9 for s in seqs):
        eng.decode_steps()                  # all lanes, fused K
    assert eng.window_pages_released > released_in_prefill
    for s in seqs:
        n = len(s.prompt_tokens)
        stream = (s.prompt_tokens + s.generated)[:n + 8]
        at = list(range(n - 1, n + 8))
        # The rows the step programs sampled from (``keep_logits``), at
        # the last prompt position and every decoded one. float32 on both
        # sides: what is left is the order of the sums (a paged softmax
        # in pages of 4, a grouped matmul against a loop over experts),
        # 1e-6 of a logit's spread, so 2e-4 is a hundred times that and a
        # hundredth of the smallest planted fault (0.007).
        for p, r in zip(at, REF.logits(weights, sz, stream, at)):
            err = (s.kept_logits[p] - r) / np.std(r)
            assert np.sqrt(np.mean(err ** 2)) < 2e-4, (backend, n, p)
            assert int(np.argmax(r)) == s.generated[p - (n - 1)]
        eng.release(s)
    assert eng.allocator.num_free == eng.engine_cfg.num_pages - 1
    assert eng.win_allocator.num_free == eng.win_allocator.num_pages - 1
    st = dict(zip(dsv3.MOE_STATS, eng.aux_stats))
    # Every expert is here: a token's three pairs are all local.
    assert st["local_pairs"] == st["computed_pairs"] == 3 * st["tokens"] > 0
    assert eng.aux_stats[-1] >= st["computed_pairs"]     # whole tiles


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
        got, _ = smallthinker.forward(weights, m, jnp.asarray(toks)[None],
                                      jnp.arange(70)[None], None,
                                      smallthinker.make_dense_attn(m))
        want = REF.logits(weights, sz, list(toks), [10, 69])
        np.testing.assert_allclose(np.asarray(got[0])[[10, 69]], want,
                                   atol=5e-5)


def test_the_parity_weights_can_pin_the_membership():
    """``assumed.weights.pinned`` (the real configuration's parity
    weights): the first ``groups`` hidden dims are a one-hot of the
    token's group that nothing writes to, and the router's row g puts
    group g's OWN ``top_k`` experts of the layer ahead of a token's own
    logits: membership cannot change between bfloat16 and float32, it
    differs BY TOKEN (every expert is some group's, and the groups are
    of uneven size), and the gates stay the token's own."""
    cfg = config_file(TINY_FILE)
    cfg["assumed"]["weights"] = dict(
        config_file(REAL_FILE)["assumed"]["weights"], text="")
    sz = REF.sizes(cfg, 8)
    constant, margin, groups = sz["pinned"]
    g = int(groups)
    assert constant == 1.0 and margin >= 4.0
    assert g * 6 >= 64, "the real configuration: every expert some group's"
    w = jax.tree.map(lambda a: np.asarray(a, np.float32),
                     REF.make_weights(sz, 9))
    group_of = w["embed"][:, :g].argmax(1)
    assert (w["embed"][:, :g].sum(1) == 1.0).all()
    assert (w["embed"][np.arange(512), group_of] == 1.0).all()
    share = np.bincount(group_of, minlength=g) / 512
    assert share.min() > 0 and share.max() > 5 * share.min()     # uneven
    assert not w["ffn_moe"]["we_down"][..., :g].any()
    assert not any(w[k]["wo"][..., :g].any()
                   for k in ("attn_full", "attn_window"))
    m = PRESETS["tiny-smallthinker"]()
    rng = np.random.default_rng(0)
    h = rng.normal(size=(200, 64)).astype(np.float32)
    of = rng.integers(0, g, 200)
    # 1 / rms(x) of a grown residual stream, at the token's group.
    h[:, :g] = 0.25 * (of[:, None] == np.arange(g))
    for l in range(8):
        rows = w["ffn_moe"]["w_router"][l, :g]                  # [G, E]
        assert ((rows == margin).sum(1) == 3).all()
        assert set(np.unique(rows)) == {0.0, margin}
        assert (rows > 0).any(0).all(), "an expert no group routes to"
        assert len({tuple(r) for r in rows[:3]}) == 3     # sixes differ
        top, gates = dsv3.route(m, {"w_router": jnp.asarray(
            w["ffn_moe"]["w_router"][l])}, jnp.asarray(h))
        assert all(set(t) == set(np.flatnonzero(rows[i]))
                   for t, i in zip(np.asarray(top), of))
        assert np.asarray(gates).std(0).min() > 0.005    # a token's own
    # ... and the program still is the reference on such weights.
    toks = np.random.default_rng(1).integers(0, 512, 70)
    got, _ = smallthinker.forward(
        jax.tree.map(jnp.asarray, w), m, jnp.asarray(toks)[None],
        jnp.arange(70)[None], None, smallthinker.make_dense_attn(m))
    want = REF.logits(w, sz, list(toks), [10, 69])
    np.testing.assert_allclose(np.asarray(got[0])[[10, 69]], want, atol=5e-5)


# ------------------------------------------------------------------ (b)
def _rms(x, w, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, _, d = x.shape
    inv = theta ** (-np.arange(0, d, 2) / d)
    ang = np.arange(s)[:, None] * inv
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hand_forward(w, cfg, toks, *, router_reads="h", rope_on_full=False,
                 act="relu", gates="softmax_of_chosen"):
    """Two layers (full, window) written out in numpy float64."""
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), w)
    nh, hkv, hd, k = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, \
        cfg.n_experts_per_tok
    x = w["embed"][toks]
    s = len(toks)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    for l, kind in enumerate(cfg.layer_types[:cfg.n_layers]):
        ap = {n: a[0] for n, a in w["attn_" + kind].items()}
        fp = {n: a[l] for n, a in w["ffn_moe"].items()}
        h = _rms(x, ap["attn_norm"])
        q = (h @ ap["wq"]).reshape(s, nh, hd)
        kk = (h @ ap["wk"]).reshape(s, hkv, hd)
        v = (h @ ap["wv"]).reshape(s, hkv, hd)
        if kind == "window" or rope_on_full:
            q, kk = _rope(q, cfg.rope_theta), _rope(kk, cfg.rope_theta)
        kk, v = (np.repeat(a, nh // hkv, axis=1) for a in (kk, v))
        sc = np.einsum("qhd,khd->hqk", q, kk) / np.sqrt(hd)
        mask = j <= i
        if kind == "window":
            mask &= j > i - cfg.sliding_window
        sc = np.where(mask[None], sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        o = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)
        x = x + o.reshape(s, nh * hd) @ ap["wo"]
        h2 = _rms(x, fp["ffn_norm"])
        z = (h if router_reads == "h" else h2) @ fp["w_router"]
        y = np.zeros_like(x)
        for t in range(s):
            top = np.argsort(-z[t])[:k]
            e_all = np.exp(z[t] - z[t].max())
            g = (e_all[top] / e_all[top].sum()
                 if gates == "softmax_of_chosen" else e_all[top] / e_all.sum())
            for ge, e in zip(g, top):
                a = h2[t] @ fp["we_gate"][e]
                a = np.maximum(a, 0) if act == "relu" else a / (1 + np.exp(-a))
                y[t] += ge * ((a * (h2[t] @ fp["we_up"][e]))
                              @ fp["we_down"][e])
        x = x + y
    return _rms(x, w["final_norm"]) @ w["lm_head"]


WRONG = [dict(router_reads="h2"), dict(rope_on_full=True), dict(act="silu"),
         dict(gates="softmax_over_all")]


def _two_layers():
    cfg = dataclasses.replace(PRESETS["tiny-smallthinker"](), n_layers=2,
                              layer_types=("full", "window"))
    w = smallthinker.init_params(cfg, jax.random.PRNGKey(2))
    # Wider than the init: so that scores, routing and gates are far
    # from uniform and each neighbouring form is far from the right one.
    w = jax.tree_util.tree_map_with_path(
        lambda path, a: a if "norm" in path[-1].key else a * 6.0, w)
    toks = np.random.default_rng(4).integers(0, 512, 21)
    got, _ = smallthinker.forward(w, cfg, jnp.asarray(toks)[None],
                                  jnp.arange(21)[None], None,
                                  smallthinker.make_dense_attn(cfg))
    return cfg, w, toks, np.asarray(got[0], np.float64)


def test_the_layer_is_the_hand_computation():
    cfg, w, toks, got = _two_layers()
    want = hand_forward(w, cfg, toks)
    assert np.abs(got - want).max() < 2e-4 * np.std(want)


@pytest.mark.parametrize("wrong", WRONG, ids=lambda d: "-".join(
    f"{k}={v}" for k, v in d.items()))
def test_the_neighbouring_form_is_not_what_runs(wrong):
    """Router fed the experts' input; rope on the full layer; silu; the
    softmax over all experts left unnormalised: each is another
    function, by hundreds of times the agreement above."""
    cfg, w, toks, got = _two_layers()
    other = hand_forward(w, cfg, toks, **wrong)
    assert np.abs(got - other).max() > 0.05 * np.std(other), wrong


# ------------------------------------------------------------------ (c)
@pytest.mark.parametrize("pallas", [False, True])
@pytest.mark.parametrize("rows", [64, 1024])
def test_grouped_kernels_with_relu_at_64_held_experts(pallas, rows):
    """All 64 experts held, top-6: 6 rows an expert at 64 tokens (tiles of
    16), 96 at 1024 (tiles of 128); a third of the rows idle (a padded
    bucket's tail), against a plain loop over the experts."""
    d, f, e, k = 32, 16, 64, 6
    ks = jax.random.split(jax.random.PRNGKey(rows), 6)
    x = jax.random.normal(ks[0], (rows, d), jnp.float32)
    wg, wu = (0.3 * jax.random.normal(kk, (2, e, d, f), jnp.float32)
              for kk in ks[1:3])
    wd = 0.3 * jax.random.normal(ks[3], (2, e, f, d), jnp.float32)
    top = jnp.argsort(-jax.random.normal(ks[4], (rows, e)), axis=1)[:, :k]
    gates = jax.nn.softmax(jax.random.normal(ks[5], (rows, k)), axis=-1)
    valid = (jnp.arange(rows) % 3 != 2)[:, None]
    top_local = jnp.where(valid, top, e)
    groups = moe_experts.group_pairs(top_local, gates, e, rows * k)
    assert groups.tm == (16 if rows == 64 else 128)
    y, done = moe_experts.grouped_experts(
        x, groups, wg, wu, wd, 1, pallas=pallas, interpret=True, act="relu")
    assert int(done) == int(valid.sum()) * k
    want = np.zeros((rows, d), np.float64)
    xs = np.asarray(x, np.float64)
    for ex in range(e):
        ge = np.where(np.asarray(top_local) == ex, np.asarray(gates),
                      0.0).sum(1)
        hid = np.maximum(xs @ np.asarray(wg[1, ex], np.float64), 0) * (
            xs @ np.asarray(wu[1, ex], np.float64))
        want += ge[:, None] * (hid @ np.asarray(wd[1, ex], np.float64))
    assert np.abs(np.asarray(y) - want).max() < 1e-3 * np.std(want)
    # ... and silu, the default, is another function.
    y2, _ = moe_experts.grouped_experts(x, groups, wg, wu, wd, 1,
                                        pallas=pallas, interpret=True)
    assert np.abs(np.asarray(y2) - want).max() > 0.05 * np.std(want)


# ------------------------------------------------------------------ (d)
REQ = dict(max_batch_size="auto", num_pages="auto", decode_ladder="auto",
           target_ctx=0, batch_cap=32)


def test_the_window_pool_is_sized_on_live_tokens_under_the_window():
    mcfg = PRESETS["smallthinker-21b-pp4"]()
    base = EngineConfig(page_size=16, max_pages_per_seq=512)
    span = kvc.window_span_pages(mcfg, base)
    assert span == (4096 + 1024) // 16 + 2 == 322
    e = autosize.resolve_sizing(
        mcfg, base, dict(REQ, target_ctx=1024, batch_cap=64),
        hbm_bytes=16.91e9)
    full_tok = autosize.kv_bytes_per_token(mcfg, kind="full")
    win_tok = autosize.kv_bytes_per_token(mcfg, kind="window")
    assert (full_tok, win_tok) == (3 * 2048, 9 * 2048)   # 24 KB a token
    assert e.max_batch_size == 64
    # Both pools hold the same tokens (a lane under the window holds the
    # same in each): the budget over 24 KB a token, to a page.
    win = kvc.num_window_pages(mcfg, e)
    assert win == e.num_window_pages and abs(win - e.num_pages) <= 1
    budget = 0.85 * 16.91e9 - autosize.weight_bytes(mcfg) - (512 << 20)
    used = 16 * (e.num_pages * full_tok + win * win_tok)
    assert 0 <= budget - used < 16 * (full_tok + win_tok)
    # 64 lanes of 1024 tokens find room in each; every lane's span would
    # be three times the budget, and served 20-odd lanes.
    assert (win - 1) * 16 // 1024 >= 64 and win - 1 >= span
    assert 64 * span * 16 * win_tok > 2 * budget
    old = autosize.auto_size(mcfg, hbm_bytes=16.91e9, max_pages_per_seq=512,
                             target_ctx=4096, batch_cap=64, window_span=span,
                             written_ahead=1024)
    assert old.max_batch_size < 30
    assert old.num_window_pages == old.max_batch_size * span + 1
    # The engine takes the smaller pool, and admission waits on what is
    # BOOKED there (test_a_full_window_pool_is_a_wait_at_admission).
    assert e.num_window_pages < 64 * span + 1


@pytest.mark.parametrize("model,mp,ctx,cap,want", [
    ("laguna-s-ep8", 832, 4608, 32, (32, 16964, 32 * 98 + 1)),
    ("phi4-mini-flash", 640, 2176, 64, (64, 22104, 64 * 98 + 1)),
])
def test_past_the_window_the_pools_are_what_they_were(model, mp, ctx, cap,
                                                      want):
    """The two accepted configurations with a pool a kind, with their
    cells' flags at the chip's own 16.91e9 bytes: batch, full pages and
    window pages as PR 32 / PR 40 sized them (benchmarks/aot_rehearsal.py's
    figures in .claude/skills/verify), the window pool left to the
    engine's default (every lane's span)."""
    mcfg = PRESETS[model]()
    e = autosize.resolve_sizing(
        mcfg, EngineConfig(max_pages_per_seq=mp),
        dict(REQ, target_ctx=ctx, batch_cap=cap), hbm_bytes=16.91e9)
    assert e.num_window_pages == 0
    assert (e.max_batch_size, e.num_pages,
            kvc.num_window_pages(mcfg, e)) == want


def test_a_full_window_pool_is_a_wait_at_admission():
    """A window pool smaller than every lane's span: the second long
    request waits for the first one's booked pages, and no allocation
    fails under a running sequence."""
    mcfg, _, weights = tiny()
    eng = engine(mcfg, weights, num_window_pages=12, max_batch_size=4,
                 keep_logits=False)
    assert eng.win_allocator.num_pages - 1 == 11 < 4 * eng.window_span
    rng = np.random.default_rng(0)
    a, b = (Sequence(request_id=i, max_new_tokens=8, prompt_tokens=[
        int(t) for t in rng.integers(0, 512, 30)]) for i in range(2))
    assert eng._window_pages_reserved(a) == eng.window_span == 8
    assert eng.can_admit(a)
    eng.prefill(a)
    assert list(eng.pages_booked()) == [10, 8]
    assert not eng.can_admit(b) and eng.can_ever_admit(b)
    while not a.done:
        eng.decode_steps()
    eng.release(a)
    assert eng.can_admit(b)


# ------------------------------------------------------------------ (e)
# sha256 of ``Lowered.as_text()`` of three step programs of each accepted
# preset's tiny twin on the CPU (``lowered_hashes`` below), as the PARENT
# commit (14d131c, PR 42) lowers them. A PR that means to change a step
# program replaces the lines it changes and says so. (PR 44: where one
# round of the expert layer's layout holds every pair the rows come back
# by a gather, kernels/moe_experts.py: tiny-kimi holds 8 of 16 experts,
# all three programs; tiny-laguna's 2-lane decode steps lay out 6 pairs,
# under one tile, and are one round too. PR 45: tiny-laguna holds 4 of
# 16 and its 32-token bucket lays out two rounds of 112 rows for 96
# pairs: the loop stays and each round gathers, ``prefill`` replaced;
# every other line kept. At published widths:
# ``test_which_served_programs_combine_by_gather``,
# tests/test_tpu_compile.py.)
PARENT_HASHES = {
    "tiny-mistral": {"prefill": "538493b173e6e567",
                     "decode_k": "df416240d56df7bf",
                     "decode_1": "d0fe7770e8b5d608"},
    "tiny-qwen2": {"prefill": "b92f31e2c994b1e2",
                   "decode_k": "fbb9479f751c24da",
                   "decode_1": "ff869e77b5cef3b6"},
    "tiny-kimi": {"prefill": "c01b6677958768ab",
                  "decode_k": "a5c9f080d47e8a54",
                  "decode_1": "cea87255482a1752"},
    "tiny-ouro": {"prefill": "a5efedbb3455cbcf",
                  "decode_k": "01c69f2e5d1ac34b",
                  "decode_1": "bed61f0ce62afae7"},
    "tiny-laguna": {"prefill": "5043be5865023ac5",
                    "decode_k": "87051d309842c151",
                    "decode_1": "8ddb155b723260dc"},
    "tiny-sambay": {"prefill": "944404ea719e1fe4",
                    "decode_k": "945a438870f3d480",
                    "decode_1": "b1951983aaba7d9e"},
}


def lowered_hashes(name):
    eng = InferenceEngine(
        PRESETS[name](), EngineConfig(page_size=4, num_pages=64,
                                      max_pages_per_seq=16,
                                      max_batch_size=2,
                                      prefill_buckets=(8, 32),
                                      enable_prefix_cache=False), seed=0)
    key = eng._base_key
    dec = jnp.zeros((2, eng._decode_layout.width), jnp.int32)
    pre = jnp.zeros((1, eng._prefill_layout(32).width), jnp.int32)
    texts = {
        "prefill": eng._prefill_jit.lower(eng.params, eng.kv, key, pre),
        "decode_k": eng._decode_multi_jit.lower(eng.params, eng.kv, key, dec),
        "decode_1": eng._decode_one_jit.lower(eng.params, eng.kv, key, dec)}
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()[:16]
            for k, v in texts.items()}


@pytest.mark.parametrize("name", ["tiny-mistral", "tiny-qwen2", "tiny-kimi",
                                  "tiny-ouro", "tiny-laguna", "tiny-sambay"])
def test_accepted_presets_lower_to_the_parents_step_programs(name):
    assert lowered_hashes(name) == PARENT_HASHES[name]


# ------------------------------------------------------------------ (f)
def test_preset_equals_the_configuration_file():
    cfg, m = config_file(REAL_FILE), PRESETS["smallthinker-21b-pp4"]()
    n = cfg["num_hidden_layers"]
    pairs = [
        (m.n_layers, n), (m.d_model, cfg["hidden_size"]),
        (m.n_heads, cfg["num_attention_heads"]),
        (m.window_n_heads, cfg["num_attention_heads"]),
        (m.n_kv_heads, cfg["num_key_value_heads"]),
        (m.head_dim, cfg["head_dim"]), (m.d_ff, 0),
        (m.vocab_size, cfg["vocab_size"]),
        (m.sliding_window, cfg["sliding_window_size"]),
        (m.moe_d_ff, cfg["moe_ffn_hidden_size"]),
        (m.n_experts, cfg["moe_num_primary_experts"]),
        (m.n_local_experts, cfg["moe_num_primary_experts"]),
        (m.n_experts_per_tok, cfg["moe_num_active_primary_experts"]),
        (m.n_shared_experts, 0), (m.first_k_dense, 0),
        (m.routed_scaling_factor, 1.0),
        (m.norm_topk_prob, cfg["norm_topk_prob"]),
        (m.moe_scoring == "softmax",
         cfg["moe_primary_router_apply_softmax"]),
        (m.norm_eps, cfg["rms_norm_eps"]),
        (m.rope_theta, cfg["rope_theta"]),
        (m.window_rope_theta, cfg["rope_theta"]),
        (m.rope_scaling, cfg["rope_scaling"]),
        (m.max_seq_len, cfg["max_position_embeddings"]),
        (m.tie_embeddings, cfg["tie_word_embeddings"]),
        ([k == "window" for k in m.layer_types],
         [bool(w) for w in cfg["sliding_window_layout"]]),
        ([k not in m.nope_kinds for k in m.layer_types],
         [bool(r) for r in cfg["rope_layout"]]),
        (m.router_input, "attn_norm"), (m.moe_act, "relu"),
    ]
    assert [p for p in pairs if p[0] != p[1]] == []
    # Every number of the catalog row but the depth.
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 52}
    assert len(cfg["rope_layout"]) == len(cfg["sliding_window_layout"]) == 52
    t, tm = config_file(TINY_FILE), PRESETS["tiny-smallthinker"]()
    sz = REF.sizes(t, tm.n_layers)
    assert sz["kinds"] == tm.layer_types
    assert (sz["experts"], sz["top_k"], sz["window"]) == (
        tm.n_experts, tm.n_experts_per_tok, tm.sliding_window)


def test_the_counts_of_the_issue_by_hand():
    """797 MB a layer, 11.12 GB a stage, 24 KB a token; a token multiplies
    through six of a layer's 64 experts."""
    m = PRESETS["smallthinker-21b-pp4"]()
    expert = 3 * 2560 * 768
    layer = (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64 + 2 * 2560
             + 64 * expert)
    assert layer == 398_627_840                       # x 2 B = 797 MB
    total = 12 * layer + 2 * 151936 * 2560 + 2560
    assert smallthinker.param_count(m) == total == 5_561_448_960
    assert autosize.weight_bytes(m) == 2 * total       # 11.12 GB
    assert smallthinker.param_count(m, active=True) == (
        total - 12 * (64 - 6) * expert)
    assert autosize.active_param_count(m) == smallthinker.param_count(m, True)
    assert autosize.kv_bytes_per_token(m) == 24 * 1024
    # The cost model: weights a step reads = what is resident (at 64
    # lanes 63.9 of 64 experts have a row), a pair costs 4 x 28 x 128.
    eng = engine(*tiny()[::2])
    cm = telemetry.StepCostModel.from_engine(eng)
    tm = eng.model_cfg
    assert cm.n_params == smallthinker.param_count(tm, True)
    assert cm.weight_bytes == autosize.weight_bytes(tm)
    assert (cm.n_layers, cm.window_layers, cm.window_heads, cm.window) == (
        2, 6, 8, 8)


def test_the_counters_and_gauges_this_family_fills():
    mcfg, _, weights = tiny()
    eng = engine(mcfg, weights, keep_logits=False)
    s = Sequence(request_id=0, max_new_tokens=6,
                 prompt_tokens=list(range(3, 40)))
    def scrape():
        return {x.name: x.collect_value()
                for x in eng.telemetry.registry.collect()
                if not x.labels and hasattr(x, "collect_value")}

    short = Sequence(request_id=1, max_new_tokens=2, prompt_tokens=[1, 2])
    eng.prefill(s)
    assert eng.can_admit(short)             # an admission pass
    mid = scrape()
    while not s.done:
        eng.decode_steps()
    assert eng.can_admit(short)
    m = scrape()
    pairs = float(m["tpu_inf_moe_computed_pairs_total"])
    rows = float(m["tpu_inf_moe_tile_rows_total"])
    assert pairs == float(m["tpu_inf_moe_local_pairs_total"]) > 0
    assert float(m["tpu_inf_moe_dropped_pairs_total"]) == 0
    # Tiles of 16 rows for 8 experts with a handful of pairs each.
    assert rows > pairs and rows % 16 == 0
    assert float(m["tpu_inf_moe_tokens_total"]) * 3 == pairs
    assert float(m["tpu_inf_moe_decode_layer_steps_total"]) > 0
    # What admission held back at its last pass for a bound sequence,
    # taken or not yet; nothing once it has ended. (The scrape reads what
    # the engine loop stored: it never walks the sequences itself.)
    assert mid["tpu_inf_kv_window_pages_booked"] == \
        eng._window_pages_reserved(s) > mid["tpu_inf_kv_window_pages_in_use"]
    assert mid["tpu_inf_kv_full_pages_booked"] == eng._pages_reserved(s)
    assert m["tpu_inf_kv_window_pages_booked"] == 0
    assert m["tpu_inf_kv_window_pages_booked_peak"] == \
        mid["tpu_inf_kv_window_pages_booked"] == 8
    assert float(m["tpu_inf_weight_stacks_transposed"]) == 6
    # Every expert held: every step program warm-up builds sums its
    # expert layers' rows by gather (nothing warmed yet: 0).
    assert m["tpu_inf_moe_gather_combine_programs"] == 0
    eng.warmup()
    assert scrape()["tpu_inf_moe_gather_combine_programs"] == \
        eng.warmup_graphs > 0


# Ladder rungs and prefill buckets 'auto' gives each expert cell
# (benchmarks/aot_rehearsal.py's flags, SKILL.md); a prefill program
# batches 1 or 4 prompts. -> the rows of the programs that gather.
BUCKET_ROWS = sorted({p * b for p in (1, 4)
                      for b in (64, 128, 256, 512, 1024)})


@pytest.mark.parametrize("model,rungs,bucket_rows", [
    ("kimi-k2-ep32", (8, 16, 32), []),
    ("laguna-s-ep8", (8, 16, 32), [64, 128]),
    ("smallthinker-21b-pp4", (8, 16, 32, 64), BUCKET_ROWS)])
def test_which_served_programs_combine_by_gather(model, rungs, bucket_rows):
    """At published widths, by ``T x k`` against a round's rows: EVERY
    decode rung gathers in all three (Kimi, 12 of 384 held: 64-256 pairs
    against rounds of 208 rows; Laguna, 32 of 256: 80-320 against
    544-592; SmallThinker, all 64: one round). A prefill program's rows:
    none of Kimi's (a round holds 224 rows of a 64-token bucket's 512
    pairs, 896 of a chunk's 8192), of Laguna's the two smallest (672
    rows for 640 pairs, 832 for 1280; from 256 rows on a round holds
    0.45 of the pairs, 0.35 of four chunks'), all of SmallThinker's."""
    mcfg = PRESETS[model]()
    assert all(dsv3.combines_by_gather(mcfg, r) for r in rungs)
    assert [r for r in BUCKET_ROWS
            if dsv3.combines_by_gather(mcfg, r)] == bucket_rows


def test_validate_and_what_is_refused():
    m = PRESETS["tiny-smallthinker"]()
    m.validate()
    for bad in (dict(router_input="x"), dict(moe_act="gelu"),
                dict(nope_kinds=("ssm",))):
        with pytest.raises(AssertionError):
            dataclasses.replace(m, **bad).validate()
    eng = engine(*tiny()[::2])
    for call, what in ((lambda: eng.embed_many([[1, 2, 3]]), "embed_many"),
                       (eng.check_numerics, "check_numerics")):
        with pytest.raises(ValueError, match=f"does not support {what}"):
            call()
    for kw, said in ((dict(kv_quant="int8"), "kv_quant"),
                     (dict(host_cache_pages=8), "host KV tier"),
                     (dict(quant="int4"), "quant='int4'"),
                     (dict(role="decode"), "role="),
                     (dict(num_speculative_tokens=2),
                      "speculative")):
        with pytest.raises(ValueError, match=said):
            engine(m, **dict(kw, keep_logits=False))
