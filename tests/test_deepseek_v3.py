"""The DeepSeek-V3 / Kimi-K2 family on the CPU at tiny widths with the
real structure (tiny-kimi: 1 dense + 2 expert layers, 16 experts top-4 of
which a rank holds 8, rope / nope split, YaRN on), seeded random weights:

(a) the engine (chunked prefill, batched fused-K decode, a prefix-cache
    hit over latent pages) against the in-repo plain reference, logits;
(b) the latent-attention kernels in interpret mode against the dense
    form, with a cached prefix, at layer > 0 of the stacked pool;
(c) the shares add up: the routed parts of all expert-parallel ranks plus
    the shared expert once equal the uncut reference's layer output;
(d) no pair is dropped under a routing skewed onto one expert; where ONE
    round of the layout holds every pair (half or more of the experts
    held) the rows come back by a gather and a sum over k, equal to the
    scatter-add under the loop, and the lowered program says which ran;
(e) the presets' latent / expert / YaRN fields equal the keys of the
    configuration files;
(f) what the family does not support is refused at construction.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.config import PRESETS, EngineConfig, YarnScaling
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.kernels import mla_attention as mla
from tpu_inference.kernels import moe_experts
from tpu_inference.models import deepseek_v3 as dsv3

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    """A file of bench/ as a module, without putting bench/ on sys.path
    (its ``tests`` directory would shadow this one's ``tests.conftest``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.splitext(os.path.basename(path))[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(REPO, "bench", "references", "deepseek_v3.py"))


def config_file(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


TINY_FILE = "bench/tests/rehearsal/configs/tiny-kimi.json"


@pytest.fixture(scope="module")
def tiny():
    cfg = config_file(TINY_FILE)
    sz = REF.sizes(cfg, 3)
    weights = jax.tree.map(lambda a: a.astype(jnp.float32),
                           REF.make_weights(sz, 5))
    return PRESETS["tiny-kimi"](), sz, weights


def engine(mcfg, weights, **kw):
    ecfg = EngineConfig(**{**dict(page_size=16, num_pages=128,
                                  max_pages_per_seq=24, max_batch_size=4,
                                  prefill_buckets=(32, 64)), **kw})
    return InferenceEngine(mcfg, ecfg, params=weights,
                           pallas_interpret=kw.get("attn_backend")
                           == "pallas")


def probe_logits(eng, seq, p):
    """Logits at position p off the pool the serving graphs wrote (as
    bench/parity.py's probe)."""
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


# ------------------------------------------------------------------ (a)
@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_engine_matches_the_reference(tiny, backend):
    mcfg, sz, weights = tiny
    eng = engine(mcfg, weights, attn_backend=backend)
    rng = np.random.default_rng(3)
    shared = [int(t) for t in rng.integers(0, 512, 48)]
    prompts = [shared + [int(t) for t in rng.integers(0, 512, n)]
               for n in (20, 100)]          # 68: two chunks; 148: three
    seqs = [Sequence(request_id=i, prompt_tokens=p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    for s in seqs:
        eng.prefill(s)
    while any(len(s.generated) < 5 for s in seqs):
        eng.decode_steps()                  # both lanes, fused K

    def check(s):
        n = len(s.prompt_tokens)
        at = [n - 1, n + 3]
        ref = REF.logits(weights, sz, (s.prompt_tokens + s.generated)[:n + 4],
                         at)
        for p, r in zip(at, ref):
            err = (probe_logits(eng, s, p) - r) / np.std(r)
            assert np.sqrt(np.mean(err ** 2)) < 1e-4, (backend, n, p)
        # Greedy tokens are the reference's argmax.
        full = REF.logits(weights, sz, (s.prompt_tokens + s.generated)[:n + 4],
                          list(range(n - 1, n + 4)))
        assert [int(np.argmax(r)) for r in full] == s.generated[:5]

    for s in seqs:
        check(s)
        eng.release(s)
    # A new stream behind the shared prefix: its pages come from the cache.
    hit = Sequence(request_id=9, max_new_tokens=8, prompt_tokens=shared + [
        int(t) for t in rng.integers(0, 512, 30)])
    eng.prefill(hit)
    assert hit.cached_tokens >= 48
    while len(hit.generated) < 5:
        eng.decode_steps()
    check(hit)
    # Routing counts came out with the tokens; nothing dropped.
    st = dict(zip(dsv3.MOE_STATS, eng.aux_stats))
    assert st["tokens"] > 0 and st["local_pairs"] > 0
    assert st["local_pairs"] == st["computed_pairs"]
    assert eng.aux_stats[len(dsv3.MOE_STATS):].sum() == st["local_pairs"]


# ------------------------------------------------------------------ (b)
@pytest.mark.parametrize("mp,block_bytes,block", [
    (8, 3 * 16 * 256 * 4, 3),       # 3 pages a block: does not divide mp
    (8, 1 << 30, 8),                # the rule stops at mp: one block a lane
    (21, 16 * 16 * 256 * 4, 16),    # 16-page blocks, a partial second one
])
def test_latent_kernels_match_the_dense_form(monkeypatch, mp, block_bytes,
                                             block):
    """Both entry points on the hand-copied page feed (interpret mode)
    against the dense form. The block size comes from page bytes and
    ``mp`` (``_block_pages``); the cases make the rule pick 3, mp and 16
    pages a block."""
    L, P, pg, R, Dr, H, W = 3, 200, 16, 128, 16, 4, 256
    monkeypatch.setattr(mla, "BLOCK_BYTES", block_bytes)
    assert mla._block_pages(pg, pg * W * 4, H, mp) == block
    k = jax.random.split(jax.random.PRNGKey(mp), 3)
    pool = jax.random.normal(k[0], (L, P, pg, W), jnp.float32)
    B, S = 2, 32
    perm = np.random.RandomState(0).permutation(np.arange(1, P - 1))

    def table(rows, lens):
        """Scattered pages; every position past a lane's last page names
        the NaN page or a page that does not exist: never read."""
        bt = perm[:rows * mp].reshape(rows, mp).copy()
        for i, n in enumerate(lens):
            bt[i, -(-n // pg):] = [P - 1, P + 7][i % 2]
        return jnp.asarray(bt, jnp.int32)

    poisoned = pool.at[:, P - 1].set(jnp.nan)
    q = jax.random.normal(k[1], (B, S, H, R + Dr))
    # Sequence 0: a 32-token chunk behind a 40-token cached prefix;
    # sequence 1: 20 valid tokens of a padded chunk behind 3.
    q_off = jnp.asarray([40, 3], jnp.int32)
    kv_len = q_off + jnp.asarray([32, 20])
    bt = table(B, kv_len.tolist())
    for layer in (1, 2):
        want = mla.mla_attention_dense(q, pool, layer, jnp.minimum(bt, P - 2),
                                       kv_len, q_off, rank=R, scale=0.1)
        got = mla.mla_prefill_attention(
            q, poisoned, layer, bt, kv_len, q_off, rank=R, scale=0.1,
            block_q=8, interpret=True)
        np.testing.assert_allclose(got[0], want[0], atol=2e-5)
        np.testing.assert_allclose(got[1, :20], want[1, :20], atol=2e-5)
    # Decode, lanes of unequal length: an idle lane (kv_len 0) first,
    # between live ones and last, a partial last block, exactly one
    # block, one token, a lane that fills its block table.
    lens = [0, 100, 0, block * pg, 1, mp * pg, 17, 0]
    kv_len = jnp.asarray(lens, jnp.int32)
    bt = table(len(lens), lens)
    qd = jax.random.normal(k[2], (len(lens), H, R + Dr))
    for layer in (0, 2):
        want = mla.mla_attention_dense(
            qd[:, None], pool, layer, jnp.minimum(bt, P - 2), kv_len,
            kv_len - 1, rank=R, scale=0.1)[:, 0]
        got = mla.mla_decode_attention(qd, poisoned, layer, bt, kv_len,
                                       rank=R, scale=0.1, interpret=True)
        live = np.asarray(kv_len) > 0
        np.testing.assert_allclose(got[live], want[live], atol=2e-5)
        # A lane that read nothing gives 0, not NaN.
        assert not np.asarray(got[~live]).any()


def test_absorbed_attention_is_the_expanded_attention(tiny):
    """forward() with the latent contract's dense attention equals the
    reference's expanded attention on a whole stream (no cache)."""
    mcfg, sz, weights = tiny
    toks = np.random.default_rng(1).integers(0, 512, 70)
    got, _ = dsv3.forward(weights, mcfg, jnp.asarray(toks)[None],
                          jnp.arange(70)[None], None,
                          dsv3.make_dense_attn(mcfg))
    want = REF.logits(weights, sz, list(toks), [10, 69])
    np.testing.assert_allclose(np.asarray(got[0])[[10, 69]], want,
                               atol=5e-5)


# ------------------------------------------------------------------ (c)
def test_the_shares_add_up(tiny):
    """One expert layer: sum over ranks of (routed part of the rank) +
    shared expert once == the uncut layer (every expert on one rank)."""
    mcfg, sz, weights = tiny
    ep = mcfg.ep_size
    full_sz = dict(sz, held=sz["experts"], first_held=0)
    full = REF.make_weights(full_sz, 11)
    lp = jax.tree.map(lambda a: a[0].astype(jnp.float32), full["moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 37, mcfg.d_model))

    def routed_plus_shared(cfg, lp_rank):
        experts = tuple(lp_rank[k][None] for k in ("we_gate", "we_up",
                                                   "we_down"))
        out, stats = dsv3.moe_ffn(cfg, lp_rank, experts, 0, h,
                                  dsv3.make_dense_attn(cfg))
        return out[0], stats

    uncut, _ = routed_plus_shared(
        dataclasses.replace(mcfg, ep_size=1, ep_rank=0), lp)
    shared = dsv3.swiglu(h[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    held = mcfg.n_local_experts
    parts, pairs = [], 0
    for rank in range(ep):
        lp_rank = dict(lp, **{k: lp[k][rank * held:(rank + 1) * held]
                              for k in ("we_gate", "we_up", "we_down")})
        out, stats = routed_plus_shared(
            dataclasses.replace(mcfg, ep_rank=rank), lp_rank)
        parts.append(out - shared)
        pairs += int(stats[1])
    assert pairs == 37 * mcfg.n_experts_per_tok     # every pair somewhere
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-5)
    # ... and the uncut layer is the reference's layer.
    x = jnp.zeros((512, mcfg.d_model)).at[:37].set(h[0])
    ref_sz = dict(full_sz, layers=1, dense_layers=0)
    sc = jax.nn.sigmoid(x @ lp["w_router"])
    _, top = jax.lax.top_k(sc + lp["router_bias"][None], sz["top_k"])
    g = jnp.take_along_axis(sc, top, 1)
    g = g / g.sum(1, keepdims=True) * ref_sz["route_scale"]
    want = REF._swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    for e in range(sz["experts"]):
        ge = jnp.where(top == e, g, 0.0).sum(1)
        want = want + ge[:, None] * REF._swiglu(
            x, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e])
    np.testing.assert_allclose(uncut, want[:37], atol=1e-5)


def test_rows_without_a_token_route_nowhere(tiny):
    """A padded bucket's tail and an idle decode lane (``attn.valid``
    False) send no pair to an expert and count in no statistic; the rows
    that hold a token get what they got."""
    mcfg, _, weights = tiny
    lp = jax.tree.map(lambda a: a[0], weights["moe"])
    experts = tuple(lp[k][None] for k in ("we_gate", "we_up", "we_down"))
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 9, mcfg.d_model))
    valid = jnp.arange(9)[None, :] < jnp.asarray([[9], [4]])

    def run(valid):
        attn = dsv3.make_dense_attn(mcfg)
        attn.valid = valid
        return dsv3.moe_ffn(mcfg, lp, experts, 0, h, attn)

    full, st_full = run(None)
    part, st_part = run(valid)
    at = dict(zip(dsv3.MOE_STATS, range(len(dsv3.MOE_STATS))))
    assert int(st_full[at["tokens"]]) == 18
    assert int(st_part[at["tokens"]]) == 13
    assert int(st_part[at["local_pairs"]]) < int(st_full[at["local_pairs"]])
    assert int(st_part[at["local_pairs"]]) == int(
        st_part[at["computed_pairs"]])
    np.testing.assert_allclose(part[valid], full[valid], atol=1e-6)
    # A row without a token keeps only the shared expert's output.
    shared = dsv3.swiglu(h[1, 5:], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    np.testing.assert_allclose(part[1, 5:], shared, atol=1e-6)


def test_the_references_bias_decides_the_held_experts(tiny):
    """bench/references/deepseek_v3.py make_weights: per layer the same
    HELD_CHOSEN held experts are among every token's k and no other held
    expert ever is, whatever the scores; the rest of the k are the
    token's own."""
    mcfg, sz, weights = tiny
    chosen = min(REF.HELD_CHOSEN, sz["top_k"] // 2, sz["held"])
    bias = np.asarray(weights["moe"]["router_bias"])
    held = slice(sz["first_held"], sz["first_held"] + sz["held"])
    assert ((bias[:, held] == REF.HELD_MARGIN).sum(1) == chosen).all()
    assert ((bias[:, held] == -REF.HELD_MARGIN).sum(1)
            == sz["held"] - chosen).all()
    assert np.abs(np.delete(bias, np.r_[held], axis=1)).max() < 0.1
    lp = jax.tree.map(lambda a: a[0], weights["moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (200, mcfg.d_model)) * 3
    top, gates = dsv3.route(mcfg, lp, x)
    top = np.asarray(top)
    is_held = (top >= sz["first_held"]) & (top < sz["first_held"] + sz["held"])
    assert (is_held.sum(1) == chosen).all()
    assert len(np.unique(top[is_held])) == chosen
    assert len(np.unique(top[~is_held])) > sz["top_k"] - chosen   # tokens differ
    assert np.asarray(gates).std() > 0.05


# ------------------------------------------------------------------ (d)
@pytest.mark.parametrize("pallas", [False, True])
def test_no_pair_is_dropped_under_skew(pallas):
    """Every token sends all its k choices to held experts, 3/4 of them to
    expert 0: far over a round's rows, so several rounds run."""
    t, k, held, d, f = 96, 4, 8, 128, 128
    rng = np.random.default_rng(0)
    top = np.stack([np.zeros(t), np.zeros(t) + (np.arange(t) % 2),
                    np.zeros(t), rng.integers(1, held, t)], 1).astype(
                        np.int32)
    top[:, 2] = np.where(np.arange(t) % 4 == 0, 5, 0)
    gates = rng.random((t, k)).astype(np.float32)
    expected = t * k * held / 64           # as if 64 experts shared them
    groups = moe_experts.group_pairs(jnp.asarray(top), jnp.asarray(gates),
                                     held, expected)
    assert int(groups.counts.sum()) == t * k
    # More tiles in use than a round holds: several rounds run.
    assert int(groups.n_tiles) * groups.tm > groups.round_rows
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (t, d))
    wg, wu = (0.1 * jax.random.normal(kk, (2, held, d, f)) for kk in ks[1:3])
    wd = 0.1 * jax.random.normal(ks[3], (2, held, f, d))
    y, done = moe_experts.grouped_experts(x, groups, wg, wu, wd, 1,
                                          pallas=pallas, interpret=True)
    assert int(done) == t * k
    want = np.zeros((t, d), np.float32)
    for i in range(t):
        for j in range(k):
            e = top[i, j]
            hh = jax.nn.silu(x[i] @ wg[1, e]) * (x[i] @ wu[1, e])
            want[i] += gates[i, j] * np.asarray(hh @ wd[1, e])
    np.testing.assert_allclose(y, want, atol=2e-4)


def _one_routing(t, k, held, n_experts, d=32, f=16, seed=0, idle=True):
    """Random activations, stacked expert weights (layer 1 is used),
    top-k of ``n_experts`` of which the first ``held`` are here, every
    third row idle."""
    ks = jax.random.split(jax.random.PRNGKey(seed + t), 6)
    x = jax.random.normal(ks[0], (t, d), jnp.float32)
    wg, wu = (0.3 * jax.random.normal(kk, (2, held, d, f), jnp.float32)
              for kk in ks[1:3])
    wd = 0.3 * jax.random.normal(ks[3], (2, held, f, d), jnp.float32)
    top = jnp.argsort(-jax.random.normal(ks[4], (t, n_experts)),
                      axis=1)[:, :k]
    gates = jax.nn.softmax(jax.random.normal(ks[5], (t, k)), axis=-1)
    valid = (jnp.arange(t) % 3 != 2)[:, None] | (not idle)
    top_local = jnp.where(valid & (top < held), top, held).astype(jnp.int32)
    return x, (wg, wu, wd), top_local, gates, valid


def _plain_loop(x, weights, top_local, gates, act):
    """sum over held experts of (the token's gate on it) * E_e(x), in
    float64: what the grouped layer computes, with no layout at all."""
    wg, wu, wd = (np.asarray(w[1], np.float64) for w in weights)
    xs, want = np.asarray(x, np.float64), 0.0
    for e in range(wg.shape[0]):
        ge = np.where(np.asarray(top_local) == e, np.asarray(gates),
                      0.0).sum(1)
        hid = xs @ wg[e]
        hid = (np.maximum(hid, 0) if act == "relu"
               else hid / (1 + np.exp(-hid))) * (xs @ wu[e])
        want = want + ge[:, None] * (hid @ wd[e])
    return want


def by_scatter(groups):
    """The same layout, combined as a layout that does not gather is: the
    loop (one trip where one round is laid out) and the scatter-add."""
    return groups._replace(pair_row=None, pair_gate=None)


def _piled(t, k, held):
    """Every pair held, all on expert 0 (a token's pairs need not differ
    for the layout) but the last ``held - 1`` pairs, which go to an
    expert each: the most tiles T x k pairs can take."""
    top = np.zeros(t * k, np.int32)
    top[1 - held:] = np.arange(1, held)
    return jnp.asarray(top.reshape(t, k))


# (held, of, top-k, tokens, routing, rounds laid out, rounds that run):
# SmallThinker's stage at a decode step and at a chunk (one round); the
# decode rungs of Laguna's chip (32 of 256, top-10: two rounds of 37
# tiles) and of Kimi's (12 of 384, top-8: two or three rounds of 13), a
# spread routing, which fills one round, and a piled one, which runs
# every round laid out.
@pytest.mark.parametrize("held,n_experts,k,t,routing,rounds,ran", [
    (64, 64, 6, 3, "spread", 1, 1), (64, 64, 6, 64, "spread", 1, 1),
    (64, 64, 6, 1024, "spread", 1, 1),
    (32, 256, 10, 32, "spread", 2, 1), (32, 256, 10, 32, "piled", 2, 2),
    (32, 256, 10, 8, "piled", 2, 2),
    (12, 384, 8, 32, "spread", 3, 1), (12, 384, 8, 32, "piled", 3, 3),
    (12, 384, 8, 16, "piled", 2, 2)])
@pytest.mark.parametrize("pallas", [False, True], ids=["dense", "pallas"])
@pytest.mark.parametrize("act", ["relu", "silu"])
def test_the_gather_combine_is_the_scatter_combine(
        act, pallas, held, n_experts, k, t, routing, rounds, ran):
    """Summing each token's gathered rows, round by round where several
    rounds are laid out, equals scatter-adding each round's rows to their
    tokens: the same pairs computed, a token whose pairs lie in two
    rounds gets each once."""
    x, w, top_local, gates, valid = _one_routing(
        t, k, held, n_experts, idle=routing == "spread")
    if routing == "piled":
        top_local = _piled(t, k, held)
    expected = t * k * held / n_experts
    assert moe_experts.combines_by_gather(t, k, held, expected)
    groups = moe_experts.group_pairs(top_local, gates, held, expected)
    rr = groups.round_rows
    assert groups.row_token.shape[0] == rounds * rr
    assert -(-int(groups.n_tiles) * groups.tm // rr) == ran
    in_round = np.asarray(groups.pair_row) // rr
    two = [i for i, r in enumerate(in_round)
           if len(set(r[np.asarray(top_local[i]) < held])) > 1]
    assert bool(two) == (ran > 1), "a token's pairs lie in two rounds"
    kw = dict(pallas=pallas, interpret=True, act=act)
    y, done = moe_experts.grouped_experts(x, groups, *w, 1, **kw)
    y2, done2 = moe_experts.grouped_experts(x, by_scatter(groups), *w, 1,
                                            **kw)
    assert y.dtype == y2.dtype == jnp.float32
    assert int(done) == int(done2) == int((top_local < held).sum())
    scale = float(np.std(np.asarray(y2)))
    assert np.abs(np.asarray(y) - np.asarray(y2)).max() < 1e-5 * scale
    want = _plain_loop(x, w, top_local, gates, act)
    assert np.abs(np.asarray(y) - want).max() < 1e-3 * np.std(want)
    # A row that holds no token has no row in the layout and adds 0.0.
    assert not np.asarray(y)[~np.asarray(valid)[:, 0]].any()


@pytest.mark.parametrize("preset,b,s", [
    ("tiny-smallthinker", 3, 1), ("tiny-smallthinker", 1, 64),
    ("tiny-kimi", 3, 1), ("tiny-kimi", 1, 64), ("tiny-laguna", 1, 32)])
def test_a_row_without_a_token_adds_nothing_to_the_gather(preset, b, s):
    """An idle decode lane (b x 1) and a padded bucket's tail (1 x s)
    through ``moe_ffn`` where the combine gathers: the routed part of such
    a row is exactly 0 and it counts in no statistic (tiny-kimi holds 8
    of 16: half of the other rows' pairs have no row here either;
    tiny-laguna 4 of 16: its bucket gathers inside the rounds' loop)."""
    mcfg = PRESETS[preset]()
    assert dsv3.combines_by_gather(mcfg, b * s)
    held, d, f = mcfg.n_local_experts, mcfg.d_model, mcfg.moe_d_ff
    ks = jax.random.split(jax.random.PRNGKey(b * s), 5)
    lp = {"w_router": jax.random.normal(ks[0], (d, mcfg.n_experts))}
    experts = tuple(0.3 * jax.random.normal(kk, shape) for kk, shape in zip(
        ks[1:4], [(1, held, d, f), (1, held, d, f), (1, held, f, d)]))
    h = jax.random.normal(ks[4], (b, s, d))
    valid = (jnp.arange(b * s) % 3 != 1).reshape(b, s)

    def attn(*a):
        raise AssertionError("the expert layer calls no attention")

    every, _ = dsv3.moe_ffn(mcfg, lp, experts, 0, h, attn)
    attn.valid = valid
    out, stats = dsv3.moe_ffn(mcfg, lp, experts, 0, h, attn)
    assert not np.asarray(out)[~np.asarray(valid)].any()
    assert np.asarray(every)[~np.asarray(valid)].any()
    np.testing.assert_allclose(out[valid], every[valid], atol=1e-5)
    at = dict(zip(dsv3.MOE_STATS, range(len(dsv3.MOE_STATS))))
    n = int(valid.sum())
    assert int(stats[at["tokens"]]) == n
    assert int(stats[at["computed_pairs"]]) == int(
        stats[at["local_pairs"]]) <= n * mcfg.n_experts_per_tok
    top, _ = dsv3.route(mcfg, lp, h.reshape(b * s, d))
    here = (np.asarray(top) < held) & np.asarray(valid).reshape(-1, 1)
    assert int(stats[at["local_pairs"]]) == here.sum()


@pytest.mark.parametrize("pallas", [False, True], ids=["dense", "pallas"])
@pytest.mark.parametrize("t", [64, 1024])
def test_every_pair_on_one_expert_is_still_one_round(pallas, t):
    """The skew a served random init shows (8x max over mean), taken to
    its end: all T x 6 pairs on expert 5 of 64. The one round is sized
    for the worst case, so it holds them, and the gather is exact."""
    e, k = 64, 6
    x, w, _, gates, _ = _one_routing(t, k, e, e, idle=False)
    top_local = jnp.full((t, k), 5, jnp.int32)
    groups = moe_experts.group_pairs(top_local, gates, e, t * k)
    assert groups.pair_row is not None
    assert int(groups.counts[5]) == t * k == int(groups.counts.sum())
    assert int(groups.n_tiles) * groups.tm <= groups.round_rows
    y, done = moe_experts.grouped_experts(x, groups, *w, 1, pallas=pallas,
                                          interpret=True, act="relu")
    assert int(done) == t * k
    want = _plain_loop(x, w, top_local, gates, "relu")
    assert np.abs(np.asarray(y) - want).max() < 1e-3 * np.std(want)


def _float_scatters(text):
    """Result types of the float scatters in a lowered program (the
    layout's own scatter writes int32 row indices and stays)."""
    import re
    return [r for r in re.findall(
        r'"stablehlo\.scatter".*?\) -> tensor<([^>]*)>', text, re.S)
        if not r.endswith("i32")]


# (held, of, top-k, tokens): two made for the test, a sixteenth or less
# held at a bucket; tiny-laguna's expert layer at its 32-token bucket and
# one more of several rounds; tiny-kimi's (half held, the boundary) and
# tiny-smallthinker's at a bucket and at a decode rung.
@pytest.mark.parametrize("held,n_experts,k,t,loops,gathers", [
    (2, 64, 4, 256, True, False), (1, 16, 4, 64, True, False),
    (4, 16, 3, 32, True, True), (3, 16, 4, 64, True, True),
    (8, 16, 4, 32, False, True), (8, 16, 4, 2, False, True),
    (8, 8, 3, 32, False, True), (8, 8, 3, 2, False, True)])
def test_the_lowered_expert_layer_says_which_combine_runs(
        held, n_experts, k, t, loops, gathers):
    """A round far smaller than ``T x k`` (a few experts held, a bucket
    of rows): the predicate is false, the program holds the ``while`` and
    the float scatter-add. Several rounds of which one holds about ``T x
    k`` rows or more: the ``while`` stays and NO float scatter is in it.
    Half or more held: one round, no ``while``, no float scatter. The
    output is the plain loop over the held experts in each."""
    expected = t * k * held / n_experts
    assert moe_experts.combines_by_gather(t, k, held, expected) == gathers
    x, w, top_local, gates, _ = _one_routing(t, k, held, n_experts)

    def layer(x, top_local, gates):
        groups = moe_experts.group_pairs(top_local, gates, held, expected)
        return moe_experts.grouped_experts(x, groups, *w, 1, pallas=False)

    text = jax.jit(layer).lower(x, top_local, gates).as_text()
    assert ("stablehlo.while" in text) == loops
    assert bool(_float_scatters(text)) == (not gathers)
    y, done = layer(x, top_local, gates)
    assert int(done) == int((np.asarray(top_local) < held).sum())
    want = _plain_loop(x, w, top_local, gates, "silu")
    assert np.abs(np.asarray(y) - want).max() < 1e-3 * np.std(want)


def test_pairs_of_absent_experts_are_left_out():
    top = jnp.asarray([[8, 3], [8, 8], [1, 8]], jnp.int32)   # 8 = not here
    groups = moe_experts.group_pairs(top, jnp.ones((3, 2), jnp.float32), 8,
                                     1.0)
    assert groups.counts.tolist() == [0, 1, 0, 1, 0, 0, 0, 0]
    assert int(groups.n_tiles) == 2
    rows = np.asarray(groups.row_token)
    assert sorted(rows[rows < 3].tolist()) == [0, 2]


# ------------------------------------------------------------------ (e)
@pytest.mark.parametrize("preset,path", [
    ("kimi-k2-ep32", "bench/configs/kimi-k2-ep32-bf16.json"),
    ("tiny-kimi", TINY_FILE)])
def test_preset_equals_the_configuration_file(preset, path):
    m, f = PRESETS[preset](), config_file(path)
    rs = f["rope_scaling"]
    pairs = {
        "q_lora_rank": m.q_lora_rank, "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
        "first_k_dense_replace": m.first_k_dense,
        "moe_intermediate_size": m.moe_d_ff,
        "n_shared_experts": m.n_shared_experts,
        "n_routed_experts": m.n_local_experts,
        "num_experts_per_tok": m.n_experts_per_tok,
        "routed_scaling_factor": m.routed_scaling_factor,
        "norm_topk_prob": m.norm_topk_prob, "scoring_func": m.moe_scoring,
        "hidden_size": m.d_model, "intermediate_size": m.d_ff,
        "num_hidden_layers": m.n_layers, "vocab_size": m.vocab_size,
        "num_attention_heads": m.n_heads, "rope_theta": m.rope_theta,
        "rms_norm_eps": m.norm_eps, "n_group": 1, "topk_group": 1,
    }
    assert {k: f[k] for k in pairs} == pairs
    assert f["published"]["n_routed_experts"] == m.n_experts
    assert f["deployment"]["expert_parallel"] == m.ep_size
    assert f["deployment"]["rank"] == m.ep_rank
    assert rs["type"] == "yarn" and m.rope_scaling == YarnScaling(
        factor=rs["factor"],
        original_max_len=rs["original_max_position_embeddings"],
        beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
        mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"])
    sz = REF.sizes(f, f["num_hidden_layers"])
    shapes = jax.tree.map(lambda s: s, REF._shapes(sz),
                          is_leaf=lambda x: isinstance(x, tuple))
    assert shapes == dsv3.param_shapes(m)


def test_the_cut_is_the_stated_one():
    f = config_file("bench/configs/kimi-k2-ep32-bf16.json")
    assert f["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert f["published"] == {"num_hidden_layers": 61,
                              "n_routed_experts": 384, "vocab_size": 163840}
    assert (f["published"]["n_routed_experts"]
            == f["n_routed_experts"] * f["deployment"]["expert_parallel"])
    assert (f["published"]["vocab_size"]
            == f["vocab_size"] * f["deployment"]["vocab_shards"])
    from tpu_inference.engine import autosize
    m = PRESETS["kimi-k2-ep32"]()
    assert autosize.weight_bytes(m) == pytest.approx(9.70e9, rel=0.005)
    assert autosize.kv_bytes_per_token(m) == 7 * 640 * 2
    assert autosize.active_param_count(m) < autosize.estimate_param_count(m)


def test_yarn_frequencies_and_scale():
    from tpu_inference.models.common import rope_frequencies
    m = PRESETS["kimi-k2-ep32"]()
    inv = np.asarray(rope_frequencies(64, m.rope_theta, m.rope_scaling))
    plain = np.asarray(rope_frequencies(64, m.rope_theta, None))
    np.testing.assert_allclose(inv[:20], plain[:20], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], plain[20:] / 32, rtol=1e-6)
    assert dsv3.softmax_scale(m) == pytest.approx(0.130861, rel=1e-5)
    f = config_file("bench/configs/kimi-k2-ep32-bf16.json")
    np.testing.assert_allclose(REF._yarn_inv_freq(REF.sizes(f, 7)), inv,
                               rtol=1e-6)


# ------------------------------------------------------------------ (f)
@pytest.mark.parametrize("kw,needle", [
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(num_speculative_tokens=3), "speculative"),
    (dict(host_cache_pages=8), "host KV tier"),
    (dict(quant="int4"), "int4"),
    (dict(role="prefill"), "role"),
])
def test_unsupported_is_refused_at_construction(kw, needle):
    with pytest.raises(ValueError, match=needle):
        InferenceEngine(PRESETS["tiny-kimi"](),
                        EngineConfig(num_pages=32, max_pages_per_seq=8, **kw))


def test_tp_is_refused_at_construction():
    from tpu_inference.config import ParallelConfig
    from tpu_inference.parallel.mesh import build_mesh
    mesh = build_mesh(ParallelConfig(tp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="tp / sp"):
        InferenceEngine(PRESETS["tiny-kimi"](),
                        EngineConfig(num_pages=32, max_pages_per_seq=8),
                        mesh=mesh)


def test_int8_weights_run_and_differ(tiny):
    """The parity control's path: every QUANT_KEYS leaf int8, the grouped
    kernels widening the codes; close to, and not equal to, float32."""
    mcfg, sz, weights = tiny
    toks = [int(t) for t in np.random.default_rng(4).integers(0, 512, 40)]
    out = {}
    for quant in ("none", "int8"):
        eng = engine(mcfg, weights, quant=quant, attn_backend="pallas")
        s = Sequence(request_id=0, prompt_tokens=toks, max_new_tokens=4)
        eng.prefill(s)
        out[quant] = probe_logits(eng, s, 39)
    err = (out["int8"] - out["none"]) / np.std(out["none"])
    assert 1e-3 < np.sqrt(np.mean(err ** 2)) < 0.1


# -------------------------------------------------- the comparison's control
@pytest.fixture(scope="module")
def planted():
    """bench/planted_fault.py on the tiny configuration: parity.py's run
    of one seed with each fault planted in the routed-expert path."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "planted_fault.py"),
         "--manifest", os.path.join(REPO, "bench", "tests", "rehearsal",
                                    "BENCHMARK_kimi.json"),
         "--workload", "tiny-kimi_tiny-doc-reask", "--seeds", "2147483700"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    return p.returncode, {ln["fault"]: ln for ln in lines if "fault" in ln}


@pytest.mark.parametrize("fault", ["routed_zero", "next_expert",
                                   "first_layer", "gates_doubled"])
def test_a_planted_routed_fault_reads_not_correct(planted, fault):
    """The comparison that decides ``correct`` sees the routed experts: a
    routed part that is zero, computed with another expert's or another
    layer's weights, or gated wrongly is over the limits by far."""
    rc, by_fault = planted
    assert rc == 0
    res = by_fault[fault]
    assert res["ok"] is False
    assert res["rms"] > 20 * res["limit"]["rms"]
