"""Several residual streams mixed by manifold-constrained
hyper-connections (models/hyper_connections.py) around the DeepSeek-V3
block, on the CPU at tiny widths with the real structure (tiny-xing: 4
streams, 1 dense + 3 expert layers, 16 routed experts top-4 all held,
one shared), seeded random weights, float32:

(a) the engine (chunked prefill, a prefix-cache hit, fused-K decode)
    against the in-repo plain reference, logits; the whole-stream forward
    against it too;
(b) with ONE stream the forward is the parent commit's: the tree has no
    new leaf and the lowered text is the one recorded there;
(c) the projection: rows and columns sum to 1 after twenty iterations and
    not after one; a 2-stream, 2-dim hyper-connection by hand;
(d) each planted fault of bench/planted_fault_xing_mhc.py reads over the
    tolerance, at natural routing;
(e) ``param_count`` equals the leaves, the preset equals the
    configuration file and the file the catalog's row, the counts of
    ISSUE 47 by hand, the counters, what 'auto' sizes and int8 leave
    alone.
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
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.models import deepseek_v3 as dsv3
from tpu_inference.models import hyper_connections as mhc
from tpu_inference.models import quant

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    """A file of bench/ as a module, without putting bench/ on sys.path."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.splitext(os.path.basename(path))[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(REPO, "bench", "references", "xing_mhc.py"))
FAULTS = _load(os.path.join(REPO, "bench", "planted_fault_xing_mhc.py"))
TINY_FILE = "bench/tests/rehearsal/configs/tiny-xing.json"
REAL_FILE = "bench/configs/xing4-29b-pp6-bf16.json"
# float32 on both sides: what is left is the order of the sums (a paged
# softmax, a grouped matmul against a loop over experts, the stream norm
# applied behind the head's matmul), ~1e-6 of a logit's spread.
TOL = 2e-4


def config_file(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    sz = REF.sizes(config_file(TINY_FILE), 4)
    weights = jax.tree.map(lambda a: a.astype(jnp.float32),
                           REF.make_weights(sz, 5))
    return PRESETS["tiny-xing"](), sz, weights


def rel_rms(got, want):
    err = (np.asarray(got) - want) / np.std(want)
    return float(np.sqrt(np.mean(err ** 2)))


# ------------------------------------------------------------------ (a)
def test_engine_matches_the_reference(tiny):
    mcfg, sz, weights = tiny
    eng = InferenceEngine(mcfg, EngineConfig(
        num_pages=128, max_pages_per_seq=24, max_batch_size=4,
        prefill_buckets=(32, 64), keep_logits=True), params=weights)
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
        at = list(range(n - 1, n + 4))
        ref = REF.logits(weights, sz, (s.prompt_tokens + s.generated)[:n + 4],
                         at)
        for p, r in zip(at, ref):
            assert rel_rms(s.kept_logits[p], r) < TOL, (n, p)
        assert [int(np.argmax(r)) for r in ref] == s.generated[:5]

    for s in seqs:
        check(s)
        eng.release(s)
    hit = Sequence(request_id=9, max_new_tokens=8, prompt_tokens=shared + [
        int(t) for t in rng.integers(0, 512, 30)])
    eng.prefill(hit)
    assert hit.cached_tokens >= 48
    while len(hit.generated) < 5:
        eng.decode_steps()
    check(hit)
    # The counts came out with the tokens: two mixes a layer a token the
    # forward ran for (the routing counts see the expert layers only).
    st = dict(zip(dsv3.MOE_STATS, eng.aux_stats))
    assert st["local_pairs"] == st["computed_pairs"] == 4 * st["tokens"] > 0
    mixes, ppm = eng.aux_stats[-2:]
    assert mixes == 2 * mcfg.n_layers * st["tokens"] // 3
    # Twenty iterations leave the COLUMNS of the slowest token's matrix
    # a percent off (rows are exact): what the counter is there to show.
    assert 0 < ppm < 50_000
    reg = telemetry.render_prometheus([({}, eng.telemetry.registry)])
    assert f"tpu_inf_mhc_mixes_total {mixes}" in reg
    assert f"tpu_inf_mhc_row_sum_err_ppm_max {ppm}" in reg


def test_forward_is_the_reference_on_a_whole_stream(tiny):
    mcfg, sz, weights = tiny
    toks = np.random.default_rng(1).integers(0, 512, 70)
    got, _ = dsv3.forward(weights, mcfg, jnp.asarray(toks)[None],
                          jnp.arange(70)[None], None,
                          dsv3.make_dense_attn(mcfg))
    want = REF.logits(weights, sz, list(toks), [10, 69])
    for g, w in zip(np.asarray(got[0])[[10, 69]], want):
        assert rel_rms(g, w) < TOL


def test_the_largest_sum_error_folds_by_max(tiny):
    """``aux_max_slots``: the last slot holds the largest value a
    readback carried, every other slot the sum."""
    mcfg, _, weights = tiny
    eng = InferenceEngine(mcfg, EngineConfig(
        num_pages=32, max_pages_per_seq=8, max_batch_size=2,
        prefill_buckets=(32,)), params=weights)
    assert dsv3.aux_max_slots(mcfg) == (len(eng.aux_stats) - 1,)
    assert dsv3.aux_max_slots(PRESETS["tiny-kimi"]()) == ()
    rows = np.zeros((3, 2 + len(eng.aux_stats)), np.int32)
    rows[:, -2], rows[:, -1] = (5, 6, 7), (40, 90, 20)
    eng._fold_aux_stats(rows)
    eng._fold_aux_stats(rows[:1])
    assert tuple(eng.aux_stats[-2:]) == (23, 90)


# ------------------------------------------------------------------ (b)
# sha256 of ``jax.jit(dsv3.forward).lower(...).as_text()`` at tiny-kimi
# (one stream) under tests/conftest.py's settings, recorded on the parent
# commit (8a68f61).
PARENT_FORWARD = "7820bd675926cc4b"


def test_one_stream_is_the_parents_forward():
    one = dataclasses.replace(PRESETS["tiny-xing"](), hc_mult=1)
    assert mhc.shapes(one, 3) == {}
    assert not [k for k in dsv3.param_shapes(one)["moe"] if "hc_" in k]
    assert dsv3.n_aux_stats(one) == dsv3.n_moe_stats(one)
    mcfg = PRESETS["tiny-kimi"]()
    params = jax.eval_shape(lambda k: dsv3.init_params(mcfg, k),
                            jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 12), jnp.int32)
    text = jax.jit(lambda p, t, pos: dsv3.forward(
        p, mcfg, t, pos, None, dsv3.make_dense_attn(mcfg))).lower(
            params, toks, toks).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_FORWARD


# ------------------------------------------------------------------ (c)
def test_the_projection_is_doubly_stochastic_after_twenty_iterations():
    m = jnp.exp(0.5 * jax.random.normal(jax.random.PRNGKey(0), (4, 4, 300)))

    def off(p):
        return max(float(jnp.max(jnp.abs(p.sum(0) - 1))),
                   float(jnp.max(jnp.abs(p.sum(1) - 1))))

    assert off(mhc.sinkhorn(m, 20, 1e-6)) < 1e-4
    assert off(mhc.sinkhorn(m, 1, 1e-6)) > 1e-2
    # Rows are normalised last: after ONE iteration they already sum to
    # 1 and the columns do not, which is why the counter reads both.
    one = mhc.sinkhorn(m, 1, 1e-6)
    assert float(jnp.max(jnp.abs(one.sum(1) - 1))) < 1e-5
    np.testing.assert_allclose(jnp.max(mhc._sum_error(one)), off(one),
                               rtol=1e-5)
    # Clamped scores: exp(+-30) stays finite in float32 and projects.
    far = jnp.exp(jnp.clip(100.0 * jnp.eye(4)[..., None], -30, 30))
    assert np.isfinite(np.asarray(mhc.sinkhorn(far, 20, 1e-6))).all()


def test_a_two_stream_hyper_connection_by_hand():
    """n = 2, D = 2, one token, every number written out."""
    cfg = dataclasses.replace(PRESETS["tiny-xing"](), hc_mult=2, d_model=2,
                              hc_sinkhorn_iters=2)
    x = np.array([1.0, -1.0, 3.0, 1.0])               # stream 0 | stream 1
    phi = np.arange(32, dtype=np.float64).reshape(4, 8) / 16.0 - 1.0
    b = np.array([0.1, -0.2, 0.3, 0.0, 0.5, -0.5, 0.0, 0.25])
    alpha = np.array([0.5, 2.0, 1.0])
    y = np.array([10.0, 20.0])
    eps = cfg.hc_eps
    xt = x / np.sqrt(np.mean(x * x) + eps)             # rms = sqrt(3)
    z = xt @ phi
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    pre = sig(alpha[0] * z[0:2] + b[0:2])
    post = 2.0 * sig(alpha[1] * z[2:4] + b[2:4])
    res = np.exp(alpha[2] * z[4:8] + b[4:8]).reshape(2, 2)
    for _ in range(2):
        res = res / (res.sum(0, keepdims=True) + eps)  # columns
        res = res / (res.sum(1, keepdims=True) + eps)  # rows
    h = pre[0] * x[0:2] + pre[1] * x[2:4]
    out = np.concatenate([res[0, 0] * x[0:2] + res[0, 1] * x[2:4]
                          + post[0] * y,
                          res[1, 0] * x[0:2] + res[1, 1] * x[2:4]
                          + post[1] * y])

    lp = {"hc_attn_phi": jnp.asarray(phi, jnp.float32),
          "hc_attn_b": jnp.asarray(b, jnp.float32),
          "hc_attn_alpha": jnp.asarray(alpha, jnp.float32)}
    xs = jnp.asarray(x, jnp.float32)[None, None]
    coef, err = mhc.coefficients(cfg, lp, "attn", xs)
    np.testing.assert_allclose(coef[0, 0], np.concatenate(
        [pre, post, res.reshape(-1)]), rtol=2e-6)
    np.testing.assert_allclose(mhc.pre_mix(cfg, coef, xs)[0, 0], h,
                               rtol=2e-6)
    np.testing.assert_allclose(
        mhc.post_mix(cfg, coef, xs, jnp.asarray(y, jnp.float32)[None, None]
                     )[0, 0], out, rtol=2e-6)
    want = max(np.abs(res.sum(0) - 1).max(), np.abs(res.sum(1) - 1).max())
    assert want > 1e-3 and abs(float(err) - want) < 1e-6
    # The fan-out copies, the read-out sums.
    e = jnp.asarray([[[1.0, 2.0]]])
    assert mhc.fan_out(cfg, e).tolist() == [[[1.0, 2.0, 1.0, 2.0]]]
    assert mhc.read_out(cfg, xs).tolist() == [[[4.0, 0.0]]]


# ------------------------------------------------------------------ (d)
@pytest.mark.parametrize("fault", FAULTS.FAULTS)
def test_a_planted_fault_reads_over_the_tolerance(tiny, fault):
    mcfg, sz, weights = tiny
    toks = np.random.default_rng(2).integers(0, 512, 40)
    want = REF.logits(weights, sz, list(toks), [39])[0]
    restore = FAULTS.plant(fault)
    try:
        got, _ = dsv3.forward(weights, mcfg, jnp.asarray(toks)[None],
                              jnp.arange(40)[None], None,
                              dsv3.make_dense_attn(mcfg))
    finally:
        restore()
    assert rel_rms(got[0, 39], want) > 50 * TOL, fault


@pytest.mark.parametrize("variant", FAULTS.PRECISION)
def test_bfloat16_coefficients_or_mixes_read_over_the_tolerance(tiny,
                                                                variant):
    """The precision the module states (coefficients float32, mixes
    accumulated in float32) is held HERE: on the chip at three layers
    ``correct`` reads either variant inside a sound run's noise (PERF.md
    section 7)."""
    mcfg, sz, weights = tiny
    toks = np.random.default_rng(2).integers(0, 512, 120)
    want = REF.logits(weights, sz, list(toks), [119])[0]
    restore = FAULTS.plant(variant)
    try:
        got, _ = dsv3.forward(weights, mcfg, jnp.asarray(toks)[None],
                              jnp.arange(120)[None], None,
                              dsv3.make_dense_attn(mcfg))
    finally:
        restore()
    assert rel_rms(got[0, 119], want) > 5 * TOL, variant


# ------------------------------------------------------------------ (e)
@pytest.mark.parametrize("name", ["tiny-xing", "xing4-29b-pp6",
                                  "kimi-k2-ep32"])
def test_param_count_equals_the_leaves(name):
    cfg = PRESETS[name]()
    leaves = jax.tree.leaves(jax.eval_shape(
        lambda k: dsv3.init_params(cfg, k), jax.random.PRNGKey(0)))
    assert dsv3.param_count(cfg) == sum(l.size for l in leaves)
    # Only the selection bias and a hyper-connection's b / alpha are
    # float32 (a name that ends in "_b" is not enough: wq_b, wkv_b).
    shapes = jax.eval_shape(lambda k: dsv3.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    wide = sorted({k for stack in ("dense", "moe")
                   for k, v in shapes[stack].items()
                   if v.dtype == jnp.float32 and cfg.dtype != jnp.float32})
    assert wide == ([] if cfg.dtype == jnp.float32 else sorted(
        {"router_bias", *(mhc.FLOAT32_LEAVES if cfg.hc_mult > 1 else ())}))


def test_preset_equals_the_configuration_file_and_the_catalog_row():
    cfg, m = config_file(REAL_FILE), PRESETS["xing4-29b-pp6"]()
    parity = _load(os.path.join(REPO, "bench", "parity.py"))
    assert parity.check_sizes(cfg, m) == []
    rs, ys = cfg["rope_scaling"], m.rope_scaling
    assert (ys.factor, ys.original_max_len, ys.beta_fast, ys.beta_slow,
            ys.mscale, ys.mscale_all_dim) == (
        rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])
    assert (m.ep_size, m.hc_res_clamp) == (cfg["ep_size"],
                                           -cfg["mhc_h_res_clamp_min"])
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "num_nextn_predict_layers"]
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "first_k_dense_replace": 2,
                                "num_nextn_predict_layers": 1,
                                "n_routed_experts": 64}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert cfg["source"] == row["source_url"]
        differ = sorted(k for k, v in row["config"].items()
                        if cfg.get(k) != v)
        assert differ == sorted(cfg["reduced"])
    t, tm = config_file(TINY_FILE), PRESETS["tiny-xing"]()
    assert parity.check_sizes(t, tm) == []


def test_the_counts_of_the_issue_by_hand():
    """28.41 M of attention a layer, 744 M an expert layer, 0.69 M of
    hyper-connections a layer, 11.08 GB a stage, 8,960 B a token."""
    m = PRESETS["xing4-29b-pp6"]()
    attn = (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192
            + 4096 * 3584)
    assert attn == 28_409_856
    norms = 2 * 3584 + 768 + 512
    hc = 2 * (4 * 3584 * 24 + 24 + 3)
    dense = attn + norms + hc + 3 * 3584 * 9216
    expert = (attn + norms + hc + 3584 * 64 + 64 + 3 * 3584 * 1024
              + 64 * 3 * 3584 * 1024)
    total = dense + 6 * expert + 2 * 131072 * 3584 + 3584
    assert dsv3.param_count(m) == total
    assert abs(2 * total / 1e9 - 11.08) < 0.01
    active = dsv3.param_count(m, active=True)
    assert active == total - 6 * 60 * 3 * 3584 * 1024
    assert autosize.kv_bytes_per_token(m) == 7 * 640 * 2 == 8960


def test_auto_sizes_count_the_streams_of_a_chunk():
    m = PRESETS["xing4-29b-pp6"]()
    assert autosize.stream_activation_bytes(m, 1024) == 3 * 1024 * 3584 * 8
    assert autosize.stream_activation_bytes(PRESETS["kimi-k2-ep32"](),
                                            1024) == 0
    kw = dict(hbm_bytes=16.9e9, max_pages_per_seq=704, batch_cap=64,
              target_ctx=2048)
    one = autosize.auto_size(dataclasses.replace(m, hc_mult=1), **kw)
    four = autosize.auto_size(m, **kw)
    # The same budget less the streams' bytes (and the hyper-connections'
    # own 9.6 MB of weights), in pages of 16 tokens x 8,960 B.
    fewer = (one.num_pages - four.num_pages) * 16 * 8960
    assert 0 <= fewer - 3 * 1024 * 3584 * 8 - 2 * 14 * 344_091 < 2 * 143_360
    assert four.max_batch_size == 64


def test_int8_leaves_the_coefficient_head_alone(tiny):
    _, _, weights = tiny
    q = quant.quantize_params(weights, "int8")
    for stack in ("dense", "moe"):
        for k, leaf in q[stack].items():
            if k.startswith("hc_") or k in ("w_router", "router_bias"):
                assert not isinstance(leaf, quant.QuantizedArray), k
        assert isinstance(q[stack]["wq_a"], quant.QuantizedArray)
    assert not [k for k in quant.STORED_TRANSPOSED["deepseek_v3"]
                if k.startswith("hc_")]


def test_validate_refuses_streams_outside_this_block():
    with pytest.raises(AssertionError):
        dataclasses.replace(PRESETS["tiny-llama"](), hc_mult=4).validate()
    with pytest.raises(AssertionError):
        dataclasses.replace(PRESETS["tiny-xing"](), hc_mult=0).validate()
    PRESETS["tiny-xing"]().validate()
    PRESETS["xing4-29b-pp6"]().validate()
