"""The Ouro family (a looped stack) on the CPU at tiny widths with the
real structure (tiny-ouro: 2 layers x 3 passes = 6 KV slots, MHA,
sandwich norms, an exit gate), seeded random weights:

(a) the engine (prefill in one chunk and in several, batched fused-K paged
    decode, a prefix-cache hit over all slots) against the in-repo plain
    reference's full forward, on logits, with the ``jnp`` and the
    interpreted ``pallas`` backend; each planted fault of
    bench/planted_fault_looped.py fails the same comparison;
(b) the exit gate and the exit rule against the reference; threshold 1.0
    is the last pass for every token;
(c) sizes: KV bytes a token, ``auto_size`` for the published preset, the
    derived slot count under ``dataclasses.replace``, the preset against
    the configuration file;
(d) what a looped stack does not run is refused at construction;
(e) the cost model: a looped stack reads and multiplies its layers once a
    pass, and the other families' numbers are what they were.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.config import PRESETS, EngineConfig
from tpu_inference.engine import autosize
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.models import ouro
from tpu_inference.telemetry import StepCostModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    """A file of bench/ as a module, without putting bench/ on sys.path
    (its ``tests`` directory would shadow this one's ``tests.conftest``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.splitext(os.path.basename(path))[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(REPO, "bench", "references", "ouro.py"))
FAULTS = _load(os.path.join(REPO, "bench", "planted_fault_looped.py"))


def config_file(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    sz = REF.sizes(config_file(
        "bench/tests/rehearsal/configs/tiny-ouro.json"), 2)
    weights = jax.tree.map(lambda a: a.astype(jnp.float32),
                           REF.make_weights(sz, 5))
    return PRESETS["tiny-ouro"](), sz, weights


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


# Both sides compute in float32 on the CPU (tests/conftest.py sets the
# matmul precision to "highest"), in different orders of summation (pages,
# online softmax): they agree to rounding, 1e-6 to 1e-5 of the logits'
# spread. A planted fault reads 0.1 to 1.
TOL = 1e-4


def worst_error(mcfg, sz, weights, backend):
    """The largest rms logit error, as a share of the reference's logit
    spread, over: a one-chunk and a three-chunk prefill, fused-K decode of
    both lanes together, and a prefix-cache hit; the greedy tokens must be
    the reference's argmax (checked where the error is small)."""
    eng = engine(mcfg, weights, attn_backend=backend)
    rng = np.random.default_rng(3)
    shared = [int(t) for t in rng.integers(0, 512, 48)]
    prompts = [shared[:20], shared + [int(t) for t in
                                      rng.integers(0, 512, 100)]]
    seqs = [Sequence(request_id=i, prompt_tokens=p, max_new_tokens=8)
            for i, p in enumerate(prompts)]      # 20: one chunk; 148: three
    for s in seqs:
        eng.prefill(s)
    while any(len(s.generated) < 5 for s in seqs):
        eng.decode_steps()                       # both lanes, fused K
    worst = 0.0

    def check(s):
        nonlocal worst
        n = len(s.prompt_tokens)
        stream = (s.prompt_tokens + s.generated)[:n + 4]
        full = REF.logits(weights, sz, stream, list(range(n - 1, n + 4)))
        errs = []
        for p in (n - 1, n + 3):
            r = full[p - (n - 1)]
            err = (probe_logits(eng, s, p) - r) / np.std(r)
            errs.append(float(np.sqrt(np.mean(err ** 2))))
        worst = max(worst, *errs)
        if max(errs) < TOL:
            assert [int(np.argmax(r)) for r in full] == s.generated[:5]

    for s in seqs:
        check(s)
        eng.release(s)
    # A new stream behind the shared prefix: its pages, in every slot,
    # come from the cache.
    hit = Sequence(request_id=9, max_new_tokens=8, prompt_tokens=shared + [
        int(t) for t in rng.integers(0, 512, 30)])
    eng.prefill(hit)
    assert hit.cached_tokens >= 48
    while len(hit.generated) < 5:
        eng.decode_steps()
    check(hit)
    return worst


# ------------------------------------------------------------------ (a)
@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_engine_matches_the_reference(tiny, backend):
    mcfg, sz, weights = tiny
    assert worst_error(mcfg, sz, weights, backend) < TOL


@pytest.mark.parametrize("fault", FAULTS.FAULTS)
def test_planted_fault_fails_the_comparison(tiny, fault):
    mcfg, sz, weights = tiny
    restore = FAULTS.plant(fault)
    try:
        err = worst_error(mcfg, sz, weights, "dense")
    finally:
        restore()
    assert err > 100 * TOL, (fault, err)


def test_preempted_sequence_resumes_over_all_slots(tiny):
    """Recompute-resume: the preempted sequence's pages are published and
    freed, its re-prefill (prompt + generated) hits the cache in every
    slot, and decoding goes on as the reference's argmax."""
    mcfg, sz, weights = tiny
    eng = engine(mcfg, weights, attn_backend="dense")
    rng = np.random.default_rng(11)
    s = Sequence(request_id=0, max_new_tokens=12, prompt_tokens=[
        int(t) for t in rng.integers(0, 512, 70)])
    eng.prefill(s)
    while len(s.generated) < 4:
        eng.decode_steps()
    free_before = eng.allocator.num_free
    eng.preempt(s)
    assert eng.allocator.num_free + eng.prefix_cache.evictable \
        > free_before
    eng.prefill(s)
    assert s.cached_tokens >= 64
    while len(s.generated) < 8:
        eng.decode_steps()
    n = len(s.prompt_tokens)
    full = REF.logits(weights, sz, (s.prompt_tokens + s.generated)[:n + 7],
                      list(range(n - 1, n + 7)))
    assert [int(np.argmax(r)) for r in full] == s.generated[:8]
    eng.release(s)
    assert eng.allocator.num_free + eng.prefix_cache.evictable \
        == eng.engine_cfg.num_pages - 1


# ------------------------------------------------------------------ (b)
def test_exit_probabilities_match_the_reference(tiny):
    mcfg, sz, weights = tiny
    tokens = [int(t) for t in np.random.default_rng(7).integers(0, 512, 50)]
    from tpu_inference.models.common import make_dense_attn
    pos = jnp.arange(len(tokens))[None]
    _, _, per_pass = ouro.forward_passes(
        weights, mcfg, jnp.asarray(tokens)[None], pos, None,
        make_dense_attn(), collect=True)
    got = np.asarray(ouro.exit_probabilities(weights, mcfg, per_pass))[:, 0]
    want = REF.exit_probabilities(weights, sz, tokens)
    assert got.shape == want.shape == (3, 50)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got.sum(0), 1.0, atol=1e-6)
    # The gate is not a constant: tokens differ in where they would leave.
    assert want[0].std() > 1e-3
    for threshold in (0.3, 0.55, 0.9):
        np.testing.assert_array_equal(
            np.asarray(ouro.exit_pass(jnp.asarray(got), threshold)),
            REF.exit_pass(want, threshold))
    assert len(set(REF.exit_pass(want, 0.55).tolist())) > 1
    # The published threshold: every token runs every pass.
    assert mcfg.early_exit_threshold == sz["exit_threshold"] == 1.0
    assert (np.asarray(ouro.exit_pass(jnp.asarray(got), 1.0)) == 2).all()
    assert (REF.exit_pass(want, 1.0) == 2).all()


# ------------------------------------------------------------------ (c)
def test_sizes_follow_the_slots():
    big = PRESETS["ouro-2.6b"]()
    assert big.n_kv_slots == 192
    assert autosize.kv_bytes_per_token(big) == 4 * 48 * 2 * 16 * 128 * 2 \
        == 1572864
    assert dataclasses.replace(big, n_layers=4).n_kv_slots == 16
    # The catalog's 51.38M a layer, 2.668B in all, stored once.
    assert ouro.param_count(big) == (48 * (51380224 + 4 * 2048)
                                     + 2 * 49152 * 2048 + 2048 + 2048 + 1)
    assert ouro.param_count(big, True) - ouro.param_count(big) \
        == 3 * 48 * (51380224 + 4 * 2048)
    sized = autosize.auto_size(big, hbm_bytes=16.6e9, max_pages_per_seq=52)
    assert 320 <= sized.num_pages <= 335 and sized.max_batch_size == 12
    assert sized.kv_bytes_per_token == 1572864
    assert autosize.decode_ladder_rungs(12) == (8, 12)
    # The host tier's sizing follows the slots, and 'auto' leaves it off.
    assert autosize.auto_host_cache_pages(big, host_ram_bytes=64 << 30) == 0
    # A pool's leading dim is slots.
    tiny = PRESETS["tiny-ouro"]()
    eng = InferenceEngine(tiny, EngineConfig(num_pages=16,
                                             max_pages_per_seq=4))
    assert eng.kv.k.shape == (6, 16, 16, 4, 32) == eng.kv.v.shape


def test_preset_equals_the_configuration_file():
    f = config_file("bench/configs/ouro-2.6b-bf16.json")
    m = PRESETS[f["serving"]["preset"]]()
    assert (m.d_model, m.d_ff, m.n_layers, m.n_heads, m.n_kv_heads,
            m.head_dim, m.vocab_size, m.loop_steps, m.max_seq_len) == (
        f["hidden_size"], f["intermediate_size"], f["num_hidden_layers"],
        f["num_attention_heads"], f["num_key_value_heads"], f["head_dim"],
        f["vocab_size"], f["total_ut_steps"], f["max_position_embeddings"])
    assert m.rope_theta == f["rope_theta"] and m.norm_eps == f["rms_norm_eps"]
    assert m.early_exit_threshold == f["early_exit_threshold"]
    assert not m.tie_embeddings and not m.sliding_window and m.sandwich_norm
    sz = REF.sizes(f, f["parity"]["layers"])
    assert sz["passes"] * sz["layers"] == 16
    assert f["reduced"] == []


# ------------------------------------------------------------------ (d)
def _tp2_mesh():
    from tpu_inference.config import ParallelConfig
    from tpu_inference.parallel.mesh import build_mesh
    return build_mesh(ParallelConfig(tp=2), devices=jax.devices()[:2])


@pytest.mark.parametrize("model_kw,engine_kw,ctor_kw,needle", [
    ({}, dict(kv_quant="int8"), {}, "kv_quant"),
    ({}, dict(num_speculative_tokens=3), {}, "speculative"),
    ({}, dict(host_cache_pages=8), {}, "host KV tier"),
    ({}, dict(role="prefill"), {}, "role"),
    (dict(early_exit_threshold=0.5), {}, {}, "early_exit_threshold"),
    ({}, {}, dict(mesh=_tp2_mesh), "tp / sp"),
])
def test_unsupported_is_refused_at_construction(model_kw, engine_kw, ctor_kw,
                                                needle):
    mcfg = dataclasses.replace(PRESETS["tiny-ouro"](), **model_kw)
    ctor_kw = {k: make() for k, make in ctor_kw.items()}
    with pytest.raises(ValueError, match=needle):
        InferenceEngine(mcfg, EngineConfig(num_pages=32, max_pages_per_seq=8,
                                           **engine_kw), **ctor_kw)


def test_pipeline_parallelism_refuses_a_looped_stack():
    from jax.sharding import Mesh

    from tpu_inference.parallel.pipeline import pp_forward
    mcfg = PRESETS["tiny-ouro"]()
    mesh = Mesh(np.array(jax.devices()[:2]), ("pp",))
    tokens = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(ValueError, match="looped"):
        pp_forward(ouro.init_params(mcfg, jax.random.PRNGKey(0)), mcfg,
                   tokens, jnp.broadcast_to(jnp.arange(8), (2, 8)), mesh)


# ------------------------------------------------------------------ (e)
class _Engine:
    def __init__(self, name, quant="none"):
        self.model_cfg = PRESETS[name]()
        self.engine_cfg = EngineConfig(quant=quant)
        self.n_params = autosize.estimate_param_count(self.model_cfg)


# (preset, quant) -> (n_params, n_layers, weight_bytes, kv_token_bytes) as
# the cost model read them before the looped family was added.
UNCHANGED = {
    ("mistral-7b", "int8"): (7241465856, 32, 7573403074, 131072),
    ("qwen2-7b", "int8"): (7615283200, 28, 8770530836, 57344),
    ("kimi-k2-ep32", "none"): (1744758016, 7, 9699183104, 8960),
    ("tiny-llama", "none"): (425984, 2, 851968, 512),
}


@pytest.mark.parametrize("name,quant", sorted(UNCHANGED))
def test_cost_model_of_the_other_families_is_unchanged(name, quant):
    m = StepCostModel.from_engine(_Engine(name, quant))
    assert (m.n_params, m.n_layers, m.weight_bytes,
            m.kv_token_bytes) == UNCHANGED[(name, quant)]


def test_cost_model_counts_a_pass_per_read():
    m = StepCostModel.from_engine(_Engine("ouro-2.6b"))
    cfg = PRESETS["ouro-2.6b"]()
    layers = 48 * (51380224 + 4 * 2048)
    rest = ouro.param_count(cfg) - layers
    assert m.n_params == rest + 4 * layers and m.n_layers == 192
    # A decode step reads the layers 4 times, embedding and head once
    # (the final norm and the gate, 4097 values, ride with the layers).
    assert m.weight_bytes == pytest.approx(2 * (rest + 4 * layers), rel=1e-5)
    assert m.kv_token_bytes == 1572864
    # One decode step of 12 lanes at 330 tokens of context each.
    rec = (0.0, "decode", 12, 12, 12, 0, 1, 0.04, 0.0, 0.0, 12 * 330, 0.0,
           0, 0)
    assert m.flops(rec) == 2.0 * m.n_params * 12 \
        + 4.0 * 192 * 16 * 128 * 12 * 330
    assert m.hbm_bytes(rec) == m.weight_bytes \
        + 1572864.0 * (12 * 330 + 12)
