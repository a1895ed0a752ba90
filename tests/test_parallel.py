"""Multi-chip sharding tests on the 8-device virtual CPU mesh.

Correctness bar: a TP/EP-sharded forward (GSPMD-placed collectives) must
match the single-device forward bit-for-bit-ish (f32, highest precision).
The reference has no parallelism to compare against (SURVEY.md §2b); the
oracle is our own unsharded graph.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.config import (
    EngineConfig,
    ModelConfig,
    ParallelConfig,
)
from tpu_inference.engine.engine import InferenceEngine
from tpu_inference.models.common import make_dense_attn
from tpu_inference.models.registry import build_model, get_model_fns
from tpu_inference.parallel import (
    build_mesh,
    param_shardings,
    shard_params,
)


def tp_llama_cfg():
    return ModelConfig(
        name="tp-llama", family="llama", vocab_size=512, d_model=128,
        n_layers=2, n_heads=8, n_kv_heads=4, d_ff=256, max_seq_len=512,
        rope_theta=10000.0, dtype=jnp.float32)


def tp_qwen2_cfg():
    """Qwen2 dialect under TP: the head-dim-sharded q/k/v biases must
    follow their projections (parallel/shardings.py bq/bk/bv specs)."""
    import dataclasses
    return dataclasses.replace(tp_llama_cfg(), name="tp-qwen2",
                               qkv_bias=True)


def tp_mixtral_cfg():
    return ModelConfig(
        name="tp-mixtral", family="mixtral", vocab_size=512, d_model=128,
        n_layers=2, n_heads=8, n_kv_heads=4, d_ff=256, max_seq_len=512,
        rope_theta=10000.0, n_experts=4, n_experts_per_tok=2,
        dtype=jnp.float32)


def _forward_logits(cfg, params, tokens):
    mod = get_model_fns(cfg)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    logits, _ = mod.forward(params, cfg, tokens, positions, None,
                            make_dense_attn())
    return logits


@pytest.mark.parametrize("cfg_fn", [tp_llama_cfg, tp_qwen2_cfg,
                                    tp_mixtral_cfg])
def test_tp_forward_matches_single_device(cfg_fn):
    cfg = cfg_fn()
    params, mod = build_model(cfg, seed=0)
    if cfg.qkv_bias:
        from tests.conftest import randomize_qkv_biases
        randomize_qkv_biases(params, seed=11)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)

    ref = jax.jit(lambda p, t: _forward_logits(cfg, p, t))(params, tokens)

    mesh = build_mesh(ParallelConfig(tp=4))
    sharded = shard_params(params, cfg, mesh)
    got = jax.jit(lambda p, t: _forward_logits(cfg, p, t))(sharded, tokens)

    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_param_shardings_cover_tree():
    """Every leaf of every family's params has a matching spec leaf."""
    import dataclasses

    from tpu_inference.config import tiny_gpt2

    tied_llama = dataclasses.replace(tp_llama_cfg(), tie_embeddings=True)
    gpt2 = dataclasses.replace(tiny_gpt2(), n_heads=4, n_kv_heads=4)
    for cfg in (tp_llama_cfg(), tied_llama, tp_mixtral_cfg(), gpt2):
        params, _ = build_model(cfg, seed=0)
        mesh = build_mesh(ParallelConfig(tp=4))
        sh = param_shardings(cfg, mesh)
        # tree.map raises if structures mismatch.
        jax.tree.map(lambda p, s: None, params, sh)


def test_validate_tp_rejects_indivisible():
    from tpu_inference.parallel import validate_tp

    cfg = tp_llama_cfg()  # n_kv_heads=4
    with pytest.raises(ValueError, match="n_kv_heads"):
        validate_tp(cfg, 8)


def test_tp_engine_generate_matches_unsharded():
    """End-to-end: paged-KV engine under a TP=4 mesh produces the same greedy
    tokens as the single-device engine."""
    cfg = tp_llama_cfg()
    ecfg = EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=8,
                        max_batch_size=4, prefill_buckets=(16, 32))
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17]]

    base = InferenceEngine(cfg, ecfg, seed=0)
    want = base.generate(prompts, max_new_tokens=8)

    mesh = build_mesh(ParallelConfig(tp=4))
    eng = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh)
    got = eng.generate(prompts, max_new_tokens=8)
    assert got == want


def test_ep_engine_generate_matches_unsharded():
    cfg = tp_mixtral_cfg()
    ecfg = EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=8,
                        max_batch_size=4, prefill_buckets=(16, 32))
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]

    base = InferenceEngine(cfg, ecfg, seed=0)
    want = base.generate(prompts, max_new_tokens=6)

    mesh = build_mesh(ParallelConfig(tp=4))
    eng = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh)
    got = eng.generate(prompts, max_new_tokens=6)
    assert got == want


def test_sp_engine_ring_prefill_matches_unsharded():
    """Serving prefill through ring attention (sp=4, composed with tp=2)
    produces the same greedy tokens as the single-device engine, including
    prompts long enough to span several sequence shards."""
    cfg = tp_llama_cfg()
    ecfg = EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=8,
                        max_batch_size=4, prefill_buckets=(16, 32))
    prompts = [list(range(1, 29)), [7, 8, 9], list(range(100, 117))]

    base = InferenceEngine(cfg, ecfg, seed=0)
    want = base.generate(prompts, max_new_tokens=8)

    mesh = build_mesh(ParallelConfig(tp=2, sp=4))
    eng = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh)
    assert eng.sp == 4
    got = eng.generate(prompts, max_new_tokens=8)
    assert got == want


@pytest.mark.slow   # 2k-token ring prefill; short-ring coverage in test_sp_engine_ring_prefill_matches_unsharded
def test_sp_long_context_prefill():
    """Long-context serving: a 2k-token prompt prefills through ring
    attention (sp=4) with per-chip sequence shards and decodes on the
    paged pool, token-equal to the unsharded engine."""
    cfg = tp_llama_cfg()
    ecfg = EngineConfig(page_size=16, num_pages=320, max_pages_per_seq=160,
                        max_batch_size=2, prefill_buckets=(256, 2048))
    prompt = [(7 * i + 3) % cfg.vocab_size for i in range(2048)]

    base = InferenceEngine(cfg, ecfg, seed=0)
    want = base.generate([prompt], max_new_tokens=4)

    mesh = build_mesh(ParallelConfig(tp=2, sp=4))
    eng = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh)
    got = eng.generate([prompt], max_new_tokens=4)
    assert got == want


def test_dp_tp_mesh_shapes():
    mesh = build_mesh(ParallelConfig(dp=2, tp=2, sp=2))
    assert mesh.shape == {"dp": 2, "tp": 2, "sp": 2}
    with pytest.raises(ValueError):
        build_mesh(ParallelConfig(dp=4, tp=4))


def test_replica_meshes_split():
    """replica_meshes hands back one (tp, sp) submesh per dp row; in a
    single process every row is local, each keeps dp=1 and the
    production axis names so sharding specs apply unchanged."""
    from tpu_inference import config as cfgs
    from tpu_inference.parallel.multihost import (build_hybrid_mesh,
                                                  replica_meshes)

    mesh = build_hybrid_mesh(cfgs.ParallelConfig(dp=2, tp=2, sp=2))
    rows = replica_meshes(mesh)
    assert [i for i, _ in rows] == [0, 1]
    for i, sub in rows:
        assert dict(sub.shape) == {"dp": 1, "tp": 2, "sp": 2}
        assert (sub.devices == mesh.devices[i:i + 1]).all()


def test_hybrid_mesh_single_slice():
    """build_hybrid_mesh == flat mesh layout when all devices share ICI."""
    from tpu_inference import config as cfgs
    from tpu_inference.parallel.multihost import build_hybrid_mesh

    pcfg = cfgs.ParallelConfig(dp=2, tp=2, sp=2)
    mesh = build_hybrid_mesh(pcfg)
    assert mesh.shape == {"dp": 2, "tp": 2, "sp": 2}
    # tp groups contiguous in device order (ICI neighbors).
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    assert ids[0, 0, 0] + 1 == ids[0, 1, 0]


def test_hybrid_mesh_multi_slice_layout():
    """dp splits across simulated slices; tp never straddles a slice."""
    from tpu_inference import config as cfgs
    from tpu_inference.parallel.multihost import build_hybrid_mesh

    pcfg = cfgs.ParallelConfig(dp=2, tp=4, sp=1)
    mesh = build_hybrid_mesh(pcfg, num_slices=2)
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    # Replica 0 = devices 0-3, replica 1 = devices 4-7: each tp group
    # stays inside one "slice" of 4 contiguous devices.
    assert set(ids[0].flat) == {0, 1, 2, 3}
    assert set(ids[1].flat) == {4, 5, 6, 7}

    with pytest.raises(ValueError, match="straddle"):
        build_hybrid_mesh(cfgs.ParallelConfig(dp=1, tp=8), num_slices=2)


def test_hybrid_mesh_runs_collectives():
    """A psum over the hybrid mesh executes (XLA inserts the collective)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_inference import config as cfgs
    from tpu_inference.parallel.multihost import build_hybrid_mesh

    mesh = build_hybrid_mesh(cfgs.ParallelConfig(dp=2, tp=2, sp=2),
                             num_slices=2)
    x = jnp.arange(8.0)
    y = jax.jit(lambda v: v.sum(),
                in_shardings=NamedSharding(mesh, P(("dp",))),
                out_shardings=NamedSharding(mesh, P()))(x)
    assert float(y) == 28.0


def test_multihost_initialize_noop_single_process():
    from tpu_inference import config as cfgs
    from tpu_inference.parallel.multihost import (initialize,
                                                  process_local_engine_role)
    initialize()                      # must not raise on single process
    from tpu_inference.parallel.mesh import build_mesh
    role = process_local_engine_role(build_mesh(cfgs.ParallelConfig(tp=2)))
    assert role["process_count"] == 1
    assert role["local_devices_in_mesh"] == 2
    assert role["hosts_frontend"] is True


def test_tp_engine_pipelined_decode_matches():
    """Dispatch-ahead decode under a tp mesh == sync unsharded engine."""
    cfg = tp_llama_cfg()
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7]]

    base = InferenceEngine(cfg, EngineConfig(
        page_size=8, num_pages=64, max_pages_per_seq=8, max_batch_size=2,
        prefill_buckets=(16,), decode_steps_per_call=4), seed=0)
    want = base.generate(prompts, max_new_tokens=12)

    ecfg = EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=8,
                        max_batch_size=2, prefill_buckets=(16,),
                        decode_steps_per_call=4, decode_pipeline_depth=2)
    eng = InferenceEngine(cfg, ecfg, seed=0,
                          mesh=build_mesh(ParallelConfig(tp=4)))
    from tpu_inference.engine.engine import Sequence
    seqs = [Sequence(request_id=i, prompt_tokens=p, max_new_tokens=12)
            for i, p in enumerate(prompts)]
    for s in seqs:
        eng.prefill(s)
    for _ in range(20):
        eng.decode_steps_pipelined()
        if all(s.done for s in seqs) and not eng.pipeline_pending:
            break
    eng.drain_pipeline()
    assert [s.generated for s in seqs] == want


def test_sp_engine_ulysses_prefill_matches_unsharded():
    """Serving prefill through Ulysses all-to-all SP (sp=2, composed
    with tp=2) produces the same greedy tokens as the single-device
    engine (the same contract the ring path satisfies)."""
    cfg = tp_llama_cfg()
    ecfg = EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=8,
                        max_batch_size=4, prefill_buckets=(16, 32),
                        sp_attn="ulysses")
    prompts = [list(range(1, 29)), [7, 8, 9], list(range(100, 117))]

    base = InferenceEngine(cfg, ecfg, seed=0)
    want = base.generate(prompts, max_new_tokens=8)

    mesh = build_mesh(ParallelConfig(tp=2, sp=2))
    eng = InferenceEngine(cfg, ecfg, seed=0, mesh=mesh)
    assert eng.sp == 2
    got = eng.generate(prompts, max_new_tokens=8)
    assert got == want


def test_sp_ulysses_rejects_indivisible_heads():
    """n_kv_heads=4 can't split across tp*sp=8 head groups — explicit
    error steering to the ring, not a wrong-shape crash mid-prefill."""
    cfg = tp_llama_cfg()
    ecfg = EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=8,
                        max_batch_size=2, prefill_buckets=(16,),
                        sp_attn="ulysses")
    mesh = build_mesh(ParallelConfig(tp=2, sp=4))
    with pytest.raises(ValueError, match="ulysses"):
        InferenceEngine(cfg, ecfg, seed=0, mesh=mesh)


# Sharded serving paths no other test reaches (these were the old driver
# hook's multi-chip dry run): each case serves under a mesh on the
# production engine graphs and must produce the unsharded engine's tokens.
_MESH_ECFG = dict(page_size=16, num_pages=32, max_pages_per_seq=8,
                  max_batch_size=4, prefill_buckets=(32,), max_new_tokens=8)


def _generate(cfg, ecfg, mesh=None, **kw):
    return InferenceEngine(cfg, ecfg, seed=0, mesh=mesh, **kw).generate(
        [[1, 2, 3, 4, 5], [7, 8, 9]], max_new_tokens=4)


def _gemma_under_tp():
    """Gemma dialect: a head_dim (32) decoupled from d_model/n_heads
    (128/8 = 16) through the sharded KV pool; norm offset, GeGLU, scaled
    embeddings and the tied unembedding ride along."""
    import dataclasses
    cfg = dataclasses.replace(
        tp_llama_cfg(), name="tp-gemma", n_kv_heads=8, tie_embeddings=True,
        norm_offset=1.0, hidden_act="gelu_tanh", embed_scale=True,
        head_dim_override=32)
    ecfg = EngineConfig(**_MESH_ECFG)
    assert (_generate(cfg, ecfg, build_mesh(ParallelConfig(tp=4)))
            == _generate(cfg, ecfg))


def _dp_tp_mesh():
    """A (dp=2, tp=4) mesh handed to ONE engine: weights shard over tp
    and replicate over dp."""
    cfg, ecfg = tp_llama_cfg(), EngineConfig(**_MESH_ECFG)
    assert (_generate(cfg, ecfg, build_mesh(ParallelConfig(dp=2, tp=4)))
            == _generate(cfg, ecfg))


def _ngram_speculation_under_tp():
    """N-gram speculation under TP: the verify round reads and writes
    the tp-sharded pool; greedy tokens equal unsharded plain decode."""
    cfg, echo = tp_llama_cfg(), [[5, 6, 7, 8] * 4]
    spec = InferenceEngine(
        cfg, EngineConfig(**_MESH_ECFG, num_speculative_tokens=2), seed=0,
        mesh=build_mesh(ParallelConfig(tp=2), devices=jax.devices()[:2]))
    plain = InferenceEngine(cfg, EngineConfig(**_MESH_ECFG), seed=0)
    assert (spec.generate(echo, max_new_tokens=16)
            == plain.generate(echo, max_new_tokens=16))
    assert spec.spec_rounds_total > 0


def _scheduler_over_tp_engine():
    """The threaded continuous-batching scheduler (admission, batched
    prefill, fused decode, streaming callbacks) — the loop the HTTP
    server drives — over a TP-sharded engine."""
    import threading

    from tpu_inference.engine.engine import Sequence
    from tpu_inference.engine.scheduler import EngineScheduler

    cfg, ecfg = tp_llama_cfg(), EngineConfig(**_MESH_ECFG)
    prompts = [[1 + i, 2, 3] for i in range(3)]
    want = InferenceEngine(cfg, ecfg, seed=0).generate(prompts,
                                                       max_new_tokens=3)
    sched = EngineScheduler(InferenceEngine(
        cfg, ecfg, seed=0, mesh=build_mesh(ParallelConfig(tp=4)))).start()
    try:
        seqs = [Sequence(request_id=i, prompt_tokens=p, max_new_tokens=3)
                for i, p in enumerate(prompts)]
        done = {s.request_id: threading.Event() for s in seqs}
        toks = {s.request_id: [] for s in seqs}
        for s in seqs:
            sched.submit(
                s, on_token=lambda sq, t: toks[sq.request_id].append(t),
                on_finish=lambda sq: done[sq.request_id].set())
        for s in seqs:
            assert done[s.request_id].wait(300), "serving loop hung"
    finally:
        sched.stop(drain=False)
    assert [toks[i] for i in range(3)] == want


@pytest.mark.parametrize("case", [_gemma_under_tp, _dp_tp_mesh,
                                  _ngram_speculation_under_tp,
                                  _scheduler_over_tp_engine],
                         ids=lambda f: f.__name__.strip("_"))
def test_sharded_serving_path_matches_unsharded(case):
    case()


def test_build_model_initialises_straight_into_shards():
    """Random init under a mesh draws every leaf into its sharded layout
    (no chip ever holds the whole model — Mistral-7B bf16 over tp=4 does
    not fit the first chip unsharded), and the values do not depend on
    the layout: tp=4 and a one-device mesh give the same bits."""
    import dataclasses

    cfg = dataclasses.replace(tp_llama_cfg(), dtype=jnp.bfloat16)
    mesh4 = build_mesh(ParallelConfig(tp=4))
    mesh1 = build_mesh(ParallelConfig(tp=1), devices=jax.devices()[:1])
    p4, _ = build_model(cfg, seed=3, shardings=param_shardings(cfg, mesh4))
    p1, _ = build_model(cfg, seed=3, shardings=param_shardings(cfg, mesh1))
    wq = p4["blocks"]["wq"]
    assert len({s.device.id for s in wq.addressable_shards}) == 4
    assert wq.addressable_shards[0].data.size * 4 == wq.size
    same = jax.tree.map(lambda a, b: bool((np.asarray(a, np.float32)
                                           == np.asarray(b, np.float32)
                                           ).all()), p4, p1)
    assert all(jax.tree.leaves(same)), same
