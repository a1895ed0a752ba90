"""The SambaY family (models/sambay.py; config ``tiny-sambay``: 8 layers
holding every kind): state-space layers whose state a token ADVANCES, a
state slot a sequence beside the page pools, one full-attention KV slot
that the cross layers read, gated memory units, differential attention.

The system is held to ``bench/references/sambay.py`` (plain float32, the
recurrence as a scan over tokens, no cache, nothing imported from the
program) on LOGITS, at every position the engine kept
(``EngineConfig.keep_logits``): the rows its own step programs sampled
from. Tolerances: the whole of both sides is float32 at "highest" matmul
precision (tests/conftest.py), so what differs is the order of sums
(chunked scan against one scan, paged softmax against dense): 2e-4 of a
logit spread of ~1 holds a hundredfold margin over what is read (~5e-6)
and is two orders below what any planted fault reads (bench/tests).
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.config import (PRESETS, EngineConfig, sambay_layer_kinds)
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine.engine import InferenceEngine, Sequence, model_is
from tpu_inference.models import sambay

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4


def _load(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(REPO, "bench", "references", "sambay.py"))
CFG = PRESETS["tiny-sambay"]()
MODEL = {"vocab_size": CFG.vocab_size, "hidden_size": CFG.d_model,
         "num_attention_heads": CFG.n_heads,
         "num_key_value_heads": CFG.n_kv_heads,
         "sliding_window": CFG.sliding_window,
         "intermediate_size": CFG.d_ff, "layer_norm_eps": CFG.norm_eps,
         "head_dim": CFG.head_dim,
         "assumed": {"mamba": {"expand": CFG.ssm_expand,
                               "d_state": CFG.ssm_d_state,
                               "d_conv": CFG.ssm_d_conv}}}
SZ = REF.sizes(MODEL, CFG.n_layers)
ENGINE = dict(page_size=4, num_pages=128, max_pages_per_seq=32,
              max_batch_size=4, prefill_buckets=(8, 16),
              decode_steps_per_call=4, keep_logits=True,
              enable_prefix_cache=False)


@pytest.fixture(scope="module")
def weights():
    """The reference's own weights (gains and biases away from 1 and 0),
    widened to the tiny preset's float32."""
    w = REF.make_weights(SZ, 5)
    assert jax.tree.map(lambda a: a.shape, w) == sambay.param_shapes(CFG)
    return w, jax.tree.map(lambda a: a.astype(jnp.float32), w)


def _engine(weights, **over):
    return InferenceEngine(CFG, EngineConfig(**dict(ENGINE, **over)),
                           params=weights[1])


def _seq(i, prompt, new=10):
    return Sequence(request_id=i, prompt_tokens=[int(t) for t in prompt],
                    max_new_tokens=new)


def _prompts(*lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n) for n in lens]


def _worst(weights, seqs):
    """Largest distance of any kept row from the reference's logits of
    the same stream; every position from the last prompt token on must
    be among the kept ones."""
    worst = 0.0
    for s in seqs:
        stream = s.prompt_tokens + s.generated
        at = sorted(s.kept_logits)
        assert at[-1] == len(stream) - 2, (at, len(stream))
        ref = REF.logits(weights[0], SZ, stream, at)
        worst = max(worst, max(float(np.abs(ref[i] - s.kept_logits[p]).max())
                               for i, p in enumerate(at)))
    return worst


def _run(eng, seqs):
    while not all(s.done for s in seqs):
        eng.decode_steps()


def test_kinds_follow_from_the_depth():
    want = {8: dict(ssm=3, window=2, full=1, gmu=1, cross=1),
            32: dict(ssm=9, window=8, full=1, gmu=7, cross=7)}
    for n, counts in want.items():
        cfg = dataclasses.replace(PRESETS["phi4-mini-flash"](), n_layers=n)
        cfg.validate()
        assert cfg.layer_types == sambay_layer_kinds(n)
        assert {k: len(cfg.kind_layers(k)) for k in counts} == counts
        assert cfg.layer_types[n // 2] == "ssm"
        assert cfg.layer_types[n // 2 + 1] == "full"
    full = PRESETS["phi4-mini-flash"]()
    assert sambay.param_count(full) == 3_852_562_944
    assert full.state_bytes_per_seq() == 9 * 5120 * (16 * 4 + 3 * 2)
    assert (full.pool_kv_heads, full.pool_head_dim) == (10, 128)
    assert model_is(full) == "state" and model_is(PRESETS["tiny-laguna"]()) \
        == "kinds"


def test_forward_matches_the_plain_reference(weights):
    toks = _prompts(40, seed=1)[0]
    lg, _ = sambay.forward(weights[1], CFG, jnp.asarray(toks)[None], None,
                           None, sambay.make_dense_attn(CFG, 1, len(toks)))
    ref = REF.logits(weights[0], SZ, list(toks), list(range(len(toks))))
    assert float(np.abs(np.asarray(lg[0]) - ref).max()) < TOL
    assert float(np.std(ref)) > 0.05


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_engine_matches_the_reference_at_every_kept_position(weights,
                                                             backend):
    """Three chunks across the window (pages released), a batched prefill
    of unequal lengths in one bucket beside each prompt alone, the fused
    decode of all of them; with the kernels (interpret mode) too."""
    over = ({} if backend == "dense"
            else dict(attn_backend="pallas", prefill_buckets=(16,)))
    eng = InferenceEngine(CFG, EngineConfig(**dict(ENGINE, **over)),
                          params=weights[1],
                          pallas_interpret=backend == "pallas")
    lens = (37, 13, 10, 16) if backend == "dense" else (37, 13)
    seqs = [_seq(i, p, 6) for i, p in enumerate(_prompts(*lens))]
    eng.prefill(seqs[0])                       # chunks of 16, 16, 5
    eng.prefill_many(seqs[1:])                 # one bucket, unequal
    assert eng.window_pages_released > 0
    _run(eng, seqs)
    assert _worst(weights, seqs) < TOL
    # What the prefill programs ran for, counted in the graph: the
    # cross layers one position a prompt chunk.
    positions, cross = (int(v) for v in eng.aux_stats)
    assert positions >= sum(lens) and cross <= 3 + len(lens) + 3
    for s in seqs:
        eng.release(s)
    assert eng.state_slots.in_use == 0
    assert eng.allocator.num_free == eng.engine_cfg.num_pages - 1
    assert eng.win_allocator.num_free == eng.win_allocator.num_pages - 1


def test_every_cross_layer_reads_the_full_layers_one_slot():
    """At 12 layers there are two cross layers; the second's place among
    its kind is 1, and the full kind's pool has ONE slot: the engine
    reads slot 0 for both (off the chip a gather past the pool clamps
    and hides the difference; on it the kernel's page DMA halts the
    core)."""
    from tpu_inference.engine import engine as eng_mod
    cfg = dataclasses.replace(CFG, n_layers=12)
    ecfg = EngineConfig(**ENGINE)
    kv = kvc.alloc_kv_pages(cfg, ecfg)
    assert kv.k.shape[0] == 1 and len(cfg.kind_layers("cross")) == 2
    seen = []
    gather = kvc.gather_kv
    try:
        kvc.gather_kv = lambda kv, layer, bt: (seen.append(layer),
                                              gather(kv, layer, bt))[1]
        attn = eng_mod.make_kind_attn(
            cfg, ecfg.page_size, jnp.zeros((1, 2 * 32 + 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), jnp.ones((1, 1), bool),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32))
        q = jnp.ones((1, 1, cfg.n_heads, 2 * cfg.head_dim))
        for place in (0, 1):
            attn.kinds["cross"](place, q, None, None, kv)
    finally:
        kvc.gather_kv = gather
    assert seen == [0, 0]


def test_alone_and_batched_agree(weights):
    """Each prompt of a batched prefill reads what it reads alone: a
    padded position advances no state."""
    prompts = _prompts(9, 16, 11, seed=3)
    eng = _engine(weights)
    batched = [_seq(i, p, 5) for i, p in enumerate(prompts)]
    eng.prefill_many(batched)
    _run(eng, batched)
    for i, p in enumerate(prompts):
        alone = _seq(10 + i, p, 5)
        one = _engine(weights)
        one.prefill(alone)
        _run(one, [alone])
        assert alone.generated == batched[i].generated
        for pos, row in alone.kept_logits.items():
            assert float(np.abs(row - batched[i].kept_logits[pos]).max()) \
                < TOL


def test_a_lane_allowed_fewer_steps_advances_no_further(weights):
    """Inside one fused call of K = 4 a lane with 2 tokens left runs 2
    steps; the steps it is masked for must not advance its state (read
    again by the NEXT call of a sequence that goes on: here the other
    lane, whose rows must stay right while its neighbour idles)."""
    eng = _engine(weights)
    a, b = _seq(0, _prompts(12)[0], 3), _seq(1, _prompts(9, seed=4)[0], 11)
    eng.prefill_many([a, b])
    _run(eng, [a, b])
    assert len(a.generated) == 3 and len(b.generated) == 11
    assert _worst(weights, [a, b]) < TOL


def test_lanes_compacted_between_calls_keep_their_state(weights):
    """Lanes move down the ladder between dispatches; the state slot
    rides in the block-table row, so the state does not."""
    eng = _engine(weights, decode_ladder=(2, 4))
    seqs = [_seq(i, p, n) for i, (p, n) in enumerate(
        zip(_prompts(7, 9, 11, 13, seed=5), (2, 2, 14, 14)))]
    eng.prefill_many(seqs)
    slot_before = seqs[3].slot
    state = seqs[3].pages.state
    for _ in range(2):
        eng.decode_steps()
    for s in seqs[:2]:
        assert s.done
        eng.release(s)
    _run(eng, seqs[2:])
    assert seqs[3].slot < slot_before and seqs[3].pages.state == state
    assert eng.decode_rung == 2
    assert _worst(weights, seqs[2:]) < TOL


def test_preempt_and_recompute_resume(weights):
    """A preempted sequence re-prefills prompt + generated from zeros (a
    chunk at position 0 reads a zero state, whatever its slot held)."""
    eng = _engine(weights)
    seq = _seq(0, _prompts(21, seed=6)[0], 12)
    other = _seq(1, _prompts(10, seed=7)[0], 12)
    eng.prefill(seq)
    eng.prefill(other)
    eng.decode_steps()
    resets = eng.state_slots.resets_total
    eng.preempt(seq)
    assert eng.state_slots.in_use == 1 and seq.resume_base == 5
    eng.decode_steps()                        # the other dirties nothing
    eng.prefill(seq)                          # takes the freed slot again
    assert eng.state_slots.resets_total == resets + 1
    _run(eng, [seq, other])
    assert _worst(weights, [seq, other]) < TOL


def test_a_freed_state_slot_is_reused(weights):
    eng = _engine(weights, max_batch_size=2)
    first = [_seq(i, p, 3) for i, p in enumerate(_prompts(9, 12, seed=8))]
    eng.prefill_many(first)
    held = {s.pages.state for s in first}
    assert held == {1, 2} and eng.state_slots.num_free == 0
    assert not eng.can_admit(_seq(9, _prompts(5)[0]))
    _run(eng, first)
    for s in first:
        eng.release(s)
    third = _seq(2, _prompts(19, seed=9)[0], 6)
    assert eng.can_admit(third)
    eng.prefill(third)
    assert third.pages.state in held
    _run(eng, [third])
    assert _worst(weights, [third]) < TOL
    assert eng.state_slots.peak_in_use == 2


REFUSED = {
    "kv_quant": (dict(kv_quant="int8"), "kv_quant='int8'"),
    "host tier": (dict(host_cache_pages=8), "the host KV tier"),
    "int4": (dict(quant="int4"), "quant='int4' (no test holds int4"),
    "ngram": (dict(num_speculative_tokens=2),
              "a rejected draft would already have advanced"),
    "role": (dict(role="prefill"), "role='prefill' (P/D handoff"),
    "hybrid": (dict(hybrid_prefill=True), "hybrid_prefill (a prefill chunk"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_refused_at_construction(weights, what):
    over, said = REFUSED[what]
    with pytest.raises(ValueError) as e:
        _engine(weights, keep_logits=False, **over)
    assert "tiny-sambay (state-space layers) does not support" in str(e.value)
    assert said in str(e.value)


def test_int8_projections_leave_the_scan_alone(weights):
    """``quant='int8'`` quantizes the projections by name and nothing of
    the scan's own; the rows move by int8's rounding and no further (the
    benchmark's control: bench/parity.py --control)."""
    from tpu_inference.models.quant import QuantizedArray
    eng = _engine(weights, quant="int8")
    ssm = eng.params["ssm"]
    assert all(isinstance(ssm[k], QuantizedArray) for k in ("w_in", "w_out"))
    assert not any(isinstance(ssm[k], QuantizedArray) for k in (
        "conv_w", "w_x", "w_dt", "b_dt", "a_log", "d_skip"))
    assert isinstance(eng.params["mlp"]["w1"], QuantizedArray)
    assert not isinstance(eng.params["embed"], QuantizedArray)
    seq = _seq(0, _prompts(21, seed=11)[0], 6)
    eng.prefill(seq)
    _run(eng, [seq])
    assert 10 * TOL < _worst(weights, [seq]) < 0.3


def test_refused_draft_mesh_and_export(weights, capsys):
    with pytest.raises(ValueError, match="speculative decoding"):
        InferenceEngine(CFG, EngineConfig(**dict(
            ENGINE, num_speculative_tokens=2, keep_logits=False)),
            params=weights[1])
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
                ("dp", "tp", "sp"))
    with pytest.raises(ValueError, match=r"tp / sp / pp > 1"):
        InferenceEngine(CFG, EngineConfig(**ENGINE), params=weights[1],
                        mesh=mesh)
    eng = _engine(weights, enable_prefix_cache=True)
    assert eng.prefix_cache is None
    assert "a snapshot of every state-space" in capsys.readouterr().out
    seq = _seq(0, _prompts(9)[0])
    eng.prefill(seq)
    for call in (eng.export_sequence_kv, eng.export_sequence_kv_live,
                 eng.adopt_sequence):
        with pytest.raises(ValueError, match="KV export / adoption"):
            call(seq)


def test_state_slots_and_counters_on_metrics(weights):
    eng = _engine(weights)
    seqs = [_seq(i, p, 3) for i, p in enumerate(_prompts(20, 6))]
    eng.prefill(seqs[0])
    eng.prefill(seqs[1])
    _run(eng, seqs)
    from tpu_inference.telemetry import render_prometheus
    text = render_prometheus([({}, eng.telemetry.registry)])
    vals = {l.split()[0]: float(l.split()[1]) for l in text.splitlines()
            if l.startswith(("tpu_inf_state_", "tpu_inf_prefill_"))
            and "{" not in l}
    assert vals["tpu_inf_state_slots_total"] == 4
    assert vals["tpu_inf_state_slots_in_use"] == 2
    assert vals["tpu_inf_state_slots_peak"] == 2
    assert vals["tpu_inf_state_resets_total"] == 2
    assert vals["tpu_inf_prefill_positions_total"] == 26
    assert vals["tpu_inf_prefill_cross_positions_total"] == 3
    assert vals["tpu_inf_state_bytes_per_seq"] == 3 * 128 * (8 * 4 + 3 * 4)
    cm = eng.telemetry.cost_model
    assert cm.full_readers == 2 and cm.state_bytes == CFG.state_bytes_per_seq()
