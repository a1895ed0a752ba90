"""Batch ladder (README "Batch ladder"): HBM-sized decode concurrency
through a ladder of compiled decode graphs.

The engine compiles the decode graphs at every configured rung, admits
up to the TOP rung's lanes, dispatches at the smallest rung covering the
occupied slots, and steps between rungs as occupancy changes. These
tests pin the load-bearing claims: greedy outputs are byte-identical at
every rung (graph width is never a behavior change), in-flight lanes
survive grow/shrink transitions, the page-leak invariant holds across
switches, preemption and the host KV tier compose under a full top-rung
batch, warmup covers every rung so NO XLA compile happens mid-serving,
the packed-int4 KV layout is rung-invariant like bf16, and the staging
reuse / admission-headroom satellites behave as documented.
"""

import logging
import threading

import numpy as np
import pytest

from tpu_inference import config as cfgs
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.engine.scheduler import EngineScheduler
from tpu_inference.models import build_model
from tests._leak import assert_pool_clean

VOCAB = 256


@pytest.fixture(scope="module")
def model_setup():
    model_cfg = cfgs.tiny_llama(vocab_size=VOCAB)
    params, _ = build_model(model_cfg, seed=0)
    return model_cfg, params


def _ecfg(**kw):
    base = dict(page_size=8, num_pages=512, max_pages_per_seq=8,
                max_batch_size=16, decode_ladder=(4, 8, 16),
                prefill_buckets=(16, 32))
    base.update(kw)
    return cfgs.EngineConfig(**base)


def _submit_and_wait(sched, seqs, timeout=180.0, start=False):
    """Queue every request, then (with start=True) start the scheduler
    — pre-start submission makes burst tests deterministic: the first
    admission pass sees the whole burst instead of racing it."""
    events = {s.request_id: [] for s in seqs}
    done = {s.request_id: threading.Event() for s in seqs}
    for s in seqs:
        sched.submit(
            s, on_token=lambda sq, t: events[sq.request_id].append(t),
            on_finish=lambda sq: done[sq.request_id].set())
    if start:
        sched.start()
    for s in seqs:
        assert done[s.request_id].wait(timeout), f"request {s.request_id} hung"
    return events


def _prompts(n, rng=None, length=6):
    rng = rng or np.random.default_rng(7)
    return [rng.integers(0, VOCAB, size=length).tolist() for _ in range(n)]


def test_invalid_ladder_rejected(model_setup):
    model_cfg, params = model_setup
    for bad in ((16, 8), (4, 4, 16), (4, 8)):   # unordered, dup, wrong top
        with pytest.raises(ValueError, match="decode_ladder"):
            InferenceEngine(model_cfg, _ecfg(decode_ladder=bad),
                            params=params)


def test_byte_identity_across_rungs(model_setup):
    """The same request set served by the fixed base-rung graph and by
    the full ladder must emit byte-identical greedy tokens — graph
    width is a memory/latency decision, never a behavior change."""
    model_cfg, params = model_setup
    prompts = _prompts(12)

    def run(ecfg):
        engine = InferenceEngine(model_cfg, ecfg, params=params)
        sched = EngineScheduler(engine)
        seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                         max_new_tokens=24) for i, p in enumerate(prompts)]
        events = _submit_and_wait(sched, seqs, start=True)
        sched.stop(drain=True, timeout=20)
        assert_pool_clean(engine)
        return events, engine

    base_events, base_eng = run(_ecfg(max_batch_size=4, decode_ladder=()))
    lad_events, lad_eng = run(_ecfg())
    assert base_events == lad_events
    assert all(len(v) == 24 for v in lad_events.values())
    # The ladder demonstrably climbed past the base rung and the single-
    # rung engine never left its one graph.
    assert lad_eng.rung_peak == 16
    assert lad_eng.rung_switches_total >= 1
    assert base_eng.ladder == (4,) and base_eng.rung_switches_total == 0


def test_inflight_lanes_survive_grow_and_shrink(model_setup):
    """Lanes admitted before a rung transition keep decoding through it
    (dispatch-ahead in flight included) and finish with their full
    budgets — growing compiles nothing away, shrinking steps down only
    once the high slots drain."""
    model_cfg, params = model_setup
    ecfg = _ecfg(decode_steps_per_call=4, decode_pipeline_depth=2,
                 latency_decode_threshold=0)
    engine = InferenceEngine(model_cfg, ecfg, params=params)
    # Reference: the same long-budget requests at the single base rung.
    ref_ecfg = _ecfg(max_batch_size=4, decode_ladder=(),
                     decode_steps_per_call=4)
    ref_engine = InferenceEngine(model_cfg, ref_ecfg, params=params)
    rng = np.random.default_rng(11)
    long_prompts = _prompts(3, rng)
    want = ref_engine.generate(long_prompts, max_new_tokens=48)

    sched = EngineScheduler(engine).start()
    try:
        longs = [Sequence(request_id=i, prompt_tokens=list(p),
                          max_new_tokens=48)
                 for i, p in enumerate(long_prompts)]
        done = {s.request_id: threading.Event() for s in longs}
        events = {s.request_id: [] for s in longs}
        for s in longs:
            sched.submit(s,
                         lambda sq, t: events[sq.request_id].append(t),
                         lambda sq: done[sq.request_id].set())
        # Wait until the longs are decoding, then burst 12 shorts so the
        # rung climbs 4 -> 16 with the longs' dispatch-ahead calls in
        # flight; the shorts finish first, shrinking back down.
        import time
        deadline = time.time() + 60
        while (not all(events.values())) and time.time() < deadline:
            time.sleep(0.005)
        shorts = [Sequence(request_id=100 + i,
                           prompt_tokens=_prompts(1, rng)[0],
                           max_new_tokens=16) for i in range(12)]
        short_events = _submit_and_wait(sched, shorts)
        for s in longs:
            assert done[s.request_id].wait(120)
    finally:
        sched.stop(drain=True, timeout=20)
    for i, s in enumerate(longs):
        assert events[s.request_id] == want[i]      # survived transitions
        assert len(s.generated) == 48
    assert all(len(v) == 16 for v in short_events.values())
    assert engine.rung_peak == 16
    assert engine.rung_switches_total >= 2          # grew AND shrank
    assert_pool_clean(engine)


def test_rung_steps_down_after_drain(model_setup):
    """Once high slots drain, compaction relocates survivors and the
    next dispatch runs a smaller compiled graph."""
    model_cfg, params = model_setup
    engine = InferenceEngine(model_cfg, _ecfg(), params=params)
    prompts = _prompts(10)
    for i, p in enumerate(prompts):
        engine.prefill(Sequence(request_id=i, prompt_tokens=list(p),
                                max_new_tokens=32))
    engine.decode_steps()
    assert engine.decode_rung == 16
    # Finish the 8 highest slots; survivors compact into low slots.
    for s in list(engine.slots)[2:]:
        if s is not None:
            s.done = True
            engine.release(s)
    engine.decode_steps()
    assert engine.decode_rung == 4
    assert all(s.slot < 4 for s in engine.active_sequences())
    for s in engine.active_sequences():
        s.done = True
        engine.release(s)
    assert_pool_clean(engine)


def test_preemption_and_host_tier_compose_at_full_top_rung(model_setup):
    """A full top-rung batch under optimistic admission with the host
    KV tier attached: watermark preemption fires, recompute-resume
    completes every request, greedy outputs match the uncontended run,
    and the pool invariant holds — more lanes never corrupt the
    admission/preemption/tiering machinery."""
    model_cfg, params = model_setup
    rng = np.random.default_rng(3)
    prompts = _prompts(12, rng, length=8)

    ref = InferenceEngine(model_cfg, _ecfg(max_batch_size=4,
                                           decode_ladder=()),
                          params=params)
    want = {i: toks
            for i, toks in enumerate(ref.generate(prompts,
                                                  max_new_tokens=16))}

    ecfg = _ecfg(max_batch_size=8, decode_ladder=(2, 4, 8),
                 num_pages=16, admission="optimistic",
                 optimistic_headroom_pages=1, preempt_watermark_pages=4,
                 host_cache_pages=64)
    engine = InferenceEngine(model_cfg, ecfg, params=params)
    assert engine.host_pool is not None
    sched = EngineScheduler(engine)
    try:
        seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                         max_new_tokens=16)
                for i, p in enumerate(prompts)]
        events = _submit_and_wait(sched, seqs, start=True)
    finally:
        sched.stop(drain=True, timeout=30)
    for i, s in enumerate(seqs):
        assert s.finish_reason == "length", (i, s.finish_reason)
        assert events[i] == want[i]
    # The tight pool genuinely exercised preemption under the ladder.
    assert engine.preemptions_total >= 1
    assert engine.rung_peak >= 4
    assert_pool_clean(engine)


def test_warmup_covers_every_rung_no_midserve_compile(model_setup):
    """The warmup-completeness satellite: after the first served
    request, NO XLA compile may occur — a burst that climbs the whole
    ladder (and steps back down, single-step latency graph included)
    must find every executable warm. Mid-serving compiles block the GIL
    and starve the HTTP loop (ADVICE r3)."""
    import jax

    model_cfg, params = model_setup
    engine = InferenceEngine(
        model_cfg, _ecfg(decode_steps_per_call=4), params=params)
    engine.warmup()

    records = []
    handler = logging.Handler()
    handler.emit = lambda rec: records.append(rec.getMessage())
    loggers = [logging.getLogger(n)
               for n in ("jax._src.interpreters.pxla", "jax._src.dispatch")]
    jax.config.update("jax_log_compiles", True)
    for lg in loggers:
        lg.addHandler(handler)
        lg.setLevel(logging.DEBUG)
    try:
        sched = EngineScheduler(engine).start()
        try:
            # First served request: any one-time non-graph stragglers
            # (transfer layouts etc.) land here, per the satellite's
            # contract.
            _submit_and_wait(sched, [Sequence(
                request_id=0, prompt_tokens=_prompts(1)[0],
                max_new_tokens=4)])
            records.clear()
            # Burst across every rung, then drain back to one lane.
            seqs = [Sequence(request_id=1 + i,
                             prompt_tokens=_prompts(1)[0],
                             max_new_tokens=16 + (i % 3))
                    for i in range(15)]
            _submit_and_wait(sched, seqs)
        finally:
            sched.stop(drain=True, timeout=20)
    finally:
        jax.config.update("jax_log_compiles", False)
        for lg in loggers:
            lg.removeHandler(handler)
    assert engine.rung_peak == 16       # the burst really climbed
    compiles = [m for m in records if m.startswith("Compiling ")]
    assert not compiles, (
        f"XLA compiled {len(compiles)} graph(s) after the first served "
        f"request: {compiles[:4]}")
    assert_pool_clean(engine)


@pytest.mark.parametrize("kv_quant", ["none", "int4"])
def test_kv_layout_rung_invariant(model_setup, kv_quant):
    """int4 lane hygiene: at EVERY ladder rung the packed-int4 KV
    layout emits exactly the tokens the base rung emits, just like the
    bf16 pool — rung width never touches the nibble-packed codes. (The
    cross-backend dense==pallas equality for int4 is pinned in
    test_kv_quant; this pins rung-invariance so the TPU int4 lane can
    be recorded at any ladder rung without new failure modes.)"""
    model_cfg, params = model_setup
    prompts = _prompts(8, np.random.default_rng(5), length=10)

    def outs(batch, ladder, n):
        eng = InferenceEngine(
            model_cfg, _ecfg(max_batch_size=batch, decode_ladder=ladder,
                             kv_quant=kv_quant),
            params=params)
        out = eng.generate(prompts[:n], max_new_tokens=8)
        assert_pool_clean(eng)
        return out

    base = outs(2, (), 8)                 # serial waves of 2
    for rung_count in (4, 8):             # exercises rungs 4 and 4->8
        assert outs(8, (4, 8), rung_count) == base[:rung_count]


def test_stage_reuse_is_output_invariant(model_setup):
    """stage_host_reuse=False (rebuild-per-dispatch, the bubble
    comparison arm) and the default reuse path must emit identical
    tokens under rung churn."""
    model_cfg, params = model_setup
    prompts = _prompts(10, np.random.default_rng(9))

    def run(reuse):
        eng = InferenceEngine(
            model_cfg, _ecfg(stage_host_reuse=reuse), params=params)
        out = eng.generate(prompts, max_new_tokens=12)
        assert_pool_clean(eng)
        return out

    assert run(True) == run(False)


def test_ladder_admit_headroom_guards_growth(model_setup):
    """ladder_admit_headroom_pages: growth past the base rung must
    leave the configured reclaimable slack, so a tight pool keeps the
    batch at the base rung instead of thrashing; with the guard off the
    same pool climbs."""
    model_cfg, params = model_setup
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, VOCAB, size=8).tolist() for _ in range(4)]

    def run(headroom):
        ecfg = _ecfg(max_batch_size=4, decode_ladder=(2, 4),
                     num_pages=12, max_pages_per_seq=2,
                     ladder_admit_headroom_pages=headroom)
        eng = InferenceEngine(model_cfg, ecfg, params=params)
        sched = EngineScheduler(eng)
        seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                         max_new_tokens=8)
                for i, p in enumerate(prompts)]
        _submit_and_wait(sched, seqs, start=True)
        sched.stop(drain=True, timeout=20)
        assert all(s.finish_reason == "length" for s in seqs)
        assert_pool_clean(eng)
        return eng.rung_peak

    assert run(headroom=0) == 4         # unguarded pool climbs
    assert run(headroom=6) == 2         # guarded growth holds the base


def test_chunk_only_calls_never_block_the_pipeline(model_setup):
    """A chunk-only prefill call in flight (rung 0: no decode half, no
    carry to fold) must not read as a rung cap — that would drain the
    pipeline every chunk and re-serialize the hybrid chaining PR 4
    built. Only decode-half calls constrain the staging width."""
    model_cfg, params = model_setup
    engine = InferenceEngine(model_cfg, _ecfg(decode_pipeline_depth=2),
                             params=params)
    engine.prefill(Sequence(request_id=0, prompt_tokens=[1, 2, 3],
                            max_new_tokens=8))
    chunk_only = {"outs": None, "final": None, "final_window": None,
                  "allowed": {}, "seqs": {}, "rung": 0, "prefill": None}
    engine._inflight.append(chunk_only)
    assert not engine._pipeline_rung_blocked()
    engine._inflight.clear()
    for s in engine.active_sequences():
        s.done = True
        engine.release(s)
    assert_pool_clean(engine)


def test_parse_decode_ladder_validates_before_boot():
    """--decode-ladder specs fail as usage errors, not as an engine
    ValueError after the checkpoint loads."""
    from tpu_inference.engine import autosize

    assert autosize.parse_decode_ladder("auto", 32) == (8, 16, 32)
    assert autosize.parse_decode_ladder("off", 32) == (32,)
    assert autosize.parse_decode_ladder("4,8,16", 16) == (4, 8, 16)
    for bad, top in (("8,x", 32), ("8,16", 32), ("16,8,32", 32),
                     ("0,32", 32), ("8,8,32", 32)):
        with pytest.raises(ValueError, match="decode.ladder"):
            autosize.parse_decode_ladder(bad, top)


@pytest.mark.parametrize("chip", [None, "TPU v5 lite"])
def test_metrics_expose_rung_occupancy_mfu(model_setup, monkeypatch, chip):
    """/metrics surfaces the ladder telemetry the acceptance names:
    active rung, top rung, graph-switch counter, lane occupancy — and
    the derived MFU estimate only where there is a chip to rate against
    (steered here: on the tests' CPU the gauge must be ABSENT, not
    computed against a v5e)."""
    from tpu_inference import telemetry as tm
    from tpu_inference.engine import autosize

    if chip:
        monkeypatch.setattr(autosize, "chip_spec",
                            lambda device=None: autosize.CHIP_SPECS[chip])
    model_cfg, params = model_setup
    engine = InferenceEngine(model_cfg, _ecfg(), params=params)
    EngineScheduler(engine)             # binds the MFU gauge
    text = tm.render_prometheus([({}, engine.telemetry.registry)])
    for name in ("tpu_inf_decode_rung", "tpu_inf_decode_ladder_top",
                 "tpu_inf_rung_switches_total", "tpu_inf_decode_occupancy"):
        assert f"\n{name}" in text or text.startswith(name), name
    assert ("\ntpu_inf_mfu_estimate" in text) == bool(chip)
    assert (engine.telemetry.mfu_estimate() is not None) == bool(chip)
    assert "tpu_inf_decode_ladder_top 16" in text


