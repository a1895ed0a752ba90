"""N-gram speculation (README "Speculative decoding"): the one kind of
speculation, on when ``num_speculative_tokens > 0``.

The load-bearing claims: greedy output is byte-identical to plain decode
(speculation is a scheduling decision, never a behavior change) through
the engine AND through the scheduler at every ladder rung, with
dispatch-ahead staging, with the repetition penalty applied, and across
preemption/recompute-resume; the adaptive-γ throttle converges to γ=0 on
adversarial (echo-free) streams so spec can never lose; the host KV tier
and the decode ladder stay ACTIVE under speculation;
warmup covers (every rung) x (every verify width) so no XLA compile ever
lands mid-serving; and the pool-leak invariant holds across spec rounds.
"""

import logging
import threading

import numpy as np
import pytest

from tpu_inference import config as cfgs
from tpu_inference.engine import engine as engine_mod
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.engine.scheduler import EngineScheduler
from tpu_inference.engine.speculative import ngram_propose
from tpu_inference.models import build_model
from tests._leak import assert_pool_clean

VOCAB = 256


@pytest.fixture(scope="module")
def model_setup():
    model_cfg = cfgs.tiny_llama(vocab_size=VOCAB)
    params, _ = build_model(model_cfg, seed=0)
    return model_cfg, params


def _ecfg(**kw):
    base = dict(page_size=8, num_pages=512, max_pages_per_seq=16,
                max_batch_size=4, prefill_buckets=(16, 32, 64))
    base.update(kw)
    return cfgs.EngineConfig(**base)


def _ngram_kw(gamma=4, **kw):
    return dict(num_speculative_tokens=gamma, **kw)


def _submit_and_wait(sched, seqs, timeout=180.0, start=False):
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


# ---------------------------------------------------------------- proposer

def test_ngram_propose_basics():
    # Suffix [1,2,3] matched one period back: proposal continues the
    # cycle, TILING past the end of history (the repetition-loop steady
    # state would otherwise truncate to one period).
    assert ngram_propose([1, 2, 3] * 6, 5, 3).tolist() == [1, 2, 3, 1, 2]
    # 1-gram fallback when no longer match exists.
    assert ngram_propose([5, 9, 5], 4, 3).tolist() == [9, 5, 9, 5]
    # Most RECENT match wins (recency beats the conversation opener).
    assert ngram_propose([7, 1, 7, 2, 7], 1, 1).tolist() == [2]
    # No match / too-short histories propose nothing.
    assert ngram_propose([1, 2, 3, 4, 5], 4, 3).size == 0
    assert ngram_propose([9], 4, 3).size == 0
    assert ngram_propose([], 4, 3).size == 0
    assert ngram_propose([1, 1, 1], 0, 3).size == 0


# ------------------------------------------------------- byte identity

def test_greedy_byte_identity_engine(model_setup):
    """ngram-spec greedy output == plain greedy output, token for token,
    and the pool comes back clean after speculative rounds."""
    model_cfg, params = model_setup
    plain = InferenceEngine(model_cfg, _ecfg(), params=params)
    ng = InferenceEngine(model_cfg, _ecfg(**_ngram_kw()), params=params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, VOCAB, size=n).tolist()
               for n in (5, 13, 22, 40)]
    want = plain.generate(prompts, max_new_tokens=48)
    got = ng.generate(prompts, max_new_tokens=48)
    assert got == want
    assert ng.spec_drafted > 0 and ng.spec_accepted > 0
    assert ng.spec_rounds_total > 0
    assert_pool_clean(ng)


def test_ngram_keeps_ladder_and_host_tier(model_setup):
    """Speculation keeps the decode ladder (no single-rung collapse)
    and the host KV tier (no second pool to desync) — the gates PRs 6-7
    built stay active."""
    model_cfg, params = model_setup
    eng = InferenceEngine(
        model_cfg, _ecfg(max_batch_size=16, decode_ladder=(4, 8, 16),
                         host_cache_pages=32, **_ngram_kw()),
        params=params)
    assert eng.ladder == (4, 8, 16)
    assert eng.host_pool is not None
    assert eng.spec_enabled
    # Verify graph widths: the full γ+1 round plus the narrow probe.
    assert eng._spec_widths == [2, 5]


def test_greedy_byte_identity_through_scheduler_every_rung(model_setup):
    """The same request set served by the plain base-rung engine and by
    ngram spec over the full ladder must stream byte-identical greedy
    tokens — and the ladder must demonstrably climb, so every rung's
    verify graph really served traffic."""
    model_cfg, params = model_setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, VOCAB, size=6).tolist() for _ in range(12)]

    def run(ecfg):
        engine = InferenceEngine(model_cfg, ecfg, params=params)
        sched = EngineScheduler(engine)
        seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                         max_new_tokens=24) for i, p in enumerate(prompts)]
        events = _submit_and_wait(sched, seqs, start=True)
        sched.stop(drain=True, timeout=20)
        assert_pool_clean(engine)
        return events, engine

    base_events, _ = run(_ecfg(max_batch_size=4, decode_ladder=(),
                               max_pages_per_seq=8))
    spec_events, eng = run(_ecfg(max_batch_size=16, max_pages_per_seq=8,
                                 decode_ladder=(4, 8, 16), **_ngram_kw()))
    assert base_events == spec_events
    assert eng.rung_peak == 16
    assert eng.spec_drafted > 0


def test_greedy_byte_identity_dispatch_ahead(model_setup):
    """Spec rounds staged into the dispatch-ahead pipeline (depth > 1,
    sync-then-stage) emit the same greedy bytes as plain decode, and the
    pipeline drains clean at shutdown."""
    model_cfg, params = model_setup
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, VOCAB, size=8).tolist() for _ in range(6)]

    plain = InferenceEngine(model_cfg, _ecfg(), params=params)
    want = plain.generate(prompts, max_new_tokens=32)

    engine = InferenceEngine(
        model_cfg, _ecfg(decode_pipeline_depth=2,
                         latency_decode_threshold=0, **_ngram_kw()),
        params=params)
    sched = EngineScheduler(engine)
    seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                     max_new_tokens=32) for i, p in enumerate(prompts)]
    events = _submit_and_wait(sched, seqs, start=True)
    sched.stop(drain=True, timeout=20)
    assert [events[i] for i in range(len(prompts))] == want
    assert engine.spec_rounds_total > 0
    assert_pool_clean(engine)


def test_repeat_penalty_composes(model_setup, monkeypatch):
    """The repetition penalty applies inside the verify round (each
    position penalized against the window rolled with its accepted
    prefix), so penalized greedy ngram output == penalized plain output
    and the server has no 'ignored under spec' warning to give.

    Two passes: the REAL proposer (the penalty suppresses the tiny
    model's cycles, so proposals mostly reject — the rejection/
    correction path must still match the penalized argmax), then an
    ORACLE proposer feeding the plain arm's own continuation — those
    proposals verify only if the verify-phase distribution is penalized
    exactly like sequential decode, so high acceptance here IS the
    penalty-composition proof."""
    model_cfg, params = model_setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, size=9).tolist() for _ in range(3)]

    def run(ecfg):
        eng = InferenceEngine(model_cfg, ecfg, params=params)
        seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                         max_new_tokens=32, repeat_penalty=1.3,
                         repeat_last_n=32)
                for i, p in enumerate(prompts)]
        for s in seqs:
            eng.prefill(s)
        while eng.active_sequences():
            eng.decode_steps()
        out = [list(s.generated) for s in seqs]
        for s in seqs:
            eng.release(s)
        assert_pool_clean(eng)
        return out, eng

    # K=1 keeps the mixed-batch gate out of the way: this test pins the
    # penalty math, and partial-proposal rounds must actually dispatch
    # verifies for the rejection path to run.
    want, _ = run(_ecfg(decode_steps_per_call=1))
    got, eng = run(_ecfg(decode_steps_per_call=1, **_ngram_kw()))
    assert got == want
    assert eng.spec_drafted > 0      # verify rounds genuinely ran

    # Oracle pass: propose the penalized plain continuation itself.
    ref = {tuple(p): w for p, w in zip(prompts, want)}

    def oracle(hist, gamma, max_n, min_n=1):
        for p, w in ref.items():
            if tuple(hist[:len(p)]) == p:
                done = len(hist) - len(p)
                return np.asarray(w[done:done + gamma], np.int32)
        return np.empty((0,), np.int32)

    monkeypatch.setattr(engine_mod, "ngram_propose", oracle)
    got2, eng2 = run(_ecfg(decode_steps_per_call=1, **_ngram_kw()))
    assert got2 == want
    # An unpenalized verify distribution would argmax-reject these
    # proposals; near-total acceptance proves the penalty landed.
    assert eng2.spec_accepted >= 0.8 * eng2.spec_drafted > 0
    # Engine-side contract the server warning logic keys on:
    seq = Sequence(request_id=99, prompt_tokens=[1], max_new_tokens=1,
                   repeat_penalty=1.3, repeat_last_n=32)
    assert eng2._penalty_arrays(seq) == (1.3, 32)


# ------------------------------------------------------ adaptive gamma

def test_adaptive_gamma_throttles_adversarial_stream(model_setup,
                                                     monkeypatch):
    """An adversarial proposer (every proposal wrong) must converge to
    γ=0: the EWMA throttles the lane, subsequent rounds degrade to the
    plain fused-K graph (fallback), probes stay on the narrow verify
    width, and greedy output remains byte-identical throughout — spec
    never loses."""
    model_cfg, params = model_setup
    plain = InferenceEngine(model_cfg, _ecfg(), params=params)
    prompt = [1, 2, 3, 4, 5, 6]
    want = plain.generate([prompt], max_new_tokens=50)[0]

    eng = InferenceEngine(
        model_cfg, _ecfg(**_ngram_kw(spec_probe_every=8)), params=params)
    monkeypatch.setattr(
        engine_mod, "ngram_propose",
        lambda hist, gamma, max_n, min_n=1: np.full((gamma,), 7, np.int32))
    s = Sequence(request_id=0, prompt_tokens=list(prompt),
                 max_new_tokens=50)
    eng.prefill(s)
    while eng.active_sequences():
        eng.decode_steps()
    eng.release(s)
    assert s.generated == want
    assert s.spec_gamma == 0                      # converged to throttle
    assert s.spec_accept_ewma < 0.35
    assert eng.spec_throttles_total >= 1
    assert eng.spec_fallback_rounds >= 1          # plain rounds took over
    assert eng.spec_accepted == 0
    # Backoff engaged: failed probes doubled the re-check interval.
    assert s.spec_probe_interval >= 8
    assert_pool_clean(eng)


def test_probe_uses_narrow_width(model_setup):
    """A probe round (single-token proposals) picks the compiled narrow
    verify width instead of paying the full γ+1 forward."""
    model_cfg, params = model_setup
    eng = InferenceEngine(model_cfg, _ecfg(**_ngram_kw(gamma=5)),
                          params=params)
    assert eng._spec_widths == [2, 6]
    assert eng._spec_width_for({0: np.array([9], np.int32)}) == 2
    assert eng._spec_width_for({0: np.array([9, 9], np.int32)}) == 6
    # A throttled sequence's probe proposes exactly one token.
    s = Sequence(request_id=0, prompt_tokens=[1], max_new_tokens=4,
                 spec_gamma=0, spec_probe_countdown=1,
                 spec_probe_interval=48)
    assert eng._seq_spec_gamma(s) == 1


def test_mixed_batch_gate(model_setup):
    """Fused-K batches (K > 1): a lone low-confidence proposer must not
    drag bystander lanes into 1-token verify rounds — the gate degrades
    the round to plain fused decode unless the proposers' expected
    accepted tokens cover one token per bystander. K == 1 has no
    bystander deficit, so the gate stays open."""
    model_cfg, params = model_setup
    eng = InferenceEngine(
        model_cfg, _ecfg(decode_steps_per_call=8, **_ngram_kw(gamma=5)),
        params=params)
    seqs = []
    for i in range(4):
        s = Sequence(request_id=i, prompt_tokens=[1 + i, 2, 3],
                     max_new_tokens=8)
        eng.prefill(s)
        seqs.append(s)
    lone = {seqs[0].slot: np.array([7], np.int32)}
    seqs[0].spec_accept_ewma = 0.5
    # 0.5 expected < 3 bystanders: degrade to plain.
    assert eng._gate_mixed_batch(seqs, lone) == {}
    # Every lane proposing (no bystanders): always dispatch.
    full = {s.slot: np.array([7, 7, 7], np.int32) for s in seqs}
    assert eng._gate_mixed_batch(seqs, full) == full
    # Confident proposers can carry bystanders.
    seqs[0].spec_accept_ewma = 1.0
    rich = {seqs[0].slot: np.array([7] * 5, np.int32)}
    assert eng._gate_mixed_batch(seqs, rich) == rich
    # K == 1: no gate (a verify round strictly dominates a 1-step call).
    eng1 = InferenceEngine(
        model_cfg, _ecfg(decode_steps_per_call=1, **_ngram_kw(gamma=5)),
        params=params)
    s1 = Sequence(request_id=0, prompt_tokens=[1, 2, 3], max_new_tokens=8,
                  spec_accept_ewma=0.01)
    eng1.prefill(s1)
    s2 = Sequence(request_id=1, prompt_tokens=[4, 5, 6], max_new_tokens=8)
    eng1.prefill(s2)
    lone1 = {s1.slot: np.array([7], np.int32)}
    assert eng1._gate_mixed_batch([s1, s2], lone1) == lone1
    for e, group in ((eng, seqs), (eng1, [s1, s2])):
        for s in group:
            s.done = True
            e.release(s)
        assert_pool_clean(e)


def test_adaptive_gamma_recovers_on_echo(model_setup):
    """A throttled sequence re-earns its γ: one clean probe lifts the
    EWMA back over the threshold and restores the full depth."""
    model_cfg, params = model_setup
    eng = InferenceEngine(model_cfg, _ecfg(**_ngram_kw(gamma=4)),
                          params=params)
    s = Sequence(request_id=0, prompt_tokens=[1], max_new_tokens=4,
                 spec_gamma=1, spec_accept_ewma=0.1,
                 spec_probe_interval=48)
    eng._spec_update_adaptive(s, drafted=1, accepted=1)
    assert s.spec_gamma == 4
    assert s.spec_probe_interval == 0


# ------------------------------------------- preemption / recompute-resume

def test_preemption_recompute_resume_composes(model_setup):
    """A tight pool under optimistic admission with ngram spec AND the
    host tier: watermark preemption fires against in-flight spec
    sequences, recompute-resume finishes every request, greedy outputs
    match the uncontended plain run, and the pool invariant holds."""
    model_cfg, params = model_setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, size=8).tolist() for _ in range(12)]

    ref = InferenceEngine(model_cfg, _ecfg(max_batch_size=4,
                                           max_pages_per_seq=8),
                          params=params)
    want = {i: toks for i, toks in
            enumerate(ref.generate(prompts, max_new_tokens=16))}

    ecfg = _ecfg(max_batch_size=8, decode_ladder=(2, 4, 8),
                 max_pages_per_seq=8, num_pages=16,
                 admission="optimistic", optimistic_headroom_pages=1,
                 preempt_watermark_pages=4, host_cache_pages=64,
                 **_ngram_kw())
    engine = InferenceEngine(model_cfg, ecfg, params=params)
    assert engine.host_pool is not None      # tier live under ngram spec
    sched = EngineScheduler(engine)
    seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                     max_new_tokens=16) for i, p in enumerate(prompts)]
    try:
        events = _submit_and_wait(sched, seqs, start=True)
    finally:
        sched.stop(drain=True, timeout=30)
    for i, s in enumerate(seqs):
        assert s.finish_reason == "length", (i, s.finish_reason)
        assert events[i] == want[i]
    assert engine.preemptions_total >= 1
    assert_pool_clean(engine)


# ------------------------------------------------------- zero compile

def test_warmup_covers_rungs_and_widths_no_midserve_compile(model_setup):
    """Extends the test_ladder.py zero-compile pin to ngram spec: after
    the first served request, a burst that climbs the whole ladder —
    speculating all the way — must find every verify width AND every
    plain fallback graph warm. No XLA compile mid-serving."""
    import jax

    model_cfg, params = model_setup
    engine = InferenceEngine(
        model_cfg, _ecfg(max_batch_size=16, decode_ladder=(4, 8, 16),
                         max_pages_per_seq=8, decode_steps_per_call=4,
                         **_ngram_kw()),
        params=params)
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
    rng = np.random.default_rng(11)
    try:
        sched = EngineScheduler(engine).start()
        try:
            _submit_and_wait(sched, [Sequence(
                request_id=0,
                prompt_tokens=rng.integers(0, VOCAB, size=6).tolist(),
                max_new_tokens=4)])
            records.clear()
            seqs = [Sequence(request_id=1 + i,
                             prompt_tokens=rng.integers(
                                 0, VOCAB, size=6).tolist(),
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
    assert engine.spec_rounds_total > 0  # and really speculated
    compiles = [m for m in records if m.startswith("Compiling ")]
    assert not compiles, (
        f"XLA compiled {len(compiles)} graph(s) after the first served "
        f"request under ngram spec: {compiles[:4]}")
    assert_pool_clean(engine)


# --------------------------------------------------------- validation

def test_spec_config_validation():
    from tpu_inference.config import validate_spec_config

    validate_spec_config(4, 3)
    with pytest.raises(ValueError, match="num-speculative-tokens"):
        validate_spec_config(0, 3)
    with pytest.raises(ValueError, match="num-speculative-tokens"):
        validate_spec_config(17, 3)
    with pytest.raises(ValueError, match="ngram-window"):
        validate_spec_config(4, 0)
    with pytest.raises(ValueError, match="ngram-window"):
        validate_spec_config(4, 9)


def test_engine_rejects_bad_spec_config(model_setup):
    model_cfg, params = model_setup
    with pytest.raises(ValueError, match="num-speculative-tokens"):
        InferenceEngine(model_cfg, _ecfg(**_ngram_kw(gamma=17)),
                        params=params)
    with pytest.raises(ValueError, match="ngram-window"):
        InferenceEngine(model_cfg, _ecfg(**_ngram_kw(ngram_window=9)),
                        params=params)


def test_spec_stats_snapshot(model_setup):
    """Scheduler stats expose the speculative block (mode/γ/counters)
    and /metrics exposes the spec series."""
    from tpu_inference import telemetry as tm

    model_cfg, params = model_setup
    engine = InferenceEngine(model_cfg, _ecfg(**_ngram_kw()),
                             params=params)
    sched = EngineScheduler(engine)
    out = engine.generate([[1, 2, 3] * 4], max_new_tokens=12)
    assert len(out[0]) == 12
    snap = sched.stats.snapshot(engine)
    spec = snap["speculative"]
    assert spec["mode"] == "ngram" and spec["gamma"] == 4
    assert spec["drafted"] >= spec["accepted"] >= 0
    assert spec["rounds"] + spec["fallback_rounds"] > 0
    text = tm.render_prometheus([({}, engine.telemetry.registry)])
    for name in ("tpu_inf_spec_drafted_total",
                 "tpu_inf_spec_accepted_total",
                 "tpu_inf_spec_acceptance_rate",
                 "tpu_inf_spec_gamma",
                 "tpu_inf_spec_rounds_total",
                 "tpu_inf_spec_fallback_rounds_total",
                 "tpu_inf_spec_throttles_total"):
        assert f"\n{name}" in text or text.startswith(name), name


# ------------------------------------------- what the draft tests held

def _serve(eng, seqs):
    """Prefill ``seqs``, run rounds until none is active, release."""
    for s in seqs:
        eng.prefill(s)
    while eng.active_sequences():
        eng.decode_steps()
    for s in seqs:
        eng.release(s)


ECHO = [3, 4, 5, 6] * 4         # proposals from the first round on


@pytest.fixture(scope="module")
def plain_engine(model_setup):
    model_cfg, params = model_setup
    return InferenceEngine(model_cfg, _ecfg(), params=params)


@pytest.fixture(scope="module")
def spec_engine(model_setup):
    """Shared γ=3 engine (counters are cumulative across tests: assert
    deltas or > 0, never totals)."""
    model_cfg, params = model_setup
    return InferenceEngine(model_cfg, _ecfg(num_speculative_tokens=3),
                           params=params)


def test_num_speculative_tokens_alone_turns_the_verify_round_on(
        plain_engine, spec_engine):
    assert spec_engine.spec_enabled and not plain_engine.spec_enabled
    assert spec_engine._spec_widths == [2, 4]
    rounds0 = spec_engine.spec_rounds_total
    want = plain_engine.generate([ECHO], max_new_tokens=24)
    assert spec_engine.generate([ECHO], max_new_tokens=24) == want
    assert spec_engine.spec_rounds_total > rounds0
    assert plain_engine.spec_rounds_total == 0


@pytest.mark.parametrize("stop", ["eos", "budget"])
def test_stops_at_the_same_token_as_plain_decode(plain_engine, spec_engine,
                                                 monkeypatch, stop):
    """EOS inside an accepted run, and a ``max_new_tokens`` budget that
    ends mid-round, cut the stream where plain decode cuts it: what the
    device emitted past the stop is dropped. An oracle proposer (the
    plain continuation itself) makes every round accept its whole
    proposal, so where the rounds end is known."""
    ref = plain_engine.generate([ECHO], max_new_tokens=24)[0]

    def oracle(hist, gamma, max_n, min_n=1):
        done = len(hist) - len(ECHO)
        return np.asarray(ref[done:done + gamma], np.int32)

    monkeypatch.setattr(engine_mod, "ngram_propose", oracle)
    probe = Sequence(request_id=0, prompt_tokens=list(ECHO),
                     max_new_tokens=24)
    spec_engine.prefill(probe)
    ends = []                       # tokens generated after each round
    while spec_engine.active_sequences():
        spec_engine.decode_steps()
        ends.append(len(probe.generated))
    spec_engine.release(probe)
    assert probe.generated == ref and len(ends) < 12   # runs accepted
    # Positions with more of their round behind them.
    inside = [i for i in range(1, 23) if i + 1 not in ends]
    if stop == "eos":
        # ... whose token occurs there FIRST (a tiny random model
        # repeats; an earlier occurrence would stop the stream earlier).
        k = max(i for i in inside if ref[i] not in ref[:i])
        kw, budget, why = dict(eos_token_id=ref[k]), 24, "stop"
    else:
        k = inside[len(inside) // 2]
        kw, budget, why = dict(), k + 1, "length"
    s = Sequence(request_id=1, prompt_tokens=list(ECHO),
                 max_new_tokens=budget, **kw)
    _serve(spec_engine, [s])
    assert s.generated == ref[:k + 1] and s.finish_reason == why
    assert_pool_clean(spec_engine)


def test_sampled_run_finishes(spec_engine):
    """Temperature > 0 through the verify round: the right count, valid
    ids, and the accounting stays ordered."""
    out = spec_engine.generate([ECHO], max_new_tokens=20,
                               temperature=0.8)[0]
    assert len(out) == 20 and all(0 <= t < VOCAB for t in out)
    assert spec_engine.spec_drafted >= spec_engine.spec_accepted >= 0
    assert spec_engine.spec_drafted > 0


def test_a_sequence_joins_a_speculating_batch(plain_engine, spec_engine):
    """A sequence prefilled while another is mid-generation perturbs
    neither stream."""
    rng = np.random.default_rng(2)
    p1 = ECHO + rng.integers(0, VOCAB, size=3).tolist()
    p2 = rng.integers(0, VOCAB, size=17).tolist()
    w1 = plain_engine.generate([p1], max_new_tokens=20)[0]
    w2 = plain_engine.generate([p2], max_new_tokens=12)[0]
    s1 = Sequence(request_id=3, prompt_tokens=p1, max_new_tokens=20)
    s2 = Sequence(request_id=4, prompt_tokens=p2, max_new_tokens=12)
    rounds0 = spec_engine.spec_rounds_total
    spec_engine.prefill(s1)
    spec_engine.decode_steps()
    spec_engine.prefill(s2)         # joins while s1 is mid-generation
    while spec_engine.active_sequences():
        spec_engine.decode_steps()
    for s in (s1, s2):
        spec_engine.release(s)
    assert (s1.generated, s2.generated) == (w1, w2)
    assert spec_engine.spec_rounds_total > rounds0
    assert_pool_clean(spec_engine)


def test_prefix_cache_hit_then_speculation(model_setup, plain_engine):
    """A request that hits the prefix cache and then speculates emits
    the cold run's tokens, which are plain decode's."""
    model_cfg, params = model_setup
    eng = InferenceEngine(
        model_cfg, _ecfg(num_speculative_tokens=3, enable_prefix_cache=True),
        params=params)
    assert eng.prefix_cache is not None
    prompt = [ECHO + list(range(40, 57))]
    want = plain_engine.generate(prompt, max_new_tokens=16)
    cold = eng.generate(prompt, max_new_tokens=16)
    hits0, rounds0 = eng.prefix_cache.hits_hbm.value, eng.spec_rounds_total
    warm = eng.generate(prompt, max_new_tokens=16)
    assert eng.prefix_cache.hits_hbm.value > hits0
    assert eng.spec_rounds_total > rounds0
    assert cold == warm == want


def test_the_fork_is_gone_from_the_signatures():
    """One kind of speculation: no field selects a proposal source, and
    no constructor, builder or sizer takes a second model."""
    import dataclasses
    import inspect

    from tpu_inference.config import validate_spec_config
    from tpu_inference.engine import autosize, speculative
    from tpu_inference.server import http

    assert "spec_mode" not in {
        f.name for f in dataclasses.fields(cfgs.EngineConfig)}
    for fn in (InferenceEngine.__init__, http.build_server,
               http.build_engine_group, autosize.auto_size,
               validate_spec_config):
        names = set(inspect.signature(fn).parameters)
        assert not any("draft" in n or n in ("spec_mode", "speculative")
                       for n in names), (fn.__qualname__, sorted(names))
    with pytest.raises(TypeError):
        InferenceEngine(cfgs.tiny_llama(vocab_size=VOCAB), _ecfg(),
                        draft_cfg=cfgs.tiny_llama(vocab_size=VOCAB))
    assert not hasattr(speculative, "spec_round")


@pytest.mark.parametrize("argv,said", [
    (["--draft-model", "tiny-llama"], "unrecognized arguments"),
    (["--spec-mode", "draft"], "invalid choice: 'draft'"),
])
def test_cli_refuses_the_draft_flags_as_unknown(monkeypatch, capsys, argv,
                                                said):
    from tpu_inference.server import __main__ as cli

    monkeypatch.setattr("sys.argv", ["tpu_inference.server"] + argv)
    with pytest.raises(SystemExit) as e:
        cli.main()
    assert e.value.code == 2
    assert said in capsys.readouterr().err
