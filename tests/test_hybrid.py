"""Hybrid prefill-decode steps (EngineConfig.hybrid_prefill).

While a multi-chunk prompt prefills, each chunk fuses into the same
device dispatch as the batch's fused decode steps, so running lanes keep
producing tokens instead of stalling a chunk wall per chunk. These tests
pin the contract that makes the fusion shippable:

- greedy outputs are BYTE-IDENTICAL to the serial scheduler under mixed
  arrivals, with and without dispatch-ahead chaining and the per-step
  token budget;
- mid-prefill cancel, watermark preemption of decode lanes, and drain
  shutdown all keep their serial-path semantics;
- the KV pool comes back clean after every mix (tests/_leak.py).
"""

import threading
import time

import numpy as np
import pytest

from tpu_inference import config as cfgs
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.engine.scheduler import EngineScheduler
from tpu_inference.models import build_model

from tests._leak import assert_pool_clean

VOCAB = 256


@pytest.fixture(scope="module")
def model_and_params():
    model_cfg = cfgs.tiny_llama(vocab_size=VOCAB)
    params, _ = build_model(model_cfg, seed=0)
    return model_cfg, params


BASE = dict(page_size=8, num_pages=128, max_pages_per_seq=16,
            max_batch_size=4, prefill_buckets=(16, 32),
            chunked_prefill_size=16, enable_prefix_cache=False)


def _submit_and_wait(sched, seqs, timeout=180.0):
    events = {s.request_id: [] for s in seqs}
    done = {s.request_id: threading.Event() for s in seqs}
    for s in seqs:
        sched.submit(
            s,
            on_token=lambda sq, t: events[sq.request_id].append(t),
            on_finish=lambda sq: done[sq.request_id].set())
    for s in seqs:
        assert done[s.request_id].wait(timeout), \
            f"request {s.request_id} hung"
    return events


def _mixed_prompts():
    rng = np.random.default_rng(21)
    short = rng.integers(0, VOCAB, size=6).tolist()
    long = rng.integers(0, VOCAB, size=90).tolist()   # 6 chunks of 16
    return short, long


@pytest.mark.parametrize("depth,budget", [(1, 0), (2, 0), (1, 24)],
                         ids=["sync", "dispatch-ahead", "token-budget"])
def test_hybrid_byte_equality_mixed_arrivals(model_and_params, depth,
                                             budget):
    """Greedy outputs through hybrid stepping must be byte-identical to
    the non-interleaved reference, across the sync path, dispatch-ahead
    chaining (depth 2), and a binding step token budget."""
    model_cfg, params = model_and_params
    short, long = _mixed_prompts()
    ref = InferenceEngine(model_cfg, cfgs.EngineConfig(**BASE),
                          params=params)
    want_short = ref.generate([short], max_new_tokens=20)[0]
    want_long = ref.generate([long], max_new_tokens=8)[0]

    eng = InferenceEngine(
        model_cfg,
        cfgs.EngineConfig(**BASE, hybrid_prefill=True,
                          decode_pipeline_depth=depth,
                          step_token_budget=budget),
        params=params)
    sched = EngineScheduler(eng).start()
    try:
        s1 = Sequence(request_id=1, prompt_tokens=short, max_new_tokens=20)
        s2 = Sequence(request_id=2, prompt_tokens=long, max_new_tokens=8)
        events = _submit_and_wait(sched, [s1, s2])
    finally:
        sched.stop(drain=False)
    assert events[1] == want_short
    assert events[2] == want_long
    assert s2.finish_reason == "length"
    # The long prompt's chunks actually rode fused dispatches.
    assert eng.hybrid_steps_total > 0
    assert_pool_clean(eng)


def test_hybrid_matches_serial_scheduler(model_and_params):
    """Serial and hybrid schedulers, identical mixed workload: token
    streams must match request for request (the scheduler-level
    byte-equality pin, not just engine-level)."""
    model_cfg, params = model_and_params
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, VOCAB, size=n).tolist()
               for n in (5, 80, 9, 50)]
    budgets = [12, 6, 10, 7]

    def run(hybrid):
        eng = InferenceEngine(
            model_cfg,
            cfgs.EngineConfig(**BASE, hybrid_prefill=hybrid),
            params=params)
        sched = EngineScheduler(eng).start()
        seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                         max_new_tokens=b)
                for i, (p, b) in enumerate(zip(prompts, budgets))]
        try:
            events = _submit_and_wait(sched, seqs)
        finally:
            sched.stop(drain=False)
        assert_pool_clean(eng)
        return events, eng

    serial_events, serial_eng = run(hybrid=False)
    hybrid_events, hybrid_eng = run(hybrid=True)
    assert serial_eng.hybrid_steps_total == 0
    assert hybrid_events == serial_events
    for i, b in enumerate(budgets):
        assert len(hybrid_events[i]) == b


def test_hybrid_mid_prefill_cancel(model_and_params):
    """Cancelling the long prompt while its chunks are mid-hybrid-flight
    must terminate it cleanly (finish_reason=cancelled, no token ever
    delivered) without disturbing the decoding lanes or leaking its
    already-allocated pages."""
    model_cfg, params = model_and_params
    short, long = _mixed_prompts()
    long = long * 2          # 180 tokens -> truncated to 127, 8 chunks
    eng = InferenceEngine(
        model_cfg,
        cfgs.EngineConfig(**BASE, hybrid_prefill=True,
                          decode_pipeline_depth=2),
        params=params)
    want_short = eng.generate([short], max_new_tokens=30)[0]
    sched = EngineScheduler(eng).start()
    try:
        events = {1: [], 2: []}
        done = {1: threading.Event(), 2: threading.Event()}
        s1 = Sequence(request_id=1, prompt_tokens=short, max_new_tokens=30)
        s2 = Sequence(request_id=2, prompt_tokens=long, max_new_tokens=8)
        for s in (s1, s2):
            sched.submit(
                s,
                on_token=lambda sq, t: events[sq.request_id].append(t),
                on_finish=lambda sq: done[sq.request_id].set())
        # Wait until the long prompt is demonstrably mid-prefill, then
        # cancel it between chunks.
        deadline = time.time() + 60
        while s2.prefill_offset == 0 and time.time() < deadline:
            time.sleep(0.002)
        sched.cancel(2)
        assert done[2].wait(60), "cancelled request never finished"
        assert done[1].wait(120), "survivor hung after cancel"
    finally:
        sched.stop(drain=False)
    assert s2.finish_reason == "cancelled"
    assert events[2] == []               # no token from a cancelled prefill
    assert events[1] == want_short       # survivor byte-identical
    assert_pool_clean(eng)


def test_hybrid_mid_prefill_preemption(model_and_params):
    """Watermark preemption under optimistic admission composes with
    hybrid stepping: decode lanes evicted for pool pressure while a long
    prompt chunk-prefills recompute-resume to byte-identical greedy
    output, and the pool comes back clean."""
    model_cfg, params = model_and_params
    rng = np.random.default_rng(11)
    shorts = [rng.integers(0, VOCAB, size=6).tolist() for _ in range(3)]
    long = rng.integers(0, VOCAB, size=90).tolist()
    base = dict(BASE, num_pages=48, max_pages_per_seq=16,
                admission="optimistic", preempt_watermark_pages=6,
                optimistic_headroom_pages=1)
    ref = InferenceEngine(model_cfg, cfgs.EngineConfig(**BASE),
                          params=params)
    want = ([ref.generate([p], max_new_tokens=40)[0] for p in shorts]
            + [ref.generate([long], max_new_tokens=8)[0]])

    # Pool math: long needs 12 prompt pages + 1 decode; shorts grow to 6
    # pages each (6 prompt+40 gen tokens at page_size 8). Total demand 31
    # pages against 47 - 20 = 27 available -> exhaustion is guaranteed,
    # and optimistic admission must preempt (not fail) to finish.
    eng = InferenceEngine(
        model_cfg,
        cfgs.EngineConfig(**base, hybrid_prefill=True,
                          chaos_page_pressure=20),
        params=params)
    sched = EngineScheduler(eng).start()
    try:
        seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                         max_new_tokens=40)
                for i, p in enumerate(shorts)]
        seqs.append(Sequence(request_id=3, prompt_tokens=long,
                             max_new_tokens=8))
        events = _submit_and_wait(sched, seqs, timeout=240.0)
    finally:
        sched.stop(drain=False)
    for i in range(3):
        assert events[i] == want[i], f"short {i} diverged after preemption"
    assert events[3] == want[3]
    # The pool really was tight enough to exercise the safety net.
    assert eng.preemptions_total >= 1
    assert eng.resumes_total == eng.preemptions_total
    assert eng.hybrid_steps_total > 0
    assert_pool_clean(eng)


def test_hybrid_drain_shutdown(model_and_params):
    """stop(drain=True) with a hybrid prefill and decode lanes in flight:
    every submitted request gets exactly one terminal callback — finished
    normally or cancelled with finish_reason=shutdown — and nothing
    leaks."""
    model_cfg, params = model_and_params
    rng = np.random.default_rng(5)
    eng = InferenceEngine(
        model_cfg,
        cfgs.EngineConfig(**BASE, hybrid_prefill=True,
                          decode_pipeline_depth=2),
        params=params)
    sched = EngineScheduler(eng).start()
    finished = []
    s_short = Sequence(request_id=1,
                       prompt_tokens=rng.integers(0, VOCAB, 6).tolist(),
                       max_new_tokens=500)      # can't finish in time
    s_long = Sequence(request_id=2,
                      prompt_tokens=rng.integers(0, VOCAB, 120).tolist(),
                      max_new_tokens=500)
    for s in (s_short, s_long):
        sched.submit(s, on_token=lambda *a: None,
                     on_finish=lambda sq: finished.append(sq))
    # Let the mix get airborne (short decoding, long mid-chunks).
    deadline = time.time() + 60
    while not s_short.generated and time.time() < deadline:
        time.sleep(0.002)
    sched.stop(drain=True, timeout=0.3)   # deadline forces shutdown cancels
    assert {s.request_id for s in finished} == {1, 2}
    for s in finished:
        assert s.finish_reason in ("length", "stop", "shutdown"), \
            (s.request_id, s.finish_reason)
    # The engine thread is stopped; settle any in-flight calls, then the
    # pool must be fully reclaimable.
    eng.drain_pipeline()
    assert_pool_clean(eng)


def test_hybrid_prefill_liveness_under_sustained_pressure(model_and_params):
    """Sustained watermark pressure (preempt_watermark > pool, so
    under_pressure never clears) must not starve a mid-prefill prompt
    while decode lanes stay busy: the pressure branch advances one chunk
    serially per iteration (its pages were all allocated at
    prefill_begin), keeping TTFT bounded like serial mode. Regression:
    the chunk was deferred until every decode lane drained."""
    model_cfg, params = model_and_params
    eng = InferenceEngine(
        model_cfg,
        cfgs.EngineConfig(**BASE, hybrid_prefill=True,
                          admission="optimistic",
                          preempt_watermark_pages=10_000),
        params=params)
    sched = EngineScheduler(eng).start()
    try:
        rng = np.random.default_rng(9)
        short = Sequence(request_id=1,
                         prompt_tokens=rng.integers(0, VOCAB, 6).tolist(),
                         max_new_tokens=500)   # context cap ends it ~121
        long = Sequence(request_id=2,
                        prompt_tokens=rng.integers(0, VOCAB, 90).tolist(),
                        max_new_tokens=4)
        done = {1: threading.Event(), 2: threading.Event()}
        long_first = threading.Event()
        short_done_at_long_first = []
        sched.submit(short, on_token=lambda *a: None,
                     on_finish=lambda s: done[1].set())
        deadline = time.time() + 60
        while not short.generated and time.time() < deadline:
            time.sleep(0.002)          # the short is decoding first

        def on_long_token(s, t):
            if not long_first.is_set():
                short_done_at_long_first.append(short.done)
                long_first.set()

        sched.submit(long, on_token=on_long_token,
                     on_finish=lambda s: done[2].set())
        assert long_first.wait(120), "long prompt starved under pressure"
        sched.cancel(1)
        for ev in done.values():
            assert ev.wait(60)
    finally:
        sched.stop(drain=False)
    # The long prompt's first token arrived while the short was still
    # decoding — the prefill stayed live under sustained pressure.
    assert short_done_at_long_first == [False]
    assert_pool_clean(eng)


def test_hybrid_chunk_only_call_then_decode_staging(model_and_params):
    """A chunk-only pipeline call (no decode lane could advance — its
    decode half is None) must not poison later staging: the in-flight
    carry fold skips it, so a lane that becomes stageable afterwards
    dispatches normally. Regression: jnp.where(None, ...) raised
    TypeError and errored out the whole batch."""
    model_cfg, params = model_and_params
    eng = InferenceEngine(
        model_cfg,
        cfgs.EngineConfig(**BASE, hybrid_prefill=True,
                          decode_pipeline_depth=4),
        params=params)
    k = eng.engine_cfg.decode_steps_per_call
    rng = np.random.default_rng(3)
    s1 = Sequence(request_id=1,
                  prompt_tokens=rng.integers(0, VOCAB, 5).tolist(),
                  max_new_tokens=k)        # one staged call covers it
    eng.prefill(s1)
    long = Sequence(request_id=2,
                    prompt_tokens=rng.integers(0, VOCAB, 90).tolist(),
                    max_new_tokens=4)
    eng.prefill_begin(long)
    eng.decode_steps_pipelined(long)        # decode grant + chunk 1
    eng.decode_steps_pipelined(long)        # s1 fully covered: chunk-only
    assert any(c["outs"] is None for c in eng._inflight), \
        "setup failed to produce a chunk-only call"
    # A fresh lane becomes stageable with the chunk-only call still in
    # flight — staging must skip its None decode half, not crash.
    s3 = Sequence(request_id=3,
                  prompt_tokens=rng.integers(0, VOCAB, 5).tolist(),
                  max_new_tokens=12)
    eng.prefill(s3)
    eng.decode_steps_pipelined(long)        # would raise before the fix
    for _ in range(50):
        eng.drain_pipeline()
        if long.prefill_prompt is None:
            break
        eng.decode_steps_pipelined(long)
    assert long.prefill_prompt is None and long.generated
    eng.drain_pipeline()
    for s in list(eng.slots):
        if s is not None:
            eng.release(s)
    assert_pool_clean(eng)


def test_hybrid_drain_error_keeps_engine_loop_alive(model_and_params):
    """A device error surfacing only at drain/sync time (async dispatch
    on real TPU) must fail the affected requests with
    finish_reason="error" — not propagate out of run() and kill the
    engine thread. Regression: the cancel-path drains ran outside the
    run loop's try/except."""
    model_cfg, params = model_and_params
    eng = InferenceEngine(
        model_cfg,
        cfgs.EngineConfig(**BASE, hybrid_prefill=True,
                          decode_pipeline_depth=2),
        params=params)
    sched = EngineScheduler(eng).start()
    real = eng.drain_pipeline
    state = {"armed": False, "fired": False}

    def flaky():
        if state["armed"] and not state["fired"]:
            state["fired"] = True
            eng.abort_pipeline()        # mimic poisoned in-flight state
            raise RuntimeError("injected sync failure")
        return real()

    eng.drain_pipeline = flaky
    try:
        rng = np.random.default_rng(13)
        short = Sequence(request_id=1,
                         prompt_tokens=rng.integers(0, VOCAB, 6).tolist(),
                         max_new_tokens=40)
        long = Sequence(request_id=2,
                        prompt_tokens=rng.integers(0, VOCAB, 90).tolist(),
                        max_new_tokens=6)
        done = {i: threading.Event() for i in (1, 2, 3)}
        for s in (short, long):
            sched.submit(s, on_token=lambda *a: None,
                         on_finish=lambda sq: done[sq.request_id].set())
        deadline = time.time() + 60
        while long.prefill_offset == 0 and time.time() < deadline:
            time.sleep(0.002)
        state["armed"] = True
        sched.cancel(2)       # cancel mid-prefill -> a drain path fires
        assert done[2].wait(60), "cancelled request never finished"
        assert done[1].wait(120), "batch-mate never finished"
        assert state["fired"]
        # The loop survived: a fresh request completes normally.
        eng.drain_pipeline = real
        fresh = Sequence(request_id=3,
                         prompt_tokens=rng.integers(0, VOCAB, 6).tolist(),
                         max_new_tokens=5)
        sched.submit(fresh, on_token=lambda *a: None,
                     on_finish=lambda sq: done[3].set())
        assert done[3].wait(60), "engine thread died after drain error"
        assert fresh.finish_reason == "length"
    finally:
        sched.stop(drain=False)
    assert_pool_clean(eng)


def test_hybrid_chunk_cap_budget_math(model_and_params):
    """step_token_budget splits each fused step between the decode
    tokens actually granted and the chunk, floored at page_size so the
    prefill always advances."""
    model_cfg, params = model_and_params
    eng = InferenceEngine(
        model_cfg,
        cfgs.EngineConfig(**BASE, hybrid_prefill=True,
                          step_token_budget=40),
        params=params)
    k = eng.engine_cfg.decode_steps_per_call
    # No decode tokens granted: the whole budget is the chunk's
    # (capped by the configured chunk size).
    assert eng._hybrid_chunk_cap(0) == min(16, 40)
    # Budget minus the granted decode tokens...
    assert eng._hybrid_chunk_cap(2 * k) == min(16, max(8, 40 - 2 * k))
    # ...but never below a page of progress.
    assert eng._hybrid_chunk_cap(800) == eng.engine_cfg.page_size
    # An over-large CLI chunked_prefill_size clamps to the largest
    # compiled bucket (a bigger chunk fits no prefill graph).
    big = cfgs.EngineConfig(**{**BASE, "chunked_prefill_size": 10_000})
    assert big.chunk_tokens_cap == big.prefill_buckets[-1]
