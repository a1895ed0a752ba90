"""Process fleet (README "Process fleet"): router + engine-worker
processes with KV page migration.

Covers the subsystem at three levels:

- pure units: the RPC frame codec, JSON config transport, and the
  migration wire format (bit-exact host-page round-trips for every
  kv_quant layout) — no processes, no jax device work beyond an engine.
- engine-level: host-tier import (capacity, LRU-for-imports, tier
  invariant, leak cleanliness).
- REAL processes: a module-scoped dp=2 subprocess fleet exercised for
  backend equivalence (byte-identical greedy outputs vs the in-process
  EngineGroup), ``kill -9``-a-worker-mid-decode chaos (requests fail
  over from the router's token record and complete byte-identically;
  the fleet restarts the worker; survivors' pools stay leak-free), the
  SIGTERM drain-and-migrate path (admission on the destination becomes
  a swap-in-resume), and metrics-label hygiene across restarts (stable
  ``replica="i"`` label, no counter resets, no duplicate series).
- P/D disaggregation (README "P/D disaggregation"): live-sequence KV
  handoff export/adopt at the engine level for every kv_quant mode
  (including the partial final page the drain path would recompute),
  the malformed-blob fallback to recompute-resume, and a second
  module-scoped 1-prefill+1-decode fleet pinning handoff routing,
  role observability, and a handoff racing a decode-worker ``kill -9``
  (stale-blob fallback, byte-identical).
"""

import hashlib
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from tests._leak import assert_arena_clean
from tpu_inference.config import (EngineConfig, FrameworkConfig,
                                  ParallelConfig, ServerConfig,
                                  framework_config_from_dict,
                                  framework_config_to_dict, tiny_llama)
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine.engine import InferenceEngine, Sequence

# One geometry for every fleet test: small enough to boot a worker in
# seconds, host tier on so drain migration has somewhere to land.
ENGINE_KW = dict(page_size=8, num_pages=64, max_pages_per_seq=8,
                 max_batch_size=2, prefill_buckets=(16,),
                 host_cache_pages=32)


def _cfg(dp=2, **server_kw) -> FrameworkConfig:
    server_kw.setdefault("fleet", "subprocess")
    server_kw.setdefault("worker_restart_max", 10)
    server_kw.setdefault("worker_restart_backoff_s", 0.1)
    server_kw.setdefault("drain_timeout_s", 8.0)
    return FrameworkConfig(
        model=tiny_llama(vocab_size=512),
        engine=EngineConfig(**ENGINE_KW),
        parallel=ParallelConfig(dp=dp),
        server=ServerConfig(model_name="t", tokenizer="byte",
                            warmup=False, **server_kw))


# ------------------------------------------------------------- units


def test_frame_codec_roundtrip():
    """Length-prefixed JSON + binary attachment round-trips through a
    real socketpair, including interleaved frames and empty blobs."""
    import socket

    from tpu_inference.server.worker import recv_frame, send_frame

    a, b = socket.socketpair()
    rfile = b.makefile("rb")
    send_frame(a, {"id": 1, "verb": "hello"})
    send_frame(a, {"ev": "token", "t": 42}, blob=b"\x00\x01\xffbytes")
    obj, blob = recv_frame(rfile)
    assert obj == {"id": 1, "verb": "hello"} and blob == b""
    obj, blob = recv_frame(rfile)
    assert obj["t"] == 42 and blob == b"\x00\x01\xffbytes"
    a.close()
    with pytest.raises(ConnectionError):
        recv_frame(rfile)
    b.close()


def test_config_json_transport_roundtrip():
    """The router->worker config envelope survives JSON: dtypes by
    name, tuples, nested dataclasses, fleet knobs."""
    cfg = _cfg(dp=3)
    cfg2 = framework_config_from_dict(
        json.loads(json.dumps(framework_config_to_dict(cfg))))
    assert cfg2.model == cfg.model
    assert cfg2.engine == cfg.engine
    assert cfg2.parallel == cfg.parallel
    assert cfg2.server == cfg.server
    assert cfg2.engine.prefill_buckets == (16,)
    assert cfg2.model.dtype == cfg.model.dtype


@pytest.mark.parametrize("quant", ["none", "int8", "int4"])
def test_host_page_serialization_bit_exact(quant):
    """The migration wire format round-trips every kv_quant host-page
    layout bit-exactly (the PR-6 stored layout, serialized)."""
    rng = np.random.default_rng(7)
    if quant == "none":
        mk = lambda: rng.standard_normal((2, 8, 2, 16)).astype(np.float32)
        pages = [kvc.HostKVPage(mk(), mk()) for _ in range(3)]
    else:
        code_dt = np.uint8 if quant == "int4" else np.int8
        d = 8 if quant == "int4" else 16
        mk = lambda: rng.integers(0, 255, (2, 8, 2, d)).astype(code_dt)
        sc = lambda: rng.standard_normal((2, 8, 2)).astype(np.float32)
        pages = [kvc.HostKVPage(mk(), mk(), sc(), sc()) for _ in range(3)]
    blob = kvc.serialize_host_pages(pages)
    back = kvc.deserialize_host_pages(blob)
    assert len(back) == len(pages)
    for orig, got in zip(pages, back):
        np.testing.assert_array_equal(orig.k, got.k)
        np.testing.assert_array_equal(orig.v, got.v)
        if orig.k_scale is None:
            assert got.k_scale is None
        else:
            np.testing.assert_array_equal(orig.k_scale, got.k_scale)
            np.testing.assert_array_equal(orig.v_scale, got.v_scale)
        assert orig.nbytes == got.nbytes
    assert kvc.deserialize_host_pages(kvc.serialize_host_pages([])) == []


def test_import_host_capacity_and_tier_invariant():
    """Engine-level migration import: entries land in the host tier
    (newest-LRU), duplicates of either tier are skipped, imports evict
    the tier's own oldest warmth to fit, overflow drops the remainder,
    and the leak invariant holds after a clear."""
    from tests._leak import assert_pool_clean

    engine = InferenceEngine(tiny_llama(vocab_size=512),
                             EngineConfig(**{**ENGINE_KW,
                                             "host_cache_pages": 4}))
    cache, pool = engine.prefix_cache, engine.host_pool

    def entry(tag: int):
        k = np.full((2, 8, 2, 16), tag, np.float32)
        return kvc.HostKVPage(k, k.copy())

    d = [bytes([i]) * 16 for i in range(8)]
    assert cache.import_host([(d[0], entry(0)), (d[1], entry(1))]) == 2
    assert pool.used == 2 and pool.imported_total == 2
    # Duplicate digest: skipped, not double-resident.
    assert cache.import_host([(d[0], entry(9))]) == 0
    # Fill to capacity, then one more: the OLDEST host entry evicts.
    assert cache.import_host([(d[2], entry(2)), (d[3], entry(3))]) == 2
    assert cache.import_host([(d[4], entry(4))]) == 1
    assert pool.used == 4 and d[0] not in cache._host
    assert d[4] in cache._host
    # Offering more than capacity drops the tail (never over-fills).
    added = cache.import_host([(d[i], entry(i)) for i in range(5, 8)])
    assert pool.used == 4 and added <= 3
    # Apply-queue path (the worker's import-kv RPC marshals through the
    # engine loop): queued entries adopt on apply, event fires.
    done = engine.request_import_host([(b"z" * 16, entry(42))])
    engine.apply_pending_imports()
    assert done.is_set()
    assert engine.migrate_in_pages >= 1
    assert_pool_clean(engine)


# ------------------------------------------------- real process fleet


def _submit(group, rid, prompt, max_new, timeout=180.0):
    toks, done, box = [], threading.Event(), {}
    seq = Sequence(request_id=rid, prompt_tokens=list(prompt),
                   max_new_tokens=max_new)
    group.submit(seq, lambda s, t: toks.append(t),
                 lambda s: (box.update(seq=s), done.set()))
    return toks, done, box


def _finish(done, box, timeout=180.0):
    assert done.wait(timeout), "request did not finish"
    return box["seq"]


def _wait_states(group, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(h.state == "up" for h in group.workers):
            return
        time.sleep(0.1)
    raise AssertionError(
        f"fleet never healed: {[h.state for h in group.workers]}")


@pytest.fixture(scope="module")
def fleet():
    from tpu_inference.server.fleet import ProcessEngineGroup

    group = ProcessEngineGroup(_cfg(dp=2))
    group.start()
    yield group
    group.stop(drain=False)


@pytest.fixture(scope="module")
def oracle():
    """In-process engine with the same seed/geometry as every worker:
    greedy outputs must match the fleet's byte for byte."""
    return InferenceEngine(tiny_llama(vocab_size=512),
                           EngineConfig(**ENGINE_KW), seed=0)


def test_fleet_basic_and_surfaces(fleet, oracle):
    toks, done, box = _submit(fleet, 0, [1, 2, 3, 4, 5], 12)
    fin = _finish(done, box)
    assert fin.finish_reason == "length"
    assert toks == oracle.generate([[1, 2, 3, 4, 5]],
                                   max_new_tokens=12)[0]
    assert fin.routed_replica in (0, 1)

    hs = fleet.health_snapshot()
    assert hs["status"] == "ok" and hs["fleet"] == "subprocess"
    assert len(hs["replicas"]) == 2
    for r in hs["replicas"]:
        assert r["pid"] and "restarts" in r and "routing" in r
        assert "pool_pressure" in r and "host_cache" in r
    ss = fleet.stats_snapshot()
    assert ss["dp"] == 2 and ss["tokens_generated"] >= 12
    assert "phases" in ss and "supervision" in ss
    pt = fleet.prometheus_text()
    assert 'replica="0"' in pt and 'replica="1"' in pt
    assert "tpu_inf_worker_up" in pt
    assert "tpu_inf_fleet_migrations_total" in pt
    # /debug/requests analogue: merged recent timelines.
    recent = fleet.recent_snapshot(10)
    assert recent and recent[-1]["finish_reason"] == "length"


def test_backend_equivalence_pinned_mix(fleet):
    """Satellite: the same pinned greedy mix through --fleet in-process
    and --fleet subprocess produces byte-identical outputs
    (outputs_sha256), identical finish reasons, and matching
    route/telemetry counter shapes."""
    from tpu_inference.server.http import build_engine_group

    prompts = [[1, 2, 3], [9, 8, 7, 6], [5, 5, 5, 5, 5], [2, 4, 6]]
    budgets = [10, 14, 8, 200]          # 200 hits the context cap

    def run(group):
        outs, reasons = [], []
        pend = [_submit(group, 1000 + i, p, b)
                for i, (p, b) in enumerate(zip(prompts, budgets))]
        for toks, done, box in pend:
            fin = _finish(done, box)
            outs.append(list(toks))
            reasons.append(fin.finish_reason)
        h = hashlib.sha256()
        for o in outs:
            h.update(np.asarray(o, np.int32).tobytes() + b"|")
        return h.hexdigest(), reasons, group.stats_snapshot()

    cfg = _cfg(dp=2, fleet="in-process")
    inproc = build_engine_group(cfg).start()
    try:
        sha_in, reasons_in, stats_in = run(inproc)
    finally:
        inproc.stop(drain=False)
    sha_sub, reasons_sub, stats_sub = run(fleet)

    assert sha_sub == sha_in
    assert reasons_sub == reasons_in
    # Counter-shape parity: every in-process supervision counter exists
    # in the subprocess fleet's view, and the aggregated stats share
    # the core serving keys.
    assert set(stats_in["supervision"]) <= set(stats_sub["supervision"])
    core = {"steps", "prefills", "tokens_generated", "requests_finished",
            "preemptions", "recompute_resumes", "swap_in_resumes",
            "migrate_out_pages", "migrate_in_pages", "kv_pages_total",
            "decode_ladder", "phases", "replicas", "dp", "supervision"}
    assert core <= set(stats_in) and core <= set(stats_sub)
    # Route stats per replica share the same shape.
    h_in = inproc.health_snapshot()["replicas"][0]["routing"]
    h_sub = fleet.health_snapshot()["replicas"][0]["routing"]
    assert set(h_in) == set(h_sub)


def test_kill9_chaos_failover(fleet, oracle):
    """Acceptance: kill -9 a worker mid-decode. In-flight requests on
    the killed worker fail over (router token record, recompute-resume
    on the survivor) and COMPLETE byte-identically; /healthz shows the
    restart; no KV pages leak on the survivors."""
    _wait_states(fleet)
    failovers0 = fleet.failovers
    # Two long streams: the cold-prompt rotating tie-break spreads them
    # across both workers, so SOME worker holds a mid-decode stream.
    a = _submit(fleet, 2000, [7, 8, 9], 40)
    b = _submit(fleet, 2001, [3, 1, 4, 1, 5], 40)
    deadline = time.monotonic() + 60
    while (len(a[0]) < 4 or len(b[0]) < 4) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(a[0]) >= 4 and len(b[0]) >= 4
    with fleet._lock:
        victim_idx = fleet._tracked[2000].worker.replica
    r = fleet.apply_chaos({"replica": victim_idx, "kill": "kill9"})
    assert r["killed"] == "kill9"

    fin_a = _finish(a[1], a[2])
    fin_b = _finish(b[1], b[2])
    assert fin_a.finish_reason == "length"
    assert fin_b.finish_reason == "length"
    # Byte-identity: the failover resume replays the streamed prefix
    # and continues exactly where the dead worker left off (greedy).
    assert a[0] == oracle.generate([[7, 8, 9]], max_new_tokens=40)[0]
    assert b[0] == oracle.generate([[3, 1, 4, 1, 5]],
                                   max_new_tokens=40)[0]
    assert fleet.failovers > failovers0

    # The fleet restarts the worker under the same replica label.
    _wait_states(fleet)
    hs = fleet.health_snapshot()
    assert hs["replicas"][victim_idx]["restarts"] >= 1
    assert hs["supervision"]["worker_restarts"] >= 1

    # Leak invariant on the survivors (worker-side debug snapshot: the
    # tests/_leak checks, evaluated in the worker process after
    # clearing its cache references).
    for h in fleet.workers:
        snap = h.client.rpc("debug", clear=True)
        assert not snap["pipeline_pending"]
        assert snap["preempted_uncollected"] == 0
        assert snap["slots_bound"] == 0
        assert snap["num_free"] == snap["num_pages"] - 1, snap
        assert snap["refs_held"] == 0 and snap["evictable_count"] == 0
        assert snap["host_used"] == 0
        assert snap.get("tier_overlap", 0) == 0


def test_sigterm_drain_migrates_kv(fleet, oracle):
    """Tentpole proof: graceful drain (SIGTERM) exports the in-flight
    sequence's KV pages over the migration channel; the router imports
    them into the destination's host tier and resubmission becomes a
    swap-in-resume — tokens byte-identical, migrated pages > 0, and the
    destination records a swap_in_resume."""
    _wait_states(fleet)
    migrations0 = fleet.migrations
    pages0 = fleet.migrated_pages
    prompt = [11, 12, 13, 14, 15, 16, 17]
    toks, done, box = _submit(fleet, 3000, prompt, 48)
    deadline = time.monotonic() + 60
    # Wait until a couple of FULL pages of KV exist (page_size=8).
    while len(toks) < 18 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(toks) >= 18
    with fleet._lock:
        src_idx = fleet._tracked[3000].worker.replica
    fleet.apply_chaos({"replica": src_idx, "kill": "sigterm"})

    fin = _finish(done, box)
    assert fin.finish_reason == "length"
    assert toks == oracle.generate([prompt], max_new_tokens=48)[0]
    assert fleet.migrations > migrations0
    assert fleet.migrated_pages > pages0
    assert fleet.resume_reused_tokens > 0
    sup = fleet.supervision_counters()
    assert sup["swap_in_resumes"] >= 1
    assert sup["migrated_bytes"] > 0
    _wait_states(fleet)


def test_metrics_label_stable_across_restart(fleet):
    """Satellite: per-worker series keep the stable replica="i" label
    across a restart, fleet-level counters never reset (restart carry),
    and no series is double-reported in the aggregated scrape."""
    from tests import _prom

    _wait_states(fleet)
    # Traffic so worker counters are non-zero, then force the periodic
    # metrics cache (the carry source) to be fresh.
    toks, done, box = _submit(fleet, 4000, [2, 7, 1, 8], 10)
    _finish(done, box)
    fleet._refresh_caches()

    def scrape():
        _, samples = _prom.parse(fleet.prometheus_text())
        out = {}
        for name, labels, value in samples:
            key = (name, tuple(sorted(labels.items())))
            assert key not in out, f"duplicate series {key}"
            out[key] = value
        return out

    before = scrape()

    def series(samples, name):
        return {labels: v for (n, labels), v in samples.items()
                if n == name}

    tok_before = series(before, "tpu_inf_tokens_generated_total")
    replicas = {dict(labels).get("replica") for labels in tok_before}
    assert replicas == {"0", "1"}
    # build_info: one info series per replica + one fleet-level, all
    # value 1 with config-pure labels.
    binfo_before = series(before, "tpu_inf_build_info")
    assert len(binfo_before) == 3
    assert all(v == 1.0 for v in binfo_before.values())

    # Restart worker 0 gracefully (drain carries the final dump).
    fleet.apply_chaos({"replica": 0, "kill": "sigterm"})
    deadline = time.monotonic() + 60
    while fleet.workers[0].state == "up" and time.monotonic() < deadline:
        time.sleep(0.05)
    _wait_states(fleet)

    after = scrape()                 # scrape() re-asserts no duplicates
    tok_after = series(after, "tpu_inf_tokens_generated_total")
    assert set(tok_after) == set(tok_before)
    for labels, v in tok_before.items():
        # Monotone across the restart: the carry folds the dead
        # incarnation's total under the same replica label.
        assert tok_after[labels] >= v, (labels, v, tok_after[labels])
    # Fleet-side restart counter moved under the stable label.
    restarts = series(after, "tpu_inf_worker_restarts_total")
    assert restarts[(("replica", "0"),)] >= 1
    # build_info label stability: the restarted worker re-minted the
    # IDENTICAL labelset (values are pure config), so the series set is
    # unchanged — no new series, none vanished, still all value 1.
    binfo_after = series(after, "tpu_inf_build_info")
    assert set(binfo_after) == set(binfo_before)
    assert all(v == 1.0 for v in binfo_after.values())


# ------------------------------------------- P/D disaggregation (live
# KV handoff): engine-level export/adopt, then a real 1p+1d fleet.

# 13 tokens: two KV pages at page_size=8, the second PARTIAL — the
# case the drain-time migrate path recomputes and the live handoff
# must move verbatim.
PD_PROMPT = [5, 9, 2, 7, 3, 8, 1, 6, 4, 2, 9, 1, 7]


def _run_sched(engine, seq, hook=None, timeout=180.0):
    """One request through a real EngineScheduler; returns
    (streamed tokens, finished seq, scheduler) after a hard stop."""
    from tpu_inference.engine.scheduler import EngineScheduler

    sched = EngineScheduler(engine)
    if hook is not None:
        sched.on_prefill_handoff = hook
    sched.start()
    toks, done, box = [], threading.Event(), {}
    try:
        sched.submit(seq, lambda s, t: toks.append(t),
                     lambda s: (box.update(seq=s), done.set()))
        assert done.wait(timeout), "request did not finish"
    finally:
        sched.stop(drain=False)
    return toks, box["seq"], sched


def _pd_engine(quant, role):
    return InferenceEngine(
        tiny_llama(vocab_size=512),
        EngineConfig(**{**ENGINE_KW, "kv_quant": quant, "role": role}),
        seed=0)


@pytest.mark.parametrize("quant", ["none", "int8", "int4"])
def test_live_handoff_export_adopt_bit_exact(quant):
    """Satellite: a LIVE (in-flight, not draining) sequence's KV
    exports on a prefill-role engine — including the partial final
    page — crosses the wire format, and adopts on a decode-role engine
    with ZERO prefill dispatches and zero recomputed tokens; the
    continued greedy stream is byte-identical to a mixed engine, for
    every kv_quant layout."""
    from tests._leak import assert_pool_clean

    src = _pd_engine(quant, "prefill")
    captured = {}

    def hook(s):
        digests, pages, ctx = src.export_sequence_kv_live(s)
        if not pages:
            return False
        captured["blob"] = kvc.serialize_host_pages(pages)
        captured["ctx"] = ctx
        captured["digests"] = digests
        return True

    seq = Sequence(request_id=1, prompt_tokens=list(PD_PROMPT),
                   max_new_tokens=24)
    seq.handoff_after_prefill = True
    toks_src, fin_src, _ = _run_sched(src, seq, hook)
    # The prefill settled, streamed exactly the first token, and
    # finished locally as a handoff.
    assert fin_src.finish_reason == "handoff"
    assert len(toks_src) == 1
    assert src.handoffs_out == 1
    # The export covers EVERY page holding ctx_len tokens — the final
    # one partial (13 % 8 != 0) — while chain digests cover only the
    # full pages (a chain digest is defined on full pages).
    assert captured["ctx"] == len(PD_PROMPT)
    pages = kvc.deserialize_host_pages(captured["blob"])
    assert len(pages) == 2 and len(captured["digests"]) == 1

    dst = _pd_engine(quant, "decode")
    seq2 = Sequence(request_id=2, prompt_tokens=list(PD_PROMPT),
                    max_new_tokens=24)
    seq2.generated = list(toks_src)
    seq2.resume_base = len(toks_src)
    seq2.adopt_kv = (pages, captured["ctx"])
    toks_dst, fin_dst, sched_dst = _run_sched(dst, seq2)
    assert fin_dst.finish_reason == "length"
    # Clean-handoff path: the adoption restored KV instead of
    # prefilling — nothing recomputed on the decode side.
    assert sched_dst.stats.prefills == 0
    assert dst.adoptions_in == 1 and dst.swap_in_resumes == 1
    assert fin_dst.cached_tokens == len(PD_PROMPT) + 1

    mixed = _pd_engine(quant, "mixed")
    want = mixed.generate([list(PD_PROMPT)], max_new_tokens=24)[0]
    assert toks_src + toks_dst == want
    assert_pool_clean(src)
    assert_pool_clean(dst)


def test_handoff_adopt_malformed_blob_recomputes():
    """A handoff blob that doesn't match its ctx_len (truncated page
    list) must NOT stick: adoption fails, the scheduler clears the
    adoption state and recompute-resumes through the ordinary prefill
    path — byte-identical, with the recompute visible in stats."""
    from tests._leak import assert_pool_clean

    src = _pd_engine("none", "prefill")
    captured = {}

    def hook(s):
        _, pages, ctx = src.export_sequence_kv_live(s)
        captured["pages"], captured["ctx"] = pages, ctx
        return bool(pages)

    seq = Sequence(request_id=3, prompt_tokens=list(PD_PROMPT),
                   max_new_tokens=16)
    seq.handoff_after_prefill = True
    toks_src, _, _ = _run_sched(src, seq, hook)

    dst = _pd_engine("none", "decode")
    seq2 = Sequence(request_id=4, prompt_tokens=list(PD_PROMPT),
                    max_new_tokens=16)
    seq2.generated = list(toks_src)
    seq2.resume_base = len(toks_src)
    # Truncated: one page short of what ctx_len needs.
    seq2.adopt_kv = (captured["pages"][:-1], captured["ctx"])
    toks_dst, fin_dst, sched_dst = _run_sched(dst, seq2)
    assert fin_dst.finish_reason == "length"
    assert dst.adoptions_in == 0
    assert dst.adopt_fallbacks == 1           # counted, not silent
    assert sched_dst.stats.prefills == 1      # the recompute-resume
    mixed = _pd_engine("none", "mixed")
    want = mixed.generate([list(PD_PROMPT)], max_new_tokens=16)[0]
    assert toks_src + toks_dst == want
    assert_pool_clean(dst)


@pytest.fixture(scope="module")
def pd_fleet():
    """1 prefill + 1 decode worker: the smallest disaggregated
    topology (README "P/D disaggregation")."""
    from tpu_inference.server.fleet import ProcessEngineGroup

    group = ProcessEngineGroup(
        _cfg(dp=2, worker_roles=("prefill", "decode")))
    group.start()
    yield group
    group.stop(drain=False)


def test_pd_fleet_handoff_byte_identity_and_surfaces(pd_fleet, oracle):
    """Tentpole proof at process level: new prompts admit to the
    prefill worker, settle, hand off, and decode on the decode worker
    — outputs byte-identical to a mixed engine, zero handoff
    recomputes, with roles/backlog/occupancy/handoff counters visible
    in /healthz, stats, and the Prometheus scrape."""
    _wait_states(pd_fleet)
    handoffs0 = pd_fleet.pd_handoffs
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [4, 4, 4, 4]]
    pend = [_submit(pd_fleet, 6000 + i, p, 16)
            for i, p in enumerate(prompts)]
    for (toks, done, box), p in zip(pend, prompts):
        fin = _finish(done, box)
        assert fin.finish_reason == "length"
        assert toks == oracle.generate([p], max_new_tokens=16)[0]
    assert pd_fleet.pd_handoffs >= handoffs0 + len(prompts)
    assert pd_fleet.pd_handoff_recomputes == 0

    # stats_snapshot refreshes each worker's cached stats, so the
    # supervision view's adoption sum is current.
    sup = pd_fleet.stats_snapshot()["supervision"]
    assert sup["roles"] == ["prefill", "decode"]
    assert sup["pd_handoffs"] >= len(prompts)
    assert sup["pd_adoptions"] >= len(prompts)
    # The handoff-wall histogram rides supervision as a diffable phase
    # snapshot (one observation per routed handoff).
    assert sup["phases"]["pd_handoff_s"]["count"] >= len(prompts)
    assert sup["phases"]["pd_handoff_s"]["p95"] is not None
    hs = pd_fleet.health_snapshot()
    roles = [r["role"] for r in hs["replicas"]]
    assert roles == ["prefill", "decode"]
    for r in hs["replicas"]:
        assert "prefill_backlog" in r and "ladder_occupancy" in r
    # The decode worker did the adopting; the prefill worker the
    # handing-off.
    assert hs["replicas"][0]["pd_handoffs"] >= len(prompts)
    assert hs["replicas"][1]["pd_adoptions"] >= len(prompts)
    pt = pd_fleet.prometheus_text()
    assert 'tpu_inf_worker_role_info{replica="0",role="prefill"}' in pt
    assert 'tpu_inf_worker_role_info{replica="1",role="decode"}' in pt
    assert "tpu_inf_pd_handoffs_total" in pt
    assert "tpu_inf_pd_handoff_seconds_bucket" in pt
    # Relay plane (no --kv-plane shm): the arena invariant checker is
    # a documented no-op, and no handoff blob leaked a tracked slab.
    assert_arena_clean(pd_fleet)


@pytest.mark.slow   # ~77s of restart-backoff waits; the handoff fallback
                    # path it races is covered fast by the malformed-blob
                    # recompute test and pd byte-identity stays tier-1
def test_pd_handoff_races_decode_restart(pd_fleet, oracle):
    """Satellite: kill -9 the decode worker AFTER it adopted a handoff
    and streamed tokens. The kept handoff blob is stale (decode
    advanced past the export), so the failover falls back to
    recompute-resume — on the prefill worker, since no decode worker
    is routable — and the stream completes byte-identically; the
    supervisor restarts the decode worker."""
    _wait_states(pd_fleet)
    recomputes0 = pd_fleet.pd_handoff_recomputes
    prompt = [8, 1, 8, 2, 8, 3]
    toks, done, box = _submit(pd_fleet, 7000, prompt, 40)
    deadline = time.monotonic() + 60
    # Wait until decode is well past the handoff point (1 token).
    while len(toks) < 6 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(toks) >= 6
    with pd_fleet._lock:
        holder = pd_fleet._tracked[7000].worker.replica
    assert holder == 1        # the decode worker owns the stream
    pd_fleet.apply_chaos({"replica": 1, "kill": "kill9"})

    fin = _finish(done, box)
    assert fin.finish_reason == "length"
    assert toks == oracle.generate([prompt], max_new_tokens=40)[0]
    # The stale-export fallback fired: the blob was dropped, not
    # adopted (adopting it would fork the stream).
    assert pd_fleet.pd_handoff_recomputes > recomputes0
    _wait_states(pd_fleet)
    assert pd_fleet.health_snapshot()["replicas"][1]["restarts"] >= 1


def test_handoff_trace_id_in_worker_logs(oracle, tmp_path):
    """Trace-id satellite, pinned at the OS level: the id a client
    sends appears in BOTH workers' structured logs for a handed-off
    request — the prefill worker's request_finish (reason "handoff")
    and the decode worker's terminal request_finish. The fleet spawns
    with fd 2 redirected to a file (workers inherit it for life) and
    TPU_INF_LOG=info, so the assertion reads the workers' REAL stderr
    stream, not an in-process shim."""
    import os

    from tpu_inference.server.fleet import ProcessEngineGroup

    log_path = tmp_path / "workers.stderr"
    log_fd = os.open(str(log_path), os.O_CREAT | os.O_WRONLY, 0o600)
    saved = os.dup(2)
    prior = os.environ.get("TPU_INF_LOG")
    os.environ["TPU_INF_LOG"] = "info"
    try:
        os.dup2(log_fd, 2)
        try:
            group = ProcessEngineGroup(
                _cfg(dp=2, worker_roles=("prefill", "decode")))
            group.start()
        finally:
            os.dup2(saved, 2)
    finally:
        os.close(saved)
        os.close(log_fd)
        if prior is None:
            os.environ.pop("TPU_INF_LOG", None)
        else:
            os.environ["TPU_INF_LOG"] = prior
    tid = "cli-e2e-7f3a"
    try:
        _wait_states(group)
        toks, done, box = [], threading.Event(), {}
        seq = Sequence(request_id=8000, prompt_tokens=list(PD_PROMPT),
                       max_new_tokens=12, trace_id=tid)
        group.submit(seq, lambda s, t: toks.append(t),
                     lambda s: (box.update(seq=s), done.set()))
        fin = _finish(done, box)
        assert fin.finish_reason == "length"
        assert toks == oracle.generate([list(PD_PROMPT)],
                                       max_new_tokens=12)[0]
        deadline = time.monotonic() + 30
        reasons = set()
        while time.monotonic() < deadline:
            lines = [l for l in log_path.read_text().splitlines()
                     if '"request_finish"' in l and tid in l]
            reasons = {json.loads(l)["reason"] for l in lines}
            if {"handoff", "length"} <= reasons:
                break
            time.sleep(0.1)
        assert {"handoff", "length"} <= reasons, \
            log_path.read_text()[-2000:]
        for line in lines:
            assert json.loads(line)["request_id"] == tid
        # /debug/requests on both workers: one timeline per side, both
        # under the client's id.
        recent = [t for t in group.recent_snapshot(50)
                  if t["trace_id"] == tid]
        assert {t["finish_reason"] for t in recent} \
            == {"handoff", "length"}
    finally:
        group.stop(drain=False)


def test_handoff_span_tree_three_processes(pd_fleet, oracle):
    """Tentpole, end to end across three OS processes: the router
    assembles ONE span tree under the client's trace id with router +
    prefill-worker + decode-worker spans, the handoff export/adopt
    spans adjacent and non-overlapping with prefill/decode."""
    _wait_states(pd_fleet)
    tid = "cli-span-9b1c"
    toks, done, box = [], threading.Event(), {}
    seq = Sequence(request_id=8200, prompt_tokens=list(PD_PROMPT),
                   max_new_tokens=12, trace_id=tid)
    pd_fleet.submit(seq, lambda s, t: toks.append(t),
                    lambda s: (box.update(seq=s), done.set()))
    fin = _finish(done, box)
    assert fin.finish_reason == "length"
    assert toks == oracle.generate([list(PD_PROMPT)],
                                   max_new_tokens=12)[0]

    # The assembled span tree: one trace id, three processes.
    snap = pd_fleet.trace_snapshot(tid)
    assert snap is not None
    assert snap["replicas"] == [-1, 0, 1]
    spans = {s["name"]: s for s in snap["spans"]}
    for name in ("request", "route", "handoff", "prefill",
                 "handoff_export", "handoff_adopt", "decode"):
        assert name in spans, (name, sorted(spans))
    assert spans["prefill"]["replica"] == 0
    assert spans["handoff_export"]["replica"] == 0
    assert spans["handoff_adopt"]["replica"] == 1
    assert spans["decode"]["replica"] == 1
    assert snap["tree"]["name"] == "request"

    def end(s):
        return s["ts"] + s["dur"]

    # Adjacent + non-overlapping: prefill -> export (same process,
    # exact) -> adopt (cross-process, 5 ms anchor tolerance) -> decode
    # (same process, exact by construction).
    assert end(spans["prefill"]) <= spans["handoff_export"]["ts"] + 1e-6
    assert end(spans["handoff_export"]) \
        <= spans["handoff_adopt"]["ts"] + 5e-3
    assert end(spans["handoff_adopt"]) <= spans["decode"]["ts"] + 1e-6

    # The pull path agrees with the event-frame assembly: the decode
    # worker's trace verb serves its half of the same trace.
    h1 = pd_fleet.workers[1]
    pulled = h1.client.rpc("trace", timeout=10.0, trace=tid)["spans"]
    assert {"handoff_adopt", "decode"} <= {s["name"] for s in pulled}


def test_pd_fleet_scrape_catalog_slo_and_build_info(pd_fleet):
    """Satellite: a LIVE dp=2 P/D fleet's aggregated scrape parses
    under the strict exposition parser, has no duplicate series across
    fleet aggregation, and carries the new slo / build_info series with
    correct types — per replica AND fleet-level."""
    from tests import _prom

    _wait_states(pd_fleet)
    # Traffic so the SLO windows hold data, then refresh the cached
    # worker stats the fleet-level pooled gauges read.
    toks, done, box = _submit(pd_fleet, 8100, [3, 1, 4, 1, 5], 8)
    _finish(done, box)
    pd_fleet._refresh_caches()

    meta, samples = _prom.parse(pd_fleet.prometheus_text())
    seen = set()
    for name, labels, _ in samples:
        key = (name, tuple(sorted(labels.items())))
        assert key not in seen, f"duplicate series {key}"
        seen.add(key)

    assert meta["tpu_inf_slo_ttft_seconds"]["type"] == "gauge"
    assert meta["tpu_inf_slo_tpot_seconds"]["type"] == "gauge"
    assert meta["tpu_inf_slo_breaches_total"]["type"] == "counter"
    assert meta["tpu_inf_build_info"]["type"] == "gauge"

    def rows(name):
        return [(labels, v) for n, labels, v in samples if n == name]

    slo = rows("tpu_inf_slo_ttft_seconds")
    # 2 quantiles x (2 replicas + 1 fleet-pooled).
    assert len(slo) == 6
    assert {l.get("q") for l, _ in slo} == {"0.5", "0.95"}
    fleet_p95 = next(v for l, v in slo
                     if "replica" not in l and l["q"] == "0.95")
    assert fleet_p95 > 0                      # pooled window has data
    binfo = rows("tpu_inf_build_info")
    assert len(binfo) == 3                    # 2 replicas + fleet
    for labels, v in binfo:
        assert v == 1.0
        assert labels["fleet"] == "subprocess"
        assert set(labels) >= {"version", "backend", "kv_quant",
                               "spec_mode", "routing"}
    assert len(rows("tpu_inf_slo_breaches_total")) == 6  # 2 kinds x 3


def test_worker_profile_rpc_captures_trace(pd_fleet, tmp_path):
    """Satellite surface: the profile RPC verb runs jax.profiler on a
    live worker (serving continues) and returns the trace dir under the
    operator's profile_dir."""
    import os

    _wait_states(pd_fleet)
    r = pd_fleet.capture_profile(1, seconds=0.3)
    assert r["replica"] == 1 and r["seconds"] == 0.3
    assert r["dir"].endswith("replica1")
    assert os.path.isdir(r["dir"])
    # jax wrote a plugins/profile capture under the dir.
    assert any(os.scandir(r["dir"]))
    # The worker's loop clock over the captured seconds rides the RPC.
    assert 0.3 <= r["loop"]["loop_wall_s"] < 1.3
    assert "tpu_inf_loop_starved_stage_seconds_total" in r["loop"]


_WARMUP_COMPILE_COUNTER = """
import logging, sys
records = []
handler = logging.Handler()
handler.emit = lambda rec: records.append(rec.getMessage())
import jax
jax.config.update("jax_log_compiles", True)
for n in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
    lg = logging.getLogger(n)
    lg.addHandler(handler)
    lg.setLevel(logging.DEBUG)
from tpu_inference.config import EngineConfig, tiny_llama
from tpu_inference.engine.engine import InferenceEngine
kw = dict(page_size=8, num_pages=64, max_pages_per_seq=8,
          max_batch_size=2, prefill_buckets=(16,), host_cache_pages=32)
engine = InferenceEngine(tiny_llama(vocab_size=512),
                         EngineConfig(**kw, role=sys.argv[1]), seed=0)
n0 = len(records)          # boot/param compiles, not warmup's
engine.warmup()
print("COMPILES", len(records) - n0)
"""


@pytest.mark.slow   # ~44s subprocess compile-census sweep; role validation
                    # and role-aware serving stay tier-1
def test_role_specialized_warmup_shrinks_compile_set():
    """Tentpole claim: a prefill-role warmup compiles only the prefill
    side and a decode-role warmup only the decode side, so each
    specialized role boots on a strictly smaller compile set than
    mixed while the two together still cover it. Each warmup runs in a
    FRESH python process: in-process jax shares a global pjit cache
    across engines, so a second engine's identical graphs never
    recompile and in-process counts compare nothing."""
    import subprocess

    def warmup_compiles(role):
        out = subprocess.run(
            [sys.executable, "-c", _WARMUP_COMPILE_COUNTER, role],
            capture_output=True, text=True, timeout=240,
            cwd=os.path.dirname(os.path.dirname(__file__)))
        assert out.returncode == 0, out.stderr[-2000:]
        return int(out.stdout.split("COMPILES")[1].strip())

    n_mixed = warmup_compiles("mixed")
    n_prefill = warmup_compiles("prefill")
    n_decode = warmup_compiles("decode")
    assert 0 < n_prefill < n_mixed
    assert 0 < n_decode < n_mixed
    # Specialization drops the OTHER phase's graphs, never its own:
    # the two role sets together cover at least the mixed set (shared
    # helper ops may double-count, so >=, not ==).
    assert n_prefill + n_decode >= n_mixed


def test_peek_fanout_deadline_and_cold_fallback():
    """Satellite: candidate peeks fan out CONCURRENTLY with a short
    deadline — one stalled worker no longer adds its full round-trip
    to every admission; it scores with the cold fallback while the
    fast sibling's real peek is used."""
    from tpu_inference.server.fleet import ProcessEngineGroup

    group = ProcessEngineGroup(_cfg(dp=2, route_peek_timeout_s=0.3))
    fast = {"hbm": 3, "host": 1, "load": 2, "pressure": False,
            "occupancy": 0.5, "backlog": 0, "role": "mixed"}

    def fake_peek(h, digests, timeout=10.0):
        if h.replica == 1:
            time.sleep(5.0)       # a wedged worker's round-trip
        return dict(fast)

    group._peek = fake_peek
    try:
        t0 = time.monotonic()
        peeks = group._peek_many(group.workers, [b"\x00" * 8])
        dt = time.monotonic() - t0
        assert dt < 2.0, f"fan-out waited on the straggler ({dt:.2f}s)"
        assert peeks[0] == fast
        assert peeks[1] == group._cold_peek(group.workers[1])
        # Single candidate short-circuits the pool (no thread hop).
        assert group._peek_many([group.workers[0]], []) == [fast]
    finally:
        group.stop(drain=False)


def test_worker_roles_resolution_and_guards():
    """Role-axis config contract: resolve_worker_roles expands/
    validates, pd_worker_roles sizes the split, and the in-process
    backend refuses phase roles (the handoff needs worker
    processes)."""
    from tpu_inference.config import resolve_worker_roles
    from tpu_inference.engine.autosize import pd_worker_roles
    from tpu_inference.server.http import build_engine_group

    assert resolve_worker_roles(3, ()) == ("mixed",) * 3
    assert resolve_worker_roles(2, (), default_role="prefill") == \
        ("prefill", "prefill")
    assert resolve_worker_roles(2, ("prefill", "decode")) == \
        ("prefill", "decode")
    with pytest.raises(ValueError, match="one role per dp replica"):
        resolve_worker_roles(3, ("prefill", "decode"))
    with pytest.raises(ValueError, match="unknown worker role"):
        resolve_worker_roles(1, ("chonk",))

    assert pd_worker_roles(4, "1:1") == ("prefill",) * 2 + ("decode",) * 2
    assert pd_worker_roles(4, "1:3") == ("prefill",) + ("decode",) * 3
    # auto with the BurstGPT-shaped default mix: prefill share =
    # 512 / (512 + 4*128) = 0.5.
    assert pd_worker_roles(4, "auto") == \
        ("prefill",) * 2 + ("decode",) * 2
    # Heavily decode-weighted observed mix: prefill floors at one.
    assert pd_worker_roles(4, "auto", prompt_token_rate=10,
                           decode_token_rate=1000) == \
        ("prefill",) + ("decode",) * 3
    with pytest.raises(ValueError, match="dp >= 2"):
        pd_worker_roles(1, "auto")
    with pytest.raises(ValueError, match="'auto' or 'P:D'"):
        pd_worker_roles(2, "half")
    with pytest.raises(ValueError, match=">= 1"):
        pd_worker_roles(2, "0:2")

    with pytest.raises(ValueError, match="subprocess"):
        build_engine_group(_cfg(dp=2, fleet="in-process",
                                worker_roles=("prefill", "decode")))


def test_draining_worker_refuses_submit_routes_to_sibling(fleet, oracle):
    """A request submitted while one worker drains lands on the
    sibling (the draining worker's refusal re-routes, not errors)."""
    _wait_states(fleet)
    fleet.apply_chaos({"replica": 1, "kill": "sigterm"})
    toks, done, box = _submit(fleet, 5000, [6, 6, 6], 8)
    fin = _finish(done, box)
    assert fin.finish_reason == "length"
    assert toks == oracle.generate([[6, 6, 6]], max_new_tokens=8)[0]
    _wait_states(fleet)


# -------------------------------------- Byzantine transport (PR "RPC
# fault injection, end-to-end KV integrity, poison quarantine"): the
# codec/chaos units live in test_transport.py; these drive REAL worker
# processes through frame corruption, wedged connections, garbage
# bytes, and poison-request quarantine.


def test_chaos_rpc_corruption_byte_identity(fleet, oracle):
    """Seeded frame corruption on the worker->router event stream:
    every corrupted frame is rejected by CRC (counted), the router
    reconnects WITHOUT restarting the worker process, resyncs the
    victims, and completions stay byte-identical to the oracle —
    zero silent corruptions."""
    _wait_states(fleet)
    frame_errors0 = fleet.frame_errors
    reconnects0 = fleet.reconnects
    restarts0 = sum(h.restarts for h in fleet.workers)
    r = fleet.apply_chaos({"rpc": {"seed": 42, "corrupt_rate": 0.1,
                                   "verbs": ["token"],
                                   "direction": "recv"}})
    assert r["rpc"]["corrupt_rate"] == 0.1
    try:
        a = _submit(fleet, 7000, [7, 1, 7], 48)
        b = _submit(fleet, 7001, [2, 7, 2, 7], 48)
        fin_a = _finish(a[1], a[2])
        fin_b = _finish(b[1], b[2])
    finally:
        fleet.apply_chaos({"rpc": {"corrupt_rate": 0.0}})
    assert fin_a.finish_reason == "length"
    assert fin_b.finish_reason == "length"
    assert a[0] == oracle.generate([[7, 1, 7]], max_new_tokens=48)[0]
    assert b[0] == oracle.generate([[2, 7, 2, 7]], max_new_tokens=48)[0]
    # Verified rejection happened (the acceptance counter) and was
    # healed at the CONNECTION level, not by process restart.
    assert fleet.frame_errors > frame_errors0
    assert fleet.reconnects > reconnects0
    assert sum(h.restarts for h in fleet.workers) == restarts0
    sup = fleet.supervision_counters()
    assert sup["frame_errors"] >= fleet.frame_errors - frame_errors0
    assert sup["worker_reconnects"] >= 1
    _wait_states(fleet)


def test_worker_survives_garbage_bytes(fleet, oracle):
    """Codec fuzz against a LIVE worker: a rogue connection spewing
    garbage (bad magic, torn frames, absurd lengths) is dropped with a
    typed error — the worker process neither crashes nor hangs nor
    over-allocates, and keeps serving its real connection."""
    import socket as _socket
    import struct as _struct

    _wait_states(fleet)
    h = fleet.workers[0]
    restarts0 = h.restarts
    for payload in (b"GARBAGE" * 64,
                    _struct.pack(">IIII", 0x54504631, 0xFFFFFF,
                                 0xFFFFFFFF, 0) + b"x" * 32,
                    _struct.pack(">IIII", 0x54504631, 8, 0, 0)):
        s = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
        s.settimeout(10.0)
        s.connect(h.socket_path)
        s.sendall(payload)
        s.shutdown(_socket.SHUT_WR)
        # The worker must close OUR connection (clean typed rejection),
        # not wedge on it.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                if not s.recv(4096):
                    break
            except OSError:
                break
        s.close()
    # Worker still up and serving (no restart burned).
    assert h.client.rpc("healthz")["ok"]
    assert h.restarts == restarts0
    toks, done, box = _submit(fleet, 7100, [9, 9, 9], 8)
    _finish(done, box)
    assert toks == oracle.generate([[9, 9, 9]], max_new_tokens=8)[0]


@pytest.fixture(scope="module")
def byz_fleet(tmp_path_factory):
    """Dedicated fleet for wedge + poison: fast RPC deadlines (the
    wedge detector), a 2-worker poison budget, and a blackbox dir for
    the router's flight recorder."""
    from tpu_inference.server.fleet import ProcessEngineGroup

    root = str(tmp_path_factory.mktemp("byz-blackbox"))
    group = ProcessEngineGroup(_cfg(dp=2, rpc_deadline_fast_s=2.0,
                                    rpc_deadline_slow_s=4.0,
                                    poison_max_workers=2,
                                    blackbox_dir=root))
    group.start()
    yield group
    group.stop(drain=False)


def test_wedged_connection_recycled_not_restarted(byz_fleet, oracle):
    """A connection that goes silent (wedge: open socket, writes
    swallowed) is detected by per-verb deadlines — structured
    rpc_timeout events, counter moves — and recycled; the request
    re-routes and completes byte-identically. The worker process is
    never restarted for a transport fault."""
    _wait_states(byz_fleet)
    timeouts0 = byz_fleet.rpc_timeouts
    restarts0 = sum(h.restarts for h in byz_fleet.workers)
    byz_fleet.apply_chaos({"rpc": {"seed": 9, "wedge_after": 1,
                                   "wedge_replica": 0,
                                   "direction": "send"}})
    try:
        # Submits to replica 0 vanish into the wedge until the deadline
        # watchdog recycles the connection; the attempt re-routes.
        pend = [_submit(byz_fleet, 7200 + i, [3, 3, 3 + i], 10)
                for i in range(3)]
        fins = [_finish(done, box, timeout=120.0)
                for _, done, box in pend]
    finally:
        byz_fleet.apply_chaos({"rpc": {"wedge_after": 0}})
    for i, (fin, (toks, _, _)) in enumerate(zip(fins, pend)):
        assert fin.finish_reason == "length"
        assert toks == oracle.generate([[3, 3, 3 + i]],
                                       max_new_tokens=10)[0]
    assert byz_fleet.rpc_timeouts > timeouts0
    assert sum(h.restarts for h in byz_fleet.workers) == restarts0
    _wait_states(byz_fleet)


def test_poison_request_quarantined(byz_fleet):
    """Acceptance: a request whose attempts crash poison_max_workers=2
    DISTINCT workers fails terminally with finish_reason="poison"
    (worth a structured 500 at the HTTP layer) after exactly 2 burned
    workers, the counter moves, the router's flight recorder captures
    the event, and the fleet heals and keeps serving."""
    _wait_states(byz_fleet)
    poison0 = byz_fleet.poison_requests
    rid = 7300
    toks, done, box = _submit(byz_fleet, rid, [8, 4, 8, 4], 200)
    deadline = time.monotonic() + 60
    while len(toks) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(toks) >= 2
    with byz_fleet._lock:
        first = byz_fleet._tracked[rid].worker.replica
    byz_fleet.apply_chaos({"replica": first, "kill": "kill9"})
    # Wait for the failover onto the OTHER worker to start streaming.
    deadline = time.monotonic() + 60
    second = None
    while time.monotonic() < deadline:
        with byz_fleet._lock:
            e = byz_fleet._tracked.get(rid)
            w = e.worker if e is not None else None
            second = w.replica if w is not None else None
        if second is not None and second != first:
            break
        time.sleep(0.05)
    assert second is not None and second != first
    byz_fleet.apply_chaos({"replica": second, "kill": "kill9"})

    fin = _finish(done, box, timeout=120.0)
    assert fin.finish_reason == "poison"
    assert byz_fleet.poison_requests == poison0 + 1
    sup = byz_fleet.supervision_counters()
    assert sup["poison_requests"] >= 1
    # Flight-recorder evidence: a router-side (replica--1) capture with
    # the poison trigger.
    idx = byz_fleet.blackbox_index()
    triggers = [c["trigger"] for c in idx["captures"]
                if c["replica"] == -1]
    assert "poison_request" in triggers
    # The fleet heals (both workers restart) and keeps serving.
    _wait_states(byz_fleet)
    toks2, done2, box2 = _submit(byz_fleet, 7301, [1, 2, 1], 8)
    fin2 = _finish(done2, box2)
    assert fin2.finish_reason == "length"
