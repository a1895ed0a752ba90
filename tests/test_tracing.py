"""Distributed request tracing + rolling SLO gauges (README
"Observability"): SpanRecorder units, wallclock anchoring, Chrome
export, tree assembly, the scheduler's span emission, EngineGroup
cross-replica assembly, SLO windows/breaches, and the build_info gauge.
Everything here is CPU-hermetic and in-process; the cross-PROCESS half
(worker event transport, trace RPC verb) lives in tests/test_fleet.py.
"""

import threading
import time

import pytest

import _prom
from tpu_inference import telemetry
from tpu_inference.config import (EngineConfig, ServerConfig, tiny_llama)
from tpu_inference.telemetry import (RollingWindow, SLOTracker,
                                     SpanRecorder, assemble_trace,
                                     pooled_quantile, pooled_slo,
                                     spans_to_chrome)

ENGINE_KW = dict(page_size=8, num_pages=64, max_pages_per_seq=8,
                 max_batch_size=2, prefill_buckets=(16,))


# ------------------------------------------------------------- units


def test_span_recorder_add_seal_export():
    rec = SpanRecorder(enabled=True, replica=3)
    t0 = time.perf_counter()
    rec.add("prefill", "t1", t0, t0 + 0.5, cached_tokens=4)
    rec.add("decode", "t1", t0 + 0.5, t0 + 1.0)
    assert rec.export_open("t1") and rec.export_recent("t1") == []
    rec.seal("t1")
    spans = rec.export_recent("t1")
    assert [s["name"] for s in spans] == ["prefill", "decode"]
    assert all(s["replica"] == 3 and s["trace"] == "t1" for s in spans)
    assert spans[0]["attrs"]["cached_tokens"] == 4
    # Wallclock anchoring: a perf_counter start maps to ~now in unix.
    assert abs(spans[0]["ts"] - time.time()) < 5.0
    assert spans[0]["dur"] == pytest.approx(0.5, abs=1e-6)
    # Export after seal keeps the ring copy (trace verb re-reads it).
    assert rec.get_trace("t1") is not None
    assert rec.recent_traces(10) == {"t1": spans}


def test_span_recorder_caps_and_disabled():
    rec = SpanRecorder(enabled=True)
    t = time.perf_counter()
    for i in range(rec.MAX_SPANS_PER_TRACE + 10):
        rec.add("prefill_chunk", "big", t, t + 0.001)
    assert len(rec.export_open("big")) == rec.MAX_SPANS_PER_TRACE
    assert rec.spans_dropped == 10
    # Unsealed traces (engine-direct callers) can never grow without
    # bound: the open table evicts oldest-first at MAX_TRACES.
    for i in range(rec.MAX_TRACES + 5):
        rec.add("prefill", f"open-{i}", t, t + 0.001)
    assert rec.export_open("big") == []          # evicted
    off = SpanRecorder(enabled=False)
    off.add("prefill", "x", t, t + 1)
    off.add_maintenance("kv_swap_out", t, t + 1)
    off.seal("x")
    assert off.get_trace("x") is None and off.maintenance_spans() == []


def test_span_recorder_ingest_after_seal():
    """A worker's finish-frame spans can arrive after the router sealed
    the trace (handoff traces span two connections): they must still
    join the sealed trace, not a fresh open one."""
    rec = SpanRecorder(enabled=True, replica=-1)
    t = time.perf_counter()
    rec.add("request", "h1", t, t + 1.0, parent="")
    rec.seal("h1")
    rec.ingest("h1", [{"name": "prefill", "trace": "h1", "parent":
                       "request", "ts": time.time(), "dur": 0.2,
                       "replica": 0}])
    names = {s["name"] for s in rec.get_trace("h1")}
    assert names == {"request", "prefill"}


def test_assemble_trace_parent_rules():
    now = time.time()

    def span(name, parent, ts, dur, replica=0):
        return {"name": name, "trace": "t", "parent": parent,
                "ts": ts, "dur": dur, "replica": replica}

    spans = [
        span("request", "", now, 2.0, replica=-1),
        span("queue_wait", "request", now + 0.0, 0.1),
        span("prefill", "request", now + 0.1, 0.5),
        span("prefill_chunk", "prefill", now + 0.1, 0.2),
        span("prefill_chunk", "prefill", now + 0.3, 0.2),
        span("decode", "request", now + 0.6, 1.0, replica=1),
        span("orphan_name", "no_such_parent", now + 0.2, 0.1),
    ]
    tree = assemble_trace("t", spans)
    assert tree["trace_id"] == "t" and tree["n_spans"] == 7
    assert tree["replicas"] == [-1, 0, 1]
    root = tree["tree"]
    assert root["name"] == "request" and "synthetic" not in root
    kids = [c["name"] for c in root["children"]]
    assert kids == ["queue_wait", "prefill", "orphan_name", "decode"]
    prefill = next(c for c in root["children"] if c["name"] == "prefill")
    assert [c["name"] for c in prefill["children"]] == \
        ["prefill_chunk", "prefill_chunk"]
    # No root span at all -> synthetic envelope covering everything.
    tree2 = assemble_trace("t", spans[1:3])
    assert tree2["tree"]["synthetic"] is True
    assert len(tree2["tree"]["children"]) == 2


def test_spans_to_chrome_shape():
    now = time.time()
    traces = {"tA": [
        {"name": "request", "trace": "tA", "parent": "", "ts": now,
         "dur": 1.0, "replica": -1},
        {"name": "prefill", "trace": "tA", "parent": "request",
         "ts": now + 0.1, "dur": 0.4, "replica": 0,
         "attrs": {"cached_tokens": 2}},
    ]}
    maint = [{"name": "kv_swap_out", "trace": "-maintenance-",
              "parent": "", "ts": now, "dur": 0.01, "replica": 0,
              "attrs": {"pages": 3}}]
    chrome = spans_to_chrome(traces, {0: "router", 1: "replica 0"},
                             maintenance=maint,
                             other_data={"note": 1})
    evs = chrome["traceEvents"]
    assert chrome["displayTimeUnit"] == "ms"
    assert chrome["otherData"] == {"note": 1}
    x = [e for e in evs if e["ph"] == "X"]
    meta = [e for e in evs if e["ph"] == "M"]
    # Router span on pid 0, worker span on pid 1, maintenance tid 0.
    assert {e["pid"] for e in x} == {0, 1}
    req = next(e for e in x if e["name"] == "request")
    pf = next(e for e in x if e["name"] == "prefill")
    assert req["pid"] == 0 and pf["pid"] == 1
    assert pf["args"]["trace_id"] == "tA"
    assert pf["args"]["cached_tokens"] == 2
    assert pf["ts"] == pytest.approx((now + 0.1) * 1e6, abs=1.0)
    assert pf["dur"] == pytest.approx(0.4e6, abs=1.0)
    m = next(e for e in x if e["name"] == "kv_swap_out")
    assert m["tid"] == 0 and m["cat"] == "maintenance"
    assert {e["name"] for e in meta} >= {"process_name", "thread_name"}


def test_rolling_window_and_pooled_quantiles():
    w = RollingWindow(size=4)
    assert w.quantile(0.95) is None
    for v in (1.0, 2.0, 3.0, 4.0):
        w.observe(v)
    assert w.quantile(0.5) == 3.0 and w.quantile(0.95) == 4.0
    w.observe(10.0)                       # evicts the oldest (1.0)
    assert sorted(w.values()) == [2.0, 3.0, 4.0, 10.0]
    # Pooling is over raw values, not per-window quantiles.
    assert pooled_quantile([[1.0, 1.0, 1.0], [100.0]], 0.5) == 1.0
    assert pooled_quantile([[], []], 0.5) is None


def test_slo_tracker_breaches_and_pooling():
    slo = SLOTracker(ttft_target_s=0.1, tpot_target_s=0.01)
    slo.observe(0.05, 0.005)              # within both targets
    slo.observe(0.5, 0.05)                # breaches both
    slo.observe(None, 0.005)              # tpot-only observation
    assert slo.ttft_breaches == 1 and slo.tpot_breaches == 1
    snap = slo.snapshot()
    assert snap["ttft_target_s"] == 0.1
    assert snap["ttft_p95_s"] == 0.5
    assert len(snap["tpot_window"]) == 3
    # No target -> quantiles yes, breaches never.
    free = SLOTracker()
    free.observe(100.0, 100.0)
    assert free.ttft_breaches == 0
    assert free.snapshot()["ttft_target_s"] is None
    pooled = pooled_slo([snap, free.snapshot()])
    assert pooled["ttft_breaches"] == 1
    assert pooled["ttft_p95_s"] == 100.0  # pooled across both windows
    import math
    assert math.isnan(SLOTracker().gauge_value("ttft", 0.95))


def test_emit_build_info_stable_series():
    r = telemetry.Registry()
    device = {"platform": "cpu", "kind": "cpu", "attn_backend": "dense"}
    telemetry.emit_build_info(r, device=device, fleet="subprocess",
                              kv_quant="int8", spec_mode="ngram",
                              routing="prefix_affinity")
    # Re-emitting (a worker restart) replaces in place: one series.
    telemetry.emit_build_info(r, device=device, fleet="subprocess",
                              kv_quant="int8", spec_mode="ngram",
                              routing="prefix_affinity")
    text = telemetry.render_prometheus([({"replica": "0"}, r)])
    meta, samples = _prom.parse(text)
    rows = [(labels, v) for name, labels, v in samples
            if name == "tpu_inf_build_info"]
    assert len(rows) == 1
    labels, value = rows[0]
    assert value == 1.0
    from tpu_inference import __version__
    assert labels["version"] == __version__
    assert labels["kv_quant"] == "int8" and labels["fleet"] == "subprocess"
    assert (labels["backend"], labels["device_kind"],
            labels["attn_backend"]) == ("cpu", "cpu", "dense")
    assert meta["tpu_inf_build_info"]["type"] == "gauge"


# ------------------------------------- scheduler/engine span emission


def _run_one(engine, seq, timeout=120.0):
    from tpu_inference.engine.scheduler import EngineScheduler

    sched = EngineScheduler(engine)
    sched.start()
    done = threading.Event()
    try:
        sched.submit(seq, lambda s, t: None, lambda s: done.set())
        assert done.wait(timeout)
    finally:
        sched.stop(drain=False)
    return sched


def test_scheduler_emits_phase_spans_and_slo():
    from tpu_inference.engine.engine import InferenceEngine, Sequence

    engine = InferenceEngine(
        tiny_llama(512),
        EngineConfig(**ENGINE_KW, slo_ttft_ms=10_000.0,
                     slo_tpot_ms=0.000001),
        seed=0)
    seq = Sequence(request_id=7, prompt_tokens=[1, 2, 3, 4, 5],
                   max_new_tokens=6, trace_id="trace-abc")
    _run_one(engine, seq)
    rec = engine.telemetry.recorder
    spans = rec.export_recent("trace-abc")
    names = [s["name"] for s in spans]
    assert names.count("queue_wait") == 1
    assert names.count("prefill") == 1
    assert names.count("decode") == 1
    decode = next(s for s in spans if s["name"] == "decode")
    assert decode["attrs"]["output_tokens"] == 6
    assert decode["attrs"]["reason"] == "length"
    prefill = next(s for s in spans if s["name"] == "prefill")
    # Phases abut: prefill ends where decode begins (same timestamp).
    assert (prefill["ts"] + prefill["dur"]
            == pytest.approx(decode["ts"], abs=1e-5))
    # SLO window observed the request; the absurd TPOT target breached,
    # the generous TTFT one did not.
    slo = engine.telemetry.slo
    assert slo.ttft.count == 1 and slo.tpot.count == 1
    assert slo.ttft_breaches == 0 and slo.tpot_breaches == 1
    # Prometheus side: gauges + breach counters render and parse.
    text = telemetry.render_prometheus(
        [({"replica": "0"}, engine.telemetry.registry)])
    _, samples = _prom.parse(text)
    by = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
    assert by[("tpu_inf_slo_breaches_total",
               (("replica", "0"), ("slo", "tpot")))] == 1
    assert by[("tpu_inf_slo_ttft_seconds",
               (("q", "0.95"), ("replica", "0")))] > 0


def test_disabled_telemetry_disables_spans(monkeypatch):
    """TPU_INF_TELEMETRY=0 must kill spans too — the overhead budget's
    comparison arm covers the whole observability layer."""
    monkeypatch.setenv("TPU_INF_TELEMETRY", "0")
    from tpu_inference.engine.engine import InferenceEngine, Sequence

    engine = InferenceEngine(tiny_llama(512), EngineConfig(**ENGINE_KW),
                             seed=0)
    assert engine.telemetry.slo is None
    seq = Sequence(request_id=8, prompt_tokens=[2, 4, 6],
                   max_new_tokens=4, trace_id="t-off")
    _run_one(engine, seq)
    assert engine.telemetry.recorder.get_trace("t-off") is None


# ------------------------------------------- EngineGroup (in-process)


@pytest.fixture(scope="module")
def group():
    from tpu_inference.engine.engine import InferenceEngine
    from tpu_inference.server.replicas import EngineGroup

    engines = [InferenceEngine(tiny_llama(512),
                               EngineConfig(**ENGINE_KW,
                                            slo_ttft_ms=10_000.0),
                               seed=0)
               for _ in range(2)]
    g = EngineGroup(engines, ServerConfig(model_name="t",
                                          tokenizer="byte"))
    g.start()
    yield g
    g.stop(drain=False)


def _group_run(group, rid, prompt, trace_id="", max_new=6):
    from tpu_inference.engine.engine import Sequence

    done = threading.Event()
    seq = Sequence(request_id=rid, prompt_tokens=list(prompt),
                   max_new_tokens=max_new, trace_id=trace_id)
    group.submit(seq, lambda s, t: None, lambda s: done.set())
    assert done.wait(120)
    return seq


def test_group_assembles_cross_replica_trace(group):
    seq = _group_run(group, 100, [1, 2, 3, 4], trace_id="grp-1")
    deadline = time.monotonic() + 10
    snap = None
    while time.monotonic() < deadline:
        snap = group.trace_snapshot("grp-1")
        if snap and {"request", "route", "decode"} <= {
                s["name"] for s in snap["spans"]}:
            break
        time.sleep(0.02)
    assert snap is not None
    names = {s["name"] for s in snap["spans"]}
    assert {"request", "route", "queue_wait", "prefill",
            "decode"} <= names
    root = snap["tree"]
    assert root["name"] == "request" and root["replica"] == -1
    # The engine-side spans carry the replica the request ran on.
    decode = next(s for s in snap["spans"] if s["name"] == "decode")
    assert decode["replica"] == seq.routed_replica
    # Chrome export: router pid 0, the serving replica's pid = idx + 1.
    chrome = group.trace_chrome()
    x = [e for e in chrome["traceEvents"] if e.get("ph") == "X"
         and e["args"].get("trace_id") == "grp-1"]
    assert {e["pid"] for e in x} == {0, seq.routed_replica + 1}


def test_group_mints_trace_id_when_absent(group):
    seq = _group_run(group, 101, [9, 8, 7])
    assert seq.trace_id            # minted at submit
    deadline = time.monotonic() + 10
    while (time.monotonic() < deadline
           and group.trace_snapshot(seq.trace_id) is None):
        time.sleep(0.02)
    assert group.trace_snapshot(seq.trace_id) is not None
    assert group.trace_snapshot("no-such-trace") is None


def test_group_health_and_stats_carry_slo(group):
    _group_run(group, 102, [5, 5, 5])
    hz = group.health_snapshot()
    assert hz["slo"]["window_requests"] >= 1
    assert hz["slo"]["ttft_p95_s"] is not None
    assert all("slo" in r for r in hz["replicas"])
    ss = group.stats_snapshot()
    assert ss["slo"]["ttft_p95_s"] is not None
    # The fleet scrape carries per-replica AND pooled slo series with
    # no duplicate (name, labels) pairs.
    _, samples = _prom.parse(group.prometheus_text())
    seen = set()
    for name, labels, _ in samples:
        key = (name, tuple(sorted(labels.items())))
        assert key not in seen, key
        seen.add(key)
    slo_rows = [l for n, l, v in samples
                if n == "tpu_inf_slo_ttft_seconds"]
    with_replica = [l for l in slo_rows if "replica" in l]
    fleet_rows = [l for l in slo_rows if "replica" not in l]
    assert len(with_replica) == 4 and len(fleet_rows) == 2   # 2q x 2rep
    binfo = [l for n, l, v in samples if n == "tpu_inf_build_info"]
    assert len(binfo) == 3                                   # 2rep+fleet


# ------------------------------- the program's spans in the profiler


def _profile_events(trace_dir):
    """{event name: [its stats dicts]} of the host planes of the one
    .xplane.pb under ``trace_dir``; each dict also holds the event's
    ``_line``, ``_start_ns`` and ``_end_ns``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir) + "/**/*.xplane.pb", recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tpu_inf/"):
                    events.setdefault(ev.name, []).append(
                        {**{k: v for k, v in ev.stats},
                         "_line": (plane.name, line.name),
                         "_start_ns": ev.start_ns,
                         "_end_ns": ev.start_ns + ev.duration_ns})
    return events


def test_profile_holds_phase_and_dispatch_annotations(tmp_path):
    """A CPU profile taken through capture_jax_profile carries the loop
    clock's phases and one tpu_inf/dispatch per device dispatch (with its
    number, the ledger's ``seq``); outside a capture nothing is emitted;
    and no step program is anonymous."""
    from tpu_inference.engine.engine import InferenceEngine, Sequence
    from tpu_inference.engine.scheduler import EngineScheduler

    engine = InferenceEngine(tiny_llama(512), EngineConfig(**ENGINE_KW),
                             seed=0)
    sched = EngineScheduler(engine)
    sched.start()

    def one(rid):
        done = threading.Event()
        sched.submit(Sequence(request_id=rid, prompt_tokens=[1, 2, 3, rid],
                              max_new_tokens=24),
                     lambda s, t: None, lambda s: done.set())
        assert done.wait(120)

    try:
        one(1)                             # compiles, before any capture
        assert not telemetry.profile_capturing()
        assert engine.telemetry.clock._ann is None
        seq_before = engine._dispatch_seq
        feeder = threading.Thread(target=lambda: [one(r) for r in (2, 3, 4)])
        out = {}
        prof = threading.Thread(target=lambda: out.update(
            telemetry.capture_jax_profile(str(tmp_path), 0, 1.0,
                                          engine.telemetry)))
        prof.start()
        time.sleep(0.2)
        feeder.start()
        feeder.join(120)
        prof.join(120)
        assert not telemetry.profile_capturing()
        one(5)                             # after: again no annotation
        assert engine.telemetry.clock._ann is None
    finally:
        sched.stop(drain=False)
    events = _profile_events(out["dir"])
    for phase in ("stage", "enqueue", "device_wait", "deliver"):
        assert events.get("tpu_inf/" + phase), (phase, sorted(events))
    parts = {"tpu_inf/stage/" + p for p in ("pages", "fill", "put")}
    assert set(events) <= {"tpu_inf/dispatch"} | parts | {
        "tpu_inf/" + p for p in telemetry.LOOP_PHASES}
    # Each marked part of stage lies inside a tpu_inf/stage visit of the
    # same thread (``rest`` is the visit's own uncovered time).
    assert parts <= set(events), sorted(events)
    stages = events["tpu_inf/stage"]
    for name in parts:
        for ev in events[name]:
            assert any(s["_line"] == ev["_line"]
                       and s["_start_ns"] <= ev["_start_ns"]
                       and ev["_end_ns"] <= s["_end_ns"] for s in stages), ev
    # The clock's own statement about the captured second.
    loop = out["loop"]
    assert 1.0 <= loop["loop_wall_s"] < 2.0
    assert loop["tpu_inf_decode_dispatches_total"] >= len(
        [d for d in events["tpu_inf/dispatch"] if d["kind"] == "decode"]) - 1
    assert 0 < loop["tpu_inf_loop_stage_put_seconds_total"] \
        <= loop["tpu_inf_loop_stage_seconds_total"] + 1e-6
    dispatches = events["tpu_inf/dispatch"]
    seqs = sorted(int(d["seq"]) for d in dispatches)
    assert seqs and seqs == sorted(set(seqs))
    assert seq_before < seqs[0] and seqs[-1] <= engine._dispatch_seq
    kinds = {d["kind"] for d in dispatches}
    assert "decode" in kinds and kinds <= set(telemetry.STEP_KINDS)
    assert all({"rung", "slots", "tokens", "chunk_tokens"} <= set(d)
               for d in dispatches)
    # The ledger joins on the same numbers.
    ledger_seqs = {r["seq"] for r in engine.telemetry.step_ledger.snapshot()}
    assert set(seqs) <= ledger_seqs


def test_step_programs_have_stable_names():
    """No step program compiles as jit__unknown: one name per role."""
    from tpu_inference.engine import kv_cache as kvc
    from tpu_inference.engine.engine import InferenceEngine

    plain = InferenceEngine(
        tiny_llama(512), EngineConfig(**ENGINE_KW, decode_steps_per_call=8,
                                      hybrid_prefill=True), seed=0)
    spec = InferenceEngine(
        tiny_llama(512), EngineConfig(**ENGINE_KW,
                                      num_speculative_tokens=3), seed=0)
    names = {
        "prefill": plain._prefill_jit.__name__,
        "decode_k": plain._decode_multi_jit.__name__,
        "decode_1": plain._decode_one_jit.__name__,
        "hybrid": plain._hybrid_jit.__name__,
        "verify": spec._verify_jit.__name__,
        "offload": kvc._offload_jit.__name__,
        "restore": kvc._restore_jit.__name__,
    }
    assert names == {
        "prefill": "tpu_inf_prefill", "decode_k": "tpu_inf_decode_k8",
        "decode_1": "tpu_inf_decode_1", "hybrid": "tpu_inf_hybrid",
        "verify": "tpu_inf_spec_verify", "offload": "tpu_inf_kv_offload",
        "restore": "tpu_inf_kv_restore"}
    # And the name is what XLA is given.
    import jax.numpy as jnp
    hlo = kvc._offload_jit.lower(jnp.zeros((2, 4, 3)),
                                 jnp.zeros((2,), jnp.int32)).as_text()
    assert "jit_tpu_inf_kv_offload" in hlo and "unknown" not in hlo


def test_queue_wait_split_sums_to_queue_wait():
    """Per request: boundary + capacity = queue_wait, on the span and in
    the three histograms, from the same timestamps."""
    from tpu_inference.engine.engine import InferenceEngine, Sequence
    from tpu_inference.engine.scheduler import EngineScheduler

    engine = InferenceEngine(tiny_llama(512), EngineConfig(**ENGINE_KW),
                             seed=0)
    sched = EngineScheduler(engine)
    sched.start()
    done = threading.Event()
    left = [5]

    def on_finish(seq):
        left[0] -= 1
        if left[0] == 0:
            done.set()

    try:
        # 5 requests into 2 slots: three wait for capacity.
        for rid in range(5):
            sched.submit(Sequence(request_id=rid,
                                  prompt_tokens=[1, 2, 3, 4 + rid],
                                  max_new_tokens=8, trace_id=f"qw-{rid}"),
                         lambda s, t: None, on_finish)
        assert done.wait(120)
    finally:
        sched.stop(drain=False)
    tel = engine.telemetry
    capacity_total = 0.0
    for rid in range(5):
        (span,) = [s for s in tel.recorder.export_recent(f"qw-{rid}")
                   if s["name"] == "queue_wait"]
        a = span["attrs"]
        assert a["boundary_wait_s"] >= 0 and a["capacity_wait_s"] >= 0
        assert a["boundary_wait_s"] + a["capacity_wait_s"] == pytest.approx(
            span["dur"], abs=3e-6)
        capacity_total += a["capacity_wait_s"]
    assert tel.queue_wait_s.count == 5
    assert tel.queue_boundary_wait_s.count == 5
    assert tel.queue_capacity_wait_s.count == 5
    assert (tel.queue_boundary_wait_s.sum + tel.queue_capacity_wait_s.sum
            == pytest.approx(tel.queue_wait_s.sum, rel=1e-9))
    # The three that found no free slot waited for capacity, visibly.
    assert tel.queue_capacity_wait_s.sum == pytest.approx(capacity_total,
                                                          abs=2e-5)
    assert capacity_total > 0
