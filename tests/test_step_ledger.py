"""Step ledger + roofline attribution + flight recorder (README
"Performance attribution").

Unit level: ring semantics and overflow, pinned bottleneck verdicts on
synthetic records through the analytic cost model, dispatch identity
and observed times (seq, t_enqueue, t_done),
fleet merging, the flight recorder's capture/retention/rate-limit
behavior, the blackbox index, and the telemetry kill switch.

Process level: ONE consolidated dp=2 subprocess-fleet test drives real
traffic over HTTP, reads per-replica verdicts from GET /debug/steps,
then kill -9s a worker and finds its surviving blackbox
capture at GET /debug/blackbox.
"""

import json
import os
import time

import pytest

from tpu_inference import telemetry
from tpu_inference.telemetry import (NULL_LEDGER, STEP_FIELDS, EngineTelemetry,
                                     FlightRecorder, Histogram, StepCostModel,
                                     StepLedger, attach_flight_recorder,
                                     blackbox_index, merge_steps_reports,
                                     percentile_from_cumulative,
                                     roofline_report)

# ------------------------------------------------------------- ring


def test_ledger_ring_semantics_and_overflow():
    led = StepLedger(depth=2)
    assert led.depth == 8, "depth must floor at 8"
    led = StepLedger(depth=8)
    for i in range(5):
        led.push("decode", rung=4, slots=2, tokens=i, chunk_tokens=0,
                 steps=1, device_s=0.01, staging_s=0.0, bubble_s=0.0,
                 kv_read_tokens=10, kv_swap_bytes=0.0, spec_accepted=0,
                 compile_event=False)
    assert led.count == 5 and not led.overflowed
    recs = led.records()
    assert [r[4] for r in recs] == [0, 1, 2, 3, 4], "oldest first"
    # Overflow: ring keeps exactly depth records, still oldest-first.
    for i in range(5, 20):
        led.push("decode", 4, 2, i, 0, 1, 0.01, 0.0, 0.0, 10, 0.0, 0,
                 False)
    assert led.count == 20 and led.overflowed
    recs = led.records()
    assert len(recs) == 8
    assert [r[4] for r in recs] == list(range(12, 20))
    # snapshot: one dict per record, keyed exactly by STEP_FIELDS.
    snap = led.snapshot()
    assert len(snap) == 8 and set(snap[0]) == set(STEP_FIELDS)
    assert snap[-1]["tokens"] == 19 and snap[-1]["kind"] == "decode"


def test_null_ledger_is_inert():
    NULL_LEDGER.push("decode", 4, 2, 1, 0, 1, 0.01, 0.0, 0.0, 0, 0.0, 0,
                     False)
    assert NULL_LEDGER.records() == []
    assert NULL_LEDGER.snapshot() == []
    assert NULL_LEDGER.count == 0 and not NULL_LEDGER.overflowed


# ------------------------------------------------------- roofline


def _model(**kw):
    base = dict(n_params=1000, n_layers=1, n_heads=1, head_dim=1,
                weight_bytes=1000, kv_token_bytes=0, peak_flops=1e6,
                peak_hbm_bw=1e6)
    base.update(kw)
    return StepCostModel(**base)


def test_roofline_pinned_verdicts():
    """Three synthetic records, one per bottleneck regime, graded by a
    hand-sized cost model — the verdict semantics the README documents,
    pinned."""
    model = _model()
    led = StepLedger(depth=16)
    # compute-bound: 500 tokens in 1 s = 2*1000*500 = 1e6 FLOPs/s
    # (compute_frac 1.0) vs 1000 weight bytes/s (hbm_frac 1e-3).
    led.push("decode", rung=4, slots=4, tokens=500, chunk_tokens=0,
             steps=1, device_s=1.0, staging_s=0.0, bubble_s=0.0,
             kv_read_tokens=0, kv_swap_bytes=0.0, spec_accepted=0,
             compile_event=False)
    # hbm-bound: 1000 device iterations stream the weights 1000 times
    # (1e6 bytes/s, hbm_frac 1.0) for only 2 positions of matmul work.
    led.push("prefill_chunk", rung=0, slots=1, tokens=1, chunk_tokens=1,
             steps=1000, device_s=1.0, staging_s=0.0, bubble_s=0.0,
             kv_read_tokens=0, kv_swap_bytes=0.0, spec_accepted=0,
             compile_event=True)
    # host-bound: staging + bubble (0.5 s) dominates device wall (0.1 s)
    # -> host_frac ~0.83 regardless of the roofline fractions.
    led.push("hybrid", rung=2, slots=2, tokens=10, chunk_tokens=16,
             steps=2, device_s=0.1, staging_s=0.3, bubble_s=0.2,
             kv_read_tokens=50, kv_swap_bytes=0.0, spec_accepted=0,
             compile_event=False)

    rep = roofline_report(led, model)
    assert rep["enabled"] and rep["records_window"] == 3
    assert not rep["truncated"]
    kinds = rep["kinds"]
    assert kinds["decode"]["verdict"] == "compute-bound"
    assert kinds["prefill_chunk"]["verdict"] == "hbm-bound"
    assert kinds["hybrid"]["verdict"] == "host-bound"
    # Achieved rates come straight from the analytic model.
    assert kinds["decode"]["achieved_flops_per_s"] == pytest.approx(1e6)
    assert kinds["prefill_chunk"]["achieved_bytes_per_s"] == (
        pytest.approx(1e6, rel=1e-3))
    assert kinds["hybrid"]["host_frac"] == pytest.approx(0.5 / 0.6,
                                                         rel=1e-3)
    # Occupancy: prefill_chunk is excluded (no decode lanes).
    assert set(rep["rung_occupancy"]) == {"4", "2"}
    assert rep["rung_occupancy"]["4"] == {"dispatches": 1,
                                          "mean_slots": 4.0}
    assert rep["compile_events"] == 1
    # Window filtering: a "now" past the window empties the report.
    empty = roofline_report(led, model, now=time.time() + 3600)
    assert empty["records_window"] == 0 and empty["kinds"] == {}


def test_without_peaks_nothing_is_rated():
    """No chip, no peaks: the same records still sum (counts, seconds,
    FLOPs, bytes) but carry no roofline share and no bottleneck verdict,
    and the ledger-replayed MFU is None — "not measured", never a
    number against a chip that is not there."""
    led = StepLedger(depth=8)
    led.push("decode", rung=4, slots=4, tokens=500, chunk_tokens=0,
             steps=1, device_s=1.0, staging_s=0.0, bubble_s=0.0,
             kv_read_tokens=0, kv_swap_bytes=0.0, spec_accepted=0,
             compile_event=False)
    rep = roofline_report(led, _model(peak_flops=None, peak_hbm_bw=None))
    agg = rep["kinds"]["decode"]
    assert agg["verdict"] == telemetry.NOT_MEASURED
    assert "compute_frac" not in agg and "hbm_frac" not in agg
    assert agg["achieved_flops_per_s"] == pytest.approx(1e6)
    assert rep["peaks"] == {"flops_per_s": None, "hbm_bytes_per_s": None}
    assert rep["mfu"] == {"gauge": None}
    merged = telemetry.merge_steps_reports([rep, rep])
    assert merged["kinds"]["decode"]["verdict"] == telemetry.NOT_MEASURED


def test_kv_read_attention_flops_counted():
    """Attention FLOPs scale with (query, context) pairs attended —
    the term that makes long-context decode drift toward hbm-bound."""
    model = _model(n_layers=2, n_heads=4, head_dim=8)
    rec = (time.time(), "decode", 4, 4, 10, 0, 1, 0.5, 0.0, 0.0,
           1000, 0.0, 0, 0)
    assert model.flops(rec) == pytest.approx(
        2.0 * 1000 * 10 + 4.0 * 2 * 4 * 8 * 1000)
    assert model.hbm_bytes(rec) == pytest.approx(1000 * 1 + 0 + 0.0)


def test_merge_steps_reports_pools_and_refinalizes():
    model = _model()
    led = StepLedger(depth=16)
    led.push("decode", 4, 4, 500, 0, 1, 1.0, 0.0, 0.0, 0, 0.0, 0, False)
    rep = roofline_report(led, model)
    merged = merge_steps_reports([rep, rep, None, {"enabled": False}])
    assert merged["enabled"] and merged["replicas_merged"] == 2
    assert merged["records_window"] == 2
    k = merged["kinds"]["decode"]
    assert k["records"] == 2 and k["tokens"] == 1000
    # Pooled rate: 2e6 FLOPs over 2 s of device wall — same verdict.
    assert k["achieved_flops_per_s"] == pytest.approx(1e6)
    assert k["verdict"] == "compute-bound"
    assert merged["rung_occupancy"]["4"] == {"dispatches": 2,
                                             "mean_slots": 4.0}
    assert merge_steps_reports([]) == {"enabled": False}
    assert merge_steps_reports([None, {"enabled": False}]) == {
        "enabled": False}


def test_quantile_implementations_unified():
    """Histogram.percentile and percentile_from_cumulative are ONE
    implementation (the server-side interpolation the traffic
    generator's client-side percentiles mirror) — pinned on a known
    distribution."""
    h = Histogram("t", "t", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    for p in (0.5, 0.95, 0.99):
        assert h.percentile(p) == percentile_from_cumulative(
            h.bounds, h.cumulative(), p)
    # 4 samples, target p50 = 2.0 cum -> bucket (1, 2], 1 prior, 2 in
    # bucket -> 1 + (2 - 1) * (2 - 1) / 2 = 1.5.
    assert h.percentile(0.5) == pytest.approx(1.5)
    assert percentile_from_cumulative((1.0, 2.0, 4.0), (0, 0, 0), 0.5) \
        is None


# -------------------------------------------------- kill switch


def test_telemetry_disabled_kills_ledger_and_recorder(tmp_path):
    tel = EngineTelemetry(enabled=False)
    assert tel.step_ledger is NULL_LEDGER
    tel.step_ledger.push("decode", 4, 1, 1, 0, 1, 0.01, 0.0, 0.0, 0,
                         0.0, 0, False)
    assert tel.steps_report() == {"enabled": False}
    assert attach_flight_recorder(tel, str(tmp_path), 0) is None
    assert tel.flight is None
    assert list(tmp_path.iterdir()) == [], "no blackbox I/O when off"
    # Empty root dir: no-op even with telemetry on.
    assert attach_flight_recorder(EngineTelemetry(enabled=True),
                                  "", 0) is None


# ---------------------------------------------- flight recorder


def test_flight_recorder_capture_retention_rate_limit(tmp_path):
    root = str(tmp_path / "bb")
    steps = [{"kind": "decode", "tokens": 3}]
    fr = FlightRecorder(root, replica=1, retain=2,
                        config={"dp": 2},
                        steps_fn=lambda: steps,
                        spans_fn=lambda: [{"name": "request"}],
                        stats_fn=lambda: {"ok": True})
    path = fr.capture("step_error", min_interval_s=0.0)
    assert path and os.path.exists(path)
    payload = json.loads(open(path).read())
    assert payload["trigger"] == "step_error"
    assert payload["replica"] == 1 and payload["pid"] == os.getpid()
    assert payload["steps"] == steps
    assert payload["spans"] == [{"name": "request"}]
    assert payload["config"] == {"dp": 2}
    assert payload["stats"] == {"ok": True}
    # Per-trigger rate limit: an immediate repeat is dropped.
    assert fr.capture("step_error", min_interval_s=60.0) is None
    # Retention: only the newest `retain` captures survive pruning.
    for i in range(4):
        assert fr.capture(f"t{i}", min_interval_s=0.0)
    caps = sorted(f for f in os.listdir(fr.dir)
                  if f.startswith("capture-"))
    assert len(caps) == 2 and caps == ["capture-000003-t2.json",
                                       "capture-000004-t3.json"]
    # Periodic heartbeat: single refreshed file, interval-gated.
    assert fr.maybe_periodic()
    assert fr.join_beat(10.0)          # the write runs on its own thread
    assert os.path.exists(os.path.join(fr.dir, "periodic.json"))
    # A restart adopts the dead incarnation's heartbeat as a numbered
    # postmortem (the kill -9 evidence) before it can be overwritten,
    # and sequence numbers resume past every existing capture.
    fr2 = FlightRecorder(root, replica=1, retain=2)
    pm = os.path.join(fr2.dir, "capture-000005-postmortem.json")
    assert os.path.exists(pm)
    assert json.loads(open(pm).read())["trigger"] == "postmortem"
    assert not os.path.exists(os.path.join(fr2.dir, "periodic.json"))
    p2 = fr2.capture("boot", min_interval_s=0.0)
    assert os.path.basename(p2) == "capture-000006-boot.json"
    # A failing section callback degrades to empty, never raises.
    fr3 = FlightRecorder(root, replica=1, retain=8,
                         steps_fn=lambda: 1 / 0)
    p3 = fr3.capture("bad_fn", min_interval_s=0.0)
    assert json.loads(open(p3).read())["steps"] == []


def test_blackbox_index_lists_newest_first(tmp_path):
    root = str(tmp_path)
    assert blackbox_index("") == {"dir": "", "captures": []}
    assert blackbox_index(str(tmp_path / "nope"))["captures"] == []
    for rep in (0, 1):
        fr = FlightRecorder(root, replica=rep, retain=8,
                            steps_fn=lambda: [{}, {}])
        fr.capture("watchdog", min_interval_s=0.0)
    # An unreadable capture is reported, not fatal.
    bad = tmp_path / "replica-0" / "capture-999999-junk.json"
    bad.write_text("{not json")
    idx = blackbox_index(root)
    assert idx["dir"] == root
    entries = idx["captures"]
    assert {e["replica"] for e in entries} == {0, 1}
    good = [e for e in entries if "error" not in e]
    assert all(e["trigger"] == "watchdog" and e["n_steps"] == 2
               and e["pid"] == os.getpid() for e in good)
    ts = [e["ts"] for e in good]
    assert ts == sorted(ts, reverse=True), "newest first"
    assert any(e.get("error") == "unreadable" for e in entries)


def test_attach_flight_recorder_binds_ledger_and_spans(tmp_path):
    tel = EngineTelemetry(enabled=True)
    tel.step_ledger = StepLedger(depth=8)
    tel.step_ledger.push("decode", 4, 1, 7, 0, 1, 0.01, 0.0, 0.0, 0,
                         0.0, 0, False)
    tel.recorder.add("request", "tid-1", 0.0, 1.0, parent="")
    tel.recorder.seal("tid-1")
    fr = attach_flight_recorder(tel, str(tmp_path), 3, retain=4,
                                config={"x": 1},
                                stats_fn=lambda: {"n": 1})
    assert fr is not None and tel.flight is fr
    path = fr.capture("watchdog", min_interval_s=0.0)
    payload = json.loads(open(path).read())
    assert payload["replica"] == 3 and payload["config"] == {"x": 1}
    assert payload["steps"][0]["tokens"] == 7
    assert any(s.get("name") == "request" for s in payload["spans"])
    assert payload["stats"] == {"n": 1}


# ------------------------------------------- committed artifact


def test_committed_smoke_artifact_carries_attribution():
    """The committed replay smoke artifact embeds the step_attribution
    block — verdicts per step kind, rung occupancy, top sinks, and the
    MFU cross-check — so a regression that silently drops attribution
    from the bench pipeline fails tier-1."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    art_path = os.path.join(root, "benchmarks", "results",
                            "replay_smoke.json")
    art = json.loads(open(art_path).read())
    att = art["summary"]["step_attribution"]
    assert att["enabled"] is True
    assert att["records"] > 0
    assert att["verdicts"], "no step kinds attributed"
    for kind, verdict in att["verdicts"].items():
        assert kind in telemetry.STEP_KINDS
        assert verdict in ("compute-bound", "hbm-bound", "host-bound")
    assert att["rung_occupancy"], "no rung occupancy histogram"
    assert att["replica_verdicts"]


# ------------------------------------- live dp=2 subprocess fleet


def test_fleet_steps_and_blackbox_over_http(tmp_path):
    """ONE consolidated process-level acceptance run: real traffic over
    HTTP against a dp=2 subprocess fleet, per-replica bottleneck
    verdicts from GET /debug/steps with the ledger-replayed MFU agreeing
    with ``tpu_inf_mfu_estimate`` within 20%, then a kill -9'd worker
    whose surviving blackbox capture shows up at GET /debug/blackbox."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from tpu_inference.config import (EngineConfig, FrameworkConfig,
                                      ParallelConfig, ServerConfig,
                                      tiny_llama)
    from tpu_inference.server.http import InferenceServer

    bb = str(tmp_path / "blackbox")
    cfg = FrameworkConfig(
        model=tiny_llama(vocab_size=512),
        engine=EngineConfig(page_size=8, num_pages=64,
                            max_pages_per_seq=8, max_batch_size=2,
                            prefill_buckets=(16,), host_cache_pages=32),
        parallel=ParallelConfig(dp=2),
        server=ServerConfig(model_name="tiny-llama", tokenizer="byte",
                            warmup=False, fleet="subprocess",
                            enable_debug=True, worker_restart_max=10,
                            worker_restart_backoff_s=0.1,
                            drain_timeout_s=8.0, blackbox_dir=bb,
                            blackbox_retain=4))
    srv = InferenceServer(cfg)

    async def go(client):
        # Concurrent streams: with max_batch_size=2 per replica, six
        # in-flight requests force the router to use both workers.
        async def one(i):
            resp = await client.post("/api/generate", json={
                "model": "tiny-llama", "prompt": f"roofline probe {i}",
                "temperature": 0.0, "max_tokens": 24, "stream": True})
            assert resp.status == 200
            await resp.read()

        await asyncio.gather(*(one(i) for i in range(6)))

        resp = await client.get("/debug/steps")
        assert resp.status == 200
        snap = await resp.json()
        assert set(snap["replicas"]) == {"0", "1"}
        for rep in snap["replicas"].values():
            assert rep["enabled"]
            assert rep["records_window"] > 0, "a replica saw no traffic"
            assert rep["kinds"], "no step kinds attributed"
            for kind, agg in rep["kinds"].items():
                assert kind in telemetry.STEP_KINDS
                # The workers run on the CPU: times and counts are
                # attributed, but there is no chip to rate them against
                # — no shares, no verdict, no MFU (never a v5e's).
                assert agg["verdict"] == telemetry.NOT_MEASURED
                assert "compute_frac" not in agg and "hbm_frac" not in agg
                assert agg["device_s"] > 0 and agg["flops"] > 0
            assert rep["peaks"] == {"flops_per_s": None,
                                    "hbm_bytes_per_s": None}
            assert rep["mfu"] == {"gauge": None}
        fleet = snap["fleet"]
        assert fleet["enabled"] and fleet["replicas_merged"] == 2
        assert fleet["records_window"] > 0 and fleet["rung_occupancy"]
        assert {k["verdict"] for k in fleet["kinds"].values()} == {
            telemetry.NOT_MEASURED}
        assert fleet["mfu"] == {"gauge": None}

        # kill -9 one worker: its blackbox directory survives the kill
        # (periodic heartbeat at minimum) and the index lists it.
        victim = 0
        resp = await client.post("/debug/chaos",
                                 json={"replica": victim,
                                       "kill": "kill9"})
        assert resp.status == 200
        deadline = time.monotonic() + 30
        caps = []
        while time.monotonic() < deadline:
            idx = await (await client.get("/debug/blackbox")).json()
            assert idx["dir"] == bb
            caps = [e for e in idx["captures"]
                    if e["replica"] == victim and "error" not in e]
            if caps:
                break
            await asyncio.sleep(0.2)
        assert caps, "kill -9'd worker left no harvested capture"
        assert any(e.get("n_steps", 0) > 0 or e.get("has_config")
                   for e in caps), caps

        # The supervisor restarts the victim under the same label.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if all(h.state == "up" for h in srv.group.workers):
                break
            await asyncio.sleep(0.1)
        assert all(h.state == "up" for h in srv.group.workers)

    async def wrapper():
        app = srv.make_app()
        async with TestClient(TestServer(app)) as client:
            await go(client)

    asyncio.run(wrapper())


# ------------------------------------ dispatch identity + true times


def test_new_step_fields_are_appended_and_old_indexes_hold():
    """Positional readers (roofline_report's r[4], r[7], r[13], ...) and
    the benchmark's use of ts / kind / chunk_tokens / kv_read_tokens must
    not move: the PR-24 fields sit at the end."""
    old = ("ts", "kind", "rung", "slots", "tokens", "chunk_tokens", "steps",
           "device_s", "staging_s", "bubble_s", "kv_read_tokens",
           "kv_swap_bytes", "spec_accepted", "compile_event")
    assert STEP_FIELDS[:len(old)] == old
    assert STEP_FIELDS[len(old):] == ("seq", "t_enqueue", "t_done",
                                      "layer_passes")
    led = StepLedger(depth=8)
    # A caller that knows nothing of the new fields still pushes.
    led.push("decode", 4, 2, 7, 0, 8, 0.25, 0.01, 0.02, 99, 0.0, 0, False)
    r = led.records()[0]
    assert len(r) == len(STEP_FIELDS)
    assert (r[1], r[4], r[7], r[10], r[13]) == ("decode", 7, 0.25, 99, 0)
    assert r[14:] == (0, 0.0, 0.0, 0)


def test_settle_fills_t_done_and_true_device_s():
    led = StepLedger(depth=8)
    for seq in (1, 2, 3):
        led.push("prefill_chunk", 0, 1, 0, 512, 1, 0.004, 0.0, 0.0, 1000,
                 0.0, 0, False, seq=seq, t_enqueue=100.0 + seq)
    before = led.records()
    assert led.settle(2, 102.75)
    after = led.snapshot()
    assert after[1]["seq"] == 2 and after[1]["t_done"] == 102.75
    assert after[1]["device_s"] == pytest.approx(0.75)   # not the 4 ms
    assert after[1]["ts"] == before[1][0], "ts stays the push instant"
    assert after[1]["chunk_tokens"] == 512 and after[1]["kind"] == \
        "prefill_chunk"
    # Neighbours untouched; an unknown number settles nothing.
    assert after[0]["t_done"] == 0.0 and after[2]["device_s"] == 0.004
    assert not led.settle(99, 1.0)
    assert not NULL_LEDGER.settle(1, 1.0)


def _tiny_engine(**kw):
    from tpu_inference.config import EngineConfig, tiny_llama
    from tpu_inference.engine.engine import InferenceEngine

    base = dict(max_batch_size=4, num_pages=128, page_size=8,
                max_pages_per_seq=16, prefill_buckets=(16, 32),
                decode_steps_per_call=4, step_ledger_depth=256)
    base.update(kw)
    return InferenceEngine(tiny_llama(), EngineConfig(**base))


def test_engine_records_carry_monotone_seq_and_observed_times():
    """Every dispatch gets the next number; a prefill's t_done comes from
    a readback the engine performs anyway and is never before its
    enqueue; no record of a finished run is left unobserved."""
    from tpu_inference.engine.engine import Sequence

    engine = _tiny_engine(chunked_prefill_size=16)
    now = time.time()
    short = Sequence(request_id=1, prompt_tokens=list(range(3, 12)),
                     max_new_tokens=6)
    long = Sequence(request_id=2, prompt_tokens=list(range(3, 60)),
                    max_new_tokens=6)                  # 4 chunks of 16
    engine.prefill_many([short])
    engine.prefill_begin(long)
    while not engine.prefill_step(long):
        engine.decode_steps_pipelined()                # lanes stalled behind
    while engine.active_sequences():
        engine.decode_steps_pipelined()
    engine.drain_pipeline()
    recs = engine.telemetry.step_ledger.snapshot()
    seqs = [r["seq"] for r in recs]
    assert sorted(seqs) == list(range(1, len(recs) + 1)), seqs
    chunks = [r for r in recs if r["kind"] == "prefill_chunk"]
    assert len(chunks) == 1 + 4
    for r in recs:
        assert abs(r["t_enqueue"] - now) < 120, "unix, recorder's anchor"
        assert r["t_done"] >= r["t_enqueue"] > 0, r
    for r in chunks:
        assert r["device_s"] == pytest.approx(r["t_done"] - r["t_enqueue"],
                                              abs=1e-6)
    assert engine._unsettled == []
    clock = engine.telemetry.clock
    assert clock.observed_seq == clock.dispatched_seq == len(recs)
    # The stall histogram is fed from those same observations (chunks
    # that ran with a decode lane active), with no sync of its own.
    stall = engine.telemetry.decode_stall_during_prefill_s
    assert 1 <= stall.count <= 4 and stall.sum > 0


@pytest.mark.parametrize("max_steps", [None, 1])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_synchronous_round_is_one_record_at_any_depth(depth, max_steps):
    """decode_steps() is the depth-1 case of the one staged round,
    whatever depth the engine serves at: nothing is left in flight, each
    round pushes exactly one decode record, observed, whose device_s
    runs from its enqueue to its readback and whose steps are the cap;
    a cap of 1 runs the one-step program."""
    engine = _tiny_engine(decode_pipeline_depth=depth,
                          decode_steps_per_call=8)
    from tpu_inference.engine.engine import Sequence

    ran = {"one": 0, "multi": 0}
    assert engine._decode_one_jit is not engine._decode_multi_jit

    def spy(name):
        real = getattr(engine, f"_decode_{name}_jit")

        def call(*args):
            ran[name] += 1
            return real(*args)
        setattr(engine, f"_decode_{name}_jit", call)
    spy("one"), spy("multi")

    engine.prefill_many([
        Sequence(request_id=i, prompt_tokens=list(range(3, 9 + i)),
                 max_new_tokens=20) for i in range(3)])
    ledger = engine.telemetry.step_ledger
    rounds = 0
    while engine.active_sequences():
        before = len(ledger.snapshot())
        out = engine.decode_steps(max_steps)
        rounds += 1
        assert not engine.pipeline_pending
        assert out and all(len(t) <= (max_steps or 8) for t in out.values())
        assert len(ledger.snapshot()) == before + 1
    recs = [r for r in ledger.snapshot() if r["kind"] == "decode"]
    assert len(recs) == rounds
    for r in recs:
        assert r["t_done"] > r["t_enqueue"] > 0
        assert r["device_s"] == pytest.approx(r["t_done"] - r["t_enqueue"],
                                              abs=1e-3)
        assert r["steps"] == (max_steps or 8) and r["slots"] == 3
    assert sum(r["tokens"] for r in recs) == 3 * 19
    assert ran == ({"one": rounds, "multi": 0} if max_steps == 1
                   else {"one": 0, "multi": rounds})
    assert engine.telemetry.decode_sync_s.count == rounds
    assert engine.telemetry.decode_dispatches.value == rounds


def test_steps_report_interval_and_records():
    led = StepLedger(depth=32)
    for i in range(10):
        led.push("decode", 4, 2, 8, 0, 8, 0.1, 0.0, 0.0, 10, 0.0, 0, False,
                 seq=i + 1)
    recs = led.records()
    t = [r[0] for r in recs]
    model = _model()
    default = roofline_report(led, model)
    assert default["records_window"] == 10 and "records" not in default
    part = roofline_report(led, model, since=t[3], until=t[6], records=True)
    assert part["records_window"] == len(part["records"]) == \
        sum(1 for x in t if t[3] <= x <= t[6])
    assert part["kinds"]["decode"]["records"] == part["records_window"]
    assert set(part["records"][0]) == set(STEP_FIELDS)
    none = roofline_report(led, model, since=t[-1] + 10.0)
    assert none["records_window"] == 0 and none["kinds"] == {}
