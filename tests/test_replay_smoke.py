"""Tier-1 CPU lane for ``benchmarks/replay.py --smoke``.

The bench-side consumer of the metrics pipeline (HTTP /metrics scrape ->
phase_breakdown artifact) must not rot between chip windows, so this
exercises the whole path end-to-end on CPU: server boot + warmup, trace
replay through the vendored traffic generator, a real-HTTP Prometheus
scrape, and the committed artifact's phase_breakdown with its sum-check.
"""

import importlib.util
import json
import os
import sys

import pytest


def _load_bench(name):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        f"{name}_smoke_mod", os.path.join(root, "benchmarks", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return root, mod


def _load_replay():
    return _load_bench("replay")


def test_replay_smoke_commits_phase_breakdown(tmp_path, monkeypatch):
    root, replay = _load_replay()
    out = tmp_path / "replay_smoke.json"
    monkeypatch.chdir(root)                 # trace/data paths repo-relative
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--out", str(out)])
    summary = replay.main()

    # Every smoke request succeeded and produced tokens.
    assert summary["succeeded"] == summary["requests"] > 0
    assert summary["output_tokens"] > 0

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    pb = art["summary"]["phase_breakdown"]
    # The roofline-attribution phases all carry data + percentiles.
    for key in ("decode_dispatch_s", "dispatch_bubble_s", "queue_wait_s",
                "prefill_dispatch_s", "e2e_s"):
        assert pb[key]["count"] > 0, f"{key} never observed"
        assert pb[key]["p50"] is not None
        assert pb[key]["p95"] is not None
        assert pb[key]["p99"] is not None
        assert pb[key]["p50"] <= pb[key]["p99"]
    # Sum-check: queue + prefill + decode == e2e (identical server-side
    # timestamps; rounding only).
    sc = pb["sum_check"]
    assert sc["ratio"] is not None
    assert abs(sc["ratio"] - 1.0) < 0.01
    # The Prometheus scrape went over real HTTP and parsed.
    prom = art["summary"]["prometheus_scrape"]
    assert prom["content_type"].startswith("text/plain; version=0.0.4")
    assert prom["families"] >= 10
    assert prom["samples"] > 50
    # The step-attribution block rode along (live /debug/steps path;
    # the committed artifact's copy is graded in test_step_ledger.py).
    att = art["summary"]["step_attribution"]
    assert att["enabled"] and att["records"] > 0
    # ...attributed but not rated: this run is on the CPU, so every
    # verdict reads "not measured" and there is no MFU figure.
    assert set(att["verdicts"].values()) == {"not measured"}
    assert att["mfu"]["gauge"] is None


def test_replay_smoke_compare_admission(tmp_path, monkeypatch):
    """Tier-1 preemption smoke (CPU): the reserve-vs-optimistic
    comparison lane boots both servers against a burst of the smoke
    trace with a pool tight enough that worst-case reservation binds.
    Optimistic admission must exercise watermark preemption +
    recompute-resume through the full HTTP path, finish every request,
    and land the win (higher occupancy, or matched throughput at no
    worse shed rate) in the committed artifact."""
    root, replay = _load_replay()
    out = tmp_path / "replay_admission.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-admission",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    for mode in ("reserve", "optimistic"):
        s = art[mode]
        # No deadlocks, no errors: every request in both arms finished.
        assert s["succeeded"] == s["requests"] > 0, (mode, s)
        assert s["admission"]["mode"] == mode
    # The optimistic arm actually hit the preemption path (otherwise
    # this smoke proves nothing about it).
    assert cmp["preemptions"] >= 1
    assert cmp["recompute_resumes"] == cmp["preemptions"]
    assert art["reserve"]["admission"]["preemptions"] == 0
    assert cmp["optimistic_wins"], cmp


def test_replay_smoke_compare_hybrid(tmp_path, monkeypatch):
    """Tier-1 hybrid-stepping smoke (CPU): the serial-vs-hybrid lane
    replays a pinned mix — one 8-chunk long prompt plus three shorts
    that decode through its prefill — through the full HTTP path, twice.
    The committed artifact must show the serial arm stalling decode
    lanes behind chunk dispatches and the hybrid arm fusing every chunk
    (structurally zero stall samples, so its p95 is <= serial's), with
    identical greedy token counts across arms."""
    root, replay = _load_replay()
    out = tmp_path / "replay_hybrid.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-hybrid",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    for mode in ("serial", "hybrid"):
        s = art[mode]
        assert s["succeeded"] == s["requests"] > 0, (mode, s)
        # Artifact schema: the stall histogram and hybrid counters are
        # present in both arms' summaries.
        assert "decode_stall_during_prefill_s" in s["phase_breakdown"]
        assert set(s["hybrid"]) >= {"enabled", "hybrid_steps",
                                    "decode_stall_count",
                                    "decode_stall_p95_s"}
    assert art["serial"]["hybrid"]["enabled"] is False
    assert art["hybrid"]["hybrid"]["enabled"] is True
    # The serial arm demonstrably stalled decode lanes behind chunks...
    assert cmp["decode_stall_count_serial"] >= 1
    assert cmp["decode_stall_p95_serial_s"] > 0
    # ...and the hybrid arm fused them instead.
    assert cmp["hybrid_steps"] >= 1
    assert cmp["decode_stall_count_hybrid"] == 0
    assert (cmp["decode_stall_p95_hybrid_s"]
            <= cmp["decode_stall_p95_serial_s"])
    # Greedy + identical prompts: same token counts in both arms.
    assert cmp["output_tokens_hybrid"] == cmp["output_tokens_serial"]
    assert cmp["hybrid_wins"], cmp


def test_replay_smoke_compare_ladder(tmp_path, monkeypatch):
    """Tier-1 batch-ladder smoke (CPU): the fixed-bs8 vs compiled-
    ladder comparison lane serves the pinned greedy burst through the
    full HTTP path three times (bs8 / ladder / ladder with staging
    reuse off). Live assertions are the DETERMINISTIC claims — byte-
    identical outputs across every batch shape, the ladder actually
    climbing to its top rung and switching graphs, and strictly higher
    aggregate tok/s than the fixed bs=8 graph; the latency/throughput
    magnitudes are graded on the committed artifact (the tiering/
    routing lanes' stance: wall-clock on a loaded CI box swings)."""
    root, replay = _load_replay()
    out = tmp_path / "replay_ladder.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-ladder",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    for arm in ("bs8", "ladder", "ladder_rebuild"):
        s = art[arm]
        assert s["requests"] > 0 and s["output_tokens"] > 0, (arm, s)
    # The ladder demonstrably climbed to the top rung, switching graphs.
    assert art["ladder"]["decode_ladder"] == [8, 16, 32]
    assert cmp["rung_peak"] == 32
    assert cmp["rung_switches"] >= 1
    assert art["bs8"]["rung_peak"] == 8
    # Byte-identity across batch shapes: graph width is never a
    # behavior change (greedy, identical weights/seed).
    assert cmp["outputs_identical"], cmp
    # The concurrency win, live: strictly higher aggregate tok/s.
    assert cmp["tokens_per_s_ladder"] > cmp["tokens_per_s_bs8"], cmp
    assert cmp["ladder_wins"], cmp
    # The staging micro-measure is deterministic enough to grade live:
    # reuse must beat rebuild-per-dispatch.
    micro = cmp["stage_us_per_dispatch"]
    assert micro["reuse_us"] < micro["rebuild_us"], micro

    # The committed artifact carries the full acceptance claim: >=2x
    # aggregate tok/s at the bs=32 rung vs the bs=8 baseline on the CPU
    # lane, per-stream latency within 1.5x, byte-identity, and the
    # host-bubble drop the staging reuse buys.
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "replay_ladder.json")).read())
    c = committed["comparison"]
    assert c["ladder_wins"] and c["outputs_identical"]
    assert c["tok_s_ratio"] >= 2.0
    assert c["per_stream_latency_ratio"] <= 1.5
    assert c["rung_peak"] == 32
    assert c["bubble_p95_improved"]
    assert (c["stage_us_per_dispatch"]["reuse_us"]
            < c["stage_us_per_dispatch"]["rebuild_us"])


def test_replay_smoke_compare_spec(tmp_path, monkeypatch):
    """Tier-1 draft-free-speculation smoke (CPU): the plain vs ngram
    comparison lane serves a pinned echo-heavy greedy multi-turn mix
    (where self-drafting wins) and an adversarial no-echo sampled mix
    (where adaptive γ must throttle) through the full HTTP path, four
    boots total. Live assertions are the DETERMINISTIC claims —
    byte-identical greedy outputs across arms (speculation is never a
    behavior change), real accepted speculation on the echo mix, and
    the throttle engaging on the adversarial mix; the >=1.3x /
    >=0.95x magnitudes are graded on the committed artifact (the
    ladder/tiering lanes' stance: wall-clock on a loaded CI box
    swings)."""
    root, replay = _load_replay()
    out = tmp_path / "replay_spec.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-spec",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    for arm in ("echo_plain", "echo_ngram", "adversarial_plain",
                "adversarial_ngram"):
        s = art[arm]
        assert s["requests"] > 0 and s["output_tokens"] > 0, (arm, s)
    # The plain arms really ran plain and the ngram arms really
    # speculated.
    assert art["echo_plain"]["speculative"] is None
    espec = art["echo_ngram"]["speculative"]
    assert espec["mode"] == "ngram"
    # Byte-identity on the greedy echo mix: speculation is a scheduling
    # decision, never a behavior change.
    assert cmp["outputs_identical"], cmp
    # Real speculation happened and mostly verified (greedy + pinned
    # weights/seed make the acceptance rate deterministic).
    assert cmp["spec_drafted"] > 0
    assert cmp["acceptance_rate"] > 0.3, cmp
    # The adversarial mix engaged the never-lose machinery: lanes
    # throttled to gamma=0 and rounds degraded to plain fused decode.
    assert (cmp["adversarial_throttles"] or 0) >= 1
    assert (cmp["adversarial_fallback_rounds"] or 0) >= 1
    assert (cmp["adversarial_acceptance_rate"] or 0) < 0.3
    assert cmp["spec_wins"], cmp

    # The committed artifact carries the full acceptance claim: >=1.3x
    # per-stream decode tok/s on the echo mix with byte-identical
    # outputs, and the adaptive-gamma arm >=0.95x plain on the
    # adversarial mix (spec never loses).
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "replay_spec.json")).read())
    c = committed["comparison"]
    assert c["spec_wins"] and c["outputs_identical"]
    assert c["per_stream_ratio"] >= 1.3
    assert c["acceptance_rate"] > 0.5
    assert c["adversarial_ratio"] >= 0.95
    assert c["spec_never_loses"]


@pytest.mark.slow   # heaviest chaos lane (~90s); fleet kill/drain behavior
                    # stays tier-1 in test_fleet.py, the committed artifact
                    # in benchmarks/results/replay_fleet.json
def test_replay_smoke_compare_fleet(tmp_path, monkeypatch):
    """Tier-1 process-fleet smoke (CPU, dp=2): the in-process vs
    subprocess comparison lane serves a pinned greedy burst through the
    full HTTP path on both fleet backends, then with a worker
    SIGKILLed mid-decode, then the pinned drain scenario twice
    (migration vs resubmission) — five boots, eight real worker
    processes total. Live assertions are the DETERMINISTIC claims:
    byte-identical outputs across every arm (the fleet backend — and a
    kill -9 — is a placement/supervision decision, never a behavior
    change), the killed worker's in-flight requests failing over and
    completing with the worker restarted, and drain-time migration
    recording swap-in-resumes with strictly fewer recomputed tokens
    than plain resubmission. Throughput magnitudes are reported, not
    graded (loaded-CI-box stance)."""
    root, replay = _load_replay()
    out = tmp_path / "replay_fleet.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-fleet",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    for arm in ("in_process", "subprocess", "subprocess_kill",
                "drain_migrate", "drain_resubmit"):
        s = art[arm]
        assert s["requests"] > 0 and s["output_tokens"] > 0, (arm, s)
    assert art["in_process"]["fleet"] == "in-process"
    assert art["subprocess"]["fleet"] == "subprocess"
    # Byte-identity across backends and chaos arms.
    assert cmp["outputs_identical"], cmp
    # The kill arm really killed a worker mid-decode, its requests
    # failed over and completed, and the supervisor restarted it.
    assert cmp["kill_chaos_fired"]
    assert cmp["failover_count"] >= 1
    assert cmp["kill_worker_restarts"] >= 1
    assert cmp["failover_wins"], cmp
    # The drain arms really drained, the migration arm moved KV pages
    # and swap-in-resumed, and it recomputed strictly fewer tokens
    # than the resubmission arm.
    assert cmp["migrations"] >= 1
    assert cmp["migrated_pages"] >= 1 and cmp["migrated_bytes"] > 0
    assert cmp["swap_in_resumes"] >= 1
    assert (cmp["recomputed_tokens_migrate"]
            < cmp["recomputed_tokens_resubmit"]), cmp
    assert cmp["migration_wins"], cmp

    # The committed artifact carries the same acceptance claims.
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "replay_fleet.json")).read())
    c = committed["comparison"]
    assert c["outputs_identical"] and c["failover_wins"]
    assert c["migration_wins"]
    assert c["swap_in_resumes"] >= 1
    assert (c["recomputed_tokens_migrate"]
            < c["recomputed_tokens_resubmit"])


def test_replay_smoke_compare_chaos_rpc(tmp_path, monkeypatch):
    """Tier-1 Byzantine-transport smoke (CPU, dp=2): the chaos-rpc
    lane serves the pinned greedy burst through a clean subprocess
    fleet and again under seeded frame-level fault injection — byte
    corruption + delays on every router<->worker frame in both
    directions, plus one wedged connection as the burst opens. Live
    assertions are the DETERMINISTIC claims: byte-identical outputs
    (zero silent corruptions — every corrupt frame was CRC-rejected
    and the connection recycled+resynced), frame errors and RPC
    timeouts actually counted, reconnects with ZERO worker process
    restarts (transport faults are repaired at the connection), and
    p95 inflation bounded. Throughput magnitudes are reported, not
    graded (loaded-CI-box stance)."""
    root, replay = _load_replay()
    out = tmp_path / "replay_chaos_rpc.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-chaos-rpc",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    for arm in ("clean", "chaos_rpc"):
        s = art[arm]
        assert s["requests"] > 0 and s["output_tokens"] > 0, (arm, s)
    # The clean arm saw no injected faults.
    assert art["clean"]["frame_errors"] == 0
    assert art["clean"]["worker_reconnects"] == 0
    # The chaos arm really injected, detected, and recovered.
    assert cmp["chaos_fired"]
    assert cmp["outputs_identical"], cmp
    assert cmp["silent_corruptions"] == 0
    assert cmp["frame_errors"] >= 1, cmp
    assert cmp["rpc_timeouts"] >= 1, cmp
    assert cmp["worker_reconnects"] >= 1, cmp
    # Connection-level failover, never a process restart.
    assert cmp["worker_restarts_chaos"] == 0, cmp
    assert cmp["p95_inflation_bounded"], cmp
    assert cmp["chaos_wins"], cmp

    # The committed artifact carries the same acceptance claims.
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "replay_chaos_rpc.json")).read())
    c = committed["comparison"]
    assert c["chaos_wins"] and c["outputs_identical"]
    assert c["silent_corruptions"] == 0
    assert c["frame_errors"] >= 1 and c["rpc_timeouts"] >= 1
    assert c["worker_reconnects"] >= 1
    assert c["worker_restarts_chaos"] == 0
    assert c["p95_inflation_bounded"]


def test_replay_smoke_compare_elastic(tmp_path, monkeypatch):
    """Tier-1 elastic-fleet smoke (CPU): the fixed vs elastic lane
    replays the pinned mini-diurnal (>= 20x offered-load swing, mixed
    X-Priority classes) through one fixed subprocess worker and
    through the autoscaled fleet — which must scale up on the
    sustained SLO breach, preempt the batch lane for interactives
    instead of shedding them, survive a rolling upgrade fired mid-
    burst over HTTP with zero failed requests, and scale back down in
    the quiet tail. Live assertions are the DETERMINISTIC claims:
    interactive TTFT p95 holds the SLO in the elastic arm, batch
    preemptions > 0 with interactive shed == 0, scale-up AND
    scale-down events visible in /metrics and /debug/trace, the
    rollout replacing every worker with none failed, and byte-
    identical greedy outputs across arms; throughput magnitudes are
    reported, not graded (loaded-CI-box stance)."""
    root, replay = _load_replay()
    out = tmp_path / "replay_elastic.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-elastic",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    for arm in ("fixed", "elastic"):
        s = art[arm]
        assert s["requests"] > 0 and s["output_tokens"] > 0, (arm, s)
    assert art["fixed"]["elastic"] is False
    assert art["elastic"]["elastic"] is True
    # The diurnal really swung >= 20x trough-to-peak.
    assert cmp["load_swing"] >= 20.0
    # Interactive held the SLO under the peak; batch absorbed the
    # slack (real preemptions, nothing interactive shed or failed).
    assert cmp["interactive_slo_held_elastic"], cmp
    assert cmp["batch_preemptions_elastic"] >= 1
    assert cmp["interactive_shed_elastic"] == 0
    assert cmp["elastic_completed_all"], cmp
    # The fleet scaled up on the breach AND back down in the lull,
    # with events in /metrics and /debug/trace.
    assert cmp["scale_ups"] >= 1 and cmp["scale_downs"] >= 1
    assert cmp["scale_events_in_metrics"]
    assert cmp["scale_events_in_trace"]
    # The mid-burst rolling upgrade replaced every worker, failed
    # none, and left a trace span.
    assert cmp["rollout_replaced"] >= 1
    assert cmp["rollout_failed"] == 0
    assert cmp["rollout_in_trace"]
    # Byte-identity across arms on every commonly-completed request:
    # elasticity is a capacity decision, never a behavior change.
    assert cmp["common_requests"] >= 1
    assert cmp["outputs_identical_common"], cmp
    assert cmp["elastic_wins"], cmp

    # The committed artifact carries the same acceptance claims.
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "replay_elastic.json")).read())
    c = committed["comparison"]
    assert c["elastic_wins"] and c["outputs_identical_common"]
    assert c["load_swing"] >= 20.0
    assert c["interactive_slo_held_elastic"]
    assert c["batch_preemptions_elastic"] >= 1
    assert c["interactive_shed_elastic"] == 0
    assert c["scale_ups"] >= 1 and c["scale_downs"] >= 1
    assert c["rollout_replaced"] >= 1 and c["rollout_failed"] == 0


def test_replay_smoke_compare_pd(tmp_path, monkeypatch):
    """Tier-1 P/D-disaggregation smoke (CPU, dp=2, three subprocess
    topologies): the pinned long-prompt burst runs unloaded then
    loaded through mixed, hybrid, and 1-prefill+1-decode arms. Live
    assertions are the DETERMINISTIC claims: byte-identical outputs
    across every arm AND phase (the topology — and a live KV handoff —
    is a placement decision, never a behavior change), handoffs > 0
    with every one adopted cleanly (zero handoff recomputes, zero
    recomputed tokens), and a genuinely 10x-plus prefill burst. The
    TPOT-isolation magnitudes (pd flat within 10%, hybrid degrading)
    are graded on the committed artifact, not re-timed on a loaded CI
    box (the routing/fleet artifacts' stance)."""
    root, replay = _load_replay()
    out = tmp_path / "replay_pd.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-pd",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    for arm in ("mixed", "hybrid", "pd"):
        s = art[arm]
        assert s["output_tokens"] > 0, (arm, s)
        assert s["outputs_phases_identical"], arm
        assert s["fleet_status"] == "ok", (arm, s)
    assert art["pd"]["roles"] == ["prefill", "decode"]
    assert art["mixed"]["roles"] == ["mixed", "mixed"]
    assert art["hybrid"]["hybrid_prefill"] is True
    # Byte-identity across the three topologies and both phases.
    assert cmp["outputs_identical"], cmp
    # The pd arm really disaggregated: every prompt prefilled on the
    # prefill worker and moved to the decode worker as a live handoff,
    # every handoff adopted cleanly — nothing recomputed.
    assert cmp["pd_handoffs"] > 0
    assert cmp["pd_adoptions"] > 0
    assert cmp["pd_handoff_recomputes"] == 0
    assert cmp["pd_recomputed_tokens"] == 0
    assert cmp["pd_clean_handoffs"], cmp
    # The loaded phase offered >= 10x the unloaded phase's prefill.
    assert cmp["prefill_load_ratio"] >= 10.0

    # Distributed tracing (README "Observability"): the lane committed
    # a Chrome trace-event artifact next to --out, and THIS run's pd
    # arm produced >= 1 handed-off request whose spans appear under one
    # trace id across router + prefill worker + decode worker pids,
    # export/adopt adjacent and non-overlapping with prefill/decode.
    trace_path = tmp_path / "replay_pd_trace.json"
    assert trace_path.exists()
    chrome = json.loads(trace_path.read_text())
    assert isinstance(chrome["traceEvents"], list) and chrome["traceEvents"]
    assert all({"name", "ph", "pid"} <= set(e)
               for e in chrome["traceEvents"])
    grading = chrome["otherData"]
    assert grading["handoff_traces_3pid"] >= 1
    assert grading["handoff_traces_clean"] >= 1
    assert grading["adjacency_ok"], grading
    assert cmp["trace"]["handoff_traces_3pid"] >= 1
    # Rolling SLO gauges tracked the replay: real targets were set, the
    # windowed p95 exists, and the gauge-vs-client ratio is recorded
    # (the within-10% magnitude is graded on the committed artifact —
    # a loaded CI box skews client-side timing).
    slo = art["pd"]["slo"]
    assert slo["ttft_target_s"] == 2.0 and slo["tpot_target_s"] == 0.2
    assert slo["ttft_p95_s"] is not None and slo["ttft_p95_s"] > 0
    assert art["pd"]["client_ttft_p95_s"] > 0
    assert art["pd"]["slo_ttft_p95_tracking_ratio"] is not None

    # The committed artifact carries the acceptance magnitudes: decode
    # TPOT p95 flat (within 10% of the arm's own unloaded baseline)
    # under the burst on the pd split, degrading on hybrid.
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "replay_pd.json")).read())
    c = committed["comparison"]
    assert c["pd_wins"] and c["outputs_identical"]
    assert c["pd_clean_handoffs"] and c["pd_handoffs"] > 0
    assert c["prefill_load_ratio"] >= 10.0
    assert c["decode_tpot_p95_ratio"]["pd"] <= 1.10
    assert c["decode_tpot_p95_ratio"]["hybrid"] >= 1.25
    assert (c["decode_tpot_p95_ratio"]["hybrid"]
            > c["decode_tpot_p95_ratio"]["pd"])


def test_committed_pd_trace_artifact():
    """The committed Chrome-trace artifact
    (benchmarks/results/replay_pd_trace.json, from the --compare-pd
    lane) is valid trace-event JSON carrying the acceptance claims: a
    handed-off request's spans under ONE trace id across three pids
    (router=0, prefill worker, decode worker) with export/adopt
    adjacent and non-overlapping with prefill/decode, and the rolling
    SLO TTFT p95 gauge tracking the replay-measured p95 within 10%."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    chrome = json.loads(open(os.path.join(
        root, "benchmarks", "results", "replay_pd_trace.json")).read())
    evs = chrome["traceEvents"]
    assert isinstance(evs, list) and len(evs) > 10
    x = [e for e in evs if e.get("ph") == "X"]
    assert all({"name", "ts", "dur", "pid", "tid", "args"} <= set(e)
               for e in x)
    # One handed-off request spanning three pids, verified from the
    # raw events (not just the recorded grading).
    by_trace = {}
    for e in x:
        tid = e["args"].get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(e)
    three_pid = [
        tid for tid, es in by_trace.items()
        if len({e["pid"] for e in es}) >= 3
        and {"handoff_export", "handoff_adopt", "prefill",
             "decode"} <= {e["name"] for e in es}]
    assert three_pid, "no handed-off request spans three pids"
    assert 0 in {e["pid"] for e in by_trace[three_pid[0]]}  # the router
    g = chrome["otherData"]
    assert g["handoff_traces_3pid"] >= 1 and g["adjacency_ok"]
    # SLO tracking: gauge p95 within 10% of the replay-measured p95.
    assert g["slo_tracks_within_10pct"], g
    assert abs(g["slo_ttft_p95_tracking_ratio"] - 1.0) <= 0.10
    assert g["slo"]["ttft_breaches"] >= 1      # targets actually bound


def test_replay_smoke_compare_tiering(tmp_path, monkeypatch):
    """Tier-1 tiered-KV-cache smoke (CPU, tiny model): the host-tier
    off-vs-on comparison lane replays the pinned multi-turn mix with the
    HBM pool sized well below the conversations' KV working set, twice.
    The tiered arm must serve STRICTLY more cached tokens (evictions
    demote instead of destroy; returning turns swap back in) with real
    demote/restore traffic, and greedy outputs must be byte-identical
    across arms — tiering is a memory-placement decision, never a
    behavior change. The repo-committed artifact must carry the full
    win (cached tokens AND returning-turn TTFT p95)."""
    root, multiturn = _load_bench("multiturn")
    out = tmp_path / "multiturn_tiering.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["multiturn.py", "--smoke", "--compare-tiering",
                         "--out", str(out)])
    cmp = multiturn.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    for mode in ("hbm_only", "tiered"):
        s = art[mode]
        assert s["requests"] > 0 and s["output_tokens"] > 0, (mode, s)
    # The pool was genuinely oversubscribed — the comparison measured
    # churn, not an idle cache.
    assert cmp["working_set_over_pool"] > 1.5
    # The HBM-only arm demonstrably destroyed KV on eviction...
    assert art["hbm_only"]["prefix_cache"].get("offloaded_pages", 0) == 0
    # ...while the tiered arm demoted and swapped back in.
    assert cmp["offloaded_pages"] > 0
    assert cmp["restored_pages"] > 0
    assert cmp["cached_tokens_tiered"] > cmp["cached_tokens_hbm_only"]
    # Byte-identity across arms (greedy, identical weights/seed).
    assert cmp["outputs_identical"], cmp
    assert cmp["tiering_wins"], cmp

    # The committed artifact carries the full acceptance claim,
    # including the returning-turn latency win (graded on the artifact,
    # not re-timed on a loaded CI box — the routing artifact's stance).
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "multiturn_tiering.json")).read())
    c = committed["comparison"]
    assert c["tiering_wins"] and c["outputs_identical"]
    assert c["ttft_returning_p95_improved"]
    assert c["cached_tokens_tiered"] > c["cached_tokens_hbm_only"]
    assert (c["ttft_returning_p95_tiered_s"]
            < c["ttft_returning_p95_hbm_only_s"])
    assert c["working_set_over_pool"] >= 3.0


def test_replay_smoke_compare_routing(tmp_path, monkeypatch):
    """Tier-1 cache-aware-routing smoke (CPU, dp=2, tiny model): the
    least-loaded vs prefix-affinity comparison lane runs the pinned
    multi-turn mix through the full dp=2 HTTP path, twice. The affinity
    arm must route strictly more cached prefix pages (the deterministic
    claim), with byte-identical greedy outputs across both routing
    modes — routing is a placement decision, never a behavior change.
    The repo-committed artifact must carry the full win (hit pages AND
    TTFT p95)."""
    root, multiturn = _load_bench("multiturn")
    out = tmp_path / "multiturn_routing.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["multiturn.py", "--smoke", "--compare-routing",
                         "--out", str(out)])
    cmp = multiturn.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    assert cmp["dp"] == 2
    for mode in ("least_loaded", "prefix_affinity"):
        s = art[mode]
        assert s["requests"] > 0 and s["output_tokens"] > 0, (mode, s)
        assert s["routing"]["mode"] == mode and s["routing"]["dp"] == 2
    # The affinity arm demonstrably routed conversations back to their
    # warm replica (peeked pages + server-side cache reuse both higher).
    assert cmp["route_warm_dispatches_prefix_affinity"] >= 1
    assert (cmp["route_hit_pages_prefix_affinity"]
            > cmp["route_hit_pages_least_loaded"])
    assert (cmp["cached_prompt_pages_prefix_affinity"]
            > cmp["cached_prompt_pages_least_loaded"])
    # Byte-identity across routing modes (greedy, identical replicas).
    assert cmp["outputs_identical"], cmp
    assert cmp["affinity_wins"], cmp

    # The committed artifact carries the full acceptance claim,
    # including the latency win (graded on the artifact, not re-timed
    # on a loaded CI box — replay's tok_s_within_5pct stance).
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "multiturn_routing.json")).read())
    c = committed["comparison"]
    assert c["affinity_wins"] and c["outputs_identical"]
    assert c["ttft_p95_improved"]
    assert (c["cached_prompt_pages_prefix_affinity"]
            > c["cached_prompt_pages_least_loaded"])
    assert (c["ttft_p95_prefix_affinity_s"]
            < c["ttft_p95_least_loaded_s"])


def test_replay_smoke_compare_fabric(tmp_path, monkeypatch):
    """Tier-1 fleet-KV-fabric smoke (CPU, dp=2, three subprocess
    fleets): the fabric lane replays the shared-system-prompt multi-
    user mix with the router-side fabric pool off, on, and on with a
    mid-run scale-up whose new worker boots fabric-warm. Live
    assertions are the DETERMINISTIC claims: byte-identical greedy
    outputs across all three arms (the fabric is a placement/transport
    decision, never a behavior change), the shared prefix prefilled
    ONCE fleet-wide in the fabric arms (replica B's first turn is
    fabric-warm with zero recomputed prefix tokens, adopting >=
    prefix-size pooled pages), the warmboot worker entering service
    with pooled pages already resident and serving its first request
    with fabric hits > 0, and zero integrity rejections. The TTFT
    ratio is graded on the committed artifact, not re-timed on a
    loaded CI box (replay's tok_s_within_5pct stance)."""
    root, replay = _load_replay()
    out = tmp_path / "replay_fabric.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-fabric",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    for arm in ("fabric_off", "fabric_on", "fabric_warmboot"):
        s = art[arm]
        assert s["requests"] > 0, (arm, s)
        assert s["kv_integrity_rejections"] == 0, (arm, s)
    assert art["fabric_off"]["fabric"]["capacity_pages"] == 0
    assert art["fabric_on"]["fabric"]["capacity_pages"] > 0
    # Byte-identity across all three arms.
    assert cmp["outputs_identical"], cmp
    # The shared prefix was prefilled ONCE fleet-wide: the fabric arm
    # re-prefilled zero prefix tokens while the off arm re-prefilled
    # the whole prefix once per returning user, and the cross-replica
    # first turn adopted the full pooled prefix.
    assert cmp["prefix_prefilled_once"], cmp
    assert cmp["prefix_recomputed_tokens_on"] == 0
    assert (cmp["prefix_recomputed_tokens_off"]
            >= cmp["prefix_tokens"])
    assert cmp["cross_replica_turns_on"] >= 1
    assert (cmp["cross_fabric_hit_pages_on"]
            * art["config"]["page_size"] >= cmp["prefix_tokens"])
    # The scaled-up worker booted fabric-warm and served its first
    # request from pooled pages, recomputing nothing.
    assert cmp["warmboot_wins"], cmp
    assert cmp["warmboot_host_pages"] >= 1
    assert cmp["warmboot_first_hit_pages"] >= 1
    assert cmp["fabric_wins"], cmp

    # The committed artifact carries the same claims PLUS the latency
    # win: returning-user TTFT p95 at least 1.3x better fabric-on.
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "replay_fabric.json")).read())
    c = committed["comparison"]
    assert c["fabric_wins"] and c["outputs_identical"]
    assert c["prefix_prefilled_once"] and c["warmboot_wins"]
    assert c["prefix_recomputed_tokens_on"] == 0
    assert c["returning_ttft_ratio"] >= 1.3
    assert c["fabric_ttft_wins"]


def test_replay_smoke_compare_kv_plane(tmp_path, monkeypatch):
    """Tier-1 zero-copy KV data plane smoke (CPU, 1 prefill + 1 decode
    subprocess fleet, both planes): the kv-plane lane replays the same
    handoff-heavy burst with KV payloads relayed through router frames
    vs handed worker-to-worker through the shared-memory page arena.
    Live assertions are the DETERMINISTIC claims (README "KV data
    plane"): byte-identical greedy outputs across the planes AND
    through each arm's kill -9 wave (the plane moves the same bytes),
    the shm arm relaying ZERO KV payload bytes through router frames
    on every verb while the relay arm moved every handoff through the
    router twice plus every fabric publish, the mid-handoff kill -9
    reclaiming the dead incarnation's slabs via the region epoch bump
    with every caught-out request recompute-resumed, and zero
    integrity rejections anywhere. The handoff-wall latency ratio is
    graded on the committed artifact, not re-timed on a loaded CI box
    (replay's tok_s_within_5pct stance)."""
    root, replay = _load_replay()
    out = tmp_path / "replay_kv_plane.json"
    monkeypatch.chdir(root)
    monkeypatch.setattr(sys, "argv",
                        ["replay.py", "--smoke", "--compare-kv-plane",
                         "--out", str(out)])
    cmp = replay.main()

    art = json.loads(out.read_text())
    assert art["config"]["smoke"] is True
    for arm in ("relay", "shm"):
        s = art[arm]
        assert s["requests"] > 0, (arm, s)
        assert s["kv_integrity_rejections"] == 0, (arm, s)
        # Every measured request handed off prefill->decode and the
        # kill wave ran to completion in both arms.
        assert s["pd_handoffs_measured"] > 0, (arm, s)
        assert s["kill_wave_requests"] == art["config"]["kvp_users"]
        assert s["worker_restarts"] >= 1, (arm, s)
    # Byte-identity across planes, including the kill waves.
    assert cmp["outputs_identical"], cmp
    # The zero-copy claim: no KV payload byte traversed a router frame
    # in the shm arm's measured phase, on ANY verb — while the relay
    # arm's books show the handoff event in, the dispatch out, and the
    # fabric publishes.
    assert cmp["shm_zero_copy"], cmp
    assert sum(cmp["rpc_blob_bytes_measured_shm"].values()) == 0
    assert cmp["rpc_blob_bytes_measured_relay"]["handoff"] > 0
    assert cmp["rpc_blob_bytes_measured_relay"]["submit"] > 0
    assert cmp["rpc_blob_bytes_measured_relay"]["fabric_put"] > 0
    # Kill -9 mid-handoff: slabs reclaimed (epoch bump), worker
    # respawned, nothing lost.
    assert cmp["kill_recovered"], cmp
    assert cmp["shm_reclaims"] >= 1
    assert cmp["kv_plane_wins"], cmp

    # The committed artifact carries the same claims PLUS the latency
    # win: handoff+adopt wall p95 at least 1.5x better on the shm
    # plane (export-span END on the prefill worker — serialized
    # payload in hand — to adopt-span end on the decode worker,
    # sequential measured series; the export itself is identical
    # prefill-side compute on either plane).
    committed = json.loads(open(os.path.join(
        root, "benchmarks", "results", "replay_kv_plane.json")).read())
    c = committed["comparison"]
    assert c["kv_plane_wins"] and c["outputs_identical"]
    assert c["shm_zero_copy"] and c["kill_recovered"]
    assert sum(c["rpc_blob_bytes_measured_shm"].values()) == 0
    assert c["shm_reclaims"] >= 1
    assert c["handoff_p95_ratio"] >= 1.5
    assert c["shm_handoff_wins"]
