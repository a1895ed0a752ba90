"""Unit tests for tpu_inference/telemetry.py: metric primitives,
percentile estimation, scrape diffing/merging, Prometheus exposition
(via the independent parser in tests/_prom.py), structured logging, and
the boot-time int4 degraded-mode gate."""

import json
import math
import os
import threading
import time

import pytest

import _prom
from tpu_inference import telemetry
from tpu_inference.telemetry import (Counter, EngineTelemetry, Gauge,
                                     Histogram, Registry, diff_phase,
                                     merge_phases, render_prometheus)


def test_histogram_buckets_and_percentiles():
    h = Histogram("t_seconds", "test", buckets=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.005, 0.005, 0.05, 5.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(5.0605)
    cum = h.cumulative()
    assert cum == [1, 3, 4, 4, 5]          # monotone, last = +Inf total
    # p50 lands in the (0.001, 0.01] bucket; interpolation stays inside.
    p50 = h.percentile(0.5)
    assert 0.001 <= p50 <= 0.01
    # An exact bucket-boundary observation counts into that bucket
    # (le is an inclusive upper bound).
    h2 = Histogram("t2", buckets=(1.0, 2.0))
    h2.observe(1.0)
    assert h2.cumulative() == [1, 1, 1]


def test_percentile_empty_histogram():
    h = Histogram("t_seconds", buckets=(0.1, 1.0))
    assert h.percentile(0.5) is None
    snap = h.phase_snapshot()
    assert snap["count"] == 0 and snap["p99"] is None


def test_diff_phase_isolates_window():
    h = Histogram("t", buckets=(0.1, 1.0))
    h.observe(0.05)
    before = h.phase_snapshot()
    h.observe(0.5)
    h.observe(0.5)
    after = h.phase_snapshot()
    d = diff_phase(after, before)
    assert d["count"] == 2
    assert d["sum"] == pytest.approx(1.0)
    assert 0.1 <= d["p50"] <= 1.0          # only the window's samples
    # No baseline -> after unchanged.
    assert diff_phase(after, None)["count"] == 3


def test_merge_phases_across_replicas():
    a, b = (Histogram("t", buckets=(0.1, 1.0)) for _ in range(2))
    a.observe(0.05)
    b.observe(0.5)
    b.observe(2.0)
    m = merge_phases([a.phase_snapshot(), b.phase_snapshot()])
    assert m["count"] == 3
    assert m["sum"] == pytest.approx(2.55)
    assert merge_phases([]) == {}


def test_render_prometheus_label_escaping_roundtrip():
    r = Registry()
    nasty = 'a"b\\c\nd'
    r.counter("t_total", "help with \\ backslash", reason=nasty).inc(3)
    text = render_prometheus([({"replica": "0"}, r)])
    # Escapes on the wire...
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    # ...and the independent parser recovers the original value.
    meta, samples = _prom.parse(text)
    # The page also carries the render-time self-histogram; pick ours.
    (name, labels, value), = [s for s in samples if s[0] == "t_total"]
    assert name == "t_total" and value == 3
    assert labels["reason"] == nasty and labels["replica"] == "0"
    assert meta["t_total"]["type"] == "counter"


def test_render_prometheus_histogram_contract():
    r = Registry()
    h = r.histogram("t_seconds", "hist", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    g = r.gauge("t_gauge", "a gauge")
    g.set(2.5)
    text = render_prometheus([({}, r)])
    meta, samples = _prom.parse(text)
    assert meta["t_seconds"]["type"] == "histogram"
    series = _prom.histogram_series(samples, "t_seconds")
    (buckets,) = series.values()
    les = [le for le, _ in buckets]
    vals = [v for _, v in buckets]
    assert les == [0.1, 1.0, math.inf]
    assert vals == sorted(vals)            # cumulative monotone
    by_name = {n: v for n, _, v in samples}
    assert by_name["t_seconds_count"] == vals[-1]   # +Inf == _count
    assert by_name["t_seconds_sum"] == pytest.approx(0.55)
    assert by_name["t_gauge"] == 2.5


def test_registry_readd_replaces():
    r = Registry()
    r.counter("t_total").inc(5)
    r.add(Counter("t_total"))              # restart: replaces, no dup
    assert len(r.collect()) == 1
    assert r.collect()[0].value == 0
    # fn metrics are read-through.
    r.add(Gauge("t_fn", fn=lambda: 7))
    assert [m.collect_value() for m in r.collect()
            if m.name == "t_fn"] == [7]
    # Getter with a fresh fn re-binds the closure (scheduler restart
    # over the same engine must not leave metrics reading the dead
    # scheduler's state).
    r.counter("t_fn2", fn=lambda: 1)
    m = r.counter("t_fn2", fn=lambda: 2)
    assert m.collect_value() == 2


def test_seconds_buckets_cover_request_timeout():
    """The log-bucket table must reach past the 600 s default request
    timeout: a saturation-tail queue wait may legally approach it, and
    percentile estimates clamp at the last bound."""
    from tpu_inference.config import ServerConfig
    from tpu_inference.telemetry import SECONDS_BUCKETS
    assert SECONDS_BUCKETS[-1] >= ServerConfig().request_timeout_s
    h = Histogram("t_seconds")
    h.observe(599.0)                       # lands in a real bucket
    assert h.cumulative()[-2] == 1         # not only in +Inf overflow


def test_log_event_level_gating(capsys, monkeypatch):
    monkeypatch.delenv("TPU_INF_LOG", raising=False)
    telemetry.log_event("quiet_info", level="info", request_id="x")
    telemetry.log_event("loud_warning", level="warning", request_id="y")
    err = capsys.readouterr().err
    assert "quiet_info" not in err         # default threshold: warning
    rec = json.loads([l for l in err.splitlines()
                      if "loud_warning" in l][0])
    assert rec["event"] == "loud_warning" and rec["request_id"] == "y"
    monkeypatch.setenv("TPU_INF_LOG", "info")
    telemetry.log_event("now_visible", level="info")
    assert "now_visible" in capsys.readouterr().err


def test_disabled_telemetry_is_noop(monkeypatch):
    tel = EngineTelemetry(enabled=False)
    tel.decode_dispatch_s.observe(0.1)     # all no-ops, no registry
    tel.prefill_dispatches.inc()
    tel.request_finished("stop")
    assert tel.phase_snapshot() == {}
    assert tel.registry.collect() == []
    monkeypatch.setenv("TPU_INF_TELEMETRY", "0")
    assert not telemetry.telemetry_enabled()


# --------------------------------- heartbeat off the engine thread


def _slow_recorder(tmp_path, gate, monkeypatch):
    """A FlightRecorder whose file write blocks on ``gate`` and records
    the thread it ran on."""
    from tpu_inference.telemetry import STEP_FIELDS, FlightRecorder

    rec = tuple([123.0, "decode"] + [0] * (len(STEP_FIELDS) - 2))
    fr = FlightRecorder(str(tmp_path), replica=0, steps_fn=lambda: [rec],
                        stats_fn=lambda: {"thread":
                                          threading.current_thread().name},
                        periodic_interval_s=0.5)
    writers = []
    real_write = fr._write

    def slow_write(path, payload):
        writers.append(threading.current_thread().name)
        assert gate.wait(10)
        real_write(path, payload)

    monkeypatch.setattr(fr, "_write", slow_write)
    return fr, writers


def test_heartbeat_writes_off_thread_and_skips_when_pending(tmp_path,
                                                            monkeypatch):
    gate = threading.Event()
    fr, writers = _slow_recorder(tmp_path, gate, monkeypatch)
    me = threading.current_thread().name
    assert fr.periodic_due()
    t0 = time.perf_counter()
    assert fr.maybe_periodic()
    assert time.perf_counter() - t0 < 1.0, "the caller never waits the write"
    assert not fr.periodic_due() and not fr.maybe_periodic()
    time.sleep(0.6)                      # next beat due, the first still out
    assert fr.maybe_periodic()
    assert (fr.beats, fr.beats_skipped) == (2, 1)
    periodic = os.path.join(fr.dir, "periodic.json")
    assert not os.path.exists(periodic)
    gate.set()
    assert fr.join_beat(10.0)
    assert writers == ["blackbox-heartbeat"] and me not in writers
    payload = json.loads(open(periodic).read())
    assert payload["trigger"] == "periodic"
    # The ring copy crossed as raw tuples; dicts were built by the writer,
    # which also took the stats.
    assert payload["steps"][0]["kind"] == "decode"
    assert payload["steps"][0]["ts"] == 123.0
    assert payload["stats"] == {"thread": "blackbox-heartbeat"}
    time.sleep(0.6)
    assert fr.maybe_periodic() and fr.join_beat(10.0)
    assert (fr.beats, fr.beats_skipped) == (3, 1)


def test_capture_atexit_still_writes_synchronously(tmp_path, monkeypatch):
    gate = threading.Event()
    gate.set()
    fr, writers = _slow_recorder(tmp_path, gate, monkeypatch)
    path = fr.capture("atexit", min_interval_s=0.0)
    assert path and os.path.exists(path), "written before capture returned"
    assert writers == [threading.current_thread().name]
    payload = json.loads(open(path).read())
    assert payload["trigger"] == "atexit"
    assert payload["steps"][0]["kind"] == "decode"
    assert payload["stats"] == {"thread": threading.current_thread().name}


def test_heartbeat_counters_and_boot_gauges_on_the_registry(tmp_path):
    tel = EngineTelemetry(enabled=True)

    def value(name):
        (m,) = [m for m in tel.registry.collect() if m.name == name]
        return m.collect_value()

    assert value("tpu_inf_loop_heartbeats_total") == 0   # no recorder yet
    fr = telemetry.attach_flight_recorder(tel, str(tmp_path), 0)
    fr.periodic_interval_s = 0.0
    assert fr.maybe_periodic() and fr.join_beat(10.0)
    assert value("tpu_inf_loop_heartbeats_total") == 1
    assert value("tpu_inf_heartbeats_skipped_total") == 0
    tel.boot_weights_s.set(9.5)
    tel.boot_ready_s.set(31.0)
    assert value("tpu_inf_boot_weights_seconds") == 9.5
    assert value("tpu_inf_boot_ready_seconds") == 31.0
    assert 0.0 < telemetry.process_age_s() < 24 * 3600


def test_compile_monitor_counts_requests_and_cache_hits():
    import jax
    import jax.numpy as jnp

    mon = telemetry.install_compile_monitor()
    assert telemetry.install_compile_monitor() is mon     # once a process
    x3, x5 = jnp.ones((3,)), jnp.ones((5,))   # their own programs: first
    before = mon.snapshot()
    fn = jax.jit(telemetry.named_program(
        "tpu_inf_test_program", lambda x: x * 3 + before[0]))
    fn(x3)
    compiles, seconds, hits = mon.snapshot()
    assert compiles == before[0] + 1 and seconds > before[1]
    fn(x3)                               # same shape: no compile request
    assert mon.snapshot()[0] == compiles
    fn(x5)                               # a new shape is a new request
    assert mon.snapshot()[0] == compiles + 1
    assert hits <= compiles              # a hit is one kind of request
    text = telemetry.render_prometheus([])
    assert f"tpu_inf_xla_compiles_total {compiles + 1}" in text
    assert "tpu_inf_xla_cache_hits_total" in text
    names = {r["name"] for r in telemetry.process_counters_dump()}
    assert names == {"tpu_inf_xla_compiles_total",
                     "tpu_inf_xla_compile_seconds_total",
                     "tpu_inf_xla_cache_hits_total"}
