"""Unit tests for tpu_inference/telemetry.py: metric primitives,
percentile estimation, scrape diffing/merging, Prometheus exposition
(via the independent parser in tests/_prom.py), structured logging, and
the boot-time int4 degraded-mode gate."""

import json
import math

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
