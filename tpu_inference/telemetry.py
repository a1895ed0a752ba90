"""Step-phase telemetry: allocation-light metrics + Prometheus exposition.

The round-5 verdict's top directive is evidence: ``hbm_util`` sits far
below target and nothing in the repo can say where the missing roofline
goes — weights vs KV vs dispatch vs host-side bubbles. This module is
the instrumentation layer that answers that with an artifact instead of
archaeology:

- **Counter / Gauge / Histogram**: plain-Python metric primitives cheap
  enough for the dispatch hot path. ``observe()`` is one ``bisect`` (C
  code) + two attribute writes — no allocation, no locks; CPython's GIL
  makes the individual updates atomic and metrics tolerate the rare
  torn read-modify-write under thread races (same stance as the
  scheduler's existing ring buffer). Histograms are log-bucketed
  (powers of two) so one static bucket table spans 10 µs dispatches
  through queue waits at the 600 s request timeout.
- **Registry + render_prometheus()**: standards-compliant Prometheus
  text exposition (format 0.0.4: HELP/TYPE lines, escaped labels,
  cumulative ``_bucket`` series with ``le="+Inf"``, ``_sum``/``_count``)
  over any number of label-tagged registries — the dp replica view
  (server/replicas.py) renders one registry per replica under
  ``replica="i"`` labels plus a fleet registry.
- **Phase snapshots**: JSON-able histogram dumps (cumulative buckets +
  sum + estimated percentiles) that survive scrape-diffing, so
  benchmarks (replay.py / bench.py) can scrape before/after a run and
  commit a ``phase_breakdown`` of exactly that window.
- **log_event()**: one-line structured JSON logs on stderr, leveled via
  ``TPU_INF_LOG`` (default "warning" so test/bench output stays clean;
  set ``TPU_INF_LOG=info`` for per-request lifecycle events). Events
  carry the propagated request id.

``TPU_INF_TELEMETRY=0`` disables collection entirely (every metric
becomes a shared no-op singleton) — the comparison arm of the overhead
budget (README "Observability": ≤1% on the decode dispatch microbench).
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
from bisect import bisect_left
from typing import (Any, Callable, Dict, Iterable, List, Mapping, Optional,
                    Sequence, Tuple)

# ---------------------------------------------------------------------------
# Structured logging
# ---------------------------------------------------------------------------

_LEVELS = {"debug": 10, "info": 20, "warning": 30, "error": 40}


def _log_threshold() -> int:
    return _LEVELS.get(os.environ.get("TPU_INF_LOG", "warning").lower(), 30)


def log_event(event: str, level: str = "info", **fields: Any) -> None:
    """Emit one structured JSON log line to stderr.

    Levels below the ``TPU_INF_LOG`` threshold are dropped before any
    serialization work. stderr (not stdout) so bench harnesses that
    parse JSON records off stdout never see log lines.
    """
    if _LEVELS.get(level, 20) < _log_threshold():
        return
    rec = {"ts": round(time.time(), 4), "level": level, "event": event}
    rec.update(fields)
    try:
        line = json.dumps(rec, default=str)
    except (TypeError, ValueError):
        line = json.dumps({"ts": rec["ts"], "level": level, "event": event,
                           "error": "unserializable fields"})
    print(line, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Metric primitives
# ---------------------------------------------------------------------------

class _NullMetric:
    """Shared no-op stand-in when telemetry is disabled: every mutator
    is a single attribute lookup + empty call, so instrumented code
    needs no ``if enabled`` branches of its own."""

    __slots__ = ()

    def inc(self, n: float = 1) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass


NULL_METRIC = _NullMetric()


class Counter:
    """Monotonic counter. ``fn`` makes it a read-through counter whose
    value is computed at collect time (zero hot-path cost for counters
    the code base already tracks, e.g. SchedulerStats fields)."""

    __slots__ = ("name", "help", "labels", "value", "fn")
    kind = "counter"

    def __init__(self, name: str, help: str = "",
                 labels: Optional[Mapping[str, str]] = None,
                 fn: Optional[Callable[[], float]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        self.value: float = 0
        self.fn = fn

    def inc(self, n: float = 1) -> None:
        self.value += n

    def collect_value(self) -> float:
        return self.fn() if self.fn is not None else self.value


class Gauge:
    """Point-in-time value; ``fn`` = computed at collect time."""

    __slots__ = ("name", "help", "labels", "value", "fn")
    kind = "gauge"

    def __init__(self, name: str, help: str = "",
                 labels: Optional[Mapping[str, str]] = None,
                 fn: Optional[Callable[[], float]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        self.value: float = 0
        self.fn = fn

    def set(self, v: float) -> None:
        self.value = v

    def inc(self, n: float = 1) -> None:
        self.value += n

    def collect_value(self) -> float:
        return self.fn() if self.fn is not None else self.value


# Log-spaced (powers of two) bucket bounds. Seconds: ~7.6 µs .. 1024 s
# covers a Pallas decode dispatch through a queue wait at the 600 s
# default request timeout (the saturation tail must not clamp at the
# last bound — that is exactly the regime these histograms measure);
# counts: 1 .. 512 covers tokens-per-dispatch at any sane fused-K*batch.
SECONDS_BUCKETS = tuple(2.0 ** e for e in range(-17, 11))
COUNT_BUCKETS = tuple(float(2 ** e) for e in range(0, 10))
# Ratio-valued histograms (e.g. per-round speculative acceptance rate):
# eighths of [0, 1] — fine enough to see "mostly rejected" vs "mostly
# accepted", coarse enough to stay allocation-light.
RATE_BUCKETS = tuple(i / 8 for i in range(9))


class Histogram:
    """Fixed-bucket histogram (Prometheus ``histogram`` semantics).

    ``_counts`` holds per-bucket (non-cumulative) counts with one
    overflow bucket at the end; exposition renders them cumulative with
    a final ``le="+Inf"``. ``observe`` is allocation-free: one C-level
    bisect + two in-place adds.
    """

    __slots__ = ("name", "help", "labels", "bounds", "_counts", "sum")
    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = SECONDS_BUCKETS,
                 labels: Optional[Mapping[str, str]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        self.bounds: Tuple[float, ...] = tuple(buckets)
        assert list(self.bounds) == sorted(self.bounds)
        self._counts: List[int] = [0] * (len(self.bounds) + 1)
        self.sum: float = 0.0

    def observe(self, v: float) -> None:
        # bisect_left(bounds, v) = first bucket whose bound >= v, i.e.
        # Prometheus's le (inclusive upper bound) convention.
        self._counts[bisect_left(self.bounds, v)] += 1
        self.sum += v

    @property
    def count(self) -> int:
        return sum(self._counts)

    def cumulative(self) -> List[int]:
        """Per-le cumulative counts (len(bounds) + 1, last = +Inf).
        Computed from a point-in-time copy so a concurrent observe can
        never yield a non-monotone series."""
        counts = list(self._counts)
        out, acc = [], 0
        for c in counts:
            acc += c
            out.append(acc)
        return out

    def percentile(self, p: float) -> Optional[float]:
        return percentile_from_cumulative(self.bounds, self.cumulative(), p)

    def phase_snapshot(self) -> Dict[str, Any]:
        """JSON-able dump: cumulative buckets (diffable across scrapes)
        + sum + estimated percentiles."""
        return _phase_dict(self.bounds, self.cumulative(), self.sum)


def _phase_dict(bounds: Sequence[float], cumulative: List[int],
                total_sum: float,
                les: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    """The one assembly point for the {count, sum, percentiles, buckets}
    snapshot shape shared by phase_snapshot / diff_phase / merge_phases —
    consumers (replay phase_breakdown, fleet merge) rely on the three
    producers never drifting apart."""
    if les is None:
        les = list(bounds) + ["+Inf"]
    return {
        "count": cumulative[-1],
        "sum": round(total_sum, 6),
        "p50": percentile_from_cumulative(bounds, cumulative, 0.50),
        "p95": percentile_from_cumulative(bounds, cumulative, 0.95),
        "p99": percentile_from_cumulative(bounds, cumulative, 0.99),
        "buckets": [[le, c] for le, c in zip(les, cumulative)],
    }


def percentile_from_cumulative(bounds: Sequence[float],
                               cumulative: Sequence[int],
                               p: float) -> Optional[float]:
    """Estimate the p-quantile from cumulative bucket counts by linear
    interpolation inside the containing bucket (the standard Prometheus
    histogram_quantile estimate). None when the histogram is empty."""
    total = cumulative[-1]
    if total <= 0:
        return None
    target = p * total
    prev_cum = 0
    for i, cum in enumerate(cumulative):
        if cum >= target:
            lower = bounds[i - 1] if i > 0 else 0.0
            upper = bounds[i] if i < len(bounds) else bounds[-1]
            in_bucket = cum - prev_cum
            frac = (target - prev_cum) / in_bucket if in_bucket else 1.0
            return round(lower + (upper - lower) * frac, 9)
        prev_cum = cum
    return round(bounds[-1], 9)


def diff_phase(after: Dict[str, Any],
               before: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """phase_snapshot(after) - phase_snapshot(before): the histogram of
    exactly the window between two scrapes, with recomputed percentiles.
    ``before=None`` (or an incompatible bucket table) returns ``after``
    unchanged."""
    if not before or len(before.get("buckets", ())) != len(after["buckets"]):
        return dict(after)
    bounds = [b[0] for b in after["buckets"][:-1]]
    cum = [max(0, a[1] - b[1])
           for a, b in zip(after["buckets"], before["buckets"])]
    # Re-monotonize (counter reset / racy scrape can dent the diff).
    for i in range(1, len(cum)):
        cum[i] = max(cum[i], cum[i - 1])
    return _phase_dict(bounds, cum,
                       max(0.0, after["sum"] - before["sum"]),
                       les=[b[0] for b in after["buckets"]])


def merge_phases(snaps: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Element-wise merge of same-shaped phase snapshots (dp replicas
    into one fleet histogram)."""
    snaps = [s for s in snaps if s]
    if not snaps:
        return {}
    base = snaps[0]
    if len(snaps) == 1:
        return dict(base)
    bounds = [b[0] for b in base["buckets"][:-1]]
    cum = [0] * len(base["buckets"])
    total_sum = 0.0
    for s in snaps:
        if len(s["buckets"]) != len(cum):
            continue
        total_sum += s["sum"]
        for i, (_, c) in enumerate(s["buckets"]):
            cum[i] += c
    return _phase_dict(bounds, cum, total_sum,
                       les=[b[0] for b in base["buckets"]])


# ---------------------------------------------------------------------------
# Registry + Prometheus text exposition
# ---------------------------------------------------------------------------

class Registry:
    """Ordered collection of metrics. Re-adding the same (name, labels)
    replaces the old metric, so restartable components (test servers
    cycling schedulers) never accumulate stale duplicates."""

    def __init__(self):
        self._metrics: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], Any] = {}

    def add(self, metric):
        key = (metric.name, tuple(sorted(metric.labels.items())))
        self._metrics[key] = metric
        return metric

    def counter(self, name: str, help: str = "", fn=None,
                **labels: str) -> Counter:
        key = (name, tuple(sorted(labels.items())))
        m = self._metrics.get(key)
        if m is None:
            m = self.add(Counter(name, help, labels=labels, fn=fn))
        elif fn is not None:
            # Component restart (e.g. a new scheduler re-binding over the
            # same engine): the fresh closure must replace the dead
            # component's, or the read-through metric freezes at the old
            # values and pins the dead object in memory.
            m.fn = fn
        return m

    def gauge(self, name: str, help: str = "", fn=None,
              **labels: str) -> Gauge:
        key = (name, tuple(sorted(labels.items())))
        m = self._metrics.get(key)
        if m is None:
            m = self.add(Gauge(name, help, labels=labels, fn=fn))
        elif fn is not None:
            m.fn = fn                      # re-bind on component restart
        return m

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = SECONDS_BUCKETS,
                  **labels: str) -> Histogram:
        key = (name, tuple(sorted(labels.items())))
        m = self._metrics.get(key)
        if m is None:
            m = self.add(Histogram(name, help, buckets=buckets,
                                   labels=labels))
        return m

    def collect(self) -> List[Any]:
        # Snapshot: the engine thread may register a new labeled counter
        # while a scrape iterates.
        return list(self._metrics.values())


def escape_label_value(v: str) -> str:
    """Prometheus text-format label value escaping: backslash, double
    quote and newline."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def escape_help(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_value(v: float) -> str:
    if v != v:                                   # NaN
        return "NaN"
    if isinstance(v, float) and v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v)) if isinstance(v, float) else str(v)


def _fmt_labels(labels: Mapping[str, str],
                extra: Optional[Mapping[str, str]] = None) -> str:
    merged = dict(extra or {})
    merged.update(labels)
    if not merged:
        return ""
    inner = ",".join(f'{k}="{escape_label_value(v)}"'
                     for k, v in merged.items())
    return "{" + inner + "}"


# Self-metrics (README "Performance attribution"): the telemetry path
# observes its own exposition cost, so observability overhead is itself
# observable. One module-level registry per process; rendered as an
# extra unlabeled group on every scrape (the render that is being timed
# exposes the PREVIOUS renders' histogram — exact-once semantics are
# not worth a second pass).
_SELF_REGISTRY = Registry()
_RENDER_SECONDS = _SELF_REGISTRY.histogram(
    "tpu_inf_metrics_render_seconds",
    "Host wall of one Prometheus text exposition render")


def render_prometheus(groups: Iterable[Tuple[Mapping[str, str], Registry]]
                      ) -> str:
    """Render label-tagged registries as one Prometheus text page.

    ``groups``: (shared labels, registry) pairs — e.g. one per dp
    replica with ``{"replica": "0"}`` plus an unlabeled fleet registry.
    HELP/TYPE are emitted once per metric name (first definition wins);
    all samples of a name stay contiguous, as the format requires.
    """
    t_render = time.perf_counter()
    groups = list(groups)
    if telemetry_enabled():
        groups.append(({}, _SELF_REGISTRY))
    # name -> (kind, help, [(merged labels, metric)])
    families: Dict[str, Tuple[str, str, List[Tuple[Dict[str, str], Any]]]] = {}
    order: List[str] = []
    for shared, registry in groups:
        for m in registry.collect():
            fam = families.get(m.name)
            if fam is None:
                families[m.name] = fam = (m.kind, m.help, [])
                order.append(m.name)
            fam[2].append((dict(shared), m))
    lines: List[str] = []
    for name in order:
        kind, help_, samples = families[name]
        lines.append(f"# HELP {name} {escape_help(help_)}")
        lines.append(f"# TYPE {name} {kind}")
        for shared, m in samples:
            if kind == "histogram":
                cum = m.cumulative()
                for le, c in zip(m.bounds, cum):
                    ll = _fmt_labels({**m.labels, "le": _fmt_value(le)},
                                     shared)
                    lines.append(f"{name}_bucket{ll} {c}")
                ll = _fmt_labels({**m.labels, "le": "+Inf"}, shared)
                lines.append(f"{name}_bucket{ll} {cum[-1]}")
                ls = _fmt_labels(m.labels, shared)
                lines.append(f"{name}_sum{ls} {_fmt_value(m.sum)}")
                lines.append(f"{name}_count{ls} {cum[-1]}")
            else:
                ls = _fmt_labels(m.labels, shared)
                lines.append(f"{name}{ls} {_fmt_value(m.collect_value())}")
    out = "\n".join(lines) + "\n"
    _RENDER_SECONDS.observe(time.perf_counter() - t_render)
    return out


# Content type the text page must be served under (version matters:
# parsers key on it).
PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


# ---------------------------------------------------------------------------
# Registry transport (subprocess fleet, README "Process fleet"): an
# engine-worker process dumps its registry as JSON-able samples over the
# RPC channel; the router rebuilds concrete metrics from the dump and
# renders them under the worker's stable replica="i" label. Counter and
# histogram series from dead worker incarnations fold into a per-replica
# CARRY so a restart never resets the fleet-level scrape (Prometheus
# counters must be monotone per series or rate() misreads the reset).
# ---------------------------------------------------------------------------


def dump_registry(registry: Registry) -> List[Dict[str, Any]]:
    """Serialize a registry's current samples (read-through metrics are
    evaluated here, so the dump is self-contained)."""
    out: List[Dict[str, Any]] = []
    for m in registry.collect():
        rec: Dict[str, Any] = {"name": m.name, "kind": m.kind,
                               "help": m.help, "labels": dict(m.labels)}
        if m.kind == "histogram":
            rec["bounds"] = list(m.bounds)
            rec["counts"] = list(m._counts)
            rec["sum"] = m.sum
        else:
            rec["value"] = m.collect_value()
        out.append(rec)
    return out


def registry_from_dump(samples: Sequence[Dict[str, Any]]) -> Registry:
    """Rebuild a renderable Registry from :func:`dump_registry` output."""
    r = Registry()
    for rec in samples:
        labels = rec.get("labels") or {}
        if rec["kind"] == "histogram":
            h = Histogram(rec["name"], rec.get("help", ""),
                          buckets=rec.get("bounds") or SECONDS_BUCKETS,
                          labels=labels)
            counts = list(rec.get("counts") or [])
            if len(counts) == len(h._counts):
                h._counts = counts
            h.sum = rec.get("sum", 0.0)
            r.add(h)
        else:
            cls = Gauge if rec["kind"] == "gauge" else Counter
            m = cls(rec["name"], rec.get("help", ""), labels=labels)
            m.value = rec.get("value", 0)
            r.add(m)
    return r


def _dump_key(rec: Dict[str, Any]) -> Tuple:
    return (rec["name"], tuple(sorted((rec.get("labels") or {}).items())))


def fold_dump_into_carry(carry: Dict[Tuple, Dict[str, Any]],
                         dump: Sequence[Dict[str, Any]]) -> None:
    """Accumulate a dead worker incarnation's MONOTONIC series (counters
    + histograms; gauges are point-in-time and die with the process)
    into ``carry``, in place."""
    import copy
    for rec in dump or ():
        if rec["kind"] == "gauge":
            continue
        key = _dump_key(rec)
        base = carry.get(key)
        if base is None:
            carry[key] = copy.deepcopy(rec)
        elif rec["kind"] == "counter":
            base["value"] = base.get("value", 0) + rec.get("value", 0)
        elif (rec["kind"] == "histogram"
              and base.get("bounds") == rec.get("bounds")):
            base["counts"] = [a + b for a, b in zip(base["counts"],
                                                    rec["counts"])]
            base["sum"] = base.get("sum", 0.0) + rec.get("sum", 0.0)


def apply_carry(carry: Dict[Tuple, Dict[str, Any]],
                dump: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Live dump + carried prior-incarnation totals, non-destructively.
    Carried series the fresh incarnation hasn't re-minted yet (lazy
    labeled children like requests_finished{reason=...}) still render,
    so a restart can never make a series vanish from the scrape."""
    import copy
    if not carry:
        return list(dump or ())
    out: List[Dict[str, Any]] = []
    seen = set()
    for rec in dump or ():
        key = _dump_key(rec)
        seen.add(key)
        base = carry.get(key)
        if base is None or rec["kind"] == "gauge":
            out.append(rec)
            continue
        rec = copy.deepcopy(rec)
        if rec["kind"] == "counter":
            rec["value"] = rec.get("value", 0) + base.get("value", 0)
        elif (rec["kind"] == "histogram"
              and base.get("bounds") == rec.get("bounds")):
            rec["counts"] = [a + b for a, b in zip(rec["counts"],
                                                   base["counts"])]
            rec["sum"] = rec.get("sum", 0.0) + base.get("sum", 0.0)
        out.append(rec)
    for key, rec in carry.items():
        if key not in seen:
            out.append(rec)
    return out


def telemetry_enabled() -> bool:
    return os.environ.get("TPU_INF_TELEMETRY", "1") != "0"


# ---------------------------------------------------------------------------
# Distributed request tracing (README "Observability": span schema).
#
# A span is one JSON-able dict describing a timed phase of one request:
#
#     {"name", "trace": trace_id, "parent": parent span NAME ("" = the
#      root "request" span), "ts": unix seconds, "dur": seconds,
#      "replica": emitting replica (-1 = the router), "attrs": {...}}
#
# Timestamps are monotonic-anchored-to-wallclock: instrumented code
# passes ``time.perf_counter()`` readings (the clock every existing
# request timestamp already uses) and the recorder converts them to
# unix seconds via a (time.time(), perf_counter()) anchor taken at
# construction — so spans exported by DIFFERENT processes (router,
# prefill worker, decode worker) land on one comparable timeline.
# Parent linkage is by span NAME within a trace (the span set is a
# small fixed vocabulary, and names are unique per trace per replica
# except prefill_chunk, whose parent "prefill" is unambiguous), which
# keeps cross-process assembly free of id coordination.
# ---------------------------------------------------------------------------


class SpanRecorder:
    """Bounded per-process span sink (one per engine replica, plus one
    in the router). Completed request traces move to a recent ring at
    ``seal()``; spans for requests the process cannot attribute (cache-
    eviction swap-outs) land in a maintenance ring instead. Thread
    stance: a lock guards the dicts (spans are recorded at request
    granularity, not the dispatch hot path), and all export methods
    return copies. Disabled (``TPU_INF_TELEMETRY=0``) every method is a
    cheap no-op, so spans ride the same kill switch as the metrics."""

    MAX_TRACES = 256
    MAX_SPANS_PER_TRACE = 96

    def __init__(self, enabled: Optional[bool] = None, replica: int = -1):
        self.enabled = (telemetry_enabled() if enabled is None else enabled)
        self.replica = replica
        self._anchor_unix = time.time()
        self._anchor_mono = time.perf_counter()
        self._open: "collections.OrderedDict[str, List[dict]]" = \
            collections.OrderedDict()
        self._recent: "collections.OrderedDict[str, List[dict]]" = \
            collections.OrderedDict()
        self._maintenance: collections.deque = collections.deque(maxlen=128)
        self._lock = threading.Lock()
        self.spans_dropped = 0
        self.traces_evicted = 0

    def to_unix(self, t_mono: float) -> float:
        return self._anchor_unix + (t_mono - self._anchor_mono)

    def _span(self, name: str, trace_id: str, t0: float, t1: float,
              parent: str, attrs: Dict[str, Any]) -> dict:
        span = {"name": name, "trace": trace_id, "parent": parent,
                "ts": round(self.to_unix(t0), 6),
                "dur": round(max(0.0, t1 - t0), 6),
                "replica": self.replica}
        if attrs:
            span["attrs"] = attrs
        return span

    def add(self, name: str, trace_id: str, t0: float, t1: float,
            parent: str = "request", **attrs: Any) -> None:
        """Record one completed span (perf_counter start/end) under a
        trace. Per-trace span counts and the number of open traces are
        both capped so an unsealed trace (engine-direct callers that
        bypass the scheduler) can never grow without bound."""
        if not self.enabled or not trace_id:
            return
        span = self._span(name, trace_id, t0, t1, parent, attrs)
        with self._lock:
            spans = self._open.get(trace_id)
            if spans is None:
                while len(self._open) >= self.MAX_TRACES:
                    self._open.popitem(last=False)
                    self.traces_evicted += 1
                spans = self._open[trace_id] = []
            if len(spans) >= self.MAX_SPANS_PER_TRACE:
                self.spans_dropped += 1
                return
            spans.append(span)

    def add_maintenance(self, name: str, t0: float, t1: float,
                        **attrs: Any) -> None:
        """Record a span no single request owns (e.g. a cache-eviction
        swap-out batch): shows up in the Chrome timeline under a
        per-replica maintenance lane, never in request trees."""
        if not self.enabled:
            return
        self._maintenance.append(self._span(name, "-maintenance-",
                                            t0, t1, "", attrs))

    def ingest(self, trace_id: str, spans: Sequence[dict]) -> None:
        """Fold spans exported by ANOTHER process (worker event frames)
        into this recorder's open table — they carry their source's
        replica tag and absolute unix timestamps already."""
        if not self.enabled or not trace_id or not spans:
            return
        with self._lock:
            dest = self._open.get(trace_id)
            if dest is None:
                # A finish frame's spans can arrive after the router
                # already sealed the trace (FIFO per connection, but
                # handoff traces span two connections): append there.
                dest = self._recent.get(trace_id)
            if dest is None:
                while len(self._open) >= self.MAX_TRACES:
                    self._open.popitem(last=False)
                    self.traces_evicted += 1
                dest = self._open[trace_id] = []
            room = self.MAX_SPANS_PER_TRACE - len(dest)
            if room < len(spans):
                self.spans_dropped += len(spans) - max(0, room)
            dest.extend(list(spans)[:max(0, room)])

    def seal(self, trace_id: str) -> None:
        """The request finished: move its spans to the recent ring (the
        /debug/trace + Chrome-export source)."""
        if not self.enabled or not trace_id:
            return
        with self._lock:
            spans = self._open.pop(trace_id, None)
            if spans is None:
                return
            prior = self._recent.pop(trace_id, None)
            if prior:
                spans = prior + spans
            while len(self._recent) >= self.MAX_TRACES:
                self._recent.popitem(last=False)
                self.traces_evicted += 1
            self._recent[trace_id] = spans

    def get_trace(self, trace_id: str) -> Optional[List[dict]]:
        with self._lock:
            spans = self._recent.get(trace_id) or self._open.get(trace_id)
            return list(spans) if spans else None

    def export_recent(self, trace_id: str) -> List[dict]:
        """Copy a sealed trace's spans (kept in the ring for the pull
        verb) — the worker's finish-event payload."""
        with self._lock:
            return list(self._recent.get(trace_id) or ())

    def export_open(self, trace_id: str) -> List[dict]:
        """Copy an UNFINISHED trace's spans so far (drain-time migrate
        events ship these: the request continues elsewhere)."""
        with self._lock:
            return list(self._open.get(trace_id) or ())

    def recent_traces(self, n: int = 64) -> Dict[str, List[dict]]:
        """The last ``n`` sealed traces, oldest first (n <= 0 returns
        none — the maintenance-only pull uses n=0)."""
        if n <= 0:
            return {}
        with self._lock:
            ids = list(self._recent)[-n:]
            return {tid: list(self._recent[tid]) for tid in ids}

    def maintenance_spans(self, n: int = 128) -> List[dict]:
        return list(self._maintenance)[-n:]


# The full span-name vocabulary any recorder in the repo can emit.
# tests/test_metric_catalog.py gates this against both the code's
# add()/add_maintenance() literals and the README span table, so a new
# span cannot ship undocumented (and a doc row cannot outlive its span).
SPAN_NAMES = (
    "request", "route", "queue_wait", "prefill", "prefill_chunk",
    "decode", "handoff", "handoff_adopt", "handoff_export",
    "drain_export", "migrate",
    "kv_swap_in", "kv_swap_out", "rollout", "scale_up", "scale_down",
)


def register_span_ring(registry: Registry, recorder: SpanRecorder) -> None:
    """Span-ring self-metrics (README "Performance attribution"):
    occupancy gauges + drop/eviction counters over one SpanRecorder, so
    trace loss under ring pressure is visible on /metrics instead of
    silently truncating /debug/trace. Shared by the engine bundle (its
    replica recorder) and both fleet backends (the router recorder)."""
    registry.gauge("tpu_inf_trace_ring_traces",
                   "Sealed request traces resident in the recent ring",
                   fn=lambda: float(len(recorder._recent)))
    registry.gauge("tpu_inf_trace_ring_open",
                   "Unsealed (in-flight or abandoned) traces in the "
                   "open table",
                   fn=lambda: float(len(recorder._open)))
    registry.counter("tpu_inf_trace_spans_dropped_total",
                     "Spans dropped by the per-trace span cap",
                     fn=lambda: recorder.spans_dropped)
    registry.counter("tpu_inf_trace_evictions_total",
                     "Whole traces evicted from the rings by the "
                     "trace-count cap",
                     fn=lambda: recorder.traces_evicted)


def assemble_trace(trace_id: str, spans: Sequence[dict]) -> dict:
    """One request's cross-process span TREE: spans sorted by start
    time, children nested under their parent by NAME (first match in
    the same replica wins, then any replica; orphans attach to the
    root). The root is the router's ``request`` span when present,
    else a synthetic envelope covering every span."""
    spans = sorted(spans, key=lambda s: (s.get("ts", 0.0),
                                         -s.get("dur", 0.0)))
    nodes = [{**s, "children": []} for s in spans]
    root = next((n for n in nodes if n["name"] == "request"), None)
    if root is None:
        t0 = min((n["ts"] for n in nodes), default=0.0)
        t1 = max((n["ts"] + n["dur"] for n in nodes), default=0.0)
        root = {"name": "request", "trace": trace_id, "parent": "",
                "ts": round(t0, 6), "dur": round(t1 - t0, 6),
                "replica": -1, "children": [], "synthetic": True}
    by_name: Dict[Tuple[str, int], dict] = {}
    for n in nodes:
        by_name.setdefault((n["name"], n.get("replica", -1)), n)
        by_name.setdefault((n["name"], None), n)
    for n in nodes:
        if n is root:
            continue
        parent = n.get("parent") or "request"
        if parent == n["name"]:
            parent = "request"
        target = (by_name.get((parent, n.get("replica", -1)))
                  or by_name.get((parent, None)))
        if target is None or target is n:
            target = root
        target["children"].append(n)
    return {"trace_id": trace_id, "n_spans": len(spans),
            "replicas": sorted({s.get("replica", -1) for s in spans}),
            "spans": spans, "tree": root}


def spans_to_chrome(traces: Mapping[str, Sequence[dict]],
                    pid_names: Optional[Mapping[int, str]] = None,
                    maintenance: Optional[Sequence[dict]] = None,
                    other_data: Optional[dict] = None) -> dict:
    """Render span traces as Chrome trace-event JSON (the "JSON Array
    Format" with complete ``ph:"X"`` events) loadable in Perfetto /
    chrome://tracing: one pid per replica (router = pid 0, replica i =
    pid i+1), one tid per trace, absolute-unix microsecond timestamps
    so spans from different processes interleave correctly."""
    events: List[dict] = []
    seen_pids: Dict[int, str] = {}
    seen_tids: set = set()
    pid_names = dict(pid_names or {})

    def _pid(replica: int) -> int:
        pid = replica + 1 if replica >= 0 else 0
        if pid not in seen_pids:
            seen_pids[pid] = pid_names.get(
                pid, "router" if pid == 0 else f"replica {pid - 1}")
        return pid

    for tidx, (trace_id, spans) in enumerate(traces.items(), start=1):
        for s in spans:
            pid = _pid(int(s.get("replica", -1)))
            if (pid, tidx) not in seen_tids:
                seen_tids.add((pid, tidx))
                events.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": tidx,
                               "args": {"name": f"trace {trace_id}"}})
            events.append({
                "name": s["name"], "cat": "request", "ph": "X",
                "ts": round(s["ts"] * 1e6, 1),
                "dur": round(max(s["dur"], 1e-6) * 1e6, 1),
                "pid": pid, "tid": tidx,
                "args": {**(s.get("attrs") or {}),
                         "trace_id": trace_id,
                         "parent": s.get("parent", "")},
            })
    for s in maintenance or ():
        pid = _pid(int(s.get("replica", -1)))
        events.append({
            "name": s["name"], "cat": "maintenance", "ph": "X",
            "ts": round(s["ts"] * 1e6, 1),
            "dur": round(max(s["dur"], 1e-6) * 1e6, 1),
            "pid": pid, "tid": 0,
            "args": dict(s.get("attrs") or {}),
        })
    meta = [{"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": name}}
            for pid, name in sorted(seen_pids.items())]
    return {"traceEvents": meta + events, "displayTimeUnit": "ms",
            "otherData": dict(other_data or {})}


# ---------------------------------------------------------------------------
# Rolling SLO gauges (README "Observability": SLO gauges). A fixed-size
# ring of the most recent request latencies yields EXACT windowed
# quantiles (unlike the log-bucketed histograms, whose interpolation
# error can exceed an SLO margin) — the input signal the autoscaler
# (ROADMAP item 3) consumes. Ring writes are GIL-atomic list stores
# (the scheduler's decode_call_s stance); quantile reads sort a copy.
# ---------------------------------------------------------------------------

SLO_WINDOW = 512
SLO_QUANTILES = (0.5, 0.95)


class RollingWindow:
    """Ring of the last ``size`` observations with exact quantiles."""

    __slots__ = ("_ring", "_n")

    def __init__(self, size: int = SLO_WINDOW):
        self._ring = [0.0] * size
        self._n = 0

    def observe(self, v: float) -> None:
        self._ring[self._n % len(self._ring)] = v
        self._n += 1

    @property
    def count(self) -> int:
        return self._n

    def values(self) -> List[float]:
        return self._ring[:min(self._n, len(self._ring))]

    def quantile(self, q: float) -> Optional[float]:
        # Delegates so the per-replica and fleet-pooled gauges can
        # never drift onto different estimators.
        return pooled_quantile([self.values()], q)


def pooled_quantile(windows: Sequence[Sequence[float]],
                    q: float) -> Optional[float]:
    """Exact quantile over several replicas' pooled ring contents (the
    fleet view — per-replica quantiles do not compose by max/mean)."""
    xs = sorted(v for w in windows for v in (w or ()))
    if not xs:
        return None
    return xs[min(len(xs) - 1, int(q * len(xs)))]


class SLOTracker:
    """Windowed TTFT/TPOT quantiles + breach counting against the
    ``--slo-ttft-ms`` / ``--slo-tpot-ms`` targets (0 = no target: the
    quantile gauges still export, breaches never count)."""

    def __init__(self, ttft_target_s: float = 0.0,
                 tpot_target_s: float = 0.0):
        self.ttft_target_s = max(0.0, ttft_target_s)
        self.tpot_target_s = max(0.0, tpot_target_s)
        self.ttft = RollingWindow()
        self.tpot = RollingWindow()
        self.ttft_breaches = 0
        self.tpot_breaches = 0

    def observe(self, ttft_s: Optional[float],
                tpot_s: Optional[float]) -> None:
        if ttft_s is not None:
            self.ttft.observe(ttft_s)
            if self.ttft_target_s > 0 and ttft_s > self.ttft_target_s:
                self.ttft_breaches += 1
        if tpot_s is not None:
            self.tpot.observe(tpot_s)
            if self.tpot_target_s > 0 and tpot_s > self.tpot_target_s:
                self.tpot_breaches += 1

    def gauge_value(self, which: str, q: float) -> float:
        """Read-through value for the Prometheus gauges (NaN = empty
        window, the Prometheus idiom for 'no data')."""
        ring = self.ttft if which == "ttft" else self.tpot
        v = ring.quantile(q)
        return float("nan") if v is None else v

    def snapshot(self, include_window: bool = True) -> dict:
        def _r(v):
            return None if v is None else round(v, 6)

        out = {
            "ttft_target_s": self.ttft_target_s or None,
            "tpot_target_s": self.tpot_target_s or None,
            "ttft_p50_s": _r(self.ttft.quantile(0.5)),
            "ttft_p95_s": _r(self.ttft.quantile(0.95)),
            "tpot_p50_s": _r(self.tpot.quantile(0.5)),
            "tpot_p95_s": _r(self.tpot.quantile(0.95)),
            "ttft_breaches": self.ttft_breaches,
            "tpot_breaches": self.tpot_breaches,
            "window_requests": min(self.ttft.count, SLO_WINDOW),
        }
        if include_window:
            # Raw ring contents so fleet aggregation can pool EXACT
            # quantiles across replicas (max/mean of p95s is not a p95).
            out["ttft_window"] = [round(v, 6) for v in self.ttft.values()]
            out["tpot_window"] = [round(v, 6) for v in self.tpot.values()]
        return out


def pooled_slo(slos: Sequence[Optional[dict]]) -> dict:
    """Fleet-level SLO view from per-replica snapshots (with windows):
    pooled exact quantiles + summed breach counts."""
    slos = [s for s in slos if s]

    def _r(v):
        return None if v is None else round(v, 6)

    ttft = [s.get("ttft_window") or [] for s in slos]
    tpot = [s.get("tpot_window") or [] for s in slos]
    return {
        "ttft_target_s": next((s.get("ttft_target_s") for s in slos
                               if s.get("ttft_target_s")), None),
        "tpot_target_s": next((s.get("tpot_target_s") for s in slos
                               if s.get("tpot_target_s")), None),
        "ttft_p50_s": _r(pooled_quantile(ttft, 0.5)),
        "ttft_p95_s": _r(pooled_quantile(ttft, 0.95)),
        "tpot_p50_s": _r(pooled_quantile(tpot, 0.5)),
        "tpot_p95_s": _r(pooled_quantile(tpot, 0.95)),
        "ttft_breaches": sum(s.get("ttft_breaches", 0) for s in slos),
        "tpot_breaches": sum(s.get("tpot_breaches", 0) for s in slos),
        "window_requests": sum(s.get("window_requests", 0) for s in slos),
    }


def register_fleet_slo(registry: Registry,
                       quantile_fn: Callable[[str, float], float],
                       breaches_fn: Callable[[str], float]) -> None:
    """THE fleet-level SLO series registration, shared by both fleet
    backends (EngineGroup pools live trackers, ProcessEngineGroup pools
    cached worker windows + the restart carry) so their /metrics
    surfaces cannot drift. ``quantile_fn(kind, q)`` returns the pooled
    exact quantile (NaN = no data); ``breaches_fn(kind)`` the monotone
    fleet breach total."""
    for q in SLO_QUANTILES:
        registry.gauge("tpu_inf_slo_ttft_seconds",
                       "Fleet rolling exact TTFT quantile (pooled "
                       "across replica windows; NaN = no data)",
                       fn=lambda q=q: quantile_fn("ttft", q),
                       q=f"{q:g}")
        registry.gauge("tpu_inf_slo_tpot_seconds",
                       "Fleet rolling exact TPOT quantile (pooled "
                       "across replica windows; NaN = no data)",
                       fn=lambda q=q: quantile_fn("tpot", q),
                       q=f"{q:g}")
    for kind in ("ttft", "tpot"):
        registry.counter("tpu_inf_slo_breaches_total",
                         "Fleet SLO target breaches (monotone across "
                         "worker restarts)",
                         fn=lambda k=kind: breaches_fn(k), slo=kind)


def register_fleet_elastic(registry: Registry,
                           scale_ups: Callable[[], int],
                           scale_downs: Callable[[], int],
                           rollouts: Callable[[], int],
                           class_preempted: Callable[[str], int],
                           class_deferred: Callable[[str], int],
                           class_shed: Callable[[str], int]) -> None:
    """Elastic-fleet series (README "Elastic fleet"): autoscaler and
    rollout actuations, plus the per-class admission outcomes. All
    router-side state, so the series survive worker restarts without a
    carry. Interactive requests never defer or preempt (they are the
    preemptORs), so those two series only exist for the lower classes."""
    from tpu_inference.config import PRIORITY_CLASSES

    registry.counter("tpu_inf_fleet_scale_ups_total",
                     "Autoscaler scale-up actuations (worker spawned on "
                     "a sustained pooled-SLO breach)", fn=scale_ups)
    registry.counter("tpu_inf_fleet_scale_downs_total",
                     "Autoscaler scale-down actuations (coldest replica "
                     "drain-and-migrated away on a sustained lull)",
                     fn=scale_downs)
    registry.counter("tpu_inf_fleet_rollouts_total",
                     "Completed rolling-upgrade passes (POST "
                     "/debug/rollout)", fn=rollouts)
    for cls in PRIORITY_CLASSES:
        registry.counter("tpu_inf_class_shed_total",
                         "Requests shed with 429 after every class "
                         "escape (defer/preempt) failed",
                         fn=lambda c=cls: class_shed(c), **{"class": cls})
        if cls == PRIORITY_CLASSES[0]:
            continue
        registry.counter("tpu_inf_class_preempted_total",
                         "Running requests of this class preempted back "
                         "to their lane by an interactive arrival",
                         fn=lambda c=cls: class_preempted(c),
                         **{"class": cls})
        registry.gauge("tpu_inf_class_deferred",
                       "Requests currently parked in this class's "
                       "deferred admission lane",
                       fn=lambda c=cls: float(class_deferred(c)),
                       **{"class": cls})


def register_fabric(registry: Registry, pool) -> None:
    """THE fleet KV-fabric series registration (README "KV fabric"),
    shared by both fleet backends so their /metrics surfaces cannot
    drift. ``pool`` is a server.kv_fabric.FabricPool; every series is
    an fn= read-through over its GIL-atomic counters — router-side
    state, so the series survive worker restarts without a carry."""
    registry.counter("tpu_inf_fabric_hits_total",
                     "Fabric pool pages served to a replica's host tier "
                     "(crc-verified before adoption)",
                     fn=lambda: pool.hits)
    registry.counter("tpu_inf_fabric_misses_total",
                     "Fabric lookups that ended short of the requested "
                     "chain (absent or corrupt entry)",
                     fn=lambda: pool.misses)
    registry.counter("tpu_inf_fabric_puts_total",
                     "Pages published into the fabric pool (supersedes "
                     "included)", fn=lambda: pool.puts)
    registry.counter("tpu_inf_fabric_evictions_total",
                     "Fabric pool LRU capacity evictions",
                     fn=lambda: pool.evictions)
    registry.gauge("tpu_inf_fabric_pages_used",
                   "Serialized KV pages resident in the fabric pool",
                   fn=lambda: float(pool.used))
    registry.gauge("tpu_inf_fabric_bytes_used",
                   "Bytes of serialized KV resident in the fabric pool",
                   fn=lambda: float(pool.bytes_used))


def named_program(name: str, fn: Callable) -> Callable:
    """``fn`` under a stable name for ``jax.jit``: step programs built
    from bound methods, partials and lambdas all show as
    ``jit__unknown`` in a profile. One name per role, not per shape
    (the name is part of the persistent compile cache's key)."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


# One process-wide flag: a jax.profiler capture is running. The profiler
# is per-process (all in-process replicas share one jax runtime), so the
# flag is too. While it is set every LoopClock phase visit is also a
# ``tpu_inf/<phase>`` TraceAnnotation and every device dispatch a
# ``tpu_inf/dispatch`` one, so the .xplane.pb carries the program's own
# spans on the profiler's clock, in the dispatching thread's line. Off a
# capture the cost is this one flag test per phase change.
_profile_capturing = False


def set_profile_capturing(on: bool) -> None:
    """Set by whoever starts/stops a jax.profiler capture
    (capture_jax_profile, POST /debug/profile's legacy start/stop)."""
    global _profile_capturing
    _profile_capturing = bool(on)


def profile_capturing() -> bool:
    return _profile_capturing


def capture_jax_profile(profile_dir: str, replica: int, seconds: float,
                        tel: "EngineTelemetry") -> Dict[str, Any]:
    """THE jax.profiler capture body behind POST /debug/profile, shared
    by the worker's profile RPC verb and the in-process group: clamp,
    trace into a per-replica dir under the OPERATOR's profile_dir
    (never a client-chosen path), return where it landed. Serving
    continues while the profiler runs — that is the point.

    ``tel`` is the traced replica's telemetry: its loop clock is read
    right after the trace starts and right before it stops, and
    ``"loop"`` is the delta of every ``tpu_inf_loop_*`` family and the
    two dispatch counters over those seconds, with the seconds
    themselves as ``loop_wall_s`` — the program's statement about the
    interval the device trace covers (absent with telemetry off)."""
    import jax

    seconds = min(max(0.1, float(seconds)), 60.0)
    trace_dir = os.path.join(profile_dir, f"replica{int(replica)}")
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    set_profile_capturing(True)
    before = tel.loop_snapshot()
    try:
        time.sleep(seconds)
    finally:
        after = tel.loop_snapshot()
        set_profile_capturing(False)
        jax.profiler.stop_trace()
    out = {"dir": trace_dir, "seconds": seconds, "replica": int(replica)}
    if after:
        out["loop"] = {k: v - before.get(k, 0.0) for k, v in after.items()}
    return out


def emit_build_info(registry: Registry, *, device: Dict[str, Any],
                    fleet: str = "", kv_quant: str = "",
                    spec_mode: str = "", routing: str = "") -> None:
    """The ``tpu_inf_build_info`` info-gauge (constant 1; the labels
    are the payload) every registry emits so dashboards can join series
    across replicas and restarts. ``device`` is an engine's
    ``device_info()``: the backend, device kind and attention backend
    the engine REALLY runs on, so a scrape says which chip its numbers
    came from. Label VALUES are config plus hardware — a worker restart
    re-mints the identical series, so the restart carry never sees a
    label change."""
    from tpu_inference import __version__
    registry.gauge(
        "tpu_inf_build_info",
        "Build/config info gauge (constant 1; the labels carry the "
        "version and serving configuration for dashboard joins)",
        fn=lambda: 1.0,
        version=__version__, backend=device.get("platform") or "unknown",
        device_kind=device.get("kind") or "unknown",
        attn_backend=device.get("attn_backend") or "unknown",
        fleet=fleet or "none", kv_quant=kv_quant or "none",
        spec_mode=spec_mode or "off", routing=routing or "none")


# ---------------------------------------------------------------------------
# Step ledger + roofline attribution (README "Performance attribution").
#
# The phase histograms say how LONG dispatches take; the step ledger
# says WHY. Every engine dispatch pushes one fixed-shape record into an
# allocation-light ring; an analytic cost model (FLOPs from the
# architecture config, HBM bytes from weight bytes per device iteration
# + KV pages touched at the active kv_quant) converts each record into
# achieved FLOP/s and bytes/s, and windowed aggregation yields one
# bottleneck verdict per step kind: compute-bound, HBM-bound, or
# host-bound (staging + bubble dominate the dispatch wall).
# ---------------------------------------------------------------------------

STEP_KINDS = ("prefill_chunk", "decode", "hybrid", "spec_verify")

# Record layout (one tuple per dispatch; field order is the wire shape
# the flight recorder and /debug/steps serialize):
STEP_FIELDS = (
    "ts",             # unix seconds the record was pushed (≈ sync time)
    "kind",           # one of STEP_KINDS
    "rung",           # compiled batch-ladder rung dispatched (0=prefill)
    "slots",          # decode lanes occupied in the dispatch
    "tokens",         # tokens GENERATED (the MFU gauge's unit)
    "chunk_tokens",   # prompt tokens processed (prefill/hybrid chunk)
    "steps",          # device loop iterations (fused-K; weights stream
                      # from HBM once per iteration)
    "device_s",       # device wall: from the call's enqueue, or the
                      # readback before it if later (it ran behind what
                      # was in flight), to its own readback
    "staging_s",      # host batch-staging wall (_stage_batch micro)
    "bubble_s",       # host gap before the dispatch (device-idle
                      # exposure while lanes were active)
    "kv_read_tokens",  # Σ (query position, context token) pairs attended
    "kv_swap_bytes",  # host<->device KV tier traffic since last record
    "spec_accepted",  # speculative positions accepted (spec_verify)
    "compile_event",  # 1 = first dispatch of this rung/bucket (compile)
    # Appended (positional readers of the fields above do not move):
    "seq",            # monotone dispatch number (the tpu_inf/dispatch
                      # annotation's ``seq``: joins ledger to trace)
    "t_enqueue",      # unix: the jitted call began (program enqueued)
    "t_done",         # unix: the host first observed its result (a
                      # readback of it or of a later program); 0 = not
                      # yet observed
    "layer_passes",   # layer applications the dispatch ran: steps x KV
                      # slots (layers, times the passes of a looped
                      # stack: each reads the layer's weights once)
)
_I_DEVICE_S = STEP_FIELDS.index("device_s")
_I_SEQ = STEP_FIELDS.index("seq")


class StepLedger:
    """Fixed-depth ring of per-dispatch step records.

    ``push`` is the hot-path write: one tuple build + one list store +
    one int add (GIL-atomic, same stance as the metric primitives); no
    locks, no allocation growth. Readers copy the ring first, so a
    concurrent push can at worst duplicate-or-miss the newest record,
    never tear one."""

    __slots__ = ("depth", "_ring", "_n")

    def __init__(self, depth: int = 256):
        self.depth = max(8, int(depth))
        self._ring: List[Optional[tuple]] = [None] * self.depth
        self._n = 0

    def push(self, kind: str, rung: int, slots: int, tokens: int,
             chunk_tokens: int, steps: int, device_s: float,
             staging_s: float, bubble_s: float, kv_read_tokens: int,
             kv_swap_bytes: float, spec_accepted: int,
             compile_event: bool, seq: int = 0, t_enqueue: float = 0.0,
             t_done: float = 0.0, layer_passes: int = 0) -> None:
        self._ring[self._n % self.depth] = (
            time.time(), kind, int(rung), int(slots), int(tokens),
            int(chunk_tokens), int(steps), float(device_s),
            float(staging_s), float(bubble_s), int(kv_read_tokens),
            float(kv_swap_bytes), int(spec_accepted),
            1 if compile_event else 0, int(seq), float(t_enqueue),
            float(t_done), int(layer_passes))
        self._n += 1

    def settle(self, seq: int, t_done: float) -> bool:
        """Dispatch ``seq`` was pushed at enqueue (a prefill chunk whose
        token nobody reads back at once); its result has now been
        observed: fill ``t_done`` and make ``device_s`` the true
        ``t_done - t_enqueue``. Looks at the newest records only — a
        chunk settles within a dispatch or two."""
        n = self._n
        for k in range(1, min(n, self.depth, 32) + 1):
            i = (n - k) % self.depth
            r = self._ring[i]
            if r is not None and r[_I_SEQ] == seq:
                self._ring[i] = (r[:_I_DEVICE_S]
                                 + (max(0.0, t_done - r[_I_SEQ + 1]),)
                                 + r[_I_DEVICE_S + 1:_I_SEQ + 2]
                                 + (float(t_done),) + r[_I_SEQ + 3:])
                return True
        return False

    @property
    def count(self) -> int:
        return self._n

    @property
    def overflowed(self) -> bool:
        return self._n > self.depth

    def records(self) -> List[tuple]:
        """Resident records, oldest first (point-in-time ring copy)."""
        ring, n = list(self._ring), self._n
        if n <= self.depth:
            return [r for r in ring[:n] if r is not None]
        i = n % self.depth
        return [r for r in ring[i:] + ring[:i] if r is not None]

    def snapshot(self) -> List[Dict[str, Any]]:
        """JSON-able dump (flight-recorder payload)."""
        return [dict(zip(STEP_FIELDS, r)) for r in self.records()]


class _NullLedger:
    """No-op ledger when telemetry is disabled (shared singleton, the
    NULL_METRIC stance): push is one attribute lookup + empty call."""

    __slots__ = ()
    depth = 0
    count = 0
    overflowed = False

    def push(self, *a, **k) -> None:
        pass

    def settle(self, seq: int, t_done: float) -> bool:
        return False

    def records(self) -> List[tuple]:
        return []

    def snapshot(self) -> List[Dict[str, Any]]:
        return []


NULL_LEDGER = _NullLedger()


class StepCostModel:
    """Analytic per-record FLOPs + HBM bytes from the architecture
    config — no device counters needed. The peaks they are rated
    against are the chip's published ones (engine/autosize.py
    CHIP_SPECS); on the CPU both are None and every share, verdict and
    MFU figure below reads "not measured" instead of being computed
    against a chip that is not there.

    - matmul FLOPs: 2 x params per token position processed (generated
      tokens + prompt chunk tokens).
    - attention FLOPs: 4 x n_heads x head_dim per layer per (query
      position, context token) pair (QK^T + AV, 2 multiply-adds each).
    - HBM bytes: the weight bytes a step READS once per device loop
      iteration (fused-K decode streams the weights K times; a looped
      stack reads its layers once a pass, so more than it stores) + KV
      bytes for every context token attended (at the active kv_quant's
      per-token footprint) + KV bytes written for new positions +
      host<->device swap traffic.
    - a model whose layers differ in kind: ``n_layers`` / ``n_heads`` /
      ``kv_token_bytes`` are the FULL kind's, and the window kind's
      layers (``window_layers`` of ``window_heads``, ``kv_window_token_
      bytes`` a token) count a query's pairs at min(context, window):
      a record's pairs are capped at window x its query positions.
    - ``full_readers``: layers that READ the full kind's pool a token
      (1: each layer its own slots; a model whose cross layers read one
      layer's K / V: that layer and its readers). Each reads every
      visible token; the writes are the one layer's.
    - ``state_bytes``: bytes of per-sequence state (state-space layers)
      a lane's step reads and writes back, whether or not it advances.
    """

    __slots__ = ("n_params", "n_layers", "n_heads", "head_dim",
                 "weight_bytes", "kv_token_bytes", "peak_flops",
                 "peak_hbm_bw", "window", "window_layers", "window_heads",
                 "kv_window_token_bytes", "full_readers", "state_bytes")

    def __init__(self, *, n_params: int, n_layers: int, n_heads: int,
                 head_dim: int, weight_bytes: int, kv_token_bytes: int,
                 peak_flops: Optional[float],
                 peak_hbm_bw: Optional[float], window: int = 0,
                 window_layers: int = 0, window_heads: int = 0,
                 kv_window_token_bytes: int = 0, full_readers: int = 1,
                 state_bytes: int = 0):
        # ``n_params``: parameters a token position multiplies through
        # (all of a dense model's; a routed model's ACTIVE ones; a
        # looped stack's layers once a pass). ``n_layers``: attention
        # applications a token (layers x passes). ``weight_bytes``: what
        # one step reads.
        # ``head_dim``: an attention pair costs 4 x n_heads x head_dim
        # FLOPs (latent attention: (latent entry + latent rank) / 2).
        self.n_params = int(n_params)
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.weight_bytes = int(weight_bytes)
        self.kv_token_bytes = int(kv_token_bytes)
        self.peak_flops = peak_flops
        self.peak_hbm_bw = peak_hbm_bw
        self.window = int(window)
        self.window_layers = int(window_layers)
        self.window_heads = int(window_heads)
        self.kv_window_token_bytes = int(kv_window_token_bytes)
        self.full_readers = int(full_readers)
        self.state_bytes = int(state_bytes)

    @classmethod
    def from_engine(cls, engine) -> "StepCostModel":
        from tpu_inference.engine import autosize
        mcfg, ecfg = engine.model_cfg, engine.engine_cfg
        chip = autosize.chip_spec()
        from tpu_inference.models.registry import family_fn
        # A routed model: a token multiplies through its ACTIVE
        # parameters, not every expert the chip stores; latent attention
        # has its own cost per pair. The family's module says.
        own = family_fn(mcfg, "param_count")
        n_params = own(mcfg, True) if own else engine.n_params
        pair = family_fn(mcfg, "attn_pair_dim")
        head_dim = pair(mcfg) if pair else mcfg.head_dim
        kinds = {}
        if mcfg.layer_types:
            kinds = dict(
                window=mcfg.sliding_window,
                window_layers=len(mcfg.kind_layers("window")),
                window_heads=mcfg.window_n_heads or mcfg.n_heads,
                kv_window_token_bytes=autosize.kv_bytes_per_token(
                    mcfg, ecfg.kv_quant, kind="window"),
                full_readers=(len(mcfg.kind_layers("full"))
                              + len(mcfg.kind_layers("cross")))
                // max(1, len(mcfg.kind_layers("full"))),
                state_bytes=mcfg.state_bytes_per_seq())
        full = len(mcfg.kind_layers("full")) if kinds else 0
        return cls(n_params=n_params,
                   n_layers=(full * kinds["full_readers"] if kinds else
                             mcfg.n_kv_slots),
                   n_heads=mcfg.n_heads, head_dim=head_dim,
                   weight_bytes=autosize.weight_read_bytes(mcfg, ecfg.quant),
                   kv_token_bytes=autosize.kv_bytes_per_token(
                       mcfg, ecfg.kv_quant,
                       kind="full" if kinds else None),
                   peak_flops=chip and chip.peak_bf16_flops,
                   peak_hbm_bw=chip and chip.hbm_bw, **kinds)

    def flops(self, rec: tuple) -> float:
        positions = rec[4] + rec[5]          # tokens + chunk_tokens
        return (2.0 * self.n_params * positions
                + 4.0 * self.head_dim
                * (self.n_layers * self.n_heads * rec[10]  # kv_read_tokens
                   + self.window_layers * self.window_heads
                   * min(rec[10], self.window * positions)))

    def hbm_bytes(self, rec: tuple) -> float:
        positions = rec[4] + rec[5]
        lanes = rec[2] or rec[3]             # rung (decode) or slots
        return (float(self.weight_bytes) * max(1, rec[6])   # steps
                + float(self.kv_token_bytes)
                * (rec[10] * self.full_readers + positions)
                + float(self.kv_window_token_bytes)
                * (min(rec[10], self.window * positions) + positions)
                + 2.0 * self.state_bytes * lanes * max(1, rec[6])
                + rec[11])                   # kv_swap_bytes


NOT_MEASURED = "not measured"


def _finalize_kind(agg: Dict[str, Any], peak_flops: Optional[float],
                   peak_hbm_bw: Optional[float]) -> Dict[str, Any]:
    """Derive achieved rates, roofline fractions, and the bottleneck
    verdict from one kind's raw sums — shared by the per-replica report
    and the fleet merge so the two can never disagree on semantics.
    Without peaks (no chip) the fractions are absent and the verdict is
    NOT_MEASURED."""
    device_s = agg["device_s"]
    host_s = agg["staging_s"] + agg["bubble_s"]
    out = dict(agg)
    out["host_s"] = round(host_s, 6)
    if device_s > 0:
        out["achieved_flops_per_s"] = round(agg["flops"] / device_s, 3)
        out["achieved_bytes_per_s"] = round(agg["hbm_bytes"] / device_s, 3)
    else:
        out["achieved_flops_per_s"] = 0.0
        out["achieved_bytes_per_s"] = 0.0
    host_frac = host_s / max(host_s + device_s, 1e-12)
    out["host_frac"] = round(host_frac, 6)
    if not (peak_flops and peak_hbm_bw):
        out["verdict"] = NOT_MEASURED
    else:
        compute_frac = out["achieved_flops_per_s"] / peak_flops
        hbm_frac = out["achieved_bytes_per_s"] / peak_hbm_bw
        out["compute_frac"] = round(compute_frac, 6)
        out["hbm_frac"] = round(hbm_frac, 6)
        if host_frac > 0.5:
            out["verdict"] = "host-bound"
        elif compute_frac >= hbm_frac:
            out["verdict"] = "compute-bound"
        else:
            out["verdict"] = "hbm-bound"
    for k in ("device_s", "staging_s", "bubble_s", "flops", "hbm_bytes",
              "kv_swap_bytes"):
        out[k] = round(out[k], 6)
    return out


def roofline_report(ledger, model: StepCostModel, *,
                    mfu_gauge: Optional[float] = None,
                    window_s: float = 60.0,
                    now: Optional[float] = None,
                    since: Optional[float] = None,
                    until: Optional[float] = None,
                    records: bool = False) -> Dict[str, Any]:
    """One replica's step-attribution report: per-kind roofline sums +
    bottleneck verdicts over the trailing window, per-rung occupancy
    and the MFU gauge's reading.

    ``since`` / ``until`` (unix seconds, on the records' ``ts``) choose
    the interval instead of the trailing ``window_s``; ``records`` adds
    the interval's per-dispatch records themselves (STEP_FIELDS dicts),
    so a caller can have exactly its own measured window."""
    now = time.time() if now is None else now
    recs = ledger.records()
    lo = now - window_s if since is None else since
    hi = float("inf") if until is None else until
    window = [r for r in recs if lo <= r[0] <= hi]
    kinds: Dict[str, Dict[str, Any]] = {}
    rungs: Dict[str, Dict[str, float]] = {}
    for r in window:
        agg = kinds.get(r[1])
        if agg is None:
            agg = kinds[r[1]] = {
                "records": 0, "tokens": 0, "chunk_tokens": 0,
                "device_s": 0.0, "staging_s": 0.0, "bubble_s": 0.0,
                "flops": 0.0, "hbm_bytes": 0.0, "kv_swap_bytes": 0.0,
                "kv_read_tokens": 0, "spec_accepted": 0,
                "compile_events": 0}
        agg["records"] += 1
        agg["tokens"] += r[4]
        agg["chunk_tokens"] += r[5]
        agg["device_s"] += r[7]
        agg["staging_s"] += r[8]
        agg["bubble_s"] += r[9]
        agg["kv_read_tokens"] += r[10]
        agg["kv_swap_bytes"] += r[11]
        agg["spec_accepted"] += r[12]
        agg["compile_events"] += r[13]
        agg["flops"] += model.flops(r)
        agg["hbm_bytes"] += model.hbm_bytes(r)
        if r[1] != "prefill_chunk":
            ra = rungs.setdefault(str(r[2]), {"dispatches": 0,
                                              "slots_sum": 0})
            ra["dispatches"] += 1
            ra["slots_sum"] += r[3]
    kinds = {k: _finalize_kind(v, model.peak_flops, model.peak_hbm_bw)
             for k, v in kinds.items()}
    occupancy = {rung: {"dispatches": ra["dispatches"],
                        "mean_slots": round(ra["slots_sum"]
                                            / max(ra["dispatches"], 1), 2)}
                 for rung, ra in rungs.items()}
    out = {
        "enabled": True,
        "ts": round(now, 3),
        "window_s": window_s if since is None and until is None
        else round(min(hi, now) - lo, 3),
        "records_window": len(window),
        "records_total": ledger.count,
        "ledger_depth": ledger.depth,
        "truncated": bool(ledger.overflowed),
        "peaks": {"flops_per_s": model.peak_flops,
                  "hbm_bytes_per_s": model.peak_hbm_bw},
        "kinds": kinds,
        "rung_occupancy": occupancy,
        "compile_events": sum(r[13] for r in window),
        "mfu": {"gauge": mfu_gauge},
    }
    if records:
        out["records"] = [dict(zip(STEP_FIELDS, r)) for r in window]
    return out


# Raw per-kind sums merge_steps_reports re-accumulates before
# re-deriving the verdict fields (which do not sum).
_KIND_SUM_FIELDS = ("records", "tokens", "chunk_tokens", "device_s",
                    "staging_s", "bubble_s", "flops", "hbm_bytes",
                    "kv_swap_bytes", "kv_read_tokens", "spec_accepted",
                    "compile_events")


def merge_steps_reports(reports: Sequence[Optional[Dict[str, Any]]]
                        ) -> Dict[str, Any]:
    """Fleet-merged step attribution from per-replica reports: per-kind
    raw sums re-finalized (verdicts recomputed over the pooled window —
    fractions and verdicts do not average), occupancy pooled, the MFU
    gauge averaged across replicas (MFU is a per-chip utilization; the
    fleet runs dp chips)."""
    reports = [r for r in reports if r and r.get("enabled")]
    if not reports:
        return {"enabled": False}
    peaks = reports[0].get("peaks") or {}
    peak_flops = peaks.get("flops_per_s")
    peak_bw = peaks.get("hbm_bytes_per_s")
    kinds: Dict[str, Dict[str, Any]] = {}
    rungs: Dict[str, Dict[str, float]] = {}
    for rep in reports:
        for k, v in (rep.get("kinds") or {}).items():
            agg = kinds.setdefault(k, {f: 0 for f in _KIND_SUM_FIELDS})
            for f in _KIND_SUM_FIELDS:
                agg[f] += v.get(f, 0)
        for rung, ra in (rep.get("rung_occupancy") or {}).items():
            dst = rungs.setdefault(rung, {"dispatches": 0,
                                          "slots_sum": 0.0})
            dst["dispatches"] += ra.get("dispatches", 0)
            dst["slots_sum"] += (ra.get("mean_slots", 0)
                                 * ra.get("dispatches", 0))
    kinds = {k: _finalize_kind(v, peak_flops, peak_bw)
             for k, v in kinds.items()}
    occupancy = {rung: {"dispatches": int(ra["dispatches"]),
                        "mean_slots": round(ra["slots_sum"]
                                            / max(ra["dispatches"], 1), 2)}
                 for rung, ra in rungs.items()}
    gauges = [r["mfu"].get("gauge") for r in reports
              if (r.get("mfu") or {}).get("gauge") is not None]
    mfu = {"gauge": round(sum(gauges) / len(gauges), 12) if gauges
           else None}
    return {
        "enabled": True,
        "replicas_merged": len(reports),
        "window_s": max(r.get("window_s", 0) for r in reports),
        "records_window": sum(r.get("records_window", 0)
                              for r in reports),
        "records_total": sum(r.get("records_total", 0) for r in reports),
        "truncated": any(r.get("truncated") for r in reports),
        "peaks": {"flops_per_s": peak_flops, "hbm_bytes_per_s": peak_bw},
        "kinds": kinds,
        "rung_occupancy": occupancy,
        "compile_events": sum(r.get("compile_events", 0)
                              for r in reports),
        "mfu": mfu,
    }


# ---------------------------------------------------------------------------
# Crash flight recorder (README "Performance attribution"). A bounded
# per-replica blackbox/ directory of JSON captures — last-N step
# records + recent spans + resolved config + stats — written on watchdog
# trip, step_error, SIGTERM, and atexit, plus a periodic heartbeat
# capture that survives kill -9 (tmp+rename keeps every file whole).
# The fleet monitor harvests dead workers' directories and serves the
# index at GET /debug/blackbox. Every write path swallows exceptions:
# the recorder must never take serving down with it.
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Per-replica crash capture sink under ``{root}/replica-{i}/``.

    ``capture(trigger)`` writes ``capture-{seq:06d}-{trigger}.json``
    atomically and prunes beyond the retention cap (oldest first);
    ``maybe_periodic()`` refreshes a single ``periodic.json`` heartbeat
    at most every ``periodic_interval_s`` — the evidence a kill -9
    leaves behind. Per-trigger rate limiting stops a step_error storm
    from churning the whole retention window."""

    def __init__(self, root_dir: str, replica: int = 0, *,
                 retain: int = 8, config: Optional[dict] = None,
                 steps_fn: Optional[Callable[[], list]] = None,
                 spans_fn: Optional[Callable[[], list]] = None,
                 stats_fn: Optional[Callable[[], dict]] = None,
                 periodic_interval_s: float = 10.0):
        self.root = root_dir
        self.replica = int(replica)
        self.dir = os.path.join(root_dir, f"replica-{self.replica}")
        self.retain = max(1, int(retain))
        self.config = dict(config or {})
        self.steps_fn = steps_fn
        self.spans_fn = spans_fn
        self.stats_fn = stats_fn
        self.periodic_interval_s = max(0.5, float(periodic_interval_s))
        self._last_periodic = 0.0
        # Heartbeats taken on the caller's (engine) thread, and those
        # among them skipped because the previous write was still out.
        self.beats = 0
        self.beats_skipped = 0
        self._beat_thread: Optional[threading.Thread] = None
        self._last_by_trigger: Dict[str, float] = {}
        self._lock = threading.Lock()
        self._seq = 0
        try:
            os.makedirs(self.dir, exist_ok=True)
            for fname in os.listdir(self.dir):
                if fname.startswith("capture-"):
                    try:
                        self._seq = max(self._seq,
                                        int(fname.split("-")[1]) + 1)
                    except (ValueError, IndexError):
                        pass
            # A heartbeat left behind by a prior incarnation IS the
            # kill -9 postmortem: archive it under a sequence number
            # before this process's first beat overwrites it.
            prior = os.path.join(self.dir, "periodic.json")
            if os.path.exists(prior):
                dest = os.path.join(
                    self.dir, f"capture-{self._seq:06d}-postmortem.json")
                try:
                    with open(prior) as f:
                        payload = json.load(f)
                    payload["trigger"] = "postmortem"
                    self._write(dest, payload)
                    os.remove(prior)
                except (OSError, ValueError):
                    os.replace(prior, dest)
                self._seq += 1
        except OSError:
            pass

    def _steps_raw(self) -> list:
        """The step section as ``steps_fn`` hands it over: the ledger's
        raw record tuples (a ring copy — the one part of a capture that
        has to be taken on the engine thread) or ready dicts."""
        try:
            return self.steps_fn() if self.steps_fn is not None else []
        except Exception:
            return []

    def _payload(self, trigger: str,
                 steps: Optional[list] = None) -> Dict[str, Any]:
        payload: Dict[str, Any] = {
            "ts": round(time.time(), 3), "replica": self.replica,
            "pid": os.getpid(), "trigger": trigger,
            "config": self.config}
        if steps is None:
            steps = self._steps_raw()
        payload["steps"] = [dict(zip(STEP_FIELDS, r))
                            if isinstance(r, tuple) else r for r in steps]
        for key, fn, empty in (("spans", self.spans_fn, []),
                               ("stats", self.stats_fn, {})):
            try:
                payload[key] = fn() if fn is not None else empty
            except Exception:
                payload[key] = empty
        return payload

    def _write(self, path: str, payload: Dict[str, Any]) -> None:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, default=str)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def capture(self, trigger: str,
                min_interval_s: float = 1.0) -> Optional[str]:
        """Write one capture; returns its path (None = rate-limited or
        failed — the recorder never raises into serving code)."""
        try:
            with self._lock:
                now = time.time()
                if (now - self._last_by_trigger.get(trigger, -1e9)
                        < min_interval_s):
                    return None
                self._last_by_trigger[trigger] = now
                seq = self._seq
                self._seq += 1
            path = os.path.join(self.dir,
                                f"capture-{seq:06d}-{trigger}.json")
            self._write(path, self._payload(trigger))
            self._prune()
            log_event("blackbox_capture", trigger=trigger, path=path,
                      replica=self.replica)
            return path
        except Exception:
            return None

    def _prune(self) -> None:
        caps = sorted(f for f in os.listdir(self.dir)
                      if f.startswith("capture-") and f.endswith(".json"))
        for fname in caps[:-self.retain]:
            try:
                os.unlink(os.path.join(self.dir, fname))
            except OSError:
                pass

    def periodic_due(self) -> bool:
        """Scheduler-loop hook, every iteration: is a heartbeat due
        (one clock read and a compare)?"""
        return time.time() - self._last_periodic >= self.periodic_interval_s

    def maybe_periodic(self) -> bool:
        """Refresh the heartbeat capture at most once per interval.
        Only the ledger's ring copy happens here, on the caller's
        (engine) thread; building the dicts, the spans, the stats,
        ``json.dump``, ``fsync`` and the rename run on a daemon thread,
        one write outstanding at most — a beat that finds the previous
        one still out is skipped and counted. True = a beat was due."""
        if not self.periodic_due():
            return False
        self._last_periodic = time.time()
        self.beats += 1
        if self._beat_thread is not None and self._beat_thread.is_alive():
            self.beats_skipped += 1
            return True
        steps = self._steps_raw()
        self._beat_thread = threading.Thread(
            target=self._write_beat, args=(steps,),
            name="blackbox-heartbeat", daemon=True)
        self._beat_thread.start()
        return True

    def _write_beat(self, steps: list) -> None:
        try:
            self._write(os.path.join(self.dir, "periodic.json"),
                        self._payload("periodic", steps))
        except Exception:
            pass

    def join_beat(self, timeout: float = 5.0) -> bool:
        """Wait for the outstanding heartbeat write, if any; True when
        none is left."""
        t = self._beat_thread
        if t is not None:
            t.join(timeout)
            return not t.is_alive()
        return True

    def install_atexit(self) -> None:
        import atexit
        atexit.register(lambda: self.capture("atexit",
                                             min_interval_s=0.0))


def blackbox_index(root_dir: str) -> Dict[str, Any]:
    """Scan a blackbox root for per-replica captures (newest first) —
    the GET /debug/blackbox body, shared by both fleet backends. Each
    entry carries enough to triage without downloading the capture:
    trigger, timestamp, pid, and payload section sizes."""
    out: Dict[str, Any] = {"dir": root_dir, "captures": []}
    if not root_dir or not os.path.isdir(root_dir):
        return out
    for sub in sorted(os.listdir(root_dir)):
        rdir = os.path.join(root_dir, sub)
        if not (sub.startswith("replica-") and os.path.isdir(rdir)):
            continue
        try:
            replica = int(sub.split("-", 1)[1])
        except ValueError:
            continue
        try:
            fnames = sorted(os.listdir(rdir))
        except OSError:
            continue
        for fname in fnames:
            if not fname.endswith(".json"):
                continue
            path = os.path.join(rdir, fname)
            entry: Dict[str, Any] = {"replica": replica, "file": fname,
                                     "path": path}
            try:
                with open(path) as f:
                    payload = json.load(f)
                entry.update({
                    "trigger": payload.get("trigger"),
                    "ts": payload.get("ts"),
                    "pid": payload.get("pid"),
                    "n_steps": len(payload.get("steps") or ()),
                    "n_spans": len(payload.get("spans") or ()),
                    "has_config": bool(payload.get("config")),
                    "has_stats": bool(payload.get("stats")),
                })
            except (OSError, ValueError):
                entry["error"] = "unreadable"
            out["captures"].append(entry)
    out["captures"].sort(key=lambda e: e.get("ts") or 0.0, reverse=True)
    return out


def attach_flight_recorder(tel: "EngineTelemetry", root_dir: str,
                           replica: int, *, retain: int = 8,
                           config: Optional[dict] = None,
                           stats_fn: Optional[Callable[[], dict]] = None
                           ) -> Optional[FlightRecorder]:
    """Bind a FlightRecorder to one engine's telemetry bundle (shared
    by the subprocess worker and the in-process fleet, so the payload
    shape cannot drift between backends). No-op when the operator left
    ``blackbox_dir`` empty or telemetry is disabled."""
    if not root_dir or not tel.enabled:
        return None
    recorder = tel.recorder

    def spans_fn() -> list:
        spans: list = []
        for tid, trace in recorder.recent_traces(32).items():
            spans.extend(trace)
        spans.extend(recorder.maintenance_spans(32))
        return spans

    fr = FlightRecorder(root_dir, replica, retain=retain, config=config,
                        steps_fn=lambda: tel.step_ledger.records(),
                        spans_fn=spans_fn, stats_fn=stats_fn)
    tel.flight = fr
    fr.install_atexit()
    return fr


def attach_router_flight_recorder(
        root_dir: str, *, retain: int = 8,
        config: Optional[dict] = None,
        stats_fn: Optional[Callable[[], dict]] = None,
        spans_fn: Optional[Callable[[], list]] = None,
        ) -> Optional[FlightRecorder]:
    """Router-side (process-fleet) capture sink: replica -1, so its
    ``replica--1/`` directory sorts apart from the workers' in the
    shared blackbox root. Poison quarantines and corrupt-KV rejections
    are router verdicts — the evidence (which workers failed, what the
    supervision counters said) lives here, not in any one worker's
    blackbox. No-op when the operator left ``blackbox_dir`` empty."""
    if not root_dir:
        return None
    return FlightRecorder(root_dir, -1, retain=retain, config=config,
                          spans_fn=spans_fn, stats_fn=stats_fn)


# ---------------------------------------------------------------------------
# Loop phase clock (README "Observability": loop phases). ONE clock for
# the engine thread: ``enter(phase)`` closes the phase being left and
# opens the next, so the phases are an exclusive and complete partition
# of the loop's wall — every idle gap, stall and host cost has a name
# from inside the program, with the profiler off, for the whole run.
# ---------------------------------------------------------------------------

# phase -> its /metrics family (no labels: scrape parsers sum labels
# away, so each phase is a family of its own).
LOOP_FAMILIES = {
    # nothing to do (the work-event wait)
    "idle": "tpu_inf_loop_idle_seconds_total",
    # admission passes, page accounting, requeues
    "admit": "tpu_inf_loop_admit_seconds_total",
    # prefix-cache lookup for an admitted prompt
    "prefix_lookup": "tpu_inf_loop_prefix_lookup_seconds_total",
    # host arrays + device_put for the next dispatch
    "stage": "tpu_inf_loop_stage_seconds_total",
    # inside the jitted call (returns once enqueued)
    "enqueue": "tpu_inf_loop_enqueue_seconds_total",
    # blocked in a readback of a dispatched program
    "device_wait": "tpu_inf_loop_device_wait_seconds_total",
    # token callbacks to the HTTP side and the wake-up that carries them
    "deliver": "tpu_inf_loop_deliver_seconds_total",
    # finish: release pages/slot, histograms, spans
    "reap": "tpu_inf_loop_reap_seconds_total",
    # host-tier offload / restore / imports
    "swap": "tpu_inf_loop_swap_seconds_total",
    # flight recorder's periodic beat (ring copy)
    "heartbeat": "tpu_inf_loop_heartbeat_seconds_total",
    # everything between the named sites
    "other": "tpu_inf_loop_other_seconds_total",
}
LOOP_PHASES = tuple(LOOP_FAMILIES)
# A single visit (not idle) longer than this is a stall: counted, summed
# and logged once with what the loop was doing.
LOOP_STALL_S = 1.0
# Phases in which an empty device is not the host's doing: nothing to
# run, or the host is itself waiting for the device.
_NOT_STARVING = frozenset(("idle", "device_wait"))
# The other nine: host work. Starved seconds and seconds off the CPU are
# kept for these. phase -> the family of its starved seconds (unlabelled,
# as the phases' own; spelled out so the README's catalog can be held to
# them).
STARVED_FAMILIES = {
    "admit": "tpu_inf_loop_starved_admit_seconds_total",
    "prefix_lookup": "tpu_inf_loop_starved_prefix_lookup_seconds_total",
    "stage": "tpu_inf_loop_starved_stage_seconds_total",
    "enqueue": "tpu_inf_loop_starved_enqueue_seconds_total",
    "deliver": "tpu_inf_loop_starved_deliver_seconds_total",
    "reap": "tpu_inf_loop_starved_reap_seconds_total",
    "swap": "tpu_inf_loop_starved_swap_seconds_total",
    "heartbeat": "tpu_inf_loop_starved_heartbeat_seconds_total",
    "other": "tpu_inf_loop_starved_other_seconds_total",
}
HOST_PHASES = tuple(STARVED_FAMILIES)
assert set(HOST_PHASES) == set(LOOP_PHASES) - _NOT_STARVING
# What the off-CPU account tells apart: the thread's CPU clock is read
# only where a phase of one class follows a phase of another.
_CPU_CLASS = {p: 0 if p in _NOT_STARVING else 2 if p == "stage" else 1
              for p in LOOP_PHASES}
# part of ``stage`` -> its family: an exact partition of the stage phase,
# marked where the work happens (``LoopClock.part``).
STAGE_FAMILIES = {
    # grants, window eviction, preemption: before any array is touched
    "pages": "tpu_inf_loop_stage_pages_seconds_total",
    # writing the host arrays of a dispatch, and their copies
    "fill": "tpu_inf_loop_stage_fill_seconds_total",
    # jnp.asarray / device_put of a dispatch's operands, its key, carries
    "put": "tpu_inf_loop_stage_put_seconds_total",
    # whatever of stage no mark covers: a hole shows as a number
    "rest": "tpu_inf_loop_stage_rest_seconds_total",
}
STAGE_PARTS = tuple(STAGE_FAMILIES)


class LoopClock:
    """Exclusive phase partition of one engine thread's wall.

    Driven from ``EngineScheduler.run`` and the engine's dispatch / sync
    sites. ``enter`` costs one clock read and a few float adds; it
    returns the instant, which callers use in place of clock reads of
    their own (staging / bubble / dispatch / sync walls come from the
    same stamps as the phases, so they cannot disagree). The clock
    accrues only between ``start`` and ``stop`` — an engine driven
    directly (tests, offline generate) still gets instants from
    ``enter`` but leaves no open visit behind to grow into a false stall.

    ``starved`` is the program's own statement of "the device sat idle
    because of the host", by what the host was doing: seconds of each
    phase other than idle/device_wait entered while no dispatched
    program was unobserved (nothing in flight) and the scheduler had
    work (``has_work``). ``starved_s`` is their sum.

    ``stage_parts`` partitions the ``stage`` phase: every visit opens in
    ``rest`` and ``part(name)`` switches (one clock read, one add), so
    the parts sum to ``seconds["stage"]`` and unmarked work is a number.

    ``host_offcpu_s`` is the wall of the nine host phases less the
    engine thread's own CPU time in them (waiting for the GIL another
    thread holds, or blocked in the runtime); ``stage_offcpu_s`` the
    same for ``stage`` alone. The thread's CPU clock is a system call
    (5-6 us on the chip's host, where the wall's read is 0.08), so it
    is read only where a run of host phases, or of ``stage``, begins or
    ends: about four times a loop turn.
    """

    def __init__(self, now: Callable[[], float] = time.perf_counter,
                 thread_time: Callable[[], float] = time.thread_time):
        self._now = now
        self._cpu_now = thread_time
        self.seconds: Dict[str, float] = dict.fromkeys(LOOP_PHASES, 0.0)
        self.starved: Dict[str, float] = dict.fromkeys(HOST_PHASES, 0.0)
        self.stage_parts: Dict[str, float] = dict.fromkeys(STAGE_PARTS, 0.0)
        self.host_offcpu_s = 0.0
        self.stage_offcpu_s = 0.0
        self.phase: Optional[str] = None      # None = not running
        self._t = 0.0
        self._cpu_t = self._cpu = 0.0         # wall / thread CPU time at
        #                                       the last CPU-clock read
        self._part: Optional[str] = None      # open part; None = not in stage
        self._pt = 0.0
        self._starving = False
        self._ann = None                      # open TraceAnnotation
        self._part_ann = None                 # ... of a part, nested in it
        self.stalls = 0
        self.stall_s = 0.0
        # Set by the scheduler each iteration: a sequence is active or
        # waiting; and the counts a stall log line carries.
        self.has_work = False
        self.active = 0
        self.waiting = 0
        # Dispatch numbers: the newest program enqueued, the newest whose
        # result the host has observed (programs run in order on the
        # device, so observing N settles everything before it).
        self.dispatched_seq = 0
        self.observed_seq = 0

    # ------------------------------------------------------------ driving

    def start(self) -> float:
        self.phase, self._t = "other", self._now()
        self._cpu_t, self._cpu = self._t, self._cpu_now()
        self._starving = False
        return self._t

    def stop(self) -> None:
        if self.phase is not None:
            self._cpu_run("other", self.enter("other"))
            self.phase = None
        self._close_annotation()

    def now(self) -> float:
        return self._now()

    def enter(self, phase: str) -> float:
        now = self._now()
        prev = self.phase
        if prev is None:                      # not running: only the time
            return now
        dt = now - self._t
        self.seconds[prev] += dt
        if self._starving:
            self.starved[prev] += dt
        if self._part is not None:            # prev is stage
            self.stage_parts[self._part] += now - self._pt
        if _CPU_CLASS[prev] != _CPU_CLASS[phase]:
            self._cpu_run(prev, now)
        if dt > LOOP_STALL_S and prev != "idle":
            self._stall(prev, dt)
        self.phase = phase
        self._t = self._pt = now
        self._part = "rest" if phase == "stage" else None
        self._starving = (self.has_work
                          and self.dispatched_seq <= self.observed_seq
                          and phase not in _NOT_STARVING)
        if _profile_capturing or self._ann is not None:
            self._annotate(phase)
        return now

    def part(self, name: str) -> float:
        """Inside a ``stage`` visit: the work from here on is ``name``
        (one of STAGE_PARTS), until the next mark or the phase's end.
        Outside one (an engine driven directly, another phase) only the
        time. Returns the instant, as ``enter`` does."""
        now = self._now()
        if self._part is not None:
            self.stage_parts[self._part] += now - self._pt
            self._part, self._pt = name, now
            if _profile_capturing or self._part_ann is not None:
                self._annotate_part(name)
        return now

    def dispatched(self, seq: int) -> float:
        """The jitted call for dispatch ``seq`` returned: it is in
        flight. Leaves the enqueue phase; returns the instant."""
        self.dispatched_seq = seq
        return self.enter("other")

    def observed(self, seq: int) -> None:
        """The host read back a result of dispatch ``seq``."""
        if seq > self.observed_seq:
            self.observed_seq = seq

    @property
    def in_flight(self) -> bool:
        return self.dispatched_seq > self.observed_seq

    @property
    def starved_s(self) -> float:
        return sum(self.starved.values())

    # ----------------------------------------------------------- internals

    def _cpu_run(self, prev: str, now: float) -> None:
        """A run of phases of ``prev``'s class ends at ``now``: read the
        thread's CPU clock and, for a run of host phases, add its wall
        less its CPU time to the off-CPU account (to ``stage``'s too
        where the run was one). Signed, run by run: the two clocks are
        not read at one instant, and over many runs that cancels."""
        cpu = self._cpu_now()
        if prev not in _NOT_STARVING:
            off = (now - self._cpu_t) - (cpu - self._cpu)
            self.host_offcpu_s += off
            if prev == "stage":
                self.stage_offcpu_s += off
        self._cpu_t, self._cpu = now, cpu

    def _stall(self, phase: str, dt: float) -> None:
        self.stalls += 1
        self.stall_s += dt
        log_event("loop_stall", level="warning", phase=phase,
                  seconds=round(dt, 4), dispatch=self.dispatched_seq,
                  in_flight=self.in_flight, active=self.active,
                  waiting=self.waiting)

    def _close_part_annotation(self) -> None:
        ann, self._part_ann = self._part_ann, None
        if ann is not None:
            ann.__exit__(None, None, None)

    def _close_annotation(self) -> None:
        self._close_part_annotation()         # nested: the inner one first
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)

    def _annotate(self, phase: str) -> None:
        self._close_annotation()
        if _profile_capturing:
            import jax.profiler
            self._ann = jax.profiler.TraceAnnotation("tpu_inf/" + phase)
            self._ann.__enter__()

    def _annotate_part(self, name: str) -> None:
        """``tpu_inf/stage/<part>`` inside the open ``tpu_inf/stage``
        (a capture that began mid-visit has none open: no part either).
        ``rest`` is the stage annotation's own uncovered time."""
        self._close_part_annotation()
        if _profile_capturing and self._ann is not None and name != "rest":
            import jax.profiler
            self._part_ann = jax.profiler.TraceAnnotation(
                "tpu_inf/stage/" + name)
            self._part_ann.__enter__()

    # ------------------------------------------------------------- export

    def total_s(self) -> float:
        return sum(self.seconds.values())

    def families(self) -> List[Tuple[str, str, Callable[[], float]]]:
        """(family, help, read) of everything the clock exports: one
        family per phase + their sum; starved seconds and their nine
        parts; the parts of stage; off-CPU and stall series."""
        out = [(family,
                f"Engine-loop wall spent in phase '{p}' (exclusive "
                "partition: the phase families sum to "
                "tpu_inf_loop_seconds_total)",
                lambda p=p: self.seconds[p])
               for p, family in LOOP_FAMILIES.items()]
        out.append((
            "tpu_inf_loop_seconds_total",
            "Engine-loop wall accounted by the phase clock (sum of the "
            "tpu_inf_loop_<phase>_seconds_total families)",
            self.total_s))
        out.append((
            "tpu_inf_loop_starved_seconds_total",
            "Loop wall outside idle/device_wait spent while no "
            "dispatched program was in flight and a sequence was "
            "active or waiting (the device idle because of the host)",
            lambda: self.starved_s))
        out += [(family,
                 f"The part of tpu_inf_loop_starved_seconds_total spent "
                 f"in phase '{p}' (the nine parts sum to it)",
                 lambda p=p: self.starved[p])
                for p, family in STARVED_FAMILIES.items()]
        out += [(family,
                 f"The part of tpu_inf_loop_stage_seconds_total spent in "
                 f"'{part}' (exclusive partition of the stage phase)",
                 lambda part=part: self.stage_parts[part])
                for part, family in STAGE_FAMILIES.items()]
        out.append((
            "tpu_inf_loop_host_offcpu_seconds_total",
            "Wall of the nine host phases (all but idle/device_wait) "
            "less the engine thread's own CPU time in them: waiting for "
            "the GIL or blocked in the runtime",
            lambda: self.host_offcpu_s))
        out.append((
            "tpu_inf_loop_stage_offcpu_seconds_total",
            "The part of tpu_inf_loop_host_offcpu_seconds_total spent "
            "in the stage phase",
            lambda: self.stage_offcpu_s))
        out.append((
            "tpu_inf_loop_stalls_total",
            f"Single phase visits (not idle) longer than "
            f"{LOOP_STALL_S:g}s; each also logs one loop_stall event",
            lambda: self.stalls))
        out.append((
            "tpu_inf_loop_stall_seconds_total",
            "Wall of the visits counted in tpu_inf_loop_stalls_total",
            lambda: self.stall_s))
        return out

    def register(self, registry: Registry) -> None:
        for family, help_, read in self.families():
            registry.counter(family, help_, fn=read)


class _NullClock:
    """The clock with telemetry off: accrues and exports nothing. It
    still tells the time — callers use ``enter`` in place of their own
    clock reads."""

    __slots__ = ()
    phase = None
    in_flight = False
    has_work = False
    active = 0
    waiting = 0

    def __setattr__(self, name, value) -> None:
        pass

    def start(self) -> float:
        return time.perf_counter()

    def stop(self) -> None:
        pass

    def enter(self, phase: str) -> float:
        return time.perf_counter()

    def part(self, name: str) -> float:
        return time.perf_counter()

    def dispatched(self, seq: int) -> float:
        return time.perf_counter()

    def observed(self, seq: int) -> None:
        pass


NULL_CLOCK = _NullClock()


# ---------------------------------------------------------------------------
# XLA compile counters (README "Observability"): jax.monitoring fires
# '/jax/core/compile/backend_compile_duration' once per compile REQUEST
# (a persistent-cache hit included: the event wraps compile-or-get-
# cached) and '/jax/compilation_cache/cache_hits' once per hit, so hits
# are counted apart and real compiles = requests - hits. Process-wide,
# like the compiler; a scrape between two instants says whether
# anything compiled in between, with no log parsing.
# ---------------------------------------------------------------------------

_XLA_FAMILIES = ("tpu_inf_xla_compiles_total",
                 "tpu_inf_xla_compile_seconds_total",
                 "tpu_inf_xla_cache_hits_total")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_xla_monitor: Optional["XlaCompileMonitor"] = None


class XlaCompileMonitor:
    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def on_event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> Tuple[int, float, int]:
        return self.compiles, self.compile_s, self.cache_hits


def install_compile_monitor() -> XlaCompileMonitor:
    """Register the jax.monitoring listeners once per process (server
    main and worker boot call this; repeat calls return the same
    monitor) and expose its counters on every scrape of this process."""
    global _xla_monitor
    if _xla_monitor is None:
        import jax.monitoring

        mon = XlaCompileMonitor()
        jax.monitoring.register_event_duration_secs_listener(
            mon.on_duration)
        jax.monitoring.register_event_listener(mon.on_event)
        _SELF_REGISTRY.counter(
            "tpu_inf_xla_compiles_total",
            "XLA compile requests in this process (persistent-cache "
            "hits included; see tpu_inf_xla_cache_hits_total)",
            fn=lambda: mon.compiles)
        _SELF_REGISTRY.counter(
            "tpu_inf_xla_compile_seconds_total",
            "Wall inside XLA compile requests (compile, or the "
            "persistent cache's retrieval on a hit)",
            fn=lambda: mon.compile_s)
        _SELF_REGISTRY.counter(
            "tpu_inf_xla_cache_hits_total",
            "Compile requests served from the persistent compilation "
            "cache", fn=lambda: mon.cache_hits)
        _xla_monitor = mon
    return _xla_monitor


def compile_monitor() -> Optional[XlaCompileMonitor]:
    return _xla_monitor


def process_counters_dump() -> List[Dict[str, Any]]:
    """This process's XLA compile counters as dump_registry samples: a
    subprocess worker appends them to its registry dump so they reach
    the router's scrape under the worker's replica label."""
    return [rec for rec in dump_registry(_SELF_REGISTRY)
            if rec["name"] in _XLA_FAMILIES]


def process_age_s() -> float:
    """Seconds since this process started, by the OS's own record
    (/proc): imports before this module loaded are counted too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time() - _IMPORT_UNIX


_IMPORT_UNIX = time.time()


# ---------------------------------------------------------------------------
# Engine-side bundle
# ---------------------------------------------------------------------------

# Histograms exported under the JSON "phases" key (and scraped into the
# bench phase_breakdown). Name -> attribute on EngineTelemetry.
PHASE_HISTOGRAMS = {
    "prefill_dispatch_s": "prefill_dispatch_s",
    "decode_dispatch_s": "decode_dispatch_s",
    "decode_sync_s": "decode_sync_s",
    "dispatch_bubble_s": "dispatch_bubble_s",
    "tokens_per_dispatch": "tokens_per_dispatch",
    "hybrid_dispatch_s": "hybrid_dispatch_s",
    "decode_stall_during_prefill_s": "decode_stall_during_prefill_s",
    "kv_swap_s": "kv_swap_s",
    "spec_acceptance_rate": "spec_accept_rate",
    "queue_wait_s": "queue_wait_s",
    "queue_boundary_wait_s": "queue_boundary_wait_s",
    "queue_capacity_wait_s": "queue_capacity_wait_s",
    "prefill_phase_s": "prefill_phase_s",
    "decode_phase_s": "decode_phase_s",
    "ttft_s": "ttft_s",
    "e2e_s": "e2e_s",
}


class EngineTelemetry:
    """Per-engine (= per dp replica) metric bundle.

    Engine phases (observed by engine/engine.py):
    - ``prefill_dispatch_s``: host wall of one prefill dispatch
      (staging + device call + the blocking first-token readback).
    - ``decode_dispatch_s``: host wall of enqueueing one decode call
      (non-blocking at every pipeline depth; the device wait is
      ``decode_sync_s``).
    - ``decode_sync_s``: host wall blocked reading a decode call's
      outputs back (at depth 1 right behind its enqueue).
    - ``dispatch_bubble_s``: host-side gap between consecutive decode
      engine calls while sequences were active — scheduler bookkeeping,
      token callbacks, admission: the time the device could sit idle
      waiting for the host (hidden when pipeline depth > 1, but still
      measured so the host overhead is visible).
    - ``tokens_per_dispatch``: tokens surfaced per fused decode call.
    - ``hybrid_dispatch_s``: host wall of one hybrid prefill+decode
      fused dispatch (EngineConfig.hybrid_prefill).
    - ``decode_stall_during_prefill_s``: wall of a serial prefill
      dispatch issued while decode lanes were active — exactly the
      inter-token stall hybrid steps exist to remove, so the
      serial-vs-hybrid replay artifact compares its p95.

    Request phases (observed by engine/scheduler.py at finish):
    ``queue_wait_s``, ``prefill_phase_s`` (prefill start -> first
    token), ``decode_phase_s`` (first token -> finish), ``ttft_s``,
    ``e2e_s``. queue + prefill + decode sums to e2e by construction
    (same timestamps), the sum-check the bench artifact commits.
    """

    def __init__(self, engine=None, enabled: Optional[bool] = None):
        self.enabled = (telemetry_enabled() if enabled is None else enabled)
        self.registry = Registry()
        # Distributed tracing (README "Observability"): the replica's
        # span sink. Disabled with the rest of telemetry, so the ≤1%
        # overhead budget covers spans too. The owning fleet stamps
        # the replica index after construction.
        self.recorder = SpanRecorder(enabled=self.enabled)
        # Rolling SLO gauges; bound to targets in bind_engine.
        self.slo: Optional[SLOTracker] = None
        # Step ledger + roofline attribution (README "Performance
        # attribution"): sized/bound in bind_engine; the flight
        # recorder is attached by the owning worker/fleet (it needs the
        # operator's --blackbox-dir, which the engine never sees).
        self.step_ledger = NULL_LEDGER
        self.cost_model: Optional[StepCostModel] = None
        self.flight: Optional[FlightRecorder] = None
        # The engine thread's phase clock (the null object when off).
        self.clock = LoopClock() if self.enabled else NULL_CLOCK
        if not self.enabled:
            for attr in PHASE_HISTOGRAMS.values():
                setattr(self, attr, NULL_METRIC)
            self.decode_dispatches = NULL_METRIC
            self.prefill_dispatches = NULL_METRIC
            self.hybrid_steps = NULL_METRIC
            self.stage_dispatches = self.stage_transfers = NULL_METRIC
            self.spec_gamma_g = NULL_METRIC
            self.kv_offload_pages = NULL_METRIC
            self.kv_restore_pages = NULL_METRIC
            self.kv_offload_bytes = NULL_METRIC
            self.kv_restore_bytes = NULL_METRIC
            self.boot_weights_s = self.boot_pool_s = NULL_METRIC
            self.boot_warmup_s = self.boot_ready_s = NULL_METRIC
            self.weight_stacks_transposed = NULL_METRIC
            return
        r = self.registry
        register_span_ring(r, self.recorder)
        self.clock.register(r)
        r.counter("tpu_inf_loop_heartbeats_total",
                  "Flight-recorder heartbeats taken by the engine loop "
                  "(each one visit of the heartbeat phase)",
                  fn=lambda: self.flight.beats if self.flight else 0)
        r.counter("tpu_inf_heartbeats_skipped_total",
                  "Heartbeats skipped because the previous beat's file "
                  "write was still outstanding on the writer thread",
                  fn=lambda: (self.flight.beats_skipped
                              if self.flight else 0))
        # Boot phases, set once (README "Observability": boot).
        self.boot_weights_s = r.gauge(
            "tpu_inf_boot_weights_seconds",
            "Boot: weights initialised or loaded, quantised and "
            "enqueued to the device (host wall; boot adds no sync)")
        self.boot_pool_s = r.gauge(
            "tpu_inf_boot_pool_seconds",
            "Boot: KV page pool allocation enqueued (host wall)")
        self.boot_warmup_s = r.gauge(
            "tpu_inf_boot_warmup_seconds",
            "Boot: all warm-up graphs compiled or fetched from the "
            "persistent cache, and run")
        self.boot_ready_s = r.gauge(
            "tpu_inf_boot_ready_seconds",
            "Boot: process start to serving")
        self.weight_stacks_transposed = r.gauge(
            "tpu_inf_weight_stacks_transposed",
            "Weight stacks the engine stores transposed, [.., N, K] "
            "(models/quant.py STORED_TRANSPOSED; set once, where the "
            "swap is done)")
        self.prefill_dispatch_s = r.histogram(
            "tpu_inf_prefill_dispatch_seconds",
            "Host wall time of one prefill dispatch")
        self.decode_dispatch_s = r.histogram(
            "tpu_inf_decode_dispatch_seconds",
            "Host wall time of enqueueing one decode call")
        self.decode_sync_s = r.histogram(
            "tpu_inf_decode_sync_seconds",
            "Host wall blocked reading one decode call's outputs back")
        self.dispatch_bubble_s = r.histogram(
            "tpu_inf_dispatch_bubble_seconds",
            "Host-side gap between consecutive decode calls with active "
            "sequences (device-idle exposure)")
        self.tokens_per_dispatch = r.histogram(
            "tpu_inf_tokens_per_dispatch",
            "Tokens surfaced per fused decode call",
            buckets=COUNT_BUCKETS)
        self.hybrid_dispatch_s = r.histogram(
            "tpu_inf_hybrid_dispatch_seconds",
            "Host wall time of one hybrid prefill+decode fused dispatch")
        self.decode_stall_during_prefill_s = r.histogram(
            "tpu_inf_decode_stall_during_prefill_seconds",
            "Wall time active decode lanes sat stalled behind a serial "
            "chunked-prefill dispatch (structurally zero while hybrid "
            "steps fuse chunks into the decode dispatch; pressure-"
            "degraded rounds chunk serially and record their real stalls)")
        self.kv_swap_s = r.histogram(
            "tpu_inf_kv_swap_seconds",
            "Host wall of one device<->host KV page-batch swap "
            "(offload is a blocking device_get; restore is the host "
            "side of an async scatter dispatch)")
        self.spec_accept_rate = r.histogram(
            "tpu_inf_spec_acceptance_rate",
            "Per-sequence-round speculative acceptance rate "
            "(accepted / drafted positions; one observation per lane "
            "per spec round)",
            buckets=RATE_BUCKETS)
        self.spec_gamma_g = r.gauge(
            "tpu_inf_spec_gamma",
            "Mean adaptive speculation depth γ across the latest spec "
            "round's lanes (0 = every lane throttled to plain decode)")
        self.kv_offload_pages = r.counter(
            "tpu_inf_kv_offload_pages_total",
            "KV pages demoted from the HBM pool to the host-RAM tier")
        self.kv_restore_pages = r.counter(
            "tpu_inf_kv_restore_pages_total",
            "KV pages promoted from the host-RAM tier back into the "
            "HBM pool")
        self.kv_offload_bytes = r.counter(
            "tpu_inf_kv_offload_bytes_total",
            "Bytes copied device->host by KV page demotion")
        self.kv_restore_bytes = r.counter(
            "tpu_inf_kv_restore_bytes_total",
            "Bytes copied host->device by KV page promotion")
        self.queue_wait_s = r.histogram(
            "tpu_inf_queue_wait_seconds",
            "Request admission queue wait (enqueue -> prefill start)")
        self.queue_boundary_wait_s = r.histogram(
            "tpu_inf_queue_boundary_wait_seconds",
            "Queue wait, first part: enqueue -> the first admission "
            "pass that saw the request (waiting for the running "
            "dispatch to come back)")
        self.queue_capacity_wait_s = r.histogram(
            "tpu_inf_queue_capacity_wait_seconds",
            "Queue wait, second part: first admission pass -> prefill "
            "start (admission work, and passes the request was turned "
            "away for slots or pages); the two parts sum to "
            "tpu_inf_queue_wait_seconds")
        self.prefill_phase_s = r.histogram(
            "tpu_inf_prefill_phase_seconds",
            "Request prefill phase (prefill start -> first token)")
        self.decode_phase_s = r.histogram(
            "tpu_inf_decode_phase_seconds",
            "Request decode phase (first token -> finish)")
        self.ttft_s = r.histogram(
            "tpu_inf_ttft_seconds",
            "Time to first token (enqueue -> first token)")
        self.e2e_s = r.histogram(
            "tpu_inf_e2e_seconds",
            "Request end-to-end latency (enqueue -> finish)")
        self.decode_dispatches = r.counter(
            "tpu_inf_decode_dispatches_total",
            "Fused-decode engine calls dispatched")
        self.prefill_dispatches = r.counter(
            "tpu_inf_prefill_dispatches_total",
            "Prefill dispatches issued")
        self.hybrid_steps = r.counter(
            "tpu_inf_hybrid_steps_total",
            "Hybrid prefill+decode fused dispatches issued")
        # What the put part of the stage phase is made of: read beside
        # tpu_inf_loop_stage_put_seconds_total, the time it takes.
        self.stage_dispatches = r.counter(
            "tpu_inf_stage_dispatches_total",
            "Step programs (prefill, decode, hybrid) handed packed "
            "operands")
        self.stage_transfers = r.counter(
            "tpu_inf_stage_transfers_total",
            "Host arrays put on the device as those programs' operands "
            "(one a dispatch, two for a hybrid call)")
        if engine is not None:
            self.bind_engine(engine)

    def bind_engine(self, engine) -> None:
        """Read-through metrics over state the engine already tracks
        (zero hot-path cost)."""
        if not self.enabled:
            return
        self.step_ledger = StepLedger(engine.engine_cfg.step_ledger_depth)
        self.cost_model = StepCostModel.from_engine(engine)
        r = self.registry
        alloc = engine.allocator
        total = engine.engine_cfg.num_pages - 1   # page 0 = trash page
        r.counter("tpu_inf_kv_page_allocs_total",
                  "KV pool pages allocated",
                  fn=lambda: alloc.pages_allocated_total)
        r.counter("tpu_inf_kv_page_frees_total",
                  "KV pool pages freed",
                  fn=lambda: alloc.pages_freed_total)
        r.gauge("tpu_inf_kv_pages_total", "Allocatable KV pool pages",
                fn=lambda: total)
        page_tokens = engine.engine_cfg.page_size
        r.gauge("tpu_inf_kv_page_tokens",
                "Tokens a KV page holds (given, or chosen from the "
                "page's bytes: autosize.resolve_page_size)",
                fn=lambda: page_tokens)
        r.gauge("tpu_inf_kv_pages_in_use", "KV pool pages in use",
                fn=lambda: total - alloc.num_free)
        r.gauge("tpu_inf_kv_page_util",
                "KV pool utilization (in_use / total)",
                fn=lambda: (total - alloc.num_free) / max(total, 1))
        r.gauge("tpu_inf_kv_pool_pressure",
                "1 - (free+evictable)/total: fraction of the pool "
                "pinned by running sequences",
                fn=lambda: engine.pool_pressure)
        r.counter("tpu_inf_preemptions_total",
                  "Sequences preempted for KV pool pressure "
                  "(admission=optimistic watermark safety net)",
                  fn=lambda: engine.preemptions_total)
        r.counter("tpu_inf_recompute_resumes_total",
                  "Preempted sequences re-prefilled (recompute-resume)",
                  fn=lambda: engine.resumes_total)
        r.counter("tpu_inf_swap_in_resumes_total",
                  "Resume prefills that restored KV pages from the "
                  "cache tiers instead of recomputing them all",
                  fn=lambda: engine.swap_in_resumes)
        # KV page migration (README "Process fleet"): drain-time exports
        # to / imports from sibling replicas. Structurally zero under
        # the in-process fleet (kept exported so backend counter shapes
        # match and dashboards need one query).
        r.counter("tpu_inf_kv_migrate_out_pages_total",
                  "KV pages exported at drain for migration to a "
                  "sibling replica",
                  fn=lambda: engine.migrate_out_pages)
        r.counter("tpu_inf_kv_migrate_out_bytes_total",
                  "Bytes exported at drain for KV migration",
                  fn=lambda: engine.migrate_out_bytes)
        r.counter("tpu_inf_kv_migrate_in_pages_total",
                  "Migrated KV pages adopted into this replica's host "
                  "tier",
                  fn=lambda: engine.migrate_in_pages)
        r.counter("tpu_inf_kv_migrate_in_bytes_total",
                  "Bytes adopted into the host tier by KV migration",
                  fn=lambda: engine.migrate_in_bytes)
        r.gauge("tpu_inf_model_params", "Model parameter count",
                fn=lambda: engine.n_params)
        mcfg = engine.model_cfg
        r.gauge("tpu_inf_model_loop_steps",
                "Passes of the layer stack a token runs (1 = unlooped)",
                fn=lambda: mcfg.loop_steps)
        r.gauge("tpu_inf_kv_layer_slots",
                "Leading dim of the KV pool: layers x passes",
                fn=lambda: mcfg.n_kv_slots)
        kv_token_bytes = self.cost_model.kv_token_bytes
        r.gauge("tpu_inf_kv_bytes_per_token",
                "KV pool bytes one token occupies over all slots (a "
                "model with a pool a kind: of both kinds, while inside "
                "the window)",
                fn=lambda: (kv_token_bytes
                            + self.cost_model.kv_window_token_bytes))
        if engine.win_allocator is not None:
            # A pool a kind (no labels: a scrape sums labels away).
            wall = engine.win_allocator
            wtotal = wall.num_pages - 1
            r.gauge("tpu_inf_kv_full_pages_total",
                    "Allocatable pages of the full-attention kind's pool",
                    fn=lambda: total)
            r.gauge("tpu_inf_kv_full_pages_in_use",
                    "Pages of the full-attention kind's pool in use",
                    fn=lambda: total - alloc.num_free)
            r.gauge("tpu_inf_kv_window_pages_total",
                    "Allocatable pages of the window kind's pool",
                    fn=lambda: wtotal)
            r.gauge("tpu_inf_kv_window_pages_in_use",
                    "Pages of the window kind's pool in use",
                    fn=lambda: wtotal - wall.num_free)
            r.gauge("tpu_inf_kv_full_pages_peak",
                    "Most pages of the full-attention kind's pool in use "
                    "at once since boot", fn=lambda: alloc.peak_in_use)
            r.gauge("tpu_inf_kv_window_pages_peak",
                    "Most pages of the window kind's pool in use at once "
                    "since boot", fn=lambda: wall.peak_in_use)
            # What admission holds back for the bound sequences, taken
            # or not yet (engine.admission_fits): a pool sized on live
            # tokens is full when its BOOKED pages reach its total, well
            # before its pages in use do.
            booked = ("Pages of the {} kind's pool that admission held "
                      "back for the bound sequences' whole lives at its "
                      "last pass")
            peak = ("Most pages of the {} kind's pool held back at once "
                    "since boot (seen at admission passes)")
            r.gauge("tpu_inf_kv_full_pages_booked", booked.format("full"),
                    fn=lambda: engine.pages_booked_seen[0])
            r.gauge("tpu_inf_kv_window_pages_booked",
                    booked.format("window"),
                    fn=lambda: engine.pages_booked_seen[1])
            r.gauge("tpu_inf_kv_full_pages_booked_peak", peak.format("full"),
                    fn=lambda: engine.pages_booked_peak[0])
            r.gauge("tpu_inf_kv_window_pages_booked_peak",
                    peak.format("window"),
                    fn=lambda: engine.pages_booked_peak[1])
            r.counter("tpu_inf_kv_window_pages_released_total",
                      "Window-kind pages released behind the window "
                      "while their sequence ran (prefill chunks and "
                      "decode)",
                      fn=lambda: engine.window_pages_released)
            kv_window_bytes = self.cost_model.kv_window_token_bytes
            r.gauge("tpu_inf_kv_full_bytes_per_token",
                    "Full-kind pool bytes one token occupies",
                    fn=lambda: kv_token_bytes)
            r.gauge("tpu_inf_kv_window_bytes_per_token",
                    "Window-kind pool bytes one token occupies (a "
                    "sequence holds at most the window's span of them)",
                    fn=lambda: kv_window_bytes)
        r.gauge("tpu_inf_active_sequences", "Bound decode slots",
                fn=lambda: sum(s is not None for s in engine.slots))
        # Batch ladder (README "Batch ladder"): which compiled decode
        # graph the engine is currently dispatching, how far up it has
        # ever climbed, how often it switched graphs, and how full the
        # top rung's lanes are.
        r.gauge("tpu_inf_decode_rung",
                "Active batch-ladder rung (batch size of the compiled "
                "decode graph the latest dispatch ran)",
                fn=lambda: engine.decode_rung)
        r.gauge("tpu_inf_decode_ladder_top",
                "Top batch-ladder rung (HBM-budgeted max concurrent "
                "decode lanes)",
                fn=lambda: engine.ladder[-1])
        r.counter("tpu_inf_rung_switches_total",
                  "Decode dispatches that changed ladder rung (compiled-"
                  "graph switches)",
                  fn=lambda: engine.rung_switches_total)
        r.gauge("tpu_inf_decode_occupancy",
                "Decode lane occupancy: bound slots / top ladder rung",
                fn=lambda: (sum(s is not None for s in engine.slots)
                            / max(engine.ladder[-1], 1)))
        # Rolling SLO gauges (README "Observability"): exact windowed
        # TTFT/TPOT quantiles over the last SLO_WINDOW requests, plus
        # breach counters against the --slo-ttft-ms/--slo-tpot-ms
        # targets — the autoscaler's input signal (ROADMAP item 3).
        ecfg = engine.engine_cfg
        slo = self.slo = SLOTracker(ecfg.slo_ttft_ms / 1e3,
                                    ecfg.slo_tpot_ms / 1e3)
        for q in SLO_QUANTILES:
            r.gauge("tpu_inf_slo_ttft_seconds",
                    "Rolling exact TTFT quantile over the last "
                    f"{SLO_WINDOW} requests (NaN = no data)",
                    fn=lambda q=q: slo.gauge_value("ttft", q),
                    q=f"{q:g}")
            r.gauge("tpu_inf_slo_tpot_seconds",
                    "Rolling exact TPOT quantile over the last "
                    f"{SLO_WINDOW} requests (NaN = no data)",
                    fn=lambda q=q: slo.gauge_value("tpot", q),
                    q=f"{q:g}")
        r.counter("tpu_inf_slo_breaches_total",
                  "Finished requests whose TTFT exceeded --slo-ttft-ms "
                  "(never counts while no target is set)",
                  fn=lambda: slo.ttft_breaches, slo="ttft")
        r.counter("tpu_inf_slo_breaches_total",
                  "Finished requests whose TPOT exceeded --slo-tpot-ms "
                  "(never counts while no target is set)",
                  fn=lambda: slo.tpot_breaches, slo="tpot")

    def bind_spec(self, engine) -> None:
        """Read-through speculative-decoding counters over state the
        engine already tracks (called only when spec decode is on, so
        non-spec servers don't expose dead spec series)."""
        if not self.enabled:
            return
        r = self.registry
        r.counter("tpu_inf_spec_drafted_total",
                  "Speculative positions proposed for verification "
                  "(n-gram proposals the host could emit)",
                  fn=lambda: engine.spec_drafted)
        r.counter("tpu_inf_spec_accepted_total",
                  "Speculative positions accepted by the target model",
                  fn=lambda: engine.spec_accepted)
        r.counter("tpu_inf_spec_rounds_total",
                  "Verify rounds dispatched",
                  fn=lambda: engine.spec_rounds_total)
        r.counter("tpu_inf_spec_fallback_rounds_total",
                  "Speculating rounds that ran the plain fused-K decode "
                  "graph because no lane proposed (cold/throttled "
                  "streams — the 'spec never loses' path)",
                  fn=lambda: engine.spec_fallback_rounds)
        r.counter("tpu_inf_spec_throttles_total",
                  "Sequences throttled to γ=0 by the acceptance EWMA",
                  fn=lambda: engine.spec_throttles_total)

    def bind_moe(self, engine) -> None:
        """Read-through expert-routing counters (family deepseek_v3):
        the model counts on the device (models/deepseek_v3.py MOE_STATS),
        the counts ride the decode token readback out, the engine sums
        them in ``engine.aux_stats`` (engine._fold_aux_stats)."""
        if not self.enabled:
            return
        from tpu_inference.models.deepseek_v3 import (GROUP_STATS, MOE_STATS,
                                                      ROW_STATS)

        r, st = self.registry, engine.aux_stats
        at = {name: i for i, name in enumerate(MOE_STATS)}
        n_held = engine.model_cfg.n_local_experts
        r.counter("tpu_inf_moe_tokens_total",
                  "Token positions routed, summed over expert layers",
                  fn=lambda: int(st[at["tokens"]]))
        r.counter("tpu_inf_moe_local_pairs_total",
                  "Routed (token, expert) pairs whose expert this chip "
                  "holds", fn=lambda: int(st[at["local_pairs"]]))
        r.counter("tpu_inf_moe_dropped_pairs_total",
                  "Local pairs the grouped expert rounds did not compute "
                  "(dropless: stays 0)",
                  fn=lambda: int(st[at["local_pairs"]]
                                 - st[at["computed_pairs"]]))
        r.counter("tpu_inf_moe_busiest_expert_pairs_total",
                  "Pairs on the busiest held expert of each expert-layer "
                  "call (a decode step's or a prefill chunk's), summed: "
                  "over local_pairs / held experts it is the load "
                  "imbalance the grouped matmul sees",
                  fn=lambda: int(st[at["busiest_pairs"]]))
        r.counter("tpu_inf_moe_distinct_experts_total",
                  "Held experts with at least one pair, summed over "
                  "decode steps and expert layers",
                  fn=lambda: int(st[at["distinct_experts"]]))
        r.counter("tpu_inf_moe_decode_layer_steps_total",
                  "Decode steps x expert layers behind "
                  "tpu_inf_moe_distinct_experts_total",
                  fn=lambda: int(st[at["decode_layers"]]))
        r.gauge("tpu_inf_moe_gather_combine_programs",
                "Warmed step programs whose expert layers sum each "
                "token's k rows by a gather from each round's result (T x "
                "k is no more than SCATTERED_ROW_COST x a round's rows: "
                "kernels/moe_experts.py combines_by_gather) and not by a "
                "scatter-add of the round's rows; set once, by warm-up",
                fn=lambda: engine.gather_combine_programs)
        for e in range(n_held):
            r.counter("tpu_inf_moe_expert_pairs_total",
                      "Routed pairs per held expert",
                      fn=lambda e=e: int(st[len(MOE_STATS) + e]),
                      expert=str(e))
        if engine.model_cfg.moe_row_stats:
            at_rows = len(MOE_STATS) + n_held + ROW_STATS.index("tile_rows")
            r.counter("tpu_inf_moe_tile_rows_total",
                      "Rows the grouped expert kernels ran, whole tiles "
                      "(each held expert's pairs padded up to a tile): "
                      "less the computed pairs it is what the tile size "
                      "costs", fn=lambda: int(st[at_rows]))
            r.counter("tpu_inf_moe_computed_pairs_total",
                      "Local pairs the grouped expert rounds computed: "
                      "the real rows among tpu_inf_moe_tile_rows_total",
                      fn=lambda: int(st[at["computed_pairs"]]))
        if engine.model_cfg.n_group > 1:
            at_reach = (len(MOE_STATS) + n_held
                        + len(ROW_STATS) * engine.model_cfg.moe_row_stats
                        + GROUP_STATS.index("group_reach_tokens"))
            r.counter("tpu_inf_moe_group_reach_tokens_total",
                      "Routed token positions (summed over expert layers, "
                      "as tpu_inf_moe_tokens_total) one of whose chosen "
                      "expert GROUPS has experts this chip holds: over "
                      "the tokens it is the share the expert exchange "
                      "would send here at all (topk_group / n_group "
                      "under uniform routing)",
                      fn=lambda: int(st[at_reach]))
        if engine.model_cfg.hc_mult > 1:
            from tpu_inference.models.hyper_connections import MHC_STATS

            first = len(st) - len(MHC_STATS)
            r.counter("tpu_inf_mhc_mixes_total",
                      "Hyper-connections applied: sublayers (two a layer) "
                      "x token positions they mixed the residual streams "
                      "of", fn=lambda: int(st[first]))
            r.gauge("tpu_inf_mhc_row_sum_err_ppm_max",
                    "Largest |sum - 1| over the rows and columns of any "
                    "residual mixing matrix since boot, in parts per "
                    "million (rows are normalised last: the columns' is "
                    "what fewer Sinkhorn iterations move)",
                    fn=lambda: int(st[first + 1]))

    def bind_state(self, engine) -> None:
        """Read-through metrics of a model with layers of a state kind
        (config.STATE_KINDS): the state slots (engine/kv_cache.py
        StateSlots) and, for the sambay family, what its prefill programs
        ran for, counted on the device (models/sambay.py AUX_STATS; the
        counts ride the decode token readback out as the routing counts
        do)."""
        if not self.enabled:
            return
        r, slots, st = self.registry, engine.state_slots, engine.aux_stats
        r.gauge("tpu_inf_state_slots_total",
                "Allocatable per-sequence state slots (layers with a "
                "state a sequence)", fn=lambda: slots.num_slots - 1)
        r.gauge("tpu_inf_state_slots_in_use",
                "State slots held by a sequence", fn=lambda: slots.in_use)
        r.gauge("tpu_inf_state_slots_peak",
                "Most state slots held at once since boot",
                fn=lambda: slots.peak_in_use)
        r.gauge("tpu_inf_state_bytes_per_seq",
                "Bytes of state one sequence's slot holds over all "
                "layers of the state kind",
                fn=lambda: engine.model_cfg.state_bytes_per_seq())
        r.counter("tpu_inf_state_resets_total",
                  "Prefill chunks at position 0 (a prompt's first, a "
                  "recompute-resume's): states started from zeros",
                  fn=lambda: slots.resets_total)
        if engine.model_cfg.family != "sambay":
            return
        from tpu_inference.models.sambay import AUX_STATS

        at = {name: i for i, name in enumerate(AUX_STATS)}
        r.counter("tpu_inf_prefill_positions_total",
                  "Prompt positions the prefill programs ran the layers "
                  "up to the full-attention one for (counted in the "
                  "graph)", fn=lambda: int(st[at["prefill_positions"]]))
        r.counter("tpu_inf_prefill_cross_positions_total",
                  "Positions the prefill programs ran the layers BEHIND "
                  "the full-attention one for (one a prompt chunk when "
                  "the skip works)",
                  fn=lambda: int(st[at["prefill_cross_positions"]]))

    def bind_host_pool(self, pool) -> None:
        """Read-through metrics over the host-RAM KV tier's capacity
        accounting (engine/kv_cache.py HostPagePool). Called by the
        engine after the pool exists — bind_engine runs before the
        prefix cache / host tier are constructed."""
        if not self.enabled:
            return
        r = self.registry
        r.gauge("tpu_inf_kv_host_pages_total",
                "Host-RAM KV tier capacity (pages)",
                fn=lambda: pool.capacity)
        r.gauge("tpu_inf_kv_host_pages_used",
                "Host-RAM KV tier pages resident",
                fn=lambda: pool.used)
        r.counter("tpu_inf_kv_host_evictions_total",
                  "Host-tier entries dropped for good (second-tier LRU "
                  "eviction or supersession by a fresh HBM publish)",
                  fn=lambda: pool.evicted_total)

    def bind_scheduler(self, sched) -> None:
        """Read-through metrics over SchedulerStats counters."""
        if not self.enabled:
            return
        r = self.registry
        stats = sched.stats
        r.counter("tpu_inf_steps_total", "Scheduler loop decode steps",
                  fn=lambda: stats.steps)
        r.counter("tpu_inf_prefills_total", "Prefills completed",
                  fn=lambda: stats.prefills)
        r.counter("tpu_inf_tokens_generated_total", "Tokens generated",
                  fn=lambda: stats.tokens_generated)
        r.counter("tpu_inf_tokens_prefix_cached_total",
                  "Prompt tokens served from KV prefix reuse",
                  fn=lambda: stats.tokens_prefix_cached)
        r.counter("tpu_inf_requests_rejected_total",
                  "Requests rejected at submission",
                  fn=lambda: stats.requests_rejected)
        r.counter("tpu_inf_step_failures_total",
                  "Prefill/decode dispatch exceptions",
                  fn=lambda: stats.step_failures)
        # How often the hand-off to the HTTP loop engages: read beside
        # tpu_inf_loop_deliver_seconds_total, the engine thread's time in it.
        r.counter("tpu_inf_deliver_tokens_total",
                  "Tokens handed to a request's on_token callback",
                  fn=lambda: stats.deliver_tokens)
        r.counter("tpu_inf_deliver_wakeups_total",
                  "Wake-ups of the HTTP event loop posted by the engine "
                  "thread (one a delivery: a turn's tokens and finishes)",
                  fn=lambda: stats.deliver_wakeups)
        r.gauge("tpu_inf_queue_depth", "Requests waiting for admission",
                fn=lambda: len(sched._waiting))
        # Derived MFU estimate: decoded-token rate x ~2 FLOPs/param/
        # token over the chip's bf16 peak (engine/autosize.py
        # CHIP_SPECS). Without a chip there is no peak and the gauge is
        # not registered at all — absent, not computed against a v5e.
        # The rate is a dt-weighted EWMA (~30 s time constant) updated by
        # WHOEVER collects — /metrics scrapes, stats snapshots, and
        # fleet merges all read the same smoothed value, so a fast
        # poller can't reset a slow scraper's window (a plain
        # between-scrapes delta would report only the last poll's
        # sliver).
        import math

        from tpu_inference.engine import autosize as _autosize

        engine = sched.engine
        chip = _autosize.chip_spec()
        if chip is None:
            return
        peak = chip.peak_bf16_flops
        # A looped stack multiplies a token through its layers once a
        # pass: more parameters than it stores.
        flop_params = (self.cost_model.n_params
                       if engine.model_cfg.loop_steps > 1
                       else engine.n_params)
        tau_s = 30.0
        state = {"tokens": stats.tokens_generated,
                 "t": time.perf_counter(), "rate": 0.0}

        def _mfu() -> float:
            now = time.perf_counter()
            dt = now - state["t"]
            if dt >= 1e-3:
                tok = stats.tokens_generated
                inst = max(0, tok - state["tokens"]) / dt
                alpha = 1.0 - math.exp(-dt / tau_s)
                state["rate"] += alpha * (inst - state["rate"])
                state["tokens"], state["t"] = tok, now
            return state["rate"] * 2 * flop_params / peak

        self._mfu_gauge = r.gauge(
            "tpu_inf_mfu_estimate",
            "Estimated model FLOPs utilization (EWMA decode tokens/s "
            "x 2 x params / chip bf16 peak, ~30s time constant)",
            fn=_mfu)

    def mfu_estimate(self) -> Optional[float]:
        """Latest scrape-window MFU estimate (None when telemetry is
        off, no scheduler is bound, or there is no chip to rate
        against)."""
        g = getattr(self, "_mfu_gauge", None)
        # 12 decimals, not 6: a small model on a big chip sits at MFU
        # ~1e-9.
        return round(g.collect_value(), 12) if g is not None else None

    def steps_report(self, window_s: float = 60.0,
                     since: Optional[float] = None,
                     until: Optional[float] = None,
                     records: bool = False) -> Dict[str, Any]:
        """This replica's step-attribution report (the ``steps`` worker
        RPC verb / GET /debug/steps body; ``since`` / ``until`` /
        ``records`` are its query parameters)."""
        if not self.enabled or self.cost_model is None:
            return {"enabled": False}
        return roofline_report(
            self.step_ledger, self.cost_model,
            mfu_gauge=self.mfu_estimate(),
            window_s=window_s, since=since, until=until, records=records)

    def request_finished(self, reason: str) -> None:
        """Per-finish-reason counter (lazy label children)."""
        if not self.enabled:
            return
        self.registry.counter(
            "tpu_inf_requests_finished_total",
            "Finished requests by terminal reason",
            reason=reason or "unknown").inc()

    def phase_snapshot(self) -> Dict[str, Dict[str, Any]]:
        """JSON phases dump for /metrics?format=json and the bench
        scrape (empty when disabled)."""
        if not self.enabled:
            return {}
        return {key: getattr(self, attr).phase_snapshot()
                for key, attr in PHASE_HISTOGRAMS.items()}

    def loop_snapshot(self) -> Dict[str, float]:
        """Every family of the loop clock as /metrics would render it
        now, the decode and prefill dispatch counters, and the clock's
        own time as ``loop_wall_s``: two of these bracket an interval
        (capture_jax_profile). Empty when disabled. A phase visit
        accrues when it ends, so a difference misses the visit open at
        each edge."""
        if not self.enabled:
            return {}
        snap = {family: float(read())
                for family, _, read in self.clock.families()}
        for counter in (self.decode_dispatches, self.prefill_dispatches):
            snap[counter.name] = float(counter.value)
        snap["loop_wall_s"] = self.clock.now()
        return snap
