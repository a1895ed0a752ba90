"""Data-parallel replica serving: dp independent engines behind one facade.

``ParallelConfig.dp`` used to replicate params inside ONE engine (useful
for the sharding dry-run, useless for throughput: one scheduler, one
decode batch). True dp serving is replica-per-group — each replica owns a
``tp*sp``-device submesh, its own KV pool, and its own continuous-batching
scheduler. The reference's analogue is the load balancer in front of its
external endpoint (implicit, out of repo — SURVEY.md §0); here it comes
in TWO backends behind one facade (``ServerConfig.fleet``, README
"Process fleet"): this module's ``EngineGroup`` runs every replica as a
thread of the server process (simple, but one Python process, one GIL,
one failure domain), while ``server/fleet.py``'s ``ProcessEngineGroup``
runs each replica as its own engine-worker OS process behind a router,
with supervised restarts, kill -9 failover, and drain-time KV page
migration. The routing/failover/admission semantics below are the
contract both backends implement.

Supervision (README "Failure handling & degraded operation"): each
replica carries a health state machine

    healthy -> degraded -> quarantined -> recovered -> healthy

driven by consecutive step failures (engine exceptions surfaced through
the scheduler hooks) and a step watchdog that detects wedged dispatches
(the round-5 TPU failure mode: a decode call that never returns).
Quarantined replicas receive no traffic; their failed or stranded
requests fail over — resubmitted from the prompt to a healthy replica
when no tokens were delivered yet, failed cleanly otherwise. Admission
control sheds load (FleetSaturated/FleetUnavailable -> HTTP 429/503 with
Retry-After) instead of queueing to the request timeout.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from tpu_inference import telemetry
from tpu_inference.config import ServerConfig
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.engine.prefix_cache import _chain_hashes
from tpu_inference.engine.scheduler import EngineScheduler
from tpu_inference.server import kv_fabric


class AdmissionError(RuntimeError):
    """Request rejected before submission; carries the Retry-After hint."""

    def __init__(self, msg: str, retry_after_s: float):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class FleetSaturated(AdmissionError):
    """Every routable replica is at the admission queue cap (HTTP 429)."""


class FleetUnavailable(AdmissionError):
    """No routable replica at all — fleet fully quarantined (HTTP 503)."""


HEALTHY = "healthy"
DEGRADED = "degraded"
QUARANTINED = "quarantined"
RECOVERED = "recovered"


class ReplicaHealth:
    """Per-replica health state machine (thread-safe; hooks fire on the
    replica's engine thread, the watchdog on the monitor thread, and
    snapshots on HTTP handler threads)."""

    def __init__(self, cfg: ServerConfig):
        self.cfg = cfg
        self.state = HEALTHY
        self.consecutive_failures = 0
        self.wedges = 0                 # watchdog firings
        self.quarantines = 0            # entries into QUARANTINED
        self.since = time.monotonic()   # last state change
        self._lock = threading.Lock()

    def _transition(self, state: str) -> None:
        if state == QUARANTINED and self.state != QUARANTINED:
            self.quarantines += 1
        if state != self.state:
            self.state = state
            self.since = time.monotonic()

    def on_ok(self) -> None:
        # Hot path: one clean step per decode call — skip the lock when
        # there is provably nothing to do.
        if self.state == HEALTHY and self.consecutive_failures == 0:
            return
        with self._lock:
            self.consecutive_failures = 0
            if self.state in (DEGRADED, RECOVERED):
                # RECOVERED -> HEALTHY is the probation pass.
                self._transition(HEALTHY)
            # QUARANTINED stays: a late success from a previously wedged
            # call does not beat the cooldown (the fault may recur).

    def on_error(self) -> None:
        with self._lock:
            self.consecutive_failures += 1
            if self.state == RECOVERED:
                # Probation failure: straight back to quarantine.
                self._transition(QUARANTINED)
            elif self.consecutive_failures >= self.cfg.quarantine_after_failures:
                self._transition(QUARANTINED)
            elif self.state == HEALTHY:
                self._transition(DEGRADED)

    def mark_wedged(self) -> bool:
        """Watchdog deadline exceeded. True only on the transition, so
        the caller fails over stranded requests exactly once."""
        with self._lock:
            if self.state == QUARANTINED:
                return False
            self.wedges += 1
            self._transition(QUARANTINED)
            return True

    def maybe_recover(self) -> None:
        """QUARANTINED -> RECOVERED after the cooldown. The caller must
        not invoke this while the replica's dispatch is still wedged."""
        with self._lock:
            if (self.state == QUARANTINED
                    and time.monotonic() - self.since
                    >= self.cfg.quarantine_cooldown_s):
                self._transition(RECOVERED)

    @property
    def routable(self) -> bool:
        return self.state != QUARANTINED

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "state": self.state,
                "consecutive_failures": self.consecutive_failures,
                "wedges": self.wedges,
                "quarantines": self.quarantines,
                "state_age_s": round(time.monotonic() - self.since, 3),
            }


def _clone_request(seq: Sequence) -> Sequence:
    """A pristine copy of the client-supplied request fields — engine-
    filled state (slot, pages, generated, timings) starts fresh, so a
    failover attempt replays from the prompt exactly like a new submit."""
    return Sequence(
        request_id=seq.request_id,
        prompt_tokens=list(seq.prompt_tokens),
        max_new_tokens=seq.max_new_tokens,
        temperature=seq.temperature, top_p=seq.top_p, top_k=seq.top_k,
        seed=seq.seed, repeat_penalty=seq.repeat_penalty,
        repeat_last_n=seq.repeat_last_n, eos_token_id=seq.eos_token_id,
        trace_id=seq.trace_id,
        priority_class=seq.priority_class,
        # The prompt's chain hashes are a pure function of the tokens:
        # the replay reuses the original's single hash pass (bytes are
        # immutable — sharing the list is safe).
        prefix_digests=seq.prefix_digests)


# Finish reasons a zero-delivery request may be resubmitted after.
_RETRYABLE = ("error",)


@dataclasses.dataclass
class _Tracked:
    """Group-side state for one in-flight request across attempts."""

    template: Sequence                  # pristine request for resubmission
    on_token: Callable
    on_finish: Callable
    sched: EngineScheduler
    delivered: int = 0                  # tokens forwarded to the caller
    attempts: int = 0                   # failover resubmissions so far
    generation: int = 0                 # bumped to orphan stale callbacks
    t_submit: float = 0.0               # perf_counter at submit (root span)
    # DISTINCT replica indices whose attempt at this request errored or
    # wedged — the poison-quarantine gate's evidence (README "Failure
    # model").
    failed_replicas: set = dataclasses.field(default_factory=set)


class EngineGroup:
    """dp EngineSchedulers with cache-aware routing, health supervision,
    failover, and admission control.

    Routing (ServerConfig.routing): "prefix_affinity" scores every
    routable replica by the prefill work routing there would cost —
    expected re-prefill pages (prompt pages minus a side-effect-free
    prefix-cache peek) blended with queue depth and preemption
    pressure — so a returning conversation lands on the replica that
    already holds its history's KV pages. Cold prompts, single-replica
    fleets, and routing="least_loaded" reduce to the legacy
    (pressure, load) key, now with a deterministic rotating tie-break
    (equal-key replicas used to all herd onto replica 0).

    With one engine this is a transparent pass-through, so the server
    always talks to an EngineGroup.
    """

    def __init__(self, engines: List[InferenceEngine],
                 server_cfg: Optional[ServerConfig] = None):
        assert engines
        self.engines = engines
        self.server_cfg = server_cfg or ServerConfig()
        self.schedulers = [EngineScheduler(e) for e in engines]
        self.health = [ReplicaHealth(self.server_cfg) for _ in engines]
        for sched, health in zip(self.schedulers, self.health):
            sched.on_step_ok = health.on_ok
            sched.on_step_error = lambda exc, h=health: h.on_error()
        # request_id -> tracked entry (ids are globally unique).
        self._tracked: Dict[int, _Tracked] = {}
        self._lock = threading.Lock()
        # Fleet counters (surfaced via stats_snapshot / /healthz).
        self.retries_attempted = 0
        self.retries_succeeded = 0
        self.failovers = 0              # stranded-by-wedge resubmissions
        self.requests_shed = 0          # 429: queue cap
        self.requests_unavailable = 0   # 503: no routable replica
        self.poison_requests = 0        # terminally quarantined (500)
        # Routing accounting. The rotation counter advances once per
        # tie-broken decision; the counters move on every dispatch
        # (initial or failover). Plain ints mutated from HTTP/engine
        # threads: GIL-atomic increments, torn reads tolerated (same
        # stance as telemetry.py).
        self._rr = 0                    # rotating tie-break cursor
        self.route_prefix_hits = 0      # dispatches with peeked hit > 0
        self.route_cold = 0             # dispatches with no cached prefix
        self.route_fabric_hits = 0      # dispatches that pulled fabric pages
        self._route_stats = [{"hits": 0, "cold": 0, "hit_pages": 0,
                              "host_hit_pages": 0, "fabric_hit_pages": 0}
                             for _ in engines]
        # Fleet KV fabric (README "KV fabric"): the router-side
        # digest-keyed pool of serialized prefix pages shared by every
        # replica. In-process, publish is a direct call (each engine's
        # fabric_publish is armed below); pulls land in the target
        # engine's host tier via request_import_host before dispatch.
        self.fabric = kv_fabric.FabricPool(self.server_cfg.fabric_cache_pages)
        for e in engines:
            if self.fabric.capacity > 0 and e.prefix_cache is not None:
                e.fabric_publish = self.fabric.put_pages
                e.fabric_publish_min_pages = \
                    self.server_cfg.fabric_publish_min_pages
        self._watch_stop = threading.Event()
        self._watch_thread: Optional[threading.Thread] = None
        # Cross-replica trace assembly (README "Observability"): each
        # engine's recorder holds its replica's spans (stamped with the
        # replica index here); the group's own recorder holds the
        # router-side spans (request root, route) — /debug/trace reads
        # them together. Same shape as the subprocess router, minus the
        # transport (everything is in-process).
        self._recorder = telemetry.SpanRecorder(replica=-1)
        for i, e in enumerate(self.engines):
            e.telemetry.recorder.replica = i
        # Fleet-level Prometheus registry: supervision counters (no
        # replica label — they are fleet decisions) + per-replica health
        # gauges. Rendered together with each engine's registry (under
        # replica="i" labels) by prometheus_text().
        self._fleet_registry = telemetry.Registry()
        r = self._fleet_registry
        telemetry.register_span_ring(r, self._recorder)
        r.gauge("tpu_inf_replicas", "Configured dp replicas",
                fn=lambda: len(self.engines))
        r.counter("tpu_inf_retries_attempted_total",
                  "Failover resubmissions attempted",
                  fn=lambda: self.retries_attempted)
        r.counter("tpu_inf_retries_succeeded_total",
                  "Failover resubmissions that finished cleanly",
                  fn=lambda: self.retries_succeeded)
        r.counter("tpu_inf_failovers_total",
                  "Requests stranded by a wedged replica and resubmitted",
                  fn=lambda: self.failovers)
        r.counter("tpu_inf_requests_shed_total",
                  "Requests shed at the admission queue cap (HTTP 429)",
                  fn=lambda: self.requests_shed)
        r.counter("tpu_inf_requests_unavailable_total",
                  "Requests rejected with no routable replica (HTTP 503)",
                  fn=lambda: self.requests_unavailable)
        r.counter("tpu_inf_poison_requests_total",
                  "Requests quarantined after crashing/wedging "
                  "poison_max_workers distinct replicas (HTTP 500)",
                  fn=lambda: self.poison_requests)
        r.counter("tpu_inf_kv_integrity_rejections_total",
                  "KV blobs rejected on a failed end-to-end digest "
                  "check (recompute fallback, never adopted silently)",
                  fn=lambda: sum(e.kv_integrity_rejections
                                 for e in self.engines)
                  + self.fabric.kv_rejections)
        r.counter("tpu_inf_route_prefix_hits_total",
                  "Dispatches routed with a non-zero prefix-cache peek "
                  "(the request landed on a warm replica)",
                  fn=lambda: self.route_prefix_hits)
        r.counter("tpu_inf_route_cold_total",
                  "Dispatches routed with no cached prefix on any scored "
                  "replica (least-loaded fallback)",
                  fn=lambda: self.route_cold)
        self._route_hit_pages_hist = r.histogram(
            "tpu_inf_route_hit_pages",
            "Peeked prefix-cache hit pages per warm-routed dispatch",
            buckets=telemetry.COUNT_BUCKETS)
        r.counter("tpu_inf_route_fabric_hits_total",
                  "Dispatches that pulled fabric pages into the routed "
                  "replica's host tier (fourth-temperature warmth)",
                  fn=lambda: self.route_fabric_hits)
        self._route_fabric_hit_pages_hist = r.histogram(
            "tpu_inf_route_fabric_hit_pages",
            "Fabric pages pulled per fabric-warm dispatch",
            buckets=telemetry.COUNT_BUCKETS)
        telemetry.register_fabric(r, self.fabric)
        for i, health in enumerate(self.health):
            r.gauge("tpu_inf_replica_routable",
                    "1 when the replica accepts traffic (not quarantined)",
                    fn=lambda h=health: float(h.routable),
                    replica=str(i))
            r.counter("tpu_inf_replica_quarantines_total",
                      "Entries into the quarantined state",
                      fn=lambda h=health: h.quarantines, replica=str(i))
            r.counter("tpu_inf_replica_wedges_total",
                      "Step-watchdog firings (wedged dispatches)",
                      fn=lambda h=health: h.wedges, replica=str(i))
        # Fleet-level rolling SLO gauges: EXACT quantiles pooled across
        # every replica's window (the per-replica series render from
        # each engine's own registry under replica="i" labels).
        telemetry.register_fleet_slo(
            r, self._pooled_slo_quantile,
            lambda k: sum(getattr(e.telemetry.slo, f"{k}_breaches", 0)
                          for e in self.engines
                          if e.telemetry.slo is not None))
        # Dashboard-join info gauge, on the fleet registry AND every
        # replica registry (label values are pure config: identical
        # across replicas and restarts).
        ecfg = self.engines[0].engine_cfg
        kw = dict(device=self.engines[0].device_info(),
                  fleet=self.server_cfg.fleet,
                  kv_quant=ecfg.kv_quant,
                  spec_mode=("ngram" if self.engines[0].spec_enabled
                             else "off"),
                  routing=self.server_cfg.routing)
        telemetry.emit_build_info(r, **kw)
        for e in self.engines:
            if e.telemetry.enabled:
                telemetry.emit_build_info(e.telemetry.registry, **kw)
        # Crash flight recorders (one per replica) when the operator
        # configured --blackbox-dir; direct-constructed test groups
        # leave it '' and do no disk I/O.
        if self.server_cfg.blackbox_dir:
            import dataclasses as _dc
            for i, (e, s) in enumerate(zip(self.engines,
                                           self.schedulers)):
                telemetry.attach_flight_recorder(
                    e.telemetry, self.server_cfg.blackbox_dir, i,
                    retain=self.server_cfg.blackbox_retain,
                    config=_dc.asdict(self.server_cfg),
                    stats_fn=lambda s=s, e=e: s.stats.snapshot(e))

    def _pooled_slo_quantile(self, which: str, q: float) -> float:
        windows = []
        for e in self.engines:
            slo = e.telemetry.slo
            if slo is not None:
                ring = slo.ttft if which == "ttft" else slo.tpot
                windows.append(ring.values())
        v = telemetry.pooled_quantile(windows, q)
        return float("nan") if v is None else v

    def _fleet_slo(self) -> dict:
        return telemetry.pooled_slo(
            [e.telemetry.slo.snapshot() for e in self.engines
             if e.telemetry.slo is not None])

    @property
    def engine(self) -> InferenceEngine:
        """Primary replica (single-engine callers, tests)."""
        return self.engines[0]

    def warmup(self) -> float:
        return sum(e.warmup() for e in self.engines)

    def start(self) -> "EngineGroup":
        for s in self.schedulers:
            s.start()
        self._watch_stop.clear()
        self._watch_thread = threading.Thread(
            target=self._watch, name="replica-watchdog", daemon=True)
        self._watch_thread.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        self._watch_stop.set()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=5.0)
            self._watch_thread = None
        for s in self.schedulers:
            s.stop(drain=drain, timeout=timeout)

    # ------------------------------------------------------- supervision

    def _watch_interval(self) -> float:
        cfg = self.server_cfg
        interval = 0.25
        if cfg.step_watchdog_s > 0:
            interval = min(interval, cfg.step_watchdog_s / 5)
        if cfg.quarantine_cooldown_s > 0:
            interval = min(interval, max(0.05, cfg.quarantine_cooldown_s / 5))
        return max(0.02, interval)

    def _wedged(self, sched: EngineScheduler) -> bool:
        wd = self.server_cfg.step_watchdog_s
        t0 = sched.step_inflight_since
        return wd > 0 and t0 is not None and time.monotonic() - t0 > wd

    def _watch(self) -> None:
        """Monitor thread: watchdog deadlines + quarantine cooldowns."""
        interval = self._watch_interval()
        while not self._watch_stop.wait(interval):
            for sched, health in zip(self.schedulers, self.health):
                if self._wedged(sched):
                    if health.mark_wedged():
                        flight = sched.engine.telemetry.flight
                        if flight is not None:
                            # The wedged dispatch's records are still
                            # the newest in the ring — dump them now.
                            flight.capture("watchdog")
                        self._failover_stranded(sched)
                else:
                    health.maybe_recover()

    def _routable(self) -> List[EngineScheduler]:
        out = []
        for sched, health in zip(self.schedulers, self.health):
            # Lazy cooldown check too, so a fleet whose monitor tick has
            # not fired yet (or tests driving the group directly) still
            # re-admits a cooled-down replica at submit time.
            if not self._wedged(sched):
                health.maybe_recover()
            if health.routable:
                out.append(sched)
        return out

    @staticmethod
    def _route_key(sched: EngineScheduler):
        """Least-loaded routing, preferring replicas whose KV pool is
        not under preemption pressure: a request routed to a pressured
        replica would likely trigger (or suffer) a preemption that a
        sibling with free pages avoids entirely."""
        return kv_fabric.cold_route_key(sched.engine.under_pressure,
                                        sched.load)

    def _rotate(self, ties: list):
        """Deterministic rotating pick among equal-key candidates.
        min() always returned the first — under a burst of equal-load
        (or equally cold) replicas everything herded onto replica 0.
        The cursor is a plain int: racy increments just skew the
        rotation, never the correctness of the pick."""
        if len(ties) == 1:
            return ties[0]
        idx = self._rr % len(ties)
        self._rr += 1
        return ties[idx]

    def _digests_for(self, seq: Sequence) -> Tuple[List[bytes], int]:
        """THE truncation/trim rule for routing-time prefix digests,
        shared by every scoring site so router math can never drift
        from engine lookup: keep the most recent max_context-1 tokens,
        never count the final prompt token (its logits are always
        recomputed). Chain-hashes the prompt ONCE per request — the
        list is cached on the Sequence and reused by admission lookup,
        publish, failover replays, and the admission-cap fallback (all
        replicas serve one EngineConfig, so page_size/max_context
        agree). The cached list may carry one extra final-page digest
        from an engine-side fill; the cap trims it. Returns
        (digests, prompt_pages)."""
        ecfg = self.engines[0].engine_cfg
        prompt_len = min(len(seq.prompt_tokens), ecfg.max_context - 1)
        prompt_pages = kvc.pages_needed(prompt_len, ecfg.page_size)
        cap = (prompt_len - 1) // ecfg.page_size
        if cap <= 0:
            return [], prompt_pages
        if seq.prefix_digests is None:
            tokens = seq.prompt_tokens
            prompt = (tokens[-prompt_len:] if len(tokens) > prompt_len
                      else tokens)
            seq.prefix_digests = _chain_hashes(prompt, ecfg.page_size)
        return seq.prefix_digests[:cap], prompt_pages

    def _pick(self, cands: List[EngineScheduler],
              seq: Optional[Sequence] = None
              ) -> Tuple[EngineScheduler, Tuple[int, int, int]]:
        """Choose a replica for one request; returns (scheduler,
        (hbm_hit_pages, host_hit_pages, fabric_extra_pages) peeked on
        that scheduler).

        prefix_affinity with a token-bearing request scores each
        candidate in KV-page units across FOUR temperatures — HBM-warm
        > host-warm > fabric-warm > cold (README "KV fabric") — via
        kv_fabric.prefill_route_score, THE formula both fleet backends
        share: the prefill work this replica would actually redo (a
        host-tier page saves the prefill compute but still pays a
        host->device swap-in; a fabric page additionally pays the pool
        pull, so it scores below host at the default weights) plus a
        queue-depth blend, plus a pressure penalty sized so that at the
        default hit weight a fully-warm pressured replica still loses
        to a cold idle one. The fabric term counts only the pages the
        pool covers BEYOND the candidate's own warm depth, from the
        router's own local index — no extra RPC. Ties break by the
        legacy (pressure, load) key, then rotate. When NO candidate
        holds any prefix page in either tier and the fabric holds none
        (or routing="least_loaded"), the score reduces to (pressure,
        load) + rotation — plain least-loaded. A single warm candidate
        is still peeked so the routing counters and span report the
        true hit (e.g. the lone survivor of a quarantined fleet must
        not read as a cold dispatch).

        The digest list computed here is cached on the Sequence
        (prefix_digests) so admission and publish reuse the same single
        hash pass over the prompt.
        """
        cfg = self.server_cfg
        if seq is not None and cfg.routing == "prefix_affinity":
            digests, prompt_pages = self._digests_for(seq)
            fdepth = self.fabric.match_depth(digests)
            hits = []
            for sched in cands:
                pc = sched.engine.prefix_cache
                hits.append(pc.peek_digests_tiered(digests)
                            if pc is not None else (0, 0))
            if any(h + w for h, w in hits) or fdepth > 0:
                scored = []
                for sched, (hbm, host) in zip(cands, hits):
                    fx = kv_fabric.fabric_extra_pages(
                        fdepth, hbm + host, prompt_pages)
                    pressured = sched.engine.under_pressure
                    score = kv_fabric.prefill_route_score(
                        cfg, prompt_pages=prompt_pages, hbm=hbm,
                        host=host, fabric=fx, load=sched.load,
                        pressured=pressured)
                    scored.append(((score, pressured, sched.load),
                                   sched, (hbm, host, fx)))
                best = min(key for key, _, _ in scored)
                return self._rotate([(s, h) for key, s, h in scored
                                     if key == best])
            # Cold everywhere: least-loaded fall-through (hit 0 is the
            # truth, not an accounting shortcut).
        keyed = [(self._route_key(sched), sched) for sched in cands]
        best = min(key for key, _ in keyed)
        return self._rotate([(s, (0, 0, 0)) for key, s in keyed
                             if key == best])

    def _peek_replica(self, sched: EngineScheduler,
                      seq: Sequence) -> Tuple[int, int, int]:
        """One replica's peeked (hbm, host, fabric_extra) hit pages for
        a request (accounting on paths that chose by load, e.g. the
        admission-cap fallback). Reuses the digest list _pick just
        cached on the Sequence — the fallback fires on exactly the
        overloaded path where a second full hash pass would hurt most."""
        if self.server_cfg.routing != "prefix_affinity":
            return (0, 0, 0)
        pc = sched.engine.prefix_cache
        if pc is None:
            return (0, 0, 0)
        digests, prompt_pages = self._digests_for(seq)
        hbm, host = pc.peek_digests_tiered(digests)
        fx = kv_fabric.fabric_extra_pages(
            self.fabric.match_depth(digests), hbm + host, prompt_pages)
        return (hbm, host, fx)

    def _least_loaded(self) -> EngineScheduler:
        routable = self._routable()
        if not routable:
            raise FleetUnavailable(
                "all replicas quarantined",
                self._retry_after())
        return self._pick(routable)[0]

    def _retry_after(self) -> float:
        return self.server_cfg.retry_after_s

    def embed_many(self, batch) -> "np.ndarray":  # noqa: F821
        """Embeddings on the least-loaded replica — pinning them to
        replica 0 would interleave dense forwards with its decode loop
        while the other replicas idle."""
        try:
            sched = self._least_loaded()
        except FleetUnavailable:
            # Same counter as submit(): embed 503s must be visible in
            # /healthz and stats, not just generate ones.
            with self._lock:
                self.requests_unavailable += 1
            raise
        return sched.engine.embed_many(batch)

    # -------------------------------------------------------- submission

    def submit(self, seq: Sequence, on_token: Callable,
               on_finish: Callable) -> None:
        """Route to the best healthy replica (prefix affinity blended
        with load/pressure; see _pick).

        Raises FleetUnavailable (no routable replica) or FleetSaturated
        (admission queue cap) instead of queueing — the HTTP layer maps
        these to 503/429 with Retry-After. Scheduler-level rejections
        (queue_full, too_large) still arrive via on_finish.
        """
        # Trace-id propagation: mint when the ingress didn't (direct
        # group submits from benchmarks/tests) so logs and spans are
        # joinable under one id on every path.
        if not seq.trace_id:
            import uuid
            seq.trace_id = uuid.uuid4().hex[:16]
        routable = self._routable()
        if not routable:
            with self._lock:
                self.requests_unavailable += 1
            raise FleetUnavailable(
                "all replicas quarantined", self._retry_after())
        t_route = time.perf_counter()
        sched, hit_pages = self._pick(routable, seq)
        self._recorder.add(
            "route", seq.trace_id, t_route, time.perf_counter(),
            dest=self.schedulers.index(sched),
            hbm_hit=hit_pages[0], host_hit=hit_pages[1],
            fabric_hit=hit_pages[2])
        cap = self.server_cfg.admission_queue_depth
        if cap > 0 and sched.load >= cap:
            # The affinity pick can saturate a warm replica while a cold
            # sibling still has room: fall back to least-loaded before
            # shedding, so 429s only fire when the whole fleet is full —
            # then re-peek the fallback so the span/counters report its
            # real warmth, not a hardcoded cold.
            sched = self._pick(routable)[0]
            hit_pages = self._peek_replica(sched, seq)
            if sched.load >= cap:
                with self._lock:
                    self.requests_shed += 1
                # A shed IS terminal: seal the route span so sustained
                # overload can't fill the recorder's open table and
                # evict a LIVE request's trace.
                self._recorder.seal(seq.trace_id)
                raise FleetSaturated(
                    f"admission queue cap reached ({sched.load} >= {cap} "
                    "on the least-loaded replica)", self._retry_after())
        entry = _Tracked(template=_clone_request(seq), on_token=on_token,
                         on_finish=on_finish, sched=sched,
                         t_submit=time.perf_counter())
        with self._lock:
            self._tracked[seq.request_id] = entry
        self._dispatch(entry, seq, sched, hit_pages)

    def _dispatch(self, entry: _Tracked, seq: Sequence,
                  sched: EngineScheduler,
                  hit_pages: Tuple[int, int, int] = (0, 0, 0)) -> None:
        gen = entry.generation
        entry.sched = sched
        # Mark the span: attempt >= 1 means this is a failover
        # resubmission — the timeline/logs distinguish replays.
        seq.attempt = entry.attempts
        # Routing span + fleet accounting: every dispatch (initial or
        # failover resubmission) is one routing decision. hit_pages is
        # the tiered peek (hbm, host, fabric_extra) the router counted
        # on.
        idx = self.schedulers.index(sched)
        hbm_hit, host_hit, fabric_extra = hit_pages
        # Fabric pull (README "KV fabric"): pages the pool covers
        # beyond this replica's own warm depth land in its host tier
        # via request_import_host BEFORE dispatch — the engine loop
        # applies pending imports ahead of admission, so this request's
        # prefill sees them. crc-verified by get_pages; a corrupt or
        # evicted-since-peek entry just shortens the run.
        fabric_pulled = 0
        if fabric_extra > 0:
            digests = self._digests_for(seq)[0]
            warm = hbm_hit + host_hit
            entries = self.fabric.get_pages(
                digests[warm:warm + fabric_extra])
            if entries:
                sched.engine.request_import_host(entries)
                sched.kick()
                fabric_pulled = len(entries)
        seq.routed_replica = idx
        seq.route_hit_pages = hbm_hit + host_hit + fabric_pulled
        seq.route_host_hit_pages = host_hit
        seq.route_fabric_hit_pages = fabric_pulled
        total_hit = seq.route_hit_pages
        stats = self._route_stats[idx]
        if total_hit > 0:
            self.route_prefix_hits += 1
            stats["hits"] += 1
            stats["hit_pages"] += total_hit
            stats["host_hit_pages"] += host_hit
            self._route_hit_pages_hist.observe(total_hit)
        else:
            self.route_cold += 1
            stats["cold"] += 1
        if fabric_pulled > 0:
            self.route_fabric_hits += 1
            stats["fabric_hit_pages"] += fabric_pulled
            self._route_fabric_hit_pages_hist.observe(fabric_pulled)

        def tok(s: Sequence, t: int) -> None:
            if entry.generation != gen:     # stale attempt (failed over)
                return
            entry.delivered += 1
            entry.on_token(s, t)

        def fin(s: Sequence) -> None:
            self._attempt_finished(entry, s, gen)

        sched.submit(seq, tok, fin)

    def _retry_target(self, failed: EngineScheduler,
                      template: Optional[Sequence] = None
                      ) -> Optional[Tuple[EngineScheduler,
                                          Tuple[int, int, int]]]:
        """Replica for a failover resubmission (and its peeked hit
        pages): affinity composes with failover — the replay prefers a
        sibling already holding the prompt's pages, but never the
        scheduler that just failed when an alternative exists."""
        routable = self._routable()
        others = [s for s in routable if s is not failed]
        pool = others or routable           # degraded-but-routable self ok
        return self._pick(pool, template) if pool else None

    def _attempt_finished(self, entry: _Tracked, seq: Sequence,
                          gen: int) -> None:
        """Terminal or retryable end of one attempt (engine thread).

        The whole decision — is this attempt still current, does it
        retry, which counters move — happens under one lock hold, so it
        cannot interleave with _failover_stranded deciding about the
        same entry from the watchdog thread (whoever bumps generation
        first wins; the loser returns without acting)."""
        rid = entry.template.request_id
        with self._lock:
            if entry.generation != gen:     # stranded failover took over
                return
            if seq.finish_reason in _RETRYABLE:
                entry.failed_replicas.add(
                    self.schedulers.index(entry.sched))
            limit = self.server_cfg.poison_max_workers
            poison = (seq.finish_reason in _RETRYABLE and limit > 0
                      and len(entry.failed_replicas) >= limit)
            retryable = (not poison
                         and seq.finish_reason in _RETRYABLE
                         and entry.delivered == 0
                         and entry.attempts
                         < self.server_cfg.failover_max_retries)
            target = (self._retry_target(entry.sched, entry.template)
                      if retryable else None)
            if target is not None:
                entry.attempts += 1
                entry.generation += 1
                self.retries_attempted += 1
            else:
                self._tracked.pop(rid, None)
                if poison:
                    self.poison_requests += 1
                if entry.attempts and seq.finish_reason in ("stop", "length"):
                    self.retries_succeeded += 1
        if target is not None:
            self._dispatch(entry, _clone_request(entry.template), *target)
            return
        if poison:
            # Every attempt errored a DIFFERENT replica: quarantine the
            # request terminally (structured 500) before it burns the
            # rest of the fleet.
            telemetry.log_event(
                "poison_quarantined", level="error",
                request_id=entry.template.trace_id or str(rid),
                replicas=sorted(entry.failed_replicas),
                attempts=entry.attempts)
            seq.finish_reason = "poison"
        self._finish_trace(entry, seq.finish_reason)
        entry.on_finish(seq)

    def _finish_trace(self, entry: _Tracked, reason: str) -> None:
        """Terminal end of a tracked request: the router-side root span
        (submit -> terminal) + seal, mirroring the subprocess router.
        The engine-side recorders sealed their phase spans at the
        scheduler's finish; /debug/trace joins the two."""
        t = entry.template
        tid = t.trace_id or str(t.request_id)
        self._recorder.add("request", tid, entry.t_submit or
                           time.perf_counter(), time.perf_counter(),
                           parent="", reason=reason,
                           attempts=entry.attempts,
                           output_tokens=entry.delivered)
        self._recorder.seal(tid)

    def _failover_stranded(self, sched: EngineScheduler) -> None:
        """A replica was quarantined by the watchdog mid-dispatch: its
        engine thread may be stuck for minutes (or forever), so its
        requests cannot finish through callbacks. Detach them here and
        resubmit (zero tokens delivered, budget left) or fail them
        cleanly; flag the originals done so the wedged thread, if it ever
        wakes, reaps them instead of streaming into the void."""
        actions = []
        with self._lock:
            # Decide everything inside one lock hold (see
            # _attempt_finished): the generation bump atomically orphans
            # both late wake-up callbacks AND any _attempt_finished
            # racing from the wedged engine thread.
            limit = self.server_cfg.poison_max_workers
            for rid, entry in list(self._tracked.items()):
                if entry.sched is not sched:
                    continue
                entry.generation += 1
                entry.failed_replicas.add(self.schedulers.index(sched))
                poison = (limit > 0
                          and len(entry.failed_replicas) >= limit)
                target = self._retry_target(sched, entry.template)
                can_retry = (not poison
                             and entry.delivered == 0
                             and entry.attempts
                             < self.server_cfg.failover_max_retries
                             and target is not None)
                if can_retry:
                    entry.attempts += 1
                    self.retries_attempted += 1
                    self.failovers += 1
                else:
                    self._tracked.pop(rid, None)
                    if poison:
                        self.poison_requests += 1
                actions.append((rid, entry, can_retry, target, poison))
        for rid, entry, can_retry, target, poison in actions:
            sched.cancel(rid)               # reap-on-wake; frees queue slot
            telemetry.log_event(
                "request_failover", level="warning",
                request_id=entry.template.trace_id or str(rid),
                resubmitted=can_retry, attempts=entry.attempts)
            if can_retry:
                self._dispatch(entry, _clone_request(entry.template), *target)
            else:
                if poison:
                    telemetry.log_event(
                        "poison_quarantined", level="error",
                        request_id=entry.template.trace_id or str(rid),
                        replicas=sorted(entry.failed_replicas),
                        attempts=entry.attempts)
                ghost = _clone_request(entry.template)
                ghost.done = True
                ghost.finish_reason = ("poison" if poison
                                       else "unavailable" if target is None
                                       else "error")
                ghost.finish_time = time.perf_counter()
                self._finish_trace(entry, ghost.finish_reason)
                entry.on_finish(ghost)

    def cancel(self, request_id: int) -> None:
        # Pop (not get): a request cancelled while still QUEUED never
        # reaches _finish/on_finish, so the tracked entry must be released
        # here or it leaks one dict entry per timed-out/disconnected
        # request. Double-pop from a later on_finish is harmless.
        with self._lock:
            entry = self._tracked.pop(request_id, None)
            if entry is not None:
                entry.generation += 1       # silence in-flight callbacks
        if entry is not None:
            entry.sched.cancel(request_id)

    # ----------------------------------------------------- observability

    def health_snapshot(self) -> dict:
        """Operator view served by /healthz: per-replica states + fleet
        status + shed/retry counters."""
        replicas = []
        for i, (h, e) in enumerate(zip(self.health, self.engines)):
            d = h.snapshot()
            # KV-pool pressure view: operators (and load balancers) see
            # which replicas are burning headroom before they quarantine.
            d["device"] = e.device_info()
            d["pool_pressure"] = round(e.pool_pressure, 4)
            d["under_pressure"] = e.under_pressure
            d["preemptions"] = e.preemptions_total
            # Affinity view: warm/cold dispatches this replica received
            # and the cached pages the router counted on — the numbers
            # that say whether conversations are actually sticking.
            d["routing"] = dict(self._route_stats[i])
            # Rolling SLO view (quantiles + breach counts).
            if e.telemetry.slo is not None:
                d["slo"] = e.telemetry.slo.snapshot(include_window=False)
            # Tiered KV cache view: host-tier residency + swap churn
            # (absent when the tier is disabled on this replica).
            if e.host_pool is not None:
                d["host_cache"] = {
                    "capacity_pages": e.host_pool.capacity,
                    "pages_used": e.host_pool.used,
                    "offloaded": e.host_pool.offloaded_total,
                    "restored": e.host_pool.restored_total,
                    "evicted": e.host_pool.evicted_total,
                    "swap_in_resumes": e.swap_in_resumes,
                }
            replicas.append(d)
        routable = sum(1 for h in self.health if h.routable)
        if routable == 0:
            status = "unavailable"
        elif all(r["state"] == HEALTHY for r in replicas):
            status = "ok"
        else:
            status = "degraded"
        return {
            "status": status,
            "routing": self.server_cfg.routing,
            "replicas": replicas,
            # Fleet-aggregated rolling SLO view (pooled exact
            # quantiles; the autoscaler's input signal).
            "slo": self._fleet_slo(),
            # Fleet KV fabric pool occupancy + churn (README "KV
            # fabric"); same shape under both fleet backends.
            "fabric": self.fabric.snapshot(),
            "supervision": self.supervision_counters(),
        }

    def supervision_counters(self) -> dict:
        with self._lock:
            return {
                "retries_attempted": self.retries_attempted,
                "retries_succeeded": self.retries_succeeded,
                "failovers": self.failovers,
                "requests_shed": self.requests_shed,
                "requests_unavailable": self.requests_unavailable,
                "poison_requests": self.poison_requests,
                "kv_integrity_rejections": sum(
                    e.kv_integrity_rejections for e in self.engines)
                + self.fabric.kv_rejections,
                "route_prefix_hits": self.route_prefix_hits,
                "route_cold": self.route_cold,
                "route_fabric_hits": self.route_fabric_hits,
                "fabric_puts": self.fabric.puts,
                "fabric_hits": self.fabric.hits,
                "preemptions": sum(e.preemptions_total
                                   for e in self.engines),
                "recompute_resumes": sum(e.resumes_total
                                         for e in self.engines),
                "states": [h.state for h in self.health],
            }

    def prometheus_text(self) -> str:
        """Standards-compliant Prometheus text page: every replica's
        engine registry under a ``replica="i"`` label plus the fleet
        registry (supervision counters, replica health gauges)."""
        groups = [({"replica": str(i)}, s.engine.telemetry.registry)
                  for i, s in enumerate(self.schedulers)]
        groups.append(({}, self._fleet_registry))
        return telemetry.render_prometheus(groups)

    def recent_snapshot(self, n: int) -> List[dict]:
        """Most recent n finished-request timelines ACROSS replicas
        (merged by completion time — a plain tail would show only the
        last replica's view)."""
        items: List[dict] = []
        for s in self.schedulers:
            items.extend(s.recent_snapshot(n))
        items.sort(key=lambda t: t.get("finished_unix", 0.0))
        return items[-n:]

    # -------------------------------------------- tracing + profiling

    def _trace_spans(self, trace_id: str) -> List[dict]:
        spans = self._recorder.get_trace(trace_id) or []
        for e in self.engines:
            spans.extend(e.telemetry.recorder.get_trace(trace_id) or ())
        return spans

    def trace_snapshot(self, trace_id: str) -> Optional[dict]:
        """One request's assembled span tree (GET /debug/trace?id=):
        router-side spans + every replica recorder's spans for the
        trace, joined in place (no transport in-process)."""
        spans = self._trace_spans(trace_id)
        if not spans:
            return None
        return telemetry.assemble_trace(trace_id, spans)

    def trace_chrome(self, n: int = 128) -> dict:
        """The recent-request ring as Chrome trace-event JSON (GET
        /debug/trace?format=chrome), one pid per replica + pid 0 for
        the group's routing spans — loadable in Perfetto."""
        traces = {tid: self._trace_spans(tid)
                  for tid in self._recorder.recent_traces(n)}
        maintenance: List[dict] = []
        for e in self.engines:
            maintenance.extend(e.telemetry.recorder.maintenance_spans())
        return telemetry.spans_to_chrome(
            traces,
            {0: "router", **{i + 1: f"replica {i}"
                             for i in range(len(self.engines))}},
            maintenance=maintenance,
            other_data={"fleet": self.server_cfg.fleet,
                        "spans_dropped": self._recorder.spans_dropped})

    def capture_profile(self, replica: int, seconds: float) -> dict:
        """POST /debug/profile {"seconds": N}: run a jax.profiler
        capture in this process (all in-process replicas share one jax
        runtime: the replica argument names the trace dir and whose loop
        clock the response's ``loop`` reads)."""
        return telemetry.capture_jax_profile(
            self.server_cfg.profile_dir, replica, seconds,
            self.engines[replica].telemetry)

    def stats_snapshot(self) -> dict:
        """Aggregate counters + per-replica breakdown."""
        per = [s.stats.snapshot(s.engine) for s in self.schedulers]
        for d, h in zip(per, self.health):
            d["health"] = h.snapshot()
        return aggregate_replica_stats(per, self.supervision_counters())

    def steps_snapshot(self, since: Optional[float] = None,
                       until: Optional[float] = None,
                       records: bool = False) -> dict:
        """Step-ledger roofline attribution (GET /debug/steps):
        per-replica bottleneck verdicts + the fleet-merged report, over
        the trailing 60 s or the ``since`` / ``until`` interval."""
        reports = {str(i): e.telemetry.steps_report(
                       since=since, until=until, records=records)
                   for i, e in enumerate(self.engines)}
        return {"replicas": reports,
                "fleet": telemetry.merge_steps_reports(
                    list(reports.values()))}

    def blackbox_index(self) -> dict:
        """Flight-recorder capture index (GET /debug/blackbox) — scans
        the operator's blackbox_dir; every replica is in-process here,
        so there is nothing to harvest, only to list."""
        return telemetry.blackbox_index(self.server_cfg.blackbox_dir)

    def apply_chaos(self, body: dict) -> dict:
        """Arm/disarm engine-level fault injection (POST /debug/chaos):
        ``{"replica": i | null, "step_failure_rate": p, "step_wedge_s":
        s, "page_pressure": n}`` — null replica applies to all. The
        subprocess fleet adds process-level verbs ("kill"); here they
        are a usage error (there is no process to kill in-process —
        chaos_step_wedge_s is the in-process simulation). Raises
        ValueError/IndexError/TypeError on bad specs (HTTP 400)."""
        if body.get("kill") is not None:
            raise ValueError(
                "'kill' chaos (kill9/sigterm) needs --fleet subprocess; "
                "the in-process fleet simulates faults via "
                "step_failure_rate / step_wedge_s / page_pressure")
        engines = self.engines
        replica = body.get("replica")
        targets = (engines if replica is None
                   else [engines[int(replica)]])
        rate = body.get("step_failure_rate")
        wedge = body.get("step_wedge_s")
        pressure = body.get("page_pressure")
        for eng in targets:
            if rate is not None:
                eng.chaos_step_failure_rate = float(rate)
            if wedge is not None:
                eng.chaos_step_wedge_s = float(wedge)
            if pressure is not None:
                # Holds real pages out of the KV pool (clamped to
                # what's free) — deterministic exhaustion testing.
                # Applied by the engine loop (the allocator is
                # engine-thread only), usually within milliseconds.
                eng.request_page_pressure(int(pressure))

        def _pp(e):
            t = e._pressure_target
            return e.chaos_page_pressure if t is None else t

        return {"replicas": [
            {"step_failure_rate": e.chaos_step_failure_rate,
             "step_wedge_s": e.chaos_step_wedge_s,
             "page_pressure": _pp(e)} for e in engines]}


# Per-chip gauges / config constants that must not be summed across
# replicas. KV page counts SUM (total and in_use together, so fleet
# utilization = in_use/total stays consistent); depth is config.
_NON_ADDITIVE = ("model_params", "approx_flops_per_token",
                 "mean_batch_occupancy", "decode_pipeline_depth",
                 "pool_pressure",
                 # Batch ladder: rung/occupancy are per-replica
                 # states (summing rungs would fabricate a fleet
                 # batch size); re-aggregated below. rung_switches
                 # stays additive (a fleet churn total).
                 "decode_rung", "rung_peak", "lane_occupancy",
                 "mfu_estimate")


def aggregate_replica_stats(per: List[dict], supervision: dict) -> dict:
    """Fold per-replica scheduler snapshots into the fleet stats dict —
    THE aggregation rule, shared by both fleet backends (EngineGroup
    over live scheduler objects; ProcessEngineGroup over stats dicts
    fetched from worker processes), so /metrics?format=json has one
    shape regardless of --fleet."""
    if len(per) == 1:
        out = dict(per[0])
        if isinstance(out.get("slo"), dict):
            # Same window-stripping as the dp>1 path (a copy — the
            # caller may cache the original, windows included).
            out["slo"] = {k: v for k, v in out["slo"].items()
                          if not k.endswith("_window")}
        out["supervision"] = supervision
        return out
    agg = dict(per[0])
    for d in per[1:]:
        for k, v in d.items():
            if (k in _NON_ADDITIVE or isinstance(v, bool)
                    or not isinstance(v, (int, float))):
                continue
            base = agg.get(k, 0)
            agg[k] = (base if isinstance(base, (int, float))
                      and not isinstance(base, bool) else 0) + v
    # Replica 0's health dict would masquerade as the fleet's;
    # per-replica health lives under "replicas", fleet under
    # "supervision". Same for the phase role — a P/D fleet's replicas
    # differ by design, and supervision carries the full role list.
    agg.pop("health", None)
    agg.pop("role", None)
    # Rolling SLO: fleet quantiles must POOL the replicas' raw windows
    # (summing or averaging per-replica quantiles fabricates numbers).
    # After pooling, the ~512-entry windows are stripped from the
    # per-replica views COPIES (never the caller's dicts — the
    # subprocess router caches them, windows included, for its pooled
    # gauges): they exist for this aggregation, not for every scrape
    # to carry kilobytes of raw floats.
    if any("slo" in d for d in per):
        agg["slo"] = telemetry.pooled_slo([d.get("slo") for d in per])
        per = [({**d, "slo": {k: v for k, v in d["slo"].items()
                              if not k.endswith("_window")}}
                if isinstance(d.get("slo"), dict) else d)
               for d in per]
    # Fleet phase histograms = element-wise bucket merge across
    # replicas (replica 0's copy would otherwise masquerade as the
    # fleet's); per-replica views stay under "replicas".
    phase_keys = sorted(set().union(
        *(d.get("phases", {}).keys() for d in per)))
    agg["phases"] = {
        k: telemetry.merge_phases(
            [d.get("phases", {}).get(k) for d in per])
        for k in phase_keys}
    agg["mean_batch_occupancy"] = (
        sum(d.get("mean_batch_occupancy", 0.0) for d in per) / len(per))
    # Batch ladder fleet view: active/peak rung = the highest any
    # replica runs (replica 0's copy must not masquerade as the
    # fleet's); occupancy/MFU = fleet means; decode_ladder is the
    # one shared EngineConfig's rungs, identical on every replica.
    # Replica detail stays under "replicas".
    agg["decode_rung"] = max(d.get("decode_rung", 0) for d in per)
    agg["rung_peak"] = max(d.get("rung_peak", 0) for d in per)
    agg["lane_occupancy"] = round(
        sum(d.get("lane_occupancy", 0.0) for d in per) / len(per), 4)
    mfus = [d["mfu_estimate"] for d in per
            if d.get("mfu_estimate") is not None]
    agg["mfu_estimate"] = (round(sum(mfus) / len(mfus), 6)
                           if mfus else None)
    if "prefix_cache" in per[0]:
        agg["prefix_cache"] = {
            k: sum(d.get("prefix_cache", {}).get(k, 0) for d in per)
            for k in per[0]["prefix_cache"]}
    # Fleet decode-dispatch latency = element-wise worst replica (an
    # operator alarms on p99; replica 0's copy masquerading as the
    # fleet number would hide a degraded replica).
    rings = [d.get("decode_call_s") for d in per]
    rings = [r for r in rings if r]
    agg["decode_call_s"] = (
        {k: max(r[k] for r in rings if k in r) for k in rings[0]}
        if rings else None)
    if "speculative" in per[0]:
        specs = [d.get("speculative") or {} for d in per]
        drafted = sum(s.get("drafted", 0) for s in specs)
        accepted = sum(s.get("accepted", 0) for s in specs)
        agg["speculative"] = {
            # Mode/γ are one shared EngineConfig, identical on every
            # replica; counters sum across the fleet.
            "mode": specs[0].get("mode"),
            "gamma": specs[0].get("gamma"),
            "drafted": drafted, "accepted": accepted,
            "acceptance_rate": (accepted / drafted) if drafted else 0.0,
            "rounds": sum(s.get("rounds", 0) for s in specs),
            "fallback_rounds": sum(s.get("fallback_rounds", 0)
                                   for s in specs),
            "throttles": sum(s.get("throttles", 0) for s in specs)}
    agg["replicas"] = per
    agg["dp"] = len(per)
    agg["supervision"] = supervision
    return agg
