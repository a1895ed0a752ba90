"""Continuous-batching scheduler: the host loop that feeds the TPU.

The reference's "Scheduler" (traffic_generator/main.py:53-84) only decides
when the *client* sends requests; this is the missing server-side scheduler
(SURVEY.md §1 "no scheduler-in-the-engine sense").

Design:
- One dedicated engine thread runs the device loop (JAX dispatch blocks the
  caller, so it must stay off the asyncio event loop). The aiohttp server
  submits requests from any thread; token/finish callbacks fire on the
  engine thread, the server's only queue for its event loop, and
  ``on_delivered`` wakes that loop once a turn (the delivery contract is
  at ``TokenCallback`` below).
- FCFS admission with **worst-case page reservation**: a request is admitted
  only when a decode slot is free and the pool can hold its prompt plus its
  full generation budget (OOM-safe admission control, SURVEY.md §5).
- Join/leave at step boundaries: at most ``max_prefills_per_step`` prefills
  per iteration (prefill is the latency-heavy graph), then one batched
  decode step for every active slot.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

import numpy as np

from tpu_inference import telemetry
from tpu_inference.config import class_rank
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine.engine import InferenceEngine, Sequence

# on_token(seq, token_id); on_finish(seq). The delivery contract: both
# fire on the engine thread (submit's rejections on the caller's), per
# request its tokens in order and then its finish, once. A callback may
# only queue what it is given for another thread, because the scheduler
# says when a delivery is over: it calls ``on_delivered`` once for all
# that a turn of the loop handed out (a decode dispatch's tokens and the
# finishes reaped after it; a prefill dispatch's first tokens), and
# always before the loop next stages a dispatch, waits on the device or
# sleeps. Nothing handed out waits across any of those.
TokenCallback = Callable[[Sequence, int], None]
FinishCallback = Callable[[Sequence], None]


@dataclasses.dataclass
class SchedulerStats:
    """Server-side observability counters (SURVEY.md §5)."""

    steps: int = 0
    prefills: int = 0
    # P/D disaggregation (README "P/D disaggregation"): settled prefills
    # handed off to a decode worker instead of decoded locally.
    pd_handoffs: int = 0
    tokens_generated: int = 0
    tokens_prefix_cached: int = 0      # prompt tokens served from KV reuse
    requests_finished: int = 0
    requests_rejected: int = 0
    # Tokens handed to on_token, and the times on_delivered had
    # something to post: their ratio is the tokens a wake-up carries.
    deliver_tokens: int = 0
    deliver_wakeups: int = 0
    step_failures: int = 0             # prefill/decode dispatch exceptions
    preemptions: int = 0               # sequences evicted for pool pressure
    batch_occupancy_sum: float = 0.0
    peak_pages_in_use: int = 0
    # Ring of recent decode-dispatch wall times (seconds): the host-side
    # number decode_steps_per_call / pipeline depth are tuned against.
    # A fixed list + index (not a deque): the engine thread writes while
    # /metrics reads, and list item assignment is GIL-atomic whereas
    # deque iteration raises if mutated mid-scan.
    decode_call_s: List[float] = dataclasses.field(
        default_factory=lambda: [0.0] * 512)
    decode_calls: int = 0

    def record_decode_call(self, seconds: float) -> None:
        self.decode_call_s[self.decode_calls % len(self.decode_call_s)] = \
            seconds
        self.decode_calls += 1

    def _decode_call_percentiles(self, pipelined: bool) -> Optional[Dict]:
        n = min(self.decode_calls, len(self.decode_call_s))
        if n == 0:
            return None
        xs = sorted(self.decode_call_s[:n])
        pick = lambda p: xs[min(n - 1, int(p * n))]  # noqa: E731
        return {"p50": round(pick(0.50), 6), "p99": round(pick(0.99), 6),
                # With pipeline depth > 1 decode_steps_pipelined returns
                # after a NON-blocking dispatch, so these percentiles
                # measure host dispatch overhead, not decode wall time —
                # label the semantics so operators don't compare across
                # modes (ADVICE r3).
                "measures": "dispatch" if pipelined else "call"}

    def snapshot(self, engine: InferenceEngine) -> Dict:
        occ = (self.batch_occupancy_sum / self.steps) if self.steps else 0.0
        total = engine.engine_cfg.num_pages - 1
        out = {
            "steps": self.steps,
            "prefills": self.prefills,
            "tokens_generated": self.tokens_generated,
            "tokens_prefix_cached": self.tokens_prefix_cached,
            "requests_finished": self.requests_finished,
            "requests_rejected": self.requests_rejected,
            "step_failures": self.step_failures,
            # Admission & preemption (README "Admission & preemption"):
            # mode, watermark evictions, resume prefills, and how much
            # of the pool is pinned right now.
            "admission": engine.admission,
            "preemptions": engine.preemptions_total,
            "recompute_resumes": engine.resumes_total,
            # Tiered KV cache: resumes whose published pages survived
            # (HBM or host tier) and swapped in instead of recomputing.
            "swap_in_resumes": engine.swap_in_resumes,
            # KV page migration (README "Process fleet"): pages exported
            # at drain / imported from a sibling replica's drain.
            "migrate_out_pages": engine.migrate_out_pages,
            "migrate_in_pages": engine.migrate_in_pages,
            # P/D disaggregation (README "P/D disaggregation"): this
            # worker's phase role, prefills handed off to decode
            # workers, and handed-off sequences adopted here (KV
            # restored + decode resumed, zero recompute).
            "role": engine.role,
            "pd_handoffs": self.pd_handoffs,
            "pd_adoptions": engine.adoptions_in,
            "pd_adopt_fallbacks": engine.adopt_fallbacks,
            # Hybrid prefill-decode stepping (README "Scheduling"):
            # whether chunks fuse into decode dispatches, and how many
            # fused dispatches have run.
            "hybrid_prefill": engine.engine_cfg.hybrid_prefill,
            "hybrid_steps": engine.hybrid_steps_total,
            "pool_pressure": round(engine.pool_pressure, 4),
            "mean_batch_occupancy": occ,
            # Batch ladder (README "Batch ladder"): compiled rungs, the
            # rung the latest dispatch ran, the highest rung reached,
            # graph switches, current lane occupancy over the top rung,
            # and the scrape-window MFU estimate.
            "decode_ladder": list(engine.ladder),
            "decode_rung": engine.decode_rung,
            "rung_peak": engine.rung_peak,
            "rung_switches": engine.rung_switches_total,
            "lane_occupancy": round(
                sum(s is not None for s in engine.slots)
                / max(engine.ladder[-1], 1), 4),
            "mfu_estimate": engine.telemetry.mfu_estimate(),
            "kv_pages_total": total,
            "kv_pages_in_use": total - engine.allocator.num_free,
            "peak_pages_in_use": self.peak_pages_in_use,
            "model_params": engine.n_params,
            # ~2 FLOPs per param per decoded token; divide tokens/s by
            # chip peak to get MFU.
            "approx_flops_per_token": 2 * engine.n_params,
            "attn_backend": engine.attn_backend,
            "quant": engine.engine_cfg.quant,
            "kv_quant": engine.engine_cfg.kv_quant,
            "decode_pipeline_depth": engine.engine_cfg.decode_pipeline_depth,
            "decode_call_s": self._decode_call_percentiles(
                engine.engine_cfg.decode_pipeline_depth > 1),
        }
        if engine.prefix_cache is not None:
            out["prefix_cache"] = engine.prefix_cache.stats()
        if engine.spec_enabled:
            d, a = engine.spec_drafted, engine.spec_accepted
            out["speculative"] = {
                # The one proposal source (tests and the replay harness
                # read the key) + configured γ.
                "mode": "ngram",
                "gamma": engine.engine_cfg.num_speculative_tokens,
                "drafted": d, "accepted": a,
                "acceptance_rate": (a / d) if d else 0.0,
                # Round mix: verify rounds vs plain-decode
                # fallbacks (no lane proposed), and γ=0 throttle events.
                "rounds": engine.spec_rounds_total,
                "fallback_rounds": engine.spec_fallback_rounds,
                "throttles": engine.spec_throttles_total,
            }
        # Rolling SLO view (README "Observability": SLO gauges): exact
        # windowed TTFT/TPOT quantiles + breach counts, with the raw
        # ring values so fleet aggregation can pool EXACT quantiles
        # across replicas. Absent when TPU_INF_TELEMETRY=0.
        if engine.telemetry.slo is not None:
            out["slo"] = engine.telemetry.slo.snapshot()
        # Step-phase histograms (telemetry.py): dispatch wall, bubble,
        # queue-wait, per-request phases — cumulative buckets + estimated
        # percentiles, diffable across scrapes (benchmarks commit the
        # diff as phase_breakdown). Empty dict when TPU_INF_TELEMETRY=0.
        out["phases"] = engine.telemetry.phase_snapshot()
        return out


@dataclasses.dataclass
class _Pending:
    seq: Sequence
    on_token: TokenCallback
    on_finish: FinishCallback


class EngineScheduler:
    """Threaded continuous-batching loop around an InferenceEngine."""

    def __init__(self, engine: InferenceEngine,
                 max_prefills_per_step: Optional[int] = None,
                 idle_sleep_s: float = 0.001):
        self.engine = engine
        if max_prefills_per_step is None:
            # Default to the engine's batched-prefill width: a burst of
            # arrivals shares one [P, S] dispatch instead of queueing
            # behind P serial prefills.
            max_prefills_per_step = engine.engine_cfg.max_prefill_batch
        self.max_prefills_per_step = max_prefills_per_step
        self.idle_sleep_s = idle_sleep_s
        self.stats = SchedulerStats()
        # Read-through Prometheus counters over this scheduler's stats
        # (steps/prefills/tokens/queue depth) join the engine's registry.
        engine.telemetry.bind_scheduler(self)
        # Per-request event timeline ring (SURVEY.md §5 observability:
        # "per-request event timeline: enqueue -> schedule -> prefill ->
        # decode -> stream"). Read by /debug/requests.
        self.recent: Deque[dict] = collections.deque(maxlen=256)
        self._waiting: Deque[_Pending] = collections.deque()
        self._callbacks: Dict[int, _Pending] = {}
        # At most one multi-chunk prompt prefills incrementally (one
        # chunk per loop iteration) so decode keeps running in between.
        self._prefilling: Optional[_Pending] = None
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Supervision hooks (set by EngineGroup): fire on the engine
        # thread after every dispatch. step_inflight_since is the
        # monotonic start of the dispatch currently on device, or None —
        # the watchdog reads it from the monitor thread (GIL-atomic
        # float/None store, no lock on the hot path).
        self.step_inflight_since: Optional[float] = None
        self.on_step_ok: Optional[Callable[[], None]] = None
        self.on_step_error: Optional[Callable[[BaseException], None]] = None
        # P/D disaggregation hook (set by a prefill-role worker): called
        # on the engine thread when a sequence flagged
        # handoff_after_prefill settles its prefill (first token already
        # delivered). Returns True when the handoff was emitted — the
        # sequence then finishes locally with reason "handoff" and the
        # router resumes it on a decode worker; False keeps it decoding
        # here (mixed fallback, e.g. nothing exportable).
        self.on_prefill_handoff: Optional[Callable[[Sequence], bool]] = None
        # Delivery hook (set by the HTTP server, whose callbacks only
        # queue): called on the engine thread when a delivery is over
        # (the contract at TokenCallback above); posts what was handed
        # out since its last call and returns whether there was any.
        self.on_delivered: Optional[Callable[[], bool]] = None
        self._unposted = False      # handed out since on_delivered ran

    # ---------------------------------------------- supervision plumbing

    def _note_ok(self) -> None:
        if self.on_step_ok is not None:
            self.on_step_ok()

    def _note_error(self, exc: BaseException) -> None:
        self.stats.step_failures += 1
        flight = self.engine.telemetry.flight
        if flight is not None:
            # Evidence first: dump the ledger/spans/config while the
            # failed step's records are still the newest in the ring.
            flight.capture("step_error")
        if self.on_step_error is not None:
            self.on_step_error(exc)

    @staticmethod
    def _log_step_error(phase: str, exc: BaseException,
                        seqs: List[Sequence]) -> None:
        """One structured, greppable error record per step failure
        (replaces bare traceback.print_exc): phase, exception, the
        request ids affected, and a trimmed traceback — all through the
        TPU_INF_LOG stream so operators can join failures to requests."""
        import traceback
        telemetry.log_event(
            "step_error", level="error", phase=phase, error=repr(exc),
            request_ids=[s.trace_id or str(s.request_id) for s in seqs],
            traceback="".join(traceback.format_exception(
                type(exc), exc, exc.__traceback__, limit=8)))

    # -------------------------------------------------- submission API

    @property
    def load(self) -> int:
        """Queued + admitted (not yet finished) requests — the number the
        least-loaded router and the admission-control queue cap compare.
        _callbacks (not active_sequences) so mid-incremental-prefill
        requests still count."""
        return len(self._waiting) + len(self._callbacks)

    def submit(self, seq: Sequence, on_token: TokenCallback,
               on_finish: FinishCallback) -> None:
        """Queue a request; callbacks fire on the engine thread."""
        if len(self._waiting) >= self.engine.engine_cfg.max_queue_len:
            self.stats.requests_rejected += 1
            seq.done, seq.finish_reason = True, "queue_full"
            on_finish(seq)
            return
        if not self.engine.can_ever_admit(seq):
            # Would block the FCFS queue forever — reject immediately.
            self.stats.requests_rejected += 1
            seq.done, seq.finish_reason = True, "too_large"
            on_finish(seq)
            return
        seq.enqueue_time = time.perf_counter()
        with self._lock:
            # Class-aware queue (README "Elastic fleet"): insert before
            # any strictly-lower class so an interactive arrival jumps a
            # batch backlog; FCFS within a class. O(n) from the tail is
            # fine — the queue is bounded by max_queue_len, and the
            # common single-class workload degenerates to append().
            rank = class_rank(seq.priority_class)
            idx = len(self._waiting)
            while idx > 0 and class_rank(
                    self._waiting[idx - 1].seq.priority_class) > rank:
                idx -= 1
            self._waiting.insert(idx, _Pending(seq, on_token, on_finish))
        self._work.set()

    def kick(self) -> None:
        """Wake the engine loop from its idle wait (e.g. after queueing
        a cross-thread engine request like a migration import) so it is
        applied promptly instead of at the next 100 ms poll."""
        self._work.set()

    def cancel(self, request_id: int) -> None:
        """Cancel a queued or running request (client disconnect)."""
        with self._lock:
            for p in list(self._waiting):
                if p.seq.request_id == request_id:
                    self._waiting.remove(p)
                    p.seq.done, p.seq.finish_reason = True, "cancelled"
                    return
            p = self._callbacks.get(request_id)
            if p is not None and not p.seq.done:
                p.seq.done = True
                p.seq.finish_reason = "cancelled"

    # -------------------------------------------------- engine loop

    def start(self) -> "EngineScheduler":
        self._stop.clear()   # restartable (server app cycles in tests)
        self._thread = threading.Thread(target=self.run, name="engine-loop",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Graceful shutdown; with drain=True, finish in-flight work
        first. Requests still unfinished at the drain deadline are
        CANCELLED with ``finish_reason="shutdown"`` — every submitted
        request gets its terminal callback, so client streams end
        cleanly instead of hanging until their own timeout."""
        if drain:
            deadline = time.monotonic() + timeout
            while (time.monotonic() < deadline
                   and (self._waiting or self._prefilling is not None
                        or self._callbacks
                        or self.engine.active_sequences())):
                time.sleep(0.01)
            self._cancel_stragglers()
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    def _cancel_stragglers(self) -> None:
        """Drain deadline passed: terminate whatever is still queued or
        running with finish_reason="shutdown". Queued requests finish
        directly; engine-bound ones are marked done for the run loop to
        reap (callbacks fire on the engine thread as usual), with a
        short grace period — if the engine thread is wedged and never
        reaps them, their terminal callbacks fire from here so no
        client hangs."""
        with self._lock:
            waiting = list(self._waiting)
            self._waiting.clear()
            running = list(self._callbacks.values())
            for p in waiting:
                # Register so _finish finds (and pops) the callback.
                self._callbacks[p.seq.request_id] = p
        stragglers = waiting + running
        if not stragglers:
            return
        for p in stragglers:
            if not p.seq.done:
                p.seq.done = True
                p.seq.finish_reason = "shutdown"
                p.seq.finish_time = time.perf_counter()
        telemetry.log_event(
            "shutdown_cancel", level="warning",
            request_ids=[p.seq.trace_id or str(p.seq.request_id)
                         for p in stragglers])
        for p in waiting:
            self._finish(p.seq)
        grace = time.monotonic() + 2.0
        while self._callbacks and time.monotonic() < grace:
            self._work.set()                 # wake the idle wait
            time.sleep(0.01)
        for p in list(self._callbacks.values()):
            self._finish(p.seq)              # engine thread wedged

    def _hybrid_active(self) -> bool:
        """True when the in-progress incremental prefill should advance
        through HYBRID steps (fused into the decode dispatch) instead of
        the serial one-chunk-per-iteration path: hybrid_prefill is on,
        speculative decoding is off (the spec round has its own fused
        graph), and there are decode lanes to fuse with — with an empty
        batch the serial chunk IS the whole step, so fusing buys
        nothing and the serial path keeps its simpler bookkeeping."""
        return (self.engine.engine_cfg.hybrid_prefill
                and not self.engine.spec_enabled
                and self._prefilling is not None
                and bool(self.engine.active_sequences()))

    def _needs_chunking(self, seq: Sequence) -> bool:
        """True when the prompt spans several prefill chunks (so it goes
        through the incremental path instead of stalling the batch).
        Conservative: a prefix-cache hit could still shrink it to one.
        Resume prefills measure prompt + already-generated tokens."""
        ecfg = self.engine.engine_cfg
        cap = ecfg.chunk_tokens_cap
        base = len(self.engine._prefill_tokens(seq))
        return min(base, ecfg.max_context - 1) > cap

    def _prefill_done(self, pending: _Pending) -> None:
        """Post-prefill bookkeeping shared by the batched and incremental
        paths: counters, first-token delivery, immediate finish."""
        seq = pending.seq
        self.engine.telemetry.clock.enter("admit")
        self.stats.prefills += 1
        self.stats.tokens_generated += 1
        if not seq.resume_base:
            # Resume prefills reuse pages THIS request published at its
            # own preemption — counting them would inflate the cross-
            # request prefix-cache hit rate the replay artifact reports.
            self.stats.tokens_prefix_cached += seq.cached_tokens
        tel = self.engine.telemetry
        if tel.enabled and seq.enqueue_time and not seq.resume_base:
            # Resume prefills skip the queue-wait histograms: their
            # enqueue->prefill gap spans the whole first attempt.
            boundary, capacity = self._queue_wait_split(seq)
            tel.queue_wait_s.observe(boundary + capacity)
            tel.queue_boundary_wait_s.observe(boundary)
            tel.queue_capacity_wait_s.observe(capacity)
        tel.clock.enter("deliver")
        self.stats.deliver_tokens += 1
        self._unposted = True
        pending.on_token(seq, seq.generated[-1])
        tel.clock.enter("admit")
        if (not seq.done and seq.handoff_after_prefill
                and self.on_prefill_handoff is not None):
            # P/D disaggregation: the prefill settled — emit the live
            # handoff (KV pages + stream state) instead of decoding on
            # this worker. The first token above already streamed; the
            # router replays it in the decode worker's resume record.
            if self.on_prefill_handoff(seq):
                self.stats.pd_handoffs += 1
                seq.done = True
                seq.finish_reason = "handoff"
                seq.finish_time = time.perf_counter()
        if seq.done:
            self._finish(seq)

    @staticmethod
    def _queue_wait_split(seq: Sequence) -> tuple:
        """Queue wait (enqueue -> prefill start) split at the first
        admission pass that saw the request: (waiting for the running
        dispatch to come back, then admission work and passes it was
        turned away for slots or pages). Same timestamps, so the two
        sum to the queue wait exactly."""
        enq = seq.enqueue_time
        start = max(enq, seq.prefill_start or enq)
        seen = min(max(seq.admit_seen_time or start, enq), start)
        return seen - enq, start - seen

    def _step_incremental_prefill(self) -> None:
        """Advance the in-progress multi-chunk prefill by ONE chunk."""
        pending = self._prefilling
        seq = pending.seq
        if seq.done:                          # cancelled mid-prefill
            self._prefilling = None
            self._finish(seq)
            return
        self.step_inflight_since = time.monotonic()
        try:
            finished = self.engine.prefill_step(seq)
        except Exception as exc:  # noqa: BLE001 — keep the engine loop alive
            self._log_step_error("incremental_prefill", exc, [seq])
            self._note_error(exc)
            self._prefilling = None
            seq.done, seq.finish_reason = True, "error"
            self._finish(seq)
            return
        finally:
            self.step_inflight_since = None
        self._note_ok()
        if finished:
            self._prefilling = None
            self._prefill_done(pending)

    def _admit(self) -> int:
        """Admit up to max_prefills_per_step waiting requests in one
        batched prefill dispatch (engine.prefill_many): same-bucket
        arrivals share a [P, S] forward instead of queueing behind P
        serial prefills. Multi-chunk prompts instead start an incremental
        prefill advanced one chunk per loop, so decode interleaves —
        and short requests can still batch-admit in the same iteration
        (no head-of-line blocking behind the long prompt)."""
        admitted = 0
        if self._prefilling is not None and not self._hybrid_active():
            # Advancing an ALREADY-admitted prefill by one chunk is not a
            # new admission; only fresh requests count below. With hybrid
            # stepping active, the chunk instead rides the decode
            # dispatch later this iteration (run()'s hybrid branch).
            seq = self._prefilling.seq
            if seq.done and self.engine.pipeline_pending:
                # Cancelled with chained hybrid chunks still in flight:
                # settle their writes before the terminal path below
                # releases the pages they target.
                self._deliver(self._drain_safely())
            self._poll_hybrid_prefill()   # completed at an earlier sync?
            if self._prefilling is not None:
                if (not seq.done and seq.prefill_prompt is not None
                        and seq.prefill_offset >= len(seq.prefill_prompt)):
                    # Every chunk is already staged into in-flight hybrid
                    # calls; the final chunk's token folds at its sync —
                    # nothing to advance serially (and re-dispatching
                    # would run an empty chunk).
                    pass
                else:
                    self._step_incremental_prefill()
        batch: List[_Pending] = []
        start_chunked: Optional[_Pending] = None
        start_adopt: Optional[_Pending] = None
        # pages a kind and state slots: [full, window, state]
        reserved = np.zeros(3, np.int64)
        # What the chunk above delivered does not wait behind the
        # prefill dispatch this pass may make.
        self._post_deliveries()
        t_pass = self.engine.telemetry.clock.enter("admit")
        with self._lock:
            engine = self.engine
            # This pass is the first to see whoever queued since the
            # last one (the queue is bounded and short; a class jump may
            # have landed one mid-queue, so all are looked at).
            for pending in self._waiting:
                if not pending.seq.admit_seen_time:
                    pending.seq.admit_seen_time = t_pass
            free_slots = len(engine.free_slots())
            bound = sum(s is not None for s in engine.slots)
            base_rung = engine.ladder[0]
            headroom = engine.engine_cfg.ladder_admit_headroom_pages
            while (len(batch) < self.max_prefills_per_step
                   and len(batch) < free_slots and self._waiting):
                pending = self._waiting[0]
                if pending.seq.done:          # cancelled while queued
                    self._waiting.popleft()
                    continue
                # Admission page accounting across the whole batch —
                # allocation happens later inside prefill_many, so each
                # candidate must fit on top of those already selected.
                # reserve mode charges the worst case; optimistic the
                # prompt footprint + headroom (engine._pages_for_admission).
                # A model with a pool a kind is charged in both, and waits
                # on either (engine.admission_fits).
                need = engine.admission_need(pending.seq)
                if not engine.admission_fits(reserved + need):
                    break
                # Batch-ladder pool-vs-lanes guard: growing the batch
                # past the BASE rung must leave at least
                # ``ladder_admit_headroom_pages`` of reclaimable slack
                # behind — extra lanes must not drain the pool to the
                # preemption watermark or force decode grants to evict
                # the whole hot set (with a host tier the evictions
                # demote and survive; the headroom keeps either tier's
                # churn off the steady-state path). Below the base
                # rung, admission keeps the legacy gate.
                if (headroom > 0
                        and bound + len(batch) + 1 > base_rung
                        and not engine.admission_fits(reserved + need,
                                                      headroom)):
                    break
                if pending.seq.adopt_kv is not None:
                    # P/D handoff adoption: no prefill dispatch — the KV
                    # restore runs solo below (before _needs_chunking,
                    # whose prompt+generated stream length would
                    # misroute an adoptable sequence into chunking).
                    if batch:
                        break     # admit the plain batch first
                    self._waiting.popleft()
                    self._callbacks[pending.seq.request_id] = pending
                    start_adopt = pending
                    reserved = reserved + need
                    break
                if self._needs_chunking(pending.seq):
                    if self._prefilling is not None:
                        break     # one incremental prefill at a time
                    if batch:
                        break     # admit the batch first; chunked head next
                    self._waiting.popleft()
                    self._callbacks[pending.seq.request_id] = pending
                    start_chunked = pending
                    reserved = reserved + need
                    break
                self._waiting.popleft()
                # Register before releasing the lock so cancel() always
                # finds the request in _waiting or _callbacks.
                self._callbacks[pending.seq.request_id] = pending
                reserved = reserved + need
                batch.append(pending)
        # Queue-wait swap-in (README "Tiered KV cache"): the head-of-
        # queue request's host-tier pages start restoring into cache-
        # owned device pages WHILE it waits, so its eventual prefill
        # begins warm instead of paying the swap inside TTFT. Engine
        # thread, bounded to the head request; no-ops without a host
        # tier (host_prefetched short-circuits repeats).
        if self.engine.host_pool is not None:
            with self._lock:
                head = self._waiting[0] if self._waiting else None
            if (head is not None and not head.seq.done
                    and head.seq.adopt_kv is None):
                # (Adoptable heads skip the prefetch: their KV arrives
                # with the handoff blob, not from the host tier.)
                try:
                    self.engine.prefetch_host_hits(head.seq)
                except Exception as exc:  # noqa: BLE001 — keep loop alive
                    # A failed host->device restore is a step failure
                    # like any other: logged AND fed to the replica's
                    # health machine, not skipped over.
                    self._log_step_error("host_prefetch", exc, [head.seq])
                    self._note_error(exc)
        if start_adopt is not None:
            seq = start_adopt.seq
            t_adopt = time.perf_counter()
            try:
                self.step_inflight_since = time.monotonic()
                self.engine.adopt_sequence(seq)
            except Exception as exc:  # noqa: BLE001 — keep the loop alive
                # Malformed blob / pool shortfall: fall back to an
                # ordinary recompute-resume (prompt + replayed tokens
                # re-prefill; byte-identical under greedy) by clearing
                # the adoption state and requeueing at the head.
                self._log_step_error("handoff_adopt", exc, [seq])
                self.engine.adopt_fallbacks += 1
                seq.adopt_kv = None
                with self._lock:
                    self._callbacks.pop(seq.request_id, None)
                    self._waiting.appendleft(start_adopt)
                return admitted
            finally:
                self.step_inflight_since = None
            self._note_ok()
            # Trace span: the adoption (KV restore, no prefill) stands
            # in for the prefill span on this worker — adjacent to the
            # prefill worker's handoff_export on the assembled
            # timeline. Ends exactly at first_token_time (set by
            # adopt_sequence), which is where the decode span begins,
            # so the two spans abut without overlapping.
            self.engine.telemetry.recorder.add(
                "handoff_adopt", seq.trace_id or str(seq.request_id),
                t_adopt, seq.first_token_time or time.perf_counter(),
                ctx_len=seq.ctx_len, pages=len(seq.pages))
            # No token delivery and no prefill counters: every token in
            # seq.generated was already streamed (the handoff's replay
            # record), and no prefill dispatch ran.
            if seq.done:              # cancelled while queued, raced
                self._finish(seq)
            return admitted + 1
        if start_chunked is not None:
            seq = start_chunked.seq
            try:
                self.engine.prefill_begin(seq)
            except Exception as exc:  # noqa: BLE001
                self._log_step_error("prefill_begin", exc, [seq])
                self._note_error(exc)
                seq.done, seq.finish_reason = True, "error"
                self._finish(seq)
                return admitted
            self._prefilling = start_chunked
            if self._hybrid_active():
                # Decode lanes are running: even the FIRST chunk rides
                # the fused hybrid dispatch this iteration instead of
                # stalling them here.
                return admitted + 1
            self._step_incremental_prefill()
            return admitted + 1
        if not batch:
            return admitted
        self.step_inflight_since = time.monotonic()
        try:
            self.engine.prefill_many([p.seq for p in batch])
        except Exception as exc:  # noqa: BLE001 — keep the engine loop alive
            self._log_step_error("batched_prefill", exc,
                                 [p.seq for p in batch])
            self._note_error(exc)
            # Coarse failure domain: the whole batch errors (admission
            # control makes device OOM here exceptional, not routine).
            for pending in batch:
                pending.seq.done, pending.seq.finish_reason = True, "error"
                self._finish(pending.seq)   # releases pages/slot
            return admitted
        finally:
            self.step_inflight_since = None
        self._note_ok()
        for pending in batch:
            self._prefill_done(pending)
        return admitted + len(batch)

    def _drain_safely(self) -> Dict[int, List[int]]:
        """drain_pipeline under the engine loop's keep-alive contract:
        a device error that surfaces only at sync time (async dispatch
        on real TPU) fails the affected requests with
        finish_reason="error" instead of propagating out of run() and
        killing the engine thread with work still queued."""
        engine = self.engine
        try:
            return engine.drain_pipeline()
        except Exception as exc:  # noqa: BLE001 — keep the loop alive
            victims = engine.active_sequences()
            pending = self._prefilling
            if pending is not None:
                self._prefilling = None
                if pending.seq not in victims:
                    victims = victims + [pending.seq]
            self._log_step_error("drain", exc, victims)
            self._note_error(exc)
            engine.abort_pipeline()
            engine.take_preempted()
            for s in victims:
                if not s.done:     # a cancelled seq keeps its reason
                    s.done, s.finish_reason = True, "error"
                    s.finish_time = time.perf_counter()
                self._finish(s)
            return {}

    def _poll_hybrid_prefill(self) -> None:
        """Hybrid prefills complete at SYNC time (possibly inside a
        drain): the final chunk's sampled token folds in the engine's
        _sync_oldest and ``prefill_prompt`` clears. Detect that here and
        run the shared post-prefill bookkeeping (counters, first-token
        delivery, immediate finish). A cancel that landed mid-chunks
        keeps ``prefill_prompt`` set and is handled by the run loop's
        cancel branch instead."""
        pending = self._prefilling
        if pending is None or pending.seq.prefill_prompt is not None:
            return
        self._prefilling = None
        self._prefill_done(pending)

    def _requeue_preempted(self) -> None:
        """Move sequences the engine preempted this step back to the
        HEAD of the wait queue (they were admitted before anything still
        waiting) for recompute-resume. The pending entry leaves
        _callbacks while it waits — _admit re-registers it — so ``load``
        counts the request exactly once and cancel() finds it in
        _waiting. Runs after _deliver: tokens folded before the
        preemption must reach the client first."""
        preempted = self.engine.take_preempted()
        if not preempted:
            return
        self.stats.preemptions += len(preempted)
        cancelled: List[Sequence] = []
        with self._lock:
            for seq in reversed(preempted):
                pending = self._callbacks.get(seq.request_id)
                if pending is None:
                    continue
                if seq.done:          # cancelled while being preempted
                    cancelled.append(seq)
                    continue
                del self._callbacks[seq.request_id]
                self._waiting.appendleft(pending)
        for seq in cancelled:
            self._finish(seq)

    def _finish(self, seq: Sequence) -> None:
        with self._lock:
            if seq.reaped:
                # Already finished — the shutdown force-finish path and
                # a slow (but alive) engine thread's own reap can both
                # reach here; counters/timelines must move once.
                return
            seq.reaped = True
            pending = self._callbacks.pop(seq.request_id, None)
        self.engine.release(seq)
        self.stats.requests_finished += 1
        self._observe_finish(seq)
        with self._lock:
            self.recent.append(self._timeline(seq))
        if pending is not None:
            self._unposted = True
            pending.on_finish(seq)

    def _observe_finish(self, seq: Sequence) -> None:
        """Fold one finished request into the phase histograms + the
        structured log stream (telemetry.py). Phases come from the same
        timestamps as the /debug/requests timeline, so queue + prefill +
        decode sums to e2e by construction — the invariant the bench
        artifact sum-checks."""
        tel = self.engine.telemetry
        tel.request_finished(seq.finish_reason)
        fin = seq.finish_time or time.perf_counter()
        first = seq.first_token_time or fin
        start = seq.prefill_start or fin
        enq = seq.enqueue_time or start
        if tel.enabled and seq.enqueue_time:
            tel.prefill_phase_s.observe(max(0.0, first - start))
            tel.decode_phase_s.observe(max(0.0, fin - first))
            tel.ttft_s.observe(max(0.0, first - enq))
            tel.e2e_s.observe(max(0.0, fin - enq))
        self._observe_trace(seq, enq, start, first, fin)
        telemetry.log_event(
            "request_finish", level="info",
            request_id=seq.trace_id or str(seq.request_id),
            reason=seq.finish_reason, attempt=seq.attempt,
            routed_replica=seq.routed_replica,
            route_hit_pages=seq.route_hit_pages,
            route_host_hit_pages=seq.route_host_hit_pages,
            route_fabric_hit_pages=seq.route_fabric_hit_pages,
            host_restored_pages=seq.host_restored_pages,
            preemptions=seq.preemptions,
            prompt_tokens=len(seq.prompt_tokens),
            output_tokens=len(seq.generated),
            queue_wait_s=round(max(0.0, start - enq), 6),
            prefill_s=round(max(0.0, first - start), 6),
            decode_s=round(max(0.0, fin - first), 6),
            e2e_s=round(max(0.0, fin - enq), 6))

    def _observe_trace(self, seq: Sequence, enq: float, start: float,
                       first: float, fin: float) -> None:
        """Emit the request's phase spans (README "Observability" span
        schema) and fold its TTFT/TPOT into the rolling SLO window.

        Span rules: queue_wait covers enqueue -> prefill start
        (admission included); prefill covers prefill start -> first
        token (per-chunk children were recorded by the engine; an
        ADOPTED sequence's handoff_adopt span, recorded at admission,
        stands in instead); decode covers first token -> finish and is
        skipped on a "handoff" finish (no decode ran on the prefill
        worker — the handoff_export span follows instead, recorded by
        the worker's handoff hook). Sealing moves the trace into the
        recorder's recent ring, where the worker's finish event, the
        trace RPC verb, and /debug/trace read it."""
        tel = self.engine.telemetry
        rec = tel.recorder
        tid = seq.trace_id or str(seq.request_id)
        if rec.enabled and seq.enqueue_time:
            boundary, capacity = self._queue_wait_split(seq)
            rec.add("queue_wait", tid, enq, max(enq, start),
                    admission=self.engine.admission,
                    boundary_wait_s=round(boundary, 6),
                    capacity_wait_s=round(capacity, 6))
            if not seq.adopted:
                rec.add("prefill", tid, start, max(start, first),
                        cached_tokens=seq.cached_tokens,
                        host_restored_pages=seq.host_restored_pages,
                        attempt=seq.attempt)
            if seq.finish_reason != "handoff":
                attrs = {"output_tokens": len(seq.generated),
                         "reason": seq.finish_reason,
                         "preemptions": seq.preemptions}
                if seq.spec_rounds:
                    attrs["spec_rounds"] = seq.spec_rounds
                    attrs["spec_accepted_tokens"] = seq.spec_accepted_toks
                rec.add("decode", tid, first, max(first, fin), **attrs)
        rec.seal(tid)
        # Rolling SLO window: TTFT only for a FRESH first attempt —
        # attempt 0 and no resume (a resume/adoption's or a failover
        # resubmission's local first-token gap is not what the client
        # waited: the first attempt's latency precedes it, and
        # understating TTFT exactly while the fleet is failing is what
        # an SLO autoscaler must not do); TPOT only where real decode
        # steps ran here.
        slo = tel.slo
        if slo is None or not seq.enqueue_time:
            return
        ttft = (max(0.0, first - enq)
                if not seq.resume_base and seq.attempt == 0
                and seq.first_token_time
                and seq.finish_reason != "error" else None)
        decoded = len(seq.generated) - seq.resume_base
        # Inter-token gaps in (first, fin]: on an ADOPTED sequence
        # `first` is the adoption instant, so all `decoded` local
        # tokens were produced after it; elsewhere the first token IS
        # `first` and only decoded-1 gaps follow.
        gaps = decoded if seq.adopted else decoded - 1
        tpot = (max(0.0, fin - first) / gaps
                if gaps > 0 and seq.finish_reason != "handoff"
                else None)
        slo.observe(ttft, tpot)

    def recent_snapshot(self, n: int) -> List[dict]:
        """Thread-safe copy of the last ``n`` request timelines (the deque
        is appended from the engine thread; iterating it unlocked from an
        HTTP handler would race a concurrent append)."""
        with self._lock:
            items = list(self.recent)
        return items[-n:]

    @staticmethod
    def _timeline(seq: Sequence) -> dict:
        """Flatten one request's lifecycle into durations (seconds)."""
        fin = seq.finish_time or time.perf_counter()
        first = seq.first_token_time or fin
        n_out = len(seq.generated)
        return {
            "request_id": seq.request_id,
            # Client-visible trace id (X-Request-Id) and failover attempt
            # count: a resubmitted span carries attempt >= 1 so operators
            # can tell a replayed request from a first try.
            "trace_id": seq.trace_id,
            "attempt": seq.attempt,
            # Routing span: the dp replica this attempt ran on and the
            # cached prefix pages the router counted on (-1/0 when the
            # request was submitted scheduler-direct, e.g. tests/bench).
            "routed_replica": seq.routed_replica,
            "route_hit_pages": seq.route_hit_pages,
            # Of route_hit_pages, the pages that were HOST-tier-warm at
            # decision time (the router's third temperature).
            "route_host_hit_pages": seq.route_host_hit_pages,
            # Pages pulled from the fleet KV fabric into this replica's
            # host tier before dispatch (the fourth temperature: warmth
            # another replica prefilled; README "KV fabric").
            "route_fabric_hit_pages": seq.route_fabric_hit_pages,
            "finished_unix": round(time.time(), 3),
            "prompt_tokens": len(seq.prompt_tokens),
            "cached_tokens": seq.cached_tokens,
            # Tiered KV cache: device pages this request's prefills
            # swapped in from the host-RAM tier (0 = every cached page
            # was already HBM-warm).
            "host_restored_pages": seq.host_restored_pages,
            "output_tokens": n_out,
            # Watermark evictions this request survived (0 = never
            # preempted); recompute-resume makes them invisible in the
            # token stream, so the span must say they happened.
            "preemptions": seq.preemptions,
            "finish_reason": seq.finish_reason,
            "queue_wait_s": round(max(0.0, (seq.prefill_start or fin)
                                      - seq.enqueue_time), 6),
            "prefill_s": round(max(0.0, first - (seq.prefill_start or first)),
                               6),
            "decode_s": round(max(0.0, fin - first), 6),
            "e2e_s": round(max(0.0, fin - (seq.enqueue_time
                                           or seq.prefill_start or fin)), 6),
            "ttft_s": round(max(0.0, first - (seq.enqueue_time or first)), 6),
            "tpot_s": round((fin - first) / (n_out - 1), 6)
            if n_out > 1 else None,
        }

    def _deliver(self, new_tokens: Dict[int, List[int]]) -> None:
        clock = self.engine.telemetry.clock
        clock.enter("deliver")
        for rid, toks in new_tokens.items():
            pending = self._callbacks.get(rid)
            if pending is not None and toks:
                self.stats.deliver_tokens += len(toks)
                self._unposted = True
                for tok in toks:
                    pending.on_token(pending.seq, tok)
        clock.enter("other")

    def _post_deliveries(self) -> None:
        """A delivery is over: have ``on_delivered`` post what this
        thread handed out since the last call (nothing: no call). The
        loop calls this wherever it is about to stage a dispatch, wait
        on the device or sleep, and at the end of every turn, so one
        turn's tokens and finishes cost one wake-up and none of them
        waits behind the device. Leaves the clock in ``other``."""
        if not self._unposted:
            return
        self._unposted = False
        if self.on_delivered is None:
            return
        clock = self.engine.telemetry.clock
        clock.enter("deliver")
        if self.on_delivered():
            self.stats.deliver_wakeups += 1
        clock.enter("other")

    def _reap(self) -> None:
        """Finish every sequence the loop may finish now."""
        done = self._reapable()
        if done:
            clock = self.engine.telemetry.clock
            clock.enter("reap")
            for s in done:
                self._finish(s)
            clock.enter("other")

    def _reapable(self) -> List[Sequence]:
        """Finished sequences the run loop may finish NOW. A sequence
        still owned by the incremental prefill (cancelled mid-chunks) is
        excluded — _step_incremental_prefill finishes it, and finishing
        twice would double-count stats and duplicate /debug timelines
        (mid-prefill sequences sit in engine.slots since prefill_begin
        binds the slot)."""
        own = self._prefilling.seq if self._prefilling is not None else None
        return [s for s in self.engine.slots
                if s is not None and s.done and s is not own]

    def run(self) -> None:
        """The engine loop. Every stretch of it runs under a phase of
        the loop clock (telemetry.LoopClock): the engine's dispatch and
        sync sites enter stage / enqueue / device_wait / swap /
        prefix_lookup themselves, this loop names the rest."""
        clock = self.engine.telemetry.clock
        clock.start()
        try:
            self._run_loop(clock)
        finally:
            self._post_deliveries()
            clock.stop()

    def _run_loop(self, clock) -> None:
        engine = self.engine
        # An empty first delivery: on_delivered learns from it that this
        # thread posts for itself (server/http.py DeliveryOutbox).
        self._unposted = True
        while not self._stop.is_set():
            # The turn that just ended (decode tokens, the finishes
            # reaped after them, an error path's) is posted here, once.
            self._post_deliveries()
            # Work for the device exists: what the clock needs to call
            # host time with nothing in flight "starved" (and what a
            # loop_stall event reports).
            clock.waiting = len(self._waiting)
            clock.active = len(self._callbacks)
            clock.has_work = bool(clock.waiting or clock.active)
            # Re-read each tick: the recorder may be attached after the
            # engine thread starts (worker boot binds it post-start).
            flight = engine.telemetry.flight
            if flight is not None and flight.periodic_due():
                # Rolling periodic.json refresh — the capture a kill -9
                # leaves behind (no signal handler runs for SIGKILL).
                # Only the ledger's ring copy happens on this thread.
                clock.enter("heartbeat")
                flight.maybe_periodic()
            # Cross-thread chaos page-pressure requests (/debug/chaos)
            # and migration imports (the worker's import-kv RPC) apply
            # HERE — the allocator and host tier are engine-thread only,
            # and imports must land before admission so a migrated
            # request's prefill sees them.
            clock.enter("swap")
            engine.apply_pending_page_pressure()
            engine.apply_pending_imports()
            self._admit()
            clock.enter("other")
            # A prefill dispatch's first tokens go out together, before
            # the decode dispatch (or the drain) that follows.
            self._post_deliveries()
            active = engine.active_sequences()
            if not active:
                # Flush any dispatch-ahead calls, then reap
                # cancelled-in-flight sequences even when idle.
                if engine.pipeline_pending:
                    self._deliver(self._drain_safely())
                    # The drain may have synced a hybrid prefill's final
                    # chunk (e.g. every decode lane finished mid-chunks).
                    self._poll_hybrid_prefill()
                self._reap()
                self._post_deliveries()
                if self._prefilling is not None:
                    continue          # next iteration runs the next chunk
                # Idle also when requests wait that admission turned
                # away with nothing running: capacity, not the host.
                clock.enter("idle")
                if not self._waiting:
                    self._work.clear()
                    self._work.wait(timeout=0.1)
                else:
                    time.sleep(self.idle_sleep_s)
                continue

            hybrid_pf = self._prefilling if self._hybrid_active() else None
            if hybrid_pf is not None and hybrid_pf.seq.done:
                # Cancelled mid-hybrid-prefill: settle in-flight chunk
                # writes BEFORE release frees its pages (a chained chunk
                # may still be writing them), deliver whatever the drain
                # surfaced, then run the terminal path.
                self._deliver(self._drain_safely())
                self._prefilling = None
                self._finish(hybrid_pf.seq)
                hybrid_pf = None
                self._post_deliveries()
            try:
                # Latency mode: with a near-empty batch and nothing queued
                # or in flight, run the single-step graph so each token
                # streams out as sampled (no K-token flush bursts). Spec
                # decode has its own emission cadence; leave it alone.
                thresh = engine.engine_cfg.latency_decode_threshold
                t_call = clock.enter("stage")
                self.step_inflight_since = time.monotonic()
                if (0 < len(active) <= thresh and not self._waiting
                        and self._prefilling is None
                        and not engine.pipeline_pending
                        and not engine.spec_enabled):
                    new_tokens = engine.decode_steps(max_steps=1)
                else:
                    # Hybrid step: the in-progress prefill's next chunk
                    # rides the decode dispatch instead of stalling it.
                    new_tokens = engine.decode_steps_pipelined(
                        hybrid_pf.seq if hybrid_pf is not None else None)
                self.stats.record_decode_call(clock.enter("other") - t_call)
            except Exception as exc:  # noqa: BLE001 — keep the engine loop alive
                victims = list(active)
                if hybrid_pf is not None:
                    # The failed dispatch may have carried a prefill
                    # chunk whose writes are now suspect — the prefilling
                    # request fails with the batch.
                    self._prefilling = None
                    victims.append(hybrid_pf.seq)
                self._log_step_error(
                    "hybrid" if hybrid_pf is not None else "decode",
                    exc, victims)
                self._note_error(exc)
                engine.abort_pipeline()   # stale in-flight state would
                engine.take_preempted()   # poison reused slots; drop any
                for s in victims:         # mid-call preemptions too —
                    s.done, s.finish_reason = True, "error"  # they fail
                    s.finish_time = time.perf_counter()      # with the
                    self._finish(s)                          # batch
                continue
            finally:
                self.step_inflight_since = None
            self._note_ok()
            self.stats.steps += 1
            self.stats.batch_occupancy_sum += len(active)
            done_seqs = self._reapable()
            if done_seqs and engine.pipeline_pending:
                # A finish releases pages a newer in-flight call may still
                # write: drain first so release happens against settled
                # device state, and deliver the drained tokens too.
                extra = self._drain_safely()
                for rid, toks in extra.items():
                    new_tokens.setdefault(rid, []).extend(toks)
            self.stats.tokens_generated += sum(
                len(toks) for toks in new_tokens.values())
            in_use = (engine.engine_cfg.num_pages - 1) - engine.allocator.num_free
            self.stats.peak_pages_in_use = max(self.stats.peak_pages_in_use,
                                               in_use)

            self._deliver(new_tokens)
            # A hybrid prefill completes at sync time (inside the hybrid
            # step or one of the drains above) — run its post-prefill
            # bookkeeping before reaping.
            self._poll_hybrid_prefill()
            self._requeue_preempted()
            self._reap()
