"""Process fleet router: ``EngineGroup`` semantics over worker processes.

The out-of-process half of ROADMAP item 3 (README "Process fleet").
``ProcessEngineGroup`` implements the same facade as the in-process
``EngineGroup`` (submit/cancel, health/stats/metrics/recent snapshots,
prefix-affinity routing, failover, admission control) behind
``--fleet subprocess``, but each dp replica is its own engine-worker OS
process (server/worker.py) speaking the length-prefixed JSON RPC over a
local unix socket — so a worker fault (wedge, crash, ``kill -9``) is one
process, not the whole fleet, and the GIL stops being the dp ceiling.

Supervision: a monitor thread restarts dead workers with doubling
backoff up to ``ServerConfig.worker_restart_max`` per worker, keeping
the ``replica="i"`` metrics label STABLE across incarnations — counter
and histogram series from dead incarnations fold into a per-replica
carry (telemetry.fold_dump_into_carry) so the aggregated /metrics scrape
never resets or double-reports across a restart.

Failure handling replaces the two recompute burns with better moves:

- graceful drain (SIGTERM / drain RPC): the worker exports each live
  request's KV pages (host serialization layout) as ``migrate`` events;
  the router imports them into the destination's host tier and resubmits
  with the streamed-token record, so admission there is a
  swap-in-resume (engine.swap_in_resumes) instead of a re-prefill.
- ``kill -9`` mid-decode: no export is possible, so the router falls
  back to resubmission failover — it replays its own token record as a
  recompute-resume on a survivor (token-identical under greedy), and
  the client stream continues where it left off.

Routing stays PR-5/PR-6 three-temperature prefix affinity: the router
hashes each prompt once and probes every worker's cache tiers through
the side-effect-free ``peek`` RPC, scoring with the same formula as
EngineGroup._pick. Tokens stream through the router without buffering
(one event frame per token, forwarded as it arrives).
"""

from __future__ import annotations

import itertools
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from tpu_inference import telemetry
from tpu_inference.config import (FrameworkConfig, class_rank,
                                  framework_config_to_dict,
                                  resolve_worker_roles)
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine.autosize import pallas_reads_pool, resolve_page_size
from tpu_inference.engine.engine import Sequence
from tpu_inference.engine.prefix_cache import _chain_hashes
from tpu_inference.runtime import chip_env
from tpu_inference.server import kv_fabric, shm_arena
from tpu_inference.server.replicas import (FleetSaturated, FleetUnavailable,
                                           _RETRYABLE, _clone_request,
                                           aggregate_replica_stats)
from tpu_inference.server.transport import (ChaosPolicy, ChaosTransport,
                                            FrameError, recv_frame,
                                            send_frame)


class WorkerGone(ConnectionError):
    """RPC failed because the worker's process/connection died."""


# Per-verb deadline classes (README "Failure model"): every RPC site
# resolves its budget from ServerConfig.rpc_deadline_{fast,slow}_s via
# this table instead of hard-coding a blanket wait. "fast" verbs answer
# from memory; "slow" verbs touch the engine loop or move KV bytes.
# hello/shutdown/embed/profile keep explicit budgets at their call
# sites (boot compile, exit drain, batch forward, profiler capture).
_SLOW_RPC_VERBS = ("submit", "import-kv", "drain")

# Consecutive same-connection RPC timeouts before the router declares
# the connection wedged and recycles it (reconnect, not restart) —
# a silent socket heals without paying a worker boot.
_WEDGE_TIMEOUTS = 3

# How long a failed re-route keeps re-picking before the request fails
# "unavailable". Covers the connection-level failover window (redial +
# hello, bounded by the 5 s connect timeout) and most of a worker
# restart, so a momentary client gap never kills a request outright.
_REROUTE_GRACE_S = 10.0


class WorkerClient:
    """One live RPC connection to one worker incarnation. Requests are
    correlated by id; unsolicited event frames dispatch to the group's
    handler on this client's reader thread."""

    def __init__(self, path: str, proc: subprocess.Popen,
                 connect_timeout: float = 1800.0, replica: int = -1,
                 deadlines: Optional[dict] = None,
                 chaos: Optional[ChaosTransport] = None):
        import socket as _socket

        deadline = time.monotonic() + connect_timeout
        last_err: Optional[Exception] = None
        self.sock = None
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise WorkerGone(
                    f"worker exited rc={proc.returncode} before accepting")
            s = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
            try:
                s.connect(path)
                self.sock = s
                break
            except OSError as e:
                last_err = e
                s.close()
                time.sleep(0.05)
        if self.sock is None:
            raise WorkerGone(f"could not connect to worker: {last_err}")
        self.rfile = self.sock.makefile("rb")
        self._wlock = threading.Lock()
        self._ids = itertools.count(1)
        self._pending: Dict[int, dict] = {}
        self._plock = threading.Lock()
        self.alive = True
        self.replica = replica
        self.deadlines = deadlines or {}
        self.chaos = chaos
        # Why the reader died, for the group's supervision accounting:
        # "" (clean/unknown) | "frame_error" | "stream_gap".
        self.lost_reason = ""
        self._consec_timeouts = 0
        self.on_event: Optional[Callable] = None     # set by the group
        self.on_lost: Optional[Callable] = None
        self.on_timeout: Optional[Callable] = None   # (verb, timeout_s)
        self._reader = threading.Thread(target=self._read_loop,
                                        name="fleet-worker-reader",
                                        daemon=True)

    def start_reader(self) -> None:
        self._reader.start()

    def close(self) -> None:
        self.alive = False
        try:
            # shutdown() — not just close() — is what actually wakes
            # the reader thread parked in recv(): closing the fd alone
            # leaves it blocked forever, on_lost never fires, and a
            # wedged connection would never be recycled.
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass

    def resolve_deadline(self, verb: str) -> float:
        if verb in _SLOW_RPC_VERBS:
            return float(self.deadlines.get("slow", 60.0))
        return float(self.deadlines.get("fast", 10.0))

    def rpc(self, verb: str, timeout: Optional[float] = None,
            blob: bytes = b"", **kw) -> dict:
        """Send one request frame and wait for its reply. ``timeout``
        None resolves the verb's deadline class. Raises WorkerGone on a
        dead connection, TimeoutError past the deadline (emitting a
        structured ``rpc_timeout`` event and recycling the connection
        after _WEDGE_TIMEOUTS consecutive ones), RuntimeError on an
        error reply."""
        if not self.alive:
            raise WorkerGone("connection closed")
        if timeout is None:
            timeout = self.resolve_deadline(verb)
        rid = next(self._ids)
        waiter = {"evt": threading.Event(), "reply": None}
        with self._plock:
            self._pending[rid] = waiter
        msg = {"id": rid, "verb": verb}
        msg.update(kw)
        try:
            with self._wlock:
                send_frame(self.sock, msg, blob, chaos=self.chaos,
                           verb=verb, direction="send")
        except (OSError, ConnectionError) as e:
            with self._plock:
                self._pending.pop(rid, None)
            raise WorkerGone(str(e))
        if not waiter["evt"].wait(timeout):
            with self._plock:
                self._pending.pop(rid, None)
            if not self.alive:
                raise WorkerGone("connection lost mid-RPC")
            self._consec_timeouts += 1
            telemetry.log_event("rpc_timeout", level="warning",
                                verb=verb, replica=self.replica,
                                timeout_s=round(float(timeout), 3),
                                consecutive=self._consec_timeouts)
            if self.on_timeout is not None:
                self.on_timeout(verb, float(timeout))
            if self._consec_timeouts >= _WEDGE_TIMEOUTS:
                # The socket is open but mute — a wedged connection.
                # Close it: the reader's on_lost runs the reconnect
                # path (the process is alive), not a worker restart.
                self.lost_reason = self.lost_reason or "wedged"
                self.close()
            raise TimeoutError(f"worker RPC {verb!r} timed out "
                               f"after {timeout:.1f}s")
        self._consec_timeouts = 0
        reply = waiter["reply"]
        if reply is None or not reply[0].get("ok", False):
            err = (reply[0].get("error", "worker error") if reply
                   else "connection lost")
            kind = reply[0].get("kind", "") if reply else "gone"
            if kind in ("gone", "draining"):
                raise WorkerGone(err)
            raise RuntimeError(f"worker RPC {verb!r}: {err}")
        return reply[0]

    def _read_loop(self) -> None:
        try:
            while True:
                obj, blob = recv_frame(self.rfile)
                if "ev" in obj:
                    if self.on_event is not None:
                        self.on_event(self, obj, blob)
                    continue
                with self._plock:
                    waiter = self._pending.pop(obj.get("id"), None)
                if waiter is not None:
                    waiter["reply"] = (obj, blob)
                    waiter["evt"].set()
        except FrameError as e:
            # Malformed frame (desync, truncation, checksum, garbage
            # lengths): the stream cannot be trusted past this point —
            # recycle the connection; the process itself may be fine.
            self.lost_reason = self.lost_reason or "frame_error"
            telemetry.log_event("frame_error", level="warning",
                                replica=self.replica,
                                reason=getattr(e, "reason", ""),
                                error=str(e))
        except (ConnectionError, OSError, json.JSONDecodeError):
            pass
        finally:
            self.alive = False
            with self._plock:
                pending, self._pending = self._pending, {}
            for waiter in pending.values():
                waiter["evt"].set()
            if self.on_lost is not None:
                self.on_lost(self)


# Worker lifecycle states.
BOOTING = "booting"
UP = "up"
DRAINING = "draining"
RESTARTING = "restarting"
DEAD = "dead"           # router teardown
# Crash-loop breaker tripped (restart budget exhausted): the replica is
# routed around and VISIBLE — in /healthz and the
# tpu_inf_worker_quarantined gauge — rather than silently absent.
QUARANTINED = "quarantined"
# Intentional exit: scaled down by the autoscaler or replaced by a
# rolling upgrade. Never respawned, excluded from fleet health math.
RETIRED = "retired"


class WorkerHandle:
    """Supervision state for one replica slot across incarnations. The
    replica index (and its metrics label) is stable; the process, socket
    and client change per restart."""

    def __init__(self, replica: int):
        self.replica = replica
        self.state = BOOTING
        self.proc: Optional[subprocess.Popen] = None
        self.client: Optional[WorkerClient] = None
        self.socket_path = ""
        self.incarnation = 0
        self.restarts = 0               # successful respawns
        self.consecutive_failures = 0   # backoff driver
        self.restart_at = 0.0           # monotonic deadline for respawn
        self.started_unix = 0.0
        self.pid: Optional[int] = None
        self.info: dict = {}
        self.last_stats: dict = {}
        self.last_metrics: list = []
        self.last_health: dict = {}
        self.last_steps: dict = {}
        # Monotonic-series carry from dead incarnations (telemetry.
        # fold_dump_into_carry) — the restart-survival half of the
        # stable replica label. folded_incarnation makes the fold
        # idempotent: the drained event and the monitor's process-exit
        # detection can both report one death.
        self.carry: Dict[tuple, dict] = {}
        self.folded_incarnation = 0
        # SLO breach totals from dead incarnations: the fleet-level
        # tpu_inf_slo_breaches_total sums live worker counts on top of
        # this, so a worker restart never makes the fleet counter
        # decrease (Prometheus rate() reads any dip as a reset).
        self.slo_breach_carry = {"ttft": 0, "tpot": 0}
        # Intentional-exit marker (scale-down / rollout): the monitor's
        # death handler retires this worker instead of respawning it.
        self.retiring = False

    @property
    def routable(self) -> bool:
        return self.state == UP


class _Tracked:
    """Router-side state for one in-flight request across attempts,
    workers, and migrations."""

    __slots__ = ("template", "on_token", "on_finish", "worker", "client",
                 "generation", "attempts", "tokens", "seq_local",
                 "resume_stream_len", "t_submit", "handoff_blob",
                 "handoff_desc", "handoff_meta", "failed_workers")

    def __init__(self, template: Sequence, on_token, on_finish):
        self.template = template
        self.on_token = on_token
        self.on_finish = on_finish
        self.worker: Optional[WorkerHandle] = None
        self.client: Optional[WorkerClient] = None
        self.generation = 0
        self.attempts = 0
        # Every token streamed to the caller, in order — the failover
        # record that lets a killed worker's mid-stream request
        # recompute-resume on a survivor instead of failing.
        self.tokens: List[int] = []
        self.seq_local = _clone_request(template)
        # Tokens the latest resume-resubmission re-prefilled (prompt +
        # replayed generated), for the migrated-vs-recomputed accounting.
        self.resume_stream_len = 0
        self.t_submit = time.perf_counter()
        # P/D handoff state (README "P/D disaggregation"): the prefill
        # worker's live KV export (wire blob + {ctx_len, n_generated}).
        # Kept across retries — valid whenever the router's token record
        # still matches n_generated, so a decode-worker death right
        # after a handoff can re-adopt elsewhere; once decode advanced
        # past the export, resubmission falls back to recompute-resume.
        self.handoff_blob: Optional[bytes] = None
        # Zero-copy variant (README "KV data plane"): the export's
        # shared-memory arena descriptor — the payload never entered
        # this process; the decode worker adopts straight from the
        # arena, crc-verified there, with the blob path as fallback.
        self.handoff_desc: Optional[dict] = None
        self.handoff_meta: Optional[dict] = None
        # Poison-quarantine evidence: replica indices whose worker this
        # request's attempts CRASHED or WEDGED (not mere step errors —
        # those retry via the normal path). At poison_max_workers
        # distinct victims the request is failed terminally instead of
        # marching through the fleet.
        self.failed_workers: set = set()


class _EngineInfo:
    """Model/engine facts the HTTP layer reads off ``group.engine``
    (/api/ps, /api/show, boot prints), fetched once from worker 0's
    hello RPC. ``prefix_cache`` mimics the engine attribute's truthiness
    (the HTTP layer only checks ``is not None``)."""

    def __init__(self, hello: dict):
        self.n_params = hello.get("n_params", 0)
        self.weight_bytes = hello.get("weight_bytes", 0)
        self.attn_backend = hello.get("attn_backend", "?")
        self.ladder = tuple(hello.get("ladder") or (1,))
        self.swa_evict = hello.get("swa_evict", False)
        self.prefix_cache = True if hello.get("prefix_cache") else None
        self.host_pool = None
        self._device = hello.get("device") or {}

    def device_info(self) -> dict:
        """Worker 0's InferenceEngine.device_info() as of its hello."""
        return dict(self._device)


class ProcessEngineGroup:
    """Router + N engine-worker processes behind the EngineGroup facade
    (``ServerConfig.fleet = "subprocess"``)."""

    def __init__(self, cfg: FrameworkConfig,
                 platform: Optional[str] = None,
                 sizing: Optional[dict] = None):
        """``platform`` (the CLI's --platform) and ``sizing`` (an
        autosize.sizing_request) ride each worker's envelope untouched:
        they are settled where the device is, and this process — the
        router — never initialises a JAX backend, so the chips stay
        free for the workers."""
        pcfg = cfg.parallel
        # The tokens of a page are settled HERE, from what the router
        # can see without a backend (the attention backend asked for and
        # --platform; with neither named, 'auto' counts as no TPU), and
        # ship to the workers as a number inside the config: the
        # router's prefix digests and the workers' pools cannot drift.
        cfg.engine = resolve_page_size(
            cfg.model, cfg.engine, tp=pcfg.tp,
            pallas=pallas_reads_pool(cfg.engine.attn_backend,
                                     platform or "cpu"))
        self.cfg = cfg
        self._worker_platform = platform
        self._worker_sizing = sizing
        self.server_cfg = cfg.server
        self.engine_cfg = cfg.engine
        self.dp = max(1, pcfg.dp)
        # Worker phase roles (README "P/D disaggregation"): one per
        # replica, "mixed" everywhere unless ServerConfig.worker_roles /
        # EngineConfig.role say otherwise. pd_enabled gates the phase-
        # aware routing below; an all-mixed fleet behaves exactly as
        # before.
        # A list, not a tuple: scale-ups and rollout successors append
        # their role at the new replica index.
        self.roles = list(resolve_worker_roles(
            self.dp, cfg.server.worker_roles,
            default_role=cfg.engine.role))
        self.pd_enabled = any(r != "mixed" for r in self.roles)
        if self.pd_enabled and (
                all(r == "decode" for r in self.roles)
                or all(r == "prefill" for r in self.roles)):
            telemetry.log_event(
                "pd_roles_one_sided", level="warning",
                roles=list(self.roles),
                note="a P/D split needs both phases; this fleet will "
                     "serve via the fallback pools (lazy compiles)")
        self.workers = [WorkerHandle(i) for i in range(self.dp)]
        self._sock_dir = tempfile.mkdtemp(prefix="tpuinf-fleet-")
        self._started = False
        self._stopping = False
        self._start_lock = threading.Lock()
        self._lock = threading.Lock()
        self._tracked: Dict[int, _Tracked] = {}
        self._monitor_stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        self.engine: Optional[_EngineInfo] = None
        self.warmup_total_s = 0.0
        # Fleet counters — the same supervision family as EngineGroup
        # (torn-read-tolerant plain ints) plus the process-fleet extras.
        self.retries_attempted = 0
        self.retries_succeeded = 0
        self.failovers = 0
        self.requests_shed = 0
        self.requests_unavailable = 0
        self.route_prefix_hits = 0
        self.route_cold = 0
        self.route_fabric_hits = 0      # dispatches that pulled fabric pages
        self.migrations = 0             # drain exports received
        self.migrated_pages = 0
        self.migrated_bytes = 0
        self.resume_resubmits = 0       # resume-replay resubmissions
        self.resume_recomputed_tokens = 0
        self.resume_reused_tokens = 0
        # P/D disaggregation counters: handoff events received, and
        # handoffs whose resubmission had to recompute (stale blob /
        # no adopter) instead of adopting cleanly.
        self.pd_handoffs = 0
        self.pd_handoff_recomputes = 0
        # Byzantine-transport counters (README "Failure model"):
        # connection-level failovers (reconnect+resync, no restart),
        # structured RPC deadline hits, malformed frames the router
        # rejected, corrupt KV blobs rejected router-side, and
        # poison-quarantined requests.
        self.reconnects = 0
        self.rpc_timeouts = 0
        self.frame_errors = 0
        self.kv_rejections = 0
        self.poison_requests = 0
        # Per-verb deadline classes every RPC site resolves through
        # (satellite: the blanket-60 s audit).
        self._deadlines = {"fast": cfg.server.rpc_deadline_fast_s,
                           "slow": cfg.server.rpc_deadline_slow_s}
        # Transport chaos policy (config knobs now, /debug/chaos rpc
        # updates later). One ChaosPolicy per replica so the wedge
        # targets exactly chaos_rpc_wedge_replica and per-replica seeds
        # decorrelate; wedge_spent on the policy makes the wedge
        # one-shot across that replica's reconnects.
        self._chaos_rpc_kw = self._chaos_kw_from_cfg(cfg.server)
        self._chaos_policies: Dict[int, ChaosPolicy] = {}
        # Router-side crash flight recorder: poison quarantines and
        # corrupt-blob rejections capture the router's view (replica -1
        # under the shared blackbox dir) so the offending payload's
        # metadata survives for postmortem.
        self._flight = telemetry.attach_router_flight_recorder(
            cfg.server.blackbox_dir,
            retain=cfg.server.blackbox_retain,
            stats_fn=self.supervision_counters)
        # Elastic fleet (README "Elastic fleet"): autoscaler, rolling
        # upgrades, and per-class admission state.
        self.scale_ups = 0
        self.scale_downs = 0
        self.rollouts = 0
        self.class_preemptions: Dict[str, int] = {}
        self.class_shed: Dict[str, int] = {}
        from collections import deque
        # Bounded per-class deferral queues (batch lanes park here at
        # the admission cap instead of shedding; the monitor pump
        # dispatches them as capacity frees up). Guarded by _lock.
        self._deferred: Dict[str, deque] = {"batch": deque(),
                                            "background": deque()}
        self._breach_since = 0.0      # monotonic start of current breach
        self._idle_since = 0.0        # monotonic start of current lull
        self._last_scale_t = 0.0      # monotonic time of last scale act
        self._rollout_lock = threading.Lock()
        # Router-observed TTFT samples (t_observed, ttft_s), pruned to
        # a time horizon at each autoscale tick. This is the scale-up
        # sensor: unlike the workers' engine-side rings it (a) counts
        # time a request spent PARKED in a class lane — the user-
        # perceived latency overload actually inflates — and (b) decays
        # with wall time, so a burst's breached samples cannot latch
        # the fleet at peak size after traffic stops. Guarded by _lock.
        self._ttft_obs: deque = deque(maxlen=2048)
        # Fan-out pool for the concurrent candidate peeks. Created
        # eagerly (threads only spawn on first submit): lazy creation
        # under concurrent HTTP submits would race and leak the losing
        # executor's threads.
        from concurrent.futures import ThreadPoolExecutor
        self._peek_pool = ThreadPoolExecutor(
            max_workers=max(4, self.dp), thread_name_prefix="fleet-peek")
        # Cross-process trace assembly (README "Observability"): the
        # router's own spans (request root, route, handoff, migrate)
        # record here, and worker-exported spans — riding finish/
        # handoff-spans/migrate event frames, already tagged with their
        # source replica and unix-anchored — fold in via ingest(), so
        # one recorder holds each request's full cross-process span
        # set. /debug/trace reads it; the trace RPC verb is the pull
        # fallback for traces this router never saw finish.
        self._recorder = telemetry.SpanRecorder(replica=-1)
        self._rr = 0
        self._route_stats = [{"hits": 0, "cold": 0, "hit_pages": 0,
                              "host_hit_pages": 0, "fabric_hit_pages": 0}
                             for _ in range(self.dp)]
        # Fleet KV fabric (README "KV fabric"): router-resident pool of
        # serialized prefix pages — workers publish via fabric_put event
        # frames; pulls ship to the routed worker's host tier over the
        # import-kv RPC before its submit.
        self.fabric = kv_fabric.FabricPool(cfg.server.fabric_cache_pages)
        # Zero-copy KV data plane (README "KV data plane"): one shared-
        # memory arena for the whole fleet, one region per boot-time
        # replica. Creation failure (or --kv-plane relay, in-process
        # fleet, non-Linux) leaves arena=None and every path below
        # rides the through-router relay exactly as before.
        self.arena: Optional[shm_arena.ArenaSegment] = None
        self._arena_dir: Optional[shm_arena.SlabDirectory] = None
        self.shm_reclaims = 0
        # Router-relayed KV payload bytes per RPC/event verb — the shm
        # arm's ≈0 on handoff/fabric verbs is the lane's headline grade.
        self.rpc_blob_bytes: Dict[str, int] = {
            "submit": 0, "import-kv": 0, "handoff": 0, "migrate": 0,
            "fabric_put": 0}
        if shm_arena.effective_kv_plane(cfg.server) == "shm":
            try:
                self.arena = shm_arena.ArenaSegment(
                    cfg.server.shm_arena_bytes,
                    regions=max(4, self.dp * 2))
                self._arena_dir = shm_arena.SlabDirectory()
                self.fabric.on_release = self._arena_dir.release
            except Exception as e:  # noqa: BLE001 — degrade to relay
                telemetry.log_event(
                    "shm_arena_unavailable", level="warning",
                    error=str(e),
                    note="kv_plane=shm degraded to relay")
                self.arena = None
        self._fleet_registry = telemetry.Registry()
        self._build_registry()

    # ------------------------------------------------------ registries

    def _build_registry(self) -> None:
        r = self._fleet_registry
        telemetry.register_span_ring(r, self._recorder)
        r.gauge("tpu_inf_replicas",
                "Live replicas (autoscaler/rollout move this; retired "
                "and quarantined workers excluded)",
                fn=lambda: float(len(self._live_workers())))
        r.counter("tpu_inf_retries_attempted_total",
                  "Failover resubmissions attempted",
                  fn=lambda: self.retries_attempted)
        r.counter("tpu_inf_retries_succeeded_total",
                  "Failover resubmissions that finished cleanly",
                  fn=lambda: self.retries_succeeded)
        r.counter("tpu_inf_failovers_total",
                  "Requests stranded by a dead/draining worker and "
                  "resubmitted",
                  fn=lambda: self.failovers)
        r.counter("tpu_inf_requests_shed_total",
                  "Requests shed at the admission queue cap (HTTP 429)",
                  fn=lambda: self.requests_shed)
        r.counter("tpu_inf_requests_unavailable_total",
                  "Requests rejected with no routable worker (HTTP 503)",
                  fn=lambda: self.requests_unavailable)
        r.counter("tpu_inf_route_prefix_hits_total",
                  "Dispatches routed with a non-zero prefix-cache peek",
                  fn=lambda: self.route_prefix_hits)
        r.counter("tpu_inf_route_cold_total",
                  "Dispatches routed with no cached prefix on any "
                  "scored worker",
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
        # Zero-copy KV data plane: how many KV payload bytes still
        # traverse the router per verb (the shm plane's reason to
        # exist is driving the handoff/fabric rows of this family to
        # ~0), plus the arena supervisor's slab books.
        for verb in self.rpc_blob_bytes:
            r.counter("tpu_inf_rpc_blob_bytes_total",
                      "KV payload bytes relayed through the router's "
                      "RPC/event frames, by verb (descriptor frames on "
                      "the shm plane count 0 here — the bytes stay in "
                      "the arena)",
                      fn=lambda v=verb: self.rpc_blob_bytes[v],
                      verb=verb)
        r.gauge("tpu_inf_shm_slabs_total",
                "Arena slabs the router still tracks: live plus "
                "released-but-not-yet-freed (frees batch to the owning "
                "worker on its next stats tick). 0 on the relay plane.",
                fn=lambda: float(self._arena_dir.slabs_tracked
                                 if self._arena_dir is not None else 0))
        r.gauge("tpu_inf_shm_slabs_used",
                "Arena slabs still referenced by a live consumer "
                "(fabric pool entry or pending handoff/migrate)",
                fn=lambda: float(self._arena_dir.slabs_live
                                 if self._arena_dir is not None else 0))
        r.counter("tpu_inf_shm_reclaims_total",
                  "Arena slabs reclaimed by the supervisor via the "
                  "region epoch bump after their owning worker "
                  "incarnation died (kill -9 mid-handoff lands here; "
                  "the in-flight request recompute-resumes)",
                  fn=lambda: self.shm_reclaims)
        r.counter("tpu_inf_fleet_migrations_total",
                  "In-flight requests migrated off a draining worker",
                  fn=lambda: self.migrations)
        r.counter("tpu_inf_fleet_migrated_pages_total",
                  "KV pages moved worker-to-worker by drain migration",
                  fn=lambda: self.migrated_pages)
        r.counter("tpu_inf_fleet_migrated_bytes_total",
                  "Bytes moved worker-to-worker by drain migration",
                  fn=lambda: self.migrated_bytes)
        r.counter("tpu_inf_resume_recomputed_tokens_total",
                  "Tokens re-prefilled from scratch by fleet "
                  "resubmission resumes (lower is better — migration "
                  "exists to shrink this)",
                  fn=lambda: self.resume_recomputed_tokens)
        r.counter("tpu_inf_resume_reused_tokens_total",
                  "Tokens served from cache tiers (incl. migrated "
                  "pages) during fleet resubmission resumes",
                  fn=lambda: self.resume_reused_tokens)
        r.counter("tpu_inf_pd_handoffs_total",
                  "Prefill->decode live KV handoffs routed (README "
                  "'P/D disaggregation')",
                  fn=lambda: self.pd_handoffs)
        r.counter("tpu_inf_pd_handoff_recomputes_total",
                  "Handoffs that fell back to recompute-resume (stale "
                  "export, no adopter, or a worker-side adoption "
                  "failure) instead of a clean adoption",
                  fn=self._pd_recomputes_total)
        r.counter("tpu_inf_worker_reconnects_total",
                  "Connection-level failovers: the socket died or a "
                  "frame was invalid while the worker process stayed "
                  "up, so the router reconnected and resynced instead "
                  "of paying a restart",
                  fn=lambda: self.reconnects)
        r.counter("tpu_inf_rpc_timeouts_total",
                  "Worker RPCs that exceeded their per-verb deadline "
                  "class (each also emits a structured rpc_timeout "
                  "event with verb + replica)",
                  fn=lambda: self.rpc_timeouts)
        r.counter("tpu_inf_frame_errors_total",
                  "Malformed RPC frames the router rejected (bad "
                  "magic/CRC/length) — each one recycles its "
                  "connection",
                  fn=lambda: self.frame_errors)
        r.counter("tpu_inf_kv_integrity_rejections_total",
                  "Corrupt KV blobs rejected by digest verification "
                  "(router gate + worker adopt/import paths); every "
                  "rejection fell back to recompute-resume, never a "
                  "silent adoption",
                  fn=self._kv_rejections_total)
        r.counter("tpu_inf_poison_requests_total",
                  "Requests quarantined after crashing or wedging "
                  "poison_max_workers distinct workers (terminal "
                  "structured 500 + router blackbox capture)",
                  fn=lambda: self.poison_requests)
        self._pd_handoff_s_hist = r.histogram(
            "tpu_inf_pd_handoff_seconds",
            "Prefill->decode handoff wall: worker-side KV export + "
            "router-side routing/dispatch until the decode worker "
            "accepted the resume")
        # Fleet-level rolling SLO gauges: EXACT quantiles pooled across
        # every worker's ring (per-replica p95s do not compose by
        # max/mean), from the cached worker stats the monitor refreshes
        # ~1/s; breach totals add the dead-incarnation carry so a
        # worker restart never makes the fleet counter decrease.
        # Per-replica series render from the workers' own registries
        # under replica="i" labels.
        telemetry.register_fleet_slo(
            r, self._pooled_slo_quantile,
            lambda k: sum(h.slo_breach_carry[k]
                          + (((h.last_stats or {}).get("slo") or {})
                             .get(f"{k}_breaches", 0))
                          for h in self.workers))
        # Elastic-fleet series (README "Elastic fleet"): scale events,
        # rolling upgrades, and the per-class admission lanes.
        telemetry.register_fleet_elastic(
            r,
            scale_ups=lambda: self.scale_ups,
            scale_downs=lambda: self.scale_downs,
            rollouts=lambda: self.rollouts,
            class_preempted=lambda c: self.class_preemptions.get(c, 0),
            class_deferred=lambda c: len(self._deferred.get(c) or ()),
            class_shed=lambda c: self.class_shed.get(c, 0))
        for h in self.workers:
            self._register_worker_gauges(h)

    def _register_worker_gauges(self, h: WorkerHandle) -> None:
        """Per-worker series under the stable replica label. Called for
        every boot-time handle and again for each worker the autoscaler
        or a rollout adds at a fresh replica index."""
        r = self._fleet_registry
        r.gauge("tpu_inf_worker_role_info",
                "Worker phase role (constant 1; the role is the "
                "label)",
                fn=lambda: 1.0, replica=str(h.replica),
                role=self.roles[h.replica])
        r.gauge("tpu_inf_replica_routable",
                "1 when the worker accepts traffic",
                fn=lambda hh=h: float(hh.routable),
                replica=str(h.replica))
        r.gauge("tpu_inf_worker_up",
                "1 while the worker process is serving",
                fn=lambda hh=h: float(hh.state == UP),
                replica=str(h.replica))
        r.counter("tpu_inf_worker_restarts_total",
                  "Worker process respawns (stable replica label "
                  "across incarnations)",
                  fn=lambda hh=h: hh.restarts,
                  replica=str(h.replica))
        r.gauge("tpu_inf_worker_quarantined",
                "1 while the crash-loop breaker holds this replica "
                "quarantined (restart budget exhausted; routed around)",
                fn=lambda hh=h: float(hh.state == QUARANTINED),
                replica=str(h.replica))

    def _kv_rejections_total(self) -> int:
        """Router-side rejections plus every worker's adopt/import
        rejections (healthz-cached; live counts, no carry needed —
        a corrupt blob implies a live incarnation that rejected it)."""
        return self.kv_rejections + self.fabric.kv_rejections + sum(
            (h.last_health or {}).get("kv_integrity_rejections", 0)
            for h in self.workers)

    @staticmethod
    def _chaos_kw_from_cfg(s) -> dict:
        return {"seed": s.chaos_rpc_seed,
                "corrupt_rate": s.chaos_rpc_corrupt_rate,
                "drop_rate": s.chaos_rpc_drop_rate,
                "delay_rate": s.chaos_rpc_delay_rate,
                "delay_s": s.chaos_rpc_delay_s,
                "truncate_rate": s.chaos_rpc_truncate_rate,
                "wedge_after": s.chaos_rpc_wedge_after,
                "wedge_replica": s.chaos_rpc_wedge_replica,
                "verbs": tuple(s.chaos_rpc_verbs),
                "direction": s.chaos_rpc_direction}

    def _make_chaos(self, replica: int) -> Optional[ChaosTransport]:
        """Router-side chaos shim for one worker connection. The policy
        persists per replica (wedge_spent survives reconnects — the
        wedge is one-shot by design); each connection gets a fresh
        transport over it. None when chaos is off or aimed only at the
        worker->router direction."""
        kw = dict(self._chaos_rpc_kw)
        if kw["direction"] not in ("send", "both"):
            return None
        wedge_after = kw.pop("wedge_after")
        wedge = wedge_after if kw.pop("wedge_replica") == replica else 0
        pol = self._chaos_policies.get(replica)
        if pol is None:
            pol = ChaosPolicy(wedge_after=wedge, **kw)
            pol.seed += replica  # decorrelate per-worker schedules
            if pol.active:
                self._chaos_policies[replica] = pol
        if not pol.active:
            return None
        return ChaosTransport(pol)

    def _live_workers(self) -> List[WorkerHandle]:
        """Workers that count toward fleet size: everything except the
        intentionally-retired (scale-down/rollout) and the crash-loop
        quarantined/dead."""
        return [h for h in self.workers
                if h.state not in (RETIRED, DEAD, QUARANTINED)]

    def _pooled_slo_quantile(self, which: str, q: float) -> float:
        windows = [(((h.last_stats or {}).get("slo") or {})
                    .get(f"{which}_window")) or []
                   for h in self.workers]
        v = telemetry.pooled_quantile(windows, q)
        return float("nan") if v is None else v

    def _fleet_slo(self) -> dict:
        out = telemetry.pooled_slo(
            [(h.last_stats or {}).get("slo") for h in self.workers])
        # Dead-incarnation carry keeps the fleet totals monotone
        # across worker restarts (same stance as the metrics carry).
        out["ttft_breaches"] += sum(h.slo_breach_carry["ttft"]
                                    for h in self.workers)
        out["tpot_breaches"] += sum(h.slo_breach_carry["tpot"]
                                    for h in self.workers)
        return out

    # ----------------------------------------------------------- spawn

    def _envelope(self, replica: int) -> dict:
        env = {
            "config": framework_config_to_dict(self.cfg),
            "platform": self._worker_platform,
            "sizing": self._worker_sizing,
            "warmup": self.cfg.server.warmup,
            # Per-worker phase role: the one envelope field that differs
            # between replicas (README "P/D disaggregation").
            "role": self.roles[replica],
            # Shared-CPU hosts: deprioritize the prefill tier so decode
            # cadence stays flat under prefill bursts (ServerConfig.
            # pd_prefill_nice; no-op at 0 or on per-chip deployments).
            "nice": (self.cfg.server.pd_prefill_nice
                     if self.roles[replica] == "prefill" else 0),
            # Pool watermark at boot (satellite: publish back-pressure);
            # the periodic stats RPC keeps it fresh afterwards.
            "fabric_free": self.fabric.free_pages,
        }
        if self.arena is not None:
            # Zero-copy plane: this worker's region assignment (segment
            # name + geometry + current epoch). None past the region
            # count — a late autoscaled worker rides the relay plane.
            shm = self.arena.region_spec(replica)
            if shm is not None:
                env["shm"] = shm
        return env

    def _spawn(self, h: WorkerHandle) -> None:
        """Launch one worker incarnation and wait for its hello (which
        blocks until the worker's engine is built and warmed)."""
        h.incarnation += 1
        if self.arena is not None and h.incarnation > 1:
            # Supervisor reclaim (README "KV data plane"): the dead
            # incarnation's in-flight slabs — published fabric pages, a
            # handoff export that never got adopted — are taken back by
            # bumping the region epoch: every outstanding descriptor
            # fails closed (ArenaStale) and its consumer falls back to
            # recompute/miss, never a stale adoption. The fresh
            # incarnation mints under the new epoch from a blank region.
            self._reclaim_region(h.replica)
        h.socket_path = os.path.join(
            self._sock_dir, f"w{h.replica}.{h.incarnation}.sock")
        env = dict(os.environ)
        # The repo may be run uninstalled (benchmarks insert sys.path
        # manually); the worker interpreter needs the same root.
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        # One worker per chip group: replica i sees chips i*n .. only.
        n_chips = self.cfg.parallel.tp * self.cfg.parallel.sp
        env.update(chip_env(h.replica * n_chips, n_chips))
        proc = subprocess.Popen(
            [sys.executable, "-m", "tpu_inference.server.worker",
             "--socket", h.socket_path, "--replica", str(h.replica)],
            stdin=subprocess.PIPE, env=env)
        try:
            assert proc.stdin is not None
            proc.stdin.write(json.dumps(
                self._envelope(h.replica)).encode())
            proc.stdin.close()
            client = WorkerClient(h.socket_path, proc,
                                  replica=h.replica,
                                  deadlines=self._deadlines,
                                  chaos=self._make_chaos(h.replica))
            client.on_event = lambda c, obj, blob, hh=h: self._on_event(
                hh, c, obj, blob)
            client.on_lost = lambda c, hh=h: self._on_conn_lost(hh, c)
            client.on_timeout = \
                lambda verb, t, hh=h: self._note_rpc_timeout(hh, verb, t)
            client.start_reader()
            hello = client.rpc("hello", timeout=1800.0)
        except BaseException:
            try:
                proc.kill()
            except OSError:
                pass
            raise
        h.proc, h.client = proc, client
        h.pid = hello.get("pid")
        h.info = hello
        h.started_unix = time.time()
        # Warm worker boot (README "KV fabric"): the fabric's hot set
        # lands in the fresh worker's host tier BEFORE the UP flip
        # makes it routable, so an autoscaled/restarted/upgraded worker
        # serves its first request with fabric hits instead of booting
        # stone-cold. No-op while the pool is empty (initial boot).
        self._fabric_warmboot(h, client)
        h.state = UP
        h.consecutive_failures = 0
        self.warmup_total_s += hello.get("warmup_s", 0.0)
        if self.engine is None:
            self.engine = _EngineInfo(hello)
            # The fleet-level join gauge waits for the first hello: its
            # device labels are facts only the workers have.
            telemetry.emit_build_info(
                self._fleet_registry, device=self.engine.device_info(),
                fleet="subprocess", kv_quant=self.engine_cfg.kv_quant,
                spec_mode=("ngram"
                           if self.engine_cfg.num_speculative_tokens > 0
                           else "off"),
                routing=self.server_cfg.routing)
        telemetry.log_event(
            "worker_up", level="info", replica=h.replica,
            pid=h.pid, incarnation=h.incarnation)

    def _fabric_warmboot(self, h: WorkerHandle,
                         client: WorkerClient) -> int:
        """Push the fabric pool's MRU hot set (capped by
        --fabric-warmboot-pages) into a just-booted worker's host tier
        over import-kv. Each pooled blob re-verifies before shipping —
        a corrupt entry is dropped and counted, never shipped. Best
        effort: any failure leaves the worker cold but serviceable."""
        budget = self.server_cfg.fabric_warmboot_pages
        adopted = 0
        offered_d = 0
        if self.arena is not None:
            # Zero-copy push first: descriptors only — the fresh worker
            # reads each slab straight from the arena and verifies it
            # there; rejected digests come back so the pool drops them.
            hot_d = self.fabric.hot_set_descs(budget)
            if hot_d:
                offered_d = len(hot_d)
                try:
                    r = client.rpc(
                        "import-kv",
                        digests=[d.hex() for d, _ in hot_d],
                        descs=[desc for _, desc in hot_d],
                        idem=f"wbd{h.replica}.{h.incarnation}")
                    adopted += int(r.get("adopted", 0))
                    for hexd in r.get("rejected_digests") or ():
                        self.fabric.reject(bytes.fromhex(hexd))
                except (WorkerGone, TimeoutError, RuntimeError) as e:
                    telemetry.log_event("fabric_warmboot_failed",
                                        level="warning",
                                        replica=h.replica, error=str(e))
                budget = max(0, budget - len(hot_d))
        hot = self.fabric.hot_set(budget)
        pairs = []
        for d, b in hot:
            try:
                pairs.append((d, kvc.deserialize_host_pages(b)[0]))
            except kvc.integrity.KVIntegrityError:
                self.fabric.reject(d)
        if not pairs:
            if adopted:
                telemetry.log_event(
                    "fabric_warmboot", level="info", replica=h.replica,
                    offered=offered_d, adopted=adopted)
            return adopted
        try:
            blob = kvc.serialize_host_pages([p for _, p in pairs])
            with self._lock:
                self.rpc_blob_bytes["import-kv"] += len(blob)
            r = client.rpc(
                "import-kv", blob=blob,
                digests=[d.hex() for d, _ in pairs],
                idem=f"wb{h.replica}.{h.incarnation}")
        except (WorkerGone, TimeoutError, RuntimeError) as e:
            telemetry.log_event("fabric_warmboot_failed",
                                level="warning", replica=h.replica,
                                error=str(e))
            return adopted
        adopted += int(r.get("adopted", 0))
        telemetry.log_event(
            "fabric_warmboot", level="info", replica=h.replica,
            offered=offered_d + len(pairs), adopted=adopted)
        return adopted

    def _reclaim_region(self, rg: int) -> int:
        """Dead-incarnation slab reclaim: drop the region's fabric
        entries, settle the directory books, bump the epoch word so
        every outstanding descriptor fails closed."""
        if self.arena is None or self._arena_dir is None \
                or not (0 <= rg < self.arena.regions):
            return 0
        dropped = self.fabric.drop_region(rg)
        n = self._arena_dir.reclaim(rg)
        self.arena.bump_epoch(rg)
        with self._lock:
            self.shm_reclaims += n
        if n or dropped:
            telemetry.log_event(
                "shm_region_reclaimed", level="info", region=rg,
                slabs=n, fabric_entries=dropped)
        return n

    def _release_handoff_desc(self, entry: "_Tracked") -> None:
        """Drop a tracked handoff's arena slab reference (idempotent).
        Called wherever the blob variant would be dropped — the slab
        frees back to its owner on the next stats tick."""
        desc = entry.handoff_desc
        entry.handoff_desc = None
        if desc is not None and self._arena_dir is not None:
            self._arena_dir.release(desc)

    def _ensure_started(self) -> None:
        with self._start_lock:
            if self._started:
                return
            for h in self.workers:
                self._spawn(h)
            self._started = True

    # ---------------------------------------------------------- facade

    @property
    def engines(self) -> List[_EngineInfo]:
        """Len/iteration parity with EngineGroup.engines (the HTTP layer
        reads ``len(group.engines)`` for the replica count — including
        workers the autoscaler or a rollout added past the configured
        dp, so e.g. /debug/profile can target them)."""
        info = self.engine or _EngineInfo({})
        return [info] * max(self.dp, len(self.workers))

    def warmup(self) -> float:
        self._ensure_started()
        return self.warmup_total_s

    def start(self) -> "ProcessEngineGroup":
        self._ensure_started()
        self._monitor_stop.clear()
        self._monitor = threading.Thread(target=self._watch,
                                         name="fleet-monitor",
                                         daemon=True)
        self._monitor.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        self._stopping = True
        if self._peek_pool is not None:
            self._peek_pool.shutdown(wait=False)
            self._peek_pool = None
        self._monitor_stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5.0)
            self._monitor = None
        for h in self.workers:
            if h.client is not None and h.client.alive:
                try:
                    h.client.rpc("shutdown", timeout=timeout + 30.0,
                                 drain=drain, timeout_s=timeout)
                except (WorkerGone, TimeoutError, RuntimeError):
                    pass
            if h.proc is not None and h.proc.poll() is None:
                try:
                    h.proc.terminate()
                    h.proc.wait(timeout=10.0)
                except (subprocess.TimeoutExpired, OSError):
                    try:
                        h.proc.kill()
                        h.proc.wait(timeout=5.0)
                    except (subprocess.TimeoutExpired, OSError):
                        pass
            if h.client is not None:
                h.client.close()
            h.state = DEAD
        # Anything still tracked gets its terminal callback (shutdown),
        # so no client stream hangs on a router teardown.
        with self._lock:
            leftovers = list(self._tracked.values())
            self._tracked.clear()
            # Parked batch/background entries are in _tracked too (the
            # ghost-finish below covers them); drop the lane handles.
            for q in self._deferred.values():
                q.clear()
        for entry in leftovers:
            self._release_handoff_desc(entry)
            self._finish_trace(entry, "shutdown")
            ghost = entry.seq_local
            ghost.done, ghost.finish_reason = True, "shutdown"
            ghost.finish_time = time.perf_counter()
            entry.on_finish(ghost)
        if self.arena is not None:
            # Every worker is dead: unlink the segment (the kernel
            # reclaims the pages; attached mappings, if any, die with
            # their processes).
            self.arena.close(unlink=True)
            self.arena = None

    # ------------------------------------------------------ supervision

    def _watch(self) -> None:
        """Monitor thread: process liveness, restart backoff, and the
        periodic metrics/stats cache that bounds kill -9 carry loss."""
        last_scrape = 0.0
        while not self._monitor_stop.wait(0.2):
            now = time.monotonic()
            for h in self.workers:
                if h.state in (UP, DRAINING) and h.proc is not None \
                        and h.proc.poll() is not None:
                    self._on_worker_down(
                        h, f"exit rc={h.proc.returncode}")
                elif h.state == RESTARTING and now >= h.restart_at \
                        and not self._stopping:
                    try:
                        self._spawn(h)
                        h.restarts += 1
                    except (WorkerGone, TimeoutError, RuntimeError,
                            OSError) as e:
                        h.consecutive_failures += 1
                        telemetry.log_event(
                            "worker_respawn_failed", level="error",
                            replica=h.replica, error=str(e))
                        self._schedule_restart(h)
            if now - last_scrape >= 1.0:
                last_scrape = now
                self._refresh_caches()
                if self.server_cfg.autoscale:
                    self._autoscale_tick(now)
            self._pump_deferred()

    def _refresh_caches(self) -> None:
        for h in self.workers:
            if h.state != UP or h.client is None:
                continue
            # The stats tick doubles as the data-plane's control
            # channel: the pool watermark rides out (publish
            # back-pressure) and the batched slab frees ride out
            # (arena lifecycle) — no extra RPCs on the hot path.
            frees = (self._arena_dir.drain_free(h.replica)
                     if self._arena_dir is not None else [])
            try:
                h.last_metrics = h.client.rpc("metrics")["samples"]
                h.last_stats = h.client.rpc(
                    "stats", fabric_free=self.fabric.free_pages,
                    arena_free=frees)["stats"]
                frees = []
                h.last_health = h.client.rpc("healthz")
                h.last_steps = h.client.rpc("steps")["steps"]
            except (WorkerGone, TimeoutError, RuntimeError):
                pass
            finally:
                if frees and self._arena_dir is not None:
                    # The tick that would have carried them failed —
                    # retry next second (a free lost forever is a leak).
                    self._arena_dir.requeue_free(h.replica, frees)

    def _schedule_restart(self, h: WorkerHandle) -> None:
        scfg = self.server_cfg
        # Budget covers BOTH successful respawns and consecutive boot
        # failures — a worker whose boot crashes deterministically
        # (deleted checkpoint, bad device) must go DEAD, not respawn a
        # jax-importing process forever.
        if self._stopping:
            h.state = DEAD
            return
        if (h.restarts >= scfg.worker_restart_max
                or h.consecutive_failures > scfg.worker_restart_max):
            # Crash-loop breaker: the budget is spent, so stop burning
            # boot cycles — but keep the replica VISIBLE. QUARANTINED
            # stays in /healthz (degraded, not absent) and pins the
            # tpu_inf_worker_quarantined gauge to 1 so an operator sees
            # a routed-around replica instead of a silently shrunk dp.
            h.state = QUARANTINED
            telemetry.log_event("worker_quarantined", level="error",
                                replica=h.replica, restarts=h.restarts,
                                consecutive_failures=h.consecutive_failures)
            # No respawn will ever bump this region's epoch — reclaim
            # its slabs now or they pin arena memory forever.
            self._reclaim_region(h.replica)
            return
        backoff = min(30.0, scfg.worker_restart_backoff_s
                      * (2 ** max(0, h.consecutive_failures)))
        h.restart_at = time.monotonic() + backoff
        h.state = RESTARTING

    def _note_rpc_timeout(self, h: WorkerHandle, verb: str,
                          timeout_s: float) -> None:
        with self._lock:
            self.rpc_timeouts += 1

    def _on_conn_lost(self, h: WorkerHandle, client: WorkerClient) -> None:
        if self._stopping or h.client is not client:
            return
        if getattr(client, "lost_reason", "") == "frame_error":
            with self._lock:
                self.frame_errors += 1
        # Distinguish "socket died / frame invalid" from "process
        # died": while the worker process is alive and serving, a
        # broken connection is a transport fault — pay a reconnect
        # (worker.serve accepts again on the same socket path), not a
        # full restart with its boot + warmup bill.
        if (h.state == UP and h.proc is not None
                and h.proc.poll() is None):
            threading.Thread(target=self._reconnect_worker,
                             args=(h, client),
                             name="fleet-reconnect",
                             daemon=True).start()
            return
        # Reader died first (socket reset); the monitor would catch the
        # process exit too — whoever flips the state first acts.
        if h.state in (UP, DRAINING):
            self._on_worker_down(h, "connection lost")

    def _reconnect_worker(self, h: WorkerHandle,
                          old_client: WorkerClient) -> None:
        """Connection-level failover: dial the live worker again, swap
        the client under the lock, then resync every request that was
        riding the dead connection. Falls back to the full worker-down
        path if the redial fails (the process may have died between
        poll() and connect)."""
        with self._lock:
            if h.client is not old_client or h.state != UP:
                return  # another actor (restart/rollout) already won
        old_client.close()
        try:
            client = WorkerClient(h.socket_path, h.proc,
                                  connect_timeout=5.0,
                                  replica=h.replica,
                                  deadlines=self._deadlines,
                                  chaos=self._make_chaos(h.replica))
            client.on_event = lambda c, obj, blob, hh=h: self._on_event(
                hh, c, obj, blob)
            client.on_lost = lambda c, hh=h: self._on_conn_lost(hh, c)
            client.on_timeout = \
                lambda verb, t, hh=h: self._note_rpc_timeout(hh, verb, t)
            client.start_reader()
            client.rpc("hello")
        except (WorkerGone, TimeoutError, RuntimeError, OSError) as e:
            telemetry.log_event("worker_reconnect_failed",
                                level="warning", replica=h.replica,
                                reason=getattr(old_client,
                                               "lost_reason", ""),
                                error=str(e))
            if h.state in (UP, DRAINING):
                self._on_worker_down(h, f"reconnect failed: {e}")
            return
        with self._lock:
            if h.client is not old_client or h.state != UP:
                client.close()
                return
            # Re-resolve chaos at swap time: /debug/chaos may have
            # retuned (e.g. disarmed) the injection while this redial
            # was in flight — installing the policy read at dial time
            # would resurrect a stale fault schedule on the fresh
            # connection.
            client.chaos = self._make_chaos(h.replica)
            h.client = client
            self.reconnects += 1
        telemetry.log_event("worker_reconnect", level="warning",
                            replica=h.replica,
                            reason=getattr(old_client, "lost_reason",
                                           "") or "connection lost")
        self._resync_worker(h, old_client)

    def _resync_worker(self, h: WorkerHandle,
                       old_client: WorkerClient) -> None:
        """Requests that were streaming over the dead connection:
        cancel the worker-side ghost (idempotent — the attempt may
        still be decoding into the void) and re-dispatch from the
        router's token record, preferring the SAME worker (its KV
        pages are warm); recompute-resume keeps the stream
        byte-identical under greedy."""
        with self._lock:
            victims = [e for e in self._tracked.values()
                       if e.worker is h and e.client is old_client]
            for entry in victims:
                entry.generation += 1
                entry.worker = entry.client = None
                entry.attempts += 1
                self.retries_attempted += 1
        for entry in victims:
            rid = entry.template.request_id
            if h.client is not None and h.client.alive:
                try:
                    h.client.rpc("cancel", rid=rid,
                                 idem=f"c{rid}.{entry.generation}")
                except (WorkerGone, TimeoutError, RuntimeError):
                    pass
            if self._quarantine_if_poison(entry):
                continue
            if h.routable and self._dispatch(entry, h, (0, 0, 0)):
                continue
            self._retry_or_fail(entry, exclude=h)

    def _quarantine_if_poison(self, entry: _Tracked) -> bool:
        """Poison-request gate (README "Failure model"): once this
        request's attempts have crashed or wedged poison_max_workers
        DISTINCT workers, fail it terminally — a structured 500 with a
        blackbox capture — instead of feeding it the rest of the
        fleet. Returns True when the request was quarantined."""
        limit = self.server_cfg.poison_max_workers
        if limit <= 0 or len(entry.failed_workers) < limit:
            return False
        rid = entry.template.request_id
        with self._lock:
            if self._tracked.pop(rid, None) is None:
                return True  # already finished/quarantined elsewhere
            self.poison_requests += 1
        telemetry.log_event(
            "poison_quarantined", level="error",
            request_id=entry.template.trace_id or str(rid),
            workers=sorted(entry.failed_workers),
            attempts=entry.attempts, streamed=len(entry.tokens))
        if self._flight is not None:
            self._flight.capture("poison_request", min_interval_s=0.0)
        self._finish_trace(entry, "poison")
        ghost = entry.seq_local
        ghost.generated = list(entry.tokens)
        ghost.done, ghost.finish_reason = True, "poison"
        ghost.finish_time = time.perf_counter()
        entry.on_finish(ghost)
        return True

    def _on_worker_down(self, h: WorkerHandle, reason: str) -> None:
        """A worker incarnation died (kill -9, crash, or post-drain
        exit): fold its last-seen monotonic series into the carry, fail
        over its in-flight requests from the router's token record, and
        schedule a respawn under the same replica label."""
        with self._lock:
            # Monitor (proc poll) and reader (conn lost) can both see
            # the death; the state flip under the lock picks one actor.
            if h.state not in (UP, DRAINING):
                return
            h.state = RETIRED if h.retiring else RESTARTING
        if h.state != RETIRED:
            h.consecutive_failures += 1
        if h.proc is not None and h.proc.poll() is None:
            try:
                h.proc.kill()
            except OSError:
                pass
        if h.client is not None:
            h.client.close()
        if h.folded_incarnation != h.incarnation:
            # Once per incarnation: the drained-event path and a second
            # death report must not double-fold the same totals. The
            # folded dump is then CLEARED — rendering it alongside the
            # carry (e.g. a scrape hitting the fresh incarnation before
            # its first metrics RPC succeeds) would double-count.
            h.folded_incarnation = h.incarnation
            telemetry.fold_dump_into_carry(h.carry, h.last_metrics)
            h.last_metrics = []
            # Fold the dead incarnation's SLO breach totals, then zero
            # the cached copy — keeping both would double-count until
            # the fresh incarnation's first stats refresh.
            slo = (h.last_stats or {}).get("slo") or {}
            h.slo_breach_carry["ttft"] += slo.get("ttft_breaches", 0)
            h.slo_breach_carry["tpot"] += slo.get("tpot_breaches", 0)
            if slo:
                h.last_stats = {**h.last_stats,
                                "slo": {**slo, "ttft_breaches": 0,
                                        "tpot_breaches": 0}}
        if h.state == RETIRED:
            # Intentional exit (scale-down or rollout retirement): the
            # drain already migrated its sequences out, so the failover
            # sweep below is a no-op safety net, and there is nothing
            # to respawn.
            h.retiring = False
            telemetry.log_event("worker_retired", replica=h.replica,
                                reason=reason)
        else:
            telemetry.log_event("worker_down", level="warning",
                                replica=h.replica, reason=reason)
            self._harvest_blackbox(h, reason)
            self._schedule_restart(h)
        self._failover_worker(h)

    def _harvest_blackbox(self, h: WorkerHandle, reason: str) -> None:
        """Post-mortem evidence sweep: the dead worker's flight-recorder
        dir is on the router's local FS (same --blackbox-dir), so a
        kill -9's last periodic heartbeat and any trigger captures are
        sitting there — surface them in the log and the /debug/blackbox
        index so the death is triaged with evidence, not guesses."""
        root = self.server_cfg.blackbox_dir
        if not root:
            return
        rdir = os.path.join(root, f"replica-{h.replica}")
        try:
            captures = sorted(f for f in os.listdir(rdir)
                              if f.endswith(".json"))
        except OSError:
            captures = []
        if captures:
            telemetry.log_event(
                "worker_blackbox_harvested", replica=h.replica,
                reason=reason, captures=len(captures),
                newest=captures[-1], dir=rdir)

    # --------------------------------------------------------- routing

    def _routable(self) -> List[WorkerHandle]:
        return [h for h in self.workers if h.routable]

    def _fleet_load(self, h: WorkerHandle) -> int:
        with self._lock:
            return sum(1 for e in self._tracked.values()
                       if e.worker is h)

    def _digests_for(self, seq: Sequence) -> Tuple[List[bytes], int]:
        """Routing-time prefix digests — same truncation/trim rule as
        EngineGroup._digests_for (replicas.py), over the router's own
        copy of the engine config."""
        ecfg = self.engine_cfg
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

    def _pd_recomputes_total(self) -> int:
        """Every non-clean handoff, both ends: router-side fallbacks
        (stale export, no adopter) plus worker-side adoption failures
        (malformed blob, pool shortfall) from the workers' cached
        stats — the ONE number tpu_inf_pd_handoff_recomputes_total and
        the supervision view report."""
        return self.pd_handoff_recomputes + sum(
            (h.last_stats or {}).get("pd_adopt_fallbacks", 0)
            for h in self.workers)

    def _cold_peek(self, h: WorkerHandle) -> dict:
        """Scoring fallback for a worker that can't answer a peek in
        time: no warmth, router-side load estimate, no pressure."""
        return {"hbm": 0, "host": 0, "load": self._fleet_load(h),
                "pressure": False, "occupancy": 0.0, "backlog": 0,
                "role": self.roles[h.replica]}

    def _peek(self, h: WorkerHandle, digests: List[bytes],
              timeout: float = 10.0) -> dict:
        client = h.client
        if client is None:
            return self._cold_peek(h)
        try:
            return client.rpc("peek", timeout=timeout,
                              digests=[d.hex() for d in digests])
        except (WorkerGone, TimeoutError, RuntimeError):
            return self._cold_peek(h)

    def _peek_many(self, cands: List[WorkerHandle],
                   digests: List[bytes]) -> List[dict]:
        """Concurrent candidate peeks with a short fan-out deadline
        (ServerConfig.route_peek_timeout_s): the serial loop used to add
        one slow worker's full round-trip to EVERY admission; now the
        peeks fly together and any straggler scores with the cold
        fallback while its late reply is discarded (the RPC layer's own
        timeout reaps it)."""
        pool = self._peek_pool
        if len(cands) == 1 or self._stopping or pool is None:
            return [self._peek(h, digests) for h in cands]
        from concurrent.futures import wait as _futures_wait
        deadline = self.server_cfg.route_peek_timeout_s
        # The RPC itself is clamped near the fan-out deadline: a wedged
        # worker's straggler threads otherwise block 10s each and can
        # saturate the small pool, cold-scoring HEALTHY candidates too.
        try:
            futs = [pool.submit(self._peek, h, digests, deadline + 0.5)
                    for h in cands]
        except RuntimeError:        # pool shut down by a racing stop()
            return [self._peek(h, digests) for h in cands]
        _futures_wait(futs, timeout=deadline)
        return [f.result() if f.done() else self._cold_peek(h)
                for h, f in zip(cands, futs)]

    def _phase_pool(self, phase: Optional[str]) -> List[WorkerHandle]:
        """Routable workers eligible for one phase (README "P/D
        disaggregation"): new prompts ("prefill") avoid decode-role
        workers, resumes/handoffs ("decode") avoid prefill-role workers.
        An empty phase pool falls back to every routable worker so a
        degraded fleet still serves (the off-role worker lazy-compiles
        the other phase's graphs)."""
        routable = self._routable()
        if not self.pd_enabled or phase is None:
            return routable
        exclude = "decode" if phase == "prefill" else "prefill"
        return ([h for h in routable
                 if self.roles[h.replica] != exclude] or routable)

    @staticmethod
    def _entry_phase(entry: "_Tracked") -> str:
        """Routing phase for a resubmission: a stream with tokens is
        decode work; a zero-delivery retry re-enters as a prompt."""
        return "decode" if entry.tokens else "prefill"

    def _rotate(self, ties: list):
        if len(ties) == 1:
            return ties[0]
        idx = self._rr % len(ties)
        self._rr += 1
        return ties[idx]

    def _pick(self, cands: List[WorkerHandle],
              seq: Optional[Sequence] = None,
              phase: Optional[str] = None
              ) -> Tuple[WorkerHandle, Tuple[int, int, int], int]:
        """Choose a worker; returns (handle, (hbm, host, fabric_extra)
        peeked pages, load at decision time). Candidate peeks fan out
        concurrently (_peek_many); the fabric depth comes from the
        router's OWN pool index — no extra RPC. The scores are
        kv_fabric.prefill_route_score / decode_route_score — THE
        four-temperature formulas shared with EngineGroup._pick
        (replicas.py — the in-process fleet is the documented
        contract), so the two backends cannot drift. For
        ``phase="decode"`` under a P/D split the score flips to the
        decode side's costs — ladder occupancy + load, minus the
        warmth discounts (a handoff lands on the least-loaded decode
        worker, warmth breaking ties)."""
        cfg = self.server_cfg
        digests: List[bytes] = []
        prompt_pages = 0
        if seq is not None and cfg.routing == "prefix_affinity":
            digests, prompt_pages = self._digests_for(seq)
        fdepth = self.fabric.match_depth(digests)
        peeks = self._peek_many(cands, digests)
        if phase == "decode" and self.pd_enabled:
            scored = []
            for h, p in zip(cands, peeks):
                occ = float(p.get("occupancy") or 0.0)
                fx = kv_fabric.fabric_extra_pages(
                    fdepth, p["hbm"] + p["host"], prompt_pages)
                score = kv_fabric.decode_route_score(
                    cfg, hbm=p["hbm"], host=p["host"], fabric=fx,
                    load=p["load"], occupancy=occ,
                    pressured=p["pressure"])
                scored.append(((score, p["pressure"], p["load"]),
                               h, (p["hbm"], p["host"], fx), p["load"]))
            best = min(key for key, _, _, _ in scored)
            return self._rotate([(h, hit, load)
                                 for key, h, hit, load in scored
                                 if key == best])
        if digests and (fdepth > 0
                        or any(p["hbm"] + p["host"] for p in peeks)):
            scored = []
            for h, p in zip(cands, peeks):
                fx = kv_fabric.fabric_extra_pages(
                    fdepth, p["hbm"] + p["host"], prompt_pages)
                score = kv_fabric.prefill_route_score(
                    cfg, prompt_pages=prompt_pages, hbm=p["hbm"],
                    host=p["host"], fabric=fx, load=p["load"],
                    pressured=p["pressure"])
                scored.append(((score, p["pressure"], p["load"]),
                               h, (p["hbm"], p["host"], fx), p["load"]))
            best = min(key for key, _, _, _ in scored)
            return self._rotate([(h, hit, load)
                                 for key, h, hit, load in scored
                                 if key == best])
        keyed = [(kv_fabric.cold_route_key(p["pressure"], p["load"]),
                  h, p["load"])
                 for h, p in zip(cands, peeks)]
        best = min(key for key, _, _ in keyed)
        return self._rotate([(h, (0, 0, 0), load)
                             for key, h, load in keyed if key == best])

    # ------------------------------------------------------- submission

    def submit(self, seq: Sequence, on_token: Callable,
               on_finish: Callable) -> None:
        # Trace-id propagation (README "Observability"): HTTP ingress
        # mints or propagates X-Request-Id; every OTHER ingress (bench
        # harnesses, tests driving the group directly) used to submit
        # with trace_id="" and worker-side logs/spans fell back to the
        # engine-internal str(request_id) — un-joinable across the
        # processes a handoff spans. Mint here so the id exists BEFORE
        # the clone/dispatch below ships it to the first worker.
        if not seq.trace_id:
            import uuid
            seq.trace_id = uuid.uuid4().hex[:16]
        # New prompts are prefill work: under a P/D split they go to the
        # prefill tier only (README "P/D disaggregation"). ONE snapshot
        # of the routable set — a worker dying between an emptiness
        # check and a second _routable() read must not hand _pick an
        # empty pool.
        pool = self._phase_pool("prefill")
        if not pool:
            with self._lock:
                self.requests_unavailable += 1
            raise FleetUnavailable("no routable worker",
                                   self.server_cfg.retry_after_s)
        t_route = time.perf_counter()
        h, hit, load = self._pick(pool, seq)
        self._recorder.add(
            "route", seq.trace_id, t_route, time.perf_counter(),
            dest=h.replica, hbm_hit=hit[0], host_hit=hit[1],
            fabric_hit=hit[2], load=load)
        cap = self.server_cfg.admission_queue_depth
        if cap > 0 and load >= cap:
            # Affinity saturated a warm worker: least-loaded fallback
            # before shedding, exactly like EngineGroup.submit.
            h2, _, load2 = self._pick(pool)
            if load2 >= cap:
                # Class-aware admission (README "Elastic fleet"): with
                # per-class queues enabled, saturation means different
                # things per class. Batch/background requests PARK in
                # a bounded deferred lane instead of bouncing a 429 at
                # the client; interactive requests PREEMPT the newest
                # batch-lane occupant (recompute-resume puts it back,
                # byte-identical under greedy) and take its slot. Only
                # when neither escape works does the legacy shed fire.
                cls = seq.priority_class or "interactive"
                if self.server_cfg.class_queue_depth > 0:
                    if class_rank(cls) > 0:
                        if self._defer(seq, on_token, on_finish, cls):
                            return
                        self._shed(seq, cls, load2, cap)
                    vw = self._preempt_for_interactive()
                    if vw is None:
                        self._shed(seq, cls, load2, cap)
                    h, hit = vw, (0, 0, 0)
                else:
                    self._shed(seq, cls, load2, cap)
            else:
                h, hit = h2, self._peek_hit(h2, seq)
        entry = _Tracked(_clone_request(seq), on_token, on_finish)
        entry.seq_local.trace_id = seq.trace_id
        entry.seq_local.enqueue_time = time.perf_counter()
        with self._lock:
            self._tracked[seq.request_id] = entry
        if not self._dispatch(entry, h, hit):
            self._retry_or_fail(entry, exclude=h)

    def _peek_hit(self, h: WorkerHandle,
                  seq: Sequence) -> Tuple[int, int, int]:
        if self.server_cfg.routing != "prefix_affinity":
            return (0, 0, 0)
        digests, prompt_pages = self._digests_for(seq)
        p = self._peek(h, digests)
        fx = kv_fabric.fabric_extra_pages(
            self.fabric.match_depth(digests), p["hbm"] + p["host"],
            prompt_pages)
        return (p["hbm"], p["host"], fx)

    def _fabric_pull(self, h: WorkerHandle, t: Sequence, warm: int,
                     fabric_extra: int, entry: "_Tracked") -> int:
        """Ship the fabric run beyond ``warm`` pages into worker ``h``'s
        host tier (import-kv). get_pages crc-verifies every blob — a
        corrupt or evicted-since-peek entry just shortens the run — and
        the pages re-serialize into one import blob whose embedded
        digests the worker re-verifies on adoption. Returns the pages
        actually shipped and applied (0 on any transport failure: the
        dispatch proceeds cold — the fabric is an accelerator, never a
        correctness dependency)."""
        if h.client is None:
            return 0
        digests = self._digests_for(t)[0]
        want = digests[warm:warm + fabric_extra]
        if self.arena is not None:
            # Zero-copy pull: ship descriptors; the worker reads each
            # slab from the arena, crc-verifies it there, and reports
            # rejects back so the pool drops them. No KV byte touches
            # a socket or this process.
            descs = self.fabric.get_descs(want)
            if descs:
                try:
                    r = h.client.rpc(
                        "import-kv",
                        digests=[d.hex() for d, _ in descs],
                        descs=[dd for _, dd in descs],
                        idem=f"fd{t.request_id}.{entry.attempts}."
                             f"{entry.generation}")
                    rejected = r.get("rejected_digests") or ()
                    for hexd in rejected:
                        self.fabric.reject(bytes.fromhex(hexd))
                    if not r.get("applied"):
                        return 0
                    return max(0, len(descs) - len(rejected))
                except (WorkerGone, TimeoutError, RuntimeError) as e:
                    telemetry.log_event("fabric_pull_failed",
                                        level="warning",
                                        replica=h.replica, error=str(e))
                    return 0
        entries = self.fabric.get_pages(want)
        if not entries:
            return 0
        try:
            blob = kvc.serialize_host_pages([p for _, p in entries])
            with self._lock:
                self.rpc_blob_bytes["import-kv"] += len(blob)
            r = h.client.rpc(
                "import-kv", blob=blob,
                digests=[d.hex() for d, _ in entries],
                idem=f"f{t.request_id}.{entry.attempts}."
                     f"{entry.generation}")
            if not r.get("applied"):
                return 0
        except (WorkerGone, TimeoutError, RuntimeError) as e:
            telemetry.log_event("fabric_pull_failed", level="warning",
                                replica=h.replica, error=str(e))
            return 0
        return len(entries)

    def _shed(self, seq: Sequence, cls: str, load: int, cap: int) -> None:
        """Terminal 429: count it (globally and per class) and raise.
        Message format is pinned by tests/clients — keep it identical
        to the pre-class-queue single-cap shed."""
        with self._lock:
            self.requests_shed += 1
            self.class_shed[cls] = self.class_shed.get(cls, 0) + 1
        # A shed IS terminal: seal the route span so sustained overload
        # can't fill the recorder's open table and evict a LIVE
        # request's trace.
        self._recorder.seal(seq.trace_id)
        raise FleetSaturated(
            f"admission queue cap reached ({load} >= {cap} on "
            "the least-loaded worker)",
            self.server_cfg.retry_after_s)

    def _defer(self, seq: Sequence, on_token: Callable,
               on_finish: Callable, cls: str) -> bool:
        """Park a batch/background request in its class lane instead of
        shedding it. Returns False when the lane itself is full (then
        the caller sheds — the deferred queues are bounded so a batch
        flood can't grow router memory without limit)."""
        entry = _Tracked(_clone_request(seq), on_token, on_finish)
        entry.seq_local.trace_id = seq.trace_id
        entry.seq_local.enqueue_time = time.perf_counter()
        with self._lock:
            q = self._deferred[cls]
            if len(q) >= self.server_cfg.class_queue_depth:
                return False
            self._tracked[seq.request_id] = entry
            q.append(entry)
        telemetry.log_event("request_deferred", request_id=seq.request_id,
                            trace_id=seq.trace_id, priority_class=cls)
        return True

    def _preempt_for_interactive(self) -> Optional[WorkerHandle]:
        """Watermark preemption: evict the newest lowest-class running
        request back to its deferred lane (recompute-resume replays its
        generated tokens on re-dispatch — byte-identical under greedy)
        and return the worker whose slot it freed."""
        with self._lock:
            victims = [e for e in self._tracked.values()
                       if e.worker is not None
                       and class_rank(e.template.priority_class) > 0]
            if not victims:
                return None
            victim = max(victims, key=lambda e: (
                class_rank(e.template.priority_class), e.t_submit))
            vw, vc = victim.worker, victim.client
            victim.generation += 1
            victim.worker = victim.client = None
            victim.attempts += 1
            vcls = victim.template.priority_class
            self.class_preemptions[vcls] = (
                self.class_preemptions.get(vcls, 0) + 1)
            # Front of its lane: a preempted request resumes before any
            # never-started work of the same class.
            self._deferred[vcls].appendleft(victim)
        rid = victim.template.request_id

        def _rpc_cancel(client=vc):
            try:
                client.rpc("cancel", timeout=10.0, rid=rid)
            except (WorkerGone, TimeoutError, RuntimeError):
                pass

        if vc is not None:
            threading.Thread(target=_rpc_cancel, daemon=True,
                             name="fleet-preempt-cancel").start()
        telemetry.log_event("class_preempted", request_id=rid,
                            trace_id=victim.template.trace_id,
                            priority_class=vcls, replica=vw.replica)
        return vw

    def _pump_deferred(self) -> None:
        """Monitor-thread lane drain: re-admit parked batch/background
        work whenever capacity frees up. Single consumer (the monitor),
        so head-pop races only against cancel()."""
        if not any(self._deferred.values()):
            return
        cap = self.server_cfg.admission_queue_depth
        while True:
            with self._lock:
                entry = None
                for cls in ("batch", "background"):
                    q = self._deferred[cls]
                    # Purge heads cancelled while parked.
                    while q and q[0].template.request_id \
                            not in self._tracked:
                        q.popleft()
                    if q:
                        entry = q[0]
                        break
                if entry is None:
                    return
            pool = self._phase_pool(self._entry_phase(entry))
            if not pool:
                return
            h, hit, load = self._pick(pool, entry.template)
            if cap > 0 and load >= cap:
                return
            with self._lock:
                q = self._deferred[cls]
                if (not q or q[0] is not entry
                        or entry.template.request_id not in self._tracked):
                    continue
                q.popleft()
            if not self._dispatch(entry, h, hit):
                self._retry_or_fail(entry, exclude=h)

    def _dispatch(self, entry: _Tracked, h: WorkerHandle,
                  hit: Tuple[int, int, int]) -> bool:
        """Submit one attempt to one worker. Returns False when the
        worker refused (dead/draining) so the caller can re-route."""
        t = entry.template
        gen_tokens = list(entry.tokens)
        with self._lock:
            entry.worker, entry.client = h, h.client
        hbm, host, fabric_extra = hit
        meta = entry.handoff_meta
        live_handoff = (meta is not None
                        and bool(entry.handoff_blob or entry.handoff_desc)
                        and len(gen_tokens) == meta["n_generated"])
        # Fabric pull (README "KV fabric"): pages the router's pool
        # covers beyond this worker's own warm depth ship to its host
        # tier over the import-kv RPC BEFORE the submit — the verb
        # replies only after the engine loop applied the import, so
        # this request's prefill is guaranteed to see them. A live
        # handoff dispatch skips it: the attempt already carries the
        # full KV, and pre-warming the same pages is a redundant
        # import-kv round trip on the handoff critical path.
        fabric_pulled = 0
        if fabric_extra > 0 and not live_handoff:
            fabric_pulled = self._fabric_pull(
                h, t, hbm + host, fabric_extra, entry)
        total_hit = hbm + host + fabric_pulled
        sl = entry.seq_local
        sl.routed_replica = h.replica
        sl.route_hit_pages = total_hit
        sl.route_host_hit_pages = host
        sl.route_fabric_hit_pages = fabric_pulled
        sl.attempt = entry.attempts
        stats = self._route_stats[h.replica]
        if total_hit > 0:
            self.route_prefix_hits += 1
            stats["hits"] += 1
            stats["hit_pages"] += total_hit
            stats["host_hit_pages"] += host
            self._route_hit_pages_hist.observe(total_hit)
        else:
            self.route_cold += 1
            stats["cold"] += 1
        if fabric_pulled > 0:
            self.route_fabric_hits += 1
            stats["fabric_hit_pages"] += fabric_pulled
            self._route_fabric_hit_pages_hist.observe(fabric_pulled)
        if gen_tokens:
            self.resume_resubmits += 1
            entry.resume_stream_len = (
                min(len(t.prompt_tokens) + len(gen_tokens),
                    self.engine_cfg.max_context - 1))
        payload = {
            "request_id": t.request_id,
            "route_hit_pages": total_hit,
            "route_host_hit_pages": host,
            "route_fabric_hit_pages": fabric_pulled,
            "prompt_tokens": list(t.prompt_tokens),
            "max_new_tokens": t.max_new_tokens,
            "temperature": t.temperature, "top_p": t.top_p,
            "top_k": t.top_k, "seed": t.seed,
            "repeat_penalty": t.repeat_penalty,
            "repeat_last_n": t.repeat_last_n,
            "eos_token_id": t.eos_token_id,
            "trace_id": t.trace_id,
            "class": t.priority_class,
            "attempt": entry.attempts,
            "generated": gen_tokens,
        }
        blob = b""
        if meta is not None:
            if live_handoff:
                # Live handoff resume: the worker adopts the exported KV
                # (incl. the partial final page) and continues decode
                # with zero recomputed tokens. On the shm plane the
                # frame carries only the arena descriptor — the decode
                # worker reads+verifies the slab itself and falls back
                # to recompute-resume on any stale/corrupt read.
                payload["handoff"] = {"ctx_len": meta["ctx_len"]}
                if entry.handoff_desc is not None:
                    payload["handoff"]["kv_desc"] = entry.handoff_desc
                else:
                    blob = entry.handoff_blob
            else:
                # Decode advanced past the export (the blob was dropped
                # at the first post-handoff token, or the length no
                # longer matches — e.g. the adopter died mid-stream):
                # fall back to recompute-resume from the router's token
                # record, byte-identical under greedy.
                entry.handoff_blob = entry.handoff_meta = None
                self._release_handoff_desc(entry)
                with self._lock:
                    self.pd_handoff_recomputes += 1
        # Idempotency token, unique per dispatch attempt: a duplicate
        # submit frame (retry over a fresh connection after a lost ack)
        # replays the recorded ack instead of admitting a second live
        # attempt.
        idem = f"s{t.request_id}.{entry.attempts}.{entry.generation}"
        try:
            if blob:
                with self._lock:
                    self.rpc_blob_bytes["submit"] += len(blob)
            h.client.rpc("submit", seq=payload, blob=blob, idem=idem)
            return True
        except (WorkerGone, RuntimeError) as e:
            telemetry.log_event(
                "dispatch_refused", level="warning", replica=h.replica,
                request_id=t.request_id, error=str(e) or type(e).__name__)
            return False
        except TimeoutError:
            # The worker wedged with this attempt (or the RPC is still
            # QUEUED behind a busy reader): count the victim toward the
            # poison gate, and cancel so the worker cannot later decode
            # a ghost alongside the re-routed copy. Best effort — if
            # the worker is truly dead the cancel fails too.
            entry.failed_workers.add(h.replica)
            try:
                h.client.rpc("cancel", timeout=5.0, rid=t.request_id,
                             idem=f"c{idem}")
            except (WorkerGone, TimeoutError, RuntimeError):
                pass
            return False

    def _retry_or_fail(self, entry: _Tracked,
                       exclude: Optional[WorkerHandle] = None) -> None:
        """Re-route one attempt after a refused/failed dispatch; fail
        cleanly when no worker remains.

        An empty pool or a refused dispatch is often a transient gap,
        not an outage — the target's connection is mid-reconnect after
        a wedge recycle, or the supervisor is restarting the process.
        Re-pick inside a short grace window before declaring the fleet
        unavailable; each round re-checks the claim so a competing
        failover path never double-runs the request."""
        if exclude is not None:
            with self._lock:
                if entry.worker is not exclude:
                    # A competing path (worker-down failover / migrate)
                    # detached and re-dispatched this entry while our
                    # dispatch to `exclude` was failing — re-routing it
                    # again here would run the request twice.
                    return
                entry.worker = entry.client = None
        last = exclude
        deadline = time.monotonic() + _REROUTE_GRACE_S
        while not self._stopping:
            if self._quarantine_if_poison(entry):
                return
            phase = self._entry_phase(entry)
            pool = [h for h in self._phase_pool(phase) if h is not last]
            if not pool:
                pool = ([h for h in self._routable() if h is not last]
                        or self._routable())
            if pool:
                h, hit, _ = self._pick(pool, entry.template, phase=phase)
                if self._dispatch(entry, h, hit):
                    return
                with self._lock:
                    if entry.worker is not h:
                        return      # a competing path took over
                    entry.worker = entry.client = None
                last = h
            if time.monotonic() >= deadline:
                break
            time.sleep(0.25)
        rid = entry.template.request_id
        telemetry.log_event("request_unavailable", level="warning",
                            request_id=rid, attempts=entry.attempts)
        with self._lock:
            self._tracked.pop(rid, None)
            self._release_handoff_desc(entry)
        self._finish_trace(entry, "unavailable")
        ghost = entry.seq_local
        ghost.done, ghost.finish_reason = True, "unavailable"
        ghost.finish_time = time.perf_counter()
        entry.on_finish(ghost)

    def cancel(self, request_id: int) -> None:
        with self._lock:
            entry = self._tracked.pop(request_id, None)
            if entry is not None:
                entry.generation += 1
                h = entry.worker
                self._release_handoff_desc(entry)
        if entry is None or h is None or h.client is None:
            return

        def _rpc_cancel(client=h.client):
            # Fire-and-forget: cancel is called from HTTP handlers
            # (timeouts, disconnects, stop sequences) that must not
            # block on a slow worker; a lost cancel only costs the
            # worker a few wasted tokens before its own reap.
            try:
                client.rpc("cancel", rid=request_id,
                           idem=f"c{request_id}.x")
            except (WorkerGone, TimeoutError, RuntimeError):
                pass

        threading.Thread(target=_rpc_cancel, name="fleet-cancel",
                         daemon=True).start()

    # ----------------------------------------------------------- events

    def _on_event(self, h: WorkerHandle, client: WorkerClient,
                  obj: dict, blob: bytes) -> None:
        ev = obj.get("ev")
        if self._stopping and ev in ("migrate", "drained", "handoff"):
            return      # teardown: no re-routing onto closing workers
        if ev == "token":
            self._on_token(h, client, obj)
        elif ev == "finish":
            self._on_finish(h, client, obj)
        elif ev == "handoff":
            self._on_handoff(h, client, obj, blob)
        elif ev == "spans":
            # A prefill worker's sealed handoff-side spans (the handoff
            # frame itself left before the worker sealed its trace).
            self._recorder.ingest(obj.get("trace") or "",
                                  obj.get("spans") or ())
        elif ev == "migrate":
            self._on_migrate(h, client, obj, blob)
        elif ev == "fabric_put":
            self._on_fabric_put(h, obj, blob)
        elif ev == "drained":
            self._on_drained(h, client, obj)

    def _on_fabric_put(self, h: WorkerHandle, obj: dict,
                       blob: bytes) -> None:
        """Ingest a worker's published prefix pages into the fabric
        pool (README "KV fabric"). The frame carries per-page blob
        lengths so the event thread slices without deserializing;
        integrity is enforced at get time (every pull re-verifies its
        blob's crc32c), so a corrupt publish can occupy a slot but can
        never be adopted. A frame whose lengths disagree with the blob
        is dropped whole — never partially ingested."""
        digests = obj.get("digests") or ()
        descs = obj.get("descs")
        if descs is not None:
            # Zero-copy publish: descriptors only — register each slab
            # with the supervisor's ledger, pool the descriptor. The
            # payload bytes never traversed this socket (the verb's
            # rpc_blob_bytes row stays at 0, the lane's grade).
            if len(digests) != len(descs) or blob:
                with self._lock:
                    self.frame_errors += 1
                telemetry.log_event(
                    "fabric_put_malformed", level="warning",
                    replica=h.replica, digests=len(digests),
                    descs=len(descs), blob_bytes=len(blob))
                return
            for d, desc in zip(digests, descs):
                if self._arena_dir is not None:
                    self._arena_dir.register(desc)
                self.fabric.put_desc(bytes.fromhex(d), desc)
            return
        lens = obj.get("lens") or ()
        if len(digests) != len(lens) or sum(lens) != len(blob):
            with self._lock:
                self.frame_errors += 1
            telemetry.log_event(
                "fabric_put_malformed", level="warning",
                replica=h.replica, digests=len(digests),
                lens=len(lens), blob_bytes=len(blob))
            return
        with self._lock:
            self.rpc_blob_bytes["fabric_put"] += len(blob)
        off = 0
        for d, n in zip(digests, lens):
            self.fabric.put_blob(bytes.fromhex(d), blob[off:off + n])
            off += n

    def _entry_for(self, rid: int, h: WorkerHandle,
                   client: WorkerClient) -> Optional[_Tracked]:
        entry = self._tracked.get(rid)
        if entry is None or entry.worker is not h \
                or entry.client is not client:
            return None
        return entry

    def _on_token(self, h, client, obj) -> None:
        with self._lock:
            entry = self._entry_for(obj["rid"], h, client)
            if entry is None:
                return
            tok = int(obj["t"])
            k = obj.get("k")
            if k is not None and int(k) != len(entry.tokens):
                # Stream-index gap: a frame went missing (or arrived
                # twice) between this worker and us. Appending would
                # silently corrupt the completion — recycle the
                # connection instead and let resync re-route the
                # request from its last known-good prefix.
                client.lost_reason = client.lost_reason or "stream_gap"
                bad = client
            else:
                bad = None
                entry.tokens.append(tok)
        if bad is not None:
            telemetry.log_event(
                "stream_gap", level="error", replica=h.replica,
                request_id=obj["rid"], expected=len(entry.tokens),
                got=int(k))
            bad.close()
            return
        with self._lock:
            meta = entry.handoff_meta
            if ((entry.handoff_blob is not None
                 or entry.handoff_desc is not None) and meta is not None
                    and len(entry.tokens) > meta["n_generated"]):
                # The adopter streamed past the export: the blob can
                # never be dispatched again (a re-adoption would fork
                # the stream) — drop it now rather than pinning
                # megabytes of dead KV for the stream's lifetime. The
                # small meta stays so a later failover still counts as
                # a handoff recompute in _dispatch.
                entry.handoff_blob = None
                self._release_handoff_desc(entry)
            sl = entry.seq_local
            sl.generated.append(tok)
            if sl.first_token_time == 0.0:
                sl.first_token_time = time.perf_counter()
                # Router-observed TTFT (submit -> first streamed token,
                # deferral park time included) — the autoscaler's
                # breach sensor.
                self._ttft_obs.append(
                    (sl.first_token_time,
                     sl.first_token_time - entry.t_submit))
        entry.on_token(sl, tok)

    def _finish_trace(self, entry: _Tracked, reason: str) -> None:
        """Terminal end of a tracked request: emit the router's root
        span (submit -> terminal, every attempt/handoff inside it) and
        seal the assembled cross-process trace into the recent ring —
        the /debug/trace and Chrome-export source."""
        rec = self._recorder
        if not rec.enabled:
            return
        t = entry.template
        tid = t.trace_id or str(t.request_id)
        rec.add("request", tid, entry.t_submit, time.perf_counter(),
                parent="", reason=reason, attempts=entry.attempts,
                output_tokens=len(entry.tokens))
        rec.seal(tid)

    def _on_finish(self, h, client, obj) -> None:
        rid = obj["rid"]
        reason = obj.get("reason", "stop")
        # Worker-side spans ride the finish frame; fold them in before
        # the terminal path below seals the trace.
        self._recorder.ingest(obj.get("trace") or "",
                              obj.get("spans") or ())
        with self._lock:
            entry = self._entry_for(rid, h, client)
            if entry is None:
                return
            if obj.get("counters") and h.last_stats:
                # The worker's engine counters as of this finish: the
                # stats cache is refreshed by the monitor thread, which
                # a respawn holds for seconds, and a caller that reads
                # supervision_counters() on its request's finish must
                # find what the request did (a swap-in resume).
                h.last_stats = {**h.last_stats, **obj["counters"]}
            retryable = (reason in _RETRYABLE
                         and not entry.tokens
                         and entry.attempts
                         < self.server_cfg.failover_max_retries)
            # Zero-delivery retries replay from the prompt: prefill work.
            pool = ([w for w in self._phase_pool("prefill") if w is not h]
                    or self._routable()) if retryable else []
            if pool:
                entry.attempts += 1
                entry.generation += 1
                entry.worker = entry.client = None   # claim (see above)
                self.retries_attempted += 1
            else:
                self._tracked.pop(rid, None)
                self._release_handoff_desc(entry)
                if entry.attempts and reason in ("stop", "length"):
                    self.retries_succeeded += 1
            # Migration accounting: the resume stream this attempt
            # re-prefilled, minus what the destination's cache tiers
            # (incl. migrated pages) served.
            if entry.resume_stream_len and not pool:
                cached = int(obj.get("cached_tokens", 0))
                reused = min(cached, entry.resume_stream_len)
                self.resume_reused_tokens += reused
                self.resume_recomputed_tokens += (
                    entry.resume_stream_len - reused)
        if pool:
            hh, hit, _ = self._pick(pool, entry.template)
            if self._dispatch(entry, hh, hit):
                return
            self._retry_or_fail(entry, exclude=hh)
            return
        self._finish_trace(entry, reason)
        sl = entry.seq_local
        sl.done = True
        sl.finish_reason = reason
        sl.finish_time = time.perf_counter()
        sl.cached_tokens = int(obj.get("cached_tokens", 0))
        sl.host_restored_pages = int(obj.get("host_restored_pages", 0))
        sl.preemptions = int(obj.get("preemptions", 0))
        if sl.first_token_time and obj.get("prefill_s") is not None:
            # Synthesize a local prefill_start from the worker-reported
            # prefill duration so the Ollama duration counters hold.
            sl.prefill_start = max(
                sl.enqueue_time,
                sl.first_token_time - float(obj["prefill_s"]))
        entry.on_finish(sl)

    def _checked_blob(self, blob: bytes, path: str, rid: int) -> bytes:
        """Gate a KV blob on its end-to-end digest before it can be
        re-dispatched or imported. A corrupt blob is rejected AND
        counted — never adopted silently — and the caller falls back to
        recompute-resume from the router's token record
        (byte-identical under greedy), exactly like a missing blob."""
        if not blob:
            return blob
        err = kvc.verify_host_pages_blob(blob)
        if err is None:
            return blob
        with self._lock:
            self.kv_rejections += 1
        telemetry.log_event(
            "kv_blob_rejected", level="error", path=path,
            request_id=rid, bytes=len(blob), error=err)
        if self._flight is not None:
            self._flight.capture("kv_corruption", min_interval_s=0.0)
        return b""

    def _fabric_salvage(self, digests: List[bytes], blob: bytes,
                        rid: int, path: str) -> int:
        """Pool-mediated fallback for a point-to-point KV transfer
        whose destination vanished (README "KV fabric" decision table):
        park the export's full prompt-prefix pages in the fabric pool,
        keyed by their chain digests, so the eventual resubmission's
        fabric pull restores them instead of re-prefilling the whole
        stream. Partial/suffix pages beyond the digest chain are not
        poolable (chain digests key FULL pages only) and still ride the
        recompute path. Returns pages parked."""
        if self.fabric.capacity <= 0 or not blob or not digests:
            return 0
        try:
            pages = kvc.deserialize_host_pages(blob)
        except Exception:  # noqa: BLE001 — checked upstream; best-effort
            return 0
        n = self.fabric.put_pages(list(zip(digests, pages)))
        if n:
            telemetry.log_event(
                "fabric_salvage", level="info", path=path,
                request_id=rid, pages=n)
        return n

    def _on_handoff(self, h, client, obj, blob) -> None:
        """A prefill worker settled a prompt's prefill and exported the
        LIVE sequence (README "P/D disaggregation"): KV pages including
        the partial final page, plus the stream state the router already
        tracks. Route it to the least-loaded decode worker and resume
        there as an adoption — no re-prefill, zero recomputed tokens on
        the clean path; every failure mode degrades to the existing
        recompute-resume machinery (byte-identical under greedy)."""
        rid = obj["rid"]
        t0 = time.perf_counter()
        with self._lock:
            entry = self._entry_for(rid, h, client)
            if entry is None:
                return
            entry.generation += 1
            # DETACH under the lock (the _on_migrate claim pattern): a
            # racing worker-down failover must not double-resubmit.
            entry.worker = entry.client = None
            entry.attempts += 1
            self.pd_handoffs += 1
        n_gen = int(obj.get("n_generated", 0))
        entry.handoff_meta = {"ctx_len": int(obj.get("ctx_len", 0)),
                              "n_generated": n_gen}
        kv_desc = obj.get("kv_desc")
        if kv_desc is not None:
            # Zero-copy handoff: the export rode the arena, only this
            # descriptor crossed the socket. Register the slab so the
            # leak ledger tracks it until the decode worker adopted (or
            # every fallback released it).
            if self._arena_dir is not None:
                self._arena_dir.register(kv_desc)
            entry.handoff_desc = dict(kv_desc)
            blob = b""
        else:
            if blob:
                with self._lock:
                    self.rpc_blob_bytes["handoff"] += len(blob)
            blob = self._checked_blob(blob, "handoff", rid)
        entry.handoff_blob = blob or None
        if n_gen != len(entry.tokens):
            # Out of sync with the export (events are FIFO per
            # connection, so this should not happen): recompute-resume.
            telemetry.log_event(
                "handoff_token_mismatch", level="warning",
                request_id=entry.template.trace_id or str(rid),
                worker_generated=n_gen,
                router_streamed=len(entry.tokens))
            entry.handoff_blob = entry.handoff_meta = None
            self._release_handoff_desc(entry)
            with self._lock:
                self.pd_handoff_recomputes += 1
        pool = [w for w in self._phase_pool("decode") if w is not h]
        if not pool:
            pool = ([w for w in self._routable() if w is not h]
                    or self._routable())
        if not pool:
            # Point-to-point handoff lost its destination: park the
            # settled prefix in the fabric pool so whichever worker the
            # grace-window retry eventually finds pulls it from the
            # pool instead of re-prefilling the whole stream. A
            # descriptor export is materialized from the arena first
            # (the salvage outlives the slab's region).
            if not blob and entry.handoff_desc is not None \
                    and self.arena is not None:
                try:
                    blob = self.arena.read(entry.handoff_desc)
                except shm_arena.ArenaError:
                    blob = b""
                self._release_handoff_desc(entry)
                entry.handoff_blob = blob or None
            self._fabric_salvage(
                self._digests_for(entry.template)[0], blob, rid,
                "handoff")
            self._retry_or_fail(entry)     # already claimed above
            return
        if len(pool) == 1 and (entry.handoff_blob
                               or entry.handoff_desc is not None):
            # Forced choice: one decode candidate and a live export in
            # hand. The peek RPC would only rank a single option, and
            # the dispatch carries the full KV so warmth cannot change
            # the answer — skip the round trip on the handoff critical
            # path.
            dest, hit = pool[0], (0, 0, 0)
        else:
            dest, hit, _ = self._pick(pool, entry.template,
                                      phase="decode")
        telemetry.log_event(
            "request_handoff", level="info",
            request_id=entry.template.trace_id or str(rid),
            source=h.replica, dest=dest.replica,
            ctx_len=entry.handoff_meta["ctx_len"]
            if entry.handoff_meta else 0,
            streamed=len(entry.tokens))
        if self._dispatch(entry, dest, hit):
            self._pd_handoff_s_hist.observe(
                float(obj.get("export_s") or 0.0)
                + time.perf_counter() - t0)
            # Router-side handoff span: routing + dispatch until the
            # decode worker accepted the resume (the worker-side export
            # span precedes it on the assembled timeline).
            self._recorder.add(
                "handoff", entry.template.trace_id or str(rid),
                t0, time.perf_counter(), source=h.replica,
                dest=dest.replica, export_s=obj.get("export_s"),
                streamed=len(entry.tokens))
        else:
            self._retry_or_fail(entry, exclude=dest)

    def _on_migrate(self, h, client, obj, blob) -> None:
        """A draining worker exported one in-flight request: import its
        KV pages into a destination worker's host tier and resubmit with
        the router's token record — the swap-in-resume path."""
        rid = obj["rid"]
        t_mig = time.perf_counter()
        # The draining worker's in-flight spans (chunks, swaps, the
        # drain_export) ride the migrate event — fold them in so the
        # trace survives the process that recorded them.
        self._recorder.ingest(obj.get("trace") or "",
                              obj.get("spans") or ())
        with self._lock:
            entry = self._entry_for(rid, h, client)
            if entry is None:
                return
            entry.generation += 1
            # DETACH under the lock: the monitor's worker-down failover
            # can race this handler for the same entry (the draining
            # process exits while its last events are still in the
            # reader's buffer); whoever claims it first owns the one
            # resubmission, the loser's _entry_for sees a changed
            # worker and stands down.
            entry.worker = entry.client = None
            entry.attempts += 1
            self.migrations += 1
            self.retries_attempted += 1
            self.failovers += 1
        n_gen = int(obj.get("n_generated", 0))
        if n_gen != len(entry.tokens):
            telemetry.log_event(
                "migrate_token_mismatch", level="warning",
                request_id=entry.template.trace_id or str(rid),
                worker_generated=n_gen, router_streamed=len(entry.tokens))
        digests = [bytes.fromhex(d) for d in obj.get("digests") or ()]
        kv_desc = obj.get("kv_desc")
        if kv_desc is not None and self._arena_dir is not None:
            self._arena_dir.register(kv_desc)
        if blob:
            with self._lock:
                self.rpc_blob_bytes["migrate"] += len(blob)
        blob = self._checked_blob(blob, "migrate", rid)
        phase = self._entry_phase(entry)
        others = ([w for w in self._phase_pool(phase) if w is not h]
                  or [w for w in self._routable() if w is not h])
        if not others:
            # Migration lost its destination: park the exported pages
            # in the fabric pool (keyed by the digests the export
            # carried) so the grace-window retry's dispatch pulls them
            # back instead of recompute-prefilling the stream. A
            # descriptor export is materialized from the arena first.
            if not blob and kv_desc is not None and self.arena is not None:
                try:
                    blob = self.arena.read(kv_desc)
                except shm_arena.ArenaError:
                    blob = b""
            if kv_desc is not None and self._arena_dir is not None:
                self._arena_dir.release(kv_desc)
            self._fabric_salvage(digests, blob, rid, "migrate")
            # No exclude: this entry is already claimed (detached) by
            # the block above and no dispatch was attempted — the guard
            # in _retry_or_fail only applies after a failed dispatch.
            self._retry_or_fail(entry)
            return
        dest, hit, _ = self._pick(others, entry.template, phase=phase)
        if (kv_desc is not None and digests
                and self.server_cfg.fleet_migrate
                and dest.client is not None):
            # Zero-copy migrate: forward the descriptor; the destination
            # adopts straight from the arena. The router never touches
            # the payload bytes.
            try:
                r = dest.client.rpc(
                    "import-kv", kv_desc=kv_desc,
                    digests=[d.hex() for d in digests],
                    idem=f"i{rid}.{entry.generation}")
                with self._lock:
                    self.migrated_pages += int(r.get("adopted", 0))
                    self.migrated_bytes += int(kv_desc.get("len", 0))
                hit = self._peek_hit(dest, entry.template)
            except (WorkerGone, TimeoutError, RuntimeError) as e:
                telemetry.log_event("migrate_import_failed",
                                    level="warning", error=str(e))
            finally:
                if self._arena_dir is not None:
                    self._arena_dir.release(kv_desc)
        elif (blob and digests and self.server_cfg.fleet_migrate
                and dest.client is not None):
            try:
                with self._lock:
                    self.rpc_blob_bytes["import-kv"] += len(blob)
                r = dest.client.rpc(
                    "import-kv", blob=blob,
                    digests=[d.hex() for d in digests],
                    idem=f"i{rid}.{entry.generation}")
                with self._lock:
                    self.migrated_pages += int(r.get("adopted", 0))
                    self.migrated_bytes += len(blob)
                # Re-peek so the routing span reflects the just-imported
                # warmth the resubmission will actually find.
                hit = self._peek_hit(dest, entry.template)
            except (WorkerGone, TimeoutError, RuntimeError) as e:
                telemetry.log_event("migrate_import_failed",
                                    level="warning", error=str(e))
        elif kv_desc is not None and self._arena_dir is not None:
            # Import preconditions failed (migration disabled, no
            # digests): the descriptor has no consumer — release it.
            self._arena_dir.release(kv_desc)
        telemetry.log_event(
            "request_migrated", level="warning",
            request_id=entry.template.trace_id or str(rid),
            source=h.replica, dest=dest.replica,
            pages=len(digests), streamed=len(entry.tokens))
        if self._dispatch(entry, dest, hit):
            self._recorder.add(
                "migrate", entry.template.trace_id or str(rid),
                t_mig, time.perf_counter(), source=h.replica,
                dest=dest.replica, pages=len(digests),
                streamed=len(entry.tokens))
        else:
            self._retry_or_fail(entry, exclude=dest)

    def _on_drained(self, h, client, obj) -> None:
        """Graceful exit notice: the final stats/metrics dump IS the
        restart carry (nothing is lost on a drain, unlike kill -9 where
        the carry is the last periodic scrape)."""
        if obj.get("metrics") and h.folded_incarnation != h.incarnation:
            h.last_metrics = obj["metrics"]
        if obj.get("stats"):
            h.last_stats = obj["stats"]
        if h.state == UP:
            h.state = DRAINING
        telemetry.log_event(
            "worker_drained", level="info", replica=h.replica,
            migrated_requests=obj.get("migrated_requests", 0))
        # The process exits right after this event; the monitor's poll()
        # flips it to RESTARTING and respawns. Any request the drain did
        # NOT migrate (e.g. migration raced the export budget) fails
        # over from the router's token record like a kill.

    def _failover_worker(self, h: WorkerHandle) -> None:
        """Resubmit every tracked request of a dead worker from the
        router's own token record (recompute-resume on a survivor;
        token-identical under greedy). Requests with no survivor fail
        cleanly with "unavailable"."""
        with self._lock:
            victims = [e for e in self._tracked.values()
                       if e.worker is h]
            for e in victims:
                e.generation += 1
                # Detach (see _on_migrate): claims the one resubmission
                # against a racing migrate-event handler.
                e.worker = e.client = None
                e.attempts += 1
                e.failed_workers.add(h.replica)
                self.retries_attempted += 1
                self.failovers += 1
        for entry in victims:
            if self._quarantine_if_poison(entry):
                continue
            phase = self._entry_phase(entry)
            others = ([w for w in self._phase_pool(phase) if w is not h]
                      or [w for w in self._routable() if w is not h])
            if not others:
                rid = entry.template.request_id
                with self._lock:
                    self._tracked.pop(rid, None)
                self._finish_trace(entry, "unavailable")
                ghost = entry.seq_local
                ghost.done, ghost.finish_reason = True, "unavailable"
                ghost.finish_time = time.perf_counter()
                entry.on_finish(ghost)
                continue
            dest, hit, _ = self._pick(others, entry.template, phase=phase)
            telemetry.log_event(
                "request_failover", level="warning",
                request_id=(entry.template.trace_id
                            or str(entry.template.request_id)),
                resubmitted=True, attempts=entry.attempts,
                streamed=len(entry.tokens))
            if not self._dispatch(entry, dest, hit):
                self._retry_or_fail(entry, exclude=dest)

    # ------------------------------------------------------------ chaos

    def apply_chaos(self, body: dict) -> dict:
        """POST /debug/chaos for the subprocess fleet: engine-level
        knobs forward to workers over the chaos RPC; the process-level
        verbs the in-process fleet can only simulate are REAL here —
        ``{"replica": i, "kill": "kill9"}`` SIGKILLs the worker process
        (supervisor restarts it; in-flight requests fail over from the
        router's token record) and ``{"kill": "sigterm"}`` triggers the
        graceful drain-and-migrate path. ``{"rpc": {...}}`` retunes the
        router<->worker frame-level fault injection (transport chaos)
        at runtime: the kwargs mirror the --chaos-rpc-* knobs, apply to
        every subsequently sent frame on both sides, and reset the
        per-replica deterministic schedules."""
        rpc = body.get("rpc")
        if rpc is not None:
            for k, v in dict(rpc).items():
                if k in self._chaos_rpc_kw and v is not None:
                    self._chaos_rpc_kw[k] = (tuple(v) if k == "verbs"
                                             else v)
            with self._lock:
                # Drop cached policies so new rates rebuild the
                # deterministic schedule from frame 0 (and a re-armed
                # wedge can fire again).
                self._chaos_policies.clear()
            for h in self.workers:
                if h.client is not None and h.client.alive:
                    h.client.chaos = self._make_chaos(h.replica)
                    try:
                        h.client.rpc("chaos", rpc=dict(rpc))
                    except (WorkerGone, TimeoutError, RuntimeError):
                        pass
            return {"rpc": {k: (list(v) if isinstance(v, tuple) else v)
                            for k, v in self._chaos_rpc_kw.items()}}
        kill = body.get("kill")
        if kill is not None:
            if kill not in ("kill9", "sigkill", "sigterm", "drain"):
                raise ValueError(
                    f"unknown kill chaos {kill!r}: one of "
                    "('kill9', 'sigterm')")
            idx = int(body["replica"])
            h = self.workers[idx]
            if h.proc is None or h.proc.poll() is not None:
                raise ValueError(f"worker {idx} has no live process")
            sig = (signal.SIGKILL if kill in ("kill9", "sigkill")
                   else signal.SIGTERM)
            os.kill(h.pid, sig)
            return {"replica": idx, "killed": kill, "pid": h.pid}
        replica = body.get("replica")
        targets = (self.workers if replica is None
                   else [self.workers[int(replica)]])
        fields = {k: body[k] for k in ("step_failure_rate",
                                       "step_wedge_s", "page_pressure")
                  if body.get(k) is not None}
        out = []
        for h in self.workers:
            state = {"step_failure_rate": None, "step_wedge_s": None,
                     "page_pressure": None}
            if h.client is not None and h.client.alive:
                try:
                    state = h.client.rpc(
                        "chaos", **(fields if h in targets else {}))
                    state = {k: v for k, v in state.items()
                             if k not in ("id", "ok")}
                except (WorkerGone, TimeoutError, RuntimeError):
                    pass
            out.append(state)
        return {"replicas": out}

    def drain_worker(self, replica: int,
                     migrate: Optional[bool] = None) -> None:
        """Programmatic graceful drain (benchmarks): same path as
        SIGTERM, but selectable migration for the comparison arm."""
        h = self.workers[replica]
        if h.client is None:
            raise ValueError(f"worker {replica} not running")
        kw = {} if migrate is None else {"migrate": migrate}
        h.client.rpc("drain", **kw)

    # --------------------------------------------------- elastic fleet

    def _add_worker(self, role: str) -> WorkerHandle:
        """Append a new replica slot (handle + role + per-replica
        routing/gauge state) without booting it. Index-keyed arrays
        grow BEFORE the workers append so no reader ever sees a worker
        whose replica index is out of range."""
        with self._lock:
            h = WorkerHandle(len(self.workers))
            self.roles.append(role)
            self._route_stats.append({"hits": 0, "cold": 0,
                                      "hit_pages": 0,
                                      "host_hit_pages": 0,
                                      "fabric_hit_pages": 0})
            self.workers.append(h)
        self._register_worker_gauges(h)
        return h

    def _autoscale_tick(self, now: float) -> None:
        """One control-loop step (monitor thread, ~1/s): scale up on a
        sustained pooled p95 SLO breach, scale down on a sustained lull.
        Hysteresis = separate breach/idle windows; flap damping = one
        cooldown shared by both directions; and NO action while any
        worker is mid-transition (booting/restarting/draining) — that
        is what makes a chaos kill and a scale-up never double-spawn."""
        scfg = self.server_cfg
        if self._stopping or self._rollout_lock.locked():
            return
        if any(h.state in (BOOTING, RESTARTING, DRAINING)
               for h in self.workers):
            self._breach_since = 0.0
            return
        live = self._live_workers()
        n = len(live)
        max_n = scfg.autoscale_max_replicas or (self.dp + 2)
        min_n = max(1, scfg.autoscale_min_replicas)
        cooled = (now - self._last_scale_t) >= scfg.autoscale_cooldown_s
        breached = False
        ecfg = self.engine_cfg
        if ecfg.slo_ttft_ms:
            # Router-observed TTFT over a rolling time horizon: the
            # sensor sees lane park time (engine-side rings do not),
            # and samples age out, so a finished burst releases the
            # breach and lets the idle path scale back down.
            horizon = max(5.0 * scfg.autoscale_breach_window_s,
                          2.0 * scfg.autoscale_cooldown_s)
            cut = time.perf_counter() - horizon  # samples' own clock
            with self._lock:
                while self._ttft_obs and self._ttft_obs[0][0] < cut:
                    self._ttft_obs.popleft()
                xs = sorted(v for _, v in self._ttft_obs)
            if xs:
                p95 = xs[min(len(xs) - 1, int(0.95 * len(xs)))]
                breached = p95 > ecfg.slo_ttft_ms / 1000.0
        if not breached and ecfg.slo_tpot_ms and self._tracked:
            # TPOT breach from the workers' pooled rings, gated on live
            # in-flight work (a count-based ring cannot age out on its
            # own — without traffic it must not pin the fleet wide).
            p95 = self._pooled_slo_quantile("tpot", 0.95)
            if p95 == p95 and p95 > ecfg.slo_tpot_ms / 1000.0:
                breached = True
        if breached:
            self._idle_since = 0.0
            if not self._breach_since:
                self._breach_since = now
            elif (now - self._breach_since >= scfg.autoscale_breach_window_s
                    and cooled and n < max_n):
                self._scale_up("slo_breach")
            return
        self._breach_since = 0.0
        occs = [float((h.last_health or {}).get("ladder_occupancy") or 0.0)
                for h in live if h.state == UP]
        pooled_occ = (sum(occs) / len(occs)) if occs else 1.0
        backlog = any(self._deferred.values())
        if backlog or pooled_occ >= scfg.autoscale_low_watermark:
            self._idle_since = 0.0
            return
        if not self._idle_since:
            self._idle_since = now
        elif (now - self._idle_since >= scfg.autoscale_idle_window_s
                and cooled and n > min_n and n > 1):
            self._scale_down("idle")

    def _scale_up(self, reason: str) -> None:
        t0 = time.perf_counter()
        role = self.server_cfg.autoscale_role or (
            "decode" if self.pd_enabled else "mixed")
        h = self._add_worker(role)
        telemetry.log_event("fleet_scale_up", replica=h.replica,
                            role=role, reason=reason)
        try:
            self._spawn(h)
        except (WorkerGone, TimeoutError, RuntimeError, OSError) as e:
            # Boot failed: hand the slot to the ordinary supervisor
            # (backoff respawn → quarantine) rather than special-casing.
            h.consecutive_failures += 1
            telemetry.log_event("worker_respawn_failed", level="error",
                                replica=h.replica, error=str(e))
            self._schedule_restart(h)
        with self._lock:
            self.scale_ups += 1
        self._last_scale_t = time.monotonic()
        self._breach_since = 0.0
        tid = f"scale-up-{self.scale_ups}"
        self._recorder.add("scale_up", tid, t0, time.perf_counter(),
                           parent="", replica=h.replica, role=role,
                           reason=reason)
        self._recorder.seal(tid)

    def _scale_down(self, reason: str) -> None:
        t0 = time.perf_counter()
        h = self._retire_candidate()
        if h is None:
            return
        h.retiring = True
        try:
            # PR 9 lossless scale-down: drain exports live KV as
            # migrate events, the router re-lands them on survivors,
            # and the post-drain exit lands in RETIRED (not a respawn)
            # because retiring is set.
            self.drain_worker(h.replica)
        except (WorkerGone, TimeoutError, RuntimeError, ValueError) as e:
            h.retiring = False
            telemetry.log_event("fleet_scale_down_failed", level="warning",
                                replica=h.replica, error=str(e))
            return
        with self._lock:
            self.scale_downs += 1
        self._last_scale_t = time.monotonic()
        self._idle_since = 0.0
        telemetry.log_event("fleet_scale_down", replica=h.replica,
                            reason=reason)
        tid = f"scale-down-{self.scale_downs}"
        self._recorder.add("scale_down", tid, t0, time.perf_counter(),
                           parent="", replica=h.replica, reason=reason)
        self._recorder.seal(tid)

    def _retire_candidate(self) -> Optional[WorkerHandle]:
        """Coldest UP replica that can leave without killing a P/D
        phase: fewest in-flight requests, then lowest occupancy, ties
        retire the newest index (scale-ups go first)."""
        cands = [h for h in self.workers
                 if h.state == UP and not h.retiring]
        if len(cands) <= 1:
            return None
        if self.pd_enabled:
            def _ok_without(w):
                rest = [self.roles[h.replica] for h in cands if h is not w]
                return (any(r in ("prefill", "mixed") for r in rest)
                        and any(r in ("decode", "mixed") for r in rest))
            cands = [h for h in cands if _ok_without(h)]
            if not cands:
                return None
        return min(cands, key=lambda h: (
            self._fleet_load(h),
            float((h.last_health or {}).get("ladder_occupancy") or 0.0),
            -h.replica))

    def rollout(self) -> dict:
        """Zero-downtime rolling upgrade (POST /debug/rollout): replace
        each worker one at a time under live traffic — spawn the
        successor FIRST, then drain-and-migrate the predecessor into
        the fleet, then let its post-drain exit retire it. In-flight
        sequences ride the migrate path (or recompute-resume), so no
        request fails or restarts from zero."""
        self._ensure_started()
        if self._stopping:
            raise ValueError("fleet is stopping")
        if not self._rollout_lock.acquire(blocking=False):
            raise ValueError("a rollout is already in progress")
        t0 = time.perf_counter()
        replaced, failed = [], []
        try:
            targets = [h for h in self.workers
                       if h.state == UP and not h.retiring]
            telemetry.log_event("fleet_rollout_start",
                                targets=[h.replica for h in targets])
            for old in targets:
                if old.state != UP:
                    continue    # died mid-rollout; supervisor owns it
                succ = self._add_worker(self.roles[old.replica])
                try:
                    self._spawn(succ)
                except (WorkerGone, TimeoutError, RuntimeError,
                        OSError) as e:
                    # Never retire a predecessor without a live
                    # successor: abort the rollout, keep serving.
                    succ.state = DEAD
                    failed.append({"replica": old.replica,
                                   "successor": succ.replica,
                                   "error": str(e)})
                    telemetry.log_event("fleet_rollout_spawn_failed",
                                        level="error",
                                        replica=succ.replica,
                                        error=str(e))
                    break
                old.retiring = True
                try:
                    self.drain_worker(old.replica)
                except (WorkerGone, TimeoutError, RuntimeError,
                        ValueError) as e:
                    # The predecessor died or restarted out from under
                    # the rollout (e.g. chaos): the supervisor owns it
                    # now and its in-flight work already failed over.
                    # The successor stays (extra capacity is harmless);
                    # move on without stalling the pass.
                    old.retiring = False
                    telemetry.log_event("fleet_rollout_drain_failed",
                                        level="warning",
                                        replica=old.replica,
                                        error=str(e))
                    replaced.append({"old": old.replica,
                                     "new": succ.replica,
                                     "old_state": old.state})
                    continue
                deadline = (time.monotonic()
                            + self.server_cfg.drain_timeout_s + 30.0)
                while (time.monotonic() < deadline
                       and old.state not in (RETIRED, DEAD)
                       and old.retiring):
                    time.sleep(0.05)
                replaced.append({"old": old.replica,
                                 "new": succ.replica,
                                 "old_state": old.state})
        finally:
            with self._lock:
                self.rollouts += 1
            tid = f"rollout-{self.rollouts}"
            self._recorder.add("rollout", tid, t0, time.perf_counter(),
                               parent="", replaced=len(replaced),
                               failed=len(failed))
            self._recorder.seal(tid)
            self._rollout_lock.release()
        wall = time.perf_counter() - t0
        telemetry.log_event("fleet_rollout_done",
                            replaced=len(replaced), failed=len(failed),
                            wall_s=round(wall, 3))
        return {"replaced": replaced, "failed": failed,
                "live": len(self._live_workers()),
                "wall_s": round(wall, 3)}

    # ---------------------------------------------------- observability

    def embed_many(self, batch):
        import numpy as np

        routable = self._routable()
        if not routable:
            with self._lock:
                self.requests_unavailable += 1
            raise FleetUnavailable("no routable worker",
                                   self.server_cfg.retry_after_s)
        h, _, _ = self._pick(routable)
        r = h.client.rpc("embed", timeout=600.0, batch=batch)
        return np.asarray(r["embeddings"])

    def supervision_counters(self) -> dict:
        stats = [h.last_stats for h in self.workers if h.last_stats]
        with self._lock:
            return {
                "retries_attempted": self.retries_attempted,
                "retries_succeeded": self.retries_succeeded,
                "failovers": self.failovers,
                "requests_shed": self.requests_shed,
                "requests_unavailable": self.requests_unavailable,
                "route_prefix_hits": self.route_prefix_hits,
                "route_cold": self.route_cold,
                # Fleet KV fabric (README "KV fabric"): same keys as
                # the in-process backend's view.
                "route_fabric_hits": self.route_fabric_hits,
                "fabric_puts": self.fabric.puts,
                "fabric_hits": self.fabric.hits,
                "preemptions": sum(d.get("preemptions", 0)
                                   for d in stats),
                "recompute_resumes": sum(d.get("recompute_resumes", 0)
                                         for d in stats),
                "states": [h.state for h in self.workers],
                # Process-fleet extras (README "Process fleet").
                "fleet": "subprocess",
                "worker_restarts": sum(h.restarts for h in self.workers),
                # P/D disaggregation (README "P/D disaggregation").
                "roles": list(self.roles),
                "pd_handoffs": self.pd_handoffs,
                "pd_handoff_recomputes": self._pd_recomputes_total(),
                "pd_adoptions": sum(d.get("pd_adoptions", 0)
                                    for d in stats),
                # Router-side handoff wall as a diffable phase snapshot
                # (the engine "phases" shape): a handoff stall shows up
                # here without log-diving.
                "phases": {"pd_handoff_s":
                           self._pd_handoff_s_hist.phase_snapshot()},
                "migrations": self.migrations,
                "migrated_pages": self.migrated_pages,
                "migrated_bytes": self.migrated_bytes,
                "resume_resubmits": self.resume_resubmits,
                "resume_recomputed_tokens": self.resume_recomputed_tokens,
                "resume_reused_tokens": self.resume_reused_tokens,
                "swap_in_resumes": sum(d.get("swap_in_resumes", 0)
                                       for d in stats),
                # Elastic fleet (README "Elastic fleet").
                "scale_ups": self.scale_ups,
                "scale_downs": self.scale_downs,
                "rollouts": self.rollouts,
                "class_preemptions": dict(self.class_preemptions),
                "class_shed": dict(self.class_shed),
                "class_deferred": {c: len(q)
                                   for c, q in self._deferred.items()},
                # Byzantine transport (README "Failure model").
                "worker_reconnects": self.reconnects,
                "rpc_timeouts": self.rpc_timeouts,
                "frame_errors": self.frame_errors,
                "kv_integrity_rejections": self._kv_rejections_total(),
                "poison_requests": self.poison_requests,
            }

    def health_snapshot(self) -> dict:
        replicas = []
        for h in self.workers:
            hz = dict(h.last_health) if h.state == UP else {}
            if h.state == UP and h.client is not None:
                try:
                    hz = h.client.rpc("healthz")
                    hz.pop("id", None), hz.pop("ok", None)
                    h.last_health = hz
                except (WorkerGone, TimeoutError, RuntimeError):
                    pass
            d = {
                "state": ("healthy" if h.state == UP else h.state),
                "worker_state": h.state,
                "role": self.roles[h.replica],
                "pid": h.pid,
                "uptime_s": (round(time.time() - h.started_unix, 3)
                             if h.started_unix and h.state == UP
                             else 0.0),
                "restarts": h.restarts,
                "incarnation": h.incarnation,
                "routing": dict(self._route_stats[h.replica]),
            }
            for k in ("device", "pool_pressure", "under_pressure",
                      "preemptions", "load", "draining", "host_cache",
                      "swap_in_resumes", "prefill_backlog",
                      "ladder_occupancy", "pd_handoffs", "pd_adoptions",
                      "pd_adopt_fallbacks", "slo",
                      "kv_integrity_rejections",
                      "fabric_published_pages"):
                if k in hz:
                    d[k] = hz[k]
            replicas.append(d)
        # RETIRED replicas left the fleet ON PURPOSE (scale-down or a
        # rollout retirement) — they must not drag status to degraded
        # forever. QUARANTINED stays in the denominator: a crash-looped
        # replica is a visible degradation, not an intentional absence.
        live = [h for h in self.workers if h.state != RETIRED]
        routable = sum(1 for h in live if h.routable)
        if routable == 0:
            status = "unavailable"
        elif routable == len(live):
            status = "ok"
        else:
            status = "degraded"
        return {
            "status": status,
            "fleet": "subprocess",
            "routing": self.server_cfg.routing,
            "replicas": replicas,
            # Fleet-aggregated rolling SLO view: EXACT quantiles pooled
            # across worker windows (the autoscaler's input signal).
            "slo": self._fleet_slo(),
            # Fleet KV fabric pool occupancy + churn (README "KV
            # fabric"); same shape under both fleet backends.
            "fabric": self.fabric.snapshot(),
            "supervision": self.supervision_counters(),
        }

    def stats_snapshot(self) -> dict:
        per = []
        for h in self.workers:
            d = None
            if h.state == UP and h.client is not None:
                try:
                    d = h.client.rpc("stats", timeout=30.0)["stats"]
                    h.last_stats = d
                except (WorkerGone, TimeoutError, RuntimeError):
                    d = None
            if d is None:
                d = dict(h.last_stats) if h.last_stats else None
            if d is not None:
                d["health"] = {"state": h.state, "pid": h.pid,
                               "restarts": h.restarts}
                per.append(d)
        if not per:
            return {"supervision": self.supervision_counters(),
                    "dp": self.dp}
        return aggregate_replica_stats(per,
                                       self.supervision_counters())

    def steps_snapshot(self, since: Optional[float] = None,
                       until: Optional[float] = None,
                       records: bool = False) -> dict:
        """Step-ledger roofline attribution (GET /debug/steps): live
        per-replica reports (cache fallback for downed workers, same
        stance as stats_snapshot) + the fleet-merged report, over the
        trailing 60 s or the ``since`` / ``until`` interval."""
        reports: Dict[str, dict] = {}
        default = since is None and until is None and not records
        for h in self.workers:
            d = None
            if h.state == UP and h.client is not None:
                try:
                    d = h.client.rpc("steps", timeout=30.0, since=since,
                                     until=until, records=records)["steps"]
                    if default:
                        h.last_steps = d
                except (WorkerGone, TimeoutError, RuntimeError):
                    d = None
            if d is None and h.last_steps:
                d = dict(h.last_steps)
                d["stale"] = True
            if d is not None:
                reports[str(h.replica)] = d
        return {"replicas": reports,
                "fleet": telemetry.merge_steps_reports(
                    list(reports.values()))}

    def blackbox_index(self) -> dict:
        """Flight-recorder capture index (GET /debug/blackbox): scans
        the operator's blackbox_dir on the router's FS — captures from
        dead incarnations are listed exactly like live ones (the dir
        survives kill -9; that is the point)."""
        return telemetry.blackbox_index(self.server_cfg.blackbox_dir)

    def prometheus_text(self) -> str:
        groups = []
        for h in self.workers:
            dump = None
            if h.state == UP and h.client is not None:
                try:
                    dump = h.client.rpc("metrics",
                                        timeout=30.0)["samples"]
                    h.last_metrics = dump
                except (WorkerGone, TimeoutError, RuntimeError):
                    dump = None
            if dump is None:
                # Dead/booting worker: keep its series rendering so
                # nothing vanishes mid-restart — from the last live
                # dump if the death hasn't been folded into the carry
                # yet, else from the carry ALONE (rendering both would
                # double-count the folded totals during the gap).
                dump = (h.last_metrics
                        if h.folded_incarnation != h.incarnation else [])
            merged = telemetry.apply_carry(h.carry, dump)
            groups.append(({"replica": str(h.replica)},
                           telemetry.registry_from_dump(merged)))
        groups.append(({}, self._fleet_registry))
        return telemetry.render_prometheus(groups)

    def recent_snapshot(self, n: int) -> List[dict]:
        items: List[dict] = []
        for h in self.workers:
            if h.state != UP or h.client is None:
                continue
            try:
                items.extend(h.client.rpc("recent", timeout=10.0,
                                          n=n)["recent"])
            except (WorkerGone, TimeoutError, RuntimeError):
                pass
        items.sort(key=lambda t: t.get("finished_unix", 0.0))
        return items[-n:]

    # -------------------------------------------- tracing + profiling

    def _pid_names(self) -> dict:
        return {0: "router",
                **{h.replica + 1:
                   f"replica {h.replica} ({self.roles[h.replica]})"
                   for h in self.workers}}

    def trace_snapshot(self, trace_id: str) -> Optional[dict]:
        """One request's assembled cross-process span tree (GET
        /debug/trace?id=). The router's recorder holds the event-frame
        assembly; a miss falls back to the workers' trace pull verb
        (e.g. the router restarted mid-request)."""
        spans = self._recorder.get_trace(trace_id)
        if spans is None:
            pulled: List[dict] = []
            for h in self.workers:
                if h.state != UP or h.client is None:
                    continue
                try:
                    pulled.extend(h.client.rpc(
                        "trace", timeout=10.0, trace=trace_id)["spans"])
                except (WorkerGone, TimeoutError, RuntimeError):
                    pass
            spans = pulled or None
        if not spans:
            return None
        return telemetry.assemble_trace(trace_id, spans)

    def trace_chrome(self, n: int = 128) -> dict:
        """The recent-request ring as Chrome trace-event JSON (GET
        /debug/trace?format=chrome): one pid per replica, router as
        pid 0, loadable in Perfetto."""
        maintenance: List[dict] = []
        for h in self.workers:
            if h.state != UP or h.client is None:
                continue
            try:
                maintenance.extend(h.client.rpc(
                    "trace", timeout=10.0, n=0)["maintenance"])
            except (WorkerGone, TimeoutError, RuntimeError):
                pass
        return telemetry.spans_to_chrome(
            self._recorder.recent_traces(n), self._pid_names(),
            maintenance=maintenance,
            other_data={"fleet": "subprocess",
                        "roles": list(self.roles),
                        "spans_dropped": self._recorder.spans_dropped})

    def capture_profile(self, replica: int, seconds: float) -> dict:
        """POST /debug/profile {"seconds": N, "replica": i}: forward a
        jax.profiler capture to one live worker over the profile RPC;
        the worker writes the trace dir (under the operator-configured
        profile_dir) and returns its path."""
        h = self.workers[int(replica)]
        if h.state != UP or h.client is None:
            raise ValueError(f"worker {replica} not serving "
                             f"(state={h.state})")
        r = h.client.rpc("profile", timeout=float(seconds) + 120.0,
                         seconds=float(seconds))
        return {k: v for k, v in r.items() if k not in ("id", "ok")}
