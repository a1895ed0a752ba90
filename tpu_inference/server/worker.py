"""Engine-worker process: one engine + scheduler per OS process.

One half of the subprocess fleet (README "Process fleet"; the other half
is ``server/fleet.py``'s router). The worker owns exactly one dp replica
— its own devices, KV pool, prefix cache/host tier, and continuous-
batching scheduler thread — and serves a small length-prefixed JSON RPC
over a local unix socket:

    frame   = [u32 magic][u32 json_len][u32 blob_len][u32 crc32c]
              [json][blob]                    (see server/transport.py)
    request = {"id": n, "verb": ..., ...}        -> {"id": n, "ok": ...}
    event   = {"ev": "token" | "finish" | "migrate" | "drained", ...}

Verbs: ``hello`` (worker/model facts), ``submit`` / ``cancel`` (request
lifecycle; tokens and the terminal record stream back as events on the
same connection, unbuffered), ``peek`` (side-effect-free tiered prefix
probe + load/pressure — the router's prefix-affinity scoring input),
``stats`` / ``metrics`` / ``healthz`` / ``recent`` (observability),
``chaos`` (engine-level fault injection), ``embed``, ``drain``
(graceful wind-down with KV export), ``import-kv`` (adopt a sibling
replica's drain export into the host tier), ``shutdown``, and ``debug``
(pool-invariant snapshot for the leak tests).

Graceful drain (SIGTERM or the drain RPC): the worker stops admitting,
settles in-flight dispatches, and — with migration enabled — exports
each live sequence's KV pages in the host serialization layout
(engine.export_sequence_kv) as one ``migrate`` event per request, so
the router can import them into a destination worker's host tier and
resubmission becomes a swap-in-resume instead of a from-scratch
re-prefill. ``kill -9`` skips all of this by definition; the router's
resubmission failover (fleet-side token record, recompute-resume)
covers it.

The module top imports only the stdlib so the router can import the
frame codec without paying for jax; everything heavy loads inside
``EngineWorker.boot``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import queue
import signal
import socket
import struct
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Tuple

# ---------------------------------------------------------------------------
# Frame codec — ONE implementation, shared with server/fleet.py. Lives
# in server/transport.py (checksummed v2 format + the chaos shim);
# re-exported here because both the router and older tests import the
# codec from this module.
# ---------------------------------------------------------------------------

from tpu_inference.integrity import KVIntegrityError  # noqa: E402
from tpu_inference.server.transport import (  # noqa: F401,E402
    MAX_FRAME,
    ChaosPolicy,
    ChaosTransport,
    FrameError,
    recv_frame,
    send_frame,
)


class _Conn:
    """One router connection: a reader thread dispatching verbs and a
    writer thread draining an outbound queue, so engine-thread callbacks
    (token/finish events) never block on socket I/O."""

    def __init__(self, worker: "EngineWorker", sock: socket.socket):
        self.worker = worker
        self.sock = sock
        self.rfile = sock.makefile("rb")
        self.outq: "queue.Queue" = queue.Queue()
        self.alive = True
        self._writer = threading.Thread(target=self._write_loop,
                                        name="worker-conn-writer",
                                        daemon=True)
        self._reader = threading.Thread(target=self._read_loop,
                                        name="worker-conn-reader",
                                        daemon=True)
        self._writer.start()
        self._reader.start()

    def send(self, obj: Dict[str, Any], blob: bytes = b"",
             verb: str = "") -> None:
        """Queue one outbound frame. ``verb`` tags it for the chaos
        shim's per-verb filter (reply frames carry their request verb,
        events their event name)."""
        if self.alive:
            self.outq.put((obj, blob, verb))

    def flush(self, timeout: float = 5.0) -> None:
        """Wait for every ALREADY-queued frame to finish its sendall
        (drain exit path: the migrate/drained events must leave before
        the process does). A sentinel rides the queue — the writer sets
        it only after the preceding frames' writes completed, so this
        cannot race a frame mid-write like an emptiness poll would."""
        evt = threading.Event()
        self.outq.put(("__flush__", evt))
        evt.wait(timeout)

    def close(self) -> None:
        self.alive = False
        self.outq.put(None)
        try:
            self.sock.close()
        except OSError:
            pass

    def _write_loop(self) -> None:
        while True:
            item = self.outq.get()
            if item is None:
                return
            if item[0] == "__flush__":
                item[1].set()
                continue
            try:
                # Worker->router frames are the chaos shim's "recv"
                # direction (named from the router's point of view).
                send_frame(self.sock, item[0], item[1],
                           chaos=self.worker.chaos_rpc,
                           verb=item[2], direction="recv")
            except (OSError, ConnectionError):
                self.alive = False
                return

    def _read_loop(self) -> None:
        try:
            while True:
                obj, blob = recv_frame(self.rfile)
                self.worker.handle(self, obj, blob)
        except (ConnectionError, OSError, json.JSONDecodeError):
            pass
        finally:
            self.alive = False
            self.worker.forget_conn(self)


class EngineWorker:
    """One replica's engine + scheduler behind the RPC socket."""

    def __init__(self, cfg, replica: int, socket_path: str,
                 warmup: bool = True):
        self.cfg = cfg
        self.replica = replica
        self.socket_path = socket_path
        # Phase role (README "P/D disaggregation"): the router ships a
        # per-worker role in the envelope (main() folds it into
        # cfg.engine.role before construction). "prefill" workers hand
        # each settled prefill off instead of decoding it; "decode"
        # workers adopt handoffs; "mixed" is the pre-P/D behavior.
        self.role = cfg.engine.role
        self.do_warmup = warmup
        self.warmup_s = 0.0
        self.started_unix = time.time()
        # Orphan guard: a worker whose router died (kill -9 of the
        # ROUTER, bench shortcut teardown) must not linger as an idle
        # orphan — reparenting to init is the tell.
        self._parent_pid = os.getppid()
        self.engine = None
        self.sched = None
        self.draining = False
        self._drained_evt = threading.Event()
        self._shutdown = threading.Event()
        self._conns: list = []
        self._conns_lock = threading.Lock()
        # rid -> the connection that submitted it (migrate events go
        # back to the submitting router connection).
        self._req_conn: Dict[int, _Conn] = {}
        # Byzantine-transport defenses (README "Failure model"):
        # worker-side chaos shim for worker->router frames, the
        # idempotency-replay cache (token -> recorded reply, so a verb
        # retried over a new connection cannot double-apply). Corrupt-KV
        # rejections count on engine.kv_integrity_rejections (healthz).
        self.chaos_rpc = self._build_chaos_rpc()
        self._idem: "OrderedDict[str, dict]" = OrderedDict()
        self._idem_lock = threading.Lock()
        # Zero-copy KV plane (README "KV data plane"): the router's
        # boot envelope may carry a shared-memory region spec; when
        # attached, KV exports (fabric publish, P/D handoff, drain
        # migrate) write payloads into the arena and ship descriptors
        # instead of blobs. None = relay plane (blobs over the socket).
        self._arena = None
        # Router pool watermark (fabric back-pressure): free pages the
        # fabric pool advertised at boot, refreshed on every stats
        # tick. None = no watermark yet, publish freely.
        self._fabric_free = None
        self.fabric_publish_skipped = 0

    def attach_arena(self, spec) -> None:
        """Map the router's shm segment from a boot-envelope region
        spec. Failure is not fatal — the worker simply stays on the
        relay plane (every payload rides the socket)."""
        if not spec:
            return
        from tpu_inference import telemetry
        from tpu_inference.server import shm_arena
        try:
            self._arena = shm_arena.WorkerArena(spec)
        except Exception as e:  # noqa: BLE001 — relay fallback, not fatal
            self._arena = None
            telemetry.log_event(
                "shm_arena_attach_failed", level="warning",
                replica=self.replica, error=str(e))

    def _arena_blob(self, desc, path: str):
        """Materialize a descriptor's payload from the arena, typed by
        failure: returns (blob, rejected) where rejected=True means the
        slab FAILED ITS INTEGRITY CHECK (counted, the router must drop
        the descriptor) and blob=b'' with rejected=False means the slab
        is stale/unreachable (epoch bumped after a reclaim, arena not
        attached) — the caller falls back to recompute/relay."""
        from tpu_inference import telemetry
        from tpu_inference.server import shm_arena
        if self._arena is None or desc is None:
            return b"", False
        try:
            return self._arena.read(desc), False
        except shm_arena.ArenaCorrupt as e:
            self.engine.kv_integrity_rejections += 1
            telemetry.log_event(
                "arena_slab_rejected", level="error", path=path,
                replica=self.replica, reason=e.reason, detail=e.detail)
            return b"", True
        except shm_arena.ArenaError:
            return b"", False

    def _build_chaos_rpc(self, over: Dict[str, Any] = None):
        """Worker-side chaos transport from config knobs (+ runtime
        overrides via the chaos verb). The wedge fault is router-side
        only — its detection signal (per-verb RPC deadlines) lives in
        the router, so the worker never arms ``wedge_after``."""
        s = self.cfg.server
        kw = {"seed": getattr(s, "chaos_rpc_seed", 0),
              "corrupt_rate": getattr(s, "chaos_rpc_corrupt_rate", 0.0),
              "drop_rate": getattr(s, "chaos_rpc_drop_rate", 0.0),
              "delay_rate": getattr(s, "chaos_rpc_delay_rate", 0.0),
              "delay_s": getattr(s, "chaos_rpc_delay_s", 0.02),
              "truncate_rate": getattr(s, "chaos_rpc_truncate_rate", 0.0),
              "verbs": getattr(s, "chaos_rpc_verbs", ()),
              "direction": getattr(s, "chaos_rpc_direction", "both")}
        for k, v in (over or {}).items():
            if k in kw and v is not None:
                kw[k] = tuple(v) if k == "verbs" else v
        if kw["direction"] not in ("recv", "both"):
            return None
        # Decorrelate from the router side's schedule (seed + replica).
        kw["seed"] = int(kw["seed"]) + 7919 * (self.replica + 1)
        pol = ChaosPolicy(**kw)
        return ChaosTransport(pol) if pol.active else None

    # ------------------------------------------------------------- boot

    def boot(self) -> None:
        from tpu_inference.engine.engine import InferenceEngine
        from tpu_inference.engine.scheduler import EngineScheduler

        from tpu_inference import telemetry as _tm

        _tm.install_compile_monitor()
        cfg = self.cfg
        pcfg = cfg.parallel
        mesh = None
        if pcfg.tp * pcfg.sp > 1:
            from tpu_inference.config import ParallelConfig
            from tpu_inference.parallel.mesh import build_mesh
            mesh = build_mesh(ParallelConfig(tp=pcfg.tp, sp=pcfg.sp))
        params = None
        t_load = time.perf_counter()
        if cfg.checkpoint_path:
            from tpu_inference.models import weights
            shardings = None
            if mesh is not None:
                from tpu_inference.parallel import shardings as shd
                shardings = shd.param_shardings(cfg.model, mesh)
            params = weights.load_checkpoint(
                cfg.model, cfg.checkpoint_path, shardings=shardings,
                quant=cfg.engine.quant)
        load_s = time.perf_counter() - t_load
        self.engine = InferenceEngine(cfg.model, cfg.engine, params=params,
                                      seed=cfg.seed, mesh=mesh)
        if params is not None:
            self.engine.note_checkpoint_load(load_s)
        self.sched = EngineScheduler(self.engine)
        # Tracing + dashboard-join series: spans this worker records
        # carry its stable replica index, and the registry emits the
        # build_info gauge with config-pure labels (identical across
        # restarts, so the router's carry never sees a label change).
        self.engine.telemetry.recorder.replica = self.replica
        _tm.emit_build_info(
            self.engine.telemetry.registry,
            device=self.engine.device_info(),
            fleet=cfg.server.fleet,
            kv_quant=cfg.engine.kv_quant,
            spec_mode="ngram" if self.engine.spec_enabled else "off",
            routing=cfg.server.routing)
        # Zero-copy KV plane counters (README "KV data plane"): arena
        # traffic this worker moved without a socket copy, plus the
        # publishes the fabric watermark gated off. Registered on the
        # relay plane too (flat zeros) so dashboards join across arms.
        reg = self.engine.telemetry.registry
        reg.counter(
            "tpu_inf_kv_plane_shm_puts_total",
            "KV payloads published into the shm arena",
            fn=lambda: self._arena.puts if self._arena else 0)
        reg.counter(
            "tpu_inf_kv_plane_shm_gets_total",
            "KV payloads adopted out of the shm arena",
            fn=lambda: self._arena.gets if self._arena else 0)
        reg.counter(
            "tpu_inf_kv_plane_shm_bytes_total",
            "bytes moved through the shm arena by direction",
            fn=lambda: self._arena.put_bytes if self._arena else 0,
            op="put")
        reg.counter(
            "tpu_inf_kv_plane_shm_bytes_total",
            "bytes moved through the shm arena by direction",
            fn=lambda: self._arena.get_bytes if self._arena else 0,
            op="get")
        reg.counter(
            "tpu_inf_fabric_publish_skipped_total",
            "fabric publishes skipped by the pool-watermark gate",
            fn=lambda: self.fabric_publish_skipped)
        if self.role == "prefill":
            self.sched.on_prefill_handoff = self._emit_handoff
        # Fleet KV fabric (README "KV fabric"): arm the engine's
        # publish hook — settled prefix pages broadcast to the router's
        # pool as fabric_put event frames, so a prefix prefilled here
        # warms every replica. The knobs ride the config envelope.
        if (cfg.server.fabric_cache_pages > 0
                and self.engine.prefix_cache is not None):
            self.engine.fabric_publish = self._publish_fabric
            self.engine.fabric_publish_min_pages = \
                cfg.server.fabric_publish_min_pages
        # Crash flight recorder: per-replica dir under the OPERATOR's
        # --blackbox-dir ('' = off). The dir outlives this process, so
        # the fleet monitor can harvest evidence after a kill -9.
        import dataclasses as _dc
        _tm.attach_flight_recorder(
            self.engine.telemetry, cfg.server.blackbox_dir, self.replica,
            retain=cfg.server.blackbox_retain,
            config=_dc.asdict(cfg),
            stats_fn=lambda: self.sched.stats.snapshot(self.engine))
        if self.do_warmup:
            self.warmup_s = self.engine.warmup()
        self.sched.start()
        self.engine.telemetry.boot_ready_s.set(_tm.process_age_s())

    # ------------------------------------------------------------ serve

    def serve(self) -> None:
        """Bind/listen FIRST (so the router's connect succeeds while the
        engine still boots — its hello RPC simply waits), then boot, then
        accept until shutdown."""
        try:
            os.unlink(self.socket_path)
        except OSError:
            pass
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        srv.bind(self.socket_path)
        srv.listen(4)
        srv.settimeout(0.25)
        self.boot()
        print(f"[worker {self.replica}] pid={os.getpid()} serving on "
              f"{self.socket_path}", file=sys.stderr, flush=True)
        while not self._shutdown.is_set():
            if os.getppid() != self._parent_pid:
                print(f"[worker {self.replica}] router gone (reparented)"
                      " — exiting", file=sys.stderr, flush=True)
                break
            try:
                sock, _ = srv.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            with self._conns_lock:
                self._conns.append(_Conn(self, sock))
        try:
            srv.close()
            os.unlink(self.socket_path)
        except OSError:
            pass

    def forget_conn(self, conn: _Conn) -> None:
        with self._conns_lock:
            if conn in self._conns:
                self._conns.remove(conn)

    def _broadcast(self, obj: Dict[str, Any], blob: bytes = b"",
                   verb: str = "") -> None:
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            c.send(obj, blob, verb)

    def _publish_fabric(self, pairs) -> None:
        """Ship settled prefix pages to the router's fabric pool
        (engine thread, via _publish_to_fabric). Each page is
        serialized individually — the pool stores per-page entries so
        they evict independently and every adoption re-verifies its own
        crc32c.

        Back-pressure gate first (README "KV fabric"): the router
        advertises its pool's free-page watermark (boot envelope +
        every stats tick); a publish that cannot fit would only be
        serialized, shipped, and evicted on arrival — skip it here and
        count the skip instead.

        On the shm plane the payloads go into this worker's arena
        region and only descriptors cross the socket; a full region
        falls back to the relay frame for the overflow pages."""
        from tpu_inference.engine import kv_cache as kvc
        from tpu_inference.server import shm_arena
        free = self._fabric_free
        if free is not None:
            if len(pairs) > free:
                self.fabric_publish_skipped += len(pairs)
                return
            self._fabric_free = free - len(pairs)
        if self._arena is not None:
            hex_descs, descs, relay = [], [], []
            for d, p in pairs:
                blob = kvc.serialize_host_pages([p])
                try:
                    descs.append(self._arena.publish(blob))
                    hex_descs.append(d.hex())
                except shm_arena.ArenaFull:
                    relay.append((d, blob))
            if descs:
                self._broadcast({"ev": "fabric_put",
                                 "digests": hex_descs,
                                 "descs": descs,
                                 "replica": self.replica},
                                verb="fabric_put")
            if not relay:
                return
            self._broadcast({"ev": "fabric_put",
                             "digests": [d.hex() for d, _ in relay],
                             "lens": [len(b) for _, b in relay],
                             "replica": self.replica},
                            b"".join(b for _, b in relay),
                            verb="fabric_put")
            return
        blobs = [kvc.serialize_host_pages([p]) for _, p in pairs]
        self._broadcast({"ev": "fabric_put",
                         "digests": [d.hex() for d, _ in pairs],
                         "lens": [len(b) for b in blobs],
                         "replica": self.replica},
                        b"".join(blobs), verb="fabric_put")

    # --------------------------------------------------------- dispatch

    # Verbs that can block for seconds (device forwards, engine-loop
    # waits, scheduler drains) run on their own thread so the reader
    # stays responsive — the router's routing peeks must never stall
    # behind a migration import or an embed batch on the same worker.
    _SLOW_VERBS = ("import_kv", "embed", "shutdown", "profile")

    # Verbs with side effects the router may retry over a fresh
    # connection: the idempotency token dedups exact duplicates so a
    # retransmitted frame replays the recorded reply instead of
    # re-applying (submit admitting a second live attempt, import-kv
    # re-offering pages).
    _IDEM_VERBS = ("submit", "cancel", "import_kv")
    _IDEM_CAP = 512

    def handle(self, conn: _Conn, obj: Dict[str, Any],
               blob: bytes) -> None:
        rid = obj.get("id")
        verb = str(obj.get("verb")).replace("-", "_")
        idem = obj.get("idem") if verb in self._IDEM_VERBS else None

        def run() -> None:
            if idem is not None:
                with self._idem_lock:
                    prev = self._idem.get(idem)
                if prev is not None:
                    out = {"id": rid}
                    out.update(prev)
                    if verb == "submit" and "rid" in prev:
                        # The first submit applied; rebind the stream
                        # to the retrying connection so in-flight
                        # tokens reach the live router socket.
                        self._req_conn[int(prev["rid"])] = conn
                    conn.send(out, verb=verb)
                    return
            try:
                fn = getattr(self, "_verb_" + verb, None)
                if fn is None:
                    raise ValueError(f"unknown verb {obj.get('verb')!r}")
                reply = fn(conn, obj, blob)
                if reply is not None:
                    out = {"id": rid, "ok": True}
                    out.update(reply)
                    if idem is not None and out.get("ok"):
                        with self._idem_lock:
                            self._idem[idem] = {k: v for k, v
                                                in out.items()
                                                if k != "id"}
                            while len(self._idem) > self._IDEM_CAP:
                                self._idem.popitem(last=False)
                    conn.send(out, verb=verb)
            except Exception as e:  # noqa: BLE001 — RPC errors reply
                conn.send({"id": rid, "ok": False, "error": str(e),
                           "kind": type(e).__name__}, verb=verb)

        if verb in self._SLOW_VERBS:
            threading.Thread(target=run, name=f"worker-{verb}",
                             daemon=True).start()
        else:
            run()

    # ------------------------------------------------------------ verbs

    def _emit_handoff(self, seq) -> bool:
        """Scheduler hook (engine thread, prefill role): export the
        settled live sequence and push it to the submitting router
        connection as a ``handoff`` event — the router imports/adopts it
        on a decode worker and the stream continues there. Returns False
        (sequence keeps decoding locally, the mixed fallback) when the
        connection is gone or nothing is exportable (e.g. SWA-evicted
        pages)."""
        from tpu_inference import telemetry
        from tpu_inference.engine import kv_cache as kvc
        conn = self._req_conn.get(seq.request_id)
        if conn is None or not conn.alive or self.draining:
            return False
        t0 = time.perf_counter()
        try:
            digests, pages, ctx_len = \
                self.engine.export_sequence_kv_live(seq)
        except Exception as e:  # noqa: BLE001 — fall back to local decode
            # The request survives (it decodes here), but a failed
            # device->host export counts against this replica's health
            # like any failed step.
            telemetry.log_event("handoff_export_failed", level="warning",
                                request_id=seq.trace_id
                                or str(seq.request_id), error=repr(e))
            self.sched._note_error(e)
            return False
        if not pages:
            return False
        parts = kvc.serialize_host_pages_parts(pages)
        total = sum(len(p) for p in parts)
        # Trace span: the live KV export — adjacent to (never
        # overlapping) this worker's prefill span and the decode
        # worker's handoff_adopt on the assembled timeline. It ends
        # HERE, before the payload leaves for the data plane: the
        # gather+serialize is identical work on every plane, while the
        # arena publish (shm) and the frame send (relay) are the data
        # plane itself and belong to the handoff window that follows.
        t_ser = time.perf_counter()
        # Zero-copy plane: the serialized parts gather-write into one
        # arena slab — the payload's single copy — and only the
        # descriptor rides the handoff frame; the decode worker adopts
        # straight from shared memory. A full region falls back to the
        # relay frame (the parts join into a blob over the socket).
        kv_desc = None
        if self._arena is not None:
            from tpu_inference.server import shm_arena
            try:
                kv_desc = self._arena.publish_parts(parts)
            except shm_arena.ArenaFull:
                kv_desc = None
        self.engine.telemetry.recorder.add(
            "handoff_export", seq.trace_id or str(seq.request_id),
            t0, t_ser, pages=len(pages), bytes=total,
            ctx_len=ctx_len, plane="shm" if kv_desc else "relay")
        self._req_conn.pop(seq.request_id, None)
        ev = {"ev": "handoff", "rid": seq.request_id,
              "n_generated": len(seq.generated),
              "ctx_len": ctx_len,
              "export_s": round(time.perf_counter() - t0, 6),
              "digests": [d.hex() for d in digests]}
        if kv_desc is not None:
            ev["kv_desc"] = kv_desc
            conn.send(ev, verb="handoff")
        else:
            conn.send(ev, b"".join(parts), verb="handoff")
        return True

    def _device_facts(self) -> dict:
        """The device facts only this process can know (the router never
        initialises a backend): the engine's device_info() — platform,
        kind, device ids, the sizes 'auto' resolved to, warm-up, peak
        memory — plus the chips the router made visible to this process
        (runtime.chip_env), as libtpu read them."""
        return dict(self.engine.device_info(),
                    visible_chips=os.environ.get("TPU_VISIBLE_CHIPS"))

    def _verb_hello(self, conn, obj, blob) -> dict:
        e = self.engine
        return {
            "pid": os.getpid(),
            "replica": self.replica,
            "role": self.role,
            "uptime_s": round(time.time() - self.started_unix, 3),
            "warmup_s": round(self.warmup_s, 3),
            "n_params": e.n_params,
            "weight_bytes": e.weight_bytes,
            "attn_backend": e.attn_backend,
            "ladder": list(e.ladder),
            "swa_evict": e.swa_evict,
            "prefix_cache": e.prefix_cache is not None,
            "host_cache_pages": (e.host_pool.capacity
                                 if e.host_pool is not None else 0),
            "device": self._device_facts(),
        }

    def _verb_submit(self, conn, obj, blob) -> dict:
        if self.draining:
            return {"ok": False, "kind": "draining",
                    "error": "worker draining"}
        from tpu_inference.engine.engine import Sequence
        s = obj["seq"]
        seq = Sequence(
            request_id=int(s["request_id"]),
            prompt_tokens=list(s["prompt_tokens"]),
            max_new_tokens=int(s["max_new_tokens"]),
            temperature=float(s.get("temperature", 0.0)),
            top_p=float(s.get("top_p", 1.0)),
            top_k=s.get("top_k"),
            seed=s.get("seed"),
            repeat_penalty=float(s.get("repeat_penalty", 1.0)),
            repeat_last_n=int(s.get("repeat_last_n", 64)),
            eos_token_id=s.get("eos_token_id"),
            trace_id=s.get("trace_id", ""),
            priority_class=s.get("class", "interactive"),
            attempt=int(s.get("attempt", 0)))
        # Router-side routing accounting rides the payload so this
        # worker's /debug/requests timelines show which replica served
        # the attempt and the fabric pull that warmed the dispatch
        # (README "KV fabric").
        seq.routed_replica = self.replica
        seq.route_hit_pages = int(s.get("route_hit_pages", 0))
        seq.route_host_hit_pages = int(
            s.get("route_host_hit_pages", 0))
        seq.route_fabric_hit_pages = int(
            s.get("route_fabric_hit_pages", 0))
        generated = s.get("generated") or []
        if generated:
            # Fleet-side recompute-resume (README "Process fleet"): the
            # router replays the tokens it already streamed; prefill
            # covers prompt + generated (host-tier hits from a drain
            # import make it a swap-in-resume) and decode continues.
            seq.generated = list(generated)
            seq.resume_base = len(generated)
        handoff = s.get("handoff")
        if handoff and not blob and handoff.get("kv_desc") is not None:
            # Zero-copy adoption: pull the export straight out of the
            # arena slab the prefill worker wrote. A stale slab (owner
            # died, region reclaimed) or a failed crc leaves blob empty
            # and the recompute-resume fallback below takes over —
            # byte-identical under greedy, exactly the relay semantics.
            blob, _ = self._arena_blob(handoff["kv_desc"], "handoff")
        if handoff and blob and generated:
            # P/D handoff resume (README "P/D disaggregation"): the blob
            # carries the prefill worker's settled KV pages (incl. the
            # partial final page); admission adopts them directly — no
            # re-prefill, zero recomputed tokens. A malformed blob falls
            # back to the recompute-resume path above at adoption time.
            from tpu_inference.engine import kv_cache as kvc
            try:
                # copy=False: the adopt path hands the pages straight
                # to the device restore — views over the blob (kept
                # alive by the arrays) skip a full payload copy.
                pages = kvc.deserialize_host_pages(blob, copy=False)
            except KVIntegrityError:
                # Corrupt blob: rejected AND counted — never adopted.
                self.engine.kv_integrity_rejections += 1
                pages = []
            except Exception:  # noqa: BLE001 — recompute-resume fallback
                pages = []
            if pages:
                seq.adopt_kv = (pages, int(handoff.get("ctx_len", 0)))
            else:
                self.engine.adopt_fallbacks += 1
        elif handoff and generated and not blob:
            self.engine.adopt_fallbacks += 1
        if self.role == "prefill" and seq.adopt_kv is None:
            # Prefill-role workers hand every prefill they settle off to
            # the decode tier (adoptions skip _prefill_done, so an
            # adopted fallback landing here decodes locally instead of
            # bouncing forever).
            seq.handoff_after_prefill = True
        rid = seq.request_id
        # A resubmitted rid (router retry after a reconnect resync or a
        # lost ack) must never leave TWO live attempts decoding the
        # same request — cancel the ghost before admitting this one.
        def _rid_live() -> bool:
            with self.sched._lock:
                return (rid in self.sched._callbacks or any(
                    p.seq.request_id == rid
                    for p in self.sched._waiting))

        if _rid_live():
            self.sched.cancel(rid)
            # cancel() only FLAGS a running attempt done — the engine
            # loop reaps it next tick. Admitting the same rid before
            # the reap would leave two registered attempts: the ghost
            # keeps streaming stale tokens through the new binding
            # (the router sees a stream gap). Wait the reap out.
            deadline = time.monotonic() + 5.0
            while _rid_live() and time.monotonic() < deadline:
                time.sleep(0.005)
            if _rid_live():
                return {"error": f"request {rid} still draining "
                                 "its previous attempt"}
        self._req_conn[rid] = conn

        # "k" is the token's absolute stream index, counted here: the
        # engine appends to seq.generated as it steps but may deliver
        # several buffered tokens in one burst (e.g. after a batch-shape
        # recompile), so len(generated)-1 at callback time would stamp
        # the last index on every token of the burst. The counter starts
        # at the resume prefix so a migrated/handoff resume continues
        # the router's stream where it left off.
        knext = itertools.count(len(seq.generated))

        def on_token(sq, tok: int) -> None:
            conn.send({"ev": "token", "rid": rid, "t": int(tok),
                       "k": next(knext)}, verb="token")

        def on_finish(sq) -> None:
            self._req_conn.pop(rid, None)
            tid = sq.trace_id or str(rid)
            spans = self.engine.telemetry.recorder.export_recent(tid)
            if sq.finish_reason == "handoff":
                # The handoff event already left on this connection and
                # IS the request's continuation — a finish frame here
                # would terminate the client stream mid-generation. The
                # prefill-side spans (sealed just now, AFTER the
                # handoff frame) ship on their own event instead.
                if spans:
                    conn.send({"ev": "spans", "rid": rid, "trace": tid,
                               "spans": spans}, verb="spans")
                return
            fin = sq.finish_time or time.perf_counter()
            first = sq.first_token_time or fin
            start = sq.prefill_start or first
            conn.send({
                "ev": "finish", "rid": rid,
                "reason": sq.finish_reason or "stop",
                "n_generated": len(sq.generated),
                "cached_tokens": sq.cached_tokens,
                "host_restored_pages": sq.host_restored_pages,
                "preemptions": sq.preemptions,
                "resume_base": sq.resume_base,
                "prefill_s": round(max(0.0, first - start), 6),
                "decode_s": round(max(0.0, fin - first), 6),
                # The engine counters the router's supervision view
                # sums (stats snapshot keys): what this request did to
                # them arrives with its finish, not a stats tick later.
                "counters": {
                    "preemptions": self.engine.preemptions_total,
                    "recompute_resumes": self.engine.resumes_total,
                    "swap_in_resumes": self.engine.swap_in_resumes,
                    "pd_adoptions": self.engine.adoptions_in},
                # Completed spans ride the finish frame back to the
                # router's trace assembly (README "Observability").
                "trace": tid,
                "spans": spans,
            }, verb="finish")

        self.sched.submit(seq, on_token, on_finish)
        return {"rid": rid}

    def _verb_cancel(self, conn, obj, blob) -> dict:
        self.sched.cancel(int(obj["rid"]))
        self._req_conn.pop(int(obj["rid"]), None)
        return {}

    def _verb_peek(self, conn, obj, blob) -> dict:
        """Router scoring probe: tiered prefix peek + load/pressure.
        Side-effect-free on the cache (PrefixCache.peek contract), safe
        from this RPC thread."""
        digests = [bytes.fromhex(d) for d in obj.get("digests") or ()]
        hbm = host = 0
        pc = self.engine.prefix_cache
        if pc is not None and digests:
            hbm, host = pc.peek_digests_tiered(digests)
        return {"hbm": hbm, "host": host, "load": self.sched.load,
                "pressure": bool(self.engine.under_pressure),
                # P/D routing inputs (README "P/D disaggregation"):
                # phase role, prefill backlog depth (queued requests),
                # and decode ladder occupancy (bound lanes / top rung).
                "role": self.role,
                "backlog": len(self.sched._waiting),
                "occupancy": self._ladder_occupancy()}

    def _ladder_occupancy(self) -> float:
        e = self.engine
        return round(sum(s is not None for s in e.slots)
                     / max(e.ladder[-1], 1), 4)

    def _verb_stats(self, conn, obj, blob) -> dict:
        # The router's stats tick doubles as the data plane's control
        # channel: the fabric pool's free-page watermark rides in
        # (publish back-pressure) and the batched arena slab frees ride
        # in (descriptor lifecycle — the router freed every consumer).
        ff = obj.get("fabric_free")
        if ff is not None:
            self._fabric_free = int(ff)
        frees = obj.get("arena_free")
        if frees and self._arena is not None:
            for off in frees:
                self._arena.free(int(off))
        return {"stats": self.sched.stats.snapshot(self.engine)}

    def _verb_steps(self, conn, obj, blob) -> dict:
        """Step-ledger roofline report (GET /debug/steps): windowed
        per-step-kind bottleneck verdicts from this replica's ring."""
        return {"steps": self.engine.telemetry.steps_report(
            since=obj.get("since"), until=obj.get("until"),
            records=bool(obj.get("records")))}

    def _verb_metrics(self, conn, obj, blob) -> dict:
        from tpu_inference import telemetry
        return {"samples": telemetry.dump_registry(
            self.engine.telemetry.registry)
            + telemetry.process_counters_dump()}

    def _verb_healthz(self, conn, obj, blob) -> dict:
        e = self.engine
        out = {
            "pid": os.getpid(),
            "uptime_s": round(time.time() - self.started_unix, 3),
            "draining": self.draining,
            "load": self.sched.load,
            "pool_pressure": round(e.pool_pressure, 4),
            "under_pressure": e.under_pressure,
            "preemptions": e.preemptions_total,
            "swap_in_resumes": e.swap_in_resumes,
            # P/D disaggregation: phase role + the two numbers a
            # handoff stall shows up in (backlog on the prefill side,
            # ladder occupancy on the decode side).
            "role": self.role,
            "prefill_backlog": len(self.sched._waiting),
            "ladder_occupancy": self._ladder_occupancy(),
            "pd_handoffs": self.sched.stats.pd_handoffs,
            "pd_adoptions": e.adoptions_in,
            "pd_adopt_fallbacks": e.adopt_fallbacks,
            # Byzantine transport: corrupt KV blobs this worker
            # rejected at adopt/import time (never adopted silently).
            "kv_integrity_rejections": e.kv_integrity_rejections,
            # Fleet KV fabric: settled prefix pages this worker has
            # published to the router's pool.
            "fabric_published_pages": e.fabric_published_pages,
            "device": self._device_facts(),
        }
        # Rolling SLO view (quantiles + breaches; windows stay in the
        # stats snapshot — healthz is the human-sized surface).
        if e.telemetry.slo is not None:
            out["slo"] = e.telemetry.slo.snapshot(include_window=False)
        if e.host_pool is not None:
            out["host_cache"] = {
                "capacity_pages": e.host_pool.capacity,
                "pages_used": e.host_pool.used,
                "offloaded": e.host_pool.offloaded_total,
                "restored": e.host_pool.restored_total,
                "imported": e.host_pool.imported_total,
                "evicted": e.host_pool.evicted_total,
                "swap_in_resumes": e.swap_in_resumes,
                "swap_out_s_total": round(
                    e.host_pool.swap_out_s_total, 6),
                "swap_in_s_total": round(
                    e.host_pool.swap_in_s_total, 6),
            }
        return out

    def _verb_recent(self, conn, obj, blob) -> dict:
        return {"recent": self.sched.recent_snapshot(
            int(obj.get("n", 50)))}

    def _verb_trace(self, conn, obj, blob) -> dict:
        """Pull-based span access (README "Observability"): one trace's
        spans by id, or the recent finished-trace ring — the router's
        fallback when its own assembly missed event frames (e.g. it
        restarted mid-request)."""
        # NB: the trace id rides under "trace" — "id" is the RPC
        # correlation id on every frame.
        rec = self.engine.telemetry.recorder
        tid = obj.get("trace")
        if tid:
            return {"spans": rec.get_trace(str(tid)) or []}
        return {"traces": rec.recent_traces(int(obj.get("n", 64))),
                "maintenance": rec.maintenance_spans()}

    def _verb_profile(self, conn, obj, blob) -> dict:
        """On-demand jax.profiler capture (README "Observability"):
        trace this worker's device+host activity for ``seconds`` and
        return the trace directory (TensorBoard / Perfetto-loadable).
        Serving continues while the profiler runs — that is the point:
        the capture shows the live fleet's dispatch stream. Runs on a
        slow-verb thread; the path is always under the operator's
        profile_dir, never client-chosen."""
        from tpu_inference import telemetry
        return telemetry.capture_jax_profile(
            self.cfg.server.profile_dir, self.replica,
            float(obj.get("seconds", 3.0)), self.engine.telemetry)

    def _verb_chaos(self, conn, obj, blob) -> dict:
        e = self.engine
        rate = obj.get("step_failure_rate")
        wedge = obj.get("step_wedge_s")
        pressure = obj.get("page_pressure")
        if rate is not None:
            e.chaos_step_failure_rate = float(rate)
        if wedge is not None:
            e.chaos_step_wedge_s = float(wedge)
        if pressure is not None:
            e.request_page_pressure(int(pressure))
        rpc = obj.get("rpc")
        if rpc is not None:
            # Transport-level chaos (README "Failure model"): rebuild
            # the worker-side shim; the router forwards the same knobs
            # it applied to its own side.
            self.chaos_rpc = self._build_chaos_rpc(rpc)
        t = e._pressure_target
        return {"step_failure_rate": e.chaos_step_failure_rate,
                "step_wedge_s": e.chaos_step_wedge_s,
                "page_pressure": (e.chaos_page_pressure if t is None
                                  else t),
                "rpc": (self.chaos_rpc.policy.snapshot()
                        if self.chaos_rpc is not None else None)}

    def _verb_embed(self, conn, obj, blob) -> dict:
        vecs = self.engine.embed_many([list(b) for b in obj["batch"]])
        return {"embeddings": vecs.tolist()}

    def _verb_import_kv(self, conn, obj, blob) -> dict:
        """Adopt a sibling replica's drain export into the host tier.
        Replies only after the engine loop APPLIED the import, so the
        router's subsequent resubmit is guaranteed to see the pages.

        Three payload shapes: a concatenated blob (relay plane), a list
        of per-page arena descriptors (``descs`` — fabric warmboot and
        fabric pulls on the shm plane), or one multi-page descriptor
        (``kv_desc`` — drain migrate on the shm plane). Descriptor
        reads that fail integrity come back in ``rejected_digests`` so
        the router evicts the poisoned pool entries."""
        from tpu_inference.engine import kv_cache as kvc
        descs = obj.get("descs")
        if descs is not None:
            return self._import_kv_descs(obj.get("digests") or (), descs)
        digests = [bytes.fromhex(d) for d in obj.get("digests") or ()]
        if not blob and obj.get("kv_desc") is not None:
            blob, rejected = self._arena_blob(obj["kv_desc"], "migrate")
            if not blob:
                return {"offered": 0, "applied": False, "adopted": 0,
                        "rejected": "arena slab unreadable"
                        if not rejected else "arena slab corrupt"}
        try:
            pages = kvc.deserialize_host_pages(blob) if blob else []
        except KVIntegrityError as e:
            # Reject-and-count: a corrupt drain export must never land
            # in the host tier; the router's resubmission falls back to
            # recompute-resume (byte-identical under greedy).
            self.engine.kv_integrity_rejections += 1
            return {"offered": 0, "applied": False, "adopted": 0,
                    "rejected": str(e)}
        n = min(len(digests), len(pages))
        before = self.engine.migrate_in_pages
        done = self.engine.request_import_host(
            list(zip(digests[:n], pages[:n])))
        self.sched.kick()
        applied = done.wait(timeout=10.0)
        return {"offered": n, "applied": bool(applied),
                "adopted": self.engine.migrate_in_pages - before}

    def _import_kv_descs(self, hex_digests, descs) -> dict:
        """Descriptor-list import (shm plane): read each per-page slab
        from the arena, deserialize its single-page blob, and offer the
        survivors to the host tier. Integrity failures (slab crc, page
        digest) are counted AND reported back by digest so the router
        drops the unusable pool entries; stale slabs are simply skipped
        (the pull falls back to recompute warmth)."""
        from tpu_inference.engine import kv_cache as kvc
        offers, rejected_hex = [], []
        for hexd, desc in zip(hex_digests, descs):
            pblob, rejected = self._arena_blob(desc, "fabric_pull")
            if not pblob:
                if rejected:
                    rejected_hex.append(hexd)
                continue
            try:
                pgs = kvc.deserialize_host_pages(pblob)
            except KVIntegrityError:
                self.engine.kv_integrity_rejections += 1
                rejected_hex.append(hexd)
                continue
            except Exception:  # noqa: BLE001 — skip, recompute covers it
                continue
            if pgs:
                offers.append((bytes.fromhex(hexd), pgs[0]))
        if not offers:
            return {"offered": 0, "applied": False, "adopted": 0,
                    "rejected_digests": rejected_hex}
        before = self.engine.migrate_in_pages
        done = self.engine.request_import_host(offers)
        self.sched.kick()
        applied = done.wait(timeout=10.0)
        return {"offered": len(offers), "applied": bool(applied),
                "adopted": self.engine.migrate_in_pages - before,
                "rejected_digests": rejected_hex}

    def _verb_drain(self, conn, obj, blob) -> dict:
        migrate = obj.get("migrate")
        if migrate is None:
            migrate = self.cfg.server.fleet_migrate
        threading.Thread(target=self.drain, args=(bool(migrate),),
                         name="worker-drain", daemon=True).start()
        return {"draining": True}

    def _verb_debug(self, conn, obj, blob) -> dict:
        """Pool-invariant snapshot for the cross-process leak tests
        (tests/_leak.py's checks, worker-side): optionally clears the
        prefix cache first so 'fully reclaimable' is checkable. Only
        meaningful when the worker is idle."""
        e = self.engine
        cache = e.prefix_cache
        out = {"pipeline_pending": bool(e.pipeline_pending),
               "preempted_uncollected": len(e._preempted_out)}
        if cache is not None and cache.host_pool is not None:
            pool = cache.host_pool
            out["host_used_matches_entries"] = (
                pool.used == len(cache._host))
            out["host_bytes_match"] = (pool.bytes_resident == sum(
                en.nbytes for en in cache._host.values()))
            out["host_within_capacity"] = (
                0 <= pool.used <= pool.capacity)
            out["tier_overlap"] = len(set(cache._host)
                                      & set(cache._table))
        if obj.get("clear"):
            e.set_page_pressure(0)
            if cache is not None:
                cache.clear()
        alloc = e.allocator
        out.update({
            "num_free": alloc.num_free,
            "num_pages": alloc.num_pages,
            "refs_held": sum(1 for p in range(1, alloc.num_pages)
                             if alloc._refs[p] > 0),
            "evictable_count": alloc.evictable_count,
            "slots_bound": sum(s is not None for s in e.slots),
            "host_used": (cache.host_pool.used
                          if cache is not None
                          and cache.host_pool is not None else 0),
        })
        return out

    def _verb_shutdown(self, conn, obj, blob) -> dict:
        drain = bool(obj.get("drain", True))
        timeout = float(obj.get("timeout_s", 30.0))
        self.draining = True
        self.sched.stop(drain=drain, timeout=timeout)
        self._shutdown.set()
        return {"stopped": True}

    # ------------------------------------------------------------ drain

    def drain(self, migrate: bool) -> None:
        """Graceful wind-down (SIGTERM / drain RPC): freeze the
        scheduler, settle in-flight device work (delivering its tokens),
        export every live request — KV pages included when migration is
        on — as ``migrate`` events, then broadcast ``drained`` (with the
        final stats + metrics dump, the router's restart carry) and
        exit."""
        if self.draining:
            return
        self.draining = True
        from tpu_inference import telemetry
        from tpu_inference.engine import kv_cache as kvc
        t0 = time.monotonic()
        budget = max(1.0, self.cfg.server.drain_timeout_s)
        engine, sched = self.engine, self.sched
        telemetry.log_event("worker_drain", level="warning",
                            replica=self.replica, migrate=migrate,
                            load=sched.load)
        if engine.telemetry.flight is not None:
            # Last full capture before state is torn down (the atexit
            # hook won't run — drain ends in os._exit).
            engine.telemetry.flight.capture("sigterm", min_interval_s=0.0)
        sched.stop(drain=False, timeout=budget)
        try:
            if engine.pipeline_pending:
                sched._deliver(engine.drain_pipeline())
        except Exception as e:  # noqa: BLE001 — exit must proceed
            telemetry.log_event("drain_settle_failed", level="warning",
                                replica=self.replica, error=repr(e))
            engine.abort_pipeline()
        engine.take_preempted()
        with sched._lock:
            pendings = (list(sched._callbacks.values())
                        + list(sched._waiting))
        migrated = 0
        for pending in pendings:
            seq = pending.seq
            if seq.done:
                continue
            tid = seq.trace_id or str(seq.request_id)
            digests, host_pages = [], []
            t_exp = time.perf_counter()
            if (migrate and seq.pages
                    and time.monotonic() - t0 < budget):
                try:
                    digests, host_pages = engine.export_sequence_kv(seq)
                except Exception as e:  # noqa: BLE001 — exit must proceed
                    # The request still migrates (as a recompute), but
                    # the failed export is on the record.
                    telemetry.log_event(
                        "drain_export_failed", level="warning",
                        replica=self.replica, request_id=tid,
                        error=repr(e))
                    digests, host_pages = [], []
            if host_pages:
                engine.telemetry.recorder.add(
                    "drain_export", tid, t_exp, time.perf_counter(),
                    pages=len(host_pages))
            ev = {"ev": "migrate", "rid": seq.request_id,
                  "n_generated": len(seq.generated),
                  "digests": [d.hex() for d in digests],
                  # In-flight spans so far (chunks, swaps, the export):
                  # the request continues on another worker, so its
                  # trace must not die with this process.
                  "trace": tid,
                  "spans": engine.telemetry.recorder.export_open(tid)}
            blob = (kvc.serialize_host_pages(host_pages)
                    if host_pages else b"")
            if blob and self._arena is not None:
                # Zero-copy migrate: the export outlives this process
                # in the arena (the segment is router-owned); only the
                # descriptor rides the event. Region full → relay blob.
                from tpu_inference.server import shm_arena
                try:
                    ev["kv_desc"] = self._arena.publish(blob)
                    blob = b""
                except shm_arena.ArenaFull:
                    pass
            target = self._req_conn.get(seq.request_id)
            if target is not None and target.alive:
                target.send(ev, blob, verb="migrate")
                migrated += 1
        self._broadcast({
            "ev": "drained", "replica": self.replica,
            "migrated_requests": migrated,
            "stats": sched.stats.snapshot(engine),
            "metrics": telemetry.dump_registry(
                engine.telemetry.registry),
        }, verb="drained")
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            c.flush(timeout=max(1.0, budget - (time.monotonic() - t0)))
        self._drained_evt.set()
        self._shutdown.set()
        # The accept loop may sit in a 250 ms timeout; exiting here is
        # the point of a drain — everything worth saving already left.
        os._exit(0)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="tpu_inference engine-worker process (one dp "
                    "replica behind the fleet router; README 'Process "
                    "fleet'). Reads a JSON config envelope from stdin.")
    ap.add_argument("--socket", required=True,
                    help="unix socket path to serve the RPC on")
    ap.add_argument("--replica", type=int, default=0)
    ap.add_argument("--config", default=None,
                    help="config envelope path (default: stdin)")
    args = ap.parse_args()

    if args.config:
        with open(args.config) as f:
            envelope = json.load(f)
    else:
        envelope = json.load(sys.stdin)

    from tpu_inference.config import framework_config_from_dict
    from tpu_inference.runtime import (enable_compile_cache,
                                       require_backend, select_platform)

    cfg = framework_config_from_dict(envelope["config"])
    # This process owns its replica's devices (the router spawned it
    # with only its own chips visible and stays off JAX itself), so the
    # router's asks that need a device are settled here: which platform
    # it must be, and what 'auto' sizes come to on this chip's HBM.
    platform = envelope.get("platform")
    select_platform(platform or "auto",
                    cfg.parallel.tp * cfg.parallel.sp)
    enable_compile_cache()
    if platform:
        require_backend(platform)
    from tpu_inference.engine.autosize import resolve_sizing

    cfg.engine = resolve_sizing(cfg.model, cfg.engine,
                                envelope.get("sizing"), tp=cfg.parallel.tp)
    role = envelope.get("role")
    if role:
        # Per-worker phase role (README "P/D disaggregation"): the
        # router resolves ServerConfig.worker_roles and ships THIS
        # worker's entry, folded into the engine config so warmup and
        # the handoff hook specialize.
        import dataclasses

        cfg.engine = dataclasses.replace(cfg.engine, role=role)
    nice = int(envelope.get("nice") or 0)
    if nice and hasattr(os, "nice"):
        # Shared-CPU hosts (README "P/D disaggregation"): the prefill
        # tier self-deprioritizes so decode workers keep their cadence
        # under prefill bursts — on per-chip deployments the isolation
        # is physical and this is a no-op. A refused increment (e.g. a
        # negative value without CAP_SYS_NICE) must NOT crash the
        # worker into a restart loop — priority is an optimization,
        # not a correctness requirement.
        try:
            os.nice(nice)
        except OSError as e:
            print(f"[worker {args.replica}] os.nice({nice}) refused: "
                  f"{e}; serving at current priority", file=sys.stderr)
    worker = EngineWorker(cfg, replica=args.replica,
                          socket_path=args.socket,
                          warmup=bool(envelope.get("warmup", True)))
    # Zero-copy KV plane (README "KV data plane"): the router ships
    # this worker's arena region spec plus the fabric pool's current
    # free-page watermark; both are absent on the relay plane.
    worker.attach_arena(envelope.get("shm"))
    ff = envelope.get("fabric_free")
    if ff is not None:
        worker._fabric_free = int(ff)

    def _sigterm(signum, frame):
        # Signal-handler context: just flag; the drain thread does the
        # blocking work (device sync + socket writes).
        threading.Thread(target=worker.drain,
                         args=(worker.cfg.server.fleet_migrate,),
                         name="worker-drain", daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    worker.serve()


if __name__ == "__main__":
    main()
