"""Ollama-protocol HTTP server over the TPU engine.

Wire contract (load-bearing — SURVEY.md §2c; the reference's traffic
generator must run unchanged against this server):

- ``POST /api/generate`` with JSON ``{"model", "prompt", "temperature",
  "max_tokens", "stream"}`` (reference: traffic_generator/main.py:241-247).
  ``options.temperature`` / ``options.num_predict`` are honored too (the
  documented Ollama placement).
- stream=true: ``200`` with ``Content-Type: application/x-ndjson`` and
  chunked transfer; one JSON line per token
  ``{"model", "created_at", "response", "done": false}``; the terminal line
  adds ``done_reason``, ``context`` (token ids) and the ns-duration counters
  ``total_duration, load_duration, prompt_eval_count, prompt_eval_duration,
  eval_count, eval_duration``.
- stream=false: one JSON object, ``response`` = full text + same counters.
- **Headers are withheld until the first token is ready** so the client-side
  TTFT metric (first streamed chunk ≈ header arrival; reference
  logs/log.json) measures model latency, not connection latency.

Also serves ``GET /api/tags``, ``/api/version``, ``/healthz``, and
``/metrics`` (scheduler counters: batch occupancy, KV-page utilization —
SURVEY.md §5 observability).

Documented sampling divergences from Ollama: ``repeat_penalty`` defaults
to 1.0 (off), not Ollama's 1.1 — send ``options.repeat_penalty`` for
parity. Options accepted but not honored exactly (``repeat_last_n``
beyond the static penalty window; ``repeat_penalty`` under speculative
decoding, where rejection sampling needs the unmodified target
distribution) are reported in a ``warnings`` list on the terminal record.
"""

from __future__ import annotations

import asyncio
import collections
import datetime
import itertools
import json
import random
import threading
import time
import uuid
from typing import Any, Optional

from aiohttp import web

from tpu_inference import telemetry
from tpu_inference.config import PRIORITY_CLASSES, FrameworkConfig
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.engine.sampling import PENALTY_WINDOW
from tpu_inference.server.replicas import (FleetSaturated, FleetUnavailable)
from tpu_inference.server.tokenizer import (IncrementalDecoder, StopMatcher,
                                            build_tokenizer)


def _now_iso() -> str:
    return (datetime.datetime.now(datetime.timezone.utc)
            .strftime("%Y-%m-%dT%H:%M:%S.%f000Z"))


class DeliveryOutbox:
    """What other threads hand to one event loop's streams, and the
    wake-up that carries it across.

    ``put`` appends ``(stream's queue, item)`` from any thread: no
    syscall, no ``Handle``, no GIL release. The loop, woken by ONE
    ``call_soon_threadsafe``, drains everything appended so far into
    the per-request ``asyncio.Queue``s, in the order it was appended
    (one FIFO: a stream's finish never overtakes its tokens).

    Who posts the wake-up: a thread that calls ``post`` says it will
    keep calling it whenever a delivery of its own is over (an engine
    thread, through ``EngineScheduler.on_delivered``), so from then on
    its puts wait for its post: a turn's ~190 tokens cross in one
    wake-up. Every other thread (a submit's rejection on the caller's
    thread, the watchdog's failover, shutdown's force-finish, the
    process fleet's reader) is posted for at each put, so nothing is
    ever left in the outbox for want of a later call.
    """

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self._loop = loop
        self._items: collections.deque = collections.deque()
        self._thread = threading.local()    # .posts: post() was called

    def put(self, queue: asyncio.Queue, item: tuple) -> None:
        self._items.append((queue, item))
        if not getattr(self._thread, "posts", False):
            self._loop.call_soon_threadsafe(self._drain)

    def post(self) -> bool:
        """Wake the loop if anything is in the outbox; says whether."""
        self._thread.posts = True
        pending = bool(self._items)
        if pending:
            self._loop.call_soon_threadsafe(self._drain)
        return pending

    def _drain(self) -> None:
        items = self._items
        while items:
            queue, item = items.popleft()
            queue.put_nowait(item)


def build_engine_group(cfg: FrameworkConfig, load_params=None,
                       platform: Optional[str] = None,
                       sizing: Optional[dict] = None) -> "EngineGroup":
    """Construct the dp replica fleet for a FrameworkConfig.

    ``cfg.server.fleet`` picks the backend (README "Process fleet"):
    "in-process" builds dp engines in this process behind an EngineGroup
    (dp=1: one engine over the whole (tp, sp) mesh; dp>1: each replica
    its own tp*sp-device submesh, KV pool and scheduler thread);
    "subprocess" returns a ProcessEngineGroup router that spawns one
    engine-worker OS process per replica at start(). ``load_params`` is
    a callable (mesh | None) -> params so a checkpoint streams into
    each replica's own device layout (in-process only — workers load
    their own checkpoints from cfg.checkpoint_path).

    ``platform`` (the CLI's --platform) and ``sizing``
    (autosize.sizing_request) are settled by whichever process owns the
    devices: here for the in-process fleet, in each worker for the
    subprocess fleet — whose router (this process, then) must not touch
    a backend at all.
    """
    from tpu_inference.server.replicas import EngineGroup

    if cfg.server.fleet == "subprocess":
        from tpu_inference.server.fleet import ProcessEngineGroup
        return ProcessEngineGroup(cfg, platform=platform, sizing=sizing)
    if cfg.server.fleet != "in-process":
        raise ValueError(f"unknown fleet backend {cfg.server.fleet!r}; "
                         "one of ('in-process', 'subprocess')")
    if (any(r != "mixed" for r in cfg.server.worker_roles)
            or cfg.engine.role != "mixed"):
        raise ValueError(
            "P/D worker roles (--role/--roles/--pd-ratio) need "
            "--fleet subprocess: the live KV handoff moves pages "
            "between worker PROCESSES (README 'P/D disaggregation'); "
            "the in-process fleet serves every replica mixed")
    import jax

    from tpu_inference.config import ParallelConfig
    from tpu_inference.engine.autosize import resolve_sizing
    from tpu_inference.parallel.mesh import build_mesh
    from tpu_inference.runtime import require_backend

    if platform is not None:
        require_backend(platform)
    pcfg = cfg.parallel
    cfg.engine = resolve_sizing(cfg.model, cfg.engine, sizing, tp=pcfg.tp)
    if pcfg.dp <= 1:
        meshes = [build_mesh(pcfg) if pcfg.n_devices > 1 else None]
    else:
        per = pcfg.tp * pcfg.sp
        devices = jax.devices()
        if len(devices) < per * pcfg.dp:
            raise ValueError(f"dp={pcfg.dp} replicas of {per} devices need "
                             f"{per * pcfg.dp}; only {len(devices)} visible")
        sub = ParallelConfig(tp=pcfg.tp, sp=pcfg.sp)
        meshes = [build_mesh(sub, devices=devices[i * per:(i + 1) * per])
                  for i in range(pcfg.dp)]
    engines = []
    for mesh in meshes:
        t_load = time.perf_counter()
        params = load_params(mesh) if load_params else None
        load_s = time.perf_counter() - t_load
        engines.append(InferenceEngine(
            cfg.model, cfg.engine, params=params, seed=cfg.seed, mesh=mesh))
        if params is not None:
            engines[-1].note_checkpoint_load(load_s)
    return EngineGroup(engines, cfg.server)


class InferenceServer:
    """Engine replicas + schedulers + tokenizer behind the Ollama HTTP
    protocol."""

    def __init__(self, cfg: FrameworkConfig,
                 engine: Optional[InferenceEngine] = None,
                 group: Optional[Any] = None,
                 load_duration_ns: Optional[int] = None):
        """``load_duration_ns``: time spent building engines/loading
        weights when the caller built the group itself (build_server) —
        it feeds the Ollama ``load_duration`` wire field."""
        from tpu_inference.server.replicas import EngineGroup

        self.cfg = cfg
        # Tokenizer first: its consistency check needs no engine, so a
        # broken deployment fails in milliseconds, not after minutes of
        # weight load + XLA compile.
        self.tokenizer = build_tokenizer(cfg.server.tokenizer,
                                         vocab_size=cfg.model.vocab_size)
        if self.tokenizer.vocab_size > cfg.model.vocab_size:
            # A tokenizer that can emit ids the model cannot embed is a
            # broken deployment: the XLA gather would clamp those ids
            # silently on the prompt path, and request validation
            # (context ids < model vocab) would reject the server's own
            # context arrays. Fail loudly at boot, not one wrong
            # embedding at a time.
            raise ValueError(
                f"tokenizer vocab ({self.tokenizer.vocab_size}) exceeds "
                f"model vocab ({cfg.model.vocab_size}): prompts could "
                "encode to ids the model cannot embed; use the "
                "checkpoint's own tokenizer or a model with a matching "
                "embedding table")
        t0 = time.perf_counter()
        if group is None:
            group = (EngineGroup([engine], cfg.server) if engine is not None
                     else build_engine_group(cfg))
        self.group = group
        self.load_duration_ns = (load_duration_ns if load_duration_ns
                                 is not None else
                                 int((time.perf_counter() - t0) * 1e9))
        self._ids = itertools.count()
        self._outbox: Optional[DeliveryOutbox] = None   # set at startup

    @property
    def engine(self):
        """Primary replica's engine facts (tests/bench and the model-
        card routes). In-process: the engine object itself; subprocess
        fleet: a read-only info proxy fetched from worker 0 (None until
        the fleet has spawned — routes only run after startup)."""
        return self.group.engine

    # ------------------------------------------------------------- app

    def make_app(self) -> web.Application:
        app = web.Application()
        app.router.add_post("/api/generate", self.handle_generate)
        app.router.add_post("/api/chat", self.handle_chat)
        app.router.add_get("/api/tags", self.handle_tags)
        app.router.add_post("/api/show", self.handle_show)
        app.router.add_post("/api/embeddings", self.handle_embeddings)
        app.router.add_post("/api/embed", self.handle_embeddings)
        app.router.add_get("/api/ps", self.handle_ps)
        app.router.add_get("/api/version", self.handle_version)
        app.router.add_get("/healthz", self.handle_health)
        app.router.add_get("/metrics", self.handle_metrics)
        if self.cfg.server.enable_debug:
            app.router.add_get("/debug/requests", self.handle_debug_requests)
            app.router.add_get("/debug/trace", self.handle_debug_trace)
            app.router.add_get("/debug/steps", self.handle_debug_steps)
            app.router.add_get("/debug/blackbox",
                               self.handle_debug_blackbox)
            app.router.add_post("/debug/profile", self.handle_profile)
            app.router.add_post("/debug/chaos", self.handle_chaos)
            app.router.add_post("/debug/rollout", self.handle_rollout)
        app.on_startup.append(self._on_startup)
        app.on_cleanup.append(self._on_cleanup)
        return app

    async def _on_startup(self, app) -> None:
        if self.cfg.server.warmup:
            secs = self.group.warmup()
            print(f"engine warmup: compiled all graphs in {secs:.1f}s")
        # One outbox a running loop (tests cycle the app over loops).
        # In-process engine threads post a turn's deliveries themselves;
        # the subprocess fleet has no scheduler here, and its reader
        # thread is posted for at each put.
        self._outbox = DeliveryOutbox(asyncio.get_running_loop())
        for sched in getattr(self.group, "schedulers", ()):
            sched.on_delivered = self._outbox.post
        # start() before the boot prints: the subprocess fleet spawns
        # its workers here, and the prints below read worker-0 facts.
        self.group.start()
        # Serving: the boot's last gauge, set once (in-process replicas;
        # a subprocess worker sets its own at the end of its boot).
        ready_s = telemetry.process_age_s()
        for engine in self.group.engines:
            tel = getattr(engine, "telemetry", None)
            if tel is not None:
                tel.boot_ready_s.set(ready_s)
        scfg = self.cfg.server
        wd = (f"{scfg.step_watchdog_s:g}s" if scfg.step_watchdog_s > 0
              else "off")
        cap = scfg.admission_queue_depth or "off"
        host_pages = self.cfg.engine.host_cache_pages
        if self.engine is not None:
            dev = self.engine.device_info()
            print(f"device: platform={dev['platform']} "
                  f"kind={dev['kind']!r} ids={dev['ids']} "
                  f"attn_backend={dev['attn_backend']} "
                  f"max_batch_size={dev['max_batch_size']} "
                  f"num_pages={dev['num_pages']}")
        ladder = self.engine.ladder if self.engine is not None else (1,)
        if len(ladder) > 1:
            print(f"batch ladder: rungs={list(ladder)} "
                  f"(decode graph per rung; dispatch follows occupancy)")
        print(f"supervision: fleet={scfg.fleet} "
              f"dp={len(self.group.engines)} "
              f"routing={scfg.routing} "
              f"hit_weight={scfg.route_hit_weight:g} "
              f"host_hit_weight={scfg.route_host_hit_weight:g} "
              f"host_cache_pages={host_pages} "
              f"step_watchdog={wd} "
              f"quarantine_after={scfg.quarantine_after_failures} "
              f"cooldown={scfg.quarantine_cooldown_s:g}s "
              f"failover_retries={scfg.failover_max_retries} "
              f"queue_cap={cap}")

    async def _on_cleanup(self, app) -> None:
        self.group.stop(drain=False)

    # ------------------------------------------------------------- routes

    @staticmethod
    def _retry_after_headers(retry_after_s: float) -> dict:
        # Retry-After takes integer seconds; round up so "0.5" doesn't
        # become "retry immediately".
        return {"Retry-After": str(max(1, int(-(-retry_after_s // 1))))}

    async def handle_health(self, request: web.Request) -> web.Response:
        """Fleet health: per-replica state machine + shed/retry counters.
        200 while at least one replica is routable ("ok"/"degraded"),
        503 with Retry-After when the whole fleet is quarantined — load
        balancers and the traffic generator back off on exactly this.
        Off the event loop: under --fleet subprocess this does worker
        RPCs (in-process it is in-memory reads; to_thread is cheap)."""
        snap = await asyncio.to_thread(self.group.health_snapshot)
        if snap["status"] == "unavailable":
            return web.json_response(
                snap, status=503, headers=self._retry_after_headers(
                    self.cfg.server.retry_after_s))
        return web.json_response(snap)

    async def handle_version(self, request: web.Request) -> web.Response:
        from tpu_inference import __version__

        return web.json_response({"version": __version__})

    def _parameter_size(self) -> str:
        """Ollama-shaped parameter_size ("8.0B", "124.4M") computed from
        the actual parameter count, not the config name (ADVICE r5)."""
        n = self.engine.n_params
        for div, suffix in ((1e9, "B"), (1e6, "M"), (1e3, "K")):
            if n >= div:
                return f"{n / div:.1f}{suffix}"
        return str(n)

    def _quantization_level(self) -> str:
        """Ollama quantization_level vocabulary ("Q8_0"/"Q4_0"-style;
        unquantized models report the serving dtype, F16/BF16/F32)."""
        q = {"int8": "Q8_0", "int4": "Q4_0"}.get(self.cfg.engine.quant)
        if q is not None:
            return q
        import jax.numpy as jnp
        dtype = self.cfg.model.dtype
        return {jnp.bfloat16: "BF16", jnp.float16: "F16"}.get(dtype, "F32")

    async def handle_tags(self, request: web.Request) -> web.Response:
        return web.json_response({"models": [{
            "name": self.cfg.server.model_name,
            "model": self.cfg.server.model_name,
            "details": {"family": self.cfg.model.family,
                        "parameter_size": self._parameter_size(),
                        "quantization_level": self._quantization_level()},
        }]})

    async def handle_ps(self, request: web.Request) -> web.Response:
        """Ollama GET /api/ps: the loaded ("running") models. One entry —
        this server loads its model at boot and never unloads it, so
        ``expires_at`` is the zero time (Ollama's "never"). ``size`` is
        ONE model copy (Ollama semantics — ADVICE r5); the dp replica
        count is exposed separately so fleet HBM is size * replicas."""
        mc = self.cfg.model
        size = int(self.engine.weight_bytes)
        return web.json_response({"models": [{
            "name": self.cfg.server.model_name,
            "model": self.cfg.server.model_name,
            "size": size,
            "size_vram": size,     # weights live in HBM, nothing on host
            "replicas": len(self.group.engines),   # additive field: dp
            "details": {"family": mc.family,
                        "parameter_size": self._parameter_size(),
                        "quantization_level": self._quantization_level()},
            "expires_at": "0001-01-01T00:00:00Z",
        }]})

    async def handle_show(self, request: web.Request) -> web.Response:
        """Ollama /api/show: model card for clients that introspect before
        generating. Serves the architecture + serving knobs of the one
        loaded model regardless of the requested name (single-model
        server, like `ollama show` on a single-model host)."""
        mc, ec = self.cfg.model, self.cfg.engine
        return web.json_response({
            "modelfile": "",
            "details": {"family": mc.family, "format": "safetensors",
                        "parameter_size": self._parameter_size(),
                        "quantization_level": self._quantization_level()},
            "model_info": {
                "general.architecture": mc.family,
                "general.parameter_count": self.engine.n_params,
                f"{mc.family}.context_length": ec.max_context,
                f"{mc.family}.embedding_length": mc.d_model,
                f"{mc.family}.block_count": mc.n_layers,
                # Passes of the stack a token runs (a looped model).
                f"{mc.family}.loop_steps": mc.loop_steps,
                f"{mc.family}.attention.head_count": mc.n_heads,
                f"{mc.family}.attention.head_count_kv": mc.n_kv_heads,
                f"{mc.family}.vocab_size": mc.vocab_size,
                # Resolved backend (not the "auto" sentinel) — matches
                # what /metrics reports.
                "serving.attn_backend": self.engine.attn_backend,
                "serving.kv_quant": ec.kv_quant,
                # SWA composition rules actually in effect (README
                # "Sliding-window models"): operators can confirm them
                # here instead of grepping the boot log.
                f"{mc.family}.attention.sliding_window":
                    mc.sliding_window or 0,
                "serving.swa_eviction": self.engine.swa_evict,
                "serving.prefix_cache": self.engine.prefix_cache is not None,
            },
        })

    async def handle_embeddings(self, request: web.Request) -> web.Response:
        """Ollama /api/embeddings ({"prompt": str} -> {"embedding": [..]})
        and /api/embed ({"input": str | [str]} -> {"embeddings": [[..]]}).
        Mean-pooled final hidden states from the loaded model. Runs in a
        worker thread so compile/forward never stalls the event loop."""
        # Same fault-injection gate as generate/chat: embedding clients
        # get exercised against failures too (previously only
        # /api/generate was chaos-gated).
        await self._chaos_gate()
        try:
            body = await request.json()
            assert isinstance(body, dict)
        except (json.JSONDecodeError, UnicodeDecodeError, AssertionError):
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "invalid JSON body"}), content_type="application/json")
        # Shape is keyed on the ROUTE (not on which keys the client sent):
        # /api/embeddings takes a single "prompt" string and returns
        # {"embedding"}; /api/embed takes "input" (str or list) and
        # returns {"model", "embeddings"}.
        legacy = request.path.endswith("/embeddings")
        if legacy:
            texts = body.get("prompt")
            if not isinstance(texts, str):
                raise web.HTTPBadRequest(text=json.dumps(
                    {"error": "missing 'prompt' string"}),
                    content_type="application/json")
            texts = [texts]
        else:
            texts = body.get("input")
            if isinstance(texts, str):
                texts = [texts]
            if (not isinstance(texts, list) or not texts
                    or not all(isinstance(t, str) for t in texts)):
                raise web.HTTPBadRequest(text=json.dumps(
                    {"error": "missing 'input' string or list of strings"}),
                    content_type="application/json")

        def compute():
            ids = [self.tokenizer.encode(t) for t in texts]
            return self.group.embed_many(ids).tolist()

        try:
            vecs = await asyncio.to_thread(compute)
        except FleetUnavailable as e:
            raise web.HTTPServiceUnavailable(
                text=json.dumps({"error": str(e)}),
                content_type="application/json",
                headers=self._retry_after_headers(e.retry_after_s))
        if legacy:
            return web.json_response({"embedding": vecs[0]})
        return web.json_response({"model": self.cfg.server.model_name,
                                  "embeddings": vecs})

    async def handle_metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition (the default — scrapeable by any
        standard collector, per-replica labels under dp>1); the legacy
        JSON snapshot is preserved under ``?format=json`` (which also
        carries the diffable "phases" histograms the bench scrapes)."""
        # to_thread: the subprocess fleet scrapes each worker over RPC —
        # a slow worker must stall this scrape, not the whole server.
        if request.query.get("format") == "json":
            return web.json_response(
                await asyncio.to_thread(self.group.stats_snapshot))
        return web.Response(
            text=await asyncio.to_thread(self.group.prometheus_text),
            headers={"Content-Type": telemetry.PROMETHEUS_CONTENT_TYPE})

    async def handle_debug_requests(self, request: web.Request
                                    ) -> web.Response:
        """Per-request event timelines for the last <=256 finished
        requests: queue wait, prefill, decode, TPOT (SURVEY.md §5)."""
        try:
            n = int(request.query.get("n", 50))
        except ValueError:
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "'n' must be an integer"}),
                content_type="application/json")
        if n <= 0:
            return web.json_response([])
        return web.json_response(
            await asyncio.to_thread(self.group.recent_snapshot, n))

    async def handle_debug_steps(self, request: web.Request
                                 ) -> web.Response:
        """Step-ledger roofline attribution (README "Performance
        attribution"): per-replica + fleet-merged bottleneck verdicts
        per step kind.

        No parameters: the trailing 60 s. ``since`` / ``until`` (unix
        seconds) choose the interval instead; ``records=1`` adds the
        interval's per-dispatch ledger records to each replica's
        report."""
        q = request.query
        try:
            since = float(q["since"]) if "since" in q else None
            until = float(q["until"]) if "until" in q else None
        except ValueError:
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "'since' and 'until' are unix seconds"}),
                content_type="application/json")
        return web.json_response(await asyncio.to_thread(
            self.group.steps_snapshot, since=since, until=until,
            records=q.get("records") == "1"))

    async def handle_debug_blackbox(self, request: web.Request
                                    ) -> web.Response:
        """Crash flight-recorder capture index: every capture under the
        operator's --blackbox-dir, newest first — including those left
        behind by dead (kill -9'd) worker incarnations."""
        return web.json_response(
            await asyncio.to_thread(self.group.blackbox_index))

    async def handle_debug_trace(self, request: web.Request
                                 ) -> web.Response:
        """Distributed request traces (README "Observability").

        ``GET /debug/trace?id=<trace_id>`` returns one request's
        assembled cross-process span tree (router + every worker that
        served an attempt/handoff under one trace id);
        ``GET /debug/trace?format=chrome`` renders the recent-request
        ring as Chrome trace-event JSON — one pid per replica, router
        as pid 0 — loadable at ui.perfetto.dev or chrome://tracing."""
        if request.query.get("format") == "chrome":
            try:
                n = int(request.query.get("n", 128))
            except ValueError:
                raise web.HTTPBadRequest(text=json.dumps(
                    {"error": "'n' must be an integer"}),
                    content_type="application/json")
            return web.json_response(
                await asyncio.to_thread(self.group.trace_chrome, n))
        tid = (request.query.get("id") or "").strip()
        if not tid:
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "pass ?id=<trace_id> or ?format=chrome"}),
                content_type="application/json")
        snap = await asyncio.to_thread(self.group.trace_snapshot, tid)
        if snap is None:
            raise web.HTTPNotFound(text=json.dumps(
                {"error": f"no trace {tid!r} in the recent ring"}),
                content_type="application/json")
        return web.json_response(snap)

    async def handle_profile(self, request: web.Request) -> web.Response:
        """On-demand jax.profiler capture (TensorBoard / Perfetto).

        POST {"seconds": N, "replica": i} captures a device profile on
        the chosen replica for N seconds while it keeps serving (the
        subprocess fleet forwards over the profile RPC; the worker
        writes the trace dir and returns its path). The legacy
        {"action": "start"} / {"action": "stop"} pair still profiles
        this process. Traces always land under
        ServerConfig.profile_dir — the client cannot choose a
        filesystem path.
        """
        import jax

        try:
            body = await request.json()
            assert isinstance(body, dict)
        except (json.JSONDecodeError, UnicodeDecodeError, AssertionError):
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "body must be a JSON object"}),
                content_type="application/json")
        if body.get("seconds") is not None:
            try:
                seconds = float(body["seconds"])
                replica = int(body.get("replica", 0))
                if not (0 < seconds <= 60):
                    raise ValueError("'seconds' must be in (0, 60]")
                if not (0 <= replica < len(self.group.engines)):
                    raise ValueError(f"no replica {replica}")
            except (TypeError, ValueError) as e:
                raise web.HTTPBadRequest(text=json.dumps(
                    {"error": str(e)}), content_type="application/json")
            try:
                result = await asyncio.to_thread(
                    self.group.capture_profile, replica, seconds)
            except Exception as e:  # noqa: BLE001 — worker-side failure
                return web.json_response({"error": str(e)}, status=503)
            return web.json_response({"status": "captured", **result})
        action = body.get("action")
        if action == "start":
            trace_dir = self.cfg.server.profile_dir
            try:
                jax.profiler.start_trace(trace_dir)
            except RuntimeError as e:     # already started
                return web.json_response({"error": str(e)}, status=409)
            telemetry.set_profile_capturing(True)
            self._profile_dir = trace_dir
            return web.json_response({"status": "tracing",
                                      "dir": trace_dir})
        if action == "stop":
            telemetry.set_profile_capturing(False)
            try:
                jax.profiler.stop_trace()
            except RuntimeError as e:
                return web.json_response({"error": str(e)}, status=409)
            return web.json_response(
                {"status": "stopped",
                 "dir": getattr(self, "_profile_dir", None)})
        raise web.HTTPBadRequest(text=json.dumps(
            {"error": "action must be 'start' or 'stop'"}),
            content_type="application/json")

    async def _chaos_gate(self) -> None:
        """HTTP-level fault injection for harness-resilience testing (off
        unless ServerConfig.chaos_* set; SURVEY.md §5). Applied to
        generate, chat, AND embed — every client type gets exercised.
        The engine-level counterpart (EngineConfig.chaos_step_*) injects
        below the router instead, exercising supervision itself."""
        scfg = self.cfg.server
        if scfg.chaos_delay_s > 0:
            await asyncio.sleep(random.uniform(0, scfg.chaos_delay_s))
        if scfg.chaos_failure_rate > 0:
            if random.random() < scfg.chaos_failure_rate:
                raise web.HTTPServiceUnavailable(text=json.dumps(
                    {"error": "chaos: injected failure"}),
                    content_type="application/json")

    async def handle_chaos(self, request: web.Request) -> web.Response:
        """Arm/disarm fault injection at runtime: ``POST {"replica": i |
        null, "step_failure_rate": p, "step_wedge_s": s,
        "page_pressure": n}`` — null replica applies to all. The
        subprocess fleet additionally takes ``{"replica": i, "kill":
        "kill9" | "sigterm"}`` — the REAL out-of-process failure modes
        (SIGKILL a worker mid-decode; SIGTERM = graceful drain with KV
        migration) the in-process chaos_step_wedge_s only simulates.
        Returns the per-replica settings now in effect. Debug-only
        (with /debug/requests), so chaos cannot be armed on a
        production endpoint that didn't opt in."""
        try:
            body = await request.json()
            assert isinstance(body, dict)
        except (json.JSONDecodeError, UnicodeDecodeError, AssertionError):
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "body must be a JSON object"}),
                content_type="application/json")
        try:
            # Both fleet backends implement apply_chaos; process-level
            # kill verbs are a usage error on the in-process one.
            result = await asyncio.to_thread(self.group.apply_chaos, body)
        except (IndexError, TypeError, ValueError, KeyError) as e:
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": f"invalid chaos spec: {e}"}),
                content_type="application/json")
        return web.json_response(result)

    async def handle_rollout(self, request: web.Request) -> web.Response:
        """Zero-downtime rolling upgrade (README "Elastic fleet"):
        ``POST /debug/rollout`` replaces every worker one at a time
        under live traffic — spawn successor, drain-and-migrate the
        predecessor's in-flight sequences, retire it. Subprocess fleet
        only (the in-process group has no worker processes to roll).
        409 when a rollout is already running; debug-only so a
        production endpoint can't be rolled by an anonymous POST."""
        roll = getattr(self.group, "rollout", None)
        if roll is None:
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "rolling upgrades need --fleet subprocess"}),
                content_type="application/json")
        try:
            result = await asyncio.to_thread(roll)
        except ValueError as e:
            raise web.HTTPConflict(text=json.dumps(
                {"error": str(e)}), content_type="application/json")
        return web.json_response(result)

    async def handle_chat(self, request: web.Request) -> web.StreamResponse:
        """Ollama ``/api/chat``: messages-based wrapper over the same
        engine path (the reference's notebooks drive this via ChatOllama —
        reference notebooks/request_demo.ipynb cell 4d5cf82f). Messages
        render through the checkpoint's own chat template when the
        tokenizer has one, else flatten to a role-prefix transcript;
        responses use the ``message`` record shape instead of
        ``response``."""
        await self._chaos_gate()
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "invalid JSON body"}), content_type="application/json")
        msgs = body.get("messages")
        if msgs == []:
            # Ollama load/ping contract, chat flavor: an empty messages
            # array preloads the model and acks immediately (mirrors the
            # empty-prompt /api/generate probe).
            return web.json_response({
                "model": body.get("model") or self.cfg.server.model_name,
                "created_at": _now_iso(),
                "message": {"role": "assistant", "content": ""},
                "done": True,
                "done_reason": "load",
            })
        if (not isinstance(msgs, list) or not msgs
                or not all(isinstance(m, dict) and "content" in m
                           for m in msgs)):
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "missing 'messages'"}),
                content_type="application/json")
        # Prefer the checkpoint's own chat template (instruct models are
        # trained on their specific format); fall back to a role-prefix
        # transcript for template-less tokenizers (byte, bare BPE).
        prompt = None
        if hasattr(self.tokenizer, "apply_chat_template"):
            prompt = self.tokenizer.apply_chat_template(
                [{"role": m.get("role", "user"), "content": m["content"]}
                 for m in msgs])
        if prompt is None:
            prompt = "\n".join(f"{m.get('role', 'user')}: {m['content']}"
                               for m in msgs) + "\nassistant:"
        body = dict(body)
        body["prompt"] = prompt
        return await self._generate_impl(request, body, chat=True)

    async def handle_generate(self, request: web.Request) -> web.StreamResponse:
        # Gate here, not in _generate_impl: handle_chat gates itself, and
        # gating the shared impl too would double the chat failure rate.
        await self._chaos_gate()
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "invalid JSON body"}), content_type="application/json")
        return await self._generate_impl(request, body)

    async def _generate_impl(self, request: web.Request, body: dict,
                             chat: bool = False) -> web.StreamResponse:
        recv_t = time.perf_counter()
        prompt = body.get("prompt")
        if not isinstance(prompt, str):
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "missing 'prompt'"}), content_type="application/json")
        if not chat and prompt == "" and not body.get("context"):
            # Ollama load/ping contract: an empty generate request warms
            # the model and returns immediately (the ollama CLI and
            # client libraries use this as a liveness/load probe). The
            # model here is always resident, so it's a pure ack.
            return web.json_response({
                "model": body.get("model") or self.cfg.server.model_name,
                "created_at": _now_iso(),
                "response": "",
                "done": True,
                "done_reason": "load",
            })

        opts = body.get("options") or {}
        if not isinstance(opts, dict):
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": "'options' must be an object"}),
                content_type="application/json")
        ecfg = self.cfg.engine
        try:
            temperature = float(opts.get(
                "temperature", body.get("temperature", ecfg.temperature)))
            max_tokens = int(opts.get(
                "num_predict", body.get("max_tokens", ecfg.max_new_tokens)))
            max_tokens = max(1, min(max_tokens, ecfg.max_context - 1))
            top_p = float(opts.get("top_p", body.get("top_p", ecfg.top_p)))
            top_k = opts.get("top_k", body.get("top_k"))
            top_k = int(top_k) if top_k is not None else None
            seed = opts.get("seed", body.get("seed"))
            seed = int(seed) if seed is not None else None
            # Documented divergence from Ollama: repeat_penalty defaults
            # to 1.0 (off) here, not Ollama's 1.1 — an inference engine
            # shouldn't silently reshape the model's distribution; send
            # options.repeat_penalty=1.1 for bug-for-bug parity. Requests
            # whose penalty options can't be honored exactly get a
            # "warnings" field in the terminal record (ADVICE r3).
            warnings: list = []
            repeat_penalty = float(opts.get("repeat_penalty", 1.0))
            if repeat_penalty <= 0:
                raise ValueError("'repeat_penalty' must be > 0")
            repeat_last_n = int(opts.get("repeat_last_n", 64))
            if repeat_penalty != 1.0:
                # With the penalty off, clamping/ignoring its window is
                # a no-op — warn only when sampling actually diverges.
                # -1 is Ollama's "whole context"; the engine clamps both
                # cases to its static window (engine._penalty_arrays).
                if repeat_last_n > PENALTY_WINDOW or repeat_last_n < 0:
                    warnings.append(
                        f"repeat_last_n={repeat_last_n} clamped to the "
                        f"static penalty window {PENALTY_WINDOW}")
            stop = opts.get("stop", body.get("stop"))
            if stop is None:
                stop = []
            elif isinstance(stop, str):
                stop = [stop]
            elif not (isinstance(stop, list)
                      and all(isinstance(s, str) for s in stop)):
                raise ValueError("'stop' must be a string or list of strings")
            stop = [s for s in stop if s]
        except (TypeError, ValueError) as e:
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": f"invalid sampling options: {e}"}),
                content_type="application/json")
        stream = bool(body.get("stream", True))
        model_name = body.get("model") or self.cfg.server.model_name

        prompt_ids = self.tokenizer.encode(prompt)
        # Stateful continuation (Ollama /api/generate "context"): a prior
        # response's context token array prepends to this prompt — the
        # reference's captured wire format round-trips exactly these ids
        # (its terminal records carry them). With the prefix cache on,
        # the continued context's KV pages are reused, not recomputed.
        # Generate-only, like Ollama: /api/chat never emits a context, so
        # honoring one there would prepend stale ids into the transcript.
        ctx_ids = body.get("context") if not chat else None
        if ctx_ids is not None:
            # bool is an int subclass; true/false are not token ids.
            if not (isinstance(ctx_ids, list)
                    and all(isinstance(t, int) and not isinstance(t, bool)
                            and 0 <= t for t in ctx_ids)):
                raise web.HTTPBadRequest(text=json.dumps(
                    {"error": "'context' must be a list of token ids"}),
                    content_type="application/json")
            # Validate against the MODEL vocab: the XLA embedding gather
            # clamps out-of-range ids silently, so an id the model can't
            # embed must 400 here, not "work" with a wrong embedding
            # (ADVICE r3). The server's own context arrays only contain
            # ids the model produced or the tokenizer encoded, both
            # < model vocab in a consistent deployment.
            vocab = self.cfg.model.vocab_size
            if any(t >= vocab for t in ctx_ids):
                raise web.HTTPBadRequest(text=json.dumps(
                    {"error": f"'context' token id out of range "
                              f"(vocab_size={vocab})"}),
                    content_type="application/json")
        if ctx_ids:
            # The encoder's BOS belongs at the very start, not mid-stream.
            if (prompt_ids and self.tokenizer.bos_token_id is not None
                    and prompt_ids[0] == self.tokenizer.bos_token_id):
                prompt_ids = prompt_ids[1:]
            prompt_ids = list(ctx_ids) + prompt_ids
        rid = next(self._ids)
        # End-to-end request tracing: honor a client-supplied
        # X-Request-Id (sanitized: printable, capped) or mint one. It
        # rides the Sequence through the scheduler/engine into the
        # structured logs, the /debug/requests span, the response's
        # X-Request-Id header and the terminal record's request_id.
        trace_id = (request.headers.get("X-Request-Id") or "").strip()
        trace_id = ("".join(c for c in trace_id if c.isprintable())[:64]
                    or uuid.uuid4().hex[:16])
        # Priority class (README "Elastic fleet"): X-Priority header
        # (interactive | batch | background), else the server default.
        # An unknown name is a 400 — silently ranking a typo'd class as
        # interactive would defeat the batch lane it asked for.
        pcls = (request.headers.get("X-Priority") or "").strip().lower()
        if not pcls:
            pcls = self.cfg.server.default_class
        if pcls not in PRIORITY_CLASSES:
            raise web.HTTPBadRequest(text=json.dumps(
                {"error": f"unknown X-Priority {pcls!r} (expected one "
                          f"of {', '.join(PRIORITY_CLASSES)})"}),
                content_type="application/json")
        seq = Sequence(request_id=rid, prompt_tokens=prompt_ids,
                       max_new_tokens=max_tokens, temperature=temperature,
                       top_p=top_p, top_k=top_k, seed=seed,
                       repeat_penalty=repeat_penalty,
                       repeat_last_n=repeat_last_n,
                       eos_token_id=(None if self.cfg.server.ignore_eos
                                     else self.tokenizer.eos_token_id),
                       trace_id=trace_id, priority_class=pcls)
        telemetry.log_event(
            "request_received", level="info", request_id=trace_id,
            route="chat" if chat else "generate",
            prompt_tokens=len(prompt_ids), max_tokens=max_tokens,
            priority_class=pcls, stream=stream)

        outbox = self._outbox
        queue: asyncio.Queue = asyncio.Queue()

        def on_token(s: Sequence, tok: int) -> None:
            outbox.put(queue, ("token", tok))

        def on_finish(s: Sequence) -> None:
            outbox.put(queue, ("finish", s))

        try:
            # to_thread: under --fleet subprocess, submit does routing
            # peeks + the submit RPC over worker sockets — blocking I/O
            # that must not freeze the event loop (and so every other
            # stream) behind one slow worker. In-process submit is
            # thread-safe by design (callbacks already arrive from
            # engine threads).
            await asyncio.to_thread(self.group.submit, seq, on_token,
                                    on_finish)
        except FleetSaturated as e:
            # Admission control: reject NOW with a backoff hint instead
            # of queueing until request_timeout_s.
            raise web.HTTPTooManyRequests(
                text=json.dumps({"error": str(e)}),
                content_type="application/json",
                headers=self._retry_after_headers(e.retry_after_s))
        except FleetUnavailable as e:
            raise web.HTTPServiceUnavailable(
                text=json.dumps({"error": str(e)}),
                content_type="application/json",
                headers=self._retry_after_headers(e.retry_after_s))
        try:
            if stream:
                return await self._stream_response(request, queue, seq,
                                                   model_name, recv_t, chat,
                                                   stop, warnings)
            return await self._unary_response(request, queue, seq, model_name,
                                              recv_t, chat, stop, warnings)
        except asyncio.TimeoutError:
            # Request exceeded request_timeout_s: free the slot and pages.
            self.group.cancel(rid)
            raise web.HTTPGatewayTimeout(text=json.dumps(
                {"error": "request timed out"}), content_type="application/json")
        except (asyncio.CancelledError, ConnectionResetError):
            self.group.cancel(rid)
            raise

    # ------------------------------------------------------------- helpers

    @staticmethod
    def _token_line(model_name: str, chunk: str, chat: bool) -> dict:
        line = {"model": model_name, "created_at": _now_iso(), "done": False}
        if chat:
            line["message"] = {"role": "assistant", "content": chunk}
        else:
            line["response"] = chunk
        return line

    def _final_record(self, seq: Sequence, model_name: str,
                      recv_t: float, chat: bool = False,
                      warnings: Optional[list] = None) -> dict:
        now = time.perf_counter()
        prompt_eval_ns = max(0, int((seq.first_token_time - seq.prefill_start)
                                    * 1e9)) if seq.first_token_time else 0
        finish = seq.finish_time or now
        eval_ns = max(0, int((finish - (seq.first_token_time or finish)) * 1e9))
        rec = {
            "model": model_name,
            "created_at": _now_iso(),
            # Propagated trace id (additive field): lets a client join
            # its response to server-side spans/logs without headers.
            "request_id": seq.trace_id,
            "response": "",
            "done": True,
            "done_reason": seq.finish_reason or "stop",
            "context": list(seq.prompt_tokens) + list(seq.generated),
            "total_duration": int((now - recv_t) * 1e9),
            "load_duration": self.load_duration_ns,
            "prompt_eval_count": len(seq.prompt_tokens),
            "prompt_eval_duration": prompt_eval_ns,
            "eval_count": len(seq.generated),
            "eval_duration": eval_ns,
        }
        if warnings:
            # Options accepted but not honored exactly (clamped/ignored);
            # additive field, absent when everything applied as sent.
            rec["warnings"] = list(warnings)
        if chat:
            # Ollama chat records use `message` and omit `context`.
            del rec["response"], rec["context"]
            rec["message"] = {"role": "assistant", "content": ""}
        return rec

    async def _stream_response(self, request: web.Request, queue: asyncio.Queue,
                               seq: Sequence, model_name: str,
                               recv_t: float, chat: bool = False,
                               stop: Optional[list] = None,
                               warnings: Optional[list] = None
                               ) -> web.StreamResponse:
        resp = web.StreamResponse(status=200, headers={
            "Content-Type": "application/x-ndjson",
            "X-Request-Id": seq.trace_id})
        resp.enable_chunked_encoding()
        decoder = IncrementalDecoder(self.tokenizer,
                                     prompt_tail=seq.prompt_tokens[-8:])
        matcher = StopMatcher(stop or [])
        consumed: list = []            # token ids delivered to THIS handler
        prepared = False
        timeout = self.cfg.server.request_timeout_s

        async def write_line(text: str) -> None:
            await resp.write(json.dumps(self._token_line(
                model_name, text, chat)).encode() + b"\n")

        async def finish(stopped: bool, fseq: Sequence = seq
                         ) -> web.StreamResponse:
            # fseq is the sequence the finish event delivered — after a
            # failover it is the resubmitted attempt, which carries the
            # real tokens/timings (the closure seq is the dead first
            # attempt).
            final = self._final_record(fseq, model_name, recv_t, chat,
                                       warnings)
            if stopped:
                # The engine thread may still be appending to
                # seq.generated until the cancel lands; report only what
                # this handler consumed so context/eval_count are
                # deterministic and never include post-stop tokens.
                final["done_reason"] = "stop"
                final["eval_count"] = len(consumed)
                if "context" in final:
                    final["context"] = list(seq.prompt_tokens) + consumed
            await resp.write(json.dumps(final).encode() + b"\n")
            await resp.write_eof()
            return resp

        while True:
            kind, payload = await asyncio.wait_for(queue.get(), timeout)
            if kind == "token":
                consumed.append(payload)
                emit, stopped = matcher.push(decoder.push(payload))
                if not prepared:
                    # First token ready -> now send headers (TTFT contract).
                    await resp.prepare(request)
                    prepared = True
                if stopped:
                    # A stop sequence completed: cut the stream here and
                    # cancel the rest of the generation (never emit the
                    # stop string itself).
                    if emit:
                        await write_line(emit)
                    self.group.cancel(seq.request_id)
                    return await finish(stopped=True)
                await write_line(emit)
            else:
                if payload.finish_reason == "poison" and not prepared:
                    # Terminal quarantine: this request crashed/wedged
                    # poison_max_workers distinct workers. A structured
                    # 500 WITHOUT Retry-After — resubmitting it would
                    # only burn more of the fleet (README "Failure
                    # model").
                    raise web.HTTPInternalServerError(
                        text=json.dumps({
                            "error": "request quarantined as poison",
                            "request_id": seq.trace_id}),
                        content_type="application/json")
                if (payload.finish_reason in ("error", "unavailable")
                        and not consumed and not prepared):
                    # The replica died (or was quarantined) before a
                    # single token left the server and the failover
                    # budget is spent: headers are unsent, so fail as a
                    # clean retryable 503 instead of a 200 whose terminal
                    # record buries done_reason="error".
                    raise web.HTTPServiceUnavailable(
                        text=json.dumps(
                            {"error": "replica failure before first token"}),
                        content_type="application/json",
                        headers=self._retry_after_headers(
                            self.cfg.server.retry_after_s))
                if not prepared:
                    await resp.prepare(request)
                    prepared = True
                tail, stopped = matcher.push(decoder.flush())
                if not stopped:
                    tail += matcher.flush()
                if tail:
                    await write_line(tail)
                return await finish(stopped, fseq=payload)

    async def _unary_response(self, request: web.Request, queue: asyncio.Queue,
                              seq: Sequence, model_name: str,
                              recv_t: float, chat: bool = False,
                              stop: Optional[list] = None,
                              warnings: Optional[list] = None
                              ) -> web.Response:
        decoder = IncrementalDecoder(self.tokenizer,
                                     prompt_tail=seq.prompt_tokens[-8:])
        matcher = StopMatcher(stop or [])
        parts: list = []
        consumed: list = []            # token ids delivered to THIS handler
        timeout = self.cfg.server.request_timeout_s

        def respond(payload, stopped: bool) -> web.Response:
            final = self._final_record(payload, model_name, recv_t, chat,
                                       warnings)
            if stopped:
                # Snapshot only handler-consumed tokens (the engine thread
                # may append more before the cancel lands).
                final["done_reason"] = "stop"
                final["eval_count"] = len(consumed)
                if "context" in final:
                    final["context"] = list(seq.prompt_tokens) + consumed
            text = "".join(parts)
            if chat:
                final["message"] = {"role": "assistant", "content": text}
            else:
                final["response"] = text
            return web.json_response(
                final, headers={"X-Request-Id": seq.trace_id})

        while True:
            kind, payload = await asyncio.wait_for(queue.get(), timeout)
            if kind == "token":
                consumed.append(payload)
                emit, stopped = matcher.push(decoder.push(payload))
                parts.append(emit)
                if stopped:
                    self.group.cancel(seq.request_id)
                    return respond(seq, stopped=True)
            else:
                if payload.finish_reason == "poison":
                    # Terminal quarantine (mirrors the streaming path):
                    # structured 500, no Retry-After — the request
                    # itself is the fault, not the fleet's state.
                    raise web.HTTPInternalServerError(
                        text=json.dumps({
                            "error": "request quarantined as poison",
                            "request_id": seq.trace_id}),
                        content_type="application/json")
                if (payload.finish_reason in ("error", "unavailable")
                        and not consumed):
                    # Replica failure before any token, failover budget
                    # spent: clean retryable 503 (mirrors the streaming
                    # path).
                    raise web.HTTPServiceUnavailable(
                        text=json.dumps(
                            {"error": "replica failure before first token"}),
                        content_type="application/json",
                        headers=self._retry_after_headers(
                            self.cfg.server.retry_after_s))
                tail, stopped = matcher.push(decoder.flush())
                parts.append(tail)
                if not stopped:
                    parts.append(matcher.flush())
                return respond(payload, stopped)


def build_server(model: str = "tiny-llama", tokenizer: str = "byte",
                 checkpoint: Optional[str] = None, warmup: bool = True,
                 tp: int = 1, sp: int = 1, dp: int = 1,
                 enable_debug: bool = False,
                 server_overrides: Optional[dict] = None,
                 platform: Optional[str] = None,
                 sizing: Optional[dict] = None,
                 **engine_overrides) -> InferenceServer:
    """Convenience constructor used by CLI, tests, and benchmarks.

    ``model`` accepts a preset name, a path to a HF
    checkpoint directory (architecture read from its config.json), or
    "auto" with ``checkpoint`` set. ``tokenizer="auto"`` uses the
    checkpoint directory's tokenizer files when present, else bytes.
    ``server_overrides`` are extra ServerConfig fields (supervision
    knobs: step_watchdog_s, admission_queue_depth, ...). ``platform``
    and ``sizing``: see build_engine_group.
    """
    import os

    from tpu_inference.config import EngineConfig, ParallelConfig, ServerConfig

    # Single model-resolution rule, shared with the pre-boot auto-sizing
    # path so the model that gets sized is the model that boots.
    from tpu_inference.engine.autosize import resolve_model_and_checkpoint

    model_cfg, checkpoint = resolve_model_and_checkpoint(model, checkpoint)
    if tokenizer == "auto":
        has_tok = checkpoint and any(
            os.path.exists(os.path.join(checkpoint, f))
            for f in ("tokenizer.json", "tokenizer_config.json"))
        tokenizer = checkpoint if has_tok else "byte"
    engine_cfg = EngineConfig(**engine_overrides) if engine_overrides else EngineConfig()
    cfg = FrameworkConfig(model=model_cfg, engine=engine_cfg,
                          parallel=ParallelConfig(dp=dp, tp=tp, sp=sp),
                          server=ServerConfig(model_name=model,
                                              tokenizer=tokenizer,
                                              warmup=warmup,
                                              enable_debug=enable_debug,
                                              **(server_overrides or {})),
                          checkpoint_path=checkpoint)

    def load(mesh):
        """(mesh | None) -> params: the checkpoint streams per-replica so
        each replica's leaves land directly in ITS device layout — never an
        unsharded copy on host or device 0 (host-OOM at 70B scale). With
        quant on, each matmul weight quantizes as it lands, so peak device
        memory stays ~int8-model-sized (never full bf16 + int8)."""
        from tpu_inference.models import weights

        shardings = None
        if mesh is not None:
            from tpu_inference.parallel import shardings as shd

            shardings = shd.param_shardings(model_cfg, mesh)
        return weights.load_checkpoint(model_cfg, checkpoint,
                                       shardings=shardings,
                                       quant=cfg.engine.quant)

    t0 = time.perf_counter()
    group = build_engine_group(
        cfg,
        load_params=load if checkpoint else None,
        platform=platform, sizing=sizing)
    load_ns = int((time.perf_counter() - t0) * 1e9)
    return InferenceServer(cfg, group=group, load_duration_ns=load_ns)
