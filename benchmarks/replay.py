"""End-to-end replay benchmark: BurstGPT trace -> in-process TPU server.

The headline metric harness (BASELINE.md: "BurstGPT replay — tokens/s/chip,
p50/p99 TTFT+TPOT"). Boots the Ollama-protocol server in a background
thread, replays a trace through the vendored traffic generator (the
reference's own benchmark client, unchanged protocol), and summarizes the
per-request metrics the harness records.

Usage:
    python benchmarks/replay.py --model tiny-llama --max-trace 20
    python benchmarks/replay.py --model llama-3-8b --tp 8 \
        --trace data/BurstGPT_1.csv --out benchmarks/results/8b_tp8.json

Timing semantics match the reference client (SURVEY.md §2c): TTFT =
first streamed chunk relative to request start; headers are withheld by
the server until the first token, so header-arrival ≈ TTFT.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import re
import socket
import sys
import threading
import time
import urllib.request
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _percentiles(xs, ps=(50, 99)):
    if not xs:
        return {f"p{p}": None for p in ps}
    return {f"p{p}": round(float(np.percentile(xs, p)), 4) for p in ps}


def scrape_metrics(port: int, fmt: str = None) -> tuple:
    """GET /metrics over real HTTP (the same path an external Prometheus
    collector takes — NOT an in-process shortcut, so this lane proves
    the scrape path end-to-end). Returns (body, content_type)."""
    url = f"http://127.0.0.1:{port}/metrics"
    if fmt == "json":
        url += "?format=json"
    with urllib.request.urlopen(url, timeout=60) as r:
        return r.read().decode(), r.headers.get("Content-Type", "")


def step_attribution(port: int) -> dict:
    """GET /debug/steps compressed into the artifact's attribution
    block (README "Performance attribution"): the fleet-merged
    bottleneck verdict per step kind, the per-rung occupancy histogram
    and the MFU gauge — so every committed row explains WHY it ran at
    the throughput it did."""
    url = f"http://127.0.0.1:{port}/debug/steps"
    with urllib.request.urlopen(url, timeout=60) as r:
        snap = json.loads(r.read().decode())
    fleet = snap.get("fleet") or {}
    if not fleet.get("enabled"):
        return {"enabled": False}
    return {
        "enabled": True,
        "records": fleet.get("records_window"),
        "verdicts": {k: v.get("verdict")
                     for k, v in (fleet.get("kinds") or {}).items()},
        "rung_occupancy": fleet.get("rung_occupancy") or {},
        "compile_events": fleet.get("compile_events"),
        "mfu": fleet.get("mfu") or {},
        "replica_verdicts": {
            rep: {k: v.get("verdict")
                  for k, v in (rr.get("kinds") or {}).items()}
            for rep, rr in (snap.get("replicas") or {}).items()
            if rr.get("enabled")},
    }


def phase_breakdown(before: dict, after: dict) -> dict:
    """Diff two /metrics?format=json scrapes into the run window's phase
    histograms: dispatch wall vs host bubble vs queue wait (p50/p95/p99)
    plus the per-request phase sums, with a sum-check of queue + prefill
    + decode against E2E — the artifact that answers "where does the
    roofline go" without archaeology."""
    from tpu_inference import telemetry as tm

    aph = after.get("phases") or {}
    bph = before.get("phases") or {}
    out = {}
    for key in ("decode_dispatch_s", "decode_sync_s", "dispatch_bubble_s",
                "prefill_dispatch_s", "tokens_per_dispatch",
                "hybrid_dispatch_s", "decode_stall_during_prefill_s",
                "queue_wait_s",
                "prefill_phase_s", "decode_phase_s", "ttft_s", "e2e_s"):
        if key in aph:
            d = tm.diff_phase(aph[key], bph.get(key))
            out[key] = {k: d[k] for k in ("count", "sum", "p50", "p95",
                                          "p99")}
    phase_sum = sum(out.get(k, {}).get("sum") or 0.0
                    for k in ("queue_wait_s", "prefill_phase_s",
                              "decode_phase_s"))
    e2e_sum = out.get("e2e_s", {}).get("sum") or 0.0
    out["sum_check"] = {
        # queue + prefill + decode vs e2e: same timestamps on the server
        # side, so the ratio must be ~1.0 (the artifact's self-test).
        "queue_plus_prefill_plus_decode_s": round(phase_sum, 6),
        "e2e_s": round(e2e_sum, 6),
        "ratio": round(phase_sum / e2e_sum, 4) if e2e_sum else None,
    }
    return out


def summarize(metrics: dict, n_chips: int = 1) -> dict:
    """Reduce the harness's per-request dicts to the headline numbers."""
    ok = {k: m for k, m in metrics.items() if m.get("success")}
    # Client-side resilience accounting: 429/503 attempts retried with
    # backoff, and queries given up after the retry budget (shed) — the
    # shed RATE is the number the admission-mode comparison lane reads.
    retries = sum(m.get("num_retries") or 0 for m in metrics.values())
    shed = sum(1 for m in metrics.values() if m.get("shed"))
    ttft, tpot, e2e, gaps, tokens = [], [], [], [], 0
    t_first, t_last = float("inf"), 0.0
    for m in ok.values():
        start = m["request_start_time"]
        first = m["first_token_arrive_time"]
        end = m["response_end_time"]
        n_out = m.get("num_output_tokens") or 0
        if first is not None and start is not None:
            ttft.append(first - start)
        if end is not None and start is not None:
            e2e.append(end - start)
        if end is not None and first is not None and n_out > 1:
            tpot.append((end - first) / (n_out - 1))
        if m.get("max_interchunk_gap") is not None:
            gaps.append(m["max_interchunk_gap"])
        tokens += n_out
        if start is not None:
            t_first = min(t_first, start)
        if end is not None:
            t_last = max(t_last, end)
    wall = max(t_last - t_first, 1e-9)
    return {
        "requests": len(metrics),
        "succeeded": len(ok),
        "client_retries": retries,
        "shed": shed,
        "shed_rate": round(shed / max(len(metrics), 1), 4),
        "output_tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(tokens / wall, 2),
        "tokens_per_s_per_chip": round(tokens / wall / max(n_chips, 1), 2),
        "ttft_s": _percentiles(ttft),
        "tpot_s": _percentiles(tpot),
        "e2e_s": _percentiles(e2e),
        # Worst per-request stall between streamed chunks (the K-bursty
        # flush sawtooth a mean TPOT hides).
        "max_interchunk_gap_s": _percentiles(gaps),
    }


def start_server(args) -> tuple:
    """Boot the server (with warmup) on a background event loop; returns
    (port, stop_fn). Blocks until it accepts connections."""
    import jax  # noqa: F401 (import before aiohttp threads)

    from aiohttp import web

    from tpu_inference.server.http import build_server

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()

    srv = build_server(
        model=args.model, tokenizer=args.tokenizer, tp=args.tp,
        sp=args.sp, sp_attn=args.sp_attn, dp=getattr(args, "dp", 1),
        checkpoint=args.checkpoint,
        warmup=not args.no_warmup,
        max_batch_size=args.max_batch_size, num_pages=args.num_pages,
        decode_ladder=tuple(getattr(args, "decode_ladder_rungs", ()) or ()),
        stage_host_reuse=getattr(args, "stage_host_reuse", True),
        ladder_admit_headroom_pages=getattr(
            args, "ladder_admit_headroom_pages", 0),
        page_size=args.page_size, max_pages_per_seq=args.max_pages_per_seq,
        decode_steps_per_call=args.decode_steps_per_call,
        decode_pipeline_depth=args.decode_pipeline_depth,
        chunked_prefill_size=getattr(args, "chunked_prefill_size", 0),
        hybrid_prefill=getattr(args, "hybrid_prefill", False),
        step_token_budget=getattr(args, "step_token_budget", 0),
        quant=getattr(args, "quant", "none"),
        kv_quant=getattr(args, "kv_quant", "none"),
        enable_prefix_cache=getattr(args, "enable_prefix_cache", True),
        host_cache_pages=getattr(args, "host_cache_pages", 0),
        admission=getattr(args, "admission", "reserve"),
        preempt_watermark_pages=getattr(
            args, "preempt_watermark_pages", 4),
        # Rolling SLO targets (README "Observability"): feed the
        # windowed quantile gauges + breach counters the artifact and
        # the autoscaler read.
        slo_ttft_ms=getattr(args, "slo_ttft_ms", 0.0),
        slo_tpot_ms=getattr(args, "slo_tpot_ms", 0.0),
        # Debug surfaces on: the bench scrapes /debug/trace for the
        # Chrome-trace artifact (local bench server, never production).
        enable_debug=True,
        server_overrides={
            "admission_queue_depth":
                getattr(args, "admission_queue_depth", 0),
            "routing": getattr(args, "routing", "prefix_affinity"),
            "route_hit_weight": getattr(args, "route_hit_weight", 1.0),
            "route_host_hit_weight":
                getattr(args, "route_host_hit_weight", 0.5),
            # Fleet KV fabric (README "KV fabric"): shared cross-
            # replica prefix pool + warm worker boot for the
            # --compare-fabric arms.
            "fabric_cache_pages":
                getattr(args, "fabric_cache_pages", 0),
            "fabric_publish_min_pages":
                getattr(args, "fabric_publish_min_pages", 1),
            "fabric_warmboot_pages":
                getattr(args, "fabric_warmboot_pages", 64),
            "route_fabric_hit_weight":
                getattr(args, "route_fabric_hit_weight", 0.25),
            # Zero-copy KV data plane (README "KV data plane"): shm
            # arena vs through-router relay for the --compare-kv-plane
            # arms.
            "kv_plane": getattr(args, "kv_plane", "relay"),
            "shm_arena_bytes": getattr(args, "shm_arena_bytes",
                                       256 * 1024 * 1024),
            # Process fleet (README "Process fleet"): backend + worker
            # supervision knobs for the subprocess arms.
            "fleet": getattr(args, "fleet", "in-process"),
            "fleet_migrate": getattr(args, "fleet_migrate", True),
            # P/D disaggregation (README "P/D disaggregation"): per-
            # worker phase roles + shared-CPU prefill deprioritization
            # for the --compare-pd arms.
            "worker_roles": tuple(getattr(args, "worker_roles", ())
                                  or ()),
            "pd_prefill_nice": getattr(args, "pd_prefill_nice", 0),
            "worker_restart_max":
                getattr(args, "worker_restart_max", 3),
            "worker_restart_backoff_s":
                getattr(args, "worker_restart_backoff_s", 0.5),
            "drain_timeout_s": getattr(args, "drain_timeout_s", 10.0),
            # Byzantine transport (README "Failure model"): per-verb
            # RPC deadline classes for the --compare-chaos-rpc arms
            # (wedge detection cost is 3 consecutive fast deadlines).
            "rpc_deadline_fast_s":
                getattr(args, "rpc_deadline_fast_s", 10.0),
            "rpc_deadline_slow_s":
                getattr(args, "rpc_deadline_slow_s", 60.0),
            # Elastic fleet (README "Elastic fleet"): autoscaler +
            # priority-class admission for the --compare-elastic arms.
            "autoscale": getattr(args, "autoscale", False),
            "autoscale_min_replicas":
                getattr(args, "autoscale_min_replicas", 1),
            "autoscale_max_replicas":
                getattr(args, "autoscale_max_replicas", 0),
            "autoscale_breach_window_s":
                getattr(args, "autoscale_breach_window_s", 3.0),
            "autoscale_cooldown_s":
                getattr(args, "autoscale_cooldown_s", 10.0),
            "autoscale_low_watermark":
                getattr(args, "autoscale_low_watermark", 0.25),
            "autoscale_idle_window_s":
                getattr(args, "autoscale_idle_window_s", 5.0),
            "default_class": getattr(args, "default_class",
                                     "interactive"),
            "class_queue_depth":
                getattr(args, "class_queue_depth", 0)},
        ngram_window=getattr(args, "ngram_window", 3),
        num_speculative_tokens=(
            args.num_speculative_tokens
            if getattr(args, "spec_mode", None) == "ngram" else 0),
        # Smoke lane: small prefill buckets so the CPU tier-1 run
        # compiles in seconds, not minutes (a lane can pin its own —
        # compare-pd needs 256-token chunks so an in-engine prefill
        # dispatch is a VISIBLE decode stall).
        **({"prefill_buckets": (getattr(args, "prefill_buckets", None)
                                or (16, 32, 64))}
           if getattr(args, "smoke", False) else {}))
    loop = asyncio.new_event_loop()
    ready = threading.Event()
    boot_err: list = []

    def run():
        asyncio.set_event_loop(loop)
        try:
            app = srv.make_app()
            runner = web.AppRunner(app)
            loop.run_until_complete(runner.setup())
            site = web.TCPSite(runner, "127.0.0.1", port)
            loop.run_until_complete(site.start())
        except BaseException as e:  # surface boot failures immediately
            boot_err.append(e)
            ready.set()
            return
        ready.set()
        loop.run_forever()

    t = threading.Thread(target=run, name="bench-server", daemon=True)
    t.start()
    if not ready.wait(timeout=1800):
        raise TimeoutError("server failed to start (warmup hang?)")
    if boot_err:
        raise boot_err[0]

    def stop():
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=30)

    return srv, port, stop


def main() -> dict:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default="tiny-llama")
    p.add_argument("--tokenizer", default="byte")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel prefill degree")
    p.add_argument("--sp-attn", default="ring", choices=("ring", "ulysses"))
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel replicas (each its own submesh, "
                        "KV pool and scheduler; requests route per "
                        "--routing)")
    p.add_argument("--routing", default="prefix_affinity",
                   choices=("prefix_affinity", "least_loaded"),
                   help="dp replica routing policy")
    p.add_argument("--route-hit-weight", type=float, default=1.0,
                   help="prefix-affinity: routing-score pages one peeked "
                        "cache-hit page is worth")
    p.add_argument("--route-host-hit-weight", type=float, default=0.5,
                   help="prefix-affinity: routing-score pages one peeked "
                        "HOST-tier hit page is worth")
    p.add_argument("--host-cache-pages", type=int, default=0,
                   help="host-RAM KV tier capacity in pages (0 = off; "
                        "README 'Tiered KV cache')")
    p.add_argument("--num-speculative-tokens", type=int, default=4)
    p.add_argument("--spec-mode", default=None, choices=("ngram",),
                   help="'ngram' = draft-free self-drafting speculation "
                        "(README 'Speculative decoding'); default off")
    p.add_argument("--ngram-window", type=int, default=3,
                   help="ngram spec: longest suffix n-gram matched "
                        "against each sequence's history")
    p.add_argument("--trace", default="data/trace1.csv")
    p.add_argument("--data", default="data/conversations.json")
    p.add_argument("--max-trace", type=int, default=100)
    from tpu_inference.engine.autosize import int_or_auto

    p.add_argument("--max-batch-size", type=int_or_auto, default=8,
                   help="decode slots, or 'auto' (size from chip HBM — "
                        "engine/autosize.py)")
    p.add_argument("--decode-ladder", default="off",
                   help="compiled decode-graph batch ladder: 'auto' "
                        "(doubling rungs up to max-batch-size), 'off' "
                        "(one graph, legacy), or comma rungs '8,16,32'")
    p.add_argument("--num-pages", type=int_or_auto, default=512,
                   help="KV pool pages, or 'auto'")
    p.add_argument("--target-ctx", type=int, default=0,
                   help="auto sizing: expected typical context per "
                        "sequence (0 = half the per-sequence max)")
    p.add_argument("--batch-cap", type=int, default=32,
                   help="upper bound for --max-batch-size auto")
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--max-pages-per-seq", type=int, default=64)
    p.add_argument("--decode-steps-per-call", type=int, default=8)
    p.add_argument("--decode-pipeline-depth", type=int, default=1)
    p.add_argument("--chunked-prefill-size", type=int, default=0,
                   help="split prompts into chunks of this many tokens "
                        "(0 = largest prefill bucket governs)")
    p.add_argument("--hybrid-prefill", action="store_true",
                   help="fuse each prefill chunk into the decode "
                        "dispatch (hybrid steps) instead of stalling "
                        "decode lanes a chunk wall per chunk")
    p.add_argument("--step-token-budget", type=int, default=0,
                   help="hybrid steps: per-fused-dispatch token budget "
                        "(chunk tokens capped at budget minus granted "
                        "decode tokens; 0 = "
                        "uncapped)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--quant", default="none",
                   choices=("none", "int8", "int4"))
    p.add_argument("--kv-quant", default="none",
                   choices=("none", "int8", "int4"))
    p.add_argument("--platform", default="auto",
                   choices=("auto", "cpu", "tpu"),
                   help="jax platform; 'cpu' forces the CPU backend "
                        "(tp*sp virtual devices) before any computation")
    p.add_argument("--admission", default="reserve",
                   choices=("reserve", "optimistic"),
                   help="KV admission mode: worst-case reservation vs "
                        "optimistic admission with watermark preemption "
                        "+ recompute-resume")
    p.add_argument("--admission-queue-depth", type=int, default=0,
                   help="server-side 429 shed cap (0 = queue unbounded)")
    p.add_argument("--client-max-retries", type=int, default=4,
                   help="traffic-generator 429/503 retry budget per "
                        "query; give-ups are recorded as shed")
    p.add_argument("--compare-admission", action="store_true",
                   help="run the trace twice — admission=reserve then "
                        "optimistic — and commit an occupancy / "
                        "throughput / shed-rate comparison artifact")
    p.add_argument("--compare-hybrid", action="store_true",
                   help="run the workload twice — serial chunked prefill "
                        "then hybrid fused steps — and commit a decode-"
                        "stall / throughput / TTFT comparison artifact "
                        "(with --smoke: a pinned long-prompt-plus-"
                        "decoding-shorts mix)")
    p.add_argument("--compare-ladder", action="store_true",
                   help="run a pinned bursty mix three times — fixed "
                        "bs=8, the auto batch ladder, and the ladder "
                        "with host-staging reuse disabled — and commit "
                        "the ladder artifact: aggregate tok/s, per-"
                        "stream latency, outputs_sha256 byte-identity, "
                        "rung/occupancy telemetry, and the host-bubble "
                        "p95 the staging reuse removes")
    p.add_argument("--ladder-requests", type=int, default=48,
                   help="compare-ladder: burst size (needs to exceed "
                        "the top rung to fill it)")
    p.add_argument("--ladder-top", type=int, default=32,
                   help="compare-ladder: top ladder rung (the bs>=32 "
                        "arm the acceptance gate measures)")
    p.add_argument("--compare-spec", action="store_true",
                   help="run two pinned mixes twice each — plain decode "
                        "vs draft-free ngram speculation — and commit "
                        "the spec artifact: per-stream decode tok/s and "
                        "outputs_sha256 byte-identity on an echo-heavy "
                        "greedy multi-turn mix (where self-drafting "
                        "wins), plus throughput on an adversarial "
                        "no-echo sampled mix (where adaptive γ must "
                        "throttle so spec never loses), with acceptance-"
                        "rate / throttle telemetry from /metrics")
    p.add_argument("--spec-streams", type=int, default=4,
                   help="compare-spec: concurrent streams per mix")
    p.add_argument("--compare-fleet", action="store_true",
                   help="run a pinned greedy burst through the two "
                        "fleet backends (README 'Process fleet') — "
                        "in-process threads vs subprocess workers, plus "
                        "a subprocess arm with kill -9-a-worker chaos — "
                        "asserting byte-identical outputs and recording "
                        "tok/s ratio + failover counts; then a pinned "
                        "drain scenario twice (migration vs plain "
                        "resubmission), recording migrated vs "
                        "recomputed tokens and swap-in-resumes")
    p.add_argument("--fleet-streams", type=int, default=6,
                   help="compare-fleet: concurrent streams per arm")
    p.add_argument("--compare-chaos-rpc", action="store_true",
                   help="Byzantine-transport lane (README 'Failure "
                        "model'): the pinned greedy burst through a "
                        "clean dp=2 subprocess fleet, then again under "
                        "seeded frame-level RPC chaos — random byte "
                        "corruption, injected delays, and one wedged "
                        "(silently muted) connection — grading that "
                        "every corrupt frame is detected (CRC) and "
                        "recycled, outputs stay byte-identical (zero "
                        "silent corruptions), no worker process "
                        "restarts for a transport fault, and p95 "
                        "latency inflation stays bounded")
    p.add_argument("--compare-pd", action="store_true",
                   help="P/D disaggregation lane (README 'P/D "
                        "disaggregation'): the pinned long-prompt burst "
                        "through three dp=2 subprocess topologies — "
                        "mixed, mixed+hybrid-prefill, and a 1-prefill+"
                        "1-decode split with live KV handoff — each "
                        "measured unloaded (decode streams only) and "
                        "loaded (same streams under a CONTINUOUS "
                        "10x-plus long-prompt prefill burst spanning "
                        "every decode window), asserting byte-identical "
                        "outputs across every arm and phase and "
                        "recording decode TPOT p95 loaded/unloaded "
                        "ratios, handoff counts, and the zero-recompute "
                        "clean-handoff claim")
    p.add_argument("--compare-elastic", action="store_true",
                   help="elastic-fleet lane (README 'Elastic fleet'): a "
                        "pinned mini-diurnal burst (>=20x offered-load "
                        "swing, mixed interactive/batch X-Priority "
                        "classes) through a FIXED one-worker subprocess "
                        "fleet and through the same fleet with the "
                        "autoscaler + class lanes on, firing a rolling "
                        "upgrade mid-burst in the elastic arm — grading "
                        "that interactive TTFT p95 holds the SLO while "
                        "batch absorbs the slack (preemptions > 0, "
                        "interactive shed == 0), the fleet scales up "
                        "AND back down with events in /metrics and "
                        "/debug/trace, and the rollout completes with "
                        "zero failed requests and byte-identical greedy "
                        "outputs")
    p.add_argument("--elastic-quiet-requests", type=int, default=2,
                   help="compare-elastic: trickle arrivals in the quiet "
                        "phase, one per second (the diurnal trough)")
    p.add_argument("--elastic-burst-interactive", type=int, default=6,
                   help="compare-elastic: interactive requests in the "
                        "peak wave")
    p.add_argument("--elastic-burst-batch", type=int, default=28,
                   help="compare-elastic: batch requests in the peak "
                        "wave (the lane the interactives preempt)")
    p.add_argument("--compare-fabric", action="store_true",
                   help="fleet-KV-fabric lane (README 'KV fabric'): "
                        "many users sharing one long system prompt hit "
                        "a dp=2 subprocess fleet three times — fabric "
                        "off, fabric on, and fabric on with a mid-run "
                        "scale-up whose new worker warm-boots from the "
                        "pool — grading that the shared prefix is "
                        "prefilled ONCE fleet-wide (a second replica's "
                        "first turn is fabric-warm with zero recomputed "
                        "prefix tokens), returning-turn TTFT p95 "
                        "improves >=1.3x over fabric-off, the warmboot "
                        "worker serves its first request with fabric-"
                        "sourced warmth, and greedy outputs stay byte-"
                        "identical across every arm")
    p.add_argument("--fabric-users", type=int, default=10,
                   help="compare-fabric: concurrent returning users in "
                        "the graded wave (each prompt = shared system "
                        "prompt + a distinct tail)")
    p.add_argument("--fabric-wave2-users", type=int, default=14,
                   help="compare-fabric: users in the second wave (the "
                        "one that spills onto the warmboot worker in "
                        "the scale-up arm)")
    p.add_argument("--fabric-prefix-pages", type=int, default=9,
                   help="compare-fabric: shared system-prompt length in "
                        "full KV pages (page_size tokens each)")
    p.add_argument("--fabric-tokens", type=int, default=8,
                   help="compare-fabric: greedy generation budget per "
                        "request")
    p.add_argument("--fabric-pool-pages", type=int, default=256,
                   help="compare-fabric: router fabric pool capacity "
                        "for the fabric-on arms (--fabric-cache-pages)")
    p.add_argument("--fabric-warmboot-pages", type=int, default=64,
                   help="compare-fabric: MRU pool pages pushed into a "
                        "newly spawned worker before it is routable")
    p.add_argument("--compare-kv-plane", action="store_true",
                   help="zero-copy KV data plane lane (README 'KV data "
                        "plane'): a 1-prefill + 1-decode subprocess "
                        "fleet serves the same handoff-heavy burst "
                        "twice — KV blobs relayed through router "
                        "frames vs handed worker-to-worker through "
                        "the shared-memory page arena — grading that "
                        "the shm arm's router relays ~0 KV payload "
                        "bytes for handoff/fabric verbs, the "
                        "handoff+adopt wall p95 improves >=1.5x "
                        "(committed-artifact grade), a kill -9 "
                        "mid-wave reclaims the dead worker's slabs "
                        "via the region epoch bump with recompute-"
                        "resume fallback, and greedy outputs stay "
                        "byte-identical across both arms")
    p.add_argument("--kvp-users", type=int, default=8,
                   help="compare-kv-plane: concurrent requests in the "
                        "measured handoff wave (each carries a "
                        "distinct multi-hundred-KB KV context)")
    p.add_argument("--kvp-prompt-pages", type=int, default=30,
                   help="compare-kv-plane: per-request prompt length "
                        "in full KV pages — sizes the handoff blob "
                        "the planes move")
    p.add_argument("--kvp-tokens", type=int, default=8,
                   help="compare-kv-plane: greedy generation budget "
                        "per request")
    p.add_argument("--kvp-pool-pages", type=int, default=256,
                   help="compare-kv-plane: router fabric pool capacity "
                        "(fabric ON in both arms so fabric_put blob "
                        "traffic is part of the contrast)")
    p.add_argument("--shm-arena-bytes", type=int, default=64 * 1024 * 1024,
                   help="compare-kv-plane: shared-memory arena size "
                        "for the shm arm (the server flag of the same "
                        "name)")
    p.add_argument("--route-fabric-hit-weight", type=float, default=0.25,
                   help="prefix-affinity: routing-score pages one "
                        "fabric-pool hit page is worth (fourth "
                        "temperature)")
    p.add_argument("--pd-streams", type=int, default=4,
                   help="compare-pd: steady decode streams per phase")
    p.add_argument("--pd-decode-tokens", type=int, default=192,
                   help="compare-pd: generation budget per decode "
                        "stream (the measured decode window)")
    p.add_argument("--pd-load-prompts", type=int, default=64,
                   help="compare-pd: cap on long prompts the loaded "
                        "phase's continuous pressure generator issues "
                        "(a runaway bound — the generator stops when "
                        "the last stream finishes)")
    p.add_argument("--pd-load-prompt-tokens", type=int, default=448,
                   help="compare-pd: tokens per long prompt")
    p.add_argument("--pd-prefill-nice", type=int, default=19,
                   help="compare-pd: os.nice() for the pd arm's "
                        "prefill worker (shared-CPU hosts; see the "
                        "server CLI flag of the same name)")
    p.add_argument("--slo-ttft-ms", type=float, default=0.0,
                   help="rolling SLO target for TTFT (ms): feeds "
                        "tpu_inf_slo_*_seconds gauges + breach "
                        "counters; 0 = no target (gauges still export)")
    p.add_argument("--slo-tpot-ms", type=float, default=0.0,
                   help="rolling SLO target for TPOT (ms); 0 = none")
    p.add_argument("--trace-artifact", default=None,
                   help="with --compare-pd: write the pd arm's "
                        "recent-request ring as Chrome trace-event "
                        "JSON (GET /debug/trace?format=chrome) to this "
                        "path — one pid per replica, router as pid 0, "
                        "loadable in Perfetto (default with --smoke: "
                        "replay_pd_trace.json next to --out)")
    p.add_argument("--no-warmup", action="store_true")
    p.add_argument("--out", default=None, help="write summary JSON here")
    p.add_argument("--smoke", action="store_true",
                   help="CPU smoke lane (tier-1): tiny model, tiny trace, "
                        "small engine — exercises the full server boot + "
                        "replay + /metrics scrape + phase_breakdown "
                        "artifact path in seconds")
    args = p.parse_args()

    if sum(map(bool, (args.compare_admission, args.compare_hybrid,
                      args.compare_ladder, args.compare_spec,
                      args.compare_fleet, args.compare_pd,
                      args.compare_elastic, args.compare_fabric,
                      args.compare_chaos_rpc,
                      args.compare_kv_plane))) > 1:
        # Each comparison pins its own workload/sizing; combining them
        # would silently measure one lane on the other's shape.
        p.error("--compare-admission/--compare-hybrid/--compare-ladder/"
                "--compare-spec/--compare-fleet/--compare-pd/"
                "--compare-elastic/--compare-fabric/--compare-chaos-rpc/"
                "--compare-kv-plane "
                "are mutually exclusive; run them as separate "
                "invocations")

    if args.smoke:
        # One switch pins every knob to the CPU-affordable shape so the
        # tier-1 lane cannot drift from what CI actually runs.
        args.model, args.tokenizer = "tiny-llama", "byte"
        args.platform = "cpu"
        args.max_trace = min(args.max_trace, 4)
        args.max_batch_size, args.num_pages = 4, 128
        args.page_size, args.max_pages_per_seq = 8, 8
        args.decode_steps_per_call = 4
        if args.compare_admission:
            # The comparison needs a pool TIGHT enough that worst-case
            # reservation actually binds: generations budgeted well past
            # their prompts, a pool that holds ~2 worst cases, and a
            # burst arrival so requests overlap. Optimistic admission
            # packs more lanes and preempts under pressure — the
            # occupancy delta is the artifact's point.
            args.num_pages, args.max_pages_per_seq = 20, 12
        if args.compare_hybrid:
            # The comparison needs one LONG (multi-chunk) prompt
            # prefilling while short requests decode: room for a
            # 127-token prompt, a 16-token chunk size (8 chunks), and
            # shorts with enough generation budget to still be decoding
            # through every chunk. run_replay pins the matching schedule.
            args.max_pages_per_seq = 16
            args.chunked_prefill_size = 16
        if args.compare_ladder:
            # The comparison needs a burst WIDER than the top rung so
            # the ladder actually climbs: a pool holding every request's
            # worst case (the comparison measures concurrency, not
            # admission), and enough generation budget per stream that
            # decode — not prefill — dominates the wall. K=1 keeps the
            # per-dispatch host round trip (the thing wide batches
            # amortize) in the measurement instead of fusing it away —
            # on CPU the fused-K scan is compute-bound and would
            # understate the chip-side concurrency win being pinned.
            args.max_batch_size = 8            # per-arm override below
            args.num_pages, args.max_pages_per_seq = 448, 8
            args.decode_steps_per_call = 1
        if args.compare_spec:
            # The comparison needs room for multi-turn transcripts (two
            # turns of prompt+reply per stream: 256-token contexts),
            # long enough generations that the tiny greedy model's
            # repetition cycles form (the echo self-drafting exploits),
            # and a γ deep enough that an accepted round visibly beats
            # a plain dispatch. K=1 keeps the per-dispatch host round
            # trip — the cost every accepted speculative token removes —
            # in the measurement (the compare-ladder stance: the fused-K
            # scan is compute-bound on CPU and would bury the dispatch
            # amortization this lane pins; on TPU decode is HBM-bound
            # and the verify's extra positions ride the same weight
            # stream).
            args.max_pages_per_seq, args.num_pages = 64, 320
            args.decode_steps_per_call = 1
            args.num_speculative_tokens = 5
            args.ngram_window = 3
        if args.compare_fleet:
            # dp=2 both backends; host tier on so drain migration has a
            # destination; no warmup (8 worker boots across the arms —
            # lazy compile keeps the tier-1 lane affordable and greedy
            # byte-identity is compile-order-independent).
            args.dp = 2
            args.num_pages, args.max_pages_per_seq = 128, 8
            args.host_cache_pages = 64
            args.decode_steps_per_call = 4
            args.no_warmup = True
        if args.compare_chaos_rpc:
            # Same dp=2 subprocess shape as compare-fleet; tight
            # per-verb deadlines so the wedged connection's detection
            # (3 consecutive timeouts -> recycle) costs seconds, not
            # the default minute, inside the tier-1 budget.
            args.dp = 2
            args.num_pages, args.max_pages_per_seq = 128, 8
            args.host_cache_pages = 64
            args.decode_steps_per_call = 4
            args.no_warmup = True
            args.rpc_deadline_fast_s = 2.0
            args.rpc_deadline_slow_s = 4.0
        if args.compare_elastic:
            # One subprocess worker to start (the whole point: the
            # AUTOSCALER adds the second), a shed cap tight enough that
            # the 20-request peak actually overflows it, and an SLO
            # target sized so parked batch TTFT breaches it by seconds
            # while a preempting interactive holds it easily. Host tier
            # on so drains migrate. Warmup stays ON — scale-up workers
            # and rollout successors join mid-burst, and a cold
            # replica's lazy compile would land in exactly the
            # interactive TTFT this lane grades; one tiny prefill
            # bucket keeps each warm boot to seconds.
            args.dp = 1
            args.num_pages, args.max_pages_per_seq = 128, 8
            args.host_cache_pages = 64
            args.decode_steps_per_call = 2
            args.admission_queue_depth = 6
            args.prefill_buckets = (16,)
            if not args.slo_ttft_ms:
                # Sits in the wide gap between warm interactive TTFT
                # (p95 20-70 ms on this CPU lane, idle or loaded) and
                # parked-batch TTFT (p95 0.48-0.80 s): the
                # router-observed p95 breaches while the batch wave is
                # parked, yet the interactive class holds it with
                # margin. (At 600 ms the wave sat ON the target — the
                # committed artifact's own 0.68 s — and whether the
                # fleet scaled up depended on what else the box ran.)
                args.slo_ttft_ms = 250.0
        if args.compare_fabric:
            # Many users share one 256-token system prompt across a
            # dp=2 subprocess fleet: prompts are prefix_pages *
            # page_size shared tokens + a short distinct tail, and the
            # prefill buckets are split so a fabric-warm prefill (tail
            # only) runs the small bucket while a cold one pays the
            # big one. Host tier ON (fabric pulls restore through it);
            # no warmup (up to 7 worker boots across the three arms —
            # each arm runs an unmeasured compile-warm pass first).
            # The raised preempt watermark makes chaos page pressure —
            # the lane's deterministic stand-in for a saturated
            # replica — actually flip the routing pressure bit: a
            # pressured worker's free+evictable (its whole prefix
            # cache) stays under 128 once every free page is held,
            # while the unpressured replica (384-page pool, ~200 pages
            # of worst-case wave footprint) never dips below it.
            args.dp = 2
            args.page_size, args.max_pages_per_seq = 8, 40
            args.num_pages = 384
            args.host_cache_pages = 128
            args.decode_steps_per_call = 4
            args.no_warmup = True
            args.fabric_prefix_pages = 32
            args.fabric_users = 6
            args.fabric_wave2_users = 6
            args.prefill_buckets = (16, 64, 320)
            args.preempt_watermark_pages = 128
        if args.compare_kv_plane:
            # 1 prefill + 1 decode worker; EVERY request hands its KV
            # off between them, so the wave is pure data-plane
            # traffic. BIG payloads without long-context compute: the
            # fatkv model carries 16 KiB of KV per token (the
            # production KV:compute ratio the stock tiny models are
            # two orders of magnitude under), so a 448-token prompt —
            # 7 full 64-token pages, distinct per user so nothing
            # prefix-caches away, one prefill bucket fitting it whole
            # — hands off ~7.3 MiB of serialized KV after a sub-second
            # CPU prefill. The fixed costs of a handoff (dispatch RPC,
            # admission, device restore, first decode step) are
            # identical in both arms; MiB-scale blobs are what make
            # the per-byte contrast visible over that floor. The relay
            # arm moves every payload twice through router sockets
            # (plus a router-side digest pass); the shm arm's
            # descriptors carry bytes that never left the arena.
            # Fabric stays ON so fabric_put publishes are part of the
            # relay-vs-shm blob contrast. No warmup (4 worker boots
            # across the arms); each arm runs an unmeasured compile-
            # warm wave first.
            args.dp = 2
            args.model = "tiny-llama-fatkv"
            args.page_size, args.max_pages_per_seq = 64, 8
            # Pool headroom and NO host tier: a reclaim during the
            # measured series must be a free-list pop, not an eviction
            # batch demoting victims through a device_get — that demote
            # lands as a ~50 ms outlier inside whichever adopt it
            # interrupts (both arms equally) and owns the p95.
            args.num_pages = 144
            args.host_cache_pages = 0
            # One decode dispatch in flight at a time: the export's
            # device_get orders after in-flight dispatch, so a deeper
            # dispatch-ahead window pads BOTH arms' export wall with
            # identical decode work and dilutes the transit contrast.
            args.decode_steps_per_call = 1
            args.no_warmup = True
            args.prefill_buckets = (16, 512)
            args.kvp_users = 12
            args.kvp_prompt_pages = 7
            args.kvp_pool_pages = 64
            # Sized so the WHOLE run's slabs fit a region without one
            # free ever landing: frees ride the periodic stats tick, so
            # during back-to-back waves the prefill region must hold
            # warm+measured+kill publishes at once (36 x ~7.45 MiB
            # extents ~= 268 MiB < 384 MiB/region at dp=2). An
            # undersized arena degrades gracefully (ArenaFull -> relay
            # fallback) but that contaminates the shm arm's walls.
            args.shm_arena_bytes = 768 * 1024 * 1024
        if args.compare_pd:
            # dp=2 subprocess topologies, room for the 448-token long
            # prompts (ctx 640 at page_size 16), host tier on. K=2
            # flushes give the client-side gap measurement ~2-token
            # resolution; no warmup (6 worker boots across 3 arms —
            # each arm runs an UNMEASURED warm pass of the exact
            # workload first, so lazy compiles never land in a measured
            # phase).
            args.dp = 2
            # SLO targets sized to the CPU lane's loaded-phase latency
            # so the breach counters exercise for real (the quantile
            # gauges export regardless; magnitudes are recorded, not
            # graded live).
            if not args.slo_ttft_ms:
                args.slo_ttft_ms = 2000.0
            if not args.slo_tpot_ms:
                args.slo_tpot_ms = 200.0
            args.page_size, args.max_pages_per_seq = 16, 40
            args.num_pages = 512
            args.host_cache_pages = 64
            args.decode_steps_per_call = 2
            args.no_warmup = True
            # 256-token chunks: one in-engine prefill dispatch stalls
            # decode by a full chunk wall (the interference this lane
            # exists to show); the pd arm's decode engine never
            # dispatches one.
            args.prefill_buckets = (16, 64, 256)
        if args.out is None:
            args.out = ("benchmarks/results/replay_hybrid.json"
                        if args.compare_hybrid
                        else "benchmarks/results/replay_ladder.json"
                        if args.compare_ladder
                        else "benchmarks/results/replay_spec.json"
                        if args.compare_spec
                        else "benchmarks/results/replay_fleet.json"
                        if args.compare_fleet
                        else "benchmarks/results/replay_pd.json"
                        if args.compare_pd
                        else "benchmarks/results/replay_elastic.json"
                        if args.compare_elastic
                        else "benchmarks/results/replay_fabric.json"
                        if args.compare_fabric
                        else "benchmarks/results/replay_chaos_rpc.json"
                        if args.compare_chaos_rpc
                        else "benchmarks/results/replay_kv_plane.json"
                        if args.compare_kv_plane
                        else "benchmarks/results/replay_smoke.json")
        if args.compare_pd and args.trace_artifact is None:
            args.trace_artifact = os.path.join(
                os.path.dirname(args.out) or ".", "replay_pd_trace.json")

    if args.platform != "auto":
        # Before any jax computation.
        import jax

        jax.config.update("jax_platforms", args.platform)
        if args.platform == "cpu" and args.dp * args.tp * args.sp > 1:
            # Only force the virtual-device count when the run actually
            # needs a multi-device mesh: the CPU default is 1 device,
            # and shrinking a host that asked for more (the in-process
            # --smoke test runs inside pytest's 8-device session) would
            # pin the whole process to 1 device before backend init.
            try:
                jax.config.update("jax_num_cpu_devices",
                                  args.dp * args.tp * args.sp)
            except RuntimeError:
                # Backends are already up (main() called inside a
                # process that has run jax, e.g. pytest): jax refuses
                # to change the count, and the host's devices stand.
                pass

    from tpu_inference.engine.autosize import (parse_decode_ladder,
                                               resolve_sizing_args)

    args.max_batch_size, args.num_pages = resolve_sizing_args(args)

    try:
        args.decode_ladder_rungs = parse_decode_ladder(
            args.decode_ladder, args.max_batch_size)
    except ValueError as e:
        p.error(str(e))

    if args.compare_admission:
        return _compare_admission(args)
    if args.compare_hybrid:
        return _compare_hybrid(args)
    if args.compare_ladder:
        return _compare_ladder(args)
    if args.compare_spec:
        return _compare_spec(args)
    if args.compare_fleet:
        return _compare_fleet(args)
    if args.compare_pd:
        return _compare_pd(args)
    if args.compare_elastic:
        return _compare_elastic(args)
    if args.compare_fabric:
        return _compare_fabric(args)
    if args.compare_chaos_rpc:
        return _compare_chaos_rpc(args)
    if args.compare_kv_plane:
        return _compare_kv_plane(args)

    summary = run_replay(args)
    out = {"config": vars(args), "summary": summary}
    print(json.dumps(summary, indent=1))
    _write_out(args.out, out)
    return summary


def run_replay(args) -> dict:
    """Boot one server, replay the trace, scrape, summarize."""
    from traffic_generator.data import DataLoader
    from traffic_generator.generator import TrafficGenerator
    from traffic_generator.metrics import MetricCollector
    from traffic_generator.schedule import Scheduler

    srv, port, stop = start_server(args)
    try:
        data = DataLoader.get_data_from_path(args.data)
        schedule = Scheduler.get_schedule_from_trace(args.trace,
                                                     args.max_trace)
        if args.compare_admission:
            # Burst arrival: all requests land at t=0 so both admission
            # modes face the same overlapping demand (trace gaps on a
            # fast CPU model would serialize the run and hide the
            # occupancy difference being measured).
            schedule["Timestamp"] = 0.0
        if getattr(args, "compare_hybrid", False) and args.smoke:
            # Pinned decode-stall workload: ONE long prompt (8 chunks of
            # 16 once truncated to max_context-1=127) submitted first so
            # it starts its incremental prefill, then three shorts that
            # batch-admit while it is mid-chunks and keep decoding
            # through every remaining chunk. Serial chunking stalls
            # those lanes once per chunk (decode_stall samples); hybrid
            # steps fuse the chunks into their decode dispatches
            # (structurally zero samples) — the artifact compares
            # exactly that histogram.
            import pandas as pd
            schedule = pd.DataFrame({
                "Timestamp": [0.0, 0.0, 0.0, 0.0],
                "Request tokens": [128, 8, 8, 8],
                "Response tokens": [8, 64, 64, 64],
            })
        collector = MetricCollector()
        gen_kw = {}
        if args.smoke:
            gen_kw = ({"max_prompt_len": 24, "max_gen_len": 48}
                      if args.compare_admission else
                      {"max_prompt_len": 128, "max_gen_len": 64}
                      if getattr(args, "compare_hybrid", False) else
                      {"max_prompt_len": 48, "max_gen_len": 12})
        gen = TrafficGenerator(
            data, schedule,
            {"url": f"http://127.0.0.1:{port}/api/generate",
             "model": args.model, "temperature": args.temperature,
             "max_tokens": None, "stream": True,
             "max_retries": args.client_max_retries},
            collector, **gen_kw)
        # Pre-run scrape over real HTTP: phase_breakdown diffs the
        # histograms so only THIS run's window is attributed.
        before_json, _ = scrape_metrics(port, fmt="json")
        before = json.loads(before_json)
        t0 = time.perf_counter()
        metrics = gen.start_profile()
        replay_s = time.perf_counter() - t0
        after_json, _ = scrape_metrics(port, fmt="json")
        after = json.loads(after_json)
        prom_text, prom_ctype = scrape_metrics(port)
        attribution = step_attribution(port)
        summary = summarize(metrics,
                            n_chips=getattr(args, "dp", 1) * args.tp * args.sp)
        summary["replay_s"] = round(replay_s, 3)
        summary["server_stats"] = after
        # Admission-mode lane: the occupancy / preemption / shed numbers
        # the reserve-vs-optimistic artifact compares.
        summary["admission"] = {
            "mode": after.get("admission"),
            "mean_batch_occupancy": after.get("mean_batch_occupancy"),
            "preemptions": after.get("preemptions"),
            "recompute_resumes": after.get("recompute_resumes"),
            "requests_rejected": after.get("requests_rejected"),
            "peak_pages_in_use": after.get("peak_pages_in_use"),
            "pool_pressure": after.get("pool_pressure"),
            "shed_rate": summary["shed_rate"],
        }
        summary["phase_breakdown"] = phase_breakdown(before, after)
        summary["step_attribution"] = attribution
        # Rolling SLO gauges (README "Observability"): the fleet's
        # exact windowed quantiles + breach counts at scrape time
        # (windows dropped — the artifact carries the numbers).
        if after.get("slo"):
            summary["slo"] = {k: v for k, v in after["slo"].items()
                              if not k.endswith("_window")}
        # Speculative-decoding lane (README "Speculative decoding"):
        # mode/γ/acceptance from the server's own counters when spec is
        # on (absent otherwise).
        if after.get("speculative"):
            summary["speculative"] = after["speculative"]
        # Hybrid-stepping lane: the decode-stall-during-prefill numbers
        # the serial-vs-hybrid artifact compares (count 0 -> p95 0.0:
        # nothing ever stalled).
        stall = summary["phase_breakdown"].get(
            "decode_stall_during_prefill_s") or {}
        summary["hybrid"] = {
            "enabled": bool(after.get("hybrid_prefill")),
            "hybrid_steps": after.get("hybrid_steps"),
            "decode_stall_count": stall.get("count", 0),
            "decode_stall_p95_s": stall.get("p95") or 0.0,
            "decode_stall_sum_s": stall.get("sum") or 0.0,
        }
        summary["prometheus_scrape"] = {
            "content_type": prom_ctype,
            "families": prom_text.count("# TYPE "),
            "samples": sum(1 for l in prom_text.splitlines()
                           if l and not l.startswith("#")),
        }
    finally:
        stop()
    return summary


def _compare_admission(args) -> dict:
    """Run the trace under admission=reserve then admission=optimistic
    (fresh server each) and commit the side-by-side artifact: batch
    occupancy, tokens/s, shed rate, preemption counts."""
    # Snapshot the invocation BEFORE the per-arm mutation below, so the
    # committed config reproduces this comparison (not the last arm).
    cfg_snapshot = dict(vars(args))
    summaries = {}
    for mode in ("reserve", "optimistic"):
        args.admission = mode
        print(f"[replay] admission={mode} lane", file=sys.stderr)
        summaries[mode] = run_replay(args)
    res, opt = summaries["reserve"], summaries["optimistic"]

    def _occ(s):
        return s["admission"]["mean_batch_occupancy"] or 0.0

    comparison = {
        "occupancy_reserve": round(_occ(res), 4),
        "occupancy_optimistic": round(_occ(opt), 4),
        "occupancy_gain": round(_occ(opt) - _occ(res), 4),
        "tokens_per_s_reserve": res["tokens_per_s"],
        "tokens_per_s_optimistic": opt["tokens_per_s"],
        "shed_rate_reserve": res["shed_rate"],
        "shed_rate_optimistic": opt["shed_rate"],
        "preemptions": opt["admission"]["preemptions"],
        "recompute_resumes": opt["admission"]["recompute_resumes"],
        # The artifact's claim: optimistic admission packs more of the
        # batch (or matches throughput with a lower shed rate).
        "optimistic_wins": bool(
            _occ(opt) > _occ(res)
            or (opt["tokens_per_s"] >= res["tokens_per_s"]
                and opt["shed_rate"] <= res["shed_rate"])),
    }
    out = {"config": cfg_snapshot, "reserve": res, "optimistic": opt,
           "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result["reserve"], result["optimistic"] = res, opt
    return result


def _compare_hybrid(args) -> dict:
    """Run the workload under serial chunked prefill then under hybrid
    fused steps (fresh server each) and commit the side-by-side
    artifact: p95 decode stall while a prompt prefills (the server-side
    inter-token stall hybrid exists to remove), aggregate tokens/s,
    TTFT, and the client-observed worst inter-chunk gap."""
    # Snapshot the invocation BEFORE the per-arm mutation below, so the
    # committed config reproduces this comparison (not the last arm).
    cfg_snapshot = dict(vars(args))
    summaries = {}
    for mode in ("serial", "hybrid"):
        args.hybrid_prefill = (mode == "hybrid")
        print(f"[replay] scheduling={mode} lane", file=sys.stderr)
        summaries[mode] = run_replay(args)
    ser, hyb = summaries["serial"], summaries["hybrid"]

    comparison = {
        "decode_stall_count_serial": ser["hybrid"]["decode_stall_count"],
        "decode_stall_count_hybrid": hyb["hybrid"]["decode_stall_count"],
        "decode_stall_p95_serial_s": ser["hybrid"]["decode_stall_p95_s"],
        "decode_stall_p95_hybrid_s": hyb["hybrid"]["decode_stall_p95_s"],
        "hybrid_steps": hyb["hybrid"]["hybrid_steps"],
        "tokens_per_s_serial": ser["tokens_per_s"],
        "tokens_per_s_hybrid": hyb["tokens_per_s"],
        "tok_s_ratio": round(hyb["tokens_per_s"]
                             / max(ser["tokens_per_s"], 1e-9), 4),
        "ttft_p99_serial_s": ser["ttft_s"]["p99"],
        "ttft_p99_hybrid_s": hyb["ttft_s"]["p99"],
        "max_interchunk_gap_p99_serial_s":
            ser["max_interchunk_gap_s"]["p99"],
        "max_interchunk_gap_p99_hybrid_s":
            hyb["max_interchunk_gap_s"]["p99"],
        # Greedy decoding + identical prompts: both arms must emit the
        # same token counts (the HTTP-level echo of the byte-equality
        # tests/test_hybrid.py pins at engine level).
        "output_tokens_serial": ser["output_tokens"],
        "output_tokens_hybrid": hyb["output_tokens"],
        # Committed-artifact throughput check (tok/s no more than 5%
        # below serial). Deliberately NOT folded into hybrid_wins: the
        # tier-1 smoke asserts hybrid_wins, and wall-clock tok/s on a
        # loaded CI box swings far more than 5% run to run — the
        # deterministic stall histogram is the CI-gradable claim, the
        # ratio is graded on the artifact actually committed.
        "tok_s_within_5pct": bool(
            hyb["tokens_per_s"] >= 0.95 * ser["tokens_per_s"]),
        # The artifact's claim: fusing removes the decode stall (the
        # chunk-sized inter-token spike) entirely. Guarded on the serial
        # arm actually MEASURING a stall (same guard as bench.py's
        # stall_removed) so a run whose chunks never met a busy batch —
        # or one with telemetry disabled — can't claim a vacuous win.
        "hybrid_wins": bool(
            ser["hybrid"]["decode_stall_count"] > 0
            and hyb["hybrid"]["decode_stall_p95_s"]
            <= ser["hybrid"]["decode_stall_p95_s"]
            and hyb["hybrid"]["decode_stall_count"]
            < ser["hybrid"]["decode_stall_count"]),
    }
    out = {"config": cfg_snapshot, "serial": ser, "hybrid": hyb,
           "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result["serial"], result["hybrid"] = ser, hyb
    return result


async def _ladder_burst(port: int, model: str, n_requests: int,
                        max_tokens: int) -> list:
    """Fire ``n_requests`` DISTINCT greedy requests at once (the bursty
    mix the ladder exists for) and stream every reply, so the arms can
    be hashed for byte-identity and timed per stream."""
    import aiohttp

    url = f"http://127.0.0.1:{port}/api/generate"
    timeout = aiohttp.ClientTimeout(total=1800)

    async def one(session, i: int) -> dict:
        # Distinct prompts (byte tokenizer: chars = tokens), so greedy
        # decoding produces a distinct transcript per stream; short
        # enough that prompt + the generation budget fits the smoke
        # shape's 64-token context.
        # NON-streamed: a 48-stream burst of per-token NDJSON chunks
        # bottlenecks on the client event loop, not the engine — the
        # ladder's chip-side concurrency win is what this lane pins,
        # so responses come back whole and timing is request-level.
        prompt = f"[{i:02d}] probe"
        payload = {"model": model, "prompt": prompt, "temperature": 0.0,
                   "stream": False, "options": {"num_predict": max_tokens}}
        t0 = time.perf_counter()
        async with session.post(url, json=payload) as resp:
            resp.raise_for_status()
            rec = await resp.json()
        e2e = time.perf_counter() - t0
        n_tokens = rec.get("eval_count", 0)
        # Server-side decode wall per token (eval_duration is the
        # engine's own decode-phase accounting): the per-stream latency
        # the batch width actually changes, independent of queue wait.
        tpot = (rec.get("eval_duration", 0) / 1e9 / (n_tokens - 1)
                if n_tokens > 1 else None)
        return {"idx": i, "reply": rec.get("response", ""),
                "ttft_s": None, "e2e_s": e2e, "output_tokens": n_tokens,
                "tpot_s": tpot}

    async with aiohttp.ClientSession(timeout=timeout) as session:
        return list(await asyncio.gather(*[one(session, i)
                                           for i in range(n_requests)]))


def _ladder_arm(args, label: str) -> dict:
    """Boot one server, run the pinned burst, summarize one arm."""
    import hashlib

    print(f"[replay] ladder arm: {label}", file=sys.stderr)
    srv, port, stop = start_server(args)
    try:
        t0 = time.perf_counter()
        records = asyncio.run(_ladder_burst(
            port, args.model, args.ladder_requests, args.ladder_tokens))
        wall = time.perf_counter() - t0
        after = json.loads(scrape_metrics(port, fmt="json")[0])
    finally:
        stop()
    h = hashlib.sha256()
    for r in sorted(records, key=lambda r: r["idx"]):
        h.update(f"{r['idx']}:".encode())
        h.update(r["reply"].encode())
        h.update(b"\x00")
    tokens = sum(r["output_tokens"] for r in records)
    tpots = [r["tpot_s"] for r in records if r["tpot_s"] is not None]
    bubble = (after.get("phases") or {}).get("dispatch_bubble_s") or {}
    return {
        "label": label,
        "max_batch_size": args.max_batch_size,
        "decode_ladder": list(args.decode_ladder_rungs
                              or (args.max_batch_size,)),
        "stage_host_reuse": getattr(args, "stage_host_reuse", True),
        "requests": len(records),
        "output_tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(tokens / wall, 2),
        "ttft_s": _percentiles([r["ttft_s"] for r in records
                                if r["ttft_s"] is not None], ps=(50, 95)),
        "tpot_s": _percentiles(tpots, ps=(50, 95)),
        "e2e_s": _percentiles([r["e2e_s"] for r in records], ps=(50, 95)),
        "outputs_sha256": h.hexdigest(),
        "rung_peak": after.get("rung_peak"),
        "rung_switches": after.get("rung_switches"),
        "mean_batch_occupancy": after.get("mean_batch_occupancy"),
        "mfu_estimate": after.get("mfu_estimate"),
        "dispatch_bubble_p50_s": bubble.get("p50"),
        "dispatch_bubble_p95_s": bubble.get("p95"),
        "dispatch_bubble_count": bubble.get("count"),
    }


def _staging_micro(model_cfg, *, page_size, num_pages, max_pages_per_seq,
                   top) -> dict:
    """Deterministic per-dispatch host staging cost at the top rung,
    reuse vs rebuild (microseconds). The arm-level bubble histograms
    also carry scheduler/callback work; this isolates exactly what the
    staging reuse removes, engine-inline with no server. THE one
    implementation — bench.py's ladder lane imports it, so the two
    committed artifacts measure the same thing."""
    from tpu_inference.config import EngineConfig
    from tpu_inference.engine.autosize import decode_ladder_rungs
    from tpu_inference.engine.engine import InferenceEngine, Sequence

    ecfg = EngineConfig(
        page_size=page_size, num_pages=num_pages,
        max_pages_per_seq=max_pages_per_seq,
        max_batch_size=top, decode_ladder=decode_ladder_rungs(top),
        prefill_buckets=(16, 32), decode_steps_per_call=1)
    engine = InferenceEngine(model_cfg, ecfg)
    for i in range(top):
        engine.prefill(Sequence(
            request_id=i, prompt_tokens=[1 + (i + j) % 250
                                         for j in range(16)],
            max_new_tokens=8))
    act = engine.active_sequences()
    out = {}
    for reuse in (True, False):
        engine._stage_reuse = reuse
        engine._stage_batch(act, top)          # warm the buffers
        t0 = time.perf_counter()
        reps = 500
        for _ in range(reps):
            engine._stage_batch(act, top)
        out["reuse_us" if reuse else "rebuild_us"] = round(
            (time.perf_counter() - t0) / reps * 1e6, 1)
    out["speedup"] = round(out["rebuild_us"] / max(out["reuse_us"], 1e-9),
                           2)
    return out


def _compare_ladder(args) -> dict:
    """The batch-ladder artifact (README "Batch ladder"): the same
    pinned greedy burst served by (a) the fixed bs=8 graph, (b) the
    compiled ladder up to ``--ladder-top``, and (c) the ladder with
    host-staging reuse disabled — so one committed file carries the
    concurrency win (aggregate tok/s at bs>=32 vs bs=8), the per-stream
    latency bound, greedy byte-identity across batch shapes, and the
    host-bubble p95 drop the staging reuse buys."""
    from tpu_inference.engine.autosize import (decode_ladder_rungs,
                                               resolve_model_config)

    args.ladder_tokens = 48
    cfg_snapshot = dict(vars(args))
    arms = {}

    args.max_batch_size, args.decode_ladder_rungs = 8, ()
    args.stage_host_reuse = True
    arms["bs8"] = _ladder_arm(args, "bs8")

    args.max_batch_size = args.ladder_top
    args.decode_ladder_rungs = decode_ladder_rungs(args.ladder_top)
    arms["ladder"] = _ladder_arm(args, "ladder")

    args.stage_host_reuse = False
    arms["ladder_rebuild"] = _ladder_arm(args, "ladder_rebuild")
    args.stage_host_reuse = True

    bs8, lad, reb = arms["bs8"], arms["ladder"], arms["ladder_rebuild"]
    comparison = {
        "ladder": lad["decode_ladder"],
        "tokens_per_s_bs8": bs8["tokens_per_s"],
        "tokens_per_s_ladder": lad["tokens_per_s"],
        "tok_s_ratio": round(lad["tokens_per_s"]
                             / max(bs8["tokens_per_s"], 1e-9), 4),
        "tpot_p50_bs8_s": bs8["tpot_s"]["p50"],
        "tpot_p50_ladder_s": lad["tpot_s"]["p50"],
        # Decode-wall-per-token ratio, reported transparently: on a
        # single-core CPU lane the 32-wide graph's compute serializes,
        # so this exceeds 1 by construction here; on TPU decode is
        # HBM-bound and the batch rides the same weight stream.
        "tpot_ratio": (
            round(lad["tpot_s"]["p50"] / bs8["tpot_s"]["p50"], 4)
            if lad["tpot_s"]["p50"] and bs8["tpot_s"]["p50"] else None),
        # The acceptance bound: what a STREAM experiences under the
        # same offered burst — per-request latency (queue wait included:
        # the fixed bs=8 graph makes 48 streams queue 6 waves deep,
        # which is precisely the cost the ladder removes). Within 1.5x
        # of bs=8 required; in practice the ladder is strictly faster.
        "per_stream_latency_ratio": (
            round(lad["e2e_s"]["p50"] / bs8["e2e_s"]["p50"], 4)
            if lad["e2e_s"]["p50"] and bs8["e2e_s"]["p50"] else None),
        "e2e_p50_bs8_s": bs8["e2e_s"]["p50"],
        "e2e_p50_ladder_s": lad["e2e_s"]["p50"],
        "e2e_p95_bs8_s": bs8["e2e_s"]["p95"],
        "e2e_p95_ladder_s": lad["e2e_s"]["p95"],
        "rung_peak": lad["rung_peak"],
        "rung_switches": lad["rung_switches"],
        "mfu_estimate_ladder": lad["mfu_estimate"],
        # Byte-identity across batch shapes: greedy decode is a per-lane
        # computation, so graph width must never change tokens.
        "outputs_identical": (bs8["outputs_sha256"]
                              == lad["outputs_sha256"]
                              == reb["outputs_sha256"]),
        # Host-staging reuse (the per-dispatch bubble shrinker): the
        # host-side gap between decode dispatches, reuse vs rebuild,
        # plus the isolated staging micro-cost (the bubble histograms
        # also carry scheduler/callback work).
        "bubble_p50_reuse_s": lad["dispatch_bubble_p50_s"],
        "bubble_p50_rebuild_s": reb["dispatch_bubble_p50_s"],
        "bubble_p95_reuse_s": lad["dispatch_bubble_p95_s"],
        "bubble_p95_rebuild_s": reb["dispatch_bubble_p95_s"],
        "bubble_p95_improved": bool(
            lad["dispatch_bubble_p95_s"] is not None
            and reb["dispatch_bubble_p95_s"] is not None
            and lad["dispatch_bubble_p95_s"]
            <= reb["dispatch_bubble_p95_s"]),
        "stage_us_per_dispatch": _staging_micro(
            resolve_model_config(args.model, args.checkpoint),
            page_size=args.page_size, num_pages=args.num_pages,
            max_pages_per_seq=args.max_pages_per_seq,
            top=args.ladder_top),
        # The artifact's claim: the ladder serves the burst strictly
        # faster in aggregate, within the per-stream latency bound,
        # with byte-identical outputs, having actually reached the top.
        "ladder_wins": bool(
            lad["tokens_per_s"] > bs8["tokens_per_s"]
            and lad["rung_peak"] == lad["decode_ladder"][-1]
            and bs8["outputs_sha256"] == lad["outputs_sha256"]),
    }
    out = {"config": cfg_snapshot, "bs8": bs8, "ladder": lad,
           "ladder_rebuild": reb, "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result.update(bs8=bs8, ladder=lad, ladder_rebuild=reb)
    return result


async def _spec_burst(port: int, model: str, prompts: list,
                      max_tokens: int, temperature: float) -> list:
    """Fire one request per prompt at once (non-streamed) and return
    [{reply, eval_count, eval_duration_ns}] in prompt order — the spec
    arms hash replies for byte-identity and read per-stream decode rate
    from the server's own eval accounting."""
    import aiohttp

    url = f"http://127.0.0.1:{port}/api/generate"
    timeout = aiohttp.ClientTimeout(total=1800)

    async def one(session, prompt: str) -> dict:
        payload = {"model": model, "prompt": prompt,
                   "temperature": temperature, "stream": False,
                   "options": {"num_predict": max_tokens}}
        async with session.post(url, json=payload) as resp:
            resp.raise_for_status()
            rec = await resp.json()
        return {"reply": rec.get("response", ""),
                "eval_count": rec.get("eval_count", 0),
                "eval_duration_ns": rec.get("eval_duration", 0)}

    async with aiohttp.ClientSession(timeout=timeout) as session:
        return list(await asyncio.gather(*[one(session, p)
                                           for p in prompts]))


def _spec_arm(args, label: str, mix: str, ngram: bool) -> dict:
    """Boot one server (plain or ngram-spec), run one pinned mix, and
    summarize: per-stream decode tok/s (server-side eval accounting, so
    queue effects don't pollute the per-stream claim), aggregate tok/s,
    a transcript hash, and the /metrics speculative block.

    Mixes:
    - "echo": greedy, two turns per stream, turn 2 re-sends turn 1's
      transcript — the multi-turn/RAG echo shape self-drafting exists
      for (the tiny model's greedy repetition cycles stand in for
      real-text echo). Byte-identity across arms is asserted here.
    - "adversarial": temperature-sampled streams whose proposals almost
      never verify — the mix adaptive γ must throttle on so spec never
      loses. No byte-identity (sampled), throughput only.
    """
    import hashlib

    print(f"[replay] spec arm: {label}/{mix}", file=sys.stderr)
    args.spec_mode = "ngram" if ngram else None
    srv, port, stop = start_server(args)
    n = args.spec_streams
    try:
        t0 = time.perf_counter()
        if mix == "echo":
            turn1 = [f"<s{i}> the quick brown fox {i:02d} " for i in range(n)]
            rec1 = asyncio.run(_spec_burst(port, args.model, turn1,
                                           max_tokens=200, temperature=0.0))
            turn2 = [p + r["reply"] for p, r in zip(turn1, rec1)]
            rec2 = asyncio.run(_spec_burst(port, args.model, turn2,
                                           max_tokens=120, temperature=0.0))
            records = rec1 + rec2
        else:
            rng = __import__("random").Random(1234)
            # 2n streams (two admission waves): decode-phase rates are
            # queue-independent, and the larger sample steadies the
            # median on a noisy CI box.
            prompts = ["".join(chr(33 + rng.randrange(90))
                               for _ in range(24)) for _ in range(2 * n)]
            # Long streams: the never-lose overhead (initial narrow
            # rounds + backed-off probes) is front-loaded, so length
            # amortizes it toward zero — and steadies the rates.
            records = asyncio.run(_spec_burst(port, args.model, prompts,
                                              max_tokens=320,
                                              temperature=1.0))
        wall = time.perf_counter() - t0
        after = json.loads(scrape_metrics(port, fmt="json")[0])
    finally:
        stop()
    h = hashlib.sha256()
    for r in records:
        h.update(r["reply"].encode())
        h.update(b"\x00")
    tokens = sum(r["eval_count"] for r in records)
    timed = sorted((r for r in records
                    if r["eval_count"] > 1 and r["eval_duration_ns"] > 0),
                   key=lambda r: (r["eval_count"] - 1)
                   / r["eval_duration_ns"])
    if len(timed) > 4:
        # Trim each arm's fastest and slowest record before pooling: one
        # GC pause or OS-scheduler stall hitting one stream otherwise
        # dominates the pooled rate on a shared CI box.
        timed = timed[1:-1]
    eval_toks = sum(r["eval_count"] - 1 for r in timed)
    eval_s = sum(r["eval_duration_ns"] / 1e9 for r in timed)
    spec = after.get("speculative") or {}
    return {
        "label": label, "mix": mix, "streams": n,
        "requests": len(records),
        "output_tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(tokens / wall, 2),
        # Pooled per-stream decode rate from the server's own
        # eval_duration (total decode tokens / total decode wall across
        # streams) — the "per-stream tok/s" the acceptance gate names.
        # Decode phase only, so queue wait / prefill / HTTP noise are
        # excluded by design, and pooling beats a median of few noisy
        # per-request rates on a loaded CI box.
        "per_stream_tok_s": round(eval_toks / eval_s, 2) if eval_s
        else None,
        "outputs_sha256": h.hexdigest(),
        "speculative": {k: spec.get(k) for k in
                        ("mode", "gamma", "drafted", "accepted",
                         "acceptance_rate", "rounds", "fallback_rounds",
                         "throttles")} if spec else None,
    }


def _compare_spec(args) -> dict:
    """The draft-free speculation artifact (README "Speculative
    decoding"): the same pinned echo-heavy greedy multi-turn mix served
    plain and with ngram self-drafting (byte-identical outputs required
    — speculation is a scheduling decision, never a behavior change),
    plus an adversarial no-echo sampled mix where the adaptive-γ
    throttle must keep the spec arm within noise of plain (spec never
    loses)."""
    cfg_snapshot = dict(vars(args))
    arms = {}
    for mix in ("echo", "adversarial"):
        for label, ngram in (("plain", False), ("ngram", True)):
            arms[f"{mix}_{label}"] = _spec_arm(args, label, mix, ngram)
    args.spec_mode = None

    def _ratio(a, b):
        return round(a / b, 4) if a and b else None

    ep, en = arms["echo_plain"], arms["echo_ngram"]
    ap, an = arms["adversarial_plain"], arms["adversarial_ngram"]
    espec = en["speculative"] or {}
    aspec = an["speculative"] or {}
    comparison = {
        "gamma": espec.get("gamma"),
        # Echo mix: the win. Byte-identity is the deterministic claim;
        # the per-stream decode ratio is the headline magnitude.
        "per_stream_tok_s_plain": ep["per_stream_tok_s"],
        "per_stream_tok_s_ngram": en["per_stream_tok_s"],
        "per_stream_ratio": _ratio(en["per_stream_tok_s"],
                                   ep["per_stream_tok_s"]),
        "tokens_per_s_plain": ep["tokens_per_s"],
        "tokens_per_s_ngram": en["tokens_per_s"],
        "tok_s_ratio": _ratio(en["tokens_per_s"], ep["tokens_per_s"]),
        "outputs_identical": (ep["outputs_sha256"]
                              == en["outputs_sha256"]),
        "acceptance_rate": espec.get("acceptance_rate"),
        "spec_drafted": espec.get("drafted"),
        "spec_accepted": espec.get("accepted"),
        # Adversarial mix: the insurance. The throttle must engage (or
        # matchless rounds fall back outright) and the per-stream decode
        # rate must stay within noise of plain. Per-stream (server-side
        # eval accounting) is the graded number for both mixes — the
        # wall-clock aggregates also carry prefill/HTTP/queue noise and
        # are reported transparently, not graded.
        "adversarial_per_stream_plain": ap["per_stream_tok_s"],
        "adversarial_per_stream_ngram": an["per_stream_tok_s"],
        "adversarial_ratio": _ratio(an["per_stream_tok_s"],
                                    ap["per_stream_tok_s"]),
        "adversarial_tok_s_plain": ap["tokens_per_s"],
        "adversarial_tok_s_ngram": an["tokens_per_s"],
        "adversarial_acceptance_rate": aspec.get("acceptance_rate"),
        "adversarial_throttles": aspec.get("throttles"),
        "adversarial_fallback_rounds": aspec.get("fallback_rounds"),
        # The artifact's claims. spec_wins carries the deterministic
        # parts (graded live by the tier-1 smoke); the >=1.3x /
        # >=0.95x magnitudes are graded on the committed artifact (the
        # ladder/tiering lanes' stance — CI wall clocks swing).
        "spec_wins": bool(
            ep["outputs_sha256"] == en["outputs_sha256"]
            and (espec.get("accepted") or 0) > 0
            and (en["per_stream_tok_s"] or 0)
            > (ep["per_stream_tok_s"] or 0)),
        "spec_never_loses": bool(
            (an["per_stream_tok_s"] or 0)
            >= 0.95 * (ap["per_stream_tok_s"] or 1e9)),
    }
    out = {"config": cfg_snapshot, **arms, "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result.update(arms)
    return result


def _wait_inflight_tokens(group, min_tokens: int,
                          timeout: float = 120.0) -> Optional[int]:
    """Block until the subprocess router has streamed ``min_tokens``
    across its tracked requests, then return the replica index holding
    the most in-flight work (the chaos victim). None if the burst
    finished first."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        with group._lock:
            entries = list(group._tracked.values())
            total = sum(len(e.tokens) for e in entries)
            if total >= min_tokens and entries:
                counts = {}
                for e in entries:
                    if e.worker is not None:
                        counts[e.worker.replica] = counts.get(
                            e.worker.replica, 0) + 1
                if counts:
                    return max(counts, key=counts.get)
        time.sleep(0.005)
    return None


def _fleet_arm(args, label: str, fleet: str, chaos: Optional[str] = None,
               migrate: bool = True,
               chaos_rpc: Optional[dict] = None) -> dict:
    """Boot one server on the given fleet backend, run the pinned
    greedy burst, optionally injecting mid-burst chaos (``"kill9"`` =
    SIGKILL the busiest worker; ``"drain"`` = graceful drain of the
    busiest worker, with or without KV migration; ``chaos_rpc`` =
    frame-level transport fault injection armed for the whole burst),
    and summarize."""
    import hashlib

    print(f"[replay] fleet arm: {label}", file=sys.stderr)
    args.fleet = fleet
    args.fleet_migrate = migrate
    args.worker_restart_backoff_s = 0.1
    args.worker_restart_max = 10
    srv, port, stop = start_server(args)
    group = srv.group
    chaos_fired = False
    try:
        # Warm requests before the clock starts: the fleet arms boot
        # without warmup (8 worker processes across the comparison), so
        # these keep lazy XLA compile out of the timed burst — the arms
        # then measure serving, not compile scheduling. Distinct cold
        # prompts ride the rotating tie-break so every replica warms.
        for i in range(2 * getattr(args, "dp", 1)):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/generate",
                data=json.dumps({"model": args.model,
                                 "prompt": f"[w{i}] warm",
                                 "temperature": 0.0, "stream": False,
                                 "options": {"num_predict": 4}}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=600).read()
        if chaos_rpc is not None:
            # Armed AFTER warmup (the warm pass is scaffolding, not the
            # graded burst) and for the burst's whole life: every frame
            # both directions rolls the seeded schedule.
            group.apply_chaos({"rpc": dict(chaos_rpc)})
            chaos_fired = True
        box = {}

        def run_burst():
            box["records"] = asyncio.run(_ladder_burst(
                port, args.model, args.fleet_streams, args.fleet_tokens))

        t0 = time.perf_counter()
        th = threading.Thread(target=run_burst, name="fleet-burst")
        th.start()
        if chaos is not None:
            # Let every stream get going, then hit the busiest worker
            # while its requests are mid-decode.
            victim = _wait_inflight_tokens(
                group, min_tokens=2 * args.fleet_streams)
            if victim is not None:
                if chaos == "kill9":
                    group.apply_chaos({"replica": victim,
                                       "kill": "kill9"})
                else:
                    group.drain_worker(victim, migrate=migrate)
                chaos_fired = True
        th.join()
        wall = time.perf_counter() - t0
        records = box["records"]
        if chaos_fired:
            # Let the supervisor finish the respawn before scraping, so
            # the arm records the restart it caused (the burst usually
            # outpaces worker boot).
            deadline = time.perf_counter() + 60
            while (time.perf_counter() < deadline
                   and not all(h.state == "up" for h in group.workers)):
                time.sleep(0.1)
        after = json.loads(scrape_metrics(port, fmt="json")[0])
        health = group.health_snapshot()
    finally:
        # Stop the fleet explicitly: the bench's loop-stop shortcut
        # skips aiohttp cleanup, and subprocess workers are real OS
        # processes that must not outlive their arm.
        group.stop(drain=False)
        stop()
    h = hashlib.sha256()
    for r in sorted(records, key=lambda r: r["idx"]):
        h.update(f"{r['idx']}:".encode())
        h.update(r["reply"].encode())
        h.update(b"\x00")
    tokens = sum(r["output_tokens"] for r in records)
    sup = after.get("supervision") or {}
    return {
        "label": label, "fleet": fleet, "chaos": chaos,
        "fleet_migrate": migrate, "chaos_fired": chaos_fired,
        "requests": len(records),
        "output_tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(tokens / wall, 2),
        "e2e_s": _percentiles([r["e2e_s"] for r in records],
                              ps=(50, 95)),
        "outputs_sha256": h.hexdigest(),
        "failovers": sup.get("failovers", 0),
        "retries_attempted": sup.get("retries_attempted", 0),
        "worker_restarts": sup.get("worker_restarts", 0),
        "migrations": sup.get("migrations", 0),
        "migrated_pages": sup.get("migrated_pages", 0),
        "migrated_bytes": sup.get("migrated_bytes", 0),
        "resume_resubmits": sup.get("resume_resubmits", 0),
        "resume_recomputed_tokens": sup.get(
            "resume_recomputed_tokens", 0),
        "resume_reused_tokens": sup.get("resume_reused_tokens", 0),
        "swap_in_resumes": sup.get("swap_in_resumes",
                                   after.get("swap_in_resumes", 0)),
        # Byzantine-transport counters (README "Failure model"): the
        # chaos-rpc lane grades these; zero everywhere else.
        "worker_reconnects": sup.get("worker_reconnects", 0),
        "rpc_timeouts": sup.get("rpc_timeouts", 0),
        "frame_errors": sup.get("frame_errors", 0),
        "kv_integrity_rejections": sup.get("kv_integrity_rejections", 0),
        "poison_requests": sup.get("poison_requests", 0),
        "fleet_status": health.get("status"),
    }


def _compare_fleet(args) -> dict:
    """The process-fleet artifact (README "Process fleet"): one pinned
    greedy burst served by (a) the in-process thread fleet, (b) the
    subprocess worker fleet, and (c) the subprocess fleet with a worker
    SIGKILLed mid-decode — outputs must be byte-identical across ALL
    arms (failover resumes replay the router's token record, so even a
    killed worker's streams complete exactly); then the pinned DRAIN
    scenario twice — graceful SIGTERM-drain with KV page migration vs
    plain resubmission — so one committed file carries the migration
    win: swap-in-resumes > 0 and strictly fewer recomputed tokens than
    the resubmission arm."""
    args.fleet_tokens = 32
    cfg_snapshot = {k: v for k, v in vars(args).items()
                    if not k.startswith("_")}
    arms = {}
    arms["in_process"] = _fleet_arm(args, "in_process", "in-process")
    arms["subprocess"] = _fleet_arm(args, "subprocess", "subprocess")
    arms["subprocess_kill"] = _fleet_arm(
        args, "subprocess_kill", "subprocess", chaos="kill9")
    arms["drain_migrate"] = _fleet_arm(
        args, "drain_migrate", "subprocess", chaos="drain", migrate=True)
    arms["drain_resubmit"] = _fleet_arm(
        args, "drain_resubmit", "subprocess", chaos="drain",
        migrate=False)
    args.fleet = "in-process"

    ip, sp = arms["in_process"], arms["subprocess"]
    kill = arms["subprocess_kill"]
    dm, dr = arms["drain_migrate"], arms["drain_resubmit"]
    shas = {a["outputs_sha256"] for a in arms.values()}
    comparison = {
        "streams": args.fleet_streams,
        "tokens_per_s_in_process": ip["tokens_per_s"],
        "tokens_per_s_subprocess": sp["tokens_per_s"],
        # The RPC-hop cost (or multi-process win — workers dodge the
        # router's GIL), reported transparently.
        "tok_s_ratio": round(sp["tokens_per_s"]
                             / max(ip["tokens_per_s"], 1e-9), 4),
        "e2e_p50_in_process_s": ip["e2e_s"]["p50"],
        "e2e_p50_subprocess_s": sp["e2e_s"]["p50"],
        # Byte-identity across backends AND chaos: the fleet is a
        # placement/supervision decision, never a behavior change.
        "outputs_identical": len(shas) == 1,
        # kill -9 arm: the real out-of-process failure mode.
        "kill_chaos_fired": kill["chaos_fired"],
        "failover_count": kill["failovers"],
        "kill_worker_restarts": kill["worker_restarts"],
        "kill_fleet_status": kill["fleet_status"],
        # Drain scenario: migration vs resubmission.
        "migrations": dm["migrations"],
        "migrated_pages": dm["migrated_pages"],
        "migrated_bytes": dm["migrated_bytes"],
        "swap_in_resumes": dm["swap_in_resumes"],
        "recomputed_tokens_migrate": dm["resume_recomputed_tokens"],
        "recomputed_tokens_resubmit": dr["resume_recomputed_tokens"],
        "reused_tokens_migrate": dm["resume_reused_tokens"],
        "reused_tokens_resubmit": dr["resume_reused_tokens"],
        # The artifact's claims (acceptance): byte-identity everywhere,
        # the killed worker's streams failed over and completed, and
        # drain-time migration swap-in-resumed with strictly fewer
        # recomputed tokens than resubmission.
        "failover_wins": bool(
            len(shas) == 1 and kill["chaos_fired"]
            and kill["failovers"] >= 1
            and kill["worker_restarts"] >= 1),
        "migration_wins": bool(
            dm["chaos_fired"] and dr["chaos_fired"]
            and dm["swap_in_resumes"] > 0
            and dm["migrated_pages"] > 0
            and dm["resume_recomputed_tokens"]
            < dr["resume_recomputed_tokens"]),
    }
    out = {"config": cfg_snapshot, **arms, "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result.update(arms)
    return result


def _compare_chaos_rpc(args) -> dict:
    """The Byzantine-transport artifact (README "Failure model"): the
    pinned greedy burst served by a clean dp=2 subprocess fleet, then
    by the same fleet under seeded frame-level RPC chaos — random byte
    corruption and injected delays on every router<->worker frame in
    both directions, plus ONE wedged connection (socket open, writes
    silently swallowed) mid-burst. Acceptance: outputs byte-identical
    across both arms (every corrupt frame was caught by the codec CRC
    and the connection recycled+resynced — zero silent corruptions),
    frame errors and RPC timeouts actually counted, connections were
    reconnected WITHOUT any worker process restart, and p95 latency
    inflation stays bounded (detection deadlines, not hangs)."""
    args.fleet_tokens = 32
    cfg_snapshot = {k: v for k, v in vars(args).items()
                    if not k.startswith("_")}
    arms = {}
    arms["clean"] = _fleet_arm(args, "clean", "subprocess")
    arms["chaos_rpc"] = _fleet_arm(
        args, "chaos_rpc", "subprocess",
        chaos_rpc={
            # Seeded: the whole fault schedule replays bit-for-bit
            # (test_chaos_deterministic_schedule holds the contract).
            "seed": 20240,
            # ~1 frame in 50 corrupted: a handful of CRC rejections +
            # connection recycles across the burst's few hundred
            # frames, on both directions.
            "corrupt_rate": 0.02,
            # Transport jitter on every 10th frame.
            "delay_rate": 0.1, "delay_s": 0.01,
            # One connection wedges right as the burst opens (router->
            # worker writes swallowed); the per-verb deadline watchdog
            # must recycle it, not hang the stream or restart the
            # process. The frame count is per-connection and corruption
            # recycles connections, so the trigger sits low enough to
            # fire before a CRC hit can reset the count.
            "wedge_after": 2, "wedge_replica": 0,
            "direction": "both",
        })
    args.fleet = "in-process"

    clean, chaos = arms["clean"], arms["chaos_rpc"]
    identical = clean["outputs_sha256"] == chaos["outputs_sha256"]
    p95_clean = max(clean["e2e_s"]["p95"], 1e-9)
    inflation = round(chaos["e2e_s"]["p95"] / p95_clean, 3)
    comparison = {
        "streams": args.fleet_streams,
        "chaos_fired": chaos["chaos_fired"],
        # Byte-identity IS the zero-silent-corruption claim: a single
        # adopted corrupt frame would change some stream's bytes.
        "outputs_identical": identical,
        "silent_corruptions": 0 if identical else 1,
        "frame_errors": chaos["frame_errors"],
        "rpc_timeouts": chaos["rpc_timeouts"],
        "worker_reconnects": chaos["worker_reconnects"],
        "kv_integrity_rejections": chaos["kv_integrity_rejections"],
        # Transport faults are repaired at the connection, never the
        # process: restarts under chaos must stay at zero.
        "worker_restarts_chaos": chaos["worker_restarts"],
        "tokens_per_s_clean": clean["tokens_per_s"],
        "tokens_per_s_chaos": chaos["tokens_per_s"],
        "e2e_p95_clean_s": clean["e2e_s"]["p95"],
        "e2e_p95_chaos_s": chaos["e2e_s"]["p95"],
        "p95_inflation": inflation,
        # Bounded: detection is deadline-driven (3 fast deadlines for
        # the wedge, one frame for a CRC hit), so chaos costs a
        # constant few seconds — not a hang. The 20x ceiling is a
        # loaded-CI-box guard, not a perf claim.
        "p95_inflation_bounded": inflation <= 20.0,
        "chaos_wins": bool(
            identical and chaos["chaos_fired"]
            and chaos["frame_errors"] >= 1
            and chaos["rpc_timeouts"] >= 1
            and chaos["worker_reconnects"] >= 1
            and chaos["worker_restarts"] == 0
            and inflation <= 20.0),
    }
    out = {"config": cfg_snapshot, **arms, "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result.update(arms)
    return result


async def _fabric_burst(port: int, model: str, reqs: list,
                        n_predict: int) -> list:
    """Fire the given (trace_id, prompt) requests at once, greedy and
    non-streamed. Client timing is recorded but the lane grades the
    SERVER-side per-request spans (/debug/requests), matched back by
    the X-Request-Id each request carries."""
    import aiohttp

    url = f"http://127.0.0.1:{port}/api/generate"
    timeout = aiohttp.ClientTimeout(total=1800)

    async def one(session, tid: str, prompt: str) -> dict:
        payload = {"model": model, "prompt": prompt, "temperature": 0.0,
                   "stream": False,
                   "options": {"num_predict": n_predict}}
        t0 = time.perf_counter()
        async with session.post(url, json=payload,
                                headers={"X-Request-Id": tid}) as resp:
            resp.raise_for_status()
            rec = await resp.json()
        return {"trace_id": tid, "reply": rec.get("response", ""),
                "e2e_s": time.perf_counter() - t0,
                "output_tokens": rec.get("eval_count", 0)}

    async with aiohttp.ClientSession(timeout=timeout) as session:
        return list(await asyncio.gather(
            *[one(session, t, pr) for t, pr in reqs]))


def _fabric_spans(port: int, prefix: str) -> list:
    """The server-side request spans whose trace id starts with
    ``prefix``, ordered by enqueue time (finished_unix - e2e_s: the
    spans carry no enqueue stamp, but every wave fires concurrently so
    the difference recovers arrival order)."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/requests?n=128",
            timeout=60) as r:
        spans = json.loads(r.read())
    out = [s for s in spans
           if str(s.get("trace_id", "")).startswith(prefix)]
    out.sort(key=lambda s: s.get("finished_unix", 0.0)
             - s.get("e2e_s", 0.0))
    return out


def _fabric_arm(args, label: str, fabric_on: bool,
                warmboot: bool = False) -> dict:
    """Boot a dp=2 subprocess fleet (fabric pool on or off), run the
    pinned shared-system-prompt workload — one seed turn, then two
    concurrent returning-user waves — optionally scaling up a third
    worker between the waves (the warm-boot grade), and summarize from
    the server-side spans."""
    import hashlib

    print(f"[replay] fabric arm: {label}", file=sys.stderr)
    args.fleet = "subprocess"
    args.fabric_cache_pages = (args.fabric_pool_pages if fabric_on
                               else 0)
    page = args.page_size
    prefix_tokens = args.fabric_prefix_pages * page
    # Byte tokenizer: chars == tokens, so the shared system prompt is
    # exactly fabric-prefix-pages FULL pages and every user's distinct
    # tail starts on the next page boundary — all users share the same
    # prefix digest chain.
    shared = ("You are a terse, careful assistant. Cite sources. "
              * ((prefix_tokens // 49) + 1))[:prefix_tokens]
    srv, port, stop = start_server(args)
    group = srv.group
    records = []

    def _pressure(replica: int) -> None:
        # Chaos page pressure is the lane's deterministic stand-in for
        # a saturated replica: the worker holds every free page, the
        # raised preempt watermark keeps free+evictable under it, and
        # the router's pressure bit routes the next wave AROUND the
        # replica — the saturation moment the fabric exists for.
        group.apply_chaos({"replica": replica,
                           "page_pressure": args.num_pages})
        deadline = time.perf_counter() + 15
        while time.perf_counter() < deadline:
            reps = group.health_snapshot()["replicas"]
            if (replica < len(reps)
                    and reps[replica].get("under_pressure")):
                return
            time.sleep(0.05)
        raise RuntimeError(
            f"replica {replica} never reported under_pressure")

    def _pool_settle(still: float = 0.8, timeout: float = 15.0) -> None:
        # Publishes ride async event frames; wait until the pool's
        # page count has been still for a beat so a later growth wait
        # can't count straggling earlier publishes.
        deadline = time.perf_counter() + timeout
        last, t_last = group.fabric.used, time.perf_counter()
        while time.perf_counter() < deadline:
            now = group.fabric.used
            if now != last:
                last, t_last = now, time.perf_counter()
            elif time.perf_counter() - t_last >= still:
                return
            time.sleep(0.05)

    try:
        # Compile warmth (the arms boot without warmup): distinct cold
        # prompts ride the rotating tie-break so every replica
        # compiles BOTH prefill buckets the measured waves use — the
        # big bucket (a cold shared-prefix prefill) and the small one
        # (a warm tail-only prefill). Without this, the fabric-off
        # arm's first cross-replica turn would pay compile + prefill
        # while the fabric-on arm's paid only compile — a contrast
        # that isn't the fabric's.
        dp = getattr(args, "dp", 1)
        warm_len = prefix_tokens + 2 * page
        longs = [(f"[w{i}] warm " + "compile pad " * 64)[:warm_len]
                 for i in range(dp)]
        for prompt in longs + [f"[w{i + dp}] warm" for i in range(dp)]:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/generate",
                data=json.dumps({"model": args.model,
                                 "prompt": prompt,
                                 "temperature": 0.0, "stream": False,
                                 "options": {"num_predict": 4}}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=600).read()
        # Decode-ladder warmth: a concurrent burst fills every decode
        # lane on both replicas so the batch rungs compile here — not
        # scattered across the measured waves, where a rung compile
        # would dwarf the prefill contrast being graded.
        asyncio.run(_fabric_burst(
            port, args.model,
            [(f"wmc{i:02d}", f"[c{i:02d}] spin") for i in range(4 * dp)],
            12))
        if fabric_on:
            _pool_settle()
        pool_baseline = group.fabric.used
        # Seed turn: ONE user prefills the shared system prompt,
        # somewhere. With the fabric on, its settled pages publish to
        # the router pool — the only fleet-wide prefill of the prefix.
        records += asyncio.run(_fabric_burst(
            port, args.model, [("seed", shared + " u00")],
            args.fabric_tokens))
        seed_span = (_fabric_spans(port, "seed") or [{}])[0]
        seed_replica = int(seed_span.get("routed_replica", 0))
        if fabric_on:
            # Wait until the pool grew by the whole prefix before
            # grading the returning wave.
            deadline = time.perf_counter() + 15
            while (time.perf_counter() < deadline
                   and group.fabric.used
                   < pool_baseline + args.fabric_prefix_pages):
                time.sleep(0.05)
        # Saturate the replica that prefilled the prefix, then the
        # returning wave: users sharing the system prompt arrive at
        # once and ALL route to the other replica — which either
        # recomputes the prefix (fabric off) or pulls it from the pool
        # (fabric on). This wave's server-side TTFT p95 is the graded
        # stat.
        _pressure(seed_replica)
        # Swap-path warmth (unmeasured): repeat each warm long with a
        # fresh tail. With the seed replica saturated these land on the
        # OTHER replica: the primer whose prefix lived on the pressured
        # replica restores it through the host tier (fabric on) or
        # recomputes it (fabric off) — compiling the swap-in scatter
        # and the first publish's offload gather on the measured
        # replica BEFORE the graded wave (the shared prefix itself
        # stays un-pulled: the wave's fabric hit is still the first).
        records += asyncio.run(_fabric_burst(
            port, args.model,
            [(f"pr{i:02d}", longs[i] + f" p{i:02d}") for i in range(dp)],
            args.fabric_tokens))
        t0 = time.perf_counter()
        w1 = [(f"w1u{i:02d}", shared + f" u{i:02d}")
              for i in range(1, args.fabric_users + 1)]
        records += asyncio.run(_fabric_burst(
            port, args.model, w1, args.fabric_tokens))
        wave1_wall = time.perf_counter() - t0
        new_replica = None
        wb_host_pages = 0
        if warmboot:
            # Saturate EVERY original replica, then scale up: _spawn
            # pushes the fabric hot set into the new worker BEFORE it
            # becomes routable, so the second wave lands on a worker
            # that never prefilled a byte yet serves its first request
            # already warm.
            _pool_settle()
            for h in list(group.workers):
                if h.replica != seed_replica:
                    _pressure(h.replica)
            group._scale_up("bench-warmboot")
            new_replica = max(h.replica for h in group.workers)
            deadline = time.perf_counter() + 90
            while (time.perf_counter() < deadline
                   and not all(h.state == "up" for h in group.workers)):
                time.sleep(0.1)
            for h, w in zip(group.workers,
                            group.health_snapshot()["replicas"]):
                if h.replica == new_replica:
                    wb_host_pages = int(
                        (w.get("host_cache") or {}).get("pages_used", 0))
        # Second wave: more returning users. In the scale-up arm every
        # old replica is saturated, so the wave lands on the
        # warm-booted worker; in the base arms it lands on the replica
        # wave 1 warmed.
        w2 = [(f"w2u{i:02d}", shared + f" u{i:02d}")
              for i in range(50, 50 + args.fabric_wave2_users)]
        records += asyncio.run(_fabric_burst(
            port, args.model, w2, args.fabric_tokens))
        w1_spans = _fabric_spans(port, "w1u")
        w2_spans = _fabric_spans(port, "w2u")
        fabric_snap = group.fabric.snapshot()
        sup = group.supervision_counters()
    finally:
        group.stop(drain=False)
        stop()

    h = hashlib.sha256()
    for r in sorted(records, key=lambda r: r["trace_id"]):
        h.update(f"{r['trace_id']}:".encode())
        h.update(r["reply"].encode())
        h.update(b"\x00")

    def _prefix_recomputed(span: dict) -> int:
        return max(0, prefix_tokens - int(span.get("cached_tokens", 0)))

    cross = [s for s in w1_spans
             if s.get("routed_replica") != seed_replica]
    wb_spans = ([s for s in w2_spans
                 if s.get("routed_replica") == new_replica]
                if new_replica is not None else [])
    wb_first = wb_spans[0] if wb_spans else None
    return {
        "label": label, "fabric_on": fabric_on, "warmboot": warmboot,
        "requests": len(records),
        "outputs_sha256": h.hexdigest(),
        "prefix_tokens": prefix_tokens,
        "wave1_wall_s": round(wave1_wall, 3),
        # Server-side TTFT (enqueue -> first token) of the graded
        # returning wave.
        "returning_ttft_s": _percentiles(
            [s.get("ttft_s", 0.0) for s in w1_spans], ps=(50, 95)),
        "wave2_ttft_s": _percentiles(
            [s.get("ttft_s", 0.0) for s in w2_spans], ps=(50, 95)),
        "seed_replica": seed_replica,
        # Returning turns the router spilled onto a replica that never
        # prefilled the shared prompt — the fabric's reason to exist.
        "cross_replica_turns": len(cross),
        "cross_fabric_hit_pages": sum(
            int(s.get("route_fabric_hit_pages", 0)) for s in cross),
        "cross_host_restored_pages": sum(
            int(s.get("host_restored_pages", 0)) for s in cross),
        # Shared-prefix tokens the wave recomputed anywhere (0 =
        # prefilled once fleet-wide).
        "prefix_recomputed_tokens": sum(
            _prefix_recomputed(s) for s in w1_spans),
        "cross_first_turn": (None if not cross else {
            "trace_id": cross[0].get("trace_id"),
            "replica": cross[0].get("routed_replica"),
            "route_fabric_hit_pages":
                int(cross[0].get("route_fabric_hit_pages", 0)),
            "host_restored_pages":
                int(cross[0].get("host_restored_pages", 0)),
            "cached_tokens": int(cross[0].get("cached_tokens", 0)),
            "prefix_recomputed_tokens": _prefix_recomputed(cross[0]),
        }),
        # Warm-boot grade (scale-up arm only): host pages the new
        # worker held BEFORE serving anything, and its first request's
        # warmth (all of it fabric-sourced — the worker never prefilled
        # a byte before this).
        "warmboot_replica": new_replica,
        "warmboot_host_pages": wb_host_pages,
        "warmboot_requests": len(wb_spans),
        "warmboot_first_turn": (None if wb_first is None else {
            "trace_id": wb_first.get("trace_id"),
            "route_hit_pages": int(wb_first.get("route_hit_pages", 0)),
            "route_fabric_hit_pages":
                int(wb_first.get("route_fabric_hit_pages", 0)),
            "host_restored_pages":
                int(wb_first.get("host_restored_pages", 0)),
            "cached_tokens": int(wb_first.get("cached_tokens", 0)),
            "prefix_recomputed_tokens": _prefix_recomputed(wb_first),
        }),
        "fabric": fabric_snap,
        "route_fabric_hits": sup.get("route_fabric_hits", 0),
        "fabric_puts": sup.get("fabric_puts", 0),
        "fabric_hits": sup.get("fabric_hits", 0),
        "kv_integrity_rejections": sup.get("kv_integrity_rejections", 0),
    }


def _compare_fabric(args) -> dict:
    """The fleet-KV-fabric artifact (README "KV fabric"): many users
    sharing one long system prompt, served three ways — fabric off
    (every replica pays its own prefix prefill), fabric on (the prefix
    is prefilled ONCE fleet-wide and every other replica pulls it from
    the router pool), and fabric on with a mid-run scale-up whose new
    worker warm-boots from the pool and serves its first request
    already warm. Outputs must stay byte-identical across every arm:
    the fabric moves settled KV bytes, it never changes them."""
    cfg_snapshot = {k: v for k, v in vars(args).items()
                    if not k.startswith("_")}
    arms = {}
    arms["fabric_off"] = _fabric_arm(args, "fabric_off", False)
    arms["fabric_on"] = _fabric_arm(args, "fabric_on", True)
    arms["fabric_warmboot"] = _fabric_arm(
        args, "fabric_warmboot", True, warmboot=True)
    args.fleet = "in-process"

    off, on, wb = (arms["fabric_off"], arms["fabric_on"],
                   arms["fabric_warmboot"])
    shas = {a["outputs_sha256"] for a in arms.values()}
    ratio = (off["returning_ttft_s"]["p95"]
             / max(on["returning_ttft_s"]["p95"], 1e-9))
    wb_first = wb.get("warmboot_first_turn") or {}
    comparison = {
        "users": args.fabric_users,
        "prefix_tokens": on["prefix_tokens"],
        # Byte-identity across all arms: pooled pages are the same
        # bit-exact serialized KV the point-to-point paths move.
        "outputs_identical": len(shas) == 1,
        # The fleet-wide prefill-once claim: with the fabric on, no
        # returning turn recomputes a shared-prefix token anywhere —
        # the cross-replica turns adopt pooled pages instead.
        "prefix_recomputed_tokens_off": off["prefix_recomputed_tokens"],
        "prefix_recomputed_tokens_on": on["prefix_recomputed_tokens"],
        "cross_replica_turns_on": on["cross_replica_turns"],
        "cross_fabric_hit_pages_on": on["cross_fabric_hit_pages"],
        "prefix_prefilled_once": bool(
            on["cross_replica_turns"] >= 1
            and on["cross_fabric_hit_pages"] >= args.fabric_prefix_pages
            and on["prefix_recomputed_tokens"] == 0
            and (on["cross_first_turn"] or {}).get(
                "route_fabric_hit_pages", 0) > 0),
        # Returning-turn TTFT p95, fabric off vs on (>= 1.3x is the
        # artifact's acceptance claim; CPU-noise makes it a committed-
        # artifact grade, not a live tier-1 assert).
        "returning_ttft_p95_off_s": off["returning_ttft_s"]["p95"],
        "returning_ttft_p95_on_s": on["returning_ttft_s"]["p95"],
        "returning_ttft_ratio": round(ratio, 4),
        "fabric_ttft_wins": bool(ratio >= 1.3),
        # Warm worker boot: the scaled-up worker held pooled pages
        # before its first request, and that request's warmth is
        # fabric-sourced (the worker had prefilled nothing).
        "warmboot_host_pages": wb["warmboot_host_pages"],
        "warmboot_requests": wb["warmboot_requests"],
        "warmboot_first_hit_pages": wb_first.get("route_hit_pages", 0),
        "warmboot_wins": bool(
            wb["warmboot_host_pages"] > 0
            and wb["warmboot_requests"] >= 1
            and wb_first.get("route_hit_pages", 0) > 0
            and wb_first.get("prefix_recomputed_tokens", 1) == 0),
        "fabric_wins": bool(
            len(shas) == 1
            and on["cross_replica_turns"] >= 1
            and on["prefix_recomputed_tokens"] == 0
            and on["fabric_hits"] > 0 and on["fabric_puts"] > 0),
    }
    out = {"config": cfg_snapshot, **arms, "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result.update(arms)
    return result


def _kv_plane_arm(args, label: str, plane: str) -> dict:
    """Boot a 1-prefill + 1-decode subprocess fleet on one KV data
    plane, run the pinned handoff-heavy burst — an unmeasured compile
    warm wave, the measured wave, then a kill -9 wave — and summarize
    the per-request handoff walls and the router's relayed-blob books."""
    import hashlib
    import threading

    print(f"[replay] kv-plane arm: {label}", file=sys.stderr)
    args.fleet = "subprocess"
    args.worker_roles = ("prefill", "decode")
    args.kv_plane = plane
    args.fabric_cache_pages = args.kvp_pool_pages
    args.worker_restart_backoff_s = 0.1
    args.worker_restart_max = 10
    page = args.page_size
    prompt_tokens = args.kvp_prompt_pages * page
    srv, port, stop = start_server(args)
    group = srv.group
    records = []

    def _wave(tag: str, n: int, start: int = 0) -> list:
        # Distinct per-user bodies (the tag+index is IN the page-0
        # content) so nothing prefix-caches away: every request
        # prefills its own ~kvp-prompt-pages pages and hands the whole
        # context off to the decode worker.
        reqs = [(f"{tag}{i:02d}",
                 (f"[{tag}{i:02d}] " + "kv plane payload " * 512)
                 [:prompt_tokens])
                for i in range(start, start + n)]
        return asyncio.run(_fabric_burst(port, args.model, reqs,
                                         args.kvp_tokens))

    try:
        # Compile warmth (the arms boot without warmup): the same wave
        # shape as the measured one, so the big prefill bucket, the
        # decode rungs at full width, and the handoff export/adopt
        # graphs all compile HERE — the measured wave times the data
        # plane, not XLA.
        records += _wave("wm", args.kvp_users)
        # Sequential warm singles: the concurrent wave above compiles
        # the full-width decode rungs, but a lone request rides the
        # batch-1 rung — its first trip through prefill+handoff+decode
        # still pays one-time setup (rung compile, allocator paths)
        # that would otherwise land as a ~40 ms outlier inside the
        # measured series and own its p95.
        for i in range(3):
            records += _wave("ws", 1, start=i)
        # Measured handoffs, SEQUENTIAL: one request in flight at a
        # time, so each wall prices exactly one trip through the data
        # plane with no cross-request compute queueing contaminating
        # the spans (the concurrent regime's walls measure the router
        # backlog and the decode worker's step queue, identically in
        # both arms — not the plane).
        t0 = time.perf_counter()
        for i in range(args.kvp_users):
            records += _wave("kw", 1, start=i)
        wave_wall = time.perf_counter() - t0
        # Per-request handoff+adopt wall, measured across processes on
        # the assembled trace timeline (the /debug/trace stance: every
        # span carries its emitter's unix-anchored timestamps): from
        # the prefill worker's "handoff_export" span END — the moment
        # the serialized payload exists and the data plane takes over —
        # to the decode worker's "handoff_adopt" span END. The window
        # covers everything the PLANES differ on: arena publish vs
        # frame send, the router's event-socket ingest and dispatch
        # (where the relay arm carries megabytes in and out), and the
        # adoption read+restore. The export span itself (device KV
        # gather + serialize) is identical prefill-side compute on
        # either plane and is reported separately below.
        walls, exports, adopts, legs = [], [], [], []
        for i in range(args.kvp_users):
            sp = {}
            for s in group._recorder.get_trace(f"kw{i:02d}") or ():
                if s.get("name") in ("handoff_export", "handoff",
                                     "handoff_adopt"):
                    sp[s["name"]] = (float(s.get("ts", 0.0)),
                                     float(s.get("dur", 0.0)))
            if "handoff_export" in sp:
                exports.append(sp["handoff_export"][1])
            if "handoff_adopt" in sp:
                adopts.append(sp["handoff_adopt"][1])
            if "handoff_export" in sp and "handoff_adopt" in sp:
                t_exp = sum(sp["handoff_export"])
                t_done = sum(sp["handoff_adopt"])
                walls.append(max(0.0, t_done - t_exp))
                if "handoff" in sp:
                    # The wall's legs on the assembled timeline: the
                    # export, the event-frame transit into the router
                    # (where the relay arm carries the payload), the
                    # router's routing+dispatch span (where it carries
                    # it out again), and the decode worker's admission
                    # wait + adoption.
                    legs.append({
                        "export_s": round(sp["handoff_export"][1], 6),
                        "transit_in_s": round(
                            sp["handoff"][0]
                            - sum(sp["handoff_export"]), 6),
                        "route_dispatch_s": round(sp["handoff"][1], 6),
                        "sched_wait_s": round(
                            sp["handoff_adopt"][0]
                            - sum(sp["handoff"]), 6),
                        "adopt_s": round(sp["handoff_adopt"][1], 6),
                    })
        blob_bytes_measured = dict(group.rpc_blob_bytes)
        sup_measured = group.supervision_counters()
        # Kill -9 mid-wave: fire the wave, then SIGKILL the prefill
        # worker while its handoffs are in flight. The shm arm's
        # supervisor must reclaim the dead incarnation's slabs via the
        # region epoch bump; the caught-out requests recompute-resume
        # (byte-identical under greedy) — the relay fallback books
        # below record whatever blob traffic the salvage paths moved.
        prefill_replica = next(
            h.replica for h in group.workers
            if group.roles[h.replica] == "prefill")
        kill_records: list = []
        kill_err: list = []

        def _kill_wave() -> None:
            try:
                kill_records.extend(_wave("kk", args.kvp_users))
            except Exception as e:          # surfaced after join
                kill_err.append(e)

        t = threading.Thread(target=_kill_wave)
        t.start()
        time.sleep(0.25)
        group.apply_chaos({"replica": prefill_replica, "kill": "kill9"})
        t.join(timeout=600)
        assert not t.is_alive(), "kill wave never finished"
        if kill_err:
            raise kill_err[0]
        records += kill_records
        deadline = time.perf_counter() + 90
        while (time.perf_counter() < deadline
               and not all(h.state == "up" for h in group.workers)):
            time.sleep(0.1)
        sup = group.supervision_counters()
        blob_bytes_final = dict(group.rpc_blob_bytes)
        shm_reclaims = group.shm_reclaims
        fabric_snap = group.fabric.snapshot()
    finally:
        group.stop(drain=False)
        stop()

    h = hashlib.sha256()
    for r in sorted(records, key=lambda r: r["trace_id"]):
        h.update(f"{r['trace_id']}:".encode())
        h.update(r["reply"].encode())
        h.update(b"\x00")
    return {
        "label": label, "kv_plane": plane,
        "requests": len(records),
        "outputs_sha256": h.hexdigest(),
        "prompt_tokens": prompt_tokens,
        "wave_wall_s": round(wave_wall, 3),
        # Handoff+adopt wall of the measured wave (export settled ->
        # adoption complete: transit + route + dispatch + adopt), per
        # request.
        "handoff_wall_s": _percentiles(walls, ps=(50, 95)),
        # The wall's worker-side legs (identical work in both arms:
        # KV gather+serialize on the prefill side, restore on the
        # decode side) — everything between them is the data plane.
        "handoff_export_s": _percentiles(exports, ps=(50, 95)),
        "handoff_adopt_s": _percentiles(adopts, ps=(50, 95)),
        "handoff_legs_p50_s": {
            k: round(float(np.median([leg[k] for leg in legs])), 6)
            for k in (legs[0] if legs else ())},
        "handoff_walls_observed": len(walls),
        # Router-relayed KV payload bytes by verb, before and after
        # the kill wave: the measured-phase books grade the zero-copy
        # claim; the final books show what the post-kill salvage /
        # fallback paths moved (the relay fallback is a feature).
        "rpc_blob_bytes_measured": blob_bytes_measured,
        "rpc_blob_bytes": blob_bytes_final,
        "pd_handoffs_measured": sup_measured.get("pd_handoffs", 0),
        "pd_handoffs": sup.get("pd_handoffs", 0),
        "pd_adoptions": sup.get("pd_adoptions", 0),
        "pd_handoff_recomputes": sup.get("pd_handoff_recomputes", 0),
        "recompute_resumes": sup.get("recompute_resumes", 0),
        "resume_recomputed_tokens": sup.get(
            "resume_recomputed_tokens", 0),
        "worker_restarts": sup.get("worker_restarts", 0),
        "kv_integrity_rejections": sup.get(
            "kv_integrity_rejections", 0),
        "shm_reclaims": shm_reclaims,
        "fabric_puts": sup.get("fabric_puts", 0),
        "fabric": fabric_snap,
        "kill_wave_requests": len(kill_records),
    }


def _compare_kv_plane(args) -> dict:
    """The zero-copy KV data plane artifact (README "KV data plane"):
    the same handoff-heavy burst through a 1-prefill + 1-decode
    subprocess fleet on both planes — KV blobs relayed through router
    frames vs handed worker-to-worker through the shared-memory page
    arena. The planes move the same bytes, so outputs must stay
    byte-identical; the shm arm's router must relay ~0 KV payload
    bytes on the handoff/fabric verbs; and a kill -9 mid-wave must
    reclaim the dead worker's slabs and recompute-resume cleanly."""
    cfg_snapshot = {k: v for k, v in vars(args).items()
                    if not k.startswith("_")}
    arms = {}
    arms["relay"] = _kv_plane_arm(args, "relay", "relay")
    arms["shm"] = _kv_plane_arm(args, "shm", "shm")
    args.worker_roles, args.fleet, args.kv_plane = (), "in-process", \
        "relay"

    relay, shm = arms["relay"], arms["shm"]
    shas = {a["outputs_sha256"] for a in arms.values()}
    ratio = (relay["handoff_wall_s"]["p95"]
             / max(shm["handoff_wall_s"]["p95"], 1e-9))
    shm_m, relay_m = (shm["rpc_blob_bytes_measured"],
                      relay["rpc_blob_bytes_measured"])
    comparison = {
        "users": args.kvp_users,
        "prompt_tokens": relay["prompt_tokens"],
        # Byte-identity: a descriptor adoption reads the same bit-exact
        # serialized KV the relay frames carry (incl. through the kill
        # wave's recompute-resumes).
        "outputs_identical": len(shas) == 1,
        # The zero-copy claim, graded on the measured phase (before
        # the kill wave's INTENTIONAL relay fallbacks): with the shm
        # plane on, no KV payload byte traversed a router frame on any
        # verb, while the relay arm moved every handoff through the
        # router twice (handoff event in, dispatch out) plus every
        # fabric publish.
        "rpc_blob_bytes_measured_relay": relay_m,
        "rpc_blob_bytes_measured_shm": shm_m,
        "shm_zero_copy": bool(
            sum(shm_m.values()) == 0
            and relay_m.get("handoff", 0) > 0
            and relay_m.get("submit", 0) > 0
            and relay_m.get("fabric_put", 0) > 0),
        # Handoff+adopt wall p95, relay vs shm (>= 1.5x is the
        # artifact's acceptance claim; CPU-noise makes it a committed-
        # artifact grade, not a live tier-1 assert).
        "handoff_p95_relay_s": relay["handoff_wall_s"]["p95"],
        "handoff_p95_shm_s": shm["handoff_wall_s"]["p95"],
        "handoff_p95_ratio": round(ratio, 4),
        "shm_handoff_wins": bool(ratio >= 1.5),
        # Kill -9 mid-wave: the dead prefill incarnation's slabs were
        # reclaimed via the epoch bump (shm arm), the worker restarted,
        # and every request in both arms' kill waves still finished
        # byte-identically (recompute-resume fallback).
        "shm_reclaims": shm["shm_reclaims"],
        "worker_restarts": {k: a["worker_restarts"]
                            for k, a in arms.items()},
        "kill_recovered": bool(
            shm["shm_reclaims"] >= 1
            and all(a["worker_restarts"] >= 1 for a in arms.values())
            and all(a["kill_wave_requests"] == args.kvp_users
                    for a in arms.values())),
        "kv_integrity_rejections": {
            k: a["kv_integrity_rejections"] for k, a in arms.items()},
        "kv_plane_wins": bool(
            len(shas) == 1
            and sum(shm_m.values()) == 0
            and relay_m.get("handoff", 0) > 0
            and shm["shm_reclaims"] >= 1
            and shm["pd_handoffs_measured"] > 0
            and all(a["kv_integrity_rejections"] == 0
                    for a in arms.values())),
    }
    out = {"config": cfg_snapshot, **arms, "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result.update(arms)
    return result


def _diurnal_schedule(args) -> list:
    """The pinned BurstGPT-shaped mini-diurnal: a quiet trickle (one
    interactive arrival per second — the trough), then a peak wave
    arriving inside half a second (>= 20x the trough's offered load),
    then silence — the night the autoscaler drains back down. Batch
    jobs land just ahead of the peak's interactives so the wave hits a
    fleet already saturated by the class the interactives preempt."""
    sched, idx = [], 0
    for i in range(args.elastic_quiet_requests):
        sched.append({"idx": idx, "t": float(i), "cls": "interactive",
                      "prompt": f"[q{idx:02d}] tick", "max_tokens": 8})
        idx += 1
    t_peak = float(args.elastic_quiet_requests)
    for i in range(args.elastic_burst_batch):
        # Batch jobs carry the bulk of the work: enough generation
        # budget that the peak saturates the single worker for tens of
        # seconds — park time is what breaches the SLO sensor, and the
        # burst must still be in flight when the rolling upgrade hits.
        sched.append({"idx": idx, "t": t_peak + 0.02 * i, "cls": "batch",
                      "prompt": f"[b{idx:02d}] job", "max_tokens": 96})
        idx += 1
    for i in range(args.elastic_burst_interactive):
        sched.append({"idx": idx, "t": t_peak + 0.1 + 0.02 * i,
                      "cls": "interactive",
                      "prompt": f"[i{idx:02d}] ask", "max_tokens": 12})
        idx += 1
    return sched


async def _diurnal_burst(port: int, model: str, schedule: list) -> list:
    """Fire the diurnal schedule: one streamed greedy request per entry
    at its arrival offset, tagged with its X-Priority class, recording
    client TTFT (first streamed chunk). 429/503 answers are retried per
    the client contract (README "Elastic fleet"): Retry-After hint plus
    FULL-jitter exponential backoff, from a shared retry budget —
    budget exhaustion sheds instead of amplifying the overload."""
    import aiohttp

    url = f"http://127.0.0.1:{port}/api/generate"
    timeout = aiohttp.ClientTimeout(total=1800)
    budget = {"n": 6 * len(schedule)}

    async def one(session, req: dict) -> dict:
        await asyncio.sleep(req["t"])
        payload = {"model": model, "prompt": req["prompt"],
                   "temperature": 0.0, "stream": True,
                   "options": {"num_predict": req["max_tokens"]}}
        headers = {"X-Priority": req["cls"],
                   "X-Request-Id": f"el-{req['idx']:02d}"}
        rec = {"idx": req["idx"], "cls": req["cls"], "t": req["t"],
               "shed": False, "retries": 0, "ttft_s": None,
               "e2e_s": None, "reply": "", "output_tokens": 0}
        t0 = time.perf_counter()
        for attempt in range(12):
            async with session.post(url, json=payload,
                                    headers=headers) as resp:
                if resp.status in (429, 503):
                    if budget["n"] <= 0 or attempt >= 11:
                        rec["shed"], rec["retries"] = True, attempt
                        return rec
                    budget["n"] -= 1
                    try:
                        hint = float(resp.headers.get("Retry-After", ""))
                    except ValueError:
                        hint = 0.0
                    await asyncio.sleep(hint + random.uniform(
                        0.0, min(10.0, 0.25 * (2 ** attempt))))
                    continue
                resp.raise_for_status()
                parts = []
                async for line in resp.content:
                    if not line.strip():
                        continue
                    if rec["ttft_s"] is None:
                        rec["ttft_s"] = time.perf_counter() - t0
                    obj = json.loads(line)
                    if obj.get("done"):
                        rec["output_tokens"] = obj.get("eval_count", 0)
                    else:
                        parts.append(obj.get("response", ""))
                rec["reply"] = "".join(parts)
                rec["e2e_s"] = time.perf_counter() - t0
                rec["retries"] = attempt
                return rec
        return rec

    async with aiohttp.ClientSession(timeout=timeout) as session:
        return list(await asyncio.gather(*[one(session, r)
                                           for r in schedule]))


def _elastic_arm(args, label: str, elastic: bool) -> dict:
    """One diurnal pass: ``elastic=False`` pins a single fixed
    subprocess worker with the legacy global 429 cap; ``elastic=True``
    turns on the autoscaler and the per-class lanes, and fires a
    rolling upgrade over HTTP once the scale-up has landed (so the
    upgrade replaces BOTH live workers under the burst)."""
    print(f"[replay] elastic arm: {label}", file=sys.stderr)
    args.fleet = "subprocess"
    args.fleet_migrate = True
    args.worker_restart_backoff_s = 0.1
    args.worker_restart_max = 10
    args.autoscale = elastic
    args.autoscale_min_replicas = 1
    args.autoscale_max_replicas = 2
    args.autoscale_breach_window_s = 1.0
    args.autoscale_cooldown_s = 2.0
    args.autoscale_low_watermark = 0.05
    args.autoscale_idle_window_s = 1.5
    args.default_class = "interactive"
    args.class_queue_depth = 32 if elastic else 0
    schedule = _diurnal_schedule(args)
    srv, port, stop = start_server(args)
    group = srv.group
    rollout: dict = {}
    try:
        # Router-path warm pass (worker boots already ran engine
        # warmup): first-request setup stays out of the measured
        # diurnal — the arms time serving, not compile.
        for i in range(2):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/generate",
                data=json.dumps({"model": args.model,
                                 "prompt": f"[w{i}] warm",
                                 "temperature": 0.0, "stream": False,
                                 "options": {"num_predict": 4}}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=600).read()
        box = {}

        def run_burst():
            box["records"] = asyncio.run(
                _diurnal_burst(port, args.model, schedule))

        t0 = time.perf_counter()
        th = threading.Thread(target=run_burst, name="diurnal-burst")
        th.start()
        if elastic:
            # Mid-replay rolling upgrade: wait for the breach-driven
            # scale-up to land, then replace every live worker one at a
            # time — under the still-running burst.
            deadline = time.perf_counter() + 90
            while time.perf_counter() < deadline and group.scale_ups < 1:
                time.sleep(0.05)
            while (time.perf_counter() < deadline
                   and not all(h.state == "up"
                               for h in group._live_workers())):
                time.sleep(0.05)
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/debug/rollout", data=b"{}",
                headers={"Content-Type": "application/json"},
                method="POST")
            rollout = json.loads(
                urllib.request.urlopen(req, timeout=600).read())
        th.join()
        wall = time.perf_counter() - t0
        records = box["records"]
        if elastic:
            # The night shift: idle occupancy under the low watermark
            # must drain the extra replica once the breach samples age
            # out of the sensor horizon.
            deadline = time.perf_counter() + 90
            while (time.perf_counter() < deadline
                   and group.scale_downs < 1):
                time.sleep(0.2)
        after = json.loads(scrape_metrics(port, fmt="json")[0])
        prom = scrape_metrics(port)[0]
        health = group.health_snapshot()
        traces = {
            "scale_up": group.trace_snapshot("scale-up-1") is not None,
            "scale_down":
                group.trace_snapshot("scale-down-1") is not None,
            "rollout": group.trace_snapshot("rollout-1") is not None,
        }
    finally:
        group.stop(drain=False)
        stop()
    sup = after.get("supervision") or {}
    done = [r for r in records if not r["shed"]]
    by_cls = lambda c: [r for r in done if r["cls"] == c]  # noqa: E731

    def _ttft(rs):
        return _percentiles([r["ttft_s"] for r in rs
                             if r["ttft_s"] is not None], ps=(50, 95))

    return {
        "label": label, "elastic": elastic,
        "requests": len(records), "completed": len(done),
        "client_shed": {c: sum(1 for r in records
                               if r["shed"] and r["cls"] == c)
                        for c in ("interactive", "batch")},
        "client_retries": sum(r["retries"] for r in records),
        "wall_s": round(wall, 3),
        "output_tokens": sum(r["output_tokens"] for r in done),
        "interactive_ttft_s": _ttft(by_cls("interactive")),
        "batch_ttft_s": _ttft(by_cls("batch")),
        "interactive_e2e_s": _percentiles(
            [r["e2e_s"] for r in by_cls("interactive")], ps=(50, 95)),
        "replies": {str(r["idx"]): r["reply"] for r in done},
        "scale_ups": sup.get("scale_ups", 0),
        "scale_downs": sup.get("scale_downs", 0),
        "rollouts": sup.get("rollouts", 0),
        "rollout": rollout,
        "class_preemptions": sup.get("class_preemptions", {}),
        "server_shed": sup.get("class_shed", {}),
        "scale_events_in_metrics": bool(
            re.search(r"^tpu_inf_fleet_scale_ups_total [1-9]", prom,
                      re.M)
            and re.search(r"^tpu_inf_fleet_scale_downs_total [1-9]",
                          prom, re.M)) if elastic else False,
        "traces": traces,
        "fleet_status": health.get("status"),
        "worker_restarts": sup.get("worker_restarts", 0),
        "migrations": sup.get("migrations", 0),
        "migrated_pages": sup.get("migrated_pages", 0),
    }


def _compare_elastic(args) -> dict:
    """The elastic-fleet artifact (README "Elastic fleet"): the pinned
    mini-diurnal (>= 20x offered-load swing, mixed priority classes)
    through a fixed one-worker fleet and through the elastic fleet —
    autoscaler + class lanes + a mid-burst rolling upgrade — grading
    the PR's acceptance claims in one committed file: interactive TTFT
    p95 holds the SLO while batch absorbs the slack, the fleet scales
    up AND back down (events in /metrics and /debug/trace), the
    upgrade replaces every worker with zero failed requests, and
    greedy outputs stay byte-identical across arms."""
    cfg_snapshot = {k: v for k, v in vars(args).items()
                    if not k.startswith("_")}
    peak = args.elastic_burst_interactive + args.elastic_burst_batch
    # Offered load: the trough trickles 1 req/s; the peak wave lands
    # inside one second.
    load_swing = float(peak)
    arms = {}
    arms["fixed"] = _elastic_arm(args, "fixed", elastic=False)
    arms["elastic"] = _elastic_arm(args, "elastic", elastic=True)
    fx, el = arms["fixed"], arms["elastic"]
    slo_s = args.slo_ttft_ms / 1000.0
    common = sorted(set(fx["replies"]) & set(el["replies"]), key=int)
    identical = bool(common) and all(fx["replies"][k] == el["replies"][k]
                                     for k in common)
    el_int_p95 = (el["interactive_ttft_s"] or {}).get("p95")
    interactive_shed = (el["client_shed"].get("interactive", 0)
                        + el["server_shed"].get("interactive", 0))
    comparison = {
        "slo_ttft_s": slo_s,
        "load_swing": load_swing,
        "requests": fx["requests"],
        "interactive_ttft_p95_fixed_s":
            (fx["interactive_ttft_s"] or {}).get("p95"),
        "interactive_ttft_p95_elastic_s": el_int_p95,
        "interactive_slo_held_elastic": bool(
            el_int_p95 is not None and el_int_p95 <= slo_s),
        "batch_preemptions_elastic":
            el["class_preemptions"].get("batch", 0),
        "interactive_shed_elastic": interactive_shed,
        "batch_shed_elastic": (el["client_shed"].get("batch", 0)
                               + el["server_shed"].get("batch", 0)),
        "shed_fixed": dict(fx["client_shed"]),
        "scale_ups": el["scale_ups"],
        "scale_downs": el["scale_downs"],
        "scale_events_in_metrics": el["scale_events_in_metrics"],
        "scale_events_in_trace": bool(el["traces"]["scale_up"]
                                      and el["traces"]["scale_down"]),
        "rollout_replaced": len(el["rollout"].get("replaced", [])),
        "rollout_failed": len(el["rollout"].get("failed", [])),
        "rollout_in_trace": el["traces"]["rollout"],
        # In-flight sequences drained off retiring workers during the
        # scale-down + rollout (reported here; the under-traffic
        # migration claim itself is pinned in tests/test_elastic.py).
        "migrations_elastic": el["migrations"],
        "elastic_completed_all": el["completed"] == el["requests"],
        "outputs_identical_common": identical,
        "common_requests": len(common),
    }
    # The acceptance gate, one boolean: every claim the committed
    # artifact makes, graded from this run.
    comparison["elastic_wins"] = bool(
        load_swing >= 20
        and comparison["interactive_slo_held_elastic"]
        and comparison["batch_preemptions_elastic"] > 0
        and interactive_shed == 0
        and comparison["elastic_completed_all"]
        and el["scale_ups"] >= 1 and el["scale_downs"] >= 1
        and comparison["scale_events_in_metrics"]
        and comparison["scale_events_in_trace"]
        and comparison["rollout_replaced"] >= 1
        and comparison["rollout_failed"] == 0
        and comparison["rollout_in_trace"]
        and identical)
    out = {"config": cfg_snapshot, **arms, "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result.update(arms)
    return result


def _grade_handoff_traces(chrome: dict) -> dict:
    """Grade a Chrome-trace export for the P/D acceptance claim: at
    least one handed-off request whose spans appear under ONE trace id
    across THREE pids (router + prefill worker + decode worker), with
    the handoff export/adopt spans adjacent to and non-overlapping with
    the prefill/decode spans. Same-process comparisons are exact; the
    one cross-process gap (export end -> adopt start) allows a 5 ms
    wall-clock anchor tolerance."""
    by_trace: dict = {}
    for e in chrome.get("traceEvents", ()):
        if e.get("ph") != "X":
            continue
        tid = (e.get("args") or {}).get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(e)
    total = clean = 0
    example = None
    for tid, evs in by_trace.items():
        spans = {}
        for e in sorted(evs, key=lambda e: e["ts"]):
            spans.setdefault(e["name"], e)
        need = ("prefill", "handoff_export", "handoff_adopt", "decode")
        if not all(k in spans for k in need):
            continue
        pids = {e["pid"] for e in evs}
        if len(pids) < 3:
            continue
        total += 1

        def end(e):
            return e["ts"] + e["dur"]

        pf, ex = spans["prefill"], spans["handoff_export"]
        ad, de = spans["handoff_adopt"], spans["decode"]
        ok = (pf["pid"] == ex["pid"] and ad["pid"] == de["pid"]
              and ex["pid"] != ad["pid"]
              and end(pf) <= ex["ts"] + 1          # same process: exact
              and end(ex) <= ad["ts"] + 5000       # cross-process: 5 ms
              and end(ad) <= de["ts"] + 1)
        if ok:
            clean += 1
            example = example or tid
    return {"handoff_traces_3pid": total,
            "handoff_traces_clean": clean,
            "adjacency_ok": total > 0 and clean == total,
            "example_trace_id": example}


# Long-prompt loads the pressure generator keeps in flight at once: 2
# per mixed worker (its other 2 slots hold the decode streams), and on
# the pd split 4 on the prefill worker — whose slots hold nothing else,
# because a num_predict=1 load finishes at prefill-settle and never
# reaches the decode tier.
PD_LOADS_IN_FLIGHT = 4


async def _pd_burst(port: int, model: str, n_streams: int,
                    decode_tokens: int, pressure: bool,
                    load_tokens: int, load_cap: int,
                    load_tag: str = "L") -> tuple:
    """The P/D lane's workload: ``n_streams`` steady greedy decode
    streams plus — when ``pressure`` — a CONTINUOUS long-prompt prefill
    burst: from the moment every stream has delivered its first chunk
    until the last stream finishes, a generator keeps
    PD_LOADS_IN_FLIGHT loads in flight (capped at ``load_cap`` total, a
    runaway bound), so every stream's entire decode window runs under
    sustained prefill pressure — no race between a one-shot volley and
    the windows it must overlap. Returns (streams, loads, issued)."""
    import aiohttp

    url = f"http://127.0.0.1:{port}/api/generate"
    timeout = aiohttp.ClientTimeout(total=1800)
    first_chunk = [asyncio.Event() for _ in range(n_streams)]
    streams_done = asyncio.Event()
    n_done = [0]

    async def stream(session, i: int) -> dict:
        prompt = f"[s{i:02d}] steady decode"
        payload = {"model": model, "prompt": prompt,
                   "temperature": 0.0, "stream": True,
                   "options": {"num_predict": decode_tokens}}
        text, final = [], {}
        t0 = time.perf_counter()
        ttft = None
        async with session.post(url, json=payload) as resp:
            resp.raise_for_status()
            async for line in resp.content:
                if not line.strip():
                    continue
                rec = json.loads(line)
                tok = rec.get("response", "")
                if tok:
                    if ttft is None:
                        ttft = time.perf_counter() - t0
                    text.append(tok)
                    first_chunk[i].set()
                if rec.get("done"):
                    final = rec
                    break
        n_done[0] += 1
        if n_done[0] == n_streams:
            streams_done.set()
        return {"idx": i, "reply": "".join(text),
                "ttft_s": round(ttft, 6) if ttft is not None else None,
                # Router-side decode window (the Ollama eval fields):
                # first token -> finish, measured by the serving
                # process — the stalls a prefill inflicts on decode
                # land here, while the measuring CLIENT's own
                # event-loop hiccups (this is a shared CPU) do not.
                "eval_count": final.get("eval_count", 0),
                "eval_duration_ns": final.get("eval_duration", 0),
                "output_tokens": final.get("eval_count", 0)}

    async def load(session, j: int) -> dict:
        # One long prompt, ONE-token reply: pure prefill pressure —
        # the request finishes at prefill-settle (its token comes out
        # of the prefill dispatch), so on the pd split a load never
        # occupies a decode-worker slot and on the mixed arms it adds
        # no decode work, only the prefill interference this lane
        # exists to measure. Content is deterministic and distinct per
        # index (and per warm/measured pass via load_tag — a measured
        # load must never hit the warm pass's prefix cache, or the
        # burst stops being prefill work).
        body = f"[{load_tag}{j:02d}] " + "the quick onyx tpu jumps "
        prompt = (body * (load_tokens // len(body) + 1))[:load_tokens]
        payload = {"model": model, "prompt": prompt,
                   "temperature": 0.0, "stream": False,
                   "options": {"num_predict": 1}}
        t0 = time.perf_counter()
        async with session.post(url, json=payload) as resp:
            resp.raise_for_status()
            rec = await resp.json()
        e2e = time.perf_counter() - t0
        # A num_predict=1 unary reply: the whole response IS the first
        # token, so e2e stands in for TTFT in the SLO comparison pool.
        return {"idx": j, "reply": rec.get("response", ""),
                "ttft_s": round(e2e, 6),
                "e2e_s": round(e2e, 4)}

    issued = [0]

    async def pump(session) -> list:
        await asyncio.gather(*[fc.wait() for fc in first_chunk])
        results, pending = [], set()
        waiter = asyncio.ensure_future(streams_done.wait())
        while not streams_done.is_set() and issued[0] < load_cap:
            while (len(pending) < PD_LOADS_IN_FLIGHT
                   and issued[0] < load_cap):
                pending.add(asyncio.ensure_future(
                    load(session, issued[0])))
                issued[0] += 1
            done, pending = await asyncio.wait(
                pending | {waiter},
                return_when=asyncio.FIRST_COMPLETED)
            pending.discard(waiter)
            results.extend(d.result() for d in done if d is not waiter)
        if pending:
            # Stop ISSUING at streams-done; in-flight loads complete
            # (the idle fleet drains them in milliseconds).
            results.extend(await asyncio.gather(*pending))
        if not waiter.done():
            waiter.cancel()
        return sorted(results, key=lambda r: r["idx"])

    async with aiohttp.ClientSession(timeout=timeout) as session:
        tasks = [stream(session, i) for i in range(n_streams)]
        if pressure:
            tasks.append(pump(session))
        res = await asyncio.gather(*tasks)
    return (res[:n_streams], (res[n_streams] if pressure else []),
            issued[0])


def _pd_tpot(streams: list) -> dict:
    """Per-stream decode TPOT (the main replay summary's definition:
    decode window over tokens-1, per request) reduced to p50/p95
    across streams, from the server's own eval accounting. The
    whole-window mean is the right estimator on a shared CPU: every
    stall a prefill inflicts on a stream lands in its window SUM,
    while measurement hiccups amortize over the stream's 100+
    tokens."""
    tpots = [s["eval_duration_ns"] / 1e9 / (s["eval_count"] - 1)
             for s in streams if s["eval_count"] > 1]
    return _percentiles(tpots, ps=(50, 95))


def _pd_tpot_merged(passes: list) -> dict:
    """Per-stream TPOT pooled across repeated passes of the same
    workload (sum of windows over sum of token gaps, per stream index),
    then p50/p95 across streams — the unloaded baseline runs twice and
    merges, halving the single-pass scheduling noise a 1-core host
    inflicts on a 1-2s window."""
    dur: dict = {}
    cnt: dict = {}
    for streams in passes:
        for s in streams:
            if s["eval_count"] > 1:
                dur[s["idx"]] = dur.get(s["idx"], 0) \
                    + s["eval_duration_ns"] / 1e9
                cnt[s["idx"]] = cnt.get(s["idx"], 0) \
                    + s["eval_count"] - 1
    tpots = [dur[i] / cnt[i] for i in sorted(dur) if cnt[i]]
    return _percentiles(tpots, ps=(50, 95))


def _pd_outputs_sha(streams: list) -> str:
    import hashlib

    h = hashlib.sha256()
    for r in sorted(streams, key=lambda r: r["idx"]):
        h.update(f"{r['idx']}:".encode())
        h.update(r["reply"].encode())
        h.update(b"\x00")
    return h.hexdigest()


def _pd_arm(args, label: str, roles: tuple,
            hybrid: bool = False) -> dict:
    """Boot one dp=2 subprocess topology, run warm + unloaded +
    loaded passes of the pinned workload, and summarize."""
    print(f"[replay] pd arm: {label}", file=sys.stderr)
    args.fleet = "subprocess"
    args.worker_roles = roles
    args.hybrid_prefill = hybrid
    args.worker_restart_backoff_s = 0.1
    args.worker_restart_max = 10
    srv, port, stop = start_server(args)
    group = srv.group
    n, dt = args.pd_streams, args.pd_decode_tokens
    nl, lt = args.pd_load_prompts, args.pd_load_prompt_tokens
    # Every client-measured TTFT this arm's server sees, across every
    # phase (pin requests, warm pass, baselines, loaded) — the SAME
    # population the workers' rolling SLO windows observed, so the
    # gauge-vs-replay comparison is apples to apples.
    client_ttfts: list = []

    def _collect(streams, loads=()):
        client_ttfts.extend(r["ttft_s"] for r in list(streams) + list(loads)
                            if r.get("ttft_s") is not None)

    chrome_trace = None
    try:
        # Pin stream placement first: prefill each stream prompt
        # SEQUENTIALLY so the rotating cold tie-break alternates
        # workers deterministically (2+2 on the mixed arms) and the
        # measured phases inherit that placement via prefix affinity —
        # concurrent cold admission with stale load peeks can land
        # 3+1, which skews the p95-across-streams baseline.
        for i in range(n):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/generate",
                data=json.dumps({"model": args.model,
                                 "prompt": f"[s{i:02d}] steady decode",
                                 "temperature": 0.0, "stream": False,
                                 "options": {"num_predict": 4}}).encode(),
                headers={"Content-Type": "application/json"})
            t_pin = time.perf_counter()
            urllib.request.urlopen(req, timeout=600).read()
            # Unary 4-token replies: e2e ~= TTFT at this size; close
            # enough for the pooled p95 of a ~100-request population.
            client_ttfts.append(round(time.perf_counter() - t_pin, 6))
        # UNMEASURED warm pass of the exact loaded workload (distinct
        # load content, a handful of loads): compiles every lazy graph
        # this arm will touch — prefill buckets, chunked/hybrid prefill
        # at real occupancy, decode, and (pd) the handoff export/adopt
        # path — so measured phases time serving, not XLA.
        warm_s, warm_l, _ = asyncio.run(
            _pd_burst(port, args.model, n, dt, True, lt,
                      load_cap=6, load_tag="W"))
        _collect(warm_s, warm_l)
        # Unloaded baseline x2 (merged per stream: a single 1-2s pass
        # on a 1-core host carries scheduling noise the merge halves).
        base_a, _, _ = asyncio.run(
            _pd_burst(port, args.model, n, dt, False, lt, 0))
        base_b, _, _ = asyncio.run(
            _pd_burst(port, args.model, n, dt, False, lt, 0))
        _collect(base_a)
        _collect(base_b)
        loaded_streams, loads, issued = asyncio.run(
            _pd_burst(port, args.model, n, dt, True, lt, nl))
        _collect(loaded_streams, loads)
        after = json.loads(scrape_metrics(port, fmt="json")[0])
        health = group.health_snapshot()
        if label == "pd" and getattr(args, "trace_artifact", None):
            # The Chrome-trace artifact (README "Observability"): the
            # recent-request ring over real HTTP — handed-off requests
            # show spans from three pids under one trace id.
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/trace?format=chrome",
                    timeout=60) as r:
                chrome_trace = json.loads(r.read().decode())
    finally:
        group.stop(drain=False)
        stop()
    sup = after.get("supervision") or {}
    sha_base = _pd_outputs_sha(base_a)
    sha_loaded = _pd_outputs_sha(loaded_streams)
    tpot_base = _pd_tpot_merged([base_a, base_b])
    tpot_loaded = _pd_tpot(loaded_streams)
    # SLO-gauge tracking: the fleet's pooled rolling-window TTFT p95
    # (scraped off the live servers) vs the same population's
    # client-measured p95, computed with the ring's own estimator so
    # the comparison isolates measurement-point skew (HTTP overhead),
    # not estimator choice.
    from tpu_inference import telemetry as _tm

    slo = {k: v for k, v in (after.get("slo") or {}).items()
           if not k.endswith("_window")}
    client_p95 = _tm.pooled_quantile([client_ttfts], 0.95)
    client_p95 = round(client_p95, 6) if client_p95 is not None else None
    gauge_p95 = slo.get("ttft_p95_s")
    ratio = (round(gauge_p95 / client_p95, 4)
             if gauge_p95 and client_p95 else None)
    return {
        "slo": slo,
        "client_ttft_p95_s": client_p95,
        "client_ttft_requests": len(client_ttfts),
        "slo_ttft_p95_tracking_ratio": ratio,
        "_chrome_trace": chrome_trace,
        "label": label, "roles": list(roles) or ["mixed", "mixed"],
        "hybrid_prefill": hybrid,
        "streams": n, "decode_tokens": dt,
        "loads_issued": issued, "loads_completed": len(loads),
        "load_prompt_tokens": lt,
        "output_tokens": sum(s["output_tokens"]
                             for s in loaded_streams),
        # Decode TPOT (per-stream window mean), per phase.
        "decode_tpot_s_unloaded": tpot_base,
        "decode_tpot_s_loaded": tpot_loaded,
        "decode_tpot_p95_ratio": (
            round(tpot_loaded["p95"] / tpot_base["p95"], 4)
            if tpot_base["p95"] else None),
        "load_e2e_s": _percentiles([r["e2e_s"] for r in loads],
                                   ps=(50, 95)),
        # Byte-identity: the same streams must read the same in both
        # phases (warm cache is a placement detail) and across arms.
        "outputs_sha256": sha_base,
        "outputs_phases_identical": (
            sha_base == sha_loaded == _pd_outputs_sha(base_b)),
        "load_replies": [r["reply"] for r in loads],
        "pd_handoffs": sup.get("pd_handoffs", 0),
        "pd_adoptions": sup.get("pd_adoptions", 0),
        "pd_handoff_recomputes": sup.get("pd_handoff_recomputes", 0),
        "resume_recomputed_tokens": sup.get(
            "resume_recomputed_tokens", 0),
        "worker_restarts": sup.get("worker_restarts", 0),
        "fleet_status": health.get("status"),
    }


def _compare_pd(args) -> dict:
    """The P/D disaggregation artifact (README "P/D disaggregation"):
    the pinned long-prompt burst through three dp=2 subprocess
    topologies — mixed (every worker runs both phases), hybrid (mixed
    + PR-4 fused prefill-decode steps), and pd (1 prefill + 1 decode
    worker with live KV handoff). Each arm measures decode TPOT p95
    unloaded (decode streams only) then loaded (same streams + a
    prefill burst >= 10x the streams' own prefill tokens). The pd
    split keeps decode cadence flat — prefill never enters the decode
    engine, and on shared-CPU hosts the prefill tier is nice()d down
    (pd_prefill_nice; on TPU the isolation is physical) — while mixed/
    hybrid serialize prefill INTO the decode engine's dispatch stream,
    an interference no priority can remove. Outputs must be
    byte-identical across every arm and phase, and the pd arm's clean
    handoffs must recompute zero tokens."""
    cfg_snapshot = {k: v for k, v in vars(args).items()
                    if not k.startswith("_")}
    arms = {}
    arms["mixed"] = _pd_arm(args, "mixed", ())
    arms["hybrid"] = _pd_arm(args, "hybrid", (), hybrid=True)
    arms["pd"] = _pd_arm(args, "pd", ("prefill", "decode"))
    args.worker_roles, args.fleet = (), "in-process"

    # Chrome-trace artifact (README "Observability"): the pd arm's
    # recent-request ring, graded for the one-trace-three-pids
    # handoff claim and the SLO-gauge tracking claim, then written as
    # pure Chrome trace-event JSON (grading rides in otherData so the
    # file stays Perfetto-loadable).
    chrome = arms["pd"].pop("_chrome_trace", None)
    for a in arms.values():
        a.pop("_chrome_trace", None)
    trace_grading = None
    if chrome is not None:
        trace_grading = _grade_handoff_traces(chrome)
        trace_grading["slo"] = dict(arms["pd"]["slo"])
        trace_grading["client_ttft_p95_s"] = \
            arms["pd"]["client_ttft_p95_s"]
        trace_grading["slo_ttft_p95_tracking_ratio"] = \
            arms["pd"]["slo_ttft_p95_tracking_ratio"]
        trace_grading["slo_tracks_within_10pct"] = bool(
            arms["pd"]["slo_ttft_p95_tracking_ratio"] is not None
            and abs(arms["pd"]["slo_ttft_p95_tracking_ratio"] - 1.0)
            <= 0.10)
        chrome.setdefault("otherData", {}).update(trace_grading)
        if getattr(args, "trace_artifact", None):
            _write_out(args.trace_artifact, chrome)
            print(f"[replay] chrome trace artifact -> "
                  f"{args.trace_artifact}", file=sys.stderr)

    mixed, hybrid, pd = arms["mixed"], arms["hybrid"], arms["pd"]
    shas = {a["outputs_sha256"] for a in arms.values()}
    phases_ok = all(a["outputs_phases_identical"]
                    for a in arms.values())
    # A load's single greedy token is deterministic per index content,
    # so the arms must agree on every load they have in common (each
    # arm absorbs a different COUNT under pressure — the pd arm's
    # nice()d prefill tier grinds slower by design).
    n_common = min(a["loads_completed"] for a in arms.values())
    loads_ok = n_common > 0 and len(
        {tuple(a["load_replies"][:n_common])
         for a in arms.values()}) == 1
    for a in arms.values():
        del a["load_replies"]
    # Offered prefill tokens vs the streams' own prompts: every arm's
    # generator ISSUED at least min_issued loads into its fleet while
    # the streams decoded.
    stream_prefill = args.pd_streams * 18      # "[sNN] steady decode"
    min_issued = min(a["loads_issued"] for a in arms.values())
    comparison = {
        "prefill_load_ratio": round(
            (stream_prefill
             + min_issued * args.pd_load_prompt_tokens)
            / stream_prefill, 1),
        "loads_issued": {k: a["loads_issued"]
                         for k, a in arms.items()},
        "loads_completed": {k: a["loads_completed"]
                            for k, a in arms.items()},
        "decode_tpot_p95_unloaded_s": {
            k: a["decode_tpot_s_unloaded"]["p95"]
            for k, a in arms.items()},
        "decode_tpot_p95_loaded_s": {
            k: a["decode_tpot_s_loaded"]["p95"]
            for k, a in arms.items()},
        "decode_tpot_p95_ratio": {
            k: a["decode_tpot_p95_ratio"] for k, a in arms.items()},
        # The lane's headline: under the 10x+ prefill burst the pd
        # arm's decode TPOT p95 holds within 10% of its own unloaded
        # baseline; the in-engine topologies degrade.
        "pd_tpot_flat": bool(pd["decode_tpot_p95_ratio"] is not None
                             and pd["decode_tpot_p95_ratio"] <= 1.10),
        "hybrid_degrades": bool(
            hybrid["decode_tpot_p95_ratio"] is not None
            and pd["decode_tpot_p95_ratio"] is not None
            and hybrid["decode_tpot_p95_ratio"] >= 1.25
            and hybrid["decode_tpot_p95_ratio"]
            > pd["decode_tpot_p95_ratio"]),
        "mixed_tpot_p95_ratio": mixed["decode_tpot_p95_ratio"],
        "outputs_identical": bool(len(shas) == 1 and loads_ok
                                  and phases_ok),
        "pd_handoffs": pd["pd_handoffs"],
        "pd_adoptions": pd["pd_adoptions"],
        # Clean-handoff path: adoption restores the exported KV (incl.
        # the partial final page) — nothing recomputes.
        "pd_handoff_recomputes": pd["pd_handoff_recomputes"],
        "pd_recomputed_tokens": pd["resume_recomputed_tokens"],
        "pd_clean_handoffs": bool(pd["pd_handoffs"] > 0
                                  and pd["pd_handoff_recomputes"] == 0
                                  and pd["resume_recomputed_tokens"]
                                  == 0),
        # Distributed tracing + SLO gauges (README "Observability"):
        # the pd arm's cross-process trace grading and the rolling
        # TTFT-p95 gauge vs the replay's own measurement.
        "trace": trace_grading,
        "slo_breaches": {k: {"ttft": (a["slo"] or {}).get(
                                 "ttft_breaches"),
                             "tpot": (a["slo"] or {}).get(
                                 "tpot_breaches")}
                         for k, a in arms.items()},
    }
    comparison["pd_wins"] = bool(
        comparison["outputs_identical"]
        and comparison["pd_clean_handoffs"]
        and comparison["pd_tpot_flat"]
        and comparison["hybrid_degrades"])
    out = {"config": cfg_snapshot, **arms, "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    _write_out(args.out, out)
    result = dict(comparison)
    result.update(arms)
    return result


def _write_out(path, record) -> None:
    if not path:
        return
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


if __name__ == "__main__":
    main()
