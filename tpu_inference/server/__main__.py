"""CLI entry point: ``python -m tpu_inference.server --model tiny-llama``.

The reference has no CLI (argparse commented out; reference:
traffic_generator/main.py:4). This is the serve() entry SURVEY.md §3.5
plans for.
"""

from __future__ import annotations

import argparse

from aiohttp import web

from tpu_inference.config import PRESETS


def main() -> None:
    p = argparse.ArgumentParser(description="TPU-native LLM inference server "
                                            "(Ollama-protocol endpoint)")
    p.add_argument("--model", default="tiny-llama",
                   help=f"preset ({', '.join(sorted(PRESETS))}), a HF "
                        "checkpoint dir (config.json read for the "
                        "architecture), or 'auto' with --checkpoint")
    p.add_argument("--tokenizer", default="byte",
                   help="'byte', a local HF tokenizer dir, or 'auto' "
                        "(= the checkpoint dir's tokenizer when present)")
    p.add_argument("--checkpoint", default=None,
                   help="HF safetensors directory (random init if omitted)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=11434)
    from tpu_inference.engine.autosize import int_or_auto

    p.add_argument("--max-batch-size", type=int_or_auto, default=8,
                   help="decode slots in the batched graph, or 'auto': "
                        "size from the chip's HBM after weights "
                        "(engine/autosize.py)")
    p.add_argument("--decode-ladder", default="auto",
                   help="compiled decode-graph batch ladder: 'auto' "
                        "(doubling rungs 8/16/32/... up to max-batch-"
                        "size — with --max-batch-size auto this is the "
                        "HBM-derived ladder), 'off' (one graph at "
                        "max-batch-size, legacy), or explicit comma "
                        "rungs e.g. '8,16,32'. The engine dispatches "
                        "at the smallest rung covering the occupied "
                        "lanes and steps between rungs as occupancy "
                        "changes (README 'Batch ladder')")
    p.add_argument("--ladder-admit-headroom-pages", type=int, default=0,
                   help="batch-ladder admission guard: growing the "
                        "batch past the base rung must leave this many "
                        "reclaimable KV pages spare, so more lanes "
                        "never drain the pool to the preemption "
                        "watermark or churn the hot cache set; 0 = off")
    p.add_argument("--num-pages", type=int_or_auto, default=512,
                   help="KV pool pages, or 'auto': fill the HBM left "
                        "after weights + activation headroom")
    p.add_argument("--target-ctx", type=int, default=0,
                   help="with auto sizing: expected typical context "
                        "tokens per sequence (0 = half the per-sequence "
                        "max); batch = KV tokens / this, capped")
    p.add_argument("--batch-cap", type=int, default=32,
                   help="upper bound for --max-batch-size auto")
    p.add_argument("--page-size", type=int_or_auto, default="auto",
                   help="tokens per KV page, or 'auto': 64 where the "
                        "Pallas kernels read the pool and a 16-token "
                        "page holds under 32 KB (a page is one DMA "
                        "descriptor there), else 16. Under 'auto' "
                        "--max-pages-per-seq and a numeric --num-pages "
                        "count 16-token pages and are restated in pages "
                        "of the size chosen")
    p.add_argument("--max-pages-per-seq", type=int, default=64,
                   help="max context = page-size * this (16 tokens * "
                        "this under --page-size auto, rounded up to a "
                        "whole page)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree (devices in the mesh)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel degree: sharded-sequence "
                        "prefill over this many devices (long prompts)")
    p.add_argument("--sp-attn", default="ring",
                   choices=("ring", "ulysses"),
                   help="sequence-parallel algorithm: 'ring' (ppermute "
                        "K/V rotation, O((S/n)^2) memory) or 'ulysses' "
                        "(two all-to-alls, balanced causal load; needs "
                        "head counts divisible by tp*sp)")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel replicas: each gets its own tp*sp "
                        "submesh, KV pool and scheduler; requests route "
                        "to the least-loaded replica")
    p.add_argument("--fleet", default="in-process",
                   choices=("in-process", "subprocess"),
                   help="dp fleet backend (README 'Process fleet'): "
                        "'in-process' runs every replica as a thread of "
                        "this server (one process, one GIL, one failure "
                        "domain); 'subprocess' runs a router plus one "
                        "engine-worker OS process per replica over a "
                        "local JSON RPC — worker faults are isolated, "
                        "workers restart with backoff, and graceful "
                        "drains migrate KV pages instead of recomputing")
    p.add_argument("--worker-restart-max", type=int, default=3,
                   help="subprocess fleet: restarts allowed per worker "
                        "(doubling backoff) before it stays down and "
                        "the fleet serves degraded on the survivors")
    p.add_argument("--drain-timeout-s", type=float, default=10.0,
                   help="subprocess fleet: budget a SIGTERM'd worker "
                        "gets to settle dispatches and export KV pages "
                        "before exiting")
    p.add_argument("--no-fleet-migrate", action="store_true",
                   help="subprocess fleet: disable drain-time KV page "
                        "migration (resubmissions re-prefill from "
                        "scratch — the benchmark comparison arm)")
    p.add_argument("--autoscale", action="store_true",
                   help="subprocess fleet: SLO-driven autoscaler "
                        "(README 'Elastic fleet') — spawn a worker when "
                        "pooled p95 TTFT/TPOT breaches --slo-ttft-ms/"
                        "--slo-tpot-ms for a sustained window, drain-"
                        "and-migrate the coldest replica away when "
                        "occupancy stays under the low watermark")
    p.add_argument("--autoscale-min", type=int, default=1,
                   help="autoscaler floor on live replicas")
    p.add_argument("--autoscale-max", type=int, default=0,
                   help="autoscaler ceiling on live replicas "
                        "(0 = dp + 2)")
    p.add_argument("--autoscale-breach-window-s", type=float, default=3.0,
                   help="seconds of continuous p95-over-target before a "
                        "scale-up")
    p.add_argument("--autoscale-cooldown-s", type=float, default=10.0,
                   help="minimum seconds between scale decisions "
                        "(anti-flap hysteresis)")
    p.add_argument("--autoscale-low-watermark", type=float, default=0.25,
                   help="scale down when pooled ladder occupancy stays "
                        "under this (0..1) for the idle window")
    p.add_argument("--autoscale-idle-window-s", type=float, default=5.0,
                   help="seconds of continuous low occupancy before a "
                        "scale-down")
    p.add_argument("--default-class", default="interactive",
                   choices=("interactive", "batch", "background"),
                   help="priority class for requests without an "
                        "X-Priority header (README 'Elastic fleet'): "
                        "interactive lanes preempt batch/background "
                        "ones at the admission watermark instead of "
                        "shedding 429")
    p.add_argument("--ignore-eos", action="store_true",
                   help="never stop a request on the tokenizer's EOS: "
                        "generate to max_tokens (load tests on random "
                        "weights, whose argmax hits EOS by chance)")
    p.add_argument("--class-queue-depth", type=int, default=0,
                   help="per-class deferral queue depth: over the "
                        "admission cap, batch/background requests park "
                        "here (drained as load drops) instead of "
                        "shedding; 0 = legacy single global cap")
    p.add_argument("--role", default="mixed",
                   choices=("prefill", "decode", "mixed"),
                   help="uniform worker phase role (README 'P/D "
                        "disaggregation'): 'prefill' workers serve "
                        "prompt prefills and hand each settled prefill "
                        "(KV pages + stream state) off to a decode "
                        "worker — no re-prefill, byte-identical under "
                        "greedy; 'decode' workers adopt handoffs and "
                        "decode at high occupancy with zero prefill "
                        "interference; 'mixed' (default) runs both "
                        "phases on every worker, unchanged from "
                        "pre-P/D behavior. Needs --fleet subprocess "
                        "when not 'mixed'")
    p.add_argument("--roles", default=None,
                   help="per-worker phase roles, comma-separated, one "
                        "per dp replica (e.g. 'prefill,decode,decode') "
                        "— overrides --role; needs --fleet subprocess")
    p.add_argument("--pd-ratio", default=None,
                   help="size the prefill:decode worker split over dp: "
                        "'P:D' (e.g. '1:3') or 'auto' (split by each "
                        "phase's chip-seconds share from the expected "
                        "prompt/decode token mix — engine/autosize.py "
                        "pd_worker_roles); overrides --role, mutually "
                        "exclusive with --roles; needs --fleet "
                        "subprocess and dp >= 2")
    p.add_argument("--pd-prompt-rate", type=float, default=None,
                   help="with --pd-ratio auto: observed prompt tokens/s "
                        "offered to the fleet (default: the BurstGPT-"
                        "shaped 512-token-prompt mix)")
    p.add_argument("--pd-decode-rate", type=float, default=None,
                   help="with --pd-ratio auto: observed decode tokens/s "
                        "(default: 128-token replies)")
    p.add_argument("--pd-prefill-nice", type=int, default=0,
                   help="os.nice() increment for prefill-role worker "
                        "processes (shared-CPU hosts: keeps decode "
                        "cadence flat under prefill bursts; no-op on "
                        "per-chip deployments or at 0)")
    p.add_argument("--attn-backend", default="auto",
                   choices=("auto", "dense", "pallas"),
                   help="decode attention: Pallas paged kernel (TPU) or "
                        "dense gather; auto = pallas on TPU")
    p.add_argument("--quant", default="none",
                   choices=("none", "int8", "int4"),
                   help="weight quantization: int8 stores matmul weights "
                        "as int8 + per-channel scales (int4: 4-bit + "
                        "group-128 scales, quartering), halving the HBM "
                        "weight traffic that bounds decode throughput")
    p.add_argument("--kv-quant", default="none",
                   choices=("none", "int8", "int4"),
                   help="KV-cache quantization: int8 codes + per-token-"
                        "head scales — halves KV HBM traffic and doubles "
                        "the context a same-sized pool holds; int4 "
                        "nibble-packs (quarter traffic, lossier — int8 "
                        "is the accuracy-safe tier)")
    p.add_argument("--spec-mode", default="off",
                   choices=("off", "ngram"),
                   help="speculative decoding: 'ngram' = self-drafting "
                        "(prompt lookup against each sequence's own "
                        "history; no second model, no extra HBM; "
                        "composes with the decode ladder, host KV tier "
                        "and repeat_penalty)")
    p.add_argument("--num-speculative-tokens", type=int, default=4,
                   help="speculation depth γ: proposed tokens verified "
                        "per round (each round emits 1..γ+1 tokens from "
                        "one target forward); [1, 16] when spec is on")
    p.add_argument("--ngram-window", type=int, default=3,
                   help="ngram spec: longest suffix n-gram matched "
                        "against the sequence's history ([1, 8]; "
                        "matching tries window..1, most recent match "
                        "wins)")
    p.add_argument("--decode-pipeline-depth", type=int, default=1,
                   help=">1 keeps that many fused-decode dispatches in "
                        "flight (hides dispatch latency; adds (depth-1)*K "
                        "steps of streaming latency)")
    p.add_argument("--chunked-prefill-size", type=int, default=0,
                   help="split multi-chunk prompts into chunks of this "
                        "many tokens (0 = the largest prefill bucket); "
                        "smaller chunks interleave/fuse with decode at "
                        "a finer grain")
    p.add_argument("--hybrid-prefill", action="store_true",
                   help="fuse each chunk of a multi-chunk prompt's "
                        "prefill into the decode dispatch (Sarathi-style "
                        "piggybacking): running lanes keep producing "
                        "tokens instead of stalling a chunk wall per "
                        "chunk; greedy outputs stay byte-identical")
    p.add_argument("--step-token-budget", type=int, default=0,
                   help="with --hybrid-prefill: per-fused-step token "
                        "budget — chunk tokens are capped at budget minus "
                        "the granted decode tokens (floor: page-size), "
                        "bounding the prefill compute added to any one "
                        "decode dispatch; 0 = uncapped")
    p.add_argument("--platform", default="auto",
                   choices=("auto", "cpu", "tpu"),
                   help="jax platform: 'auto' takes the environment's "
                        "default and 'tpu' pins it; either way the "
                        "server exits non-zero when no TPU is found. "
                        "'cpu' serves from the CPU on purpose (tests, "
                        "protocol work) with --cpu-devices virtual "
                        "devices")
    p.add_argument("--cpu-devices", type=int, default=0,
                   help="with --platform cpu: number of virtual CPU "
                        "devices (0 = max(1, dp*tp*sp), enough for the "
                        "requested mesh)")
    p.add_argument("--step-watchdog-s", type=float, default=0.0,
                   help="quarantine a replica whose prefill/decode "
                        "dispatch stays in flight this long (the wedged-"
                        "TPU failure mode); 0 = off. Use with --no-warmup "
                        "cautiously: the first dispatch includes XLA "
                        "compile")
    p.add_argument("--quarantine-after", type=int, default=3,
                   help="consecutive step failures before a replica is "
                        "quarantined (first failure marks it degraded)")
    p.add_argument("--quarantine-cooldown-s", type=float, default=30.0,
                   help="quarantined replicas re-enter (probation) after "
                        "this long; one clean step re-promotes, one "
                        "failure re-quarantines")
    p.add_argument("--failover-retries", type=int, default=1,
                   help="resubmit a request failed/stranded by a sick "
                        "replica (before any token streamed) to a "
                        "healthy one at most this many times")
    p.add_argument("--routing", default="prefix_affinity",
                   choices=("prefix_affinity", "least_loaded"),
                   help="dp replica routing: 'prefix_affinity' scores "
                        "replicas by expected re-prefill pages (prompt "
                        "pages minus a prefix-cache peek) blended with "
                        "load/pressure so returning conversations land "
                        "on the replica holding their KV pages; "
                        "'least_loaded' is the legacy load-only policy")
    p.add_argument("--route-hit-weight", type=float, default=1.0,
                   help="prefix-affinity: pages of prefill work one "
                        "peeked cache-hit page is worth in the routing "
                        "score (1.0 = at cost; larger lets warmth "
                        "outbid queue depth and preemption pressure)")
    p.add_argument("--route-host-hit-weight", type=float, default=0.5,
                   help="prefix-affinity: pages of prefill work one "
                        "HOST-tier hit page is worth (three "
                        "temperatures: HBM-warm > host-warm > cold — a "
                        "host page saves the compute but still pays a "
                        "host->device swap-in; 0 ignores host warmth)")
    p.add_argument("--host-cache-pages", type=int_or_auto, default="auto",
                   help="host-RAM KV tier capacity in pages: evicted "
                        "prefix-cache pages demote to host memory and "
                        "swap back in on reuse instead of re-prefilling "
                        "(README 'Tiered KV cache'); 0 = off, 'auto' "
                        "(default) = size from available RAM "
                        "(/proc/meminfo MemAvailable; capacity is a "
                        "cap — RAM is consumed only as pages demote)")
    p.add_argument("--fabric-cache-pages", type=int, default=0,
                   help="fleet KV fabric: router-side shared pool "
                        "capacity in pages (README 'KV fabric'); "
                        "settled prefix pages published by any replica "
                        "warm prefills on EVERY replica, and autoscaled "
                        "workers boot warm from the pool; 0 = off")
    p.add_argument("--fabric-publish-min-pages", type=int, default=1,
                   help="fleet KV fabric: publish a prefix to the pool "
                        "only once at least this many settled pages are "
                        "available (filters short one-off prompts)")
    p.add_argument("--fabric-warmboot-pages", type=int, default=64,
                   help="fleet KV fabric: push up to this many MRU pool "
                        "pages into a newly spawned worker BEFORE it "
                        "becomes routable (warm boot for autoscale "
                        "scale-ups, restarts, and rollouts); 0 = off")
    p.add_argument("--kv-plane", default="relay",
                   choices=("relay", "shm"),
                   help="KV data plane (README 'KV data plane'): how KV "
                        "payloads (fabric publishes, P/D handoffs, drain "
                        "migrations) move between processes. 'relay' = "
                        "blobs ride the RPC sockets through the router "
                        "(default, works everywhere); 'shm' = payloads "
                        "go into a shared-memory page arena and only "
                        "descriptors cross the sockets (zero-copy; "
                        "needs --fleet subprocess on Linux, silently "
                        "falls back to relay otherwise)")
    p.add_argument("--shm-arena-bytes", type=int,
                   default=256 * 1024 * 1024,
                   help="--kv-plane shm: total shared-memory arena size "
                        "in bytes, split into one single-writer region "
                        "per worker (default 256 MiB)")
    p.add_argument("--route-fabric-hit-weight", type=float, default=0.25,
                   help="prefix-affinity: pages of prefill work one "
                        "fabric-pool hit page is worth (fourth "
                        "temperature: HBM-warm > host-warm > "
                        "fabric-warm > cold — a fabric page saves the "
                        "compute but pays deserialize + host->device "
                        "swap-in; 0 ignores fabric warmth)")
    p.add_argument("--admission-queue-depth", type=int, default=0,
                   help="shed load (429 + Retry-After) when every "
                        "routable replica has this many requests queued "
                        "or running; 0 = queue without bound (legacy)")
    p.add_argument("--admission", default="reserve",
                   choices=("reserve", "optimistic"),
                   help="KV admission mode: 'reserve' charges each "
                        "request prompt+max_new worst case (OOM-free, "
                        "strands pool under bursty traffic); "
                        "'optimistic' charges prompt+headroom and "
                        "preempts/recompute-resumes on exhaustion "
                        "(token-identical under greedy decoding)")
    p.add_argument("--optimistic-headroom-pages", type=int, default=2,
                   help="optimistic admission: decode-headroom pages "
                        "charged per request on top of its prompt")
    p.add_argument("--preempt-watermark-pages", type=int, default=4,
                   help="preempt the most-recently-admitted sequences "
                        "when a decode grant comes up short and "
                        "free+evictable pages fall below this")
    p.add_argument("--preempt-max-per-request", type=int, default=3,
                   help="starvation guard: after this many preemptions "
                        "a request re-admits under full worst-case "
                        "reservation (and is never preempted again)")
    p.add_argument("--chaos-page-pressure", type=int, default=0,
                   help="fault injection: hold this many KV pages out "
                        "of the pool at boot (deterministic exhaustion "
                        "testing; adjustable via POST /debug/chaos)")
    p.add_argument("--chaos-failure-rate", type=float, default=0.0,
                   help="HTTP fault injection: 503 this fraction of "
                        "generate/chat/embed requests (harness testing)")
    p.add_argument("--chaos-delay-s", type=float, default=0.0,
                   help="HTTP fault injection: delay requests uniformly "
                        "up to this many seconds")
    p.add_argument("--chaos-step-failure-rate", type=float, default=0.0,
                   help="engine fault injection: each prefill/decode "
                        "dispatch raises with this probability "
                        "(exercises quarantine + failover end to end)")
    p.add_argument("--chaos-step-wedge-s", type=float, default=0.0,
                   help="engine fault injection: each dispatch sleeps "
                        "this long first (exercises the step watchdog)")
    p.add_argument("--chaos-rpc-seed", type=int, default=0,
                   help="transport fault injection: deterministic seed "
                        "for the frame-level fault schedule (same seed "
                        "=> same faults at the same frame indices)")
    p.add_argument("--chaos-rpc-corrupt-rate", type=float, default=0.0,
                   help="transport fault injection: flip one byte in "
                        "this fraction of RPC frames (CRC rejects them; "
                        "exercises reconnect + resync)")
    p.add_argument("--chaos-rpc-drop-rate", type=float, default=0.0,
                   help="transport fault injection: reset the "
                        "connection instead of sending this fraction "
                        "of frames")
    p.add_argument("--chaos-rpc-delay-rate", type=float, default=0.0,
                   help="transport fault injection: delay this "
                        "fraction of frames by --chaos-rpc-delay-s")
    p.add_argument("--chaos-rpc-delay-s", type=float, default=0.02,
                   help="transport fault injection: per-delayed-frame "
                        "sleep (seconds)")
    p.add_argument("--chaos-rpc-truncate-rate", type=float, default=0.0,
                   help="transport fault injection: torn write — send "
                        "a prefix of the frame, then reset")
    p.add_argument("--chaos-rpc-wedge-after", type=int, default=0,
                   help="transport fault injection: after this many "
                        "matching frames, the connection silently "
                        "swallows ALL traffic until the deadline "
                        "watchdog recycles it (0 = off; one-shot)")
    p.add_argument("--chaos-rpc-wedge-replica", type=int, default=0,
                   help="replica whose router connection arms the "
                        "wedge (with --chaos-rpc-wedge-after)")
    p.add_argument("--chaos-rpc-verbs", default="",
                   help="comma-separated RPC verbs the transport chaos "
                        "applies to ('' = every verb)")
    p.add_argument("--chaos-rpc-direction", default="both",
                   choices=("send", "recv", "both"),
                   help="which direction transport chaos applies to: "
                        "send = router->worker frames, recv = "
                        "worker->router frames")
    p.add_argument("--rpc-deadline-fast-s", type=float, default=10.0,
                   help="deadline for control-plane RPCs (cancel, "
                        "chaos, healthz, ...); timeouts emit "
                        "structured rpc_timeout events and three "
                        "consecutive ones recycle the connection")
    p.add_argument("--rpc-deadline-slow-s", type=float, default=60.0,
                   help="deadline for data-plane RPCs that move KV "
                        "bytes or block on admission (submit, "
                        "import-kv, drain)")
    p.add_argument("--poison-max-workers", type=int, default=3,
                   help="quarantine a request as poison (terminal 500) "
                        "once its attempts have crashed or wedged this "
                        "many DISTINCT workers (0 disables)")
    p.add_argument("--slo-ttft-ms", type=float, default=0.0,
                   help="rolling SLO target for time-to-first-token "
                        "(ms): requests past it count into "
                        "tpu_inf_slo_breaches_total{slo=\"ttft\"}; the "
                        "windowed p50/p95 gauges export regardless. "
                        "0 = no target")
    p.add_argument("--slo-tpot-ms", type=float, default=0.0,
                   help="rolling SLO target for time-per-output-token "
                        "(ms): breaches count into "
                        "tpu_inf_slo_breaches_total{slo=\"tpot\"}; "
                        "0 = no target")
    p.add_argument("--no-warmup", action="store_true")
    p.add_argument("--step-ledger-depth", type=int, default=256,
                   help="per-replica step-ledger ring depth (per-"
                        "dispatch records behind GET /debug/steps and "
                        "the flight recorder; floor 8)")
    p.add_argument("--blackbox-dir", default="/tmp/tpu-inf-blackbox",
                   help="crash flight-recorder root (per-replica "
                        "capture dirs survive kill -9; '' disables). "
                        "Operator-chosen — clients never name capture "
                        "paths")
    p.add_argument("--blackbox-retain", type=int, default=8,
                   help="flight-recorder retention cap: newest N "
                        "trigger captures kept per replica")
    p.add_argument("--profile-dir", default="/tmp/jax-trace",
                   help="where POST /debug/profile writes its traces "
                        "(a per-replica directory under it). Operator-"
                        "chosen — clients never name a path")
    p.add_argument("--debug", action="store_true",
                   help="expose the unauthenticated /debug/* endpoints "
                        "(request timelines, profiler control)")
    p.add_argument("--check-numerics", action="store_true",
                   help="verify params are finite + run a checkify'd "
                        "forward before serving (catches corrupt "
                        "checkpoints)")
    p.add_argument("--debug-nans", action="store_true",
                   help="enable jax_debug_nans: any NaN-producing op "
                        "re-runs un-jitted and raises at the source")
    args = p.parse_args()

    # Before any backend exists. Under --fleet subprocess this process
    # is the router and never initialises one (the chip belongs to the
    # first process that does): the workers inherit the platform through
    # the environment, and whichever process builds an engine checks
    # what it got (runtime.require_backend, via build_server).
    from tpu_inference.runtime import enable_compile_cache, select_platform

    select_platform(args.platform,
                    args.cpu_devices or args.dp * args.tp * args.sp)
    enable_compile_cache()
    # XLA compile counters on /metrics (listeners only: no backend is
    # initialised by registering them, so the router stays off the chip).
    from tpu_inference.telemetry import install_compile_monitor

    install_compile_monitor()

    if args.debug_nans:
        import jax

        jax.config.update("jax_debug_nans", True)

    from tpu_inference.config import validate_spec_config

    if args.spec_mode != "off":
        try:
            validate_spec_config(args.num_speculative_tokens,
                                 args.ngram_window)
        except ValueError as e:
            p.error(str(e))
    if args.autoscale and args.fleet != "subprocess":
        p.error("--autoscale needs --fleet subprocess (scaling spawns "
                "and drains worker processes)")
    if args.autoscale and not (args.slo_ttft_ms or args.slo_tpot_ms):
        p.error("--autoscale needs an SLO target to scale on: set "
                "--slo-ttft-ms and/or --slo-tpot-ms")

    # P/D disaggregation (README "P/D disaggregation"): resolve the
    # per-worker role tuple from --roles > --pd-ratio > --role before
    # any model loads, so a bad split is a usage error in milliseconds.
    if args.roles and args.pd_ratio:
        p.error("--roles and --pd-ratio both name the worker split; "
                "pick one")
    from tpu_inference.config import resolve_worker_roles

    worker_roles: tuple = ()
    try:
        if args.roles:
            worker_roles = resolve_worker_roles(
                args.dp, tuple(r.strip() for r in args.roles.split(",")))
        elif args.pd_ratio:
            from tpu_inference.engine.autosize import pd_worker_roles

            worker_roles = pd_worker_roles(
                args.dp, args.pd_ratio,
                prompt_token_rate=args.pd_prompt_rate,
                decode_token_rate=args.pd_decode_rate)
        elif args.role != "mixed":
            worker_roles = resolve_worker_roles(
                args.dp, (), default_role=args.role)
    except ValueError as e:
        p.error(str(e))
    if any(r != "mixed" for r in worker_roles):
        if args.fleet != "subprocess":
            p.error("--role/--roles/--pd-ratio need --fleet subprocess "
                    "(the live KV handoff moves pages between worker "
                    "processes)")
        import sys

        print(f"[pd] worker roles: {list(worker_roles)}",
              file=sys.stderr)

    # 'auto' sizes resolve where the device is owned (build_engine_group
    # in this process, the worker under --fleet subprocess) from that
    # device's own HBM figure; explicit sizes validate here, before any
    # model loads.
    from tpu_inference.engine.autosize import sizing_request

    try:
        sizing = sizing_request(args)
    except ValueError as e:
        p.error(str(e))

    page_size = None if args.page_size == "auto" else args.page_size
    host_cache_pages = args.host_cache_pages
    if host_cache_pages == "auto":
        from tpu_inference.engine.autosize import (
            auto_host_cache_pages, auto_page_tokens, pallas_reads_pool,
            resolve_model_config)

        model_cfg = resolve_model_config(args.model, args.checkpoint)
        # (The page this server will come to, without touching JAX: the
        # rule resolve_sizing applies where the device is.)
        host_page = page_size or auto_page_tokens(
            model_cfg, kv_quant=args.kv_quant, tp=args.tp,
            pallas=pallas_reads_pool(args.attn_backend, args.platform))
        # Every dp replica builds its OWN host pool from this one
        # EngineConfig — divide the machine budget so the fleet's tiers
        # together stay inside available RAM.
        host_cache_pages = auto_host_cache_pages(
            model_cfg, kv_quant=args.kv_quant,
            page_size=host_page) // max(1, args.dp)
        import sys

        print(f"[autosize] host KV tier: {host_cache_pages} pages/replica "
              f"(from /proc/meminfo MemAvailable, dp={args.dp})",
              file=sys.stderr)

    from tpu_inference.server.http import build_server

    server = build_server(model=args.model, tokenizer=args.tokenizer,
                          checkpoint=args.checkpoint,
                          warmup=not args.no_warmup, tp=args.tp, sp=args.sp,
                          dp=args.dp,
                          enable_debug=args.debug,
                          server_overrides=dict(
                              routing=args.routing,
                              route_hit_weight=args.route_hit_weight,
                              route_host_hit_weight=(
                                  args.route_host_hit_weight),
                              fabric_cache_pages=args.fabric_cache_pages,
                              fabric_publish_min_pages=(
                                  args.fabric_publish_min_pages),
                              fabric_warmboot_pages=(
                                  args.fabric_warmboot_pages),
                              route_fabric_hit_weight=(
                                  args.route_fabric_hit_weight),
                              fleet=args.fleet,
                              kv_plane=args.kv_plane,
                              shm_arena_bytes=args.shm_arena_bytes,
                              worker_roles=worker_roles,
                              pd_prefill_nice=args.pd_prefill_nice,
                              worker_restart_max=args.worker_restart_max,
                              drain_timeout_s=args.drain_timeout_s,
                              fleet_migrate=not args.no_fleet_migrate,
                              autoscale=args.autoscale,
                              autoscale_min_replicas=args.autoscale_min,
                              autoscale_max_replicas=args.autoscale_max,
                              autoscale_breach_window_s=(
                                  args.autoscale_breach_window_s),
                              autoscale_cooldown_s=args.autoscale_cooldown_s,
                              autoscale_low_watermark=(
                                  args.autoscale_low_watermark),
                              autoscale_idle_window_s=(
                                  args.autoscale_idle_window_s),
                              default_class=args.default_class,
                              ignore_eos=args.ignore_eos,
                              class_queue_depth=args.class_queue_depth,
                              step_watchdog_s=args.step_watchdog_s,
                              quarantine_after_failures=args.quarantine_after,
                              quarantine_cooldown_s=args.quarantine_cooldown_s,
                              failover_max_retries=args.failover_retries,
                              admission_queue_depth=args.admission_queue_depth,
                              chaos_failure_rate=args.chaos_failure_rate,
                              chaos_delay_s=args.chaos_delay_s,
                              chaos_rpc_seed=args.chaos_rpc_seed,
                              chaos_rpc_corrupt_rate=(
                                  args.chaos_rpc_corrupt_rate),
                              chaos_rpc_drop_rate=args.chaos_rpc_drop_rate,
                              chaos_rpc_delay_rate=(
                                  args.chaos_rpc_delay_rate),
                              chaos_rpc_delay_s=args.chaos_rpc_delay_s,
                              chaos_rpc_truncate_rate=(
                                  args.chaos_rpc_truncate_rate),
                              chaos_rpc_wedge_after=(
                                  args.chaos_rpc_wedge_after),
                              chaos_rpc_wedge_replica=(
                                  args.chaos_rpc_wedge_replica),
                              chaos_rpc_verbs=tuple(
                                  v for v in
                                  args.chaos_rpc_verbs.split(",") if v),
                              chaos_rpc_direction=args.chaos_rpc_direction,
                              rpc_deadline_fast_s=args.rpc_deadline_fast_s,
                              rpc_deadline_slow_s=args.rpc_deadline_slow_s,
                              poison_max_workers=args.poison_max_workers,
                              blackbox_dir=args.blackbox_dir,
                              blackbox_retain=args.blackbox_retain,
                              profile_dir=args.profile_dir),
                          step_ledger_depth=args.step_ledger_depth,
                          chaos_step_failure_rate=args.chaos_step_failure_rate,
                          chaos_step_wedge_s=args.chaos_step_wedge_s,
                          chaos_page_pressure=args.chaos_page_pressure,
                          admission=args.admission,
                          optimistic_headroom_pages=(
                              args.optimistic_headroom_pages),
                          preempt_watermark_pages=args.preempt_watermark_pages,
                          preempt_max_per_request=args.preempt_max_per_request,
                          attn_backend=args.attn_backend,
                          sp_attn=args.sp_attn,
                          quant=args.quant, kv_quant=args.kv_quant,
                          platform=args.platform, sizing=sizing,
                          ladder_admit_headroom_pages=(
                              args.ladder_admit_headroom_pages),
                          host_cache_pages=host_cache_pages,
                          slo_ttft_ms=args.slo_ttft_ms,
                          slo_tpot_ms=args.slo_tpot_ms,
                          page_size=page_size,
                          max_pages_per_seq=args.max_pages_per_seq,
                          decode_pipeline_depth=args.decode_pipeline_depth,
                          chunked_prefill_size=args.chunked_prefill_size,
                          hybrid_prefill=args.hybrid_prefill,
                          step_token_budget=args.step_token_budget,
                          ngram_window=args.ngram_window,
                          num_speculative_tokens=(
                              args.num_speculative_tokens
                              if args.spec_mode != "off" else 0))
    if args.check_numerics:
        if args.fleet == "subprocess":
            p.error("--check-numerics needs the in-process fleet "
                    "(workers own their params)")
        for eng in server.group.engines:
            eng.check_numerics()
        print("numerics check passed: params finite, forward NaN-free")
    app = server.make_app()
    web.run_app(app, host=args.host, port=args.port)


if __name__ == "__main__":
    main()
