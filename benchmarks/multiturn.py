"""Multi-turn conversation benchmark (BASELINE.json config 3 workload).

Simulates C concurrent chat sessions of T turns each against the
in-process Ollama-protocol server. Every turn resends the full
conversation so far plus a new user message — exactly how the
reference's interactive chat loop accumulates context (reference:
notebooks/request_demo.ipynb cell 4d5cf82f keeps `context` across
turns) — so each request's prompt is a strict extension of the previous
turn's prompt + response. That is the workload the prefix cache
(engine/prefix_cache.py) exists for: turn N's prefill should reuse turn
N-1's published KV pages and recompute only the new suffix.

Reported per run: per-turn-index TTFT (flat-ish with the cache, growing
~linearly with context without it), aggregate TTFT/TPOT percentiles,
server-side prefix-hit tokens. ``--compare`` runs the same workload a
second time with the prefix cache disabled and reports the speedup.

``--compare-routing`` runs the same pinned mix on a dp>=2 fleet twice —
routing=least_loaded then routing=prefix_affinity — and commits the
cache-aware-routing artifact: the least-loaded router sends a returning
conversation to a cold replica ~(dp-1)/dp of the time (full-history
re-prefill), the affinity router routes it back to its warm replica, so
the artifact compares prefix-hit pages, TTFT and tok/s, and checks the
greedy outputs are byte-identical across both policies (routing is a
placement decision, never a behavior change).

Usage:
    python benchmarks/multiturn.py --model tiny-llama --conversations 6 \
        --turns 5 --compare --out benchmarks/results/config3_multiturn.json
    python benchmarks/multiturn.py --smoke --compare-routing \
        --out benchmarks/results/multiturn_routing.json
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmarks.replay import _percentiles, start_server  # noqa: E402

USER_TOPICS = [
    "Tell me about the weather patterns in the Pacific Northwest.",
    "How does that compare to the East Coast?",
    "What should I pack for a trip there in October?",
    "Are there any hiking trails you would recommend?",
    "How difficult is the most popular one?",
    "What wildlife might I encounter on the trail?",
    "Is it safe to hike alone in that area?",
    "What emergency supplies should I carry?",
]


async def _one_conversation(session, url: str, model: str, conv_id: int,
                            turns: int, max_tokens: int) -> list[dict]:
    """Run one chat session; each turn resends the accumulated history."""
    records = []
    history = ""
    for t in range(turns):
        # Tag the session id into every user message so conversations
        # are DISTINCT token streams (like real users): otherwise greedy
        # decoding makes every conversation an identical clone, every
        # replica warms up for the one shared prefix, and both the
        # cache and routing comparisons measure nothing.
        user_msg = (f"[session {conv_id}] "
                    f"{USER_TOPICS[t % len(USER_TOPICS)]}")
        prompt = f"{history}User: {user_msg}\nAssistant:"
        payload = {"model": model, "prompt": prompt, "temperature": 0.0,
                   "stream": True, "options": {"num_predict": max_tokens}}
        t0 = time.perf_counter()
        ttft = None
        chunks = []
        n_tokens = 0
        async with session.post(url, json=payload) as resp:
            resp.raise_for_status()
            async for line in resp.content:
                if not line.strip():
                    continue
                if ttft is None:
                    ttft = time.perf_counter() - t0
                rec = json.loads(line)
                if rec.get("response"):
                    chunks.append(rec["response"])
                if rec.get("done"):
                    n_tokens = rec.get("eval_count", len(chunks))
        e2e = time.perf_counter() - t0
        reply = "".join(chunks)
        history = prompt + reply + "\n"
        records.append({
            "conv": conv_id, "turn": t, "prompt_chars": len(prompt),
            "ttft_s": ttft, "e2e_s": e2e, "output_tokens": n_tokens,
            "tpot_s": ((e2e - ttft) / (n_tokens - 1)
                       if ttft is not None and n_tokens > 1 else None),
            # Reply text rides along (stripped before the artifact) so
            # the routing comparison can hash the full transcript set.
            "reply": reply,
        })
    return records


def _outputs_sha256(records: list[dict]) -> str:
    """Digest of every conversation's full transcript, in (conv, turn)
    order — deterministic regardless of completion interleaving, so two
    runs of the same greedy workload match iff their outputs are
    byte-identical."""
    h = hashlib.sha256()
    for r in sorted(records, key=lambda r: (r["conv"], r["turn"])):
        h.update(f"{r['conv']}:{r['turn']}:".encode())
        h.update(r["reply"].encode())
        h.update(b"\x00")
    return h.hexdigest()


async def _drive(port: int, model: str, conversations: int, turns: int,
                 max_tokens: int) -> list[dict]:
    import aiohttp

    url = f"http://127.0.0.1:{port}/api/generate"
    timeout = aiohttp.ClientTimeout(total=1800)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        results = await asyncio.gather(*[
            _one_conversation(session, url, model, c, turns, max_tokens)
            for c in range(conversations)])
    return [r for conv in results for r in conv]


def _summarize(records: list[dict], turns: int) -> dict:
    ttfts = [r["ttft_s"] for r in records if r["ttft_s"] is not None]
    tpots = [r["tpot_s"] for r in records if r["tpot_s"] is not None]
    # Returning turns (>= 1) are the prefix-cache beneficiaries: their
    # history was served before, so their TTFT is what tiering/routing
    # exist to cut. First turns are cold by construction.
    returning = [r["ttft_s"] for r in records
                 if r["turn"] > 0 and r["ttft_s"] is not None]
    by_turn = []
    for t in range(turns):
        xs = [r["ttft_s"] for r in records
              if r["turn"] == t and r["ttft_s"] is not None]
        by_turn.append(round(float(np.median(xs)), 4) if xs else None)
    return {
        "requests": len(records),
        "output_tokens": int(sum(r["output_tokens"] for r in records)),
        "ttft_s": _percentiles(ttfts, ps=(50, 95, 99)),
        "ttft_returning_s": _percentiles(returning, ps=(50, 95, 99)),
        "tpot_s": _percentiles(tpots),
        "ttft_p50_by_turn": by_turn,
        "final_prompt_chars_p50": round(float(np.median(
            [r["prompt_chars"] for r in records
             if r["turn"] == turns - 1])), 0) if records else None,
    }


def _working_set_pages(records: list[dict], turns: int,
                       page_size: int) -> int:
    """The run's KV working set in pages: every conversation's FINAL
    context (prompt + reply; byte tokenizer => chars ~ tokens), summed.
    This is what the prefix cache would need resident to serve every
    returning turn warm — the number the HBM pool is deliberately sized
    ~5x below in the tiering comparison."""
    total = 0
    for r in records:
        if r["turn"] == turns - 1:
            total += -(-(r["prompt_chars"] + r["output_tokens"])
                       // page_size)
    return total


def run_once(args, enable_prefix_cache: bool) -> dict:
    args.enable_prefix_cache = enable_prefix_cache
    srv, port, stop = start_server(args)
    try:
        t0 = time.perf_counter()
        records = asyncio.run(_drive(port, args.model, args.conversations,
                                     args.turns, args.max_tokens))
        wall = time.perf_counter() - t0
        summary = _summarize(records, args.turns)
        summary["wall_s"] = round(wall, 3)
        summary["tok_s"] = round(summary["output_tokens"] / wall, 2)
        summary["outputs_sha256"] = _outputs_sha256(records)
        summary["working_set_pages"] = _working_set_pages(
            records, args.turns, args.page_size)
        stats = srv.group.stats_snapshot()
        summary["prefix_cache_enabled"] = enable_prefix_cache
        summary["tokens_prefix_cached"] = stats.get("tokens_prefix_cached", 0)
        summary["prefix_cache"] = stats.get("prefix_cache")
        summary["swap_in_resumes"] = stats.get("swap_in_resumes", 0)
        summary["steps"] = stats.get("steps")
        summary["prefills"] = stats.get("prefills")
        # Router view (dp>1): warm/cold dispatch counts and the cached
        # pages the router counted on, per replica and fleet-wide.
        group = srv.group
        summary["routing"] = {
            "mode": group.server_cfg.routing,
            "dp": len(group.engines),
            "route_prefix_hits": group.route_prefix_hits,
            "route_cold": group.route_cold,
            "route_hit_pages": sum(st["hit_pages"]
                                   for st in group._route_stats),
            "per_replica": [dict(st) for st in group._route_stats],
        }
    finally:
        stop()
    return summary


def _compare_routing(args) -> dict:
    """Run the pinned multi-turn mix on a dp>=2 fleet under
    routing=least_loaded then routing=prefix_affinity (fresh servers
    each) and commit the side-by-side artifact: prefix-hit pages, TTFT
    p50/p95, tok/s, and the byte-identity check on greedy outputs."""
    args.dp = max(getattr(args, "dp", 1), 2)
    cfg_snapshot = dict(vars(args))
    summaries = {}
    for mode in ("least_loaded", "prefix_affinity"):
        args.routing = mode
        print(f"[multiturn] routing={mode} lane", file=sys.stderr)
        summaries[mode] = run_once(args, enable_prefix_cache=True)
    ll, aff = summaries["least_loaded"], summaries["prefix_affinity"]

    def _pages(s):
        # Server-side truth: prompt tokens actually served from KV reuse,
        # in page units (what the affinity router exists to maximize).
        return s["tokens_prefix_cached"] // args.page_size

    comparison = {
        "dp": args.dp,
        "cached_prompt_pages_least_loaded": _pages(ll),
        "cached_prompt_pages_prefix_affinity": _pages(aff),
        "route_hit_pages_least_loaded": ll["routing"]["route_hit_pages"],
        "route_hit_pages_prefix_affinity": aff["routing"]["route_hit_pages"],
        "route_warm_dispatches_least_loaded":
            ll["routing"]["route_prefix_hits"],
        "route_warm_dispatches_prefix_affinity":
            aff["routing"]["route_prefix_hits"],
        "ttft_p50_least_loaded_s": ll["ttft_s"]["p50"],
        "ttft_p50_prefix_affinity_s": aff["ttft_s"]["p50"],
        "ttft_p95_least_loaded_s": ll["ttft_s"]["p95"],
        "ttft_p95_prefix_affinity_s": aff["ttft_s"]["p95"],
        "tok_s_least_loaded": ll["tok_s"],
        "tok_s_prefix_affinity": aff["tok_s"],
        # Greedy decoding + identical weights per replica (same init
        # seed): routing must be a pure placement decision.
        "outputs_identical": bool(
            ll["outputs_sha256"] == aff["outputs_sha256"]),
        # Wall-clock TTFT swings on a loaded CI box, so the claim is
        # split (same stance as replay's tok_s_within_5pct): the
        # deterministic part — affinity routed strictly more cached
        # pages, byte-identically — is what the tier-1 smoke asserts;
        # the latency win is graded on the artifact actually committed.
        "ttft_p95_improved": bool(
            aff["ttft_s"]["p95"] is not None
            and ll["ttft_s"]["p95"] is not None
            and aff["ttft_s"]["p95"] < ll["ttft_s"]["p95"]),
        "affinity_wins": bool(
            _pages(aff) > _pages(ll)
            and aff["routing"]["route_hit_pages"]
            > ll["routing"]["route_hit_pages"]
            and ll["outputs_sha256"] == aff["outputs_sha256"]),
    }
    out = {"config": cfg_snapshot, "least_loaded": ll,
           "prefix_affinity": aff, "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    result = dict(comparison)
    result["least_loaded"], result["prefix_affinity"] = ll, aff
    return result


def _compare_tiering(args) -> dict:
    """Tiered-KV-cache comparison (README "Tiered KV cache"): replay the
    multi-turn mix against an HBM pool deliberately sized ~5x SMALLER
    than the conversations' KV working set, twice — host tier off
    (evictions destroy KV; returning turns re-prefill their history)
    then on (evictions demote to host RAM; returning turns swap back
    in) — and commit the side-by-side artifact: total cached tokens
    served, returning-turn TTFT p95, swap counters, and the byte-
    identity check on greedy outputs (tiering is a memory-placement
    decision, never a behavior change)."""
    # Size the pool from the workload so the working set oversubscribes
    # it ~working_set_factor x: per-conversation final context ~ turns *
    # (user message + tag + protocol overhead + reply tokens), byte
    # tokenizer => chars ~ tokens. The per-sequence cap (and reserve
    # admission's worst case) still fits inside the pool.
    if not args.smoke:
        # Enough concurrent conversations that the working set genuinely
        # dwarfs the pool even after the one-sequence-must-fit floor on
        # num_pages below.
        args.conversations = max(args.conversations, 10)
    per_conv = args.turns * (65 + args.max_tokens)
    ws_pages_est = args.conversations * -(-per_conv // args.page_size)
    per_seq = -(-per_conv // args.page_size) + \
        -(-args.max_tokens // args.page_size) + 2
    factor = args.working_set_factor
    args.num_pages = max(per_seq + 4, int(ws_pages_est / factor))
    args.max_pages_per_seq = min(args.max_pages_per_seq,
                                 args.num_pages - 2)
    # Byte-identity across arms requires every prefill chunk to compile
    # to ONE query shape: a cold re-prefill (one big bucket) and a warm
    # tail (small bucket) otherwise run different XLA graphs, whose
    # reduction orders differ in ulps — enough to flip greedy argmax on
    # near-ties. Chunking at the smallest bucket pins the shape.
    if not args.chunked_prefill_size:
        args.chunked_prefill_size = 16 if args.smoke else 64
    host_pages = args.host_cache_pages or 2 * ws_pages_est
    cfg_snapshot = dict(vars(args))
    # The config block must reproduce the TIERED arm (the hbm_only arm
    # is the same config with host_cache_pages=0 — recorded per arm).
    cfg_snapshot["host_cache_pages"] = host_pages
    summaries = {}
    for mode, pages in (("hbm_only", 0), ("tiered", host_pages)):
        args.host_cache_pages = pages
        print(f"[multiturn] tiering={mode} lane "
              f"(num_pages={args.num_pages}, host_cache_pages={pages})",
              file=sys.stderr)
        summaries[mode] = run_once(args, enable_prefix_cache=True)
    off, on = summaries["hbm_only"], summaries["tiered"]
    pool = args.num_pages - 1
    ws = max(off["working_set_pages"], on["working_set_pages"])
    tiered_pc = on.get("prefix_cache") or {}
    comparison = {
        "hbm_pool_pages": pool,
        "host_cache_pages": host_pages,
        "working_set_pages": ws,
        "working_set_over_pool": round(ws / pool, 2),
        "cached_tokens_hbm_only": off["tokens_prefix_cached"],
        "cached_tokens_tiered": on["tokens_prefix_cached"],
        "offloaded_pages": tiered_pc.get("offloaded_pages", 0),
        "restored_pages": tiered_pc.get("restored_pages", 0),
        "swap_in_resumes": on.get("swap_in_resumes", 0),
        "ttft_returning_p95_hbm_only_s": off["ttft_returning_s"]["p95"],
        "ttft_returning_p95_tiered_s": on["ttft_returning_s"]["p95"],
        "tok_s_hbm_only": off["tok_s"],
        "tok_s_tiered": on["tok_s"],
        # Greedy decoding + identical weights/seed: tiering must be a
        # pure memory-placement decision.
        "outputs_identical": bool(
            off["outputs_sha256"] == on["outputs_sha256"]),
        # Wall-clock TTFT swings on a loaded CI box, so the claim is
        # split (same stance as the routing artifact): the
        # deterministic part — strictly more cached tokens served, with
        # real demote/restore traffic, byte-identically — is what the
        # tier-1 smoke asserts; the latency win is graded on the
        # artifact actually committed.
        "ttft_returning_p95_improved": bool(
            on["ttft_returning_s"]["p95"] is not None
            and off["ttft_returning_s"]["p95"] is not None
            and on["ttft_returning_s"]["p95"]
            < off["ttft_returning_s"]["p95"]),
        "tiering_wins": bool(
            on["tokens_prefix_cached"] > off["tokens_prefix_cached"]
            and tiered_pc.get("restored_pages", 0) > 0
            and off["outputs_sha256"] == on["outputs_sha256"]),
    }
    out = {"config": cfg_snapshot, "hbm_only": off, "tiered": on,
           "comparison": comparison}
    print(json.dumps(comparison, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    result = dict(comparison)
    result["hbm_only"], result["tiered"] = off, on
    return result


def main() -> dict:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default="tiny-llama")
    p.add_argument("--tokenizer", default="byte")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--conversations", type=int, default=6)
    p.add_argument("--turns", type=int, default=5)
    p.add_argument("--max-tokens", type=int, default=48,
                   help="assistant tokens per turn")
    # Consumed by the shared replay.start_server (its parser grew
    # --sp/--sp-attn in r4; this parser must carry them too).
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel prefill degree")
    p.add_argument("--sp-attn", default="ring", choices=("ring", "ulysses"))
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel replicas (requests route per "
                        "--routing; --compare-routing forces >= 2)")
    p.add_argument("--routing", default="prefix_affinity",
                   choices=("prefix_affinity", "least_loaded"),
                   help="dp replica routing policy")
    p.add_argument("--route-hit-weight", type=float, default=1.0,
                   help="prefix-affinity: routing-score pages one peeked "
                        "cache-hit page is worth")
    p.add_argument("--route-host-hit-weight", type=float, default=0.5,
                   help="prefix-affinity: routing-score pages one peeked "
                        "HOST-tier hit page is worth (HBM-warm > "
                        "host-warm > cold)")
    p.add_argument("--host-cache-pages", type=int, default=0,
                   help="host-RAM KV tier capacity (0 = off; "
                        "--compare-tiering sizes it from the working "
                        "set when left at 0)")
    p.add_argument("--working-set-factor", type=float, default=5.0,
                   help="--compare-tiering: size the HBM pool so the "
                        "conversations' KV working set oversubscribes "
                        "it by about this factor")
    p.add_argument("--max-batch-size", type=int, default=8)
    p.add_argument("--chunked-prefill-size", type=int, default=0,
                   help="prefill chunk tokens (0 = largest bucket); the "
                        "tiering comparison pins it to the smallest "
                        "bucket so every chunk compiles to ONE query "
                        "shape and greedy outputs stay byte-identical "
                        "across arms (XLA reduction order is "
                        "shape-dependent)")
    p.add_argument("--num-pages", type=int, default=512)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--max-pages-per-seq", type=int, default=64)
    p.add_argument("--decode-steps-per-call", type=int, default=8)
    p.add_argument("--decode-pipeline-depth", type=int, default=1)
    p.add_argument("--quant", default="none", choices=("none", "int8"))
    p.add_argument("--kv-quant", default="none",
                   choices=("none", "int8", "int4"))
    p.add_argument("--platform", default="auto",
                   choices=("auto", "cpu", "tpu"),
                   help="jax platform; 'cpu' forces the CPU backend "
                        "before any computation (same pattern as "
                        "replay.py / tests/conftest.py)")
    p.add_argument("--no-warmup", action="store_true")
    p.add_argument("--compare", action="store_true",
                   help="also run with the prefix cache disabled and "
                        "report the TTFT delta")
    p.add_argument("--compare-routing", action="store_true",
                   help="run the mix on a dp>=2 fleet under least-loaded "
                        "then prefix-affinity routing and commit a "
                        "prefix-hit-pages / TTFT / tok_s comparison "
                        "artifact with a byte-identity check")
    p.add_argument("--compare-tiering", action="store_true",
                   help="replay the mix with the HBM pool sized ~5x "
                        "below the KV working set, host tier off vs on, "
                        "and commit a cached-tokens / returning-TTFT / "
                        "swap-traffic artifact with a byte-identity "
                        "check")
    p.add_argument("--smoke", action="store_true",
                   help="CPU smoke lane (tier-1): tiny model, small "
                        "conversation mix, small engine + prefill "
                        "buckets — exercises the full dp=2 routing "
                        "comparison in seconds")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    if sum((args.compare, args.compare_routing, args.compare_tiering)) > 1:
        p.error("--compare / --compare-routing / --compare-tiering are "
                "mutually exclusive; run them as separate invocations")

    if args.smoke:
        # One switch pins every knob to the CPU-affordable shape so the
        # tier-1 lane cannot drift from what CI actually runs (replay.py
        # --smoke stance). Small pages make the pinned mix cache-dense:
        # every turn's history re-lands on page boundaries quickly.
        args.model, args.tokenizer = "tiny-llama", "byte"
        args.platform = "cpu"
        # ODD conversation count: with an even count and a near-idle
        # fleet, the rotating tie-break cursor's parity can stay
        # constant per conversation, giving the least-loaded arm
        # accidental perfect stickiness (both arms fully warm -> the
        # routing comparison flakes to a tie on fast boxes). An odd
        # count flips the parity every round, so least-loaded provably
        # migrates conversations across replicas.
        args.conversations = min(args.conversations, 5)
        args.turns = min(args.turns, 4)
        args.max_tokens = min(args.max_tokens, 12)
        args.max_batch_size, args.num_pages = 4, 256
        args.page_size, args.max_pages_per_seq = 8, 48
        args.decode_steps_per_call = 4
        if args.compare_tiering:
            # The tiering smoke needs real churn in seconds: a ~3x
            # oversubscribed pool is enough to force demotes/restores
            # on CPU (_compare_tiering recomputes num_pages from this).
            args.working_set_factor = min(args.working_set_factor, 3.0)
        if args.out is None and args.compare_routing:
            args.out = "benchmarks/results/multiturn_routing.json"
        if args.out is None and args.compare_tiering:
            args.out = "benchmarks/results/multiturn_tiering.json"

    if args.platform != "auto":
        # Before any jax computation.
        import jax

        jax.config.update("jax_platforms", args.platform)
        if args.platform == "cpu":
            need = max(args.dp, 2 if args.compare_routing else 1)
            try:
                jax.config.update("jax_num_cpu_devices",
                                  max(1, need * args.tp * args.sp))
            except RuntimeError:
                # Backends are already up (main() called inside a
                # process that has run jax, e.g. pytest): jax refuses
                # to change the count, and the host's devices stand.
                pass

    if args.compare_routing:
        return _compare_routing(args)
    if args.compare_tiering:
        return _compare_tiering(args)

    # Snapshot before run_once mutates args (enable_prefix_cache toggles).
    out = {"config": dict(vars(args))}
    out["cached"] = run_once(args, enable_prefix_cache=True)
    if args.compare:
        out["uncached"] = run_once(args, enable_prefix_cache=False)
        c, u = out["cached"], out["uncached"]
        if c["ttft_s"]["p50"] and u["ttft_s"]["p50"]:
            out["ttft_p50_speedup_from_cache"] = round(
                u["ttft_s"]["p50"] / c["ttft_s"]["p50"], 3)
    print(json.dumps({k: v for k, v in out.items() if k != "config"},
                     indent=1))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main()
