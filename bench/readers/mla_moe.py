"""Roofline shares of the latent-attention and grouped-expert kernels and
of the decode step of a DeepSeek-V3 / Kimi-K2 cell, from the profiler
trace (kernel and program times) and the client's own view of what was
in flight while the profile ran. Counts: roofline_mla_moe.py.

  mla_decode_attn   bytes / flops one call per layer needs for the visible
                    latent contexts of the sequences decoding during the
                    profile, over ``mla_decode_attention``'s traced time
  mla_prefill_attn  absorbed attention flops (and latent bytes) of the
                    prefill dispatches the profile holds (tokens and
                    contexts from the engine's step ledger), over
                    ``mla_prefill_attention``'s traced time
  mla_prefill_ms_per_ktok
                    device milliseconds of the prefill programs in the
                    profile per 1000 prompt tokens they computed
  moe_experts       the grouped kernels' least time over their traced
                    time, in the DECODE programs (those the decode
                    kernel ran in). An expert layer of a step must read
                    every distinct held expert that has a token, once
                    (expected count at T tokens:
                    roofline_mla_moe.expected_distinct_experts), and
                    multiply the pairs T tokens send here; T is the
                    client's count of sequences decoding at each sampled
                    instant. The program's routing counters stay out. A
                    prefill's calls are left out: its tokens do not
                    choose independently (a 1024-token chunk of random
                    ASCII reached at most 8-9 of the 12 held experts by
                    the kernels' own time, v5e, PR 26), so no count from
                    the configuration and the client is right there
  mla_moe_decode_hbm
                    (non-expert weights + expected distinct held experts
                    at the observed batch + latents of the visible
                    contexts) / peak bytes/s, over one traced decode step

A program without these kernels (another family, the parent commit) has
none of the ops: every reading is None and the metric is left out.
"""

import bisect
import re

import roofline_mla_moe as R

DECODE, PREFILL, EXPERTS = ("mla_decode_attention", "mla_prefill_attention",
                            "moe_grouped_experts")
LAG_MAX_S = 2.5


def _kernel(ctx, prefix):
    """(calls, seconds) of ops whose name starts with ``prefix``, a chip."""
    chips = ctx["trace"]["chips"].values()
    calls = secs = 0.0
    for c in chips:
        for name, (n, s) in c["ops"].items():
            if name.startswith(prefix):
                calls += n
                secs += s
    return calls / len(chips), secs / len(chips)


def _expert_seconds(ops, cfg):
    """Seconds of the two grouped expert kernels among ``ops`` (name ->
    (calls, seconds)). The v5e's trace names their ops
    ``tpu_custom_call.<n>`` (the kernels sit in a ``while`` inside the
    scans; the AOT compile's HLO calls the same instructions
    ``moe_grouped_experts_<which>.<n>``), so an op counts under either
    name when its result has the kernel's shape: bf16 [rows,
    moe_intermediate_size] for ``gate_up``, f32 [rows, hidden_size] for
    ``down``. No other kernel of the program returns those."""
    pat = re.compile(
        rf"^({EXPERTS}_gate_up|tpu_custom_call)\.[0-9]+_bf16_[0-9]+_"
        rf"{cfg['moe_intermediate_size']}_$|"
        rf"^({EXPERTS}_down|tpu_custom_call)\.[0-9]+_f32_[0-9]+_"
        rf"{cfg['hidden_size']}_$")
    return sum(s for name, (_, s) in ops.items() if pat.match(name))


def _in_flight(ctx):
    """(sequences decoding at each of 60 instants of the profiled seconds,
    mean of their summed contexts), on the client's clock."""
    prof = ctx["profile"]
    t0, t1 = prof["start_s"], prof["start_s"] + prof["seconds"]
    seqs, total = [], 0.0
    n = 60
    for k in range(n):
        t = t0 + (t1 - t0) * (k + 0.5) / n
        seqs.append(0)
        for r in ctx["records"]:
            ts = r["token_s"]
            if len(ts) >= 2 and ts[0] <= t <= ts[-1]:
                seqs[-1] += 1
                total += r["prompt_tokens"] + bisect.bisect_right(ts, t)
    return seqs, total / n


def _mean(xs):
    return sum(xs) / len(xs)


def _rows(op_name, cfg):
    """Token rows of one prefill kernel call, from its result's shape in
    the op's name ('<op>_bf16_1_32_2048_512_': elements / (heads * latent
    rank)), padding to the graph's bucket included."""
    dims = re.search(r"_[a-z]+[0-9]+_((?:[0-9]+_)+)$", op_name)
    if not dims:
        return None
    n = 1
    for d in dims.group(1).strip("_").split("_"):
        n *= int(d)
    return n // (cfg["num_attention_heads"] * cfg["kv_lora_rank"])


def _prefill_in_profile(ctx):
    """The ledger's prefill records that are the profile's prefill runs
    (as readers/kernels.py matches them: a stretch of consecutive records
    whose chunks fit the runs' rows, at the most even lag from the
    profile call's instant), and the runs' program seconds."""
    cfg, prof = ctx["config"], ctx["profile"]
    runs, program_s = [], 0.0
    for mod in ctx["trace"]["modules"].values():
        rows = [_rows(n, cfg) for n in mod["ops"] if n.startswith(PREFILL)]
        if rows and rows[0]:
            program_s += mod["seconds"]
            runs.extend((t, rows[0]) for t in mod["starts"])
    runs.sort()
    recs = [r for r in ctx["ledger"] if r["kind"] == "prefill_chunk"]
    best = None
    for k in range(len(recs) - len(runs) + 1 if runs else 0):
        block = recs[k:k + len(runs)]
        if any(r["chunk_tokens"] > rows for r, (_, rows) in zip(block, runs)):
            continue
        lag = sorted(r["ts"] - (prof["start_unix"] + t)
                     for r, (t, _) in zip(block, runs))
        mid = lag[len(lag) // 2]
        if abs(mid) > LAG_MAX_S:
            continue
        cost = sum(abs(x - mid) for x in lag) + 0.1 * abs(mid)
        if best is None or cost < best[0]:
            best = (cost, block)
    if best is None or not sum(r["chunk_tokens"] for r in best[1]):
        return None
    return best[1], program_s


def _decode_steps(ctx):
    """(decode steps in the profile, their programs' seconds, the grouped
    expert kernels' seconds inside them): the decode programs are those
    the decode kernel ran in, once a layer a step."""
    calls = secs = experts = 0.0
    for mod in ctx["trace"]["modules"].values():
        n = sum(c for name, (c, _) in mod["ops"].items()
                if name.startswith(DECODE))
        if n:
            calls += n
            secs += mod["seconds"]
            experts += _expert_seconds(mod["ops"], ctx["config"])
    return calls / ctx["config"]["num_hidden_layers"], secs, experts


def _least(byts, flops, peaks):
    return max(byts / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"])


def read(ctx, what):
    if ctx["peaks"] is None or "kv_lora_rank" not in ctx["config"]:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    layers = cfg["num_hidden_layers"]
    if what == "mla_decode_attn":
        calls, secs = _kernel(ctx, DECODE)
        if not calls or not secs:
            return None
        _, vis = _in_flight(ctx)
        return 100.0 * calls * _least(R.mla_attn_bytes(vis, cfg),
                                      R.mla_attn_flops(vis, cfg),
                                      peaks) / secs
    if what in ("mla_prefill_attn", "mla_prefill_ms_per_ktok"):
        work = _prefill_in_profile(ctx)
        if work is None:
            return None
        recs, program_s = work
        if what == "mla_prefill_ms_per_ktok":
            return 1e6 * program_s / sum(r["chunk_tokens"] for r in recs)
        _, secs = _kernel(ctx, PREFILL)
        if not secs:
            return None
        # The ledger's kv_read_tokens of a chunk are its (query, key)
        # pairs, chunk * offset + chunk * (chunk + 1) / 2; the latents it
        # must read are those of offset + chunk tokens, once.
        least = sum(_least(
            R.mla_attn_bytes(
                r["kv_read_tokens"] / r["chunk_tokens"]
                + (r["chunk_tokens"] - 1) / 2.0, cfg),
            R.mla_attn_flops(r["kv_read_tokens"], cfg), peaks)
            for r in recs if r["chunk_tokens"])
        return 100.0 * layers * least / secs
    if what == "moe_experts":
        steps, _, secs = _decode_steps(ctx)
        if not secs:
            return None
        seqs, _ = _in_flight(ctx)
        # One expert layer's least time for a decode step: the mean over
        # the sampled instants.
        step = _mean([_least(R.moe_layer_bytes(b, cfg),
                             R.moe_layer_flops(b, cfg), peaks) for b in seqs])
        return 100.0 * R.expert_layers(cfg) * steps * step / secs
    if what == "mla_moe_decode_hbm":
        steps, secs, _ = _decode_steps(ctx)
        if not steps:
            return None
        seqs, vis = _in_flight(ctx)
        return 100.0 * (R.decode_step_bytes(_mean(seqs), vis, cfg)
                        / peaks["hbm_bytes_per_s"]) / (secs / steps)
    raise ValueError(f"mla_moe knows no {what!r}")
