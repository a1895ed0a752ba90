"""Roofline shares of the attention kernels and of the decode step, from
the profiler trace (kernel and program times) and the client's own view
of what was in flight while the profile ran (contexts, prompts).

  decode_attn     HBM-bound: bytes the decode kernel's calls had to read
                  (visible KV of the sequences decoding during the
                  profile) / peak bytes/s, over the kernel's traced time
  prefill_attn    compute-bound: attention flops of the prefill dispatches
                  the profile holds (their tokens and contexts from the
                  engine's step ledger, counted by roofline.py) / peak
                  bf16 flops, over the prefill kernel's traced time
  prefill_ms_per_ktok
                  device milliseconds of the prefill programs in the
                  profile per 1000 prompt tokens those dispatches computed
  decode_hbm      (weights + visible KV read per decode step) / peak
                  bytes/s, over the traced time of one decode step
"""

import bisect
import re

import roofline as R

LAG_MAX_S = 2.5


def _kernel(ctx, prefix):
    """(calls, seconds) of ops whose name starts with ``prefix``, summed
    over the trace and averaged over chips."""
    chips = ctx["trace"]["chips"].values()
    calls = secs = 0.0
    for c in chips:
        for name, (n, s) in c["ops"].items():
            if name.startswith(prefix):
                calls += n
                secs += s
    return calls / len(chips), secs / len(chips)


def _profiled(prof):
    """The traced interval on the client's clock: from the profile call
    to its asked-for length later (the call itself returns seconds after
    that, once the trace is written)."""
    return prof["start_s"], prof["start_s"] + prof["seconds"]


def _mean_visible_context(ctx):
    """Mean over the profile of the visible context summed over the
    sequences decoding at that instant (client's clock)."""
    prof, cfg = ctx["profile"], ctx["config"]
    t0, t1 = _profiled(prof)
    total, n = 0.0, 60
    for k in range(n):
        t = t0 + (t1 - t0) * (k + 0.5) / n
        for r in ctx["records"]:
            ts = r["token_s"]
            if len(ts) >= 2 and ts[0] <= t <= ts[-1]:
                seen = bisect.bisect_right(ts, t)
                total += R.visible(r["prompt_tokens"] + seen, cfg)
    return total / n


def _rows(op_name, cfg):
    """Token rows one call of the prefill kernel computed, from its
    result's shape in the op's name ('<op>_bf16_1_8_8_512_128_'): elements
    / (query heads * head size). Padding to the graph's bucket included."""
    dims = re.search(r"_[a-z]+[0-9]+_((?:[0-9]+_)+)$", op_name)
    if not dims:
        return None
    n = 1
    for d in dims.group(1).strip("_").split("_"):
        n *= int(d)
    return n // (cfg["num_attention_heads"] * R.head_dim(cfg))


def _prefill_in_profile(ctx, kernel):
    """(prompt tokens computed, query-key pairs, device seconds of the
    prefill programs) of the prefill dispatches the PROFILE holds, so that
    work and time are of the same few seconds.

    The trace gives each run of a program in which the prefill kernel ran:
    when it started and how many token rows its graph holds. The engine's
    step ledger gives each prefill dispatch of the whole run: when (unix
    clock), its real tokens and the context they read. The runs of the
    trace are a stretch of consecutive ledger records. It is found as the
    stretch whose every chunk fits its run's rows and whose instants keep
    the most even distance to the runs' starts, taken from the profile
    call's instant (a dispatch is recorded when it is queued and runs at
    most a few decode steps later; the trace starts a fraction of a second
    after the call). Periodic traffic repeats its pattern every few
    seconds, so a stretch more than LAG_MAX_S off is no candidate and,
    between near-equal ones, the nearer wins."""
    cfg, prof = ctx["config"], ctx["profile"]
    runs, program_s = [], 0.0
    for mod in ctx["trace"]["modules"].values():
        rows = [_rows(n, cfg) for n in mod["ops"] if n.startswith(kernel)]
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
    if best is None:
        return None
    tokens = sum(r["chunk_tokens"] for r in best[1])
    keys = sum(R.ledger_prefill_keys(r, cfg) for r in best[1])
    return (tokens, keys, program_s) if tokens else None


def read(ctx, what, kernel):
    if ctx["peaks"] is None:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    chips, layers = ctx["cell"]["chips"], cfg["num_hidden_layers"]
    if what == "decode_attn":
        calls, secs = _kernel(ctx, kernel)
        if not calls or not secs:
            return None
        vis = _mean_visible_context(ctx)
        least = max(R.decode_attn_bytes(vis, cfg) / chips
                    / peaks["hbm_bytes_per_s"],
                    R.decode_attn_flops(vis, cfg) / chips
                    / peaks["flops_bf16"])
        return 100.0 * calls * least / secs
    if what in ("prefill_attn", "prefill_ms_per_ktok"):
        work = _prefill_in_profile(ctx, kernel)
        if work is None:
            return None
        tokens, keys, program_s = work
        if what == "prefill_ms_per_ktok":
            return 1e6 * program_s / tokens
        _, secs = _kernel(ctx, kernel)
        if not secs:
            return None
        flops = layers * R.prefill_attn_flops(keys, cfg) / chips
        return 100.0 * (flops / peaks["flops_bf16"]) / secs
    if what == "decode_hbm":
        # The decode programs are those in which the decode kernel ran;
        # one step of one of them calls it once a layer.
        calls = secs = 0.0
        for mod in ctx["trace"]["modules"].values():
            n = sum(c for name, (c, _) in mod["ops"].items()
                    if name.startswith(kernel))
            if n:
                calls += n
                secs += mod["seconds"]
        steps = calls / layers
        if not steps:
            return None
        step_s = secs / steps
        vis = _mean_visible_context(ctx)
        byts = (R.weight_bytes_per_step(cfg, cfg["serving"]["quant"], chips)
                + layers * R.decode_attn_bytes(vis, cfg) / chips)
        return 100.0 * (byts / peaks["hbm_bytes_per_s"]) / step_s
    raise ValueError(f"kernels knows no {what!r}")
