"""Readings of a cell whose configuration's layers differ in KIND (Laguna:
full and sliding attention layers with different head counts, a pool a
kind, routed experts beside a shared one), from the profiler trace, the
client's own view of what was in flight while the profile ran, and the
program's pool gauges. Counts: roofline_mixed.py.

  window_decode_attn / full_decode_attn
                    the decode kernel's calls of ONE kind (told apart by
                    the query heads in the result's shape): bytes / flops
                    one call needs for the contexts visible to that kind
                    (a sliding layer: min(context, window)) of the
                    sequences decoding during the profile, over those
                    calls' traced time
  prefill_attn      attention flops of the prefill dispatches the profile
                    holds, each kind's layers by its own head count and
                    window, over the prefill kernel's traced time
  prefill_ms_per_ktok
                    device milliseconds of the prefill programs in the
                    profile per 1000 prompt tokens they computed
  moe_experts       the grouped kernels' least time over their traced
                    time, in the DECODE programs (readers/mla_moe.py says
                    why a prefill's calls are left out)
  decode_hbm        (non-expert weights + expected distinct held experts
                    at the observed batch + visible KV of both kinds) /
                    peak bytes/s, over one traced decode step
  pool_live_share   the most pages of a kind's pool in use at once since
                    boot / its allocatable pages, % (program gauges; the
                    pages in use at the instant of a scrape swing with
                    the drain: 22% and 4% in two runs of one tree)
  released_per_s    window-kind pages released behind the window while
                    their sequence ran, a second of the window

A configuration of one kind (no ``layer_types``), a program without these
kernels or gauges (the parent commit), or no chip: every reading is None
and the metric is left out.
"""

import bisect
import re

import roofline_mixed as R

DECODE, PREFILL, EXPERTS = ("paged_attention", "paged_prefill_attention",
                            "moe_grouped_experts")
LAG_MAX_S = 2.5


def _dims(op_name):
    """The result's dims from an op's short name
    ('<op>.<n>_bf16_32_72_128_' -> ('bf16', [32, 72, 128])), or None."""
    m = re.search(r"\.[0-9]+_([a-z]+[0-9]+)_((?:[0-9]+_)+)$", op_name)
    if not m:
        return None
    return m.group(1), [int(d) for d in m.group(2).strip("_").split("_")]


def _named(op_name, kernel):
    """The trace names a kernel by its own name, or ``tpu_custom_call``
    where it sits in a loop inside a scan (readers/mla_moe.py)."""
    return op_name.startswith((kernel + ".", "tpu_custom_call."))


def _decode_kind(op_name, cfg):
    """The kind whose decode kernel this op is (result [lanes, query
    heads, head_dim]), or None."""
    shape = _dims(op_name)
    if not _named(op_name, DECODE) or shape is None or len(shape[1]) != 3:
        return None
    _, (_, heads, d) = shape
    for kind in ("full", "window"):
        if (heads, d) == (R.heads_of(cfg, kind), R.head_dim(cfg)):
            return kind
    return None


def _prefill_kind_rows(op_name, cfg):
    """(kind, token rows) of a prefill kernel op (result [prompts, query
    blocks, kv heads, block rows x heads a kv head, head_dim]), or None."""
    shape = _dims(op_name)
    if not _named(op_name, PREFILL) or shape is None or len(shape[1]) != 5:
        return None
    _, (b, blocks, hkv, m, d) = shape
    if (hkv, d) != (cfg["num_key_value_heads"], R.head_dim(cfg)):
        return None
    for kind in ("window", "full"):
        n_rep = R.heads_of(cfg, kind) // hkv
        rows, rest = divmod(b * blocks * m, n_rep)
        if not rest and rows & (rows - 1) == 0:     # buckets: powers of 2
            return kind, rows
    return None


def _expert_seconds(ops, cfg):
    """Seconds of the two grouped expert kernels among ``ops``: bf16
    [rows, moe_intermediate_size] for ``gate_up``, f32 [rows,
    hidden_size] for ``down``; no other kernel returns those."""
    secs = 0.0
    for name, (_, s) in ops.items():
        shape = _dims(name)
        if (shape is None or len(shape[1]) != 2
                or not _named(name, EXPERTS + "_gate_up")
                and not _named(name, EXPERTS + "_down")):
            continue
        if (shape[0], shape[1][1]) in (
                ("bf16", cfg["moe_intermediate_size"]),
                ("f32", cfg["hidden_size"])):
            secs += s
    return secs


def _in_flight(ctx):
    """(sequences decoding at each of 60 instants of the profiled seconds,
    {kind: mean over the instants of the contexts visible to that kind,
    summed over the sequences}), on the client's clock."""
    prof, cfg = ctx["profile"], ctx["config"]
    t0, t1 = prof["start_s"], prof["start_s"] + prof["seconds"]
    seqs, vis, n = [], {"full": 0.0, "window": 0.0}, 60
    for k in range(n):
        t = t0 + (t1 - t0) * (k + 0.5) / n
        seqs.append(0)
        for r in ctx["records"]:
            ts = r["token_s"]
            if len(ts) >= 2 and ts[0] <= t <= ts[-1]:
                seqs[-1] += 1
                held = r["prompt_tokens"] + bisect.bisect_right(ts, t)
                for kind in vis:
                    vis[kind] += R.visible(held, cfg, kind) / n
    return seqs, vis


def _mean(xs):
    return sum(xs) / len(xs)


def _least(byts, flops, peaks):
    return max(byts / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"])


def _decode_programs(ctx):
    """(decode steps in the profile, their programs' seconds, the grouped
    expert kernels' seconds inside them, {kind: (calls, seconds) of its
    decode kernel}): a step calls the decode kernel once a layer."""
    cfg = ctx["config"]
    calls = secs = experts = 0.0
    by_kind = {"full": [0.0, 0.0], "window": [0.0, 0.0]}
    for mod in ctx["trace"]["modules"].values():
        n = 0
        for name, (c, s) in mod["ops"].items():
            kind = _decode_kind(name, cfg)
            if kind:
                n += c
                by_kind[kind][0] += c
                by_kind[kind][1] += s
        if n:
            calls += n
            secs += mod["seconds"]
            experts += _expert_seconds(mod["ops"], cfg)
    return calls / cfg["num_hidden_layers"], secs, experts, by_kind


def _prefill_in_profile(ctx):
    """The ledger's prefill records that are the profile's prefill runs
    (matched as readers/kernels.py matches them), the runs' program
    seconds and the prefill kernel's seconds in them."""
    cfg, prof = ctx["config"], ctx["profile"]
    runs, program_s, kernel_s = [], 0.0, 0.0
    for mod in ctx["trace"]["modules"].values():
        found = [(_prefill_kind_rows(n, cfg), s)
                 for n, (_, s) in mod["ops"].items()]
        found = [(kr, s) for kr, s in found if kr]
        if found:
            program_s += mod["seconds"]
            kernel_s += sum(s for _, s in found)
            runs.extend((t, found[0][0][1]) for t in mod["starts"])
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
    return best[1], program_s, kernel_s


def _gauges(ctx, what, kind):
    end, t0 = ctx["metrics_end"], ctx["metrics_open"]
    if what == "pool_live_share":
        total = end.get(f"tpu_inf_kv_{kind}_pages_total")
        peak = end.get(f"tpu_inf_kv_{kind}_pages_peak")
        return None if not total or peak is None else 100.0 * peak / total
    name = "tpu_inf_kv_window_pages_released_total"
    if name not in end:
        return None
    return (end[name] - t0.get(name, 0.0)) / ctx["seconds"]


def read(ctx, what, kind=None):
    cfg = ctx["config"]
    if "layer_types" not in cfg:
        return None
    if what in ("pool_live_share", "released_per_s"):
        return _gauges(ctx, what, kind)
    if ctx["peaks"] is None:
        return None
    peaks = ctx["peaks"]
    if what in ("window_decode_attn", "full_decode_attn"):
        kind = what.split("_")[0]
        calls, secs = _decode_programs(ctx)[3][kind]
        if not calls or not secs:
            return None
        vis = _in_flight(ctx)[1][kind]
        return 100.0 * calls * _least(R.decode_attn_bytes(vis, cfg),
                                      R.attn_flops(vis, cfg, kind),
                                      peaks) / secs
    if what in ("prefill_attn", "prefill_ms_per_ktok"):
        work = _prefill_in_profile(ctx)
        if work is None:
            return None
        recs, program_s, kernel_s = work
        if what == "prefill_ms_per_ktok":
            return 1e6 * program_s / sum(r["chunk_tokens"] for r in recs)
        if not kernel_s:
            return None
        flops = sum(R.layers_of(cfg, k) * R.attn_flops(
            R.ledger_prefill_pairs(r, cfg, k), cfg, k)
            for r in recs for k in ("full", "window"))
        return 100.0 * (flops / peaks["flops_bf16"]) / kernel_s
    if what == "moe_experts":
        steps, _, secs, _ = _decode_programs(ctx)
        if not steps or not secs:
            return None
        seqs, _ = _in_flight(ctx)
        step = _mean([_least(R.moe_layer_bytes(b, cfg),
                             R.moe_layer_flops(b, cfg), peaks) for b in seqs])
        return 100.0 * R.expert_layers(cfg) * steps * step / secs
    if what == "decode_hbm":
        steps, secs, _, _ = _decode_programs(ctx)
        if not steps:
            return None
        seqs, vis = _in_flight(ctx)
        return 100.0 * (R.decode_step_bytes(_mean(seqs), vis, cfg)
                        / peaks["hbm_bytes_per_s"]) / (secs / steps)
    raise ValueError(f"mixed knows no {what!r}")
