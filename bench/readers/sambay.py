"""Readings of a cell whose configuration is a SambaY stack (state-space
layers with a state slot a sequence, window attention, one full-attention
KV slot that eight layers read, gated memory units), from the profiler
trace, the client's own view of what was in flight while the profile ran,
and the program's gauges and counters. Counts: roofline_sambay.py.

  shared_decode_attn / window_decode_attn
                    the decode kernel's calls over the FULL slot (the full
                    layer's and the cross layers': n_cross + 1 a step) /
                    of the window layers. All have one result shape, so
                    the kinds are told apart by how often a decode program
                    calls each of its decode-kernel ops: the window
                    layers' op runs n_window times a step, the cross
                    layers' n_cross, the full layer's once
  prefill_attn      attention flops (as the equations need them) of the
                    prefill dispatches the profile holds over the prefill
                    kernel's traced time
  scan_prefill      the selective-scan kernel's bytes (the one kernel whose
                    result is a pair: y and the leaving state) for the
                    prompt tokens of those dispatches, over its traced time
  decode_state      the state-space layers' state bytes in and out a step,
                    over the traced time of the decode programs' ops whose
                    result is state-shaped (float32 [.., d_state, d_inner])
  prefill_ms_per_ktok
                    device milliseconds of the prefill programs in the
                    profile per 1000 prompt tokens they computed
  decode_hbm        (weights + n_readers x visible full KV + window KV +
                    states in and out) / peak bytes/s, over one traced
                    decode step
  slots_live_share  the most state slots held at once since boot / the
                    allocatable ones, % (program gauges)
  pool_live_share   the most pages of a kind's pool (``kind``: full,
                    window) in use at once since boot / its allocatable
                    pages, % (program gauges; readers/mixed.py reads the
                    same of a configuration that states its kinds, this
                    one derives them)
  released_per_s    window-kind pages released behind the window while
                    their sequence ran, a second of the window
  cross_positions_share
                    positions the prefill programs ran the layers BEHIND
                    the full layer for / prompt positions they ran the
                    first half for, %, both counted inside the graph

Another configuration, a program without these kernels or gauges (the
parent commit), or no chip: every reading is None and the metric is left
out.
"""

import bisect
import re

import roofline_sambay as R

DECODE, PREFILL, SCAN = ("paged_attention", "paged_prefill_attention",
                         "selective_scan")
LAG_MAX_S = 2.5


def _dims(op_name):
    """The result's dims from an op's short name
    ('<op>.<n>_bf16_32_72_128_' -> ('bf16', [32, 72, 128])), or None (a
    tuple result has no shape in its name)."""
    m = re.search(r"\.[0-9]+_([a-z]+[0-9]+)_((?:[0-9]+_)+)$", op_name)
    if not m:
        return None
    return m.group(1), [int(d) for d in m.group(2).strip("_").split("_")]


def _named(op_name, kernel):
    """The trace names a kernel by its own name, or ``tpu_custom_call``
    where it sits in a loop inside a scan (readers/mla_moe.py)."""
    return op_name.startswith((kernel + ".", "tpu_custom_call."))


def _is_decode(op_name, cfg):
    """The decode kernel: result [lanes, query heads, pair width]."""
    shape = _dims(op_name)
    return (_named(op_name, DECODE) and shape is not None
            and len(shape[1]) == 3 and tuple(shape[1][1:]) == (
                cfg["num_attention_heads"], R.pool_heads(cfg)[1]))


def _prefill_rows(op_name, cfg):
    """Token rows of a prefill kernel op (result [prompts, query blocks,
    pair heads, block rows x queries a pair head, pair width]), or None."""
    shape = _dims(op_name)
    if not _named(op_name, PREFILL) or shape is None or len(shape[1]) != 5:
        return None
    _, (b, blocks, hkv, m, d) = shape
    if (hkv, d) != R.pool_heads(cfg):
        return None
    rows, rest = divmod(b * blocks * m, cfg["num_attention_heads"] // hkv)
    return None if rest else rows


def _is_scan(op_name):
    return (_dims(op_name) is None
            and (op_name.startswith(SCAN)
                 or op_name.startswith("tpu_custom_call.")))


def _is_state(op_name, cfg):
    """An op whose result is state-shaped: float32 [.., d_state, d_inner]."""
    shape = _dims(op_name)
    di, ns, _, _ = R.scan_sizes(cfg)
    return (shape is not None and shape[0] == "f32" and len(shape[1]) >= 3
            and tuple(shape[1][-2:]) == (ns, di))


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
    """(decode steps in the profile, their programs' seconds, the
    state-shaped ops' seconds in them, {"shared" | "window": (calls,
    seconds) of the decode kernel}). A step calls the decode kernel once a
    layer that attends, through three ops: the window layers' (n_window
    calls a step), the cross layers' and the full layer's, which read
    the full slot."""
    cfg = ctx["config"]
    n_win = R.layers_of(cfg, "window")
    per_step = n_win + R.full_readers(cfg)
    calls = secs = state_s = 0.0
    by_kind = {"shared": [0.0, 0.0], "window": [0.0, 0.0]}
    for mod in ctx["trace"]["modules"].values():
        ops = sorted(((c, s) for name, (c, s) in mod["ops"].items()
                      if _is_decode(name, cfg)), reverse=True)
        n = sum(c for c, _ in ops)
        if not n or any(_prefill_rows(name, cfg) for name in mod["ops"]):
            # (A prefill program calls the decode kernel too: its cross
            # layers' one query a prompt.)
            continue
        calls += n
        secs += mod["seconds"]
        state_s += sum(s for name, (_, s) in mod["ops"].items()
                       if _is_state(name, cfg))
        # The op called least is the full layer's (once a step); beside
        # it one op runs n_window times a step and one n_cross times (the
        # profile cuts a program at either end: to a few calls).
        (full_c, full_s), rest = ops[-1], ops[:-1]
        per = {round(c / full_c): (c, s) for c, s in rest
               if abs(c / full_c - round(c / full_c)) < 0.3}
        n_cross = R.layers_of(cfg, "cross")
        if len(rest) == 2 and n_win in per and n_cross in per:
            by_kind["window"][0] += per[n_win][0]
            by_kind["window"][1] += per[n_win][1]
            by_kind["shared"][0] += full_c + per[n_cross][0]
            by_kind["shared"][1] += full_s + per[n_cross][1]
    return calls / per_step, secs, state_s, by_kind


def _prefill_in_profile(ctx):
    """The ledger's prefill records that are the profile's prefill runs
    (matched as readers/mixed.py matches them), the runs' program seconds,
    the prefill kernel's seconds and the scan kernel's (calls, seconds) in
    them."""
    cfg, prof = ctx["config"], ctx["profile"]
    runs, program_s, kernel_s, scan = [], 0.0, 0.0, [0.0, 0.0]
    for mod in ctx["trace"]["modules"].values():
        found = [(_prefill_rows(n, cfg), s)
                 for n, (_, s) in mod["ops"].items()]
        found = [(rows, s) for rows, s in found if rows]
        if found:
            program_s += mod["seconds"]
            kernel_s += sum(s for _, s in found)
            for n, (c, s) in mod["ops"].items():
                if _is_scan(n):
                    scan[0] += c
                    scan[1] += s
            runs.extend((t, found[0][0]) for t in mod["starts"])
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
    return best[1], program_s, kernel_s, scan


def _counters(ctx, what, kind):
    end, t0 = ctx["metrics_end"], ctx["metrics_open"]
    if what == "slots_live_share":
        total = end.get("tpu_inf_state_slots_total")
        peak = end.get("tpu_inf_state_slots_peak")
        return None if not total or peak is None else 100.0 * peak / total
    if what == "pool_live_share":
        total = end.get(f"tpu_inf_kv_{kind}_pages_total")
        peak = end.get(f"tpu_inf_kv_{kind}_pages_peak")
        return None if not total or peak is None else 100.0 * peak / total
    if what == "released_per_s":
        name = "tpu_inf_kv_window_pages_released_total"
        if name not in end:
            return None
        return (end[name] - t0.get(name, 0.0)) / ctx["seconds"]
    cross, ran = ("tpu_inf_prefill_cross_positions_total",
                  "tpu_inf_prefill_positions_total")
    if cross not in end or ran not in end:
        return None
    positions = end[ran] - t0.get(ran, 0.0)
    if positions <= 0:
        return None
    return 100.0 * (end[cross] - t0.get(cross, 0.0)) / positions


def read(ctx, what, kind=None):
    cfg = ctx["config"]
    if cfg.get("model_type") != "phi4flash":
        return None
    if what in ("slots_live_share", "cross_positions_share",
                "pool_live_share", "released_per_s"):
        return _counters(ctx, what, kind)
    if ctx["peaks"] is None:
        return None
    peaks = ctx["peaks"]
    if what in ("shared_decode_attn", "window_decode_attn"):
        kind = what.split("_")[0]
        calls, secs = _decode_programs(ctx)[3][kind]
        if not calls or not secs:
            return None
        vis = _in_flight(ctx)[1]["window" if kind == "window" else "full"]
        return 100.0 * calls * _least(R.decode_attn_bytes(vis, cfg),
                                      R.attn_flops(vis, cfg), peaks) / secs
    if what in ("prefill_attn", "prefill_ms_per_ktok", "scan_prefill"):
        work = _prefill_in_profile(ctx)
        if work is None:
            return None
        recs, program_s, kernel_s, (scan_calls, scan_s) = work
        tokens = sum(r["chunk_tokens"] for r in recs)
        if what == "prefill_ms_per_ktok":
            return 1e6 * program_s / tokens
        if what == "scan_prefill":
            if not scan_s:
                return None
            lanes = sum(max(1, r["slots"]) for r in recs)
            n = R.layers_of(cfg, "ssm")
            return 100.0 * n * _least(R.scan_bytes(tokens, lanes, cfg),
                                      R.scan_flops(tokens, cfg),
                                      peaks) / scan_s
        if not kernel_s:
            return None
        flops = sum(R.layers_of(cfg, k) * R.attn_flops(
            R.ledger_prefill_pairs(r, cfg, k), cfg)
            for r in recs for k in ("full", "window"))
        return 100.0 * (flops / peaks["flops_bf16"]) / kernel_s
    if what == "decode_state":
        steps, _, state_s, _ = _decode_programs(ctx)
        if not steps or not state_s:
            return None
        lanes = _mean(_in_flight(ctx)[0])
        return 100.0 * steps * (R.decode_state_bytes(lanes, cfg)
                                / peaks["hbm_bytes_per_s"]) / state_s
    if what == "decode_hbm":
        steps, secs, _, _ = _decode_programs(ctx)
        if not steps:
            return None
        seqs, vis = _in_flight(ctx)
        return 100.0 * (R.decode_step_bytes(_mean(seqs), vis, cfg)
                        / peaks["hbm_bytes_per_s"]) / (secs / steps)
    raise ValueError(f"sambay knows no {what!r}")
