"""Readings of the SmallThinker cell (one pipeline stage that holds every
expert of its layers; rope-less full layers among window ones, a pool a
kind), from the profiler trace, the client's view of what was in flight
while the profile ran, and the program's counters. The attention, pool
and whole-program readings are ``readers/mixed.py``'s own, handed the
configuration under the key names it reads
(``roofline_smallthinker.as_mixed``); the expert readings are new,
because here the program COUNTS what the kernels were given.

  prefill_attn / prefill_ms_per_ktok / pool_live_share
                    as readers/mixed.py
  decode_attn       the decode kernel's calls of BOTH kinds together:
                    bytes / flops a step's twelve calls need for the
                    contexts visible to each kind (a window layer:
                    min(context, window)) of the sequences decoding
                    during the profile, over those calls' traced time.
                    One share, not one a kind as readers/mixed.py has:
                    both kinds have 28 query heads here, and the trace
                    tells a kernel's calls apart by its result's shape
  moe_experts_decode
                    HBM roofline of the grouped kernels in the DECODE
                    programs: distinct experts a decode layer step (the
                    program's counters over the window) x 3 x hidden x
                    width x 2 bytes, or the real pairs' FLOPs if that is
                    longer, over the kernels' traced time a layer step
  moe_experts_prefill
                    share of the bf16 peak of the grouped kernels in the
                    PREFILL programs: 2 x tokens x top-k x expert
                    parameters a layer (real pairs; padded rows are not
                    work) for the prompt tokens of the profile's prefill
                    dispatches, over the kernels' traced time there
  decode_hbm        the whole decode step: (non-expert weights once +
                    the counted distinct experts in every layer + visible
                    K / V of both kinds) / peak bytes/s, over one traced
                    decode step's busy time
  rows_per_expert   real rows an expert with any row gets in a decode
                    layer step: decode_batch_mean x top-k / distinct
                    experts a decode layer step
  padded_row_share  rows the grouped kernels ran that held no pair, % of
                    all rows they ran (what tile_rows costs), prefill
                    and decode together
  pool_booked_share the most pages of a kind's pool that admission held
                    back for the bound sequences at once since boot / its
                    allocatable pages, % (a pool sized on live tokens is
                    full when this reaches 100, before its pages in use
                    do; the instant's gauge reads 0 at the close, when the
                    client has hung up on its streams)

A program without these counters or kernels (the parent commit), or no
chip for the shares: the reading is None and the metric is left out.
"""

import os

import roofline_smallthinker as R
from manifest import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
X = load_module(os.path.join(HERE, "mixed.py"))
steps = load_module(os.path.join(HERE, "steps.py"))
delta = load_module(os.path.join(HERE, "metrics_delta.py")).read

SAME = ("prefill_attn", "prefill_ms_per_ktok", "pool_live_share")
KINDS = ("full", "window")
DISTINCT = ("tpu_inf_moe_distinct_experts_total",
            "tpu_inf_moe_decode_layer_steps_total")


def _prefill_expert_seconds(ctx, cfg):
    """Seconds of the grouped kernels in the programs that ran the
    prefill kernel."""
    return sum(X._expert_seconds(mod["ops"], cfg)
               for mod in ctx["trace"]["modules"].values()
               if any(X._prefill_kind_rows(n, cfg) for n in mod["ops"]))


def read(ctx, what, kind=None):
    cfg = ctx["config"]
    if "sliding_window_layout" not in cfg:
        return None
    as_mixed = dict(ctx, config=R.as_mixed(cfg))
    if what in SAME:
        return X.read(as_mixed, what, kind)
    if what == "pool_booked_share":
        end = ctx["metrics_end"]
        total = end.get(f"tpu_inf_kv_{kind}_pages_total")
        booked = end.get(f"tpu_inf_kv_{kind}_pages_booked_peak")
        return None if not total or booked is None else 100.0 * booked / total
    if what == "padded_row_share":
        real = delta(ctx, "tpu_inf_moe_computed_pairs_total",
                     "tpu_inf_moe_tile_rows_total")
        return None if real is None else 100.0 * (1.0 - real)
    # Distinct experts a decode layer step over the window, or None.
    distinct = delta(ctx, *DISTINCT)
    if what == "rows_per_expert":
        batch = steps.read(ctx, "decode_batch_mean")
        return None if not distinct or not batch else \
            batch * R.top_k(cfg) / distinct
    if ctx["peaks"] is None:
        return None
    peaks, mcfg = ctx["peaks"], as_mixed["config"]
    if what == "moe_experts_prefill":
        work = X._prefill_in_profile(as_mixed)
        secs = _prefill_expert_seconds(ctx, mcfg)
        if work is None or not secs:
            return None
        tokens = sum(r["chunk_tokens"] for r in work[0])
        return 100.0 * (R.layers(cfg) * R.moe_flops(tokens, cfg)
                        / peaks["flops_bf16"]) / secs
    n_steps, secs, expert_s, by_kind = X._decode_programs(as_mixed)
    if not n_steps:
        return None
    seqs, vis = X._in_flight(as_mixed)
    if what == "decode_attn":
        attn_s = sum(s for _, s in by_kind.values())
        if not attn_s:
            return None
        byts, flops = (sum(X.R.layers_of(mcfg, k) * f(k) for k in KINDS)
                       for f in (
                           lambda k: X.R.decode_attn_bytes(vis[k], mcfg),
                           lambda k: X.R.attn_flops(vis[k], mcfg, k)))
        return 100.0 * n_steps * X._least(byts, flops, peaks) / attn_s
    if distinct is None:
        return None
    if what == "moe_experts_decode":
        if not expert_s:
            return None
        least = max(R.moe_read_bytes(distinct, cfg)
                    / peaks["hbm_bytes_per_s"],
                    R.moe_flops(X._mean(seqs), cfg) / peaks["flops_bf16"])
        return 100.0 * R.layers(cfg) * n_steps * least / expert_s
    if what == "decode_hbm":
        return 100.0 * (R.decode_step_bytes(distinct, vis, cfg)
                        / peaks["hbm_bytes_per_s"]) / (secs / n_steps)
    raise ValueError(f"smallthinker knows no {what!r}")
