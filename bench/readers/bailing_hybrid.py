"""Readings of a cell whose model has delta-rule layers beside latent
attention over routed experts limited to groups (Ling-3.0-flash), from
the profiler trace, the client's view of what was in flight while the
profile ran, and the program's counters. Counts:
roofline_bailing_hybrid.py. The matching of prefill dispatches and decode
steps is ``readers/mla_moe.py``'s, run on a view of the configuration
under that reader's keys (its latent layers are this stage's two).

  delta_state_decode   the one-token update's bytes (every live lane's
                       matrix state in and out + the token's operands, a
                       delta-rule layer a step) / peak bytes/s, over the
                       traced time of the ``kda_step`` kernel
  delta_chunk_prefill  the chunk kernel's least time (counted FLOPs
                       against the bf16 peak, bytes against the HBM peak,
                       the larger) for the prompt tokens of the profile's
                       prefill dispatches, over ``kda_chunk_prefill``'s
                       traced time
  mla_decode_attn / mla_prefill_attn / prefill_ms_per_ktok
                       as readers/mla_moe.py reads them, over this
                       stage's latent layers
  moe_experts_decode   HBM roofline of the grouped kernels in the DECODE
                       programs: distinct held experts a decode layer
                       step (the program's counters over the window) x 3
                       x hidden x width x 2 bytes, or the real pairs'
                       FLOPs if that is longer, over the kernels' traced
                       time a layer step
  slots_live_share     the most state slots held at once since boot / the
                       slots there are (the program's gauges)
  decode_hbm           the whole decode step: (non-expert weights once +
                       the counted distinct experts in every expert layer
                       + every live lane's states in and out + the visible
                       latents) / peak bytes/s, over one traced decode
                       step

A program without these kernels or counters (another configuration, the
parent commit), or no chip for the shares: the reading is None and the
metric is left out.
"""

import os
import re

import roofline_bailing_hybrid as R
from manifest import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
X = load_module(os.path.join(HERE, "mla_moe.py"))
delta = load_module(os.path.join(HERE, "metrics_delta.py")).read

STEP, CHUNK = "kda_step", "kda_chunk_prefill"
DISTINCT = ("tpu_inf_moe_distinct_experts_total",
            "tpu_inf_moe_decode_layer_steps_total")
PAIRS = ("tpu_inf_moe_local_pairs_total", "tpu_inf_moe_tokens_total")
SHAPE = re.compile(r"_[a-z]+[0-9]+_(?:[0-9]+_)+$")


def view(ctx):
    """``ctx`` with the configuration under readers/mla_moe.py's keys:
    its ``num_hidden_layers`` are the layers that run the latent
    kernels."""
    cfg = ctx["config"]
    return dict(ctx, config=dict(cfg, num_hidden_layers=R.layers_of(
        cfg, "full")))


def is_delta(op_name: str, kernel: str) -> bool:
    """Whether a traced op is the delta-rule kernel ``kernel``: by its
    name, or, inside a scan over layers, where the v5e's trace calls a
    kernel ``tpu_custom_call.<n>``, by having NO shape behind the name:
    these kernels return a pair (the output and the state pool), and the
    trace summary gives a pair no shape. Every other kernel of these
    programs (latent attention, the grouped experts) returns one array,
    whose shape the name carries; which of the two delta kernels it is
    follows from the program (``kda_step`` where the decode kernel ran,
    ``kda_chunk_prefill`` where the prefill kernel did)."""
    return (SHAPE.search(op_name) is None
            and (op_name.startswith(kernel)
                 or op_name.startswith("tpu_custom_call.")))


def _delta_seconds(ctx, kernel, beside):
    """Seconds of the delta-rule kernel ``kernel`` in the programs of the
    profile that also hold an op named ``beside``.."""
    secs = 0.0
    for mod in ctx["trace"]["modules"].values():
        if any(n.startswith(beside) for n in mod["ops"]):
            secs += sum(s for n, (_, s) in mod["ops"].items()
                        if is_delta(n, kernel))
    return secs


def read(ctx, what):
    cfg = ctx["config"]
    if cfg.get("model_type") != "bailing_hybrid":
        return None
    if what == "slots_live_share":
        end = ctx["metrics_end"]
        total = end.get("tpu_inf_state_slots_total")
        peak = end.get("tpu_inf_state_slots_peak")
        return None if not total or peak is None else 100.0 * peak / total
    if ctx["peaks"] is None:
        return None
    peaks, v = ctx["peaks"], view(ctx)
    if what in ("mla_decode_attn", "mla_prefill_attn",
                "mla_prefill_ms_per_ktok"):
        return X.read(v, what)
    if what == "delta_chunk_prefill":
        work = X._prefill_in_profile(v)
        secs = _delta_seconds(ctx, CHUNK, X.PREFILL)
        if work is None or not secs:
            return None
        tokens = sum(r["chunk_tokens"] for r in work[0])
        lanes = sum(max(1, r["slots"]) for r in work[0])
        least = X._least(R.chunk_bytes(tokens, lanes, cfg),
                         R.chunk_flops(tokens, cfg), peaks)
        return 100.0 * R.layers_of(cfg, "kda") * least / secs
    n_steps, secs, expert_s = X._decode_steps(v)
    if not n_steps:
        return None
    seqs, vis = X._in_flight(v)
    lanes = X._mean(seqs)
    if what == "delta_state_decode":
        step_s = _delta_seconds(ctx, STEP, X.DECODE)
        if not step_s:
            return None
        least = X._least(R.step_bytes(lanes, cfg), R.step_flops(lanes, cfg),
                         peaks)
        return 100.0 * R.layers_of(cfg, "kda") * n_steps * least / step_s
    # Distinct experts a decode layer step over the window, or None.
    distinct = delta(ctx, *DISTINCT)
    if distinct is None:
        return None
    if what == "moe_experts_decode":
        pairs = delta(ctx, *PAIRS)
        if not expert_s or pairs is None:
            return None
        least = X._least(R.moe_read_bytes(distinct, cfg),
                         R.moe_flops(lanes * pairs, cfg), peaks)
        return 100.0 * R.expert_layers(cfg) * n_steps * least / expert_s
    if what == "decode_hbm":
        return 100.0 * (R.decode_step_bytes(lanes, distinct, vis, cfg)
                        / peaks["hbm_bytes_per_s"]) / (secs / n_steps)
    raise ValueError(f"bailing_hybrid knows no {what!r}")
