"""Readings of a LOOPED configuration's cell (Ouro: the layers run
``total_ut_steps`` times a token, a KV slot per (pass, layer)), from the
profiler trace and the client's own view of what was in flight while the
profile ran. Counts: roofline_looped.py; pass and layer counts from the
configuration file, never from the program. Which ops are the kernels',
which ledger records are the profile's prefill runs and what contexts
were visible are read as readers/kernels.py reads them (its functions).

  looped_decode_hbm    (passes x layer weights + head + passes x layers x
                       visible KV) / peak bytes/s, over one traced decode
                       step; a step is passes x layers calls of the decode
                       kernel
  looped_layer_pass_us traced decode-program microseconds per layer
                       application (= per decode-kernel call)
  looped_prefill_attn  attention flops of the prefill dispatches the
                       profile holds, over every (pass, layer), / peak bf16
                       flops, over the prefill kernel's traced time
  looped_prefill_ms_per_ktok
                       device milliseconds of the prefill programs in the
                       profile per 1000 prompt tokens they computed

A configuration that is not looped (no ``total_ut_steps``), a program
without these kernels, or no chip: every reading is None.
"""

import os

import roofline_looped as L
from manifest import load_module

K = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "kernels.py"))
DECODE, PREFILL = "paged_attention", "paged_prefill_attention"


def _decode_programs(ctx):
    """(calls of the decode kernel, seconds) of the programs it ran in."""
    calls = secs = 0.0
    for mod in ctx["trace"]["modules"].values():
        n = sum(c for name, (c, _) in mod["ops"].items()
                if name.startswith(DECODE))
        if n:
            calls += n
            secs += mod["seconds"]
    return calls, secs


def read(ctx, what):
    if ctx["peaks"] is None or "total_ut_steps" not in ctx["config"]:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    if what in ("looped_decode_hbm", "looped_layer_pass_us"):
        calls, secs = _decode_programs(ctx)
        if not calls or not secs:
            return None
        if what == "looped_layer_pass_us":
            return 1e6 * secs / calls
        step_s = secs * L.layer_applications(cfg) / calls
        vis = K._mean_visible_context(ctx)
        return 100.0 * (L.decode_step_bytes(vis, cfg)
                        / peaks["hbm_bytes_per_s"]) / step_s
    if what in ("looped_prefill_attn", "looped_prefill_ms_per_ktok"):
        work = K._prefill_in_profile(ctx, PREFILL)
        if work is None:
            return None
        tokens, keys, program_s = work
        if what == "looped_prefill_ms_per_ktok":
            return 1e6 * program_s / tokens
        _, secs = K._kernel(ctx, PREFILL)
        if not secs:
            return None
        return 100.0 * (L.prefill_attn_flops(keys, cfg)
                        / peaks["flops_bf16"]) / secs
    raise ValueError(f"looped knows no {what!r}")
