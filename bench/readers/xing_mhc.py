"""Readings of a cell whose model carries several residual streams mixed
by hyper-connections around latent attention and a layer that holds
every routed expert (Xing4.0), from the profiler trace, the client's
view of what was in flight while the profile ran, and the program's
counters. Counts: roofline_xing_mhc.py. The latent attention's own
readings are ``readers/mla_moe.py``'s, which this cell lists as they are.

  mhc_busy_share    seconds of the ops a trace can tell are a
                    hyper-connection's BY THEIR RESULT'S SHAPE, % of the
                    device's busy seconds: the coefficient head's matmul
                    (f32 [n (n + 2), T]), the projection's ops (f32 [n^2
                    | n, n | 1, n | n, T]), the transposed coefficients
                    (f32 [.., n (n + 2)]) and the streams' re-assembly
                    (a result n x hidden_size wide). A LOWER bound of
                    the time under the program's ``mhc_*`` scopes: the
                    trace summary carries an op's name and its result's
                    shape, not its scope; the compiler fuses the
                    pre-mix into the norm and projection behind it and
                    gives the post-mix's two halves tuple results, which
                    carry no shape (PERF.md section 5 has the share by
                    scope, from a profile read with its metadata)
  mhc_prefill_us_per_ktok
                    microseconds of those ops in the PREFILL programs per
                    1000 prompt tokens the profile's prefill dispatches
                    computed. A time, not a share of the HBM peak: the
                    v5e's compiler keeps a chunk's streams in VMEM
                    between the ops of a hyper-connection (the head's
                    matmul over a 1024-token chunk's 29 MB took 26.7 us,
                    1.1 TB/s, and the re-assembly of a 512-token chunk's
                    streams 22.3 us, 1.3 TB/s: my chip run, PR 47), so
                    their bytes are not HBM traffic and a roofline over
                    them read 111-123%
  moe_experts_decode
                    HBM roofline of the grouped kernels in the DECODE
                    programs: distinct experts a decode layer step (the
                    program's counters over the window) x 3 x hidden x
                    width x 2 bytes, or the real pairs' FLOPs if that is
                    longer, over the kernels' traced time a layer step
  decode_hbm        the whole decode step: (non-expert weights once + the
                    counted distinct experts in every expert layer + the
                    visible latents) / peak bytes/s, over one traced
                    decode step

A program without these counters, kernels or streams (another
configuration, the parent commit), or no chip for the shares: the
reading is None and the metric is left out.
"""

import os
import re

import roofline_xing_mhc as R
from manifest import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
X = load_module(os.path.join(HERE, "mla_moe.py"))
delta = load_module(os.path.join(HERE, "metrics_delta.py")).read

DISTINCT = ("tpu_inf_moe_distinct_experts_total",
            "tpu_inf_moe_decode_layer_steps_total")
SHAPE = re.compile(r"_([a-z]+[0-9]+)_((?:[0-9]+_)+)$")


def is_mhc(op_name: str, cfg: dict) -> bool:
    """Whether a traced op's result has a shape only a hyper-connection
    makes (the module docstring lists them)."""
    dtype, dims = _dims(op_name)
    if not dims:
        return False
    n, c = R.streams(cfg), R.n_coeff(cfg)
    if dims[-1] == R.wide(cfg):
        return True
    if dtype != "f32" or len(dims) < 2:
        return False
    return (dims[:-1] in ((c,), (n * n,), (n, n), (1, n), (n,))
            or dims[-1] == c)


def _dims(op_name: str):
    m = SHAPE.search(op_name)
    return (m.group(1), tuple(int(d) for d in
                              m.group(2).strip("_").split("_"))) if m \
        else (None, ())


def _mhc_seconds(ops, cfg):
    return sum(s for name, (_, s) in ops.items() if is_mhc(name, cfg))


def read(ctx, what):
    cfg = ctx["config"]
    if "hc_mult" not in cfg:
        return None
    if ctx["peaks"] is None:
        return None
    peaks = ctx["peaks"]
    if what == "mhc_busy_share":
        chips = ctx["trace"]["chips"].values()
        secs = sum(_mhc_seconds(c["ops"], cfg) for c in chips) / len(chips)
        return 100.0 * secs / ctx["trace"]["busy_s"] if secs else None
    if what == "mhc_prefill_us_per_ktok":
        work = X._prefill_in_profile(ctx)
        secs = sum(_mhc_seconds(mod["ops"], cfg)
                   for mod in ctx["trace"]["modules"].values()
                   if any(n.startswith(X.PREFILL) for n in mod["ops"]))
        if work is None or not secs:
            return None
        return 1e9 * secs / sum(r["chunk_tokens"] for r in work[0])
    n_steps, secs, expert_s = X._decode_steps(ctx)
    # Distinct experts a decode layer step over the window, or None.
    distinct = delta(ctx, *DISTINCT)
    if not n_steps or distinct is None:
        return None
    seqs, vis = X._in_flight(ctx)
    if what == "moe_experts_decode":
        if not expert_s:
            return None
        least = X._least(R.moe_read_bytes(distinct, cfg),
                         R.moe_flops(X._mean(seqs), cfg), peaks)
        return (100.0 * R.M.expert_layers(cfg) * n_steps * least
                / expert_s)
    if what == "decode_hbm":
        return 100.0 * (R.decode_step_bytes(distinct, vis, cfg)
                        / peaks["hbm_bytes_per_s"]) / (secs / n_steps)
    raise ValueError(f"xing_mhc knows no {what!r}")
