"""The program's loop clock over the profiled seconds: ``POST
/debug/profile`` answers with ``"loop"``, the delta of every
``tpu_inf_loop_*`` family and of the two dispatch counters between the
trace's start and its stop, and ``loop_wall_s``, those seconds on the
clock. The one interval on which the device trace's idle share and the
clock's statement can be laid side by side. A server that answers
without ``"loop"`` (or without a family asked for) gives None."""

TOTAL = "tpu_inf_loop_seconds_total"
IDLE = "tpu_inf_loop_idle_seconds_total"
WAIT = "tpu_inf_loop_device_wait_seconds_total"
STARVED = "tpu_inf_loop_starved_seconds_total"
DISPATCHES = ("tpu_inf_decode_dispatches_total",
              "tpu_inf_prefill_dispatches_total")
# What host seconds a dispatch are made of.
RATE_KEYS = (TOTAL, IDLE, WAIT) + DISPATCHES


def _host_per_dispatch(d):
    """Seconds of the nine host phases (all but idle / device_wait) a
    dispatch, from one set of deltas; None where nothing was dispatched."""
    n = sum(d[k] for k in DISPATCHES)
    return (d[TOTAL] - d[IDLE] - d[WAIT]) / n if n > 0 else None


def read(ctx, what):
    loop = (ctx.get("profile") or {}).get("loop")
    keys = RATE_KEYS + (STARVED, "loop_wall_s")
    if not loop or any(k not in loop for k in keys) \
            or loop["loop_wall_s"] <= 0:
        return None
    wall = loop["loop_wall_s"]
    starved = 100.0 * loop[STARVED] / wall
    if what == "capture_starved_share":
        return starved
    if what == "capture_unclaimed_idle_share":
        # Device idleness no host phase can own: under device_wait or
        # enqueue, or inside a program.
        return (100.0 * ctx["trace"]["idle_share_worst"] - starved
                - 100.0 * loop[IDLE] / wall)
    if what == "tracer_host_stretch":
        a, b = ctx["metrics_open"], ctx["metrics_end"]
        if any(k not in b for k in RATE_KEYS):
            return None
        outside = {k: b[k] - a.get(k, 0.0) - loop[k] for k in RATE_KEYS}
        inside, outside = _host_per_dispatch(loop), _host_per_dispatch(outside)
        return inside / outside if inside and outside else None
    raise ValueError(f"profile_loop knows no {what!r}")
