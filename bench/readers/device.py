"""The device itself: idle share of the traced span (worst chip) from the
profiler trace, and peak memory from /healthz (memory_stats)."""


def read(ctx, what):
    if what == "device_idle_share":
        return 100.0 * ctx["trace"]["idle_share_worst"]
    if what == "hbm_peak_gb":
        peak = ctx["device"].get("memory_peak_bytes")
        return peak / 1e9 if peak else None
    raise ValueError(f"device knows no {what!r}")
