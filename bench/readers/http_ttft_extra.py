"""Client TTFT mean minus the scheduler's own TTFT mean (its histogram's
window delta on /metrics), in ms: what HTTP, tokenizing and the
generator's lateness add on top of the engine."""

import metrics as M


def read(ctx, family="tpu_inf_ttft_seconds"):
    a, b = ctx["metrics_open"], ctx["metrics_end"]
    n = b.get(family + "_count", 0) - a.get(family + "_count", 0)
    client = M.mean([M.ttft(r) for r in ctx["ok"]])
    if n <= 0 or client is None:
        return None
    server = (b[family + "_sum"] - a.get(family + "_sum", 0)) / n
    return 1000.0 * (client - server)
