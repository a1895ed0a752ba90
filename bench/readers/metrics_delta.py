"""A ratio of /metrics counter deltas over the window (scraped at window
open and after the drain), e.g. a histogram's sum over its count."""


def read(ctx, num, den=None, scale=1.0):
    a, b = ctx["metrics_open"], ctx["metrics_end"]
    if num not in b or (den and den not in b):
        return None
    dn = b[num] - a.get(num, 0.0)
    if den is None:
        return scale * dn
    dd = b[den] - a.get(den, 0.0)
    return scale * dn / dd if dd > 0 else None
