"""A /metrics sample as it stood when the window closed (a gauge the
program sets once, e.g. a boot phase's seconds): ``metrics_delta`` would
subtract such a value from itself."""


def read(ctx, name, scale=1.0):
    value = ctx["metrics_end"].get(name)
    return None if value is None else scale * value
