"""XLA compilations the server logged between window open and the end of
the drain (JAX_LOG_COMPILES is set on the child). Must read 0."""


def read(ctx):
    return float(ctx["compiles_in_window"])
