"""Device idle share (%) of the traced window: 1 - busy / window."""


def read(ctx):
    return 100.0 * (1.0 - ctx["trace"]["busy_s"] / ctx["window_s"])
