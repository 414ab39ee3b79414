"""Device milliseconds per tick-program invocation (``jit_tick``
module events) in the traced window."""


def read(ctx):
    m = ctx["trace"]["modules"]["tick"]
    if not m["count"]:
        return None
    return 1e3 * m["total_s"] / m["count"]
