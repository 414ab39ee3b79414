"""Device milliseconds per edge_spmm_nb kernel call in the traced
window: the Pallas call alone, without the ops that feed it."""


def read(ctx):
    k = ctx["trace"]["kernels"]["edge_spmm_nb"]
    if not k["count"]:
        return None
    return 1e3 * k["total_s"] / k["count"]
