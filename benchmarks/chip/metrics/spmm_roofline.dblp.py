"""The edge SpMM's share of its roofline (%) in the traced window: the
least time the chip needs for one call's algorithmic work (costs.py)
over the device time per call of the whole SpMM, the edge_spmm_nb
kernel and the ops that make its operands (the gather of the rows it
reads and their lane pad), so that work moved between the two leaves
the share as it is."""
import costs


def read(ctx):
    k = ctx["trace"]["kernels"]["edge_spmm_nb"]
    if not k["count"]:
        return None
    per_call = (k["total_s"] + k["feed_s"]) / k["count"]
    return 100.0 * costs.roofline(ctx["spmm_cost"], per_call,
                                  ctx["device_kind"])["share"]
