"""Operation and byte counts of the kernels, and the chips' peaks.

Counts are of the ALGORITHM's work, not of one implementation's: a
rewrite of a kernel reads against the same roofline.

Peaks are keyed by ``device_kind`` as JAX reports it; a kind missing
from the table is an error, never a default.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to benchmarks/chip/costs.py with its source") from None


def edge_spmm_cost(half_edges: int, num_nodes: int, k: int,
                   value_bytes: int = 4, index_bytes: int = 4) -> dict:
    """One Y = a (L V) + b V over a graph of ``half_edges`` real
    half-edges (two per undirected edge) and an (n, k) panel.

    Bytes: each half-edge's index pair and weight read once; the panel
    read once, the degrees read once, the output written once.  FLOPs:
    one multiply-add per half-edge and column (2 H k).  Padded chunk
    slots, gathered rows and one-hot products are the implementation's
    and are not counted."""
    read_edges = half_edges * (2 * index_bytes + value_bytes)
    panel = num_nodes * k * value_bytes
    degrees = num_nodes * value_bytes
    return {"flops": 2.0 * half_edges * k,
            "bytes": float(read_edges + panel + degrees + panel)}


def roofline(cost: dict, seconds_per_call: float, device_kind: str) -> dict:
    """Least time the chip could take for ``cost`` (the larger of its
    compute and memory bounds), as a share of the measured time."""
    pk = peaks(device_kind)
    t_flops = cost["flops"] / pk["flops_per_s"]
    t_bytes = cost["bytes"] / pk["hbm_bytes_per_s"]
    bound = max(t_flops, t_bytes)
    return {"share": bound / seconds_per_call, "min_s": bound,
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
