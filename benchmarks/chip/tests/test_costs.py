import pytest

import costs


def test_edge_spmm_cost_counts_the_algorithm():
    # 5 undirected edges = 10 half-edges, n = 4 nodes, k = 3 columns
    c = costs.edge_spmm_cost(10, 4, 3)
    assert c["flops"] == 2 * 10 * 3
    # index pair + weight per half-edge, panel in, degrees, panel out
    assert c["bytes"] == 10 * 12 + 4 * 3 * 4 + 4 * 4 + 4 * 3 * 4


def test_dblp_matvec_is_bytes_bound_near_51us():
    c = costs.edge_spmm_cost(2_127_408, 317_080, 6)
    assert abs(c["bytes"] - 42.0e6) < 0.5e6
    r = costs.roofline(c, 51.3e-6, "TPU v5 lite")
    assert r["bound"] == "bytes"
    assert 0.99 < r["share"] < 1.01


def test_roofline_share_against_measured_time():
    c = {"flops": 197e12, "bytes": 1.0}  # one second of peak compute
    r = costs.roofline(c, 4.0, "TPU v5 lite")
    assert r["bound"] == "flops" and r["share"] == pytest.approx(0.25)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v99")
    with pytest.raises(KeyError):
        costs.roofline({"flops": 1.0, "bytes": 1.0}, 1.0, "cpu")
