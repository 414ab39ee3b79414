"""The input generators: seeded, and the same solve on every seed."""
import numpy as np

import generate

BIG_SEED = 2**31 + 12345


def test_sparse_sbm_matches_the_programs_generator():
    from repro.core import graphs

    for seed in (0, 3):
        g, lab = graphs.sparse_sbm_graph(5000, 4, 6.0, 0.6, seed=seed,
                                         min_degree=3)
        edges, labels = generate.sparse_sbm(5000, 4, 6.0, 0.6, seed, 3)
        np.testing.assert_array_equal(edges[:, 0], np.asarray(g.src))
        np.testing.assert_array_equal(edges[:, 1], np.asarray(g.dst))
        np.testing.assert_array_equal(labels, lab)


def test_solve_graph_is_one_graph_sent_in_another_order():
    g = {"num_nodes": 3000, "num_blocks": 4, "avg_degree_in": 6.0,
         "avg_degree_out": 0.6, "min_degree": 3, "graph_seed": 0}
    base, base_lab = generate.sparse_sbm(3000, 4, 6.0, 0.6, 0, 3)
    a, la, sa = generate.solve_graph(g, 6, BIG_SEED)
    b, lb, sb = generate.solve_graph(g, 6, BIG_SEED)
    c, lc, sc = generate.solve_graph(g, 6, BIG_SEED + 1)
    for x, y in ((a, b), (la, lb), (sa, sb)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a, c) and sa.shape == (3000, 6)
    # the nodes keep their names, labels and start rows on every seed
    np.testing.assert_array_equal(la, base_lab)
    np.testing.assert_array_equal(lc, base_lab)
    np.testing.assert_array_equal(sa, sc)
    for e in (a, c):
        # the same edge set, in another order and orientation
        assert len(e) == len(base)
        np.testing.assert_array_equal(generate.canonical_edges(e), base)
    assert (a[:, 0] > a[:, 1]).any() and (a[:, 0] < a[:, 1]).any()
