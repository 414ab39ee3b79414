"""Seeded input generation for the chip benchmark (host numpy only).

Everything a run sends to the program is made here from ``--seed``.
Nothing here imports the program, so a later change to the program
cannot move what the benchmark offers.

* :func:`sparse_sbm` is the memory-light SBM of the large tenant (a
  copy of the algorithm the bring-up smoke used, kept here so the
  yardstick cannot move);
* :func:`solve_graph` draws it, and a start panel, from the
  configuration's fixed seed; the run's seed shuffles the order in
  which its edges are sent, so every seed offers the same solve.
"""
from __future__ import annotations

import numpy as np

# Sub-stream id: each purpose draws from its own stream of the seed.
STREAM_GRAPH = 0


def rng_for(seed: int, stream: int, *more: int) -> np.random.Generator:
    """A generator for one purpose of one run; any whole ``seed`` >= 0."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed), int(stream), *map(int, more)]))


def canonical_edges(edges: np.ndarray) -> np.ndarray:
    """Drop self loops, orient (lo, hi) and deduplicate."""
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    edges = edges[edges[:, 0] != edges[:, 1]]
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    return np.unique(np.stack([lo, hi], axis=1), axis=0)


def sparse_sbm(num_nodes: int, num_blocks: int, avg_degree_in: float,
               avg_degree_out: float, seed, min_degree: int = 1):
    """SBM in O(E): a binomial edge count per block pair, then uniform
    endpoints.  Isolated nodes chain to their block neighbour, and
    nodes below ``min_degree`` are topped up with same-block partners.
    Returns ((E, 2) int64 canonical edges, (n,) int32 planted labels).
    ``seed`` is passed to ``np.random.default_rng`` as given."""
    rng = np.random.default_rng(seed)
    sizes = np.full((num_blocks,), num_nodes // num_blocks, dtype=np.int64)
    sizes[: num_nodes % num_blocks] += 1
    starts = np.concatenate([[0], np.cumsum(sizes)])
    labels = np.repeat(np.arange(num_blocks), sizes).astype(np.int32)
    chunks = []
    for a in range(num_blocks):
        na = int(sizes[a])
        pairs_in = na * (na - 1) // 2
        p_in = min(1.0, avg_degree_in / max(na - 1, 1))
        m = rng.binomial(pairs_in, p_in)
        if m:
            i = rng.integers(starts[a], starts[a + 1], size=m)
            j = rng.integers(starts[a], starts[a + 1], size=m)
            chunks.append(np.stack([i, j], axis=1))
        for b in range(a + 1, num_blocks):
            nb = int(sizes[b])
            p_out = min(1.0, avg_degree_out / max(num_nodes - na, 1))
            m = rng.binomial(na * nb, p_out)
            if m:
                i = rng.integers(starts[a], starts[a + 1], size=m)
                j = rng.integers(starts[b], starts[b + 1], size=m)
                chunks.append(np.stack([i, j], axis=1))
    edges = (np.concatenate(chunks, axis=0) if chunks
             else np.zeros((0, 2), np.int64))
    edges = canonical_edges(edges)
    present = np.zeros(num_nodes, bool)
    present[edges.ravel()] = True
    extra = []
    for v in np.nonzero(~present)[0]:
        blk = labels[v]
        if int(sizes[blk]) > 1:
            u = int(starts[blk]) + (v - int(starts[blk]) + 1) % int(sizes[blk])
        else:
            u = (v + 1) % num_nodes
        extra.append((min(u, v), max(u, v)))
    if extra:
        edges = np.concatenate([edges, np.asarray(extra, np.int64)], axis=0)
    while min_degree > 1:
        deg = np.bincount(edges.ravel(), minlength=num_nodes)
        short = np.nonzero((deg < min_degree)
                           & (sizes[labels] > min_degree))[0]
        if not len(short):
            break
        v = np.repeat(short, min_degree - deg[short])
        blk = labels[v]
        u = starts[blk] + rng.integers(0, sizes[blk])
        edges = canonical_edges(np.concatenate(
            [edges, np.stack([v, u], axis=1)], axis=0))
    return edges.astype(np.int64), labels


def solve_graph(g: dict, k: int, seed: int):
    """The solve cell's tenant and its start panel.

    One graph, :func:`sparse_sbm` from the configuration's fixed
    ``graph_seed``, with a Gaussian (n, k) start panel drawn once beside
    it: a cold start, like the service's own random one.  The run's
    ``seed`` shuffles the order of the edge rows and the orientation of
    each pair.  The nodes keep their names: the service's admission probe
    draws its random vectors over node ids, so renamed nodes would give
    each seed another probe, another step size and another amount of
    work.  Returns ((E, 2) int64 edges as sent, planted labels, start
    panel float32)."""
    n = int(g["num_nodes"])
    edges, labels = sparse_sbm(
        n, int(g["num_blocks"]), float(g["avg_degree_in"]),
        float(g["avg_degree_out"]), int(g["graph_seed"]),
        int(g["min_degree"]))
    start = rng_for(int(g["graph_seed"]), STREAM_GRAPH).standard_normal(
        (n, k), dtype=np.float32)
    rng = rng_for(seed, STREAM_GRAPH)
    sent = edges[rng.permutation(len(edges))]
    flip = rng.random(len(sent)) < 0.5
    sent[flip] = sent[flip][:, ::-1]
    return sent, labels, start
