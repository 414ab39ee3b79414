"""Graph generators used in the paper's experiments (Sec. 5, App. A).

- three_room_mdp: Fig. 1 grid world (3 rooms joined by small doors) whose
  state-transition graph yields proto-value functions (Sec. 5.3).
- clique_graph: k cliques joined by 0..25 random short-circuit edges
  (Sec. 5.4).
- sbm_graph: stochastic block model (referenced via Saade et al. / SBM
  discussion in App. B) — used for property tests.

Generators are host-side numpy (graph construction is data prep, not a
jit region) and return EdgeList plus ground-truth cluster labels where
defined.
"""
from __future__ import annotations

import numpy as np

from repro.core.laplacian import EdgeList, make_edge_list


def three_room_mdp(s: int = 2, h: int = 10):
    """3-room grid world, 10s+1 cells tall, 30s+1 cells wide (paper Fig. 1).

    Two interior walls split the width into 3 equal rooms; each wall has a
    door of height ceil((10s+1)/h) centered vertically.  Nodes are cells,
    undirected edges are the 4-neighbour transitions.

    Returns (EdgeList, labels) with labels = room index per cell.
    """
    height = 10 * s + 1
    width = 30 * s + 1
    room_w = width // 3  # wall sits between columns room_w-1 / room_w (x2)
    door_h = max(1, (height + h - 1) // h)
    door_lo = (height - door_h) // 2
    door_hi = door_lo + door_h  # exclusive

    def node(r, c):
        return r * width + c

    edges = []
    for r in range(height):
        for c in range(width):
            # vertical edge down
            if r + 1 < height:
                edges.append((node(r, c), node(r + 1, c)))
            # horizontal edge right, unless crossing a wall outside the door
            if c + 1 < width:
                crossing_wall = (c + 1) % room_w == 0 and (c + 1) // room_w in (1, 2) \
                    and (c + 1) < width
                if crossing_wall and not (door_lo <= r < door_hi):
                    continue
                edges.append((node(r, c), node(r, c + 1)))
    labels = np.zeros((height * width,), dtype=np.int32)
    for r in range(height):
        for c in range(width):
            labels[node(r, c)] = min(c // room_w, 2)
    g = make_edge_list(np.asarray(edges, dtype=np.int32), height * width)
    return g, labels


def clique_graph(
    num_nodes: int,
    num_cliques: int,
    seed: int = 0,
    max_short_circuit: int = 25,
):
    """k cliques of ~n/k nodes + 0..25 random cross edges per clique pair.

    Paper Sec. 5.4.  Returns (EdgeList, labels).
    """
    rng = np.random.default_rng(seed)
    sizes = np.full((num_cliques,), num_nodes // num_cliques, dtype=np.int64)
    sizes[: num_nodes % num_cliques] += 1
    starts = np.concatenate([[0], np.cumsum(sizes)])
    edges = []
    labels = np.zeros((num_nodes,), dtype=np.int32)
    for k in range(num_cliques):
        lo, hi = int(starts[k]), int(starts[k + 1])
        labels[lo:hi] = k
        members = np.arange(lo, hi)
        iu = np.triu_indices(len(members), k=1)
        edges.append(np.stack([members[iu[0]], members[iu[1]]], axis=1))
    # short circuits between every pair of cliques
    seen = set()
    cross = []
    for a in range(num_cliques):
        for b in range(a + 1, num_cliques):
            m = int(rng.integers(0, max_short_circuit + 1))
            for _ in range(m):
                i = int(rng.integers(starts[a], starts[a + 1]))
                j = int(rng.integers(starts[b], starts[b + 1]))
                if (i, j) not in seen:
                    seen.add((i, j))
                    cross.append((i, j))
    if cross:
        edges.append(np.asarray(cross, dtype=np.int64))
    all_edges = np.concatenate(edges, axis=0).astype(np.int32)
    g = make_edge_list(all_edges, num_nodes)
    return g, labels


def sbm_graph(
    num_nodes: int,
    num_blocks: int,
    p_in: float = 0.5,
    p_out: float = 0.01,
    seed: int = 0,
):
    """Stochastic block model (Holland et al. 1983).  Returns (EdgeList, labels)."""
    edges, labels = sbm_edges(num_nodes, num_blocks, p_in, p_out, seed)
    return make_edge_list(edges, num_nodes), labels


def sbm_edges(
    num_nodes: int,
    num_blocks: int,
    p_in: float = 0.5,
    p_out: float = 0.01,
    seed: int = 0,
):
    """:func:`sbm_graph` as host arrays: ((E, 2) int32 edges, labels).
    Pure numpy, so a process that must stay off the device can build
    the graph."""
    rng = np.random.default_rng(seed)
    labels = np.sort(rng.integers(0, num_blocks, size=num_nodes)).astype(np.int32)
    iu = np.triu_indices(num_nodes, k=1)
    same = labels[iu[0]] == labels[iu[1]]
    p = np.where(same, p_in, p_out)
    mask = rng.random(len(p)) < p
    edges = np.stack([iu[0][mask], iu[1][mask]], axis=1).astype(np.int32)
    # ensure no isolated nodes (attach to a random same-block partner)
    present = np.zeros(num_nodes, bool)
    present[edges.ravel()] = True
    extra = []
    for v in np.nonzero(~present)[0]:
        u = (v + 1) % num_nodes
        extra.append((min(u, v), max(u, v)))
    if extra:
        edges = np.concatenate([edges, np.asarray(extra, np.int32)], axis=0)
    return edges, labels


def sparse_sbm_graph(
    num_nodes: int,
    num_blocks: int,
    avg_degree_in: float = 8.0,
    avg_degree_out: float = 0.5,
    seed: int = 0,
    min_degree: int = 1,
):
    """Memory-light SBM for large n (>= 10k nodes, streaming benchmarks).

    `sbm_graph` materializes all O(n^2) node pairs; this samples a
    binomial edge COUNT per block pair and then draws endpoints, so cost
    is O(E).  Expected within-block degree is `avg_degree_in`, expected
    cross-block degree `avg_degree_out`.  Returns (EdgeList, labels).

    Isolated nodes chain to their block neighbour.  ``min_degree`` > 1
    then tops every node below it up with edges to random same-block
    partners.  At a mean degree near 6 and a few hundred thousand
    nodes, Poisson degrees leave small components and dangling paths
    whose localized Laplacian eigenvalues fall below the community
    ones; a floor of 3 removes them.
    """
    rng = np.random.default_rng(seed)
    sizes = np.full((num_blocks,), num_nodes // num_blocks, dtype=np.int64)
    sizes[: num_nodes % num_blocks] += 1
    starts = np.concatenate([[0], np.cumsum(sizes)])
    labels = np.repeat(np.arange(num_blocks), sizes).astype(np.int32)
    chunks = []
    for a in range(num_blocks):
        na = int(sizes[a])
        # within-block: n_a * deg_in / 2 edges in expectation
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
    edges = _canonical_edges(edges)
    # ensure no isolated nodes (chain to the next node in the same block;
    # a size-1 block chains to its global neighbour instead)
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
        edges = _canonical_edges(np.concatenate(
            [edges, np.stack([v, u], axis=1)], axis=0))
    return make_edge_list(edges.astype(np.int32), num_nodes), labels


def _canonical_edges(edges: np.ndarray) -> np.ndarray:
    """Drop self loops, orient (lo, hi) and deduplicate."""
    edges = edges[edges[:, 0] != edges[:, 1]]
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    return np.unique(np.stack([lo, hi], axis=1), axis=0)


def power_law_graph(
    num_nodes: int,
    avg_degree: float = 8.0,
    alpha: float = 2.5,
    seed: int = 0,
    dedup: bool = True,
):
    """Chung–Lu style power-law graph: endpoint probabilities follow a
    Pareto(alpha - 1) weight per node, so degrees are power-law with
    exponent ~alpha — the skewed-degree regime the chunked node-blocking
    layout exists for (hub blocks concentrate half-edges).

    Cost is O(E log n) (inverse-CDF endpoint draws), so it scales to the
    million-node / 5e7-edge acceptance row.  ``dedup=False`` skips the
    O(E) unique pass and keeps duplicate draws as parallel unit-weight
    edges (a weighted multigraph — every consumer in this repo sums
    parallel weights, so the spectrum just sees heavier hub edges);
    the default dedups for exact small-graph tests.  Self loops are
    dropped.  Returns an EdgeList (no planted labels — this family has
    none).
    """
    rng = np.random.default_rng(seed)
    w = rng.pareto(max(alpha - 1.0, 1e-3), size=num_nodes) + 1.0
    p = w / w.sum()
    m = max(int(num_nodes * avg_degree / 2), 1)
    src = rng.choice(num_nodes, size=m, p=p)
    dst = rng.choice(num_nodes, size=m, p=p)
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep]).astype(np.int64)
    hi = np.maximum(src[keep], dst[keep]).astype(np.int64)
    edges = np.stack([lo, hi], axis=1)
    if dedup:
        edges = np.unique(edges, axis=0)
    if len(edges) == 0:  # degenerate tiny draw: keep the graph non-empty
        edges = np.asarray([[0, min(1, num_nodes - 1)]], np.int64)
    return make_edge_list(edges, num_nodes)


def ring_of_cliques(num_cliques: int, clique_size: int):
    """Deterministic well-clustered graph for exact tests."""
    n = num_cliques * clique_size
    edges = []
    labels = np.zeros((n,), dtype=np.int32)
    for k in range(num_cliques):
        lo = k * clique_size
        labels[lo: lo + clique_size] = k
        for i in range(clique_size):
            for j in range(i + 1, clique_size):
                edges.append((lo + i, lo + j))
        nxt = ((k + 1) % num_cliques) * clique_size
        edges.append((min(lo, nxt), max(lo, nxt)))
    return make_edge_list(np.asarray(edges, np.int32), n), labels
