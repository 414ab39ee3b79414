"""jit-stable mutable edge store with padded capacity classes.

The streaming service's ground truth for each graph.  Shapes never depend
on the live edge count: the edge buffer is padded to a CAPACITY CLASS
(power of two), and mutations are fixed-size batched upserts, so every
graph in a class shares one compiled program (store update, matvec,
solver tick).

Slot convention: ``weight == 0``  <=>  the slot is free/inert.  A free
slot contributes nothing to any edge-wise computation, which is exactly
the contract of :func:`repro.core.laplacian.pad_edge_list` — so
``as_edge_list(store)`` feeds every existing operator (dense L, matvec,
series, sharded matvec) unchanged.

Degrees are cached and recomputed LAZILY: mutations only set a dirty
flag; :func:`refresh_degrees` recomputes under ``lax.cond`` the next time
degrees are actually needed (spectral-radius bound, dilation scale).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.laplacian import EdgeList

# Edge-buffer capacity ladder (powers of two).  Few classes => few
# compiled programs; headroom on admission makes growth rare.  The top
# rungs (2^25, 2^26 ~ 67M edges) exist for the million-node tier: a
# streamed n=1M, E~50M power-law graph admits without overflowing.
CAPACITY_CLASSES = tuple(2 ** p for p in range(8, 27))


def capacity_class(num_edges: int, headroom: float = 1.5) -> int:
    """Smallest ladder capacity >= num_edges * headroom."""
    want = max(int(np.ceil(num_edges * headroom)), 1)
    for c in CAPACITY_CLASSES:
        if c >= want:
            return c
    raise ValueError(f"{num_edges} edges exceeds the capacity ladder")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class GraphStore:
    """Fixed-capacity mutable graph; a pytree with static num_nodes."""

    src: jax.Array  # (cap,) int32, src < dst for live slots
    dst: jax.Array  # (cap,) int32
    weight: jax.Array  # (cap,) float32; 0 => slot free
    deg: jax.Array  # (num_nodes,) float32 cached weighted degrees
    deg_dirty: jax.Array  # () bool — True => deg is stale
    num_nodes: int  # static (may itself be a padded node capacity)

    @property
    def capacity(self) -> int:
        return self.src.shape[0]

    def tree_flatten(self):
        return (
            (self.src, self.dst, self.weight, self.deg, self.deg_dirty),
            self.num_nodes,
        )

    @classmethod
    def tree_unflatten(cls, num_nodes, children):
        return cls(*children, num_nodes=num_nodes)


class EdgeBatch(NamedTuple):
    """A fixed-size batch of edge mutations (canonicalized on build).

    Semantics per entry under mode="set": upsert the edge (src, dst) to
    `weight`; weight 0 deletes.  Under mode="add": add `weight` to the
    current weight (inserting if absent; reaching exactly 0 deletes).
    Entries must have UNIQUE canonical (src, dst) pairs — use
    :func:`coalesce_batch` for raw update streams.  Padding entries
    (src == dst == 0, weight == 0) are no-ops and must sit at the END of
    the batch so real inserts claim free slots first.
    """

    src: jax.Array  # (B,) int32
    dst: jax.Array  # (B,) int32
    weight: jax.Array  # (B,) float32


def make_edge_batch(edges, weights, pad_to: int | None = None) -> EdgeBatch:
    """Canonicalize + zero-pad an update batch to a fixed size.

    Self-loop entries (src == dst) are dropped: a self-loop contributes
    nothing to a Laplacian, and a live (0, 0) slot would collide with
    the padding sentinel (and double-count in cached degrees).
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    weights = np.asarray(weights, dtype=np.float32).reshape(-1)
    proper = edges[:, 0] != edges[:, 1]
    edges, weights = edges[proper], weights[proper]
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    b = len(weights)
    size = b if pad_to is None else pad_to
    if size < b:
        raise ValueError(f"pad_to {pad_to} < batch size {b}")
    src = np.zeros((size,), np.int32)
    dst = np.zeros((size,), np.int32)
    w = np.zeros((size,), np.float32)
    src[:b], dst[:b], w[:b] = lo, hi, weights
    return EdgeBatch(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w))


def coalesce_batch(edges, weights, mode: str = "set",
                   pad_to: int | None = None) -> EdgeBatch:
    """Collapse duplicate pairs in a raw update stream (host-side).

    mode="set": last write wins;  mode="add": deltas sum.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    weights = np.asarray(weights, dtype=np.float32).reshape(-1)
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    out: dict[tuple[int, int], float] = {}
    for s, d, w in zip(lo, hi, weights):
        if s == d:
            continue  # self-loops are no-ops on a Laplacian
        key = (int(s), int(d))
        if mode == "add":
            out[key] = out.get(key, 0.0) + float(w)
        else:
            out[key] = float(w)
    pairs = np.asarray(list(out.keys()), np.int64).reshape(-1, 2)
    vals = np.asarray(list(out.values()), np.float32)
    return make_edge_batch(pairs, vals, pad_to=pad_to)


def from_edge_list(g: EdgeList, capacity: int | None = None,
                   num_nodes: int | None = None) -> GraphStore:
    """Admit a graph: pad its edges into a capacity-class buffer.

    `num_nodes` may exceed g.num_nodes to place the graph in a padded
    NODE capacity class (extra nodes are isolated and inert as long as
    eigen-panels keep zero rows there — see stream.service).
    """
    n = g.num_nodes if num_nodes is None else num_nodes
    if n < g.num_nodes:
        raise ValueError("num_nodes below the graph's node count")
    cap = capacity_class(g.num_edges) if capacity is None else capacity
    if cap < g.num_edges:
        raise ValueError(f"capacity {cap} < num_edges {g.num_edges}")
    pad = cap - g.num_edges
    src = jnp.concatenate([g.src, jnp.zeros((pad,), jnp.int32)])
    dst = jnp.concatenate([g.dst, jnp.zeros((pad,), jnp.int32)])
    w = jnp.concatenate([g.weight, jnp.zeros((pad,), jnp.float32)])
    deg = jnp.zeros((n,), jnp.float32).at[src].add(w).at[dst].add(w)
    return GraphStore(src=src, dst=dst, weight=w, deg=deg,
                      deg_dirty=jnp.zeros((), bool), num_nodes=n)


def as_edge_list(store: GraphStore) -> EdgeList:
    """Zero-copy padded EdgeList view; free slots are inert."""
    return EdgeList(src=store.src, dst=store.dst, weight=store.weight,
                    num_nodes=store.num_nodes)


def num_edges(store: GraphStore) -> jax.Array:
    """Live edge count (traced scalar)."""
    return jnp.sum(store.weight != 0.0)


def grow(store: GraphStore, capacity: int | None = None) -> GraphStore:
    """Host-side move to the next capacity class (recompiles downstream)."""
    old = store.capacity
    if capacity is None:
        bigger = [c for c in CAPACITY_CLASSES if c > old]
        if not bigger:
            raise ValueError("already at the top capacity class")
        capacity = bigger[0]
    pad = capacity - old
    if pad < 0:
        raise ValueError(f"cannot shrink {old} -> {capacity}")
    return dataclasses.replace(
        store,
        src=jnp.concatenate([store.src, jnp.zeros((pad,), jnp.int32)]),
        dst=jnp.concatenate([store.dst, jnp.zeros((pad,), jnp.int32)]),
        weight=jnp.concatenate([store.weight, jnp.zeros((pad,), jnp.float32)]),
    )


class BatchStats(NamedTuple):
    matched: jax.Array  # () int32 — entries that updated an existing edge
    inserted: jax.Array  # () int32 — entries that claimed a free slot
    dropped: jax.Array  # () int32 — inserts lost to a full buffer


@jax.jit
def _apply_set(store: GraphStore, batch: EdgeBatch):
    return _apply(store, batch, False)


@jax.jit
def _apply_add(store: GraphStore, batch: EdgeBatch):
    return _apply(store, batch, True)


def _apply(store: GraphStore, batch: EdgeBatch, add: bool):
    cap = store.capacity
    b = batch.src.shape[0]
    occ = store.weight != 0.0
    # (B, cap) match of live slots; O(B * cap) compare — branch-free and
    # batched, the jit-stable trade the store makes for hash tables.
    match = (
        (store.src[None, :] == batch.src[:, None])
        & (store.dst[None, :] == batch.dst[:, None])
        & occ[None, :]
    )
    found = jnp.any(match, axis=1)
    match_idx = jnp.argmax(match, axis=1)
    # No-op entries (padding, or deletes of absent edges) write nothing:
    # they must neither consume a free slot nor count as drops, or a
    # padded reweight batch near capacity would spuriously overflow.
    noop = (batch.weight == 0.0) & ~found
    needs_slot = ~found & ~noop
    # i-th entry needing a slot gets the i-th free slot; fill=cap when the
    # buffer runs out, and the scatter below then drops that write.
    free_idx = jnp.nonzero(~occ, size=b, fill_value=cap)[0]
    new_rank = jnp.cumsum(needs_slot) - 1
    slot = jnp.where(
        found, match_idx,
        jnp.where(needs_slot, free_idx[jnp.clip(new_rank, 0, b - 1)], cap))
    in_range = slot < cap
    old_w = jnp.where(found, store.weight[jnp.clip(slot, 0, cap - 1)], 0.0)
    new_w = old_w + batch.weight if add else batch.weight
    applied_w = jnp.where(in_range, new_w, 0.0)
    dw = applied_w - jnp.where(in_range, old_w, 0.0)  # realized weight deltas
    src = store.src.at[slot].set(batch.src, mode="drop")
    dst = store.dst.at[slot].set(batch.dst, mode="drop")
    weight = store.weight.at[slot].set(new_w, mode="drop")
    stats = BatchStats(
        matched=jnp.sum(found.astype(jnp.int32)),
        inserted=jnp.sum((needs_slot & in_range).astype(jnp.int32)),
        dropped=jnp.sum((needs_slot & ~in_range).astype(jnp.int32)),
    )
    new_store = dataclasses.replace(
        store, src=src, dst=dst, weight=weight,
        deg_dirty=jnp.ones((), bool))
    return new_store, dw, stats


def apply_edge_batch(store: GraphStore, batch: EdgeBatch, mode: str = "set"):
    """Apply a batched upsert; returns (store', dw, stats).

    `dw` is the REALIZED per-entry weight delta (0 for dropped/no-op
    entries) — exactly the ΔL description the incremental eigen-update
    path consumes (stream.updates).  Jitted once per (capacity, batch
    size, mode).
    """
    if mode == "set":
        return _apply_set(store, batch)
    if mode == "add":
        return _apply_add(store, batch)
    raise ValueError(f"unknown mode {mode!r}")


@jax.jit
def refresh_degrees(store: GraphStore) -> GraphStore:
    """Lazy degree recomputation: only pays the O(capacity) scatter when
    the cache is actually stale."""

    def recompute(s):
        return (
            jnp.zeros_like(s.deg).at[s.src].add(s.weight).at[s.dst].add(s.weight)
        )

    deg = jax.lax.cond(store.deg_dirty, recompute, lambda s: s.deg, store)
    return dataclasses.replace(store, deg=deg, deg_dirty=jnp.zeros((), bool))


def spectral_radius_upper_bound(store: GraphStore) -> tuple[GraphStore, jax.Array]:
    """(refreshed store, 2 * max weighted degree) — the Sec. 5.4 bound."""
    store = refresh_degrees(store)
    return store, 2.0 * jnp.max(store.deg)
