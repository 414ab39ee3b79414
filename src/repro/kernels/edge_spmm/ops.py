"""jit'd wrappers for the edge_spmm kernels.

``edge_spmm`` pads edges to block multiples (zero weight => no
contribution) and lane-aligns the panel; it holds the full (n, k) panel
plus a (block_e, n) one-hot in VMEM, so the backend layer only selects
it up to ``repro.core.backend.ONE_HOT_NODE_LIMIT`` (4096) nodes.

``build_node_blocking`` + ``edge_spmm_blocked`` are the scalable path:
edges are expanded host-side into directed half-edges (u <- o, w) and
bucketed by the node-block of the destination u into a CSR-style
VARIABLE-chunks-per-block layout: each block owns ceil(bucket / BE)
chunks (min 1), a flat chunk->block index map steers the kernel's
scalar-prefetched BlockSpecs, and only the TOTAL chunk count is
snapped (``snap_chunk_count``: powers of two for small layouts, a
finer grid for large ones) so graphs of similar size share one compiled
program (the streaming store's capacity-class economics) without paying
the old uniform blocks x max-chunks padding on skewed degree
distributions.
The kernel then works on (block_n, k) panel slices only — see
kernel.py.  ``build_model_sharded_blocking`` splits the same layout by
DESTINATION node range so each mesh shard owns its panel rows' output
outright (the panel-sharding convention of ``core.distributed``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.edge_spmm import kernel


def _ab(alpha, beta) -> jax.Array:
    a = jnp.asarray(alpha, jnp.float32).reshape(())
    b = jnp.asarray(beta, jnp.float32).reshape(())
    return jnp.stack([a, b])


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def edge_spmm(src: jax.Array, dst: jax.Array, w: jax.Array, v: jax.Array,
              alpha=1.0, beta=0.0,
              *, block_e: int = 128, interpret: bool = False) -> jax.Array:
    """alpha * (sum_e w_e x_e x_e^T V) + beta * V; default plain matvec.

    Accepts (n,) or (n, k) panels (1-D round-trips through a column).
    """
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    e = src.shape[0]
    n, k = v.shape
    # an edgeless input still needs one (inert) block: a zero-size grid
    # is invalid, and the segment backend returns zeros there
    pad_e = block_e if e == 0 else (-e) % block_e
    if pad_e:
        src = jnp.concatenate([src, jnp.zeros((pad_e,), src.dtype)])
        dst = jnp.concatenate([dst, jnp.ones((pad_e,), dst.dtype)])
        w = jnp.concatenate([w, jnp.zeros((pad_e,), w.dtype)])
    pad_k = (-k) % 128
    pad_n = (-n) % 8  # sublane alignment
    vp = jnp.pad(v.astype(jnp.float32), ((0, pad_n), (0, pad_k)))
    out = kernel.edge_spmm(src, dst, w.astype(jnp.float32), vp,
                           _ab(alpha, beta),
                           block_e=block_e, interpret=interpret)
    out = out[:n, :k]
    return out[:, 0] if squeeze else out


class NodeBlocking(NamedTuple):
    """Node-blocked half-edge layout for ``edge_spmm_blocked``.

    Built host-side ONCE per graph (or per capacity-class admission in
    the streaming graph store) and cached alongside the padded edge
    buffers; every matvec/fused-series-step reuses it.  Arrays are
    device-resident; the ints are static and part of the compile key.

    The chunk layout is CSR-style: block b owns ``ceil(bucket_b / BE)``
    chunks (min 1 so every block is initialized), laid out contiguously
    in block order; ``chunk_block`` maps chunk -> block for the kernel's
    scalar-prefetched BlockSpecs.  Padding chunks (total snapped by
    :func:`snap_chunk_count`) extend the LAST block's run with zero
    weights, so no block's init/epilogue ever re-fires.
    """

    u_local: jax.Array  # (NC*BE,) int32 — dest index local to its block
    other: jax.Array  # (NC*BE,) int32 — global source node per half-edge
    weight: jax.Array  # (NC*BE,) float32 — 0 => padding slot
    chunk_block: jax.Array  # (NC+1,) int32 — block per chunk + tail sentinel
    deg: jax.Array  # (NB*block_n,) float32 — weighted degrees, row-padded
    block_n: int  # nodes per block (static)
    block_e: int  # half-edges per kernel chunk (static)
    num_chunks: int  # NC, TOTAL chunks (static, snap_chunk_count)
    num_nodes: int  # real node count n (static); NB = ceil(n / block_n)

    @property
    def num_blocks(self) -> int:
        return self.deg.shape[0] // self.block_n

    @property
    def padded_nodes(self) -> int:
        return self.deg.shape[0]

    @property
    def padded_half_edges(self) -> int:
        """Half-edge SLOTS the kernel walks (live + padding) — the work
        metric the skew benchmarks compare against the uniform layout."""
        return self.num_chunks * self.block_e


def next_pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1) — shared by the blocking's
    chunk snapping here and the service's occupancy buckets."""
    return 1 << max(int(np.ceil(np.log2(max(int(x), 1)))), 0)


# Total chunk counts below FINE_SNAP_FROM snap to a power of two: the
# many small tenants of a fleet then share a few compiled programs.  A
# layout this large is one big tenant, for which that sharing is worth
# little and pow2 padding costs up to half of every matvec (the kernel's
# grid, the XLA gather feeding it and its lane pad all walk the padding
# chunks), so its count rounds up to a multiple of
# next_pow2(count) // FINE_SNAP_SPLIT instead: less than one such
# granule of padding, at most eight shapes per octave, and headroom
# enough that a small edge batch usually keeps the shape.
FINE_SNAP_FROM = 1024
FINE_SNAP_SPLIT = 16


def snap_chunk_count(nc_raw: int) -> int:
    """The static total chunk count a layout of ``nc_raw`` live chunks
    compiles against — the one rule of all three blocking builders."""
    pow2 = next_pow2(nc_raw)
    if nc_raw < FINE_SNAP_FROM:
        return pow2
    granule = pow2 // FINE_SNAP_SPLIT
    return -(-nc_raw // granule) * granule


def _block_sorted_half_edges(src, dst, weight, block_n: int, nb: int):
    """Live edges -> directed half-edges sorted by destination node-block.

    Returns (u, o, w2, counts): half-edge destination/source/weight in
    deterministic block order plus per-block half-edge counts.  Shared
    by the single-device and per-shard blocking builders so both lay
    half-edges out identically.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    weight = np.asarray(weight, np.float32)
    live = weight != 0.0
    src, dst, weight = src[live], dst[live], weight[live]
    # directed half-edges: destination u, source o
    u = np.concatenate([src, dst])
    o = np.concatenate([dst, src])
    w2 = np.concatenate([weight, weight])
    blk = u // block_n
    order = np.argsort(blk, kind="stable")  # deterministic layout
    counts = np.bincount(blk[order], minlength=nb)
    return u[order], o[order], w2[order], counts


def uniform_chunks_for_counts(counts, block_e: int,
                              snap_chunks: bool = True) -> int:
    """Chunks per block under the LEGACY uniform layout (every block
    pays the worst bucket, pow2-snapped).  Kept as the comparison
    baseline for the skew benchmarks and property tests."""
    c = max(int(np.ceil(counts.max(initial=0) / block_e)), 1)
    return next_pow2(c) if snap_chunks else c


def uniform_padded_half_edges(counts, block_e: int,
                              snap_chunks: bool = True) -> int:
    """Half-edge slots the legacy uniform layout would walk:
    num_blocks * max-chunks * block_e."""
    nb = int(np.asarray(counts).shape[0])
    return nb * uniform_chunks_for_counts(counts, block_e, snap_chunks) \
        * block_e


def _chunk_counts(counts, block_e: int):
    """Per-block chunk counts: ceil(bucket / BE), min 1 so every block
    gets its init/epilogue pass even when it holds no live half-edges."""
    counts = np.asarray(counts, np.int64)
    return np.maximum((counts + block_e - 1) // block_e, 1)


def _fill_chunked(u, o, w2, counts, nb: int, nc: int,
                  block_n: int, block_e: int):
    """Scatter block-sorted half-edges into the CSR chunk layout.

    Returns (u_local, other, weight, chunk_block) with flat (nc*BE,)
    half-edge arrays and the (nc+1,) chunk->block map; unfilled slots
    stay zero-weight (inert) and padding chunks extend the last block's
    run (sentinel tail included).
    """
    cb_counts = _chunk_counts(counts, block_e)
    chunk_off = np.concatenate([[0], np.cumsum(cb_counts)])
    nc_raw = int(chunk_off[-1])
    assert nc >= nc_raw, (nc, nc_raw)
    ul = np.zeros((nc * block_e,), np.int32)
    ot = np.zeros((nc * block_e,), np.int32)
    wt = np.zeros((nc * block_e,), np.float32)
    total = u.shape[0]
    if total:
        offs = np.concatenate([[0], np.cumsum(counts)])
        blk_of = np.repeat(np.arange(nb, dtype=np.int64), counts)
        within = np.arange(total, dtype=np.int64) - offs[blk_of]
        slot = chunk_off[blk_of] * block_e + within
        ul[slot] = (u - blk_of * block_n).astype(np.int32)
        ot[slot] = o.astype(np.int32)
        wt[slot] = w2
    chunk_block = np.full((nc + 1,), nb - 1, np.int32)
    chunk_block[:nc_raw] = np.repeat(
        np.arange(nb, dtype=np.int32), cb_counts)
    return ul, ot, wt, chunk_block


def _weighted_degrees(src, dst, weight, n_pad: int):
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    weight = np.asarray(weight, np.float32)
    live = weight != 0.0
    deg = np.zeros((n_pad,), np.float32)
    np.add.at(deg, src[live], weight[live])
    np.add.at(deg, dst[live], weight[live])
    return deg


def build_node_blocking(src, dst, weight, num_nodes: int,
                        *, block_n: int = 512, block_e: int = 128,
                        snap_chunks: bool = True) -> NodeBlocking:
    """Host-side (numpy) bucketing of edges by destination node-block.

    Each undirected edge (s, d, w) becomes two half-edges — out[s] takes
    +w*(v[s]-v[d]), out[d] takes +w*(v[d]-v[s]) — and L v = deg*v - A v
    lets the kernel carry the v[u] part as a precomputed degree, so a
    half-edge only records (u_local, other, w).  Zero-weight slots
    (capacity padding in the streaming store) are DROPPED here: they are
    inert anyway, and keeping them would pile the entire padding into
    node-block 0.  Blocks own ceil(bucket / block_e) chunks each
    (CSR-style; min 1), and only the TOTAL chunk count is snapped
    (`snap_chunks`, :func:`snap_chunk_count`) so the compile key — and
    therefore the compiled-program count — stays logarithmic in graph
    size while skewed buckets no longer inflate every other block's
    padding.
    """
    nb = max((num_nodes + block_n - 1) // block_n, 1)
    n_pad = nb * block_n
    u, o, w2, counts = _block_sorted_half_edges(src, dst, weight,
                                                block_n, nb)
    nc_raw = int(_chunk_counts(counts, block_e).sum())
    nc = snap_chunk_count(nc_raw) if snap_chunks else nc_raw
    ul, ot, wt, cb = _fill_chunked(u, o, w2, counts, nb, nc,
                                   block_n, block_e)
    deg = _weighted_degrees(src, dst, weight, n_pad)
    return NodeBlocking(
        u_local=jnp.asarray(ul),
        other=jnp.asarray(ot),
        weight=jnp.asarray(wt),
        chunk_block=jnp.asarray(cb),
        deg=jnp.asarray(deg),
        block_n=block_n,
        block_e=block_e,
        num_chunks=nc,
        num_nodes=int(num_nodes),
    )


class ShardedNodeBlocking(NamedTuple):
    """Per-shard node-blocked half-edge layouts for mesh-parallel matvecs.

    The edge buffer is split into ``num_shards`` contiguous slices (the
    :func:`repro.core.distributed.pad_edges_for_mesh` contract) and each
    slice is bucketed INDEPENDENTLY by destination node-block, exactly
    like :func:`build_node_blocking` does for the whole buffer.  All
    shards share ONE static layout — the chunk count is snapped from
    the worst shard's — so the stacked arrays drop straight into a
    ``shard_map`` with the shard axis partitioned over the mesh's edge
    axes, and every shard compiles against identical shapes.

    The matvec decomposes per shard as ``L_s v = deg_s * v - A_s v``
    with ``deg_s`` the weighted degrees of THAT SHARD's edges only, so
    the one psum of the (n, k) panel reconstructs
    ``sum_s L_s v = L v`` exactly (no double-counted diagonal).  A shard
    whose slice holds zero live edges (all capacity padding) gets an
    all-zero layout in the same shapes: its kernel output is exactly
    zero and the psum is unaffected.
    """

    u_local: jax.Array  # (S, NC*BE) int32 — dest index local to block
    other: jax.Array  # (S, NC*BE) int32 — global source node
    weight: jax.Array  # (S, NC*BE) float32 — 0 => padding slot
    chunk_block: jax.Array  # (S, NC+1) int32 — per-shard chunk->block map
    deg: jax.Array  # (S, NB*block_n) float32 — PER-SHARD weighted degrees
    block_n: int  # static
    block_e: int  # static
    num_chunks: int  # NC, TOTAL chunks, shared across shards (static)
    num_nodes: int  # real node count n (static)
    num_shards: int  # S (static)

    @property
    def num_blocks(self) -> int:
        return self.deg.shape[1] // self.block_n

    @property
    def padded_nodes(self) -> int:
        return self.deg.shape[1]

    @property
    def padded_half_edges(self) -> int:
        """Total half-edge slots across shards."""
        return self.num_shards * self.num_chunks * self.block_e

    def shard(self, s: int) -> NodeBlocking:
        """Single-shard view — what one mesh device computes with."""
        return NodeBlocking(
            u_local=self.u_local[s], other=self.other[s],
            weight=self.weight[s], chunk_block=self.chunk_block[s],
            deg=self.deg[s],
            block_n=self.block_n, block_e=self.block_e,
            num_chunks=self.num_chunks,
            num_nodes=self.num_nodes)

    @property
    def statics(self) -> dict:
        """The compile-key statics, as kwargs for
        :func:`shard_local_blocking` (and tick-program builders)."""
        return dict(block_n=self.block_n, block_e=self.block_e,
                    num_chunks=self.num_chunks,
                    num_nodes=self.num_nodes)


def shard_local_blocking(u_local, other, weight, chunk_block, deg,
                         *, block_n: int, block_e: int, num_chunks: int,
                         num_nodes: int) -> NodeBlocking:
    """One device's NodeBlocking from shard_map-LOCAL slices of a
    :class:`ShardedNodeBlocking`'s stacked arrays (the leading shard
    axis is partitioned down to size 1 inside the shard_map body).  The
    single place the slice-and-rewrap wiring lives, so every shard_map
    call site stays in sync when the layout grows fields.
    """
    return NodeBlocking(
        u_local=u_local[0], other=other[0], weight=weight[0],
        chunk_block=chunk_block[0], deg=deg[0],
        block_n=block_n, block_e=block_e,
        num_chunks=num_chunks, num_nodes=num_nodes)


def build_sharded_node_blocking(src, dst, weight, num_nodes: int,
                                num_shards: int,
                                *, block_n: int = 512, block_e: int = 128,
                                snap_chunks: bool = True
                                ) -> ShardedNodeBlocking:
    """Host-side per-shard node blockings of a mesh-padded edge buffer.

    ``len(src)`` must divide evenly by ``num_shards`` (pad the buffer
    with :func:`repro.core.distributed.pad_edges_for_mesh` first); shard
    ``s`` owns the ``s``-th contiguous slice, matching how a
    ``P(edge_axes)`` sharding splits the same buffer on the mesh.  The
    chunk count is resolved ONCE across shards (the worst shard's,
    snapped), so an all-padding shard still materializes the shared
    layout — all zero weights and zero degrees — instead of a
    shape-mismatched empty one.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    weight = np.asarray(weight, np.float32)
    e = src.shape[0]
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if e % num_shards != 0:
        raise ValueError(
            f"edge buffer ({e}) does not divide into {num_shards} shards;"
            " pad with distributed.pad_edges_for_mesh first")
    per = e // num_shards
    nb = max((num_nodes + block_n - 1) // block_n, 1)
    n_pad = nb * block_n
    shards = [
        _block_sorted_half_edges(
            src[s * per:(s + 1) * per], dst[s * per:(s + 1) * per],
            weight[s * per:(s + 1) * per], block_n, nb)
        for s in range(num_shards)
    ]
    # ONE total chunk count for every shard: shard_map needs identical
    # static shapes per device, and snapping to the worst shard keeps
    # the compile key stable under admission-time edge balance wobble.
    nc_raw = max(int(_chunk_counts(counts, block_e).sum())
                 for _, _, _, counts in shards)
    nc = snap_chunk_count(nc_raw) if snap_chunks else nc_raw
    ul = np.zeros((num_shards, nc * block_e), np.int32)
    ot = np.zeros((num_shards, nc * block_e), np.int32)
    wt = np.zeros((num_shards, nc * block_e), np.float32)
    cb = np.zeros((num_shards, nc + 1), np.int32)
    deg = np.zeros((num_shards, n_pad), np.float32)
    for s, (u, o, w2, counts) in enumerate(shards):
        ul[s], ot[s], wt[s], cb[s] = _fill_chunked(
            u, o, w2, counts, nb, nc, block_n, block_e)
        deg[s] = _weighted_degrees(
            src[s * per:(s + 1) * per], dst[s * per:(s + 1) * per],
            weight[s * per:(s + 1) * per], n_pad)
    return ShardedNodeBlocking(
        u_local=jnp.asarray(ul),
        other=jnp.asarray(ot),
        weight=jnp.asarray(wt),
        chunk_block=jnp.asarray(cb),
        deg=jnp.asarray(deg),
        block_n=block_n,
        block_e=block_e,
        num_chunks=nc,
        num_nodes=int(num_nodes),
        num_shards=int(num_shards),
    )


class ModelShardedBlocking(NamedTuple):
    """DESTINATION-aligned per-shard chunk layouts for panel sharding.

    Where :class:`ShardedNodeBlocking` splits the EDGE BUFFER (each
    shard sees every node, outputs partial sums, and a psum adds them),
    this splits the NODE RANGE: shard ``s`` owns panel rows
    ``[s * R, (s + 1) * R)`` and receives ALL half-edges destined to
    those rows.  Its local matvec output rows are therefore FINAL — no
    cross-shard summation — which is what lets a solver step (a) fuse
    the dilation AXPY back into the kernel epilogue per shard, (b)
    compute its mu-EG gram contribution on local rows only, and (c)
    ship rows + gram in ONE fused collective (see
    ``core.program.build_tick_model_sharded``).  Skew is absorbed by
    the CSR chunk layout: a shard owning hub nodes simply has more live
    chunks, and the shared snapped total keeps shapes identical
    across shards (a hub shard pads less, a sparse shard pads more).

    ``u_local``/``chunk_block`` are local to the shard's own blocks;
    ``other`` stays GLOBAL (sources live anywhere), and ``deg`` holds
    the FULL weighted degrees of the shard's rows (rows are owned
    outright, so no per-shard degree splitting).
    """

    u_local: jax.Array  # (S, NC*BE) int32 — dest local to its block
    other: jax.Array  # (S, NC*BE) int32 — GLOBAL source node
    weight: jax.Array  # (S, NC*BE) float32 — 0 => padding slot
    chunk_block: jax.Array  # (S, NC+1) int32 — SHARD-local block map
    deg: jax.Array  # (S, R) float32 — full degrees of the shard's rows
    block_n: int  # static
    block_e: int  # static
    num_chunks: int  # NC, shared across shards (static, snapped)
    num_nodes: int  # real node count n (static)
    num_shards: int  # S (static)

    @property
    def rows_per_shard(self) -> int:
        return self.deg.shape[1]

    @property
    def padded_nodes(self) -> int:
        return self.num_shards * self.deg.shape[1]

    @property
    def num_blocks(self) -> int:
        """Blocks per shard."""
        return self.deg.shape[1] // self.block_n

    @property
    def padded_half_edges(self) -> int:
        """Total half-edge slots across shards."""
        return self.num_shards * self.num_chunks * self.block_e

    def shard(self, s: int) -> NodeBlocking:
        """Single-shard view in the shard's LOCAL node coordinates."""
        return NodeBlocking(
            u_local=self.u_local[s], other=self.other[s],
            weight=self.weight[s], chunk_block=self.chunk_block[s],
            deg=self.deg[s],
            block_n=self.block_n, block_e=self.block_e,
            num_chunks=self.num_chunks, num_nodes=self.rows_per_shard)

    @property
    def statics(self) -> dict:
        """Compile-key statics for the model-sharded tick builders."""
        return dict(block_n=self.block_n, block_e=self.block_e,
                    num_chunks=self.num_chunks, num_nodes=self.num_nodes,
                    num_shards=self.num_shards)


def build_model_sharded_blocking(src, dst, weight, num_nodes: int,
                                 num_shards: int,
                                 *, block_n: int = 512, block_e: int = 128,
                                 snap_chunks: bool = True
                                 ) -> ModelShardedBlocking:
    """Host-side destination-aligned chunk layouts for panel sharding.

    Node-blocks are padded to a multiple of ``num_shards`` and assigned
    contiguously (shard ``s`` owns blocks ``[s * NBs, (s + 1) * NBs)``,
    i.e. rows ``[s * R, (s + 1) * R)``); every live half-edge lands on
    the shard owning its DESTINATION row.  Unlike the edge-sharded
    builder there is no per-shard edge-buffer slicing contract: any
    (src, dst, weight) buffer works, zero-weight slots are dropped.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    nb_real = max((num_nodes + block_n - 1) // block_n, 1)
    nb_per = (nb_real + num_shards - 1) // num_shards
    nb_total = nb_per * num_shards
    rows = nb_per * block_n
    n_pad = nb_total * block_n
    u, o, w2, counts = _block_sorted_half_edges(src, dst, weight,
                                                block_n, nb_total)
    deg_full = _weighted_degrees(src, dst, weight, n_pad)
    offs = np.concatenate([[0], np.cumsum(counts)])
    # shared snapped total chunk count: identical static shapes per
    # shard; a hub-heavy shard uses more live chunks, not a new shape
    nc_raw = max(
        int(_chunk_counts(counts[s * nb_per:(s + 1) * nb_per],
                          block_e).sum())
        for s in range(num_shards))
    nc = snap_chunk_count(nc_raw) if snap_chunks else nc_raw
    ul = np.zeros((num_shards, nc * block_e), np.int32)
    ot = np.zeros((num_shards, nc * block_e), np.int32)
    wt = np.zeros((num_shards, nc * block_e), np.float32)
    cb = np.zeros((num_shards, nc + 1), np.int32)
    deg = np.zeros((num_shards, rows), np.float32)
    for s in range(num_shards):
        lo, hi = offs[s * nb_per], offs[(s + 1) * nb_per]
        ul[s], ot[s], wt[s], cb[s] = _fill_chunked(
            u[lo:hi] - s * rows,  # shard-local node coordinates
            o[lo:hi], w2[lo:hi],
            counts[s * nb_per:(s + 1) * nb_per],
            nb_per, nc, block_n, block_e)
        deg[s] = deg_full[s * rows:(s + 1) * rows]
    return ModelShardedBlocking(
        u_local=jnp.asarray(ul),
        other=jnp.asarray(ot),
        weight=jnp.asarray(wt),
        chunk_block=jnp.asarray(cb),
        deg=jnp.asarray(deg),
        block_n=block_n,
        block_e=block_e,
        num_chunks=nc,
        num_nodes=int(num_nodes),
        num_shards=int(num_shards),
    )


def model_shard_local_blocking(u_local, other, weight, chunk_block, deg,
                               *, block_n: int, block_e: int,
                               num_chunks: int, num_nodes: int,
                               num_shards: int) -> NodeBlocking:
    """One device's local-coordinate NodeBlocking from shard_map-LOCAL
    slices of a :class:`ModelShardedBlocking` (leading shard axis
    partitioned down to size 1).  ``num_nodes`` of the result is the
    shard's ROW count, not the global n."""
    del num_nodes, num_shards  # statics travel for key symmetry only
    return NodeBlocking(
        u_local=u_local[0], other=other[0], weight=weight[0],
        chunk_block=chunk_block[0], deg=deg[0],
        block_n=block_n, block_e=block_e,
        num_chunks=num_chunks, num_nodes=deg.shape[1])


@functools.partial(jax.jit, static_argnames=(
    "block_n", "block_e", "num_chunks", "padded_nodes", "use_kernel",
    "interpret"))
def model_local_rows(u_local, other, weight, chunk_block, deg,
                     v: jax.Array, ab: jax.Array, row_start,
                     *, block_n: int, block_e: int, num_chunks: int,
                     padded_nodes: int, use_kernel: bool,
                     interpret: bool = False):
    """This shard's (R, k) OWNED rows of alpha * (L V) + beta * V.

    ``v`` is the FULL replicated (n, k) panel (sources live anywhere);
    the layout arrays are one shard's slices of a
    :class:`ModelShardedBlocking` (shard-local coordinates);
    ``row_start`` is the (traced) first global row this shard owns.
    Rows are final — the caller's collective across shards merely
    assembles disjoint row ranges, it never sums overlapping
    contributions — so the affine epilogue fuses HERE, per shard, not
    post-collective.  ``use_kernel`` picks the Pallas chunk kernel vs
    the segment-sum form (same layout arrays either way).
    """
    n, k = v.shape
    rows = deg.shape[0]
    v = v.astype(jnp.float32)
    if use_kernel:
        pad_k = (-k) % kernel.LANES
        vp = jnp.pad(v, ((0, padded_nodes - n), (0, pad_k)))
        gathered = vp[other]  # (NC*BE, kp) global gather
        v_rows = jax.lax.dynamic_slice(
            vp, (row_start, 0), (rows, k + pad_k))
        out = kernel.edge_spmm_nb(
            u_local, weight, gathered, chunk_block, deg, v_rows, ab,
            k=k, block_n=block_n, block_e=block_e,
            num_chunks=num_chunks, interpret=interpret)
        return out[:, :k]
    vp = jnp.pad(v, ((0, padded_nodes - n), (0, 0)))
    blk = jnp.repeat(chunk_block[:num_chunks], block_e)
    dest = blk * block_n + u_local  # shard-local row ids
    contrib = weight[:, None] * vp[other]
    av = jnp.zeros((rows, k), jnp.float32).at[dest].add(contrib)
    v_rows = jax.lax.dynamic_slice(vp, (row_start, 0), (rows, k))
    out = deg[:, None] * v_rows - av
    return ab[0] * out + ab[1] * v_rows


@functools.partial(jax.jit, static_argnames=(
    "block_n", "block_e", "num_chunks", "interpret"))
def _edge_spmm_blocked(u_local, other, weight, chunk_block, deg, v, ab,
                       *, block_n: int, block_e: int,
                       num_chunks: int, interpret: bool):
    n, k = v.shape
    n_pad = deg.shape[0]
    pad_k = (-k) % kernel.LANES
    vp = jnp.pad(v.astype(jnp.float32), ((0, n_pad - n), (0, pad_k)))
    gathered = vp[other]  # (NC*BE, kp) XLA gather; the scatter is MXU
    out = kernel.edge_spmm_nb(
        u_local, weight, gathered, chunk_block, deg, vp, ab,
        k=k, block_n=block_n, block_e=block_e,
        num_chunks=num_chunks, interpret=interpret)
    return out[:n, :k]


def edge_spmm_blocked(nb: NodeBlocking, v: jax.Array,
                      alpha=1.0, beta=0.0,
                      *, interpret: bool = False) -> jax.Array:
    """alpha * (L V) + beta * V via the node-blocked kernel.

    Accepts (n,) or (n, k) with n == nb.num_nodes; alpha/beta may be
    traced scalars (the streaming service's per-session dilation scale).
    """
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    if v.shape[0] != nb.num_nodes:
        raise ValueError(
            f"panel rows {v.shape[0]} != blocking num_nodes {nb.num_nodes}")
    out = _edge_spmm_blocked(
        nb.u_local, nb.other, nb.weight, nb.chunk_block, nb.deg, v,
        _ab(alpha, beta),
        block_n=nb.block_n, block_e=nb.block_e,
        num_chunks=nb.num_chunks, interpret=interpret)
    return out[:, 0] if squeeze else out
