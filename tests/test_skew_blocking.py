"""Property battery for the skew-proof chunked blocking layouts.

The CSR chunk layout (``ops.build_node_blocking``: blocks own
``ceil(bucket / block_e)`` chunks, only the TOTAL chunk count is
snapped (pow2 at these sizes), and a scalar-prefetched chunk->block map
drives the kernel) exists for skewed degree distributions — power-law
graphs whose hub blocks would otherwise inflate every block to the worst
bucket's padding.  This file drives power-law samples through the layout and
asserts the structural contracts the kernels rely on:

  * every live half-edge is materialized exactly once, at its
    destination block (single-device AND model-sharded layouts);
  * the chunk->block map is well formed (monotone, covers every block,
    pow2 tail extends the last block as inert padding);
  * padded work never exceeds the legacy uniform layout's, and beats it
    >= 2x on a genuinely skewed graph;
  * all-padding model shards are exact-zero operators on both the
    kernel and segment row paths.

Runs as a seeded battery (the CI image ships without hypothesis); when
hypothesis IS importable the same checks also run generatively.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graphs
from repro.kernels.edge_spmm import ops as es_ops
from repro.kernels.edge_spmm import ref as es_ref

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI image has no hypothesis
    HAVE_HYPOTHESIS = False

pytestmark = pytest.mark.pallas

SEEDS = list(range(1, 21))


def _skewed_case(seed: int):
    """Power-law graph + DISTINCT weights (exact multiset comparisons)
    + a random block size, with some zero (capacity-padding) slots."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(60, 400))
    g = graphs.power_law_graph(
        n, avg_degree=float(rng.uniform(2.0, 12.0)), alpha=2.5, seed=seed)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = (np.arange(1, len(src) + 1, dtype=np.float32)
         * rng.uniform(0.5, 1.5)).astype(np.float32)
    w[rng.uniform(size=len(src)) < 0.15] = 0.0
    block_n = int(rng.choice([8, 16, 32, 64]))
    return src, dst, w, n, block_n


def _half_edge_multiset(src, dst, w):
    """Expected live half-edges {(u, o, w)}: two per live edge."""
    live = w != 0.0
    s, d, ww = src[live], dst[live], w[live]
    return sorted(zip(np.concatenate([s, d]).tolist(),
                      np.concatenate([d, s]).tolist(),
                      np.concatenate([ww, ww]).tolist()))


def _blocking_half_edges(nb: es_ops.NodeBlocking, row_offset: int = 0):
    """Live half-edges the CSR layout materialized, in global row ids
    (``row_offset`` globalizes a model shard's local coordinates)."""
    cb = np.asarray(nb.chunk_block)[: nb.num_chunks]
    ul = np.asarray(nb.u_local).reshape(nb.num_chunks, nb.block_e)
    ot = np.asarray(nb.other).reshape(nb.num_chunks, nb.block_e)
    wt = np.asarray(nb.weight).reshape(nb.num_chunks, nb.block_e)
    out = []
    for c in range(nb.num_chunks):
        live = wt[c] != 0.0
        rows = ul[c, live] + int(cb[c]) * nb.block_n + row_offset
        out.extend(zip(rows.tolist(), ot[c, live].tolist(),
                       wt[c, live].tolist()))
    return sorted(out)


def _half_edge_counts(src, dst, w, block_n: int, nb: int):
    """Per-block live half-edge counts (the uniform-layout baseline)."""
    live = w != 0.0
    u = np.concatenate([src[live], dst[live]])
    return np.bincount(u // block_n, minlength=nb)


# ---------------------------------------------------------------------------
# the checks (seed -> assertions); parametrized battery + optional
# hypothesis drivers below
# ---------------------------------------------------------------------------

def _check_chunk_block_well_formed(seed: int):
    src, dst, w, n, block_n = _skewed_case(seed)
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    cb = np.asarray(nb.chunk_block)
    blocks = nb.padded_nodes // nb.block_n
    assert cb.shape == (nb.num_chunks + 1,)
    assert nb.num_chunks == es_ops.next_pow2(nb.num_chunks)
    # monotone chunk runs, every block owns >= 1 chunk, and the pow2
    # padding tail (sentinel included) extends the LAST block's run
    assert (np.diff(cb) >= 0).all()
    assert np.array_equal(np.unique(cb), np.arange(blocks))
    raw = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n,
                                     snap_chunks=False)
    assert (cb[raw.num_chunks:] == blocks - 1).all()
    # padding chunks carry no live half-edges
    wt = np.asarray(nb.weight).reshape(nb.num_chunks, nb.block_e)
    assert (wt[raw.num_chunks:] == 0.0).all()


def _check_chunked_covers_each_half_edge_once(seed: int):
    src, dst, w, n, block_n = _skewed_case(seed)
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    assert _blocking_half_edges(nb) == _half_edge_multiset(src, dst, w)


def _check_padded_work_le_uniform(seed: int):
    """Raw CSR padded work (sum of per-block ceils) never exceeds the
    raw uniform layout's (every block pays the max ceil); the pow2
    total-snap then costs < 2x on top.  Snapped-to-snapped comparison
    is NOT monotone on near-uniform degree counts (total-snap vs the
    uniform layout's per-block snap), so the invariant is raw-to-raw —
    the >= 2x win on skewed graphs is asserted separately."""
    src, dst, w, n, block_n = _skewed_case(seed)
    raw = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n,
                                     snap_chunks=False)
    counts = _half_edge_counts(src, dst, w, block_n,
                               raw.padded_nodes // block_n)
    assert raw.padded_half_edges <= es_ops.uniform_padded_half_edges(
        counts, raw.block_e, snap_chunks=False)
    snapped = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    assert snapped.padded_half_edges < 2 * raw.padded_half_edges


def _check_chunked_kernel_matches_segment(seed: int):
    src, dst, w, n, block_n = _skewed_case(seed)
    rng = np.random.default_rng(seed + 10_000)
    k = int(rng.integers(1, 6))
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    got = es_ops.edge_spmm_blocked(nb, v, interpret=True)
    want = es_ref.edge_spmm(jnp.asarray(src), jnp.asarray(dst),
                            jnp.asarray(w), v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _check_model_sharded_slices_consistent(seed: int):
    """Shard s materializes exactly the half-edges destined to its row
    range [s*R, (s+1)*R) — in local coordinates — and its degree slice
    is the global degree vector's slice; the union over shards covers
    every live half-edge exactly once."""
    src, dst, w, n, block_n = _skewed_case(seed)
    num_shards = int(np.random.default_rng(seed + 1).choice([2, 4, 8]))
    mb = es_ops.build_model_sharded_blocking(src, dst, w, n, num_shards,
                                             block_n=block_n)
    rows = mb.rows_per_shard
    assert mb.num_chunks == es_ops.next_pow2(mb.num_chunks)
    want_all = _half_edge_multiset(src, dst, w)
    got_all = []
    deg_full = np.zeros(mb.padded_nodes, np.float32)
    np.add.at(deg_full, src, w)
    np.add.at(deg_full, dst, w)
    for s in range(num_shards):
        got = _blocking_half_edges(mb.shard(s), row_offset=s * rows)
        want = [he for he in want_all
                if s * rows <= he[0] < (s + 1) * rows]
        assert got == sorted(want), s
        got_all.extend(got)
        np.testing.assert_allclose(
            np.asarray(mb.deg[s]), deg_full[s * rows:(s + 1) * rows],
            rtol=1e-6, atol=1e-6)
    assert sorted(got_all) == want_all


def _check_model_sharded_rows_match_dense(seed: int):
    """Concatenated per-shard owned rows (kernel AND segment paths)
    == L v on the skewed graph."""
    from repro.core import laplacian as lap
    src, dst, w, n, block_n = _skewed_case(seed)
    rng = np.random.default_rng(seed + 20_000)
    k = int(rng.integers(1, 5))
    num_shards = int(rng.choice([2, 4]))
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    mb = es_ops.build_model_sharded_blocking(src, dst, w, n, num_shards,
                                             block_n=block_n)
    rows = mb.rows_per_shard
    want = np.asarray(lap.edge_matvec_arrays(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w), v))
    ab = jnp.asarray([1.0, 0.0], jnp.float32)
    for use_kernel in (False, True):
        out = np.concatenate([
            np.asarray(es_ops.model_local_rows(
                mb.u_local[s], mb.other[s], mb.weight[s],
                mb.chunk_block[s], mb.deg[s], v, ab,
                jnp.asarray(s * rows, jnp.int32),
                block_n=mb.block_n, block_e=mb.block_e,
                num_chunks=mb.num_chunks, padded_nodes=mb.padded_nodes,
                use_kernel=use_kernel, interpret=True))
            for s in range(num_shards)])
        np.testing.assert_allclose(out[:n], want, rtol=2e-4, atol=2e-4,
                                   err_msg=f"use_kernel={use_kernel}")


# ---------------------------------------------------------------------------
# seeded battery (always runs)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_chunk_block_well_formed(seed):
    _check_chunk_block_well_formed(seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_chunked_covers_each_half_edge_once(seed):
    _check_chunked_covers_each_half_edge_once(seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_padded_work_le_uniform(seed):
    _check_padded_work_le_uniform(seed)


@pytest.mark.parametrize("seed", SEEDS[:8])
def test_chunked_kernel_matches_segment(seed):
    _check_chunked_kernel_matches_segment(seed)


@pytest.mark.parametrize("seed", SEEDS[:10])
def test_model_sharded_slices_consistent(seed):
    _check_model_sharded_slices_consistent(seed)


@pytest.mark.parametrize("seed", SEEDS[:6])
def test_model_sharded_rows_match_dense(seed):
    _check_model_sharded_rows_match_dense(seed)


def test_skew_reduction_on_power_law():
    """On a genuinely skewed graph (alpha = 2.5, hub blocks), the CSR
    chunk layout walks >= 2x fewer padded half-edge slots than the
    legacy uniform layout — the acceptance bar the skew bench rows
    measure at scale."""
    g = graphs.power_law_graph(4096, avg_degree=8.0, alpha=2.5, seed=0)
    nb = es_ops.build_node_blocking(g.src, g.dst, g.weight, g.num_nodes,
                                    block_n=256)
    counts = _half_edge_counts(np.asarray(g.src), np.asarray(g.dst),
                               np.asarray(g.weight), 256,
                               nb.padded_nodes // 256)
    uniform = es_ops.uniform_padded_half_edges(counts, nb.block_e)
    assert uniform / nb.padded_half_edges >= 2.0, \
        (uniform, nb.padded_half_edges)


def test_model_all_padding_shard_inert():
    """A model shard owning only empty rows is a zero operator (exact
    zeros, no NaN) on BOTH row paths: every edge lands in shard 0, so
    shards 1..3 hold pure padding."""
    rng = np.random.default_rng(5)
    n, block_n, num_shards = 64, 8, 4
    rows_owned = 16  # rows per shard with these sizes
    src = rng.integers(0, rows_owned, 40)
    dst = rng.integers(0, rows_owned, 40)
    keep = src != dst
    w = rng.uniform(0.5, 1.5, keep.sum()).astype(np.float32)
    mb = es_ops.build_model_sharded_blocking(
        src[keep], dst[keep], w, n, num_shards, block_n=block_n)
    assert mb.rows_per_shard == rows_owned
    v = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    ab = jnp.asarray([1.0, 0.0], jnp.float32)
    for s in (1, 3):
        assert (np.asarray(mb.weight[s]) == 0.0).all()
        for use_kernel in (False, True):
            out = np.asarray(es_ops.model_local_rows(
                mb.u_local[s], mb.other[s], mb.weight[s],
                mb.chunk_block[s], mb.deg[s], v, ab,
                jnp.asarray(s * rows_owned, jnp.int32),
                block_n=mb.block_n, block_e=mb.block_e,
                num_chunks=mb.num_chunks, padded_nodes=mb.padded_nodes,
                use_kernel=use_kernel, interpret=True))
            assert not np.isnan(out).any()
            np.testing.assert_array_equal(out, 0.0)


def _path_layout(builder: str, nc_raw: int, snap: bool):
    """A layout of exactly ``nc_raw`` raw chunks from a tiny graph: one
    node per block (block_n=1), each owning one chunk (degree <= 2 <
    block_e), so the count comes from the node count, not the edges."""
    kw = dict(block_n=1, block_e=8, snap_chunks=snap)
    n = 2 * nc_raw if builder == "model" else nc_raw  # 2 shards' rows
    m = 64  # a path over the first nodes: an even edge count
    src = np.arange(m, dtype=np.int64)
    dst = src + 1
    w = np.ones(m, np.float32)
    if builder == "single":
        return es_ops.build_node_blocking(src, dst, w, n, **kw)
    if builder == "edge":
        return es_ops.build_sharded_node_blocking(src, dst, w, n, 2, **kw)
    return es_ops.build_model_sharded_blocking(src, dst, w, n, 2, **kw)


@pytest.mark.parametrize("builder", ["single", "edge", "model"])
@pytest.mark.parametrize("nc_raw", [1023, 1024, 1025, 2121, 70_000])
def test_chunk_count_snapping(builder, nc_raw):
    """All three builders snap the total chunk count by one rule: the
    next power of two below FINE_SNAP_FROM raw chunks, from there a
    multiple of next_pow2 // FINE_SNAP_SPLIT, less than one granule
    above the raw count."""
    raw = _path_layout(builder, nc_raw, snap=False).num_chunks
    assert raw == nc_raw
    nc = _path_layout(builder, nc_raw, snap=True).num_chunks
    assert nc == es_ops.snap_chunk_count(raw)
    pow2 = es_ops.next_pow2(raw)
    if raw < es_ops.FINE_SNAP_FROM:
        assert nc == pow2
    else:
        granule = pow2 // es_ops.FINE_SNAP_SPLIT
        assert nc % granule == 0
        assert raw <= nc < raw + granule
    if raw == 2121:  # the dblp_1c layout: 2,304 grid steps, not 4,096
        assert nc == 2304


def test_padding_chunks_bitwise_inert():
    """The blocked matvec is bitwise the same whatever count of padding
    chunks trails the layout: raw, pow2-snapped, or raw + 3."""
    rng = np.random.default_rng(3)
    src, dst, w, n, block_n = _skewed_case(3)
    block_e = 128
    v = jnp.asarray(rng.normal(size=(n, 6)), jnp.float32)
    raw = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n,
                                     snap_chunks=False)
    pow2 = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    nb = raw.num_blocks
    u, o, w2, counts = es_ops._block_sorted_half_edges(src, dst, w,
                                                       block_n, nb)
    nc = raw.num_chunks + 3
    assert nc not in (raw.num_chunks, pow2.num_chunks)
    ul, ot, wt, cb = es_ops._fill_chunked(u, o, w2, counts, nb, nc,
                                          block_n, block_e)
    odd = raw._replace(u_local=jnp.asarray(ul), other=jnp.asarray(ot),
                       weight=jnp.asarray(wt), chunk_block=jnp.asarray(cb),
                       num_chunks=nc)
    outs = [np.asarray(es_ops.edge_spmm_blocked(b, v, interpret=True))
            for b in (raw, pow2, odd)]
    for out in outs[1:]:
        np.testing.assert_array_equal(out.view(np.uint32),
                                      outs[0].view(np.uint32))


if HAVE_HYPOTHESIS:
    @given(st.integers(1, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_chunked_covers_property(seed):
        _check_chunked_covers_each_half_edge_once(seed)
        _check_chunk_block_well_formed(seed)
        _check_padded_work_le_uniform(seed)

    @given(st.integers(1, 10_000))
    @settings(max_examples=8, deadline=None)
    def test_model_sharded_property(seed):
        _check_model_sharded_slices_consistent(seed)
