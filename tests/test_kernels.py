"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Shape/dtype sweeps + hypothesis property tests per kernel, as required:
every kernel asserts allclose against its ref.py oracle.
"""
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="property tests need hypothesis (see pyproject.toml [test] extra)")
from hypothesis import given, settings, strategies as st

from repro.kernels.edge_spmm import ops as es_ops, ref as es_ref
from repro.kernels.eg_update import ops as eg_ops, ref as eg_ref
from repro.kernels.laplacian_poly import ops as lp_ops, ref as lp_ref

pytestmark = pytest.mark.pallas

I = dict(interpret=True)


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


# --- laplacian_poly --------------------------------------------------------

@pytest.mark.parametrize("n", [128, 256, 300, 512])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_poly_step_shapes(n, k):
    l_mat = rand(0, (n, n))
    l_mat = l_mat + l_mat.T
    u = rand(1, (n, k))
    got = lp_ops.poly_step(l_mat, u, 0.02, **I)
    want = lp_ref.poly_step(l_mat, u, 0.02)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_poly_step_dtypes(dtype):
    n, k = 256, 4
    l_mat = rand(2, (n, n), dtype)
    u = rand(3, (n, k), dtype)
    got = lp_ops.poly_step(l_mat, u, 0.1, **I)
    want = lp_ref.poly_step(l_mat.astype(jnp.float32), u.astype(jnp.float32), 0.1)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_limit_series_apply_matches_series_module():
    """Kernel path == core.series recurrence == eigh oracle."""
    from repro.core import limit_neg_exp
    n, k, deg = 256, 3, 11
    l_mat = rand(4, (n, n))
    l_mat = (l_mat + l_mat.T) / 20
    v = rand(5, (n, k))
    got = lp_ops.limit_series_apply(l_mat, v, degree=deg, **I)
    want = limit_neg_exp(deg).apply(lambda u: l_mat @ u, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@given(st.integers(1, 500))
@settings(max_examples=8, deadline=None)
def test_poly_step_property(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 64)) * 8
    k = int(rng.integers(1, 6))
    c = float(rng.uniform(-0.5, 0.5))
    l_mat = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    got = lp_ops.poly_step(l_mat, u, c, block=128, **I)
    want = lp_ref.poly_step(l_mat, u, c)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# --- edge_spmm -------------------------------------------------------------

@pytest.mark.parametrize("e", [64, 128, 200, 512])
@pytest.mark.parametrize("n,k", [(50, 2), (256, 8), (300, 5)])
def test_edge_spmm_shapes(e, n, k):
    key = jax.random.PRNGKey(e * 7 + n)
    src = jax.random.randint(key, (e,), 0, n)
    dst = jax.random.randint(jax.random.fold_in(key, 1), (e,), 0, n)
    w = jax.random.uniform(jax.random.fold_in(key, 2), (e,))
    v = rand(6, (n, k))
    got = es_ops.edge_spmm(src, dst, w, v, **I)
    want = es_ref.edge_spmm(src, dst, w, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_edge_spmm_equals_laplacian_on_full_edge_set():
    """Full-batch edge_spmm == dense Laplacian matvec (paper L = X^T W X)."""
    from repro.core import graphs, laplacian_dense
    g, _ = graphs.ring_of_cliques(3, 6)
    v = rand(7, (g.num_nodes, 4))
    got = es_ops.edge_spmm(g.src, g.dst, g.weight, v, **I)
    want = laplacian_dense(g) @ v
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@given(st.integers(1, 500))
@settings(max_examples=8, deadline=None)
def test_edge_spmm_property(seed):
    rng = np.random.default_rng(seed)
    e = int(rng.integers(1, 300))
    n = int(rng.integers(4, 200))
    k = int(rng.integers(1, 9))
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 2, e), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    got = es_ops.edge_spmm(src, dst, w, v, **I)
    want = es_ref.edge_spmm(src, dst, w, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_edge_spmm_affine_epilogue():
    """alpha * L V + beta * V fused into the one-hot kernel epilogue."""
    rng = np.random.default_rng(3)
    e, n, k = 200, 120, 4
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 2, e), jnp.float32)
    v = rand(20, (n, k))
    got = es_ops.edge_spmm(src, dst, w, v, alpha=-0.3, beta=1.0, **I)
    want = es_ref.edge_spmm_affine(src, dst, w, v, -0.3, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@given(st.integers(1, 500))
@settings(max_examples=8, deadline=None)
def test_edge_spmm_node_blocked_property(seed):
    """build_node_blocking + blocked kernel == scatter-add oracle on
    random (unaligned) graphs and block sizes."""
    rng = np.random.default_rng(seed)
    e = int(rng.integers(1, 300))
    n = int(rng.integers(8, 200))
    k = int(rng.integers(1, 9))
    block_n = int(rng.choice([8, 16, 32, 64]))
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 2, e), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    got = es_ops.edge_spmm_blocked(nb, v, **I)
    # self-loops (src == dst) cancel in both paths: deg adds 2w, the two
    # half-edges subtract w each
    want = es_ref.edge_spmm(src, dst, w, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _laplacian_f64(src, dst, w, v):
    """float64 L v over the edge list, and per element the sum of its
    terms' magnitudes: deg_u |v_u| + sum_o w |v_o|."""
    w64, v64 = w.astype(np.float64), v.astype(np.float64)
    deg = np.zeros(len(v))
    np.add.at(deg, src, w64)
    np.add.at(deg, dst, w64)
    lv = deg[:, None] * v64
    terms = deg[:, None] * np.abs(v64)
    for a, b in ((src, dst), (dst, src)):
        np.add.at(lv, a, -w64[:, None] * v64[b])
        np.add.at(terms, a, w64[:, None] * np.abs(v64[b]))
    return lv, terms


def _one_bf16_pass(nb, v):
    """The former scatter at one default-precision MXU pass, as the TPU
    runs it: the weighted one-hot and the gathered rows rounded to bf16,
    exact products, float32 sums."""
    def bf16(x):
        return np.asarray(x, np.float32).astype(jnp.bfloat16).astype(
            np.float32)
    n, k = v.shape
    vp = np.zeros((nb.padded_nodes, k), np.float32)
    vp[:n] = v
    blk = np.repeat(np.asarray(nb.chunk_block)[:-1], nb.block_e)
    dest = blk * nb.block_n + np.asarray(nb.u_local)
    out = np.asarray(nb.deg)[:, None] * vp
    np.subtract.at(out, dest, bf16(nb.weight)[:, None]
                   * bf16(vp[np.asarray(nb.other)]))
    return out[:n]


# k = 6 stacks the data's three bf16 parts into 24 operand rows; k = 48
# into 144, more than one 128-row MXU tile
@pytest.mark.parametrize("k", [6, 48])
def test_edge_spmm_node_blocked_exact_to_f32(k):
    """The blocked kernel's bf16 passes keep float32: each element is
    within 8 eps32 of the float64 result, scaled by its terms' sum.
    One bf16 pass of the weighted one-hot misses that bound."""
    rng = np.random.default_rng(k)
    n, e, block_n = 200, 1500, 32
    src = rng.integers(0, n, e)
    hub = rng.uniform(size=e) < 0.4  # three hubs of node-block 0
    src[hub] = rng.integers(0, 3, hub.sum())
    dst = rng.integers(0, n, e)
    proper = src != dst
    src, dst = src[proper], dst[proper]
    w = rng.uniform(0, 2, len(src)).astype(np.float32)
    v = rng.normal(size=(n, k)).astype(np.float32)
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    assert np.bincount(np.asarray(nb.chunk_block)[:-1]).max() >= 4
    got = np.asarray(es_ops.edge_spmm_blocked(nb, jnp.asarray(v), **I))
    want, terms = _laplacian_f64(src, dst, w, v)
    bound = 8 * np.finfo(np.float32).eps * terms
    assert np.all(np.abs(got - want) <= bound)
    control = _one_bf16_pass(nb, v)
    assert np.mean(np.abs(control - want) > bound) > 0.5


def _eqns(jaxpr, name: str) -> list:
    """The equations of primitive ``name`` in a jaxpr, nested ones
    included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    found += _eqns(sub, name)
    return found


@pytest.mark.parametrize("k", [6, 48])
def test_edge_spmm_node_blocked_one_dot_per_chunk(k):
    """Every chunk of the blocked kernel makes one dot, on bf16
    operands, at any panel width: the data's three bf16 parts go
    through the MXU stacked."""
    rng = np.random.default_rng(k)
    n = 100
    src, dst = rng.integers(0, n, 400), rng.integers(0, n, 400)
    proper = src != dst
    src, dst = src[proper], dst[proper]
    w = rng.uniform(0, 2, len(src)).astype(np.float32)
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=32)
    v = jnp.zeros((n, k), jnp.float32)
    closed = jax.make_jaxpr(
        lambda v: es_ops.edge_spmm_blocked(nb, v, **I))(v)
    calls = _eqns(closed.jaxpr, "pallas_call")
    assert len(calls) == 1
    dots = _eqns(calls[0].params["jaxpr"], "dot_general")
    assert len(dots) == 1
    assert all(x.aval.dtype == jnp.bfloat16 for x in dots[0].invars)


# --- node-blocking layout properties (single-device + per-shard) ----------

def _rand_blocking_case(seed: int):
    rng = np.random.default_rng(seed)
    e = int(rng.integers(1, 300))
    n = int(rng.integers(8, 200))
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    proper = src != dst  # self-loops excluded: a half-edge (u, u, w)
    src, dst = src[proper], dst[proper]  # cancels against deg in L v
    # DISTINCT weights so the half-edge multiset comparison is exact,
    # with some zero (capacity-padding) slots mixed in
    w = (np.arange(1, len(src) + 1, dtype=np.float32)
         * rng.uniform(0.5, 1.5)).astype(np.float32)
    w[rng.uniform(size=len(src)) < 0.2] = 0.0
    block_n = int(rng.choice([8, 16, 32, 64]))
    return src, dst, w, n, block_n


def _half_edge_multiset(src, dst, w):
    """Expected live half-edges {(u, o, w)}: two per live edge."""
    live = w != 0.0
    s, d, ww = src[live], dst[live], w[live]
    return sorted(zip(np.concatenate([s, d]).tolist(),
                      np.concatenate([d, s]).tolist(),
                      np.concatenate([ww, ww]).tolist()))


def _blocking_half_edges(nb: es_ops.NodeBlocking):
    """Live half-edges a blocking actually materialized, globalized.

    Walks the CSR chunk layout: chunk c belongs to block
    ``chunk_block[c]``, so a destination's global row id is
    ``chunk_block[c] * block_n + u_local``."""
    cb = np.asarray(nb.chunk_block)[: nb.num_chunks]
    ul = np.asarray(nb.u_local).reshape(nb.num_chunks, nb.block_e)
    ot = np.asarray(nb.other).reshape(nb.num_chunks, nb.block_e)
    wt = np.asarray(nb.weight).reshape(nb.num_chunks, nb.block_e)
    out = []
    for c in range(nb.num_chunks):
        live = wt[c] != 0.0
        out.extend(zip((ul[c, live] + int(cb[c]) * nb.block_n).tolist(),
                       ot[c, live].tolist(), wt[c, live].tolist()))
    return sorted(out)


def _check_blocking_covers_each_half_edge_once(seed: int):
    src, dst, w, n, block_n = _rand_blocking_case(seed)
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    assert _blocking_half_edges(nb) == _half_edge_multiset(src, dst, w)
    # degrees match the live edges too
    deg = np.zeros(nb.padded_nodes, np.float32)
    np.add.at(deg, src, w)
    np.add.at(deg, dst, w)
    np.testing.assert_allclose(np.asarray(nb.deg), deg, rtol=1e-6)


def _check_sharded_blocking_covers_each_half_edge_once(seed: int):
    """Per-shard variant: shard s covers exactly ITS slice's half-edges
    (so the union covers everything once), per-shard degrees sum to the
    global degrees, and the chunk count is shared and pow2."""
    src, dst, w, n, block_n = _rand_blocking_case(seed)
    num_shards = int(np.random.default_rng(seed + 1).choice([2, 4, 8]))
    pad = (-len(src)) % num_shards
    src = np.concatenate([src, np.zeros(pad, src.dtype)])
    dst = np.concatenate([dst, np.zeros(pad, dst.dtype)])
    w = np.concatenate([w, np.zeros(pad, np.float32)])
    sb = es_ops.build_sharded_node_blocking(src, dst, w, n, num_shards,
                                            block_n=block_n)
    per = len(src) // num_shards
    assert sb.num_chunks == es_ops.next_pow2(sb.num_chunks)
    for s in range(num_shards):
        sl = slice(s * per, (s + 1) * per)
        assert (_blocking_half_edges(sb.shard(s))
                == _half_edge_multiset(src[sl], dst[sl], w[sl])), s
    deg = np.zeros(sb.padded_nodes, np.float32)
    np.add.at(deg, src, w)
    np.add.at(deg, dst, w)
    np.testing.assert_allclose(
        np.asarray(sb.deg).sum(axis=0), deg, rtol=1e-5, atol=1e-6)


def _check_blocking_node_permutation_invariance(seed: int):
    """Relabeling nodes commutes with the blocked matvec: permuting the
    graph and the panel permutes the result — the layout (which nodes
    share a block) is an implementation detail, not a semantics."""
    rng = np.random.default_rng(seed)
    src, dst, w, n, block_n = _rand_blocking_case(seed)
    k = int(rng.integers(1, 5))
    v = rng.normal(size=(n, k)).astype(np.float32)
    perm = rng.permutation(n)
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    nb_p = es_ops.build_node_blocking(perm[src], perm[dst], w, n,
                                      block_n=block_n)
    out = np.asarray(es_ops.edge_spmm_blocked(nb, jnp.asarray(v), **I))
    v_p = np.empty_like(v)
    v_p[perm] = v
    out_p = np.asarray(es_ops.edge_spmm_blocked(nb_p, jnp.asarray(v_p), **I))
    np.testing.assert_allclose(out_p[perm], out, rtol=2e-4, atol=2e-4)


def _check_blocking_chunks_pow2_snapped(seed: int):
    src, dst, w, n, block_n = _rand_blocking_case(seed)
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    raw = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n,
                                     snap_chunks=False)
    assert nb.num_chunks == es_ops.next_pow2(raw.num_chunks)
    assert raw.num_chunks <= nb.num_chunks < 2 * max(raw.num_chunks, 1)


def _check_blocking_padding_inert(seed: int):
    """Capacity padding is invisible: the blocking of a padded buffer is
    bitwise the blocking of the live edges, and padding-only blocks
    (and shards) contribute exact zeros to the matvec."""
    rng = np.random.default_rng(seed)
    src, dst, w, n, block_n = _rand_blocking_case(seed)
    k = int(rng.integers(1, 5))
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    pad = int(rng.integers(1, 128))
    src_p = np.concatenate([src, np.zeros(pad, src.dtype)])
    dst_p = np.concatenate([dst, np.zeros(pad, dst.dtype)])
    w_p = np.concatenate([w, np.zeros(pad, np.float32)])
    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=block_n)
    nb_p = es_ops.build_node_blocking(src_p, dst_p, w_p, n, block_n=block_n)
    for a, b in zip(nb[:4], nb_p[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert nb.num_chunks == nb_p.num_chunks
    # an all-padding shard is a zero operator (exact zeros, no NaN)
    sb = es_ops.build_sharded_node_blocking(
        np.zeros(16, np.int64), np.zeros(16, np.int64),
        np.zeros(16, np.float32), n, 4, block_n=block_n)
    out = np.asarray(es_ops.edge_spmm_blocked(sb.shard(0), v, **I))
    np.testing.assert_array_equal(out, 0.0)


@given(st.integers(1, 10_000))
@settings(max_examples=20, deadline=None)
def test_blocking_covers_each_half_edge_once(seed):
    _check_blocking_covers_each_half_edge_once(seed)


@given(st.integers(1, 10_000))
@settings(max_examples=10, deadline=None)
def test_sharded_blocking_covers_each_half_edge_once(seed):
    _check_sharded_blocking_covers_each_half_edge_once(seed)


@given(st.integers(1, 10_000))
@settings(max_examples=8, deadline=None)
def test_blocking_node_permutation_invariance(seed):
    _check_blocking_node_permutation_invariance(seed)


@given(st.integers(1, 10_000))
@settings(max_examples=20, deadline=None)
def test_blocking_chunks_pow2_snapped(seed):
    _check_blocking_chunks_pow2_snapped(seed)


@given(st.integers(1, 10_000))
@settings(max_examples=8, deadline=None)
def test_blocking_padding_inert(seed):
    _check_blocking_padding_inert(seed)


def test_limit_series_apply_edges_matches_dense():
    """Edge-list fused series == dense-kernel series == core.series."""
    from repro.core import graphs, laplacian_dense, limit_neg_exp

    g, _ = graphs.ring_of_cliques(3, 6)
    nb = es_ops.build_node_blocking(g.src, g.dst, g.weight, g.num_nodes,
                                    block_n=8)
    v = rand(21, (g.num_nodes, 3))
    got = lp_ops.limit_series_apply_edges(nb, v, degree=9, scale=0.5,
                                          interpret=True)
    want = limit_neg_exp(9, scale=0.5).apply(
        lambda u: laplacian_dense(g) @ u, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# --- eg_update -------------------------------------------------------------

@pytest.mark.parametrize("n", [128, 512, 700])
@pytest.mark.parametrize("k", [2, 4, 8])
def test_eg_update_shapes(n, k):
    v = rand(8, (n, k))
    v = v / jnp.linalg.norm(v, axis=0, keepdims=True)
    av = rand(9, (n, k))
    got = eg_ops.mu_eg_update(v, av, 0.05, **I)
    want = eg_ref.mu_eg_update(v, av, 0.05)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_eg_update_matches_solver_step():
    """Fused kernel == solvers.mu_eg_step (the training loop's oracle)."""
    from repro.core.solvers import SolverState, mu_eg_step
    n, k = 384, 5
    v = rand(10, (n, k))
    v = v / jnp.linalg.norm(v, axis=0, keepdims=True)
    av = rand(11, (n, k))
    st_ = SolverState(v=v, step=jnp.zeros((), jnp.int32))
    want = mu_eg_step(st_, av, 0.03).v
    got = eg_ops.mu_eg_update(v, av, 0.03, **I)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@given(st.integers(1, 500))
@settings(max_examples=6, deadline=None)
def test_eg_update_property(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 80)) * 8
    k = int(rng.integers(1, 7))
    lr = float(rng.uniform(0.001, 0.3))
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    v = v / jnp.linalg.norm(v, axis=0, keepdims=True)
    av = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    got = eg_ops.mu_eg_update(v, av, lr, block_n=128, **I)
    want = eg_ref.mu_eg_update(v, av, lr)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


def test_eg_update_preserves_unit_norm():
    n, k = 256, 6
    v = rand(12, (n, k))
    v = v / jnp.linalg.norm(v, axis=0, keepdims=True)
    av = rand(13, (n, k))
    out = eg_ops.mu_eg_update(v, av, 0.1, **I)
    np.testing.assert_allclose(jnp.linalg.norm(out, axis=0), 1.0, atol=1e-5)
