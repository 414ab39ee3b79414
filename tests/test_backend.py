"""Backend equivalence: pallas(interpret) == segment on every
solver-facing operator (repro.core.backend).

The acceptance contract of the backend layer: for the same inputs each
backend is deterministic, and the pallas kernels (run in interpret mode
on CPU — the exact kernel code path, minus Mosaic) match the segment
gather/scatter to <= 1e-5 max-abs on weighted, capacity-padded, and
non-block-aligned graphs, for the plain matvec, the fused series step,
and the fused mu-EG step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, graphs, operators, solvers
from repro.core import laplacian as lap
from repro.core.series import (cheb_log, limit_neg_exp, taylor_log,
                               taylor_neg_exp)

pytestmark = pytest.mark.pallas

TOL = 1e-5


def _rand_graph(seed: int, n: int, e: int) -> lap.EdgeList:
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.integers(0, n, e)], axis=1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    w = rng.uniform(0.1, 2.0, size=len(edges)).astype(np.float32)
    return lap.make_edge_list(edges, n, weights=w)


def _panel(seed: int, n: int, k: int) -> jax.Array:
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(n, k)), jnp.float32)


# weighted / capacity-padded / non-aligned (n, k, E not block multiples)
CASES = {
    "weighted": lambda: _rand_graph(0, 96, 300),
    "capacity_padded": lambda: lap.pad_edge_list(_rand_graph(1, 96, 300), 512),
    "non_aligned": lambda: _rand_graph(2, 301, 517),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_matvec_equivalence(case):
    g = CASES[case]()
    v = _panel(3, g.num_nodes, 5)
    seg = operators.edge_matvec(g, backend="segment")(v)
    pal = operators.edge_matvec(g, backend="pallas")(v)
    assert float(jnp.max(jnp.abs(seg - pal))) <= TOL


@pytest.mark.parametrize("case", sorted(CASES))
def test_matvec_equivalence_node_blocked(case):
    """Forced blocking exercises the scalable kernel on small graphs
    (block_n far below n, non-divisible on the non_aligned case)."""
    g = CASES[case]()
    blk = backend.blocking_for(g, block_n=64)
    v = _panel(4, g.num_nodes, 6)
    seg = operators.edge_matvec(g, backend="segment")(v)
    pal = operators.edge_matvec(g, backend="pallas", blocking=blk)(v)
    assert float(jnp.max(jnp.abs(seg - pal))) <= TOL


def test_matvec_edgeless_graph():
    """Zero-edge graphs (a supported streaming-admission state) must
    return zeros on BOTH backends — the pallas wrapper pads an inert
    block instead of emitting a zero-size grid."""
    g = lap.make_edge_list(np.zeros((0, 2), np.int64), 40)
    v = _panel(16, 40, 3)
    for b in ("segment", "pallas"):
        out = operators.edge_matvec(g, backend=b)(v)
        np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_matvec_1d_column_agree():
    g = CASES["non_aligned"]()
    mv = operators.edge_matvec(g, backend="pallas")
    v = _panel(5, g.num_nodes, 1)
    np.testing.assert_allclose(mv(v[:, 0]), mv(v)[:, 0], atol=TOL)


def test_auto_resolves_and_rejects():
    assert backend.resolve_backend("auto") in ("segment", "pallas")
    assert backend.resolve_backend("segment") == "segment"
    with pytest.raises(ValueError):
        backend.resolve_backend("cuda")


def _unit_radius(g: lap.EdgeList, target: float = 1.5) -> lap.EdgeList:
    """Rescale weights so the Gershgorin radius is `target` — the regime
    every production series runs in (the planner/auto_scale normalize L),
    and the only one where taylor_log converges at all."""
    rho = float(lap.spectral_radius_upper_bound(g))
    return g._replace(weight=g.weight * (target / rho))


@pytest.mark.parametrize("series_fn", [
    lambda: limit_neg_exp(7, scale=0.4),
    lambda: taylor_neg_exp(5),
    lambda: taylor_log(5),
    lambda: cheb_log(12, rho=1.5),
], ids=["limit_neg_exp", "taylor_neg_exp", "taylor_log", "cheb_log"])
@pytest.mark.parametrize("case", ["weighted", "non_aligned"])
def test_fused_series_equivalence(series_fn, case):
    """series_operator with the fused pallas step == classic segment
    recurrence, for every fused series family."""
    g = _unit_radius(CASES[case]())
    s = series_fn()
    v = _panel(6, g.num_nodes, 4)
    seg = operators.edge_series_operator(g, s, backend="segment")(v)
    pal = operators.edge_series_operator(g, s, backend="pallas")(v)
    assert float(jnp.max(jnp.abs(seg - pal))) <= TOL


def test_fused_series_node_blocked():
    g = CASES["capacity_padded"]()
    s = limit_neg_exp(9, scale=0.3)
    blk = backend.blocking_for(g, block_n=32)
    v = _panel(7, g.num_nodes, 3)
    seg = operators.edge_series_operator(g, s, backend="segment")(v)
    pal = operators.edge_series_operator(g, s, backend="pallas",
                                         blocking=blk)(v)
    assert float(jnp.max(jnp.abs(seg - pal))) <= TOL


def test_poly_step_edges_matches_dense_poly_step():
    """The edge-list extension of laplacian_poly.poly_step == its dense
    oracle on the graph Laplacian."""
    from repro.kernels.laplacian_poly import ops as lp_ops, ref as lp_ref

    g = CASES["weighted"]()
    blk = backend.blocking_for(g, block_n=32)
    u = _panel(8, g.num_nodes, 4)
    got = lp_ops.poly_step_edges(blk, u, 0.07, interpret=True)
    want = lp_ref.poly_step(lap.laplacian_dense(g), u, 0.07)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_mu_eg_step_backend_equivalence():
    v = _panel(9, 300, 6)
    v = v / jnp.linalg.norm(v, axis=0, keepdims=True)
    av = _panel(10, 300, 6)
    st = solvers.SolverState(v=v, step=jnp.zeros((), jnp.int32))
    seg = solvers.make_step_fn("mu_eg", "segment")(st, av, 0.05)
    pal = solvers.make_step_fn("mu_eg", "pallas")(st, av, 0.05)
    assert float(jnp.max(jnp.abs(seg.v - pal.v))) <= TOL
    assert int(seg.step) == int(pal.step) == 1


def test_minibatch_matvec_1d_2d_weighting():
    """The minibatch matvec weights 1-D and (N, 1) inputs identically
    (regression for the old atleast_2d(diff.T).T contortion; also
    asserted with hypothesis sweeps in test_laplacian when available)."""
    g = CASES["weighted"]()
    rng = np.random.default_rng(15)
    sel = jnp.asarray(rng.integers(0, g.num_edges, 32), jnp.int32)
    v = jnp.asarray(rng.normal(size=(g.num_nodes,)), jnp.float32)
    out1 = lap.minibatch_laplacian_matvec(
        g.src[sel], g.dst[sel], g.weight[sel], v, g.num_edges)
    out2 = lap.minibatch_laplacian_matvec(
        g.src[sel], g.dst[sel], g.weight[sel], v[:, None], g.num_edges)
    assert out1.shape == (g.num_nodes,) and out2.shape == (g.num_nodes, 1)
    np.testing.assert_allclose(out1, out2[:, 0], rtol=1e-6, atol=1e-6)
    # full edge set => scale E_total/B == 1 => exact L @ v
    full = lap.minibatch_laplacian_matvec(
        g.src, g.dst, g.weight, v, g.num_edges)
    np.testing.assert_allclose(full, lap.laplacian_matvec(g, v),
                               rtol=1e-5, atol=1e-5)


def test_minibatch_operator_backend_equivalence():
    """Same key => same sampled edges; only the SpMM implementation
    differs between backends."""
    g = CASES["weighted"]()
    s = limit_neg_exp(5, scale=0.4)
    v = _panel(11, g.num_nodes, 4)
    key = jax.random.PRNGKey(42)
    seg = operators.minibatch_operator(g, s, 64, backend="segment")(key, v)
    pal = operators.minibatch_operator(g, s, 64, backend="pallas")(key, v)
    assert float(jnp.max(jnp.abs(seg - pal))) <= TOL


def test_run_solver_backend_equivalence():
    """Whole-solve equivalence: identical traces and panels for a short
    run under each backend (matvec AND mu-EG step fused on pallas)."""
    g = CASES["weighted"]()
    s = limit_neg_exp(7, scale=0.4)
    outs = {}
    for b in ("segment", "pallas"):
        op = operators.edge_series_operator(g, s, backend=b)
        cfg = solvers.SolverConfig(method="mu_eg", lr=0.3, steps=10,
                                   eval_every=5, k=4, seed=0, backend=b)
        state, trace = solvers.run_solver(op, g.num_nodes, cfg)
        outs[b] = (state.v, trace.subspace_error)
    assert float(jnp.max(jnp.abs(outs["segment"][0] - outs["pallas"][0]))) <= TOL


def test_planned_operator_backend():
    g, _ = graphs.ring_of_cliques(4, 8)
    op_s, plan_s = operators.planned_operator(
        g, k=4, key=jax.random.PRNGKey(0), backend="segment")
    op_p, plan_p = operators.planned_operator(
        g, k=4, key=jax.random.PRNGKey(0), backend="pallas")
    assert plan_s.family == plan_p.family
    v = _panel(12, g.num_nodes, 4)
    assert float(jnp.max(jnp.abs(op_s(v) - op_p(v)))) <= TOL


def test_probe_backend_equivalence():
    g = CASES["weighted"]()
    from repro.spectral import probes
    ps = probes.probe_graph(g, backend="segment")
    pp = probes.probe_graph(g, backend="pallas")
    assert abs(float(ps.lambda_max) - float(pp.lambda_max)) <= 1e-4
    np.testing.assert_allclose(ps.ritz, pp.ritz, atol=1e-4)


def test_sharded_matvec_backend_equivalence():
    from jax.sharding import Mesh

    from repro.core import distributed

    g = CASES["weighted"]()
    mesh = Mesh(np.array(jax.devices()).reshape(1, 1), ("data", "model"))
    gp = distributed.pad_edges_for_mesh(g, mesh.shape["data"])
    v = _panel(13, g.num_nodes, 4)
    seg = distributed.sharded_laplacian_matvec(mesh, backend="segment")(
        gp.src, gp.dst, gp.weight, v)
    pal = distributed.sharded_laplacian_matvec(mesh, backend="pallas")(
        gp.src, gp.dst, gp.weight, v)
    assert float(jnp.max(jnp.abs(seg - pal))) <= TOL


def test_streaming_tick_backend_equivalence():
    """One tick program per backend over the same admitted graph: the
    panels and residuals must agree (node-blocked kernel + fused mu-EG
    step vs the vmapped segment tick)."""
    from repro.stream.service import ServiceConfig, StreamingService

    g, _ = graphs.sbm_graph(120, 3, p_in=0.35, p_out=0.03, seed=1)
    common = dict(k=5, num_clusters=3, degree=7, steps_per_tick=5, lr=0.3,
                  seed=0)
    seg = StreamingService(ServiceConfig(backend="segment", **common))
    pal = StreamingService(ServiceConfig(backend="pallas", tick_block_n=32,
                                         **common))
    for svc in (seg, pal):
        svc.add_graph("a", g)
    rs, rp = seg.tick(), pal.tick()
    assert abs(rs["a"] - rp["a"]) <= TOL
    vs = seg._sessions["a"].v
    vp = pal._sessions["a"].v
    assert float(jnp.max(jnp.abs(vs - vp))) <= TOL
    # updates invalidate + rebuild the blocking; ticks stay equivalent
    for svc in (seg, pal):
        svc.apply_updates("a", [[0, 5], [1, 7]], [1.0, 1.0])
    seg.tick(), pal.tick()
    assert pal._sessions["a"].layout is not None
    vs = seg._sessions["a"].v
    vp = pal._sessions["a"].v
    assert float(jnp.max(jnp.abs(vs - vp))) <= TOL
    assert pal.compile_count == 1  # one program for the whole episode


def test_blocking_determinism_and_padding():
    """Same graph => bitwise-identical blocking; zero-weight (capacity
    padding) slots are dropped, not bucketed."""
    g = CASES["weighted"]()
    gp = lap.pad_edge_list(g, 512)
    b1 = backend.blocking_for(g, block_n=32)
    b2 = backend.blocking_for(g, block_n=32)
    bp = backend.blocking_for(gp, block_n=32)
    for a, b in zip(b1[:4], b2[:4]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(b1[:4], bp[:4]):
        np.testing.assert_array_equal(a, b)  # padding slots invisible
    assert b1.num_chunks == bp.num_chunks


@pytest.mark.slow
@pytest.mark.parametrize("block_n", [16, 64, 256])
@pytest.mark.parametrize("block_e", [128, 256])
def test_block_sweep_equivalence(block_n, block_e):
    """Blocking layout sweep on a larger skewed graph (slow lane)."""
    rng = np.random.default_rng(7)
    n, e = 1500, 6000
    # skewed: hub nodes concentrate edges in a few buckets
    hub = rng.integers(0, 32, e)
    far = rng.integers(0, n, e)
    edges = np.stack([hub, far], axis=1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    w = rng.uniform(0.5, 1.5, len(edges)).astype(np.float32)
    g = _unit_radius(lap.make_edge_list(edges, n, weights=w))
    blk = backend.blocking_for(g, block_n=block_n, block_e=block_e)
    v = _panel(14, n, 4)
    seg = operators.edge_matvec(g, backend="segment")(v)
    pal = operators.edge_matvec(g, backend="pallas", blocking=blk)(v)
    assert float(jnp.max(jnp.abs(seg - pal))) <= TOL
