"""Distributed SPED operators (shard_map).

The mesh fixture spans EVERY device the process sees: plain tier-1 runs
are single-device (collectives degenerate to copies), while the
scripts/ci.sh distributed lane re-runs this file in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the psums
actually cross shard boundaries.  Tests marked ``distributed`` REQUIRE
>= 2 devices (conftest skips them below that) and pin the acceptance
contract of sharded serving: sharded == single-device to <= 1e-5 for
matvecs, fused series programs, full solves, and streaming ticks, on
weighted / capacity-padded / non-aligned graphs, including per-shard
node blockings and all-padding shards.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.compat import default_edge_mesh
from repro.core import (
    SolverConfig, backend, build_edge_incidence, laplacian_dense,
    limit_neg_exp, run_solver,
)
from repro.core import distributed, graphs, metrics, operators, program, solvers
from repro.core import laplacian as lap
from repro.kernels.edge_spmm import ops as es_ops

TOL = 1e-5


@pytest.fixture(scope="module")
def mesh():
    """("data", "model") mesh over ALL local devices — 1 in tier-1,
    8 in the distributed CI lane (the old fixture pinned 1x1, which
    made every collective a no-op even under the lane)."""
    return default_edge_mesh()


@pytest.fixture(scope="module")
def graph():
    g, labels = graphs.clique_graph(120, 3, seed=0)
    return g, laplacian_dense(g)


def _rand_graph(seed: int, n: int, e: int) -> lap.EdgeList:
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.integers(0, n, e)], axis=1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    w = rng.uniform(0.1, 2.0, size=len(edges)).astype(np.float32)
    return lap.make_edge_list(edges, n, weights=w)


def _panel(seed: int, n: int, k: int) -> jax.Array:
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(n, k)), jnp.float32)


# weighted / capacity-padded / non-aligned (n, E not block multiples)
CASES = {
    "weighted": lambda: _rand_graph(0, 96, 300),
    "capacity_padded": lambda: lap.pad_edge_list(_rand_graph(1, 96, 300), 512),
    "non_aligned": lambda: _rand_graph(2, 301, 517),
}


def test_mesh_spans_all_devices(mesh):
    """The lane's reason to exist: on 8 virtual devices the edge axis
    really holds 8 shards (a 1x1 mesh would silently test nothing)."""
    assert mesh.shape["data"] == jax.device_count()


def test_sharded_matvec_matches_dense(mesh, graph):
    g, L = graph
    gp = distributed.pad_edges_for_mesh(g, mesh.shape["data"])
    mv = distributed.sharded_laplacian_matvec(mesh)
    v = jax.random.normal(jax.random.PRNGKey(0), (g.num_nodes, 4))
    np.testing.assert_allclose(
        mv(gp.src, gp.dst, gp.weight, v), L @ v, rtol=1e-4, atol=1e-4)


def test_edge_padding_adds_no_mass(graph):
    g, L = graph
    gp = distributed.pad_edges_for_mesh(g, 8)
    assert gp.num_edges % 8 == 0
    from repro.core import laplacian_matvec
    v = jax.random.normal(jax.random.PRNGKey(1), (g.num_nodes, 2))
    np.testing.assert_allclose(
        laplacian_matvec(gp, v), L @ v, rtol=1e-4, atol=1e-4)


def test_distributed_series_operator_matches_local(mesh, graph):
    g, L = graph
    s = limit_neg_exp(51, scale=4.0 / float(2 * jnp.max(jnp.diag(L))))
    op_d = distributed.distributed_series_operator(mesh, g, s)
    op_l = operators.series_operator(s, operators.dense_matvec(L))
    v = jax.random.normal(jax.random.PRNGKey(2), (g.num_nodes, 3))
    np.testing.assert_allclose(op_d(v), op_l(v), rtol=1e-3, atol=1e-3)


def test_distributed_minibatch_converges(mesh, graph):
    g, L = graph
    rho = float(2 * jnp.max(jnp.diag(L)))
    s = limit_neg_exp(51, scale=6.0 / rho)
    op = distributed.distributed_minibatch_operator(
        mesh, g, s, batch_edges_per_device=512)
    k = 3
    _, v_star = metrics.ground_truth_bottom_k(L, k)
    cfg = SolverConfig(method="mu_eg", lr=0.1, steps=800, eval_every=100, k=k)
    _, tr = run_solver(op, g.num_nodes, cfg, v_star=v_star, stochastic=True)
    assert float(tr.subspace_error[-1]) < 0.08


def test_distributed_walk_operator_matches_expectation(mesh):
    g, _ = graphs.ring_of_cliques(3, 4)
    inc = build_edge_incidence(g)
    L = np.asarray(laplacian_dense(g))
    coeffs = (0.0, 0.0, 1.0)  # pure L^2
    # ~100k walks TOTAL regardless of device count (pmean averages the
    # per-device estimates, so the total sample budget sets the error)
    per_device = max(100_000 // jax.device_count(), 12_500)
    op = distributed.distributed_walk_operator(
        mesh, g, inc, coeffs, lambda_star=0.0, walkers_per_device=per_device)
    v = jnp.eye(g.num_nodes)
    est = -np.asarray(op(jax.random.PRNGKey(0), v))  # op = 0 - P(L)
    want = L @ L
    rel = np.linalg.norm(est - want) / np.linalg.norm(want)
    assert rel < 0.08, rel


# ---------------------------------------------------------------------------
# per-shard node blockings (host-side: run everywhere, no mesh needed)
# ---------------------------------------------------------------------------

def test_sharded_blocking_shares_one_layout():
    """All shards carry identical static shapes and a shared
    pow2-snapped chunk count (the shard_map shape contract)."""
    g = CASES["non_aligned"]()
    gp = distributed.pad_edges_for_mesh(g, 8)
    sb = backend.sharded_blocking_for(gp, 8, block_n=64)
    assert sb.num_shards == 8
    assert sb.num_chunks == es_ops.next_pow2(sb.num_chunks)
    assert sb.u_local.shape == sb.other.shape == sb.weight.shape
    assert sb.u_local.shape[0] == 8 and sb.deg.shape[0] == 8


def test_sharded_blocking_matches_dense_per_shard_sum():
    """sum_s (deg_s * v - A_s v) == L v: the per-shard decomposition
    reconstructs the matvec exactly (no double-counted diagonal)."""
    for case in sorted(CASES):
        g = CASES[case]()
        L = np.asarray(laplacian_dense(g))
        v = _panel(3, g.num_nodes, 4)
        for num_shards in (1, 4, 8):
            gp = distributed.pad_edges_for_mesh(g, num_shards)
            sb = backend.sharded_blocking_for(gp, num_shards, block_n=64)
            acc = np.zeros_like(np.asarray(v))
            for s in range(num_shards):
                acc += np.asarray(es_ops.edge_spmm_blocked(
                    sb.shard(s), v, interpret=True))
            np.testing.assert_allclose(acc, L @ v, rtol=1e-5, atol=1e-5)


def test_sharded_blocking_rejects_unbalanced_buffer():
    g = CASES["weighted"]()  # num_edges not a multiple of 7
    assert g.num_edges % 7 != 0
    with pytest.raises(ValueError, match="pad_edges_for_mesh"):
        backend.sharded_blocking_for(g, 7)


def test_all_padding_shard_exact_zeros():
    """A shard whose slice is pure capacity padding must contribute
    EXACT zeros (not NaN) on both backends — the sharded sibling of
    PR 3's zero-edge pallas fix."""
    g = lap.make_edge_list(np.array([[0, 1], [1, 2], [2, 3]]), 40)
    gp = distributed.pad_edges_for_mesh(g, 8)  # shards 3..7 all padding
    sb = backend.sharded_blocking_for(gp, 8, block_n=16)
    v = _panel(4, 40, 3)
    per = gp.num_edges // 8
    for s in (3, 7):
        # pallas node-blocked path
        out = np.asarray(es_ops.edge_spmm_blocked(
            sb.shard(s), v, interpret=True))
        assert not np.isnan(out).any()
        np.testing.assert_array_equal(out, 0.0)
        # pallas one-hot path on the raw shard slice
        sl = slice(s * per, (s + 1) * per)
        out = np.asarray(es_ops.edge_spmm(
            gp.src[sl], gp.dst[sl], gp.weight[sl], v, interpret=True))
        assert not np.isnan(out).any()
        np.testing.assert_array_equal(out, 0.0)
        # segment path on the same slice
        out = np.asarray(lap.edge_matvec_arrays(
            gp.src[sl], gp.dst[sl], gp.weight[sl], v))
        np.testing.assert_array_equal(out, 0.0)


def test_edgeless_store_sharded_blocking():
    """Every shard all-padding (edgeless admission): the layout still
    builds with the uniform chunk count and zero degrees."""
    g = lap.make_edge_list(np.zeros((0, 2), np.int64), 32)
    gp = distributed.pad_edges_for_mesh(lap.pad_edge_list(g, 64), 8)
    sb = backend.sharded_blocking_for(gp, 8, block_n=16)
    # CSR chunk layout: every block owns >= 1 chunk even when edgeless
    assert sb.num_chunks == es_ops.next_pow2(sb.num_blocks)
    v = _panel(5, 32, 2)
    for s in range(8):
        out = np.asarray(es_ops.edge_spmm_blocked(
            sb.shard(s), v, interpret=True))
        np.testing.assert_array_equal(out, 0.0)


# ---------------------------------------------------------------------------
# sharded == single-device equivalence (the distributed lane's contract)
# ---------------------------------------------------------------------------

@pytest.mark.distributed
@pytest.mark.parametrize("case", sorted(CASES))
def test_sharded_matvec_equivalence(mesh, case):
    """Sharded raw-array matvec == single-device segment, per backend."""
    g = CASES[case]()
    gp = distributed.pad_edges_for_mesh(g, mesh.shape["data"])
    v = _panel(6, g.num_nodes, 4)
    want = operators.edge_matvec(g, backend="segment")(v)
    for b in ("segment", "pallas"):
        got = distributed.sharded_laplacian_matvec(mesh, backend=b)(
            gp.src, gp.dst, gp.weight, v)
        np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


@pytest.mark.distributed
@pytest.mark.parametrize("case", sorted(CASES))
def test_sharded_blocked_matvec_equivalence(mesh, case):
    """Per-shard NODE-BLOCKED sharded matvec == single-device segment —
    the layout that carries the sharded pallas path past
    ONE_HOT_NODE_LIMIT (forced small block_n exercises it at test n)."""
    g = CASES[case]()
    num_shards = distributed.num_edge_shards(mesh)
    gp = distributed.pad_edges_for_mesh(g, num_shards)
    sb = backend.sharded_blocking_for(gp, num_shards, block_n=64)
    v = _panel(7, g.num_nodes, 4)
    want = operators.edge_matvec(g, backend="segment")(v)
    got = distributed.sharded_blocked_matvec(mesh, sb)(v)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


@pytest.mark.distributed
@pytest.mark.parametrize("backend_name", ["segment", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_sharded_fused_series_equivalence(mesh, case, backend_name):
    """The one-shard_map fused series program == local series operator."""
    g = CASES[case]()
    rho = float(lap.spectral_radius_upper_bound(g))
    s = limit_neg_exp(7, scale=1.2 / rho)
    op_d = distributed.distributed_series_operator(
        mesh, g, s, backend=backend_name)
    op_l = operators.edge_series_operator(g, s, backend="segment")
    v = _panel(8, g.num_nodes, 4)
    np.testing.assert_allclose(op_d(v), op_l(v), rtol=TOL, atol=TOL)


@pytest.mark.distributed
def test_sharded_blocked_series_equivalence(mesh):
    """Forced per-shard blocking through the series program (the
    past-the-one-hot-limit configuration, at test scale)."""
    g = CASES["non_aligned"]()
    rho = float(lap.spectral_radius_upper_bound(g))
    s = limit_neg_exp(9, scale=1.0 / rho)
    op_d = distributed.distributed_series_operator(
        mesh, g, s, backend="pallas", block_n=64)
    op_l = operators.edge_series_operator(g, s, backend="segment")
    v = _panel(9, g.num_nodes, 3)
    np.testing.assert_allclose(op_d(v), op_l(v), rtol=TOL, atol=TOL)


@pytest.mark.distributed
def test_sharded_full_solve_equivalence(mesh):
    """Whole-solve: identical panels after a short run through the
    sharded series program vs the local segment operator."""
    g = CASES["weighted"]()
    rho = float(lap.spectral_radius_upper_bound(g))
    s = limit_neg_exp(7, scale=1.2 / rho)
    cfg = solvers.SolverConfig(method="mu_eg", lr=0.3, steps=10,
                               eval_every=5, k=4, seed=0)
    outs = {}
    for name, op in (
        ("local", operators.edge_series_operator(g, s, backend="segment")),
        ("sharded", distributed.distributed_series_operator(
            mesh, g, s, backend="segment")),
        ("sharded_pallas", distributed.distributed_series_operator(
            mesh, g, s, backend="pallas")),
    ):
        state, _ = solvers.run_solver(op, g.num_nodes, cfg)
        outs[name] = state.v
    for name in ("sharded", "sharded_pallas"):
        err = float(jnp.max(jnp.abs(outs[name] - outs["local"])))
        assert err <= TOL, (name, err)


def test_distributed_solve_routes_through_unified_program(mesh):
    """core.distributed.distributed_solve == run_solver over the local
    operator: the distributed layer's whole-series solve runs THE same
    step construction (core.program) as every other deployment shape."""
    g = CASES["weighted"]()
    rho = float(lap.spectral_radius_upper_bound(g))
    s = limit_neg_exp(7, scale=1.2 / rho)
    cfg = solvers.SolverConfig(method="mu_eg", lr=0.3, steps=10,
                               eval_every=5, k=4, seed=0)
    st_d, _ = distributed.distributed_solve(mesh, g, s, cfg,
                                            backend="segment")
    op_l = operators.edge_series_operator(g, s, backend="segment")
    st_l, _ = run_solver(op_l, g.num_nodes, cfg)
    assert float(jnp.max(jnp.abs(st_d.v - st_l.v))) <= TOL


@pytest.mark.distributed
def test_sharded_probe_matches_single_device(mesh):
    """Sharded SLQ == single-device SLQ (same keys, psum'd matvec)."""
    from repro.spectral import probes

    g = CASES["weighted"]()
    gp = distributed.pad_edges_for_mesh(
        g, distributed.num_edge_shards(mesh))
    key = jax.random.PRNGKey(11)
    n_real = jnp.asarray(g.num_nodes, jnp.int32)
    ps = probes.probe_edge_arrays(
        gp.src, gp.dst, gp.weight, key, n_real, num_nodes=g.num_nodes)
    pd = probes.probe_sharded_edge_arrays(
        mesh, gp.src, gp.dst, gp.weight, key, n_real,
        num_nodes=g.num_nodes)
    assert abs(float(ps.lambda_max) - float(pd.lambda_max)) <= 1e-3
    np.testing.assert_allclose(ps.ritz, pd.ritz, atol=1e-3)


# ---------------------------------------------------------------------------
# sharded streaming ticks (ServiceConfig(mesh=...))
# ---------------------------------------------------------------------------

def _service_graphs():
    g_w, _ = graphs.sbm_graph(120, 3, p_in=0.35, p_out=0.03, seed=1)
    return {
        "weighted": CASES["weighted"](),
        "capacity_padded": g_w,  # admission pads to a capacity class
        "non_aligned": CASES["non_aligned"](),
    }


@pytest.mark.distributed
def test_sharded_streaming_tick_equivalence(mesh):
    """Sharded class ticks == single-device segment ticks to <= 1e-5 on
    weighted, capacity-padded, and non-aligned graphs, for BOTH sharded
    backends; updates invalidate + rebuild the per-shard blockings and
    the compiled-program count stays one per (class, layout, bucket)."""
    from repro.stream.service import ServiceConfig, StreamingService

    common = dict(k=5, num_clusters=3, degree=7, steps_per_tick=5,
                  lr=0.3, seed=0)
    single = StreamingService(ServiceConfig(backend="segment", **common))
    shard_seg = StreamingService(ServiceConfig(
        backend="segment", mesh=mesh, **common))
    shard_pal = StreamingService(ServiceConfig(
        backend="pallas", mesh=mesh, tick_block_n=32, **common))
    svcs = (single, shard_seg, shard_pal)
    for sid, g in _service_graphs().items():
        for svc in svcs:
            svc.add_graph(sid, g)
    res = [svc.tick() for svc in svcs]
    for sid in _service_graphs():
        for r in res[1:]:
            assert abs(r[sid] - res[0][sid]) <= TOL, sid
        for svc in svcs[1:]:
            err = float(jnp.max(jnp.abs(
                svc._sessions[sid].v - single._sessions[sid].v)))
            assert err <= TOL, (sid, err)
    # shard-balanced capacities: every store divides into the mesh
    num_shards = distributed.num_edge_shards(mesh)
    for svc in (shard_seg, shard_pal):
        for sess in svc._sessions.values():
            assert sess.store.capacity % num_shards == 0
    # updates stale the per-shard layouts; ticks stay glued afterwards
    for svc in svcs:
        svc.apply_updates("weighted", [[0, 5], [1, 7]], [1.0, 1.0])
    assert shard_pal._sessions["weighted"].layout is None
    for svc in svcs:
        svc.tick()
    assert shard_pal._sessions["weighted"].layout is not None
    for svc in svcs[1:]:
        err = float(jnp.max(jnp.abs(
            svc._sessions["weighted"].v - single._sessions["weighted"].v)))
        assert err <= TOL, err
    # one compiled program per (class, layout, occupancy bucket)
    assert shard_pal.compile_count == len(
        {s.group_key for s in shard_pal._sessions.values()})


@pytest.mark.distributed
def test_sharded_edgeless_admission_ticks(mesh):
    """An edgeless session (every shard all-padding) must tick to exact
    finite panels — no NaN — on both sharded backends."""
    from repro.stream.service import ServiceConfig, StreamingService

    g = lap.make_edge_list(np.zeros((0, 2), np.int64), 40)
    for b, extra in (("segment", {}), ("pallas", {"tick_block_n": 16})):
        svc = StreamingService(ServiceConfig(
            backend=b, mesh=mesh, k=4, num_clusters=3, degree=5,
            steps_per_tick=3, seed=0, **extra))
        svc.add_graph("empty", g)
        svc.tick()
        v = np.asarray(svc._sessions["empty"].v)
        assert np.isfinite(v).all(), b


# ---------------------------------------------------------------------------
# PANEL (model) sharded ticks — one fused rows+gram collective per step
# ---------------------------------------------------------------------------

def _model_mesh(num_shards: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < num_shards:
        pytest.skip(f"needs {num_shards} devices")
    return Mesh(np.array(devs[:num_shards]).reshape(1, num_shards),
                ("data", "model"))


def _model_tick_args(g, num_shards, *, block_n=32, k=4, seed=7,
                     c=0.05, lr=0.2):
    """One-session (G=1) argument pack for build_tick_model_sharded."""
    mb = backend.build_model_sharded_blocking(
        np.asarray(g.src), np.asarray(g.dst), np.asarray(g.weight),
        g.num_nodes, num_shards, block_n=block_n)
    v = _panel(seed, g.num_nodes, k)
    args = (mb.u_local[None], mb.other[None], mb.weight[None],
            mb.chunk_block[None], mb.deg[None], v[None],
            jnp.asarray([c], jnp.float32), jnp.asarray([lr], jnp.float32),
            jnp.asarray(1, jnp.int32))
    return mb, args


@pytest.mark.distributed
def test_model_tick_sharding_invariance():
    """The panel-sharded tick is shard-count invariant: S in {2, 4, 8}
    matches S=1 to <= 1e-5 for BOTH solver methods (short horizon — the
    per-shard gram partial sums reorder float adds, ~1e-7/step)."""
    g = CASES["weighted"]()
    for method in ("mu_eg", "oja"):
        sched = program.StepSchedule(method=method, degree=3, steps=4,
                                     backend="segment")
        by_s = {}
        for s in (1, 2, 4, 8):
            if s > len(jax.devices()):
                continue
            mesh = _model_mesh(s)
            mb, args = _model_tick_args(g, s)
            tick = program.build_tick_model_sharded(
                sched, mesh, ("model",), mb.block_n, mb.num_chunks,
                mb.block_e)
            out, res = tick(*args)
            by_s[s] = (np.asarray(out), np.asarray(res))
        base_v, base_r = by_s[1]
        assert np.isfinite(base_v).all()
        for s, (v, r) in by_s.items():
            assert np.max(np.abs(v - base_v)) <= TOL, (method, s)
            np.testing.assert_allclose(r, base_r, atol=TOL)


@pytest.mark.distributed
def test_model_tick_one_fused_collective_per_step():
    """Trace-time psum accounting: the mu-EG model tick ships its row
    assembly and 2k x 2k gram in EXACTLY ONE fused (tuple) collective
    per solver step; oja has no gram form and fuses nothing.  Plain
    counts pin the rest of the budget — loop bodies trace ONCE, so the
    traced program holds: one assembly inside the dilation body, the
    final residual apply's dilation body + its own assembly, and (oja
    only) the step's plain row assembly."""
    g = CASES["weighted"]()
    mesh = _model_mesh(2)
    degree = 3
    for method, fused_want, plain_want in (
            ("mu_eg", 1, 3),
            ("oja", 0, 4)):
        sched = program.StepSchedule(method=method, degree=degree,
                                     steps=4, backend="segment")
        mb, args = _model_tick_args(g, 2)
        tick = program.build_tick_model_sharded(
            sched, mesh, ("model",), mb.block_n, mb.num_chunks,
            mb.block_e)
        with program.count_psums() as stats:
            jax.eval_shape(tick, *args)
        assert stats.fused == fused_want, method
        assert stats.plain == plain_want, method


@pytest.mark.distributed
def test_service_model_sharded_tick_equivalence():
    """model_axes serving == single-device segment serving to <= 1e-5
    on weighted / capacity-padded / non-aligned graphs, S in {2, 4, 8},
    including the admission probe routed through the row-sharded matvec
    and update-triggered layout invalidation + rebuild."""
    from repro.stream.service import ServiceConfig, StreamingService

    common = dict(k=4, num_clusters=3, degree=5, steps_per_tick=5,
                  lr=0.3, seed=0, backend="segment")
    single = StreamingService(ServiceConfig(**common))
    sharded = []
    for s in (2, 4, 8):
        if s > len(jax.devices()):
            continue
        sharded.append(StreamingService(ServiceConfig(
            mesh=_model_mesh(s), model_axes=("model",), **common)))
    assert sharded, "distributed marker guarantees >= 2 devices"
    svcs = [single] + sharded
    for sid, g in _service_graphs().items():
        for svc in svcs:
            svc.add_graph(sid, g)
    res = [svc.tick() for svc in svcs]
    for sid in _service_graphs():
        for svc, r in zip(svcs[1:], res[1:]):
            assert abs(r[sid] - res[0][sid]) <= TOL, sid
            err = float(np.max(np.abs(
                np.asarray(svc._sessions[sid].v)
                - np.asarray(single._sessions[sid].v))))
            assert err <= TOL, (sid, err)
    # updates stale the destination-aligned layouts; ticks re-glue
    for svc in svcs:
        svc.apply_updates("weighted", [[0, 5], [1, 7]], [1.0, 1.0])
    assert sharded[0]._sessions["weighted"].layout is None
    for svc in svcs:
        svc.tick()
    assert sharded[0]._sessions["weighted"].layout is not None
    for svc in svcs[1:]:
        err = float(np.max(np.abs(
            np.asarray(svc._sessions["weighted"].v)
            - np.asarray(single._sessions["weighted"].v))))
        assert err <= TOL, err
    # one compiled program per (class, degree, layout, occupancy bucket)
    assert sharded[0].compile_count == len(
        {s.group_key for s in sharded[0]._sessions.values()})


@pytest.mark.distributed
def test_million_node_model_sharded_tick():
    """Million-node acceptance row: n = 1e6, E ~ 5e7 power-law edges
    (alpha = 2.5 — the hub-skewed regime the CSR chunk layout exists
    for) admitted, planned, and ticked end-to-end through the
    panel-sharded service on the 8-virtual-device lane.  Lean knobs
    (degree budget 1, k = 3, 2 steps, probe off) keep this a wall-time
    test of the SCALE path, not of convergence."""
    from repro.stream import service as service_mod
    from repro.stream.service import ServiceConfig, StreamingService

    n = 1_000_000
    g = graphs.power_law_graph(n, avg_degree=100.0, alpha=2.5, seed=0,
                               dedup=False)
    assert g.num_edges >= 45_000_000
    num_shards = min(8, len(jax.devices()))
    svc = StreamingService(ServiceConfig(
        backend="segment", mesh=_model_mesh(num_shards),
        model_axes=("model",), probe_spectrum=False,
        k=3, num_clusters=2, degree=1, steps_per_tick=2, seed=0))
    # pin the ladder's top class outright: the default 1.5x admission
    # headroom would walk past it at 5e7 live edges
    from repro.stream import graph_store as gs
    svc.add_graph("web", g, edge_capacity=gs.CAPACITY_CLASSES[-1])
    res = svc.tick()["web"]
    assert np.isfinite(res)
    sess = svc._sessions["web"]
    # the panel lives at the node-capacity class (pow2 >= n); the real
    # graph occupies the first n rows
    v = np.asarray(sess.v)
    assert v.shape == (service_mod.node_capacity_class(n), 3)
    assert np.isfinite(v[:n]).all()
    # the layout really shards: every shard owns rows, and the skewed
    # half-edge mass spreads without any per-shard edge-balance contract
    mb = sess.layout
    assert mb.num_shards == num_shards
    assert mb.rows_per_shard * num_shards >= n
    assert mb.padded_half_edges >= 2 * g.num_edges
