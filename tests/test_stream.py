"""Streaming subsystem: graph store vs rebuilt-Laplacian ground truth,
warm-start reconvergence, incremental-update fallback, label stability,
and the service's one-compiled-step-per-capacity-class invariant."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    graphs, laplacian_dense, laplacian_matvec, make_edge_list, metrics,
    operators,
)
from repro.core.kmeans import cluster_agreement
from repro.core.series import limit_neg_exp
from repro.core.laplacian import spectral_radius_upper_bound
from repro.stream import graph_store as gs
from repro.stream import tracking, updates, warm
from repro.stream.service import (
    ServiceConfig, StreamingService, UnknownSessionError,
)


# ---------------------------------------------------------------------------
# graph store
# ---------------------------------------------------------------------------

def _dense_from_dict(ref: dict, n: int) -> np.ndarray:
    l = np.zeros((n, n), np.float32)
    for (i, j), w in ref.items():
        if w == 0.0:
            continue
        l[i, i] += w
        l[j, j] += w
        l[i, j] -= w
        l[j, i] -= w
    return l


def test_edge_batches_match_rebuilt_laplacian():
    """Random insert/delete/reweight batches == ground-truth rebuild."""
    rng = np.random.default_rng(0)
    n = 12
    g = make_edge_list(np.array([[0, 1], [1, 2], [2, 3]]), n)
    ref = {(0, 1): 1.0, (1, 2): 1.0, (2, 3): 1.0}
    store = gs.from_edge_list(g, capacity=64)
    for step in range(6):
        pairs, ws = [], []
        for _ in range(5):
            i, j = sorted(rng.choice(n, size=2, replace=False).tolist())
            w = float(rng.choice([0.0, 0.5, 1.0, 2.0]))  # 0 => delete
            pairs.append((i, j))
            ws.append(w)
        batch = gs.coalesce_batch(pairs, ws, mode="set", pad_to=8)
        store, _, _ = gs.apply_edge_batch(store, batch, mode="set")
        for (i, j), w in zip(pairs, ws):
            ref[(i, j)] = w  # same last-write-wins semantics
        got = np.asarray(laplacian_dense(gs.as_edge_list(store)))
        np.testing.assert_allclose(got, _dense_from_dict(ref, n), atol=1e-6)
    # live edge count agrees too
    assert int(gs.num_edges(store)) == sum(1 for w in ref.values() if w != 0)


def test_add_mode_accumulates_and_deletes_at_zero():
    g = make_edge_list(np.array([[0, 1]]), 4)
    store = gs.from_edge_list(g, capacity=16)
    b = gs.make_edge_batch([[0, 1]], [2.0], pad_to=4)
    store, dw, _ = gs.apply_edge_batch(store, b, mode="add")
    assert float(dw[0]) == 2.0
    assert int(gs.num_edges(store)) == 1
    b = gs.make_edge_batch([[0, 1]], [-3.0], pad_to=4)
    store, dw, _ = gs.apply_edge_batch(store, b, mode="add")
    assert float(dw[0]) == -3.0
    assert int(gs.num_edges(store)) == 0  # weight hit 0 => slot freed


def test_lazy_degrees_and_radius_bound():
    g = make_edge_list(np.array([[0, 1], [1, 2]]), 4)
    store = gs.from_edge_list(g, capacity=16)
    b = gs.make_edge_batch([[2, 3]], [4.0], pad_to=4)
    store, _, _ = gs.apply_edge_batch(store, b)
    assert bool(store.deg_dirty)  # mutation only marks the cache stale
    store, rho = gs.spectral_radius_upper_bound(store)
    assert not bool(store.deg_dirty)
    np.testing.assert_allclose(float(rho), 10.0)  # node 2: deg 1+4
    exp = np.asarray(jnp.zeros(4).at[store.src].add(store.weight)
                     .at[store.dst].add(store.weight))
    np.testing.assert_allclose(np.asarray(store.deg), exp)


def test_padded_reweight_near_capacity_does_not_drop():
    """Padding/no-op batch entries must not consume free slots or count
    as drops — a reweight on a nearly-full store stays in place."""
    n = 16
    pairs = [(i, j) for i in range(n) for j in range(i + 1, n)][:14]
    g = make_edge_list(np.asarray(pairs, np.int32), n)
    store = gs.from_edge_list(g, capacity=16)  # only 2 free slots
    batch = gs.make_edge_batch([pairs[0]], [5.0], pad_to=8)  # 7 pads
    store2, dw, stats = gs.apply_edge_batch(store, batch, mode="set")
    assert int(stats.dropped) == 0
    assert int(stats.matched) == 1
    assert int(stats.inserted) == 0
    assert float(dw[0]) == 4.0
    assert int(gs.num_edges(store2)) == 14


def test_self_loops_dropped_and_padding_sentinel_safe():
    """Self-loop entries must be dropped: a live (0, 0) slot would
    collide with the padding sentinel and be silently deleted by any
    later padded batch."""
    g = make_edge_list(np.array([[0, 1]]), 4)
    store = gs.from_edge_list(g, capacity=16)
    b = gs.make_edge_batch([[0, 0], [2, 3]], [1.0, 1.0], pad_to=4)
    store, _, stats = gs.apply_edge_batch(store, b)
    assert int(gs.num_edges(store)) == 2  # (0,1) and (2,3); no (0,0)
    # a later padded batch must not disturb anything
    b2 = gs.make_edge_batch([[0, 1]], [2.0], pad_to=8)
    store, _, stats2 = gs.apply_edge_batch(store, b2)
    assert int(stats2.matched) == 1
    assert int(gs.num_edges(store)) == 2
    # coalesce path drops self loops too
    cb = gs.coalesce_batch([[3, 3], [1, 2]], [1.0, 1.0], pad_to=4)
    assert int(jnp.sum(cb.weight != 0)) == 1


def test_sparse_sbm_degenerate_blocks():
    """Size-1 blocks and zero sampled edges must still produce a valid,
    isolated-node-free graph with in-range indices."""
    for n, b in [(9, 5), (5, 5)]:
        g, labels = graphs.sparse_sbm_graph(n, b, avg_degree_in=0.0,
                                            avg_degree_out=0.0, seed=0)
        src = np.asarray(g.src)
        dst = np.asarray(g.dst)
        assert src.min() >= 0 and dst.max() < n
        present = np.zeros(n, bool)
        present[src] = True
        present[dst] = True
        assert present.all()


def test_capacity_classes_and_grow():
    assert gs.capacity_class(100) == 256
    assert gs.capacity_class(200) == 512
    g = make_edge_list(np.array([[0, 1]]), 4)
    store = gs.from_edge_list(g, capacity=256)
    grown = gs.grow(store)
    assert grown.capacity == 512
    np.testing.assert_allclose(
        np.asarray(laplacian_dense(gs.as_edge_list(grown))),
        np.asarray(laplacian_dense(gs.as_edge_list(store))), atol=1e-6)


def test_padded_store_feeds_core_operators():
    g, _ = graphs.ring_of_cliques(3, 8)
    store = gs.from_edge_list(g, capacity=256)
    v = jax.random.normal(jax.random.PRNGKey(0), (g.num_nodes, 3))
    np.testing.assert_allclose(
        np.asarray(laplacian_matvec(gs.as_edge_list(store), v)),
        np.asarray(laplacian_matvec(g, v)), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# warm-started reconvergence
# ---------------------------------------------------------------------------

def _dilated_op(g, degree=7, strength=6.0):
    rho = float(spectral_radius_upper_bound(g))
    s = limit_neg_exp(degree, scale=strength / rho)
    return operators.series_operator(s, operators.edge_matvec(g))


def test_warm_start_reconverges_faster_than_cold():
    """Perturbed SBM: warm-started session needs fewer iterations."""
    g, _ = graphs.sbm_graph(150, 3, p_in=0.3, p_out=0.02, seed=0)
    cfg = warm.WarmConfig(tol=5e-3, chunk=10, max_steps=3000, lr=0.3)
    key = jax.random.PRNGKey(0)
    op = _dilated_op(g)
    state, cold = warm.reconverge(key, op, g.num_nodes, 5, cfg, v_prev=None)
    assert cold["iterations"] > 0 and cold["residual"] <= cfg.tol
    # perturb ~1% of edges, re-solve warm from the previous panel
    rng = np.random.default_rng(1)
    e = g.num_edges
    keep = np.ones(e, bool)
    keep[rng.choice(e, size=max(e // 100, 1), replace=False)] = False
    g2 = make_edge_list(
        np.stack([np.asarray(g.src)[keep], np.asarray(g.dst)[keep]], 1),
        g.num_nodes)
    op2 = _dilated_op(g2)
    _, warm_info = warm.reconverge(key, op2, g.num_nodes, 5, cfg,
                                   v_prev=state.v)
    assert warm_info["warm"]  # restart test must accept the old panel
    assert warm_info["residual"] <= cfg.tol
    assert warm_info["iterations"] < cold["iterations"]


def test_restart_test_rejects_garbage_panel():
    g, _ = graphs.ring_of_cliques(4, 10)
    op = _dilated_op(g)
    # a panel of indicators of WRONG nodes has a large residual
    junk = jnp.eye(g.num_nodes)[:, :4]
    state, info = warm.warm_start_state(
        jax.random.PRNGKey(0), op, g.num_nodes, 4, junk,
        restart_residual=0.05)
    assert not info["warm"]


# ---------------------------------------------------------------------------
# incremental eigen-updates
# ---------------------------------------------------------------------------

def test_first_order_update_tracks_exact_eigh():
    g, _ = graphs.ring_of_cliques(3, 8)
    n, k = g.num_nodes, 4
    l0 = np.asarray(laplacian_dense(g), np.float64)
    lam0, v0 = np.linalg.eigh(l0)
    est = updates.estimate_from_panel(
        lambda v: laplacian_matvec(g, v), jnp.asarray(v0[:, :k], jnp.float32))
    np.testing.assert_allclose(np.asarray(est.lam), lam0[:k], atol=1e-4)
    # tiny reweight of one edge
    src = jnp.asarray([0], jnp.int32)
    dst = jnp.asarray([1], jnp.int32)
    dw = jnp.asarray([0.01], jnp.float32)
    est2 = updates.first_order_update(est, src, dst, dw)
    l1 = l0.copy()
    for i, j, w in [(0, 1, 0.01)]:
        l1[i, i] += w; l1[j, j] += w; l1[i, j] -= w; l1[j, i] -= w
    lam1 = np.linalg.eigh(l1)[0]
    np.testing.assert_allclose(np.asarray(est2.lam), lam1[:k], atol=1e-3)
    assert float(est2.drift) > 0


def test_fallback_triggers_at_drift_threshold():
    lam = jnp.asarray([0.0, 0.1, 0.5, 1.0])
    v = jnp.eye(8)[:, :4]
    cfg = updates.UpdateConfig(fallback_ratio=0.5)
    small = updates.EigenEstimate(lam=lam, v=v, drift=jnp.asarray(0.04))
    big = updates.EigenEstimate(lam=lam, v=v, drift=jnp.asarray(0.06))
    # min gap 0.1, threshold 0.05: drift just below vs just above
    assert not bool(updates.should_fallback(small, cfg))
    assert bool(updates.should_fallback(big, cfg))
    # drift accumulates across batches by the Frobenius bound
    src = jnp.asarray([0], jnp.int32)
    dst = jnp.asarray([1], jnp.int32)
    dw = jnp.asarray([3.0], jnp.float32)
    est2 = updates.first_order_update(small, src, dst, dw)
    np.testing.assert_allclose(float(est2.drift), 0.04 + 6.0, rtol=1e-5)
    assert bool(updates.should_fallback(est2, cfg))


def test_delta_norm_bound_covers_hub_batches():
    """The drift bound must dominate ||ΔL||_F even when batch edges share
    an endpoint (diagonal contributions stack at the hub)."""
    src = jnp.asarray([0, 0], jnp.int32)
    dst = jnp.asarray([1, 2], jnp.int32)
    dw = jnp.asarray([1.0, 1.0], jnp.float32)
    dl = np.zeros((3, 3))
    for s, d, w in [(0, 1, 1.0), (0, 2, 1.0)]:
        dl[s, s] += w; dl[d, d] += w; dl[s, d] -= w; dl[d, s] -= w
    true_norm = np.linalg.norm(dl)  # sqrt(10) ≈ 3.162
    bound = float(updates.delta_norm_bound(dw))
    assert bound >= true_norm - 1e-6, (bound, true_norm)


# ---------------------------------------------------------------------------
# label tracking
# ---------------------------------------------------------------------------

def test_label_tracking_stable_under_permutation_and_noop():
    rng = np.random.default_rng(0)
    labels = jnp.asarray(rng.integers(0, 3, size=40))
    tracker = tracking.LabelTracker(3)
    first = tracker.update(labels)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(labels))
    # a re-solve that permutes cluster ids must map back to stable ids
    perm = jnp.asarray([2, 0, 1])
    relabelled = perm[labels]
    stable = tracker.update(relabelled)
    np.testing.assert_array_equal(np.asarray(stable), np.asarray(labels))
    # and a genuine no-op update keeps ids verbatim
    stable2 = tracker.update(stable)
    np.testing.assert_array_equal(np.asarray(stable2), np.asarray(labels))


# ---------------------------------------------------------------------------
# service
# ---------------------------------------------------------------------------

SVC_CFG = ServiceConfig(k=4, num_clusters=3, degree=7, steps_per_tick=25,
                        lr=0.3, tol=5e-3, dilation_strength=6.0)


@pytest.fixture(scope="module")
def eight_session_service():
    svc = StreamingService(SVC_CFG)
    truths = {}
    for i in range(8):
        g, lab = graphs.sbm_graph(60, 3, p_in=0.4, p_out=0.02, seed=i)
        svc.add_graph(f"g{i}", g, num_clusters=3, edge_capacity=1024)
        truths[f"g{i}"] = lab
    svc.run_until_converged(max_ticks=120)
    return svc, truths


def test_service_eight_sessions_share_group_logarithmic_compiles(
        eight_session_service):
    svc, truths = eight_session_service
    # all 8 sessions share ONE (capacity class, degree) tick group for
    # the entire lifecycle — per-session lr/scale and the scheduler's
    # tick multiplier are traced, so no per-session (or per-multiplier)
    # programs ever compile.  Distinct compiles only along the pow2
    # occupancy buckets (groups shrink as sessions converge — converged
    # sessions cost zero device work): <= 1 + log2(8).
    group_keys = {key for key, _ in svc._compiled}
    assert len(group_keys) == 1
    occs = {occ for _, occ in svc._compiled}
    assert all(occ == 1 << (occ.bit_length() - 1) for occ in occs)
    assert max(occs) <= 8
    assert svc.compile_count <= 4
    for sid in truths:
        assert svc.session_info(sid)["converged"], sid


def test_service_labels_recover_communities(eight_session_service):
    svc, truths = eight_session_service
    agree = [
        float(cluster_agreement(jnp.asarray(svc.labels(sid)),
                                jnp.asarray(truths[sid]), 3))
        for sid in truths
    ]
    assert np.mean(agree) > 0.9, agree


def test_service_noop_update_keeps_labels_and_convergence(
        eight_session_service):
    svc, truths = eight_session_service
    before = svc.labels("g0")
    # rewrite an existing edge to its current weight: realized dw == 0
    src, dst, w = svc.live_edges("g0")
    stats = svc.apply_updates("g0", [[int(src[0]), int(dst[0])]],
                              [float(w[0])], mode="set")
    info = svc.session_info("g0")
    assert int(stats.matched) == 1
    assert info["converged"]  # no-op must not trigger a re-solve
    assert info["fallbacks"] == 0
    after = svc.labels("g0")
    np.testing.assert_array_equal(np.asarray(before), np.asarray(after))


def test_service_update_fallback_and_warm_reconverge(eight_session_service):
    svc, truths = eight_session_service
    src, dst, _ = svc.live_edges("g1")
    rng = np.random.default_rng(2)
    sel = rng.choice(len(src), size=len(src) // 4, replace=False)
    stats = svc.apply_updates(
        "g1", np.stack([src[sel], dst[sel]], 1), np.zeros(len(sel)),
        mode="set")
    info = svc.session_info("g1")
    assert info["fallbacks"] == 1 and not info["converged"]
    ticks_before = info["ticks"]
    svc.run_until_converged(max_ticks=120)
    info = svc.session_info("g1")
    assert info["converged"]
    # warm restart: reconvergence is no costlier than the cold admission
    # solve despite the 25% perturbation (the >=3x iteration saving at 1%
    # perturbation is asserted by benchmarks/bench_stream.py, where the
    # tick granularity can resolve it)
    assert info["ticks"] - ticks_before <= ticks_before
    # the whole update/reconverge cycle stayed inside the one (class,
    # degree) tick group — no per-session or per-update recompiles,
    # only pow2 occupancy buckets
    assert len({key for key, _ in svc._compiled}) == 1


def test_service_buffer_overflow_grows_capacity_class():
    svc = StreamingService(dataclasses.replace(SVC_CFG, steps_per_tick=5))
    g, _ = graphs.ring_of_cliques(3, 6)
    svc.add_graph("tiny", g, num_clusters=3, edge_capacity=64)
    n = g.num_nodes
    pairs = [(i, j) for i in range(n) for j in range(i + 1, n)]
    stats = svc.apply_updates("tiny", pairs, np.full(len(pairs), 0.5),
                              mode="set")
    info = svc.session_info("tiny")
    assert info["edge_capacity"] == 256  # grew to the next ladder class
    assert int(stats.dropped) == 0
    # the near-complete reweighted graph has no cluster structure, so we
    # assert growth correctness, not clustering convergence, here
    assert info["num_edges"] == len(pairs)


def test_service_overflow_grows_multiple_classes_without_loss():
    """A batch bigger than one ladder step keeps growing until nothing
    drops — no silent edge loss."""
    svc = StreamingService(dataclasses.replace(SVC_CFG, steps_per_tick=5))
    g, _ = graphs.ring_of_cliques(4, 10)  # n=40, 184 edges
    svc.add_graph("burst", g, num_clusters=3, edge_capacity=256)
    n = g.num_nodes
    pairs = [(i, j) for i in range(n) for j in range(i + 1, n)]  # 780
    stats = svc.apply_updates("burst", pairs, np.full(len(pairs), 0.5),
                              mode="set")
    info = svc.session_info("burst")
    assert int(stats.dropped) == 0
    assert info["edge_capacity"] == 1024  # 256 -> 512 -> 1024 (two steps)
    assert info["num_edges"] == len(pairs)


def test_add_graph_rejects_underprovisioned_k():
    svc = StreamingService(SVC_CFG)  # k=4, drop_trivial=True
    g, _ = graphs.ring_of_cliques(3, 6)
    with pytest.raises(ValueError, match="tracked"):
        svc.add_graph("bad", g, num_clusters=4)  # needs 5 > k=4


@pytest.mark.parametrize("backend", ["segment", "pallas"])
def test_edgeless_admission_recovers_after_updates(backend):
    """Regression: a graph admitted with zero edges has rho == rho_ub
    == 0; the ratio-based rho rescale must re-anchor on the fresh bound
    when edges arrive instead of pinning rho at 0 forever (which blew
    the dilation scale up to ~1/eps and NaN'd the panel)."""
    from repro.core.laplacian import make_edge_list

    svc = StreamingService(dataclasses.replace(
        SVC_CFG, steps_per_tick=5, backend=backend, tick_block_n=32))
    g0 = make_edge_list(np.zeros((0, 2), np.int64), 40)
    svc.add_graph("empty", g0, num_clusters=3, edge_capacity=256)
    svc.apply_updates("empty", [[0, 1], [1, 2], [2, 3], [3, 0]],
                      [1.0, 1.0, 1.0, 1.0])
    res = svc.tick()["empty"]
    sess = svc._sessions["empty"]
    assert np.isfinite(res)
    assert sess.rho > 0.0
    assert bool(jnp.all(jnp.isfinite(sess.v)))


@pytest.mark.parametrize("backend", ["segment", "pallas"])
def test_operator_source_feeds_the_tick_program(backend):
    """The service's one operator source gives the group key's layout
    statics and the operands that ``program.build_tick_program(
    **tick_args)`` takes, and that program ticks like the service; an
    edge batch drops the session's one layout, and the next tick
    rebuilds it where the tick reads one."""
    from repro.core import program

    svc = StreamingService(dataclasses.replace(
        SVC_CFG, steps_per_tick=2, backend=backend, tick_block_n=32,
        tick_schedule="round_robin"))
    g, _ = graphs.ring_of_cliques(3, 6)
    svc.add_graph("a", g, num_clusters=3)
    source, sess = svc._source, svc._sessions["a"]
    blocked = backend == "pallas"
    assert source.blocked == blocked
    svc.tick()
    assert (sess.layout is not None) == blocked
    key = sess.group_key
    assert len(key) == (5 if blocked else 2)
    assert key[2:] == source.statics(sess.layout)
    ops = source.operands(sess)
    i32, f32 = jnp.dtype(jnp.int32), jnp.dtype(jnp.float32)
    assert [o.dtype for o in ops] == (
        [i32, i32, f32, i32, f32] if blocked else [i32, i32, f32])
    tick = program.build_tick_program(
        program.StepSchedule(method=svc.cfg.method, degree=key[1],
                             steps=svc.cfg.steps_per_tick,
                             backend=svc.backend),
        **source.tick_args(key[2:]))
    vs, res = tick(*(o[None] for o in ops), sess.v[None],
                   jnp.asarray([program.dilation_scale(sess.plan, key[1])],
                               jnp.float32),
                   jnp.asarray([sess.lr], jnp.float32),
                   jnp.ones((1,), jnp.int32))
    assert not sess.converged
    want = svc.tick()["a"]
    np.testing.assert_allclose(np.asarray(vs[0]), np.asarray(sess.v),
                               atol=1e-6)
    np.testing.assert_allclose(float(res[0]), want, rtol=1e-5)
    svc.apply_updates("a", [[0, 7]], [1.0])
    assert sess.layout is None
    assert not sess.converged
    svc.tick()
    assert (sess.layout is not None) == blocked
    assert svc.compile_count == 1


# ---------------------------------------------------------------------------
# typed session errors, converged re-entry, per-session multipliers
# ---------------------------------------------------------------------------

def test_unknown_session_raises_typed_error():
    """Unknown/evicted sids raise UnknownSessionError (a KeyError
    subclass, so pre-typed callers keep working) from every session
    accessor — and evict is NOT idempotent."""
    svc = StreamingService(dataclasses.replace(SVC_CFG, steps_per_tick=5))
    g, _ = graphs.ring_of_cliques(3, 6)
    svc.add_graph("here", g, num_clusters=3)
    for fn in (svc.labels, svc.session_info, svc.evict, svc.panel,
               svc.live_edges):
        with pytest.raises(UnknownSessionError, match="never"):
            fn("never")
    with pytest.raises(UnknownSessionError):
        svc.apply_updates("never", [[0, 1]], [1.0])
    assert issubclass(UnknownSessionError, KeyError)
    summary = svc.evict("here")  # first evict succeeds...
    assert summary["n"] == g.num_nodes
    with pytest.raises(UnknownSessionError, match="here"):
        svc.evict("here")  # ...the double evict reports the id as gone
    with pytest.raises(UnknownSessionError, match="here"):
        svc.labels("here")


def test_converged_session_reenters_ticking_after_update():
    """Regression: an edge batch that moves a CONVERGED session's
    residual back above tolerance must re-enter it into its tick group
    on the next tick() — before the fix the first-order update path
    marked the panel patched and the session stayed 'converged' with a
    stale residual forever (no fallback, no ticks)."""
    cfg = dataclasses.replace(SVC_CFG, steps_per_tick=25, tol=5e-4)
    svc = StreamingService(cfg)
    g, _ = graphs.sbm_graph(60, 3, p_in=0.4, p_out=0.02, seed=3)
    svc.add_graph("s", g, num_clusters=3, edge_capacity=1024)
    assert svc.run_until_converged(max_ticks=400) < 400
    info = svc.session_info("s")
    assert info["converged"] and info["residual"] <= cfg.tol
    # a small real perturbation: two weak cross-community edges, well
    # under the drift bound (2*sum|dw| = 0.08 << 0.5 * ~0.44 min gap)
    # so the first-order path handles it, yet the patched panel's
    # re-measured residual lands back above the tight tolerance
    svc.apply_updates("s", [[0, 25], [5, 30]], [0.02, 0.02], mode="add")
    info = svc.session_info("s")
    assert info["fallbacks"] == 0  # cheap path, not a re-solve
    assert not info["converged"]  # re-entered: residual re-measured
    assert info["residual"] > cfg.tol
    ticks_before = info["ticks"]
    assert svc.run_until_converged(max_ticks=400) < 400
    info = svc.session_info("s")
    assert info["converged"] and info["ticks"] > ticks_before


def test_mixed_contraction_group_schedules_per_session():
    """Regression: the residual-decay multiplier is PER SESSION — a
    near-converged member no longer drags far-from-converged peers in
    the same tick group down to multiplier 1 (the old group-min)."""
    cfg = dataclasses.replace(SVC_CFG, steps_per_tick=5,
                              max_tick_multiplier=8, eval_payoff=2.0)
    svc = StreamingService(cfg)
    for i, sid in enumerate(("near", "far")):
        g, _ = graphs.sbm_graph(60, 3, p_in=0.4, p_out=0.02, seed=40 + i)
        svc.add_graph(sid, g, num_clusters=3, edge_capacity=1024)
    near, far = svc._sessions["near"], svc._sessions["far"]
    # pin the forecasts: 'near' is one plain tick from tolerance,
    # 'far' needs far more than eval_payoff plain ticks
    near.residual, near.rate = cfg.tol * 1.5, 0.8
    far.residual, far.rate = 0.5, 0.995
    mults = svc._tick_multipliers([near, far])
    assert mults[0] == 1  # the old min() would have forced BOTH to 1
    assert mults[1] == cfg.max_tick_multiplier
    before = svc.multiplied_ticks
    svc.tick()
    # the mixed group still counted as a multiplied (stretched) tick,
    # through the one shared compiled program
    assert svc.multiplied_ticks == before + 1
    assert len({key for key, _ in svc._compiled}) == 1
