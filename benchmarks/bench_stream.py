"""Streaming service benchmarks: graph-store update throughput,
iterations-to-reconverge (warm + dilation vs cold) on a >=10k-node SBM,
and the residual-decay tick scheduler vs round-robin on a mixed fleet.

The headline claims:
  * warm + dilation: after a 1% edge perturbation, warm-starting the
    previous eigenvector panel against the dilated operator reconverges
    in >= 3x fewer solver iterations than a cold solve (in practice far
    more);
  * scheduled ticks: on a fleet mixing fast- and slow-converging SBM
    tenants, forecasting each group's remaining steps from measured
    residual decay (ServiceConfig(tick_schedule="residual_decay"))
    reaches fleet convergence in a fraction of round-robin's compiled
    tick invocations — skipping the no-payoff intermediate residual
    evaluations and host round-trips — at equal per-tenant quality.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call, write_bench_json
from repro.core import graphs, make_edge_list, operators
from repro.core.kmeans import cluster_agreement
from repro.core.laplacian import spectral_radius_upper_bound
from repro.core.series import limit_neg_exp
from repro.stream import graph_store as gs
from repro.stream import warm
from repro.stream.service import ServiceConfig, StreamingService

N_NODES = 10_000
N_BLOCKS = 10
K = 8
DEGREE = 15
STRENGTH = 8.0
BATCH = 256

# mixed-fleet scheduler comparison
FLEET_N = 200
FLEET_FAST = 4  # well-separated tenants (few ticks to tolerance)
FLEET_SLOW = 4  # weak-structure tenants (many ticks to tolerance)
FLEET_CFG = ServiceConfig(
    k=6, num_clusters=4, degree=15, steps_per_tick=5, lr=0.3,
    tol=2e-3, dilation_strength=8.0, max_tick_multiplier=16, seed=0)


def _dilated_op(g):
    rho = float(spectral_radius_upper_bound(g))
    s = limit_neg_exp(DEGREE, scale=STRENGTH / rho)
    return operators.series_operator(s, operators.edge_matvec(g))


def _perturb_one_percent(g, seed=1):
    """Delete E/200 random edges and insert E/200 random new ones —
    1% of the edge set churned."""
    rng = np.random.default_rng(seed)
    e = g.num_edges
    m = max(e // 200, 1)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    keep = np.ones(e, bool)
    keep[rng.choice(e, size=m, replace=False)] = False
    add = np.sort(
        rng.integers(0, g.num_nodes, size=(m, 2)).astype(np.int32), axis=1)
    add = add[add[:, 0] != add[:, 1]]
    edges = np.concatenate(
        [np.stack([src[keep], dst[keep]], 1), add], axis=0)
    edges = np.unique(edges, axis=0)
    return make_edge_list(edges, g.num_nodes), 2 * m


def _fleet_graphs():
    """FLEET_FAST well-separated + FLEET_SLOW weak-structure tenants —
    the mixed convergence-rate fleet the scheduler is built for."""
    out = []
    for i in range(FLEET_FAST):
        g, lab = graphs.sbm_graph(FLEET_N, 4, p_in=0.35, p_out=0.01,
                                  seed=i)
        out.append((f"fast{i}", g, lab))
    for i in range(FLEET_SLOW):
        g, lab = graphs.sbm_graph(FLEET_N, 4, p_in=0.12, p_out=0.04,
                                  seed=100 + i)
        out.append((f"slow{i}", g, lab))
    return out


def _run_fleet(schedule: str, fleet, max_ticks: int = 600):
    svc = StreamingService(
        dataclasses.replace(FLEET_CFG, tick_schedule=schedule))
    for sid, g, _ in fleet:
        svc.add_graph(sid, g, edge_capacity=8192)
    t0 = time.perf_counter()
    svc.run_until_converged(max_ticks=max_ticks)
    wall = time.perf_counter() - t0
    agree = float(np.mean([
        cluster_agreement(jnp.asarray(svc.labels(sid)), jnp.asarray(lab),
                          FLEET_CFG.num_clusters)
        for sid, _, lab in fleet]))
    residuals = {sid: svc.session_info(sid)["residual"]
                 for sid, _, _ in fleet}
    return svc, wall, agree, residuals


def run():
    rows = []

    # -- residual-decay tick scheduler vs round-robin --------------------
    fleet = _fleet_graphs()
    results = {}
    for schedule in ("round_robin", "residual_decay"):
        svc, wall, agree, residuals = _run_fleet(schedule, fleet)
        results[schedule] = dict(
            wall_s=wall, agreement=agree,
            tick_invocations=svc.tick_invocations,
            device_work_steps=svc.device_work,
            converged=svc.all_converged,
            max_residual=max(residuals.values()))
        rows.append((
            f"stream/fleet{FLEET_FAST + FLEET_SLOW}_{schedule}",
            wall * 1e6,
            f"invocations={svc.tick_invocations};"
            f"device_steps={svc.device_work};"
            f"agreement={agree:.3f};converged={svc.all_converged}"))
        assert svc.all_converged
        assert max(residuals.values()) <= FLEET_CFG.tol
    tick_speedup = (results["round_robin"]["tick_invocations"]
                    / max(results["residual_decay"]["tick_invocations"], 1))
    wall_speedup = (results["round_robin"]["wall_s"]
                    / max(results["residual_decay"]["wall_s"], 1e-9))
    g, _ = graphs.sparse_sbm_graph(
        N_NODES, N_BLOCKS, avg_degree_in=10.0, avg_degree_out=1.0, seed=0)
    e = g.num_edges

    # -- graph store: batched update throughput --------------------------
    store = gs.from_edge_list(g)
    rng = np.random.default_rng(0)
    sel = rng.choice(e, size=BATCH, replace=False)
    pairs = np.stack([np.asarray(g.src)[sel], np.asarray(g.dst)[sel]], 1)
    batch = gs.make_edge_batch(pairs, rng.random(BATCH).astype(np.float32))
    us = time_call(
        lambda s, b: gs.apply_edge_batch(s, b)[0].weight, store, batch)
    rows.append((
        f"stream/apply_edge_batch_b{BATCH}_cap{store.capacity}", us,
        f"updates_per_s={BATCH / us * 1e6:.0f}"))

    # -- cold solve to tolerance -----------------------------------------
    cfg = warm.WarmConfig(tol=5e-3, chunk=10, max_steps=5000, lr=0.3)
    op = _dilated_op(g)
    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    state, cold = warm.reconverge(key, op, g.num_nodes, K, cfg, v_prev=None)
    cold_wall = time.perf_counter() - t0
    rows.append((
        f"stream/cold_solve_n{N_NODES}_e{e}", cold_wall * 1e6,
        f"iters={cold['iterations']};residual={cold['residual']:.1e}"))

    # -- warm + dilation reconverge after 1% churn -----------------------
    g2, churned = _perturb_one_percent(g)
    op2 = _dilated_op(g2)
    t0 = time.perf_counter()
    _, winfo = warm.reconverge(key, op2, g.num_nodes, K, cfg,
                               v_prev=state.v)
    warm_wall = time.perf_counter() - t0
    speedup = cold["iterations"] / max(winfo["iterations"], cfg.chunk)
    rows.append((
        f"stream/warm_reconverge_churn{churned}", warm_wall * 1e6,
        f"iters={winfo['iterations']};warm={winfo['warm']};"
        f"iter_speedup={speedup:.1f}x"))
    assert winfo["residual"] <= cfg.tol
    write_bench_json(
        "stream", rows,
        extra={"config": {"n_nodes": N_NODES, "n_blocks": N_BLOCKS, "k": K,
                          "degree": DEGREE, "strength": STRENGTH,
                          "batch": BATCH},
               "iter_speedup_warm_vs_cold": speedup,
               "fleet": {
                   "n": FLEET_N, "fast": FLEET_FAST, "slow": FLEET_SLOW,
                   "round_robin": results["round_robin"],
                   "residual_decay": results["residual_decay"],
               },
               "tick_speedup_scheduled_vs_round_robin": tick_speedup,
               "wall_speedup_scheduled_vs_round_robin": wall_speedup})
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, derived in run():
        print(f"{name},{us:.0f},{derived}")
