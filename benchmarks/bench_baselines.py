"""Beyond-paper comparison vs related work (paper App. B): SPED vs Bethe
Hessian (Saade et al. 2014) vs shift-and-invert (Garber et al. 2016) on
SBM community detection.  The paper cites both but compares against
neither; we do.

Cost accounting: shift-and-invert pays `cg_iters` Laplacian matvecs per
operator application (a linear solve), SPED pays `degree` matvecs of a
FIXED polynomial — same O() primitive, but SPED's is embarrassingly
parallel and unbiased under minibatching (the paper's §4.3 point).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import (SolverConfig, laplacian_dense, limit_neg_exp,
                        run_solver, spectral_radius_upper_bound)
from repro.core import baselines, graphs, metrics, operators
from repro.core.kmeans import cluster_agreement, kmeans


def _cluster_from_vecs(vecs, k, truth):
    emb = vecs[:, 1: k + 1] if vecs.shape[1] > k else vecs[:, :k]
    emb = emb / jnp.maximum(jnp.linalg.norm(emb, axis=1, keepdims=True),
                            1e-12)
    labels = kmeans(jax.random.PRNGKey(1), emb, k).labels
    return float(cluster_agreement(labels, jnp.asarray(truth), k))


def run():
    g, truth = graphs.sbm_graph(240, 3, p_in=0.2, p_out=0.01, seed=0)
    L = laplacian_dense(g)
    k = 3
    rho = float(spectral_radius_upper_bound(g))
    rows = []

    # SPED (limit series + mu-EG)
    s = limit_neg_exp(151, scale=8.0 / rho)
    op = operators.series_operator(s, operators.dense_matvec(L))
    cfg = SolverConfig(method="mu_eg", lr=0.4, steps=500, eval_every=100,
                       k=k + 1)
    t0 = time.perf_counter()
    state, tr = run_solver(op, g.num_nodes, cfg)
    dt = time.perf_counter() - t0
    acc = _cluster_from_vecs(state.v, k, truth)
    rows.append(("baselines/sped_limit151", round(dt * 1e6 / cfg.steps, 1),
                 f"acc={acc:.3f};matvecs_per_step={s.degree}"))

    # shift-and-invert (CG inner solves)
    op_si = baselines.shift_invert_operator(
        operators.dense_matvec(L), shift=0.05, cg_iters=50)
    cfg_si = SolverConfig(method="oja", lr=0.5, steps=300, eval_every=100,
                          k=k + 1)
    t0 = time.perf_counter()
    state_si, _ = run_solver(op_si, g.num_nodes, cfg_si)
    dt = time.perf_counter() - t0
    acc = _cluster_from_vecs(state_si.v, k, truth)
    rows.append(("baselines/shift_invert_cg50",
                 round(dt * 1e6 / cfg_si.steps, 1),
                 f"acc={acc:.3f};matvecs_per_step=50"))

    # Bethe Hessian (direct eigendecomposition; not stochastic)
    t0 = time.perf_counter()
    labels, info = baselines.bethe_hessian_cluster(g, k)
    dt = time.perf_counter() - t0
    acc = float(cluster_agreement(labels, jnp.asarray(truth), k))
    rows.append(("baselines/bethe_hessian_eigh", round(dt * 1e6, 1),
                 f"acc={acc:.3f};r={info['r']:.2f}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
