"""Paper Figs. 1-3: 3-room MDP proto-value functions.

Longest eigenvector streak + subspace error vs steps, for mu-EG and Oja,
across the transform suite.  Reduced size (s=1) for CPU wall time; the
qualitative claim (series transform accelerates by ~an order of
magnitude) is asserted by tests/test_solvers.py as well.
"""
from __future__ import annotations

from benchmarks.common import convergence_run, paper_transform_suite, time_call
from repro.core import graphs, laplacian_dense, spectral_radius_upper_bound
from repro.core import operators


def run(k: int = 6, steps: int = 1500):
    g, _ = graphs.three_room_mdp(s=1, h=10)
    rho = float(spectral_radius_upper_bound(g))
    rows = []
    for name, tf in paper_transform_suite(rho, degree=151).items():
        for method in ("mu_eg", "oja"):
            lr = 2e-2 if name == "identity" else 0.4
            r = convergence_run(g, tf, method, lr, steps, k)
            op = operators.series_operator(
                tf, operators.dense_matvec(laplacian_dense(g)))
            import jax.numpy as jnp
            import jax
            v = jax.random.normal(jax.random.PRNGKey(0), (g.num_nodes, k))
            us = time_call(jax.jit(op), v, iters=3)
            rows.append((f"mdp/{name}/{method}", us,
                         f"streak@{r['steps_to_streak']}"
                         f";err1pct@{r['steps_to_1pct']}"
                         f";final_streak={r['final_streak']}/{k}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
