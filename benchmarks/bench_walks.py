"""Paper Sec. 4.3: stochastic walk estimator — throughput (walks/s) and
relative error of L^2 estimates, rejection (paper) vs importance
weighting (beyond-paper), plus acceptance rate of the Eq. 14 coin."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call
from repro.core import build_edge_incidence, laplacian_dense
from repro.core import graphs, walks


def run():
    g, _ = graphs.clique_graph(200, 4, seed=0)
    inc = build_edge_incidence(g)
    L = np.asarray(laplacian_dense(g))
    want = L @ L
    rows = []
    w = 20000
    sample = jax.jit(lambda k: walks.sample_walks(k, inc, w, 3))
    us = time_call(sample, jax.random.PRNGKey(0), iters=3)
    rows.append(("walks/sample_20k_len3", round(us, 1),
                 f"walks_per_s={w / (us / 1e6):.3g}"))
    wb = sample(jax.random.PRNGKey(1))
    for mode in ("importance", "rejection"):
        est = walks.estimate_power_dense(
            wb, g, inc, 2, g.num_nodes, mode=mode,
            key=jax.random.PRNGKey(2) if mode == "rejection" else None)
        rel = float(np.linalg.norm(np.asarray(est) - want)
                    / np.linalg.norm(want))
        fn = jax.jit(lambda v, m=mode: walks.estimate_power_matvec(
            wb, g, inc, 2, v, mode=m,
            key=jax.random.PRNGKey(2) if m == "rejection" else None))
        v = jnp.ones((g.num_nodes, 8))
        us = time_call(fn, v, iters=3)
        rows.append((f"walks/estimate_L2_{mode}", round(us, 1),
                     f"rel_err={rel:.3g}"))
    # acceptance rate of the paper's rejection coin
    log_pmin = -2 * np.log(inc.deg_star_inc) - np.log(g.num_edges)
    p_acc = np.exp(np.minimum(log_pmin - np.asarray(wb.logp[:, 1]), 0.0))
    rows.append(("walks/rejection_acceptance", 0.0,
                 f"mean_acc={float(p_acc.mean()):.3g}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
