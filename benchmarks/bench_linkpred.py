"""Paper Fig. 5 / App. A.1: clustering graphs completed by
common-neighbors link prediction (weighted Laplacian)."""
from __future__ import annotations

from benchmarks.common import convergence_run, paper_transform_suite
from repro.core import graphs, linkpred, spectral_radius_upper_bound


def run(steps: int = 1000):
    rows = []
    g, _ = graphs.clique_graph(300, 3, seed=1)
    gw = linkpred.complete_graph(g, drop_prob=0.2, seed=2)
    rho = float(spectral_radius_upper_bound(gw))
    for name, tf in paper_transform_suite(rho).items():
        lr = 2e-2 if name == "identity" else 0.4
        r = convergence_run(gw, tf, "mu_eg", lr, steps, 3)
        rows.append((f"linkpred/{name}",
                     round(r["wall_s"] * 1e6 / steps, 1),
                     f"streak@{r['steps_to_streak']}"
                     f";final_streak={r['final_streak']}/3"
                     f";err={r['final_err']:.4f}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
