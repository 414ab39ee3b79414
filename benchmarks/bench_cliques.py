"""Paper Fig. 4: clique graphs (n nodes, k cliques, 0-25 short circuits).

Includes the paper's failure regime: when rho(L) ~ clique size exceeds
~2*degree, the raw limit series folds and fails, while the beyond-paper
auto-scaled series keeps working (Sec. 5.4 hypothesis, which our
Fig. 6-style degree sweep in bench_series_degree.py also probes).
"""
from __future__ import annotations

from benchmarks.common import convergence_run, paper_transform_suite
from repro.core import graphs, spectral_radius_upper_bound


def run(steps: int = 1200):
    rows = []
    for n, k in ((300, 3), (400, 4)):
        g, _ = graphs.clique_graph(n, k, seed=0)
        rho = float(spectral_radius_upper_bound(g))
        for name, tf in paper_transform_suite(rho).items():
            lr = 2e-2 if name == "identity" else 0.4
            r = convergence_run(g, tf, "mu_eg", lr, steps, k)
            rows.append((f"cliques_n{n}_k{k}/{name}",
                         round(r["wall_s"] * 1e6 / steps, 1),
                         f"streak@{r['steps_to_streak']}"
                         f";final_streak={r['final_streak']}/{k}"
                         f";err={r['final_err']:.4f}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
