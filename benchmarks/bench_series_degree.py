"""Paper Fig. 6 / App. A.2: accuracy of the series approximation vs
degree (11/51/151/251).  Reproduces the claim that insufficient degree
fails to accelerate, and that the limit approximation dominates the
Taylor forms; adds the beyond-paper scaled/chebyshev variants that fix
the low-degree failures."""
from __future__ import annotations

from benchmarks.common import convergence_run
from repro.core import (graphs, limit_neg_exp, spectral_radius_upper_bound,
                        taylor_log, taylor_neg_exp)
from repro.core.series import cheb_neg_exp


def run(steps: int = 900):
    g, _ = graphs.clique_graph(300, 3, seed=0)
    rho = float(spectral_radius_upper_bound(g))
    k = 3
    rows = []
    series = []
    for d in (11, 51, 151, 251):
        series.append((f"limit_neg_exp_d{d}", limit_neg_exp(d)))
        series.append((f"taylor_neg_exp_d{d}", taylor_neg_exp(d)))
    series.append(("limit_d51_scaled(beyond)",
                   limit_neg_exp(51, scale=8.0 / rho)))
    series.append(("cheb_d16(beyond)", cheb_neg_exp(16, rho=rho, tau=8.0 / rho)))
    for name, tf in series:
        r = convergence_run(g, tf, "mu_eg", 0.4, steps, k)
        rows.append((f"series_degree/{name}",
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
