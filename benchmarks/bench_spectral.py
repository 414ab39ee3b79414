"""Spectral probing & dilation-planner benchmark: probe cost vs solver
iterations saved.

For each graph family, three dilation configurations solve the same
bottom-k problem to the same panel-residual tolerance from the same
random init:

  * oracle  — plan_dilation fed the EXACT spectrum (eigh): the best the
              planner's decision rule can do, at zero probe noise.
  * planned — plan_dilation fed the SLQ probe (what production runs).
  * fixed   — the pre-planner repo default: limit_neg_exp(15) scaled by
              strength 8 over the Gershgorin 2*max-degree bound.

Headline claims (tracked in BENCH_spectral.json):
  * planner-tuned dilation reaches tolerance in <= 1.1x the oracle's
    solver iterations on >= 3 families;
  * the fixed config is >= 2x worse than the oracle on >= 1 family;
  * total probe cost (single-vector matvecs) stays < 10% of the
    planned-path solve cost (panel-column matvecs).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import write_bench_json
from repro.core import graphs, operators, solvers
from repro.core.laplacian import laplacian_dense, spectral_radius_upper_bound
from repro.core.series import limit_neg_exp
from repro.spectral import (plan_dilation, probe_from_eigenvalues,
                            probe_graph, series_from_plan)
from repro.stream import warm

K = 6  # eigenvector panel width (trivial + clusters + slack)
BUDGET = 96
TOL = 5e-3
LR = 0.4
CHUNK = 5
MAX_STEPS = 4000
NUM_PROBES = 4
NUM_STEPS = 24
FIXED_DEGREE = 15  # the streaming service's pre-planner defaults
FIXED_STRENGTH = 8.0


def _families():
    return {
        "ring_of_cliques": graphs.ring_of_cliques(6, 20)[0],
        "sbm": graphs.sbm_graph(300, 4, p_in=0.3, p_out=0.05, seed=0)[0],
        "sbm_sparse": graphs.sparse_sbm_graph(
            600, 4, avg_degree_in=8.0, avg_degree_out=2.0, seed=0)[0],
        "three_room_mdp": graphs.three_room_mdp(s=2)[0],
    }


def _iters_to_tol(series, g, key, lr=LR):
    """Solver iterations for one (series, graph) from a fixed init."""
    op = operators.series_operator(series, operators.edge_matvec(g))
    state = solvers.init_state(key, g.num_nodes, K)
    cfg = warm.WarmConfig(tol=TOL, chunk=CHUNK, max_steps=MAX_STEPS, lr=lr)
    t0 = time.perf_counter()
    _, used, res = warm.run_to_tolerance(op, state, cfg)
    return used, float(res), time.perf_counter() - t0


def _plan_dict(plan):
    return {
        "family": plan.family,
        "degree": plan.degree,
        "tau": plan.tau,
        "rho": plan.rho,
        "gamma": plan.gamma,
        "source": plan.source,
    }


def run():
    rows = []
    fam_results = {}
    total_probe_matvecs = 0
    total_solve_matvecs = 0
    key = jax.random.PRNGKey(0)
    for name, g in _families().items():
        lam = np.linalg.eigvalsh(np.asarray(laplacian_dense(g)))
        rho_ub = float(spectral_radius_upper_bound(g))

        oracle_plan = plan_dilation(
            probe_from_eigenvalues(lam), k=K, budget=BUDGET, source="oracle")
        probe = probe_graph(g, key=key, num_probes=NUM_PROBES,
                            num_steps=NUM_STEPS)
        planned_plan = plan_dilation(probe, k=K, budget=BUDGET,
                                     rho_fallback=rho_ub)
        fixed_series = limit_neg_exp(
            FIXED_DEGREE, scale=FIXED_STRENGTH / rho_ub)

        runs = {}
        init_key = jax.random.fold_in(key, g.num_nodes)
        for tag, series, lr in [
            ("oracle", series_from_plan(oracle_plan),
             oracle_plan.suggested_lr(LR)),
            ("planned", series_from_plan(planned_plan),
             planned_plan.suggested_lr(LR)),
            ("fixed", fixed_series, LR),
        ]:
            iters, res, wall = _iters_to_tol(series, g, init_key, lr=lr)
            runs[tag] = {"iters": iters, "residual": res, "wall_s": wall,
                         "converged": res <= TOL}

        # Ratios on iteration counts; the chunked residual check floors
        # counts at CHUNK so 0-iteration warm cases cannot divide by 0.
        base = max(runs["oracle"]["iters"], CHUNK)
        planned_ratio = max(runs["planned"]["iters"], CHUNK) / base
        fixed_ratio = max(runs["fixed"]["iters"], CHUNK) / base
        probe_matvecs = int(probe.num_matvecs)
        solve_matvecs = runs["planned"]["iters"] * planned_plan.degree * K
        total_probe_matvecs += probe_matvecs
        total_solve_matvecs += solve_matvecs

        fam_results[name] = {
            "n": g.num_nodes,
            "num_edges": g.num_edges,
            "k": K,
            "lambda_max_exact": float(lam[-1]),
            "lambda_max_slq": float(probe.lambda_max),
            "rho_gershgorin": rho_ub,
            "plans": {
                "oracle": _plan_dict(oracle_plan),
                "planned": _plan_dict(planned_plan),
                "fixed": {"family": "limit_neg_exp", "degree": FIXED_DEGREE,
                          "tau": FIXED_STRENGTH, "rho": rho_ub,
                          "source": "fixed"},
            },
            "runs": runs,
            "planned_vs_oracle": planned_ratio,
            "fixed_vs_oracle": fixed_ratio,
            "probe_matvecs": probe_matvecs,
            "solve_matvecs_planned": solve_matvecs,
        }
        rows.append((
            f"spectral/{name}_n{g.num_nodes}",
            runs["planned"]["wall_s"] * 1e6,
            f"iters_oracle={runs['oracle']['iters']};"
            f"iters_planned={runs['planned']['iters']};"
            f"iters_fixed={runs['fixed']['iters']};"
            f"planned_vs_oracle={planned_ratio:.2f};"
            f"fixed_vs_oracle={fixed_ratio:.2f}",
        ))

    probe_cost_fraction = total_probe_matvecs / max(total_solve_matvecs, 1)
    acceptance = {
        "families_planned_within_1p1x_oracle": sum(
            1 for f in fam_results.values() if f["planned_vs_oracle"] <= 1.1),
        "num_families": len(fam_results),
        "fixed_at_least_2x_worse_somewhere": any(
            f["fixed_vs_oracle"] >= 2.0 for f in fam_results.values()),
        "max_fixed_vs_oracle": max(
            f["fixed_vs_oracle"] for f in fam_results.values()),
        "total_probe_matvecs": total_probe_matvecs,
        "total_solve_matvecs_planned": total_solve_matvecs,
        "total_probe_cost_fraction": probe_cost_fraction,
    }
    rows.append((
        "spectral/acceptance", 0.0,
        f"within_1p1x={acceptance['families_planned_within_1p1x_oracle']}"
        f"/{acceptance['num_families']};"
        f"max_fixed_vs_oracle={acceptance['max_fixed_vs_oracle']:.2f};"
        f"probe_cost_fraction={probe_cost_fraction:.4f}",
    ))
    write_bench_json(
        "spectral", rows,
        extra={"families": fam_results, "acceptance": acceptance,
               "config": {"k": K, "budget": BUDGET, "tol": TOL, "lr": LR,
                          "chunk": CHUNK, "max_steps": MAX_STEPS,
                          "num_probes": NUM_PROBES, "num_steps": NUM_STEPS,
                          "fixed_degree": FIXED_DEGREE,
                          "fixed_strength": FIXED_STRENGTH}})
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, derived in run():
        print(f"{name},{us:.0f},{derived}")
