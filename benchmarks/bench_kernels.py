"""Pallas kernel benchmarks: per-kernel micro rows plus backend-vs-
segment END-TO-END solve timings, tracked in BENCH_kernels.json.

CPU caveat: pallas kernels execute via interpret=True on CPU (the kernel
body lowered through a grid loop) so absolute pallas numbers are NOT TPU
projections; the segment path is timed as the comparable baseline and
the derived column records the cross-backend max-abs delta (the perf
claims live in the roofline analysis, not here).  What this file tracks
across PRs is (a) that the pallas path stays numerically glued to
segment end-to-end, and (b) the segment hot-path trajectory; on TPU the
same harness times the real kernels.

The solve rows run the full operator -> solver pipeline on two graph
sizes: one inside the one-hot kernel's VMEM limit and one ABOVE the old
ONE_HOT_NODE_LIMIT (4096) ceiling, exercising the node-blocked layout.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call, write_bench_json
from repro.core import backend as backend_mod
from repro.core import graphs, operators, solvers
from repro.core import laplacian as lap
from repro.core.series import limit_neg_exp
from repro.kernels.edge_spmm import ops as es_ops, ref as es_ref
from repro.kernels.eg_update import ops as eg_ops, ref as eg_ref
from repro.kernels.laplacian_poly import ops as lp_ops, ref as lp_ref

# (tag, n, avg_deg_in, series degree, solver steps); n=9216 sits above
# backend.ONE_HOT_NODE_LIMIT (4096) => node-blocked path.
SOLVE_SIZES = (
    ("n2048", 2048, 4.0, 7, 4),
    ("n9216", 9216, 3.0, 5, 2),
)


def _micro_rows(key):
    rows = []
    n, k = 512, 8
    l_mat = jax.random.normal(key, (n, n)) / 32
    u = jax.random.normal(jax.random.fold_in(key, 1), (n, k))

    ref_fn = jax.jit(lambda: lp_ref.poly_step(l_mat, u, 0.01))
    us = time_call(ref_fn, iters=5)
    kout = lp_ops.poly_step(l_mat, u, 0.01, interpret=True)
    err = float(jnp.max(jnp.abs(kout - ref_fn())))
    rows.append(("kernels/poly_step_ref_n512", round(us, 1),
                 f"kernel_maxerr={err:.2g}"))

    e = 4096
    src = jax.random.randint(jax.random.fold_in(key, 2), (e,), 0, n)
    dst = jax.random.randint(jax.random.fold_in(key, 3), (e,), 0, n)
    w = jax.random.uniform(jax.random.fold_in(key, 4), (e,))
    ref_fn = jax.jit(lambda: es_ref.edge_spmm(src, dst, w, u))
    us = time_call(ref_fn, iters=5)
    kout = es_ops.edge_spmm(src, dst, w, u, interpret=True)
    err = float(jnp.max(jnp.abs(kout - ref_fn())))
    rows.append(("kernels/edge_spmm_ref_e4096", round(us, 1),
                 f"kernel_maxerr={err:.2g}"))

    nb = es_ops.build_node_blocking(src, dst, w, n, block_n=128)
    nb_fn = lambda: es_ops.edge_spmm_blocked(nb, u, interpret=True)
    us = time_call(nb_fn, iters=5)
    err = float(jnp.max(jnp.abs(nb_fn() - ref_fn())))
    # interpret-mode pallas timings are informational (us_per_call=0
    # rows are exempt from run.py --check); the maxerr column stays the
    # gated signal
    interp = backend_mod.kernel_interpret()
    rows.append(("kernels/edge_spmm_nb_e4096",
                 0.0 if interp else round(us, 1),
                 f"kernel_maxerr={err:.2g},chunks={nb.num_chunks}"
                 + (f",interp_us={us:.0f}" if interp else "")))

    v = u / jnp.linalg.norm(u, axis=0, keepdims=True)
    av = jax.random.normal(jax.random.fold_in(key, 5), (n, k))
    ref_fn = jax.jit(lambda: eg_ref.mu_eg_update(v, av, 0.05))
    us = time_call(ref_fn, iters=5)
    kout = eg_ops.mu_eg_update(v, av, 0.05, interpret=True)
    err = float(jnp.max(jnp.abs(kout - ref_fn())))
    rows.append(("kernels/eg_update_ref_n512", round(us, 1),
                 f"kernel_maxerr={err:.2g}"))
    return rows


def _solve_rows():
    """End-to-end: tuned-series operator -> mu-EG solve, per backend.

    Two numbers per (size, backend): the WARM jitted operator
    application (the solve hot path — one full series of fused matvecs
    over the panel; this is the trajectory tracked across PRs) and one
    cold full-solve wall time (jit + `steps` solver steps; run_solver
    re-traces per call, so repeating it would time the compiler, not
    the solve).
    """
    rows = []
    extra = {}
    for tag, n, deg_in, degree, steps in SOLVE_SIZES:
        g, _ = graphs.sparse_sbm_graph(n, 4, avg_degree_in=deg_in,
                                       avg_degree_out=0.5, seed=0)
        rho = float(lap.spectral_radius_upper_bound(g))
        s = limit_neg_exp(degree, scale=8.0 / rho)
        cfg_base = solvers.SolverConfig(
            method="mu_eg", lr=0.3, steps=steps, eval_every=max(steps, 1),
            k=6, seed=0)
        v0 = jax.random.normal(jax.random.PRNGKey(1), (n, cfg_base.k))
        results = {}
        for b in ("segment", "pallas"):
            op_jit = jax.jit(operators.edge_series_operator(g, s, backend=b))
            op_us = time_call(op_jit, v0, iters=3)
            cfg = dataclasses.replace(cfg_base, backend=b)
            t0 = time.perf_counter()
            state, _ = solvers.run_solver(
                operators.edge_series_operator(g, s, backend=b), n, cfg)
            v_final = jax.block_until_ready(state.v)
            solve_cold_s = time.perf_counter() - t0
            results[b] = (op_us, solve_cold_s, v_final)
        delta = float(jnp.max(jnp.abs(results["segment"][2]
                                      - results["pallas"][2])))
        for b in ("segment", "pallas"):
            op_us, solve_cold_s, _ = results[b]
            interp = b == "pallas" and backend_mod.kernel_interpret()
            mode = "interpret" if interp else "native"
            # interpret-mode rows time the pallas grid loop, not the
            # kernel: report us_per_call=0 (informational, exempt from
            # run.py --check) and keep the measured number in derived;
            # xbackend_maxerr stays the gated signal either way
            rows.append((
                f"kernels/op_apply_{tag}_{b}",
                0.0 if interp else round(op_us, 1),
                f"degree={degree},mode={mode},"
                f"xbackend_maxerr={delta:.2g}"
                + (f",interp_us={op_us:.0f}" if interp else "")))
            rows.append((
                f"kernels/solve_cold_{tag}_{b}",
                0.0 if interp else round(solve_cold_s * 1e6, 1),
                f"steps={steps},incl_compile=1,mode={mode}"
                + (f",interp_us={solve_cold_s * 1e6:.0f}"
                   if interp else "")))
        extra[tag] = {
            "n": n,
            "num_edges": int(g.num_edges),
            "degree": degree,
            "solver_steps": steps,
            "node_blocked": n > backend_mod.ONE_HOT_NODE_LIMIT,
            "op_apply_us_segment": results["segment"][0],
            "op_apply_us_pallas": results["pallas"][0],
            "solve_cold_s_segment": results["segment"][1],
            "solve_cold_s_pallas": results["pallas"][1],
            "cross_backend_maxerr": delta,
        }
    return rows, extra


def _skew_rows():
    """Skew acceptance: on an alpha=2.5 power-law graph (hub blocks
    concentrate half-edges) the CSR chunk layout — per-block chunk
    counts, ONE pow2 snap of the total — must walk >= 2x fewer padded
    half-edge slots than the legacy uniform layout (every block pays
    the worst bucket's snapped chunk count), and the segment-form
    matvec over the SAME layout arrays gets faster in proportion.  The
    uniform layout no longer exists in the library, so its arrays are
    synthesized here as the baseline."""
    n, block_n, block_e, k = 4096, 256, 128, 8
    g = graphs.power_law_graph(n, avg_degree=8.0, alpha=2.5, seed=0)
    nb = es_ops.build_node_blocking(
        np.asarray(g.src), np.asarray(g.dst), np.asarray(g.weight), n,
        block_n=block_n, block_e=block_e)
    u, o, w2, counts = es_ops._block_sorted_half_edges(
        np.asarray(g.src), np.asarray(g.dst), np.asarray(g.weight),
        block_n, nb.num_blocks)
    uniform_slots = es_ops.uniform_padded_half_edges(counts, block_e)
    work_ratio = uniform_slots / nb.padded_half_edges
    # synthesized legacy arrays: block b's bucket starts at slot
    # b * C * BE, trailing slots stay inert zero-weight padding
    nbk, c_uni = nb.num_blocks, es_ops.uniform_chunks_for_counts(
        counts, block_e)
    ul = np.zeros((uniform_slots,), np.int32)
    ot = np.zeros((uniform_slots,), np.int32)
    wt = np.zeros((uniform_slots,), np.float32)
    offs = np.concatenate([[0], np.cumsum(counts)])
    blk_of = np.repeat(np.arange(nbk, dtype=np.int64), counts)
    slot = (blk_of * c_uni * block_e
            + (np.arange(u.shape[0]) - offs[blk_of]))
    ul[slot] = (u - blk_of * block_n).astype(np.int32)
    ot[slot] = o.astype(np.int32)
    wt[slot] = w2
    cb_uni = np.repeat(np.arange(nbk, dtype=np.int32), c_uni)

    deg = jnp.asarray(nb.deg)
    n_pad = int(deg.shape[0])
    v = jax.random.normal(jax.random.PRNGKey(9), (n_pad, k))

    def seg_mv(ul_a, ot_a, wt_a, blk_a):
        dest = blk_a * block_n + ul_a

        @jax.jit
        def mv(x):
            av = jnp.zeros((n_pad, k), jnp.float32).at[dest].add(
                wt_a[:, None] * x[ot_a])
            return deg[:, None] * x - av
        return mv

    mv_csr = seg_mv(nb.u_local, nb.other, nb.weight,
                    jnp.repeat(jnp.asarray(nb.chunk_block[:nb.num_chunks]),
                               block_e))
    mv_uni = seg_mv(jnp.asarray(ul), jnp.asarray(ot), jnp.asarray(wt),
                    jnp.repeat(jnp.asarray(cb_uni), block_e))
    err = float(jnp.max(jnp.abs(mv_csr(v) - mv_uni(v))))
    us_csr = time_call(mv_csr, v, iters=5)
    us_uni = time_call(mv_uni, v, iters=5)
    rows = [
        (f"kernels/skew_seg_mv_csr_n{n}", round(us_csr, 1),
         f"slots={nb.padded_half_edges},alpha=2.5,layout_maxerr={err:.2g}"),
        (f"kernels/skew_seg_mv_uniform_n{n}", round(us_uni, 1),
         f"slots={uniform_slots},alpha=2.5"),
    ]
    extra = {
        "n": n,
        "num_edges": int(g.num_edges),
        "block_n": block_n,
        "block_e": block_e,
        "padded_half_edges_csr": int(nb.padded_half_edges),
        "padded_half_edges_uniform": int(uniform_slots),
        "segment_matvec_us_csr": us_csr,
        "segment_matvec_us_uniform": us_uni,
    }
    return rows, extra, work_ratio


def run():
    rows = _micro_rows(jax.random.PRNGKey(0))
    solve_rows, extra = _solve_rows()
    rows += solve_rows
    skew_rows, skew, work_ratio = _skew_rows()
    rows += skew_rows
    write_bench_json("kernels", rows, extra={
        "solves": extra,
        "skew": skew,
        # gated (higher-is-better): layout math, not wall noise
        "skew_padded_work_speedup": work_ratio,
        "pallas_mode": ("interpret" if backend_mod.kernel_interpret()
                        else "native"),
    })
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
