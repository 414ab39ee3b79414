"""Weak-scaling benchmarks for sharded serving (BENCH_distributed.json).

Rows track the mesh-parallel path PR 4 built: the sharded Laplacian
matvec and the sharded streaming tick at 1/2/4/8 virtual devices on a
fixed n=9216 problem (weak scaling of the collective footprint: the
per-shard edge slice shrinks as devices grow, the psum'd (n, k) panel
does not), plus the acceptance row — a sharded n=9216 solve past
``ONE_HOT_NODE_LIMIT`` running PER-SHARD NODE BLOCKINGS on the pallas
backend, cross-checked against the sharded segment solve.

Everything runs in ONE process (a TPU chip admits one process): each
device count ``d`` builds its meshes from the first ``d`` of
``jax.devices()``, and counts above what the process sees are skipped.
On the CPU the caller provides the devices —
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, as
``scripts/ci.sh`` sets it.  CPU caveat (same as bench_kernels): the
virtual devices share one host and pallas runs in interpret mode, so
these rows track correctness-adjacent latency trends and collective
overhead, NOT TPU speedups.
"""
from __future__ import annotations

import sys

N = 9216  # past backend.ONE_HOT_NODE_LIMIT => node-blocked layouts
DEGREE = 5
SOLVE_STEPS = 2
DEVICE_COUNTS = (1, 2, 4, 8)


def _graph():
    from repro.core import graphs

    g, _ = graphs.sparse_sbm_graph(N, 4, avg_degree_in=3.0,
                                   avg_degree_out=0.5, seed=0)
    return g


def _rows_for(num_devices: int, top: bool) -> list:
    """(name, us, derived) rows on the first ``num_devices`` devices;
    ``top`` adds the sharded pallas acceptance row."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.common import time_call
    from repro.compat import default_edge_mesh
    from repro.core import backend as backend_mod
    from repro.core import distributed, solvers
    from repro.core import laplacian as lap
    from repro.core.series import limit_neg_exp
    from repro.stream.service import ServiceConfig, StreamingService

    d = num_devices
    mesh = default_edge_mesh(max_shards=d)
    g = _graph()
    rows = []

    # --- sharded segment matvec (the tick/solve hot path's inner op) --
    gp = distributed.pad_edges_for_mesh(g, d)
    mv = distributed.sharded_laplacian_matvec(mesh, backend="segment")
    v = jax.random.normal(jax.random.PRNGKey(0), (N, 6))
    us = time_call(lambda: mv(gp.src, gp.dst, gp.weight, v), iters=5)
    rows.append((f"distributed/matvec_n{N}_d{d}", round(us, 1),
                 f"edges_per_shard={gp.num_edges // d}"))

    # --- warm sharded streaming tick (ServiceConfig(mesh=...)) --------
    svc = StreamingService(ServiceConfig(
        backend="segment", mesh=mesh, k=6, num_clusters=4,
        degree=7, steps_per_tick=5, seed=0))
    svc.add_graph("wk", g)
    svc.tick()  # compile + first tick
    t0 = time.perf_counter()
    svc.tick()
    warm_us = (time.perf_counter() - t0) * 1e6
    sess = svc.session_info("wk")
    rows.append((f"distributed/tick_warm_n{N}_d{d}", round(warm_us, 1),
                 f"degree=7,steps=5,edge_cap={sess['edge_capacity']},"
                 f"rho={sess['rho']:.3g}"))

    # --- panel-sharded model tick (weak scaling of the fused path) ----
    # the derived column carries the trace-time collective budget: the
    # mu-EG model tick must issue EXACTLY ONE fused (rows+gram) psum
    # per solver step at EVERY device count
    import numpy as np

    from repro.core import program

    mmesh = jax.sharding.Mesh(
        np.array(jax.devices()[:d]).reshape(1, d), ("data", "model"))
    mb = backend_mod.build_model_sharded_blocking(
        np.asarray(g.src), np.asarray(g.dst), np.asarray(g.weight),
        N, d, block_n=512)
    sched = program.StepSchedule(method="mu_eg", degree=7, steps=5,
                                 backend="segment")
    tick = program.build_tick_model_sharded(
        sched, mmesh, ("model",), mb.block_n, mb.num_chunks, mb.block_e)
    v0 = jax.random.normal(jax.random.PRNGKey(2), (1, N, 6))
    args = (mb.u_local[None], mb.other[None], mb.weight[None],
            mb.chunk_block[None], mb.deg[None], v0,
            jnp.asarray([0.01], jnp.float32),
            jnp.asarray([0.3], jnp.float32), jnp.asarray(1, jnp.int32))
    with program.count_psums() as st:
        jax.eval_shape(tick, *args)
    us = time_call(lambda: tick(*args), iters=3)
    rows.append((f"distributed/model_tick_warm_n{N}_d{d}", round(us, 1),
                 f"degree=7,steps=5,shards={d},"
                 f"fused_psums={st.fused},plain_psums={st.plain},"
                 f"padded_half_edges={mb.padded_half_edges}"))

    # --- acceptance row: sharded node-blocked pallas solve ------------
    # (only at the top device count — interpret-mode pallas is slow)
    if top:
        rho = float(lap.spectral_radius_upper_bound(g))
        s = limit_neg_exp(DEGREE, scale=8.0 / rho)
        cfg = solvers.SolverConfig(
            method="mu_eg", lr=0.3, steps=SOLVE_STEPS,
            eval_every=SOLVE_STEPS, k=6, seed=0)
        panels = {}
        for b in ("segment", "pallas"):
            op = distributed.distributed_series_operator(
                mesh, g, s, backend=b)
            t0 = time.perf_counter()
            state, _ = solvers.run_solver(op, N, cfg)
            panels[b] = jax.block_until_ready(state.v)
            wall = time.perf_counter() - t0
            mode = ("interpret" if b == "pallas"
                    and backend_mod.kernel_interpret() else "native")
            rows.append((
                f"distributed/solve_nb_n{N}_d{d}_{b}",
                round(wall * 1e6, 1),
                f"steps={SOLVE_STEPS},degree={DEGREE},mode={mode},"
                f"per_shard_blocking={b == 'pallas'},"
                f"one_hot_limit={backend_mod.ONE_HOT_NODE_LIMIT}"))
        err = float(jnp.max(jnp.abs(panels["segment"] - panels["pallas"])))
        rows[-1] = (rows[-1][0], rows[-1][1],
                    rows[-1][2] + f",xbackend_maxerr={err:.2g}")
    return rows


def run():
    """Rows for every device count this process can hold; writes
    BENCH_distributed.json."""
    import jax

    from benchmarks.common import write_bench_json

    counts = [d for d in DEVICE_COUNTS if d <= jax.device_count()]
    rows = []
    weak = {}
    for d in counts:
        d_rows = _rows_for(d, top=d == counts[-1])
        rows.extend(d_rows)
        for name, us, derived in d_rows:
            if name.startswith(f"distributed/tick_warm_n{N}_d"):
                weak[f"tick_warm_us_d{d}"] = us
            if name.startswith(f"distributed/matvec_n{N}_d"):
                weak[f"matvec_us_d{d}"] = us
            if name.startswith(f"distributed/model_tick_warm_n{N}_d"):
                weak[f"model_tick_warm_us_d{d}"] = us
                assert "fused_psums=1," in derived, derived
    write_bench_json("distributed", rows, extra={
        "weak_scaling": {
            "n": N,
            "device_counts": counts,
            **weak,
        },
    })
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
