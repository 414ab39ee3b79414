"""Runner of a cold solve: one large tenant admitted, no other traffic.

Set-up generates the graph and its random start panel (the
configuration's fixed graph and start, its edges sent in an order drawn
from the seed: :func:`generate.solve_graph`), admits it with that start
(the admission probe runs), starts the engine and waits for the first
tick's commit, which opens the window.  The window closes after
``--seconds`` or at the first commit at ``tol``, whichever comes first,
and measures how fast the served panels improve: over the commits
inside it, from the opening one to the last, ``solve_dec_per_s`` =
log10(r_first / r_last) / (t_last - t_first), where r is the plain
Laplacian block residual the reference computes for each committed
panel.

``correct``: the solve's answer when the window closes, the last panel
committed in it, is compared with the reference's bottom eigenspace of
L (the planted communities' directions and the trivial one, by LOBPCG
in float64): the sine of the largest principal angle between that
space and the panel's span may not pass the limit.  At least one commit
after the opening one has to fall in the window.  (Earlier commits are
intermediate versions of a solve that is still running; each is
printed with its reading.)
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

import costs
import generate
import reference
from harness import Check
from serving import make_server, wait_until

# the Pallas call inside kernels/edge_spmm/ops.py's jitted
# _edge_spmm_blocked, as the TPU's trace names it
KERNELS = {"edge_spmm_nb": r"^%_edge_spmm_blocked[.\d]* = .*custom-call\("}
MODULES = {"tick": "jit_tick"}
SID = "solve"


def _wait_commit(rec, srv, min_version: int, timeout: float):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        for c in rec.history(SID):
            if c[1] >= min_version:
                return c
        if srv.engine_error is not None:
            raise RuntimeError("engine thread died") from srv.engine_error
        time.sleep(0.01)
    raise TimeoutError(f"no commit of version {min_version} in {timeout} s")


def _warm_convergence(svc, k: int) -> None:
    """Compile (or load from the cache) the one program the service first
    runs when the session converges, the anchor of its eigen-estimate,
    at the session's shapes: it would otherwise compile inside the
    window, just before the commit that closes it."""
    import jax
    import jax.numpy as jnp
    from repro.stream import updates

    node_cap, edge_cap = svc.capacity_class(SID)
    idx = jnp.zeros((edge_cap,), jnp.int32)
    jax.block_until_ready(updates.anchor_estimate_arrays(
        idx, idx, jnp.zeros((edge_cap,), jnp.float32),
        jnp.zeros((node_cap, k), jnp.float32)))


def _wait_window(rec, srv, t_end: float) -> float:
    """Wait until ``t_end`` or the first commit at ``tol``, whichever
    comes first (nothing is left to measure after it); returns the
    window's close."""
    while time.perf_counter() < t_end:
        done = [c[0] for c in rec.history(SID) if c[3]]
        if done:
            return done[0]
        wait_until(min(t_end, time.perf_counter() + 0.05), srv)
    return t_end


def run(ctx) -> dict:
    g = ctx.config["graph"]
    n = int(g["num_nodes"])
    srv = make_server(ctx.config)
    rec = srv.results
    k = srv.service.cfg.k
    edges, planted, start = generate.solve_graph(g, k, ctx.seed)
    ctx.info(f"graph: n={n} edges={len(edges)}")
    traffic = ctx.traffic
    with ctx.span("admit"):
        srv.admit(SID, edges, n, resume_panel=start)
    with ctx.span("warm"):
        _warm_convergence(srv.service, k)
    t_admitted = time.perf_counter()
    c0 = ctx.compile_snapshot()
    srv.start()
    try:
        opened = _wait_commit(rec, srv, 2, float(traffic["open_timeout_s"]))
        t_open = opened[0]
        setup_s = t_open - ctx.t_start
        ctx.info(f"setup: admit {t_admitted - ctx.t_start:.3f} s, first "
                 f"tick {t_open - t_admitted:.3f} s, compile "
                 f"{ctx.compile_snapshot()}")
        c1 = ctx.compile_snapshot()
        with ctx.traced_window(KERNELS, MODULES):
            t_close = _wait_window(rec, srv, t_open + ctx.seconds)
        c2 = ctx.compile_snapshot()
        ctx.read_device()
    finally:
        t_stop = time.perf_counter()
        srv.stop(timeout=float(traffic["stop_timeout_s"]))
    stats = srv.stats()["engine"]
    ctx.info(f"stop waited {time.perf_counter() - t_stop:.3f} s; engine "
             f"{stats}; window compiles "
             f"{c2['backend_compiles'] - c1['backend_compiles']} "
             f"(setup compiles {c1['backend_compiles'] - c0['backend_compiles']})")

    window = []
    for t, version, _, converged, panel in rec.history(SID):
        if t_open <= t <= t_close:
            window.append((t, version, converged, panel))
            if converged:
                break
    panels = [np.asarray(p, np.float64) for _, _, _, p in window]
    times = [t for t, _, _, _ in window]
    versions = [v for _, v, _, _ in window]
    del rec, srv, window
    gc.collect()

    src, dst = edges[:, 0], edges[:, 1]
    w = np.ones(len(edges))
    t_ref = time.perf_counter()
    res = [reference.block_residual(src, dst, w, n, p) for p in panels]
    indicators = np.eye(int(g["num_blocks"]))[planted]
    vals, space, rel = reference.bottom_eigenspace(src, dst, w, n,
                                                   indicators)
    if rel > 1e-6:
        raise RuntimeError(f"reference eigenspace not converged: {rel}")
    sines = [reference.subspace_sin(space, p) for p in panels]
    ctx.info(f"reference: {time.perf_counter() - t_ref:.3f} s, "
             f"eigenvalues {vals.tolist()}, eigen-residual {rel:.3e}")
    for t, v, r, s in zip(times, versions, res, sines):
        ctx.info(f"commit v{v}: t={t - t_open:.3f} s plain_residual={r!r} "
                 f"sin={s!r}")

    lim = ctx.config["limits"]
    later = len(panels) - 1  # commits after the opening one
    checks = [Check("commits_missing", 0.0 if later else 1.0, 0.0),
              Check("sin_last", sines[-1] if later else None,
                    float(lim["sin_last"]))]
    rate = None
    if later:
        rate = (math.log10(res[0] / res[-1]) / (times[-1] - times[0]))
    cost = costs.edge_spmm_cost(2 * len(edges), n, k)
    return {
        "setup_s": setup_s,
        "e2e": {"solve_dec_per_s": rate},
        "layer": {"spmm_cost": cost},
        "checks": checks,
        "attempted": 1,
        "failed": int(not all(c.ok for c in checks)),
    }
