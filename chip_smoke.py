"""Bring-up smoke of the served clustering path on a TPU.

Drives the production path once, through the entry points a user
calls: ``Server`` -> ``StreamingService.tick`` -> ``core.program`` ->
the Pallas kernels, in this one process (a chip admits one process).

    python chip_smoke.py            # one chip: serve and check (a)-(c)
    python chip_smoke.py --chips 4  # only the sharded services, 4 chips

One chip: an in-process ``Server(ServerConfig())`` (the default
``ServiceConfig``) admits one large tenant — an SBM at the node and
edge count of SNAP's com-DBLP (317,080 nodes, ~1.06M edges; Yang &
Leskovec, ICDM 2012), generated from a seed — and eight small SBM
tenants, takes 16 pushed batches of 64 edges per tenant, starts the
engine and runs it until every session has converged to ``tol`` (at
most ``MAX_TICKS`` engine ticks), reads labels and summaries, and
checks:

  (a) each small tenant's served labels agree >= 0.99 (up to
      permutation) with a plain float32 reference: dense Laplacian,
      ``jnp.linalg.eigh``, plain k-means;
  (b) the large tenant's served labels agree >= 0.95 with the planted
      communities;
  (c) one tick of the large tenant's group on the pallas backend and
      one on the segment backend, from the same panel, agree to 1e-4.

``--chips 4`` ticks the large tenant through the edge-sharded and the
panel-sharded services on a 4-device mesh, compares both with the
single-device service to 1e-4, and checks that the panel-sharded tick
traces exactly one fused collective per solver step.

There is no fallback: without a TPU, or with a backend that resolves to
anything but the compiled Pallas kernels, the script exits non-zero and
prints no result.  Earlier lines carry each phase's wall and compile
seconds — bring-up observations, not benchmark numbers.  The last line
of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

BIG = dict(num_nodes=317_080, num_blocks=4, avg_degree_in=6.0,
           avg_degree_out=0.6, seed=0, min_degree=3)
SMALL = dict(num_nodes=2000, num_blocks=4, p_in=0.05, p_out=0.002)
NUM_SMALL = 8
PUSH_BATCHES, PUSH_EDGES = 16, 64
MAX_TICKS = 60  # engine ticks allowed to reach tol; hitting it fails
CONVERGE_DEADLINE_S = 780.0
SMALL_AGREEMENT, BIG_AGREEMENT, TICK_MAXERR = 0.99, 0.95, 1e-4
SHARDED_CHIPS = 4


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class PhaseClock:
    """Per-phase wall seconds, with compile seconds (trace + lower +
    backend compile) and persistent-cache loads reported apart: each
    phase is a ``repro.tracing`` span, and its compiles are those of the
    spans that ran inside it, on any thread."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0

    @contextlib.contextmanager
    def phase(self, name: str):
        from repro import tracing

        with tracing.span(f"smoke.{name}") as sp:
            yield
        me = sp.record
        inside = [r for r in tracing.records()
                  if me.start_ns <= r.start_ns and r.end_ns <= me.end_ns]
        compile_s = sum(r.compile_s for r in inside)
        hits = sum(r.cache_loads for r in inside)
        self.compile_s += compile_s
        self.cache_hits += hits
        print(f"phase {name}: wall_s={me.seconds:.2f} "
              f"compile_s={compile_s:.2f} cache_hits={hits}", flush=True)


def agreement(labels, truth, k: int) -> float:
    """Share of nodes whose label matches ``truth`` under the best
    permutation of the k cluster ids."""
    import numpy as np

    conf = np.zeros((k, k))
    np.add.at(conf, (np.asarray(labels), np.asarray(truth)), 1)
    best = max(conf[list(p), range(k)].sum()
               for p in itertools.permutations(range(k)))
    return float(best / len(truth))


def plain_kmeans(x, k: int, seed: int, restarts: int = 8,
                 iters: int = 100):
    """Lloyd's k-means with k-means++ seeding, best of ``restarts``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    best = None
    for _ in range(restarts):
        c = x[rng.integers(len(x))][None]
        for _ in range(k - 1):
            d2 = ((x[:, None] - c[None]) ** 2).sum(-1).min(1)
            c = np.vstack([c, x[rng.choice(len(x), p=d2 / d2.sum())]])
        for _ in range(iters):
            lab = ((x[:, None] - c[None]) ** 2).sum(-1).argmin(1)
            new = np.stack([x[lab == j].mean(0) if (lab == j).any()
                            else c[j] for j in range(k)])
            if np.allclose(new, c):
                break
            c = new
        inertia = float(((x - c[lab]) ** 2).sum())
        if best is None or inertia < best[0]:
            best = (inertia, lab)
    return best[1]


def reference_labels(src, dst, w, n: int, clusters: int, seed: int):
    """Plain float32 spectral clustering: dense L, eigh, k-means on the
    row-normalized eigenvectors 1..clusters (the trivial one dropped,
    as the service does).  It runs on the host's CPU device: the
    reference stays independent of the chip, and the TPU's eigh takes
    minutes to compile at n = 2000."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lap = np.zeros((n, n), np.float32)
    np.add.at(lap, (src, dst), -w)
    np.add.at(lap, (dst, src), -w)
    lap[np.diag_indices(n)] = -lap.sum(1)
    with jax.default_device(jax.devices("cpu")[0]):
        _, vecs = jnp.linalg.eigh(jnp.asarray(lap))
    emb = np.asarray(vecs[:, 1:1 + clusters], np.float64)
    emb /= np.maximum(np.linalg.norm(emb, axis=1, keepdims=True), 1e-12)
    return plain_kmeans(emb, clusters, seed)


def big_graph():
    """The large tenant: (edges (E, 2) int64, planted labels)."""
    import numpy as np

    from repro.core import graphs

    g, labels = graphs.sparse_sbm_graph(**BIG)
    return np.stack([np.asarray(g.src), np.asarray(g.dst)], 1), labels


def push_batches(labels, seed: int):
    """PUSH_BATCHES batches of PUSH_EDGES distinct within-community
    node pairs — streamed edges that keep the planted structure."""
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(PUSH_BATCHES):
        members = np.flatnonzero(labels == rng.integers(labels.max() + 1))
        pairs = rng.choice(members, size=(4 * PUSH_EDGES, 2))
        pairs = pairs[pairs[:, 0] != pairs[:, 1]]
        pairs = np.unique(np.sort(pairs, axis=1), axis=0)
        out.append(pairs[rng.permutation(len(pairs))[:PUSH_EDGES]])
    return out


def device_peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip(clock: PhaseClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import backend as backend_mod
    from repro.core import graphs, program
    from repro.serve import Server, ServerConfig

    with clock.phase("generate"):
        big_edges, big_truth = big_graph()
        tenants = {"big": (big_edges, BIG["num_nodes"], big_truth)}
        for i in range(NUM_SMALL):
            e, lab = graphs.sbm_edges(**SMALL, seed=i)
            tenants[f"small{i}"] = (e, SMALL["num_nodes"], lab)
    print(f"tenants: big n={BIG['num_nodes']} edges={len(big_edges)}; "
          f"{NUM_SMALL} x small n={SMALL['num_nodes']} edges="
          f"{[len(tenants[f'small{i}'][0]) for i in range(NUM_SMALL)]}",
          flush=True)

    # admissions and pushes land before the engine thread starts, so the
    # converge phase below times the engine alone
    srv = Server(ServerConfig())
    svc = srv.service
    cfg = svc.cfg
    try:
        with clock.phase("admit"):
            for sid, (edges, n, _) in tenants.items():
                srv.admit(sid, edges, n)
        for sid, (_, n, _) in [("big", tenants["big"]),
                               ("small0", tenants["small0"])]:
            print(f"backend {sid}: tick={backend_mod.resolve_backend()} "
                  f"probe={backend_mod.resolve_for_arrays('auto', n)} "
                  f"interpret={backend_mod.kernel_interpret()}",
                  flush=True)
        with clock.phase("push"):
            batches = {sid: push_batches(lab, 1000 + j)
                       for j, (sid, (_, _, lab)) in enumerate(
                           tenants.items())}
            for b in range(PUSH_BATCHES):
                for sid, bs in batches.items():
                    srv.push(sid, bs[b], np.ones(len(bs[b]), np.float32))
        with clock.phase("converge"):
            srv.start()
            deadline = time.monotonic() + CONVERGE_DEADLINE_S
            while not srv.wait_converged(timeout=2.0):
                ticks = srv.metrics.counter("ticks")
                check(ticks < MAX_TICKS,
                      f"tick cap {MAX_TICKS} hit before every session "
                      f"reached tol={cfg.tol}")
                check(time.monotonic() < deadline,
                      f"not converged after {CONVERGE_DEADLINE_S:.0f} s "
                      f"({ticks} ticks)")
        print(f"converged: engine_ticks={srv.metrics.counter('ticks')} "
              f"(cap {MAX_TICKS}) tol={cfg.tol}", flush=True)
        with clock.phase("read"):
            served = {sid: np.asarray(srv.labels(sid)["labels"])
                      for sid in tenants}
            summaries = {sid: srv.summary(sid) for sid in tenants}
    finally:
        srv.stop()  # raises EngineError when the engine thread died
    for sid, s in summaries.items():
        check(s["converged"] and s["residual"] <= cfg.tol,
              f"{sid} not converged to tol={cfg.tol}: {s}")
        print(f"summary {sid}: residual={s['residual']:.3e} "
              f"ticks={s['ticks']} degree={s['degree']} "
              f"family={s['family']} rho={s['rho']:.4g} "
              f"edges={s['num_edges']} version={s['version']}",
              flush=True)
    print(f"compile_count={svc.compile_count} "
          f"tick_invocations={svc.tick_invocations} "
          f"device_peak_bytes={device_peak_bytes()}", flush=True)

    failures = []
    with clock.phase("check_a_reference"):
        for i in range(NUM_SMALL):
            sid = f"small{i}"
            src, dst, w = svc.live_edges(sid)
            ref = reference_labels(src, dst, w, SMALL["num_nodes"],
                                   cfg.num_clusters, seed=i)
            agree = agreement(served[sid], ref, cfg.num_clusters)
            print(f"check (a) {sid}: agreement_vs_reference={agree:.4f}",
                  flush=True)
            if agree < SMALL_AGREEMENT:
                failures.append(f"(a) {sid} agreement {agree:.4f}")
    agree = agreement(served["big"], big_truth, cfg.num_clusters)
    print(f"check (b) big: agreement_vs_planted={agree:.4f}", flush=True)
    if agree < BIG_AGREEMENT:
        failures.append(f"(b) big agreement {agree:.4f}")

    with clock.phase("check_c_pallas_tick"):
        sess = svc._get("big")  # the served session's own state
        degree = summaries["big"]["degree"]
        blocking = svc._source.build(sess.store)  # single-device layout
        schedule = program.StepSchedule(method=cfg.method, degree=degree,
                                        steps=cfg.steps_per_tick,
                                        backend="pallas")
        cs = jnp.asarray([program.dilation_scale(sess.plan, degree)],
                         jnp.float32)
        lrs = jnp.asarray([sess.lr], jnp.float32)
        chunks = jnp.ones((1,), jnp.int32)
        tick_pallas = program.build_tick_program(
            schedule, layout=(blocking.block_n, blocking.num_chunks,
                              blocking.block_e))
        v_p, r_p = tick_pallas(
            blocking.u_local[None], blocking.other[None],
            blocking.weight[None], blocking.chunk_block[None],
            blocking.deg[None], sess.v[None], cs, lrs, chunks)
        jax.block_until_ready(v_p)
    with clock.phase("check_c_segment_tick"):
        tick_segment = program.build_tick_program(
            program.StepSchedule(method=cfg.method, degree=degree,
                                 steps=cfg.steps_per_tick,
                                 backend="segment"))
        st = sess.store
        v_s, r_s = tick_segment(st.src[None], st.dst[None],
                                st.weight[None], sess.v[None], cs, lrs,
                                chunks)
        jax.block_until_ready(v_s)
    err = float(jnp.max(jnp.abs(v_p - v_s)))
    print(f"check (c) big: pallas_vs_segment_maxabs={err:.3e} "
          f"residual_pallas={float(r_p[0]):.3e} "
          f"residual_segment={float(r_s[0]):.3e} "
          f"num_chunks={blocking.num_chunks}", flush=True)
    if not err <= TICK_MAXERR:
        failures.append(f"(c) pallas vs segment max-abs {err:.3e}")
    print(f"device_peak_bytes={device_peak_bytes()}", flush=True)
    check(not failures, "; ".join(failures))


def four_chips(clock: PhaseClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.compat import default_edge_mesh
    from repro.core import laplacian as lap
    from repro.core import program
    from repro.stream.service import ServiceConfig, StreamingService

    devs = jax.devices()
    check(len(devs) >= SHARDED_CHIPS,
          f"--chips {SHARDED_CHIPS} needs {SHARDED_CHIPS} devices, "
          f"JAX sees {len(devs)}")
    with clock.phase("generate"):
        edges, _ = big_graph()
        g = lap.make_edge_list(edges, BIG["num_nodes"])
    edge_mesh = default_edge_mesh(max_shards=SHARDED_CHIPS)
    model_mesh = Mesh(np.array(devs[:SHARDED_CHIPS]).reshape(
        1, SHARDED_CHIPS), ("data", "model"))
    services = {
        "single": StreamingService(ServiceConfig()),
        "edge_sharded": StreamingService(ServiceConfig(mesh=edge_mesh)),
        "panel_sharded": StreamingService(ServiceConfig(
            mesh=model_mesh, model_axes=("model",))),
    }
    panels = {}
    for name, svc in services.items():
        with clock.phase(f"{name}_admit_tick"):
            svc.add_graph("big", g)
            res = svc.tick()["big"]
            panels[name] = svc.panel("big")
            jax.block_until_ready(panels[name])
        print(f"{name}: residual={res:.3e} "
              f"degree={svc.session_info('big')['degree']} "
              f"compile_count={svc.compile_count}", flush=True)
    failures = []
    for name in ("edge_sharded", "panel_sharded"):
        err = float(jnp.max(jnp.abs(panels[name] - panels["single"])))
        print(f"check {name} vs single: maxabs={err:.3e}", flush=True)
        if not err <= TICK_MAXERR:
            failures.append(f"{name} max-abs {err:.3e}")

    svc = services["panel_sharded"]
    sess = svc._get("big")
    mb = sess.layout
    cfg = svc.cfg
    degree = svc.session_info("big")["degree"]
    tick = program.build_tick_model_sharded(
        program.StepSchedule(method=cfg.method, degree=degree,
                             steps=cfg.steps_per_tick, backend="pallas"),
        model_mesh, ("model",), mb.block_n, mb.num_chunks, mb.block_e)
    one = jnp.ones((1,), jnp.float32)
    with program.count_psums() as psums:
        jax.eval_shape(tick, mb.u_local[None], mb.other[None],
                       mb.weight[None], mb.chunk_block[None],
                       mb.deg[None], sess.v[None], one, one,
                       jnp.ones((1,), jnp.int32))
    print(f"check panel_sharded collectives: fused_psums={psums.fused} "
          f"plain_psums={psums.plain}", flush=True)
    if psums.fused != 1:
        failures.append(f"fused_psums={psums.fused}, expected 1")
    print(f"device_peak_bytes={device_peak_bytes()}", flush=True)
    check(not failures, "; ".join(failures))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                    default=1,
                    help="4 = run only the sharded-services phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: FAIL: no repro package under {ROOT}/src; run "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    print(f"compile_cache={cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU found — JAX's first device is on "
              f"platform {dev.platform!r}; this smoke runs only on a TPU",
              file=sys.stderr)
        return 2
    from repro.core import backend as backend_mod

    env = os.environ.get("REPRO_BACKEND", "")
    resolved = backend_mod.resolve_backend("auto")
    interp = backend_mod.kernel_interpret()
    print(f"backend auto={resolved} interpret={interp} "
          f"REPRO_BACKEND={env or '(unset)'}", flush=True)
    if env and env != "pallas":
        print(f"chip_smoke: FAIL: REPRO_BACKEND={env!r} would move the "
              "served path off the Pallas kernels", file=sys.stderr)
        return 2
    if resolved != "pallas" or interp:
        print("chip_smoke: FAIL: the served path does not resolve to "
              "compiled Pallas kernels", file=sys.stderr)
        return 2

    clock = PhaseClock()
    t0 = time.perf_counter()
    try:
        if args.chips == SHARDED_CHIPS:
            four_chips(clock)
        else:
            one_chip(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"total: wall_s={time.perf_counter() - t0:.2f} "
          f"compile_s={clock.compile_s:.2f} "
          f"cache_hits={clock.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
