"""Serving layer: SLO metrics, the versioned results store, the
double-buffered async ingest/tick pipeline (including the threaded
concurrency suite), and the stdlib HTTP front end."""
import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graphs
from repro.core.kmeans import cluster_agreement
from repro.serve import EngineError, Server, ServerConfig, VersionedResults
from repro.serve.http import ServeHTTP
from repro.serve.metrics import LatencyHistogram, ServeMetrics
from repro.stream.service import ServiceConfig, UnknownSessionError

SERVE_SVC = ServiceConfig(k=4, num_clusters=3, degree=7, steps_per_tick=25,
                          lr=0.3, tol=5e-3, dilation_strength=6.0)


def _sbm_edges(seed: int, n: int = 60):
    g, truth = graphs.sbm_graph(n, 3, p_in=0.4, p_out=0.02, seed=seed)
    edges = np.stack([np.asarray(g.src), np.asarray(g.dst)], axis=1)
    return edges, np.asarray(g.weight), n, truth


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_latency_histogram_percentiles_conservative():
    h = LatencyHistogram()
    samples = [1e-5] * 98 + [0.5, 0.9]
    for s in samples:
        h.record(s)
    assert h.count == 100
    # the reported quantile is the holding bucket's UPPER edge: at least
    # the true quantile (SLO-conservative), within one bucket factor
    from repro.serve.metrics import LATENCY_BUCKET_FACTOR as F
    assert 1e-5 <= h.percentile(0.50) <= 1e-5 * F
    assert 0.5 <= h.percentile(0.99) <= 0.5 * F  # 99th of 100 = 0.5
    assert 0.9 <= h.percentile(1.0) <= 0.9 * F
    assert h.percentile(0.0) > 0.0  # min sample's bucket, not 0
    assert h.max_s == 0.9
    assert abs(h.mean_s - np.mean(samples)) < 1e-9
    with pytest.raises(ValueError):
        h.percentile(1.5)
    assert LatencyHistogram().percentile(0.99) == 0.0  # empty => 0


def test_serve_metrics_aggregate_threaded():
    m = ServeMetrics(("push", "labels"))

    def hammer():
        for _ in range(200):
            m.record("push", 2e-6)
            m.inc("staged_batches")

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = m.snapshot()
    assert snap["counters"]["staged_batches"] == 800
    assert snap["latency"]["push"]["count"] == 800
    assert snap["latency"]["labels"]["count"] == 0
    with m.timed("labels"):
        pass
    assert m.percentile("labels", 0.5) > 0.0
    assert m.percentile("nope", 0.5) == 0.0


# ---------------------------------------------------------------------------
# versioned results store
# ---------------------------------------------------------------------------

def test_versioned_results_monotone_versions_and_lazy_labels():
    store = VersionedResults()
    store.register("a", 3)
    with pytest.raises(ValueError):
        store.register("a", 3)  # live duplicate
    with pytest.raises(UnknownSessionError):
        store.commit("ghost", {}, None)
    panel = np.eye(4)
    calls = []

    def labeler(p):
        calls.append(1)
        return np.asarray([0, 1, 2, 0])

    assert store.commit("a", {"residual": 1.0}, panel) == 1
    assert store.commit("a", {"residual": 0.5}, panel) == 2
    assert store.version("a") == 2
    assert store.summary("a")["version"] == 2  # summary carries version
    lab, version, churn = store.labels("a", labeler)
    assert version == 2 and churn == 0.0
    np.testing.assert_array_equal(lab, [0, 1, 2, 0])
    store.labels("a", labeler)
    assert len(calls) == 1  # cached: one labeler run per version
    # a permuted relabelling of the next version serves STABLE ids
    store.commit("a", {"residual": 0.4}, panel)
    lab2, version2, churn2 = store.labels(
        "a", lambda p: np.asarray([1, 2, 0, 1]))  # same partition, permuted
    assert version2 == 3
    np.testing.assert_array_equal(lab2, lab)  # tracker mapped ids back
    assert churn2 == 0.0  # measured guarantee: no genuine movement
    # eviction tombstones: reads 404 but re-registration works
    store.evict("a")
    with pytest.raises(UnknownSessionError):
        store.summary("a")
    with pytest.raises(UnknownSessionError):
        store.evict("a")  # not idempotent, same as the engine
    store.register("a", 3)
    assert store.commit("a", {}, panel) == 1  # fresh lineage
    assert store.stats()["commits"] == 4


# ---------------------------------------------------------------------------
# server (manual stepping: deterministic pipeline semantics)
# ---------------------------------------------------------------------------

def test_server_pipeline_manual_steps_end_to_end():
    srv = Server(ServerConfig(service=SERVE_SVC))
    edges, w, n, truth = _sbm_edges(11)
    out = srv.admit("a", edges, n, weights=w, num_clusters=3,
                    edge_capacity=1024)
    assert out["version"] == 1  # queryable before the first tick
    # staging alone must not touch the engine: no compiles, no version
    c0 = srv.service.compile_count
    for i in range(6):
        r = srv.push("a", [[i, i + 1]], [0.5], mode="add")
        assert r["staged"] == 1 and r["applied"] == 0
    assert srv.service.compile_count == c0
    assert srv.results.version("a") == 1
    assert r["queue_depth"] == 6
    # drain + tick until converged
    for _ in range(200):
        srv.step()
        if srv.service.all_converged:
            break
    assert srv.service.all_converged
    lab = srv.labels("a")
    assert lab["version"] > 1
    agree = float(cluster_agreement(jnp.asarray(lab["labels"]),
                                    jnp.asarray(truth), 3))
    assert agree > 0.9
    # repeated query at one version: identical bytes, zero churn
    again = srv.labels("a")
    assert again["version"] == lab["version"]
    np.testing.assert_array_equal(again["labels"], lab["labels"])
    s = srv.summary("a")
    assert s["converged"] and s["version"] == lab["version"]
    # staged batches all landed
    m = srv.metrics
    assert m.counter("applied_batches") > 0
    assert m.counter("dropped_batches") == 0
    ev = srv.evict("a")
    assert np.asarray(ev["panel"]).shape[0] == n  # resumable panel
    for fn in (lambda: srv.labels("a"), lambda: srv.summary("a"),
               lambda: srv.evict("a"),
               lambda: srv.push("a", [[0, 1]], [1.0])):
        with pytest.raises(UnknownSessionError):
            fn()
    # a batch staged just before eviction is dropped, not applied
    srv.admit("b", edges, n, weights=w, edge_capacity=1024)
    srv.push("b", [[0, 1]], [1.0])
    srv.evict("b")
    assert m.counter("dropped_batches") == 1


def test_server_stats_layout_pad_share():
    """The engine reports the padding share of the blocked layouts the
    pallas tick path built: 1 - live half-edges / slots walked."""
    cfg = dataclasses.replace(SERVE_SVC, backend="pallas", tick_block_n=32)
    srv = Server(ServerConfig(service=cfg))
    assert srv.stats()["engine"]["layout_pad_share"] == 0.0
    edges, w, n, _ = _sbm_edges(11)
    srv.admit("a", edges, n, weights=w, num_clusters=3, edge_capacity=1024)
    for _ in range(50):
        srv.step()
        if srv.service.all_converged:
            break
    assert srv.service.all_converged
    share = srv.stats()["engine"]["layout_pad_share"]
    slots = srv.service._sessions["a"].blocking.padded_half_edges
    assert 0.0 < share < 1.0
    assert share == pytest.approx(1.0 - 2 * len(edges) / slots)


@pytest.mark.parametrize("k", [6, 48])
def test_server_stats_scatter_passes(k):
    """The engine reports the MXU passes per chunk of the node-blocked
    kernel its pallas tick runs: one, also where the data's three bf16
    parts stack into more than 128 rows (k = 48); a segment tick runs
    no kernel."""
    cfg = dataclasses.replace(SERVE_SVC, backend="pallas", k=k,
                              tick_block_n=32)
    srv = Server(ServerConfig(service=cfg))
    edges, w, n, _ = _sbm_edges(13, n=64)
    srv.admit("a", edges, n, weights=w, num_clusters=3, edge_capacity=1024)
    assert srv.step()
    assert srv.service._sessions["a"].blocking is not None
    assert srv.stats()["engine"]["scatter_passes"] == 1
    seg = Server(ServerConfig(service=dataclasses.replace(
        SERVE_SVC, backend="segment", k=k)))
    assert seg.stats()["engine"]["scatter_passes"] == 0


def test_server_serialized_pipeline_applies_inline():
    srv = Server(ServerConfig(service=SERVE_SVC, pipeline="serialized"))
    edges, w, n, _ = _sbm_edges(12)
    srv.admit("s", edges, n, weights=w, edge_capacity=1024)
    r = srv.push("s", [[0, 1]], [0.5], mode="add")
    # the baseline has no staging: the batch applies under the engine
    # lock and commits a fresh version before returning
    assert r["staged"] == 0 and r["applied"] == 1
    assert r["version"] == 2 == srv.results.version("s")
    with pytest.raises(ValueError):
        srv.push("s", [[0, 1]], [1.0], mode="xor")
    with pytest.raises(ValueError):
        srv.push("s", [[0, 1]], [1.0, 2.0])  # length mismatch
    with pytest.raises(ValueError):
        ServerConfig(pipeline="bogus")


def test_server_drains_capacity_classes_through_one_pad():
    """The drain groups staged sessions by capacity class and pins ONE
    pow2 batch pad per class, so every member's apply hits the same
    compiled edge-batch program (keyed on capacity, pad, mode) — one
    compile per class, not one per pow2 batch size per session.  A
    different-capacity session forms its own class, and the padded
    applies land identically to the serialized pipeline's unpadded
    inline applies."""
    srv = Server(ServerConfig(service=SERVE_SVC))
    base = Server(ServerConfig(service=SERVE_SVC, pipeline="serialized"))
    edges, w, n, _ = _sbm_edges(21)
    for s in (srv, base):
        s.admit("a", edges, n, weights=w, edge_capacity=1024)
        s.admit("b", edges, n, weights=w, edge_capacity=1024)
        s.admit("c", edges, n, weights=w, edge_capacity=2048)
    assert (srv.service.capacity_class("a")
            == srv.service.capacity_class("b")
            != srv.service.capacity_class("c"))
    # different batch sizes inside the shared class: the class pad is
    # the pow2 of the largest, so both applies share one batch shape
    pushes = [("a", [[0, 5], [1, 6], [2, 7]]), ("b", [[3, 8]]),
              ("c", [[4, 9]])]
    for s in (srv, base):
        for sid, es in pushes:
            s.push(sid, es, [0.5] * len(es), mode="add")
    srv.step()
    assert srv.metrics.counter("drain_classes") == 2  # {a, b} and {c}
    assert srv.metrics.counter("applied_batches") == 3
    assert srv.metrics.counter("dropped_batches") == 0
    # padding is a no-op on the stores: padded slots carry zero weight
    for sid in ("a", "b", "c"):
        np.testing.assert_array_equal(
            np.asarray(srv.service._sessions[sid].store.weight),
            np.asarray(base.service._sessions[sid].store.weight))
        np.testing.assert_array_equal(
            np.asarray(srv.service._sessions[sid].store.src),
            np.asarray(base.service._sessions[sid].store.src))


# ---------------------------------------------------------------------------
# concurrency: threaded ingest + queries against a live engine thread
# ---------------------------------------------------------------------------

def test_server_concurrent_ingest_no_lost_updates():
    """Interleaved push/query threads against the running engine:
    every `add` lands exactly once (weights prove it), served result
    versions never go backwards, and staging stays compile-free."""
    srv = Server(ServerConfig(service=SERVE_SVC, idle_sleep_s=0.001))
    edges, w, n, _ = _sbm_edges(13)
    # the accounting session: a path graph whose high node ids are
    # untouched, so each pusher thread owns fresh (40+t, 41+t) slots
    path = np.stack([np.arange(19), np.arange(1, 20)], axis=1)
    with srv:
        srv.admit("query", edges, n, weights=w, num_clusters=3,
                  edge_capacity=1024)
        srv.admit("acc", path, 60, num_clusters=3, edge_capacity=1024)
        pushes_per_thread, num_push = 25, 4
        errors = []
        versions = []

        def pusher(t):
            try:
                for _ in range(pushes_per_thread):
                    srv.push("acc", [[40 + t, 41 + t]], [1.0], mode="add")
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def querier():
            try:
                seen = []
                for _ in range(60):
                    seen.append(srv.summary("query")["version"])
                    srv.labels("query")
                versions.append(seen)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = ([threading.Thread(target=pusher, args=(t,))
                    for t in range(num_push)]
                   + [threading.Thread(target=querier) for _ in range(2)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert srv.flush(timeout=60.0)
        # no lost updates: thread t's accumulated weight is exact
        src, dst, ws = srv.service.live_edges("acc")
        got = {(int(a), int(b)): float(x)
               for a, b, x in zip(src, dst, ws)}
        for t in range(num_push):
            assert got[(40 + t, 41 + t)] == pushes_per_thread, (t, got)
        # versions observed by query threads never went backwards
        for seen in versions:
            assert all(a <= b for a, b in zip(seen, seen[1:])), seen
        # accounting closes: everything staged was applied (coalesced
        # drains may batch many staged pushes into one apply)
        mc = srv.metrics
        assert mc.counter("staged_batches") == pushes_per_thread * num_push
        assert mc.counter("applied_batches") >= 1
        assert mc.counter("dropped_batches") == 0
        assert srv.wait_converged(timeout=120.0)
        # one capacity class end to end: the pipeline added no compiles
        # beyond the engine's pow2 occupancy buckets
        assert len({key for key, _ in srv.service._compiled}) == 1
    assert not srv.running  # context exit drained and stopped cleanly
    snap = srv.stats()
    assert snap["latency"]["push"]["count"] == 100
    assert snap["latency"]["push"]["p99_s"] > 0.0
    assert snap["gauges"]["tick_utilization"] > 0.0


# ---------------------------------------------------------------------------
# HTTP front end
# ---------------------------------------------------------------------------

def _req(url, method="GET", body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_roundtrip_and_error_mapping():
    edges, w, n, truth = _sbm_edges(14)
    with ServeHTTP(Server(ServerConfig(service=SERVE_SVC))) as front:
        base = f"http://{front.host}:{front.port}"
        assert _req(base + "/healthz")[0] == 200
        code, out = _req(base + f"/v1/sessions/h1", "POST",
                         {"edges": edges.tolist(), "num_nodes": n,
                          "weights": w.tolist(), "num_clusters": 3,
                          "edge_capacity": 1024})
        assert code == 200 and out["version"] == 1
        code, out = _req(base + "/v1/sessions/h1/edges", "POST",
                         {"edges": [[0, 1]], "weights": [0.5],
                          "mode": "add"})
        assert code == 200 and out["staged"] == 1
        assert front.app.wait_converged(timeout=120.0)
        code, out = _req(base + "/v1/sessions/h1/labels")
        assert code == 200 and out["version"] >= 1
        agree = float(cluster_agreement(jnp.asarray(out["labels"]),
                                        jnp.asarray(truth), 3))
        assert agree > 0.9
        code, out = _req(base + "/v1/sessions/h1")
        assert code == 200 and out["converged"]
        code, out = _req(base + "/metrics")
        assert code == 200
        assert out["latency"]["push"]["count"] == 1
        assert out["engine"]["sessions"] == 1
        # error mapping: 404 unknown sid, 400 malformed, 404 bad route
        assert _req(base + "/v1/sessions/ghost/labels")[0] == 404
        assert _req(base + "/v1/sessions/ghost", "DELETE")[0] == 404
        assert _req(base + "/v1/sessions/h1/edges", "POST",
                    {"edges": [[0, 1]]})[0] == 400
        assert _req(base + "/v1/sessions/zz", "POST",
                    {"edges": [[0, 1]]})[0] == 400  # missing num_nodes
        assert _req(base + "/nope")[0] == 404
        code, out = _req(base + "/v1/sessions/h1", "DELETE")
        assert code == 200 and "panel" not in out  # stripped on the wire
        assert _req(base + "/v1/sessions/h1")[0] == 404


def test_engine_failure_surfaces_and_never_serves_as_healthy(monkeypatch):
    """An exception inside a tick kills the engine thread: from then on
    health, requests, flush and stop report it instead of serving stale
    versions as if nothing happened."""
    edges, w, n, _ = _sbm_edges(15)
    srv = Server(ServerConfig(service=SERVE_SVC))
    srv.admit("e1", edges, n, weights=w, num_clusters=3,
              edge_capacity=1024)  # unconverged: the first tick fires

    def refused_tick():
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setattr(srv.service, "tick", refused_tick)
    front = ServeHTTP(srv).start()
    base = f"http://{front.host}:{front.port}"
    deadline = time.monotonic() + 60.0
    while srv.running and time.monotonic() < deadline:
        time.sleep(0.01)
    try:
        assert not srv.running
        assert isinstance(srv.engine_error, RuntimeError)
        code, out = _req(base + "/healthz")
        assert code == 503 and not out["ok"] and "refused" in out["error"]
        assert _req(base + "/v1/sessions/e1/labels")[0] == 503
        assert srv.stats()["engine_error"].startswith("RuntimeError")
        with pytest.raises(EngineError, match="kernel refused"):
            srv.labels("e1")
        with pytest.raises(EngineError):
            srv.summary("e1")
        with pytest.raises(EngineError):
            srv.push("e1", [[0, 1]], [1.0])
        with pytest.raises(EngineError):
            srv.flush(timeout=5.0)
    finally:
        with pytest.raises(EngineError) as info:
            front.stop()
    assert isinstance(info.value.__cause__, RuntimeError)
