"""The span recorder (repro.tracing) and the spans the serve engine and
the streaming service record with it."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import graphs
from repro.serve import Server, ServerConfig
from repro.stream.service import ServiceConfig

SVC = ServiceConfig(k=4, num_clusters=3, degree=7, steps_per_tick=25,
                    lr=0.3, tol=5e-3, dilation_strength=6.0)


@pytest.fixture(autouse=True)
def fresh_recorder():
    tracing.reset()
    yield
    tracing.reset()


def _mine():
    me = threading.current_thread().name
    return [r for r in tracing.records() if r.thread == me]


def _edges(seed=0, n=60):
    g, _ = graphs.sbm_graph(n, 3, p_in=0.4, p_out=0.02, seed=seed)
    return np.stack([np.asarray(g.src), np.asarray(g.dst)], axis=1), n


def test_nesting_parents_and_self_time():
    with tracing.span("outer") as outer:
        time.sleep(0.01)
        with tracing.span("inner") as inner:
            time.sleep(0.02)
        with tracing.span("inner"):
            pass
    recs = {r.index: r for r in _mine()}
    assert [r.name for r in _mine()] == ["inner", "inner", "outer"]
    assert recs[outer.record.index].parent == -1
    assert recs[inner.record.index].parent == outer.record.index
    o, i = outer.record, inner.record
    assert o.start_ns <= i.start_ns and i.end_ns <= o.end_ns
    t = tracing.totals()
    assert t["inner"]["count"] == 2 and t["outer"]["count"] == 1
    assert t["outer"]["total_s"] == pytest.approx(o.seconds)
    # the outer span's own time excludes both children
    children = t["inner"]["total_s"]
    assert t["outer"]["self_s"] == pytest.approx(o.seconds - children,
                                                 abs=1e-6)
    assert t["inner"]["self_s"] == pytest.approx(children)
    assert t["inner"]["max_s"] == pytest.approx(i.seconds)
    assert 0.01 <= t["outer"]["self_s"] < o.seconds


def test_stacks_are_thread_local():
    entered = threading.Barrier(2, timeout=30)

    def work(name):
        with tracing.span(f"{name}.outer"):
            entered.wait()  # both outer spans open at once
            with tracing.span(f"{name}.inner"):
                pass

    threads = [threading.Thread(target=work, args=(n,), name=n)
               for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    recs = tracing.records()
    by_name = {r.name: r for r in recs}
    assert len(recs) == 4
    for n in ("a", "b"):
        inner, outer = by_name[f"{n}.inner"], by_name[f"{n}.outer"]
        assert inner.thread == outer.thread == n
        assert inner.parent == outer.index
        assert outer.parent == -1


def test_ring_keeps_the_newest_records():
    extra = 5
    for _ in range(tracing.RING_SIZE + extra):
        with tracing.span("tiny"):
            pass
    recs = tracing.records()
    assert len(recs) == tracing.RING_SIZE
    assert recs[-1].index - recs[0].index == tracing.RING_SIZE - 1
    assert tracing.totals()["tiny"]["count"] == tracing.RING_SIZE + extra


def test_compile_counts_in_the_innermost_span():
    fn = jax.jit(lambda x: jnp.sin(x) * 3.0 + x.sum())  # never compiled
    x = jnp.arange(7.0).block_until_ready()
    with tracing.span("outer") as outer:
        with tracing.span("inner") as inner:
            fn(x).block_until_ready()
    assert inner.record.compile_s > 0.0
    assert inner.record.compiles + inner.record.cache_loads == 1
    assert outer.record.compile_s == 0.0
    assert outer.record.compiles == outer.record.cache_loads == 0
    t = tracing.totals()
    assert t["inner"]["compile_s"] == pytest.approx(inner.record.compile_s)
    assert t["outer"]["compile_s"] == 0.0
    # a jit traced inside another's trace: the outer trace's duration
    # holds the inner ones, and is counted alone
    inner_fn = jax.jit(lambda v: jnp.cos(v) + 1.0)
    nested = jax.jit(lambda v: inner_fn(v) * inner_fn(v + 2.0))
    with tracing.span("nested") as sp:
        nested(x).block_until_ready()
    assert sp.record.compiles == 1
    assert 0.0 < sp.record.compile_s <= sp.record.seconds
    # a compile outside every span goes nowhere
    jax.jit(lambda x: x - 1.0)(jnp.ones(3)).block_until_ready()
    assert len(tracing.records()) == 3


def test_traced_decorator_wraps_each_call():
    @tracing.traced("deco")
    def f(x):
        return x + 1

    assert f(1) == 2 and f(2) == 3
    assert tracing.totals()["deco"]["count"] == 2


def test_unstarted_server_step_records_engine_and_service_spans():
    srv = Server(ServerConfig(service=SVC))
    assert srv.step() is False  # nothing admitted: idle
    assert tracing.records() == []
    edges, n = _edges()
    srv.admit("g", edges, n)
    names = {r.name for r in _mine()}
    assert {"request.admit", "service.admit", "service.store",
            "service.probe", "engine.commit"} <= names
    tracing.reset()
    assert srv.step() is True
    recs = _mine()
    names = [r.name for r in recs]
    for want in ("engine.step", "engine.tick",
                 "service.tick.prepare", "service.tick.launch",
                 "service.tick.wait", "service.tick.update",
                 "engine.commit"):
        assert want in names, (want, names)
    assert "engine.drain" not in names  # nothing was staged
    by_idx = {r.index: r for r in recs}
    step = next(r for r in recs if r.name == "engine.step")
    wait = next(r for r in recs if r.name == "service.tick.wait")
    tick = by_idx[wait.parent]
    assert tick.name == "engine.tick" and tick.parent == step.index
    commit = next(r for r in recs if r.name == "engine.commit")
    assert commit.parent == step.index
    # evicted: no session, nothing staged, so a step is idle again
    srv.evict("g")
    tracing.reset()
    assert srv.step() is False
    assert tracing.records() == []


def test_stats_serves_span_totals_and_push_wait():
    srv = Server(ServerConfig(service=SVC))
    edges, n = _edges(seed=1)
    srv.admit("g", edges, n)
    srv.push("g", [[0, 31]], [1.0])
    srv.push("g", [[2, 45]], [1.0])
    time.sleep(0.01)
    srv.step()
    srv.labels("g")
    snap = srv.stats()
    wait = snap["latency"]["push_wait"]
    assert wait["count"] == 1  # one session's buffer drained once
    assert wait["max_s"] >= 0.01
    spans = snap["spans"]
    for name in ("engine.step", "engine.drain", "service.apply",
                 "engine.tick", "request.push", "request.labels",
                 "request.admit", "service.probe"):
        assert spans[name]["count"] >= 1, name
    assert spans["request.push"]["count"] == 2
    # one timing feeds both the span and the request histogram
    assert snap["latency"]["push"]["count"] == 2
    assert snap["latency"]["push"]["max_s"] == pytest.approx(
        spans["request.push"]["max_s"])


def test_tick_utilization_counts_from_start():
    srv = Server(ServerConfig(service=SVC))
    edges, n = _edges(seed=2)
    srv.admit("g", edges, n)
    srv.step()  # an engine.tick before start() does not count
    assert srv.stats()["gauges"]["tick_utilization"] == 0.0
    with srv:
        assert srv.wait_converged(timeout=120.0)
    util = srv.stats()["gauges"]["tick_utilization"]
    assert 0.0 < util <= 1.0
    # frozen at stop(): the idle time after it does not dilute it
    time.sleep(0.05)
    assert srv.stats()["gauges"]["tick_utilization"] == util


def test_persistent_cache_loads_count_apart_from_compiles(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    opts = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in opts}
    x = jnp.linspace(0.0, 1.0, 5).block_until_ready()
    try:
        for k, v in opts.items():
            jax.config.update(k, v)
        cc.reset_cache()
        # two function objects of one program: the second misses JAX's
        # in-memory caches and loads the first's persistent entry
        with tracing.span("first") as first:
            jax.jit(lambda v: jnp.cos(v) * 2.5)(x).block_until_ready()
        with tracing.span("again") as again:
            jax.jit(lambda v: jnp.cos(v) * 2.5)(x).block_until_ready()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert (first.record.compiles, first.record.cache_loads) == (1, 0)
    assert (again.record.compiles, again.record.cache_loads) == (0, 1)
    assert again.record.compile_s > 0.0  # loading is program loading too
    assert tracing.totals()["again"]["compiles"] == 0


def test_chip_smoke_phase_clock_reads_spans_inside_the_phase(capsys):
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    clock = smoke.PhaseClock()
    x = jnp.arange(4.0).block_until_ready()

    def compile_in_span():
        with tracing.span("engine.step"):
            jax.jit(lambda v: jnp.tanh(v) - 0.5)(x).block_until_ready()

    with clock.phase("converge"):
        t = threading.Thread(target=compile_in_span)  # as the engine does
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    line = capsys.readouterr().out.strip()
    assert line.startswith("phase converge: wall_s=")
    assert " compile_s=" in line and line.endswith(" cache_hits=0")
    # the compile ran on another thread, inside the phase
    assert clock.compile_s > 0.0 and clock.cache_hits == 0
    assert tracing.totals()["smoke.converge"]["count"] == 1
