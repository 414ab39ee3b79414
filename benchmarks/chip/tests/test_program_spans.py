"""The readers of the program's own spans give a number for a run of the
solve cell, and the set-up parts they measure fit inside its set-up.

The run is the cell at 4,000 nodes on the CPU, without the look for a
chip and without a trace, as ``test_faults`` drives it.  The CPU ticks
on the segment backend, which needs no node-blocked layout; on the chip
the Pallas tick builds one on the first tick.  The test has the service
build it at that same point (the layout is built and not used), so
``layout_s.dblp`` has its span to read.
"""
import sys

import harness
import repro
from repro import tracing
from repro.stream import service as service_mod
from test_faults import solve_run

READERS = ("probe_s.dblp", "layout_s.dblp", "load_s.dblp",
           "engine_host_ms.dblp")


def test_span_readers_read_a_solve_run(monkeypatch):
    group_key = service_mod.StreamingService._group_key

    def with_layout(self, sess, degrees=None):
        self._ensure_blocking(sess)
        return group_key(self, sess, degrees)

    monkeypatch.setattr(service_mod.StreamingService, "_group_key",
                        with_layout)
    tracing.reset()
    r = solve_run()
    assert r["correct"], r["checks"]
    got = {name: harness.load_reader(name)({}) for name in READERS}
    for name, value in got.items():
        assert value is not None and value > 0.0, (name, got)
    setup_s = r["metrics"]["setup_s"]["value"]
    assert got["probe_s.dblp"] + got["layout_s.dblp"] <= setup_s, got
    assert tracing.totals()["service.layout"]["count"] == 1


def test_span_readers_read_nothing_before_any_span():
    tracing.reset()
    for name in READERS:
        assert harness.load_reader(name)({}) is None, name


def test_span_readers_read_nothing_from_a_program_without_spans(
        monkeypatch):
    # the program as it was before it had a span recorder
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    for name in READERS:
        assert harness.load_reader(name)({}) is None, name
