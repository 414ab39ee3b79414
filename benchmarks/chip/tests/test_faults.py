"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives a whole run of
a cell at a tiny size on the CPU, with one fault planted in the program
below the benchmark: a tick that returns its panels unchanged, and an
answer (a committed panel) altered where it is produced.  The same run
without a fault is correct.  One chip has no exchange between chips to
leave out, and one tenant no batch to halve; the lowered-precision
control is in ``test_control.py``.
"""
import harness
from repro.core import program
from repro.stream import service as service_mod

SOLVE_GRAPH = {"generator": "sparse_sbm", "num_nodes": 4000,
               "num_blocks": 4, "avg_degree_in": 6.0,
               "avg_degree_out": 0.6, "min_degree": 3,
               "graph_seed": 0}


def solve_run(seed=11):
    return harness.run_cell("dblp.solve", seed, 4, False, check_chip=False,
                            config_overrides={"graph": SOLVE_GRAPH})


def unchanged_ticks(monkeypatch):
    build = program.build_tick_program

    def broken_build(*a, **k):
        fn = build(*a, **k)

        def tick(*args):
            vs, res = fn(*args)
            # the panels come back as they went in: (vs, cs, lrs, chunks)
            # are the last four arguments of every tick signature
            return args[-4], res
        return tick

    monkeypatch.setattr(program, "build_tick_program", broken_build)


def test_solve_sound_run_is_correct():
    r = solve_run()
    assert r["correct"], r["checks"]
    assert r["metrics"]["solve_dec_per_s"]["value"] > 0


def test_solve_unchanged_state_is_not_correct(monkeypatch):
    unchanged_ticks(monkeypatch)
    r = solve_run()
    assert not r["correct"]
    assert not r["checks"]["sin_last"]["pass"]


def test_solve_altered_panel_is_not_correct(monkeypatch):
    panel = service_mod.StreamingService.panel

    def altered(self, sid):
        v = panel(self, sid)
        return v[::-1]  # rows no longer belong to their nodes

    monkeypatch.setattr(service_mod.StreamingService, "panel", altered)
    r = solve_run()
    assert not r["correct"]
