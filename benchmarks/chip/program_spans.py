"""The program's own spans (``repro.tracing``), read in the process
that ran the cell, after the run.

The recorder is process-wide, so what a run's program recorded is
still there when the per-layer metrics are read.  A program without
the recorder, or a run in which the span never closed, reads None.
"""
from __future__ import annotations


def _tracing():
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


def total_s(name: str) -> float | None:
    """Seconds summed over every ``name`` span of the run."""
    tracing = _tracing()
    entry = tracing.totals().get(name) if tracing else None
    return entry["total_s"] if entry else None


def compile_s() -> float | None:
    """Program-loading seconds (trace, lower, backend compile or cache
    retrieval) summed over every span of the run; each compile counts
    in its innermost span only, so this counts it once."""
    tracing = _tracing()
    totals = tracing.totals() if tracing else {}
    if not totals:
        return None
    return sum(t["compile_s"] for t in totals.values())


def records() -> list:
    """The recorder's span records, oldest first ([] without one)."""
    tracing = _tracing()
    return tracing.records() if tracing else []
