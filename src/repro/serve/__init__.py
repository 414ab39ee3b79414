"""Request-level serving layer over the streaming engine.

- server.py  — ``Server``: admit/push/labels/summary/evict API with the
  double-buffered async ingest/tick pipeline (and the ``serialized``
  A/B baseline).
- results.py — ``VersionedResults``: monotonic result versions, stable
  cluster ids, lazy label materialization; reads never touch the engine.
- metrics.py — ``ServeMetrics``: per-request latency histograms
  (p50/p99), pipeline counters, gauges.
- http.py    — ``ServeHTTP``: stdlib JSON-over-HTTP front end
  (``UnknownSessionError`` -> 404, ``ValueError`` -> 400,
  ``EngineError`` -> 503).
- __main__.py — ``python -m repro.serve`` process shell with clean
  SIGTERM shutdown (non-zero exit after an engine failure).
"""
from repro.serve.metrics import LatencyHistogram, ServeMetrics
from repro.serve.results import ResultVersion, VersionedResults
from repro.serve.server import EngineError, Server, ServerConfig

__all__ = [
    "EngineError",
    "LatencyHistogram",
    "ResultVersion",
    "ServeMetrics",
    "Server",
    "ServerConfig",
    "VersionedResults",
]
