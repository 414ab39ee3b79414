"""Building the system under test from a configuration file."""
from __future__ import annotations

import time

from recording import RecordingResults


def make_server(config: dict):
    """A ``Server`` under the configuration's ``service`` fields (empty:
    the default ``ServerConfig()``), with a recording results store."""
    from repro.serve import Server, ServerConfig
    from repro.stream.service import ServiceConfig

    service = ServiceConfig(**config.get("service", {}))
    srv = Server(ServerConfig(service=service))
    srv.results = RecordingResults()
    return srv


def wait_until(t_abs: float, srv) -> None:
    """Sleep until perf_counter() reaches ``t_abs``, raising as soon as
    the engine thread has died."""
    while True:
        left = t_abs - time.perf_counter()
        if left <= 0:
            return
        if srv.engine_error is not None:
            raise RuntimeError("engine thread died") from srv.engine_error
        time.sleep(min(left, 0.05))
