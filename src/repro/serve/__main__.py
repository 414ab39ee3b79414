"""Process shell: ``python -m repro.serve`` boots the HTTP front end.

Prints one parseable banner line — ``SERVING host=<h> port=<p>`` — once
the socket is bound (port 0 picks a free port, so harnesses read the
banner rather than guessing), then serves until SIGTERM/SIGINT, which
trigger a clean shutdown: the acceptor stops, the engine thread drains
every staged batch, and the process exits 0.  If the engine thread
dies, the process shuts down on its own, prints ``ENGINE FAILED`` with
the cause on stderr and exits 1.  ``scripts/ci.sh`` and the
bench's ``--http-smoke`` lane drive exactly this contract.
"""
from __future__ import annotations

import argparse
import signal
import sys
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="HTTP serving front end for the streaming "
                    "spectral-clustering engine.")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = pick a free port (read the banner)")
    ap.add_argument("--pipeline", default="double_buffer",
                    choices=("double_buffer", "serialized"))
    ap.add_argument("--k", type=int, default=6)
    ap.add_argument("--num-clusters", type=int, default=4)
    ap.add_argument("--degree", type=int, default=15)
    ap.add_argument("--steps-per-tick", type=int, default=20)
    ap.add_argument("--tol", type=float, default=2e-3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # deferred: the banner contract says nothing prints before imports
    # succeed, and jax import cost should not be paid for --help
    from repro.compile_cache import enable_compile_cache
    from repro.serve.http import ServeHTTP
    from repro.serve.server import EngineError, Server, ServerConfig
    from repro.stream.service import ServiceConfig

    enable_compile_cache()

    cfg = ServerConfig(
        service=ServiceConfig(
            k=args.k, num_clusters=args.num_clusters, degree=args.degree,
            steps_per_tick=args.steps_per_tick, tol=args.tol,
            seed=args.seed),
        pipeline=args.pipeline)
    server = Server(cfg)
    front = ServeHTTP(server, host=args.host, port=args.port)
    front.start()
    print(f"SERVING host={front.host} port={front.port}", flush=True)

    done = threading.Event()

    def _stop(signum, frame):
        done.set()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    while not done.wait(timeout=0.5):
        if server.engine_error is not None:
            break
    try:
        front.stop()
    except EngineError as e:
        print(f"ENGINE FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print("STOPPED", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
