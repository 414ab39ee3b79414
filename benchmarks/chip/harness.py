"""The chip benchmark's harness: finds a cell's pieces by name, runs its
runner, reads its per-layer metrics and prints the result line.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a
configuration and a traffic mix.  The configuration's ``file`` holds
its sizes; the traffic mix is ``traffic/<name>.json``, whose ``runner``
names the module ``runner_<runner>.py`` that runs it; each per-layer
metric is read by ``metrics/<metric name>.py``.  Adding a cell, a mix
or a metric adds files; no file here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(Exception):
    """No accelerator of the kind the cell needs: no result is printed."""


@dataclasses.dataclass
class Check:
    """One number compared with its limit: it passes when it is a number
    no larger than the limit."""

    name: str
    value: float | None
    limit: float

    @property
    def ok(self) -> bool:
        return self.value is not None and self.value <= self.limit


class CompileClock:
    """Compile seconds (trace + lower + backend compile), backend
    compiles and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += secs
        if event == _BACKEND_COMPILE:
            self.backend_compiles += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s,
                "backend_compiles": self.backend_compiles,
                "cache_hits": self.cache_hits}


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), so that only a cell's
    first run in a checkout compiles; returns its directory."""
    import jax

    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # no size limit: a limited cache takes a file lock on every read and
    # write, and the run's threads would wait on one another for it
    jax.config.update("jax_compilation_cache_max_size", -1)
    # keep every program, not only those that take a second to compile,
    # so that a later run compiles none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def resolve_cell(spec: dict, workload: str):
    """(workload entry, configuration data, traffic data) of a cell."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     f"{cell['traffic']}.json"))
    return cell, config, traffic


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: end-to-end ones without a trace,
    per-layer ones with it; each only where its ``workloads`` (if
    any) names the cell."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in spec[key]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(metric: str):
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def require_chip(chips: int) -> None:
    """Raise :class:`NoChip` unless JAX sees at least ``chips`` TPU
    devices and the served path resolves to compiled Pallas kernels."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is on platform "
                     f"{devs[0].platform!r}; the benchmark runs only on "
                     "a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    from repro.core import backend as backend_mod

    env = os.environ.get("REPRO_BACKEND", "")
    if env and env != "pallas":
        raise NoChip(f"REPRO_BACKEND={env!r} would move the served path "
                     "off the Pallas kernels")
    if (backend_mod.resolve_backend("auto") != "pallas"
            or backend_mod.kernel_interpret()):
        raise NoChip("the served path does not resolve to compiled "
                     "Pallas kernels")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


class RunContext:
    """What a runner gets: the cell's data, the run's arguments, spans,
    the compile clock and the traced window."""

    def __init__(self, workload: str, cell: dict, config: dict,
                 traffic: dict, seed: int, seconds: float, trace: bool,
                 t_start: float, clock: CompileClock | None):
        self.workload = workload
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.clock = clock
        self.trace_summary: dict | None = None
        self.window_s: float | None = None
        self.device: dict | None = None

    def info(self, msg: str) -> None:
        print(msg, flush=True)

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op when none)."""
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def compile_snapshot(self) -> dict:
        return self.clock.snapshot() if self.clock else {}

    def read_device(self) -> dict:
        """Device, count and peak memory; call once the window closed
        and before any reference work."""
        self.device = device_info()
        return self.device

    @contextlib.contextmanager
    def traced_window(self, kernels: dict, modules: dict):
        """Profile the enclosed window when ``--trace 1``: afterwards
        ``trace_summary`` holds the reduced device numbers and
        ``window_s`` the traced length."""
        if not self.trace:
            yield
            return
        import jax

        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(tdir)
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.window_s = time.perf_counter() - t0
                jax.profiler.stop_trace()
            from jax.profiler import ProfileData

            import trace_reduce

            t_red = time.perf_counter()
            pd = ProfileData.from_file(trace_reduce.find_xplane(tdir))
            self.trace_summary = trace_reduce.reduce_profile(
                pd, kernels, modules)
            self.info(f"trace: reduced in "
                      f"{time.perf_counter() - t_red:.2f} s; kernels "
                      f"{json.dumps(self.trace_summary['kernels'])}")
        finally:
            shutil.rmtree(tdir, ignore_errors=True)


def import_runner(name: str):
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    return importlib.import_module(f"runner_{name}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             config_overrides: dict | None = None,
             check_chip: bool = True, t_start: float | None = None) -> dict:
    """Run one cell and return its result line (a dict).  Prints the
    runner's observations on stdout as it goes.  ``check_chip=False``
    (tests) skips the look for a TPU."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec()
    cell, config, traffic = resolve_cell(spec, workload)
    config = {**config, **(config_overrides or {})}
    if check_chip:
        require_chip(int(cell["chips"]))
    clock = CompileClock()
    ctx = RunContext(workload, cell, config, traffic, seed, seconds, trace,
                     t_start, clock)
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    out = import_runner(traffic["runner"]).run(ctx)
    device = ctx.device or ctx.read_device()
    checks: list[Check] = out["checks"]
    correct = all(c.ok for c in checks)
    wanted = cell_metrics(spec, workload, trace)
    metrics = {}
    if trace:
        if ctx.trace_summary is None:
            raise RuntimeError("--trace 1 but the runner traced no window")
        device = {**device, "busy_s": ctx.trace_summary["busy_s"],
                  "window_s": ctx.window_s}
        layer_ctx = {**out.get("layer", {}), "trace": ctx.trace_summary,
                     "window_s": ctx.window_s, "device_kind": device["kind"]}
        for m in wanted:
            value = load_reader(m["name"])(layer_ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {**out["e2e"], "setup_s": out["setup_s"]}
        for m in wanted:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = {
            "device_ops": ctx.trace_summary["device_ops"],
            "idle_gaps": ctx.trace_summary["idle_gaps"]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                                 "pass": c.ok} for c in checks}
    return result


def print_result(result: dict) -> None:
    """Each compared number beside its limit as the last lines of
    stderr, then the result as the last line of stdout."""
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit <= {c['limit']!r}) "
              f"{'ok' if c['pass'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
