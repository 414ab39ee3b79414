"""Request-level serving over :class:`~repro.stream.service.StreamingService`.

``Server`` is the in-process front end (the HTTP shell in
:mod:`repro.serve.http` is a thin adapter over it) exposing the five
request types — **admit** a graph, **push** an edge batch, query
**labels**, query a session **summary**, **evict** — with the process
concerns the library never had:

* **async ingest / tick pipeline** (``pipeline="double_buffer"``, the
  default): pushes DO NOT touch the solve engine.  Each push merges its
  edges into a host-side staging buffer (mode-aware last-write-wins /
  accumulate semantics per edge key, so N pushes against one session
  flush as one coalesced ``apply_edge_batch`` instead of N) and returns
  immediately.  A dedicated engine thread swaps the double buffer each
  iteration — ingest keeps filling the fresh front buffer while the
  engine drains the back buffer and runs the scheduled device tick —
  so ingest and ticking no longer serialize.
  ``pipeline="serialized"`` is the pre-pipeline baseline (each push
  applies inline under the engine lock, contending with device ticks);
  it exists for the A/B comparison in ``benchmarks/bench_serve.py``.
* **versioned reads**: ``labels``/``summary`` are served from the last
  committed :class:`~repro.serve.results.VersionedResults` version —
  monotonic version ids, stable cluster ids (the store's own
  per-session tracker), and NO engine lock on the query path, so a
  slow device tick never stalls a read.
* **observability**: per-request-type latency histograms (p50/p99 via
  :mod:`repro.serve.metrics`) and the ``push_wait`` queue-wait
  histogram, pipeline counters (staged / applied / dropped batches,
  commits, ticks), queue-depth / tick-utilization gauges, and the
  per-name totals of the :mod:`repro.tracing` spans the engine and the
  service record, all surfaced by :meth:`Server.stats`.

Thread model: ONE engine thread owns every ``StreamingService`` call
(the engine lock exists only because ``admit``/``evict``/serialized
pushes run on request threads); any number of request threads stage
pushes and read results concurrently.  Unknown or evicted session ids
raise :class:`~repro.stream.service.UnknownSessionError` end to end —
the HTTP layer maps it to 404.

Engine failure is terminal and loud: an exception escaping the engine
thread's loop (a kernel the device's compiler refuses inside a tick,
say) is recorded, the thread ends, and from then on every request,
``flush``, ``wait_converged`` and ``stop`` raise :class:`EngineError`
chained to it — a dead engine never serves stale versions as healthy.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from repro import tracing
from repro.core import laplacian as lap
from repro.serve.metrics import ServeMetrics
from repro.serve.results import VersionedResults
from repro.stream.service import (
    ServiceConfig,
    StreamingService,
    UnknownSessionError,
    panel_labels,
)

REQUEST_OPS = ("admit", "push", "labels", "summary", "evict")
PIPELINES = ("double_buffer", "serialized")


class EngineError(RuntimeError):
    """The engine thread died; its exception is the ``__cause__``."""


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    service: ServiceConfig = ServiceConfig()
    pipeline: str = "double_buffer"  # | "serialized" (A/B baseline)
    idle_sleep_s: float = 0.002  # engine-thread wait when nothing to do
    drop_evicted_results: bool = False  # True = free memory eagerly

    def __post_init__(self):
        if self.pipeline not in PIPELINES:
            raise ValueError(f"unknown pipeline {self.pipeline!r}")


class _PendingBuffer:
    """Host-side accumulation of staged edge updates for one session.

    Merge semantics reproduce sequential application order per edge key
    (keys are canonicalized (min, max) pairs, matching the store):
    ``set`` overwrites whatever is pending, ``add`` accumulates onto a
    pending value of either mode.  Flushing yields at most one batch
    per mode, so a burst of pushes costs one ``apply_edge_batch`` each.
    """

    __slots__ = ("slots", "batches_staged", "t_first")

    def __init__(self):
        self.slots: dict[tuple[int, int], list] = {}
        self.batches_staged = 0
        self.t_first = time.perf_counter()  # queue wait starts here

    def merge(self, edges: np.ndarray, weights: np.ndarray,
              mode: str) -> int:
        self.batches_staged += 1
        slots = self.slots
        for (a, b), w in zip(edges, weights):
            key = (int(a), int(b)) if a <= b else (int(b), int(a))
            slot = slots.get(key)
            if mode == "set" or slot is None:
                slots[key] = [mode, float(w)]
            else:
                slot[1] += float(w)
        return len(edges)

    def flush_batches(self):
        """Yield (edges, weights, mode) — one coalesced batch per mode."""
        by_mode: dict[str, tuple[list, list]] = {}
        for (a, b), (mode, w) in self.slots.items():
            pairs, ws = by_mode.setdefault(mode, ([], []))
            pairs.append((a, b))
            ws.append(w)
        for mode, (pairs, ws) in by_mode.items():
            yield (np.asarray(pairs, np.int64),
                   np.asarray(ws, np.float32), mode)


class Server:
    """In-process serving front end; see the module docstring."""

    def __init__(self, cfg: ServerConfig = ServerConfig()):
        self.cfg = cfg
        self.service = StreamingService(cfg.service)
        self.results = VersionedResults()
        self.metrics = ServeMetrics(REQUEST_OPS + ("push_wait",))
        self._engine_lock = threading.RLock()
        self._stage_lock = threading.Lock()
        self._front: dict[str, _PendingBuffer] = {}
        self._known: set[str] = set()
        self._labelers: dict[str, object] = {}
        self._wake = threading.Event()
        self._drain_cond = threading.Condition()
        self._drained_seq = 0
        # (clock, engine.tick seconds) at start() and at stop()
        self._ticks_from: tuple[float, float] | None = None
        self._ticks_to: tuple[float, float] | None = None
        self._stop_flag = False
        self._thread: threading.Thread | None = None
        self._engine_error: Exception | None = None

    @property
    def engine_error(self) -> Exception | None:
        """The exception that killed the engine thread, if any."""
        return self._engine_error

    def _check_engine(self) -> None:
        err = self._engine_error
        if err is not None:
            raise EngineError(
                f"engine thread died: {type(err).__name__}: {err}") from err

    # ------------------------------------------------------------------
    # request API
    # ------------------------------------------------------------------

    def admit(self, sid: str, edges, num_nodes: int, weights=None,
              num_clusters: int | None = None,
              edge_capacity: int | None = None,
              resume_panel=None) -> dict:
        """Admit a graph; commits result version 1 immediately, so
        labels/summary are queryable before the first tick lands."""
        self._check_engine()
        with self.metrics.timed("admit"):
            edges = np.asarray(edges, np.int64).reshape(-1, 2)
            g = lap.make_edge_list(edges, int(num_nodes), weights=weights)
            svc_cfg = self.cfg.service
            clusters = num_clusters or svc_cfg.num_clusters
            with self._engine_lock:
                self.service.add_graph(
                    sid, g, num_clusters=num_clusters,
                    edge_capacity=edge_capacity,
                    resume_panel=resume_panel)
                self.results.register(sid, clusters)
                version = self._commit(sid)
            labeler = lambda panel: panel_labels(
                panel, clusters, drop_trivial=svc_cfg.drop_trivial,
                seed=svc_cfg.seed,
                kmeans_restarts=svc_cfg.kmeans_restarts)
            with self._stage_lock:
                self._known.add(sid)
                self._labelers[sid] = labeler
            self.metrics.inc("admitted")
            self._wake.set()
            summary = self.summary_unmetered(sid)
            summary["version"] = version
            return summary

    def push(self, sid: str, edges, weights, mode: str = "set") -> dict:
        """Stage (or, serialized pipeline, apply) one edge batch."""
        self._check_engine()
        with self.metrics.timed("push"):
            if mode not in ("set", "add"):
                raise ValueError(f"unknown update mode {mode!r}")
            edges = np.asarray(edges, np.int64).reshape(-1, 2)
            weights = np.atleast_1d(np.asarray(weights, np.float32))
            if len(weights) != len(edges):
                raise ValueError(
                    f"{len(edges)} edges but {len(weights)} weights")
            if self.cfg.pipeline == "serialized":
                with self._engine_lock:
                    stats = self.service.apply_updates(
                        sid, edges, weights, mode=mode)
                    version = self._commit(sid)
                self.metrics.inc("applied_batches")
                return {"staged": 0, "applied": int(len(edges)),
                        "matched": int(stats.matched),
                        "version": version, "queue_depth": 0}
            with self._stage_lock:
                if sid not in self._known:
                    raise UnknownSessionError(sid)
                buf = self._front.setdefault(sid, _PendingBuffer())
                n = buf.merge(edges, weights, mode)
                depth = sum(len(b.slots) for b in self._front.values())
            self.metrics.inc("staged_batches")
            self.metrics.set_gauge("queue_depth", depth)
            self._wake.set()
            return {"staged": n, "applied": 0,
                    "version": self.results.version(sid),
                    "queue_depth": depth}

    def labels(self, sid: str) -> dict:
        """Stable-id cluster assignment of the last committed version.

        Served entirely from the versioned results store: no engine
        lock, and repeated queries at one version are cached."""
        self._check_engine()
        with self.metrics.timed("labels"):
            with self._stage_lock:
                labeler = self._labelers.get(sid)
            if labeler is None:
                raise UnknownSessionError(sid)
            lab, version, churn = self.results.labels(sid, labeler)
            return {"sid": sid, "version": version, "churn": churn,
                    "labels": lab}

    def summary(self, sid: str) -> dict:
        """Last committed session summary (carries its version)."""
        with self.metrics.timed("summary"):
            return self.summary_unmetered(sid)

    def summary_unmetered(self, sid: str) -> dict:
        self._check_engine()
        out = self.results.summary(sid)
        out["sid"] = sid
        return out

    def evict(self, sid: str) -> dict:
        """Remove a session; staged-but-undrained batches are dropped
        (counted in ``dropped_batches``).  The returned summary carries
        the live panel for ``admit(resume_panel=...)`` re-admission."""
        self._check_engine()
        with self.metrics.timed("evict"):
            with self._stage_lock:
                self._known.discard(sid)
                self._labelers.pop(sid, None)
                pending = self._front.pop(sid, None)
            if pending is not None:
                self.metrics.inc("dropped_batches",
                                 pending.batches_staged)
            with self._engine_lock:
                summary = self.service.evict(sid)
            self.results.evict(sid, drop=self.cfg.drop_evicted_results)
            self.metrics.inc("evicted")
            return summary

    def stats(self) -> dict:
        """Observability snapshot: latency histograms, pipeline
        counters/gauges, engine and results-store state."""
        snap = self.metrics.snapshot()
        snap["gauges"]["tick_utilization"] = self._tick_utilization()
        snap["spans"] = tracing.totals()
        snap["results"] = self.results.stats()
        err = self._engine_error
        snap["engine_error"] = (None if err is None
                                else f"{type(err).__name__}: {err}")
        with self._engine_lock:
            svc = self.service
            snap["engine"] = {
                "sessions": len(svc.session_ids()),
                "all_converged": svc.all_converged,
                "compile_count": svc.compile_count,
                "tick_invocations": svc.tick_invocations,
                "device_work": svc.device_work,
                "multiplied_ticks": svc.multiplied_ticks,
                "layout_pad_share": svc.layout_pad_share,
                # MXU passes per chunk of the node-blocked kernel the
                # tick runs: one at every panel width (0: no kernel)
                "scatter_passes": int(svc.backend == "pallas"),
            }
        return snap

    # ------------------------------------------------------------------
    # the ingest/tick pipeline
    # ------------------------------------------------------------------

    def step(self) -> bool:
        """One engine iteration: swap the double buffer, apply the
        drained batches, run one scheduled tick, commit touched
        versions.  Returns True when any work happened.  The background
        thread calls this in a loop; tests may drive it manually on an
        un-started server.  An idle iteration (nothing staged, no tick
        due) records no span."""
        with self._stage_lock:
            staged, self._front = self._front, {}
        drained, ticked = 0, {}
        with self._engine_lock:
            if staged or self._tick_due():
                with tracing.span("engine.step"):
                    touched, drained = self._drain(staged)
                    if self._tick_due():
                        with tracing.span("engine.tick"):
                            ticked = self.service.tick()
                        self.metrics.inc("ticks")
                    for sid in {*touched, *ticked}:
                        try:
                            self._commit(sid)
                        except UnknownSessionError:
                            pass  # raced an eviction; tombstone served
        if staged:
            with self._stage_lock:
                depth = sum(len(b.slots) for b in self._front.values())
            self.metrics.set_gauge("queue_depth", depth)
        with self._drain_cond:
            self._drained_seq += 1
            self._drain_cond.notify_all()
        return bool(drained or ticked)

    def _tick_due(self) -> bool:
        svc = self.service
        return bool(svc.session_ids()) and not svc.all_converged

    def _drain(self, staged: dict) -> tuple[list[str], int]:
        """Apply the swapped-out buffers — caller holds the engine lock.
        Returns (touched sids, staged batches applied)."""
        touched: list[str] = []
        drained = 0
        if not staged:
            return touched, drained
        with tracing.span("engine.drain"):
            # Per-capacity-class drain batching: staged sessions group
            # by their (node, edge) capacity class, and each class pins
            # ONE batch pad (pow2 of its largest coalesced batch) for
            # every member's apply.  The compiled edge-batch apply keys
            # on (capacity class, pad, mode), so the whole class drains
            # through one compiled apply per mode instead of one per
            # pow2 batch size per session.
            classes: dict[tuple, list] = {}
            for sid, buf in staged.items():
                try:
                    ck = self.service.capacity_class(sid)
                except UnknownSessionError:
                    self.metrics.inc("dropped_batches",
                                     buf.batches_staged)
                    continue
                classes.setdefault(ck, []).append(
                    (sid, buf, list(buf.flush_batches())))
            if classes:
                self.metrics.inc("drain_classes", len(classes))
            for members in classes.values():
                pad = max((len(edges) for _, _, batches in members
                           for edges, _, _ in batches), default=0)
                for sid, buf, batches in members:
                    self.metrics.record("push_wait",
                                        time.perf_counter() - buf.t_first)
                    try:
                        for edges, ws, mode in batches:
                            self.service.apply_updates(
                                sid, edges, ws, mode=mode, pad_to=pad)
                        touched.append(sid)
                        drained += buf.batches_staged
                        self.metrics.inc("applied_batches",
                                         buf.batches_staged)
                    except UnknownSessionError:
                        self.metrics.inc("dropped_batches",
                                         buf.batches_staged)
        return touched, drained

    @tracing.traced("engine.commit")
    def _commit(self, sid: str) -> int:
        """Version commit point — caller holds the engine lock."""
        summary = self.service.session_info(sid)
        version = self.results.commit(sid, summary,
                                      self.service.panel(sid))
        self.metrics.inc("commits")
        return version

    def _tick_utilization(self) -> float:
        """Share of the time since start() (up to stop(), once stopped)
        spent in ``engine.tick`` spans; 0.0 before start().  The span
        totals are process-wide, so servers ticking at once in one
        process share them."""
        if self._ticks_from is None:
            return 0.0
        t0, busy0 = self._ticks_from
        t1, busy1 = self._ticks_to or self._tick_clock()
        return (busy1 - busy0) / max(t1 - t0, 1e-9)

    @staticmethod
    def _tick_clock() -> tuple[float, float]:
        busy = tracing.totals().get("engine.tick", {}).get("total_s", 0.0)
        return time.perf_counter(), busy

    def _serve_loop(self) -> None:
        try:
            while not self._stop_flag:
                if not self.step():
                    self._wake.wait(timeout=self.cfg.idle_sleep_s)
                    self._wake.clear()
            self.step()  # final drain: stop() loses no staged update
        except Exception as e:  # the engine boundary: record, then die
            self._engine_error = e
            with self._drain_cond:  # wake flush() waiters to see it
                self._drain_cond.notify_all()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "Server":
        if self.running:
            raise RuntimeError("server already started")
        self._stop_flag = False
        self._ticks_from, self._ticks_to = self._tick_clock(), None
        self._thread = threading.Thread(
            target=self._serve_loop, name="serve-engine", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Stop the engine thread after a final drain (clean shutdown:
        every staged batch is applied or counted dropped).  Raises
        :class:`EngineError` when the engine thread had died."""
        if self._thread is not None:
            self._stop_flag = True
            self._wake.set()
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                raise RuntimeError("engine thread did not stop in time")
            self._thread = None
            self._ticks_to = self._tick_clock()
        self._check_engine()

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def flush(self, timeout: float = 60.0) -> bool:
        """Block until every batch staged before the call has been
        drained (applied or dropped).  Returns False on timeout; raises
        :class:`EngineError` once the engine thread has died."""
        self._check_engine()
        if not self.running:
            self.step()
            return True
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self._check_engine()
            with self._stage_lock:
                pending = bool(self._front)
            with self._drain_cond:
                seq = self._drained_seq
            self._wake.set()
            with self._drain_cond:
                ok = self._drain_cond.wait_for(
                    lambda: (self._drained_seq > seq
                             or self._engine_error is not None),
                    timeout=max(deadline - time.monotonic(), 0.0))
            self._check_engine()
            if not pending and ok:
                # an empty front buffer followed by one full step
                # boundary: any in-flight drain has landed
                with self._stage_lock:
                    if not self._front:
                        return True
        return False

    def wait_converged(self, timeout: float = 120.0) -> bool:
        """Block until staged work is drained AND every session's panel
        is at tolerance (the bench's equal-residual-target barrier)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self.flush(timeout=max(deadline - time.monotonic(),
                                          0.0)):
                return False
            self._check_engine()
            with self._engine_lock:
                done = self.service.all_converged
            with self._stage_lock:
                pending = bool(self._front)
            if done and not pending:
                return True
            if not self.running:
                self.step()
            else:
                time.sleep(0.005)
        return False


__all__ = ["EngineError", "PIPELINES", "REQUEST_OPS", "Server",
           "ServerConfig"]
