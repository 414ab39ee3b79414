"""Multi-tenant streaming clustering service.

Owns many mutable graphs (stream.graph_store), each with a live
eigenvector panel, and advances them with BATCHED jitted ticks built by
:mod:`repro.core.program` — the ONE solve loop shared with the one-shot
solver, the warm reconvergence path, and the distributed solves:

  * Sessions are grouped by CAPACITY CLASS — (node_cap, edge_cap) — plus
    their scheduled dilation DEGREE (and, on pallas, the node-blocking
    layout), and every group tick is ONE compiled `SolveProgram`
    invocation over the group's stacked edge buffers and panels.  Shapes
    never depend on a session's live edge count or real node count, so
    admitting graph #9 to a class that already ticked reuses the
    compiled step (no per-session recompilation).  Groups are padded to
    power-of-two occupancy of their ACTIVE (unconverged) members, so the
    compiled-program set stays logarithmic while converged sessions cost
    ZERO device work per tick.
  * The per-session operator is the dilated reversed Laplacian
    (I - c L)^degree — the paper's limit_neg_exp series with λ* = 0 —
    scheduled from a real :class:`~repro.spectral.plan.DilationPlan`:
    admission/re-solve probes (SLQ lambda_max + bottom-edge gap) feed
    ``plan_dilation``, which picks the per-session strength tau (capped
    by the wanted-decay guard and the configured ceiling), the
    per-CLASS degree (snapped onto the planner grid, re-planned on
    admission drift — a new tenant needing more dilation raises the
    class degree), and the per-session lr (``plan.suggested_lr``,
    normalized to the unit-scale program form).  The dilation scale c
    and lr are TRACED per-session inputs — different graphs, one
    program.  Wide-gap tenants get identity plans: degree-1 groups that
    spend ONE matvec per operator application.
  * Per-session convergence is the ground-truth-free panel residual;
    converged sessions leave the tick rotation entirely (their groups
    shrink — zero device work), get their eigen estimate anchored
    (stream.updates), and serve labels until edge updates arrive.
    Updates take the cheap first-order eigen-update path and only
    re-enter the solve rotation when accumulated drift triggers the
    fallback, warm-started per stream.warm's restart test.
  * The RESIDUAL-DECAY TICK SCHEDULER (``tick_schedule=
    "residual_decay"``, the default): each session's measured residual
    decay rate forecasts its remaining solver steps
    (core.program.predicted_steps_to_tol).  A session predicted to stay
    far above tolerance after an ordinary tick skips the intermediate
    residual evaluations by riding a MULTIPLIED tick — the multipliers
    are TRACED per-session chunk budgets inside the compiled program
    (members past their own budget freeze under a mask while slower
    peers keep stepping), so scheduling adds ZERO compiles — fewer
    program invocations, fewer eval operator applications, and fewer
    host round-trips to fleet convergence, with identical solver math.
    Because a frozen slot still executes device steps, a group mixing
    plain and stretched members sub-batches into two invocations of
    the same compiled programs when that costs fewer slot-steps
    (``_split_by_multiplier``).  ``tick_schedule="round_robin"``
    restores fixed-size ticks.

Node padding invariant: panels keep EXACT zeros on rows >= the session's
real node count.  No edge ever touches a padding node, and every solver
operation (edge matvec, series recurrence, QR, normalization) maps zero
rows to zero rows, so the padded problem is numerically identical to the
unpadded one.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import backend as backend_mod
from repro.core import kmeans as km
from repro.core import operators, program, solvers
from repro.kernels.edge_spmm import ops as es_ops
from repro.spectral import plan as plan_mod
from repro.spectral import probes as spectral_probes
from repro.stream import graph_store as gs
from repro.stream import tracking, updates


_next_pow2 = es_ops.next_pow2

# Families the tick programs can execute: the (I - c L)^degree form only
# (identity rides as degree 1 with c = 1/lambda*); cheb recurrences need
# the series evaluator, so the planner weakens tau into the budget
# instead of switching family.
_TICK_FAMILIES = ("identity", "limit_neg_exp")


def node_capacity_class(num_nodes: int) -> int:
    """Node-count capacity class (power of two >= num_nodes)."""
    return max(_next_pow2(num_nodes), 64)


def _split_by_multiplier(members: list, mults: np.ndarray) -> list:
    """Sub-batch a tick group so short-budget members don't ride a
    long invocation.  The shared program's device cost is occupancy x
    the LARGEST member budget — short-budget members freeze under the
    per-session chunk mask (``core.program``) but their slots still
    step — so batching a plain (multiplier-1) member with a stretched
    one executes the stretched member's whole budget in the plain
    member's slot for nothing.  Members bucket by pow2 of their
    multiplier (within-bucket waste stays under 2x), then adjacent
    buckets greedily re-merge whenever pow2 occupancy padding makes
    the joint invocation no dearer in slot-steps (e.g. 1 plain + 7
    stretched pads to occupancy 8 either way).  Sub-batches reuse the
    same compiled programs at smaller occupancy buckets; singleton and
    uniform-multiplier groups never split."""
    buckets: dict[int, list[int]] = {}
    for i, m in enumerate(mults):
        buckets.setdefault((int(m) - 1).bit_length(), []).append(i)
    if len(buckets) == 1:
        return [(members, mults)]
    subs = [idx for _, idx in sorted(buckets.items())]
    merged = [subs[0]]
    for idx in subs[1:]:
        prev = merged[-1]
        cost_split = (_next_pow2(len(prev)) * int(mults[prev].max())
                      + _next_pow2(len(idx)) * int(mults[idx].max()))
        cost_joint = (_next_pow2(len(prev) + len(idx))
                      * int(mults[idx].max()))
        if cost_joint <= cost_split:
            merged[-1] = prev + idx
        else:
            merged.append(idx)
    return [([members[i] for i in s], mults[s]) for s in merged]


class UnknownSessionError(KeyError):
    """An operation referenced a session id that was never admitted or
    was already evicted.

    Subclasses ``KeyError`` for backward compatibility with callers that
    guarded the old raw-dict lookups; the serving layer
    (:mod:`repro.serve`) relies on the typed class to map these to 404
    responses instead of a generic 500.
    """

    def __init__(self, sid: str):
        super().__init__(sid)
        self.sid = sid

    def __str__(self) -> str:  # KeyError.__str__ repr-quotes the arg
        return f"unknown or evicted session {self.sid!r}"


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    k: int = 6  # eigenvectors tracked per session
    num_clusters: int = 4  # default clusters served per session
    method: str = "mu_eg"  # solver step: "mu_eg" | "oja"
    lr: float = 0.3  # base step size (per-session values trace over it)
    degree: int = 15  # odd; BUDGET for the planned per-class degree
    dilation_strength: float = 8.0  # ceiling on the planned tau
    steps_per_tick: int = 20  # solver steps per session per tick
    tol: float = 2e-3  # panel-residual convergence target
    restart_residual: float = 0.6  # warm.py restart test
    fallback_ratio: float = 0.5  # updates.py drift fallback
    min_batch_pad: int = 16  # update batches pad to pow2 >= this
    drop_trivial: bool = True  # skip the all-ones nullvector in embeddings
    kmeans_restarts: int = 8
    seed: int = 0
    # SLQ spectral probing (repro.spectral): a tight lambda_max estimate
    # replaces the Gershgorin 2*max_degree bound when setting the
    # dilation scale — the bound over-estimates by ~2x on dense graphs,
    # silently halving the dilation.  Probes run on session admission
    # and on drift-triggered re-solves; ordinary update batches keep the
    # cheap bound-only rescale.  The bound always survives as cap (it is
    # certain; the probe is not) and as fallback when probing is off.
    probe_spectrum: bool = True
    probe_vectors: int = 2  # SLQ probe vectors per (re-)probe
    probe_steps: int = 16  # Lanczos steps per probe vector
    # Matvec backend for tick programs and probes (repro.core.backend):
    # "auto" = pallas on TPU, segment elsewhere.  Pallas ticks run the
    # node-blocked incidence-SpMM kernel with the dilation step fused
    # into its epilogue; the per-session blocking is built on admission
    # and rebuilt after edge updates (graph_store.node_blocking), and
    # sessions group by (capacity class, degree, blocking layout) — the
    # chunk count is snapped (es_ops.snap_chunk_count), so compile
    # counts stay logarithmic.
    backend: str = "auto"
    tick_block_n: int = 512  # node-block rows per VMEM panel slice
    # Device mesh for SHARDED serving (core.program sharded builders):
    # when set, every group tick runs as one shard_mapped fused series
    # program with the group's edge buffers (segment) or per-shard node
    # blockings (pallas) partitioned over `edge_axes`, one psum of the
    # stacked panels per dilation matvec, and admission probes routed
    # through the same sharded matvec.  Admission/growth round edge
    # capacities up to a multiple of the shard count so shard slices
    # stay balanced.  None = single-device ticks (the default).
    mesh: object | None = None
    edge_axes: tuple = ("data",)
    # PANEL sharding (core.program.build_tick_model_sharded): when set
    # (with a mesh), every group tick shards the (n, k) panel itself
    # over these mesh axes — shard s owns rows [s*R, (s+1)*R) and the
    # destination-aligned half-edges landing there
    # (graph_store.model_sharded_blocking) — and mu-EG solver steps ship
    # their row assembly and 2k x 2k gram in ONE fused collective.  This
    # is the million-node serving mode: no device ever materializes
    # per-shard panel copies of the edge buffer, and admission probes
    # route through the same row-sharded matvec.  None = replicated
    # panels (edge sharding over `edge_axes` if a mesh is set).
    model_axes: tuple | None = None
    # Residual-decay tick scheduling: "residual_decay" forecasts each
    # SESSION's remaining solver steps from its measured residual decay
    # and gives it its own chunk budget (a TRACED per-session count —
    # any mix reuses the group's one compiled program) when it is
    # predicted to stay above `eval_payoff * steps_per_tick` steps from
    # tolerance — the intermediate residual evals would have no payoff.
    # Members past their budget freeze inside the shared program, so a
    # soon-converging member no longer caps its group's cadence.
    # "round_robin" = fixed-size ticks for every group.
    tick_schedule: str = "residual_decay"
    max_tick_multiplier: int = 8  # cap on the scheduled multiplier
    eval_payoff: float = 2.0  # multiply only past this many plain ticks
    # Sessions within this factor of tol cap their multiplier at a
    # gentle 2: the measured decay rate plateaus against the residual
    # floor near convergence (rate -> 1), so forecasts there are
    # unreliable in both directions — a full-forecast stretch executes
    # hundreds of steps for a session one short hop from tolerance,
    # while plain ticks grind out an invocation per hop.
    stretch_residual_floor: float = 4.0

    def __post_init__(self):
        if self.degree % 2 == 0:
            raise ValueError("degree must be odd (limit_neg_exp series)")
        if self.backend not in backend_mod.BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.tick_schedule not in ("round_robin", "residual_decay"):
            raise ValueError(
                f"unknown tick_schedule {self.tick_schedule!r}")
        if self.mesh is not None:
            axes = tuple(self.edge_axes) + tuple(self.model_axes or ())
            missing = [a for a in axes if a not in self.mesh.axis_names]
            if missing:
                raise ValueError(
                    f"mesh axes {missing} not in mesh axes "
                    f"{self.mesh.axis_names}")
        elif self.model_axes is not None:
            raise ValueError("model_axes requires a mesh")


@dataclasses.dataclass
class _Session:
    sid: str
    n: int  # real node count (<= store.num_nodes == node capacity)
    num_clusters: int
    store: gs.GraphStore
    v: jax.Array  # (node_cap, k) panel, zero rows >= n
    plan: plan_mod.DilationPlan  # the session's dilation schedule source
    rho_ub: float  # Gershgorin bound at the time plan.rho was set
    lr: float  # per-session step size (traced into the tick program)
    plan_degree: int  # the session's own planned degree suggestion
    tracker: tracking.LabelTracker
    blocking: es_ops.NodeBlocking | None = None  # pallas tick layout cache
    # per-shard layout cache for sharded pallas ticks; invalidated
    # together with `blocking` on edge mutations
    sharded_blocking: es_ops.ShardedNodeBlocking | None = None
    # destination-aligned layout cache for PANEL-sharded ticks
    # (ServiceConfig.model_axes); same invalidation discipline
    model_blocking: es_ops.ModelShardedBlocking | None = None
    group_key: tuple | None = None  # last tick-group key (introspection)
    est: updates.EigenEstimate | None = None
    converged: bool = False
    residual: float = float("inf")
    rate: float | None = None  # measured per-step residual decay ratio
    ticks: int = 0
    solves: int = 0  # full (re-)solve episodes entered
    incremental_updates: int = 0
    fallbacks: int = 0

    @property
    def rho(self) -> float:
        return self.plan.rho

    @property
    def tau(self) -> float:
        return self.plan.tau


def panel_labels(panel, num_clusters: int, *, drop_trivial: bool = True,
                 seed: int = 0, kmeans_restarts: int = 8) -> np.ndarray:
    """Raw k-means labelling of an (n, k) embedding panel — the
    tracker-free labelling primitive shared by :meth:`StreamingService
    .labels` (which feeds it through the session tracker) and the serve
    layer's versioned results store (which runs its own tracker in
    commit order so served ids stay stable)."""
    panel = jnp.asarray(panel)
    start = 1 if drop_trivial else 0
    emb = panel[:, start: start + num_clusters]
    norms = jnp.linalg.norm(emb, axis=1, keepdims=True)
    emb = emb / jnp.maximum(norms, 1e-12)
    res = km.kmeans(
        jax.random.PRNGKey(seed + 2), emb, num_clusters,
        restarts=kmeans_restarts)
    return np.asarray(res.labels)


@functools.partial(jax.jit, static_argnames=("node_cap", "n", "k"))
def _init_panel(key, node_cap: int, n: int, k: int):
    """Random orthonormal panel supported on the first n rows."""
    v = jax.random.normal(key, (node_cap, k), jnp.float32)
    v = v * (jnp.arange(node_cap) < n)[:, None]
    q, _ = jnp.linalg.qr(v)
    return q


class StreamingService:
    """Session manager: admission, streaming updates, batched ticking,
    label serving, eviction."""

    def __init__(self, cfg: ServiceConfig = ServiceConfig()):
        from repro.stream import sharded as sharded_mod

        self.cfg = cfg
        self._backend = backend_mod.resolve_backend(cfg.backend)
        self._mesh = cfg.mesh
        # panel sharding is orthogonal to edge sharding: model serving
        # re-buckets half-edges by destination shard itself, so the
        # edge-balance contract (and _num_shards) stays on edge_axes
        self._model_axes = (tuple(cfg.model_axes)
                            if cfg.mesh is not None
                            and cfg.model_axes is not None else None)
        self._model_shards = (
            program.num_model_shards(cfg.mesh, self._model_axes)
            if self._model_axes is not None else 1)
        self._num_shards = (
            sharded_mod.num_edge_shards(cfg.mesh, cfg.edge_axes)
            if cfg.mesh is not None else 1)
        self._sessions: dict[str, _Session] = {}
        self._compiled: dict[tuple, object] = {}
        self._admitted = 0
        self._probes_run = 0
        # scheduler/work accounting: program invocations and the
        # device-work slots they spent (occupancy x solver steps) — the
        # witnesses for "converged sessions cost zero device work".
        self._tick_invocations = 0
        self._device_work = 0
        self._multiplied_ticks = 0  # invocations the scheduler stretched
        # over the layouts _ensure_blocking builds: live half-edges and
        # the slots the kernel walks for them (live + chunk padding)
        self._layout_half_edges = 0
        self._layout_slots = 0
        # per-class degree map memo: degrees only move on admission /
        # eviction / re-plans, so status sweeps (session_info per
        # tenant) must not rebuild the map per session — O(S^2) fleets
        self._class_degree_cache: dict[tuple, int] | None = None

    def _get(self, sid: str) -> _Session:
        try:
            return self._sessions[sid]
        except KeyError:
            raise UnknownSessionError(sid) from None

    def has_session(self, sid: str) -> bool:
        return sid in self._sessions

    def session_ids(self) -> list[str]:
        return list(self._sessions)

    def _balanced(self, capacity: int) -> int:
        """Edge capacity rounded up to a shard-balanced size."""
        from repro.stream import sharded as sharded_mod

        if self._num_shards <= 1:
            return capacity
        return sharded_mod.balanced_capacity(capacity, self._num_shards)

    # ------------------------------------------------------------------
    # spectral probing + dilation planning
    # ------------------------------------------------------------------

    @tracing.traced("service.probe")
    def _rho_estimate(self, store: gs.GraphStore, n: int) -> tuple:
        """(refreshed store, rho, rho_ub, lam_k, lam_k1) — plan anchors.

        rho is the SLQ lambda_max estimate capped by the Gershgorin
        bound (the bound is certain, the probe is not); with probing
        disabled — or a degenerate probe — it IS the bound, which keeps
        this path jit-friendly and dependency-free.  lam_k/lam_k1 are
        the probed bottom-edge eigenvalues (None without a probe),
        feeding the planner's strength/degree selection in
        `_plan_session`.  Probe compiles are shared per capacity class
        (fixed edge/node shapes, traced n).
        """
        cfg = self.cfg
        store, rho_ub = gs.spectral_radius_upper_bound(store)
        rho_ub = float(rho_ub)
        rho = rho_ub
        lam_k = lam_k1 = None
        if cfg.probe_spectrum and n > 1:
            self._probes_run += 1
            probe_key = jax.random.fold_in(
                jax.random.PRNGKey(cfg.seed + 7), self._probes_run)
            if self._model_axes is not None:
                # Panel-sharded serving probes through the row-sharded
                # matvec (owned rows per shard, one psum assembly) —
                # the same decomposition the model tick runs.  The
                # probe-time blocking is throwaway (the session builds
                # its own on first tick); probes only run on admission
                # and drift re-solves, so the host-side rebucket is off
                # the tick path.
                mb = gs.model_sharded_blocking(
                    store, self._model_shards,
                    block_n=cfg.tick_block_n)
                probe = spectral_probes.probe_model_sharded(
                    self._mesh, mb, probe_key,
                    jnp.asarray(n, jnp.int32),
                    model_axes=self._model_axes,
                    num_probes=cfg.probe_vectors,
                    num_steps=cfg.probe_steps,
                    backend=self._backend,
                )
            elif self._mesh is not None:
                # Sharded serving probes through the SAME psum-assembled
                # matvec the tick programs run, so the rho anchoring the
                # per-session dilation rescale is measured per shard and
                # agrees with single-device serving up to collective
                # summation order.
                probe = spectral_probes.probe_sharded_edge_arrays(
                    self._mesh, store.src, store.dst, store.weight,
                    probe_key, jnp.asarray(n, jnp.int32),
                    num_nodes=store.num_nodes,
                    edge_axes=cfg.edge_axes,
                    num_probes=cfg.probe_vectors,
                    num_steps=cfg.probe_steps,
                    backend=self._backend,
                )
            else:
                probe = spectral_probes.probe_edge_arrays(
                    store.src, store.dst, store.weight, probe_key,
                    jnp.asarray(n, jnp.int32),
                    num_nodes=store.num_nodes,
                    num_probes=cfg.probe_vectors,
                    # NOT clamped to n: probe_steps is jit-static, and
                    # the Lanczos recurrence handles m >= n via sticky
                    # breakdown, so the compile stays shared across the
                    # capacity class.
                    num_steps=cfg.probe_steps,
                    backend=self._backend,
                )
            est = float(probe.lambda_max)
            if np.isfinite(est) and est > 0.0:
                rho = min(est, rho_ub)
                lam_k, lam_k1 = spectral_probes.bottom_edge(probe, cfg.k)
        return store, rho, rho_ub, lam_k, lam_k1

    def _plan_session(self, sess: _Session, rho: float, rho_ub: float,
                      lam_k: float | None = None,
                      lam_k1: float | None = None) -> None:
        """Re-run the dilation planner on fresh probe anchors.

        The plan carries the session's whole solve schedule: strength
        tau (capped by the wanted-decay guard and
        ``cfg.dilation_strength``), the degree suggestion (snapped onto
        the planner grid, capped by the ``cfg.degree`` budget — the
        class degree is the max over its members' suggestions), and the
        per-session lr (normalized to the plan's wanted-direction scale
        — see ``core.program.session_lr``).
        """
        cfg = self.cfg
        sess.plan = plan_mod.plan_dilation(
            None, k=cfg.k, budget=cfg.degree,
            rho_fallback=rho_ub,
            rho=rho if rho > 0.0 else None,
            lam_k=lam_k, lam_k1=lam_k1,
            tau_cap=cfg.dilation_strength,
            families=_TICK_FAMILIES,
            source="slq" if lam_k is not None else "fallback")
        sess.rho_ub = rho_ub
        sess.plan_degree = (1 if sess.plan.family == "identity"
                            else sess.plan.degree)
        # step size normalized to the plan's WANTED-direction scale
        # (core.program.session_lr): strongly dilated tenants take
        # proportionally larger steps — the lr rides traced, so the
        # per-session values share one compiled program
        sess.lr = program.session_lr(sess.plan, cfg.lr)
        sess.rate = None  # operator changed: stale decay forecast
        self._class_degree_cache = None  # degree suggestion may move

    def _shift_rho(self, sess: _Session, rho_new: float,
                   rho_ub_new: float) -> None:
        """Ordinary-batch rescale: move the plan's rho anchor without
        re-probing (no probe matvecs).  Degenerate plans (edgeless
        admission, rho == 0) re-plan from the fresh bound instead — the
        ratio tracking would pin rho at 0 forever."""
        if sess.plan.rho <= 0.0 or not math.isfinite(sess.plan.rho):
            self._plan_session(sess, rho_new, rho_ub_new)
            return
        repl = {"rho": rho_new}
        if sess.plan.family == "identity":
            repl["lambda_star"] = plan_mod.identity_lambda_star(rho_new)
        sess.plan = dataclasses.replace(sess.plan, **repl)
        sess.rho_ub = rho_ub_new
        # the wanted-direction scale moved with rho: re-derive the lr
        # boost (the only other plan-derived session field)
        sess.lr = program.session_lr(sess.plan, self.cfg.lr)

    # ------------------------------------------------------------------
    # admission / eviction
    # ------------------------------------------------------------------

    @tracing.traced("service.admit")
    def add_graph(self, sid: str, g, num_clusters: int | None = None,
                  edge_capacity: int | None = None,
                  resume_panel=None) -> None:
        """Admit a graph into its capacity class.

        ``resume_panel`` warm-starts the session from a previously
        evicted panel (the ``panel`` entry of :meth:`evict`'s summary):
        the panel is re-orthonormalized through
        ``solvers.init_from_panel`` onto the class's node padding, so a
        re-admitted tenant reconverges in a fraction of the cold ticks.
        """
        if sid in self._sessions:
            raise ValueError(f"session {sid!r} already exists")
        cfg = self.cfg
        clusters = num_clusters or cfg.num_clusters
        need = clusters + (1 if cfg.drop_trivial else 0)
        if need > cfg.k:
            raise ValueError(
                f"num_clusters={clusters} needs {need} tracked "
                f"eigenvectors (drop_trivial={cfg.drop_trivial}) but "
                f"ServiceConfig.k={cfg.k}")
        node_cap = node_capacity_class(g.num_nodes)
        cap = (gs.capacity_class(g.num_edges) if edge_capacity is None
               else edge_capacity)
        with tracing.span("service.store"):
            store = gs.from_edge_list(g, capacity=self._balanced(cap),
                                      num_nodes=node_cap)
        store, rho, rho_ub, lam_k, lam_k1 = self._rho_estimate(
            store, g.num_nodes)
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed),
                                 self._admitted)
        self._admitted += 1
        if resume_panel is not None:
            rp = jnp.asarray(resume_panel, jnp.float32)
            if rp.shape != (g.num_nodes, cfg.k):
                raise ValueError(
                    f"resume_panel shape {rp.shape} != "
                    f"({g.num_nodes}, {cfg.k})")
            v = jnp.zeros((node_cap, cfg.k), jnp.float32).at[
                : g.num_nodes].set(rp)
            v = solvers.init_from_panel(v).v
        else:
            v = _init_panel(key, node_cap, g.num_nodes, cfg.k)
        sess = _Session(
            sid=sid,
            n=g.num_nodes,
            num_clusters=clusters,
            store=store,
            v=v,
            plan=plan_mod.plan_dilation(None, k=cfg.k, budget=cfg.degree),
            rho_ub=rho_ub,
            lr=cfg.lr,
            plan_degree=1,
            tracker=tracking.LabelTracker(clusters),
        )
        self._plan_session(sess, rho, rho_ub, lam_k, lam_k1)
        sess.solves = 1  # the admission (cold or resumed) solve
        self._sessions[sid] = sess
        self._class_degree_cache = None  # fleet membership changed

    def evict(self, sid: str) -> dict:
        """Remove a session; returns its summary, including the live
        eigenvector ``panel`` (real rows only) so a later re-admission
        can warm-start through ``add_graph(resume_panel=...)``.

        Raises :class:`UnknownSessionError` on an unknown or
        already-evicted sid (evict is not idempotent: the second call
        reports the id as gone instead of silently succeeding)."""
        sess = self._get(sid)
        # summarize BEFORE removal so the reported degree is the one the
        # session actually solved under (it may anchor its class's max)
        summary = self._summary(sess)
        summary["panel"] = np.asarray(sess.v[: sess.n])
        del self._sessions[sid]
        self._class_degree_cache = None  # fleet membership changed
        return summary

    def evict_converged(self) -> dict[str, dict]:
        """Drop every converged session (label consumers are done)."""
        done = [s for s in self._sessions.values() if s.converged]
        return {s.sid: self.evict(s.sid) for s in done}

    # ------------------------------------------------------------------
    # streaming updates
    # ------------------------------------------------------------------

    @tracing.traced("service.apply")
    def apply_updates(self, sid: str, edges, weights,
                      mode: str = "set",
                      pad_to: int | None = None) -> gs.BatchStats:
        """Apply an edge batch; converged sessions take the first-order
        eigen-update path, falling back to a warm re-solve on drift.

        ``pad_to`` lets a caller draining many sessions at once (the
        serve engine's per-capacity-class drain) pin one batch pad for
        a whole class, so every session in the class hits the SAME
        compiled apply instead of one compile per pow2 batch size."""
        cfg = self.cfg
        sess = self._get(sid)
        pad = max(_next_pow2(len(np.atleast_1d(weights))),
                  cfg.min_batch_pad)
        if pad_to is not None:
            pad = max(pad, _next_pow2(pad_to))
        batch = gs.coalesce_batch(edges, weights, mode=mode, pad_to=pad)
        store, dw, stats = gs.apply_edge_batch(sess.store, batch, mode=mode)
        base = sess.store
        while int(stats.dropped) > 0:
            # buffer overflow: grow the ORIGINAL store (untouched —
            # apply is functional) and re-apply the whole batch, growing
            # again until nothing drops (a batch can exceed one ladder
            # step).  The session changes capacity class, so its next
            # tick joins a different group.  Sharded serving keeps the
            # grown capacity a multiple of the shard count.
            base = gs.grow(base)
            if base.capacity != self._balanced(base.capacity):
                base = gs.grow(base, self._balanced(base.capacity))
            store, dw, stats = gs.apply_edge_batch(base, batch, mode=mode)
        # Ordinary batches rescale cheaply: track the probed estimate by
        # the Gershgorin bound's relative change (no probe matvecs), cap
        # by the fresh bound.  Full re-probes happen on admission and on
        # the drift-triggered re-solve below.
        store, rho_ub = gs.spectral_radius_upper_bound(store)
        rho_ub_new = float(rho_ub)
        sess.store = store
        # edge mutation stales the blocked layouts (single, sharded,
        # and model-sharded), the measured residual-decay rate
        # (operator changed), and — when the buffer grew a capacity
        # class — the degree map
        sess.blocking = None
        sess.sharded_blocking = None
        sess.model_blocking = None
        sess.rate = None
        self._class_degree_cache = None
        if sess.rho_ub > 0.0:
            rho_new = min(rho_ub_new,
                          sess.plan.rho * rho_ub_new / sess.rho_ub)
        else:
            # degenerate (edgeless) admission: rho == rho_ub == 0, and
            # the ratio would pin rho at 0 forever (c -> 1/eps -> NaN
            # panels); re-anchor on the fresh bound instead
            rho_new = rho_ub_new
        self._shift_rho(sess, rho_new, rho_ub_new)
        if sess.est is not None:
            prev_v = sess.est.v
            est, drift_flag = updates.update_or_flag(
                sess.est, batch.src, batch.dst, dw,
                updates.UpdateConfig(fallback_ratio=cfg.fallback_ratio))
            sess.v = est.v
            sess.incremental_updates += 1
            if not drift_flag:
                sess.est = est  # cheap path: drift bound still safe
                # The drift bound guards first-order VALIDITY, not the
                # residual target: a converged session absorbing real
                # weight deltas can sit well above tolerance while the
                # bound stays "safe" (large eigengap => large drift
                # budget), and because converged sessions left their
                # tick groups entirely, it would NEVER be re-solved —
                # every later batch silently staged against a stale
                # panel.  Verify with one operator application and
                # re-enter the tick rotation when the panel misses
                # tolerance; a genuinely realized no-op (dw == 0, e.g.
                # a reweight to the current value) skips the check and
                # keeps convergence verbatim.
                if sess.converged and bool(np.any(np.asarray(dw) != 0.0)):
                    res = float(self._residual(sess))
                    sess.residual = res
                    if res > cfg.tol:
                        sess.converged = False
                        sess.est = None  # ticking owns the panel again
                return stats
            # The drift bound is conservative (Σ 2|dw| vs the min
            # PANEL gap, which bulk eigenvalues make tiny) — so before
            # paying for a re-solve, VERIFY with one operator
            # application: does the updated panel still meet tolerance
            # under the new operator?
            res = float(self._residual(sess))
            sess.residual = res
            if res <= 2.0 * cfg.tol:
                # panel survived: re-anchor the estimate (drift resets)
                st = sess.store
                sess.est = updates.anchor_estimate_arrays(
                    st.src, st.dst, st.weight, sess.v)
                return stats
            # Full SPED re-solve.  The accumulated drift that invalidated
            # the panel also staled the admission-time lambda_max, so
            # RE-PROBE the spectrum and re-run the dilation planner
            # before deciding how to seed the solve.  A first-order
            # update outside its validity region can be WORSE than the
            # stale panel, so seed from whichever candidate has the
            # lower residual under the new (re-planned) operator; go
            # cold when even that fails the restart test (stream.warm).
            sess.fallbacks += 1
            sess.est = None
            sess.converged = False
            st2, rho2, rho_ub2, lam_k2, lam_k12 = self._rho_estimate(
                sess.store, sess.n)
            sess.store = st2
            self._plan_session(sess, rho2, rho_ub2, lam_k2, lam_k12)
            res = float(self._residual(sess))  # est.v under re-probed op
            sess.v = prev_v
            res_prev = float(self._residual(sess))
            if res <= res_prev:
                sess.v, best = est.v, res
            else:
                best = res_prev
            if best > cfg.restart_residual:
                key = jax.random.fold_in(
                    jax.random.PRNGKey(cfg.seed + 1), sess.solves)
                sess.v = _init_panel(key, sess.store.num_nodes,
                                     sess.n, cfg.k)
            sess.residual = best
            sess.solves += 1
        return stats

    # ------------------------------------------------------------------
    # batched ticking
    # ------------------------------------------------------------------

    def _class_key(self, sess: _Session) -> tuple[int, int]:
        return (sess.store.num_nodes, sess.store.capacity)

    def _class_degrees(self) -> dict[tuple, int]:
        """Per-capacity-class dilation degree: the max over the class's
        resident exp-family sessions' planned suggestions (snapped onto
        the planner grid by construction, capped by ``cfg.degree``).

        This IS the per-class degree re-plan: a newly admitted (or
        drift-re-probed) tenant whose plan needs more dilation raises
        its class's degree — a new compile key, but only on the snapped
        degree set (`core.program.schedule_degrees`).  Identity-family
        sessions stay out: they tick in their own degree-1 groups.
        Memoized until admission/eviction/re-plan invalidates it.
        """
        if self._class_degree_cache is None:
            degs: dict[tuple, int] = {}
            for s in self._sessions.values():
                if s.plan.family == "identity":
                    continue
                ck = self._class_key(s)
                degs[ck] = max(degs.get(ck, 0), s.plan_degree)
            self._class_degree_cache = degs
        return self._class_degree_cache

    def _session_degree(self, sess: _Session,
                        degrees: dict | None = None) -> int:
        if sess.plan.family == "identity":
            return 1
        degrees = self._class_degrees() if degrees is None else degrees
        return degrees.get(self._class_key(sess), sess.plan_degree)

    def _ensure_blocking(self, sess: _Session) -> None:
        """Build (or rebuild after updates) the session's blocked
        layout for its tick path — host-side, cached on the session.
        Edge-sharded serving builds the per-shard variant; panel
        sharding builds the destination-aligned model layout (used by
        BOTH backends — the model tick's segment path scatters over the
        same per-shard arrays the kernel consumes)."""
        block_n = self.cfg.tick_block_n
        if self._model_axes is not None:
            attr, build = "model_blocking", lambda: gs.model_sharded_blocking(
                sess.store, self._model_shards, block_n=block_n)
        elif self._mesh is not None:
            attr, build = "sharded_blocking", lambda: gs.sharded_node_blocking(
                sess.store, self._num_shards, block_n=block_n)
        else:
            attr, build = "blocking", lambda: gs.node_blocking(
                sess.store, block_n=block_n)
        if getattr(sess, attr) is None:
            with tracing.span("service.layout"):
                b = build()
                setattr(sess, attr, b)
            # the builder pulled the weights to the host: no device read
            live = np.count_nonzero(np.asarray(sess.store.weight))
            self._layout_half_edges += 2 * int(live)
            self._layout_slots += b.padded_half_edges

    def _group_key(self, sess: _Session, degrees: dict | None = None
                   ) -> tuple:
        """Sessions sharing a group share one compiled tick program.

        Groups by capacity class + scheduled dilation degree; pallas
        additionally groups by the blocking's static layout (block size
        and snapped chunk count), since those are the shapes the
        kernel compiles against — sharded pallas uses the per-shard
        layout's statics the same way.  Only ACTIVE (unconverged)
        sessions are ever grouped, so a converged session's invalidated
        blocking is never rebuilt just to anchor a bucket.
        """
        deg = self._session_degree(sess, degrees)
        if self._model_axes is not None:
            # panel sharding needs the layout statics on BOTH backends
            # (the model tick's segment path runs over the same arrays)
            self._ensure_blocking(sess)
            b = sess.model_blocking
            key = (self._class_key(sess), deg, b.block_n,
                   b.num_chunks, b.block_e)
        elif self._backend == "pallas":
            self._ensure_blocking(sess)
            b = (sess.sharded_blocking if self._mesh is not None
                 else sess.blocking)
            key = (self._class_key(sess), deg, b.block_n,
                   b.num_chunks, b.block_e)
        else:
            key = (self._class_key(sess), deg)
        sess.group_key = key
        return key

    def _get_step(self, key: tuple, occupancy: int):
        cfg = self.cfg
        fn = self._compiled.get((key, occupancy))
        if fn is None:
            # lr is NOT part of the schedule here: tick programs take
            # the per-session learning rates as a traced input
            schedule = program.StepSchedule(
                method=cfg.method, degree=key[1],
                steps=cfg.steps_per_tick, backend=self._backend)
            has_layout = (self._backend == "pallas"
                          or self._model_axes is not None)
            layout = key[2:] if has_layout else None
            fn = program.build_tick_program(
                schedule, layout=layout, mesh=self._mesh,
                edge_axes=cfg.edge_axes, model_axes=self._model_axes)
            self._compiled[(key, occupancy)] = fn
        return fn

    @property
    def compile_count(self) -> int:
        """Distinct compiled tick programs — (capacity class, degree,
        layout) x pow2 occupancy bucket, so the count stays logarithmic
        in fleet size (the schedule-plumbing invariant's witness).  The
        scheduler's tick multiplier and every per-session hyperparameter
        are traced: they add NO programs."""
        return len(self._compiled)

    @property
    def tick_invocations(self) -> int:
        """Compiled tick-program invocations so far (all groups)."""
        return self._tick_invocations

    @property
    def device_work(self) -> int:
        """Accumulated device work in session-slot solver steps
        (occupancy x steps per invocation).  Converged sessions leave
        their groups, so they contribute ZERO here — the counter the
        zero-work-when-converged tests assert on."""
        return self._device_work

    @property
    def multiplied_ticks(self) -> int:
        """Invocations the residual-decay scheduler stretched past one
        plain tick (traced chunk multiplier > 1 — zero extra compiles)."""
        return self._multiplied_ticks

    @property
    def layout_pad_share(self) -> float:
        """Share of the half-edge slots in the blocked layouts built so
        far that are padding (empty chunk tails and snapped-count padding
        chunks): what the kernel, its gather and its lane pad walk for
        nothing.  0.0 before any layout is built."""
        if not self._layout_slots:
            return 0.0
        return 1.0 - self._layout_half_edges / self._layout_slots

    def _tick_multipliers(self, members: list[_Session]) -> np.ndarray:
        """Residual-decay scheduling: PER-SESSION steps multipliers.

        Each member's own forecast (measured decay rate, see
        ``core.program.contraction_rate``) sizes its own chunk budget:
        a member predicted to stay above tolerance for more than
        ``eval_payoff`` plain ticks stretches to ``min(predicted plain
        ticks, max_tick_multiplier)`` — floored, so nobody overshoots
        their forecast — while a member near convergence (or with no
        usable forecast yet) keeps multiplier 1 and freezes after its
        own budget inside the shared program (``core.program``'s
        per-session chunk mask).  Before this split the group took ONE
        multiplier ``min``-ed over members, so the soonest-converging
        (or merely forecast-less) session capped every peer at plain
        ticks.  The multipliers ride as a traced ``(G,)`` input, so any
        mix reuses the group's one compiled program; ``tick`` then
        sub-batches plain members away from stretched ones when that
        executes fewer slot-steps (``_split_by_multiplier``).
        """
        cfg = self.cfg
        mults = np.ones(len(members), np.int64)
        if (cfg.tick_schedule != "residual_decay"
                or cfg.max_tick_multiplier <= 1):
            return mults
        for i, m in enumerate(members):
            if m.rate is None or not (0.0 < m.rate < 1.0):
                continue
            need = program.predicted_steps_to_tol(m.residual, m.rate,
                                                  cfg.tol)
            if need <= cfg.eval_payoff * cfg.steps_per_tick:
                continue
            mult = max(1, min(need // cfg.steps_per_tick,
                              cfg.max_tick_multiplier))
            if m.residual <= cfg.stretch_residual_floor * cfg.tol:
                mult = min(mult, 4)  # endgame cap (see config)
            mults[i] = mult
        return mults

    def _tick_operands(self, gkey: tuple, members: list[_Session],
                       mults: np.ndarray) -> tuple:
        """(compiled step, occupancy, operands) of one group invocation:
        the members' edge layouts and panels stacked to the pow2
        occupancy of the ACTIVE member count (padded with replicas of
        the first session: converged sessions no longer ride along as
        padding, at the cost of at most log2(max occupancy) compiled
        buckets per group), their dilation scales, learning rates and
        chunk budgets."""
        occ = _next_pow2(len(members))
        step = self._get_step(gkey, occ)
        idx = list(range(len(members))) + [0] * (occ - len(members))
        stack = lambda f: jnp.stack([f(members[i]) for i in idx])
        cs = jnp.asarray(
            [program.dilation_scale(members[i].plan, gkey[1])
             for i in idx], jnp.float32)
        lrs = jnp.asarray([members[i].lr for i in idx], jnp.float32)
        # traced per-session chunk budgets: no recompile for any mix
        chunks = jnp.asarray(mults[np.asarray(idx)], jnp.int32)
        if self._model_axes is not None:
            layout = lambda s: s.model_blocking
        elif self._backend == "pallas" and self._mesh is not None:
            layout = lambda s: s.sharded_blocking
        elif self._backend == "pallas":
            layout = lambda s: s.blocking
        else:
            layout = None
        if layout is None:
            # single-device segment AND sharded segment take the same
            # stacked-edge-buffer signature (the sharded builder shards
            # the capacity axis over the mesh)
            edges = [stack(lambda s: s.store.src),
                     stack(lambda s: s.store.dst),
                     stack(lambda s: s.store.weight)]
        else:
            edges = [stack(lambda s, f=f: getattr(layout(s), f))
                     for f in ("u_local", "other", "weight", "chunk_block",
                               "deg")]
        return step, occ, (*edges, stack(lambda s: s.v), cs, lrs, chunks)

    def tick(self) -> dict[str, float]:
        """Advance every unconverged session one scheduled tick — one
        compiled program invocation per (capacity class, degree) group
        (and, on pallas, per blocking layout), or two when the
        scheduler sub-batches plain members away from stretched ones
        (``_split_by_multiplier``).  Converged sessions are not grouped
        at all: zero device work."""
        cfg = self.cfg
        degrees = self._class_degrees()
        groups: dict[tuple, list[_Session]] = defaultdict(list)
        for sess in self._sessions.values():
            if not sess.converged:
                groups[self._group_key(sess, degrees)].append(sess)
        out: dict[str, float] = {}
        for gkey, g_members in groups.items():
            g_mults = self._tick_multipliers(g_members)
            for members, mults in _split_by_multiplier(g_members, g_mults):
                max_mult = int(mults.max())
                with tracing.span("service.tick.prepare"):
                    step, occ, args = self._tick_operands(
                        gkey, members, mults)
                with tracing.span("service.tick.launch"):
                    vs, res = step(*args)
                # drop the stacked operands with the call, or they stay
                # alive next to the next invocation's (a higher peak)
                del args
                self._tick_invocations += 1
                # device work is what the hardware executes: every occupancy
                # slot rides the longest member's chunk budget (short-budget
                # members freeze under the mask but their slots still step)
                self._device_work += occ * cfg.steps_per_tick * max_mult
                if max_mult > 1:
                    self._multiplied_ticks += 1
                with tracing.span("service.tick.wait"):
                    res = np.asarray(res)
                with tracing.span("service.tick.update"):
                    for i, sess in enumerate(members):
                        prev = sess.residual
                        sess.v = vs[i]
                        sess.residual = float(res[i])
                        # fresh decay estimate over the member's OWN
                        # executed step count (its panel froze after its
                        # chunk budget); a non-contracting observation
                        # resets the forecast (the scheduler then stays
                        # at plain ticks until contraction re-establishes)
                        sess.rate = program.contraction_rate(
                            prev, sess.residual,
                            cfg.steps_per_tick * int(mults[i]))
                        sess.ticks += 1
                        out[sess.sid] = sess.residual
                        if sess.residual <= cfg.tol:
                            sess.converged = True
                            st = sess.store
                            sess.est = updates.anchor_estimate_arrays(
                                st.src, st.dst, st.weight, sess.v)
        return out

    @property
    def all_converged(self) -> bool:
        return all(s.converged for s in self._sessions.values())

    @property
    def backend(self) -> str:
        """The resolved SpMM backend the tick runs ("pallas" or
        "segment")."""
        return self._backend

    def run_until_converged(self, max_ticks: int = 500) -> int:
        """Tick until every session converges; returns ticks used.

        Check `all_converged` afterwards: hitting the tick budget without
        converging also returns (with the budget spent), and serving
        labels from an unconverged panel is the caller's decision.
        Converged sessions cost zero device work here — their groups
        shrink away — so waiting on a slow tenant never re-runs the
        finished ones.
        """
        used = 0
        while not self.all_converged and used < max_ticks:
            self.tick()
            used += 1
        return used

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------

    def _residual(self, sess: _Session) -> float:
        st = sess.store
        deg = self._session_degree(sess)
        c = program.dilation_scale(sess.plan, deg)
        return float(operators.dilated_panel_residual(
            st.src, st.dst, st.weight, sess.v, c, deg))

    def live_edges(self, sid: str) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, weight) of the session's live edges — the public
        view of the store for consumers building update batches."""
        st = self._get(sid).store
        w = np.asarray(st.weight)
        live = w != 0
        return np.asarray(st.src)[live], np.asarray(st.dst)[live], w[live]

    def panel(self, sid: str) -> jax.Array:
        """The session's live eigenvector panel (real rows only) — the
        immutable embedding snapshot the serving layer commits per
        result version (repro.serve.results)."""
        sess = self._get(sid)
        return sess.v[: sess.n]

    def labels(self, sid: str) -> np.ndarray:
        """Current cluster assignment with STABLE ids (tracking.py)."""
        cfg = self.cfg
        sess = self._get(sid)
        raw = panel_labels(
            sess.v[: sess.n], sess.num_clusters,
            drop_trivial=cfg.drop_trivial, seed=cfg.seed,
            kmeans_restarts=cfg.kmeans_restarts)
        return np.asarray(sess.tracker.update(raw))

    def capacity_class(self, sid: str) -> tuple[int, int]:
        """(node capacity, edge capacity) of the session's class — the
        serve layer's drain-batching group key (sessions in one class
        share the compiled edge-batch apply at a common pad)."""
        return self._class_key(self._get(sid))

    def session_info(self, sid: str) -> dict:
        return self._summary(self._get(sid))

    def _summary(self, sess: _Session) -> dict:
        return {
            "n": sess.n,
            "node_capacity": sess.store.num_nodes,
            "edge_capacity": sess.store.capacity,
            "num_edges": int(gs.num_edges(sess.store)),
            "converged": sess.converged,
            "residual": sess.residual,
            "rho": sess.rho,
            "rho_ub": sess.rho_ub,
            "tau": sess.tau,
            "family": sess.plan.family,
            "degree": self._session_degree(sess),
            "lr": sess.lr,
            "rate": sess.rate,
            "ticks": sess.ticks,
            "solves": sess.solves,
            "incremental_updates": sess.incremental_updates,
            "fallbacks": sess.fallbacks,
        }
