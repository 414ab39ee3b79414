"""Unified solve programs: ONE dilated solve loop for every deployment shape.

Before this module the repo carried four hand-rolled copies of the same
iteration — ``core.solvers.run_solver``'s eval loop, the streaming
service's segment and pallas tick builders, and the sharded service's
shard_mapped tick programs — so every convergence improvement (adaptive
lr, probe-driven degrees, smarter stopping) had to be implemented four
times or not at all.  The builders here own the composition

    dilated matvec  x  mu-EG/Oja step  x  residual evaluation

as one compiled unit, parameterized along three axes:

* **operator source** (chosen once per service, :mod:`repro.stream
  .source`) — raw edge arrays (segment gather/scatter), a node-blocked
  pallas layout with the dilation AXPY fused into the kernel epilogue,
  or per-shard sharded layouts whose matvecs psum under ``shard_map``;
* **batching shape** — a single panel (`run_chunk`, `run_program`), a
  vmapped/``lax.map``-ped session group (`build_tick_program` without a
  mesh), or a shard_mapped capacity class (`build_tick_program` with a
  mesh);
* **a** :class:`StepSchedule` — the compile-relevant statics (solver
  method, dilation degree, steps per invocation), derived from a
  session's :class:`~repro.spectral.plan.DilationPlan` instead of fixed
  constants, while the per-session learning rate and dilation scale
  ride as TRACED inputs so adaptive per-session hyperparameters never
  grow the compile cache.

:func:`apply_solver_step` is THE single construction site of the
mu-EG/Oja dilated solver step; ``core.solvers.run_solver``,
``stream.service``'s tick programs, ``stream.warm``'s chunk runner, and
``core.distributed``'s whole-series solves are thin wrappers over the
loops below.

The scheduling helpers at the bottom (`contraction_rate`,
`predicted_residual`, `predicted_steps_to_tol`) turn observed residual
decay into step forecasts — the streaming service's residual-decay tick
scheduler and its predicted-contraction stopping are built on them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core import backend as backend_mod
from repro.core import metrics, operators, solvers
from repro.core import laplacian as lap
from repro.kernels.edge_spmm import ops as es_ops

MatVec = Callable[[jax.Array], jax.Array]


# ---------------------------------------------------------------------------
# schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StepSchedule:
    """Compile-relevant hyperparameters of one solve-program invocation.

    ``method`` / ``degree`` / ``steps`` / ``backend`` are STATIC — they
    are part of the compile-cache key, and adaptive layers must only
    move them on snapped grids (see :func:`schedule_degrees`).  ``lr``
    is advisory metadata for SINGLE-PANEL callers (a plan-derived step
    size to feed ``run_chunk``/``SolverConfig``); the group tick
    builders never read it — their learning rates always arrive as the
    traced per-session ``lrs`` input, so per-session values are free
    (no recompilation).
    """

    method: str = "mu_eg"  # "mu_eg" | "oja"
    degree: int = 15  # dilation degree of the (I - c L)^degree operator
    steps: int = 20  # solver steps per program invocation
    lr: float = 0.3  # advisory: single-panel callers; ticks trace lrs
    backend: str = "auto"  # repro.core.backend

    @property
    def statics(self) -> tuple:
        """The compile-cache key contribution of this schedule."""
        return (self.method, self.degree, self.steps, self.backend)

    @classmethod
    def from_plan(cls, plan, *, steps: int, base_lr: float,
                  method: str = "mu_eg", backend: str = "auto",
                  max_degree: int | None = None,
                  normalized: bool = True) -> "StepSchedule":
        """Derive (lr, degree) from a :class:`DilationPlan`.

        ``normalized=True`` is the tick-program form ``(I - c L)^degree``
        whose TOP eigenvalue is 1 by construction (an identity plan runs
        as degree 1 with ``c = 1/lambda_star`` — the scaled operator and
        the rescaled ``suggested_lr`` cancel exactly for the linear
        mu-EG/Oja updates), so the lr is instead normalized to the
        plan's WANTED-direction scale (:func:`session_lr`) — the axis
        along which plans genuinely differ.  ``normalized=False`` keeps
        ``plan.suggested_lr`` verbatim for callers driving the raw
        reversed operator ``lambda* I - S(L)`` (one-shot solves over
        ``planned_operator``).
        """
        degree = 1 if plan.family == "identity" else int(plan.degree)
        if max_degree is not None:
            cap = max_degree if max_degree % 2 == 1 else max_degree - 1
            degree = min(degree, max(cap, 1))
        if normalized:
            lr = session_lr(plan, base_lr)
        else:
            lr = plan.suggested_lr(base_lr)
        return cls(method=method, degree=degree, steps=steps, lr=lr,
                   backend=backend)


def wanted_scale(plan) -> float:
    """Transformed operator value of the slowest WANTED direction.

    Dilation deliberately decays the wanted spread — the planner allows
    ``tau * lam_k / rho`` up to ``MAX_WANTED_DECAY``, i.e. wanted
    directions down to ``exp(-1.5) ~ 0.22`` — and the mu-EG/Oja utility
    gradient of that trailing direction scales with this value, so a
    step size tuned for a unit-scale direction under-steps it by
    exactly this factor.  This is the denominator of the per-session lr
    normalization (:func:`session_lr`).
    """
    if plan.family == "identity":
        lam_star = max(plan.lambda_star, 1e-30)
        return max(1.0 - plan.lam_k / lam_star, 1e-3)
    if plan.rho <= 0.0 or not math.isfinite(plan.rho):
        return 1.0
    return math.exp(-plan.tau * min(plan.lam_k, plan.rho) / plan.rho)


# The top direction still sees operator value 1, so the wanted-scale lr
# boost must stay inside the solver's stable step range.
LR_BOOST_CAP = 2.0


def session_lr(plan, base_lr: float, boost_cap: float = LR_BOOST_CAP
               ) -> float:
    """Plan-driven per-session step size for the unit-normalized tick
    program form: the base lr boosted by the inverse wanted-direction
    scale (capped).  Strongly dilated tenants — whose trailing wanted
    eigenvalue the transform decayed hardest — take proportionally
    larger steps; tenants with their wanted spread intact keep the
    base lr."""
    return base_lr * min(1.0 / max(wanted_scale(plan), 1e-3), boost_cap)


def dilation_scale(plan, degree: int) -> float:
    """Per-matvec scale ``c`` of the ``(I - c L)^degree`` program form.

    For the exp-family plans this is the series step ``tau / (rho *
    degree)``; an identity plan maps onto degree 1 with ``c = 1 /
    lambda_star`` (the unit-normalized reversed identity — see
    :meth:`StepSchedule.from_plan` for why the lr needs no compensation).
    """
    if plan.family == "identity":
        return 1.0 / max(plan.lambda_star, 1e-30)
    return plan.scale / max(degree, 1)


def schedule_degrees(max_degree: int) -> tuple[int, ...]:
    """Every degree a plan-derived schedule may take under ``max_degree``.

    The planner emits degrees only from the snapped tau grid (plus the
    identity's degree 1 and the budget-truncation fallback), so
    per-class degree re-planning moves on THIS set — the compile-cache
    economy bound asserted by the schedule-plumbing tests.
    """
    from repro.spectral import plan as plan_mod

    degs = {1, plan_mod.MIN_DEGREE}
    for t in plan_mod.TAU_GRID:
        d = int(math.ceil(plan_mod.DEGREE_PER_TAU * t))
        d = d if d % 2 == 1 else d + 1
        degs.add(max(d, plan_mod.MIN_DEGREE))
    degs.add(max(max_degree if max_degree % 2 == 1 else max_degree - 1, 1))
    return tuple(sorted(d for d in degs if d <= max_degree))


# ---------------------------------------------------------------------------
# collective accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PsumStats:
    """Trace-time psum-call counts of a shard_mapped tick program.

    ``fused`` counts TUPLE psums (several operands in one call — XLA
    lowers them to ONE variadic all-reduce), ``plain`` single-operand
    calls.  Loop bodies (scan/fori) trace once, so the counts are per
    TRACED body, independent of step counts: the model-sharded tick's
    contract — exactly one fused collective per solver step — shows up
    as ``fused == 1``.
    """

    plain: int = 0
    fused: int = 0


_PSUM_STATS: PsumStats | None = None


@contextlib.contextmanager
def count_psums():
    """Count collective calls issued while TRACING under this context
    (e.g. ``jax.eval_shape`` of a tick program) — the weak-scaling
    benchmarks' and tests' fused-collective assertion hook."""
    global _PSUM_STATS
    prev, _PSUM_STATS = _PSUM_STATS, PsumStats()
    try:
        yield _PSUM_STATS
    finally:
        _PSUM_STATS = prev


def _psum(x, axes):
    """jax.lax.psum routed through the trace-time counter.  Every
    collective the tick builders below issue goes through here."""
    if _PSUM_STATS is not None:
        if isinstance(x, tuple):
            _PSUM_STATS.fused += 1
        else:
            _PSUM_STATS.plain += 1
    return jax.lax.psum(x, axes)


# ---------------------------------------------------------------------------
# the solver step — THE single construction site
# ---------------------------------------------------------------------------

def apply_solver_step(step_fn, state: solvers.SolverState, av: jax.Array,
                      lr, gram: jax.Array | None = None
                      ) -> solvers.SolverState:
    """THE construction site of the mu-EG/Oja dilated solver step.

    Every solve loop in the repo — one-shot, streaming segment/pallas
    ticks, sharded class ticks, model-sharded panel ticks, distributed
    series solves, warm reconvergence chunks — applies its solver update
    through this call; nothing else composes an operator application
    with a solver step.

    ``gram`` is the fused-collective hook: when the caller already holds
    the global 2k x 2k gram of [V | AV] (a model-sharded tick psums
    per-shard grams fused with its panel assembly), the mu-EG update
    runs as the row-local mix :func:`solvers.mu_eg_step_from_gram` on
    whatever row slice ``state``/``av`` hold — no second panel
    reduction.  ``gram=None`` is every other path: the step function
    computes its own panel products.
    """
    if gram is not None:
        return solvers.mu_eg_step_from_gram(state, av, gram, lr)
    return step_fn(state, av, lr)


# ---------------------------------------------------------------------------
# single-panel loops
# ---------------------------------------------------------------------------

def run_chunk(opv: MatVec, step_fn, state: solvers.SolverState, lr,
              steps: int) -> tuple[solvers.SolverState, jax.Array]:
    """``steps`` dilated solver steps on one panel + one residual eval.

    The building block of ``stream.warm``'s chunked reconvergence and of
    the per-session tick bodies (which batch it via vmap/``lax.map``).
    """
    def body(st, _):
        return apply_solver_step(step_fn, st, opv(st.v), lr), None

    state, _ = jax.lax.scan(body, state, None, length=steps)
    return state, metrics.operator_residual(opv, state.v)


def run_program(
    operator,
    n: int,
    cfg: solvers.SolverConfig,
    v_star: jax.Array | None = None,
    stochastic: bool = False,
    init_v: jax.Array | None = None,
) -> tuple[solvers.SolverState, "solvers.Trace"]:
    """One-shot solve with ground-truth traces — ``run_solver``'s engine.

    One jitted scan over eval chunks (Python overhead O(1) in steps);
    ``init_v`` warm-starts from an (n, k) panel via ``init_from_panel``.
    Stochastic operators take a per-step PRNG key.
    """
    step_fn = solvers.make_step_fn(cfg.method, cfg.backend)
    key = jax.random.PRNGKey(cfg.seed)
    key, init_key = jax.random.split(key)
    if init_v is None:
        state0 = solvers.init_state(init_key, n, cfg.k)
    else:
        state0 = solvers.init_from_panel(init_v)
    num_evals = max(1, cfg.steps // cfg.eval_every)
    if v_star is None:
        v_star = jnp.zeros((n, cfg.k))

    def one_step(state, key_step):
        if stochastic:
            av = operator(key_step, state.v)
        else:
            av = operator(state.v)
        return apply_solver_step(step_fn, state, av, cfg.lr), None

    def eval_chunk(state, chunk_keys):
        state, _ = jax.lax.scan(one_step, state, chunk_keys)
        m = (
            state.step,
            metrics.subspace_error(state.v, v_star),
            metrics.eigenvector_streak(state.v, v_star),
        )
        return state, m

    keys = jax.random.split(key, num_evals * cfg.eval_every).reshape(
        num_evals, cfg.eval_every, -1)

    run = jax.jit(lambda s, ks: jax.lax.scan(eval_chunk, s, ks))
    final, (steps, err, streak) = run(state0, keys)
    return final, solvers.Trace(steps=steps, subspace_error=err,
                                streak=streak)


# ---------------------------------------------------------------------------
# batched (session-group) loop
# ---------------------------------------------------------------------------

def _group_loop(opv_all, step_all, vs, lrs, steps: int, chunks):
    """The batched dilated solve loop every tick program runs.

    ``opv_all``: (G, n, k) -> (G, n, k) dilated-operator application for
    the whole stacked group (psums live inside it on sharded sources);
    ``step_all`` maps the solver step over the group axis (vmap on
    segment, ``lax.map`` on pallas — its grids don't vmap).

    ``chunks`` is the residual-decay scheduler's tick MULTIPLIER: a
    traced scalar runs ``chunks * steps`` solver steps for every member
    before the single residual evaluation, and a traced PER-SESSION
    ``(G,)`` vector gives each member its own chunk budget — session i
    steps for ``chunks[i] * steps`` steps and then FREEZES (its panel
    stops moving under a mask) while slower group peers keep iterating
    up to ``max(chunks)``, so one member forecast to converge soon no
    longer caps the whole group's cadence at multiplier 1.  Either way
    the value is TRACED (the static scan of ``steps`` steps repeats
    under a ``fori_loop`` with a traced bound, the freeze is a
    ``where``), so scheduled multi-chunk ticks reuse the exact compiled
    program of a plain tick — the adaptive layer costs zero
    recompilation.
    """
    state = solvers.SolverState(
        v=vs, step=jnp.zeros((vs.shape[0],), jnp.int32))
    chunks = jnp.asarray(chunks, jnp.int32)
    per_session = jnp.broadcast_to(chunks, (vs.shape[0],))

    def body(st, _):
        return step_all(st, opv_all(st.v), lrs), None

    def chunk_body(i, st):
        stepped, _ = jax.lax.scan(body, st, None, length=steps)
        live = i < per_session  # (G,) — members past their budget freeze
        return solvers.SolverState(
            v=jnp.where(live[:, None, None], stepped.v, st.v),
            step=jnp.where(live, stepped.step, st.step))

    state = jax.lax.fori_loop(0, jnp.max(per_session), chunk_body, state)
    avs = opv_all(state.v)
    return state.v, jax.vmap(metrics.panel_residual)(state.v, avs)


def _vmapped_step(step_fn):
    def step_all(st, avs, lrs):
        return jax.vmap(
            lambda s, av, lr: apply_solver_step(step_fn, s, av, lr)
        )(st, avs, lrs)
    return step_all


def _mapped_step(step_fn):
    """``lax.map`` variant for pallas steps (kernel grids don't vmap)."""
    def step_all(st, avs, lrs):
        return jax.lax.map(
            lambda args: apply_solver_step(
                step_fn,
                solvers.SolverState(v=args[0], step=args[1]),
                args[2], args[3]),
            (st.v, st.step, avs, lrs))
    return step_all


def _segment_opv_all(src, dst, w, cs, degree: int, edge_axes=None):
    """Group dilated operator over stacked (G, cap) edge buffers.

    Without ``edge_axes`` it is ``operators.dilated_operator_arrays``
    vmapped over the group (the summation order the CPU tests compare);
    with it each device holds a slice of every buffer, and every matvec
    psums the stacked panels before the AXPY.
    """
    if edge_axes is None:
        return lambda us: jax.vmap(
            lambda s, d, wt, x, c:
            operators.dilated_operator_arrays(s, d, wt, c, degree)(x)
        )(src, dst, w, us, cs)
    local_mv = jax.vmap(lap.edge_matvec_arrays)

    def opv_all(us):
        def body(_, xs):
            lxs = _psum(local_mv(src, dst, w, xs), edge_axes)
            return xs - cs[:, None, None] * lxs
        return jax.lax.fori_loop(0, degree, body, us)

    return opv_all


def _blocked_opv_all(u_local, other, w, cb, deg, cs, degree: int,
                     block_n: int, num_chunks: int, block_e: int,
                     interpret: bool, edge_axes=None):
    """Group dilated operator over stacked node-blocked pallas layouts.

    With ``edge_axes`` the layouts are per-shard (leading shard axis
    inside each device's slice) and every matvec psums; the dilation
    AXPY then applies post-psum (the collective is the fusion barrier).
    Without it the single-device kernel fuses ``alpha=-c, beta=1`` into
    its epilogue.  ``cb`` is the per-session (or per-shard) stacked
    chunk->block index map of the CSR chunk layout.
    """
    def local_mv(args):
        # shard_map-local slices: the leading shard axis is partitioned
        # down to size 1 inside the body (es_ops.shard_local_blocking)
        ul, ot, wt, cbv, dg, x = args
        nb = es_ops.shard_local_blocking(
            ul, ot, wt, cbv, dg, block_n=block_n, block_e=block_e,
            num_chunks=num_chunks, num_nodes=x.shape[0])
        return es_ops.edge_spmm_blocked(nb, x, interpret=interpret)

    def fused_mv(args):
        ul, ot, wt, cbv, dg, x, c = args
        nb = es_ops.NodeBlocking(
            u_local=ul, other=ot, weight=wt, chunk_block=cbv, deg=dg,
            block_n=block_n, block_e=block_e, num_chunks=num_chunks,
            num_nodes=x.shape[0])
        return es_ops.edge_spmm_blocked(nb, x, alpha=-c, beta=1.0,
                                        interpret=interpret)

    def opv_all(us):
        def body(_, xs):
            if edge_axes is not None:
                lxs = _psum(
                    jax.lax.map(local_mv,
                                (u_local, other, w, cb, deg, xs)),
                    edge_axes)
                return xs - cs[:, None, None] * lxs
            return jax.lax.map(fused_mv,
                               (u_local, other, w, cb, deg, xs, cs))
        return jax.lax.fori_loop(0, degree, body, us)

    return opv_all


def num_model_shards(mesh, model_axes=("model",)) -> int:
    """Product of the mesh's panel-sharding axis sizes."""
    s = 1
    for a in model_axes:
        s *= mesh.shape[a]
    return s


def build_tick_model_sharded(schedule: StepSchedule, mesh, model_axes,
                             block_n: int, num_chunks: int, block_e: int):
    """PANEL-sharded tick: fn(u_local, other, w, cb, deg, vs, cs, lrs,
    chunks) over destination-aligned per-shard layouts
    (:class:`~repro.kernels.edge_spmm.ops.ModelShardedBlocking`, stacked
    (G, S, ...) and sharded over ``model_axes`` along the shard axis).

    Each shard owns a contiguous row range of the (n, k) panel outright:
    its local matvec rows are FINAL (the dilation AXPY fuses back into
    the per-shard kernel epilogue — unlike the edge-sharded ticks, where
    beta must wait for the psum), and the collectives per dilated apply
    merely ASSEMBLE disjoint row ranges.  The mu-EG step then needs only
    the global 2k x 2k gram of [V | AV] (``solvers.panel_gram2k``), which
    is a sum of per-shard grams over owned rows — so the LAST matvec of
    the dilation ships its row assembly and the grams in ONE fused
    collective::

        av_full, grams = psum((embed(av_rows), gram_s), model_axes)

    and every shard mixes its rows row-locally
    (:func:`apply_solver_step` with ``gram=``) with zero further
    communication.  Per solver step: ``degree`` psums total, EXACTLY ONE
    of them fused — the gram costs no extra collective over the matvecs
    the dilation already pays (the gather-then-gram alternative pays
    ``degree + 1``).  ``count_psums`` asserts this at trace time.

    ``schedule.backend`` picks the per-shard row computation: the
    scalar-prefetched chunk kernel ("pallas") or the segment
    gather/scatter over the same layout arrays ("segment"/"auto"
    off-TPU).  Oja has no gram form (its QR retraction needs the full
    panel), so it assembles plainly and steps replicated — mu-EG is the
    fused-collective path.
    """
    interp = backend_mod.kernel_interpret()
    use_kernel = backend_mod.resolve_backend(schedule.backend) == "pallas"
    step_fn = solvers.make_step_fn(schedule.method, schedule.backend)
    fused_gram = schedule.method == "mu_eg"
    degree, steps = schedule.degree, schedule.steps
    num_shards = num_model_shards(mesh, model_axes)
    spec_b = P(None, model_axes)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec_b, spec_b, spec_b, spec_b, spec_b,
                  P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False)  # pallas_call has no replication rule
    def tick(u_local, other, w, cb, deg, vs, cs, lrs, chunks):
        g, n, k = vs.shape
        rows = deg.shape[-1]
        n_pad = num_shards * rows
        sidx = jnp.zeros((), jnp.int32)
        for a in model_axes:
            sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
        row_start = sidx * rows
        vp = jnp.pad(vs.astype(jnp.float32),
                     ((0, 0), (0, n_pad - n), (0, 0)))

        def mv_one(args):
            ul, ot, wt, cbv, dg, xf, c = args
            ab = jnp.stack([-c, jnp.ones_like(c)]).astype(jnp.float32)
            return es_ops.model_local_rows(
                ul[0], ot[0], wt[0], cbv[0], dg[0], xf, ab, row_start,
                block_n=block_n, block_e=block_e, num_chunks=num_chunks,
                padded_nodes=n_pad, use_kernel=use_kernel,
                interpret=interp)

        def mv_all(full):
            # (G, n_pad, k) replicated -> (G, rows, k) FINAL owned rows
            # of (I - c L) applied per session
            return jax.lax.map(
                mv_one, (u_local, other, w, cb, deg, full, cs))

        def embed(ys):
            z = jnp.zeros((g, n_pad, k), jnp.float32)
            return jax.lax.dynamic_update_slice(z, ys, (0, row_start, 0))

        def dilated_local(full):
            # degree - 1 matvecs with plain row assembly; the LAST
            # matvec's rows stay local so its assembly can fuse with
            # whatever reduction the caller needs next
            def body(_, fz):
                return _psum(embed(mv_all(fz)), model_axes)
            fz = jax.lax.fori_loop(0, degree - 1, body, full)
            return mv_all(fz)

        def step_one(vv, st, av, lr, gr=None):
            return apply_solver_step(
                step_fn, solvers.SolverState(v=vv, step=st), av, lr,
                gram=gr)

        def step_body(carry, _):
            vloc, full, stepc = carry
            av_loc = dilated_local(full)
            if fused_gram:
                grams = jax.vmap(solvers.panel_gram2k)(vloc, av_loc)
                # THE fused collective: row assembly + gram reduction
                av_full, grams = _psum((embed(av_loc), grams), model_axes)
                stepped = jax.vmap(step_one)(vloc, stepc, av_loc, lrs,
                                             grams)
                new_full = jax.vmap(step_one)(full, stepc, av_full, lrs,
                                              grams).v
                return (stepped.v, new_full, stepped.step), None
            # no gram form (oja): assemble plainly, step replicated
            av_full = _psum(embed(av_loc), model_axes)
            stepped = jax.vmap(step_one)(full, stepc, av_full, lrs)
            new_loc = jax.lax.dynamic_slice(
                stepped.v, (0, row_start, 0), (g, rows, k))
            return (new_loc, stepped.v, stepped.step), None

        per_session = jnp.broadcast_to(jnp.asarray(chunks, jnp.int32),
                                       (g,))
        vloc0 = jax.lax.dynamic_slice(vp, (0, row_start, 0),
                                      (g, rows, k))
        carry0 = (vloc0, vp, jnp.zeros((g,), jnp.int32))

        def chunk_body(i, carry):
            stepped, _ = jax.lax.scan(step_body, carry, None,
                                      length=steps)
            live = i < per_session  # (G,) freeze mask past the budget
            return tuple(
                jnp.where(live.reshape((g,) + (1,) * (s.ndim - 1)), s, c)
                for s, c in zip(stepped, carry))

        _, full, _ = jax.lax.fori_loop(0, jnp.max(per_session),
                                       chunk_body, carry0)
        av_full = _psum(embed(dilated_local(full)), model_axes)
        res = jax.vmap(metrics.panel_residual)(full, av_full)
        return full[:, :n, :], res

    return jax.jit(tick)


def build_tick_program(schedule: StepSchedule, *, layout=None, mesh=None,
                       edge_axes=("data",), model_axes=None):
    """One compiled batched tick program for a session group:
    fn(*operands, vs, cs, lrs, chunks).

    ``operands`` are the group's stacked operator source
    (:mod:`repro.stream.source`): the (G, cap) edge buffers ``src, dst,
    w`` when ``layout`` is None, else node-blocked layouts ``u_local,
    other, w, cb, deg`` with the static ``layout = (block_n, num_chunks,
    block_e)``.  ``vs`` are the (G, n, k) panels, ``cs``/``lrs`` the
    traced per-session (G,) dilation scales and learning rates, and
    ``chunks`` the scheduler's traced tick multipliers (see
    :func:`_group_loop`): the whole adaptive layer moves underneath one
    compiled program per (schedule statics, layout, shapes).

    The operands pick the matvec and the batching: edge buffers run the
    segment gather/scatter with the solver step vmapped; a layout runs
    the node-blocked kernel and the fused mu-EG kernel step under
    ``lax.map`` (pallas grids don't vmap).  ``mesh`` shard_maps the body
    with the operands split over ``edge_axes`` along their second
    (capacity or shard) axis: every dilation matvec psums the stacked
    panels and the AXPY applies after it, where the single-device kernel
    fuses it into its epilogue.  ``model_axes`` (with a mesh and a
    layout) selects the PANEL-sharded tick
    (:func:`build_tick_model_sharded`).
    """
    if mesh is not None and model_axes is not None:
        if layout is None:
            raise ValueError(
                "the model-sharded tick needs the blocking layout "
                "statics (block_n, num_chunks, block_e)")
        return build_tick_model_sharded(schedule, mesh, model_axes,
                                        *layout)
    degree, steps = schedule.degree, schedule.steps
    interp = backend_mod.kernel_interpret()
    if layout is None:
        step_all = _vmapped_step(solvers.STEP_FNS[schedule.method])
    else:
        step_all = _mapped_step(
            solvers.make_step_fn(schedule.method, "pallas"))

    axes = None if mesh is None else edge_axes

    def tick(*args):
        *ops, vs, cs, lrs, chunks = args
        if layout is None:
            opv_all = _segment_opv_all(*ops, cs, degree, axes)
        else:
            opv_all = _blocked_opv_all(*ops, cs, degree, *layout, interp,
                                       edge_axes=axes)
        return _group_loop(opv_all, step_all, vs, lrs, steps, chunks)

    if mesh is not None:
        spec = P(None, edge_axes)
        tick = shard_map(
            tick, mesh=mesh,
            in_specs=(spec,) * (3 if layout is None else 5) + (P(),) * 4,
            out_specs=(P(), P()),
            # scan carries mix varying/unvarying values, and pallas_call
            # has no replication rule
            check_vma=False)
    return jax.jit(tick)


# ---------------------------------------------------------------------------
# residual-decay forecasting (the adaptive scheduler's math)
# ---------------------------------------------------------------------------

def contraction_rate(res_prev: float, res: float,
                     steps: int) -> float | None:
    """Measured per-step residual decay ratio, or None when the pair of
    observations carries no usable contraction signal (non-finite,
    non-positive, zero steps, or not actually decaying)."""
    if steps <= 0 or not (math.isfinite(res_prev) and math.isfinite(res)):
        return None
    if not (0.0 < res < res_prev):
        return None
    return (res / res_prev) ** (1.0 / steps)


def predicted_residual(res: float, rate: float, steps: int) -> float:
    """Forecast the panel residual after ``steps`` more solver steps."""
    return res * rate ** steps


def predicted_steps_to_tol(res: float, rate: float | None,
                           tol: float) -> int:
    """Predicted-contraction stopping: solver steps until the residual
    is forecast to reach ``tol`` (0 when already there; a large sentinel
    when the rate predicts no convergence)."""
    if res <= tol:
        return 0
    if rate is None or not (0.0 < rate < 1.0):
        return 1 << 30
    return int(math.ceil(math.log(tol / res) / math.log(rate)))


__all__ = [
    "PsumStats",
    "StepSchedule",
    "apply_solver_step",
    "build_tick_model_sharded",
    "build_tick_program",
    "contraction_rate",
    "count_psums",
    "num_model_shards",
    "dilation_scale",
    "predicted_residual",
    "predicted_steps_to_tol",
    "run_chunk",
    "run_program",
    "schedule_degrees",
    "session_lr",
    "wanted_scale",
]
