"""Iterative/stochastic top-k SVD solvers (paper Sec. 5.1).

Two representative solvers from the paper:
  * Oja's algorithm (Shamir 2015): gradient ascent on the trace objective
    with QR retraction.
  * mu-EigenGame / "EigenGame Unloaded" (Gemp et al. 2021b): per-vector
    utility ascent with Riemannian projection; penalties use v_j (not
    A v_j), which is what makes unbiased minibatch estimates possible.

Both consume an OPERATOR ``matvec: (n,k) -> (n,k)`` computing A @ V where
A is the (reversed, transformed) Laplacian — exact, series-approximated,
or stochastic.  The solver itself is agnostic; that separation is the
paper's architecture: transformation and estimation happen inside the
operator, convergence happens here.

Solvers find the TOP-k of A; the Eq. (8) reversal makes those the
bottom-k of L.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

MatVec = Callable[[jax.Array], jax.Array]
# stochastic operators additionally take a PRNG key
StochMatVec = Callable[[jax.Array, jax.Array], jax.Array]

# f32 matmuls at full precision: TPU's default is one bf16 pass, which
# caps panel products at ~3 significant digits (the residual target is
# 2e-3).  Other backends compute f32 exactly either way.
_HI = jax.lax.Precision.HIGHEST


class SolverState(NamedTuple):
    v: jax.Array  # (n, k) current estimate, orthonormal columns
    step: jax.Array  # scalar int32


def init_state(key: jax.Array, n: int, k: int, dtype=jnp.float32) -> SolverState:
    v0 = jax.random.normal(key, (n, k), dtype=dtype)
    q, _ = jnp.linalg.qr(v0)
    return SolverState(v=q, step=jnp.zeros((), jnp.int32))


def init_from_panel(v: jax.Array) -> SolverState:
    """Warm-start hook: seed a solver from an existing (n, k) panel.

    Orthonormalizes via QR (with the same sign fix as `oja_step`), so a
    previous session's converged eigenvectors — or a first-order
    incrementally-updated panel — can seed the next solve directly.
    """
    q, r = jnp.linalg.qr(v)
    sign = jnp.sign(jnp.diagonal(r))
    sign = jnp.where(sign == 0, 1.0, sign)
    return SolverState(v=q * sign[None, :], step=jnp.zeros((), jnp.int32))


def oja_step(state: SolverState, av: jax.Array, lr: float) -> SolverState:
    """V <- QR(V + lr * A V).  One Oja update with QR retraction."""
    v = state.v + lr * av
    q, r = jnp.linalg.qr(v)
    # fix QR sign ambiguity for determinism (diag(R) >= 0)
    sign = jnp.sign(jnp.diagonal(r))
    sign = jnp.where(sign == 0, 1.0, sign)
    return SolverState(v=q * sign[None, :], step=state.step + 1)


def mu_eg_step(state: SolverState, av: jax.Array, lr: float) -> SolverState:
    """One mu-EigenGame (unloaded) update.

    grad_i = A v_i - sum_{j<i} <v_i, A v_j> v_j        (utility gradient)
    r_i    = grad_i - <v_i, grad_i> v_i                (sphere projection)
    v_i   <- normalize(v_i + lr * r_i)
    """
    v = state.v
    vav = jnp.matmul(v.T, av, precision=_HI)  # [i, j] = <v_i, A v_j>
    # strictly-lower mask: penalties from parents j < i
    k = v.shape[1]
    lower = jnp.tril(jnp.ones((k, k), v.dtype), k=-1)
    # penalty_i = sum_{j<i} vav[i, j] * v_j  -> columns: V @ (lower * vav)^T
    penalties = jnp.matmul(v, (lower * vav).T, precision=_HI)
    grad = av - penalties
    grad = grad - v * jnp.sum(v * grad, axis=0, keepdims=True)  # Riemannian
    vn = v + lr * grad
    vn = vn / jnp.maximum(jnp.linalg.norm(vn, axis=0, keepdims=True), 1e-30)
    return SolverState(v=vn, step=state.step + 1)


def mu_eg_step_fused(state: SolverState, av: jax.Array, lr: float,
                     *, interpret: bool = False) -> SolverState:
    """mu-EigenGame step via the fused Pallas kernels: the update is the
    linear combination V' = (V @ M1 + AV @ M2) * colscale with k x k
    coefficient matrices from the gram of [V | AV]
    (repro.kernels.eg_update.coefficient_matrices), so the whole step is
    TWO panel passes (gram + mix) instead of ~7 elementwise/matmul
    passes.  Same math as :func:`mu_eg_step` — the segment oracle."""
    from repro.kernels.eg_update import ops as eg_ops

    v = eg_ops.mu_eg_update(state.v, av, lr, interpret=interpret)
    return SolverState(v=v, step=state.step + 1)


def panel_gram2k(v: jax.Array, av: jax.Array) -> jax.Array:
    """2k x 2k gram of the stacked panel [V | AV] — the ONLY panel
    reduction the mu-EG step needs (see :func:`mu_eg_step_from_gram`).

    Row-decomposable: for any partition of the rows into disjoint
    slices, the full gram is the SUM of the per-slice grams.  That is
    what lets a model-sharded tick compute it per shard on owned rows
    and psum the contributions fused with the panel assembly."""
    x = jnp.concatenate([v, av], axis=1)
    return jnp.matmul(x.T, x, precision=_HI)


def mu_eg_step_from_gram(state: SolverState, av: jax.Array,
                         gram: jax.Array, lr) -> SolverState:
    """mu-EG update from a PRECOMPUTED 2k x 2k gram of [V | AV].

    Same math as :func:`mu_eg_step`: the update is the linear mix
    V' = (V @ M1 + AV @ M2) * colscale with coefficient matrices derived
    from the gram alone (repro.kernels.eg_update.ref), so once ``gram``
    is known the step is ROW-LOCAL — ``state.v``/``av`` may be any row
    slice of the panel (a model shard's owned rows) as long as ``gram``
    is the global gram.  This is the fused-collective hook of the
    model-sharded tick: per-shard grams psum together with the panel
    assembly, then every shard mixes its own rows with zero further
    communication.
    """
    from repro.kernels.eg_update import ref as eg_ref

    k = state.v.shape[1]
    m1, m2, colscale = eg_ref.coefficient_matrices(gram, k, lr)
    vn = (jnp.matmul(state.v, m1, precision=_HI)
          + jnp.matmul(av, m2, precision=_HI)) * colscale[None, :]
    return SolverState(v=vn, step=state.step + 1)


STEP_FNS = {"oja": oja_step, "mu_eg": mu_eg_step}


def make_step_fn(method: str, backend: str = "auto"):
    """Solver step on the selected backend (repro.core.backend).

    ``mu_eg`` + pallas selects the fused two-pass kernel step; ``oja``
    has no kernel form (its QR retraction dominates) and stays on the
    segment implementation for every backend.
    """
    from repro.core import backend as backend_mod

    if method == "mu_eg" and backend_mod.resolve_backend(backend) == "pallas":
        return functools.partial(
            mu_eg_step_fused, interpret=backend_mod.kernel_interpret())
    return STEP_FNS[method]


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    method: str = "mu_eg"  # "oja" | "mu_eg"
    lr: float = 1e-3
    steps: int = 1000
    eval_every: int = 10
    k: int = 8
    seed: int = 0
    backend: str = "auto"  # solver-step kernels: auto | segment | pallas


class Trace(NamedTuple):
    """Metrics recorded every eval_every steps."""
    steps: jax.Array  # (T,)
    subspace_error: jax.Array  # (T,)
    streak: jax.Array  # (T,)


def run_solver(
    operator: MatVec | StochMatVec,
    n: int,
    cfg: SolverConfig,
    v_star: jax.Array | None = None,
    stochastic: bool = False,
    init_v: jax.Array | None = None,
) -> tuple[SolverState, Trace]:
    """Run a solver, recording metrics against ground truth v_star.

    Thin wrapper over :func:`repro.core.program.run_program` — the
    unified solve loop shared with the streaming tick programs and the
    distributed solves.  The whole run is one jitted scan over eval
    chunks, so Python overhead is O(1) in the number of steps.  `init_v`
    warm-starts from an (n, k) panel (orthonormalized via
    `init_from_panel`) instead of the default random init — the
    streaming service's reconvergence path.
    """
    from repro.core import program  # deferred: program builds on solvers

    return program.run_program(operator, n, cfg, v_star=v_star,
                               stochastic=stochastic, init_v=init_v)


def steps_to_tolerance(trace: Trace, tol: float) -> int:
    """First recorded step at which subspace error <= tol (or -1)."""
    err = np.asarray(trace.subspace_error)
    idx = np.nonzero(err <= tol)[0]
    return int(np.asarray(trace.steps)[idx[0]]) if len(idx) else -1


def steps_to_streak(trace: Trace, k: int) -> int:
    """First recorded step with a full-k eigenvector streak (or -1)."""
    st = np.asarray(trace.streak)
    idx = np.nonzero(st >= k)[0]
    return int(np.asarray(trace.steps)[idx[0]]) if len(idx) else -1
