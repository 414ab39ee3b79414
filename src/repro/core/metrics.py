"""Convergence metrics (paper Sec. 5.2)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST  # TPU's default is one bf16 pass


def subspace_error(v: jax.Array, v_star: jax.Array) -> jax.Array:
    """Normalized subspace error, Eq. (15):  1 - tr(U* P_t) / k.

    v, v_star: (n, k) bases (v need not be orthonormal — P uses a
    pseudo-inverse via QR as in Tang 2019 / Gemp et al. 2021a).
    """
    k = v_star.shape[1]
    q, _ = jnp.linalg.qr(v)  # orthonormal basis of span(v)
    # tr(V* V*^T Q Q^T) = ||V*^T Q||_F^2
    m = jnp.matmul(v_star.T, q, precision=_HI)
    return 1.0 - jnp.sum(m * m) / k


def eigenvector_streak(v: jax.Array, v_star: jax.Array,
                       eps: float = 1e-2) -> jax.Array:
    """Longest consecutive run of matched eigenvectors (Gemp et al. 2021a).

    Eigenvector i counts as converged when |cos(angle(v_i, v*_i))| is
    within eps of 1 (sign-invariant).  Harsher than subspace error: the
    actual ORDERED eigenvectors must be recovered.
    """
    vn = v / jnp.maximum(jnp.linalg.norm(v, axis=0, keepdims=True), 1e-30)
    cos = jnp.abs(jnp.sum(vn * v_star, axis=0))
    ok = cos >= 1.0 - eps
    # longest prefix of ok
    return jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))


def panel_residual(v: jax.Array, av: jax.Array, eps: float = 1e-30) -> jax.Array:
    """Relative block-Rayleigh residual ||A V - V (V^T A V)||_F / ||A V||_F.

    Ground-truth-free convergence signal: 0 iff span(V) is an invariant
    subspace of A.  Used by the streaming service to decide per-session
    convergence and by warm-start to decide restart-vs-continue (columns
    of V are assumed orthonormal, as solver states maintain).
    """
    rayleigh = jnp.matmul(v.T, av, precision=_HI)  # (k, k)
    r = av - jnp.matmul(v, rayleigh, precision=_HI)
    return jnp.linalg.norm(r) / jnp.maximum(jnp.linalg.norm(av), eps)


def operator_residual(matvec, v: jax.Array) -> jax.Array:
    """``panel_residual`` of a panel under an operator: one operator
    application + the block-Rayleigh residual.  The single residual
    evaluation every solve program (one-shot, streaming ticks, sharded,
    warm reconvergence) ends its compiled loop with."""
    return panel_residual(v, matvec(v))


def ground_truth_bottom_k(l_mat: jax.Array, k: int, drop_trivial: bool = False):
    """Bottom-k eigenpairs of dense L via eigh (ascending).

    drop_trivial skips the all-ones nullvector (lambda_1 = 0) when the
    clustering only cares about the Fiedler directions.
    """
    lam, v = jnp.linalg.eigh(l_mat)
    s = 1 if drop_trivial else 0
    return lam[s: s + k], v[:, s: s + k]
