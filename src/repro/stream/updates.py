"""First-order incremental eigen-updates with drift-triggered fallback.

Dhanjal et al. ("Efficient Eigen-updating for Spectral Graph Clustering")
update the eigenbasis of a streaming graph far cheaper than re-solving.
This module implements the first-order (Rayleigh-Schrodinger) flavour for
the Laplacian: an edge batch with realized weight deltas {dw_e} is the
perturbation  ΔL = Σ_e dw_e x_e x_e^T  (rank <= B), and for eigenpairs
(λ_i, v_i) of L:

    λ_i' ≈ λ_i + v_i^T ΔL v_i
    v_i' ≈ v_i + Σ_{j≠i} (v_j^T ΔL v_i) / (λ_i - λ_j) · v_j

computed entirely from B-edge matvecs — O(B k + n k^2), no solver
iterations.  First-order accuracy degrades as accumulated perturbation
approaches the panel's eigengaps, so the module tracks a Frobenius drift
bound  Σ batches Σ_e 2|dw_e|  >= accumulated ||ΔL||_F and triggers
a FALLBACK to a full (warm-started, dilated) SPED re-solve when drift
exceeds `fallback_ratio` × (min panel eigengap) — the scheme's safety
valve.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.laplacian import edge_matvec_arrays

_HI = jax.lax.Precision.HIGHEST  # TPU's default is one bf16 pass

MatVec = Callable[[jax.Array], jax.Array]


class EigenEstimate(NamedTuple):
    """Tracked bottom-k eigenpairs of L plus accumulated perturbation."""

    lam: jax.Array  # (k,) eigenvalue estimates, ascending-ish
    v: jax.Array  # (n, k) orthonormal panel
    drift: jax.Array  # () accumulated upper bound on ||ΔL||_F since solve


@dataclasses.dataclass(frozen=True)
class UpdateConfig:
    # fallback when drift > fallback_ratio * min eigengap of the panel
    fallback_ratio: float = 0.5
    gap_floor: float = 1e-8  # denominators |λ_i - λ_j| below this are skipped


def estimate_from_panel(matvec: MatVec, v: jax.Array) -> EigenEstimate:
    """Anchor an estimate at a freshly solved panel: λ = diag(VᵀLV)."""
    lam = jnp.diagonal(jnp.matmul(v.T, matvec(v), precision=_HI))
    return EigenEstimate(lam=lam, v=v, drift=jnp.zeros((), v.dtype))


@jax.jit
def anchor_estimate_arrays(src: jax.Array, dst: jax.Array, w: jax.Array,
                           v: jax.Array) -> EigenEstimate:
    """Anchor an estimate on a padded edge buffer: ``lambda = diag(V^T L
    V)`` with drift reset (was ``stream.service._anchor_estimate``)."""
    return estimate_from_panel(
        lambda x: edge_matvec_arrays(src, dst, w, x), v)


def delta_matvec(src: jax.Array, dst: jax.Array, dw: jax.Array,
                 v: jax.Array) -> jax.Array:
    """ΔL @ v for an edge batch with realized weight deltas dw, O(B k)."""
    return edge_matvec_arrays(src, dst, dw, v)


def delta_norm_bound(dw: jax.Array) -> jax.Array:
    """||ΔL||_F <= Σ_e 2|dw_e|  (triangle inequality over per-edge
    contributions; each dw_e x_e x_eᵀ has Frobenius norm exactly 2|dw_e|).

    A per-edge sum, not 2·sqrt(Σdw²): edges sharing an endpoint stack
    their diagonal contributions, so the root-sum-of-squares form is NOT
    an upper bound for hub-centered batches.
    """
    return 2.0 * jnp.sum(jnp.abs(dw))


def min_gap(lam: jax.Array, floor: float = 1e-8) -> jax.Array:
    """Smallest consecutive gap of the sorted eigenvalue estimates."""
    s = jnp.sort(lam)
    return jnp.maximum(jnp.min(s[1:] - s[:-1]), floor)


@functools.partial(jax.jit, static_argnames=("gap_floor",))
def first_order_update(
    est: EigenEstimate,
    src: jax.Array,
    dst: jax.Array,
    dw: jax.Array,
    gap_floor: float = 1e-8,
) -> EigenEstimate:
    """One Dhanjal-style first-order eigen-update for an edge batch.

    Correction terms between eigenpairs closer than `gap_floor` are
    skipped (their 1/gap amplification is noise-dominated).
    """
    dv = delta_matvec(src, dst, dw, est.v)  # ΔL V, (n, k)
    c = jnp.matmul(est.v.T, dv, precision=_HI)  # c[j, i] = v_jᵀ ΔL v_i
    lam_new = est.lam + jnp.diagonal(c)
    k = est.lam.shape[0]
    denom = est.lam[None, :] - est.lam[:, None]  # [j, i] = λ_i - λ_j
    offdiag = ~jnp.eye(k, dtype=bool)
    safe = offdiag & (jnp.abs(denom) > gap_floor)
    coef = jnp.where(safe, c / jnp.where(safe, denom, 1.0), 0.0)
    # column i += Σ_j coef[j, i] v_j
    v_new = est.v + jnp.matmul(est.v, coef, precision=_HI)
    q, r = jnp.linalg.qr(v_new)  # restore orthonormality
    sign = jnp.sign(jnp.diagonal(r))
    sign = jnp.where(sign == 0, 1.0, sign)
    return EigenEstimate(
        lam=lam_new,
        v=q * sign[None, :],
        drift=est.drift + delta_norm_bound(dw),
    )


def should_fallback(est: EigenEstimate, cfg: UpdateConfig = UpdateConfig()
                    ) -> jax.Array:
    """True when accumulated perturbation endangers first-order validity."""
    return est.drift > cfg.fallback_ratio * min_gap(est.lam, cfg.gap_floor)


def update_or_flag(
    est: EigenEstimate,
    src: jax.Array,
    dst: jax.Array,
    dw: jax.Array,
    cfg: UpdateConfig = UpdateConfig(),
) -> tuple[EigenEstimate, bool]:
    """Apply the first-order update; report whether the caller must now
    fall back to a full re-solve (stream.service resets drift to 0 by
    re-anchoring via `estimate_from_panel` after that solve)."""
    est = first_order_update(est, src, dst, dw, gap_floor=cfg.gap_floor)
    return est, bool(should_fallback(est, cfg))
