"""Matrix-free spectral probes: SLQ, Hutchinson, and edge localizers.

Every dilation transform in this repo has free parameters — polynomial
degree, spectral-radius scale, reversal shift — whose right values are
functions of the SPECTRUM of the graph at hand.  This module estimates
that spectrum with a handful of matvecs, using the same ``MatVec``
convention as :mod:`repro.core.operators`, so the probes run unchanged
on dense, edge-list, capacity-padded, sharded, and minibatch operators.

Probes
------
``lanczos``
    m-step Lanczos with full (twice-is-enough classical Gram-Schmidt)
    reorthogonalization.  m is small (10-30), so the O(m n) per-step
    reorthogonalization is cheaper than losing orthogonality and
    duplicating Ritz values.  Breakdown (Krylov space exhausted, e.g.
    m >= n on tiny graphs) is guarded: the recurrence continues on zero
    vectors, which appends decoupled zero-weight blocks to the
    tridiagonal that quadrature then ignores.
``slq_probe``
    Stochastic Lanczos quadrature (Ubaru, Chen & Saad 2017): run
    ``num_probes`` independent Lanczos recurrences from random unit
    vectors; each tridiagonal's eigendecomposition yields Ritz nodes
    theta_j and weights tau_j^2 (squared first eigenvector components)
    — an n-point spectral measure compressed to m points.  From these we
    read off (1) a tight ``lambda_max`` estimate (top Ritz value plus
    its residual bound beta_m |e_m^T y|; Lanczos converges at the edges
    first, so a few steps suffice), (2) an unbiased trace estimate, and
    (3) a coarse spectral-density histogram (`spectral_density`).
``hutchinson_trace``
    Girard-Hutchinson trace estimator with Rademacher probes; works on
    both deterministic and keyed (stochastic minibatch) matvecs, and is
    unbiased for the minibatch operator because batch and probe draws
    are independent.
``bottom_edge``
    Cheap bottom-edge eigengap localizer: the SLQ weights estimate the
    eigenvalue COUNTING function N(t) ~ n * sum_{theta_j <= t} w_j
    (weights carry eigenspace multiplicity, so clustered bottom
    eigenvalues that Lanczos dedupes still count), and the k-th /
    (k+1)-th crossing points localize (lambda_k, lambda_{k+1}).

Node-padded operators (the streaming store's capacity classes) are
handled by ``n_real``: probe vectors are masked to the first ``n_real``
rows, and since no edge touches a padding node, the whole Krylov space
stays in the real subspace — the probe never sees the padding zeros.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.laplacian import EdgeList, edge_matvec_arrays

MatVec = Callable[[jax.Array], jax.Array]

# Breakdown test is RELATIVE to the raw matvec norm: normalizing a
# residual that is pure round-off (||w|| ~ eps * ||L q||) would amplify
# its non-orthogonal round-off components by 1/||w|| and poison every
# later reorthogonalization, so such steps terminate the recurrence
# instead (the Krylov space is numerically invariant at that point).
_BREAKDOWN_REL = 1e-4
_TINY = 1e-30
_HI = jax.lax.Precision.HIGHEST  # TPU's default is one bf16 pass


class ProbeResult(NamedTuple):
    """Compressed spectral information from one SLQ run.

    All fields are arrays (jit-transparent); ``n`` is the REAL node
    count the quadrature is normalized to (a padded operator probes as
    its unpadded self).
    """

    ritz: jax.Array  # (num_probes, num_steps) Ritz nodes per probe
    weights: jax.Array  # (num_probes, num_steps) quadrature weights, rows sum to 1
    lambda_max: jax.Array  # () residual-corrected top-edge estimate
    trace: jax.Array  # () SLQ estimate of tr(L)
    n: jax.Array  # () float32 real node count
    num_matvecs: jax.Array  # () int32 probe cost in single-vector matvecs


def lanczos(matvec: MatVec, v0: jax.Array, num_steps: int
            ) -> tuple[jax.Array, jax.Array]:
    """m-step Lanczos with full reorthogonalization.

    Returns (alpha (m,), beta (m,)): the tridiagonal is
    diag(alpha) + offdiag(beta[:-1]); beta[-1] is the residual norm
    feeding the Ritz-value error bound.  v0 need not be normalized.

    Breakdown (graphs with few distinct eigenvalues exhaust the Krylov
    space in < m steps) is sticky: the recurrence continues on zero
    vectors, with zero alpha/beta, so the tridiagonal gains decoupled
    zero blocks whose quadrature weight is exactly zero.
    """
    n = v0.shape[0]
    dtype = v0.dtype
    q0 = v0 / jnp.maximum(jnp.linalg.norm(v0), _TINY)
    # num_steps + 1 rows: row m is scratch for the final next-vector write
    q_buf = jnp.zeros((num_steps + 1, n), dtype).at[0].set(q0)

    def body(i, carry):
        q, alpha, beta = carry
        w = matvec(q[i])
        raw_norm = jnp.linalg.norm(w)
        a = jnp.vdot(q[i], w)
        # Full reorthogonalization against every stored vector (rows > i
        # are zero, so no masking needed); twice kills the O(eps kappa)
        # residue of the first pass.
        for _ in range(2):
            w = w - jnp.matmul(q.T, jnp.matmul(q, w, precision=_HI),
                               precision=_HI)
        b = jnp.linalg.norm(w)
        alive = b > _BREAKDOWN_REL * (raw_norm + _TINY)
        keep = jnp.where(alive, 1.0, 0.0)
        q_next = keep * w / jnp.maximum(b, _TINY)
        return (q.at[i + 1].set(q_next), alpha.at[i].set(a),
                beta.at[i].set(keep * b))

    _, alpha, beta = jax.lax.fori_loop(
        0, num_steps, body,
        (q_buf, jnp.zeros((num_steps,), dtype), jnp.zeros((num_steps,), dtype)))
    return alpha, beta


def _tridiag_eig(alpha: jax.Array, beta: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    """(theta, U) of the m x m Lanczos tridiagonal (m is small)."""
    m = alpha.shape[0]
    t = jnp.diag(alpha)
    if m > 1:
        t = t + jnp.diag(beta[:-1], 1) + jnp.diag(beta[:-1], -1)
    return jnp.linalg.eigh(t)


def slq_probe(
    matvec: MatVec,
    n: int,
    key: jax.Array,
    *,
    num_probes: int = 4,
    num_steps: int = 24,
    n_real: jax.Array | int | None = None,
) -> ProbeResult:
    """Stochastic Lanczos quadrature of the operator's spectrum.

    Fully traceable: wrap in jit at the call site (see ``probe_graph``
    and the streaming service) so shapes — not values — decide
    compilation.  ``n_real`` masks probe vectors for node-padded
    operators and may be a traced scalar.
    """
    n_real_f = jnp.asarray(n if n_real is None else n_real, jnp.float32)
    mask = (jnp.arange(n, dtype=jnp.float32) <
            n_real_f) if n_real is not None else None

    def one(k: jax.Array):
        v0 = jax.random.normal(k, (n,), jnp.float32)
        if mask is not None:
            v0 = v0 * mask
        alpha, beta = lanczos(matvec, v0, num_steps)
        theta, u = _tridiag_eig(alpha, beta)
        w = u[0, :] ** 2  # quadrature weights; sums to 1
        # Ritz residual ||L y - theta y|| = beta_m |e_m^T u| per pair
        resid = beta[-1] * jnp.abs(u[-1, :])
        return theta, w, jnp.max(theta + resid)

    theta, weights, lam_ub = jax.vmap(one)(jax.random.split(key, num_probes))
    trace = n_real_f * jnp.mean(jnp.sum(weights * theta, axis=1))
    return ProbeResult(
        ritz=theta,
        weights=weights,
        lambda_max=jnp.max(lam_ub),
        trace=trace,
        n=n_real_f,
        num_matvecs=jnp.asarray(num_probes * num_steps, jnp.int32),
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "num_probes", "num_steps", "backend"))
def probe_edge_arrays(
    src: jax.Array,
    dst: jax.Array,
    weight: jax.Array,
    key: jax.Array,
    n_real: jax.Array,
    *,
    num_nodes: int,
    num_probes: int = 4,
    num_steps: int = 24,
    backend: str = "segment",
) -> ProbeResult:
    """Jitted SLQ over bare (possibly capacity-padded) edge buffers.

    One compile per (edge capacity, node capacity, probe config,
    backend) — the streaming service's capacity classes hit this cache,
    so probing a newly admitted session recompiles nothing.

    ``backend`` routes the probe matvec through repro.core.backend so
    the spectrum estimate exercises the same kernels the solve will.
    Blockings cannot be built under trace, so the pallas path uses the
    one-hot kernel and silently stays on segment past its n limit.
    """
    from repro.core import backend as backend_mod

    matvec = backend_mod.edge_arrays_matvec_fn(src, dst, weight, backend,
                                               num_nodes=num_nodes)
    return slq_probe(
        matvec, num_nodes, key,
        num_probes=num_probes, num_steps=num_steps, n_real=n_real)


@functools.lru_cache(maxsize=64)
def _sharded_probe_program(mesh, edge_axes: tuple, num_nodes: int,
                           num_probes: int, num_steps: int, backend: str):
    """Compiled sharded-SLQ program, cached per (mesh, shapes, config).

    ONE shard_mapped program wraps the whole quadrature: probe vectors
    are vmapped inside on replicated panels and every Lanczos matvec is
    a per-shard kernel followed by one psum over the edge axes — the
    probe distributes exactly like the solve it tunes.
    """
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.core import backend as backend_mod

    b = backend_mod.resolve_for_arrays(backend, num_nodes)
    interp = backend_mod.kernel_interpret()
    spec_e = P(edge_axes)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec_e, spec_e, spec_e, P(), P()),
        out_specs=P(),
        check_vma=False)  # Lanczos scan carries mixed-replication values
    def probe(src, dst, weight, key, n_real):
        local = backend_mod.edge_arrays_matvec_fn(
            src, dst, weight, b, num_nodes=num_nodes, interpret=interp)

        def mv(v):
            return jax.lax.psum(local(v), edge_axes)

        return slq_probe(mv, num_nodes, key,
                         num_probes=num_probes, num_steps=num_steps,
                         n_real=n_real)

    return jax.jit(probe)


def probe_sharded_edge_arrays(
    mesh,
    src: jax.Array,
    dst: jax.Array,
    weight: jax.Array,
    key: jax.Array,
    n_real: jax.Array,
    *,
    num_nodes: int,
    edge_axes=("data",),
    num_probes: int = 4,
    num_steps: int = 24,
    backend: str = "segment",
) -> ProbeResult:
    """SLQ over MESH-SHARDED edge buffers (stream.sharded's probe path).

    Semantically identical to :func:`probe_edge_arrays` — same Lanczos
    recurrence, same keys, the matvec is just psum-assembled from edge
    shards — so the streaming service's dilation anchors match between
    sharded and single-device serving up to collective summation order.
    The edge buffer's length must divide evenly by the mesh's edge-axis
    shard count (the store's balanced capacity invariant).
    """
    program = _sharded_probe_program(
        mesh, tuple(edge_axes), num_nodes, num_probes, num_steps, backend)
    return program(src, dst, weight, key, n_real)


@functools.lru_cache(maxsize=64)
def _model_probe_program(mesh, model_axes: tuple, block_n: int,
                         block_e: int, num_chunks: int, num_nodes: int,
                         num_shards: int, rows: int,
                         num_probes: int, num_steps: int, backend: str):
    """Compiled PANEL-sharded SLQ program, cached per (mesh, layout
    statics, config).

    The matvec decomposes by node ownership instead of by edge slice:
    each shard computes its OWNED rows of ``L v`` from its
    destination-aligned chunk layout (``model_local_rows`` — the same
    row computation the model-sharded tick runs) and one psum assembles
    the disjoint row ranges.  No shard ever materializes another
    shard's edges.
    """
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.core import backend as backend_mod
    from repro.kernels.edge_spmm import ops as es_ops

    use_kernel = backend_mod.resolve_backend(backend) == "pallas"
    interp = backend_mod.kernel_interpret()
    n_pad = num_shards * rows
    spec_b = P(model_axes)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec_b,) * 5 + (P(), P()),
        out_specs=P(),
        check_vma=False)  # Lanczos scan carries mixed-replication values
    def probe(u_local, other, weight, chunk_block, deg, key, n_real):
        sidx = jnp.zeros((), jnp.int32)
        for a in model_axes:
            sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
        row_start = sidx * rows
        ab = jnp.asarray([1.0, 0.0], jnp.float32)  # plain L v

        def mv(v):
            owned = es_ops.model_local_rows(
                u_local[0], other[0], weight[0], chunk_block[0], deg[0],
                v[:, None], ab, row_start,
                block_n=block_n, block_e=block_e, num_chunks=num_chunks,
                padded_nodes=n_pad, use_kernel=use_kernel,
                interpret=interp)
            z = jnp.zeros((n_pad, 1), jnp.float32)
            full = jax.lax.psum(
                jax.lax.dynamic_update_slice(z, owned, (row_start, 0)),
                model_axes)
            return full[:num_nodes, 0]

        return slq_probe(mv, num_nodes, key,
                         num_probes=num_probes, num_steps=num_steps,
                         n_real=n_real)

    return jax.jit(probe)


def probe_model_sharded(
    mesh,
    blocking,
    key: jax.Array,
    n_real: jax.Array,
    *,
    model_axes=("model",),
    num_probes: int = 4,
    num_steps: int = 24,
    backend: str = "segment",
) -> ProbeResult:
    """SLQ over a PANEL-sharded layout (the model-serving probe path).

    ``blocking`` is a :class:`~repro.kernels.edge_spmm.ops
    .ModelShardedBlocking`; the quadrature is semantically identical to
    :func:`probe_edge_arrays` — same Lanczos recurrence, same keys —
    with the matvec psum-assembled from each shard's owned rows, so the
    dilation anchors match replicated serving up to summation order.
    """
    program = _model_probe_program(
        mesh, tuple(model_axes), blocking.block_n, blocking.block_e,
        blocking.num_chunks, blocking.num_nodes, blocking.num_shards,
        blocking.rows_per_shard, num_probes, num_steps, backend)
    return program(blocking.u_local, blocking.other, blocking.weight,
                   blocking.chunk_block, blocking.deg, key, n_real)


def probe_graph(
    g: EdgeList,
    key: jax.Array | None = None,
    num_probes: int = 4,
    num_steps: int = 24,
    backend: str = "segment",
) -> ProbeResult:
    """Host convenience: SLQ-probe an EdgeList's Laplacian spectrum."""
    if key is None:
        key = jax.random.PRNGKey(0)
    num_steps = min(num_steps, g.num_nodes)
    return probe_edge_arrays(
        g.src, g.dst, g.weight, key,
        jnp.asarray(g.num_nodes, jnp.int32),
        num_nodes=g.num_nodes, num_probes=num_probes, num_steps=num_steps,
        backend=backend)


def probe_from_eigenvalues(lam) -> ProbeResult:
    """Exact ProbeResult from a full spectrum — the oracle the planner
    benchmarks calibrate against (same planner, perfect probe)."""
    lam = jnp.sort(jnp.asarray(lam, jnp.float32).ravel())
    n = lam.shape[0]
    w = jnp.full((1, n), 1.0 / n, jnp.float32)
    return ProbeResult(
        ritz=lam[None, :],
        weights=w,
        lambda_max=lam[-1],
        trace=jnp.sum(lam),
        n=jnp.asarray(n, jnp.float32),
        num_matvecs=jnp.asarray(0, jnp.int32),
    )


def hutchinson_trace(
    matvec,
    n: int,
    key: jax.Array,
    *,
    num_probes: int = 16,
    keyed: bool = False,
    n_real: jax.Array | int | None = None,
) -> jax.Array:
    """Girard-Hutchinson trace estimate with Rademacher probes.

    ``keyed=True`` treats ``matvec`` as a stochastic op(key, v) — e.g.
    the minibatch Laplacian — and gives each probe an independent batch
    key, keeping the estimator unbiased for E_batch[op] (probe and batch
    draws are independent, and each enters the quadratic form linearly).
    """
    mask = (jnp.arange(n, dtype=jnp.float32) <
            jnp.asarray(n_real, jnp.float32)) if n_real is not None else None

    def one(k: jax.Array) -> jax.Array:
        zk, bk = jax.random.split(k)
        z = jax.random.rademacher(zk, (n,), jnp.float32)
        if mask is not None:
            z = z * mask
        az = matvec(bk, z) if keyed else matvec(z)
        return jnp.vdot(z, az)

    return jnp.mean(jax.vmap(one)(jax.random.split(key, num_probes)))


# ---------------------------------------------------------------------------
# Host-side readouts (feed the planner, which returns static jit args).
# ---------------------------------------------------------------------------

def _counting_points(probe: ProbeResult) -> tuple[np.ndarray, np.ndarray]:
    """Pooled (sorted ritz nodes, cumulative eigenvalue counts)."""
    theta = np.asarray(probe.ritz, np.float64).ravel()
    num_probes = probe.ritz.shape[0]
    count = np.asarray(probe.weights, np.float64).ravel() \
        * float(probe.n) / num_probes
    order = np.argsort(theta)
    return theta[order], np.cumsum(count[order])


def eigenvalue_count(probe: ProbeResult, t: float) -> float:
    """Estimated #{lambda_i <= t} from the SLQ measure."""
    theta, cum = _counting_points(probe)
    idx = np.searchsorted(theta, t, side="right")
    return float(cum[idx - 1]) if idx > 0 else 0.0


def _crossing(theta: np.ndarray, cum: np.ndarray, level: float) -> float:
    return float(theta[min(np.searchsorted(cum, level), len(theta) - 1)])


def bottom_edge(probe: ProbeResult, k: int) -> tuple[float, float]:
    """Coarse (lambda_k, lambda_{k+1}) localizer (1-indexed, ascending).

    Scans the estimated eigenvalue counting function
    N(t) ~ n * sum_{theta_j <= t} w_j for the WIDEST gap between pooled
    Ritz nodes whose below-count is plausibly k (within max(1, k/2) —
    per-probe cluster weights fluctuate at Chi^2 scale, so exact
    crossings of k are coin flips on degenerate spectra, while a
    macroscopic gap survives any plausible count).  Weights carry
    eigenspace multiplicity, so a cluster of near-equal bottom
    eigenvalues that Lanczos collapses to one Ritz node still
    contributes its full count.  Falls back to the plain k-th/(k+1)-th
    crossings when no gap has a plausible count (gapless bottom edge).
    Coarse by construction — the planner consumes it through a snapped
    decision grid, so small probe noise maps to the same plan.
    """
    theta, cum = _counting_points(probe)
    tol = max(1.0, 0.5 * k)
    best_width = -1.0
    best = None
    for i in range(len(theta) - 1):
        if abs(cum[i] - k) <= tol:
            width = theta[i + 1] - theta[i]
            if width > best_width:
                best_width = width
                best = (theta[i], theta[i + 1])
    if best is None:
        best = (_crossing(theta, cum, k - 0.5), _crossing(theta, cum, k + 0.5))
    lam_k, lam_k1 = best
    lam_k = max(float(lam_k), 0.0)
    return lam_k, max(float(lam_k1), lam_k)


def spectral_density(
    probe: ProbeResult,
    num_bins: int = 32,
    lo: float = 0.0,
    hi: float | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Coarse spectral-density histogram: (bin_edges (B+1,), mass (B,)).

    ``mass`` estimates eigenvalue counts per bin and sums to ~n (Ritz
    nodes outside [lo, hi] are clipped into the boundary bins so no mass
    is silently dropped).
    """
    if hi is None:
        hi = float(probe.lambda_max)
    hi = max(hi, lo + 1e-12)
    theta = np.asarray(probe.ritz, np.float64).ravel()
    num_probes = probe.ritz.shape[0]
    count = np.asarray(probe.weights, np.float64).ravel() \
        * float(probe.n) / num_probes
    edges = np.linspace(lo, hi, num_bins + 1)
    mass, _ = np.histogram(np.clip(theta, lo, hi), bins=edges, weights=count)
    return edges, mass
