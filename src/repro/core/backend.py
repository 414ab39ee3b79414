"""Matvec backend selection: segment gather/scatter vs Pallas kernels.

Every solver-facing operator constructor (``operators.edge_matvec``,
``operators.minibatch_operator``, ``operators.planned_operator``,
``operators.series_operator`` via its fused-step hook, the streaming
service's compiled tick programs, and
``distributed.sharded_laplacian_matvec``) routes its inner Laplacian
matvec through this layer:

  * ``backend="segment"`` — the pure-jnp ``at[].add`` gather/scatter in
    :mod:`repro.core.laplacian`.  Portable; the XLA scatter serializes
    on TPU.
  * ``backend="pallas"`` — the TPU kernels in :mod:`repro.kernels`.
    On small graphs (n <= ``ONE_HOT_NODE_LIMIT``) the one-hot incidence
    SpMM holds the whole (n, k) panel in VMEM; beyond that the
    NODE-BLOCKED kernel is used, whose host-side layout
    (:func:`build_node_blocking`) buckets half-edges by destination
    node-block so VMEM only ever holds a (block_n, k) panel slice —
    that is the VMEM blocking contract: per grid step the kernel touches
    one (block_n, k) output slice, one (block_e, k) pre-gathered source
    chunk, and a (block_e, block_n) local one-hot, independent of n.
  * ``backend="auto"`` — pallas on TPU, segment elsewhere.

Off-TPU, pallas kernels run in INTERPRET mode (``kernel_interpret()``),
which is correct but slow — it exists so the equivalence tests and CPU
CI exercise the exact kernel code paths.  Force a backend by passing
``backend="segment"|"pallas"`` to any operator constructor, or set the
``REPRO_BACKEND`` environment variable to override ``"auto"``.

Fused series steps: the factories here return, alongside the plain
matvec, a ``fused_step(u, alpha, beta) -> alpha * L u + beta * u`` that
folds one series-recurrence AXPY into the SpMM epilogue (see
``SpectralSeries.apply_fused``).  For the segment backend the fused
step is ``None`` and series fall back to their classic recurrences.
"""
from __future__ import annotations

import functools
import os
from typing import Callable

import jax

from repro.core import laplacian as lap
from repro.kernels.edge_spmm import ops as es_ops
from repro.kernels.edge_spmm.ops import (  # noqa: F401  (re-exported API)
    ModelShardedBlocking,
    NodeBlocking,
    ShardedNodeBlocking,
    build_model_sharded_blocking,
    build_node_blocking,
    build_sharded_node_blocking,
)

MatVec = Callable[[jax.Array], jax.Array]
# fused_step(u, alpha, beta) -> alpha * (L @ u) + beta * u
FusedStep = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]

BACKENDS = ("auto", "segment", "pallas")

# Largest n the one-hot kernel may hold as a full (block_e, n) incidence
# block + (n, k) panel in VMEM; past it the node-blocked layout is used.
ONE_HOT_NODE_LIMIT = 4096

# Default node-block size for auto-built blockings: 512 rows x 128 lanes
# x 4 B = 256 kB per panel slice — comfortably inside ~16 MB VMEM next
# to the (block_e, block_n) one-hot and the gathered chunk.
DEFAULT_BLOCK_N = 512


def is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_interpret() -> bool:
    """Pallas interpret mode: on for every non-TPU backend (tests/CI)."""
    return not is_tpu()


def resolve_backend(backend: str = "auto") -> str:
    """'auto' -> 'pallas' on TPU, 'segment' elsewhere (overridable via
    the REPRO_BACKEND environment variable)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    if backend == "auto":
        env = os.environ.get("REPRO_BACKEND", "")
        if env:
            if env not in ("segment", "pallas"):
                raise ValueError(
                    f"REPRO_BACKEND={env!r}: expected 'segment' or 'pallas'")
            return env
        return "pallas" if is_tpu() else "segment"
    return backend


def resolve_for_arrays(backend: str, num_nodes: int) -> str:
    """Backend for call sites WITHOUT a precomputed node blocking
    (minibatch draws, probes, per-shard matvecs): pallas there means the
    one-hot kernel, so past its VMEM node limit the resolution degrades
    to segment instead of blowing VMEM.  THE single copy of that policy
    — blocking-aware call sites use ``resolve_backend`` directly."""
    b = resolve_backend(backend)
    if b == "pallas" and num_nodes > ONE_HOT_NODE_LIMIT:
        return "segment"
    return b


def blocking_for(g: lap.EdgeList, *, block_n: int | None = None,
                 block_e: int = 128) -> NodeBlocking:
    """Host-side node-blocked layout of an EdgeList (concrete arrays)."""
    return build_node_blocking(
        g.src, g.dst, g.weight, g.num_nodes,
        block_n=block_n or DEFAULT_BLOCK_N, block_e=block_e)


def sharded_blocking_for(g: lap.EdgeList, num_shards: int,
                         *, block_n: int | None = None,
                         block_e: int = 128) -> ShardedNodeBlocking:
    """Per-shard node-blocked layouts of a mesh-padded EdgeList — the
    scalable layout for ``distributed.sharded_blocked_matvec`` (the
    sharded pallas path past ``ONE_HOT_NODE_LIMIT``)."""
    return build_sharded_node_blocking(
        g.src, g.dst, g.weight, g.num_nodes, num_shards,
        block_n=block_n or DEFAULT_BLOCK_N, block_e=block_e)


def _needs_blocking(num_nodes: int) -> bool:
    return num_nodes > ONE_HOT_NODE_LIMIT


def fused_step_fn(g: lap.EdgeList, backend: str = "auto",
                  blocking: NodeBlocking | None = None) -> FusedStep | None:
    """fused_step(u, alpha, beta) = alpha * L u + beta * u, or None.

    The pallas path picks the one-hot kernel for small n and the
    node-blocked kernel otherwise (building — host-side, so ``g`` must
    hold concrete arrays — and capturing the blocking when none is
    supplied).  Segment returns None: callers then use the plain matvec
    recurrences, whose subtract-after-matvec ordering is bitwise
    identical to an explicit AXPY.
    """
    if resolve_backend(backend) == "segment":
        return None
    interp = kernel_interpret()
    if blocking is None and _needs_blocking(g.num_nodes):
        blocking = blocking_for(g)
    if blocking is not None:
        def fused(u, alpha, beta):
            return es_ops.edge_spmm_blocked(
                blocking, u, alpha=alpha, beta=beta, interpret=interp)
        return fused

    def fused(u, alpha, beta):
        return es_ops.edge_spmm(g.src, g.dst, g.weight, u,
                                alpha=alpha, beta=beta, interpret=interp)
    return fused


def laplacian_matvec_fn(g: lap.EdgeList, backend: str = "auto",
                        blocking: NodeBlocking | None = None) -> MatVec:
    """V -> L @ V on the resolved backend (V may be (n,) or (n, k))."""
    fused = fused_step_fn(g, backend, blocking)
    if fused is None:
        return functools.partial(lap.laplacian_matvec, g)
    return lambda v: fused(v, 1.0, 0.0)


def edge_arrays_matvec_fn(src: jax.Array, dst: jax.Array, weight: jax.Array,
                          backend: str = "auto",
                          *, num_nodes: int | None = None,
                          interpret: bool | None = None) -> MatVec:
    """Raw-array matvec factory for jit-internal call sites (spectral
    probes, minibatch draws, per-shard matvecs) where no host-side
    blocking can be built: the pallas path uses the one-hot kernel, and
    when ``num_nodes`` is given the ``resolve_for_arrays`` guard drops
    to segment past the kernel's VMEM node limit."""
    b = (resolve_for_arrays(backend, num_nodes) if num_nodes is not None
         else resolve_backend(backend))
    if b == "segment":
        return functools.partial(lap.edge_matvec_arrays, src, dst, weight)
    interp = kernel_interpret() if interpret is None else interpret
    return lambda v: es_ops.edge_spmm(src, dst, weight, v, interpret=interp)
