"""Pallas TPU kernels: incidence SpMM  Y = X^T W (X V), one-hot and
node-blocked variants, with a fused affine epilogue.

The stochastic heart of SPED (paper Sec. 3/4.3): a batch of E edges
defines incidence rows x_e (+1 at src, -1 at dst); the Laplacian
(estimate) applied to the panel V is

    Y = sum_e w_e x_e (x_e^T V)  =  X^T diag(w) X V.

GPU implementations scatter-add per edge.  TPUs have no efficient
scatter, so the TPU-native adaptation (DESIGN.md Sec. 3) materializes
one-hot incidence BLOCKS in VMEM and rides the MXU:

one-hot variant (``edge_spmm``, n <= ONE_HOT_NODE_LIMIT = 4096):

    X_t   = onehot(src)^T - onehot(dst)^T      (n, BE)   built via iota
    D     = X_t^T @ V                           (BE, k)   MXU
    Y    += (X_t * w) @ D                       (n, k)    MXU

Grid over edge blocks; Y accumulates in the output ref.  V is assumed to
fit VMEM (n x k panels with k <= 128; the backend layer caps this
variant at n <= ONE_HOT_NODE_LIMIT = 4096 — the small-graph
spectral-clustering regime).

node-blocked variant (``edge_spmm_nb``, any n):

    L v = deg * v - A v  decomposes the matvec into an elementwise
    degree term and an adjacency SpMM.  Host code (ops.py) expands each
    edge into two directed half-edges (u <- o, weight w), buckets them
    by the node-block of the DESTINATION u, and pre-gathers the source
    rows G = V[o].  The kernel then only ever holds a (block_n, k)
    panel slice plus a (block_n, BE) LOCAL one-hot in VMEM:

    out[b]  = deg[b] * V[b]                     (init, first chunk of b)
    out[b] -= (onehot(u_local)^T * w) @ G_chunk (block_n, BE) MXU per chunk

    The chunk layout is CSR-style VARIABLE-per-block: a hub node-block
    owns many chunks, a sparse one owns a single chunk, and the grid is
    1-D over TOTAL chunks.  A scalar-prefetched chunk->block index map
    (``PrefetchScalarGridSpec``) steers the deg/panel/output BlockSpecs
    to the right node-block per chunk, so skewed (power-law) graphs pay
    sum-of-chunks work instead of blocks * max-chunks uniform padding.
    Chunks arrive sorted by block, so each output block is revisited
    contiguously (the Pallas revisiting contract: the block accumulates
    in VMEM across its run and writes back once) and the per-block init/
    epilogue fire on the first/last chunk of the run, detected from the
    prefetched map.  Each (BE, k) gathered slice streams HBM->VMEM via
    the standard Pallas grid pipeline, i.e. the slice for chunk j+1 is
    double-buffered behind chunk j's MXU work.

Per-edge streams (src/dst/w, u_local/w) and the degrees reach the
kernels as lane-dense (1, BE) rows, which is why both build their
one-hot blocks transposed (edge index on lanes).  Matmuls run at
HIGHEST precision.

Both kernels end with the fused AFFINE EPILOGUE

    out = alpha * (L V)_block + beta * V_block

on the last grid step, which folds one series-recurrence step — the
limit-series u <- u - c (L u) (alpha=-c, beta=1) or the Chebyshev/
Clenshaw t(L) u = a L u + b u — into the SpMM so the panel never
round-trips HBM between the matvec and the AXPY.  alpha=1, beta=0
recovers the plain matvec.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 matmuls at full precision: the default on TPU is one bf16 pass,
# which would cap the matvec at ~3 significant digits.
_HIGHEST = jax.lax.Precision.HIGHEST


def _edge_spmm_kernel(src_ref, dst_ref, w_ref, v_ref, ab_ref, out_ref):
    e = pl.program_id(0)
    ne = pl.num_programs(0)

    @pl.when(e == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    n = v_ref.shape[0]
    be = src_ref.shape[-1]
    # edge streams arrive as lane-dense (1, BE) rows, so the incidence
    # block is built transposed: x_t[c, e] = [src_e == c] - [dst_e == c]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, be), 0)
    x_t = ((rows == src_ref[...]).astype(jnp.float32)
           - (rows == dst_ref[...]).astype(jnp.float32))  # (n, BE)
    d = jax.lax.dot_general(  # X_blk @ V, contracting x_t's node axis
        x_t, v_ref[...], (((0,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    out_ref[...] += jnp.dot(x_t * w_ref[...], d, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(e == ne - 1)
    def _epilogue():
        out_ref[...] = ab_ref[0] * out_ref[...] + ab_ref[1] * v_ref[...]


def _edge_rows(x: jax.Array, block_e: int) -> jax.Array:
    """(E,) edge stream -> (E / block_e, 1, block_e): each grid step then
    reads one lane-dense (1, block_e) row.  A 1-D (block_e,) block is
    refused by Mosaic once XLA tiles the stream T(1024)."""
    return x.reshape(x.shape[0] // block_e, 1, block_e)


def edge_spmm(src: jax.Array, dst: jax.Array, w: jax.Array, v: jax.Array,
              ab: jax.Array | None = None,
              *, block_e: int = 128, interpret: bool = False) -> jax.Array:
    """Y = alpha * sum_e w_e x_e x_e^T V + beta * V over the edge batch.
    ``ab`` is the (2,) [alpha, beta] epilogue (default [1, 0] == plain
    matvec), read as scalars from SMEM.  E % block_e == 0 (ops.py pads
    with zero-weight edges)."""
    e = src.shape[0]
    n, k = v.shape
    assert e % block_e == 0, (e, block_e)
    if ab is None:
        ab = jnp.asarray([1.0, 0.0], jnp.float32)
    edge_spec = pl.BlockSpec((None, 1, block_e), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _edge_spmm_kernel,
        grid=(e // block_e,),
        in_specs=[
            edge_spec, edge_spec, edge_spec,
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((n, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(_edge_rows(src, block_e), _edge_rows(dst, block_e),
      _edge_rows(w, block_e), v, ab)


def _edge_spmm_nb_kernel(cb_ref, u_ref, w_ref, g_ref, deg_ref, v_ref,
                         ab_ref, out_ref):
    j = pl.program_id(0)
    nc = pl.num_programs(0)
    blk = cb_ref[j]
    # First/last chunk of this block's (contiguous, block-sorted) run.
    # cb_ref has nc + 1 entries; the tail sentinel repeats the last block
    # so cb_ref[j + 1] is always in bounds and never opens a new run.
    prev = cb_ref[jnp.maximum(j - 1, 0)]
    is_first = jnp.logical_or(j == 0, prev != blk)
    is_last = jnp.logical_or(j == nc - 1, cb_ref[j + 1] != blk)
    bn, kp = out_ref.shape

    @pl.when(is_first)
    def _init():
        # the (1, block_n) degree row becomes a (block_n, kp) column
        # broadcast through one transpose
        deg_col = jnp.broadcast_to(deg_ref[...], (kp, bn)).T
        out_ref[...] = deg_col * v_ref[...]

    be = u_ref.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, be), 0)
    # weighted local one-hot, transposed: oh_t[r, e] = w_e [u_e == r]
    oh_t = jnp.where(rows == u_ref[...], w_ref[...], 0.0)  # (block_n, BE)
    out_ref[...] -= jnp.dot(oh_t, g_ref[...], precision=_HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(is_last)
    def _epilogue():
        out_ref[...] = ab_ref[0] * out_ref[...] + ab_ref[1] * v_ref[...]


def edge_spmm_nb(u_local: jax.Array, w: jax.Array, gathered: jax.Array,
                 chunk_block: jax.Array, deg: jax.Array, v: jax.Array,
                 ab: jax.Array, *, block_n: int, block_e: int,
                 num_chunks: int, interpret: bool = False) -> jax.Array:
    """Node-blocked Y = alpha * (L V) + beta * V, variable chunks/block.

    Half-edges are bucketed by destination node-block into a CSR-style
    chunk list (ops.build_node_blocking): ``chunk_block`` maps each of
    the ``num_chunks`` grid steps to its node-block, every block owns at
    least one chunk, and padding chunks extend the LAST block's run with
    zero weights.  The map is scalar-prefetched so the deg/panel/output
    BlockSpecs below index data-dependently per chunk; source rows are
    pre-gathered into ``gathered`` = V[other] and streamed (BE, k) at a
    time by the grid pipeline.  VMEM per grid step: one (block_n, k)
    panel slice, one (block_e, k) gathered chunk, and the
    (block_n, block_e) local one-hot — independent of total n and of
    graph skew.  The per-half-edge streams and the degrees are read as
    lane-dense rows (see :func:`_edge_rows`); ``ab`` sits in SMEM.
    """
    np_, k = v.shape
    assert np_ % block_n == 0, (np_, block_n)
    assert u_local.shape[0] == num_chunks * block_e, \
        (u_local.shape, num_chunks, block_e)
    assert chunk_block.shape[0] == num_chunks + 1, \
        (chunk_block.shape, num_chunks)
    edge_spec = pl.BlockSpec((None, 1, block_e), lambda j, cb: (j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_chunks,),
        in_specs=[
            edge_spec, edge_spec,
            pl.BlockSpec((block_e, k), lambda j, cb: (j, 0)),
            pl.BlockSpec((None, 1, block_n), lambda j, cb: (cb[j], 0, 0)),
            pl.BlockSpec((block_n, k), lambda j, cb: (cb[j], 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_n, k), lambda j, cb: (cb[j], 0)),
    )
    return pl.pallas_call(
        _edge_spmm_nb_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((np_, k), jnp.float32),
        interpret=interpret,
    )(chunk_block, _edge_rows(u_local, block_e), _edge_rows(w, block_e),
      gathered, _edge_rows(deg, block_n), v, ab)
