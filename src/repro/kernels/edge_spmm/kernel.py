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
    acc[b] += (w * G_chunk)^T @ onehot(u_local) (k, block_n) MXU per chunk
    out[b] -= acc[b]^T                          (last chunk of b)

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

    Its scatter keeps float32 in bf16 MXU passes: the local one-hot is
    exactly 0/1 in bf16, the weight multiplies the gathered chunk in f32,
    and that product splits exactly into three bf16 parts (hi + mid + lo,
    8 + 8 + 8 bits of mantissa), so every MXU product is exact and only
    the f32 accumulation rounds.  The product contracts the chunk's
    half-edges: the chunk's k live columns are transposed onto sublanes
    (k rounded up to whole sublane tiles, kr), so the weight row
    multiplies them with no broadcast and the accumulator is a small
    (3*kr, block_n) f32 scratch rather than a (block_n, 128) block.
    The three parts stack on sublanes into one (3*kr, BE) operand, so
    each chunk makes ONE bf16 dot at every k (the MXU takes any number
    of operand rows), and the block's last chunk folds the scratch's
    three row groups.

Per-edge streams (src/dst/w, u_local/w) and the degrees reach the
kernels as lane-dense (1, BE) rows, which is why both build their
one-hot blocks transposed (edge index on lanes).  The one-hot variant's
matmuls run at HIGHEST precision.

Both kernels end with the fused AFFINE EPILOGUE

    out = alpha * (L V)_block + beta * V_block

on the last grid step, which folds one series-recurrence step — the
limit-series u <- u - c (L u) (alpha=-c, beta=1) or the Chebyshev/
Clenshaw t(L) u = a L u + b u — into the SpMM so the panel never
round-trips HBM between the matvec and the AXPY.  alpha=1, beta=0
recovers the plain matvec.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 matmuls at full precision: the default on TPU is one bf16 pass,
# which would cap the matvec at ~3 significant digits.
_HIGHEST = jax.lax.Precision.HIGHEST
# vreg lane width: the wrappers pad panels to a multiple of it
LANES = 128


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


def _rows(k: int) -> int:
    """k panel columns as whole sublane tiles of 8 rows."""
    return -(-k // 8) * 8


def _top8(x: jax.Array) -> jax.Array:
    """x with its f32 mantissa cut to the 8 significant bits bf16 holds
    (truncated, not rounded: the low 16 bits of the word cleared)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & -(1 << 16)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split3(x: jax.Array):
    """f32 x -> f32 (hi, mid, lo), each exact in bf16, with hi + mid + lo
    == x exactly: hi takes the top 8 of x's 24 significant bits, mid the
    top 8 of the remainder and lo the at most 8 bits left; each
    subtraction is exact in f32."""
    hi = _top8(x)
    r = x - hi
    mid = _top8(r)
    return hi, mid, r - mid


def _edge_spmm_nb_kernel(cb_ref, u_ref, w_ref, g_ref, deg_ref, v_ref,
                         ab_ref, out_ref, acc_ref, *, k: int):
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
    be = u_ref.shape[-1]
    kr = _rows(k)

    @pl.when(is_first)
    def _init():
        # the (1, block_n) degree row becomes a (block_n, kp) column
        # broadcast through one transpose; done here, not in the
        # epilogue, so the transpose stays off the per-chunk path
        deg_col = jnp.broadcast_to(deg_ref[...], (kp, bn)).T
        out_ref[...] = deg_col * v_ref[...]
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, be), 0)
    # local one-hot, transposed: oh[r, e] = [u_e == r], exact in bf16
    oh = (rows == u_ref[...]).astype(jnp.bfloat16)  # (block_n, BE)
    # the chunk's live columns as rows, so the lane-dense weight row
    # multiplies them in f32 with no broadcast across lanes
    x = g_ref[...].T[:kr] * w_ref[...]  # (kr, BE)
    hi, mid, lo = _split3(x)

    # the three parts stacked on sublanes, @ oh^T, contracting the
    # chunk's half-edges: one pass, in which every product is 1 x a bf16
    # value, so it is exact up to the MXU's f32 accumulation; acc_ref's
    # row groups keep sum(hi), sum(mid), sum(lo) apart
    acc_ref[...] += jax.lax.dot_general(
        jnp.concatenate([hi, mid, lo], axis=0).astype(jnp.bfloat16), oh,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(is_last)
    def _epilogue():
        a = acc_ref[...]
        yt = a[:kr] + a[kr:2 * kr] + a[2 * kr:]  # (kr, block_n)
        if kr < kp:
            yt = jnp.concatenate(
                [yt, jnp.zeros((kp - kr, bn), jnp.float32)])
        lv = out_ref[...] - yt.T
        out_ref[...] = ab_ref[0] * lv + ab_ref[1] * v_ref[...]


def edge_spmm_nb(u_local: jax.Array, w: jax.Array, gathered: jax.Array,
                 chunk_block: jax.Array, deg: jax.Array, v: jax.Array,
                 ab: jax.Array, *, k: int, block_n: int, block_e: int,
                 num_chunks: int, interpret: bool = False) -> jax.Array:
    """Node-blocked Y = alpha * (L V) + beta * V, variable chunks/block.

    Half-edges are bucketed by destination node-block into a CSR-style
    chunk list (ops.build_node_blocking): ``chunk_block`` maps each of
    the ``num_chunks`` grid steps to its node-block, every block owns at
    least one chunk, and padding chunks extend the LAST block's run with
    zero weights.  The map is scalar-prefetched so the deg/panel/output
    BlockSpecs below index data-dependently per chunk; source rows are
    pre-gathered into ``gathered`` = V[other] and streamed (BE, kp) at a
    time by the grid pipeline.  ``v`` and ``gathered`` are padded to kp
    lanes of which the first ``k`` are live and the rest zero; the
    output's lanes >= k are zero.  VMEM per grid step: one
    (block_n, kp) panel slice, one (block_e, kp) gathered chunk, the
    (block_n, block_e) local one-hot and the (3*kr, block_n) f32
    accumulator — independent of total n and of graph skew.  The
    per-half-edge streams and the degrees are read as lane-dense rows
    (see :func:`_edge_rows`); ``ab`` sits in SMEM.
    """
    np_, kp = v.shape
    assert np_ % block_n == 0, (np_, block_n)
    assert 0 < k and kp == k + (-k) % LANES, (k, kp)
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
            pl.BlockSpec((block_e, kp), lambda j, cb: (j, 0)),
            pl.BlockSpec((None, 1, block_n), lambda j, cb: (cb[j], 0, 0)),
            pl.BlockSpec((block_n, kp), lambda j, cb: (cb[j], 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_n, kp), lambda j, cb: (cb[j], 0)),
        # sum(hi), sum(mid), sum(lo) of the open block, k rows each
        scratch_shapes=[pltpu.VMEM((3 * _rows(k), block_n), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_edge_spmm_nb_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((np_, kp), jnp.float32),
        interpret=interpret,
    )(chunk_block, _edge_rows(u_local, block_e), _edge_rows(w, block_e),
      gathered, _edge_rows(deg, block_n), v, ab)
