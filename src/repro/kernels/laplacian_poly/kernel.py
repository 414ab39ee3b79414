"""Pallas TPU kernel: fused AXPY-matmul  out = U - c * (L @ U).

This is one step of the limit-series recurrence u <- u - (L u)/l (paper
Table 2), the inner loop of SPED's deployable path.  Fusing the AXPY into
the matmul epilogue halves HBM traffic for the panel: the naive form
writes L@U to HBM and reads it back for the subtraction; here the
subtraction happens in VMEM on the final reduction step.

Tiling: L is (n, n) blocked (bm, bk) on the MXU-aligned grid
(n/bm, n/bk); U is an (n, k) panel blocked (bk, k).  The (bm, k)
accumulator lives in the output ref (f32) across the reduction dimension
— revisited blocks stay resident in VMEM (Mosaic guarantees the output
block is carried across grid steps that map to the same output tile when
the reduction dimension is the innermost grid axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST  # TPU's default is one bf16 pass


def _poly_step_kernel(l_ref, u_in_ref, u_row_ref, c_ref, out_ref):
    """Grid (i, j): out[i] accumulates sum_j L[i,j] @ U[j]; on the last j
    the epilogue rewrites out[i] = U[i] - c * acc."""
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        l_ref[...], u_in_ref[...], precision=_HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _epilogue():
        c = c_ref[0]
        out_ref[...] = u_row_ref[...] - c * out_ref[...]


def poly_step(l_mat: jax.Array, u: jax.Array, c: float | jax.Array,
              *, block_m: int = 256, block_k: int = 256,
              interpret: bool = False) -> jax.Array:
    """out = U - c * (L @ U).  Shapes: L (n, n), U (n, k); n % block == 0
    (the ops.py wrapper pads)."""
    n, k = u.shape
    assert l_mat.shape == (n, n)
    assert n % block_m == 0 and n % block_k == 0, (n, block_m, block_k)
    c_arr = jnp.asarray(c, jnp.float32).reshape(1)
    grid = (n // block_m, n // block_k)
    return pl.pallas_call(
        _poly_step_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),  # L tile
            pl.BlockSpec((block_k, k), lambda i, j: (j, 0)),  # U (reduce)
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),  # U (row, AXPY)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # c scalar
        ],
        out_specs=pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(l_mat, u, u, c_arr)


def _matmul_kernel(a_ref, b_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def dense_matvec_panel(l_mat: jax.Array, u: jax.Array,
                       *, block_m: int = 256, block_k: int = 256,
                       interpret: bool = False) -> jax.Array:
    """Plain tiled L @ U (the baseline the fused kernel is measured
    against in benchmarks)."""
    n, k = u.shape
    assert n % block_m == 0 and n % block_k == 0
    grid = (n // block_m, n // block_k)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),
            pl.BlockSpec((block_k, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(l_mat, u)
