"""Pallas TPU kernels for the fused mu-EigenGame update.

A mu-EG step (paper Sec. 5.1; Gemp et al. 2021b) on a panel V with
operator output AV is, in matrix form:

    vav  = V^T A V                         (k, k)
    grad = AV - V (tril(vav, -1))^T        penalties from parents
    grad = grad - V diag(colsum(V * grad)) Riemannian projection
    V'   = colnormalize(V + lr grad)

Every term after the grams is a LINEAR combination V' = (V M1 + AV M2) S
with k x k coefficient matrices computed from the grams of [V | AV]
(ops.py does that tiny k x k algebra in plain jnp).  So the whole update
needs exactly TWO passes over the (n, k) panels:

  * gram2k:    S2 = [V|AV]^T [V|AV]   — one fused tiled reduction
  * panel_mix: V' = (V @ M1 + AV @ M2) * colscale — one fused pass

versus ~7 separate elementwise/matmul passes in the naive form.  This is
the paper's solver inner loop made HBM-minimal.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST  # TPU's default is one bf16 pass


def _gram2k_kernel(v_ref, av_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    cat = jnp.concatenate([v_ref[...], av_ref[...]], axis=1)  # (bn, 2k)
    out_ref[...] += jnp.dot(cat.T, cat, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)


def gram2k(v: jax.Array, av: jax.Array, *, block_n: int = 512,
           interpret: bool = False) -> jax.Array:
    """S = [V|AV]^T [V|AV]  (2k, 2k); n % block_n == 0 (ops pads)."""
    n, k = v.shape
    assert n % block_n == 0
    return pl.pallas_call(
        _gram2k_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((2 * k, 2 * k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((2 * k, 2 * k), jnp.float32),
        interpret=interpret,
    )(v, av)


def _panel_mix_kernel(v_ref, av_ref, m1_ref, m2_ref, scale_ref, out_ref):
    acc = jnp.dot(v_ref[...], m1_ref[...], precision=_HIGHEST,
                  preferred_element_type=jnp.float32)
    acc += jnp.dot(av_ref[...], m2_ref[...], precision=_HIGHEST,
                   preferred_element_type=jnp.float32)
    out_ref[...] = acc * scale_ref[0:1, :]


def panel_mix(v: jax.Array, av: jax.Array, m1: jax.Array, m2: jax.Array,
              colscale: jax.Array, *, block_n: int = 512,
              interpret: bool = False) -> jax.Array:
    """V' = (V @ M1 + AV @ M2) * colscale, one pass over the panels."""
    n, k = v.shape
    assert n % block_n == 0
    scale2d = jnp.broadcast_to(colscale.reshape(1, k), (8, k))
    return pl.pallas_call(
        _panel_mix_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),
            pl.BlockSpec((k, k), lambda i: (0, 0)),
            pl.BlockSpec((k, k), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(v, av, m1, m2, scale2d)
