"""Pure-jnp oracle for the fused mu-EG update (mirrors solvers.mu_eg_step)."""
import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST  # TPU's default is one bf16 pass


def mu_eg_update(v: jax.Array, av: jax.Array, lr: float) -> jax.Array:
    k = v.shape[1]
    vav = jnp.matmul(v.T, av, precision=_HI)
    lower = jnp.tril(jnp.ones((k, k), v.dtype), k=-1)
    penalties = jnp.matmul(v, (lower * vav).T, precision=_HI)
    grad = av - penalties
    grad = grad - v * jnp.sum(v * grad, axis=0, keepdims=True)
    vn = v + lr * grad
    return vn / jnp.maximum(jnp.linalg.norm(vn, axis=0, keepdims=True), 1e-30)


def coefficient_matrices(s2: jax.Array, k: int, lr: float):
    """Derive (M1, M2, colscale) from the 2k x 2k gram of [V | AV] such
    that mu_eg_update(V, AV) == (V @ M1 + AV @ M2) * colscale.

    Algebra: penalties = V C0 with C0 = (tril(vav,-1))^T;
    Riemannian coefficient d = diag(vav) - diag(vv C0);
    V + lr grad = V M1 + AV M2, M1 = I - lr (C0 + diag(d)), M2 = lr I;
    col norms^2 = diag([M1; M2]^T S2 [M1; M2]).
    """
    vv = s2[:k, :k]
    vav = s2[:k, k:]
    avav = s2[k:, k:]
    eye = jnp.eye(k, dtype=s2.dtype)
    lower = jnp.tril(jnp.ones((k, k), s2.dtype), k=-1)
    c0 = (lower * vav).T
    d = jnp.diagonal(vav) - jnp.diagonal(jnp.matmul(vv, c0, precision=_HI))
    m1 = eye - lr * (c0 + jnp.diag(d))
    m2 = lr * eye
    def quad(a, s, b):
        return jnp.diagonal(
            jnp.linalg.multi_dot([a.T, s, b], precision=_HI))

    norm2 = (quad(m1, vv, m1) + quad(m1, vav, m2) + quad(m2, vav.T, m1)
             + quad(m2, avav, m2))
    colscale = jax.lax.rsqrt(jnp.maximum(norm2, 1e-60))
    return m1, m2, colscale
