"""Paper Table 2: the transformation functions — analytic eigengap
dilation factor on a synthetic well-clustered spectrum plus operator
apply cost (us) at n=512, k=8."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call
from repro.core import (identity_series, limit_neg_exp, taylor_log,
                        taylor_neg_exp, with_lambda_star)
from repro.core.series import cheb_log, cheb_neg_exp
from repro.core.transforms import eigengap_ratio


def run():
    # synthetic spectrum: 4 bottom eigenvalues << bulk (well-clustered)
    lam = jnp.concatenate([
        jnp.asarray([0.0, 0.05, 0.08, 0.12]),
        jnp.linspace(20.0, 60.0, 60),
    ])
    rho = float(lam[-1])
    k = 4
    suite = {
        "identity": with_lambda_star(identity_series(), rho * 1.01),
        "taylor_log_d51": taylor_log(51, eps=0.05),
        "taylor_neg_exp_d51": taylor_neg_exp(51),
        "limit_neg_exp_d251": limit_neg_exp(251),
        "limit_neg_exp_d251_s8": limit_neg_exp(251, scale=8.0 / rho),
        "cheb_log_d64": cheb_log(64, rho=rho),
        "cheb_neg_exp_d32": cheb_neg_exp(32, rho=rho, tau=8.0 / rho),
    }
    def conv_ratio(f_vals):
        # convergence-relevant ratio for recovering the BOTTOM-k of L
        # after transform f (monotone: order preserved): spectral range
        # over the min eigengap among the bottom k+1 transformed values
        f_vals = jnp.sort(f_vals.astype(jnp.float64)
                          if False else f_vals)
        gaps = jnp.diff(f_vals[: k + 1])
        rng = f_vals[-1] - f_vals[0]
        return float(rng / jnp.maximum(jnp.min(gaps), 1e-30))

    base = conv_ratio(lam)
    n = 512
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n)) / np.sqrt(n)
    l_mat = a @ a.T * (rho / 4)
    v = jax.random.normal(jax.random.fold_in(key, 1), (n, 8))
    rows = []
    for name, s in suite.items():
        import numpy as _np
        ratio = conv_ratio(s.scalar(lam))
        fn = jax.jit(lambda vv, s=s: s.apply_reversed(lambda u: l_mat @ u, vv))
        us = time_call(fn, v, iters=3)
        dil = base / ratio if _np.isfinite(ratio) and ratio > 0 else float("nan")
        note = "" if _np.isfinite(ratio) else ";DIVERGED(paper Sec 5.3)"
        rows.append((f"transforms/{name}", round(us, 1),
                     f"ratio={ratio:.3g};dilation_x={dil:.3g}{note}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
