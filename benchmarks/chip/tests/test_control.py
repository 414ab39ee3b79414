"""The solve cell's control comes out as not correct (test size): the
cell's own run with its Laplacian products lowered to bfloat16, judged
by the cell's own comparison."""
import jax.numpy as jnp
import ml_dtypes
import numpy as np

import control
from test_faults import SOLVE_GRAPH


def test_round_bf16_is_round_to_nearest_even():
    rng = np.random.default_rng(3)
    x = np.concatenate([
        rng.standard_normal(4096).astype(np.float32) * 10.0 ** rng.integers(
            -6, 6, 4096),
        # exact ties between two bfloat16 neighbours, both parities
        np.array([1.0 + 2.0 ** -8, 1.0 + 3 * 2.0 ** -8, -(1.0 + 2.0 ** -8),
                  0.0, -0.0], np.float32)])
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    got = np.asarray(control.round_bf16(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)


def test_bfloat16_products_fail_the_solve_check():
    r = control.lowered_precision_run(
        "dblp.solve", 2**31 + 9, 4, check_chip=False,
        config_overrides={"graph": SOLVE_GRAPH})
    assert r["checks"]["commits_missing"]["pass"], r["checks"]
    assert not r["correct"]
    assert not r["checks"]["sin_last"]["pass"]
