"""The controls behind the correctness limits, at a size a CPU holds.

The control of a cell is its plain reference computed one precision below
the configuration's: m4's matmuls as the three-pass bfloat16 product of
`high` precision instead of float32 `highest`. On the chip, at the cell's
size, it has to fail the cell's limit (`bench/control.py`, readings in
PERF.md); here it has to fail the tiny cell's limit, set the same way.
"""
import jax
import jax.numpy as jnp
import numpy as np
from bench import control
from bench.systems import m4_ref
from bench.systems.lowp import round_bf16


def test_round_bf16_is_round_to_nearest_even():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,), jnp.float32) * 1e3
    ties = jnp.array([1.0 + 2.0 ** -8, 1.0 + 3 * 2.0 ** -8], jnp.float32)
    for v in (x, ties):
        want = np.asarray(v).astype(jnp.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(round_bf16(v)), want)


def test_high_matmul_is_three_bf16_passes():
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    a = jax.random.normal(k[0], (64, 400), jnp.float32)
    b = jax.random.normal(k[1], (400, 1200), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    scale = np.abs(exact).max()
    hi = np.abs(np.asarray(m4_ref._mm_highest(a, b)) - exact).max() / scale
    lo = np.abs(np.asarray(m4_ref._mm_high(a, b)) - exact).max() / scale
    one = np.abs(np.asarray(round_bf16(a) @ round_bf16(b),
                            np.float64) - exact).max() / scale
    assert hi < 1e-6 < lo < 1e-4 < one


def test_control_fails_where_the_program_passes(checkout):
    r = control.readings(checkout, "m4.tiny", [5, 11], log=lambda s: 0)
    assert r["program_correct"] == [True, True]
    assert r["control_correct"] == [False, False]
    gap = "fct_gap_mean"
    assert (max(c[gap] for c in r["program"]) < r["limits"][gap]
            < min(c[gap] for c in r["control"]))
