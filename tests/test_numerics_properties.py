"""Property-style sweeps for core/numerics, hypothesis-free by construction.

Complements tests/test_numerics.py (which uses hypothesis): these are
plain seeded ``pytest.mark.parametrize`` sweeps, so they run
identically everywhere and pin down the exact properties the AMLA kernels
lean on — the compensated-increment identity, the MIN_EXP_DELTA clamp, and
the zero-increment skip that ``rescale_skip_rate`` measures.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import numerics
from repro.core.amla import rescale_skip_rate


# ---------------------------------------------------------------------------
# apply_int_increment(acc, pow2_int_increment(dn, eps)) ~= acc * 2^dn * (1+eps)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(8))
def test_compensated_increment_matches_exact_product(seed):
    rng = np.random.default_rng(seed)
    n = 256
    # accumulator magnitudes spanning ~60 binades, both signs
    acc = np.float32(
        rng.choice([-1, 1], n) * np.exp2(rng.uniform(-30, 30, n))
        * rng.uniform(1.0, 2.0, n)
    )
    dn = rng.integers(numerics.MIN_EXP_DELTA, 1, size=n).astype(np.int32)
    eps = np.float32(rng.uniform(-1 / 256, 1 / 256, n))  # Appendix A regime

    inc = numerics.pow2_int_increment(jnp.asarray(dn), jnp.asarray(eps))
    got = np.asarray(numerics.apply_int_increment(jnp.asarray(acc), inc))
    want = acc.astype(np.float64) * np.exp2(dn.astype(np.float64)) * (1.0 + eps)
    # Appendix A: mantissa-midpoint compensation is ~2^-9 relative.
    np.testing.assert_allclose(got, want.astype(np.float32), rtol=4e-3)


@pytest.mark.parametrize("seed", range(4))
def test_increment_without_compensation_is_exact_pow2(seed):
    """eps=None: the increment is exactly the power-of-two multiply."""
    rng = np.random.default_rng(100 + seed)
    acc = np.float32(rng.normal(0, 10, 128))
    dn = rng.integers(-20, 1, size=128).astype(np.int32)
    inc = numerics.pow2_int_increment(jnp.asarray(dn), None)
    got = np.asarray(numerics.apply_int_increment(jnp.asarray(acc), inc))
    want = np.asarray(
        numerics.pow2_mul_by_add(jnp.asarray(acc), jnp.asarray(dn))
    )
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# MIN_EXP_DELTA clamp (Algorithm 2 line 11)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dn", [-31, -64, -1000, -(2**31) + 100])
def test_min_exp_delta_clamps_large_negative_deltas(dn):
    clamped = numerics.pow2_int_increment(jnp.int32(numerics.MIN_EXP_DELTA), None)
    got = numerics.pow2_int_increment(jnp.int32(dn), None)
    assert int(got) == int(clamped) == numerics.MIN_EXP_DELTA * (1 << 23)
    # applying the clamped increment scales by exactly 2^MIN_EXP_DELTA
    x = jnp.float32(3.75)
    out = float(numerics.apply_int_increment(x, got))
    assert out == pytest.approx(3.75 * 2.0**numerics.MIN_EXP_DELTA, rel=0)


def test_deltas_above_clamp_are_not_clamped():
    for dn in range(numerics.MIN_EXP_DELTA, 1):
        assert int(numerics.pow2_int_increment(jnp.int32(dn), None)) == dn * (1 << 23)


# ---------------------------------------------------------------------------
# zero-increment skip: the no-op case the kernels elide entirely
# ---------------------------------------------------------------------------
def test_zero_delta_zero_eps_gives_zero_increment():
    inc = numerics.pow2_int_increment(jnp.int32(0), jnp.float32(0.0))
    assert int(inc) == 0  # enables the kernels' @pl.when(any(inc != 0)) skip


def test_zero_increment_is_bitwise_identity():
    rng = np.random.default_rng(7)
    acc = np.float32(rng.normal(0, 100, 512))
    got = np.asarray(numerics.apply_int_increment(jnp.asarray(acc), jnp.int32(0)))
    assert got.tobytes() == acc.tobytes()


@pytest.mark.parametrize("seed", range(4))
def test_tiny_eps_rounds_to_zero_increment(seed):
    """|1.5 * eps * 2^23| < 0.5 must round to a no-op, not drift the acc."""
    rng = np.random.default_rng(200 + seed)
    eps = np.float32(rng.uniform(-1, 1, 64) * (0.49 / 1.5) * 2.0**-23)
    inc = numerics.pow2_int_increment(jnp.zeros(64, jnp.int32), jnp.asarray(eps))
    assert np.all(np.asarray(inc) == 0)


# ---------------------------------------------------------------------------
# rescale_skip_rate: the fraction of blocks where the skip fires
# ---------------------------------------------------------------------------
def test_rescale_skip_rate_counts_pow2_crossings():
    ln2 = numerics.LN2
    # n = round(-m/ln2): choose m so n is [0, 0, 1, 1, 1, 3] -> 2 changes in 5
    m_trace = -jnp.asarray([0.0, 0.2, 1.0, 1.2, 0.9, 3.0]) * ln2
    rate = float(rescale_skip_rate(m_trace[:, None]))
    assert rate == pytest.approx(1.0 - 2.0 / 5.0)


def test_rescale_skip_rate_constant_max_never_rescales():
    m_trace = jnp.full((16, 8), -3.7)
    assert float(rescale_skip_rate(m_trace)) == 1.0


def test_rescale_skip_rate_monotone_growth_always_rescales():
    m_trace = jnp.arange(10, dtype=jnp.float32)[:, None] * 5.0
    assert float(rescale_skip_rate(m_trace)) == 0.0
