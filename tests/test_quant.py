"""NVFP4 quantization recipe properties (paper Appendix E).

Property tests run under ``hypothesis`` when it is installed; seeded
plain-pytest subsets call the same check bodies so collection and coverage
never depend on the optional package.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import nvfp4

try:
    import hypothesis
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

FP4_GRID = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0])
GRID_ALL = np.sort(np.concatenate([-FP4_GRID, FP4_GRID]))


def test_fp4_round_onto_grid():
    x = np.linspace(-8, 8, 4001).astype(np.float32)
    y = np.asarray(quant.fp4_round(jnp.asarray(x)))
    assert set(np.unique(np.abs(y))) <= set(FP4_GRID)


def test_fp4_round_nearest():
    x = np.array([0.24, 0.26, 0.74, 0.76, 2.4, 2.6, 4.9, 5.1, 100.0, -1.3])
    y = np.asarray(quant.fp4_round(jnp.asarray(x)))
    expected = np.array([0.0, 0.5, 0.5, 1.0, 2.0, 3.0, 4.0, 6.0, 6.0, -1.5])
    np.testing.assert_array_equal(y, expected)


def test_fp4_code_decode_roundtrip():
    x = np.linspace(-7, 7, 997).astype(np.float32)
    codes = quant.fp4_code(jnp.asarray(x))
    dec = np.asarray(quant.fp4_decode(codes))
    np.testing.assert_array_equal(dec, np.asarray(quant.fp4_round(x)))


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 16, (32, 4)).astype(np.uint8)
    packed = nvfp4.pack_rows(jnp.asarray(codes))
    assert packed.shape == (16, 4)
    np.testing.assert_array_equal(np.asarray(nvfp4.unpack_rows(packed)),
                                  codes)


# -- shared check bodies ----------------------------------------------------
def check_e4m3_idempotent_and_bounded(x):
    y = np.asarray(quant.e4m3_round(jnp.asarray(x)))
    y2 = np.asarray(quant.e4m3_round(jnp.asarray(y)))
    np.testing.assert_array_equal(y, y2)          # representable fixed point
    assert np.all(np.abs(y) <= 448.0)
    # relative error of a normal e4m3 value is <= 2^-4 (+ denormal floor)
    err = np.abs(y - x)
    bound = np.maximum(np.abs(x) * (2 ** -3), 2.0 ** -10 + 1e-12)
    assert np.all(err <= bound + 1e-6)


def check_quantize_roundtrip_error_bound(seed, scale):
    rng = np.random.default_rng(seed)
    w = (rng.normal(0, scale, (64, 4))).astype(np.float32)
    q = quant.quantize_fp4(jnp.asarray(w))
    dq = np.asarray(quant.dequantize_fp4(q))
    wg = w.reshape(4, 16, 4)                      # groups of 16 along K
    amax = np.abs(wg).max(1, keepdims=True)
    err = np.abs(dq.reshape(4, 16, 4) - wg)
    # grid step <= amax/3 around the top; scale rounding <= 6.25% extra
    assert np.all(err <= 0.25 * amax + 1e-7)


# -- hypothesis property tests (optional) -----------------------------------
if HAVE_HYPOTHESIS:
    @hypothesis.given(hnp.arrays(np.float32, (8,),
                                 elements=st.floats(-448, 448, width=32)))
    @hypothesis.settings(deadline=None, max_examples=100)
    def test_e4m3_idempotent_and_bounded(x):
        check_e4m3_idempotent_and_bounded(x)

    @hypothesis.given(st.integers(0, 2 ** 31 - 1), st.floats(1e-3, 10.0))
    @hypothesis.settings(deadline=None, max_examples=40)
    def test_quantize_roundtrip_error_bound(seed, scale):
        check_quantize_roundtrip_error_bound(seed, scale)


# -- plain-pytest subset (always runs) --------------------------------------
@pytest.mark.parametrize("seed", range(10))
def test_e4m3_idempotent_and_bounded_sampled(seed):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-448, 448, 8).astype(np.float32)
    if seed == 0:
        x = np.array([0.0, -0.0, 448.0, -448.0, 1e-6, -1e-6, 2.0, 3.1],
                     np.float32)
    check_e4m3_idempotent_and_bounded(x)


@pytest.mark.parametrize("seed,scale", [(0, 1e-3), (1, 0.05), (2, 1.0),
                                        (3, 10.0), (4, 0.3)])
def test_quantize_roundtrip_error_bound_sampled(seed, scale):
    check_quantize_roundtrip_error_bound(seed, scale)


def test_e4m3_clamps():
    y = np.asarray(quant.e4m3_round(jnp.asarray([1e6, -1e6, 500.0])))
    np.testing.assert_array_equal(y, [448.0, -448.0, 448.0])


def test_fp4_sim_gradient_straight_through():
    import jax
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (4, 32)),
                    jnp.float32)
    g = jax.grad(lambda v: quant.fp4_sim(v).sum())(x)
    np.testing.assert_allclose(np.asarray(g), 1.0)


def test_matmul_w4a4_matches_manual():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1, (8, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.1, (32, 16)), jnp.float32)   # [K,N]
    q = quant.quantize_fp4(w)
    y = quant.matmul_w4a4(x, q)
    xq = quant.fp4_sim(x)
    wq = quant.dequantize_fp4(q)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(xq) @ np.asarray(wq), rtol=2e-5,
                               atol=2e-5)


def test_quant_error_reasonable():
    w = jnp.asarray(np.random.default_rng(0).normal(0, 0.02, (256, 256)),
                    jnp.float32)
    err = float(quant.quant_error(w))
    assert 0.01 < err < 0.2       # fp4 w/ group scales ~ 5-12% on gaussian
