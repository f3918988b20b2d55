"""Single-source NVFP4 numerics: parity pins so the Pallas kernels and the
jnp oracle cannot drift (they all import repro.kernels.nvfp4)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.kernels import grouped_fp4_ffn, nvfp4, quantize_fp4


def _sweep_values():
    """Every code point, every midpoint, boundary cases, random fill."""
    grid = np.asarray(quant.FP4_LEVELS)
    mids = np.asarray(quant.FP4_MIDPOINTS)
    eps = np.float32(1e-3)
    pts = np.concatenate([grid, -grid, mids, -mids, mids - eps, mids + eps,
                          [0.0, -0.0, 7.5, -7.5, 1e-9, -1e-9],
                          np.random.RandomState(0).randn(512) * 3])
    pad = (-len(pts)) % 16
    pts = np.concatenate([pts, pts[:pad]])
    return pts.astype(np.float32).reshape(-1, 16)


def test_modules_share_one_implementation():
    """The anti-drift pin: kernels alias nvfp4, they don't re-implement."""
    assert quantize_fp4.quantize_rows is nvfp4.quantize_rows
    assert grouped_fp4_ffn.dequant_rows is nvfp4.dequant_rows
    assert grouped_fp4_ffn.fake_quant_a4 is nvfp4.fake_quant_a4
    assert quant.fp4_round is nvfp4.fp4_round
    assert quant.fp4_code is nvfp4.fp4_code
    assert quant.fp4_decode is nvfp4.decode_level
    assert quant.e4m3_round is nvfp4.e4m3_round


def test_compare_select_matches_level_table():
    """fp4_round / fp4_level vs an explicit FP4_LEVELS gather, bitwise."""
    x = jnp.asarray(_sweep_values())
    idx = nvfp4.fp4_index(jnp.abs(x))
    gathered = jnp.sign(x) * quant.FP4_LEVELS[idx]
    np.testing.assert_array_equal(np.asarray(nvfp4.fp4_round(x)),
                                  np.asarray(gathered))
    np.testing.assert_array_equal(np.asarray(nvfp4.fp4_level(idx)),
                                  np.asarray(quant.FP4_LEVELS[idx]))


def test_code_decode_roundtrip_all_16_codes():
    codes = jnp.arange(16, dtype=jnp.uint8)
    vals = nvfp4.decode_level(codes)
    table = np.asarray(quant.FP4_LEVELS)
    signs = np.where(np.arange(16) >= 8, -1.0, 1.0)
    np.testing.assert_array_equal(np.asarray(vals),
                                  (signs * table[np.arange(16) % 8]
                                   ).astype(np.float32))
    # re-encode returns the same code (modulo ±0 which shares a value)
    re = nvfp4.fp4_code(vals)
    np.testing.assert_array_equal(np.asarray(re)[1:8],
                                  np.asarray(codes)[1:8])
    np.testing.assert_array_equal(np.asarray(re)[9:], np.asarray(codes)[9:])


def test_fake_quant_a4_matches_ref_recipe():
    """fake_quant_a4 == the ref.py a4 recipe: dynamic per-group amax/6
    scale in exact f32, fp4_round on the scaled values."""
    x = jnp.asarray(_sweep_values())
    m, k = x.shape
    got = nvfp4.fake_quant_a4(x, 16)
    xg = x.reshape(m, k // 16, 16)
    gs = jnp.maximum(jnp.max(jnp.abs(xg), -1, keepdims=True) / 6.0, 1e-20)
    want = (quant.fp4_round(xg / gs) * gs).reshape(m, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fake_quant_a4_leading_dims():
    """Arbitrary leading shape (the decode path fake-quants [E,t,F])."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 32))
    y = nvfp4.fake_quant_a4(x, 16)
    y2 = nvfp4.fake_quant_a4(x.reshape(15, 32), 16).reshape(3, 5, 32)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))


def test_e4m3_round_idempotent_on_sweep():
    x = jnp.asarray(_sweep_values()).reshape(-1) * 100.0
    y = nvfp4.e4m3_round(x)
    np.testing.assert_array_equal(np.asarray(nvfp4.e4m3_round(y)),
                                  np.asarray(y))


def test_e4m3_round_matches_log2_formula():
    """The bit-pattern exponent == floor(log2) on values away from binade
    edges, where the float formula is exact."""
    rng = np.random.RandomState(1)
    x = (rng.uniform(1.05, 1.95, 4096) * np.exp2(rng.randint(-12, 10, 4096))
         * np.where(rng.rand(4096) < 0.5, -1, 1)).astype(np.float32)
    mag = np.minimum(np.abs(x), 448.0)
    e = np.clip(np.floor(np.log2(mag)), -6, 8)
    ulp = np.exp2(e - 3)
    want = np.sign(x) * np.minimum(np.round(mag / ulp) * ulp, 448.0)
    np.testing.assert_array_equal(np.asarray(nvfp4.e4m3_round(x)),
                                  want.astype(np.float32))


def test_fake_quant_a4_axis_minus2_is_transpose():
    """Grouping along sublanes (the in-kernel form) == lanes, transposed."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    y = nvfp4.fake_quant_a4(x, 16)
    yt = nvfp4.fake_quant_a4(x.swapaxes(-1, -2), 16, axis=-2)
    np.testing.assert_array_equal(np.asarray(yt.swapaxes(-1, -2)),
                                  np.asarray(y))


def test_pack_rows_pairs_rows_j_and_j_plus_group():
    """The storage format: packed row c·16+j = row 32c+j | row 32c+16+j<<4."""
    rng = np.random.RandomState(2)
    codes = rng.randint(0, 16, (3, 96, 40)).astype(np.int32)
    packed = np.asarray(nvfp4.pack_rows(jnp.asarray(codes)))
    assert packed.dtype == np.uint8 and packed.shape == (3, 48, 40)
    c = codes.reshape(3, 3, 2, 16, 40)
    want = (c[:, :, 0] | (c[:, :, 1] << 4)).reshape(3, 48, 40)
    np.testing.assert_array_equal(packed, want)
    np.testing.assert_array_equal(
        np.asarray(nvfp4.unpack_rows(jnp.asarray(packed))), codes)


def test_quantize_rows_groups_along_k():
    """Scales are per 16 rows of K per column; dequant inverts the pack."""
    w = jax.random.normal(jax.random.PRNGKey(4), (64, 48)) * 0.2
    gs = quant.global_scale_for(w)
    packed, scales = nvfp4.quantize_rows(w, gs)
    assert packed.shape == (32, 48) and scales.shape == (4, 48)
    amax = np.abs(np.asarray(w)).reshape(4, 16, 48).max(1)
    want = np.asarray(nvfp4.e4m3_round(amax * nvfp4.INV_FP4_MAX / gs))
    np.testing.assert_array_equal(np.asarray(scales),
                                  np.maximum(want, 2.0 ** -9))
    dq = np.asarray(nvfp4.dequant_rows(packed, scales, gs))
    codes = np.asarray(nvfp4.fp4_code(
        np.asarray(w).reshape(4, 16, 48)
        / (np.asarray(scales) * np.asarray(gs))[:, None, :])).reshape(64, 48)
    np.testing.assert_array_equal(
        dq, (np.asarray(nvfp4.decode_level(codes)).reshape(4, 16, 48)
             * np.asarray(scales)[:, None, :] * np.asarray(gs)
             ).reshape(64, 48))
