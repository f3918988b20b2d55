"""Per-kernel correctness: Pallas (interpret mode) vs the jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ReaLBConfig
from repro.core import ep_moe, quant
from repro.kernels import nvfp4, ops

SHAPES = [(128, 256, 512), (64, 128, 128), (256, 384, 1024), (8, 128, 64)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _check_quantize_kernel(g, n, k, dtype, seed):
    """Pallas quantize of a ``[G, K, N]`` stack == quant.quantize_fp4."""
    w = (jax.random.normal(jax.random.PRNGKey(seed), (g, k, n))
         * 0.07).astype(dtype)
    q = ops.quantize_experts_fp4(w, interpret=True)
    assert q.packed.shape == (g, k // 2, n)
    assert q.scales.shape == (g, k // 16, n)
    q_ref = quant.quantize_fp4(w)
    np.testing.assert_array_equal(np.asarray(q.packed),
                                  np.asarray(q_ref.packed))
    np.testing.assert_array_equal(np.asarray(q.scales),
                                  np.asarray(q_ref.scales))


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_kernel_matches_oracle(m, n, k, dtype):
    _check_quantize_kernel(2, n, k, dtype, m * 7 + n * 3 + k)


def test_kernel_matches_ep_moe_sim_numerics():
    """The grouped kernel on one slot == the quant oracle composed by hand
    (same QTensor → same dequant → same matmul semantics)."""
    m, d, f = 32, 128, 128
    wq = _quantized_experts(jax.random.PRNGKey(4), 1, d, f)
    xs = jax.random.normal(jax.random.PRNGKey(5), (m, d))
    y = ops.grouped_fp4_ffn(xs, jnp.asarray([m], jnp.int32), wq,
                            interpret=True)
    dq = {n: quant.dequantize_fp4(q)[0] for n, q in wq.items()}
    xq = nvfp4.fake_quant_a4(xs)
    h = jax.nn.silu(xq @ dq["w_gate"]) * (xq @ dq["w_up"])
    y_sim = nvfp4.fake_quant_a4(h) @ dq["w_down"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_sim),
                               rtol=1e-5, atol=1e-4)


# --------------------------------------------------------------------------
# odd shapes: N is arbitrary, K any multiple of 2·group
# --------------------------------------------------------------------------
ODD_SHAPES = [(37, 130, 96), (5, 17, 64), (100, 200, 544), (1, 1, 32)]


@pytest.mark.parametrize("m,n,k", ODD_SHAPES)
def test_quantize_kernel_odd_shapes(m, n, k):
    """Arbitrary d_ff: no caller-side padding."""
    _check_quantize_kernel(1, n, k, jnp.float32, n * k)


# --------------------------------------------------------------------------
# fused grouped FP4 expert FFN vs the _grouped_ffn_fp4 jnp oracle
# --------------------------------------------------------------------------
def _quantized_experts(rng_key, n_groups, d, f, dtype=jnp.float32):
    """QTensors in the exact layout the EP layer's FP4 branch produces: gate/up
    ``[G, D, F]`` quantized along D, down ``[G, F, D]`` along d_ff.
    Each weight is drawn output-axis-first and transposed."""
    keys = jax.random.split(rng_key, 3)
    out = {}
    for key, (name, (rows, cols)) in zip(
            keys, dict(w_gate=(f, d), w_up=(f, d), w_down=(d, f)).items()):
        w = (jax.random.normal(key, (n_groups, rows, cols)) * 0.5)
        out[name] = quant.quantize_fp4(w.swapaxes(-1, -2).astype(dtype))
    return out


def _oracle_grouped_ffn_fp4(xs, gs, wq, rcfg, act):
    """_grouped_ffn_fp4 with the backend pinned to the jnp oracle."""
    prev = ops.ffn_backend()
    ops.set_ffn_backend("jnp")
    try:
        return ep_moe._grouped_ffn_fp4(xs, gs, wq, rcfg, act)
    finally:
        ops.set_ffn_backend(prev if prev != "jnp" else None)


GROUPED_CASES = [
    # (m, d, f, gs) — sum(gs) == m; patterns from the dispatch path:
    # empty groups interleaved + zero-count pad slot (the trailing slot
    # every _moe_dispatch call appends for capacity-dropped rows)
    (24, 64, 64, [3, 0, 5, 0, 0, 9, 7, 0, 0]),
    # all tokens land in one slot (worst-case hotspot)
    (16, 64, 96, [0, 16, 0, 0, 0]),
    # first slot only, trailing slots (incl. pad) empty
    (40, 128, 64, [40, 0, 0]),
    # m not a multiple of block_m (pad-to-block inside the kernel)
    (37, 64, 64, [10, 0, 12, 15]),
    # cap-dropped rows: pad slot (last) holds unfilled capacity rows
    (32, 64, 64, [6, 10, 0, 16]),
    (8, 32, 32, [1, 2, 0, 5]),
]


@pytest.mark.parametrize("m,d,f,gs", GROUPED_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_grouped_ffn_kernel_matches_oracle(m, d, f, gs, dtype):
    gs = jnp.asarray(gs, jnp.int32)
    assert int(gs.sum()) == m
    rcfg = ReaLBConfig()
    wq = _quantized_experts(jax.random.PRNGKey(m + d + f), gs.shape[0],
                            d, f, dtype)
    xs = jax.random.normal(jax.random.PRNGKey(m * 3 + 1), (m, d)).astype(
        dtype)
    y_ref = _oracle_grouped_ffn_fp4(xs, gs, wq, rcfg, jax.nn.silu)
    y = ops.grouped_fp4_ffn(xs, gs, wq, group=rcfg.group_size,
                            act=jax.nn.silu, interpret=True)
    assert y.shape == y_ref.shape and y.dtype == y_ref.dtype
    ya = np.asarray(y, jnp.float32)
    ra = np.asarray(y_ref, jnp.float32)
    # both keep gate/up/h in f32 up to the a4 of h and round the same
    # values to bf16, so bf16 agrees as tightly as f32
    np.testing.assert_allclose(ya, ra, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,d,f,gs", [
    (40, 128, 384, [0, 25, 0, 15, 0]),   # three 128-wide F blocks
    (24, 64, 256, [7, 0, 17]),           # one 256-wide F block
])
def test_grouped_ffn_kernel_lane_aligned_f_blocks(m, d, f, gs):
    """d_ff split into lane-aligned blocks, as at real widths.  The kernel
    sums the down projection block by block, the oracle in one dot, so f32
    results agree to rounding relative to the output's peak."""
    gs = jnp.asarray(gs, jnp.int32)
    wq = _quantized_experts(jax.random.PRNGKey(m + d + f), gs.shape[0], d, f)
    xs = jax.random.normal(jax.random.PRNGKey(m * 3 + 1), (m, d))
    ra = np.asarray(_oracle_grouped_ffn_fp4(xs, gs, wq, ReaLBConfig(),
                                            jax.nn.silu))
    ya = np.asarray(ops.grouped_fp4_ffn(xs, gs, wq, interpret=True))
    assert np.abs(ya - ra).max() <= 1e-5 * np.abs(ra).max()


def test_grouped_ffn_kernel_block_m_invariance():
    """Token-block size must not change results (same per-row math)."""
    m, d, f = 48, 64, 64
    gs = jnp.asarray([11, 0, 20, 17], jnp.int32)
    wq = _quantized_experts(jax.random.PRNGKey(9), 4, d, f)
    xs = jax.random.normal(jax.random.PRNGKey(10), (m, d))
    ys = [ops.grouped_fp4_ffn(xs, gs, wq, interpret=True)]
    from repro.kernels.grouped_fp4_ffn import grouped_fp4_ffn_kernel
    gsc = jnp.stack([wq[n].global_scale for n in ("w_gate", "w_up",
                                                  "w_down")])
    for bm in (8, 16, 128):
        ys.append(grouped_fp4_ffn_kernel(
            xs, gs, wq["w_gate"].packed, wq["w_gate"].scales,
            wq["w_up"].packed, wq["w_up"].scales,
            wq["w_down"].packed, wq["w_down"].scales, gsc,
            block_m=bm, interpret=True))
    for y in ys[1:]:
        np.testing.assert_allclose(np.asarray(y), np.asarray(ys[0]),
                                   rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("pad", [0, 1])
def test_quantize_experts_fp4_bitwise_matches_jnp(pad):
    """The grouped Pallas quantize path == quant.quantize_fp4 exactly
    (same global scale over the stack, same per-group recipe); with
    ``pad`` the trailing experts repeat the first."""
    w = jax.random.normal(jax.random.PRNGKey(2), (5, 96, 48)) * 0.3
    q_ref = quant.quantize_fp4(jnp.concatenate([w, w[:pad]], axis=0))
    q_k = ops.quantize_experts_fp4(w, pad=pad, interpret=True)
    np.testing.assert_array_equal(np.asarray(q_k.packed),
                                  np.asarray(q_ref.packed))
    np.testing.assert_array_equal(np.asarray(q_k.scales),
                                  np.asarray(q_ref.scales))
    np.testing.assert_array_equal(np.asarray(q_k.global_scale),
                                  np.asarray(q_ref.global_scale))


def test_ffn_backend_switch_roundtrip():
    assert ops.ffn_backend() in ops.FFN_BACKENDS
    prev = ops.ffn_backend()
    try:
        assert ops.set_ffn_backend("interpret") == "interpret"
        assert ops.ffn_fused()
        assert ops.set_ffn_backend("jnp") == "jnp"
        assert not ops.ffn_fused()
        with pytest.raises(ValueError):
            ops.set_ffn_backend("cuda")
    finally:
        ops.set_ffn_backend(prev)
