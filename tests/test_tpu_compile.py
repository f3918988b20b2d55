"""Compile the serving path's Pallas kernels for one described TPU v5e at
Moonlight widths (d_model 2048, 64 experts of width 1408), no chip needed.

The TPU compiler refuses what the interpreter-mode tests cannot see:
blocks not aligned to the (8, 128) tiling, lane-splitting reshapes, and
more VMEM than a kernel may use.  Shapes are in the storage layout of
``repro.kernels.nvfp4``: contraction axis second-to-last.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.grouped_fp4_ffn import grouped_fp4_ffn_kernel
from repro.kernels.quantize_fp4 import quantize_fp4_kernel

G, D, F, M = 64, 2048, 1408, 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one, so keep them out of any persistent cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("pad", [0, 1])
@pytest.mark.parametrize("name,k,n", [("gate_up", D, F), ("down", F, D)])
def test_quantize_kernel_compiles_for_v5e(one_chip, name, k, n, pad):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    text = _compiled_text(lambda w, s: quantize_fp4_kernel(w, s, pad=pad),
                          spec((G, k, n), jnp.bfloat16),
                          spec((), jnp.float32))
    assert "tpu_custom_call" in text


def test_grouped_ffn_kernel_compiles_for_v5e(one_chip):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    text = _compiled_text(
        lambda *a: grouped_fp4_ffn_kernel(*a),
        spec((M, D), jnp.bfloat16), spec((G,), jnp.int32),
        spec((G, D // 2, F), jnp.uint8), spec((G, D // 16, F), jnp.float32),
        spec((G, D // 2, F), jnp.uint8), spec((G, D // 16, F), jnp.float32),
        spec((G, F // 2, D), jnp.uint8), spec((G, F // 16, D), jnp.float32),
        spec((3,), jnp.float32))
    assert "tpu_custom_call" in text
