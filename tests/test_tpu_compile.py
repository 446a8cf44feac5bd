"""The five Pallas kernels compile for a TPU v5e at real widths.

The chip is described, not attached (`jax.experimental.topologies`): the
TPU compiler that ships with libtpu refuses here what the chip would
refuse — unaligned blocks, ops Mosaic cannot lower, too much VMEM — which
the interpret-mode tests in test_kernels.py cannot see.  Nothing runs, so
results are checked there, not here.

The topology is described inside a fixture and never at import: only one
process at a time may load libtpu, and every pytest-xdist worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.mamba2_ssd.kernel import ssd_pallas
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.rwkv6_scan.kernel import wkv6_pallas
from repro.kernels.tiled_matmul.kernel import tiled_matmul_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the program"
    return compiled


def test_tiled_matmul_compiles(one_chip, no_persistent_cache):
    """The paper's METG task: single-precision A^T B at 4096^2."""
    f32 = jnp.float32
    _compile(tiled_matmul_pallas, one_chip, ((4096, 4096), f32),
             ((4096, 4096), f32))


@pytest.mark.parametrize("H,hd", [(12, 128), (32, 80)])
def test_flash_attention_compiles(one_chip, no_persistent_cache, H, hd):
    """qwen2-vl-2b (hd 128) and zamba2-2.7b (hd 80) heads at S 2048."""
    shape = ((1, H, 2048, hd), jnp.bfloat16)
    _compile(flash_attention_pallas, one_chip, shape, shape, shape)


def test_rwkv6_scan_compiles(one_chip, no_persistent_cache):
    """rwkv6-1.6b: 32 heads of 64, chunk 64."""
    seq = ((1, 2048, 32, 64), jnp.float32)
    _compile(lambda r, k, v, w, u: wkv6_pallas(r, k, v, w, u, chunk=64),
             one_chip, seq, seq, seq, seq, ((32, 64), jnp.float32))


def test_mamba2_ssd_compiles(one_chip, no_persistent_cache):
    """zamba2-2.7b's Mamba2 layers: 80 heads of 64, state 64, chunk 64."""
    f32 = jnp.float32
    _compile(lambda x, a, b, c: ssd_pallas(x, a, b, c, chunk=64), one_chip,
             ((1, 2048, 80, 64), f32), ((1, 2048, 80), f32),
             ((1, 2048, 1, 64), f32), ((1, 2048, 1, 64), f32))


@pytest.mark.parametrize("rows", [6, 12288])
def test_moe_gmm_compiles(one_chip, no_persistent_cache, rows):
    """deepseek-v2-lite's experts held on one chip: 16 of D 2048, F 1408,
    for a decode step's 6 routed rows and a 2048-token prefill's 12,288."""
    bf16 = jnp.bfloat16
    _compile(lambda x, a, b, c, g: moe_gmm(x, a, b, c, g), one_chip,
             ((rows, 2048), bf16), ((16, 2048, 1408), bf16),
             ((16, 2048, 1408), bf16), ((16, 1408, 2048), bf16),
             ((16,), jnp.int32))
