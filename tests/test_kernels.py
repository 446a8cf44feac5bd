"""Per-kernel shape/dtype sweeps: Pallas in interpret mode (these tests run
on the CPU) vs the pure-jnp ref.py oracle (deliverable c).  The compiled
kernels are checked against a described v5e in test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.mamba2_ssd.ops import ssd, ssd_ref, ssd_sequential_ref
from repro.kernels.rwkv6_scan.ops import (wkv6, wkv6_ref,
                                          wkv6_sequential_ref)
from repro.kernels.tiled_matmul.ops import tiled_matmul
from repro.kernels.tiled_matmul.ref import tiled_matmul_ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("K,M,N", [(256, 256, 256), (512, 256, 384),
                                   (384, 128, 512), (128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tiled_matmul(K, M, N, dtype):
    a = jax.random.normal(KEY, (K, M), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N),
                          jnp.float32).astype(dtype)
    out = tiled_matmul(a, b, bm=128, bn=128, bk=128, interpret=True)
    ref = tiled_matmul_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 0.25
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("B,H,S,hd", [(2, 4, 256, 64), (1, 2, 512, 128),
                                      (2, 1, 128, 32)])
@pytest.mark.parametrize("window,cap", [(0, 0.0), (128, 0.0), (0, 50.0)])
def test_flash_attention(B, H, S, hd, window, cap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, hd), jnp.float32)
    out = flash_attention(q, k, v, window=window, logit_softcap=cap,
                          bq=128, bk=128, interpret=True)
    ref = flash_attention_ref(q, k, v, window=(window or None),
                              logit_softcap=cap)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


@pytest.mark.parametrize("B,S,H,hd", [(2, 128, 4, 32), (1, 64, 2, 64),
                                      (1, 96, 1, 32)])
@pytest.mark.parametrize("chunk", [16, 32])
def test_wkv6(B, S, H, hd, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, hd))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) * 0.5 - 1.0)
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    y_seq, _ = wkv6_sequential_ref(r, k, v, logw, u)
    y_chk, _ = wkv6_ref(r, k, v, logw, u, chunk=chunk)
    y_pal = wkv6(r, k, v, logw, u, chunk=chunk, interpret=True)
    assert float(jnp.max(jnp.abs(y_seq - y_chk))) < 1e-3
    assert float(jnp.max(jnp.abs(y_seq - y_pal))) < 1e-3


@pytest.mark.parametrize("B,S,H,hd,N", [(2, 128, 8, 16, 16),
                                        (1, 64, 4, 32, 8)])
@pytest.mark.parametrize("chunk", [16, 32])
def test_mamba2_ssd(B, S, H, hd, N, chunk):
    ks = jax.random.split(KEY, 4)
    xdt = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Bc = jax.random.normal(ks[2], (B, S, 1, N)) * 0.5
    Cc = jax.random.normal(ks[3], (B, S, 1, N)) * 0.5
    y_seq, _ = ssd_sequential_ref(xdt, dA, Bc, Cc)
    y_chk, _ = ssd_ref(xdt, dA, Bc, Cc, chunk=chunk)
    y_pal = ssd(xdt, dA, Bc, Cc, chunk=chunk, interpret=True)
    assert float(jnp.max(jnp.abs(y_seq - y_chk))) < 1e-3
    assert float(jnp.max(jnp.abs(y_seq - y_pal))) < 1e-3


def test_mamba2_ssd_refuses_grouped_bc():
    """n_groups > 1 raises instead of quietly running the reference."""
    xdt = jnp.zeros((1, 32, 4, 16))
    dA = jnp.zeros((1, 32, 4))
    bc = jnp.zeros((1, 32, 2, 8))
    with pytest.raises(NotImplementedError, match="n_groups"):
        ssd(xdt, dA, bc, bc, chunk=16, interpret=True)


def test_flash_kernel_matches_model_blockwise():
    """Kernel, oracle, and the model's blockwise path agree."""
    from repro.models.attention import flash_attention as model_blockwise
    B, H, S, hd = 2, 2, 256, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
    blockwise = model_blockwise(q, k, v, causal=True, scale=hd ** -0.5,
                                q_block=64, kv_block=64)
    kern = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), bq=64, bk=64,
                           interpret=True)
    assert float(jnp.max(jnp.abs(blockwise.transpose(0, 2, 1, 3)
                                 - kern))) < 2e-5
