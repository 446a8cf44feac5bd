"""Pallas TPU kernel: C = A^T B with MXU-aligned VMEM tiling.

The paper benchmarks schedulers with tiled single-precision A^T B (wave-
function overlap building block).  TPU adaptation: (bm, bn, bk) blocks are
multiples of 128 to fill the 128x128 MXU; A and B tiles stream HBM->VMEM
along the contraction grid dim with an fp32 VMEM accumulator, written out
on the last k-step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, c_ref, acc_ref, *, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # A tile is (bk, bm): contract over the leading (k) dim => A^T @ B.
    # f32 operands ask for full f32 products (the paper's single-precision
    # task), not the MXU's one bf16 pass
    precision = (jax.lax.Precision.HIGHEST if a_ref.dtype == jnp.float32
                 else None)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((0,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _flush():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


def tiled_matmul_pallas(a, b, *, bm: int = 256, bn: int = 256, bk: int = 256,
                        interpret: bool = False):
    """a: (K, M), b: (K, N) -> C (M, N)."""
    K, M = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    kernel = functools.partial(_matmul_kernel, n_k=n_k)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        name="tiled_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(a, b)
