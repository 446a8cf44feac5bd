"""Pallas TPU kernel: chunked Mamba2 SSD scan (n_groups == 1 only).

Grid (B, H, n_chunks): one head per program, the chunk dim sequential,
carrying that head's (hd, N) state in VMEM scratch.  Every operand inside
the kernel is 2-D, so each step is a handful of MXU matmuls: the (Q,Q)
C·B scores, the decay-masked (Q,Q) x (Q,hd) intra-chunk product, and the
state terms.  The chunk's prefix sum of dA is a lower-triangular matmul
(Mosaic has no cumsum lowering).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, contract=((1,), (0,)), precision=None):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(xdt_ref, dA_ref, b_ref, c_ref, o_ref, state_ref, *, Q: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = xdt_ref[0, 0].astype(jnp.float32)        # (Q, hd)
    dA = dA_ref[0, 0].astype(jnp.float32)        # (Q, 1)
    Bc = b_ref[0].astype(jnp.float32)            # (Q, N)   (G == 1)
    Cc = c_ref[0].astype(jnp.float32)            # (Q, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = row >= col
    tri = causal.astype(jnp.float32)
    # inclusive prefix sums of dA, as a column and as a row; HIGHEST keeps
    # them exact in f32
    cum_c = _dot(tri, dA, precision=_HI)                            # (Q, 1)
    cum_r = _dot(dA, tri, ((0,), (1,)), precision=_HI)              # (1, Q)
    L = jnp.exp(jnp.where(causal, cum_c - cum_r, -1e9))             # (Q, Q)
    scores = _dot(Cc, Bc, ((1,), (1,)))                             # (Q, Q)
    Y = _dot(L * scores, x)                                         # (Q, hd)
    # inter-chunk: the carried state, decayed to each position
    Y = Y + _dot(Cc, state_ref[...], ((1,), (1,))) * jnp.exp(cum_c)
    # state update.  The chunk's total decay is taken as a column of the
    # height it multiplies (Mosaic cannot broadcast a (1, 1) block in
    # both sublanes and lanes)
    hd = x.shape[1]
    tot_q = _dot(jnp.ones((Q, Q), jnp.float32), dA, precision=_HI)  # (Q, 1)
    tot_h = _dot(jnp.ones((hd, Q), jnp.float32), dA, precision=_HI)  # (hd, 1)
    dec_end = jnp.exp(tot_q - cum_c)                                # (Q, 1)
    state_ref[...] = (state_ref[...] * jnp.exp(tot_h)
                      + _dot(x * dec_end, Bc, ((0,), (0,))))        # (hd, N)
    o_ref[0, 0] = Y.astype(o_ref.dtype)


def ssd_pallas(xdt, dA, B_, C_, *, chunk: int = 64, interpret: bool = False):
    """xdt (B,S,H,hd); dA (B,S,H); B_/C_ (B,S,1,N) -> Y (B,S,H,hd)."""
    Bb, S, H, hd = xdt.shape
    G, N = B_.shape[2], B_.shape[3]
    if G != 1:
        raise NotImplementedError(
            f"ssd_pallas supports n_groups == 1, got {G}; "
            "grouped B/C go through ssd_ref")
    Q = min(chunk, S)
    assert S % Q == 0
    grid = (Bb, H, S // Q)
    kernel = functools.partial(_ssd_kernel, Q=Q)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        name="mamba2_ssd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, hd), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, H, S, hd), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(xdt.transpose(0, 2, 1, 3), dA.transpose(0, 2, 1)[..., None],
      B_[:, :, 0], C_[:, :, 0])
    return out.transpose(0, 2, 1, 3)
