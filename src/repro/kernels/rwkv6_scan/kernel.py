"""Pallas TPU kernel: chunked RWKV6 WKV recurrence.

Grid (B*H, n_chunks): the chunk dim is sequential, carrying the (hd x hd)
state in VMEM scratch.  Per chunk the intra-chunk decayed products
exp(cum_excl[t,d] - cumw[j,d]) are <= 1 (numerically safe), computed as a
(Q, Q, hd) VMEM tensor and reduced over hd on the VPU — the TPU adaptation
of the fla-style kernel (no warp shuffles needed); the MXU takes the
(Q,Q) x (Q,hd) product and the state terms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, logw_ref, u_ref, o_ref, state_ref, *,
                Q: int, hd: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)            # (Q, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    logw = logw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)            # (1, hd)

    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum lowering); HIGHEST keeps it exact in f32
    cumw = jax.lax.dot_general((row >= col).astype(jnp.float32), logw,
                               (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)  # (Q, hd)
    cum_excl = cumw - logw
    # intra-chunk: A[t,j] = sum_d r[t,d] k[j,d] exp(cum_excl[t,d]-cumw[j,d])
    diff = cum_excl[:, None, :] - cumw[None, :, :]            # (Q,Q,hd)
    # strict lower triangle, built in 3-D (Mosaic cannot reshape a mask)
    mask = jax.lax.broadcasted_iota(jnp.int32, (Q, Q, hd), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q, hd), 1)
    E = jnp.exp(jnp.where(mask, diff, -1e9))
    A = jnp.sum(r[:, None, :] * k[None, :, :] * E, axis=-1)   # (Q, Q)
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # bonus diagonal
    y = y + jnp.sum(r * u * k, axis=-1, keepdims=True) * v
    # inter-chunk from carried state
    rd = r * jnp.exp(cum_excl)
    y = y + jax.lax.dot_general(rd, state_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # state update
    last = cumw[Q - 1:Q]                                      # (1, hd)
    dec_end = jnp.exp(last - cumw)                            # (Q, hd)
    state_ref[...] = (state_ref[...] * jnp.exp(last).T
                      + jax.lax.dot_general(
                          (k * dec_end), v, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
    o_ref[0] = y.astype(o_ref.dtype)


def wkv6_pallas(r, k, v, logw, u, *, chunk: int = 32, interpret: bool = False):
    """r/k/v/logw: (B, S, H, hd); u: (H, hd) -> y (B,S,H,hd).

    `u` goes in as (B*H, 1, hd) so its block's last two dims equal the
    array's, as the TPU's (8, 128) tiling rule requires."""
    B, S, H, hd = r.shape
    Q = min(chunk, S)
    assert S % Q == 0
    nC = S // Q
    flat = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    u_bh = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    grid = (B * H, nC)
    kernel = functools.partial(_wkv_kernel, Q=Q, hd=hd)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        name="rwkv6_scan",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, hd), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(flat(r), flat(k), flat(v), flat(logw), u_bh)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
