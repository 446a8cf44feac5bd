"""Pallas TPU kernel: fused causal flash attention (prefill/training fwd).

Grid (B*H, n_q, n_kv); the kv dim is the innermost sequential ("arbitrary")
dim so the online-softmax state (m, l, acc) lives in VMEM scratch across kv
steps and the output block is written once on the last visited kv step.
Causal block-skipping uses pl.when, so out-of-triangle blocks issue no MXU
work — the kernel-level version of the model path's `skip_masked_blocks`.

VMEM per step: q(bq,hd) + k/v(bk,hd) + scores(bq,bk) + acc(bq,hd) — sized
for bq=bk=512, hd<=256 within the ~16 MB v5e VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, logit_softcap: float, window: int,
                  causal: bool, bq: int, bk: int, n_kv: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = True
    if causal:
        needed = ik * bk <= (iq + 1) * bq - 1
    if window:
        needed = jnp.logical_and(
            needed, (ik + 1) * bk - 1 >= iq * bq - window + 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if logit_softcap:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        allow = jnp.ones((bq, bk), bool)
        if causal:
            allow &= qpos >= kpos
        if window:
            allow &= (qpos - kpos) < window
        s = jnp.where(allow, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-37)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           logit_softcap: float = 0.0, scale: float = None,
                           bq: int = 512, bk: int = 512,
                           interpret: bool = False):
    """q/k/v: (B, H, S, hd) -> (B, H, S, hd)."""
    B, H, S, hd = q.shape
    T = k.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    bq, bk = min(bq, S), min(bk, T)
    assert S % bq == 0 and T % bk == 0
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, T, hd)
    vf = v.reshape(B * H, T, hd)
    grid = (B * H, S // bq, T // bk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, logit_softcap=logit_softcap,
        window=window, causal=causal, bq=bq, bk=bk, n_kv=T // bk)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
