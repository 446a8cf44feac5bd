"""Multi-head Latent Attention (DeepSeek-V2): low-rank compressed KV cache.

Cache per token is (kv_lora_rank + qk_rope_head_dim) floats — ~9x smaller
than full GQA KV.  Decode supports two paths:
  * naive   — decompress the whole cache to K/V each step (baseline)
  * absorb  — fold W_uk into the query and W_uv into the output so attention
              runs directly against the compressed cache (§Perf hillclimb)

The rotary part of q and k is laid out as DeepSeek-V2 publishes it: its
rope dims come in interleaved pairs (2i, 2i+1), which are de-interleaved
(evens, then odds) before the half-split rotation.  With YaRN
(`cfg.yarn`) the softmax scale is (dn+dr)^-0.5 times mscale_all_dim's
term squared, in prefill and in both decode paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import flash_attention
from repro.models.common import dense_init, ones_init, rms_norm, shard_hint
from repro.models.rope import apply_rope, yarn_softmax_factor


def init_mla(key, cfg, n_layers: int):
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    dn, dr, dv, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.kv_lora_rank
    ks = jax.random.split(key, 5)
    L = (n_layers,) if n_layers else ()
    return {
        "wq": dense_init(ks[0], L + (D, H * (dn + dr)), in_axis_size=D),
        "wdkv": dense_init(ks[1], L + (D, r + dr), in_axis_size=D),
        "kv_norm": ones_init(None, L + (r,)),
        "wuk": dense_init(ks[2], L + (r, H * dn), in_axis_size=r),
        "wuv": dense_init(ks[3], L + (r, H * dv), in_axis_size=r),
        "wo": dense_init(ks[4], L + (H * dv, D), in_axis_size=H * dv),
    }


def _deinterleave(x):
    """(..., d) with rope pairs (2i, 2i+1) -> (..., d) evens then odds."""
    d = x.shape[-1]
    return x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2) \
        .reshape(x.shape)


def _rope(x, sin, cos):
    return apply_rope(_deinterleave(x), sin, cos)


def softmax_scale(cfg) -> float:
    m = cfg.mla
    return (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5 \
        * yarn_softmax_factor(cfg.yarn)


def _project_q(p, x, cfg, sin, cos):
    m = cfg.mla
    H, dn, dr = cfg.n_heads, m.qk_nope_head_dim, m.qk_rope_head_dim
    B, S, _ = x.shape
    q = shard_hint(x @ p["wq"].astype(x.dtype), "batch", None, "model_ff")
    q = q.reshape(B, S, H, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    qr = _rope(qr, sin, cos)
    return qn, qr


def _compress_kv(p, x, cfg, sin, cos):
    m = cfg.mla
    r, dr = m.kv_lora_rank, m.qk_rope_head_dim
    ckv_full = x @ p["wdkv"].astype(x.dtype)          # (B,S,r+dr)
    ckv = rms_norm(ckv_full[..., :r], p["kv_norm"], cfg.norm_eps)
    krope = _rope(ckv_full[..., None, r:], sin, cos)[:, :, 0]  # (B,S,dr)
    return ckv, krope


def mla_forward(p, x, cfg, sin, cos, *, q_block=1024, kv_block=1024,
                skip_masked_blocks=False, return_cache=False,
                probs_bf16=False):
    """Training / prefill: full-sequence causal MLA."""
    with jax.named_scope("mla"):
        return _mla_forward(p, x, cfg, sin, cos, q_block=q_block,
                            kv_block=kv_block,
                            skip_masked_blocks=skip_masked_blocks,
                            return_cache=return_cache, probs_bf16=probs_bf16)


def _mla_forward(p, x, cfg, sin, cos, *, q_block, kv_block,
                 skip_masked_blocks, return_cache, probs_bf16):
    m = cfg.mla
    H, dn, dr, dv = cfg.n_heads, m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B, S, _ = x.shape
    qn, qr = _project_q(p, x, cfg, sin, cos)
    ckv, krope = _compress_kv(p, x, cfg, sin, cos)
    kn = shard_hint(ckv @ p["wuk"].astype(x.dtype), "batch", None, "model_ff")
    v = shard_hint(ckv @ p["wuv"].astype(x.dtype), "batch", None, "model_ff")
    kn = kn.reshape(B, S, H, dn)
    v = v.reshape(B, S, H, dv)
    q = jnp.concatenate([qn, qr], axis=-1)
    k = jnp.concatenate([kn, jnp.broadcast_to(krope[:, :, None, :],
                                              (B, S, H, dr))], axis=-1)
    scale = softmax_scale(cfg)
    ctx = flash_attention(q, k, v, causal=True, scale=scale, q_block=q_block,
                          kv_block=kv_block, skip_masked_blocks=skip_masked_blocks,
                          probs_bf16=probs_bf16)
    out = ctx.reshape(B, S, H * dv) @ p["wo"].astype(x.dtype)
    out = shard_hint(out, "batch", None, None)
    if return_cache:
        return out, (ckv, krope)
    return out


def mla_decode(p, x, cfg, sin, cos, cache, positions, *, absorb: bool = False):
    """One decode step. x: (B,1,D). cache: (ckv (B,T,r), krope (B,T,dr)).

    Returns (out (B,1,D), new_cache).
    """
    with jax.named_scope("mla"):
        return _mla_decode(p, x, cfg, sin, cos, cache, positions, absorb)


def _mla_decode(p, x, cfg, sin, cos, cache, positions, absorb):
    m = cfg.mla
    H, dn, dr, dv, r = (cfg.n_heads, m.qk_nope_head_dim, m.qk_rope_head_dim,
                        m.v_head_dim, m.kv_lora_rank)
    B = x.shape[0]
    ckv_c, krope_c = cache
    T = ckv_c.shape[1]
    qn, qr = _project_q(p, x, cfg, sin, cos)              # (B,1,H,dn/dr)
    ckv_new, krope_new = _compress_kv(p, x, cfg, sin, cos)
    # write into cache at `positions`
    upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, 0))
    ckv_c = upd(ckv_c, ckv_new.astype(ckv_c.dtype), positions)
    krope_c = upd(krope_c, krope_new.astype(krope_c.dtype), positions)

    kpos = jnp.arange(T)
    allow = kpos[None, :] <= positions[:, None]           # (B,T)
    scale = softmax_scale(cfg)

    if absorb:
        wuk = p["wuk"].astype(x.dtype).reshape(r, H, dn)
        # fold W_uk into q: scores_nope = (q_abs · ckv)
        q_abs = jnp.einsum("bshd,rhd->bshr", qn, wuk)     # (B,1,H,r)
        s = (jnp.einsum("bshr,btr->bhst", q_abs.astype(jnp.float32),
                        ckv_c.astype(jnp.float32))
             + jnp.einsum("bshd,btd->bhst", qr.astype(jnp.float32),
                          krope_c.astype(jnp.float32))) * scale
        s = jnp.where(allow[:, None, None, :], s, -2.0e38)
        prob = jax.nn.softmax(s, axis=-1)
        ctx_r = jnp.einsum("bhst,btr->bshr", prob, ckv_c.astype(jnp.float32))
        wuv = p["wuv"].astype(x.dtype).reshape(r, H, dv)
        ctx = jnp.einsum("bshr,rhd->bshd", ctx_r.astype(x.dtype), wuv)
    else:
        kn = (ckv_c.astype(x.dtype) @ p["wuk"].astype(x.dtype)).reshape(B, T, H, dn)
        vv = (ckv_c.astype(x.dtype) @ p["wuv"].astype(x.dtype)).reshape(B, T, H, dv)
        q = jnp.concatenate([qn, qr], axis=-1)
        k = jnp.concatenate([kn, jnp.broadcast_to(krope_c.astype(x.dtype)[:, :, None, :],
                                                  (B, T, H, dr))], axis=-1)
        s = jnp.einsum("bshe,bthe->bhst", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        s = jnp.where(allow[:, None, None, :], s, -2.0e38)
        prob = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhst,bthd->bshd", prob, vv.astype(jnp.float32)).astype(x.dtype)

    out = ctx.reshape(B, 1, H * dv) @ p["wo"].astype(x.dtype)
    return out, (ckv_c, krope_c)


def init_mla_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, n_layers=None):
    m = cfg.mla
    L = (n_layers,) if n_layers else ()
    return (jnp.zeros(L + (batch, max_len, m.kv_lora_rank), dtype),
            jnp.zeros(L + (batch, max_len, m.qk_rope_head_dim), dtype))


def mla_cache_specs(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, n_layers=None):
    m = cfg.mla
    L = (n_layers,) if n_layers else ()
    sds = jax.ShapeDtypeStruct
    return (sds(L + (batch, max_len, m.kv_lora_rank), dtype),
            sds(L + (batch, max_len, m.qk_rope_head_dim), dtype))
