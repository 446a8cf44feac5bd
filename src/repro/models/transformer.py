"""Decoder-only transformer LM covering the dense / moe / mla / vlm families:
qwen2.5-32b, deepseek-67b, deepseek-7b, gemma2-2b (local+global, softcaps),
qwen2-vl-2b (M-RoPE), deepseek-v2-lite (MLA+MoE), arctic-480b (MoE+dense
residual).

Layers are stacked on a leading (L, ...) dim and executed with lax.scan.
MoE configs with `first_dense_layers` keep those leading layers unstacked.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.common import (Options, activation, dense_init, embed_init,
                                 maybe_remat, ones_init, rms_norm, shard_hint,
                                 softcap)
from repro.models.rope import apply_rope, mrope_angles, rope_angles

# The leaves every step reads only as `.astype(<compute dtype>)` (the
# `moe_gmm` kernel casts its expert blocks to its rows' dtype), so a
# served model may hold them in bfloat16 (`Model.serving_weights`).  The
# norm scales (`ln1`, `ln2`, `pn1`, `pn2`, `final_norm`, `kv_norm`) and the
# MoE `router` are read in float32 and stay out.
SERVED_BF16 = frozenset({"embed", "head", "wq", "wk", "wv", "wo", "bq", "bk",
                         "bv", "w1", "w2", "w3", "ws1", "ws2", "ws3", "wdkv",
                         "wuk", "wuv"})

# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def init_ffn(key, cfg, n_layers: int, d_ff: Optional[int] = None):
    D, F = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    L = (n_layers,) if n_layers else ()
    p = {"w1": dense_init(ks[0], L + (D, F), in_axis_size=D),
         "w2": dense_init(ks[2], L + (F, D), in_axis_size=F)}
    if cfg.gated_mlp:
        p["w3"] = dense_init(ks[1], L + (D, F), in_axis_size=D)
    return p


def apply_ffn(p, x, cfg):
    act = activation(cfg.act)
    h = x @ p["w1"].astype(x.dtype)
    if "w3" in p:
        h = act(h) * (x @ p["w3"].astype(x.dtype))
    else:
        h = act(h)
    h = shard_hint(h, "batch", None, "model_ff")
    return shard_hint(h @ p["w2"].astype(x.dtype), "batch", None, None)


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------


def init_block(key, cfg, n_layers: int, *, use_moe: bool, d_ff: Optional[int] = None):
    ks = jax.random.split(key, 3)
    L = (n_layers,) if n_layers else ()
    p = {"ln1": ones_init(None, L + (cfg.d_model,)),
         "ln2": ones_init(None, L + (cfg.d_model,))}
    if cfg.rms_plus_one:          # gemma zero-centered scales
        p["ln1"] = p["ln1"] * 0.0
        p["ln2"] = p["ln2"] * 0.0
    if cfg.post_norms:            # independent buffers (donation-safe)
        p["pn1"] = jnp.array(p["ln1"])
        p["pn2"] = jnp.array(p["ln2"])
    if cfg.mla is not None:
        p["attn"] = mla_mod.init_mla(ks[0], cfg, n_layers)
    else:
        p["attn"] = attn.init_attention(ks[0], cfg, n_layers)
    if use_moe:
        p["mlp"] = moe_mod.init_moe(ks[1], cfg, n_layers)
    else:
        p["mlp"] = init_ffn(ks[1], cfg, n_layers, d_ff)
    return p


def _norm(x, scale, cfg):
    return rms_norm(x, scale, cfg.norm_eps, plus_one=cfg.rms_plus_one)


def _attn_scale(cfg) -> float:
    if cfg.query_pre_attn_scalar:
        return cfg.query_pre_attn_scalar ** -0.5
    return cfg.resolved_head_dim ** -0.5


def apply_block(bp, x, cfg, sin, cos, *, opts: Options, window=None,
                mode: str = "train", cache=None, positions=None,
                experts=None, layer=None):
    """One transformer block.

    mode: train | prefill | decode.  Serving (prefill, decode) runs an
    MoE layer dropless (`moe.apply_moe_dropless`), training with capacity;
    a scanned MoE layer's expert weights come as the whole stack
    (`experts`) and its index (`layer`), for the kernel to read in place.
    cache: (k, v) (B,T,Hkv,hd) or MLA (ckv, krope) — required for decode.
    Returns (x, cache_out, aux, counts) where cache_out is the new/filled
    cache entry (prefill/decode) or None (train), and counts the serving
    MoE layer's `moe.N_COUNTS` counts (None for any other layer).
    """
    aux = jnp.zeros((), jnp.float32)
    h = _norm(x, bp["ln1"], cfg)
    cache_out = None

    if cfg.mla is not None:
        if mode == "decode":
            a_out, cache_out = mla_mod.mla_decode(
                bp["attn"], h, cfg, sin, cos, cache, positions,
                absorb=opts.mla_absorb)
        else:
            a_out, kv = mla_mod.mla_forward(
                bp["attn"], h, cfg, sin, cos, q_block=opts.q_block,
                kv_block=opts.kv_block,
                skip_masked_blocks=opts.skip_masked_blocks, return_cache=True,
                probs_bf16=opts.probs_bf16)
            if mode == "prefill":
                cache_out = kv
    else:
        if mode == "decode":
            q, k_new, v_new = attn.project_qkv(bp["attn"], h, cfg)
            q = apply_rope(q, sin, cos)
            k_new = apply_rope(k_new, sin, cos)
            k_c, v_c = cache
            upd = jax.vmap(
                lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, 0))
            k_c = upd(k_c, k_new.astype(k_c.dtype), positions)
            v_c = upd(v_c, v_new.astype(v_c.dtype), positions)
            ctx = attn.decode_attention(
                q, k_c.astype(q.dtype), v_c.astype(q.dtype), positions,
                window=window, logit_softcap=cfg.attn_logit_softcap,
                scale=_attn_scale(cfg))
            a_out = attn.project_out(bp["attn"], ctx, cfg)
            cache_out = (k_c, v_c)
        else:
            q, k, v = attn.project_qkv(bp["attn"], h, cfg)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
            hq_pad = q.shape[2]
            ctx = attn.flash_attention(
                q, attn.expand_kv(k, hq_pad), attn.expand_kv(v, hq_pad),
                causal=True, window=window,
                logit_softcap=cfg.attn_logit_softcap, scale=_attn_scale(cfg),
                q_block=opts.q_block, kv_block=opts.kv_block,
                skip_masked_blocks=opts.skip_masked_blocks,
                probs_bf16=opts.probs_bf16)
            a_out = attn.project_out(bp["attn"], ctx, cfg)
            if mode == "prefill":
                cache_out = (k, v)

    if cfg.post_norms:
        a_out = _norm(a_out, bp["pn1"], cfg)
    x = x + a_out

    h = _norm(x, bp["ln2"], cfg)
    counts = None
    if "router" not in bp["mlp"]:
        f_out = apply_ffn(bp["mlp"], h, cfg)
    elif mode == "train":
        f_out, aux = moe_mod.apply_moe(bp["mlp"], h, cfg,
                                       group_size=opts.moe_group)
    else:
        f_out, counts = moe_mod.apply_moe_dropless(bp["mlp"], h, cfg,
                                                   experts, layer)
    if cfg.post_norms:
        f_out = _norm(f_out, bp["pn2"], cfg)
    x = x + f_out
    return x, cache_out, aux, counts


# ---------------------------------------------------------------------------
# Full LM
# ---------------------------------------------------------------------------


def _n_first(cfg) -> int:
    return cfg.moe.first_dense_layers if cfg.moe is not None else 0


def init_lm(key, cfg):
    ks = jax.random.split(key, 4 + _n_first(cfg))
    p = {"embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model))}
    n_first = _n_first(cfg)
    if n_first:
        dff = cfg.moe.dense_d_ff or cfg.d_ff
        p["first"] = tuple(
            init_block(ks[3 + i], cfg, 0, use_moe=False, d_ff=dff)
            for i in range(n_first))
    p["blocks"] = init_block(ks[1], cfg, cfg.n_layers - n_first,
                             use_moe=cfg.moe is not None)
    p["final_norm"] = (ones_init(None, (cfg.d_model,)) * 0.0
                       if cfg.rms_plus_one else ones_init(None, (cfg.d_model,)))
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[2], (cfg.d_model, cfg.padded_vocab),
                               in_axis_size=cfg.d_model)
    return p


def _layer_windows(cfg, n_layers: int, seq_len: int):
    """Per-layer window values (traced through scan), or None if all-global."""
    if not cfg.sliding_window:
        return None
    if not cfg.local_global_every:
        return jnp.full((n_layers,), cfg.sliding_window, jnp.int32)
    li = jnp.arange(n_layers)
    is_global = (li % cfg.local_global_every) == (cfg.local_global_every - 1)
    return jnp.where(is_global, jnp.int32(seq_len + 1),
                     jnp.int32(cfg.sliding_window))


def _embed(params, cfg, tokens, dtype):
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    return shard_hint(x, "batch", None, None)


def _head(params, cfg, x):
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T.astype(x.dtype)
    else:
        logits = x @ params["head"].astype(x.dtype)
    if cfg.final_logit_softcap:
        logits = softcap(logits, cfg.final_logit_softcap)
    return shard_hint(logits, "batch", None, "vocab")


def _angles(cfg, positions, mrope_positions):
    hd = cfg.mla.qk_rope_head_dim if cfg.mla is not None else cfg.resolved_head_dim
    if cfg.mrope and mrope_positions is not None:
        return mrope_angles(mrope_positions, cfg.mrope_sections, hd, cfg.rope_theta)
    return rope_angles(positions, hd, cfg.rope_theta, cfg.yarn)


def _add_counts(total, counts):
    return total if counts is None else total + counts


def _scan_inputs(params, mode):
    """The scan's per-layer inputs, and the stacked expert weights a
    serving MoE layer reads in place (None otherwise): slicing a layer's
    experts out of the stack would copy them every layer and step."""
    blocks = params["blocks"]
    if mode == "train" or "router" not in blocks["mlp"]:
        return {"bp": blocks}, None
    mlp = dict(blocks["mlp"])
    experts = [mlp.pop(k) for k in moe_mod.EXPERT_WEIGHTS]
    n = experts[0].shape[0]
    return {"bp": dict(blocks, mlp=mlp), "layer": jnp.arange(n)}, experts


def _no_counts(cfg):
    """The zero of the serving MoE counts, or None for a model without an
    MoE layer (which then carries nothing)."""
    if cfg.moe is None:
        return None
    return jnp.zeros((moe_mod.N_COUNTS,), jnp.int32)


def forward(params, cfg, tokens, *, opts: Options = None, mode: str = "train",
            mrope_positions=None, dtype=jnp.bfloat16, moe_counts: bool = False):
    """tokens (B,S) -> logits (B,S,Vp) [, cache] ; plus moe aux loss; plus,
    with `moe_counts` in prefill, the MoE counts summed over layers."""
    opts = opts or Options()
    B, S = tokens.shape
    x = _embed(params, cfg, tokens, dtype)
    positions = jnp.arange(S)
    sin, cos = _angles(cfg, positions, mrope_positions)
    windows = _layer_windows(cfg, cfg.n_layers - _n_first(cfg), S)
    aux_total = jnp.zeros((), jnp.float32)
    counts = _no_counts(cfg) if mode != "train" else None

    first_caches = []
    for fb in params.get("first", ()):
        x, c_out, aux_l, cnt = apply_block(fb, x, cfg, sin, cos, opts=opts,
                                           window=None, mode=mode)
        first_caches.append(c_out)
        aux_total = aux_total + aux_l
        counts = _add_counts(counts, cnt)

    xs, experts = _scan_inputs(params, mode)

    def body(carry, xs):
        x, aux, counts = carry
        bp = xs["bp"]
        w = xs.get("w")
        x, cache_out, aux_l, cnt = apply_block(
            bp, x, cfg, sin, cos, opts=opts, window=w, mode=mode,
            experts=experts, layer=xs.get("layer"))
        return (x, aux + aux_l, _add_counts(counts, cnt)), cache_out

    if windows is not None:
        xs["w"] = windows
    (x, aux_total, counts), caches = jax.lax.scan(
        maybe_remat(body, opts.remat), (x, aux_total, counts), xs)

    if mode == "prefill":
        # serving only needs next-token logits after prefill
        x_last = _norm(x[:, -1:], params["final_norm"], cfg)
        logits = _head(params, cfg, x_last)[:, 0]
        out = (logits, {"layers": caches, "first": tuple(first_caches)},
               aux_total)
        return out + (counts,) if moe_counts else out
    x = _norm(x, params["final_norm"], cfg)
    logits = _head(params, cfg, x)
    return logits, aux_total


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, abstract=False):
    """Decode cache pytree. Leading L dim over scanned layers; `first` layers
    keep their own unstacked entries."""
    n_first = _n_first(cfg)
    L = cfg.n_layers - n_first
    mk = jax.ShapeDtypeStruct if abstract else (lambda s, d: jnp.zeros(s, d))
    if cfg.mla is not None:
        m = cfg.mla
        entry = (mk((L, batch, max_len, m.kv_lora_rank), dtype),
                 mk((L, batch, max_len, m.qk_rope_head_dim), dtype))
        first = tuple((mk((batch, max_len, m.kv_lora_rank), dtype),
                       mk((batch, max_len, m.qk_rope_head_dim), dtype))
                      for _ in range(n_first))
    else:
        hd = cfg.resolved_head_dim
        entry = (mk((L, batch, max_len, cfg.n_kv_heads, hd), dtype),
                 mk((L, batch, max_len, cfg.n_kv_heads, hd), dtype))
        first = tuple((mk((batch, max_len, cfg.n_kv_heads, hd), dtype),
                       mk((batch, max_len, cfg.n_kv_heads, hd), dtype))
                      for _ in range(n_first))
    return {"layers": entry, "first": first}


def decode_step(params, cfg, tokens, positions, cache, *, opts: Options = None,
                dtype=jnp.bfloat16, moe_counts: bool = False):
    """One token per sequence. tokens/positions (B,). Returns (logits (B,Vp),
    new_cache), and with `moe_counts` the MoE counts summed over layers."""
    opts = opts or Options()
    B = tokens.shape[0]
    x = _embed(params, cfg, tokens[:, None], dtype)
    pos2d = positions[:, None]                       # (B,1)
    if cfg.mrope:
        mpos = jnp.broadcast_to(pos2d[None], (3, B, 1))
        sin, cos = _angles(cfg, pos2d, mpos)
    else:
        sin, cos = _angles(cfg, pos2d, None)
    S_max = jax.tree_util.tree_leaves(cache["layers"])[0].shape[2]
    windows = _layer_windows(cfg, cfg.n_layers - _n_first(cfg), S_max)

    counts = _no_counts(cfg)
    new_first = []
    for fb, fc in zip(params.get("first", ()), cache["first"]):
        x, c_out, _, cnt = apply_block(fb, x, cfg, sin, cos, opts=opts,
                                       window=None, mode="decode", cache=fc,
                                       positions=positions)
        new_first.append(c_out)
        counts = _add_counts(counts, cnt)

    xs, experts = _scan_inputs(params, "decode")
    xs["cache"] = cache["layers"]

    def body(carry, xs):
        x, counts = carry
        bp = xs["bp"]
        w = xs.get("w")
        cache_l = xs["cache"]
        x, c_out, _, cnt = apply_block(bp, x, cfg, sin, cos, opts=opts,
                                       window=w, mode="decode", cache=cache_l,
                                       positions=positions, experts=experts,
                                       layer=xs.get("layer"))
        return (x, _add_counts(counts, cnt)), c_out

    if windows is not None:
        xs["w"] = windows
    (x, counts), new_layers = jax.lax.scan(body, (x, counts), xs)

    x = _norm(x, params["final_norm"], cfg)
    logits = _head(params, cfg, x)[:, 0]
    out = (logits, {"layers": new_layers, "first": tuple(new_first)})
    return out + (counts,) if moe_counts else out
