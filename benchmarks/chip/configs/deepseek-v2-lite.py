"""Plain reference of DeepSeek-V2-Lite, in float32.

DeepSeek-V2 (arXiv:2405.04434; the published modelling_deepseek.py) is a
decoder of RMSNorm-before blocks.  Attention is multi-head latent
attention without a q-LoRA: q = h Wq split into a 128-wide no-rope part
and a 64-wide rope part per head; h Wdkv gives a 512-wide latent,
RMS-normed and expanded to each head's 128-wide k and v, and a 64-wide
rope key shared by the heads.  Rotary positions are YaRN-scaled, and the
rope dims are taken as interleaved pairs: de-interleaved, then rotated
half against half.  The softmax scale is 192^-0.5 times YaRN's
mscale_all_dim term squared.  The first layer's FFN is a dense SiLU-gated
MLP; every later one is a mixture: a float32 softmax router over the 64
routed experts, the greedy top 6, their weights not renormalised
(`norm_topk_prob` false) and times `routed_scaling_factor`, each expert
a gated MLP of width 1,408, plus the two shared experts as one gated MLP
of width 2,816 on every token.  A final RMSNorm and an untied head.
This file writes that down in `jax.numpy` with every matmul at
`Precision.HIGHEST`; it imports nothing of the program.

Departures from the published model, each the benchmark's:
- the expert share: the configuration holds `n_routed_experts` (16) of
  the published 64 (`published`), from `deployment.held_first_expert`;
  the router keeps all 64 outputs, and only the held experts' part of
  the routed sum is added, as on one chip of the four that share a layer;
- random weights, made by `make_weights` from the seed in the program's
  parameter layout (k and v up-projections as separate `wuk` and `wuv`,
  experts stacked), as bfloat16 values; the reference upcasts them one
  layer at a time, so that it fits beside them.

With `fp8=True` it is the control: the same forward with both operands
of every linear layer rounded to float8 e4m3's four significant bits, the
step below the configuration's bfloat16.  Only the significand is
rounded: per-channel scaling keeps e4m3's exponent range in reach.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# router weights drawn twice as wide as the other matrices: router logits
# of std 2, so the top 6 of 64 softmax weights sum to about 0.68 (0.36 at
# std 1), a router that prefers some experts as a trained one does, and a
# routed part large enough for the comparison to see
ROUTER_SCALE = 2.0


def sizes(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "E": cfg["published"]["n_routed_experts"],
            "held": cfg["n_routed_experts"],
            "first": cfg["deployment"]["held_first_expert"],
            "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
            "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"]}


# ---------------------------------------------------------------- weights
def _last(path) -> str:
    name = jax.tree_util.keystr(path)
    return name.rsplit("'", 2)[-2] if "'" in name else name


def _normal(key, shape, scale, dtype, stacked: bool):
    """normal(0, scale^2) in `dtype`; a stacked leaf one layer at a time,
    so that no float32 copy of a whole stack is ever made."""
    def one(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * scale).astype(dtype)

    if not stacked:
        return one(key, shape)
    return jax.lax.map(lambda k: one(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def _leaf(path, shape, key, dtype):
    last = _last(path)
    stacked = "blocks" in jax.tree_util.keystr(path) and len(shape) > 1
    if last == "embed":
        return _normal(key, shape, 0.02, dtype, False)
    if last in ("ln1", "ln2", "final_norm", "kv_norm"):
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    scale = ROUTER_SCALE if last == "router" else 1.0
    return _normal(key, shape, scale / math.sqrt(shape[-2]), dtype, stacked)


def make_weights(abstract, key):
    """Random bfloat16 weights for every leaf of the program's parameter
    tree `abstract` (its shapes), in one jitted call on the device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(p, a.shape, k, jnp.bfloat16)
            for (p, a), k in zip(leaves, keys)])

    return make(key)


# ------------------------------------------------------------ rotary, YaRN
def _yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(rot: float, dim: int, base: float, max_pos: int) -> float:
    return (dim * math.log(max_pos / (rot * 2 * math.pi))) / (2 * math.log(base))


def rope_tables(cfg: dict, S: int):
    """(cos, sin) of shape (S, dr), each the frequencies repeated twice
    and times YaRN's cos/sin mscale, as DeepseekV2YarnRotaryEmbedding."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / factor
    low = max(math.floor(_correction_dim(rs["beta_fast"], dim, base, orig)), 0)
    high = min(math.ceil(_correction_dim(rs["beta_slow"], dim, base, orig)),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    freqs = np.outer(np.arange(S, dtype=np.float64), inv_freq)
    emb = np.concatenate([freqs, freqs], -1)
    m = (_yarn_get_mscale(factor, rs["mscale"])
         / _yarn_get_mscale(factor, rs["mscale_all_dim"]))
    return (jnp.asarray(np.cos(emb) * m, jnp.float32),
            jnp.asarray(np.sin(emb) * m, jnp.float32))


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        m = _yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
        scale = scale * m * m
    return scale


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], -1)


def _apply_rotary(x, cos, sin):
    """x (B, S, ..., d): pairs (2i, 2i+1) de-interleaved, then rotated."""
    *lead, d = x.shape
    x = x.reshape(*lead, d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*lead, d)
    c = cos.reshape(cos.shape[0], *([1] * (x.ndim - 3)), d)
    s = sin.reshape(sin.shape[0], *([1] * (x.ndim - 3)), d)
    return x * c + _rotate_half(x) * s


# ---------------------------------------------------------------- forward
def _e4m3(x):
    """x rounded to four significant bits, float8 e4m3's significand."""
    m, e = jnp.frexp(x)                       # x = m 2^e, 1/2 <= |m| < 1
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def _mm(x, w, fp8):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _e4m3(x), _e4m3(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _mlp(x, w_gate, w_up, w_down, fp8):
    return _mm(jax.nn.silu(_mm(x, w_gate, fp8)) * _mm(x, w_up, fp8),
               w_down, fp8)


def _attention(x, p, cfg, z, cos, sin, fp8):
    B, S, _ = x.shape
    H, dn, dr, dv, r = z["H"], z["dn"], z["dr"], z["dv"], z["r"]
    q = _mm(x, p["wq"], fp8).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    ckv = _mm(x, p["wdkv"], fp8)
    latent, k_pe = ckv[..., :r], ckv[..., r:]
    latent = _rms(latent, p["kv_norm"], z["eps"])
    k_nope = _mm(latent, p["wuk"], fp8).reshape(B, S, H, dn)
    v = _mm(latent, p["wuv"], fp8).reshape(B, S, H, dv)
    q_pe = _apply_rotary(q_pe, cos, sin)
    k_pe = _apply_rotary(k_pe, cos, sin)                   # (B, S, dr)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, H, dr))], -1)
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST) \
        * softmax_scale(cfg)
    pos = jnp.arange(S)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    ctx = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v,
                     precision=HIGHEST).reshape(B, S, H * dv)
    return _mm(ctx, p["wo"], fp8)


def _moe(x, p, cfg, z, fp8):
    """The held experts' part of the routed sum, plus the shared experts."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    scores = jax.nn.softmax(_mm(xt, p["router"], fp8), -1)   # (T, 64)
    w, idx = jax.lax.top_k(scores, z["k"])
    if cfg["norm_topk_prob"] and z["k"] > 1:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    else:
        w = w * cfg["routed_scaling_factor"]
    # each token's weight on each held expert (0 where not routed there)
    held = z["first"] + jnp.arange(z["held"])
    per_expert = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None],
                                   0.0), 1)                   # (T, held)

    def expert(y, e):
        w1, w3, w2, g = e
        return y + g[:, None] * _mlp(xt, w1, w3, w2, fp8), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(xt),
                        (p["w1"], p["w3"], p["w2"], per_expert.T))
    y = y + _mlp(xt, p["ws1"], p["ws3"], p["ws2"], fp8)
    return y.reshape(B, S, D)


def _block(x, p, cfg, z, cos, sin, fp8):
    x = x + _attention(_rms(x, p["ln1"], z["eps"]), p["attn"], cfg, z, cos,
                       sin, fp8)
    h = _rms(x, p["ln2"], z["eps"])
    mlp = p["mlp"]
    if "router" in mlp:
        return x + _moe(h, mlp, cfg, z, fp8)
    return x + _mlp(h, mlp["w1"], mlp["w3"], mlp["w2"], fp8)


def logits_at(params, tokens, positions, cfg: dict, *, fp8: bool = False):
    """tokens (B, S) -> logits (B, len(positions), V) in float32: the
    full causal forward, read at `positions`."""
    z = sizes(cfg)
    S = tokens.shape[1]
    cos, sin = rope_tables(cfg, S)
    x = jnp.take(params["embed"][:z["V"]], tokens, axis=0) \
        .astype(jnp.float32)
    for p in params["first"]:
        x = _block(x, p, cfg, z, cos, sin, fp8)

    def layer(x, p):
        return _block(x, p, cfg, z, cos, sin, fp8), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x[:, positions], params["final_norm"], z["eps"])
    return _mm(x, params["head"][:, :z["V"]], fp8)


def _gaps(params, seqs, prompt_len, cfg, fp8):
    """Per position from the prompt's last: (reference best logit minus
    the reference logit of the next served token, the token the fp8
    forward puts first)."""
    positions = jnp.arange(prompt_len - 1, seqs.shape[1] - 1)
    ref = logits_at(params, seqs, positions, cfg)
    best = jnp.max(ref, -1)
    nxt = seqs[:, prompt_len:]
    served = jnp.take_along_axis(ref, nxt[..., None], -1)[..., 0]
    if not fp8:
        return best - served
    low = logits_at(params, seqs, positions, cfg, fp8=True)
    pick = jnp.argmax(low, -1)
    return best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]


def served_gap(params, seqs, prompt_len: int, cfg: dict, *,
               block: int = 2, fp8: bool = False) -> np.ndarray:
    """(B, max_new) gaps, `block` requests at a time so that it fits."""
    seqs = np.asarray(seqs, np.int32)
    fn = jax.jit(lambda p, s: _gaps(p, s, prompt_len, cfg, fp8))
    out = [np.asarray(fn(params, jnp.asarray(seqs[i:i + block])))
           for i in range(0, len(seqs), block)]
    return np.concatenate(out)
