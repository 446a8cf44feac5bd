"""Plain reference of qwen2-vl-2b's language model, in float32.

Qwen2-VL (arXiv:2409.12191) is a Qwen2 decoder: RMSNorm before attention
and MLP, GQA attention with q/k/v biases and rotary positions (M-RoPE,
whose three position streams are equal for text, so it is RoPE with the
half-split convention), a SiLU-gated MLP, a final RMSNorm and an output
head tied to the embedding.  This file writes that down in `jax.numpy`
with every matmul at `Precision.HIGHEST`; it imports nothing of the
program.

The weights are the benchmark's, made by `make_weights` from the seed in
the program's parameter layout, which is read from the shapes alone: the
program pads the 12 query heads to 16 (head h = g * M_pad + m of kv
group g, real where m < 6); the reference reads the 12 real ones, and
the padded vocabulary rows are never read.

With `fp8=True` it is the control: the same forward with both operands
of every linear layer rounded to float8 e4m3's four significant bits, the
step below the configuration's bfloat16 that a later change would be
tempted by.  Only the significand is rounded: per-channel scaling keeps
e4m3's exponent range in reach, so the range is not modelled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {"D": D, "H": H, "G": cfg["num_key_value_heads"], "hd": D // H,
            "F": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
            "theta": cfg["rope_theta"]}


# ---------------------------------------------------------------- weights
def _leaf(path, shape, key, dtype):
    name = jax.tree_util.keystr(path)
    last = name.rsplit("'", 2)[-2] if "'" in name else name
    if last == "embed":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    if last in ("ln1", "ln2", "final_norm"):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if last in ("bq", "bk", "bv"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def make_weights(abstract, key):
    """Random weights for every leaf of the program's parameter tree
    `abstract` (shapes and dtypes), in one jitted call on the device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(p, a.shape, k, a.dtype).astype(a.dtype)
            for (p, a), k in zip(leaves, keys)])

    return make(key)


# ---------------------------------------------------------------- forward
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, hd, theta):
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv              # (S, half)
    s, c = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _e4m3(x):
    """x rounded to four significant bits, float8 e4m3's significand."""
    m, e = jnp.frexp(x)                       # x = m 2^e, 1/2 <= |m| < 1
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def _mm(x, w, fp8):
    if fp8:
        x, w = _e4m3(x), _e4m3(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def real_heads(w_cols, G, H, hd, axis):
    """The 12 real heads' slices of a padded projection: columns of wq/bq
    (axis -1) or rows of wo (axis 0)."""
    hq_pad = w_cols.shape[axis] // hd
    m_pad, m = hq_pad // G, H // G
    idx = np.concatenate([np.arange(hd) + (g * m_pad + j) * hd
                          for g in range(G) for j in range(m)])
    return jnp.take(w_cols, idx, axis=axis)


def logits_at(params, tokens, positions, cfg: dict, *, fp8: bool = False):
    """tokens (B, S) -> logits (B, len(positions), V) in float32: the
    full causal forward, read at `positions`."""
    z = sizes(cfg)
    D, H, G, hd, L, V = z["D"], z["H"], z["G"], z["hd"], z["L"], z["V"]
    B, S = tokens.shape
    pos = jnp.arange(S)
    x = jnp.take(params["embed"][:V].astype(jnp.float32), tokens, axis=0)
    blocks = params["blocks"]
    attn, mlp = blocks["attn"], blocks["mlp"]
    layer = {
        "ln1": blocks["ln1"], "ln2": blocks["ln2"],
        "wq": real_heads(attn["wq"], G, H, hd, -1),
        "bq": real_heads(attn["bq"], G, H, hd, -1),
        "wk": attn["wk"], "bk": attn["bk"], "wv": attn["wv"], "bv": attn["bv"],
        "wo": real_heads(attn["wo"], G, H, hd, 1),
        "w1": mlp["w1"], "w3": mlp["w3"], "w2": mlp["w2"]}
    mask = pos[:, None] >= pos[None, :]

    def block(x, p):
        h = _rms(x, p["ln1"], z["eps"])
        q = (_mm(h, p["wq"], fp8) + p["bq"]).reshape(B, S, H, hd)
        k = (_mm(h, p["wk"], fp8) + p["bk"]).reshape(B, S, G, hd)
        v = (_mm(h, p["wv"], fp8) + p["bv"]).reshape(B, S, G, hd)
        q, k = _rope(q, pos, hd, z["theta"]), _rope(k, pos, hd, z["theta"])
        k = jnp.repeat(k, H // G, axis=2)
        v = jnp.repeat(v, H // G, axis=2)
        s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST) / np.sqrt(hd)
        s = jnp.where(mask, s, -jnp.inf)
        ctx = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v,
                         precision=HIGHEST).reshape(B, S, H * hd)
        x = x + _mm(ctx, p["wo"], fp8)
        h = _rms(x, p["ln2"], z["eps"])
        f = jax.nn.silu(_mm(h, p["w1"], fp8)) * _mm(h, p["w3"], fp8)
        return x + _mm(f, p["w2"], fp8), None

    x, _ = jax.lax.scan(block, x, layer, length=L)
    x = _rms(x[:, positions], params["final_norm"], z["eps"])
    return _mm(x, params["embed"][:V].T, fp8)


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
               block: int = 4, fp8: bool = False) -> np.ndarray:
    """(B, max_new) gaps, `block` requests at a time so that it fits."""
    seqs = np.asarray(seqs, np.int32)
    fn = jax.jit(lambda p, s: _gaps(p, s, prompt_len, cfg, fp8))
    out = [np.asarray(fn(params, jnp.asarray(seqs[i:i + block])))
           for i in range(0, len(seqs), block)]
    return np.concatenate(out)
