"""Mixture-of-Experts FFN, two ways.

Training (`apply_moe`): grouped and capacity-dropped.  Dispatch uses
per-group scatter/gather (no (tokens, E, C) one-hot materialization);
experts are sharded on the `model` mesh axis (EP), tokens on `data` —
GSPMD inserts the dispatch/combine collectives.

Serving (`apply_moe_dropless`): every token's top-k assignments to the
experts held here are sorted by expert and run through the `moe_gmm`
grouped-matmul kernel, so no token is dropped and only routed rows are
computed.  A layer may hold a share of the routed experts
(`MoEConfig.first_expert`, `n_held`): the router keeps all `n_experts`
outputs, and the layer returns its held experts' part of the routed sum
(what the other shares would add is left to them).

Shared experts (DeepSeek-V2) and the Arctic dense residual are merged into a
single wide "shared" gated FFN applied to every token.  Top-k weights are
renormalised to sum to 1 where `norm_topk_prob`, else scaled by
`routed_scaling_factor` (DeepSeek-V2's MoEGate).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.ops import moe_gmm
from repro.models.common import activation, dense_init, shard_hint

# MoE counts a serving step returns, summed over layers: rows the held
# experts computed, held experts with at least one row, MoE layer calls
N_COUNTS = 3
EXPERT_WEIGHTS = ("w1", "w3", "w2")


def shared_width(cfg) -> int:
    m = cfg.moe
    w = m.n_shared_experts * m.d_expert
    if m.dense_residual:
        w += m.dense_d_ff or cfg.d_ff
    return w


def init_moe(key, cfg, n_layers: int):
    m = cfg.moe
    D, E, F = cfg.d_model, m.held, m.d_expert
    ks = jax.random.split(key, 7)
    L = (n_layers,) if n_layers else ()
    p = {
        "router": dense_init(ks[0], L + (D, m.n_experts), in_axis_size=D),
        "w1": dense_init(ks[1], L + (E, D, F), in_axis_size=D),
        "w3": dense_init(ks[2], L + (E, D, F), in_axis_size=D),
        "w2": dense_init(ks[3], L + (E, F, D), in_axis_size=F),
    }
    sw = shared_width(cfg)
    if sw:
        p["ws1"] = dense_init(ks[4], L + (D, sw), in_axis_size=D)
        p["ws3"] = dense_init(ks[5], L + (D, sw), in_axis_size=D)
        p["ws2"] = dense_init(ks[6], L + (sw, D), in_axis_size=sw)
    return p


def _capacity(g: int, k: int, cf: float, E: int) -> int:
    c = int(g * k * cf / E)
    c = max(8, ((c + 7) // 8) * 8)
    return min(c, g * k)


def _route(p, x2, m):
    """Router probabilities (T, n_experts) and the top-k weights and
    experts (T, k), from x2 (T, D) in float32."""
    logits = jnp.matmul(x2.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, m.top_k)
    if m.top_k > 1 and m.norm_topk_prob:
        vals = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    else:
        vals = vals * m.routed_scaling_factor
    return probs, vals, idx


def _shared(p, x, cfg):
    act = activation(cfg.act)
    hs = act(x @ p["ws1"].astype(x.dtype)) * (x @ p["ws3"].astype(x.dtype))
    hs = shard_hint(hs, "batch", None, "model_ff")
    return hs @ p["ws2"].astype(x.dtype)


def apply_moe_dropless(p, x, cfg, experts=None, layer=None):
    """x: (B, S, D) -> (out (B,S,D), counts int32 (N_COUNTS,)).  The held
    experts' part of every token's routed sum, dropping none, plus the
    shared FFN; counts as `N_COUNTS` says.  The expert weights are p's
    own, or layer `layer` of `experts`, the layer stack's `EXPERT_WEIGHTS`,
    which the kernel reads in place."""
    m = cfg.moe
    B, S, D = x.shape
    T, k, E = B * S, m.top_k, m.held
    xf = x.reshape(T, D)
    with jax.named_scope("moe.route"):
        _probs, vals, idx = _route(p, xf, m)
        local = idx.reshape(T * k) - m.first_expert
        held = (local >= 0) & (local < E)
        key = jnp.where(held, local, E)                 # E: not held here
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, E, dtype=jnp.int32), axis=0)
        tok = order // k
    w = experts if experts is not None else [p[n] for n in EXPERT_WEIGHTS]
    y_sorted = moe_gmm(jnp.take(xf, tok, axis=0), *w, sizes, layer,
                       act=cfg.act, interpret=jax.default_backend() != "tpu")
    gate = jnp.take(vals.reshape(T * k), order)
    y = jnp.zeros((T, D), jnp.float32).at[tok].add(
        y_sorted.astype(jnp.float32) * gate[:, None])
    y = y.astype(x.dtype).reshape(B, S, D)
    if "ws1" in p:
        y = y + _shared(p, x, cfg)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                        jnp.ones((), jnp.int32)])
    return shard_hint(y, "batch", None, None), counts


def apply_moe(p, x, cfg, *, group_size: int = 1024):
    """x: (B, S, D) -> (out (B,S,D), aux_loss scalar).  Every expert must
    be held here (`n_held` 0 or `n_experts`)."""
    m = cfg.moe
    E, k = m.n_experts, m.top_k
    if m.held != E:
        raise ValueError("the capacity path computes every routed expert; "
                         f"this layer holds {m.held} of {E}")
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    g = min(group_size, T)
    while T % g:
        g //= 2
    G = T // g
    xg = shard_hint(xf.reshape(G, g, D), "moe_groups", None, None)

    probs, vals, idx = _route(p, xg, m)                                   # (G,g,E|k)

    # Switch-style load-balance aux loss
    me = jnp.mean(probs, axis=(0, 1))                                     # (E,)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=2),
                  axis=(0, 1))                                            # (E,)
    aux = E * jnp.sum(me * ce) * m.router_aux_loss

    C = _capacity(g, k, m.capacity_factor, E)

    # GShard choice-major slot assignment: all 1st choices, then 2nd, ...
    idx_km = idx.transpose(0, 2, 1).reshape(G, k * g)                     # (G,k*g)
    oh = jax.nn.one_hot(idx_km, E, dtype=jnp.int32)                       # (G,k*g,E)
    slot = jnp.cumsum(oh, axis=1) - oh                                    # pos within expert
    slot = jnp.sum(slot * oh, axis=-1)                                    # (G,k*g)
    keep = slot < C

    gate_km = vals.transpose(0, 2, 1).reshape(G, k * g)
    tok_km = jnp.tile(jnp.arange(g), (k,))                                # (k*g,)

    def dispatch_one(xg1, e1, s1, keep1):
        upd = xg1[tok_km] * keep1[:, None].astype(xg1.dtype)              # (k*g, D)
        buf = jnp.zeros((E, C, D), xg1.dtype)
        return buf.at[e1, jnp.where(keep1, s1, 0)].add(
            jnp.where(keep1[:, None], upd, 0))

    ein = jax.vmap(dispatch_one)(xg, idx_km, slot, keep)                  # (G,E,C,D)
    # 2D-weight mode: slice the dispatch on the contraction dim ("moe_ff" ->
    # data) so the expert matmul is a partial-dot + tiny psum — weights never
    # move (GSPMD would otherwise all-to-all the expert weights each layer)
    ein = shard_hint(ein, "moe_groups", "expert", None, "moe_ff")

    act = activation(cfg.act)
    h = jnp.einsum("gecd,edf->gecf", ein, p["w1"].astype(ein.dtype))
    h = act(h) * jnp.einsum("gecd,edf->gecf", ein, p["w3"].astype(ein.dtype))
    h = shard_hint(h, "moe_groups", "expert", None, "moe_ff")
    eout = jnp.einsum("gecf,efd->gecd", h, p["w2"].astype(ein.dtype))
    eout = shard_hint(eout, "moe_groups", "expert", None, None)

    def combine_one(eo1, e1, s1, keep1, gate1):
        y = eo1[e1, s1] * (gate1 * keep1)[:, None].astype(eo1.dtype)      # (k*g,D)
        return jnp.sum(y.reshape(k, g, D), axis=0)

    y = jax.vmap(combine_one)(eout, idx_km, slot, keep,
                              gate_km.astype(eout.dtype))                 # (G,g,D)
    y = y.reshape(B, S, D)

    if "ws1" in p:
        y = y + _shared(p, x, cfg)

    return shard_hint(y, "batch", None, None), aux
