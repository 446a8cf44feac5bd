"""Serving steps: prefill (fill cache, emit first token logits) and decode
(one token per sequence against the cache).  Sampling is greedy-argmax for
determinism; the dwork serving loop batches requests into these steps."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine.tracing import span


def make_prefill_step(model):
    cfg = model.cfg

    def prefill_step(params, batch):
        logits, cache, _aux = model.forward(params, batch, mode="prefill")
        next_tok = jnp.argmax(logits[..., :cfg.vocab_size], axis=-1)
        return next_tok.astype(jnp.int32), cache

    return prefill_step


def make_decode_step(model):
    cfg = model.cfg

    def serve_step(params, tokens, positions, cache):
        logits, cache = model.decode_step(params, tokens, positions, cache)
        next_tok = jnp.argmax(logits[..., :cfg.vocab_size], axis=-1)
        return next_tok.astype(jnp.int32), cache

    return serve_step


def prefill_into_cache(model, params, batch, cache_len: int, prefill,
                       decode):
    """Run the prompts of `batch` through the jitted `prefill` step (the
    recurrent families: token by token through `decode`, for exactness)
    and return (first greedy token, a decode cache of `cache_len`
    positions holding the prompts)."""
    B, S = batch["tokens"].shape
    if model.cfg.family in ("ssm", "hybrid"):
        cache = model.init_cache(B, cache_len)
        tok = batch["tokens"][:, 0]
        for t in range(S):
            tok, cache = decode(params, batch["tokens"][:, t],
                                jnp.full((B,), t, jnp.int32), cache)
        return tok, cache
    tok, small_cache = prefill(params, batch)
    cache = model.init_cache(B, cache_len)

    def splice(big, small):
        difs = [i for i, (a, b) in enumerate(zip(big.shape, small.shape))
                if a != b]
        if not difs:
            return small.astype(big.dtype)
        ax = difs[0]
        idx = tuple(slice(None) if i != ax else slice(0, small.shape[ax])
                    for i in range(big.ndim))
        return big.at[idx].set(small.astype(big.dtype))

    return tok, jax.tree_util.tree_map(splice, cache, small_cache)


def greedy_generate(model, params, batch, max_new: int, cache_len: int):
    """Small-scale example driver: prefill then greedy-decode max_new tokens."""
    prefill = jax.jit(make_prefill_step(model))
    decode = jax.jit(make_decode_step(model))
    B, S = batch["tokens"].shape
    with span("serve.prefill"):
        tok, cache = prefill_into_cache(model, params, batch, cache_len,
                                        prefill, decode)
    out = [tok]
    with span("serve.decode"):
        for t in range(S, S + max_new - 1):
            tok, cache = decode(params, tok, jnp.full((B,), t, jnp.int32),
                                cache)
            out.append(tok)
    return jnp.stack(out, axis=1)
