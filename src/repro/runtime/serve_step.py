"""Serving steps: prefill (fill cache, emit first token logits) and decode
(one token per sequence against the cache).  Sampling is greedy-argmax for
determinism; the dwork serving loop batches requests into these steps.

An MoE model's steps, built with `moe_counts=True`, also return the MoE
counts of the step (`moe.N_COUNTS`: rows the held experts computed, held
experts with a row, MoE layer calls; summed over layers).
`greedy_generate` sums them on the device over the batch's steps, fetches
them with the tokens, and records each batch's with its completion time:
`moe_counts()` lists the records.  A model without MoE layers builds and
runs its steps as before and records nothing."""
from __future__ import annotations

import threading
import time
from collections import deque

import jax
import jax.numpy as jnp

from repro.core.engine.tracing import span


class _CountLog:
    """The last `size` batches' MoE counts, each with its completion time
    on the `time.perf_counter` clock."""

    def __init__(self, size: int):
        self.lock = threading.Lock()
        self.records: deque = deque(maxlen=size)

    def add(self, counts) -> None:
        rows, hit, calls = (int(c) for c in counts)
        with self.lock:
            self.records.append({"t_done": time.perf_counter(), "rows": rows,
                                 "experts_hit": hit, "calls": calls})

    def clear(self) -> None:
        with self.lock:
            self.records.clear()


_COUNTS = _CountLog(4096)


def moe_counts() -> list[dict]:
    """Each served batch's MoE counts, oldest first: `t_done`
    (`time.perf_counter()` when its tokens reached the host), `rows`,
    `experts_hit`, `calls`, summed over its layers and steps."""
    with _COUNTS.lock:
        return list(_COUNTS.records)


def moe_counts_clear() -> None:
    _COUNTS.clear()


def make_prefill_step(model, moe_counts: bool = False):
    cfg = model.cfg

    def prefill_step(params, batch):
        logits, cache, _aux, *counts = model.forward(
            params, batch, mode="prefill", moe_counts=moe_counts)
        next_tok = jnp.argmax(logits[..., :cfg.vocab_size], axis=-1)
        return (next_tok.astype(jnp.int32), cache, *counts)

    return prefill_step


def make_decode_step(model, moe_counts: bool = False):
    cfg = model.cfg

    def serve_step(params, tokens, positions, cache):
        logits, cache, *counts = model.decode_step(
            params, tokens, positions, cache, moe_counts=moe_counts)
        next_tok = jnp.argmax(logits[..., :cfg.vocab_size], axis=-1)
        return (next_tok.astype(jnp.int32), cache, *counts)

    return serve_step


def prefill_into_cache(model, params, batch, cache_len: int, prefill,
                       decode):
    """Run the prompts of `batch` through the jitted `prefill` step (the
    recurrent families: token by token through `decode`, for exactness)
    and return (first greedy token, a decode cache of `cache_len`
    positions holding the prompts), followed by whatever else `prefill`
    returns (an MoE model's counts)."""
    B, S = batch["tokens"].shape
    if model.cfg.family in ("ssm", "hybrid"):
        cache = model.init_cache(B, cache_len)
        tok = batch["tokens"][:, 0]
        for t in range(S):
            tok, cache = decode(params, batch["tokens"][:, t],
                                jnp.full((B,), t, jnp.int32), cache)
        return tok, cache
    tok, small_cache, *rest = prefill(params, batch)
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

    return (tok, jax.tree_util.tree_map(splice, cache, small_cache), *rest)


def greedy_generate(model, params, batch, max_new: int, cache_len: int):
    """Prefill, then greedy-decode max_new tokens.
    An MoE model's tokens come back as a host array, fetched together with
    the batch's MoE counts (recorded for `moe_counts()`)."""
    moe = model.cfg.moe is not None
    kw = {"moe_counts": True} if moe else {}
    prefill = jax.jit(make_prefill_step(model, **kw))
    decode = jax.jit(make_decode_step(model, **kw))
    B, S = batch["tokens"].shape
    with span("serve.prefill"):
        tok, cache, *counts = prefill_into_cache(model, params, batch,
                                                 cache_len, prefill, decode)
    out = [tok]
    with span("serve.decode"):
        for t in range(S, S + max_new - 1):
            tok, cache, *step_counts = decode(
                params, tok, jnp.full((B,), t, jnp.int32), cache)
            out.append(tok)
            counts += step_counts
    tokens = jnp.stack(out, axis=1)
    if not moe:
        return tokens
    tokens, total = jax.device_get((tokens, jnp.sum(jnp.stack(counts), 0)))
    _COUNTS.add(total)
    return tokens
