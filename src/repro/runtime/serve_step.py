"""Serving steps: prefill (fill cache, emit first token logits) and decode
(one token per sequence against the cache).  Sampling is greedy-argmax for
determinism; the dwork serving loop batches requests into these steps.

An MoE model's steps, built with `moe_counts=True`, also return the MoE
counts of the step (`moe.N_COUNTS`: rows the held experts computed, held
experts with a row, MoE layer calls; summed over layers).
`greedy_generate` sums them on the device over the batch's steps, fetches
them with the tokens, and records each batch's with its completion time:
`moe_counts()` lists the records.  A model without MoE layers builds and
runs its steps as before and records nothing.

`greedy_generate` builds a model's jitted prefill and decode steps on its
first call and keeps them on the model instance (its `_serve_steps`), so
they are freed with the model and no two models share one.  A later call
reuses them through JAX's dispatch fast path: nothing is traced, lowered
or loaded from the compile cache for a batch shape the model has run
before.  `make_prefill_step` and `make_decode_step` are looked up in this
module when the steps are built, and the weights and the batch stay
arguments, never constants of the kept programs.  `jit_cache_info()`
counts the calls that built the steps or ran a new batch shape (misses,
where JAX traces again) and the rest (hits); `jit_cache_clear()` drops
every model's kept steps and resets the counts.

The steps read their weight matrices only in bfloat16, so
`greedy_generate` hands them the caller's weights with those leaves cast
once (`Model.serving_weights`, one jitted call, in the span
`serve.cast_weights`) and keeps the cast copy on the model (its
`_serve_weights`): one copy a model, under the step cache's generation
and the identities of the caller's leaves, which the copy holds.  New
weights replace the copy; it is freed with the model, and
`jit_cache_clear()` drops it.  Where no leaf needs the cast (weights
already in bfloat16) nothing is kept and the caller's tree goes to the
steps as it is.  `weight_cast_info()` counts each call as one that cast,
reused the copy, or bypassed it."""
from __future__ import annotations

import threading
import time
import weakref
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


class _StepCache:
    """Each model's kept jitted steps and the batch shapes they have run,
    with the hits and misses since the last `clear()`.  The steps are kept
    under the model's `id` and the generation, which `clear()` moves on:
    a copy of a model, or its steps from before a `clear()`, is rebuilt."""

    def __init__(self):
        self.lock = threading.Lock()
        self.generation = 0
        self.clear()

    def clear(self) -> None:
        with self.lock:
            self.generation += 1
            self.hits = self.misses = 0

    def get(self, model, shape: tuple):
        """The model's (prefill, decode), built on its first call."""
        with self.lock:
            key = (id(model), self.generation)
            kept = getattr(model, "_serve_steps", None)
            if kept is None or kept["key"] != key:
                kw = {"moe_counts": True} if model.cfg.moe is not None else {}
                kept = model._serve_steps = {
                    "key": key, "shapes": set(),
                    "prefill": jax.jit(make_prefill_step(model, **kw)),
                    "decode": jax.jit(make_decode_step(model, **kw))}
            if shape in kept["shapes"]:
                self.hits += 1
            else:
                self.misses += 1
                kept["shapes"].add(shape)
            return kept["prefill"], kept["decode"]


_STEPS = _StepCache()


def jit_cache_info() -> dict:
    """Hits and misses of the kept serving steps, all models together."""
    with _STEPS.lock:
        return {"hits": _STEPS.hits, "misses": _STEPS.misses}


def jit_cache_clear() -> None:
    _STEPS.clear()
    _WEIGHTS.clear()


class _WeightCache:
    """Each model's served weights (`Model.serving_weights` of the
    caller's tree) kept on the model, with the calls since the last
    `clear()` that cast, reused the copy, or had nothing to cast."""

    def __init__(self):
        self.lock = threading.Lock()
        self.holders = weakref.WeakValueDictionary()    # id -> model
        self.clear()

    def clear(self) -> None:
        with self.lock:
            for model in list(self.holders.values()):
                model._serve_weights = None
            self.holders.clear()
            self.casts = self.reuses = self.bypassed = 0

    def get(self, model, params, generation: int):
        """What the model's steps read for `params`."""
        leaves = jax.tree_util.tree_leaves(params)
        key = (id(model), generation, tuple(map(id, leaves)))
        with self.lock:
            kept = getattr(model, "_serve_weights", None)
            if kept is not None and kept["key"] == key:
                self.reuses += 1
                return kept["weights"]
            model._serve_weights = None     # the old copy goes first
            with span("serve.cast_weights"):
                weights = model.serving_weights(params)
            if weights is params:
                self.bypassed += 1
                return params
            model._serve_weights = {"key": key, "leaves": leaves,
                                    "weights": weights}
            self.holders[id(model)] = model
            self.casts += 1
            return weights


_WEIGHTS = _WeightCache()


def weight_cast_info() -> dict:
    """`greedy_generate` calls, all models together, that cast the
    weights, reused a kept copy, or had no leaf to cast."""
    with _WEIGHTS.lock:
        return {"casts": _WEIGHTS.casts, "reuses": _WEIGHTS.reuses,
                "bypassed": _WEIGHTS.bypassed}


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
    """Prefill, then greedy-decode max_new tokens with the model's kept
    steps and served weights.  An MoE model's tokens come back as a host
    array, fetched together with the batch's MoE counts (recorded for
    `moe_counts()`)."""
    B, S = batch["tokens"].shape
    prefill, decode = _STEPS.get(model, (B, S, cache_len))
    params = _WEIGHTS.get(model, params, _STEPS.generation)
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
    if model.cfg.moe is None:
        return tokens
    tokens, total = jax.device_get((tokens, jnp.sum(jnp.stack(counts), 0)))
    _COUNTS.add(total)
    return tokens
