"""Uniform model API over all families.

    model = build_model(cfg)
    params = model.init(key)
    logits, aux = model.forward(params, batch)                  # train
    logits, cache, aux = model.forward(params, batch, mode="prefill")
    logits, cache = model.decode_step(params, tokens, pos, cache)
    cache = model.init_cache(batch, max_len, abstract=True)

An MoE model's serving steps also return their MoE counts, summed over
layers, where asked (`moe_counts=True`; `moe.N_COUNTS`).

    weights = model.serving_weights(params)   # what the steps read, in bf16
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import rwkv, transformer, whisper, zamba
from repro.models.common import Options


_to_bf16 = jax.jit(lambda ws: [w.astype(jnp.bfloat16) for w in ws])


@dataclass
class Model:
    cfg: Any
    opts: Options
    _mod: Any

    def init(self, key):
        return self._mod.init_lm(key, self.cfg)

    def init_abstract(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: self._mod.init_lm(k, self.cfg), key)

    def forward(self, params, batch: dict, mode: str = "train",
                moe_counts: bool = False):
        kw = {"moe_counts": True} if moe_counts else {}
        if self.cfg.mrope and "mrope_positions" in batch:
            kw["mrope_positions"] = batch["mrope_positions"]
        if self.cfg.family == "audio":
            kw["encoder_frames"] = batch["encoder_frames"]
        return self._mod.forward(params, self.cfg, batch["tokens"],
                                 opts=self.opts, mode=mode, **kw)

    def decode_step(self, params, tokens, positions, cache,
                    moe_counts: bool = False):
        kw = {"moe_counts": True} if moe_counts else {}
        return self._mod.decode_step(params, self.cfg, tokens, positions,
                                     cache, opts=self.opts, **kw)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   abstract: bool = False):
        if self.cfg.family == "ssm":
            return self._mod.init_state(self.cfg, batch, abstract=abstract)
        return self._mod.init_cache(self.cfg, batch, max_len, dtype=dtype,
                                    abstract=abstract)

    def serving_weights(self, params):
        """`params` with the leaves that the family's steps read only as
        bfloat16 (its module's `SERVED_BF16` names) cast to bfloat16, in
        one jitted call; every other leaf, and one already in bfloat16,
        is the caller's own object.  `params` itself where no leaf needs
        the cast, as for a family that declares no names."""
        names = getattr(self._mod, "SERVED_BF16", frozenset())
        paths, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [leaf for _, leaf in paths]
        pick = [i for i, (path, leaf) in enumerate(paths)
                if getattr(path[-1], "key", None) in names
                and leaf.dtype != jnp.bfloat16]
        if not pick:
            return params
        for i, w in zip(pick, _to_bf16([leaves[i] for i in pick])):
            leaves[i] = w
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def with_opts(self, **kw) -> "Model":
        return Model(self.cfg, self.opts.replace(**kw), self._mod)


_FAMILY_MODULES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "hybrid": zamba,
    "ssm": rwkv,
    "audio": whisper,
}


def build_model(cfg, opts: Options = None) -> Model:
    return Model(cfg, opts or Options(), _FAMILY_MODULES[cfg.family])
