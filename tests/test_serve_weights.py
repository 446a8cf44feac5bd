"""The served weights kept per model (`runtime/serve_step.py`,
`Model.serving_weights`): `greedy_generate` casts the leaves its steps read
in bfloat16 once for each set of weights, keeps the copy on the model and
reuses it, returns the tokens the steps give on the float32 tree, passes
weights already in bfloat16 through untouched, and drops the copy with
new weights, with `jit_cache_clear()` and with the model."""
import gc
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.common import Options
from repro.models.model import build_model
from repro.runtime import serve_step

ARCHS = ("qwen2-vl-2b", "deepseek-v2-lite")
KEPT_F32 = {"ln1", "ln2", "final_norm", "kv_norm", "router"}
S, NEW = 12, 4


def _model(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg, Options(q_block=16, kv_block=16, moe_group=64))
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    return _model(request.param)


def _batch(model, seed, B=1):
    b = {"tokens": jax.random.randint(jax.random.PRNGKey(seed), (B, S), 2,
                                      model.cfg.vocab_size)}
    if model.cfg.mrope:
        b["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (3, B, S))
    return b


def _generate(model, params, batch):
    return np.asarray(serve_step.greedy_generate(model, params, batch, NEW,
                                                 S + NEW + 1))


def _named(params):
    """(last key, leaf) of each leaf."""
    paths, _ = jax.tree_util.tree_flatten_with_path(params)
    return [(getattr(p[-1], "key", None), leaf) for p, leaf in paths]


def _cast_leaves(model):
    """The kept copy's own leaves (the rest are the caller's)."""
    return [w for w in jax.tree_util.tree_leaves(
        model._serve_weights["weights"]) if w.dtype == jnp.bfloat16]


def test_tokens_are_the_f32_trees_through_the_kept_steps(served):
    """The kept steps called directly on the float32 tree give the same
    tokens as `greedy_generate`, which hands them the cast copy."""
    model, params = served
    batch = _batch(model, 1, B=2)
    got = _generate(model, params, batch)
    prefill = model._serve_steps["prefill"]
    decode = model._serve_steps["decode"]
    tok, cache, *_ = serve_step.prefill_into_cache(
        model, params, batch, S + NEW + 1, prefill, decode)
    want = [tok]
    for t in range(S, S + NEW - 1):
        tok, cache, *_ = decode(params, tok, jnp.full((2,), t, jnp.int32),
                                cache)
        want.append(tok)
    np.testing.assert_array_equal(got, np.asarray(jnp.stack(want, 1)))


def test_three_batches_cast_once(served):
    model, params = served
    serve_step.jit_cache_clear()
    for seed in (2, 3, 4):
        _generate(model, params, _batch(model, seed))
    assert serve_step.weight_cast_info() == {"casts": 1, "reuses": 2,
                                             "bypassed": 0}
    # the step cache counts as it did before the weights were kept
    assert serve_step.jit_cache_info() == {"hits": 2, "misses": 1}


def test_a_replaced_leaf_casts_again_and_drops_the_old_copy(served):
    model, params = served
    _generate(model, params, _batch(model, 5))
    serve_step.jit_cache_clear()
    _generate(model, params, _batch(model, 5))
    old = [weakref.ref(w) for w in _cast_leaves(model)]
    new = jax.tree_util.tree_map(lambda x: x, params)     # a new tree ...
    new["embed"] = params["embed"] + 0.0                  # ... one new leaf
    _generate(model, new, _batch(model, 6))
    assert serve_step.weight_cast_info() == {"casts": 2, "reuses": 0,
                                             "bypassed": 0}
    assert model._serve_weights["weights"]["embed"].dtype == jnp.bfloat16
    gc.collect()
    assert all(r() is None for r in old)


@pytest.mark.parametrize("how", ["all_bf16", "served"])
def test_bf16_weights_are_bypassed_and_passed_as_they_are(served, how,
                                                          monkeypatch):
    """Weights whose served leaves are bfloat16 already (every leaf, as
    the MoE cell's harness draws them, or `serving_weights`' own output)
    are neither copied nor cast: the steps get the caller's leaves."""
    model, params = served
    if how == "all_bf16":
        mine = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                      params)
    else:
        mine = model.serving_weights(params)
    seen = []
    real_get = serve_step._STEPS.get

    def get(model, shape):
        steps = real_get(model, shape)

        def recording(step):
            def call(weights, *a):
                seen.append(weights)
                return step(weights, *a)
            return call

        return tuple(map(recording, steps))

    monkeypatch.setattr(serve_step._STEPS, "get", get)
    serve_step.jit_cache_clear()
    for seed in (7, 8):
        _generate(model, mine, _batch(model, seed))
    assert serve_step.weight_cast_info() == {"casts": 0, "reuses": 0,
                                             "bypassed": 2}
    assert model._serve_weights is None
    want = jax.tree_util.tree_leaves(mine)
    for weights in seen:
        got = jax.tree_util.tree_leaves(weights)
        assert len(got) == len(want)
        assert all(g is w for g, w in zip(got, want))


def test_norm_scales_and_router_stay_f32(served):
    model, params = served
    cast = model.serving_weights(params)
    names = set()
    for (name, mine), (_, w) in zip(_named(params), _named(cast)):
        names.add(name)
        if name in KEPT_F32:
            assert w is mine and w.dtype == jnp.float32, name
        else:
            assert w.dtype == jnp.bfloat16, name
            np.testing.assert_array_equal(
                np.asarray(w), np.asarray(mine.astype(jnp.bfloat16)))
    assert {"ln1", "ln2", "final_norm"} <= names
    if model.cfg.moe is not None:
        assert {"kv_norm", "router"} <= names


def test_a_family_that_declares_nothing_gets_its_tree_back():
    model = build_model(get_config("rwkv6-1.6b").reduced())
    params = model.init_abstract()
    assert model.serving_weights(params) is params


def test_clear_drops_the_copy(served):
    model, params = served
    _generate(model, params, _batch(model, 9))
    kept = [weakref.ref(w) for w in _cast_leaves(model)]
    serve_step.jit_cache_clear()
    gc.collect()
    assert model._serve_weights is None
    assert all(r() is None for r in kept)
    assert serve_step.weight_cast_info() == {"casts": 0, "reuses": 0,
                                             "bypassed": 0}


def test_the_copy_goes_with_the_model():
    model, params = _model(ARCHS[0])
    _generate(model, params, _batch(model, 10))
    gone = [weakref.ref(model)] + [weakref.ref(w) for w in
                                   _cast_leaves(model)]
    del model
    gc.collect()
    assert all(r() is None for r in gone)


def _f32_weight_converts(text, params):
    """The converts, in lowered text, of a float32 array shaped as a
    weight matrix, one layer of a stack of them, or a transposed one (the
    tied head reads `embed.T`) into bfloat16."""
    shapes = set()
    for leaf in jax.tree_util.tree_leaves(params):
        if leaf.ndim >= 2 and leaf.dtype == jnp.float32:
            shapes |= {leaf.shape, leaf.shape[1:], leaf.shape[::-1]}
    dims = "|".join("x".join(map(str, s)) for s in shapes if len(s) >= 2)
    return re.findall(rf"\(tensor<({dims})xf32>\) -> tensor<\1xbf16>", text)


def test_the_decode_step_given_the_copy_converts_no_weight():
    """The converts leave the kept decode step: given the cast copy it
    converts no float32 weight, given the float32 tree it does."""
    model, params = _model(ARCHS[0])
    _generate(model, params, _batch(model, 11))
    decode = model._serve_steps["decode"]
    args = (jnp.ones((1,), jnp.int32), jnp.full((1,), S, jnp.int32),
            model.init_cache(1, S + NEW + 1))
    cast = model._serve_weights["weights"]
    assert _f32_weight_converts(decode.lower(cast, *args).as_text(),
                                params) == []
    assert _f32_weight_converts(decode.lower(params, *args).as_text(),
                                params)
