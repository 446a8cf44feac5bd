"""The serving steps kept per model (`runtime/serve_step.py`): a later
`greedy_generate` on the same model and batch shape compiles nothing and
counts a hit, returns what freshly jitted steps return, keeps MoE counts
per batch, and a model's steps are its own and go with it."""
import copy
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.common import Options
from repro.models.model import build_model
from repro.runtime import serve_step

ARCHS = {"dense": "deepseek-7b", "moe": "deepseek-v2-lite"}
S, NEW = 12, 4

def _model(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg, Options(q_block=16, kv_block=16, moe_group=64))
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=sorted(ARCHS))
def served(request):
    return _model(ARCHS[request.param])


@pytest.fixture(scope="module")
def compiles():
    """The backend compiles of this process so far, in a list of one."""
    n = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: n.__setitem__(0, n[0] + (
            event == "/jax/core/compile/backend_compile_duration")))
    return n


def _batch(model, B, seed):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(seed), (B, S),
                                         2, model.cfg.vocab_size)}


def _generate(model, params, batch):
    return np.asarray(serve_step.greedy_generate(model, params, batch, NEW,
                                                 S + NEW + 1))


def _fresh(model, params, batch):
    """What freshly jitted steps give, step by step."""
    kw = {"moe_counts": True} if model.cfg.moe is not None else {}
    prefill = jax.jit(serve_step.make_prefill_step(model, **kw))
    decode = jax.jit(serve_step.make_decode_step(model, **kw))
    B = batch["tokens"].shape[0]
    tok, cache, *_ = serve_step.prefill_into_cache(
        model, params, batch, S + NEW + 1, prefill, decode)
    out = [tok]
    for t in range(S, S + NEW - 1):
        tok, cache, *_ = decode(params, tok, jnp.full((B,), t, jnp.int32),
                                cache)
        out.append(tok)
    return np.asarray(jnp.stack(out, 1))


def test_a_second_batch_compiles_nothing_and_hits(served, compiles):
    model, params = served
    _generate(model, params, _batch(model, 1, 1))
    serve_step.jit_cache_clear()
    _generate(model, params, _batch(model, 1, 2))     # rebuilt: a miss
    before = compiles[0]
    _generate(model, params, _batch(model, 1, 3))
    assert compiles[0] == before
    assert serve_step.jit_cache_info() == {"hits": 1, "misses": 1}


def test_kept_steps_give_the_fresh_steps_tokens(served):
    model, params = served
    serve_step.moe_counts_clear()
    for seed in (4, 5):                     # two batches in a row
        batch = _batch(model, 2, seed)
        np.testing.assert_array_equal(_generate(model, params, batch),
                                      _fresh(model, params, batch))
    records = serve_step.moe_counts()
    if model.cfg.moe is None:
        assert records == []
    else:                                   # one record a batch
        assert len(records) == 2
        n_moe = model.cfg.n_layers - model.cfg.moe.first_dense_layers
        assert all(r["calls"] == n_moe * NEW for r in records)


def test_a_new_batch_size_misses_once_then_hits(served):
    model, params = served
    _generate(model, params, _batch(model, 1, 6))
    serve_step.jit_cache_clear()
    for seed in (7, 8, 9):
        _generate(model, params, _batch(model, 3, seed))
    assert serve_step.jit_cache_info() == {"hits": 2, "misses": 1}


def test_two_models_keep_their_own_steps_and_tokens():
    (dense, pd), (moe, pm) = _model(ARCHS["dense"]), _model(ARCHS["moe"])
    bd, bm = _batch(dense, 1, 10), _batch(moe, 1, 10)
    for _ in range(2):                      # interleaved
        np.testing.assert_array_equal(_generate(dense, pd, bd),
                                      _fresh(dense, pd, bd))
        np.testing.assert_array_equal(_generate(moe, pm, bm),
                                      _fresh(moe, pm, bm))
    assert dense._serve_steps["prefill"] is not moe._serve_steps["prefill"]
    assert dense._serve_steps["decode"] is not moe._serve_steps["decode"]


def test_a_copied_model_builds_steps_of_its_own():
    model, params = _model(ARCHS["dense"])
    batch = _batch(model, 1, 13)
    serve_step.jit_cache_clear()
    want = _generate(model, params, batch)
    twin = copy.copy(model)                 # shares the first's attributes
    np.testing.assert_array_equal(_generate(twin, params, batch), want)
    assert twin._serve_steps["decode"] is not model._serve_steps["decode"]
    assert serve_step.jit_cache_info() == {"hits": 0, "misses": 2}


def test_a_dropped_model_frees_its_steps():
    model, params = _model(ARCHS["dense"])
    _generate(model, params, _batch(model, 1, 11))
    gone = [weakref.ref(model), weakref.ref(model._serve_steps["prefill"]),
            weakref.ref(model._serve_steps["decode"])]
    del model
    gc.collect()
    assert [r() for r in gone] == [None, None, None]


def test_clear_resets_the_counts(served):
    model, params = served
    _generate(model, params, _batch(model, 1, 12))
    assert sum(serve_step.jit_cache_info().values()) > 0
    serve_step.jit_cache_clear()
    assert serve_step.jit_cache_info() == {"hits": 0, "misses": 0}
