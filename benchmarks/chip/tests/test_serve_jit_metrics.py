"""The reader of the serving steps' kept-program counter: what
`jit_hit_share.serve` counts, and when it reads nothing."""
from types import SimpleNamespace

import pytest

import registry

HIT_SHARE = registry.metric("jit_hit_share.serve")
CTX = SimpleNamespace(trace=None, work=SimpleNamespace())


@pytest.mark.parametrize("hits, misses, share", [
    (20, 1, 100 * 20 / 21),     # one miss: the first warm-up batch
    (0, 3, 0.0),
    (5, 0, 100.0)])
def test_hit_share_is_hits_over_calls(monkeypatch, hits, misses, share):
    from repro.runtime import serve_step

    monkeypatch.setattr(serve_step, "jit_cache_info",
                        lambda: {"hits": hits, "misses": misses})
    assert HIT_SHARE.read(CTX) == pytest.approx(share)


def test_a_program_without_the_counter_reads_nothing(monkeypatch):
    from repro.runtime import serve_step

    monkeypatch.delattr(serve_step, "jit_cache_info")
    assert HIT_SHARE.read(CTX) is None


def test_hit_share_reads_the_program_itself():
    import jax

    from repro.configs import get_config
    from repro.models.common import Options
    from repro.models.model import build_model
    from repro.runtime import serve_step

    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg, Options(q_block=16, kv_block=16))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (1, 8), 2,
                                          cfg.vocab_size)}
    serve_step.jit_cache_clear()
    assert HIT_SHARE.read(CTX) is None                # no call yet
    for _ in range(4):
        serve_step.greedy_generate(model, params, batch, 2, 11)
    assert HIT_SHARE.read(CTX) == 75.0
