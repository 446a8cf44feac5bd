"""What decides `correct` for the served MoE model, at a size a CPU holds:
`serve.deepseek-v2-lite.chat-2k` run through the harness (set-up,
window, finish, check) as in test_correct.py, with the same published
keys at small widths and 4 of 16 experts held.  A sound run passes; the
fp8 control, a decode step that keeps its cache, and the held experts'
part of one expert left out each come out not correct.
"""
import jax.numpy as jnp
import pytest

from test_correct import run_tiny

CELL = "serve.deepseek-v2-lite.chat-2k"
MOE_CFG = dict(hidden_size=128, num_attention_heads=4, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64,
               num_hidden_layers=3, intermediate_size=256,
               moe_intermediate_size=64, n_routed_experts=4,
               published={"n_routed_experts": 16}, vocab_size=2048,
               program_overrides={"vocab_pad_multiple": 16},
               # at this size sound runs read 0.01 to 0.06, the control
               # 0.45 and the faults 0.5 and more
               limits={"served_token_logit_gap": 0.2})
MOE = dict(prompt_len=24, max_new=6, rate_per_s=4.0, check_requests=4,
           warmup_requests=1)


def serve_control(d):
    d.use_control()


def cache_unchanged(d):
    from repro.runtime import serve_step

    real = serve_step.make_decode_step

    def make_decode_step(model, **kw):
        step = real(model, **kw)

        def stale(params, tokens, positions, cache):
            tok, _new, *rest = step(params, tokens, positions, cache)
            return (tok, cache, *rest)

        return stale

    d._restore = (serve_step, "make_decode_step", real)
    serve_step.make_decode_step = make_decode_step


def expert_left_out(d):
    """The first held expert's rows come back as zeros."""
    from repro.models import moe

    real = moe.moe_gmm

    def moe_gmm(x, w1, w3, w2, sizes, *a, **kw):
        out = real(x, w1, w3, w2, sizes, *a, **kw)
        first = jnp.arange(x.shape[0]) < sizes[0]
        return jnp.where(first[:, None], 0, out)

    d._restore = (moe, "moe_gmm", real)
    moe.moe_gmm = moe_gmm


def _serve(patch=None):
    holder = {}

    def wrapped(d):
        holder["d"] = d
        if patch is not None:
            patch(d)

    try:
        return run_tiny(CELL, MOE_CFG, MOE, patch=wrapped, seconds=1.5)
    finally:
        restore = getattr(holder.get("d"), "_restore", None)
        if restore is not None:
            setattr(*restore)


def test_moe_serve_sound_run_is_correct():
    res = _serve()
    assert res["correct"], res["checks"]
    assert res["attempted"] == 6 and res["failed"] == 0


@pytest.mark.parametrize("fault", [serve_control, cache_unchanged,
                                   expert_left_out])
def test_moe_serve_fault_is_not_correct(fault):
    res = _serve(fault)
    assert not res["correct"], (fault.__name__, res["checks"])
