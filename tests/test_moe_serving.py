"""The served MoE model path (DeepSeek-V2-Lite): YaRN rotary scaling and
its softmax factor, top-k weights as published, dropless serving through
the `moe_gmm` kernel, a held share of the routed experts, and agreement
with the benchmark's plain float32 reference.

The reference (`benchmarks/chip/configs/deepseek-v2-lite.py`) imports
nothing of the program; it is loaded here by path, with its published
config at small widths (`SMALL`)."""
import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe
from repro.models.common import Options
from repro.models.model import build_model
from repro.models.rope import (rope_angles, rope_inv_freq, yarn_mscale,
                               yarn_softmax_factor)
from repro.runtime import serve_step

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
# the published keys at small widths: 3 layers (one dense), 16 routed
# experts of which 4 are held here, top-6, YaRN as published
SMALL = dict(hidden_size=128, num_attention_heads=4, qk_nope_head_dim=32,
             qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64,
             num_hidden_layers=3, intermediate_size=256,
             moe_intermediate_size=64, n_routed_experts=4,
             published={"n_routed_experts": 16}, vocab_size=2048,
             program_overrides={"vocab_pad_multiple": 16})


def _load(name):
    path = CHIP / "configs" / name
    spec = importlib.util.spec_from_file_location(
        "chip_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def small():
    """(reference module, its config dict, the program's model, the
    reference's bf16 weights in the program's layout)."""
    ref = _load("deepseek-v2-lite.py")
    binding = _load("deepseek-v2-lite.program.py")
    cfg = {**json.loads((CHIP / "configs" / "deepseek-v2-lite.json")
                        .read_text()), **SMALL}
    pc = binding.program_config(cfg, get_config(cfg["program_arch"]))
    model = build_model(pc, Options(q_block=16, kv_block=16, moe_group=64))
    key = jax.random.PRNGKey(11)
    params = ref.make_weights(jax.eval_shape(model.init, key), key)
    return ref, cfg, model, params


# ------------------------------------------------------------------ YaRN
def test_yarn_frequencies_match_the_published_formulas():
    """DeepSeek-V2-Lite's rope dim 64: pairs 0-10 keep the base frequency,
    pairs 23-31 are divided by 40, and the pairs between are ramped."""
    yarn = get_config("deepseek-v2-lite").yarn
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # correction dims: floor(64 ln(4096 / (32 2pi)) / (2 ln 1e4)) = 10 and
    # ceil(64 ln(4096 / 2pi) / (2 ln 1e4)) = 23
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base * (1 - ramp) + base / 40 * ramp
    got = rope_inv_freq(64, 10000.0, yarn)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(rope_inv_freq(64, 10000.0), base, rtol=1e-6)


def test_yarn_mscale_factors():
    """The softmax scale gains mscale(40, 0.707)^2 = (0.1 0.707 ln 40 +
    1)^2, about 1.590; sin and cos are scaled by mscale / mscale_all_dim,
    which is 1 where the two are equal (DeepSeek-V2-Lite)."""
    yarn = get_config("deepseek-v2-lite").yarn
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m)
    assert yarn_softmax_factor(yarn) == pytest.approx(m * m)
    assert yarn_softmax_factor(yarn) == pytest.approx(1.590, abs=1e-3)
    assert yarn_softmax_factor(None) == 1.0
    pos = jnp.arange(5)
    s1, c1 = rope_angles(pos, 64, 1e4, yarn)
    other = dataclasses.replace(yarn, mscale=1.0)
    s2, c2 = rope_angles(pos, 64, 1e4, other)
    ratio = yarn_mscale(40, 1.0) / m
    np.testing.assert_allclose(s2, s1 * ratio, rtol=1e-6)
    np.testing.assert_allclose(c2, c1 * ratio, rtol=1e-6)


# --------------------------------------------------------------- routing
def _moe_cfg(**moe_kw):
    cfg = get_config("deepseek-v2-lite").reduced()
    return cfg.replace(moe=dataclasses.replace(cfg.moe, **moe_kw))


def test_topk_weights_unnormalised_where_published():
    """norm_topk_prob false: the top-k weights are the router's softmax
    probabilities times routed_scaling_factor; true: they sum to 1."""
    cfg = _moe_cfg(top_k=2, norm_topk_prob=False, routed_scaling_factor=1.5)
    p = moe.init_moe(jax.random.PRNGKey(0), cfg, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    probs, vals, idx = moe._route(p, x, cfg.moe)
    top = -np.sort(-np.asarray(probs), -1)[:, :2]
    np.testing.assert_allclose(vals, 1.5 * top, rtol=1e-6)
    assert float(jnp.max(jnp.sum(vals, -1))) < 1.5
    _, vals_n, _ = moe._route(p, x, dataclasses.replace(
        cfg.moe, norm_topk_prob=True))
    np.testing.assert_allclose(jnp.sum(vals_n, -1), 1.0, rtol=1e-6)


def _dense_moe(p, x, cfg):
    """Every token's routed sum (all experts held) plus the shared FFN,
    written per token and expert in float32."""
    m = cfg.moe
    _, vals, idx = moe._route(p, x, m)
    act = jax.nn.silu
    hi = jax.lax.Precision.HIGHEST
    y = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(m.top_k):
            e = int(idx[t, j])
            h = act(jnp.dot(x[t], p["w1"][e], precision=hi)) \
                * jnp.dot(x[t], p["w3"][e], precision=hi)
            y[t] += float(vals[t, j]) * np.asarray(
                jnp.dot(h, p["w2"][e], precision=hi))
    hs = act(jnp.dot(x, p["ws1"], precision=hi)) \
        * jnp.dot(x, p["ws3"], precision=hi)
    return y + np.asarray(jnp.dot(hs, p["ws2"], precision=hi))


def test_serving_drops_no_token_when_all_route_to_one_expert():
    """128 tokens in groups of 64 (the serving model option), every one
    routed first to expert 0: capacity would keep 24 of each group's 64;
    the serving path computes all of them."""
    cfg = _moe_cfg(top_k=2)
    p = moe.init_moe(jax.random.PRNGKey(0), cfg, 0)
    # expert 0's logit is 50 x feature 0, which every token sets to 1
    p["router"] = p["router"].at[:, 0].set(0.0).at[0, 0].set(50.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, cfg.d_model))
    x = x.at[..., 0].set(1.0)
    _, _, idx = moe._route(p, x.reshape(128, -1), cfg.moe)
    assert bool(jnp.all(idx[:, 0] == 0))
    assert moe._capacity(64, 2, cfg.moe.capacity_factor,
                         cfg.moe.n_experts) < 64
    y, counts = moe.apply_moe_dropless(p, x, cfg)
    want = _dense_moe(p, x.reshape(128, -1), cfg)
    np.testing.assert_allclose(np.asarray(y).reshape(128, -1), want,
                               rtol=1e-4, atol=1e-4)
    assert counts.tolist() == [128 * 2, int(jnp.unique(idx).size), 1]


def test_held_shares_add_up_to_the_uncut_reference_layer(small):
    """Four shares of 2 of 8 routed experts: each share's routed part,
    plus the shared experts once, add up to the reference's layer with
    all 8 held.  In float32 throughout, so the sum is exact to rounding
    (1e-5 of the layer's scale)."""
    ref = small[0]
    cfg = _moe_cfg(top_k=6)
    m = cfg.moe
    p = moe.init_moe(jax.random.PRNGKey(4), cfg, 0)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.d_model))
    parts = []
    for first in range(0, 8, 2):
        share = cfg.replace(moe=dataclasses.replace(m, first_expert=first,
                                                    n_held=2))
        sp = dict(p, w1=p["w1"][first:first + 2], w3=p["w3"][first:first + 2],
                  w2=p["w2"][first:first + 2])
        y, counts = moe.apply_moe_dropless(sp, x, share)
        shared = moe._shared(sp, x, share)
        parts.append(np.asarray(y - shared))
    total = sum(parts) + np.asarray(moe._shared(p, x, cfg))
    rcfg = {"norm_topk_prob": m.norm_topk_prob,
            "routed_scaling_factor": m.routed_scaling_factor}
    z = {"k": 6, "held": 8, "first": 0}
    whole = np.asarray(ref._moe(x, p, rcfg, z, False))
    np.testing.assert_allclose(total, whole, atol=1e-5 * np.abs(whole).max())


# ----------------------------------------------------- serving, counter
def test_non_moe_generate_is_unchanged_and_counts_nothing():
    """A dense model's greedy_generate returns what the two jitted steps
    give, step by step, as a device array, and records no MoE counts."""
    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg, Options(q_block=16, kv_block=16))
    params = model.init(jax.random.PRNGKey(0))
    B, S, new = 2, 16, 4
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (B, S), 2,
                                          cfg.vocab_size)}
    before = len(serve_step.moe_counts())
    out = serve_step.greedy_generate(model, params, batch, new, S + new + 1)
    prefill = jax.jit(serve_step.make_prefill_step(model))
    decode = jax.jit(serve_step.make_decode_step(model))
    tok, cache = serve_step.prefill_into_cache(model, params, batch,
                                               S + new + 1, prefill, decode)
    want = [tok]
    for t in range(S, S + new - 1):
        tok, cache = decode(params, tok, jnp.full((B,), t, jnp.int32), cache)
        want.append(tok)
    assert isinstance(out, jax.Array)
    np.testing.assert_array_equal(out, jnp.stack(want, 1))
    assert len(serve_step.moe_counts()) == before


def test_moe_generate_records_its_counts(small):
    """One record a batch, summed over prefill and decode: 6 routed rows
    a token, of which the held experts' land here; one call per MoE layer
    and step."""
    _, cfg, model, params = small
    B, S, new = 1, 12, 3
    toks = jax.random.randint(jax.random.PRNGKey(8), (B, S), 2, 2048)
    serve_step.moe_counts_clear()
    out = serve_step.greedy_generate(model, params, {"tokens": toks}, new,
                                     S + new + 1)
    assert isinstance(out, np.ndarray) and out.shape == (B, new)
    (rec,) = serve_step.moe_counts()
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert rec["calls"] == n_moe * new
    assert 0 < rec["rows"] <= n_moe * 6 * (S + new - 1)
    assert 0 < rec["experts_hit"] <= n_moe * new * 4


# ------------------------------------------------- against the reference
def test_prefill_then_decode_matches_the_reference(small):
    """Prefill a prompt, then decode through the cache token by token
    (teacher-forced): the logits at each position agree with the
    reference's full float32 forward.  The program runs bf16 weights and
    activations: at this size the largest gap read 0.084-0.090 over three
    weight seeds, of logits whose spread is 1.0; the reference's own fp8
    control moves them by 1.0-1.3.  The tolerance, 0.25, lies between."""
    ref, cfg, model, params = small
    B, S, new = 2, 20, 6
    toks = jax.random.randint(jax.random.PRNGKey(9), (B, S + new), 2, 2048)
    V = cfg["vocab_size"]
    lg, cache, _ = model.forward(params, {"tokens": toks[:, :S]},
                                 mode="prefill")
    big = model.init_cache(B, S + new)
    cache = jax.tree_util.tree_map(
        lambda b, s: b.at[tuple(slice(0, n) for n in s.shape)].set(
            s.astype(b.dtype)), big, cache)
    got = [lg[:, :V]]
    decode = jax.jit(model.decode_step)
    for t in range(S, S + new - 1):
        lg, cache = decode(params, toks[:, t], jnp.full((B,), t, jnp.int32),
                           cache)
        got.append(lg[:, :V])
    got = jnp.stack(got, 1).astype(jnp.float32)
    positions = jnp.arange(S - 1, S + new - 1)
    want = ref.logits_at(params, toks, positions, cfg)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 0.25, err
    fp8 = ref.logits_at(params, toks, positions, cfg, fp8=True)
    assert float(jnp.max(jnp.abs(fp8 - want))) > 0.25
