"""How DeepSeek-V2-Lite's published config maps onto the program's
registered config: the fields set from the file's keys, the expert share
this chip holds, and the architecture the program has to run for the
comparison to mean anything (latent attention without a q-LoRA, YaRN
rotary scaling, a softmax router whose greedy top-k weights are used as
published, a held share of the routed experts, an untied head).  A
program that lacks any of these is refused here, before anything is
built.  The serving kind calls `program_config` and knows nothing of the
model.
"""
from __future__ import annotations

import dataclasses

# the program's config fields, from the published config's keys
PROGRAM_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads",
                "moe_intermediate_size": "d_ff", "vocab_size": "vocab_size",
                "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta"}
NEEDS = {"model": {"mla", "moe", "yarn"},
         "moe": {"norm_topk_prob", "routed_scaling_factor", "first_expert",
                 "n_held"}}


def _fields(obj) -> set:
    return {f.name for f in dataclasses.fields(obj)}


def program_config(cfg: dict, registered):
    """`registered` (the program's config of `cfg["program_arch"]`) held
    to the sizes in the file, holding experts held_first_expert ..
    + n_routed_experts - 1 of the published count; an error if the
    program cannot run the published architecture."""
    missing = NEEDS["model"] - _fields(registered)
    if not missing and registered.moe is not None:
        missing = NEEDS["moe"] - _fields(registered.moe)
    if missing or registered.mla is None or registered.moe is None:
        raise ValueError(f"program config {registered.name} lacks "
                         f"{sorted(missing) or 'latent attention and MoE'}")
    if (cfg["q_lora_rank"] is not None or cfg["scoring_func"] != "softmax"
            or cfg["topk_method"] != "greedy" or cfg["n_group"] != 1
            or cfg["moe_layer_freq"] != 1 or cfg["tie_word_embeddings"]
            or cfg["rope_scaling"]["type"] != "yarn"):
        raise ValueError("the file is not the architecture this binding "
                         "maps")
    rs = cfg["rope_scaling"]
    want = {PROGRAM_KEYS[k]: cfg[k] for k in PROGRAM_KEYS}
    want["mla"] = dataclasses.replace(
        registered.mla, q_lora_rank=0, kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"])
    want["moe"] = dataclasses.replace(
        registered.moe, n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        d_expert=cfg["moe_intermediate_size"], dense_residual=False,
        first_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        first_expert=cfg["deployment"]["held_first_expert"],
        n_held=cfg["n_routed_experts"])
    want["yarn"] = dataclasses.replace(
        registered.yarn, factor=float(rs["factor"]),
        original_max_position=rs["original_max_position_embeddings"],
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]))
    want.update(cfg.get("program_overrides", {}))
    pc = registered.replace(**want)
    if pc.tie_embeddings or pc.act != cfg["hidden_act"] or pc.qkv_bias:
        raise ValueError(f"program config {pc.name} is not the published "
                         f"architecture")
    return pc
