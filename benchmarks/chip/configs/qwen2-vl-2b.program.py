"""How qwen2-vl-2b's published config maps onto the program's registered
config: the fields set from the file's keys, and the architecture the
program has to run for the comparison to mean anything (M-RoPE, q/k/v
biases, an output head tied to the embedding).  The serving kind calls
`program_config` and knows nothing of the model.
"""
from __future__ import annotations

# the program's config fields, from the published config's keys
PROGRAM_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads",
                "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta"}


def program_config(cfg: dict, registered):
    """`registered` (the program's config of `cfg["program_arch"]`) held
    to the sizes in the file; an error if it is not the published
    architecture."""
    want = {PROGRAM_KEYS[k]: cfg[k] for k in PROGRAM_KEYS}
    want["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
    want["mrope_sections"] = tuple(cfg["rope_scaling"]["mrope_section"])
    want.update(cfg.get("program_overrides", {}))
    pc = registered.replace(**want)
    if not (pc.mrope and pc.qkv_bias and pc.tie_embeddings
            and pc.act == cfg["hidden_act"]):
        raise ValueError(f"program config {pc.name} is not the published "
                         f"architecture")
    return pc
