"""Model operations of one DeepSeek-V2-Lite request at its published
widths, on the chip that holds `n_routed_experts` of the published
routed experts: every matmul of the layers, attention over the causal
context, and the output head where a token's logits are needed.

Attention is counted as the model defines it: each token's k and v
up-projections once, when the token enters, and per query and context
token the 192-wide score and the 128-wide value product of each head;
not the naive decode path's re-expansion of the whole latent cache every
step.  Routed experts are counted at their expected share here:
`num_experts_per_tok` x n_routed_experts / published experts a token
(6 x 16 / 64 = 1.5), each a gated MLP of `moe_intermediate_size`."""


def _per_token(cfg: dict) -> tuple:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, L = cfg["kv_lora_rank"], cfg["num_hidden_layers"]
    n_dense = cfg["first_k_dense_replace"]
    F = cfg["moe_intermediate_size"]
    E = cfg["published"]["n_routed_experts"]
    routed = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / E
    attn = D * H * (dn + dr) + D * (r + dr) + r * H * dn + r * H * dv \
        + H * dv * D
    dense = 3 * D * cfg["intermediate_size"]
    moe = D * E + (routed + cfg["n_shared_experts"]) * 3 * D * F
    body = L * attn + n_dense * dense + (L - n_dense) * moe
    per_ctx = 2.0 * L * H * (dn + dr + dv)
    return 2.0 * body, 2.0 * D * cfg["vocab_size"], per_ctx


def request_flops(cfg: dict, prompt_len: int, max_new: int) -> float:
    """Prefill of the prompt (logits at its last position only), then
    max_new - 1 decode steps, each with its logits."""
    body, head, per_ctx = _per_token(cfg)
    S = prompt_len
    prefill = S * body + head + per_ctx * S * (S + 1) / 2
    decode = sum(body + head + per_ctx * (S + t + 1)
                 for t in range(max_new - 1))
    return prefill + decode
