"""Model operations of one qwen2-vl-2b request at its published widths
(12 query heads, as published, not the 16 the program pads to): every
matmul of the layers, attention over the causal context, and the output
head where a token's logits are needed."""


def _per_token(cfg: dict) -> tuple:
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    G = cfg["num_key_value_heads"]
    hd = D // H
    F = cfg["intermediate_size"]
    L = cfg["num_hidden_layers"]
    layer = D * H * hd + 2 * D * G * hd + H * hd * D + 3 * D * F
    return 2.0 * L * layer, 2.0 * D * cfg["vocab_size"], 4.0 * L * H * hd


def request_flops(cfg: dict, prompt_len: int, max_new: int) -> float:
    """Prefill of the prompt (logits at its last position only), then
    max_new - 1 decode steps, each with its logits."""
    body, head, attn_per_ctx = _per_token(cfg)
    S = prompt_len
    prefill = S * body + head + attn_per_ctx * S * (S + 1) / 2
    decode = sum(body + head + attn_per_ctx * (S + t + 1)
                 for t in range(max_new - 1))
    return prefill + decode
