"""Operations and bytes of the DeepseekV3ForCausalLM decoder (Moonlight,
DeepSeek-V3): MLA attention, a leading run of dense layers, then routed and
shared experts. Found by the configuration's `reference` name
(benchmark/flops.py `counts_for`). Same rules as there: what the algorithm
needs, from shapes alone; a multiply-add is 2 operations.
"""

from __future__ import annotations

from benchmark.flops import head_flops_per_row


def attn_linear_flops_per_token(cfg: dict) -> int:
    """q, kv-down, kv-up (or its absorbed halves: the same count) and o."""
    H, n = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    assert cfg.get("q_lora_rank") is None, "q-lora is not counted here yet"
    return 2 * (H * n * (dn + dr) + H * (r + dr) + r * n * (dn + dv)
                + n * dv * H)


def mlp_flops_per_token(cfg: dict, expert_layer: bool) -> int:
    H = cfg["hidden_size"]
    if not expert_layer:
        return 2 * 3 * H * cfg["intermediate_size"]
    Im = cfg["moe_intermediate_size"]
    routed = cfg["num_experts_per_tok"] * 2 * 3 * H * Im
    shared = 2 * 3 * H * Im * cfg["n_shared_experts"]
    router = 2 * H * cfg["n_routed_experts"]
    return routed + shared + router


def layers_linear_flops_per_token(cfg: dict) -> int:
    """Every matrix product of the decoder body for one token (no attention
    scores, no head)."""
    L, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return (L * attn_linear_flops_per_token(cfg)
            + k * mlp_flops_per_token(cfg, False)
            + (L - k) * mlp_flops_per_token(cfg, True))


def attn_score_flops(cfg: dict, context_tokens: int) -> int:
    """QK^T and PV of all layers for rows that attend to `context_tokens`
    keys in total (the sum over rows of each row's context length), over the
    published heads (nope + rope wide keys, v wide values)."""
    n = cfg["num_attention_heads"]
    per_key = 2 * n * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                       + cfg["v_head_dim"])
    return cfg["num_hidden_layers"] * per_key * context_tokens


def serve_step_flops(cfg: dict, rows: int, context_tokens: int,
                     sampled_rows: int) -> int:
    """Model FLOPs of one serve step that held `rows` real rows attending to
    `context_tokens` keys in total and sampled `sampled_rows` of them."""
    return (rows * layers_linear_flops_per_token(cfg)
            + attn_score_flops(cfg, context_tokens)
            + sampled_rows * head_flops_per_row(cfg))


def paged_mla_call(cfg: dict, rows: int, context_tokens: int,
                   sequence_tokens: int, bytes_per_el: int = 2) -> dict:
    """One call (one layer) of latent-space paged MLA attention: queries
    already folded into the latent space (rows, heads, kv_lora + rope), keys
    and values both the cached latent. `context_tokens`: sum over rows of the
    row's context; `sequence_tokens`: sum over the step's distinct sequences
    of the cached tokens that have to be read (a chunk's rows share them)."""
    n, r, dr = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                cfg["qk_rope_head_dim"])
    flops = 2 * n * ((r + dr) + r) * context_tokens
    bytes_ = bytes_per_el * (
        sequence_tokens * (r + dr)        # the cache, once per sequence
        + rows * n * (r + dr)             # q in
        + rows * n * r                    # out
    )
    return {"flops": flops, "bytes": bytes_}
