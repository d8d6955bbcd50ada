"""Operations and bytes that the ALGORITHM needs, from shapes alone. These are
the numerators of every `*_mfu*` and `*_roofline` metric; they never look at
what the program's kernels pad, recompute or read twice. A multiply-add counts
as 2 operations. `cfg` is a configuration file's dict (Hugging Face keys).
"""

from __future__ import annotations


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


def head_flops_per_row(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


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


def roofline(flops: float, bytes_: float, peaks: dict) -> dict:
    """Least seconds the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
