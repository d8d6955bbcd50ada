"""Operations and bytes of the tests' Mistral-shaped dense decoder
(`tiny_dense`): grouped-query attention and a gated MLP in every layer. What
the algorithm needs, from shapes alone; a multiply-add is 2 operations; a
bias's additions are not counted."""

from __future__ import annotations

from benchmark.flops import head_flops_per_row


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_linear_flops_per_token(cfg: dict) -> int:
    """q and o over all heads, k and v over the key/value heads, three
    matrices of the gated MLP."""
    H, n, nkv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], head_dim(cfg))
    return 2 * (2 * H * n * d + 2 * H * nkv * d
                + 3 * H * cfg["intermediate_size"])


def serve_step_flops(cfg: dict, rows: int, context_tokens: int,
                     sampled_rows: int) -> int:
    """Model FLOPs of one serve step that held `rows` real rows attending to
    `context_tokens` keys in total and sampled `sampled_rows` of them."""
    scores = 2 * 2 * cfg["num_attention_heads"] * head_dim(cfg) * context_tokens
    return (cfg["num_hidden_layers"]
            * (rows * layer_linear_flops_per_token(cfg) + scores)
            + sampled_rows * head_flops_per_row(cfg))


def paged_gqa_call(cfg: dict, rows: int, context_tokens: int,
                   sequence_tokens: int, bytes_per_el: int = 2) -> dict:
    """One call (one layer) of paged grouped-query attention: every cached
    key and value of the step's sequences read once, q in, out."""
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    flops = 2 * 2 * n * d * context_tokens
    bytes_ = bytes_per_el * (sequence_tokens * 2 * nkv * d + 2 * rows * n * d)
    return {"flops": flops, "bytes": bytes_}
