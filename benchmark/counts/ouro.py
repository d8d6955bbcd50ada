"""Operations and bytes of the OuroForCausalLM decoder (Ouro 1.4B / 2.6B): a
dense decoder (multi-head attention without grouping in the published sizes,
a gated MLP) whose layer stack runs `total_ut_steps` times over every token
with the same weights, keys and values kept per (pass, layer). Found by the
configuration's `reference` name (benchmark/flops.py `counts_for`). Same
rules as there: what the algorithm needs, from shapes alone; a multiply-add
is 2 operations; norms, rope, the exit gate (one row) are not counted.
"""

from __future__ import annotations

from benchmark.flops import head_flops_per_row


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def passes(cfg: dict) -> int:
    return int(cfg["total_ut_steps"])


def layer_linear_flops_per_token(cfg: dict) -> int:
    """q and o over all heads, k and v over the key/value heads, three
    matrices of the gated MLP: one layer, one pass."""
    H, n, nkv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], head_dim(cfg))
    return 2 * (2 * H * n * d + 2 * H * nkv * d
                + 3 * H * cfg["intermediate_size"])


def attn_score_flops(cfg: dict, context_tokens: int) -> int:
    """QK^T and PV of one layer in one pass for rows that attend to
    `context_tokens` keys in total."""
    return 2 * 2 * cfg["num_attention_heads"] * head_dim(cfg) * context_tokens


def serve_step_flops(cfg: dict, rows: int, context_tokens: int,
                     sampled_rows: int) -> int:
    """Model FLOPs of one serve step that held `rows` real rows attending to
    `context_tokens` keys in total and sampled `sampled_rows` of them: every
    layer in every pass, the head once."""
    per_pass = cfg["num_hidden_layers"] * (
        rows * layer_linear_flops_per_token(cfg)
        + attn_score_flops(cfg, context_tokens))
    return passes(cfg) * per_pass + sampled_rows * head_flops_per_row(cfg)


def calls_per_step(cfg: dict) -> int:
    """`paged_attention_gqa` calls in one serve step: one a layer a pass."""
    return passes(cfg) * cfg["num_hidden_layers"]


def paged_attention_gqa_call(cfg: dict, rows: int, context_tokens: int,
                             sequence_tokens: int, bytes_per_el: int = 2) -> dict:
    """One call (one layer of one pass) of paged attention over that entry's
    cache: every cached key and value of the step's sequences read once
    (`sequence_tokens`: sum over the step's distinct sequences of their cached
    tokens; a chunk's rows share them), q in, out. Of the work, whatever
    implements it: not of the padded rows x pages grid."""
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    flops = attn_score_flops(cfg, context_tokens)
    bytes_ = bytes_per_el * (sequence_tokens * 2 * nkv * d + 2 * rows * n * d)
    return {"flops": flops, "bytes": bytes_}
