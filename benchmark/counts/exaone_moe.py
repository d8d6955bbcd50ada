"""Operations and bytes of the ExaoneMoeForCausalLM decoder (K-EXAONE), whole
or as one chip's share of its experts: grouped-query attention in every layer,
with a window in the `sliding_attention` layers; leading dense layers, then
routed and shared experts. Found by the configuration's `reference` name
(benchmark/flops.py `counts_for`). Same rules as there: what the algorithm
needs, from shapes alone; a multiply-add is 2 operations; norms and the rotary
embedding are not counted.

The share: `num_experts` experts are HELD of the `router_num_experts` the
router scores (absent: all are held). A token's `num_experts_per_tok` choices
fall on a held expert with probability held / routed each, so a row's routed
work here is that share of the published top-k: what this chip computes, not
what the whole deployment would.
"""

from __future__ import annotations

from benchmark.flops import head_flops_per_row


def layer_windows(cfg: dict) -> list:
    """Per layer its window, 0 for full attention."""
    types, w = cfg.get("layer_types"), cfg.get("sliding_window")
    L = cfg["num_hidden_layers"]
    if not w or not types:
        return [0] * L
    assert len(types) == L, (len(types), L)
    return [int(w) if t == "sliding_attention" else 0 for t in types]


def full_layers(cfg: dict) -> int:
    return layer_windows(cfg).count(0)


def window_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - full_layers(cfg)


def dense_layers(cfg: dict) -> int:
    types = cfg.get("mlp_layer_types")
    if types is None:
        return int(cfg.get("first_k_dense_replace", 0))
    return sum(t == "dense" for t in types)


def held_share(cfg: dict) -> float:
    return cfg["num_experts"] / cfg.get("router_num_experts", cfg["num_experts"])


def attn_linear_flops_per_token(cfg: dict) -> int:
    """q and o over all heads, k and v over the key/value heads."""
    H, n, nkv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return 2 * (2 * H * n * d + 2 * H * nkv * d)


def mlp_flops_per_token(cfg: dict, expert_layer: bool) -> float:
    H = cfg["hidden_size"]
    if not expert_layer:
        return 2 * 3 * H * cfg["intermediate_size"]
    Im = cfg["moe_intermediate_size"]
    routed = cfg["num_experts_per_tok"] * held_share(cfg) * 2 * 3 * H * Im
    shared = 2 * 3 * H * Im * cfg["num_shared_experts"]
    router = 2 * H * cfg.get("router_num_experts", cfg["num_experts"])
    return routed + shared + router


def layers_linear_flops_per_token(cfg: dict) -> float:
    """Every matrix product of the decoder body for one token (no attention
    scores, no head), the routed experts at the held share."""
    L, k = cfg["num_hidden_layers"], dense_layers(cfg)
    return (L * attn_linear_flops_per_token(cfg)
            + k * mlp_flops_per_token(cfg, False)
            + (L - k) * mlp_flops_per_token(cfg, True))


def window_context_tokens(cfg: dict, rows: int, context_tokens: int) -> int:
    """Keys the rows of a window layer attend to in all: no row more than
    the window (a bound from above where some contexts are shorter)."""
    return min(context_tokens, rows * int(cfg["sliding_window"] or 0))


def attn_score_flops(cfg: dict, context_tokens: int) -> int:
    """QK^T and PV of ONE attention layer for rows that attend to
    `context_tokens` keys in total."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * context_tokens


def serve_step_flops(cfg: dict, rows: int, context_tokens: int,
                     sampled_rows: int) -> float:
    """Model FLOPs of one serve step that held `rows` real rows attending to
    `context_tokens` keys in total (in a full layer; a window layer's rows
    stop at the window) and sampled `sampled_rows` of them."""
    scores = (full_layers(cfg) * attn_score_flops(cfg, context_tokens)
              + window_layers(cfg) * attn_score_flops(
                  cfg, window_context_tokens(cfg, rows, context_tokens)))
    return (rows * layers_linear_flops_per_token(cfg) + scores
            + sampled_rows * head_flops_per_row(cfg))


def calls_per_step(cfg: dict) -> int:
    """`paged_attention_gqa` calls in one serve step: one a FULL layer. (A
    window layer's call runs the same kernel body under the name
    `paged_attention_window_gqa`: `window_calls_per_step`.)"""
    return full_layers(cfg)


def window_calls_per_step(cfg: dict) -> int:
    return window_layers(cfg)


def paged_attention_gqa_call(cfg: dict, rows: int, context_tokens: int,
                             sequence_tokens: int, bytes_per_el: int = 2,
                             window: int | None = None) -> dict:
    """One call (one attention layer) of paged attention: the cached keys
    and values the rows attend to read once, q in, out. Of the work, whatever
    implements it: not of a padded rows x pages grid. With `window` the call
    is a window layer's: `context_tokens` is then the in-window keys the rows
    attend to and `sequence_tokens` the tokens of the live in-window blocks
    (the pages a window's keys lie in: the cache's own granularity), so the
    least work is the in-window bytes, not the context's."""
    n, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    if window:
        context_tokens = min(context_tokens, rows * window)
    flops = attn_score_flops(cfg, context_tokens)
    bytes_ = bytes_per_el * (sequence_tokens * 2 * nkv * d + 2 * rows * n * d)
    return {"flops": flops, "bytes": bytes_}
