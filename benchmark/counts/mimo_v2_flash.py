"""Operations and bytes of the MiMoV2FlashForCausalLM decoder (MiMo-V2-Flash),
whole or as one chip's share of its experts: grouped-query attention in every
layer at one of two geometries, the full layers' (`num_key_value_heads`) and
the window layers' (`swa_*`, a window of `sliding_window` tokens), both with
keys `head_dim` wide and values `v_head_dim` wide; leading dense layers, then
routed experts. Found by the configuration's `reference` name
(benchmark/flops.py `counts_for`). Same rules as there: what the algorithm
needs, from shapes alone; a multiply-add is 2 operations; norms, the rotary
embedding, the value scale and the sinks (one logit a head and row) are not
counted. Keys are counted at their own width, not at the whole lane tiles a
page stores them at.

The share: `n_routed_experts` experts are HELD of the `router_num_experts`
the router scores (absent: all are held). A token's `num_experts_per_tok`
choices fall on a held expert with probability held / routed each, so a row's
routed work here is that share of the published top-k: what this chip
computes, not what the whole deployment would.
"""

from __future__ import annotations

from benchmark.flops import head_flops_per_row


def geometry(cfg: dict, window: bool) -> tuple:
    """(heads, key/value heads, key width, value width) of a kind."""
    d = int(cfg["head_dim"])
    n, m = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dv = int(cfg.get("v_head_dim") or d)
    if not window:
        return n, m, d, dv
    return (int(cfg.get("swa_num_attention_heads") or n),
            int(cfg.get("swa_num_key_value_heads") or m), d,
            int(cfg.get("swa_v_head_dim") or dv))


def window_layers(cfg: dict) -> int:
    return sum(cfg["hybrid_layer_pattern"])


def full_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - window_layers(cfg)


def dense_layers(cfg: dict) -> int:
    return list(cfg["moe_layer_freq"]).count(0)


def held_share(cfg: dict) -> float:
    held = cfg["n_routed_experts"]
    return held / cfg.get("router_num_experts", held)


def attn_linear_flops_per_token(cfg: dict, window: bool) -> int:
    """q over all heads, k and v over the key/value heads, o back."""
    H = cfg["hidden_size"]
    n, m, d, dv = geometry(cfg, window)
    return 2 * (H * n * d + H * m * d + H * m * dv + n * dv * H)


def mlp_flops_per_token(cfg: dict, expert_layer: bool) -> float:
    H = cfg["hidden_size"]
    if not expert_layer:
        return 2 * 3 * H * cfg["intermediate_size"]
    Im = cfg["moe_intermediate_size"]
    routed = cfg["num_experts_per_tok"] * held_share(cfg) * 2 * 3 * H * Im
    shared = 2 * 3 * H * Im * int(cfg.get("n_shared_experts") or 0)
    router = 2 * H * cfg.get("router_num_experts", cfg["n_routed_experts"])
    return routed + shared + router


def layers_linear_flops_per_token(cfg: dict) -> float:
    """Every matrix product of the decoder body for one token (no attention
    scores, no head), the routed experts at the held share."""
    L, k = cfg["num_hidden_layers"], dense_layers(cfg)
    return (full_layers(cfg) * attn_linear_flops_per_token(cfg, False)
            + window_layers(cfg) * attn_linear_flops_per_token(cfg, True)
            + k * mlp_flops_per_token(cfg, False)
            + (L - k) * mlp_flops_per_token(cfg, True))


def window_context_tokens(cfg: dict, rows: int, context_tokens: int) -> int:
    """Keys the rows of a window layer attend to in all: no row more than
    the window (a bound from above where some contexts are shorter)."""
    return min(context_tokens, rows * int(cfg["sliding_window"]))


def attn_score_flops(cfg: dict, context_tokens: int, window: bool) -> int:
    """QK^T (key width) and PV (value width) of ONE attention layer of a
    kind for rows that attend to `context_tokens` keys in total."""
    n, _m, d, dv = geometry(cfg, window)
    return 2 * n * (d + dv) * context_tokens


def serve_step_flops(cfg: dict, rows: int, context_tokens: int,
                     sampled_rows: int) -> float:
    """Model FLOPs of one serve step that held `rows` real rows attending to
    `context_tokens` keys in total (in a full layer; a window layer's rows
    stop at the window) and sampled `sampled_rows` of them."""
    scores = (full_layers(cfg) * attn_score_flops(cfg, context_tokens, False)
              + window_layers(cfg) * attn_score_flops(
                  cfg, window_context_tokens(cfg, rows, context_tokens), True))
    return (rows * layers_linear_flops_per_token(cfg) + scores
            + sampled_rows * head_flops_per_row(cfg))


def calls_per_step(cfg: dict) -> int:
    """`paged_attention_gqa` calls in one serve step: one a FULL layer. A
    window layer's call runs the same kernel body under the name
    `paged_attention_sink_gqa` (it has sinks): `window_calls_per_step`."""
    return full_layers(cfg)


def window_calls_per_step(cfg: dict) -> int:
    return window_layers(cfg)


def sink_calls_per_step(cfg: dict) -> int:
    """The calls with sinks: the window layers' where
    `add_swa_attention_sink_bias`, the full layers' where
    `add_full_attention_sink_bias`."""
    return (window_layers(cfg) * bool(cfg.get("add_swa_attention_sink_bias"))
            + full_layers(cfg) * bool(cfg.get("add_full_attention_sink_bias")))


def paged_attention_gqa_call(cfg: dict, rows: int, context_tokens: int,
                             sequence_tokens: int, bytes_per_el: int = 2,
                             window: int | None = None) -> dict:
    """One call (one attention layer) of paged attention at its kind's
    geometry: the cached keys and values the rows attend to read once, q in,
    out. Of the work, whatever implements it: not of a padded rows x pages
    grid, nor of keys padded to lane tiles. With `window` the call is a
    window layer's: `context_tokens` is then the in-window keys the rows
    attend to and `sequence_tokens` the tokens of the live in-window blocks
    (the pages a window's keys lie in: the cache's own granularity)."""
    n, m, d, dv = geometry(cfg, bool(window))
    if window:
        context_tokens = min(context_tokens, rows * window)
    flops = attn_score_flops(cfg, context_tokens, bool(window))
    bytes_ = bytes_per_el * (sequence_tokens * m * (d + dv)
                             + rows * n * (d + dv))
    return {"flops": flops, "bytes": bytes_}
