"""Operations and bytes of the Phi4FlashForCausalLM decoder (Phi-4-mini-flash-
reasoning, SambaY): Mamba-1 mixers and differential attention over a window
in the self-decoder, a memory layer and one full-attention layer whose cache
the cross-decoder reads, then gated memory units and cross-attention layers
that compute queries alone. Found by the configuration's `reference` name
(benchmark/flops.py `counts_for`). Same rules as there: what the algorithm
needs, from shapes alone; a multiply-add is 2 operations; norms, the
convolution, the softplus, the gates and the pairs' combination are not
counted.

The step needs the cross-decoder (its GMUs, cross-attention layers and
MLPs) and the head over the SAMPLED rows alone: a prompt row that samples no
token never reaches them. That is the model's own saving, so the model
FLOPs of a step count those layers over the sampled rows and no more.
"""

from __future__ import annotations

from benchmark.flops import head_flops_per_row


def heads(cfg: dict) -> tuple:
    """(query heads, key/value heads, head width)."""
    n = cfg["num_attention_heads"]
    return n, cfg["num_key_value_heads"], cfg["hidden_size"] // n


def layer_kinds(cfg: dict) -> list:
    """Per layer "mamba", "window", "full", "gmu" or "cross", as the family's
    code lays them out."""
    L, k = cfg["num_hidden_layers"], cfg.get("mb_per_layer", 2)
    out = []
    for i in range(L):
        if i < L // 2 + 2:
            out.append("mamba" if i % k == 0 else
                       "full" if i == L // 2 + 1 else "window")
        else:
            out.append("gmu" if i % k == 0 else "cross")
    return out


def count(cfg: dict, kind: str) -> int:
    return layer_kinds(cfg).count(kind)


def self_layers(cfg: dict) -> int:
    """Layers that run over every row of a step: the self-decoder, the
    memory layer and the full-attention layer."""
    return cfg["num_hidden_layers"] // 2 + 2


def d_inner(cfg: dict) -> int:
    return 2 * cfg["hidden_size"]


def dt_rank(cfg: dict) -> int:
    return -(-cfg["hidden_size"] // 16)


D_STATE = 16


def mlp_flops_per_token(cfg: dict) -> int:
    return 2 * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def attn_linear_flops_per_token(cfg: dict, cross: bool = False) -> int:
    """q and o over all heads, k and v over the key/value heads (a cross
    layer has no k or v of its own)."""
    H = cfg["hidden_size"]
    n, m, d = heads(cfg)
    return 2 * (2 * H * n * d + (0 if cross else 2 * H * m * d))


def ssm_linear_flops_per_token(cfg: dict) -> int:
    """in_proj (H -> 2C), x_proj (C -> R + 2N), dt_proj (R -> C), out (C -> H)."""
    H, C, R = cfg["hidden_size"], d_inner(cfg), dt_rank(cfg)
    return 2 * (H * 2 * C + C * (R + 2 * D_STATE) + R * C + C * H)


def ssm_scan_flops_per_token(cfg: dict) -> int:
    """As Jamba's counts: about 9 a (channel, state) a row."""
    return 9 * d_inner(cfg) * D_STATE


def gmu_flops_per_token(cfg: dict) -> int:
    return 2 * 2 * cfg["hidden_size"] * d_inner(cfg)


def attn_score_flops(cfg: dict, context_tokens: int) -> int:
    """QK^T (64 wide) and PV (the 128-wide value of two value heads) of ONE
    differential layer for rows that attend to `context_tokens` keys."""
    n, _m, d = heads(cfg)
    return 2 * n * (d + 2 * d) * context_tokens


def window_context_tokens(cfg: dict, rows: int, context_tokens: int) -> int:
    """Keys the rows of a window layer attend to in all: no row more than
    the window (a bound from above where some contexts are shorter)."""
    return min(context_tokens, rows * int(cfg["sliding_window"]))


def sampled_context_tokens(rows: int, context_tokens: int,
                           sampled_rows: int) -> int:
    """Keys the sampled rows attend to in all, from a step's totals: the
    rows' mean context times the sampled rows. A decode row's context is
    longer than most prompt rows', so this counts the cross layers' keys
    low, never high."""
    return context_tokens * sampled_rows // max(rows, 1)


def serve_step_flops(cfg: dict, rows: int, context_tokens: int,
                     sampled_rows: int) -> int:
    """Model FLOPs of one serve step that held `rows` real rows attending to
    `context_tokens` keys in total and sampled `sampled_rows` of them: the
    self-decoder, the memory layer and the full layer over every row, the
    cross-decoder and the head over the sampled rows."""
    every_row = (
        self_layers(cfg) * mlp_flops_per_token(cfg)
        + (count(cfg, "window") + count(cfg, "full"))
        * attn_linear_flops_per_token(cfg)
        + count(cfg, "mamba") * (ssm_linear_flops_per_token(cfg)
                                 + ssm_scan_flops_per_token(cfg)))
    sampled_row = (
        (cfg["num_hidden_layers"] - self_layers(cfg)) * mlp_flops_per_token(cfg)
        + count(cfg, "cross") * attn_linear_flops_per_token(cfg, cross=True)
        + count(cfg, "gmu") * gmu_flops_per_token(cfg)
        + head_flops_per_row(cfg))
    scores = (
        count(cfg, "full") * attn_score_flops(cfg, context_tokens)
        + count(cfg, "window") * attn_score_flops(
            cfg, window_context_tokens(cfg, rows, context_tokens))
        + count(cfg, "cross") * attn_score_flops(
            cfg, sampled_context_tokens(rows, context_tokens, sampled_rows)))
    return rows * every_row + sampled_rows * sampled_row + scores


def paged_attention_diff_call(cfg: dict, rows: int, context_tokens: int,
                              block_tokens: int, bytes_per_el: int = 2) -> dict:
    """One differential call: the keys and values of the live blocks
    (`block_tokens`: the pages the rows attend to, the cache's own
    granularity) read once, each token's Hkv x 64 keys and as many values
    (the 128-wide value a head weighs is two of the page's value heads, read
    out of the page, not a second copy); q in (64 a head) and out (128 a
    head) for `rows` rows."""
    n, m, d = heads(cfg)
    flops = attn_score_flops(cfg, context_tokens)
    bytes_ = bytes_per_el * (block_tokens * 2 * m * d + rows * n * 3 * d)
    return {"flops": flops, "bytes": bytes_}


def shared_kv_calls(cfg: dict) -> tuple:
    """(calls over every row, calls over the sampled rows) that read the
    one shared cache: the full layer's, and one a cross layer."""
    return count(cfg, "full"), count(cfg, "cross")


def window_calls_per_step(cfg: dict) -> int:
    return count(cfg, "window")


def ssm_layers(cfg: dict) -> int:
    return count(cfg, "mamba")


def state_bytes_per_slot_layer(cfg: dict, bytes_per_el: int = 2) -> int:
    C = d_inner(cfg)
    return C * D_STATE * 4 + 3 * C * bytes_per_el


def ssm_scan_call(cfg: dict, rows: int, state_runs: int,
                  bytes_per_el: int = 2) -> dict:
    """As Jamba's counts: each run reads and writes its slot's state once,
    each row reads u and z, delta, B and C and writes y."""
    C, N = d_inner(cfg), D_STATE
    bytes_ = (state_runs * 2 * state_bytes_per_slot_layer(cfg, bytes_per_el)
              + rows * (2 * C * bytes_per_el + 2 * C * 4 + 2 * N * 4))
    return {"flops": rows * ssm_scan_flops_per_token(cfg), "bytes": bytes_}
