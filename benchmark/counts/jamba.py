"""Operations and bytes of the JambaForCausalLM decoder, dense sizes
(Jamba2-3B): a Mamba-1 state-space mixer in most layers, grouped-query
attention in every `attn_layer_period`-th, a gated MLP in every layer. Found
by the configuration's `reference` name (benchmark/flops.py `counts_for`).
Same rules as there: what the algorithm needs, from shapes alone; a
multiply-add is 2 operations; norms, the convolution (K taps a channel), the
softplus and the gates are not counted.
"""

from __future__ import annotations

from benchmark.flops import head_flops_per_row


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_kinds(cfg: dict) -> list:
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def attn_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("attention")


def ssm_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("mamba")


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def dt_rank(cfg: dict) -> int:
    rank = cfg.get("mamba_dt_rank", "auto")
    return -(-cfg["hidden_size"] // 16) if rank in (None, "auto") else int(rank)


def mlp_flops_per_token(cfg: dict) -> int:
    return 2 * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def attn_linear_flops_per_token(cfg: dict) -> int:
    """q and o over all heads, k and v over the key/value heads."""
    H, n, nkv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], head_dim(cfg))
    return 2 * (2 * H * n * d + 2 * H * nkv * d)


def ssm_linear_flops_per_token(cfg: dict) -> int:
    """in_proj (H -> 2C), x_proj (C -> R + 2N), dt_proj (R -> C), out (C -> H)."""
    H, C, N, R = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"], dt_rank(cfg)
    return 2 * (H * 2 * C + C * (R + 2 * N) + R * C + C * H)


def ssm_scan_flops_per_token(cfg: dict) -> int:
    """The recurrence of one layer for one row, per (channel, state): the
    decay's product and exp (2), delta*u*B (2), the state's multiply-add (2),
    the product with C and its sum (2), D*u's share (1): about 9."""
    return 9 * d_inner(cfg) * cfg["mamba_d_state"]


def attn_score_flops(cfg: dict, context_tokens: int) -> int:
    """QK^T and PV of one attention layer for rows that attend to
    `context_tokens` keys in total."""
    return 2 * 2 * cfg["num_attention_heads"] * head_dim(cfg) * context_tokens


def serve_step_flops(cfg: dict, rows: int, context_tokens: int,
                     sampled_rows: int) -> int:
    """Model FLOPs of one serve step that held `rows` real rows attending to
    `context_tokens` keys in total and sampled `sampled_rows` of them."""
    per_row = (
        cfg["num_hidden_layers"] * mlp_flops_per_token(cfg)
        + attn_layers(cfg) * attn_linear_flops_per_token(cfg)
        + ssm_layers(cfg) * (ssm_linear_flops_per_token(cfg)
                             + ssm_scan_flops_per_token(cfg)))
    return (rows * per_row + attn_layers(cfg) * attn_score_flops(cfg, context_tokens)
            + sampled_rows * head_flops_per_row(cfg))


def calls_per_step(cfg: dict) -> int:
    """`paged_attention_gqa` calls in one serve step: one an attention layer."""
    return attn_layers(cfg)


def paged_attention_gqa_call(cfg: dict, rows: int, context_tokens: int,
                             sequence_tokens: int, bytes_per_el: int = 2) -> dict:
    """One call (one attention layer) of paged attention: every cached key
    and value of the step's sequences read once, q in, out. Of the work,
    whatever implements it: not of the padded rows x pages grid."""
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    flops = attn_score_flops(cfg, context_tokens)
    bytes_ = bytes_per_el * (sequence_tokens * 2 * nkv * d + 2 * rows * n * d)
    return {"flops": flops, "bytes": bytes_}


def state_bytes_per_slot_layer(cfg: dict, bytes_per_el: int = 2) -> int:
    """What one slot carries in one state-space layer: the recurrent state
    (C x N float32) and the convolution's last K - 1 inputs."""
    C = d_inner(cfg)
    return C * cfg["mamba_d_state"] * 4 + (cfg["mamba_d_conv"] - 1) * C * bytes_per_el


def ssm_scan_call(cfg: dict, rows: int, state_runs: int,
                  bytes_per_el: int = 2) -> dict:
    """One state-space layer's scan over a step of `rows` real rows in
    `state_runs` runs (a run: one slot's rows at consecutive positions): each
    run reads its slot's state once and writes it once; each row reads u and
    z (served dtype), delta (float32), B and C (float32, N each) and writes y
    (float32). Of the work, whatever implements it: a scan that walks the
    rows one by one and a kernel that holds a run's state on chip need the
    same."""
    C, N = d_inner(cfg), cfg["mamba_d_state"]
    bytes_ = (state_runs * 2 * state_bytes_per_slot_layer(cfg, bytes_per_el)
              + rows * (2 * C * bytes_per_el + 2 * C * 4 + 2 * N * 4))
    return {"flops": rows * ssm_scan_flops_per_token(cfg), "bytes": bytes_}
