"""Model-family adapters: HF config dict → TransformerConfig.

The analog of the reference's per-family model modules + registry
(reference: nemo_automodel/components/models/{llama,qwen2,qwen3,mistral3,
gemma…}/model.py and _transformers/registry.py:30 MODEL_ARCH_MAPPING).
Dense families differ only by config; MoE families live in models/moe_lm/.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax.numpy as jnp

from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.ops.rope import RopeScalingConfig


def _base_kwargs(hf: Mapping[str, Any]) -> dict:
    hidden = int(hf["hidden_size"])
    heads = int(hf["num_attention_heads"])
    return dict(
        vocab_size=int(hf["vocab_size"]),
        hidden_size=hidden,
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=int(hf["num_hidden_layers"]),
        num_heads=heads,
        num_kv_heads=int(hf.get("num_key_value_heads", heads)),
        head_dim=int(hf["head_dim"]) if hf.get("head_dim") else None,
        max_position_embeddings=int(hf.get("max_position_embeddings", 4096)),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_scaling=RopeScalingConfig.from_hf(hf.get("rope_scaling")),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
    )


def llama_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """LlamaForCausalLM (Llama 2/3/3.x; reference: models/llama/model.py)."""
    kw = _base_kwargs(hf)
    kw["attention_bias"] = bool(hf.get("attention_bias", False))
    kw.update(overrides)
    return TransformerConfig(**kw)


def llama_bidirectional_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """LlamaBidirectionalModel / ...ForSequenceClassification — the llama
    retrieval encoder with causal masking removed (reference:
    models/llama_bidirectional/model.py:79). Pooling ('avg'/'cls'/'last',
    hf['pooling']) is applied by the retrieval/seq-cls recipes, not here."""
    kw = _base_kwargs(hf)
    kw["attention_bias"] = bool(hf.get("attention_bias", False))
    kw["causal"] = False
    kw.update(overrides)
    return TransformerConfig(**kw)


def mistral_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """MistralForCausalLM (reference: models/mistral3)."""
    kw = _base_kwargs(hf)
    if hf.get("sliding_window"):
        kw["sliding_window"] = int(hf["sliding_window"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def qwen2_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """Qwen2ForCausalLM — qkv bias (reference: models/qwen2/model.py)."""
    kw = _base_kwargs(hf)
    kw["attention_bias"] = True
    if hf.get("use_sliding_window") and hf.get("sliding_window"):
        kw["sliding_window"] = int(hf["sliding_window"])
        # HF Qwen2 windows only layers >= max_window_layers
        mwl = int(hf.get("max_window_layers", 0))
        kw["layer_types"] = tuple(
            "sliding" if i >= mwl else "global" for i in range(kw["num_layers"])
        )
    kw.update(overrides)
    return TransformerConfig(**kw)


def qwen3_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """Qwen3ForCausalLM — qk-norm, no bias (reference: models/qwen3_5)."""
    kw = _base_kwargs(hf)
    kw["qk_norm"] = True
    kw.update(overrides)
    return TransformerConfig(**kw)


def glm4_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """Glm4ForCausalLM — partial interleaved rotary, sandwich norms
    (post_self_attn/post_mlp_layernorm) and a fused gate_up MLP handled by
    the glm4 adapter style (reference: transformers modeling_glm4; the
    reference framework ships GLM via glm4_moe — components/models/glm4_moe)."""
    kw = _base_kwargs(hf)
    kw["attention_bias"] = bool(hf.get("attention_bias", True))
    kw["partial_rotary_factor"] = float(hf.get("partial_rotary_factor", 0.5))
    kw["rope_interleaved"] = True
    kw["use_post_norms"] = True
    kw.update(overrides)
    return TransformerConfig(**kw)


def ernie4_5_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """Ernie4_5ForCausalLM — llama-shaped with GLM-style INTERLEAVED rotary
    (full head_dim), `use_bias` qkv flag, tied embeddings by default
    (reference: models/ernie4_5)."""
    kw = _base_kwargs(hf)
    kw["rope_interleaved"] = True
    kw["attention_bias"] = bool(hf.get("use_bias", False))
    kw["tie_word_embeddings"] = bool(hf.get("tie_word_embeddings", True))
    kw.update(overrides)
    return TransformerConfig(**kw)


def gemma3_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """Gemma3ForCausalLM (text tower) — gemma2's zero-centered sandwich
    norms + qk-norm, 5:1 sliding/global layer pattern, and a separate
    unscaled rope theta for sliding layers (`rope_local_base_freq`).
    Reference: the gemma family dirs (gemma4_moe is its successor)."""
    kw = _base_kwargs(hf)
    kw["activation"] = "gelu_tanh"
    kw["zero_centered_norm"] = True
    kw["use_post_norms"] = True
    kw["qk_norm"] = True
    kw["embed_scale"] = float(kw["hidden_size"]) ** 0.5
    kw["rms_norm_eps"] = float(hf.get("rms_norm_eps", 1e-6))
    kw["tie_word_embeddings"] = bool(hf.get("tie_word_embeddings", True))
    if hf.get("query_pre_attn_scalar"):
        kw["attn_scale"] = float(hf["query_pre_attn_scalar"]) ** -0.5
    if hf.get("final_logit_softcapping"):
        kw["logits_soft_cap"] = float(hf["final_logit_softcapping"])
    if hf.get("sliding_window"):
        kw["sliding_window"] = int(hf["sliding_window"])
        n_layers = kw["num_layers"]
        if hf.get("layer_types"):
            kw["layer_types"] = tuple(
                "sliding" if t == "sliding_attention" else "global"
                for t in hf["layer_types"]
            )
        else:
            # gemma3 default: every 6th layer global, the rest sliding
            pattern = int(hf.get("sliding_window_pattern", 6))
            kw["layer_types"] = tuple(
                "global" if (i + 1) % pattern == 0 else "sliding"
                for i in range(n_layers)
            )
        if hf.get("rope_local_base_freq"):
            kw["rope_local_theta"] = float(hf["rope_local_base_freq"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def hunyuan_dense_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """HunYuanDenseV1ForCausalLM (reference: models/hy_mt2/hy_v3 family):
    llama-shaped with an unconditional per-head qk-norm applied AFTER
    rotary (query/key_layernorm)."""
    kw = _base_kwargs(hf)
    kw["attention_bias"] = bool(hf.get("attention_bias", False))
    kw["qk_norm"] = True
    kw["qk_norm_after_rope"] = True
    kw.update(overrides)
    return TransformerConfig(**kw)


def gemma2_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """Gemma2: zero-centered 4-norm layers, embed scaling, soft caps,
    query_pre_attn_scalar attention scale, alternating sliding/global."""
    kw = _base_kwargs(hf)
    kw["activation"] = "gelu_tanh"
    kw["zero_centered_norm"] = True
    kw["use_post_norms"] = True
    kw["embed_scale"] = float(kw["hidden_size"]) ** 0.5
    if hf.get("final_logit_softcapping"):
        kw["logits_soft_cap"] = float(hf["final_logit_softcapping"])
    if hf.get("attn_logit_softcapping"):
        kw["attn_soft_cap"] = float(hf["attn_logit_softcapping"])
    if hf.get("query_pre_attn_scalar"):
        kw["attn_scale"] = float(hf["query_pre_attn_scalar"]) ** -0.5
    if hf.get("sliding_window"):
        kw["sliding_window"] = int(hf["sliding_window"])
        n_layers = kw["num_layers"]
        if hf.get("layer_types"):
            kw["layer_types"] = tuple(
                "sliding" if t == "sliding_attention" else "global"
                for t in hf["layer_types"]
            )
        else:
            # gemma2 alternates: even layers sliding, odd layers global
            kw["layer_types"] = tuple(
                "sliding" if i % 2 == 0 else "global" for i in range(n_layers)
            )
    # gemma HF configs rely on the class default of tie_word_embeddings=True
    kw["tie_word_embeddings"] = bool(hf.get("tie_word_embeddings", True))
    kw.update(overrides)
    return TransformerConfig(**kw)


def ouro_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """OuroForCausalLM (ByteDance Ouro 1.4B / 2.6B, "Scaling Latent Reasoning
    via Looped Language Models"): a llama-shaped MHA decoder with sandwich
    norms on both branches whose whole layer stack runs `total_ut_steps`
    times over every token, the final norm after each walk, a cache entry
    per (pass, layer), and a Linear hidden -> 1 exit gate on each pass's
    normed state. With the published `early_exit_threshold` of 1 the exit
    distribution's cumulated mass reaches the threshold only at the last
    pass, so every token's logits come from the last pass; a lower threshold
    lets tokens leave at different passes, which is not built."""
    threshold = float(hf.get("early_exit_threshold", 1.0))
    if threshold < 1.0:
        raise NotImplementedError(
            f"early_exit_threshold={threshold}: adaptive exit (rows that leave "
            "the layer stack at different passes) is not built; every token "
            "runs all total_ut_steps passes, which is what a threshold of 1 "
            "means"
        )
    types = hf.get("layer_types") or []
    if any(t != "full_attention" for t in types) or hf.get("use_sliding_window"):
        raise NotImplementedError(
            "OuroForCausalLM with sliding-window layers (the published models "
            "are full attention throughout)"
        )
    kw = _base_kwargs(hf)
    kw["rms_norm_eps"] = float(hf.get("rms_norm_eps", 1e-6))
    kw["use_post_norms"] = True
    kw["num_passes"] = int(hf.get("total_ut_steps", 4))
    kw["exit_gate"] = True
    kw.update(overrides)
    return TransformerConfig(**kw)


def jamba_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """JambaForCausalLM (AI21 Jamba / Jamba2; the family's modeling_jamba.py):
    layer i is attention where `i % attn_layer_period == attn_layer_offset`
    and a Mamba-1 mixer everywhere else (models/llm/mamba.py); every layer
    `x += mixer(rms(x))`, `x += mlp(rms(x))`, a silu-gated MLP. No positional
    encoding of any kind: attention runs WITHOUT rotary embedding. The sizes
    with experts (`num_experts` > 1 on every `expert_layer_period`-th layer)
    are refused: the dense sizes (Jamba2-3B, Jamba Reasoning 3B) are what is
    built."""
    if int(hf.get("num_experts", 1)) != 1:
        raise NotImplementedError(
            f"JambaForCausalLM with num_experts={hf['num_experts']}: the "
            "expert layers of the larger Jamba sizes are not built (every "
            "MLP here is dense)"
        )
    if hf.get("mamba_proj_bias", False) or not hf.get("mamba_conv_bias", True):
        raise NotImplementedError(
            "JambaForCausalLM with mamba_proj_bias or without mamba_conv_bias "
            "(no published size has either)"
        )
    if hf.get("sliding_window"):
        raise NotImplementedError("JambaForCausalLM with a sliding window")
    kw = _base_kwargs(hf)
    kw["rms_norm_eps"] = float(hf.get("rms_norm_eps", 1e-6))
    period = int(hf.get("attn_layer_period", 8))
    offset = int(hf.get("attn_layer_offset", 4))
    kw["layer_ops"] = tuple(
        "attention" if i % period == offset else "mamba"
        for i in range(kw["num_layers"])
    )
    kw["use_rope"] = False
    kw["mamba_d_state"] = int(hf.get("mamba_d_state", 16))
    kw["mamba_d_conv"] = int(hf.get("mamba_d_conv", 4))
    kw["mamba_expand"] = int(hf.get("mamba_expand", 2))
    rank = hf.get("mamba_dt_rank", "auto")
    kw["mamba_dt_rank"] = None if rank in (None, "auto") else int(rank)
    kw.update(overrides)
    return TransformerConfig(**kw)


def phi4flash_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """Phi4FlashForCausalLM (Phi-4-mini-flash-reasoning; the SambaY
    decoder-hybrid-decoder of arXiv:2507.06607), its layout derived from
    `num_hidden_layers` L and `mb_per_layer` as the family's code derives it:
    below L/2 (the self-decoder) a Mamba-1 mixer where i % mb_per_layer == 0
    and attention with the `sliding_window` elsewhere; layer L/2 a Mamba
    layer whose output before its gate is the MEMORY, L/2 + 1 full attention
    whose keys and values are the one full-length cache; from L/2 + 2 on (the
    cross-decoder) a gated memory unit where a Mamba layer would stand and
    cross-attention, queries alone against that cache, elsewhere. Every
    attention layer is differential (`TransformerConfig.differential_
    attention`) with biased projections and no rotary embedding; the norms
    are LayerNorms with a bias; the Mamba mixers have none on dt, B or C."""
    L, every = int(hf["num_hidden_layers"]), int(hf.get("mb_per_layer", 2))
    if every < 1 or L % 2 or L // 2 % every:
        raise NotImplementedError(
            f"Phi4FlashForCausalLM with num_hidden_layers={L}, mb_per_layer="
            f"{every}: layer L/2 must be a Mamba layer")
    if hf.get("mlp_bias") or hf.get("lm_head_bias"):
        raise NotImplementedError("Phi4FlashForCausalLM with MLP or head biases")
    ops = []
    for i in range(L):
        mamba = i % every == 0
        if i < L // 2 + 2:
            ops.append("mamba" if mamba else
                       "attention" if i == L // 2 + 1 else "window_attention")
        else:
            ops.append("gmu" if mamba else "cross_attention")
    kw = _base_kwargs(hf)
    kw["rms_norm_eps"] = float(hf.get("layer_norm_eps", 1e-5))
    kw["norm_type"] = "layer"
    kw["layer_ops"] = tuple(ops)
    kw["sliding_window"] = int(hf["sliding_window"])
    kw["layer_types"] = tuple(
        "sliding" if op == "window_attention" else "full" for op in ops)
    kw["use_rope"] = False
    kw["attention_bias"] = True
    kw["o_proj_bias"] = True
    kw["differential_attention"] = True
    kw["mamba_inner_norms"] = False
    kw["mamba_d_state"] = int(hf.get("mamba_d_state", 16))
    kw["mamba_d_conv"] = int(hf.get("mamba_d_conv", 4))
    kw["mamba_expand"] = int(hf.get("mamba_expand", 2))
    kw["activation"] = {"silu": "silu", "gelu": "gelu"}[hf.get("hidden_act", "silu")]
    kw.update(overrides)
    return TransformerConfig(**kw)


def baichuan_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """BaichuanForCausalLM — Baichuan2 7B shape (reference: models/baichuan/
    model.py): llama-like MHA with a fused W_pack qkv projection (handled by
    the adapter's "baichuan" style) and an L2-normalized lm_head (NormHead).
    The 13B ALiBi variant is not covered (rope only, like the reference)."""
    kw = _base_kwargs(hf)
    kw["num_kv_heads"] = kw["num_heads"]  # MHA
    kw["normalized_lm_head"] = True
    kw.update(overrides)
    return TransformerConfig(**kw)


def ministral3_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """Ministral3ForCausalLM (reference: models/mistral3/model.py:50
    Ministral3Config): mistral body with an explicit head_dim, optional
    sliding window, and rope_theta nested under rope_parameters."""
    kw = _base_kwargs(hf)
    rp = hf.get("rope_parameters") or {}
    if rp.get("rope_theta"):
        kw["rope_theta"] = float(rp["rope_theta"])
    if hf.get("sliding_window"):
        kw["sliding_window"] = int(hf["sliding_window"])
    kw["attention_bias"] = bool(hf.get("attention_bias", False))
    kw.update(overrides)
    return TransformerConfig(**kw)


def ministral_bidirectional_config(hf: Mapping[str, Any], **overrides) -> TransformerConfig:
    """Ministral3BidirectionalModel (reference: models/
    ministral_bidirectional/model.py:36): the ministral retrieval encoder
    with causal masking removed; pooling is applied by the recipes."""
    kw_over = dict(overrides)
    kw_over["causal"] = False
    return ministral3_config(hf, **kw_over)
