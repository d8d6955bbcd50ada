"""Long-tail model families: baichuan, qwen3.5(-moe) (+ later additions).

Parity strategy: no torch oracle exists in-env for these architectures
(transformers 4.57 predates them / never shipped baichuan natively), so the
tests pin the checkpoint-layout contracts (adapter round-trips through the
exact HF tensor layout) and the architecture semantics (NormHead, separate
GDN projections) the reference implements.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.registry import get_model_spec


BAICHUAN_HF = {
    "architectures": ["BaichuanForCausalLM"],
    "model_type": "baichuan",
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "rms_norm_eps": 1e-6,
}


def test_baichuan_registry_and_normhead():
    spec = get_model_spec(BAICHUAN_HF)
    cfg = spec.config_from_hf(BAICHUAN_HF, dtype=jnp.float32, remat_policy="none")
    assert cfg.num_kv_heads == cfg.num_heads  # MHA
    assert cfg.normalized_lm_head
    params = decoder.init(cfg, jax.random.key(0))
    # NormHead: scaling lm_head rows must NOT change logits (normalized away)
    ids = jax.random.randint(jax.random.key(1), (2, 8), 0, 128)
    base = decoder.forward(params, cfg, ids)
    scaled = dict(params)
    scaled["lm_head"] = {"kernel": params["lm_head"]["kernel"] * 7.5}
    again = decoder.forward(scaled, cfg, ids)
    np.testing.assert_allclose(np.asarray(base), np.asarray(again), atol=1e-5)


def test_baichuan_adapter_w_pack_roundtrip():
    from automodel_tpu.checkpoint.hf_adapter import get_adapter

    spec = get_model_spec(BAICHUAN_HF)
    cfg = spec.config_from_hf(BAICHUAN_HF, dtype=jnp.float32, remat_policy="none")
    params = decoder.init(cfg, jax.random.key(0))
    ad = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    sd = dict(ad.to_hf(params))
    assert "model.layers.0.self_attn.W_pack.weight" in sd
    assert sd["model.layers.0.self_attn.W_pack.weight"].shape == (3 * 32, 32)
    assert not any("q_proj" in k for k in sd)
    p2 = ad.from_hf(lambda k: sd[k])
    ids = jax.random.randint(jax.random.key(2), (2, 8), 0, 128)
    o1 = decoder.forward(params, cfg, ids)
    o2 = decoder.forward(jax.tree.map(jnp.asarray, p2), cfg, ids)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


QWEN3_5_MOE_HF = {
    "architectures": ["Qwen3_5MoeForConditionalGeneration"],
    "model_type": "qwen3_5_moe",
    "text_config": {
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "layer_types": [
            "linear_attention", "full_attention",
            "linear_attention", "full_attention",
        ],
        "linear_num_value_heads": 4, "linear_num_key_heads": 2,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "num_experts": 4, "num_experts_per_tok": 2,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
        "norm_topk_prob": True, "rope_theta": 10000.0,
    },
}


@pytest.mark.slow
def test_qwen3_5_moe_adapter_roundtrip():
    """to_hf emits the Qwen3.5 layout (separate GDN projections, stacked
    experts, language_model prefix) and from_hf inverts it exactly."""
    from automodel_tpu.checkpoint.hf_adapter import get_adapter
    from automodel_tpu.models.hybrid import qwen3_5 as q35

    spec = get_model_spec(QWEN3_5_MOE_HF)
    cfg = spec.config_from_hf(QWEN3_5_MOE_HF, remat_policy="none")
    assert cfg.moe is not None
    params = q35.init(cfg, jax.random.key(0))
    ad = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    sd = dict(ad.to_hf(params))
    pre = "model.language_model."
    assert pre + "layers.0.linear_attn.in_proj_qkv.weight" in sd
    assert pre + "layers.0.linear_attn.in_proj_z.weight" in sd
    assert pre + "layers.0.linear_attn.in_proj_b.weight" in sd
    assert pre + "layers.0.linear_attn.in_proj_a.weight" in sd
    assert not any("in_proj_qkvz" in k for k in sd)
    assert sd[pre + "layers.0.mlp.experts.gate_up_proj"].shape == (4, 32, 32)
    assert sd[pre + "layers.0.mlp.experts.down_proj"].shape == (4, 32, 16)
    p2 = ad.from_hf(lambda k: np.asarray(sd[k]))
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    o1, _ = q35.forward(params, cfg, ids)
    o2, _ = q35.forward(jax.tree.map(jnp.asarray, p2), cfg, ids)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


@pytest.mark.slow
def test_qwen3_5_dense_config():
    hf = {
        "architectures": ["Qwen3_5ForCausalLM"],
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "layer_types": ["linear_attention", "full_attention"],
        "linear_num_value_heads": 4, "linear_num_key_heads": 2,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    }
    spec = get_model_spec(hf)
    cfg = spec.config_from_hf(hf, remat_policy="none")
    assert cfg.moe is None
    from automodel_tpu.models.hybrid import qwen3_5 as q35

    params = q35.init(cfg, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    out = q35.forward(params, cfg, ids)
    assert np.isfinite(np.asarray(out)).all()


GLM_DSA_HF = {
    "architectures": ["GlmMoeDsaForCausalLM"],
    "model_type": "glm_moe_dsa",
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "n_routed_experts": 4, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "first_k_dense_replace": 0, "norm_topk_prob": True,
    "routed_scaling_factor": 1.0,
    "kv_lora_rank": 16, "q_lora_rank": 12,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "index_topk": 6, "index_n_heads": 2, "index_head_dim": 16,
    "indexer_types": ["full", "shared"],
}


def _glm_dsa_setup():
    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    spec = get_model_spec(GLM_DSA_HF)
    cfg = spec.config_from_hf(GLM_DSA_HF, dtype=jnp.float32, remat_policy="none")
    params = moe_decoder.init(cfg, jax.random.key(0))
    return spec, cfg, params, moe_decoder


@pytest.mark.slow
def test_glm_dsa_index_share_ignores_shared_layer_indexer():
    """IndexShare: a "shared" layer reuses the previous full layer's top-k,
    so zeroing its own indexer weights must not change the output (while
    zeroing it under all-"full" types must)."""
    import dataclasses

    spec, cfg, params, moe_decoder = _glm_dsa_setup()
    assert cfg.dsa_indexer_style == "glm"
    assert cfg.dsa_indexer_types == ("full", "shared")
    ids = jax.random.randint(jax.random.key(1), (2, 12), 0, 128)

    def zero_layer2_indexer(p):
        p = jax.tree.map(lambda x: x, p)  # copy
        p["moe_layers"]["indexer"] = jax.tree.map(
            lambda x: x.at[1].set(0.0), p["moe_layers"]["indexer"]
        )
        return p

    base, _ = moe_decoder.forward(params, cfg, ids)
    zeroed, _ = moe_decoder.forward(zero_layer2_indexer(params), cfg, ids)
    np.testing.assert_allclose(np.asarray(base), np.asarray(zeroed), atol=1e-6)

    cfg_full = dataclasses.replace(cfg, dsa_indexer_types=("full", "full"))
    base_f, _ = moe_decoder.forward(params, cfg_full, ids)
    zeroed_f, _ = moe_decoder.forward(zero_layer2_indexer(params), cfg_full, ids)
    assert np.abs(np.asarray(base_f) - np.asarray(zeroed_f)).max() > 1e-6


@pytest.mark.slow
def test_glm_dsa_adapter_roundtrip_index_share():
    """Export omits indexer keys for shared layers (matching HF); import
    zero-fills them; the round-trip reproduces logits exactly."""
    from automodel_tpu.checkpoint.hf_adapter import get_adapter

    spec, cfg, params, moe_decoder = _glm_dsa_setup()
    ad = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    sd = dict(ad.to_hf(params))
    assert "model.layers.0.self_attn.indexer.wq_b.weight" in sd
    assert "model.layers.0.self_attn.indexer.k_norm.bias" in sd
    assert not any("layers.1.self_attn.indexer" in k for k in sd)
    p2 = ad.from_hf(lambda k: np.asarray(sd[k]))
    ids = jax.random.randint(jax.random.key(2), (2, 12), 0, 128)
    o1, _ = moe_decoder.forward(params, cfg, ids)
    o2, _ = moe_decoder.forward(jax.tree.map(jnp.asarray, p2), cfg, ids)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


GEMMA4_HF = {
    "architectures": ["Gemma4ForConditionalGeneration"],
    "model_type": "gemma4",
    "text_config": {
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "layer_types": [
            "sliding_attention", "full_attention",
            "sliding_attention", "full_attention",
        ],
        "sliding_window": 8, "rope_theta": 1000000.0,
        "rope_local_base_freq": 10000.0, "query_pre_attn_scalar": 8,
        "num_kv_shared_layers": 2,
        "num_experts": 4, "top_k_experts": 2, "moe_intermediate_size": 16,
        "rms_norm_eps": 1e-6,
    },
    "tie_word_embeddings": True,
}


def _gemma4_setup():
    from automodel_tpu.models.moe_lm import gemma4

    spec = get_model_spec(GEMMA4_HF)
    cfg = spec.config_from_hf(GEMMA4_HF, dtype=jnp.float32, remat_policy="none")
    params = gemma4.init(cfg, jax.random.key(0))
    return spec, cfg, params, gemma4


@pytest.mark.slow
def test_gemma4_forward_and_kv_sharing():
    """Layers 2/3 share layer 0/1's K/V (same-type): zeroing a shared
    layer's k/v kernels must not change the output."""
    spec, cfg, params, gemma4 = _gemma4_setup()
    assert cfg.num_kv_shared_layers == 2
    assert cfg.layer_types == ("sliding", "global", "sliding", "global")
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    out, aux, stats = gemma4.forward(params, cfg, ids, return_stats=True)
    assert np.isfinite(np.asarray(out)).all()
    assert stats["tokens_per_expert"].shape == (4, 4)
    # every token routes to exactly top-k experts per layer
    np.testing.assert_allclose(
        np.asarray(stats["tokens_per_expert"]).sum(-1),
        2 * 16 * cfg.moe.experts_per_token,
    )

    zeroed = jax.tree.map(lambda x: x, params)
    for pk in ("k_proj", "v_proj"):
        zeroed["layers"][pk]["kernel"] = (
            zeroed["layers"][pk]["kernel"].at[2:].set(0.0)
        )
    out2, _, _ = gemma4.forward(zeroed, cfg, ids, return_stats=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)


@pytest.mark.slow
def test_gemma4_adapter_roundtrip():
    from automodel_tpu.checkpoint.hf_adapter import get_adapter

    spec, cfg, params, gemma4 = _gemma4_setup()
    ad = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    sd = dict(ad.to_hf(params))
    pre = "model.language_model."
    assert pre + "layers.0.self_attn.k_proj.weight" in sd
    assert pre + "layers.0.router.scale" in sd
    assert pre + "layers.0.moe.gate_up_proj" in sd
    assert sd[pre + "layers.0.moe.gate_up_proj"].shape == (4, 32, 32)
    assert sd[pre + "layers.0.moe.down_proj"].shape == (4, 32, 16)
    # kv-shared layers export no k/v keys (matching HF)
    assert pre + "layers.2.self_attn.k_proj.weight" not in sd
    assert pre + "layers.3.self_attn.v_proj.weight" not in sd
    p2 = ad.from_hf(lambda k: np.asarray(sd[k]))
    ids = jax.random.randint(jax.random.key(2), (2, 16), 0, 128)
    o1, _, _ = gemma4.forward(params, cfg, ids, return_stats=True)
    o2, _, _ = gemma4.forward(
        jax.tree.map(jnp.asarray, p2), cfg, ids, return_stats=True
    )
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


@pytest.mark.slow
def test_gemma4_recipe_trains(tmp_path):
    import json

    from automodel_tpu.cli.app import resolve_recipe_class
    from tests.unit.test_recipe import _smoke_cfg

    cfg = _smoke_cfg(tmp_path)
    cfg.set("model.hf_config", GEMMA4_HF)
    cfg.set("distributed", {"dp_shard": -1, "ep": 2})
    cfg.set("checkpoint.enabled", False)
    cfg.set("step_scheduler.max_steps", 3)
    r = resolve_recipe_class(cfg)(cfg)
    r.setup()
    assert r.is_moe
    r.run_train_validation_loop()
    recs = [json.loads(l) for l in open(tmp_path / "training.jsonl") if l.strip()]
    assert len(recs) == 3
    assert all(np.isfinite(x["loss"]) for x in recs)


LING_HF = {
    "architectures": ["BailingMoeV2ForCausalLM"],
    "model_type": "bailing_moe",
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8,
    "use_qk_norm": True, "partial_rotary_factor": 0.5,
    "num_experts": 4, "num_shared_experts": 1, "num_experts_per_tok": 2,
    "n_group": 2, "topk_group": 2, "moe_intermediate_size": 16,
    "first_k_dense_replace": 1, "score_function": "sigmoid",
    "routed_scaling_factor": 1.0, "norm_topk_prob": True,
    "moe_router_enable_expert_bias": True,
}


@pytest.mark.slow
def test_ling_v2_adapter_fused_qkv_roundtrip():
    """Ling 2.0 (BailingMoeV2): fused query_key_value / attention.dense /
    word_embeddings naming round-trips exactly."""
    from automodel_tpu.checkpoint.hf_adapter import get_adapter
    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    spec = get_model_spec(LING_HF)
    cfg = spec.config_from_hf(LING_HF, dtype=jnp.float32, remat_policy="none")
    assert cfg.qk_norm and cfg.partial_rotary_factor == 0.5
    assert cfg.first_k_dense == 1
    assert cfg.moe.gate_bias_update_speed > 0
    params = moe_decoder.init(cfg, jax.random.key(0))
    ad = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    sd = dict(ad.to_hf(params))
    assert "model.word_embeddings.weight" in sd
    assert sd["model.layers.0.attention.query_key_value.weight"].shape == (4 * 8 + 2 * 2 * 8, 32)
    assert "model.layers.0.attention.dense.weight" in sd
    assert "model.layers.0.attention.query_layernorm.weight" in sd
    assert "model.layers.1.mlp.gate.expert_bias" in sd
    assert "model.layers.1.mlp.shared_experts.gate_proj.weight" in sd
    assert not any("q_proj" in k for k in sd)
    p2 = ad.from_hf(lambda k: np.asarray(sd[k]))
    ids = jax.random.randint(jax.random.key(1), (2, 12), 0, 128)
    o1, _ = moe_decoder.forward(params, cfg, ids)
    o2, _ = moe_decoder.forward(jax.tree.map(jnp.asarray, p2), cfg, ids)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


STEP35_HF = {
    "architectures": ["Step3p5ForCausalLM"],
    "model_type": "step3p5",
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_attention_groups": 2, "head_dim": 8,
    "attention_other_setting": {"num_attention_heads": 2, "num_attention_groups": 1},
    "layer_types": [
        "full_attention", "sliding_attention",
        "sliding_attention", "full_attention",
    ],
    "sliding_window": 8,
    "rope_theta": [10000.0, 5000.0, 5000.0, 10000.0],
    "partial_rotary_factors": [1.0, 0.5, 0.5, 1.0],
    "use_rope_layers": [True, True, False, True],
    "use_head_wise_attn_gate": True,
    "moe_layers_enum": [1, 3],
    "moe_num_experts": 4, "moe_top_k": 2, "moe_intermediate_size": 16,
    "moe_router_activation": "sigmoid", "use_moe_router_bias": True,
    "share_expert_dims": [16, 16, 16, 16],
    "rms_norm_eps": 1e-5,
}

MIMO_HF = {
    "architectures": ["MiMoV2FlashForCausalLM"],
    "model_type": "mimo_v2_flash",
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "v_head_dim": 8,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 1,
    "swa_head_dim": 8, "swa_v_head_dim": 4,
    "hybrid_layer_pattern": [0, 1, 1, 0],
    "sliding_window": 8,
    "rope_theta": 5000000.0, "swa_rope_theta": 10000.0,
    "partial_rotary_factor": 0.5,
    "attention_value_scale": 0.707,
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "scoring_func": "sigmoid",
    "n_group": 2, "topk_group": 2, "norm_topk_prob": True,
    "moe_layer_freq": [0, 1, 1, 1], "n_shared_experts": 1,
}


@pytest.mark.slow
def test_step3p5_forward_and_roundtrip():
    from automodel_tpu.checkpoint.hf_adapter import get_adapter
    from automodel_tpu.models.moe_lm import het_moe

    spec = get_model_spec(STEP35_HF)
    cfg = spec.config_from_hf(STEP35_HF, dtype=jnp.float32, remat_policy="none")
    assert cfg.layer_types == ("global", "sliding", "sliding", "global")
    assert cfg.mlp_kinds == ("dense", "moe", "dense", "moe")
    assert cfg.sliding_attn.num_heads == 2 and cfg.global_attn.num_heads == 4
    assert cfg.use_rope == (True, True, False, True)  # NoPE layer
    assert cfg.head_gate
    params = het_moe.init(cfg, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    out, aux, stats = het_moe.forward(params, cfg, ids, return_stats=True)
    assert np.isfinite(np.asarray(out)).all()
    assert stats["tokens_per_expert"].shape == (2, 4)

    ad = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    sd = dict(ad.to_hf(params))
    assert sd["model.layers.1.moe.gate_proj.weight"].shape == (4, 16, 32)
    assert "model.layers.1.moe.router_bias" in sd
    assert "model.layers.1.share_expert.up_proj.weight" in sd
    assert "model.layers.0.self_attn.g_proj.weight" in sd
    assert sd["model.layers.1.self_attn.q_proj.weight"].shape == (2 * 8, 32)
    p2 = ad.from_hf(lambda k: np.asarray(sd[k]))
    o2, _, _ = het_moe.forward(
        jax.tree.map(jnp.asarray, p2), cfg, ids, return_stats=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(o2), atol=1e-5)


@pytest.mark.slow
def test_mimo_v2_flash_forward_and_roundtrip():
    """MiMo-V2-Flash on the expert decoder: the window layers' attention an
    operator stack of its own (its key/value heads, value width and sinks),
    the value scale read, the checkpoint's names both ways."""
    from automodel_tpu.checkpoint.hf_adapter import get_adapter
    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    spec = get_model_spec(MIMO_HF)
    cfg = spec.config_from_hf(MIMO_HF, dtype=jnp.float32, remat_policy="none")
    assert spec.module is moe_decoder
    assert cfg.layer_types == ("global", "sliding", "sliding", "global")
    assert cfg.layer_ops == ("attention", "window_attention",
                             "window_attention", "attention")
    assert cfg.first_k_dense == 1 and cfg.attention_value_scale == 0.707
    assert dict(cfg.window_attention) == {
        "num_heads": 2, "num_kv_heads": 1, "v_head_dim": 4,
        "attention_sinks": True}
    assert not cfg.attention_sinks and cfg.rope_local_theta == 10000.0
    params = moe_decoder.init(cfg, jax.random.key(0))
    # non-zero sinks so the path is exercised
    params["window_attn_layers"]["sinks"] = 0.3 + 0.1 * jax.random.normal(
        jax.random.key(5), params["window_attn_layers"]["sinks"].shape
    )
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    out, aux, stats = moe_decoder.forward(params, cfg, ids, return_stats=True)
    assert np.isfinite(np.asarray(out)).all()
    assert stats["tokens_per_expert"].shape == (3, 4)

    ad = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    sd = dict(ad.to_hf(params))
    assert "model.layers.1.self_attn.attention_sink_bias" in sd
    assert "model.layers.0.self_attn.attention_sink_bias" not in sd
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in sd
    assert "model.layers.1.mlp.shared_experts.down_proj.weight" in sd
    assert "model.layers.0.mlp.gate_proj.weight" in sd  # dense layer
    assert sd["model.layers.1.self_attn.k_proj.weight"].shape == (1 * 8, 32)
    assert sd["model.layers.1.self_attn.v_proj.weight"].shape == (1 * 4, 32)
    assert sd["model.layers.0.self_attn.v_proj.weight"].shape == (2 * 8, 32)
    p2 = ad.from_hf(lambda k: np.asarray(sd[k]))
    o2, _, _ = moe_decoder.forward(
        jax.tree.map(jnp.asarray, p2), cfg, ids, return_stats=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(o2), atol=1e-5)


@pytest.mark.recipe
def test_step3p5_recipe_trains(tmp_path):
    import json

    from automodel_tpu.cli.app import resolve_recipe_class
    from tests.unit.test_recipe import _smoke_cfg

    cfg = _smoke_cfg(tmp_path)
    cfg.set("model.hf_config", STEP35_HF)
    cfg.set("distributed", {"dp_shard": -1, "ep": 2})
    cfg.set("checkpoint.enabled", False)
    cfg.set("step_scheduler.max_steps", 3)
    r = resolve_recipe_class(cfg)(cfg)
    r.setup()
    assert r.is_moe
    r.run_train_validation_loop()
    recs = [json.loads(l) for l in open(tmp_path / "training.jsonl") if l.strip()]
    assert len(recs) == 3
    assert all(np.isfinite(x["loss"]) for x in recs)


MINISTRAL_HF = {
    "architectures": ["Ministral3BidirectionalModel"],
    "model_type": "ministral3",
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8,
    "rope_parameters": {"rope_theta": 1000000.0},
    "sliding_window": 16, "pooling": "avg",
}


def test_ministral3_and_bidirectional():
    spec = get_model_spec(MINISTRAL_HF)
    cfg = spec.config_from_hf(MINISTRAL_HF, dtype=jnp.float32, remat_policy="none")
    assert cfg.causal is False
    assert cfg.rope_theta == 1000000.0 and cfg.sliding_window == 16
    params = decoder.init(cfg, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 12), 0, 128)
    h1 = decoder.forward(params, cfg, ids, return_hidden=True)
    # bidirectional: a LATE token change moves an EARLY hidden state
    ids2 = ids.at[0, -1].set((int(ids[0, -1]) + 1) % 128)
    h2 = decoder.forward(params, cfg, ids2, return_hidden=True)
    assert np.abs(np.asarray(h1[0, 0]) - np.asarray(h2[0, 0])).max() > 1e-7

    causal_hf = dict(MINISTRAL_HF, architectures=["Ministral3ForCausalLM"])
    cfg_c = get_model_spec(causal_hf).config_from_hf(
        causal_hf, dtype=jnp.float32, remat_policy="none"
    )
    assert cfg_c.causal is True


GLM_LITE_HF = {
    "architectures": ["Glm4MoeLiteForCausalLM"],
    "model_type": "glm4_moe_lite",
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "n_routed_experts": 4, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1.0, "n_group": 2, "topk_group": 2,
    "kv_lora_rank": 16, "q_lora_rank": 12,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
}


def test_glm4_moe_lite_is_mla_moe():
    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    spec = get_model_spec(GLM_LITE_HF)
    cfg = spec.config_from_hf(GLM_LITE_HF, dtype=jnp.float32, remat_policy="none")
    assert cfg.attention_type == "mla" and cfg.first_k_dense == 1
    assert cfg.moe.score_func == "sigmoid"
    params = moe_decoder.init(cfg, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 8), 0, 128)
    logits, _ = moe_decoder.forward(params, cfg, ids)
    assert np.isfinite(np.asarray(logits)).all()


HY_MT2_HF = {
    "architectures": ["HyMT2ForCausalLM"],
    "model_type": "hy_mt2",
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "qk_norm": True,
    "num_experts": 4, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "expert_hidden_dim": 16, "moe_intermediate_size": 16,
    "moe_router_use_sigmoid": True, "moe_router_enable_expert_bias": True,
    "first_k_dense_replace": 1, "rope_theta": 11158840.0,
}


@pytest.mark.slow
def test_hy_mt2_adapter_roundtrip():
    from automodel_tpu.checkpoint.hf_adapter import get_adapter
    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    spec = get_model_spec(HY_MT2_HF)
    cfg = spec.config_from_hf(HY_MT2_HF, dtype=jnp.float32, remat_policy="none")
    assert cfg.qk_norm and cfg.first_k_dense == 1
    assert cfg.moe.score_func == "sigmoid"
    assert cfg.moe.gate_bias_update_speed > 0
    params = moe_decoder.init(cfg, jax.random.key(0))
    ad = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    sd = dict(ad.to_hf(params))
    # the Hy-MT2 on-disk layout (reference: hy_mt2/state_dict_adapter.py)
    assert "model.layers.1.mlp.router.gate.weight" in sd
    assert "model.layers.1.mlp.expert_bias" in sd
    assert "model.layers.1.mlp.shared_mlp.up_proj.weight" in sd
    assert not any(".mlp.gate.weight" in k for k in sd)
    p2 = ad.from_hf(lambda k: np.asarray(sd[k]))
    ids = jax.random.randint(jax.random.key(2), (2, 8), 0, 128)
    o1, _ = moe_decoder.forward(params, cfg, ids)
    o2, _ = moe_decoder.forward(jax.tree.map(jnp.asarray, p2), cfg, ids)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


MISTRAL4_HF = {
    "architectures": ["Mistral4ForCausalLM"],
    "model_type": "mistral4",
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "n_routed_experts": 4, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1.0,
    "kv_lora_rank": 16, "q_lora_rank": 12,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "rope_parameters": {
        "rope_theta": 10000.0, "llama_4_scaling_beta": 0.1,
        "original_max_position_embeddings": 8,
    },
}


@pytest.mark.slow
def test_mistral4_llama4_qpe_scaling():
    """Positions past orig_max get the llama4 log scaling on q_pe — the
    forward must differ from the unscaled config exactly there."""
    import dataclasses

    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    spec = get_model_spec(MISTRAL4_HF)
    cfg = spec.config_from_hf(MISTRAL4_HF, dtype=jnp.float32, remat_policy="none")
    assert cfg.mla_qpe_scaling_beta == 0.1
    assert cfg.mla_qpe_scaling_orig_max == 8
    params = moe_decoder.init(cfg, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (1, 16), 0, 128)
    l_scaled, _ = moe_decoder.forward(params, cfg, ids)
    cfg_off = dataclasses.replace(cfg, mla_qpe_scaling_beta=None)
    l_plain, _ = moe_decoder.forward(params, cfg_off, ids)
    d = np.abs(np.asarray(l_scaled) - np.asarray(l_plain)).max(axis=-1)[0]
    # positions 0..7: floor(pos/8)=0 → scale 1 → identical
    assert d[:8].max() < 1e-6, d[:8]
    # positions 8..: scale > 1 → outputs differ
    assert d[8:].max() > 1e-6, d[8:]
