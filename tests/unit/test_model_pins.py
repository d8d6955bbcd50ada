"""Numeric golden pins for families without a torch-oracle parity test.

transformers 4.57 (the in-env version) predates these architectures, so
their other tests are structural/self-consistency only (see
test_model_tail.py) — a transposed weight or a wrong norm epsilon could
pass every one of them. Each family here pins a fixed-seed tiny model's
logits against a COMMITTED reference (tests/golden_values/model_pins/);
any numeric drift in the forward path fails the pin (reference discipline:
tests/ci_tests/golden_values/ committed JSONL).

The configs below are DELIBERATE copies of the tiny configs in the other
test files: a pin must not silently move when another test edits its
config. Regenerate after an intentional numeric change with:

    AM_WRITE_PINS=1 python -m pytest tests/unit/test_model_pins.py -q

and commit the diff (review it — a pin change IS a semantics change).
"""

import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.registry import get_model_spec

#: compile-heavy (13 families × full forward) — slow tier; the pins still
#: gate CI (the full suite runs slow) without costing the smoke budget
pytestmark = pytest.mark.slow

PIN_DIR = pathlib.Path(__file__).parent.parent / "golden_values" / "model_pins"
WRITE = bool(os.environ.get("AM_WRITE_PINS"))

_TEXT = "text"
_VLM = "vlm"
_BAGEL = "bagel"

FAMILIES = {
    "baichuan": (_TEXT, {
        "architectures": ["BaichuanForCausalLM"], "model_type": "baichuan",
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4, "rms_norm_eps": 1e-6,
    }),
    "ling_v2": (_TEXT, {
        "architectures": ["BailingMoeV2ForCausalLM"], "model_type": "bailing_moe",
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "use_qk_norm": True, "partial_rotary_factor": 0.5,
        "num_experts": 4, "num_shared_experts": 1, "num_experts_per_tok": 2,
        "n_group": 2, "topk_group": 2, "moe_intermediate_size": 16,
        "first_k_dense_replace": 1, "score_function": "sigmoid",
        "routed_scaling_factor": 1.0, "norm_topk_prob": True,
        "moe_router_enable_expert_bias": True,
    }),
    "glm_moe_dsa": (_TEXT, {
        "architectures": ["GlmMoeDsaForCausalLM"], "model_type": "glm_moe_dsa",
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
    }),
    "gemma4_moe": (_TEXT, {
        "architectures": ["Gemma4ForConditionalGeneration"], "model_type": "gemma4",
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
    }),
    "step3p5": (_TEXT, {
        "architectures": ["Step3p5ForCausalLM"], "model_type": "step3p5",
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
    }),
    "mimo_v2_flash": (_TEXT, {
        "architectures": ["MiMoV2FlashForCausalLM"], "model_type": "mimo_v2_flash",
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "v_head_dim": 8,
        "swa_num_attention_heads": 2, "swa_num_key_value_heads": 1,
        "swa_head_dim": 8, "swa_v_head_dim": 4,
        "attention_value_scale": 0.707,
        "hybrid_layer_pattern": [0, 1, 1, 0],
        "sliding_window": 8,
        "rope_theta": 5000000.0, "swa_rope_theta": 10000.0,
        "partial_rotary_factor": 0.5,
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True,
        "n_routed_experts": 4, "num_experts_per_tok": 2,
        "moe_intermediate_size": 16, "scoring_func": "sigmoid",
        "n_group": 2, "topk_group": 2, "norm_topk_prob": True,
        "moe_layer_freq": [0, 1, 1, 1], "n_shared_experts": 1,
    }),
    "minimax_m3": (_TEXT, {
        "architectures": ["MiniMaxM3SparseForCausalLM"], "model_type": "minimax_m3",
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 16,
        "dense_intermediate_size": 64, "shared_intermediate_size": 16,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "rotary_dim": 4,
        "rope_theta": 5000000.0, "use_gemma_norm": True, "use_qk_norm": True,
        "num_local_experts": 4, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "scoring_func": "sigmoid",
        "use_routing_bias": True, "routed_scaling_factor": 2.0,
        "moe_layer_freq": [0, 1, 1],
        "sparse_attention_config": {
            "use_sparse_attention": True, "sparse_attention_freq": [0, 1, 1],
            "sparse_num_index_heads": 2, "sparse_index_dim": 8,
            "sparse_block_size": 4, "sparse_topk_blocks": 3,
            "sparse_init_block": 1, "sparse_local_block": 1,
        },
        "rms_norm_eps": 1e-6,
    }),
    "qwen3_5": (_TEXT, {
        "architectures": ["Qwen3_5ForCausalLM"], "model_type": "qwen3_5",
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "layer_types": ["linear_attention", "full_attention"],
        "linear_num_value_heads": 4, "linear_num_key_heads": 2,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    }),
    "qwen3_5_moe": (_TEXT, {
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
    }),
    "kimi_vl": (_VLM, {
        "architectures": ["KimiVLForConditionalGeneration"], "model_type": "kimi_vl",
        "media_placeholder_token_id": 120,
        "vision_config": {
            "patch_size": 14, "init_pos_emb_height": 8, "init_pos_emb_width": 8,
            "num_attention_heads": 2, "num_hidden_layers": 2,
            "hidden_size": 32, "intermediate_size": 48,
            "merge_kernel_size": [2, 2],
        },
        "text_config": {
            "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 4,
            "n_routed_experts": 4, "n_shared_experts": 1,
            "num_experts_per_tok": 2, "moe_intermediate_size": 16,
            "first_k_dense_replace": 1, "norm_topk_prob": True,
            "kv_lora_rank": 16, "q_lora_rank": 12,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
        },
    }),
    "qwen3_vl_moe": (_VLM, {
        "architectures": ["Qwen3VLMoeForConditionalGeneration"],
        "model_type": "qwen3_vl_moe",
        "image_token_id": 120,
        "vision_config": {
            "patch_size": 14, "temporal_patch_size": 2, "spatial_merge_size": 2,
            "num_heads": 2, "depth": 3, "hidden_size": 32, "intermediate_size": 48,
            "out_hidden_size": 32, "num_position_embeddings": 64,
            "deepstack_visual_indexes": [0, 1],
        },
        "text_config": {
            "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 8,
            "num_experts": 4, "num_experts_per_tok": 2,
            "moe_intermediate_size": 16, "norm_topk_prob": True,
            "rope_scaling": {"mrope_section": [2, 1, 1], "mrope_interleaved": True},
        },
    }),
    "minimax_m3_vl": (_VLM, {
        "architectures": ["MiniMaxM3SparseForConditionalGeneration"],
        "model_type": "minimax_m3_vl",
        "image_token_index": 120, "projector_hidden_size": 48,
        "multimodal_projector_bias": True, "patch_merge_bias": True,
        "vision_config": {
            "hidden_size": 32, "num_attention_heads": 2, "num_hidden_layers": 2,
            "intermediate_size": 48, "patch_size": 14,
            "img_token_compression_config": {
                "spatial_merge_size": 2, "temporal_patch_size": 2,
            },
        },
        "text_config": {
            "architectures": ["MiniMaxM3SparseForCausalLM"],
            "vocab_size": 128, "hidden_size": 32, "intermediate_size": 16,
            "dense_intermediate_size": 64, "shared_intermediate_size": 16,
            "num_hidden_layers": 3, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 8, "rotary_dim": 4,
            "use_gemma_norm": True, "use_qk_norm": True,
            "num_local_experts": 4, "num_experts_per_tok": 2,
            "n_shared_experts": 1, "scoring_func": "sigmoid",
            "use_routing_bias": True, "routed_scaling_factor": 2.0,
            "moe_layer_freq": [0, 1, 1],
            "sparse_attention_config": {
                "use_sparse_attention": True, "sparse_attention_freq": [0, 1, 1],
                "sparse_num_index_heads": 2, "sparse_index_dim": 8,
                "sparse_block_size": 4, "sparse_topk_blocks": 3,
                "sparse_init_block": 1, "sparse_local_block": 1,
            },
        },
    }),
    "llama_nemotron_vl": (_VLM, {
        "architectures": ["LlamaNemotronVLModel"], "model_type": "llama_nemotron_vl",
        "img_context_token_id": 120, "downsample_ratio": 0.5,
        "select_layer": -1, "pooling": "avg",
        "vision_config": {
            "model_type": "siglip_vision_model",
            "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 2,
            "num_attention_heads": 2, "image_size": 56, "patch_size": 14,
        },
        "llm_config": {
            "architectures": ["LlamaBidirectionalModel"],
            "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "pooling": "avg",
        },
    }),
    "bagel": (_BAGEL, {
        "architectures": ["BagelForUnifiedMultimodal"], "model_type": "bagel",
        "visual_gen": True,
        "llm_config": {
            "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "qk_norm": True,
        },
        "vision_config": {
            "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 2,
            "num_attention_heads": 2, "image_size": 56, "patch_size": 14,
        },
        "vit_max_num_patch_per_side": 8, "latent_patch_size": 2,
        "max_latent_size": 8, "vae_config": {"z_channels": 4, "downsample": 8},
    }),
}


def _vlm_inputs(image_token: int, n_img: int = 4, B: int = 2, S: int = 24):
    rng = np.random.default_rng(0)
    text = rng.integers(1, 100, (B, S - n_img), dtype=np.int32)
    ids = np.concatenate(
        [text[:, :4], np.full((B, n_img), image_token, np.int32), text[:, 4:]],
        axis=1,
    )
    pixels = rng.normal(size=(B, 56, 56, 3)).astype(np.float32)
    return jnp.asarray(ids), jnp.asarray(pixels)


def _run(name):
    kind, hf = FAMILIES[name]
    spec = get_model_spec(hf)
    cfg = spec.config_from_hf(hf, dtype=jnp.float32, remat_policy="none")
    params = spec.module.init(cfg, jax.random.key(0))
    if kind == _BAGEL:
        rng = np.random.default_rng(0)
        B, S = 2, 40
        ids = jnp.asarray(rng.integers(1, 100, (B, S), dtype=np.int32))
        tt = np.zeros((B, S), np.int32)
        tt[:, 2:18] = 1
        tt[:, 20:36] = 2
        pix = jnp.asarray(rng.normal(size=(B, 56, 56, 3)).astype(np.float32))
        lat = jnp.asarray(rng.normal(size=(B, 4, 8, 8)).astype(np.float32))
        t = jnp.asarray(rng.normal(size=(B,)).astype(np.float32))
        out, _gen = spec.module.forward(
            params, cfg, ids, jnp.asarray(tt), pixel_values=pix,
            latents=lat, timesteps=t, rng=jax.random.key(1),
        )
    elif kind == _VLM:
        tok = int(
            hf.get("image_token_id")
            or hf.get("image_token_index")
            or hf.get("media_placeholder_token_id")
            or hf.get("img_context_token_id")
        )
        ids, pixels = _vlm_inputs(tok)
        out = spec.module.forward(params, cfg, ids, pixels)
    else:
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(1, 100, (2, 16), dtype=np.int32))
        out = spec.module.forward(params, cfg, ids)
    if isinstance(out, tuple):
        out = out[0]
    out = np.asarray(out, dtype=np.float64)
    return {
        "arch": hf["architectures"][0],
        "shape": list(out.shape),
        "slice": out[0, -1, :16].tolist(),
        "mean": float(out.mean()),
        "std": float(out.std()),
    }


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_model_pin(name):
    pin_file = PIN_DIR / f"{name}.json"
    got = _run(name)
    if WRITE:
        PIN_DIR.mkdir(parents=True, exist_ok=True)
        pin_file.write_text(json.dumps(got, indent=1) + "\n")
        pytest.skip(f"wrote {pin_file}")
    assert pin_file.exists(), (
        f"missing committed pin {pin_file} — generate with AM_WRITE_PINS=1"
    )
    want = json.loads(pin_file.read_text())
    assert got["shape"] == want["shape"]
    np.testing.assert_allclose(got["slice"], want["slice"], atol=1e-5, rtol=0)
    np.testing.assert_allclose(got["mean"], want["mean"], atol=1e-6, rtol=0)
    np.testing.assert_allclose(got["std"], want["std"], atol=1e-6, rtol=0)
