"""Architecture registry: HF `architectures[0]` → TPU-native implementation.

The analog of the reference's `MODEL_ARCH_MAPPING` + `_ModelRegistry.get`
(reference: nemo_automodel/_transformers/registry.py:30-490). Each entry
yields a `ModelSpec` bundling config-adapter, init/forward/param_specs, and
the HF state-dict adapter used for zero-conversion checkpoint I/O.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

from automodel_tpu.models.hybrid import mamba2 as mamba2_module
from automodel_tpu.models.hybrid import nemotron_h as nemotron_h_module
from automodel_tpu.models.hybrid import qwen3_5 as qwen3_5_module
from automodel_tpu.models.hybrid import qwen3_next as qwen3_next_module
from automodel_tpu.models.llm import decoder, families
from automodel_tpu.models.moe_lm import decoder as moe_decoder
from automodel_tpu.models.moe_lm import families as moe_families
from automodel_tpu.models.moe_lm import gemma4 as gemma4_module
from automodel_tpu.models.moe_lm import het_families
from automodel_tpu.models.moe_lm import het_moe as het_moe_module
from automodel_tpu.models.omni import bagel as bagel_module
from automodel_tpu.models.omni import model as omni_module
from automodel_tpu.models.vlm import kimi_vl as kimi_vl_module
from automodel_tpu.models.vlm import llama_nemotron_vl as llama_nemotron_vl_module
from automodel_tpu.models.vlm import minimax_m3_vl as minimax_m3_vl_module
from automodel_tpu.models.vlm import llava as llava_module
from automodel_tpu.models.vlm import qwen3_vl as qwen3_vl_module


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Everything the framework needs to drive one architecture."""

    name: str
    config_from_hf: Callable[..., Any]
    module: Any  # provides init / forward / param_specs / (unembed)
    adapter_name: str = "dense_decoder"  # state-dict adapter key
    adapter_kwargs: dict = dataclasses.field(default_factory=dict)


MODEL_ARCH_MAPPING: dict[str, ModelSpec] = {
    "LlamaForCausalLM": ModelSpec("llama", families.llama_config, decoder),
    "MistralForCausalLM": ModelSpec("mistral", families.mistral_config, decoder),
    "Ministral3ForCausalLM": ModelSpec(
        "ministral3", families.ministral3_config, decoder
    ),
    # Ministral bidirectional retrieval encoder (reference: models/
    # ministral_bidirectional, 188 LoC)
    "Ministral3BidirectionalModel": ModelSpec(
        "ministral_bidirectional", families.ministral_bidirectional_config, decoder
    ),
    "Qwen2ForCausalLM": ModelSpec("qwen2", families.qwen2_config, decoder),
    "Qwen3ForCausalLM": ModelSpec("qwen3", families.qwen3_config, decoder),
    "Gemma2ForCausalLM": ModelSpec("gemma2", families.gemma2_config, decoder),
    "Gemma3ForCausalLM": ModelSpec("gemma3", families.gemma3_config, decoder),
    "Glm4ForCausalLM": ModelSpec(
        "glm4", families.glm4_config, decoder, adapter_kwargs={"style": "glm4"}
    ),
    "Ernie4_5ForCausalLM": ModelSpec("ernie4_5", families.ernie4_5_config, decoder),
    "HunYuanDenseV1ForCausalLM": ModelSpec(
        "hunyuan_dense", families.hunyuan_dense_config, decoder,
        adapter_kwargs={"style": "hunyuan"},
    ),
    "Qwen3MoeForCausalLM": ModelSpec(
        "qwen3_moe", moe_families.qwen3_moe_config, moe_decoder, adapter_name="moe_decoder"
    ),
    "MixtralForCausalLM": ModelSpec(
        "mixtral", moe_families.mixtral_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "mixtral"},
    ),
    "DeepseekV3ForCausalLM": ModelSpec(
        "deepseek_v3", moe_families.deepseek_v3_moe_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "deepseek"},
    ),
    # K-EXAONE: window and full attention mixed (rope in the window layers
    # alone), DeepSeek-style experts, names as deepseek's
    "ExaoneMoeForCausalLM": ModelSpec(
        "exaone_moe", moe_families.exaone_moe_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "deepseek"},
    ),
    "DeepseekV4ForCausalLM": ModelSpec(
        "deepseek_v4", moe_families.deepseek_v4_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "deepseek"},
    ),
    "GptOssForCausalLM": ModelSpec(
        "gpt_oss", moe_families.gpt_oss_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "gpt_oss"},
    ),
    "Glm4MoeForCausalLM": ModelSpec(
        "glm4_moe", moe_families.glm4_moe_config, moe_decoder,
        adapter_name="moe_decoder",
    ),
    # GLM4-MoE-Lite: the GLM4 MoE body on MLA attention (reference:
    # models/glm4_moe_lite/, 387 LoC — reuses deepseek MLA + glm4 adapter)
    "Glm4MoeLiteForCausalLM": ModelSpec(
        "glm4_moe_lite", moe_families.deepseek_v3_moe_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "deepseek"},
    ),
    # Hy-MT2 translation MoE (reference: models/hy_mt2/, 964 LoC)
    "HyMT2ForCausalLM": ModelSpec(
        "hy_mt2", moe_families.hy_mt2_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "hy_mt2"},
    ),
    # Mistral4: DSv3 MLA+MoE body + llama4 position-dependent q-rope
    # scaling (reference: models/mistral4/, 1483 LoC)
    "Mistral4ForCausalLM": ModelSpec(
        "mistral4", moe_families.mistral4_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "deepseek"},
    ),
    # Gemma4-MoE (VL composite; text decoder — reference: models/gemma4_moe,
    # parallel dense+MoE FFN, KV sharing, Gemma4Gate router)
    "Gemma4ForConditionalGeneration": ModelSpec(
        "gemma4_moe", gemma4_module.gemma4_moe_config, gemma4_module,
        adapter_name="gemma4_moe",
    ),
    # Step-3.5: heterogeneous sliding/global attention geometries over
    # per-layer dense/MoE MLPs (reference: models/step3p5) — the het_moe
    # engine
    "Step3p5ForCausalLM": ModelSpec(
        "step3p5", het_families.step3p5_config, het_moe_module,
        adapter_name="het_moe", adapter_kwargs={"style": "step3p5"},
    ),
    # MiMo-V2-Flash: the expert decoder with the window layers' attention an
    # operator of its own (key/value heads, sinks), keys wider than values
    "MiMoV2FlashForCausalLM": ModelSpec(
        "mimo_v2_flash", moe_families.mimo_v2_flash_config, moe_decoder,
        adapter_name="mimo",
    ),
    # Ling 2.0 (reference: models/ling_v2): deepseek-style routed MoE on
    # qk-normed partial-rope GQA; fused query_key_value checkpoint layout
    "BailingMoeV2ForCausalLM": ModelSpec(
        "ling_v2", moe_families.bailing_moe_v2_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "bailing"},
    ),
    # GLM-5.x: MLA+MoE body + GLM indexer with IndexShare (reference:
    # models/glm_moe_dsa — deepseek-style checkpoint naming for MLA/MoE)
    "GlmMoeDsaForCausalLM": ModelSpec(
        "glm_moe_dsa", moe_families.glm_moe_dsa_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "deepseek"},
    ),
    "Ernie4_5_MoeForCausalLM": ModelSpec(
        "ernie4_5_moe", moe_families.ernie4_5_moe_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "ernie"},
    ),
    "HunYuanMoEV1ForCausalLM": ModelSpec(
        "hunyuan_moe", moe_families.hunyuan_moe_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "hunyuan"},
    ),
    "MiniMaxM2ForCausalLM": ModelSpec(
        "minimax_m2", moe_families.minimax_m2_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "minimax"},
    ),
    # MiniMax M3: mixed sparse/dense MoE with block-level DSA (lightning
    # indexer top-k key blocks), gemma norms, swigluoai MLPs (reference:
    # models/minimax_m3_vl/, 2980 LoC — text backbone on the het engine)
    "MiniMaxM3SparseForCausalLM": ModelSpec(
        "minimax_m3", het_families.minimax_m3_text_config, het_moe_module,
        adapter_name="het_moe", adapter_kwargs={"style": "minimax_m3"},
    ),
    # kimi_k2 is checkpoint-compatible with DeepSeek-V3 (reference:
    # components/models/kimi_k2/__init__.py — a 34-LoC alias of deepseek_v3)
    "KimiK2ForCausalLM": ModelSpec(
        "kimi_k2", moe_families.deepseek_v3_moe_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "deepseek"},
    ),
    # DeepSeek-V3.2 = the V3 body + DSA sparse attention (reference:
    # components/models/deepseek_v32 — carries index_topk in its config)
    "DeepseekV32ForCausalLM": ModelSpec(
        "deepseek_v32", moe_families.deepseek_v4_config, moe_decoder,
        adapter_name="moe_decoder", adapter_kwargs={"style": "deepseek"},
    ),
    # Ouro LoopLM: the dense decoder walked total_ut_steps times, with an
    # exit gate (HF names its four norms input_layernorm{,_2} and
    # post_attention_layernorm{,_2})
    "OuroForCausalLM": ModelSpec(
        "ouro", families.ouro_config, decoder, adapter_kwargs={"style": "ouro"},
    ),
    # Jamba (dense sizes): Mamba-1 mixers with attention every
    # attn_layer_period-th layer, no positional encoding; the layers name
    # their mixer (decoder.TransformerConfig.layer_ops)
    "JambaForCausalLM": ModelSpec(
        "jamba", families.jamba_config, decoder, adapter_name="jamba",
    ),
    # Phi-4-mini-flash (SambaY): Mamba-1 and window layers, one full layer
    # whose cache the cross-decoder's layers read, gated memory units;
    # differential attention throughout (no checkpoint adapter yet)
    "Phi4FlashForCausalLM": ModelSpec(
        "phi4flash", families.phi4flash_config, decoder,
    ),
    "BaichuanForCausalLM": ModelSpec(
        "baichuan", families.baichuan_config, decoder,
        adapter_kwargs={"style": "baichuan"},
    ),
    "LlamaBidirectionalModel": ModelSpec(
        "llama_bidirectional", families.llama_bidirectional_config, decoder
    ),
    "LlamaBidirectionalForSequenceClassification": ModelSpec(
        "llama_bidirectional", families.llama_bidirectional_config, decoder
    ),
    "Mamba2ForCausalLM": ModelSpec(
        "mamba2", mamba2_module.from_hf_config, mamba2_module, adapter_name="mamba2"
    ),
    "NemotronHForCausalLM": ModelSpec(
        "nemotron_h", nemotron_h_module.from_hf_config, nemotron_h_module,
        adapter_name="nemotron_h",
    ),
    "NemotronHForCausalLMV3": ModelSpec(
        "nemotron_h", nemotron_h_module.from_hf_config, nemotron_h_module,
        adapter_name="nemotron_h",
    ),
    "Qwen3NextForCausalLM": ModelSpec(
        "qwen3_next", qwen3_next_module.from_hf_config, qwen3_next_module,
        adapter_name="qwen3_next",
    ),
    # Qwen3.5 dense / MoE (VL text decoder) — the qwen3-next engine with the
    # Qwen3.5 checkpoint layout (reference: models/qwen3_5{,_moe}/model.py
    # rebuild both on the Qwen3-Next Block)
    "Qwen3_5ForCausalLM": ModelSpec(
        "qwen3_5", qwen3_5_module.qwen3_5_config, qwen3_5_module,
        adapter_name="qwen3_5", adapter_kwargs={"vl_prefix": False},
    ),
    "Qwen3_5MoeForConditionalGeneration": ModelSpec(
        "qwen3_5_moe", qwen3_5_module.qwen3_5_moe_config, qwen3_5_module,
        adapter_name="qwen3_5",
    ),
    # omni (text·image·audio; reference: components/models/nemotron_omni,
    # qwen2_5_omni) — towers + projectors around a dense decoder backbone
    "OmniForConditionalGeneration": ModelSpec(
        "omni", omni_module.omni_config, omni_module, adapter_name="omni"
    ),
    # BAGEL: unified multimodal understanding + generation — MoT decoder
    # with und/gen expert siblings, SigLIP tower, flow-matching latent head
    # (reference: components/models/bagel/, 4227 LoC)
    "BagelForUnifiedMultimodal": ModelSpec(
        "bagel", bagel_module.bagel_config, bagel_module, adapter_name="bagel"
    ),
    "BagelForConditionalGeneration": ModelSpec(
        "bagel", bagel_module.bagel_config, bagel_module, adapter_name="bagel"
    ),
    # Kimi-VL: MoonViT tower + 2×2-merge projector + DeepSeek-V3 MoE text
    # (reference: models/kimivl, 908 LoC)
    "KimiVLForConditionalGeneration": ModelSpec(
        "kimi_vl", kimi_vl_module.kimi_vl_config, kimi_vl_module,
        adapter_name="kimi_vl",
    ),
    # Kimi-K2.5 VL: MoonViT3d (divided space/time pos emb; image t=0) +
    # DeepseekV3 text (reference: models/kimi_k25_vl/, 1593 LoC)
    "KimiK25VLForConditionalGeneration": ModelSpec(
        "kimi_k25_vl", kimi_vl_module.kimi_k25_vl_config, kimi_vl_module,
        adapter_name="kimi_vl", adapter_kwargs={"style": "k25"},
    ),
    "KimiK25ForConditionalGeneration": ModelSpec(
        "kimi_k25_vl", kimi_vl_module.kimi_k25_vl_config, kimi_vl_module,
        adapter_name="kimi_vl", adapter_kwargs={"style": "k25"},
    ),
    # MiniMax M3 VL: CLIP-style 3D-rope tower + projector/patch-merger +
    # the M3 sparse/dense MoE text backbone (reference: models/minimax_m3_vl)
    "MiniMaxM3SparseForConditionalGeneration": ModelSpec(
        "minimax_m3_vl", minimax_m3_vl_module.minimax_m3_vl_config,
        minimax_m3_vl_module, adapter_name="minimax_m3_vl",
    ),
    # Qwen3-VL-MoE: deepstack ViT + interleaved-MRoPE qwen3-moe text
    # (reference: models/qwen3_vl_moe, 707 LoC)
    "Qwen3VLMoeForConditionalGeneration": ModelSpec(
        "qwen3_vl_moe", qwen3_vl_module.qwen3_vl_moe_config, qwen3_vl_module,
        adapter_name="qwen3_vl_moe",
    ),
    # Llama-Nemotron VL: SigLIP tower + pixel-shuffle + mlp1 projector +
    # bidirectional llama — a retrieval/reranking EMBEDDING model
    # (reference: models/llama_nemotron_vl/, registered under the retrieval
    # tag in _transformers/registry.py:126)
    "LlamaNemotronVLModel": ModelSpec(
        "llama_nemotron_vl", llama_nemotron_vl_module.llama_nemotron_vl_config,
        llama_nemotron_vl_module, adapter_name="llama_nemotron_vl",
    ),
    "LlavaForConditionalGeneration": ModelSpec(
        "llava", llava_module.llava_config, llava_module, adapter_name="llava"
    ),
    "LlavaOnevisionForConditionalGeneration": ModelSpec(
        "llava_onevision", llava_module.llava_config, llava_module, adapter_name="llava"
    ),
}


def register_model(arch: str, spec: ModelSpec) -> None:
    MODEL_ARCH_MAPPING[arch] = spec


def get_model_spec(arch_or_hf_config: "str | Mapping") -> ModelSpec:
    if isinstance(arch_or_hf_config, str):
        arch = arch_or_hf_config
    else:
        archs = arch_or_hf_config.get("architectures") or []
        arch = archs[0] if archs else ""
    try:
        return MODEL_ARCH_MAPPING[arch]
    except KeyError:
        raise KeyError(
            f"Architecture '{arch}' is not registered; known: "
            f"{sorted(MODEL_ARCH_MAPPING)}"
        ) from None
