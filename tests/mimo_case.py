"""What the MiMo-V2-Flash tests share: a toy MiMoV2FlashForCausalLM (the
published config's keys at toy widths: 6 layers full, window x 2, full,
window x 2 with a window of 8 tokens; 4 query heads over 1 key/value head in
the full layers and over 2 with sinks in the window layers; keys 160 wide,
stored at 256, beside values 16 wide, scaled by 0.707; one leading dense
layer, 8 experts top-2, no shared expert), seeded weights drawn the way the
benchmark draws them (`benchmark/weights.py`: sinks near ln 128, a selection
bias that is not 0), and the plain reference
(benchmark/reference/mimo_v2_flash.py) asked for its leaves out of the same
tree."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.models.moe_lm import decoder as moe_decoder
from automodel_tpu.models.registry import get_model_spec
from benchmark import load_module, weights
from tests.jamba_case import flat_leaves, log_softmax  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, VOCAB, WINDOW, EXPERTS = 6, 96, 8, 8

HF = {
    "architectures": ["MiMoV2FlashForCausalLM"], "model_type": "mimo_v2_flash",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": LAYERS,
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 160,
    "v_head_dim": 16, "swa_num_attention_heads": 4,
    "swa_num_key_value_heads": 2, "swa_head_dim": 160, "swa_v_head_dim": 16,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1],
    "moe_layer_freq": [0] + [1] * (LAYERS - 1),
    "sliding_window": WINDOW, "rope_theta": 5000000, "swa_rope_theta": 10000,
    "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
    "add_full_attention_sink_bias": False, "add_swa_attention_sink_bias": True,
    "attention_bias": False, "layernorm_epsilon": 1e-5,
    "max_position_embeddings": 512, "moe_intermediate_size": 32,
    "n_routed_experts": EXPERTS, "n_shared_experts": None,
    "num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "tie_word_embeddings": False,
    "vocab_size": VOCAB, "hidden_act": "silu",
    "published": {"num_hidden_layers": LAYERS},
}

REF = load_module(ROOT, ["benchmark"], "reference", "mimo_v2_flash")


def share_hf(held: int, first: int = 0, hf=HF) -> dict:
    """The toy as one chip's share: `held` experts from `first` on, the
    router as wide as the whole model's."""
    return {**hf, "n_routed_experts": held,
            "router_num_experts": hf["n_routed_experts"],
            "first_held_expert": first}


def config(hf=HF, dtype=jnp.float32, **overrides):
    kw = dict(dtype=dtype, remat_policy="none", attn_impl="xla")
    return get_model_spec(hf).config_from_hf(hf, **{**kw, **overrides})


def init_params(cfg, seed=0, dtype=jnp.float32, hf=HF):
    """The benchmark's draw of the program's own tree."""
    shapes = jax.eval_shape(lambda: moe_decoder.init(cfg, jax.random.key(0)))
    return weights.make_params(seed, shapes, dtype, weights.draw_for(REF, hf))


def reference(params, ids, control=None, hf=HF):
    """Logits (B, S, V) of the plain reference over `ids` (B, S), from the
    leaves of the stacked `params`."""
    flat = flat_leaves(params)

    def leaf(path):
        return jnp.asarray(flat[path])

    def layer(stack, l):
        return {p[len(stack) + 1:]: jnp.asarray(v[l]) for p, v in flat.items()
                if p.split("/")[0] == stack}

    h = REF.hidden_states(hf, jnp.asarray(ids, jnp.int32), leaf, layer, control)
    B, S, H = h.shape
    logits = REF.logits_at(hf, h.reshape(B * S, H), leaf, control)
    return np.asarray(logits).reshape(B, S, -1)
