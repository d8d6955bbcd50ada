"""What the window-attention and held-expert tests share: a toy
ExaoneMoeForCausalLM (the published config's keys at toy widths: 8 layers
`LLLG LLLG`, a window of 8 tokens, 4 query heads over 2 key/value heads, one
leading dense layer, 8 experts top-2 with a shared expert), seeded weights
drawn the way the benchmark draws them (`benchmark/weights.py`: norms that
are not 1, a selection bias that is not 0), and the plain reference
(benchmark/reference/exaone_moe.py) asked for its leaves out of the same
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
LAYERS, VOCAB, WINDOW, EXPERTS = 8, 96, 8, 8

HF = {
    "architectures": ["ExaoneMoeForCausalLM"], "model_type": "exaone_moe",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": LAYERS,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "first_k_dense_replace": 1, "hidden_act": "silu",
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * (LAYERS - 1),
    "sliding_window": WINDOW, "sliding_window_pattern": "LLLG",
    "max_position_embeddings": 512, "moe_intermediate_size": 32,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "num_experts": EXPERTS, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "num_nextn_predict_layers": 0, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "vocab_size": VOCAB,
    "published": {"num_hidden_layers": LAYERS},
}

REF = load_module(ROOT, ["benchmark"], "reference", "exaone_moe")


def share_hf(held: int, first: int = 0) -> dict:
    """The toy as one chip's share: `held` experts from `first` on, the
    router as wide as the whole model's."""
    return {**HF, "num_experts": held, "router_num_experts": EXPERTS,
            "first_held_expert": first}


def config(hf=HF, dtype=jnp.float32, **overrides):
    kw = dict(dtype=dtype, remat_policy="none", attn_impl="xla")
    return get_model_spec(hf).config_from_hf(hf, **{**kw, **overrides})


def init_params(cfg, seed=0, dtype=jnp.float32, hf=HF):
    """The benchmark's draw of the program's own tree."""
    shapes = jax.eval_shape(lambda: moe_decoder.init(cfg, jax.random.key(0)))
    return weights.make_params(seed, shapes, dtype, weights.draw_for(REF, hf))


def reference(params, ids, control=None, hf=HF, hidden=False):
    """Logits (B, S, V) of the plain reference over `ids` (B, S), from the
    leaves of the stacked `params` (`hidden`: the final hidden states)."""
    flat = flat_leaves(params)

    def leaf(path):
        return jnp.asarray(flat[path])

    def layer(stack, l):
        return {p[len(stack) + 1:]: jnp.asarray(v[l]) for p, v in flat.items()
                if p.split("/")[0] == stack}

    h = REF.hidden_states(hf, jnp.asarray(ids, jnp.int32), leaf, layer, control)
    if hidden:
        return np.asarray(h)
    B, S, H = h.shape
    logits = REF.logits_at(hf, h.reshape(B * S, H), leaf, control)
    return np.asarray(logits).reshape(B, S, -1)
