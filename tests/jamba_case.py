"""What the state-space tests share: a toy JambaForCausalLM (the published
config's keys at toy widths: 6 layers, attention at i % 3 == 1, so layers 1
and 4; 4 query heads over ONE key/value head; a tied head, as the 3B sizes
are published), seeded weights drawn the way the benchmark draws them
(`benchmark/weights.py` with the reference's `LEAF_RULES`: norms that are
not 1, a `dt_bias` that makes the state remember), and the plain reference
(benchmark/reference/jamba.py) asked for its leaves out of the same tree."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.registry import get_model_spec
from benchmark import load_module, weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, VOCAB = 6, 96

HF = {
    "architectures": ["JambaForCausalLM"], "model_type": "jamba",
    "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": LAYERS,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "attn_layer_period": 3, "attn_layer_offset": 1,
    "expert_layer_period": 2, "expert_layer_offset": 1,
    "num_experts": 1, "num_experts_per_tok": 1,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 4, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "vocab_size": VOCAB, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "max_position_embeddings": 256, "sliding_window": None,
    "tie_word_embeddings": True, "published": {"num_hidden_layers": LAYERS},
}

REF = load_module(ROOT, ["benchmark"], "reference", "jamba")


def config(dtype=jnp.float32, **overrides):
    kw = dict(dtype=dtype, remat_policy="none", attn_impl="xla")
    return get_model_spec(HF).config_from_hf(HF, **{**kw, **overrides})


def init_params(cfg, seed=0, dtype=jnp.float32):
    """The benchmark's draw of the program's own tree."""
    shapes = jax.eval_shape(lambda: decoder.init(cfg, jax.random.key(0)))
    return weights.make_params(seed, shapes, dtype, weights.draw_for(REF, HF))


def flat_leaves(params) -> dict:
    return {path: np.asarray(leaf, np.float32)
            for path, leaf in weights.tree_paths(params).items()}


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


def log_softmax(logits: np.ndarray) -> np.ndarray:
    logits = logits.astype(np.float64)
    best = logits.max(-1, keepdims=True)
    return logits - best - np.log(np.exp(logits - best).sum(-1, keepdims=True))
