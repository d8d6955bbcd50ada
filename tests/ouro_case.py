"""What the looped-decoder tests share: a toy OuroForCausalLM (the published
config's keys at toy widths: 3 layers walked 4 times, 4 heads without
grouping, sandwich norms, an exit gate), seeded weights whose norms and gate
bias are not their init's ones and zeros, and the plain reference
(benchmark/reference/ouro.py) asked for its leaves out of the same tree."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.registry import get_model_spec
from benchmark import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, PASSES, VOCAB = 3, 4, 96

HF = {
    "architectures": ["OuroForCausalLM"], "model_type": "ouro",
    "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": LAYERS,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "vocab_size": VOCAB, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": None, "max_position_embeddings": 256,
    "layer_types": ["full_attention"] * LAYERS, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "total_ut_steps": PASSES, "early_exit_threshold": 1,
}

REF = load_module(ROOT, ["benchmark"], "reference", "ouro")


def config(dtype=jnp.float32, **overrides):
    kw = dict(dtype=dtype, remat_policy="none", attn_impl="xla")
    return get_model_spec(HF).config_from_hf(HF, **{**kw, **overrides})


def init_params(cfg, seed=0):
    """`decoder.init` with every norm scale 1 + 0.1 N and the gate's bias
    0.3: a norm left out, or applied in the wrong place, then shows."""
    params = decoder.init(cfg, jax.random.key(seed))
    key = jax.random.key(seed + 1)
    for name in ("input_norm", "post_attn_norm", "post_attn_out_norm",
                 "post_mlp_norm"):
        key, sub = jax.random.split(key)
        scale = params["layers"][name]["scale"]
        params["layers"][name]["scale"] = (
            1.0 + 0.1 * jax.random.normal(sub, scale.shape))
    key, sub = jax.random.split(key)
    params["final_norm"]["scale"] = (
        1.0 + 0.1 * jax.random.normal(sub, params["final_norm"]["scale"].shape))
    params["exit_gate"]["bias"] = jnp.asarray([0.3])
    return params


def flat_leaves(params) -> dict:
    out = {}

    def walk(tree, prefix):
        for name, sub in tree.items():
            path = f"{prefix}/{name}" if prefix else name
            if isinstance(sub, dict):
                walk(sub, path)
            else:
                out[path] = np.asarray(sub, np.float32)

    walk(params, "")
    return out


def reference(params, ids, control=None):
    """(logits (B, S, V), gate probabilities (passes, B, S)) of the plain
    reference over `ids` (B, S), from the leaves of the stacked `params`."""
    flat = flat_leaves(params)

    def leaf(path):
        return jnp.asarray(flat[path])

    def layer(stack, l):
        return {p[len(stack) + 1:]: jnp.asarray(v[l]) for p, v in flat.items()
                if p.split("/")[0] == stack}

    ids = jnp.asarray(ids, jnp.int32)
    states = REF.pass_states(HF, ids, leaf, layer, control)
    B, S, H = states[-1].shape
    logits = REF.logits_at(HF, states[-1].reshape(B * S, H), leaf, control)
    return (np.asarray(logits).reshape(B, S, -1),
            np.asarray(REF.gate_probabilities(HF, states, leaf)))


def log_softmax(logits: np.ndarray) -> np.ndarray:
    logits = logits.astype(np.float64)
    best = logits.max(-1, keepdims=True)
    return logits - best - np.log(np.exp(logits - best).sum(-1, keepdims=True))
