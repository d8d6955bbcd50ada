"""What the decoder-hybrid-decoder tests share: a toy Phi4FlashForCausalLM
(the published config's keys at toy widths: 8 layers, so Mamba at 0, 2 and
4, window attention at 1 and 3, full attention at 5, a GMU at 6 and
cross-attention at 7; 4 query heads of 16 over 2 key/value heads; a window
of 8; a tied head, as the model is published), seeded weights drawn the way
the benchmark draws them (`benchmark/weights.py` with the reference's
`LEAF_RULES`), and the plain reference (benchmark/reference/phi4_flash.py)
asked for its leaves out of the same tree."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.registry import get_model_spec
from benchmark import load_module, weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, VOCAB, WINDOW = 8, 96, 8

HF = {
    "architectures": ["Phi4FlashForCausalLM"], "model_type": "phi4flash",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": LAYERS,
    "num_attention_heads": 4, "num_key_value_heads": 2, "mb_per_layer": 2,
    "sliding_window": WINDOW, "layer_norm_eps": 1e-5, "hidden_act": "silu",
    "vocab_size": VOCAB, "max_position_embeddings": 256,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "published": {"num_hidden_layers": LAYERS},
}

REF = load_module(ROOT, ["benchmark"], "reference", "phi4_flash")


def config(dtype=jnp.float32, hf=HF, **overrides):
    kw = dict(dtype=dtype, remat_policy="none", attn_impl="xla")
    return get_model_spec(hf).config_from_hf(hf, **{**kw, **overrides})


def init_params(cfg, seed=0, dtype=jnp.float32, hf=HF):
    """The benchmark's draw of the program's own tree."""
    shapes = jax.eval_shape(lambda: decoder.init(cfg, jax.random.key(0)))
    return weights.make_params(seed, shapes, dtype, weights.draw_for(REF, hf))


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
