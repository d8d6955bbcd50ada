"""het_generate: KV-cache decode parity for the heterogeneous MoE engine.

The het engine (step3p5 / minimax-m3) decodes through
`inference/het_generate.py` — per-layer python-loop caches including the
block-sparse DSA index cache. Parity oracle: re-run the full het_moe
forward for every new token (the discipline of test_generate.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.inference.generate import GenerateConfig, generate
from automodel_tpu.models.moe_lm import het_moe
from automodel_tpu.models.registry import get_model_spec

# MiniMax-M3 shape (tests/unit/test_minimax_m3.py): gemma norms, partial
# rotary, sigmoid-routed MoE + shared expert, block-sparse DSA on layers 1-2
M3_TEXT_HF = {
    "architectures": ["MiniMaxM3SparseForCausalLM"],
    "model_type": "minimax_m3",
    "vocab_size": 128,
    "hidden_size": 32,
    "intermediate_size": 16,
    "dense_intermediate_size": 64,
    "shared_intermediate_size": 16,
    "num_hidden_layers": 3,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 8,
    "rotary_dim": 4,
    "rope_theta": 5000000.0,
    "use_gemma_norm": True,
    "use_qk_norm": True,
    "num_local_experts": 4,
    "num_experts_per_tok": 2,
    "n_shared_experts": 1,
    "scoring_func": "sigmoid",
    "use_routing_bias": True,
    "routed_scaling_factor": 2.0,
    "moe_layer_freq": [0, 1, 1],
    "sparse_attention_config": {
        "use_sparse_attention": True,
        "sparse_attention_freq": [0, 1, 1],
        "sparse_num_index_heads": 2,
        "sparse_index_dim": 8,
        "sparse_block_size": 4,
        "sparse_topk_blocks": 3,
        "sparse_init_block": 1,
        "sparse_local_block": 1,
        "sparse_score_type": "max",
    },
    "rms_norm_eps": 1e-6,
}


def _setup():
    spec = get_model_spec(M3_TEXT_HF)
    cfg = spec.config_from_hf(M3_TEXT_HF, dtype=jnp.float32, remat_policy="none")
    return cfg, het_moe.init(cfg, jax.random.key(0))


def _naive_greedy(params, cfg, ids, n):
    for _ in range(n):
        logits, _ = het_moe.forward(params, cfg, ids)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    return ids


def test_het_greedy_matches_naive():
    """Sparse index cache + per-layer heterogeneity decode == full
    re-forward (also exercises the generate() HetMoEConfig dispatch)."""
    cfg, params = _setup()
    prompt = jnp.asarray(
        np.random.default_rng(5).integers(1, 128, (2, 7)), jnp.int32
    )
    fast = generate(
        params, cfg, prompt, jax.random.key(2), GenerateConfig(max_new_tokens=3)
    )
    slow = _naive_greedy(params, cfg, prompt, 3)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(slow))


@pytest.mark.slow
def test_het_eos_early_stop_pads_with_eos():
    cfg, params = _setup()
    prompt = jnp.asarray(
        np.random.default_rng(6).integers(1, 128, (1, 5)), jnp.int32
    )
    probe = generate(
        params, cfg, prompt, jax.random.key(0), GenerateConfig(max_new_tokens=3)
    )
    eos = int(probe[0, 5 + 1])  # second generated token
    out = generate(
        params, cfg, prompt, jax.random.key(0),
        GenerateConfig(max_new_tokens=6, eos_token_id=eos),
    )
    gen_tokens = np.asarray(out[0, 5:])
    hits = np.flatnonzero(gen_tokens == eos)
    assert len(hits) > 0
    assert (gen_tokens[hits[0]:] == eos).all()


@pytest.mark.slow
def test_het_temperature_sampling_valid_and_uses_shared_filter():
    """Sampled decode stays in-vocab and varies by key; the filter is the
    shared inference.sampling one (top_k=1 sampling == greedy)."""
    cfg, params = _setup()
    prompt = jnp.asarray(
        np.random.default_rng(7).integers(1, 128, (1, 4)), jnp.int32
    )
    g = GenerateConfig(max_new_tokens=4, temperature=1.0)
    a = generate(params, cfg, prompt, jax.random.key(1), g)
    b = generate(params, cfg, prompt, jax.random.key(2), g)
    assert ((np.asarray(a) >= 0) & (np.asarray(a) < 128)).all()
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    topk1 = generate(
        params, cfg, prompt, jax.random.key(3),
        GenerateConfig(max_new_tokens=4, temperature=1.0, top_k=1),
    )
    greedy = generate(
        params, cfg, prompt, jax.random.key(4), GenerateConfig(max_new_tokens=4)
    )
    np.testing.assert_array_equal(np.asarray(topk1), np.asarray(greedy))
