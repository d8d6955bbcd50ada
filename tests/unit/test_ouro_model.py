"""OuroForCausalLM on the normal path: `forward` against the plain reference
(benchmark/reference/ouro.py) on seeded weights at 3 layers x 4 passes, the
config adapter against the catalog's row, the checkpoint keys both ways.

Tolerances, with their reasons. Float32 `forward` and the float32 reference
do the same arithmetic in another order (a scan over stacked layers against
a Python loop, einsum against matmul): logits of standard deviation 1.03
agree to 2.3e-6 here and the gates to 7e-7, so 5e-5 and 1e-5 leave some
twenty times of room; the same model in bfloat16 reads 0.17, which is what
the limit has to refuse (and does, below), one pass fewer 2.5."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.checkpoint.hf_adapter import get_adapter
from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.families import ouro_config
from automodel_tpu.models.registry import get_model_spec
from tests import ouro_case

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LOGIT_TOL = 5e-5   # float32 against float32: see the module docstring
GATE_TOL = 1e-5    # a probability: a sigmoid of one dot product
IDS = np.random.default_rng(7).integers(0, ouro_case.VOCAB, (2, 24))


@pytest.fixture(scope="module")
def case():
    cfg = ouro_case.config()
    params = ouro_case.init_params(cfg)
    return cfg, params, ouro_case.reference(params, IDS)


def test_forward_matches_the_reference_logits_and_gates(case):
    cfg, params, (want, gates) = case
    assert cfg.num_passes == 4 and cfg.exit_gate and cfg.use_post_norms
    with jax.default_matmul_precision("highest"):
        got, gate_logits = decoder.forward(
            params, cfg, jnp.asarray(IDS), return_gate_logits=True)
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL
    assert gate_logits.shape == (4, 2, 24)
    assert np.abs(np.asarray(jax.nn.sigmoid(gate_logits)) - gates).max() < GATE_TOL
    # the four passes' gates differ (a gate read four times off one state,
    # or a final norm applied once, would not)
    assert np.abs(gates[1:] - gates[:-1]).max() > 1e-3
    # the exit distribution the reference derives from them is one
    p = np.asarray(ouro_case.REF.exit_distribution(jnp.asarray(gates)))
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    # plain call: the logits alone, the same
    with jax.default_matmul_precision("highest"):
        alone = decoder.forward(params, cfg, jnp.asarray(IDS))
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(got))


def test_one_pass_fewer_or_bfloat16_is_refused_by_the_tolerance(case):
    cfg, params, (want, _) = case
    with jax.default_matmul_precision("highest"):
        three = decoder.forward(
            params, dataclasses.replace(cfg, num_passes=3), jnp.asarray(IDS))
    assert np.abs(np.asarray(three) - want).max() > 100 * LOGIT_TOL
    low = decoder.forward(
        params, dataclasses.replace(cfg, dtype=jnp.bfloat16), jnp.asarray(IDS))
    assert np.abs(np.asarray(low, np.float32) - want).max() > 10 * LOGIT_TOL


def test_gate_logits_need_a_gate():
    cfg = dataclasses.replace(ouro_case.config(), exit_gate=False)
    params = decoder.init(cfg, jax.random.key(0))
    assert "exit_gate" not in params
    with pytest.raises(ValueError, match="exit gate"):
        decoder.forward(params, cfg, jnp.asarray(IDS), return_gate_logits=True)


def test_config_from_the_catalog_row_unchanged():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    hf = dict(row["config"], architectures=["OuroForCausalLM"])
    spec = get_model_spec(hf)
    assert spec.name == "ouro" and spec.module is decoder
    cfg = spec.config_from_hf(hf)
    assert (cfg.num_layers, cfg.num_passes, cfg.hidden_size) == (48, 4, 2048)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim) == (16, 16, 128)
    assert (cfg.intermediate_size, cfg.vocab_size) == (5632, 49152)
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6
    assert cfg.use_post_norms and cfg.exit_gate and not cfg.tie_word_embeddings
    assert cfg.sliding_window is None and not cfg.attention_bias
    # 2.668 B parameters, the layers' counted once however often they run
    shapes = jax.eval_shape(lambda: decoder.init(cfg, jax.random.key(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 48 * 51_388_416 + 2 * 49152 * 2048 + 2048 + 2049


def test_adaptive_exit_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="early_exit_threshold=0.5"):
        ouro_config(dict(ouro_case.HF, early_exit_threshold=0.5))
    with pytest.raises(NotImplementedError, match="sliding-window"):
        ouro_config(dict(ouro_case.HF, use_sliding_window=True))


def test_checkpoint_keys_round_trip(case):
    cfg, params, _ = case
    spec = get_model_spec(ouro_case.HF)
    adapter = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    state = dict(adapter.to_hf(params))
    for name in ("input_layernorm", "input_layernorm_2",
                 "post_attention_layernorm", "post_attention_layernorm_2"):
        assert f"model.layers.2.{name}.weight" in state
    assert state["model.early_exit_gate.weight"].shape == (1, cfg.hidden_size)
    assert state["model.early_exit_gate.bias"].shape == (1,)
    assert "model.layers.0.pre_feedforward_layernorm.weight" not in state
    # which norm goes under which name: the attention branch's own norm
    np.testing.assert_array_equal(
        state["model.layers.1.input_layernorm_2.weight"],
        np.asarray(params["layers"]["post_attn_out_norm"]["scale"][1]))
    np.testing.assert_array_equal(
        state["model.layers.1.post_attention_layernorm.weight"],
        np.asarray(params["layers"]["post_attn_norm"]["scale"][1]))
    back = adapter.from_hf(state.__getitem__)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
