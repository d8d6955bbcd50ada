"""The plain reference against the program at a tiny width, float32, CPU:
the program's TRAINING forward (no cache, no pages) on the benchmark's own
weights gives the reference's logits; and the weights are the same numbers
whether made whole for the program or layer by layer for the reference."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import deepseek_v3 as ref

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "tiny", "tiny_deepseek_v3.json")) as _f:
    CFG = json.load(_f)
SEED = 2**31 + 5
DRAW = weights.draw_for(ref, CFG)
DEPTH = DRAW.depth


@pytest.fixture(scope="module")
def program():
    from automodel_tpu.models.registry import get_model_spec

    hf = {k: v for k, v in CFG.items() if not isinstance(v, dict)}
    spec = get_model_spec(hf)
    model_cfg = spec.config_from_hf(
        hf, dtype=jnp.float32, remat_policy="none", attn_impl="xla")
    shapes = jax.eval_shape(lambda: spec.module.init(model_cfg, jax.random.key(0)))
    params = weights.make_params(SEED, shapes, jnp.float32, DRAW)
    return spec, model_cfg, shapes, params


def test_every_leaf_of_the_program_has_a_rule(program):
    _, _, shapes, params = program
    flat = weights.tree_paths(shapes)
    assert {"embed/embedding", "lm_head/kernel", "final_norm/scale",
            "moe_layers/moe/gate/weight", "moe_layers/moe/gate/e_score_bias",
            "moe_layers/moe/experts/down_proj/kernel",
            "dense_layers/down_proj/kernel"} <= set(flat)
    got = weights.tree_paths(params)
    assert {p: a.shape for p, a in got.items()} == {
        p: s.shape for p, s in flat.items()}


@pytest.mark.parametrize("stack,layer", [("dense_layers", 0), ("moe_layers", 0),
                                         ("moe_layers", 1)])
def test_a_layer_made_alone_is_the_layer_of_the_whole(program, stack, layer):
    _, _, shapes, params = program
    flat = weights.tree_paths(shapes)
    alone = weights.make_layer(weights.root_key(SEED), flat, stack, layer,
                               DRAW, jnp.float32)
    whole = weights.tree_paths(params)
    # to one unit in the last place of float32: inside the whole's loop the
    # compiler may contract a multiply-add that it leaves apart outside it.
    # Rounded to bf16 as served, that moves about one element in 65,536 by
    # one bf16 step, far under what the check's limits see.
    for path, leaf in alone.items():
        np.testing.assert_allclose(
            leaf, whole[f"{stack}/{path}"][layer], rtol=3e-7, atol=0)


def test_weights_differ_by_seed_layer_and_leaf(program):
    _, _, shapes, params = program
    flat = weights.tree_paths(params)
    a = flat["moe_layers/moe/experts/up_proj/kernel"]
    assert not np.allclose(a[0], a[1])
    assert not np.allclose(a[0], flat["moe_layers/moe/experts/gate_proj/kernel"][0])
    other = weights.make_params(SEED + 1, shapes, jnp.float32, DRAW)
    assert not np.allclose(a, other["moe_layers"]["moe"]["experts"]["up_proj"]["kernel"])
    # the rules: residual writers are scaled down, norm scales sit near 1
    assert float(jnp.std(flat["moe_layers/o_proj/kernel"])) == pytest.approx(
        64 ** -0.5 * (2 * DEPTH) ** -0.5, rel=0.05)
    assert float(jnp.mean(flat["final_norm/scale"])) == pytest.approx(1.0, abs=0.05)


def test_training_forward_gives_the_reference_logits(program):
    from automodel_tpu.models.llm.decoder import unembed

    spec, model_cfg, shapes, params = program
    ids = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 24))
    hidden, _aux = spec.module.forward(
        params, model_cfg, jnp.asarray(ids), return_hidden=True)
    got = unembed(params, model_cfg, hidden)

    flat = weights.tree_paths(shapes)
    key = weights.root_key(SEED)
    leaf = weights.tree_paths(params).__getitem__
    h = ref.hidden_states(
        CFG, jnp.asarray(ids), leaf,
        lambda stack, l: weights.make_layer(key, flat, stack, l, DRAW, jnp.float32))
    want = ref.logits_at(CFG, h.reshape(-1, h.shape[-1]), leaf)
    np.testing.assert_allclose(
        np.asarray(got).reshape(want.shape), want, atol=2e-4, rtol=0)
    assert float(jnp.std(want)) > 0.5   # logits are not degenerate


def test_route_uses_the_selection_bias_for_choice_only():
    cfg = dict(CFG, n_routed_experts=4, num_experts_per_tok=2)
    x = jnp.eye(4, dtype=jnp.float32)
    w = {"moe/gate/weight": jnp.diag(jnp.array([3.0, 2.0, 1.0, 0.0])),
         "moe/gate/e_score_bias": jnp.array([0.0, 0.0, 0.0, 10.0])}
    combine = ref.route(x[:1], w, cfg)      # token 0: logits (3, 0, 0, 0)
    s = jax.nn.sigmoid(jnp.array([3.0, 0.0]))
    # expert 3 is chosen by its bias, but weighted by its raw score
    want = jnp.array([s[0], 0.0, 0.0, s[1]]) / s.sum() * cfg["routed_scaling_factor"]
    np.testing.assert_allclose(combine[0], want, rtol=1e-6)
