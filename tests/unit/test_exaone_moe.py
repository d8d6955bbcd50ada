"""ExaoneMoeForCausalLM (K-EXAONE): window and full attention mixed with the
rotary embedding on the window layers alone, a per-head norm on q and k,
DeepSeek-style experts of which a layer may hold one chip's SHARE. The family
mapping, the published parameter count from shapes alone, the training
forward and `generate` against the plain reference at a toy size, the
checkpoint names, the sum of the shares, and every refusal by name."""

import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.checkpoint.hf_adapter import get_adapter
from automodel_tpu.models.llm.decoder import make_freq_for
from automodel_tpu.models.moe_lm import decoder as moe_decoder
from automodel_tpu.models.registry import get_model_spec
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.layer import moe_forward
from tests import exaone_case as ec

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOL = 2e-5  # float32 against float32 at "highest": the order of the sums


@pytest.fixture(scope="module")
def case():
    cfg = ec.config()
    return cfg, ec.init_params(cfg)


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(0, ec.VOCAB, shape)


def _forward(params, cfg, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(moe_decoder.forward(params, cfg, jnp.asarray(ids))[0])


def test_family_mapping_reads_the_published_keys():
    cfg = ec.config()
    assert cfg.layer_types == ("sliding",) * 3 + ("global",) + ("sliding",) * 3 + ("global",)
    assert cfg.sliding_window == ec.WINDOW and cfg.rope_layers == "sliding"
    assert cfg.qk_norm and not cfg.qk_norm_after_rope and cfg.rope_theta == 1e6
    assert cfg.first_k_dense == 1 and cfg.num_moe_layers == 7
    moe = cfg.moe
    assert (moe.n_routed_experts, moe.experts_per_token, moe.n_shared_experts) == (8, 2, 1)
    assert moe.score_func == "sigmoid" and moe.norm_topk_prob
    assert moe.route_scale == 2.5 and moe.holds_all_experts and moe.num_held == 8
    # one chip's share: the router as wide as the model, the tree 2 experts
    share = ec.config(ec.share_hf(2, first=4)).moe
    assert (share.n_routed_experts, share.num_held, share.first_held_expert) == (8, 2, 4)
    assert not share.holds_all_experts
    # a model without a window anywhere rotates every layer
    plain = ec.config({**ec.HF, "sliding_window": None})
    assert plain.rope_layers == "all" and plain.layer_types is None


def test_published_236b_parameter_count_from_shapes_alone():
    try:
        with open(CATALOG) as f:
            row = next(json.loads(ln) for ln in f if '"K-EXAONE-236B-A23B"' in ln)
    except OSError:
        pytest.skip("no catalog beside the guide here")
    hf = {**row["config"], "architectures": ["ExaoneMoeForCausalLM"],
          "num_nextn_predict_layers": 0}
    spec = get_model_spec(hf)
    cfg = spec.config_from_hf(hf, dtype=jnp.bfloat16)
    assert cfg.layer_types.count("sliding") == 36 and cfg.first_k_dense == 1
    shapes = jax.eval_shape(lambda: spec.module.init(cfg, jax.random.key(0)))
    count = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    assert count == 236_571_156_352
    # the benchmark's cut: one chip's share of 16, two periods deep
    with open(f"{ec.ROOT}/benchmark/configs/k_exaone_236b_a23b_serve_v5e1.json") as f:
        cut = json.load(f)
    from benchmark.run import Run

    hf = {k: v for k, v in cut.items() if k not in Run.NOT_HF_KEYS}
    hf["architectures"] = cut["architectures"]
    cfg = spec.config_from_hf(hf, dtype=jnp.bfloat16)
    assert (cfg.moe.num_held, cfg.moe.n_routed_experts) == (8, 128)
    shapes = jax.eval_shape(lambda: spec.module.init(cfg, jax.random.key(0)))
    count = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    assert count == 3_865_420_672
    assert shapes["moe_layers"]["moe"]["experts"]["up_proj"]["kernel"].shape == (
        7, 8, 6144, 2048)
    assert shapes["moe_layers"]["moe"]["gate"]["weight"].shape == (7, 6144, 128)
    # every number of the catalog row stands in the file but the four cuts
    lists = ("layer_types", "mlp_layer_types", "sliding_windows")
    assert set(cut["reduced"]) == set(lists) | {
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"}
    for key, value in row["config"].items():
        if key in lists:               # the cut in depth, an entry a layer
            assert cut[key] == value[:8]
        elif key in cut["reduced"]:
            assert cut["published"][key] == value and cut[key] != value
        else:
            assert cut[key] == value, key


def test_forward_matches_reference_with_all_experts_held(case):
    cfg, params = case
    ids = _ids((2, 40))
    ref = ec.reference(params, ids)
    assert np.abs(_forward(params, cfg, ids) - ref).max() < TOL * np.abs(ref).max()


def test_rotary_embedding_is_the_window_layers_alone(case):
    cfg, params = case
    table = jnp.ones((4,))
    pick = make_freq_for(cfg, table)
    assert pick(None) is None and pick(ec.WINDOW) is table
    every = make_freq_for(dataclasses.replace(cfg, rope_layers="all"), table)
    assert every(None) is table
    # rotating the full layers too is another function: the reference says no
    ids = _ids((1, 24), seed=1)
    ref = ec.reference(params, ids)
    moved = _forward(params, dataclasses.replace(cfg, rope_layers="all"), ids)
    assert np.abs(moved - ref).max() > 100 * TOL * np.abs(ref).max()


def test_q_and_k_carry_a_norm_per_head(case):
    cfg, params = case
    for stack, n in (("dense_layers", 1), ("moe_layers", 7)):
        assert params[stack]["q_norm"]["scale"].shape == (n, 16)
        assert params[stack]["k_norm"]["scale"].shape == (n, 16)
    # the benchmark draws them 1 + 0.1 N, so the parity above reads them; a
    # tree whose head norms are doubled is followed by the reference too
    bent = jax.tree.map(lambda a: a, params)
    bent["moe_layers"] = {**params["moe_layers"], "q_norm": {
        "scale": 2.0 * params["moe_layers"]["q_norm"]["scale"]}}
    ids = _ids((1, 20), seed=2)
    ref = ec.reference(bent, ids)
    assert np.abs(_forward(bent, cfg, ids) - ref).max() < TOL * np.abs(ref).max()
    assert np.abs(ref - ec.reference(params, ids)).max() > 1e-3


def test_generate_follows_the_window_and_the_layer_kinds(case):
    from automodel_tpu.inference.generate import GenerateConfig, generate

    cfg, params = case
    prompt = _ids((1, 21), seed=3)
    with jax.default_matmul_precision("highest"):
        out = generate(params, cfg, jnp.asarray(prompt), jax.random.key(0),
                       GenerateConfig(max_new_tokens=8, temperature=0.0))
    seq = np.asarray(out["tokens"] if isinstance(out, dict) else out)[0]
    seq = seq[: 21 + 8].tolist()
    ref = ec.reference(params, [seq])[0]
    assert ref.argmax(-1)[20:-1].tolist() == seq[21:]


# -- one chip's share ----------------------------------------------------------
def _share_params(moe_params, first, held):
    experts = jax.tree.map(lambda a: a[first:first + held], moe_params["experts"])
    return {**moe_params, "experts": experts}


def test_the_shares_add_up_to_the_uncut_layer(case):
    """4 shares of 2 of 8 experts: what each adds for its own experts, with
    the shared expert (which every chip computes alike) counted once, equals
    the uncut reference's expert layer."""
    cfg, params = case
    lp = jax.tree.map(lambda a: a[2], params["moe_layers"]["moe"])
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 37, 64)), jnp.float32)
    flat = ec.flat_leaves({"moe": lp})
    with jax.default_matmul_precision("highest"):
        whole = ec.REF.expert_mlp(
            x[0], {k: jnp.asarray(v) for k, v in flat.items()}, ec.HF, jnp.matmul)
        shared_cfg = dataclasses.replace(cfg.moe, dispatcher="dropless")
        full, _, _ = moe_forward(lp, shared_cfg, x)
        from automodel_tpu.moe.experts import shared_expert_forward

        shared = shared_expert_forward(lp["shared"], cfg.moe, x[0])
        total = shared
        for first in range(0, 8, 2):
            share_cfg = dataclasses.replace(
                shared_cfg, n_held_experts=2, first_held_expert=first)
            out, _, stats = moe_forward(_share_params(lp, first, 2), share_cfg, x)
            # the router sees all 8 whatever is held
            assert stats["tokens_per_expert"].shape == (8,)
            total = total + (out[0] - shared)
            # and the reference, given the same share, says the same
            part = ec.REF.expert_mlp(
                x[0], {k: jnp.asarray(v) for k, v in ec.flat_leaves(
                    {"moe": _share_params(lp, first, 2)}).items()},
                ec.share_hf(2, first), jnp.matmul)
            assert np.abs(np.asarray(out[0] - part)).max() < 1e-5
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    assert np.abs(np.asarray(full[0] - whole)).max() < 1e-5
    # no share is nothing: each adds a routed part of its own
    assert np.abs(np.asarray(whole - shared)).max() > 1e-2


def test_a_share_of_the_model_matches_the_reference_given_the_same_share():
    hf = ec.share_hf(2, first=2)
    cfg = ec.config(hf)
    params = ec.init_params(cfg, seed=3, hf=hf)
    assert params["moe_layers"]["moe"]["experts"]["up_proj"]["kernel"].shape[:2] == (7, 2)
    assert params["moe_layers"]["moe"]["gate"]["weight"].shape == (7, 64, 8)
    ids = _ids((2, 30), seed=6)
    ref = ec.reference(params, ids, hf=hf)
    assert np.abs(_forward(params, cfg, ids) - ref).max() < TOL * np.abs(ref).max()


def test_checkpoint_keys_round_trip():
    hf = ec.share_hf(2, first=2)
    cfg = ec.config(hf)
    params = ec.init_params(cfg, seed=1, hf=hf)
    spec = get_model_spec(hf)
    adapter = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    state = dict(adapter.to_hf(params))
    assert state["model.layers.0.self_attn.q_norm.weight"].shape == (16,)
    assert "model.layers.0.mlp.gate_proj.weight" in state           # dense
    assert state["model.layers.1.mlp.gate.weight"].shape == (8, 64)  # router
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in state
    assert "model.layers.1.mlp.shared_experts.up_proj.weight" in state
    # the held experts under the checkpoint's own numbers: 2 and 3
    held = sorted({k.split(".experts.")[1].split(".")[0] for k in state
                   if ".mlp.experts." in k})
    assert held == ["2", "3"]
    back = adapter.from_hf(state.__getitem__)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert jax.tree.structure(params) == jax.tree.structure(back)


@pytest.mark.parametrize("change, said", [
    ({"num_nextn_predict_layers": 1}, "multi-token prediction"),
    ({"mlp_layer_types": ["sparse", "dense"] + ["sparse"] * 6},
     "mlp_layer_types other than leading dense"),
    ({"rope_parameters": {"rope_type": "yarn", "rope_theta": 1e6}}, "rope_type"),
    ({"layer_types": ["sliding_attention"] * 3}, "layer_types has 3 entries"),
    ({"num_experts": 3, "router_num_experts": 8, "first_held_expert": 6},
     "are not among the 8 routed"),
])
def test_family_refuses_by_name(change, said):
    with pytest.raises((NotImplementedError, ValueError), match=said):
        ec.config({**ec.HF, **change})


def test_a_share_refuses_what_it_cannot_follow(case):
    cfg, _ = case
    with pytest.raises(ValueError, match="needs the dropless dispatcher"):
        MoEConfig(n_routed_experts=8, n_held_experts=2, dispatcher="capacity")

    class FakeMesh:
        sizes = {"ep": 2}

    share = dataclasses.replace(cfg.moe, n_held_experts=2)
    with pytest.raises(NotImplementedError, match="held without its exchange"):
        moe_forward({"gate": {"weight": jnp.zeros((64, 8))}}, share,
                    jnp.zeros((1, 4, 64)), mesh_ctx=FakeMesh())
