"""MiMoV2FlashForCausalLM (MiMo-V2-Flash) on the expert decoder: window layers
whose attention is an operator stack of its own (key/value heads and a sink
logit a head), keys wider than values and the values scaled, DeepSeek-style
experts of which a layer may hold one chip's SHARE. The family mapping, the
published parameter count from shapes alone, the training forward and the
serving engine against the plain reference at a toy size, the paged kernel
with sinks and unlike widths against the XLA reference in interpret mode,
the sum of the shares, the checkpoint names, and every refusal by name."""

import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.checkpoint.hf_adapter import get_adapter
from automodel_tpu.models.llm.decoder import kind_config
from automodel_tpu.models.moe_lm import decoder as moe_decoder
from automodel_tpu.models.registry import get_model_spec
from automodel_tpu.moe.layer import moe_forward
from automodel_tpu.observability.metrics import default_registry
from automodel_tpu.serving import Request, ServingConfig, ServingEngine
from tests import mimo_case as mc
from tests.serving_params import own

TOL = 2e-5  # float32 against float32 at "highest": the order of the sums


@pytest.fixture(scope="module")
def case():
    cfg = mc.config()
    return cfg, mc.init_params(cfg)


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(0, mc.VOCAB, shape)


def _forward(params, cfg, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(moe_decoder.forward(params, cfg, jnp.asarray(ids))[0])


def test_family_mapping_reads_the_published_keys():
    cfg = mc.config()
    assert cfg.layer_types == ("global", "sliding", "sliding") * 2
    assert cfg.layer_ops == ("attention", "window_attention",
                             "window_attention") * 2
    assert cfg.sliding_window == mc.WINDOW and cfg.rope_layers == "all"
    assert (cfg.rope_theta, cfg.rope_local_theta) == (5e6, 1e4)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rope_dim) == (160, 16, 52)
    assert cfg.attention_value_scale == 0.707 and not cfg.attention_sinks
    window = kind_config(cfg, "window_attention")
    assert (window.num_kv_heads, window.attention_sinks) == (2, True)
    assert (window.head_dim, window.resolved_v_head_dim) == (160, 16)
    assert kind_config(cfg, "attention") is cfg
    assert cfg.first_k_dense == 1 and cfg.num_moe_layers == 5
    moe = cfg.moe
    assert (moe.n_routed_experts, moe.experts_per_token, moe.n_shared_experts) == (8, 2, 0)
    assert moe.score_func == "sigmoid" and moe.norm_topk_prob
    assert moe.route_scale == 1.0 and moe.gate_bias_update_speed > 0
    share = mc.config(mc.share_hf(2, first=4)).moe
    assert (share.n_routed_experts, share.num_held, share.first_held_expert) == (8, 2, 4)
    params = jax.eval_shape(lambda: moe_decoder.init(cfg, jax.random.key(0)))
    # the layers' norms and MLPs by stack, each kind's attention apart
    assert set(params["moe_layers"]) == {"input_norm", "post_attn_norm", "moe"}
    assert params["attn_layers"]["k_proj"]["kernel"].shape == (2, 64, 160)
    assert params["window_attn_layers"]["k_proj"]["kernel"].shape == (4, 64, 320)
    assert params["window_attn_layers"]["v_proj"]["kernel"].shape == (4, 64, 32)
    assert params["window_attn_layers"]["o_proj"]["kernel"].shape == (4, 64, 64)
    assert params["window_attn_layers"]["sinks"].shape == (4, 4)
    assert "sinks" not in params["attn_layers"]


def _hf_of(config_file):
    from benchmark.run import Run

    hf = {k: v for k, v in config_file.items() if k not in Run.NOT_HF_KEYS}
    hf["architectures"] = config_file["architectures"]
    return hf


def test_published_309b_parameter_count_from_shapes_alone():
    """The whole model from the configuration file with its cuts undone (the
    file's `published` block; tests/benchmark/test_benchmark_manifest.py
    holds every other key to the catalog row), then the cut itself."""
    with open(f"{mc.ROOT}/benchmark/configs/mimo_v2_flash_serve_v5e1.json") as f:
        cut = json.load(f)
    published = {k: v for k, v in _hf_of(cut).items() if k != "router_num_experts"}
    published.update(
        num_hidden_layers=48, n_routed_experts=256, vocab_size=152576,
        # full, window x 4, full, then (window x 5, full) x 7
        hybrid_layer_pattern=[0] + [1] * 4 + [0] + ([1] * 5 + [0]) * 7,
        moe_layer_freq=[0] + [1] * 47)
    assert {k: cut["published"][k] for k in ("num_hidden_layers", "n_routed_experts",
            "vocab_size")} == {"num_hidden_layers": 48, "n_routed_experts": 256,
                               "vocab_size": 152576}
    assert cut["hybrid_layer_pattern"] == published["hybrid_layer_pattern"][:11]
    spec = get_model_spec(published)
    cfg = spec.config_from_hf(published, dtype=jnp.bfloat16)
    assert cfg.layer_ops.count("window_attention") == 39 and cfg.first_k_dense == 1
    assert dict(cfg.window_attention) == {"num_kv_heads": 8, "attention_sinks": True}
    assert (cfg.rope_dim, cfg.resolved_v_head_dim) == (64, 128)
    shapes = jax.eval_shape(lambda: spec.module.init(cfg, jax.random.key(0)))
    count = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    assert count == 308_778_780_864
    # the benchmark's cut: one chip's share of 16, the dense layer and one
    # whole period behind the four window layers that open the pattern
    cfg = spec.config_from_hf(_hf_of(cut), dtype=jnp.bfloat16)
    assert (cfg.moe.num_held, cfg.moe.n_routed_experts) == (16, 256)
    assert cfg.layer_ops.count("window_attention") == 9
    shapes = jax.eval_shape(lambda: spec.module.init(cfg, jax.random.key(0)))
    count = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    assert count == 5_422_283_840
    assert shapes["moe_layers"]["moe"]["experts"]["up_proj"]["kernel"].shape == (
        10, 16, 4096, 2048)
    assert shapes["moe_layers"]["moe"]["gate"]["weight"].shape == (10, 4096, 256)
    assert shapes["window_attn_layers"]["sinks"].shape == (9, 64)


def test_forward_matches_reference_with_all_experts_held(case):
    cfg, params = case
    ids = _ids((2, 40))
    ref = mc.reference(params, ids)
    assert np.abs(_forward(params, cfg, ids) - ref).max() < TOL * np.abs(ref).max()


@pytest.mark.parametrize("what", ["sinks", "value scale"])
def test_the_sinks_and_the_value_scale_each_move_the_result(case, what):
    """Dropped from the program alone, each moves the logits far past the
    parity above: the reference holds the program to both."""
    cfg, params = case
    ids = _ids((1, 30), seed=1)
    ref = mc.reference(params, ids)
    if what == "sinks":
        bent = dataclasses.replace(cfg, window_attention=(
            ("num_kv_heads", 2), ("attention_sinks", False)))
        params = {**params, "window_attn_layers": {
            k: v for k, v in params["window_attn_layers"].items() if k != "sinks"}}
    else:
        bent = dataclasses.replace(cfg, attention_value_scale=1.0)
    assert np.abs(_forward(params, bent, ids) - ref).max() > 100 * TOL * np.abs(ref).max()


def test_the_engine_serves_against_the_reference(case):
    """Chunked prefill past the window and the ring, then decode through the
    pool (full layers) and the rings (window layers): every sampled token's
    log-probability as the reference gives it over the whole sequence."""
    cfg, params = case
    sc = ServingConfig(page_size=4, num_pages=64, max_slots=3,
                       pages_per_slot=16, token_budget=16, prefill_chunk=12)
    engine = ServingEngine(own(params), cfg, sc)
    # keys of 160 lie in pages 256 wide, values in their own 16
    assert engine.pool[1][0][0].shape[-1] == 256
    assert engine.pool[1][0][1].shape[-1] == 16
    assert engine.state[0][0].shape[-2:] == (2, 256)
    reg = engine.obs.registry.snapshot()
    assert reg["serve_kv_bytes_per_token"] == 2 * 1 * (256 + 16) * 4
    assert reg["serve_window_bytes_per_slot"] == (
        4 * engine._ring_pages * 4 * 2 * (256 + 16) * 4)
    prompts = [_ids((n,), seed=10 + n).tolist() for n in (5, 27, 41)]
    sched = engine.make_scheduler()
    for p in prompts:
        sched.submit(Request(prompt=list(p), max_new_tokens=6))
    logprobs, step = {}, 0
    while sched.has_work:
        plan = sched.schedule(step)
        if plan is not None:
            tokens, lps = engine.run_step(plan)
            for slot, _c, samples in plan.scheduled:
                if samples:
                    rid = sched.running[slot].rid
                    logprobs.setdefault(rid, []).append(float(lps[slot]))
            sched.update(plan, tokens, step)
        step += 1
    for p, req in zip(prompts, sorted(sched.finished, key=lambda r: r.rid)):
        seq = p + req.generated
        ref = mc.log_softmax(mc.reference(params, [seq])[0])
        want = [ref[t, seq[t + 1]] for t in range(len(p) - 1, len(seq) - 1)]
        np.testing.assert_allclose(logprobs[req.rid], want, atol=2e-4)
        assert ref.argmax(-1)[len(p) - 1:-1].tolist() == req.generated


# -- the kernel: sinks, keys wider than values ---------------------------------
def _kernel_case(Hq, Hkv, D, Dv, quant, seed):
    from automodel_tpu.serving.kv_pages import key_width

    rng = np.random.default_rng(seed)
    ps, P, N, Dk = 8, 6, 20, key_width(D)
    # a decode row, a chunk of 16 rows (the tile body), another decode row,
    # two pad rows
    rows = [(0, 30)] + [(1, p) for p in range(10, 26)] + [(2, 44)] + [(-1, -1)] * 2
    slot = np.array([r[0] for r in rows], np.int32)
    pos = np.array([r[1] for r in rows], np.int32)
    q = jnp.asarray(rng.standard_normal((len(rows), Hq, D)), jnp.float32)
    k = rng.standard_normal((N, ps, Hkv, Dk))
    k[..., D:] = 0.0
    v = rng.standard_normal((N, ps, Hkv, Dv))
    tables = np.stack([rng.permutation(N - 1)[:P] for _ in range(3)])
    pt = jnp.asarray(tables[np.maximum(slot, 0)], jnp.int32)
    sinks = jnp.asarray(rng.standard_normal(Hq) + 2.0, jnp.float32)
    kw = {}
    if quant:
        kw = dict(k_scales=jnp.asarray(rng.uniform(0.01, 0.02, (N, ps)), jnp.float32),
                  v_scales=jnp.asarray(rng.uniform(0.01, 0.02, (N, ps)), jnp.float32))
        k = np.clip(np.round(k * 40), -127, 127)
        v = np.clip(np.round(v * 40), -127, 127)
        dt = jnp.int8
    else:
        dt = jnp.float32
    return q, jnp.asarray(k, dt), jnp.asarray(v, dt), pt, jnp.asarray(pos), slot, sinks, kw


@pytest.mark.parametrize("window", [None, 12], ids=["full", "window"])
@pytest.mark.parametrize("heads", [(16, 1), (16, 2)], ids=["16 groups", "8 groups"])
@pytest.mark.parametrize("widths", [(192, 128, False), (64, 32, True)],
                         ids=["keys 192 values 128", "int8 keys 64 values 32"])
def test_the_kernel_takes_sinks_and_unlike_widths(heads, window, widths):
    """Interpret mode against the XLA reference, in the one-row body (the
    decode rows) and the tile body (the chunk), with and without sinks:
    int8 pages take sinks too where the keys fit a lane tile."""
    from automodel_tpu.ops.paged_attention import (
        max_row_segments, ragged_paged_attention, ragged_paged_attention_xla,
        row_segments, row_tile,
    )

    (Hq, Hkv), (D, Dv, quant) = heads, widths
    q, k, v, pt, pos, slot, sinks, kw = _kernel_case(Hq, Hkv, D, Dv, quant, seed=Hkv)
    tile = row_tile(q.shape[0], Hq * (D + Dv))
    seg = row_segments(jnp.asarray(slot), pos, pt, page_size=8, tile=tile,
                       max_segments=max_row_segments(q.shape[0], 3, tile),
                       window=window)
    for s in (None, sinks):
        want = ragged_paged_attention_xla(
            q, k, v, pt, pos, scale=D ** -0.5, window=window, sinks=s, **kw)
        got = ragged_paged_attention(
            q, k, v, pt, pos, scale=D ** -0.5, window=window, sinks=s,
            impl="pallas", segments=seg, **kw)
        assert got.shape == (q.shape[0], Hq, Dv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5 if not quant else 2e-4)
    # the sink takes mass from every row: a call without it is another result
    plain = ragged_paged_attention_xla(q, k, v, pt, pos, scale=D ** -0.5,
                                       window=window, **kw)
    assert np.abs(np.asarray(plain - want)).max() > 1e-2


def test_a_call_with_sinks_is_named_and_counted():
    from automodel_tpu.ops.paged_attention import ragged_paged_attention

    q, k, v, pt, pos, _slot, sinks, _ = _kernel_case(16, 2, 64, 32, False, seed=3)

    def count(impl):
        return default_registry().counter("paged_attention_sink_total",
                                          impl=impl).value

    before = count("pallas"), count("xla")
    # the kernel's name is the pallas_call's (interpret mode inlines it in
    # the lowered text; on the chip it is the custom call's name)
    text = str(jax.make_jaxpr(lambda *a: ragged_paged_attention(
        *a, scale=0.1, window=12, sinks=sinks, impl="pallas"))(q, k, v, pt, pos))
    assert "paged_attention_sink_gqa" in text
    assert "paged_attention_window_gqa" not in text
    jax.jit(lambda *a: ragged_paged_attention(
        *a, scale=0.1, sinks=sinks, impl="xla")).lower(q, k, v, pt, pos)
    assert (count("pallas"), count("xla")) == (before[0] + 1, before[1] + 1)


# -- one chip's share ----------------------------------------------------------
def _share_params(moe_params, first, held):
    experts = jax.tree.map(lambda a: a[first:first + held], moe_params["experts"])
    return {**moe_params, "experts": experts}


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """16 shares of 1 of 16 experts (the cell's deployment: 16 chips a
    layer): what each adds for its own expert sums to the uncut layer, the
    program's and the reference's alike."""
    hf = {**mc.HF, "n_routed_experts": 16}
    cfg = mc.config(hf)
    params = mc.init_params(cfg, seed=4, hf=hf)
    lp = jax.tree.map(lambda a: a[2], params["moe_layers"]["moe"])
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 37, 64)), jnp.float32)
    moe_cfg = dataclasses.replace(cfg.moe, dispatcher="dropless")
    with jax.default_matmul_precision("highest"):
        whole = mc.REF.expert_mlp(
            x[0], {k: jnp.asarray(v) for k, v in mc.flat_leaves({"moe": lp}).items()},
            hf, jnp.matmul)
        full, _, _ = moe_forward(lp, moe_cfg, x)
        total = jnp.zeros_like(x[0])
        for first in range(16):
            share_cfg = dataclasses.replace(
                moe_cfg, n_held_experts=1, first_held_expert=first)
            out, _, stats = moe_forward(_share_params(lp, first, 1), share_cfg, x)
            assert stats["tokens_per_expert"].shape == (16,)
            total = total + out[0]
            part = mc.REF.expert_mlp(
                x[0], {k: jnp.asarray(v) for k, v in mc.flat_leaves(
                    {"moe": _share_params(lp, first, 1)}).items()},
                mc.share_hf(1, first, hf), jnp.matmul)
            assert np.abs(np.asarray(out[0] - part)).max() < 1e-5
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    assert np.abs(np.asarray(full[0] - whole)).max() < 1e-5
    assert np.abs(np.asarray(whole)).max() > 1e-2


def test_a_share_of_the_model_matches_the_reference_given_the_same_share():
    hf = mc.share_hf(2, first=2)
    cfg = mc.config(hf)
    params = mc.init_params(cfg, seed=3, hf=hf)
    assert params["moe_layers"]["moe"]["experts"]["up_proj"]["kernel"].shape[:2] == (5, 2)
    assert params["moe_layers"]["moe"]["gate"]["weight"].shape == (5, 64, 8)
    ids = _ids((2, 30), seed=6)
    ref = mc.reference(params, ids, hf=hf)
    assert np.abs(_forward(params, cfg, ids) - ref).max() < TOL * np.abs(ref).max()


def test_checkpoint_keys_round_trip():
    hf = mc.share_hf(2, first=2)
    cfg = mc.config(hf)
    params = mc.init_params(cfg, seed=1, hf=hf)
    spec = get_model_spec(hf)
    adapter = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    state = dict(adapter.to_hf(params))
    assert state["model.layers.1.self_attn.k_proj.weight"].shape == (2 * 160, 64)
    assert state["model.layers.1.self_attn.v_proj.weight"].shape == (2 * 16, 64)
    assert state["model.layers.0.self_attn.k_proj.weight"].shape == (160, 64)
    assert state["model.layers.1.self_attn.attention_sink_bias"].shape == (4,)
    assert "model.layers.3.self_attn.attention_sink_bias" not in state
    assert "model.layers.0.mlp.gate_proj.weight" in state           # dense
    assert state["model.layers.1.mlp.gate.weight"].shape == (8, 64)  # router
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in state
    held = sorted({k.split(".experts.")[1].split(".")[0] for k in state
                   if ".mlp.experts." in k})
    assert held == ["2", "3"]
    back = adapter.from_hf(state.__getitem__)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert jax.tree.structure(params) == jax.tree.structure(back)


@pytest.mark.parametrize("change, said", [
    ({"swa_head_dim": 96}, "head width"),
    ({"moe_layer_freq": [1, 0, 1, 1, 1, 1]}, "moe_layer_freq other than leading"),
    ({"hybrid_layer_pattern": [0, 1, 1]}, "hybrid_layer_pattern of 6"),
    ({"n_routed_experts": 3, "router_num_experts": 8, "first_held_expert": 6},
     "are not among the 8 routed"),
])
def test_family_refuses_by_name(change, said):
    with pytest.raises((NotImplementedError, ValueError), match=said):
        mc.config({**mc.HF, **change})


def test_what_the_model_cannot_run_is_refused_by_name(case):
    from automodel_tpu.inference.generate import GenerateConfig, generate

    cfg, params = case
    with pytest.raises(NotImplementedError, match="layer_ops"):
        generate(params, cfg, jnp.zeros((1, 4), jnp.int32), jax.random.key(0),
                 GenerateConfig(max_new_tokens=2))
    with pytest.raises(NotImplementedError, match="apart from the full layers'"):
        kind_config(dataclasses.replace(
            cfg, window_attention=(("head_dim", 64),)), "window_attention")
    with pytest.raises(NotImplementedError, match="routing capture"):
        moe_decoder.forward(params, cfg, jnp.zeros((1, 4), jnp.int32),
                            return_routing=True)
