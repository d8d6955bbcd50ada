"""The seams through which a second architecture enters the harness as data:
its reference, its weight rules, its operation counts and the kernels its step
must contain are found by the configuration's name, under the manifest's
`paths`. `tiny_dense` (tests/benchmark/reference, counts, tiny, limits) is the
proof: no file under benchmark/ knows it."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, load_module, peaks, served_check, weights
from benchmark import trace_reduce as tr
from benchmark.drivers import closed_loop_serve as driver

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PATHS = ["benchmark", "tests/benchmark"]


def config(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


DENSE = config("tests", "benchmark", "tiny", "tiny_dense.json")
MOONLIGHT = config("benchmark", "configs", "moonlight_16b_a3b_serve_v5e1.json")


# -- the step's kernel check is data ----------------------------------------
def test_a_configuration_without_step_kernels_is_refused_on_the_chip():
    bare = {k: v for k, v in DENSE.items() if k != "step_kernels"}
    with pytest.raises(RuntimeError, match="names no `step_kernels`"):
        driver.step_kernel_counts(bare, "whatever the step holds", on_chip=True)
    with pytest.raises(RuntimeError, match="names no `step_kernels`"):
        driver.step_kernel_counts(dict(bare, step_kernels=[]), "", on_chip=True)
    # a rehearsal off the chip lowers nothing and refuses nothing
    assert driver.step_kernel_counts(bare, "", on_chip=False) == {}


def test_a_kernel_the_lowered_step_lacks_raises_and_names_it():
    text = 'tpu_custom_call {name = "paged_attention_gqa"} paged_attention_gqa'
    cfg = dict(DENSE, step_kernels=["paged_attention_gqa"])
    assert driver.step_kernel_counts(cfg, text, on_chip=True) == {
        "paged_attention_gqa": 2}
    cfg["step_kernels"].append("paged_attention_mla")
    with pytest.raises(RuntimeError, match="no paged_attention_mla kernel"):
        driver.step_kernel_counts(cfg, text, on_chip=True)
    assert driver.step_kernel_counts(cfg, text, on_chip=False) == {
        "paged_attention_gqa": 2, "paged_attention_mla": 0}


def test_step_kernels_is_the_benchmarks_key_not_the_models():
    from benchmark.run import Run

    assert "step_kernels" in Run.NOT_HF_KEYS
    assert MOONLIGHT["step_kernels"] == ["paged_attention_mla"]


# -- weight rules an architecture can add to ---------------------------------
def test_leaf_rule_takes_a_bias_and_a_references_own_leaves():
    ref = types.SimpleNamespace(
        stacks=lambda cfg: [("layers", None, 3)],
        LEAF_RULES={"exit_gate": (0.5, 0.25)})
    draw = weights.draw_for(ref, DENSE)
    assert draw == weights.Draw(3, ("layers",), {"exit_gate": (0.5, 0.25)})
    assert weights.leaf_rule("layers/q_proj/bias", (64,), draw) == (0.0, 0.02)
    assert weights.leaf_rule("early_exit/exit_gate", (64,), draw) == (0.5, 0.25)
    # the benchmark's own rules come first: a module cannot redraw a kernel
    loud = weights.Draw(3, ("layers",), {"kernel": (9.0, 9.0)})
    assert weights.leaf_rule("lm_head/kernel", (64, 512), loud) == (0.0, 0.125)
    with pytest.raises(KeyError, match="early_exit/exit_bound"):
        weights.leaf_rule("early_exit/exit_bound", (64,), draw)
    # a reference without LEAF_RULES has none
    plain = types.SimpleNamespace(stacks=ref.stacks)
    with pytest.raises(KeyError, match="exit_gate"):
        weights.leaf_rule("early_exit/exit_gate", (64,),
                          weights.draw_for(plain, DENSE))


def test_the_stacked_subtrees_are_the_references_to_name():
    shapes = {"tower": {"w": {"kernel": jax.ShapeDtypeStruct((2, 8, 4), jnp.float32)}},
              "lm_head": {"kernel": jax.ShapeDtypeStruct((2, 8, 4), jnp.float32)}}
    draw = weights.Draw(2, ("tower",), {})
    made = weights.make_params(7, shapes, jnp.float32, draw)
    alone = weights.make_layer(weights.root_key(7), weights.tree_paths(shapes),
                               "tower", 1, draw, jnp.float32)
    np.testing.assert_allclose(alone["w/kernel"], made["tower"]["w"]["kernel"][1],
                               rtol=3e-7)
    # `lm_head` is no stack here: it is drawn whole from the leaf's own key,
    # not layer by layer from per-layer keys
    whole = weights.make_leaf(weights.root_key(7), "lm_head/kernel", (2, 8, 4),
                              jnp.float32, draw)
    np.testing.assert_allclose(made["lm_head"]["kernel"], whole, rtol=3e-7)


# -- found by name, under `paths` --------------------------------------------
def test_a_reference_under_tests_benchmark_is_found_through_paths():
    ref = served_check.load_reference(DENSE, ROOT, PATHS)
    assert ref.__file__ == os.path.join(HERE, "reference", "tiny_dense.py")
    assert [(name, n) for name, _fn, n in ref.stacks(DENSE)] == [("layers", 3)]
    with pytest.raises(FileNotFoundError, match="reference/tiny_dense.py"):
        served_check.load_reference(DENSE, ROOT, ["benchmark"])
    own = served_check.load_reference(MOONLIGHT, ROOT, PATHS)
    assert own.__file__ == os.path.join(ROOT, "benchmark", "reference",
                                        "deepseek_v3.py")


def test_the_counts_lookup_fails_loudly_for_a_name_with_no_file():
    ctx = {"root": ROOT, "paths": PATHS, "config": {"reference": "no_such_model"}}
    with pytest.raises(FileNotFoundError, match="counts/no_such_model.py"):
        flops.counts_for(ctx)
    with pytest.raises(FileNotFoundError, match="layer_metrics/nothing.py"):
        load_module(ROOT, PATHS, "layer_metrics", "nothing")
    assert flops.counts_for(dict(ctx, config=DENSE)).__file__ == os.path.join(
        HERE, "counts", "tiny_dense.py")


# -- operation counts per architecture ----------------------------------------
def test_tiny_dense_counts_by_hand():
    counts = flops.counts_for({"root": ROOT, "paths": PATHS, "config": DENSE})
    # q 64x64, k and v 64x32, o 64x64, three matrices of 64x160
    layer = 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 160)
    assert layer == 86_016
    assert counts.layer_linear_flops_per_token(DENSE) == layer
    # 10 rows attending to 200 keys in all, 3 of them sampled
    scores = 2 * 2 * 4 * 16 * 200
    assert counts.serve_step_flops(DENSE, 10, 200, 3) == (
        3 * (10 * layer + scores) + 3 * 2 * 64 * 512)
    need = counts.paged_gqa_call(DENSE, 10, 200, 50)
    assert need == {"flops": scores, "bytes": 2 * (50 * 2 * 2 * 16 + 2 * 10 * 4 * 16)}


@pytest.mark.parametrize("cfg", [MOONLIGHT, DENSE], ids=["moonlight", "tiny_dense"])
def test_the_one_mfu_reader_serves_either_architecture(cfg):
    """A synthetic trace: two runs of the step program, the device busy 4.75 s;
    the harness held two traced steps. The share is the architecture's own
    count of them over busy seconds times the peak."""
    ops = [("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 1.25), ("fusion.3", 3.0, 1.0),
           ("fusion.4", 5.0, 0.5), ("fusion.5", 7.0, 1.0)]
    dev = tr.DeviceTrace(chip=0, ops=ops, modules=[
        ("jit__step_impl(123)", 0.0, 2.25), ("jit__step_impl(123)", 5.0, 3.0)])
    steps = [driver.Step(1.0, 2.0, 250, 60, 50_000, 12_000),
             driver.Step(2.0, 3.0, 256, 50, 60_000, 13_000),
             driver.Step(9.0, 9.5, 1, 1, 1, 1)]        # after the trace: not read
    reader = load_module(ROOT, PATHS, "layer_metrics", "serve_step_mfu_pct")
    peak = peaks.peaks_for("TPU v5 lite")
    ctx = {"trace": tr.Trace(devices=[dev], host=[]), "steps": steps,
           "window": {"trace_on": 0.5, "trace_off": 3.5}, "config": cfg,
           "peaks": peak, "root": ROOT, "paths": PATHS}
    counts = flops.counts_for(ctx)
    mean = (counts.serve_step_flops(cfg, 250, 50_000, 60)
            + counts.serve_step_flops(cfg, 256, 60_000, 50)) / 2
    assert reader.read(ctx) == pytest.approx(
        100.0 * mean * 2 / (4.75 * 197e12))
    assert reader.read(dict(ctx, trace=None)) is None
    with pytest.raises(FileNotFoundError):
        reader.read(dict(ctx, config={"reference": "no_such_model"}))


# -- the tests' reference is a reference --------------------------------------
@pytest.fixture(scope="module")
def dense_program():
    from automodel_tpu.models.registry import get_model_spec

    from benchmark.run import Run

    hf = {k: v for k, v in DENSE.items() if k not in Run.NOT_HF_KEYS}
    hf["architectures"] = DENSE["architectures"]
    spec = get_model_spec(hf)
    model_cfg = spec.config_from_hf(
        hf, dtype=jnp.float32, remat_policy="none", attn_impl="xla")
    shapes = jax.eval_shape(lambda: spec.module.init(model_cfg, jax.random.key(0)))
    ref = served_check.load_reference(DENSE, ROOT, PATHS)
    draw = weights.draw_for(ref, DENSE)
    return spec, model_cfg, shapes, ref, draw, weights.make_params(
        11, shapes, jnp.float32, draw)


def test_every_leaf_of_the_dense_program_is_drawn_biases_too(dense_program):
    _, _, shapes, _, _, params = dense_program
    flat, made = weights.tree_paths(shapes), weights.tree_paths(params)
    assert {"layers/q_proj/bias", "layers/k_proj/bias", "layers/v_proj/bias",
            "layers/down_proj/kernel", "lm_head/kernel"} <= set(flat)
    assert {p: a.shape for p, a in made.items()} == {
        p: s.shape for p, s in flat.items()}
    bias = made["layers/q_proj/bias"]
    assert float(jnp.std(bias)) == pytest.approx(0.02, rel=0.2)
    assert not np.allclose(bias[0], bias[1])


def test_the_dense_training_forward_gives_the_tests_reference_logits(dense_program):
    from automodel_tpu.models.llm.decoder import unembed

    spec, model_cfg, shapes, ref, draw, params = dense_program
    ids = np.random.default_rng(0).integers(0, DENSE["vocab_size"], (2, 24))
    hidden = spec.module.forward(
        params, model_cfg, jnp.asarray(ids), return_hidden=True)
    got = unembed(params, model_cfg, hidden)

    flat, key = weights.tree_paths(shapes), weights.root_key(11)
    leaf = weights.tree_paths(params).__getitem__
    h = ref.hidden_states(
        DENSE, jnp.asarray(ids), leaf,
        lambda stack, l: weights.make_layer(key, flat, stack, l, draw, jnp.float32))
    want = ref.logits_at(DENSE, h.reshape(-1, h.shape[-1]), leaf)
    np.testing.assert_allclose(
        np.asarray(got).reshape(want.shape), want, atol=2e-4, rtol=0)
    assert float(jnp.std(want)) > 0.5   # logits are not degenerate
