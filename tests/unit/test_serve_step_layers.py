"""The serve step walks its layers over per-layer buffers.

Two things are pinned here. (a) The compiled step (`paged_serve_step`,
`quant_serve_step`, `sharded_serve_step` of analysis/entrypoints.py): no
`while` over layers, no `dynamic-slice` / `dynamic-update-slice` whose
operand carries a layer axis, and every per-layer pool argument aliased to
an output. (b) `split_layer_stacks`: what it returns, that it gives the
stacked buffers up, and that a split tree passes through and is shared.
"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.models.moe_lm import decoder as moe_decoder
from automodel_tpu.serving import (
    DisaggConfig,
    DisaggRouter,
    Request,
    ServingConfig,
    ServingEngine,
    split_layer_stacks,
)
from automodel_tpu.serving.engine import LAYER_STACKS
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=3,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)
GEO = dict(page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
           token_budget=8)


# -- (a) the compiled step ---------------------------------------------------
_DEF = re.compile(r"%([\w\.\-]+) = \w+\[([\d,]*)\]")
_SLICE = re.compile(r" (dynamic-slice|dynamic-update-slice)\(%([\w\.\-]+)")


def _layer_axis_slices(text: str, per_layer_shapes: set, num_layers: int):
    """The dynamic slices / updates of `text` whose first operand is one of
    the step's per-layer arrays with a layer axis in front of it."""
    shape_of = {
        name: tuple(int(d) for d in dims.split(",") if d)
        for name, dims in _DEF.findall(text)
    }
    found = []
    for op, operand in _SLICE.findall(text):
        shape = shape_of[operand]
        if shape[:1] == (num_layers,) and shape[1:] in per_layer_shapes:
            found.append((op, shape))
    return found


@pytest.mark.parametrize(
    "entry", ["paged_serve_step", "quant_serve_step", "sharded_serve_step"]
)
def test_compiled_step_has_no_layer_axis(entry, monkeypatch):
    """Compile the entry point as the analysis gate does, keeping hold of
    the engine it builds, and read the program's text and aliasing."""
    from automodel_tpu.analysis import entrypoints
    from automodel_tpu.analysis.hlo import analyze_compiled

    engines = []
    real = ServingEngine.__init__

    def spy(self, *a, **k):
        real(self, *a, **k)
        engines.append(self)

    monkeypatch.setattr(ServingEngine, "__init__", spy)
    compiled, mesh_axes = entrypoints.ENTRY_POINTS[entry]()
    (eng,) = engines
    text = compiled.as_text()
    # loops there may be (the sampling keys' threefry), none around layers
    assert not [
        line for line in text.splitlines()
        if " while(" in line and "serve.layers" in line
    ]

    num_layers = sum(L for *_, L in eng._stacks)
    assert num_layers > 1  # or a layer axis of 1 would prove nothing
    per_layer = [
        leaf for key in LAYER_STACKS for leaf in jax.tree.leaves(
            eng.params.get(key, ()))
    ] + jax.tree.leaves(eng.pool)
    shapes = {
        tuple(s.data.shape) for a in per_layer for s in a.addressable_shards
    } | {tuple(a.shape) for a in per_layer}
    assert _layer_axis_slices(text, shapes, num_layers) == []
    # the guard can see one: a stacked operand sliced by layer
    probe = jax.jit(lambda a, i: a[i]).lower(
        jnp.zeros((num_layers,) + tuple(per_layer[0].shape)), 0
    ).compile().as_text()
    assert _layer_axis_slices(probe, shapes, num_layers)

    # every page array of every layer is donated and aliased to an output
    report = analyze_compiled(compiled, entry=entry, mesh_axes=mesh_axes)
    n_pool = len(jax.tree.leaves(eng.pool))
    assert n_pool == num_layers * (4 if eng._kv_quant else 2)
    assert len(report.donation) == n_pool, report.donation


# -- (b) the split -----------------------------------------------------------
def _moe_cfg():
    from automodel_tpu.analysis.entrypoints import _configs

    _, moe = _configs()
    return dataclasses.replace(moe, pipeline_microbatches=1)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_split_gives_per_layer_values_and_gives_up_the_stacks(family):
    if family == "dense":
        cfg, params = CFG, decoder.init(CFG, jax.random.key(0))
    else:
        cfg = _moe_cfg()
        params = moe_decoder.init(cfg, jax.random.key(0))
    keys = [k for k in LAYER_STACKS if k in params]
    assert keys
    want = {k: jax.tree.map(np.asarray, params[k]) for k in keys}
    given = own(params)
    split = split_layer_stacks(given, jnp.float32)
    for key in keys:
        stacked_leaves = jax.tree.leaves(want[key])
        L = stacked_leaves[0].shape[0]
        assert isinstance(split[key], tuple) and len(split[key]) == L
        for i, layer in enumerate(split[key]):
            assert jax.tree.structure(layer) == jax.tree.structure(want[key])
            for got, stack in zip(jax.tree.leaves(layer), stacked_leaves):
                np.testing.assert_array_equal(np.asarray(got), stack[i])
        # the stacked buffers were given up, every one
        assert all(a.is_deleted() for a in jax.tree.leaves(given[key]))
    # what is not a layer stack is the caller's as before
    for key in set(params) - set(keys):
        assert split[key] is given[key]
        assert not any(a.is_deleted() for a in jax.tree.leaves(given[key]))


def test_split_casts_floating_leaves_on_the_way():
    params = decoder.init(CFG, jax.random.key(0))
    params["layers"]["steps"] = jnp.arange(CFG.num_layers, dtype=jnp.int32)
    split = split_layer_stacks(own(params), jnp.bfloat16)
    for layer in split["layers"]:
        assert layer["steps"].dtype == jnp.int32
        assert layer["o_proj"]["kernel"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(split["layers"][1]["o_proj"]["kernel"]),
        np.asarray(params["layers"]["o_proj"]["kernel"][1].astype(jnp.bfloat16)),
    )


def test_split_tree_passes_through_and_is_shared_by_two_engines():
    params = decoder.init(CFG, jax.random.key(0))
    split = split_layer_stacks(own(params), CFG.dtype)
    again = split_layer_stacks(split, CFG.dtype)
    assert again["layers"] is split["layers"]

    a = ServingEngine(split, CFG, ServingConfig(**GEO))
    b = ServingEngine(split, CFG, ServingConfig(**GEO))
    for x, y, z in zip(*(jax.tree.leaves(t["layers"])
                         for t in (a.params, b.params, split))):
        assert x is y is z and not x.is_deleted()
    reqs = lambda: [Request(prompt=[3, 5, 7, 9, 11], max_new_tokens=4)]  # noqa: E731
    out_a = a.serve_batch(reqs())["outputs"]
    assert out_a == b.serve_batch(reqs())["outputs"]
    # and an engine that split the stacked tree itself serves the same
    c = ServingEngine(own(params), CFG, ServingConfig(**GEO))
    assert out_a == c.serve_batch(reqs())["outputs"]


def test_router_splits_once_for_both_classes():
    params = decoder.init(CFG, jax.random.key(0))
    given = own(params)
    router = DisaggRouter(
        given, CFG, ServingConfig(**GEO),
        DisaggConfig(enabled=True, prefill_replicas=1, decode_replicas=1),
    )
    assert all(a.is_deleted() for a in jax.tree.leaves(given["layers"]))
    (p,), (d,) = router.prefill, router.decode
    for x, y in zip(jax.tree.leaves(p.params["layers"]),
                    jax.tree.leaves(d.params["layers"])):
        assert x is y


# -- (c) the looped step -------------------------------------------------------
#: sha256 of the lowered (StableHLO) serve step of CFG at GEO with jax 0.9.0
#: on the CPU. The walk over passes must leave a one-pass model's program as
#: it was: a PR that changes this step on purpose updates the digest and says
#: why. Last re-derived for the copy-on-write block alone: a `while` over the
#: plan's split pages (a dynamic slice and update a pool array a trip, after
#: a stable argsort that puts the splits first) where a gather and a scatter
#: a pool array over every slot's entry were; with value names, locations and
#: private function names masked, every other line of the text is the one
#: the digest before it pinned.
ONE_PASS_STEP_SHA256 = (
    "a13714b3dec097c369ec05e59fc38bff00b209c1ec9beec10b944e9d8969ae01")


def test_one_pass_step_text_is_unchanged():
    eng = ServingEngine(decoder.init(CFG, jax.random.key(0)), CFG,
                        ServingConfig(**GEO))
    text = eng.lower_step().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == ONE_PASS_STEP_SHA256


def _ops(text: str, name: str) -> int:
    return len(re.findall(rf"= \"?stablehlo\.{name}\"?[ (]", text))


def test_looped_step_walks_the_same_weights_over_its_own_cache_entries():
    from automodel_tpu.analysis.hlo import analyze_compiled
    from tests import ouro_case

    looped = ouro_case.config()
    single = dataclasses.replace(looped, num_passes=1)
    P, L = looped.num_passes, looped.num_layers
    params = ouro_case.init_params(looped)
    engines = {
        cfg.num_passes: ServingEngine(own(params), cfg, ServingConfig(**GEO))
        for cfg in (single, looped)
    }
    text = {p: e.lower_step().as_text() for p, e in engines.items()}
    eng = engines[P]
    # passes x layers cache entries, each its own pair of arguments; the
    # weights are arguments ONCE however often they are read
    assert len(eng.pool[0]) == P * L and len(engines[1].pool[0]) == L
    n_weights = len(jax.tree.leaves(eng.params))
    assert n_weights == len(jax.tree.leaves(engines[1].params))

    def n_args(t):
        return re.search(r"func\.func public @main\((.*?)\) ->", t,
                         re.S).group(1).count("%arg")

    assert n_args(text[P]) - n_args(text[1]) == 2 * L * (P - 1)
    # every pass does a pass's work: the pool writes' scatters, the page
    # gathers and (the head's one aside) the matrix products of the one-pass
    # step, P times over
    assert _ops(text[1], "scatter") > 0
    assert _ops(text[P], "scatter") == P * _ops(text[1], "scatter")
    assert _ops(text[P], "dot_general") - 1 == P * (_ops(text[1], "dot_general") - 1)

    compiled = eng.lower_step().compile()
    hlo = compiled.as_text()
    # no loop around layers or passes, no copy of a pool array, and every
    # page array donated and aliased to an output
    assert not [ln for ln in hlo.splitlines()
                if " while(" in ln and "serve.layers" in ln]
    pool_shapes = {
        "f32[" + ",".join(map(str, a.shape)) + "]" for a in jax.tree.leaves(eng.pool)}
    assert not [ln for ln in hlo.splitlines() if " copy(" in ln
                and any(sh in ln.split(" copy(")[0] for sh in pool_shapes)]
    report = analyze_compiled(compiled, entry="looped_serve_step", mesh_axes=None)
    assert len(report.donation) == len(jax.tree.leaves(eng.pool)) == 2 * P * L
    # each pass's scope names its ops (what the per-pass reader looks for)
    for t in range(P):
        assert f"serve.layers/serve.pass{t}/serve.attn" in hlo
    assert f"serve.pass{P}" not in hlo
