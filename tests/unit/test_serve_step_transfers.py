"""One transfer each way a serve step (PR 39): the StepPlan crosses to the
device as ONE packed int32 vector, and the step's tokens, log-probabilities
(and acceptances) come back as ONE. The packing must be lossless, and the
step's maths untouched: the outputs of a fixed serve run equal what the
commit before the packing (9488542) returned, bit for bit."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.serving import Request, ServingConfig, ServingEngine
from automodel_tpu.serving.engine import unpack_device
from automodel_tpu.serving.router import ServeMeshConfig
from automodel_tpu.speculative.serve_draft import SpeculativeConfig
from tests import exaone_case, jamba_case, ouro_case
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=3,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)
GEO = dict(page_size=4, num_pages=24, max_slots=3, pages_per_slot=4,
           token_budget=12, prefill_chunk=6)


def _dense_engine(**serving):
    return ServingEngine(decoder.init(CFG, jax.random.key(0)), CFG,
                         ServingConfig(**{**GEO, **serving}))


def _random_plan(eng, rng):
    """The engine's empty plan with every field drawn at random, the edge
    values included: pad rows' -1, a slot that samples nothing, and the
    temperatures whose bits a float round trip could lose."""
    plan = eng.empty_plan()
    for arr in vars(plan).values():
        if isinstance(arr, np.ndarray) and arr.dtype == np.int32:
            arr[...] = rng.integers(-2**31, 2**31 - 1, arr.shape, np.int64)
    plan.slot[:2], plan.pos[:2], plan.sample_tok[0] = -1, -1, -1
    temps = np.asarray([0.0, -0.0, 1e-6, 0.7, -3.5, np.inf], np.float32)
    plan.temp[:] = np.resize(temps, plan.temp.shape)
    return plan


@pytest.mark.parametrize("kind", ["plain", "speculative", "one_device_mesh"])
def test_plan_round_trips_bit_for_bit(kind):
    if kind == "speculative":
        eng = _dense_engine(speculative=SpeculativeConfig(
            enabled=True, draft_len=3))
    elif kind == "one_device_mesh":
        eng = ServingEngine(
            decoder.init(CFG, jax.random.key(0)), CFG, ServingConfig(**GEO),
            mesh_ctx=ServeMeshConfig().build_contexts(jax.devices()[:1])[0])
    else:
        eng = _dense_engine()
    plan = _random_plan(eng, np.random.default_rng(39))
    packed = eng._plan_batch(plan)
    assert packed.dtype == jnp.int32 and packed.ndim == 1
    back = jax.jit(lambda v: unpack_device(eng._plan_layout, v))(packed)
    want = {k: v for k, v in vars(plan).items() if isinstance(v, np.ndarray)}
    assert set(back) == set(want)
    assert ("verify_rows" in want) == (kind == "speculative")
    for name, arr in want.items():
        got = np.asarray(back[name])
        assert got.dtype == arr.dtype and got.shape == arr.shape, name
        np.testing.assert_array_equal(
            got.view(np.int32), arr.view(np.int32), err_msg=name)


def _serve_digest(eng, vocab, steps=5):
    """sha256 over the (tokens, log-probabilities) `run_step` returned on
    each step of a fixed serve run: three prompts, one of them sampled."""
    rng = np.random.default_rng(7)
    sched = eng.make_scheduler()
    for i, n in enumerate((9, 5, 3)):
        sched.submit(Request(
            prompt=rng.integers(0, vocab, n).tolist(), max_new_tokens=6,
            temperature=0.8 if i == 1 else 0.0, seed=1234 + i))
    h = hashlib.sha256()
    for step in range(steps):
        plan = eng.plan_turn(sched, step)
        out = eng.run_step(plan)
        h.update(np.ascontiguousarray(out[0]).tobytes())
        h.update(np.ascontiguousarray(out[1]).tobytes())
        eng.absorb_outputs(sched, plan, out, step)
    return h.hexdigest()


def _case(name):
    if name == "dense":
        return CFG, decoder.init(CFG, jax.random.key(0)), CFG.vocab_size
    mod = {"ouro": ouro_case, "jamba": jamba_case, "exaone": exaone_case}[name]
    cfg = mod.config()
    return cfg, own(mod.init_params(cfg)), mod.VOCAB


#: `_serve_digest` of each case as the parent commit (9488542, eleven uploads
#: and two read-back copies a step) returned it with jax 0.9.0 on the CPU
PARENT_DIGESTS = {
    "dense": (
        "1b4217b539f74e3e7ddb18a9ea60ddf8c550bf6f26c14c982322ded54bdd0e3c"),
    "exaone": (
        "8d18401fa3d61da412e3693ba5b290bfb03acb1c3555feed3b1c2b715d6549db"),
    "jamba": (
        "008d8a5d8e2707f9b101d2a3b9ac25ef8754df7b242ec1b6c91b22c0f0830656"),
    "ouro": (
        "daf4a79011aa5dd2627733182993389b0243a124acd26060f1bddb0c40faf0e2"),
}


@pytest.mark.parametrize("name", sorted(PARENT_DIGESTS))
def test_outputs_unchanged_and_one_transfer_each_way(name):
    cfg, params, vocab = _case(name)
    eng = ServingEngine(params, cfg, ServingConfig(**GEO))
    reg = eng.obs.registry
    moved = {d: reg.counter("serve_step_transfers_total", direction=d)
             for d in ("h2d", "d2h")}
    before = {d: c.value for d, c in moved.items()}
    steps_before = reg.counter("serve_steps_total").value
    assert _serve_digest(eng, vocab) == PARENT_DIGESTS[name]
    steps = reg.counter("serve_steps_total").value - steps_before
    assert steps == 5
    assert {d: c.value - before[d] for d, c in moved.items()} == {
        "h2d": steps, "d2h": steps}
    assert eng.step_cache_size() == 1
