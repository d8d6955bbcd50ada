"""Layers that are not attention, on the serving path: a Jamba-shaped toy
(Mamba-1 mixers whose convolution and recurrent state live per SLOT beside
the two attention layers' pages, the TIED head) through chunked prefill and
decode against the plain reference's full forward; slot re-use and preemption
without a reset; defrag and copy-on-write leave the state alone; prefix hits
cut and counted; speculation, the pool hand-off and tp > 1 refused by name;
replicas behind a router work."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.observability import ObservabilityConfig
from automodel_tpu.serving import Request, ServingConfig, ServingEngine
from automodel_tpu.serving.kv_pages import apply_defrag, init_state, pool_bytes
from automodel_tpu.serving.prefix_cache import PrefixCacheConfig
from automodel_tpu.serving.router import (
    DisaggConfig, DisaggRouter, ReplicaRouter, ServeMeshConfig)
from automodel_tpu.speculative.serve_draft import SpeculativeConfig
from tests import jamba_case
from tests.serving_params import own

#: float32 engine against the float32 reference: the order of a scan's sums
#: and of XLA's matrix products; the tied head's logits reach tens, so a
#: log-probability carries 1e-4 of them at worst (read: 3e-5)
LOGPROB_TOL = 2e-4
GEO = dict(page_size=4, num_pages=48, max_slots=3, pages_per_slot=12,
           token_budget=10, prefill_chunk=6)


@pytest.fixture(scope="module")
def case():
    cfg = jamba_case.config()
    return cfg, jamba_case.init_params(cfg)


def _prompts(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, jamba_case.VOCAB, n).tolist() for n in lengths]


def _reqs(prompts, new=6):
    return [Request(prompt=list(p), max_new_tokens=new) for p in prompts]


def _serve_with_logprobs(engine, requests):
    """`serve_batch`, keeping (token, log-probability) as every step
    reported them for each request, and each turn's `state_runs`."""
    seen, scheds, runs = {}, [], []
    make, inner, plan_turn = engine.make_scheduler, engine.run_step, engine.plan_turn

    def make_scheduler(**kw):
        scheds.append(make(**kw))
        return scheds[-1]

    def run_step(plan):
        out = inner(plan)
        for slot, _c, samples in plan.scheduled:
            if samples:
                rid = scheds[-1].running[slot].rid
                seen.setdefault(rid, []).append(
                    (int(out[0][slot]), float(out[1][slot])))
        return out

    def planned(sched, step_idx):
        plan = plan_turn(sched, step_idx)
        runs.append(sched.turn_stats(sched.n_preemptions, plan)["state_runs"])
        return plan

    engine.make_scheduler, engine.run_step = make_scheduler, run_step
    engine.plan_turn = planned
    return engine.serve_batch(requests), seen, runs


def _logprob_errors(params, prompts, res, seen):
    errs = []
    for rid, (prompt, out) in enumerate(zip(prompts, res["outputs"])):
        assert [t for t, _ in seen[rid]] == out
        logits = jamba_case.reference(params, [prompt + out])
        rows = jamba_case.log_softmax(logits[0, len(prompt) - 1:-1])
        assert rows.argmax(-1).tolist() == out, rid
        errs += [abs(lp - rows[i, t]) for i, (t, lp) in enumerate(seen[rid])]
    return np.asarray(errs)


def test_logits_match_reference_through_chunks_slot_reuse_and_preemption(case):
    """Seven requests over three slots (every slot re-used, never reset),
    prompts of up to 23 tokens in chunks of 6 (the carried state crosses
    steps inside the convolution's reach and past it), a pool so small that
    requests are preempted and start again from position 0."""
    cfg, params = case
    geo = {**GEO, "num_pages": 10}
    engine = ServingEngine(own(params), cfg, ServingConfig(**geo))
    assert len(engine.pool[0]) == 2 and len(engine.state) == 4
    prompts = _prompts([9, 23, 6, 14, 2, 17, 11], seed=40)
    res, seen, runs = _serve_with_logprobs(engine, _reqs(prompts))
    assert res["stats"]["preemptions"] >= 1
    assert res["stats"]["compiled_signatures"] == 1
    assert max(runs) == 3 and min(r for r in runs if r) >= 1
    errs = _logprob_errors(params, prompts, res, seen)
    assert len(errs) == 7 * 6 and errs.max() < LOGPROB_TOL, errs.max()

    # a bfloat16 engine keeps the recurrent state in float32 (under the tied
    # head of this draw a served token has probability 1 in any precision, so
    # its log-probabilities cannot tell the dtypes apart: the benchmark's
    # configuration unties the head for that reason)
    low = jamba_case.config(dtype=jnp.bfloat16)
    eng16 = ServingEngine(own(params), low, ServingConfig(**geo))
    assert eng16.state[0][1].dtype == jnp.float32
    assert eng16.state[0][0].dtype == jnp.bfloat16
    res16 = eng16.serve_batch(_reqs(prompts))
    assert res16["stats"]["compiled_signatures"] == 1
    assert [len(o) for o in res16["outputs"]] == [6] * 7


def test_junk_in_every_slot_and_in_the_trash_slot_changes_nothing(case):
    cfg, params = case
    prompts = _prompts([7, 12, 3], seed=41)
    clean = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    want = clean.serve_batch(_reqs(prompts))["outputs"]
    dirty = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    dirty.state = jax.tree.map(
        lambda a: jnp.full(a.shape, 1e4, a.dtype), dirty.state)
    assert dirty.serve_batch(_reqs(prompts))["outputs"] == want


def test_state_is_a_tree_of_its_own_donated_and_aliased(case):
    cfg, params = case
    engine = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    S = GEO["max_slots"]
    for conv, ssm in engine.state:
        assert conv.shape == (3, S + 1, 64) and ssm.shape == (S + 1, 8, 64)
    # no leaf of the pool has a slot axis; no leaf of the state a page axis
    assert all(a.shape[0] == GEO["num_pages"] + 1
               for a in jax.tree.leaves(engine.pool))
    text = engine.lower_step().as_text()
    donated = len(jax.tree.leaves(engine.pool)) + len(jax.tree.leaves(engine.state))
    assert len(re.findall(r"tf\.aliasing_output", text)) == donated == 4 + 8
    # a decoder of attention alone holds none, and lowers as it always did
    # (tests/unit/test_serve_step_layers.py pins its text)
    assert init_state(dataclasses.replace(cfg, layer_ops=None), S) == ()
    reg = engine.obs.registry.snapshot()
    assert reg["serve_ssm_layers"] == 4 and reg["serve_attn_layers"] == 2
    assert reg["serve_state_bytes_per_slot"] == 4 * (8 * 64 * 4 + 3 * 64 * 4)
    assert reg["serve_state_bytes_per_slot"] == pool_bytes(engine.state) // (S + 1)
    # keys and values of the two attention layers alone: 2 x 2 x 1 x 8 x 4 B
    assert reg["serve_kv_bytes_per_token"] == 2 * 2 * 8 * 4


def test_defrag_and_copy_on_write_leave_the_state_alone(case):
    """The prefix cache on (shared pages, copy-on-write splits in the step)
    and a defrag in mid-run: page-axis operations map over the pool, and the
    state is not in it."""
    cfg, params = case
    system = _prompts([9], seed=42)[0]
    prompts = [system + p for p in _prompts([3, 5, 2, 4], seed=43)]
    sc = ServingConfig(**{**GEO, "prefix_cache": PrefixCacheConfig(enabled=True)})
    engine = ServingEngine(own(params), cfg, sc)
    sched = engine.make_scheduler()
    for p in prompts:
        sched.submit(Request(prompt=list(p), max_new_tokens=5))
    step, compacted = 0, 0
    while sched.has_work:
        plan = sched.schedule(step)
        if plan is not None:
            tokens, _ = engine.run_step(plan)
            sched.update(plan, tokens, step)
            if step in (3, 6):
                before = jax.tree.map(np.asarray, engine.state)
                compacted += engine.defrag(sched)
                for a, b in zip(jax.tree.leaves(before),
                                jax.tree.leaves(engine.state)):
                    np.testing.assert_array_equal(a, np.asarray(b))
        step += 1
    assert compacted >= 1
    for p, req in zip(prompts, sorted(sched.finished, key=lambda r: r.rid)):
        ref = jamba_case.reference(params, [p + req.generated])[0]
        assert ref.argmax(-1)[len(p) - 1:-1].tolist() == req.generated
    # `apply_defrag` handed the state would index its first axis as pages:
    # it is handed the pool alone
    src = jnp.arange(sc.num_pages, dtype=jnp.int32)
    moved = apply_defrag(jax.tree.map(jnp.copy, engine.pool), src)
    assert jax.tree.structure(moved) == jax.tree.structure(engine.pool)


def test_prefix_hits_are_cut_and_counted(case, caplog):
    cfg, params = case
    system = _prompts([13], seed=44)[0]
    mk = lambda: [Request(prompt=system + tail, max_new_tokens=4)  # noqa: E731
                  for tail in _prompts([2, 3], seed=45)]
    sc = ServingConfig(**{**GEO, "prefix_cache": PrefixCacheConfig(enabled=True)})
    with caplog.at_level("WARNING"):
        engine = ServingEngine(own(params), cfg, sc)
    assert "every hit is CUT" in caplog.text
    engine.serve_batch(mk())
    res = engine.serve_batch(mk())            # the tree now holds the prompt
    st = res["stats"]
    assert st["prefix_hits"] == 0 and st["prefill_skipped_tokens"] == 0
    assert st["prefix_hits_cut"] == 2
    assert engine.obs.registry.snapshot()["serve_prefix_hits_cut_total"] >= 2
    for r, out in zip(res["requests"], res["outputs"]):
        ref = jamba_case.reference(params, [r.prompt + out])[0]
        assert ref.argmax(-1)[len(r.prompt) - 1:-1].tolist() == out
    # the same traffic on a decoder of attention alone DOES hit
    plain = dataclasses.replace(cfg, layer_ops=None, use_rope=True)
    from automodel_tpu.models.llm import decoder

    eng = ServingEngine(decoder.init(plain, jax.random.key(0)), plain, sc)
    eng.serve_batch(mk())
    st = eng.serve_batch(mk())["stats"]
    assert st["prefix_hits"] >= 1 and st["prefix_hits_cut"] == 0


def test_speculation_is_refused_by_name(case):
    cfg, params = case
    sc = ServingConfig(**{**GEO, "speculative": SpeculativeConfig(
        enabled=True, draft_len=2)})
    with pytest.raises(NotImplementedError, match="speculative decoding over"):
        ServingEngine(own(params), cfg, sc)


def test_pool_handoff_is_refused_by_name(case):
    from automodel_tpu.serving.kv_transfer import KVTransfer

    cfg, params = case
    with pytest.raises(NotImplementedError, match="hand-off between pools"):
        DisaggRouter(own(params), cfg, ServingConfig(**GEO),
                     DisaggConfig(enabled=True))
    a = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    b = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    with pytest.raises(NotImplementedError, match="per-slot state would not"):
        KVTransfer(a, b)


def test_tp2_is_refused_by_name(case):
    cfg, params = case
    wide = dataclasses.replace(cfg, num_kv_heads=2)
    with pytest.raises(ValueError, match="holds a recurrent state"):
        ReplicaRouter(own(jamba_case.init_params(wide)), wide,
                      ServingConfig(**GEO), ServeMeshConfig(replicas=1, tp=2))


def test_replicas_behind_a_router_work(case):
    """Whole engines side by side: each holds its own slots' state; a
    request lives and dies on one replica."""
    cfg, params = case
    prompts = _prompts([8, 15, 4, 11, 6], seed=46)
    base = ServingEngine(own(params), cfg, ServingConfig(**GEO)).serve_batch(
        _reqs(prompts))
    router = ReplicaRouter(own(params), cfg, ServingConfig(**GEO),
                           ServeMeshConfig(replicas=2, tp=1))
    res = router.serve_batch(_reqs(prompts))
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["compiled_signatures"] == 1
    assert min(res["stats"]["requests_per_replica"]) >= 1


def test_plan_span_carries_state_runs(case):
    cfg, params = case
    sc = ServingConfig(**{**GEO, "observability": ObservabilityConfig(enabled=True)})
    engine = ServingEngine(own(params), cfg, sc)
    engine.serve_batch(_reqs(_prompts([7, 3], seed=47), new=3))
    plans = [e for e in engine.obs.tracer.events if e.name == "step.plan"]
    assert plans and all("state_runs" in e.args for e in plans)
    assert max(e.args["state_runs"] for e in plans) == 2
    # a decoder of attention alone says nothing of runs
    from automodel_tpu.models.llm import decoder

    plain = dataclasses.replace(cfg, layer_ops=None, use_rope=True)
    eng = ServingEngine(decoder.init(plain, jax.random.key(0)), plain, sc)
    eng.serve_batch(_reqs(_prompts([5], seed=48), new=2))
    assert all("state_runs" not in e.args
               for e in eng.obs.tracer.events if e.name == "step.plan")
