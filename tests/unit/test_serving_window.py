"""Attention layers with a window, on the serving path: the toy K-EXAONE
(three window layers of 8 tokens to every full layer, a leading dense layer,
experts) through chunked prefill and decode against the plain reference's
full forward at contexts several times the window AND the ring; the window
layers' keys in a ring per slot beside the full layers' pages: slot re-use
and preemption without a reset, junk in every ring page, the ring a tree of
its own that defrag and copy-on-write never see; prefix hits cut and
counted; speculation, the pool hand-off and tp > 1 refused by name; a model
without windows lowers as it always did."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.moe_lm import decoder as moe_decoder
from automodel_tpu.observability import ObservabilityConfig
from automodel_tpu.serving import Request, ServingConfig, ServingEngine
from automodel_tpu.serving.kv_pages import (
    apply_defrag, init_rings, pool_bytes, ring_page_tables, ring_pages)
from automodel_tpu.serving.prefix_cache import PrefixCacheConfig
from automodel_tpu.serving.router import (
    DisaggConfig, DisaggRouter, ReplicaRouter, ServeMeshConfig)
from automodel_tpu.speculative.serve_draft import SpeculativeConfig
from tests import exaone_case as ec
from tests.serving_params import own

#: float32 engine against the float32 reference: the order of XLA's sums
LOGPROB_TOL = 2e-4
#: pages of 4 tokens, chunks of 6: a ring of ceil((8 - 1 + 6) / 4) + 1 = 5
#: pages = 20 tokens a slot, contexts of up to 64
GEO = dict(page_size=4, num_pages=64, max_slots=3, pages_per_slot=16,
           token_budget=12, prefill_chunk=6)
RING = 5


@pytest.fixture(scope="module")
def case():
    cfg = ec.config()
    return cfg, ec.init_params(cfg)


def _prompts(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, ec.VOCAB, n).tolist() for n in lengths]


def _reqs(prompts, new=6):
    return [Request(prompt=list(p), max_new_tokens=new) for p in prompts]


def _serve_with_logprobs(engine, requests):
    """`serve_batch`, keeping (token, log-probability) as every step
    reported them for each request."""
    seen, scheds = {}, []
    make, inner = engine.make_scheduler, engine.run_step

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

    engine.make_scheduler, engine.run_step = make_scheduler, run_step
    return engine.serve_batch(requests), seen


def _logprob_errors(params, prompts, res, seen):
    errs = []
    for rid, (prompt, out) in enumerate(zip(prompts, res["outputs"])):
        assert [t for t, _ in seen[rid]] == out
        logits = ec.reference(params, [prompt + out])
        rows = ec.log_softmax(logits[0, len(prompt) - 1:-1])
        assert rows.argmax(-1).tolist() == out, rid
        errs += [abs(lp - rows[i, t]) for i, (t, lp) in enumerate(seen[rid])]
    return np.asarray(errs)


def test_ring_geometry():
    assert ring_pages(8, 6, 4) == RING
    # the cell's: a window of 128 behind chunks of 768 rows in 128-token pages
    assert ring_pages(128, 768, 128) == 8 and ring_pages(128, 384, 128) == 5
    # every page a chunk's rows and their windows can touch has a ring index
    # of its own: positions p0 - window + 1 .. p0 + chunk - 1, any p0
    for window, chunk, ps in ((8, 6, 4), (128, 768, 128), (5, 1, 4), (16, 7, 8)):
        R = ring_pages(window, chunk, ps)
        for p0 in range(0, 200):
            pages = {p // ps for p in range(max(p0 - window + 1, 0), p0 + chunk)}
            assert len({p % R for p in pages}) == len(pages) <= R
    tables = np.asarray(ring_page_tables(jnp.asarray([0, 2, 3]), 5, 7))
    assert tables.tolist() == [[0, 1, 2, 3, 4, 0, 1], [10, 11, 12, 13, 14, 10, 11],
                               [15, 16, 17, 18, 19, 15, 16]]


def test_logits_match_reference_through_chunks_slot_reuse_and_preemption(case):
    """Seven requests over three slots (every slot re-used, never reset),
    prompts of up to 57 tokens in chunks of 6: seven windows and three rings
    long, so every ring page is recycled under later chunks; a pool so small
    that requests are preempted and start again from position 0."""
    cfg, params = case
    geo = {**GEO, "num_pages": 24}
    engine = ServingEngine(own(params), cfg, ServingConfig(**geo))
    # the pool holds the two full layers alone, the state the six rings
    assert [len(s) for s in engine.pool] == [0, 2] and len(engine.state) == 6
    assert engine._ring_pages == RING and engine.begins_at_zero
    prompts = _prompts([33, 57, 6, 41, 2, 50, 21], seed=40)
    res, seen = _serve_with_logprobs(engine, _reqs(prompts))
    assert res["stats"]["preemptions"] >= 1
    assert res["stats"]["compiled_signatures"] == 1
    errs = _logprob_errors(params, prompts, res, seen)
    assert len(errs) == 7 * 6 and errs.max() < LOGPROB_TOL, errs.max()
    # the same through int8 pages and rings (scales ride both): it runs, in
    # one program, and mostly agrees
    low = ServingEngine(own(params), cfg, ServingConfig(
        **{**geo, "kv_cache_dtype": "int8"}))
    assert len(low.state[0]) == 4 and low.state[0][0].dtype == jnp.int8
    res8 = low.serve_batch(_reqs(prompts))
    assert res8["stats"]["compiled_signatures"] == 1
    same = sum(a == b for x, y in zip(res8["outputs"], res["outputs"])
               for a, b in zip(x, y))
    assert same >= 0.8 * 42


def test_junk_in_every_ring_page_and_in_the_trash_slot_changes_nothing(case):
    cfg, params = case
    prompts = _prompts([27, 44, 3], seed=41)
    clean = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    want = clean.serve_batch(_reqs(prompts))["outputs"]
    dirty = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    dirty.state = jax.tree.map(
        lambda a: jnp.full(a.shape, 1e4, a.dtype), dirty.state)
    assert dirty.serve_batch(_reqs(prompts))["outputs"] == want


def test_ring_is_a_tree_of_its_own_donated_and_aliased(case):
    cfg, params = case
    engine = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    S, ps = GEO["max_slots"], GEO["page_size"]
    for k, v in engine.state:
        assert k.shape == v.shape == ((S + 1) * RING, ps, 2, 16)
    # no leaf of the pool is a ring; no ring has the pool's page axis
    assert all(a.shape[0] == GEO["num_pages"] + 1
               for a in jax.tree.leaves(engine.pool))
    assert (S + 1) * RING != GEO["num_pages"] + 1
    lowered = engine.lower_step()
    donated = len(jax.tree.leaves(engine.pool)) + len(jax.tree.leaves(engine.state))
    assert len(re.findall(
        r"tf\.aliasing_output", lowered.as_text())) == donated == 4 + 12
    # the compiled step names both kinds' sublayers and both writes
    text = lowered.compile().as_text()
    for scope in ("serve.attn/serve.attn.window", "serve.attn/serve.attn.full",
                  "serve.ring_write", "serve.pool_write"):
        assert f"/{scope}/" in text, scope
    reg = engine.obs.registry.snapshot()
    assert reg["serve_window_layers"] == 6 and reg["serve_full_layers"] == 2
    assert reg["serve_attn_layers"] == 8 and reg["serve_ssm_layers"] == 0
    assert reg["serve_experts_held"] == 8
    # a slot's rings: 6 layers x k, v x 5 pages x 4 tokens x 2 heads x 16 x 4 B
    assert reg["serve_window_bytes_per_slot"] == 6 * 2 * 5 * 4 * 2 * 16 * 4
    assert reg["serve_window_bytes_per_slot"] == pool_bytes(engine.state) // (S + 1)
    assert reg["serve_state_bytes_per_slot"] == 0
    # keys and values of the two FULL layers alone: 2 x 2 x 2 x 16 x 4 B
    assert reg["serve_kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    # whatever the context: a longer one costs pages of the pool, not ring
    longer = ServingEngine(own(params), cfg, ServingConfig(
        **{**GEO, "pages_per_slot": 64}))
    assert pool_bytes(longer.state) == pool_bytes(engine.state)


def test_a_model_without_windows_holds_no_ring_and_lowers_as_it_did(case):
    """(tests/unit/test_serve_step_layers.py pins the very text.)"""
    cfg, _ = case
    plain = ec.config({**ec.HF, "sliding_window": None})
    engine = ServingEngine(moe_decoder.init(plain, jax.random.key(0)), plain,
                           ServingConfig(**GEO))
    assert engine.state == () and engine._ring_pages == 0
    assert not engine.begins_at_zero
    assert [len(s) for s in engine.pool] == [1, 7]
    text = engine.lower_step().compile().as_text()
    assert "/serve.attn/" in text
    assert "serve.attn.full" not in text and "serve.ring_write" not in text
    assert init_rings(plain, 0, 3, 0, 4) == ()


def test_defrag_and_copy_on_write_leave_the_rings_alone(case):
    """The prefix cache on (copy-on-write splits in the step: every hit is
    cut, the block still runs) and a defrag in mid-run: page-axis operations
    map over the pool, and the rings are not in it."""
    cfg, params = case
    system = _prompts([19], seed=42)[0]
    prompts = [system + p for p in _prompts([3, 15, 2, 24], seed=43)]
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
            if step in (3, 6, 9):
                before = jax.tree.map(np.asarray, engine.state)
                compacted += engine.defrag(sched)
                for a, b in zip(jax.tree.leaves(before),
                                jax.tree.leaves(engine.state)):
                    np.testing.assert_array_equal(a, np.asarray(b))
        step += 1
    assert compacted >= 1
    for p, req in zip(prompts, sorted(sched.finished, key=lambda r: r.rid)):
        ref = ec.reference(params, [p + req.generated])[0]
        assert ref.argmax(-1)[len(p) - 1:-1].tolist() == req.generated
    # `apply_defrag` handed a ring would index its first axis as pool pages:
    # it is handed the pool alone
    src = jnp.arange(sc.num_pages, dtype=jnp.int32)
    moved = apply_defrag(jax.tree.map(jnp.copy, engine.pool), src)
    assert jax.tree.structure(moved) == jax.tree.structure(engine.pool)


def test_prefix_hits_are_cut_and_counted(case, caplog):
    cfg, params = case
    system = _prompts([23], seed=44)[0]
    mk = lambda: [Request(prompt=system + tail, max_new_tokens=4)  # noqa: E731
                  for tail in _prompts([2, 3], seed=45)]
    sc = ServingConfig(**{**GEO, "prefix_cache": PrefixCacheConfig(enabled=True)})
    with caplog.at_level("WARNING"):
        engine = ServingEngine(own(params), cfg, sc)
    assert "every hit is CUT" in caplog.text and "window layer's ring" in caplog.text
    engine.serve_batch(mk())
    res = engine.serve_batch(mk())            # the tree now holds the prompt
    st = res["stats"]
    assert st["prefix_hits"] == 0 and st["prefill_skipped_tokens"] == 0
    assert st["prefix_hits_cut"] == 2
    assert engine.obs.registry.snapshot()["serve_prefix_hits_cut_total"] >= 2
    for r, out in zip(res["requests"], res["outputs"]):
        ref = ec.reference(params, [r.prompt + out])[0]
        assert ref.argmax(-1)[len(r.prompt) - 1:-1].tolist() == out
    # the same traffic on the same model without its windows DOES hit
    plain = ec.config({**ec.HF, "sliding_window": None})
    eng = ServingEngine(moe_decoder.init(plain, jax.random.key(0)), plain, sc)
    eng.serve_batch(mk())
    st = eng.serve_batch(mk())["stats"]
    assert st["prefix_hits"] >= 1 and st["prefix_hits_cut"] == 0


def test_speculation_is_refused_by_name(case):
    cfg, params = case
    sc = ServingConfig(**{**GEO, "speculative": SpeculativeConfig(
        enabled=True, draft_len=2)})
    with pytest.raises(NotImplementedError, match="window layer's keys in a ring"):
        ServingEngine(own(params), cfg, sc)


def test_pool_handoff_is_refused_by_name(case):
    from automodel_tpu.serving.kv_transfer import KVTransfer

    cfg, params = case
    with pytest.raises(NotImplementedError, match="hand-off between pools"):
        DisaggRouter(own(params), cfg, ServingConfig(**GEO),
                     DisaggConfig(enabled=True))
    a = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    b = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    with pytest.raises(NotImplementedError, match="ring per slot would not"):
        KVTransfer(a, b)


def test_tp2_and_ep2_are_refused_by_name(case):
    cfg, params = case
    with pytest.raises(ValueError, match="sliding-window layers"):
        ReplicaRouter(own(params), cfg, ServingConfig(**GEO),
                      ServeMeshConfig(replicas=1, tp=2))
    hf = ec.share_hf(2)
    share = ec.config(hf)
    with pytest.raises(ValueError, match="holds a share of its experts"):
        ReplicaRouter(own(ec.init_params(share, hf=hf)), share,
                      ServingConfig(**GEO), ServeMeshConfig(replicas=1, ep=2))


def test_replicas_behind_a_router_work(case):
    cfg, params = case
    prompts = _prompts([28, 35, 4, 11, 46], seed=46)
    base = ServingEngine(own(params), cfg, ServingConfig(**GEO)).serve_batch(
        _reqs(prompts))
    router = ReplicaRouter(own(params), cfg, ServingConfig(**GEO),
                           ServeMeshConfig(replicas=2, tp=1))
    res = router.serve_batch(_reqs(prompts))
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["compiled_signatures"] == 1


def test_plan_span_carries_both_kinds_live_blocks(case):
    cfg, params = case
    sc = ServingConfig(**{**GEO, "observability": ObservabilityConfig(enabled=True)})
    engine = ServingEngine(own(params), cfg, sc)
    engine.serve_batch(_reqs(_prompts([39, 3], seed=47), new=3))
    plans = [e for e in engine.obs.tracer.events if e.name == "step.plan"]
    assert plans and all(
        {"window_blocks", "full_blocks"} <= set(e.args) for e in plans)
    # a full layer walks every page up to the row, a window layer the few
    # its window touches: at a 41-token context 11 against at most 3
    last = max(plans, key=lambda e: e.args["full_blocks"]).args
    assert last["full_blocks"] >= 10 and last["window_blocks"] <= 6
    assert all(e.args["window_blocks"] <= e.args["full_blocks"] for e in plans)
    # a model without windows says nothing of either
    plain = ec.config({**ec.HF, "sliding_window": None})
    eng = ServingEngine(moe_decoder.init(plain, jax.random.key(0)), plain, sc)
    eng.serve_batch(_reqs(_prompts([5], seed=48), new=2))
    assert all("window_blocks" not in e.args
               for e in eng.obs.tracer.events if e.name == "step.plan")


def test_a_share_of_the_experts_serves_like_its_reference():
    hf = ec.share_hf(2, first=4)
    cfg = ec.config(hf)
    params = ec.init_params(cfg, seed=2, hf=hf)
    engine = ServingEngine(own(params), cfg, ServingConfig(**GEO))
    assert engine.obs.registry.snapshot()["serve_experts_held"] == 2
    prompts = _prompts([31, 8], seed=49)
    res = engine.serve_batch(_reqs(prompts, new=5))
    for p, out in zip(prompts, res["outputs"]):
        ref = ec.reference(params, [p + out], hf=hf)[0]
        assert ref.argmax(-1)[len(p) - 1:-1].tolist() == out
