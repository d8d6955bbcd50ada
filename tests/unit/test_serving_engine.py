"""Serving engine: token-for-token parity vs generate(), fixed-shape step.

The acceptance contract of the continuous-batching engine:

- under greedy decoding, outputs on a RAGGED request stream (staggered
  arrivals, mixed prompt lengths, chunked prefill interleaved with decode,
  preempt-and-requeue) exactly match per-request `generate()` — for a GQA
  and an MLA decoder, on CPU;
- the decode step compiles ONCE: the jit cache-miss counter stays at 1 no
  matter how requests join/leave (the fixed-shape contract).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.inference.generate import GenerateConfig, generate
from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.serving import Request, ServingConfig, ServingEngine
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)
MLA = dataclasses.replace(
    CFG, attention_type="mla", mla_kv_lora_rank=16, mla_q_lora_rank=12,
    mla_qk_nope_head_dim=8, mla_qk_rope_head_dim=8, mla_v_head_dim=8,
)


def _ragged_prompts(lens, vocab=64, seed0=0):
    return [
        [int(t) for t in np.random.default_rng(seed0 + i).integers(1, vocab, (l,))]
        for i, l in enumerate(lens)
    ]


def _assert_parity(params, cfg, engine, prompts, arrivals, max_new):
    reqs = [
        Request(prompt=list(p), max_new_tokens=max_new, arrival=a)
        for p, a in zip(prompts, arrivals)
    ]
    res = engine.serve_batch(reqs)
    for p, out in zip(prompts, res["outputs"]):
        ref = generate(
            params, cfg, jnp.asarray([p], jnp.int32), jax.random.key(0),
            GenerateConfig(max_new_tokens=max_new),
        )
        ref_new = [int(t) for t in np.asarray(ref)[0, len(p):]]
        assert ref_new == out, f"paged engine diverged: {ref_new} vs {out}"
    return res


def test_gqa_parity_ragged_stream_compiles_once():
    """Mixed prompt lengths + staggered arrivals: chunked prefill of late
    joiners interleaves with running decodes; greedy tokens match the
    batch-synchronous path exactly and the step compiles exactly once."""
    params = decoder.init(CFG, jax.random.key(0))
    engine = ServingEngine(own(params), CFG, ServingConfig(
        page_size=4, num_pages=24, max_slots=3, pages_per_slot=6,
        token_budget=8, prefill_chunk=4,
    ))
    prompts = _ragged_prompts([5, 9, 3, 7, 11])
    res = _assert_parity(params, CFG, engine, prompts, [0, 0, 2, 3, 5], 6)
    # 5 requests through 3 slots: joins/leaves happened, one signature
    assert res["stats"]["compiled_signatures"] == 1
    assert engine.step_cache_size() == 1
    assert res["stats"]["new_tokens"] == 5 * 6


def test_mla_parity_ragged_stream_compiles_once():
    params = decoder.init(MLA, jax.random.key(0))
    engine = ServingEngine(own(params), MLA, ServingConfig(
        page_size=4, num_pages=20, max_slots=3, pages_per_slot=5,
        token_budget=6, prefill_chunk=3,
    ))
    prompts = _ragged_prompts([6, 9, 4, 8], seed0=10)
    res = _assert_parity(params, MLA, engine, prompts, [0, 1, 2, 4], 5)
    assert res["stats"]["compiled_signatures"] == 1


def test_preempt_and_requeue_parity():
    """A pool too small for every admitted request forces recompute-style
    preemption; greedy outputs stay exact (and the requeue actually ran)."""
    params = decoder.init(CFG, jax.random.key(0))
    engine = ServingEngine(own(params), CFG, ServingConfig(
        page_size=2, num_pages=8, max_slots=3, pages_per_slot=6,
        token_budget=6, prefill_chunk=3,
    ))
    prompts = _ragged_prompts([4, 4, 4], seed0=20)
    res = _assert_parity(params, CFG, engine, prompts, [0, 0, 0], 5)
    assert res["stats"]["preemptions"] >= 1
    assert res["stats"]["compiled_signatures"] == 1
    # preempted requests carry the audit trail
    assert sum(r.preemptions for r in res["requests"]) >= 1


def test_eos_stops_and_frees_pages():
    params = decoder.init(CFG, jax.random.key(0))
    prompt = _ragged_prompts([5], seed0=30)[0]
    # discover greedy continuation, declare its 2nd token EOS
    ref = generate(
        params, CFG, jnp.asarray([prompt], jnp.int32), jax.random.key(0),
        GenerateConfig(max_new_tokens=4),
    )
    eos = int(np.asarray(ref)[0, len(prompt) + 1])
    engine = ServingEngine(own(params), CFG, ServingConfig(
        page_size=4, num_pages=8, max_slots=2, pages_per_slot=4, token_budget=6,
    ))
    sched = engine.make_scheduler()
    sched.submit(Request(prompt=list(prompt), max_new_tokens=8, eos_token_id=eos))
    step = 0
    while sched.has_work:
        plan = sched.schedule(step)
        tokens, _ = engine.run_step(plan)
        sched.update(plan, tokens, step)
        step += 1
    (req,) = sched.finished
    assert req.finish_reason == "eos" and req.generated[-1] == eos
    assert len(req.generated) == 2  # stopped AT the eos, not after max_new
    assert sched.alloc.num_free == 8  # every page returned to the pool


def test_moe_decoder_parity():
    """DeepSeek shape: dense prefix + MoE stack + MLA paged cache."""
    from automodel_tpu.models.moe_lm import decoder as moe_decoder
    from automodel_tpu.models.moe_lm.decoder import MoETransformerConfig
    from automodel_tpu.moe.config import MoEConfig

    cfg = MoETransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=3,
        num_heads=4, num_kv_heads=4, first_k_dense=1, dtype=jnp.float32,
        remat_policy="none",
        attention_type="mla", mla_kv_lora_rank=16, mla_q_lora_rank=12,
        mla_qk_nope_head_dim=8, mla_qk_rope_head_dim=8, mla_v_head_dim=8,
        moe=MoEConfig(
            n_routed_experts=4, n_shared_experts=1, experts_per_token=2,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            aux_loss_coeff=0.0, dispatcher="dropless",
        ),
    )
    params = moe_decoder.init(cfg, jax.random.key(0))
    engine = ServingEngine(own(params), cfg, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=6, prefill_chunk=3,
    ))
    prompts = _ragged_prompts([5, 7], seed0=40)
    res = _assert_parity(params, cfg, engine, prompts, [0, 1], 4)
    assert res["stats"]["compiled_signatures"] == 1


@pytest.mark.slow
def test_windows_and_sinks_parity():
    """gemma2/gpt-oss shape (alternating windows + sinks) takes the XLA
    paged path; greedy parity must hold there too."""
    cfg = dataclasses.replace(
        CFG, qk_norm=False, sliding_window=4,
        layer_types=("sliding", "global"), attention_sinks=True,
    )
    params = decoder.init(cfg, jax.random.key(0))
    params["layers"]["sinks"] = 0.5 + 0.1 * jax.random.normal(
        jax.random.key(11), params["layers"]["sinks"].shape
    )
    engine = ServingEngine(own(params), cfg, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=6, prefill_chunk=3,
    ))
    prompts = _ragged_prompts([5, 7], seed0=50)
    _assert_parity(params, cfg, engine, prompts, [0, 0], 4)


@pytest.mark.slow
def test_sampling_deterministic_across_batching():
    """Sampling keys derive from (request seed, position): the same request
    yields the same tokens no matter the engine geometry, co-resident
    traffic, or preemptions."""
    params = decoder.init(CFG, jax.random.key(0))
    prompt = _ragged_prompts([5], seed0=60)[0]

    def run(serve_cfg, extra=()):
        engine = ServingEngine(own(params), CFG, serve_cfg)
        reqs = [Request(prompt=list(prompt), max_new_tokens=5,
                        temperature=0.8, seed=7)]
        reqs += [Request(prompt=list(p), max_new_tokens=4, seed=1 + i)
                 for i, p in enumerate(extra)]
        return engine.serve_batch(reqs)["outputs"][0]

    a = run(ServingConfig(page_size=4, num_pages=16, max_slots=2,
                          pages_per_slot=4, token_budget=6))
    b = run(
        ServingConfig(page_size=2, num_pages=20, max_slots=3,
                      pages_per_slot=8, token_budget=4, prefill_chunk=2),
        extra=_ragged_prompts([6, 3], seed0=70),
    )
    assert a == b
    assert all(0 <= t < 64 for t in a)


@pytest.mark.slow
def test_defrag_preserves_decode():
    """Compacting the pool mid-run (tables rewritten + device gather) must
    not change subsequent decode output."""
    params = decoder.init(CFG, jax.random.key(0))
    engine = ServingEngine(own(params), CFG, ServingConfig(
        page_size=2, num_pages=16, max_slots=3, pages_per_slot=8,
        token_budget=6,
    ))
    prompts = _ragged_prompts([4, 5, 3], seed0=80)
    sched = engine.make_scheduler()
    for i, p in enumerate(prompts):
        sched.submit(Request(prompt=list(p), max_new_tokens=6))
    step = 0
    while sched.has_work:
        plan = sched.schedule(step)
        if plan is not None:
            tokens, _ = engine.run_step(plan)
            sched.update(plan, tokens, step)
            if step == 4:
                # finishings have punched holes by now; force compaction
                engine.defrag(sched)
        step += 1
    for p, req in zip(prompts, sorted(sched.finished, key=lambda r: r.rid)):
        ref = generate(
            params, CFG, jnp.asarray([p], jnp.int32), jax.random.key(0),
            GenerateConfig(max_new_tokens=6),
        )
        assert [int(t) for t in np.asarray(ref)[0, len(p):]] == req.generated


_OVERLOAD = dict(
    page_size=2, num_pages=8, max_slots=2, pages_per_slot=8,
    token_budget=8, prefill_chunk=4,
)


def _overload_stream(deadline):
    """A pool-hogging request (grows toward the WHOLE pool) + a smaller
    late joiner: together they oversubscribe the pool, so the stream only
    progresses by preempt-and-requeue churn until one of them leaves."""
    hog_prompt, blocked_prompt = _ragged_prompts([8, 6], seed0=90)
    return (
        Request(prompt=list(hog_prompt), max_new_tokens=8, deadline=deadline),
        Request(prompt=list(blocked_prompt), max_new_tokens=3, arrival=1),
        blocked_prompt,
    )


def test_deadline_evicts_pool_hog_from_stalled_stream():
    """Graceful degradation under overload: the hog's deadline evicts it
    mid-generation (pages freed, reported `timed_out`) instead of occupying
    pool pages for the rest of its decode; the co-resident request then
    runs without further churn and its output keeps exact greedy parity."""
    params = decoder.init(CFG, jax.random.key(0))
    engine = ServingEngine(own(params), CFG, ServingConfig(**_OVERLOAD))
    hog, blocked, blocked_prompt = _overload_stream(deadline=6)
    res = engine.serve_batch([hog, blocked])
    stats = res["stats"]
    assert stats["timed_out"] == 1 and stats["requests"] == 2
    a, b = res["requests"]
    assert a.finish_reason == "timed_out" and a.finished_at == 6
    assert 0 < len(a.generated) < 8  # partial generation survives eviction
    # the surviving request matches the batch-synchronous path exactly
    ref = generate(
        params, CFG, jnp.asarray([blocked_prompt], jnp.int32), jax.random.key(0),
        GenerateConfig(max_new_tokens=3),
    )
    assert b.finish_reason == "length"
    assert [int(t) for t in np.asarray(ref)[0, len(blocked_prompt):]] == b.generated
    assert res["stats"]["compiled_signatures"] == 1
    # eviction relieved the overload: strictly fewer engine steps than the
    # churning no-deadline run of the same stream (see companion test)
    assert b.finished_at <= 11


def test_no_deadline_same_stream_churns_but_completes():
    """The same overload stream WITHOUT a deadline completes only through
    preempt-and-requeue churn (the victim re-prefills from scratch), and
    the smaller request finishes AFTER the hog despite needing 3 tokens —
    the latency cliff the per-request deadline bounds."""
    params = decoder.init(CFG, jax.random.key(0))
    engine = ServingEngine(own(params), CFG, ServingConfig(**_OVERLOAD))
    hog, blocked, _ = _overload_stream(deadline=None)
    res = engine.serve_batch([hog, blocked])
    assert res["stats"]["timed_out"] == 0
    a, b = res["requests"]
    assert a.finish_reason == "length" and len(a.generated) == 8
    assert b.preemptions >= 1            # pool churn, recompute-style
    assert b.finished_at > a.finished_at  # 3-token request served LAST


def test_deadline_fast_forward_never_skips_a_future_arrival():
    """The serve loop's idle fast-forward to the next deadline must not
    jump PAST a future arrival — the request would be expired without ever
    getting its window to run."""
    params = decoder.init(CFG, jax.random.key(0))
    engine = ServingEngine(own(params), CFG, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=8,
    ))
    (prompt,) = _ragged_prompts([5], seed0=95)
    res = engine.serve_batch([
        Request(prompt=list(prompt), max_new_tokens=3, arrival=5, deadline=100),
    ])
    (req,) = res["requests"]
    assert req.finish_reason == "length" and len(req.generated) == 3
    assert res["stats"]["timed_out"] == 0


def test_deadline_expires_waiting_request_without_pages():
    """A request whose deadline passes while it is still QUEUED leaves with
    zero generated tokens and never touches the pool."""
    from automodel_tpu.serving.scheduler import Scheduler

    sched = Scheduler(
        num_pages=8, page_size=2, max_slots=1, pages_per_slot=8,
        token_budget=8,
    )
    sched.submit(Request(prompt=[1, 2, 3, 4, 5, 6], max_new_tokens=6))
    sched.submit(Request(prompt=[7, 8], max_new_tokens=2, deadline=2))
    free0 = sched.alloc.num_free
    plan = sched.schedule(0)  # only the first request admits (max_slots=1)
    assert plan is not None and len(sched.running) == 1
    sched.schedule(3)  # past the waiter's deadline
    timed_out = [r for r in sched.finished if r.finish_reason == "timed_out"]
    assert len(timed_out) == 1 and timed_out[0].generated == []
    assert sched.n_timed_out == 1
    assert sched.alloc.num_free < free0  # only the running request holds pages


# -- a looped decoder: logits against the plain reference --------------------
#: |the step's log-probability of its token - the float32 reference's|. Both
#: are float32 here, in another order of arithmetic (chunked prefill and a
#: paged cache against one full forward): 1.3e-6 at most in these runs, so
#: 1e-4 leaves seventy times of room; the same engine in bfloat16 reads a
#: median of 0.01 (0.04 at most) and is refused (below).
LOGPROB_TOL = 1e-4


def _serve_with_logprobs(engine, requests):
    """`serve_batch`, keeping what every step reported for each request:
    {rid: [(token, log-probability), ...]} in the order it was sampled."""
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
    """Per request, |reported - reference| at every served token, after
    checking that each served token is the reference's best."""
    from tests import ouro_case

    errs = []
    for rid, (prompt, out) in enumerate(zip(prompts, res["outputs"])):
        assert [t for t, _ in seen[rid]] == out
        logits, _ = ouro_case.reference(params, [prompt + out])
        rows = ouro_case.log_softmax(logits[0, len(prompt) - 1:-1])
        assert rows.argmax(-1).tolist() == out, rid
        errs += [abs(lp - rows[i, t]) for i, (t, lp) in enumerate(seen[rid])]
    return np.asarray(errs)


def test_looped_decoder_logits_match_reference_through_preemption():
    """An Ouro-shaped decoder (3 layers walked 4 times, a cache entry per
    pass and layer under one page table): chunked prefill, then decode
    through the paged cache, in a pool so small that requests are preempted
    and requeued, against the plain reference's full forward; `generate`'s
    dense cache of passes x layers entries against the same."""
    from tests import ouro_case

    cfg = ouro_case.config()
    params = ouro_case.init_params(cfg)
    geo = dict(page_size=4, num_pages=9, max_slots=3, pages_per_slot=6,
               token_budget=8, prefill_chunk=5)
    engine = ServingEngine(own(params), cfg, ServingConfig(**geo))
    assert len(engine.pool[0]) == cfg.num_passes * cfg.num_layers == 12
    prompts = _ragged_prompts([9, 14, 6, 11], vocab=ouro_case.VOCAB, seed0=40)
    reqs = lambda: [Request(prompt=list(p), max_new_tokens=6) for p in prompts]  # noqa: E731
    res, seen = _serve_with_logprobs(engine, reqs())
    assert res["stats"]["preemptions"] >= 1
    assert res["stats"]["compiled_signatures"] == 1
    errs = _logprob_errors(params, prompts, res, seen)
    assert len(errs) == 4 * 6 and errs.max() < LOGPROB_TOL, errs.max()

    for p, out in zip(prompts, res["outputs"]):
        dense = generate(params, cfg, jnp.asarray([p], jnp.int32),
                         jax.random.key(0), GenerateConfig(max_new_tokens=6))
        assert np.asarray(dense)[0, len(p):].tolist() == out

    # the same engine in bfloat16 is outside the tolerance
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    res_low, seen_low = _serve_with_logprobs(
        ServingEngine(own(params), low, ServingConfig(**geo)), reqs())
    low_errs = []
    for rid, (p, out) in enumerate(zip(prompts, res_low["outputs"])):
        logits, _ = ouro_case.reference(params, [p + out])
        rows = ouro_case.log_softmax(logits[0, len(p) - 1:-1])
        low_errs += [abs(lp - rows[i, t]) for i, (t, lp) in enumerate(seen_low[rid])]
    assert np.median(low_errs) > 10 * LOGPROB_TOL


TRASH = 16  # the toy looped engine's trash page: num_pages


@pytest.mark.parametrize("src,dst", [
    ([TRASH] * 4, [TRASH] * 4),                     # no split
    ([3, TRASH, TRASH, TRASH], [9, TRASH, TRASH, TRASH]),  # one split
    ([1, 1, 2, 3], [8, 9, 10, 11]),                 # a split in every slot
    ([3, 1, TRASH, TRASH], [7, 3, TRASH, TRASH]),   # a page copied, then
], ids=["none", "one", "every_slot", "copied_then_split_into"])  # split into
def test_step_copies_the_split_pages_as_one_scatter_would(src, dst):
    """The looped engine's step over a plan of pad rows and the given
    copy-on-write entries: every pool array equals the scatter
    `a.at[dst].set(a[src])` of the pool it was given, page for page (the
    trash page aside, which the pad rows write). A plan without a split
    leaves the pool as it was; the last case's second split overwrites the
    page its first copies, as a page freed and handed out again within the
    step is."""
    from tests import ouro_case

    cfg = ouro_case.config()
    eng = ServingEngine(own(ouro_case.init_params(cfg)), cfg, ServingConfig(
        page_size=4, num_pages=TRASH, max_slots=4, pages_per_slot=4,
        token_budget=8))
    leaves, tree = jax.tree.flatten(eng.pool)
    rng = np.random.default_rng(0)
    before = [rng.standard_normal(a.shape).astype(a.dtype) for a in leaves]
    eng.pool = jax.tree.unflatten(tree, [jnp.asarray(a) for a in before])
    plan = eng.empty_plan()
    plan.page[:] = TRASH
    plan.cow_src[:], plan.cow_dst[:] = src, dst
    pool, _ = eng._step(*eng._step_args(eng._plan_batch(plan)))
    after = jax.tree.leaves(pool)
    assert len(after) == len(before) == 2 * cfg.num_passes * cfg.num_layers
    for a, got in zip(before, after):
        want = a.copy()
        want[dst] = a[src]
        np.testing.assert_array_equal(np.asarray(got)[:TRASH], want[:TRASH])


def test_request_larger_than_the_whole_pool_is_refused_by_name():
    """The looped cell's geometry: 10 pages of 64 tokens a slot. In a pool of
    84 pages a 640-token request is admissible; a pool of 8 could never hold
    it, and `submit` says so instead of stalling the loop."""
    from automodel_tpu.serving.scheduler import Scheduler

    geo = dict(page_size=64, max_slots=24, pages_per_slot=10, token_budget=48,
               prefill_chunk=32)
    long = lambda: Request(prompt=[1] * 384, max_new_tokens=256)  # noqa: E731
    sched = Scheduler(num_pages=84, **geo)
    sched.submit(long())
    assert len(sched.waiting) == 1
    with pytest.raises(ValueError, match="needs 10 pages but the whole pool holds 8"):
        Scheduler(num_pages=8, **geo).submit(long())
    with pytest.raises(ValueError, match="641 positions"):
        sched.submit(Request(prompt=[1] * 385, max_new_tokens=256))


def test_het_engine_rejected():
    from automodel_tpu.serving.engine import ServingEngine as SE

    class FakeHet:  # avoid building real het params just for the raise
        pass

    from automodel_tpu.models.moe_lm.het_moe import HetMoEConfig

    cfg = HetMoEConfig(
        num_layers=1, layer_types=("global",), mlp_kinds=("dense",),
    )
    with pytest.raises(NotImplementedError):
        SE({}, cfg, ServingConfig())
