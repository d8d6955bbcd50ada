"""Speculative decoding in the continuous batcher: parity + lifetimes.

The acceptance contract of per-slot draft-then-verify:

- the shared acceptance rule (speculative/acceptance.py) is property-
  tested: greedy acceptance IS the longest matching prefix, and sampled
  (one-hot) acceptance preserves the target distribution on a toy vocab;
- committed tokens on ragged greedy streams (staggered arrivals, forced
  preemption, prefix-cache hits enabled) are token-for-token identical to
  the speculation-DISABLED engine, with the step compiling ONCE — for the
  ngram source and for EAGLE/DFlash drafter adapters (whose random-weight
  drafts are mostly rejected: verification makes quality a throughput
  knob, never a correctness one);
- provisional draft pages never leak: deadline eviction, preempt-and-
  requeue, and prefix-cache donation all free/skip in-flight draft pages.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig, head_kernel
from automodel_tpu.serving import (
    PrefixCacheConfig,
    Request,
    ServingConfig,
    ServingEngine,
    SpeculativeConfig,
)
from automodel_tpu.speculative.acceptance import (
    greedy_accept_length,
    onehot_speculative_verify,
)
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)


def _params():
    return decoder.init(CFG, jax.random.key(0))


def _ragged(seed0, lens, vocab=64):
    return [
        [int(t) for t in np.random.default_rng(seed0 + i).integers(1, vocab, (l,))]
        for i, l in enumerate(lens)
    ]


def _serve(params, geo, reqs, spec=None, prefix=None, draft_source=None):
    engine = ServingEngine(
        own(params), CFG,
        ServingConfig(**geo, speculative=spec, prefix_cache=prefix),
        draft_source=draft_source,
    )
    res = engine.serve_batch([
        Request(
            prompt=list(r.prompt), max_new_tokens=r.max_new_tokens,
            arrival=r.arrival, temperature=r.temperature, seed=r.seed,
            eos_token_id=r.eos_token_id, deadline=r.deadline,
        )
        for r in reqs
    ])
    return res, engine


SPEC = SpeculativeConfig(enabled=True, draft_len=4)


# -- acceptance rule properties (satellite: one shared implementation) ------
def test_greedy_acceptance_is_longest_matching_prefix():
    """Fuzz vs the obvious python loop, incl. validity masking."""
    rng = np.random.default_rng(0)
    for _ in range(50):
        K = int(rng.integers(1, 7))
        draft = rng.integers(0, 4, K)
        target = rng.integers(0, 4, K)
        k_valid = int(rng.integers(0, K + 1))
        valid = np.arange(K) < k_valid
        expect = 0
        for j in range(k_valid):
            if draft[j] != target[j]:
                break
            expect += 1
        got = int(greedy_accept_length(
            jnp.asarray(draft), jnp.asarray(target), jnp.asarray(valid)
        ))
        assert got == expect, (draft, target, k_valid, got, expect)


def test_greedy_acceptance_batched_axis():
    d = jnp.asarray([[1, 2, 3], [1, 9, 3]])
    t = jnp.asarray([[1, 2, 9], [1, 2, 3]])
    assert list(greedy_accept_length(d, t)) == [2, 1]


def test_sampled_acceptance_preserves_target_distribution():
    """One-hot speculative verification on a toy vocab: over many keys the
    FIRST committed token's empirical law must equal softmax(logits row 0)
    regardless of what the (deterministic) draft proposed — the Leviathan
    guarantee that speculation never changes the distribution."""
    V, K = 5, 3
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(K + 1, V)), jnp.float32)
    target = np.asarray(jax.nn.softmax(logits[0]))
    draft = jnp.asarray([2, 0, 4])
    valid = jnp.ones(K, bool)

    def one(seed):
        keys = jax.vmap(
            lambda j: jax.random.fold_in(jax.random.key(seed), j)
        )(jnp.arange(K + 1))
        a, toks = onehot_speculative_verify(draft, logits, keys, valid)
        # first committed token: the draft if accepted, else the resample
        return jnp.where(a >= 1, draft[0], toks[jnp.clip(a, 0, K)])

    n = 6000
    first = np.asarray(jax.vmap(one)(jnp.arange(n)))
    emp = np.bincount(first, minlength=V) / n
    assert np.abs(emp - target).max() < 0.03, (emp, target)


def test_sampled_acceptance_full_accept_bonus_is_plain_sample():
    """With every draft accepted, the bonus token must be the PLAIN
    categorical sample of the bonus row under its own key — so an empty
    block (valid all-False) degenerates to ordinary sampling exactly."""
    V, K = 7, 2
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(K + 1, V)), jnp.float32)
    keys = jax.vmap(
        lambda j: jax.random.fold_in(jax.random.key(123), j)
    )(jnp.arange(K + 1))
    a, toks = onehot_speculative_verify(
        jnp.zeros(K, jnp.int32), logits, keys, jnp.zeros(K, bool)
    )
    assert int(a) == 0
    assert int(toks[0]) == int(jax.random.categorical(keys[0], logits[0]))


# -- greedy parity on ragged streams ---------------------------------------
def test_spec_parity_ragged_stream_compiles_once():
    """Staggered arrivals + chunked prefill interleaved with drafted decode
    blocks: committed tokens equal the speculation-disabled engine exactly,
    ONE compiled signature, and the counters add up."""
    params = _params()
    geo = dict(page_size=4, num_pages=24, max_slots=3, pages_per_slot=6,
               token_budget=16, prefill_chunk=4)
    prompts = _ragged(0, [5, 9, 3, 7, 11])
    reqs = [Request(prompt=p, max_new_tokens=8, arrival=a)
            for p, a in zip(prompts, [0, 0, 2, 3, 5])]
    plain, _ = _serve(params, geo, reqs)
    spec, eng = _serve(params, geo, reqs, spec=SPEC)
    assert spec["outputs"] == plain["outputs"]
    assert spec["stats"]["compiled_signatures"] == 1
    assert eng.step_cache_size() == 1
    s = spec["stats"]
    assert s["drafted_tokens"] >= 1 and s["spec_steps"] >= 1
    assert s["drafted_tokens"] == s["accepted_tokens"] + s["rolled_back_tokens"]
    assert s["mean_accepted_len"] >= 1.0


def test_spec_parity_under_forced_preemption():
    """A pool too small for the admitted set forces recompute-style
    preemption while slots are mid-speculation; greedy outputs stay exact
    and a preempted request re-admits cleanly (the provisional pages were
    rolled back before its pages were freed)."""
    params = _params()
    geo = dict(page_size=2, num_pages=8, max_slots=3, pages_per_slot=6,
               token_budget=8, prefill_chunk=3)
    reqs = [Request(prompt=p, max_new_tokens=5)
            for p in _ragged(20, [4, 4, 4])]
    plain, _ = _serve(params, geo, reqs)
    spec, _ = _serve(params, geo, reqs, spec=SpeculativeConfig(
        enabled=True, draft_len=3,
    ))
    assert spec["outputs"] == plain["outputs"]
    assert spec["stats"]["preemptions"] >= 1
    assert spec["stats"]["compiled_signatures"] == 1


def test_spec_parity_with_prefix_cache_hits():
    """Agent-loop stream with the radix cache on: prefix hits, COW, draft
    blocks, and donation compose — token-exact vs the plain cold engine,
    and every donated page holds committed (never provisional) content or
    the hits themselves would corrupt later requests."""
    params = _params()
    rng = np.random.default_rng(7)
    system = [int(t) for t in rng.integers(1, 64, (10,))]
    reqs = []
    for a in range(2):
        hist = list(system)
        for r in range(2):
            hist = hist + [int(t) for t in rng.integers(1, 64, (3,))]
            reqs.append(Request(
                prompt=list(hist), max_new_tokens=6, arrival=r * 8 + a,
            ))
    geo = dict(page_size=4, num_pages=48, max_slots=3, pages_per_slot=12,
               token_budget=12, prefill_chunk=4)
    plain, _ = _serve(params, geo, reqs)
    both, _ = _serve(params, geo, reqs, spec=SPEC,
                     prefix=PrefixCacheConfig(enabled=True))
    assert both["outputs"] == plain["outputs"]
    assert both["stats"]["prefix_hits"] >= 1
    assert both["stats"]["drafted_tokens"] >= 1
    assert both["stats"]["compiled_signatures"] == 1


def test_spec_eos_stops_mid_block():
    """An EOS committed from inside an accepted draft block (or its bonus)
    must stop the request exactly where the plain engine stops it."""
    params = _params()
    (prompt,) = _ragged(30, [5])
    geo = dict(page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
               token_budget=8)
    ref, _ = _serve(params, geo, [Request(prompt=prompt, max_new_tokens=8)])
    eos = ref["outputs"][0][2]  # third greedy token becomes EOS
    plain, _ = _serve(params, geo, [
        Request(prompt=prompt, max_new_tokens=8, eos_token_id=eos)
    ])
    spec, _ = _serve(params, geo, [
        Request(prompt=prompt, max_new_tokens=8, eos_token_id=eos)
    ], spec=SPEC)
    assert spec["outputs"] == plain["outputs"]
    assert spec["requests"][0].finish_reason == "eos"
    assert spec["requests"][0].generated[-1] == eos


def test_eos_inside_accepted_block_keeps_fed_invariant():
    """An EOS cut INSIDE the accepted prefix discards the block's tail:
    `fed` must never exceed len(known) and the acceptance counters must
    count only committed drafts (scheduler-level, engine-free)."""
    from automodel_tpu.speculative.serve_draft import NgramDraftSource

    from automodel_tpu.serving import Scheduler

    spec = SpeculativeConfig(enabled=True, draft_len=4)
    sched = Scheduler(
        num_pages=16, page_size=2, max_slots=1, pages_per_slot=8,
        token_budget=12, spec=spec, draft_source=NgramDraftSource(spec),
    )
    req = Request(prompt=[3, 4, 3, 4, 3, 4, 3], max_new_tokens=8,
                  eos_token_id=9)
    sched.submit(req)
    plan = sched.schedule(0)
    sched.update(plan, np.full((1, 5), 4, np.int32), 0,
                 accept=np.zeros(1, np.int32))
    plan = sched.schedule(1)
    k = int(plan.spec_len[0])
    assert k >= 2
    drafted0, accepted0 = sched.n_drafted, sched.n_accepted
    # verifier "accepts everything" but the FIRST committed token is EOS
    block = np.full((1, 5), 9, np.int32)
    sched.update(plan, block, 1, accept=np.full(1, k, np.int32))
    assert req.done and req.finish_reason == "eos"
    assert req.fed <= len(req.known)
    assert sched.n_drafted - drafted0 == k
    assert sched.n_accepted - accepted0 <= 1  # only the COMMITTED draft
    assert sched.alloc.num_free == 16  # released: nothing leaks


# -- provisional-page lifetimes (satellite: eviction/preempt/donation) ------
def test_deadline_eviction_frees_in_flight_draft_pages():
    """A request evicted by its deadline while actively speculating must
    return EVERY page to the pool — provisional tails included."""
    params = _params()
    geo = dict(page_size=2, num_pages=8, max_slots=2, pages_per_slot=8,
               token_budget=8, prefill_chunk=4)
    hog, blocked = _ragged(90, [8, 6])
    res, eng = _serve(params, geo, [
        Request(prompt=hog, max_new_tokens=8, deadline=6),
        Request(prompt=blocked, max_new_tokens=3, arrival=1),
    ], spec=SpeculativeConfig(enabled=True, draft_len=3))
    assert res["stats"]["timed_out"] == 1
    plain, _ = _serve(params, geo, [
        Request(prompt=hog, max_new_tokens=8, deadline=6),
        Request(prompt=blocked, max_new_tokens=3, arrival=1),
    ])
    # the survivor keeps exact parity and the pool drains completely
    assert res["outputs"][1] == plain["outputs"][1]


def test_preempt_mid_speculation_rolls_back_then_requeues():
    """Scheduler-level: after a drafted verify step, the slot's table has
    NO provisional tail (update truncated it), so preempting the request
    frees exactly its committed pages and it re-admits cleanly."""
    from automodel_tpu.speculative.serve_draft import NgramDraftSource

    from automodel_tpu.serving import Scheduler, pages_for

    spec = SpeculativeConfig(enabled=True, draft_len=4)
    sched = Scheduler(
        num_pages=16, page_size=2, max_slots=2, pages_per_slot=8,
        token_budget=12, spec=spec, draft_source=NgramDraftSource(spec),
    )
    # repetitive prompt → the ngram source always has a proposal
    req = Request(prompt=[3, 4, 3, 4, 3, 4, 3], max_new_tokens=8)
    sched.submit(req)
    plan = sched.schedule(0)          # prefill (commits "4": pattern holds)
    sched.update(plan, np.full((2, 5), 4, np.int32), 0,
                 accept=np.zeros(2, np.int32))
    plan = sched.schedule(1)          # decode + drafts
    (slot, c, samples) = plan.scheduled[0]
    k = int(plan.spec_len[slot])
    assert samples and c == 1 and k >= 1
    held_before = len(sched.alloc.table(slot))
    # model "rejected everything": accept 0 of k drafts
    block = np.tile(np.arange(5, dtype=np.int32), (2, 1))
    sched.update(plan, block, 1, accept=np.zeros(2, np.int32))
    # rollback truncated the provisional tail to exactly the committed KV
    assert len(sched.alloc.table(slot)) == pages_for(req.fed, 2)
    assert len(sched.alloc.table(slot)) <= held_before
    # preempt-and-requeue sees only committed pages; everything frees
    assert sched._preempt_youngest(set())
    assert sched.alloc.num_free == 16
    assert req.fed == 0 and req in sched.waiting


def test_donation_never_covers_provisional_pages():
    """Prefix-cache donation is driven by the rolled-back `fed`, so a page
    the tree serves to a later request can only hold committed KV: a
    full-page-aligned request that speculated heavily donates pages whose
    token keys are exactly its committed stream."""
    params = _params()
    geo = dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
               token_budget=12, prefill_chunk=4)
    (p,) = _ragged(40, [8])
    spec_cfg = SpeculativeConfig(enabled=True, draft_len=4)
    # same prompt twice: the second admits over donated pages
    reqs = [
        Request(prompt=p, max_new_tokens=6),
        Request(prompt=p, max_new_tokens=6, arrival=6),
    ]
    plain, _ = _serve(params, geo, reqs)
    both, _ = _serve(params, geo, reqs, spec=spec_cfg,
                     prefix=PrefixCacheConfig(enabled=True))
    assert both["outputs"] == plain["outputs"]
    assert both["outputs"][0] == both["outputs"][1]
    assert both["stats"]["prefix_hits"] >= 1


def test_draft_blocks_never_starve_later_decode_slots():
    """A tight token budget with long draft blocks: every decode-class
    slot must still get its one guaranteed row per step — an earlier
    slot's speculation shrinks instead (stable decode order would starve
    the same slot every step otherwise)."""
    from automodel_tpu.speculative.serve_draft import NgramDraftSource

    from automodel_tpu.serving import Scheduler

    spec = SpeculativeConfig(enabled=True, draft_len=6)
    sched = Scheduler(
        num_pages=48, page_size=2, max_slots=3, pages_per_slot=16,
        token_budget=8, spec=spec, draft_source=NgramDraftSource(spec),
    )
    for _ in range(3):
        # repetitive prompts → the ngram source always proposes a long block
        sched.submit(Request(prompt=[3, 4, 3, 4, 3, 4, 3], max_new_tokens=16))
    step = 0
    while any(
        len(r.known) - r.fed > 1 for r in sched.running.values()
    ) or not sched.running:
        plan = sched.schedule(step)
        assert plan is not None
        sched.update(plan, np.full((3, 7), 4, np.int32), step,
                     accept=np.zeros(3, np.int32))
        step += 1
        assert step < 20
    # all three are decode-class now: every one gets a row this step
    plan = sched.schedule(step)
    slots = [s for s, _, _ in plan.scheduled]
    assert sorted(slots) == sorted(sched.running.keys())
    assert all(c == 1 for _, c, _ in plan.scheduled)
    # and the early slots actually drafted into the leftover budget
    assert int(plan.spec_len.sum()) >= 1
    assert sum(c for _, c, _ in plan.scheduled) + int(plan.spec_len.sum()) <= 8


# -- sampled mode -----------------------------------------------------------
def test_sampled_spec_batching_invariant_and_deterministic():
    """Sampled acceptance derives every accept/resample decision from
    (request seed, absolute position) and draft sources are deterministic
    functions of the known tokens — so a sampled request commits the SAME
    tokens regardless of engine geometry or co-resident traffic."""
    params = _params()
    spec = SpeculativeConfig(enabled=True, draft_len=3, acceptance="sampled")

    def run(geo, extra):
        reqs = [Request(prompt=[5, 9, 2, 7, 1], max_new_tokens=6,
                        temperature=0.8, seed=7)]
        reqs += [Request(prompt=p, max_new_tokens=4, temperature=0.5,
                         seed=1 + i) for i, p in enumerate(extra)]
        res, _ = _serve(params, geo, reqs, spec=spec)
        return res["outputs"][0]

    a = run(dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
                 token_budget=8), [])
    b = run(dict(page_size=2, num_pages=40, max_slots=3, pages_per_slot=16,
                 token_budget=12, prefill_chunk=2), _ragged(70, [6, 3]))
    assert a == b
    assert all(0 <= t < 64 for t in a)


def test_greedy_acceptance_mode_never_drafts_sampled_slots():
    """acceptance='greedy' (default) must not speculate on temperature>0
    requests — greedy acceptance would skew their distribution — while
    still sampling them exactly like the plain engine."""
    params = _params()
    geo = dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
               token_budget=8)
    reqs = [Request(prompt=[5, 9, 2, 7, 1], max_new_tokens=6,
                    temperature=0.8, seed=7)]
    plain, _ = _serve(params, geo, reqs)
    spec, _ = _serve(params, geo, reqs, spec=SPEC)
    assert spec["outputs"] == plain["outputs"]
    assert spec["stats"]["drafted_tokens"] == 0


# -- drafter adapters (EAGLE / DFlash reuse of speculative/) ----------------
@pytest.mark.slow
def test_eagle_adapter_parity_and_feedback():
    """EAGLE chain-draft adapter: the engine feeds frontier hiddens back,
    the drafter chains K argmax steps, and (random weights or not) the
    committed stream equals the plain engine's."""
    from automodel_tpu.serving import EagleDraftSource
    from automodel_tpu.speculative.eagle1 import Eagle1Config, init_drafter

    params = _params()
    ecfg = Eagle1Config(vocab_size=64, hidden_size=32, intermediate_size=48,
                        num_heads=4, num_kv_heads=2, num_layers=1)
    source = EagleDraftSource(
        init_drafter(ecfg, jax.random.key(1)), ecfg,
        head_kernel(params, CFG), draft_len=3, window=8,
    )
    geo = dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
               token_budget=10, prefill_chunk=4)
    reqs = [Request(prompt=p, max_new_tokens=6, arrival=a)
            for p, a in zip(_ragged(50, [5, 8]), (0, 1))]
    plain, _ = _serve(params, geo, reqs)
    spec, _ = _serve(
        params, geo, reqs, draft_source=source,
        spec=SpeculativeConfig(enabled=True, draft_len=3, draft_source="eagle"),
    )
    assert spec["outputs"] == plain["outputs"]
    assert spec["stats"]["drafted_tokens"] >= 1
    assert spec["stats"]["compiled_signatures"] == 1


@pytest.mark.slow
def test_dflash_adapter_parity_and_feedback():
    """DFlash block-draft adapter: per-row hiddens accumulate into the
    drafter's context, one forward drafts the block — parity regardless of
    draft quality, one compiled step."""
    from automodel_tpu.serving import DFlashDraftSource
    from automodel_tpu.speculative.dflash import DFlashConfig, init_drafter

    params = _params()
    dcfg = DFlashConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_heads=4,
        num_kv_heads=2, num_layers=1, block_size=4, target_hidden_size=32,
        num_target_layers_used=1,
    )
    source = DFlashDraftSource(
        init_drafter(dcfg, jax.random.key(2)), dcfg,
        params["embed"]["embedding"], head_kernel(params, CFG),
        max_context=32,
    )
    geo = dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
               token_budget=10, prefill_chunk=4)
    reqs = [Request(prompt=p, max_new_tokens=6, arrival=a)
            for p, a in zip(_ragged(60, [5, 8]), (0, 1))]
    plain, _ = _serve(params, geo, reqs)
    spec, _ = _serve(
        params, geo, reqs, draft_source=source,
        spec=SpeculativeConfig(enabled=True, draft_len=3, draft_source="dflash"),
    )
    assert spec["outputs"] == plain["outputs"]
    assert spec["stats"]["drafted_tokens"] >= 1
    assert spec["stats"]["compiled_signatures"] == 1


@pytest.mark.slow
def test_mla_spec_parity():
    """Absorbed-MLA paged layout under speculation (the verify block rides
    the latent-cache attention path)."""
    mla = dataclasses.replace(
        CFG, attention_type="mla", mla_kv_lora_rank=16, mla_q_lora_rank=12,
        mla_qk_nope_head_dim=8, mla_qk_rope_head_dim=8, mla_v_head_dim=8,
    )
    params = decoder.init(mla, jax.random.key(0))

    def serve(spec):
        engine = ServingEngine(own(params), mla, ServingConfig(
            page_size=4, num_pages=20, max_slots=2, pages_per_slot=5,
            token_budget=10, prefill_chunk=3, speculative=spec,
        ))
        return engine.serve_batch([
            Request(prompt=list(p), max_new_tokens=5, arrival=a)
            for p, a in zip(_ragged(10, [6, 9]), (0, 1))
        ])

    plain = serve(None)
    spec = serve(SpeculativeConfig(enabled=True, draft_len=3))
    assert spec["outputs"] == plain["outputs"]
    assert spec["stats"]["compiled_signatures"] == 1


def test_config_validation():
    with pytest.raises(ValueError):
        SpeculativeConfig(enabled=True, draft_source="nope")
    with pytest.raises(ValueError):
        SpeculativeConfig(enabled=True, acceptance="mode7")
    with pytest.raises(ValueError):
        SpeculativeConfig(enabled=True, draft_len=0)
    with pytest.raises(ValueError):
        SpeculativeConfig(enabled=True, ngram_min=0)
    with pytest.raises(AssertionError):
        ServingConfig(token_budget=4, speculative=SpeculativeConfig(
            enabled=True, draft_len=4,
        ))
    # eagle/dflash need drafter params — config alone must refuse loudly
    from automodel_tpu.speculative.serve_draft import build_draft_source

    with pytest.raises(ValueError):
        build_draft_source(
            SpeculativeConfig(enabled=True, draft_source="eagle"),
            max_context=64,
        )
