"""Prefix cache: radix matching, COW, eviction, and engine-level parity.

The acceptance contract of the prefix-sharing layer:

- greedy outputs with the cache ENABLED are token-for-token identical to a
  cold (cache-disabled) engine on overlapping ragged streams — including
  divergence mid-page (copy-on-write), a preempted-and-requeued request
  whose prefix is shared, and defrag firing while pages are multiply
  referenced;
- the jitted step keeps ONE compiled signature across hit / miss / COW
  steps (the fixed-shape contract survives the new subsystem untouched);
- the radix hit actually skips prefill (> 50% of prompt tokens on a
  shared-system-prompt stream — the bench `prefix` headline's workload).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.serving import (
    PageAllocator,
    PrefixCache,
    PrefixCacheConfig,
    Request,
    Scheduler,
    ServingConfig,
    ServingEngine,
)
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)
ENABLED = PrefixCacheConfig(enabled=True)


# -- radix tree unit tests ----------------------------------------------------
def _tree(num_pages=16, ps=4, **kw):
    alloc = PageAllocator(num_pages=num_pages, page_size=ps)
    return alloc, PrefixCache(alloc, ps, PrefixCacheConfig(enabled=True, **kw))


def _fill(alloc, slot, n_tokens):
    alloc.ensure(slot, n_tokens)
    return list(alloc.table(slot))


def test_radix_match_is_page_granular():
    alloc, tree = _tree()
    toks = list(range(1, 11))            # 10 tokens, ps=4 → 2 full pages
    pages = _fill(alloc, 0, 10)
    assert tree.insert(toks, pages[:2]) == 2
    assert tree.cached_pages == 2

    # exact full-page prefix: both pages, fed to the divergence point
    m = tree.lookup(toks[:8] + [99, 98])
    assert m.pages == pages[:2] and m.fed == 8 and not m.cow_pending

    # divergence INSIDE page 2 → only page 1 matches fully
    m = tree.lookup(toks[:5] + [99, 98, 97])
    assert m.pages[0] == pages[0] and m.fed >= 4

    # full hit on an exact page multiple: capped one token short → COW
    m = tree.lookup(toks[:8])
    assert m.pages == pages[:2] and m.fed == 7 and m.cow_pending

    # no overlap at all
    m = tree.lookup([50, 51, 52, 53, 54])
    assert m.pages == [] and m.fed == 0


def test_radix_partial_page_match_sets_cow():
    """Mid-page divergence with share_partial: the divergent page is
    adopted by longest-common-prefix and flagged for copy-on-write."""
    alloc, tree = _tree()
    toks = list(range(1, 9))
    pages = _fill(alloc, 0, 8)
    tree.insert(toks, pages[:2])
    m = tree.lookup(toks[:6] + [99, 98])  # diverges 2 tokens into page 2
    assert m.pages == pages[:2] and m.fed == 6 and m.cow_pending

    alloc2, tree2 = _tree(share_partial=False)
    pages2 = _fill(alloc2, 0, 8)
    tree2.insert(toks, pages2[:2])
    m2 = tree2.lookup(toks[:6] + [99, 98])
    assert m2.pages == pages2[:1] and m2.fed == 4 and not m2.cow_pending


def test_radix_insert_dedupes_and_caps():
    alloc, tree = _tree(max_pages=2)
    toks = list(range(1, 13))
    pages = _fill(alloc, 0, 12)
    assert tree.insert(toks, pages[:3]) == 2    # capacity stops the third
    assert tree.insert(toks, pages[:3]) == 0    # pure dedupe
    assert tree.cached_pages == 2


def test_lru_reclaim_frees_coldest_unreferenced_first():
    alloc, tree = _tree(num_pages=8)
    a = _fill(alloc, 0, 4)
    b = _fill(alloc, 1, 4)
    tree.insert([1, 2, 3, 4], a)
    tree.insert([9, 8, 7, 6], b)
    alloc.free_slot(0)
    alloc.free_slot(1)                  # both pages now tree-only
    tree.lookup([9, 8, 7, 6, 5])        # touch b → a is the LRU victim
    assert tree.reclaimable() == 2
    assert tree.reclaim(1) == 1
    assert tree.cached_pages == 1
    assert alloc.num_free == 7          # a's page went back to the pool
    m = tree.lookup([9, 8, 7, 6, 5])
    assert m.pages == b                 # survivor is the recently used one


def test_reclaim_skips_pages_pinned_by_slots():
    alloc, tree = _tree(num_pages=8)
    a = _fill(alloc, 0, 4)
    tree.insert([1, 2, 3, 4], a)        # refcount 2: slot 0 + tree
    assert tree.reclaimable() == 0
    assert tree.reclaim(4) == 0         # nothing evictable while pinned
    alloc.free_slot(0)
    assert tree.reclaimable() == 1 and tree.reclaim(4) == 1


def test_tree_follows_defrag_remap():
    alloc, tree = _tree(num_pages=8)
    _fill(alloc, 0, 8)                  # slot 0: pages 0, 1
    b = _fill(alloc, 1, 8)              # slot 1: pages 2, 3
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    tree.insert(toks, b)                # pin pages 2, 3
    alloc.free_slot(0)                  # holes at 0, 1
    alloc.free_slot(1)                  # pages 2, 3 are tree-only now
    plan = alloc.defrag_plan()
    assert plan is not None
    m = tree.lookup(toks + [9])
    assert m.pages == [0, 1] and m.fed == 8  # nodes follow the compaction


# -- engine-level parity (the satellite contract) -----------------------------
def _ragged(seed, lens, vocab=64):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, (l,))] for l in lens]


def _serve(params, serve_cfg, prompts, arrivals, max_new=6):
    engine = ServingEngine(own(params), CFG, serve_cfg)
    reqs = [Request(prompt=list(p), max_new_tokens=max_new, arrival=a)
            for p, a in zip(prompts, arrivals)]
    return engine.serve_batch(reqs)


def test_warm_vs_cold_parity_overlapping_stream():
    """Token-for-token greedy parity vs the cache-disabled engine on a
    stream of overlapping prompts: full hits (page-aligned AND not),
    divergence mid-page (COW), and cold misses, concurrent and staggered."""
    params = decoder.init(CFG, jax.random.key(0))
    (sys_p,) = _ragged(1, [9])
    prompts = [
        sys_p + t for t in _ragged(2, [3, 5, 2])  # shared system prompt
    ] + [
        sys_p[:8],                     # page-aligned full hit → COW
        sys_p + [5],                   # full hit one past the shared prefix
        sys_p[:6] + [61, 62, 63],      # diverges mid-page → partial COW
        _ragged(3, [7])[0],            # cold miss
    ]
    arrivals = [0, 3, 5, 7, 9, 11, 13]
    geo = dict(page_size=4, num_pages=32, max_slots=3, pages_per_slot=8,
               token_budget=8, prefill_chunk=4)
    cold = _serve(params, ServingConfig(**geo), prompts, arrivals)
    warm = _serve(params, ServingConfig(**geo, prefix_cache=ENABLED),
                  prompts, arrivals)
    assert warm["outputs"] == cold["outputs"]
    stats = warm["stats"]
    assert stats["prefix_hits"] >= 4
    assert stats["prefill_skipped_tokens"] >= 20
    assert stats["cow_copies"] >= 2
    assert stats["compiled_signatures"] == 1
    # the cache saved real prefill work: fewer tokens through the device
    assert stats["tokens_fed"] < cold["stats"]["tokens_fed"]


def test_preempted_request_readmits_through_its_own_donation():
    """Preempt-and-requeue with the cache on: the victim's donated pages
    turn its recompute-style re-prefill into a radix hit, and outputs still
    match the cold engine exactly."""
    params = decoder.init(CFG, jax.random.key(0))
    prompts = _ragged(20, [4, 4, 4])
    geo = dict(page_size=2, num_pages=10, max_slots=3, pages_per_slot=6,
               token_budget=6, prefill_chunk=3)
    cold = _serve(params, ServingConfig(**geo), prompts, [0, 0, 0], max_new=5)
    warm = _serve(params, ServingConfig(**geo, prefix_cache=ENABLED),
                  prompts, [0, 0, 0], max_new=5)
    assert warm["outputs"] == cold["outputs"]
    assert warm["stats"]["compiled_signatures"] == 1
    assert cold["stats"]["preemptions"] >= 1
    if warm["stats"]["preemptions"]:   # victim re-admitted via the tree
        assert warm["stats"]["prefix_hits"] >= 1


def test_defrag_with_multiply_referenced_pages_preserves_decode():
    """Force compaction while shared pages are live in several tables AND
    the radix tree: every output still matches the cold engine."""
    params = decoder.init(CFG, jax.random.key(0))
    (sys_p,) = _ragged(30, [8])
    prompts = [sys_p + t for t in _ragged(31, [2, 3, 4])]
    # 8+2+5 = 15 tokens: request 0's last page stays partial, so finishing
    # frees it (donated full pages survive) and punches a mid-pool hole
    # while requests 1/2 still share the system-prompt pages
    geo = dict(page_size=4, num_pages=24, max_slots=3, pages_per_slot=6,
               token_budget=8, prefill_chunk=4)
    cold = _serve(params, ServingConfig(**geo), prompts, [0, 1, 2], max_new=5)

    engine = ServingEngine(own(params), CFG, ServingConfig(
        **geo, prefix_cache=ENABLED,
    ))
    sched = engine.make_scheduler()
    for i, p in enumerate(prompts):
        sched.submit(Request(prompt=list(p), max_new_tokens=5, arrival=i))
    step = 0
    defrags = 0
    while sched.has_work:
        plan = sched.schedule(step)
        if plan is not None:
            tokens, _ = engine.run_step(plan)
            sched.update(plan, tokens, step)
            shared = any(
                sched.alloc.refcount(p) > 1
                for t in sched.alloc._tables.values() for p in t
            )
            if shared and engine.defrag(sched):
                defrags += 1
        step += 1
    assert defrags >= 1, "defrag never fired while pages were shared"
    outs = [r.generated for r in sorted(sched.finished, key=lambda r: r.rid)]
    assert outs == cold["outputs"]
    assert engine.step_cache_size() == 1


def test_full_hit_goes_straight_to_decode():
    """A resubmitted identical prompt skips prefill entirely: its only fed
    rows before sampling are decode-class (one pending token)."""
    params = decoder.init(CFG, jax.random.key(0))
    (p,) = _ragged(40, [8])
    engine = ServingEngine(own(params), CFG, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=8, prefix_cache=ENABLED,
    ))
    sched = engine.make_scheduler()
    sched.submit(Request(prompt=list(p), max_new_tokens=4))
    sched.submit(Request(prompt=list(p), max_new_tokens=4, arrival=4))
    first_feed = {}
    step = 0
    while sched.has_work:
        plan = sched.schedule(step)
        if plan is not None:
            for slot, c, _ in plan.scheduled:
                rid = sched.running[slot].rid
                first_feed.setdefault(rid, c)
            tokens, _ = engine.run_step(plan)
            sched.update(plan, tokens, step)
        step += 1
    a, b = sorted(sched.finished, key=lambda r: r.rid)
    assert b.generated == a.generated
    assert first_feed[0] == 8        # cold prefill of the whole prompt
    assert first_feed[1] == 1        # full hit: first step is the decode row
    assert b.prefix_hit_tokens == 7
    assert sched.n_cow >= 1          # page-aligned hit splits the last page


def test_prefix_hit_admission_policy_prefers_hits_when_tight():
    """Non-FIFO admission: with the pool too tight for the cold queue head,
    the high-hit-ratio waiter behind it is admitted first; FIFO order
    resumes once pages free up, and nothing is lost or reordered wrongly."""
    params = decoder.init(CFG, jax.random.key(0))
    (sys_p,) = _ragged(50, [16])            # 4 full pages of system prompt
    hot = sys_p + _ragged(51, [2])[0]       # needs 1 fresh page after the hit
    cold_long = _ragged(52, [16])[0]        # needs 5 pages, no hit
    engine = ServingEngine(own(params), CFG, ServingConfig(
        page_size=4, num_pages=9, max_slots=2, pages_per_slot=6,
        token_budget=16, prefill_chunk=16,
        prefix_cache=ENABLED, admission_policy="prefix-hit",
    ))
    sched = engine.make_scheduler()
    warmer = Request(prompt=list(sys_p) + [9], max_new_tokens=4)
    sched.submit(warmer)                      # seeds the tree, hogs pages
    sched.submit(Request(prompt=list(cold_long), max_new_tokens=4, arrival=2))
    sched.submit(Request(prompt=list(hot), max_new_tokens=4, arrival=2))
    admit_order = []
    step = 0
    while sched.has_work and step < 200:
        plan = sched.schedule(step)
        if plan is not None:
            for slot, req in sched.running.items():
                if req.rid not in admit_order:
                    admit_order.append(req.rid)
            tokens, _ = engine.run_step(plan)
            sched.update(plan, tokens, step)
        step += 1
    assert not sched.has_work
    assert admit_order.index(2) < admit_order.index(1), (
        f"hit-ratio waiter was not preferred: {admit_order}"
    )
    assert len(sched.finished) == 3


def test_shared_system_prompt_skips_majority_of_prefill():
    """The bench `prefix` headline's workload shape in miniature: an
    agent-loop stream re-sending its whole history must skip > 50% of
    prompt tokens (the acceptance bar for the headline)."""
    params = decoder.init(CFG, jax.random.key(0))
    (sys_p,) = _ragged(60, [12])
    turns = _ragged(61, [4, 4, 4])
    prompts, hist = [], list(sys_p)
    for t in turns:                     # history grows every round
        hist = hist + t
        prompts.append(list(hist))
    arrivals = [6 * i for i in range(len(prompts))]
    res = _serve(
        params,
        ServingConfig(page_size=4, num_pages=48, max_slots=3,
                      pages_per_slot=12, token_budget=8, prefill_chunk=8,
                      prefix_cache=ENABLED),
        prompts, arrivals, max_new=4,
    )
    total_prompt = sum(len(p) for p in prompts)
    skipped = res["stats"]["prefill_skipped_tokens"]
    assert skipped / total_prompt > 0.5, (skipped, total_prompt)
    assert res["stats"]["compiled_signatures"] == 1


def test_eviction_capped_cache_still_parity():
    """A tiny max_pages forces constant LRU eviction; parity must hold."""
    params = decoder.init(CFG, jax.random.key(0))
    (sys_p,) = _ragged(70, [8])
    prompts = [sys_p + t for t in _ragged(71, [3, 4, 5])]
    geo = dict(page_size=4, num_pages=24, max_slots=2, pages_per_slot=6,
               token_budget=8, prefill_chunk=4)
    cold = _serve(params, ServingConfig(**geo), prompts, [0, 2, 4])
    warm = _serve(
        params,
        ServingConfig(**geo, prefix_cache=PrefixCacheConfig(
            enabled=True, max_pages=3,
        )),
        prompts, [0, 2, 4],
    )
    assert warm["outputs"] == cold["outputs"]
    assert warm["stats"]["prefix_cached_pages"] <= 3


def test_admission_accounting_excludes_pages_the_request_would_pin():
    """Regression: admission must not count a candidate's own matched
    tree-only pages as BOTH adopted (subtracted from need) and reclaimable
    (added to avail) — adoption pins them. Pool = 3; the donor leaves 2
    tree-only pages + 1 free. An identical page-aligned prompt needs a COW
    page + a decode-slack page on top of the 2 it would pin: the honest
    ledger says that does not fit (1 free + 0 reclaimable-after-pinning),
    so the admit must fall back to COLD — reclaiming the tree during
    prefill — instead of leaning on preemption/reclaim it already spent.
    A roomier pool takes the warm hit; outputs match either way."""
    params = decoder.init(CFG, jax.random.key(0))
    (donor_prompt,) = _ragged(80, [8])   # exactly 2 pages of known tokens

    def run(num_pages):
        engine = ServingEngine(own(params), CFG, ServingConfig(
            page_size=4, num_pages=num_pages, max_slots=2, pages_per_slot=3,
            token_budget=8, prefix_cache=ENABLED,
        ))
        return engine.serve_batch([
            Request(prompt=list(donor_prompt), max_new_tokens=0),
            Request(prompt=list(donor_prompt), max_new_tokens=1, arrival=4),
        ])

    res = run(num_pages=3)               # tight: warm admit must be refused
    assert [r.finish_reason for r in res["requests"]] == ["length", "length"]
    assert res["stats"]["prefix_hits"] == 0           # cold admission
    assert res["stats"]["prefix_evicted_pages"] >= 1  # tree reclaimed
    res2 = run(num_pages=8)              # roomy: the hit goes through
    assert res2["stats"]["prefix_hits"] == 1
    assert res2["outputs"] == res["outputs"]


def test_config_validation():
    with pytest.raises(ValueError):
        PrefixCacheConfig(enabled=True, eviction="random")
    with pytest.raises(ValueError):
        Scheduler(num_pages=8, page_size=2, max_slots=1, pages_per_slot=4,
                  token_budget=4, admission_policy="prefix-hit")
