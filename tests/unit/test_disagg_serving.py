"""Disaggregated prefill/decode serving + engine-lifetime prefix cache.

The acceptance contract of the disaggregation tier (docs/SERVING.md
§"Disaggregated serving"):

- token-for-token greedy parity DisaggRouter vs the monolithic engine on
  ragged streams — staggered arrivals, prefix-cache hits, decode-side
  speculation, and forced preemption of already-handed-off requests —
  with ONE compiled step signature per replica class (prefill's wider
  token budget compiles its own program; neither class recompiles);
- KV handoff edge cases: a handoff racing its request's deadline expires
  in flight with every prefill-side pin released; a half-transferred
  (admitted-then-preempted) request requeues and still finishes right;
  transferred pages spliced against the decode replica's radix tree keep
  allocator refcounts consistent to the last page;
- the engine-lifetime prefix cache: allocator + radix tree now survive
  across `serve_batch` calls, so a second batch re-serves the first
  call's system prompt with most of its prefill skipped — and
  `reset_prefix_cache()` returns the engine to cold.

The fused transfer program's compiled structure (gather/scatter only,
zero collectives, destination donation) is pinned separately by the
`kv_transfer` analysis baseline (test_hlo_guards).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.serving import (
    DisaggConfig,
    DisaggRouter,
    KVTransfer,
    PrefixCacheConfig,
    Request,
    ServingConfig,
    ServingEngine,
    SpeculativeConfig,
)
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)


@pytest.fixture(scope="module")
def params():
    return decoder.init(CFG, jax.random.key(0))


def _prompts(lens, seed0=0):
    return [
        [int(t) for t in np.random.default_rng(seed0 + i).integers(1, 64, (l,))]
        for i, l in enumerate(lens)
    ]


def _reqs(prompts, arrivals, max_new=6):
    return [
        Request(prompt=list(p), max_new_tokens=max_new, arrival=a)
        for p, a in zip(prompts, arrivals)
    ]


def _mono(params, sc, requests):
    res = ServingEngine(own(params), CFG, sc).serve_batch(requests)
    assert res["stats"]["compiled_signatures"] == 1, res["stats"]
    return res


def _disagg(params, sc, dc, requests, **kw):
    router = DisaggRouter(own(params), CFG, sc, dc)
    res = router.serve_batch(requests, **kw)
    assert res["stats"]["compiled_signatures_prefill"] == 1, res["stats"]
    assert res["stats"]["compiled_signatures_decode"] == 1, res["stats"]
    return router, res


def _pool_consistent(engine):
    """Engine-lifetime allocator identity once no request is resident:
    every page is either on the free list or held by exactly one radix
    node — a leaked handoff pin or a lost splice ref breaks this."""
    return (
        engine.alloc.num_free + engine.prefix.cached_pages
        == engine.serve_cfg.num_pages
    )


# -- parity ------------------------------------------------------------------
def test_disagg_parity_ragged_stream(params):
    """Staggered ragged arrivals through 1 prefill + 1 decode replica:
    greedy tokens equal the monolithic engine's, every request actually
    migrated (its first decode step ran on the decode replica), and the
    wider prefill budget still compiles once per class."""
    sc = ServingConfig(
        page_size=4, num_pages=32, max_slots=3, pages_per_slot=6,
        token_budget=8, prefill_chunk=4,
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    reqs = lambda: _reqs(_prompts([5, 11, 3, 7], 30), [0, 0, 2, 4])  # noqa: E731
    base = _mono(params, sc, reqs())
    dc = DisaggConfig(enabled=True, transfer_pages=4, prefill_token_budget=16)
    _, res = _disagg(params, sc, dc, reqs())
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["handoffs"] == 4
    assert res["stats"]["handoff_pages_moved"] >= 4
    assert res["stats"]["transfer_chunks"] >= 1


def test_disagg_looped_decoder_hands_over_every_pass():
    """A looped decoder through 1 prefill + 1 decode replica: a page's copy
    in every pass and layer crosses in the one transfer (the pool composes
    by structure), so greedy tokens equal the monolithic engine's and the
    wire bytes count the passes."""
    from tests import ouro_case

    cfg = ouro_case.config()
    lp = ouro_case.init_params(cfg)
    sc = ServingConfig(
        page_size=4, num_pages=32, max_slots=3, pages_per_slot=6,
        token_budget=8, prefill_chunk=4,
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    reqs = lambda: _reqs(_prompts([5, 11, 3, 7], 30), [0, 0, 2, 4])  # noqa: E731
    base = ServingEngine(own(lp), cfg, sc).serve_batch(reqs())
    router = DisaggRouter(
        own(lp), cfg, sc,
        DisaggConfig(enabled=True, transfer_pages=4, prefill_token_budget=16))
    res = router.serve_batch(reqs())
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["handoffs"] == 4
    moved = res["stats"]["handoff_pages_moved"]
    assert moved >= 4
    # a page: 4 tokens x (4 passes x 3 layers) x (k and v) x 4 heads x 8 x 4 B
    page_bytes = 4 * 12 * 2 * 4 * 8 * 4
    snap = router.obs.registry.snapshot()
    assert snap["serve_kv_transfer_bytes_total"] >= moved * page_bytes
    assert snap["serve_kv_transfer_bytes_total"] % page_bytes == 0
    assert snap["serve_passes"] == 4
    assert snap["serve_kv_bytes_per_token"] == page_bytes // 4


def test_disagg_parity_decode_side_speculation(params):
    """Decode-class speculation (ngram draft-then-verify) composes with
    the handoff: drafts fire only after migration, acceptance is lossless,
    so tokens still equal the PLAIN monolithic stream's."""
    sc = ServingConfig(
        page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
        token_budget=8, prefill_chunk=4,
    )
    prompts = _prompts([9, 7], 40)
    reqs = lambda: _reqs(prompts, [0, 2], max_new=8)  # noqa: E731
    base = _mono(params, sc, reqs())
    spec_sc = dataclasses.replace(
        sc, speculative=SpeculativeConfig(enabled=True, draft_len=3),
    )
    dc = DisaggConfig(enabled=True, transfer_pages=2)
    _, res = _disagg(params, spec_sc, dc, reqs())
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["drafted_tokens"] > 0
    assert res["stats"]["handoffs"] == 2


def test_disagg_parity_forced_preemption(params):
    """A pool tight enough to preempt ALREADY-MIGRATED requests: the
    victim requeues on the decode replica (fed reset, pages donated),
    recomputes through the radix tree, and the final tokens still equal
    the monolithic engine's — the half-transferred request edge case."""
    sc = ServingConfig(
        page_size=2, num_pages=8, max_slots=3, pages_per_slot=6,
        token_budget=6, prefill_chunk=3,
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    reqs = lambda: _reqs(_prompts([4, 4, 4], 20), [0, 0, 0], 8)  # noqa: E731
    base = _mono(params, sc, reqs())
    dc = DisaggConfig(enabled=True, transfer_pages=2)
    router, res = _disagg(params, sc, dc, reqs())
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["preemptions"] >= 1
    # preempted victims re-prefill ON the decode replica (its scheduler
    # requeued them) — they never migrate twice
    assert res["stats"]["handoffs"] == 3
    assert _pool_consistent(router.prefill[0])
    assert _pool_consistent(router.decode[0])


# -- handoff edge cases ------------------------------------------------------
def test_handoff_expires_in_flight_and_releases_pins(params):
    """A handoff racing its deadline: the decode replica's single slot is
    hogged, the victim's prefill finishes and its pinned pages sit in
    flight until the deadline expires them — finish_reason "timed_out",
    and every prefill-side pin is released (no leaked pages)."""
    sc = ServingConfig(
        page_size=4, num_pages=32, max_slots=1, pages_per_slot=8,
        token_budget=8, prefill_chunk=4,
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    hog = Request(prompt=_prompts([4], 7)[0], max_new_tokens=20, arrival=0)
    victim = Request(
        prompt=_prompts([4], 8)[0], max_new_tokens=4, arrival=1, deadline=8,
    )
    dc = DisaggConfig(enabled=True, transfer_pages=4)
    router, res = _disagg(params, sc, dc, [hog, victim])
    assert victim.finish_reason == "timed_out"
    assert res["stats"]["handoff_expired"] == 1
    assert res["stats"]["timed_out"] == 1
    assert hog.finish_reason == "length"
    assert len(hog.generated) == 20
    assert _pool_consistent(router.prefill[0])
    assert _pool_consistent(router.decode[0])


def test_transferred_pages_splice_against_decode_radix(params):
    """Two requests sharing a long system prompt, far enough apart that
    the first has finished (and donated) on the decode replica before the
    second's handoff arrives: the shared pages SPLICE out of the decode
    tree instead of moving again, refcounts stay consistent, and tokens
    match the monolithic run."""
    rng = np.random.default_rng(3)
    system = [int(t) for t in rng.integers(1, 64, (12,))]
    prompts = [
        system + [int(t) for t in rng.integers(1, 64, (3,))],
        system + [int(t) for t in rng.integers(1, 64, (2,))],
    ]
    sc = ServingConfig(
        page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
        token_budget=8, prefill_chunk=4,
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    reqs = lambda: _reqs(prompts, [0, 30], max_new=6)  # noqa: E731
    base = _mono(params, sc, reqs())
    dc = DisaggConfig(enabled=True, transfer_pages=4)
    router, res = _disagg(params, sc, dc, reqs())
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["handoff_pages_spliced"] >= 3  # 12-token system
    assert res["stats"]["sticky_routed"] >= 1
    assert _pool_consistent(router.prefill[0])
    assert _pool_consistent(router.decode[0])


# -- engine-lifetime prefix cache --------------------------------------------
def test_engine_lifetime_cache_across_serve_batch_calls(params):
    """The tentpole's second half: allocator + radix tree survive across
    `serve_batch` calls on one engine. A second batch re-sending the first
    call's system prompt skips >50% of its prefill (zero re-prefill of the
    shared full pages), still matches a cold engine's tokens, and
    `reset_prefix_cache()` restores cold behavior."""
    system = [int(t) for t in np.random.default_rng(5).integers(1, 64, (16,))]

    def mk(seed):
        tail = np.random.default_rng(100 + seed).integers(1, 64, (2,))
        return _reqs([system + [int(t) for t in tail]], [0], max_new=4)
    sc = ServingConfig(
        page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
        token_budget=8, prefill_chunk=4,
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    eng = ServingEngine(own(params), CFG, sc)
    first = eng.serve_batch(mk(0))
    assert first["stats"]["prefill_skipped_tokens"] == 0  # cold tree
    second_reqs = mk(1)
    second = eng.serve_batch(second_reqs)
    skipped = second["stats"]["prefill_skipped_tokens"]
    prompt_len = len(second_reqs[0].prompt)
    assert skipped >= len(system), (skipped, len(system))
    assert skipped / prompt_len > 0.5
    # the shared prefix truly never re-prefilled: only tokens past the
    # cached pages (plus the sampled ones) were ever fed
    assert second["stats"]["tokens_fed"] <= prompt_len - skipped + 1 + 4
    # parity: warm tokens equal a cold engine's on the identical request
    cold = ServingEngine(own(params), CFG, sc).serve_batch(mk(1))
    assert second["outputs"] == cold["outputs"]
    assert eng.step_cache_size() == 1  # both calls, one signature
    # explicit reset returns the engine to cold
    assert eng.reset_prefix_cache() > 0
    assert eng.alloc.num_free == sc.num_pages
    third = eng.serve_batch(mk(1))
    assert third["stats"]["prefill_skipped_tokens"] == 0
    assert third["outputs"] == cold["outputs"]


def test_engine_lifetime_feeds_disagg_peers(params):
    """Across two DisaggRouter.serve_batch calls the prefill replica's
    radix tree is warm too: the second call's prefill skips the system
    prompt entirely — engine-lifetime caching composes with handoff."""
    rng = np.random.default_rng(9)
    system = [int(t) for t in rng.integers(1, 64, (12,))]
    tail = [int(t) for t in rng.integers(1, 64, (3,))]
    mk = lambda: _reqs([system + tail], [0], max_new=4)  # noqa: E731
    sc = ServingConfig(
        page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
        token_budget=8, prefill_chunk=4,
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    router = DisaggRouter(own(params), CFG, sc, DisaggConfig(enabled=True))
    router.serve_batch(mk())
    res = router.serve_batch(mk())
    assert res["stats"]["prefill_skipped_tokens"] >= len(system) - sc.page_size
    assert res["stats"]["handoffs"] == 1


# -- KVTransfer unit behavior ------------------------------------------------
def _tiny_engine(params, **over):
    geo = dict(page_size=4, num_pages=8, max_slots=2, pages_per_slot=4,
               token_budget=8)
    geo.update(over)
    return ServingEngine(own(params), CFG, ServingConfig(**geo))


def test_kv_transfer_moves_pages_and_chunks(params):
    src = _tiny_engine(params)
    dst = _tiny_engine(params, num_pages=16)  # num_pages may differ
    # stamp recognizable values into three source pages
    src.pool = jax.tree.map(
        lambda a: a.at[2].set(1.5).at[3].set(2.5).at[5].set(3.5),
        src.pool,
    )
    xfer = KVTransfer(src, dst, batch_pages=2)
    moved = xfer.move([(2, 7), (3, 9), (5, 1)])
    assert moved == 3
    assert xfer.n_pages == 3 and xfer.n_chunks == 2  # 2+1 under batch=2
    for leaf_dst in jax.tree.leaves(dst.pool):
        np.testing.assert_allclose(np.asarray(leaf_dst[7]), 1.5)
        np.testing.assert_allclose(np.asarray(leaf_dst[9]), 2.5)
        np.testing.assert_allclose(np.asarray(leaf_dst[1]), 3.5)
        np.testing.assert_allclose(np.asarray(leaf_dst[0]), 0.0)
    assert xfer.move([]) == 0
    assert xfer.n_chunks == 2


def test_kv_transfer_rejects_mismatched_geometry(params):
    src = _tiny_engine(params)
    with pytest.raises(ValueError, match="page_size"):
        KVTransfer(src, _tiny_engine(params, page_size=8))
    with pytest.raises(ValueError, match="batch_pages"):
        KVTransfer(src, _tiny_engine(params), batch_pages=0)


def test_disagg_config_validation():
    with pytest.raises(ValueError):
        DisaggConfig(prefill_replicas=0)
    with pytest.raises(ValueError):
        DisaggConfig(transfer_pages=0)
    with pytest.raises(ValueError):
        DisaggConfig(prefill_token_budget=0)
