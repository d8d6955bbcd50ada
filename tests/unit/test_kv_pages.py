"""Paged KV pool: allocator accounting, page tables, defrag compaction."""

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.serving.kv_pages import (
    PageAllocator,
    apply_defrag,
    init_pool,
    pages_for,
    pool_trash_index,
)


def test_pages_for():
    assert pages_for(1, 4) == 1
    assert pages_for(4, 4) == 1
    assert pages_for(5, 4) == 2
    assert pages_for(0, 4) == 0


def test_alloc_grow_free_accounting():
    a = PageAllocator(num_pages=6, page_size=4)
    assert a.num_free == 6
    assert a.ensure(0, 5)            # 2 pages
    assert a.ensure(1, 9)            # 3 pages
    assert a.num_free == 1
    assert len(a.table(0)) == 2 and len(a.table(1)) == 3
    # growth within the covered range allocates nothing
    assert a.ensure(0, 8) and len(a.table(0)) == 2
    # dense-prefix tables: pages are appended, never reordered
    t0 = list(a.table(0))
    assert a.ensure(0, 12) and a.table(0)[:2] == t0
    assert a.num_free == 0
    # exhausted: refuse WITHOUT partial allocation
    assert not a.ensure(1, 16)
    assert len(a.table(1)) == 3 and a.num_free == 0
    a.free_slot(0)
    assert a.num_free == 3 and a.table(0) == []
    # no double-free surprises: every page accounted exactly once
    a.free_slot(1)
    assert sorted(a._free) == list(range(6))


def test_defrag_compacts_live_pages():
    a = PageAllocator(num_pages=8, page_size=2)
    a.ensure(0, 4)   # 2 pages
    a.ensure(1, 4)   # 2 pages
    a.ensure(2, 2)   # 1 page
    a.free_slot(1)   # holes in the middle
    live_before = {s: list(a.table(s)) for s in (0, 2)}
    plan = a.defrag_plan()
    assert plan is not None
    src, n_live = plan
    assert n_live == 3
    # tables now a dense prefix, contents preserved through the mapping
    used = sorted(p for s in (0, 2) for p in a.table(s))
    assert used == [0, 1, 2]
    assert a.num_free == 5
    # device-side: new page i holds old page src[i] (apply_defrag donates
    # the pool, so compare against a host snapshot taken before the call)
    # two layers of one page array each, the page axis first
    before = np.arange(2 * 9 * 2 * 1 * 1, dtype=np.float32).reshape(2, 9, 2, 1, 1)
    pool = [tuple((jnp.asarray(layer),) for layer in before)]
    for (moved,), was in zip(apply_defrag(pool, src)[0], before):
        for slot in (0, 2):
            for old, new in zip(live_before[slot], a.table(slot)):
                np.testing.assert_array_equal(np.asarray(moved[new]), was[old])
        # trash page (index num_pages) stays put
        np.testing.assert_array_equal(np.asarray(moved[8]), was[8])


def test_refcount_share_and_free():
    """A page adopted into a second table frees only when the LAST
    reference drops; incref/decref pin pages without any table."""
    a = PageAllocator(num_pages=4, page_size=2)
    a.ensure(0, 4)                      # slot 0: 2 pages
    shared = list(a.table(0))
    a.adopt(1, shared)                  # slot 1 maps the same pages
    assert a.table(1) == shared
    assert all(a.refcount(p) == 2 for p in shared)
    a.free_slot(0)
    assert a.num_free == 2              # nothing freed: slot 1 still reads
    assert all(a.refcount(p) == 1 for p in shared)
    a.incref(shared[0])                 # radix-tree style pin
    a.free_slot(1)
    assert a.num_free == 3 and a.refcount(shared[0]) == 1
    a.decref(shared[0])
    assert a.num_free == 4


def test_cow_splits_shared_page():
    a = PageAllocator(num_pages=4, page_size=2)
    a.ensure(0, 4)
    a.adopt(1, list(a.table(0)))
    old = a.table(1)[1]
    pair = a.cow(1, 1)
    assert pair is not None and pair[0] == old
    src, dst = pair
    assert a.table(1)[1] == dst and a.table(0)[1] == old
    assert a.refcount(old) == 1 and a.refcount(dst) == 1
    # exclusive page → write in place, no copy
    assert a.cow(1, 1) is None


def test_ensure_reclaims_behind_free_list():
    """The reclaim hook is consulted only once the free list is short."""
    calls = []
    a = PageAllocator(num_pages=3, page_size=2)

    def reclaim(n):
        calls.append(n)
        return 0

    assert a.ensure(0, 4, reclaim=reclaim)   # 2 pages, free list suffices
    assert calls == []
    assert not a.ensure(0, 8, reclaim=reclaim)  # needs 2 more, 1 free
    assert calls == [1]


def test_defrag_moves_shared_page_once_and_patches_every_table():
    """A multiply-referenced page gets ONE mapping entry (one device copy)
    while every referencing table — and any remap listener, i.e. the radix
    tree — sees the new index."""
    a = PageAllocator(num_pages=8, page_size=2)
    a.ensure(0, 4)                      # slot 0: pages 0, 1
    a.ensure(2, 4)                      # slot 2: pages 2, 3
    a.adopt(1, list(a.table(0)))        # slot 1 shares 0, 1
    a.ensure(1, 6)                      # + one private page (4)
    a.free_slot(2)                      # holes at 2, 3
    seen = []
    a.register_remap_listener(seen.append)
    plan = a.defrag_plan()
    assert plan is not None
    src, n_live = plan
    assert n_live == 3                  # 2 shared (once each) + 1 private
    assert a.table(0) == a.table(1)[:2]  # sharing survives the move
    assert sorted({p for t in (a.table(0), a.table(1)) for p in t}) == [0, 1, 2]
    (mapping,) = seen
    assert sorted(mapping.values()) == [0, 1, 2]
    # shared pages keep their refcounts under the new numbering
    assert all(a.refcount(p) == 2 for p in a.table(0))
    assert a.num_free == 5


def test_truncate_drops_provisional_tail():
    """Speculative rollback: truncate() releases exclusively-held tail
    pages to the free list, but a SHARED tail page survives for its other
    holder (only the truncating slot's reference drops)."""
    a = PageAllocator(num_pages=8, page_size=2)
    a.ensure(0, 8)                 # 4 pages
    assert a.truncate(0, 2) == 2   # drop 2 exclusive provisional pages
    assert len(a.table(0)) == 2 and a.num_free == 6
    # shared tail: slot 1 adopts slot 0's pages, then truncates them away
    a.adopt(1, list(a.table(0)))
    assert a.truncate(1, 0) == 2
    assert a.num_free == 6         # slot 0 still references both pages
    assert all(a.refcount(p) == 1 for p in a.table(0))
    assert a.truncate(0, 2) == 0   # no-op at or below the target length
    a.free_slot(0)
    assert a.num_free == 8


def test_defrag_noop_when_compact():
    a = PageAllocator(num_pages=4, page_size=2)
    a.ensure(0, 4)
    assert a.defrag_plan() is None


def test_init_pool_shapes():
    from automodel_tpu.models.llm.decoder import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=8, hidden_size=16, intermediate_size=16, num_layers=2,
        num_heads=4, num_kv_heads=2, dtype=jnp.float32, remat_policy="none",
    )
    pool = init_pool(cfg, [2], num_pages=6, page_size=4)
    (stack,) = pool
    assert len(stack) == 2  # one (k, v) per layer, each a buffer of its own
    D = cfg.resolved_head_dim
    for k, v in stack:
        assert k.shape == (7, 4, 2, D) and v.shape == k.shape  # N+1 pages
    assert pool_trash_index(pool) == 6

    import dataclasses

    mla = dataclasses.replace(
        cfg, attention_type="mla", mla_kv_lora_rank=8, mla_q_lora_rank=0,
        mla_qk_nope_head_dim=4, mla_qk_rope_head_dim=4, mla_v_head_dim=4,
    )
    (stack,) = init_pool(mla, [2], num_pages=6, page_size=4)
    assert len(stack) == 2
    for c, kr in stack:
        assert c.shape == (7, 4, 8) and kr.shape == (7, 4, 4)
