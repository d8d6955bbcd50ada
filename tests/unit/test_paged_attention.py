"""Ragged paged attention: XLA reference vs dense oracle, Pallas parity.

The XLA gather-based reference is checked against `ops/attention.py`'s
dense einsum attention on a contiguous cache scattered into randomly-
permuted pages; the Pallas kernels (interpret mode on CPU) are then checked
against the reference — the same two-hop oracle chain as flash attention.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops.attention import xla_attention
from automodel_tpu.ops.paged_attention import (
    ragged_paged_attention,
    ragged_paged_attention_xla,
    ragged_paged_mla_attention_xla,
)


def _paged_setup(seed=0, T=6, Hkv=2, G=2, D=16, Dv=16, ps=4, P=5, N=12):
    """Scatter a contiguous (T_ctx, Hkv, D) cache into shuffled pool pages;
    token t sees positions 0..pos[t] of the context."""
    rng = np.random.default_rng(seed)
    Hq = Hkv * G
    ctx = P * ps
    q = jnp.asarray(rng.normal(size=(T, Hq, D)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(ctx, Hkv, D)), jnp.float32)
    values = jnp.asarray(rng.normal(size=(ctx, Hkv, Dv)), jnp.float32)
    pages = rng.permutation(N)[:P]              # the pool pages backing ctx
    k_pages = jnp.asarray(rng.normal(size=(N + 1, ps, Hkv, D)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(N + 1, ps, Hkv, Dv)), jnp.float32)
    k_pages = k_pages.at[pages].set(keys.reshape(P, ps, Hkv, D))
    v_pages = v_pages.at[pages].set(values.reshape(P, ps, Hkv, Dv))
    pt = jnp.broadcast_to(jnp.asarray(pages, jnp.int32), (T, P))
    pos = jnp.asarray(rng.integers(0, ctx, (T,)), jnp.int32)
    return q, keys, values, k_pages, v_pages, pt, pos


def _dense_oracle(q, keys, values, pos, window=None, soft_cap=None, sinks=None):
    """Per-token dense attention over positions <= pos[t]."""
    T = q.shape[0]
    ctx = keys.shape[0]
    kv_idx = jnp.arange(ctx)
    mask = kv_idx[None, :] <= pos[:, None]
    if window is not None:
        dist = pos[:, None] - kv_idx[None, :]
        mask = jnp.logical_and(mask, (window == 0) | (dist < window))
    out = xla_attention(
        q[:, None], jnp.broadcast_to(keys[None], (T, *keys.shape)),
        jnp.broadcast_to(values[None], (T, *values.shape)),
        mask=mask[:, None, :], scale=q.shape[-1] ** -0.5,
        logits_soft_cap=soft_cap, sinks=sinks,
    )
    return out[:, 0]


def test_xla_reference_matches_dense_oracle():
    q, keys, values, kp, vp, pt, pos = _paged_setup()
    got = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=q.shape[-1] ** -0.5)
    want = _dense_oracle(q, keys, values, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_xla_reference_window_softcap_sinks():
    q, keys, values, kp, vp, pt, pos = _paged_setup(seed=1)
    sinks = jnp.asarray([0.3, -0.2, 0.1, 0.5], jnp.float32)
    got = ragged_paged_attention_xla(
        q, kp, vp, pt, pos, scale=q.shape[-1] ** -0.5,
        window=jnp.int32(5), soft_cap=10.0, sinks=sinks,
    )
    want = _dense_oracle(q, keys, values, pos, window=jnp.int32(5),
                         soft_cap=10.0, sinks=sinks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # window == 0 means global (the layer-scan convention)
    got0 = ragged_paged_attention_xla(
        q, kp, vp, pt, pos, scale=q.shape[-1] ** -0.5, window=jnp.int32(0),
    )
    want0 = _dense_oracle(q, keys, values, pos)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(want0), atol=1e-5)


def test_pad_rows_zero():
    q, keys, values, kp, vp, pt, pos = _paged_setup(seed=2)
    pos = pos.at[2].set(-1).at[5].set(-1)
    got = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=0.25)
    assert np.asarray(got)[2].max() == 0.0 and np.asarray(got)[5].max() == 0.0
    # sinks must not leak mass into pad rows either
    got_s = ragged_paged_attention_xla(
        q, kp, vp, pt, pos, scale=0.25,
        sinks=jnp.ones((q.shape[1],), jnp.float32),
    )
    assert np.asarray(got_s)[2].max() == 0.0


def test_pallas_gqa_kernel_matches_reference():
    q, keys, values, kp, vp, pt, pos = _paged_setup(seed=3)
    pos = pos.at[4].set(-1)
    from automodel_tpu.ops.pallas.ragged_paged_attention import (
        paged_attention_kernel,
    )

    want = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=0.25)
    got = paged_attention_kernel(q, kp, vp, pt, pos, scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # soft-cap rides the kernel too (gemma-style decode)
    want_c = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=0.25, soft_cap=8.0)
    got_c = paged_attention_kernel(q, kp, vp, pt, pos, scale=0.25, soft_cap=8.0)
    np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c), atol=1e-5)


def test_pallas_mla_kernel_matches_reference():
    rng = np.random.default_rng(4)
    T, n, r, dr, ps, P, N = 5, 4, 16, 8, 4, 4, 9
    qa = jnp.asarray(rng.normal(size=(T, n, r)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(T, n, dr)), jnp.float32)
    cp = jnp.asarray(rng.normal(size=(N + 1, ps, r)), jnp.float32)
    krp = jnp.asarray(rng.normal(size=(N + 1, ps, dr)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, N, (T, P)), jnp.int32)
    pos = jnp.asarray([0, 3, -1, 11, 15], jnp.int32)
    from automodel_tpu.ops.pallas.ragged_paged_attention import (
        paged_mla_attention_kernel,
    )

    want = ragged_paged_mla_attention_xla(qa, qr, cp, krp, pt, pos, scale=0.2)
    got = paged_mla_attention_kernel(qa, qr, cp, krp, pt, pos, scale=0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert np.asarray(got)[2].max() == 0.0  # pad row


def test_dispatch_is_strict_about_kernel_unsupported_features():
    """A TRACED window and int8 pages of keys wider than a lane tile are
    outside the kernel: an explicit impl='pallas' raises instead of quietly
    running the reference; 'auto' (off-TPU: the reference by rule) still
    serves the call. A window that is a static of the call is the kernel's,
    and so are sinks."""
    q, keys, values, kp, vp, pt, pos = _paged_setup(seed=5)
    with pytest.raises(NotImplementedError, match="a traced sliding window"):
        ragged_paged_attention(
            q, kp, vp, pt, pos, scale=0.25, window=jnp.int32(4), impl="pallas",
        )
    np.testing.assert_allclose(
        np.asarray(ragged_paged_attention(
            q, kp, vp, pt, pos, scale=0.25, window=4, impl="pallas")),
        np.asarray(ragged_paged_attention_xla(
            q, kp, vp, pt, pos, scale=0.25, window=4)), atol=1e-5)
    sinks = jnp.linspace(-1.0, 2.0, q.shape[1], dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ragged_paged_attention(
            q, kp, vp, pt, pos, scale=0.25, impl="pallas", sinks=sinks)),
        np.asarray(ragged_paged_attention_xla(
            q, kp, vp, pt, pos, scale=0.25, sinks=sinks)), atol=1e-5)
    wide = jnp.zeros(kp.shape[:3] + (256,), jnp.int8)
    scales = jnp.ones(kp.shape[:2], jnp.float32)
    with pytest.raises(NotImplementedError,
                       match="int8 pages of keys wider than a lane tile"):
        ragged_paged_attention(
            jnp.zeros(q.shape[:2] + (192,), q.dtype), wide, vp.astype(jnp.int8),
            pt, pos, scale=0.25, impl="pallas", k_scales=scales,
            v_scales=scales,
        )
    got = ragged_paged_attention(
        q, kp, vp, pt, pos, scale=0.25, window=jnp.int32(4), impl="auto",
    )
    want = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=0.25, window=jnp.int32(4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# -- the (segment, page) grid: ragged plans against the reference -------------
PS, PAGES_A_SLOT, TILE = 4, 6, 8

#: a plan's rows in order: (slot, first position, rows) is a run of one
#: slot's rows at consecutive positions, None one pad row; `share` makes
#: slot b's first page slot a's (a shared prefix)
PLANS = {
    "decode rows only": dict(
        runs=[(0, 7, 1), (1, 13, 1), (2, 0, 1), (3, 22, 1), (4, 4, 1)]),
    "a chunk longer than the tile (split at the tile)": dict(
        runs=[(0, 9, 1), (1, 2, 13)]),
    "a chunk that ends on a page boundary": dict(
        runs=[(0, 5, 1), (1, 4, 4), (2, 11, 1)]),
    "a chunk that ends one token past a page boundary": dict(
        runs=[(0, 5, 1), (1, 4, 5), (2, 11, 1)]),
    "adjacent decode rows of two slots, consecutive positions": dict(
        runs=[(0, 6, 1), (1, 7, 1), (2, 8, 1)]),
    "pad rows in the middle and at the end": dict(
        runs=[(0, 3, 2), None, None, (1, 9, 1), None, (2, 0, 6), None]),
    "a speculative run: pending token and drafts": dict(
        runs=[(0, 17, 1), (1, 10, 5), (2, 3, 1)]),
    "two slots whose tables share a page": dict(
        runs=[(0, 5, 3), (1, 4, 1), (2, 9, 1)], share=(0, 1)),
}


#: every plan above in one shape, so each kernel is traced once: 16 rows,
#: 5 slots' pages, room for 6 segments (one more than any of them holds)
PLAN_SLOTS, PLAN_SEGMENTS = 5, 6


def _ragged_plan(runs, share=None, seed=0, min_rows=16, slots=PLAN_SLOTS):
    """(slot, pos, per-row page tables, pool pages) for `runs`, padded to
    whole tiles; every slot's table is a full row of distinct pages."""
    rng = np.random.default_rng(seed)
    rows = sum(1 if r is None else r[2] for r in runs)
    T = max(min_rows, -(-rows // TILE) * TILE)
    slot = np.full(T, -1, np.int32)
    pos = np.full(T, -1, np.int32)
    N = slots * PAGES_A_SLOT
    tables = rng.permutation(N).reshape(slots, PAGES_A_SLOT).astype(np.int32)
    if share is not None:
        a, b = share
        tables[b, 0] = tables[a, 0]
    row = 0
    for r in runs:
        if r is not None:
            s, p0, n = r
            slot[row:row + n] = s
            pos[row:row + n] = np.arange(p0, p0 + n)
        row += 1 if r is None else r[2]
    return slot, pos, tables[np.maximum(slot, 0)], N


def _segments_of(slot, pos, pt, max_segments=PLAN_SEGMENTS, window=None):
    from automodel_tpu.ops.paged_attention import row_segments

    return row_segments(
        jnp.asarray(slot), jnp.asarray(pos), jnp.asarray(pt), page_size=PS,
        tile=TILE, max_segments=max_segments, window=window,
    )


def _quantized(pages):
    from automodel_tpu.ops.quant import quantize_kv_rows

    q8, scales = quantize_kv_rows(pages.reshape(-1, *pages.shape[2:]))
    return q8.reshape(pages.shape), scales.reshape(pages.shape[:2])


@functools.lru_cache(maxsize=None)
def _jitted(kernel_name, **kw):
    """One trace per kernel and shape: the plans differ in their values."""
    from automodel_tpu.ops.pallas import ragged_paged_attention as rpa
    from automodel_tpu.ops.paged_attention import RowSegments

    kernel = getattr(rpa, kernel_name)
    return jax.jit(lambda blocks, count, *args: kernel(
        *args, segments=RowSegments(TILE, blocks, count), **kw))


#: (query heads, key/value heads): a one-row segment's block scores on the
#: VPU where a key/value head serves ONE query head and as a product against
#: the page read as a (ps x Hkv, D) matrix where it serves several
HEADS = {
    "no grouping 16:16": (16, 16),
    "grouped 4:1": (8, 2),
    "20 heads over one": (20, 1),
    "8 groups a head 16:2": (16, 2),
}


def _gqa_against_reference(plan, heads, quant, window=None):
    slot, pos, pt, N = _ragged_plan(**plan)
    Hq, Hkv = heads
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(len(slot), Hq, 16)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N + 1, PS, Hkv, 16)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N + 1, PS, Hkv, 16)), jnp.float32)
    pt, pos = jnp.asarray(pt), jnp.asarray(pos)
    _tile, *segments = _segments_of(slot, pos, pt, window=window)
    if quant:
        (kp, ks), (vp, vs) = _quantized(kp), _quantized(vp)
        want = ragged_paged_attention_xla(
            q, kp, vp, pt, pos, scale=0.25, window=window,
            k_scales=ks, v_scales=vs)
        got = _jitted("paged_attention_quant_kernel", scale=0.25,
                      window=window)(*segments, q, kp, vp, ks, vs, pt, pos)
    else:
        want = ragged_paged_attention_xla(
            q, kp, vp, pt, pos, scale=0.25, window=window)
        got = _jitted("paged_attention_kernel", scale=0.25, window=window)(
            *segments, q, kp, vp, pt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got)[np.asarray(pos) < 0].any()  # pads: exactly 0


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("heads", HEADS.values(), ids=HEADS.keys())
@pytest.mark.parametrize("plan", PLANS.values(), ids=PLANS.keys())
def test_pallas_gqa_segment_grid_matches_reference(plan, heads, quant):
    _gqa_against_reference(plan, heads, quant)


#: plans whose rows lie further into their sequences than either window
WINDOW_PLANS = ("decode rows only",
                "a chunk longer than the tile (split at the tile)",
                "pad rows in the middle and at the end",
                "a speculative run: pending token and drafts")


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize(
    "window", [3, 6], ids=["a window inside a page", "a window over two pages"])
@pytest.mark.parametrize("heads", HEADS.values(), ids=HEADS.keys())
@pytest.mark.parametrize("plan", WINDOW_PLANS)
def test_pallas_gqa_segment_grid_with_a_window_matches_reference(
        plan, heads, window, quant):
    """The same grid under a sliding window of 3 and of 6 tokens over
    4-token pages: the block list starts at the first in-window page and
    both bodies mask what lies further back."""
    _gqa_against_reference(PLANS[plan], heads, quant, window)


def test_the_counter_says_which_body_a_decode_row_got():
    """`paged_attention_one_row_body_total{scores}` ticks once a traced
    call site: `mxu` where a key/value head serves several query heads,
    `vpu` where it serves one; the rule reads the call's shape alone."""
    from automodel_tpu.observability.metrics import default_registry
    from automodel_tpu.ops.pallas import ragged_paged_attention as rpa

    def ticks():
        return {body: default_registry().counter(
            "paged_attention_one_row_body_total", scores=body).value
            for body in ("mxu", "vpu")}

    slot, pos, pt, N = _ragged_plan(**PLANS["decode rows only"])
    seen = {}
    for name, (Hq, Hkv) in HEADS.items():
        q = jnp.ones((len(slot), Hq, 16), jnp.float32)
        pages = jnp.ones((N + 1, PS, Hkv, 16), jnp.float32)
        before = ticks()
        jax.jit(functools.partial(
            rpa.paged_attention_kernel, scale=0.25, window=3,
            segments=_segments_of(slot, pos, pt, window=3))).lower(
                q, pages, pages, jnp.asarray(pt), jnp.asarray(pos))
        seen[name] = {b: n - before[b] for b, n in ticks().items()}
    assert seen == {
        "no grouping 16:16": {"mxu": 0, "vpu": 1},
        "grouped 4:1": {"mxu": 1, "vpu": 0},
        "20 heads over one": {"mxu": 1, "vpu": 0},
        "8 groups a head 16:2": {"mxu": 1, "vpu": 0},
    }


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("plan", PLANS.values(), ids=PLANS.keys())
def test_pallas_mla_segment_grid_matches_reference(plan, quant):
    slot, pos, pt, N = _ragged_plan(**plan)
    n, r, dr = 4, 16, 8
    rng = np.random.default_rng(8)
    qa = jnp.asarray(rng.normal(size=(len(slot), n, r)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(len(slot), n, dr)), jnp.float32)
    cp = jnp.asarray(rng.normal(size=(N + 1, PS, r)), jnp.float32)
    krp = jnp.asarray(rng.normal(size=(N + 1, PS, dr)), jnp.float32)
    pt, pos = jnp.asarray(pt), jnp.asarray(pos)
    _tile, *segments = _segments_of(slot, pos, pt)
    if quant:
        (cp, cs), (krp, krs) = _quantized(cp), _quantized(krp)
        want = ragged_paged_mla_attention_xla(
            qa, qr, cp, krp, pt, pos, scale=0.2, c_scales=cs, kr_scales=krs)
        got = _jitted("paged_mla_attention_quant_kernel", scale=0.2)(
            *segments, qa, qr, cp, krp, cs, krs, pt, pos)
    else:
        want = ragged_paged_mla_attention_xla(
            qa, qr, cp, krp, pt, pos, scale=0.2)
        got = _jitted("paged_mla_attention_kernel", scale=0.2)(
            *segments, qa, qr, cp, krp, pt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got)[np.asarray(pos) < 0].any()


def _random_runs(rng, T, slots):
    """A plan as the scheduler lays it out: decode rows first, then
    chunks, each slot's rows ONE run, pads at the end."""
    runs, left = [], T - int(rng.integers(0, 4))
    for s in range(slots):
        if left <= 0:
            break
        n = 1 if rng.random() < 0.6 else int(rng.integers(2, 14))
        n = min(n, left)
        runs.append((s, int(rng.integers(0, PS * PAGES_A_SLOT - n)), n))
        left -= n
    return runs


SEGMENT_CASES = {
    # (rows, slots, runs): the bound's two worst cases, then what a
    # scheduler lays out
    "every row another slot's decode row": (
        24, 24, [(s, s, 1) for s in range(24)]),  # positions consecutive too
    "one slot holds every row": (24, 1, [(0, 0, 24)]),
    **{
        f"random plan {i}": (24, 6, _random_runs(np.random.default_rng(i), 24, 6))
        for i in range(6)
    },
}


def _blocks_of(segments):
    """The live blocks of a `RowSegments`, a dict of its six rows."""
    from automodel_tpu.ops import paged_attention as pa

    blocks = np.asarray(segments.blocks)[:, :int(segments.count)]
    return {name[len("BLOCK_"):].lower(): blocks[getattr(pa, name)]
            for name in dir(pa) if name.startswith("BLOCK_")}


@pytest.mark.parametrize(
    "rows,slots,runs", SEGMENT_CASES.values(), ids=SEGMENT_CASES.keys())
def test_row_segments_partition_the_real_rows(rows, slots, runs):
    """The descriptors alone: every real row in exactly one segment, no
    segment spans two slots, a tile boundary or a break in the positions,
    none past the static bound; and a segment's blocks are its pages in
    order, each the pool page its table names, none past its last
    position."""
    from automodel_tpu.ops.paged_attention import max_row_segments

    slot, pos, pt, _N = _ragged_plan(runs, min_rows=rows, slots=slots)
    G = max_row_segments(rows, slots, TILE)
    segments = _segments_of(slot, pos, pt, max_segments=G)
    assert segments.blocks.shape == (6, G * PAGES_A_SLOT)
    b = _blocks_of(segments)
    firsts = np.flatnonzero(b["column"] == 0)
    assert len(firsts) <= G
    covered = np.zeros(rows, int)
    for lo, hi in zip(firsts, [*firsts[1:], len(b["column"])]):
        start = b["tile"][lo] * TILE + b["offset"][lo]
        seg = slice(start, start + b["length"][lo])
        covered[seg] += 1
        assert len(set(slot[seg])) == 1 and slot[start] >= 0
        assert (np.diff(pos[seg]) == 1).all() and pos[start] == b["position"][lo]
        assert b["offset"][lo] + b["length"][lo] <= TILE
        # its blocks: pages 0 .. that of its last position, from ITS table
        np.testing.assert_array_equal(
            b["column"][lo:hi], np.arange(pos[seg][-1] // PS + 1))
        np.testing.assert_array_equal(b["page"][lo:hi], pt[start][: hi - lo])
        for name in ("tile", "offset", "length", "position"):
            assert (b[name][lo:hi] == b[name][lo]).all()
    np.testing.assert_array_equal(covered, (pos >= 0).astype(int))


def test_adjacent_slots_with_consecutive_positions_do_not_merge():
    slot, pos, pt, _N = _ragged_plan(
        **PLANS["adjacent decode rows of two slots, consecutive positions"])
    b = _blocks_of(_segments_of(slot, pos, pt))
    first = b["column"] == 0
    assert b["length"][first].tolist() == [1, 1, 1]
    assert b["offset"][first].tolist() == [0, 1, 2]


def test_a_step_of_pad_rows_walks_one_empty_block():
    slot, pos = np.full(16, -1, np.int32), np.full(16, -1, np.int32)
    segments = _segments_of(slot, pos, np.zeros((16, PAGES_A_SLOT), np.int32))
    assert int(segments.count) == 0
    assert not np.asarray(segments.blocks)[3].any()  # BLOCK_LENGTH: all 0
    from automodel_tpu.ops.pallas import ragged_paged_attention as rpa

    q = jnp.ones((16, 4, 16), jnp.float32)
    pages = jnp.ones((PLAN_SLOTS * PAGES_A_SLOT + 1, PS, 2, 16), jnp.float32)
    out = rpa.paged_attention_kernel(
        q, pages, pages, jnp.zeros((16, PAGES_A_SLOT), jnp.int32),
        jnp.asarray(pos), scale=0.25, segments=segments)
    assert not np.asarray(out).any()


def test_turn_stats_count_the_grid_the_step_walks():
    """`Scheduler.turn_stats` (the `serve.step.plan` span's args, the two
    /metrics gauges) against the descriptors the step derives from the
    same plan: as many segments, as many (segment, page) blocks."""
    from automodel_tpu.serving.scheduler import Request, Scheduler

    sched = Scheduler(
        num_pages=32, page_size=PS, max_slots=4, pages_per_slot=8,
        token_budget=16, prefill_chunk=12, attn_row_tile=TILE)
    for rid, n in enumerate((3, 19, 7)):
        sched.submit(Request(prompt=list(range(1, n + 1)), max_new_tokens=4,
                             rid=rid))
    seen = []
    for step in range(4):
        plan = sched.schedule(step)
        stats = sched.turn_stats(0, plan)
        segments = _segments_of(
            plan.slot, plan.pos, plan.page_tables[np.maximum(plan.slot, 0)],
            max_segments=16)
        assert stats["attn_live_blocks"] == int(segments.count)
        b = _blocks_of(segments)
        assert stats["attn_segments"] == (b["column"] == 0).sum()
        assert stats["attn_one_row_blocks"] == (b["length"] == 1).sum()
        seen.append((stats["attn_segments"], stats["attn_live_blocks"]))
        sched.update(plan, np.zeros(4, np.int32), step)
    # 3 rows of the first prompt (1 page); 12 of the second, cut at the
    # tile into 5 and 7 (2 + 3 pages); 1 of the third (1 page)
    assert seen[0] == (4, 7)
    assert sched.turn_stats(0)["attn_segments"] == 0  # no plan, no grid


def test_turn_stats_count_the_blocks_of_one_row_runs():
    """`attn_one_row_blocks`: of a plan of decode rows, a chunk and pad
    rows, the live blocks of the runs of ONE row, which the GQA kernel
    scores in a body of its own; a chunk's last row alone in its tile is
    such a run too."""
    from automodel_tpu.serving.scheduler import Request, Scheduler

    sched = Scheduler(
        num_pages=32, page_size=PS, max_slots=4, pages_per_slot=8,
        token_budget=16, prefill_chunk=12, attn_row_tile=TILE)
    for rid, n in enumerate((5, 2, 9)):
        sched.submit(Request(prompt=list(range(1, n + 1)), max_new_tokens=8,
                             rid=rid))
    plan = sched.schedule(0)           # three chunks: 5 + 2 + 9 rows
    stats = sched.turn_stats(0, plan)
    # rows 0-4, 5-6, then 7 ALONE before the tile's end, 8-15: the ninth
    # prompt's first row is a run of one row (1 page)
    assert (stats["attn_segments"], stats["attn_one_row_blocks"]) == (4, 1)
    sched.update(plan, np.zeros(4, np.int32), 0)
    plan = sched.schedule(1)           # three decode rows and 13 pad rows
    assert (plan.pos >= 0).sum() == 3 and (plan.pos < 0).sum() == 13
    stats = sched.turn_stats(0, plan)
    # positions 5, 2 and 9 over 4-token pages: 2 + 1 + 3 pages
    assert stats["attn_segments"] == 3
    assert stats["attn_one_row_blocks"] == stats["attn_live_blocks"] == 6
    segments = _segments_of(
        plan.slot, plan.pos, plan.page_tables[np.maximum(plan.slot, 0)],
        max_segments=16)
    assert (_blocks_of(segments)["length"] == 1).sum() == 6
    assert sched.turn_stats(0)["attn_one_row_blocks"] == 0


def test_pallas_gqa_under_tp_takes_the_segments_replicated():
    """tp=2: the kernel runs inside a shard_map on each rank's heads, the
    step's row segments replicated beside the page tables."""
    from automodel_tpu.distributed.mesh import MeshConfig

    mesh_ctx = MeshConfig(tp=2, dp_shard=1).build(jax.devices()[:2])
    slot, pos, pt, N = _ragged_plan(**PLANS["pad rows in the middle and at the end"])
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(len(slot), 8, 16)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N + 1, PS, 2, 16)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N + 1, PS, 2, 16)), jnp.float32)
    pt, pos = jnp.asarray(pt), jnp.asarray(pos)
    want = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=0.25)
    got = jax.jit(lambda *a: ragged_paged_attention(
        *a, scale=0.25, impl="pallas", mesh_ctx=mesh_ctx,
        segments=_segments_of(slot, pos, pt)))(q, kp, vp, pt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
