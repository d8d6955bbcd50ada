"""Pallas TPU kernel for ragged paged attention (serving decode/prefill).

The TPU backend of `ops/paged_attention.py` (arXiv:2604.15464 style). The
unit of work is a (segment, page) BLOCK: a segment is a run of consecutive
rows of ONE sequence inside one aligned tile of `tile` rows, and the grid
is the flat list of the blocks that hold a key some row attends to
(`RowSegments`, derived once a step by `ops/paged_attention.row_segments`
from each row's slot, position and page table; its length is a run-time
grid bound, so a step walks its live blocks and no others). The list
drives the BlockSpec index maps through scalar prefetch: block w DMAs
`pages[blocks[PAGE, w]]`, so

- a sequence's pages are fetched once per segment, not once per row (a
  prefill chunk's rows share them), and never gathered into a
  (T, P, page_size, ...) intermediate in HBM as the XLA reference does;
- a page past a segment's last position, a table's padding and a pad row
  are no block at all: nothing is fetched for them and no grid step spent.

A segment's pages stream in order with the usual online-softmax (m, l,
acc) VMEM scratch carried across them (the flash_attention.py recipe),
reset on its first page and stored on its last. The q and output blocks
are the segment's aligned tile: consecutive segments of one tile revisit
the same output block and each stores its own rows alone. A segment of
several rows computes the tile's rows at once, each row masked at its own
position, the rows outside the segment wholly. A segment of ONE row (a
decode row) runs a one-row body, and the GQA kernel has two, chosen by the
call's static head counts alone (`one_row_body`): where a key/value head
serves ONE query head the keys times the query summed over the lanes, on
the VPU (the MXU would want every head's keys turned for one query row);
where it serves SEVERAL, one product of the row's (Hq, D) queries against
the page read as the (ps x Hkv, D) matrix it is in memory, the other
key/value heads' columns masked out of the softmax. Rows of no segment come
out zero (the wrapper zeroes them behind the call).

Without descriptors (`segments=None`: tests, chip_smoke.py) every row is
its own segment. Covers GQA (kv-head sharing, no KV repeat) and
absorbed-MLA (scores latent + rope parts summed in one accumulator, output
in latent space), over bf16 and int8 pages. The GQA kernels take a sliding
window as a static of the call: the block list then begins at the page of
the segment's first in-window key (`row_segments(window=...)`) and the mask
drops every key `window` or more positions behind its row; such a call is
named `paged_attention_window_gqa`. The MLA kernels
take no window and none takes attention sinks: the dispatcher
(ops/paged_attention.py) states the rules and never calls in here with them.

GQA head dims that are not lane (128) multiples are zero-padded host-side
(pad lanes add zero logits / zero value columns — exact), which copies the
pool on every call; the MLA latent/rope pages go in as they are. Runs on
CPU via interpret mode for unit-test parity against the XLA reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.paged_attention import (
    BLOCK_COLUMN,
    BLOCK_LENGTH,
    BLOCK_OFFSET,
    BLOCK_PAGE,
    BLOCK_POSITION,
    BLOCK_TILE,
    RowSegments,
    row_segments,
    row_tile,
    window_first_page,
)
from automodel_tpu.ops.pallas.flash_attention import LANE, NEG_INF, _pad_last


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# -- what the four kernels share ---------------------------------------------
def _block(blocks_ref, page_size, window=None):
    """This grid step's block: its segment's (row offset in the tile,
    length, first position), the index of its page's first key, and
    whether the page is its segment's first and its last. With a `window`
    the first is the page of the first row's first in-window key."""
    w = pl.program_id(0)
    off, length, pos0, column = (
        blocks_ref[row, w]
        for row in (BLOCK_OFFSET, BLOCK_LENGTH, BLOCK_POSITION, BLOCK_COLUMN)
    )
    last = column == (pos0 + length - 1) // page_size
    first = column == (
        window_first_page(pos0, window, page_size) if window else 0)
    return off, length, pos0, column * page_size, first, last


def _attends(kv_idx, row_pos, window):
    """Which keys a row at `row_pos` attends to: none after it and, with a
    window, none `window` or more positions before it."""
    mask = kv_idx <= row_pos
    if window:
        mask = jnp.logical_and(mask, row_pos - kv_idx < window)
    return mask


def _reset(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _softmax_page(s, mask, m_ref, l_ref, acc_ref, weighted_values):
    """One page of online softmax: scores `s` (..., R, ps) against the
    (..., R, LANE) / (..., R, Dv) running state; `weighted_values(p)` is
    p @ V."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[..., :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(
        l_ref[..., :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
        l_ref.shape,
    )
    acc_ref[...] = acc_ref[...] * alpha + weighted_values(p)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _normalized(l_ref, acc_ref):
    l = l_ref[..., :1]
    return jnp.where(l == 0.0, 0.0, acc_ref[...] / jnp.where(l == 0.0, 1.0, l))


def _tile_row_positions(tile_row, off, length, pos0):
    """Position of each score row's token, `tile_row` (R, 1) its row of
    the tile: row i holds position pos0 + i - off inside the segment, -1
    (it attends to nothing) outside."""
    inside = jnp.logical_and(tile_row >= off, tile_row < off + length)
    return jnp.where(inside, pos0 + tile_row - off, -1)


def _store_segment_rows(out_ref, val, off, length):
    """Store tile rows [off, off + length) of `val` (tile, H, w) into the
    revisited output block, the other rows as they stand."""
    i = jax.lax.broadcasted_iota(jnp.int32, val.shape, 0)
    inside = jnp.logical_and(i >= off, i < off + length)
    out_ref[...] = jnp.where(inside, val.astype(out_ref.dtype), out_ref[...])


def _rows_as_segments(page_tables, positions, page_size, row_width,
                      window=None):
    """Every row a sequence of its own: the descriptors of a call that
    brings none."""
    T = positions.shape[0]
    return row_segments(
        jnp.arange(T, dtype=jnp.int32), positions, page_tables,
        page_size=page_size, tile=row_tile(T, row_width), max_segments=T,
        window=window,
    )


def _segment_call(kernel, name, interpret, segments, positions, queries,
                  pages, scales, out_width, scratch, page_size):
    """`pallas_call` over the step's blocks. `queries` are (T, H, w) row
    arrays blocked by the block's tile, `pages` (N, ...) pools of
    `page_size` tokens a page and `scales` their (N, ps) per-row scales
    blocked by the block's page, the output (T, H, out_width) blocked like
    the queries."""
    tile, blocks, count = segments
    T, H, _ = queries[0].shape
    ps = page_size

    def row_map(w, blocks):
        return (blocks[BLOCK_TILE, w], 0, 0)

    def page_map(ndim):
        return lambda w, blocks: (blocks[BLOCK_PAGE, w],) + (0,) * (ndim - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # a step with no real row still walks one block, of length 0
        grid=(jnp.maximum(count, 1),),
        in_specs=[
            *(pl.BlockSpec((tile, *q.shape[1:]), row_map) for q in queries),
            *(pl.BlockSpec((1, *p.shape[1:]), page_map(p.ndim)) for p in pages),
            # (N, 1, ps): a (1, ps) block of an (N, ps) array breaks Mosaic's
            # rule that a block's second-to-last dim is a multiple of 8 or
            # the whole axis
            *(pl.BlockSpec((1, 1, ps), page_map(3)) for _ in scales),
        ],
        out_specs=pl.BlockSpec((tile, H, out_width), row_map),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(kernel, tile=tile, page_size=ps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H, out_width), queries[0].dtype),
        interpret=interpret,
        name=name,
    )(
        blocks, *queries, *pages,
        *(s.astype(jnp.float32)[:, None, :] for s in scales),
    )
    # rows of no segment were never stored
    return jnp.where((positions >= 0)[:, None, None], out, 0)


# -- GQA ----------------------------------------------------------------------
def one_row_body(Hq: int, Hkv: int) -> str:
    """How a GQA call of these head counts scores a ONE-ROW segment's block
    (a decode row's): "mxu" where a key/value head serves several query
    heads, "vpu" where it serves one. The call's static shape decides and
    nothing else; `_gqa_kernel` says why each."""
    return "mxu" if Hq // Hkv > 1 else "vpu"


def _gqa_kernel(
    blocks_ref,  # (6, W) scalar-prefetch blocks (RowSegments.blocks)
    q_ref,     # (tile, Hq, D)
    k_ref,     # (1, ps, Hkv, D)   bf16, or int8 with scales; groups > 1:
               # the same page as a matrix, (1, ps x Hkv, D)
    v_ref,     # (1, ps, Hkv, Dv); groups > 1: (1, ps x Hkv, Dv)
    *rest,     # [ks_ref, vs_ref (1, 1, ps) f32 per-row scales of THIS page,]
               # out_ref (tile, Hq, Dv), one row's m/l/acc, the tile's
               # head-major q and m/l/acc
    scale, soft_cap, page_size, groups, tile, quant, window,
):
    """bf16 and int8 pages alike. The tile body scores (Hkv, groups x
    tile, D) queries, the tile's rows turned head-major once a segment,
    against the page's (ps, Hkv, D) keys on the MXU, batched over the
    key/value heads. The one-row body depends on `groups`, the query heads
    a key/value head serves (`one_row_body`): one, and it scores on the VPU
    (`one_row_vpu`); several, and the page IS a matrix the row's queries
    multiply with nothing to turn (`one_row_mxu`), which is why such a call
    hands the pages in as (ps x Hkv, w) matrices. int8: the per-page scale
    rows ride the SAME page-table entry as the payload, and dequantization
    is algebraic per page: the K scale multiplies each kv slot's score
    column, the V scale folds into the softmax weights before the value
    product — the int8 blocks are cast for the products, never materialized
    dequantized in HBM."""
    if quant:
        ks_ref, vs_ref, *rest = rest
    out_ref, m1, l1, acc1, qt, mt, lt, acct = rest
    off, length, pos0, key0, first_page, last_page = _block(
        blocks_ref, page_size, window)
    one_row = length == 1
    many_rows = length > 1
    Hq = q_ref.shape[1]
    Hkv, ps, Dv = Hq // groups, page_size, v_ref.shape[-1]
    kv_idx = key0 + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)

    def finished(s, k_scale=None):
        """Raw scores (..., ps) scaled and capped; int8: the K scale of
        each kv slot first, before any soft-cap nonlinearity."""
        if quant:
            s = s * (ks_ref[0] if k_scale is None else k_scale)
        s = s * scale
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        return s

    def by_head(page_ref):
        """The page as the tile body's batched products read it, and the
        axis its key/value heads lie on: (ps, Hkv, w) as an ungrouped call
        hands it in, or made of a grouped call's (ps x Hkv, w) matrix."""
        page = page_ref[0]
        if groups == 1:
            return page, 1
        if Hkv == 1:
            return page[None], 0
        return page.reshape(ps, Hkv, page.shape[-1]), 1

    def tile_scores(q):
        """q (Hkv, R, D) → (Hkv, R, ps) f32 on the MXU."""
        k, heads = by_head(k_ref)
        if quant:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        return finished(jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (heads,))),
            preferred_element_type=jnp.float32,
        ))

    def weighted_values(p):
        """p (Hkv, R, ps) → (Hkv, R, Dv); the V dequant folds into the
        weights: (p * vs) @ v_int8 == p @ v_fp."""
        v, heads = by_head(v_ref)
        if quant:
            p, v = p * vs_ref[...], v.astype(jnp.float32)
        else:
            p = p.astype(v.dtype)
        return jax.lax.dot_general(
            p, v, (((2,), (1 - heads,)), ((0,), (heads,))),
            preferred_element_type=jnp.float32,
        )

    # -- a decode row ---------------------------------------------------------
    def one_row_vpu():
        """groups == 1. (Hq, ps) f32 scores on the VPU: a product with the
        keys and a sum over D. The MXU wants every head's (ps, D) keys
        turned to (D, ps) first, and for ONE query row a head that turning
        is most of the block: 2.9 us a block where this takes 1.25 and the
        page's fetch alone 0.97 (measured on a TPU v5e, 16 heads over 16 of
        128, 64-token pages: PERF.md, PR 30). The softmax runs on (Hq, ps)
        scores: on (Hkv, 1, ps) Mosaic is handed a compare with a one-wide
        sublane axis, which the TPU compiler refuses (LLO_CHECK
        ProducesVreg)."""
        k = k_ref[0].astype(jnp.float32)  # (ps, Hkv, D)
        # (as (Hkv, 1, D) and sliced again: the ops of the body the chip
        # measured, whose jaxpr tests/unit/test_tpu_compile.py pins)
        q = q_ref[off].astype(jnp.float32).reshape(Hkv, 1, -1)
        s = jnp.sum(k * q[:, 0][None], axis=-1)  # (ps, Hkv)
        _softmax_page(
            finished(jnp.swapaxes(s, 0, 1)), _attends(kv_idx, pos0, window),
            m1, l1, acc1,
            lambda p: weighted_values(p.reshape(Hkv, 1, ps)).reshape(Hq, Dv),
        )

    def one_row_mxu():
        """groups > 1. The page lies in memory as a (ps x Hkv, D) matrix,
        row t x Hkv + h the key of token t and key/value head h, so the
        row's (Hq, D) queries score against ALL of it in one product with
        nothing to turn, and the columns of a query head's OTHER key/value
        heads leave the softmax by the mask: `p @ V` over the flat values
        is then each head's own sum. Hkv times the MXU's work on a unit
        that idles in this body; on the VPU the same scores were `groups`
        passes over the page (measured on a TPU v5e, the kernel alone,
        PERF.md PR 37: 4.6 us a block -> 0.53 at 20 heads over 1 of 128
        with 64-token pages; 1.4 -> 0.8 at 2 groups a head; a call of 2,010
        blocks, 1,650 of them one-row, 7.7 -> 4.8 ms at 64 over 8 with
        128-token pages, where the product batched over the key/value
        heads, which turns keys and values head-major as the tile body
        does, read 10.2)."""
        q, k, v = q_ref[off], k_ref[0], v_ref[0]
        if quant:
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        column = jax.lax.broadcasted_iota(jnp.int32, (1, ps * Hkv), 1)
        mask = _attends(key0 + column // Hkv, pos0, window)
        if Hkv > 1:
            head = jax.lax.broadcasted_iota(jnp.int32, (Hq, 1), 0) // groups
            mask = jnp.logical_and(mask, column % Hkv == head)

        def of_rows(per_key):
            """(1, ps) of the page's tokens → (1, ps x Hkv) of the
            matrix's rows, exactly: a 0/1 product in full precision."""
            if Hkv == 1:
                return per_key
            token = jax.lax.broadcasted_iota(jnp.int32, (ps, ps * Hkv), 0)
            return jnp.dot(
                per_key, (column // Hkv == token).astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

        def weighted(p):
            p = p * of_rows(vs_ref[0]) if quant else p.astype(v.dtype)
            return jnp.dot(p, v, preferred_element_type=jnp.float32)

        _softmax_page(
            finished(s, of_rows(ks_ref[0]) if quant else None), mask,
            m1, l1, acc1, weighted)

    @pl.when(jnp.logical_and(one_row, first_page))
    def _reset_one():
        _reset(m1, l1, acc1)

    pl.when(one_row)(one_row_mxu if groups > 1 else one_row_vpu)

    @pl.when(jnp.logical_and(one_row, last_page))
    def _store_one():
        out_ref[off] = _normalized(l1, acc1).astype(out_ref.dtype)

    # -- a chunk's rows: the whole tile, head-major ---------------------------
    @pl.when(jnp.logical_and(many_rows, first_page))
    def _reset_tile():
        _reset(mt, lt, acct)
        # (tile, Hq, D) → (Hkv, groups x tile, D): score row g * tile + i
        # of a key/value head is its query head g of tile row i
        qt[...] = jnp.swapaxes(q_ref[...], 0, 1).reshape(qt.shape)

    @pl.when(many_rows)
    def _tile_rows():
        tile_row = jax.lax.rem(jax.lax.broadcasted_iota(
            jnp.int32, (groups * tile, 1), 0), tile)
        mask = _attends(
            kv_idx, _tile_row_positions(tile_row, off, length, pos0), window)
        _softmax_page(tile_scores(qt[...]), mask[None], mt, lt, acct,
                      weighted_values)

    @pl.when(jnp.logical_and(many_rows, last_page))
    def _store_tile():
        rows = _normalized(lt, acct).reshape(Hq, tile, Dv)
        _store_segment_rows(out_ref, jnp.swapaxes(rows, 0, 1), off, length)


@functools.partial(
    jax.jit, inline=True,
    static_argnames=("tile", "scale", "soft_cap", "name", "interpret",
                     "window"))
def _gqa_call(q, k_pages, v_pages, scales, positions, blocks, count, *,
              tile, scale, soft_cap, name, interpret, window=None):
    """Jitted (inlined into its caller) for its cache alone: a step traces
    one call per layer and pass, and every one after the first is the
    first's jaxpr."""
    Hq, (N, ps, Hkv, _) = q.shape[1], k_pages.shape
    groups = Hq // Hkv
    Dv = v_pages.shape[-1]
    qp = _pad_last(q, LANE)
    kp = _pad_last(k_pages, LANE)
    vp = _pad_last(v_pages, LANE)
    Dp, Dvp = qp.shape[-1], vp.shape[-1]
    if one_row_body(Hq, Hkv) == "mxu":
        # the page as the matrix `one_row_mxu` multiplies: a view, the pool
        # is laid out so. (With ONE key/value head the four-dimensional page
        # has a one-wide axis second-to-last, for which XLA copied the whole
        # pool into a two-fold padded layout before every call.)
        kp = kp.reshape(N, ps * Hkv, Dp)
        vp = vp.reshape(N, ps * Hkv, Dvp)
    f32 = jnp.float32
    scratch = [
        pltpu.VMEM((Hq, LANE), f32),
        pltpu.VMEM((Hq, LANE), f32),
        pltpu.VMEM((Hq, Dvp), f32),
        pltpu.VMEM((Hkv, groups * tile, Dp), qp.dtype),
        pltpu.VMEM((Hkv, groups * tile, LANE), f32),
        pltpu.VMEM((Hkv, groups * tile, LANE), f32),
        pltpu.VMEM((Hkv, groups * tile, Dvp), f32),
    ]
    kernel = functools.partial(
        _gqa_kernel, scale=scale, soft_cap=soft_cap, groups=groups,
        quant=len(scales) > 0, window=window,
    )
    out = _segment_call(
        kernel, name, interpret, RowSegments(tile, blocks, count), positions,
        [qp], [kp, vp], scales, Dvp, scratch, ps,
    )
    return out[..., :Dv]


def _gqa(q, k_pages, v_pages, scales, page_tables, positions, *, scale,
         soft_cap, segments, name, window=None):
    window = window or None
    if window:
        # the same body under a name of its own: a trace and a lowered step
        # tell a window layer's calls from a full layer's
        name = name.replace("paged_attention", "paged_attention_window")
    if segments is None:
        segments = _rows_as_segments(
            page_tables, positions, k_pages.shape[1],
            q.shape[1] * (q.shape[2] + v_pages.shape[-1]), window)
    from automodel_tpu.observability.metrics import default_registry

    # trace time: once a call site (`_gqa_call`'s cache would tick once a
    # shape), so an operator and a test see which body a model got
    default_registry().counter(
        "paged_attention_one_row_body_total",
        scores=one_row_body(q.shape[1], k_pages.shape[2])).inc()
    return _gqa_call(
        q, k_pages, v_pages, tuple(scales), positions,
        segments.blocks, segments.count, tile=segments.tile,
        scale=float(scale), soft_cap=soft_cap, name=name,
        interpret=_interpret(), window=window,
    )


def paged_attention_kernel(
    q, k_pages, v_pages, page_tables, positions,
    *,
    scale: float,
    soft_cap: float | None = None,
    segments: RowSegments | None = None,
    window: int | None = None,
):
    """GQA ragged paged attention; q (T, Hq, D), pages (N, ps, Hkv, D[v]),
    `page_tables` (T, P) per ROW. `segments` groups the rows into runs of
    one sequence (`row_segments`, built with the same `window`); None:
    every row its own. `window` (static): a row attends to the keys fewer
    than `window` positions behind it."""
    return _gqa(
        q, k_pages, v_pages, (), page_tables, positions,
        scale=scale, soft_cap=soft_cap, segments=segments,
        name="paged_attention_gqa", window=window,
    )


def paged_attention_quant_kernel(
    q, k_pages, v_pages, k_scales, v_scales, page_tables, positions,
    *,
    scale: float,
    soft_cap: float | None = None,
    segments: RowSegments | None = None,
    window: int | None = None,
):
    """GQA ragged paged attention over int8 pages with (N, ps) per-row
    scales; same contract as `paged_attention_kernel`."""
    return _gqa(
        q, k_pages, v_pages, (k_scales, v_scales), page_tables, positions,
        scale=scale, soft_cap=soft_cap, segments=segments,
        name="paged_attention_gqa_int8", window=window,
    )


# -- absorbed MLA -------------------------------------------------------------
def _mla_kernel(
    blocks_ref,  # (6, W) scalar-prefetch blocks (RowSegments.blocks)
    qa_ref,    # (tile, n, r)
    qr_ref,    # (tile, n, dr)
    c_ref,     # (1, ps, r)    bf16, or int8 with scales
    kr_ref,    # (1, ps, dr)
    *rest,     # [cs_ref, krs_ref (1, 1, ps) f32 per-row scales of THIS page,]
               # out_ref (tile, n, r), one row's m/l/acc, the tile's m/l/acc
    scale, page_size, tile, quant,
):
    """One shared latent: every head of every row scores against the same
    (ps, r + dr) page, so a tile's rows are one (tile x n, r) product.
    int8: the latent and rope score parts carry DIFFERENT per-row scales
    (two cached quantities, two scale arrays), so each is applied to its
    dot before the parts sum into the shared accumulator; the latent scale
    folds into the softmax weights for the value product (values ARE the
    latent pages)."""
    if quant:
        cs_ref, krs_ref, *rest = rest
    out_ref, m1, l1, acc1, mt, lt, acct = rest
    off, length, pos0, key0, first_page, last_page = _block(
        blocks_ref, page_size)
    one_row = length == 1
    many_rows = length > 1
    n, r = qa_ref.shape[1:]
    ps = c_ref.shape[1]
    kv_idx = key0 + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)

    def page(qa, qr, mask, m_ref, l_ref, acc_ref):
        """qa (R, r), qr (R, dr): the absorbed scores' latent and rope
        parts share one accumulator."""
        c, kr = c_ref[0], kr_ref[0]      # (ps, r), (ps, dr)
        if quant:
            qa, qr, c, kr = (x.astype(jnp.float32) for x in (qa, qr, c, kr))
        dims = (((1,), (1,)), ((), ()))
        s_lat = jax.lax.dot_general(
            qa, c, dims, preferred_element_type=jnp.float32)
        s_rope = jax.lax.dot_general(
            qr, kr, dims, preferred_element_type=jnp.float32)
        if quant:
            s_lat, s_rope = s_lat * cs_ref[0], s_rope * krs_ref[0]

        def weighted_values(p):
            p = p * cs_ref[0] if quant else p.astype(c.dtype)
            return jnp.dot(p, c, preferred_element_type=jnp.float32)

        _softmax_page(
            (s_lat + s_rope) * scale, mask, m_ref, l_ref, acc_ref,
            weighted_values,
        )

    @pl.when(jnp.logical_and(one_row, first_page))
    def _reset_one():
        _reset(m1, l1, acc1)

    @pl.when(one_row)
    def _one_row():
        page(qa_ref[off], qr_ref[off], kv_idx <= pos0, m1, l1, acc1)

    @pl.when(jnp.logical_and(one_row, last_page))
    def _store_one():
        out_ref[off] = _normalized(l1, acc1).astype(out_ref.dtype)

    @pl.when(jnp.logical_and(many_rows, first_page))
    def _reset_tile():
        _reset(mt, lt, acct)

    @pl.when(many_rows)
    def _tile_rows():
        # score row i * n + h is head h of tile row i
        tile_row = jax.lax.broadcasted_iota(
            jnp.int32, (tile * n, 1), 0) // n
        mask = kv_idx <= _tile_row_positions(tile_row, off, length, pos0)
        page(
            qa_ref[...].reshape(tile * n, r),
            qr_ref[...].reshape(tile * n, -1),
            mask, mt, lt, acct,
        )

    @pl.when(jnp.logical_and(many_rows, last_page))
    def _store_tile():
        _store_segment_rows(
            out_ref, _normalized(lt, acct).reshape(tile, n, r), off, length)


@functools.partial(
    jax.jit, inline=True,
    static_argnames=("tile", "scale", "name", "interpret"))
def _mla_call(q_abs, q_rope, c_pages, kr_pages, scales, positions, blocks,
              count, *, tile, scale, name, interpret):
    """Jitted for its cache alone, as `_gqa_call`."""
    n, r = q_abs.shape[1:]
    f32 = jnp.float32
    scratch = [
        pltpu.VMEM((n, LANE), f32),
        pltpu.VMEM((n, LANE), f32),
        pltpu.VMEM((n, r), f32),
        pltpu.VMEM((tile * n, LANE), f32),
        pltpu.VMEM((tile * n, LANE), f32),
        pltpu.VMEM((tile * n, r), f32),
    ]
    kernel = functools.partial(
        _mla_kernel, scale=scale, quant=len(scales) > 0)
    return _segment_call(
        kernel, name, interpret, RowSegments(tile, blocks, count), positions,
        [q_abs, q_rope], [c_pages, kr_pages], scales, r, scratch,
        c_pages.shape[1],
    )


def _mla(q_abs, q_rope, c_pages, kr_pages, scales, page_tables, positions, *,
         scale, segments, name):
    if segments is None:
        n, r = q_abs.shape[1:]
        segments = _rows_as_segments(
            page_tables, positions, c_pages.shape[1],
            n * (2 * r + q_rope.shape[-1]))
    return _mla_call(
        q_abs, q_rope, c_pages, kr_pages, tuple(scales), positions,
        segments.blocks, segments.count, tile=segments.tile,
        scale=float(scale), name=name, interpret=_interpret(),
    )


def paged_mla_attention_kernel(
    q_abs, q_rope, c_pages, kr_pages, page_tables, positions,
    *,
    scale: float,
    segments: RowSegments | None = None,
):
    """Absorbed-MLA ragged paged attention; returns latent outputs
    (T, n, r). `segments` as in `paged_attention_kernel`."""
    return _mla(
        q_abs, q_rope, c_pages, kr_pages, (), page_tables, positions,
        scale=scale, segments=segments, name="paged_attention_mla",
    )


def paged_mla_attention_quant_kernel(
    q_abs, q_rope, c_pages, kr_pages, c_scales, kr_scales,
    page_tables, positions,
    *,
    scale: float,
    segments: RowSegments | None = None,
):
    """Absorbed-MLA ragged paged attention over int8 latent/rope pages
    with (N, ps) per-row scales; same contract as
    `paged_mla_attention_kernel`."""
    return _mla(
        q_abs, q_rope, c_pages, kr_pages, (c_scales, kr_scales),
        page_tables, positions,
        scale=scale, segments=segments, name="paged_attention_mla_int8",
    )
