"""Ragged paged attention over a paged KV pool (serving decode/prefill op).

The attention core of the continuous-batching serving engine
(`automodel_tpu/serving/engine.py`), after arXiv:2604.15464 (Ragged Paged
Attention): query tokens arrive as ONE flat ragged batch — decode tokens
from many requests interleaved with chunked-prefill tokens — and the KV
cache lives in fixed-size pages of a global pool, indexed per token through
a page table. Nothing is padded per request and no dense (B, T) cache is
ever materialized.

Two backends, chosen by the one dispatch rule of ops/attention.py
(`resolve_kernel_impl`: explicit "pallas" is strict, "auto" on a TPU logs
and counts every call it hands to the reference):

- XLA reference (this file): gather each token's pages from the pool and
  run masked softmax attention — pure gather/einsum, runs (and is tested)
  under `JAX_PLATFORMS=cpu`, and is the correctness oracle for the kernel.
- Pallas TPU kernel (`ops/pallas/ragged_paged_attention.py`): streams pages
  through VMEM with the page table as a scalar-prefetch BlockSpec index map
  (no gathered (T, P, page, ...) intermediate in HBM), one (segment, page)
  block at a time: `row_segments` below groups a step's rows into runs of
  one sequence, so a prefill chunk's rows fetch their pages once. The GQA
  kernel takes a sliding window as a static of the call (the block list
  then starts at the page of the segment's first in-window key and the mask
  drops what lies further back) and per-head sink logits (a call with
  them is named `paged_attention_sink_gqa`); the MLA kernels take neither
  (`_gqa_unsupported_reason` / `_mla_unsupported_reason` below state the
  rules).

Layouts (see serving/kv_pages.py for the pool):

- GQA:  k_pages (N, ps, Hkv, Dk), v_pages (N, ps, Hkv, Dv); q (T, Hq, D):
  values may be narrower than keys, and keys wider than a lane tile lie at
  a whole number of tiles (Dk >= D, zeros behind the key).
- Quantized pools (serving kv_cache_dtype="int8"): the same page layouts
  hold int8 plus (N, ps) per-row scale arrays riding alongside; the
  reference dequantizes the gathered per-token view, the kernel variant
  dequantizes per page inside the online-softmax loop (the scale rides
  the same scalar-prefetch page table as the payload).
- MLA:  c_pages (N, ps, r) rms-normed kv latents, kr_pages (N, ps, dr)
  rotated shared key-rope head; queries come pre-absorbed — q_abs (T, n, r)
  is q_nope folded through the kv up-projection's key half, q_rope (T, n, dr)
  — and the output is returned in LATENT space (T, n, r): the caller applies
  the value half of the up-projection (exactly `inference/generate.py`'s
  absorbed decode, paged).

Per token t: positions[t] is its sequence position; it attends to pool slots
whose global kv index `page_idx * ps + offset` is <= positions[t] within its
own page table row. Page tables are dense prefixes (pages allocated in
order), so the position bound alone masks both the causal future *and*
unallocated page-table padding (which must still hold a VALID page index —
the pool's trash page — to keep gathers in bounds). positions[t] < 0 marks a
pad row: fully masked, output 0.

Multi-query-per-slot scoring rows: nothing ties a request to one row per
call — chunked prefill feeds whole chunks, and speculative decoding's
draft-then-verify (serving/engine.py) feeds a slot's pending token plus K
provisional drafts at positions p..p+K in the SAME batch. Because the
engine scatters each row's K/V into the pool BEFORE this op gathers (per
layer), a draft row at position p+j attends to the drafts before it
through the ordinary position bound — verifying a whole block costs one
call, the same bandwidth the pages cost anyway. The kernel contract is
unchanged: rows are independent given (page table row, position), so a
verify block is just more ragged rows.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from automodel_tpu.ops.attention import NEG_INF


def _gather_mask(page_tables, positions, page_size, T, P, window=None):
    """(T, P*ps) attend mask from positions (pads → all-False)."""
    kv_idx = jnp.arange(P * page_size, dtype=jnp.int32)
    mask = kv_idx[None, :] <= positions[:, None]  # causal + allocation bound
    if window is not None:
        # window == 0 → global (the layer-scan convention of generate.py)
        dist = positions[:, None] - kv_idx[None, :]
        mask = jnp.logical_and(mask, (window == 0) | (dist < window))
    return mask


def ragged_paged_attention_xla(
    q: jnp.ndarray,            # (T, Hq, D)
    k_pages: jnp.ndarray,      # (N, ps, Hkv, D)
    v_pages: jnp.ndarray,      # (N, ps, Hkv, Dv)
    page_tables: jnp.ndarray,  # (T, P) int32 — per-TOKEN page table row
    positions: jnp.ndarray,    # (T,) int32; -1 = pad row
    *,
    scale: float,
    window=None,               # traced per-layer window; 0/None = global
    soft_cap: float | None = None,
    sinks: jnp.ndarray | None = None,  # (Hq,) learned sink logits
    k_scales: jnp.ndarray | None = None,  # (N, ps) per-row dequant scales
    v_scales: jnp.ndarray | None = None,  # (int8 pages; None = fp pages)
) -> jnp.ndarray:
    """Gather-based reference; returns (T, Hq, Dv) with pad rows zeroed.
    With `k_scales`/`v_scales` the pages are int8: the gather stays on the
    cheap int8 payload (plus the tiny scale rows) and dequantization runs
    on the gathered per-token view in f32 — the CPU-testable oracle for
    the quantized Pallas kernel."""
    T, Hq, D = q.shape
    N, ps, Hkv, Dk = k_pages.shape
    P = page_tables.shape[1]
    G = Hq // Hkv
    if Dk != D:
        # keys stored wider than the head, zeros behind them
        # (serving/kv_pages.key_width): the query's zeros meet them
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Dk - D)))
        D = Dk

    # gather each token's pages → a contiguous per-token KV view
    keys = k_pages[page_tables].reshape(T, P * ps, Hkv, D)
    values = v_pages[page_tables].reshape(T, P * ps, Hkv, v_pages.shape[-1])
    if k_scales is not None:
        from automodel_tpu.ops.quant import dequantize_kv

        keys = dequantize_kv(
            keys, k_scales[page_tables].reshape(T, P * ps)
        ).astype(q.dtype)
        values = dequantize_kv(
            values, v_scales[page_tables].reshape(T, P * ps)
        ).astype(q.dtype)

    qg = q.reshape(T, Hkv, G, D)
    s = jnp.einsum("tkgd,tckd->tkgc", qg, keys, preferred_element_type=jnp.float32)
    s = s * scale
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    mask = _gather_mask(page_tables, positions, ps, T, P, window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    if sinks is not None:
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(1, Hkv, G, 1), (T, Hkv, G, 1)
        )
        s = jnp.concatenate([s, sink], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    if sinks is not None:
        p = p[..., :-1]
    # pad rows (positions < 0): every slot masked → softmax is uniform junk
    # (or all mass on the sink); zero the output explicitly
    p = jnp.where(positions[:, None, None, None] >= 0, p, 0.0)
    o = jnp.einsum("tkgc,tckd->tkgd", p.astype(values.dtype), values)
    return o.reshape(T, Hq, values.shape[-1])


def ragged_paged_mla_attention_xla(
    q_abs: jnp.ndarray,        # (T, n, r) — q_nope absorbed through W_uk
    q_rope: jnp.ndarray,       # (T, n, dr)
    c_pages: jnp.ndarray,      # (N, ps, r) kv latents
    kr_pages: jnp.ndarray,     # (N, ps, dr) shared rotated key-rope head
    page_tables: jnp.ndarray,  # (T, P)
    positions: jnp.ndarray,    # (T,)
    *,
    scale: float,
    window=None,
    c_scales: jnp.ndarray | None = None,   # (N, ps) per-row dequant scales
    kr_scales: jnp.ndarray | None = None,  # (int8 pages; None = fp pages)
) -> jnp.ndarray:
    """Absorbed-MLA reference; returns latent-space outputs (T, n, r)."""
    T, n, r = q_abs.shape
    N, ps, _ = c_pages.shape
    P = page_tables.shape[1]

    c = c_pages[page_tables].reshape(T, P * ps, r)
    kr = kr_pages[page_tables].reshape(T, P * ps, kr_pages.shape[-1])
    if c_scales is not None:
        from automodel_tpu.ops.quant import dequantize_kv

        c = dequantize_kv(
            c, c_scales[page_tables].reshape(T, P * ps)
        ).astype(q_abs.dtype)
        kr = dequantize_kv(
            kr, kr_scales[page_tables].reshape(T, P * ps)
        ).astype(q_abs.dtype)
    s = jnp.einsum("tnr,tcr->tnc", q_abs, c, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("tnd,tcd->tnc", q_rope, kr, preferred_element_type=jnp.float32)
    s = s * scale
    mask = _gather_mask(page_tables, positions, ps, T, P, window)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(positions[:, None, None] >= 0, p, 0.0)
    return jnp.einsum("tnc,tcr->tnr", p.astype(c.dtype), c)


# -- row segments: the Pallas kernels' unit of work ---------------------------
#: rows of `RowSegments.blocks`
(BLOCK_TILE, BLOCK_PAGE, BLOCK_OFFSET, BLOCK_LENGTH, BLOCK_POSITION,
 BLOCK_COLUMN) = range(6)

#: VMEM a q tile's blocks and scratch may take beside two pages of keys and
#: values (the chip's compiler gives a kernel 16 MiB unasked)
_TILE_VMEM_BYTES = 6 * 2**20
#: rows of a q tile at most: a segment longer than one row computes the
#: whole tile whatever its length, so a larger tile costs every chunk's
#: tail and every speculative block more than it saves on page fetches
_TILE_ROWS = 32


class RowSegments(NamedTuple):
    """A step's rows grouped into SEGMENTS, runs of one sequence's rows,
    and laid out as the list of (segment, page) blocks the Pallas kernels
    walk: one for every page a segment attends to, a segment's pages in
    order, nothing for a page past its last position, for a page that lies
    wholly before its first row's window, or for a pad row.
    `tile` (static) is the rows of a q tile. `blocks` is (6, W) int32,
    column w one block: the tile its segment lies in, the POOL page it
    reads, then its segment's first row as an offset into the tile, its
    length and its first row's position, and which page of the sequence
    this is. `count` () int32 says how many of the W are the step's; the
    kernels' grid stops there."""

    tile: int
    blocks: jnp.ndarray
    count: jnp.ndarray


def row_tile(rows: int, row_width: int) -> int:
    """Rows of a q tile for a step of `rows` rows whose queries and
    outputs are `row_width` elements a row: a power of two of at least 16
    (a bf16 tile's sublanes) and at most `_TILE_ROWS`, no more than the
    step's rows rounded up, and small enough that its blocks (bf16 in and
    out, double-buffered) and float32 accumulator fit `_TILE_VMEM_BYTES`."""
    fit = _TILE_VMEM_BYTES // (8 * max(row_width, 1))
    tile = 16
    while tile * 2 <= min(_TILE_ROWS, fit) and tile < rows:
        tile *= 2
    return tile


def max_row_segments(rows: int, max_slots: int, tile: int) -> int:
    """Static bound on a step's segments when every slot's rows are ONE
    run, as the scheduler lays them out: a run starts one segment and
    every tile boundary inside a run one more."""
    return min(rows, max_slots + rows // tile)


def segment_bounds(xp, slot, pos, tile: int):
    """(is_start, is_last): which rows open and which close a segment.
    `xp` is numpy (the scheduler's counters) or jax.numpy (the step). A
    row continues its predecessor's segment when both are real (pos >= 0),
    of one SLOT (two decode rows of different slots may well hold
    consecutive positions), at consecutive positions, and in one aligned
    tile of `tile` rows; pad rows belong to no segment."""
    rows = xp.arange(slot.shape[0])
    real = pos >= 0
    joins = (
        real[1:] & real[:-1]
        & (slot[1:] == slot[:-1]) & (pos[1:] == pos[:-1] + 1)
        & (rows[1:] % tile != 0)
    )
    no = xp.zeros((1,), bool)
    is_start = real & ~xp.concatenate([no, joins])
    is_last = real & ~xp.concatenate([joins, no])
    return is_start, is_last


def window_first_page(pos0, window: int, page_size: int):
    """Which page of its sequence holds the first key that a row at
    position `pos0` attends to under `window`."""
    return jnp.maximum(pos0 - window + 1, 0) // page_size


def row_segments(slot, pos, page_tables, *, page_size: int, tile: int,
                 max_segments: int, window: int | None = None,
                 max_pages: int | None = None) -> RowSegments:
    """The step's `RowSegments`, in-jit, from each row's slot, position
    and page table (T, P). More than `max_segments` runs cannot be told
    here: the caller's bound must hold (`max_row_segments`). With a
    `window` (static) a segment's blocks begin at the page of its FIRST
    row's first in-window key, the near end of what any of its rows
    attends to; `max_pages` then bounds the pages one segment can span
    (default: the table's width)."""
    P = page_tables.shape[1]
    G, W = max_segments, max_segments * min(P, max_pages or P)
    is_start, is_last = segment_bounds(jnp, slot, pos, tile)
    (start,) = jnp.nonzero(is_start, size=G, fill_value=0)
    (end,) = jnp.nonzero(is_last, size=G, fill_value=0)
    real = jnp.arange(G) < jnp.sum(is_start)
    length = jnp.where(real, end - start + 1, 0)
    # a segment's blocks
    pages = pos[end] // page_size + 1
    if window:
        first = window_first_page(pos[start], window, page_size)
        pages = pages - first
    pages = jnp.where(real, pages, 0)
    ends = jnp.cumsum(pages)
    count = ends[-1]
    # block w's segment and which of its pages; past the count, the last's
    w = jnp.minimum(jnp.arange(W), jnp.maximum(count - 1, 0))
    seg = jnp.minimum(jnp.searchsorted(ends, w, side="right"), G - 1)
    column = w - (ends[seg] - pages[seg])
    if window:
        column = jnp.minimum(first[seg] + column, P - 1)
    row = start[seg]
    blocks = jnp.stack([
        row // tile, page_tables[row, column], row % tile, length[seg],
        pos[row], column,
    ]).astype(jnp.int32)
    return RowSegments(tile=tile, blocks=blocks, count=count.astype(jnp.int32))


def step_row_segments(slot, pos, page_tables, *, page_size: int, tile: int,
                      max_slots: int, impl: str = "auto",
                      window: int | None = None, max_pages: int | None = None):
    """A serve step's `RowSegments`, once for all its attention calls of
    one kind (full, or with `window`), or None where the dispatch rule
    hands them to the reference (off the TPU: the step that CPU tests lower
    holds nothing of this). `page_tables` is per ROW, (T, P)."""
    from automodel_tpu.ops.attention import resolve_kernel_impl

    if resolve_kernel_impl(impl, "pallas", None, "paged_attention") != "pallas":
        return None
    return row_segments(
        slot, pos, page_tables, page_size=page_size, tile=tile,
        max_segments=max_row_segments(slot.shape[0], max_slots, tile),
        window=window, max_pages=max_pages,
    )


def _tp_size(mesh_ctx) -> int:
    return 1 if mesh_ctx is None else mesh_ctx.sizes["tp"]


def _annotate_tp(x, mesh_ctx, dim: int):
    """Pin `x`'s axis `dim` to tp — the ONE sharding annotation of the
    reference path (no-op without a mesh: the single-chip program stays
    byte-identical). For GQA `dim` is the head axis (every rank owns
    whole KV heads of every page, so gather + softmax + weighted sum are
    rank-local); for MLA it is the latent-rank axis (heads share one
    latent, so score/value contractions over r reduce cross-rank)."""
    if _tp_size(mesh_ctx) == 1:
        return x
    axes = [None] * x.ndim
    axes[dim] = "tp"
    return jax.lax.with_sharding_constraint(x, mesh_ctx.sharding(*axes))


def _pallas_gqa_tp(mesh_ctx, q, k_pages, v_pages, page_tables, positions, *,
                   scale, soft_cap, segments, window):
    """The Pallas GQA kernel under tp>1, inside a shard_map: each rank
    runs the SAME kernel on its local head slice — q/k/v/out shard the
    head dim, page tables, positions and row segments replicate, and the
    grid/BlockSpec machinery (scalar-prefetch page indexing, online
    softmax) is untouched because GQA groups never cross a KV-head
    boundary."""
    from jax.sharding import PartitionSpec as P

    from automodel_tpu.ops.pallas.ragged_paged_attention import (
        paged_attention_kernel,
    )

    heads = P(None, "tp", None)
    pages = P(None, None, "tp", None)

    def body(q, k, v, pt, pos, *seg):
        seg = RowSegments(segments.tile, *seg) if seg else None
        return paged_attention_kernel(
            q, k, v, pt, pos, scale=scale, soft_cap=soft_cap, segments=seg,
            window=window,
        )

    seg = () if segments is None else (segments.blocks, segments.count)
    return jax.shard_map(
        body, mesh=mesh_ctx.mesh,
        in_specs=(heads, pages, pages, P(None, None), P(None),
                  *(P(*(None,) * a.ndim) for a in seg)),
        out_specs=heads, check_vma=False,
    )(q, k_pages, v_pages, page_tables, positions, *seg)


def _gqa_unsupported_reason(q, k_pages, window, sinks, quant, tp):
    """Why the Pallas GQA kernels cannot take this call, or None."""
    Hq, Hkv = q.shape[1], k_pages.shape[2]
    if window is not None and not isinstance(window, int):
        # the kernel's block list and mask take the window as a static
        return "a traced sliding window"
    if sinks is not None and tp > 1:
        # the tp shard_map wrapper hands the kernel no sinks
        return "attention sinks under tp > 1"
    if Hq % Hkv != 0:
        return f"GQA needs Hq % Hkv == 0 (got {Hq} % {Hkv})"
    if quant and k_pages.shape[-1] > 128:
        # the int8 bodies widen a page to float32 for their products: at 64
        # heads over 4 or 8 of 256-wide keys that passes the 16 MiB of VMEM
        # a kernel is given (the chip's compiler, for a described v5e)
        return "int8 pages of keys wider than a lane tile"
    if tp > 1 and quant:
        # scales replicate while heads shard; the quantized kernel has no
        # shard_map wrapper
        return "tp-sharded int8 pages"
    if tp > 1 and (Hq % tp or Hkv % tp):
        return f"heads ({Hq}/{Hkv}) not divisible by tp={tp}"
    return None


def ragged_paged_attention(
    q, k_pages, v_pages, page_tables, positions,
    *,
    scale: float | None = None,
    window=None,
    soft_cap: float | None = None,
    sinks=None,
    impl: str = "auto",
    mesh_ctx=None,
    k_scales=None,
    v_scales=None,
    segments: RowSegments | None = None,
):
    """GQA entry. impl: "xla" | "pallas" | "auto" (pallas on TPU where the
    kernel covers the call). With a `mesh_ctx` (tp>1) the reference path
    carries head-sharding annotations and the Pallas kernel runs inside a
    shard_map over the tp axis (rank-local head slices). With
    `k_scales`/`v_scales` ((N, ps) per-row scales) the pages are int8 and
    the quantized kernel/reference dequantizes per page. `segments`
    (`step_row_segments`) is the kernel's alone: the reference reads each
    row's own table and never sees it. `window`: None or 0 for none; a
    Python int is a static of the kernel call (`segments` must then have
    been built with the same window), a traced value goes to the reference."""
    from automodel_tpu.ops.attention import resolve_kernel_impl

    scale = scale if scale is not None else float(q.shape[-1]) ** -0.5
    quant = k_scales is not None
    tp = _tp_size(mesh_ctx)
    unsupported = _gqa_unsupported_reason(
        q, k_pages, window, sinks, quant, tp
    )
    chosen = resolve_kernel_impl(impl, "pallas", unsupported, "paged_attention")
    if sinks is not None:
        from automodel_tpu.observability.metrics import default_registry

        # trace time: which calls with sinks lowered to the kernel
        default_registry().counter(
            "paged_attention_sink_total", impl=chosen).inc()
    if chosen == "pallas":
        if tp > 1:
            return _pallas_gqa_tp(
                mesh_ctx, q, k_pages, v_pages, page_tables, positions,
                scale=scale, soft_cap=soft_cap, segments=segments,
                window=window,
            )
        if quant:
            from automodel_tpu.ops.pallas.ragged_paged_attention import (
                paged_attention_quant_kernel,
            )

            return paged_attention_quant_kernel(
                q, k_pages, v_pages, k_scales, v_scales,
                page_tables, positions, scale=scale, soft_cap=soft_cap,
                segments=segments, window=window, sinks=sinks,
            )
        from automodel_tpu.ops.pallas.ragged_paged_attention import (
            paged_attention_kernel,
        )

        return paged_attention_kernel(
            q, k_pages, v_pages, page_tables, positions,
            scale=scale, soft_cap=soft_cap, segments=segments, window=window,
            sinks=sinks,
        )
    q = _annotate_tp(q, mesh_ctx, 1)              # head axis
    k_pages = _annotate_tp(k_pages, mesh_ctx, 2)
    v_pages = _annotate_tp(v_pages, mesh_ctx, 2)
    out = ragged_paged_attention_xla(
        q, k_pages, v_pages, page_tables, positions,
        scale=scale, window=window, soft_cap=soft_cap, sinks=sinks,
        k_scales=k_scales, v_scales=v_scales,
    )
    return _annotate_tp(out, mesh_ctx, 1)


def diff_row_width(Hq: int, Hkv: int, D: int) -> int:
    """A differential call's elements a row for `row_tile`: its scores run
    against a whole (ps, Hkv x D) page and its products give Hkv x D values
    a head before two value heads are picked, so a tile's VMEM grows with
    the page's width, not the head's."""
    return Hq * (Hkv * D + 4 * D)


#: the head width the differential kernel takes: two heads to a lane tile
DIFF_HEAD_DIM = 64


def _diff_unsupported_reason(q, k_pages, window, tp):
    Hq, D = q.shape[1:]
    Hkv = k_pages.shape[-1] // D
    if window is not None and not isinstance(window, int):
        return "a traced sliding window"
    if tp > 1:
        return "differential attention under tp > 1"
    if D != DIFF_HEAD_DIM or Hkv % 4 or Hq % Hkv:
        # two heads to a lane tile, the value heads' two halves each a whole
        # number of tiles, a query head's pair in the other half
        return f"differential heads of {D} ({Hq} over {Hkv})"
    return None


def ragged_paged_diff_attention(
    q, k_pages, v_pages, page_tables, positions,
    *,
    scale: float,
    window=None,
    impl: str = "auto",
    mesh_ctx=None,
    segments: RowSegments | None = None,
):
    """Differential attention's maps over MERGED pages, (N, ps, Hkv x D) keys
    and values (serving/kv_pages.py: the heads side by side in a page's minor
    dimension, no lane padding at D = 64). q (T, Hq, D); query head i scores
    against key head i // (Hq / Hkv), and its map weighs the 2D-wide value of
    `decoder.diff_values`, read out of the page it already holds: no second
    copy of the values. Returns (T, Hq, 2D), the pairs not yet combined
    (`decoder.diff_combine`). Same dispatch contract as the GQA entry; the
    kernel's call is named `paged_attention_diff_gqa`, or
    `paged_attention_diff_window_gqa` with a window."""
    from automodel_tpu.ops.attention import resolve_kernel_impl
    from automodel_tpu.observability.metrics import default_registry

    unsupported = _diff_unsupported_reason(q, k_pages, window, _tp_size(mesh_ctx))
    chosen = resolve_kernel_impl(impl, "pallas", unsupported, "paged_attention")
    # trace time: which differential calls lowered to the kernel
    default_registry().counter("paged_attention_diff_total", impl=chosen).inc()
    if chosen == "pallas":
        from automodel_tpu.ops.pallas.ragged_paged_attention import (
            paged_attention_diff_kernel,
        )

        return paged_attention_diff_kernel(
            q, k_pages, v_pages, page_tables, positions, scale=scale,
            segments=segments, window=window)
    from automodel_tpu.models.llm.decoder import diff_values

    N, ps, _ = k_pages.shape
    D = q.shape[-1]
    return ragged_paged_attention_xla(
        q, k_pages.reshape(N, ps, -1, D),
        diff_values(v_pages.reshape(N, ps, -1, D)), page_tables, positions,
        scale=scale, window=window)


def _mla_unsupported_reason(window, tp):
    """Why the Pallas MLA kernels cannot take this call, or None."""
    if window is not None:
        return "sliding windows"
    if tp > 1:
        # the latent rank r is the sharded dim: the score contraction
        # reduces over r across ranks, which a rank-local online softmax
        # cannot express
        return "latent-sharded MLA (tp > 1)"
    return None


def ragged_paged_mla_attention(
    q_abs, q_rope, c_pages, kr_pages, page_tables, positions,
    *,
    scale: float,
    window=None,
    impl: str = "auto",
    mesh_ctx=None,
    c_scales=None,
    kr_scales=None,
    segments: RowSegments | None = None,
):
    """MLA (absorbed latent-cache) entry; same dispatch contract as the GQA
    one. Returns latent-space outputs (T, n, r). Under tp>1 the latent rank
    r is the sharded dim (q_abs/c_pages/out; the tiny shared rope head
    replicates) and the annotated XLA reference serves the call."""
    from automodel_tpu.ops.attention import resolve_kernel_impl

    unsupported = _mla_unsupported_reason(window, _tp_size(mesh_ctx))
    if resolve_kernel_impl(
        impl, "pallas", unsupported, "paged_mla_attention"
    ) == "pallas":
        if c_scales is not None:
            from automodel_tpu.ops.pallas.ragged_paged_attention import (
                paged_mla_attention_quant_kernel,
            )

            return paged_mla_attention_quant_kernel(
                q_abs, q_rope, c_pages, kr_pages, c_scales, kr_scales,
                page_tables, positions, scale=scale, segments=segments,
            )
        from automodel_tpu.ops.pallas.ragged_paged_attention import (
            paged_mla_attention_kernel,
        )

        return paged_mla_attention_kernel(
            q_abs, q_rope, c_pages, kr_pages, page_tables, positions,
            scale=scale, segments=segments,
        )
    q_abs = _annotate_tp(q_abs, mesh_ctx, 2)      # latent-rank axis
    c_pages = _annotate_tp(c_pages, mesh_ctx, 2)
    out = ragged_paged_mla_attention_xla(
        q_abs, q_rope, c_pages, kr_pages, page_tables, positions,
        scale=scale, window=window,
        c_scales=c_scales, kr_scales=kr_scales,
    )
    return _annotate_tp(out, mesh_ctx, 2)
