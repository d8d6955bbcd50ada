"""Pallas TPU kernel for ragged paged attention (serving decode/prefill).

The TPU backend of `ops/paged_attention.py` (arXiv:2604.15464 style): the
grid is (token, page) and the PAGE TABLE drives the kv BlockSpec index map
through scalar prefetch — page j of token t's sequence is DMA'd from
`k_pages[page_tables[t, j]]` directly, so the kernel never materializes the
gathered (T, P, page_size, ...) intermediate the XLA reference builds in
HBM. Pages are streamed innermost with the usual online-softmax (m, l, acc)
VMEM scratch carried across pages (the flash_attention.py recipe), and
pages past a token's position are predicated off with `pl.when` (they still
prefetch — the table's padded entries must point at a valid page index, the
pool's trash page).

Covers the serving engine's hot path: GQA (kv-head sharing via reshape, no
KV repeat) and absorbed-MLA (scores latent + rope parts summed in one
accumulator, output in latent space). Sliding windows and attention sinks
are not covered: the dispatcher (ops/paged_attention.py) states the rules
and never calls in here with them.

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

from automodel_tpu.ops.pallas.flash_attention import LANE, NEG_INF, _pad_last


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _gqa_kernel(
    pt_ref,    # (T, P) scalar-prefetch page table
    pos_ref,   # (T,)   scalar-prefetch positions (-1 = pad row)
    q_ref,     # (1, Hq, D)
    k_ref,     # (1, ps, Hkv, D)
    v_ref,     # (1, ps, Hkv, Dv)
    out_ref,   # (1, Hq, Dv)
    m_scr, l_scr, acc_scr,
    *,
    scale, soft_cap, page_size, groups,
):
    t, j = pl.program_id(0), pl.program_id(1)
    np_ = pl.num_programs(1)
    pos = pos_ref[t]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # pages whose first slot is past the token's position hold nothing it
    # may attend to (tables are dense prefixes); pad rows (pos < 0) skip all
    run = jnp.logical_and(pos >= 0, j * page_size <= pos)

    @pl.when(run)
    def _body():
        q = q_ref[0]                     # (Hq, D)
        k = k_ref[0]                     # (ps, Hkv, D)
        v = v_ref[0]                     # (ps, Hkv, Dv)
        Hq, D = q.shape
        ps, Hkv, Dv = v.shape
        qg = q.reshape(Hkv, groups, D)
        # (Hkv, G, ps): contract D, batch over kv heads
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        ) * scale
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        # the causal mask is made on the (Hq, ps) scores: on (Hkv, groups,
        # ps) a model without grouping (groups == 1: one query head per
        # key/value head) hands Mosaic a compare with a one-wide sublane
        # axis, which the TPU compiler refuses (LLO_CHECK ProducesVreg)
        s = s.reshape(Hq, ps)
        kv_idx = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (Hq, ps), 1
        )
        mask = kv_idx <= pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape,
        )
        # (Hq, Dv) += (Hkv, G, ps) @ (ps, Hkv, Dv) batched over kv heads
        pv = jax.lax.dot_general(
            p.reshape(Hkv, groups, ps).astype(v.dtype), v,
            (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv.reshape(Hq, Dv)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == np_ - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = jnp.where(l == 0.0, 0.0, acc_scr[:] / l_safe)
        out_ref[0] = out.astype(out_ref.dtype)


def paged_attention_kernel(
    q, k_pages, v_pages, page_tables, positions,
    *,
    scale: float,
    soft_cap: float | None = None,
):
    """GQA ragged paged attention; q (T, Hq, D), pages (N, ps, Hkv, D[v])."""
    T, Hq, D = q.shape
    N, ps, Hkv, Dv = v_pages.shape
    P = page_tables.shape[1]
    G = Hq // Hkv

    qp = _pad_last(q, LANE)
    kp = _pad_last(k_pages, LANE)
    vp = _pad_last(v_pages, LANE)
    Dp, Dvp = qp.shape[-1], vp.shape[-1]

    kernel = functools.partial(
        _gqa_kernel, scale=scale, soft_cap=soft_cap, page_size=ps, groups=G,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, P),
        in_specs=[
            pl.BlockSpec((1, Hq, Dp), lambda t, j, pt, pos: (t, 0, 0)),
            pl.BlockSpec((1, ps, Hkv, Dp), lambda t, j, pt, pos: (pt[t, j], 0, 0, 0)),
            pl.BlockSpec((1, ps, Hkv, Dvp), lambda t, j, pt, pos: (pt[t, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, Dvp), lambda t, j, pt, pos: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, LANE), jnp.float32),
            pltpu.VMEM((Hq, LANE), jnp.float32),
            pltpu.VMEM((Hq, Dvp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, Hq, Dvp), q.dtype),
        interpret=_interpret(),
        name="paged_attention_gqa",
    )(page_tables.astype(jnp.int32), positions.astype(jnp.int32), qp, kp, vp)
    return out[..., :Dv]


def _gqa_quant_kernel(
    pt_ref,    # (T, P) scalar-prefetch page table
    pos_ref,   # (T,)   scalar-prefetch positions (-1 = pad row)
    q_ref,     # (1, Hq, D)
    k_ref,     # (1, ps, Hkv, D)  int8
    v_ref,     # (1, ps, Hkv, Dv) int8
    ks_ref,    # (1, 1, ps) f32 per-row K scales of THIS page
    vs_ref,    # (1, 1, ps) f32 per-row V scales
    out_ref,   # (1, Hq, Dv)
    m_scr, l_scr, acc_scr,
    *,
    scale, soft_cap, page_size, groups,
):
    """The int8 variant of `_gqa_kernel`: identical grid/online-softmax
    machinery, but pages arrive quantized and the per-page scale rows ride
    the SAME scalar-prefetch page table (`pt[t, j]` indexes payload and
    scale blocks alike). Dequantization is algebraic per page: the K scale
    multiplies each kv slot's score column, the V scale folds into the
    softmax weights before the value product — the big int8 blocks are
    cast once for the MXU dots, never materialized dequantized in HBM."""
    t, j = pl.program_id(0), pl.program_id(1)
    np_ = pl.num_programs(1)
    pos = pos_ref[t]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = jnp.logical_and(pos >= 0, j * page_size <= pos)

    @pl.when(run)
    def _body():
        q = q_ref[0]                              # (Hq, D)
        k = k_ref[0].astype(jnp.float32)          # (ps, Hkv, D)
        v = v_ref[0].astype(jnp.float32)          # (ps, Hkv, Dv)
        ks = ks_ref[...]                          # (1, 1, ps) per-slot K scales
        vs = vs_ref[...]
        Hq, D = q.shape
        ps, Hkv, Dv = v.shape
        qg = q.reshape(Hkv, groups, D).astype(jnp.float32)
        # (Hkv, G, ps): contract D, batch over kv heads; the per-row K
        # scale lands on the score column of its kv slot (before any
        # soft-cap nonlinearity)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        ) * ks * scale
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        # the mask on the 2-D scores, as in `_gqa_kernel` (and why)
        s = s.reshape(Hq, ps)
        kv_idx = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (Hq, ps), 1
        )
        mask = kv_idx <= pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape,
        )
        # V dequant folds into the weights: (p * vs) @ v_int8 == p @ v_fp
        pv = jax.lax.dot_general(
            (p.reshape(Hkv, groups, ps) * vs), v,
            (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv.reshape(Hq, Dv)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == np_ - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = jnp.where(l == 0.0, 0.0, acc_scr[:] / l_safe)
        out_ref[0] = out.astype(out_ref.dtype)


def paged_attention_quant_kernel(
    q, k_pages, v_pages, k_scales, v_scales, page_tables, positions,
    *,
    scale: float,
    soft_cap: float | None = None,
):
    """GQA ragged paged attention over int8 pages with (N, ps) per-row
    scales; same contract as `paged_attention_kernel`."""
    T, Hq, D = q.shape
    N, ps, Hkv, Dv = v_pages.shape
    P = page_tables.shape[1]
    G = Hq // Hkv

    qp = _pad_last(q, LANE)
    kp = _pad_last(k_pages, LANE)
    vp = _pad_last(v_pages, LANE)
    Dp, Dvp = qp.shape[-1], vp.shape[-1]

    kernel = functools.partial(
        _gqa_quant_kernel,
        scale=scale, soft_cap=soft_cap, page_size=ps, groups=G,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, P),
        in_specs=[
            pl.BlockSpec((1, Hq, Dp), lambda t, j, pt, pos: (t, 0, 0)),
            pl.BlockSpec((1, ps, Hkv, Dp), lambda t, j, pt, pos: (pt[t, j], 0, 0, 0)),
            pl.BlockSpec((1, ps, Hkv, Dvp), lambda t, j, pt, pos: (pt[t, j], 0, 0, 0)),
            pl.BlockSpec((1, 1, ps), lambda t, j, pt, pos: (pt[t, j], 0, 0)),
            pl.BlockSpec((1, 1, ps), lambda t, j, pt, pos: (pt[t, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, Dvp), lambda t, j, pt, pos: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, LANE), jnp.float32),
            pltpu.VMEM((Hq, LANE), jnp.float32),
            pltpu.VMEM((Hq, Dvp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, Hq, Dvp), q.dtype),
        interpret=_interpret(),
        name="paged_attention_gqa_int8",
    )(
        page_tables.astype(jnp.int32), positions.astype(jnp.int32),
        qp, kp, vp,
        # (N, 1, ps): a (1, ps) block of an (N, ps) array breaks Mosaic's
        # rule that a block's second-to-last dim is a multiple of 8 or the
        # whole axis
        k_scales.astype(jnp.float32)[:, None, :],
        v_scales.astype(jnp.float32)[:, None, :],
    )
    return out[..., :Dv]


def _mla_kernel(
    pt_ref, pos_ref,
    qa_ref,    # (1, n, r)
    qr_ref,    # (1, n, dr)
    c_ref,     # (1, ps, r)
    kr_ref,    # (1, ps, dr)
    out_ref,   # (1, n, r)
    m_scr, l_scr, acc_scr,
    *,
    scale, page_size,
):
    t, j = pl.program_id(0), pl.program_id(1)
    np_ = pl.num_programs(1)
    pos = pos_ref[t]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = jnp.logical_and(pos >= 0, j * page_size <= pos)

    @pl.when(run)
    def _body():
        qa = qa_ref[0]   # (n, r)
        qr = qr_ref[0]   # (n, dr)
        c = c_ref[0]     # (ps, r)
        kr = kr_ref[0]   # (ps, dr)
        n = qa.shape[0]
        ps = c.shape[0]
        # absorbed scores: latent part + rope part share one accumulator
        s = jax.lax.dot_general(
            qa, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s + jax.lax.dot_general(
            qr, kr, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        kv_idx = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (n, ps), 1)
        mask = kv_idx <= pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape,
        )
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == np_ - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = jnp.where(l == 0.0, 0.0, acc_scr[:] / l_safe)
        out_ref[0] = out.astype(out_ref.dtype)


def paged_mla_attention_kernel(
    q_abs, q_rope, c_pages, kr_pages, page_tables, positions,
    *,
    scale: float,
):
    """Absorbed-MLA ragged paged attention; returns latent outputs (T, n, r)."""
    T, n, r = q_abs.shape
    N, ps, _ = c_pages.shape
    P, dr = page_tables.shape[1], q_rope.shape[-1]

    kernel = functools.partial(_mla_kernel, scale=scale, page_size=ps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, P),
        in_specs=[
            pl.BlockSpec((1, n, r), lambda t, j, pt, pos: (t, 0, 0)),
            pl.BlockSpec((1, n, dr), lambda t, j, pt, pos: (t, 0, 0)),
            pl.BlockSpec((1, ps, r), lambda t, j, pt, pos: (pt[t, j], 0, 0)),
            pl.BlockSpec((1, ps, dr), lambda t, j, pt, pos: (pt[t, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, r), lambda t, j, pt, pos: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, LANE), jnp.float32),
            pltpu.VMEM((n, LANE), jnp.float32),
            pltpu.VMEM((n, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, n, r), q_abs.dtype),
        interpret=_interpret(),
        name="paged_attention_mla",
    )(
        page_tables.astype(jnp.int32), positions.astype(jnp.int32),
        q_abs, q_rope, c_pages, kr_pages,
    )


def _mla_quant_kernel(
    pt_ref, pos_ref,
    qa_ref,    # (1, n, r)
    qr_ref,    # (1, n, dr)
    c_ref,     # (1, ps, r)  int8
    kr_ref,    # (1, ps, dr) int8
    cs_ref,    # (1, 1, ps) f32 per-row latent scales of THIS page
    krs_ref,   # (1, 1, ps) f32 per-row rope scales
    out_ref,   # (1, n, r)
    m_scr, l_scr, acc_scr,
    *,
    scale, page_size,
):
    """int8 variant of `_mla_kernel`: the latent and rope score parts
    carry DIFFERENT per-row scales (two cached quantities, two scale
    arrays), so each is applied to its dot before the parts sum into the
    shared accumulator; the latent scale folds into the softmax weights
    for the value product (values ARE the latent pages)."""
    t, j = pl.program_id(0), pl.program_id(1)
    np_ = pl.num_programs(1)
    pos = pos_ref[t]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = jnp.logical_and(pos >= 0, j * page_size <= pos)

    @pl.when(run)
    def _body():
        qa = qa_ref[0].astype(jnp.float32)    # (n, r)
        qr = qr_ref[0].astype(jnp.float32)    # (n, dr)
        c = c_ref[0].astype(jnp.float32)      # (ps, r)
        kr = kr_ref[0].astype(jnp.float32)    # (ps, dr)
        cs = cs_ref[0]                        # (1, ps)
        krs = krs_ref[0]
        n = qa.shape[0]
        ps = c.shape[0]
        s = jax.lax.dot_general(
            qa, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cs
        s = s + jax.lax.dot_general(
            qr, kr, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * krs
        s = s * scale
        kv_idx = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (n, ps), 1)
        mask = kv_idx <= pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape,
        )
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p * cs, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == np_ - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = jnp.where(l == 0.0, 0.0, acc_scr[:] / l_safe)
        out_ref[0] = out.astype(out_ref.dtype)


def paged_mla_attention_quant_kernel(
    q_abs, q_rope, c_pages, kr_pages, c_scales, kr_scales,
    page_tables, positions,
    *,
    scale: float,
):
    """Absorbed-MLA ragged paged attention over int8 latent/rope pages
    with (N, ps) per-row scales; same contract as
    `paged_mla_attention_kernel`."""
    T, n, r = q_abs.shape
    N, ps, _ = c_pages.shape
    P, dr = page_tables.shape[1], q_rope.shape[-1]

    kernel = functools.partial(_mla_quant_kernel, scale=scale, page_size=ps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, P),
        in_specs=[
            pl.BlockSpec((1, n, r), lambda t, j, pt, pos: (t, 0, 0)),
            pl.BlockSpec((1, n, dr), lambda t, j, pt, pos: (t, 0, 0)),
            pl.BlockSpec((1, ps, r), lambda t, j, pt, pos: (pt[t, j], 0, 0)),
            pl.BlockSpec((1, ps, dr), lambda t, j, pt, pos: (pt[t, j], 0, 0)),
            pl.BlockSpec((1, 1, ps), lambda t, j, pt, pos: (pt[t, j], 0, 0)),
            pl.BlockSpec((1, 1, ps), lambda t, j, pt, pos: (pt[t, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, r), lambda t, j, pt, pos: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, LANE), jnp.float32),
            pltpu.VMEM((n, LANE), jnp.float32),
            pltpu.VMEM((n, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, n, r), q_abs.dtype),
        interpret=_interpret(),
        name="paged_attention_mla_int8",
    )(
        page_tables.astype(jnp.int32), positions.astype(jnp.int32),
        q_abs, q_rope, c_pages, kr_pages,
        # (N, 1, ps) for the same block-shape rule as the GQA int8 kernel
        c_scales.astype(jnp.float32)[:, None, :],
        kr_scales.astype(jnp.float32)[:, None, :],
    )
