"""Pallas TPU flash attention — forward + custom-VJP backward.

The TPU-native replacement for the reference's attention kernel stack
(reference: TE `DotProductAttention` injection, nemo_automodel/_transformers/
te_attention.py; FlexAttention block-mask wrapper, components/attention/
flex_attention.py:32). One kernel family covers the mask zoo the reference
spreads across TE/flex/FFPA backends:

- causal by global token index (the default; valid for packed per-document
  positions, since within a segment document order == global order and
  cross-segment pairs are killed by the segment mask),
- causal by POSITION (q/kv carry independent global positions — the ring
  attention mode, where visiting kv blocks come from other cp ranks),
- packed-sequence segment ids (the THD/cu_seqlens analog),
- sliding windows, static or TRACED (a traced window — e.g. selected per
  layer inside a `lax.scan` — is folded into the per-token `qwin` aux array
  host-side, so the kernel itself never branches on it),
- attention sinks (gpt-oss): the sink joins the softmax denominator but
  contributes no value, so it is exactly a host-side rescale of the no-sink
  kernel output by sigmoid(lse - sink); the VJP stays exact because the
  residuals store the sink-adjusted (out, lse) — see `_flash_bwd`,
- attention logit soft-capping (gemma style),
- GQA (kv-head sharing via block index maps, no KV repeat materialized),
- MLA-shaped heads: v's head_dim may differ from q/k's, and head dims that
  are not lane multiples (64, 96, 192) are zero-padded to the next multiple
  of 128 host-side (differentiable; pad lanes contribute zero logits).

The public entry can also return the per-row logsumexp with a full VJP
(cotangents on lse fold into the kernel's delta term), which is what lets
ring attention merge per-step partials differentiably.

Implementation notes:
- Internally (B, H, S, D) layout so blocks satisfy the TPU (8,128) tiling
  rule; per-token int arrays carry an 8-wide trailing/leading broadcast dim
  (compact in HBM, padded only in VMEM).
- Online-softmax forward on a (batch, q_head, q_block, kv_block) grid; the
  kv dimension is innermost so VMEM scratch carries (m, l, acc) across kv
  steps; blocks above the causal diagonal are predicated off with pl.when.
- Backward splits dq (grid over q blocks, scan kv) and dk/dv (grid over kv
  blocks, scan q-heads-in-group × q blocks) — each output is written by
  exactly one grid cell, the standard TPU flash backward decomposition.
- Saves (out, logsumexp) from forward; backward recomputes p block-wise
  (flops-for-memory, same trade as the reference's Triton kernels).
- Runs on CPU via interpret mode for unit-test parity against the XLA
  oracle in ops/attention.py.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
# sentinel the kernel writes into lse for fully-masked rows: keeps backward's
# p = exp(s - lse) at exp(-huge) = 0 instead of NaN
EMPTY_LSE = -NEG_INF
LANE = 128
SUBLANE = 8


@dataclasses.dataclass(frozen=True)
class BlockSizes:
    block_q: int = 512
    block_kv: int = 512
    block_q_dq: int = 512
    block_kv_dkv: int = 512


def _pick_block(seq: int, want: int) -> int:
    """Largest multiple of LANE that divides seq, capped at `want`."""
    best = 0
    b = LANE
    while b <= min(seq, want):
        if seq % b == 0:
            best = b
        b += LANE
    return best


def _pad_last(x, multiple: int):
    d = x.shape[-1]
    pad = (-d) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def _block_mask(iq, ik, qpos_col, qwin_col, kpos_row, qseg_col, kseg_row,
                *, causal_mode, has_window, block_q, block_kv):
    """(BQ, BK) boolean mask from column/row-shaped aux vectors."""
    mask = qseg_col == kseg_row
    if causal_mode == "index":
        qi = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        ki = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = jnp.logical_and(mask, qi >= ki)
    elif causal_mode == "position":
        mask = jnp.logical_and(mask, qpos_col >= kpos_row)
    if has_window:
        # qwin = qpos - window + 1 (host-computed, so `window` may be traced)
        mask = jnp.logical_and(mask, kpos_row >= qwin_col)
        if causal_mode is None:
            # bidirectional local attention: two-sided window. The upper
            # bound qpos + window - 1 == 2*qpos - qwin needs no extra aux.
            mask = jnp.logical_and(mask, kpos_row <= 2 * qpos_col - qwin_col)
    return mask


def _run_predicate(iq, ik, *, causal_mode, skip_window, block_q, block_kv):
    """Whether this (q_block, kv_block) cell can contain any unmasked pair.

    Block skipping needs static info: only index-causal (global order) and a
    static-int window over monotonic positions qualify; everything else runs
    every block and relies on the in-block mask.
    """
    run = jnp.bool_(True)
    if causal_mode == "index":
        run = jnp.logical_and(run, (iq + 1) * block_q - 1 >= ik * block_kv)
    if skip_window is not None:
        # skip_window is only ever set for monotonic positions (qpos == kpos
        # == arange), so the bounds hold for non-causal windows too
        run = jnp.logical_and(
            run, (ik + 1) * block_kv - 1 >= iq * block_q - skip_window
        )
        if causal_mode is None:
            run = jnp.logical_and(
                run, ik * block_kv <= (iq + 1) * block_q - 1 + skip_window
            )
    return run


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------
def _fwd_kernel(
    qpos_ref,  # (1, BQ, 8)
    qwin_ref,  # (1, BQ, 8)
    qseg_ref,  # (1, BQ, 8)
    kpos_ref,  # (1, 8, BK)
    kseg_ref,  # (1, 8, BK)
    q_ref,     # (1, 1, BQ, D)
    k_ref,     # (1, 1, BK, D)
    v_ref,     # (1, 1, BK, Dv)
    out_ref,   # (1, 1, BQ, Dv)
    lse_ref,   # (1, 1, BQ, 8)
    m_scr, l_scr, acc_scr,
    *,
    scale, causal_mode, has_window, skip_window, soft_cap, block_q, block_kv,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = _run_predicate(iq, ik, causal_mode=causal_mode, skip_window=skip_window,
                         block_q=block_q, block_kv=block_kv)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        mask = _block_mask(
            iq, ik,
            qpos_ref[0, :, :1], qwin_ref[0, :, :1], kpos_ref[0, :1, :],
            qseg_ref[0, :, :1], kseg_ref[0, :1, :],
            causal_mode=causal_mode, has_window=has_window,
            block_q=block_q, block_kv=block_kv,
        )
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # (BQ, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # explicit re-mask: a fully-masked row has m_new == NEG_INF and
        # exp(s - m_new) == 1 for every (masked) entry — zero those out
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        m = m_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_scr[:] / l_safe
        out = jnp.where(l == 0.0, 0.0, out)
        out_ref[0, 0, :, :] = out.astype(out_ref.dtype)
        lse = jnp.where(l == 0.0, EMPTY_LSE, m + jnp.log(l_safe))
        lse_ref[0, 0, :, :] = jnp.broadcast_to(lse, lse_ref.shape[2:])


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------
def _recompute_p_ds(q, k, v, do, lse_col, delta_col, mask, *, scale, soft_cap):
    """Shared bwd math: p (softmax probs) and grad wrt the pre-scale scores."""
    s_raw = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if soft_cap is not None:
        t = jnp.tanh(s_raw / soft_cap)
        s = soft_cap * t
    else:
        s = s_raw
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse_col)  # (BQ, BK); masked → 0
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta_col)
    if soft_cap is not None:
        ds = ds * (1.0 - t * t)
    ds = jnp.where(mask, ds, 0.0)
    return p, ds * scale


def _dq_kernel(
    qpos_ref, qwin_ref, qseg_ref, kpos_ref, kseg_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_scr,
    *,
    scale, causal_mode, has_window, skip_window, soft_cap, block_q, block_kv,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = _run_predicate(iq, ik, causal_mode=causal_mode, skip_window=skip_window,
                         block_q=block_q, block_kv=block_kv)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        mask = _block_mask(
            iq, ik,
            qpos_ref[0, :, :1], qwin_ref[0, :, :1], kpos_ref[0, :1, :],
            qseg_ref[0, :, :1], kseg_ref[0, :1, :],
            causal_mode=causal_mode, has_window=has_window,
            block_q=block_q, block_kv=block_kv,
        )
        _, ds = _recompute_p_ds(
            q, k, v, do, lse_ref[0, 0, :, :1], delta_ref[0, 0, :, :1], mask,
            scale=scale, soft_cap=soft_cap,
        )
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    qpos_ref, qwin_ref, qseg_ref, kpos_ref, kseg_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *,
    scale, causal_mode, has_window, skip_window, soft_cap, block_q, block_kv,
):
    # grid: (B, Hkv, nk, G, nq) — accumulate over group members and q blocks
    ik, g, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    ng, nq = pl.num_programs(3), pl.num_programs(4)

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = _run_predicate(iq, ik, causal_mode=causal_mode, skip_window=skip_window,
                         block_q=block_q, block_kv=block_kv)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        mask = _block_mask(
            iq, ik,
            qpos_ref[0, :, :1], qwin_ref[0, :, :1], kpos_ref[0, :1, :],
            qseg_ref[0, :, :1], kseg_ref[0, :1, :],
            causal_mode=causal_mode, has_window=has_window,
            block_q=block_q, block_kv=block_kv,
        )
        p, ds = _recompute_p_ds(
            q, k, v, do, lse_ref[0, 0, :, :1], delta_ref[0, 0, :, :1], mask,
            scale=scale, soft_cap=soft_cap,
        )
        # dv += p^T @ do ; dk += ds^T @ q
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(g == ng - 1, iq == nq - 1))
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers (public layout: B, S, H, D)
# ---------------------------------------------------------------------------
def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _aux_q(a, B, S):
    return jnp.broadcast_to(a.astype(jnp.int32)[:, :, None], (B, S, SUBLANE))


def _aux_kv(a, B, T):
    return jnp.broadcast_to(a.astype(jnp.int32)[:, None, :], (B, SUBLANE, T))


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13, 14))
def _flash(q, k, v, sinks, qpos, qwin, qseg, kpos, kseg,
           causal_mode, has_window, skip_window, soft_cap, scale, block_sizes):
    out, lse_pub, _ = _flash_fwd_impl(
        q, k, v, sinks, qpos, qwin, qseg, kpos, kseg,
        causal_mode, has_window, skip_window, soft_cap, scale, block_sizes,
    )
    return out, lse_pub


def _flash_fwd_impl(q, k, v, sinks, qpos, qwin, qseg, kpos, kseg,
                    causal_mode, has_window, skip_window, soft_cap, scale,
                    block_sizes):
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    BQ = _pick_block(S, block_sizes.block_q)
    BK = _pick_block(T, block_sizes.block_kv)
    nq, nk = S // BQ, T // BK

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale, causal_mode=causal_mode, has_window=has_window,
        skip_window=skip_window, soft_cap=soft_cap, block_q=BQ, block_kv=BK,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, SUBLANE), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, BQ, SUBLANE), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, BQ, SUBLANE), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, SUBLANE, BK), lambda b, h, iq, ik: (b, 0, ik)),
            pl.BlockSpec((1, SUBLANE, BK), lambda b, h, iq, ik: (b, 0, ik)),
            pl.BlockSpec((1, 1, BQ, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, BK, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, BK, Dv), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, BQ, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, BQ, SUBLANE), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, S, SUBLANE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BQ, LANE), jnp.float32),
            pltpu.VMEM((BQ, LANE), jnp.float32),
            pltpu.VMEM((BQ, Dv), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(qpos, qwin, qseg, kpos, kseg, q, k, v)

    lse_row = lse[..., 0]                                # (B, Hq, S)
    empty = lse_row >= 0.5 * EMPTY_LSE
    lse_pub = jnp.where(empty, NEG_INF, lse_row)
    if sinks is not None:
        # sink joins the denominator only: rescale out, lift lse. For a fully
        # masked row all mass goes to the sink → out stays 0, lse becomes sink.
        sink_b = sinks.astype(jnp.float32).reshape(1, Hq, 1)
        lse_tot = jnp.logaddexp(lse_pub, sink_b)
        out = (
            out.astype(jnp.float32) * jnp.exp(lse_pub - lse_tot)[..., None]
        ).astype(out.dtype)
        lse_pub = lse_tot
    # residual for the bwd kernels: fully-masked rows keep the +huge sentinel
    # so p = exp(s - lse) underflows to 0 instead of NaN
    lse_res = jnp.where(empty, EMPTY_LSE, lse_pub)
    return out, lse_pub, lse_res


def _flash_fwd(q, k, v, sinks, qpos, qwin, qseg, kpos, kseg,
               causal_mode, has_window, skip_window, soft_cap, scale,
               block_sizes):
    out, lse_pub, lse_res = _flash_fwd_impl(
        q, k, v, sinks, qpos, qwin, qseg, kpos, kseg,
        causal_mode, has_window, skip_window, soft_cap, scale, block_sizes,
    )
    res = (q, k, v, sinks, qpos, qwin, qseg, kpos, kseg, out, lse_pub, lse_res)
    return (out, lse_pub), res


def _flash_bwd(causal_mode, has_window, skip_window, soft_cap, scale,
               block_sizes, res, cts):
    dout, dlse = cts
    q, k, v, sinks, qpos, qwin, qseg, kpos, kseg, out, lse_pub, lse_res = res
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    BQ = _pick_block(S, block_sizes.block_q_dq)
    BK = _pick_block(T, block_sizes.block_kv_dkv)
    nq, nk = S // BQ, T // BK

    # delta = rowsum(dout * out) - dlse: the standard correction term, plus
    # the lse cotangent folded in (d lse / d s_i = p_i, so it rides the same
    # p * (… - delta) expression in the kernels)
    dout = dout.astype(jnp.float32)
    delta = jnp.einsum("bhsd,bhsd->bhs", dout, out.astype(jnp.float32))
    delta = delta - dlse.astype(jnp.float32)
    delta_b = jnp.broadcast_to(delta[..., None], (B, Hq, S, SUBLANE))
    lse_b = jnp.broadcast_to(lse_res[..., None], (B, Hq, S, SUBLANE))
    dout = dout.astype(q.dtype)

    common = dict(
        scale=scale, causal_mode=causal_mode, has_window=has_window,
        skip_window=skip_window, soft_cap=soft_cap, block_q=BQ, block_kv=BK,
    )
    aux_specs_q = [
        pl.BlockSpec((1, BQ, SUBLANE), lambda b, h, iq, ik: (b, iq, 0)),
        pl.BlockSpec((1, BQ, SUBLANE), lambda b, h, iq, ik: (b, iq, 0)),
        pl.BlockSpec((1, BQ, SUBLANE), lambda b, h, iq, ik: (b, iq, 0)),
        pl.BlockSpec((1, SUBLANE, BK), lambda b, h, iq, ik: (b, 0, ik)),
        pl.BlockSpec((1, SUBLANE, BK), lambda b, h, iq, ik: (b, 0, ik)),
    ]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(B, Hq, nq, nk),
        in_specs=aux_specs_q + [
            pl.BlockSpec((1, 1, BQ, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, BK, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, BK, Dv), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, BQ, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, BQ, SUBLANE), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, BQ, SUBLANE), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BQ, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((BQ, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_attention_dq",
    )(qpos, qwin, qseg, kpos, kseg, q, k, v, dout, lse_b, delta_b)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(B, Hkv, nk, G, nq),
        in_specs=[
            pl.BlockSpec((1, BQ, SUBLANE), lambda b, hk, ik, g, iq: (b, iq, 0)),
            pl.BlockSpec((1, BQ, SUBLANE), lambda b, hk, ik, g, iq: (b, iq, 0)),
            pl.BlockSpec((1, BQ, SUBLANE), lambda b, hk, ik, g, iq: (b, iq, 0)),
            pl.BlockSpec((1, SUBLANE, BK), lambda b, hk, ik, g, iq: (b, 0, ik)),
            pl.BlockSpec((1, SUBLANE, BK), lambda b, hk, ik, g, iq: (b, 0, ik)),
            pl.BlockSpec((1, 1, BQ, D), lambda b, hk, ik, g, iq: (b, hk * G + g, iq, 0)),
            pl.BlockSpec((1, 1, BK, D), lambda b, hk, ik, g, iq: (b, hk, ik, 0)),
            pl.BlockSpec((1, 1, BK, Dv), lambda b, hk, ik, g, iq: (b, hk, ik, 0)),
            pl.BlockSpec((1, 1, BQ, Dv), lambda b, hk, ik, g, iq: (b, hk * G + g, iq, 0)),
            pl.BlockSpec((1, 1, BQ, SUBLANE), lambda b, hk, ik, g, iq: (b, hk * G + g, iq, 0)),
            pl.BlockSpec((1, 1, BQ, SUBLANE), lambda b, hk, ik, g, iq: (b, hk * G + g, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, BK, D), lambda b, hk, ik, g, iq: (b, hk, ik, 0)),
            pl.BlockSpec((1, 1, BK, Dv), lambda b, hk, ik, g, iq: (b, hk, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((BK, D), jnp.float32),
            pltpu.VMEM((BK, Dv), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_dkv",
    )(qpos, qwin, qseg, kpos, kseg, q, k, v, dout, lse_b, delta_b)

    dsinks = None
    if sinks is not None:
        # d sink = p_sink * (0 - delta_tot + dlse) = -p_sink * delta
        p_sink = jnp.exp(sinks.astype(jnp.float32).reshape(1, Hq, 1) - lse_pub)
        dsinks = -(p_sink * delta).sum(axis=(0, 2)).astype(sinks.dtype)

    return dq, dk, dv, dsinks, None, None, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_unsupported_reason(q, k) -> str | None:
    """Why the kernel cannot take q (B,S,Hq,D) against k (B,T,Hkv,D), or
    None. The single statement of its shape rules."""
    S, Hq, Dq = q.shape[1:]
    T, Hkv, Dk = k.shape[1:]
    if Dq != Dk:
        return f"q/k head_dim mismatch ({Dq} vs {Dk})"
    if Hq % Hkv != 0:
        return f"GQA needs Hq % Hkv == 0 (got {Hq} % {Hkv})"
    if S % LANE or T % LANE:
        return f"seq lens ({S}, {T}) are not multiples of {LANE}"
    return None


def flash_attention(
    q, k, v,
    *,
    causal: bool = True,
    segment_ids=None,
    positions=None,
    kv_segment_ids=None,
    kv_positions=None,
    sliding_window=None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    sinks=None,
    block_sizes: BlockSizes | None = None,
    return_lse: bool = False,
):
    """Flash attention; q (B,S,Hq,D), k (B,T,Hkv,D), v (B,T,Hkv,Dv) → (B,S,Hq,Dv).

    `sliding_window` may be a static int or a traced scalar (per-layer window
    selected inside a scan). `kv_positions`/`kv_segment_ids` give the kv side
    independent coordinates (ring attention); providing them switches causal
    masking from global-index to position comparison. `sinks` is a (Hq,)
    vector of learned sink logits (gpt-oss). With `return_lse=True` returns
    (out, lse) where lse is (B, Hq, S) fp32 (NEG_INF for fully-masked rows)
    and is differentiable.

    Raises NotImplementedError for the shapes `flash_unsupported_reason`
    names; the dispatcher in ops/attention.py asks it first.
    """
    reason = flash_unsupported_reason(q, k)
    if reason is not None:
        raise NotImplementedError(f"flash_attention: {reason}")
    B, S, Hq, Dq = q.shape
    _, T, Hkv, _ = k.shape
    Dv = v.shape[-1]
    scale = scale if scale is not None else float(Dq) ** -0.5

    asym = kv_positions is not None or kv_segment_ids is not None
    if not causal:
        causal_mode = None
    elif asym:
        causal_mode = "position"
    else:
        causal_mode = "index"

    qp = positions if positions is not None else jnp.arange(S, dtype=jnp.int32)[None, :]
    qp = jnp.broadcast_to(qp.astype(jnp.int32), (B, S))
    if asym:
        kp = kv_positions if kv_positions is not None else qp
        kp = jnp.broadcast_to(kp.astype(jnp.int32), (B, T))
    else:
        kp = qp
    qs = segment_ids if segment_ids is not None else jnp.zeros((B, S), jnp.int32)
    qs = jnp.broadcast_to(qs.astype(jnp.int32), (B, S))
    if asym:
        ks = kv_segment_ids if kv_segment_ids is not None else jnp.zeros((B, T), jnp.int32)
        ks = jnp.broadcast_to(ks.astype(jnp.int32), (B, T))
    else:
        ks = qs

    has_window = sliding_window is not None
    if has_window:
        qwin = qp - (jnp.asarray(sliding_window, jnp.int32) - 1)
        monotonic = positions is None and not asym
        skip_window = (
            sliding_window
            if monotonic and isinstance(sliding_window, int)
            else None
        )
    else:
        qwin = jnp.zeros((B, S), jnp.int32)
        skip_window = None

    # zero-pad narrow head dims to the lane width (differentiable; the pad
    # lanes add zero logits / zero value columns)
    qt = jnp.swapaxes(_pad_last(q, LANE), 1, 2)
    kt = jnp.swapaxes(_pad_last(k, LANE), 1, 2)
    vt = jnp.swapaxes(_pad_last(v, LANE), 1, 2)

    out, lse = _flash(
        qt, kt, vt, sinks,
        _aux_q(qp, B, S), _aux_q(qwin, B, S), _aux_q(qs, B, S),
        _aux_kv(kp, B, T), _aux_kv(ks, B, T),
        causal_mode, has_window, skip_window, logits_soft_cap, float(scale),
        block_sizes or BlockSizes(),
    )
    out = jnp.swapaxes(out, 1, 2)[..., :Dv]
    if return_lse:
        return out, lse
    return out
