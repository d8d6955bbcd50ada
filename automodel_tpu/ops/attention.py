"""Attention entry point with pluggable backends.

The analog of the reference's attention backend dispatch
(reference: nemo_automodel/components/models/common/utils.py BackendConfig
attn = te/sdpa/flex/eager; components/attention/flex_attention.py:32).
TPU backends:

- "xla":    einsum + masked softmax reference path (CPU-testable, and the
            correctness oracle for the Pallas kernels).
- "flash":  Pallas flash-attention kernel (ops/pallas/flash_attention.py).
            Strict: a call the kernel cannot take raises.
- "auto":   flash on TPU, xla elsewhere. On a TPU, a call the kernel cannot
            take runs the reference, logged once per reason and counted on
            the process metrics registry (`attention_reference_fallbacks_
            total`), so a benchmark can assert which implementation ran.

Supports GQA (num_q_heads a multiple of num_kv_heads), causal and
bidirectional masks, packed-sequence segment ids (the THD/cu_seqlens analog,
reference: components/distributed/thd_utils.py), sliding windows, and
logit soft-capping (gemma-style).
"""

from __future__ import annotations

import functools
import logging
from typing import Literal

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

AttnImpl = Literal["auto", "xla", "flash"]

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_kernel_impl(
    impl: str, kernel: str, unsupported: str | None, op: str
) -> str:
    """The one dispatch rule of the attention ops: returns `kernel` (the
    Pallas implementation's name) or "xla". `unsupported` is the reason the
    kernel cannot take this call, or None. Runs at trace time, so the
    fallback count ticks once per traced call site, not per step."""
    if impl == "xla":
        return "xla"
    if impl == kernel:
        if unsupported is not None:
            raise NotImplementedError(
                f"{op}: impl={impl!r} cannot be honoured: {unsupported}"
            )
        return kernel
    if impl != "auto":
        raise ValueError(f"Unknown {op} impl '{impl}'")
    if not _on_tpu():
        return "xla"
    if unsupported is None:
        return kernel
    from automodel_tpu.observability.metrics import default_registry

    # trace-time by design: one tick per compiled call site that resolved
    # to the reference
    counter = default_registry().counter(
        "attention_reference_fallbacks_total", op=op, reason=unsupported
    )
    if counter.value == 0:
        logger.warning(
            "%s: impl='auto' runs the XLA reference on this TPU: %s",
            op, unsupported,
        )
    counter.inc()
    return "xla"


def make_attention_mask(
    q_len: int,
    kv_len: int,
    *,
    causal: bool = True,
    q_segment_ids: jnp.ndarray | None = None,
    kv_segment_ids: jnp.ndarray | None = None,
    q_positions: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    sliding_window: int | None = None,
) -> jnp.ndarray | None:
    """Boolean mask (B?, q_len, kv_len); True = attend."""
    masks = []
    if causal or sliding_window is not None:
        qp = q_positions if q_positions is not None else jnp.arange(q_len)
        kp = kv_positions if kv_positions is not None else jnp.arange(kv_len)
    if causal:
        masks.append(qp[..., :, None] >= kp[..., None, :])
    if sliding_window is not None:
        masks.append(qp[..., :, None] - kp[..., None, :] < sliding_window)
        if not causal:
            # bidirectional local attention: the window is two-sided
            masks.append(kp[..., None, :] - qp[..., :, None] < sliding_window)
    if q_segment_ids is not None and kv_segment_ids is not None:
        masks.append(q_segment_ids[..., :, None] == kv_segment_ids[..., None, :])
    if not masks:
        return None
    out = masks[0]
    for m in masks[1:]:
        out = jnp.logical_and(out, m)
    return out


def xla_attention(
    q: jnp.ndarray,  # (B, S, Hq, D)
    k: jnp.ndarray,  # (B, T, Hkv, D)
    v: jnp.ndarray,  # (B, T, Hkv, D)
    *,
    mask: jnp.ndarray | None,
    scale: float | None = None,
    logits_soft_cap: float | None = None,
    sinks: jnp.ndarray | None = None,  # (Hq,) learnable sink logits
) -> jnp.ndarray:
    """Reference einsum attention; softmax in fp32.

    `sinks` implements gpt-oss attention sinks: one virtual kv slot per head
    whose logit is learned; it absorbs probability mass (joins the softmax
    denominator) but contributes no value.
    """
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    assert Hq % Hkv == 0, f"GQA requires Hq % Hkv == 0, got {Hq} % {Hkv}"
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5

    qg = q.reshape(B, S, Hkv, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    if sinks is not None:
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(1, Hkv, G, 1, 1), (B, Hkv, G, S, 1)
        )
        logits = jnp.concatenate([logits, sink], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    if sinks is not None:
        probs = probs[..., :-1]
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    # v's head dim may differ from q/k's (MLA) — reshape with v's
    return out.reshape(B, S, Hq, v.shape[-1])


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    segment_ids: jnp.ndarray | None = None,
    positions: jnp.ndarray | None = None,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    sinks: jnp.ndarray | None = None,
    impl: AttnImpl = "auto",
    mesh_ctx=None,
) -> jnp.ndarray:
    """Main attention entry. Shapes: q (B,S,Hq,D); k,v (B,T,Hkv,D).

    `mesh_ctx` (a GSPMD caller on a multi-device mesh with cp == 1): the
    flash kernel is a Mosaic custom call with no partitioning rule, so it
    runs inside the shared attention shard_map (parallel/cp.py) — batch on
    the data axes, heads on tp — and every chip computes its own shard."""
    from automodel_tpu.ops.pallas.flash_attention import (
        flash_attention,
        flash_unsupported_reason,
    )

    sharded = mesh_ctx is not None and mesh_ctx.num_devices > 1
    if sharded and mesh_ctx.sizes["cp"] != 1:
        raise ValueError("cp > 1 attention goes through parallel/cp.py")
    unsupported = flash_unsupported_reason(q, k)
    if unsupported is None and sharded:
        unsupported = _shard_unsupported_reason(q, k, mesh_ctx)
    if resolve_kernel_impl(impl, "flash", unsupported, "attention") == "flash":
        def flash(q, k, v, positions, segment_ids, sinks=None):
            return flash_attention(
                q, k, v,
                causal=causal,
                segment_ids=segment_ids,
                positions=positions,
                sliding_window=sliding_window,
                logits_soft_cap=logits_soft_cap,
                scale=scale,
                sinks=sinks,
            )

        if not sharded:
            return flash(q, k, v, positions, segment_ids, sinks)
        from automodel_tpu.parallel.cp import shard_map_attention

        B, S = q.shape[:2]
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        if segment_ids is None:
            segment_ids = jnp.zeros((B, S), jnp.int32)
        return shard_map_attention(
            flash, mesh_ctx, q, k, v,
            jnp.broadcast_to(positions, (B, S)),
            jnp.broadcast_to(segment_ids, (B, S)), sinks,
        )
    mask = make_attention_mask(
        q.shape[1], k.shape[1],
        causal=causal,
        q_segment_ids=segment_ids,
        kv_segment_ids=segment_ids,
        q_positions=positions,
        kv_positions=positions,
        sliding_window=sliding_window,
    )
    return xla_attention(
        q, k, v, mask=mask, scale=scale,
        logits_soft_cap=logits_soft_cap, sinks=sinks,
    )


def _shard_unsupported_reason(q, k, mesh_ctx) -> str | None:
    """Why the attention shard_map cannot split this call, or None."""
    batch, tp = mesh_ctx.batch_size_divisor, mesh_ctx.sizes["tp"]
    if q.shape[0] % batch:
        return f"batch {q.shape[0]} not divisible by the data axes ({batch})"
    if q.shape[2] % tp or k.shape[2] % tp:
        return f"heads ({q.shape[2]}/{k.shape[2]}) not divisible by tp={tp}"
    return None
