"""Context parallelism: ring attention + load-balanced sequence sharding.

The analog of the reference CP stack (reference: nemo_automodel/components/
distributed/context_parallel/sharder.py:15-49 `ContextParallelSharder`
closed-verb contract, :116 round-robin head/tail load balancing; TE ring
attention wiring moe/parallelizer.py:749-800). TPU-native design:

- The sequence dim of activations is sharded on the `cp` mesh axis (GSPMD).
- Attention runs inside a `shard_map` over the mesh: each cp rank holds its
  local q and rotates k/v blocks around the ring with `lax.ppermute`
  (ICI-neighbor traffic, the XLA analog of TE's p2p ring), merging partial
  results with a running online softmax — differentiable end-to-end, so the
  backward pass is the reverse ring for free.
- Causality is evaluated by POSITION, so any sequence layout works. The
  load-balanced layout is the reference's head/tail round-robin: the global
  sequence is permuted so cp rank r owns chunks (r, 2*cp-1-r), equalizing
  causal work across ranks; positions ride the permutation.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from automodel_tpu.distributed.mesh import MeshContext
from automodel_tpu.ops.attention import NEG_INF


# ---------------------------------------------------------------------------
# load-balanced layout (reference: sharder.py:116-143)
# ---------------------------------------------------------------------------
def load_balanced_permutation(seq_len: int, cp_size: int) -> np.ndarray:
    """perm[i] = global index of the token placed at layout slot i.

    Rank r's contiguous slice [r*S/cp, (r+1)*S/cp) holds global chunks
    (r, 2*cp-1-r), so every rank sees an equal mix of early (cheap) and late
    (expensive) causal positions.
    """
    assert seq_len % (2 * cp_size) == 0, (seq_len, cp_size)
    chunk = seq_len // (2 * cp_size)
    order = []
    for r in range(cp_size):
        order.append(np.arange(r * chunk, (r + 1) * chunk))
        hi = 2 * cp_size - 1 - r
        order.append(np.arange(hi * chunk, (hi + 1) * chunk))
    return np.concatenate(order)


@dataclasses.dataclass
class ContextParallelSharder:
    """Permutes packed batches into the load-balanced CP layout.

    Closed-verb contract mirroring the reference (sharder.py:15-49):
    `shard_batch` reorders the sequence dim and attaches positions;
    `local_token_global_indices` exposes the layout coordinate.
    """

    cp_size: int
    load_balanced: bool = True
    seq_keys: tuple = ("input_ids", "labels", "positions", "segment_ids", "loss_mask")

    def permutation(self, seq_len: int) -> np.ndarray:
        if self.cp_size == 1 or not self.load_balanced:
            return np.arange(seq_len)
        return load_balanced_permutation(seq_len, self.cp_size)

    def shard_batch(self, batch: dict) -> dict:
        seq_len = batch["input_ids"].shape[-1]
        perm = self.permutation(seq_len)
        if "positions" not in batch:
            batch = {**batch, "positions": np.broadcast_to(
                np.arange(seq_len, dtype=np.int32), batch["input_ids"].shape
            )}
        out = {}
        for k, v in batch.items():
            if k in self.seq_keys and getattr(v, "ndim", 0) >= 2 and v.shape[-1] == seq_len:
                out[k] = np.asarray(v)[..., perm]
            else:
                out[k] = v
        return out

    def local_token_global_indices(self, seq_len: int, rank: int) -> np.ndarray:
        perm = self.permutation(seq_len)
        local = seq_len // self.cp_size
        return perm[rank * local : (rank + 1) * local]


# ---------------------------------------------------------------------------
# per-document (blockdiag) CP layout: whole documents per rank → NO exchange
# ---------------------------------------------------------------------------
def document_pack_permutation(segment_row: np.ndarray, cp_size: int) -> np.ndarray:
    """perm[i] = source index of the token placed at layout slot i, packing
    WHOLE documents onto cp ranks (first-fit decreasing by length).

    The TPU-native answer to the reference's blockdiag_cp exchange
    (reference: distributed/blockdiag_cp/exchange.py — differentiable
    all-gather / left-halo / a2av collectives restricted to same-document
    blocks): with packed attention already block-diagonal per document,
    placing each document entirely on one rank makes every key a query
    needs LOCAL — the per-document exchange collapses to none at all.
    Raises when a document exceeds the per-rank capacity S/cp (those need
    the ring layout, which handles any span)."""
    S = segment_row.shape[0]
    assert S % cp_size == 0, (S, cp_size)
    cap = S // cp_size
    # contiguous document spans (packing emits docs back-to-back);
    # vectorized — this runs per row per batch in the host data path
    cuts = (np.flatnonzero(np.diff(segment_row)) + 1).tolist()
    bounds = [0] + cuts + [S]
    docs = [(bounds[j], bounds[j + 1]) for j in range(len(bounds) - 1)]
    # capacity-aligned packing (datasets/packing.py align=S/cp): no doc
    # crosses a rank boundary already → identity layout, nothing to move
    if all(lo // cap == (hi - 1) // cap for lo, hi in docs):
        return np.arange(S)
    too_big = [d for d in docs if d[1] - d[0] > cap]
    if too_big:
        raise ValueError(
            f"blockdiag CP: document of {too_big[0][1] - too_big[0][0]} tokens "
            f"exceeds the per-rank capacity {cap} (= seq {S} / cp {cp_size}); "
            "use distributed.cp_layout: balanced (the ring handles documents "
            "of any span)"
        )
    loads = [0] * cp_size
    assign: list[list[tuple]] = [[] for _ in range(cp_size)]
    for d in sorted(docs, key=lambda d: d[0] - d[1]):  # longest first
        r = min(
            (r for r in range(cp_size) if loads[r] + (d[1] - d[0]) <= cap),
            key=lambda r: loads[r],
            default=None,
        )
        if r is None:
            raise ValueError(
                f"blockdiag CP: documents do not fit cp={cp_size} ranks of "
                f"capacity {cap} (first-fit-decreasing overflow); repack with "
                "a multiple-of-capacity target or use cp_layout: balanced"
            )
        assign[r].append(d)
        loads[r] += d[1] - d[0]
    perm = np.empty(S, np.int64)
    i = 0
    for r in range(cp_size):
        for lo, hi in sorted(assign[r]):  # preserve order within the rank
            perm[i : i + hi - lo] = np.arange(lo, hi)
            i += hi - lo
    assert i == S  # capacities sum to S, so every token lands exactly once
    return perm


@dataclasses.dataclass
class BlockDiagContextParallelSharder:
    """Per-document CP sharder: permutes each packed row so whole documents
    land on single cp ranks (document_pack_permutation above); positions /
    labels / segment ids ride the same per-row permutation. Attention then
    runs LOCAL per shard (`cp_blockdiag` on the model config) — zero ring
    steps. Requires packed batches (segment_ids) whose documents fit S/cp."""

    cp_size: int
    seq_keys: tuple = ("input_ids", "labels", "positions", "segment_ids", "loss_mask")

    def shard_batch(self, batch: dict) -> dict:
        if "segment_ids" not in batch:
            raise ValueError(
                "blockdiag CP needs packed batches with segment_ids; use a "
                "packing dataset or distributed.cp_layout: balanced"
            )
        seg = np.asarray(batch["segment_ids"])
        seq_len = seg.shape[-1]
        flat = seg.reshape(-1, seq_len)
        perms = np.stack([
            document_pack_permutation(row, self.cp_size) for row in flat
        ]).reshape(seg.shape)
        if "positions" not in batch:
            batch = {**batch, "positions": np.broadcast_to(
                np.arange(seq_len, dtype=np.int32), batch["input_ids"].shape
            )}
        out = {}
        for k, v in batch.items():
            if k in self.seq_keys and getattr(v, "ndim", 0) >= 2 and v.shape[-1] == seq_len:
                out[k] = np.take_along_axis(np.asarray(v), perms, axis=-1)
            else:
                out[k] = v
        return out


def shard_map_attention(inner_fn, mesh_ctx, q, k, v, positions,
                        segment_ids, sinks):
    """The one shard_map wrapper of attention: batch on the data axes,
    sequence on cp, heads on tp; sinks (per-q-head) ride the tp axis.
    `inner_fn(q, k, v, positions, segment_ids, sinks=None)` runs per-shard.
    Serves the CP variants below and, on cp == 1 meshes, the flash kernel
    (ops/attention.py) — a Mosaic call GSPMD cannot partition."""
    batch = ("dp_replicate", "dp_shard", "ep")
    qkv_spec = P(batch, "cp", "tp", None)
    tok_spec = P(batch, "cp")
    in_specs = [qkv_spec, qkv_spec, qkv_spec, tok_spec, tok_spec]
    args = [q, k, v, positions, segment_ids]
    if sinks is not None:
        in_specs.append(P("tp"))
        args.append(sinks)
    return jax.shard_map(
        inner_fn,
        mesh=mesh_ctx.mesh,
        in_specs=tuple(in_specs),
        out_specs=qkv_spec,
        check_vma=False,
    )(*args)


def local_cp_attention(
    q, k, v,
    positions, segment_ids,
    mesh_ctx: MeshContext,
    *,
    causal: bool = True,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    sinks=None,
    attn_impl: str = "auto",
):
    """Blockdiag-CP attention: every document is rank-local (the sharder's
    contract), so attention is one LOCAL flash per cp shard — no ppermute
    ring, no exchange. segment/position masking inside the shard keeps
    cross-document isolation identical to the ring's."""
    from automodel_tpu.ops.attention import dot_product_attention

    if segment_ids is None:
        # zero-segment defaulting (the ring's behavior) would silently cut
        # a genuinely rank-spanning sequence at shard boundaries here —
        # the local path is only valid under the per-document contract
        raise ValueError(
            "blockdiag CP local attention requires packed segment_ids "
            "(every document rank-local); got none — use the ring layout "
            "for unpacked sequences"
        )

    def fn(q, k, v, positions, segment_ids, sinks=None):
        return dot_product_attention(
            q, k, v, causal=causal, segment_ids=segment_ids,
            positions=positions, sliding_window=sliding_window,
            logits_soft_cap=logits_soft_cap, scale=scale,
            sinks=sinks, impl=attn_impl,
        )

    return shard_map_attention(
        fn, mesh_ctx, q, k, v, positions, segment_ids, sinks
    )


# ---------------------------------------------------------------------------
# ring attention (inside shard_map)
# ---------------------------------------------------------------------------
def _partial_attention_xla(q, k, v, qpos, kpos, qseg, kseg, *, scale, soft_cap, window, causal):
    """One XLA ring step: normalized partial out + lse of local q vs a
    visiting kv block.

    Returns (o (B,S,Hq,Dv) normalized fp32, lse (B,Hq,S) fp32; NEG_INF for
    rows with no unmasked kv). Shapes: q (B,S,Hq,D); k,v (B,T,Hkv,D).
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32) * scale
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    mask = jnp.ones((B, S, T), bool)
    if causal:
        mask = jnp.logical_and(mask, qpos[:, :, None] >= kpos[:, None, :])
    if window is not None:
        mask = jnp.logical_and(mask, qpos[:, :, None] - kpos[:, None, :] < window)
        if not causal:
            # bidirectional local attention: two-sided window (matches the
            # flash kernel and ops/attention.py oracle)
            mask = jnp.logical_and(mask, kpos[:, None, :] - qpos[:, :, None] < window)
    mask = jnp.logical_and(mask, qseg[:, :, None] == kseg[:, None, :])
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                      # (B,Hkv,G,S)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask[:, None, None, :, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p.astype(v.dtype), v).astype(jnp.float32)
    o = o.reshape(B, S, Hq, v.shape[-1])
    l_safe = jnp.where(l == 0.0, 1.0, l)
    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe)).reshape(B, Hq, S)
    o = o / jnp.moveaxis(l_safe.reshape(B, Hq, S), 1, 2)[..., None]
    return o, lse


def ring_attention(
    q, k, v,
    positions, segment_ids,
    *,
    axis_name: str = "cp",
    causal: bool = True,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    sinks=None,
    attn_impl: str = "auto",
):
    """Ring attention over `axis_name`; call INSIDE shard_map.

    All inputs are local shards: q/k/v (B, S_loc, H, D); positions and
    segment_ids (B, S_loc) in GLOBAL coordinates (survive any layout).

    Each step computes local-q × visiting-kv attention — through the Pallas
    flash kernel in position-causal mode (reference: TE ring wiring,
    moe/parallelizer.py:749-800) or the XLA oracle, chosen by the dispatch
    rule of ops/attention.py (`resolve_kernel_impl`) — and merges
    (out, lse) partials with a running logsumexp. The merge is plain JAX, so
    the whole ring differentiates through the flash kernel's lse-aware VJP.
    gpt-oss sinks join once at the end: out *= sigmoid(lse_final - sink).
    """
    B, S, Hq, D = q.shape
    Dv = v.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    cp = lax.axis_size(axis_name)

    if segment_ids is None:
        segment_ids = jnp.zeros((B, S), jnp.int32)

    from automodel_tpu.ops.attention import resolve_kernel_impl
    from automodel_tpu.ops.pallas.flash_attention import (
        flash_attention,
        flash_unsupported_reason,
    )

    if resolve_kernel_impl(
        attn_impl, "flash", flash_unsupported_reason(q, k), "ring_attention"
    ) == "flash":
        def partial_step(k_blk, v_blk, kpos, kseg):
            o, lse = flash_attention(
                q, k_blk, v_blk,
                causal=causal,
                positions=positions, segment_ids=segment_ids,
                kv_positions=kpos, kv_segment_ids=kseg,
                sliding_window=sliding_window,
                logits_soft_cap=logits_soft_cap,
                scale=scale, return_lse=True,
            )
            return o.astype(jnp.float32), lse
    else:
        def partial_step(k_blk, v_blk, kpos, kseg):
            return _partial_attention_xla(
                q, k_blk, v_blk, positions, kpos, segment_ids, kseg,
                scale=scale, soft_cap=logits_soft_cap,
                window=sliding_window, causal=causal,
            )

    def step(carry, _):
        o_acc, lse_acc, kv = carry
        k_blk, v_blk, kpos, kseg = kv
        o_i, lse_i = partial_step(k_blk, v_blk, kpos, kseg)
        lse_new = jnp.logaddexp(lse_acc, lse_i)
        w_old = jnp.exp(lse_acc - lse_new)       # (B,Hq,S)
        w_new = jnp.exp(lse_i - lse_new)
        to_bshd = lambda x: jnp.moveaxis(x, 1, 2)[..., None]
        o_acc = o_acc * to_bshd(w_old) + o_i * to_bshd(w_new)
        kv = lax.ppermute(
            kv, axis_name, [(i, (i + 1) % cp) for i in range(cp)]
        )
        return (o_acc, lse_new, kv), None

    o0 = jnp.zeros((B, S, Hq, Dv), jnp.float32)
    lse0 = jnp.full((B, Hq, S), NEG_INF, jnp.float32)
    kv0 = (k, v, positions, segment_ids)
    (o_f, lse_f, _), _ = lax.scan(step, (o0, lse0, kv0), None, length=cp)

    if sinks is not None:
        # the sink joins the global softmax denominator exactly once
        sig = jax.nn.sigmoid(lse_f - sinks.astype(jnp.float32).reshape(1, Hq, 1))
        o_f = o_f * jnp.moveaxis(sig, 1, 2)[..., None]
    return o_f.astype(q.dtype)


def ring_dot_product_attention(
    q, k, v,
    positions, segment_ids,
    mesh_ctx: MeshContext,
    *,
    causal: bool = True,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    sinks=None,
    attn_impl: str = "auto",
):
    """shard_map wrapper: GSPMD everywhere else, explicit ring on `cp`."""
    if segment_ids is None:
        segment_ids = jnp.zeros(positions.shape, jnp.int32)

    def fn(q, k, v, positions, segment_ids, sinks=None):
        return ring_attention(
            q, k, v, positions, segment_ids,
            axis_name="cp", causal=causal,
            sliding_window=sliding_window,
            logits_soft_cap=logits_soft_cap,
            scale=scale, sinks=sinks, attn_impl=attn_impl,
        )

    return shard_map_attention(
        fn, mesh_ctx, q, k, v, positions, segment_ids, sinks
    )
