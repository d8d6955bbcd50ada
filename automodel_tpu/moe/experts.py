"""Expert compute + token dispatch.

The TPU-native replacement for the reference's experts/dispatcher stack
(reference: nemo_automodel/components/moe/experts.py:202 `GroupedExperts`,
:651 `GroupedExpertsDeepEP`; megatron/token_dispatcher.py:504
`MoEFlexTokenDispatcher`; megatron/fused_a2a.py DeepEP NVSHMEM all-to-all).

Design: capacity-based einsum dispatch — the GSPMD-native MoE pattern.
Routing produces a (tokens, experts, capacity) dispatch tensor; two einsums
move tokens to expert-major layout and back. When the expert dim is sharded
on the `ep` mesh axis and tokens on `batch`, XLA lowers the einsums to
exactly the all-to-all pair DeepEP implements by hand, riding ICI. Static
shapes (capacity padding) keep everything jit-compatible; overflow tokens
are dropped (capacity_factor controls headroom), matching Megatron-style
capacity dispatch semantics.

The second dispatcher is the sort-based dropless path
(`experts_forward_dropless`, and `_dropless_ep_local` across an `ep` axis):
rows sorted by expert, the three expert products as grouped matmuls
(`ops/grouped_matmul.py`: a Pallas kernel that streams every expert's
weights once on a TPU where the call qualifies, `lax.ragged_dot` everywhere
else). Its contract on the rows past `sum(group_sizes)`, where the sort puts
the masked tokens' sentinel rows, is that they come out ZERO: their combine
weight is 0, and 0 x NaN is NaN. Both dispatchers share the gate and the
expert weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.ops.grouped_matmul import grouped_matmul

_EXPERT_ACT = {
    "silu": jax.nn.silu,
    "geglu": jax.nn.gelu,
    "quick_geglu": lambda x: x * jax.nn.sigmoid(1.702 * x),
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def gated_combine(g, u, kind: str, limit: float = 7.0):
    """gate/up → MLP inner. "swigluoai" is gpt-oss's clamped variant:
    min(g,limit)·sigmoid(1.702·g)·(clip(u,±limit)+1); others are act(g)·u."""
    if kind == "swigluoai":
        g = jnp.minimum(g, limit)
        u = jnp.clip(u, -limit, limit)
        return g * jax.nn.sigmoid(1.702 * g) * (u + 1.0)
    return _EXPERT_ACT[kind](g) * u


def init_experts(cfg: MoEConfig, hidden_size: int, rng: jax.Array) -> dict:
    """The weights of the experts this layer holds (`cfg.num_held`: all the
    routed ones, or one chip's share of them)."""
    E, H, I = cfg.num_held, hidden_size, cfg.moe_intermediate_size
    k1, k2, k3 = jax.random.split(rng, 3)
    std_in, std_out = H ** -0.5, I ** -0.5
    params = {
        "up_proj": {"kernel": std_in * jax.random.truncated_normal(k2, -3, 3, (E, H, I))},
        "down_proj": {"kernel": std_out * jax.random.truncated_normal(k3, -3, 3, (E, I, H))},
    }
    if cfg.gated_experts:
        params["gate_proj"] = {
            "kernel": std_in * jax.random.truncated_normal(k1, -3, 3, (E, H, I))
        }
    if cfg.expert_bias:
        params["up_proj"]["bias"] = jnp.zeros((E, I))
        params["down_proj"]["bias"] = jnp.zeros((E, H))
        if cfg.gated_experts:
            params["gate_proj"]["bias"] = jnp.zeros((E, I))
    return params


def expert_param_specs(cfg: MoEConfig) -> dict:
    specs = {
        "up_proj": {"kernel": ("expert", "expert_embed", "expert_mlp")},
        "down_proj": {"kernel": ("expert", "expert_mlp", "expert_embed")},
    }
    if cfg.gated_experts:
        specs["gate_proj"] = {"kernel": ("expert", "expert_embed", "expert_mlp")}
    if cfg.expert_bias:
        specs["up_proj"]["bias"] = ("expert", "expert_mlp")
        specs["down_proj"]["bias"] = ("expert", "expert_embed")
        if cfg.gated_experts:
            specs["gate_proj"]["bias"] = ("expert", "expert_mlp")
    return specs


def compute_capacity(cfg: MoEConfig, num_tokens: int) -> int:
    per_expert = num_tokens * cfg.experts_per_token / cfg.n_routed_experts
    cap = int(per_expert * cfg.capacity_factor)
    return max(8, ((cap + 7) // 8) * 8)  # sublane-align


def dispatch_tensors(
    cfg: MoEConfig,
    indices: jnp.ndarray,  # (T, K) int32
    weights: jnp.ndarray,  # (T, K) f32
    capacity: int,
    dtype=jnp.float32,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Build the dispatch one-hot (T,E,C) and combine weights (T,E).

    Position of token t within expert e's buffer = number of earlier
    (token, slot) pairs routed to e — a cumsum over the flattened (T*K)
    routing order, matching Megatron's capacity dispatcher semantics.

    Memory note (the reference's DeepEP path never materializes per-slot
    buffers; this is the GSPMD formulation's cost): ONE (T,E,C) tensor in
    the COMPUTE dtype. The per-token combine weights factor as a (T,E)
    matrix — `experts_forward` fuses it into the combine einsum instead of
    materializing a second (T,E,C). For DSv3-scale expert counts prefer
    `dispatcher: dropless` (sort + ragged_dot, EP-capable), which has no
    (T,E,C) at all.
    """
    T, K = indices.shape
    E = cfg.n_routed_experts
    flat = indices.reshape(T * K)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)          # (T*K, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot                   # (T*K, E)
    pos_in_expert = jnp.sum(pos * onehot, axis=-1).reshape(T, K)
    keep = (pos_in_expert < capacity).astype(dtype)             # (T, K)

    # Accumulate per top-k slot so peak memory stays at one (T, E, C) tensor
    # (a (T*K, E, C) intermediate would be K× larger).
    dispatch = jnp.zeros((T, E, capacity), dtype)
    combine_w = jnp.zeros((T, E), jnp.float32)
    idx_tk = indices.reshape(T, K)
    for k in range(K):
        eh = jax.nn.one_hot(idx_tk[:, k], E, dtype=dtype)                # (T, E)
        ch = jax.nn.one_hot(pos_in_expert[:, k], capacity, dtype=dtype)
        kept_e = eh * keep[:, k : k + 1]
        dispatch = dispatch + kept_e[:, :, None] * ch[:, None, :]
        combine_w = combine_w + kept_e.astype(jnp.float32) * weights[:, k][:, None]
    return dispatch, combine_w


#: rows of the grouped matmul's row tile (`ops/grouped_matmul._TILE_ROWS`):
#: the compact path of a share rounds its rows up to whole tiles
_SHARE_ROW_TILE = 128


def share_rows(cfg: MoEConfig, pairs: int) -> int | None:
    """Rows of the COMPACT path of a layer that holds a share of its experts,
    over `pairs` (row, top-k) pairs: twice the pairs its held experts expect
    under even routing, rounded up to whole row tiles. None where the layer
    holds all its experts, or where that bound is no fewer than `pairs`: the
    layer then runs its whole path alone."""
    if cfg.holds_all_experts:
        return None
    expected2 = -(-2 * pairs * cfg.num_held // cfg.n_routed_experts)
    rows = -(-expected2 // _SHARE_ROW_TILE) * _SHARE_ROW_TILE
    return rows if rows < pairs else None


def experts_forward_dropless(
    params: dict,
    cfg: MoEConfig,
    x: jnp.ndarray,        # (T, H)
    weights: jnp.ndarray,  # (T, K)
    indices: jnp.ndarray,  # (T, K)
    mesh_ctx=None,
    *,
    with_live: bool = False,
):
    """Dropless sort-based dispatch + ragged grouped GEMM.

    The megablox/`GroupedExpertsDeepEP` analog (reference: experts.py:651):
    (token, slot) pairs are sorted by expert id, the three expert matmuls run
    as `ops/grouped_matmul.grouped_matmul` over the per-expert group sizes
    (no capacity padding, no dropped tokens), and outputs scatter-add back
    into token order. Static shapes throughout (TK rows total), so
    jit-compatible. Masked tokens sort last (sentinel expert E) and belong
    to no group: the grouped matmul returns their rows as zeros.

    Scope: replicated or dp-sharded experts (ep=1) — ragged group sizes
    don't split across an `ep` axis under GSPMD; on an ep>1 mesh
    `moe/layer.py` calls `experts_forward_dropless_ep` below instead. A
    GSPMD caller on more than one device passes its `mesh_ctx` (the Pallas
    kernel has no partitioning rule: the reference serves such a call); a
    caller inside a `shard_map` passes none.

    A layer that holds a SHARE of its experts (`cfg.n_held_experts`) sorts
    the pairs whose expert it holds to the front, by the expert's index among
    the held, and every other pair behind them under the sentinel: they
    belong to no group, the grouped matmul gives their rows zeros, and the
    token's result is the held experts' part alone, each with the weight the
    router gave it over ALL its chosen experts.

    Such a layer's live pairs are few (8 held of 128 routed at top-8: a
    sixteenth), and everything after the sort runs over the sorted pairs'
    first `R` rows alone: the COMPACT path at `R = share_rows(cfg, T*K)`,
    about twice the live pairs expected, when the live pairs fit in it, else
    the WHOLE path at `R = T*K`, one `lax.cond` between the two. Both are
    the same function of `R`, exact for any routing: no pair is dropped, and
    a pair computes what it did, summed in the same order. A layer that
    holds all its experts runs the whole path alone, traced as it always
    was. `with_live=True` returns (out, the live pairs, whether the whole
    path ran): int32 and bool scalars.
    """
    T, H = x.shape
    K = cfg.experts_per_token
    E = cfg.num_held
    dtype = x.dtype

    flat_expert = indices.reshape(T * K)
    if not cfg.holds_all_experts:
        local = flat_expert - cfg.first_held_expert
        flat_expert = jnp.where((local >= 0) & (local < E), local, E)
    # stable sort groups rows by expert while keeping token order within
    sort_idx = jnp.argsort(flat_expert, stable=True)

    def first_pairs(rows):
        """The layer over the first `rows` sorted pairs: the held experts'
        pairs lie first, the rows past them come out of the grouped
        matmuls zero and add nothing."""
        idx = sort_idx if rows == T * K else sort_idx[:rows]
        token_of = idx // K
        expert_of = jnp.take(flat_expert, idx)
        xs = jnp.take(x, token_of, axis=0)  # (rows, H)
        group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)

        # masked tokens carry the sentinel index E (see gate_forward) — clip
        # once for the bias gathers; their rows are zero-weighted in the
        # combine anyway
        safe_expert = jnp.clip(expert_of, 0, E - 1)
        gmm = functools.partial(grouped_matmul, mesh_ctx=mesh_ctx)
        u = gmm(xs, params["up_proj"]["kernel"].astype(dtype), group_sizes)
        if "bias" in params["up_proj"]:
            u = u + jnp.take(params["up_proj"]["bias"].astype(dtype), safe_expert, axis=0)
        if cfg.gated_experts:
            g = gmm(xs, params["gate_proj"]["kernel"].astype(dtype), group_sizes)
            if "bias" in params["gate_proj"]:
                g = g + jnp.take(params["gate_proj"]["bias"].astype(dtype), safe_expert, axis=0)
            h_in = gated_combine(g, u, cfg.expert_activation, cfg.swiglu_limit)
        else:
            h_in = _EXPERT_ACT[cfg.expert_activation](u)
        y = gmm(h_in, params["down_proj"]["kernel"].astype(dtype), group_sizes)
        if "bias" in params["down_proj"]:
            y = y + jnp.take(params["down_proj"]["bias"].astype(dtype), safe_expert, axis=0)

        w_sorted = jnp.take(weights.reshape(T * K), idx, axis=0).astype(dtype)
        contrib = y * w_sorted[:, None]
        return jnp.zeros((T, H), dtype).at[token_of].add(contrib)

    bound = share_rows(cfg, T * K)
    if bound is None and not with_live:
        return first_pairs(T * K)
    live = jnp.sum(flat_expert < E, dtype=jnp.int32)
    if bound is None:
        return first_pairs(T * K), live, jnp.bool_(True)
    whole = live > bound
    out = jax.lax.cond(
        whole, lambda: first_pairs(T * K), lambda: first_pairs(bound))
    return (out, live, whole) if with_live else out


def _raw_ragged_a2a(x, out, in_off, send_sz, out_off, recv_sz, axis_name):
    """Seam over `lax.ragged_all_to_all` — tests monkeypatch this with a
    collective emulator because XLA:CPU has no ragged-all-to-all thunk."""
    from jax import lax

    return lax.ragged_all_to_all(
        x, out, in_off, send_sz, out_off, recv_sz, axis_name=axis_name
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _ragged_exchange(x, in_off, send_sz, out_off, recv_sz, recv_off,
                     back_out_off, out_rows, axis_name):
    """Differentiable ragged all-to-all (TPU): sends x's contiguous
    per-peer row chunks, returns an (out_rows, …) buffer with untouched rows
    zero. The VJP runs the REVERSE ragged exchange of the cotangents — the
    combine direction's metadata is exactly the dispatch direction's swapped.
    """
    out = jnp.zeros((out_rows,) + x.shape[1:], x.dtype)
    return _raw_ragged_a2a(x, out, in_off, send_sz, out_off, recv_sz, axis_name)


def _ragged_exchange_fwd(x, in_off, send_sz, out_off, recv_sz, recv_off,
                         back_out_off, out_rows, axis_name):
    out = _ragged_exchange(
        x, in_off, send_sz, out_off, recv_sz, recv_off, back_out_off,
        out_rows, axis_name,
    )
    return out, (x.shape[0], in_off, send_sz, out_off, recv_sz, recv_off,
                 back_out_off)


def _ragged_exchange_bwd(out_rows, axis_name, res, dout):
    n_in, in_off, send_sz, out_off, recv_sz, recv_off, back_out_off = res
    dx = jnp.zeros((n_in,) + dout.shape[1:], dout.dtype)
    dx = _raw_ragged_a2a(
        dout, dx, recv_off, recv_sz, back_out_off, send_sz, axis_name
    )
    return dx, None, None, None, None, None, None


_ragged_exchange.defvjp(_ragged_exchange_fwd, _ragged_exchange_bwd)


def _dropless_ep_local(params, cfg, x, weights, indices, *, axis_name, bucket,
                       ragged=False):
    """Per-shard body of the EP dropless dispatch; call INSIDE shard_map.

    The DeepEP-semantics analog (reference: moe/megatron/fused_a2a.py:139
    `fused_dispatch`, :238 `fused_combine`; token_dispatcher.py:504): tokens
    travel to the EP rank that owns their expert and come back, with NO
    capacity drops. Two exchange layouts:

    - ragged=True (TPU): `lax.ragged_all_to_all` ships exactly the routed
      rows — wire traffic proportional to actual tokens, DeepEP's defining
      property. Offsets ride a tiny (P,P) counts all_gather. The receive
      buffer stays worst-case sized (P·bucket — every token in the step
      could route here), but bytes on ICI are the ragged sizes.
    - ragged=False (CPU fallback / dryrun): a static (ep, bucket, H)
      all_to_all padded to the dropless worst case (XLA:CPU has no
      ragged-all-to-all).

    Layout invariant: rows sorted by global expert id are grouped by owner
    rank (experts are contiguous per rank), so one stable sort serves both
    the send bucketing and, on the receiver, the grouped matmuls' grouping.
    """
    from jax import lax

    T, H = x.shape
    K = cfg.experts_per_token
    E = cfg.n_routed_experts
    P = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    E_loc = E // P
    TK = T * K
    dtype = x.dtype

    flat_expert = indices.reshape(TK)                       # sentinel E = masked
    sort_idx = jnp.argsort(flat_expert, stable=True)
    expert_sorted = jnp.take(flat_expert, sort_idx)
    token_of = sort_idx // K
    xs = jnp.take(x, token_of, axis=0)                      # (TK, H) sorted rows

    counts_e = jnp.bincount(flat_expert, length=E).astype(jnp.int32)
    counts_peer = counts_e.reshape(P, E_loc).sum(-1)        # rows per dest rank
    offsets_peer = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts_peer)[:-1]]
    )

    if ragged:
        # C[j, i] = rows rank j sends to rank i (tiny (P,P) metadata gather)
        C = lax.all_gather(counts_peer, axis_name)          # (P, P)
        recv_sz = C[:, r]                                   # from each sender
        recv_off = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(recv_sz)[:-1]]
        )
        # where MY chunk lands on receiver i: after all senders j < r
        out_off = (jnp.cumsum(C, axis=0) - C)[r]            # (P,)
        # where my RETURN chunk lands on source i: at i's offsets_peer[r]
        OP = lax.all_gather(offsets_peer, axis_name)        # (P, P)
        back_out_off = OP[:, r]
        R = P * bucket

        recv_x = _ragged_exchange(
            xs, offsets_peer, counts_peer, out_off, recv_sz, recv_off,
            back_out_off, R, axis_name,
        )
        eid_out = jnp.full((R,), E, jnp.int32)
        recv_eid = _raw_ragged_a2a(
            expert_sorted.astype(jnp.int32), eid_out, offsets_peer,
            counts_peer, out_off, recv_sz, axis_name,
        )
        le = recv_eid - r * E_loc                           # local expert id
        recv_valid = (le >= 0) & (le < E_loc)
        valid_send = expert_sorted < E
    else:
        dest = jnp.minimum(expert_sorted // E_loc, P)       # sentinel → P (drop)
        slot = jnp.arange(TK, dtype=jnp.int32) - jnp.take(
            offsets_peer, jnp.minimum(dest, P - 1)
        )
        valid_send = (dest < P) & (slot < bucket)
        flat_pos = jnp.where(valid_send, dest * bucket + slot, P * bucket)

        send_x = jnp.zeros((P * bucket, H), dtype).at[flat_pos].set(xs, mode="drop")
        send_eid = jnp.full((P * bucket,), E, jnp.int32).at[flat_pos].set(
            expert_sorted, mode="drop"
        )

        recv_x = lax.all_to_all(send_x.reshape(P, bucket, H), axis_name, 0, 0)
        recv_eid = lax.all_to_all(send_eid.reshape(P, bucket), axis_name, 0, 0)
        recv_x = recv_x.reshape(P * bucket, H)
        le = recv_eid.reshape(P * bucket) - r * E_loc       # local expert id
        recv_valid = (le >= 0) & (le < E_loc)

    # regroup received rows by local expert (invalid rows sort last);
    # group sizes come from the received expert ids — no extra collective
    key = jnp.where(recv_valid, le, E_loc)
    sort2 = jnp.argsort(key, stable=True)
    xs2 = jnp.take(recv_x, sort2, axis=0)
    group_sizes = jnp.bincount(key, length=E_loc + 1)[:E_loc].astype(jnp.int32)
    safe_le = jnp.clip(jnp.take(key, sort2), 0, E_loc - 1)

    u = grouped_matmul(xs2, params["up_proj"]["kernel"].astype(dtype), group_sizes)
    if "bias" in params["up_proj"]:
        u = u + jnp.take(params["up_proj"]["bias"].astype(dtype), safe_le, axis=0)
    if cfg.gated_experts:
        g = grouped_matmul(xs2, params["gate_proj"]["kernel"].astype(dtype), group_sizes)
        if "bias" in params["gate_proj"]:
            g = g + jnp.take(params["gate_proj"]["bias"].astype(dtype), safe_le, axis=0)
        h_in = gated_combine(g, u, cfg.expert_activation, cfg.swiglu_limit)
    else:
        h_in = _EXPERT_ACT[cfg.expert_activation](u)
    y2 = grouped_matmul(h_in, params["down_proj"]["kernel"].astype(dtype), group_sizes)
    if "bias" in params["down_proj"]:
        y2 = y2 + jnp.take(params["down_proj"]["bias"].astype(dtype), safe_le, axis=0)
    y2 = jnp.where(jnp.take(recv_valid, sort2)[:, None], y2, 0.0)

    # undo the regroup sort, return rows to their source rank
    y_recv = jnp.zeros_like(y2).at[sort2].set(y2)
    if ragged:
        # combine = dispatch with the metadata roles swapped; rows land back
        # at their original sorted offsets, unsent rows stay zero
        ys = _ragged_exchange(
            y_recv, recv_off, recv_sz, back_out_off, counts_peer,
            offsets_peer, out_off, TK, axis_name,
        )
    else:
        y_back = lax.all_to_all(y_recv.reshape(P, bucket, H), axis_name, 0, 0)
        y_back = y_back.reshape(P * bucket, H)
        ys = jnp.take(y_back, jnp.minimum(flat_pos, P * bucket - 1), axis=0)
    ys = jnp.where(valid_send[:, None], ys, 0.0)
    w_sorted = jnp.take(weights.reshape(TK), sort_idx).astype(dtype)
    return jnp.zeros((T, H), dtype).at[token_of].add(ys * w_sorted[:, None])


def shared_expert_forward(
    params: dict,
    cfg: MoEConfig,
    flat: jnp.ndarray,  # (T, H)
    *,
    tp_axis: str | None = None,
) -> jnp.ndarray:
    """Dense shared-expert branch added to the routed output (DeepSeek /
    Qwen-MoE style). One implementation for both execution modes: under
    GSPMD (moe/layer.py) leave `tp_axis=None`; inside the pipeline
    shard_map (moe_lm `_pp_moe_layer_setup`) pass the mesh axis so the
    mlp-dim-sharded down-proj partials are psummed manually."""
    dtype = flat.dtype
    u = flat @ params["up_proj"]["kernel"].astype(dtype)
    if cfg.shared_expert_is_gated:
        g = flat @ params["gate_proj"]["kernel"].astype(dtype)
        inner = gated_combine(g, u, cfg.shared_expert_activation, cfg.swiglu_limit)
    else:
        inner = _EXPERT_ACT[cfg.shared_expert_activation](u)
    out = inner @ params["down_proj"]["kernel"].astype(dtype)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    if cfg.shared_expert_gated:
        out = out * jax.nn.sigmoid(flat @ params["gate"]["kernel"].astype(dtype))
    return out


def dropless_ep_shardmap_body(
    params: dict,
    cfg: MoEConfig,
    x: jnp.ndarray,        # (T_loc, H) — this shard's tokens
    weights: jnp.ndarray,  # (T_loc, K)
    indices: jnp.ndarray,  # (T_loc, K)
    *,
    axis_name: str = "ep",
    ragged: bool | None = None,
) -> jnp.ndarray:
    """Dropless EP dispatch for callers ALREADY inside a shard_map over a
    mesh containing `axis_name` — the pipeline-stage entry point: the pp
    schedules (parallel/pp.py) run each stage's layer scan inside one
    full-mesh shard_map, so the expert A2A must be issued as a manual
    collective confined to that stage's step (it overlaps with other
    stages' compute instead of fencing the whole program).

    `params` holds the LOCAL expert slice (E/ep experts, dim 0); token rows
    are this shard's. bucket = the dropless worst case for the local rows
    (every (token, slot) pair could target one peer).
    """
    if ragged is None:
        ragged = jax.default_backend() == "tpu"
    bucket = max(8, x.shape[0] * cfg.experts_per_token)
    return _dropless_ep_local(
        params, cfg, x, weights, indices,
        axis_name=axis_name, bucket=bucket, ragged=ragged,
    )


def experts_forward_dropless_ep(
    params: dict,
    cfg: MoEConfig,
    x: jnp.ndarray,        # (T, H) flat tokens, sharded (dp, ep, cp)
    weights: jnp.ndarray,  # (T, K)
    indices: jnp.ndarray,  # (T, K)
    mesh_ctx,
    ragged: bool | None = None,  # None = auto (TPU yes, CPU dense fallback)
) -> jnp.ndarray:
    """Dropless dispatch ACROSS an ep>1 mesh axis (DeepEP semantics).

    shard_map wrapper around `_dropless_ep_local`: tokens stay sharded on
    (dp, ep, cp); expert weights enter sharded on `ep` only (fsdp/tp dims
    are gathered at the boundary, the FSDP-unshard analog).
    """
    import functools

    from jax.sharding import PartitionSpec as P

    ep = mesh_ctx.sizes["ep"]
    E = cfg.n_routed_experts
    if E % ep != 0:
        raise ValueError(f"n_routed_experts={E} not divisible by ep={ep}")

    tok = P(("dp_replicate", "dp_shard", "ep", "cp"), None)
    tok_k = tok
    eparams = {
        proj: params[proj]
        for proj in ("gate_proj", "up_proj", "down_proj")
        if proj in params
    }
    espec = {
        proj: {k: P("ep", *([None] * (v.ndim - 1))) for k, v in eparams[proj].items()}
        for proj in eparams
    }

    # dropless worst case: every local (token, slot) row targets one rank
    t_total = x.shape[0]
    t_loc = t_total // (mesh_ctx.axis_size("batch") * mesh_ctx.sizes["cp"])
    bucket = max(8, t_loc * cfg.experts_per_token)

    # ragged A2A ships only the routed rows (DeepEP's bandwidth property);
    # XLA:CPU has no ragged-all-to-all, so the virtual-device mesh (tests,
    # driver dryrun) uses the dense worst-case bucket layout instead
    if ragged is None:
        ragged = jax.default_backend() == "tpu"
    fn = functools.partial(
        _dropless_ep_local, axis_name="ep", bucket=bucket, cfg=cfg,
        ragged=ragged,
    )
    return jax.shard_map(
        lambda p, xx, ww, ii: fn(p, x=xx, weights=ww, indices=ii),
        mesh=mesh_ctx.mesh,
        in_specs=(espec, tok, tok_k, tok_k),
        out_specs=tok,
        check_vma=False,
    )(eparams, x, weights, indices)


def experts_forward(
    params: dict,
    cfg: MoEConfig,
    x: jnp.ndarray,        # (T, H)
    dispatch: jnp.ndarray, # (T, E, C) one-hot
    combine_w: jnp.ndarray,  # (T, E) routing weights
    constrain=None,
) -> jnp.ndarray:
    """Dispatch → batched expert MLP → weighted combine. Returns (T, H)."""
    c = constrain or (lambda a, axes: a)
    dtype = x.dtype
    # tokens → expert-major: XLA inserts the A2A here when ep-sharded
    xe = jnp.einsum("tec,th->ech", dispatch.astype(dtype), x)
    xe = c(xe, ("act_expert", None, "act_embed"))
    u = jnp.einsum("ech,ehi->eci", xe, params["up_proj"]["kernel"].astype(dtype))
    if "bias" in params["up_proj"]:
        u = u + params["up_proj"]["bias"].astype(dtype)[:, None, :]
    if cfg.gated_experts:
        g = jnp.einsum("ech,ehi->eci", xe, params["gate_proj"]["kernel"].astype(dtype))
        if "bias" in params["gate_proj"]:
            g = g + params["gate_proj"]["bias"].astype(dtype)[:, None, :]
        h_in = gated_combine(g, u, cfg.expert_activation, cfg.swiglu_limit)
    else:
        h_in = _EXPERT_ACT[cfg.expert_activation](u)
    y = jnp.einsum("eci,eih->ech", h_in, params["down_proj"]["kernel"].astype(dtype))
    if "bias" in params["down_proj"]:
        y = y + params["down_proj"]["bias"].astype(dtype)[:, None, :]
    y = c(y, ("act_expert", None, "act_embed"))
    # expert-major → tokens (the A2A back); the per-token routing weight
    # factors as (T,E) and fuses into the einsum — no second (T,E,C)
    return jnp.einsum(
        "tec,te,ech->th", dispatch.astype(dtype), combine_w.astype(dtype), y
    )
