"""Pipeline parallelism: GPipe-schedule microbatch streaming over the `pp`
mesh axis.

The analog of the reference's `AutoPipeline` on torch.distributed.pipelining
(reference: nemo_automodel/components/distributed/pipelining/
autopipeline.py:49, functional.py:98 layer-FQN splitting, :777 schedule
builder). TPU-native design — there is no runtime pipelining framework to
call; the schedule is compiled:

- Layer weights stay STACKED (L, ...) and shard dim 0 over `pp` (the
  logical `layers` axis maps to the pp mesh axis), so "splitting the model
  into stages" is a sharding annotation, not a graph surgery.
- The whole pipeline is one `shard_map`: each stage scans its local layer
  stack; activations hop stage→stage with `lax.ppermute` (ICI neighbor
  traffic, the p2p `send/recv` analog); a `lax.scan` over
  (num_microbatches + num_stages - 1) ticks realizes the GPipe schedule.
- Backward is the transposed program — autodiff of ppermute/scan gives the
  reverse schedule for free, with weight-grad psums over the data axes
  inserted by shard_map's transpose.
- Embedding / final-norm / loss run OUTSIDE the shard_map under plain GSPMD
  (they are dp/cp-sharded elementwise-ish work).

Round-1 scope: pure pp × dp (tp=1, cp=1 inside the pipeline); interleaved /
1F1B schedules and tp-in-pipeline come later. The bubble fraction is the
GPipe (P-1)/(M+P-1).
"""

from __future__ import annotations

import logging
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from automodel_tpu.distributed.mesh import MeshContext

logger = logging.getLogger(__name__)


def _check_microbatch_split(B: int, M: int, mesh_ctx, batch_axes) -> None:
    """The microbatch dim splits the GLOBAL batch, and each microbatch is
    still sharded over the data axes — so B must divide by M·dp_total.
    Validate eagerly with an actionable message (the raw shard_map
    divisibility error names in_specs, not the config knobs)."""
    if B % M != 0:
        raise ValueError(f"batch {B} must divide into {M} pipeline microbatches")
    dp_total = 1
    for ax in batch_axes:
        dp_total *= mesh_ctx.sizes.get(ax, 1)
    if (B // M) % dp_total != 0:
        raise ValueError(
            f"per-microbatch batch {B}//{M}={B // M} must be divisible by the "
            f"data-parallel extent {dp_total} ({'×'.join(batch_axes)}); raise "
            "dataloader.microbatch_size or lower pipeline_microbatches"
        )


def pipeline_bubble_fraction(num_microbatches: int, num_stages: int) -> float:
    """Idle fraction of the schedule span — (P-1)/(M+P-1) for both GPipe
    and non-interleaved 1F1B (1F1B buys memory, not bubble)."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipeline_layers(
    h: jnp.ndarray,            # (B, S, H) embedded activations (global)
    positions: jnp.ndarray,    # (B, S) int32
    segment_ids: jnp.ndarray,  # (B, S) int32
    stacked_params: Any,       # layer stack, leaves (L, ...), L % pp == 0
    layer_fn: Callable,        # (h, layer_params, positions, segment_ids) -> h
    mesh_ctx: MeshContext,
    num_microbatches: int,
    batch_axes: tuple = ("dp_replicate", "dp_shard", "ep"),
    remat_policy: str | None = "full",
    param_logical_specs: Any = None,
    layer_aux: bool = False,
    extras_specs: Any = None,
    token_mask: jnp.ndarray | None = None,
):
    """Run the stacked layers as a pp-staged pipeline; returns (B, S, H).

    positions/segment_ids travel with their microbatch through the ring so
    every stage masks with the right coordinates.

    Composition: the seq dim stays sharded on `cp` (layer_fn must run the
    in-shard ring attention — decoder `manual=True` mode); head/mlp param
    dims stay sharded on `tp` when `param_logical_specs` names them
    (layer_fn psums the partial o/down projections over tp).

    `layer_aux=True` switches the layer contract to
    `layer_fn(h, lp, pos, seg) -> (h, aux_scalar, extras_pytree)` — the MoE
    mode: per-layer load-balance losses accumulate across (stage,
    microbatch) into one global scalar — the MEAN over (data-shard,
    microbatch) token chunks, summed over layers (psum over pp + token
    axes, then / n_chunks). The switch loss is a product of per-token
    means, so the global-gate value is not recoverable from chunk scalars;
    the chunk-mean is the standard per-microbatch estimator (equal to the
    global value under uniform routing stats) — and the
    per-layer `extras` leaves (e.g. tokens_per_expert (E,)) stack over the
    layer dim and come back (L, ...) with `extras_specs` out-specs (use
    P("pp", ...) for the stacked layer dim). Returns (out, aux, extras).

    `token_mask` ((B, S) bool, False = pad/ignored; layer_aux mode only)
    extends the contract to `layer_fn(h, lp, pos, seg, mask)` so routing /
    aux stats exclude masked tokens, matching the GSPMD scan path. The mask
    does NOT ride the ppermute ring: every pp rank holds all microbatches'
    token arrays (same in_spec as positions), so stage p just indexes its
    current microbatch `t - p` directly.
    """
    pp = mesh_ctx.sizes["pp"]
    B, S, H = h.shape
    M = num_microbatches
    _check_microbatch_split(B, M, mesh_ctx, batch_axes)
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % pp == 0, f"{L} layers not divisible by pp={pp}"
    logger.info(
        "pipeline(gpipe): pp=%d M=%d bubble=%.3f",
        pp, M, pipeline_bubble_fraction(M, pp),
    )

    h_mb = h.reshape(M, B // M, S, H)
    pos_mb = positions.reshape(M, B // M, S)
    seg_mb = segment_ids.reshape(M, B // M, S)
    has_mask = layer_aux and token_mask is not None
    n_chunks = M * math.prod(
        mesh_ctx.sizes[a] for a in tuple(batch_axes) + ("cp",)
    )

    def run(h_mb, pos_mb, seg_mb, params_local, *maybe_mask):
        # inside shard_map: h_mb (M, B_loc, S, H); params leaves (L/pp, ...)
        p_idx = lax.axis_index("pp")
        n_stage = lax.axis_size("pp")
        T = M + n_stage - 1
        mask_mb = maybe_mask[0] if has_mask else None

        def apply_stage(x, pos, seg, tm=None):
            from automodel_tpu.models.common.layers import maybe_remat

            if layer_aux:
                def body(c, lp):
                    y, a, e = (
                        layer_fn(c, lp, pos, seg, tm)
                        if has_mask else layer_fn(c, lp, pos, seg)
                    )
                    return y, (a, e)

                y, (auxs, extras) = lax.scan(
                    maybe_remat(body, remat_policy), x, params_local
                )
                return y, jnp.sum(auxs).astype(jnp.float32), extras

            def body(c, lp):
                return layer_fn(c, lp, pos, seg), None

            y, _ = lax.scan(maybe_remat(body, remat_policy), x, params_local)
            return y, jnp.float32(0.0), ()

        if layer_aux:
            ex_shapes = jax.eval_shape(
                lambda p: apply_stage(
                    h_mb[0], pos_mb[0], seg_mb[0],
                    mask_mb[0] if has_mask else None,
                )[2],
                params_local,
            )
            ex0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), ex_shapes)
        else:
            ex0 = ()

        def tick(carry, t):
            (act, pos, seg), outputs, aux_acc, ex_acc = carry
            m = jnp.clip(t, 0, M - 1)
            is_first = p_idx == 0
            x = jnp.where(is_first, h_mb[m], act)
            pos = jnp.where(is_first, pos_mb[m], pos)
            seg = jnp.where(is_first, seg_mb[m], seg)
            # stage p works on microbatch t - p; its token mask is read from
            # the (rank-complete) mask_mb rather than streamed with the act
            tm = (
                mask_mb[jnp.clip(t - p_idx, 0, M - 1)] if has_mask else None
            )
            y, aux, ex = apply_stage(x, pos, seg, tm)
            # stage p holds real data for microbatch t - p on ticks
            # p <= t < p + M; off-window ticks recompute clipped garbage that
            # must not leak into the aux/stat accumulators
            valid = jnp.logical_and(t >= p_idx, t - p_idx < M)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            ex_acc = jax.tree.map(
                lambda a, e: a + jnp.where(valid, e, jnp.zeros_like(e)),
                ex_acc, ex,
            )
            out_idx = t - (n_stage - 1)
            write = jnp.logical_and(out_idx >= 0, p_idx == n_stage - 1)
            outputs = lax.cond(
                write,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, M - 1), 0
                ),
                lambda o: o,
                outputs,
            )
            perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
            stream = lax.ppermute((y, pos, seg), "pp", perm)
            return (stream, outputs, aux_acc, ex_acc), None

        init_stream = (jnp.zeros_like(h_mb[0]), pos_mb[0], seg_mb[0])
        (_, outputs, aux_acc, ex_acc), _ = lax.scan(
            tick,
            (init_stream, jnp.zeros_like(h_mb), jnp.zeros((), jnp.float32),
             ex0),
            jnp.arange(T),
        )
        # Only the last stage's buffer is real; every pp rank needs it because
        # the head (final norm + lm-head/loss) runs under GSPMD outside this
        # shard_map with pp unmapped. masked-psum IS the broadcast: an
        # all-reduce of one activation buffer moves the same bytes as any
        # one-to-all broadcast over the ring, and XLA lowers it to one
        # collective — the zeros are the selection mask, not wasted traffic.
        outputs = lax.psum(
            jnp.where(p_idx == n_stage - 1, outputs, jnp.zeros_like(outputs)), "pp"
        )
        data_axes = tuple(batch_axes) + ("cp",)
        # each stage's aux covers its own layers → sum over pp; each token
        # shard routes its own tokens → mean over the (data shard,
        # microbatch) chunks (replicated over tp already — tp ranks see
        # identical tokens)
        aux_acc = lax.psum(aux_acc, data_axes + ("pp",)) / n_chunks
        ex_acc = jax.tree.map(lambda e: lax.psum(e, data_axes), ex_acc)
        return outputs, aux_acc, ex_acc

    act_spec = P(None, batch_axes, "cp", None)  # (M, B, S_cp, H)
    tok_spec = P(None, batch_axes, "cp")
    mask_ops = (token_mask.reshape(M, B // M, S),) if has_mask else ()
    out, aux, extras = jax.shard_map(
        run,
        mesh=mesh_ctx.mesh,
        in_specs=(
            act_spec, tok_spec, tok_spec,
            _param_specs_pp(stacked_params, param_logical_specs),
        ) + ((tok_spec,) if has_mask else ()),
        out_specs=(act_spec, P(), extras_specs if layer_aux else ()),
        check_vma=False,
    )(h_mb, pos_mb, seg_mb, stacked_params, *mask_ops)
    out = out.reshape(B, S, H)
    if layer_aux:
        return out, aux, extras
    return out


# ---------------------------------------------------------------------------
# interleaved (virtual-stage) 1F1B schedule tables
# ---------------------------------------------------------------------------
def interleaved_1f1b_tables(num_microbatches: int, num_devices: int, virtual: int):
    """Greedy simulation of interleaved 1F1B over S = P·V virtual stages,
    stage s living on device s % P (the Megatron cyclic mapping; reference:
    distributed/pipelining/functional.py:182 virtual stages + :777
    ScheduleInterleaved1F1B).

    Returns (fwd_tab, bwd_tab): int32 arrays (T, P) encoding the action per
    half-tick as `v * M + m` (virtual-stage-major) or -1 for idle. One fwd
    and one bwd slot per device per tick; every dependency is satisfied with
    ≥ 1 tick of latency so the +1/-1 ppermute streams deliver in time.

    Policy: depth-first over microbatch GROUPS of size P per virtual stage
    (Megatron's ordering), bwd-first once a stage's backward is ready —
    giving the interleaved bubble ≈ (P-1)/(V·M) instead of (P-1)/(M+P-1).
    """
    M, P, V = num_microbatches, num_devices, virtual
    S = P * V
    not_done = 10 ** 9
    fwd_done = [[not_done] * M for _ in range(S)]
    bwd_done = [[not_done] * M for _ in range(S)]
    fwd_next = [0] * S
    bwd_next = [0] * S

    def stage_key(s: int, m: int, fwd: bool) -> tuple:
        # depth-first group ordering: finish group g of vstage v before
        # starting group g of vstage v+1's successors; backward prefers the
        # LAST vstage first (it becomes ready first)
        g = m // P
        v = s // P
        return (g, v if fwd else (V - 1 - v), m % P)

    fwd_rows, bwd_rows = [], []
    t = 0
    while any(bwd_next[s] < M for s in range(S)) and t < 8 * V * (M + P):
        frow, brow = [-1] * P, [-1] * P
        for p in range(P):
            # candidate forward actions on this device, best schedule-key first
            f_cands = []
            b_cands = []
            for v in range(V):
                s = v * P + p
                f = fwd_next[s]
                if f < M and (s == 0 or fwd_done[s - 1][f] < t):
                    # in-flight bound per stage chain: keep ≤ S - s microbatches
                    # between this stage's fwd and its bwd (generalizes the
                    # non-interleaved P - p bound; also keys the stash mod)
                    if (f - bwd_next[s]) < (S - s):
                        f_cands.append((stage_key(s, f, True), s, f))
                b = bwd_next[s]
                if b < M and fwd_done[s][b] < t and (
                    s == S - 1 or bwd_done[s + 1][b] < t
                ):
                    b_cands.append((stage_key(s, b, False), s, b))
            if b_cands:
                _, s, b = min(b_cands)
                brow[p] = (s // P) * M + b
                bwd_done[s][b] = t
                bwd_next[s] += 1
            if f_cands:
                # bwd-first steady state: allow the fwd too (separate slot)
                _, s, f = min(f_cands)
                frow[p] = (s // P) * M + f
                fwd_done[s][f] = t
                fwd_next[s] += 1
        fwd_rows.append(frow)
        bwd_rows.append(brow)
        t += 1
    assert all(bwd_next[s] == M and fwd_next[s] == M for s in range(S)), (
        f"interleaved schedule incomplete for M={M} P={P} V={V}: "
        f"fwd={fwd_next} bwd={bwd_next}"
    )
    import numpy as np

    return np.asarray(fwd_rows, np.int32), np.asarray(bwd_rows, np.int32)


# ---------------------------------------------------------------------------
# 1F1B schedule (memory-capped training pipeline)
# ---------------------------------------------------------------------------
def one_f_one_b_tables(num_microbatches: int, num_stages: int):
    """Static per-half-tick action tables for non-interleaved 1F1B.

    The schedule builder analog (reference: distributed/pipelining/
    functional.py:777): greedy simulation of Megatron's policy — stage p
    warms up with (P-1-p) forwards, then alternates 1 fwd / 1 bwd, then
    drains. Returns (fwd_mb, bwd_mb): int arrays (T, P) holding the
    microbatch id acted on, or -1 for an idle slot. At most one action per
    (tick, stage); dependencies are satisfied with ≥1-tick latency, so
    ppermute streams inserted between ticks carry the data in time.
    """
    M, P = num_microbatches, num_stages
    not_done = 10 ** 9
    fwd_done = [[not_done] * M for _ in range(P)]  # completion half-tick
    bwd_done = [[not_done] * M for _ in range(P)]
    next_f = [0] * P
    next_b = [0] * P
    warmup_left = [P - 1 - p for p in range(P)]
    fwd_rows, bwd_rows = [], []
    t = 0
    while any(next_b[p] < M for p in range(P)) and t < 4 * (M + P):
        frow, brow = [-1] * P, [-1] * P
        for p in range(P):
            f, b = next_f[p], next_b[p]
            # 1F1B memory bound: at most P-p microbatches in flight at stage
            # p (warmup depth + the steady-state one) — also what keeps the
            # mod-P stash indexing collision-free
            f_ready = (
                f < M
                and (p == 0 or fwd_done[p - 1][f] < t)
                and (f - b) < (P - p)
            )
            b_ready = (
                b < M
                and fwd_done[p][b] < t
                and (p == P - 1 or bwd_done[p + 1][b] < t)
            )
            # policy: forwards during warmup, then bwd-first (1F1B steady)
            if warmup_left[p] > 0 and f_ready:
                frow[p] = f
                fwd_done[p][f] = t
                next_f[p] += 1
                warmup_left[p] -= 1
            elif b_ready:
                brow[p] = b
                bwd_done[p][b] = t
                next_b[p] += 1
            elif f_ready:
                frow[p] = f
                fwd_done[p][f] = t
                next_f[p] += 1
        fwd_rows.append(frow)
        bwd_rows.append(brow)
        t += 1
    assert all(next_b[p] == M and next_f[p] == M for p in range(P)), (
        f"1F1B schedule did not complete for M={M} P={P}: "
        f"fwd={next_f} bwd={next_b} — silent gradient loss prevented"
    )
    import numpy as np

    return np.asarray(fwd_rows, np.int32), np.asarray(bwd_rows, np.int32)


def pipeline_train_1f1b(
    h: jnp.ndarray,            # (B, S, H) embedded activations (global)
    positions: jnp.ndarray,    # (B, S)
    segment_ids: jnp.ndarray,  # (B, S)
    labels: jnp.ndarray,       # (B, S) int32 (-100 = ignored)
    stacked_params: Any,       # leaves (L, ...), L % pp == 0
    layer_fn: Callable,        # (h, layer_params, positions, segment_ids) -> h
    head_params: Any,
    head_loss_fn: Callable,    # (h_mb, head_params, labels_mb) -> scalar SUM loss
    mesh_ctx: MeshContext,
    num_microbatches: int,
    batch_axes: tuple = ("dp_replicate", "dp_shard", "ep"),
    param_logical_specs: Any = None,
    aux_scale: jnp.ndarray | None = None,
    extras_specs: Any = None,
) -> tuple:
    """1F1B training pipeline: returns (loss_sum, d_h, layer_grads, head_grads).

    Unlike `pipeline_layers` (GPipe + autodiff, which stashes all M
    microbatch boundary activations), this runs an explicit fwd/bwd
    interleave with per-stage `jax.vjp`: at most `pp` microbatch inputs are
    stashed per stage — the 1F1B memory bound — at the same bubble fraction
    (P-1)/(M+P-1). The head (final-norm + lm-head + loss) runs fused into
    the last stage's backward, so logits are never stored.

    Grads come back already reduced: layer_grads sharded (pp on dim 0),
    head_grads and d_h replicated. Compose with `jax.vjp` of the embedding
    outside. Loss/grad parity vs end-to-end autodiff: tests/unit/test_pp.py.

    `aux_scale` (a traced scalar, e.g. the global label-token count) enables
    the MoE layer contract `layer_fn -> (h, aux, extras)`: every stage's
    backward adds `aux_scale · aux` into the differentiated scalar, so the
    expert-dispatch A2A and its gradients stay confined to that stage's step
    while load-balance gradients flow. The per-layer `extras` pytree (e.g.
    tokens_per_expert (E,)) accumulates over microbatches, stacks over the
    stage's layers, and is returned as a fifth output with `extras_specs`
    out-specs (P("pp", ...) on the stacked layer dim). The returned loss is
    then ce_sum + aux_scale·Σaux — the `combine_losses` contract.
    """
    pp = mesh_ctx.sizes["pp"]
    B, S, H = h.shape
    M = num_microbatches
    has_aux = aux_scale is not None
    _check_microbatch_split(B, M, mesh_ctx, batch_axes)
    fwd_tab, bwd_tab = one_f_one_b_tables(M, pp)
    T = fwd_tab.shape[0]
    logger.info(
        "pipeline(1f1b): pp=%d M=%d ticks=%d bubble=%.3f",
        pp, M, T, pipeline_bubble_fraction(M, pp),
    )

    h_mb = h.reshape(M, B // M, S, H)
    pos_mb = positions.reshape(M, B // M, S)
    seg_mb = segment_ids.reshape(M, B // M, S)
    lab_mb = labels.reshape(M, B // M, S)
    scale_in = jnp.asarray(aux_scale if has_aux else 0.0, jnp.float32)

    def run(h_mb, pos_mb, seg_mb, lab_mb, params_local, head_local, scale):
        p_idx = lax.axis_index("pp")
        n_stage = lax.axis_size("pp")
        is_last = p_idx == n_stage - 1
        ftab = jnp.asarray(fwd_tab)
        btab = jnp.asarray(bwd_tab)

        def stage(x, params, pos, seg):
            if has_aux:
                def body(c, lp):
                    y, a, e = layer_fn(c, lp, pos, seg)
                    return y, (a, e)

                y, (auxs, extras) = lax.scan(body, x, params)
                return y, jnp.sum(auxs).astype(jnp.float32), extras

            def body(c, lp):
                return layer_fn(c, lp, pos, seg), None

            y, _ = lax.scan(body, x, params)
            return y, jnp.float32(0.0), ()

        def full_bwd(x, params, head, pos, seg, lab, dy):
            """Backward of one microbatch at this stage: last stage fuses the
            head+loss (ignoring dy), others pull the streamed cotangent. The
            has_aux report carries (loss_contribution, per-layer extras).

            stage() is hoisted OUT of the is_last cond: its collectives (cp
            ring hops, tp psums, ep A2As) must execute rank-uniformly — pp
            ranks take different branches, and branch-divergent collectives
            deadlock the CPU runtime's global rendezvous (reproduced:
            pp×cp 1F1B dryrun hang). The cond keeps only local head/vdot
            math, so the head matmul still runs on the last stage alone."""

            def fwd(xx, pp_, hh_):
                y, aux, ex = stage(xx, pp_, pos, seg)
                sa = aux * scale
                s = lax.cond(
                    is_last,
                    lambda yy, hh: head_loss_fn(yy, hh, lab).astype(jnp.float32),
                    lambda yy, hh: jnp.vdot(
                        yy.astype(jnp.float32), dy.astype(jnp.float32)
                    ),
                    y, hh_,
                ) + sa
                return s, (jnp.where(is_last, s, sa), ex)

            out, vjp, (rep, extras) = jax.vjp(fwd, x, params, head, has_aux=True)
            dx, dparams, dhead = vjp(jnp.ones((), out.dtype))
            return rep, dx, dparams, dhead, extras

        zeros_g = jax.tree.map(jnp.zeros_like, params_local)
        zeros_h = jax.tree.map(jnp.zeros_like, head_local)
        stash0 = jnp.zeros((n_stage,) + h_mb.shape[1:], h_mb.dtype)
        ex0 = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(
                lambda p: stage(h_mb[0], p, pos_mb[0], seg_mb[0])[2],
                params_local,
            ),
        )

        def tick(carry, t):
            (fstream, bstream, fstash, bstash, stash,
             gacc, hacc, dh_acc, loss_acc, ex_acc) = carry
            mf = jnp.take(ftab[t], p_idx)
            mb = jnp.take(btab[t], p_idx)

            # ---- bank arrivals (streams hold the NEIGHBOR's t-1 output;
            # consumption may be ticks later, so stash by microbatch id) ----
            prev_t = jnp.maximum(t - 1, 0)
            from_prev = jnp.take(ftab[prev_t], (p_idx - 1) % n_stage)
            f_arrived = jnp.logical_and(
                jnp.logical_and(t > 0, p_idx > 0), from_prev >= 0
            )
            fstash = jnp.where(
                f_arrived,
                lax.dynamic_update_index_in_dim(
                    fstash, fstream, jnp.clip(from_prev, 0, M - 1) % n_stage, 0
                ),
                fstash,
            )
            from_next = jnp.take(btab[prev_t], (p_idx + 1) % n_stage)
            b_arrived = jnp.logical_and(
                jnp.logical_and(t > 0, p_idx < n_stage - 1), from_next >= 0
            )
            bstash = jnp.where(
                b_arrived,
                lax.dynamic_update_index_in_dim(
                    bstash, bstream, jnp.clip(from_next, 0, M - 1) % n_stage, 0
                ),
                bstash,
            )

            # ---- forward slot ----
            mf_c = jnp.clip(mf, 0, M - 1)
            x_in = jnp.where(p_idx == 0, h_mb[mf_c], fstash[mf_c % n_stage])
            stash = jnp.where(
                mf >= 0,
                lax.dynamic_update_index_in_dim(stash, x_in, mf_c % n_stage, 0),
                stash,
            )
            y, _, _ = stage(x_in, params_local, pos_mb[mf_c], seg_mb[mf_c])
            fout = jnp.where(mf >= 0, y, jnp.zeros_like(y))

            # ---- backward slot ----
            mb_c = jnp.clip(mb, 0, M - 1)
            x_b = stash[mb_c % n_stage]
            loss_i, dx, dparams, dhead, ex = full_bwd(
                x_b, params_local, head_local,
                pos_mb[mb_c], seg_mb[mb_c], lab_mb[mb_c], bstash[mb_c % n_stage],
            )
            do_b = mb >= 0
            gacc = jax.tree.map(
                lambda a, g: a + jnp.where(do_b, g, jnp.zeros_like(g)), gacc, dparams
            )
            hacc = jax.tree.map(
                lambda a, g: a + jnp.where(do_b, g, jnp.zeros_like(g)), hacc, dhead
            )
            ex_acc = jax.tree.map(
                lambda a, e: a + jnp.where(do_b, e, jnp.zeros_like(e)), ex_acc, ex
            )
            dh_acc = jnp.where(
                jnp.logical_and(do_b, p_idx == 0),
                lax.dynamic_update_index_in_dim(dh_acc, dx, mb_c, 0),
                dh_acc,
            )
            loss_acc = loss_acc + jnp.where(do_b, loss_i, 0.0)

            fwd_perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
            bwd_perm = [((i + 1) % n_stage, i) for i in range(n_stage)]
            fstream = lax.ppermute(fout, "pp", fwd_perm)
            bout = jnp.where(do_b, dx, jnp.zeros_like(dx))
            bstream = lax.ppermute(bout, "pp", bwd_perm)
            return (
                fstream, bstream, fstash, bstash, stash,
                gacc, hacc, dh_acc, loss_acc, ex_acc,
            ), None

        carry0 = (
            jnp.zeros_like(h_mb[0]),
            jnp.zeros_like(h_mb[0]),
            stash0,
            stash0,
            stash0,
            zeros_g,
            zeros_h,
            jnp.zeros_like(h_mb),
            jnp.zeros((), jnp.float32),
            ex0,
        )
        (_, _, _, _, _, gacc, hacc, dh_acc, loss_acc, ex_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T)
        )
        # Manual-collective grad reduction (the transpose of shard_map would
        # have inserted these in the autodiff path): param grads are partial
        # per data shard → psum over batch+cp; NOT over tp (activations are
        # tp-replicated so per-rank grads are already correct for each
        # rank's param slice) and NOT over axes a leaf is sharded on (an
        # ep-sharded expert slice already holds its complete grad — every
        # token routed to it arrived through the A2A). Layer grads stay on
        # their own pp stage; head grads / loss / d_h are made consistent
        # across pp.
        data_axes = tuple(batch_axes) + ("cp",)
        gacc = jax.tree.map(
            lambda g, s: lax.psum(g, _grad_reduce_axes(s, data_axes)),
            gacc, pspecs,
        )
        hacc = jax.tree.map(lambda g: lax.psum(g, data_axes + ("pp",)), hacc)
        dh_acc = lax.psum(dh_acc, "pp")
        loss_acc = lax.psum(loss_acc, data_axes + ("pp",))
        ex_acc = jax.tree.map(lambda e: lax.psum(e, data_axes), ex_acc)
        return loss_acc, dh_acc, gacc, hacc, ex_acc

    act_spec = P(None, batch_axes, "cp", None)
    tok_spec = P(None, batch_axes, "cp")
    pspecs = _param_specs_pp(stacked_params, param_logical_specs)
    hspec = jax.tree.map(lambda x: P(*([None] * x.ndim)), head_params)
    loss, dh, gl, gh, ex = jax.shard_map(
        run,
        mesh=mesh_ctx.mesh,
        in_specs=(act_spec, tok_spec, tok_spec, tok_spec, pspecs, hspec, P()),
        out_specs=(P(), act_spec, pspecs, hspec,
                   extras_specs if has_aux else ()),
        check_vma=False,
    )(h_mb, pos_mb, seg_mb, lab_mb, stacked_params, head_params, scale_in)
    if has_aux:
        return loss, dh.reshape(B, S, H), gl, gh, ex
    return loss, dh.reshape(B, S, H), gl, gh


# ---------------------------------------------------------------------------
# zero-bubble (ZB-H1) schedule: backward split into B (input-grad) and W
# (weight-grad) passes; W fills the drain bubbles
# ---------------------------------------------------------------------------
def zero_bubble_tables(num_microbatches: int, num_stages: int):
    """Static per-tick action tables for the ZB-H1 zero-bubble schedule
    (Qi et al. 2023; the reference exposes it as the `zbv` option of
    `build_pipeline_schedule`, distributed/pipelining/functional.py:777).

    The backward splits into B (activation/input gradient — on the critical
    path, streamed upstream immediately) and W (weight gradient — no
    dataflow successors, so it can fill what would otherwise be drain
    bubbles). Greedy per-device policy: warmup forwards like 1F1B, then
    B > F > W priority; W(m) only after the same stage's B(m). Returns
    (fwd, bwd, wgt) int arrays (T, P): microbatch id or -1. Stash capacity
    is bounded by the (·) < P constraints below, which keep the mod-P stash
    slots (inputs held F→W, cotangents held B→W) collision-free.
    """
    M, P = num_microbatches, num_stages
    not_done = 10 ** 9
    fwd_done = [[not_done] * M for _ in range(P)]
    bwd_done = [[not_done] * M for _ in range(P)]
    next_f, next_b, next_w = [0] * P, [0] * P, [0] * P
    warmup_left = [P - 1 - p for p in range(P)]
    fwd_rows, bwd_rows, wgt_rows = [], [], []
    t = 0
    while any(next_w[p] < M for p in range(P)) and t < 6 * (M + P):
        frow, brow, wrow = [-1] * P, [-1] * P, [-1] * P
        for p in range(P):
            f, b, w = next_f[p], next_b[p], next_w[p]
            f_ready = (
                f < M
                and (p == 0 or fwd_done[p - 1][f] < t)
                and (f - b) < (P - p)   # 1F1B in-flight bound
                and (f - w) < P         # input stash held until W
            )
            b_ready = (
                b < M
                and fwd_done[p][b] < t
                and (p == P - 1 or bwd_done[p + 1][b] < t)
                and (b - w) < P         # cotangent stash held until W
            )
            w_ready = w < M and bwd_done[p][w] < t
            if warmup_left[p] > 0 and f_ready:
                frow[p] = f
                fwd_done[p][f] = t
                next_f[p] += 1
                warmup_left[p] -= 1
            elif b_ready:
                brow[p] = b
                bwd_done[p][b] = t
                next_b[p] += 1
            elif f_ready:
                frow[p] = f
                fwd_done[p][f] = t
                next_f[p] += 1
            elif w_ready:
                wrow[p] = w
                next_w[p] += 1
        fwd_rows.append(frow)
        bwd_rows.append(brow)
        wgt_rows.append(wrow)
        t += 1
    assert all(next_w[p] == M and next_b[p] == M for p in range(P)), (
        f"zero-bubble schedule did not complete for M={M} P={P}: "
        f"f={next_f} b={next_b} w={next_w} — silent gradient loss prevented"
    )
    import numpy as np

    return (
        np.asarray(fwd_rows, np.int32),
        np.asarray(bwd_rows, np.int32),
        np.asarray(wgt_rows, np.int32),
    )


def pipeline_train_zb(
    h: jnp.ndarray,
    positions: jnp.ndarray,
    segment_ids: jnp.ndarray,
    labels: jnp.ndarray,
    stacked_params: Any,
    layer_fn: Callable,
    head_params: Any,
    head_loss_fn: Callable,
    mesh_ctx: MeshContext,
    num_microbatches: int,
    batch_axes: tuple = ("dp_replicate", "dp_shard", "ep"),
    param_logical_specs: Any = None,
    aux_scale: jnp.ndarray | None = None,
    extras_specs: Any = None,
) -> tuple:
    """Zero-bubble (ZB-H1) training pipeline — pipeline_train_1f1b's
    interface with the backward split into B and W passes, including the
    MoE layer-aux contract (`aux_scale`/`extras_specs`, see 1F1B): aux
    gradients split naturally — B's x-only vjp carries the aux input-grad,
    W's param-only vjp the aux weight-grad; extras are reported by B.

    B computes only the input gradient (XLA dead-code-eliminates the
    weight-grad matmuls from the x-only vjp) and streams it upstream at
    1F1B latency; W re-linearizes against the stashed microbatch input and
    stashed cotangent to produce the weight gradients in the schedule's
    idle slots. Memory matches 1F1B's O(P) activation stash plus an O(P)
    cotangent stash (the ZB-H1 point: no extra in-flight microbatches).

    HONEST SCOPE: this executor runs all three lanes (F, B, W) where-masked
    every tick inside one lax.scan, so each tick costs a constant
    F + split-backward regardless of the schedule's idle pattern — exactly
    like pipeline_train_1f1b ("1F1B buys memory, not bubble" above). The
    zb value here is schedule parity with the reference's zbv option
    (pipelining/functional.py:777) and the B/W machinery a future
    branch-per-tick executor needs for the actual bubble win; wall-clock
    today tracks the table span at the same per-tick cost.
    """
    pp = mesh_ctx.sizes["pp"]
    B, S, H = h.shape
    M = num_microbatches
    has_aux = aux_scale is not None
    _check_microbatch_split(B, M, mesh_ctx, batch_axes)
    fwd_tab, bwd_tab, wgt_tab = zero_bubble_tables(M, pp)
    T = fwd_tab.shape[0]
    logger.info(
        "pipeline(zb): pp=%d M=%d ticks=%d (1f1b bubble %.3f; W fills drain)",
        pp, M, T, pipeline_bubble_fraction(M, pp),
    )

    h_mb = h.reshape(M, B // M, S, H)
    pos_mb = positions.reshape(M, B // M, S)
    seg_mb = segment_ids.reshape(M, B // M, S)
    lab_mb = labels.reshape(M, B // M, S)
    scale_in = jnp.asarray(aux_scale if has_aux else 0.0, jnp.float32)

    def run(h_mb, pos_mb, seg_mb, lab_mb, params_local, head_local, scale):
        p_idx = lax.axis_index("pp")
        n_stage = lax.axis_size("pp")
        is_last = p_idx == n_stage - 1
        ftab = jnp.asarray(fwd_tab)
        btab = jnp.asarray(bwd_tab)
        wtab = jnp.asarray(wgt_tab)

        def stage(x, params, pos, seg):
            if has_aux:
                def body(c, lp):
                    y, a, e = layer_fn(c, lp, pos, seg)
                    return y, (a, e)

                y, (auxs, extras) = lax.scan(body, x, params)
                return y, jnp.sum(auxs).astype(jnp.float32), extras

            def body(c, lp):
                return layer_fn(c, lp, pos, seg), None

            y, _ = lax.scan(body, x, params)
            return y, jnp.float32(0.0), ()

        def b_pass(x, pos, seg, lab, dy):
            """Input-grad-only backward (weight grads are W's job). stage()
            runs OUTSIDE the is_last cond — collectives must be rank-uniform
            (see pipeline_train_1f1b.full_bwd)."""

            def fwd(xx):
                y, aux, ex = stage(xx, params_local, pos, seg)
                sa = aux * scale
                s = lax.cond(
                    is_last,
                    lambda yy: head_loss_fn(yy, head_local, lab).astype(
                        jnp.float32
                    ),
                    lambda yy: jnp.vdot(
                        yy.astype(jnp.float32), dy.astype(jnp.float32)
                    ),
                    y,
                ) + sa
                return s, (jnp.where(is_last, s, sa), ex)

            out, vjp, (rep, ex) = jax.vjp(fwd, x, has_aux=True)
            (dx,) = vjp(jnp.ones((), out.dtype))
            return rep, dx, ex

        def w_pass(x, pos, seg, lab, dy):
            """Weight-grad-only backward against the stashed input/cotangent.
            Same hoisted-stage structure as b_pass."""

            def fwd(pp_, hh_):
                y, aux, _ = stage(x, pp_, pos, seg)
                sa = aux * scale
                return lax.cond(
                    is_last,
                    lambda yy, hh: head_loss_fn(yy, hh, lab).astype(jnp.float32),
                    lambda yy, hh: jnp.vdot(
                        yy.astype(jnp.float32), dy.astype(jnp.float32)
                    ),
                    y, hh_,
                ) + sa

            _, vjp = jax.vjp(fwd, params_local, head_local)
            return vjp(jnp.ones((), jnp.float32))

        zeros_g = jax.tree.map(jnp.zeros_like, params_local)
        zeros_h = jax.tree.map(jnp.zeros_like, head_local)
        stash0 = jnp.zeros((n_stage,) + h_mb.shape[1:], h_mb.dtype)
        ex0 = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(
                lambda p: stage(h_mb[0], p, pos_mb[0], seg_mb[0])[2],
                params_local,
            ),
        )

        def tick(carry, t):
            (fstream, bstream, fstash, bstash, stash,
             gacc, hacc, dh_acc, loss_acc, ex_acc) = carry
            mf = jnp.take(ftab[t], p_idx)
            mb = jnp.take(btab[t], p_idx)
            mw = jnp.take(wtab[t], p_idx)

            prev_t = jnp.maximum(t - 1, 0)
            from_prev = jnp.take(ftab[prev_t], (p_idx - 1) % n_stage)
            f_arrived = jnp.logical_and(
                jnp.logical_and(t > 0, p_idx > 0), from_prev >= 0
            )
            fstash = jnp.where(
                f_arrived,
                lax.dynamic_update_index_in_dim(
                    fstash, fstream, jnp.clip(from_prev, 0, M - 1) % n_stage, 0
                ),
                fstash,
            )
            from_next = jnp.take(btab[prev_t], (p_idx + 1) % n_stage)
            b_arrived = jnp.logical_and(
                jnp.logical_and(t > 0, p_idx < n_stage - 1), from_next >= 0
            )
            bstash = jnp.where(
                b_arrived,
                lax.dynamic_update_index_in_dim(
                    bstash, bstream, jnp.clip(from_next, 0, M - 1) % n_stage, 0
                ),
                bstash,
            )

            # ---- forward slot ----
            mf_c = jnp.clip(mf, 0, M - 1)
            x_in = jnp.where(p_idx == 0, h_mb[mf_c], fstash[mf_c % n_stage])
            stash = jnp.where(
                mf >= 0,
                lax.dynamic_update_index_in_dim(stash, x_in, mf_c % n_stage, 0),
                stash,
            )
            y, _, _ = stage(x_in, params_local, pos_mb[mf_c], seg_mb[mf_c])
            fout = jnp.where(mf >= 0, y, jnp.zeros_like(y))

            # ---- B slot: input grad only ----
            mb_c = jnp.clip(mb, 0, M - 1)
            loss_i, dx, ex = b_pass(
                stash[mb_c % n_stage], pos_mb[mb_c], seg_mb[mb_c],
                lab_mb[mb_c], bstash[mb_c % n_stage],
            )
            do_b = mb >= 0
            dh_acc = jnp.where(
                jnp.logical_and(do_b, p_idx == 0),
                lax.dynamic_update_index_in_dim(dh_acc, dx, mb_c, 0),
                dh_acc,
            )
            loss_acc = loss_acc + jnp.where(do_b, loss_i, 0.0)
            ex_acc = jax.tree.map(
                lambda a, e: a + jnp.where(do_b, e, jnp.zeros_like(e)), ex_acc, ex
            )

            # ---- W slot: weight grads against stashed input + cotangent ----
            mw_c = jnp.clip(mw, 0, M - 1)
            dparams, dhead = w_pass(
                stash[mw_c % n_stage], pos_mb[mw_c], seg_mb[mw_c],
                lab_mb[mw_c], bstash[mw_c % n_stage],
            )
            do_w = mw >= 0
            gacc = jax.tree.map(
                lambda a, g: a + jnp.where(do_w, g, jnp.zeros_like(g)), gacc, dparams
            )
            hacc = jax.tree.map(
                lambda a, g: a + jnp.where(do_w, g, jnp.zeros_like(g)), hacc, dhead
            )

            fwd_perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
            bwd_perm = [((i + 1) % n_stage, i) for i in range(n_stage)]
            fstream = lax.ppermute(fout, "pp", fwd_perm)
            bout = jnp.where(do_b, dx, jnp.zeros_like(dx))
            bstream = lax.ppermute(bout, "pp", bwd_perm)
            return (
                fstream, bstream, fstash, bstash, stash,
                gacc, hacc, dh_acc, loss_acc, ex_acc,
            ), None

        carry0 = (
            jnp.zeros_like(h_mb[0]),
            jnp.zeros_like(h_mb[0]),
            stash0,
            stash0,
            stash0,
            zeros_g,
            zeros_h,
            jnp.zeros_like(h_mb),
            jnp.zeros((), jnp.float32),
            ex0,
        )
        (_, _, _, _, _, gacc, hacc, dh_acc, loss_acc, ex_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T)
        )
        data_axes = tuple(batch_axes) + ("cp",)
        gacc = jax.tree.map(
            lambda g, s: lax.psum(g, _grad_reduce_axes(s, data_axes)),
            gacc, pspecs,
        )
        hacc = jax.tree.map(lambda g: lax.psum(g, data_axes + ("pp",)), hacc)
        dh_acc = lax.psum(dh_acc, "pp")
        loss_acc = lax.psum(loss_acc, data_axes + ("pp",))
        ex_acc = jax.tree.map(lambda e: lax.psum(e, data_axes), ex_acc)
        return loss_acc, dh_acc, gacc, hacc, ex_acc

    act_spec = P(None, batch_axes, "cp", None)
    tok_spec = P(None, batch_axes, "cp")
    pspecs = _param_specs_pp(stacked_params, param_logical_specs)
    hspec = jax.tree.map(lambda x: P(*([None] * x.ndim)), head_params)
    loss, dh, gl, gh, ex = jax.shard_map(
        run,
        mesh=mesh_ctx.mesh,
        in_specs=(act_spec, tok_spec, tok_spec, tok_spec, pspecs, hspec, P()),
        out_specs=(P(), act_spec, pspecs, hspec,
                   extras_specs if has_aux else ()),
        check_vma=False,
    )(h_mb, pos_mb, seg_mb, lab_mb, stacked_params, head_params, scale_in)
    if has_aux:
        return loss, dh.reshape(B, S, H), gl, gh, ex
    return loss, dh.reshape(B, S, H), gl, gh


def interleave_layer_order(num_layers: int, num_devices: int, virtual: int):
    """Row permutation putting stage s = ℓ // chunk on device s % P under
    contiguous pp sharding of dim 0: device p's rows become its V stage
    chunks in v order. Returns (perm, inv_perm) index arrays."""
    import numpy as np

    S = num_devices * virtual
    assert num_layers % S == 0, (num_layers, S)
    chunk = num_layers // S
    order = []
    for p in range(num_devices):
        for v in range(virtual):
            s = v * num_devices + p
            order.extend(range(s * chunk, (s + 1) * chunk))
    perm = np.asarray(order, np.int64)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(num_layers)
    return perm, inv


def pipeline_train_interleaved(
    h: jnp.ndarray,            # (B, S, H) embedded activations (global)
    positions: jnp.ndarray,
    segment_ids: jnp.ndarray,
    labels: jnp.ndarray,
    stacked_params: Any,       # leaves (L, ...), L % (pp·virtual) == 0
    layer_fn: Callable,
    head_params: Any,
    head_loss_fn: Callable,
    mesh_ctx: MeshContext,
    num_microbatches: int,
    virtual: int,
    batch_axes: tuple = ("dp_replicate", "dp_shard", "ep"),
    param_logical_specs: Any = None,
    aux_scale: jnp.ndarray | None = None,
    extras_specs: Any = None,
) -> tuple:
    """Interleaved (virtual-stage) 1F1B: S = pp·virtual stages mapped
    cyclically onto the pp ring (stage s on device s % pp) — the Megatron
    interleaved schedule (reference: pipelining/functional.py:777
    ScheduleInterleaved1F1B). Same contract as `pipeline_train_1f1b`; the
    bubble shrinks ≈ V× because each pipeline hop carries 1/V of the layer
    work. Layer stacks are row-permuted so contiguous pp sharding gives each
    device its V stage chunks (`interleave_layer_order`); returned layer
    grads are un-permuted back to natural order.

    KNOWN COST: the permute/unpermute pair reshards the layer stack across
    pp every step (two all-to-alls). Storing params in permuted order for
    the whole run (one-time setup permutation) removes it; so would folding
    the non-interleaved 1F1B into this implementation as the V=1 case —
    both are staged follow-ups.
    """
    pp = mesh_ctx.sizes["pp"]
    B, Sq, H = h.shape
    M = num_microbatches
    V = virtual
    Svirt = pp * V
    has_aux = aux_scale is not None
    _check_microbatch_split(B, M, mesh_ctx, batch_axes)
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % Svirt == 0, f"{L} layers not divisible by pp*virtual={Svirt}"
    chunk = L // Svirt
    fwd_tab, bwd_tab = interleaved_1f1b_tables(M, pp, V)
    T = fwd_tab.shape[0]
    logger.info(
        "pipeline(interleaved-1f1b): pp=%d V=%d M=%d ticks=%d",
        pp, V, M, T,
    )

    perm, inv = interleave_layer_order(L, pp, V)
    params_perm = jax.tree.map(lambda x: x[perm], stacked_params)

    h_mb = h.reshape(M, B // M, Sq, H)
    pos_mb = positions.reshape(M, B // M, Sq)
    seg_mb = segment_ids.reshape(M, B // M, Sq)
    lab_mb = labels.reshape(M, B // M, Sq)
    K = min(Svirt, M)  # stash depth: in-flight per stage ≤ Svirt, consecutive
    scale_in = jnp.asarray(aux_scale if has_aux else 0.0, jnp.float32)

    def run(h_mb, pos_mb, seg_mb, lab_mb, params_local, head_local, scale):
        p_idx = lax.axis_index("pp")
        P = lax.axis_size("pp")
        ftab = jnp.asarray(fwd_tab)
        btab = jnp.asarray(bwd_tab)

        def chunk_params(v):
            return jax.tree.map(
                lambda x: lax.dynamic_slice_in_dim(x, v * chunk, chunk, 0),
                params_local,
            )

        def chunk_scan(x, cparams, pos, seg):
            """One virtual stage's layer scan → (y, aux_sum, extras)."""
            if has_aux:
                def body(c, lp):
                    y, a, e = layer_fn(c, lp, pos, seg)
                    return y, (a, e)

                y, (auxs, extras) = lax.scan(body, x, cparams)
                return y, jnp.sum(auxs).astype(jnp.float32), extras

            def body(c, lp):
                return layer_fn(c, lp, pos, seg), None

            y, _ = lax.scan(body, x, cparams)
            return y, jnp.float32(0.0), ()

        def stage(x, v, pos, seg):
            return chunk_scan(x, chunk_params(v), pos, seg)[0]

        def full_bwd(x, v, head, pos, seg, lab, dy, is_last):
            # chunk_scan OUTSIDE the is_last cond — collectives must be
            # rank-uniform (see pipeline_train_1f1b.full_bwd)
            def fwd(xx, pp_, hh_):
                y, aux, ex = chunk_scan(xx, pp_, pos, seg)
                sa = aux * scale
                s = lax.cond(
                    is_last,
                    lambda yy, hh: head_loss_fn(yy, hh, lab).astype(jnp.float32),
                    lambda yy, hh: jnp.vdot(
                        yy.astype(jnp.float32), dy.astype(jnp.float32)
                    ),
                    y, hh_,
                ) + sa
                return s, (jnp.where(is_last, s, sa), ex)

            out, vjp, (rep, ex) = jax.vjp(
                fwd, x, chunk_params(v), head, has_aux=True
            )
            dx, dparams, dhead = vjp(jnp.ones((), out.dtype))
            return rep, dx, dparams, dhead, ex

        zeros_g = jax.tree.map(jnp.zeros_like, params_local)
        zeros_h = jax.tree.map(jnp.zeros_like, head_local)
        stash0 = jnp.zeros((V, K) + h_mb.shape[1:], h_mb.dtype)
        # extras accumulate per LOCAL layer row (V·chunk rows, permuted
        # order — un-permuted with the grads outside)
        ex0 = jax.tree.map(
            lambda s: jnp.zeros((V * chunk,) + s.shape[1:], s.dtype),
            jax.eval_shape(
                lambda p: chunk_scan(h_mb[0], p, pos_mb[0], seg_mb[0])[2],
                chunk_params(0),
            ),
        )

        def decode(a):
            return a // M, a % M  # (vstage, microbatch); a < 0 → idle

        def tick(carry, t):
            (fstream, bstream, fstash, bstash, stash,
             gacc, hacc, dh_acc, loss_acc, ex_acc) = carry
            fa = jnp.take(ftab[t], p_idx)
            ba = jnp.take(btab[t], p_idx)

            # ---- bank arrivals (sent at t-1 by ring neighbors) ----
            prev_t = jnp.maximum(t - 1, 0)
            fa_prev = jnp.take(ftab[prev_t], (p_idx - 1) % P)
            v_prev, m_prev = decode(jnp.maximum(fa_prev, 0))
            v_recv = v_prev + jnp.where(p_idx == 0, 1, 0)
            f_ok = jnp.logical_and(t > 0, fa_prev >= 0)
            # stage Svirt-1's fwd output has no consumer; stage index of the
            # sender is v_prev*P + (p_idx-1)%P — drop when it was the last
            s_prev = v_prev * P + (p_idx - 1) % P
            f_ok = jnp.logical_and(f_ok, s_prev < Svirt - 1)
            f_ok = jnp.logical_and(f_ok, v_recv < V)
            fstash = jnp.where(
                f_ok,
                lax.dynamic_update_index_in_dim(
                    fstash,
                    lax.dynamic_update_index_in_dim(
                        jnp.take(fstash, jnp.clip(v_recv, 0, V - 1), axis=0),
                        fstream, m_prev % K, 0,
                    ),
                    jnp.clip(v_recv, 0, V - 1), 0,
                ),
                fstash,
            )
            ba_prev = jnp.take(btab[prev_t], (p_idx + 1) % P)
            vb_prev, mb_prev = decode(jnp.maximum(ba_prev, 0))
            vb_recv = vb_prev - jnp.where(p_idx == P - 1, 1, 0)
            s_bprev = vb_prev * P + (p_idx + 1) % P
            b_ok = jnp.logical_and(t > 0, ba_prev >= 0)
            b_ok = jnp.logical_and(b_ok, s_bprev > 0)
            b_ok = jnp.logical_and(b_ok, vb_recv >= 0)
            bstash = jnp.where(
                b_ok,
                lax.dynamic_update_index_in_dim(
                    bstash,
                    lax.dynamic_update_index_in_dim(
                        jnp.take(bstash, jnp.clip(vb_recv, 0, V - 1), axis=0),
                        bstream, mb_prev % K, 0,
                    ),
                    jnp.clip(vb_recv, 0, V - 1), 0,
                ),
                bstash,
            )

            # ---- forward slot ----
            vf, mf = decode(jnp.maximum(fa, 0))
            first_stage = jnp.logical_and(vf == 0, p_idx == 0)
            x_in = jnp.where(
                first_stage, h_mb[mf],
                jnp.take(fstash, vf, axis=0)[mf % K],
            )
            stash = jnp.where(
                fa >= 0,
                lax.dynamic_update_index_in_dim(
                    stash,
                    lax.dynamic_update_index_in_dim(
                        jnp.take(stash, vf, axis=0), x_in, mf % K, 0
                    ),
                    vf, 0,
                ),
                stash,
            )
            y = stage(x_in, vf, pos_mb[mf], seg_mb[mf])
            fout = jnp.where(fa >= 0, y, jnp.zeros_like(y))

            # ---- backward slot ----
            vb, mb = decode(jnp.maximum(ba, 0))
            x_b = jnp.take(stash, vb, axis=0)[mb % K]
            is_last = jnp.logical_and(vb == V - 1, p_idx == P - 1)
            loss_i, dx, dparams, dhead, ex = full_bwd(
                x_b, vb, head_local, pos_mb[mb], seg_mb[mb], lab_mb[mb],
                jnp.take(bstash, vb, axis=0)[mb % K], is_last,
            )
            do_b = ba >= 0
            gacc = jax.tree.map(
                lambda a, g: jnp.where(
                    do_b,
                    lax.dynamic_update_slice_in_dim(
                        a,
                        lax.dynamic_slice_in_dim(a, vb * chunk, chunk, 0) + g,
                        vb * chunk, 0,
                    ),
                    a,
                ),
                gacc, dparams,
            )
            ex_acc = jax.tree.map(
                lambda a, e: jnp.where(
                    do_b,
                    lax.dynamic_update_slice_in_dim(
                        a,
                        lax.dynamic_slice_in_dim(a, vb * chunk, chunk, 0) + e,
                        vb * chunk, 0,
                    ),
                    a,
                ),
                ex_acc, ex,
            )
            hacc = jax.tree.map(
                lambda a, g: a + jnp.where(do_b, g, jnp.zeros_like(g)), hacc, dhead
            )
            dh_acc = jnp.where(
                jnp.logical_and(do_b, jnp.logical_and(vb == 0, p_idx == 0)),
                lax.dynamic_update_index_in_dim(dh_acc, dx, mb, 0),
                dh_acc,
            )
            loss_acc = loss_acc + jnp.where(do_b, loss_i, 0.0)

            fwd_perm = [(i, (i + 1) % P) for i in range(P)]
            bwd_perm = [((i + 1) % P, i) for i in range(P)]
            fstream = lax.ppermute(fout, "pp", fwd_perm)
            bout = jnp.where(do_b, dx, jnp.zeros_like(dx))
            bstream = lax.ppermute(bout, "pp", bwd_perm)
            return (
                fstream, bstream, fstash, bstash, stash,
                gacc, hacc, dh_acc, loss_acc, ex_acc,
            ), None

        carry0 = (
            jnp.zeros_like(h_mb[0]),
            jnp.zeros_like(h_mb[0]),
            stash0, stash0, stash0,
            zeros_g, zeros_h,
            jnp.zeros_like(h_mb),
            jnp.zeros((), jnp.float32),
            ex0,
        )
        (_, _, _, _, _, gacc, hacc, dh_acc, loss_acc, ex_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T)
        )
        data_axes = tuple(batch_axes) + ("cp",)
        gacc = jax.tree.map(
            lambda g, s: lax.psum(g, _grad_reduce_axes(s, data_axes)),
            gacc, pspecs,
        )
        hacc = jax.tree.map(lambda g: lax.psum(g, data_axes + ("pp",)), hacc)
        dh_acc = lax.psum(dh_acc, "pp")
        loss_acc = lax.psum(loss_acc, data_axes + ("pp",))
        ex_acc = jax.tree.map(lambda e: lax.psum(e, data_axes), ex_acc)
        return loss_acc, dh_acc, gacc, hacc, ex_acc

    act_spec = P(None, batch_axes, "cp", None)
    tok_spec = P(None, batch_axes, "cp")
    pspecs = _param_specs_pp(params_perm, param_logical_specs)
    hspec = jax.tree.map(lambda x: P(*([None] * x.ndim)), head_params)
    loss, dh, gl, gh, ex = jax.shard_map(
        run,
        mesh=mesh_ctx.mesh,
        in_specs=(act_spec, tok_spec, tok_spec, tok_spec, pspecs, hspec, P()),
        out_specs=(P(), act_spec, pspecs, hspec,
                   extras_specs if has_aux else ()),
        check_vma=False,
    )(h_mb, pos_mb, seg_mb, lab_mb, params_perm, head_params, scale_in)
    gl = jax.tree.map(lambda x: x[inv], gl)  # back to natural layer order
    if has_aux:
        # extras rows follow the permuted layer order like the grads
        ex = jax.tree.map(lambda x: x[inv], ex)
        return loss, dh.reshape(B, Sq, H), gl, gh, ex
    return loss, dh.reshape(B, Sq, H), gl, gh


#: logical param axes that stay sharded inside the pipeline shard_map;
#: everything else (fsdp/embed dims) is gathered at the boundary — the
#: per-step FSDP-unshard analog. `expert` stays on ep so each pipeline
#: stage's MoE dispatch exchanges tokens over its own ragged A2A step.
_PP_MANUAL_AXES = {
    "layers": "pp", "heads": "tp", "kv_heads": "tp", "mlp": "tp",
    "expert": "ep",
}


def _grad_reduce_axes(spec, data_axes: tuple) -> tuple:
    """Data axes to psum a param grad over inside the pipeline shard_map:
    every data axis the leaf is NOT sharded on. An ep-sharded expert slice
    already holds its complete grad — every token routed to its experts
    arrived through the A2A — so psum over ep would mix grads of DIFFERENT
    experts living at the same buffer offset on different ranks."""
    named = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            named.update(entry)
        else:
            named.add(entry)
    return tuple(a for a in data_axes if a not in named)


def _param_specs_pp(stacked_params, logical=None):
    """Stacked-leaf in_specs: dim 0 on pp; tp dims kept when `logical`
    (a pytree of logical axis-name tuples, decoder param_specs style)."""
    if logical is None:
        return jax.tree.map(
            lambda x: P(*(["pp"] + [None] * (x.ndim - 1))), stacked_params
        )

    def one(spec):
        return P(*(_PP_MANUAL_AXES.get(ax) for ax in spec))

    return jax.tree.map(
        one, logical,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(a, (str, type(None))) for a in x),
    )
