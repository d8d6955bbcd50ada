"""Mamba-1's selective scan and its short causal convolution: one operator,
two entry points.

The recurrence (Gu & Dao, "Mamba", section 3; per channel c of C and state n
of N, everything in float32):

    h_t[n, c] = exp(delta_t[c] * A[n, c]) * h_{t-1}[n, c]
                + delta_t[c] * u_t[c] * B_t[n]
    y_t[c]    = sum_n h_t[n, c] * C_t[n]

`A` is (N, C) and negative, so every state decays; `delta`, `B` and `C` are
functions of the input (the "selection"). The state is laid out (N, C): the
C = thousands of channels are the vector axis, the N = 16 states the second
minor one, so a state tiles a TPU's (8, 128) registers without padding (the
published (C, N) order would pad 16 lanes to 128: eight times the memory).

Entry points:

- `selective_scan` / `causal_conv`: whole sequences (B, S, C), the training
  and reference-parity path; a packed document starts from zeros wherever
  its `positions` say 0.
- `ragged_conv` / `ragged_selective_scan`: the rows of ONE serve step in plan
  order. A RUN (`step_runs`) is one slot's rows at consecutive positions; it
  continues the state that slot carried in from the step before
  (`state[slot]`), or starts from zeros where its first position is 0: a
  select, so nothing is ever zeroed and a slot that is re-used, or whose
  request was preempted and starts again, needs no reset. The run's last
  state (and the last K-1 inputs of the convolution) is written back to the
  slot; pad rows (slot < 0) read zeros and write the TRASH slot, the last
  index of the slot axis. The runs are derived inside the step from `slot`
  and `pos` alone, on every backend.

Two backends of the recurrence behind one rule (`ragged_selective_scan`),
built like `ops/grouped_matmul.py` and `ops/paged_attention.py`; the
convolution and everything per row is batched over the rows on both:

- the XLA form (the reference): off the TPU, and on it for a call the
  kernel does not take. One `lax.scan` over the rows that carries ONE
  (N, C) state, reads a slot's state where a run starts and writes it where
  a run ends (both dynamic slices of the donated state array, in place),
  `ROW_UNROLL` rows a trip. Every CPU test lowers through it. (An
  associative scan over (rows, N, C) operands would move tens of gigabytes
  a step.)
- the Pallas TPU kernel (`ops/pallas/selective_scan.py`), whose unit of
  work is a RUN: a run's state is fetched once from its slot, held in VMEM
  for the run's rows and written back once, the state array aliased in
  place. The rule (`_unsupported_reason`) reads the call alone: the state's
  dtype, whether its widths tile the chip's registers and fit its VMEM, and
  whether GSPMD may shard the operands; it has no clause on the rows or the
  runs' lengths (on the chip the kernel won on a decode-only step, a
  prefill-heavy one and the mix between: PERF.md section 6, PR 35).

Every traced call site ticks `selective_scan_calls_total{impl, reason}` on
the process registry (trace time: once a compiled call site, not once a
step), and the first call a TPU hands to the reference is logged.

Each slot has at most ONE run a step (the scheduler packs a slot's rows
together; speculation, which would add more, is refused for such models):
the convolution reads every slot's carried inputs once, before any run
writes, and the kernel fetches a run's state while the run before computes.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

F32 = jnp.float32
#: rows a trip of the XLA form's scan, the reference that serves off the TPU
#: and what the kernel does not take: on a v5e one layer's call over 256 rows
#: took 2.26 ms at 1, 1.34 at 4, 1.44 at 8 (PR 34's chip runs, CHANGES.md).
#: The Pallas kernel has no such knob: it walks a run's rows one by one on
#: a state that stays in VMEM
ROW_UNROLL = 4
#: VMEM the kernel's resident blocks may take (the rows' inputs and outputs
#: of one channel block, twice buffered): the chip has 128 MiB
_VMEM_BYTES = 64 * 2**20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ssm_update(h, delta_t, u_t, b_t, c_t, a):
    """One position of the recurrence. h (..., N, C) float32; delta_t, u_t
    (..., C); b_t, c_t (..., N); a (N, C). Returns (h_t, y_t (..., C))."""
    du = delta_t * u_t
    h = jnp.exp(delta_t[..., None, :] * a) * h + b_t[..., :, None] * du[..., None, :]
    return h, jnp.sum(h * c_t[..., :, None], axis=-2)


# ---------------------------------------------------------------------------
# whole sequences
# ---------------------------------------------------------------------------
def causal_conv(x, kernel, bias, positions=None):
    """Depthwise causal convolution over the last K positions. x (B, S, C),
    kernel (K, C) with kernel[K-1] on the current position, bias (C,).
    Where `positions` (B, S) is given, a tap that would reach before a
    document's position 0 reads zero (packed documents do not leak)."""
    K = kernel.shape[0]
    xf = x.astype(F32)
    out = xf * kernel[K - 1].astype(F32)
    for d in range(1, K):
        tap = jnp.pad(xf, ((0, 0), (d, 0), (0, 0)))[:, : x.shape[1]]
        if positions is not None:
            tap = jnp.where((positions >= d)[..., None], tap, 0.0)
        out = out + tap * kernel[K - 1 - d].astype(F32)
    return out + bias.astype(F32)


def selective_scan(u, delta, a, b, c, positions=None):
    """y (B, S, C) float32 of the recurrence over whole sequences, from a
    zero state; with `positions` the state is zeroed wherever a position is
    0 (the start of a packed document). u, delta (B, S, C); b, c (B, S, N);
    a (N, C)."""
    B, S, C = u.shape
    N = a.shape[0]
    a = a.astype(F32)
    fresh = (jnp.zeros((B, S), bool) if positions is None else positions == 0)

    def body(h, xs):
        delta_t, u_t, b_t, c_t, fresh_t = xs
        h = jnp.where(fresh_t[:, None, None], 0.0, h)
        return ssm_update(h, delta_t, u_t, b_t, c_t, a)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (
        delta.astype(F32), u.astype(F32), b.astype(F32), c.astype(F32), fresh))
    _, y = jax.lax.scan(body, jnp.zeros((B, N, C), F32), xs)
    return jnp.moveaxis(y, 0, 1)


# ---------------------------------------------------------------------------
# the ragged rows of a serve step
# ---------------------------------------------------------------------------
def step_runs(slot, pos, trash: int) -> dict:
    """The runs of a step's rows, from `slot` and `pos` (T,) alone: row t
    starts a run unless it is the same slot's next position after row t - 1.
    Pad rows (slot < 0) are runs of one row each.

    start, end (T,) bool; offset (T,) rows since the run's start; first_pos
    (T,) the position the row's run began at; read (T,) the slot index a row
    reads its carried state at and write (T,) where the run's last row
    writes it back: `trash` for every other row and for pads."""
    T = slot.shape[0]
    idx = jnp.arange(T, dtype=jnp.int32)
    prev_slot = jnp.concatenate([jnp.full((1,), -2, slot.dtype), slot[:-1]])
    prev_pos = jnp.concatenate([jnp.full((1,), -2, pos.dtype), pos[:-1]])
    start = (slot != prev_slot) | (pos != prev_pos + 1) | (slot < 0)
    end = jnp.concatenate([start[1:], jnp.ones((1,), bool)])
    offset = idx - jax.lax.cummax(jnp.where(start, idx, 0))
    real = slot >= 0
    read = jnp.where(real, slot, trash).astype(jnp.int32)
    return {
        "start": start, "end": end, "offset": offset,
        "first_pos": pos - offset, "read": read,
        "write": jnp.where(end & real, read, trash).astype(jnp.int32),
    }


def ragged_conv(x, kernel, bias, conv_state, pos, runs):
    """The convolution over a step's rows. x (T, C) the rows' inputs;
    conv_state (K-1, S+1, C) each slot's last K-1 inputs, index K-2 the
    newest. Returns (out (T, C) float32, new conv_state): a tap d positions
    back is row t - d where the run reaches that far, else the slot's
    carried input, else (before position 0) zero."""
    K = kernel.shape[0]
    T = x.shape[0]
    offset = runs["offset"]
    with jax.named_scope("serve.ssm.state"):
        carried = conv_state[:, runs["read"]]               # (K-1, T, C)
    taps = [x]
    for d in range(1, K):
        tap = jnp.pad(x, ((d, 0), (0, 0)))[:T]              # row t - d
        for r in range(d):                                   # the run is shorter
            tap = jnp.where((offset == r)[:, None], carried[K - 1 - d + r], tap)
        taps.append(jnp.where((pos >= d)[:, None], tap, jnp.zeros_like(tap)))
    out = bias.astype(F32)
    for d, tap in enumerate(taps):
        out = out + tap.astype(F32) * kernel[K - 1 - d].astype(F32)
    # the slot's next K-1 carried inputs are the run's last row's taps
    with jax.named_scope("serve.ssm.state"):
        new_state = conv_state.at[:, runs["write"]].set(
            jnp.stack(taps[K - 2::-1]).astype(conv_state.dtype))
    return out, new_state


def channel_block(T: int, C: int, N: int) -> int:
    """Channels of the kernel's block for a call's shapes: all of C where
    the rows' resident blocks fit `_VMEM_BYTES`, else the widest multiple
    of 128 lanes dividing C that does; 0 where none does."""
    from automodel_tpu.ops.pallas.selective_scan import vmem_bytes

    for parts in range(1, C // 128 + 1):
        cb = C // parts
        if C % parts == 0 and cb % 128 == 0 \
                and vmem_bytes(T, N, cb) <= _VMEM_BYTES:
            return cb
    return 0


def _unsupported_reason(delta, ssm_state, sharded: bool) -> str | None:
    """Why the Pallas kernel does not take this call, or None."""
    (T, C), N = delta.shape, ssm_state.shape[1]
    if sharded:
        # a Mosaic call has no partitioning rule: GSPMD would gather it
        return "operands sharded under GSPMD"
    if ssm_state.dtype != F32:
        return f"state {ssm_state.dtype}, not float32"
    if C % 128 or N % 8:
        return f"C={C}, N={N} not multiples of 128 lanes and 8 sublanes"
    if not channel_block(T, C, N):
        return f"T={T}: the rows of one 128-lane block pass {_VMEM_BYTES} bytes"
    return None


def _resolve(impl: str, unsupported: str | None) -> str:
    """"pallas" or "xla" for this call site, counted."""
    from automodel_tpu.ops.dispatch import resolve_counted

    return resolve_counted(
        "ragged_selective_scan", impl, unsupported, on_tpu=_on_tpu(),
        counter="selective_scan_calls_total", taken="float32 state on a TPU",
        reference="the lax.scan", logger=logger)


def ragged_selective_scan(u, delta, a, b, c, ssm_state, runs, *,
                          impl: str = "auto", mesh_ctx=None):
    """The recurrence over a step's rows in plan order. u, delta (T, C);
    b, c (T, N); a (N, C); ssm_state (S+1, N, C) float32, donated by the
    step and updated in place. Returns (y (T, C) float32, new state).

    impl: "xla" | "pallas" | "auto" (the kernel on a TPU where the call
    qualifies). `mesh_ctx` is the caller's when its operands may be sharded
    by GSPMD (more than one device): the reference then serves it."""
    a, delta = a.astype(F32), delta.astype(F32)
    sharded = mesh_ctx is not None and mesh_ctx.num_devices > 1
    unsupported = _unsupported_reason(delta, ssm_state, sharded)
    if _resolve(impl, unsupported) == "pallas":
        from automodel_tpu.ops.pallas.selective_scan import (
            selective_scan_kernel,
        )

        return selective_scan_kernel(
            delta, delta * u.astype(F32), a, b.astype(F32), c.astype(F32),
            ssm_state, runs,
            cb=channel_block(*delta.shape, ssm_state.shape[1]))
    return _ragged_scan_xla(u, delta, a, b, c, ssm_state, runs)


def _ragged_scan_xla(u, delta, a, b, c, ssm_state, runs):
    """The reference: one `lax.scan` over the rows, `ROW_UNROLL` a trip.
    The recurrence is float32 whatever the state array's dtype."""
    # a run that begins at position 0 (and a pad row) starts from zeros
    fresh = runs["start"] & (runs["first_pos"] <= 0)
    carried = runs["start"] & ~fresh

    def body(carry, xs):
        h, state = carry
        delta_t, u_t, b_t, c_t, fresh_t, carried_t, read_t, write_t = xs
        with jax.named_scope("serve.ssm.state"):
            h_in = jax.lax.dynamic_index_in_dim(state, read_t, keepdims=False)
        h = jnp.where(fresh_t, 0.0, jnp.where(carried_t, h_in.astype(F32), h))
        h, y_t = ssm_update(h, delta_t, u_t, b_t, c_t, a)
        with jax.named_scope("serve.ssm.state"):
            state = jax.lax.dynamic_update_index_in_dim(
                state, h.astype(state.dtype), write_t, 0)
        return (h, state), y_t

    xs = (delta, u.astype(F32), b.astype(F32), c.astype(F32),
          fresh, carried, runs["read"], runs["write"])
    (_, state), y = jax.lax.scan(
        body, (jnp.zeros(ssm_state.shape[1:], F32), ssm_state), xs,
        unroll=ROW_UNROLL)
    return y, state
