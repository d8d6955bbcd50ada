"""Pallas TPU kernel for the ragged selective scan of a serve step.

`selective_scan_kernel(delta, du, a, b, c, ssm_state, runs)` is the TPU
backend of `ops/selective_scan.ragged_selective_scan`, which states when a
call comes here and keeps the `lax.scan` it is checked against. The
mathematics is `ssm_update`'s, float32 throughout; what differs is the unit
of work and where the state lives while it is worked on.

The unit is a RUN (`step_runs`: one slot's rows at consecutive positions),
not a row. `run_list` compacts the step's runs in-jit into (first row,
length, slot, fresh) columns that are scalar-prefetched, and their count is
a run-time grid bound: the grid is (channel block, run), a pad row is no
run and moves no state, a slot that is not in the step is never touched.

- A run's (N, cb) float32 state is ONE block, fetched from its slot by the
  BlockSpec's index map while the run before it computes, held in VMEM
  while an in-kernel loop walks the run's rows, and written back once. The
  state array is aliased to the output (`input_output_aliases`): in place,
  as the step donates it. A run that starts at position 0 takes zeros by a
  select on the block it fetched; nothing is zeroed in memory.
- The rows' inputs (`delta`, `du` = delta x u, `b`, `c`) and `y` are
  resident for all the runs of a channel block: their block index does not
  move along the run axis, so each crosses the HBM once a call. `b` and `c`
  stay (T, N), dense; a row's N coefficients are turned from the lanes
  onto the sublanes, where the state's N lies, by a masked sum (`_column`:
  a (T, N, 1) operand would need no turning and 128 times the bytes).
- On this chip the cost is per block, not per byte (PERF.md section 6, PRs
  30-34), so the channel block is as wide as VMEM takes
  (`ops/selective_scan.channel_block`): at Jamba2-3B's widths a run's whole
  320 KiB state, 128 grid steps a layer for 128 decode rows.

Each slot has at most ONE run a step (the scheduler packs a slot's rows
together): a second run of a slot would be fetched before the first was
written back. Runs on the CPU in interpret mode for the parity tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: rows of `run_list`'s table
RUN_FIRST, RUN_LENGTH, RUN_SLOT, RUN_FRESH = range(4)
#: lanes of one pass over a row's channels: (N, _LANES) operands stay in
#: the vector registers from the state's load to its store
_LANES = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def run_list(runs: dict, trash: int):
    """The step's runs, compacted: (`table` (4, T) int32, column r the
    first row, the length, the slot and whether run r starts from zeros;
    `count` () int32, how many of the T columns are the step's, at least
    1). A pad row (it reads the trash slot) is no run. A step of pads alone
    has one: row 0, from zeros, into the trash slot, as the reference walks
    a pad row."""
    T = runs["start"].shape[0]
    real = runs["read"] != trash
    starts = runs["start"] & real
    (first,) = jnp.nonzero(starts, size=T, fill_value=0)
    (last,) = jnp.nonzero(runs["end"] & real, size=T, fill_value=0)
    count = jnp.sum(starts).astype(jnp.int32)
    live = jnp.arange(T) < count
    table = jnp.stack([
        first,
        jnp.where(live, last - first + 1, 1),
        jnp.where(live, runs["read"][first], trash),
        jnp.where(live, runs["first_pos"][first] <= 0, True),
    ]).astype(jnp.int32)
    return table, jnp.maximum(count, 1)


def _column(ref, t, eye):
    """Row t of a (T, N) ref as an (N, 1) column: the row along every
    sublane, all but the diagonal masked, summed over the lanes."""
    return jnp.sum(jnp.where(eye, ref[pl.ds(t, 1), :], 0.0), axis=1,
                   keepdims=True)


def _kernel(runs_ref, delta_ref, du_ref, b_ref, c_ref, a_ref, carried_ref,
            y_ref, h_ref):
    r = pl.program_id(1)
    first = runs_ref[RUN_FIRST, r]
    length = runs_ref[RUN_LENGTH, r]
    fresh = runs_ref[RUN_FRESH, r] != 0
    cb = y_ref.shape[1]
    N = a_ref.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))

    @pl.when(r == 0)
    def _pad_rows():
        # rows of no run come out zero, not as the buffer stood
        y_ref[...] = jnp.zeros_like(y_ref)

    def row(t, h_before):
        """Row t of the run: the state of `h_before` (a lane slice ->
        (N, lanes)) through `ssm_update` into `h_ref`, y_t into `y_ref`."""
        b_t, c_t = _column(b_ref, t, eye), _column(c_ref, t, eye)
        for lo in range(0, cb, _LANES):
            sl = pl.ds(lo, min(_LANES, cb - lo))
            delta_t = delta_ref[pl.ds(t, 1), sl]              # (1, lanes)
            h = (jnp.exp(delta_t * a_ref[:, sl]) * h_before(sl)
                 + b_t * du_ref[pl.ds(t, 1), sl])
            h_ref[0, :, sl] = h
            y_ref[pl.ds(t, 1), sl] = jnp.sum(h * c_t, axis=0, keepdims=True)

    row(first, lambda sl: jnp.where(fresh, 0.0, carried_ref[0, :, sl]))

    def later_row(t, carry):
        row(t, lambda sl: h_ref[0, :, sl])
        return carry

    jax.lax.fori_loop(first + 1, first + length, later_row, 0)


def vmem_bytes(T: int, N: int, cb: int) -> int:
    """VMEM of one call: two buffers of every block (delta, du and y at
    (T, cb); b and c at (T, N), a lane tile wide; a and the state in and
    out at (N, cb)), and room for the compiler's own."""
    return 2 * 4 * (3 * T * cb + 2 * T * 128 + 3 * N * cb) + 4 * 2**20


def selective_scan_kernel(delta, du, a, b, c, ssm_state, runs, *, cb: int):
    """delta, du (T, C) float32; a (N, C); b, c (T, N); ssm_state
    (S+1, N, C) float32, donated by the caller's step; `runs` of
    `step_runs`. `cb`: `ops/selective_scan.channel_block`, or any multiple
    of 128 lanes that divides C. Returns (y (T, C) float32, new state)."""
    return _call(delta, du, a, b, c, ssm_state, runs, cb=cb,
                 interpret=_interpret())


@functools.partial(jax.jit, inline=True, static_argnames=("cb", "interpret"))
def _call(delta, du, a, b, c, ssm_state, runs, *, cb, interpret):
    """Jitted (inlined into its caller) for its cache alone: a step traces
    one call a state-space layer, and every one after the first is the
    first's jaxpr."""
    T, C = delta.shape
    slots, N, _ = ssm_state.shape
    table, count = run_list(runs, trash=slots - 1)

    def channels(j, r, table):
        return (0, j)

    def coefficients(j, r, table):
        return (0, 0)

    def state(j, r, table):
        return (table[RUN_SLOT, r], 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C // cb, count),
        in_specs=[
            pl.BlockSpec((T, cb), channels),                  # delta
            pl.BlockSpec((T, cb), channels),                  # du
            pl.BlockSpec((T, N), coefficients),               # b
            pl.BlockSpec((T, N), coefficients),               # c
            pl.BlockSpec((N, cb), channels),                  # a
            pl.BlockSpec((1, N, cb), state),
        ],
        out_specs=[
            pl.BlockSpec((T, cb), channels),                  # y
            pl.BlockSpec((1, N, cb), state),
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, C), F32),
            jax.ShapeDtypeStruct(ssm_state.shape, F32),
        ],
        # the state is operand 6 with the prefetched table counted
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(T, N, cb),
        ),
        # at most a run a row: every row's state once in and once out
        cost_estimate=pl.CostEstimate(
            flops=9 * T * N * C, transcendentals=T * N * C,
            bytes_accessed=4 * (3 * T * C + 2 * T * N + N * C
                                + 2 * T * N * C),
        ),
        interpret=interpret,
        name="selective_scan",
    )(table, delta, du, b, c, a, ssm_state)
