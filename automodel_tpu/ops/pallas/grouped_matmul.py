"""Pallas TPU kernel for the grouped matmul of the routed experts.

`grouped_matmul_kernel(lhs (m, k), rhs (E, k, n), group_sizes (E,))` has
the semantics of `lax.ragged_dot`: the rows of `lhs` are sorted by group,
group g's `group_sizes[g]` rows are multiplied by `rhs[g]`, and the rows
past `sum(group_sizes)` come out ZERO (the sentinel rows of masked tokens:
`moe/experts.py`). It is the TPU backend of `ops/grouped_matmul.py`, which
states when a call comes here; the construction is megablox's
(`jax/experimental/pallas/ops/tpu/megablox/gmm.py`), rebuilt for the regime
in which the weights, not the rows, are the traffic: a serve step's few
hundred rows a layer against every expert's matrices.

The grid is the list of (group, row tile) VISITS in sorted-row order,
derived from the group sizes in-jit and handed to the BlockSpec index maps
through scalar prefetch; its length is a run-time grid bound, so a call
walks its live visits and no others. A group is visited once for every
aligned tile of `tm` rows that holds one of its rows, an empty group never.
What makes it stream:

- the weight block is one group's WHOLE (k, tn) slab, `tn` all of n where
  VMEM takes it (`ops/grouped_matmul.tiles`), so one DMA moves megabytes
  that lie contiguous in HBM, and consecutive visits of one group keep the
  block index and fetch nothing: every expert's weights cross the HBM once
  a call;
- the row tile (tm, k) and the output tile (tm, tn) are small beside it and
  pipeline under the weight fetch; consecutive visits of one row tile
  revisit the same output block, each storing its own group's rows alone;
- the rows past the last group are one more, weightless group: its visits
  compute nothing and store zeros, so every row of the output is written by
  exactly one visit and none is left as the buffer stood.

float32 accumulation, output in the operands' dtype. Runs on the CPU in
interpret mode for the parity tests against `lax.ragged_dot`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of `visits`
VISIT_GROUP, VISIT_TILE, VISIT_SLAB = range(3)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def group_visits(group_sizes, m: int, tm: int):
    """The call's visits from its group sizes: (`offsets` (E + 2,) int32,
    the first row of each group, of the tail and `m`; `visits` (3, V) int32,
    column w the group, the row tile and the weight slab of visit w;
    `count` () int32, how many of the V = tiles + E columns are the
    call's). Group E is the TAIL, the rows past `sum(group_sizes)`: its
    visits name the slab of the last group visited, so they fetch none.
    Sizes that sum past `m` are cut at `m`, as the rows are."""
    E = group_sizes.shape[0]
    tiles = pl.cdiv(m, tm)
    V = tiles + E
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), m)
    ends = jnp.concatenate([ends, jnp.full((1,), m, jnp.int32)])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    starts = offsets[:-1]
    first = starts // tm
    held = jnp.where(ends > starts, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(held)
    count = visit_ends[-1]
    # every row is some group's or the tail's, so count >= 1; past the count
    # a column repeats the last visit: no block index moves
    w = jnp.minimum(jnp.arange(V), count - 1)
    group = jnp.minimum(jnp.searchsorted(visit_ends, w, side="right"), E)
    tile = first[group] + w - (visit_ends[group] - held[group])
    last = jnp.max(jnp.where(held[:E] > 0, jnp.arange(E), 0))
    visits = jnp.stack([group, tile, jnp.minimum(group, last)])
    return offsets, visits.astype(jnp.int32), count.astype(jnp.int32)


def _kernel(offsets_ref, visits_ref, lhs_ref, rhs_ref, out_ref, *, tm, groups):
    w = pl.program_id(1)
    g = visits_ref[VISIT_GROUP, w]
    row = visits_ref[VISIT_TILE, w] * tm + jax.lax.broadcasted_iota(
        jnp.int32, out_ref.shape, 0)
    mine = jnp.logical_and(row >= offsets_ref[g], row < offsets_ref[g + 1])

    @pl.when(g < groups)
    def _group_rows():
        acc = jnp.dot(
            lhs_ref[...], rhs_ref[0], preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])

    @pl.when(g == groups)
    def _tail_rows():
        out_ref[...] = jnp.where(mine, 0, out_ref[...]).astype(out_ref.dtype)


def grouped_matmul_kernel(lhs, rhs, group_sizes, *, tm: int, tn: int):
    """`tm`, `tn`: `ops/grouped_matmul.tiles`, or any row tile that is a
    multiple of the dtype's sublanes and slab width that is one of 128."""
    return _call(lhs, rhs, group_sizes, tm=tm, tn=tn, interpret=_interpret())


@functools.partial(
    jax.jit, inline=True, static_argnames=("tm", "tn", "interpret"))
def _call(lhs, rhs, group_sizes, *, tm, tn, interpret):
    """Jitted (inlined into its caller) for its cache alone: a step traces
    three calls an expert layer, and every one of a shape after the first
    is the first's jaxpr."""
    m, k = lhs.shape
    E, _, n = rhs.shape
    offsets, visits, count = group_visits(group_sizes, m, tm)
    itemsize = jnp.dtype(lhs.dtype).itemsize

    def rows(j, w, offsets, visits):
        return (visits[VISIT_TILE, w], 0)

    def weights(j, w, offsets, visits):
        return (visits[VISIT_SLAB, w], 0, j)

    def out(j, w, offsets, visits):
        return (visits[VISIT_TILE, w], j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(n, tn), count),
        in_specs=[
            pl.BlockSpec((tm, k), rows),
            pl.BlockSpec((1, k, tn), weights),
        ],
        out_specs=pl.BlockSpec((tm, tn), out),
    )
    # two buffers of each block, the float32 product and its cast, and room
    # for the compiler's own
    vmem = (2 * itemsize * (tm * k + k * tn + tm * tn) + 8 * tm * tn
            + 4 * 2**20)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, groups=E),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=itemsize * (E * k * n + m * k + m * n),
        ),
        interpret=interpret,
        name="grouped_matmul",
    )(offsets, visits, lhs, rhs)
