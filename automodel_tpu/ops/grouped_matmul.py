"""Grouped matmul: the three products of the routed experts.

`grouped_matmul(lhs (m, k), rhs (E, k, n), group_sizes (E,)) -> (m, n)`:
the rows of `lhs` lie sorted by group, group g's `group_sizes[g]` rows are
multiplied by `rhs[g]`. The semantics are `lax.ragged_dot`'s, and what it
does with the rows past `sum(group_sizes)` is a contract here: **they come
out zero** (the sort of `moe/experts.py` puts the masked tokens' sentinel
rows there; their combine weight is 0, and 0 x NaN is NaN).

Two backends behind one rule, built like `ops/paged_attention.py`:

- `lax.ragged_dot` (the reference): off the TPU, and on it for a call the
  kernel does not take. Every CPU test and the pinned one-pass serve step
  lower through it as before.
- the Pallas TPU kernel (`ops/pallas/grouped_matmul.py`), which streams
  every group's weights once a call in whole-slab blocks. It is built for
  the weight-streaming regime (a serve step: 256 rows x top-6 over 64
  experts is 24 rows a group, and the call is bound by the bytes of `rhs`),
  and on the chip it was no slower than the reference at a training
  batch's 384 rows a group either (PERF.md section 6, PR 32), so the rule
  (`_unsupported_reason`) has no clause on the rows: it reads the call's
  dtypes and widths, and whether GSPMD may shard the operands.

Every traced call site ticks `grouped_matmul_calls_total{impl, reason}` on
the process registry (trace time: once a compiled call site, not once a
step), and the first call a TPU hands to the reference is logged.

The op carries a `jax.custom_vjp` whose backward is `lax.ragged_dot`'s own,
so a call that lands on the kernel differentiates exactly as the reference.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

#: rows of a row tile at most: the height of the MXU. On the chip the serve
#: step's call read the same at 32, 64, 128 and 256 rows (the fetch hides the
#: product); a training batch's read best at 128, where a group that
#: straddles a tile boundary recomputes least
_TILE_ROWS = 128
#: bytes of ONE weight slab (k, tn); two are in flight. 16 MiB takes a whole
#: (k, n) of every published expert width below 4096 x 2048 in bf16
_SLAB_BYTES = 16 * 2**20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def tiles(m: int, k: int, n: int, dtype) -> tuple[int, int]:
    """(tm, tn) of the kernel for a call's shapes: a row tile of at most
    `_TILE_ROWS` rows, no more than the rows rounded up to the dtype's
    sublanes; a slab of the whole k and all of n where `_SLAB_BYTES` takes
    it, else the most lanes that fit."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    tm = min(_TILE_ROWS, -(-m // sublanes) * sublanes)
    if k * n * itemsize <= _SLAB_BYTES:
        return tm, n
    return tm, _SLAB_BYTES // (k * itemsize) // 128 * 128


def _unsupported_reason(lhs, rhs, sharded: bool) -> str | None:
    """Why the Pallas kernel does not take this call, or None."""
    (m, k), n = lhs.shape, rhs.shape[2]
    if sharded:
        # a Mosaic call has no partitioning rule: GSPMD would gather it
        return "operands sharded under GSPMD"
    if lhs.dtype != jnp.bfloat16 or rhs.dtype != jnp.bfloat16:
        # the kernel computes float32 operands too; only bf16 has been
        # timed against the reference on a chip
        return f"dtypes {lhs.dtype} x {rhs.dtype}, not bfloat16"
    if k % 128 or n % 128:
        return f"k={k}, n={n} not multiples of 128 lanes"
    if tiles(m, k, n, lhs.dtype)[1] < 128:
        return f"k={k}: one 128-lane slab passes {_SLAB_BYTES} bytes"
    return None


def _resolve(impl: str, unsupported: str | None) -> str:
    """"pallas" or "xla" for this call site, counted."""
    from automodel_tpu.ops.dispatch import resolve_counted

    return resolve_counted(
        "grouped_matmul", impl, unsupported, on_tpu=_on_tpu(),
        counter="grouped_matmul_calls_total", taken="bf16 call on a TPU",
        reference="lax.ragged_dot", logger=logger)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas(lhs, rhs, group_sizes, tm, tn):
    from automodel_tpu.ops.pallas.grouped_matmul import grouped_matmul_kernel

    return grouped_matmul_kernel(lhs, rhs, group_sizes, tm=tm, tn=tn)


def _pallas_fwd(lhs, rhs, group_sizes, tm, tn):
    return _pallas(lhs, rhs, group_sizes, tm, tn), (lhs, rhs, group_sizes)


def _pallas_bwd(tm, tn, res, dout):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(
        lambda a, b: jax.lax.ragged_dot(a, b, group_sizes), lhs, rhs)
    return (*vjp(dout), None)


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, impl: str = "auto",
                   mesh_ctx=None):
    """impl: "xla" | "pallas" | "auto" (the kernel on a TPU where the call
    qualifies). `mesh_ctx` is the caller's when its operands may be sharded
    by GSPMD (more than one device and no `shard_map` around the call): the
    reference then serves it. Inside a `shard_map` the operands are local
    and the caller passes none."""
    sharded = mesh_ctx is not None and mesh_ctx.num_devices > 1
    unsupported = _unsupported_reason(lhs, rhs, sharded)
    if _resolve(impl, unsupported) == "pallas":
        return _pallas(
            lhs, rhs, group_sizes,
            *tiles(lhs.shape[0], lhs.shape[1], rhs.shape[2], lhs.dtype))
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)
