"""Page-granular KV transfer between engine pools (disaggregated serving).

The device half of the prefill→decode handoff (serving/router.py's
DisaggRouter, Mooncake/DistServe-style): a prefill-class replica finishes a
prompt, the scheduler pins the request's committed pages and releases its
slot, and this module moves those pages into a decode-class replica's pool
— by GLOBAL page ID, with no cache-format conversion. Both pools share the
same layout family (kv_pages.py: per layer, GQA (N+1, ps, Hkv, D) or
absorbed-MLA (N+1, ps, r)/(N+1, ps, dr)); only `num_pages` may differ between
the classes, so a transfer is a pure index copy along the pages axis (axis 0
of every array).

The copy plan is HOST-side (the (src_page, dst_page) pairs the decode
scheduler's `try_admit_handoff` returns after splicing out pages its own
radix tree already holds); the data movement is DEVICE-side, batched
`batch_pages` pages per issued program:

- fused path (both engines meshless → pools share a device):
  `apply_transfer` — ONE jitted gather+scatter along the pages axis per
  pool array, destination pool donated (in-place buffer reuse, no second
  pool-sized allocation). This is the program the `kv_transfer` analysis
  baseline pins: gather/scatter only, zero collectives.
- split path (engines on disjoint mesh slices): a jitted gather on the
  source mesh lifts the pages into (B, ...) staging blocks, one
  `jax.device_put` hops it onto the destination placement (pages
  unsharded; the per-page head/latent dim follows the destination's tp
  cut), and a jitted donated scatter lands it. Three steps instead of
  one, but each keeps a single compiled signature per replica pair.

Index arrays have a FIXED length (`batch_pages`, short chunks padded with
trash→trash pairs — the same in-bounds-by-construction trick the step's
pad rows use), so transfers never mint new compiled signatures as handoff
sizes vary: compile-once extends to the transfer programs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.resilience.faults import fault_hit
from automodel_tpu.serving.kv_pages import pool_trash_index


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_transfer(dst_pool, src_pool, src_idx, dst_idx):
    """Fused same-device page copy: dst_pool[dst_idx[i]] =
    src_pool[src_idx[i]] for every pool array, in one program. `src_idx`
    / `dst_idx` are fixed-length (B,) int32; pad entries point both sides
    at their trash page (a self-overwrite of garbage). The destination
    pool is donated — callers rebind."""
    return jax.tree.map(
        lambda d, s: d.at[dst_idx].set(s[src_idx]), dst_pool, src_pool
    )


@jax.jit
def _gather_pages(src_pool, src_idx):
    """Split-path stage 1: lift B pages out of the source pool."""
    return jax.tree.map(lambda a: a[src_idx], src_pool)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(dst_pool, rows, dst_idx):
    """Split-path stage 3: land B staged pages in the donated dest pool."""
    return jax.tree.map(lambda d, r: d.at[dst_idx].set(r), dst_pool, rows)


def refuse_state_handoff(cfg) -> None:
    """A hand-off between pools moves PAGES. A model that also carries a
    per-slot recurrent state (kv_pages.init_state) would arrive without it
    and decode from a state of zeros: refused by name until the hand-off
    carries the state. So would a model with GQA window layers, whose keys
    and values live in a ring per slot (kv_pages.init_rings) and not in the
    pages."""
    from automodel_tpu.serving.kv_pages import keeps_rings

    if keeps_rings(cfg):
        raise NotImplementedError(
            "a hand-off between pools (kv_transfer / DisaggRouter) for a "
            "model with sliding-window layers: the full layers' pages would "
            "move and the window layers' ring per slot would not; serve it "
            "from one pool (a ServingEngine, or ReplicaRouter over whole "
            "engines)"
        )
    if getattr(cfg, "holds_state", False):
        raise NotImplementedError(
            "a hand-off between pools (kv_transfer / DisaggRouter) for a "
            "model that holds a recurrent state: the pages would move and "
            "the per-slot state would not; serve it from one pool (a "
            "ServingEngine, or ReplicaRouter over whole engines)"
        )


class KVTransfer:
    """Page mover from one engine's pool to another's.

    Holds no request state — the DisaggRouter owns the handoff lifecycle
    (pinning, admission, deadline expiry); this object just executes copy
    plans and keeps transfer counters. One instance per (prefill, decode)
    replica pair keeps the compiled programs per pair stable."""

    def __init__(self, src_engine, dst_engine, batch_pages: int = 8):
        if src_engine.serve_cfg.page_size != dst_engine.serve_cfg.page_size:
            raise ValueError(
                "kv transfer needs equal page_size on both replica classes "
                f"(src={src_engine.serve_cfg.page_size}, "
                f"dst={dst_engine.serve_cfg.page_size}) — pages move with "
                "no cache-format conversion"
            )
        if batch_pages < 1:
            raise ValueError(f"batch_pages must be >= 1, got {batch_pages}")
        refuse_state_handoff(src_engine.cfg)
        self.src = src_engine
        self.dst = dst_engine
        self.batch_pages = int(batch_pages)
        self.src_trash = pool_trash_index(src_engine.pool)
        self.dst_trash = pool_trash_index(dst_engine.pool)
        # fused single-program path only when both pools share a device
        # placement (meshless engines); disjoint mesh slices take the
        # gather → device_put hop → scatter split path
        self.fused = src_engine._mesh is None and dst_engine._mesh is None
        self.page_bytes = sum(
            (a.size // a.shape[0]) * a.dtype.itemsize
            for a in jax.tree.leaves(src_engine.pool)
        )
        self.n_pages = 0    # real (non-pad) pages moved
        self.n_chunks = 0   # device copy programs issued
        self.n_bytes = 0    # wire bytes for real pages (quantized pools
                            # ship int8 payload + f32 scales natively, so
                            # this is ~half the fp equivalent)

    def _put_src(self, idx: np.ndarray):
        if self.src._mesh is None:
            return jnp.asarray(idx)
        return jax.device_put(idx, self.src._mesh.replicated())

    def _put_dst(self, idx: np.ndarray):
        if self.dst._mesh is None:
            return jnp.asarray(idx)
        return jax.device_put(idx, self.dst._mesh.replicated())

    def move(self, pairs: list) -> int:
        """Execute a copy plan: `pairs` is [(src_page, dst_page)] in the
        two pools' global page IDs. Batched `batch_pages` per program with
        trash-padding, so any plan length reuses the compiled signatures.
        Returns the number of pages moved."""
        if not pairs:
            return 0
        # chaos hook, BEFORE any device copy: a failed move retries as a
        # whole (page copies are idempotent — re-copying is a self-
        # overwrite), so the retry wrapper in serving/resilience.py can
        # re-call this safely after an injected transfer fault
        fault_hit("kv_transfer", None)
        B = self.batch_pages
        for i in range(0, len(pairs), B):
            chunk = pairs[i : i + B]
            src_idx = np.full(B, self.src_trash, np.int32)
            dst_idx = np.full(B, self.dst_trash, np.int32)
            for j, (s, d) in enumerate(chunk):
                src_idx[j], dst_idx[j] = s, d
            if self.fused:
                self.dst.pool = apply_transfer(
                    self.dst.pool, self.src.pool,
                    jnp.asarray(src_idx), jnp.asarray(dst_idx),
                )
            else:
                rows = _gather_pages(self.src.pool, self._put_src(src_idx))
                # the one cross-slice hop: pages land with the destination
                # pool's sharding (pages axis unsharded; per-page heads /
                # latent follow the destination tp cut)
                rows = jax.tree.map(
                    lambda r, d: jax.device_put(r, d.sharding),
                    rows, self.dst.pool,
                )
                self.dst.pool = _scatter_pages(
                    self.dst.pool, rows, self._put_dst(dst_idx)
                )
            self.n_chunks += 1
            self.n_pages += len(chunk)
            self.n_bytes += len(chunk) * self.page_bytes
        return len(pairs)
