"""Paged KV cache: a global page pool + per-request page tables.

The serving analog of `inference/generate.py`'s dense per-request cache
(whose per-layer entry SHAPES it reuses — (Hkv, D) K/V rows for GQA, (r,)
latent + (dr,) rope rows for MLA), re-laid-out vLLM/RPA-style
(arXiv:2604.15464): the sequence dimension is cut into fixed-size pages
living in one global pool shared by every request, and each request holds a
PAGE TABLE — the dense-prefix list of pool pages backing its sequence.
Token at position p of a request lives at `(table[p // page_size],
p % page_size)`. Admission, growth, and preemption then become integer
page accounting on the host (`PageAllocator`), while the device arrays keep
ONE fixed shape for the whole serving run — the engine step never reshapes
or recompiles as requests join and leave.

Device-side layouts. The pool is PER LAYER: `pool[stack][layer]` is a tuple
of page arrays, every one a buffer of its own with the PAGE axis first (axis
0), so the serve step (which walks its layers in a Python loop and is
donated every array) writes each layer's rows in place and hands the paged
kernel the argument's own buffer: no layer is sliced out of a stack or
written back into one. N = `num_pages`, ps = `page_size`; allocated as N+1
pages — page index N is the TRASH page that pad token rows write into and
padded page-table entries point at, keeping every gather/scatter in bounds
without branching:

- GQA:  (k, v)   each (N+1, ps, Hkv, D)
- MLA:  (c, kr)  (N+1, ps, r) and (N+1, ps, dr)   (absorbed decode —
  r+dr cached floats per token instead of n*(dn+dr+dv))

Looped decoders (`cfg.num_passes` = P > 1: the layer stack runs P times over
every token): a stack of L layers holds P x L entries, entry `t * L + l` the
page arrays of pass t, layer l — a token of pass t attends to the earlier
tokens' keys of the same pass and layer. Page ids stay global: a page means
"these ps tokens in every pass and layer", so the allocator, the scheduler,
the prefix tree, copy-on-write, defrag, transfer and the int8 scale planes
below never see the passes (they tree-map over the leaves). What changes is
what a page costs: P times the bytes, so P times fewer pages in the same
memory, and the pool runs out before the slots do: requests wait for pages,
not for a slot, and growth preempts the youngest.

Quantized pools (kv_cache_dtype="int8"): the same layouts hold int8 and
each layer gains PARALLEL per-page scale arrays (N+1, ps) — one f32
scale per cache row, stored page-major so scales travel with their pages
through every page-axis pytree op (COW, defrag, prefix-cache adoption,
truncate, kv_transfer handoff) without the host allocator/scheduler/radix
tree ever seeing them. Dequantization happens inside the paged attention
op (ops/paged_attention.py), quantization in-jit at scatter time
(ops/quant.quantize_kv_rows).

Under a serving mesh (ServingEngine(mesh_ctx=...)) the pool becomes a
MESH-SHARDED array: pages stay global/replicated while the per-page head
dim partitions over tp (`pool_axes` — GQA KV heads, MLA kv-latent rank),
so every integer in this file — page IDs, tables, refcounts, defrag
plans — is mesh-oblivious and admission/COW/preemption/prefix-sharing
compose with sharding unchanged.

Layers that are not attention (`cfg.layer_ops`: a state-space mixer) keep no
pages. What such a layer carries from token to token does not grow with the
sequence, so its handle is the SLOT, not the page: `init_state` below makes,
per such layer, `(conv (K-1, S+1, C), ssm (S+1, N, C) float32)`, S =
`max_slots`, slot index S the trash slot that pad rows use. The state is a
TREE OF ITS OWN beside the pool (`ServingEngine.state`), donated to the step
and aliased to its output like the pool, so no page-axis operation here or
elsewhere (the step's copy-on-write block, `apply_defrag`, `kv_transfer`,
the int8 planes, `pool_shardings`) can index a slot-axis array: they
tree-map over the pool and the state is not in it. The pool of such a model
holds entries for its attention layers alone. A state per PAGE (a snapshot
at every page's end, which prefix hits and hand-offs could adopt) would cost
the whole state per page: for a model whose state is megabytes and whose
keys and values are a kilobyte a token, nearly all of the pool.

Attention layers with a WINDOW (`cfg.sliding_window` on some or all GQA
layers) keep no pool pages either. A row at position t of such a layer reads
the keys of positions t - window + 1 .. t and nothing older, so what a slot
needs alive is bounded whatever its context: `window - 1` tokens behind the
first row of a step's chunk and the chunk itself. Each such layer gets a RING
per slot (`init_rings`): R = `ring_pages(window, prefill_chunk, page_size)`
pages of the pool's page shape, page j of a slot's sequence at ring index
j % R, slot s's ring at "pages" s * R .. s * R + R - 1 of one array of
(S + 1) * R pages, slot S the trash slot that pad rows write into. So the
array reads as a small pool of its own: the paged kernel and its reference
take it with a page table that is arithmetic (`ring_page_tables`), the block
list starts at the page of the first in-window key, and the mask drops what
lies further back, which is also everything a recycled page still holds of
the position R pages earlier. The rings live in the per-slot state tree
(`ServingEngine.state`, behind the state-space layers' entries), donated and
aliased like it: `num_pages`, the allocator, copy-on-write, defrag, transfer
and the prefix tree count and move the FULL layers' pages alone and cannot
see a ring. Nothing resets a ring when its slot changes hands: a new request
starts at position 0 and every key at or before a row's position has been
written by its own request by the time the row reads it. What cannot follow:
a prefix hit or a hand-off begins a request past position 0 with the ring
empty behind it, so hits are cut (counted) and hand-offs refused, as for a
model that holds state.

The allocator is deliberately host-side pure-python: page churn is a few
integer ops per request per step, nothing a device roundtrip could beat.
`defrag()` exists for pool COMPACTION (paged allocation never fragments in
the "can't allocate despite free space" sense — any free page serves any
request — but long-lived mixed workloads scatter live pages across the
pool; compaction moves them to a dense prefix so the tail can be released
or checkpointed cheaply). It returns a gather plan `apply_defrag` executes
on the device arrays in one indexed copy.

Pages are REFCOUNTED (prefix sharing, serving/prefix_cache.py): the same
pool page may appear in many slots' dense-prefix tables (a shared system
prompt's KV is stored once) and be pinned by the radix tree over known
tokens. `adopt` maps existing pages into a fresh slot's table, `free_slot`
only returns a page to the free list when its last reference drops, and
`cow` gives a slot a private copy-on-write replacement before it appends
into a page someone else can still read. `defrag_plan` moves a shared page
ONCE and patches every referencing table (plus any registered remap
listener — the radix tree keeps its node→page map current this way).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


def pages_for(num_tokens: int, page_size: int) -> int:
    """Pages needed to hold `num_tokens` sequence positions."""
    return -(-num_tokens // page_size)


@dataclasses.dataclass
class PageAllocator:
    """Free-list page accounting + per-slot dense-prefix page tables."""

    num_pages: int
    page_size: int

    def __post_init__(self):
        # LIFO free list: recently freed (still-warm) pages are reused first
        self._free: list[int] = list(range(self.num_pages - 1, -1, -1))
        self._tables: dict[int, list[int]] = {}
        # page → reference count; absent == 0 == on the free list. A page is
        # referenced once per table that lists it plus once if the prefix
        # cache's radix tree pins it (incref/decref).
        self._refs: dict[int, int] = {}
        self._remap_listeners: list = []

    @property
    def num_free(self) -> int:
        return len(self._free)

    def table(self, slot: int) -> list[int]:
        return self._tables.get(slot, [])

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def incref(self, page: int) -> None:
        """Take an extra reference on an allocated page (radix-tree pin or
        cross-slot sharing)."""
        if page not in self._refs:
            raise ValueError(f"incref of free page {page}")
        self._refs[page] += 1

    def decref(self, page: int) -> None:
        """Drop one reference; the last drop returns the page to the free
        list (LIFO, so the still-warm page is reused first)."""
        r = self._refs[page] - 1
        if r == 0:
            del self._refs[page]
            self._free.append(page)
        else:
            self._refs[page] = r

    def _alloc_page(self) -> int:
        p = self._free.pop()
        self._refs[p] = 1
        return p

    def ensure(self, slot: int, num_tokens: int, reclaim=None) -> bool:
        """Grow `slot`'s table to cover `num_tokens` positions. Returns False
        (allocating nothing) when the pool cannot cover the growth — the
        scheduler then preempts or stalls. `reclaim(n)`, when given, is asked
        to free up to n more pages ONLY once the free list is short — cached
        prefix pages are reclaimed strictly behind truly-free pages."""
        table = self._tables.setdefault(slot, [])
        need = pages_for(num_tokens, self.page_size) - len(table)
        if need <= 0:
            return True
        if need > len(self._free) and reclaim is not None:
            reclaim(need - len(self._free))
        if need > len(self._free):
            return False
        table.extend(self._alloc_page() for _ in range(need))
        return True

    def adopt(self, slot: int, pages: list[int]) -> None:
        """Map already-allocated (shared) pages into the dense prefix of a
        fresh slot's table, taking a reference on each — the admission path
        of a radix-tree prefix hit."""
        table = self._tables.setdefault(slot, [])
        if table:
            raise ValueError(f"adopt into non-empty table of slot {slot}")
        for p in pages:
            self.incref(p)
        table.extend(pages)

    def cow(self, slot: int, index: int):
        """Copy-on-write: repoint `slot`'s table entry `index` (a page some
        other table or the radix tree still references) at a fresh page and
        drop the shared reference. Returns (src, dst) for the one-page device
        copy the engine step executes, or None when the page was exclusive
        (write in place). Needs one free page — the caller reclaims/preempts
        first."""
        table = self._tables[slot]
        old = table[index]
        if self._refs[old] <= 1:
            return None
        if not self._free:
            raise RuntimeError("cow needs a free page; reclaim/preempt first")
        new = self._alloc_page()
        table[index] = new
        self.decref(old)
        return old, new

    def truncate(self, slot: int, n_pages: int) -> int:
        """Shrink `slot`'s table to its first `n_pages` entries, dropping
        one reference per removed page (an exclusively-held page returns
        to the free list; a shared one lives on for its other holders).
        The speculative-decode rollback: provisional pages a rejected
        draft suffix spilled into are released between steps. Returns the
        number of entries dropped."""
        table = self._tables.get(slot, [])
        dropped = table[n_pages:]
        del table[n_pages:]
        for p in dropped:
            self.decref(p)
        return len(dropped)

    def free_slot(self, slot: int) -> None:
        for p in self._tables.pop(slot, []):
            self.decref(p)

    def register_remap_listener(self, fn) -> None:
        """`fn(mapping: dict[old_page, new_page])` is called whenever defrag
        renumbers pages, so holders of page ids outside the slot tables (the
        radix tree) stay consistent."""
        self._remap_listeners.append(fn)

    def defrag_plan(self):
        """Compact live pages to a dense prefix. Rewrites the host tables in
        place and returns (src, n_live): `src` (num_pages,) int32 where
        new page i must be copied from old page src[i] (identity past
        n_live) — feed to `apply_defrag`. Returns None when already compact.
        A multiply-referenced page is moved ONCE (one mapping entry, one
        device copy) and every table listing it is patched; remap listeners
        fire so the radix tree follows."""
        live = sorted(self._refs)  # every page any table or the tree holds
        if live == list(range(len(live))):
            return None
        mapping = {old: new for new, old in enumerate(live)}
        for table in self._tables.values():
            table[:] = [mapping[p] for p in table]
        self._refs = {mapping[p]: r for p, r in self._refs.items()}
        src = list(range(self.num_pages))
        for old, new in mapping.items():
            src[new] = old
        self._free = list(range(self.num_pages - 1, len(live) - 1, -1))
        for fn in self._remap_listeners:
            fn(mapping)
        return jnp.asarray(src, jnp.int32), len(live)


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_defrag(pool, src: jnp.ndarray):
    """Apply a defrag plan to a pool pytree: one gather along the page axis
    (axis 0) per array; the trash page stays put.
    The old pool is donated — callers rebind (`pool = apply_defrag(pool,
    src)`), and XLA may reuse the donated buffers instead of double-
    buffering the whole KV pool during compaction."""
    full = jnp.concatenate(
        [src, jnp.asarray([pool_trash_index(pool)], jnp.int32)]
    )
    return jax.tree.map(lambda a: a[full], pool)


def copy_split_pages(pool, src: jnp.ndarray, dst: jnp.ndarray):
    """A step's copy-on-write splits on a pool pytree: for each entry with
    `src != dst`, page `src` is copied onto page `dst` in every array (the
    page axis is axis 0). Idle entries (trash onto trash) copy nothing, so a
    step without a split does no work here; with k splits, k pages an array.

    The splits copy one after another in the order of their entries (the
    scheduler's order of making them): a page one split reads may be freed
    and handed out again as a LATER split's fresh page within the step,
    never the reverse. The loop carries the pool, which XLA updates in
    place."""
    split = src != dst
    order = jnp.argsort(~split, stable=True)  # the splits first, in order

    def copy(i, pool):
        s, d = src[order[i]], dst[order[i]]
        return jax.tree.map(lambda a: jax.lax.dynamic_update_slice_in_dim(
            a, jax.lax.dynamic_slice_in_dim(a, s, 1), d, 0), pool)

    return jax.lax.fori_loop(0, split.sum(), copy, pool)


def pool_trash_index(pool) -> int:
    """The trash page index = num_pages (pages axis is num_pages + 1)."""
    return jax.tree.leaves(pool)[0].shape[0] - 1


def _init_layer(shapes, dtype, num_pages, page_size, kv_cache_dtype):
    """One layer's page arrays at `shapes` (the row shapes behind the
    (N+1, ps) page axes). kv_cache_dtype="int8": int8 payloads plus two
    per-page scale arrays (N+1, ps) — one f32 scalar per cache row, rows of
    a page contiguous so every page-axis operation on the pool pytree (COW
    copy, defrag gather, transfer gather/scatter) moves a page's scales with
    its int8 payload for free; 1.0 (identity dequant) for never-written
    rows."""
    lead = (num_pages + 1, page_size)
    if kv_cache_dtype is None:
        return tuple(jnp.zeros(lead + s, dtype) for s in shapes)
    assert kv_cache_dtype == "int8", kv_cache_dtype
    return (
        *(jnp.zeros(lead + s, jnp.int8) for s in shapes),
        jnp.ones(lead, jnp.float32), jnp.ones(lead, jnp.float32),
    )


#: lanes of the chip's vector registers and of its memory tiles
LANE = 128


def key_width(head_dim: int) -> int:
    """The width a cached key is stored at: its own up to a lane tile, a
    whole number of tiles above that, zeros behind the key (they add nothing
    to a score). For a key of 192 the chip's compiler lays a (..., ps, Hkv,
    192) page out with the TOKEN axis minor, which the paged kernel cannot
    read without a copy of the whole pool every call; at 256 the page lies
    as the kernel reads it where 8 key/value heads fill a tile's rows (with
    4, the (ps x Hkv, 256) view the kernel takes is a relayout, not a view),
    for a third more bytes of keys."""
    return head_dim if head_dim <= LANE else -(-head_dim // LANE) * LANE


def gqa_rows(cfg) -> tuple:
    """One cached token's (key, value) rows of a GQA layer of `cfg`'s
    geometry: (Hkv, key width), (Hkv, value width). With differential
    attention the heads lie side by side in one row each, (Hkv x D,): at a
    head of 64 a (ps, Hkv, 64) page is laid out with its token axis minor,
    which the kernel cannot read without a copy of the pool every call; a
    (ps, Hkv x 64) page is lane-dense and holds the same bytes."""
    if getattr(cfg, "differential_attention", False):
        width = cfg.num_kv_heads * cfg.resolved_head_dim
        return (width,), (width,)
    return ((cfg.num_kv_heads, key_width(cfg.resolved_head_dim)),
            (cfg.num_kv_heads, cfg.resolved_v_head_dim))


def init_gqa_pool(
    cfg, num_layers: int, num_pages: int, page_size: int,
    kv_cache_dtype: str | None = None,
):
    """One GQA stack: a (k, v) tuple of page arrays per layer (dtype/row
    shapes from cfg, `gqa_rows`). kv_cache_dtype="int8" → (k, v, k_scale,
    v_scale): int8 payloads at the SAME shapes plus the per-page scale
    arrays."""
    return tuple(
        _init_layer(gqa_rows(cfg), cfg.dtype, num_pages, page_size,
                    kv_cache_dtype)
        for _ in range(num_layers)
    )


def init_mla_pool(
    cfg, num_layers: int, num_pages: int, page_size: int,
    kv_cache_dtype: str | None = None,
):
    """One MLA stack: a (c, kr) tuple per layer (absorbed latent cache);
    kv_cache_dtype="int8" → (c, kr, c_scale, kr_scale)."""
    rows = ((cfg.mla_kv_lora_rank,), (cfg.mla_qk_rope_head_dim,))
    return tuple(
        _init_layer(rows, cfg.dtype, num_pages, page_size, kv_cache_dtype)
        for _ in range(num_layers)
    )


def pool_axes(cfg, kv_cache_dtype: str | None = None) -> tuple:
    """Mesh-axis tuples for the page arrays of one layer (feed each through
    `MeshContext.sharding(*axes)`). Page IDs stay GLOBAL — the page axis is
    never sharded, so the host-side allocator/scheduler/prefix-cache
    integer accounting composes with any mesh unchanged. Only the per-page
    head dim is partitioned over tp:

    - GQA:  k/v shard KV heads (each tp rank owns Hkv/tp heads of every
      page — the query heads of its GQA groups live on the same rank, so
      the paged attention gather/softmax is rank-local);
    - MLA:  the kv latent `c` shards its rank dim r (the big cached
      quantity; heads share one latent, so there is no head dim to cut),
      while the tiny shared rope head `kr` (dr floats/token) replicates.

    With kv_cache_dtype="int8" the int8 payloads keep the fp cuts and the
    two per-page scale arrays REPLICATE — a scale is one scalar per cache
    row with no head/latent dim to partition, and every rank needs it to
    dequantize its local head slice.
    """
    if cfg.attention_type == "mla":
        data = ((None, None, "tp"), (None, None, None))
    elif getattr(cfg, "differential_attention", False):
        data = ((None, None, "tp"), (None, None, "tp"))
    else:
        data = ((None, None, "tp", None), (None, None, "tp", None))
    if kv_cache_dtype is None:
        return data
    return data + ((None, None), (None, None))


def pool_shardings(
    cfg, stack_layers: list[int], mesh_ctx, kv_cache_dtype: str | None = None,
):
    """NamedShardings matching `init_pool`'s structure: per stack, per
    layer, one per page array."""
    layer = tuple(
        mesh_ctx.sharding(*a) for a in pool_axes(cfg, kv_cache_dtype)
    )
    return [(layer,) * (cfg.num_passes * L) for L in stack_layers]


def init_pool(
    cfg, stack_layers: list[int], num_pages: int, page_size: int,
    mesh_ctx=None, kv_cache_dtype: str | None = None,
):
    """The pool of a decoder: per stack (dense decoders have one; MoE
    decoders a dense prefix + MoE stack — mirrors generate.py) a tuple with
    one tuple of page arrays per (pass, layer): `cfg.num_passes * L`
    entries, entry `t * L + l`. With a `mesh_ctx` the arrays are
    placed mesh-sharded (`pool_axes`). With kv_cache_dtype="int8" each
    layer carries int8 payloads plus per-page scale arrays — same page
    axis, so COW/defrag/transfer move scales with their pages and the
    host-side allocator never knows."""
    init = init_mla_pool if cfg.attention_type == "mla" else init_gqa_pool
    pool = [
        init(cfg, cfg.num_passes * L, num_pages, page_size, kv_cache_dtype)
        for L in stack_layers
    ]
    if mesh_ctx is not None:
        pool = jax.device_put(
            pool, pool_shardings(cfg, stack_layers, mesh_ctx, kv_cache_dtype)
        )
    return pool


def state_shapes(cfg, max_slots: int) -> tuple:
    """One state layer's (conv, ssm) ShapeDtypeStructs. The convolution's
    carried inputs in the model's dtype with the slot axis second (K-1 = 3
    rows would pad to a whole tile of 16 if they were the second-minor
    axis); the recurrent state in float32 whatever the model's dtype (the
    family's kernels accumulate it so; rounded to bf16 at every token its
    error compounds), (N, C) with the channels the vector axis."""
    C, N, K = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    return (
        jax.ShapeDtypeStruct((K - 1, max_slots + 1, C), cfg.dtype),
        jax.ShapeDtypeStruct((max_slots + 1, N, C), jnp.float32),
    )


def num_state_layers(cfg) -> int:
    from automodel_tpu.models.llm.decoder import STATE_OPS

    return sum(op in STATE_OPS for op in cfg.layer_ops or ())


def state_shardings(cfg, mesh_ctx):
    """NamedShardings matching `init_state`: replicated (an engine whose
    model holds state refuses tp > 1, see ServingEngine._validate_mesh)."""
    layer = (mesh_ctx.sharding(None, None, None),) * 2
    return (layer,) * num_state_layers(cfg)


def init_state(cfg, max_slots: int, mesh_ctx=None) -> tuple:
    """The per-slot state of a decoder: one `(conv, ssm)` pair per layer
    that is not attention, in layer order; `()` for a decoder of attention
    alone. Zeros, though nothing reads them: a run that starts at position 0
    starts from zeros by a select (ops/selective_scan.py), so a slot is never
    reset, whoever held it before."""
    state = tuple(
        tuple(jnp.zeros(s.shape, s.dtype) for s in state_shapes(cfg, max_slots))
        for _ in range(num_state_layers(cfg))
    )
    if mesh_ctx is not None and state:
        state = jax.device_put(state, state_shardings(cfg, mesh_ctx))
    return state


def keeps_rings(cfg) -> bool:
    """Whether some attention layer keeps its keys and values in a ring per
    slot: a GQA layer with a sliding window (MLA layers with one stay on the
    pool). Such a model's requests begin at position 0 alone."""
    from automodel_tpu.models.llm.decoder import layer_windows

    return cfg.attention_type != "mla" and any(layer_windows(cfg))


def ring_pages(window: int, prefill_chunk: int, page_size: int) -> int:
    """Pages of one slot's ring: what `window - 1` tokens behind a chunk's
    first row and the chunk's own `prefill_chunk` rows can span, and one more
    for where the span starts inside a page."""
    return pages_for(window - 1 + prefill_chunk, page_size) + 1


def init_rings(
    cfg, num_layers: int, max_slots: int, ring: int, page_size: int,
    mesh_ctx=None, kv_cache_dtype: str | None = None,
) -> tuple:
    """The window layers' cache: per layer (and pass) the page arrays of a
    pool of (max_slots + 1) * `ring` pages, slot s's ring at pages s * ring
    and on, the last slot the trash slot, at the rows of `cfg`, the window
    layers' own configuration (`decoder.kind_config`). Zeros, and nothing
    ever clears them (see the module's text). Replicated on a mesh: an
    engine with window layers refuses tp > 1."""
    rings = tuple(
        _init_layer(gqa_rows(cfg), cfg.dtype, (max_slots + 1) * ring - 1,
                    page_size, kv_cache_dtype)
        for _ in range(num_layers)
    )
    if mesh_ctx is not None and rings:
        rings = jax.device_put(rings, mesh_ctx.replicated())
    return rings


def ring_page_tables(ring_slot, ring: int, pages_per_slot: int):
    """(T, P) int32: for each row the ring page that holds page c of its
    slot's sequence, c < P: the ring as a page table, so that the paged
    attention op reads a ring as it reads the pool. `ring_slot` (T,) is the
    row's slot, the trash slot for a pad row."""
    column = jnp.arange(pages_per_slot, dtype=jnp.int32) % ring
    return ring_slot[:, None] * ring + column[None, :]


def pool_bytes(pool) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
