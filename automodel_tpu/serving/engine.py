"""Continuous-batching serving engine: one jitted fixed-shape step + loop.

The serving analog of `inference/generate.py` (which stays the batch-
synchronous offline path): requests of any length join and leave a running
batch freely. The device-side step function has ONE compiled signature for
the whole serving run —

    step(params, pool, plan) -> (pool, outputs)

where `plan` is the fixed-shape `StepPlan` the scheduler builds (a flat
`token_budget`-row ragged token batch: decode rows of many requests
interleaved with chunked-prefill rows) packed into ONE int32 vector, `pool`
is the paged KV cache (kv_pages.py; donated, so the update is in-place
buffer reuse), and `outputs` is ONE int32 vector too, the sampled token and
its log-probability per slot, for the host scheduler to absorb: one transfer
each way a step. No
shape in the step depends on which requests are active, how long they are,
or how many pages they hold — requests joining/leaving NEVER recompile
(pinned by the jit cache-miss counter test in tier-1).

Layer math is shared with generate.py (project_qkv / mlp_inner / the MoE
stack split); only attention differs — the ragged paged op from
ops/paged_attention.py (XLA gather reference on CPU, Pallas kernel on TPU),
with the MLA absorbed-decode algebra reproduced over the latent page pool.
A layer that names another mixer than attention (a Mamba-1 state-space layer,
models/llm/mamba.py) runs the ragged selective scan (ops/selective_scan.py)
over a per-SLOT state that lives in a tree of its own beside the pool
(`ServingEngine.state`, kv_pages.init_state; donated and aliased like the
pool): `step(params, pool, plan, state) -> (pool, outputs, state)`.
A GQA attention layer with a WINDOW keeps no pool pages either: its keys and
values live in a ring of a few pages per slot (kv_pages.init_rings), in the
same state tree behind the state-space entries, and the same paged kernel
reads it through an arithmetic page table with the window in its block list
and mask. The pool then holds the full-attention layers alone.
The layers are walked differently too: generate.py scans stacked arrays, the
step loops in Python over PER-LAYER buffers (split_layer_stacks below splits
the weights once, at construction; kv_pages.init_pool makes the pool per
layer), because a scan over stacks copies every layer's operands out of them
on every step.

Sampling runs inside the jit: greedy where a slot's temperature <= 0, else
top-k/top-p (static, engine-wide) filtered categorical with the key derived
as fold_in(key(slot seed), position) — deterministic per request and stable
across preempt-and-requeue recompute.

Speculative decoding (ServingConfig.speculative, opt-in): the scheduler
appends up to K drafted rows behind each decode slot's pending token
(speculative/serve_draft.py sources them), the SAME ragged paged-attention
step scores the whole block, and an in-jit verify tail
(speculative/acceptance.py — greedy: longest matching prefix; sampled:
distribution-preserving one-hot rejection) returns the committed-candidate
block plus the accepted length. The spec step is its own single compiled
signature — fixed (S, K+1) verify rows, idle slots carry empty blocks —
and the plain program is byte-identical to the speculation-disabled
engine's (both pinned by analysis baselines paged_serve_step /
spec_serve_step).

Pod-scale serving (mesh_ctx given): the SAME step runs TP/EP-sharded
under GSPMD over a mesh slice — the paged pool becomes a mesh-sharded
array (kv_pages.pool_axes: pages global, GQA KV heads / MLA latent rank
partitioned over tp), params re-shard onto the serving plan
(_serving_param_specs), MoE decoders dispatch experts through PR 1's EP
shard_map inside the step, and the sampling tail runs on replicated
logits so it stays collective-free (the sharded_serve_step analysis
baseline pins the per-layer all-reduce budget and the pool donation).
Page IDs are global, so the host-side scheduler/allocator/prefix-cache
never know the mesh exists. Data parallelism is a layer above: N engine
replicas behind serving/router.py's ReplicaRouter.

`serve_batch()` is the offline API (recipes/llm/serve.py wires it to the
CLI): submit a list of requests with arrival times, drive steps until
drained, return per-request outputs + throughput/latency counters (logged
through loggers/metric_logger.MetricLogger when one is passed).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.inference.generate import (
    _dense_mlp,
    _embed,
    mla_absorbed_inputs,
)
from automodel_tpu.inference.sampling import filter_logits
from automodel_tpu.models.common.layers import cast_params
from automodel_tpu.models.llm.decoder import (
    ATTENTION_OPS,
    OPERATOR_STACKS,
    _dense,
    apply_norm,
    cross_decoder,
    diff_combine,
    gmu_block,
    kind_config,
    layer_operators,
    layer_windows,
    make_freq_for,
    project_qkv,
    unembed,
)
from automodel_tpu.models.llm.mamba import (
    mamba_inputs,
    mamba_output,
    mamba_selection,
)
from automodel_tpu.ops.paged_attention import (
    diff_row_width,
    ragged_paged_attention,
    ragged_paged_diff_attention,
    ragged_paged_mla_attention,
    row_tile,
    step_row_segments,
)
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.quant import matmul as _mm, quantize_kv_rows
from automodel_tpu.ops.rope import rope_frequencies
from automodel_tpu.ops.selective_scan import (
    ragged_conv,
    ragged_selective_scan,
    step_runs,
)
from automodel_tpu.observability import Observability, ObservabilityConfig
from automodel_tpu.resilience.faults import fault_hit
from automodel_tpu.serving.kv_pages import (
    PageAllocator,
    apply_defrag,
    copy_split_pages,
    init_pool,
    init_rings,
    init_state,
    keeps_rings,
    pool_bytes,
    pool_shardings,
    ring_page_tables,
    ring_pages,
    state_shardings,
)
from automodel_tpu.serving.prefix_cache import PrefixCache, PrefixCacheConfig
from automodel_tpu.serving.scheduler import Request, Scheduler, StepPlan
from automodel_tpu.speculative.acceptance import (
    greedy_accept_length,
    onehot_speculative_verify,
)
from automodel_tpu.speculative.serve_draft import (
    SpeculativeConfig,
    build_draft_source,
)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Static engine geometry + engine-wide sampling filters (per-request
    temperature/eos/seed live on the Request; top-k/top-p are static because
    they shape a lax.top_k/sort inside the jit)."""

    page_size: int = 16
    num_pages: int = 128
    max_slots: int = 8          # concurrent requests resident on device
    pages_per_slot: int = 16    # max context = pages_per_slot * page_size
    token_budget: int = 32      # rows per step (decode + prefill chunks)
    prefill_chunk: int | None = None  # ≤ token_budget; None → token_budget
    top_k: int | None = None
    top_p: float | None = None
    # prefix sharing (serving/prefix_cache.py): refcounted COW pages + a
    # radix tree over known tokens; None/disabled → PR-2 behavior exactly
    prefix_cache: PrefixCacheConfig | None = None
    # speculative decoding (speculative/serve_draft.py): per-slot
    # draft-then-verify inside the one jitted step; None/disabled → the
    # plain one-token-per-slot decode program exactly
    speculative: SpeculativeConfig | None = None
    admission_policy: str = "fifo"  # "fifo" | "prefix-hit"
    # quantized serving (docs/SERVING.md §Quantized serving): int8 KV pages
    # with per-page scale arrays riding the pool pytree, and/or low-precision
    # serve-step linears via ops/quant.quantized_matmul. None/None → the fp
    # engine BYTE-identical (both are trace-time choices; the one jitted
    # step signature, donation and compile-once contract hold either way)
    kv_cache_dtype: str | None = None   # None (model dtype) | "int8"
    serve_precision: str | None = None  # None | "int8" | "fp8"
    # debug tripwire: run the jitted step under jax.transfer_guard
    # ("disallow") so an unintended device↔host transfer inside the step
    # raises instead of silently serializing the serve loop (the dryrun
    # stages turn this on; see docs/ANALYSIS.md)
    guard_transfers: bool = False
    # host-side tracing/metrics/profiling (automodel_tpu/observability/);
    # None/disabled → null tracer, the jitted step is byte-identical and
    # the serve loop pays two attribute lookups per probe
    observability: ObservabilityConfig | None = None

    def __post_init__(self):
        assert self.page_size >= 1 and self.num_pages >= 1
        assert self.max_slots >= 1 and self.token_budget >= 1
        assert self.pages_per_slot >= 1
        if self.prefill_chunk is not None:
            assert 1 <= self.prefill_chunk <= self.token_budget
        assert self.admission_policy in ("fifo", "prefix-hit")
        assert self.kv_cache_dtype in (None, "int8"), self.kv_cache_dtype
        assert self.serve_precision in (None, "int8", "fp8"), (
            self.serve_precision
        )
        if self.admission_policy == "prefix-hit":
            assert self.prefix_cache is not None and self.prefix_cache.enabled
        if self.speculative is not None and self.speculative.enabled:
            # at least one full verify block must fit a step
            assert self.token_budget >= self.speculative.draft_len + 1, (
                "token_budget must cover draft_len + 1 verify rows"
            )


def _percentiles_ms(samples: list) -> tuple:
    """(p50, p95) of a millisecond sample list, or (None, None)."""
    if not samples:
        return None, None
    return (
        round(float(np.percentile(samples, 50)), 4),
        round(float(np.percentile(samples, 95)), 4),
    )


def _stamp_arrivals(requests, step_idx: int, watch: list) -> None:
    """Mark every request whose arrival window just opened with the wall
    clock, and put it on the TTFT watch list. Serve-loop helper (the loop
    owns the wall clock; step indices alone cannot price TTFT)."""
    now = time.perf_counter()
    for r in requests:
        if r.arrival <= step_idx and r.arrived_t < 0:
            r.arrived_t = now
            watch.append(r)


def _resolve_ttft(watch: list) -> list:
    """Stamp time-to-first-token on every watched request that committed
    its first token; returns the still-waiting remainder."""
    now = time.perf_counter()
    still = []
    for r in watch:
        if r.generated:
            r.ttft_s = now - r.arrived_t
        else:
            still.append(r)
    return still


logger = logging.getLogger(__name__)

#: the keys of a decoder's parameter tree that hold its layers, and (a model
#: whose layers name their mixer) each kind of operator's stack
LAYER_STACKS = ("layers", "dense_layers", "moe_layers",
                *OPERATOR_STACKS.values())


@functools.partial(jax.jit, static_argnames=("dtype",))
def _unstack(leaves, dtype):
    """Per stacked leaf, one array per index of its leading (layer) axis,
    floating leaves cast to `dtype` on the way."""
    def one(leaf):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            leaf = leaf.astype(dtype)
        return tuple(leaf[i] for i in range(leaf.shape[0]))

    return [one(leaf) for leaf in leaves]


def _batches(leaves, budget: int):
    """`leaves` in order, cut into runs of at most `budget` bytes (a leaf
    above it goes alone)."""
    batch, held = [], 0
    for leaf in leaves:
        if batch and held + leaf.nbytes > budget:
            yield batch
            batch, held = [], 0
        batch.append(leaf)
        held += leaf.nbytes
    if batch:
        yield batch


def split_layer_stacks(params: dict, dtype) -> dict:
    """The parameter tree with every layer stack (`LAYER_STACKS`: a dict
    whose leaves carry a leading layer axis) replaced by a tuple of per-layer
    trees, floating leaves in `dtype`: the form the serve step reads, every
    layer's weights a buffer of its own and an argument of the program, so
    no layer is sliced out of a stack step after step.

    CONSUMES the stacks it splits: the stacked leaves are split a batch at a
    time (small leaves share a program; no batch holds more bytes than the
    largest leaf) and their buffers deleted before the next batch's turn, so
    the peak is one stacked leaf above the tree's own size. A second whole
    copy does not fit beside a model that fills most of a chip. The caller's
    tree shares those buffers and must not read its layer stacks afterwards.
    A stack that is already a sequence of per-layer trees passes through
    untouched, so one split tree can be handed to many engines."""
    out = dict(params)
    for key in LAYER_STACKS:
        stack = params.get(key)
        if not isinstance(stack, dict):
            continue  # absent, or per layer already
        leaves, treedef = jax.tree.flatten(stack)
        num_layers = leaves[0].shape[0]
        assert all(leaf.shape[0] == num_layers for leaf in leaves), key
        parts = []  # per leaf, its per-layer arrays
        for batch in _batches(leaves, max(leaf.nbytes for leaf in leaves)):
            parts += jax.block_until_ready(_unstack(batch, dtype))
            for leaf in batch:
                if isinstance(leaf, jax.Array):
                    leaf.delete()
        out[key] = tuple(
            treedef.unflatten([part[i] for part in parts])
            for i in range(num_layers)
        )
    return out


def packed_layout(fields) -> tuple:
    """((name, offset, shape, dtype), ...) of `fields` ((name, shape, dtype)
    pairs in order) laid end to end in ONE int32 vector, a float32 field as
    its bits: what crosses between host and device in a single transfer."""
    layout, off = [], 0
    for name, shape, dtype in fields:
        layout.append((name, off, tuple(shape), np.dtype(dtype)))
        off += int(np.prod(shape))
    return tuple(layout)


def _extent(field) -> slice:
    _name, off, shape, _dtype = field
    return slice(off, off + int(np.prod(shape)))


def pack_host(layout, values) -> np.ndarray:
    """`values` (name -> array) as the one int32 vector of `layout`."""
    buf = np.empty(_extent(layout[-1]).stop, np.int32)
    for field in layout:
        buf[_extent(field)] = np.asarray(
            values[field[0]], field[3]).view(np.int32).ravel()
    return buf


def unpack_host(layout, buf: np.ndarray) -> tuple:
    """The fields of `layout` as numpy views of the fetched vector `buf`."""
    return tuple(buf[_extent(f)].view(f[3]).reshape(f[2]) for f in layout)


def pack_device(arrays) -> jax.Array:
    """Traced arrays (int32 or float32) as one int32 vector, in order."""
    return jnp.concatenate([
        (a if a.dtype == jnp.int32
         else jax.lax.bitcast_convert_type(a, jnp.int32)).reshape(-1)
        for a in arrays
    ])


def _share_words(share_runs) -> tuple:
    """The step's two words on its expert layers that hold a share of their
    experts (none in another model): how many ran the whole path, and the
    most live (row, expert) pairs one of them had."""
    if not share_runs:
        return ()
    live, whole = (jnp.stack(v) for v in zip(*share_runs))
    return jnp.sum(whole, dtype=jnp.int32), jnp.max(live)


def unpack_device(layout, buf: jax.Array) -> dict:
    """The uploaded vector `buf` sliced back into the fields of `layout`."""
    out = {}
    for field in layout:
        name, _off, shape, dtype = field
        x = buf[_extent(field)].reshape(shape)
        out[name] = (x if dtype == np.int32
                     else jax.lax.bitcast_convert_type(x, dtype))
    return out


class ServingEngine:
    """Paged-cache continuous-batching engine for the generic decoder
    families (TransformerConfig / MoETransformerConfig): GQA or MLA attention
    over pages; a looped decoder's passes walked over a pool of passes x
    layers entries; layers that name a state-space mixer instead of attention
    (`cfg.layer_ops`), whose convolution and recurrent state live per SLOT in
    `self.state` beside the pool, and GQA layers with a sliding window, whose
    keys and values live in a ring per slot in that same tree. What begins a
    request at a position other than 0 without having run the rows before
    it has no state, and no ring, to begin from: for a model that holds
    either (`begins_at_zero`) prefix hits are cut to none (counted),
    speculation and a hand-off between pools are refused by name. Window
    layers whose attention has a geometry of its own (key/value heads, value
    width, sinks: `decoder.kind_config`) keep rings at that geometry. The
    heterogeneous engine (HetMoEConfig: Step-3.5's and MiniMax-M3's layers,
    sparse index caches) is not servable here."""

    def __init__(
        self,
        params,
        cfg,
        serve_cfg: ServingConfig = ServingConfig(),
        draft_source=None,
        mesh_ctx=None,
        obs: Observability | None = None,
        track: str = "engine",
    ):
        """The engine TAKES OWNERSHIP of `params`. A tree with stacked
        layers (what `init` and a checkpoint give: `params["layers"]` a dict
        whose leaves carry a leading layer axis) is split into per-layer
        buffers by `split_layer_stacks`, which deletes each stacked buffer
        as it goes; the caller's tree shares those buffers, so its layer
        stacks are gone when this returns (hand over a copy to keep them).
        That happens first, before the pool is allocated, so construction
        never holds more than one stacked leaf above the steady state. A
        tree that is split already (its stacks sequences of per-layer
        trees) is used as it is and stays the caller's: the routers split
        once and build every engine from the one tree. Either way
        `self.params` is the per-layer tree, on the engine's mesh."""
        from automodel_tpu.models.moe_lm.het_moe import HetMoEConfig

        if isinstance(cfg, HetMoEConfig):
            raise NotImplementedError(
                "ServingEngine serves TransformerConfig / MoETransformerConfig "
                "decoders: full attention layers over one page pool, window "
                "layers (at a geometry of their own, `window_attention`) over "
                "rings, layers whose state lives per slot; the het engine's "
                "per-layer geometries and index caches need a step function "
                "and a pool of their own"
            )
        # serve-step linear precision: all decoder/generate linears already
        # route through ops/quant.matmul(x, kernel, cfg.linear_precision),
        # so low-precision serving is ONE config replace — the params stay
        # high precision (dynamic per-channel quantization inside the step)
        if serve_cfg.serve_precision is not None:
            cfg = dataclasses.replace(
                cfg, linear_precision=serve_cfg.serve_precision
            )
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        # int8 KV pages + per-page scales (a trace-time choice: the fp and
        # quantized engines each compile their one program; fp stays
        # byte-identical to the quantization-unaware engine)
        self._kv_quant = serve_cfg.kv_cache_dtype is not None
        if self._kv_quant and getattr(cfg, "differential_attention", False):
            raise NotImplementedError(
                "int8 key/value pages under differential attention: its "
                "kernel reads merged bf16 pages and takes no scales")
        # observability bundle: routers pass ONE shared bundle to every
        # engine (distinct track names) so a single tracer/registry sees
        # the whole request lifecycle across replica classes; standalone
        # engines build their own from the config
        self.obs = obs if obs is not None else Observability(
            serve_cfg.observability
        )
        self.track = track
        self.is_moe = getattr(cfg, "moe", None) is not None
        self.is_mla = cfg.attention_type == "mla"
        # some layer carries a state from token to token (kv_pages.init_state)
        self.holds_state = cfg.holds_state
        self._plan_layers()
        spec = serve_cfg.speculative
        if self.begins_at_zero and spec is not None and spec.enabled:
            raise NotImplementedError(
                "speculative decoding over a model that holds a recurrent "
                "state or keeps a window layer's keys in a ring per slot: a "
                "rejected draft would have to roll the state back (no "
                "snapshot of it is kept), and a verify block's rows may "
                "overwrite ring pages the pending row still reads"
            )
        # tp/ep-sharded step (mesh_ctx set): the paged pool becomes a
        # mesh-sharded array (kv_pages.pool_axes) and GSPMD partitions the
        # ONE jitted step over the mesh — page IDs stay global, so the host
        # scheduler/allocator/prefix cache are untouched. mesh_ctx=None is
        # the PR-2 single-process program, byte-identical (pinned by the
        # paged_serve_step / spec_serve_step analysis baselines); a trivial
        # 1-device mesh runs the sharded code path with no-op constraints.
        self._mesh = mesh_ctx
        if mesh_ctx is not None:
            self._validate_mesh(cfg, serve_cfg, mesh_ctx)
        # the layer stacks are split first, before anything else is
        # allocated: the split gives the stacked buffers up leaf by leaf
        self.params = cast_params(
            split_layer_stacks(params, cfg.dtype), cfg.dtype
        )
        if mesh_ctx is not None:
            from automodel_tpu.parallel.sharding import logical_to_shardings

            # params may arrive with ANY placement (the recipe chassis'
            # FSDP shardings flow straight in — no de-shard hop through
            # host memory); device_put reshards onto the serving plan
            self.params = jax.device_put(
                self.params,
                logical_to_shardings(
                    self._serving_param_specs(), mesh_ctx,
                    shapes=jax.tree.map(lambda p: p.shape, self.params),
                ),
            )

        self.pool = init_pool(
            cfg, self._stack_attn,
            serve_cfg.num_pages, serve_cfg.page_size,
            mesh_ctx=self._mesh, kv_cache_dtype=serve_cfg.kv_cache_dtype,
        )
        # the pool's shardings, in the pool's own structure (mesh only)
        self._pool_shardings = None if self._mesh is None else pool_shardings(
            cfg, self._stack_attn, self._mesh,
            serve_cfg.kv_cache_dtype,
        )
        # what the layers that are not attention carry per SLOT: a tree of
        # its own, so that nothing which maps over the pool's page axis
        # (copy-on-write, defrag, transfer) ever sees a slot-axis array;
        # behind them the window layers' rings, one per layer and pass; `()`
        # for a decoder of full attention alone
        ssm_state = init_state(cfg, serve_cfg.max_slots, self._mesh)
        self._num_ssm = len(ssm_state)
        self.state = ssm_state + init_rings(
            kind_config(cfg, "window_attention"),
            cfg.num_passes * sum(self._stack_rings), serve_cfg.max_slots,
            self._ring_pages, serve_cfg.page_size, self._mesh,
            serve_cfg.kv_cache_dtype,
        )
        # ENGINE-LIFETIME prefix cache (SGLang-RadixAttention-style): with
        # the cache enabled, the refcounted allocator and the radix tree
        # are created ONCE here and threaded through every scheduler this
        # engine makes — the device pool above already persists across
        # serve_batch calls, so a system prompt cached during one call
        # serves every later call until `reset_prefix_cache()`. Cache off →
        # each scheduler keeps its private throwaway allocator (per-call
        # semantics exactly as before).
        pc = serve_cfg.prefix_cache
        if self.begins_at_zero and pc is not None and pc.enabled:
            logger.warning(
                "prefix cache over a model that holds a recurrent state or a "
                "window layer's ring per slot: every hit is CUT "
                "(serve_prefix_hits_cut_total counts them): adopted pages "
                "would skip rows whose state, or whose window's keys, nobody "
                "kept"
            )
        if pc is not None and pc.enabled:
            self.alloc = PageAllocator(serve_cfg.num_pages, serve_cfg.page_size)
            self.prefix = PrefixCache(self.alloc, serve_cfg.page_size, pc)
        else:
            self.alloc = None
            self.prefix = None
        # speculative decoding: a STATIC trace-time choice — the spec and
        # plain engines each compile exactly one step program (the plain
        # program is byte-identical to the non-speculative engine's, so
        # the paged_serve_step HLO baseline is untouched)
        self._spec = spec if (spec is not None and spec.enabled) else None
        self._draft_source = None
        if self._spec is not None:
            self._draft_source = draft_source or build_draft_source(
                self._spec,
                max_context=serve_cfg.pages_per_slot * serve_cfg.page_size,
            )
        self._needs_hidden = getattr(self._draft_source, "needs_hidden", "none")
        # what a cached token costs here, every pass and layer (and scale
        # plane) counted: with the passes, what makes a page dear
        reg = self.obs.registry
        reg.gauge("serve_passes").set(cfg.num_passes)
        reg.gauge("serve_kv_bytes_per_token").set(
            pool_bytes(self.pool)
            // ((serve_cfg.num_pages + 1) * serve_cfg.page_size)
        )
        # and what a slot costs whatever its length (0: attention alone)
        reg.gauge("serve_attn_layers").set(
            sum(self._stack_attn) + sum(self._stack_rings))
        reg.gauge("serve_ssm_layers").set(self._num_ssm)
        reg.gauge("serve_state_bytes_per_slot").set(
            pool_bytes(self.state[:self._num_ssm]) // (serve_cfg.max_slots + 1)
        )
        # of the attention layers, which keep a ring per slot and which pages
        # (`serve_kv_bytes_per_token` counts the latter alone)
        reg.gauge("serve_window_layers").set(sum(self._stack_rings))
        reg.gauge("serve_full_layers").set(sum(self._stack_attn))
        reg.gauge("serve_window_bytes_per_slot").set(
            pool_bytes(self.state[self._num_ssm:]) // (serve_cfg.max_slots + 1)
        )
        reg.gauge("serve_experts_held").set(
            cfg.moe.num_held if self.is_moe else 0)
        # the pool and the state are donated: both are written in place
        if self._mesh is None:
            self._step = jax.jit(self._step_impl, donate_argnums=(1, 3))
        else:
            # explicit in/out shardings: jit normalizes sharding specs on
            # its outputs (trailing/size-1 axes dropped), so without a
            # pinned signature the SECOND step would see a "different"
            # pool sharding and recompile — breaking the compile-once
            # contract the cache-miss counter tests pin per replica
            rep = self._mesh.replicated()
            psh = self._pool_shardings
            # the plan and the packed outputs are one replicated vector each
            out_sh: list = [psh, rep]
            if self._needs_hidden in ("frontier", "rows"):
                out_sh.append(rep)
            in_sh: list = [
                jax.tree.map(lambda p: p.sharding, self.params),
                psh,
                rep,
            ]
            if self.state:
                ssh = state_shardings(cfg, self._mesh) + jax.tree.map(
                    lambda a: a.sharding, self.state[self._num_ssm:])
                in_sh.append(ssh)
                out_sh.append(ssh)
            self._step = jax.jit(
                self._step_impl,
                donate_argnums=(1, 3),
                in_shardings=tuple(in_sh),
                out_shardings=tuple(out_sh),
            )
        self.steps_run = 0

    def _plan_layers(self) -> None:
        """What `_step_impl` walks, from the configurations alone (no
        parameter is read: tests/step_shapes.py builds a step from shapes):
        per stack its layers' operators, windows, rotary tables, and which
        of its attention layers read the pool and which a ring."""
        cfg, sc = self.cfg, self.serve_cfg
        # stacks mirror generate.py: dense decoder = one; MoE = dense prefix
        # stack then MoE stack. Under an ep>1 mesh the MoE stack routes
        # through PR 1's EP shard_map machinery (dropless dispatch + expert
        # A2A INSIDE the step) instead of the single-shard dropless path.
        if self.is_moe:
            self._stacks = []
            if cfg.first_k_dense > 0:
                self._stacks.append(("dense_layers", _dense_mlp, cfg.first_k_dense))
            self._stacks.append(
                ("moe_layers", self._moe_mlp, cfg.num_moe_layers)
            )
        else:
            self._stacks = [("layers", _dense_mlp, cfg.num_layers)]

        # per stack and layer (kind, index into that kind's operator stack),
        # the index None where attention's weights are the layer's own
        ops = layer_operators(cfg)
        self._stack_ops, off = [], 0
        for *_, L in self._stacks:
            self._stack_ops.append(
                ops[off:off + L] if ops is not None else (("attention", None),) * L)
            off += L
        # per stack and layer its window, a STATIC of the trace (None: full
        # attention): the step's loop is Python's, so the paged op takes the
        # window as a static of each call and a rotary table is chosen, or
        # left out, per layer
        windows = layer_windows(cfg, sum(L for *_, L in self._stacks))
        self._stack_windows, off = [], 0
        for *_, L in self._stacks:
            self._stack_windows.append(tuple(windows[off : off + L]))
            off += L
        # A GQA layer with a window keeps its keys and values in a ring of
        # `_ring_pages` pages per slot instead of pool pages (kv_pages.py);
        # the ring holds the window behind the longest run of rows one slot
        # can have in a step, the prefill chunk. (MLA layers with a window
        # stay on the pool and the reference.)
        rings = keeps_rings(cfg)
        self._ring_pages = ring_pages(
            cfg.sliding_window, sc.prefill_chunk or sc.token_budget,
            sc.page_size,
        ) if rings else 0
        # per stack: layers that read a ring, and pool entries a pass (one
        # per attention layer that does not)
        self._stack_rings = [
            sum(kind in ATTENTION_OPS and bool(w)
                for (kind, _), w in zip(ops, wins))
            if rings else 0
            for ops, wins in zip(self._stack_ops, self._stack_windows)
        ]
        self._stack_attn = [
            sum(kind in ATTENTION_OPS for kind, _ in ops) - n_rings
            for ops, n_rings in zip(self._stack_ops, self._stack_rings)
        ]
        # a model without rotary embedding (cfg.use_rope False) has no table;
        # a layer kind without one (cfg.rope_layers) gets None
        self._inv_freq = rope_frequencies(
            cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
        ) if cfg.use_rope else None
        self._freq_for = make_freq_for(cfg, self._inv_freq)
        # a request may begin at position 0 alone: what a slot carries
        # (a recurrent state, a window layer's ring) cannot be adopted
        self.begins_at_zero = cfg.holds_state or self._ring_pages > 0
        # rows of the paged kernels' q tile, from the step's rows and the
        # elements of one row's attention queries and outputs (the widest
        # kind's, where window layers have a geometry of their own)
        self._attn_row_tile = row_tile(sc.token_budget, cfg.num_heads * (
            2 * cfg.mla_kv_lora_rank + cfg.mla_qk_rope_head_dim
        ) if self.is_mla else max(
            k.num_heads * (k.resolved_head_dim + k.resolved_v_head_dim)
            for k in (cfg, kind_config(cfg, "window_attention"))
        ) if not cfg.differential_attention else diff_row_width(
            cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim))
        # a decoder-hybrid-decoder (decoder.cross_decoder): (memory layer,
        # shared-cache layer, first cross layer); from the first cross layer
        # on the step runs over the S sample rows alone, whose cross-attention
        # calls read the shared layer's pages through segments of their own
        self._cross = cross_decoder(cfg)
        self._cross_row_tile = row_tile(sc.max_slots, diff_row_width(
            cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim)
        ) if self._cross else None

    # -- mesh plumbing ------------------------------------------------------
    @staticmethod
    def _validate_mesh(cfg, serve_cfg, mesh_ctx) -> None:
        """An engine's mesh shards tp (attention/MLP/pool heads) and ep
        (expert dispatch) only — data parallelism is the ReplicaRouter tier
        (serving/router.py), and pp/cp make no sense for one decode step."""
        sizes = mesh_ctx.sizes
        for ax in ("pp", "cp", "dp_replicate", "dp_shard"):
            if sizes[ax] != 1:
                raise ValueError(
                    f"serving mesh must keep {ax}=1 (got {sizes[ax]}): the "
                    "engine shards tp/ep; replicate engines behind a "
                    "ReplicaRouter for data parallelism"
                )
        tp, ep = sizes["tp"], sizes["ep"]
        if tp > 1 and cfg.holds_state:
            raise ValueError(
                f"tp={tp} over a model that holds a recurrent state: the "
                "per-slot state and the scan over it are not partitioned; "
                "serve it on tp=1 and replicate engines behind a ReplicaRouter"
            )
        if tp > 1 and keeps_rings(cfg):
            raise ValueError(
                f"tp={tp} over a model with sliding-window layers: their "
                "keys and values live in a ring per slot, which is not "
                "partitioned over heads; serve it on tp=1 and replicate "
                "engines behind a ReplicaRouter"
            )
        if tp > 1:
            if cfg.attention_type == "mla":
                if cfg.mla_kv_lora_rank % tp:
                    raise ValueError(
                        f"mla_kv_lora_rank={cfg.mla_kv_lora_rank} not "
                        f"divisible by tp={tp} (the latent pool shards r)"
                    )
            elif cfg.num_kv_heads % tp or cfg.num_heads % tp:
                # the GQA head-divisibility constraint (docs/SERVING.md):
                # each tp rank must own whole KV heads of every page, with
                # their GQA query groups on the same rank
                raise ValueError(
                    f"num_heads={cfg.num_heads} / num_kv_heads="
                    f"{cfg.num_kv_heads} not divisible by tp={tp}"
                )
            if cfg.intermediate_size % tp:
                raise ValueError(
                    f"intermediate_size={cfg.intermediate_size} not "
                    f"divisible by tp={tp}"
                )
        if ep > 1:
            moe = getattr(cfg, "moe", None)
            if moe is None:
                raise ValueError("ep>1 needs an MoE decoder")
            if not moe.holds_all_experts:
                raise ValueError(
                    f"ep={ep} over a layer that holds a share of its experts "
                    f"({moe.num_held} of {moe.n_routed_experts}): the share is "
                    "one rank's part, held without its exchange"
                )
            if moe.n_routed_experts % ep:
                raise ValueError(
                    f"n_routed_experts={moe.n_routed_experts} not "
                    f"divisible by ep={ep}"
                )
            if serve_cfg.token_budget % ep:
                # the EP shard_map splits the flat token batch over ep
                raise ValueError(
                    f"token_budget={serve_cfg.token_budget} not divisible "
                    f"by ep={ep}"
                )

    def _serving_param_specs(self):
        """Model param specs adjusted for the serving TP plan. GQA keeps the
        training plan (q/k/v/o on heads, MLP column/row — so k/v land
        pre-sharded on the pool's KV-head cut). MLA switches the attention
        block to LATENT-parallel: heads share one cached latent, so head
        sharding would force every rank to read the full latent pages;
        instead `kv_up_proj` shards its rank dim r (matching the pool) and
        the head-sharded q/o projections replicate — scores and the
        absorbed value product then reduce over the sharded r via two
        all-reduces per layer, and the big cached quantity is what halves
        per chip."""
        if self.is_moe:
            from automodel_tpu.models.moe_lm import decoder as mod
        else:
            from automodel_tpu.models.llm import decoder as mod
        specs = mod.param_specs(self.cfg)

        def _drop_heads(spec):
            return tuple(None if a == "heads" else a for a in spec)

        def _is_spec(x):
            return isinstance(x, tuple)

        for key in LAYER_STACKS:
            ld = specs.get(key)
            if not ld:
                continue
            if self.is_mla:
                for name in ("q_proj", "q_up_proj", "o_proj"):
                    if name in ld:
                        ld[name] = jax.tree.map(
                            _drop_heads, ld[name], is_leaf=_is_spec
                        )
                if "kv_up_proj" in ld:
                    ld["kv_up_proj"]["kernel"] = ("layers", "mla_latent", None)
            # the engine holds one tree per layer (split_layer_stacks): each
            # gets the stack's specs less their leading "layers" axis
            one = jax.tree.map(lambda sp: sp[1:], ld, is_leaf=_is_spec)
            specs[key] = (one,) * len(self.params[key])
        return specs

    def _constrain_rep(self, x):
        """Pin an activation replicated (no-op off-mesh). Applied to the
        post-layer hidden and the logits, so every cross-rank reduction
        happens INSIDE the layer stack / unembed and the sampling tail
        (filters, fold_in keys, categorical) is rank-local — zero
        collectives after the logits all-gather, pinned by the
        sharded_serve_step baseline."""
        if self._mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, self._mesh.replicated())

    def _constrain_pool(self, pool):
        """Pin every layer's page arrays to their kv_pages.pool_axes layout
        through the COW block and the layer loop (no-op off-mesh)."""
        if self._mesh is None:
            return pool
        return jax.lax.with_sharding_constraint(pool, self._pool_shardings)

    def _moe_mlp(self, h, lp, cfg, b, share_runs: list):
        """MoE block of the step. Dropless dispatch always: the capacity
        dispatcher's bound depends on the token population, so a
        capacity-trained config would drop differently at serve time;
        dropless is exact for any population. Under ep>1 that is PR 1's
        EP dispatch (sort + ragged GEMM + expert A2A confined to this
        step) via the shard_map wrapper — the flat token batch shards over
        ep, expert weights enter sharded on ep only. Routing is
        deterministic in the logits, so EP changes where experts run,
        never which tokens they see. A layer that holds a share of its
        experts routes its pad rows (slot < 0) to no expert: they are all
        alike, so they would all add the same held experts' pairs to its
        live ones, which its compact path bounds; a real row's result is
        unchanged. Such a layer appends (its live pairs, whether it ran the
        whole path) to `share_runs`."""
        from automodel_tpu.moe.layer import moe_forward

        moe_cfg = dataclasses.replace(cfg.moe, dispatcher="dropless")
        x = rms_norm(h, lp["post_attn_norm"]["scale"], cfg.rms_norm_eps,
                     cfg.zero_centered_norm)
        rows = None if moe_cfg.holds_all_experts else (b["slot"] >= 0)[None]
        moe_out, _aux, stats = moe_forward(
            lp["moe"], moe_cfg, x, token_mask=rows, mesh_ctx=self._mesh,
            scope="serve.moe"
        )
        if "share_live" in stats:
            share_runs.append((stats["share_live"], stats["share_whole"]))
        return h + moe_out

    # -- device step --------------------------------------------------------
    def _attn(self, h, lp, cfg, window, cache, b, layer: int = 0):
        """One attention sub-block over the paged pool; `cache` is one
        layer's page arrays — (k, v) fp, or (k, v, k_scale, v_scale)
        with kv_cache_dtype="int8", where new-token rows quantize IN-JIT at
        scatter time (ops/quant.quantize_kv_rows) and attention dequantizes
        behind the page gather. `window` is the layer's, static (None:
        full attention). With a window, in a model that keeps rings, `cache`
        is the layer's ring of pages per slot, not a pool entry, and the
        rows' write pages, page tables and block list are the ring's. `cfg`
        is the layer's operator's configuration (`decoder.kind_config`).
        With differential attention (`_diff_attn`) `layer` is the layer's
        index. Returns (post-residual h, written cache). h is (1, T, H)."""
        freq = self._freq_for(window)
        if window and self._ring_pages:
            page, tables, segments, write = (
                b["ring_page"], b["ring_pt_tok"], b["ring_segments"],
                "serve.ring_write")
        else:
            page, tables, segments, write = (
                b["page"], b["pt_tok"], b["segments"], "serve.pool_write")
        positions = jnp.maximum(b["pos"], 0)[None]  # (1, T); pads clamped
        x = apply_norm(h, lp["input_norm"], cfg)
        if cfg.differential_attention:
            q, k, v = project_qkv(x, lp, cfg, positions, freq)
            pool_k, pool_v = cache
            with jax.named_scope(write):
                # merged pages (kv_pages.gqa_rows): a token's heads side by side
                pool_k = pool_k.at[page, b["off"]].set(
                    k[0].reshape(k.shape[1], -1).astype(pool_k.dtype))
                pool_v = pool_v.at[page, b["off"]].set(
                    v[0].reshape(v.shape[1], -1).astype(pool_v.dtype))
            return self._diff_attn(h, q[0], lp, cfg, (pool_k, pool_v), tables,
                                   b["pos"], segments, window, layer), (
                pool_k, pool_v)
        if self.is_mla:
            n = cfg.num_heads
            dn, dr = cfg.mla_qk_nope_head_dim, cfg.mla_qk_rope_head_dim
            dv = cfg.mla_v_head_dim
            # one shared implementation of the absorbed projections
            # (inference/generate.py) — the paged part is just where the
            # two cached quantities land and how attention reads them back
            q_abs, q_rope, c_kv, k_rope, w_uv = mla_absorbed_inputs(
                x, lp, cfg, positions, freq
            )
            scales_kw = {}
            if self._kv_quant:
                pool_k, pool_v, s_c, s_kr = cache
                qc, c_rows = quantize_kv_rows(c_kv[0])
                qkr, kr_rows = quantize_kv_rows(k_rope[0])
                with jax.named_scope(write):
                    pool_k = pool_k.at[page, b["off"]].set(qc)
                    pool_v = pool_v.at[page, b["off"]].set(qkr)
                    s_c = s_c.at[page, b["off"]].set(c_rows)
                    s_kr = s_kr.at[page, b["off"]].set(kr_rows)
                scales_kw = dict(c_scales=s_c, kr_scales=s_kr)
            else:
                pool_k, pool_v = cache
                with jax.named_scope(write):
                    pool_k = pool_k.at[page, b["off"]].set(
                        c_kv[0].astype(pool_k.dtype)
                    )
                    pool_v = pool_v.at[page, b["off"]].set(
                        k_rope[0].astype(pool_v.dtype)
                    )
            scale = (
                cfg.attn_scale if cfg.attn_scale is not None
                else (dn + dr) ** -0.5
            )
            out_lat = ragged_paged_mla_attention(
                q_abs[0], q_rope[0], pool_k, pool_v,
                tables, b["pos"],
                scale=scale, window=window, mesh_ctx=self._mesh,
                segments=segments, **scales_kw,
            )
            attn = jnp.einsum("tnr,rnd->tnd", out_lat, w_uv)
            attn = attn.reshape(1, -1, n * dv)
            h = h + _mm(attn, lp["o_proj"]["kernel"], cfg.linear_precision)
            if self._kv_quant:
                return h, (pool_k, pool_v, s_c, s_kr)
            return h, (pool_k, pool_v)
        # GQA
        q, k, v = project_qkv(x, lp, cfg, positions, freq)
        if cache[0].shape[-1] != k.shape[-1]:
            # keys lie in the pages at a whole number of lane tiles
            # (kv_pages.key_width), zeros behind them
            k = jnp.pad(k, [(0, 0)] * 3 + [(0, cache[0].shape[-1] - k.shape[-1])])
        scales_kw = {}
        if self._kv_quant:
            pool_k, pool_v, s_k, s_v = cache
            qk, k_rows = quantize_kv_rows(k[0])
            qv, v_rows = quantize_kv_rows(v[0])
            with jax.named_scope(write):
                pool_k = pool_k.at[page, b["off"]].set(qk)
                pool_v = pool_v.at[page, b["off"]].set(qv)
                s_k = s_k.at[page, b["off"]].set(k_rows)
                s_v = s_v.at[page, b["off"]].set(v_rows)
            scales_kw = dict(k_scales=s_k, v_scales=s_v)
        else:
            pool_k, pool_v = cache
            with jax.named_scope(write):
                pool_k = pool_k.at[page, b["off"]].set(
                    k[0].astype(pool_k.dtype)
                )
                pool_v = pool_v.at[page, b["off"]].set(
                    v[0].astype(pool_v.dtype)
                )
        scale = (
            cfg.attn_scale if cfg.attn_scale is not None
            else cfg.resolved_head_dim ** -0.5
        )
        attn = ragged_paged_attention(
            q[0], pool_k, pool_v, tables, b["pos"],
            scale=scale, window=window,
            soft_cap=cfg.attn_soft_cap, sinks=lp.get("sinks"),
            mesh_ctx=self._mesh, segments=segments, **scales_kw,
        )
        T = attn.shape[0]
        attn = attn.reshape(1, T, cfg.num_heads * attn.shape[-1])
        attn_out = _dense(attn, lp["o_proj"])
        if cfg.use_post_norms:
            attn_out = rms_norm(
                attn_out, lp["post_attn_out_norm"]["scale"],
                cfg.rms_norm_eps, cfg.zero_centered_norm,
            )
        if self._kv_quant:
            return h + attn_out, (pool_k, pool_v, s_k, s_v)
        return h + attn_out, (pool_k, pool_v)

    def _diff_attn(self, h, q, lp, cfg, cache, tables, pos, segments, window,
                   layer):
        """h + differential attention's output: q (R, Hq, D) of the rows of h
        (1, R, H) against the pages of `cache`, through `tables` and `pos` of
        those rows and their `segments`; the pairs combined outside the
        kernel (`decoder.diff_combine`)."""
        o = ragged_paged_diff_attention(
            q, *cache, tables, pos,
            scale=cfg.attn_scale if cfg.attn_scale is not None
            else cfg.resolved_head_dim ** -0.5,
            window=window, mesh_ctx=self._mesh, segments=segments,
        )
        o = diff_combine(o, lp, cfg, layer)
        return h + _dense(o[None], lp["o_proj"], cfg.linear_precision)

    def _cross_attn(self, h, lp, cfg, cache, b, layer):
        """A cross-attention layer over the sample rows h (1, S, H): queries
        alone, against the shared layer's pages `cache`, each row a segment
        of its own (`b["cross_*"]`, once a step)."""
        x = apply_norm(h, lp["input_norm"], cfg)
        q = _dense(x, lp["q_proj"], cfg.linear_precision)
        q = q[0].reshape(q.shape[1], cfg.num_heads, cfg.resolved_head_dim)
        return self._diff_attn(h, q, lp, cfg, cache, b["cross_pt"],
                               b["cross_pos"], b["cross_segments"], None, layer)

    def _gmu(self, h, lp, memory):
        """A gated memory unit over the sample rows h (1, S, H): h +=
        (silu(x W1) * m) W2, `memory` (S, C) the memory layer's at the rows."""
        return gmu_block(h, lp, self.cfg, memory[None])

    def _ssm(self, h, lp, cache, b, with_memory: bool = False):
        """One state-space mixer (models/llm/mamba.py) over the step's ragged
        rows; `cache` is the layer's `(conv, ssm)` per-slot state. Each run
        of one slot's rows continues what the slot carried in, or starts
        from zeros at position 0 (ops/selective_scan.py). Returns
        (post-residual h, written state), and `with_memory` the mixer's output
        before its gate, (T, C) float32, too. h is (1, T, H)."""
        cfg = self.cfg
        conv_state, ssm_state = cache
        with jax.named_scope("serve.ssm.proj"):
            x = apply_norm(h[0], lp["input_norm"], cfg)
            u, z = mamba_inputs(x, lp, cfg)
        with jax.named_scope("serve.ssm.conv"):
            u, conv_state = ragged_conv(
                u, lp["conv"]["kernel"], lp["conv"]["bias"], conv_state,
                b["pos"], b["runs"],
            )
            u = jax.nn.silu(u).astype(h.dtype)
        with jax.named_scope("serve.ssm.proj"):
            delta, sel_b, sel_c, a = mamba_selection(u, lp, cfg)
        with jax.named_scope("serve.ssm.scan"):
            y, ssm_state = ragged_selective_scan(
                u, delta, a, sel_b, sel_c, ssm_state, b["runs"],
                mesh_ctx=self._mesh,
            )
        with jax.named_scope("serve.ssm.proj"):
            out = mamba_output(y, u, z, lp, cfg, with_memory)
        if with_memory:
            out, memory = out
            return h + out[None], (conv_state, ssm_state), memory
        return h + out[None], (conv_state, ssm_state)

    def _step_impl(self, params, pool, b, state=()):
        cfg = self.cfg
        # The layers are walked in a Python loop at trace time over
        # per-layer buffers: each layer's weights (split_layer_stacks) and
        # each layer's page arrays (kv_pages.init_pool) are arguments of the
        # program in their own right, the page arrays donated and aliased to
        # the outputs. So no instruction slices an operand out of a stack or
        # writes one back along a layer axis, as a lax.scan over stacked
        # operands made the compiler do for every layer of every step.
        #
        # The serve.* named scopes are metadata only: they name each op's
        # sublayer in a profiler trace and add no instruction. What lies
        # under serve.layers and under none of its sublayers is the
        # hidden state's sharding constraint: nothing off-mesh.
        #
        # the plan arrives as ONE int32 vector (`_plan_batch`): sliced back
        # into its fields here, the temperatures bit-cast to float32
        with jax.named_scope("serve.unpack"):
            b = unpack_device(self._plan_layout, b)
        # per-token page-table rows: pads index slot 0's table but their
        # position is -1, so they attend to nothing
        b["pt_tok"] = b["page_tables"][jnp.maximum(b["slot"], 0)]
        # the rows grouped into runs of one slot, once for every attention
        # call of the step: the Pallas kernels' unit of work (None, and
        # nothing traced, where the calls go to the XLA reference)
        b["segments"] = step_row_segments(
            b["slot"], b["pos"], b["pt_tok"],
            page_size=self.serve_cfg.page_size, tile=self._attn_row_tile,
            max_slots=self.serve_cfg.max_slots,
        )
        if self._num_ssm:
            # the rows grouped into runs of one slot at consecutive positions,
            # once for every state-space layer of the step, on every backend
            b["runs"] = step_runs(
                b["slot"], b["pos"], trash=self.serve_cfg.max_slots
            )
        if self._ring_pages:
            # the window layers' side of the same rows, once for all of
            # them: where each row's key and value land in its slot's ring
            # (pad rows in the trash slot's), the ring as a page table, and
            # the block list that starts at the first in-window page
            sc, R = self.serve_cfg, self._ring_pages
            ring_slot = jnp.where(b["slot"] >= 0, b["slot"], sc.max_slots)
            b["ring_page"] = ring_slot * R + (
                jnp.maximum(b["pos"], 0) // sc.page_size) % R
            b["ring_pt_tok"] = ring_page_tables(ring_slot, R, sc.pages_per_slot)
            b["ring_segments"] = step_row_segments(
                b["slot"], b["pos"], b["ring_pt_tok"],
                page_size=sc.page_size, tile=self._attn_row_tile,
                max_slots=sc.max_slots, window=self.cfg.sliding_window,
                max_pages=R,
            )
        if self._cross:
            # the cross-decoder's rows, once for all its layers: each slot's
            # sample row (none: a pad row) with its position and page table,
            # each a segment of its own over the shared layer's pages
            sc = self.serve_cfg
            sampled = b["sample_tok"] >= 0
            idx = jnp.clip(b["sample_tok"], 0, b["pos"].shape[0] - 1)
            b["cross_rows"] = idx
            b["cross_pos"] = jnp.where(sampled, b["pos"][idx], -1)
            b["cross_pt"] = b["pt_tok"][idx]
            b["cross_segments"] = step_row_segments(
                jnp.where(sampled, b["slot"][idx], -1), b["cross_pos"],
                b["cross_pt"], page_size=sc.page_size,
                tile=self._cross_row_tile, max_slots=sc.max_slots,
            )
        # copy-on-write splits first (≤ 1 per slot): a slot about to append
        # into a page some other table or the radix tree still reads gets a
        # private copy. Only the plan's splits are copied; a step with none
        # (no prefix cache, or no shared page to append into) copies
        # nothing. A model whose requests begin at position 0 alone never
        # shares a page (its prefix hits are cut, a hand-off refused): its
        # step has no such block
        if not self.begins_at_zero:
            with jax.named_scope("serve.cow"):
                pool = copy_split_pages(pool, b["cow_src"], b["cow_dst"])
        # under a mesh: pool pinned to its pages-global / heads-sharded
        # layout through the COW block and the layers; hidden replicated so
        # every tp reduction lives inside the layer stack (no-ops off-mesh)
        pool = self._constrain_pool(pool)
        with jax.named_scope("serve.embed"):
            h = _embed(params, cfg, b["tok"][None])  # (1, T, H)
        h = self._constrain_rep(h)

        # A looped decoder (cfg.num_passes = P > 1) walks the same per-layer
        # weight buffers P times, pass t with the cache entries t * L + l of
        # each stack (kv_pages.init_pool) and the final norm between two
        # passes; the last pass's norm is the head's. The exit gate is not
        # computed here: while every token runs every pass (the only case
        # built) it cannot change a logit.
        #
        # A layer's operator is attention over its pool entry, attention
        # with a window over its ring (entry `num_ssm + r` of `state`, r
        # counting the ring layers of every pass in order), or (a model whose
        # layers name their mixer) a state-space mixer over its entry of
        # `state`; the operator's weights then come from its kind's stack.
        new_pool = [[] for _ in self._stacks]
        new_state = list(state)
        share_runs = []  # the expert layers that hold a share: _moe_mlp
        r = self._num_ssm
        # a decoder-hybrid-decoder's memory (the memory layer's mixer output
        # before its gate) and the shared layer's written pages
        memory_layer, shared_layer, first_cross = self._cross or (None,) * 3
        memory = shared = None
        i = 0  # the layer's index in the model
        # the full layers' own scope only beside window layers: a model
        # without them lowers as it always did
        full_scope = functools.partial(
            jax.named_scope, "serve.attn.full"
        ) if self._ring_pages else contextlib.nullcontext
        for t in range(cfg.num_passes):
            with jax.named_scope("serve.layers"), \
                    jax.named_scope(f"serve.pass{t}"):
                for (pkey, mlp_fn, L), stack, wins, new_stack, ops, A in zip(
                    self._stacks, pool, self._stack_windows, new_pool,
                    self._stack_ops, self._stack_attn,
                ):
                    mlp_scope = (
                        "serve.mlp" if mlp_fn is _dense_mlp else "serve.moe"
                    )
                    caches = iter(stack[t * A:(t + 1) * A])
                    for lp, win, (kind, j) in zip(params[pkey], wins, ops):
                        if j is not None:
                            lp = {**lp, **params[OPERATOR_STACKS[kind]][j]}
                        kcfg = kind_config(cfg, kind)
                        if i == first_cross:
                            # the rest of the model runs over the sample rows
                            h = h[:, b["cross_rows"]]
                            memory = memory[b["cross_rows"]]
                        cross_scope = functools.partial(
                            jax.named_scope, "serve.cross"
                        ) if first_cross is not None and i >= first_cross \
                            else contextlib.nullcontext
                        if kind in ATTENTION_OPS and win and self._ring_pages:
                            with jax.named_scope("serve.attn"), \
                                    jax.named_scope("serve.attn.window"):
                                h, new_state[r] = self._attn(
                                    h, lp, kcfg, win, state[r], b, layer=i
                                )
                            r += 1
                        elif kind in ATTENTION_OPS:
                            with jax.named_scope("serve.attn"), full_scope():
                                h, cache = self._attn(
                                    h, lp, kcfg, win, next(caches), b, layer=i
                                )
                            new_stack.append(cache)
                            if i == shared_layer:
                                shared = cache
                        elif kind == "gmu":
                            with cross_scope(), jax.named_scope("serve.gmu"):
                                h = self._gmu(h, lp, memory)
                        elif kind == "cross_attention":
                            with cross_scope(), jax.named_scope("serve.attn"), \
                                    jax.named_scope("serve.attn.cross"):
                                h = self._cross_attn(h, lp, kcfg, shared, b, i)
                        elif i == memory_layer:
                            with jax.named_scope("serve.ssm"):
                                h, new_state[j], memory = self._ssm(
                                    h, lp, state[j], b, with_memory=True
                                )
                        else:
                            with jax.named_scope("serve.ssm"):
                                h, new_state[j] = self._ssm(
                                    h, lp, state[j], b
                                )
                        with cross_scope(), jax.named_scope(mlp_scope):
                            if mlp_fn is _dense_mlp:
                                h = mlp_fn(h, lp, cfg)
                            else:
                                h = mlp_fn(h, lp, cfg, b, share_runs)
                        h = self._constrain_rep(h)
                        i += 1
                if t + 1 < cfg.num_passes:
                    h = rms_norm(h, params["final_norm"]["scale"],
                                 cfg.rms_norm_eps, cfg.zero_centered_norm)
        new_pool = self._constrain_pool([tuple(st) for st in new_pool])

        with jax.named_scope("serve.head"):
            out = self._head(params, new_pool, h, b,
                             sampled=first_cross is not None)
        # tokens, log-probabilities (and acceptances) go back as ONE int32
        # vector, the float32 values bit-cast; hidden feedback stays its own.
        # A model whose expert layers hold a share adds two words
        with jax.named_scope("serve.pack"):
            words = _share_words(share_runs)
            n = len(self._out_layout) - len(words)
            out = (out[0], pack_device(out[1:1 + n] + words)) + out[1 + n:]
        # the state last, where there is one: a decoder of attention alone
        # lowers to the program it always was
        return out + (tuple(new_state),) if state else out

    def _head(self, params, new_pool, h, b, sampled: bool = False):
        """Final norm, unembed of the sample rows, sampling, log-softmax.
        `sampled`: h holds the sample rows already, (1, S, H)."""
        cfg = self.cfg
        h = apply_norm(h, params["final_norm"], cfg)
        if self._spec is not None:
            return self._spec_verify_tail(params, new_pool, h, b)
        # sample rows: each slot's last scheduled token (or a junk row when
        # sample_tok < 0 — the host ignores those slots)
        idx = jnp.clip(b["sample_tok"], 0, b["pos"].shape[0] - 1)
        h_s = h[0] if sampled else h[0, idx]       # (S, H)
        logits = unembed(params, cfg, h_s[None])[0]  # (S, V) fp32
        logits = self._constrain_rep(logits)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        next_pos = jnp.maximum(b["pos"], 0)[idx] + 1
        sampled = self._sample_rows(logits, b["temp"], b["seed"], next_pos)
        tokens = jnp.where(b["temp"] > 0.0, sampled, greedy)
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        lp_tok = jnp.take_along_axis(logprobs, tokens[:, None], axis=-1)[:, 0]
        return new_pool, tokens, lp_tok

    def _sample_rows(self, logits, temp, seed, next_pos):
        """Per-slot filtered categorical over one logits row each — the ONE
        sampling recipe (temperature clamp → static top-k/p filter → key =
        fold_in(key(seed), position-of-the-new-token): per-request
        deterministic, independent of batching, preemption-stable). Shared
        by the plain tail and the spec tail's greedy-acceptance branch;
        the spec-on == spec-off contract for sampled slots rests on this
        being a single implementation."""
        sc = self.serve_cfg
        filtered = filter_logits(
            logits / jnp.maximum(temp, 1e-6)[:, None], sc.top_k, sc.top_p
        )
        keys = jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.key(s), p)
        )(seed, next_pos)
        return jax.vmap(
            lambda k, l: jax.random.categorical(k, l)
        )(keys, filtered).astype(jnp.int32)

    def _spec_verify_tail(self, params, new_pool, h, b):
        """Draft-then-verify sampling tail (speculation enabled): score
        every slot's verify block — the row feeding its pending token plus
        the rows feeding its K drafts — and keep the longest valid prefix
        via the shared acceptance rule (speculative/acceptance.py). A slot
        with spec_len == 0 (prefill, or a decode slot whose block shrank
        away) reduces exactly to the plain one-row tail: its verify rows
        all alias the sample row and acceptance is always 0, so tokens[:1]
        is the plain greedy/sampled token."""
        cfg, sc = self.cfg, self.serve_cfg
        K = self._spec.draft_len
        T = h.shape[1]
        vr = jnp.clip(b["verify_rows"], 0, T - 1)              # (S, K+1)
        h_sel = h[0, vr]                                       # (S, K+1, H)
        S = h_sel.shape[0]
        logits = unembed(params, cfg, h_sel.reshape(1, S * (K + 1), -1))
        logits = logits[0].reshape(S, K + 1, -1)               # fp32
        logits = self._constrain_rep(logits)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        draft = b["tok"][vr[:, 1:]]                            # (S, K)
        valid = jnp.arange(K)[None, :] < b["spec_len"][:, None]
        a_greedy = greedy_accept_length(draft, greedy[:, :K], valid)

        base = jnp.maximum(b["pos"], 0)[vr[:, 0]] + 1          # (S,)
        use_sample = b["temp"] > 0.0
        if self._spec.acceptance == "sampled":
            # distribution-preserving one-hot verification over the SAME
            # filtered per-slot distribution the plain tail samples from,
            # with key[j] = fold_in(request seed, absolute position) —
            # batching-invariant and preemption-stable, and identical to
            # the plain tail when the block is empty
            temp = jnp.maximum(b["temp"], 1e-6)[:, None, None]
            filtered = filter_logits(logits / temp, sc.top_k, sc.top_p)
            keys = jax.vmap(
                lambda s, p0: jax.vmap(
                    lambda j: jax.random.fold_in(jax.random.key(s), p0 + j)
                )(jnp.arange(K + 1))
            )(b["seed"], base)
            a_samp, tok_samp = jax.vmap(onehot_speculative_verify)(
                draft, filtered, keys, valid
            )
            accept = jnp.where(use_sample, a_samp, a_greedy).astype(jnp.int32)
            # greedy committed tokens ARE the verifier's own argmax rows
            # (an accepted draft equals the argmax of the row before it)
            tokens = jnp.where(use_sample[:, None], tok_samp, greedy)
        else:
            # acceptance == "greedy" (static): only temperature<=0 slots
            # draft, so sampled slots need exactly the plain one-row tail
            # (_sample_rows, the shared implementation) — the block
            # machinery is argmax-only, keeping the default program lean
            sampled0 = self._sample_rows(
                logits[:, 0], b["temp"], b["seed"], base
            )
            accept = jnp.where(use_sample, 0, a_greedy).astype(jnp.int32)
            tokens = greedy.at[:, 0].set(
                jnp.where(use_sample, sampled0, greedy[:, 0])
            )
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        lp_tok = jnp.take_along_axis(logprobs, tokens[..., None], -1)[..., 0]
        out = [new_pool, tokens, lp_tok, accept]
        # EAGLE/DFlash hidden-state feedback is gathered PER SLOT from the
        # sharded step's outputs: the replication constraint makes the
        # feedback fully addressable on the host however the step is
        # partitioned (the ngram source is sharding-oblivious — it never
        # sees a device array, only known tokens)
        if self._needs_hidden == "frontier":
            # the hidden that produced the bonus token (row `accept`)
            out.append(self._constrain_rep(jnp.take_along_axis(
                h_sel, jnp.clip(accept, 0, K)[:, None, None], axis=1
            )[:, 0]))
        elif self._needs_hidden == "rows":
            out.append(self._constrain_rep(h[0]))
        return tuple(out)

    # -- host API -----------------------------------------------------------
    def step_cache_size(self) -> int:
        """Compiled-signature count of the step jit (must stay 1 for a
        serving run — the fixed-shape contract)."""
        return self._step._cache_size()

    @functools.cached_property
    def _plan_layout(self) -> tuple:
        """Where each StepPlan array lies in the one vector the step takes:
        the engine's fixed geometry, read off `empty_plan`."""
        plan = self.empty_plan()
        return packed_layout(
            (f.name, a.shape, a.dtype) for f in dataclasses.fields(plan)
            if isinstance(a := getattr(plan, f.name), np.ndarray)
        )

    @functools.cached_property
    def _out_layout(self) -> tuple:
        """Where the step's tokens, log-probabilities and (speculation)
        acceptances lie in the one vector it returns; in a model whose
        expert layers hold a share of their experts two words follow
        (`_share_words`), which `run_step` counts and returns no further."""
        S = self.serve_cfg.max_slots
        if self._spec is None:
            fields = [("tokens", (S,), np.int32), ("logprobs", (S,), np.float32)]
        else:
            K1 = self._spec.draft_len + 1
            fields = [
                ("tokens", (S, K1), np.int32), ("logprobs", (S, K1), np.float32),
                ("accept", (S,), np.int32),
            ]
        if self._holds_expert_share:
            fields += [("share_whole", (), np.int32),
                       ("share_live_max", (), np.int32)]
        return packed_layout(fields)

    @functools.cached_property
    def _holds_expert_share(self) -> bool:
        return self.is_moe and not self.cfg.moe.holds_all_experts

    def _plan_batch(self, plan: StepPlan) -> jax.Array:
        """StepPlan → the jitted step's one int32 plan vector (the ONE
        sanctioned host→device upload per step, a single transfer whatever
        the fields; replicated under a mesh)."""
        buf = pack_host(self._plan_layout, vars(plan))
        if self._mesh is None:
            return jnp.asarray(buf)
        # replicated onto the engine's mesh (the host scheduler is
        # mesh-oblivious: page IDs are global)
        return jax.device_put(buf, self._mesh.replicated())

    def run_step(self, plan: StepPlan):
        """Upload one StepPlan, run the jitted step, return numpy outputs:
        (tokens (S,), logprobs (S,)) plainly, or — with speculation — the
        committed-candidate block (tokens (S, K+1), logprobs (S, K+1),
        accept (S,)[, hidden feedback for the draft source]).

        Lockstep observability: the step/plan-token/plan-sample counters
        increment HERE, so a follower replaying broadcast plans
        (plan_wire.PlanFollower) mirrors the lead's counters exactly —
        the multi-host CI dryrun asserts that parity."""
        # chaos hooks (serving/resilience.py): probed BEFORE the lockstep
        # counters and the pool rebind, so an injected replica death leaves
        # this engine's counters and device state exactly as they were —
        # lead/follower parity comparisons stay valid across a recovery.
        # The track-qualified point lets a chaos trace kill ONE replica of
        # a router deterministically (replica1 / prefill0 / decode2 / ...).
        fault_hit("serve_step_run", self.steps_run)
        fault_hit(f"serve_step_run.{self.track}", self.steps_run)
        reg = self.obs.registry
        reg.counter("serve_steps_total").inc()
        reg.counter("serve_plan_tokens_total").inc(plan.n_tokens)
        reg.counter("serve_plan_samples_total").inc(plan.n_samples)
        if not self.begins_at_zero:
            # the copy-on-write block's work: "copy" where the plan splits a
            # page (those pages alone are copied), "skip" where it splits none
            split = bool(np.any(plan.cow_src != plan.cow_dst))
            reg.counter(
                "serve_cow_steps_total", path="copy" if split else "skip",
            ).inc()
        # step.run's three children split what the host does around the
        # device's step: the plan's upload, the enqueue of the jitted
        # step (a compile lands here), and the read-back, itself in two:
        # the wait until the host learns the device is done, and the
        # copy to numpy. All six carry this step's number.
        span = functools.partial(
            self.obs.tracer.span, track=self.track, step=self.steps_run
        )
        with span("step.run", rows=plan.n_tokens, samples=plan.n_samples):
            with span("step.upload"):
                batch = self._plan_batch(plan)
                reg.counter("serve_step_transfers_total", direction="h2d").inc()
            # the StepPlan upload above is the ONE sanctioned host→device
            # copy per step; with guard_transfers the step invocation runs
            # under transfer_guard("disallow") so any other transfer raises
            with span("step.dispatch"):
                if self.serve_cfg.guard_transfers:
                    with jax.transfer_guard("disallow"):
                        out = self._step(*self._step_args(batch))
                else:
                    out = self._step(*self._step_args(batch))
                self.pool = out[0]
                if self.state:
                    *out, self.state = out
                # the packed outputs (and hidden feedback, where the step
                # returns it) start their copies now: each then waits on
                # the device, not on the host's turn to ask
                fetched = out[1:]
                for x in fetched:
                    x.copy_to_host_async()
                reg.counter(
                    "serve_step_transfers_total", direction="d2h",
                ).inc(len(fetched))
            self.steps_run += 1
            with span("step.readback"):
                with span("step.wait"):
                    jax.block_until_ready(fetched)
                with span("step.fetch"):
                    packed, *hidden = (np.asarray(x) for x in fetched)
                    out = unpack_host(self._out_layout, packed)
            if self._holds_expert_share:
                *out, whole, live_max = out
                self._count_share_runs(int(whole), int(live_max))
            return tuple(out) + tuple(hidden)

    def _count_share_runs(self, whole: int, live_max: int) -> None:
        """A step's expert layer-runs by the path they took, and the most
        live pairs one of them had (`experts_forward_dropless`)."""
        reg = self.obs.registry
        reg.counter("serve_moe_share_runs_total", path="whole").inc(whole)
        reg.counter("serve_moe_share_runs_total", path="compact").inc(
            self.cfg.num_moe_layers - whole)
        reg.gauge("serve_moe_share_live_max").set(live_max)

    def _step_args(self, batch: jax.Array) -> tuple:
        """The jitted step's arguments: the per-slot state last, where the
        model holds one."""
        args = (self.params, self.pool, batch)
        return args + (self.state,) if self.state else args

    def empty_plan(self) -> StepPlan:
        """A zero-work StepPlan with the engine's fixed shapes — shape
        donor for AOT lowering (`lower_step`) and cost analysis."""
        sc = self.serve_cfg
        T, S, P = sc.token_budget, sc.max_slots, sc.pages_per_slot
        plan = StepPlan(
            tok=np.zeros(T, np.int32),
            slot=np.full(T, -1, np.int32),
            pos=np.full(T, -1, np.int32),
            page=np.zeros(T, np.int32),
            off=np.zeros(T, np.int32),
            page_tables=np.zeros((S, P), np.int32),
            sample_tok=np.full(S, -1, np.int32),
            temp=np.zeros(S, np.float32),
            seed=np.zeros(S, np.int32),
            cow_src=np.zeros(S, np.int32),
            cow_dst=np.zeros(S, np.int32),
        )
        if self._spec is not None:
            K = self._spec.draft_len
            plan.verify_rows = np.zeros((S, K + 1), np.int32)
            plan.spec_len = np.zeros(S, np.int32)
        return plan

    def lower_step(self, plan: StepPlan | None = None):
        """AOT-lower the jitted step for `plan`'s shapes (default: the
        engine's fixed geometry). Lowering/compiling through the AOT path
        does NOT populate the jit call cache, so `step_cache_size()` —
        the compile-once contract — is unaffected."""
        batch = self._plan_batch(plan if plan is not None else self.empty_plan())
        return self._step.lower(*self._step_args(batch))

    def run_and_absorb(
        self, sched: Scheduler, plan: StepPlan, step_idx: int,
    ) -> tuple[int, float]:
        """One engine step + scheduler absorption (speculative outputs
        unpacked and fed back to the draft source). Returns (tokens
        committed, device-step seconds) — the shared inner loop of
        `serve_batch` and the ReplicaRouter's per-replica drive. The
        timing covers run_step ONLY (upload + jitted step + readback),
        not the host-side scheduler bookkeeping, so latency counters stay
        comparable with the pre-router serve loop's."""
        step = self.steps_run  # what run_step stamps on this turn's spans
        t0 = time.perf_counter()
        out = self.run_step(plan)
        dt = time.perf_counter() - t0
        self.obs.observe_step(self.steps_run, dt * 1e3)
        with self.obs.tracer.span(
            "step.absorb", track=self.track, step=step
        ):
            n_new = self.absorb_outputs(sched, plan, out, step_idx)
        return n_new, dt

    def absorb_outputs(
        self, sched: Scheduler, plan: StepPlan, out, step_idx: int,
    ) -> int:
        """Feed one run_step output tuple back into the scheduler
        (speculative outputs unpacked, draft source observed). Split from
        `run_and_absorb` so the async frontend can run the blocking jitted
        step in a worker thread while EVERY scheduler mutation stays on
        the event-loop thread — the scheduler is not thread-safe and never
        needs to be."""
        if self._spec is not None:
            tokens, _lps, accept, *hid = out
            fh = hid[0] if self._needs_hidden == "frontier" else None
            rh = hid[0] if self._needs_hidden == "rows" else None
            return sched.update(
                plan, tokens, step_idx, accept=accept,
                frontier_hidden=fh, row_hidden=rh,
            )
        tokens, _lps = out
        return sched.update(plan, tokens, step_idx)

    def plan_turn(self, sched: Scheduler, step_idx: int) -> StepPlan | None:
        """The first half of a serve turn, the one place any loop plans:
        `sched.schedule(step_idx)` under the `step.plan` span, on this
        engine's track and under the number `run_step` will stamp on the
        turn's `step.run`. The span says what the turn did to the pool and
        what grid the step's attention walks (`Scheduler.turn_stats`; the
        grid's two also on /metrics; `state_runs`, the runs of one slot's
        rows that a state-space layer's scan continues or starts, where the
        model holds state) and, where a step was planned, its
        `rows` / `samples`. None when nothing could be packed: the CALLER
        decides whether to fast-forward, sleep or shed. Not held across
        an `await`."""
        with self.obs.tracer.span(
            "step.plan", track=self.track, step=self.steps_run
        ) as span:
            preempted = sched.n_preemptions
            plan = sched.schedule(step_idx)
            stats = sched.turn_stats(preempted, plan)
            if plan is not None:
                stats.update(rows=plan.n_tokens, samples=plan.n_samples)
                if self._cross:
                    stats.update(self._cross_stats(plan))
            span.set_metadata(**stats)
            reg = self.obs.registry
            reg.gauge("serve_attn_segments").set(stats["attn_segments"])
            reg.gauge("serve_attn_live_blocks").set(stats["attn_live_blocks"])
        return plan

    def _cross_stats(self, plan: StepPlan) -> dict:
        """A decoder-hybrid-decoder's turn: `cross_rows`, the sample rows its
        cross-decoder runs over, and `cross_blocks`, the (row, page) blocks
        one cross-attention call over the shared layer's pages walks."""
        rows = plan.sample_tok[plan.sample_tok >= 0]
        pages = plan.pos[rows] // self.serve_cfg.page_size + 1
        return {"cross_rows": int(rows.size), "cross_blocks": int(pages.sum())}

    def run_one_step(
        self, sched: Scheduler, step_idx: int,
    ) -> tuple[StepPlan | None, int, float]:
        """ONE reentrant serve step: plan → run → absorb. Returns (plan,
        tokens committed, device-step seconds), plan=None when `plan_turn`
        packed nothing. The inner loop of the offline `serve_batch` below."""
        plan = self.plan_turn(sched, step_idx)
        if plan is None:
            return None, 0, 0.0
        n_new, dt = self.run_and_absorb(sched, plan, step_idx)
        return plan, n_new, dt

    def _mirror_stats(self, stats: dict, sched: Scheduler) -> None:
        """Land one serve_batch call's outcome counters on the central
        registry (per-call deltas — the registry keeps lifetime totals)."""
        reg = self.obs.registry
        for name, key in (
            ("serve_new_tokens_total", "new_tokens"),
            ("serve_requests_total", "requests"),
            ("serve_preemptions_total", "preemptions"),
            ("serve_timed_out_total", "timed_out"),
            ("serve_prefix_hits_total", "prefix_hits"),
            ("serve_prefix_hits_cut_total", "prefix_hits_cut"),
            ("serve_prefill_skipped_tokens_total", "prefill_skipped_tokens"),
            ("serve_cow_copies_total", "cow_copies"),
            ("serve_spec_drafted_total", "drafted_tokens"),
            ("serve_spec_accepted_total", "accepted_tokens"),
            ("serve_spec_rolled_back_total", "rolled_back_tokens"),
            ("serve_spec_steps_total", "spec_steps"),
        ):
            if key in stats:
                reg.counter(name).inc(stats[key])
        reg.counter("serve_cancelled_total").inc(sched.n_cancelled)
        reg.gauge("serve_compiled_signatures").set(stats["compiled_signatures"])
        reg.gauge("serve_free_pages").set(sched.alloc.num_free)

    def make_scheduler(self, *, arrival_gating: bool = True) -> Scheduler:
        sc = self.serve_cfg
        if self.alloc is not None:
            # a prior serve_batch cut short (max_steps budget) may have
            # left slot tables behind in the engine-lifetime allocator —
            # release them so only the radix tree's own references carry
            # into the fresh scheduler
            for slot in list(self.alloc._tables):
                self.alloc.free_slot(slot)
        return Scheduler(
            num_pages=sc.num_pages, page_size=sc.page_size,
            max_slots=sc.max_slots, pages_per_slot=sc.pages_per_slot,
            token_budget=sc.token_budget, prefill_chunk=sc.prefill_chunk,
            prefix_cache=sc.prefix_cache,
            admission_policy=sc.admission_policy,
            spec=self._spec, draft_source=self._draft_source,
            alloc=self.alloc, prefix=self.prefix,
            arrival_gating=arrival_gating,
            tracer=self.obs.tracer, track=self.track,
            attn_row_tile=self._attn_row_tile,
            carries_state=self.begins_at_zero,
            attn_window=self.cfg.sliding_window if self._ring_pages else None,
        )

    def reset_prefix_cache(self) -> int:
        """Explicitly drop the engine-lifetime radix tree: every cached
        node releases its page pin (pages held by nobody else return to
        the free list). Returns nodes evicted; no-op without the cache."""
        return self.prefix.reset() if self.prefix is not None else 0

    def defrag(self, scheduler: Scheduler) -> bool:
        """Compact live pages to a dense pool prefix (kv_pages.defrag_plan);
        returns whether a compaction ran."""
        if not jax.tree.leaves(self.pool):
            return False  # every attention layer keeps a ring: no page array
        plan = scheduler.alloc.defrag_plan()
        if plan is None:
            return False
        src, _n_live = plan
        self.pool = apply_defrag(self.pool, src)
        return True

    def serve_batch(
        self,
        requests: list[Request],
        *,
        metric_logger=None,
        max_steps: int | None = None,
        log_every: int = 0,
    ) -> dict:
        """Offline continuous-batching run: drive steps until every request
        finished. Returns {"outputs": [generated ids per request, submission
        order], "requests": finished Request objects, "stats": counters}.

        On any abnormal exit the observability flight recorder dumps its
        ring of recent trace events (reason "stall" for the pool-deadlock
        RuntimeError below, "crash" for everything else — including
        injected FaultCrash, which is a BaseException) before re-raising.
        """
        try:
            return self._serve_batch(
                requests, metric_logger=metric_logger,
                max_steps=max_steps, log_every=log_every,
            )
        except RuntimeError as e:
            self.obs.flight_dump(
                "stall" if str(e).startswith("serving stalled") else "crash"
            )
            raise
        except BaseException:
            self.obs.flight_dump("crash")
            raise

    def _serve_batch(
        self,
        requests: list[Request],
        *,
        metric_logger=None,
        max_steps: int | None = None,
        log_every: int = 0,
    ) -> dict:
        sched = self.make_scheduler()
        for r in requests:
            sched.submit(r)
        budget = max_steps if max_steps is not None else 10_000_000
        t_start = time.perf_counter()
        decode_s = 0.0
        n_sampled = 0
        n_tokens_fed = 0
        n_steps = 0  # this call only (self.steps_run is engine-lifetime)
        itl_ms: list = []     # per-step ms per committed token
        ttft_watch: list = []  # arrived requests awaiting their first token
        step_idx = 0
        while sched.has_work and step_idx < budget:
            # chaos probe (resilience/faults.py "serve_step"): disarmed it
            # is two dict lookups; an injected crash exercises the flight
            # recorder's crash dump in serve_batch
            fault_hit("serve_step", step_idx)
            _stamp_arrivals(sched.waiting, step_idx, ttft_watch)
            plan, n_new, dt = self.run_one_step(sched, step_idx)
            if plan is None:
                if not sched.has_work:
                    # deadline expiry inside schedule() drained the last
                    # request(s) — nothing left to run
                    break
                arrivals = [
                    r.arrival for r in sched.waiting if r.arrival > step_idx
                ]
                nd = sched.next_deadline
                if nd is not None and nd > step_idx:
                    # a pending deadline will evict the blocker and free its
                    # pages — jump ahead (offline loop; an online server
                    # would keep serving other traffic), but never PAST a
                    # servable arrival: skipping it would wrongly expire a
                    # request that was never given its window to run
                    step_idx = min([nd] + arrivals)
                    continue
                if not arrivals:
                    # no step could be packed and no future arrival can
                    # change that: whether the blocker is an inadmissible
                    # queue head or a RUNNING request that filled the pool
                    # with no preemptible victim, the offline loop can never
                    # make progress — fail loudly instead of spinning
                    blocked = (
                        sched.waiting[0] if sched.waiting
                        else next(iter(sched.running.values()), None)
                    )
                    raise RuntimeError(
                        "serving stalled: request "
                        f"rid={getattr(blocked, 'rid', '?')} needs more pages "
                        f"than the pool can ever free ({sched.alloc.num_free} "
                        f"free of {sched.alloc.num_pages}, "
                        f"{len(sched.running)} running, "
                        f"{len(sched.waiting)} waiting)"
                    )
                # nothing runnable yet (future arrivals): the offline loop
                # just advances; an online server would sleep
                step_idx += 1
                continue
            n_steps += 1
            n_tokens_fed += plan.n_tokens
            if plan.n_samples:
                decode_s += dt
                n_sampled += n_new
                if n_new:
                    itl_ms.append(dt * 1e3 / n_new)
            if ttft_watch:
                ttft_watch = _resolve_ttft(ttft_watch)
            if metric_logger is not None and log_every and (
                self.steps_run % log_every == 0
            ):
                rec = {
                    "step": self.steps_run,
                    "serving_step_ms": round(dt * 1e3, 3),
                    "tokens_fed": plan.n_tokens,
                    "tokens_sampled": n_new,
                    "running": len(sched.running),
                    "waiting": len(sched.waiting),
                    "free_pages": sched.alloc.num_free,
                }
                if self._spec is not None:
                    rec.update(
                        drafted_tokens=sched.n_drafted,
                        accepted_tokens=sched.n_accepted,
                        rolled_back_tokens=sched.n_drafted - sched.n_accepted,
                    )
                metric_logger.log(rec)
            step_idx += 1
        elapsed = time.perf_counter() - t_start
        assert not sched.has_work or max_steps is not None, "serve stalled"
        by_rid = sorted(sched.finished, key=lambda r: r.rid)
        # TTFT per request (requests that never committed a token — timed
        # out mid-prefill — carry no sample) + per-step inter-token latency
        ttft_p50, ttft_p95 = _percentiles_ms(
            [r.ttft_s * 1e3 for r in by_rid if r.ttft_s >= 0]
        )
        itl_p50, itl_p95 = _percentiles_ms(itl_ms)
        stats = {
            "steps": n_steps,
            "requests": len(by_rid),
            "new_tokens": n_sampled,
            "tokens_fed": n_tokens_fed,
            "elapsed_s": round(elapsed, 4),
            "decode_tokens_per_sec": round(n_sampled / max(decode_s, 1e-9), 2),
            "ms_per_token": round(1e3 * decode_s / max(n_sampled, 1), 4),
            "ttft_p50_ms": ttft_p50,
            "ttft_p95_ms": ttft_p95,
            "itl_p50_ms": itl_p50,
            "itl_p95_ms": itl_p95,
            "preemptions": sched.n_preemptions,
            "timed_out": sched.n_timed_out,
            "compiled_signatures": self.step_cache_size(),
        }
        if sched.prefix is not None:
            stats.update({
                "prefix_hits": sched.n_prefix_hits,
                "prefix_hits_cut": sched.n_prefix_hits_cut,
                "prefill_skipped_tokens": sched.prefill_skipped,
                "cow_copies": sched.n_cow,
                "prefix_cached_pages": sched.prefix.cached_pages,
                "prefix_evicted_pages": sched.prefix.n_evicted,
            })
        if self._spec is not None:
            stats.update({
                "drafted_tokens": sched.n_drafted,
                "accepted_tokens": sched.n_accepted,
                "rolled_back_tokens": sched.n_drafted - sched.n_accepted,
                "spec_steps": sched.n_spec_steps,
                "acceptance_rate": round(
                    sched.n_accepted / max(sched.n_drafted, 1), 4
                ),
                # committed tokens per drafted verify step (accepted + the
                # bonus) — the "tokens per jitted step" headline; > 1 means
                # speculation is beating one-token-per-step decode
                "mean_accepted_len": round(
                    (sched.n_accepted + sched.n_spec_steps)
                    / max(sched.n_spec_steps, 1), 4
                ),
            })
        self._mirror_stats(stats, sched)
        if metric_logger is not None:
            metric_logger.log({"step": self.steps_run, **{
                f"serve_{k}": v for k, v in stats.items()
            }})
        return {
            "outputs": [list(r.generated) for r in by_rid],
            "requests": by_rid,
            "stats": stats,
        }
