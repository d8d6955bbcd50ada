"""The five jitted entry points whose compiled structure is baselined.

One builder per headline program — the same tiny-shape, virtual-CPU-mesh
setups the old hand-rolled guards in tests/unit/test_hlo_guards.py used
(``jit(...).lower().compile()`` on a CPU mesh emits the same logical
collectives GSPMD/shard_map would emit for TPU):

- ``fsdp_grad``          — dp_shard=8 dense decoder grad
- ``ring_cp_forward``    — cp=2 ring-attention forward
- ``ep_moe_forward``     — ep=4 dropless-MoE forward
- ``paged_serve_step``   — the serving engine's single-chip jitted step
- ``spec_serve_step``    — the same step with speculative draft-then-verify
- ``sharded_serve_step`` — the tp=2 mesh-sharded serving step
- ``prefill_step``       — the prefill-class replica's step (disaggregated
                           serving: wider token budget, no speculation)
- ``kv_transfer``        — the fused page-copy program of the prefill→
                           decode KV handoff
- ``quant_serve_step``   — the int8-KV + int8-linears serving step
- ``quant_kv_transfer``  — the page-copy program over a quantized pool
                           (int8 payload + scale planes ship natively)
- ``pp_ep_1f1b_grad``    — the flagship PP×EP explicit 1F1B grad

Each builder returns ``(compiled, mesh_axes)``; callers feed both to
:func:`automodel_tpu.analysis.hlo.analyze_compiled`. Requires an 8-device
(virtual CPU) platform — ``force_cpu_devices(8)`` before any backend
touch, exactly like tests/conftest.py.

Every future jitted entry point (quantized serve step, multimodal serve
step, multi-host frontend step) earns its structural guard by adding a
builder here and running ``--update-baselines`` once.
"""

from __future__ import annotations

import dataclasses


def _configs():
    import jax.numpy as jnp

    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.models.moe_lm.decoder import MoETransformerConfig
    from automodel_tpu.moe import MoEConfig

    dense = TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
        num_heads=4, num_kv_heads=2, dtype=jnp.float32, remat_policy="none",
        pipeline_microbatches=2,
    )
    moe = MoETransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
        num_heads=4, num_kv_heads=2, first_k_dense=0,
        moe=MoEConfig(
            n_routed_experts=4, n_shared_experts=1, experts_per_token=2,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            aux_loss_coeff=0.01, dispatcher="dropless",
        ),
        dtype=jnp.float32, remat_policy="none", pipeline_microbatches=2,
    )
    return dense, moe


def _sharded(cfg, mod, ctx):
    import jax

    from automodel_tpu.parallel import logical_to_shardings

    params = mod.init(cfg, jax.random.key(0))
    sh = logical_to_shardings(
        mod.param_specs(cfg), ctx,
        shapes=jax.tree.map(lambda p: p.shape, params),
    )
    return jax.device_put(params, sh)


def _ids(ctx, B=8, S=16, seq_axis=None):
    import jax
    import jax.numpy as jnp

    return jax.device_put(
        jnp.zeros((B, S), jnp.int32), ctx.sharding("batch", seq_axis)
    )


def fsdp_grad():
    """dp_shard=8 dense decoder grad: per-layer-scan param all-gathers +
    grad all-reduces; pure FSDP must stay permute-free (and free of any
    all-to-all beyond the partitioner's own pinned reshard)."""
    import jax

    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.loss import fused_linear_cross_entropy
    from automodel_tpu.models.llm import decoder

    dense, _ = _configs()
    ctx = MeshConfig(dp_shard=8).build()
    p = _sharded(dense, decoder, ctx)
    ids, lab = _ids(ctx), _ids(ctx)

    def loss(p, i, l):
        h = decoder.forward(p, dense, i, mesh_ctx=ctx, return_hidden=True)
        ce, _ = fused_linear_cross_entropy(
            h, p["lm_head"]["kernel"], l, chunk_size=64
        )
        return ce

    compiled = jax.jit(jax.grad(loss)).lower(p, ids, lab).compile()
    return compiled, dict(ctx.sizes)


def ring_cp_forward():
    """cp=2 ring attention forward: the KV ring must stay collective-
    permutes (one hop per cp peer per scanned attention), never an A2A."""
    import jax

    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.models.llm import decoder

    dense, _ = _configs()
    ctx = MeshConfig(cp=2, dp_shard=4).build()
    p = _sharded(dense, decoder, ctx)
    ids = _ids(ctx, B=4, seq_axis="cp")
    compiled = (
        jax.jit(lambda p, i: decoder.forward(p, dense, i, mesh_ctx=ctx))
        .lower(p, ids).compile()
    )
    return compiled, dict(ctx.sizes)


def ep_moe_forward():
    """ep=4 dropless MoE forward: the manual EP dispatch is a bounded
    number of all-to-alls; a re-gather of expert weights would spike
    all-gather."""
    import jax

    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    _, moe = _configs()
    ctx = MeshConfig(ep=4, dp_shard=2).build()
    p = _sharded(moe, moe_decoder, ctx)
    ids = _ids(ctx)
    compiled = (
        jax.jit(lambda p, i: moe_decoder.forward(p, moe, i, mesh_ctx=ctx))
        .lower(p, ids).compile()
    )
    return compiled, dict(ctx.sizes)


def paged_serve_step():
    """The serving engine's jitted step: paged-pool reads stay gathers,
    pool writes are in-place scatters into per-layer page arrays (no
    dynamic-slice / dynamic-update-slice along a layer axis: the step walks
    its layers over per-layer buffers), zero collectives on a
    single-process engine, and the pool donation must survive (the
    aliasing table, one entry per layer and page array, is part of the
    baseline). The prefix-hit path rides the
    SAME program — COW is the bounded copy block pinned here."""
    import jax

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.serving.engine import ServingConfig, ServingEngine

    dense, _ = _configs()
    cfg = dataclasses.replace(dense, pipeline_microbatches=1)
    params = decoder.init(cfg, jax.random.key(0))
    eng = ServingEngine(params, cfg, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=8,
    ))
    compiled = eng.lower_step().compile()
    return compiled, None


def spec_serve_step():
    """The serving step with speculative decoding enabled: the verify
    block adds row gathers + the (S, K+1)-row unembed/acceptance tail on
    top of the paged_serve_step program. Must stay collective-free with
    the pool donation intact, and the paged k/v page gathers must survive
    — a lowering that drops the verify-row gather would silently verify
    nothing."""
    import jax

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.serving.engine import ServingConfig, ServingEngine
    from automodel_tpu.speculative.serve_draft import SpeculativeConfig

    dense, _ = _configs()
    cfg = dataclasses.replace(dense, pipeline_microbatches=1)
    params = decoder.init(cfg, jax.random.key(0))
    K = 3
    eng = ServingEngine(params, cfg, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=8,
        speculative=SpeculativeConfig(enabled=True, draft_len=K),
    ))
    compiled = eng.lower_step().compile()
    return compiled, None


def sharded_serve_step():
    """The TP-sharded serving step (tp=2 mesh slice): the paged pool
    partitions KV heads over tp (pages stay global), attention and the
    page gathers are rank-local, and the only collectives are the
    per-layer partial-sum reductions of the row-parallel projections plus
    the logits gather feeding the replicated sampling tail — the sampling
    tail itself (filters, fold_in keys, categorical) must stay
    collective-free, and the pool donation must survive sharding. The
    per-layer all-gather/reduce-scatter budget is the baseline's pinned
    collective table (two-sided ratchet)."""
    import jax

    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.models.llm import decoder
    from automodel_tpu.serving.engine import ServingConfig, ServingEngine

    dense, _ = _configs()
    cfg = dataclasses.replace(dense, pipeline_microbatches=1)
    ctx = MeshConfig(tp=2, dp_shard=1).build(jax.devices()[:2])
    params = decoder.init(cfg, jax.random.key(0))
    eng = ServingEngine(params, cfg, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=8,
    ), mesh_ctx=ctx)
    compiled = eng.lower_step().compile()
    return compiled, dict(ctx.sizes)


def prefill_step():
    """The prefill-class replica's jitted step (disaggregated serving):
    the SAME step program as paged_serve_step at the prefill-class
    geometry — a wider token budget (prefill replicas never carry
    latency-critical decode rows, so they amortize step overhead over
    wide chunks) and no speculative block (nothing to speculate on while
    feeding a prompt). Must stay collective-free with the pool donation
    intact and the paged k/v page gathers alive, exactly like the decode
    class — disaggregation changes WHERE phases run, never what the step
    compiles to."""
    import jax

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.serving.engine import ServingConfig, ServingEngine

    dense, _ = _configs()
    cfg = dataclasses.replace(dense, pipeline_microbatches=1)
    params = decoder.init(cfg, jax.random.key(0))
    eng = ServingEngine(params, cfg, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=16,
    ))
    compiled = eng.lower_step().compile()
    return compiled, None


def kv_transfer():
    """The fused same-device page-copy program of the prefill→decode
    handoff (serving/kv_transfer.py `apply_transfer`): one gather along
    the pages axis per pool array and the matching in-place scatter into
    the DONATED destination pool. Must stay data-movement only — zero
    collectives (the split cross-slice path hops via device_put outside
    any program), and the destination donation must survive (a dropped
    alias would double-buffer the pool on every handoff)."""
    import jax.numpy as jnp

    from automodel_tpu.serving.kv_pages import init_pool
    from automodel_tpu.serving.kv_transfer import apply_transfer

    dense, _ = _configs()
    cfg = dataclasses.replace(dense, pipeline_microbatches=1)
    src = init_pool(cfg, [cfg.num_layers], 16, 4)
    dst = init_pool(cfg, [cfg.num_layers], 16, 4)
    B = 4
    idx = jnp.zeros(B, jnp.int32)
    compiled = apply_transfer.lower(dst, src, idx, idx).compile()
    return compiled, None


def quant_serve_step():
    """The quantized serving step (kv_cache_dtype=int8 + serve_precision=
    int8): the SAME single-chip step program as paged_serve_step with the
    int8 pool — page gathers now pull int8 payload AND the per-page scale
    rows (so the gather floor RISES: k, v, k_scale, v_scale), the
    new-token KV quantizes in-jit at scatter time, and the linears run
    through quantized_matmul. Still collective-free with the pool donation
    intact, and — the cfg serves in f32 — zero bf16→f32 upcast converts:
    a quantization path that round-trips through bf16 casts would show up
    here before it shows up as a tolerance failure."""
    import jax

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.serving.engine import ServingConfig, ServingEngine

    dense, _ = _configs()
    cfg = dataclasses.replace(dense, pipeline_microbatches=1)
    params = decoder.init(cfg, jax.random.key(0))
    eng = ServingEngine(params, cfg, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=8,
        kv_cache_dtype="int8", serve_precision="int8",
    ))
    compiled = eng.lower_step().compile()
    return compiled, None


def quant_kv_transfer():
    """The fused page-copy program over a QUANTIZED pool: identical shape
    to kv_transfer but the pool has four leaves per stack (int8 k/v +
    f32 scale planes), so the handoff ships the quantized pages natively
    — the scales ride the same gather/scatter, never a dequant-requant
    round trip (which would appear as extra convert/multiply traffic and
    break bit-exact page adoption on the decode side)."""
    import jax.numpy as jnp

    from automodel_tpu.serving.kv_pages import init_pool
    from automodel_tpu.serving.kv_transfer import apply_transfer

    dense, _ = _configs()
    cfg = dataclasses.replace(dense, pipeline_microbatches=1)
    src = init_pool(cfg, [cfg.num_layers], 16, 4, kv_cache_dtype="int8")
    dst = init_pool(cfg, [cfg.num_layers], 16, 4, kv_cache_dtype="int8")
    B = 4
    idx = jnp.zeros(B, jnp.int32)
    compiled = apply_transfer.lower(dst, src, idx, idx).compile()
    return compiled, None


def pp_ep_1f1b_grad():
    """The flagship PP×EP program: explicit 1F1B grad with the expert A2A
    inside each stage's step. The ppermute ring (fwd + bwd streams) and
    the per-stage A2As are the pinned structure; expert weights must NOT
    be re-gathered per microbatch."""
    import jax

    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.moe_lm import decoder as moe_decoder

    _, moe = _configs()
    cfg = dataclasses.replace(moe, pipeline_schedule="1f1b")
    ctx = MeshConfig(pp=2, ep=2, dp_shard=2).build()
    p = _sharded(cfg, moe_decoder, ctx)
    batch = {"input_ids": _ids(ctx), "labels": _ids(ctx)}
    grad_fn = decoder.make_pp_1f1b_loss_and_grad(cfg, ctx, chunk_size=64)
    compiled = jax.jit(grad_fn).lower(p, batch, jax.random.key(0)).compile()
    return compiled, dict(ctx.sizes)


ENTRY_POINTS = {
    "fsdp_grad": fsdp_grad,
    "ring_cp_forward": ring_cp_forward,
    "ep_moe_forward": ep_moe_forward,
    "paged_serve_step": paged_serve_step,
    "spec_serve_step": spec_serve_step,
    "sharded_serve_step": sharded_serve_step,
    "prefill_step": prefill_step,
    "kv_transfer": kv_transfer,
    "quant_serve_step": quant_serve_step,
    "quant_kv_transfer": quant_kv_transfer,
    "pp_ep_1f1b_grad": pp_ep_1f1b_grad,
}

# Structural invariants — what each program must BE, independent of any
# baseline: `floors` are collectives that must exist (a degenerate lowering
# that drops the ring or the EP dispatch must not pass just because a
# freshly re-pinned baseline agrees), `zeros` must not exist, `op_floors`
# are data-movement ops that must exist (the serve step's paged k/v page
# gathers). The CLI gate checks these on every run AND refuses to write a
# baseline that violates them — `--update-baselines` cannot launder a lost
# collective. Keys must cover ENTRY_POINTS exactly (asserted below).
STRUCTURAL_INVARIANTS = {
    "fsdp_grad": {
        "floors": {"all-gather": 1, "all-reduce": 1},
        # all-to-all is not a zero here: XLA's partitioner (jaxlib 0.9.0)
        # reshards one small cotangent of the chunked-CE loop's
        # dynamic_slice with an all-to-all instead of all-reduces. The
        # baseline pins its count, so a dispatch-sized one still drifts.
        "zeros": ("collective-permute", "ragged-all-to-all"),
        "op_floors": {},
    },
    "ring_cp_forward": {
        "floors": {"collective-permute": 1},
        "zeros": ("all-to-all", "ragged-all-to-all"),
        "op_floors": {},
    },
    "ep_moe_forward": {
        "floors": {"all-to-all": 1},
        "zeros": ("collective-permute", "ragged-all-to-all"),
        "op_floors": {},
    },
    "paged_serve_step": {
        "floors": {},
        "zeros": (
            "all-gather", "all-reduce", "reduce-scatter",
            "collective-permute", "all-to-all", "ragged-all-to-all",
        ),
        "op_floors": {"gather": 2},  # >= the paged k/v page gathers
    },
    "spec_serve_step": {
        "floors": {},
        "zeros": (
            "all-gather", "all-reduce", "reduce-scatter",
            "collective-permute", "all-to-all", "ragged-all-to-all",
        ),
        # paged k/v page gathers PLUS the (S, K+1) verify-row gather —
        # a program below this floor stopped verifying drafted blocks
        "op_floors": {"gather": 3},
    },
    "prefill_step": {
        "floors": {},
        "zeros": (
            "all-gather", "all-reduce", "reduce-scatter",
            "collective-permute", "all-to-all", "ragged-all-to-all",
        ),
        "op_floors": {"gather": 2},  # >= the paged k/v page gathers
    },
    "kv_transfer": {
        "floors": {},
        "zeros": (
            "all-gather", "all-reduce", "reduce-scatter",
            "collective-permute", "all-to-all", "ragged-all-to-all",
        ),
        # the per-pool-array page gathers — a program below this floor
        # stopped reading the source pool (scatters ride the fused
        # gather+set, which HLO folds into dynamic-update-slice forms
        # the DATA_OPS census does not count, so gather is the pin)
        "op_floors": {"gather": 1},
    },
    "quant_serve_step": {
        "floors": {},
        "zeros": (
            "all-gather", "all-reduce", "reduce-scatter",
            "collective-permute", "all-to-all", "ragged-all-to-all",
        ),
        # int8 k/v page gathers PLUS the per-page scale-row gathers —
        # below this floor the step stopped fetching scales and is
        # decoding garbage magnitudes
        "op_floors": {"gather": 4},
        # the engine serves in f32 end to end; any bf16→f32 convert is a
        # quantization path round-tripping through a low-precision cast
        "max_upcasts": 0,
    },
    "quant_kv_transfer": {
        "floors": {},
        "zeros": (
            "all-gather", "all-reduce", "reduce-scatter",
            "collective-permute", "all-to-all", "ragged-all-to-all",
        ),
        # quantized pages ship natively: int8 payload + scale planes ride
        # the same page gathers, never a dequant-requant round trip
        "op_floors": {"gather": 1},
        "max_upcasts": 0,
    },
    "pp_ep_1f1b_grad": {
        "floors": {"collective-permute": 2, "all-to-all": 2},
        "zeros": ("ragged-all-to-all",),
        "op_floors": {},
    },
    "sharded_serve_step": {
        # tp partial-sum reductions must exist (o_proj/down_proj are
        # row-parallel — a program with zero all-reduces silently stopped
        # sharding the matmuls); permutes/A2As have no business in a
        # tp-only decode step, so any appearance is drift the two-sided
        # baseline alone could launder by re-pinning
        "floors": {"all-reduce": 1},
        "zeros": ("collective-permute", "all-to-all", "ragged-all-to-all"),
        # the paged k/v page gathers survive sharding (rank-local)
        "op_floors": {"gather": 2},
    },
}
assert set(STRUCTURAL_INVARIANTS) == set(ENTRY_POINTS)


def check_invariants(report) -> list[str]:
    """Violations of `report.entry`'s structural invariants (empty = ok)."""
    inv = STRUCTURAL_INVARIANTS.get(report.entry)
    if inv is None:
        return []
    out = []
    for kind, lo in inv["floors"].items():
        if report.collectives[kind] < lo:
            out.append(
                f"{report.entry}: {kind} = {report.collectives[kind]} < "
                f"floor {lo} — the program lost a collective it needs "
                f"(degenerate lowering? full counts: {report.collectives})"
            )
    for kind in inv["zeros"]:
        if report.collectives[kind] != 0:
            out.append(
                f"{report.entry}: {kind} = {report.collectives[kind]} "
                f"must be 0 (full counts: {report.collectives})"
            )
    for op, lo in inv["op_floors"].items():
        if report.ops[op] < lo:
            out.append(
                f"{report.entry}: {op} = {report.ops[op]} < floor {lo} — "
                f"the paged access structure degenerated (full ops: "
                f"{report.ops})"
            )
    max_up = inv.get("max_upcasts")
    if max_up is not None and report.convert_upcasts > max_up:
        out.append(
            f"{report.entry}: convert_upcasts = {report.convert_upcasts} "
            f"> max {max_up} — a low-precision cast crept into a path "
            f"that must stay full-precision"
        )
    return out


def build_report(name: str):
    """Compile entry point `name` and analyze it into an HLOReport."""
    from automodel_tpu.analysis.hlo import analyze_compiled

    compiled, mesh_axes = ENTRY_POINTS[name]()
    return analyze_compiled(compiled, entry=name, mesh_axes=mesh_axes)
