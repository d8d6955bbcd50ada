"""Quantized serving: int8 KV pages with per-page scales + int8 linears.

The acceptance contract of the quantized tier (docs/SERVING.md
§"Quantized serving"):

- scale rows travel with their pages through every page-movement op (the
  in-step COW copy, defrag compaction, the disagg handoff transfer)
  because they are pool leaves indexed by the same global page IDs — the
  host-side allocator / scheduler / prefix cache never learn the pool is
  quantized;
- the quantized engine is SELF-consistent exactly: prefix-cache COW,
  lossless greedy speculation, preemption churn, the disaggregated
  handoff, and tp2 sharding all reproduce the plain quant engine's
  greedy stream token for token (the identical quantized arithmetic runs
  in every path — a dequant-requant round trip anywhere would break it);
- vs the fp engine the contract is TOLERANCE, not bit-equality: on a
  model with confident predictions greedy top-1 agreement >= 0.99 (an
  untrained random init has top-1 margins below any quantization noise
  floor, so agreement there measures coin flips, not correctness);
- ONE compiled step signature (fixed-shape contract survives the extra
  pool leaves), and the engine-lifetime allocator identity
  `num_free + cached_pages == num_pages` after preempt/churn storms.

The quantized step's compiled structure (collective-free, donation over
all four pool leaves, the int8-payload + scale-row gather floor, zero
bf16→f32 upcasts) is pinned separately by the `quant_serve_step` /
`quant_kv_transfer` analysis baselines (test_hlo_guards).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.distributed import MeshConfig
from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.serving import (
    DisaggConfig,
    DisaggRouter,
    KVTransfer,
    PrefixCacheConfig,
    Request,
    ServingConfig,
    ServingEngine,
    SpeculativeConfig,
)
from automodel_tpu.serving.kv_pages import (
    apply_defrag,
    init_pool,
    pool_bytes,
)
from automodel_tpu.serving.kv_transfer import apply_transfer
from tests.serving_params import own

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
    num_heads=4, num_kv_heads=2, qk_norm=True, dtype=jnp.float32,
    remat_policy="none",
)
MLA = dataclasses.replace(
    CFG, qk_norm=False, attention_type="mla", mla_kv_lora_rank=16,
    mla_q_lora_rank=12, mla_qk_nope_head_dim=8, mla_qk_rope_head_dim=8,
    mla_v_head_dim=8,
)
QUANT = dict(kv_cache_dtype="int8", serve_precision="int8")


@pytest.fixture(scope="module")
def params():
    return decoder.init(CFG, jax.random.key(0))


def _prompts(lens, seed0=0):
    return [
        [int(t) for t in np.random.default_rng(seed0 + i).integers(1, 64, (l,))]
        for i, l in enumerate(lens)
    ]


def _reqs(prompts, arrivals, max_new=6):
    return [
        Request(prompt=list(p), max_new_tokens=max_new, arrival=a)
        for p, a in zip(prompts, arrivals)
    ]


def _serve(params, cfg, sc, requests, mesh_ctx=None):
    eng = ServingEngine(own(params), cfg, sc, mesh_ctx=mesh_ctx)
    res = eng.serve_batch(requests)
    assert res["stats"]["compiled_signatures"] == 1, res["stats"]
    return eng, res


# -- pool plumbing -----------------------------------------------------------
def test_init_quant_pool_shapes_and_dtypes():
    """Quantized layers are 4-leaf: int8 payloads at the fp shapes plus
    (N+1, ps) f32 scale planes initialized to identity dequant."""
    (gqa,) = init_pool(CFG, [CFG.num_layers], 8, 4, kv_cache_dtype="int8")
    assert len(gqa) == CFG.num_layers == 2
    D = CFG.resolved_head_dim
    for k, v, ks, vs in gqa:
        assert k.shape == v.shape == (9, 4, CFG.num_kv_heads, D)
        assert k.dtype == v.dtype == jnp.int8
        assert ks.shape == vs.shape == (9, 4)
        assert ks.dtype == vs.dtype == jnp.float32
        assert bool(jnp.all(ks == 1.0)) and bool(jnp.all(vs == 1.0))

    (mla,) = init_pool(MLA, [MLA.num_layers], 8, 4, kv_cache_dtype="int8")
    assert len(mla) == MLA.num_layers
    for c, kr, cs, krs in mla:
        assert c.shape == (9, 4, MLA.mla_kv_lora_rank)
        assert kr.shape == (9, 4, MLA.mla_qk_rope_head_dim)
        assert c.dtype == kr.dtype == jnp.int8
        assert cs.shape == krs.shape == (9, 4)

    # int8 + f32-scale pool is well under half the f32 pool (>= 1.8x even
    # against a bf16 pool: 2 bytes -> 1 + 4/ps)
    (fp,) = init_pool(CFG, [CFG.num_layers], 8, 4)
    assert pool_bytes([fp]) / pool_bytes([gqa]) > 3.0


def test_defrag_moves_scales_with_pages():
    """apply_defrag gathers along the page axis of EVERY leaf — a moved
    page's scale rows arrive at the new page ID with its int8 payload."""
    (stack,) = init_pool(CFG, [CFG.num_layers], 4, 2, kv_cache_dtype="int8")
    stack = tuple(
        (k.at[3].set(7), v, ks.at[3].set(0.25), vs) for k, v, ks, vs in stack
    )
    # plan: live page 3 compacts to slot 0; rest backfilled from free pages
    src = jnp.asarray([3, 1, 2, 0], jnp.int32)
    for k2, v2, ks2, vs2 in apply_defrag([stack], src)[0]:
        assert bool(jnp.all(k2[0] == 7))
        assert bool(jnp.all(ks2[0] == 0.25))
        # trash page stayed put, identity scales everywhere else
        assert bool(jnp.all(ks2[1:] == 1.0))


def test_transfer_ships_scale_planes_natively():
    """apply_transfer copies int8 payload AND scale rows page-for-page —
    the handoff never dequantizes, so adopted pages are bit-identical."""
    src = init_pool(CFG, [CFG.num_layers], 4, 2, kv_cache_dtype="int8")
    dst = init_pool(CFG, [CFG.num_layers], 4, 2, kv_cache_dtype="int8")
    src[0] = tuple(
        (k.at[1].set(-5), v, ks.at[1].set(0.5), vs) for k, v, ks, vs in src[0]
    )
    out = apply_transfer(dst, src, jnp.asarray([1], jnp.int32),
                         jnp.asarray([2], jnp.int32))
    for k2, _, ks2, _ in out[0]:
        assert bool(jnp.all(k2[2] == -5))
        assert bool(jnp.all(ks2[2] == 0.5))


def test_step_cow_copies_scale_rows(params):
    """The in-step COW block is a pytree copy along the page axis: the
    destination page's scale rows equal the source's after the step."""
    eng = ServingEngine(own(params), CFG, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=8, **QUANT,
    ))
    eng.pool[0] = tuple(
        (k.at[2].set(9), v, ks.at[2].set(0.125), vs)
        for k, v, ks, vs in eng.pool[0]
    )
    T, S, P, trash = 8, 2, 4, 16
    batch = {key: jnp.full(T, trash if key == "page" else 0, jnp.int32)
             for key in ("tok", "slot", "pos", "page", "off")}
    batch.update(
        page_tables=jnp.full((S, P), trash, jnp.int32),
        sample_tok=jnp.zeros(S, jnp.int32),
        temp=jnp.zeros(S, jnp.float32),
        seed=jnp.zeros(S, jnp.int32),
        cow_src=jnp.asarray([2, trash], jnp.int32),
        cow_dst=jnp.asarray([5, trash], jnp.int32),
    )
    new_pool, _, _ = eng._step(eng.params, eng.pool, batch)
    for k2, _, ks2, _ in new_pool[0]:
        assert bool(jnp.all(k2[5] == 9))
        assert bool(jnp.all(ks2[5] == 0.125))


# -- exact self-parity across every serving feature --------------------------
def test_quant_prefix_cache_cow_parity(params):
    """Radix hits + COW against the plain quant engine: adopted pages are
    shared quantized pages (scales adopt with them), so tokens match
    exactly and hits actually fired."""
    rng = np.random.default_rng(1)
    system = [int(t) for t in rng.integers(1, 64, (8,))]
    prompts = [
        system + [int(t) for t in rng.integers(1, 64, (3,))],
        system + [int(t) for t in rng.integers(1, 64, (2,))],
    ]
    geo = dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
               token_budget=8, prefill_chunk=4)
    _, base = _serve(params, CFG, ServingConfig(**geo, **QUANT),
                     _reqs(prompts, (0, 2)))
    eng, warm = _serve(
        params, CFG,
        ServingConfig(**geo, **QUANT,
                      prefix_cache=PrefixCacheConfig(enabled=True)),
        _reqs(prompts, (0, 2)),
    )
    assert warm["outputs"] == base["outputs"]
    assert warm["stats"]["prefix_hits"] >= 1, warm["stats"]
    # engine-lifetime allocator identity: free + radix-cached == total
    assert (eng.alloc.num_free + eng.prefix.cached_pages
            == eng.serve_cfg.num_pages)


def test_quant_speculation_parity(params):
    """Greedy draft-then-verify over the quantized pool is lossless: the
    verifier's argmax IS the quant engine's argmax."""
    prompts = _prompts([9, 7], seed0=40)
    geo = dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
               token_budget=8, prefill_chunk=4)
    _, base = _serve(params, CFG, ServingConfig(**geo, **QUANT),
                     _reqs(prompts, (0, 0), max_new=8))
    _, spec = _serve(
        params, CFG,
        ServingConfig(**geo, **QUANT,
                      speculative=SpeculativeConfig(enabled=True, draft_len=4)),
        _reqs(prompts, (0, 0), max_new=8),
    )
    assert spec["outputs"] == base["outputs"]
    assert spec["stats"]["drafted_tokens"] >= 1, spec["stats"]


def test_quant_preemption_parity(params):
    """A tight pool forces recompute-style preemption (truncate drops the
    provisional tail — its stale scale rows are simply overwritten at the
    next quantize-at-scatter); greedy tokens match the untight engine."""
    prompts = _prompts([4, 4, 4], seed0=20)
    roomy = dict(page_size=2, num_pages=32, max_slots=3, pages_per_slot=6,
                 token_budget=6, prefill_chunk=3)
    tight = dict(roomy, num_pages=8)
    _, base = _serve(params, CFG, ServingConfig(**roomy, **QUANT),
                     _reqs(prompts, (0, 0, 0), 5))
    eng, res = _serve(
        params, CFG,
        ServingConfig(**tight, **QUANT,
                      prefix_cache=PrefixCacheConfig(enabled=True)),
        _reqs(prompts, (0, 0, 0), 5),
    )
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["preemptions"] >= 1
    # after the storm every page is free or radix-cached — a scale-aware
    # leak anywhere in the churn path would break the lifetime identity
    assert eng.alloc.num_free + eng.prefix.cached_pages == 8


def test_quant_looped_decoder_scale_planes_per_pass():
    """A looped decoder under int8 pages: every (pass, layer) entry carries
    its own two scale planes, copy-on-write and preemption move them with
    their pages, and greedy tokens through a tight pool equal the roomy
    quantized engine's."""
    from tests import ouro_case

    cfg = ouro_case.config()
    lp = ouro_case.init_params(cfg)
    prompts = _prompts([4, 4, 4], seed0=20)
    roomy = dict(page_size=2, num_pages=32, max_slots=3, pages_per_slot=6,
                 token_budget=6, prefill_chunk=3)
    base_eng, base = _serve(lp, cfg, ServingConfig(**roomy, **QUANT),
                            _reqs(prompts, (0, 0, 0), 5))
    assert len(base_eng.pool[0]) == 12
    assert all(len(entry) == 4 and entry[0].dtype == jnp.int8
               and entry[2].shape == (33, 2) for entry in base_eng.pool[0])
    # written rows left scales other than 1 in every pass's planes
    assert all(bool((np.asarray(entry[2]) != 1.0).any())
               for entry in base_eng.pool[0])
    eng, res = _serve(
        lp, cfg,
        ServingConfig(**dict(roomy, num_pages=8), **QUANT,
                      prefix_cache=PrefixCacheConfig(enabled=True)),
        _reqs(prompts, (0, 0, 0), 5),
    )
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["preemptions"] >= 1
    assert eng.alloc.num_free + eng.prefix.cached_pages == 8


def test_quant_disagg_handoff_parity(params):
    """Prefill→decode handoff ships quantized pages natively: router
    tokens equal the monolithic quant engine's, and the wire-bytes
    counter advanced by pages × quantized page_bytes (~half the fp
    engine's page_bytes)."""
    sc = ServingConfig(
        page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
        token_budget=8, prefill_chunk=4, **QUANT,
    )
    prompts = _prompts([6, 9, 4], seed0=30)
    _, mono = _serve(params, CFG, sc, _reqs(prompts, (0, 1, 3)))
    router = DisaggRouter(own(params), CFG, sc, DisaggConfig(
        prefill_replicas=1, decode_replicas=1,
    ))
    res = router.serve_batch(_reqs(prompts, (0, 1, 3)))
    assert res["outputs"] == mono["outputs"]
    transfers = list(router.transfers.values())
    assert sum(t.n_pages for t in transfers) >= 1
    assert all(t.n_bytes == t.n_pages * t.page_bytes for t in transfers)
    # quantized wire bytes: >= 1.8x fewer than the same handoff in fp
    fp_sc = dataclasses.replace(sc, kv_cache_dtype=None, serve_precision=None)
    fp_router = DisaggRouter(own(params), CFG, fp_sc, DisaggConfig(
        prefill_replicas=1, decode_replicas=1,
    ))
    fp_router.serve_batch(_reqs(prompts, (0, 1, 3)))
    fp_pb = next(iter(fp_router.transfers.values())).page_bytes
    q_pb = transfers[0].page_bytes
    assert fp_pb / q_pb >= 1.8, (fp_pb, q_pb)


def test_quant_tp2_parity(params):
    """tp2 shards the int8 KV heads while the scale planes replicate;
    greedy tokens equal the single-chip quant engine's through the
    sharded gather-dequant attention."""
    sc = ServingConfig(
        page_size=2, num_pages=8, max_slots=3, pages_per_slot=6,
        token_budget=6, prefill_chunk=3, **QUANT,
    )
    prompts = _prompts([4, 4, 4], seed0=20)
    _, base = _serve(params, CFG, sc, _reqs(prompts, (0, 0, 0), 5))
    ctx = MeshConfig(tp=2, dp_shard=1).build(jax.devices()[:2])
    eng, tp2 = _serve(params, CFG, sc, _reqs(prompts, (0, 0, 0), 5),
                      mesh_ctx=ctx)
    assert tp2["outputs"] == base["outputs"]
    # int8 payload sharded over kv heads; scale planes replicated
    for k, v, ks, vs in eng.pool[0]:
        assert k.sharding.spec[2] == "tp"
        assert all(s is None for s in ks.sharding.spec)


def test_quant_mla_stream_compiles_once():
    """Absorbed-MLA quantized pool (int8 latent + rope stripes, separate
    scale planes) serves a ragged stream geometry-independently: tokens
    match across pool sizes, one compiled signature each."""
    params = decoder.init(MLA, jax.random.key(0))
    prompts = _prompts([6, 9, 4], seed0=10)
    small = dict(page_size=4, num_pages=20, max_slots=3, pages_per_slot=5,
                 token_budget=6, prefill_chunk=3)
    big = dict(small, num_pages=40, pages_per_slot=10)
    _, a = _serve(params, MLA, ServingConfig(**small, **QUANT),
                  _reqs(prompts, (0, 1, 2), 5))
    _, b = _serve(params, MLA, ServingConfig(**big, **QUANT),
                  _reqs(prompts, (0, 1, 2), 5))
    assert a["outputs"] == b["outputs"]


# -- tolerance vs the fp engine ----------------------------------------------
@pytest.mark.slow
def test_quant_vs_fp_greedy_agreement_confident_model():
    """The tolerance contract: a model with real top-1 margins (briefly
    trained on a deterministic next-token mapping) keeps >= 0.99 greedy
    top-1 agreement between the int8 engine and the fp engine."""
    import optax

    from automodel_tpu.loss import fused_linear_cross_entropy

    V = CFG.vocab_size
    params = decoder.init(CFG, jax.random.key(0))

    def f_next(tok):
        return (tok * 3 + 7) % (V - 1) + 1

    def loss_fn(p, ids, labels):
        h = decoder.forward(p, CFG, ids, return_hidden=True)
        ce, n = fused_linear_cross_entropy(
            h, p["lm_head"]["kernel"], labels, chunk_size=64
        )
        return ce / n

    tx = optax.adam(3e-3)

    @jax.jit
    def train_one(p, o, key):
        ids = jax.random.randint(key, (8, 32), 1, V)
        _, g = jax.value_and_grad(loss_fn)(p, ids, f_next(ids))
        up, o = tx.update(g, o, p)
        return optax.apply_updates(p, up), o

    opt = tx.init(params)
    key = jax.random.key(1)
    for _ in range(150):
        key, k = jax.random.split(key)
        params, opt = train_one(params, opt, k)

    sc = dict(page_size=4, num_pages=32, max_slots=3, pages_per_slot=8,
              token_budget=8, prefill_chunk=4)
    prompts = _prompts([5, 9, 3, 7], seed0=50)
    _, fp = _serve(params, CFG, ServingConfig(**sc),
                   _reqs(prompts, (0, 0, 2, 3), 8))
    _, qt = _serve(params, CFG, ServingConfig(**sc, **QUANT),
                   _reqs(prompts, (0, 0, 2, 3), 8))
    agree = sum(
        a == b
        for o1, o2 in zip(fp["outputs"], qt["outputs"])
        for a, b in zip(o1, o2)
    )
    total = sum(len(o) for o in fp["outputs"])
    assert agree / total >= 0.99, (agree, total, fp["outputs"], qt["outputs"])


# -- config validation -------------------------------------------------------
def test_quant_config_validation(params):
    with pytest.raises(AssertionError):
        ServingConfig(page_size=4, num_pages=8, max_slots=1,
                      pages_per_slot=2, kv_cache_dtype="int4")
    with pytest.raises(AssertionError):
        ServingConfig(page_size=4, num_pages=8, max_slots=1,
                      pages_per_slot=2, serve_precision="int2")
