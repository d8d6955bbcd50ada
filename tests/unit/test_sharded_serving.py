"""Pod-scale serving: mesh-sharded pool + TP/EP step + DP replica router.

The acceptance contract of the sharded engine (docs/SERVING.md §"Sharded
serving"):

- token-for-token greedy parity tp1 vs tp2 vs dp2×tp2 on a CPU mesh over
  ragged streams — staggered arrivals, forced preemption, prefix-cache
  hits, and speculation enabled — against the single-chip engine (whose
  own parity vs generate() is pinned in test_serving_engine.py);
- compile-once per replica via the jit cache-miss counter (the sharded
  step's in/out shardings are pinned so the donated pool's normalized
  output sharding can never re-cut the cache);
- the MLA pool shards its LATENT rank, the GQA pool its KV heads; MoE
  decoders run PR 1's dropless EP dispatch inside the step;
- the router's per-replica admission: least-loaded-by-free-pages with
  sticky prefix-cache affinity.

The compiled collective structure of the tp2 step is pinned separately by
the `sharded_serve_step` analysis baseline (test_hlo_guards).
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
    PrefixCacheConfig,
    ReplicaRouter,
    Request,
    ServeMeshConfig,
    ServingConfig,
    ServingEngine,
    SpeculativeConfig,
)
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


def _tp_ctx(tp):
    return MeshConfig(tp=tp, dp_shard=1).build(jax.devices()[:tp])


def _serve(params, cfg, mesh_ctx, sc, requests):
    eng = ServingEngine(own(params), cfg, sc, mesh_ctx=mesh_ctx)
    res = eng.serve_batch(requests)
    assert res["stats"]["compiled_signatures"] == 1, res["stats"]
    return res


def test_tp2_parity_ragged_stream_with_preemption():
    """GQA tp2 (KV-head-sharded pool): greedy tokens equal the single-chip
    engine's on a ragged stream whose tight pool forces recompute-style
    preemption — and the trivial 1-device mesh rides the same code path."""
    params = decoder.init(CFG, jax.random.key(0))
    sc = ServingConfig(
        page_size=2, num_pages=8, max_slots=3, pages_per_slot=6,
        token_budget=6, prefill_chunk=3,
    )
    requests = lambda: _reqs(_prompts([4, 4, 4], 20), [0, 0, 0], 5)  # noqa: E731
    base = _serve(params, CFG, None, sc, requests())
    tp1 = _serve(params, CFG, _tp_ctx(1), sc, requests())
    tp2 = _serve(params, CFG, _tp_ctx(2), sc, requests())
    assert tp1["outputs"] == base["outputs"]
    assert tp2["outputs"] == base["outputs"]
    assert tp2["stats"]["preemptions"] >= 1  # the churn actually happened


def test_tp2_looped_decoder_parity_with_preemption():
    """A looped decoder's pool (passes x layers entries, KV heads cut over
    tp) composes with sharding by structure: tp2 tokens equal the
    single-chip engine's through a preemption, one compiled step."""
    from tests import ouro_case

    cfg = ouro_case.config()
    params = ouro_case.init_params(cfg)
    sc = ServingConfig(
        page_size=2, num_pages=8, max_slots=3, pages_per_slot=6,
        token_budget=6, prefill_chunk=3,
    )
    requests = lambda: _reqs(_prompts([4, 4, 4], 20), [0, 0, 0], 5)  # noqa: E731
    base = _serve(params, cfg, None, sc, requests())
    eng = ServingEngine(own(params), cfg, sc, mesh_ctx=_tp_ctx(2))
    assert len(eng.pool[0]) == len(eng._pool_shardings[0]) == 12
    k, _v = eng.pool[0][7]   # pass 2, layer 1: heads cut over tp
    assert {s.data.shape for s in k.addressable_shards} == {(9, 2, 2, 8)}
    tp2 = eng.serve_batch(requests())
    assert tp2["outputs"] == base["outputs"]
    assert tp2["stats"]["preemptions"] >= 1
    assert tp2["stats"]["compiled_signatures"] == 1


def test_tp2_parity_prefix_cache_and_speculation():
    """Prefix sharing (radix hits + COW) and draft-then-verify compose
    with the sharded step: tokens equal the plain single-chip engine's,
    hits and drafts actually fire, one compiled signature."""
    params = decoder.init(CFG, jax.random.key(0))
    rng = np.random.default_rng(1)
    system = [int(t) for t in rng.integers(1, 64, (8,))]
    prompts = [
        system + [int(t) for t in rng.integers(1, 64, (3,))],
        system + [int(t) for t in rng.integers(1, 64, (2,))],
    ]
    geo = dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
               token_budget=8, prefill_chunk=4)
    base = _serve(
        params, CFG, None, ServingConfig(**geo), _reqs(prompts, (0, 2)),
    )
    tp2 = _serve(
        params, CFG, _tp_ctx(2),
        ServingConfig(
            **geo,
            prefix_cache=PrefixCacheConfig(enabled=True),
            speculative=SpeculativeConfig(enabled=True, draft_len=4),
        ),
        _reqs(prompts, (0, 2)),
    )
    assert tp2["outputs"] == base["outputs"]
    assert tp2["stats"]["prefix_hits"] >= 1, tp2["stats"]
    assert tp2["stats"]["drafted_tokens"] >= 1, tp2["stats"]


def test_mla_tp2_latent_sharded_parity():
    """Absorbed-MLA pool under tp2 shards the kv-latent rank (heads share
    one latent — there is no head dim to cut); greedy parity must hold
    through the latent-parallel attention algebra."""
    params = decoder.init(MLA, jax.random.key(0))
    sc = ServingConfig(
        page_size=4, num_pages=20, max_slots=3, pages_per_slot=5,
        token_budget=6, prefill_chunk=3,
    )
    requests = lambda: _reqs(_prompts([6, 9, 4], 10), [0, 1, 2], 5)  # noqa: E731
    base = _serve(params, MLA, None, sc, requests())
    tp2 = _serve(params, MLA, _tp_ctx(2), sc, requests())
    assert tp2["outputs"] == base["outputs"]
    # the latent pool is genuinely partitioned: each rank holds r/tp
    eng = ServingEngine(own(params), MLA, sc, mesh_ctx=_tp_ctx(2))
    for c, _kr in eng.pool[0]:  # every layer's own latent array
        assert c.sharding.spec[2] == "tp", c.sharding


def test_dp2_tp2_router_parity_balance_and_compile_once():
    """dp2×tp2: two tp2 replicas behind the router emit the exact
    single-chip token stream; admission is least-loaded (both replicas
    get work) and each replica keeps ONE compiled signature."""
    params = decoder.init(CFG, jax.random.key(0))
    sc = ServingConfig(
        page_size=4, num_pages=24, max_slots=3, pages_per_slot=6,
        token_budget=8, prefill_chunk=4,
    )
    prompts = _prompts([5, 9, 3, 7, 11, 4])
    arrivals = [0, 0, 1, 2, 3, 4]
    base = ServingEngine(own(params), CFG, sc).serve_batch(
        _reqs(prompts, arrivals)
    )
    router = ReplicaRouter(
        own(params), CFG, sc, ServeMeshConfig(replicas=2, tp=2),
    )
    res = router.serve_batch(_reqs(prompts, arrivals))
    st = res["stats"]
    assert res["outputs"] == base["outputs"]
    assert st["compiled_signatures"] == 1, st
    assert all(
        pr["compiled_signatures"] == 1 for pr in st["per_replica"]
    ), st
    assert min(st["requests_per_replica"]) >= 1, st
    assert sum(st["tokens_per_replica"]) == st["new_tokens"]
    assert 0 < st["balance"] <= 1


def test_router_sticky_prefix_affinity():
    """A later request sharing a cached prefix routes to the replica that
    already holds the pages (and admits as a radix hit there) even when
    the other replica has more free pages."""
    params = decoder.init(CFG, jax.random.key(0))
    rng = np.random.default_rng(7)
    system = [int(t) for t in rng.integers(1, 64, (8,))]
    reqs = [
        Request(
            prompt=system + [int(t) for t in rng.integers(1, 64, (3,))],
            max_new_tokens=4, arrival=0,
        ),
        Request(
            prompt=system + [int(t) for t in rng.integers(1, 64, (2,))],
            max_new_tokens=4, arrival=6,
        ),
    ]
    router = ReplicaRouter(
        own(params), CFG,
        ServingConfig(
            page_size=4, num_pages=24, max_slots=3, pages_per_slot=6,
            token_budget=8, prefill_chunk=4,
            prefix_cache=PrefixCacheConfig(enabled=True),
        ),
        ServeMeshConfig(replicas=2, tp=1),
    )
    st = router.serve_batch(reqs)["stats"]
    assert st["sticky_routed"] >= 1, st
    assert st["prefix_hits"] >= 1, st
    # both landed on one replica — affinity beat least-loaded
    assert sorted(st["requests_per_replica"]) == [0, 2], st


def test_moe_ep2_expert_dispatch_inside_step():
    """DeepSeek shape (dense prefix + MoE stack + MLA cache) under ep2:
    the dropless EP shard_map (expert A2A inside the step) commits the
    exact single-shard token stream."""
    from automodel_tpu.models.moe_lm import decoder as moe_decoder
    from automodel_tpu.models.moe_lm.decoder import MoETransformerConfig
    from automodel_tpu.moe.config import MoEConfig

    cfg = MoETransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=3,
        num_heads=4, num_kv_heads=4, first_k_dense=1, dtype=jnp.float32,
        remat_policy="none",
        attention_type="mla", mla_kv_lora_rank=16, mla_q_lora_rank=12,
        mla_qk_nope_head_dim=8, mla_qk_rope_head_dim=8, mla_v_head_dim=8,
        moe=MoEConfig(
            n_routed_experts=4, n_shared_experts=1, experts_per_token=2,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            aux_loss_coeff=0.0, dispatcher="dropless",
        ),
    )
    params = moe_decoder.init(cfg, jax.random.key(0))
    sc = ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=6, prefill_chunk=3,
    )
    requests = lambda: _reqs(_prompts([5, 7], 40), [0, 1], 4)  # noqa: E731
    base = _serve(params, cfg, None, sc, requests())
    ctx = MeshConfig(ep=2, dp_shard=1).build(jax.devices()[:2])
    ep2 = _serve(params, cfg, ctx, sc, requests())
    assert ep2["outputs"] == base["outputs"]


def test_tp2_defrag_preserves_decode_and_sharding():
    """Pool compaction under tp2: the defrag gather rides the sharded
    (donated) pool — page IDs stay global so the host plan is unchanged,
    the head shards move together, and subsequent decode is unaffected."""
    from automodel_tpu.inference.generate import GenerateConfig, generate

    params = decoder.init(CFG, jax.random.key(0))
    eng = ServingEngine(own(params), CFG, ServingConfig(
        page_size=2, num_pages=16, max_slots=3, pages_per_slot=8,
        token_budget=6,
    ), mesh_ctx=_tp_ctx(2))
    prompts = _prompts([4, 5, 3], seed0=80)
    sched = eng.make_scheduler()
    for p in prompts:
        sched.submit(Request(prompt=list(p), max_new_tokens=6))
    step = 0
    while sched.has_work:
        plan = sched.schedule(step)
        if plan is not None:
            eng.run_and_absorb(sched, plan, step)
            if step == 4:
                eng.defrag(sched)
                for k, _v in eng.pool[0]:
                    assert k.sharding.spec[2] == "tp"
        step += 1
    for p, req in zip(prompts, sorted(sched.finished, key=lambda r: r.rid)):
        ref = generate(
            params, CFG, jnp.asarray([p], jnp.int32), jax.random.key(0),
            GenerateConfig(max_new_tokens=6),
        )
        assert [int(t) for t in np.asarray(ref)[0, len(p):]] == req.generated


def test_mesh_validation_errors():
    """The engine rejects meshes it cannot shard: non-tp/ep axes, GQA head
    indivisibility, ep without MoE, token budgets the EP shard_map cannot
    split — loud errors, not silent replication."""
    params = decoder.init(CFG, jax.random.key(0))
    sc = ServingConfig(page_size=4, num_pages=8, max_slots=2,
                       pages_per_slot=4, token_budget=4)
    with pytest.raises(ValueError, match="dp_shard=1"):
        ServingEngine(
            own(params), CFG, sc,
            mesh_ctx=MeshConfig(dp_shard=2).build(jax.devices()[:2]),
        )
    bad_heads = dataclasses.replace(CFG, num_kv_heads=3, num_heads=3)
    with pytest.raises(ValueError, match="divisible by tp"):
        ServingEngine(own(params), bad_heads, sc, mesh_ctx=_tp_ctx(2))
    with pytest.raises(ValueError, match="MoE"):
        ServingEngine(
            own(params), CFG, sc,
            mesh_ctx=MeshConfig(ep=2, dp_shard=1).build(jax.devices()[:2]),
        )
    with pytest.raises(ValueError, match="devices"):
        ServeMeshConfig(replicas=8, tp=2).build_contexts()


@pytest.mark.slow
def test_tp2_eagle_hidden_feedback_host_addressable():
    """EAGLE speculation under tp2: the frontier hidden feedback is
    gathered per-slot from the sharded step (replicated output), so the
    host-side drafter state machinery works unchanged — and greedy
    verification keeps the committed stream token-exact regardless of
    draft quality."""
    from automodel_tpu.models.llm.decoder import head_kernel
    from automodel_tpu.serving import EagleDraftSource
    from automodel_tpu.speculative.eagle1 import Eagle1Config, init_drafter

    params = decoder.init(CFG, jax.random.key(0))
    ecfg = Eagle1Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_heads=4, num_kv_heads=2, num_layers=1,
    )
    sc_kw = dict(page_size=4, num_pages=32, max_slots=2, pages_per_slot=8,
                 token_budget=8, prefill_chunk=4)
    requests = lambda: _reqs(_prompts([5, 9], 60), [0, 1], 6)  # noqa: E731
    base = _serve(params, CFG, None, ServingConfig(**sc_kw), requests())
    eng = ServingEngine(
        own(params), CFG,
        ServingConfig(
            **sc_kw,
            speculative=SpeculativeConfig(
                enabled=True, draft_source="eagle", draft_len=3,
            ),
        ),
        draft_source=EagleDraftSource(
            init_drafter(ecfg, jax.random.key(1)), ecfg,
            head_kernel(params, CFG), draft_len=3, window=8,
        ),
        mesh_ctx=_tp_ctx(2),
    )
    res = eng.serve_batch(requests())
    assert res["outputs"] == base["outputs"]
    assert res["stats"]["compiled_signatures"] == 1
