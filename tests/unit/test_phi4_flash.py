"""Phi4FlashForCausalLM (Phi-4-mini-flash-reasoning, SambaY): the family
mapping of the published keys, the published parameter count from shapes
alone, the training forward against the plain float32 reference
(benchmark/reference/phi4_flash.py), and the serving engine against the same
reference through chunked prefill and decode: the cross-decoder over the
sampled rows alone, the one shared cache read by the cross layers, the GMUs
fed by the memory layer. The toy (tests/phi4_case.py): 8 layers, 4 query
heads of 16 over 2, a window of 8."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.llm import decoder
from automodel_tpu.serving import Request, ServingConfig, ServingEngine
from tests import phi4_case as pc
from tests.serving_params import own

#: float32 program against the float32 reference: rounding only
LOGIT_TOL = 1e-5
#: an untied head of the toy's widths: the tied one gives the row's own input
#: token probability 1 in any precision (std-1 embedding), so a log-
#: probability comparison through the engine is sharp only untied
UNTIED = {**pc.HF, "tie_word_embeddings": False}


@pytest.fixture(scope="module")
def case():
    cfg = pc.config()
    return cfg, pc.init_params(cfg)


@pytest.fixture(scope="module")
def untied():
    cfg = pc.config(hf=UNTIED)
    return cfg, pc.init_params(cfg, hf=UNTIED)


def published() -> dict:
    with open(os.path.join(pc.ROOT, "benchmark", "configs",
                           "phi4_mini_flash_serve_v5e1.json")) as f:
        return json.load(f)


def test_family_mapping_derives_the_published_layout():
    hf = {k: v for k, v in published().items() if k not in (
        "source", "reduced", "assumed", "published", "stands_for", "serving")}
    cfg = pc.config(hf=hf)
    ops = cfg.layer_ops
    assert ops[:16] == ("mamba", "window_attention") * 8
    assert ops[16:18] == ("mamba", "attention")
    assert ops[18:] == ("gmu", "cross_attention") * 7
    # the memory is layer 16's, the shared cache layer 17's, the cross-
    # decoder from 18
    assert decoder.cross_decoder(cfg) == (16, 17, 18)
    assert decoder.layer_windows(cfg)[:17] == (None, 512) * 8 + (None,)
    assert set(decoder.layer_windows(cfg)[17:]) == {None}
    assert (cfg.norm_type, cfg.use_rope, cfg.differential_attention) == (
        "layer", False, True)
    assert (cfg.attention_bias, cfg.o_proj_bias, cfg.mamba_inner_norms) == (
        True, True, False)
    assert (cfg.resolved_head_dim, cfg.resolved_dt_rank, cfg.mamba_d_inner) == (
        64, 160, 5120)
    # the head is untied in the benchmark's file alone
    assert not cfg.tie_word_embeddings and published()["published"][
        "tie_word_embeddings"]


def test_published_parameter_count_from_shapes_alone():
    hf = {k: v for k, v in published().items() if k not in (
        "source", "reduced", "assumed", "published", "stands_for", "serving")}
    cfg = pc.config(hf={**hf, "tie_word_embeddings": True})
    shapes = jax.eval_shape(lambda: decoder.init(cfg, jax.random.key(0)))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    H = 2560
    mamba = 41_241_600
    attention = 19_668_864
    cross = 13_112_704
    gmu = 26_214_400
    mlp = 78_643_200
    norms = 10_240
    layers = 9 * mamba + 9 * attention + 7 * gmu + 7 * cross + 32 * (mlp + norms)
    assert layers == 3_340_393_984
    assert count == layers + 200_064 * H + 2 * H
    # the benchmark's untied head: 512,163,840 more
    untied = jax.eval_shape(lambda: decoder.init(
        dataclasses.replace(cfg, tie_word_embeddings=False), jax.random.key(0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(untied)) == (
        count + 200_064 * H)


def test_forward_matches_reference_with_the_tied_head(case):
    cfg, params = case
    ids = np.random.default_rng(0).integers(0, pc.VOCAB, (2, 40))
    out = np.asarray(decoder.forward(params, cfg, jnp.asarray(ids)))
    ref = pc.reference(params, ids)
    assert np.abs(out - ref).max() < LOGIT_TOL * np.abs(ref).max()
    assert "lm_head" not in params


@pytest.mark.parametrize("what", ["memory", "lambda", "sub_norm"])
def test_each_part_of_the_cross_decoder_moves_the_result(case, what):
    """The GMU reads layer 16's memory, lambda weighs the second map, the
    sub-norm's scale is applied: leaving any out changes the logits."""
    cfg, params = case
    ids = np.random.default_rng(1).integers(0, pc.VOCAB, (1, 24))
    ref = pc.reference(params, ids)
    bent = jax.tree.map(lambda a: a, params)
    if what == "memory":
        bent["gmu_layers"] = dict(bent["gmu_layers"], in_proj={
            "kernel": jnp.zeros_like(bent["gmu_layers"]["in_proj"]["kernel"])})
    elif what == "lambda":
        for stack in ("attn_layers", "window_attn_layers", "cross_attn_layers"):
            bent[stack] = dict(bent[stack], lambda_q1=10 * bent[stack]["lambda_q1"])
    else:
        for stack in ("attn_layers", "window_attn_layers", "cross_attn_layers"):
            bent[stack] = dict(bent[stack], sub_norm={
                "scale": 2 * bent[stack]["sub_norm"]["scale"]})
    out = np.asarray(decoder.forward(bent, cfg, jnp.asarray(ids)))
    assert np.abs(out - ref).max() > 100 * LOGIT_TOL * np.abs(ref).max()


def _serve(engine, prompts, max_new=6):
    """Every prompt through the engine; {rid: [(token, logprob)]}."""
    sched = engine.make_scheduler()
    for p in prompts:
        sched.submit(Request(prompt=list(p), max_new_tokens=max_new))
    out, step = {}, 0
    while sched.has_work:
        plan = sched.schedule(step)
        if plan is not None:
            tokens, lps = engine.run_step(plan)
            for slot, _c, samples in plan.scheduled:
                if samples:
                    rid = sched.running[slot].rid
                    out.setdefault(rid, []).append(float(lps[slot]))
            sched.update(plan, tokens, step)
        step += 1
    return out, sorted(sched.finished, key=lambda r: r.rid)


def _log_softmax(logits):
    logits = logits.astype(np.float64)
    best = logits.max(-1, keepdims=True)
    return logits - best - np.log(np.exp(logits - best).sum(-1, keepdims=True))


SERVING = dict(page_size=4, num_pages=64, max_slots=3, pages_per_slot=16,
               token_budget=16, prefill_chunk=12)


def test_the_engine_serves_against_the_reference(untied):
    """Chunked prefill (prompts of 5, 27 and 41 tokens: several chunks, past
    the window and the ring), then decode with the slots mixed in one step:
    every sampled token's log-probability as the reference gives it over the
    whole sequence. The pool holds the full layer's pages alone; the window
    layers keep rings, the Mamba layers their state, the cross layers
    nothing."""
    cfg, params = untied
    engine = ServingEngine(own(params), cfg, ServingConfig(**SERVING))
    assert len(engine.pool) == 1 and len(engine.pool[0]) == 1
    # merged pages: a token's 2 heads of 16 side by side
    assert [a.shape for a in engine.pool[0][0]] == [(65, 4, 32)] * 2
    assert engine._num_ssm == 3 and len(engine.state) == 3 + 2
    reg = engine.obs.registry.snapshot()
    assert reg["serve_full_layers"] == 1 and reg["serve_window_layers"] == 2
    assert reg["serve_kv_bytes_per_token"] == 2 * 32 * 4
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, pc.VOCAB, n).tolist() for n in (5, 27, 41)]
    logprobs, finished = _serve(engine, prompts)
    for p, req in zip(prompts, finished):
        seq = p + req.generated
        ref = _log_softmax(pc.reference(params, [seq], hf=UNTIED)[0])
        want = [ref[t, seq[t + 1]] for t in range(len(p) - 1, len(seq) - 1)]
        np.testing.assert_allclose(logprobs[req.rid], want, atol=1e-4)
        assert ref.argmax(-1)[len(p) - 1:-1].tolist() == req.generated


def test_the_cross_decoder_runs_over_the_sample_rows_alone(untied, monkeypatch):
    """The skip is exact and it is taken: the GMU and the cross-attention
    layer see the engine's S sample rows (one a slot), never the step's T;
    the cross layers write no page (the pool is the full layer's alone, its
    one entry written by it); and a prompt row that samples nothing never
    reaches them, yet the sample rows' log-probabilities are the
    reference's."""
    cfg, params = untied
    seen = []
    inner_gmu, inner_cross = ServingEngine._gmu, ServingEngine._cross_attn

    def gmu(self, h, lp, memory):
        seen.append(("gmu", h.shape, memory.shape))
        return inner_gmu(self, h, lp, memory)

    def cross(self, h, lp, kcfg, cache, b, layer):
        seen.append(("cross", h.shape, len(cache)))
        return inner_cross(self, h, lp, kcfg, cache, b, layer)

    monkeypatch.setattr(ServingEngine, "_gmu", gmu)
    monkeypatch.setattr(ServingEngine, "_cross_attn", cross)
    engine = ServingEngine(own(params), cfg, ServingConfig(**SERVING))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, pc.VOCAB, n).tolist() for n in (30, 9)]
    logprobs, finished = _serve(engine, prompts, max_new=4)
    S, H, C = SERVING["max_slots"], 64, 128
    assert seen == [("gmu", (1, S, H), (S, C)), ("cross", (1, S, H), 2)]
    for p, req in zip(prompts, finished):
        seq = p + req.generated
        ref = _log_softmax(pc.reference(params, [seq], hf=UNTIED)[0])
        want = [ref[t, seq[t + 1]] for t in range(len(p) - 1, len(seq) - 1)]
        np.testing.assert_allclose(logprobs[req.rid], want, atol=1e-4)


def test_the_engine_serves_through_the_kernel(monkeypatch):
    """The same through the differential KERNEL (interpret mode), heads of
    64 (8 over 4): the window layers' calls over their rings, the full
    layer's over the pool, the cross layer's over the sample rows' segments
    of the same pool, every call the kernel's."""
    import automodel_tpu.ops.attention as attention
    from automodel_tpu.observability.metrics import default_registry

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    hf = {**UNTIED, "hidden_size": 512, "num_attention_heads": 8,
          "num_key_value_heads": 4}
    cfg = pc.config(hf=hf)
    params = pc.init_params(cfg, hf=hf)
    kernel = default_registry().counter("paged_attention_diff_total", impl="pallas")
    before = kernel.value
    engine = ServingEngine(own(params), cfg, ServingConfig(
        page_size=8, num_pages=48, max_slots=3, pages_per_slot=8,
        token_budget=32, prefill_chunk=20))
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, pc.VOCAB, n).tolist() for n in (6, 30, 45)]
    logprobs, finished = _serve(engine, prompts, max_new=5)
    # 2 window layers, the full layer, the cross layer: one trace
    assert kernel.value - before == 4
    for p, req in zip(prompts, finished):
        seq = p + req.generated
        ref = _log_softmax(pc.reference(params, [seq], hf=hf)[0])
        want = [ref[t, seq[t + 1]] for t in range(len(p) - 1, len(seq) - 1)]
        np.testing.assert_allclose(logprobs[req.rid], want, atol=1e-4)


def test_the_gmus_read_the_memory_layer(untied, monkeypatch):
    """A step whose GMUs are handed zeros for the memory serves other
    log-probabilities than the reference's."""
    cfg, params = untied
    inner = ServingEngine._gmu
    monkeypatch.setattr(ServingEngine, "_gmu", lambda self, h, lp, m: inner(
        self, h, lp, jnp.zeros_like(m)))
    engine = ServingEngine(own(params), cfg, ServingConfig(**SERVING))
    prompt = np.random.default_rng(4).integers(0, pc.VOCAB, 20).tolist()
    logprobs, finished = _serve(engine, [prompt], max_new=4)
    seq = prompt + finished[0].generated
    ref = _log_softmax(pc.reference(params, [seq], hf=UNTIED)[0])
    want = [ref[t, seq[t + 1]] for t in range(len(prompt) - 1, len(seq) - 1)]
    assert np.abs(np.asarray(logprobs[finished[0].rid]) - want).max() > 1e-2


def test_a_tied_head_is_served(case):
    """The published (tied) head through the engine: the reference's
    greedy tokens."""
    cfg, params = case
    engine = ServingEngine(own(params), cfg, ServingConfig(**SERVING))
    prompt = np.random.default_rng(5).integers(0, pc.VOCAB, 17).tolist()
    _, finished = _serve(engine, [prompt], max_new=5)
    seq = prompt + finished[0].generated
    ref = pc.reference(params, [seq])[0]
    assert ref.argmax(-1)[len(prompt) - 1:-1].tolist() == finished[0].generated


# -- the kernel ------------------------------------------------------------------
def _kernel_case(Hq, Hkv, seed):
    rng = np.random.default_rng(seed)
    D, ps, P, N = 64, 8, 6, 20
    # a decode row, a chunk of 16 rows (the tile body), another decode row,
    # two pad rows
    rows = [(0, 30)] + [(1, p) for p in range(10, 26)] + [(2, 44)] + [(-1, -1)] * 2
    slot = np.array([r[0] for r in rows], np.int32)
    pos = np.array([r[1] for r in rows], np.int32)
    q = jnp.asarray(rng.standard_normal((len(rows), Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((N, ps, Hkv * D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((N, ps, Hkv * D)), jnp.float32)
    tables = np.stack([rng.permutation(N - 1)[:P] for _ in range(3)])
    pt = jnp.asarray(tables[np.maximum(slot, 0)], jnp.int32)
    return q, k, v, pt, jnp.asarray(pos), slot


@pytest.mark.parametrize("window", [None, 12], ids=["full", "window"])
@pytest.mark.parametrize("heads", [(8, 4), (16, 4)], ids=["2 groups", "4 groups"])
def test_the_kernel_matches_the_reference(heads, window):
    """The differential kernel (interpret mode) against the XLA reference
    of the same op: the one-row body (decode rows), the tile body (a chunk),
    pad rows zeroed; each query head's map weighs its two value heads read
    out of the merged page."""
    from automodel_tpu.ops.paged_attention import (
        diff_row_width, ragged_paged_diff_attention, row_segments, row_tile,
    )
    from automodel_tpu.ops.pallas.ragged_paged_attention import (
        paged_attention_diff_kernel,
    )

    Hq, Hkv = heads
    q, k, v, pt, pos, slot = _kernel_case(Hq, Hkv, seed=Hq + (window or 0))
    ref = ragged_paged_diff_attention(q, k, v, pt, pos, scale=0.125,
                                      window=window, impl="xla")
    tile = row_tile(len(slot), diff_row_width(Hq, Hkv, 64))
    seg = row_segments(jnp.asarray(slot), pos, pt, page_size=8, tile=tile,
                       max_segments=len(slot), window=window)
    out = paged_attention_diff_kernel(q, k, v, pt, pos, scale=0.125,
                                      segments=seg, window=window)
    assert out.shape == (len(slot), Hq, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    assert not np.asarray(out)[-2:].any()


def test_the_values_are_diffllamas_pairs():
    """The value a query head weighs: value heads g mod Hkv/2 and
    Hkv/2 + g mod Hkv/2 of its key head g side by side, as DiffLlama builds
    it by repeating the value heads over the query heads, their two halves
    side by side, twice."""
    v = jnp.arange(2 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 3)  # (S, Hkv, d)
    Hq = 16
    rep = jnp.repeat(v, Hq // 8, axis=1)
    want = jnp.concatenate([rep[:, : Hq // 2], rep[:, Hq // 2:]], -1)
    want = jnp.concatenate([want, want], 1)          # (S, Hq, 2d)
    got = jnp.repeat(decoder.diff_values(v), Hq // 8, axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_differential_calls_are_counted_by_impl():
    from automodel_tpu.observability.metrics import default_registry
    from automodel_tpu.ops.paged_attention import ragged_paged_diff_attention

    counter = default_registry().counter("paged_attention_diff_total", impl="xla")
    before = counter.value
    q, k, v, pt, pos, _ = _kernel_case(8, 4, seed=0)
    ragged_paged_diff_attention(q, k, v, pt, pos, scale=0.125)
    assert counter.value == before + 1


def test_what_the_model_cannot_run_is_refused_by_name(case):
    cfg, params = case
    with pytest.raises(NotImplementedError, match="layer L/2"):
        pc.config(hf={**pc.HF, "num_hidden_layers": 6})
    with pytest.raises(NotImplementedError, match="speculative"):
        from automodel_tpu.speculative.serve_draft import SpeculativeConfig

        ServingEngine(own(params), cfg, ServingConfig(
            **SERVING, speculative=SpeculativeConfig(enabled=True, draft_len=2)))
    with pytest.raises(NotImplementedError, match="int8 key/value pages"):
        ServingEngine(own(params), cfg, ServingConfig(
            **SERVING, kv_cache_dtype="int8"))
    with pytest.raises(NotImplementedError, match="cross-attention without"):
        decoder.forward(params, dataclasses.replace(
            cfg, differential_attention=False), jnp.zeros((1, 4), jnp.int32))
