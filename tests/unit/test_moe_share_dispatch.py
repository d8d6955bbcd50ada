"""A layer that holds a share of its experts works on its live pairs alone.

`experts_forward_dropless` runs everything after the sort over the first
`share_rows(cfg, T*K)` sorted (row, expert) pairs when the live pairs fit
there, else over all of them, one `lax.cond` between the two: the same
function of the rows either way. Pinned here at two deployments' ratios
(8 of 128 experts held and 16 of 256, top-8, at small widths): the compact
path gives what every pair gives, bit for bit in float32, with the live
pairs below the bound, at it, one above it (the whole path) and none; the
run-time words say which path ran and how many pairs were live; a layer
that holds all its experts traces as it always did; and a serve step of a
share counts its layer-runs by path and serves what the whole path serves.
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.moe import experts
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.experts import (
    _EXPERT_ACT,
    experts_forward_dropless,
    gated_combine,
    init_experts,
    share_rows,
)
from automodel_tpu.moe.gate import gate_forward
from automodel_tpu.moe.layer import init_moe, moe_forward
from automodel_tpu.ops.grouped_matmul import grouped_matmul
from automodel_tpu.serving import Request, ServingConfig, ServingEngine
from tests import exaone_case as ec
from tests.serving_params import own

H, T = 32, 128

#: (routed, held, first held, top-k): K-EXAONE's and MiMo-V2-Flash's cuts
RATIOS = {"8 of 128": (128, 8, 40, 8), "16 of 256": (256, 16, 96, 8)}


def _whole_path_as_before(params, cfg, x, weights, indices, mesh_ctx=None):
    """The dispatch as it stood before the compact path, over every pair
    whatever the routing: the whole path's reference, and the jaxpr a layer
    that holds all its experts still traces."""
    T, H = x.shape
    K = cfg.experts_per_token
    E = cfg.num_held
    dtype = x.dtype

    flat_expert = indices.reshape(T * K)
    if not cfg.holds_all_experts:
        local = flat_expert - cfg.first_held_expert
        flat_expert = jnp.where((local >= 0) & (local < E), local, E)
    sort_idx = jnp.argsort(flat_expert, stable=True)
    token_of = sort_idx // K
    expert_of = jnp.take(flat_expert, sort_idx)
    xs = jnp.take(x, token_of, axis=0)
    group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)

    safe_expert = jnp.clip(expert_of, 0, E - 1)
    gmm = functools.partial(grouped_matmul, mesh_ctx=mesh_ctx)
    u = gmm(xs, params["up_proj"]["kernel"].astype(dtype), group_sizes)
    if "bias" in params["up_proj"]:
        u = u + jnp.take(params["up_proj"]["bias"].astype(dtype), safe_expert, axis=0)
    if cfg.gated_experts:
        g = gmm(xs, params["gate_proj"]["kernel"].astype(dtype), group_sizes)
        if "bias" in params["gate_proj"]:
            g = g + jnp.take(params["gate_proj"]["bias"].astype(dtype), safe_expert, axis=0)
        h_in = gated_combine(g, u, cfg.expert_activation, cfg.swiglu_limit)
    else:
        h_in = _EXPERT_ACT[cfg.expert_activation](u)
    y = gmm(h_in, params["down_proj"]["kernel"].astype(dtype), group_sizes)
    if "bias" in params["down_proj"]:
        y = y + jnp.take(params["down_proj"]["bias"].astype(dtype), safe_expert, axis=0)

    w_sorted = jnp.take(weights.reshape(T * K), sort_idx, axis=0).astype(dtype)
    contrib = y * w_sorted[:, None]
    return jnp.zeros((T, H), dtype).at[token_of].add(contrib)


def _share(ratio):
    routed, held, first, k = RATIOS[ratio]
    return MoEConfig(
        n_routed_experts=routed, n_held_experts=held, first_held_expert=first,
        experts_per_token=k, moe_intermediate_size=16, score_func="sigmoid",
        dispatcher="dropless")


def _routing(cfg, live, seed):
    """(T, K) experts, distinct within a row, exactly `live` pairs of them
    held here and spread over the rows, the rest held elsewhere."""
    rng = np.random.default_rng(seed)
    K, held, first = cfg.experts_per_token, cfg.num_held, cfg.first_held_expert
    elsewhere = np.setdiff1d(np.arange(cfg.n_routed_experts),
                             first + np.arange(held))
    idx = np.stack([rng.choice(elsewhere, K, replace=False) for _ in range(T)])
    for pair in rng.permutation(T * K)[:live]:
        # slot s of a row takes held expert s (K <= held): distinct in a row
        row, slot = divmod(int(pair), K)
        idx[row, slot] = first + slot
    return jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("where", ["below", "at", "one above", "none"])
@pytest.mark.parametrize("ratio", sorted(RATIOS))
def test_compact_path_equals_the_whole_path(ratio, where):
    cfg = _share(ratio)
    bound = share_rows(cfg, T * cfg.experts_per_token)
    assert bound == 128 < T * cfg.experts_per_token
    live = {"below": bound // 2, "at": bound, "one above": bound + 1,
            "none": 0}[where]
    params = init_moe(cfg, H, jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (1, T, H), jnp.float32)
    forced = _routing(cfg, live, seed=live)
    here = (forced >= cfg.first_held_expert) & (
        forced < cfg.first_held_expert + cfg.num_held)
    assert int(here.sum()) == live

    out, _, stats = jax.jit(
        lambda p, x, f: moe_forward(p, cfg, x, forced_indices=f))(
        params, x, forced)
    assert int(stats["share_live"]) == live
    assert bool(stats["share_whole"]) == (live > bound)

    flat = x.reshape(T, H)
    weights, indices, _, _ = gate_forward(params["gate"], cfg, flat, None, forced)
    want = jax.jit(lambda p, x, w, i: _whole_path_as_before(p, cfg, x, w, i))(
        params["experts"], flat, weights, indices)
    np.testing.assert_array_equal(np.asarray(out).reshape(T, H), np.asarray(want))
    assert (float(jnp.abs(want).max()) > 0) == bool(live)


def test_the_bound_is_twice_the_expected_live_pairs_in_whole_tiles():
    def share(routed, held, k=8):
        return MoEConfig(n_routed_experts=routed, n_held_experts=held,
                         experts_per_token=k)

    # K-EXAONE's 1,024-row step, MiMo-V2-Flash's 256-row step
    assert share_rows(share(128, 8), 1024 * 8) == 1024
    assert share_rows(share(256, 16), 256 * 8) == 256
    # a tile at the least; none where the bound leaves no pair out
    assert share_rows(share(128, 8), 256 * 8) == 256
    assert share_rows(share(128, 8), 130 * 8) == 256
    assert share_rows(share(128, 8), 16 * 8) is None
    assert share_rows(share(8, 2, k=2), 24) is None
    assert share_rows(share(128, 64), 1024 * 8) is None
    assert share_rows(MoEConfig(n_routed_experts=128, experts_per_token=8),
                      1024 * 8) is None


def test_a_layer_that_holds_all_its_experts_traces_as_before():
    cfg = MoEConfig(n_routed_experts=8, experts_per_token=2,
                    moe_intermediate_size=16, dispatcher="dropless")
    params = init_experts(cfg, H, jax.random.key(3))
    x = jnp.zeros((T, H), jnp.float32)
    w = jnp.zeros((T, 2), jnp.float32)
    idx = jnp.zeros((T, 2), jnp.int32)
    now = jax.make_jaxpr(
        lambda *a: experts_forward_dropless(params, cfg, *a))(x, w, idx)
    before = jax.make_jaxpr(
        lambda *a: _whole_path_as_before(params, cfg, *a))(x, w, idx)
    assert str(now) == str(before)
    assert "cond" not in str(now)


def _share_engine_run(monkeypatch, bound):
    """A toy K-EXAONE share (2 of 8 experts held, top-2) served with the
    compact path's rows pinned to `bound` (None: the whole path alone). Returns
    (tokens and log-probabilities of every step, the whole / compact runs,
    the live maximum and the real (not pad) rows of each step, the
    engine)."""
    monkeypatch.setattr(experts, "share_rows", lambda cfg, pairs: (
        None if cfg.holds_all_experts or bound is None else bound))
    hf = ec.share_hf(2, first=4)
    cfg = ec.config(hf)
    engine = ServingEngine(own(ec.init_params(cfg, seed=2, hf=hf)), cfg,
                           ServingConfig(page_size=4, num_pages=64, max_slots=3,
                                         pages_per_slot=16, token_budget=12,
                                         prefill_chunk=6))
    reg = engine.obs.registry
    runs = {p: reg.counter("serve_moe_share_runs_total", path=p)
            for p in ("whole", "compact")}
    seen, words = [], []
    inner = engine.run_step

    def run_step(plan):
        before = {p: c.value for p, c in runs.items()}
        out = inner(plan)
        seen.append((np.array(out[0]), np.array(out[1])))
        words.append(({p: c.value - before[p] for p, c in runs.items()},
                      reg.gauge("serve_moe_share_live_max").value,
                      int((plan.slot >= 0).sum())))
        return out

    engine.run_step = run_step
    rng = np.random.default_rng(50)
    engine.serve_batch([Request(prompt=rng.integers(0, ec.VOCAB, n).tolist(),
                                max_new_tokens=5) for n in (17, 9, 4)])
    return seen, words, engine


def test_a_share_step_counts_its_layer_runs_by_path(monkeypatch):
    want, whole_words, _ = _share_engine_run(monkeypatch, None)
    # every step's 7 expert layers ran the whole path
    assert whole_words and all(w == {"whole": 7, "compact": 0}
                               for w, *_ in whole_words)
    bound = 6
    got, words, engine = _share_engine_run(monkeypatch, bound)
    # the same tokens; the log-probabilities to an ulp or two: the step's
    # compiler fuses around the cond's branches otherwise (so it reads even
    # where both branches run every pair), the layer's own output is bit for
    # bit the whole path's (above)
    assert len(got) == len(want)
    for (tok, lp), (tok_w, lp_w) in zip(got, want):
        np.testing.assert_array_equal(tok, tok_w)
        np.testing.assert_allclose(lp, lp_w, rtol=1e-6, atol=0)
    # both paths ran; a step ran the whole path in some layer exactly when
    # its largest live count passed the bound
    for w, live, _rows in words:
        assert w["whole"] + w["compact"] == 7
        assert (w["whole"] > 0) == (live > bound)
    assert {w["whole"] > 0 for w, *_ in words} == {True, False}
    assert [live for _w, live, _r in words] == [
        live for _w, live, _r in whole_words]
    assert engine.step_cache_size() == 1


def test_a_share_step_routes_its_pad_rows_to_no_expert(monkeypatch):
    """A step's pad rows are all alike: routed, each would add the same held
    experts' pairs to every layer's live ones. Masked, a layer's live pairs
    are its real rows' alone, at most top-k of them a row."""
    _, words, _ = _share_engine_run(monkeypatch, None)
    assert any(rows < 12 for *_, rows in words)  # steps with pad rows
    for _w, live, rows in words:
        assert live <= 2 * rows


#: sha256 of the lowered (StableHLO) serve step of `ec.config()` (an expert
#: decoder that holds all 8 of its experts) at the geometry below, with jax
#: 0.9.0 on the CPU: the step as it was before the compact path, which such a
#: model never traces. A PR that changes this step on purpose updates the
#: digest and says why.
ALL_EXPERTS_STEP_SHA256 = (
    "b3a3f1df57a232fc4230959a3f4ff4201909def7e3d88f1a3742b70b444a066b")


def test_a_model_that_holds_all_its_experts_keeps_its_output():
    cfg = ec.config()
    engine = ServingEngine(own(ec.init_params(cfg)), cfg, ServingConfig(
        page_size=4, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=8))
    text = engine.lower_step().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == ALL_EXPERTS_STEP_SHA256
    assert [f[0] for f in engine._out_layout] == ["tokens", "logprobs"]
    engine.serve_batch([Request(prompt=[3, 5, 7], max_new_tokens=3)])
    snap = engine.obs.registry.snapshot()
    assert not [k for k in snap if k.startswith("serve_moe_share")]
