"""JambaForCausalLM (dense sizes): the Mamba-1 mixer, the decoder whose layers
name their mixer, attention without rotary embedding, the ragged selective
scan of a serve step against the whole-sequence one, the checkpoint adapter,
and every refusal. Toy widths, float32, the TIED head (as the 3B sizes are
published), against the plain reference (benchmark/reference/jamba.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.checkpoint.hf_adapter import get_adapter
from automodel_tpu.models.llm import decoder
from automodel_tpu.models.registry import get_model_spec
from automodel_tpu.ops import selective_scan as scan_ops
from tests import jamba_case

#: float32 program against a float32 reference at "highest": what differs is
#: the order of sums (a scan's carry, XLA's matrix products on the CPU)
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def case():
    cfg = jamba_case.config()
    return cfg, jamba_case.init_params(cfg)


def test_family_mapping_names_each_layers_mixer():
    cfg = jamba_case.config()
    assert cfg.layer_ops == ("mamba", "attention", "mamba",
                             "mamba", "attention", "mamba")
    assert cfg.use_rope is False and cfg.holds_state
    assert cfg.tie_word_embeddings and cfg.num_kv_heads == 1
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.resolved_dt_rank) == (64, 8, 4, 4)
    auto = get_model_spec(jamba_case.HF).config_from_hf(
        {**jamba_case.HF, "mamba_dt_rank": "auto", "hidden_size": 2560,
         "num_attention_heads": 20})
    assert auto.resolved_dt_rank == 160 and auto.mamba_d_inner == 5120
    assert decoder.layer_operators(cfg) == (
        ("mamba", 0), ("attention", 0), ("mamba", 1),
        ("mamba", 2), ("attention", 1), ("mamba", 3))
    # a decoder of one kind names nothing, and holds no state
    assert decoder.layer_operators(decoder.TransformerConfig()) is None
    assert not decoder.TransformerConfig().holds_state


def test_published_3b_parameter_count():
    """The published widths: 3,029,337,472 parameters with the tied head."""
    hf = {**jamba_case.HF, "hidden_size": 2560, "intermediate_size": 8192,
          "num_hidden_layers": 28, "num_attention_heads": 20,
          "attn_layer_period": 14, "attn_layer_offset": 7,
          "mamba_d_state": 16, "mamba_dt_rank": 160, "vocab_size": 65536}
    cfg = get_model_spec(hf).config_from_hf(hf)
    shapes = jax.eval_shape(lambda: decoder.init(cfg, jax.random.key(0)))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == 3_029_337_472
    assert [i for i, op in enumerate(cfg.layer_ops) if op == "attention"] == [7, 21]


def test_tree_holds_operators_in_stacks_by_kind(case):
    cfg, params = case
    assert set(params) == {"embed", "layers", "attn_layers", "mamba_layers",
                           "final_norm"}            # tied: no lm_head
    assert set(params["layers"]) == {"input_norm", "post_attn_norm",
                                     "gate_proj", "up_proj", "down_proj"}
    assert params["attn_layers"]["q_proj"]["kernel"].shape[0] == 2
    assert params["mamba_layers"]["A_log"].shape == (4, 8, 64)
    assert params["mamba_layers"]["conv"]["kernel"].shape == (4, 4, 64)
    specs = decoder.param_specs(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda s: 0, specs, is_leaf=lambda s: isinstance(s, tuple))
    ) == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    for spec, leaf in zip(
            jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, tuple)),
            jax.tree.leaves(params)):
        assert len(spec) == leaf.ndim


def test_init_draws_the_familys_delta_range():
    cfg = jamba_case.config()
    m = decoder.init(cfg, jax.random.key(3))["mamba_layers"]
    delta = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 1e-3 * 0.99 <= delta.min() and delta.max() <= 1e-1 * 1.01
    np.testing.assert_allclose(np.exp(np.asarray(m["A_log"]))[0, :, 0],
                               np.arange(1, 9), rtol=1e-6)


def test_forward_matches_reference_with_the_tied_head(case):
    cfg, params = case
    ids = np.random.default_rng(0).integers(0, jamba_case.VOCAB, (2, 40))
    out = np.asarray(decoder.forward(params, cfg, jnp.asarray(ids)))
    ref = jamba_case.reference(params, ids)
    assert np.abs(out - ref).max() < LOGIT_TOL * np.abs(ref).max()
    # the head IS the embedding: an untied reading of the same tree differs
    assert "lm_head" not in params


def test_forward_without_rotary_embedding(case):
    """Position enters through the state-space layers alone: the attention
    layers' q and k are not rotated (a rotated forward differs)."""
    cfg, params = case
    ids = np.random.default_rng(1).integers(0, jamba_case.VOCAB, (1, 24))
    ref = jamba_case.reference(params, ids)
    roped = np.asarray(decoder.forward(
        params, dataclasses.replace(cfg, use_rope=True), jnp.asarray(ids)))
    assert np.abs(roped - ref).max() > 100 * LOGIT_TOL * np.abs(ref).max()


def test_packed_documents_start_from_zeros(case):
    cfg, params = case
    ids = np.random.default_rng(2).integers(0, jamba_case.VOCAB, (2, 24))
    pos = np.concatenate([np.arange(10), np.arange(14)])[None].repeat(2, 0)
    seg = np.concatenate([np.zeros(10), np.ones(14)])[None].repeat(2, 0)
    out = np.asarray(decoder.forward(
        params, cfg, jnp.asarray(ids), positions=jnp.asarray(pos),
        segment_ids=jnp.asarray(seg.astype(np.int32))))
    ref = np.concatenate([jamba_case.reference(params, ids[:, :10]),
                          jamba_case.reference(params, ids[:, 10:])], 1)
    assert np.abs(out - ref).max() < LOGIT_TOL * np.abs(ref).max()


def test_control_precision_moves_the_reference(case):
    _, params = case
    ids = np.random.default_rng(3).integers(0, jamba_case.VOCAB, (1, 32))
    ref = jamba_case.reference(params, ids)
    for control in ("fp8", "int8"):
        low = jamba_case.reference(params, ids, control)
        assert np.abs(low - ref).max() > 1e-2


# -- the ragged scan against the whole-sequence scan --------------------------
# widths the Pallas kernel's rule takes (one 128-lane tile of channels, one
# 8-sublane tile of states): both implementations run every case, the kernel
# interpreted on the CPU
C, N, K, SLOTS = 128, 8, 4, 3
IMPLS = ["xla", "pallas"]


def _inputs(rng, length):
    return dict(
        x=rng.normal(size=(length, C)).astype(np.float32),
        delta=np.exp(rng.normal(-3, 1, (length, C))).astype(np.float32),
        b=rng.normal(size=(length, N)).astype(np.float32),
        c=rng.normal(size=(length, N)).astype(np.float32))


def _whole(seq, kernel, bias, a):
    """(conv output, scan output) of one whole sequence from zeros."""
    u = scan_ops.causal_conv(seq["x"][None], kernel, bias)
    y = scan_ops.selective_scan(u, seq["delta"][None], a, seq["b"][None],
                                seq["c"][None])
    return np.asarray(u[0]), np.asarray(y[0])


def _step(rows, seqs, conv_state, ssm_state, kernel, bias, a, T, impl="xla"):
    """One serve step over `rows` [(slot, sequence index, position)], padded
    to T rows. Returns (conv out, scan out, new states) for the real rows."""
    n = len(rows)
    slot = np.full(T, -1, np.int32)
    pos = np.full(T, -1, np.int32)
    feed = {k: np.zeros((T,) + v.shape[1:], np.float32)
            for k, v in seqs[0].items()}
    for i, (s, q, p) in enumerate(rows):
        slot[i], pos[i] = s, p
        for k in feed:
            feed[k][i] = seqs[q][k][p]
    trash = ssm_state.shape[0] - 1
    runs = scan_ops.step_runs(jnp.asarray(slot), jnp.asarray(pos), trash=trash)
    u, conv_state = scan_ops.ragged_conv(
        jnp.asarray(feed["x"]), kernel, bias, conv_state, jnp.asarray(pos), runs)
    y, ssm_state = scan_ops.ragged_selective_scan(
        u, jnp.asarray(feed["delta"]), a, jnp.asarray(feed["b"]),
        jnp.asarray(feed["c"]), ssm_state, runs, impl=impl)
    return np.asarray(u)[:n], np.asarray(y)[:n], conv_state, ssm_state, runs


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("chunk", [1, 2, 3, 5, 8])
def test_ragged_scan_matches_whole_sequence_at_every_chunk_boundary(chunk, impl):
    """Three sequences of unlike length through steps of interleaved runs
    (a chunk of one, then one decode-like row of each other), every chunk
    boundary from inside the convolution's reach to past it; the states
    start as JUNK, in the slots and in the trash slot: a run that starts at
    position 0 must not read them."""
    rng = np.random.default_rng(chunk)
    kernel = jnp.asarray(rng.normal(size=(K, C)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(C,)).astype(np.float32))
    a = -jnp.exp(jnp.asarray(rng.normal(size=(N, C)).astype(np.float32)))
    seqs = [_inputs(rng, n) for n in (13, 7, 10)]
    want = [_whole(s, kernel, bias, a) for s in seqs]
    conv_state = jnp.asarray(rng.normal(size=(K - 1, SLOTS + 1, C)) * 1e3,
                             jnp.float32)
    ssm_state = jnp.asarray(rng.normal(size=(SLOTS + 1, N, C)) * 1e3,
                            jnp.float32)
    fed = [0, 0, 0]
    got = [([], []) for _ in seqs]
    T = chunk + 4
    while any(f < len(s["x"]) for f, s in zip(fed, seqs)):
        rows = []
        for q in np.argsort(fed):          # the least fed gets the chunk
            take = chunk if not rows else 1
            for p in range(fed[q], min(fed[q] + take, len(seqs[q]["x"]))):
                rows.append((q, q, p))      # slot q holds sequence q
        u, y, conv_state, ssm_state, runs = _step(
            rows, seqs, conv_state, ssm_state, kernel, bias, a, T, impl)
        for (s, q, p), u_t, y_t in zip(rows, u, y):
            assert p == fed[q]
            got[q][0].append(u_t)
            got[q][1].append(y_t)
            fed[q] += 1
    for (u_want, y_want), (u_got, y_got) in zip(want, got):
        np.testing.assert_allclose(np.stack(u_got), u_want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.stack(y_got), y_want, rtol=1e-4, atol=1e-5)


def test_step_runs_from_slot_and_pos():
    slot = jnp.asarray([2, 2, 2, 0, 1, 1, -1, -1], jnp.int32)
    pos = jnp.asarray([5, 6, 7, 9, 0, 1, -1, -1], jnp.int32)
    runs = jax.tree.map(np.asarray, scan_ops.step_runs(slot, pos, trash=3))
    assert runs["start"].tolist() == [1, 0, 0, 1, 1, 0, 1, 1]
    assert runs["end"].tolist() == [0, 0, 1, 1, 0, 1, 1, 1]
    assert runs["offset"].tolist() == [0, 1, 2, 0, 0, 1, 0, 0]
    assert runs["first_pos"].tolist()[:6] == [5, 5, 5, 9, 0, 0]
    assert runs["read"].tolist() == [2, 2, 2, 0, 1, 1, 3, 3]
    assert runs["write"].tolist() == [3, 3, 2, 0, 3, 1, 3, 3]
    # two decode rows of different slots at consecutive positions are two runs
    two = scan_ops.step_runs(jnp.asarray([0, 1], jnp.int32),
                             jnp.asarray([4, 5], jnp.int32), trash=3)
    assert np.asarray(two["start"]).tolist() == [1, 1]


def test_run_list_compacts_the_runs_and_leaves_the_pads_out():
    """What the kernel walks: a column a run (first row, length, slot, from
    zeros), the step's runs first; a pad row is no run, and a step of pads
    alone has one run, of row 0 into the trash slot."""
    from automodel_tpu.ops.pallas.selective_scan import run_list

    slot = jnp.asarray([2, 2, 2, 0, -1, 1, 1, -1], jnp.int32)
    pos = jnp.asarray([5, 6, 7, 9, -1, 0, 1, -1], jnp.int32)
    table, count = run_list(scan_ops.step_runs(slot, pos, trash=3), trash=3)
    assert int(count) == 3
    assert np.asarray(table)[:, :3].tolist() == [
        [0, 3, 5], [3, 1, 2], [2, 0, 1], [0, 0, 1]]
    pads = jnp.full((4,), -1, jnp.int32)
    table, count = run_list(scan_ops.step_runs(pads, pads, trash=3), trash=3)
    assert int(count) == 1
    assert np.asarray(table)[:, 0].tolist() == [0, 1, 3, 1]


#: the steps the kernel must take, as [(slot, first position, rows)] in plan
#: order over 8 slots and 24 rows: name -> runs
MIXES = {
    "decode only": [(s, 3 + s, 1) for s in range(8)],
    "the cell's mix": [(s, 9 + s, 1) for s in range(5)] + [(5, 4, 7), (7, 0, 6)],
    "one run of the whole budget": [(2, 6, 24)],
    "pads only": [],
    "from position 0 in a slot that carried state": [(4, 0, 3), (1, 0, 1)],
    "a long run, a decode row, no pad": [(6, 2, 23), (0, 11, 1)],
}


@pytest.mark.parametrize("mix", MIXES.values(), ids=MIXES.keys())
def test_kernel_matches_the_xla_form_on_the_mixes_a_step_can_hold(mix):
    """The Pallas kernel (interpreted) against the XLA form on the same
    step, to float32 rounding: y of the real rows and every slot's state.
    The states start as junk: a run from position 0 must not read its
    slot's, a slot absent from the step stays BIT-identical, and pad rows
    write nothing but the trash slot."""
    slots, T = 8, 24
    rng = np.random.default_rng(len(mix))
    seq = _inputs(rng, 64)
    a = -jnp.exp(jnp.asarray(rng.normal(size=(N, C)).astype(np.float32)))
    state = jnp.asarray(rng.normal(size=(slots + 1, N, C)) * 1e3, jnp.float32)
    rows = [(s, p) for s, p0, n in mix for p in range(p0, p0 + n)]
    slot = np.full(T, -1, np.int32)
    pos = np.full(T, -1, np.int32)
    slot[:len(rows)], pos[:len(rows)] = np.asarray(rows, np.int32).reshape(-1, 2).T
    runs = scan_ops.step_runs(jnp.asarray(slot), jnp.asarray(pos), trash=slots)
    feed = {k: jnp.asarray(v[:T]) for k, v in seq.items()}
    (y_ref, state_ref), (y, new) = (
        scan_ops.ragged_selective_scan(
            feed["x"], feed["delta"], a, feed["b"], feed["c"], state, runs,
            impl=impl)
        for impl in IMPLS)
    n = len(rows)
    scale = max(1.0, float(np.abs(np.asarray(y_ref)[:n]).max(initial=0)))
    np.testing.assert_allclose(np.asarray(y)[:n], np.asarray(y_ref)[:n],
                               rtol=1e-5, atol=1e-6 * scale)
    np.testing.assert_allclose(np.asarray(new)[:slots],
                               np.asarray(state_ref)[:slots], rtol=1e-5,
                               atol=1e-3)
    absent = sorted(set(range(slots)) - {s for s, _, _ in mix})
    np.testing.assert_array_equal(np.asarray(new)[absent],
                                  np.asarray(state)[absent])
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("impl", IMPLS)
def test_a_reused_slot_needs_no_reset(impl):
    """A slot's next holder starts at position 0 and reads zeros whatever
    the last holder left; pad rows write the trash slot alone."""
    rng = np.random.default_rng(9)
    kernel = jnp.asarray(rng.normal(size=(K, C)).astype(np.float32))
    bias = jnp.zeros((C,), jnp.float32)
    a = -jnp.exp(jnp.asarray(rng.normal(size=(N, C)).astype(np.float32)))
    first, second = _inputs(rng, 6), _inputs(rng, 5)
    conv_state = jnp.zeros((K - 1, SLOTS + 1, C), jnp.float32)
    ssm_state = jnp.zeros((SLOTS + 1, N, C), jnp.float32)
    _, _, conv_state, ssm_state, _ = _step(
        [(1, 0, p) for p in range(6)], [first], conv_state, ssm_state,
        kernel, bias, a, 8, impl)
    assert np.abs(np.asarray(ssm_state[1])).max() > 0
    before = np.asarray(ssm_state)
    u, y, conv_state, ssm_state, _ = _step(
        [(1, 0, p) for p in range(5)], [second], conv_state, ssm_state,
        kernel, bias, a, 8, impl)
    u_want, y_want = _whole(second, kernel, bias, a)
    np.testing.assert_allclose(u, u_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, y_want, rtol=1e-4, atol=1e-5)
    # slots 0 and 2 were never a run's: untouched by both steps
    np.testing.assert_array_equal(np.asarray(ssm_state)[[0, 2]], before[[0, 2]])


# -- the adapter ---------------------------------------------------------------
def test_checkpoint_keys_round_trip(case):
    cfg, params = case
    spec = get_model_spec(jamba_case.HF)
    adapter = get_adapter(spec.adapter_name, cfg, **spec.adapter_kwargs)
    state = dict(adapter.to_hf(params))
    assert "lm_head.weight" not in state          # tied
    assert state["model.final_layernorm.weight"].shape == (32,)
    assert state["model.layers.0.mamba.conv1d.weight"].shape == (64, 1, 4)
    assert state["model.layers.0.mamba.A_log"].shape == (64, 8)
    assert state["model.layers.0.mamba.in_proj.weight"].shape == (128, 32)
    assert state["model.layers.0.mamba.dt_proj.bias"].shape == (64,)
    assert state["model.layers.1.self_attn.k_proj.weight"].shape == (8, 32)
    assert "model.layers.1.mamba.in_proj.weight" not in state
    assert "model.layers.0.self_attn.q_proj.weight" not in state
    # layer 4 is the SECOND attention layer, layer 5 the FOURTH mixer
    np.testing.assert_array_equal(
        state["model.layers.4.self_attn.o_proj.weight"],
        np.asarray(params["attn_layers"]["o_proj"]["kernel"][1]).T)
    np.testing.assert_array_equal(
        state["model.layers.5.mamba.conv1d.weight"][:, 0, :],
        np.asarray(params["mamba_layers"]["conv"]["kernel"][3]).T)
    np.testing.assert_array_equal(
        state["model.layers.5.pre_ff_layernorm.weight"],
        np.asarray(params["layers"]["post_attn_norm"]["scale"][5]))
    back = adapter.from_hf(state.__getitem__)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- refusals ------------------------------------------------------------------
REFUSED = {
    "expert layers": ({"num_experts": 16}, "num_experts=16"),
    "projection bias": ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    "no conv bias": ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    "sliding window": ({"sliding_window": 128}, "sliding window"),
}


@pytest.mark.parametrize("change,said", REFUSED.values(), ids=REFUSED.keys())
def test_family_refuses_by_name(change, said):
    hf = {**jamba_case.HF, **change}
    with pytest.raises(NotImplementedError, match=said):
        get_model_spec(hf).config_from_hf(hf)


def test_unknown_operator_and_mixed_features_are_refused(case):
    cfg, params = case
    with pytest.raises(NotImplementedError, match="no layer operator"):
        decoder.layer_operators(dataclasses.replace(
            cfg, layer_ops=("mamba", "conv") * 3))
    for change in (dict(attention_type="mla"), dict(num_passes=2),
                   dict(sliding_window=8)):
        with pytest.raises(NotImplementedError, match="layer_ops"):
            decoder.init(dataclasses.replace(cfg, **change), jax.random.key(0))
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="aux-hidden"):
        decoder.forward(params, cfg, ids, return_aux_hidden=(1,))


def test_generate_refuses_by_name(case):
    from automodel_tpu.inference.generate import GenerateConfig, generate

    cfg, params = case
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        generate(params, cfg, jnp.zeros((1, 4), jnp.int32), jax.random.key(0),
                 GenerateConfig(max_new_tokens=2))
