"""Compiles for a DESCRIBED v5e chip (none attached): what interpret mode on
the CPU cannot show. The TPU's compiler is installed here and compiles for a
topology that is described, not attached; nothing runs, so these prove that
the chip's compiler takes a kernel at its real widths, never a result or a
time. Kept in ONE file, the topology described inside a fixture: only the
worker that is given this file loads the TPU's library.

Why it is here: `paged_attention_gqa` passed every interpret-mode test and
the TPU compiler refused it (LLO_CHECK `ProducesVreg`) for a model WITHOUT
grouping (16 heads over 16 key/value heads, Ouro-2.6B's), where the causal
mask's compare had a one-wide sublane axis.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to prove
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branch(monkeypatch):
    """Point the code that asks for the backend at its TPU branch: the
    process runs on the CPU, the program is compiled for the chip."""
    import automodel_tpu.ops.attention as attention
    import automodel_tpu.ops.pallas.ragged_paged_attention as rpa

    import automodel_tpu.ops.grouped_matmul as gmm
    import automodel_tpu.ops.pallas.grouped_matmul as gmm_kernel
    import automodel_tpu.ops.pallas.selective_scan as scan_kernel
    import automodel_tpu.ops.selective_scan as scan

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    monkeypatch.setattr(gmm, "_on_tpu", lambda: True)
    monkeypatch.setattr(gmm_kernel, "_interpret", lambda: False)
    monkeypatch.setattr(scan, "_on_tpu", lambda: True)
    monkeypatch.setattr(scan_kernel, "_interpret", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield rpa
    jax.config.update("jax_enable_compilation_cache", cache)


def _shapes(sharding, *trees):
    """The trees' arrays as shapes on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        trees)


def _one_row_bodies():
    """What `paged_attention_one_row_body_total` reads now, by label."""
    from automodel_tpu.observability.metrics import default_registry

    return {body: default_registry().counter(
        "paged_attention_one_row_body_total", scores=body).value
        for body in ("mxu", "vpu")}


#: (rows, heads, key/value heads, head width, pages, page size, pages a
#: slot, slots)
WIDTHS = {
    "ouro_2_6b: no grouping": (48, 16, 16, 128, 84, 64, 10, 24),
    "grouped 4:1": (48, 32, 8, 128, 84, 64, 10, 24),
    "one key/value head": (48, 8, 1, 128, 84, 64, 10, 24),
    "jamba2_3b: 20 heads over one": (256, 20, 1, 128, 3072, 64, 24, 128),
    "k_exaone: 64 heads over 8": (1024, 64, 8, 128, 4096, 128, 64, 64),
    "k_exaone at tp 4, a rank's: 16 heads over 2": (
        1024, 16, 2, 128, 4096, 128, 64, 64),
}


def _segments(rows, slots, pages_a_slot, row_width, shape):
    """The step's row segments as the engine derives them, their arrays
    shapes: the kernels compile with both bodies, a decode row's and a
    chunk's, and a run-time grid bound, whatever a step holds. Returns
    (tile, segments at most, the two shapes, shapes → RowSegments)."""
    from automodel_tpu.ops.paged_attention import (
        RowSegments, max_row_segments, row_tile,
    )

    tile = row_tile(rows, row_width)
    most = max_row_segments(rows, slots, tile)
    shapes = (shape((6, most * pages_a_slot), jnp.int32), shape((), jnp.int32))
    return tile, most, shapes, lambda b, c: RowSegments(tile, b, c)


def _gqa_call(rpa, widths, quant, s):
    """(function, its arguments as `s(shape, dtype)` shapes) of the paged
    GQA call at `widths`, its row segments derived as the engine does."""
    T, Hq, Hkv, D, N, ps, P, S = widths
    q = s((T, Hq, D), jnp.bfloat16)
    pages = s((N + 1, ps, Hkv, D), jnp.int8 if quant else jnp.bfloat16)
    tables, pos = s((T, P), jnp.int32), s((T,), jnp.int32)
    tile, most, seg, segments = _segments(T, S, P, 2 * Hq * D, s)
    assert (tile, most) == (32, min(T, S + T // 32))
    if quant:
        scales = s((N + 1, ps), jnp.float32)
        fn = lambda q, k, v, ks, vs, pt, pos, *seg: rpa.paged_attention_quant_kernel(  # noqa: E731
            q, k, v, ks, vs, pt, pos, scale=D ** -0.5, segments=segments(*seg))
        return fn, (q, pages, pages, scales, scales, tables, pos, *seg)
    fn = lambda q, k, v, pt, pos, *seg: rpa.paged_attention_kernel(  # noqa: E731
        q, k, v, pt, pos, scale=D ** -0.5, segments=segments(*seg))
    return fn, (q, pages, pages, tables, pos, *seg)


@pytest.mark.parametrize("widths", WIDTHS.values(), ids=WIDTHS.keys())
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_attention_gqa_compiles_for_the_chip(one_chip, tpu_branch, widths, quant):
    _T, Hq, Hkv, _D, N, ps, _P, _S = widths
    fn, args = _gqa_call(
        tpu_branch, widths, quant,
        lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=one_chip))
    before = _one_row_bodies()
    compiled = jax.jit(fn).lower(*args).compile()
    assert "paged_attention_gqa" in compiled.as_text()
    body = "mxu" if Hq > Hkv else "vpu"
    assert _one_row_bodies() == {**before, body: before[body] + 1}
    # the page handed in as a (ps x Hkv, D) matrix is a view of the pool,
    # never a copy of it (int8 pages of 2 key/value heads apart, which XLA
    # pads four-fold and relays for either body, the parent's too)
    if not (quant and 1 < Hkv < 4):
        assert not [ln for ln in compiled.as_text().splitlines()
                    if " copy(" in ln
                    and f"[{N + 1},{ps}" in ln.split(" copy(")[0]]


#: sha256 of the GQA kernel's body (the jaxpr inside its `pallas_call`) at
#: Ouro-2.6B's shape, 16 heads over 16, as PR 36's tree (7a1e9a3) traced it
#: with jax 0.9.0, over bf16 and over int8 pages. A model WITHOUT grouping
#: keeps the one-row body PR 30 measured for it: a PR that changes that body
#: on purpose updates the digests and says what the chip read. (The jaxpr
#: carries no source line; the lowered text does, and an edit above the
#: kernel would move a hash of that.)
UNGROUPED_BODY_SHA256 = {
    False: "798b8aaae39c11a6e35adb721e94187787ab8c4d5458dfe5552e49fd1b83a354",
    True: "bb8a9559db10f85f676b82327e6987fcefbc37a9377e06167b57b7bd18492f5f",
}


def _pallas_call_of(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for inner in eqn.params.values():
            found = hasattr(inner, "jaxpr") and _pallas_call_of(inner.jaxpr)
            if found:
                return found
    return None


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_the_ungrouped_kernel_body_is_unchanged(tpu_branch, quant):
    import hashlib

    fn, args = _gqa_call(tpu_branch, WIDTHS["ouro_2_6b: no grouping"], quant,
                         jax.ShapeDtypeStruct)
    call = _pallas_call_of(jax.make_jaxpr(fn)(*args).jaxpr)
    assert call.params["name"] == "paged_attention_gqa" + "_int8" * quant
    body = str(call.params["jaxpr"])
    assert hashlib.sha256(body.encode()).hexdigest() == (
        UNGROUPED_BODY_SHA256[quant])


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_attention_gqa_with_a_window_compiles_for_the_chip(
        one_chip, tpu_branch, quant):
    """K-EXAONE's window layers' call at the cell's geometry: 1,024 rows of
    64 heads over 8 of 128, a window of 128 tokens, the cache a ring of 8
    pages of 128 tokens for each of 64 slots and the trash slot; the block
    list (64 + 1024 / 32) segments x 8 pages of scalar memory, not x 64."""
    from automodel_tpu.ops.paged_attention import (
        RowSegments, max_row_segments, row_tile,
    )

    T, Hq, Hkv, D, ps, P, S, R, W = 1024, 64, 8, 128, 128, 64, 64, 8, 128

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = s((T, Hq, D), jnp.bfloat16)
    ring = s(((S + 1) * R, ps, Hkv, D), jnp.int8 if quant else jnp.bfloat16)
    tables, pos = s((T, P), jnp.int32), s((T,), jnp.int32)
    tile = row_tile(T, 2 * Hq * D)
    most = max_row_segments(T, S, tile)
    assert (tile, most) == (32, 96)
    seg = (s((6, most * R), jnp.int32), s((), jnp.int32))
    kw = dict(scale=D ** -0.5, window=W)
    if quant:
        scales = s(((S + 1) * R, ps), jnp.float32)
        fn = lambda q, k, v, ks, vs, pt, pos, b, c: tpu_branch.paged_attention_quant_kernel(  # noqa: E731
            q, k, v, ks, vs, pt, pos, segments=RowSegments(tile, b, c), **kw)
        args = (q, ring, ring, scales, scales, tables, pos, *seg)
    else:
        fn = lambda q, k, v, pt, pos, b, c: tpu_branch.paged_attention_kernel(  # noqa: E731
            q, k, v, pt, pos, segments=RowSegments(tile, b, c), **kw)
        args = (q, ring, ring, tables, pos, *seg)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "paged_attention_window_gqa" in compiled.as_text()


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_attention_mla_compiles_for_the_chip(one_chip, tpu_branch, quant):
    """Moonlight's cell: 256 rows, 16 heads over one shared latent of 512
    + 64 rope, 2,049 pages of 64, 20 pages and 64 slots."""
    T, n, r, dr, N, ps, P, S = 256, 16, 512, 64, 2049, 64, 20, 64

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    dtype = jnp.int8 if quant else jnp.bfloat16
    qa, qr = s((T, n, r), jnp.bfloat16), s((T, n, dr), jnp.bfloat16)
    c, kr = s((N, ps, r), dtype), s((N, ps, dr), dtype)
    tables, pos = s((T, P), jnp.int32), s((T,), jnp.int32)
    tile, most, seg, segments = _segments(T, S, P, n * (2 * r + dr), s)
    assert (tile, most) == (32, 72)
    if quant:
        scales = s((N, ps), jnp.float32)
        fn = lambda qa, qr, c, kr, cs, krs, pt, pos, *seg: (  # noqa: E731
            tpu_branch.paged_mla_attention_quant_kernel(
                qa, qr, c, kr, cs, krs, pt, pos, scale=192 ** -0.5,
                segments=segments(*seg)))
        args = (qa, qr, c, kr, scales, scales, tables, pos, *seg)
    else:
        fn = lambda qa, qr, c, kr, pt, pos, *seg: (  # noqa: E731
            tpu_branch.paged_mla_attention_kernel(
                qa, qr, c, kr, pt, pos, scale=192 ** -0.5,
                segments=segments(*seg)))
        args = (qa, qr, c, kr, tables, pos, *seg)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "paged_attention_mla" in compiled.as_text()


#: (rows, k, n, experts): the sorted rows of one expert layer's product
EXPERT_CALLS = {
    "moonlight: gate / up": (1536, 2048, 1408, 64),
    "moonlight: down": (1536, 1408, 2048, 64),
    "moonlight: 64 decode rows": (384, 2048, 1408, 64),
    "qwen3-30b-a3b: gate / up": (2048, 2048, 768, 128),
}


@pytest.mark.parametrize("call", EXPERT_CALLS.values(), ids=EXPERT_CALLS.keys())
def test_grouped_matmul_compiles_for_the_chip(one_chip, tpu_branch, call):
    """The dispatcher's own choice for a serve step's call: the kernel, at
    the tiles its rule gives (whole (k, n) slabs: 5.8 MB each, two in
    flight, past the compiler's default 16 MiB of VMEM)."""
    from automodel_tpu.ops.grouped_matmul import grouped_matmul, tiles

    m, k, n, E = call

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert tiles(m, k, n, jnp.bfloat16) == (128, n)
    compiled = jax.jit(grouped_matmul).lower(
        s((m, k), jnp.bfloat16), s((E, k, n), jnp.bfloat16),
        s((E,), jnp.int32)).compile()
    assert "grouped_matmul" in compiled.as_text()
    assert "ragged-dot" not in compiled.as_text()


#: (rows, channels, states, slots, the rule's channel block) of one
#: state-space layer's call
SCAN_CALLS = {
    "jamba2_3b: a 256-row step": (256, 5120, 16, 128, 5120),
    "jamba2_3b: a 512-row step, two channel blocks": (512, 5120, 16, 128, 2560),
    "mamba-130m's widths, 64 rows": (64, 1536, 16, 16, 1536),
}


@pytest.mark.parametrize("call", SCAN_CALLS.values(), ids=SCAN_CALLS.keys())
def test_selective_scan_compiles_for_the_chip(one_chip, tpu_branch, call):
    """The dispatcher's own choice for a serve step's call: the kernel, at
    the channel block its rule gives (Jamba2-3B's: a run's whole 320 KiB
    state a block, the rows' inputs resident: 46 MB of VMEM, past the
    compiler's default 16 MiB; twice the rows, half the channels), the
    state updated in place."""
    from automodel_tpu.ops.selective_scan import (
        channel_block, ragged_selective_scan, step_runs,
    )

    T, C, N, slots, cb = call

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(u, delta, a, b, c, state, slot, pos):
        return ragged_selective_scan(
            u, delta, a, b, c, state, step_runs(slot, pos, trash=slots))

    assert channel_block(T, C, N) == cb
    compiled = jax.jit(layer, donate_argnums=(5,)).lower(
        s((T, C), jnp.bfloat16), s((T, C)), s((N, C)), s((T, N)), s((T, N)),
        s((slots + 1, N, C)), s((T,), jnp.int32), s((T,), jnp.int32)).compile()
    assert "selective_scan" in compiled.as_text()
    assert "while" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= (slots + 1) * N * C * 4
    assert mem.temp_size_in_bytes < 4 * T * C * 4


def test_looped_step_lowered_for_the_chip_holds_a_kernel_per_pass_and_layer(
        one_chip, tpu_branch):
    """The toy looped decoder's serve step, lowered for the chip: passes x
    layers `paged_attention_gqa` calls in ONE program (what the benchmark's
    `step_kernels` check counts at the real size: 192)."""
    from automodel_tpu.serving import ServingConfig, ServingEngine
    from tests import ouro_case
    from tests.serving_params import own

    cfg = ouro_case.config(attn_impl="auto")
    eng = ServingEngine(own(ouro_case.init_params(cfg)), cfg, ServingConfig(
        page_size=8, num_pages=16, max_slots=2, pages_per_slot=4,
        token_budget=8))

    batch = eng._plan_batch(eng.empty_plan())
    before = _one_row_bodies()
    lowered = jax.jit(eng._step_impl, donate_argnums=(1,)).lower(
        *_shapes(one_chip, eng.params, eng.pool, batch))
    assert lowered.as_text().count("paged_attention_gqa") == (
        cfg.num_passes * cfg.num_layers) == 12
    # 4 heads over 4: a decode row scores on the VPU, as at Ouro's size
    assert _one_row_bodies() == {**before, "vpu": before["vpu"] + 12}
    lowered.compile()


def test_state_space_step_compiles_whole_for_the_chip(one_chip, tpu_branch):
    """AI21-Jamba2-3B's serve step at its cell's geometry, from shapes alone
    (no weight is allocated): all 28 layers, 26 of them a `selective_scan`
    Mosaic call over the step's runs beside the two paged calls, compiled
    for the described chip in ONE program whose arguments are the 6.39 GB of
    weights, the 1.20 GB of per-slot state and the 0.20 GB pool, the last
    two donated and aliased to the outputs: the kernels update the state in
    place."""
    import json

    from tests.step_shapes import engine_of_shapes

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "jamba2_3b_serve_v5e1.json")) as f:
        config = json.load(f)
    eng, args = engine_of_shapes(config, config["serving"], one_chip)
    assert eng.cfg.layer_ops.count("mamba") == 26 and len(args[3]) == 26
    from automodel_tpu.observability.metrics import default_registry

    kernels = default_registry().counter(
        "selective_scan_calls_total", impl="pallas",
        reason="float32 state on a TPU")
    before, bodies = kernels.value, _one_row_bodies()
    lowered = jax.jit(eng._step_impl, donate_argnums=(1, 3)).lower(*args)
    assert lowered.as_text().count("paged_attention_gqa") == 2
    assert lowered.as_text().count("selective_scan") == 26
    assert kernels.value - before == 26
    # 20 heads over ONE key/value head: a decode row scores on the MXU
    assert _one_row_bodies() == {**bodies, "mxu": bodies["mxu"] + 2}
    compiled = lowered.compile()
    # and the kernel reads each pool as it lies: with the one-wide head axis
    # second-to-last XLA copied all four into a padded layout every step
    assert not [ln for ln in compiled.as_text().splitlines()
                if " copy(" in ln and "[3073,64," in ln.split(" copy(")[0]]
    mem = compiled.memory_analysis()
    state = 26 * 129 * (16 * 5120 * 4 + 3 * 5120 * 2)
    pool = 2 * 2 * 3073 * 64 * 128 * 2
    assert mem.alias_size_in_bytes >= state + pool
    # nothing is padded: a (5120, 16) state would take eight times its bytes
    assert mem.argument_size_in_bytes < 7.9e9
    assert mem.temp_size_in_bytes < 1.5e9


def test_window_and_share_step_compiles_whole_for_the_chip(one_chip, tpu_branch):
    """K-EXAONE-236B-A23B's one-chip share at its cell's geometry, from
    shapes alone (no weight is allocated): the dense layer and 7 expert layers
    in ONE program whose arguments are the 7.73 GB of weights, the 4.30 GB
    pool of the 2 full layers and the 1.64 GB of rings of the 6 window
    layers, the last two donated and aliased to the outputs. The paged kernel
    runs in every layer, under its window name in six; the three products of
    the 8 HELD experts are `grouped_matmul` calls in each of 7 layers."""
    import json

    from tests.step_shapes import engine_of_shapes

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "k_exaone_236b_a23b_serve_v5e1.json")) as f:
        config = json.load(f)
    eng, args = engine_of_shapes(config, config["serving"], one_chip)
    assert eng._ring_pages == 8 and eng._stack_rings == [1, 5]
    assert eng._stack_attn == [0, 2] and len(args[3]) == 6
    assert eng.cfg.moe.num_held == 8 and eng.cfg.moe.n_routed_experts == 128
    bodies = _one_row_bodies()
    lowered = jax.jit(eng._step_impl, donate_argnums=(1, 3)).lower(*args)
    text = lowered.as_text()
    assert text.count("paged_attention_gqa") == 2
    assert text.count("paged_attention_window_gqa") == 6
    # 64 heads over 8, full and window layers alike: the MXU body
    assert _one_row_bodies() == {**bodies, "mxu": bodies["mxu"] + 8}
    assert text.count("grouped_matmul") == 21 and "ragged_dot" not in text
    mem = lowered.compile().memory_analysis()
    page = 128 * 8 * 128 * 2
    pool = 2 * 2 * 4097 * page
    rings = 6 * 2 * 65 * 8 * page
    assert (pool, rings) == (4_296_015_872, 1_635_778_560)
    assert mem.alias_size_in_bytes >= pool + rings
    # nothing is padded: weights 7.73 GB + pool + rings, and the plan
    assert mem.argument_size_in_bytes < 7.74e9 + pool + rings + 1e7
    assert mem.temp_size_in_bytes < 0.6e9


def test_grouped_matmul_compiles_inside_a_shard_map_over_four_chips(
        topo, tpu_branch):
    """The EP path's use: each chip's local experts over the rows it
    received, inside a `shard_map` (a training batch's: 1,536 a group)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from automodel_tpu.ops.grouped_matmul import grouped_matmul

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("ep",))

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    fn = jax.shard_map(
        grouped_matmul, mesh=mesh,
        in_specs=(P("ep", None), P("ep", None, None), P("ep")),
        out_specs=P("ep", None), check_vma=False)
    compiled = jax.jit(fn).lower(
        s((4 * 24576, 2048), jnp.bfloat16, P("ep", None)),
        s((64, 2048, 1408), jnp.bfloat16, P("ep", None, None)),
        s((64,), jnp.int32, P("ep"))).compile()
    assert "grouped_matmul" in compiled.as_text()


def test_expert_step_lowered_for_the_chip_holds_three_grouped_matmuls_a_layer(
        one_chip, tpu_branch):
    """A toy DeepSeek-shaped decoder's serve step (one dense layer, two
    expert layers), lowered for the chip: gate, up and down of every expert
    layer are `grouped_matmul` calls under `serve.moe.experts` and no
    `ragged_dot` is left (at Moonlight's size the benchmark counts 24)."""
    from automodel_tpu.models.moe_lm import decoder as moe_decoder
    from automodel_tpu.models.moe_lm.decoder import MoETransformerConfig
    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.serving import ServingConfig, ServingEngine

    cfg = MoETransformerConfig(
        vocab_size=64, hidden_size=128, intermediate_size=128, num_layers=3,
        num_heads=4, num_kv_heads=4, first_k_dense=1, dtype=jnp.bfloat16,
        remat_policy="none",
        attention_type="mla", mla_kv_lora_rank=128, mla_q_lora_rank=64,
        mla_qk_nope_head_dim=64, mla_qk_rope_head_dim=64, mla_v_head_dim=64,
        moe=MoEConfig(
            n_routed_experts=8, n_shared_experts=1, experts_per_token=2,
            moe_intermediate_size=128, shared_expert_intermediate_size=128,
            aux_loss_coeff=0.0, dispatcher="dropless",
        ),
    )
    eng = ServingEngine(moe_decoder.init(cfg, jax.random.key(0)), cfg, ServingConfig(
        page_size=16, num_pages=8, max_slots=2, pages_per_slot=2,
        token_budget=16))

    batch = eng._plan_batch(eng.empty_plan())
    lowered = jax.jit(eng._step_impl, donate_argnums=(1,)).lower(
        *_shapes(one_chip, eng.params, eng.pool, batch))
    text = lowered.as_text()
    assert text.count("ragged_dot") == 0
    assert text.count("grouped_matmul") == 6
    assert text.count("paged_attention_mla") == 3
    # the compiled calls keep their scope: `serve_moe_device_ms` reads them
    calls = [ln for ln in lowered.compile().as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert sum("serve.moe.experts/grouped_matmul/pallas_call" in ln
               for ln in calls) == 6
