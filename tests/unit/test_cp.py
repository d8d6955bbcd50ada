"""Context-parallel ring attention parity tests (8-device virtual mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.distributed import MeshConfig
from automodel_tpu.models.llm import decoder
from automodel_tpu.models.llm.decoder import TransformerConfig
from automodel_tpu.ops.attention import make_attention_mask, xla_attention
from automodel_tpu.parallel import logical_to_shardings
from automodel_tpu.parallel.cp import (
    ContextParallelSharder,
    load_balanced_permutation,
    ring_dot_product_attention,
)


def _qkv(key, B=2, S=64, Hq=4, Hkv=2, D=16):
    ks = jax.random.split(key, 3)
    return (
        jax.random.normal(ks[0], (B, S, Hq, D)),
        jax.random.normal(ks[1], (B, S, Hkv, D)),
        jax.random.normal(ks[2], (B, S, Hkv, D)),
    )


def test_load_balanced_permutation_props():
    perm = load_balanced_permutation(32, 4)
    assert sorted(perm.tolist()) == list(range(32))
    # rank 0 owns chunks 0 and 7
    assert perm[:4].tolist() == [0, 1, 2, 3]
    assert perm[4:8].tolist() == [28, 29, 30, 31]


def test_sharder_contract():
    sh = ContextParallelSharder(cp_size=4)
    batch = {
        "input_ids": np.arange(32)[None, :].repeat(2, 0),
        "labels": np.arange(32)[None, :].repeat(2, 0),
    }
    out = sh.shard_batch(batch)
    assert "positions" in out
    # positions equal the permuted global indices
    np.testing.assert_array_equal(out["positions"][0], out["input_ids"][0])
    idx0 = sh.local_token_global_indices(32, 0)
    np.testing.assert_array_equal(idx0, out["positions"][0][:8])


@pytest.mark.slow
@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.parametrize("balanced", [False, True])
def test_ring_attention_matches_oracle(cp, balanced):
    ctx = MeshConfig(cp=cp, dp_shard=8 // cp).build()
    q, k, v = _qkv(jax.random.key(0), B=8 // cp, S=64)
    S = 64
    perm = (
        load_balanced_permutation(S, cp) if balanced else np.arange(S)
    )
    positions = jnp.asarray(perm, jnp.int32)[None, :].repeat(q.shape[0], 0)
    qp, kp, vp = q[:, perm], k[:, perm], v[:, perm]

    @jax.jit
    def ring(q, k, v, pos):
        return ring_dot_product_attention(q, k, v, pos, None, ctx, causal=True)

    out = ring(
        jax.device_put(qp, ctx.sharding("batch", "cp", None, None)),
        jax.device_put(kp, ctx.sharding("batch", "cp", None, None)),
        jax.device_put(vp, ctx.sharding("batch", "cp", None, None)),
        jax.device_put(positions, ctx.sharding("batch", "cp")),
    )
    ref = xla_attention(q, k, v, mask=make_attention_mask(S, S, causal=True))
    # un-permute the ring output back to natural order before comparing
    inv = np.argsort(perm)
    np.testing.assert_allclose(
        np.asarray(out)[:, inv], np.asarray(ref), rtol=2e-4, atol=2e-4
    )


@pytest.mark.slow
def test_ring_attention_grads_match():
    cp = 4
    ctx = MeshConfig(cp=cp, dp_shard=2).build()
    q, k, v = _qkv(jax.random.key(1), B=2, S=64)
    S = 64
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (2, S))

    def loss_ring(q, k, v):
        return jnp.sum(ring_dot_product_attention(q, k, v, positions, None, ctx) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            xla_attention(q, k, v, mask=make_attention_mask(S, S, causal=True)) ** 2
        )

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


def test_ring_attention_packed_segments():
    cp = 2
    ctx = MeshConfig(cp=cp, dp_shard=4).build()
    q, k, v = _qkv(jax.random.key(2), B=4, S=64)
    S = 64
    seg = jnp.concatenate(
        [jnp.zeros((4, 24), jnp.int32), jnp.ones((4, 40), jnp.int32)], axis=1
    )
    pos = jnp.concatenate(
        [jnp.arange(24)[None].repeat(4, 0), jnp.arange(40)[None].repeat(4, 0)], axis=1
    ).astype(jnp.int32)

    @jax.jit
    def ring(q, k, v):
        return ring_dot_product_attention(q, k, v, pos, seg, ctx, causal=True)

    out = ring(q, k, v)
    mask = make_attention_mask(
        S, S, causal=True, q_segment_ids=seg, kv_segment_ids=seg,
        q_positions=pos, kv_positions=pos,
    )
    ref = xla_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_decoder_with_cp_matches_single_device():
    """Full decoder forward under cp=2 (ring path) == single-device."""
    cfg = TransformerConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, dtype=jnp.float32, remat_policy="none",
    )
    ctx = MeshConfig(dp_shard=2, tp=2, cp=2).build()
    params = decoder.init(cfg, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(5), (4, 64), 0, 128)
    ref = decoder.forward(params, cfg, ids)

    shardings = logical_to_shardings(
        decoder.param_specs(cfg), ctx, shapes=jax.tree.map(lambda p: p.shape, params)
    )
    sp = jax.device_put(params, shardings)

    @jax.jit
    def f(p, i):
        return decoder.forward(p, cfg, i, mesh_ctx=ctx)

    out = f(sp, jax.device_put(ids, ctx.sharding("batch", "cp")))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=5e-4, atol=5e-4)


@pytest.mark.slow
def test_ring_flash_kernel_parity():
    """cp=2 ring where each shard's S_loc (128) engages the Pallas flash
    kernel (position-causal mode, interpret on CPU) — fwd + grads vs cp=1."""
    cp = 2
    ctx = MeshConfig(cp=cp, dp_shard=4).build()
    S = 256  # S_loc = 128 per rank: a lane multiple, as the kernel needs
    q, k, v = _qkv(jax.random.key(5), B=4, S=S, Hq=2, Hkv=1, D=128)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (4, S))

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_dot_product_attention(
                q, k, v, positions, None, ctx, attn_impl="flash"
            ) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            xla_attention(q, k, v, mask=make_attention_mask(S, S, causal=True)) ** 2
        )

    out = jax.jit(
        lambda q, k, v: ring_dot_product_attention(
            q, k, v, positions, None, ctx, attn_impl="flash"
        )
    )(q, k, v)
    ref = xla_attention(q, k, v, mask=make_attention_mask(S, S, causal=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=f"d{n}"
        )


def test_ring_attention_with_sinks():
    """gpt-oss sinks under CP: the sink joins the softmax denominator once
    globally; parity vs the single-device XLA sink path."""
    cp = 4
    ctx = MeshConfig(cp=cp, dp_shard=2).build()
    S = 64
    q, k, v = _qkv(jax.random.key(6), B=2, S=S)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (2, S))
    sinks = jax.random.normal(jax.random.key(7), (4,))

    out = jax.jit(
        lambda q, k, v, s: ring_dot_product_attention(
            q, k, v, positions, None, ctx, sinks=s
        )
    )(q, k, v, sinks)
    ref = xla_attention(
        q, k, v, mask=make_attention_mask(S, S, causal=True), sinks=sinks
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# per-document (blockdiag) CP
# ---------------------------------------------------------------------------
def test_document_pack_permutation_props():
    """Bijection; whole documents contiguous on one rank; capacity honored;
    oversize documents rejected with the ring-layout pointer."""
    from automodel_tpu.parallel.cp import document_pack_permutation

    seg = np.asarray([0] * 10 + [1] * 6 + [2] * 10 + [3] * 6)  # S=32, cp=2
    perm = document_pack_permutation(seg, 2)
    assert sorted(perm) == list(range(32))
    placed = seg[perm]
    cap = 16
    for r in range(2):
        shard = placed[r * cap : (r + 1) * cap]
        # each doc id appears in exactly one rank and contiguously
        for d in set(shard):
            idx = np.nonzero(placed == d)[0]
            assert idx[0] // cap == idx[-1] // cap          # one rank
            assert (np.diff(idx) == 1).all()                # contiguous
    # two 10-token docs must land on different ranks (capacity 16)
    r10a = np.nonzero(placed == 0)[0][0] // cap
    r10b = np.nonzero(placed == 2)[0][0] // cap
    assert r10a != r10b

    with pytest.raises(ValueError, match="ring handles documents"):
        document_pack_permutation(np.zeros(32, np.int64), 2)  # one 32-doc


def test_blockdiag_local_equals_ring_on_packed():
    """Blockdiag layout + LOCAL attention == ring attention on the same
    packed content: per-token outputs match after inverting the layout."""
    from automodel_tpu.parallel.cp import (
        BlockDiagContextParallelSharder,
        local_cp_attention,
    )

    cp = 2
    ctx = MeshConfig(cp=cp, dp_shard=4).build()
    B, S = 4, 64
    rng = np.random.default_rng(0)
    seg = np.asarray([0] * 20 + [1] * 12 + [2] * 20 + [3] * 12, np.int32)
    seg = np.broadcast_to(seg, (B, S)).copy()
    pos = np.concatenate([
        np.arange(20), np.arange(12), np.arange(20), np.arange(12)
    ]).astype(np.int32)
    pos = np.broadcast_to(pos, (B, S)).copy()
    q, k, v = _qkv(jax.random.key(3), B=B, S=S)

    sharder = BlockDiagContextParallelSharder(cp_size=cp)
    batch = sharder.shard_batch({
        "input_ids": np.zeros((B, S), np.int32),
        "positions": pos, "segment_ids": seg,
        "q": None,  # not a seq key — untouched
    })
    from automodel_tpu.parallel.cp import document_pack_permutation

    perm = np.stack([document_pack_permutation(row, cp) for row in seg])
    qp = jnp.asarray(np.take_along_axis(np.asarray(q), perm[:, :, None, None], 1))
    kp = jnp.asarray(np.take_along_axis(np.asarray(k), perm[:, :, None, None], 1))
    vp = jnp.asarray(np.take_along_axis(np.asarray(v), perm[:, :, None, None], 1))

    out_local = jax.jit(
        lambda *a: local_cp_attention(
            *a, ctx, causal=True,
        )
    )(qp, kp, vp, jnp.asarray(batch["positions"]), jnp.asarray(batch["segment_ids"]))

    out_ring = jax.jit(
        lambda *a: ring_dot_product_attention(
            *a, ctx, causal=True,
        )
    )(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pos), jnp.asarray(seg))

    # invert the layout: out_local[perm_slot] corresponds to source token
    inv = np.empty_like(perm)
    for b in range(B):
        inv[b, perm[b]] = np.arange(S)
    out_local_nat = np.take_along_axis(
        np.asarray(out_local), inv[:, :, None, None], 1
    )
    np.testing.assert_allclose(
        out_local_nat, np.asarray(out_ring), rtol=2e-4, atol=2e-4
    )


@pytest.mark.slow
def test_decoder_blockdiag_cp_matches_single_device():
    """Full decoder forward: blockdiag layout + local attention == the
    single-device forward on the same packed content (inverted layout)."""
    import dataclasses

    from automodel_tpu.parallel.cp import (
        BlockDiagContextParallelSharder,
        document_pack_permutation,
    )

    cfg = TransformerConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, dtype=jnp.float32, remat_policy="none",
    )
    cfg_bd = dataclasses.replace(cfg, cp_blockdiag=True)
    ctx = MeshConfig(dp_shard=2, tp=2, cp=2).build()
    B, S = 4, 64
    rng = np.random.default_rng(1)
    ids = rng.integers(1, 128, (B, S), dtype=np.int32)
    seg = np.broadcast_to(
        np.asarray([0] * 20 + [1] * 12 + [2] * 20 + [3] * 12, np.int32), (B, S)
    ).copy()
    pos = np.broadcast_to(np.concatenate([
        np.arange(20), np.arange(12), np.arange(20), np.arange(12)
    ]).astype(np.int32), (B, S)).copy()

    params = decoder.init(cfg, jax.random.key(0))
    sharder = BlockDiagContextParallelSharder(cp_size=2)
    batch = sharder.shard_batch(
        {"input_ids": ids, "positions": pos, "segment_ids": seg}
    )
    sh = logical_to_shardings(
        decoder.param_specs(cfg), ctx, shapes=jax.tree.map(lambda p: p.shape, params)
    )
    sharded = jax.device_put(params, sh)

    out_bd = jax.jit(
        lambda p, i, po, sg: decoder.forward(
            p, cfg_bd, i, positions=po, segment_ids=sg, mesh_ctx=ctx
        )
    )(
        sharded, jnp.asarray(batch["input_ids"]),
        jnp.asarray(batch["positions"]), jnp.asarray(batch["segment_ids"]),
    )

    ref = decoder.forward(
        params, cfg, jnp.asarray(ids), positions=jnp.asarray(pos),
        segment_ids=jnp.asarray(seg),
    )
    perm = np.stack([document_pack_permutation(row, 2) for row in seg])
    ref_perm = np.take_along_axis(np.asarray(ref), perm[:, :, None], 1)
    np.testing.assert_allclose(
        np.asarray(out_bd), ref_perm, rtol=3e-4, atol=3e-4
    )


@pytest.mark.recipe
def test_blockdiag_cp_recipe_loss_parity(tmp_path):
    """cp_layout=blockdiag trains on packed data and its per-step losses
    match the balanced-ring run on the SAME data/seed — the reference's
    blockdiag-vs-dense loss-parity contract (blockdiag_cp/ parity tests)."""
    import json

    from automodel_tpu.cli.app import resolve_recipe_class
    from automodel_tpu.config import ConfigNode

    def run(layout, run_dir):
        cfg = ConfigNode({
            "seed": 7,
            "run_dir": str(run_dir),
            "auto_resume": False,
            "recipe": "llm_finetune",
            "model": {"hf_config": {
                "architectures": ["LlamaForCausalLM"],
                "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
                "num_hidden_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 2,
            }, "dtype": "float32", "remat_policy": "none"},
            "distributed": {"dp_shard": -1, "cp": 2, "cp_layout": layout},
            "dataset": {
                "_target_": "automodel_tpu.datasets.mock.MockDatasetConfig",
                "num_samples": 16, "seq_len": 64, "vocab_size": 128,
                # align = seq_len // cp: capacity-aligned packing, the
                # blockdiag layout's contract (docs never cross a rank)
                "packed": True, "docs_per_sample": 4, "align": 32,
            },
            "dataloader": {"microbatch_size": 8, "grad_acc_steps": 1},
            "optimizer": {"name": "adamw", "lr": 1e-3},
            "lr_scheduler": {"style": "constant", "warmup_steps": 0},
            "step_scheduler": {"max_steps": 2, "ckpt_every_steps": 100},
            "checkpoint": {"enabled": False},
            "loss": {"chunk_size": 64},
        })
        r = resolve_recipe_class(cfg)(cfg)
        r.setup()
        if layout == "blockdiag":
            assert r.model_cfg.cp_blockdiag
            assert type(r.cp_sharder).__name__ == "BlockDiagContextParallelSharder"
        r.run_train_validation_loop()
        return [
            json.loads(l) for l in open(run_dir / "training.jsonl") if l.strip()
        ]

    bd = run("blockdiag", tmp_path / "bd")
    ring = run("balanced", tmp_path / "ring")
    assert len(bd) == len(ring) == 2
    for a, b in zip(bd, ring):
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-4)
