"""Flash-attention kernel parity vs the XLA oracle (interpret mode on CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops.attention import make_attention_mask, xla_attention
from automodel_tpu.ops.pallas.flash_attention import BlockSizes, flash_attention

SMALL_BLOCKS = BlockSizes(block_q=128, block_kv=128, block_q_dq=128, block_kv_dkv=128)


def _rand_qkv(key, B=1, S=256, Hq=4, Hkv=2, D=128, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, Hq, D), dtype)
    k = jax.random.normal(kk, (B, S, Hkv, D), dtype)
    v = jax.random.normal(kv, (B, S, Hkv, D), dtype)
    return q, k, v


def _oracle(q, k, v, **kw):
    mask = make_attention_mask(
        q.shape[1], k.shape[1],
        causal=kw.get("causal", True),
        q_segment_ids=kw.get("segment_ids"),
        kv_segment_ids=kw.get("segment_ids"),
        q_positions=kw.get("positions"),
        kv_positions=kw.get("positions"),
        sliding_window=kw.get("sliding_window"),
    )
    return xla_attention(
        q, k, v, mask=mask,
        scale=kw.get("scale"), logits_soft_cap=kw.get("logits_soft_cap"),
    )


CASES = {
    "causal": {},
    "noncausal": {"causal": False},
    "gqa8": {"Hq": 8, "Hkv": 2},
    "mha": {"Hq": 2, "Hkv": 2},
    "window": {"sliding_window": 100},
    "noncausal_window": {"causal": False, "sliding_window": 100},
    "softcap": {"logits_soft_cap": 20.0},
    "scale": {"scale": 0.05},
}


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(CASES))
def test_fwd_parity(name):
    kw = dict(CASES[name])
    shape_kw = {k: kw.pop(k) for k in ("Hq", "Hkv") if k in kw}
    q, k, v = _rand_qkv(jax.random.key(0), **shape_kw)
    out = flash_attention(q, k, v, block_sizes=SMALL_BLOCKS, **kw)
    ref = _oracle(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_fwd_packed_segments():
    q, k, v = _rand_qkv(jax.random.key(1), S=256)
    seg = jnp.concatenate(
        [jnp.zeros((1, 100), jnp.int32), jnp.ones((1, 156), jnp.int32)], axis=1
    )
    pos = jnp.concatenate(
        [jnp.arange(100)[None], jnp.arange(156)[None]], axis=1
    ).astype(jnp.int32)
    out = flash_attention(q, k, v, segment_ids=seg, positions=pos, block_sizes=SMALL_BLOCKS)
    ref = _oracle(q, k, v, segment_ids=seg, positions=pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_fwd_noncausal_window_block_skip():
    """S=512 with window 100 and 128-blocks: kv blocks fully outside the
    two-sided window are skipped by _run_predicate; parity proves no valid
    block is dropped."""
    q, k, v = _rand_qkv(jax.random.key(7), S=512)
    kw = {"causal": False, "sliding_window": 100}
    out = flash_attention(q, k, v, block_sizes=SMALL_BLOCKS, **kw)
    ref = _oracle(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["causal", "gqa8", "window", "noncausal_window", "softcap"])
def test_bwd_parity(name):
    kw = dict(CASES[name])
    shape_kw = {k: kw.pop(k) for k in ("Hq", "Hkv") if k in kw}
    q, k, v = _rand_qkv(jax.random.key(2), S=256, **shape_kw)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_sizes=SMALL_BLOCKS, **kw) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_oracle(q, k, v, **kw) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3, err_msg=f"d{n}"
        )


@pytest.mark.slow
def test_bwd_packed_segments():
    q, k, v = _rand_qkv(jax.random.key(3), S=256)
    seg = jnp.concatenate(
        [jnp.zeros((1, 128), jnp.int32), jnp.ones((1, 128), jnp.int32)], axis=1
    )

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, segment_ids=seg, block_sizes=SMALL_BLOCKS) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_oracle(q, k, v, segment_ids=seg) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3)


def test_unsupported_shapes_raise():
    q = jnp.zeros((1, 100, 4, 64))  # seq not 128-divisible
    with pytest.raises(NotImplementedError):
        flash_attention(q, q, q)
    # ...and through the dispatcher an explicit impl="flash" is strict: it
    # raises, it does not quietly run the XLA reference
    from automodel_tpu.ops.attention import dot_product_attention

    with pytest.raises(NotImplementedError, match="not multiples of 128"):
        dot_product_attention(q, q, q, impl="flash")
    np.testing.assert_allclose(  # "auto" off-TPU is the reference by rule
        np.asarray(dot_product_attention(q, q, q, impl="auto")),
        np.asarray(_oracle(q, q, q)),
    )


def test_auto_fallback_on_tpu_is_counted(monkeypatch):
    """On a TPU, impl="auto" handing a call to the XLA reference ticks the
    process registry once per traced call site, labeled with the reason."""
    from automodel_tpu.observability.metrics import default_registry
    from automodel_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    counter = default_registry().counter(
        "attention_reference_fallbacks_total", op="attention",
        reason="seq lens (100, 100) are not multiples of 128",
    )
    before = counter.value
    q = jnp.ones((1, 100, 4, 64))
    out = attention.dot_product_attention(q, q, q, impl="auto")
    assert counter.value == before + 1
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle(q, q, q)))


def test_flash_runs_per_shard_on_a_mesh():
    """cp == 1 mesh: the kernel runs inside the attention shard_map (batch
    on the data axes, heads on tp) and matches the unsharded oracle; a
    batch the data axes do not divide is refused by name."""
    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.ops.attention import dot_product_attention

    ctx = MeshConfig(dp_shard=2, ep=2, tp=2).build()
    q, k, v = _rand_qkv(jax.random.key(11), B=4, S=128, Hq=4, Hkv=2, D=128)
    out = jax.jit(
        lambda q, k, v: dot_product_attention(
            q, k, v, impl="flash", mesh_ctx=ctx
        )
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_oracle(q, k, v)), rtol=2e-4, atol=2e-4
    )
    with pytest.raises(NotImplementedError, match="not divisible by the data axes"):
        dot_product_attention(q[:3], k[:3], v[:3], impl="flash", mesh_ctx=ctx)


@pytest.mark.slow
@pytest.mark.parametrize("D", [64, 96])
def test_narrow_head_dim_padded(D):
    """head_dim 64/96 (gpt-oss, qwen2-0.5B class) runs via lane padding."""
    q, k, v = _rand_qkv(jax.random.key(4), S=256, D=D)
    out = flash_attention(q, k, v, block_sizes=SMALL_BLOCKS)
    ref = _oracle(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    g1 = jax.grad(lambda *a: jnp.sum(flash_attention(*a, block_sizes=SMALL_BLOCKS) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(_oracle(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3, err_msg=f"d{n}"
        )


@pytest.mark.slow
def test_mla_shaped_heads():
    """MLA: q/k head_dim (192) differs from v head_dim (128)."""
    key = jax.random.key(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 256, 4, 192))
    k = jax.random.normal(kk, (1, 256, 4, 192))
    v = jax.random.normal(kv, (1, 256, 4, 128))
    out = flash_attention(q, k, v, block_sizes=SMALL_BLOCKS)
    ref = _oracle(q, k, v)
    assert out.shape == (1, 256, 4, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    g1 = jax.grad(lambda *a: jnp.sum(flash_attention(*a, block_sizes=SMALL_BLOCKS) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(_oracle(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3, err_msg=f"d{n}"
        )


@pytest.mark.slow
def test_sinks_parity():
    """gpt-oss attention sinks: fwd/bwd parity incl. the sink gradient."""
    q, k, v = _rand_qkv(jax.random.key(6), S=256, Hq=4, Hkv=2)
    sinks = jax.random.normal(jax.random.key(7), (4,))

    def f_flash(q, k, v, s):
        return jnp.sum(
            flash_attention(q, k, v, sinks=s, block_sizes=SMALL_BLOCKS) ** 2
        )

    def f_ref(q, k, v, s):
        mask = make_attention_mask(q.shape[1], k.shape[1], causal=True)
        return jnp.sum(xla_attention(q, k, v, mask=mask, sinks=s) ** 2)

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, sinks=sinks, block_sizes=SMALL_BLOCKS)),
        np.asarray(xla_attention(
            q, k, v,
            mask=make_attention_mask(q.shape[1], k.shape[1], causal=True),
            sinks=sinks,
        )),
        rtol=2e-4, atol=2e-4,
    )
    g1 = jax.grad(f_flash, argnums=(0, 1, 2, 3))(q, k, v, sinks)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2, 3))(q, k, v, sinks)
    for a, b, n in zip(g1, g2, ("q", "k", "v", "sinks")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3, err_msg=f"d{n}"
        )


def test_traced_sliding_window():
    """A traced (scan-carried) window matches the static-window kernel."""
    q, k, v = _rand_qkv(jax.random.key(8), S=256)
    ref = flash_attention(q, k, v, sliding_window=100, block_sizes=SMALL_BLOCKS)

    @jax.jit
    def run(w):
        return flash_attention(q, k, v, sliding_window=w, block_sizes=SMALL_BLOCKS)

    out = run(jnp.int32(100))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_position_causal_asymmetric_kv():
    """Ring-step mode: kv carries its own global positions/segments."""
    B, S, H, D = 1, 128, 2, 128
    kq, kk, kv = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(kq, (B, S, H, D))
    k = jax.random.normal(kk, (B, S, H, D))
    v = jax.random.normal(kv, (B, S, H, D))
    # q holds global tokens [128..256), visiting kv block holds [0..128)
    qpos = jnp.arange(S, dtype=jnp.int32)[None] + S
    kpos = jnp.arange(S, dtype=jnp.int32)[None]
    out, lse = flash_attention(
        q, k, v, positions=qpos, kv_positions=kpos,
        block_sizes=SMALL_BLOCKS, return_lse=True,
    )
    # every kv position precedes every q position → dense (non-causal) scores
    ref = _oracle(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    assert lse.shape == (B, H, S)

    # reversed: q precedes all kv → fully masked, zero output, -inf-like lse
    out2, lse2 = flash_attention(
        q, k, v, positions=kpos, kv_positions=qpos + 1,
        block_sizes=SMALL_BLOCKS, return_lse=True,
    )
    np.testing.assert_allclose(np.asarray(out2), 0.0, atol=1e-6)
    assert bool(jnp.all(lse2 < -1e30))


@pytest.mark.slow
def test_return_lse_differentiable():
    """lse cotangents fold into the kernel backward (ring merge needs this)."""
    q, k, v = _rand_qkv(jax.random.key(10), S=128)

    def f_flash(q, k, v):
        out, lse = flash_attention(q, k, v, block_sizes=SMALL_BLOCKS, return_lse=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def f_ref(q, k, v):
        mask = make_attention_mask(q.shape[1], k.shape[1], causal=True)
        B, S, Hq, D = q.shape
        G = Hq // k.shape[2]
        qg = q.reshape(B, S, k.shape[2], G, D)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, k) * (D ** -0.5)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        lse = jax.scipy.special.logsumexp(s, axis=-1)  # (B,Hkv,G,S)
        lse = lse.reshape(B, Hq, S)
        out = xla_attention(q, k, v, mask=mask)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    np.testing.assert_allclose(
        float(f_flash(q, k, v)), float(f_ref(q, k, v)), rtol=1e-4
    )
    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3, err_msg=f"d{n}"
        )
