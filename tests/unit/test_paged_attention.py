"""Ragged paged attention: XLA reference vs dense oracle, Pallas parity.

The XLA gather-based reference is checked against `ops/attention.py`'s
dense einsum attention on a contiguous cache scattered into randomly-
permuted pages; the Pallas kernels (interpret mode on CPU) are then checked
against the reference — the same two-hop oracle chain as flash attention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops.attention import xla_attention
from automodel_tpu.ops.paged_attention import (
    ragged_paged_attention,
    ragged_paged_attention_xla,
    ragged_paged_mla_attention_xla,
)


def _paged_setup(seed=0, T=6, Hkv=2, G=2, D=16, Dv=16, ps=4, P=5, N=12):
    """Scatter a contiguous (T_ctx, Hkv, D) cache into shuffled pool pages;
    token t sees positions 0..pos[t] of the context."""
    rng = np.random.default_rng(seed)
    Hq = Hkv * G
    ctx = P * ps
    q = jnp.asarray(rng.normal(size=(T, Hq, D)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(ctx, Hkv, D)), jnp.float32)
    values = jnp.asarray(rng.normal(size=(ctx, Hkv, Dv)), jnp.float32)
    pages = rng.permutation(N)[:P]              # the pool pages backing ctx
    k_pages = jnp.asarray(rng.normal(size=(N + 1, ps, Hkv, D)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(N + 1, ps, Hkv, Dv)), jnp.float32)
    k_pages = k_pages.at[pages].set(keys.reshape(P, ps, Hkv, D))
    v_pages = v_pages.at[pages].set(values.reshape(P, ps, Hkv, Dv))
    pt = jnp.broadcast_to(jnp.asarray(pages, jnp.int32), (T, P))
    pos = jnp.asarray(rng.integers(0, ctx, (T,)), jnp.int32)
    return q, keys, values, k_pages, v_pages, pt, pos


def _dense_oracle(q, keys, values, pos, window=None, soft_cap=None, sinks=None):
    """Per-token dense attention over positions <= pos[t]."""
    T = q.shape[0]
    ctx = keys.shape[0]
    kv_idx = jnp.arange(ctx)
    mask = kv_idx[None, :] <= pos[:, None]
    if window is not None:
        dist = pos[:, None] - kv_idx[None, :]
        mask = jnp.logical_and(mask, (window == 0) | (dist < window))
    out = xla_attention(
        q[:, None], jnp.broadcast_to(keys[None], (T, *keys.shape)),
        jnp.broadcast_to(values[None], (T, *values.shape)),
        mask=mask[:, None, :], scale=q.shape[-1] ** -0.5,
        logits_soft_cap=soft_cap, sinks=sinks,
    )
    return out[:, 0]


def test_xla_reference_matches_dense_oracle():
    q, keys, values, kp, vp, pt, pos = _paged_setup()
    got = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=q.shape[-1] ** -0.5)
    want = _dense_oracle(q, keys, values, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_xla_reference_window_softcap_sinks():
    q, keys, values, kp, vp, pt, pos = _paged_setup(seed=1)
    sinks = jnp.asarray([0.3, -0.2, 0.1, 0.5], jnp.float32)
    got = ragged_paged_attention_xla(
        q, kp, vp, pt, pos, scale=q.shape[-1] ** -0.5,
        window=jnp.int32(5), soft_cap=10.0, sinks=sinks,
    )
    want = _dense_oracle(q, keys, values, pos, window=jnp.int32(5),
                         soft_cap=10.0, sinks=sinks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # window == 0 means global (the layer-scan convention)
    got0 = ragged_paged_attention_xla(
        q, kp, vp, pt, pos, scale=q.shape[-1] ** -0.5, window=jnp.int32(0),
    )
    want0 = _dense_oracle(q, keys, values, pos)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(want0), atol=1e-5)


def test_pad_rows_zero():
    q, keys, values, kp, vp, pt, pos = _paged_setup(seed=2)
    pos = pos.at[2].set(-1).at[5].set(-1)
    got = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=0.25)
    assert np.asarray(got)[2].max() == 0.0 and np.asarray(got)[5].max() == 0.0
    # sinks must not leak mass into pad rows either
    got_s = ragged_paged_attention_xla(
        q, kp, vp, pt, pos, scale=0.25,
        sinks=jnp.ones((q.shape[1],), jnp.float32),
    )
    assert np.asarray(got_s)[2].max() == 0.0


def test_pallas_gqa_kernel_matches_reference():
    q, keys, values, kp, vp, pt, pos = _paged_setup(seed=3)
    pos = pos.at[4].set(-1)
    from automodel_tpu.ops.pallas.ragged_paged_attention import (
        paged_attention_kernel,
    )

    want = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=0.25)
    got = paged_attention_kernel(q, kp, vp, pt, pos, scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # soft-cap rides the kernel too (gemma-style decode)
    want_c = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=0.25, soft_cap=8.0)
    got_c = paged_attention_kernel(q, kp, vp, pt, pos, scale=0.25, soft_cap=8.0)
    np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c), atol=1e-5)


def test_pallas_mla_kernel_matches_reference():
    rng = np.random.default_rng(4)
    T, n, r, dr, ps, P, N = 5, 4, 16, 8, 4, 4, 9
    qa = jnp.asarray(rng.normal(size=(T, n, r)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(T, n, dr)), jnp.float32)
    cp = jnp.asarray(rng.normal(size=(N + 1, ps, r)), jnp.float32)
    krp = jnp.asarray(rng.normal(size=(N + 1, ps, dr)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, N, (T, P)), jnp.int32)
    pos = jnp.asarray([0, 3, -1, 11, 15], jnp.int32)
    from automodel_tpu.ops.pallas.ragged_paged_attention import (
        paged_mla_attention_kernel,
    )

    want = ragged_paged_mla_attention_xla(qa, qr, cp, krp, pt, pos, scale=0.2)
    got = paged_mla_attention_kernel(qa, qr, cp, krp, pt, pos, scale=0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert np.asarray(got)[2].max() == 0.0  # pad row


def test_dispatch_is_strict_about_kernel_unsupported_features():
    """Windows/sinks are outside the kernel: an explicit impl='pallas'
    raises instead of quietly running the reference; 'auto' (off-TPU: the
    reference by rule) still serves the call."""
    q, keys, values, kp, vp, pt, pos = _paged_setup(seed=5)
    with pytest.raises(NotImplementedError, match="sliding windows"):
        ragged_paged_attention(
            q, kp, vp, pt, pos, scale=0.25, window=jnp.int32(4), impl="pallas",
        )
    with pytest.raises(NotImplementedError, match="attention sinks"):
        ragged_paged_attention(
            q, kp, vp, pt, pos, scale=0.25, impl="pallas",
            sinks=jnp.zeros((q.shape[1],), jnp.float32),
        )
    got = ragged_paged_attention(
        q, kp, vp, pt, pos, scale=0.25, window=jnp.int32(4), impl="auto",
    )
    want = ragged_paged_attention_xla(q, kp, vp, pt, pos, scale=0.25, window=jnp.int32(4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
