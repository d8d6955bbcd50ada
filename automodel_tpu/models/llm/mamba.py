"""The Mamba-1 mixer (Gu & Dao, "Mamba: Linear-Time Sequence Modeling with
Selective State Spaces") as the Jamba family writes it: a state-space layer
that stands where attention stands in a decoder layer (`decoder.py`:
`TransformerConfig.layer_ops`).

    [u, z] = in_proj(x)                       x (.., H) -> 2 x (.., C), C = expand * H
    u      = silu(conv1d_causal_depthwise(u) + b_conv)      the last K positions
    [dt, B, C] = x_proj(u)                    split R / N / N
    dt, B, C = rms(dt), rms(B), rms(C)        Jamba's three inner RMSNorms
    delta  = softplus(dt_proj(dt) + dt_bias)  (.., C)
    A      = -exp(A_log)                      (N, C): a decay per (state, channel)
    h_t    = exp(delta_t (x) A) * h_{t-1} + (delta_t * u_t) (x) B_t     float32
    y_t    = h_t C_t + D * u_t
    out    = o_proj(y * silu(z))

`hybrid/mamba2.py` is the SSD form (one scalar decay a head) and cannot
express the per-(state, channel) `A`, the low-rank `dt` or the inner norms.
The recurrence and the convolution are `ops/selective_scan.py`; the three
functions below (`mamba_inputs`, `mamba_selection`, `mamba_output`) are what
the whole-sequence forward here and the serve step (`serving/engine.py`,
which carries h and the convolution's last K-1 inputs per slot) share.

Layouts against the published checkpoint (`checkpoint/hf_adapter.py` maps
both ways): `conv/kernel` (K, C) for conv1d.weight (C, 1, K); `A_log` (N, C)
for (C, N); every Linear (in, out) for (out, in). The output projection is
`o_proj` like attention's: it is the branch's write into the residual stream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.layers import dense_init
from automodel_tpu.models.llm.decoder import _stack
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.quant import matmul as _mm
from automodel_tpu.ops.selective_scan import causal_conv, selective_scan

F32 = jnp.float32


def init_mamba_layers(cfg, rng: jax.Array, L: int) -> dict:
    """The mixers of L layers, stacked. `A_log`, `D` and `dt_bias` as the
    family initialises them: A = 1..N per channel, D = 1, and a bias whose
    softplus is log-uniform over [0.001, 0.1]."""
    H, C, N = cfg.hidden_size, cfg.mamba_d_inner, cfg.mamba_d_state
    K, R = cfg.mamba_d_conv, cfg.resolved_dt_rank
    ks = jax.random.split(rng, 6)
    dt = jnp.exp(jax.random.uniform(
        ks[5], (L, C), F32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "in_proj": {"kernel": _stack(dense_init, ks[0], (H, 2 * C), L)},
        "conv": {"kernel": _stack(dense_init, ks[1], (K, C), L),
                 "bias": jnp.zeros((L, C))},
        "x_proj": {"kernel": _stack(dense_init, ks[2], (C, R + 2 * N), L)},
        "dt_norm": {"scale": jnp.ones((L, R))},
        "b_norm": {"scale": jnp.ones((L, N))},
        "c_norm": {"scale": jnp.ones((L, N))},
        "dt_proj": {"kernel": _stack(dense_init, ks[3], (R, C), L)},
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus's inverse
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=F32))[None, :, None], (L, N, C)),
        "D": jnp.ones((L, C)),
        "o_proj": {"kernel": _stack(dense_init, ks[4], (C, H), L)},
    }


def mamba_layer_specs(cfg) -> dict:
    """The inner channels are the mixer's wide axis, cut like an MLP's."""
    return {
        "in_proj": {"kernel": ("layers", "embed", "mlp")},
        "conv": {"kernel": ("layers", None, "mlp"), "bias": ("layers", "mlp")},
        "x_proj": {"kernel": ("layers", "mlp", None)},
        "dt_norm": {"scale": ("layers", "norm")},
        "b_norm": {"scale": ("layers", "norm")},
        "c_norm": {"scale": ("layers", "norm")},
        "dt_proj": {"kernel": ("layers", None, "mlp")},
        "dt_bias": ("layers", "mlp"),
        "A_log": ("layers", None, "mlp"),
        "D": ("layers", "mlp"),
        "o_proj": {"kernel": ("layers", "mlp", "embed")},
    }


def mamba_params_per_layer(cfg) -> int:
    H, C, N = cfg.hidden_size, cfg.mamba_d_inner, cfg.mamba_d_state
    K, R = cfg.mamba_d_conv, cfg.resolved_dt_rank
    return (H * 2 * C + K * C + C + C * (R + 2 * N) + R + 2 * N
            + R * C + C + N * C + C + C * H)


def mamba_inputs(x, lp, cfg):
    """in_proj of the normed state: (u before the convolution, z), (.., C)."""
    uz = _mm(x, lp["in_proj"]["kernel"], cfg.linear_precision)
    return jnp.split(uz, 2, axis=-1)


def mamba_selection(u, lp, cfg):
    """From u after the convolution and silu: (delta (.., C), B (.., N),
    C (.., N)) in float32, and A (N, C)."""
    N, R = cfg.mamba_d_state, cfg.resolved_dt_rank
    eps = cfg.rms_norm_eps
    sel = _mm(u, lp["x_proj"]["kernel"], cfg.linear_precision)
    dt, b, c = jnp.split(sel, [R, R + N], axis=-1)
    dt = rms_norm(dt, lp["dt_norm"]["scale"], eps)
    b = rms_norm(b, lp["b_norm"]["scale"], eps)
    c = rms_norm(c, lp["c_norm"]["scale"], eps)
    dt = _mm(dt, lp["dt_proj"]["kernel"], cfg.linear_precision)
    delta = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"].astype(F32))
    a = -jnp.exp(lp["A_log"].astype(F32))
    return delta, b.astype(F32), c.astype(F32), a


def mamba_output(y, u, z, lp, cfg):
    """(y + D * u) * silu(z) through o_proj. y (.., C) float32: the scan's."""
    y = y + lp["D"].astype(F32) * u.astype(F32)
    y = (y * jax.nn.silu(z.astype(F32))).astype(u.dtype)
    return _mm(y, lp["o_proj"]["kernel"], cfg.linear_precision)


def mamba_block(h, lp, cfg, positions, constrain):
    """Pre-norm mixer with residual over whole sequences h (B, S, H), every
    document from a zero state (`positions` restart at 0 in a packed row)."""
    x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_norm_eps,
                 cfg.zero_centered_norm)
    u, z = mamba_inputs(x, lp, cfg)
    u = jax.nn.silu(causal_conv(
        u, lp["conv"]["kernel"], lp["conv"]["bias"], positions)).astype(h.dtype)
    delta, b, c, a = mamba_selection(u, lp, cfg)
    y = selective_scan(u, delta, a, b, c, positions)
    h = h + mamba_output(y, u, z, lp, cfg)
    return constrain(h, ("act_batch", "act_seq", "act_embed"))
