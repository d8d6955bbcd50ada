"""The MoE block: gate + routed experts + shared experts.

The analog of the reference `MoE` module (reference: nemo_automodel/
components/moe/layers.py:611-793): routed expert output plus an
always-active shared-expert MLP, aux loss surfaced to the training loss.

Aux-loss contract (the `MoEAuxLossAutoScaler` analog, reference:
moe/megatron/moe_utils.py:569): each layer's aux loss is O(1). Training
losses in this framework are SUM cross-entropy later divided by the global
label-token count, so the aux term must be multiplied by that count before
joining the sum — use loss/utils.py `combine_losses`, which preserves the
reference's effective aux_loss_coeff at any scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.experts import (
    compute_capacity,
    dispatch_tensors,
    expert_param_specs,
    experts_forward,
    experts_forward_dropless,
    experts_forward_dropless_ep,
    init_experts,
)
from automodel_tpu.moe.gate import gate_forward, gate_param_specs, init_gate


def init_moe(cfg: MoEConfig, hidden_size: int, rng: jax.Array) -> dict:
    kg, ke, ks = jax.random.split(rng, 3)
    params = {
        "gate": init_gate(cfg, hidden_size, kg),
        "experts": init_experts(cfg, hidden_size, ke),
    }
    if cfg.n_shared_experts > 0:
        Hs = cfg.shared_intermediate
        std_in, std_out = hidden_size ** -0.5, Hs ** -0.5
        k1, k2, k3 = jax.random.split(ks, 3)
        params["shared"] = {
            "up_proj": {"kernel": std_in * jax.random.truncated_normal(k2, -3, 3, (hidden_size, Hs))},
            "down_proj": {"kernel": std_out * jax.random.truncated_normal(k3, -3, 3, (Hs, hidden_size))},
        }
        if cfg.shared_expert_is_gated:
            params["shared"]["gate_proj"] = {
                "kernel": std_in * jax.random.truncated_normal(k1, -3, 3, (hidden_size, Hs))
            }
        if cfg.shared_expert_gated:
            params["shared"]["gate"] = {
                "kernel": std_in * jax.random.truncated_normal(
                    jax.random.fold_in(ks, 9), -3, 3, (hidden_size, 1)
                )
            }
    return params


def moe_param_specs(cfg: MoEConfig) -> dict:
    specs = {
        "gate": gate_param_specs(cfg),
        "experts": expert_param_specs(cfg),
    }
    if cfg.n_shared_experts > 0:
        specs["shared"] = {
            "up_proj": {"kernel": ("embed", "mlp")},
            "down_proj": {"kernel": ("mlp", "embed")},
        }
        if cfg.shared_expert_is_gated:
            specs["shared"]["gate_proj"] = {"kernel": ("embed", "mlp")}
        if cfg.shared_expert_gated:
            specs["shared"]["gate"] = {"kernel": ("embed", None)}
    return specs


def moe_forward(
    params: dict,
    cfg: MoEConfig,
    x: jnp.ndarray,  # (B, S, H)
    constrain=None,
    token_mask: jnp.ndarray | None = None,  # (B, S) bool
    mesh_ctx=None,
    forced_indices: jnp.ndarray | None = None,  # (B*S, K) routing replay
    scope: str = "moe",  # named-scope prefix: the serve step passes its own
) -> tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Returns (out (B,S,H), aux_loss scalar, stats). stats["indices"] is
    the (T,K) selection — capture it for routing replay (R3); a layer that
    holds a share of its experts adds `share_live`, its live (row, expert)
    pairs, and `share_whole`, whether its dispatch ran the whole path
    (`experts_forward_dropless`). The three
    sublayers run under `<scope>.route`, `.experts` and `.shared` named
    scopes (metadata only), so a profiler trace tells them apart."""
    B, S, H = x.shape
    flat = x.reshape(B * S, H)
    flat_mask = token_mask.reshape(B * S) if token_mask is not None else None
    with jax.named_scope(f"{scope}.route"):
        weights, indices, aux_loss, stats = gate_forward(
            params["gate"], cfg, flat, flat_mask, forced_indices
        )
    stats = {**stats, "indices": indices}
    with jax.named_scope(f"{scope}.experts"):
        if cfg.dispatcher == "dropless":
            if mesh_ctx is not None and mesh_ctx.sizes["ep"] > 1:
                if not cfg.holds_all_experts:
                    raise NotImplementedError(
                        "a share of the experts (n_held_experts) under ep > 1: "
                        "the share IS one rank's part of an expert-parallel "
                        "layer, held without its exchange"
                    )
                routed = experts_forward_dropless_ep(
                    params["experts"], cfg, flat, weights, indices, mesh_ctx
                )
            elif cfg.holds_all_experts:
                routed = experts_forward_dropless(
                    params["experts"], cfg, flat, weights, indices, mesh_ctx
                )
            else:
                routed, live, whole = experts_forward_dropless(
                    params["experts"], cfg, flat, weights, indices, mesh_ctx,
                    with_live=True,
                )
                stats.update(share_live=live, share_whole=whole)
        else:
            capacity = compute_capacity(cfg, B * S)
            dispatch, combine = dispatch_tensors(cfg, indices, weights, capacity)
            routed = experts_forward(params["experts"], cfg, flat, dispatch, combine, constrain)
    out = routed
    if cfg.n_shared_experts > 0:
        from automodel_tpu.moe.experts import shared_expert_forward

        with jax.named_scope(f"{scope}.shared"):
            out = out + shared_expert_forward(params["shared"], cfg, flat)
    return out.reshape(B, S, H).astype(x.dtype), aux_loss, stats
