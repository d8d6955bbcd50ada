"""MoE decoder LM — the engine behind Qwen3-MoE / Mixtral / DeepSeek-style
sparse models.

The analog of the reference's MoE model zoo (reference: nemo_automodel/
components/models/deepseek_v3/model.py:45-263 `DeepseekV3Model`,
qwen3_moe, glm4_moe …). Structure: the first `first_k_dense` layers are
dense decoder layers, the rest replace the gated MLP with the MoE block —
two stacked-layer scans, each rematerialized. Aux (load-balance) loss rides
the scan carry and is returned next to the logits; the recipe adds it to
the CE loss (the `MoEAuxLossAutoScaler` role, reference: moe/megatron/
moe_utils.py:569, without autograd-function tricks).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.layers import (
    dense_init,
    embed_init,
    scan_layers_windowed,
)
from automodel_tpu.models.llm.decoder import (
    TransformerConfig,
    _stack,
    attention_block,
    attention_layer_specs,
    init_attention_layers,
    layer_windows,
    make_freq_for,
    mlp_block,
    unembed,
    _make_constrain,
)
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.layer import init_moe, moe_forward, moe_param_specs
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import rope_frequencies

def deepstack_inject(h, gidx, deepstack_embeds):
    """Add the gidx-th deepstack visual residual when gidx < K (reference:
    qwen3_vl_moe/model.py:419 _deepstack_process — the embeds arrive
    pre-scattered over the sequence, zeros off-image). Shared by the
    training forward and the KV-cache generate prefill, which must inject
    identically for decode to match teacher forcing."""
    if deepstack_embeds is None:
        return h
    K = deepstack_embeds.shape[0]
    inj = jax.lax.dynamic_index_in_dim(
        deepstack_embeds, jnp.clip(gidx, 0, K - 1), 0, keepdims=False
    )
    return h + jnp.where(gidx < K, inj.astype(h.dtype), 0.0)


#: Attention (incl. MLA/DSA) masks by position/segment and MoE routing is
#: per-token, so the CP load-balanced permuted layout is transparent —
#: EXCEPT the MTP head, which shifts in layout order; the recipe gates the
#: permutation on mtp_num_layers == 0.
CP_PERMUTATION_SAFE = True


@dataclasses.dataclass(frozen=True)
class MoETransformerConfig(TransformerConfig):
    moe: MoEConfig = dataclasses.field(default_factory=MoEConfig)
    first_k_dense: int = 0  # deepseek first_k_dense_replace
    mtp_num_layers: int = 0      # depth-1 MTP head when > 0
    mtp_loss_coeff: float = 0.1  # weight of the MTP CE term

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    def flops_per_token(self, seq_len: int) -> float:
        """Activated-params FLOPs/token for MFU (routed experts count k/E)."""
        D = self.resolved_head_dim
        H = self.hidden_size
        attn_params = self.attn_params_per_layer()
        dense_mlp = 3 * H * self.intermediate_size
        moe_mlp = (
            3 * H * self.moe.moe_intermediate_size * self.moe.experts_per_token
            + 3 * H * self.moe.shared_intermediate * (1 if self.moe.n_shared_experts else 0)
            + H * self.moe.n_routed_experts  # router
        )
        n_active = (
            self.vocab_size * H * (1 if self.tie_word_embeddings else 2)
            + self.num_layers * attn_params
            + self.first_k_dense * dense_mlp
            + self.num_moe_layers * moe_mlp
        )
        attn_flops = 6 * self.num_layers * self.num_heads * D * seq_len
        return 6.0 * n_active + attn_flops


def init(cfg: MoETransformerConfig, rng: jax.Array) -> dict:
    H, I = cfg.hidden_size, cfg.intermediate_size
    ks = jax.random.split(rng, 6)
    params: dict = {
        "embed": {"embedding": embed_init(ks[0], (cfg.vocab_size, H))},
        "final_norm": {"scale": jnp.ones((H,))},
    }
    if cfg.first_k_dense > 0:
        L = cfg.first_k_dense
        kg, ku, kd = jax.random.split(ks[2], 3)
        dense_layers = init_attention_layers(cfg, ks[1], L)
        dense_layers.update(
            {
                "gate_proj": {"kernel": _stack(dense_init, kg, (H, I), L)},
                "up_proj": {"kernel": _stack(dense_init, ku, (H, I), L)},
                "down_proj": {"kernel": _stack(dense_init, kd, (I, H), L)},
            }
        )
        params["dense_layers"] = dense_layers
    Lm = cfg.num_moe_layers
    moe_layers = init_attention_layers(cfg, ks[3], Lm)
    moe_keys = jax.random.split(ks[4], Lm)
    moe_stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[init_moe(cfg.moe, H, k) for k in moe_keys]
    )
    moe_layers["moe"] = moe_stacked
    params["moe_layers"] = moe_layers
    if cfg.mtp_num_layers > 0:
        from automodel_tpu.models.moe_lm.mtp import init_mtp

        params["mtp"] = init_mtp(cfg, jax.random.fold_in(rng, 777))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": dense_init(ks[5], (H, cfg.vocab_size))}
    return params


def param_specs(cfg: MoETransformerConfig) -> dict:
    specs: dict = {
        "embed": {"embedding": ("vocab", "embed")},
        "final_norm": {"scale": ("norm",)},
    }
    mlp_specs = {
        "gate_proj": {"kernel": ("layers", "embed", "mlp")},
        "up_proj": {"kernel": ("layers", "embed", "mlp")},
        "down_proj": {"kernel": ("layers", "mlp", "embed")},
    }
    if cfg.first_k_dense > 0:
        d = attention_layer_specs(cfg)
        d.update(mlp_specs)
        specs["dense_layers"] = d
    m = attention_layer_specs(cfg)
    # prepend the stacked-layers axis to every moe param spec
    m["moe"] = jax.tree.map(
        lambda s: ("layers",) + s,
        moe_param_specs(cfg.moe),
        is_leaf=lambda x: isinstance(x, tuple),
    )
    specs["moe_layers"] = m
    if cfg.mtp_num_layers > 0:
        from automodel_tpu.models.moe_lm.mtp import mtp_param_specs

        specs["mtp"] = mtp_param_specs(cfg)
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = {"kernel": ("embed", "vocab")}
    return specs


def _pp_moe_layer_setup(moe_layers_params, cfg: MoETransformerConfig, mesh_ctx, freq_for):
    """Per-stage MoE layer fn for the pipeline executors (parallel/pp.py).

    The MoE analog of llm.decoder._pp_layer_setup: inside the pipeline
    shard_map every collective is manual — attention psums its o_proj over
    `tp`, and the dropless expert dispatch issues its all-to-all over `ep`
    confined to THIS stage's step, so it overlaps with other stages'
    compute instead of fencing the whole program (the PP×EP composition,
    TorchTitan-style).

    Layer contract (pp.py `layer_aux=True` / `aux_scale` mode):
      pl_layer(h, lp, pos, seg[, token_mask]) ->
        (h, aux_scalar, {"tokens_per_expert": (E,)})
    aux is this layer's load-balance loss over the shard's LOCAL tokens; the
    executors psum over (data axes, pp). The GPipe forward threads the
    optional per-microbatch token_mask (pad tokens excluded from routing /
    aux, matching the GSPMD scan); the explicit 1F1B/ZB schedules do not —
    pad tokens route normally there (their CE contribution is still masked
    by labels == -100 in the head loss).

    Returns (layers_in, lspecs, pl_layer, extras_specs).
    """
    from jax.sharding import PartitionSpec as P

    from automodel_tpu.moe.experts import (
        dropless_ep_shardmap_body,
        experts_forward_dropless,
        shared_expert_forward,
    )
    from automodel_tpu.moe.gate import gate_forward

    windows = layer_windows(cfg)
    if len(set(windows)) != 1:
        raise NotImplementedError(
            "MoE pipeline with mixed per-layer sliding windows; use the "
            "GSPMD (non-pipelined) path for this model"
        )
    tp = mesh_ctx.sizes["tp"]
    ep = mesh_ctx.sizes["ep"]
    moe_cfg = cfg.moe
    if cfg.attention_type == "mla" and (tp > 1 or mesh_ctx.sizes["cp"] > 1):
        raise NotImplementedError(
            "pp×tp / pp×cp with MLA attention: the manual-collective layer "
            "mode is implemented for standard GQA attention only"
        )
    if moe_cfg.dispatcher != "dropless":
        raise NotImplementedError(
            "MoE inside the pipeline shard_map requires the dropless "
            "dispatcher (the capacity einsum path relies on GSPMD to place "
            "its all-to-all); set model.moe_dispatcher: dropless"
        )
    if moe_cfg.n_routed_experts % max(ep, 1) != 0:
        raise ValueError(
            f"n_routed_experts={moe_cfg.n_routed_experts} not divisible by "
            f"ep={ep}"
        )
    if tp > 1:
        if cfg.num_heads % tp or cfg.num_kv_heads % tp:
            raise ValueError(
                f"pp×tp needs num_heads={cfg.num_heads}, "
                f"num_kv_heads={cfg.num_kv_heads} divisible by tp={tp}"
            )
        if moe_cfg.n_shared_experts > 0 and moe_cfg.shared_intermediate % tp:
            raise ValueError(
                f"pp×tp needs shared_intermediate={moe_cfg.shared_intermediate} "
                f"divisible by tp={tp}"
            )
        cfg_pl = dataclasses.replace(
            cfg,
            num_heads=cfg.num_heads // tp,
            num_kv_heads=cfg.num_kv_heads // tp,
            head_dim=cfg.resolved_head_dim,  # pin before num_heads changes
        )
    else:
        cfg_pl = cfg
    window = windows[0]
    identity = lambda x, axes: x  # noqa: E731  (GSPMD constraints inert here)

    def pl_layer(hh, lp, pos, sg, tok_mask=None):
        h = attention_block(
            hh, lp, cfg_pl, pos, sg, freq_for(window), identity, window,
            mesh_ctx, manual=True,
        )
        x = rms_norm(
            h, lp["post_attn_norm"]["scale"], cfg.rms_norm_eps,
            cfg.zero_centered_norm,
        )
        B, S, H = x.shape
        flat = x.reshape(B * S, H)
        mp = lp["moe"]
        weights, indices, aux, stats = gate_forward(
            mp["gate"], moe_cfg, flat,
            token_mask=None if tok_mask is None else tok_mask.reshape(B * S),
        )
        if ep > 1:
            routed = dropless_ep_shardmap_body(
                mp["experts"], moe_cfg, flat, weights, indices, axis_name="ep"
            )
        else:
            routed = experts_forward_dropless(
                mp["experts"], moe_cfg, flat, weights, indices
            )
        out = routed
        if moe_cfg.n_shared_experts > 0:
            out = out + shared_expert_forward(
                mp["shared"], moe_cfg, flat,
                tp_axis="tp" if tp > 1 else None,  # mlp-dim slices → psum
            )
        h = h + out.reshape(B, S, H).astype(h.dtype)
        return h, aux, {"tokens_per_expert": stats["tokens_per_expert"]}

    lspecs = param_specs(cfg)["moe_layers"]
    extras_specs = {"tokens_per_expert": P("pp", None)}  # stacked layer dim
    return moe_layers_params, lspecs, pl_layer, extras_specs


def _pp_pipeline_compatible(cfg: MoETransformerConfig, mesh_ctx) -> bool:
    """Whether the pipelined (shard_map) MoE path covers this config; the
    out-of-scope remainder falls back to the GSPMD layer scan."""
    use_dsa = cfg.attention_type == "mla" and cfg.dsa_index_topk is not None
    return (
        cfg.first_k_dense == 0
        and cfg.moe.dispatcher == "dropless"
        and not use_dsa
        and len(set(layer_windows(cfg))) == 1
        and not (
            cfg.attention_type == "mla"
            and (mesh_ctx.sizes["tp"] > 1 or mesh_ctx.sizes["cp"] > 1)
        )
        and cfg.moe.n_routed_experts % mesh_ctx.sizes["ep"] == 0
    )


def forward(
    params: dict,
    cfg: MoETransformerConfig,
    input_ids: jnp.ndarray,
    *,
    positions: jnp.ndarray | None = None,
    segment_ids: jnp.ndarray | None = None,
    mesh_ctx=None,
    rules=None,
    return_hidden: bool = False,
    token_mask: jnp.ndarray | None = None,  # (B,S) bool; False = pad tokens
    return_stats: bool = False,
    return_routing: bool = False,           # stats["routing"] (Lm, B*S, K)
    routing_override: jnp.ndarray | None = None,  # replay a captured routing
    return_aux_hidden: tuple | None = None,  # EAGLE-3 target-side capture
    inputs_embeds: jnp.ndarray | None = None,  # (B,S,H) — VLM merged embeds
    rope_angles: jnp.ndarray | None = None,    # (B,S,rope_dim/2) MRoPE angles
    deepstack_embeds: jnp.ndarray | None = None,  # (K,B,S,H) injected after layer k<K
) -> tuple:
    """Returns (logits-or-hidden, aux_loss[, stats]).

    `return_aux_hidden=(lo, mid, hi)` additionally captures those layers'
    outputs (global layer indices over dense+moe layers, pre-final-norm),
    stacked (k, B, S, H) — the EAGLE-3 aux-hidden hook (same contract as the
    dense decoder). The first return becomes (out, aux_hidden).

    stats["tokens_per_expert"] is (num_moe_layers, E) — feed it to
    `apply_gate_bias_update` after the optimizer step for DeepSeek aux-free
    balancing (reference: train_ft.py:1164 `update_moe_gate_bias`) and to
    moe load-balance metrics.

    Routing replay (R3, reference: components/moe/router_replay.py): run
    once with `return_routing=True`, pass stats["routing"] back as
    `routing_override` on the training forward — the discrete expert
    selection is pinned while scores/weights recompute from live router
    weights (RL rollout/training mismatch removal).
    """
    from automodel_tpu.models.common.layers import cast_params

    if cfg.num_passes != 1:
        raise NotImplementedError(
            "a looped MoE decoder (num_passes > 1): only the dense decoder's "
            "forward walks its stack more than once"
        )
    params = cast_params(params, cfg.dtype)  # fp32 master → compute dtype
    B, S = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    constrain = _make_constrain(mesh_ctx, rules)

    if inputs_embeds is not None:
        h = inputs_embeds.astype(cfg.dtype)
    else:
        # FSDP-unshard the table's embed dim before the gather (see llm/decoder)
        tbl = constrain(params["embed"]["embedding"], ("vocab", None))
        h = jnp.take(tbl, input_ids, axis=0).astype(cfg.dtype)
        if cfg.embed_scale != 1.0:
            h = h * jnp.asarray(cfg.embed_scale, cfg.dtype)
    h = constrain(h, ("act_batch", "act_seq", "act_embed"))

    inv_freq = rope_frequencies(cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
    freq_for = make_freq_for(cfg, inv_freq)
    if rope_angles is not None:
        # qwen-vl MRoPE: per-token angles precomputed by the VL wrapper
        # (apply_rope detects the ndim>=2 form); window-local thetas don't
        # apply to mrope models
        freq_for = lambda w: rope_angles  # noqa: E731
    windows = layer_windows(cfg)
    Lm, E = cfg.num_moe_layers, cfg.moe.n_routed_experts

    pp_ok = (
        mesh_ctx is not None
        and mesh_ctx.sizes["pp"] > 1
        and _pp_pipeline_compatible(cfg, mesh_ctx)
        and routing_override is None
        and not return_routing
        and return_aux_hidden is None
        and deepstack_embeds is None
        and rope_angles is None
    )
    if pp_ok:
        # Pipelined GPipe forward: one shard_map over the whole mesh, expert
        # A2A confined to each stage's step (see _pp_moe_layer_setup). The
        # GSPMD scan below stays as the fallback for out-of-scope configs
        # (first_k_dense > 0, DSA, capacity dispatcher, deepstack, replay).
        from automodel_tpu.parallel.pp import pipeline_layers

        seg = segment_ids if segment_ids is not None else jnp.zeros_like(positions)
        layers_in, lspecs, pl_layer, extras_specs = _pp_moe_layer_setup(
            params["moe_layers"], cfg, mesh_ctx, freq_for
        )
        h, aux_loss, extras = pipeline_layers(
            h, positions, seg, layers_in, pl_layer, mesh_ctx,
            cfg.pipeline_microbatches, remat_policy=cfg.remat_policy,
            param_logical_specs=lspecs, layer_aux=True,
            extras_specs=extras_specs, token_mask=token_mask,
        )
        h = constrain(h, ("act_batch", "act_seq", "act_embed"))
        h = rms_norm(
            h, params["final_norm"]["scale"], cfg.rms_norm_eps,
            cfg.zero_centered_norm,
        )
        out = h if return_hidden else unembed(params, cfg, h)
        if return_stats:
            return out, aux_loss, {
                "tokens_per_expert": extras["tokens_per_expert"]
            }
        return out, aux_loss

    def _deepstack(h, gidx):
        return deepstack_inject(h, gidx, deepstack_embeds)

    # DSA: lightning-indexer sparse MLA returns an indexer-KL aux that rides
    # the same loss carry as the MoE balance loss (reference: deepseek_v4).
    # GLM IndexShare (reference: glm_moe_dsa/model.py:50): per-layer
    # indexer_types; "shared" layers reuse the running top-k selection, which
    # rides the layer-scan carry, with a traced 0/1 flag riding the xs.
    use_dsa = cfg.attention_type == "mla" and cfg.dsa_index_topk is not None
    idx_types = getattr(cfg, "dsa_indexer_types", None)
    index_share = use_dsa and idx_types is not None
    if index_share:
        assert len(idx_types) == cfg.num_layers, (len(idx_types), cfg.num_layers)
        assert idx_types[0] == "full", "IndexShare: layer 0 must run its indexer"
        idx_flags = jnp.asarray(
            [1 if t == "full" else 0 for t in idx_types], jnp.int32
        )
    else:
        idx_flags = jnp.ones((cfg.num_layers,), jnp.int32)
    # the running selection ((B,S,S) bool for the oracle, (B,S,K) indices
    # for the chunked path) rides the carry ONLY under IndexShare; plain DSA
    # would drag a dead S²-scale buffer through every layer boundary
    if index_share:
        from automodel_tpu.models.llm.mla import dsa_sel_init

        sel0 = dsa_sel_init(cfg, B, S)
    else:
        sel0 = jnp.zeros((1, 1, 1), bool)

    def _attn(h, lp, window, sel, iflag):
        if use_dsa:
            from automodel_tpu.models.llm.mla import mla_sparse_attention_block

            h, aux, sel_new = mla_sparse_attention_block(
                h, lp, cfg, positions, segment_ids, inv_freq, constrain,
                token_mask=token_mask,
                prev_sel=sel if index_share else None,
                indexer_flag=iflag if index_share else None,
            )
            return h, aux, (sel_new if index_share else sel)
        h = attention_block(
            h, lp, cfg, positions, segment_ids, freq_for(window), constrain,
            window, mesh_ctx,
        )
        return h, jnp.float32(0.0), sel

    cap_ids = tuple(return_aux_hidden) if return_aux_hidden is not None else None

    def _capture(auxbuf, gidx, y):
        for j, lid in enumerate(cap_ids):
            auxbuf = auxbuf.at[j].set(jnp.where(gidx == lid, y, auxbuf[j]))
        return auxbuf

    def dense_layer(carry, xs, window):
        h, aux, stats, routing, auxbuf, sel = carry
        lp, gidx, iflag = xs
        h, idx_aux, sel = _attn(h, lp, window, sel, iflag)
        h = mlp_block(h, lp, cfg, constrain)
        h = _deepstack(h, gidx)
        if cap_ids is not None:
            auxbuf = _capture(auxbuf, gidx, h)
        return (h, aux + idx_aux, stats, routing, auxbuf, sel)

    K = cfg.moe.experts_per_token
    replay = routing_override is not None

    def moe_layer(carry, xs, window):
        h, aux, stats, routing, auxbuf, sel = carry
        lp, idx, iflag = xs
        h, idx_aux, sel = _attn(h, lp, window, sel, iflag)
        aux = aux + idx_aux
        x = rms_norm(h, lp["post_attn_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
        forced = routing_override[idx] if replay else None
        moe_out, layer_aux, layer_stats = moe_forward(
            lp["moe"], cfg.moe, x, constrain, token_mask=token_mask,
            mesh_ctx=mesh_ctx, forced_indices=forced,
        )
        h = constrain(h + moe_out, ("act_batch", "act_seq", "act_embed"))
        h = _deepstack(h, idx + cfg.first_k_dense)
        stats = jax.lax.dynamic_update_index_in_dim(
            stats, layer_stats["tokens_per_expert"], idx, 0
        )
        routing = jax.lax.dynamic_update_index_in_dim(
            routing, layer_stats["indices"], idx, 0
        )
        if cap_ids is not None:
            auxbuf = _capture(auxbuf, idx + cfg.first_k_dense, h)
        return (h, aux + layer_aux, stats, routing, auxbuf, sel)

    stats0 = jnp.zeros((Lm, E), jnp.float32)
    routing0 = jnp.zeros((Lm, B * S, K), jnp.int32)
    auxbuf0 = (
        jnp.zeros((len(cap_ids),) + h.shape, h.dtype)
        if cap_ids is not None
        else jnp.zeros((0,) + h.shape, h.dtype)
    )
    carry = (h, jnp.float32(0.0), stats0, routing0, auxbuf0, sel0)
    if cfg.first_k_dense > 0:
        carry = scan_layers_windowed(
            dense_layer, carry,
            (
                params["dense_layers"],
                jnp.arange(cfg.first_k_dense),
                idx_flags[: cfg.first_k_dense],
            ),
            windows[: cfg.first_k_dense],
            remat_policy=cfg.remat_policy, unroll=cfg.scan_unroll,
        )
    carry = scan_layers_windowed(
        moe_layer, carry,
        (params["moe_layers"], jnp.arange(Lm), idx_flags[cfg.first_k_dense :]),
        windows[cfg.first_k_dense :],
        remat_policy=cfg.remat_policy, unroll=cfg.scan_unroll,
    )
    h, aux_loss, tokens_per_expert, routing, aux_hidden, _sel = carry

    h = rms_norm(h, params["final_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    out = h if return_hidden else unembed(params, cfg, h)
    if cap_ids is not None:
        out = (out, aux_hidden)
    if return_stats:
        stats_out = {"tokens_per_expert": tokens_per_expert}
        if return_routing:
            stats_out["routing"] = routing
        return out, aux_loss, stats_out
    return out, aux_loss


def apply_gate_bias_update(params: dict, cfg: MoETransformerConfig, tokens_per_expert) -> dict:
    """DeepSeek aux-free balancing across all MoE layers at once
    (reference: layers.py:463 update_bias + train_ft.py:1164).
    tokens_per_expert: (num_moe_layers, E) from forward(..., return_stats=True).
    """
    gate = params["moe_layers"]["moe"]["gate"]
    if "e_score_bias" not in gate:
        return params
    err = tokens_per_expert.mean(-1, keepdims=True) - tokens_per_expert
    new_bias = gate["e_score_bias"] + cfg.moe.gate_bias_update_speed * jnp.sign(err)
    new_gate = {**gate, "e_score_bias": new_bias}
    new_moe = {**params["moe_layers"]["moe"], "gate": new_gate}
    new_layers = {**params["moe_layers"], "moe": new_moe}
    return {**params, "moe_layers": new_layers}
