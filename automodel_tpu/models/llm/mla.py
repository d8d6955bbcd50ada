"""Multi-head Latent Attention (MLA) — the DeepSeek V2/V3 attention.

The analog of the reference's MLA implementation inside
nemo_automodel/components/models/deepseek_v3/model.py:45-263 (Block / MLA
layers) — queries and keys/values are projected through low-rank latents;
RoPE applies to a small per-head rope slice plus ONE shared key-rope head:

    q = W_uq · rmsnorm(W_dq · x)            (or a direct W_q when no q rank)
    [c_kv ; k_rope] = W_dkv · x             (kv_lora_rank + qk_rope_head_dim)
    [k_nope ; v] = W_ukv · rmsnorm(c_kv)
    per head:  q = [q_nope ; rope(q_rope)],  k = [k_nope ; rope(k_rope)]

Attention logits use head_dim = qk_nope + qk_rope while values use
v_head_dim — the XLA attention path handles the asymmetric dims natively
(a dedicated Pallas MLA kernel is a later-round optimization; the
reference's TileLang sparse-MLA kernels map to that slot).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from automodel_tpu.models.llm.decoder import _dense
from automodel_tpu.ops.attention import dot_product_attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope


def init_mla_layers(cfg, rng: jax.Array, L: int) -> dict:
    """MLA attention params for a stacked layer block (cfg: TransformerConfig
    with mla_* fields set)."""
    from automodel_tpu.models.llm.decoder import _stack
    from automodel_tpu.models.common.layers import dense_init

    H = cfg.hidden_size
    n = cfg.num_heads
    qk = cfg.mla_qk_nope_head_dim + cfg.mla_qk_rope_head_dim
    ks = jax.random.split(rng, 6)
    layers: dict = {
        "input_norm": {"scale": jnp.ones((L, H))},
        "post_attn_norm": {"scale": jnp.ones((L, H))},
        "kv_down_proj": {
            "kernel": _stack(
                dense_init, ks[0], (H, cfg.mla_kv_lora_rank + cfg.mla_qk_rope_head_dim), L
            )
        },
        "kv_norm": {"scale": jnp.ones((L, cfg.mla_kv_lora_rank))},
        "kv_up_proj": {
            "kernel": _stack(
                dense_init, ks[1],
                (cfg.mla_kv_lora_rank, n * (cfg.mla_qk_nope_head_dim + cfg.mla_v_head_dim)),
                L,
            )
        },
        "o_proj": {"kernel": _stack(dense_init, ks[2], (n * cfg.mla_v_head_dim, H), L)},
    }
    if cfg.mla_q_lora_rank:
        layers["q_down_proj"] = {"kernel": _stack(dense_init, ks[3], (H, cfg.mla_q_lora_rank), L)}
        layers["q_norm"] = {"scale": jnp.ones((L, cfg.mla_q_lora_rank))}
        layers["q_up_proj"] = {
            "kernel": _stack(dense_init, ks[4], (cfg.mla_q_lora_rank, n * qk), L)
        }
    else:
        layers["q_proj"] = {"kernel": _stack(dense_init, ks[5], (H, n * qk), L)}
    if cfg.dsa_index_topk is not None:
        layers["indexer"] = init_indexer(cfg, jax.random.fold_in(rng, 1234), L)
    return layers


def init_indexer(cfg, rng: jax.Array, L: int) -> dict:
    """Fresh lightning-indexer stack — also used to backfill checkpoints
    that predate DSA (reference: deepseek_v4 checkpoints carry indexer.*
    keys; V3-style ones do not). GLM style (dsa_indexer_style="glm")
    projects queries from the q-lora residual and LayerNorms keys."""
    from automodel_tpu.models.llm.decoder import _stack
    from automodel_tpu.models.common.layers import dense_init

    H = cfg.hidden_size
    Hi, Di = cfg.dsa_index_n_heads, cfg.dsa_index_head_dim
    ki = jax.random.split(rng, 3)
    if getattr(cfg, "dsa_indexer_style", "deepseek") == "glm":
        rq = cfg.mla_q_lora_rank or H
        return {
            "wq": {"kernel": _stack(dense_init, ki[0], (rq, Hi * Di), L)},
            "wk": {"kernel": _stack(dense_init, ki[1], (H, Di), L)},
            "k_norm": {"scale": jnp.ones((L, Di)), "bias": jnp.zeros((L, Di))},
            "wgate": {"kernel": _stack(dense_init, ki[2], (H, Hi), L)},
        }
    return {
        "wq": {"kernel": _stack(dense_init, ki[0], (H, Hi * Di), L)},
        "wk": {"kernel": _stack(dense_init, ki[1], (H, Di), L)},
        "wgate": {"kernel": _stack(dense_init, ki[2], (H, Hi), L)},
    }


def mla_layer_specs(cfg) -> dict:
    layers = {
        "input_norm": {"scale": ("layers", "norm")},
        "post_attn_norm": {"scale": ("layers", "norm")},
        "kv_down_proj": {"kernel": ("layers", "embed", None)},  # latent: replicated
        "kv_norm": {"scale": ("layers", "norm")},
        "kv_up_proj": {"kernel": ("layers", None, "heads")},
        "o_proj": {"kernel": ("layers", "heads", "embed")},
    }
    if cfg.mla_q_lora_rank:
        layers["q_down_proj"] = {"kernel": ("layers", "embed", None)}
        layers["q_norm"] = {"scale": ("layers", "norm")}
        layers["q_up_proj"] = {"kernel": ("layers", None, "heads")}
    else:
        layers["q_proj"] = {"kernel": ("layers", "embed", "heads")}
    if cfg.dsa_index_topk is not None:
        layers["indexer"] = {
            "wq": {"kernel": ("layers", "embed", "heads")},
            "wk": {"kernel": ("layers", "embed", None)},
            "wgate": {"kernel": ("layers", "embed", None)},
        }
        if getattr(cfg, "dsa_indexer_style", "deepseek") == "glm":
            layers["indexer"]["wq"] = {"kernel": ("layers", None, "heads")}
            layers["indexer"]["k_norm"] = {
                "scale": ("layers", "norm"), "bias": ("layers", "norm"),
            }
    return layers


def _mla_qkv(x, lp, cfg, positions, constrain, inv_freq):
    """Project normed input to MLA q/k/v (B,S,n,·), the logit scale, and the
    q-lora residual (post q_norm; None without q compression) — the GLM
    indexer's query source."""
    from automodel_tpu.ops.quant import matmul as _mm

    B, S, H = x.shape
    n = cfg.num_heads
    dn, dr, dv = cfg.mla_qk_nope_head_dim, cfg.mla_qk_rope_head_dim, cfg.mla_v_head_dim
    prec = cfg.linear_precision

    q_lat = None
    if cfg.mla_q_lora_rank:
        q_lat = rms_norm(_mm(x, lp["q_down_proj"]["kernel"], prec), lp["q_norm"]["scale"], cfg.rms_norm_eps)
        q = _mm(q_lat, lp["q_up_proj"]["kernel"], prec)
    else:
        q = _mm(x, lp["q_proj"]["kernel"], prec)
    q = q.reshape(B, S, n, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, inv_freq)
    if cfg.mla_qpe_scaling_beta is not None:
        # mistral4 llama4-style scaling (reference: mistral4/model.py:52)
        sc = 1.0 + cfg.mla_qpe_scaling_beta * jnp.log1p(
            jnp.floor(positions.astype(jnp.float32) / cfg.mla_qpe_scaling_orig_max)
        )
        q_rope = q_rope * sc[:, :, None, None].astype(q_rope.dtype)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None))

    kv = _mm(x, lp["kv_down_proj"]["kernel"], prec)  # (B,S, kv_rank + dr)
    c_kv, k_rope = kv[..., : cfg.mla_kv_lora_rank], kv[..., cfg.mla_kv_lora_rank :]
    # shared single-head key rope, broadcast across heads after rotation
    k_rope = apply_rope(k_rope[:, :, None, :], positions, inv_freq)
    c_kv = rms_norm(c_kv, lp["kv_norm"]["scale"], cfg.rms_norm_eps)
    kv_up = _mm(c_kv, lp["kv_up_proj"]["kernel"], prec).reshape(B, S, n, dn + dv)
    k_nope, v = kv_up[..., :dn], kv_up[..., dn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, n, dr))], axis=-1)
    k = constrain(k, ("act_batch", "act_seq", "act_heads", None))
    v = constrain(v, ("act_batch", "act_seq", "act_heads", None))
    scale = cfg.attn_scale if cfg.attn_scale is not None else (dn + dr) ** -0.5
    return q, k, v, scale, q_lat


def resolve_dsa_impl(cfg, seq_len: int) -> str:
    impl = getattr(cfg, "dsa_impl", "auto")
    if impl == "auto":
        return "chunked" if seq_len > 4 * getattr(cfg, "dsa_query_block", 256) else "oracle"
    return impl


def dsa_sel_init(cfg, B: int, S: int):
    """Zero-initialized IndexShare carry for the configured implementation:
    a dense (B,S,S) bool selection for the oracle, (B,S,K) top-k indices
    for the chunked path."""
    if resolve_dsa_impl(cfg, S) == "chunked":
        return jnp.zeros((B, S, min(cfg.dsa_index_topk, S)), jnp.int32)
    return jnp.zeros((B, S, S), bool)


def _indexer_qkw(x, q_lat, lp, cfg, positions):
    """Roped indexer queries (B,S,Hi,Di), keys (B,S,Di) and fp32 gate
    weights (B,S,Hi), canonicalized so that for BOTH styles
    score[t,s] = Σ_h w[t,h] · relu(q[t,h]·k[s]) · Di**-0.5."""
    from automodel_tpu.ops.rope import rope_frequencies

    B, S, H = x.shape
    Hi, Di = cfg.dsa_index_n_heads, cfg.dsa_index_head_dim
    ip = lp["indexer"]
    if getattr(cfg, "dsa_indexer_style", "deepseek") == "glm":
        inv_freq_idx = rope_frequencies(
            cfg.mla_qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling
        )
        qsrc = q_lat if q_lat is not None else x
        q = (qsrc @ ip["wq"]["kernel"].astype(x.dtype)).reshape(B, S, Hi, Di)
        k = x @ ip["wk"]["kernel"].astype(x.dtype)
        mu = jnp.mean(k.astype(jnp.float32), axis=-1, keepdims=True)
        var = jnp.var(k.astype(jnp.float32), axis=-1, keepdims=True)
        k = (k.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + 1e-6)
        k = (k * ip["k_norm"]["scale"].astype(jnp.float32)
             + ip["k_norm"]["bias"].astype(jnp.float32)).astype(x.dtype)
        w = (x @ ip["wgate"]["kernel"].astype(x.dtype)).astype(jnp.float32)
        w = w * (Hi ** -0.5)
    else:
        inv_freq_idx = rope_frequencies(Di, cfg.rope_theta, cfg.rope_scaling)
        q = (x @ ip["wq"]["kernel"].astype(x.dtype)).reshape(B, S, Hi, Di)
        k = x @ ip["wk"]["kernel"].astype(x.dtype)
        w = (x @ ip["wgate"]["kernel"].astype(x.dtype)).astype(jnp.float32)
    q = apply_rope(q, positions, inv_freq_idx)
    k = apply_rope(k[:, :, None, :], positions, inv_freq_idx)[:, :, 0, :]
    return q, k, w


def mla_sparse_attention_block_chunked(
    h, lp, cfg, positions, segment_ids, inv_freq, constrain, token_mask=None,
    prev_idx=None, indexer_flag=None,
):
    """Two-phase sparse MLA without (S,S) materialization (the 32k-context
    DSA path; reference: deepseek_v4/kernels/tilelang_sparse_mla_fwd.py +
    tilelang_indexer_topk — here a blockwise XLA program: `lax.map` over
    query blocks keeps peak memory at O(S·block) while the MXU sees dense
    (block, K) dots).

    Per query block: indexer scores vs all keys → masked top-k indices →
    gather the kv LATENTS (c_kv (K, r) + shared rope key (K, dr)) → absorbed
    attention (scores and values in latent space via the kv up-projection
    halves — the exact-algebra form also used by the decode cache,
    inference/generate._mla_attn_with_cache). Returns (h_out, aux, idx) with
    idx (B, S, K) — the IndexShare carry in index form.
    """
    from automodel_tpu.ops.attention import NEG_INF

    B, S, H = h.shape
    n = cfg.num_heads
    dn, dr, dv = cfg.mla_qk_nope_head_dim, cfg.mla_qk_rope_head_dim, cfg.mla_v_head_dim
    r = cfg.mla_kv_lora_rank
    prec = cfg.linear_precision
    from automodel_tpu.ops.quant import matmul as _mm

    K = min(cfg.dsa_index_topk, S)
    bq = getattr(cfg, "dsa_query_block", 256)
    while S % bq != 0:
        bq //= 2
    nb = S // bq

    x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)

    # full-sequence latents (O(S·(r+dr)) — the whole point of MLA)
    q_lat = None
    if cfg.mla_q_lora_rank:
        q_lat = rms_norm(_mm(x, lp["q_down_proj"]["kernel"], prec), lp["q_norm"]["scale"], cfg.rms_norm_eps)
        q = _mm(q_lat, lp["q_up_proj"]["kernel"], prec)
    else:
        q = _mm(x, lp["q_proj"]["kernel"], prec)
    q = q.reshape(B, S, n, dn + dr)
    q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], positions, inv_freq)

    kv = _mm(x, lp["kv_down_proj"]["kernel"], prec)
    c_kv = rms_norm(kv[..., :r], lp["kv_norm"]["scale"], cfg.rms_norm_eps)
    k_rope = apply_rope(kv[..., r:][:, :, None, :], positions, inv_freq)[:, :, 0, :]

    qi, ki, wi = _indexer_qkw(x, q_lat, lp, cfg, positions)

    W = lp["kv_up_proj"]["kernel"].astype(x.dtype).reshape(r, n, dn + dv)
    w_uk, w_uv = W[..., :dn], W[..., dn:]
    q_abs = jnp.einsum("bsnd,rnd->bsnr", q_nope, w_uk)
    scale = cfg.attn_scale if cfg.attn_scale is not None else (dn + dr) ** -0.5
    Di = cfg.dsa_index_head_dim

    seg = segment_ids if segment_ids is not None else jnp.zeros_like(positions)
    tmask = token_mask if token_mask is not None else jnp.ones((B, S), bool)

    def blk(xs):
        (qa_b, qr_b, qi_b, wi_b, qpos_b, qseg_b, tm_b, pidx_b, flag_or_none) = xs
        # ---- phase 1: indexer scores vs all keys, masked top-k ----
        # head loop (Hi is 2-8): peak stays at one (B, bq, S) buffer instead
        # of the (B, Hi, bq, S) einsum intermediate — at 32k keys that is
        # the difference between ~33MB and ~0.5GB per block
        scores = jnp.zeros(qi_b.shape[:2] + (ki.shape[1],), jnp.float32)
        for hh in range(qi_b.shape[2]):
            d = jnp.einsum(
                "bqd,bsd->bqs", qi_b[:, :, hh], ki,
                preferred_element_type=jnp.float32,
            )
            scores = scores + wi_b[:, :, hh][..., None] * jax.nn.relu(d)
        scores = scores * (Di ** -0.5)  # (B, bq, S) fp32
        adm = jnp.logical_and(
            qpos_b[:, :, None] >= positions[:, None, :],
            qseg_b[:, :, None] == seg[:, None, :],
        ) if cfg.causal else (qseg_b[:, :, None] == seg[:, None, :])
        masked = jnp.where(adm, scores, -jnp.inf)
        top_vals, idx = jax.lax.top_k(masked, K)  # (B, bq, K)
        if flag_or_none is not None:
            run = flag_or_none.astype(bool)
            idx = jnp.where(run, idx, pidx_b)
            # recompute validity/scores at the (possibly replayed) indices
            top_vals = jnp.take_along_axis(masked, idx, axis=-1)
        valid = jnp.isfinite(top_vals)

        # ---- phase 2: gather latents, absorbed attention over K ----
        flat = idx.reshape(B, -1)
        c_sel = jnp.take_along_axis(c_kv, flat[..., None], axis=1).reshape(B, bq, K, r)
        kr_sel = jnp.take_along_axis(k_rope, flat[..., None], axis=1).reshape(B, bq, K, dr)
        s = jnp.einsum("bqnr,bqkr->bqnk", qa_b, c_sel, preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bqnd,bqkd->bqnk", qr_b, kr_sel, preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, :, None, :], s * scale, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out_lat = jnp.einsum("bqnk,bqkr->bqnr", p.astype(c_sel.dtype), c_sel)
        out = jnp.einsum("bqnr,rnd->bqnd", out_lat, w_uv)

        # ---- indexer KL over the selected set ----
        neg = jnp.float32(NEG_INF)
        logq = jax.nn.log_softmax(jnp.where(valid, top_vals, neg), axis=-1)
        pm = jax.lax.stop_gradient(jnp.mean(p, axis=2))  # (B, bq, K) head-avg
        pm = jnp.where(valid, pm, 0.0)
        pm = pm / jnp.maximum(jnp.sum(pm, -1, keepdims=True), 1e-9)
        kl = jnp.sum(pm * (jnp.log(jnp.maximum(pm, 1e-9)) - logq), axis=-1)
        m = tm_b.astype(jnp.float32)
        return out, idx, jnp.sum(kl * m), jnp.sum(m)

    def rs(a):  # (B, S, ...) → (nb, B, bq, ...)
        return jnp.swapaxes(a.reshape(B, nb, bq, *a.shape[2:]), 0, 1)

    xs = (
        rs(q_abs), rs(q_rope), rs(qi), rs(wi), rs(positions), rs(seg), rs(tmask),
        rs(prev_idx) if prev_idx is not None else rs(jnp.zeros((B, S, K), jnp.int32)),
        (jnp.broadcast_to(indexer_flag, (nb,)) if indexer_flag is not None else None),
    )
    if xs[-1] is None:
        xs = xs[:-1]

        def blk_noflag(args):
            return blk(args + (None,))

        out_b, idx_b, kl_b, cnt_b = jax.lax.map(blk_noflag, xs)
    else:
        out_b, idx_b, kl_b, cnt_b = jax.lax.map(blk, xs)

    attn = jnp.swapaxes(out_b, 0, 1).reshape(B, S, n * dv)
    idx = jnp.swapaxes(idx_b, 0, 1).reshape(B, S, K)
    aux = cfg.dsa_indexer_loss_coeff * jnp.sum(kl_b) / jnp.maximum(jnp.sum(cnt_b), 1.0)
    if indexer_flag is not None:
        aux = jnp.where(indexer_flag.astype(bool), aux, 0.0)

    h = h + _dense(attn, {"kernel": lp["o_proj"]["kernel"]}, prec)
    return constrain(h, ("act_batch", "act_seq", "act_embed")), aux, idx


def mla_sparse_attention_block(
    h, lp, cfg, positions, segment_ids, inv_freq, constrain, token_mask=None,
    prev_sel=None, indexer_flag=None,
):
    """DSA: lightning-indexer top-k sparse MLA (reference:
    deepseek_v4/layers.py; mask-based like its SDPA fallback path;
    glm_moe_dsa/layers.py for the GLM indexer + IndexShare variant).

    Returns (h_out, indexer_kl_aux, sel) — the aux rides the MoE decoder's
    loss carry; it is the ONLY gradient path into the indexer (hard top-k).
    `token_mask` (B,S) excludes pad queries from the indexer KL.

    IndexShare (GLM-5.x): `indexer_flag` is a traced 0/1 scalar riding the
    layer scan — 1 runs this layer's indexer, 0 reuses `prev_sel` (the most
    recent full layer's selection) and contributes no indexer KL. The
    returned `sel` is the running selection for the next layer.

    Implementation dispatch (cfg.dsa_impl): this dense-mask oracle, or the
    blockwise two-phase `mla_sparse_attention_block_chunked` for long
    sequences (prev_sel is then (B,S,K) indices)."""
    if resolve_dsa_impl(cfg, h.shape[1]) == "chunked":
        return mla_sparse_attention_block_chunked(
            h, lp, cfg, positions, segment_ids, inv_freq, constrain,
            token_mask=token_mask, prev_idx=prev_sel, indexer_flag=indexer_flag,
        )
    from automodel_tpu.ops.attention import NEG_INF, make_attention_mask
    from automodel_tpu.ops.dsa import (
        indexer_kl_loss,
        indexer_scores,
        indexer_scores_glm,
        topk_select_mask,
    )
    from automodel_tpu.ops.rope import rope_frequencies

    B, S, H = h.shape
    x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    q, k, v, scale, q_lat = _mla_qkv(x, lp, cfg, positions, constrain, inv_freq)

    base_mask = make_attention_mask(
        S, S, causal=cfg.causal,
        q_segment_ids=segment_ids, kv_segment_ids=segment_ids,
        q_positions=positions, kv_positions=positions,
    )
    if base_mask is None:
        base_mask = jnp.ones((1, S, S), bool)

    if getattr(cfg, "dsa_indexer_style", "deepseek") == "glm":
        # rope applies to the FIRST qk_rope_head_dim channels only (GLM)
        inv_freq_idx = rope_frequencies(
            cfg.mla_qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling
        )
        scores = indexer_scores_glm(
            x, q_lat if q_lat is not None else x, lp["indexer"],
            cfg.dsa_index_n_heads, cfg.dsa_index_head_dim,
            positions, inv_freq_idx,
        )
    else:
        # same rope scaling as the main path — a yarn-scaled model's indexer
        # must agree with its attention about long-context positions
        inv_freq_idx = rope_frequencies(
            cfg.dsa_index_head_dim, cfg.rope_theta, cfg.rope_scaling
        )
        scores = indexer_scores(
            x, lp["indexer"], cfg.dsa_index_n_heads, cfg.dsa_index_head_dim,
            positions, inv_freq_idx,
        )
    sel = topk_select_mask(scores, base_mask, cfg.dsa_index_topk)
    if indexer_flag is not None and prev_sel is not None:
        run = indexer_flag.astype(bool)
        sel = jnp.where(run, sel, prev_sel)

    logits = jnp.einsum("bsnd,btnd->bnst", q, k, preferred_element_type=jnp.float32) * scale
    logits = jnp.where(sel[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnst,btnv->bsnv", probs.astype(v.dtype), v)

    aux = cfg.dsa_indexer_loss_coeff * indexer_kl_loss(
        scores, jnp.mean(probs, axis=1), sel, token_mask=token_mask
    )
    if indexer_flag is not None:
        aux = jnp.where(indexer_flag.astype(bool), aux, 0.0)

    attn = out.reshape(B, S, cfg.num_heads * cfg.mla_v_head_dim)
    h = h + _dense(attn, {"kernel": lp["o_proj"]["kernel"]}, cfg.linear_precision)
    return constrain(h, ("act_batch", "act_seq", "act_embed")), aux, sel


def mla_attention_block(h, lp, cfg, positions, segment_ids, inv_freq, constrain, sliding_window, mesh_ctx=None):
    """Pre-norm MLA attention with residual (drop-in for attention_block)."""
    B, S, H = h.shape
    n = cfg.num_heads
    dv = cfg.mla_v_head_dim

    x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    q, k, v, scale, _ = _mla_qkv(x, lp, cfg, positions, constrain, inv_freq)

    if mesh_ctx is not None and mesh_ctx.sizes["cp"] > 1:
        from automodel_tpu.parallel.cp import ring_dot_product_attention

        attn = ring_dot_product_attention(
            q, k, v, positions, segment_ids, mesh_ctx,
            causal=cfg.causal,
            sliding_window=sliding_window,
            logits_soft_cap=cfg.attn_soft_cap,
            scale=scale,
            attn_impl=cfg.attn_impl,
        )
    else:
        # the flash kernel handles MLA's asymmetric qk (192) / v (128) head
        # dims natively (qk padded to 256 lanes, v block carries its own dim)
        attn = dot_product_attention(
            q, k, v,
            causal=cfg.causal,
            segment_ids=segment_ids,
            positions=positions,
            sliding_window=sliding_window,
            logits_soft_cap=cfg.attn_soft_cap,
            scale=scale,
            impl=cfg.attn_impl,
            mesh_ctx=mesh_ctx,
        )
    attn = attn.reshape(B, S, n * dv)
    h = h + _dense(attn, {"kernel": lp["o_proj"]["kernel"]}, cfg.linear_precision)
    return constrain(h, ("act_batch", "act_seq", "act_embed"))
