"""KV-cache generation for the heterogeneous MoE engine (het_moe).

The het engine (step3p5 / minimax-m3 and the minimax-m3-vl
text side) keeps per-layer python-loop heterogeneity — per-layer attention
geometries, dense/MoE MLPs, and (M3) block-sparse DSA layers — so the
generic `inference.generate` layer-scan cannot drive it. This module mirrors
its structure: prefill is one batched pass writing per-layer caches, decode
is a `lax.scan` over steps with the layer loop unrolled inside (layer count
is static config). Sparse layers cache the shared index key alongside K/V
and re-run the block top-k per decoded token against the cached keys, so
decode applies exactly the training-time selection (reference:
minimax_m3_vl/layers.py select_sparse_blocks — the selection is part of the
model's semantics, not an optimization, unlike deepseek DSA's oracle).
`inference.generate.generate` dispatches here when cfg is a HetMoEConfig.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from automodel_tpu.models.moe_lm.het_moe import (
    HetMoEConfig,
    _clamped_swiglu,
    index_projections,
    layer_rows,
    select_sparse_blocks,
)
from automodel_tpu.moe.layer import moe_forward
from automodel_tpu.ops.attention import NEG_INF
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope, rope_frequencies


def _qkv(x, lp, ai, g, cfg, positions, inv_freq):
    from automodel_tpu.ops.quant import matmul as _mm

    B, S, _ = x.shape
    prec = cfg.linear_precision
    q = _mm(x, lp["q_proj"]["kernel"][ai], prec).reshape(B, S, g.num_heads, g.head_dim)
    k = _mm(x, lp["k_proj"]["kernel"][ai], prec).reshape(B, S, g.num_kv_heads, g.head_dim)
    v = _mm(x, lp["v_proj"]["kernel"][ai], prec).reshape(B, S, g.num_kv_heads, g.head_dim)
    if cfg.attention_bias:
        q = q + lp["q_proj"]["bias"][ai].reshape(1, 1, g.num_heads, g.head_dim)
        k = k + lp["k_proj"]["bias"][ai].reshape(1, 1, g.num_kv_heads, g.head_dim)
        v = v + lp["v_proj"]["bias"][ai].reshape(1, 1, g.num_kv_heads, g.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"]["scale"][ai], cfg.rms_norm_eps, cfg.zero_centered_norm)
        k = rms_norm(k, lp["k_norm"]["scale"][ai], cfg.rms_norm_eps, cfg.zero_centered_norm)
    if inv_freq is not None:
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
    return q, k, v


def _cached_attention(q, keys, values, positions, attend_len, g, cfg, keep=None):
    """q (B,Sq,Hq,D) vs cache (B,T,Hkv,·); causal by `positions`, bounded by
    attend_len, optional sliding window and precomputed sparse `keep`."""
    B, Sq, Hq, D = q.shape
    T, Hkv = keys.shape[1], keys.shape[2]
    kv_idx = jnp.arange(T)
    mask = kv_idx[None, None, :] <= positions[:, :, None]       # (B,Sq,T)
    mask = jnp.logical_and(mask, (kv_idx < attend_len)[None, None, :])
    if g.sliding_window:
        dist = positions[:, :, None] - kv_idx[None, None, :]
        mask = jnp.logical_and(mask, dist < g.sliding_window)
    mask4 = jnp.broadcast_to(mask[:, None, :, :], (B, Hq, Sq, T))
    if keep is not None:
        mask4 = mask4 & keep
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, keys, preferred_element_type=jnp.float32)
    s = s * (g.head_dim ** -0.5)
    s = jnp.where(mask4.reshape(B, Hkv, G, Sq, T), s, NEG_INF)
    return s, values


def _softmax_out(s, values, B, Sq, g):
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p.astype(values.dtype), values)
    return o.reshape(B, Sq, g.num_heads * g.head_dim)


@partial(jax.jit, static_argnames=("cfg", "gen"))
def het_generate(
    params: dict,
    cfg: HetMoEConfig,
    input_ids: jnp.ndarray,  # (B, S_prompt) — right-aligned, no padding
    rng: jax.Array,
    gen,
    prompt_embeds: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Returns (B, S_prompt + max_new_tokens) token ids (greedy / sampled)."""
    from automodel_tpu.inference.sampling import filter_logits
    from automodel_tpu.models.common.layers import cast_params

    params = cast_params(params, cfg.dtype)
    B, S = input_ids.shape
    T = S + gen.max_new_tokens
    rows = layer_rows(cfg)
    eps, zc = cfg.rms_norm_eps, cfg.zero_centered_norm

    freqs = []
    for li, lt, *_ in rows:
        g = cfg.geom(lt)
        theta = cfg.rope_thetas[li] if cfg.rope_thetas else 10000.0
        frac = cfg.partial_rotary[li] if cfg.partial_rotary else 1.0
        roped = cfg.use_rope[li] if cfg.use_rope else True
        rot = int(g.head_dim * frac) // 2 * 2
        freqs.append(rope_frequencies(rot, theta) if roped and rot else None)

    def moe_mlp(x, mi):
        import dataclasses as _dc

        mp = jax.tree.map(lambda p: p[mi], params["moe"])
        # dropless is exact for any token population (see generate._moe_mlp)
        moe_cfg = _dc.replace(cfg.moe, dispatcher="dropless")
        out, _aux, _st = moe_forward(mp, moe_cfg, x, lambda a, ax: a)
        if cfg.share_expert_dim:
            out = out + _clamped_swiglu(
                x, params["shared_mlp"], mi, cfg.swiglu_limit, cfg.dense_activation
            )
        return out

    def run_once(h, positions, caches, write_at, attend_len):
        """One pass over all layers; Sq = h.shape[1] (S for prefill, 1 for
        decode). caches: per-layer (k, v[, idx_k]) tuples, written at
        write_at."""
        new_caches = []
        for (li, lt, gk, ai, is_moe, mi, is_sparse, spi), inv_freq in zip(rows, freqs):
            g = cfg.geom(lt)
            lp = params[gk]
            c = caches[li]
            x = rms_norm(h, params["input_norms"]["scale"][li], eps, zc)
            q, k, v = _qkv(x, lp, ai, g, cfg, positions, inv_freq)
            ck = jax.lax.dynamic_update_slice(c[0], k.astype(c[0].dtype), (0, write_at, 0, 0))
            cv = jax.lax.dynamic_update_slice(c[1], v.astype(c[1].dtype), (0, write_at, 0, 0))
            keep = None
            if is_sparse:
                idx_q, idx_k = index_projections(
                    params["indexer"], cfg, x, positions, inv_freq, spi
                )
                cik = jax.lax.dynamic_update_slice(
                    c[2], idx_k.astype(c[2].dtype), (0, write_at, 0)
                )
                keep = select_sparse_blocks(
                    idx_q, cik, positions,
                    block_size=cfg.sparse_block_size,
                    topk_blocks=cfg.sparse_topk_blocks,
                    init_blocks=cfg.sparse_init_blocks,
                    local_blocks=cfg.sparse_local_blocks,
                    score_type=cfg.sparse_score_type,
                )
                Hq = g.num_heads
                keep = jnp.repeat(keep, Hq // cfg.sparse_index_heads, axis=1)
                new_caches.append((ck, cv, cik))
            else:
                new_caches.append((ck, cv))
            s, values = _cached_attention(
                q, ck, cv, positions, attend_len, g, cfg, keep=keep
            )
            attn = _softmax_out(s, values, h.shape[0], h.shape[1], g)
            if cfg.head_gate:
                gate = jax.nn.sigmoid(x @ lp["g_proj"]["kernel"][ai])
                gr = jnp.repeat(
                    gate[..., None], g.head_dim, axis=-1
                ).reshape(h.shape[0], h.shape[1], g.num_heads * g.head_dim)
                attn = attn * gr.astype(attn.dtype)
            out = attn @ lp["o_proj"]["kernel"][ai]
            if cfg.attention_bias and "bias" in lp["o_proj"]:
                out = out + lp["o_proj"]["bias"][ai]
            h = h + out
            x = rms_norm(h, params["post_norms"]["scale"][li], eps, zc)
            if is_moe:
                h = h + moe_mlp(x, mi)
            else:
                h = h + _clamped_swiglu(
                    x, params["dense_mlp"], mi, cfg.swiglu_limit, cfg.dense_activation
                )
        return h, tuple(new_caches)

    def unembed(h):
        kernel = (
            params["embed"]["embedding"].T
            if cfg.tie_word_embeddings
            else params["lm_head"]["kernel"]
        )
        out = jnp.einsum(
            "bsh,hv->bsv", h, kernel.astype(h.dtype),
            preferred_element_type=jnp.float32,
        )
        if cfg.logits_soft_cap is not None:
            out = cfg.logits_soft_cap * jnp.tanh(out / cfg.logits_soft_cap)
        return out

    caches = []
    for (li, lt, *_rest) in rows:
        g = cfg.geom(lt)
        is_sparse = bool(cfg.sparse_attn and cfg.sparse_attn[li])
        c = (
            jnp.zeros((B, T, g.num_kv_heads, g.head_dim), cfg.dtype),
            jnp.zeros((B, T, g.num_kv_heads, g.head_dim), cfg.dtype),
        )
        if is_sparse:
            c = c + (jnp.zeros((B, T, cfg.sparse_index_dim), cfg.dtype),)
        caches.append(c)
    caches = tuple(caches)

    # -- prefill -------------------------------------------------------------
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if prompt_embeds is not None:
        h = prompt_embeds.astype(cfg.dtype)
    else:
        h = jnp.take(params["embed"]["embedding"], input_ids, axis=0).astype(cfg.dtype)
    h, caches = run_once(h, positions, caches, 0, S)
    h_last = rms_norm(h[:, -1:], params["final_norm"]["scale"], eps, zc)
    logits = unembed(h_last)[:, 0]

    def sample(logits, key):
        if gen.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = filter_logits(logits / gen.temperature, gen.top_k, gen.top_p)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    first = sample(logits, rng)
    eos = gen.eos_token_id
    done0 = first == eos if eos is not None else jnp.zeros_like(first, dtype=bool)

    def decode_step(carry, step):
        token, done, caches, key = carry
        pos = S + step
        positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
        h = jnp.take(params["embed"]["embedding"], token[:, None], axis=0).astype(cfg.dtype)
        h, caches = run_once(h, positions, caches, pos, pos + 1)
        h = rms_norm(h, params["final_norm"]["scale"], eps, zc)
        logits = unembed(h)[:, 0]
        key, sub = jax.random.split(key)
        next_token = sample(logits, sub)
        if eos is not None:
            next_token = jnp.where(done, eos, next_token)
            done = jnp.logical_or(done, next_token == eos)
        return (next_token, done, caches, key), token

    (last, _, _, _), tokens = jax.lax.scan(
        decode_step,
        (first, done0, caches, rng),
        jnp.arange(gen.max_new_tokens - 1) if gen.max_new_tokens > 1 else jnp.arange(0),
    )
    new_tokens = (
        jnp.concatenate([tokens.T, last[:, None]], axis=1)
        if gen.max_new_tokens > 1
        else first[:, None]
    )
    return jnp.concatenate([input_ids, new_tokens], axis=1)
