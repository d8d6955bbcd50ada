"""Autoregressive generation with a static KV cache (dense + MoE decoders).

The analog of the reference's generation surfaces (reference: examples
vlm_generate / dllm_generate; speculative target servers). TPU-native
design: static-shape caches; prefill runs one batched pass over the prompt
collecting per-layer cache entries as scan outputs; decode is a `lax.scan`
over new tokens with an inner layer scan — the whole generate call is one
jit with no dynamic shapes.

Attention flavors:
- GQA: (L, B, T, Hkv, D) K/V caches, sliding windows (global/alternating
  per-layer patterns — gemma2/gpt-oss style) and attention sinks.
- MLA (DeepSeek V2/V3/V4 family): the cache stores the COMPRESSED per-token
  state — the kv latent (B, T, r) plus the single shared rotated key-rope
  head (B, T, dr) — and attention runs ABSORBED (reference:
  deepseek_v3/model.py MLA; the absorbed decode is the standard latent-cache
  identity): q_nope is folded through the kv up-projection's key half so
  scores are taken in latent space, and the value half is applied after the
  softmax. Exactly equal to materializing full k/v, at r+dr cached floats
  per token instead of n*(dn+dr+dv). DSA models (dsa_index_topk set) decode
  with DENSE MLA over the cache — the indexer's top-k is an efficiency
  device for long-context scoring, not a correctness requirement at the
  cache sizes generate targets.

MoE decoders (MoETransformerConfig) run their dense-mlp prefix stack then
the MoE stack, routing each decoded token through the gate; dispatch is
forced dropless at decode time (exact for any token population — the
capacity bound would depend on B·S vs B and silently drop differently).

Looped decoders (`cfg.num_passes` > 1: the layer stack walked that many
times over every token, the final norm after each walk) keep one such cache
per pass: passes x L entries in all.

Greedy or temperature sampling. Batched beam search is later-round work.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.layers import cast_params
from automodel_tpu.models.llm.decoder import (
    TransformerConfig,
    _dense,
    layer_windows,
    mlp_inner,
    project_qkv,
    unembed,
)
from automodel_tpu.ops.quant import matmul as _mm
from automodel_tpu.ops.attention import NEG_INF
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope, rope_frequencies


@dataclasses.dataclass(frozen=True)
class GenerateConfig:
    max_new_tokens: int = 64
    temperature: float = 0.0  # 0 → greedy
    top_k: int | None = None      # sample from the k highest-prob tokens
    top_p: float | None = None    # nucleus sampling (smallest mass ≥ p)
    eos_token_id: int | None = None


def _filter_logits(logits: jnp.ndarray, gen: "GenerateConfig") -> jnp.ndarray:
    """Compat shim over the public `inference.sampling.filter_logits` (the
    implementation lives there so het_generate and the serving engine share
    it without importing a private symbol)."""
    from automodel_tpu.inference.sampling import filter_logits

    return filter_logits(logits, gen.top_k, gen.top_p)


def _attend(q, keys, values, mask_len, cfg, *, q_positions, window=None, sinks=None):
    """q (B,Sq,Hq,D) vs cache keys/values (B,T,Hkv,D); attend to < mask_len
    (per-query causal when q spans several positions).

    `window` is a (possibly traced) per-layer sliding window size (0 =
    global); `sinks` the (Hq,) learned sink logits (gpt-oss). Both ride the
    layer scan so alternating-window / sinked models decode in one jit."""
    B, Sq, Hq, D = q.shape
    T, Hkv = keys.shape[1], keys.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, keys, preferred_element_type=jnp.float32)
    scale = cfg.attn_scale if cfg.attn_scale is not None else D ** -0.5
    s = s * scale
    if cfg.attn_soft_cap is not None:
        s = cfg.attn_soft_cap * jnp.tanh(s / cfg.attn_soft_cap)
    kv_idx = jnp.arange(T)
    mask = kv_idx[None, :] <= q_positions[:, :, None]  # (B, Sq, T) causal
    mask = jnp.logical_and(mask, (kv_idx < mask_len)[None, None, :])
    if window is not None:
        # window==0 → global; else attend only the last `window` positions
        dist = q_positions[:, :, None] - kv_idx[None, None, :]
        mask = jnp.logical_and(mask, (window == 0) | (dist < window))
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    if sinks is not None:
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(1, Hkv, G, 1, 1), (B, Hkv, G, Sq, 1)
        )
        s = jnp.concatenate([s, sink], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    if sinks is not None:
        p = p[..., :-1]
    o = jnp.einsum("bkgst,btkd->bskgd", p.astype(values.dtype), values)
    return o.reshape(B, Sq, Hq, D)


def _gqa_attn_with_cache(h, lp, cfg, positions, inv_freq, cache_k, cache_v,
                         write_at, attend_len, window=None):
    """GQA attention sub-block with cache write; returns post-residual h."""
    B, Sq, _ = h.shape
    x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    q, k, v = project_qkv(x, lp, cfg, positions, inv_freq)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, write_at, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, write_at, 0, 0))
    attn = _attend(
        q, cache_k, cache_v, attend_len, cfg, q_positions=positions,
        window=window, sinks=lp.get("sinks"),
    )
    attn = attn.reshape(B, Sq, cfg.num_heads * cfg.resolved_head_dim)
    attn_out = _dense(attn, lp["o_proj"])
    if cfg.use_post_norms:
        attn_out = rms_norm(attn_out, lp["post_attn_out_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    return h + attn_out, cache_k, cache_v


def mla_absorbed_inputs(x, lp, cfg, positions, inv_freq):
    """Shared MLA absorbed-decode projections (this module's dense-cache
    decode AND the paged serving engine — one implementation so a scaling/
    norm tweak can never silently break their token-parity contract).

    Returns (q_abs, q_rope, c_kv, k_rope, w_uv): q_abs (B,S,n,r) is q_nope
    folded through the key half of the kv up-projection (scores are taken in
    latent space), c_kv (B,S,r) the rms-normed kv latent and k_rope (B,S,dr)
    the rotated shared key-rope head (the two cached quantities), and w_uv
    (r,n,dv) the value half the caller applies after the softmax."""
    B, Sq, _ = x.shape
    n = cfg.num_heads
    dn, dr, dv = cfg.mla_qk_nope_head_dim, cfg.mla_qk_rope_head_dim, cfg.mla_v_head_dim
    r = cfg.mla_kv_lora_rank
    prec = cfg.linear_precision
    if cfg.mla_q_lora_rank:
        q_lat = rms_norm(_mm(x, lp["q_down_proj"]["kernel"], prec), lp["q_norm"]["scale"], cfg.rms_norm_eps)
        q = _mm(q_lat, lp["q_up_proj"]["kernel"], prec)
    else:
        q = _mm(x, lp["q_proj"]["kernel"], prec)
    q = q.reshape(B, Sq, n, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, inv_freq)
    if cfg.mla_qpe_scaling_beta is not None:
        sc = 1.0 + cfg.mla_qpe_scaling_beta * jnp.log1p(
            jnp.floor(positions.astype(jnp.float32) / cfg.mla_qpe_scaling_orig_max)
        )
        q_rope = q_rope * sc[:, :, None, None].astype(q_rope.dtype)

    kv = _mm(x, lp["kv_down_proj"]["kernel"], prec)
    c_kv, k_rope = kv[..., :r], kv[..., r:]
    c_kv = rms_norm(c_kv, lp["kv_norm"]["scale"], cfg.rms_norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, inv_freq)[:, :, 0, :]
    W = lp["kv_up_proj"]["kernel"].reshape(r, n, dn + dv)
    w_uk, w_uv = W[..., :dn], W[..., dn:]
    q_abs = jnp.einsum("bsnd,rnd->bsnr", q_nope, w_uk)
    return q_abs, q_rope, c_kv, k_rope, w_uv


def _mla_attn_with_cache(h, lp, cfg, positions, inv_freq, cache_c, cache_kr,
                         write_at, attend_len, window=None):
    """MLA attention sub-block over the absorbed latent cache.

    cache_c (B,T,r) holds the rms-normed kv latent; cache_kr (B,T,dr) the
    rotated shared key-rope head. Scores/values are taken in latent space by
    folding the kv up-projection halves into q and out respectively — the
    exact-algebra absorbed form of models/llm/mla.py `_mla_qkv` + attention.
    """
    B, Sq, H = h.shape
    n = cfg.num_heads
    dn, dr, dv = cfg.mla_qk_nope_head_dim, cfg.mla_qk_rope_head_dim, cfg.mla_v_head_dim
    prec = cfg.linear_precision

    x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    q_abs, q_rope, c_kv, k_rope, w_uv = mla_absorbed_inputs(
        x, lp, cfg, positions, inv_freq
    )
    cache_c = jax.lax.dynamic_update_slice(cache_c, c_kv.astype(cache_c.dtype), (0, write_at, 0))
    cache_kr = jax.lax.dynamic_update_slice(cache_kr, k_rope.astype(cache_kr.dtype), (0, write_at, 0))

    # absorbed scores: (q_nope · W_uk) · c  +  q_rope · k_rope
    s = jnp.einsum("bsnr,btr->bnst", q_abs, cache_c, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bsnd,btd->bnst", q_rope, cache_kr, preferred_element_type=jnp.float32)
    scale = cfg.attn_scale if cfg.attn_scale is not None else (dn + dr) ** -0.5
    s = s * scale
    T = cache_c.shape[1]
    kv_idx = jnp.arange(T)
    mask = kv_idx[None, :] <= positions[:, :, None]
    mask = jnp.logical_and(mask, (kv_idx < attend_len)[None, None, :])
    if window is not None:
        # window==0 → global (same per-layer convention as the GQA path)
        dist = positions[:, :, None] - kv_idx[None, None, :]
        mask = jnp.logical_and(mask, (window == 0) | (dist < window))
    s = jnp.where(mask[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out_lat = jnp.einsum("bnst,btr->bsnr", p.astype(cache_c.dtype), cache_c)
    attn = jnp.einsum("bsnr,rnd->bsnd", out_lat, w_uv).reshape(B, Sq, n * dv)
    h = h + _dense(attn, {"kernel": lp["o_proj"]["kernel"]}, prec)
    return h, cache_c, cache_kr


def _dense_mlp(h, lp, cfg):
    x = rms_norm(h, lp["post_attn_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    mlp_out = _mm(mlp_inner(x, lp, cfg), lp["down_proj"]["kernel"], cfg.linear_precision)
    if cfg.use_post_norms:
        mlp_out = rms_norm(mlp_out, lp["post_mlp_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    return h + mlp_out


def _moe_mlp(h, lp, cfg):
    from automodel_tpu.moe.layer import moe_forward

    # force dropless dispatch: the capacity dispatcher's bound depends on the
    # token population (B·S in a full forward vs B in one decode step), so a
    # capacity-trained config would silently drop differently-routed tokens
    # at decode time; dropless is exact for any population
    moe_cfg = dataclasses.replace(cfg.moe, dispatcher="dropless")
    x = rms_norm(h, lp["post_attn_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    moe_out, _aux, _stats = moe_forward(lp["moe"], moe_cfg, x, lambda a, ax: a)
    return h + moe_out


def _embed(params, cfg, ids):
    h = jnp.take(params["embed"]["embedding"], ids, axis=0).astype(cfg.dtype)
    if cfg.embed_scale != 1.0:
        h = h * jnp.asarray(cfg.embed_scale, cfg.dtype)
    return h


def _cache_shapes(cfg, L, B, T):
    """Per-stack cache arrays; a (kind, *arrays) tuple rides the scans."""
    if cfg.attention_type == "mla":
        return (
            jnp.zeros((L, B, T, cfg.mla_kv_lora_rank), cfg.dtype),
            jnp.zeros((L, B, T, cfg.mla_qk_rope_head_dim), cfg.dtype),
        )
    D = cfg.resolved_head_dim
    return (
        jnp.zeros((L, B, T, cfg.num_kv_heads, D), cfg.dtype),
        jnp.zeros((L, B, T, cfg.num_kv_heads, D), cfg.dtype),
    )


def _attn_with_cache(h, lp, cfg, positions, inv_freq, c0, c1, write_at, attend_len, window):
    if cfg.attention_type == "mla":
        return _mla_attn_with_cache(
            h, lp, cfg, positions, inv_freq, c0, c1, write_at, attend_len,
            window=window,
        )
    return _gqa_attn_with_cache(
        h, lp, cfg, positions, inv_freq, c0, c1, write_at, attend_len, window=window
    )


@partial(jax.jit, static_argnames=("cfg", "gen"))
def generate(
    params: dict,
    cfg: TransformerConfig,
    input_ids: jnp.ndarray,  # (B, S_prompt) — right-aligned, no padding
    rng: jax.Array,
    gen: GenerateConfig = GenerateConfig(),
    prompt_embeds: jnp.ndarray | None = None,  # (B, S_prompt, H) — VLM merge
    rope_angles: jnp.ndarray | None = None,    # (B, S_prompt, D/2) MRoPE prefill
    decode_rope_pos0: jnp.ndarray | None = None,  # (B,) rope pos of 1st new token
    deepstack_embeds: jnp.ndarray | None = None,  # (K, B, S_prompt, H)
) -> jnp.ndarray:
    """Returns (B, S_prompt + max_new_tokens) token ids.

    `prompt_embeds` replaces the prompt's token embeddings (the VLM path:
    image features already merged at the placeholder positions —
    vlm_generate below builds them); decode steps embed tokens normally.

    MRoPE models (qwen3-vl-moe) pass `rope_angles` — precomputed per-token
    multi-axis angles for the prompt (apply_rope's ndim>=2 form) — plus
    `decode_rope_pos0`, the per-sample rope position of the first generated
    token (text resumes at max(pos3)+1, which is ≤ the cache slot index
    because the image block advances positions by max(gh,gw) not by its
    token count). Decode steps rotate with angles = (pos0+step)·inv_freq —
    on all three mrope axes a text token has the same position, so the
    multi-axis rope collapses to standard rope there. `deepstack_embeds`
    (zeros off-image, pre-scattered) are added after global layer k<K
    during prefill only — decode tokens are text and take no visual
    residual (reference: qwen3_vl_moe/model.py:419 _deepstack_process)."""
    from automodel_tpu.models.moe_lm.het_moe import HetMoEConfig

    if isinstance(cfg, HetMoEConfig):
        # heterogeneous engine (step3p5/mimo/minimax-m3): per-layer python-
        # loop decode with its own cache layout (incl. sparse index caches)
        assert rope_angles is None and deepstack_embeds is None
        from automodel_tpu.inference.het_generate import het_generate

        return het_generate(
            params, cfg, input_ids, rng, gen, prompt_embeds=prompt_embeds
        )
    if getattr(cfg, "layer_ops", None) is not None:
        raise NotImplementedError(
            "generate() over layers that name their mixer (layer_ops: a "
            "state-space layer's convolution and recurrent state are not in "
            "this dense per-request cache); serve such a model through "
            "serving.ServingEngine, which carries the state per slot"
        )
    params = cast_params(params, cfg.dtype)
    B, S = input_ids.shape
    T = S + gen.max_new_tokens
    is_moe = getattr(cfg, "moe", None) is not None
    inv_freq = rope_frequencies(cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
    if cfg.rope_local_theta is not None:
        # gemma3: sliding layers rotate with the unscaled local theta; the
        # selection is traced per layer off the scanned (L,) window array
        inv_freq_local = rope_frequencies(cfg.rope_dim, cfg.rope_local_theta, None)
        freq_for_win = lambda win: jnp.where(win > 0, inv_freq_local, inv_freq)
    elif cfg.rope_layers == "sliding":
        # the layers without a window have no rotary embedding: the zero
        # table rotates by no angle, exactly the identity
        freq_for_win = lambda win: jnp.where(win > 0, inv_freq, 0.0)
    else:
        freq_for_win = lambda win: inv_freq

    # (stack_params, mlp_fn, L) per stack: dense decoder has one; MoE
    # decoders run first_k_dense dense layers then the MoE stack
    if is_moe:
        stacks = []
        if cfg.first_k_dense > 0:
            stacks.append((params["dense_layers"], _dense_mlp, cfg.first_k_dense))
        stacks.append((params["moe_layers"], _moe_mlp, cfg.num_moe_layers))
    else:
        L = jax.tree.leaves(params["layers"])[0].shape[0]
        stacks = [(params["layers"], _dense_mlp, L)]

    all_windows = [w or 0 for w in layer_windows(cfg, sum(s[2] for s in stacks))]
    # a looped decoder (cfg.num_passes > 1) walks its stacks that many times
    # with the same weights and keeps a cache per (pass, layer): `caches`
    # holds one list of per-stack caches for each pass
    caches = [
        [_cache_shapes(cfg, L, B, T) for _, _, L in stacks]
        for _ in range(cfg.num_passes)
    ]
    stack_windows = []
    off = 0
    for _, _, L in stacks:
        stack_windows.append(jnp.asarray(all_windows[off : off + L], jnp.int32))
        off += L

    def final_norm(h):
        return rms_norm(h, params["final_norm"]["scale"], cfg.rms_norm_eps,
                        cfg.zero_centered_norm)

    def run_stacks(h, positions, caches, write_at, attend_len, **kw):
        """Every pass over the stacks; the final norm stands between two
        passes (the last pass's is the caller's, on the rows it reads)."""
        new_caches = []
        for t, pass_caches in enumerate(caches):
            if t:
                h = final_norm(h)
            h, pass_caches = run_pass(
                h, positions, pass_caches, write_at, attend_len, **kw)
            new_caches.append(pass_caches)
        return h, new_caches

    def run_pass(h, positions, caches, write_at, attend_len,
                 freq_override=None, deepstack=None):
        """`freq_override` (per-token angles) replaces the layer-window freq
        table (MRoPE); `deepstack` (K,B,S,H) is injected after global layer
        gidx<K (prefill only)."""
        new_caches = []
        off = 0
        for (sp, mlp_fn, L), (c0, c1), wins in zip(stacks, caches, stack_windows):
            gidxs = jnp.arange(L, dtype=jnp.int32) + off
            off += L

            def one_layer(carry, xs, mlp_fn=mlp_fn):
                (h,) = carry
                lp, cc0, cc1, win, gidx = xs
                freq = freq_override if freq_override is not None else freq_for_win(win)
                h, cc0, cc1 = _attn_with_cache(
                    h, lp, cfg, positions, freq, cc0, cc1,
                    write_at, attend_len, win,
                )
                h = mlp_fn(h, lp, cfg)
                if deepstack is not None:
                    from automodel_tpu.models.moe_lm.decoder import deepstack_inject

                    h = deepstack_inject(h, gidx, deepstack)
                return (h,), (cc0, cc1)

            (h,), (c0, c1) = jax.lax.scan(one_layer, (h,), (sp, c0, c1, wins, gidxs))
            new_caches.append((c0, c1))
        return h, new_caches

    # -- prefill: one batched pass over the prompt --------------------------
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if prompt_embeds is not None:
        h = prompt_embeds.astype(cfg.dtype)
        if cfg.embed_scale != 1.0:
            # match decoder.forward's inputs_embeds handling AND _embed below
            h = h * jnp.asarray(cfg.embed_scale, cfg.dtype)
    else:
        h = _embed(params, cfg, input_ids)
    h, caches = run_stacks(
        h, positions, caches, 0, S,
        freq_override=rope_angles, deepstack=deepstack_embeds,
    )
    h_last = final_norm(h[:, -1:])
    logits = unembed(params, cfg, h_last)[:, 0]

    def sample(logits, key):
        if gen.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = _filter_logits(logits / gen.temperature, gen)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    first = sample(logits, rng)
    eos = gen.eos_token_id
    done0 = (
        first == eos if eos is not None else jnp.zeros_like(first, dtype=bool)
    )

    # -- decode loop ---------------------------------------------------------
    def decode_step(carry, step):
        token, done, caches, key = carry
        pos = S + step  # cache slot of `token` in the sequence
        positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
        if decode_rope_pos0 is not None:
            # MRoPE: rope position ≠ cache slot; all axes equal for text
            rpos = (decode_rope_pos0 + step).astype(jnp.float32)
            freq = rpos[:, None, None] * inv_freq[None, None, :]  # (B,1,D/2)
        else:
            freq = None
        h = _embed(params, cfg, token[:, None])
        h, caches = run_stacks(h, positions, caches, pos, pos + 1,
                               freq_override=freq)
        h = final_norm(h)
        logits = unembed(params, cfg, h)[:, 0]
        key, sub = jax.random.split(key)
        next_token = sample(logits, sub)
        if eos is not None:
            # static shapes: after EOS, keep emitting EOS (HF-style padding)
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


@partial(jax.jit, static_argnames=("module", "cfg"))
def _encode_and_merge(module, params, cfg, input_ids, pixel_values):
    from automodel_tpu.models.vlm.llava import merge_image_embeddings

    image_embeds = module.encode_images(params, cfg, pixel_values)
    token_embeds = jnp.take(
        params["language_model"]["embed"]["embedding"], input_ids, axis=0
    ).astype(cfg.dtype)
    return merge_image_embeddings(
        token_embeds, image_embeds, input_ids == cfg.image_token_id
    )


def vlm_generate(
    module,
    params: dict,
    cfg,                       # VLM config (llava / kimi-vl)
    input_ids: jnp.ndarray,    # (B, S_prompt) incl. image placeholder tokens
    pixel_values: jnp.ndarray,
    rng: jax.Array,
    gen: GenerateConfig = GenerateConfig(),
) -> jnp.ndarray:
    """Image-conditioned generation (the reference's vlm_generate examples):
    run the model's own `encode_images` (tower + projector, jitted with the
    merge), scatter the features into the prompt's token embeddings, and
    decode with the text model's KV cache. Exactly matches the teacher-
    forced module.forward argmax loop for the supported families
    (tests/unit/test_vlm.py, test_kimi_vl.py, test_qwen3_vl.py).

    Families whose TEXT-side prompt encoding needs more than merged
    embeddings expose `prepare_generation(params, cfg, ids, pixels)`
    returning extra generate() kwargs — qwen3-vl-moe builds MRoPE prefill
    angles, the decode rope-position origin, and deepstack residuals there.
    """
    if hasattr(module, "prepare_generation"):
        prep = module.prepare_generation(params, cfg, input_ids, pixel_values)
        return generate(
            params["language_model"], cfg.text, input_ids, rng, gen, **prep
        )
    if not hasattr(module, "encode_images"):
        raise NotImplementedError(
            f"vlm_generate: {getattr(module, '__name__', module)} exposes "
            "neither prepare_generation() nor encode_images()"
        )
    merged = _encode_and_merge(module, params, cfg, input_ids, pixel_values)
    return generate(
        params["language_model"], cfg.text, input_ids, rng, gen,
        prompt_embeds=merged,
    )
