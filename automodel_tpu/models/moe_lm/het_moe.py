"""Heterogeneous-attention MoE decoder — the Step-3.5 / MiniMax-M3 engine.

The analog of the reference's step3p5 family (reference: nemo_automodel/
components/models/step3p5/, 2581 LoC): TWO attention geometries interleaved
by `layer_types` — global layers and sliding-window layers with their OWN
head counts (and head dims) — over a decoder whose MLPs are per-layer dense or
routed-MoE (+ a per-layer shared expert):

- step3p5 (layers.py:183 `Step3p5Attention`): per-head qk-RMSNorm, optional
  head-wise sigmoid gate (g_proj), per-layer rope theta / partial rotary /
  NoPE layers (`use_rope_layers`), clamped swiglu MLPs with per-layer
  limits, arbitrary `moe_layers_enum` MoE placement, separate shared expert.

(MiMo-V2-Flash, window layers with key/value heads and sinks of their own,
runs on the expert decoder that the serving engine serves:
models/moe_lm/families.py `mimo_v2_flash_config`.)

TPU design: stacked parameter groups per attention geometry and per MLP
kind, a python loop over `layer_types` with running per-group indices (the
models/hybrid/qwen3_next idiom — the heterogeneity is static config), all
attention through ops/attention.dot_product_attention (flash on TPU,
windows native).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.layers import dense_init, embed_init
from automodel_tpu.models.llm.decoder import _make_constrain, _stack
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.layer import init_moe, moe_forward, moe_param_specs
from automodel_tpu.ops.attention import dot_product_attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope, rope_frequencies


@dataclasses.dataclass(frozen=True)
class AttnGeom:
    """One attention geometry (the global or the sliding group)."""

    num_heads: int = 8
    num_kv_heads: int = 4
    head_dim: int = 64
    sliding_window: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class HetMoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632      # dense-layer MLP width
    num_layers: int = 4
    layer_types: tuple = ()            # "global" | "sliding" per layer
    global_attn: AttnGeom = dataclasses.field(default_factory=AttnGeom)
    sliding_attn: AttnGeom = dataclasses.field(default_factory=AttnGeom)
    qk_norm: bool = True               # per-head-dim RMSNorm on q/k
    head_gate: bool = False            # step3p5 g_proj sigmoid head gate
    attention_bias: bool = False
    # per-layer rope: theta / rotary fraction / enabled (NoPE layers)
    rope_thetas: tuple = ()
    partial_rotary: tuple = ()
    use_rope: tuple = ()
    mlp_kinds: tuple = ()              # "dense" | "moe" per layer
    moe: MoEConfig = dataclasses.field(default_factory=MoEConfig)
    share_expert_dim: int = 0          # per-moe-layer shared expert width
    swiglu_limit: Optional[float] = None  # clamp for dense/shared MLPs
    # "swiglu_clamped": silu(clip(g))·clip(u) (step3p5);
    # "swigluoai": g·sigmoid(1.702g)·(u+1) with gate max-clamp (minimax m3)
    dense_activation: str = "swiglu_clamped"
    zero_centered_norm: bool = False   # gemma (1+w) norms (minimax m3)
    # MiniMax-M3 block-sparse attention: a selection-only lightning indexer
    # picks, per query, the top-k key BLOCKS (reference: minimax_m3_vl/
    # layers.py:318 MiniMaxM3Indexer + select_sparse_blocks)
    sparse_attn: tuple = ()            # per-layer bool; () → none
    sparse_index_heads: int = 1
    sparse_index_dim: int = 64
    sparse_block_size: int = 32
    sparse_topk_blocks: int = 8
    sparse_init_blocks: int = 1
    sparse_local_blocks: int = 1
    sparse_score_type: str = "max"     # "max" | "lse" block reduction
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    logits_soft_cap: Optional[float] = None
    causal: bool = True
    linear_precision: Optional[str] = None  # None | "fp8" | "int8"
    dtype: Any = jnp.bfloat16
    remat_policy: str = "full"
    attn_impl: str = "auto"
    scan_unroll: int = 1
    mtp_num_layers: int = 0  # chassis compatibility

    def __post_init__(self):
        assert len(self.layer_types) == self.num_layers
        assert len(self.mlp_kinds) == self.num_layers
        assert not self.sparse_attn or len(self.sparse_attn) == self.num_layers

    @property
    def num_sparse_layers(self) -> int:
        return sum(1 for s in self.sparse_attn if s)

    def geom(self, lt: str) -> AttnGeom:
        return self.sliding_attn if lt == "sliding" else self.global_attn

    @property
    def num_moe_layers(self) -> int:
        return sum(1 for k in self.mlp_kinds if k == "moe")

    def flops_per_token(self, seq_len: int) -> float:
        H = self.hidden_size
        total = self.vocab_size * H * (1 if self.tie_word_embeddings else 2)
        for i, lt in enumerate(self.layer_types):
            g = self.geom(lt)
            total += H * g.head_dim * (g.num_heads + 2 * g.num_kv_heads)
            total += g.num_heads * g.head_dim * H
            if self.mlp_kinds[i] == "moe":
                total += 3 * H * self.moe.moe_intermediate_size * self.moe.experts_per_token
                total += 3 * H * self.share_expert_dim
                if self.moe.n_shared_experts:
                    total += 3 * H * self.moe.shared_intermediate
                total += H * self.moe.n_routed_experts  # router
            else:
                total += 3 * H * self.intermediate_size
        attn_flops = sum(
            6.0 * self.geom(lt).num_heads * self.geom(lt).head_dim * seq_len
            for lt in self.layer_types
        )
        return 6.0 * total + attn_flops


def _init_attn_group(cfg: HetMoEConfig, g: AttnGeom, rng, n: int) -> dict:
    H = cfg.hidden_size
    ks = jax.random.split(rng, 6)
    p = {
        "q_proj": {"kernel": _stack(dense_init, ks[0], (H, g.num_heads * g.head_dim), n)},
        "k_proj": {"kernel": _stack(dense_init, ks[1], (H, g.num_kv_heads * g.head_dim), n)},
        "v_proj": {"kernel": _stack(dense_init, ks[2], (H, g.num_kv_heads * g.head_dim), n)},
        "o_proj": {"kernel": _stack(dense_init, ks[3], (g.num_heads * g.head_dim, H), n)},
    }
    if cfg.attention_bias:
        for name, width in (
            ("q_proj", g.num_heads * g.head_dim),
            ("k_proj", g.num_kv_heads * g.head_dim),
            ("v_proj", g.num_kv_heads * g.head_dim),
            ("o_proj", H),
        ):
            p[name]["bias"] = jnp.zeros((n, width))
    if cfg.qk_norm:
        norm1 = jnp.zeros if cfg.zero_centered_norm else jnp.ones
        p["q_norm"] = {"scale": norm1((n, g.head_dim))}
        p["k_norm"] = {"scale": norm1((n, g.head_dim))}
    if cfg.head_gate:
        p["g_proj"] = {"kernel": _stack(dense_init, ks[4], (H, g.num_heads), n)}
    return p


def _attn_group_specs(cfg: HetMoEConfig, g: AttnGeom) -> dict:
    p = {
        "q_proj": {"kernel": ("layers", "embed", "heads")},
        "k_proj": {"kernel": ("layers", "embed", "kv_heads")},
        "v_proj": {"kernel": ("layers", "embed", "kv_heads")},
        "o_proj": {"kernel": ("layers", "heads", "embed")},
    }
    if cfg.attention_bias:
        for name in ("q_proj", "k_proj", "v_proj"):
            p[name]["bias"] = ("layers", "heads")
        p["o_proj"]["bias"] = ("layers", "norm")
    if cfg.qk_norm:
        p["q_norm"] = {"scale": ("layers", "norm")}
        p["k_norm"] = {"scale": ("layers", "norm")}
    if cfg.head_gate:
        p["g_proj"] = {"kernel": ("layers", "embed", None)}
    return p


def _mlp_stack(cfg: HetMoEConfig, rng, n: int, width: int) -> dict:
    H = cfg.hidden_size
    ks = jax.random.split(rng, 3)
    return {
        "gate_proj": {"kernel": _stack(dense_init, ks[0], (H, width), n)},
        "up_proj": {"kernel": _stack(dense_init, ks[1], (H, width), n)},
        "down_proj": {"kernel": _stack(dense_init, ks[2], (width, H), n)},
    }


_MLP_SPECS = {
    "gate_proj": {"kernel": ("layers", "embed", "mlp")},
    "up_proj": {"kernel": ("layers", "embed", "mlp")},
    "down_proj": {"kernel": ("layers", "mlp", "embed")},
}


def init(cfg: HetMoEConfig, rng: jax.Array) -> dict:
    H = cfg.hidden_size
    L = cfg.num_layers
    n_g = sum(1 for t in cfg.layer_types if t == "global")
    n_s = L - n_g
    n_d = sum(1 for k in cfg.mlp_kinds if k == "dense")
    n_m = L - n_d
    ks = jax.random.split(rng, 9)
    norm1 = jnp.zeros if cfg.zero_centered_norm else jnp.ones
    params: dict = {
        "embed": {"embedding": embed_init(ks[0], (cfg.vocab_size, H))},
        "final_norm": {"scale": norm1((H,))},
        "input_norms": {"scale": norm1((L, H))},
        "post_norms": {"scale": norm1((L, H))},
        "g_attn": _init_attn_group(cfg, cfg.global_attn, ks[1], max(n_g, 1)),
        "s_attn": _init_attn_group(cfg, cfg.sliding_attn, ks[2], max(n_s, 1)),
    }
    n_sp = cfg.num_sparse_layers
    if n_sp:
        Di, Hi = cfg.sparse_index_dim, cfg.sparse_index_heads
        kq, kk = jax.random.split(ks[7])
        params["indexer"] = {
            "index_q_proj": {"kernel": _stack(dense_init, kq, (H, Hi * Di), n_sp)},
            "index_k_proj": {"kernel": _stack(dense_init, kk, (H, Di), n_sp)},
            "index_q_norm": {"scale": norm1((n_sp, Di))},
            "index_k_norm": {"scale": norm1((n_sp, Di))},
        }
    if n_d:
        params["dense_mlp"] = _mlp_stack(cfg, ks[3], n_d, cfg.intermediate_size)
    if n_m:
        params["moe"] = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[init_moe(cfg.moe, H, k) for k in jax.random.split(ks[4], n_m)],
        )
        if cfg.share_expert_dim:
            params["shared_mlp"] = _mlp_stack(cfg, ks[5], n_m, cfg.share_expert_dim)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": dense_init(ks[6], (H, cfg.vocab_size))}
    return params


def param_specs(cfg: HetMoEConfig) -> dict:
    specs: dict = {
        "embed": {"embedding": ("vocab", "embed")},
        "final_norm": {"scale": ("norm",)},
        "input_norms": {"scale": ("layers", "norm")},
        "post_norms": {"scale": ("layers", "norm")},
        "g_attn": _attn_group_specs(cfg, cfg.global_attn),
        "s_attn": _attn_group_specs(cfg, cfg.sliding_attn),
    }
    if cfg.num_sparse_layers:
        specs["indexer"] = {
            "index_q_proj": {"kernel": ("layers", "embed", "heads")},
            "index_k_proj": {"kernel": ("layers", "embed", None)},
            "index_q_norm": {"scale": ("layers", "norm")},
            "index_k_norm": {"scale": ("layers", "norm")},
        }
    if any(k == "dense" for k in cfg.mlp_kinds):
        specs["dense_mlp"] = _MLP_SPECS
    if cfg.num_moe_layers:
        specs["moe"] = jax.tree.map(
            lambda s: ("layers",) + s,
            moe_param_specs(cfg.moe),
            is_leaf=lambda x: isinstance(x, tuple),
        )
        if cfg.share_expert_dim:
            specs["shared_mlp"] = _MLP_SPECS
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = {"kernel": ("embed", "vocab")}
    return specs


def _clamped_swiglu(x, lp, i, limit, kind: str = "swiglu_clamped",
                    precision: str | None = None):
    from automodel_tpu.ops.quant import matmul as _mm

    g = _mm(x, lp["gate_proj"]["kernel"][i], precision)
    u = _mm(x, lp["up_proj"]["kernel"][i], precision)
    if kind == "swigluoai":
        from automodel_tpu.moe.experts import gated_combine

        inner = gated_combine(g, u, "swigluoai", limit if limit is not None else 7.0)
    else:
        if limit is not None:
            g = jnp.clip(g, -limit, limit)
            u = jnp.clip(u, -limit, limit)
        inner = jax.nn.silu(g) * u
    return _mm(inner, lp["down_proj"]["kernel"][i], precision)


def layer_rows(cfg: HetMoEConfig):
    """Static per-layer bookkeeping shared by forward, the HF adapter, and
    the KV-cache decode path: (li, layer_type, attn_group_key, attn_index,
    is_moe, mlp_index, is_sparse, sparse_index) per layer."""
    gi = si = di = mi = spi = 0
    rows = []
    for li, lt in enumerate(cfg.layer_types):
        a_key = "s_attn" if lt == "sliding" else "g_attn"
        ai = si if lt == "sliding" else gi
        is_moe = cfg.mlp_kinds[li] == "moe"
        is_sparse = bool(cfg.sparse_attn and cfg.sparse_attn[li])
        rows.append((li, lt, a_key, ai, is_moe, mi if is_moe else di, is_sparse, spi))
        si, gi = si + (lt == "sliding"), gi + (lt != "sliding")
        mi, di = mi + is_moe, di + (not is_moe)
        spi += is_sparse
    return rows


def index_projections(ip, cfg: HetMoEConfig, x, positions, inv_freq, spi):
    """The spi-th lightning indexer's (idx_q (B,S,Hi,Di), idx_k (B,S,Di)) —
    per-head gemma-normed projections + the layer's partial rope, shared by
    the training forward and the decode cache path. The indexer stays in
    full precision (the reference checkpoint keeps index_* unquantized:
    minimax_m3_vl/state_dict_adapter.py:52)."""
    B, S, _ = x.shape
    Hi, Di = cfg.sparse_index_heads, cfg.sparse_index_dim
    eps, zc = cfg.rms_norm_eps, cfg.zero_centered_norm
    idx_q = (x @ ip["index_q_proj"]["kernel"][spi]).reshape(B, S, Hi, Di)
    idx_k = (x @ ip["index_k_proj"]["kernel"][spi]).reshape(B, S, 1, Di)
    idx_q = rms_norm(idx_q, ip["index_q_norm"]["scale"][spi], eps, zc)
    idx_k = rms_norm(idx_k, ip["index_k_norm"]["scale"][spi], eps, zc)
    if inv_freq is not None:
        idx_q = apply_rope(idx_q, positions, inv_freq)
        idx_k = apply_rope(idx_k, positions, inv_freq)
    return idx_q, idx_k[:, :, 0, :]


def select_sparse_blocks(
    idx_q: jnp.ndarray,       # (B, S, Hi, Di) post-norm+rope index queries
    idx_k: jnp.ndarray,       # (B, T, Di) shared index key (post-norm+rope)
    positions: jnp.ndarray,   # (B, S) KEY-ROW position of each query — the
                              # row index in the key buffer, NOT a packed
                              # document-local rope position (the reference's
                              # eager path is row-causal, layers.py:290 tril;
                              # doc gating is a separate AND in the caller)
    *,
    block_size: int,
    topk_blocks: int,
    init_blocks: int,
    local_blocks: int,
    score_type: str = "max",
) -> jnp.ndarray:
    """Per-query top-k key-BLOCK selection (MiniMax-M3 DSA; reference:
    minimax_m3_vl/layers.py:179 select_sparse_blocks). Key-level causal →
    block scores (max|lse) → force-include the first `init_blocks` and the
    query's current block → top-k of the rest. Returns a bool keep mask
    (B, Hi, S, T) expanded back to key granularity — non-differentiable
    hard selection (the indexer is selection-only, as in the reference's
    `disable_index_value=True` branch)."""
    B, S, Hi, Di = idx_q.shape
    T = idx_k.shape[1]
    s = jnp.einsum(
        "bqhd,btd->bhqt", idx_q.astype(jnp.float32), idx_k.astype(jnp.float32)
    ) * (Di ** -0.5)
    kpos = jnp.arange(T)
    causal_key = kpos[None, None, None, :] <= positions[:, None, :, None]
    from automodel_tpu.ops.attention import NEG_INF

    s = jnp.where(causal_key, s, NEG_INF)
    nb = -(-T // block_size)
    pad = nb * block_size - T
    if pad:
        s = jnp.pad(s, ((0, 0), (0, 0), (0, 0), (0, pad)), constant_values=NEG_INF)
    s = s.reshape(B, Hi, S, nb, block_size)
    if score_type == "lse":
        block_score = jax.nn.logsumexp(s, axis=-1)
    else:
        block_score = jnp.max(s, axis=-1)              # (B, Hi, S, nb)
    blk = jnp.arange(nb)
    cur_block = positions // block_size                 # (B, S)
    causal_block = blk[None, None, None, :] <= cur_block[:, None, :, None]
    # force the trailing `local_blocks` blocks (ending at the current one)
    # and the first `init_blocks`. NOTE the reference treats local_blocks as
    # a boolean current-block switch (layers.py:165 `(blk == cur_block) &
    # (local_blocks > 0)`); this generalizes it the way init_blocks already
    # is — identical for the shipped local_blocks ∈ {0, 1} configs.
    forced = (
        blk[None, None, None, :] > (cur_block[:, None, :, None] - local_blocks)
    ) | (blk[None, None, None, :] < init_blocks)
    forced = forced & causal_block
    sel = jnp.where(causal_block, block_score, NEG_INF)
    sel = jnp.where(forced, jnp.inf, sel)
    k_eff = min(topk_blocks, nb)
    top_idx = jax.lax.top_k(sel, k_eff)[1]              # (B, Hi, S, k_eff)
    keep_blocks = jnp.any(
        jax.nn.one_hot(top_idx, nb, dtype=jnp.bool_), axis=-2
    )
    keep_blocks = keep_blocks & causal_block
    keep = jnp.repeat(keep_blocks, block_size, axis=-1)[..., :T]
    return keep & causal_key                            # token-level causal


def sparse_keep_mask(ip, cfg: HetMoEConfig, x, positions, inv_freq, spi,
                     num_heads: int, segment_ids=None):
    """Run the spi-th lightning indexer over normed hidden states `x` and
    return the (B, Hq, S, S) bool keep mask for the main attention
    (reference: MiniMaxM3Indexer.forward — per-head gemma-normed index q +
    single shared index k, same partial rope as the main attention, block
    top-k selection; GQA-expanded across `num_heads`//Hi groups for THIS
    layer's geometry).

    Block selection runs over key-ROW indices (the reference's eager path
    is row-causal); packed documents are handled by the segment AND below.
    `positions` (possibly document-local rope positions) only drive the
    indexer's rope phase."""
    idx_q, idx_k = index_projections(ip, cfg, x, positions, inv_freq, spi)
    B, S = x.shape[:2]
    rows = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    keep = select_sparse_blocks(
        idx_q, idx_k, rows,
        block_size=cfg.sparse_block_size,
        topk_blocks=cfg.sparse_topk_blocks,
        init_blocks=cfg.sparse_init_blocks,
        local_blocks=cfg.sparse_local_blocks,
        score_type=cfg.sparse_score_type,
    )
    if segment_ids is not None:
        same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        keep = keep & same
    return jnp.repeat(keep, num_heads // cfg.sparse_index_heads, axis=1)


def _sparse_masked_attention(q, k, v, keep, scale):
    """GQA attention under an explicit (B, Hq, S, T) bool keep mask (already
    causal) — XLA path; the block-sparse pattern has no flash kernel yet.
    (The head-repeat of `keep` fuses into this `where` under XLA; folding a
    per-head-mask arg into ops/attention.xla_attention would deduplicate the
    two bodies if a third explicit-mask caller appears.)"""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32)
    s = s * scale
    from automodel_tpu.ops.attention import NEG_INF

    s = jnp.where(keep.reshape(B, Hkv, G, S, T), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v)
    return o.reshape(B, S, Hq, D)


def forward(
    params: dict,
    cfg: HetMoEConfig,
    input_ids: jnp.ndarray,
    *,
    positions: jnp.ndarray | None = None,
    segment_ids: jnp.ndarray | None = None,
    mesh_ctx=None,
    rules=None,
    return_hidden: bool = False,
    token_mask: jnp.ndarray | None = None,
    return_stats: bool = False,
    inputs_embeds: jnp.ndarray | None = None,  # (B,S,H) — VLM merged embeds
    **_ignored,
) -> tuple:
    """Returns (logits-or-hidden, aux_loss[, stats]) — the moe_lm protocol."""
    from automodel_tpu.models.common.layers import cast_params

    params = cast_params(params, cfg.dtype)
    B, S = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    constrain = _make_constrain(mesh_ctx, rules)

    if inputs_embeds is not None:
        h = inputs_embeds.astype(cfg.dtype)
    else:
        tbl = constrain(params["embed"]["embedding"], ("vocab", None))
        h = jnp.take(tbl, input_ids, axis=0).astype(cfg.dtype)
    h = constrain(h, ("act_batch", "act_seq", "act_embed"))

    eps = cfg.rms_norm_eps
    zc = cfg.zero_centered_norm
    prec = cfg.linear_precision
    remat = cfg.remat_policy not in (None, "none")
    aux_total = jnp.float32(0.0)
    stats_rows = []

    from automodel_tpu.ops.quant import matmul as _mm

    for li, lt, gk, ai, is_moe, mi, is_sparse, spi in layer_rows(cfg):
        g = cfg.geom(lt)
        theta = cfg.rope_thetas[li] if cfg.rope_thetas else 10000.0
        frac = cfg.partial_rotary[li] if cfg.partial_rotary else 1.0
        roped = cfg.use_rope[li] if cfg.use_rope else True
        rot = int(g.head_dim * frac) // 2 * 2
        inv_freq = rope_frequencies(rot, theta) if roped and rot else None

        def layer(h, li=li, gk=gk, ai=ai, g=g, inv_freq=inv_freq, is_moe=is_moe,
                  mi=mi, is_sparse=is_sparse, spi=spi):
            lp = params[gk]
            x = rms_norm(h, params["input_norms"]["scale"][li], eps, zc)
            q = _mm(x, lp["q_proj"]["kernel"][ai], prec).reshape(B, S, g.num_heads, g.head_dim)
            k = _mm(x, lp["k_proj"]["kernel"][ai], prec).reshape(B, S, g.num_kv_heads, g.head_dim)
            v = _mm(x, lp["v_proj"]["kernel"][ai], prec).reshape(B, S, g.num_kv_heads, g.head_dim)
            if cfg.attention_bias:
                q = q + lp["q_proj"]["bias"][ai].reshape(1, 1, g.num_heads, g.head_dim)
                k = k + lp["k_proj"]["bias"][ai].reshape(1, 1, g.num_kv_heads, g.head_dim)
                v = v + lp["v_proj"]["bias"][ai].reshape(1, 1, g.num_kv_heads, g.head_dim)
            if cfg.qk_norm:
                q = rms_norm(q, lp["q_norm"]["scale"][ai], eps, zc)
                k = rms_norm(k, lp["k_norm"]["scale"][ai], eps, zc)
            if inv_freq is not None:
                q = apply_rope(q, positions, inv_freq)
                k = apply_rope(k, positions, inv_freq)
            q = constrain(q, ("act_batch", "act_seq", "act_heads", None))
            if is_sparse:
                keep = sparse_keep_mask(
                    params["indexer"], cfg, x, positions, inv_freq, spi,
                    g.num_heads, segment_ids=segment_ids,
                )
                attn = _sparse_masked_attention(q, k, v, keep, g.head_dim ** -0.5)
            else:
                attn = dot_product_attention(
                    q, k, v, causal=cfg.causal, segment_ids=segment_ids,
                    positions=positions, sliding_window=g.sliding_window,
                    impl=cfg.attn_impl,
                )
            if cfg.head_gate:
                gate = jax.nn.sigmoid(x @ lp["g_proj"]["kernel"][ai])
                attn = attn * gate[..., :, None].astype(attn.dtype)
            attn = attn.reshape(B, S, g.num_heads * g.head_dim)
            out = _mm(attn, lp["o_proj"]["kernel"][ai], prec)
            if cfg.attention_bias and "bias" in lp["o_proj"]:
                out = out + lp["o_proj"]["bias"][ai]
            h = constrain(h + out, ("act_batch", "act_seq", "act_embed"))

            x = rms_norm(h, params["post_norms"]["scale"][li], eps, zc)
            if is_moe:
                mp = jax.tree.map(lambda p: p[mi], params["moe"])
                moe_out, aux, st = moe_forward(
                    mp, cfg.moe, x, constrain, token_mask=token_mask,
                    mesh_ctx=mesh_ctx,
                )
                if cfg.share_expert_dim:
                    moe_out = moe_out + _clamped_swiglu(
                        x, params["shared_mlp"], mi, cfg.swiglu_limit,
                        cfg.dense_activation, prec,
                    )
                h = h + moe_out
                extra = (aux, st["tokens_per_expert"])
            else:
                h = h + _clamped_swiglu(
                    x, params["dense_mlp"], mi, cfg.swiglu_limit,
                    cfg.dense_activation, prec,
                )
                extra = (jnp.float32(0.0), None)
            return constrain(h, ("act_batch", "act_seq", "act_embed")), extra

        h, (aux, tpe) = (jax.checkpoint(layer) if remat else layer)(h)
        aux_total = aux_total + aux
        if is_moe:
            stats_rows.append(tpe)

    h = rms_norm(h, params["final_norm"]["scale"], eps, zc)
    if return_hidden:
        out = h
    else:
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
    if return_stats:
        stats = {
            "tokens_per_expert": (
                jnp.stack(stats_rows) if stats_rows
                else jnp.zeros((0, cfg.moe.n_routed_experts), jnp.float32)
            )
        }
        return out, aux_total, stats
    return out, aux_total


def apply_gate_bias_update(params: dict, cfg: HetMoEConfig, tokens_per_expert) -> dict:
    """DeepSeek aux-free balancing over the het layout's stacked MoE gates
    (same math as moe_lm/decoder.apply_gate_bias_update; tokens_per_expert
    is (num_moe_layers, E))."""
    gate = params["moe"]["gate"]
    if "e_score_bias" not in gate:
        return params
    err = tokens_per_expert.mean(-1, keepdims=True) - tokens_per_expert
    new_bias = gate["e_score_bias"] + cfg.moe.gate_bias_update_speed * jnp.sign(err)
    new_gate = {**gate, "e_score_bias": new_bias}
    return {**params, "moe": {**params["moe"], "gate": new_gate}}
