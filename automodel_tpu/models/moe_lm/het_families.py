"""Step-3.5 and MiniMax-M3 family adapters for the heterogeneous MoE
decoder (models/moe_lm/het_moe.py). (MiMo-V2-Flash is served by the expert
decoder: models/moe_lm/families.py `mimo_v2_flash_config`.)

References: nemo_automodel/components/models/step3p5/ (model.py:235 MoE
mapping, layers.py:183 attention, state_dict_adapter.py stacked-expert
layout) and minimax_m3_vl/.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.models.moe_lm.het_moe import AttnGeom, HetMoEConfig
from automodel_tpu.moe.config import MoEConfig


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------
def step3p5_config(hf: Mapping[str, Any], **overrides) -> HetMoEConfig:
    """Step3p5ForCausalLM: sliding layers re-head via attention_other_setting,
    per-layer rope theta / partial rotary / NoPE, head-wise sigmoid gate,
    moe_layers_enum MoE placement with a separate shared expert."""
    L = int(hf["num_hidden_layers"])
    heads = int(hf["num_attention_heads"])
    kv = int(hf.get("num_attention_groups", heads))
    other = dict(hf.get("attention_other_setting") or {})
    head_dim = int(hf.get("head_dim", hf["hidden_size"] // heads))
    lt_raw = list(hf.get("layer_types") or ["full_attention"] * L)
    layer_types = tuple(
        "sliding" if t == "sliding_attention" else "global" for t in lt_raw
    )
    enum = hf.get("moe_layers_enum")
    if enum is None:
        moe_set = set(range(1, L))
    elif isinstance(enum, str):
        moe_set = {int(i) for i in enum.strip().split(",")}
    elif isinstance(enum, int):
        moe_set = {enum}
    else:
        moe_set = {int(i) for i in enum}
    thetas = hf.get("rope_theta", 10000.0)
    thetas = tuple(thetas) if isinstance(thetas, (list, tuple)) else (float(thetas),) * L
    prf = hf.get("partial_rotary_factors")
    prf = tuple(prf) if prf else (1.0,) * L
    use_rope = hf.get("use_rope_layers")
    use_rope = tuple(bool(b) for b in use_rope) if use_rope else (True,) * L
    use_bias = bool(hf.get("use_moe_router_bias", False))
    act = str(hf.get("moe_router_activation", "softmax"))
    share_dim = hf.get("share_expert_dims") or hf.get("share_expert_dim") or 0
    if isinstance(share_dim, (list, tuple)):
        if len(set(share_dim)) != 1:
            raise NotImplementedError("step3p5 per-layer share_expert_dims")
        share_dim = share_dim[0]
    limits = hf.get("swiglu_limits_shared") or hf.get("swiglu_limits")
    limit = None
    if limits:
        nz = {float(x) for x in limits if x}
        if len(nz) > 1:
            raise NotImplementedError("step3p5 per-layer swiglu limits")
        limit = nz.pop() if nz else None
    moe = MoEConfig(
        n_routed_experts=int(hf["moe_num_experts"]),
        experts_per_token=int(hf.get("moe_top_k", 2)),
        moe_intermediate_size=int(hf.get("moe_intermediate_size", hf["intermediate_size"])),
        score_func="sigmoid" if act == "sigmoid" else "softmax",
        norm_topk_prob=True,
        route_scale=float(hf.get("moe_router_scaling_factor", 1.0)),
        gate_bias_update_speed=0.001 if use_bias else 0.0,
    )
    kw = dict(
        vocab_size=int(hf["vocab_size"]),
        hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=L,
        layer_types=layer_types,
        global_attn=AttnGeom(num_heads=heads, num_kv_heads=kv, head_dim=head_dim),
        sliding_attn=AttnGeom(
            num_heads=int(other.get("num_attention_heads", heads)),
            num_kv_heads=int(other.get("num_attention_groups", kv)),
            head_dim=head_dim,
            sliding_window=int(hf.get("sliding_window") or 0) or None,
        ),
        qk_norm=True,
        head_gate=bool(hf.get("use_head_wise_attn_gate", False)),
        attention_bias=bool(hf.get("attention_bias", False)),
        rope_thetas=thetas,
        partial_rotary=prf,
        use_rope=use_rope,
        mlp_kinds=tuple("moe" if i in moe_set else "dense" for i in range(L)),
        moe=moe,
        share_expert_dim=int(share_dim),
        swiglu_limit=limit,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
    )
    moe_overrides = overrides.pop("moe", None)
    kw.update(overrides)  # unknown keys raise loudly in HetMoEConfig
    if moe_overrides is not None:
        kw["moe"] = moe_overrides
    return HetMoEConfig(**kw)


def minimax_m3_text_config(hf: Mapping[str, Any], **overrides) -> HetMoEConfig:
    """MiniMaxM3SparseForCausalLM (reference: models/minimax_m3_vl/config.py
    MiniMaxM3VLTextConfig + layers.py): single attention geometry with
    per-head GEMMA qk-norm, partial rope (rotary_dim of head_dim), per-layer
    dense-vs-MoE from moe_layer_freq (0 = dense), SwiGLU-OAI dense/shared
    MLPs, sigmoid routing with correction bias + routed scaling, and
    block-level DSA sparse attention on layers selected by
    sparse_attention_config.sparse_attention_freq.

    num_mtp_modules is accepted and DROPPED (the reference VL adapter's
    stage-1 behavior, state_dict_adapter.py:30; MTP for M3 is future work —
    training uses the main CE path only)."""
    L = int(hf["num_hidden_layers"])
    heads = int(hf["num_attention_heads"])
    kv = int(hf.get("num_key_value_heads", heads))
    head_dim = int(hf.get("head_dim", hf["hidden_size"] // heads))
    rotary_dim = int(hf.get("rotary_dim") or round(
        head_dim * float(hf.get("partial_rotary_factor", 1.0))
    ))
    freq = hf.get("moe_layer_freq")
    mlp_kinds = tuple(
        "dense" if (freq is not None and not freq[i]) else "moe" for i in range(L)
    )
    sp_cfg = dict(hf.get("sparse_attention_config") or {})
    if sp_cfg and sp_cfg.get("use_sparse_attention", True):
        sp_freq = sp_cfg.get("sparse_attention_freq")
        sparse = tuple(
            bool(sp_freq[i]) if sp_freq is not None else True for i in range(L)
        )
    else:
        sparse = ()
    n_shared = int(hf.get("n_shared_experts") or 0)
    moe = MoEConfig(
        n_routed_experts=int(hf.get("num_local_experts", hf.get("num_experts", 8))),
        n_shared_experts=0,  # shared expert is the swigluoai share_expert_dim path
        experts_per_token=int(hf.get("num_experts_per_tok", 4)),
        moe_intermediate_size=int(hf["intermediate_size"]),
        score_func=(
            "softmax" if str(hf.get("scoring_func", "sigmoid")).lower() == "softmax"
            else "sigmoid"
        ),
        norm_topk_prob=True,
        route_scale=float(hf.get("routed_scaling_factor", 1.0) or 1.0),
        gate_bias_update_speed=0.001 if bool(hf.get("use_routing_bias", True)) else 0.0,
        expert_activation="swigluoai",
        swiglu_limit=float(hf.get("swiglu_limit", 7.0)),
    )
    kw = dict(
        vocab_size=int(hf["vocab_size"]),
        hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf.get("dense_intermediate_size", hf["intermediate_size"])),
        num_layers=L,
        layer_types=("global",) * L,
        global_attn=AttnGeom(num_heads=heads, num_kv_heads=kv, head_dim=head_dim),
        sliding_attn=AttnGeom(num_heads=heads, num_kv_heads=kv, head_dim=head_dim),
        qk_norm=bool(hf.get("use_qk_norm", True)),
        rope_thetas=(float(hf.get("rope_theta", 5_000_000.0)),) * L,
        partial_rotary=(rotary_dim / head_dim,) * L,
        use_rope=(True,) * L,
        mlp_kinds=mlp_kinds,
        moe=moe,
        share_expert_dim=int(hf.get("shared_intermediate_size", hf["intermediate_size"])) * n_shared,
        swiglu_limit=float(hf.get("swiglu_limit", 7.0)),
        dense_activation="swigluoai",
        zero_centered_norm=bool(hf.get("use_gemma_norm", True)),
        sparse_attn=sparse,
        sparse_index_heads=int(sp_cfg.get("sparse_num_index_heads", 1) or 1),
        sparse_index_dim=int(sp_cfg.get("sparse_index_dim", 64) or 64),
        sparse_block_size=int(sp_cfg.get("sparse_block_size", 32) or 32),
        sparse_topk_blocks=int(sp_cfg.get("sparse_topk_blocks", 8) or 8),
        sparse_init_blocks=int(sp_cfg.get("sparse_init_block", 0) or 0),
        sparse_local_blocks=int(sp_cfg.get("sparse_local_block", 1) or 1),
        sparse_score_type=str(sp_cfg.get("sparse_score_type", "max")),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
    )
    if bool(hf.get("attention_output_gate", False)):
        raise NotImplementedError(
            "minimax_m3 attention_output_gate (the reference rejects it too: "
            "minimax_m3_vl/layers.py:411)"
        )
    moe_overrides = overrides.pop("moe", None)
    kw.update(overrides)  # unknown keys raise loudly in HetMoEConfig
    if moe_overrides is not None:
        kw["moe"] = moe_overrides
    return HetMoEConfig(**kw)


# ---------------------------------------------------------------------------
# state-dict adapter (shared; per-family naming via `style`)
# ---------------------------------------------------------------------------
class HetMoEAdapter:
    """HF ↔ het_moe params.

    style="step3p5": self_attn.{q,k}_norm + g_proj; STACKED expert tensors
    moe.{gate,up,down}_proj.weight (E, I, H)/(E, H, I), router moe.gate.weight
    (E, H) + moe.router_bias, shared expert under share_expert.*.
    style="minimax_m3": per-expert block_sparse_moe.experts.{e}.w1/w3/w2
    (gate/up/down), router block_sparse_moe.gate.weight +
    block_sparse_moe.e_score_correction_bias, shared experts under
    block_sparse_moe.shared_experts.* (→ the share_expert_dim shared_mlp),
    indexer self_attn.index_{q,k}_{proj,norm} on sparse layers (reference:
    minimax_m3_vl/state_dict_adapter.py key maps).
    """

    _M3_PROJ = {"gate_proj": "w1", "up_proj": "w3", "down_proj": "w2"}

    def __init__(self, cfg: HetMoEConfig, style: str = "step3p5"):
        self.cfg = cfg
        self.style = style

    # per-layer bookkeeping -------------------------------------------------
    def _index_maps(self):
        from automodel_tpu.models.moe_lm.het_moe import layer_rows

        return layer_rows(self.cfg)

    _IDX_ENTRIES = [
        ("self_attn.index_q_proj.weight", ("index_q_proj", "kernel"), True),
        ("self_attn.index_k_proj.weight", ("index_k_proj", "kernel"), True),
        ("self_attn.index_q_norm.weight", ("index_q_norm", "scale"), False),
        ("self_attn.index_k_norm.weight", ("index_k_norm", "scale"), False),
    ]

    def _attn_entries(self, g: AttnGeom):
        e = [
            ("self_attn.q_proj.weight", ("q_proj", "kernel"), True),
            ("self_attn.k_proj.weight", ("k_proj", "kernel"), True),
            ("self_attn.v_proj.weight", ("v_proj", "kernel"), True),
            ("self_attn.o_proj.weight", ("o_proj", "kernel"), True),
        ]
        if self.cfg.qk_norm:
            e += [
                ("self_attn.q_norm.weight", ("q_norm", "scale"), False),
                ("self_attn.k_norm.weight", ("k_norm", "scale"), False),
            ]
        if self.cfg.head_gate:
            e.append(("self_attn.g_proj.weight", ("g_proj", "kernel"), True))
        return e

    def to_hf(self, params):
        cfg = self.cfg
        E = cfg.moe.n_routed_experts

        def _t(x):
            return np.ascontiguousarray(np.asarray(x).T)

        yield "model.embed_tokens.weight", np.asarray(params["embed"]["embedding"])
        yield "model.norm.weight", np.asarray(params["final_norm"]["scale"])
        if not cfg.tie_word_embeddings:
            yield "lm_head.weight", _t(params["lm_head"]["kernel"])
        for li, lt, a_key, ai, is_moe, mi, is_sparse, spi in self._index_maps():
            base = f"model.layers.{li}."
            yield base + "input_layernorm.weight", np.asarray(
                params["input_norms"]["scale"][li]
            )
            yield base + "post_attention_layernorm.weight", np.asarray(
                params["post_norms"]["scale"][li]
            )
            ap = params[a_key]
            for suf, path, tr in self._attn_entries(cfg.geom(lt)):
                node = ap
                for pseg in path:
                    node = node[pseg]
                x = np.asarray(node[ai])
                yield base + suf, (_t(x) if tr else x)
            if is_sparse:
                for suf, path, tr in self._IDX_ENTRIES:
                    node = params["indexer"]
                    for pseg in path:
                        node = node[pseg]
                    x = np.asarray(node[spi])
                    yield base + suf, (_t(x) if tr else x)
            if not is_moe:
                for proj in ("gate_proj", "up_proj", "down_proj"):
                    yield base + f"mlp.{proj}.weight", _t(
                        params["dense_mlp"][proj]["kernel"][mi]
                    )
                continue
            moe = params["moe"]
            if self.style == "minimax_m3":
                yield base + "block_sparse_moe.gate.weight", _t(
                    np.asarray(moe["gate"]["weight"][mi])
                )
                if "e_score_bias" in moe["gate"]:
                    yield base + "block_sparse_moe.e_score_correction_bias", (
                        np.asarray(moe["gate"]["e_score_bias"][mi])
                    )
                for e in range(E):
                    for proj, w in self._M3_PROJ.items():
                        yield base + f"block_sparse_moe.experts.{e}.{w}.weight", _t(
                            np.asarray(moe["experts"][proj]["kernel"][mi, e])
                        )
                if cfg.share_expert_dim:
                    for proj in ("gate_proj", "up_proj", "down_proj"):
                        yield base + f"block_sparse_moe.shared_experts.{proj}.weight", _t(
                            params["shared_mlp"][proj]["kernel"][mi]
                        )
            else:  # step3p5
                yield base + "moe.gate.weight", _t(np.asarray(moe["gate"]["weight"][mi]))
                if "e_score_bias" in moe["gate"]:
                    yield base + "moe.router_bias", np.asarray(
                        moe["gate"]["e_score_bias"][mi]
                    )
                # stacked (E, I, H)/(E, H, I): ours are (E, H, I)/(E, I, H)
                for proj in ("gate_proj", "up_proj", "down_proj"):
                    w = np.asarray(moe["experts"][proj]["kernel"][mi])
                    yield base + f"moe.{proj}.weight", np.ascontiguousarray(
                        np.swapaxes(w, -1, -2)
                    )
                if cfg.share_expert_dim:
                    for proj in ("gate_proj", "up_proj", "down_proj"):
                        yield base + f"share_expert.{proj}.weight", _t(
                            params["shared_mlp"][proj]["kernel"][mi]
                        )

    def from_hf(self, read, shardings=None) -> dict:
        from automodel_tpu.checkpoint.hf_adapter import _get, _set, memo1_reader

        read = memo1_reader(read)
        cfg = self.cfg
        E = cfg.moe.n_routed_experts
        rows = self._index_maps()
        params: dict = {}

        def put(path, value):
            sh = _get(shardings, path) if shardings is not None else None
            _set(params, path, jax.device_put(value, sh) if sh is not None else jnp.asarray(value))

        def one(name, tr):
            x = np.asarray(read(name))
            return np.ascontiguousarray(x.T) if tr else x

        put(("embed", "embedding"), one("model.embed_tokens.weight", False))
        put(("final_norm", "scale"), one("model.norm.weight", False))
        if not cfg.tie_word_embeddings:
            put(("lm_head", "kernel"), one("lm_head.weight", True))
        put(("input_norms", "scale"), np.stack([
            one(f"model.layers.{li}.input_layernorm.weight", False)
            for li in range(cfg.num_layers)
        ]))
        put(("post_norms", "scale"), np.stack([
            one(f"model.layers.{li}.post_attention_layernorm.weight", False)
            for li in range(cfg.num_layers)
        ]))
        for a_key, lt_name in (("g_attn", "global"), ("s_attn", "sliding")):
            lis = [r for r in rows if r[1] == lt_name]
            if not lis:
                # dummy stack kept for pytree uniformity — placed onto its
                # declared shardings so jitted in_shardings stay consistent
                from automodel_tpu.models.moe_lm.het_moe import _init_attn_group

                dummy = _init_attn_group(cfg, cfg.geom(lt_name), jax.random.key(0), 1)
                sub = _get(shardings, (a_key,)) if shardings is not None else None
                if sub is not None:
                    params[a_key] = jax.tree.map(jax.device_put, dummy, sub)
                else:
                    params[a_key] = jax.tree.map(jnp.asarray, dummy)
                continue
            for suf, path, tr in self._attn_entries(cfg.geom(lt_name)):
                put(
                    (a_key,) + path,
                    np.stack([
                        one(f"model.layers.{li}.{suf}", tr)
                        for (li, *_rest) in lis
                    ]),
                )
        sparse_rows = [r for r in rows if r[6]]
        if sparse_rows:
            for suf, path, tr in self._IDX_ENTRIES:
                put(("indexer",) + path, np.stack([
                    one(f"model.layers.{li}.{suf}", tr)
                    for (li, *_r) in sparse_rows
                ]))
        dense_rows = [r for r in rows if not r[4]]
        if dense_rows:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                put(("dense_mlp", proj, "kernel"), np.stack([
                    one(f"model.layers.{li}.mlp.{proj}.weight", True)
                    for (li, *_r) in dense_rows
                ]))
        moe_rows = [r for r in rows if r[4]]
        if moe_rows:
            if self.style == "minimax_m3":
                put(("moe", "gate", "weight"), np.stack([
                    one(f"model.layers.{li}.block_sparse_moe.gate.weight", True)
                    for (li, *_r) in moe_rows
                ]))
                if cfg.moe.gate_bias_update_speed > 0:
                    put(("moe", "gate", "e_score_bias"), np.stack([
                        one(
                            f"model.layers.{li}.block_sparse_moe."
                            "e_score_correction_bias",
                            False,
                        )
                        for (li, *_r) in moe_rows
                    ]))
                for proj, w in self._M3_PROJ.items():
                    put(("moe", "experts", proj, "kernel"), np.stack([
                        np.stack([
                            one(
                                f"model.layers.{li}.block_sparse_moe."
                                f"experts.{e}.{w}.weight",
                                True,
                            )
                            for e in range(E)
                        ])
                        for (li, *_r) in moe_rows
                    ]))
                if cfg.share_expert_dim:
                    for proj in ("gate_proj", "up_proj", "down_proj"):
                        put(("shared_mlp", proj, "kernel"), np.stack([
                            one(
                                f"model.layers.{li}.block_sparse_moe."
                                f"shared_experts.{proj}.weight",
                                True,
                            )
                            for (li, *_r) in moe_rows
                        ]))
            else:  # step3p5
                put(("moe", "gate", "weight"), np.stack([
                    one(f"model.layers.{li}.moe.gate.weight", True)
                    for (li, *_r) in moe_rows
                ]))
                if cfg.moe.gate_bias_update_speed > 0:
                    put(("moe", "gate", "e_score_bias"), np.stack([
                        one(f"model.layers.{li}.moe.router_bias", False)
                        for (li, *_r) in moe_rows
                    ]))
                for proj in ("gate_proj", "up_proj", "down_proj"):
                    put(("moe", "experts", proj, "kernel"), np.stack([
                        np.ascontiguousarray(np.swapaxes(
                            np.asarray(read(f"model.layers.{li}.moe.{proj}.weight")),
                            -1, -2,
                        ))
                        for (li, *_r) in moe_rows
                    ]))
                if cfg.share_expert_dim:
                    for proj in ("gate_proj", "up_proj", "down_proj"):
                        put(("shared_mlp", proj, "kernel"), np.stack([
                            one(f"model.layers.{li}.share_expert.{proj}.weight", True)
                            for (li, *_r) in moe_rows
                        ]))
        return params


def _register_adapter():
    from automodel_tpu.checkpoint.hf_adapter import ADAPTERS

    ADAPTERS["het_moe"] = HetMoEAdapter


_register_adapter()
