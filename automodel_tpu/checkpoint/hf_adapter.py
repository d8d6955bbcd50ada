"""HF-checkpoint ↔ stacked-pytree state-dict adapters.

The analog of the reference's per-model `StateDictAdapter`
(reference: nemo_automodel/components/checkpoint/state_dict_adapter.py:20
abstract to_hf/from_hf; models/*/state_dict_adapter.py; MoE split/merge
moe/state_dict_mixin.py): zero-conversion I/O between Hugging Face
safetensors checkpoints and this framework's stacked-layer parameter
pytrees. Key transforms:

- HF `nn.Linear.weight` is (out, in); our kernels are (in, out) → transpose.
- Per-layer HF tensors `model.layers.{i}.…` ↔ one stacked array dim 0.
- Per-expert HF tensors `…experts.{e}.…` ↔ the (L, E, …) grouped arrays
  (the MoESplitExpertsStateDictMixin analog).
- Loading streams tensor-by-tensor from safetensors shards (lazy
  `safe_open`), assembling each stacked param then placing it directly into
  its target sharding — host memory peaks at one parameter, mirroring the
  reference's streamed `load_base_model` (checkpointing.py:722).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Any, Callable, Iterator, Mapping

import jax
import numpy as np

logger = logging.getLogger(__name__)

from automodel_tpu.models.llm.decoder import TransformerConfig

Reader = Callable[[str], np.ndarray]


def _t(x: np.ndarray) -> np.ndarray:
    return np.asarray(x).T


def _rope_perm(dr: int, inverse: bool) -> np.ndarray:
    """DeepSeek checkpoints store rope dims in interleaved pair order
    ((0,1),(2,3),…) while this framework rotates the llama half-split way
    ([evens…, odds…]); permute the weight COLUMNS once at load/export so
    runtime rotation needs no de-interleave (the vLLM approach)."""
    deinter = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    if not inverse:
        return deinter
    inv = np.empty(dr, np.int64)
    inv[deinter] = np.arange(dr)
    return inv


def _permute_q_rope(kernel: np.ndarray, n_heads: int, dn: int, dr: int, inverse: bool) -> np.ndarray:
    """kernel (…, in, n_heads*(dn+dr)): permute each head's rope columns."""
    *lead, fan_in, out = kernel.shape
    k = kernel.reshape(*lead, fan_in, n_heads, dn + dr)
    perm = _rope_perm(dr, inverse)
    rope = k[..., dn:][..., perm]
    k = np.concatenate([k[..., :dn], rope], axis=-1)
    return k.reshape(*lead, fan_in, out)


def _permute_k_rope(kernel: np.ndarray, kv_rank: int, dr: int, inverse: bool) -> np.ndarray:
    """kv_down kernel (…, in, kv_rank+dr): permute the trailing rope cols."""
    perm = _rope_perm(dr, inverse)
    rope = kernel[..., kv_rank:][..., perm]
    return np.concatenate([kernel[..., :kv_rank], rope], axis=-1)


def reader_has_key(read, key: str) -> bool:
    """O(1) key-existence probe when `read` exposes keys() (HFCheckpointReader
    / dict); falls back to a try-read for plain callables (tests)."""
    ks = getattr(read, "keys", None)
    if callable(ks):
        return key in ks()
    try:
        read(key)
        return True
    except KeyError:
        return False


def memo1_reader(read):
    """Wrap `read` with a one-entry cache — per-expert adapter shims slice
    the same stacked tensor E times in a row; this makes that one disk read
    without holding more than one tensor."""
    last: dict = {}

    def cached(name):
        if last.get("name") != name:
            last["name"], last["val"] = name, read(name)
        return last["val"]

    ks = getattr(read, "keys", None)
    if callable(ks):
        cached.keys = ks  # preserve the O(1) existence probe
    return cached


def _stack_layers_zero_fill(one, names, transpose, tr, absent_ok):
    """Stack per-layer tensors, zero-filling layers `absent_ok` declares
    keyless (GLM IndexShare "shared" layers own no indexer weights). A key
    missing on a layer that should have one raises KeyError — that is a
    broken checkpoint (or the reference's compressed-indexer layout), and
    the caller's skip-and-backfill path must handle it, not silent zeros."""
    vals = []
    for j, n in enumerate(names):
        try:
            vals.append(one(n, transpose, tr))
        except KeyError:
            if not absent_ok(j):
                raise
            vals.append(None)
    ref = next((v for v in vals if v is not None), None)
    if ref is None:
        raise KeyError(names[0])
    return np.stack([v if v is not None else np.zeros_like(ref) for v in vals])


@dataclasses.dataclass
class DenseDecoderAdapter:
    """llama/mistral/qwen2/qwen3/gemma2/glm4/ernie ↔ models/llm/decoder params.

    `style="glm4"` switches to GLM-4 naming: the fused `mlp.gate_up_proj`
    (first half gate, second half up — transformers modeling_glm4 Glm4MLP)
    and the `post_self_attn/post_mlp_layernorm` sandwich-norm names.
    `style="ouro"` names the sandwich norms `input_layernorm_2` /
    `post_attention_layernorm_2`; a model with an exit gate carries
    `model.early_exit_gate.{weight,bias}`.
    """

    cfg: TransformerConfig
    style: str = "llama"

    # -- name tables ---------------------------------------------------------
    def _layer_entries(self) -> list[tuple[str, tuple, bool]]:
        """(hf_suffix, param_path, transpose) per layer."""
        cfg = self.cfg
        if getattr(cfg, "attention_type", "gqa") == "mla":
            return self._mla_layer_entries()
        e = []
        if self._fused_qkv_name() is None:
            e += [
                ("self_attn.q_proj.weight", ("q_proj", "kernel"), True),
                ("self_attn.k_proj.weight", ("k_proj", "kernel"), True),
                ("self_attn.v_proj.weight", ("v_proj", "kernel"), True),
            ]
        o_name = (
            "attention.dense.weight" if self.style == "bailing"
            else "self_attn.o_proj.weight"
        )
        e += [
            (o_name, ("o_proj", "kernel"), True),
            ("mlp.down_proj.weight", ("down_proj", "kernel"), True),
            ("input_layernorm.weight", ("input_norm", "scale"), False),
        ]
        if self.style != "glm4":  # glm4 fuses these into mlp.gate_up_proj
            e += [
                ("mlp.gate_proj.weight", ("gate_proj", "kernel"), True),
                ("mlp.up_proj.weight", ("up_proj", "kernel"), True),
            ]
        if cfg.use_post_norms:
            if self.style == "glm4":
                e += [
                    ("post_self_attn_layernorm.weight", ("post_attn_out_norm", "scale"), False),
                    ("post_attention_layernorm.weight", ("post_attn_norm", "scale"), False),
                    ("post_mlp_layernorm.weight", ("post_mlp_norm", "scale"), False),
                ]
            elif self.style == "ouro":
                e += [
                    ("input_layernorm_2.weight", ("post_attn_out_norm", "scale"), False),
                    ("post_attention_layernorm.weight", ("post_attn_norm", "scale"), False),
                    ("post_attention_layernorm_2.weight", ("post_mlp_norm", "scale"), False),
                ]
            else:
                # gemma2 4-norm naming
                e += [
                    ("post_attention_layernorm.weight", ("post_attn_out_norm", "scale"), False),
                    ("pre_feedforward_layernorm.weight", ("post_attn_norm", "scale"), False),
                    ("post_feedforward_layernorm.weight", ("post_mlp_norm", "scale"), False),
                ]
        else:
            e.append(("post_attention_layernorm.weight", ("post_attn_norm", "scale"), False))
        if cfg.attention_bias:
            e += [
                ("self_attn.q_proj.bias", ("q_proj", "bias"), False),
                ("self_attn.k_proj.bias", ("k_proj", "bias"), False),
                ("self_attn.v_proj.bias", ("v_proj", "bias"), False),
            ]
        if cfg.qk_norm or getattr(cfg, "qk_norm_flat", False):
            if self.style == "hunyuan":
                e += [
                    ("self_attn.query_layernorm.weight", ("q_norm", "scale"), False),
                    ("self_attn.key_layernorm.weight", ("k_norm", "scale"), False),
                ]
            elif self.style == "bailing":
                e += [
                    ("attention.query_layernorm.weight", ("q_norm", "scale"), False),
                    ("attention.key_layernorm.weight", ("k_norm", "scale"), False),
                ]
            else:
                e += [
                    ("self_attn.q_norm.weight", ("q_norm", "scale"), False),
                    ("self_attn.k_norm.weight", ("k_norm", "scale"), False),
                ]
        if getattr(cfg, "o_proj_bias", False):
            e.append(("self_attn.o_proj.bias", ("o_proj", "bias"), False))
        if getattr(cfg, "attention_sinks", False):
            e.append(("self_attn.sinks", ("sinks",), False))
        return [entry if len(entry) == 4 else (*entry, None) for entry in e]

    def _mla_layer_entries(self) -> list[tuple[str, tuple, bool]]:
        cfg = self.cfg
        e = [
            ("input_layernorm.weight", ("input_norm", "scale"), False),
            ("post_attention_layernorm.weight", ("post_attn_norm", "scale"), False),
            ("self_attn.kv_a_proj_with_mqa.weight", ("kv_down_proj", "kernel"), True, "k_rope"),
            ("self_attn.kv_a_layernorm.weight", ("kv_norm", "scale"), False),
            ("self_attn.kv_b_proj.weight", ("kv_up_proj", "kernel"), True),
            ("self_attn.o_proj.weight", ("o_proj", "kernel"), True),
        ]
        if cfg.mla_q_lora_rank:
            e += [
                ("self_attn.q_a_proj.weight", ("q_down_proj", "kernel"), True),
                ("self_attn.q_a_layernorm.weight", ("q_norm", "scale"), False),
                ("self_attn.q_b_proj.weight", ("q_up_proj", "kernel"), True, "q_rope"),
            ]
        else:
            e.append(("self_attn.q_proj.weight", ("q_proj", "kernel"), True, "q_rope"))
        if getattr(cfg, "dsa_index_topk", None) is not None:
            if getattr(cfg, "dsa_indexer_style", "deepseek") == "glm":
                # GLM-5.x indexer: HF-layout-compatible (glm_moe_dsa/
                # layers.py — wq_b from the q-lora residual, LayerNorm'd wk,
                # weights_proj). IndexShare "shared" layers carry no indexer
                # keys; the loaders zero-fill those stack rows (unused).
                e += [
                    ("self_attn.indexer.wq_b.weight", ("indexer", "wq", "kernel"), True),
                    ("self_attn.indexer.wk.weight", ("indexer", "wk", "kernel"), True),
                    ("self_attn.indexer.k_norm.weight", ("indexer", "k_norm", "scale"), False),
                    ("self_attn.indexer.k_norm.bias", ("indexer", "k_norm", "bias"), False),
                    ("self_attn.indexer.weights_proj.weight", ("indexer", "wgate", "kernel"), True),
                ]
            else:
                # DSA lightning indexer — OUR uncompressed parameterization
                # (reference DSv4 checkpoints carry the compressed
                # wkv/wq_b/weights_proj form, which is not layout-compatible;
                # those keys are absent here, the loaders treat indexer
                # entries as optional, and the recipe backfills + warns)
                e += [
                    ("self_attn.indexer.wq.weight", ("indexer", "wq", "kernel"), True),
                    ("self_attn.indexer.wk.weight", ("indexer", "wk", "kernel"), True),
                    ("self_attn.indexer.wgate.weight", ("indexer", "wgate", "kernel"), True),
                ]
        # note: MLA models pair with the MoE adapter; MLP entries come from
        # the dense path only for the first-k dense layers
        e += [
            ("mlp.gate_proj.weight", ("gate_proj", "kernel"), True),
            ("mlp.up_proj.weight", ("up_proj", "kernel"), True),
            ("mlp.down_proj.weight", ("down_proj", "kernel"), True),
        ]
        return [entry if len(entry) == 4 else (*entry, None) for entry in e]

    def _fused_qkv_name(self) -> str | None:
        """HF key suffix when the checkpoint stores q/k/v fused: baichuan
        W_pack, bailing (Ling 2.0) query_key_value — row order [Q|K|V]."""
        return {
            "baichuan": "self_attn.W_pack.weight",
            "bailing": "attention.query_key_value.weight",
        }.get(self.style)

    def _split_fused_qkv(self, w: np.ndarray) -> dict[str, np.ndarray]:
        """HF fused (q+k+v, H) → our per-projection (H, ·) kernels."""
        D = self.cfg.resolved_head_dim
        qd, kd = self.cfg.num_heads * D, self.cfg.num_kv_heads * D
        wT = np.ascontiguousarray(w.T)
        return {
            "q_proj": wT[:, :qd],
            "k_proj": wT[:, qd : qd + kd],
            "v_proj": wT[:, qd + kd : qd + 2 * kd],
        }

    def _fuse_qkv(self, layers, i: int) -> np.ndarray:
        """Inverse of _split_fused_qkv for layer i → HF (q+k+v, H)."""
        cat = np.concatenate(
            [np.asarray(layers[p]["kernel"][i]) for p in ("q_proj", "k_proj", "v_proj")],
            axis=1,
        )
        return _t(cat)

    def _top_entries(self) -> list[tuple[str, tuple, bool]]:
        embed_name = (
            "model.word_embeddings.weight" if self.style == "bailing"
            else "model.embed_tokens.weight"
        )
        e = [
            (embed_name, ("embed", "embedding"), False),
            ("model.norm.weight", ("final_norm", "scale"), False),
        ]
        if not self.cfg.tie_word_embeddings:
            e.append(("lm_head.weight", ("lm_head", "kernel"), True))
        if getattr(self.cfg, "exit_gate", False):
            # nn.Linear(hidden, 1): weight (1, H), bias (1,)
            e += [
                ("model.early_exit_gate.weight", ("exit_gate", "kernel"), True),
                ("model.early_exit_gate.bias", ("exit_gate", "bias"), False),
            ]
        return [(*entry, None) for entry in e]

    def _indexer_absent(self, layer_idx: int) -> bool:
        """GLM IndexShare "shared" layers own no indexer in HF checkpoints;
        the zero-filled stack rows must not be exported as real keys."""
        t = getattr(self.cfg, "dsa_indexer_types", None)
        return t is not None and t[layer_idx] == "shared"

    def _transform(self, x: np.ndarray, tname: str | None, inverse: bool) -> np.ndarray:
        """Named weight transforms (rope layout permutations; see _rope_perm)."""
        if tname is None:
            return x
        cfg = self.cfg
        if tname == "q_rope":
            return _permute_q_rope(
                x, cfg.num_heads, cfg.mla_qk_nope_head_dim, cfg.mla_qk_rope_head_dim, inverse
            )
        if tname == "k_rope":
            return _permute_k_rope(
                x, cfg.mla_kv_lora_rank, cfg.mla_qk_rope_head_dim, inverse
            )
        raise KeyError(tname)

    # -- export --------------------------------------------------------------
    def to_hf(self, params: Mapping) -> Iterator[tuple[str, np.ndarray]]:
        """Yield (hf_name, tensor) — layer-stacked params are unstacked."""
        for name, path, transpose, tr in self._top_entries():
            x = np.asarray(_get(params, path))
            x = self._transform(x, tr, inverse=True)
            yield name, (_t(x) if transpose else x)
        layers = params["layers"]
        for i in range(self.cfg.num_layers):
            for suffix, path, transpose, tr in self._layer_entries():
                if path[0] == "indexer" and self._indexer_absent(i):
                    continue
                x = np.asarray(_get(layers, path)[i])
                x = self._transform(x, tr, inverse=True)
                yield f"model.layers.{i}.{suffix}", (_t(x) if transpose else x)
            if self.style == "glm4":
                g = np.asarray(layers["gate_proj"]["kernel"][i])  # (H, I)
                u = np.asarray(layers["up_proj"]["kernel"][i])
                yield (
                    f"model.layers.{i}.mlp.gate_up_proj.weight",
                    _t(np.concatenate([g, u], axis=1)),
                )
            if self._fused_qkv_name() is not None:
                yield (
                    f"model.layers.{i}.{self._fused_qkv_name()}",
                    self._fuse_qkv(layers, i),
                )

    # -- import --------------------------------------------------------------
    def from_hf(self, read: Reader, shardings: Any = None) -> dict:
        """Assemble the params pytree; `shardings` (same tree) places each
        param directly into its target layout as it is built.

        Key fallbacks: base-model checkpoints (e.g. LlamaBidirectionalModel
        saved without the CausalLM wrapper) drop the `model.` prefix, and
        head-swapped checkpoints (ForSequenceClassification) carry no
        `lm_head.weight` — that leaf is then simply absent and the consumer
        (seq-cls/retrieval recipes) installs its own head."""
        out: dict = {}

        def put(path, value):
            sh = _get(shardings, path) if shardings is not None else None
            _set(out, path, jax.device_put(value, sh) if sh is not None else value)

        def read_any(name):
            try:
                return read(name)
            except KeyError:
                if name.startswith("model."):
                    return read(name[len("model."):])
                raise

        def one(name, transpose, tr):
            x = _t(read_any(name)) if transpose else np.asarray(read_any(name))
            return self._transform(x, tr, inverse=False)

        for name, path, transpose, tr in self._top_entries():
            try:
                put(path, one(name, transpose, tr))
            except KeyError:
                if path == ("lm_head", "kernel"):
                    logger.warning("checkpoint has no lm_head.weight; leaf omitted")
                    continue
                raise
        for suffix, path, transpose, tr in self._layer_entries():
            names = [f"model.layers.{i}.{suffix}" for i in range(self.cfg.num_layers)]
            try:
                if path[0] == "indexer":
                    stacked = _stack_layers_zero_fill(
                        one, names, transpose, tr, self._indexer_absent
                    )
                else:
                    stacked = np.stack([one(n, transpose, tr) for n in names])
            except KeyError:
                if path[0] == "indexer":  # optional: see _mla_layer_entries
                    continue
                raise
            put(("layers",) + path, stacked)
        if self.style == "glm4":
            fused = np.stack(
                [
                    _t(read_any(f"model.layers.{i}.mlp.gate_up_proj.weight"))
                    for i in range(self.cfg.num_layers)
                ]
            )  # (L, H, 2I)
            I = self.cfg.intermediate_size
            put(("layers", "gate_proj", "kernel"), fused[..., :I])
            put(("layers", "up_proj", "kernel"), fused[..., I:])
        if self._fused_qkv_name() is not None:
            splits = [
                self._split_fused_qkv(
                    np.asarray(read_any(f"model.layers.{i}.{self._fused_qkv_name()}"))
                )
                for i in range(self.cfg.num_layers)
            ]
            for p in ("q_proj", "k_proj", "v_proj"):
                put(("layers", p, "kernel"), np.stack([s[p] for s in splits]))
        return out


@dataclasses.dataclass
class MoEDecoderAdapter:
    """qwen3_moe / mixtral ↔ models/moe_lm/decoder params.

    Per-expert HF weights split/merge into the grouped (L, E, H, I) arrays
    (reference: moe/state_dict_mixin.py MoESplitExpertsStateDictMixin).
    """

    cfg: Any  # MoETransformerConfig
    style: str = "qwen3_moe"  # or "mixtral"

    def _expert_names(self, i: int, e: int) -> dict:
        if self.style in ("mixtral", "minimax"):
            base = f"model.layers.{i}.block_sparse_moe.experts.{e}"
            return {
                "gate_proj": f"{base}.w1.weight",
                "up_proj": f"{base}.w3.weight",
                "down_proj": f"{base}.w2.weight",
            }
        base = f"model.layers.{i}.mlp.experts.{e}"
        return {k: f"{base}.{k}.weight" for k in ("gate_proj", "up_proj", "down_proj")}

    def _gate_name(self, i: int) -> str:
        if self.style in ("mixtral", "minimax"):
            return f"model.layers.{i}.block_sparse_moe.gate.weight"
        if self.style == "gpt_oss":
            return f"model.layers.{i}.mlp.router.weight"
        if self.style == "hunyuan":
            return f"model.layers.{i}.mlp.gate.wg.weight"
        if self.style == "hy_mt2":
            return f"model.layers.{i}.mlp.router.gate.weight"
        return f"model.layers.{i}.mlp.gate.weight"

    def _shared_base(self, i: int) -> str:
        if self.style in ("hunyuan", "hy_mt2"):
            return f"model.layers.{i}.mlp.shared_mlp"
        return f"model.layers.{i}.mlp.shared_experts"

    def _escore_name(self, i: int) -> str:
        # ernie stores the aux-free bias under moe_statics with a leading
        # groups dim of 1 (transformers Ernie4_5_MoeStatics)
        if self.style == "ernie":
            return f"model.layers.{i}.mlp.moe_statics.e_score_correction_bias"
        if self.style == "minimax":
            return f"model.layers.{i}.block_sparse_moe.e_score_correction_bias"
        if self.style == "bailing":
            return f"model.layers.{i}.mlp.gate.expert_bias"
        if self.style == "hy_mt2":
            return f"model.layers.{i}.mlp.expert_bias"
        return f"model.layers.{i}.mlp.gate.e_score_correction_bias"

    def _dense(self) -> DenseDecoderAdapter:
        # styles the dense adapter understands (attention/norm naming)
        style = self.style if self.style in ("glm4", "hunyuan", "bailing") else "llama"
        return DenseDecoderAdapter(self.cfg, style=style)

    def _attn_entries(self):
        mlp_keys = ("gate_proj", "up_proj", "down_proj")
        return [
            entry
            for entry in self._dense()._layer_entries()
            if entry[1][0] not in mlp_keys
        ]

    def to_hf(self, params: Mapping) -> Iterator[tuple[str, np.ndarray]]:
        cfg = self.cfg
        dense = self._dense()
        for name, path, transpose, tr in dense._top_entries():
            x = dense._transform(np.asarray(_get(params, path)), tr, inverse=True)
            yield name, (_t(x) if transpose else x)
        fk = cfg.first_k_dense
        fused = dense._fused_qkv_name()
        if fk:
            for i in range(fk):
                for suffix, path, transpose, tr in dense._layer_entries():
                    if path[0] == "indexer" and dense._indexer_absent(i):
                        continue
                    x = dense._transform(
                        np.asarray(_get(params["dense_layers"], path)[i]), tr, inverse=True
                    )
                    yield f"model.layers.{i}.{suffix}", (_t(x) if transpose else x)
                if fused is not None:
                    yield (
                        f"model.layers.{i}.{fused}",
                        dense._fuse_qkv(params["dense_layers"], i),
                    )
        moe_layers = params["moe_layers"]
        for li in range(cfg.num_moe_layers):
            i = fk + li
            for suffix, path, transpose, tr in self._attn_entries():
                if path[0] == "indexer" and dense._indexer_absent(i):
                    continue
                x = dense._transform(
                    np.asarray(_get(moe_layers, path)[li]), tr, inverse=True
                )
                yield f"model.layers.{i}.{suffix}", (_t(x) if transpose else x)
            if fused is not None:
                yield f"model.layers.{i}.{fused}", dense._fuse_qkv(moe_layers, li)
            moe = moe_layers["moe"]
            yield self._gate_name(i), _t(np.asarray(moe["gate"]["weight"][li]))
            if "bias" in moe["gate"]:
                yield self._gate_name(i).replace(".weight", ".bias"), np.asarray(
                    moe["gate"]["bias"][li]
                )
            if self.style == "gpt_oss":
                ek = moe["experts"]
                g = np.asarray(ek["gate_proj"]["kernel"][li])  # (E, H, I)
                u = np.asarray(ek["up_proj"]["kernel"][li])
                fused = np.empty((g.shape[0], g.shape[1], 2 * g.shape[2]), g.dtype)
                fused[..., ::2] = g
                fused[..., 1::2] = u
                yield f"model.layers.{i}.mlp.experts.gate_up_proj", fused
                gb = np.asarray(ek["gate_proj"]["bias"][li])
                ub = np.asarray(ek["up_proj"]["bias"][li])
                fb = np.empty((gb.shape[0], 2 * gb.shape[1]), gb.dtype)
                fb[..., ::2] = gb
                fb[..., 1::2] = ub
                yield f"model.layers.{i}.mlp.experts.gate_up_proj_bias", fb
                yield f"model.layers.{i}.mlp.experts.down_proj", np.asarray(
                    ek["down_proj"]["kernel"][li]
                )
                yield f"model.layers.{i}.mlp.experts.down_proj_bias", np.asarray(
                    ek["down_proj"]["bias"][li]
                )
                continue
            if "e_score_bias" in moe["gate"]:
                b = np.asarray(moe["gate"]["e_score_bias"][li])
                yield self._escore_name(i), (b[None] if self.style == "ernie" else b)
            # the tree holds the held experts alone (all, or one chip's
            # share): entry j is the checkpoint's expert first_held + j
            for j in range(cfg.moe.num_held):
                names = self._expert_names(i, cfg.moe.first_held_expert + j)
                for proj in ("gate_proj", "up_proj", "down_proj"):
                    yield names[proj], _t(np.asarray(moe["experts"][proj]["kernel"][li, j]))
            if cfg.moe.n_shared_experts > 0:
                base = self._shared_base(i)
                for proj in ("gate_proj", "up_proj", "down_proj"):
                    yield f"{base}.{proj}.weight", _t(np.asarray(moe["shared"][proj]["kernel"][li]))

    def from_hf(self, read: Reader, shardings: Any = None) -> dict:
        cfg = self.cfg
        out: dict = {}

        def put(path, value):
            sh = _get(shardings, path) if shardings is not None else None
            _set(out, path, jax.device_put(value, sh) if sh is not None else value)

        dense = self._dense()

        def one(name, transpose, tr):
            x = _t(read(name)) if transpose else np.asarray(read(name))
            return dense._transform(x, tr, inverse=False)

        for name, path, transpose, tr in dense._top_entries():
            put(path, one(name, transpose, tr))
        fk = cfg.first_k_dense
        if fk:
            for suffix, path, transpose, tr in dense._layer_entries():
                names = [f"model.layers.{i}.{suffix}" for i in range(fk)]
                try:
                    if path[0] == "indexer":
                        stacked = _stack_layers_zero_fill(
                            one, names, transpose, tr, dense._indexer_absent
                        )
                    else:
                        stacked = np.stack([one(n, transpose, tr) for n in names])
                except KeyError:
                    if path[0] == "indexer":  # optional: see _mla_layer_entries
                        continue
                    raise
                put(("dense_layers",) + path, stacked)
        for suffix, path, transpose, tr in self._attn_entries():
            names = [
                f"model.layers.{fk + li}.{suffix}"
                for li in range(cfg.num_moe_layers)
            ]
            try:
                if path[0] == "indexer":
                    stacked = _stack_layers_zero_fill(
                        one, names, transpose, tr,
                        lambda li: dense._indexer_absent(fk + li),
                    )
                else:
                    stacked = np.stack([one(n, transpose, tr) for n in names])
            except KeyError:
                if path[0] == "indexer":  # optional: see _mla_layer_entries
                    continue
                raise
            put(("moe_layers",) + path, stacked)
        fused = dense._fused_qkv_name()
        if fused is not None:
            def _qkv_stacks(i0, n):
                splits = [
                    dense._split_fused_qkv(
                        np.asarray(read(f"model.layers.{i0 + j}.{fused}"))
                    )
                    for j in range(n)
                ]
                return {
                    p: np.stack([s_[p] for s_ in splits])
                    for p in ("q_proj", "k_proj", "v_proj")
                }

            if fk:
                for p_, v_ in _qkv_stacks(0, fk).items():
                    put(("dense_layers", p_, "kernel"), v_)
            for p_, v_ in _qkv_stacks(fk, cfg.num_moe_layers).items():
                put(("moe_layers", p_, "kernel"), v_)
        put(
            ("moe_layers", "moe", "gate", "weight"),
            np.stack([_t(read(self._gate_name(fk + li))) for li in range(cfg.num_moe_layers)]),
        )
        if cfg.moe.router_bias:
            put(
                ("moe_layers", "moe", "gate", "bias"),
                np.stack([
                    np.asarray(read(self._gate_name(fk + li).replace(".weight", ".bias")))
                    for li in range(cfg.num_moe_layers)
                ]),
            )
        if self.style == "gpt_oss":
            fused = np.stack([
                np.asarray(read(f"model.layers.{fk + li}.mlp.experts.gate_up_proj"))
                for li in range(cfg.num_moe_layers)
            ])  # (L, E, H, 2I)
            put(("moe_layers", "moe", "experts", "gate_proj", "kernel"), fused[..., ::2])
            put(("moe_layers", "moe", "experts", "up_proj", "kernel"), fused[..., 1::2])
            fb = np.stack([
                np.asarray(read(f"model.layers.{fk + li}.mlp.experts.gate_up_proj_bias"))
                for li in range(cfg.num_moe_layers)
            ])
            put(("moe_layers", "moe", "experts", "gate_proj", "bias"), fb[..., ::2])
            put(("moe_layers", "moe", "experts", "up_proj", "bias"), fb[..., 1::2])
            put(
                ("moe_layers", "moe", "experts", "down_proj", "kernel"),
                np.stack([
                    np.asarray(read(f"model.layers.{fk + li}.mlp.experts.down_proj"))
                    for li in range(cfg.num_moe_layers)
                ]),
            )
            put(
                ("moe_layers", "moe", "experts", "down_proj", "bias"),
                np.stack([
                    np.asarray(read(f"model.layers.{fk + li}.mlp.experts.down_proj_bias"))
                    for li in range(cfg.num_moe_layers)
                ]),
            )
            return out
        if cfg.moe.gate_bias_update_speed > 0:
            def read_bias(li):
                try:
                    return np.asarray(read(self._escore_name(fk + li))).reshape(-1)
                except KeyError:
                    return np.zeros((cfg.moe.n_routed_experts,), np.float32)

            put(
                ("moe_layers", "moe", "gate", "e_score_bias"),
                np.stack([read_bias(li) for li in range(cfg.num_moe_layers)]),
            )
        for proj in ("gate_proj", "up_proj", "down_proj"):
            stacked = np.stack(
                [
                    np.stack(
                        [
                            _t(read(self._expert_names(
                                fk + li, cfg.moe.first_held_expert + j)[proj]))
                            for j in range(cfg.moe.num_held)
                        ]
                    )
                    for li in range(cfg.num_moe_layers)
                ]
            )
            put(("moe_layers", "moe", "experts", proj, "kernel"), stacked)
        if cfg.moe.n_shared_experts > 0:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                stacked = np.stack(
                    [
                        _t(read(f"{self._shared_base(fk + li)}.{proj}.weight"))
                        for li in range(cfg.num_moe_layers)
                    ]
                )
                put(("moe_layers", "moe", "shared", proj, "kernel"), stacked)
        return out


@dataclasses.dataclass
class JambaAdapter:
    """JambaForCausalLM (dense sizes) <-> a decoder whose layers name their
    mixer (`TransformerConfig.layer_ops`): `layers` holds every layer's two
    norms and MLP, `attn_layers` / `mamba_layers` the operators by kind.
    Layouts: conv1d.weight (C, 1, K) <-> conv/kernel (K, C); A_log (C, N)
    <-> (N, C); dt_proj.bias <-> the leaf `dt_bias`; Linears transposed."""

    cfg: TransformerConfig

    #: (hf suffix, path under the stack, transpose)
    LAYER = [
        ("input_layernorm.weight", ("input_norm", "scale"), False),
        ("pre_ff_layernorm.weight", ("post_attn_norm", "scale"), False),
        ("feed_forward.gate_proj.weight", ("gate_proj", "kernel"), True),
        ("feed_forward.up_proj.weight", ("up_proj", "kernel"), True),
        ("feed_forward.down_proj.weight", ("down_proj", "kernel"), True),
    ]
    OPERATORS = {
        "attention": [
            ("self_attn.q_proj.weight", ("q_proj", "kernel"), True),
            ("self_attn.k_proj.weight", ("k_proj", "kernel"), True),
            ("self_attn.v_proj.weight", ("v_proj", "kernel"), True),
            ("self_attn.o_proj.weight", ("o_proj", "kernel"), True),
        ],
        "mamba": [
            ("mamba.in_proj.weight", ("in_proj", "kernel"), True),
            ("mamba.conv1d.weight", ("conv", "kernel"), "conv"),
            ("mamba.conv1d.bias", ("conv", "bias"), False),
            ("mamba.x_proj.weight", ("x_proj", "kernel"), True),
            ("mamba.dt_layernorm.weight", ("dt_norm", "scale"), False),
            ("mamba.b_layernorm.weight", ("b_norm", "scale"), False),
            ("mamba.c_layernorm.weight", ("c_norm", "scale"), False),
            ("mamba.dt_proj.weight", ("dt_proj", "kernel"), True),
            ("mamba.dt_proj.bias", ("dt_bias",), False),
            ("mamba.A_log", ("A_log",), True),
            ("mamba.D", ("D",), False),
            ("mamba.out_proj.weight", ("o_proj", "kernel"), True),
        ],
    }
    TOP = [
        ("model.embed_tokens.weight", ("embed", "embedding"), False),
        ("model.final_layernorm.weight", ("final_norm", "scale"), False),
    ]

    @staticmethod
    def _lay(x, transpose, inverse: bool):
        x = np.asarray(x)
        if transpose == "conv":  # (C, 1, K) <-> (K, C)
            return _t(x)[:, None, :] if inverse else _t(x[:, 0, :])
        return _t(x) if transpose else x

    def _top(self):
        head = [] if self.cfg.tie_word_embeddings else [
            ("lm_head.weight", ("lm_head", "kernel"), True)]
        return self.TOP + head

    def _by_layer(self):
        """(layer i, stack key, index in the stack, entries)."""
        from automodel_tpu.models.llm.decoder import OPERATOR_STACKS, layer_operators

        for i, (kind, j) in enumerate(layer_operators(self.cfg)):
            yield i, "layers", i, self.LAYER
            yield i, OPERATOR_STACKS[kind], j, self.OPERATORS[kind]

    def to_hf(self, params: Mapping) -> Iterator[tuple[str, np.ndarray]]:
        for name, path, transpose in self._top():
            yield name, self._lay(_get(params, path), transpose, True)
        for i, stack, j, entries in self._by_layer():
            for suffix, path, transpose in entries:
                x = np.asarray(_get(params[stack], path)[j])
                yield f"model.layers.{i}.{suffix}", self._lay(x, transpose, True)

    def from_hf(self, read: Reader, shardings: Any = None) -> dict:
        out: dict = {}
        rows: dict = {}  # (stack,) + path -> {index in the stack: array}

        def put(path, value):
            sh = _get(shardings, path) if shardings is not None else None
            _set(out, path, jax.device_put(value, sh) if sh is not None else value)

        for name, path, transpose in self._top():
            put(path, self._lay(read(name), transpose, False))
        for i, stack, j, entries in self._by_layer():
            for suffix, path, transpose in entries:
                rows.setdefault((stack,) + path, {})[j] = self._lay(
                    read(f"model.layers.{i}.{suffix}"), transpose, False)
        for path, by_index in rows.items():
            put(path, np.stack([by_index[j] for j in range(len(by_index))]))
        return out


@dataclasses.dataclass
class MiMoAdapter:
    """MiMoV2FlashForCausalLM <-> the expert decoder whose attention names
    its operator (`layer_ops`): `dense_layers` / `moe_layers` hold each
    layer's two norms and its MLP, `attn_layers` / `window_attn_layers` the
    attention by kind (a window layer's with its `attention_sink_bias`).
    The tree holds the held experts alone: entry j is the checkpoint's
    expert `first_held_expert + j`. Linears transposed."""

    cfg: Any  # MoETransformerConfig

    NORMS = [
        ("input_layernorm.weight", ("input_norm", "scale"), False),
        ("post_attention_layernorm.weight", ("post_attn_norm", "scale"), False),
    ]
    DENSE = [(f"mlp.{p}.weight", (p, "kernel"), True)
             for p in ("gate_proj", "up_proj", "down_proj")]
    ATTENTION = [(f"self_attn.{p}.weight", (p, "kernel"), True)
                 for p in ("q_proj", "k_proj", "v_proj", "o_proj")]
    TOP = [
        ("model.embed_tokens.weight", ("embed", "embedding"), False),
        ("model.norm.weight", ("final_norm", "scale"), False),
        ("lm_head.weight", ("lm_head", "kernel"), True),
    ]

    def _by_layer(self):
        """(layer i, stack key, index in the stack, entries), experts apart."""
        from automodel_tpu.models.llm.decoder import (
            OPERATOR_STACKS, kind_config, layer_operators,
        )

        fk = self.cfg.first_k_dense
        for i, (kind, j) in enumerate(layer_operators(self.cfg)):
            dense = i < fk
            layer = self.NORMS + (self.DENSE if dense else [
                ("mlp.gate.weight", ("moe", "gate", "weight"), True)])
            if not dense and self.cfg.moe.gate_bias_update_speed > 0:
                layer = layer + [("mlp.gate.e_score_correction_bias",
                                  ("moe", "gate", "e_score_bias"), False)]
            if not dense and self.cfg.moe.n_shared_experts:
                layer = layer + [
                    (f"mlp.shared_experts.{p}.weight", ("moe", "shared", p, "kernel"), True)
                    for p in ("gate_proj", "up_proj", "down_proj")]
            yield i, ("dense_layers", i) if dense else ("moe_layers", i - fk), layer
            attn = self.ATTENTION + ([
                ("self_attn.attention_sink_bias", ("sinks",), False)]
                if kind_config(self.cfg, kind).attention_sinks else [])
            yield i, (OPERATOR_STACKS[kind], j), attn

    def _experts(self):
        """(layer i, index in `moe_layers`, held expert j, hf name, proj)."""
        fk, moe = self.cfg.first_k_dense, self.cfg.moe
        for li in range(self.cfg.num_layers - fk):
            for j in range(moe.num_held):
                e = moe.first_held_expert + j
                for p in ("gate_proj", "up_proj", "down_proj"):
                    yield li, j, f"model.layers.{fk + li}.mlp.experts.{e}.{p}.weight", p

    def _top(self):
        return self.TOP[:2] if self.cfg.tie_word_embeddings else self.TOP

    def to_hf(self, params: Mapping) -> Iterator[tuple[str, np.ndarray]]:
        for name, path, transpose in self._top():
            x = np.asarray(_get(params, path))
            yield name, (_t(x) if transpose else x)
        for i, (stack, idx), entries in self._by_layer():
            for suffix, path, transpose in entries:
                x = np.asarray(_get(params[stack], path)[idx])
                yield f"model.layers.{i}.{suffix}", (_t(x) if transpose else x)
        experts = params["moe_layers"]["moe"]["experts"]
        for li, j, name, p in self._experts():
            yield name, _t(np.asarray(experts[p]["kernel"][li, j]))

    def from_hf(self, read: Reader, shardings: Any = None) -> dict:
        out: dict = {}
        rows: dict = {}  # (stack,) + path -> {index in the stack: array}

        def put(path, value):
            sh = _get(shardings, path) if shardings is not None else None
            _set(out, path, jax.device_put(value, sh) if sh is not None else value)

        def one(name, transpose):
            return _t(read(name)) if transpose else np.asarray(read(name))

        for name, path, transpose in self._top():
            put(path, one(name, transpose))
        for i, (stack, idx), entries in self._by_layer():
            for suffix, path, transpose in entries:
                rows.setdefault((stack,) + path, {})[idx] = one(
                    f"model.layers.{i}.{suffix}", transpose)
        for path, by_index in rows.items():
            put(path, np.stack([by_index[j] for j in range(len(by_index))]))
        experts: dict = {}
        for li, j, name, p in self._experts():
            experts.setdefault(p, {}).setdefault(li, []).append(one(name, True))
        for p, by_layer in experts.items():
            put(("moe_layers", "moe", "experts", p, "kernel"),
                np.stack([np.stack(by_layer[li]) for li in sorted(by_layer)]))
        return out


ADAPTERS = {
    "dense_decoder": DenseDecoderAdapter,
    "moe_decoder": MoEDecoderAdapter,
    "jamba": JambaAdapter,
    "mimo": MiMoAdapter,
}


def get_adapter(adapter_name: str, cfg, **kw):
    return ADAPTERS[adapter_name](cfg, **kw)


# ---------------------------------------------------------------------------
# safetensors shard I/O
# ---------------------------------------------------------------------------
def save_hf_checkpoint(
    named_tensors: Iterator[tuple[str, np.ndarray]],
    out_dir: str,
    hf_config: dict | None = None,
    max_shard_bytes: int = 4 << 30,
    retry_policy=None,
    on_retry=None,
) -> None:
    """Write sharded `model-XXXXX-of-YYYYY.safetensors` + index + config.json
    (the consolidated-HF-export analog, reference: checkpointing.py
    consolidate_safetensors_files_on_every_rank).

    Crash-consistent: everything is staged into a sibling `<out_dir>.staging-
    <pid>` directory and PUBLISHED with one atomic rename at the end — a
    crash mid-export can never leave a loadable-looking but truncated
    `out_dir` (a partial safetensors set without its index parses as a
    complete smaller model). `retry_policy` (resilience/retry.py) retries
    transient per-shard write failures; the `hf_export_write` /
    `hf_export_commit` fault points make both paths chaos-testable.
    """
    import shutil

    from safetensors.numpy import save_file

    from automodel_tpu.checkpoint.checkpointer import is_remote_path
    from automodel_tpu.resilience.faults import fault_hit
    from automodel_tpu.resilience.retry import retry_call

    if is_remote_path(out_dir):
        # os.makedirs would silently create a LOCAL './gs:/…' tree and the
        # safetensors would die with the job's ephemeral disk
        raise NotImplementedError(
            f"consolidated HF export writes local safetensors files; "
            f"{out_dir!r} is a remote URI (orbax step checkpoints DO support "
            "remote checkpoint_dir) — export to a local directory via "
            "save_consolidated_hf(out_dir=...) and sync it to the bucket"
        )
    import glob as _glob

    if jax.process_count() > 1 and jax.process_index() != 0:
        # single-writer publish: the staged-rename protocol (and the stale-
        # staging sweep below) assumes ONE exporter per out_dir; to_hf
        # consumers hand in host numpy tensors, so rank 0 alone writes the
        # consolidated artifact (the MetricLogger rank-0 convention)
        return
    out_dir = os.path.abspath(out_dir).rstrip(os.sep)
    old_dir = f"{out_dir}.old"
    # recovery from a previous interrupted publish: a crash between the two
    # swap renames leaves the old COMPLETE export under `.old` and no
    # out_dir — restore it before staging the new one (self-healing; a
    # reader in between sees a missing dir, never a truncated one)
    if os.path.isdir(old_dir):
        if not os.path.isdir(out_dir):
            os.rename(old_dir, out_dir)
        else:
            shutil.rmtree(old_dir, ignore_errors=True)
    for stale in _glob.glob(f"{out_dir}.staging-*"):
        shutil.rmtree(stale, ignore_errors=True)
    stage_dir = f"{out_dir}.staging-{os.getpid()}"
    os.makedirs(stage_dir)
    # Stream: flush each shard to a temp-named file as soon as it fills so
    # host memory peaks at ONE shard, then rename once the count is known.
    tmp_files: list[str] = []
    shard_keys: list[list[str]] = []
    shard: dict = {}
    size = 0
    total = 0

    def flush():
        nonlocal shard, size
        if not shard:
            return
        tmp = os.path.join(stage_dir, f"__tmp_shard_{len(tmp_files):05d}")

        def write():
            fault_hit("hf_export_write")
            save_file(shard, tmp)

        retry_call(
            write, policy=retry_policy, point="hf_export_write",
            on_attempt=on_retry,
        )
        tmp_files.append(tmp)
        shard_keys.append(list(shard))
        shard = {}
        size = 0

    try:
        for name, tensor in named_tensors:
            nbytes = tensor.nbytes
            if size + nbytes > max_shard_bytes and shard:
                flush()
            shard[name] = np.ascontiguousarray(tensor)
            size += nbytes
            total += nbytes
        flush()

        n = len(tmp_files)
        weight_map = {}
        for idx, (tmp, keys) in enumerate(zip(tmp_files, shard_keys), 1):
            fname = (
                "model.safetensors" if n == 1
                else f"model-{idx:05d}-of-{n:05d}.safetensors"
            )
            os.replace(tmp, os.path.join(stage_dir, fname))
            for k in keys:
                weight_map[k] = fname
        if n > 1:
            index = {"metadata": {"total_size": int(total)}, "weight_map": weight_map}
            with open(os.path.join(stage_dir, "model.safetensors.index.json"), "w") as f:
                json.dump(index, f, indent=2)
        if hf_config is not None:
            with open(os.path.join(stage_dir, "config.json"), "w") as f:
                json.dump(hf_config, f, indent=2)

        # -- atomic publish -----------------------------------------------
        fault_hit("hf_export_commit")
        if os.path.isdir(out_dir):
            # replacing a previous export: move it aside first so a crash
            # between the two renames leaves the old COMPLETE export under
            # `.old` (restored by the recovery path above on the next
            # export) — never a truncated mix at out_dir
            os.rename(out_dir, old_dir)
            fault_hit("hf_export_swap")
            os.rename(stage_dir, out_dir)
            # sidecar files next to the previous export (tokenizer.json,
            # generation_config.json, …) survive the replace; model shards
            # and the index always come from the NEW export only
            for name in os.listdir(old_dir):
                if name.endswith(".safetensors") or name == "model.safetensors.index.json":
                    continue
                dst = os.path.join(out_dir, name)
                if not os.path.exists(dst):
                    os.rename(os.path.join(old_dir, name), dst)
            shutil.rmtree(old_dir, ignore_errors=True)
        else:
            os.rename(stage_dir, out_dir)
    except Exception:
        # ordinary failures clean their staging tree; an injected/real CRASH
        # (BaseException) leaves it — which is fine: `.staging-*` is not a
        # loadable checkpoint directory (and the next export sweeps it), the
        # invariant holds either way
        shutil.rmtree(stage_dir, ignore_errors=True)
        raise


def _dequant_fp8_block(
    w: np.ndarray, scale_inv: np.ndarray, block: tuple = (128, 128)
) -> np.ndarray:
    """DeepSeek-V3 fp8 checkpoint dequant: weights are stored
    float8_e4m3fn with one fp32 inverse scale per (bm × bn) tile
    (reference: models/deepseek_v3/state_dict_adapter.py:96
    `_weight_dequant_kernel` — a Triton kernel there; plain numpy
    broadcast here, load-time only)."""
    M, N = w.shape
    bm, bn = block
    s = np.asarray(scale_inv, np.float32)
    expect = (-(-M // bm), -(-N // bn))
    if s.shape != expect:
        raise ValueError(
            f"fp8 scale_inv grid {s.shape} does not match weight {w.shape} "
            f"at block size {block} (expected {expect}); check "
            "quantization_config.weight_block_size in config.json"
        )
    # block-row-wise multiply: no weight-sized scale temporary (a DSv3
    # 7168×18432 weight would otherwise allocate a ~500MB scale matrix)
    out = np.empty((M, N), np.float32)
    for bi in range(expect[0]):
        r0, r1 = bi * bm, min((bi + 1) * bm, M)
        row_scale = np.repeat(s[bi], bn)[:N]  # (N,)
        out[r0:r1] = w[r0:r1].astype(np.float32) * row_scale
    return out


def _read_safetensors_header(path: str) -> tuple:
    """(header_len, parsed header dict) of one safetensors file."""
    import struct

    with open(path, "rb") as f:
        (hlen,) = struct.unpack("<Q", f.read(8))
        return hlen, json.loads(f.read(hlen))


def _read_fp8_slice(path: str, name: str, header: tuple | None = None) -> np.ndarray:
    """Read one (possibly fp8) tensor straight from a safetensors file.

    The numpy framework of `safetensors` cannot represent float8 dtypes;
    parse the header manually and reinterpret the raw bytes with
    ml_dtypes (shipped with jax)."""
    import ml_dtypes

    dtypes = {
        "F8_E4M3": ml_dtypes.float8_e4m3fn,
        "F8_E5M2": ml_dtypes.float8_e5m2,
        "BF16": ml_dtypes.bfloat16,
        "F16": np.float16,
        "F32": np.float32,
    }
    hlen, meta_map = header if header is not None else _read_safetensors_header(path)
    meta = meta_map[name]
    start, end = meta["data_offsets"]
    with open(path, "rb") as f:
        f.seek(8 + hlen + start)
        buf = f.read(end - start)
    return np.frombuffer(buf, dtype=dtypes[meta["dtype"]]).reshape(meta["shape"])


class HFCheckpointReader:
    """Lazy per-tensor reader over a local HF checkpoint directory.

    `retry_policy` (resilience/retry.py) retries transient tensor-read
    failures with backoff — checkpoint dirs on network mounts (GCS FUSE,
    NFS) fail transiently under load, and a 70B streamed load should not
    die on one flaky read. The `remote_io` fault point fires inside each
    attempt so the retry path is chaos-testable."""

    def __init__(self, ckpt_dir: str, retry_policy=None, on_retry=None):
        from safetensors import safe_open

        self._dir = ckpt_dir
        self.retry_policy = retry_policy
        self.on_retry = on_retry
        self._handles: dict[str, Any] = {}
        self._header_cache: dict[str, tuple] = {}
        self._fp8_block_cache: tuple | None = None
        index_path = os.path.join(ckpt_dir, "model.safetensors.index.json")
        if os.path.exists(index_path):
            with open(index_path) as f:
                self._weight_map = json.load(f)["weight_map"]
        else:
            single = os.path.join(ckpt_dir, "model.safetensors")
            h = safe_open(single, framework="numpy")
            self._weight_map = {k: "model.safetensors" for k in h.keys()}
            self._handles["model.safetensors"] = h

    def _handle(self, fname: str):
        from safetensors import safe_open

        if fname not in self._handles:
            self._handles[fname] = safe_open(os.path.join(self._dir, fname), framework="numpy")
        return self._handles[fname]

    def keys(self):
        return self._weight_map.keys()

    def __call__(self, name: str) -> np.ndarray:
        if name not in self._weight_map:
            raise KeyError(name)

        def attempt():
            from automodel_tpu.resilience.faults import fault_hit

            fault_hit("remote_io")
            t = self._read_raw(name)
            scale_name = f"{name}_scale_inv"
            if scale_name in self._weight_map:
                t = _dequant_fp8_block(
                    t, self._read_raw(scale_name), self._fp8_block()
                )
            return t

        from automodel_tpu.resilience.retry import retry_call

        # KeyError is a MISSING tensor, not a transient — never retried
        return retry_call(
            attempt, policy=self.retry_policy, point="remote_io",
            on_attempt=self.on_retry, retry_on=(OSError, RuntimeError),
        )

    def _fp8_block(self) -> tuple:
        """Block size of fp8-quantized checkpoints, from config.json's
        quantization_config.weight_block_size (DSv3 convention: [128, 128]).
        Cached — this is consulted once per quantized tensor."""
        if self._fp8_block_cache is None:
            cfg = self.hf_config() or {}
            bs = (cfg.get("quantization_config") or {}).get("weight_block_size")
            self._fp8_block_cache = (int(bs[0]), int(bs[1])) if bs else (128, 128)
        return self._fp8_block_cache

    def _read_raw(self, name: str) -> np.ndarray:
        h = self._handle(self._weight_map[name])
        try:
            return h.get_tensor(name)
        except (TypeError, ValueError, KeyError, AttributeError):
            # fp8 dtypes are outside the numpy framework's type table —
            # re-read the raw buffer and reinterpret via ml_dtypes
            fname = self._weight_map[name]
            if fname not in self._header_cache:
                self._header_cache[fname] = _read_safetensors_header(
                    os.path.join(self._dir, fname)
                )
            return _read_fp8_slice(
                os.path.join(self._dir, fname), name, self._header_cache[fname]
            )

    def hf_config(self) -> dict | None:
        p = os.path.join(self._dir, "config.json")
        if os.path.exists(p):
            with open(p) as f:
                return json.load(f)
        return None


# ---------------------------------------------------------------------------
# pytree path helpers
# ---------------------------------------------------------------------------
def _get(tree, path: tuple):
    for p in path:
        tree = tree[p]
    return tree


def _set(tree: dict, path: tuple, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


@dataclasses.dataclass
class LlavaAdapter:
    """llava-style VLM ↔ models/vlm/llava params.

    HF layout: `language_model.model.*` / `language_model.lm_head.weight`,
    `multi_modal_projector.linear_{1,2}.*`, and a CLIP-style
    `vision_tower.vision_model.encoder.layers.{i}.*` tower
    (reference: models/llava_onevision/state_dict_adapter.py).
    """

    cfg: Any  # LlavaConfig

    def _lm(self) -> DenseDecoderAdapter:
        return DenseDecoderAdapter(self.cfg.text)

    _VIT_LAYER = (
        ("layer_norm1.weight", ("ln1", "scale"), False),
        ("layer_norm1.bias", ("ln1", "bias"), False),
        ("self_attn.q_proj.weight", ("q_proj", "kernel"), True),
        ("self_attn.q_proj.bias", ("q_proj", "bias"), False),
        ("self_attn.k_proj.weight", ("k_proj", "kernel"), True),
        ("self_attn.k_proj.bias", ("k_proj", "bias"), False),
        ("self_attn.v_proj.weight", ("v_proj", "kernel"), True),
        ("self_attn.v_proj.bias", ("v_proj", "bias"), False),
        ("self_attn.out_proj.weight", ("o_proj", "kernel"), True),
        ("self_attn.out_proj.bias", ("o_proj", "bias"), False),
        ("layer_norm2.weight", ("ln2", "scale"), False),
        ("layer_norm2.bias", ("ln2", "bias"), False),
        ("mlp.fc1.weight", ("fc1", "kernel"), True),
        ("mlp.fc1.bias", ("fc1", "bias"), False),
        ("mlp.fc2.weight", ("fc2", "kernel"), True),
        ("mlp.fc2.bias", ("fc2", "bias"), False),
    )

    def _vit_top(self):
        e = [
            ("vision_model.embeddings.patch_embedding.weight", ("patch_embed", "kernel"), "patch"),
            ("vision_model.embeddings.patch_embedding.bias", ("patch_embed", "bias"), None),
            ("vision_model.embeddings.position_embedding.weight", ("pos_embed",), None),
            ("vision_model.post_layernorm.weight", ("final_ln", "scale"), None),
            ("vision_model.post_layernorm.bias", ("final_ln", "bias"), None),
        ]
        if self.cfg.vision.use_cls_token:
            e.append(("vision_model.embeddings.class_embedding", ("cls_embed",), None))
        if self.cfg.vision.use_pre_layernorm:
            e += [
                ("vision_model.pre_layrnorm.weight", ("pre_ln", "scale"), None),
                ("vision_model.pre_layrnorm.bias", ("pre_ln", "bias"), None),
            ]
        return e

    def _patch_kernel(self, x: np.ndarray, to_hf: bool) -> np.ndarray:
        """HF conv patch embed (H, C, P, P) ↔ our (P*P*C, H) matmul kernel.
        Our patchify flattens row-major as (P, P, C)."""
        cfg = self.cfg.vision
        P, C, H = cfg.patch_size, cfg.num_channels, cfg.hidden_size
        if to_hf:
            k = np.asarray(x).reshape(P, P, C, H).transpose(3, 2, 0, 1)
            return np.ascontiguousarray(k)
        k = np.asarray(x).transpose(2, 3, 1, 0)  # (P, P, C, H)
        return np.ascontiguousarray(k.reshape(P * P * C, H))

    @staticmethod
    def _encoder_layers_to_hf(
        layers: Mapping, prefix: str, n: int
    ) -> Iterator[tuple[str, np.ndarray]]:
        """Unstack the shared pre-LN encoder layer table (ViT + sound)."""
        for i in range(n):
            for suffix, path, transpose in LlavaAdapter._VIT_LAYER:
                x = np.asarray(_get(layers, path)[i])
                yield f"{prefix}.{i}.{suffix}", (_t(x) if transpose else x)

    @staticmethod
    def _encoder_layers_from_hf(read: Reader, prefix: str, n: int) -> dict:
        layers: dict = {}
        for suffix, path, transpose in LlavaAdapter._VIT_LAYER:
            stacked = np.stack(
                [
                    _t(read(f"{prefix}.{i}.{suffix}"))
                    if transpose
                    else np.asarray(read(f"{prefix}.{i}.{suffix}"))
                    for i in range(n)
                ]
            )
            _set(layers, path, stacked)
        return layers

    def _vit_to_hf(self, vt: Mapping, prefix: str) -> Iterator[tuple[str, np.ndarray]]:
        for name, path, kind in self._vit_top():
            x = np.asarray(_get(vt, path))
            if kind == "patch":
                x = self._patch_kernel(x, to_hf=True)
            yield f"{prefix}.{name}", x
        yield from self._encoder_layers_to_hf(
            vt["layers"], f"{prefix}.vision_model.encoder.layers",
            self.cfg.vision.num_layers,
        )

    def _vit_from_hf(self, read: Reader, prefix: str) -> dict:
        vt: dict = {}
        for name, path, kind in self._vit_top():
            x = np.asarray(read(f"{prefix}.{name}"))
            if kind == "patch":
                x = self._patch_kernel(x, to_hf=False)
            _set(vt, path, x)
        vt["layers"] = self._encoder_layers_from_hf(
            read, f"{prefix}.vision_model.encoder.layers", self.cfg.vision.num_layers
        )
        return vt

    def to_hf(self, params: Mapping) -> Iterator[tuple[str, np.ndarray]]:
        for name, tensor in self._lm().to_hf(params["language_model"]):
            yield f"language_model.{name}", tensor
        pj = params["projector"]
        yield "multi_modal_projector.linear_1.weight", _t(np.asarray(pj["fc1"]["kernel"]))
        yield "multi_modal_projector.linear_1.bias", np.asarray(pj["fc1"]["bias"])
        yield "multi_modal_projector.linear_2.weight", _t(np.asarray(pj["fc2"]["kernel"]))
        yield "multi_modal_projector.linear_2.bias", np.asarray(pj["fc2"]["bias"])
        yield from self._vit_to_hf(params["vision_tower"], "vision_tower")

    def from_hf(self, read: Reader, shardings: Any = None) -> dict:
        def sub_read(prefix):
            return lambda name: read(f"{prefix}.{name}")

        lm_shardings = shardings["language_model"] if shardings is not None else None
        out: dict = {
            "language_model": self._lm().from_hf(sub_read("language_model"), lm_shardings)
        }
        pj = {
            "fc1": {
                "kernel": _t(read("multi_modal_projector.linear_1.weight")),
                "bias": np.asarray(read("multi_modal_projector.linear_1.bias")),
            },
            "fc2": {
                "kernel": _t(read("multi_modal_projector.linear_2.weight")),
                "bias": np.asarray(read("multi_modal_projector.linear_2.bias")),
            },
        }
        out["projector"] = pj
        out["vision_tower"] = self._vit_from_hf(read, "vision_tower")
        if shardings is not None:
            for key in ("projector", "vision_tower"):
                out[key] = jax.tree.map(
                    lambda v, sh: jax.device_put(v, sh), out[key], shardings[key]
                )
        return out


ADAPTERS["llava"] = LlavaAdapter


@dataclasses.dataclass
class OmniAdapter:
    """Omni (text·image·audio) ↔ models/omni/model params.

    Naming follows the reference's nemotron_omni checkpoint structure
    (reference: models/nemotron_omni/state_dict_adapter.py —
    `vision_projection.*` / `sound_projection.{norm,linear1,linear2}` /
    `sound_encoder.*` / `language_model.*`); the vision tower reuses the
    llava CLIP naming, and the sound encoder's transformer layers use the
    same encoder-layer suffixes with our conv front-end stored in its
    native (K, in, out) layout."""

    cfg: Any  # OmniConfig

    _AUDIO_TOP = (
        ("conv1.kernel", ("conv1", "kernel")),
        ("conv1.bias", ("conv1", "bias")),
        ("conv2.kernel", ("conv2", "kernel")),
        ("conv2.bias", ("conv2", "bias")),
        ("final_ln.weight", ("final_ln", "scale")),
        ("final_ln.bias", ("final_ln", "bias")),
    )

    def _base(self) -> LlavaAdapter:
        return LlavaAdapter(self.cfg)

    def _proj_entries(self, key: str):
        return (
            (f"{key}.norm.weight", (key, "norm", "scale"), False),
            (f"{key}.linear1.weight", (key, "linear1", "kernel"), True),
            (f"{key}.linear2.weight", (key, "linear2", "kernel"), True),
        )

    def to_hf(self, params: Mapping) -> Iterator[tuple[str, np.ndarray]]:
        base = self._base()
        for name, tensor in base._lm().to_hf(params["language_model"]):
            yield f"language_model.{name}", tensor
        yield from base._vit_to_hf(params["vision_tower"], "vision_tower")
        for key in ("vision_projection", "sound_projection"):
            for name, path, transpose in self._proj_entries(key):
                x = np.asarray(_get(params, path))
                yield name, (_t(x) if transpose else x)
        at = params["audio_tower"]
        for suffix, path in self._AUDIO_TOP:
            yield f"sound_encoder.{suffix}", np.asarray(_get(at, path))
        yield from LlavaAdapter._encoder_layers_to_hf(
            at["layers"], "sound_encoder.encoder.layers", self.cfg.audio.num_layers
        )

    def from_hf(self, read: Reader, shardings: Any = None) -> dict:
        base = self._base()

        def sub_read(prefix):
            return lambda name: read(f"{prefix}.{name}")

        lm_shardings = shardings["language_model"] if shardings is not None else None
        out: dict = {
            "language_model": base._lm().from_hf(sub_read("language_model"), lm_shardings),
            "vision_tower": base._vit_from_hf(read, "vision_tower"),
        }
        for key in ("vision_projection", "sound_projection"):
            for name, path, transpose in self._proj_entries(key):
                x = _t(read(name)) if transpose else np.asarray(read(name))
                _set(out, path, x)
        at: dict = {}
        for suffix, path in self._AUDIO_TOP:
            _set(at, path, np.asarray(read(f"sound_encoder.{suffix}")))
        at["layers"] = LlavaAdapter._encoder_layers_from_hf(
            read, "sound_encoder.encoder.layers", self.cfg.audio.num_layers
        )
        out["audio_tower"] = at
        if shardings is not None:
            for key in ("vision_tower", "audio_tower", "vision_projection", "sound_projection"):
                out[key] = jax.tree.map(
                    lambda v, sh: jax.device_put(v, sh), out[key], shardings[key]
                )
        return out


ADAPTERS["omni"] = OmniAdapter
