"""Generic dense decoder LM — the shared engine behind the Llama/Qwen/Mistral/
Gemma model families.

The reference hand-writes one model.py per family
(reference: nemo_automodel/components/models/llama/model.py:71-265,
qwen2, qwen3, mistral3, gemma …); on TPU those families differ only by
config knobs (GQA ratio, qkv bias, qk-norm, sliding windows, soft caps,
tied embeddings), so one functional decoder with a `TransformerConfig`
covers them, and each family module is a thin HF-config adapter
(see models/llm/families.py + models/registry.py, the analog of
_transformers/registry.py:30 MODEL_ARCH_MAPPING).

Architecture is params-as-pytree + stacked-layer `lax.scan` (see
models/common/layers.py). All parallelism is logical-axis annotations
resolved by parallel/sharding.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.layers import (
    dense_init,
    embed_init,
    scan_layers,
    scan_layers_windowed,
)
from automodel_tpu.ops.attention import dot_product_attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import RopeScalingConfig, apply_rope, rope_frequencies

#: Attention here is position-causal everywhere (ring attention under cp,
#: position/segment masks otherwise), so a permuted sequence layout — the
#: CP load-balanced head/tail ordering — is numerically transparent. Order-
#: sensitive modules (SSM/linear-attention hybrids) must NOT set this.
CP_PERMUTATION_SAFE = True


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rope_scaling: RopeScalingConfig = dataclasses.field(default_factory=RopeScalingConfig)
    rms_norm_eps: float = 1e-5
    attention_bias: bool = False
    qk_norm: bool = False  # qwen3-style per-head-dim RMSNorm on q/k
    # hunyuan applies the per-head qk-norm AFTER rotary instead of before
    qk_norm_after_rope: bool = False
    # MiniMax-M2: RMSNorm over the FLATTENED q/k projections (num_heads*D)
    # before the head reshape, instead of per-head-dim
    # (reference: models/minimax_m2/layers.py:78 "HF MiniMax applies RMSNorm
    # over flattened q/k projection dims before head reshape")
    qk_norm_flat: bool = False
    # GLM/Nemotron partial rotary: rotate only this fraction of head_dim
    partial_rotary_factor: float = 1.0
    # GLM-4 dense rotates interleaved even/odd pairs instead of split halves
    rope_interleaved: bool = False
    # gemma3: sliding-window layers use this rope theta (no scaling) while
    # global layers use rope_theta + rope_scaling
    rope_local_theta: Optional[float] = None
    attn_scale: Optional[float] = None  # None → head_dim**-0.5 (gemma2 overrides)
    sliding_window: Optional[int] = None
    # per-layer "sliding"/"global" types; None → sliding_window on all layers
    layer_types: Optional[tuple] = None
    use_post_norms: bool = False  # gemma2-style norms on the attn/mlp branches
    # looped decoders (Ouro's `total_ut_steps`): the whole layer stack runs
    # `num_passes` times over every token with the SAME weights, the final
    # norm after each walk; pass t, layer l keeps a cache entry of its own
    # (index t * num_layers + l). 1 = an ordinary decoder.
    num_passes: int = 1
    # a Linear hidden -> 1 with bias on each pass's normed state (shared by
    # the passes): the per-pass exit probability of a looped decoder
    exit_gate: bool = False
    # layers that name their mixer (Jamba): per layer "attention" | "mamba".
    # None = attention in every layer, the operator's weights inside the
    # `layers` stack. With it, `layers` holds what every layer has (the two
    # norms and the MLP) and each kind's operators are a stack of their own
    # (OPERATOR_STACKS), entry j the j-th layer of that kind.
    layer_ops: Optional[tuple] = None
    # the Mamba-1 mixer's widths (models/llm/mamba.py): states per channel,
    # taps of the causal convolution, inner channels = expand * hidden, rank
    # of the low-rank dt projection (None -> ceil(hidden / 16))
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None
    # False: no rotary embedding on q and k (Jamba has no positional
    # encoding at all: its state-space layers carry the order)
    use_rope: bool = True
    # which layers rotate q and k, where `use_rope`: "all", or "sliding": the
    # layers with a window alone (EXAONE 4.0 / K-EXAONE: a full-attention
    # layer has no positional encoding, the window layers carry the order)
    rope_layers: str = "all"
    logits_soft_cap: Optional[float] = None
    attn_soft_cap: Optional[float] = None
    embed_scale: float = 1.0  # gemma multiplies embeddings by sqrt(hidden)
    tie_word_embeddings: bool = False
    activation: str = "silu"
    zero_centered_norm: bool = False  # gemma stores scale-1
    # False → bidirectional attention (retrieval/embedding encoders,
    # reference: models/llama_bidirectional)
    causal: bool = True
    # baichuan NormHead: L2-normalize lm_head rows on every forward
    normalized_lm_head: bool = False
    # gpt-oss: learnable per-head sink logits in the softmax denominator
    attention_sinks: bool = False
    o_proj_bias: bool = False  # gpt-oss biases ALL four attention projections
    # attention flavor: "gqa" (default) or "mla" (DeepSeek latent attention)
    attention_type: str = "gqa"
    mla_q_lora_rank: Optional[int] = None
    mla_kv_lora_rank: int = 512
    mla_qk_nope_head_dim: int = 128
    mla_qk_rope_head_dim: int = 64
    mla_v_head_dim: int = 128
    # Mistral-4 llama4-style position-dependent q-rope scaling
    # (reference: mistral4/model.py:52 _get_llama_4_attn_scale):
    # q_pe *= 1 + beta * log(1 + floor(pos / orig_max)); None = off
    mla_qpe_scaling_beta: Optional[float] = None
    mla_qpe_scaling_orig_max: int = 8192
    # DSA (DeepSeek sparse attention, V3.2/V4): lightning-indexer top-k
    # sparse MLA. None → dense MLA. (reference: deepseek_v4/layers.py)
    dsa_index_topk: Optional[int] = None
    dsa_index_n_heads: int = 4
    dsa_index_head_dim: int = 64
    dsa_indexer_loss_coeff: float = 0.01
    # "deepseek": lightning indexer on hidden states, full-head rope.
    # "glm": GLM-5.x variant — queries from the MLA q-lora residual,
    # LayerNorm'd keys, rope-first half-split slice, n_heads**-0.5 gate
    # scaling (reference: glm_moe_dsa/layers.py GlmMoeDsaIndexer).
    dsa_indexer_style: str = "deepseek"
    # GLM IndexShare: per-layer "full" (runs its own indexer) | "shared"
    # (reuses the previous full layer's top-k selection). None → all full.
    dsa_indexer_types: Optional[tuple] = None
    # "oracle": dense (S,S) mask formulation (exact, test reference).
    # "chunked": blockwise two-phase sparse path — per-query-block indexer
    # scores + top-k, then gather-based absorbed MLA over the selected kv
    # latents; peak memory O(S·block) instead of O(S²) (the 32k-context
    # path; reference: deepseek_v4/kernels/tilelang_sparse_mla_fwd.py).
    # "auto": chunked once S > dsa_query_block·4.
    dsa_impl: str = "auto"
    dsa_query_block: int = 256
    # execution knobs
    dtype: Any = jnp.bfloat16
    remat_policy: str = "full"
    scan_unroll: int = 1
    attn_impl: str = "auto"
    pipeline_microbatches: int = 2  # used when the mesh has pp > 1
    # "gpipe": forward pipeline_layers + autodiff (stashes all M microbatch
    # boundary activations). "1f1b": explicit fwd/bwd interleave with the
    # 1F1B memory bound (≤ pp stashed microbatches per stage). "interleaved":
    # virtual-stage 1F1B over pp·pipeline_virtual_stages stages mapped
    # cyclically onto the ring — ~V× smaller bubble (reference: distributed/
    # pipelining/functional.py:182 virtual stages, :777 schedule builder).
    # "zb": zero-bubble ZB-H1 — backward split into input-grad (B, critical
    # path) and weight-grad (W, fills drain bubbles) at 1F1B memory.
    pipeline_schedule: str = "gpipe"
    # blockdiag CP (distributed.cp_layout: blockdiag): documents are
    # rank-local (parallel/cp.py BlockDiagContextParallelSharder), so
    # attention runs LOCAL per cp shard instead of the ring — the reference
    # blockdiag_cp/ package's per-document exchange, collapsed to zero
    cp_blockdiag: bool = False
    pipeline_virtual_stages: int = 2  # used when pipeline_schedule=interleaved
    linear_precision: Optional[str] = None  # None | "fp8" | "int8"

    @property
    def resolved_head_dim(self) -> int:
        if self.attention_type == "mla":
            return self.mla_qk_nope_head_dim + self.mla_qk_rope_head_dim
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def rope_dim(self) -> int:
        if self.attention_type == "mla":
            return self.mla_qk_rope_head_dim
        d = round(self.resolved_head_dim * self.partial_rotary_factor)
        return d - (d % 2)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def resolved_dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def holds_state(self) -> bool:
        """Whether some layer carries a recurrent state from token to token
        (what a serving engine must keep per slot, beside the pages)."""
        return any(op != "attention" for op in self.layer_ops or ())

    def attn_params_per_layer(self) -> int:
        """Projection parameter count of one attention block."""
        H = self.hidden_size
        if self.attention_type == "mla":
            dn, dr, dv = (
                self.mla_qk_nope_head_dim,
                self.mla_qk_rope_head_dim,
                self.mla_v_head_dim,
            )
            n = self.num_heads
            q = (
                H * self.mla_q_lora_rank + self.mla_q_lora_rank * n * (dn + dr)
                if self.mla_q_lora_rank
                else H * n * (dn + dr)
            )
            kv = H * (self.mla_kv_lora_rank + dr) + self.mla_kv_lora_rank * n * (dn + dv)
            return q + kv + n * dv * H
        D = self.resolved_head_dim
        return H * (self.num_heads + 2 * self.num_kv_heads) * D + self.num_heads * D * H

    def flops_per_token(self, seq_len: int) -> float:
        """Training FLOPs/token (fwd+bwd ≈ 6*N + attention term) for MFU."""
        D = self.resolved_head_dim
        layer_params = (
            self.attn_params_per_layer()
            + 3 * self.hidden_size * self.intermediate_size
        )
        n_attn = self.num_layers
        mixers_instead = 0  # layers whose mixer is not attention
        if self.layer_ops is not None:
            from automodel_tpu.models.llm.mamba import mamba_params_per_layer

            n_attn = sum(op == "attention" for op in self.layer_ops)
            mixers_instead = (self.num_layers - n_attn) * (
                mamba_params_per_layer(self) - self.attn_params_per_layer()
            )
        n_params = (
            self.vocab_size * self.hidden_size * (1 if self.tie_word_embeddings else 2)
            + self.num_layers * layer_params + mixers_instead
        )
        attn_flops = 6 * n_attn * self.num_heads * D * seq_len  # 2*2*1.5 causal
        # a looped decoder runs its layers (not the embedding or the head)
        # num_passes times over every token
        layers_again = (self.num_passes - 1) * (
            6.0 * self.num_layers * layer_params + attn_flops
        )
        return 6.0 * n_params + attn_flops + layers_again


def layer_windows(cfg: "TransformerConfig", num_layers: int | None = None) -> tuple:
    """Per-layer static sliding windows (None = global attention)."""
    L = num_layers if num_layers is not None else cfg.num_layers
    if cfg.sliding_window is None:
        return (None,) * L
    if cfg.layer_types is None:
        return (cfg.sliding_window,) * L
    assert len(cfg.layer_types) == L, (len(cfg.layer_types), L)
    return tuple(
        cfg.sliding_window if t == "sliding" else None for t in cfg.layer_types
    )


#: the parameter tree's key for each kind of operator's stack (a model with
#: `layer_ops`); "attention" in a model without them lives in the layers
OPERATOR_STACKS = {"attention": "attn_layers", "mamba": "mamba_layers"}


def layer_operators(cfg: "TransformerConfig") -> tuple:
    """Per layer (kind, index into that kind's operator stack), or None for
    a decoder of one kind (attention's weights inside the layer stack)."""
    if cfg.layer_ops is None:
        return None
    assert len(cfg.layer_ops) == cfg.num_layers, (len(cfg.layer_ops), cfg.num_layers)
    seen: dict = {}
    out = []
    for op in cfg.layer_ops:
        if op not in OPERATOR_STACKS:
            raise NotImplementedError(f"no layer operator {op!r}")
        out.append((op, seen.get(op, 0)))
        seen[op] = seen.get(op, 0) + 1
    return tuple(out)


def mixed_window_xs(windows: tuple, freq_for) -> tuple:
    """Encode static per-layer windows as scan-able arrays: window ints with
    a huge sentinel for None (global attention — the window mask becomes a
    tautology), plus the per-layer rope freq table selected statically."""
    win_arr = jnp.asarray(
        [w if w is not None else (1 << 30) for w in windows], jnp.int32
    )
    freqs = [freq_for(w) for w in windows]
    some = next(f for f in freqs if f is not None)
    # a layer without rotary embedding rides the scan as the zero table: a
    # rotation by no angle, exactly the identity
    freq_arr = jnp.stack(
        [f if f is not None else jnp.zeros_like(some) for f in freqs]
    )
    return win_arr, freq_arr


def make_freq_for(cfg: "TransformerConfig", inv_freq):
    """Per-layer-window rope frequency selector.

    gemma3 (`rope_local_base_freq`, reference: transformers
    Gemma3TextConfig): sliding-window layers rotate with a LOCAL unscaled
    theta while global layers use rope_theta + rope_scaling. Window
    grouping is static (scan_layers_windowed groups layers by window), so
    this is a python-level selection with no traced branching.

    `cfg.rope_layers == "sliding"`: a layer without a window gets None, and
    `project_qkv` then leaves its q and k unrotated."""
    if cfg.rope_layers == "sliding":
        assert cfg.rope_local_theta is None, "rope_layers with a local theta"
        return lambda window: inv_freq if window is not None else None
    assert cfg.rope_layers == "all", cfg.rope_layers
    if cfg.rope_local_theta is None:
        return lambda window: inv_freq
    local = rope_frequencies(cfg.rope_dim, cfg.rope_local_theta, None)
    return lambda window: local if window is not None else inv_freq


ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


# ---------------------------------------------------------------------------
# init / specs
# ---------------------------------------------------------------------------
def _stack(init_fn, key, shape, L):
    keys = jax.random.split(key, L)
    return jnp.stack([init_fn(k, shape) for k in keys])


def init_attention_layers(cfg: TransformerConfig, rng: jax.Array, L: int) -> dict:
    """Attention + norms portion of a layer stack (shared with MoE models)."""
    if cfg.attention_type == "mla":
        from automodel_tpu.models.llm.mla import init_mla_layers

        return init_mla_layers(cfg, rng, L)
    D = cfg.resolved_head_dim
    H = cfg.hidden_size
    ks = jax.random.split(rng, 4)
    layers = {
        "input_norm": {"scale": jnp.ones((L, H))},
        "q_proj": {"kernel": _stack(dense_init, ks[0], (H, cfg.num_heads * D), L)},
        "k_proj": {"kernel": _stack(dense_init, ks[1], (H, cfg.num_kv_heads * D), L)},
        "v_proj": {"kernel": _stack(dense_init, ks[2], (H, cfg.num_kv_heads * D), L)},
        "o_proj": {"kernel": _stack(dense_init, ks[3], (cfg.num_heads * D, H), L)},
        "post_attn_norm": {"scale": jnp.ones((L, H))},
    }
    if cfg.attention_bias:
        layers["q_proj"]["bias"] = jnp.zeros((L, cfg.num_heads * D))
        layers["k_proj"]["bias"] = jnp.zeros((L, cfg.num_kv_heads * D))
        layers["v_proj"]["bias"] = jnp.zeros((L, cfg.num_kv_heads * D))
    if cfg.o_proj_bias:
        layers["o_proj"]["bias"] = jnp.zeros((L, H))
    if cfg.qk_norm:
        layers["q_norm"] = {"scale": jnp.ones((L, D))}
        layers["k_norm"] = {"scale": jnp.ones((L, D))}
    if cfg.qk_norm_flat:
        layers["q_norm"] = {"scale": jnp.ones((L, cfg.num_heads * D))}
        layers["k_norm"] = {"scale": jnp.ones((L, cfg.num_kv_heads * D))}
    if cfg.use_post_norms:
        layers["post_attn_out_norm"] = {"scale": jnp.ones((L, H))}
        layers["post_mlp_norm"] = {"scale": jnp.ones((L, H))}
    if cfg.attention_sinks:
        layers["sinks"] = jnp.zeros((L, cfg.num_heads))
    return layers


def attention_layer_specs(cfg: TransformerConfig) -> dict:
    if cfg.attention_type == "mla":
        from automodel_tpu.models.llm.mla import mla_layer_specs

        return mla_layer_specs(cfg)
    layers = {
        "input_norm": {"scale": ("layers", "norm")},
        "q_proj": {"kernel": ("layers", "embed", "heads")},
        "k_proj": {"kernel": ("layers", "embed", "kv_heads")},
        "v_proj": {"kernel": ("layers", "embed", "kv_heads")},
        "o_proj": {"kernel": ("layers", "heads", "embed")},
        "post_attn_norm": {"scale": ("layers", "norm")},
    }
    if cfg.attention_bias:
        layers["q_proj"]["bias"] = ("layers", "heads")
        layers["k_proj"]["bias"] = ("layers", "kv_heads")
        layers["v_proj"]["bias"] = ("layers", "kv_heads")
    if cfg.o_proj_bias:
        layers["o_proj"]["bias"] = ("layers", "norm")
    if cfg.qk_norm or cfg.qk_norm_flat:
        layers["q_norm"] = {"scale": ("layers", "norm")}
        layers["k_norm"] = {"scale": ("layers", "norm")}
    if cfg.use_post_norms:
        layers["post_attn_out_norm"] = {"scale": ("layers", "norm")}
        layers["post_mlp_norm"] = {"scale": ("layers", "norm")}
    if cfg.attention_sinks:
        layers["sinks"] = ("layers", "heads")
    return layers


#: the norms every layer has whatever its operator: before the operator and
#: before the MLP
_LAYER_NORMS = ("input_norm", "post_attn_norm")


def _check_layer_ops(cfg: TransformerConfig) -> None:
    if cfg.layer_ops is None:
        return
    if cfg.attention_type != "gqa" or cfg.num_passes != 1 or cfg.sliding_window:
        raise NotImplementedError(
            "layers that name their mixer (layer_ops) with MLA, more than "
            "one pass or sliding windows"
        )


def init(cfg: TransformerConfig, rng: jax.Array) -> dict:
    """Build fp32 master params with per-layer weights stacked on dim 0."""
    _check_layer_ops(cfg)
    H, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    ks = jax.random.split(rng, 8)

    ops = layer_operators(cfg)
    operators = {}
    if ops is None:
        layers = init_attention_layers(cfg, ks[0], L)
    else:
        # every layer's two norms here; each kind's operators a stack apart
        from automodel_tpu.models.llm.mamba import init_mamba_layers

        layers = {name: {"scale": jnp.ones((L, H))} for name in _LAYER_NORMS}
        n_attn = sum(op == "attention" for op, _ in ops)
        if n_attn:
            attn = init_attention_layers(cfg, ks[0], n_attn)
            operators["attn_layers"] = {
                k: v for k, v in attn.items() if k not in _LAYER_NORMS
            }
        if L - n_attn:
            operators["mamba_layers"] = init_mamba_layers(cfg, ks[1], L - n_attn)
    layers.update(
        {
            "gate_proj": {"kernel": _stack(dense_init, ks[4], (H, I), L)},
            "up_proj": {"kernel": _stack(dense_init, ks[5], (H, I), L)},
            "down_proj": {"kernel": _stack(dense_init, ks[6], (I, H), L)},
        }
    )
    params = {
        "embed": {"embedding": embed_init(ks[7], (cfg.vocab_size, H))},
        "layers": layers,
        **operators,
        "final_norm": {"scale": jnp.ones((H,))},
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": dense_init(jax.random.fold_in(rng, 99), (H, cfg.vocab_size))}
    if cfg.exit_gate:
        params["exit_gate"] = {
            "kernel": dense_init(jax.random.fold_in(rng, 98), (H, 1)),
            "bias": jnp.zeros((1,)),
        }
    return params


def param_specs(cfg: TransformerConfig) -> dict:
    """Logical axis names per param (consumed by parallel/sharding.py)."""
    ops = layer_operators(cfg)
    layers = attention_layer_specs(cfg)
    operators = {}
    if ops is not None:
        from automodel_tpu.models.llm.mamba import mamba_layer_specs

        kinds = {op for op, _ in ops}
        if "attention" in kinds:
            operators["attn_layers"] = {
                k: v for k, v in layers.items() if k not in _LAYER_NORMS
            }
        if "mamba" in kinds:
            operators["mamba_layers"] = mamba_layer_specs(cfg)
        layers = {k: layers[k] for k in _LAYER_NORMS}
    layers.update(
        {
            "gate_proj": {"kernel": ("layers", "embed", "mlp")},
            "up_proj": {"kernel": ("layers", "embed", "mlp")},
            "down_proj": {"kernel": ("layers", "mlp", "embed")},
        }
    )
    specs = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": layers,
        **operators,
        "final_norm": {"scale": ("norm",)},
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = {"kernel": ("embed", "vocab")}
    if cfg.exit_gate:
        specs["exit_gate"] = {"kernel": ("embed", None), "bias": (None,)}
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _pp_layer_setup(layers_params, cfg: TransformerConfig, mesh_ctx, freq_for):
    """Shared setup for both pipeline schedules: the per-stage layer fn plus
    the (possibly window-augmented) scanned layer pytree and its logical
    specs. Returns (layers_in, lspecs, pl_layer, uniform_windows).

    Inside the pipeline shard_map, tp is explicit: each tp rank holds a
    head/mlp slice, so the layer cfg carries the LOCAL counts and the layer
    fn psums partial o/down projections over tp (manual=True mode).
    """
    windows = layer_windows(cfg)
    if cfg.attention_type == "mla" and (
        mesh_ctx.sizes["tp"] > 1 or mesh_ctx.sizes["cp"] > 1
    ):
        raise NotImplementedError(
            "pp×tp / pp×cp with MLA attention: the manual-collective "
            "layer mode is implemented for standard GQA attention only"
        )
    tp = mesh_ctx.sizes["tp"]
    if tp > 1:
        if (cfg.num_heads % tp or cfg.num_kv_heads % tp
                or cfg.intermediate_size % tp):
            raise ValueError(
                f"pp×tp needs num_heads={cfg.num_heads}, "
                f"num_kv_heads={cfg.num_kv_heads}, "
                f"intermediate_size={cfg.intermediate_size} divisible by tp={tp}"
            )
        cfg_pl = dataclasses.replace(
            cfg,
            num_heads=cfg.num_heads // tp,
            num_kv_heads=cfg.num_kv_heads // tp,
            intermediate_size=cfg.intermediate_size // tp,
            head_dim=cfg.resolved_head_dim,  # pin before num_heads changes
        )
    else:
        cfg_pl = cfg

    layers_in = layers_params
    lspecs = param_specs(cfg)["layers"]
    if len(set(windows)) == 1:

        def pl_layer(hh, lp, pos, sg):
            return _decoder_layer(
                hh, lp, cfg_pl, pos, sg, freq_for(windows[0]),
                lambda x, axes: x, windows[0], mesh_ctx, manual=True,
            )

        return layers_in, lspecs, pl_layer, True

    # mixed per-layer windows inside the pipeline: the window value and its
    # rope freq table ride the scanned layer pytree (windows are static per
    # layer; only the stage scan makes them traced — the flash kernel folds
    # a traced window into its qwin aux array)
    win_arr, freq_arr = mixed_window_xs(windows, freq_for)
    layers_in = dict(layers_in, _window=win_arr, _freq=freq_arr)
    lspecs = dict(lspecs, _window=("layers",), _freq=("layers", None))

    def pl_layer(hh, lp, pos, sg):
        lp = dict(lp)
        w = lp.pop("_window")
        fr = lp.pop("_freq")
        return _decoder_layer(
            hh, lp, cfg_pl, pos, sg, fr, lambda x, axes: x, w,
            mesh_ctx, manual=True,
        )

    return layers_in, lspecs, pl_layer, False


def make_pp_1f1b_loss_and_grad(cfg: TransformerConfig, mesh_ctx, chunk_size: int = 1024):
    """Explicit 1F1B value-and-grad for the dense AND MoE decoders — the
    training-path analog of `forward` + autodiff under pp, with the 1F1B
    memory bound (at most pp stashed microbatch inputs per stage instead of
    all M boundary activations; reference schedule zoo: distributed/
    pipelining/functional.py:777 — here the schedule is precomputed action
    tables inside one lax.scan, parallel/pp.py:219).

    Returns grad_fn(params, batch, rng) -> (grads, ce_sum_plus_aux, aux)
    pluggable into training.make_train_step(grad_fn=...). The head (final
    norm + lm-head/tied-embed + fused linear CE) runs fused into the last
    stage's backward so logits are never materialized.

    MoE configs (cfg.moe set) run the dropless expert dispatch INSIDE each
    stage's step — the ep all-to-all overlaps with other stages' compute
    (moe_lm.decoder._pp_moe_layer_setup). Their load-balance aux is folded
    into the differentiated scalar pre-scaled by the global label-token
    count (the `combine_losses` contract), and the returned aux dict
    carries `tokens_per_expert` (Lm, E) for gate-bias updates / metrics.
    """
    from automodel_tpu.loss import fused_linear_cross_entropy
    from automodel_tpu.parallel.pp import (
        pipeline_train_1f1b,
        pipeline_train_interleaved,
    )

    tie = cfg.tie_word_embeddings
    is_moe = getattr(cfg, "moe", None) is not None
    layers_key = "moe_layers" if is_moe else "layers"
    if is_moe:
        if getattr(cfg, "first_k_dense", 0) > 0:
            raise NotImplementedError(
                f"pipeline_schedule={cfg.pipeline_schedule} with "
                "first_k_dense > 0 (heterogeneous layer stacks don't fit one "
                "scanned stage pytree); use the default gpipe schedule"
            )
        if getattr(cfg, "mtp_num_layers", 0) > 0:
            raise NotImplementedError(
                f"pipeline_schedule={cfg.pipeline_schedule} with the MTP "
                "head (it shifts outside the pipelined stack); use the "
                "default gpipe schedule"
            )

    def grad_fn(params, batch, rng):
        del rng  # no dropout in the decoder
        ids = batch["input_ids"]
        labels = batch["labels"]
        B, S = ids.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
            )
        seg = batch.get("segment_ids")
        if seg is None:
            seg = jnp.zeros_like(positions)
        n = jnp.sum((labels != -100).astype(jnp.float32))

        inv_freq = rope_frequencies(cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
        freq_for = make_freq_for(cfg, inv_freq)
        from automodel_tpu.models.common.layers import cast_params

        def cast_layer(fn):
            def wrapped(hh, lp, pos, sg):
                return fn(hh, cast_params(lp, cfg.dtype), pos, sg)

            return wrapped

        if is_moe:
            from automodel_tpu.models.moe_lm.decoder import _pp_moe_layer_setup

            layers_in, lspecs, pl_layer, extras_specs = _pp_moe_layer_setup(
                params[layers_key], cfg, mesh_ctx, freq_for
            )
            # aux contract: each (stage, microbatch) chunk contributes
            # aux·scale to the differentiated sum; scale = n / n_chunks makes
            # the total n·mean(chunk aux) — combine_losses' n·aux with aux
            # the per-microbatch chunk-mean estimator (see pipeline_layers)
            n_chunks = cfg.pipeline_microbatches * math.prod(
                mesh_ctx.sizes[a]
                for a in ("dp_replicate", "dp_shard", "ep", "cp")
            )
            aux_kw = {"aux_scale": n / n_chunks, "extras_specs": extras_specs}
        else:
            layers_in, lspecs, pl_layer, uniform = _pp_layer_setup(
                params[layers_key], cfg, mesh_ctx, freq_for
            )
            if not uniform:
                raise NotImplementedError(
                    f"pipeline_schedule={cfg.pipeline_schedule} with mixed "
                    "per-layer sliding windows (the window aux arrays are "
                    "non-differentiable scan inputs); use gpipe for this model"
                )
            aux_kw = {}
        pl_layer = cast_layer(pl_layer)

        def embed_fwd(embed_p):
            tbl = embed_p["embedding"].astype(cfg.dtype)
            h = jnp.take(tbl, ids, axis=0)
            if cfg.embed_scale != 1.0:
                h = h * jnp.asarray(cfg.embed_scale, cfg.dtype)
            return h

        h, embed_vjp = jax.vjp(embed_fwd, params["embed"])

        head = {"final_norm": params["final_norm"]}
        if tie:
            head["embed"] = params["embed"]
        else:
            head["lm_head"] = params["lm_head"]

        def head_loss(h_mb, head_p, labels_mb):
            hh = rms_norm(
                h_mb, head_p["final_norm"]["scale"], cfg.rms_norm_eps,
                cfg.zero_centered_norm,
            )
            kernel = head_kernel(head_p, cfg)
            ce, _ = fused_linear_cross_entropy(
                hh, kernel.astype(hh.dtype), labels_mb, chunk_size=chunk_size,
                logits_soft_cap=cfg.logits_soft_cap,
            )
            return ce

        if cfg.pipeline_schedule == "interleaved":
            out = pipeline_train_interleaved(
                h, positions, seg, labels, layers_in, pl_layer, head,
                head_loss, mesh_ctx, cfg.pipeline_microbatches,
                cfg.pipeline_virtual_stages, param_logical_specs=lspecs,
                **aux_kw,
            )
        elif cfg.pipeline_schedule == "zb":
            from automodel_tpu.parallel.pp import pipeline_train_zb

            out = pipeline_train_zb(
                h, positions, seg, labels, layers_in, pl_layer, head,
                head_loss, mesh_ctx, cfg.pipeline_microbatches,
                param_logical_specs=lspecs, **aux_kw,
            )
        else:
            out = pipeline_train_1f1b(
                h, positions, seg, labels, layers_in, pl_layer, head,
                head_loss, mesh_ctx, cfg.pipeline_microbatches,
                param_logical_specs=lspecs, **aux_kw,
            )
        if is_moe:
            loss, dh, gl, gh, extras = out
        else:
            loss, dh, gl, gh = out
        (d_embed,) = embed_vjp(dh.astype(h.dtype))
        grads = {layers_key: gl, "final_norm": gh["final_norm"]}
        if tie:
            grads["embed"] = jax.tree.map(jnp.add, d_embed, gh["embed"])
        else:
            grads["embed"] = d_embed
            grads["lm_head"] = gh["lm_head"]
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        aux = {"num_label_tokens": n}
        if is_moe:
            aux["tokens_per_expert"] = extras["tokens_per_expert"]
        return grads, loss, aux

    return grad_fn


def _dense(x, p, precision=None):
    from automodel_tpu.ops.quant import matmul

    y = matmul(x, p["kernel"], precision)
    if "bias" in p:
        y = y + p["bias"]
    return y


def forward(
    params: dict,
    cfg: TransformerConfig,
    input_ids: jnp.ndarray,  # (B, S) int32
    *,
    positions: jnp.ndarray | None = None,
    segment_ids: jnp.ndarray | None = None,
    mesh_ctx=None,
    rules=None,
    return_hidden: bool = False,
    inputs_embeds: jnp.ndarray | None = None,  # (B,S,H) — VLM merged embeds
    return_aux_hidden: tuple | None = None,    # layer indices → EAGLE-3 aux
    return_gate_logits: bool = False,          # looped decoders: (P,B,S) too
) -> jnp.ndarray:
    """Run the decoder. Returns logits (B,S,V) fp32, or hidden (B,S,H) when
    `return_hidden` (pair with loss/linear_ce.py to avoid materializing
    logits — the FusedLinearCrossEntropy analog).

    A looped decoder (`cfg.num_passes` > 1) walks the same layer stack that
    many times, the final norm after each walk; the normed state is the next
    pass's input and the last pass's is what the head reads. With
    `return_gate_logits` (needs `cfg.exit_gate`) the result becomes
    (out, gate_logits): the exit gate's logit on each pass's normed state,
    (num_passes, B, S) fp32. Which pass a token leaves at is not decided
    here (adaptive exit is not built: every token runs every pass).

    `return_aux_hidden=(lo, mid, hi)` additionally returns the outputs of
    those layers (pre-final-norm) stacked (k, B, S, H) — the target-side
    hidden capture for EAGLE-3 speculative training (reference:
    components/speculative/eagle/target.py hidden-state hooks; here it is a
    scan-ys selection, no hooks needed). Result becomes (out, aux)."""
    from automodel_tpu.models.common.layers import cast_params

    params = cast_params(params, cfg.dtype)  # fp32 master → compute dtype
    cfg_dtype = cfg.dtype
    B, S = input_ids.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].astype(jnp.int32) * jnp.ones((B, 1), jnp.int32)

    constrain = _make_constrain(mesh_ctx, rules)

    if inputs_embeds is not None:
        h = inputs_embeds.astype(cfg_dtype)
    else:
        # FSDP-unshard the table's embed dim before the gather: a gather out
        # of a (vocab×tp, embed×dp_shard) 2-D-sharded table otherwise yields
        # an H-on-dp_shard output the partitioner can only move to the
        # batch-sharded activation layout via involuntary full remat
        tbl = constrain(params["embed"]["embedding"], ("vocab", None))
        h = jnp.take(tbl, input_ids, axis=0).astype(cfg_dtype)
    if cfg.embed_scale != 1.0:
        h = h * jnp.asarray(cfg.embed_scale, cfg_dtype)
    h = constrain(h, ("act_batch", "act_seq", "act_embed"))

    inv_freq = rope_frequencies(cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
    freq_for = make_freq_for(cfg, inv_freq)

    if return_gate_logits and not cfg.exit_gate:
        raise ValueError("return_gate_logits needs a model with an exit gate")
    gate_logits = []

    def end_pass(h):
        """A pass's final norm (and, when asked, the exit gate's logit on
        the normed state, (B, S) fp32)."""
        h = rms_norm(h, params["final_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
        if return_gate_logits:
            g = params["exit_gate"]
            gate_logits.append(
                jnp.einsum("bsh,ho->bso", h, g["kernel"].astype(h.dtype),
                           preferred_element_type=jnp.float32)[..., 0]
                + g["bias"].astype(jnp.float32)
            )
        return h

    if cfg.num_passes > 1 and (
        return_aux_hidden is not None
        or (mesh_ctx is not None and mesh_ctx.sizes["pp"] > 1)
    ):
        raise NotImplementedError(
            "a looped decoder (num_passes > 1) under the pp pipeline or with "
            "aux-hidden capture"
        )

    if cfg.layer_ops is not None:
        h = _walk_named_layers(
            params, cfg, h, positions, segment_ids, inv_freq, constrain,
            mesh_ctx, return_aux_hidden,
        )
    elif mesh_ctx is not None and mesh_ctx.sizes["pp"] > 1:
        from automodel_tpu.parallel.pp import pipeline_layers

        if return_aux_hidden is not None:
            raise NotImplementedError("aux-hidden capture inside the pp pipeline")
        seg = segment_ids if segment_ids is not None else jnp.zeros_like(positions)
        layers_in, lspecs, pl_layer, _ = _pp_layer_setup(
            params["layers"], cfg, mesh_ctx, freq_for
        )

        h = pipeline_layers(
            h, positions, seg, layers_in, pl_layer, mesh_ctx,
            cfg.pipeline_microbatches, remat_policy=cfg.remat_policy,
            param_logical_specs=lspecs,
        )
        # pin the exit layout: without this the partitioner may propagate the
        # (pp-replicated) head's weight shardings backward into the pipeline
        # boundary and fall into involuntary full remat on the transition
        h = constrain(h, ("act_batch", "act_seq", "act_embed"))
    else:

        def layer(h, lp, window):
            return _decoder_layer(
                h, lp, cfg, positions, segment_ids, freq_for(window), constrain,
                window, mesh_ctx,
            )

        if return_aux_hidden is not None:
            windows = layer_windows(cfg)
            from automodel_tpu.models.common.layers import maybe_remat

            aux_ids = tuple(return_aux_hidden)
            mixed = len(set(windows)) != 1
            if mixed:
                # per-layer windows ride the scan as traced values (the flash
                # kernel folds them into its qwin aux array); rope freqs are
                # selected statically per layer and stacked
                win_xs, freq_xs = mixed_window_xs(windows, freq_for)

            # carry an (A, B, S, H) buffer updated only at the selected
            # layers — never materializes all L per-layer outputs
            def body(carry, xs):
                c, aux = carry
                if mixed:
                    lp, i, w, fr = xs
                    y = _decoder_layer(
                        c, lp, cfg, positions, segment_ids, fr, constrain, w,
                        mesh_ctx,
                    )
                else:
                    lp, i = xs
                    y = layer(c, lp, windows[0])
                for j, lid in enumerate(aux_ids):
                    aux = aux.at[j].set(jnp.where(i == lid, y, aux[j]))
                return (y, aux), None

            xs = (
                (params["layers"], jnp.arange(cfg.num_layers), win_xs, freq_xs)
                if mixed
                else (params["layers"], jnp.arange(cfg.num_layers))
            )
            aux0 = jnp.zeros((len(aux_ids),) + h.shape, h.dtype)
            (h, aux), _ = jax.lax.scan(
                maybe_remat(body, cfg.remat_policy),
                (h, aux0),
                xs,
                unroll=cfg.scan_unroll,
            )
        else:
            for t in range(cfg.num_passes):
                if t:  # the pass before leaves its normed state as the input
                    h = end_pass(h)
                h = scan_layers_windowed(
                    layer, h, params["layers"], layer_windows(cfg),
                    remat_policy=cfg.remat_policy, unroll=cfg.scan_unroll,
                )

    h = end_pass(h)
    out = h if return_hidden else unembed(params, cfg, h)
    if return_aux_hidden is not None:
        return out, aux
    if return_gate_logits:
        return out, jnp.stack(gate_logits)
    return out


def _walk_named_layers(params, cfg, h, positions, segment_ids, inv_freq,
                       constrain, mesh_ctx, return_aux_hidden):
    """The layers of a decoder whose layers name their mixer, in a Python
    loop: layer i's norms and MLP from `layers`, its operator from its
    kind's stack. Forward-correct; nothing here is tuned or measured."""
    from automodel_tpu.models.common.layers import maybe_remat
    from automodel_tpu.models.llm.mamba import mamba_block

    _check_layer_ops(cfg)
    if return_aux_hidden is not None or (mesh_ctx is not None and (
        mesh_ctx.sizes["pp"] > 1 or mesh_ctx.sizes["cp"] > 1
    )):
        raise NotImplementedError(
            "layers that name their mixer (layer_ops) under the pp pipeline, "
            "under context parallelism (a scan over positions is order-"
            "sensitive: no ring, no permuted layout) or with aux-hidden capture"
        )

    def layer(h, lp, kind):
        if kind == "attention":
            h = attention_block(h, lp, cfg, positions, segment_ids, inv_freq,
                                constrain, None, mesh_ctx)
        else:
            h = mamba_block(h, lp, cfg, positions, constrain)
        return mlp_block(h, lp, cfg, constrain, mesh_ctx)

    for i, (kind, j) in enumerate(layer_operators(cfg)):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        lp.update(jax.tree.map(lambda a: a[j], params[OPERATOR_STACKS[kind]]))
        h = maybe_remat(
            lambda h, lp, kind=kind: layer(h, lp, kind), cfg.remat_policy
        )(h, lp)
    return h


def head_kernel(params: dict, cfg: TransformerConfig) -> jnp.ndarray:
    """(H, V) output-projection kernel: tied/untied, with baichuan NormHead
    L2-normalization per vocab row when cfg.normalized_lm_head."""
    if cfg.tie_word_embeddings:
        kernel = params["embed"]["embedding"].T
    else:
        kernel = params["lm_head"]["kernel"]
    if getattr(cfg, "normalized_lm_head", False):
        # baichuan NormHead (reference: models/baichuan/model.py NormHead):
        # F.normalize over the hidden dim, applied on every training forward
        norm = jnp.sqrt(jnp.sum(kernel.astype(jnp.float32) ** 2, axis=0, keepdims=True))
        kernel = (kernel.astype(jnp.float32) / jnp.maximum(norm, 1e-12)).astype(kernel.dtype)
    return kernel


def unembed(params: dict, cfg: TransformerConfig, h: jnp.ndarray) -> jnp.ndarray:
    """hidden → fp32 logits (with optional tied embeddings / soft cap)."""
    kernel = head_kernel(params, cfg)
    logits = jnp.einsum("bsh,hv->bsv", h, kernel.astype(h.dtype), preferred_element_type=jnp.float32)
    if cfg.logits_soft_cap is not None:
        logits = cfg.logits_soft_cap * jnp.tanh(logits / cfg.logits_soft_cap)
    return logits


def project_qkv(x, lp, cfg: TransformerConfig, positions, inv_freq):
    """q/k/v projections incl. bias, qk-norm, rope, linear precision —
    shared by training attention and the KV-cache generate path."""
    B, S, _ = x.shape
    D = cfg.resolved_head_dim
    q = _dense(x, lp["q_proj"], cfg.linear_precision)
    k = _dense(x, lp["k_proj"], cfg.linear_precision)
    v = _dense(x, lp["v_proj"], cfg.linear_precision)
    if cfg.qk_norm_flat:
        q = rms_norm(q, lp["q_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
        k = rms_norm(k, lp["k_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    q = q.reshape(B, S, cfg.num_heads, D)
    k = k.reshape(B, S, cfg.num_kv_heads, D)
    v = v.reshape(B, S, cfg.num_kv_heads, D)
    if cfg.qk_norm and not cfg.qk_norm_after_rope:
        q = rms_norm(q, lp["q_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
        k = rms_norm(k, lp["k_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    # `inv_freq` None: this layer's kind has no rotary embedding (make_freq_for)
    if cfg.use_rope and inv_freq is not None:
        q = apply_rope(q, positions, inv_freq, cfg.rope_interleaved)
        k = apply_rope(k, positions, inv_freq, cfg.rope_interleaved)
    if cfg.qk_norm and cfg.qk_norm_after_rope:
        q = rms_norm(q, lp["q_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
        k = rms_norm(k, lp["k_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    return q, k, v


def mlp_inner(x, lp, cfg: TransformerConfig):
    """Gated MLP core (no norm/residual) — shared with generate."""
    from automodel_tpu.ops.quant import matmul as _mm

    act = ACTIVATIONS[cfg.activation]
    gate = act(_mm(x, lp["gate_proj"]["kernel"], cfg.linear_precision))
    up = _mm(x, lp["up_proj"]["kernel"], cfg.linear_precision)
    return gate * up


def attention_block(h, lp, cfg: TransformerConfig, positions, segment_ids, inv_freq, constrain, sliding_window, mesh_ctx=None, manual=False):
    """Pre-norm attention with residual; shared by dense and MoE decoders.

    When the mesh has cp > 1 the sequence dim is sharded and attention runs
    as ring attention over the cp axis (parallel/cp.py); otherwise the
    backend dispatcher in ops/attention.py picks flash (TPU) or XLA, and
    runs flash per shard of the mesh.

    `manual=True` = running INSIDE a full-mesh shard_map (the pp pipeline):
    GSPMD constraints are inert there, so tensor parallelism is explicit —
    lp holds the per-tp-rank head/mlp slice (cfg carries the LOCAL counts)
    and the o_proj partial sum is psum'd over `tp`; cp attention calls the
    in-shard ring directly.
    """
    if cfg.attention_type == "mla":
        from automodel_tpu.models.llm.mla import mla_attention_block

        return mla_attention_block(
            h, lp, cfg, positions, segment_ids, inv_freq, constrain, sliding_window,
            None if manual else mesh_ctx,
        )
    D = cfg.resolved_head_dim
    B, S, _ = h.shape

    # -- attention ----------------------------------------------------------
    x = rms_norm(h, lp["input_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    q, k, v = project_qkv(x, lp, cfg, positions, inv_freq)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None))
    k = constrain(k, ("act_batch", "act_seq", "act_kv_heads", None))
    v = constrain(v, ("act_batch", "act_seq", "act_kv_heads", None))

    sinks = lp.get("sinks") if cfg.attention_sinks else None
    if mesh_ctx is not None and mesh_ctx.sizes["cp"] > 1:
        if cfg.cp_blockdiag and not manual:
            # per-document layout: all keys a query needs are rank-local
            from automodel_tpu.parallel.cp import local_cp_attention

            attn = local_cp_attention(
                q, k, v, positions, segment_ids, mesh_ctx,
                causal=cfg.causal,
                sliding_window=sliding_window,
                logits_soft_cap=cfg.attn_soft_cap,
                scale=cfg.attn_scale,
                sinks=sinks,
                attn_impl=cfg.attn_impl,
            )
        elif manual:
            from automodel_tpu.parallel.cp import ring_attention

            attn = ring_attention(
                q, k, v, positions, segment_ids, axis_name="cp",
                causal=cfg.causal,
                sliding_window=sliding_window,
                logits_soft_cap=cfg.attn_soft_cap,
                scale=cfg.attn_scale,
                sinks=sinks,
                attn_impl=cfg.attn_impl,
            )
        else:
            from automodel_tpu.parallel.cp import ring_dot_product_attention

            attn = ring_dot_product_attention(
                q, k, v, positions, segment_ids, mesh_ctx,
                causal=cfg.causal,
                sliding_window=sliding_window,
                logits_soft_cap=cfg.attn_soft_cap,
                scale=cfg.attn_scale,
                sinks=sinks,
                attn_impl=cfg.attn_impl,
            )
    else:
        attn = dot_product_attention(
            q, k, v,
            causal=cfg.causal,
            segment_ids=segment_ids,
            positions=positions,
            sliding_window=sliding_window,
            logits_soft_cap=cfg.attn_soft_cap,
            scale=cfg.attn_scale,
            sinks=sinks,
            impl=cfg.attn_impl,
            mesh_ctx=None if manual else mesh_ctx,
        )
    attn = attn.reshape(B, S, cfg.num_heads * D)
    from automodel_tpu.ops.quant import matmul as _mm

    attn_out = _mm(attn, lp["o_proj"]["kernel"], cfg.linear_precision)
    if manual and mesh_ctx is not None and mesh_ctx.sizes["tp"] > 1:
        attn_out = jax.lax.psum(attn_out, "tp")  # partial head-slice sums
    if "bias" in lp["o_proj"]:
        attn_out = attn_out + lp["o_proj"]["bias"]
    if cfg.use_post_norms:
        attn_out = rms_norm(
            attn_out, lp["post_attn_out_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm
        )
    h = h + attn_out
    return constrain(h, ("act_batch", "act_seq", "act_embed"))


def mlp_block(h, lp, cfg: TransformerConfig, constrain, mesh_ctx=None, manual=False):
    """Pre-norm gated MLP with residual. `manual` as in attention_block:
    explicit tp — lp holds the I/tp slice; the down_proj partial is psum'd."""
    from automodel_tpu.ops.quant import matmul as _mm

    x = rms_norm(h, lp["post_attn_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm)
    mlp = constrain(mlp_inner(x, lp, cfg), ("act_batch", "act_seq", "act_mlp"))
    mlp_out = _mm(mlp, lp["down_proj"]["kernel"], cfg.linear_precision)
    if manual and mesh_ctx is not None and mesh_ctx.sizes["tp"] > 1:
        mlp_out = jax.lax.psum(mlp_out, "tp")
    if cfg.use_post_norms:
        mlp_out = rms_norm(
            mlp_out, lp["post_mlp_norm"]["scale"], cfg.rms_norm_eps, cfg.zero_centered_norm
        )
    h = h + mlp_out
    return constrain(h, ("act_batch", "act_seq", "act_embed"))


def _decoder_layer(h, lp, cfg: TransformerConfig, positions, segment_ids, inv_freq, constrain, sliding_window, mesh_ctx=None, manual=False):
    h = attention_block(h, lp, cfg, positions, segment_ids, inv_freq, constrain, sliding_window, mesh_ctx, manual)
    return mlp_block(h, lp, cfg, constrain, mesh_ctx, manual)


def _make_constrain(mesh_ctx, rules):
    if mesh_ctx is None:
        return lambda x, axes: x
    from automodel_tpu.parallel.sharding import AxisRules, with_logical_constraint

    rules = rules or AxisRules()

    def constrain(x, axes):
        return with_logical_constraint(x, axes, mesh_ctx, rules)

    return constrain
