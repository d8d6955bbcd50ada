"""A `ServingEngine` made from SHAPES alone, for compiling a configuration's
whole serve step at its real size for a described chip without allocating a
weight: `object.__new__(ServingEngine)` with the attributes `_step_impl`
reads, the parameters as the per-layer trees `split_layer_stacks` would give,
the pool and the per-slot state as `init_pool` / `init_state` would."""

import jax
import jax.numpy as jnp

from automodel_tpu.inference.generate import _dense_mlp
from automodel_tpu.models.llm.decoder import layer_operators, layer_windows
from automodel_tpu.models.registry import get_model_spec
from automodel_tpu.ops.paged_attention import row_tile
from automodel_tpu.ops.rope import rope_frequencies
from automodel_tpu.serving import ServingConfig, ServingEngine
from automodel_tpu.serving.engine import LAYER_STACKS
from automodel_tpu.serving.kv_pages import init_pool, init_state

NOT_HF_KEYS = ("source", "reduced", "assumed", "published", "stands_for",
               "reference", "serve_dtype", "serving", "attn_impl",
               "architectures", "step_kernels")


def engine_of_shapes(config: dict, serving: dict, sharding):
    """(engine, the step's arguments as ShapeDtypeStructs on `sharding`) for
    a dense decoder's configuration file `config` (benchmark/configs)."""
    hf = {k: v for k, v in config.items() if k not in NOT_HF_KEYS}
    hf["architectures"] = config["architectures"]
    dtype = jnp.dtype(config["serve_dtype"])
    spec = get_model_spec(hf)
    cfg = spec.config_from_hf(hf, dtype=dtype, remat_policy="none",
                              attn_impl=config.get("attn_impl", "auto"))
    sc = ServingConfig(**serving)
    shapes = jax.eval_shape(lambda: spec.module.init(cfg, jax.random.key(0)))

    def on_chip(s, drop_layer_axis=False):
        shape = s.shape[1:] if drop_layer_axis else s.shape
        dt = dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params = {}
    for key, sub in shapes.items():
        if key in LAYER_STACKS:
            n = jax.tree.leaves(sub)[0].shape[0]
            one = jax.tree.map(lambda s: on_chip(s, True), sub)
            params[key] = (one,) * n
        else:
            params[key] = jax.tree.map(on_chip, sub)

    eng = object.__new__(ServingEngine)
    eng.cfg, eng.serve_cfg = cfg, sc
    eng._kv_quant, eng._mesh, eng._spec = False, None, None
    eng.is_moe, eng.is_mla = False, cfg.attention_type == "mla"
    eng.holds_state = cfg.holds_state
    eng._attn_row_tile = row_tile(
        sc.token_budget, cfg.num_heads * 2 * cfg.resolved_head_dim)
    L = cfg.num_layers
    eng._stacks = [("layers", _dense_mlp, L)]
    ops = layer_operators(cfg)
    eng._stack_ops = [ops if ops is not None else (("attention", None),) * L]
    eng._stack_attn = [sum(k == "attention" for k, _ in eng._stack_ops[0])]
    eng._stack_windows = [jnp.asarray(
        [w or 0 for w in layer_windows(cfg, L)], jnp.int32)]
    eng._any_window = False
    eng._inv_freq = rope_frequencies(
        cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling) if cfg.use_rope else None
    eng._freq_for_win = lambda win: eng._inv_freq

    def as_shapes(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    pool = as_shapes(jax.eval_shape(lambda: init_pool(
        cfg, eng._stack_attn, sc.num_pages, sc.page_size)))
    state = as_shapes(jax.eval_shape(lambda: init_state(cfg, sc.max_slots)))
    batch = as_shapes(jax.eval_shape(
        lambda: eng._plan_batch(eng.empty_plan())))
    args = (params, pool, batch) + ((state,) if state else ())
    return eng, args
