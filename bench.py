"""Benchmark: decoder-LM pretrain step throughput + MFU on the local chip(s).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline: the reference framework's H100 MFU on Llama3-class workloads —
402/989 TFLOPs ≈ 40.6% (BASELINE.md, docs/performance-summary.mdx:35).
vs_baseline therefore compares hardware utilization (MFU/MFU), the only
apples-to-apples number across a single H100 and a single TPU chip.

Run: python bench.py [--steps N] [--preset small|medium]   (needs a TPU;
`--platform cpu` is the tiny CPU run, counts only)
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time

H100_BASELINE_MFU_PCT = 40.6  # reference Llama3-8B single-GPU, BASELINE.md


def _force_cpu(n_devices: int = 1) -> None:
    from automodel_tpu.utils.hostplatform import force_cpu_devices

    force_cpu_devices(n_devices)


def build(preset: str):
    import jax.numpy as jnp

    from automodel_tpu.models.llm.decoder import TransformerConfig

    if preset == "tiny":  # harness sanity check (runs on a CPU mesh)
        return TransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2,
            dtype=jnp.float32, remat_policy="none", attn_impl="xla",
        ), 4, 128
    if preset == "small":  # fits v5e (16 GB) with adam fp32 states
        return TransformerConfig(
            vocab_size=32768, hidden_size=1024, intermediate_size=4096,
            num_layers=16, num_heads=16, num_kv_heads=8,
            rope_theta=500000.0, dtype=jnp.bfloat16, remat_policy="full",
            attn_impl="auto",
        ), 8, 2048
    # medium: ~1.1B
    return TransformerConfig(
        vocab_size=32768, hidden_size=2048, intermediate_size=5632,
        num_layers=22, num_heads=16, num_kv_heads=8,
        rope_theta=500000.0, dtype=jnp.bfloat16, remat_policy="full",
        attn_impl="auto",
    ), 4, 2048


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--preset", default=None, choices=["tiny", "small", "medium"])
    ap.add_argument(
        "--platform", default="tpu", choices=["tpu", "cpu"],
        help="tpu: require a TPU (the run fails without one); cpu: the tiny "
        "CPU run, which reports counts and no device metric",
    )
    ap.add_argument(
        "--serve-scale-child", default=None, metavar="MESH_JSON",
        help="internal: run one serve_scale mesh shape in this process "
        "(the parent forces the virtual CPU device count via env) and "
        "print a SERVE_SCALE: JSON line",
    )
    ap.add_argument(
        "--serve-chaos-child", action="store_true",
        help="internal: run the serve_chaos scenario in this process (the "
        "parent forces 2 virtual CPU devices via env) and print a "
        "SERVE_CHAOS: JSON line",
    )
    ap.add_argument(
        "--no-headline", action="store_true",
        help="emit only the llama-MFU metric (skip the flash-vs-XLA, MoE "
        "dropless, long-context CP, serving-decode, prefix-cache, "
        "speculative-decode, serve-scale, and resilience headlines)",
    )
    args = ap.parse_args()

    if args.serve_scale_child is not None:
        _serve_scale_child(args.serve_scale_child)
        return
    if args.serve_chaos_child:
        _serve_chaos_child()
        return

    if args.platform == "cpu":
        _force_cpu()
        args.preset = args.preset or "tiny"
    else:
        args.preset = args.preset or "medium"

    import jax

    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    platform = jax.devices()[0].platform
    if platform != args.platform:
        raise SystemExit(
            f"bench.py --platform {args.platform}: JAX found "
            f"'{platform}' devices"
        )

    result = _run(args)
    if not args.no_headline:
        result["headline"] = _run_headline(platform != "cpu")
        result["headline"]["llama_pretrain_mfu_pct"] = {
            "value": result["value"], "unit": result["unit"],
            "detail": dict(result["detail"]),
        }
    print(json.dumps(result))
    failed = sorted(
        name for name, r in result.get("headline", {}).items()
        if "error" in r
    )
    if failed:
        raise SystemExit(f"bench.py: headline(s) failed: {failed}")


def _run(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.loss import fused_linear_cross_entropy
    from automodel_tpu.models.llm import decoder
    from automodel_tpu.optim import OptimizerConfig
    from automodel_tpu.parallel import logical_to_shardings
    from automodel_tpu.training import init_train_state, make_train_step
    from automodel_tpu.utils.flops import MFUCalculator, device_peak_tflops

    cfg, batch, seq = build(args.preset)
    ctx = MeshConfig().build()
    n_dev = ctx.num_devices
    # batch must divide across the token-sharding axes of whatever mesh
    # this host exposes (1 chip on TPU, N virtual devices on CPU)
    div = ctx.batch_size_divisor
    batch = ((batch + div - 1) // div) * div

    params = jax.jit(
        lambda k: decoder.init(cfg, k),
        out_shardings=logical_to_shardings(
            decoder.param_specs(cfg), ctx,
            shapes=jax.tree.map(
                lambda p: p.shape,
                jax.eval_shape(lambda: decoder.init(cfg, jax.random.key(0))),
            ),
        ),
    )(jax.random.key(0))

    def loss_fn(p, b, rng):
        hidden = decoder.forward(
            p, cfg, b["input_ids"], return_hidden=True, mesh_ctx=ctx
        )
        return fused_linear_cross_entropy(
            hidden, p["lm_head"]["kernel"], b["labels"], chunk_size=2048
        )

    tx = OptimizerConfig(lr=1e-4, weight_decay=0.1).build()
    state = init_train_state(params, tx)
    step_fn = jax.jit(make_train_step(loss_fn, tx), donate_argnums=0)

    rng = np.random.default_rng(0)
    ids = rng.integers(1, cfg.vocab_size, (1, batch, seq + 1), dtype=np.int64)
    b = {
        "input_ids": jnp.asarray(ids[..., :-1], jnp.int32),
        "labels": jnp.asarray(ids[..., 1:], jnp.int32),
    }
    b = jax.device_put(b, ctx.sharding(None, "batch", None))

    # warmup / compile
    state, m = step_fn(state, b, jax.random.key(0))
    jax.block_until_ready(m["loss"])

    # best-of-N windows: a co-resident process on the host inflates step
    # dispatch time — external interference only ever slows a window down,
    # never speeds it up, so the fastest window is reported
    windows = 3
    per = max(1, args.steps // windows)
    dt = float("inf")
    for w in range(windows):
        t0 = time.perf_counter()
        for i in range(per):
            state, m = step_fn(state, b, jax.random.key(w * per + i))
        jax.block_until_ready(m["loss"])
        dt = min(dt, (time.perf_counter() - t0) / per)

    tokens = batch * seq
    mfu = MFUCalculator(
        flops_per_token=cfg.flops_per_token(seq), num_devices=n_dev
    ).metrics(tokens, dt)

    # a CPU has no peak: the tiny CPU run reports no utilization
    on_cpu = mfu["mfu_pct"] is None
    return {
        "metric": "llama_pretrain_mfu_pct",
        "value": None if on_cpu else round(mfu["mfu_pct"], 2),
        "unit": "% MFU",
        "vs_baseline": (
            None if on_cpu
            else round(mfu["mfu_pct"] / H100_BASELINE_MFU_PCT, 3)
        ),
        "detail": {
            "preset": args.preset,
            "devices": n_dev,
            "device_kind": jax.devices()[0].device_kind,
            "peak_tflops": None if on_cpu else device_peak_tflops(),
            "step_seconds": round(dt, 4),
            "tokens_per_sec_per_device": round(mfu["tps_per_device"], 1),
            "tflops_per_device": round(mfu["tflops_per_device"], 1),
            "loss": float(m["loss"]),
        },
    }


def _time_best(fn, *args, windows: int = 3, inner: int = 3) -> float:
    """Best-of-N windows of `inner` calls each (see the MFU loop: external
    interference only slows a window down), returns seconds per call."""
    out = fn(*args)
    import jax

    jax.block_until_ready(out)  # compile outside the window
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def _headline_attention(accel: bool) -> dict:
    """Flash-kernel vs XLA-attention microbench on one causal GQA shape."""
    import jax
    import jax.numpy as jnp

    from automodel_tpu.ops.attention import dot_product_attention

    B, S, Hq, Hkv, D = (4, 2048, 16, 8, 128) if accel else (2, 256, 4, 2, 64)
    ks = jax.random.split(jax.random.key(0), 3)
    dt = jnp.bfloat16 if accel else jnp.float32
    q = jax.random.normal(ks[0], (B, S, Hq, D), dt)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dt)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dt)
    out = {"shape": {"B": B, "S": S, "Hq": Hq, "Hkv": Hkv, "D": D}}

    xla = jax.jit(lambda q, k, v: dot_product_attention(q, k, v, impl="xla"))
    out["xla_ms"] = round(_time_best(xla, q, k, v) * 1e3, 3)
    try:
        fl = jax.jit(lambda q, k, v: dot_product_attention(q, k, v, impl="flash"))
        out["flash_ms"] = round(_time_best(fl, q, k, v) * 1e3, 3)
        out["speedup"] = round(out["xla_ms"] / out["flash_ms"], 3)
    except Exception as e:  # noqa: BLE001 — pallas needs a TPU backend
        out["flash_ms"] = None
        out["error"] = f"flash kernel unavailable: {repr(e)[:160]}"
    return out


def _headline_moe(accel: bool) -> dict:
    """Dropless MoE train-step time (the sort + ragged GEMM + A2A path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.loss import fused_linear_cross_entropy
    from automodel_tpu.loss.utils import combine_losses
    from automodel_tpu.models.moe_lm import decoder as moe_decoder
    from automodel_tpu.models.moe_lm.decoder import MoETransformerConfig
    from automodel_tpu.moe.config import MoEConfig
    from automodel_tpu.optim import OptimizerConfig
    from automodel_tpu.parallel import logical_to_shardings
    from automodel_tpu.training import init_train_state, make_train_step

    ctx = MeshConfig(ep=-1).build() if accel else MeshConfig().build()
    if accel:
        cfg = MoETransformerConfig(
            vocab_size=32768, hidden_size=1024, intermediate_size=2048,
            num_layers=4, num_heads=16, num_kv_heads=8, first_k_dense=1,
            moe=MoEConfig(
                n_routed_experts=max(8, 2 * ctx.sizes["ep"]),
                n_shared_experts=1, experts_per_token=2,
                moe_intermediate_size=512, shared_expert_intermediate_size=512,
                aux_loss_coeff=0.01, dispatcher="dropless",
            ),
            dtype=jnp.bfloat16, remat_policy="full", attn_impl="auto",
        )
        batch, seq = 4, 2048
    else:
        cfg = MoETransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, first_k_dense=0,
            moe=MoEConfig(
                n_routed_experts=4, n_shared_experts=1, experts_per_token=2,
                moe_intermediate_size=32, shared_expert_intermediate_size=32,
                aux_loss_coeff=0.01, dispatcher="dropless",
            ),
            dtype=jnp.float32, remat_policy="none", attn_impl="xla",
        )
        batch, seq = 4, 128
    div = ctx.batch_size_divisor
    batch = ((batch + div - 1) // div) * div
    params = moe_decoder.init(cfg, jax.random.key(0))
    params = jax.device_put(params, logical_to_shardings(
        moe_decoder.param_specs(cfg), ctx,
        shapes=jax.tree.map(lambda p: p.shape, params),
    ))

    def loss_fn(p, b, rng):
        hidden, aux = moe_decoder.forward(
            p, cfg, b["input_ids"], return_hidden=True, mesh_ctx=ctx
        )
        ce, n = fused_linear_cross_entropy(
            hidden, p["lm_head"]["kernel"], b["labels"], chunk_size=2048
        )
        return combine_losses(ce, n, aux)

    tx = OptimizerConfig(lr=1e-4).build()
    state = init_train_state(params, tx)
    step_fn = jax.jit(make_train_step(loss_fn, tx), donate_argnums=0)
    ids = np.random.default_rng(0).integers(
        1, cfg.vocab_size, (1, batch, seq + 1)
    )
    b = jax.device_put(
        {"input_ids": jnp.asarray(ids[..., :-1], jnp.int32),
         "labels": jnp.asarray(ids[..., 1:], jnp.int32)},
        ctx.sharding(None, "batch", None),
    )
    state, m = step_fn(state, b, jax.random.key(0))
    jax.block_until_ready(m["loss"])
    best = float("inf")
    for w in range(3):
        t0 = time.perf_counter()
        state, m = step_fn(state, b, jax.random.key(w))
        jax.block_until_ready(m["loss"])
        best = min(best, time.perf_counter() - t0)
    return {
        "step_ms": round(best * 1e3, 2),
        "tokens_per_sec": round(batch * seq / best, 1),
        "config": {
            "experts": cfg.moe.n_routed_experts, "ep": ctx.sizes["ep"],
            "layers": cfg.num_layers, "hidden": cfg.hidden_size,
            "batch": batch, "seq": seq,
        },
    }


def _headline_cp(accel: bool) -> dict:
    """Long-context step time: 32k tokens under ring-CP when the mesh has
    ≥2 devices (cp=-1 soaks them), else the single-chip 32k step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.loss import fused_linear_cross_entropy
    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.optim import OptimizerConfig
    from automodel_tpu.parallel import logical_to_shardings
    from automodel_tpu.training import init_train_state, make_train_step

    n_dev = len(jax.devices())
    cp = n_dev if n_dev > 1 else 1
    ctx = MeshConfig(cp=cp, dp_shard=1).build()
    if accel:
        cfg = TransformerConfig(
            vocab_size=32768, hidden_size=1024, intermediate_size=4096,
            num_layers=4, num_heads=16, num_kv_heads=8,
            rope_theta=500000.0, dtype=jnp.bfloat16, remat_policy="full",
            attn_impl="auto",
        )
        seq = 32768
    else:
        cfg = TransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2,
            dtype=jnp.float32, remat_policy="none", attn_impl="xla",
        )
        seq = 1024 * max(1, cp)
    params = decoder.init(cfg, jax.random.key(0))
    params = jax.device_put(params, logical_to_shardings(
        decoder.param_specs(cfg), ctx,
        shapes=jax.tree.map(lambda p: p.shape, params),
    ))

    def loss_fn(p, b, rng):
        hidden = decoder.forward(
            p, cfg, b["input_ids"], return_hidden=True, mesh_ctx=ctx
        )
        return fused_linear_cross_entropy(
            hidden, p["lm_head"]["kernel"], b["labels"], chunk_size=2048
        )

    tx = OptimizerConfig(lr=1e-4).build()
    state = init_train_state(params, tx)
    step_fn = jax.jit(make_train_step(loss_fn, tx), donate_argnums=0)
    ids = np.random.default_rng(0).integers(1, cfg.vocab_size, (1, 1, seq + 1))
    b = jax.device_put(
        {"input_ids": jnp.asarray(ids[..., :-1], jnp.int32),
         "labels": jnp.asarray(ids[..., 1:], jnp.int32)},
        ctx.sharding(None, "batch", "cp"),
    )
    state, m = step_fn(state, b, jax.random.key(0))
    jax.block_until_ready(m["loss"])
    best = float("inf")
    for w in range(3):
        t0 = time.perf_counter()
        state, m = step_fn(state, b, jax.random.key(w))
        jax.block_until_ready(m["loss"])
        best = min(best, time.perf_counter() - t0)
    return {
        "step_ms": round(best * 1e3, 2),
        "tokens_per_sec": round(seq / best, 1),
        "config": {"seq": seq, "cp": cp, "hidden": cfg.hidden_size,
                   "layers": cfg.num_layers},
    }


def _headline_decode(accel: bool) -> dict:
    """Serving-engine decode: sustained tokens/s + per-token latency on a
    mixed-length request stream (staggered arrivals, chunked prefill
    interleaved with decode) through the continuous-batching paged-KV
    engine — the arXiv:2605.25645-style engine-loop number, not a kernel
    microbench."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.serving import Request, ServingConfig, ServingEngine

    if accel:
        cfg = TransformerConfig(
            vocab_size=32768, hidden_size=1024, intermediate_size=4096,
            num_layers=8, num_heads=16, num_kv_heads=8,
            rope_theta=500000.0, dtype=jnp.bfloat16, remat_policy="none",
            attn_impl="auto",
        )
        serve = ServingConfig(
            page_size=16, num_pages=2048, max_slots=16, pages_per_slot=64,
            token_budget=64, prefill_chunk=48,
        )
        lens, max_new, n_req = (128, 512, 256, 768, 384), 64, 16
    else:
        cfg = TransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2,
            dtype=jnp.float32, remat_policy="none", attn_impl="xla",
        )
        serve = ServingConfig(
            page_size=8, num_pages=64, max_slots=4, pages_per_slot=8,
            token_budget=16, prefill_chunk=8,
        )
        lens, max_new, n_req = (12, 30, 7, 21, 16), 16, 8
    params = decoder.init(cfg, jax.random.key(0))
    engine = ServingEngine(params, cfg, serve)
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            prompt=[int(t) for t in rng.integers(1, cfg.vocab_size, (lens[i % len(lens)],))],
            max_new_tokens=max_new, arrival=i // 2,
        )
        for i in range(n_req)
    ]
    # warmup: compile the single step signature outside the timed window
    engine.serve_batch([Request(prompt=[1, 2, 3], max_new_tokens=2)])
    res = engine.serve_batch(reqs)
    stats = res["stats"]
    assert stats["compiled_signatures"] == 1, stats
    return {
        "tokens_per_sec": stats["decode_tokens_per_sec"],
        "ms_per_token": stats["ms_per_token"],
        "new_tokens": stats["new_tokens"],
        "steps": stats["steps"],
        "preemptions": stats["preemptions"],
        "config": {
            "requests": n_req, "prompt_lens": list(lens),
            "max_new_tokens": max_new, "max_slots": serve.max_slots,
            "page_size": serve.page_size, "num_pages": serve.num_pages,
            "token_budget": serve.token_budget,
            "hidden": cfg.hidden_size, "layers": cfg.num_layers,
        },
    }


def _headline_prefix(accel: bool) -> dict:
    """Prefix cache: prefill tokens skipped (hit ratio) + sustained decode
    tokens/s on a shared-system-prompt agent-loop workload — K agents each
    re-sending their whole growing history every round (the traffic shape
    the radix tree exists for) — against the cache-DISABLED engine on the
    identical stream."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.serving import (
        PrefixCacheConfig,
        Request,
        ServingConfig,
        ServingEngine,
        split_layer_stacks,
    )

    if accel:
        cfg = TransformerConfig(
            vocab_size=32768, hidden_size=1024, intermediate_size=4096,
            num_layers=8, num_heads=16, num_kv_heads=8,
            rope_theta=500000.0, dtype=jnp.bfloat16, remat_policy="none",
            attn_impl="auto",
        )
        geo = dict(page_size=16, num_pages=4096, max_slots=16,
                   pages_per_slot=128, token_budget=64, prefill_chunk=48)
        sys_len, turn_len, agents, rounds, max_new = 256, 32, 4, 4, 32
        arrival_stride = 40
    else:
        cfg = TransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2,
            dtype=jnp.float32, remat_policy="none", attn_impl="xla",
        )
        geo = dict(page_size=4, num_pages=256, max_slots=4,
                   pages_per_slot=32, token_budget=16, prefill_chunk=8)
        sys_len, turn_len, agents, rounds, max_new = 24, 6, 3, 4, 8
        arrival_stride = 12
    # split once: the engines below share the per-layer tree
    params = split_layer_stacks(
        decoder.init(cfg, jax.random.key(0)), cfg.dtype
    )
    rng = np.random.default_rng(0)
    system = [int(t) for t in rng.integers(1, cfg.vocab_size, (sys_len,))]

    # agent loops: every round re-sends system + the whole history so far;
    # rounds are staggered so earlier rounds complete (and donate) first
    reqs = []
    for a in range(agents):
        hist = list(system)
        for r in range(rounds):
            hist = hist + [
                int(t) for t in rng.integers(1, cfg.vocab_size, (turn_len,))
            ]
            reqs.append(Request(
                prompt=list(hist), max_new_tokens=max_new,
                arrival=r * arrival_stride + a,
            ))
    total_prompt = sum(len(r.prompt) for r in reqs)

    def run(prefix_cfg):
        engine = ServingEngine(params, cfg, ServingConfig(
            **geo, prefix_cache=prefix_cfg,
        ))
        # warmup compiles the single step signature outside the timed window
        engine.serve_batch([Request(prompt=[1, 2, 3], max_new_tokens=2)])
        return engine.serve_batch([
            Request(prompt=list(r.prompt), max_new_tokens=r.max_new_tokens,
                    arrival=r.arrival)
            for r in reqs
        ])["stats"]

    cold = run(None)
    warm = run(PrefixCacheConfig(enabled=True))
    assert warm["compiled_signatures"] == 1, warm
    skipped = warm["prefill_skipped_tokens"]
    return {
        "prefill_skipped_tokens": skipped,
        "prefill_hit_ratio": round(skipped / max(total_prompt, 1), 4),
        "tokens_per_sec": warm["decode_tokens_per_sec"],
        "tokens_per_sec_nocache": cold["decode_tokens_per_sec"],
        "elapsed_s": warm["elapsed_s"],
        "elapsed_s_nocache": cold["elapsed_s"],
        "tokens_fed": warm["tokens_fed"],
        "tokens_fed_nocache": cold["tokens_fed"],
        "cow_copies": warm["cow_copies"],
        "prefix_hits": warm["prefix_hits"],
        "config": {
            "agents": agents, "rounds": rounds, "system_len": sys_len,
            "turn_len": turn_len, "max_new_tokens": max_new,
            "requests": len(reqs), "total_prompt_tokens": total_prompt,
            **geo,
        },
    }


def _headline_spec(accel: bool) -> dict:
    """Speculative decoding: sustained decode tokens/s with vs without
    per-slot draft-then-verify (ngram prompt-lookup drafts, greedy
    acceptance — lossless, so both runs emit the identical token stream)
    on a decode-heavy agent-loop-ish stream where generations run long
    enough for self-repetition to feed the lookup. Reports acceptance
    rate and mean accepted length (committed tokens per jitted verify
    step); > 1 means speculation is beating one-token-per-step decode.
    Compile-once asserted for both engines."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.serving import (
        Request,
        ServingConfig,
        ServingEngine,
        SpeculativeConfig,
        split_layer_stacks,
    )

    if accel:
        cfg = TransformerConfig(
            vocab_size=32768, hidden_size=1024, intermediate_size=4096,
            num_layers=8, num_heads=16, num_kv_heads=8,
            rope_theta=500000.0, dtype=jnp.bfloat16, remat_policy="none",
            attn_impl="auto",
        )
        geo = dict(page_size=16, num_pages=2048, max_slots=8,
                   pages_per_slot=64, token_budget=64, prefill_chunk=32)
        lens, max_new, n_req, draft_len = (128, 256, 192, 512), 128, 16, 6
    else:
        cfg = TransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2,
            dtype=jnp.float32, remat_policy="none", attn_impl="xla",
        )
        geo = dict(page_size=8, num_pages=96, max_slots=4,
                   pages_per_slot=16, token_budget=32, prefill_chunk=8)
        lens, max_new, n_req, draft_len = (24, 16, 30, 20), 64, 8, 6
    # split once: the engines below share the per-layer tree
    params = split_layer_stacks(
        decoder.init(cfg, jax.random.key(0)), cfg.dtype
    )
    rng = np.random.default_rng(0)
    prompts = [
        [int(t) for t in rng.integers(1, cfg.vocab_size, (lens[i % len(lens)],))]
        for i in range(n_req)
    ]

    def run(spec):
        engine = ServingEngine(params, cfg, ServingConfig(**geo, speculative=spec))
        # warmup compiles the single step signature outside the timed window
        engine.serve_batch([Request(prompt=[1, 2, 3], max_new_tokens=2)])
        res = engine.serve_batch([
            Request(prompt=list(p), max_new_tokens=max_new, arrival=i // 2)
            for i, p in enumerate(prompts)
        ])
        assert res["stats"]["compiled_signatures"] == 1, res["stats"]
        return res

    plain = run(None)
    spec = run(SpeculativeConfig(
        enabled=True, draft_source="ngram", draft_len=draft_len,
    ))
    # greedy speculation is lossless — both engines emit the same stream
    assert spec["outputs"] == plain["outputs"], "speculation changed tokens"
    s = spec["stats"]
    return {
        "tokens_per_sec": s["decode_tokens_per_sec"],
        "tokens_per_sec_nospec": plain["stats"]["decode_tokens_per_sec"],
        "speedup": round(
            s["decode_tokens_per_sec"]
            / max(plain["stats"]["decode_tokens_per_sec"], 1e-9), 3,
        ),
        "steps": s["steps"],
        "steps_nospec": plain["stats"]["steps"],
        "acceptance_rate": s["acceptance_rate"],
        "mean_accepted_len": s["mean_accepted_len"],
        "drafted_tokens": s["drafted_tokens"],
        "accepted_tokens": s["accepted_tokens"],
        "rolled_back_tokens": s["rolled_back_tokens"],
        "config": {
            "requests": n_req, "prompt_lens": list(lens),
            "max_new_tokens": max_new, "draft_len": draft_len,
            "draft_source": "ngram", **geo,
            "hidden": cfg.hidden_size, "layers": cfg.num_layers,
        },
    }


def _serve_scale_child(mesh_json: str) -> None:
    """Child-process half of the `serve_scale` headline: build the given
    serving mesh over virtual CPU devices (the parent sets
    XLA_FLAGS=--xla_force_host_platform_device_count), drive one identical
    request stream through the sharded engine / replica router, print ONE
    JSON line of stats. A subprocess because the parent has already
    initialized its backend with a different device count."""
    import dataclasses
    import json as _json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.serving import (
        ReplicaRouter,
        Request,
        ServeMeshConfig,
        ServingConfig,
    )

    mesh = ServeMeshConfig(**_json.loads(mesh_json))
    cfg = TransformerConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        dtype=jnp.float32, remat_policy="none", attn_impl="xla",
    )
    serve = ServingConfig(
        page_size=8, num_pages=64, max_slots=4, pages_per_slot=8,
        token_budget=16, prefill_chunk=8,
    )
    lens, max_new, n_req = (12, 30, 7, 21, 16), 16, 8
    params = decoder.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [
        [int(t) for t in rng.integers(1, cfg.vocab_size, (lens[i % len(lens)],))]
        for i in range(n_req)
    ]

    def reqs():
        return [
            Request(prompt=list(p), max_new_tokens=max_new, arrival=i // 2)
            for i, p in enumerate(prompts)
        ]

    # every shape goes through the router (replicas=1 is the trivial
    # routing decision) so p50/p95 are TRUE per-step percentiles for all
    # mesh shapes — comparing a 1chip mean against a tp2 tail percentile
    # would understate single-chip tail latency
    router = ReplicaRouter(params, cfg, serve, mesh)
    router.serve_batch(reqs())  # warmup: compile outside the window
    stats = router.serve_batch(reqs())["stats"]
    per = stats["per_replica"]
    out = {
        "decode_tokens_per_sec": stats["decode_tokens_per_sec"],
        "p50_ms_per_token": [p["p50_ms_per_token"] for p in per],
        "p95_ms_per_token": [p["p95_ms_per_token"] for p in per],
        "requests_per_replica": stats["requests_per_replica"],
        "balance": stats["balance"],
        "sticky_routed": stats["sticky_routed"],
    }
    out.update(
        compiled_signatures=stats["compiled_signatures"],
        new_tokens=stats["new_tokens"],
        mesh=dataclasses.asdict(mesh),
        devices=len(jax.devices()),
    )
    assert stats["compiled_signatures"] == 1, stats
    print("SERVE_SCALE:" + _json.dumps(out))


def _serve_chaos_child() -> None:
    """Child-process half of the `serve_chaos` headline: 256 live streams
    through a 2-replica `OnlineRouter` over virtual CPU devices, one
    deterministic replica death injected mid-trace, and the same trace
    re-run clean. Reports goodput fraction under the death vs clean, the
    recovered-request TTFT penalty (the re-prefill detour's cost), and
    token-for-token offline parity for every completed stream — the
    recovery must be invisible in the sampled tokens. Prints ONE
    SERVE_CHAOS: JSON line."""
    import asyncio
    import json as _json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.resilience import FaultSpec, injected
    from automodel_tpu.serving import (
        FrontendConfig,
        OnlineRouter,
        ReplicaRouter,
        Request,
        ServeMeshConfig,
        ServingConfig,
        ServingEngine,
        pool_identity_ok,
        split_layer_stacks,
    )
    from automodel_tpu.serving.load_test import (
        LoadTestConfig,
        _consume,
        make_trace,
    )

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        dtype=jnp.float32, remat_policy="none", attn_impl="xla",
    )
    serve = ServingConfig(
        page_size=8, num_pages=96, max_slots=4, pages_per_slot=8,
        token_budget=16, prefill_chunk=8,
    )
    lt = LoadTestConfig(
        num_requests=256, prompt_len=(3, 12), max_new_tokens=8,
        mean_interarrival_steps=0.25, deadline_in=160,
        deadline_fraction=0.25, vocab=cfg.vocab_size,
    )
    # split once: the engines below share the per-layer tree
    params = split_layer_stacks(
        decoder.init(cfg, jax.random.key(0)), cfg.dtype
    )
    trace = make_trace(lt)

    async def drive(router):
        # arrival pacing against the SURVIVOR's step counter (replica0 —
        # the injected death targets replica1): the router's wait_step
        # awaits every replica, and a dead replica's counter freezes
        orouter = OnlineRouter(
            router, FrontendConfig(idle_sleep_s=0.0002)
        ).start()
        records: dict = {}
        consumers, submitted = [], []
        for arrival, prompt, dl in trace:
            if arrival:
                await orouter.frontends[0].wait_step(arrival)
            req = Request(prompt=list(prompt),
                          max_new_tokens=lt.max_new_tokens)
            submitted.append(req)
            s = orouter.submit(req, deadline_in=dl)
            consumers.append(asyncio.ensure_future(_consume(s, records)))
        await asyncio.gather(*consumers)
        stats = await orouter.close()
        return orouter, stats, submitted, records

    def run(spec=None):
        router = ReplicaRouter(
            params, cfg, serve, ServeMeshConfig(replicas=2, tp=1)
        )
        if spec is None:
            return asyncio.run(drive(router))
        with injected(spec):
            return asyncio.run(drive(router))

    def pct(xs, q):
        return round(float(np.percentile(xs, q)), 4) if xs else None

    def summarize(stats, submitted) -> dict:
        ok = [r for r in submitted if r.finish_reason in ("eos", "length")]
        recovered = [r for r in ok if r.recovered > 0]
        undisturbed = [r for r in ok if r.recovered == 0]
        ttft = lambda rs: [r.ttft_s * 1e3 for r in rs if r.ttft_s >= 0]  # noqa: E731
        return {
            "completed": len(ok),
            "shed": stats["shed"],
            "timed_out": stats["timed_out"],
            "recovered": len(recovered),
            "goodput_fraction": round(len(ok) / max(len(submitted), 1), 4),
            "ttft_p50_ms": pct(ttft(undisturbed), 50),
            "ttft_p50_recovered_ms": pct(ttft(recovered), 50),
        }

    _, clean_stats, clean_sub, _ = run()
    orouter, chaos_stats, chaos_sub, chaos_rec = run(
        FaultSpec(point="serve_step_run.replica1", call=30)
    )
    clean = summarize(clean_stats, clean_sub)
    chaos = summarize(chaos_stats, chaos_sub)
    assert chaos_stats["replica_health"]["replica1"] == "dead", chaos_stats
    assert chaos["recovered"] >= 1, chaos
    assert chaos_stats["per_replica"][0]["compiled_signatures"] == 1
    assert pool_identity_ok(orouter.frontends[0].sched)
    # recovered-request TTFT penalty: what the re-prefill detour costs
    # the adopted streams vs the undisturbed completed population
    penalty = None
    if chaos["ttft_p50_recovered_ms"] and chaos["ttft_p50_ms"]:
        penalty = round(
            chaos["ttft_p50_recovered_ms"] - chaos["ttft_p50_ms"], 4
        )
    # offline parity on survivors: every completed chaos stream must be
    # the greedy continuation a fresh single engine produces — recovery
    # (evacuate → route → re-prefill on a survivor) is host-side only
    done = [r for r in chaos_sub if r.finish_reason in ("eos", "length")]
    offline = ServingEngine(params, cfg, serve).serve_batch([
        Request(prompt=list(r.prompt), max_new_tokens=lt.max_new_tokens)
        for r in done
    ])
    for r, want in zip(done, offline["outputs"]):
        got = chaos_rec[r.rid][0]
        assert got == want, (
            f"chaos stream rid={r.rid} (recovered={r.recovered}) diverged "
            f"from offline serve_batch: {got} vs {want}"
        )
    print("SERVE_CHAOS:" + _json.dumps({
        "requests": len(chaos_sub),
        "clean": clean,
        "chaos": chaos,
        "goodput_retention": round(
            chaos["goodput_fraction"]
            / max(clean["goodput_fraction"], 1e-9), 4
        ),
        "recovered_ttft_penalty_ms": penalty,
        "parity_checked": len(done),
        "replica_health": chaos_stats["replica_health"],
        "devices": len(jax.devices()),
    }))


def _headline_serve_chaos(accel: bool) -> dict:
    """Serving resilience under live traffic: one injected replica death
    at 256 live streams — goodput fraction retained vs a clean run of the
    same trace, the recovered-request TTFT penalty, and offline parity on
    every completed stream. Runs in a subprocess over virtual CPU devices
    for the same reason as serve_scale: the recovery structure (health
    machine, evacuation, re-prefill routing) is host-side and backend-
    independent, and the chaos parity contract is pinned by the tier-1
    suite on the identical CPU mesh."""
    import os
    import subprocess

    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--serve-chaos-child"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    line = next(
        (l for l in r.stdout.splitlines() if l.startswith("SERVE_CHAOS:")),
        None,
    )
    if r.returncode != 0 or line is None:
        return {"error": (r.stderr or r.stdout)[-300:]}
    return json.loads(line[len("SERVE_CHAOS:"):])


def _headline_disagg(accel: bool) -> dict:
    """Disaggregated serving: decode TTFT/ITL p50/p95 with vs without the
    prefill/decode phase split on a MIXED load — long ingestion prompts
    arriving throughout a latency-sensitive chat stream (the interference
    shape Mooncake/DistServe target: monolithic steps carry prefill
    chunks whose slots commit nothing, diluting per-step decode output
    and fattening the ITL tail) — plus the engine-lifetime prefix cache's
    warm-vs-cold hit ratio across two serve_batch calls on ONE engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.serving import (
        DisaggConfig,
        DisaggRouter,
        PrefixCacheConfig,
        Request,
        ServingConfig,
        ServingEngine,
        split_layer_stacks,
    )

    if accel:
        cfg = TransformerConfig(
            vocab_size=32768, hidden_size=1024, intermediate_size=4096,
            num_layers=8, num_heads=16, num_kv_heads=8,
            rope_theta=500000.0, dtype=jnp.bfloat16, remat_policy="none",
            attn_impl="auto",
        )
        geo = dict(page_size=16, num_pages=2048, max_slots=8,
                   pages_per_slot=64)
        mono_budget = dict(token_budget=32, prefill_chunk=24)
        # the decode class rightsizes its fixed step shape to its decode
        # rows (prefill chunks never ride it); the prefill class takes
        # the wide budget — the phase split's structural win
        disagg_budget = dict(token_budget=16, prefill_chunk=None)
        long_len, long_n, chat_len, chat_n = 768, 6, 32, 12
        long_new, chat_new, chat_stride = 8, 64, 8
        disagg = DisaggConfig(enabled=True, transfer_pages=8,
                              prefill_token_budget=64)
        sys_len = 256
    else:
        cfg = TransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2,
            dtype=jnp.float32, remat_policy="none", attn_impl="xla",
        )
        geo = dict(page_size=4, num_pages=256, max_slots=4,
                   pages_per_slot=32)
        mono_budget = dict(token_budget=16, prefill_chunk=8)
        disagg_budget = dict(token_budget=8, prefill_chunk=None)
        long_len, long_n, chat_len, chat_n = 96, 4, 8, 8
        long_new, chat_new, chat_stride = 4, 16, 6
        disagg = DisaggConfig(enabled=True, transfer_pages=8,
                              prefill_token_budget=32)
        sys_len = 24
    # split once: the engines below share the per-layer tree
    params = split_layer_stacks(
        decoder.init(cfg, jax.random.key(0)), cfg.dtype
    )
    rng = np.random.default_rng(0)

    def reqs():
        out = []
        for i in range(long_n):  # batch-ingestion stream: long, few tokens
            out.append(Request(
                prompt=[int(t) for t in
                        rng.integers(1, cfg.vocab_size, (long_len,))],
                max_new_tokens=long_new,
                arrival=i * (chat_stride * chat_n // max(long_n, 1)),
                seed=i,
            ))
        for i in range(chat_n):  # chat stream: short, latency-sensitive
            out.append(Request(
                prompt=[int(t) for t in
                        rng.integers(1, cfg.vocab_size, (chat_len,))],
                max_new_tokens=chat_new, arrival=i * chat_stride,
                seed=100 + i,
            ))
        return out

    warm_req = lambda: [Request(prompt=[1, 2, 3], max_new_tokens=2)]  # noqa: E731

    # both timed runs trace (host-side only — the comparison stays
    # apples-to-apples and the compile-once asserts double as the
    # tracing-changes-nothing check); the disagg trace feeds the
    # TTFT attribution block below
    from automodel_tpu.observability import (
        ObservabilityConfig,
        attribution_summary,
    )

    obs_cfg = ObservabilityConfig(enabled=True)
    engine = ServingEngine(
        params, cfg, ServingConfig(**geo, **mono_budget, observability=obs_cfg)
    )
    engine.serve_batch(warm_req())  # compile outside the timed window
    mono = engine.serve_batch(reqs())["stats"]

    router = DisaggRouter(
        params, cfg,
        ServingConfig(**geo, **disagg_budget, observability=obs_cfg),
        disagg,
    )
    router.serve_batch(warm_req())  # compiles both step classes + transfer
    # slice off the warm run's events: serve_batch reassigns rids per call,
    # so warm rid 0 would otherwise alias the timed run's rid 0 timeline
    n0 = len(router.obs.tracer.events)
    res = router.serve_batch(reqs())["stats"]
    assert res["compiled_signatures_prefill"] == 1, res
    assert res["compiled_signatures_decode"] == 1, res
    attribution = attribution_summary(list(router.obs.tracer.events[n0:]))

    # engine-lifetime cache: the SAME engine serves a shared-system-prompt
    # batch twice — call 2's prefill rides call 1's radix tree
    system = [int(t) for t in rng.integers(1, cfg.vocab_size, (sys_len,))]
    pe = ServingEngine(params, cfg, ServingConfig(
        **geo, **mono_budget, prefix_cache=PrefixCacheConfig(enabled=True),
    ))
    pe.serve_batch(warm_req())

    def sys_batch():
        return [
            Request(
                prompt=system + [int(t) for t in
                                 rng.integers(1, cfg.vocab_size, (4,))],
                max_new_tokens=chat_new,
            )
            for _ in range(3)
        ]

    cold = pe.serve_batch(sys_batch())["stats"]
    warm = pe.serve_batch(sys_batch())["stats"]
    total_prompt = 3 * (sys_len + 4)

    return {
        "itl_p50_ms": res["itl_p50_ms"],
        "itl_p95_ms": res["itl_p95_ms"],
        "itl_p50_ms_monolithic": mono["itl_p50_ms"],
        "itl_p95_ms_monolithic": mono["itl_p95_ms"],
        "ttft_p50_ms": res["ttft_p50_ms"],
        "ttft_p95_ms": res["ttft_p95_ms"],
        "ttft_p50_ms_monolithic": mono["ttft_p50_ms"],
        "ttft_p95_ms_monolithic": mono["ttft_p95_ms"],
        "decode_tokens_per_sec": res["decode_tokens_per_sec"],
        "decode_tokens_per_sec_monolithic": mono["decode_tokens_per_sec"],
        "handoffs": res["handoffs"],
        "handoff_pages_moved": res["handoff_pages_moved"],
        "transfer_chunks": res["transfer_chunks"],
        "latency_attribution": attribution,
        "engine_lifetime": {
            "cold_hit_ratio": round(
                cold["prefill_skipped_tokens"] / total_prompt, 4
            ),
            "warm_hit_ratio": round(
                warm["prefill_skipped_tokens"] / total_prompt, 4
            ),
            "warm_prefill_skipped_tokens": warm["prefill_skipped_tokens"],
            "warm_tokens_fed": warm["tokens_fed"],
            "cold_tokens_fed": cold["tokens_fed"],
        },
        "config": {
            "long": {"n": long_n, "len": long_len, "max_new": long_new},
            "chat": {"n": chat_n, "len": chat_len, "max_new": chat_new,
                     "stride": chat_stride},
            "prefill_token_budget": disagg.prefill_token_budget,
            "transfer_pages": disagg.transfer_pages,
            "system_len": sys_len,
            "monolithic_budget": mono_budget,
            "disagg_decode_budget": disagg_budget,
            **geo,
        },
    }


def _headline_serve_scale(accel: bool) -> dict:
    """Pod-scale serving: aggregate decode tokens/s + per-replica p50/p95
    ms/token for the SAME request stream at mesh {1, tp2, dp2×tp2}, plus
    router balance stats — the scaling-structure headline (the Gemma-on-
    TPU study's comparison axis). Runs each mesh in a subprocess over
    virtual CPU devices: the bench process owns the real backend with its
    own device count, and the scaling story is about collective/routing
    structure, which the CPU mesh reproduces exactly (the HLO ratchet
    pins it). A child pinned to the CPU can never give a device number."""
    import os
    import subprocess

    shapes = {
        "1chip": {"replicas": 1, "tp": 1},
        "tp2": {"replicas": 1, "tp": 2},
        "dp2xtp2": {"replicas": 2, "tp": 2},
    }
    out: dict = {"config": {"shapes": shapes, "backend": "cpu-mesh"}}
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    for name, mesh in shapes.items():
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--serve-scale-child", json.dumps(mesh)],
            capture_output=True, text=True, timeout=900, env=env,
        )
        line = next(
            (l for l in r.stdout.splitlines() if l.startswith("SERVE_SCALE:")),
            None,
        )
        if r.returncode != 0 or line is None:
            out[name] = {"error": (r.stderr or r.stdout)[-300:]}
            continue
        out[name] = json.loads(line[len("SERVE_SCALE:"):])
    ok = [n for n in shapes if "error" not in out.get(n, {})]
    if len(ok) >= 2 and "1chip" in ok:
        base = out["1chip"]["decode_tokens_per_sec"]
        out["scaling"] = {
            n: round(out[n]["decode_tokens_per_sec"] / max(base, 1e-9), 3)
            for n in ok
        }
    return out


def _headline_serve_online(accel: bool) -> dict:
    """Online serving frontend: 1024 live streaming requests through the
    asyncio serve loop (staggered admission mid-flight, one consumer per
    stream, a quarter of the trace carrying step deadlines) — wall-clock
    TTFT and inter-token-latency percentiles, shed rate, and goodput
    (deadline-respecting completions/s), the numbers an offline
    serve_batch run structurally cannot produce. Completed streams are
    re-served through the SAME engine's offline serve_batch and must
    match token-for-token (live admission churn invisible in sampled
    tokens)."""
    import jax
    import jax.numpy as jnp

    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.serving import (
        FrontendConfig, Request, ServingConfig, ServingEngine,
        split_layer_stacks,
    )
    from automodel_tpu.serving.load_test import LoadTestConfig, run_load_test

    if accel:
        cfg = TransformerConfig(
            vocab_size=32768, hidden_size=1024, intermediate_size=4096,
            num_layers=8, num_heads=16, num_kv_heads=8,
            rope_theta=500000.0, dtype=jnp.bfloat16, remat_policy="none",
            attn_impl="auto",
        )
        serve = ServingConfig(
            page_size=16, num_pages=2048, max_slots=16, pages_per_slot=64,
            token_budget=64, prefill_chunk=48,
        )
        # bf16 argmax near-ties make full-trace parity a CPU-mesh contract
        # (see the sharded-serving fp32 note); spot-check a prefix here
        lt = LoadTestConfig(
            num_requests=1024, prompt_len=(16, 96), max_new_tokens=32,
            mean_interarrival_steps=0.1, deadline_in=512,
            deadline_fraction=0.25, vocab=cfg.vocab_size, parity_check=64,
        )
    else:
        cfg = TransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2,
            dtype=jnp.float32, remat_policy="none", attn_impl="xla",
        )
        serve = ServingConfig(
            page_size=8, num_pages=96, max_slots=4, pages_per_slot=8,
            token_budget=16, prefill_chunk=8,
        )
        lt = LoadTestConfig(
            num_requests=1024, prompt_len=(3, 12), max_new_tokens=8,
            mean_interarrival_steps=0.25, deadline_in=128,
            deadline_fraction=0.25, vocab=cfg.vocab_size,
            parity_check=1024,
        )
    # split once: the engines below share the per-layer tree
    params = split_layer_stacks(
        decoder.init(cfg, jax.random.key(0)), cfg.dtype
    )
    engine = ServingEngine(params, cfg, serve)
    # warmup: compile the single step signature outside the timed window
    engine.serve_batch([Request(prompt=[1, 2, 3], max_new_tokens=2)])
    report = run_load_test(
        engine, lt, FrontendConfig(idle_sleep_s=0.0002)
    )
    fe = report["frontend"]
    assert fe["compiled_signatures"] == 1, fe

    # tracing-on rerun: identical trace through a fresh engine with the
    # observability layer enabled — yields the TTFT/ITL attribution block
    # and measures the layer's throughput cost (contract: < 3% decode
    # tokens/s, compile-once intact)
    import dataclasses as _dc

    from automodel_tpu.observability import (
        ObservabilityConfig,
        attribution_summary,
    )

    traced_engine = ServingEngine(
        params, cfg,
        _dc.replace(serve, observability=ObservabilityConfig(enabled=True)),
    )
    traced_engine.serve_batch([Request(prompt=[1, 2, 3], max_new_tokens=2)])
    n0 = len(traced_engine.obs.tracer.events)
    traced = run_load_test(
        traced_engine, lt, FrontendConfig(idle_sleep_s=0.0002)
    )
    assert traced["frontend"]["compiled_signatures"] == 1, traced["frontend"]
    attribution = attribution_summary(
        list(traced_engine.obs.tracer.events[n0:])
    )
    tracing_overhead_pct = round(
        100.0 * (1.0 - traced["tokens_per_sec"]
                 / max(report["tokens_per_sec"], 1e-9)), 2
    )
    return {
        "requests": report["requests"],
        "completed": report["completed"],
        "shed_rate": report["shed_rate"],
        "goodput_rps": report["goodput_rps"],
        "tokens_per_sec": report["tokens_per_sec"],
        "ttft_p50_ms": report["ttft_p50_ms"],
        "ttft_p95_ms": report["ttft_p95_ms"],
        "ttft_p99_ms": report["ttft_p99_ms"],
        "itl_p50_ms": report["itl_p50_ms"],
        "itl_p95_ms": report["itl_p95_ms"],
        "itl_p99_ms": report["itl_p99_ms"],
        "parity_checked": report.get("parity_checked"),
        "latency_attribution": attribution,
        "tracing_overhead_pct": tracing_overhead_pct,
        "tokens_per_sec_traced": traced["tokens_per_sec"],
        "config": {
            "requests": lt.num_requests, "prompt_len": list(lt.prompt_len),
            "max_new_tokens": lt.max_new_tokens,
            "mean_interarrival_steps": lt.mean_interarrival_steps,
            "deadline_in": lt.deadline_in,
            "deadline_fraction": lt.deadline_fraction,
            "max_slots": serve.max_slots, "token_budget": serve.token_budget,
            "hidden": cfg.hidden_size, "layers": cfg.num_layers,
        },
    }


def _headline_resilience(accel: bool) -> dict:
    """Goodput under one injected preemption: a tiny train run is
    SIGTERM'd (via the deterministic fault injector) at mid-run, emergency-
    checkpoints, and a fresh recipe auto-resumes to completion. Reports
    time-to-resume seconds (restore cost, from training.jsonl) and the
    goodput fraction (uninterrupted wall / preempted+resumed wall — the
    denominator pays the emergency save, restore, and re-jit, exactly what
    a preempted pod pays). Robustness headline: shapes stay tiny on every
    backend."""
    import json
    import os
    import tempfile

    from automodel_tpu.cli.app import resolve_recipe_class
    from automodel_tpu.config import ConfigNode

    steps, kill_at = 8, 4

    def cfg_for(run_dir, ckpt_dir, faults):
        return ConfigNode({
            "seed": 3,
            "run_dir": run_dir,
            "auto_resume": True,
            "model": {
                "hf_config": {
                    "architectures": ["LlamaForCausalLM"],
                    "vocab_size": 256, "hidden_size": 64,
                    "intermediate_size": 128, "num_hidden_layers": 2,
                    "num_attention_heads": 4, "num_key_value_heads": 2,
                },
                "dtype": "float32", "remat_policy": "none",
            },
            "distributed": {"dp_shard": -1},
            "dataset": {
                "_target_": "automodel_tpu.datasets.mock.MockDatasetConfig",
                "num_samples": 256, "seq_len": 64, "vocab_size": 256,
            },
            "dataloader": {"microbatch_size": 8, "grad_acc_steps": 1},
            "optimizer": {"name": "adamw", "lr": 1e-3, "weight_decay": 0.0},
            "lr_scheduler": {"warmup_steps": 1, "decay_steps": steps, "style": "cosine"},
            "step_scheduler": {"max_steps": steps, "ckpt_every_steps": steps, "num_epochs": 4},
            "checkpoint": {"enabled": True, "checkpoint_dir": ckpt_dir, "async_save": True},
            "resilience": {"faults": faults, "sigterm_grace_s": 60.0},
            "loss": {"chunk_size": 64},
        })

    def run(cfg):
        t0 = time.perf_counter()
        recipe = resolve_recipe_class(cfg)(cfg)
        recipe.setup()
        recipe.run_train_validation_loop()
        return time.perf_counter() - t0

    with tempfile.TemporaryDirectory(prefix="bench_resilience_") as td:
        t_base = run(cfg_for(os.path.join(td, "base"), os.path.join(td, "base_ckpt"), []))
        pre_dir, pre_ckpt = os.path.join(td, "pre"), os.path.join(td, "pre_ckpt")
        t_kill = run(cfg_for(pre_dir, pre_ckpt, [{"point": "sigterm", "step": kill_at}]))
        t_resume = run(cfg_for(pre_dir, pre_ckpt, []))
        recs = [
            json.loads(l) for l in open(os.path.join(pre_dir, "training.jsonl"))
            if l.strip()
        ]
        step_recs = [r for r in recs if "loss" in r]
        assert step_recs[-1]["step"] == steps, step_recs[-1]
        ttr = next(
            (r["time_to_resume_s"] for r in step_recs if "time_to_resume_s" in r),
            None,
        )
        emergency = next(
            (r for r in recs if r.get("event") == "emergency_checkpoint"), {}
        )
    return {
        "time_to_resume_s": ttr,
        "goodput_fraction": round(t_base / max(t_kill + t_resume, 1e-9), 3),
        "emergency_save_s": emergency.get("seconds"),
        "emergency_committed": emergency.get("committed"),
        "config": {
            "steps": steps, "preempted_at": kill_at,
            "uninterrupted_s": round(t_base, 3),
            "preempted_s": round(t_kill, 3), "resumed_s": round(t_resume, 3),
        },
    }


def _headline_kv_quant(accel: bool) -> dict:
    """Quantized serving: int8 KV pages + int8 serve-step linears against
    the fp engine on the identical stream. Headline numbers are the
    KV-bytes-per-page ratio (== resident requests per HBM pool at equal
    page count — the quant pool fits that many more pages per byte),
    sustained decode tokens/s, and greedy top-1 agreement with the fp
    engine (the tolerance contract: >= 0.99).

    Greedy agreement is only meaningful on a model with confident
    predictions: an untrained random init has top-1 margins below ANY
    quantization noise floor (even CPU thread scheduling flips its
    argmaxes), so a few seconds of training on a deterministic
    next-token mapping first gives the model real margins — the
    production claim under test is that int8 KV + int8 linears preserve
    a confident model's greedy outputs, not that they win coin flips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from automodel_tpu.loss import fused_linear_cross_entropy
    from automodel_tpu.models.llm import decoder
    from automodel_tpu.models.llm.decoder import TransformerConfig
    from automodel_tpu.serving import Request, ServingConfig, ServingEngine, split_layer_stacks
    from automodel_tpu.serving.kv_pages import pool_bytes

    if accel:
        cfg = TransformerConfig(
            vocab_size=32768, hidden_size=1024, intermediate_size=4096,
            num_layers=8, num_heads=16, num_kv_heads=8,
            rope_theta=500000.0, dtype=jnp.bfloat16, remat_policy="none",
            attn_impl="auto",
        )
        geo = dict(page_size=16, num_pages=2048, max_slots=16,
                   pages_per_slot=64, token_budget=64, prefill_chunk=48)
        lens, max_new, n_req = (128, 512, 256, 768, 384), 64, 16
        train_steps = 300
    else:
        cfg = TransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2,
            dtype=jnp.float32, remat_policy="none", attn_impl="xla",
        )
        geo = dict(page_size=8, num_pages=64, max_slots=4,
                   pages_per_slot=8, token_budget=16, prefill_chunk=8)
        lens, max_new, n_req = (12, 30, 7, 21, 16), 16, 8
        train_steps = 200
    params = decoder.init(cfg, jax.random.key(0))

    # active token range: tokens and the mapping stay inside [1, A) so the
    # tiny train budget sees every token a few times even at 32k vocab
    A = min(cfg.vocab_size, 4096)

    def f_next(tok):
        return (tok * 3 + 7) % (A - 1) + 1

    def loss_fn(p, ids, labels):
        h = decoder.forward(p, cfg, ids, return_hidden=True)
        ce, n = fused_linear_cross_entropy(
            h, p["lm_head"]["kernel"], labels, chunk_size=128
        )
        return ce / n

    tx = optax.adam(3e-3)

    @jax.jit
    def train_one(p, o, key):
        ids = jax.random.randint(key, (8, 32), 1, A)
        loss, g = jax.value_and_grad(loss_fn)(p, ids, f_next(ids))
        up, o = tx.update(g, o, p)
        return optax.apply_updates(p, up), o, loss

    opt = tx.init(params)
    key = jax.random.key(1)
    for _ in range(train_steps):
        key, k = jax.random.split(key)
        params, opt, ce = train_one(params, opt, k)
    # both engines below are built from the trained tree: split it once
    params = split_layer_stacks(params, cfg.dtype)

    rng = np.random.default_rng(0)
    prompts = [
        [int(t) for t in rng.integers(1, A, (lens[i % len(lens)],))]
        for i in range(n_req)
    ]

    def run(**quant_kw):
        engine = ServingEngine(params, cfg, ServingConfig(**geo, **quant_kw))
        # warmup compiles the single step signature outside the timed window
        engine.serve_batch([Request(prompt=[1, 2, 3], max_new_tokens=2)])
        res = engine.serve_batch([
            Request(prompt=list(p), max_new_tokens=max_new, arrival=i // 2)
            for i, p in enumerate(prompts)
        ])
        return res, pool_bytes(engine.pool)

    fp, fp_bytes = run()
    qt, qt_bytes = run(kv_cache_dtype="int8", serve_precision="int8")
    assert qt["stats"]["compiled_signatures"] == 1, qt["stats"]
    agree = sum(
        a == b
        for o_fp, o_qt in zip(fp["outputs"], qt["outputs"])
        for a, b in zip(o_fp, o_qt)
    )
    total = sum(len(o) for o in fp["outputs"])
    return {
        "pool_bytes_ratio": round(fp_bytes / max(qt_bytes, 1), 4),
        "greedy_agreement": round(agree / max(total, 1), 4),
        "tokens_per_sec": qt["stats"]["decode_tokens_per_sec"],
        "tokens_per_sec_fp": fp["stats"]["decode_tokens_per_sec"],
        "pool_bytes_fp": fp_bytes,
        "pool_bytes_int8": qt_bytes,
        "tokens_compared": total,
        "calibration_ce": round(float(ce), 4),
        "config": {
            "requests": n_req, "prompt_lens": list(lens),
            "max_new_tokens": max_new, "kv_dtype": str(jnp.dtype(cfg.dtype)),
            "train_steps": train_steps,
            "hidden": cfg.hidden_size, "layers": cfg.num_layers, **geo,
        },
    }


def _run_headline(accel: bool) -> dict:
    """The other headline metrics (the MFU number is merged in by the
    caller). A headline that raises fails the run."""
    out = {}
    for name, fn in (
        ("flash_vs_xla_attention", _headline_attention),
        ("moe_dropless_step", _headline_moe),
        ("cp_long_context_step", _headline_cp),
        ("decode", _headline_decode),
        ("prefix", _headline_prefix),
        ("spec", _headline_spec),
        ("disagg", _headline_disagg),
        ("serve_scale", _headline_serve_scale),
        ("serve_online", _headline_serve_online),
        ("serve_chaos", _headline_serve_chaos),
        ("kv_quant", _headline_kv_quant),
        ("resilience", _headline_resilience),
    ):
        out[name] = fn(accel)
    return out


if __name__ == "__main__":
    main()
