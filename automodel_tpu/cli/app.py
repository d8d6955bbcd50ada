"""CLI entry: ``automodel_tpu <cfg.yaml> [--key.path=value ...]``.

The analog of the reference CLI (reference: nemo_automodel/cli/app.py:95
`main`, cli/utils.py resolve_recipe_name). The recipe class resolves from,
in priority order: the ``recipe._target_`` field, a bare ``recipe:`` name
from RECIPE_ALIASES, or the default next-token-prediction trainer.

The launcher story differs from torchrun by design: a TPU pod runs ONE
process per host, each executing this same command; multi-host rendezvous
is `jax.distributed.initialize` inside the recipe (distributed/init_utils),
driven by env (GKE/XPK set it up). There is no process-spawning launcher to
re-exec through.
"""

from __future__ import annotations

import sys

from automodel_tpu.config import ConfigNode, parse_args_and_load_config
from automodel_tpu.config.loader import _resolve_target

RECIPE_ALIASES = {
    "llm_train_ft": "automodel_tpu.recipes.llm.train_ft.TrainFinetuneRecipeForNextTokenPrediction",
    "llm_finetune": "automodel_tpu.recipes.llm.train_ft.TrainFinetuneRecipeForNextTokenPrediction",
    "llm_pretrain": "automodel_tpu.recipes.llm.train_ft.TrainFinetuneRecipeForNextTokenPrediction",
    "llm_benchmark": "automodel_tpu.recipes.llm.benchmark.BenchmarkRecipe",
    "llm_kd": "automodel_tpu.recipes.llm.kd.KDRecipeForNextTokenPrediction",
    "llm_train_eagle3": "automodel_tpu.recipes.llm.train_eagle3.TrainEagle3Recipe",
    "llm_train_eagle1": "automodel_tpu.recipes.llm.train_eagle1.TrainEagle1Recipe",
    "llm_train_eagle2": "automodel_tpu.recipes.llm.train_eagle1.TrainEagle2Recipe",
    "llm_train_dflash": "automodel_tpu.recipes.llm.train_dflash.TrainDFlashRecipe",
    "llm_serve": "automodel_tpu.recipes.llm.serve.ServeRecipe",
    "llm_spec_bench": "automodel_tpu.recipes.llm.spec_bench.SpecAcceptanceBenchRecipe",
    "llm_dflash_decode_eval": "automodel_tpu.recipes.llm.spec_bench.DFlashDecodeEvalRecipe",
    "dllm_train_ft": "automodel_tpu.recipes.dllm.train_ft.DiffusionLMSFTRecipe",
    "diffusion_train": "automodel_tpu.recipes.diffusion.train.TrainDiffusionRecipe",
    "bagel_finetune": "automodel_tpu.recipes.multimodal.bagel.BagelRecipe",
    "multimodal_pretrain": "automodel_tpu.recipes.multimodal.pretrain.PretrainRecipeForMultimodal",
    "vlm_finetune": "automodel_tpu.recipes.vlm.finetune.FinetuneRecipeForVLM",
    "vlm_kd": "automodel_tpu.recipes.vlm.kd.KDRecipeForVLM",
    "vlm_generate": "automodel_tpu.recipes.vlm.generate.GenerateRecipeForVLM",
    "multimodal_finetune": "automodel_tpu.recipes.multimodal.finetune.FinetuneRecipeForOmni",
    "llm_seq_cls": "automodel_tpu.recipes.llm.train_seq_cls.TrainSeqClsRecipe",
    "retrieval_bi_encoder": "automodel_tpu.recipes.retrieval.train_bi_encoder.TrainBiEncoderRecipe",
    "retrieval_cross_encoder": "automodel_tpu.recipes.retrieval.train_cross_encoder.TrainCrossEncoderRecipe",
    "retrieval_distill_bi_encoder": "automodel_tpu.recipes.retrieval.distill_bi_encoder.DistillBiEncoderRecipe",
    "retrieval_mine_hard_negatives": "automodel_tpu.recipes.retrieval.mine_hard_negatives.MineHardNegativesRecipe",
}


def resolve_recipe_class(cfg: ConfigNode):
    node = cfg.get("recipe")
    if node is None:
        path = RECIPE_ALIASES["llm_train_ft"]
    elif isinstance(node, str):
        path = RECIPE_ALIASES.get(node, node)
    elif "_target_" in node:
        path = node.get("_target_")
    else:
        path = RECIPE_ALIASES["llm_train_ft"]
    return _resolve_target(path)


def print_capabilities() -> None:
    """`python -m automodel_tpu --capabilities` — the analog of the
    reference's capability query (reference: cli/query_capabilities.py).

    Runs on the host CPU platform: a metadata query needs no accelerator
    and must not take the chip from a process that is using it."""
    import json

    from automodel_tpu.utils.hostplatform import force_cpu_devices

    try:
        force_cpu_devices(1)
    except RuntimeError:
        pass  # a backend is already live in this process — query that one

    import jax

    from automodel_tpu import __version__
    from automodel_tpu.models.registry import MODEL_ARCH_MAPPING

    caps = {
        "version": __version__,
        "backend": "cpu (forced for query)",
        "devices": len(jax.devices()),
        "architectures": sorted(MODEL_ARCH_MAPPING),
        "recipes": sorted(RECIPE_ALIASES),
        "parallelism": [
            "dp_replicate", "dp_shard(fsdp)", "tp",
            "cp(ring load-balanced | blockdiag per-document)",
            "ep(dropless ragged-a2a)", "pp(gpipe|1f1b|interleaved|zb)",
        ],
        "features": [
            "lora_peft", "knowledge_distillation", "mtp", "fp8_int8_matmul",
            "dropless_moe", "attention_sinks", "kv_cache_generation",
            "mla_latent_cache_decode", "vlm_generation", "chunked_sparse_dsa",
            "speculative_eagle1_eagle3", "speculative_dflash_jetspec",
            "dflash_decode_eval", "acceptance_length_bench",
            "sampling_eval", "agent_tool_call_sft", "neat_packing",
            "orbax_checkpointing", "hf_safetensors_io", "golden_value_ci",
            "profiler_traces", "wandb_mlflow_trackers",
            "bagel_unified_multimodal", "flow_matching_adapters",
        ],
    }
    print(json.dumps(caps, indent=2))


def main(argv=None) -> None:
    import sys as _sys

    args = list(_sys.argv[1:] if argv is None else argv)
    if args and args[0] in ("--capabilities", "capabilities"):
        print_capabilities()
        return
    if args and args[0] == "launch":
        # `automodel_tpu launch <cfg.yaml> [--launcher.k=v] [--any.other=v]`
        # — generate (and optionally submit) a SLURM/GKE multi-host job
        # spec. Non-launcher overrides are forwarded into the job's train
        # command so the cluster run matches what was asked for.
        from automodel_tpu.launcher import launch_main

        largs = args[1:]
        cfg = parse_args_and_load_config(largs)
        import shlex

        train_overrides = " ".join(
            shlex.quote(a) for a in largs[1:]
            if not a.startswith("--launcher.") and not a.startswith("--platform.")
        )
        launch_main(largs[0], cfg.get("launcher"), train_overrides=train_overrides)
        return
    run_recipe(parse_args_and_load_config(args))


def run_recipe(cfg: ConfigNode):
    """Resolve `cfg`'s recipe, set it up and run it to the end; returns the
    finished recipe. What `main` does with a parsed config, and what a
    caller that wants to inspect the outcome (chip_smoke.py) calls."""
    # `platform: {force_cpu_devices: N}` — run the recipe on an N-device
    # virtual CPU mesh (dev boxes / CI without accelerators). Must happen
    # before the recipe's first JAX backend touch.
    n_cpu = cfg.get("platform.force_cpu_devices", None)
    if n_cpu:
        from automodel_tpu.utils.hostplatform import force_cpu_devices

        force_cpu_devices(int(n_cpu))
    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    recipe = resolve_recipe_class(cfg)(cfg)
    recipe.setup()
    recipe.run_train_validation_loop()
    return recipe


if __name__ == "__main__":
    main()
