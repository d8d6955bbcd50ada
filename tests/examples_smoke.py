"""What the example-YAML tests share (tests/unit/test_examples*.py): the
example surface, which of it is a hermetic smoke, which smokes are slow or
excluded, and one smoke's run. The trainings are spread over several test
files (`smokes(group)`, one file a group) so that `--dist loadfile` can
give them to several workers; no list is written twice.
"""

import json
import pathlib

import numpy as np
import pytest

from automodel_tpu.cli.app import resolve_recipe_class
from automodel_tpu.config import ConfigNode
from automodel_tpu.config.loader import load_yaml

EXAMPLES = sorted(
    pathlib.Path(__file__).parent.parent.glob("examples/**/*.yaml")
)
assert len(EXAMPLES) >= 70, f"example surface shrank: {len(EXAMPLES)}"


def example_id(path) -> str:
    return str(path.relative_to(path.parents[2]))


def _load(path) -> ConfigNode:
    return load_yaml(str(path))


def _is_hermetic(cfg: ConfigNode) -> bool:
    ds = cfg.get("dataset")
    tgt = ds.get("_target_", "") if ds is not None else ""
    mock = "mock" in str(tgt).lower() or "bagel_mock" in str(tgt)
    run_dir = str(cfg.get("run_dir", ""))
    return mock and run_dir.startswith("/tmp")


#: hermetic by shape but not runnable on the CPU smoke host — excluded with
#: a reason, never silently (test_example_parses_and_resolves still covers
#: them)
_SMOKE_EXCLUDE = {
    # 1.1B × 2048-seq benchmark: a single CPU step takes longer than the
    # whole smoke tier; meaningful only on an accelerator
    "examples/llm_benchmark/llama_1b_bench.yaml",
}

#: compile-heaviest smokes (≥15s on the 1-core host, --durations audit) whose
#: recipes already have a dedicated tier-1 recipe test — slow tier keeps the
#: end-to-end YAML coverage without blowing the 870s smoke budget
_SLOW_SMOKES = {
    "examples/multimodal/omni_mock_smoke.yaml",      # test_omni recipe test
    "examples/multimodal/bagel_smoke.yaml",          # test_bagel recipe test
    "examples/vlm_finetune/minimax_m3_vl_smoke.yaml",  # test_minimax_m3
    "examples/multimodal/pretrain_smoke.yaml",       # test_vlm recipe tests
    "examples/llm_finetune/deepseek_v4_dsa_smoke.yaml",  # test_dsa recipe smoke
    "examples/llm_finetune/qwen3_next_smoke.yaml",   # test_hf_parity logits
    "examples/vlm_kd/llava_kd_smoke.yaml",           # test_recipe_matrix KD
    "examples/llm_finetune/mimo_v2_flash_smoke.yaml",  # test_model_tail + pin
    "examples/llm_finetune/gemma4_moe_smoke.yaml",   # test_model_tail + pin
    "examples/vlm_finetune/qwen3_vl_moe_mock_smoke.yaml",  # test_qwen3_vl
    "examples/vlm_finetune/kimi_vl_mock_smoke.yaml",  # test_kimi_vl
    "examples/diffusion/dit_flow_smoke.yaml",        # test_diffusion_pipeline
    "examples/llm_finetune/deepseek_v32_smoke.yaml",  # test_dsa recipe tests
    # same tiny-llama train as tiny_llama_mock_smoke + the resilience knobs,
    # which tier-1 already exercises end-to-end in test_resilience.py
    "examples/llm_finetune/tiny_llama_resilient_smoke.yaml",
}

_SMOKES = [
    pytest.param(
        p,
        marks=[pytest.mark.slow]
        if example_id(p) in _SLOW_SMOKES
        else [],
    )
    for p in EXAMPLES
    if _is_hermetic(_load(p))
    and example_id(p) not in _SMOKE_EXCLUDE
]



#: example directories whose smokes train something other than an LLM
#: fine-tune or serve nothing: one group beside `llm_finetune`'s two halves
_TRAIN_OTHER = {
    "llm_pretrain", "llm_kd", "llm_seq_cls", "long_context", "multimodal",
    "vlm_finetune", "vlm_generate", "vlm_kd", "diffusion", "dllm",
}


def _group(path) -> str:
    """The test file a smoke runs in. Every path has exactly one group, so
    the files partition `_SMOKES` whatever YAML is added."""
    d = path.parent.name
    if d == "llm_finetune":
        return "finetune_a_l" if path.name < "m" else "finetune_m_z"
    return "train_other" if d in _TRAIN_OTHER else "rest"


def smokes(group: str) -> list:
    return [p for p in _SMOKES if _group(p.values[0]) == group]


def run_smoke(path, tmp_path) -> None:
    """Run one hermetic example end-to-end (redirected run_dir)."""
    cfg = _load(path)
    cfg.set("run_dir", str(tmp_path))
    # keep every smoke cheap regardless of the YAML's own step budget
    if cfg.get("step_scheduler") is not None:
        cfg.set("step_scheduler.max_steps", min(
            int(cfg.get("step_scheduler.max_steps", 2)), 2
        ))
    # redirect the checkpoint dir too: a YAML's absolute /tmp path outlives
    # the test, and a stale checkpoint from an earlier (longer) run makes
    # auto_resume skip straight past the clamped step budget — the smoke
    # then "passes" zero steps or fails with no train records
    if cfg.get("checkpoint") is not None and cfg.get("checkpoint.checkpoint_dir"):
        cfg.set("checkpoint.checkpoint_dir", str(tmp_path / "ckpt"))
    r = resolve_recipe_class(cfg)(cfg)
    r.setup()
    r.run_train_validation_loop()
    out = tmp_path / "training.jsonl"
    recs = (
        [json.loads(l) for l in open(out) if l.strip()] if out.exists() else []
    )
    if recs:
        assert all(np.isfinite(x["loss"]) for x in recs)
    else:
        # eval/generate-style recipes log no train steps (the metrics logger
        # still touches training.jsonl) — they must leave their own artifact
        arts = [
            p for p in (
                "generations.jsonl", "decode_eval.jsonl", "acceptance.jsonl",
            )
            if (tmp_path / p).exists() and (tmp_path / p).stat().st_size > 0
        ]
        assert arts, "recipe produced neither train records nor an eval artifact"
