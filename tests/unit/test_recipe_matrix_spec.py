"""Feature-matrix tests, the speculative recipes' half: EAGLE-1/3 draft
training against dense and MoE targets with serve-layout export, and the
acceptance bench (the other families: test_recipe_matrix.py, whose helpers
these share).
"""

import json

import pytest

from automodel_tpu.config import ConfigNode
from tests.unit.test_recipe_matrix import MOE_HF, _finite, _records, _run

pytestmark = pytest.mark.recipe


def _eagle_cfg(tmp_path, recipe, target_hf, spec=None):
    cfg = ConfigNode({
        "recipe": recipe,
        "seed": 3,
        "run_dir": str(tmp_path),
        "target_model": {"hf_config": target_hf, "dtype": "float32"},
        "speculative": spec or {},
        "distributed": {"dp_shard": -1},
        "dataset": {
            "_target_": "automodel_tpu.datasets.mock.MockDatasetConfig",
            "num_samples": 16, "seq_len": 16,
            "vocab_size": target_hf["vocab_size"],
        },
        "dataloader": {"microbatch_size": 8, "grad_acc_steps": 1},
        "optimizer": {"name": "adamw", "lr": 1e-3},
        "lr_scheduler": {"warmup_steps": 1, "decay_steps": 10},
        "step_scheduler": {"max_steps": 3},
        "checkpoint": {
            "enabled": False, "checkpoint_dir": str(tmp_path / "ckpt"),
        },
    })
    return cfg


def test_eagle3_moe_target_and_export(tmp_path):
    """EAGLE-3 with a MoE (qwen3-moe) target: aux-hidden capture rides the
    MoE layer scan; the trained drafter exports in the SGLang layout."""
    cfg = _eagle_cfg(
        tmp_path, "llm_train_eagle3", dict(MOE_HF),
        spec={"draft_vocab_size": 64, "ttt_steps": 2, "aux_layer_ids": [0, 1]},
    )
    r = _run(cfg)
    recs = _records(tmp_path)
    _finite(recs)
    assert "accept_length" in recs[-1]
    out = r.save_consolidated_hf()
    import os

    files = os.listdir(out)
    assert "config.json" in files
    assert any(f.endswith(".safetensors") for f in files)


def test_eagle1_dense_target_and_export(tmp_path):
    """EAGLE-1 feature-regression drafter trains and exports."""
    dense_hf = {
        "architectures": ["LlamaForCausalLM"],
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2,
    }
    cfg = _eagle_cfg(
        tmp_path, "llm_train_eagle1", dense_hf,
        spec={"num_layers": 1, "feature_noise": 0.1},
    )
    r = _run(cfg)
    recs = _records(tmp_path)
    _finite(recs)
    assert "hidden_loss" in recs[-1] and "token_loss" in recs[-1]
    out = r.save_consolidated_hf()
    import os

    assert any(f.endswith(".safetensors") for f in os.listdir(out))


def test_spec_acceptance_bench_end_to_end(tmp_path):
    """Train EAGLE-1 briefly, export the drafter, run the acceptance bench
    on the export (VERDICT r4: accept-length JSONL harness)."""
    import json
    import os

    dense_hf = {
        "architectures": ["LlamaForCausalLM"],
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2,
    }
    cfg = _eagle_cfg(
        tmp_path / "train", "llm_train_eagle1", dense_hf,
        spec={"num_layers": 1, "feature_noise": 0.0},
    )
    r = _run(cfg)
    drafter_dir = r.save_consolidated_hf()

    bench_cfg = _eagle_cfg(
        tmp_path / "bench", "llm_spec_bench", dense_hf,
        spec={"num_layers": 1},
    )
    bench_cfg.set("drafter_path", str(drafter_dir))
    bench_cfg.set("bench", {"gamma": 3, "path_source": "dataset", "max_batches": 2})
    from automodel_tpu.cli.app import resolve_recipe_class

    b = resolve_recipe_class(bench_cfg)(bench_cfg)
    b.setup()
    b.run_train_validation_loop()
    recs = [
        json.loads(l)
        for l in open(os.path.join(tmp_path / "bench", "acceptance.jsonl"))
        if l.strip()
    ]
    assert recs[-1]["summary"] is True
    assert 1.0 <= recs[-1]["mean_accept_length"] <= 4.0  # 1..gamma+1
    per_batch = [r for r in recs if "batch" in r]
    assert len(per_batch) == 2
    for rec in per_batch:
        assert len(rec["step_hit_rates"]) == 3
        assert all(0.0 <= h <= 1.0 for h in rec["step_hit_rates"])


def test_spec_acceptance_generate_path(tmp_path):
    """path_source=generate: the target's greedy continuation feeds the
    estimator (and a perfect drafter would score gamma+1 on it)."""
    import json
    import os

    dense_hf = {
        "architectures": ["LlamaForCausalLM"],
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2,
    }
    cfg = _eagle_cfg(
        tmp_path, "llm_spec_bench", dense_hf, spec={"num_layers": 1},
    )
    cfg.set("bench", {
        "gamma": 2, "path_source": "generate",
        "max_new_tokens": 8, "max_batches": 1,
    })
    from automodel_tpu.cli.app import resolve_recipe_class

    b = resolve_recipe_class(cfg)(cfg)
    b.setup()
    b.run_train_validation_loop()
    recs = [
        json.loads(l)
        for l in open(os.path.join(tmp_path, "acceptance.jsonl"))
        if l.strip()
    ]
    assert recs[-1]["summary"] is True
